"""What the `afmoe` readers share: the traced steps with the program's
routing counts (tags on the program's ``serve.step`` spans: pairs routed to
the held experts, held experts touched, busiest over mean) and the device
time of the ops named after a kernel."""
from __future__ import annotations

from benchmark.metrics import _program_spans
from benchmark.metrics._common import SERVE_PROGRAM
from benchmark.reduce import xplane

GMM = "mx_moe_gmm"
RPA = "ragged_paged_attention"
ROUTING = ("moe_tokens_routed", "moe_experts_touched",
           "moe_load_max_over_mean")


def routing_of_traced_steps(ctx):
    """[tags of each traced step], or None where the program records no
    routing counts (it has no expert layer, or is an older program)."""
    got = _program_spans.collect(ctx)
    if got is None:
        return None
    tags = [st["tags"] for st in got["steps"]]
    if not tags or not all(k in t for t in tags for k in ROUTING):
        return None
    return tags


def kernel_seconds_by_step(ctx, kernel: str):
    """Device seconds in the ops named ``%<kernel>.N`` inside each traced
    run of the serve program, or None."""
    trace = ctx["trace"]
    if trace is None:
        return None
    runs = xplane.module_runs(trace, SERVE_PROGRAM)
    if not runs:
        return None
    return [sum(e[2] for e in xplane.ops_within(trace, s, s + d)
                if e[0].lstrip("%").startswith(kernel)) / 1e9
            for _, s, d in runs]


def longest_context(cell) -> int:
    return int(cell.traffic["reference_pad_to"])
