"""Device milliseconds per engine step in the paged-attention kernel: the
ops whose instruction is named after the kernel's `name=`
(`%ragged_paged_attention.<n> = ... custom-call(...)`), found by name and
not by shape."""
from benchmark.metrics._common import SERVE_PROGRAM, ops_per_run_ms

KERNEL = "ragged_paged_attention"


def read(ctx):
    if ctx["window"].get("kind") != "closed_loop":
        return None
    ms = ops_per_run_ms(
        ctx, SERVE_PROGRAM,
        lambda text: text.lstrip("%").startswith(KERNEL))
    return ms or None
