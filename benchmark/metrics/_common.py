"""Shared arithmetic of the per-layer readers.  A reader takes the run's
context ``{"cell", "window", "trace", "peaks", "counters", "end_to_end"}``
and returns a number, or None where it finds nothing to read (the harness
then leaves the metric out of the line)."""
from __future__ import annotations

from benchmark.reduce import xplane
from benchmark.reduce.stats import median

SERVE_PROGRAM = "jit_step"
TRAIN_PROGRAM = "jit_step"


def idle_share_pct(ctx):
    if ctx["trace"] is None or not xplane.device_planes(ctx["trace"]):
        return None
    return 100.0 * xplane.busy_idle(ctx["trace"])["max_idle_share"]


def serve_runs_by_width(ctx) -> dict:
    """{width: [device seconds of each run of that program]} for the traced
    engine steps: the i-th run of the serve program on the chip belongs to
    the i-th traced engine step, whose width the driver recorded."""
    trace, win = ctx["trace"], ctx["window"]
    if trace is None or win.get("kind") != "closed_loop":
        return {}
    steps = win["steps"][:win["traced_steps"]]
    runs = xplane.module_runs(trace, SERVE_PROGRAM)
    if len(runs) != len(steps):
        return {}
    out = {}
    for r, s in zip(runs, steps):
        out.setdefault(s["width"], []).append(r[2] / 1e9)
    return out


def serve_step_ms(ctx, width: int):
    runs = serve_runs_by_width(ctx).get(width)
    return 1e3 * median(runs) if runs else None


def ops_per_run_ms(ctx, program_prefix: str, pick) -> float:
    """Device milliseconds per run of the program spent in the ops that
    `pick(name)` accepts, over the traced window."""
    trace = ctx["trace"]
    if trace is None:
        return None
    runs = xplane.module_runs(trace, program_prefix)
    if not runs:
        return None
    total = 0.0
    for _, s, d in runs:
        total += sum(e[2] for e in xplane.ops_within(trace, s, s + d)
                     if pick(e[0]))
    return total / 1e6 / len(runs)
