"""1 - device time of the serve program / host wall time of `engine.step`,
over the traced steps: what the scheduler and the transfers add."""
from benchmark.metrics._common import serve_runs_by_width


def read(ctx):
    by_width = serve_runs_by_width(ctx)
    if not by_width:
        return None
    win = ctx["window"]
    steps = win["steps"][:win["traced_steps"]]
    host = sum(s["t1"] - s["t0"] for s in steps)
    dev = sum(sum(v) for v in by_width.values())
    return 100.0 * (1.0 - dev / host)
