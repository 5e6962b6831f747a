"""Median host milliseconds a traced engine step spends before its launch:
the program's `serve.step.admit` (expiry sweep, admission) + `serve.step.plan`
(width, drafts, capacity, copy-on-write guard, the numpy batch) spans."""
from benchmark.metrics import _program_spans


def read(ctx):
    got = _program_spans.collect(ctx)
    return None if got is None else _program_spans.phase_ms(
        got, "admit", "plan")
