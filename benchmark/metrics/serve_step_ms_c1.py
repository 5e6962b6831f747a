"""Median device time of the C = 1 (pure decode) program in the trace."""
from benchmark.metrics._common import serve_step_ms


def read(ctx):
    return serve_step_ms(ctx, 1)
