"""Traffic kind `closed_loop_waves`: see `closed_loop.py` (waves=True)."""
from benchmark.kinds.closed_loop import run as _run


def run(ctx):
    return _run(ctx, waves=True)
