"""Device milliseconds per engine step in the K/V write kernel: the ops
whose instruction is named after the kernel's `name=`
(`%paged_kv_write.<n> = ... custom-call(...)`), found by name and not by
shape.  A program that writes the pool by an XLA scatter has no such op
and reads nothing."""
from benchmark.metrics._common import SERVE_PROGRAM, ops_per_run_ms

KERNEL = "paged_kv_write"


def read(ctx):
    if ctx["window"].get("kind") != "closed_loop":
        return None
    ms = ops_per_run_ms(
        ctx, SERVE_PROGRAM,
        lambda text: text.lstrip("%").startswith(KERNEL))
    return ms or None
