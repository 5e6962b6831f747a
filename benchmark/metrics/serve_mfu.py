"""Whole serving step's share of the chip's peak: the least time the chip
could take for the window's engine steps (per step the larger of model
operations / bf16 peak and (weights once + live KV read + new KV written) /
HBM bandwidth, from shapes and the step's own context lengths) over the
seconds those steps took."""
from benchmark.reduce import flops


def read(ctx):
    win, cell = ctx["window"], ctx["cell"]
    if win.get("kind") != "closed_loop" or ctx["peaks"] is None:
        return None
    steps = win["steps"][win.get("steady_from", 0):]
    if not steps:
        return None
    least = 0.0
    for s in steps:
        f = flops.gpt2_step_flops(cell.config, s["tokens"], s["attended"],
                                  s["emitted"])
        b = flops.gpt2_step_bytes(cell.config, s["tokens"], s["kv_read"])
        least += flops.min_seconds(f, b, ctx["peaks"])[0]
    start = steps[0]["t0"] if win.get("steady_from", 0) else win["t0"]
    return 100.0 * least / (steps[-1]["t1"] - start)
