"""Whole serving step's share of the chip's peak for an `afmoe` cell: the
least time the chip could take for the window's engine steps (per step the
larger of the fed tokens' operations / bf16 peak and (weights touched once:
dense ones whole, held experts that got a token; live KV under each layer's
window; new KV) / HBM bandwidth) over the seconds those steps took.  Experts
touched and pairs routed here come from the program's routing counts of the
traced steps (their median stands for the untraced steps, whose traffic is
the same); sliding layers' KV at its lower bound (`reduce/afmoe.py`)."""
from benchmark.metrics import _afmoe
from benchmark.reduce import afmoe, flops
from benchmark.reduce.stats import median


def read(ctx):
    win, cell = ctx["window"], ctx["cell"]
    if win.get("kind") != "closed_loop" or ctx["peaks"] is None \
            or cell.config.get("family") != "afmoe":
        return None
    tags = _afmoe.routing_of_traced_steps(ctx)
    steps = win["steps"][win.get("steady_from", 0):]
    if tags is None or not steps:
        return None
    touched = median(t["moe_experts_touched"] for t in tags)
    pairs_a_token = median(t["moe_tokens_routed"] / max(1, t["tokens_fed"])
                           for t in tags)
    longest = _afmoe.longest_context(cell)
    least = 0.0
    for s in steps:
        f = afmoe.step_flops(cell.config, s["tokens"], s["attended"],
                             s["emitted"], pairs_a_token * s["tokens"],
                             longest)
        b = afmoe.step_bytes(cell.config, s["tokens"], s["kv_read"], touched,
                             longest)
        least += flops.min_seconds(f, b, ctx["peaks"])[0]
    start = steps[0]["t0"] if win.get("steady_from", 0) else win["t0"]
    return 100.0 * least / (steps[-1]["t1"] - start)
