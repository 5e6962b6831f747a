"""The grouped expert product's share of its roofline over the traced
steps: per step the larger of (its operations: the pairs routed to held
experts x three E x F products) / bf16 peak and (the touched experts'
matrices + the pairs' rows in and out) / HBM bandwidth, from the step's own
routing counts, over the device time of the `%mx_moe_gmm.N` ops."""
from benchmark.metrics import _afmoe
from benchmark.reduce import afmoe, flops


def read(ctx):
    if ctx["window"].get("kind") != "closed_loop" or ctx["peaks"] is None:
        return None
    tags = _afmoe.routing_of_traced_steps(ctx)
    spent = _afmoe.kernel_seconds_by_step(ctx, _afmoe.GMM)
    if tags is None or spent is None or len(spent) != len(tags) \
            or sum(spent) == 0.0:
        return None
    cfg = ctx["cell"].config
    least = sum(flops.min_seconds(
        afmoe.gmm_flops(cfg, t["moe_tokens_routed"]),
        afmoe.gmm_bytes(cfg, t["moe_tokens_routed"],
                        t["moe_experts_touched"]), ctx["peaks"])[0]
        for t in tags)
    return 100.0 * least / sum(spent)
