"""Over the experts held here (layer by layer), the pairs of the busiest
over the mean: the program's own routing counts, returned with each step's
tokens, median over the traced steps.  Beside it on an `info` line: held
experts touched a step and pairs routed here a step."""
import json
import sys

from benchmark.metrics import _afmoe
from benchmark.reduce.stats import median


def read(ctx):
    tags = _afmoe.routing_of_traced_steps(ctx)
    if tags is None:
        return None
    print("info routing " + json.dumps({
        "experts_touched_a_step": [t["moe_experts_touched"] for t in tags],
        "experts_held": tags[0].get("moe_experts_held"),
        "pairs_routed_here_a_step": [t["moe_tokens_routed"] for t in tags],
        "tokens_fed_a_step": [t["tokens_fed"] for t in tags],
        "kv_pages": [[t.get(k) for k in ("kv_pages_full", "kv_pages_sliding",
                                         "kv_pages_released")]
                     for t in tags]}), file=sys.stderr)
    return median(t["moe_load_max_over_mean"] for t in tags)
