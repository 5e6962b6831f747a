"""Device milliseconds per engine step in the grouped expert product: the
ops whose instruction is named after the kernel's `name=`
(`%mx_moe_gmm.<n> = ... custom-call(...)`), two a layer (W1|W3, then W2).
The sort and the combine around it are XLA fusions whose instruction names
say nothing stable, so they are not in this number."""
from benchmark.metrics import _afmoe
from benchmark.metrics._common import SERVE_PROGRAM, ops_per_run_ms


def read(ctx):
    if ctx["window"].get("kind") != "closed_loop":
        return None
    ms = ops_per_run_ms(
        ctx, SERVE_PROGRAM,
        lambda text: text.lstrip("%").startswith(_afmoe.GMM))
    return ms or None
