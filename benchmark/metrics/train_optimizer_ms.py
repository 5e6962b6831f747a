"""Device milliseconds per training step in the ops over the packed
parameter tree: every op one of whose results holds between 0.9 and 1.01
times as many elements as all parameters together (the fused-optimizer
kernel over the packed 16-bit leaves, padded to its grid, and the
concatenate / pad around it; activations are smaller, the logits larger)."""
from benchmark.metrics._common import TRAIN_PROGRAM, ops_per_run_ms
from benchmark.reduce import xplane


def read(ctx):
    win, cell = ctx["window"], ctx["cell"]
    if win.get("kind") != "train_job":
        return None
    total = sum(xplane.elements(shape)
                for _, shape, _, _ in cell.family().param_spec(cell.config))

    def pick(name):
        op = xplane.parse_op(name)
        return any(0.9 * total <= xplane.elements(dims) <= 1.01 * total
                   for _, dims in op["results"])
    return ops_per_run_ms(ctx, TRAIN_PROGRAM, pick)
