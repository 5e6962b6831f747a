"""Device milliseconds per engine step spent in `copy` ops whose result has
the K or V pool's type and element count: whole-pool relayouts around the
paged-attention call, whatever shape XLA gave them."""
from benchmark.metrics._common import SERVE_PROGRAM, ops_per_run_ms
from benchmark.reduce import xplane

_HLO_DTYPE = {"bfloat16": "bf16", "float32": "f32", "float16": "f16",
              "int8": "s8"}


def read(ctx):
    win = ctx["window"]
    if win.get("kind") != "closed_loop":
        return None
    want = (_HLO_DTYPE.get(win["pool_dtype"], win["pool_dtype"]),
            win["pool_elements"])

    def pick(name):
        op = xplane.parse_op(name)
        return op["opcode"] == "copy" and any(
            (d, xplane.elements(dims)) == want for d, dims in op["results"])
    return ops_per_run_ms(ctx, SERVE_PROGRAM, pick)
