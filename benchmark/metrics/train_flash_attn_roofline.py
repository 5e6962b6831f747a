"""Flash-attention kernels' share of their roofline in the training step:
the custom calls whose array results are all (batch x heads, seq, head_dim)
in the model's type or the kernel's (.., seq, 128) float32 row statistics -
forward, dQ and dK/dV - against the operations and bytes attention needs
from shapes (no recomputed QK^T, row statistics not counted as traffic)."""
from benchmark.metrics._common import TRAIN_PROGRAM
from benchmark.reduce import flops, xplane


def read(ctx):
    win, cell, trace = ctx["window"], ctx["cell"], ctx["trace"]
    if win.get("kind") != "train_job" or trace is None or not ctx["peaks"]:
        return None
    cfg, job = cell.config, cell.traffic
    b = job["global_batch"] // win["chips"]
    nh = cfg["num_attention_heads"]
    l, hd = job["seq_len"], cfg["hidden_size"] // nh
    want = (b * nh, l, hd)

    def pick(name):
        op = xplane.parse_op(name)
        arrays = [dims for dt, dims in op["results"] if dt != "f32"]
        return op["opcode"] == "custom-call" and arrays and all(
            dims == want for dims in arrays)

    runs = xplane.module_runs(trace, TRAIN_PROGRAM)
    if not runs:
        return None
    spent = 0.0
    for _, s, d in runs:
        spent += sum(e[2] for e in xplane.ops_within(trace, s, s + d)
                     if pick(e[0])) / 1e9
    if spent == 0.0:
        return None
    layers = cfg["num_hidden_layers"]
    least = 0.0
    for backward in (False, True):
        least += layers * flops.min_seconds(
            flops.attention_flops(b, nh, l, l, hd, backward),
            flops.attention_bytes(b, nh, l, l, hd, backward=backward),
            ctx["peaks"])[0]
    return 100.0 * least * len(runs) / spent
