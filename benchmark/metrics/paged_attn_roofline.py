"""The paged-attention kernel's share of its roofline over the traced steps
of an `afmoe` cell: per step the larger of attention's operations / bf16
peak and (K/V live under each layer's window + the chunk's q in and o out) /
HBM bandwidth, from the traced steps' own context lengths (sliding layers at
their lower bound, `reduce/afmoe.py`), over the device time of the
`%ragged_paged_attention.N` ops."""
from benchmark.metrics import _afmoe
from benchmark.reduce import afmoe, flops


def read(ctx):
    win, cell = ctx["window"], ctx["cell"]
    if win.get("kind") != "closed_loop" or ctx["peaks"] is None \
            or cell.config.get("family") != "afmoe":
        return None
    spent = _afmoe.kernel_seconds_by_step(ctx, _afmoe.RPA)
    steps = win["steps"][:win["traced_steps"]]
    if spent is None or len(spent) != len(steps) or sum(spent) == 0.0:
        return None
    cfg, longest = cell.config, _afmoe.longest_context(cell)
    qo = 2 * cfg["num_attention_heads"] * cfg["head_dim"] * afmoe.BF16 \
        * cfg["depth"]["num_hidden_layers"]
    least = sum(flops.min_seconds(
        afmoe.attention_flops(cfg, s["attended"], longest),
        afmoe.kv_read_bytes(cfg, s["kv_read"], longest) + qo * s["tokens"],
        ctx["peaks"])[0] for s in steps)
    return 100.0 * least / sum(spent)
