"""Whole training step's share of the chips' bf16 peak: operations the
forward and backward passes need per position fed (from shapes, no
recomputation) x positions a second / (chips x peak)."""
from benchmark.reduce import flops


def read(ctx):
    win, cell = ctx["window"], ctx["cell"]
    if win.get("kind") != "train_job" or ctx["peaks"] is None:
        return None
    job = cell.traffic
    per_pos = flops.bert_train_flops_per_position(
        cell.config, job["seq_len"], job["n_masked"])
    rate = win["steady_steps"] * win["tokens_per_step"] / win["steady_span_s"]
    return 100.0 * per_pos * rate / (
        win["chips"] * ctx["peaks"]["bf16_flops_per_s"])
