"""Operations and bytes the ALGORITHM needs, from shapes alone.

Every function takes sizes (a configuration's dict, a batch, context
lengths) and never an implementation's name: a kernel that recomputes, pads
or relayouts does not get its extra work counted, so a share of a peak built
on these can only read too low, never above 100%.
"""
from __future__ import annotations

BF16 = 2


# -- BERT pretraining (encoder + MLM head on masked positions + NSP) ---------

def bert_forward_flops_per_sequence(cfg: dict, seq_len: int,
                                    n_masked: int) -> float:
    """Multiply-adds x 2 of one forward pass over one sequence.  Padding
    positions are fed and computed (the published job pads to `seq_len`),
    embeddings are lookups and count nothing."""
    h, i = cfg["hidden_size"], cfg["intermediate_size"]
    layers, v = cfg["num_hidden_layers"], cfg["vocab_size"]
    per_pos_layer = 2 * h * 3 * h + 2 * h * h + 2 * 2 * h * i
    attn_per_pos_layer = 2 * seq_len * h + 2 * seq_len * h   # QK^T and PV
    encoder = layers * seq_len * (per_pos_layer + attn_per_pos_layer)
    mlm = n_masked * (2 * h * h + 2 * h * v)
    pooler_nsp = 2 * h * h + 2 * h * 2
    return float(encoder + mlm + pooler_nsp)


def bert_train_flops_per_position(cfg: dict, seq_len: int,
                                  n_masked: int) -> float:
    """Forward + backward (2x forward: one product for the input's gradient,
    one for the weight's), no recomputation, per position fed."""
    return 3.0 * bert_forward_flops_per_sequence(cfg, seq_len, n_masked) \
        / seq_len


# -- GPT-2 serving ------------------------------------------------------------

def gpt2_matmul_params(cfg: dict) -> int:
    """Weights that take part in a matrix product for every token (layers
    only; the tied head is counted per emitted row)."""
    h = cfg["n_embd"]
    i = cfg.get("n_inner") or 4 * h
    return cfg["n_layer"] * (3 * h * h + h * h + 2 * h * i)


def gpt2_weight_bytes(cfg: dict, itemsize: int = BF16) -> int:
    """All weights as stored for serving: token and position tables (the
    head is tied to the token table), layers with biases and norms, final
    norm."""
    h, v, p = cfg["n_embd"], cfg["vocab_size"], cfg["n_positions"]
    i = cfg.get("n_inner") or 4 * h
    per_layer = (3 * h * h + 3 * h) + (h * h + h) + (h * i + i) \
        + (i * h + h) + 4 * h
    return (v * h + p * h + cfg["n_layer"] * per_layer + 2 * h) * itemsize


def gpt2_kv_bytes_per_token(cfg: dict, itemsize: int = BF16) -> int:
    """K and V of one token over all layers."""
    return 2 * cfg["n_layer"] * cfg["n_embd"] * itemsize


def gpt2_step_flops(cfg: dict, tokens: int, attended: int,
                    emitted: int) -> float:
    """One engine step.  `tokens`: positions fed over all slots;
    `attended`: sum over those positions of the context length each one
    attends to; `emitted`: rows whose logits are needed (one per stream that
    gets a token)."""
    h = cfg["n_embd"]
    return float(2 * gpt2_matmul_params(cfg) * tokens
                 + cfg["n_layer"] * 4 * h * attended
                 + 2 * h * cfg["vocab_size"] * emitted)


def gpt2_step_bytes(cfg: dict, tokens: int, kv_read_tokens: int) -> float:
    """Weights once, the live KV of every active slot read once, the new
    tokens' KV written once."""
    kv = gpt2_kv_bytes_per_token(cfg)
    return float(gpt2_weight_bytes(cfg) + kv * kv_read_tokens + kv * tokens)


def min_seconds(flops: float, nbytes: float, peak: dict) -> tuple:
    """The least time the chip could take and which bound it is."""
    tc = flops / peak["bf16_flops_per_s"]
    tm = nbytes / peak["hbm_bytes_per_s"]
    return (tc, "compute") if tc >= tm else (tm, "memory")


# -- kernels -----------------------------------------------------------------

def attention_flops(batch: int, heads: int, q_len: int, kv_len: int,
                    head_dim: int, backward: bool = False) -> float:
    """softmax(QK^T)V: two products forward; the backward pass alone is four
    (dV, dP, dQ, dK) and counts no recomputed QK^T."""
    fwd = 2 * 2 * batch * heads * q_len * kv_len * head_dim
    return float(fwd * (2.0 if backward else 1.0))


def attention_bytes(batch: int, heads: int, q_len: int, kv_len: int,
                    head_dim: int, itemsize: int = BF16,
                    backward: bool = False) -> float:
    """Forward: q, k, v read and o written once.  The backward pass alone
    reads q, k, v, o, do and writes dq, dk, dv."""
    q = batch * heads * q_len * head_dim * itemsize
    kv = batch * heads * kv_len * head_dim * itemsize
    if backward:
        return float(3 * q + 2 * kv + q + 2 * kv)
    return float(2 * q + 2 * kv)
