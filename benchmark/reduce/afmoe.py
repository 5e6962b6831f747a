"""Operations and bytes one chip's share of an `afmoe` model needs, from the
configuration's sizes and a step's own counts: never from an implementation's
name.  A count that cannot be made exactly from what a step records is taken
at a LOWER bound, so that a share of a peak built on these reads too low,
never above 100%.
"""
from __future__ import annotations

BF16, F32 = 2, 4


def _depth(cfg):
    d = cfg["depth"]
    types = d["layer_types"]
    n_sliding = sum(t == "sliding_attention" for t in types)
    return (d["num_hidden_layers"], d["num_dense_layers"], n_sliding,
            len(types) - n_sliding)


def attention_params(cfg) -> int:
    """One layer: the stacked q | k | v | gate projection and the
    out-projection."""
    e, d = cfg["hidden_size"], cfg["head_dim"]
    hq, hkv = cfg["num_attention_heads"] * d, cfg["num_key_value_heads"] * d
    return e * (2 * hq + 2 * hkv) + hq * e


def expert_params(cfg) -> int:
    """One routed expert: W1, W3, W2."""
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def norm_params(cfg, moe_layers: int) -> int:
    layers = cfg["depth"]["num_hidden_layers"]
    return layers * (4 * cfg["hidden_size"] + 2 * cfg["head_dim"]) \
        + cfg["hidden_size"] + moe_layers * cfg["num_experts"]  # + the bias


def weight_bytes(cfg, experts_touched=None) -> float:
    """Stored bytes of this chip's weights; with `experts_touched` (held
    experts, summed over the expert layers, that got a token) only those
    experts' matrices are counted: what a step has to read once."""
    layers, dense, _, _ = _depth(cfg)
    moe = layers - dense
    e = cfg["hidden_size"]
    held = cfg["experts_held"]["count"]
    touched = moe * held if experts_touched is None else experts_touched
    params = layers * attention_params(cfg) \
        + dense * 3 * e * cfg["intermediate_size"] \
        + touched * expert_params(cfg) \
        + moe * cfg["num_shared_experts"] * expert_params(cfg) \
        + moe * cfg["num_experts"] * e \
        + 2 * cfg["vocab_size"] * e
    return float(params * BF16 + norm_params(cfg, moe) * F32)


def kv_bytes_per_token_layer(cfg) -> int:
    return 2 * cfg["num_key_value_heads"] * cfg["head_dim"] * BF16


def pool_pages(cfg, group: str, page: int = 128, chunk: int = 16) -> int:
    """Pages of a cache group's pool at the engine's default size."""
    eng = cfg["engine"]
    if group == "full":
        return eng["max_slots"] * -(-eng["max_len"] // page) + 1
    walk = -(-(cfg["sliding_window"] - 1 + chunk) // page) + 1
    return eng["max_slots"] * walk + 1


def pool_bytes(cfg, group: str, page: int = 128) -> float:
    _, _, n_sliding, n_full = _depth(cfg)
    layers = n_full if group == "full" else n_sliding
    return float(pool_pages(cfg, group, page) * page * layers
                 * kv_bytes_per_token_layer(cfg))


def window_share(cfg, longest_context: int) -> float:
    """Lower bound of (keys a sliding layer's query sees) / (keys a full
    layer's sees): min(a, W) >= a W / A for every context a <= A."""
    return min(1.0, cfg["sliding_window"] / float(longest_context))


def attention_flops(cfg, attended: int, longest_context: int) -> float:
    """QK^T and PV over all layers.  `attended`: sum over the fed positions
    of the keys a causal query sees; a sliding layer counts
    `window_share` of it."""
    _, _, n_sliding, n_full = _depth(cfg)
    per_key = 4 * cfg["num_attention_heads"] * cfg["head_dim"]
    return float(per_key * attended * (
        n_full + n_sliding * window_share(cfg, longest_context)))


def kv_read_bytes(cfg, kv_read_tokens: int, longest_context: int) -> float:
    """Live K/V a step's attention has to read (full layers: the whole
    context of every active slot; sliding layers: `window_share` of it)."""
    _, _, n_sliding, n_full = _depth(cfg)
    return float(kv_bytes_per_token_layer(cfg) * kv_read_tokens * (
        n_full + n_sliding * window_share(cfg, longest_context)))


def step_flops(cfg, tokens: int, attended: int, emitted: int,
               pairs_held: float, longest_context: int) -> float:
    """One engine step: the dense matrix products of every fed token, the
    held experts' products of the pairs routed here, attention, the head
    for the rows that get a token."""
    layers, dense, _, _ = _depth(cfg)
    moe = layers - dense
    e = cfg["hidden_size"]
    per_token = layers * attention_params(cfg) \
        + dense * 3 * e * cfg["intermediate_size"] \
        + moe * (cfg["num_shared_experts"] * expert_params(cfg)
                 + cfg["num_experts"] * e)
    return float(2 * per_token * tokens + 2 * expert_params(cfg) * pairs_held
                 + 2 * e * cfg["vocab_size"] * emitted) \
        + attention_flops(cfg, attended, longest_context)


def step_bytes(cfg, tokens: int, kv_read_tokens: int, experts_touched: float,
               longest_context: int) -> float:
    """Weights touched once (dense ones whole, held experts that got a
    token), live KV under each layer's window, new KV written."""
    layers = cfg["depth"]["num_hidden_layers"]
    return weight_bytes(cfg, experts_touched) \
        + kv_read_bytes(cfg, kv_read_tokens, longest_context) \
        + float(kv_bytes_per_token_layer(cfg) * layers * tokens)


def gmm_flops(cfg, pairs_held: float) -> float:
    return float(2 * expert_params(cfg) * pairs_held)


def gmm_bytes(cfg, pairs_held: float, experts_touched: float) -> float:
    """The touched experts' matrices once, each pair's input row and its
    two outputs (bf16 in, f32 out of the two products)."""
    e, f = cfg["hidden_size"], cfg["moe_intermediate_size"]
    return float(experts_touched * expert_params(cfg) * BF16
                 + pairs_held * ((e + f) * BF16 + (2 * f + e) * F32))
