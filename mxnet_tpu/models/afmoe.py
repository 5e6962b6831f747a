"""`afmoe` decoder (arcee-ai Trinity family): window and full attention
layers mixed, gated QK-normed GQA, sandwich RMSNorms, and a sigmoid-routed
expert layer with a shared expert.

The layer, from the published ``config.json`` keys (items marked + are the
family's convention, not a key):

- ``x0 = E[tok] * sqrt(hidden)`` (+ ``mup_enabled``).
- Attention: ``a = RMS(x)``; q (heads x head_dim), k, v (kv heads x
  head_dim) and a gate (heads x head_dim) from ONE stacked projection, no
  biases; q and k RMS-normalised per head (+); ``layer_types[l]``
  ``"sliding_attention"``: RoPE (``rope_theta``, rotate-half) and key j
  visible to query i iff ``i - sliding_window < j <= i`` (the window counts
  the query itself); ``"full_attention"``: no rotation at all (+) and every
  ``j <= i``.  ``o = (softmax(q k^T / sqrt(head_dim)) v * sigmoid(gate))
  Wo`` (+); ``x += RMS(o)`` (+ sandwich norm).
- FFN: ``b = RMS(x)``.  The first ``num_dense_layers`` layers:
  ``(silu(b W1) * b W3) W2`` at ``intermediate_size``.  The rest:
  ``s = sigmoid(b Wr)`` in float32 over all ``num_experts``; the
  ``num_experts_per_tok`` experts with the largest ``s + bias`` (+ the
  balancing buffer); weights ``s[chosen] / (sum + 1e-20) * route_scale``;
  ``m = shared(b) + sum over chosen experts HELD HERE of w_e expert_e(b)``
  at ``moe_intermediate_size``; ``x += RMS(m)``.
- ``logits = RMS(x) W_head^T`` over the vocabulary rows held here.

**A chip's share.**  ``experts_held = (first, count)`` and ``vocab_rows =
(first, count)`` say which routed experts and which rows of the embedding
and the head this chip holds (expert parallelism, a sliced vocabulary).
The router keeps its published width; what the absent experts would add is
left out and nothing stands in for them.  Token ids are counted from the
slice's first row.

`AfmoeForCausalLM.forward` is the full forward over whole sequences (dense
causal attention, no cache); the serving engine's prefill-then-decode
through the paged cache reproduces it (`serve/decode.py::transformer_step`
reads `AfmoeConfig.decode_spec()`; tests/unittest/test_afmoe.py).
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from ..gluon.block import HybridBlock
from ..gluon.parameter import Parameter
from ..ndarray.ndarray import apply_op

__all__ = ["AfmoeConfig", "AfmoeForCausalLM", "afmoe_forward"]

SLIDING, FULL = "sliding_attention", "full_attention"


class AfmoeConfig:
    def __init__(self, vocab_size=200192, hidden_size=3072, num_layers=60,
                 num_heads=48, num_kv_heads=8, head_dim=128,
                 intermediate_size=12288, moe_intermediate_size=3072,
                 num_dense_layers=6, num_experts=256, num_experts_per_tok=4,
                 num_shared_experts=1, route_scale=2.448, layer_types=None,
                 sliding_window=4096, rope_theta=10000.0, rms_norm_eps=1e-5,
                 max_position=262144, mup_enabled=True, dtype="float32",
                 experts_held=None, vocab_rows=None):
        if num_heads % num_kv_heads:
            raise ValueError(f"num_heads ({num_heads}) must be divisible "
                             f"by num_kv_heads ({num_kv_heads})")
        if head_dim % 2:
            raise ValueError(f"rope requires an even head_dim, got "
                             f"{head_dim}")
        if layer_types is None:     # three sliding, one full, in every four
            layer_types = [FULL if (i + 1) % 4 == 0 else SLIDING
                           for i in range(num_layers)]
        if len(layer_types) != num_layers or \
                set(layer_types) - {SLIDING, FULL}:
            raise ValueError(
                f"layer_types names {num_layers} layers as {SLIDING!r} or "
                f"{FULL!r}; got {list(layer_types)}")
        #: (first, count) of the routed experts / vocabulary rows held
        #: here; the whole model by default
        self.experts_held = tuple(experts_held or (0, num_experts))
        self.vocab_rows = tuple(vocab_rows or (0, vocab_size))
        f, n = self.experts_held
        if not 0 <= f < f + n <= num_experts:
            raise ValueError(f"experts_held {self.experts_held} is not a "
                             f"run of the {num_experts} experts")
        #: rows of the embedding and the head on this chip: what the
        #: engine samples over
        self.vocab_size = self.vocab_rows[1]
        self.published_vocab_size = vocab_size
        self.hidden_size = hidden_size
        self.num_layers = num_layers
        self.num_heads = num_heads
        self.num_kv_heads = num_kv_heads
        self.head_dim = head_dim
        self.intermediate_size = intermediate_size
        self.moe_intermediate_size = moe_intermediate_size
        self.num_dense_layers = num_dense_layers
        self.num_experts = num_experts
        self.num_experts_per_tok = num_experts_per_tok
        self.num_shared_experts = num_shared_experts
        self.route_scale = route_scale
        self.layer_types = list(layer_types)
        self.sliding_window = sliding_window
        self.rope_theta = rope_theta
        self.rms_norm_eps = rms_norm_eps
        self.max_position = max_position
        self.mup_enabled = mup_enabled
        self.dtype = dtype
        self.tie_embeddings = False

    def is_moe(self, layer: int) -> bool:
        return layer >= self.num_dense_layers

    def decode_spec(self):
        """What each layer is, for `serve.decode.transformer_step`."""
        from ..serve.decode import DecodeSpec, LayerSpec
        layers = []
        for i, kind in enumerate(self.layer_types):
            sliding = kind == SLIDING
            layers.append(LayerSpec(
                norm="rms_sandwich", rope=sliding,
                rope_theta=float(self.rope_theta),
                # the window counts the query itself: window - 1 earlier
                window=self.sliding_window - 1 if sliding else None,
                qk_norm=True, out_gate=True,
                ffn="moe" if self.is_moe(i) else "swiglu",
                cache_group="sliding" if sliding else "full"))
        return DecodeSpec(
            layers=tuple(layers), head_dim=self.head_dim,
            eps=self.rms_norm_eps, learned_positions=False,
            embed_scale=(math.sqrt(self.hidden_size)
                         if self.mup_enabled else 1.0),
            cast_inputs=True, n_experts=self.num_experts,
            top_k=self.num_experts_per_tok,
            experts_held=self.experts_held, route_scale=self.route_scale)


class _Norm(HybridBlock):
    def __init__(self, size):
        super().__init__()
        self.gamma = Parameter("gamma", shape=(size,), dtype="float32",
                               init="ones")


class _Linear(HybridBlock):
    def __init__(self, out, inp, dtype):
        super().__init__()
        self.weight = Parameter("weight", shape=(out, inp), dtype=dtype)


class _Experts(HybridBlock):
    """Router over all experts, the held experts stacked one array a
    matrix, and the shared expert."""

    def __init__(self, cfg: AfmoeConfig):
        super().__init__()
        e, f, dt = cfg.hidden_size, cfg.moe_intermediate_size, cfg.dtype
        n_held = cfg.experts_held[1]
        self.router = _Linear(cfg.num_experts, e, dt)
        self.router_bias = Parameter("router_bias", shape=(cfg.num_experts,),
                                     dtype="float32", init="zeros")
        self.experts_w13 = Parameter("experts_w13", shape=(n_held, e, 2 * f),
                                     dtype=dt)
        self.experts_w2 = Parameter("experts_w2", shape=(n_held, f, e),
                                    dtype=dt)
        fs = f * cfg.num_shared_experts
        self.shared_w13 = _Linear(2 * fs, e, dt)
        self.shared_w2 = _Linear(e, fs, dt)


class _DenseFFN(HybridBlock):
    def __init__(self, cfg: AfmoeConfig):
        super().__init__()
        e, i, dt = cfg.hidden_size, cfg.intermediate_size, cfg.dtype
        self.w13 = _Linear(2 * i, e, dt)
        self.w2 = _Linear(e, i, dt)


class _Layer(HybridBlock):
    def __init__(self, cfg: AfmoeConfig, index: int):
        super().__init__()
        e, d, dt = cfg.hidden_size, cfg.head_dim, cfg.dtype
        hq, hkv = cfg.num_heads * d, cfg.num_kv_heads * d
        self.attn_norm = _Norm(e)
        self.attn_qkvg = _Linear(2 * hq + 2 * hkv, e, dt)   # q | k | v | gate
        self.q_norm = _Norm(d)
        self.k_norm = _Norm(d)
        self.attn_proj = _Linear(e, hq, dt)
        self.attn_post_norm = _Norm(e)
        self.ffn_norm = _Norm(e)
        if cfg.is_moe(index):
            self.moe = _Experts(cfg)
        else:
            self.ffn = _DenseFFN(cfg)
        self.ffn_post_norm = _Norm(e)


class AfmoeForCausalLM(HybridBlock):
    """The model, as this chip holds it.  ``model(ids)`` -> logits (B, L,
    rows held) by the full forward; ``mx.serve.InferenceEngine(model)``
    serves it through the paged cache."""

    def __init__(self, cfg: AfmoeConfig):
        super().__init__()
        self.cfg = cfg
        v, e, dt = cfg.vocab_size, cfg.hidden_size, cfg.dtype
        self.embed = _Linear(v, e, dt)
        from ..gluon import nn
        self.layers = nn.HybridSequential()
        for i in range(cfg.num_layers):
            self.layers.add(_Layer(cfg, i))
        self.final_norm = _Norm(e)
        self.head = _Linear(v, e, dt)

    def decode_weights(self) -> dict:
        """The weight pytree `transformer_step` reads
        (`serve.decode.extract_decode_weights`)."""
        def w(p):
            return p.data()._data

        layers = []
        for blk in self.layers:
            L = dict(ln1_g=w(blk.attn_norm.gamma),
                     wqkv=w(blk.attn_qkvg.weight),
                     q_norm_g=w(blk.q_norm.gamma),
                     k_norm_g=w(blk.k_norm.gamma),
                     wo=w(blk.attn_proj.weight),
                     ln1_post_g=w(blk.attn_post_norm.gamma),
                     ln2_g=w(blk.ffn_norm.gamma),
                     ln2_post_g=w(blk.ffn_post_norm.gamma))
            if hasattr(blk, "moe"):
                m = blk.moe
                L.update(router=w(m.router.weight),
                         router_bias=w(m.router_bias),
                         experts_w13=w(m.experts_w13),
                         experts_w2=w(m.experts_w2),
                         shared_w13=w(m.shared_w13.weight),
                         shared_w2=w(m.shared_w2.weight))
            else:
                L.update(w13=w(blk.ffn.w13.weight), w2=w(blk.ffn.w2.weight))
            layers.append(L)
        return dict(embed=w(self.embed.weight), head=w(self.head.weight),
                    lnf_g=w(self.final_norm.gamma), layers=layers)

    def forward(self, input_ids):
        P = self.decode_weights()
        leaves, tree = jax.tree_util.tree_flatten(P)

        def fn(ids, *flat):
            return afmoe_forward(jax.tree_util.tree_unflatten(tree, flat),
                                 self.cfg, ids)
        return apply_op(fn, (input_ids, *leaves), {}, name="afmoe_forward")


def afmoe_forward(P: dict, cfg: AfmoeConfig, ids):
    """Full forward over whole sequences: ids (B, L) -> logits (B, L, rows
    held).  Dense masked attention, every position at once; the layer's
    arithmetic is the decode core's (`serve.decode`: `rms_norm`, `swiglu`,
    `moe_ffn`), the cache and the chunks are not."""
    from ..ops.attention import rope_rotate
    from ..serve.decode import _mm, moe_ffn, rms_norm, swiglu
    spec = cfg.decode_spec()
    B, L = ids.shape
    H, Hkv, D, E = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim, \
        cfg.hidden_size
    eps = cfg.rms_norm_eps
    pos = jnp.arange(L)
    x = P["embed"][ids].astype(jnp.float32) * spec.embed_scale
    for Lw, ls in zip(P["layers"], spec.layers):
        a = rms_norm(x, Lw["ln1_g"], eps)
        qkvg = _mm(a, Lw["wqkv"])
        q = qkvg[..., :H * D].reshape(B, L, H, D)
        k = qkvg[..., H * D:(H + Hkv) * D].reshape(B, L, Hkv, D)
        v = qkvg[..., (H + Hkv) * D:(H + 2 * Hkv) * D].reshape(B, L, Hkv, D)
        gate = qkvg[..., (H + 2 * Hkv) * D:]
        q = rms_norm(q, Lw["q_norm_g"], eps)
        k = rms_norm(k, Lw["k_norm_g"], eps)
        if ls.rope:
            q = rope_rotate(q.transpose(0, 2, 1, 3), pos, ls.rope_theta
                            ).transpose(0, 2, 1, 3)
            k = rope_rotate(k.transpose(0, 2, 1, 3), pos, ls.rope_theta
                            ).transpose(0, 2, 1, 3)
        rep = H // Hkv
        qg = q.reshape(B, L, Hkv, rep, D)
        s = jnp.einsum("bqgrd,bkgd->bgrqk", qg, k) / math.sqrt(D)
        see = pos[None, :] <= pos[:, None]
        if ls.window is not None:
            see &= pos[None, :] >= pos[:, None] - ls.window
        s = jnp.where(see[None, None, None], s, -1e30)
        ctx = jnp.einsum("bgrqk,bkgd->bqgrd", jax.nn.softmax(s, axis=-1), v)
        ctx = ctx.reshape(B, L, H * D) * jax.nn.sigmoid(gate)
        x = x + rms_norm(_mm(ctx, Lw["wo"]), Lw["ln1_post_g"], eps)
        b = rms_norm(x, Lw["ln2_g"], eps)
        if ls.ffn == "moe":
            m = moe_ffn(b.reshape(B * L, E), Lw, spec)[0].reshape(B, L, E)
        else:
            m = swiglu(b, Lw["w13"], Lw["w2"])
        x = x + rms_norm(m, Lw["ln2_post_g"], eps)
    return _mm(rms_norm(x, P["lnf_g"], eps), P["head"])
