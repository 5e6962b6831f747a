"""Shared incremental-decode transformer core.

ONE implementation of the cached pre-LN decoder step, used by BOTH
surfaces that decode token-by-token:

- ``GPTForCausalLM.generate`` (models/gpt.py) — dense per-request caches
  carried through a ``lax.scan``;
- the serving engine (``serve/engine.py``) — a shared paged KV pool with
  per-slot page tables, mixed prefill/decode chunks.

Before this module the decode math lived in ``GPTForCausalLM._token_step``
(single token, dense cache only) and would have been duplicated a third
time by the serving engine.  Here the transformer arithmetic (layernorms,
fused-QKV projection, RoPE, residuals, FFN, LM head) is written once over a
chunk of C tokens; what differs between callers — where the new K/V go and
how attention reads the cached context — is injected as a single
``kv_fn(layer_idx, q, k_new, v_new) -> context`` callback.  C = 1
reproduces the old per-token step bit-for-bit; C > 1 is chunked prefill
(every row's output depends only on rows at earlier positions, so chunked
and token-at-a-time prefill agree).

Weights travel as a plain dict-of-jax-arrays pytree
(:func:`extract_decode_weights`) so the whole step stays jit/scan-friendly
and the serving engine can compile one fused program over it.

Weight-only quantization (docs/quantization.md): any matmul weight in
the dict may be a `QuantizedTensor` (int8/int4 planes + per-channel
scales) instead of a dense array — :func:`quantize_decode_weights`
rewrites the pytree, and every projection routes through
`ops.pallas.quantized_matmul.matmul_nt`, which fuses the dequantize
into the matmul.  Embeddings, positions, norms, and biases stay f32 by
default (an opt-in ``include`` allowlist covers the embedding table).
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ..ops.pallas.quantized_matmul import (QuantizedTensor,  # noqa: F401
                                           gather_rows, matmul_nt,
                                           quantize_weight)
from .kv_cache import FULL

__all__ = ["extract_decode_weights", "transformer_step", "lm_logits",
           "layer_norm", "rms_norm", "quantize_decode_weights",
           "decode_weight_bytes", "QUANT_DEFAULT_TARGETS", "tp_qkv_row_perm",
           "LayerSpec", "DecodeSpec", "decode_spec", "moe_ffn"]


@dataclass(frozen=True)
class LayerSpec:
    """What ONE decoder layer is, as `transformer_step` reads it.

    ``norm``: ``"layernorm"`` (a biased LayerNorm before each sublayer,
    GPT-2) or ``"rms_sandwich"`` (an RMSNorm before AND after each
    sublayer, no biases anywhere in the layer).  Attention: ``rope``
    rotates q and k (cached keys are stored rotated); ``window`` is how
    many EARLIER keys a query sees besides itself (None: all of them);
    ``qk_norm`` RMS-normalises q and k per head before the rotation;
    ``out_gate`` multiplies the attention output by ``sigmoid`` of a
    fourth projection before the out-projection.  ``ffn``: ``"gelu"``
    (biased), ``"swiglu"`` or ``"moe"`` (`moe_ffn`).  ``cache_group``
    labels the KV pool the layer's keys live in: `kv_cache.FULL` keeps
    the whole context, any other label (``"sliding"``) is a group that
    keeps only what the layers' one window can still see
    (`serve/kv_cache.py::plan_cache_groups`)."""
    norm: str = "layernorm"
    rope: bool = False
    rope_theta: float = 10000.0
    window: Optional[int] = None
    qk_norm: bool = False
    out_gate: bool = False
    ffn: str = "gelu"
    cache_group: str = FULL


@dataclass(frozen=True)
class DecodeSpec:
    """A model as the decode core sees it: one `LayerSpec` a layer plus
    what sits around the stack.  Comes from the model's configuration
    alone (`decode_spec`): a ``GPTConfig`` gives the GPT-2 block, a
    configuration with its own ``decode_spec()`` (``AfmoeConfig``) says
    what each of its layers is."""
    layers: Tuple[LayerSpec, ...]
    head_dim: int
    eps: float
    learned_positions: bool = True
    embed_scale: float = 1.0
    #: matmul operands cast to the weights' type (bf16 x bf16 -> f32 on
    #: the MXU) instead of the weights promoted to the activations'
    cast_inputs: bool = False
    # the expert layers: router width, experts a token, (first, count)
    # of the experts this chip holds, the scale on the normalised scores
    n_experts: int = 0
    top_k: int = 0
    experts_held: Tuple[int, int] = (0, 0)
    route_scale: float = 1.0

    def cache_groups(self) -> Tuple[str, ...]:
        """The cache groups the layers' labels name, in the layers'
        order but for the whole-context one, which comes first."""
        names = dict.fromkeys(ls.cache_group for ls in self.layers)
        return tuple(sorted(names, key=lambda n: n != FULL))

    def group_layers(self, group: str) -> Tuple[int, ...]:
        return tuple(i for i, ls in enumerate(self.layers)
                     if ls.cache_group == group)

    def cache_plan(self) -> Tuple[Tuple[str, int, Optional[int]], ...]:
        """``(group, index in the group's pool, window)`` a layer: what
        `serve.kv_cache.make_paged_kv_fn` takes as its `layer_plan`."""
        seen = {}
        plan = []
        for ls in self.layers:
            plan.append((ls.cache_group, seen.get(ls.cache_group, 0),
                         ls.window))
            seen[ls.cache_group] = plan[-1][1] + 1
        return tuple(plan)


def decode_spec(cfg) -> DecodeSpec:
    """The `DecodeSpec` of a model configuration: its own
    ``decode_spec()`` where it has one, else the GPT block a
    ``GPTConfig`` describes (one window / RoPE flag for every layer, one
    pool: a windowed GPT masks by the window and keeps the whole
    context)."""
    own = getattr(cfg, "decode_spec", None)
    if own is not None:
        return own()
    rope = bool(getattr(cfg, "rope", False))
    layer = LayerSpec(rope=rope,
                      rope_theta=float(getattr(cfg, "rope_theta", 10000.0)),
                      window=getattr(cfg, "window", None))
    return DecodeSpec(layers=(layer,) * cfg.num_layers,
                      head_dim=cfg.hidden_size // cfg.num_heads,
                      eps=cfg.layer_norm_eps, learned_positions=not rope)


def extract_decode_weights(model) -> dict:
    """Pure-jax view of a GPT-style causal LM's decoder weights.

    `model` is a ``GPTForCausalLM`` (or anything structurally matching:
    ``.transformer`` with word_embed / optional position_embed / layers of
    (attn_norm, attention.attn_qkv/attn_proj, ffn_norm,
    ffn.ffn_intermediate/ffn_output) / final_norm, plus an optional
    ``.lm_head``).  Returns the dict pytree `transformer_step` consumes.

    A model carrying a prebuilt ``_decode_weights`` pytree short-circuits
    the extraction — the process-fleet worker (`serve.worker`) rebuilds
    an engine from spec-dir serialized weights without materializing the
    full ``HybridBlock`` parameter tree.
    """
    pre = getattr(model, "_decode_weights", None)
    if pre is not None:
        return pre
    own = getattr(model, "decode_weights", None)
    if own is not None:        # a model that is not the GPT block
        return own()
    t = model.transformer

    def w(p):
        return p.data()._data

    layers = []
    for blk in t.layers:
        layers.append(dict(
            ln1_g=w(blk.attn_norm.gamma), ln1_b=w(blk.attn_norm.beta),
            wqkv=w(blk.attention.attn_qkv.weight),
            bqkv=w(blk.attention.attn_qkv.bias),
            wo=w(blk.attention.attn_proj.weight),
            bo=w(blk.attention.attn_proj.bias),
            ln2_g=w(blk.ffn_norm.gamma), ln2_b=w(blk.ffn_norm.beta),
            w1=w(blk.ffn.ffn_intermediate.weight),
            b1=w(blk.ffn.ffn_intermediate.bias),
            w2=w(blk.ffn.ffn_output.weight),
            b2=w(blk.ffn.ffn_output.bias)))
    cfg = model.cfg
    head = (None if cfg.tie_embeddings else w(model.lm_head.weight))
    pos = (None if getattr(cfg, "rope", False)
           else w(t.position_embed.weight))
    return dict(embed=w(t.word_embed.weight), pos=pos,
                lnf_g=w(t.final_norm.gamma), lnf_b=w(t.final_norm.beta),
                head=head, layers=layers)


# the matmul weights quantization targets by default: every FFN /
# attention projection plus the (untied) LM head.  Embeddings stay f32
# unless allowlisted ("embed"); norms/biases are never quantized (sub-
# percent of the bytes, all of the numerics risk).
QUANT_DEFAULT_TARGETS = ("wqkv", "wo", "w1", "w2", "head")


def quantize_decode_weights(P: dict, bits: int = 8, include=(),
                            thresholds: Optional[Dict[str, float]] = None):
    """Rewrite an `extract_decode_weights` pytree to int8/int4 planes.

    Quantizes the 2-D matmul weights (`QUANT_DEFAULT_TARGETS`) with
    per-channel symmetric scales; ``include`` opts additional leaves in
    (``"embed"`` — the table is then dequantized per gathered row and
    the tied LM head runs the fused kernel).  ``thresholds`` maps
    ``"layers.<i>.<name>"`` / top-level names to calibrated activation
    amax values (a `LayerCalibrator.thresholds()` dict) attached for
    the ``MXTPU_QUANT_ACT=1`` int8-activation path.

    Returns ``(newP, info)`` — info records bits, per-leaf byte
    deltas, and the skipped module names (the artifact manifest's
    ``quant`` field).
    """
    targets = set(QUANT_DEFAULT_TARGETS) | set(include)
    thresholds = thresholds or {}
    skipped, quantized = [], []
    f32_bytes = q_bytes = 0

    def one(name, key, w):
        nonlocal f32_bytes, q_bytes
        if w is None:
            return None
        dense_ok = hasattr(w, "ndim") and w.ndim == 2
        if key not in targets or not dense_ok:
            skipped.append(name)
            return w
        qt = quantize_weight(w, bits,
                             act_amax=thresholds.get(name,
                                                     thresholds.get(key)))
        f32_bytes += int(w.size) * jnp.dtype(w.dtype).itemsize
        q_bytes += qt.nbytes()
        quantized.append(name)
        return qt

    newP = dict(P)
    for key in ("embed", "pos", "head"):
        newP[key] = one(key, key, P.get(key))
    layers = []
    for li, L in enumerate(P["layers"]):
        NL = dict(L)
        for key in ("wqkv", "wo", "w1", "w2"):
            if key in L:
                NL[key] = one(f"layers.{li}.{key}", key, L[key])
        layers.append(NL)
    newP["layers"] = layers
    info = {"bits": int(bits), "scheme": "symmetric-per-channel",
            "quantized": quantized, "skipped": sorted(set(skipped)),
            "f32_bytes": int(f32_bytes), "quantized_bytes": int(q_bytes),
            "saved_bytes": int(f32_bytes - q_bytes)}
    return newP, info


def decode_weight_bytes(P: dict) -> int:
    """Stored bytes of a decode-weight pytree (dense or quantized)."""
    total = 0
    for leaf in jax.tree_util.tree_leaves(P):
        total += int(leaf.size) * jnp.dtype(leaf.dtype).itemsize
    return total


def layer_norm(x, g, b, eps):
    m = x.mean(-1, keepdims=True)
    v = ((x - m) ** 2).mean(-1, keepdims=True)
    return (x - m) / jnp.sqrt(v + eps) * g + b


def rms_norm(x, g, eps):
    """RMSNorm over the last axis, statistics in float32."""
    x32 = x.astype(jnp.float32)
    return x32 * jax.lax.rsqrt(
        jnp.mean(x32 * x32, axis=-1, keepdims=True) + eps) * g


def _mm(x, w):
    """``x @ w.T`` with x cast to the weight's type and f32 accumulation:
    one bf16 pass over the MXU for bf16 weights, exact for f32 ones."""
    return jnp.einsum("...i,oi->...o", x.astype(w.dtype), w,
                      preferred_element_type=jnp.float32)


def swiglu(x, w13, w2):
    """``(silu(x W1) * x W3) W2`` with W1 and W3 stacked in `w13`
    (2 F, E) and `w2` (E, F)."""
    gu = _mm(x, w13)
    f = gu.shape[-1] // 2
    return _mm(jax.nn.silu(gu[..., :f]) * gu[..., f:], w2)


def moe_ffn(x, L, spec: DecodeSpec, valid=None):
    """A chip's share of a sigmoid-routed expert layer: route every row
    over ALL ``spec.n_experts`` experts, compute the part of the result
    that the experts held here give (``spec.experts_held = (first,
    count)``; their weights stacked in ``L["experts_w13"]`` (count, E,
    2 F) and ``L["experts_w2"]`` (count, F, E)), and add the shared
    expert.  What the absent experts would have added is left out: no
    code stands in for the chips that hold them.  No token is dropped:
    the pairs held here are sorted by expert into a buffer sized for the
    worst case and multiplied in one grouped product
    (`ops.pallas.moe_gmm`).

    x: (T, E) rows after the pre-FFN norm; `valid` (T,) bool masks the
    padded rows of a chunk out of the routing counts and the grouped
    product.  Returns ``(out (T, E) f32, counts (count,) int32)``: how
    many pairs each held expert got."""
    from ..ops.pallas import moe_gmm as G
    T, E = x.shape
    first, n_held = spec.experts_held
    k = spec.top_k
    with jax.named_scope("mx.serve.moe.route"):
        # float32 throughout: the scores decide WHICH experts a token
        # gets, and a score rounded to bfloat16 flips near-ties
        logits = jnp.einsum(
            "ti,oi->to", x.astype(jnp.float32),
            L["router"].astype(jnp.float32),
            precision=jax.lax.Precision.HIGHEST)
        scores = jax.nn.sigmoid(logits)
        _, chosen = jax.lax.top_k(scores + L["router_bias"], k)  # (T, k)
        picked = jnp.take_along_axis(scores, chosen, axis=-1)
        weight = picked / (picked.sum(-1, keepdims=True) + 1e-20) \
            * spec.route_scale
    with jax.named_scope("mx.serve.moe.experts"):
        local = (chosen - first).reshape(T * k)
        held = (local >= 0) & (local < n_held)
        if valid is not None:
            held &= jnp.repeat(valid, k)
        dest, counts, group_rows = G.plan_rows(
            jnp.where(held, local, n_held).astype(jnp.int32), n_held)
        R = G.padded_rows(T * k, n_held)
        # row r of the buffer holds the token of the pair laid there
        # (row T of the padded input, zeros, where no pair is)
        src = jnp.full((R,), T, jnp.int32).at[dest].set(
            jnp.repeat(jnp.arange(T, dtype=jnp.int32), k), mode="drop")
        wdt = L["experts_w13"].dtype
        xs = jnp.concatenate(
            [x.astype(wdt), jnp.zeros((1, E), wdt)])[src]
        gu = G.grouped_matmul(xs, L["experts_w13"], group_rows)
        f = gu.shape[-1] // 2
        act = (jax.nn.silu(gu[:, :f]) * gu[:, f:]).astype(wdt)
        ys = G.grouped_matmul(act, L["experts_w2"], group_rows)
        # combine: a pair's row, weighted; rows never written (past the
        # active tiles) belong to no held pair and are masked, not read
        pair = jnp.where(held[:, None],
                         ys[jnp.minimum(dest, R - 1)], 0.0)
        routed = (pair.reshape(T, k, E)
                  * weight[..., None].astype(jnp.float32)).sum(1)
    with jax.named_scope("mx.serve.moe.shared"):
        shared = swiglu(x, L["shared_w13"], L["shared_w2"])
    return shared + routed, counts


def tp_qkv_row_perm(H: int, Hkv: int, D: int, tp: int):
    """Row permutation that reorders a packed ``wqkv`` weight from
    ``[q_all | k_all | v_all]`` to ``[q_0 k_0 v_0 | q_1 k_1 v_1 | ...]``
    so a plain contiguous dim-0 'tp' shard hands shard *i* exactly its
    head-aligned ``[q_i, k_i, v_i]`` block (heads stay in original
    order within each shard, so an all-gather over the head axis after
    attention restores the exact tp=1 head order).  Applied host-side
    once at engine construction, BEFORE quantization — per-out-channel
    scales permute with their rows for free."""
    E, kvw = H * D, Hkv * D
    Hl, Hkvl = H // tp, Hkv // tp
    idx = []
    for i in range(tp):
        idx.extend(range(i * Hl * D, (i + 1) * Hl * D))
        idx.extend(range(E + i * Hkvl * D, E + (i + 1) * Hkvl * D))
        idx.extend(range(E + kvw + i * Hkvl * D,
                         E + kvw + (i + 1) * Hkvl * D))
    return idx


def transformer_step(P: dict, cfg, tok, pos,
                     kv_fn: Callable[[int, jax.Array, jax.Array,
                                      jax.Array], jax.Array],
                     tp: int = 1, tp_axis: Optional[str] = None,
                     row_valid=None, aux: Optional[dict] = None):
    """Run C cached decoder tokens per batch row through the transformer.

    P: weights from :func:`extract_decode_weights`; cfg: the model's
    configuration (static fields only are read; what each layer is comes
    from `decode_spec(cfg)`); tok: (B, C) int32 token
    ids; pos: (B, C) int32 absolute positions; kv_fn(li, q, k_new, v_new)
    receives the layer index, rotated queries (B, H, C, D) and new
    keys/values (B, Hkv, C, D), must make the new K/V visible to its
    cache, and returns the attention context (B, H, C, D).

    ``row_valid`` (B, C) bool marks the real rows of a ragged chunk: an
    expert layer leaves the padded ones out of its routing (None: all
    real).  ``aux``, where given, receives ``aux["moe_counts"]``: one
    (held experts,) int32 vector per expert layer, the pairs each held
    expert got in this step.

    ``tp > 1`` (with ``tp_axis`` naming the mesh axis — the body then
    runs inside a `shard_map` over that axis): wqkv/wo/w1/w2 arrive as
    OUTPUT-dim shards (wqkv rows pre-permuted head-aligned by
    :func:`tp_qkv_row_perm`), attention runs on the local H/tp heads,
    and each sharded matmul keeps its FULL contraction length — partial
    outputs are all-gathered, never psum-reduced.  Every f32 dot
    product therefore accumulates in exactly the tp=1 order, which is
    what keeps greedy streams bit-identical across tp (the PR 6/14
    invariant; a psum row-parallel split would reassociate the sum and
    flip near-tie argmaxes).  Only the GPT block shards.

    Returns the final-normed hidden states (B, C, E) — feed them to
    :func:`lm_logits` (callers usually slice to the rows they need
    first: one LM-head matmul per kept row, not per padded row).
    """
    spec = decode_spec(cfg)
    H, E = cfg.num_heads, cfg.hidden_size
    D = spec.head_dim
    Hkv = getattr(cfg, "num_kv_heads", None) or H
    eps = spec.eps
    B, C = tok.shape
    # local head counts (tp=1: globals); the per-shard qkv slab keeps
    # the [q | k | v] layout with local widths thanks to the row perm
    Hl, Hkvl = H // tp, Hkv // tp
    El, kvwl = Hl * D, Hkvl * D
    mm = _mm if spec.cast_inputs else matmul_nt

    def gather(x, axis):
        if tp == 1:
            return x
        return jax.lax.all_gather(x, tp_axis, axis=axis, tiled=True)

    def heads(x, n):
        return x.reshape(B, C, n, D).transpose(0, 2, 1, 3)

    moe_counts = []
    # `jax.named_scope`s (trace-time only) give the blocks stable names
    # in the compiled program's op metadata, whatever shapes they take
    with jax.named_scope("mx.serve.embed"):
        h = gather_rows(P["embed"], tok)                 # (B, C, E)
        if spec.embed_scale != 1.0:
            h = h.astype(jnp.float32) * spec.embed_scale
        if spec.learned_positions:
            h = h + P["pos"][pos]
    for li, (L, ls) in enumerate(zip(P["layers"], spec.layers)):
        sandwich = ls.norm == "rms_sandwich"
        with jax.named_scope("mx.serve.qkv"):
            if sandwich:
                a = rms_norm(h, L["ln1_g"], eps)
                qkv = mm(a, L["wqkv"])
            else:
                a = layer_norm(h, L["ln1_g"], L["ln1_b"], eps)
                qkv = mm(a, L["wqkv"]) + L["bqkv"]
            q = heads(qkv[..., :El], Hl)
            k = heads(qkv[..., El:El + kvwl], Hkvl)
            v = heads(qkv[..., El + kvwl:El + 2 * kvwl], Hkvl)
            if ls.qk_norm:
                q = rms_norm(q, L["q_norm_g"], eps)
                k = rms_norm(k, L["k_norm_g"], eps)
            if ls.rope:
                from ..ops.attention import rope_rotate
                # same rotation helper as the full forward; cached keys
                # are stored pre-rotated.  Rotation is per-head-dim,
                # identical for every head — shard-local heads rotate
                # exactly as the same heads do at tp=1.
                q = rope_rotate(q, pos[:, None, :], ls.rope_theta)
                k = rope_rotate(k, pos[:, None, :], ls.rope_theta)
            if spec.cast_inputs:
                # the kernel feeds q to the MXU as it comes: the pool's
                # type, like the keys it meets there
                q = q.astype(L["wqkv"].dtype)
        # the paged kv_fn scopes its own halves (`mx.serve.pool_write`,
        # `mx.serve.paged_attn`)
        ctx = kv_fn(li, q, k, v)                          # (B, Hl, C, D)
        with jax.named_scope("mx.serve.attn_out"):
            # all-gather the head axis (contiguous head blocks ->
            # original order), then the out-proj runs its full
            # contraction against the local OUT-dim rows of wo; gather
            # the partial out columns
            ctx = gather(ctx, 1)
            ctx = ctx.transpose(0, 2, 1, 3).reshape(B, C, H * D)
            if ls.out_gate:
                ctx = ctx.astype(jnp.float32) * jax.nn.sigmoid(
                    qkv[..., El + 2 * kvwl:])
            attn = mm(ctx, L["wo"])
            if sandwich:
                h = h + rms_norm(attn, L["ln1_post_g"], eps)
            else:
                h = h + gather(attn, -1) + L["bo"]
        with jax.named_scope("mx.serve.mlp"):
            if not sandwich:
                f = layer_norm(h, L["ln2_g"], L["ln2_b"], eps)
                inter = jax.nn.gelu(mm(f, L["w1"]) + L["b1"])
                h = h + gather(mm(gather(inter, -1), L["w2"]), -1) \
                    + L["b2"]
                continue
            f = rms_norm(h, L["ln2_g"], eps)
            if ls.ffn == "moe":
                m, counts = moe_ffn(
                    f.reshape(B * C, E), L, spec,
                    None if row_valid is None else row_valid.reshape(B * C))
                m = m.reshape(B, C, E)
                moe_counts.append(counts)
            else:
                m = swiglu(f, L["w13"], L["w2"])
            h = h + rms_norm(m, L["ln2_post_g"], eps)
    if aux is not None:
        aux["moe_counts"] = moe_counts
    with jax.named_scope("mx.serve.final_norm"):
        if "lnf_b" not in P:
            return rms_norm(h, P["lnf_g"], eps)
        return layer_norm(h, P["lnf_g"], P["lnf_b"], eps)


def lm_logits(P: dict, h, tp: int = 1, tp_axis: Optional[str] = None,
              cast_inputs: bool = False):
    """LM-head logits for hidden states `h` (..., E) -> (..., V)
    (`cast_inputs`: `DecodeSpec.cast_inputs`; over the rows of the
    vocabulary this chip holds, where the model holds a slice).

    Under tp the UNTIED head is an output(vocab)-dim shard — gather the
    logit columns; the tied path reads the replicated embedding table,
    so every shard computes identical full logits with no collective."""
    mm = _mm if cast_inputs else matmul_nt
    if P["head"] is None:
        return mm(h, P["embed"])
    out = mm(h, P["head"])
    if tp > 1:
        out = jax.lax.all_gather(out, tp_axis, axis=-1, tiled=True)
    return out


def dense_kv_fn(kcache, vcache, pos, window: Optional[int] = None):
    """Build a `kv_fn` over dense per-request caches — the `generate`
    scan path.  kcache/vcache: (n_layers, B, Hkv, T, D); `pos`: (B, C)
    absolute positions of this step's tokens (the scan passes C = 1).
    Returns (kv_fn, new_caches_accumulator): after `transformer_step`,
    ``new_caches()`` yields the updated (kc, vc) stacks for the carry.

    Writes use ``dynamic_update_slice`` at the chunk's start position —
    chunk positions are contiguous by construction (generate feeds
    consecutive tokens), which the serving engine's paged writes do NOT
    assume (it scatters per token).
    """
    from jax import lax

    new_k, new_v = [], []
    t0 = pos[0, 0]   # chunk start (identical across rows in generate)

    def kv_fn(li, q, k_new, v_new):
        from ..ops.pallas.paged_attention import _dense_attend
        # the cache holds the model dtype; activations may be wider (the
        # f32 norm parameters of a bf16 model promote them) — store like
        # the paged pool does, in the cache's dtype
        kc = lax.dynamic_update_slice_in_dim(
            kcache[li], k_new.astype(kcache.dtype), t0, axis=2)
        vc = lax.dynamic_update_slice_in_dim(
            vcache[li], v_new.astype(vcache.dtype), t0, axis=2)
        new_k.append(kc)
        new_v.append(vc)
        return _dense_attend(q, kc, vc, pos, window=window)

    def new_caches():
        return jnp.stack(new_k), jnp.stack(new_v)

    return kv_fn, new_caches
