"""Plain float32 `afmoe` (arcee-ai Trinity family): the full forward pass
over whole sequences, one chip's share of the model.

From the published ``config.json`` (`model_type: afmoe`): RMSNorm before and
after each sublayer; grouped-query attention with per-head RMS-normalised q
and k and a sigmoid gate on the attention output; ``layer_types`` mixing
sliding-window layers (RoPE, window counted with the query itself) and full
layers (no rotation); a SwiGLU FFN in the leading dense layers and, after
them, a sigmoid-routed expert layer (top-k of score + bias, weights
normalised and scaled by ``route_scale``) plus a shared expert; an untied
head.  No cache, no paging, no chunks, no kernels: every position attends in
one pass.  Imports nothing of the program; parameters come as a dict of
float32 arrays under the names `benchmark/families/afmoe.py::param_spec`
lists, experts stacked one array a matrix.

The share (`cfg["experts_held"]`, `cfg["vocab_size"]` rows): the router
scores all ``num_experts``; only the held experts' outputs are added, and
nothing is added for the others.  The logits are over the held rows.

Departures from the published description, each also under `assumed` in the
configuration's file:
- QK-norm, the output gate, NoPE on full layers, the sandwich norms and the
  embedding scaled by sqrt(hidden) (`mup_enabled`) are the family's
  convention, not keys of the config;
- "depth-scaled" sandwich norm concerns the published INITIAL gains; seeded
  gains (1 + 0.02 n) replace them;
- the selection bias is a seeded parameter here (a balancing buffer there).

So that two rows padded to 12,544 tokens fit a 16 GB chip: parameters may
come in their stored type and are widened to float32 where they are used,
matrix by matrix (`f32`; the values are the same either way), attention runs
in blocks of `Q_BLOCK` queries, the dense FFN in blocks of `ROW_BLOCK` rows,
and the experts one at a time.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .lowprec import linear

HI = jax.lax.Precision.HIGHEST
Q_BLOCK = 128
ROW_BLOCK = 1568


def f32(w):
    return w.astype(jnp.float32)


def lin(x, w, precision):
    """`lowprec.linear` without a bias, the matrix widened here."""
    return linear(x, f32(w), None, precision)


def rms(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * g


def rope(x, positions, theta):
    """Rotate-half RoPE over the whole head: x (..., l, d), positions (l,)."""
    d2 = x.shape[-1] // 2
    freq = theta ** (-jnp.arange(d2, dtype=jnp.float32) / d2)
    ang = positions.astype(jnp.float32)[:, None] * freq
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :d2], x[..., d2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _blocks(n: int, size: int) -> int:
    """Largest block <= size that divides n."""
    size = min(size, n)
    while n % size:
        size -= 1
    return size


def attention(q, k, v, window):
    """q (b, l, hkv, rep, d), k/v (b, l, hkv, d) -> (b, l, hkv, rep, d);
    key j visible to query i iff j <= i and (window is None or
    i - window < j).  In blocks of queries, each against every key."""
    b, l, hkv, rep, d = q.shape
    qb = _blocks(l, Q_BLOCK)
    kpos = jnp.arange(l)

    def block(i):
        qi = jax.lax.dynamic_slice_in_dim(q, i * qb, qb, axis=1)
        qpos = i * qb + jnp.arange(qb)
        s = jnp.einsum("bqgrd,bkgd->bgrqk", qi, k, precision=HI) / d ** 0.5
        see = kpos[None, :] <= qpos[:, None]
        if window is not None:
            see &= kpos[None, :] > qpos[:, None] - window
        s = jnp.where(see[None, None, None], s, -1e30)
        return jnp.einsum("bgrqk,bkgd->bqgrd", jax.nn.softmax(s, -1), v,
                          precision=HI)

    out = jax.lax.map(block, jnp.arange(l // qb))       # (n, b, qb, ...)
    return jnp.moveaxis(out, 0, 1).reshape(b, l, hkv, rep, d)


def swiglu(x, w13, w2, precision):
    """w13 (2 f, e): W1 over W3; w2 (e, f)."""
    gu = lin(x, w13, precision)
    f = gu.shape[-1] // 2
    return lin(jax.nn.silu(gu[..., :f]) * gu[..., f:], w2, precision)


def by_rows(fn, x):
    """fn over the rows of x (n, e) in blocks of `ROW_BLOCK`."""
    n = x.shape[0]
    rb = _blocks(n, ROW_BLOCK)
    return jax.lax.map(fn, x.reshape(n // rb, rb, -1)).reshape(n, -1)


def expert_layer(x, p, pre, cfg, precision):
    """x (n, e) -> the held experts' weighted outputs + the shared
    expert.  Also (n,) the routing margin that matters here: the gap
    between the last chosen score + bias and the first not chosen, where
    one of those two experts is held here (inf where neither is: which of
    two absent experts a row goes to changes nothing on this chip)."""
    held = cfg["experts_held"]
    first, count = int(held["first"]), int(held["count"])
    k = cfg["num_experts_per_tok"]
    s = jax.nn.sigmoid(lin(x, p[pre + "router.weight"], precision))
    ranked, chosen = jax.lax.top_k(s + p[pre + "router_bias"], k + 1)
    edge = chosen[:, k - 1:] - first                 # last in, first out
    margin = jnp.where(((edge >= 0) & (edge < count)).any(-1),
                       ranked[:, k - 1] - ranked[:, k], jnp.inf)
    chosen = chosen[:, :k]
    picked = jnp.take_along_axis(s, chosen, -1)
    weight = picked / (picked.sum(-1, keepdims=True) + 1e-20) \
        * cfg["route_scale"]

    def one(acc, e):
        # w_e of every row (0 where the row did not choose expert e)
        w_e = jnp.where(chosen == first + e, weight, 0.0).sum(-1)
        # stored (in, out): `linear` takes (out, in)
        y = swiglu(x, p[pre + "experts_w13"][e].T, p[pre + "experts_w2"][e].T,
                   precision)
        return acc + w_e[:, None] * y, None

    routed, _ = jax.lax.scan(one, jnp.zeros_like(x), jnp.arange(count))
    shared = swiglu(x, p[pre + "shared_w13.weight"],
                    p[pre + "shared_w2.weight"], precision)
    return shared + routed, margin


def hidden_states(p, cfg, ids, precision="float32"):
    """ids (b, l) -> (hidden states before the final norm (b, l, e), the
    smallest routing margin of each position over the expert layers)."""
    b, l = ids.shape
    e, d = cfg["hidden_size"], cfg["head_dim"]
    h, hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    eps, depth = cfg["rms_norm_eps"], cfg["depth"]
    pos = jnp.arange(l)
    # mup_enabled: the embedding is scaled by sqrt(hidden)
    x = f32(p["embed.weight"][ids]) * (
        e ** 0.5 if cfg["mup_enabled"] else 1.0)
    margin = jnp.full((b, l), jnp.inf)
    for i, kind in enumerate(depth["layer_types"]):
        pre = f"layers.{i}."
        a = rms(x, p[pre + "attn_norm.gamma"], eps)
        qkvg = lin(a, p[pre + "attn_qkvg.weight"], precision)
        q = qkvg[..., :h * d].reshape(b, l, h, d)
        k = qkvg[..., h * d:(h + hkv) * d].reshape(b, l, hkv, d)
        v = qkvg[..., (h + hkv) * d:(h + 2 * hkv) * d].reshape(b, l, hkv, d)
        gate = qkvg[..., (h + 2 * hkv) * d:]
        q = rms(q, p[pre + "q_norm.gamma"], eps)
        k = rms(k, p[pre + "k_norm.gamma"], eps)
        sliding = kind == "sliding_attention"
        if sliding:            # full layers: no rotation at all
            q = jnp.swapaxes(rope(jnp.swapaxes(q, 1, 2), pos,
                                  cfg["rope_theta"]), 1, 2)
            k = jnp.swapaxes(rope(jnp.swapaxes(k, 1, 2), pos,
                                  cfg["rope_theta"]), 1, 2)
        ctx = attention(q.reshape(b, l, hkv, h // hkv, d), k, v,
                        cfg["sliding_window"] if sliding else None)
        ctx = ctx.reshape(b, l, h * d) * jax.nn.sigmoid(gate)
        o = lin(ctx, p[pre + "attn_proj.weight"], precision)
        x = x + rms(o, p[pre + "attn_post_norm.gamma"], eps)
        f = rms(x, p[pre + "ffn_norm.gamma"], eps).reshape(b * l, e)
        if i < depth["num_dense_layers"]:
            m = by_rows(lambda r: swiglu(r, p[pre + "ffn.w13.weight"],
                                         p[pre + "ffn.w2.weight"],
                                         precision), f)
        else:
            m, mg = expert_layer(f, p, pre + "moe.", cfg, precision)
            margin = jnp.minimum(margin, mg.reshape(b, l))
        x = x + rms(m.reshape(b, l, e), p[pre + "ffn_post_norm.gamma"], eps)
    return x, margin


def logits_and_margins_at(p, cfg, ids, positions, precision="float32"):
    """Logits (b, k, rows held) at `positions` (b, k) of each row, and
    each of those positions' smallest routing margin over the expert
    layers (`expert_layer`)."""
    hs, margin = hidden_states(p, cfg, ids, precision)
    rows = jnp.take_along_axis(hs, positions[..., None], axis=1)
    rows = rms(rows, p["final_norm.gamma"], cfg["rms_norm_eps"])
    return lin(rows, p["head.weight"], precision), \
        jnp.take_along_axis(margin, positions, axis=1)


def logits_at(p, cfg, ids, positions, precision="float32"):
    return logits_and_margins_at(p, cfg, ids, positions, precision)[0]
