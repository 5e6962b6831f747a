"""Pallas fused softmax cross-entropy over a large vocabulary.

Why: the standard `log_softmax -> take_along_axis` loss materialises an
fp32 (tokens, vocab) log-probability tensor — for BERT/GPT vocab sizes
that is pure HBM traffic (round-2 ablations flagged it as the per-token
cost driver; the reference's fused analog is `SoftmaxOutput`/
`softmax_cross_entropy`, `src/operator/softmax_output.cc`).

This kernel streams the bf16/fp32 logits once, blockwise over the vocab
axis, keeping only per-row online (max, sumexp, target-logit) statistics
in VMEM — the fp32 (N, V) intermediate never exists:

    loss_i = logsumexp_v(x_iv) - x_i,label_i

Backward recomputes softmax blockwise from the saved lse and writes the
only unavoidable (N, V) tensor, the logits cotangent:

    dx_iv = (exp(x_iv - lse_i) - [v == label_i]) * g_i

Forward+backward are exercised on CPU via the Pallas interpreter
(`MXTPU_PALLAS_INTERPRET=1`) and cross-lowered for TPU in
`tests/unittest/test_tpu_lowering.py`.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .flash_attention import (LANES, _compiler_params, _interpret, _lanes)

__all__ = ["softmax_cross_entropy"]


def _fwd_kernel(x_ref, lab_ref, loss_ref, lse_ref, m_scr, l_scr, t_scr,
                *, v_total):
    bn = x_ref.shape[0]
    bv = x_ref.shape[1]
    vi = pl.program_id(1)
    n_v = pl.num_programs(1)

    @pl.when(vi == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, -jnp.inf)
        l_scr[...] = jnp.zeros_like(l_scr)
        t_scr[...] = jnp.zeros_like(t_scr)

    # ceil-grid: the last vocab block overhangs past v_total (real vocab
    # sizes — 30522, 50257 — have no large power-of-2 divisor); garbage
    # lanes are masked to -inf so they contribute exp(-inf) = 0
    cols = jax.lax.broadcasted_iota(jnp.int32, (bn, bv), 1) + vi * bv
    lane_ok = cols < v_total
    x = jnp.where(lane_ok, x_ref[...].astype(jnp.float32), -jnp.inf)
    m_prev = m_scr[...]
    m_cur = jnp.max(x, axis=1)[:, None]
    m_next = jnp.maximum(m_prev, m_cur)
    alpha = jnp.exp(m_prev - m_next)
    p = jnp.exp(x - _lanes(m_next, bv))
    l_scr[...] = l_scr[...] * alpha + jnp.sum(p, axis=1)[:, None]
    m_scr[...] = m_next
    hit = (cols == lab_ref[...][:, :1]) & lane_ok    # lab lane-replicated
    t_scr[...] = t_scr[...] + jnp.sum(
        jnp.where(hit, x, 0.0), axis=1)[:, None]

    @pl.when(vi == n_v - 1)
    def _store():
        lse = m_scr[...] + jnp.log(l_scr[...])
        lse_ref[...] = lse
        loss_ref[...] = lse - t_scr[...]


def _bwd_kernel(x_ref, lab_ref, lse_ref, g_ref, dx_ref, *, v_total):
    bn = x_ref.shape[0]
    bv = x_ref.shape[1]
    vi = pl.program_id(1)
    cols = jax.lax.broadcasted_iota(jnp.int32, (bn, bv), 1) + vi * bv
    lane_ok = cols < v_total
    x = jnp.where(lane_ok, x_ref[...].astype(jnp.float32), -jnp.inf)
    p = jnp.exp(x - _lanes(lse_ref[...], bv))       # garbage lanes -> 0
    hit = ((cols == lab_ref[...][:, :1]) & lane_ok).astype(jnp.float32)
    dx_ref[...] = ((p - hit) * _lanes(g_ref[...], bv)).astype(dx_ref.dtype)


def _cdiv(a, b):
    return -(-a // b)


def _xent_fwd(x, labels, block_n, block_v):
    n, v = x.shape
    lab = jnp.broadcast_to(labels.astype(jnp.int32)[:, None], (n, LANES))
    grid = (_cdiv(n, block_n), _cdiv(v, block_v))
    loss, lse = pl.pallas_call(
        functools.partial(_fwd_kernel, v_total=v),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_n, block_v), lambda ni, vi: (ni, vi)),
            pl.BlockSpec((block_n, LANES), lambda ni, vi: (ni, 0)),
        ],
        out_specs=[
            pl.BlockSpec((block_n, LANES), lambda ni, vi: (ni, 0)),
            pl.BlockSpec((block_n, LANES), lambda ni, vi: (ni, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n, LANES), jnp.float32),
            jax.ShapeDtypeStruct((n, LANES), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_n, LANES), jnp.float32),
            pltpu.VMEM((block_n, LANES), jnp.float32),
            pltpu.VMEM((block_n, LANES), jnp.float32),
        ],
        compiler_params=_compiler_params("parallel", "arbitrary"),
        interpret=_interpret(),
        name="mx_softmax_xent_fwd",
    )(x, lab)
    return loss[:, 0], lse[:, 0]


def _xent_bwd(x, labels, lse, g, block_n, block_v):
    n, v = x.shape
    lab = jnp.broadcast_to(labels.astype(jnp.int32)[:, None], (n, LANES))
    lse2 = jnp.broadcast_to(lse[:, None], (n, LANES))
    g2 = jnp.broadcast_to(g[:, None], (n, LANES)).astype(jnp.float32)
    grid = (_cdiv(n, block_n), _cdiv(v, block_v))
    return pl.pallas_call(
        functools.partial(_bwd_kernel, v_total=v),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_n, block_v), lambda ni, vi: (ni, vi)),
            pl.BlockSpec((block_n, LANES), lambda ni, vi: (ni, 0)),
            pl.BlockSpec((block_n, LANES), lambda ni, vi: (ni, 0)),
            pl.BlockSpec((block_n, LANES), lambda ni, vi: (ni, 0)),
        ],
        out_specs=pl.BlockSpec((block_n, block_v), lambda ni, vi: (ni, vi)),
        out_shape=jax.ShapeDtypeStruct((n, v), x.dtype),
        compiler_params=_compiler_params("parallel", "arbitrary"),
        interpret=_interpret(),
        name="mx_softmax_xent_bwd",
    )(x, lab, lse2, g2)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def _xent(x, labels, block_n, block_v):
    loss, _ = _xent_fwd(x, labels, block_n, block_v)
    return loss


def _xent_vjp_fwd(x, labels, block_n, block_v):
    loss, lse = _xent_fwd(x, labels, block_n, block_v)
    return loss, (x, labels, lse)


def _xent_vjp_bwd(block_n, block_v, res, g):
    x, labels, lse = res
    dx = _xent_bwd(x, labels, lse, g, block_n, block_v)
    import numpy as _np
    return dx, _np.zeros(labels.shape, jax.dtypes.float0)


_xent.defvjp(_xent_vjp_fwd, _xent_vjp_bwd)


def _reference(x, labels):
    logp = jax.nn.log_softmax(x.astype(jnp.float32), axis=-1)
    return -jnp.take_along_axis(
        logp, labels.astype(jnp.int32)[:, None], axis=-1)[:, 0]


def softmax_cross_entropy(logits, labels, block_n: int = None,
                          block_v: int = None):
    """Per-row sparse-label cross entropy over (N, V) logits -> (N,) loss.

    Dispatches to the streaming Pallas kernel when the shapes tile onto
    the TPU (same eligibility style as `flash_attention`); otherwise the
    XLA reference path. Accepts leading batch dims (flattened internally).
    """
    from ..attention import _use_pallas
    from .flash_attention import _env_int
    if block_n is None:
        block_n = _env_int("MXTPU_XENT_BLOCK_N", 256)
    if block_v is None:
        block_v = _env_int("MXTPU_XENT_BLOCK_V", 512)
    shape = logits.shape
    v = shape[-1]
    x = logits.reshape(-1, v)
    lab = labels.reshape(-1)
    n = x.shape[0]
    # ceil-grid + in-kernel lane masking: ANY (n, v) tiles — real vocab
    # sizes (30522, 50257) have no power-of-2 divisor. Blocks align to
    # the sublane (8) / lane (128) granules; overhang is masked.
    bn = min(block_n, _cdiv(n, 8) * 8)
    bv = min(block_v, _cdiv(v, LANES) * LANES)
    if not _use_pallas():
        return _reference(x, lab).reshape(shape[:-1])
    return _xent(x, lab, bn, bv).reshape(shape[:-1])
