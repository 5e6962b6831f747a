"""Fused dequant-matmul for int8/int4 weight-only quantization.

The serving capacity lever (ROADMAP item 2): weights ship as int8 (or
int4, packed two-per-byte in int8 planes) with ONE symmetric scale per
output channel, and the matmul dequantizes blockwise in-register —
the weight tile is read from HBM at 1/4 (1/8) of its f32 width and
never materialized dense.  The roofline consequence is the whole
point: for the decode-step matmuls (batch rows ≪ weight rows) the
kernel is weight-bandwidth-bound, so bytes-moved drops ~4x/8x and the
achievable tokens/s rises with it.

Layout: a quantized weight stands in for a dense ``(out, in)`` matrix
(the `Dense`/`attn_qkv` convention — forward is ``x @ w.T``):

- ``int8``: ``q`` is ``(out, in)`` int8, ``scale`` is ``(out,)`` f32,
  per-channel symmetric (``w ≈ q * scale[:, None]``).
- ``int4``: ``q`` is ``(out, ceil(in/2))`` int8; byte ``j`` packs value
  ``2j`` in its low nibble and ``2j+1`` in its high nibble (two's
  complement, full ``[-8, 7]`` range round-trips; the quantizer itself
  stays symmetric in ``[-7, 7]``).  Odd ``in`` pads with a zero value.

Dispatch follows the package policy (`MXTPU_PALLAS`): Pallas kernel on
TPU / forced-kernel mode, jnp reference everywhere else.  The
reference (`quantized_matmul_reference`) is dequantize-then-matmul —
the CPU tier-1 path, the interpret-mode parity oracle, AND the
baseline `bench.py --ops` compares the fused kernel against.

``MXTPU_QUANT_ACT=1`` additionally quantizes the *activations* to int8
(per-call symmetric, calibrated threshold when the weight carries one
— `contrib.quantization.LayerCalibrator`) and contracts int8 x int8 →
int32 on the MXU's native 8-bit path, dequantizing in the epilogue.

Backward (`custom_vjp`): weights are frozen integers — only ``dx``
flows, computed against the dequantized weight in jnp (a plain matmul
XLA handles well).  TODO(tpu): measure the kernel on the chip and fit
the autotune grid (ROADMAP A2/A6).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from ...base import MXNetError, getenv_bool
from . import autotune, interpret_mode, kernel_active, note_fused_launch

__all__ = ["QuantizedTensor", "quantize_weight", "dequantize_weight",
           "pack_int4", "unpack_int4", "quantized_matmul",
           "quantized_matmul_reference", "int8_act_matmul",
           "act_quant_enabled", "kernel_eligible", "matmul_nt",
           "weight_nbytes"]

_LANES = 128


def act_quant_enabled() -> bool:
    """``MXTPU_QUANT_ACT=1``: int8 activations for quantized matmuls.
    Read at trace time (like ``MXTPU_REMAT_POLICY``) — part of the
    compiled program's identity, recorded in serve export configs."""
    return getenv_bool("MXTPU_QUANT_ACT", False)


# ---------------------------------------------------------------------------
# int4 packing
# ---------------------------------------------------------------------------

def pack_int4(q):
    """Pack int4 values (int8-held, each in [-8, 7]) two-per-byte along
    the last axis: byte ``j`` = value ``2j`` (low nibble) | value
    ``2j+1`` (high nibble).  Odd trailing dims pad with a zero value;
    callers record the logical length (`QuantizedTensor.in_features`)."""
    q = jnp.asarray(q, jnp.int8)
    k = q.shape[-1]
    if k % 2:
        pad = [(0, 0)] * (q.ndim - 1) + [(0, 1)]
        q = jnp.pad(q, pad)
    lo = q[..., 0::2]
    hi = q[..., 1::2]
    # two's-complement nibbles: mask the low, shift the high; int8 '<<'
    # keeps the byte width
    return ((lo & 0x0F) | jnp.left_shift(hi, 4)).astype(jnp.int8)


def unpack_int4(packed, k: int):
    """Inverse of :func:`pack_int4` -> int8 values in [-8, 7], sliced
    back to the logical last-dim length `k`."""
    b = jnp.asarray(packed, jnp.int8)
    # arithmetic shifts on int8 sign-extend: (b << 4) >> 4 recovers the
    # signed low nibble, b >> 4 the signed high nibble
    lo = jnp.right_shift(jnp.left_shift(b, 4), 4)
    hi = jnp.right_shift(b, 4)
    out = jnp.stack([lo, hi], axis=-1).reshape(
        b.shape[:-1] + (2 * b.shape[-1],))
    return out[..., :k]


# ---------------------------------------------------------------------------
# QuantizedTensor
# ---------------------------------------------------------------------------

@jax.tree_util.register_pytree_node_class
class QuantizedTensor:
    """A per-channel symmetrically quantized ``(out, in)`` weight.

    A jax pytree node — rides through jit/export/avals like any array
    pair; ``bits``/``in_features``/``act_amax`` are static aux data, so
    a program traced for int8 can never silently run int4 planes.
    ``act_amax`` is an optional calibrated activation threshold (float)
    the int8-activation path uses instead of a dynamic per-call amax.
    """

    def __init__(self, q, scale, bits: int, in_features: int,
                 act_amax: Optional[float] = None):
        self.q = q              # int8 (out, in) or packed (out, ceil(in/2))
        self.scale = scale      # f32 (out,)
        self.bits = int(bits)
        self.in_features = int(in_features)
        self.act_amax = act_amax

    # -- pytree ----------------------------------------------------------
    def tree_flatten(self):
        return (self.q, self.scale), (self.bits, self.in_features,
                                      self.act_amax)

    @classmethod
    def tree_unflatten(cls, aux, children):
        q, scale = children
        return cls(q, scale, aux[0], aux[1], act_amax=aux[2])

    # -- metadata --------------------------------------------------------
    @property
    def out_features(self) -> int:
        return int(self.q.shape[0])

    @property
    def shape(self):
        """Logical (dense) shape — what the f32 weight had."""
        return (self.out_features, self.in_features)

    def nbytes(self) -> int:
        return weight_nbytes(self)

    def __repr__(self):
        return (f"QuantizedTensor(int{self.bits}, {self.shape}, "
                f"planes {tuple(self.q.shape)})")


# jax.export serializes the in/out pytrees of a captured program:
# QuantizedTensor nodes appear in serve-step calling conventions, so the
# aux data (bits, in_features, act_amax) rides the artifact as JSON
def _serialize_aux(aux) -> bytes:
    import json
    return json.dumps(list(aux)).encode()


def _deserialize_aux(data: bytes):
    import json
    bits, in_features, act_amax = json.loads(bytes(data).decode())
    return (int(bits), int(in_features),
            None if act_amax is None else float(act_amax))


jax.export.register_pytree_node_serialization(
    QuantizedTensor,
    serialized_name="mxnet_tpu.QuantizedTensor",
    serialize_auxdata=_serialize_aux,
    deserialize_auxdata=_deserialize_aux)


def weight_nbytes(w) -> int:
    """Stored bytes of a weight leaf (quantized planes + scales, or the
    dense array)."""
    if isinstance(w, QuantizedTensor):
        return (int(w.q.size) * w.q.dtype.itemsize
                + int(w.scale.size) * w.scale.dtype.itemsize)
    return int(w.size) * jnp.dtype(w.dtype).itemsize


def quantize_weight(w, bits: int = 8,
                    act_amax: Optional[float] = None) -> QuantizedTensor:
    """Per-channel symmetric quantization of a dense ``(out, in)``
    weight.  ``scale[n] = amax(w[n, :]) / qmax`` with qmax 127 (int8)
    or 7 (int4); an all-zero channel gets scale 0 and dequantizes to
    exact zeros.  Deterministic (round-half-away via jnp.round), so two
    processes quantizing the same f32 weights agree bit-for-bit."""
    if bits not in (4, 8):
        raise MXNetError(f"quantize_weight supports bits in (4, 8), "
                         f"got {bits}")
    w = jnp.asarray(w)
    if w.ndim != 2:
        raise MXNetError(f"quantize_weight expects a 2-D (out, in) "
                         f"weight, got shape {tuple(w.shape)}")
    qmax = 127.0 if bits == 8 else 7.0
    wf = w.astype(jnp.float32)
    amax = jnp.max(jnp.abs(wf), axis=1)                      # (out,)
    scale = amax / qmax
    inv = jnp.where(scale > 0.0, 1.0 / jnp.maximum(scale, 1e-30), 0.0)
    q = jnp.clip(jnp.round(wf * inv[:, None]), -qmax, qmax).astype(jnp.int8)
    if bits == 4:
        q = pack_int4(q)
    return QuantizedTensor(q, scale, bits, int(w.shape[1]),
                           act_amax=act_amax)


def dequantize_weight(qt: QuantizedTensor, dtype=jnp.float32):
    """Dense ``(out, in)`` reconstruction — the oracle's weight and the
    backward pass's operand."""
    q = qt.q
    if qt.bits == 4:
        q = unpack_int4(q, qt.in_features)
    return (q.astype(jnp.float32) * qt.scale[:, None]).astype(dtype)


# ---------------------------------------------------------------------------
# jnp reference (tier-1 path + interpret parity oracle + bench baseline)
# ---------------------------------------------------------------------------

def quantized_matmul_reference(x, qt: QuantizedTensor):
    """Dequantize-then-matmul: ``x @ deq(qt).T``.  This is exactly the
    unfused formulation the Pallas kernel must beat on weight bytes —
    it materializes the dense f32 weight."""
    w = dequantize_weight(qt, jnp.float32)
    return (x.astype(jnp.float32) @ w.T).astype(x.dtype)


def int8_act_matmul(x, qt: QuantizedTensor, act_amax=None):
    """int8 x int8 -> int32 contraction with an f32 dequant epilogue
    (the MXU-native 8-bit path; `contrib.quantization` parity widened
    to per-channel weight scales).  ``act_amax``: calibrated symmetric
    activation threshold; None -> dynamic per-call amax."""
    xf = x.astype(jnp.float32)
    if act_amax is None:
        act_amax = qt.act_amax
    if act_amax is None:
        amax = jnp.max(jnp.abs(xf))
    else:
        amax = jnp.asarray(act_amax, jnp.float32)
    x_scale = amax / 127.0
    inv = jnp.where(x_scale > 0.0,
                    1.0 / jnp.maximum(x_scale, 1e-30), 0.0)
    xq = jnp.clip(jnp.round(xf * inv), -127, 127).astype(jnp.int8)
    q = qt.q
    if qt.bits == 4:
        q = unpack_int4(q, qt.in_features)
    acc = jax.lax.dot_general(
        xq, q, (((xf.ndim - 1,), (1,)), ((), ())),
        preferred_element_type=jnp.int32)
    out = acc.astype(jnp.float32) * x_scale * qt.scale
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# Pallas kernel
# ---------------------------------------------------------------------------

def _qmm_kernel(bits: int):
    """Blockwise fused dequant-matmul over a (bm, bkx) x tile and a
    (bn, bk) weight tile (bkx = bk values; for int4 the weight tile is
    bk PACKED bytes = 2*bk values).  The f32 accumulator lives in VMEM
    scratch across the arbitrary k dimension; the per-channel scale is
    applied once in the epilogue — the dense f32 weight never exists."""
    from jax.experimental import pallas as pl

    def kernel(x_ref, q_ref, s_ref, o_ref, acc_ref):
        kk = pl.program_id(2)

        @pl.when(kk == 0)
        def _init():
            acc_ref[...] = jnp.zeros_like(acc_ref)

        w = q_ref[...]                                  # (bn, bk[packed])
        if bits == 4:
            lo = jnp.right_shift(jnp.left_shift(w, 4), 4)
            hi = jnp.right_shift(w, 4)
            w = jnp.stack([lo, hi], axis=-1).reshape(
                w.shape[0], 2 * w.shape[1])
        x = x_ref[...].astype(jnp.float32)              # (bm, bkx)
        acc_ref[...] += jax.lax.dot_general(
            x, w.astype(jnp.float32), (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)

        @pl.when(kk == pl.num_programs(2) - 1)
        def _epilogue():
            o_ref[...] = (acc_ref[...]
                          * s_ref[...].astype(jnp.float32)
                          ).astype(o_ref.dtype)

    return kernel


def _default_blocks(m: int, n: int, k: int, bits: int):
    cfg = autotune.cached_config("quantized_matmul", (m, n, k),
                                 f"int{bits}")
    if cfg is not None:
        return cfg.block_m, cfg.block_n, cfg.block_k
    return 128, 128, 512


def _qmm_pallas(x2, q, scale, bits: int, k: int, blocks=None):
    """Launch the kernel over 2-D operands: x2 (M, K), q (N, Kp) int8
    planes, scale (N,).  Pads every dim to its block multiple (padded
    weight rows carry scale 0, padded k columns are zero on both
    sides), slices the (M, N) result back."""
    from jax.experimental import pallas as pl

    M, K = x2.shape
    if K != k:
        raise MXNetError(
            f"_qmm_pallas: x2 width {K} != logical in_features {k} "
            "(int4 callers must pass the UNPACKED width)")
    N = q.shape[0]
    if bits == 4:
        # block over PACKED bytes; the x tile spans 2x the values
        kp = q.shape[1]
        vals_per_byte = 2
    else:
        kp = q.shape[1]
        vals_per_byte = 1
    bm, bn, bk = blocks or _default_blocks(M, N, K, bits)
    bm = max(8, min(bm, 1024))
    bn = max(_LANES, min(bn, 4096))
    bk = max(_LANES, min(bk, 4096))
    bkx = bk * vals_per_byte            # x-tile width in values

    mp = -(-M // bm) * bm
    np_ = -(-N // bn) * bn
    kpp = -(-kp // bk) * bk             # padded packed-k
    kxp = kpp * vals_per_byte           # padded value-k for x

    xpad = jnp.pad(x2, ((0, mp - M), (0, kxp - K)))
    qpad = jnp.pad(q, ((0, np_ - N), (0, kpp - kp)))
    spad = jnp.pad(scale, (0, np_ - N)).reshape(1, np_)

    grid = (mp // bm, np_ // bn, kpp // bk)
    out = pl.pallas_call(
        _qmm_kernel(bits),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bkx), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bn, bk), lambda i, j, kk: (j, kk)),
            pl.BlockSpec((1, bn), lambda i, j, kk: (0, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((mp, np_), x2.dtype),
        scratch_shapes=[_vmem((bm, bn), jnp.float32)],
        compiler_params=_compiler_params(),
        interpret=interpret_mode(),
        name="mx_quant_matmul",
    )(xpad, qpad, spad)
    return out[:M, :N]


def _vmem(shape, dtype):
    from jax.experimental.pallas import tpu as pltpu
    return pltpu.VMEM(shape, dtype)


def _compiler_params():
    from . import tpu_compiler_params
    return tpu_compiler_params("parallel", "parallel", "arbitrary")


# ---------------------------------------------------------------------------
# public dispatch (+ custom_vjp: dx only, weights are frozen ints)
# ---------------------------------------------------------------------------

def kernel_eligible(x) -> bool:
    """Can (and should) this call take the Pallas path right now?"""
    if not kernel_active():
        return False
    return jnp.issubdtype(x.dtype, jnp.floating) and \
        jnp.dtype(x.dtype).itemsize in (2, 4)


def quantized_matmul(x, qt: QuantizedTensor, act_amax=None,
                     use_kernel: Optional[bool] = None,
                     act_quant: Optional[bool] = None):
    """``x @ dequantize(qt).T`` with the dequant fused into the matmul.

    x: (..., in_features) float; returns (..., out_features) in x's
    dtype.  Differentiable in x (the weight is a frozen integer plane —
    its cotangent is structurally zero, which is what `custom_vjp`'s
    closure capture encodes).  ``act_quant`` (default: the
    ``MXTPU_QUANT_ACT`` env, read at trace time) switches to the int8
    activation x int8 weight path using ``act_amax`` (or the weight's
    calibrated threshold, or a dynamic amax).
    """
    if not isinstance(qt, QuantizedTensor):
        raise MXNetError("quantized_matmul needs a QuantizedTensor "
                         f"weight, got {type(qt).__name__}")
    if x.shape[-1] != qt.in_features:
        raise MXNetError(
            f"quantized_matmul: x last dim {x.shape[-1]} != weight "
            f"in_features {qt.in_features}")
    if act_quant is None:
        act_quant = act_quant_enabled()
    if use_kernel is None:
        use_kernel = kernel_eligible(x) and not act_quant
    if use_kernel:
        note_fused_launch(f"quantized_matmul_int{qt.bits}")

    lead = x.shape[:-1]
    x2 = x.reshape(-1, qt.in_features)

    # custom_vjp over x alone: qt's planes ride as closure constants,
    # so no float0 cotangent bookkeeping for the int arrays is needed
    # and the backward is one dense matmul against the dequantized
    # weight (bandwidth-bound, XLA fuses it fine)
    @jax.custom_vjp
    def _fwd_only(xv):
        if act_quant:
            return int8_act_matmul(xv, qt, act_amax=act_amax)
        if use_kernel:
            return _qmm_pallas(xv, qt.q, qt.scale, qt.bits,
                               qt.in_features)
        return quantized_matmul_reference(xv, qt)

    def _f(xv):
        return _fwd_only(xv), None

    def _b(_res, dy):
        w = dequantize_weight(qt, jnp.float32)
        return ((dy.astype(jnp.float32) @ w).astype(x.dtype),)

    _fwd_only.defvjp(_f, _b)
    out = _fwd_only(x2)
    return out.reshape(*lead, qt.out_features)


def matmul_nt(x, w, act_amax=None):
    """``x @ w.T`` for a dense array OR a `QuantizedTensor` — the one
    routing point the decode core and the Gluon parity API share."""
    if isinstance(w, QuantizedTensor):
        return quantized_matmul(x, w, act_amax=act_amax)
    return x @ w.T


def gather_rows(w, idx):
    """Row gather ``w[idx]`` with per-row dequantization for quantized
    weights (the opt-in quantized-embedding path: only the touched
    vocab rows are dequantized, never the full table)."""
    if not isinstance(w, QuantizedTensor):
        return w[idx]
    q = w.q[idx]
    if w.bits == 4:
        q = unpack_int4(q, w.in_features)
    return q.astype(jnp.float32) * w.scale[idx][..., None]


# ---------------------------------------------------------------------------
# autotune registration
# ---------------------------------------------------------------------------

def _candidates(shapes, dtype):
    m = shapes[0] if shapes else 256
    out = []
    for bm in (64, 128, 256):
        if bm > max(8, m * 2):
            continue
        for bn in (128, 256, 512):
            for bk in (128, 256, 512, 1024):
                out.append(autotune.BlockConfig(block_m=bm, block_n=bn,
                                                block_k=bk))
    return out


def _bits_of(dtype: str) -> int:
    return 4 if "4" in str(dtype) else 8


def _roofline(config, shapes, dtype):
    m = shapes[0] if shapes else 256
    n = shapes[1] if len(shapes) > 1 else 1024
    k = shapes[2] if len(shapes) > 2 else 1024
    bits = _bits_of(dtype)
    # THE point of the kernel: weight traffic at bits/8 bytes per
    # element (+ f32 scales), not 4 — the reference's dense f32 weight
    # read is what the fused path deletes
    weight_bytes = n * k * bits / 8.0 + n * 4.0
    return {
        "flops": 2.0 * m * n * k,
        "bytes": m * k * 4.0 + weight_bytes + m * n * 4.0,
        "steps": max(1.0, (m / config.block_m) * (n / config.block_n)
                     * (k / config.block_k)),
    }


def _build(config, shapes, dtype):
    import numpy as onp
    m = shapes[0] if shapes else 256
    n = shapes[1] if len(shapes) > 1 else 1024
    k = shapes[2] if len(shapes) > 2 else 1024
    bits = _bits_of(dtype)
    rng = onp.random.RandomState(0)
    x = jnp.asarray(rng.randn(m, k), jnp.float32)
    qt = quantize_weight(jnp.asarray(rng.randn(n, k), jnp.float32), bits)
    blocks = (config.block_m, config.block_n, config.block_k)

    # off-TPU trials run the interpreter so a search can still produce
    # (and persist) a config; the CPU timings only need to exist, not
    # predict — real ranking happens on hardware (ROADMAP §5)
    import os
    needs_interp = not interpret_mode() and \
        jax.default_backend() != "tpu"
    fn = jax.jit(functools.partial(_qmm_pallas, bits=bits, k=k,
                                   blocks=blocks))

    def thunk():
        if needs_interp:
            old = os.environ.get("MXTPU_PALLAS_INTERPRET")
            os.environ["MXTPU_PALLAS_INTERPRET"] = "1"
            try:
                return fn(x, qt.q, qt.scale)
            finally:
                if old is None:
                    os.environ.pop("MXTPU_PALLAS_INTERPRET", None)
                else:
                    os.environ["MXTPU_PALLAS_INTERPRET"] = old
        return fn(x, qt.q, qt.scale)

    return thunk


autotune.register_tunable("quantized_matmul", _candidates, _build,
                          _roofline)
