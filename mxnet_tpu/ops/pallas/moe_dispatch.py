"""Blockwise MoE dispatch/combine (Pallas TPU + jnp reference).

`parallel/moe.py`'s original formulation materialises a dense one-hot
dispatch tensor ``disp (T, E, C)`` and contracts it twice::

    buf = einsum("tec,th->ech", disp, x)          # dispatch
    out = einsum("tec,ech->th", disp * gate, dn)  # combine

— O(T·E·C·H) multiply-adds and an O(T·E·C) intermediate for what is a
permutation: every kept token lands in exactly one ``(expert, slot)``
capacity cell.  This module implements the permutation directly, so
cost scales with T·H (≈ T·C per expert), not T·E·C·H:

- **dispatch**: invert the token→slot map on the slot side (one tiny
  int32 scatter), then a Pallas *gather* kernel walks the E·C capacity
  rows and pulls each row's source token via a scalar-prefetched index
  — empty slots read a zero row, so the buffer needs no separate
  zero-init pass and garbage can never leak into expert FFN gradients.
- **combine**: a Pallas gather kernel walks the T tokens, pulls each
  token's expert output row via its slot index, and scales by
  ``gate * kept`` in-register.  Dropped tokens read the zero row —
  overflow semantics stay identical to the dense-einsum path.

Both kernels are pure gathers with scalar-prefetched page-table-style
indices (the `paged_attention.py` BlockSpec idiom).  Gradients run
through `jax.custom_vjp` with the jnp reference as the backward
(scatter/gather transpose pair); TODO(tpu): dedicated backward kernels
once the forward has been measured on the chip (ROADMAP A2).

The jnp reference (`moe_dispatch_reference` / `moe_combine_reference`)
— an XLA scatter-add and gather — is the CPU tier-1 path and the
interpret-mode parity oracle; `MXTPU_PALLAS=reference` forces it,
`MXTPU_PALLAS=off` restores the dense einsums in `parallel/moe.py`.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as _onp

from . import interpret_mode, kernel_active, note_fused_launch

LANES = 128

__all__ = ["moe_dispatch", "moe_combine", "moe_dispatch_reference",
           "moe_combine_reference", "kernel_eligible"]


def _slots(expert, pos, kept, num_experts, capacity):
    """Flat capacity-cell index per token; dropped tokens map to the
    one-past-the-end dummy cell (sliced/zero-rowed by the callers)."""
    flat = expert.astype(jnp.int32) * capacity + pos.astype(jnp.int32)
    return jnp.where(kept, flat, num_experts * capacity)


# ---------------------------------------------------------------------------
# jnp reference (tier-1 path + parity oracle)
# ---------------------------------------------------------------------------

def moe_dispatch_reference(x, expert, pos, kept, num_experts, capacity):
    """Scatter tokens to their (expert, slot) capacity cells.

    x: (T, H); expert/pos: (T,) int; kept: (T,) bool.  Returns
    (E, C, H) with empty cells exactly zero (the einsum contract)."""
    t, h = x.shape
    slot = _slots(expert, pos, kept, num_experts, capacity)
    buf = jnp.zeros((num_experts * capacity + 1, h), x.dtype)
    buf = buf.at[slot].add(x)      # kept cells are unique: add == set
    return buf[:num_experts * capacity].reshape(num_experts, capacity, h)


def moe_combine_reference(down, expert, pos, kept, gate):
    """Gather each token's expert output row, scaled by gate (dropped
    tokens produce zero rows — identical to the dense-einsum path)."""
    e, c, h = down.shape
    flat = down.reshape(e * c, h)
    flat = jnp.concatenate([flat, jnp.zeros((1, h), flat.dtype)])
    slot = _slots(expert, pos, kept, e, c)
    rows = flat[slot]
    scale = gate.astype(down.dtype) * kept.astype(down.dtype)
    return rows * scale[:, None]


# ---------------------------------------------------------------------------
# Pallas gather kernels
# ---------------------------------------------------------------------------

def kernel_eligible(h: int) -> bool:
    """The gathered rows are (1, H) lane vectors: H must slice
    (<= LANES) or tile (multiple of LANES)."""
    return h <= LANES or h % LANES == 0


def _gather_rows_pallas(src, idx, scale=None):
    """out[i] = src[idx[i]] (* scale[i]) via one grid step per row.

    src: (N, H) — callers append a zero row so every index is valid;
    idx: (R,) int32 scalar-prefetched; scale: optional (R,) f32."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    r = idx.shape[0]
    h = src.shape[1]
    has_scale = scale is not None

    def kernel(*refs):
        if has_scale:
            idx_ref, sc_ref, src_ref, o_ref = refs
        else:
            idx_ref, src_ref, o_ref = refs
            sc_ref = None
        row = src_ref[...]
        if sc_ref is not None:
            i = pl.program_id(0)
            row = (row.astype(jnp.float32)
                   * sc_ref[i]).astype(o_ref.dtype)
        o_ref[...] = row

    n_prefetch = 2 if has_scale else 1
    in_specs = [pl.BlockSpec((1, h),
                             (lambda i, idxr, scr: (idxr[i], 0))
                             if has_scale else
                             (lambda i, idxr: (idxr[i], 0)))]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=n_prefetch,
        grid=(r,),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, h),
                               (lambda i, idxr, scr: (i, 0))
                               if has_scale else
                               (lambda i, idxr: (i, 0))),
    )
    args = [idx.astype(jnp.int32)]
    if has_scale:
        args.append(scale.astype(jnp.float32))
    args.append(src)
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((r, h), src.dtype),
        compiler_params=_compiler_params(pltpu),
        interpret=interpret_mode(),
        name="mx_moe_dispatch",
    )(*args)


def _compiler_params(pltpu):
    from . import tpu_compiler_params
    return tpu_compiler_params("arbitrary")


def _int_cot(a):
    """Zero cotangent for an integer/bool input (float0, flash-kernel
    seed pattern)."""
    return _onp.zeros(a.shape, jax.dtypes.float0)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def _dispatch_kernel(x, expert, pos, kept, num_experts, capacity):
    t, h = x.shape
    slot = _slots(expert, pos, kept, num_experts, capacity)
    # invert token->slot on the slot side: inv[s] = source token (or T,
    # the appended zero row). The int32 scatter is O(T) — negligible.
    inv = jnp.full((num_experts * capacity + 1,), t, jnp.int32)
    inv = inv.at[slot].set(jnp.arange(t, dtype=jnp.int32))
    inv = inv[:num_experts * capacity]
    xz = jnp.concatenate([x, jnp.zeros((1, h), x.dtype)])
    buf = _gather_rows_pallas(xz, inv)
    return buf.reshape(num_experts, capacity, h)


def _dispatch_fwd(x, expert, pos, kept, num_experts, capacity):
    out = _dispatch_kernel(x, expert, pos, kept, num_experts, capacity)
    return out, (expert, pos, kept)


def _dispatch_bwd(num_experts, capacity, saved, dbuf):
    expert, pos, kept = saved
    # transpose of the scatter: gather each token's cell cotangent
    # (dbuf carries x's dtype — the buffer was built in it)
    dx = moe_combine_reference(
        dbuf, expert, pos, kept,
        jnp.ones(expert.shape, jnp.float32))
    return dx, _int_cot(expert), _int_cot(pos), _int_cot(kept)


_dispatch_kernel.defvjp(_dispatch_fwd, _dispatch_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=())
def _combine_kernel(down, expert, pos, kept, gate):
    e, c, h = down.shape
    flat = down.reshape(e * c, h)
    flat = jnp.concatenate([flat, jnp.zeros((1, h), flat.dtype)])
    slot = _slots(expert, pos, kept, e, c)
    scale = gate.astype(jnp.float32) * kept.astype(jnp.float32)
    return _gather_rows_pallas(flat, slot, scale=scale)


def _combine_fwd(down, expert, pos, kept, gate):
    out = _combine_kernel(down, expert, pos, kept, gate)
    return out, (down, expert, pos, kept, gate)


def _combine_bwd(saved, dout):
    down, expert, pos, kept, gate = saved
    e, c, _ = down.shape
    scale = gate.astype(dout.dtype) * kept.astype(dout.dtype)
    # d(down): scatter the scaled token cotangents back to their cells
    ddown = moe_dispatch_reference(dout * scale[:, None], expert, pos,
                                   kept, e, c).astype(down.dtype)
    # d(gate): row dot of the gathered expert output with the cotangent
    rows = moe_combine_reference(down, expert, pos, kept,
                                 jnp.ones_like(gate))
    dgate = jnp.sum(rows.astype(jnp.float32)
                    * dout.astype(jnp.float32), axis=-1)
    dgate = (dgate * kept.astype(jnp.float32)).astype(gate.dtype)
    return (ddown, _int_cot(expert), _int_cot(pos), _int_cot(kept),
            dgate)


_combine_kernel.defvjp(_combine_fwd, _combine_bwd)


# ---------------------------------------------------------------------------
# public wrappers
# ---------------------------------------------------------------------------

def moe_dispatch(x, expert, pos, kept, num_experts, capacity,
                 use_kernel=None):
    """Tokens -> (E, C, H) capacity buffer (kernel when active)."""
    if use_kernel is None:
        use_kernel = kernel_active() and kernel_eligible(x.shape[1])
    if not use_kernel:
        return moe_dispatch_reference(x, expert, pos, kept, num_experts,
                                      capacity)
    note_fused_launch("moe_dispatch")
    return _dispatch_kernel(x, expert, pos, kept, num_experts, capacity)


def moe_combine(down, expert, pos, kept, gate, use_kernel=None):
    """(E, C, H) expert outputs -> (T, H) gated token rows."""
    if use_kernel is None:
        use_kernel = kernel_active() and kernel_eligible(down.shape[2])
    if not use_kernel:
        return moe_combine_reference(down, expert, pos, kept, gate)
    note_fused_launch("moe_combine")
    return _combine_kernel(down, expert, pos, kept, gate)


# ---------------------------------------------------------------------------
# autotune registration — the kernels have no free block parameter (one
# row per grid step), but registering keeps them in the tuner's op
# inventory so `tune()` can compare kernel vs reference end-to-end and
# the JSON cache records which path won per shape bucket.
# ---------------------------------------------------------------------------

def _candidates(shapes, dtype):
    from . import autotune as _at
    return [_at.BlockConfig(use_kernel=1), _at.BlockConfig(use_kernel=0)]


def _roofline(config, shapes, dtype):
    t = shapes[0] if shapes else 4096
    e = shapes[1] if len(shapes) > 1 else 8
    c = shapes[2] if len(shapes) > 2 else 1024
    h = shapes[3] if len(shapes) > 3 else 1024
    itemsize = 2 if "16" in str(dtype) else 4
    if config.get("use_kernel"):
        return {"flops": 2.0 * t * h, "bytes": 2.0 * t * h * itemsize,
                "steps": float(t + e * c)}
    # dense einsum pair: T·E·C·H MACs each way
    return {"flops": 4.0 * t * e * c * h,
            "bytes": (2.0 * t * h + t * e * c) * itemsize,
            "steps": 2.0}


def _build(config, shapes, dtype):
    import numpy as onp
    t = shapes[0] if shapes else 4096
    e = shapes[1] if len(shapes) > 1 else 8
    c = shapes[2] if len(shapes) > 2 else max(1, t // e)
    h = shapes[3] if len(shapes) > 3 else 1024
    rng = onp.random.RandomState(0)
    x = jnp.asarray(rng.randn(t, h), dtype)
    expert = jnp.asarray(rng.randint(0, e, t), jnp.int32)
    pos = jnp.asarray(rng.randint(0, c, t), jnp.int32)
    kept = jnp.ones((t,), bool)
    use_k = bool(config.get("use_kernel"))

    fn = jax.jit(functools.partial(moe_dispatch, num_experts=e,
                                   capacity=c, use_kernel=use_k))

    def thunk():
        return fn(x, expert, pos, kept)

    return thunk


def _register():
    from . import autotune as _at
    _at.register_tunable("moe_dispatch", _candidates, _build, _roofline)


_register()
