"""Fused LayerNorm/RMSNorm + residual-add (Pallas TPU + jnp reference).

One row kernel covers the transformer's whole normalisation surface:

- ``fused_layer_norm(x, gamma, beta)`` — plain LN over the last axis;
- ``fused_rms_norm(x, gamma)`` — RMSNorm (no centering, no beta);
- ``layer_norm_residual(x, residual, ...)`` / ``rms_norm_residual`` —
  the pre-LN transformer step ``s = residual + x; y = norm(s)`` in ONE
  pass: the residual sum is computed in-register and written alongside
  the normalised output, so the unfused three-op chain (add → mean/var
  reduction → scale/shift), each a separate HBM round-trip of the
  activation, collapses to one read and two writes.

Kernel shape: rows are the flattened leading dims, the normalised axis
is padded to the 128-lane minimum and masked; statistics use the
two-pass mean → centered-variance formulation (the numerically stable
half of Welford — with the whole row resident in VMEM the streaming
update is pointless) and `jax.lax.rsqrt` in fp32.

Backward: the forward runs as a Pallas kernel under `jax.custom_vjp`;
the backward recomputes row statistics and applies the standard LN/RMS
gradient in jnp — it is a bandwidth-bound elementwise+reduction XLA
already fuses well.  TODO(tpu): measure on the chip whether a dx/dgamma
Pallas backward pays for itself (ROADMAP A2).

The jnp reference (`*_reference`) is the CPU tier-1 path and the
interpret-mode parity oracle; `MXTPU_PALLAS=reference` forces it
everywhere (see `ops/pallas/__init__`).
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from . import autotune, interpret_mode, kernel_active, note_fused_launch

LANES = 128
_SUBLANES = 8

__all__ = ["fused_layer_norm", "fused_rms_norm", "layer_norm_residual",
           "rms_norm_residual", "layer_norm_reference",
           "rms_norm_reference", "kernel_eligible"]


# ---------------------------------------------------------------------------
# jnp reference (tier-1 path + parity oracle)
# ---------------------------------------------------------------------------

def layer_norm_reference(x, gamma, beta, eps=1e-5, residual=None):
    """Reference LN(+residual) over the last axis.  Mirrors
    `npx.layer_norm`'s math exactly (mean/var in the input dtype,
    rsqrt), with the residual added first when given."""
    s = residual + x if residual is not None else x
    mean = jnp.mean(s, axis=-1, keepdims=True)
    var = jnp.var(s, axis=-1, keepdims=True)
    y = (s - mean) * jax.lax.rsqrt(var + eps)
    shape = (1,) * (s.ndim - 1) + (s.shape[-1],)
    y = y * gamma.reshape(shape) + beta.reshape(shape)
    return (y, s) if residual is not None else y


def rms_norm_reference(x, gamma, eps=1e-6, residual=None):
    """Reference RMSNorm(+residual): y = s * rsqrt(mean(s^2)+eps) * g."""
    s = residual + x if residual is not None else x
    ms = jnp.mean(jnp.square(s), axis=-1, keepdims=True)
    y = s * jax.lax.rsqrt(ms + eps)
    shape = (1,) * (s.ndim - 1) + (s.shape[-1],)
    y = y * gamma.reshape(shape)
    return (y, s) if residual is not None else y


# ---------------------------------------------------------------------------
# Pallas kernel
# ---------------------------------------------------------------------------

def _norm_kernel(has_res, rms, eps, h, hp):
    """Row kernel over a (block_rows, hp) tile; hp >= h is the padded
    lane count, columns >= h are masked out of the statistics."""

    def kernel(*refs):
        if has_res:
            x_ref, r_ref, g_ref, b_ref, y_ref, s_ref = refs
        else:
            x_ref, g_ref, b_ref, y_ref = refs
            r_ref = s_ref = None
        x = x_ref[...].astype(jnp.float32)
        if r_ref is not None:
            x = x + r_ref[...].astype(jnp.float32)
            s_ref[...] = x.astype(s_ref.dtype)
        if hp == h:
            mask = None
            xm = x
        else:
            cols = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
            mask = cols < h
            xm = jnp.where(mask, x, 0.0)
        inv_h = 1.0 / h
        if rms:
            ms = jnp.sum(xm * xm, axis=1, keepdims=True) * inv_h
            y = x * jax.lax.rsqrt(ms + eps)
        else:
            # two-pass: exact mean first, then the centered second
            # moment (padded columns re-masked after centering)
            mean = jnp.sum(xm, axis=1, keepdims=True) * inv_h
            cent = x - mean
            if mask is not None:
                cent = jnp.where(mask, cent, 0.0)
            var = jnp.sum(cent * cent, axis=1, keepdims=True) * inv_h
            y = cent * jax.lax.rsqrt(var + eps)
        y = y * g_ref[...].astype(jnp.float32)
        if b_ref is not None:
            y = y + b_ref[...].astype(jnp.float32)
        y_ref[...] = y.astype(y_ref.dtype)

    return kernel


def _default_block_rows(rows: int, h: int, dtype) -> int:
    cfg = autotune.cached_config("fused_norm", (rows, h), str(dtype))
    br = cfg.block_rows if cfg is not None else 128
    br = max(_SUBLANES, min(br, 1024))
    return br


def _norm_pallas(x2, res2, gamma, beta, eps, rms, block_rows=None):
    """Launch the kernel over 2-D (rows, h) operands; returns y2 (and
    s2 when res2 is given)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    rows, h = x2.shape
    hp = max(LANES, ((h + LANES - 1) // LANES) * LANES)
    br = block_rows or _default_block_rows(rows, h, x2.dtype)
    rp = ((rows + br - 1) // br) * br

    def pad2(a):
        return jnp.pad(a, ((0, rp - rows), (0, hp - h)))

    xpad = pad2(x2)
    gpad = jnp.pad(gamma, (0, hp - h)).reshape(1, hp)
    has_res = res2 is not None
    has_beta = beta is not None
    bpad = jnp.pad(beta, (0, hp - h)).reshape(1, hp) if has_beta \
        else jnp.zeros((1, hp), gamma.dtype)

    row_spec = pl.BlockSpec((br, hp), lambda i: (i, 0))
    vec_spec = pl.BlockSpec((1, hp), lambda i: (0, 0))
    in_specs = [row_spec]
    args = [xpad]
    if has_res:
        in_specs.append(row_spec)
        args.append(pad2(res2))
    in_specs += [vec_spec, vec_spec]
    args += [gpad, bpad]
    out_specs = [row_spec]
    out_shape = [jax.ShapeDtypeStruct((rp, hp), x2.dtype)]
    if has_res:
        out_specs.append(row_spec)
        out_shape.append(jax.ShapeDtypeStruct((rp, hp), x2.dtype))

    outs = pl.pallas_call(
        _norm_kernel(has_res, rms, float(eps), h, hp),
        grid=(rp // br,),
        in_specs=in_specs,
        out_specs=out_specs if has_res else out_specs[0],
        out_shape=out_shape if has_res else out_shape[0],
        compiler_params=_compiler_params(pltpu),
        interpret=interpret_mode(),
        name="mx_fused_norm",
    )(*args)
    if has_res:
        y, s = outs
        return y[:rows, :h], s[:rows, :h]
    return outs[:rows, :h]


def _compiler_params(pltpu):
    from . import tpu_compiler_params
    return tpu_compiler_params("parallel")


# ---------------------------------------------------------------------------
# custom_vjp: Pallas forward, jnp backward (recompute stats)
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def _fused(x2, res2, gamma, beta, eps, rms):
    return _norm_pallas(x2, res2, gamma, beta, eps, rms)


def _fused_fwd(x2, res2, gamma, beta, eps, rms):
    y, s = _fused(x2, res2, gamma, beta, eps, rms)
    return (y, s), (s, gamma)


def _norm_grads(s, gamma, dy, eps, rms):
    """Shared backward math (recomputed stats): cotangents for the
    summed stream, gamma, and beta given dL/dy."""
    sf = s.astype(jnp.float32)
    dyf = dy.astype(jnp.float32)
    g = gamma.astype(jnp.float32).reshape(1, -1)
    if rms:
        rstd = jax.lax.rsqrt(
            jnp.mean(sf * sf, axis=-1, keepdims=True) + eps)
        xhat = sf * rstd
        dxh = dyf * g
        ds = rstd * (dxh - xhat * jnp.mean(dxh * xhat, axis=-1,
                                           keepdims=True))
        dbeta = None
    else:
        mean = jnp.mean(sf, axis=-1, keepdims=True)
        var = jnp.mean(jnp.square(sf - mean), axis=-1, keepdims=True)
        rstd = jax.lax.rsqrt(var + eps)
        xhat = (sf - mean) * rstd
        dxh = dyf * g
        ds = rstd * (dxh - jnp.mean(dxh, axis=-1, keepdims=True)
                     - xhat * jnp.mean(dxh * xhat, axis=-1,
                                       keepdims=True))
        dbeta = jnp.sum(dyf, axis=0).astype(gamma.dtype)
    dgamma = jnp.sum(dyf * xhat, axis=0).astype(gamma.dtype)
    return ds, dgamma, dbeta


def _fused_bwd(eps, rms, saved, cot):
    s, gamma = saved
    dy, ds_out = cot
    ds, dgamma, dbeta = _norm_grads(s, gamma, dy, eps, rms)
    # the summed stream s feeds BOTH outputs: its own cotangent adds
    ds = ds + ds_out.astype(jnp.float32)
    dx = ds.astype(s.dtype)
    dres = dx
    return dx, dres, dgamma, dbeta


_fused.defvjp(_fused_fwd, _fused_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _fused_nores(x2, gamma, beta, eps, rms):
    return _norm_pallas(x2, None, gamma, beta, eps, rms)


def _fused_nores_fwd(x2, gamma, beta, eps, rms):
    return _fused_nores(x2, gamma, beta, eps, rms), (x2, gamma)


def _fused_nores_bwd(eps, rms, saved, dy):
    s, gamma = saved
    ds, dgamma, dbeta = _norm_grads(s, gamma, dy, eps, rms)
    return ds.astype(s.dtype), dgamma, dbeta


_fused_nores.defvjp(_fused_nores_fwd, _fused_nores_bwd)


def _fused_2d(x2, res2, gamma, beta, eps, rms):
    """Differentiable kernel entry over 2-D rows.  The no-residual case
    has its own custom_vjp around the has_res=False kernel launch — a
    zeros-residual detour would cost an extra read of x AND a write of
    the discarded s stream on the hottest norm path."""
    if res2 is None:
        return _fused_nores(x2, gamma, beta, eps, rms)
    return _fused(x2, res2, gamma, beta, eps, rms)


# ---------------------------------------------------------------------------
# public wrappers
# ---------------------------------------------------------------------------

def kernel_eligible(x, axis=-1) -> bool:
    """Can (and should) this call take the Pallas path right now?"""
    if not kernel_active():
        return False
    if x.ndim < 2 or axis not in (-1, x.ndim - 1):
        return False
    return jnp.issubdtype(x.dtype, jnp.floating) and \
        jnp.dtype(x.dtype).itemsize in (2, 4)


def _dispatch(x, residual, gamma, beta, eps, rms, use_kernel):
    if use_kernel is None:
        use_kernel = kernel_eligible(x)
    if not use_kernel:
        if rms:
            return rms_norm_reference(x, gamma, eps=eps,
                                      residual=residual)
        return layer_norm_reference(x, gamma, beta, eps=eps,
                                    residual=residual)
    note_fused_launch("rms_norm" if rms else "layer_norm")
    lead = x.shape[:-1]
    h = x.shape[-1]
    x2 = x.reshape(-1, h)
    res2 = None if residual is None else residual.reshape(-1, h)
    out = _fused_2d(x2, res2, gamma, beta, eps, rms)
    if residual is None:
        return out.reshape(*lead, h)
    y, s = out
    return y.reshape(*lead, h), s.reshape(*lead, h)


def fused_layer_norm(x, gamma, beta, eps=1e-5, use_kernel=None):
    """LayerNorm over the last axis (Pallas kernel when active)."""
    return _dispatch(x, None, gamma, beta, eps, False, use_kernel)


def fused_rms_norm(x, gamma, eps=1e-6, use_kernel=None):
    """RMSNorm over the last axis (Pallas kernel when active)."""
    return _dispatch(x, None, gamma, None, eps, True, use_kernel)


def layer_norm_residual(x, residual, gamma, beta, eps=1e-5,
                        use_kernel=None) -> Tuple:
    """Fused ``s = residual + x; y = LN(s)``; returns ``(y, s)`` — the
    pre-LN transformer step with the residual stream kept live."""
    return _dispatch(x, residual, gamma, beta, eps, False, use_kernel)


def rms_norm_residual(x, residual, gamma, eps=1e-6,
                      use_kernel=None) -> Tuple:
    """Fused ``s = residual + x; y = RMSNorm(s)``; returns ``(y, s)``."""
    return _dispatch(x, residual, gamma, None, eps, True, use_kernel)


# ---------------------------------------------------------------------------
# autotune registration
# ---------------------------------------------------------------------------

def _candidates(shapes, dtype):
    rows = shapes[0] if shapes else 4096
    out = []
    for br in (8, 16, 32, 64, 128, 256, 512, 1024):
        if br <= max(_SUBLANES, rows * 2):
            out.append(autotune.BlockConfig(block_rows=br))
    return out


def _roofline(config, shapes, dtype):
    rows = shapes[0] if shapes else 4096
    h = shapes[1] if len(shapes) > 1 else 1024
    itemsize = 2 if "16" in str(dtype) else 4
    br = config.block_rows
    return {
        "flops": 8.0 * rows * h,
        # x read + y write (+ residual read/write amortised upward)
        "bytes": 2.0 * rows * h * itemsize,
        "steps": max(1.0, rows / br),
    }


def _build(config, shapes, dtype):
    import numpy as onp
    rows = shapes[0] if shapes else 4096
    h = shapes[1] if len(shapes) > 1 else 1024
    rng = onp.random.RandomState(0)
    x = jnp.asarray(rng.randn(rows, h), dtype)
    g = jnp.ones((h,), dtype)
    b = jnp.zeros((h,), dtype)

    fn = jax.jit(functools.partial(_norm_pallas, eps=1e-5, rms=False,
                                   block_rows=config.block_rows))

    def thunk():
        return fn(x, None, g, b)

    return thunk


autotune.register_tunable("fused_norm", _candidates, _build, _roofline)
