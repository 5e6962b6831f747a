"""Fused multi-tensor optimizer update (Pallas TPU + reference).

TPU analogue of the reference's multi-tensor kernels
(`src/operator/contrib/multi_lamb.cc`, `multi_sgd`, adamw): instead of
one tiny elementwise program per parameter leaf — dozens of HBM
round-trips per step for a transformer's bias/scale zoo — the
parameter/optimizer-state tree is flattened into contiguous same-dtype
**chunks** and ONE kernel per chunk applies the optimizer math *and*
the PR 5 non-finite skip-guard in-register:

- grouping key: (weight dtype, state-leaf dtypes, state structure) —
  so bf16 weights with fp32 Adam moments form one chunk, fp32 weights
  another;
- each chunk is padded to the (8, 128) tile and walked by a
  ``block_rows x 128`` grid (block size via the autotuner,
  ``tune("fused_optimizer", ...)``);
- the per-optimizer math inside the kernel IS `optimizer._rule` — the
  rules for the elementwise family (Adam/AdamW/SGD/...) are pure jnp
  elementwise programs, so the exact same code traces into the Pallas
  kernel body and into the jnp reference path (single source of truth,
  bit-identical math);
- the skip flag (non-finite gradient probe) rides in SMEM and selects
  the old weight/state in-register — no post-hoc `jnp.where` ladder;
- LAMB's trust ratio needs per-TENSOR norms, which a mixed chunk
  cannot give it: LAMB runs per-leaf as kernel A (elementwise m/v/r +
  per-block norm partials) → host-free jnp scalar glue (trust ratio)
  → kernel B (the bounded update), still two launches per tensor
  instead of the XLA ladder.

The reference path (`apply_updates(use_kernel=False)`) is per-leaf
`optimizer._rule` + one `jnp.where` per leaf — exactly the semantics
the per-leaf ladder in `parallel/train.py` used to hard-code, now in
one place.  It is the CPU tier-1 path and the interpret-mode parity
oracle; `MXTPU_PALLAS=reference` forces it everywhere.
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from . import autotune, interpret_mode, kernel_active, note_fused_launch

LANES = 128
_SUBLANES = 8

__all__ = ["apply_updates", "supported", "kernel_supported",
           "kernel_route", "tree_update"]


# ---------------------------------------------------------------------------
# support predicates
# ---------------------------------------------------------------------------

def _is_lamb(optimizer) -> bool:
    from ...optimizer.lamb import LAMB
    return type(optimizer) is LAMB


def _elementwise(optimizer) -> bool:
    return bool(getattr(optimizer, "fused_elementwise", False)) and \
        bool(getattr(optimizer, "fused_safe", True))


def supported(optimizer) -> bool:
    """Can `apply_updates` handle this optimizer at all?  (The reference
    path calls `_rule` per leaf, so the answer is yes for anything with
    a pure rule — this only excludes rules with python-side state.)"""
    return bool(getattr(optimizer, "fused_safe", True))


def kernel_supported(optimizer) -> bool:
    """Can the Pallas chunk/tensor kernels run this optimizer's math?"""
    return _elementwise(optimizer) or _is_lamb(optimizer)


def kernel_route(optimizer) -> bool:
    """Should a caller ask for the kernel path right now? (mode says
    kernels are active AND the optimizer's math is kernel-eligible)."""
    return kernel_active() and kernel_supported(optimizer)


# ---------------------------------------------------------------------------
# reference path — the former per-leaf ladder, verbatim semantics
# ---------------------------------------------------------------------------

def _cast_like(new, old):
    return new.astype(old.dtype) \
        if hasattr(new, "dtype") and new.dtype != old.dtype else new


def _reference_leaf(optimizer, w, g, s_old, hp, skip):
    nw, ns = optimizer._rule(w, g, s_old, hp)
    # low-precision training: fp32 hyperparameter scalars promote the
    # update math (the implicit master-weight path), but the stored
    # weight/state dtypes must stay EXACTLY as declared or donation
    # breaks and every step retraces
    nw = _cast_like(nw, w)
    ns = jax.tree_util.tree_map(_cast_like, ns, s_old)
    if skip is not None:
        # non-finite probe fired: the whole update becomes the identity
        # — weights and optimizer state keep their pre-step values
        nw = jnp.where(skip, w, nw)
        ns = jax.tree_util.tree_map(
            lambda new, old: jnp.where(skip, old, new), ns, s_old)
    return nw, ns


# ---------------------------------------------------------------------------
# chunked elementwise kernel
# ---------------------------------------------------------------------------

def _scalar_smem_spec():
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    return pl.BlockSpec(memory_space=pltpu.SMEM)


def _compiler_params():
    from . import tpu_compiler_params
    return tpu_compiler_params("arbitrary")


def _hp_scalars(hp, skip):
    """Pack traced hp scalars (+ the skip flag) into (1, 1) SMEM
    operands; returns (arrays, has_clip, has_skip)."""
    def s11(v):
        return jnp.asarray(v, jnp.float32).reshape(1, 1)

    has_clip = hp.get("clip_gradient") is not None
    arrs = [s11(hp["lr"]), s11(hp["wd"]), s11(hp["rescale_grad"]),
            s11(hp.get("t", 0.0))]
    if has_clip:
        arrs.append(s11(hp["clip_gradient"]))
    has_skip = skip is not None
    if has_skip:
        arrs.append(s11(skip))
    return arrs, has_clip, has_skip


def _read_hp(refs, has_clip, has_skip):
    lr, wd, rg, t = (r[0, 0] for r in refs[:4])
    # the rules raise constants to the step count (`beta ** t`); Mosaic
    # legalizes pow on vectors only, so `t` enters the rule as one
    # lane-row instead of an SMEM scalar
    t = jnp.full((1, LANES), t, jnp.float32)
    i = 4
    cg = None
    if has_clip:
        cg = refs[i][0, 0]
        i += 1
    skip = None
    if has_skip:
        skip = refs[i][0, 0] > 0.0
        i += 1
    hp = {"lr": lr, "wd": wd, "rescale_grad": rg, "clip_gradient": cg,
          "t": t}
    return hp, skip, i


def _elementwise_chunk_kernel(rule, treedef, n_state, has_clip,
                              has_skip):
    def kernel(*refs):
        hp, skip, i = _read_hp(refs, has_clip, has_skip)
        w_ref, g_ref = refs[i], refs[i + 1]
        s_refs = refs[i + 2:i + 2 + n_state]
        ow_ref = refs[i + 2 + n_state]
        os_refs = refs[i + 3 + n_state:]
        w = w_ref[...]
        s = treedef.unflatten([r[...] for r in s_refs])
        nw, ns = rule(w, g_ref[...], s, hp)
        ns_leaves = jax.tree_util.tree_leaves(ns)
        if skip is not None:
            nw = jnp.where(skip, w, nw)
            ns_leaves = [jnp.where(skip, s_refs[k][...], ns_leaves[k])
                         for k in range(n_state)]
        ow_ref[...] = nw.astype(ow_ref.dtype)
        for k in range(n_state):
            os_refs[k][...] = ns_leaves[k].astype(os_refs[k].dtype)

    return kernel


def _block_rows(total: int, dtype) -> int:
    cfg = autotune.cached_config("fused_optimizer", (total,), str(dtype))
    br = cfg.block_rows if cfg is not None else 256
    rows = max(1, (total + LANES - 1) // LANES)
    br = max(_SUBLANES, min(br, 1024))
    while br > _SUBLANES and br > rows:
        br //= 2
    return max(_SUBLANES, br)


def _to_grid(flat, rows, dtype=None):
    pad = rows * LANES - flat.shape[0]
    if pad:
        flat = jnp.pad(flat, (0, pad))
    out = flat.reshape(rows, LANES)
    return out if dtype is None else out.astype(dtype)


def _run_elementwise_chunk(optimizer, w_flat, g_flat, slot_flats,
                           slot_dtypes, treedef, hp, skip, total):
    """One kernel launch over a packed chunk; returns flat outputs."""
    from jax.experimental import pallas as pl

    br = _block_rows(total, w_flat.dtype)
    rows = ((max(1, (total + LANES - 1) // LANES) + br - 1) // br) * br
    with jax.named_scope("mx.optimizer.pack"):
        w2 = _to_grid(w_flat, rows)
        g2 = _to_grid(g_flat, rows)
        s2 = [_to_grid(s, rows) for s in slot_flats]

    hp_arrs, has_clip, has_skip = _hp_scalars(hp, skip)
    n_state = len(s2)
    row_spec = pl.BlockSpec((br, LANES), lambda i: (i, 0))
    in_specs = [_scalar_smem_spec()] * len(hp_arrs) + \
        [row_spec] * (2 + n_state)
    out_specs = [row_spec] * (1 + n_state)
    out_shape = [jax.ShapeDtypeStruct((rows, LANES), w2.dtype)] + \
        [jax.ShapeDtypeStruct((rows, LANES), d) for d in slot_dtypes]

    outs = pl.pallas_call(
        _elementwise_chunk_kernel(optimizer._rule, treedef, n_state,
                                  has_clip, has_skip),
        grid=(rows // br,),
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        compiler_params=_compiler_params(),
        interpret=interpret_mode(),
        name="mx_fused_opt_update",
    )(*hp_arrs, w2, g2, *s2)
    with jax.named_scope("mx.optimizer.pack"):
        nw = outs[0].reshape(-1)[:total]
        ns = [o.reshape(-1)[:total] for o in outs[1:]]
    return nw, ns


# ---------------------------------------------------------------------------
# LAMB per-tensor kernels (trust ratio needs whole-tensor norms)
# ---------------------------------------------------------------------------

def _lamb_phase_a_kernel(beta1, beta2, eps, bias_correction, has_clip,
                         has_skip):
    """Elementwise m/v/r (mirrors `optimizer/lamb.py:_rule` line for
    line) + per-block lane-partial sums of w^2 and r^2."""

    def kernel(*refs):
        hp, skip, i = _read_hp(refs, has_clip, has_skip)
        w_ref, g_ref, m_ref, v_ref = refs[i:i + 4]
        om_ref, ov_ref, r_ref, wp_ref, rp_ref = refs[i + 4:i + 9]
        w = w_ref[...].astype(jnp.float32)
        g = g_ref[...].astype(jnp.float32) * hp["rescale_grad"]
        if hp["clip_gradient"] is not None:
            g = jnp.clip(g, -hp["clip_gradient"], hp["clip_gradient"])
        m = beta1 * m_ref[...] + (1 - beta1) * g
        v = beta2 * v_ref[...] + (1 - beta2) * g * g
        if bias_correction:
            t = hp["t"]
            mhat = m / (1 - beta1 ** t)
            vhat = v / (1 - beta2 ** t)
        else:
            mhat, vhat = m, v
        r = mhat / (jnp.sqrt(vhat) + eps) + hp["wd"] * w
        if skip is not None:
            m = jnp.where(skip, m_ref[...], m)
            v = jnp.where(skip, v_ref[...], v)
        om_ref[...] = m.astype(om_ref.dtype)
        ov_ref[...] = v.astype(ov_ref.dtype)
        r_ref[...] = r
        wp_ref[...] = jnp.sum(w * w, axis=0, keepdims=True)
        rp_ref[...] = jnp.sum(r * r, axis=0, keepdims=True)

    return kernel


def _lamb_phase_b_kernel(has_clip, has_skip):
    """w' = w - lr * ratio * r, skip-guarded (ratio rides in SMEM)."""

    def kernel(*refs):
        hp, skip, i = _read_hp(refs, has_clip, has_skip)
        ratio_ref, w_ref, r_ref, ow_ref = refs[i:i + 4]
        w = w_ref[...]
        nw = w.astype(jnp.float32) - \
            hp["lr"] * ratio_ref[0, 0] * r_ref[...]
        if skip is not None:
            nw = jnp.where(skip, w.astype(jnp.float32), nw)
        ow_ref[...] = nw.astype(ow_ref.dtype)

    return kernel


def _run_lamb_leaf(optimizer, w, g, s_old, hp, skip):
    """Two launches + scalar jnp glue for one LAMB tensor."""
    from jax.experimental import pallas as pl

    m_old, v_old = s_old
    total = w.size
    br = _block_rows(total, w.dtype)
    rows = ((max(1, (total + LANES - 1) // LANES) + br - 1) // br) * br
    w2 = _to_grid(w.ravel(), rows)
    g2 = _to_grid(g.ravel(), rows)
    m2 = _to_grid(m_old.ravel(), rows)
    v2 = _to_grid(v_old.ravel(), rows)

    hp_arrs, has_clip, has_skip = _hp_scalars(hp, skip)
    row_spec = pl.BlockSpec((br, LANES), lambda i: (i, 0))
    part_spec = pl.BlockSpec((1, LANES), lambda i: (i, 0))
    nb = rows // br
    f32 = jnp.float32
    m_new2, v_new2, r2, wpart, rpart = pl.pallas_call(
        _lamb_phase_a_kernel(optimizer.beta1, optimizer.beta2,
                             optimizer.epsilon,
                             optimizer.bias_correction, has_clip,
                             has_skip),
        grid=(nb,),
        in_specs=[_scalar_smem_spec()] * len(hp_arrs) + [row_spec] * 4,
        out_specs=[row_spec] * 3 + [part_spec] * 2,
        out_shape=[jax.ShapeDtypeStruct((rows, LANES), m_old.dtype),
                   jax.ShapeDtypeStruct((rows, LANES), v_old.dtype),
                   jax.ShapeDtypeStruct((rows, LANES), f32),
                   jax.ShapeDtypeStruct((nb, LANES), f32),
                   jax.ShapeDtypeStruct((nb, LANES), f32)],
        compiler_params=_compiler_params(),
        interpret=interpret_mode(),
        name="mx_fused_lamb_moments",
    )(*hp_arrs, w2, g2, m2, v2)

    # scalar glue (device-side, a handful of flops — mirrors _rule)
    w_norm = jnp.sqrt(jnp.sum(wpart))
    r_norm = jnp.sqrt(jnp.sum(rpart))
    if optimizer.lower_bound is not None:
        w_norm = jnp.maximum(w_norm, optimizer.lower_bound)
    if optimizer.upper_bound is not None:
        w_norm = jnp.minimum(w_norm, optimizer.upper_bound)
    ratio = jnp.where((w_norm > 0) & (r_norm > 0), w_norm / r_norm, 1.0)

    (nw2,) = [pl.pallas_call(
        _lamb_phase_b_kernel(has_clip, has_skip),
        grid=(nb,),
        in_specs=[_scalar_smem_spec()] * (len(hp_arrs) + 1)
        + [row_spec] * 2,
        out_specs=row_spec,
        out_shape=jax.ShapeDtypeStruct((rows, LANES), w.dtype),
        compiler_params=_compiler_params(),
        interpret=interpret_mode(),
        name="mx_fused_lamb_apply",
    )(*hp_arrs, ratio.astype(f32).reshape(1, 1), w2, r2)]
    nw = nw2.reshape(-1)[:total].reshape(w.shape)
    nm = m_new2.reshape(-1)[:total].reshape(w.shape)
    nv = v_new2.reshape(-1)[:total].reshape(w.shape)
    return nw, (nm, nv)


# ---------------------------------------------------------------------------
# public entry
# ---------------------------------------------------------------------------

def apply_updates(optimizer, params: Dict[str, Any],
                  grads: Dict[str, Any], states: Dict[str, Any],
                  hp: Dict[str, Any], skip=None,
                  use_kernel: bool = False
                  ) -> Tuple[Dict[str, Any], Dict[str, Any]]:
    """Apply one optimizer step over name-keyed pytrees.

    params/grads: {name: array}; states: {name: state tree from
    `create_state_jax`}; hp: the device-resident scalar dict
    (lr/wd/rescale_grad/clip_gradient/t); skip: optional traced bool —
    True turns the whole update into the identity (params AND state
    keep their pre-step values bit-exactly).

    ``use_kernel=False`` (or an optimizer the kernels don't cover) runs
    the per-leaf reference; ``use_kernel=True`` packs elementwise
    optimizers into dtype chunks with one Pallas launch each (LAMB:
    two launches per tensor).  Pure jnp/pallas — safe under jit.
    """
    names = sorted(params)
    if not use_kernel or not kernel_supported(optimizer):
        out_p, out_s = {}, {}
        for n in names:
            out_p[n], out_s[n] = _reference_leaf(
                optimizer, params[n], grads[n], states[n], hp, skip)
        return out_p, out_s

    if _is_lamb(optimizer):
        note_fused_launch("fused_optimizer")
        out_p, out_s = {}, {}
        for n in names:
            out_p[n], out_s[n] = _run_lamb_leaf(
                optimizer, params[n], grads[n], states[n], hp, skip)
        return out_p, out_s

    # group elementwise leaves into contiguous same-dtype chunks
    note_fused_launch("fused_optimizer")
    groups: Dict[Any, list] = {}
    for n in names:
        leaves, treedef = jax.tree_util.tree_flatten(states[n])
        key = (str(params[n].dtype),
               tuple(str(s.dtype) for s in leaves), treedef)
        groups.setdefault(key, []).append((n, leaves, treedef))

    out_p: Dict[str, Any] = {}
    out_s: Dict[str, Any] = {}
    for (_, slot_dtypes, treedef), members in groups.items():
        sizes = [params[n].size for n, _, _ in members]
        total = sum(sizes)
        n_state = len(slot_dtypes)
        # `mx.optimizer.pack`: the copies around the kernel (concatenate
        # and pad in, slice out), under one name in the op metadata
        with jax.named_scope("mx.optimizer.pack"):
            w_flat = jnp.concatenate(
                [params[n].ravel() for n, _, _ in members])
            g_flat = jnp.concatenate(
                [grads[n].ravel() for n, _, _ in members])
            slot_flats = [
                jnp.concatenate([lv[k].ravel() for _, lv, _ in members])
                for k in range(n_state)]
        nw, ns = _run_elementwise_chunk(
            optimizer, w_flat, g_flat, slot_flats,
            [jnp.dtype(d) for d in slot_dtypes], treedef, hp, skip,
            total)
        with jax.named_scope("mx.optimizer.pack"):
            off = 0
            for (n, _, td), size in zip(members, sizes):
                shape = params[n].shape
                out_p[n] = nw[off:off + size].reshape(shape)
                out_s[n] = td.unflatten(
                    [ns[k][off:off + size].reshape(shape)
                     for k in range(n_state)])
                off += size
    return out_p, out_s


@functools.partial(jax.jit, static_argnums=(0,), donate_argnums=(1, 3))
def _tree_update_jit(optimizer, params, grads, states, hp):
    return apply_updates(optimizer, params, grads, states, hp,
                         skip=None, use_kernel=True)


def tree_update(optimizer, params, grads, states, hp):
    """Jitted whole-tree kernel update for `gluon.Trainer._fused_update`
    (buffers donated; one compiled program per optimizer identity +
    tree structure)."""
    return _tree_update_jit(optimizer, params, grads, states, hp)


# ---------------------------------------------------------------------------
# autotune registration
# ---------------------------------------------------------------------------

def _candidates(shapes, dtype):
    total = shapes[0] if shapes else 1 << 20
    rows = max(1, (total + LANES - 1) // LANES)
    out = []
    for br in (64, 128, 256, 512, 1024):
        if br <= max(_SUBLANES, rows):
            out.append(autotune.BlockConfig(block_rows=br))
    return out or [autotune.BlockConfig(block_rows=_SUBLANES)]


def _roofline(config, shapes, dtype):
    total = shapes[0] if shapes else 1 << 20
    itemsize = 2 if "16" in str(dtype) else 4
    rows = max(1, (total + LANES - 1) // LANES)
    # Adam shape: read w/g/m/v + write w/m/v (m/v fp32)
    return {"flops": 18.0 * total,
            "bytes": total * (2 * itemsize + 4 * 2 + 4 * 3),
            "steps": max(1.0, rows / config.block_rows)}


def _build(config, shapes, dtype):
    import numpy as onp
    from ...optimizer import Adam
    total = shapes[0] if shapes else 1 << 20
    rng = onp.random.RandomState(0)
    opt = Adam(learning_rate=1e-3)
    w = jnp.asarray(rng.randn(total), dtype)
    g = jnp.asarray(rng.randn(total), dtype)
    m = jnp.zeros((total,), jnp.float32)
    v = jnp.zeros((total,), jnp.float32)
    hp = {"lr": jnp.float32(1e-3), "wd": jnp.float32(0.0),
          "rescale_grad": jnp.float32(1.0), "clip_gradient": None,
          "t": jnp.float32(1.0)}
    td = jax.tree_util.tree_structure((0, 0))
    br = config.block_rows

    def run(wv, gv, mv, vv):
        rows_min = max(1, (total + LANES - 1) // LANES)
        rows = ((rows_min + br - 1) // br) * br
        from jax.experimental import pallas as pl
        w2 = _to_grid(wv, rows)
        g2 = _to_grid(gv, rows)
        s2 = [_to_grid(mv, rows), _to_grid(vv, rows)]
        hp_arrs, has_clip, has_skip = _hp_scalars(hp, None)
        row_spec = pl.BlockSpec((br, LANES), lambda i: (i, 0))
        outs = pl.pallas_call(
            _elementwise_chunk_kernel(opt._rule, td, 2, has_clip,
                                      has_skip),
            grid=(rows // br,),
            in_specs=[_scalar_smem_spec()] * len(hp_arrs)
            + [row_spec] * 4,
            out_specs=[row_spec] * 3,
            out_shape=[jax.ShapeDtypeStruct((rows, LANES), w2.dtype),
                       jax.ShapeDtypeStruct((rows, LANES), jnp.float32),
                       jax.ShapeDtypeStruct((rows, LANES),
                                            jnp.float32)],
            compiler_params=_compiler_params(),
            interpret=interpret_mode(),
            name="mx_fused_opt_autotune",
        )(*hp_arrs, w2, g2, *s2)
        return outs

    fn = jax.jit(run)

    def thunk():
        return fn(w, g, m, v)

    return thunk


autotune.register_tunable("fused_optimizer", _candidates, _build,
                          _roofline)
