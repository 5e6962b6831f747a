"""Weights from the seed, made on the device in one jitted call, in the type
they are trained or served in.

A family lists its parameters as ``[(name, shape, dtype, kind), ...]`` with
kind ``weight`` (normal, std 0.02), ``bias`` (normal, std 0.02, so that no
bias path is invisible to the comparison), ``gamma`` (1 + normal x 0.02) or
``beta`` (normal x 0.02).  The program gets these arrays; the reference gets
`as_float32` of the same values, so both start from identical numbers and
only the arithmetic differs.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

STD = 0.02


def key_of(seed: int):
    """A key from any whole number up to 2**63: the low 31 bits seed it, the
    rest is folded in."""
    seed = int(seed)
    if seed < 0:
        raise ValueError("--seed is a whole number >= 0")
    key = jax.random.PRNGKey(seed & 0x7FFFFFFF)
    return jax.random.fold_in(key, (seed >> 31) & 0x7FFFFFFF)


def make(spec, seed: int, sharding=None) -> dict:
    """{name: array} for `spec`; `sharding` maps name -> Sharding (optional),
    so that a sharded step's weights are born where they live."""
    spec = [(n, tuple(s), jnp.dtype(d), k) for n, s, d, k in spec]

    def build(key):
        out = {}
        for i, (name, shape, dtype, kind) in enumerate(spec):
            x = jax.random.normal(jax.random.fold_in(key, i), shape,
                                  jnp.float32) * STD
            if kind == "gamma":
                x = 1.0 + x
            elif kind not in ("weight", "bias", "beta"):
                raise ValueError(f"unknown parameter kind {kind!r}")
            out[name] = x.astype(dtype)
        return out

    out_shardings = None
    if sharding is not None:
        out_shardings = {n: sharding[n] for n, *_ in spec}
    return jax.jit(build, out_shardings=out_shardings)(key_of(seed))


def as_float32(weights: dict) -> dict:
    return jax.jit(lambda w: {k: v.astype(jnp.float32)
                              for k, v in w.items()})(weights)
