"""Collective communication (parity: `src/kvstore/comm.h` reduce trees, NCCL
`kvstore_nccl.h`, ps-lite — all replaced by XLA collectives over ICI/DCN).

These wrappers are usable inside `shard_map`/`pjit` bodies; outside a mapped
context they degrade to identity (single device).
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

__all__ = ["allreduce", "allgather", "reduce_scatter", "broadcast",
           "ppermute_shift", "all_to_all", "axis_index", "axis_size"]


def allreduce(x, axis_name: str = "dp", op: str = "sum"):
    if op == "sum":
        return lax.psum(x, axis_name)
    if op == "mean":
        return lax.pmean(x, axis_name)
    if op == "max":
        return lax.pmax(x, axis_name)
    if op == "min":
        return lax.pmin(x, axis_name)
    raise ValueError(f"unknown reduce op {op}")


def allgather(x, axis_name: str, axis: int = 0, tiled: bool = True):
    return lax.all_gather(x, axis_name, axis=axis, tiled=tiled)


def reduce_scatter(x, axis_name: str, axis: int = 0):
    return lax.psum_scatter(x, axis_name, scatter_dimension=axis, tiled=True)


def broadcast(x, axis_name: str, src: int = 0):
    idx = lax.axis_index(axis_name)
    n = axis_size(axis_name)
    perm = [(src, i) for i in range(n)]
    return lax.ppermute(x, axis_name, perm)


def ppermute_shift(x, axis_name: str, shift: int = 1):
    """Ring shift: device i sends to (i+shift) mod n (ring-attention hop)."""
    n = axis_size(axis_name)
    perm = [(i, (i + shift) % n) for i in range(n)]
    return lax.ppermute(x, axis_name, perm)


def all_to_all(x, axis_name: str, split_axis: int, concat_axis: int):
    return lax.all_to_all(x, axis_name, split_axis=split_axis,
                          concat_axis=concat_axis, tiled=True)


def axis_index(axis_name: str):
    return lax.axis_index(axis_name)


def axis_size(axis_name: str):
    """Static size of a named mesh axis, trace-safe inside shard_map (a
    concrete int, usable in ``range()`` for ppermute permutations)."""
    return lax.axis_size(axis_name)
