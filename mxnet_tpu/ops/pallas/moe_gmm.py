"""Grouped expert matmul for a chip's share of an expert layer (Pallas TPU
+ reference).

The serving step's expert layer (`serve/decode.py::moe_ffn`) is told which
experts this chip holds, routes every token over ALL experts and computes
the part of the result its own experts give.  The token-expert pairs that
land here are laid out **sorted by expert, each expert's run padded to a
whole number of `TILE_ROWS`-row tiles** (`plan_rows`), so that every row
tile belongs to exactly one expert and no pair is ever dropped: the buffer
is sized for the worst case (every pair held here), and what is computed
follows the step's own counts.

`grouped_matmul(xs, w, group_rows)` multiplies each expert's run of rows by
that expert's matrix: ``out[r] = xs[r] @ w[g(r)]``.  On the kernel route
ONE Pallas call walks only the ACTIVE row tiles (a dynamic grid bound, the
megablox idea): a grid step DMAs one contiguous ``(tk, N)`` slab of the
tile's expert and accumulates into the tile's resident f32 output block, so
an expert with no rows costs nothing and an expert's weights stream once a
row tile.  Rows past the active tiles are left unwritten (callers never
read them).  Everywhere else `jax.lax.ragged_dot` is the reference and the
interpret-mode oracle.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from . import interpret_mode, kernel_active, note_fused_launch, \
    tpu_compiler_params

__all__ = ["TILE_ROWS", "padded_rows", "plan_rows", "grouped_matmul",
           "grouped_matmul_reference"]

#: rows of one tile: a bf16 sublane tile is 16 rows; 32 keeps an expert
#: with a few dozen tokens in one pass over its weights
TILE_ROWS = 32


def padded_rows(n_pairs: int, n_groups: int, tile: int = TILE_ROWS) -> int:
    """Rows of the sorted, tile-aligned buffer that holds `n_pairs` pairs
    over `n_groups` experts whatever their split: every expert wastes less
    than one tile."""
    worst = n_pairs + n_groups * (tile - 1)
    return -(-worst // tile) * tile


def plan_rows(group_of_pair, n_groups: int, tile: int = TILE_ROWS):
    """Lay pairs out by expert.  group_of_pair: (P,) int32, the local
    expert of each pair, `n_groups` for a pair that is not held here (or
    not real).  Returns ``(dest, counts, group_rows)``: dest (P,) the row
    of each pair in the buffer (`padded_rows(P, n_groups)` for one not
    held: out of range, dropped by the scatter and masked by the caller);
    counts (n_groups,) pairs a held expert; group_rows (n_groups,) each
    count rounded up to whole tiles."""
    P = group_of_pair.shape[0]
    held = group_of_pair < n_groups
    onehot = (group_of_pair[:, None] == jnp.arange(n_groups)[None, :])
    counts = onehot.sum(0).astype(jnp.int32)
    group_rows = -(-counts // tile) * tile
    seg_start = jnp.cumsum(group_rows) - group_rows
    # rank of a pair among the pairs of its expert, in pair order
    rank = (jnp.cumsum(onehot, axis=0) - 1)[
        jnp.arange(P), jnp.minimum(group_of_pair, n_groups - 1)]
    dest = seg_start[jnp.minimum(group_of_pair, n_groups - 1)] + rank
    return (jnp.where(held, dest, padded_rows(P, n_groups, tile)
                      ).astype(jnp.int32), counts, group_rows)


def grouped_matmul_reference(xs, w, group_rows):
    """``jax.lax.ragged_dot`` over the tile-aligned runs, f32 out; rows
    past the last run are zero."""
    return jax.lax.ragged_dot(xs, w, group_rows.astype(jnp.int32),
                              preferred_element_type=jnp.float32)


def _pick_tk(K: int, N: int, itemsize: int) -> int:
    """Largest divisor of K (a multiple of 128) whose (tk, N) slab stays
    under 3 MB: two buffers of it fit the default scoped VMEM."""
    best = 128 if K % 128 == 0 else K
    for tk in range(128, K + 1, 128):
        if K % tk == 0 and tk * N * itemsize <= 3 * 1024 * 1024:
            best = tk
    return best


@functools.partial(jax.jit, static_argnames=("tile", "interpret"))
def _gmm_pallas(xs, w, group_rows, *, tile, interpret):
    """The launch, under a `jax.jit` of its own: the expert layers of a
    step make the same two calls (W1|W3, W2), so the step traces and
    lowers two bodies a width, not two a layer (PERF.md, PR 32)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    R, K = xs.shape
    G, _, N = w.shape
    tk = _pick_tk(K, N, w.dtype.itemsize)
    n_tiles = R // tile
    # tile t belongs to the expert whose run covers row t * tile; tiles
    # past the active ones repeat the last expert and are never visited
    ends = jnp.cumsum(group_rows)
    tile_group = jnp.minimum(
        jnp.searchsorted(ends, jnp.arange(n_tiles) * tile, side="right"),
        G - 1).astype(jnp.int32)
    n_active = (ends[-1] // tile).astype(jnp.int32)

    def kernel(tg_ref, x_ref, w_ref, o_ref):
        @pl.when(pl.program_id(1) == 0)
        def _zero():
            o_ref[...] = jnp.zeros_like(o_ref)

        o_ref[...] += jax.lax.dot(x_ref[...], w_ref[...],
                                  preferred_element_type=jnp.float32)

    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(n_active, K // tk),
            in_specs=[
                pl.BlockSpec((tile, tk), lambda t, k, tg: (t, k)),
                pl.BlockSpec((None, tk, N), lambda t, k, tg: (tg[t], k, 0)),
            ],
            out_specs=pl.BlockSpec((tile, N), lambda t, k, tg: (t, 0))),
        out_shape=jax.ShapeDtypeStruct((R, N), jnp.float32),
        compiler_params=tpu_compiler_params("arbitrary", "arbitrary"),
        interpret=interpret,
        name="mx_moe_gmm",
    )(tile_group, xs, w)


def grouped_matmul(xs, w, group_rows, tile: int = TILE_ROWS,
                   use_kernel=None):
    """``out[r] = xs[r] @ w[g(r)]`` in f32 for rows laid out by
    `plan_rows`.  xs: (R, K) with R a multiple of `tile`; w: (G, K, N);
    group_rows: (G,) int32 multiples of `tile`, summing to at most R.
    Rows past the active tiles are undefined on the kernel route."""
    R, K = xs.shape
    if R % tile:
        raise ValueError(f"rows ({R}) must be a multiple of the tile "
                         f"({tile})")
    if use_kernel is None:
        # the kernel tiles K and N by the 128 lanes; a toy width (the CPU
        # tests' models) takes the reference on every route
        use_kernel = kernel_active() and K % 128 == 0 \
            and w.shape[2] % 128 == 0
    if not use_kernel:
        return grouped_matmul_reference(xs, w, group_rows)
    note_fused_launch("moe_gmm")
    return _gmm_pallas(xs.astype(w.dtype), w, group_rows, tile=tile,
                       interpret=interpret_mode())
