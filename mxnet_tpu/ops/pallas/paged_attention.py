"""Ragged paged attention for the serving stack (Pallas TPU + reference).

TPU-native serving kernel in the *Ragged Paged Attention* shape (PAPERS.md,
arxiv 2604.15464): ONE launch handles a mixed continuous-batching step —
some slots mid-prefill (a chunk of C query tokens), others decoding (one
query token) — attending over a **paged KV pool**.  The pool stores keys and
values head-major as fixed-size pages `(Hkv, num_pages, page_size, D)` in
HBM, so one (kv head, page) is a contiguous `(page_size, D)` tile — the
block Mosaic can DMA (a kv-head axis in the second-minor position cannot
be squeezed out of a TPU block); each slot's logical context is the
concatenation of the pages its page table names.  Where the head dim is
under the 128 lanes and divides them, ``g = 128 / D`` kv heads share a
page row instead (**folded**, `fold_heads`): the pool is
``(Hkv / g, num_pages, page_size, g * D)``, kv head ``j * g + t`` in lanes
``t * D ..`` of row group j, the same bytes with no padding.  A minor dim
of 128 keeps the page's rows in sublanes in XLA:TPU's own layout, so the
write moves one 16-row tile a row group where rows in lanes made it move
the whole page (PERF.md section 6).  The kernel's grid is a
**work list of the live (slot, logical page) pairs** with a traced bound
(`serve.kv_cache.live_page_items`: for each slot the pages from its first
query's window to its context's end, slot after slot), so a page no query
can see costs no grid step; one step takes ALL kv heads of its page and
walks on through the slot's pages (online softmax, flash style, the state
of every head in VMEM scratch), fetching the physical page via
scalar-prefetched page-table indices — no (B, L_max, ...) contiguous
gather is ever materialised on the TPU path.  The kernel is bound by the
count of its grid steps, not by bytes or FLOPs: the `(slots, kv heads,
table pages)` grid this replaced paid 6,144 steps a GPT-2-small layer for
≈ 100 live pages (PERF.md, PR 32).

Both kernels read the stacked pool of a cache group and pick the layer in
their index maps from a **prefetched scalar**, under an inner `jax.jit`
(`_rpa_pallas`, `_kv_write_pallas`): the layers of a group make the same
call, so a serving step traces each kernel body and lowers it to Mosaic
once a compiled width, not once a layer.  A warm start compiles nothing,
but it does trace and lower, and a Python constant in an index map made
every layer's call a program of its own (PERF.md, PR 32).

The chunk's new K/V rows enter the pool through a second kernel,
`paged_kv_write`, whose pool operands are aliased to its results: on the
kernel route the pools are operands and results of custom calls only, so
XLA:TPU has no layout of its own to give them and nothing to copy.  Both
kernels take the pool in either page orientation (`pages_in_lanes`): as
``(page_size, D)`` tiles, or — where the device keeps a page's rows in
lanes, as a v5e does for an unfolded D < 128 — as the ``(D, page_size)``
tiles of the transposed view, which is a bitcast there.

Grouped-query attention uses the same folding trick as
`flash_attention.py`: the `rep = H // Hkv` query heads sharing a kv head
stack along the row axis, so K/V pages stream once per kv head.  A folded
pool stacks the query rows of a row group's g kv heads the same way, each
row in its own head's lanes and zero in the others': its scores against
the 128-lane keys are its head's alone (every added product is x·0), and
of the 128 lanes P·V gives it, it keeps its head's.

The **reference path** (`paged_attention_reference`) gathers the page table
into a contiguous `(B, L, Hkv, D)` context and runs masked dense attention
with `_dense_attend` — the CPU tier-1 path, and the numerical baseline the
kernel is tested against (interpret mode runs the exact kernel code on
CPU).  `_dense_attend` is also what `GPTForCausalLM.generate`'s dense-cache
scan uses, so the serving engine and single-model generate can never
disagree on attention semantics.

Masking uses exact arithmetic on purpose: hard-masked scores become
``MASK_VALUE`` whose exp underflows to exactly 0.0, so a longer padded
context contributes exact zero terms and stays bit-identical to the
unpadded computation (the serve smoke asserts streamed tokens equal
unbatched `generate`).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from . import interpret_mode, kernel_active, tpu_compiler_params

MASK_VALUE = -1e30
LANES = 128

__all__ = ["ragged_paged_attention", "paged_attention_reference",
           "paged_kv_write", "paged_kv_write_bytes", "paged_kernel_route",
           "pages_in_lanes", "gather_pages", "fold_heads", "unfold_heads",
           "kernel_tileable", "MASK_VALUE", "LANES"]


# ---------------------------------------------------------------------------
# dense attention over a contiguous cached context (shared semantics)
# ---------------------------------------------------------------------------

def _dense_attend(q, kc, vc, q_pos, ctx_len=None, window=None, scale=None):
    """Masked attention of chunk queries against a contiguous KV context.

    q: (B, H, C, D); kc/vc: (B, Hkv, T, D) (Hkv divides H — GQA);
    q_pos: (B, C) absolute position of each query row; ctx_len: (B,)
    valid context length (None = causal mask alone suffices, the
    dense-cache decode case where unwritten slots are masked by q_pos).

    Exactly the decode attention semantics of the pre-refactor
    `GPTForCausalLM._token_step`, generalised to C query rows: scores in
    the activation dtype scaled by 1/sqrt(D), softmax in fp32, GQA scored
    per kv-head group without expanding the cache.
    """
    B, H, C, D = q.shape
    Hkv, T = kc.shape[1], kc.shape[2]
    if scale is None:
        scale = 1.0 / jnp.sqrt(jnp.float32(D)).astype(q.dtype)
    if Hkv == H:
        s = jnp.einsum("bhcd,bhtd->bhct", q, kc) * scale
    else:
        rep = H // Hkv
        qg = q.reshape(B, Hkv, rep, C, D).reshape(B, Hkv, rep * C, D)
        s = jnp.einsum("bgrd,bgtd->bgrt", qg, kc).reshape(
            B, Hkv, rep, C, T).reshape(B, H, C, T) * scale
    t_idx = jnp.arange(T)[None, None, None, :]
    pos = q_pos[:, None, :, None]
    mask = t_idx <= pos
    if ctx_len is not None:
        mask &= t_idx < ctx_len[:, None, None, None]
    if window is not None:
        mask &= t_idx >= pos - window
    s = jnp.where(mask, s, MASK_VALUE)
    p = jax.nn.softmax(s.astype(jnp.float32), axis=-1).astype(q.dtype)
    if Hkv == H:
        return jnp.einsum("bhct,bhtd->bhcd", p, vc)
    rep = H // Hkv
    pg = p.reshape(B, Hkv, rep, C, T).reshape(B, Hkv, rep * C, T)
    ctx = jnp.einsum("bgrt,bgtd->bgrd", pg, vc)
    return ctx.reshape(B, Hkv, rep, C, D).reshape(B, H, C, D)


# ---------------------------------------------------------------------------
# folded pools: g kv heads side by side in the lanes of one page row
# ---------------------------------------------------------------------------

def fold_heads(x, g: int, axis: int = 1):
    """Put kv heads `g` at a time side by side in the minor dim: an array
    with kv heads on `axis` and D minor becomes one with ``Hkv / g`` row
    groups on `axis` and ``g * D`` minor, kv head ``j * g + t`` in lanes
    ``t * D .. (t + 1) * D`` of group j.  Pure data movement (exact);
    ``g == 1`` returns `x` as it is."""
    if g == 1:
        return x
    x = jnp.moveaxis(x, axis, -2)
    *lead, h, d = x.shape
    return jnp.moveaxis(x.reshape(*lead, h // g, g * d), -2, axis)


def unfold_heads(x, g: int, axis: int = 1):
    """The inverse of `fold_heads`: one kv head a row again."""
    if g == 1:
        return x
    x = jnp.moveaxis(x, axis, -2)
    *lead, n, gd = x.shape
    return jnp.moveaxis(x.reshape(*lead, n * g, gd // g), -2, axis)


# ---------------------------------------------------------------------------
# page gathering (reference path + int8 dequant epilogue)
# ---------------------------------------------------------------------------

def gather_pages(pool, page_tables, scales=None, heads_per_row: int = 1):
    """Materialise each slot's logical context from the paged pool.

    pool: (Hkv, num_pages, page_size, D), or a folded pool (Hkv / g,
    num_pages, page_size, g * D) with ``heads_per_row = g``; page_tables:
    (B, max_pages) int32 (unallocated entries may point anywhere —
    callers mask by ctx_len).  Returns (B, Hkv, max_pages * page_size,
    D) either way.

    `scales` (Hkv, num_pages, page_size) dequantizes an int8 pool on the
    fly — only the gathered context is dequantized, never the whole pool.
    """
    g = pool[:, page_tables]                    # (Hkv, B, maxp, ps, D)
    Hkv, B, maxp, ps, D = g.shape
    g = unfold_heads(g.reshape(Hkv, B, maxp * ps, D).transpose(1, 0, 2, 3),
                     heads_per_row)
    if scales is not None:
        sc = scales[:, page_tables].reshape(Hkv, B, maxp * ps, 1)
        g = g.astype(jnp.float32) * sc.transpose(1, 0, 2, 3)
    return g


def paged_attention_reference(q, kpool, vpool, page_tables, ctx_lens,
                              start_pos, window=None, scale=None,
                              k_scales=None, v_scales=None, out_dtype=None,
                              heads_per_row: int = 1):
    """Dense reference: gather the page table to a contiguous context and
    run `_dense_attend`.  CPU tier-1 path and the kernel's test oracle."""
    B, H, C, D = q.shape
    q_pos = start_pos[:, None] + jnp.arange(C)[None, :]
    dt = out_dtype or q.dtype
    kc = gather_pages(kpool, page_tables, k_scales, heads_per_row).astype(dt)
    vc = gather_pages(vpool, page_tables, v_scales, heads_per_row).astype(dt)
    return _dense_attend(q.astype(dt), kc, vc, q_pos, ctx_len=ctx_lens,
                         window=window, scale=scale)


# ---------------------------------------------------------------------------
# Pallas kernel
# ---------------------------------------------------------------------------

def _make_rpa_kernel(scale, chunk, rep, window, page_in_lanes=False):
    """Build the kernel body with static shape parameters.

    One grid step is one item of the work list (`live_page_items`): a
    live (slot, logical page) pair, for ALL kv heads.  A slot's
    items are consecutive, pages ascending, so each head walks the
    slot's pages in order with flash-style online softmax in VMEM
    scratch; the scratch is reset at a slot's first item and the output
    normalised and stored at its last (both read from the list's
    neighbours).  Rows are the GQA fold — row r = (query-head-in-group
    r // chunk, chunk token r % chunk), so every row's query position is
    ``start + r % chunk`` (`rep` counts the query heads a block of K/V
    serves: a folded pool's whole row group).  All elementwise math is
    f32 (v5e has no bf16 VPU): q/k/v go to the MXU as stored and
    accumulate in f32.  With
    `page_in_lanes` the K/V blocks are (D, page_size) tiles a head (see
    `pages_in_lanes`): K^T feeds the score matmul as it lies and V^T is
    contracted over its lane dim, the form q.K^T has otherwise."""
    from jax.experimental import pallas as pl

    nt_dims = (((1,), (1,)), ((), ()))      # contract both minor dims

    def kernel(pt_ref, ctx_ref, start_ref, slot_ref, page_ref, lay_ref,
               q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr):
        i = pl.program_id(0)
        b = slot_ref[i]
        pi = page_ref[i]            # the logical page this step reads
        opens = (i == 0) | (slot_ref[jnp.maximum(i - 1, 0)] != b)
        closes = (i == pl.num_programs(0) - 1) | \
            (slot_ref[jnp.minimum(i + 1, slot_ref.shape[0] - 1)] != b)

        n_kv, rows, d = q_ref.shape
        ps = k_ref.shape[2 if page_in_lanes else 1]

        @pl.when(opens)
        def _init():
            m_scr[...] = jnp.full_like(m_scr, -jnp.inf)
            l_scr[...] = jnp.zeros_like(l_scr)
            acc_scr[...] = jnp.zeros_like(acc_scr)

        ctx = ctx_ref[b]
        start = start_ref[b]
        # row r -> query position start + r % chunk; col j -> key
        # position pi * ps + j.  (Without a GQA fold rows past the chunk
        # are sublane padding the wrapper slices off, so the modulo is
        # skipped.)  An idle slot's one item keeps nothing: exact zeros.
        r = jax.lax.broadcasted_iota(jnp.int32, (rows, ps), 0)
        c = jax.lax.broadcasted_iota(jnp.int32, (rows, ps), 1)
        qpos = start + (r if rep == 1 else jax.lax.rem(r, chunk))
        kpos = pi * ps + c
        keep = (kpos < ctx) & (kpos <= qpos)
        if window is not None:
            keep &= kpos >= qpos - window

        for h in range(n_kv):
            qb = q_ref[h]
            kb = k_ref[h]
            if page_in_lanes:
                s = jax.lax.dot(qb, kb, preferred_element_type=jnp.float32)
            else:
                s = jax.lax.dot_general(
                    qb, kb, nt_dims, preferred_element_type=jnp.float32)
            s = jnp.where(keep, s * scale, MASK_VALUE)
            m_prev = m_scr[h]
            l_prev = l_scr[h]
            m_cur = jnp.max(s, axis=1)[:, None]
            m_next = jnp.maximum(m_prev, m_cur)
            p = jnp.exp(s - _lanes(m_next, ps))
            # fully-masked rows: exp(MASK - m) must be exactly 0, not 1
            p = jnp.where(s > 0.5 * MASK_VALUE, p, 0.0)
            alpha = jnp.exp(m_prev - m_next)
            l_scr[h] = alpha * l_prev + jnp.sum(p, axis=1)[:, None]
            m_scr[h] = m_next
            vb = v_ref[h]
            pb = p.astype(vb.dtype)
            if page_in_lanes:
                pv = jax.lax.dot_general(
                    pb, vb, nt_dims, preferred_element_type=jnp.float32)
            else:
                pv = jax.lax.dot(pb, vb, preferred_element_type=jnp.float32)
            acc_scr[h] = acc_scr[h] * _lanes(alpha, d) + pv

        @pl.when(closes)
        def _store():
            for h in range(n_kv):
                l = l_scr[h]
                l_safe = jnp.where(l == 0.0, 1.0, l)
                o_ref[h] = (acc_scr[h] / _lanes(l_safe, d)).astype(
                    o_ref.dtype)

    return kernel


def _lanes(x, n):
    """Expand a lane-replicated [rows, LANES] stat to n lanes."""
    if n == LANES:
        return x
    if n < LANES:
        return x[:, :n]
    assert n % LANES == 0
    return jnp.tile(x, (1, n // LANES))


def _stack_queries(q, n_kv, g):
    """(B, H, C, D) -> (B, n_kv, g * rep * C, g * D) for a pool folded
    `g` kv heads a row: the query rows of a row group's g kv heads stacked
    (head t's rows at ``t * rep * C ..``), each in its head's lanes and
    zero in the other heads'."""
    B, H, C, D = q.shape
    qg = q.reshape(B, n_kv, g, H // (n_kv * g) * C, 1, D)
    own = jnp.eye(g, dtype=bool)[:, None, :, None]
    return jnp.where(own, qg, 0).reshape(B, n_kv, -1, g * D)


def _unstack_outputs(o, g, shape):
    """The inverse of `_stack_queries` on the kernel's output rows: each
    row keeps its own head's lanes."""
    B, H, C, D = shape
    n_kv = o.shape[1]
    o = jnp.diagonal(o.reshape(B, n_kv, g, -1, g, D), axis1=2, axis2=4)
    return jnp.moveaxis(o, -1, 2).reshape(shape)


@functools.partial(jax.jit, static_argnames=(
    "window", "scale", "page_in_lanes", "heads_per_row", "interpret"))
def _rpa_pallas(q, kpool, vpool, lay, page_tables, ctx_lens, start_pos,
                item_slot, item_page, n_items, *, window, scale,
                page_in_lanes, heads_per_row, interpret):
    """Launch the Pallas kernel over the stacked ``(n_layers, Hkv / g,
    pages, ps, g * D)`` pools (``g = heads_per_row``; shapes pre-validated
    by the wrapper).  The grid is the
    work list, its bound the traced count of live items: a page past a
    slot's context or before its window costs no step.  The layer is
    picked in the K/V index map, so XLA never materialises a per-layer
    slice of the pool to feed the custom call — and it is picked from a
    prefetched scalar (`lay`, int32 ``(1,)``), not from a Python
    constant, under a `jax.jit` of its own: the layers of a cache group
    make the same call, so a step traces this body and lowers it to
    Mosaic once a width, not once a layer (a warm set-up is Python
    tracing and lowering: PERF.md, PR 32)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, H, C, D = q.shape
    _, Hkv, _, ps, _ = kpool.shape      # Hkv: rows of g kv heads
    g = heads_per_row
    rep = H // Hkv              # query heads a row of K/V serves
    rows = rep * C

    # fold query heads onto rows: (B, H, C, D) -> (B, Hkv, rep*C, D) (a
    # folded pool: `_stack_queries`, D becomes g * D), padded to the
    # dtype's sublane tile so tiny decode batches still tile
    if g > 1:
        qf = _stack_queries(q, Hkv, g)
        D = g * D
    else:
        qf = q.reshape(B, Hkv, rows, D)
    min_rows = 8 * max(1, 4 // q.dtype.itemsize)
    pad = (-rows) % min_rows
    if pad:
        qf = jnp.pad(qf, ((0, 0), (0, 0), (0, pad), (0, 0)))
    rows_p = rows + pad

    def q_map(i, pt, ctx, st, slot, page, lay):
        return (slot[i], 0, 0, 0)

    def kv_map(i, pt, ctx, st, slot, page, lay):
        return (lay[0], 0, pt[slot[i], page[i]], 0, 0)

    kv_block = (None, Hkv, None, ps, D)
    if page_in_lanes:
        kpool, vpool = _lane_view(kpool), _lane_view(vpool)
        kv_block = (None, Hkv, None, D, ps)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=6,
        grid=(n_items,),
        in_specs=[
            pl.BlockSpec((None, Hkv, rows_p, D), q_map),
            pl.BlockSpec(kv_block, kv_map),
            pl.BlockSpec(kv_block, kv_map),
        ],
        out_specs=pl.BlockSpec((None, Hkv, rows_p, D), q_map),
        scratch_shapes=[
            pltpu.VMEM((Hkv, rows_p, LANES), jnp.float32),
            pltpu.VMEM((Hkv, rows_p, LANES), jnp.float32),
            pltpu.VMEM((Hkv, rows_p, D), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        _make_rpa_kernel(scale, C, rep, window, page_in_lanes),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Hkv, rows_p, D), q.dtype),
        compiler_params=tpu_compiler_params("arbitrary"),
        interpret=interpret,
        name="ragged_paged_attention",
    )(page_tables.astype(jnp.int32), ctx_lens.astype(jnp.int32),
      start_pos.astype(jnp.int32), item_slot, item_page, lay,
      qf, kpool, vpool)
    if g > 1:
        return _unstack_outputs(out[:, :, :rows], g, q.shape)
    return out[:, :, :rows].reshape(B, H, C, D)


def _layer_scalar(layer):
    """The layer index as the int32 ``(1,)`` array the kernels prefetch."""
    return jnp.asarray(layer, jnp.int32).reshape(1)


def kernel_tileable(page_size: int, head_dim: int) -> bool:
    """Can the kernel tile this pool?  The page is the K/V block's
    sublane dim (multiple of 8) and the score tile's lane dim; the
    lane-replicated softmax stats slice (<= LANES) or tile (multiple of
    LANES) to both the page and the head dim."""
    def lanes_ok(n):
        return n <= LANES or n % LANES == 0
    return page_size % 8 == 0 and lanes_ok(page_size) and lanes_ok(head_dim)


def paged_kernel_route(quantized: bool) -> bool:
    """Does the serving step take the Pallas kernels for this pool?  One
    predicate for the attention and for the K/V write before it: fp pools
    wherever the ``MXTPU_PALLAS`` policy makes kernels active; int8 pools
    (scale planes) and every other backend take the references."""
    return not quantized and kernel_active()


# ---------------------------------------------------------------------------
# K/V write: the chunk's new rows go into the pool inside a Pallas call
# whose pool operands are aliased to its outputs, so that on the kernel
# route the pools are operands and results of custom calls only.  An XLA
# scatter beside the attention call made XLA:TPU relayout the WHOLE pool
# before and after every layer (96% of a decode step; PERF.md, PR 28).
# ---------------------------------------------------------------------------

def pages_in_lanes(pool) -> bool:
    """Does the device keep this (concrete) pool's pages with their rows
    in lanes?  XLA:TPU's default layout of a ``(..., page_size, D)`` array
    with D < 128 <= page_size is ``{3,4,2,1,0}``: D second-minor, the
    page's rows minor, i.e. a page lies in HBM as an unpadded (D,
    page_size) tile.  A Mosaic call takes its operands row-major, so fed
    the pool as it is shaped it costs a whole-pool relayout at each end of
    the step; fed the transposed VIEW (a bitcast of that layout) it costs
    none.  The kernels take either orientation (`page_in_lanes=`); the
    engine asks here, once, which one its pool has.  A folded pool
    (`fold_heads`) has a minor dim of 128 and keeps its rows in
    sublanes; this orientation is left to a D < 128 pool the engine
    cannot fold (an odd count of kv heads a shard, a D that does not
    divide 128)."""
    try:
        order = tuple(pool.format.layout.major_to_minor)
    except AttributeError:      # nothing with a layout to ask (a tracer)
        return False
    n = len(order)
    return n >= 2 and order[-2:] == (n - 1, n - 2)


def _lane_view(pool):
    """(..., page_size, D) -> (..., D, page_size): a bitcast where
    `pages_in_lanes` holds (and back: the swap is its own inverse)."""
    return jnp.swapaxes(pool, -1, -2)


def _kv_write_tile(page_size: int, dtype, page_in_lanes: bool) -> int:
    """Page rows one grid step reads, merges and writes back.  Rows in
    sublanes: the dtype's sublane tile (8 rows of f32, 16 of bf16, which
    packs two rows a sublane) where it divides the page.  Rows in lanes:
    one 128-lane tile.  Else the whole page."""
    t = LANES if page_in_lanes else \
        8 * max(1, 4 // jnp.dtype(dtype).itemsize)
    return t if page_size % t == 0 else page_size


def _make_kv_write_kernel(tile, page_in_lanes):
    """One (slot, tile) grid step: the aligned `tile`-row piece of the
    slot's page that holds some of the chunk's rows is read for all kv
    heads, the new rows are merged in under an iota mask (never a one-row
    store: bf16 rows share a sublane) and the tile is written back."""
    from jax.experimental import pallas as pl

    def kernel(pt_ref, start_ref, nt_ref, lay_ref, kn_ref, vn_ref, kin_ref,
               vin_ref, ko_ref, vo_ref):
        b = pl.program_id(0)
        start = start_ref[b]
        nt = nt_ref[b]
        g = start // tile + pl.program_id(1)
        g_last = (start + jnp.maximum(nt, 1) - 1) // tile
        base = g * tile - start       # tile row r holds chunk row base + r
        pairs = ((kn_ref, kin_ref, ko_ref), (vn_ref, vin_ref, vo_ref))

        def merge_sublanes():
            # rows in sublanes: select each of the tile's new rows in turn
            row = jax.lax.broadcasted_iota(jnp.int32, ko_ref.shape, 1)

            def body(c, tiles):
                hit = row == c - base
                return tuple(
                    jnp.where(hit, n_ref[:, pl.ds(c, 1), :], t)
                    for (n_ref, _, _), t in zip(pairs, tiles))

            tiles = jax.lax.fori_loop(
                jnp.maximum(base, 0), jnp.minimum(base + tile, nt), body,
                tuple(i_ref[...].astype(jnp.float32)
                      for _, i_ref, _ in pairs))
            for (_, _, o_ref), t in zip(pairs, tiles):
                o_ref[...] = t.astype(o_ref.dtype)

        def merge_lanes():
            # rows in lanes: a new row is a column, and columns move on
            # the MXU.  (Hkv * D, Cp) new^T times a (Cp, tile) one-hot
            # drops each column at its lane, exactly: every product is
            # x * 1 or x * 0 (f32 at HIGHEST: three bf16 pieces of x)
            hkv, d, cp = kn_ref.shape
            c = jax.lax.broadcasted_iota(jnp.int32, (cp, tile), 0)
            lane = jax.lax.broadcasted_iota(jnp.int32, (cp, tile), 1)
            onehot = ((c == base + lane) & (c < nt)).astype(kn_ref.dtype)
            src = base + jax.lax.broadcasted_iota(
                jnp.int32, ko_ref.shape, 2)
            new_here = (src >= 0) & (src < nt)
            exact = jax.lax.Precision.HIGHEST \
                if kn_ref.dtype == jnp.float32 else None
            for n_ref, i_ref, o_ref in pairs:
                placed = jax.lax.dot(
                    n_ref[...].reshape(hkv * d, cp), onehot,
                    precision=exact, preferred_element_type=jnp.float32)
                o_ref[...] = jnp.where(
                    new_here, placed.reshape(hkv, d, tile),
                    i_ref[...].astype(jnp.float32)).astype(o_ref.dtype)

        # steps past the chunk's last tile keep that tile's block index
        # (see `pool_map`), so its buffer stays resident: touch nothing
        pl.when(g <= g_last)(merge_lanes if page_in_lanes
                             else merge_sublanes)

    return kernel


def paged_kv_write(kpool, vpool, k_new, v_new, layer, page_tables,
                   start_pos, num_tokens, null_page: int = 0,
                   page_in_lanes: bool = False, heads_per_row: int = 1):
    """Write a chunk's new K/V rows into layer `layer` of the stacked
    pools, in place: returns the (k, v) pools, aliased to the operands.

    kpool/vpool: (n_layers, Hkv, num_pages, page_size, D), or folded
    (n_layers, Hkv / g, num_pages, page_size, g * D) with ``heads_per_row
    = g`` (`fold_heads`: the new rows are folded the same way before the
    call, and a grid step moves a row tile of every row group); k_new/v_new:
    (B, Hkv, C, D); page_tables: (B, max_pages); start_pos/num_tokens:
    (B,).  Row ``c < num_tokens[b]`` of slot b lands at offset
    ``(start_pos[b] + c) % page_size`` of page ``page_tables[b,
    min((start_pos[b] + c) // page_size, max_pages - 1)]``, cast to the
    pool's dtype; rows past `num_tokens` are dropped and an idle slot
    (``num_tokens == 0``) reads and rewrites a tile of `null_page` alone.
    Every other byte of the pool is left as it was.  Page ids, starts,
    counts and the layer arrive by scalar prefetch and the layer is
    picked in the index map, as in `_rpa_pallas`, under the same kind of
    inner `jax.jit`; `page_in_lanes` as in `pages_in_lanes`."""
    g = int(heads_per_row)
    if kpool.shape[-1] != g * k_new.shape[-1]:
        raise ValueError(f"a pool of {g} kv head(s) a row holds rows of "
                         f"{g} x {k_new.shape[-1]} lanes, got "
                         f"{kpool.shape[-1]}")
    return _kv_write_pallas(
        kpool, vpool, fold_heads(k_new, g), fold_heads(v_new, g),
        _layer_scalar(layer), page_tables, start_pos, num_tokens,
        null_page=int(null_page), page_in_lanes=bool(page_in_lanes),
        interpret=interpret_mode())


def paged_kv_write_bytes(pool_shape, dtype, slots: int, chunk: int,
                         page_in_lanes: bool = False) -> int:
    """HBM bytes one `paged_kv_write` call moves, reckoned from its block
    shapes and grid: each of its ``slots x tiles`` grid steps reads and
    writes one pool block of K and one of V (a tile of page rows, every
    row group), and each slot reads its new K and V rows once.  A step of
    a chunk that straddles fewer tiles than the grid allows repeats its
    block and moves nothing: this is the grid's bound, what the chunk's
    widest placement moves.  `pool_shape` is the stacked pool's."""
    _, n_kv, _, ps, d = pool_shape
    item = jnp.dtype(dtype).itemsize
    tile = _kv_write_tile(ps, dtype, page_in_lanes)
    new = n_kv * d * (-(-chunk // 16) * 16 * item if page_in_lanes
                      else chunk * 4)
    return slots * (_kv_write_tiles(chunk, tile) * 4 * n_kv * tile * d * item
                    + 2 * new)


def _kv_write_tiles(chunk: int, tile: int) -> int:
    """Tiles a chunk of `chunk` rows can straddle (C = 16 on 16-row
    tiles: two, maybe on two pages): the write's grid steps a slot."""
    return (chunk + tile - 2) // tile + 1


@functools.partial(jax.jit, static_argnames=(
    "null_page", "page_in_lanes", "interpret"))
def _kv_write_pallas(kpool, vpool, k_new, v_new, lay, page_tables,
                     start_pos, num_tokens, *, null_page, page_in_lanes,
                     interpret):
    """`paged_kv_write`'s launch: `lay` is the layer as an int32 ``(1,)``
    array, so that every layer of a cache group shares this trace."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, Hkv, C, D = k_new.shape
    ps = kpool.shape[3]
    maxp = page_tables.shape[1]
    tile = _kv_write_tile(ps, kpool.dtype, page_in_lanes)
    # the surplus steps of a chunk that straddles fewer tiles than the
    # grid allows repeat its last block index and are skipped in the kernel
    n_tiles = _kv_write_tiles(C, tile)

    def new_map(b, j, pt, st, nt, lay):
        return (b, 0, 0, 0)

    def pool_map(b, j, pt, st, nt, lay):
        g = jnp.minimum(st[b] // tile + j,
                        (st[b] + jnp.maximum(nt[b], 1) - 1) // tile)
        page = pt[b, jnp.minimum(g * tile // ps, maxp - 1)]
        page = jnp.where(nt[b] > 0, page, null_page)
        in_page = g % (ps // tile)
        return ((lay[0], 0, page, 0, in_page) if page_in_lanes
                else (lay[0], 0, page, in_page, 0))

    if page_in_lanes:
        # the pool's dtype here already (the one-hot product is exact),
        # columns for rows, the chunk padded to a sublane tile of either
        # dtype: the matmul's contraction dim
        cp = -(-C // 16) * 16
        k_new, v_new = (
            jnp.pad(_lane_view(x.astype(kpool.dtype)),
                    ((0, 0), (0, 0), (0, 0), (0, cp - C)))
            for x in (k_new, v_new))
        kpool, vpool = _lane_view(kpool), _lane_view(vpool)
        new_spec = pl.BlockSpec((None, Hkv, D, cp), new_map)
        pool_spec = pl.BlockSpec((None, Hkv, None, D, tile), pool_map)
    else:
        # the merge runs in f32 (v5e has no bf16 VPU): f32 -> pool dtype
        # is the round-to-nearest-even of the scatter route's astype
        k_new, v_new = (x.astype(jnp.float32) for x in (k_new, v_new))
        new_spec = pl.BlockSpec((None, Hkv, C, D), new_map)
        pool_spec = pl.BlockSpec((None, Hkv, None, tile, D), pool_map)
    kpool, vpool = pl.pallas_call(
        _make_kv_write_kernel(tile, page_in_lanes),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(B, n_tiles),
            in_specs=[new_spec, new_spec, pool_spec, pool_spec],
            out_specs=[pool_spec, pool_spec]),
        out_shape=[jax.ShapeDtypeStruct(kpool.shape, kpool.dtype),
                   jax.ShapeDtypeStruct(vpool.shape, vpool.dtype)],
        # operands count the four prefetched scalars: 6, 7 are the pools
        input_output_aliases={6: 0, 7: 1},
        compiler_params=tpu_compiler_params("arbitrary", "arbitrary"),
        interpret=interpret,
        name="paged_kv_write",
    )(page_tables.astype(jnp.int32), start_pos.astype(jnp.int32),
      num_tokens.astype(jnp.int32), lay, k_new, v_new, kpool, vpool)
    if page_in_lanes:
        kpool, vpool = _lane_view(kpool), _lane_view(vpool)
    return kpool, vpool


def ragged_paged_attention(q, kpool, vpool, page_tables, ctx_lens,
                           start_pos, window=None, scale=None,
                           k_scales=None, v_scales=None, use_kernel=None,
                           layer=None, page_in_lanes=False, work_list=None,
                           heads_per_row: int = 1):
    """Mixed prefill/decode attention over a paged KV pool — one launch.

    q: (B, H, C, D) chunk queries (C = 1 for a pure-decode step);
    kpool/vpool: (Hkv, num_pages, page_size, D) — or, with ``layer=li``,
    the stacked (n_layers, Hkv, num_pages, page_size, D) pools of which
    layer `li` is read (likewise the scale planes); folded
    (`fold_heads`) where ``heads_per_row = g > 1``: (.., Hkv / g,
    num_pages, page_size, g * D); page_tables:
    (B, max_pages) int32 physical-page ids per logical page; ctx_lens:
    (B,) valid context length INCLUDING this chunk's tokens (already
    written to the pool); start_pos: (B,) absolute position of each
    slot's first chunk token.  Rows past a slot's real token count
    produce causally-valid garbage the caller must ignore.

    `window`: how many EARLIER keys a query sees besides itself.  The
    kernel visits only the pages some query of a slot's chunk can see,
    from the first query's window to the context's end (a windowed
    layer's older pages may have gone back to the allocator; their table
    entries may name any page).  `work_list`: those (slot, page) pairs
    as `serve.kv_cache.live_page_items` lists them, for a caller that
    makes several calls over the same contexts and window (the layers of
    one cache group); built here, the table's width a slot, if not given.

    fp pools run the Pallas kernel wherever the package's ``MXTPU_PALLAS``
    policy makes kernels active (a TPU backend, or ``kernel`` mode); a
    pool the kernel cannot tile is a configuration error there, not a
    slow path.  int8 pools (``k_scales``/``v_scales``) and every other
    backend run the gather-based reference.
    """
    B, H, C, D = q.shape
    if layer is None:
        kpool, vpool, layer = kpool[None], vpool[None], 0
        k_scales = None if k_scales is None else k_scales[None]
        v_scales = None if v_scales is None else v_scales[None]
    g = int(heads_per_row)
    Hkv, ps = kpool.shape[1] * g, kpool.shape[3]
    if kpool.shape[-1] != g * D:
        raise ValueError(f"a pool of {g} kv head(s) a row holds rows of "
                         f"{g} x {D} lanes, got {kpool.shape[-1]}")
    if H % Hkv:
        raise ValueError(f"query heads ({H}) must be a multiple of pool "
                         f"kv heads ({Hkv})")
    quantized = k_scales is not None or v_scales is not None
    if use_kernel is None:
        use_kernel = paged_kernel_route(quantized)
    if use_kernel:
        if quantized:
            raise ValueError("the Pallas paged-attention kernel takes an "
                             "fp pool; int8 pools use the reference path")
        if not kernel_tileable(ps, g * D):
            raise ValueError(
                f"paged-attention kernel cannot tile page_size={ps}, "
                f"head_dim={D}: the page size must be a multiple of 8 and "
                f"both must be <= {LANES} or a multiple of {LANES} "
                "(ServeConfig.page_size / MXTPU_SERVE_PAGE_SIZE)")
        if work_list is None:
            from ...serve.kv_cache import live_page_items
            work_list = live_page_items(ctx_lens, start_pos, window, ps,
                                        page_tables.shape[1])
        return _rpa_pallas(
            q, kpool, vpool, _layer_scalar(layer), page_tables, ctx_lens,
            start_pos, *work_list, window=window,
            scale=float(scale) if scale is not None else 1.0 / math.sqrt(D),
            page_in_lanes=bool(page_in_lanes), heads_per_row=g,
            interpret=interpret_mode())
    return paged_attention_reference(
        q, kpool[layer], vpool[layer], page_tables, ctx_lens, start_pos,
        window=window, scale=scale,
        k_scales=None if k_scales is None else k_scales[layer],
        v_scales=None if v_scales is None else v_scales[layer],
        heads_per_row=g)


# ---------------------------------------------------------------------------
# autotune registration: the launch itself has no free block parameter
# (a grid step is one page, all its kv heads), so the knob is the POOL's page
# size — `tune("paged_attention", (slots, heads, kv_heads, head_dim,
# ctx))` times a serving-shaped decode step per candidate and
# `serve.ServeConfig` picks the persisted winner up when
# MXTPU_SERVE_PAGE_SIZE is unset (docs/perf.md).
# ---------------------------------------------------------------------------

def recommended_page_size(default: int = 16) -> int:
    """The tuned page size for this device (or `default`).  The page
    size is a per-DEVICE knob: any persisted `tune("paged_attention",
    ...)` result for this device kind applies, whatever serving shape
    it was searched under."""
    from . import autotune as _at
    cfg = _at.lookup_any("paged_attention")
    return int(cfg.page_size) if cfg is not None else default


def _at_candidates(shapes, dtype):
    from . import autotune as _at
    return [_at.BlockConfig(page_size=ps) for ps in (16, 32, 64, 128)]


def _at_roofline(config, shapes, dtype):
    b, h, hkv, d, ctx = (list(shapes) + [8, 8, 8, 64, 512])[:5]
    ps = config.page_size
    pages = max(1, -(-ctx // ps))
    # each slot streams ceil(ctx/ps) pages of K and V, all kv heads of a
    # page one grid step; bigger pages waste tail bandwidth but cost
    # fewer steps
    return {"flops": 4.0 * b * h * ctx * d,
            "bytes": b * hkv * pages * ps * d * 2.0 * 4,
            "steps": float(b * pages)}


def _at_build(config, shapes, dtype):
    import numpy as _np
    b, h, hkv, d, ctx = (list(shapes) + [8, 8, 8, 64, 512])[:5]
    ps = config.page_size
    maxp = max(1, -(-ctx // ps))
    n_pages = b * maxp + 1
    rng = _np.random.RandomState(0)
    dt = jnp.bfloat16 if "16" in str(dtype) else jnp.float32
    q = jnp.asarray(rng.randn(b, h, 1, d), dt)
    kpool = jnp.asarray(rng.randn(hkv, n_pages, ps, d), dt)
    vpool = jnp.asarray(rng.randn(hkv, n_pages, ps, d), dt)
    pt = jnp.asarray(
        1 + _np.arange(b * maxp).reshape(b, maxp), jnp.int32)
    ctx_lens = jnp.full((b,), ctx, jnp.int32)
    start = ctx_lens - 1
    fn = jax.jit(functools.partial(ragged_paged_attention))

    def thunk():
        return fn(q, kpool, vpool, pt, ctx_lens, start)

    return thunk


def _at_register():
    from . import autotune as _at
    _at.register_tunable("paged_attention", _at_candidates, _at_build,
                         _at_roofline)


_at_register()
