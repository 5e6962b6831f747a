"""Paged KV cache: host-side page-table allocator + device page pools.

The serving analogue of the reference's memory pool (`src/storage/`): all
KV memory for all concurrent requests lives in ONE preallocated device pool
of fixed-size pages, `(n_layers, Hkv, num_pages, page_size, D)` per tensor
(head-major: one kv head's page is a contiguous `(page_size, D)` tile, the
block the paged-attention kernel DMAs).  **Where D is under the 128 lanes
and divides them**, ``g = 128 / D`` kv heads share a page row instead:
`(n_layers, Hkv / g, num_pages, page_size, 128)`, kv head ``j * g + t`` in
lanes ``t * D ..`` of row group j (`kv_heads_per_row` decides from the
shapes alone; `KVPools.heads_per_row` records g a group).  The bytes are
the same; what changes is the device's layout: a v5e keeps an unfolded
``(page_size, 64)`` page with its rows in lanes, so one new token is a
lane column through the whole page and the K/V write moved the whole page
for it, where a 128-lane row keeps the rows in sublanes and the write
moves one 16-row tile (PERF.md section 6).  The page axis stays axis 2
whatever the fold, so page copies, the prefix cache and the handoff's
page export/install index pages alike; the handoff's payload is always
unfolded (`KVPools.unfold`), so engines that fold differently (a tp split
that would cut a row group) still exchange pages.
A sequence owns an ordered list of physical pages (its *page table*);
logical token position ``p`` lives in page ``table[p // page_size]`` at
offset ``p % page_size``.  Admission, growth, and eviction are pure
host-side free-list operations — the device arrays never reallocate, which
is what lets the engine compile ONE step program and donate the pool
buffers through it (in-place updates, zero per-step allocation).

Page 0 is reserved as the **null page**: masked writes (padded chunk rows,
inactive slots) are scattered there and no allocation ever returns it, so
the jitted step needs no host-side branching on raggedness.

The step writes a chunk's new K/V where it attends (`make_paged_kv_fn`):
on the kernel route by the Pallas call `paged_kv_write`, pools aliased in
and out, so that the donated pools pass through custom calls alone and
are never relayouted; everywhere else (int8 pools, the CPU,
``MXTPU_PALLAS`` off) by an XLA scatter, `scatter_kv_write`.

**Cache groups** (docs/serving.md "Layers and cache groups"): a model
whose layers are not all of one kind has one pool a kind.  What a group
is lives here and nowhere else: one `CacheGroup` record a group (its
name, layers, window, pool size, walk and `PageAllocator`), all of them
built by `plan_cache_groups` from the model's `DecodeSpec` and the
`ServeConfig`; the engine and the scheduler loop over that list.  The
whole-context group (label `FULL`, arrays ``k`` / ``v``) comes first and
keeps a sequence's whole context, as above.  Every other group (arrays
``k_<name>`` / ``v_<name>``, a page table and an allocator of its own)
holds layers whose queries see a window only: a page that lies wholly
before ``cursor - window`` goes back to its allocator
(`window_first_page`), and its table entry becomes the null page.  A
model of one kind of layer (GPT-2) has a list of one.  What a slot holds
in a group is a `PageRun`: a contiguous run of logical pages that grows
at the back, is trimmed at the back, and in a windowed group is let go at
the front.  In any group the attention kernel visits only the (slot,
page) pairs some query of the step can see (`live_page_range`): one work
list a group and step (`live_page_items`), of at most the group's `walk`
pages a slot (`window_walk_pages` in a windowed group, the table's width
in the whole-context one).  Sharing (the prefix cache, copy-on-write
forks, the prefill-to-decode handoff) addresses the whole-context group
only: a page may be shared only while every owner still holds it, and a
windowed group has let the prompt's pages go.

``kv_dtype="int8"`` stores the pool quantized (symmetric per-token-per-head
int8 via `contrib/quantization.quantize_kv`) at ~4x less HBM per token;
attention dequantizes only the gathered context.  An int8 pool is never
folded: its scale planes hold one scale a (kv head, token), and it takes
the references, which read any layout.

**Shared pages & copy-on-write** (docs/serving.md "Speculative decoding &
prefix caching"): every allocated page carries a reference count.  A page
with refcount > 1 is read-only — `PageAllocator.share` adds owners (the
cross-request prefix cache attaching cached prompt blocks to a new
sequence), and a writer must `fork` first: the fork moves one reference
onto a fresh physical page, the caller device-copies the contents, and
only then scatters into it.  `free` is a decref; the physical page
returns to the free list only when its last owner lets go — which is what
lets N concurrent requests attend over ONE copy of a shared prompt prefix
while each still owns its divergent suffix exclusively.  `PrefixIndex`
maps token-block prefixes to those shared read-only page runs, with LRU
eviction of refcount-1 entries under pool pressure.
"""
from __future__ import annotations

import itertools
import math
import threading
from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as onp

from ..base import MXNetError

__all__ = ["PageAllocator", "PrefixIndex", "KVPools", "make_paged_kv_fn",
           "NULL_PAGE", "FULL", "CacheGroup", "PageRun", "plan_cache_groups",
           "window_first_page", "window_walk_pages", "live_page_range",
           "live_page_items", "kv_heads_per_row"]

NULL_PAGE = 0
#: the `LayerSpec.cache_group` label of the group that keeps a sequence's
#: whole context; a layer under any other label lives in a windowed group
FULL = "full"
#: lanes of a TPU vreg: a pool row of exactly this many keeps its page's
#: rows in sublanes in XLA:TPU's default layout
LANES = 128


def kv_heads_per_row(n_kv_heads: int, head_dim: int, tp: int = 1,
                     quantized: bool = False) -> int:
    """How many kv heads share a pool row: ``LANES / head_dim`` where the
    head dim is under the lanes and divides them and the kv heads of one
    tp shard divide by that (a row group is never cut by a shard); 1
    otherwise — a head dim of 128 or more, one kv head a shard (MQA), an
    int8 pool (its scale planes are a head's)."""
    if quantized or head_dim >= LANES or LANES % head_dim:
        return 1
    g = LANES // head_dim
    return g if (n_kv_heads // max(1, tp)) % g == 0 else 1


def window_first_page(cursor, window: int, page_size: int):
    """First logical page a query at position >= `cursor` can still see
    in a layer whose queries see `window` earlier keys: every page before
    it lies wholly before ``cursor - window``.  Works on ints and on
    arrays (the host's release rule and a slot's first page in the
    kernel's work list are this one expression)."""
    return (cursor - window) // page_size * (cursor > window)


def window_walk_pages(window: int, chunk: int, page_size: int) -> int:
    """Pages a chunk of `chunk` queries can see in such a layer: keys
    ``start - window .. start + chunk - 1`` span at most this many pages
    wherever `start` falls (``window / page + 2`` for a 4096-key window,
    pages of 128 and chunks of 16)."""
    return -(-(window + chunk) // page_size) + 1


def live_page_range(ctx, start, window: Optional[int], page_size: int,
                    walk: int):
    """``(first, count)``: the closed run of logical pages some query of
    a slot's chunk can see.  `ctx` is the slot's context length with this
    chunk's tokens in, `start` the position of the chunk's first query,
    `window` the layer's (None: the whole context).  The run goes from
    `window_first_page` of the first query (0 without a window) to the
    page that holds key ``ctx - 1``; it is never empty (an idle slot,
    ``ctx == 0``, gets its first page, which the kernel masks whole, so
    that every slot's output is written) and never longer than `walk`,
    the static bound its caller sized the work list for.  Works on ints
    and on arrays: the paged-attention kernel's work list
    (`live_page_items`) and the scheduler's ``attn_items_*`` counters
    are this one expression."""
    first = 0 * start if window is None \
        else window_first_page(start, window, page_size)
    count = (ctx + page_size - 1) // page_size - first
    count = count + (1 - count) * (count < 1)
    return first, count - (count - walk) * (count > walk)


def live_page_items(ctx_lens, start_pos, window: Optional[int],
                    page_size: int, walk: int):
    """The paged-attention kernel's work list for one cache group:
    ``(item_slot, item_page, n_items)``, the live (slot, logical page)
    pairs of `live_page_range` flattened slot by slot, pages ascending.
    The two int32 arrays have the static length ``slots x walk``; the
    traced `n_items` says how many are real (at least one a slot), and
    the entries past it repeat the last real one (the kernel's grid ends
    at `n_items`: they are never visited).  Device arrays in, device
    arrays out: built inside the jitted step, once a group."""
    first, count = live_page_range(ctx_lens, start_pos, window, page_size,
                                   walk)
    slots = ctx_lens.shape[0]
    ends = jnp.cumsum(count)
    n_items = ends[-1]
    i = jnp.minimum(jnp.arange(slots * walk), n_items - 1)
    # dense (items, slots) compares, not a search and two gathers: those
    # cost a GPT-2 decode step 0.2 ms of its 3.6 (PR 31's chip run)
    before = i[:, None] >= ends[None, :]      # slots that end before item i
    slot = before.sum(1)
    own = slot[:, None] == jnp.arange(slots)[None, :]
    page = i - (before * count[None, :]).sum(1) + (own * first[None, :]).sum(1)
    return (slot.astype(jnp.int32), page.astype(jnp.int32),
            n_items.astype(jnp.int32))


class PageAllocator:
    """Free-list allocator over the physical pages of a pool, with
    per-page reference counts for cross-request sharing.

    Thread-safe (the scheduler may admit from a submit thread while the
    step loop extends sequences).  Pages are recycled LIFO — a just-freed
    page is the next handed out, keeping the hot working set of physical
    pages small and cache-friendly.
    """

    def __init__(self, num_pages: int, page_size: int):
        if num_pages < 2:
            raise MXNetError(
                f"KV pool needs >= 2 pages (page 0 is the reserved null "
                f"page), got {num_pages}")
        self.num_pages = int(num_pages)
        self.page_size = int(page_size)
        # LIFO free list; page 0 (null) is never allocatable
        self._free: List[int] = list(range(num_pages - 1, 0, -1))
        # page id -> owner count for every allocated page (alloc = 1;
        # share increfs; free decrefs and recycles at zero)
        self._ref: Dict[int, int] = {}
        self._lock = threading.Lock()

    @property
    def free_pages(self) -> int:
        with self._lock:
            return len(self._free)

    @property
    def total_pages(self) -> int:
        """Allocatable pages (the null page is not)."""
        return self.num_pages - 1

    def occupancy(self) -> float:
        """Fraction of allocatable pages currently owned by sequences."""
        return 1.0 - self.free_pages / max(1, self.total_pages)

    def pages_for(self, tokens: int) -> int:
        return max(1, math.ceil(tokens / self.page_size))

    def can_alloc(self, n: int) -> bool:
        with self._lock:
            return len(self._free) >= n

    def alloc(self, n: int) -> Optional[List[int]]:
        """Take `n` pages, or None (backpressure — caller defers/evicts).
        All-or-nothing: a partial grab under contention is never held."""
        with self._lock:
            if len(self._free) < n:
                return None
            taken = [self._free.pop() for _ in range(n)]
            for p in taken:
                self._ref[p] = 1
        return taken

    def free(self, pages: List[int]) -> None:
        """Release one reference per page; a page returns to the free
        list only when its LAST owner lets go (shared prefix pages stay
        resident for their other owners)."""
        with self._lock:
            for p in pages:
                if p == NULL_PAGE:
                    raise MXNetError("attempt to free the null page")
                ref = self._ref.get(p)
                if ref is None:
                    raise MXNetError(f"double free of page {p}")
                if ref > 1:
                    self._ref[p] = ref - 1
                else:
                    del self._ref[p]
                    self._free.append(p)

    # -- sharing / copy-on-write (docs/serving.md) ---------------------
    def refcount(self, page: int) -> int:
        """Current owner count of `page` (0 = free/never allocated)."""
        with self._lock:
            return self._ref.get(page, 0)

    def shared_pages(self) -> int:
        """Physical pages with more than one owner (the
        ``serve_kv_pages_shared`` gauge)."""
        with self._lock:
            return sum(1 for r in self._ref.values() if r > 1)

    def share(self, pages: Sequence[int]) -> None:
        """Add one owner to each page — attaching cached prefix pages to
        a new sequence (or registering them in a `PrefixIndex`).  Only
        allocated pages can be shared."""
        with self._lock:
            for p in pages:
                ref = self._ref.get(p)
                if ref is None:
                    raise MXNetError(
                        f"share of unallocated page {p} (free or never "
                        f"handed out)")
                self._ref[p] = ref + 1

    def fork(self, page: int) -> Optional[Tuple[int, bool]]:
        """Copy-on-write: make `page` exclusively writable for ONE of
        its owners.  Exclusive already (refcount 1) returns ``(page,
        False)`` — write in place.  Shared returns ``(new_page, True)``
        after moving one reference onto a fresh page: the CALLER must
        device-copy the contents ``page -> new_page`` before writing
        (the allocator is host-side bookkeeping only).  Returns None
        when the pool has no free page for the fork — the caller applies
        its pressure policy (prefix-cache eviction, slot preemption) and
        retries."""
        with self._lock:
            ref = self._ref.get(page)
            if ref is None:
                raise MXNetError(f"fork of unallocated page {page}")
            if ref == 1:
                return page, False
            if not self._free:
                return None
            new = self._free.pop()
            self._ref[new] = 1
            self._ref[page] = ref - 1
        return new, True


def _pool_names(group: str) -> Tuple[str, str]:
    """The K and V arrays of the cache group labelled `group`."""
    return ("k", "v") if group == FULL else ("k_" + group, "v_" + group)


@dataclass(frozen=True)
class CacheGroup:
    """One cache group of a model: the layers whose K/V share a pool, and
    what a slot keeps of them.  The one record the pools, the step, the
    scheduler and the stats are spelled from (`plan_cache_groups` builds
    the list; the whole-context group is its first entry).

    ``name`` is the layers' `LayerSpec.cache_group` label: it names the
    pool arrays (`pool_names`), the step's tags ``kv_pages_<name>`` /
    ``attn_items_<name>`` and the stats.  ``layers``: the model's layer
    indices in the group, in pool order.  ``window``: None where a slot
    keeps its whole context, else the pages wholly before ``cursor -
    window`` go back (`PageRun.release_before`).  ``num_pages``: the
    pool's physical pages, the null page among them.  ``walk``: the
    static bound on the live pages a slot's chunk can see, what the
    attention kernel's work list is sized for: the table's width for the
    whole-context group, `window_walk_pages` for a windowed one."""
    name: str
    layers: Tuple[int, ...]
    window: Optional[int]
    num_pages: int
    walk: int
    allocator: PageAllocator = field(compare=False, repr=False)

    @property
    def pool_names(self) -> Tuple[str, str]:
        """The group's K and V arrays in `KVPools.arrays`."""
        return _pool_names(self.name)


def plan_cache_groups(spec, config, max_pages: int, chunk: int,
                      bonus_pages: int = 0,
                      quantized: bool = False) -> Tuple[CacheGroup, ...]:
    """The cache groups of a model under a serving configuration, the
    whole-context group first: the ONE place that decides which groups
    there are and, for each, its layers, window, pool size, walk and
    allocator.

    `spec` is the model's `DecodeSpec` (the groups are what its layers'
    ``cache_group`` labels say), `config` the `ServeConfig`, `max_pages`
    the width of a slot's page table (``ceil(max_len / page_size)``),
    `chunk` the widest step the engine compiles, `bonus_pages` what
    quantized weights paid for (they go to the whole-context group).

    The whole-context pool is sized so that every slot can hold a
    full-length sequence, plus the null page (an explicit ``num_pages``
    wins).  A windowed pool is sized so that every slot can hold what a
    chunk of the widest step can see (`window_walk_pages`), plus the null
    page; an explicit ``num_pages`` caps it too.  A windowed group needs
    one window for all its layers, an fp pool, and ``role='both'``: the
    prefill-to-decode handoff moves the whole-context group's pages
    only."""
    names = spec.cache_groups()
    if FULL not in names:
        # admission and the request caps count whole-context pages: a
        # model of window layers alone still has the (empty) group
        names = (FULL,) + names
    groups = []
    for name in names:
        layers = spec.group_layers(name)
        window, walk, bonus = None, max_pages, bonus_pages
        if name != FULL:
            windows = {spec.layers[i].window for i in layers}
            if len(windows) != 1 or None in windows:
                raise MXNetError(
                    f"the {name} cache group needs ONE window for all its "
                    f"layers, got {sorted(map(str, windows))}")
            if config.role != "both":
                raise MXNetError(
                    f"a model with a windowed cache group ({name}) serves "
                    f"with role='both': the prefill->decode handoff moves "
                    f"the whole-context group's pages only")
            if quantized:
                raise MXNetError(
                    f"an int8 KV pool has no windowed group ({name}): "
                    f"serve a model with window layers with an fp kv_dtype")
            window, bonus = windows.pop(), 0
            walk = min(max_pages,
                       window_walk_pages(window, chunk, config.page_size))
        pages = config.max_slots * walk + 1 + bonus
        num_pages = config.num_pages or pages
        if window is not None:
            num_pages = min(num_pages, pages)
        groups.append(CacheGroup(
            name, layers, window, num_pages, walk,
            PageAllocator(num_pages, config.page_size)))
    return tuple(groups)


class PageRun:
    """What ONE slot holds in ONE cache group: a contiguous run of
    logical pages, ``first .. first + len(pages) - 1``, and the table row
    the step reads (the null page wherever no page is held).  Logical
    page ``p`` holds tokens ``p * page_size ..``, in every group alike.

    The run grows at the back (`grow`), is cut at the back when rejected
    drafts roll the cursor back (`trim`), loses pages at the front where
    the group has a window (`release_before`), and goes back whole when
    the slot leaves (`free`).  `pages` and `table` are changed in place
    and never rebound: the scheduler's sharing code holds the
    whole-context group's two by reference."""

    __slots__ = ("group", "first", "pages", "table")

    def __init__(self, group: CacheGroup, max_pages: int):
        self.group = group
        self.first = 0
        self.pages: List[int] = []
        self.table = onp.zeros(max_pages, onp.int32)    # NULL_PAGE fill

    def grow(self, cursor: int, need: int, take: Callable, who) -> bool:
        """Hold every logical page from the first a query at `cursor`
        can see up to page ``need - 1``.  ``take(who, group)`` hands
        over one physical page of the group's allocator for the slot
        `who`, or None when none can be had: then the run keeps what it
        got and this returns False."""
        pages = self.pages
        if not pages and self.group.window is not None:
            self.first = int(window_first_page(
                cursor, self.group.window, self.group.allocator.page_size))
        for at in range(self.first + len(pages), need):
            page = take(who, self.group)
            if page is None:
                return False
            self.table[at] = page
            pages.append(page)
        return True

    def release_before(self, cursor: int) -> int:
        """Give back the pages that lie wholly before ``cursor -
        window``: no query from `cursor` on can see a key in them.
        Returns how many went (0 in a group that keeps the whole
        context)."""
        window = self.group.window
        if window is None:
            return 0
        alloc = self.group.allocator
        n = min(len(self.pages), int(window_first_page(
            cursor, window, alloc.page_size)) - self.first)
        if n <= 0:
            return 0
        alloc.free(self.pages[:n])
        del self.pages[:n]
        self.table[self.first:self.first + n] = NULL_PAGE
        self.first += n
        return n

    def trim(self, keep: int) -> None:
        """Give back the pages at logical index `keep` and beyond: the
        cursor rolled back past rejected drafts (those pages are freshly
        allocated by construction, so they go straight to the free
        list)."""
        cut = max(0, keep - self.first)
        extra = self.pages[cut:]
        if extra:
            del self.pages[cut:]
            self.table[self.first + cut:self.first + cut + len(extra)] = \
                NULL_PAGE
            self.group.allocator.free(extra)

    def free(self) -> None:
        """Give every page back: the slot leaves."""
        self.trim(0)


class _PrefixEntry:
    """One cached token block: a single shared read-only page holding
    ``n_tokens`` (< page_size for a terminal partial block) of KV."""

    __slots__ = ("key", "page", "tokens", "n_tokens", "parent", "stamp")

    def __init__(self, key, page: int, tokens: tuple, n_tokens: int,
                 parent, stamp: int):
        self.key = key
        self.page = page
        self.tokens = tokens
        self.n_tokens = n_tokens
        self.parent = parent
        self.stamp = stamp


class PrefixIndex:
    """Cross-request prompt-prefix cache: token-block prefixes -> shared
    read-only KV page runs (docs/serving.md "Speculative decoding &
    prefix caching").

    Entries are chained per page-sized block and keyed by EXACT token
    content — ``key = (parent_key, block_tokens)`` — so a hit guarantees
    the cached KV was computed from the same tokens (no hash-collision
    risk).  Each entry owns one allocator reference on its page;
    `lookup` walks the chain for a new prompt and adds a reference per
    matched page for the requesting sequence (the scheduler then skips
    those prefill chunks entirely).  A prompt's trailing partial block
    is cached too (at most one per parent): attaching it means the new
    sequence's first write lands INSIDE a shared page, which is exactly
    the copy-on-write fork case.

    Under pool pressure `evict_pages` drops least-recently-used entries
    whose page has refcount 1 (sole owner = this index) — a page any
    live sequence still reads is never reclaimed.  Thread-safe: the
    router probes `longest_match` from submit threads while the step
    loop inserts/attaches."""

    _ROOT = ()

    def __init__(self, allocator: PageAllocator, page_size: int):
        self.allocator = allocator
        self.page_size = int(page_size)
        self._entries: Dict[tuple, _PrefixEntry] = {}
        # parent key -> the single terminal partial-block entry
        self._partials: Dict[tuple, _PrefixEntry] = {}
        # parent key -> number of child entries (full blocks + partial);
        # only childless entries are evictable (an orphaned child would
        # be unreachable but still pin its page)
        self._children: Dict[tuple, int] = {}
        self._stamp = itertools.count()
        self._lock = threading.Lock()
        self.hits = 0
        self.hit_tokens = 0
        self.insertions = 0
        self.evictions = 0

    def __len__(self) -> int:
        with self._lock:
            return len(self._entries) + len(self._partials)

    # ------------------------------------------------------------------
    def _walk(self, tokens: Sequence[int]):
        """Longest cached chain for `tokens`: yields matched entries in
        order (full blocks, then at most one terminal partial).  Caller
        holds the lock."""
        ps = self.page_size
        parent = self._ROOT
        n = 0
        out = []
        while n + ps <= len(tokens):
            block = tuple(int(t) for t in tokens[n:n + ps])
            e = self._entries.get((parent, block))
            if e is None:
                break
            out.append(e)
            parent = e.key
            n += ps
        part = self._partials.get(parent)
        if part is not None and part.n_tokens <= len(tokens) - n and \
                tuple(int(t) for t in tokens[n:n + part.n_tokens]) \
                == part.tokens:
            out.append(part)
        return out

    def lookup(self, tokens: Sequence[int]) -> Tuple[List[int], int]:
        """Longest cached prefix of `tokens`: returns ``(pages,
        n_tokens)`` with one allocator reference added per returned page
        FOR THE CALLER (released through the normal `free` path when the
        sequence lets go).  ``([], 0)`` on miss."""
        with self._lock:
            matched = self._walk(tokens)
            if not matched:
                return [], 0
            pages = [e.page for e in matched]
            n = sum(e.n_tokens for e in matched)
            self.allocator.share(pages)
            for e in matched:
                e.stamp = next(self._stamp)
            self.hits += 1
            self.hit_tokens += n
        return pages, n

    def longest_match(self, tokens: Sequence[int]) -> int:
        """Tokens a `lookup` would attach — read-only (no references
        taken, no LRU refresh).  The router's prefix-affinity score."""
        with self._lock:
            return sum(e.n_tokens for e in self._walk(tokens))

    # ------------------------------------------------------------------
    def insert(self, tokens: Sequence[int], pages: Sequence[int]) -> int:
        """Register a just-prefilled prompt: ``pages[i]`` holds tokens
        ``[i*ps, (i+1)*ps)`` of `tokens` (the owning slot's page table
        prefix).  Creates entries for blocks not yet cached (one shared
        reference each); existing entries are LRU-refreshed, never
        replaced (first writer wins — both pages hold identical KV by
        construction).  Returns the number of NEW entries."""
        tokens = [int(t) for t in tokens]
        ps = self.page_size
        need = math.ceil(len(tokens) / ps) if tokens else 0
        if len(pages) < need:
            raise MXNetError(
                f"prefix insert: {len(tokens)} tokens span {need} pages "
                f"but only {len(pages)} supplied")
        created = 0
        with self._lock:
            parent = self._ROOT
            for bi in range(len(tokens) // ps):
                block = tuple(tokens[bi * ps:(bi + 1) * ps])
                key = (parent, block)
                e = self._entries.get(key)
                if e is None:
                    self.allocator.share([pages[bi]])
                    e = _PrefixEntry(key, pages[bi], block, ps, parent,
                                     next(self._stamp))
                    self._entries[key] = e
                    self._children[parent] = \
                        self._children.get(parent, 0) + 1
                    self.insertions += 1
                    created += 1
                else:
                    e.stamp = next(self._stamp)
                parent = key
            r = len(tokens) % ps
            if r:
                blk = tuple(tokens[-r:])
                part = self._partials.get(parent)
                if part is not None and part.tokens == blk:
                    part.stamp = next(self._stamp)
                elif part is None or (len(part.tokens) < r
                                      and blk[:len(part.tokens)]
                                      == part.tokens):
                    # no partial yet, or the new one strictly extends it
                    if part is not None:
                        self._drop(part)
                    self.allocator.share([pages[len(tokens) // ps]])
                    self._partials[parent] = _PrefixEntry(
                        ("partial", parent), pages[len(tokens) // ps],
                        blk, r, parent, next(self._stamp))
                    self._children[parent] = \
                        self._children.get(parent, 0) + 1
                    self.insertions += 1
                    created += 1
        return created

    # ------------------------------------------------------------------
    def _drop(self, e: _PrefixEntry) -> None:
        """Remove one entry and release its page reference (lock held)."""
        if e.key[0] == "partial":
            self._partials.pop(e.parent, None)
        else:
            self._entries.pop(e.key, None)
        left = self._children.get(e.parent, 0) - 1
        if left > 0:
            self._children[e.parent] = left
        else:
            self._children.pop(e.parent, None)
        self.allocator.free([e.page])
        self.evictions += 1

    def evict_pages(self, n: int) -> int:
        """Pool pressure: reclaim up to `n` pages by dropping LRU
        childless entries whose page refcount is 1 (sole owner = this
        index).  A page a live sequence still shares is NEVER evicted.
        Returns pages actually freed."""
        freed = 0
        with self._lock:
            while freed < n:
                cands = [
                    e for e in list(self._entries.values())
                    + list(self._partials.values())
                    if self._children.get(e.key, 0) == 0
                    and self.allocator.refcount(e.page) == 1]
                if not cands:
                    break
                victim = min(cands, key=lambda e: e.stamp)
                self._drop(victim)
                freed += 1
        return freed

    def clear(self) -> int:
        """Drop every entry (engine teardown / tests); returns entries
        released.  Shared pages simply lose the index's reference."""
        with self._lock:
            all_e = list(self._entries.values()) \
                + list(self._partials.values())
            for e in all_e:
                self.allocator.free([e.page])
            self._entries.clear()
            self._partials.clear()
            self._children.clear()
            return len(all_e)

    def stats(self) -> dict:
        with self._lock:
            return {"entries": len(self._entries) + len(self._partials),
                    "hits": self.hits, "hit_tokens": self.hit_tokens,
                    "insertions": self.insertions,
                    "evictions": self.evictions}


class KVPools:
    """Device-side paged K/V storage for every layer, one K and one V
    array a cache group (`CacheGroup.pool_names`), plus scale planes when
    quantized:

    - ``k``/``v``: (the whole-context group's layers, Hkv / g, its pages,
      page_size, g * D) `dtype`, ``g = heads_per_row[FULL]``
    - ``k_scale``/``v_scale``: (layers, Hkv, pages, page_size) float32
      (int8 pools only, never folded; one symmetric scale per stored
      vector)
    - ``k_<name>``/``v_<name>``: (the group's layers, Hkv / g, the
      group's pages, page_size, g * D), one pair a windowed group

    ``heads_per_row``: the g of each group, by name (`kv_heads_per_row`,
    from the shapes and the tp degree the pools are sharded over).  The
    arrays are exposed as a flat tuple (`as_tuple`) so the engine can
    pass them through a jitted step with ``donate_argnums`` and rebind the
    donated outputs (`replace`).
    """

    def __init__(self, arrays: Dict[str, jax.Array],
                 groups: Tuple[CacheGroup, ...], page_size: int,
                 n_kv_heads: int, head_dim: int, quantized: bool,
                 heads_per_row: Dict[str, int]):
        self.arrays = arrays
        self.groups = groups
        self.page_size = page_size
        self.n_kv_heads = n_kv_heads
        self.head_dim = head_dim
        self.quantized = quantized
        self.heads_per_row = heads_per_row

    @classmethod
    def create(cls, groups: Sequence[CacheGroup], page_size: int,
               n_kv_heads: int, head_dim: int,
               dtype="float32", tp: int = 1) -> "KVPools":
        quantized = str(dtype) == "int8"
        store_dt = jnp.int8 if quantized else jnp.dtype(dtype)
        arrays, fold = {}, {}
        for g in groups:
            fold[g.name] = n = kv_heads_per_row(n_kv_heads, head_dim, tp,
                                                quantized)
            shape = (len(g.layers), n_kv_heads // n, g.num_pages, page_size,
                     n * head_dim)
            for name in g.pool_names:
                arrays[name] = jnp.zeros(shape, store_dt)
                if quantized:
                    arrays[name + "_scale"] = jnp.zeros(shape[:-1],
                                                        jnp.float32)
        return cls(arrays, tuple(groups), page_size, n_kv_heads, head_dim,
                   quantized, fold)

    @property
    def num_pages(self) -> int:
        """Physical pages of the whole-context group's pool."""
        return self.groups[0].num_pages

    @property
    def names(self):
        return tuple(sorted(self.arrays))

    def as_tuple(self):
        return tuple(self.arrays[n] for n in self.names)

    def replace(self, values) -> "KVPools":
        """Rebind to the donated step outputs (same metadata)."""
        return KVPools(dict(zip(self.names, values)), self.groups,
                       self.page_size, self.n_kv_heads, self.head_dim,
                       self.quantized, self.heads_per_row)

    @property
    def full_names(self):
        """The whole-context group's arrays: what a page id of its
        allocator indexes (page copies, handoff export/install)."""
        rest = {n for g in self.groups[1:] for n in g.pool_names}
        return tuple(n for n in self.names if n not in rest)

    def _folded(self, name: str) -> int:
        """g of array `name` (1 for a scale plane, never folded)."""
        for g in self.groups:
            if name in g.pool_names:
                return self.heads_per_row[g.name]
        return 1

    def unfold(self, name: str, x):
        """Pages of array `name` (page axis 2) as one kv head a row,
        whatever this engine's fold: the handoff's payload."""
        from ..ops.pallas.paged_attention import unfold_heads
        return unfold_heads(x, self._folded(name))

    def fold(self, name: str, x):
        """`unfold`'s payload in this engine's layout of array `name`."""
        from ..ops.pallas.paged_attention import fold_heads
        return fold_heads(x, self._folded(name))

    def pages_in_lanes(self) -> bool:
        """Does the device keep a page's rows in lanes (a (D, page_size)
        tile)?  Asked of the K pool's own layout; the step's kernels take
        the pool in that orientation (`ops.pallas.paged_attention`)."""
        from ..ops.pallas.paged_attention import pages_in_lanes
        return pages_in_lanes(self.arrays["k"])

    def nbytes(self) -> int:
        return sum(int(a.size) * a.dtype.itemsize
                   for a in self.arrays.values())


def make_paged_kv_fn(pools: Dict[str, jax.Array], tables, start_pos,
                     num_tokens, ctx_lens, page_size: int, quantized: bool,
                     page_in_lanes: bool = False, *, layer_plan, walks,
                     heads_per_row: Optional[Dict[str, int]] = None):
    """Build the `kv_fn` closure `transformer_step` calls per layer inside
    the jitted serving step: write the chunk's new K/V into the paged
    pool, then attend over each slot's pages via
    `ragged_paged_attention`.

    The write takes a kernel exactly where the attention does
    (`paged_kernel_route`: an fp pool and kernels active): the Pallas
    call `paged_kv_write`, pools aliased in and out, so that between the
    step's donated argument and its returned pools the K and V pools
    pass through custom calls only and XLA never relayouts them
    (`page_in_lanes`: what `KVPools.pages_in_lanes` found, so that the
    calls take the pool in the layout the device keeps it in).  Every
    other route (int8 pools with scale planes, the CPU and any non-TPU
    backend, ``MXTPU_PALLAS`` off) keeps the XLA scatter
    (`scatter_kv_write`) beside the reference attention.

    `pools` is a MUTABLE dict of the pool arrays (functional updates are
    written back per layer); after `transformer_step` returns it holds the
    step's updated pools — the engine returns them as donated outputs.

    `tables`: ``{group name: (B, max_pages) int32}``, one page table a
    cache group, all under the same logical indexing (released pages are
    the null page); start_pos/num_tokens/ctx_lens: (B,) int32.  Chunk
    token c of slot b sits at absolute position ``start_pos[b] + c`` and
    is real iff ``c < num_tokens[b]`` — padded rows scatter to the null
    page (the kernel drops them).

    `layer_plan`: one ``(group name, index in the group's pool, window)``
    a layer, from the model's `DecodeSpec.cache_plan`: the layer writes
    and reads its group's arrays (`CacheGroup.pool_names`) through its
    group's table, and its mask reads ITS window.  On the kernel route
    the attention walks a work list of live (slot, page) pairs
    (`live_page_items`), built here once a (group, window) and shared by
    its layers: at most ``walks[group name]`` pages a slot
    (`CacheGroup.walk`).  `heads_per_row`: `KVPools.heads_per_row`, the
    kv heads a pool row of each group holds (1 where not given).
    """
    from ..ops.pallas.paged_attention import (
        paged_kernel_route, paged_kv_write, ragged_paged_attention)

    kernel = paged_kernel_route(quantized)
    fold = heads_per_row or {}
    # one work list a cache group (and window): its layers share both
    work_lists = {}

    def kv_fn(li, q, k_new, v_new):
        group, gi, win = layer_plan[li]
        kn, vn = _pool_names(group)
        table = tables[group]
        g = fold.get(group, 1)
        with jax.named_scope("mx.serve.pool_write"):
            if kernel:
                pools[kn], pools[vn] = paged_kv_write(
                    pools[kn], pools[vn], k_new, v_new, gi, table,
                    start_pos, num_tokens, null_page=NULL_PAGE,
                    page_in_lanes=page_in_lanes, heads_per_row=g)
            else:
                scatter_kv_write(pools, gi, k_new, v_new, table,
                                 start_pos, num_tokens, page_size,
                                 quantized, names=(kn, vn),
                                 heads_per_row=g)
        with jax.named_scope("mx.serve.paged_attn"):
            if kernel and (group, win) not in work_lists:
                work_lists[group, win] = live_page_items(
                    ctx_lens, start_pos, win, page_size, walks[group])
            return ragged_paged_attention(
                q, pools[kn], pools[vn], table, ctx_lens,
                start_pos, window=win, layer=gi,
                page_in_lanes=page_in_lanes, heads_per_row=g,
                k_scales=pools["k_scale"] if quantized else None,
                v_scales=pools["v_scale"] if quantized else None,
                work_list=work_lists.get((group, win)))

    return kv_fn


def scatter_kv_write(pools: Dict[str, jax.Array], li, k_new, v_new,
                     page_tables, start_pos, num_tokens, page_size: int,
                     quantized: bool, names=("k", "v"),
                     heads_per_row: int = 1) -> None:
    """The XLA-scatter form of the K/V write (updates `pools` in place; a
    folded pool, ``heads_per_row = g``, takes the new rows folded the same
    way, one ``g * D`` row a (token, row group)):
    the route of int8 pools and of every backend without the kernels, and
    the oracle `paged_kv_write` is tested against.  Next to a Mosaic
    custom call XLA:TPU relayouts the whole pool around a scatter of any
    form (my chip run, PR 21), which is why the kernel route has none."""
    from ..ops.pallas.paged_attention import fold_heads
    ps = page_size
    k_new = fold_heads(k_new, heads_per_row)
    v_new = fold_heads(v_new, heads_per_row)
    B, Hkv, C, D = k_new.shape
    pos = start_pos[:, None] + jnp.arange(C)[None, :]      # (B, C)
    logical = jnp.minimum(pos // ps, page_tables.shape[1] - 1)
    phys = jnp.take_along_axis(page_tables, logical, axis=1)
    active = jnp.arange(C)[None, :] < num_tokens[:, None]
    # one D-vector per (token, kv head)
    head = jnp.tile(jnp.arange(Hkv), B * C)
    page = jnp.repeat(
        jnp.where(active, phys, NULL_PAGE).reshape(B * C), Hkv)
    off = jnp.repeat((pos % ps).reshape(B * C), Hkv)

    def scatter(name, new):
        # (B, Hkv, C, D) -> (token, head)-major vectors, scattered
        # straight into the stacked pool (in place under donation)
        vecs = new.transpose(0, 2, 1, 3).reshape(B * C * Hkv, D)
        if quantized:
            from ..contrib.quantization import quantize_kv
            vecs, scales = quantize_kv(vecs)
            pools[name + "_scale"] = pools[name + "_scale"].at[
                li, head, page, off].set(scales)
        pools[name] = pools[name].at[li, head, page, off].set(
            vecs.astype(pools[name].dtype))

    scatter(names[0], k_new)
    scatter(names[1], v_new)
