"""Inference serving engine: ONE compiled step over a paged KV pool.

Wraps a causal-LM ``HybridBlock`` (``GPTForCausalLM``) the way
`ShardedTrainStep` wraps training: the whole serving iteration — embed a
ragged chunk of tokens for every slot, write new K/V into the paged pool,
ragged paged attention, LM head, sample — is ONE jitted program with the
pool buffers **donated** (in-place page updates, zero per-step device
allocation).  Two variants compile at `warmup()`: the mixed
prefill+decode step at the prefill-chunk width and the steady-state
pure-decode step at C=1; with the persistent compile cache on
(`runtime.enable_compile_cache`) both come back from it on restart (the
TVM-flavored "serving path as a compiled, cached artifact" — the
AOT-export layer loads these same programs from disk).

The KV pool is one pool a **cache group** (`serve/kv_cache.py`): the
engine holds the model's groups as ONE list, ``self.groups``, of
`CacheGroup` records built by `plan_cache_groups` (name, layers, window,
pool size, walk, allocator; the whole-context group first), and the
pools, the step's page tables (a tuple, one a group), `_step_avals` and
`stats()` are loops over that list: a model of one kind of layer has a
list of one.  ``self.allocator`` is the whole-context group's, because
sharing (prefix cache, copy-on-write fork, handoff) is defined only
where a slot keeps every page of its context; the prefix cache and the
disaggregated roles are on only when every group does.

Instrumented from day one: compile/journal events, per-step histograms,
page-occupancy gauges (via the scheduler), and a ``serve.step`` heartbeat
the hang watchdog monitors like any training loop.

Typical use::

    eng = mx.serve.InferenceEngine(model)
    eng.warmup()
    h = eng.submit([1, 2, 3], max_new_tokens=16,
                   on_token=lambda t, r: print(t))
    eng.run_until_idle()
    full = h.result()

or one-shot: ``eng.generate([1, 2, 3], max_new_tokens=16)``.
"""
from __future__ import annotations

import math
import os
import threading
import time
from dataclasses import dataclass, field
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as onp

from ..base import MXNetError
from .. import health as _health
from .. import telemetry as _tele
from .. import tracing as _trace
from .decode import (extract_decode_weights, transformer_step, lm_logits,
                     quantize_decode_weights, decode_weight_bytes,
                     tp_qkv_row_perm, decode_spec)
from .kv_cache import (KVPools, PrefixIndex, make_paged_kv_fn,
                       plan_cache_groups)
from .scheduler import ContinuousBatchingScheduler, ServeRequest
from .spec import Drafter, NGramDrafter

__all__ = ["ServeConfig", "InferenceEngine"]


def _env_int(name, default):
    try:
        return int(os.environ.get(name, "") or default)
    except ValueError:
        return default


def _default_page_size() -> int:
    """MXTPU_SERVE_PAGE_SIZE wins; otherwise the paged-attention
    autotuner's persisted recommendation for this device
    (`tune("paged_attention", ...)` — docs/perf.md), else the lane width
    on a TPU (one page = one full score tile of the kernel) and 16 on
    the reference path."""
    explicit = _env_int("MXTPU_SERVE_PAGE_SIZE", 0)
    if explicit:
        return explicit
    from ..ops.pallas.paged_attention import recommended_page_size, LANES
    return recommended_page_size(
        LANES if jax.default_backend() == "tpu" else 16)


class StepLayout:
    """Where a step's host arrays lie in the ONE int32 array a launch
    hands the device: ``tok``, ``num_tokens``, ``start_pos``, one page
    table a cache group, ``ctx_lens``, ``greedy_mask`` (as int32) and
    ``temps`` (its float32 bits), end to end.  The offsets follow from
    the shapes alone — (slots, chunk width, cache groups, table width)
    — so the host's `pack` and the jitted step's `unpack` cannot
    disagree, and a further cache group is one more entry."""

    def __init__(self, slots: int, chunk: int, n_groups: int,
                 table_width: int):
        B = slots
        self.shapes = ((B, chunk), (B,), (B,),
                       *[(B, table_width)] * n_groups, (B,), (B,), (B,))
        #: elements of the packed array
        self.size = sum(math.prod(s) for s in self.shapes)

    def pack(self, tok, num_tokens, start_pos, tables, ctx_lens, temps,
             greedy_mask) -> onp.ndarray:
        """`_execute`'s arrays as one fresh int32 array (never a reused
        buffer: the CPU backend may alias host memory)."""
        parts = (tok, num_tokens, start_pos, *tables, ctx_lens,
                 onp.asarray(greedy_mask, onp.int32),
                 onp.asarray(temps, onp.float32).view(onp.int32))
        if tuple(onp.shape(p) for p in parts) != self.shapes:
            raise MXNetError(
                f"step inputs of shapes "
                f"{[onp.shape(p) for p in parts]} do not fit the step's "
                f"layout {list(self.shapes)}")
        return onp.concatenate(
            [onp.asarray(p, onp.int32).ravel() for p in parts])

    def unpack(self, packed):
        """The packed array back as `pack`'s arguments, in their order:
        static slices, reshapes, a bitcast and a compare — nothing that
        depends on a value."""
        parts, at = [], 0
        for shape in self.shapes:
            n = math.prod(shape)
            parts.append(packed[at:at + n].reshape(shape))
            at += n
        tok, num_tokens, start_pos, *tables, ctx_lens, greedy, temps = parts
        return (tok, num_tokens, start_pos, tuple(tables), ctx_lens,
                jax.lax.bitcast_convert_type(temps, jnp.float32),
                greedy != 0)


@dataclass
class ServeConfig:
    """Serving knobs; every field defaults from its ``MXTPU_SERVE_*``
    environment variable (docs/env_vars.md)."""

    max_slots: int = field(
        default_factory=lambda: _env_int("MXTPU_SERVE_SLOTS", 8))
    page_size: int = field(
        default_factory=lambda: _default_page_size())
    num_pages: int = field(
        default_factory=lambda: _env_int("MXTPU_SERVE_PAGES", 0))
    prefill_chunk: int = field(
        default_factory=lambda: _env_int("MXTPU_SERVE_PREFILL_CHUNK", 16))
    max_len: int = field(
        default_factory=lambda: _env_int("MXTPU_SERVE_MAX_LEN", 0))
    kv_dtype: str = field(
        default_factory=lambda: os.environ.get("MXTPU_SERVE_KV_DTYPE", ""))
    # per-request wall-clock deadline in ms (0 = none): queued/active
    # requests past it are expired by the scheduler so one stuck or
    # abandoned client can never pin KV pages forever
    deadline_ms: int = field(
        default_factory=lambda: _env_int("MXTPU_SERVE_DEADLINE_MS", 0))
    # weight-only quantization: 8 or 4 rewrites the decode weights to
    # int8/int4 planes at engine construction and routes the FFN/
    # attention projections + LM head through the fused dequant-matmul
    # kernel (docs/quantization.md).  0 = dense f32 weights.
    quant_bits: int = field(
        default_factory=lambda: _env_int("MXTPU_QUANT_BITS", 0))
    # speculative decoding: k > 0 lets a drafter propose k tokens per
    # decode slot, verified by ONE fused launch at width k+1 (greedy
    # streams stay bit-identical — docs/serving.md).  Program-shaping:
    # part of the compiled-width set and the export identity.
    spec_tokens: int = field(
        default_factory=lambda: _env_int("MXTPU_SPEC_TOKENS", 0))
    # cross-request prefix caching: finished prompt prefills register
    # their full KV blocks in a PrefixIndex; a new request whose prompt
    # shares a cached prefix attaches those pages by reference (COW on
    # first write) and skips the matching prefill chunks entirely.
    # Host-side policy only — the compiled program is unchanged.
    prefix_cache: bool = field(
        default_factory=lambda: _env_int("MXTPU_PREFIX_CACHE", 0) > 0)
    # tensor parallelism: shard the decode weights + paged KV pool over
    # a 'tp' mesh axis; the fused step runs under shard_map with
    # all-gather collectives (docs/serving.md "Disaggregated serving").
    # Degrades (gcd) to what the device count / head counts allow —
    # never refuses.  Part of the export identity.
    tp: int = field(
        default_factory=lambda: _env_int("MXTPU_SERVE_TP", 1))
    # disaggregated serving role: 'prefill' engines run chunked prefill
    # then hand the request + its KV pages off; 'decode' engines adopt
    # prefilled requests; 'both' (default) is the classic combined
    # engine.  Host-side policy — the compiled program is unchanged.
    role: str = field(
        default_factory=lambda: os.environ.get(
            "MXTPU_SERVE_ROLE", "") or "both")
    # engine-wide sampling filter (static: part of the compiled step)
    top_k: int = 0
    top_p: float = 1.0

    def __post_init__(self):
        if self.max_slots < 1:
            raise MXNetError("max_slots must be >= 1")
        if self.page_size < 1:
            raise MXNetError("page_size must be >= 1")
        if self.prefill_chunk < 1:
            raise MXNetError("prefill_chunk must be >= 1")
        if self.tp < 1:
            raise MXNetError(
                f"tp must be >= 1, got {self.tp} (MXTPU_SERVE_TP)")
        if self.role not in ("prefill", "decode", "both"):
            raise MXNetError(
                f"role must be 'prefill', 'decode', or 'both'; got "
                f"{self.role!r} (MXTPU_SERVE_ROLE)")
        if self.quant_bits not in (0, 4, 8):
            raise MXNetError(
                f"quant_bits must be 0 (dense), 8, or 4; got "
                f"{self.quant_bits} (MXTPU_QUANT_BITS)")
        if self.spec_tokens < 0:
            raise MXNetError(
                f"spec_tokens must be >= 0, got {self.spec_tokens} "
                f"(MXTPU_SPEC_TOKENS)")


class InferenceEngine:
    """Continuous-batching inference over a GPT-style causal LM.

    ``drafter`` (docs/serving.md "Speculative decoding & prefix
    caching"): the token-proposal hook used when
    ``ServeConfig.spec_tokens`` > 0; defaults to the model-free
    :class:`~mxnet_tpu.serve.spec.NGramDrafter` over each request's own
    context.  A learned draft model plugs in through the same
    `Drafter` interface."""

    def __init__(self, model, config: Optional[ServeConfig] = None,
                 seed: int = 0, act_thresholds=None,
                 drafter: Optional[Drafter] = None):
        self.model = model
        self.cfg = model.cfg
        self.serve_config = config or ServeConfig()
        sc = self.serve_config

        cfg = self.cfg
        H = cfg.num_heads
        #: what each layer is (norms, attention kind, FFN kind, cache
        #: group): from the model's configuration alone
        self.spec = decode_spec(cfg)
        self.n_kv_heads = getattr(cfg, "num_kv_heads", None) or H
        self.head_dim = self.spec.head_dim
        self.max_len = sc.max_len or cfg.max_position
        if self.max_len > cfg.max_position:
            raise MXNetError(
                f"MXTPU_SERVE_MAX_LEN={self.max_len} exceeds the model's "
                f"max_position={cfg.max_position}")
        self.max_pages_per_seq = max(
            1, math.ceil(self.max_len / sc.page_size))
        kv_dtype = sc.kv_dtype or cfg.dtype
        self.quantized = str(kv_dtype) == "int8"
        self._kv_dtype = kv_dtype

        self.P = extract_decode_weights(model)
        self.quant_bits = 0
        self.quant_info = None
        self._step_fns = {}       # chunk width C -> jitted step
        self._execs = {}          # chunk width C -> AOT executable
        self._write_bytes = {}    # chunk width C -> `kv_write_bytes(C)`
        #: disaggregation role ('prefill' | 'decode' | 'both') — read by
        #: the scheduler (handoff detach) and the fleet router
        self.role = sc.role
        self._resolve_tp()
        if self.tp > 1:
            self._permute_qkv_rows()
        if sc.quant_bits:
            self.quantize_weights(sc.quant_bits,
                                  thresholds=act_thresholds)
        if self.tp > 1:
            self._tp_shard_weights()
        self._build_pools()
        #: cross-request prompt-prefix cache (MXTPU_PREFIX_CACHE):
        #: shared read-only page runs with COW forks; None when off.
        #: Off too unless every cache group keeps the whole context: a
        #: prefix may be shared only where every group still holds it,
        #: and a windowed group has let the prompt's pages go
        #: (docs/serving.md)
        self.prefix_index = (
            PrefixIndex(self.allocator, sc.page_size)
            if sc.prefix_cache
            and all(g.window is None for g in self.groups) else None)
        #: speculative-decoding proposal hook (MXTPU_SPEC_TOKENS)
        self.drafter = drafter if drafter is not None else (
            NGramDrafter() if sc.spec_tokens > 0 else None)
        self._cow_fn = None        # lazy jitted page-copy (COW forks)
        # serializes every device op that donates or reads the pool
        # buffers (the fused step, COW copies, handoff page
        # export/install): a worker's control thread lands kv_import
        # while the main loop is mid-step, and racing two donations of
        # the same buffer is use-after-free
        self._device_lock = threading.RLock()
        self.scheduler = ContinuousBatchingScheduler(self)
        self._key = jax.random.PRNGKey(seed)
        self.compile_seconds = None
        self._steps_executed = 0
        #: the last step's routing counts (expert layers, held experts),
        #: None for a model without expert layers
        self.last_moe_counts = None
        #: `perf_counter` instant the last step's executable call
        #: returned (`_execute`): the scheduler's launch/wait boundary
        self.launched_ts = 0.0
        #: what that call was handed from the host: (bytes, arrays)
        self.launched_h2d = (0, 0)
        self._note_weight_bytes()
        _health.beat("serve.step")   # announce the heartbeat name early

    def _build_pools(self) -> None:
        """The model's cache groups (`kv_cache.plan_cache_groups`: one
        record a group with its allocator, the whole-context group
        first) and the device pools over them.  An auto-sized
        whole-context pool also gets the pages the quantized weights
        just paid for: the capacity freed by smaller weights lands in
        the free-page gauges, not in unaccounted HBM slack (ROADMAP item
        2's whole premise).  An explicit num_pages wins."""
        sc = self.serve_config
        bonus = 0
        if sc.num_pages == 0 and self.quant_info is not None:
            bonus = self.quant_info["saved_bytes"] // max(
                1, self._page_nbytes(self._kv_dtype))
        self.bonus_pages = bonus
        #: the cache groups, whole-context first: the scheduler, the
        #: step and `stats()` loop over this one list
        self.groups = plan_cache_groups(
            self.spec, sc, self.max_pages_per_seq,
            max(self._step_widths()), bonus, self.quantized)
        self.pools = KVPools.create(
            self.groups, sc.page_size, self.n_kv_heads, self.head_dim,
            dtype=self._kv_dtype, tp=self.tp)
        if self.tp > 1:
            self._tp_shard_pools()
        #: the whole-context group's free list: the one whose pages a
        #: prefix, a copy-on-write fork or a handoff may share (the
        #: fleet, worker and router planes read it)
        self.allocator = self.groups[0].allocator

    # ------------------------------------------------------------------
    # weight-only quantization (docs/quantization.md)
    # ------------------------------------------------------------------
    def _page_nbytes(self, kv_dtype) -> int:
        """HBM bytes of ONE physical KV page across all layers (K + V,
        plus scale planes for the int8 pool)."""
        cfg = self.cfg
        sc = self.serve_config
        per_vec = self.head_dim * (1 if self.quantized
                                   else jnp.dtype(kv_dtype).itemsize)
        if self.quantized:
            per_vec += 4        # one f32 scale per stored vector
        return 2 * cfg.num_layers * sc.page_size * self.n_kv_heads \
            * per_vec

    def quantize_weights(self, bits: int, include=(),
                         thresholds=None) -> dict:
        """Rewrite the decode weights to int8/int4 planes (per-channel
        symmetric — `serve.decode.quantize_decode_weights`).  Drops any
        compiled step executables (their avals changed).  Called at
        construction for ``ServeConfig.quant_bits`` / the
        ``MXTPU_QUANT_BITS`` env; the export-time `QuantizePass` calls
        it on a live capture.  Returns the quantization info dict (the
        manifest ``quant`` field)."""
        if self.quant_bits:
            raise MXNetError(
                f"engine weights are already int{self.quant_bits}-"
                "quantized; re-quantizing quantized planes would "
                "compound the rounding — build a fresh engine")
        # the weight swap invalidates every compiled step AND the KV
        # context already computed with the dense weights — a live call
        # (QuantizePass, explicit pool size or not) requires idleness
        sched = getattr(self, "scheduler", None)
        if sched is not None and (sched.active_count
                                  or sched.queue_depth):
            raise MXNetError(
                "quantize_weights needs an idle engine (in-flight "
                "streams hold dense-weight KV state, and the paged "
                "pool may be rebuilt to claim the freed weight "
                "bytes); drain() first")
        self.P, info = quantize_decode_weights(self.P, bits,
                                               include=include,
                                               thresholds=thresholds)
        self.quant_bits = int(bits)
        self.quant_info = info
        self._step_fns.clear()
        self._execs.clear()
        # live-engine call (QuantizePass): grow the auto-sized pool by
        # the pages the freed weight bytes pay for — the SAME formula
        # construction uses, so an artifact captured here installs into
        # a ``quant_bits``-constructed engine with identical pool avals
        if self.tp > 1:
            self._tp_shard_weights()
        if getattr(self, "pools", None) is not None and \
                self.serve_config.num_pages == 0 and \
                info["saved_bytes"] >= self._page_nbytes(self._kv_dtype):
            self._build_pools()
            if getattr(self, "prefix_index", None) is not None:
                # the old index references the replaced allocator
                # and pool; start empty over the new ones (idle
                # engine — nothing was attached)
                self.prefix_index = PrefixIndex(
                    self.allocator, self.serve_config.page_size)
            if sched is not None:
                sched._bind_groups()
        self._note_weight_bytes()
        return info

    def weight_bytes(self) -> int:
        """Stored bytes of the decode weights (planes + scales when
        quantized)."""
        return decode_weight_bytes(self.P)

    def _note_weight_bytes(self) -> None:
        if not _tele.enabled():
            return
        _tele.gauge(
            "serve_weight_bytes",
            "Stored bytes of the engine's decode weights (quantized "
            "planes + scales when MXTPU_QUANT_BITS is set)"
        ).set(self.weight_bytes())

    # ------------------------------------------------------------------
    # tensor parallelism (ServeConfig.tp / MXTPU_SERVE_TP)
    # ------------------------------------------------------------------
    @staticmethod
    def _outdim(w) -> int:
        q = getattr(w, "q", None)    # QuantizedTensor plane
        return int((q if q is not None else w).shape[0])

    def _resolve_tp(self) -> None:
        """Clamp the requested tp to what the device count and the
        model's shapes allow — the `fit_axes` degrade contract: tp=2 on
        1 device (or odd head counts) becomes tp=1 with a LOUD log,
        never a crash.  tp must divide the kv-head count (contiguous
        head blocks keep every GQA query head with its kv head), the
        FFN intermediate width, the hidden size, and the untied vocab."""
        from ..parallel.mesh import fit_axes, make_mesh
        sc = self.serve_config
        want = max(1, int(sc.tp))
        tp = fit_axes(len(jax.devices()), tp=want)["tp"]
        dims = [self.n_kv_heads, self.cfg.num_heads,
                self.cfg.hidden_size]
        if any(ls.norm != "layernorm" for ls in self.spec.layers):
            dims.append(1)        # only the GPT block has a tp scheme
        elif self.P["layers"]:
            dims.append(self._outdim(self.P["layers"][0]["w1"]))
        if self.P.get("head") is not None:
            dims.append(self._outdim(self.P["head"]))
        for d in dims:
            tp = math.gcd(tp, int(d))
        if tp != want:
            import logging
            logging.getLogger(__name__).warning(
                "serve tp degraded %d -> %d (%d visible device(s), "
                "kv_heads=%d, hidden=%d): the serve mesh re-forms at "
                "what the topology supports instead of refusing "
                "(docs/serving.md)", want, tp, len(jax.devices()),
                self.n_kv_heads, self.cfg.hidden_size)
        self.tp = tp
        self._mesh = (make_mesh({"tp": tp}, jax.devices()[:tp])
                      if tp > 1 else None)

    def _permute_qkv_rows(self) -> None:
        """Host-side head-aligned row permutation of every packed qkv
        projection (weights AND biases) so a contiguous dim-0 'tp'
        shard carries ``[q_i, k_i, v_i]`` — see `tp_qkv_row_perm`.
        Runs BEFORE quantization (per-out-channel scales then permute
        with their rows) and never mutates a model-shared pytree."""
        H = self.cfg.num_heads
        perm = onp.asarray(tp_qkv_row_perm(H, self.n_kv_heads,
                                           self.head_dim, self.tp))
        layers = []
        for L in self.P["layers"]:
            NL = dict(L)
            NL["wqkv"] = jnp.asarray(L["wqkv"])[perm]
            NL["bqkv"] = jnp.asarray(L["bqkv"])[perm]
            layers.append(NL)
        self.P = dict(self.P, layers=layers)

    # weight leaves sharded on their OUTPUT dim under tp (all-gather
    # scheme — full-length contractions keep greedy streams bit-
    # identical to tp=1); everything else replicated
    _TP_SHARDED_KEYS = frozenset(
        {"wqkv", "bqkv", "wo", "w1", "b1", "w2", "head"})

    def _tp_weight_specs(self):
        """Pytree of `PartitionSpec`s matching ``self.P`` (QuantizedTensor
        planes and their per-channel scales both shard dim 0)."""
        from jax.sharding import PartitionSpec as PS
        tu = jax.tree_util

        def spec(path, v):
            names = {p.key for p in path if isinstance(p, tu.DictKey)}
            if names & self._TP_SHARDED_KEYS:
                return PS("tp", *([None] * (v.ndim - 1)))
            return PS()
        return tu.tree_map_with_path(spec, self.P)

    def _pool_specs(self):
        """PartitionSpecs for the pool arrays: K/V pages and the int8
        per-vector scale planes all shard their kv-head dim (axis 1)."""
        from jax.sharding import PartitionSpec as PS
        return tuple(PS(None, "tp", *([None] * (a.ndim - 2)))
                     for a in self.pools.as_tuple())

    def _tp_shard_weights(self) -> None:
        from jax.sharding import NamedSharding
        mesh = self._mesh
        self.P = jax.tree_util.tree_map(
            lambda v, s: jax.device_put(v, NamedSharding(mesh, s)),
            self.P, self._tp_weight_specs())

    def _tp_shard_pools(self) -> None:
        from jax.sharding import NamedSharding
        mesh = self._mesh
        self.pools = self.pools.replace(tuple(
            jax.device_put(a, NamedSharding(mesh, s))
            for a, s in zip(self.pools.as_tuple(), self._pool_specs())))

    # ------------------------------------------------------------------
    # compiled step
    # ------------------------------------------------------------------
    def _layout(self, C: int) -> StepLayout:
        """The packed launch array's layout at chunk width C."""
        return StepLayout(self.serve_config.max_slots, C,
                          len(self.groups), self.max_pages_per_seq)

    def _step_body(self, C: int):
        """One fused step over its inputs one by one and the step's own
        key, not jitted: ``body(P, pools, tok, num_tokens, start_pos,
        tables, ctx_lens, temps, greedy_mask, key)`` returns ``(pools,
        next_token[, all_tok][, moe_counts])``.  `_step_fn` wraps it in
        the launch's two arguments; the tests run it as the reference."""
        cfg = self.cfg
        sc = self.serve_config
        ps = sc.page_size
        spec = self.spec
        layer_plan = spec.cache_plan()
        group_names = tuple(g.name for g in self.groups)
        walks = {g.name: g.walk for g in self.groups}
        has_moe = any(ls.ffn == "moe" for ls in spec.layers)
        quantized = self.quantized
        pool_names = self.pools.names
        page_in_lanes = self.pools.pages_in_lanes()
        heads_per_row = self.pools.heads_per_row
        top_k, top_p = sc.top_k, sc.top_p
        max_pos = cfg.max_position
        spec_k = sc.spec_tokens
        tp = self.tp
        tp_axis = "tp" if tp > 1 else None

        def body(P, pools_t, tok, num_tokens, start_pos, tables,
                 ctx_lens, temps, greedy_mask, key):
            from ..models.gpt import _filter_logits
            pools = dict(zip(pool_names, pools_t))
            kv_fn = make_paged_kv_fn(pools, dict(zip(group_names, tables)),
                                     start_pos, num_tokens, ctx_lens, ps,
                                     quantized, page_in_lanes=page_in_lanes,
                                     layer_plan=layer_plan, walks=walks,
                                     heads_per_row=heads_per_row)
            # padded rows may run past the table; clamp for the embedding
            # gather only (writes are masked, attention rows are ignored)
            pos = jnp.minimum(start_pos[:, None] + jnp.arange(C)[None, :],
                              max_pos - 1)
            aux = {}
            h = transformer_step(
                P, cfg, tok, pos, kv_fn, tp=tp, tp_axis=tp_axis,
                row_valid=(jnp.arange(C)[None, :] < num_tokens[:, None])
                if has_moe else None, aux=aux)
            # routing counts leave with the step's tokens: (expert
            # layers, held experts), no second pass over the router
            tail = (jnp.stack(aux["moe_counts"]),) if has_moe else ()
            B = tok.shape[0]
            with jax.named_scope("mx.serve.sample"):
                last = h[jnp.arange(B), jnp.maximum(num_tokens - 1, 0)]
                logits = lm_logits(P, last, tp, tp_axis,
                                   spec.cast_inputs)          # (B, V)
                greedy_tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
                filtered = _filter_logits(
                    logits.astype(jnp.float32) / temps[:, None],
                    top_k, top_p)
                sampled = jax.random.categorical(
                    key, filtered, axis=-1).astype(jnp.int32)
                nxt = jnp.where(greedy_mask, greedy_tok, sampled)
            if spec_k > 0:
                # speculative verification: the greedy argmax at the
                # TAIL fed positions (B, T), T = min(C, k+1) — the emit
                # loop only ever reads a slot's last 1 + draft_len fed
                # positions (the fed sequence token + its drafts), so
                # computing the vocab-sized LM head at every prefill
                # position would multiply discarded work by ~C/k.
                # Column t is fed position num_tokens - T + t (t = T-1
                # is the `last` row).  Tail position t's argmax is the
                # true greedy continuation of the fed prefix before it
                # (causal attention makes it independent of fed tokens
                # after it), so the scheduler can accept a run of
                # matching drafts and stay bit-identical to one-token
                # decode.  Each row goes through the SAME (B, E) 2-D
                # LM-head matmul shape as `last` — a 3-D (B, C, E)
                # matmul could tile differently and flip a near-tie
                # argmax.
                T = min(C, spec_k + 1)
                all_tok = jnp.stack(
                    [jnp.argmax(lm_logits(
                        P, h[jnp.arange(B),
                             jnp.maximum(num_tokens - T + j, 0)],
                        tp, tp_axis, spec.cast_inputs),
                        axis=-1)
                     for j in range(T)], axis=1).astype(jnp.int32)
                return (tuple(pools[n] for n in pool_names), nxt,
                        all_tok) + tail
            return (tuple(pools[n] for n in pool_names), nxt) + tail

        return body

    def _step_fn(self, C: int):
        """The jitted step at chunk width C: ``step(P, pools, packed,
        key)`` — the step's host arrays as ONE int32 array (`StepLayout`)
        and the sampling key, which the program splits itself and hands
        back as its last output, so that it never leaves the device.
        Returns ``(pools, next_token[, all_tok][, moe_counts], key)``."""
        fn = self._step_fns.get(C)
        if fn is not None:
            return fn
        body = self._step_body(C)
        layout = self._layout(C)

        def step(P, pools_t, packed, key):
            # split here, so the key never leaves the device: the chain
            # a host-side `jax.random.split` would walk, value for value
            key, sub = jax.random.split(key)
            return body(P, pools_t, *layout.unpack(packed), sub) + (key,)

        if self.tp > 1:
            # `_resolve_tp`: only the one-group GPT block has a tp scheme
            assert len(self.groups) == 1 and \
                not any(ls.ffn == "moe" for ls in self.spec.layers)
            # the body runs per-shard: weights/pools arrive as their
            # local OUT-dim / kv-head shards, the packed inputs and the
            # key replicated; every cross-shard combine inside is an
            # all-gather, so the sampled/greedy outputs (and the next
            # key) are computed identically on every shard (replicated
            # out_specs, checker off — the numeric pin is the tp
            # bit-identity test)
            from jax.sharding import PartitionSpec as PS
            from ..parallel.mesh import shard_map_nocheck
            rep = PS()
            pool_specs = self._pool_specs()
            in_specs = (self._tp_weight_specs(), pool_specs, rep, rep)
            out_specs = (pool_specs, rep, rep) + (
                (rep,) if self.serve_config.spec_tokens > 0 else ())
            step = shard_map_nocheck(step, self._mesh, in_specs,
                                     out_specs)
        fn = jax.jit(step, donate_argnums=(1,))
        self._step_fns[C] = fn
        return fn

    def _step_widths(self):
        """Chunk widths the engine compiles: the prefill chunk, the
        pure-decode C=1 step, and (speculation on) the k+1-wide
        verification row — part of the export identity."""
        ws = {self.serve_config.prefill_chunk, 1}
        if self.serve_config.spec_tokens > 0:
            ws.add(self.serve_config.spec_tokens + 1)
        return sorted(ws)

    def warmup(self, artifact: Optional[str] = None) -> float:
        """AOT-compile the mixed prefill step and the C=1 decode step
        (``.lower().compile()`` — no step executed, the
        `ShardedTrainStep.warmup` idiom).  Returns total compile seconds;
        with the persistent compile cache on the binaries come back
        from it on a warm start.

        ``artifact=<path>`` (or an auto-matched artifact under the
        export dir — docs/export.md) skips the AOT lower entirely: both
        widths deserialize from the StableHLO capture, so NO transformer
        Python is traced in this process.  With ``MXTPU_EXPORT=1`` a
        missing artifact is captured+saved after the live compile —
        replica N>1 of a fleet cold-starts from the artifact."""
        t0 = time.perf_counter()
        if artifact is not None:
            # an EXPLICIT artifact is a contract: a missing or
            # mismatched one raises (docs/export.md "never a silent
            # retrace") — only the auto-discovered path degrades
            self.load_export(artifact)
            self.compile_seconds = time.perf_counter() - t0
            return self.compile_seconds
        path = self._auto_artifact_path()
        if path is not None and \
                os.path.isfile(os.path.join(path, "manifest.json")):
            try:
                self.load_export(path)
                self.compile_seconds = time.perf_counter() - t0
                return self.compile_seconds
            except MXNetError as e:
                import logging
                logging.getLogger(__name__).warning(
                    "serve export artifact %s unusable (%s); compiling "
                    "live", path, str(e).splitlines()[0])
        for C in self._step_widths():
            self._compile(C)
        self.compile_seconds = time.perf_counter() - t0
        if artifact is None and path is not None:
            try:
                self.export(path)
            except Exception:
                import logging
                logging.getLogger(__name__).exception(
                    "serve auto-capture to %s failed", path)
        return self.compile_seconds

    # -- ahead-of-time export (docs/export.md) -------------------------
    def export(self, path: str, passes=None) -> str:
        """Capture both compiled step widths to an export artifact,
        optionally through an offline pass pipeline first (e.g.
        ``passes=[QuantizePass(bits=8)]`` — docs/quantization.md)."""
        from ..export import capture_serve, PassManager
        if self.tp > 1:
            raise MXNetError(
                "serve export capture is single-device today: a tp>1 "
                "engine compiles live (its executables embed the tp "
                "mesh; `_export_config()['tp']` refuses cross-topology "
                "installs) — capture at tp=1 or drop MXTPU_SERVE_TP")
        cap = capture_serve(self)
        if passes:
            cap = PassManager(passes).run(cap)
        return cap.save(path)

    def load_export(self, path: str) -> None:
        """Install both step widths from an artifact — zero model
        traces in this process.  Fails fast on kind/config/aval
        mismatch (docs/export.md failure matrix)."""
        from ..export import load as _load
        la = _load(path)
        if la.kind != "serve_step":
            raise MXNetError(
                f"engine.load_export: artifact at {path} is kind="
                f"{la.kind!r}, not a serve_step capture")
        want = self._export_config()
        got = la.manifest.get("meta", {}).get("serve_config", {})
        if got != want:
            raise MXNetError(
                f"serve export artifact {path} was captured for config "
                f"{got} but this engine runs {want}; re-capture")
        quant = la.manifest.get("quant")
        if (quant or {}).get("bits", 0) != self.quant_bits or \
                (quant or {}).get("scheme",
                                  "symmetric-per-channel") != \
                "symmetric-per-channel":
            raise MXNetError(
                f"serve export artifact {path} quant scheme "
                f"{quant!r} does not match this engine "
                f"(quant_bits={self.quant_bits}); construct the engine "
                "with the matching MXTPU_QUANT_BITS / "
                "ServeConfig.quant_bits (docs/quantization.md failure "
                "matrix)")
        # stage into a local dict: a failure on the SECOND width must
        # not leave a half-artifact engine (live fallback would keep
        # the already-installed exec via _compile's early return)
        staged = {}
        for C in self._step_widths():
            avals = self._step_avals(C)
            topo = {"devices": 1, "axes": {}}
            la.artifact.check_avals(topo, avals, tag=f"c{C}")
            exp = la.exported_for(topo, tag=f"c{C}")
            if _tele.enabled():
                _tele.event("compile_start", kind="serve_export_load",
                            chunk=C)
            t0 = time.perf_counter()
            with _health.suppress_stalls("serve_export_compile"):
                staged[C] = jax.jit(
                    exp.call, donate_argnums=(1,)
                ).lower(*avals).compile()
            self._record_cost(C, staged[C], source="export_load")
            if _tele.enabled():
                _tele.event("compile_end", kind="serve_export_load",
                            chunk=C,
                            seconds=round(time.perf_counter() - t0, 4))
        # a QuantizePass artifact SHIPS its pre-quantized planes: adopt
        # them so the served weights are byte-identical to the capture
        # (requantizing locally agrees for f32 sources, but the shipped
        # planes make the artifact the single source of truth).  LAST,
        # after every width staged/validated: a refused load must leave
        # the engine untouched — weights included (the planes carry the
        # same avals as self.P, per-leaf-validated, so the staged
        # executables compiled above accept them)
        if quant and la.artifact.params is not None:
            self._install_weights(la.artifact.params, path)
        self._execs.update(staged)

    def _export_config(self) -> dict:
        from ..ops.pallas.quantized_matmul import act_quant_enabled
        sc = self.serve_config
        return {"max_slots": sc.max_slots, "page_size": sc.page_size,
                "prefill_chunk": sc.prefill_chunk,
                "max_len": self.max_len,
                "kv_dtype": sc.kv_dtype or self.cfg.dtype,
                # program-shaping quantization knobs: an int8 artifact
                # must never install into a dense (or int4, or int8-
                # activation) engine — scheme mismatch fails fast
                "quant_bits": self.quant_bits,
                "quant_act": act_quant_enabled(),
                # speculation width shapes the program (extra compiled
                # width + per-position verify outputs): artifacts refuse
                # to load across differing values (docs/serving.md
                # failure matrix).  prefix_cache is deliberately absent
                # — host-side policy, same compiled program.
                "spec_tokens": sc.spec_tokens,
                # tp topology is part of the artifact identity: a tp=2
                # capture must never install into a tp=1 engine (the
                # weight shards/collectives differ) — mismatch refuses
                # at load, the zero-retrace contract stays intact
                "tp": self.tp,
                "top_k": sc.top_k, "top_p": sc.top_p}

    def _install_weights(self, params: dict, path: str) -> None:
        """Adopt an artifact's shipped weight leaves (flatten-order
        named ``w<i>``; the engine's own quantized tree defines the
        structure — `_export_config`/aval checks already proved the
        trees agree)."""
        leaves, treedef = jax.tree_util.tree_flatten(self.P)
        if len(params) != len(leaves):
            raise MXNetError(
                f"serve export artifact {path} ships {len(params)} "
                f"weight leaves but this engine's tree has "
                f"{len(leaves)}; re-capture")
        new = []
        for i, old in enumerate(leaves):
            v = params.get(f"w{i:05d}")
            if v is None:
                raise MXNetError(
                    f"serve export artifact {path} is missing weight "
                    f"leaf w{i:05d}; re-capture")
            if tuple(v.shape) != tuple(old.shape) or \
                    jnp.dtype(v.dtype) != jnp.dtype(old.dtype):
                raise MXNetError(
                    f"serve export artifact {path} weight leaf "
                    f"w{i:05d} is {tuple(v.shape)}/{v.dtype}, engine "
                    f"expects {tuple(old.shape)}/{old.dtype}")
            new.append(jnp.asarray(v))
        self.P = jax.tree_util.tree_unflatten(treedef, new)
        self._note_weight_bytes()

    def _auto_artifact_path(self) -> Optional[str]:
        # MXTPU_EXPORT=1 gates BOTH auto-load and auto-capture (the
        # train-side rule): the signature hashes avals/config/backend,
        # not code, so an un-opted-in engine must never silently serve
        # a stale artifact left in the store by an earlier run
        from ..export import auto_capture_enabled, export_dir, signature
        if not auto_capture_enabled() or self.tp > 1:
            return None
        d = export_dir()
        if not d:
            return None
        import jax as _jax
        leaves = jax.tree_util.tree_flatten_with_path(self.P)[0]
        pav = sorted((str(p), tuple(v.shape), str(v.dtype))
                     for p, v in leaves)
        sig = signature([pav, sorted(self._export_config().items()),
                         self.quantized, _jax.__version__,
                         _jax.default_backend()])
        return os.path.join(d, f"serve-{sig}")

    def _step_avals(self, C: int):
        """The aval tuple one fused step takes at chunk width C (shared
        by AOT compile and export capture): weights, pools, the packed
        int32 array and the key."""
        sd = jax.ShapeDtypeStruct
        return (
            jax.tree_util.tree_map(
                lambda x: sd(x.shape, x.dtype), self.P),
            tuple(sd(a.shape, a.dtype)
                  for a in self.pools.as_tuple()),
            sd((self._layout(C).size,), jnp.int32),
            sd(self._key.shape, self._key.dtype),
        )

    def _compile(self, C: int):
        ex = self._execs.get(C)
        if ex is not None:
            return ex
        fn = self._step_fn(C)
        avals = self._step_avals(C)
        if _tele.enabled():
            _tele.event("compile_start", kind="serve_step", chunk=C)
        t0 = time.perf_counter()
        c_span = _trace.get_tracer("serve").span(
            "serve.compile", chunk=C, **self._fold_tags()) \
            if _trace.enabled() else None
        try:
            with _health.suppress_stalls("serve_compile"):
                ex = fn.lower(*avals).compile()
        finally:
            if c_span is not None:
                c_span.__exit__(None, None, None)
        self._record_cost(C, ex, source="live_compile")
        if _tele.enabled():
            _tele.event("compile_end", kind="serve_step", chunk=C,
                        seconds=round(time.perf_counter() - t0, 4))
        self._execs[C] = ex
        return ex

    # -- performance attribution (mx.tracing) --------------------------
    def _record_cost(self, C: int, compiled, source: str) -> None:
        """Register one chunk width's executable in the process cost
        registry (``serve_step_c<C>@...``); the scheduler's per-step
        wall times then carry FLOP attribution."""
        _trace.record_executable(
            f"serve_step_c{C}@{id(self):x}", compiled, kind="serve_step",
            chunk=C, source=source,
            quantized=self.quantized)

    def _fold_tags(self) -> dict:
        """``kv_heads_per_row_<group>``: the kv heads a pool row of each
        cache group holds (`KVPools.heads_per_row`; 1 where not folded) —
        on the ``serve.compile`` span and in `stats()`."""
        return {"kv_heads_per_row_" + g.name:
                self.pools.heads_per_row[g.name] for g in self.groups}

    def kv_write_bytes(self, C: int) -> int:
        """HBM bytes the K/V write kernels of a step at chunk width C
        move, reckoned from their block shapes and grid
        (`paged_kv_write_bytes`, one call a layer, every slot); 0 where
        the step scatters instead.  The ``kv_write_bytes`` tag on
        ``serve.step``: what tells a reader the fold engaged."""
        got = self._write_bytes.get(C)
        if got is None:
            from ..ops.pallas.paged_attention import (paged_kernel_route,
                                                      paged_kv_write_bytes)
            got = 0
            if paged_kernel_route(self.quantized):
                lanes = self.pools.pages_in_lanes()
                for g in self.groups:
                    a = self.pools.arrays[g.pool_names[0]]
                    got += len(g.layers) * paged_kv_write_bytes(
                        a.shape, a.dtype, self.serve_config.max_slots, C,
                        lanes)
            self._write_bytes[C] = got
        return got

    def cost_features(self) -> dict:
        """{chunk_width: XLA cost-feature vector} for every compiled
        step width (empty before warmup)."""
        out = {}
        for C in self._execs:
            feats = _trace.account().features(
                f"serve_step_c{C}@{id(self):x}")
            if feats is not None:
                out[C] = feats
        return out

    # ------------------------------------------------------------------
    def _execute(self, tok, num_tokens, start_pos, tables, ctx_lens,
                 temps, greedy_mask, C: int):
        """Run one fused step (called by the scheduler); returns
        ``(next_token[B], all_tok)`` as host numpy — `all_tok` is the
        (B, C) per-position greedy argmax when speculation is enabled,
        else None.  `tables`: one (slots, table width) page table a
        cache group, in `self.groups`' order.  A model with expert layers also
        leaves the step's routing counts, (expert layers, held experts),
        in ``self.last_moe_counts``, read back with the tokens.
        Leaves in ``self.launched_ts`` the `perf_counter`
        instant the executable's call returned: the boundary between
        the step's ``launch`` phase (the arrays packed into one int32
        array, `StepLayout`, and the call, which carries that one
        host-to-device transfer; the key is the previous step's output
        and is split inside the program) and its ``wait`` (blocking on
        the tokens), which the scheduler reads after the call; beside
        it ``self.launched_h2d``, the bytes and the host arrays that
        call was handed."""
        ex = self._execs.get(C)
        if ex is None:
            ex = self._compile(C)
        if self.tp > 1:
            # fault-injection point for the tp collective path: a shard
            # lost mid-step surfaces here (docs/resilience.md)
            from ..resilience import fault_point
            fault_point("tp_collective")
        self._steps_executed += 1
        with _trace.annotation("serve.step.launch"):
            packed = self._layout(C).pack(tok, num_tokens, start_pos,
                                          tables, ctx_lens, temps,
                                          greedy_mask)
            with self._device_lock:
                out_pools, nxt, *rest, self._key = ex(
                    self.P, self.pools.as_tuple(), packed, self._key)
                all_tok = rest.pop(0) \
                    if self.serve_config.spec_tokens > 0 else None
                counts = rest.pop(0) if rest else None
                # rebind the donated pool buffers to the step's outputs
                self.pools = self.pools.replace(out_pools)
            self.launched_h2d = (packed.nbytes, 1)
            self.launched_ts = time.perf_counter()
        with _trace.annotation("serve.step.wait"):
            nxt, all_tok, counts = jax.device_get((nxt, all_tok, counts))
            self.last_moe_counts = counts
            return (onp.asarray(nxt),
                    None if all_tok is None else onp.asarray(all_tok))

    def copy_page(self, src: int, dst: int) -> None:
        """Device-copy ONE physical page (every layer, K + V + scale
        planes) — the data half of a copy-on-write fork, after
        `PageAllocator.fork` moved a reference onto the fresh page.
        Jitted with the pool donated so the copy updates in place; page
        ids are traced scalars, so one compile per pool-array aval
        covers every fork."""
        if self._cow_fn is None:
            self._cow_fn = jax.jit(
                lambda a, s, d: a.at[:, :, d].set(a[:, :, s]),
                donate_argnums=(0,))
        s = jnp.int32(src)
        d = jnp.int32(dst)
        with self._device_lock:
            arrs = self.pools.arrays
            for name in self.pools.full_names:
                arrs[name] = self._cow_fn(arrs[name], s, d)

    # ------------------------------------------------------------------
    # KV page transfer (prefill -> decode handoff, docs/serving.md)
    # ------------------------------------------------------------------
    def export_pages(self, page_ids) -> dict:
        """Host copies of the listed physical pages, every pool array
        (K + V + scale planes): ``{name: ndarray[..., n_pages, ...]}``
        with the page dim at axis 2, one kv head a row whatever this
        pool's fold (`KVPools.unfold`).  The prefill side of a cross-
        process handoff — the fleet ships these as binary wire blobs."""
        ids = onp.asarray(page_ids, onp.int32)
        with self._device_lock:
            return {name: onp.asarray(jax.device_get(self.pools.unfold(
                        name, self.pools.arrays[name][:, :, ids])))
                    for name in self.pools.full_names}

    def install_pages(self, page_ids, arrays: dict) -> None:
        """Scatter `export_pages`-shaped contents into this engine's
        pool at (already-allocated) `page_ids` — the decode side of a
        cross-process handoff.  Jitted with the pool donated (in-place
        on device); page ids are traced, so one compile per
        (pool aval, page count) covers repeated handoffs."""
        if getattr(self, "_install_fn", None) is None:
            self._install_fn = jax.jit(
                lambda a, ids, vals: a.at[:, :, ids].set(vals),
                donate_argnums=(0,))
        ids = jnp.asarray(page_ids, jnp.int32)
        with self._device_lock:
            arrs = self.pools.arrays
            for name in self.pools.full_names:
                arrs[name] = self._install_fn(
                    arrs[name], ids, self.pools.fold(
                        name, jnp.asarray(arrays[name], arrs[name].dtype)))

    # ------------------------------------------------------------------
    # public API (delegates to the scheduler)
    # ------------------------------------------------------------------
    def submit(self, prompt, max_new_tokens: int = 20, greedy: bool = True,
               temperature: float = 1.0, eos_token_id=None,
               on_token=None, deadline_ms=None) -> ServeRequest:
        return self.scheduler.submit(prompt, max_new_tokens,
                                     greedy=greedy, temperature=temperature,
                                     eos_token_id=eos_token_id,
                                     on_token=on_token,
                                     deadline_ms=deadline_ms)

    def step(self) -> bool:
        return self.scheduler.step()

    def run_until_idle(self, max_steps: int = 100000) -> int:
        return self.scheduler.run_until_idle(max_steps)

    def drain(self, max_steps: int = 100000):
        """Gracefully retire this engine: stop admitting new work, run
        every already-accepted stream to completion, and return the
        requests that were still QUEUED (they hold no pages and no
        progress worth keeping here — a fleet re-dispatches them to a
        surviving replica; a standalone caller can resubmit them).

        Evicted actives re-queue internally and still re-admit — drain
        finishes every stream that ever held a slot.  After drain the
        active set is empty and `submit`/`enqueue` raise."""
        sched = self.scheduler
        sched.draining = True
        handed_back = sched.detach_queued()
        steps = 0
        while (sched.active_count or sched.queue_depth) \
                and steps < max_steps:
            sched.step()
            steps += 1
        return handed_back

    def adopt_executables(self, other: "InferenceEngine") -> None:
        """Install another engine's compiled step executables instead of
        lowering our own — replica N>1 of a fleet warms from replica 0's
        AOT compile (the executables are pure programs over (weights,
        pools, packed batch, key); each engine still passes its OWN
        pool buffers and carries its OWN key).
        Requires an identical serving configuration."""
        if other._export_config() != self._export_config():
            raise MXNetError(
                f"adopt_executables: config mismatch "
                f"({other._export_config()} vs {self._export_config()})")
        if not other._execs:
            raise MXNetError(
                "adopt_executables: source engine has no compiled steps "
                "(call warmup() on it first)")
        self._execs.update(other._execs)
        for C, ex in other._execs.items():
            self._record_cost(C, ex, source="adopted")
        self.compile_seconds = 0.0

    def generate(self, prompt, max_new_tokens: int = 20, greedy: bool = True,
                 temperature: float = 1.0, eos_token_id=None):
        """One-shot convenience: submit a single request, drive the loop
        to completion, return prompt + generated token ids (list)."""
        h = self.submit(prompt, max_new_tokens, greedy=greedy,
                        temperature=temperature, eos_token_id=eos_token_id)
        self.run_until_idle()
        return h.result(timeout=0)

    def stats(self) -> dict:
        return {
            "steps_executed": self._steps_executed,
            "queue_depth": self.scheduler.queue_depth,
            "active_slots": self.scheduler.active_count,
            "free_pages": self.allocator.free_pages,
            **{"free_pages_" + g.name: g.allocator.free_pages
               for g in self.groups[1:]},
            "kv_pages_released": self.scheduler.kv_pages_released,
            **self._fold_tags(),
            "page_occupancy": round(self.allocator.occupancy(), 4),
            "pool_bytes": self.pools.nbytes(),
            "weight_bytes": self.weight_bytes(),
            "quant_bits": self.quant_bits,
            "bonus_pages": getattr(self, "bonus_pages", 0),
            "compile_seconds": self.compile_seconds,
            "tp": self.tp,
            "role": self.role,
            "handoff_pending": self.scheduler.handoff_depth,
            "handoffs_out": self.scheduler.handoffs_out,
            "handoffs_in": self.scheduler.handoffs_in,
            "spec_tokens": self.serve_config.spec_tokens,
            "spec": self.scheduler.spec_stats(),
            "prefix_cache": (None if self.prefix_index is None
                             else self.prefix_index.stats()),
            # where the last steps' host time went (median and max a
            # phase; the five longest steps with their phase split) —
            # stamped on every step, traced or not
            **self.scheduler.phase_stats(),
        }
