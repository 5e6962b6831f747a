"""Block-size autotuner for the Pallas kernel set.

Search-then-persist loop in the TVM shape (arxiv 1802.04799): each
tunable op registers a candidate grid of `BlockConfig`s, an analytic
cost model prunes the grid, the survivors are *timed* through the
`mxnet_tpu/benchmark/opperf.py` harness, and the winner is persisted as
JSON keyed by (op, shape-bucket, dtype, device kind) so a warm start
performs zero timed trials.

The pruning model follows *A Learned Performance Model for TPUs*
(arxiv 2008.01040) in shape only — their learned model scores kernels
from tile/layout features; ours is the analytic skeleton of the same
features: bytes moved vs MXU flops per candidate (roofline), plus a
per-grid-step launch overhead term that is what actually separates
block sizes for bandwidth-bound kernels.  TODO(tpu): fit the overhead
and bandwidth constants on the chip (ROADMAP A6); the CPU constants
only need to rank, not predict.

Trace-safety contract: `tune()` runs timed trials and must only be
called from host code (benchmarks, smokes, an explicit warmup).
`cached_config()` is a pure dict/JSON lookup — kernels consult it at
trace time to pick block sizes without ever searching.
"""
from __future__ import annotations

import dataclasses
import json
import math
import os
import threading
import time
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

__all__ = ["BlockConfig", "TuneResult", "register_tunable", "tunables",
           "tune", "cached_config", "lookup_any", "cache_dir",
           "clear_memory_cache"]


class BlockConfig(dict):
    """One block-size/layout choice for a kernel launch.

    A plain (hashable via `key()`) str->int mapping with attribute
    access: ``BlockConfig(block_q=256, block_k=512).block_q``.  Shared
    by every tunable op so the tuner, the JSON cache, and the kernel
    wrappers speak one type.
    """

    def __getattr__(self, name: str) -> int:
        try:
            return self[name]
        except KeyError:
            raise AttributeError(name) from None

    def key(self) -> Tuple[Tuple[str, int], ...]:
        return tuple(sorted(self.items()))

    def __repr__(self):
        inner = ", ".join(f"{k}={v}" for k, v in sorted(self.items()))
        return f"BlockConfig({inner})"


@dataclasses.dataclass
class TuneResult:
    """Outcome of one `tune()` call."""

    config: BlockConfig
    cache_hit: bool          # True: no search ran (memory or disk hit)
    source: str              # "memory" | "disk" | "search"
    trials: int              # timed candidates (0 on a warm start)
    search_ms: float
    timings_ms: Dict[Tuple[Tuple[str, int], ...], float]


@dataclasses.dataclass
class _Tunable:
    name: str
    # candidates(shapes, dtype) -> [BlockConfig, ...]
    candidates: Callable[[Sequence[int], str], List[BlockConfig]]
    # build(config, shapes, dtype) -> zero-arg thunk running ONE launch
    # (the thunk owns its inputs; opperf times it)
    build: Callable[[BlockConfig, Sequence[int], str], Callable[[], Any]]
    # roofline(config, shapes, dtype) -> {"flops", "bytes", "steps"}
    roofline: Callable[[BlockConfig, Sequence[int], str], Dict[str, float]]


_REGISTRY: Dict[str, _Tunable] = {}
_MEM: Dict[str, BlockConfig] = {}
# keys confirmed absent on disk — without this, every lookup for an
# untuned key would re-open and re-parse the JSON file (per norm call
# in eager mode).  Per-process: a search in THIS process clears its
# key; configs written by another process land after a restart or
# `clear_memory_cache()`.
_MEM_MISS: set = set()
_LOCK = threading.Lock()


def register_tunable(name: str, candidates, build, roofline) -> None:
    """Register one tunable op (idempotent — last registration wins, so
    a module reload doesn't raise)."""
    _REGISTRY[name] = _Tunable(name, candidates, build, roofline)


def tunables() -> List[str]:
    _ensure_builtin()
    return sorted(_REGISTRY)


def _ensure_builtin() -> None:
    """Import the kernel modules that self-register tunables."""
    from . import flash_attention, fused_norm, fused_optimizer  # noqa: F401
    from . import moe_dispatch, paged_attention  # noqa: F401
    from . import quantized_matmul  # noqa: F401


# ---------------------------------------------------------------------------
# cost model
# ---------------------------------------------------------------------------

# (peak flops, HBM bytes/s, per-grid-step overhead s) by device-kind
# substring; the CPU row only needs to RANK candidates (see module doc)
_DEVICE_MODEL = (
    ("v6", 918e12, 1640e9, 2e-7),
    ("trillium", 918e12, 1640e9, 2e-7),
    ("v5 lite", 197e12, 819e9, 2e-7),
    ("v5e", 197e12, 819e9, 2e-7),
    ("v5", 459e12, 2765e9, 2e-7),
    ("v4", 275e12, 1228e9, 2e-7),
    ("cpu", 1e11, 5e10, 2e-6),
)


def device_kind() -> str:
    import jax
    try:
        d = jax.devices()[0]
        return getattr(d, "device_kind", d.platform) or d.platform
    except Exception:
        return "cpu"


def _model_for(kind: str) -> Tuple[float, float, float]:
    k = kind.lower()
    for sub, flops, bw, ovh in _DEVICE_MODEL:
        if sub in k:
            return flops, bw, ovh
    return _DEVICE_MODEL[-1][1:]


def predict_s(tunable: _Tunable, config: BlockConfig,
              shapes: Sequence[int], dtype: str,
              kind: Optional[str] = None) -> float:
    """Analytic time estimate: max(compute roofline, memory roofline)
    plus grid-step overhead — the pruning score."""
    peak, bw, overhead = _model_for(kind or device_kind())
    r = tunable.roofline(config, shapes, dtype)
    return max(r.get("flops", 0.0) / peak, r.get("bytes", 0.0) / bw) \
        + r.get("steps", 1.0) * overhead


# ---------------------------------------------------------------------------
# persistence
# ---------------------------------------------------------------------------

def cache_dir() -> Optional[str]:
    """Resolve the persistence directory: ``MXTPU_AUTOTUNE_CACHE``, else
    an ``autotune/`` subdirectory of the active compile cache
    (`runtime.compile_cache_dir` — tuned block sizes live next to the
    compiled binaries they shaped), else None (in-memory only)."""
    d = os.environ.get("MXTPU_AUTOTUNE_CACHE")
    if d:
        return d
    from ...runtime import compile_cache_dir
    cc = compile_cache_dir()
    return os.path.join(cc, "autotune") if cc else None


def shape_bucket(shapes: Sequence[int]) -> Tuple[int, ...]:
    """Round every dim up to the next power of two: one tuned config
    serves the whole bucket, so ragged batch tails don't re-tune."""
    out = []
    for s in shapes:
        s = int(s)
        out.append(s if s <= 1 else 1 << (s - 1).bit_length())
    return tuple(out)


def _key(op: str, shapes: Sequence[int], dtype: str, kind: str) -> str:
    b = "x".join(str(s) for s in shape_bucket(shapes))
    return f"{op}|{b}|{dtype}|{kind.replace(' ', '_')}"


def _disk_path(op: str) -> Optional[str]:
    d = cache_dir()
    return None if d is None else os.path.join(d, f"autotune_{op}.json")


def _disk_load(op: str) -> Dict[str, dict]:
    path = _disk_path(op)
    if path is None:
        return {}
    try:
        with open(path) as f:
            data = json.load(f)
        return data if isinstance(data, dict) else {}
    except (OSError, json.JSONDecodeError, ValueError):
        return {}

def _disk_store(op: str, key: str, config: BlockConfig,
                extra: Optional[dict] = None) -> None:
    path = _disk_path(op)
    if path is None:
        return
    try:
        os.makedirs(os.path.dirname(path), exist_ok=True)
        data = _disk_load(op)
        data[key] = {"config": dict(config)}
        if extra:
            data[key].update(extra)
        tmp = f"{path}.tmp.{os.getpid()}"
        with open(tmp, "w") as f:
            json.dump(data, f, indent=1, sort_keys=True)
        os.replace(tmp, path)   # atomic: concurrent tuners race benignly
    except OSError:
        pass                    # persistence is best-effort, never fatal


def clear_memory_cache() -> None:
    """Drop the in-process cache (tests; disk entries survive)."""
    with _LOCK:
        _MEM.clear()
        _MEM_MISS.clear()


# ---------------------------------------------------------------------------
# lookup + search
# ---------------------------------------------------------------------------

def _autotune_enabled() -> bool:
    v = os.environ.get("MXTPU_AUTOTUNE", "1").strip().lower()
    return v not in ("0", "false", "off", "no")


def cached_config(op: str, shapes: Sequence[int],
                  dtype: str = "float32") -> Optional[BlockConfig]:
    """Trace-safe lookup of a previously-tuned config (memory, then
    disk).  Returns None when nothing was tuned for this key or when
    ``MXTPU_AUTOTUNE=0`` — kernels then use their static defaults."""
    if not _autotune_enabled():
        return None
    key = _key(op, shapes, dtype, device_kind())
    with _LOCK:
        hit = _MEM.get(key)
        if hit is not None:
            return hit
        if key in _MEM_MISS:
            return None
    entry = _disk_load(op).get(key)
    if entry and isinstance(entry.get("config"), dict):
        cfg = BlockConfig({k: int(v) for k, v in entry["config"].items()})
        with _LOCK:
            _MEM[key] = cfg
        return cfg
    with _LOCK:
        _MEM_MISS.add(key)
    return None


def lookup_any(op: str) -> Optional[BlockConfig]:
    """Any persisted config for this op on this device kind, regardless
    of the shape bucket/dtype it was tuned under — for knobs that are
    per-DEVICE rather than per-shape (the serving page size).  Memory
    first, then disk; trace-safe like `cached_config`."""
    if not _autotune_enabled():
        return None
    kind = device_kind().replace(" ", "_")

    def match(key: str) -> bool:
        parts = key.split("|")
        return len(parts) == 4 and parts[0] == op and parts[3] == kind

    with _LOCK:
        for key, cfg in _MEM.items():
            if match(key):
                return cfg
    for key, entry in sorted(_disk_load(op).items()):
        if match(key) and isinstance(entry.get("config"), dict):
            cfg = BlockConfig(
                {k: int(v) for k, v in entry["config"].items()})
            with _LOCK:
                _MEM[key] = cfg
            return cfg
    return None


def tune(op: str, shapes: Sequence[int], dtype: str = "float32",
         warmup: int = 1, runs: int = 5, top_k: int = 4) -> TuneResult:
    """Pick (and persist) the best BlockConfig for one (op, shapes,
    dtype, device) key.

    Warm path: a memory or disk hit returns immediately with ZERO timed
    trials (``autotune_hits``).  Cold path: the candidate grid from the
    op's registration is pruned to `top_k` by the analytic model, the
    survivors are timed through `opperf.time_callable` (median-of-k,
    fully synchronized), and the winner is written to the JSON cache
    (``autotune_misses`` + ``autotune_search_ms`` + an ``autotune``
    journal event).

    Runs timed work — host code only, never inside a jit trace.
    """
    from ... import telemetry as _tele
    _ensure_builtin()
    if op not in _REGISTRY:
        from ...base import MXNetError
        raise MXNetError(f"unknown tunable op {op!r}; registered: "
                         f"{sorted(_REGISTRY)}")
    tunable = _REGISTRY[op]
    kind = device_kind()
    key = _key(op, shapes, dtype, kind)

    hit = cached_config(op, shapes, dtype)
    if hit is not None:
        if _tele.enabled():
            _tele.counter(
                "autotune_hits",
                "tune() calls served from the persisted/in-memory "
                "config cache (zero timed trials)").inc()
        return TuneResult(hit, True, "memory", 0, 0.0, {})

    t0 = time.perf_counter()
    cands = [c for c in tunable.candidates(shapes, dtype) if c]
    if not cands:
        from ...base import MXNetError
        raise MXNetError(f"tunable {op!r} produced no candidates for "
                         f"shapes={tuple(shapes)} dtype={dtype}")
    # analytic prune: rank by predicted time, keep the top_k survivors
    ranked = sorted(cands, key=lambda c: predict_s(tunable, c, shapes,
                                                   dtype, kind))
    survivors = ranked[:max(1, top_k)]

    from ...benchmark.opperf import time_callable
    timings: Dict[Tuple[Tuple[str, int], ...], float] = {}
    best, best_ms = survivors[0], math.inf
    for cfg in survivors:
        try:
            thunk = tunable.build(cfg, shapes, dtype)
            ms = time_callable(thunk, warmup=warmup,
                               runs=runs)["median_ms"]
        except Exception:
            continue    # an untileable survivor loses, it doesn't abort
        timings[cfg.key()] = ms
        if ms < best_ms:
            best, best_ms = cfg, ms
    search_ms = (time.perf_counter() - t0) * 1e3

    if not timings:
        # EVERY survivor failed to build or run (wrong backend, device
        # OOM mid-search, ...): do NOT pin an unvalidated config — the
        # key stays cold so a later healthy process re-searches instead
        # of inheriting a block size that never even compiled
        if _tele.enabled():
            _tele.counter(
                "autotune_misses",
                "tune() calls that ran a timed search").inc()
            _tele.event("autotune", op=op, key=key, config=None,
                        trials=0, failed=True,
                        search_ms=round(search_ms, 2))
        return TuneResult(best, False, "search", 0, search_ms, {})

    with _LOCK:
        _MEM[key] = best
        _MEM_MISS.discard(key)
    _disk_store(op, key, best, extra={
        "dtype": dtype, "device_kind": kind,
        "median_ms": None if best_ms is math.inf else round(best_ms, 4)})
    # performance-attribution corpus (mx.tracing): pair the winner's
    # analytic cost features with its measured time — one labeled row
    # per tuned key for the learned performance model (ROADMAP item 3).
    # The trial thunks are opaque (they own their jit), so the roofline
    # stands in for XLA's cost_analysis here.
    try:
        from ... import tracing as _trace
        rf = tunable.roofline(best, shapes, dtype)
        _trace.account().record_features(
            f"autotune/{op}/{key}",
            {"flops": float(rf.get("flops", 0.0)),
             "bytes_accessed": float(rf.get("bytes", 0.0))},
            kind="autotune_trial", op=op, config=dict(best),
            measured_ms=(None if best_ms is math.inf
                         else round(best_ms, 4)),
            source="roofline")
    except Exception:   # attribution must never fail a search
        pass
    if _tele.enabled():
        _tele.counter(
            "autotune_misses",
            "tune() calls that ran a timed search").inc()
        _tele.histogram(
            "autotune_search_ms",
            "Wall time of one autotune search (prune + timed trials)"
        ).observe(search_ms)
        _tele.event("autotune", op=op, key=key, config=dict(best),
                    trials=len(timings), search_ms=round(search_ms, 2))
    return TuneResult(best, False, "search", len(timings), search_ms,
                      timings)
