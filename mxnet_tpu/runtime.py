"""Runtime feature detection (parity: `python/mxnet/runtime.py` over
`include/mxnet/libinfo.h:132-213`) plus compile-cache warm starts."""
from __future__ import annotations

import os
from collections import namedtuple
from typing import Optional

import jax

__all__ = ["Features", "feature_list", "libinfo_features",
           "enable_compile_cache", "compile_cache_dir"]

Feature = namedtuple("Feature", ["name", "enabled"])

_STATIC = {
    "TPU": None,  # resolved lazily
    "CPU": True,
    "CUDA": False,
    "CUDNN": False,
    "NCCL": False,
    "ONEDNN": False,
    "XLA": True,
    "PALLAS": None,
    "BF16": True,
    "INT64_TENSOR_SIZE": True,
    "DIST_KVSTORE": True,
    "OPENCV": False,
    "BLAS_OPEN": False,
    "SIGNAL_HANDLER": True,
    "PROFILER": True,
    # runtime-observability subsystems (PR 3/4): the metrics/journal
    # substrate and the training-health monitor are always compiled in
    # (both off by default at runtime; MXTPU_TELEMETRY / MXTPU_HEALTH)
    "TELEMETRY": True,
    "HEALTH_MONITOR": True,
    # inference serving stack (PR 6): paged KV cache + ragged paged
    # attention + continuous batching (`mx.serve`, MXTPU_SERVE_*)
    "SERVING": True,
    # ahead-of-time export + offline graph-rewrite pipeline (PR 9):
    # StableHLO artifacts, remat-policy search, zero-retrace loads
    # (`mx.export`, MXTPU_EXPORT_DIR / MXTPU_EXPORT; docs/export.md).
    # Artifacts store their module hash: a load compiles the identical
    # HLO, so the persistent compile cache (`enable_compile_cache`)
    # serves the XLA binary once per cluster.
    "EXPORT": True,
}


def _resolve():
    feats = dict(_STATIC)
    platforms = {d.platform.lower() for d in jax.devices()}
    feats["TPU"] = "tpu" in platforms
    try:
        # NOTE: `import jax.experimental.pallas` would rebind `jax` as a
        # function-local and break the `jax.devices()` call above
        import importlib
        importlib.import_module("jax.experimental.pallas")
        feats["PALLAS"] = True
    except ImportError:
        feats["PALLAS"] = False
    return feats


class Features(dict):
    def __init__(self):
        super().__init__({k: Feature(k, bool(v)) for k, v in _resolve().items()})

    def is_enabled(self, name):
        return self[name.upper()].enabled


def feature_list():
    return list(Features().values())


libinfo_features = feature_list


# ---------------------------------------------------------------------------
# Compile-cache warm starts
# ---------------------------------------------------------------------------
# XLA compiles of a full train step run minutes at BERT/GPT scale, and the
# reference never pays them (its graphs are interpreted per-op).  JAX's
# persistent compilation cache keys executables by HLO + compile options +
# backend, so a restarted (or elastically rescheduled) process re-loads the
# binary instead of recompiling — the warm-start half of the async pipeline
# (`ShardedTrainStep.warmup` is the AOT half).
#
# One rule for where it lives: ``JAX_COMPILATION_CACHE_DIR`` when the
# environment sets it — JAX reads that variable itself and this module never
# overrides it in code, so whoever launches the process places the cache —
# otherwise ``<checkout>/.jax_cache`` (git-ignored; a FIXED path, because the
# path is part of the cache key and a directory that moves never hits).
# Entry points that compile at scale (`chip_smoke.py`, `bench.py`,
# `serve/worker.py`) call `enable_compile_cache()` before their first
# compile; importing the package does not, so a test run writes no cache.

_ENV_DIR = "JAX_COMPILATION_CACHE_DIR"
_DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), ".jax_cache")
_enabled = False


def enable_compile_cache() -> str:
    """Turn JAX's persistent compilation cache on (see the rule above) and
    return its directory.  Every entry is cached regardless of size or
    compile time — a train step that took 0.3 s to compile still costs a
    retrace-stall when it recompiles inline at step 1.  Idempotent; a
    shared filesystem path warms every host of a multi-process mesh."""
    global _enabled
    path = os.environ.get(_ENV_DIR)
    if not path:
        path = _DEFAULT_DIR
        os.makedirs(path, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", path)
    # cache unconditionally: the defaults skip small/fast programs, which
    # is exactly wrong for a step fn re-verified on every restart
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    # hit/miss counters ride jax.monitoring events; the listener is a
    # no-op until telemetry is enabled (docs/observability.md)
    from . import telemetry as _telemetry
    _telemetry.install_compile_cache_listener()
    _enabled = True
    return path


def compile_cache_dir() -> Optional[str]:
    """The directory the persistent compile cache is active at, or None:
    ``JAX_COMPILATION_CACHE_DIR`` whenever it is set (JAX caches there on
    its own), else ``<checkout>/.jax_cache`` once `enable_compile_cache`
    ran.  The autotuner and the export store keep their files in
    subdirectories of it."""
    return os.environ.get(_ENV_DIR) or (_DEFAULT_DIR if _enabled else None)
