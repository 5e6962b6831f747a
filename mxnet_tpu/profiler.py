"""Profiler facade (parity: `python/mxnet/profiler.py:34,125,154` over
`src/profiler/profiler.h:263`).

The reference collects engine-op stats into chrome://tracing JSON plus an
aggregate per-op table (`src/profiler/aggregate_stats.cc`). Here the same
`set_config/start/stop/dump(s)` API drives `jax.profiler`, whose XPlane
traces open in TensorBoard/Perfetto (chrome-trace parity for free), while
aggregate stats are accumulated host-side: when `aggregate_stats=True`,
every imperative op dispatched through `apply_op` is timed (the reference
equivalently wraps each engine op when profiling is on,
`src/engine/threaded_engine.cc:288`), and user scopes
(`ProfileTask`/`scope`) record into the same table. User scopes map to
`jax.profiler.TraceAnnotation` for the trace view, through
`tracing.annotation` (the one place the program writes into the
profiler's trace).
"""
from __future__ import annotations

import json as _json
import os
import threading
import time
from typing import Optional

import jax

from . import tracing as _tracing

__all__ = [
    "set_config", "start", "stop", "pause", "resume", "dump", "dumps",
    "state", "scope", "Task", "Frame", "Event", "Counter", "Marker",
    "step_annotation",
]


def step_annotation(name: str = "train", step_num: Optional[int] = None):
    """Step-boundary marker for the XPlane trace (the engine-profiler's
    per-iteration spans, TPU-native): wraps
    `jax.profiler.StepTraceAnnotation`, which TensorBoard/Perfetto use to
    segment the timeline into steps and derive step time and input-
    pipeline (prefetch) overlap.  `ShardedTrainStep.dispatch` wraps every
    step in one; use directly around custom loops:

        with mx.profiler.step_annotation("train", step_num=i):
            loss = step.dispatch(*batch)

    Cheap when no trace is active — safe to leave on every step."""
    return jax.profiler.StepTraceAnnotation(name, step_num=step_num)

_config = {"profile_all": False, "filename": "profile_output",
           "aggregate_stats": False, "running": False}

# name -> [count, total_s, min_s, max_s]; guarded by _agg_lock (imperative
# ops may run from DataLoader worker threads)
_agg: dict = {}
_agg_lock = threading.Lock()
_counters: dict = {}
# chrome://tracing events [(name, t_begin_s, dur_s, tid)], bounded
_events: list = []
_MAX_EVENTS = 200_000


def _record_stat(name: str, elapsed_s: float) -> None:
    now = time.perf_counter()
    warn_cap = False
    with _agg_lock:
        st = _agg.get(name)
        if st is None:
            _agg[name] = [1, elapsed_s, elapsed_s, elapsed_s]
        else:
            st[0] += 1
            st[1] += elapsed_s
            if elapsed_s < st[2]:
                st[2] = elapsed_s
            if elapsed_s > st[3]:
                st[3] = elapsed_s
        if len(_events) < _MAX_EVENTS:
            _events.append((name, now - elapsed_s, elapsed_s,
                            threading.get_ident()))
        elif not _config.get("_events_truncated"):
            _config["_events_truncated"] = True
            _events.append(("<TRACE TRUNCATED: event cap reached>",
                            now, 0.0, threading.get_ident()))
            warn_cap = True
    if warn_cap:  # log OUTSIDE the lock every op dispatch takes
        import logging
        logging.getLogger(__name__).warning(
            "profiler: chrome-trace event cap (%d) reached; later "
            "ops are not recorded in the trace", _MAX_EVENTS)


def set_config(**kwargs):
    _config.update(kwargs)


def _ndarray_module():
    import importlib
    return importlib.import_module("mxnet_tpu.ndarray.ndarray")


def start():
    out = _config.get("filename", "profile_output")
    outdir = out if not out.endswith(".json") else out + "_dir"
    os.makedirs(outdir, exist_ok=True)
    try:
        jax.profiler.start_trace(outdir)
        _config["tracing"] = True
    except Exception:  # trace already running, or backend quirk
        _config["tracing"] = False
    _config["running"] = True
    _config["outdir"] = outdir
    _config["_events_truncated"] = False
    with _agg_lock:
        _events.clear()  # no stale events from a previous session
    if _config.get("aggregate_stats"):
        _ndarray_module()._op_profile_hook = _record_stat


def stop():
    if _config.get("running"):
        _ndarray_module()._op_profile_hook = None
        if _config.get("tracing"):
            jax.profiler.stop_trace()
        _config["running"] = False


def pause(profile_process="worker"):
    """Temporarily stop collecting aggregate stats (trace keeps running).
    No-op when the profiler isn't running: a pause() before start() (a
    worker pausing around its own setup, say) must not clobber the hook
    state a later start() installs."""
    if _config.get("running"):
        _ndarray_module()._op_profile_hook = None


def resume(profile_process="worker"):
    if _config.get("running") and _config.get("aggregate_stats"):
        _ndarray_module()._op_profile_hook = _record_stat


def dump(finished=True, profile_process="worker"):
    """Stop (like the reference's finished=True) and write the collected
    op events as chrome://tracing JSON to `filename` (parity:
    `src/profiler/profiler.h:87,441` DumpProfile; open in
    chrome://tracing or Perfetto). The XPlane trace from `jax.profiler`
    lands separately under the trace directory."""
    if finished and _config.get("running"):
        stop()  # finished=False: snapshot and keep collecting
    out = _config.get("filename", "profile_output")
    if not out.endswith(".json"):
        out = out + ".json"
    with _agg_lock:
        events = list(_events)
        if finished:
            _events.clear()
    trace = {"traceEvents": [
        {"name": name, "ph": "X", "cat": "op",
         "ts": t0 * 1e6, "dur": dur * 1e6, "pid": os.getpid(), "tid": tid}
        for name, t0, dur, tid in events]}
    with open(out, "w") as f:
        _json.dump(trace, f)
    return out


def dumps(reset=False, format="table", sort_by="total", ascending=False):
    """Return aggregate stats (parity: `python/mxnet/profiler.py:154` over
    `src/profiler/aggregate_stats.cc`).

    format: "table" (reference-style text table) or "json".
    sort_by: one of "total", "avg", "min", "max", "count".
    """
    with _agg_lock:
        rows = [(name, st[0], st[1] * 1e3, st[2] * 1e3, st[3] * 1e3,
                 st[1] * 1e3 / st[0])
                for name, st in _agg.items()]
        counters = dict(_counters)
        if reset:
            # resets aggregate stats only (reference semantics); the
            # chrome-trace buffer lives until the next start()
            _agg.clear()
            _counters.clear()

    key_idx = {"count": 1, "total": 2, "min": 3, "max": 4, "avg": 5}
    idx = key_idx.get(sort_by, 2)
    rows.sort(key=lambda r: r[idx], reverse=not ascending)

    if format == "json":
        return _json.dumps({
            "Time": {name: {"Count": c, "Total": t, "Min": mn, "Max": mx,
                            "Avg": avg}
                     for name, c, t, mn, mx, avg in rows},
            "Unit": "ms",
            "Counters": counters,
        })

    lines = ["", "Profile Statistics:",
             "\tNote the difference in units for different entries."]
    lines.append("Device Time (imperative ops + user scopes)")
    lines.append("=" * 42)
    hdr = (f"{'Name':<40s} {'Total Count':>12s} {'Time (ms)':>14s} "
           f"{'Min Time (ms)':>14s} {'Max Time (ms)':>14s} "
           f"{'Avg Time (ms)':>14s}")
    lines.append(hdr)
    lines.append(f"{'----':<40s} {'-----------':>12s} {'---------':>14s} "
                 f"{'-------------':>14s} {'-------------':>14s} "
                 f"{'-------------':>14s}")
    for name, c, t, mn, mx, avg in rows:
        lines.append(f"{name[:40]:<40s} {c:>12d} {t:>14.4f} {mn:>14.4f} "
                     f"{mx:>14.4f} {avg:>14.4f}")
    if counters:
        lines.append("")
        lines.append("Counters")
        lines.append("=" * 8)
        for name, v in sorted(counters.items()):
            v_str = f"{v:d}" if isinstance(v, int) else f"{v:g}"
            lines.append(f"{name[:40]:<40s} {v_str:>12s}")
    lines.append("")
    return "\n".join(lines)


def state():
    return "RUNNING" if _config.get("running") else "STOPPED"


class scope:
    """Named profiling scope (parity: profiler scopes `profiler.h:772`).

    Records into the trace (TraceAnnotation) and, when the profiler is
    running, into the aggregate-stats table.
    """

    def __init__(self, name="<unk>:"):
        self._name = name
        self._t = None
        self._t0 = None

    def __enter__(self):
        self._t = _tracing.annotation(self._name)
        self._t.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        if self._t0 is not None:
            if _config.get("running"):
                _record_stat(self._name, time.perf_counter() - self._t0)
            self._t0 = None
        self._t.__exit__(*exc)
        return False


class Task(scope):
    def __init__(self, name="task", domain=None):
        super().__init__(name)
        self.start_time = None

    def start(self):
        self.__enter__()

    def stop(self):
        self.__exit__(None, None, None)


Frame = Task
Event = Task


class Counter:
    def __init__(self, name="counter", domain=None, value=0):
        self.name = name
        self.set_value(value)

    def set_value(self, value):
        # recorded unconditionally (not gated on `running`): a counter set
        # before start() would otherwise be silently dropped, and dumps()
        # after a late start() would miss it. dumps(reset=True) clears.
        self.value = value
        with _agg_lock:
            _counters[self.name] = value

    def increment(self, delta=1):
        self.set_value(self.value + delta)

    def decrement(self, delta=1):
        self.set_value(self.value - delta)


class Marker:
    def __init__(self, name="marker", domain=None):
        self.name = name

    def mark(self, scope_="process"):
        if _config.get("running"):
            _record_stat(f"marker:{self.name}", 0.0)
