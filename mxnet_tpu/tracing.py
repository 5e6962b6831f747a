"""Distributed tracing + FLOP-accounted performance attribution.

Two instruments, one module (docs/observability.md, "Tracing &
performance attribution"):

* :class:`Tracer` — lightweight spans (trace_id / span_id / parent_id, a
  per-tracer thread-local current-span stack, explicit cross-thread
  context handoff via :meth:`Tracer.current_context`).  Finished spans
  go to the `telemetry.RunJournal` as ``span`` events (when a journal is
  attached) and accumulate in a bounded ring exportable as
  Chrome/Perfetto ``trace_event`` JSON (:func:`export_chrome` — open the
  file in https://ui.perfetto.dev or chrome://tracing).  Instrumentation
  sites live in the serve scheduler (the full request lifecycle:
  queue → admit → prefill chunks → decode steps → finish;
  and one ``serve.step`` span per scheduler step covering the WHOLE
  call, tiled by its five phases ``serve.step.admit`` / ``.plan`` /
  ``.launch`` / ``.wait`` / ``.emit`` — :func:`record_phases`),
  the serve fleet/router (``serve.route`` per dispatch,
  ``serve.failover`` per replica death, ``serve.shed`` per rejection —
  phase spans carry a ``replica`` tag so `tools/diagnose.py --trace`
  can roll a fleet up per replica),
  `ShardedTrainStep` (dispatch → compile → device execute → retire,
  correlated with journal step ids), `DevicePrefetcher` /
  `data.DataPipeline`, `CheckpointManager`, and the elastic reform path.

* :class:`CostAccountant` — a per-executable registry of XLA's own cost
  model: every ``.lower().compile()`` site hands its compiled object to
  :func:`record_executable`, which captures ``cost_analysis()`` +
  ``memory_analysis()`` into a feature vector (flops, bytes accessed,
  argument/output/temp bytes).  At step retire the cost flops combine
  with measured wall time into the always-on ``mfu_estimate`` /
  ``step_flops`` / ``hbm_bytes_est`` gauges, and each ``step_retired``
  journal row carries the feature vector — the labeled
  (cost-features, measured-ms) corpus a learned performance model
  (arxiv 2008.01040) trains on.

MFU semantics: on TPU the estimate is real attribution (XLA-counted
flops / wall / device peak).  On CPU the flop count is still exact for
the compiled program, but the peak is the **projected** peak of the
configured device kind (``MXTPU_MFU_DEVICE_KIND``, default ``v5e``) —
a trajectory proxy for `bench.py`, explicitly NOT a CPU utilization
number (the entry carries ``projected=True``).

Gating contract (the `telemetry.enabled()` idiom): span creation sites
guard on one module-level bool (:func:`enabled` — ``MXTPU_TRACE``), so
a run without tracing pays one boolean read and ZERO allocations per
step.  The serving step's phase spans follow the wider
:func:`capturing` gate instead: ``MXTPU_TRACE`` **or** a live
``jax.profiler`` session — whoever takes a device trace gets the
program's phases beside the device ops (:func:`annotation` writes them
into the profiler's own trace) without setting any switch.  Cost capture happens once per compile (never on the hot path)
and is always on — it is how `bench.py` gets a defensible MFU proxy
without any env vars set.
"""
from __future__ import annotations

import atexit
import collections
import contextlib
import itertools
import json
import logging
import os
import threading
import time
from typing import Dict, List, Optional, Tuple

from jax.profiler import TraceAnnotation as _TraceAnnotation

from . import telemetry as _tele
from .base import MXNetError

__all__ = [
    "Span", "SpanContext", "Tracer", "CostAccountant", "ClockSync",
    "enabled", "enable", "disable", "capturing", "annotation",
    "record_phases", "get_tracer", "tracers", "span",
    "trace_dir", "export_chrome", "chrome_events", "reset",
    "span_to_wire", "note_remote_process", "remote_processes",
    "account", "record_executable", "cost_features_of", "estimate_mfu",
    "peak_flops", "projected_peak_flops", "note_step_cost",
    "ENV_TRACE", "ENV_TRACE_DIR", "ENV_MFU_KIND", "ENV_PEAK_TFLOPS",
]

_log = logging.getLogger(__name__)

ENV_TRACE = "MXTPU_TRACE"
ENV_TRACE_DIR = "MXTPU_TRACE_DIR"
ENV_MFU_KIND = "MXTPU_MFU_DEVICE_KIND"
ENV_PEAK_TFLOPS = "MXTPU_PEAK_TFLOPS"

# spans kept per tracer for export (oldest dropped); a multi-hour run
# with tracing left on must stay bounded in host memory
DEFAULT_SPAN_CAP = 200_000

# ts anchor: chrome trace_event wants wall-clock microseconds, span
# timing wants a monotonic clock — record the pair once and convert
_EPOCH_WALL = time.time()
_EPOCH_PERF = time.perf_counter()

# span-id allocation is salted by pid so spans SHIPPED from a worker
# process into the parent's trace tree (Tracer.ingest) can never
# collide with the parent's own ids — parent_id links must stay
# unambiguous within one trace
_SPAN_ID_BASE = (os.getpid() & 0xFFFFF) << 32


def _wall_us(t_perf: float) -> float:
    return (_EPOCH_WALL + (t_perf - _EPOCH_PERF)) * 1e6


# ---------------------------------------------------------------------------
# spans
# ---------------------------------------------------------------------------

class SpanContext:
    """The portable identity of a span: what another thread needs to
    parent its own spans under it (`Tracer.current_context` →
    ``span(..., parent=ctx)``)."""

    __slots__ = ("trace_id", "span_id")

    def __init__(self, trace_id: str, span_id: int):
        self.trace_id = trace_id
        self.span_id = int(span_id)

    def __repr__(self):
        return f"SpanContext({self.trace_id}, {self.span_id})"


class Span:
    """One timed operation.  Usable as a context manager (lexical spans)
    or via explicit :meth:`finish` (request-lifecycle spans that outlive
    any single call frame)."""

    __slots__ = ("tracer", "name", "trace_id", "span_id", "parent_id",
                 "t0", "t1", "tags", "track", "pid", "_on_stack")

    def __init__(self, tracer: "Tracer", name: str, trace_id: str,
                 span_id: int, parent_id: Optional[int],
                 track: Optional[str], tags: Dict[str, object],
                 t0: Optional[float] = None):
        self.tracer = tracer
        self.name = name
        self.trace_id = trace_id
        self.span_id = span_id
        self.parent_id = parent_id
        self.track = track
        self.tags = tags
        self.t0 = time.perf_counter() if t0 is None else t0
        self.t1: Optional[float] = None
        self.pid: Optional[int] = None  # None = this process; set on ingest
        self._on_stack = False

    def context(self) -> SpanContext:
        return SpanContext(self.trace_id, self.span_id)

    def set_tag(self, key: str, value) -> "Span":
        self.tags[key] = value
        return self

    @property
    def duration_ms(self) -> Optional[float]:
        if self.t1 is None:
            return None
        return (self.t1 - self.t0) * 1e3

    def finish(self, t1: Optional[float] = None, **tags) -> "Span":
        """Close the span (idempotent).  Extra `tags` merge in; manual
        spans pass nothing, post-hoc recorders pass an explicit `t1`."""
        if self.t1 is not None:
            return self
        if tags:
            self.tags.update(tags)
        self.t1 = time.perf_counter() if t1 is None else t1
        self.tracer._finish(self)
        return self

    def __enter__(self):
        return self

    def __exit__(self, exc_type, exc, tb):
        if exc_type is not None:
            self.tags.setdefault("error", exc_type.__name__)
        if self._on_stack:
            self.tracer._pop(self)
        self.finish()
        return False

    def __repr__(self):
        state = "open" if self.t1 is None else f"{self.duration_ms:.3f}ms"
        return (f"Span({self.name}, trace={self.trace_id}, "
                f"id={self.span_id}, parent={self.parent_id}, {state})")


class Tracer:
    """One span namespace (e.g. ``serve``, ``train``, ``data``).

    Each tracer owns its OWN trace-id space and its OWN thread-local
    current-span stack, so a serving engine and a training step tracing
    concurrently in one process can never contaminate each other's
    traces (the trace_id carries the tracer name).  Root spans (no
    parent on the stack, no explicit parent) open a fresh trace_id;
    children inherit the parent's."""

    def __init__(self, name: str, span_cap: int = DEFAULT_SPAN_CAP):
        self.name = name
        self._span_ids = itertools.count(_SPAN_ID_BASE + 1)
        self._trace_ids = itertools.count(1)
        self._local = threading.local()
        self._lock = threading.Lock()
        # deque(maxlen): O(1) eviction at the cap — a list.pop(0) would
        # shift 200k entries under the lock on every finish once full
        self._spans: "collections.deque[Span]" = collections.deque(
            maxlen=int(span_cap))
        self._span_cap = int(span_cap)
        self.dropped = 0

    # -- stack ----------------------------------------------------------
    def _stack(self) -> list:
        st = getattr(self._local, "stack", None)
        if st is None:
            st = self._local.stack = []
        return st

    def current(self) -> Optional[Span]:
        """The innermost open span on THIS thread (or None)."""
        st = self._stack()
        return st[-1] if st else None

    def current_context(self) -> Optional[SpanContext]:
        """Cross-thread handoff: capture on the owning thread, pass the
        context to the worker, parent its spans with ``parent=ctx``."""
        cur = self.current()
        return cur.context() if cur is not None else None

    def _new_trace_id(self) -> str:
        return f"{self.name}-{os.getpid():x}-{next(self._trace_ids):x}"

    def _ids_for(self, parent) -> Tuple[str, Optional[int]]:
        """(trace_id, parent_id) from an explicit parent (Span /
        SpanContext), the thread-local stack, or a fresh root."""
        if parent is not None:  # Span and SpanContext share the fields
            return parent.trace_id, parent.span_id
        cur = self.current()
        if cur is not None:
            return cur.trace_id, cur.span_id
        return self._new_trace_id(), None

    # -- span creation --------------------------------------------------
    def span(self, name: str, parent=None, track: Optional[str] = None,
             **tags) -> Span:
        """Lexical span: ``with tracer.span("phase"): ...`` — pushed on
        the thread-local stack, so nested ``span()`` calls on the same
        thread parent automatically."""
        s = self.start_span(name, parent=parent, track=track, **tags)
        s._on_stack = True
        self._stack().append(s)
        return s

    def start_span(self, name: str, parent=None,
                   track: Optional[str] = None, **tags) -> Span:
        """Manual span: NOT pushed on the stack (finish() explicitly).
        For operations that outlive the creating call frame — a serve
        request, an in-flight train step."""
        trace_id, parent_id = self._ids_for(parent)
        return Span(self, name, trace_id, next(self._span_ids),
                    parent_id, track, dict(tags))

    def record_span(self, name: str, t0: float, t1: float, parent=None,
                    track: Optional[str] = None, **tags) -> Span:
        """Post-hoc span from already-measured perf_counter endpoints
        (per-slot serve phases reconstructed after the fused step ran)."""
        trace_id, parent_id = self._ids_for(parent)
        s = Span(self, name, trace_id, next(self._span_ids), parent_id,
                 track, dict(tags), t0=t0)
        s.finish(t1=t1)
        return s

    def _pop(self, span: Span) -> None:
        st = self._stack()
        if st and st[-1] is span:
            st.pop()
        elif span in st:            # exited out of order: drop through it
            st.remove(span)

    def _finish(self, span: Span) -> None:
        with self._lock:
            if len(self._spans) == self._span_cap:
                self.dropped += 1      # deque maxlen evicts the oldest
            self._spans.append(span)
        if _tele.enabled():
            # a `step` tag intentionally lands as the journal row's step
            # id, correlating the span with step_dispatched/retired rows
            _tele.event("span", span=span.name, tracer=self.name,
                        trace_id=span.trace_id, span_id=span.span_id,
                        parent_id=span.parent_id,
                        dur_ms=round(span.duration_ms, 3),
                        **{k: v for k, v in span.tags.items()
                           if k not in ("span", "tracer", "trace_id",
                                        "span_id", "parent_id", "dur_ms")})

    # -- introspection / export -----------------------------------------
    def spans(self) -> List[Span]:
        with self._lock:
            return list(self._spans)

    def clear(self) -> None:
        with self._lock:
            self._spans.clear()
        self.dropped = 0

    def drain(self) -> List[Span]:
        """Pop every finished span out of the ring (worker processes
        drain on each heartbeat and ship the batch to the parent, so
        the same span is never sent twice)."""
        with self._lock:
            out = list(self._spans)
            self._spans.clear()
        return out

    def ingest(self, rows: List[dict], offset: float = 0.0,
               pid: Optional[int] = None,
               replica: Optional[str] = None) -> int:
        """Adopt finished spans shipped from another process
        (:func:`span_to_wire` dicts).  `offset` is the remote clock's
        perf_counter offset relative to ours (``ClockSync.offset``):
        remote timestamps are rebased by subtracting it, so the adopted
        spans land on THIS process's timeline.  Keeps the remote
        trace/span/parent ids verbatim — that is what stitches the
        cross-process tree together."""
        n = 0
        for row in rows:
            try:
                tags = dict(row.get("tags") or {})
                if replica is not None:
                    tags.setdefault("replica", replica)
                s = Span(self, str(row["name"]), str(row["trace_id"]),
                         int(row["span_id"]),
                         (int(row["parent_id"])
                          if row.get("parent_id") is not None else None),
                         row.get("track"), tags,
                         t0=float(row["t0"]) - offset)
                s.pid = int(pid) if pid is not None else None
                s.finish(t1=float(row["t1"]) - offset)
                n += 1
            except (AttributeError, KeyError, TypeError, ValueError):
                continue   # one malformed row must not drop the batch
        return n


def span_to_wire(s: Span) -> dict:
    """One finished span as a JSON-safe dict for the events channel
    (the inverse of :meth:`Tracer.ingest`).  Timestamps stay in the
    SENDER's perf_counter domain — the receiver rebases with its
    ClockSync offset for this peer."""
    return {"name": s.name, "trace_id": s.trace_id,
            "span_id": s.span_id, "parent_id": s.parent_id,
            "track": s.track, "t0": s.t0, "t1": s.t1,
            "tags": _tele.json_safe(s.tags)}


class ClockSync:
    """NTP-style offset estimator between this process's perf_counter
    and a peer's (docs/observability.md, "Fleet observability").

    Each :meth:`update` sample is one request/response round trip:
    ``offset = remote_ts - (t_send + t_recv) / 2`` — the RTT-halving
    assumption (symmetric paths).  The estimate served is the offset of
    the MINIMUM-RTT sample in a sliding window: low-RTT exchanges bound
    the asymmetry error tightest, and the window lets the estimate
    track drift as old samples age out.  ``rebase`` maps a remote
    timestamp onto the local timeline."""

    __slots__ = ("_window", "offset", "rtt", "samples")

    def __init__(self, window: int = 8):
        self._window: "collections.deque[Tuple[float, float]]" = \
            collections.deque(maxlen=int(window))
        self.offset = 0.0
        self.rtt: Optional[float] = None
        self.samples = 0

    def seed(self, offset: float) -> None:
        """Coarse one-way estimate (the hello handshake timestamp,
        unknown RTT).  Only used until the first real round-trip
        sample — a one-way sample has no RTT bound, so it must never
        outcompete measured ones in the min-RTT selection."""
        if self.samples == 0:
            self.offset = float(offset)

    def update(self, t_send: float, remote_ts: float,
               t_recv: float) -> float:
        rtt = max(0.0, float(t_recv) - float(t_send))
        off = float(remote_ts) - (float(t_send) + float(t_recv)) / 2.0
        self._window.append((rtt, off))
        self.samples += 1
        self.rtt, self.offset = min(self._window, key=lambda s: s[0])
        return self.offset

    def rebase(self, remote_t: float) -> float:
        """A remote perf_counter timestamp on the local timeline."""
        return float(remote_t) - self.offset

    def __repr__(self):
        rtt = "?" if self.rtt is None else f"{self.rtt * 1e3:.3f}ms"
        return (f"ClockSync(offset={self.offset * 1e3:.3f}ms, "
                f"rtt={rtt}, samples={self.samples})")


# ---------------------------------------------------------------------------
# module-level tracer registry + enable gate
# ---------------------------------------------------------------------------

_enabled = False
_trace_dir: Optional[str] = None
_tracers: Dict[str, Tracer] = {}
_remote_procs: Dict[int, str] = {}
_reg_lock = threading.Lock()
_atexit_registered = False


def note_remote_process(pid: Optional[int], name: str) -> None:
    """Name a remote pid whose spans this process ingests — becomes a
    ``process_name`` metadata row in the Perfetto export, so worker
    tracks render under "worker d1" instead of a bare pid."""
    if pid is not None:
        with _reg_lock:
            _remote_procs[int(pid)] = str(name)


def remote_processes() -> Dict[int, str]:
    with _reg_lock:
        return dict(_remote_procs)


def enabled() -> bool:
    """One global read — the zero-cost fast path every span site guards
    on (`MXTPU_TRACE`)."""
    return _enabled


def capturing() -> bool:
    """True while somebody is looking: span collection is on
    (``MXTPU_TRACE`` / :func:`enable`) **or** a ``jax.profiler`` session
    is capturing (``TraceAnnotation.is_enabled()`` — true exactly
    between ``start_trace`` and ``stop_trace``).  The gate of the
    serving step's phase spans: a device trace always comes with the
    program's own phases on its clock, and an untraced run records
    nothing."""
    return _enabled or _TraceAnnotation.is_enabled()


_NO_ANNOTATION = contextlib.nullcontext()


def annotation(name: str, **tags):
    """``with annotation("serve.step.plan"): ...`` — while
    :func:`capturing`, a ``jax.profiler.TraceAnnotation``: a host span in
    the PROFILER's own trace (the ``.xplane.pb``), on the clock of the
    device ops.  Otherwise one shared null context (no allocation).
    The one place the program writes into the profiler's trace
    (`profiler.scope` goes through here too)."""
    return _TraceAnnotation(name, **tags) if capturing() \
        else _NO_ANNOTATION


def record_phases(tracer: "Tracer", name: str, phases, stamps,
                  track: Optional[str] = None, tags=None,
                  phase_tags=None) -> Span:
    """Post-hoc spans of one multi-phase operation from clock stamps
    already taken: a parent `name` over ``stamps[0]..stamps[-1]`` and one
    child ``<name>.<phase>`` per consecutive pair, so the children tile
    the parent by construction (no gap, no overlap).  `tags` go on the
    parent, ``phase_tags[phase]`` on that child."""
    if len(stamps) != len(phases) + 1:
        raise ValueError(f"{len(phases)} phases need {len(phases) + 1} "
                         f"stamps, got {len(stamps)}")
    parent = tracer.record_span(name, stamps[0], stamps[-1], track=track,
                                **(tags or {}))
    phase_tags = phase_tags or {}
    for i, phase in enumerate(phases):
        tracer.record_span(f"{name}.{phase}", stamps[i], stamps[i + 1],
                           parent=parent, track=track,
                           **phase_tags.get(phase, {}))
    return parent


def get_tracer(name: str) -> Tracer:
    """Get-or-create the named tracer (instrumentation sites call this
    once and cache, or call per use — it is a dict lookup)."""
    t = _tracers.get(name)
    if t is None:
        with _reg_lock:
            t = _tracers.get(name)
            if t is None:
                t = _tracers[name] = Tracer(name)
    return t


def tracers() -> Dict[str, Tracer]:
    return dict(_tracers)


def span(name: str, tracer: str = "run", **tags) -> Span:
    """Module facade: a lexical span on the named tracer."""
    return get_tracer(tracer).span(name, **tags)


def trace_dir() -> Optional[str]:
    return _trace_dir


def enable(dir: Optional[str] = None) -> None:
    """Turn span collection on; `dir` (or ``MXTPU_TRACE_DIR``) is where
    :func:`export_chrome` writes by default, and where the atexit hook
    auto-exports when the env enabled tracing."""
    global _enabled, _trace_dir, _atexit_registered
    if dir is not None:
        _trace_dir = os.path.abspath(dir)
    elif _trace_dir is None:
        env_dir = os.environ.get(ENV_TRACE_DIR, "").strip()
        if env_dir:
            _trace_dir = os.path.abspath(env_dir)
    _enabled = True
    if not _atexit_registered:
        atexit.register(_atexit_export)
        _atexit_registered = True


def disable() -> None:
    global _enabled
    _enabled = False


def reset() -> None:
    """Drop every tracer and collected span (tests)."""
    global _trace_dir
    with _reg_lock:
        _tracers.clear()
        _remote_procs.clear()
    _trace_dir = None


def _atexit_export() -> None:
    if not _enabled or _trace_dir is None:
        return
    try:
        if any(t.spans() for t in _tracers.values()):
            export_chrome()
    except Exception:   # export-at-exit must never mask the real exit
        _log.debug("tracing atexit export failed", exc_info=True)


# ---------------------------------------------------------------------------
# Chrome/Perfetto trace_event export
# ---------------------------------------------------------------------------

def chrome_events(include: Optional[List[str]] = None,
                  since: Optional[float] = None) -> List[dict]:
    """All finished spans as Chrome ``trace_event`` dicts.
    ``since`` (a ``time.perf_counter`` instant) keeps only spans that
    were still open at or after it — bounded exports for incident
    capsules.

    Every span becomes a complete ``"ph": "X"`` event.  Tracks: spans
    carry either an explicit ``track`` (serve requests get one per
    request, so concurrent requests render as separate Perfetto rows
    instead of interleaving on one thread track) or the OS thread id
    they ran on; each (process, track) pair gets a synthetic tid plus
    an ``"M"`` thread_name metadata event naming it.  Spans ingested
    from worker processes keep their origin pid, and every remote pid
    named via :func:`note_remote_process` gets a ``process_name``
    metadata row — one export, one Perfetto tree per request, one
    process group per replica."""
    local_pid = os.getpid()
    events: List[dict] = []
    track_tids: Dict[Tuple[int, str], int] = {}
    next_tid = itertools.count(1)

    def tid_for(pid: int, track: str) -> int:
        t = track_tids.get((pid, track))
        if t is None:
            t = track_tids[(pid, track)] = next(next_tid)
        return t

    names = include if include is not None else sorted(_tracers)
    for tname in names:
        tracer = _tracers.get(tname)
        if tracer is None:
            continue
        for s in tracer.spans():
            if s.t1 is None:
                continue
            if since is not None and s.t1 < since:
                continue
            spid = s.pid if s.pid is not None else local_pid
            track = s.track if s.track is not None else f"{tname}"
            args = {"trace_id": s.trace_id, "span_id": s.span_id}
            if s.parent_id is not None:
                args["parent_id"] = s.parent_id
            args.update(_tele.json_safe(s.tags))
            events.append({
                "name": s.name, "ph": "X", "cat": tname,
                "ts": round(_wall_us(s.t0), 3),
                "dur": round((s.t1 - s.t0) * 1e6, 3),
                "pid": spid, "tid": tid_for(spid, track), "args": args,
            })
    meta = [{"name": "thread_name", "ph": "M", "pid": pid, "tid": tid,
             "args": {"name": track}}
            for (pid, track), tid in sorted(track_tids.items(),
                                            key=lambda kv: kv[1])]
    remote = remote_processes()
    seen_pids = {pid for pid, _ in track_tids}
    if remote and seen_pids - {local_pid}:
        # merged multi-process export: name every process group
        meta += [{"name": "process_name", "ph": "M", "pid": local_pid,
                  "args": {"name": f"parent {local_pid}"}}]
        meta += [{"name": "process_name", "ph": "M", "pid": pid,
                  "args": {"name": pname}}
                 for pid, pname in sorted(remote.items())
                 if pid in seen_pids]
    # stable render order: metadata first, then spans by start time
    events.sort(key=lambda e: e["ts"])
    return meta + events


def export_chrome(path: Optional[str] = None,
                  since: Optional[float] = None) -> str:
    """Write the collected spans as a Chrome/Perfetto-loadable JSON
    trace; returns the path (default:
    ``<trace_dir>/trace_<pid>.json``).  ``since`` bounds the export to
    spans still open at/after that ``perf_counter`` instant (capsules)."""
    if path is None:
        d = _trace_dir or os.getcwd()
        os.makedirs(d, exist_ok=True)
        path = os.path.join(d, f"trace_{os.getpid()}.json")
    else:
        d = os.path.dirname(os.path.abspath(path))
        if d:
            os.makedirs(d, exist_ok=True)
    doc = {"traceEvents": chrome_events(since=since),
           "displayTimeUnit": "ms",
           "otherData": {"exporter": "mxnet_tpu.tracing",
                         "pid": os.getpid()}}
    with open(path, "w") as f:
        json.dump(doc, f)
    if _tele.enabled():
        _tele.event("trace_export", path=path,
                    spans=len(doc["traceEvents"]))
    return path


# ---------------------------------------------------------------------------
# cost accounting
# ---------------------------------------------------------------------------

# Peak bf16 matmul FLOP/s per chip, matched by substring of
# `device_kind` — the ONE table the MFU gauge and the bench divide by.
# Source: Google Cloud TPU documentation, the per-version system
# architecture pages ("TPU v5e": 197 TFLOP/s bf16, 819 GB/s HBM;
# "TPU v5p": 459; "TPU v4": 275; "TPU v6e" / Trillium: 918).  A device
# that is not in the table is an error, never a default.
_PEAK_FLOPS = (
    ("v5 lite", 197e12), ("v5e", 197e12), ("v5p", 459e12), ("v5", 459e12),
    ("v4", 275e12), ("v6", 918e12), ("trillium", 918e12),
)


def peak_flops(device_kind: str) -> float:
    """Peak bf16 FLOP/s for a device-kind string; ``MXTPU_PEAK_TFLOPS``
    overrides the table.  An unknown kind raises — a utilization figure
    over a guessed denominator is worse than none."""
    env = os.environ.get(ENV_PEAK_TFLOPS, "").strip()
    if env:
        try:
            return float(env) * 1e12
        except ValueError:
            _log.warning("ignoring non-numeric %s=%r", ENV_PEAK_TFLOPS, env)
    kind = (device_kind or "").lower()
    for key, val in _PEAK_FLOPS:
        if key in kind:
            return val
    raise MXNetError(
        f"no published peak FLOP/s for device kind {device_kind!r}: add it "
        f"to tracing._PEAK_FLOPS with its source, or set {ENV_PEAK_TFLOPS}")


def projected_peak_flops() -> Tuple[float, str]:
    """(peak_flops, kind) for MFU **projection** on a non-TPU backend:
    the device kind the run is being sized for (``MXTPU_MFU_DEVICE_KIND``,
    default ``v5e``)."""
    kind = os.environ.get(ENV_MFU_KIND, "v5e").strip() or "v5e"
    return peak_flops(kind), kind


def estimate_mfu(flops, measured_s: float, device=None) -> Optional[dict]:
    """MFU of `flops` executed in `measured_s` wall seconds on `device`
    (default: the first jax device).  TPU: real peak for the attached
    kind; anything else: the PROJECTED peak of the configured kind
    (``MXTPU_MFU_DEVICE_KIND``) with ``projected=True`` — a trajectory
    proxy, never a CPU utilization claim."""
    if not flops or measured_s is None or measured_s <= 0:
        return None
    if device is None:
        try:
            import jax
            device = jax.devices()[0]
        except Exception:
            device = None
    platform = getattr(device, "platform", "").lower()
    if platform == "tpu":
        peak = peak_flops(getattr(device, "device_kind", ""))
        kind = getattr(device, "device_kind", "tpu")
        projected = False
    else:
        peak, kind = projected_peak_flops()
        projected = True
    achieved = float(flops) / measured_s
    return {"mfu_estimate": achieved / peak,
            "achieved_flops_per_s": achieved,
            "peak_flops": peak, "projected": projected,
            "device_kind": kind}


def cost_features_of(compiled) -> Optional[dict]:
    """Normalize one compiled executable's ``cost_analysis()`` +
    ``memory_analysis()`` into a flat feature dict (the per-op feature
    vector shape the learned performance model consumes).  Returns None
    when the runtime exposes neither (old jaxlib, exotic backend) —
    callers treat that as "no attribution", never an error."""
    cost = None
    try:
        cost = compiled.cost_analysis()
    except Exception:
        cost = None
    if isinstance(cost, (list, tuple)):
        cost = cost[0] if cost else None
    feats: Dict[str, float] = {}
    if isinstance(cost, dict):
        for key, out in (("flops", "flops"),
                         ("bytes accessed", "bytes_accessed"),
                         ("transcendentals", "transcendentals"),
                         ("optimal_seconds", "optimal_seconds")):
            v = cost.get(key)
            if v is not None:
                try:
                    feats[out] = float(v)
                except (TypeError, ValueError):
                    pass
    try:
        mem = compiled.memory_analysis()
    except Exception:
        mem = None
    if mem is not None:
        for attr, out in (
                ("argument_size_in_bytes", "argument_bytes"),
                ("output_size_in_bytes", "output_bytes"),
                ("temp_size_in_bytes", "temp_bytes"),
                ("alias_size_in_bytes", "alias_bytes"),
                ("generated_code_size_in_bytes", "generated_code_bytes")):
            v = getattr(mem, attr, None)
            if v is not None:
                feats[out] = float(v)
        # estimated peak live HBM for one execution: arguments + outputs
        # + XLA temp buffers, minus donated aliases counted twice
        feats["hbm_bytes_est"] = (
            feats.get("argument_bytes", 0.0)
            + feats.get("output_bytes", 0.0)
            + feats.get("temp_bytes", 0.0)
            - feats.get("alias_bytes", 0.0))
    return feats or None


class CostAccountant:
    """Registry of per-executable cost features keyed by a stable name
    (``train_step@<id>``, ``serve_step_c8@<id>``, ``autotune/<op>`` ...).

    `record` is called once per compile — every ``.lower().compile()``
    site in the framework feeds it — so lookups at step retire are one
    dict read.  `mfu` combines an entry's flops with a measured wall
    time and the device peak (projected peak on non-TPU backends)."""

    def __init__(self):
        self._lock = threading.Lock()
        self._entries: Dict[str, dict] = {}

    def record(self, key: str, compiled, **meta) -> Optional[dict]:
        feats = cost_features_of(compiled)
        if feats is None:
            return None
        return self.record_features(key, feats, **meta)

    def record_features(self, key: str, features: dict,
                        **meta) -> dict:
        """Register a pre-computed feature dict (the autotuner's
        analytic roofline for opaque kernel thunks; everything else goes
        through `record`)."""
        entry = {"key": key, "features": dict(features),
                 "meta": dict(meta)}
        with self._lock:
            self._entries[key] = entry
        if _tele.enabled():
            _tele.event("cost_analysis", key=key,
                        flops=features.get("flops"),
                        bytes_accessed=features.get("bytes_accessed"),
                        hbm_bytes_est=features.get("hbm_bytes_est"),
                        **meta)
        return entry

    def get(self, key: str) -> Optional[dict]:
        with self._lock:
            return self._entries.get(key)

    def features(self, key: str) -> Optional[dict]:
        e = self.get(key)
        return dict(e["features"]) if e else None

    def entries(self) -> Dict[str, dict]:
        with self._lock:
            return dict(self._entries)

    def clear(self) -> None:
        with self._lock:
            self._entries.clear()

    def discard(self, key: str) -> None:
        """Drop one entry (a reshard invalidates the old topology's
        cost features; the next compile re-records)."""
        with self._lock:
            self._entries.pop(key, None)

    def mfu(self, key: str, measured_s: float,
            device=None) -> Optional[dict]:
        """MFU estimate for one execution of `key` taking `measured_s`
        wall seconds: ``{"mfu_estimate", "achieved_flops_per_s",
        "peak_flops", "projected", "device_kind"}`` (None when the key
        has no flops or the measurement is degenerate)."""
        e = self.get(key)
        if e is None:
            return None
        return estimate_mfu(e["features"].get("flops"), measured_s,
                            device=device)


_account = CostAccountant()


def account() -> CostAccountant:
    """The process-wide cost registry."""
    return _account


def record_executable(key: str, compiled, **meta) -> Optional[dict]:
    """Facade over ``account().record`` — what the compile sites call.
    Never raises: attribution must not take a compile down."""
    try:
        return _account.record(key, compiled, **meta)
    except Exception:
        _log.debug("cost capture failed for %s", key, exc_info=True)
        return None


def note_step_cost(key: str, measured_s: float,
                   device=None) -> Optional[dict]:
    """Combine one retired execution's measured wall time with its
    executable's recorded cost: updates the always-on ``mfu_estimate`` /
    ``step_flops`` / ``hbm_bytes_est`` gauges (when telemetry is
    enabled) and returns the cost-feature row for the caller to embed
    in its journal record.  One dict lookup + arithmetic — cheap enough
    for every retire."""
    e = _account.get(key)
    if e is None:
        return None
    feats = e["features"]
    mfu = _account.mfu(key, measured_s, device=device)
    row = {"measured_ms": round(measured_s * 1e3, 3)}
    if feats.get("flops"):
        row["flops"] = feats["flops"]
    if feats.get("bytes_accessed"):
        row["bytes_accessed"] = feats["bytes_accessed"]
    if feats.get("hbm_bytes_est"):
        row["hbm_bytes_est"] = feats["hbm_bytes_est"]
    if mfu is not None:
        # full precision: a tiny CPU proxy model's MFU is ~1e-9 and must
        # stay NONZERO (it is a trajectory number, not a pretty one)
        row["mfu_estimate"] = mfu["mfu_estimate"]
        row["mfu_projected"] = mfu["projected"]
    if _tele.enabled():
        # per-program label: a process serving AND training must not
        # have the two executables overwrite each other's gauges
        program = e["meta"].get("kind", "unknown")
        if mfu is not None:
            _tele.gauge(
                "mfu_estimate",
                "Model-flops utilization of the last retired step "
                "(XLA cost_analysis flops / wall / device peak; "
                "PROJECTED peak on non-TPU backends)",
                labelnames=("program",)).set(mfu["mfu_estimate"],
                                             program=program)
        if feats.get("flops"):
            _tele.gauge(
                "step_flops",
                "XLA-counted flops of the executing step program",
                labelnames=("program",)).set(feats["flops"],
                                             program=program)
        if feats.get("hbm_bytes_est"):
            _tele.gauge(
                "hbm_bytes_est",
                "Estimated peak HBM bytes of the executing step "
                "program (args + outputs + temps - aliases)",
                labelnames=("program",)).set(feats["hbm_bytes_est"],
                                             program=program)
    return row


# auto-enable from the environment: MXTPU_TRACE=1 (or a path value,
# which doubles as the trace dir).  Same child-process rule as
# telemetry: spawned workers stay dark.
_env = os.environ.get(ENV_TRACE, "").strip()
if _env and _env.lower() not in ("0", "false", "no", "off") \
        and not _tele._in_child_process():
    _is_path = os.sep in _env
    enable(dir=_env if _is_path else None)
del _env
