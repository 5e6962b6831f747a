"""Supervised serving fleet: N `InferenceEngine` replicas behind one
router, with replica supervision, mid-stream failover, graceful drain,
and (since the process transport) worker respawn.

Two replica transports share ONE supervision/state machine
(``MXTPU_FLEET_TRANSPORT``, docs/serving.md "Process fleet"):

- ``thread`` — a replica is an in-process driver thread pumping its own
  engine (the `parallel/elastic_mesh.py` host-simulation pattern);
- ``process`` — a replica is a real OS process (`serve.worker`) spawned
  via ``subprocess`` and reached over the `serve.wire` RPC protocol.
  The router keeps a per-request **stream ledger** (the local
  `ServeRequest` objects, fed token-by-token by the stream RPC), so a
  ``kill -9``'d worker — which has no scheduler left to `salvage()` —
  still fails over from the parent's copy of each stream: the emitted
  tokens fold into the re-prefill prefix (the eviction rule) and greedy
  streams resume bit-identical on a survivor, never re-emitting.

Supervision protocol (docs/serving.md "Fleet, failover & overload"):

- every replica touches a per-replica heartbeat
  (``serve.replica.<name>`` via `health.beat`) — thread drivers once
  per loop, process workers via ~5 Hz heartbeat events;
- a **supervisor thread** declares a replica dead on (a) an escaped
  exception from its step loop, (b) a driver thread / worker process /
  event stream that exited without reporting, or (c) a heartbeat older
  than ``stall_timeout`` while the replica holds work — the
  wedged-in-device-call (or ``SIGSTOP``-wedged-socket) case;
- a dead replica is retired WHOLE and its in-flight requests are
  **salvaged** (from its scheduler, or from the stream ledger when the
  process is simply gone) and re-dispatched through the router;
- a dead replica **respawns** under a fleet-wide budget
  (``MXTPU_REPLICA_RESPAWNS`` — the dataloader-worker pattern): a fresh
  engine/worker replaces it under the same name, journalled as a
  ``replica_respawn`` event.  An exhausted budget degrades to the old
  permanently-shrinking behavior with a loud log;
- `drain()` is the graceful inverse — for process replicas it travels
  over the wire: the worker detaches its queued work (handed back to
  the router), finishes its active streams, reports ``drained`` and
  exits cleanly.

Failure matrix: see docs/serving.md.  Chaos: ``replica_step`` (die
mid-step), ``router_dispatch`` (dispatch edge), ``rpc_send`` /
``rpc_recv`` (dropped control frames), ``worker_spawn`` (spawn
failure) — `make fleet-smoke` and `make procfleet-smoke` arm them and
assert zero dropped requests and bit-identical streams.
"""
from __future__ import annotations

import dataclasses
import logging
import math
import os
import shutil
import subprocess
import sys
import tempfile
import threading
import time
from collections import OrderedDict, deque
from typing import List, Optional, Tuple

import jax

from ..base import MXNetError
from ..resilience import fault_point
from .. import health as _health
from .. import slo as _slo
from .. import telemetry as _tele
from .. import tracing as _trace
from .engine import InferenceEngine, ServeConfig, _env_int
from .router import RequestRouter
from . import qos as _qos
from . import traffic as _traffic
from .scheduler import (ContinuousBatchingScheduler, ServeRequest,
                        deliver_token, expire_request, finish_request,
                        terminate_request)
from . import wire

__all__ = ["ServeFleet", "Replica", "ProcessReplica", "worker_env"]

_log = logging.getLogger(__name__)

#: how often the supervisor refreshes each process replica's clock
#: offset (seconds); the hello timestamp seeds a coarse estimate and
#: the first post-ready `clock` RPC replaces it with an RTT-halved one
ENV_CLOCK_SYNC = "MXTPU_CLOCK_SYNC_INTERVAL"

#: observability env vars that must NOT leak from the parent into
#: spawned workers: an inherited metrics port would collide on bind,
#: an inherited journal/trace path would interleave worker rows into
#: (or clobber) the parent's files, and an inherited SLO spec would
#: run a second, conflicting burn evaluator per worker
_SCOPED_ENV = ("MXTPU_METRICS_PORT", "MXTPU_TELEMETRY",
               "MXTPU_TRACE", "MXTPU_TRACE_DIR", "MXTPU_SLO_SPEC",
               "MXTPU_TRAFFIC_JOURNAL", "MXTPU_CAPSULE_DIR")


def worker_env(base: Optional[dict] = None) -> dict:
    """The spawn environment for a `serve.worker` process: the parent's
    env with the parent-only observability vars scoped out, plus
    ``MXTPU_WORKER_OBS`` telling the worker which planes to run locally
    (shipping rows/spans over the events channel instead of writing
    files or binding ports)."""
    env = dict(os.environ if base is None else base)
    for key in _SCOPED_ENV:
        env.pop(key, None)
    obs = []
    if _tele.enabled():
        obs.append("telemetry")
    if _trace.enabled():
        obs.append("trace")
    if obs:
        env["MXTPU_WORKER_OBS"] = ",".join(obs)
    else:
        env.pop("MXTPU_WORKER_OBS", None)
    return env


class Replica:
    """One supervised serving replica: an engine plus its driver thread.

    ``state`` lifecycle: ``starting`` (accepts work, driver not yet
    running) → ``running`` → ``draining`` → ``drained``, or → ``dead``
    (exception/stall/kill), or → ``stopped`` (fleet closed).  Dead,
    drained and stopped are terminal — but a dead replica may be
    REPLACED by a respawned one under the same name
    (``MXTPU_REPLICA_RESPAWNS``)."""

    transport = "thread"

    def __init__(self, name: str, engine):
        self.name = name
        self.engine = engine
        self.state = "starting"
        self.thread: Optional[threading.Thread] = None
        self.wake = threading.Event()
        self.drained_event = threading.Event()
        self.error: Optional[str] = None
        self.pid: Optional[int] = os.getpid()
        #: respawn lineage: 0 = original, +1 per respawn under this name
        self.generation = 0

    @property
    def heartbeat_name(self) -> str:
        return f"serve.replica.{self.name}"

    def notify(self) -> None:
        self.wake.set()

    def start_driver(self, fleet: "ServeFleet") -> None:
        self.thread = threading.Thread(
            target=fleet._drive, args=(self,), daemon=True,
            name=f"serve-replica-{self.name}")
        self.thread.start()

    def probe(self, ages: dict, stall_timeout: float) -> Optional[str]:
        """Supervisor liveness check; an error string means dead."""
        sched = self.engine.scheduler
        busy = sched.active_count or sched.queue_depth
        if self.thread is not None and not self.thread.is_alive():
            # backstop: the driver died without reporting
            return "driver thread exited"
        age = ages.get(self.heartbeat_name)
        if age is not None and age > stall_timeout and busy:
            return (f"replica stalled: no heartbeat for "
                    f"{age:.1f}s (> {stall_timeout:.1f}s) "
                    f"with work in flight")
        return None

    def terminate(self, force: bool = False) -> None:
        """Tear down transport resources (no-op for thread replicas —
        the driver exits on the state check)."""

    def __repr__(self):
        s = self.engine.scheduler
        return (f"Replica({self.name}, {self.state}, active="
                f"{s.active_count}, queued={s.queue_depth})")


# ---------------------------------------------------------------------------
# process transport: remote engine/scheduler proxies + the worker handle
# ---------------------------------------------------------------------------

class _RemoteAllocator:
    """Stats-only stand-in for `PageAllocator`: the router's load scores
    and `validate_request` read page counts; the REAL allocator lives in
    the worker.  ``free_pages`` mirrors the worker's heartbeats."""

    def __init__(self, page_size: int, num_pages: int):
        self.page_size = int(page_size)
        self.num_pages = int(num_pages)
        self.free_pages = self.total_pages

    @property
    def total_pages(self) -> int:
        return self.num_pages - 1          # page 0 is the null page

    def pages_for(self, tokens: int) -> int:
        return max(1, math.ceil(tokens / self.page_size))

    def shared_pages(self) -> int:
        return 0


class _Ledger:
    """Stream-ledger entry: the caller-side request plus the token
    offset its current dispatch started from (re-dispatch folds emitted
    tokens into the prompt, so the worker's token indices restart at 0)
    and a stash for any out-of-order arrival."""

    __slots__ = ("req", "base", "stash")

    def __init__(self, req: ServeRequest):
        self.req = req
        self.base = len(req.tokens)
        self.stash = {}


class _RemoteScheduler:
    """Parent-side proxy for a worker's scheduler: dispatch goes over
    the wire, stream events mirror back onto the ledgered
    `ServeRequest` objects through the SAME `deliver_token` /
    `finish_request` / `terminate_request` paths the in-process
    scheduler uses.  `salvage()` — the whole point — needs no worker at
    all: the ledger IS the in-flight set."""

    def __init__(self, engine: "_RemoteEngine", name: str):
        self.engine = engine
        sc = engine.serve_config
        self.max_slots = sc.max_slots
        self.page_size = sc.page_size
        self.max_len = engine.max_len
        self.allocator = engine.allocator
        self.name = name
        self.draining = False
        self.salvage_on_error = True
        self._abandoned = False
        # reentrant: an on_token callback delivered under this lock may
        # re-enter (e.g. submit a follow-up request through the router)
        self._lock = threading.RLock()
        self._ledger: "OrderedDict[int, _Ledger]" = OrderedDict()
        self._stats = {"queued": 0, "active": 0}
        self._submitted_since_hb = 0
        self.replica: Optional["ProcessReplica"] = None

    # the one validation authority — shared with the in-process
    # scheduler by calling its method on this duck-typed proxy (it only
    # reads ``max_len`` and ``allocator``)
    validate_request = ContinuousBatchingScheduler.validate_request

    # -- router-facing surface -----------------------------------------
    @property
    def queue_depth(self) -> int:
        return self._stats["queued"] + self._submitted_since_hb

    @property
    def active_count(self) -> int:
        return self._stats["active"]

    @property
    def inflight(self) -> int:
        """Ledgered (dispatched, unfinished) requests — the busy signal
        for quiesce/stall checks; heartbeat stats may lag."""
        with self._lock:
            return len(self._ledger)

    def enqueue(self, req: ServeRequest, front: bool = False) -> None:
        """Dispatch one request to the worker (the router's edge).  Any
        wire failure raises `MXNetError` — the router parks the request
        instead of dropping it.  Retried frames are safe: the worker
        dedupes by the router-assigned rid."""
        with self._lock:
            if self.draining or self._abandoned:
                raise MXNetError(
                    f"replica {self.name} is "
                    f"{'draining' if self.draining else 'retired'} and "
                    f"not accepting requests")
        rep = self.replica
        if rep is None or not rep.ready.is_set():
            raise MXNetError(
                f"replica {self.name} is not connected yet "
                f"(worker warming up)")
        remaining = 0.0
        if req.deadline_ms > 0:
            remaining = max(1.0, req.deadline_ms - (
                time.perf_counter() - req.submitted_ts) * 1e3)
        rep.call(
            "submit", rid=req.id, prompt=req._sequence(),
            attempt=req._epoch,
            max_new=req.max_new_tokens - len(req.tokens),
            greedy=req.greedy, temperature=req.temperature,
            eos=req.eos_token_id, front=bool(front),
            deadline_ms=remaining, tenant=req.tenant,
            _span_parent=(req._span.context()
                          if req._span is not None else None),
            _track=f"serve req {req.id}")
        with self._lock:
            if self._abandoned:
                # the replica died between the accepted RPC and this
                # insert; its salvage already ran — re-park via the
                # router (the worker that accepted the frame is gone)
                raise MXNetError(
                    f"replica {self.name} retired during dispatch")
            self._ledger[req.id] = _Ledger(req)
            self._submitted_since_hb += 1
        req.state = "queued"

    # -- event mirror (the ProcessReplica reader thread) ---------------
    def on_hb(self, ev: dict) -> None:
        with self._lock:
            self._stats["queued"] = int(ev.get("queued", 0))
            self._stats["active"] = int(ev.get("active", 0))
            self._submitted_since_hb = 0
        fp = ev.get("free_pages")
        if fp is not None:
            self.allocator.free_pages = int(fp)

    def on_token(self, rid: int, i: int, tok: int) -> None:
        """Apply one streamed token to the ledger: contiguous tokens
        deliver, duplicates drop, gaps stash until filled — the stream
        can never re-emit or skip."""
        with self._lock:
            if self._abandoned:
                return
            e = self._ledger.get(rid)
            if e is None:
                return               # finished/salvaged: late event
            req = e.req
            if i < len(req.tokens) - e.base:
                return               # duplicate
            e.stash[i] = int(tok)
            while True:
                t = e.stash.pop(len(req.tokens) - e.base, None)
                if t is None:
                    return
                if deliver_token(req, t, replica=self.name):
                    self._ledger.pop(rid, None)
                    finish_request(req, replica=self.name)
                    return

    def on_done(self, rid: int, state: str, tokens: List[int],
                error: Optional[str], expired: bool) -> None:
        """Terminal record from the worker (carries the FULL token
        list): reconcile any tokens whose ``tok`` frames raced the
        close, then finish/fail through the shared terminal paths."""
        with self._lock:
            if self._abandoned:
                return
            e = self._ledger.pop(rid, None)
            if e is None:
                return
            req = e.req
            if state == "finished":
                for t in tokens[len(req.tokens) - e.base:]:
                    if deliver_token(req, int(t), replica=self.name):
                        break
                finish_request(req, replica=self.name)
            elif expired:
                expire_request(req, "active", replica=self.name)
            else:
                terminate_request(
                    req, error or "worker reported failure",
                    state="failed", phase="failed", replica=self.name,
                    generated=len(req.tokens))

    # -- disaggregation: ledger custody moves with the KV handoff ------
    def handoff_out(self, rid: int, tokens: List[int]
                    ) -> Optional[_Ledger]:
        """Take custody of a ledgered request at prefill-complete time:
        reconcile the worker's token list (``tok`` frames may race the
        ``prefilled`` event), then pop the entry — the fleet's handoff
        pump owns the stream until the decode replica adopts it.
        Returns None when there is nothing to hand off (request already
        finished/salvaged)."""
        with self._lock:
            if self._abandoned:
                return None
            e = self._ledger.pop(rid, None)
            if e is None:
                return None
            req = e.req
            for t in tokens[len(req.tokens) - e.base:]:
                if deliver_token(req, int(t), replica=self.name):
                    finish_request(req, replica=self.name)
                    return None
        return None if req.done() else e

    def adopt_ledger(self, rid: int, entry: _Ledger) -> None:
        """Install a ledger entry moved in from the prefill replica.
        The decode worker pre-seeds the FULL parent token list, so its
        token indices are absolute — reset ``base`` to 0 (a folded
        re-dispatch left it at the fold offset) and drop any stash
        keyed in the old worker's numbering."""
        with self._lock:
            if self.draining or self._abandoned:
                raise MXNetError(
                    f"replica {self.name} is "
                    f"{'draining' if self.draining else 'retired'} and "
                    f"not adopting handoffs")
            entry.base = 0
            entry.stash.clear()
            self._ledger[rid] = entry
            self._submitted_since_hb += 1

    def drop_ledger(self, rid: int) -> Optional[_Ledger]:
        with self._lock:
            return self._ledger.pop(rid, None)

    # -- fleet hooks -----------------------------------------------------
    def detach_queued(self) -> List[ServeRequest]:
        """Drain-over-the-wire: the worker detaches its queued requests
        and returns their rids; the matching ledger entries hand back to
        the router while the worker's actives run to completion."""
        rep = self.replica
        if rep is None:
            return []
        try:
            resp = rep.call("drain")
        except MXNetError:
            # worker unreachable mid-drain: the supervisor will declare
            # it dead and salvage the whole ledger instead
            return []
        out: List[ServeRequest] = []
        with self._lock:
            for rid in resp.get("queued", []):
                e = self._ledger.pop(rid, None)
                if e is not None and not e.req.done():
                    out.append(e.req)
        for r in out:
            r.state = "queued"
        return out

    def salvage(self, lock_timeout: float = 5.0) -> List[ServeRequest]:
        """Retire this proxy and return every ledgered request
        un-terminated — requests with streamed progress first, each with
        its epoch bumped so any late wire event is discarded.  The
        SIGKILL path: no worker participates."""
        with self._lock:
            self._abandoned = True
            entries = list(self._ledger.values())
            self._ledger.clear()
            self._submitted_since_hb = 0
            self._stats["queued"] = self._stats["active"] = 0
        progressed = [e.req for e in entries if e.req.tokens]
        fresh = [e.req for e in entries if not e.req.tokens]
        reqs = [r for r in progressed + fresh if not r.done()]
        for r in reqs:
            r._epoch += 1
            r.state = "queued"
        return reqs


class _RemoteEngine:
    """Engine-shaped proxy for a worker process: carries the config /
    capacity math the router and validation need; the compiled step and
    the KV pool live in the worker."""

    def __init__(self, model_cfg, serve_config: ServeConfig, name: str,
                 role: Optional[str] = None, tp: Optional[int] = None):
        self.cfg = model_cfg
        self.serve_config = serve_config
        #: mirrored role/tp of the REMOTE engine (per-worker overrides
        #: of the fleet-wide spec) — the router's role-aware dispatch
        #: and the handoff pump read these
        self.role = role or serve_config.role
        self.tp = tp or serve_config.tp
        self.max_len = serve_config.max_len or model_cfg.max_position
        self.max_pages_per_seq = max(
            1, math.ceil(self.max_len / serve_config.page_size))
        num_pages = serve_config.num_pages or \
            serve_config.max_slots * self.max_pages_per_seq + 1
        self.allocator = _RemoteAllocator(serve_config.page_size,
                                          num_pages)
        self.prefix_index = None
        self._steps_executed = 0           # mirrored from heartbeats
        self.scheduler = _RemoteScheduler(self, name)


class ProcessReplica(Replica):
    """A replica hosted in a spawned `serve.worker` process, reached
    over the wire protocol.  Same lifecycle/supervision surface as the
    thread replica; `engine` is a `_RemoteEngine` proxy whose scheduler
    keeps the stream ledger."""

    transport = "process"

    def __init__(self, name: str, fleet: "ServeFleet", idx: int):
        super().__init__(name,
                         _RemoteEngine(fleet.model.cfg, fleet.config,
                                       name, role=fleet._role_for(idx),
                                       tp=fleet._tp_for(idx)))
        self.engine.scheduler.replica = self
        self._fleet = fleet
        self._idx = idx
        self.proc: Optional[subprocess.Popen] = None
        self.pid = None
        self.ready = threading.Event()
        self.compile_seconds: Optional[float] = None
        self._control: Optional[wire.WireClient] = None
        self._events = None
        self._reader: Optional[threading.Thread] = None
        #: worker perf_counter offset vs ours — rebases shipped span
        #: timestamps onto the parent timeline
        self.clock = _trace.ClockSync()
        self._last_clock_sync = 0.0

    def call(self, verb: str, **kw) -> dict:
        c = self._control
        if c is None:
            raise wire.WireError(
                f"replica {self.name} has no control channel")
        return c.call(verb, **kw)

    def spawn(self, timeout: float = 120.0) -> None:
        """`worker_spawn` fault point, then ``python -m
        mxnet_tpu.serve.worker`` against the fleet's spec dir; blocks
        until the worker connected both channels AND reported ready
        (engine rebuilt + warmed)."""
        fault_point("worker_spawn")
        fleet = self._fleet
        listener = fleet._ensure_listener()
        listener.expect(self.name)
        t0 = time.perf_counter()
        cmd = [sys.executable, "-m", "mxnet_tpu.serve.worker",
               "--name", self.name, "--host", listener.host,
               "--port", str(listener.port),
               "--spec", fleet._write_spec(),
               "--seed", str(fleet._seed + self._idx),
               # the spec dir is fleet-wide; role/tp specialize it
               # per worker (disaggregation)
               "--role", self.engine.role,
               "--tp", str(self.engine.tp),
               # the worker refuses to serve from any other platform
               # (a chip it could not claim leaves it on the CPU)
               "--platform", jax.default_backend()]
        self.proc = subprocess.Popen(cmd, env=worker_env())
        try:
            control, events, hello = listener.wait(
                self.name, timeout=timeout,
                alive=lambda: self.proc.poll() is None)
            self.pid = hello.get("pid") or self.proc.pid
            if hello.get("ts") is not None:
                # coarse one-way offset from the hello timestamp
                # (handshake latency error); the first `clock` RPC
                # below replaces it with an RTT-halved estimate
                try:
                    self.clock.seed(float(hello["ts"])
                                    - time.perf_counter())
                except (TypeError, ValueError):
                    pass
            self._control = wire.WireClient(control, replica=self.name)
            self._events = events
            self._reader = threading.Thread(
                target=self._read_events, daemon=True,
                name=f"serve-wire-{self.name}")
            self._reader.start()
            deadline = time.monotonic() + timeout
            while not self.ready.wait(0.1):
                if self.proc.poll() is not None:
                    raise MXNetError(
                        f"worker {self.name} exited "
                        f"(rc={self.proc.returncode}) during warmup")
                if time.monotonic() > deadline:
                    raise MXNetError(
                        f"worker {self.name} never became ready "
                        f"within {timeout:.0f}s")
        except BaseException:
            self.terminate(force=True)
            raise
        _health.beat(self.heartbeat_name)
        self.sync_clock()
        if _trace.enabled():
            _trace.note_remote_process(self.pid,
                                       f"worker {self.name}")
            _trace.get_tracer("serve").record_span(
                "serve.replica", t0, time.perf_counter(),
                track="serve fleet", replica=self.name,
                transport=self.transport, pid=self.pid,
                generation=self.generation,
                compile_seconds=self.compile_seconds)

    def sync_clock(self) -> Optional[float]:
        """One RTT-halving clock exchange (``clock`` RPC): feeds the
        min-RTT offset estimator.  Best-effort — a wedged worker must
        not take the supervisor down with it."""
        try:
            t_send = time.perf_counter()
            resp = self.call("clock", _timeout_ms=2000)
            off = self.clock.update(t_send, float(resp["ts"]),
                                    time.perf_counter())
        except Exception:
            return None
        self._last_clock_sync = time.monotonic()
        return off

    def start_driver(self, fleet: "ServeFleet") -> None:
        pass      # no driver thread: the reader + supervisor own liveness

    def _read_events(self) -> None:
        """Drain the worker's event stream.  EOF (or a wire error) with
        the replica still non-terminal means the worker died — the
        fast-path death report (the supervisor's poll is the backstop)."""
        sched = self.engine.scheduler
        fatal = None
        try:
            while True:
                ev = wire.recv_frame(self._events)
                if ev is None:
                    break
                kind = ev.get("ev")
                if kind == "tok":
                    sched.on_token(ev["rid"], ev["i"], ev["t"])
                elif kind == "hb":
                    _health.beat(self.heartbeat_name)
                    sched.on_hb(ev)
                    self.engine._steps_executed = int(
                        ev.get("steps", self.engine._steps_executed))
                    if ev.get("metrics"):
                        self._fleet._federate(self, ev["metrics"])
                elif kind == "obs":
                    self._ingest_obs(ev)
                elif kind == "done":
                    _health.beat(self.heartbeat_name)
                    sched.on_done(ev["rid"], ev.get("state", "failed"),
                                  ev.get("tokens") or [],
                                  ev.get("error"),
                                  bool(ev.get("expired")))
                elif kind == "prefilled":
                    _health.beat(self.heartbeat_name)
                    self._fleet._on_prefilled(self, ev)
                elif kind == "ready":
                    self.compile_seconds = ev.get("compile_seconds")
                    _health.beat(self.heartbeat_name)
                    self.ready.set()
                elif kind == "drained":
                    self._fleet._finish_drain(self)
                elif kind == "fatal":
                    fatal = ev.get("error")
        except wire.WireError:
            pass
        if self._fleet._stop.is_set():
            return
        if self.state in ("starting", "running", "draining"):
            self._fleet._replica_died(self, MXNetError(
                fatal or f"worker {self.name} connection lost"))

    def _ingest_obs(self, ev: dict) -> None:
        """Adopt one shipped observability batch: finished worker spans
        (rebased by the clock offset) join the parent's serve tracer,
        and worker journal rows re-emit into the parent's journal —
        tagged with the replica and ``origin=worker`` so downstream
        consumers (the SLO tap, dedup tooling) can tell them from the
        parent's own rows.  Worker ``cost_analysis`` rows land here,
        which is how worker compiles reach the learned-cost-model
        corpus."""
        spans = ev.get("spans") or ()
        if spans and _trace.enabled():
            _trace.note_remote_process(self.pid, f"worker {self.name}")
            _trace.get_tracer("serve").ingest(
                spans, offset=self.clock.offset, pid=self.pid,
                replica=self.name)
        rows = ev.get("rows") or ()
        if rows and _tele.enabled():
            for row in rows:
                try:
                    fields = dict(row)
                    name = fields.pop("event", None)
                    if not name:
                        continue
                    fields.pop("ts", None)
                    step = fields.pop("step", None)
                    fields.setdefault("replica", self.name)
                    fields["origin"] = "worker"
                    _tele.event(str(name), step=step, **fields)
                except Exception:
                    continue   # one bad row must not kill the reader

    def probe(self, ages: dict, stall_timeout: float) -> Optional[str]:
        if self.proc is not None and self.proc.poll() is not None:
            return (f"worker process exited "
                    f"(rc={self.proc.returncode})")
        if self._reader is not None and not self._reader.is_alive():
            return "worker event stream closed"
        busy = self.engine.scheduler.inflight
        age = ages.get(self.heartbeat_name)
        if age is not None and age > stall_timeout and busy:
            return (f"replica stalled: no heartbeat for "
                    f"{age:.1f}s (> {stall_timeout:.1f}s) "
                    f"with work in flight")
        return None

    def terminate(self, force: bool = False) -> None:
        """Stop the worker: graceful shutdown RPC first (unless
        `force`), then SIGKILL; closes both channels (which unblocks
        any in-flight RPC with a wire error and ends the reader)."""
        if not force and self.proc is not None \
                and self.proc.poll() is None and self._control is not None:
            try:
                self._control.call("shutdown", _timeout_ms=1000)
            except MXNetError:
                pass
        if self.proc is not None and self.proc.poll() is None:
            try:
                self.proc.kill()
            except OSError:
                pass
        if self._control is not None:
            self._control.close()
        if self._events is not None:
            try:
                self._events.close()
            except OSError:
                pass

    def __repr__(self):
        s = self.engine.scheduler
        return (f"ProcessReplica({self.name}, {self.state}, "
                f"pid={self.pid}, gen={self.generation}, "
                f"inflight={s.inflight})")


class ServeFleet:
    """A supervised fleet of `InferenceEngine` replicas over one model.

    Typical use::

        fleet = mx.serve.ServeFleet(model, replicas=3)
        with fleet:                        # start() ... close()
            h = fleet.submit([1, 2, 3], max_new_tokens=32)
            out = h.result(timeout=30)

    `submit` routes through the fleet's `RequestRouter` (load-aware
    dispatch, bounded global queue, load shedding — `ShedError`).
    Thread transport: all replicas share the model weights and, after
    `warmup()`, the SAME compiled step executables (replica 0 lowers,
    the rest adopt).  Process transport
    (``MXTPU_FLEET_TRANSPORT=process`` or ``transport="process"``):
    `warmup()` serializes a spec dir and spawns one `serve.worker` per
    replica; each worker compiles its own engine.
    """

    def __init__(self, model, replicas: Optional[int] = None,
                 config: Optional[ServeConfig] = None, seed: int = 0,
                 router_queue: Optional[int] = None,
                 shed_deadline_ms: Optional[float] = None,
                 stall_timeout: float = 10.0,
                 poll_interval: float = 0.02,
                 supervise_interval: Optional[float] = None,
                 transport: Optional[str] = None,
                 respawn_budget: Optional[int] = None,
                 spawn_timeout: float = 120.0,
                 disagg: Optional[Tuple[int, int]] = None,
                 qos_config: Optional[_qos.QoSConfig] = None):
        self.model = model
        self.config = config or ServeConfig()
        # disaggregated serving (docs/serving.md "Disaggregated
        # serving"): `disagg=(P, D)` — or MXTPU_SERVE_DISAGG="PxD" —
        # splits the fleet into P prefill + D decode replicas joined by
        # the KV handoff pump; replica count becomes P + D
        if disagg is None:
            spec = os.environ.get("MXTPU_SERVE_DISAGG", "").strip()
            if spec:
                try:
                    p, d = spec.lower().split("x")
                    disagg = (int(p), int(d))
                except ValueError:
                    raise MXNetError(
                        f"MXTPU_SERVE_DISAGG must look like '1x2' "
                        f"(prefill x decode), got {spec!r}")
        if disagg is not None:
            disagg = (int(disagg[0]), int(disagg[1]))
            if disagg[0] < 1 or disagg[1] < 1:
                raise MXNetError(
                    f"disagg needs >= 1 prefill and >= 1 decode "
                    f"replica, got {disagg}")
        self.disagg = disagg
        n = (disagg[0] + disagg[1]) if disagg is not None \
            else (replicas if replicas is not None
                  else _env_int("MXTPU_SERVE_REPLICAS", 2))
        if n < 1:
            raise MXNetError(f"fleet needs >= 1 replica, got {n}")
        self.stall_timeout = float(stall_timeout)
        self.poll_interval = float(poll_interval)
        self.supervise_interval = float(
            supervise_interval if supervise_interval is not None
            else max(0.01, min(0.25, self.stall_timeout / 4)))
        self.transport = (transport
                          or os.environ.get("MXTPU_FLEET_TRANSPORT", "")
                          or "thread").strip().lower()
        if self.transport not in ("thread", "process"):
            raise MXNetError(
                f"MXTPU_FLEET_TRANSPORT must be 'thread' or 'process', "
                f"got {self.transport!r}")
        self.spawn_timeout = float(spawn_timeout)
        # respawn budget (MXTPU_REPLICA_RESPAWNS): fleet-wide count of
        # replica deaths healed in place.  Defaults to 2 for the process
        # transport (workers are disposable by design) and 0 for the
        # thread transport (a dead in-process replica keeps today's
        # permanent-retire semantics unless opted in).
        if respawn_budget is None:
            respawn_budget = _env_int(
                "MXTPU_REPLICA_RESPAWNS",
                2 if self.transport == "process" else 0)
        self.respawn_budget = max(0, int(respawn_budget))
        self.respawns = 0
        self.retired: List[Replica] = []
        self._seed = seed
        self._listener: Optional[wire.Listener] = None
        self._spec_path: Optional[str] = None
        self._exec_source: Optional[InferenceEngine] = None
        self._respawn_threads: List[threading.Thread] = []
        self.replicas: List[Replica] = []
        for i in range(n):
            self.replicas.append(self._make_replica(i))
        # per-tenant QoS plane (docs/serving.md "Per-tenant QoS"):
        # admission quotas/priorities/breaker live PARENT-side in this
        # controller (they survive worker deaths); WFQ + bulkheads live
        # in each replica's scheduler — thread replicas get the config
        # pushed here, process workers re-read MXTPU_QOS_SPEC (the env
        # is deliberately NOT scoped out of worker_env)
        cfg_qos = qos_config if qos_config is not None \
            else _qos.QoSConfig.from_env()
        self.qos: Optional[_qos.AdmissionController] = \
            _qos.AdmissionController(cfg_qos) \
            if cfg_qos is not None else None
        if self.qos is not None:
            _qos.install_controller(self.qos)
            for rep in self.replicas:
                sched = rep.engine.scheduler
                if isinstance(sched, ContinuousBatchingScheduler):
                    sched.set_qos(cfg_qos)
        self.router = RequestRouter(
            lambda: list(self.replicas), queue_bound=router_queue,
            shed_deadline_ms=shed_deadline_ms,
            default_deadline_ms=self.config.deadline_ms,
            qos=self.qos)
        self.deaths = 0
        # KV handoff pump (prefill -> decode): items queue here from the
        # replica drivers (thread transport) / event readers (process
        # transport) and one pump thread executes the transfers
        self._handoff_q: deque = deque()
        self._handoff_evt = threading.Event()
        self._handoff_thread: Optional[threading.Thread] = None
        #: per-transfer RPC timeout (MXTPU_HANDOFF_TIMEOUT_MS; 0 = the
        #: wire default) — bulk page frames can dwarf control frames
        self.handoff_timeout_ms = \
            _env_int("MXTPU_HANDOFF_TIMEOUT_MS", 0) or None
        self.handoffs = 0
        self.handoff_failures = 0
        self._handoff_inflight = 0
        self.handoff_ms: List[float] = []
        self._stop = threading.Event()
        self._lock = threading.RLock()
        self._supervisor: Optional[threading.Thread] = None
        self._warmed = False
        self._started = False
        self._closed = False
        # metrics federation: latest registry snapshot per live process
        # replica (riding heartbeats); re-exported per-replica-labeled
        # through a registry collector while the fleet runs
        self._fed_lock = threading.Lock()
        self._federated: "OrderedDict[str, dict]" = OrderedDict()
        try:
            self.clock_sync_interval = float(
                os.environ.get(ENV_CLOCK_SYNC, "") or 10.0)
        except ValueError:
            self.clock_sync_interval = 10.0
        # SLO burn-rate engine (MXTPU_SLO_SPEC): samples the fleet's own
        # telemetry events, evaluated every supervisor sweep
        self.slo: Optional[_slo.SLOEngine] = _slo.SLOEngine.from_env()
        if self.slo is not None:
            self.slo.attach()
        # incident capsules (MXTPU_CAPSULE_DIR): a burn alert snapshots
        # a bounded, replayable capsule; the supervisor finalizes it
        # once the post-alert window lapses so in-flight requests'
        # outcomes (and digests) land in the traffic window
        self.capsule_dir = \
            os.environ.get(_traffic.ENV_CAPSULE_DIR, "").strip() or None
        self.capsules: List[str] = []
        self._pending_capsules: List[Tuple[str, float]] = []
        if self.slo is not None and self.capsule_dir:
            self.slo.add_alert_listener(self._on_slo_alert)

    def _role_for(self, idx: int) -> str:
        if self.disagg is not None:
            return "prefill" if idx < self.disagg[0] else "decode"
        return self.config.role

    def _tp_for(self, idx: int) -> int:
        # the prefill tier stays single-device in a disagg fleet: tp
        # buys decode-latency, and prefill throughput scales by adding
        # prefill replicas instead
        if self.disagg is not None and self._role_for(idx) == "prefill":
            return 1
        return self.config.tp

    def _make_replica(self, idx: int, generation: int = 0) -> Replica:
        role = self._role_for(idx)
        name = f"r{idx}" if self.disagg is None else \
            (f"p{idx}" if role == "prefill" else f"d{idx}")
        if self.transport == "process":
            rep = ProcessReplica(name, self, idx)
        else:
            cfg = self.config
            if role != cfg.role or self._tp_for(idx) != cfg.tp:
                cfg = dataclasses.replace(cfg, role=role,
                                          tp=self._tp_for(idx))
            eng = InferenceEngine(self.model, cfg,
                                  seed=self._seed + idx)
            rep = Replica(name, eng)
            eng.scheduler.name = name
            # fleet mode: a failed device step leaves requests for
            # salvage instead of terminally failing them
            eng.scheduler.salvage_on_error = True
        rep.generation = generation
        return rep

    # ------------------------------------------------------------------
    # lifecycle
    # ------------------------------------------------------------------
    def _write_spec(self) -> str:
        """Serialize the model + serving config once per fleet — the
        worker-spawn recipe (`serve.worker.write_spec`)."""
        if self._spec_path is None:
            from .worker import write_spec
            self._spec_path = write_spec(
                tempfile.mkdtemp(prefix="mxtpu_fleet_spec_"),
                self.model, self.config)
        return self._spec_path

    def _ensure_listener(self) -> wire.Listener:
        with self._lock:
            if self._listener is None:
                self._listener = wire.Listener()
            return self._listener

    def warmup(self) -> float:
        """Thread transport: compile the step programs ONCE (replica 0 —
        live AOT lower or an export-artifact load, docs/export.md) and
        share the executables with every other replica.  Process
        transport: write the spec dir and spawn every worker in
        parallel, waiting until each reports ready.  Returns the
        longest compile seconds observed."""
        if self._warmed:
            return 0.0
        if self.transport == "process":
            errors: List[BaseException] = []

            def _spawn(rep):
                try:
                    rep.spawn(self.spawn_timeout)
                except BaseException as e:  # noqa: B036 — reported below
                    errors.append(e)

            threads = [threading.Thread(target=_spawn, args=(rep,),
                                        daemon=True,
                                        name=f"serve-spawn-{rep.name}")
                       for rep in self.replicas]
            for t in threads:
                t.start()
            for t in threads:
                t.join(self.spawn_timeout + 10)
            if errors:
                for rep in self.replicas:
                    rep.terminate(force=True)
                raise errors[0]
            self._warmed = True
            return max((rep.compile_seconds or 0.0)
                       for rep in self.replicas)
        first = self.replicas[0].engine
        secs = first.warmup()
        # getattr: duck-typed engines (tests, external drivers) without a
        # tp attribute are single-device
        _tp = lambda e: getattr(e, "tp", 1)  # noqa: E731
        for rep in self.replicas[1:]:
            if _tp(rep.engine) == _tp(first):
                rep.engine.adopt_executables(first)
            else:
                # a different tp is a different step program (disagg:
                # tp=1 prefill tier, tp=N decode tier) — compile it once
                # here and let same-tp peers adopt below
                peer = next(
                    (r.engine for r in self.replicas
                     if r.engine is not rep.engine and r.engine._execs
                     and _tp(r.engine) == _tp(rep.engine)), None)
                if peer is not None:
                    rep.engine.adopt_executables(peer)
                else:
                    secs = max(secs, rep.engine.warmup())
        self._exec_source = first
        self._warmed = True
        return secs

    def start(self) -> "ServeFleet":
        if self._started:
            return self
        if self._closed:
            raise MXNetError(
                "this ServeFleet was closed — close() is terminal and "
                "its replicas are retired; create a new fleet.  (A "
                "replica DEATH, by contrast, heals in place via the "
                "MXTPU_REPLICA_RESPAWNS respawn budget.)")
        if not self._warmed:
            self.warmup()
        self._started = True
        for rep in self.replicas:
            if rep.state != "starting":
                continue
            rep.state = "running"
            _health.beat(rep.heartbeat_name)
            rep.start_driver(self)
            self._journal_replica(rep, "started")
            self._trace_replica(rep)
        _tele.registry().add_collector(self._federated_metrics)
        self._supervisor = threading.Thread(
            target=self._supervise, daemon=True, name="serve-supervisor")
        self._supervisor.start()
        if any(getattr(r.engine, "role", "both") == "prefill"
               for r in self.replicas):
            self._handoff_thread = threading.Thread(
                target=self._handoff_pump, daemon=True,
                name="serve-handoff")
            self._handoff_thread.start()
        self._update_fleet_gauges()
        return self

    def close(self, timeout: float = 10.0) -> None:
        """Stop every driver/worker and the supervisor; the fleet is
        terminal afterwards (submit sheds `no_replicas`, start()
        raises).  Does NOT drain — call `drain()` per replica first for
        a graceful rolling stop."""
        self._stop.set()
        for rep in self.replicas:
            rep.notify()
        for rep in self.replicas:
            rep.terminate()
        for rep in self.replicas:
            if rep.thread is not None:
                rep.thread.join(timeout)
        if self._supervisor is not None:
            self._supervisor.join(timeout)
        if self._handoff_thread is not None:
            self._handoff_evt.set()
            self._handoff_thread.join(timeout)
        for t in self._respawn_threads:
            t.join(timeout)
        with self._lock:
            # non-terminal replicas have no driver anymore: a "running"
            # label would let submit() enqueue work nobody will ever
            # pump, and a restarted supervisor would misread the dead
            # threads as replica deaths
            stopped = [rep for rep in self.replicas
                       if rep.state in ("starting", "running",
                                        "draining")]
            for rep in stopped:
                rep.state = "stopped"
        self._closed = True
        self._started = False
        # every waiter unblocks: requests still queued or active on a
        # stopped replica are as undeliverable as router-parked ones —
        # a stuck result() waiter is worse than an error
        for rep in stopped:
            for req in rep.engine.scheduler.salvage():
                terminate_request(
                    req, "fleet closed with the request in flight",
                    state="failed", phase="failover_failed",
                    replica=rep.name, generated=len(req.tokens))
        # requests caught between prefill and decode: the pump is gone,
        # so unblock their waiters too
        with self._lock:
            pending_handoffs = list(self._handoff_q)
            self._handoff_q.clear()
        for item in pending_handoffs:
            req = item.get("req")
            if req is not None and not req.done():
                terminate_request(
                    req, "fleet closed with the request mid-handoff",
                    state="failed", phase="failover_failed",
                    generated=len(req.tokens))
        self.router.fail_all_parked("fleet closed")
        # flush pending incident capsules now — a short-lived fleet must
        # not lose the traffic window to an un-lapsed post-alert timer
        self._finalize_due_capsules(force=True)
        if self.slo is not None:
            self.slo.remove_alert_listener(self._on_slo_alert)
        if self._listener is not None:
            self._listener.close()
        if self._spec_path is not None:
            shutil.rmtree(self._spec_path, ignore_errors=True)
        _tele.registry().remove_collector(self._federated_metrics)
        with self._fed_lock:
            self._federated.clear()
        if self.slo is not None:
            self.slo.detach()
        if self.qos is not None:
            _qos.uninstall_controller(self.qos)
        self._update_fleet_gauges()

    def __enter__(self) -> "ServeFleet":
        return self.start()

    def __exit__(self, *exc) -> None:
        self.close()

    # ------------------------------------------------------------------
    # public request API
    # ------------------------------------------------------------------
    def submit(self, prompt, max_new_tokens: int = 20, greedy: bool = True,
               temperature: float = 1.0, eos_token_id=None, on_token=None,
               deadline_ms: Optional[float] = None,
               tenant: Optional[str] = None) -> ServeRequest:
        """Route one request into the fleet (may raise `ShedError` under
        overload — callers retry after `.retry_after_ms`)."""
        return self.router.submit(
            prompt, max_new_tokens, greedy=greedy, temperature=temperature,
            eos_token_id=eos_token_id, on_token=on_token,
            deadline_ms=deadline_ms, tenant=tenant)

    def quiesce(self, timeout: float = 120.0) -> bool:
        """Block until no request is parked, queued, or active anywhere
        in the fleet (or `timeout` elapses — returns False)."""
        deadline = time.perf_counter() + timeout
        while time.perf_counter() < deadline:
            busy = self.router.queue_depth > 0 \
                or len(self._handoff_q) > 0 \
                or self._handoff_inflight > 0 or any(
                r.engine.scheduler.active_count
                or r.engine.scheduler.queue_depth
                or getattr(r.engine.scheduler, "inflight", 0)
                for r in self.replicas if r.state in
                ("starting", "running", "draining"))
            if not busy:
                return True
            time.sleep(0.005)
        return False

    # ------------------------------------------------------------------
    # failure handling
    # ------------------------------------------------------------------
    def kill(self, name: str, error: str = "killed by fleet.kill()"):
        """Abruptly retire a replica (bench/chaos hook): its in-flight
        requests fail over exactly as if its step loop had died.  For a
        process replica this also SIGKILLs the worker."""
        self._replica_died(self._rep(name), MXNetError(error))

    def _rep(self, name: str) -> Replica:
        for rep in self.replicas:
            if rep.name == name:
                return rep
        raise MXNetError(f"no replica named {name!r} "
                         f"({[r.name for r in self.replicas]})")

    def _replica_died(self, rep: Replica, exc: BaseException) -> None:
        with self._lock:
            if rep.state in ("dead", "drained", "stopped"):
                return          # double-fire guard (driver + supervisor)
            rep.state = "dead"
            rep.error = f"{type(exc).__name__}: {exc}"
            self.deaths += 1
        rep.terminate(force=True)
        t0 = time.perf_counter()
        salvaged = rep.engine.scheduler.salvage()
        if _tele.enabled():
            _tele.counter("serve_replica_deaths_total",
                          "Replicas retired by the supervisor",
                          labelnames=("replica",)).inc(replica=rep.name)
            self._journal_replica(rep, "dead", error=rep.error,
                                  salvaged=len(salvaged))
        self.router.redispatch(salvaged, source=rep.name,
                               reason="failover")
        if not self.router._running():
            self.router.fail_all_parked(
                f"no surviving replica after {rep.name} died")
        if _trace.enabled():
            _trace.get_tracer("serve").record_span(
                "serve.failover", t0, time.perf_counter(),
                track="serve router", replica=rep.name,
                requests=len(salvaged), error=rep.error)
        self._retire_series(rep)
        for other in self.replicas:
            other.notify()
        self._update_fleet_gauges()
        self._maybe_respawn(rep)

    # ------------------------------------------------------------------
    # respawn (MXTPU_REPLICA_RESPAWNS — the dataloader-worker pattern)
    # ------------------------------------------------------------------
    def _maybe_respawn(self, rep: Replica) -> None:
        with self._lock:
            if self._stop.is_set() or self._closed or not self._started:
                return
            try:
                idx = self.replicas.index(rep)
            except ValueError:
                return              # already replaced / never installed
            if self.respawns >= self.respawn_budget:
                if self.respawn_budget:
                    _log.error(
                        "fleet: replica %s died with the respawn budget "
                        "exhausted (%d/%d used) — retiring it "
                        "permanently; the fleet shrinks.  Raise "
                        "MXTPU_REPLICA_RESPAWNS or create a new fleet "
                        "to restore capacity.", rep.name, self.respawns,
                        self.respawn_budget)
                    self._journal_replica(rep, "respawn_exhausted",
                                          used=self.respawns,
                                          budget=self.respawn_budget)
                return
            self.respawns += 1
            used = self.respawns
        t = threading.Thread(target=self._respawn, args=(rep, idx, used),
                             daemon=True,
                             name=f"serve-respawn-{rep.name}")
        self._respawn_threads.append(t)
        t.start()

    def _respawn(self, dead: Replica, idx: int, used: int) -> None:
        """Build and install the replacement replica (same name, next
        generation).  Runs off the supervisor thread — a process spawn
        takes seconds and supervision must keep sweeping meanwhile."""
        t0 = time.perf_counter()
        gen = dead.generation + 1
        try:
            new = self._make_replica(idx, generation=gen)
            if isinstance(new, ProcessReplica):
                new.spawn(self.spawn_timeout)
            else:
                src = self._exec_source
                if src is not None:
                    new.engine.adopt_executables(src)
                else:
                    new.engine.warmup()
            with self._lock:
                if self._stop.is_set() or self._closed \
                        or self.replicas[idx] is not dead:
                    new.terminate(force=True)
                    return
                self.replicas[idx] = new
                self.retired.append(dead)
                new.state = "running"
            _health.beat(new.heartbeat_name)
            new.start_driver(self)
            if _tele.enabled():
                _tele.counter(
                    "serve_replica_respawns_total",
                    "Workers respawned in place after a replica death",
                    labelnames=("replica",)).inc(replica=new.name)
                _tele.event("replica_respawn", replica=new.name,
                            generation=gen, used=used,
                            budget=self.respawn_budget,
                            transport=new.transport, pid=new.pid,
                            spawn_s=round(time.perf_counter() - t0, 3))
            self._journal_replica(new, "respawned", generation=gen)
            self._trace_replica(new, t0=t0)
            # the reborn replica pulls parked work immediately — the
            # loss window ends here, not at the next supervisor tick
            self.router.feed(new)
            self._update_fleet_gauges()
        except Exception as exc:
            _log.error("fleet: respawn of replica %s failed: %s",
                       dead.name, exc)
            self._journal_replica(dead, "respawn_failed",
                                  error=f"{type(exc).__name__}: {exc}")
            # a transient spawn fault (worker_spawn injection, OOM
            # blip) may clear: burn another budget slot if one remains
            self._maybe_respawn(dead)

    def _trace_replica(self, rep: Replica,
                       t0: Optional[float] = None) -> None:
        if not _trace.enabled():
            return
        now = time.perf_counter()
        _trace.get_tracer("serve").record_span(
            "serve.replica", t0 if t0 is not None else now, now,
            track="serve fleet", replica=rep.name,
            transport=rep.transport, pid=rep.pid,
            generation=rep.generation)

    def _federate(self, rep: Replica, snap: dict) -> None:
        """Store a worker's registry snapshot (heartbeat payload) for
        re-export; only live replicas keep an entry."""
        if not isinstance(snap, dict):
            return
        with self._fed_lock:
            self._federated[rep.name] = snap

    def _federated_metrics(self) -> dict:
        """Registry collector (installed in `start`): every stored
        worker snapshot re-labeled with ``replica=<name>`` and merged
        into the parent's exports — one /metrics scrape point for the
        whole fleet."""
        with self._fed_lock:
            snaps = list(self._federated.items())
        out: dict = {}
        for rep_name, snap in snaps:
            for mname, metric in snap.items():
                try:
                    mtype = metric.get("type", "gauge")
                    dst = out.get(mname)
                    if dst is None:
                        dst = out[mname] = {
                            "type": mtype,
                            "help": metric.get("help", ""),
                            "series": []}
                    elif dst["type"] != mtype:
                        continue
                    for s in metric.get("series", ()):
                        entry = dict(s)
                        labels = dict(entry.get("labels") or {})
                        labels["replica"] = rep_name
                        entry["labels"] = labels
                        dst["series"].append(entry)
                except Exception:
                    continue   # a malformed snapshot must not kill scrape
        return out

    def _retire_series(self, rep: Replica) -> None:
        """Drop the dead/drained replica's per-replica gauge series and
        heartbeat — stale last-values must not outlive the replica.
        The replica's federated worker snapshot retires with it, so its
        series vanish from /metrics at the same moment."""
        _health.clear_beat(rep.heartbeat_name)
        with self._fed_lock:
            self._federated.pop(rep.name, None)
        if not _tele.enabled():
            return
        reg = _tele.registry()
        for gname in ("serve_replica_queue_depth",
                      "serve_replica_active_slots",
                      "serve_replica_free_pages",
                      "serve_replica_kv_pages_shared",
                      "serve_replica_spec_accept_rate"):
            g = reg.get(gname)
            if g is not None:
                g.remove(replica=rep.name)

    # ------------------------------------------------------------------
    # graceful drain
    # ------------------------------------------------------------------
    def drain(self, name: str, timeout: float = 60.0) -> bool:
        """Gracefully retire one replica: stop routing to it, hand its
        queued requests back to the router, let its active streams
        finish, then the driver (or worker process) exits with an EMPTY
        active set.  Blocks up to `timeout`; True when fully drained."""
        rep = self._rep(name)
        with self._lock:
            if rep.state != "running":
                raise MXNetError(
                    f"cannot drain replica {name} in state {rep.state}")
            rep.state = "draining"
        sched = rep.engine.scheduler
        sched.draining = True
        handed = sched.detach_queued()
        self._journal_replica(rep, "draining", handed_back=len(handed))
        self.router.redispatch(handed, source=rep.name, reason="drain")
        if not self.router._running():
            # draining the LAST accepting replica: its active streams
            # still finish, but un-started work has nowhere to go
            self.router.fail_all_parked(
                f"no accepting replica after draining {rep.name}")
        rep.notify()
        return rep.drained_event.wait(timeout)

    def _finish_drain(self, rep: Replica) -> None:
        with self._lock:
            if rep.state != "draining":
                return
            rep.state = "drained"
        self._journal_replica(
            rep, "drained",
            active=rep.engine.scheduler.active_count)
        self._retire_series(rep)
        rep.drained_event.set()
        self._update_fleet_gauges()

    # ------------------------------------------------------------------
    # KV handoff pump (prefill tier -> decode tier)
    # ------------------------------------------------------------------
    def _on_prefilled(self, rep: "ProcessReplica", ev: dict) -> None:
        """Event-reader hook: a prefill worker detached a freshly
        prefilled request.  Take ledger custody (reconciling any racing
        ``tok`` frames) and queue the transfer for the pump thread."""
        rid = int(ev["rid"])
        entry = rep.engine.scheduler.handoff_out(
            rid, [int(t) for t in ev.get("tokens") or []])
        if entry is None:
            # finished during prefill (or already salvaged): no decode
            # leg — just release the worker-side pages
            self._enqueue_handoff(rep, {"rid": rid, "req": None})
            return
        self._enqueue_handoff(rep, {
            "rid": rid, "req": entry.req, "entry": entry,
            "ctx": int(ev["ctx"]), "n_pages": int(ev.get("n_pages", 0))})

    def _enqueue_handoff(self, rep: Replica, item: dict) -> None:
        item["src"] = rep
        item.setdefault("ts", time.perf_counter())
        with self._lock:
            self._handoff_q.append(item)
        self._handoff_evt.set()
        if _tele.enabled():
            _tele.gauge("serve_handoff_queue_depth",
                        "Handoffs waiting for the pump thread"
                        ).set(len(self._handoff_q))

    def _handoff_pump(self) -> None:
        while not self._stop.is_set():
            self._handoff_evt.wait(0.05)
            self._handoff_evt.clear()
            while not self._stop.is_set():
                with self._lock:
                    if not self._handoff_q:
                        break
                    item = self._handoff_q.popleft()
                    self._handoff_inflight += 1
                try:
                    self._do_handoff(item)
                finally:
                    with self._lock:
                        self._handoff_inflight -= 1

    def _pick_decode(self) -> Optional[Replica]:
        cands = [r for r in self.replicas
                 if r.state in ("starting", "running")
                 and getattr(r.engine, "role", "both")
                 in ("decode", "both")]
        if not cands:
            return None
        return min(cands, key=self.router._score)

    def _do_handoff(self, item: dict) -> None:
        """Execute ONE prefill->decode transfer.  Cross-process: page
        contents travel as binary wire frames (kv_export -> kv_import ->
        submit_prefilled -> kv_free); same-process (thread transport):
        content copy between the two engines' pools.  ANY failure —
        including an injected ``kv_handoff`` fault — re-queues the
        request at the prefill tier with its pages freed on both sides:
        admitted work is never dropped."""
        src, req, rid = item["src"], item.get("req"), item.get("rid")
        # trace context: handoff RPCs and the serve.handoff phase span
        # parent under the request's root span (cross-process tree)
        ctx = req._span.context() \
            if (req is not None and req._span is not None) else None
        track = f"serve req {req.id}" if req is not None else None
        try:
            fault_point("kv_handoff")
            if req is None:      # no decode leg: free worker-side pages
                if src.transport == "process":
                    src.call("kv_free", rid=rid)
                return
            dst = self._pick_decode()
            if dst is None:
                raise MXNetError("no decode-capable replica to adopt "
                                 "the prefilled request")
            if src.transport == "process":
                resp = src.call("kv_export", rid=rid,
                                _timeout_ms=self.handoff_timeout_ms,
                                _span_parent=ctx, _track=track)
                dst.call("kv_import", rid=rid, meta=resp["meta"],
                         n_pages=int(resp["n_pages"]),
                         _timeout_ms=self.handoff_timeout_ms,
                         _span_parent=ctx, _track=track,
                         _blobs=tuple(resp.get("_blobs") or ()))
                item["_dst"] = dst
                dsched = dst.engine.scheduler
                # ledger BEFORE submit: the decode worker may start
                # streaming the moment the adopt seats
                dsched.adopt_ledger(rid, item["entry"])
                try:
                    remaining = 0.0
                    if req.deadline_ms > 0:
                        remaining = max(1.0, req.deadline_ms - (
                            time.perf_counter()
                            - req.submitted_ts) * 1e3)
                    dst.call(
                        "submit_prefilled", rid=rid, prompt=req.prompt,
                        tokens=[int(t) for t in req.tokens],
                        attempt=req._epoch, ctx=int(item["ctx"]),
                        max_new=req.max_new_tokens, greedy=req.greedy,
                        temperature=req.temperature,
                        eos=req.eos_token_id, deadline_ms=remaining,
                        tenant=req.tenant,
                        _timeout_ms=self.handoff_timeout_ms,
                        _span_parent=ctx, _track=track)
                except BaseException:
                    dsched.drop_ledger(rid)
                    raise
                src.call("kv_free", rid=rid,
                         _span_parent=ctx, _track=track)
            else:
                item["_dst"] = dst
                pages = item["pages"]
                new_pages = dst.engine.allocator.alloc(len(pages))
                if new_pages is None:
                    raise MXNetError(
                        f"decode replica {dst.name} has no room for "
                        f"{len(pages)} handoff pages")
                try:
                    dst.engine.install_pages(
                        new_pages, src.engine.export_pages(pages))
                    dst.engine.scheduler.adopt_prefilled(
                        req, new_pages, int(item["ctx"]))
                except BaseException:
                    dst.engine.allocator.free(new_pages)
                    raise
                src.engine.allocator.free(pages)
                item["pages"] = None         # consumed
            dst.notify()
            self.handoffs += 1
            ms = (time.perf_counter() - item["ts"]) * 1e3
            if len(self.handoff_ms) < 100000:
                self.handoff_ms.append(ms)
            if _trace.enabled() and ctx is not None:
                # the handoff phase in the request's own tree: queued-
                # for-pump wait + both transfer legs, start-to-adopt
                _trace.get_tracer("serve").record_span(
                    "serve.handoff", item["ts"], time.perf_counter(),
                    parent=ctx, track=track, request_id=req.id,
                    src=src.name, dst=dst.name,
                    pages=item.get("n_pages") or 0)
            if _tele.enabled():
                _tele.histogram(
                    "serve_handoff_ms",
                    "Prefill->decode KV handoff latency").observe(ms)
                _tele.counter(
                    "serve_handoffs_total",
                    "Prefill->decode KV handoffs completed",
                    labelnames=("src", "dst")).inc(src=src.name,
                                                   dst=dst.name)
                _tele.event("handoff", request_id=req.id, src=src.name,
                            dst=dst.name, ms=round(ms, 3),
                            pages=item.get("n_pages") or 0)
        except Exception as exc:
            self._handoff_failed(item, exc)

    def _handoff_failed(self, item: dict, exc: Exception) -> None:
        """Free every copy of the pages (best-effort, both sides), then
        re-queue the request at the PREFILL tier with its generated
        tokens intact — re-dispatch re-prefills ``prompt + generated``
        (the ONE recovery rule), so a failed handoff costs latency,
        never a stream."""
        src, req, rid = item["src"], item.get("req"), item.get("rid")
        self.handoff_failures += 1
        _log.warning(
            "fleet: kv handoff of request %s from %s failed (%s: %s) — "
            "re-queueing at the prefill tier",
            getattr(req, "id", rid), src.name, type(exc).__name__, exc)
        if src.transport == "process":
            for rep in (src, item.get("_dst")):
                if rep is None or rep.transport != "process":
                    continue
                try:
                    rep.call("kv_free", rid=rid, _timeout_ms=2000)
                except Exception:
                    pass             # replica gone: pages died with it
        elif item.get("pages"):
            try:
                src.engine.allocator.free(item["pages"])
            except Exception:
                pass
        if _tele.enabled():
            _tele.counter("serve_handoff_failures_total",
                          "KV handoffs aborted and re-queued",
                          labelnames=("src",)).inc(src=src.name)
            if req is not None:
                _tele.event("handoff_requeued", request_id=req.id,
                            src=src.name,
                            error=f"{type(exc).__name__}: {exc}")
        if req is None or req.done():
            return
        req._epoch += 1              # discard any straggler wire events
        req.state = "queued"
        self.router.redispatch([req], source=src.name, reason="handoff")

    # ------------------------------------------------------------------
    # driver + supervisor threads
    # ------------------------------------------------------------------
    def _drive(self, rep: Replica) -> None:
        sched = rep.engine.scheduler
        while not self._stop.is_set():
            if rep.state not in ("running", "draining") \
                    or sched._abandoned:
                return
            _health.beat(rep.heartbeat_name)
            try:
                progressed = rep.engine.step()
            except BaseException as exc:  # noqa: B036 — FaultExit et al.
                # in-process replicas: ANY escape (device failure,
                # injected fault, even a FaultExit "process kill") is a
                # replica death, never a fleet death
                self._replica_died(rep, exc)
                return
            pulled = self.router.feed(rep)
            if getattr(sched, "handoff", None):
                # thread-transport prefill tier: detached prefills move
                # to the fleet's handoff pump (content copy into a
                # decode replica's pool)
                for item in sched.take_handoffs():
                    self._enqueue_handoff(rep, item)
            if rep.state == "draining" and not sched.active_count \
                    and not sched.queue_depth:
                self._finish_drain(rep)
                return
            if not progressed and not pulled:
                rep.wake.wait(self.poll_interval)
                rep.wake.clear()

    def _supervise(self) -> None:
        while not self._stop.wait(self.supervise_interval):
            ages = _health.heartbeat_ages()
            for rep in list(self.replicas):
                if self._stop.is_set():
                    # close() in progress: drivers exit deliberately —
                    # a cleanly-stopped thread is not a dead replica
                    return
                if rep.state not in ("running", "draining"):
                    continue
                err = rep.probe(ages, self.stall_timeout)
                if err is not None:
                    self._replica_died(rep, MXNetError(err))
                    continue
                if rep.transport == "process" and rep.state == "running":
                    # process replicas have no driver thread — the
                    # supervisor pulls parked work for them
                    self.router.feed(rep)
                    if isinstance(rep, ProcessReplica) and \
                            time.monotonic() - rep._last_clock_sync \
                            > self.clock_sync_interval:
                        rep._last_clock_sync = time.monotonic()
                        rep.sync_clock()
            self.router.sweep_expired()
            if self.qos is not None:
                # advance breaker cooldowns (open -> half_open) even
                # when the quarantined tenant has gone quiet
                self.qos.tick()
            if self.slo is not None:
                self.slo.tick()
            self._finalize_due_capsules()
            self._update_fleet_gauges()

    # ------------------------------------------------------------------
    # observability
    # ------------------------------------------------------------------
    def _on_slo_alert(self, name: str, entry: dict) -> None:
        """SLO burn-alert listener (runs on the supervisor's tick): snap
        an incident capsule NOW — metrics/trace/topology at alert time —
        and queue it for traffic-window finalization once the post-alert
        window lapses."""
        spec_dir = None
        try:
            spec_dir = self._write_spec()
        except Exception:   # capsules degrade, never break the sweep
            _log.warning("capsule: model spec snapshot failed",
                         exc_info=True)
        try:
            topology = {
                "replicas": len(self.replicas),
                "transport": self.transport,
                "disagg": self.disagg,
                "tp": self.config.tp,
                "serve_config": dataclasses.asdict(self.config),
            }
            slo_spec = {"objectives": [dataclasses.asdict(o)
                                       for o in self.slo.objectives()]}
            path = _traffic.begin_capsule(
                self.capsule_dir, name, entry, self.stats(), topology,
                slo_spec=slo_spec, spec_dir=spec_dir)
        except Exception:
            _log.warning("capsule: snapshot failed", exc_info=True)
            return
        _, post_s = _traffic._capsule_windows()
        with self._lock:
            self.capsules.append(path)
            self._pending_capsules.append(
                (path, time.perf_counter() + post_s))
        if _tele.enabled():
            _tele.counter("serve_capsules_total",
                          "Incident capsules written").inc()
            _tele.event("capsule", slo=name, path=path)
        _log.warning("SLO %s: incident capsule begun at %s", name, path)

    def _finalize_due_capsules(self, force: bool = False) -> None:
        """Write the traffic window into capsules whose post-alert
        window has lapsed (`force` flushes them all — fleet close)."""
        now = time.perf_counter()
        with self._lock:
            due = [p for p, t in self._pending_capsules
                   if force or now >= t]
            self._pending_capsules = [
                (p, t) for p, t in self._pending_capsules
                if not (force or now >= t)]
        for path in due:
            try:
                _traffic.finalize_capsule(path)
            except Exception:
                _log.warning("capsule: finalize failed for %s", path,
                             exc_info=True)

    def _journal_replica(self, rep: Replica, phase: str, **fields):
        if _tele.enabled():
            _tele.event("replica", replica=rep.name, phase=phase,
                        **fields)

    def _update_fleet_gauges(self) -> None:
        if not _tele.enabled():
            return
        counts = {"starting": 0, "running": 0, "draining": 0,
                  "drained": 0, "dead": 0, "stopped": 0}
        for rep in self.replicas:
            counts[rep.state] = counts.get(rep.state, 0) + 1
        g = _tele.gauge("serve_fleet_replicas",
                        "Replicas by lifecycle state",
                        labelnames=("state",))
        for state, n in counts.items():
            g.set(n, state=state)
        # per-role backlog (disaggregation observability): how deep each
        # tier's queues run — prefill-bound vs decode-bound at a glance
        depth = {"prefill": 0, "decode": 0, "both": 0}
        for rep in self.replicas:
            if rep.state not in ("starting", "running", "draining"):
                continue
            s = rep.engine.scheduler
            role = getattr(rep.engine, "role", "both")
            depth[role] = depth.get(role, 0) \
                + s.queue_depth + s.active_count
        rg = _tele.gauge("serve_role_queue_depth",
                         "Queued + active requests by replica role",
                         labelnames=("role",))
        for role, n in depth.items():
            rg.set(n, role=role)

    def stats(self) -> dict:
        return {
            "replicas": {
                rep.name: {
                    "state": rep.state,
                    "transport": rep.transport,
                    "role": getattr(rep.engine, "role", "both"),
                    "tp": getattr(rep.engine, "tp", 1),
                    "pid": rep.pid,
                    "generation": rep.generation,
                    "active": rep.engine.scheduler.active_count,
                    "queued": rep.engine.scheduler.queue_depth,
                    "free_pages": rep.engine.allocator.free_pages,
                    "steps": rep.engine._steps_executed,
                    "error": rep.error,
                } for rep in self.replicas},
            "router": self.router.stats(),
            "disagg": self.disagg,
            "handoffs": self.handoffs,
            "handoff_failures": self.handoff_failures,
            "handoff_pending": len(self._handoff_q),
            "deaths": self.deaths,
            "respawns": self.respawns,
            "respawn_budget": self.respawn_budget,
            "retired": [r.name for r in self.retired],
            "slo": self.slo.evaluate() if self.slo is not None else None,
            "qos": self.qos.stats() if self.qos is not None else None,
            "capsules": list(self.capsules),
        }
