"""Serving worker process: one `InferenceEngine` behind the wire
protocol (docs/serving.md "Process fleet").

Spawned by `ServeFleet` as ``python -m mxnet_tpu.serve.worker`` with a
**spec dir** (``config.json`` + ``weights.npz`` — enough to rebuild the
engine without the parent's live ``HybridBlock``), the worker dials the
fleet's `wire.Listener` twice (control + events channels), rebuilds and
warms its engine, then pumps the scheduler in its main loop:

- **control** RPCs (handled on a dedicated thread): ``submit`` (deduped
  by router-assigned rid — retried frames are idempotent), ``cancel``,
  ``drain`` (detach queued work, hand the rids back, finish actives,
  then exit), ``health``, ``shutdown``;
- **events** pushed from the main loop: ``tok`` per streamed token
  (with its index — the parent's stream ledger applies them
  contiguously), ``done`` with the FULL generated token list (the
  reconciliation record), ``hb`` heartbeats (~5 Hz) carrying scheduler
  stats the parent mirrors into the router's load scores, ``ready``
  after warmup, ``drained`` on graceful exit.

Failure contract: a worker is DISPOSABLE (the dataloader-worker
pattern).  Any escaped step error, a lost parent connection, or an
injected ``FaultExit`` ends the process; the parent salvages the stream
ledger, fails the streams over, and respawns within
``MXTPU_REPLICA_RESPAWNS``.  Nothing here tries to recover in place.
"""
from __future__ import annotations

import argparse
import collections
import dataclasses
import inspect
import json
import os
import sys
import threading
import time
from typing import Optional

import numpy as onp

from ..base import MXNetError
from ..resilience import EXIT_CODE, FaultExit
from .. import telemetry as _tele
from .. import tracing as _trace
from .decode import extract_decode_weights
from .engine import InferenceEngine, ServeConfig
from .scheduler import ServeRequest, _close_request_spans, \
    terminate_request
from . import wire

__all__ = ["write_spec", "load_spec", "main", "ENV_WORKER_OBS"]

#: set by the parent in the scoped spawn env (fleet.worker_env): a
#: comma list of "telemetry" / "trace".  The worker runs its OWN
#: registry/tracer (no journal file, no /metrics port, no trace dir —
#: those stay parent-only) and ships rows/spans over the events channel.
ENV_WORKER_OBS = "MXTPU_WORKER_OBS"

#: journal rows buffered between heartbeats before the oldest drop
_OBS_ROW_CAP = 10_000
#: every Nth heartbeat carries a full metrics-registry snapshot (the
#: federation payload); at ~5 Hz heartbeats that is ~1 Hz freshness
_HB_PER_SNAPSHOT = 5

_SPEC_CONFIG = "config.json"
_SPEC_WEIGHTS = "weights.npz"
_TOP_KEYS = ("embed", "pos", "lnf_g", "lnf_b", "head")


# ---------------------------------------------------------------------------
# spec dir: everything a worker needs to rebuild the engine
# ---------------------------------------------------------------------------

def write_spec(spec_dir: str, model, serve_config: ServeConfig) -> str:
    """Serialize `model`'s config + DENSE decode weights and the serving
    config into `spec_dir` (quantization re-applies in the worker from
    ``ServeConfig.quant_bits`` — planes are never shipped)."""
    from ..models.gpt import GPTConfig
    os.makedirs(spec_dir, exist_ok=True)
    params = inspect.signature(GPTConfig.__init__).parameters
    cfg_d = {k: v for k, v in vars(model.cfg).items() if k in params}
    with open(os.path.join(spec_dir, _SPEC_CONFIG), "w") as f:
        json.dump({"model": cfg_d,
                   "serve": dataclasses.asdict(serve_config)}, f)
    P = extract_decode_weights(model)
    arrs = {}
    for k in _TOP_KEYS:
        if P.get(k) is not None:
            arrs[k] = onp.asarray(P[k])
    for i, layer in enumerate(P["layers"]):
        for k, v in layer.items():
            if v is not None:
                arrs[f"layers.{i}.{k}"] = onp.asarray(v)
    onp.savez(os.path.join(spec_dir, _SPEC_WEIGHTS), **arrs)
    return spec_dir


class _SpecModel:
    """Engine-facing stand-in for the parent's model: `InferenceEngine`
    only reads ``.cfg`` and `extract_decode_weights` (which returns the
    prebuilt ``_decode_weights`` pytree directly)."""

    def __init__(self, cfg, P: dict):
        self.cfg = cfg
        self._decode_weights = P


def load_spec(spec_dir: str):
    """Rebuild ``(model_shim, serve_config)`` from a `write_spec` dir."""
    from ..models.gpt import GPTConfig
    with open(os.path.join(spec_dir, _SPEC_CONFIG)) as f:
        d = json.load(f)
    cfg = GPTConfig(**d["model"])
    sc = ServeConfig(**d["serve"])
    data = onp.load(os.path.join(spec_dir, _SPEC_WEIGHTS))
    P = {k: (data[k] if k in data.files else None) for k in _TOP_KEYS}
    layers = [dict() for _ in range(cfg.num_layers)]
    for k in data.files:
        if k.startswith("layers."):
            _, i, name = k.split(".", 2)
            layers[int(i)][name] = data[k]
    P["layers"] = layers
    return _SpecModel(cfg, P), sc


# ---------------------------------------------------------------------------
# the worker process
# ---------------------------------------------------------------------------

class Worker:
    """One serving worker: engine + scheduler + the two wire channels."""

    HB_INTERVAL = 0.2

    def __init__(self, name: str, host: str, port: int, spec_dir: str,
                 seed: int = 0, role: Optional[str] = None,
                 tp: Optional[int] = None,
                 platform: Optional[str] = None):
        self.name = name
        self.spec_dir = spec_dir
        self.seed = seed
        #: the fleet parent's JAX platform (--platform): this worker
        #: must land on the same one or die (see `_check_platform`)
        self.platform = platform or None
        #: per-worker overrides of the fleet-wide spec (disaggregation:
        #: one spec dir serves every role; --role/--tp specialize it)
        self.role_override = role or None
        self.tp_override = tp if tp and tp > 0 else None
        self.engine: Optional[InferenceEngine] = None
        # worker-local observability (ENV_WORKER_OBS, set by the parent's
        # scoped spawn env): enable BEFORE the engine builds so warmup
        # compiles land in the cost corpus, and before the hello so the
        # first heartbeat can already ship
        obs = {t.strip() for t in
               os.environ.get(ENV_WORKER_OBS, "").lower().split(",") if t}
        self._obs_tele = "telemetry" in obs
        self._obs_trace = "trace" in obs
        self._obs_rows: "collections.deque[dict]" = collections.deque(
            maxlen=_OBS_ROW_CAP)
        if self._obs_tele:
            _tele.enable()
            _tele.add_event_tap(self._obs_tap)
        if self._obs_trace:
            _trace.enable()
        # hello carries our perf_counter so the parent can seed a coarse
        # clock offset before the first `clock` RPC round-trip
        self._control = wire.connect(host, port, "control", name,
                                     ts=time.perf_counter())
        self._events = wire.connect(host, port, "events", name)
        self._send_lock = threading.Lock()
        self._wake = threading.Event()
        self._shutdown = threading.Event()
        self._lost_parent = threading.Event()
        self._live = {}           # router rid -> local ServeRequest
        # rid -> highest dispatch attempt accepted.  A RETRIED frame
        # (same attempt) is a duplicate; a HIGHER attempt is a
        # legitimate re-submission (handoff failure / failover folds the
        # stream back to the prefill tier, which may be this same
        # worker again)
        self._seen = {}
        self._handoff = {}        # rid -> detached handoff item (pages
        #                           stay allocated until kv_free)
        self._pending = {}        # rid -> imported pages awaiting adopt
        self._lock = threading.Lock()
        self._last_hb = 0.0
        self._hb_count = 0

    # -- observability shipping ----------------------------------------
    def _obs_tap(self, row: dict) -> None:
        """Buffer every journal row for the next heartbeat's obs batch.
        Finished spans already ship via the tracer rings — their journal
        echo is skipped here, or the parent would journal each twice."""
        if row.get("event") != "span":
            self._obs_rows.append(row)

    def _ship_obs(self) -> None:
        """Drain buffered journal rows + finished spans into one
        ``obs`` event frame (heartbeat cadence; also called once on the
        way out so a graceful exit loses nothing)."""
        rows = []
        while self._obs_rows and len(rows) < 2000:
            rows.append(self._obs_rows.popleft())
        spans = []
        if self._obs_trace:
            for tr in _trace.tracers().values():
                spans.extend(_trace.span_to_wire(s) for s in tr.drain())
        if rows or spans:
            self._send({"ev": "obs", "rows": rows, "spans": spans})

    def _join_trace(self, req: ServeRequest, frame: dict) -> None:
        """Adopt the propagated trace context from a submit frame: root
        a ``serve.worker`` span under the parent's request span, and an
        initial queue span under that, so every scheduler phase span on
        this request lands in the SAME cross-process trace tree."""
        tc = frame.get("_trace")
        if not tc or not self._obs_trace or not _trace.enabled():
            return
        try:
            parent = _trace.SpanContext(str(tc["tid"]), int(tc["sid"]))
        except (KeyError, TypeError, ValueError):
            return
        tr = _trace.get_tracer("serve")
        track = f"serve req {req.id}"
        req._span = tr.start_span(
            "serve.worker", parent=parent, track=track,
            request_id=req.id, replica=self.name,
            role=getattr(self.engine, "role", None))
        req._queue_span = tr.start_span(
            "serve.queue", parent=req._span.context(), track=track,
            request_id=req.id)

    # -- events channel (main thread + on_token, serialized) -----------
    def _send(self, ev: dict) -> None:
        with self._send_lock:
            try:
                wire.send_frame(self._events, ev)
            except wire.WireError:
                # the parent is gone: a worker with no fleet has no
                # reason to live (dataloader-worker semantics)
                self._lost_parent.set()
                self._shutdown.set()

    def _on_token(self, rid: int):
        def cb(tok, req):
            self._send({"ev": "tok", "rid": rid,
                        "i": len(req.tokens) - 1, "t": int(tok)})
        return cb

    def _heartbeat(self, force: bool = False) -> None:
        now = time.monotonic()
        if not force and now - self._last_hb < self.HB_INTERVAL:
            return
        self._last_hb = now
        sched = self.engine.scheduler
        ev = {"ev": "hb", "queued": sched.queue_depth,
              "active": sched.active_count,
              "free_pages": self.engine.allocator.free_pages,
              "steps": self.engine._steps_executed,
              "pid": os.getpid(), "ts": time.perf_counter()}
        if self._obs_tele and self._hb_count % _HB_PER_SNAPSHOT == 0:
            # federation payload: the parent re-exports these series
            # per-replica-labeled on its own /metrics
            ev["metrics"] = _tele.registry().snapshot()
        self._hb_count += 1
        self._send(ev)
        self._ship_obs()

    def _scan_done(self) -> None:
        with self._lock:
            finished = [(rid, req) for rid, req in self._live.items()
                        if req.done()]
            for rid, _ in finished:
                del self._live[rid]
        for rid, req in finished:
            ev = {"ev": "done", "rid": rid, "state": req.state,
                  "tokens": [int(t) for t in req.tokens]}
            if req.state != "finished":
                ev["error"] = req.error
                ev["expired"] = bool(
                    req.error and req.error.startswith("deadline exceeded"))
            self._send(ev)

    def _scan_handoffs(self, sched) -> None:
        """Announce freshly prefilled requests to the parent (role
        ``prefill`` only — other roles never detach).  Pages stay
        allocated in our pool, registered under the rid, until the
        parent's `kv_free` confirms the decode side owns a copy."""
        if not sched.handoff:
            return
        for item in sched.take_handoffs():
            rid = getattr(item["req"], "rid", None)
            if rid is None:
                # not a fleet-submitted request: nothing upstream can
                # adopt it — put it back on the local queue, pages freed
                sched.enqueue(sched.requeue_handoff(item,
                                                    reason="no_router"),
                              front=True)
                self._wake.set()
                continue
            with self._lock:
                # a re-prefill of the same rid (failed handoff folded
                # back here) may land before the parent's kv_free for
                # the previous attempt: release the stale pages first
                stale = self._handoff.pop(rid, None)
                self._handoff[rid] = item
                self._live.pop(rid, None)   # the stream leaves this worker
            if stale is not None:
                self.engine.allocator.free(stale["pages"])
            # close this side's spans now — the request never finishes
            # HERE (the decode adopter opens its own), and only finished
            # spans ship to the parent's merged trace
            _close_request_spans(item["req"], "handoff",
                                 replica=self.name)
            self._send({"ev": "prefilled", "rid": rid,
                        "ctx": int(item["ctx"]),
                        "n_pages": len(item["pages"]),
                        "tokens": [int(t) for t in item["req"].tokens]})

    # -- control channel (dedicated thread) ----------------------------
    def _control_loop(self) -> None:
        while not self._shutdown.is_set():
            try:
                # recv_message: kv_import requests carry binary page
                # frames after their JSON header
                frame = wire.recv_message(self._control)
            except wire.WireError:
                frame = None
            if frame is None:                 # parent closed the channel
                self._lost_parent.set()
                self._shutdown.set()
                self._wake.set()
                return
            verb, call_id = frame.get("verb"), frame.get("id")
            try:
                resp = self._handle(verb, frame)
                resp.update(id=call_id, ok=True)
            except Exception as e:
                resp = {"id": call_id, "ok": False,
                        "error": f"{type(e).__name__}: {e}"}
            blobs = resp.pop("_blobs", ())
            try:
                # control responses are written only by this thread; the
                # events channel has its own lock
                wire.send_frame(self._control, resp, blobs=blobs)
            except wire.WireError:
                self._lost_parent.set()
                self._shutdown.set()
                self._wake.set()
                return

    def _handle(self, verb: str, frame: dict) -> dict:
        if verb == "health":
            eng = self.engine
            if eng is None:
                return {"ready": False}
            return {"ready": True, "queued": eng.scheduler.queue_depth,
                    "active": eng.scheduler.active_count,
                    "free_pages": eng.allocator.free_pages,
                    "steps": eng._steps_executed, "pid": os.getpid()}
        if verb == "shutdown":
            self._shutdown.set()
            self._wake.set()
            return {}
        if verb == "clock":
            # one clock-sync round trip (works during warmup too): the
            # parent RTT-halves (ClockSync.update) to estimate our
            # perf_counter offset and rebase shipped span timestamps
            return {"ts": time.perf_counter()}
        if self.engine is None:
            raise MXNetError(f"worker {self.name} is still warming up")
        sched = self.engine.scheduler
        if verb == "submit":
            rid = int(frame["rid"])
            att = int(frame.get("attempt", 0))
            with self._lock:
                if rid in self._live or att <= self._seen.get(rid, -1):
                    return {"dup": True}   # retried frame: idempotent
            req = ServeRequest(
                frame["prompt"], frame["max_new"],
                greedy=bool(frame.get("greedy", True)),
                temperature=float(frame.get("temperature", 1.0)),
                eos_token_id=frame.get("eos"),
                on_token=self._on_token(rid),
                deadline_ms=float(frame.get("deadline_ms") or 0.0),
                tenant=frame.get("tenant"))
            req.rid = rid
            # adopt the ROUTER's id: worker journal rows / span tags for
            # this request then correlate with the parent's by one key
            req.id = rid
            self._join_trace(req, frame)
            sched.enqueue(req, front=bool(frame.get("front")))
            with self._lock:
                self._live[rid] = req
                self._seen[rid] = att
            self._wake.set()
            return {}
        if verb == "cancel":
            rid = int(frame["rid"])
            with self._lock:
                req = self._live.get(rid)
            cancelled = False
            if req is not None:
                with sched._lock:
                    if req in sched._queue:     # queued only: no pages
                        sched._queue.remove(req)
                        cancelled = True
                if cancelled:
                    terminate_request(req, "cancelled by the router",
                                      state="failed", phase="cancelled",
                                      replica=self.name)
            return {"cancelled": cancelled}
        if verb == "kv_export":
            # handoff step 1: ship the detached request's KV pages to
            # the parent as binary frames (pages stay allocated here
            # until kv_free acknowledges the transfer landed)
            rid = int(frame["rid"])
            with self._lock:
                item = self._handoff.get(rid)
            if item is None:
                raise MXNetError(
                    f"no detached handoff state for rid {rid}")
            meta, blobs = wire.pack_arrays(
                self.engine.export_pages(item["pages"]))
            return {"meta": meta, "ctx": int(item["ctx"]),
                    "n_pages": len(item["pages"]), "_blobs": blobs}
        if verb == "kv_import":
            # handoff step 2 (decode side): land the shipped pages in
            # our pool, parked until submit_prefilled adopts them
            rid = int(frame["rid"])
            arrays = wire.unpack_arrays(frame["meta"],
                                        frame.get("_blobs") or [])
            n = int(frame["n_pages"])
            pages = self.engine.allocator.alloc(n)
            if pages is None:
                raise MXNetError(
                    f"kv_import: no room for {n} pages "
                    f"({self.engine.allocator.free_pages} free)")
            self.engine.install_pages(pages, arrays)
            with self._lock:
                prev = self._pending.pop(rid, None)
                self._pending[rid] = pages
            if prev is not None:       # retried import: drop the stale copy
                self.engine.allocator.free(prev)
            return {"pages": len(pages)}
        if verb == "submit_prefilled":
            # handoff step 3: adopt the imported pages as a running
            # decode slot (cursor invariant: next feed is the last
            # emitted token at start_pos=ctx — bit-identical resume)
            rid = int(frame["rid"])
            att = int(frame.get("attempt", 0))
            with self._lock:
                dup = rid in self._live \
                    or att <= self._seen.get(rid, -1)
                pages = None if dup else self._pending.pop(rid, None)
            if dup:
                return {"dup": True}
            if pages is None:
                raise MXNetError(
                    f"submit_prefilled: no imported pages for rid {rid}")
            req = ServeRequest(
                frame["prompt"], frame["max_new"],
                greedy=bool(frame.get("greedy", True)),
                temperature=float(frame.get("temperature", 1.0)),
                eos_token_id=frame.get("eos"),
                on_token=self._on_token(rid),
                deadline_ms=float(frame.get("deadline_ms") or 0.0),
                tenant=frame.get("tenant"))
            req.rid = rid
            req.id = rid
            self._join_trace(req, frame)
            req.tokens = [int(t) for t in frame.get("tokens") or []]
            try:
                sched.adopt_prefilled(req, pages, int(frame["ctx"]))
            except MXNetError:
                self.engine.allocator.free(pages)
                raise
            with self._lock:
                self._live[rid] = req
                self._seen[rid] = att
            self._wake.set()
            return {}
        if verb == "kv_free":
            # handoff step 4 (prefill side) / abort cleanup (either
            # side): release every page still parked under this rid
            rid = int(frame["rid"])
            freed = 0
            with self._lock:
                item = self._handoff.pop(rid, None)
                pending = self._pending.pop(rid, None)
            for pages in (item["pages"] if item else None, pending):
                if pages:
                    self.engine.allocator.free(pages)
                    freed += len(pages)
            return {"freed": freed}
        if verb == "drain":
            sched.draining = True
            detached = sched.detach_queued()
            rids = []
            with self._lock:
                for req in detached:
                    rid = getattr(req, "rid", None)
                    if rid is not None:
                        self._live.pop(rid, None)
                        rids.append(rid)
            self._wake.set()
            return {"queued": rids}
        raise MXNetError(f"unknown wire verb {verb!r}")

    def _check_platform(self) -> str:
        """Die loudly when this process is not on its parent's platform.
        A chip belongs to one process: a worker that could not claim it
        (the parent, or a sibling, holds it) is left on the CPU by JAX
        with one log line — it must never serve from there."""
        import jax
        got = jax.default_backend()
        if self.platform and got != self.platform:
            msg = (f"worker {self.name} (pid {os.getpid()}) is on "
                   f"platform {got!r} but its fleet parent runs on "
                   f"{self.platform!r}: a chip belongs to one process, so "
                   "a worker that cannot claim it refuses to serve "
                   "(docs/serving.md \"Process fleet\")")
            self._send({"ev": "fatal", "error": msg})
            raise MXNetError(msg)
        return got

    # -- main loop ------------------------------------------------------
    def run(self) -> int:
        threading.Thread(target=self._control_loop, daemon=True,
                         name="worker-control").start()
        if self._check_platform() != "cpu":
            # accelerator compiles run minutes; CPU workers (the test
            # fleets) compile toys and write no cache
            from ..runtime import enable_compile_cache
            enable_compile_cache()
        model, sc = load_spec(self.spec_dir)
        if self.role_override or self.tp_override:
            sc = dataclasses.replace(
                sc, role=self.role_override or sc.role,
                tp=self.tp_override or sc.tp)
        eng = InferenceEngine(model, sc, seed=self.seed)
        eng.scheduler.name = self.name
        secs = eng.warmup()
        self.engine = eng
        self._send({"ev": "ready", "compile_seconds": secs,
                    "pid": os.getpid()})
        sched = eng.scheduler
        while not self._shutdown.is_set():
            try:
                progressed = eng.step()
            except FaultExit:
                # injected process kill: die hard, like the real thing
                os._exit(EXIT_CODE)
            except Exception as e:
                self._send({"ev": "fatal",
                            "error": f"{type(e).__name__}: {e}"})
                raise
            self._scan_done()
            self._scan_handoffs(sched)
            self._heartbeat()
            if sched.draining and not sched.active_count \
                    and not sched.queue_depth:
                self._send({"ev": "drained"})
                break
            if not progressed:
                self._wake.wait(0.01)
                self._wake.clear()
        try:
            self._ship_obs()   # final batch: a graceful drain loses nothing
        except Exception:
            pass
        for sock in (self._events, self._control):
            try:
                sock.close()
            except OSError:
                pass
        return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="python -m mxnet_tpu.serve.worker",
        description="serving-fleet worker (spawned by ServeFleet)")
    ap.add_argument("--name", required=True)
    ap.add_argument("--host", default="127.0.0.1")
    ap.add_argument("--port", type=int, required=True)
    ap.add_argument("--spec", required=True, help="spec dir (write_spec)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--role", default="",
                    help="override ServeConfig.role from the spec "
                         "(prefill | decode | both)")
    ap.add_argument("--tp", type=int, default=0,
                    help="override ServeConfig.tp from the spec")
    ap.add_argument("--platform", default="",
                    help="the parent's jax.default_backend(); the worker "
                         "exits unless it lands on the same platform")
    args = ap.parse_args(argv)
    worker = Worker(args.name, args.host, args.port, args.spec,
                    seed=args.seed, role=args.role or None,
                    tp=args.tp or None, platform=args.platform or None)
    rc = worker.run()
    # a worker that lost its parent exits quietly — the stack is noise
    return 0 if worker._lost_parent.is_set() else rc


if __name__ == "__main__":
    sys.exit(main())
