"""Continuous-batching scheduler: admit/evict per step, slot packing,
token streaming.

The serving control loop the reference never had (its `module.predict` is
batch-synchronous): requests arrive at any time, are admitted into a fixed
set of **slots** as soon as a slot AND enough KV pages are free, prefill in
chunks alongside other slots' single-token decodes (one fused device step
per iteration — the ragged mixed launch), stream each generated token
through a callback the moment it lands, and leave the moment they finish —
no head-of-line blocking on the longest sequence in the batch.

Eviction (vLLM-style *recompute preemption*): when a growing sequence
needs a page and the pool is exhausted, the youngest-admitted OTHER active
sequence is evicted — its pages return to the free list and the request
re-queues at the FRONT with its prompt extended by everything it already
generated.  On re-admission it re-prefills that prefix (compute traded for
memory) and continues decoding; already-streamed tokens are never
re-emitted.  Greedy decoding makes the continuation deterministic, so an
evicted request's final output is identical to an uninterrupted run.

A slot holds, a **cache group** of the model (`engine.groups`, a list of
`kv_cache.CacheGroup`), one `kv_cache.PageRun`: the contiguous run of
logical pages it has there and the table row the step reads.  Growing to
hold a chunk, letting go what lies behind a group's window, trimming past
a rolled-back cursor and freeing are written once over a run; this module
calls them in a loop over the groups and names no group.  What SHARES
pages (the prefix attach at admission, the copy-on-write guard, the
handoff's detach and adopt, the prefix eviction under pressure) addresses
the whole-context group alone, as ``slot.pages`` / ``slot.table`` /
``self.allocator``: a page can be shared only while every owner keeps it,
and a windowed group gives the prompt's pages back.

Everything host-side here is plain Python bookkeeping (lists, a free-list
allocator); the device work happens in the engine's compiled step.
Telemetry (`serve_*` metrics + `request` journal events) is emitted at
every lifecycle edge — this subsystem is instrumented from day one.

**Fleet mode** (`mx.serve.ServeFleet`, docs/serving.md "Fleet, failover &
overload"): when this scheduler is one replica of a supervised fleet it
carries a ``name``, runs with ``salvage_on_error=True`` (a failed device
step hands the in-flight requests back to the fleet instead of failing
them — the whole replica retires, pool and all), and its in-flight set
can be :meth:`salvage`\\ d by the supervisor after a death or stall.  A
salvaged/evicted/failed-over request always resumes by re-prefilling
``prompt + generated`` on the next scheduler — the ONE recovery rule
shared by eviction and failover, which is why greedy streams survive a
replica death bit-identical and never re-emit a token.
"""
from __future__ import annotations

import itertools
import statistics
import threading
import time
from collections import deque
from typing import Callable, List, Optional

import numpy as onp

from ..base import MXNetError
from ..resilience import fault_point
from .. import health as _health
from .. import telemetry as _tele
from .. import tracing as _trace
from . import qos as _qos
from . import traffic as _traffic
from .kv_cache import PageRun, live_page_range

__all__ = ["ServeRequest", "ContinuousBatchingScheduler",
           "terminate_request", "finish_request", "deliver_token"]

_rid = itertools.count(1)


class ServeRequest:
    """One in-flight generation request (also the caller's handle).

    `on_token(token_id, request)` fires synchronously as each token is
    generated (streaming); `result()` blocks until completion and returns
    the full sequence (prompt + generated)."""

    def __init__(self, prompt, max_new_tokens: int, greedy: bool = True,
                 temperature: float = 1.0, eos_token_id: Optional[int] = None,
                 on_token: Optional[Callable] = None,
                 deadline_ms: float = 0.0,
                 tenant: Optional[str] = None):
        self.id = next(_rid)
        #: opaque caller tag carried into the traffic journal
        self.tenant = tenant
        self.prompt = [int(t) for t in prompt]
        self.max_new_tokens = int(max_new_tokens)
        self.greedy = bool(greedy)
        self.temperature = float(temperature)
        self.eos_token_id = eos_token_id
        self.on_token = on_token
        #: wall-clock budget from submit (ms); 0 = unbounded
        self.deadline_ms = float(deadline_ms or 0.0)
        self.tokens: List[int] = []          # generated so far (streamed)
        self.state = "queued"                # queued|running|finished|failed
        self.evictions = 0
        self.failovers = 0                   # replica deaths survived
        self.prefix_hits = 0                 # prompt tokens served from
        #                                      the prefix cache (summed
        #                                      across re-admissions)
        # ownership epoch: salvage() bumps it when the request moves to
        # another replica, so a wedged old driver's late emit is ignored
        self._epoch = 0
        # serializes terminal transitions across threads (a dying
        # replica's sweep vs the router's deadline sweep)
        self._terminate_lock = threading.Lock()
        self.submitted_ts = time.perf_counter()
        self.first_token_ts: Optional[float] = None
        self.finished_ts: Optional[float] = None
        self.error: Optional[str] = None
        self._done = threading.Event()
        # tracing (mx.tracing, MXTPU_TRACE): the request's root span +
        # the currently-open queue-phase span; None when tracing is off
        self._span = None
        self._queue_span = None

    # -- caller-side API -------------------------------------------------
    @property
    def ttft_s(self) -> Optional[float]:
        if self.first_token_ts is None:
            return None
        return self.first_token_ts - self.submitted_ts

    @property
    def latency_s(self) -> Optional[float]:
        if self.finished_ts is None:
            return None
        return self.finished_ts - self.submitted_ts

    def done(self) -> bool:
        return self._done.is_set()

    def deadline_due(self, now: Optional[float] = None) -> bool:
        """True when this request's wall-clock budget has lapsed (the
        ONE deadline predicate — scheduler and router both use it)."""
        if self.deadline_ms <= 0:
            return False
        now = time.perf_counter() if now is None else now
        return (now - self.submitted_ts) * 1e3 > self.deadline_ms

    def result(self, timeout: Optional[float] = None) -> List[int]:
        if not self._done.wait(timeout):
            raise TimeoutError(f"request {self.id} not finished")
        if self.state == "failed":
            raise MXNetError(f"request {self.id} failed: {self.error}")
        return list(self.prompt) + list(self.tokens)

    # -- scheduler-side helpers ------------------------------------------
    def _sequence(self) -> List[int]:
        """Tokens that must be in the KV cache: prompt + generated."""
        return self.prompt + self.tokens

    def __repr__(self):
        return (f"ServeRequest(id={self.id}, state={self.state}, "
                f"prompt={len(self.prompt)}t, generated="
                f"{len(self.tokens)}/{self.max_new_tokens})")


def _close_request_spans(req: ServeRequest, state: str, **tags) -> None:
    """Finish a request's open tracing spans (queue phase + root)."""
    if req._queue_span is not None:
        req._queue_span.finish(state=state)
        req._queue_span = None
    if req._span is not None:
        req._span.finish(state=state, generated=len(req.tokens),
                         evictions=req.evictions,
                         prefix_hit=req.prefix_hits, **tags)
        req._span = None


def _open_queue_span(req: ServeRequest, reason: str) -> None:
    """(Re-)open a request's "serve.queue" span — eviction re-queue and
    failover re-dispatch park the request again; its timeline should show
    the second (third, ...) wait.  No-op when one is already open."""
    if req._span is not None and req._queue_span is None:
        req._queue_span = _trace.get_tracer("serve").start_span(
            "serve.queue", parent=req._span.context(),
            track=f"serve req {req.id}", request_id=req.id,
            evicted=True, reason=reason)


def terminate_request(req: ServeRequest, err: str, *, state: str = "failed",
                      phase: str = "failed", replica: Optional[str] = None,
                      shed_reason: Optional[str] = None,
                      **extras) -> bool:
    """Shared terminal path for every non-finished outcome — scheduler
    expiry/failure AND router-side shedding/expiry use this ONE function,
    so a request can only ever be terminated once: the first caller wins
    (marks the request failed, counts it under its terminal-state label,
    journals the phase, closes spans, unblocks the waiter) and every
    later attempt is a no-op returning False.  The exactly-once guarantee
    matters in fleet mode, where a dying replica's failure sweep and the
    router's deadline sweep can race over the same request — the
    per-request lock makes the check-then-terminate atomic."""
    with req._terminate_lock:
        if req._done.is_set():
            return False
        req.state = "failed"
        req.error = err
        req.finished_ts = time.perf_counter()
        _close_request_spans(req, state, error=err)
        if _tele.enabled():
            _tele.counter("serve_requests_total",
                          "Requests by terminal state",
                          labelnames=("state",)).inc(state=state)
            fields = dict(extras)
            if replica is not None:
                fields.setdefault("replica", replica)
            if req.tenant is not None:
                fields.setdefault("tenant", req.tenant)
            _tele.event("request", request_id=req.id, phase=phase,
                        **fields)
        _traffic.note_outcome(req, state, error=err, replica=replica,
                              shed_reason=shed_reason)
        req._done.set()
        _qos.note_terminal(req, state)
    return True


def expire_request(req: ServeRequest, where: str,
                   replica: Optional[str] = None,
                   detail: Optional[str] = None) -> bool:
    """The ONE deadline-expiry terminal: counter + terminate, shared by
    the scheduler (queued/active) and the router (parked) so the two
    tiers can never disagree on what expiry means.  `where` is the
    counter label (queued/active/router); `detail` overrides it in the
    human-facing error.  The counter only moves when this call actually
    won the terminate race."""
    won = terminate_request(
        req, f"deadline exceeded ({req.deadline_ms:g} ms) while "
             f"{detail or where}",
        state="expired", phase="deadline_expired", where=where,
        replica=replica, generated=len(req.tokens),
        deadline_ms=req.deadline_ms)
    if won and _tele.enabled():
        _tele.counter(
            "serve_deadline_expired_total",
            "Requests expired past their per-request deadline",
            labelnames=("where",)).inc(where=where)
    return won


def deliver_token(req: ServeRequest, token: int,
                  replica: Optional[str] = None) -> bool:
    """Mirror ONE streamed token onto a request handle: append, TTFT
    bookkeeping, telemetry, the `on_token` callback.  Returns True when this token completed the
    request (``max_new_tokens`` reached or EOS) — the caller owns the
    finish.  Shared by the in-process scheduler's emit path and the
    process fleet's parent-side stream ledger (`ProcessReplica`), so a
    token delivered over the wire is indistinguishable from one emitted
    by a local slot."""
    req.tokens.append(token)
    if req.first_token_ts is None:
        req.first_token_ts = time.perf_counter()
        if _tele.enabled():
            _tele.histogram(
                "serve_ttft_ms",
                "Time to first token per request (submit -> first "
                "streamed token)").observe(req.ttft_s * 1e3)
            fields = {"replica": replica} if replica is not None else {}
            if req.tenant is not None:
                fields["tenant"] = req.tenant
            _tele.event("request", request_id=req.id, phase="first_token",
                        ttft_ms=round(req.ttft_s * 1e3, 3), **fields)
    if _tele.enabled():
        _tele.counter("serve_tokens_generated_total",
                      "Tokens generated across all requests").inc()
    if req.on_token is not None:
        try:
            req.on_token(token, req)
        except Exception:
            import logging
            logging.getLogger(__name__).exception(
                "serve: on_token callback failed (request %d)", req.id)
    return len(req.tokens) >= req.max_new_tokens or (
        req.eos_token_id is not None and token == req.eos_token_id)


def finish_request(req: ServeRequest,
                   replica: Optional[str] = None) -> bool:
    """The ONE successful-completion terminal: state, latency metrics,
    journal, spans, waiter unblock.  First caller wins (False if the
    request already terminated) — shared by the in-process scheduler's
    slot-finish and the process fleet's remote done/reconcile path, so
    the two transports can never disagree on what "finished" means."""
    with req._terminate_lock:
        if req._done.is_set():
            return False
        req.state = "finished"
        req.finished_ts = time.perf_counter()
        _close_request_spans(
            req, "finished",
            ttft_ms=(round(req.ttft_s * 1e3, 3)
                     if req.ttft_s is not None else None))
        if _tele.enabled():
            _tele.counter("serve_requests_total",
                          "Requests by terminal state",
                          labelnames=("state",)).inc(state="finished")
            _tele.histogram(
                "serve_request_latency_ms",
                "End-to-end request latency (submit -> last token)"
            ).observe(req.latency_s * 1e3)
            fields = {"replica": replica} if replica is not None else {}
            if req.tenant is not None:
                fields["tenant"] = req.tenant
            _tele.event("request", request_id=req.id, phase="finished",
                        generated=len(req.tokens),
                        latency_ms=round(req.latency_s * 1e3, 3),
                        **fields)
        _traffic.note_outcome(req, "finished", replica=replica)
        req._done.set()
        _qos.note_terminal(req, "finished")
    return True


class _Slot:
    """One occupied batch slot: the request plus, a cache group, the run
    of KV pages it holds there and its page table (`kv_cache.PageRun`)."""

    def __init__(self, req: ServeRequest, slot_idx: int, groups,
                 max_pages: int, admit_seq: int):
        self.req = req
        self.slot_idx = slot_idx
        #: one `PageRun` a cache group, in the engine's order
        self.runs = tuple(PageRun(g, max_pages) for g in groups)
        # the whole-context group's pages and table row, by the names
        # the sharing code reads (prefix attach, copy-on-write, handoff:
        # defined there only).  The same objects as ``runs[0]``'s, which
        # changes them in place
        self.pages: List[int] = self.runs[0].pages
        self.table = self.runs[0].table
        self.ctx = 0          # tokens already written to the pool
        self.admit_seq = admit_seq    # admission order (eviction priority)
        # prompt blocks registered in the engine's PrefixIndex (once,
        # when the prompt's prefill completes)
        self.prefix_inserted = False
        # ownership epoch at admission: salvage() bumps the request's
        # epoch when it moves to another replica, so this slot's emits
        # become no-ops if its driver was wedged past the salvage
        self.epoch = req._epoch


class ContinuousBatchingScheduler:
    """Drives admission, per-step batch packing, eviction, streaming.

    Owned by an `InferenceEngine`; `step()` runs one fused device step
    over the current actives (call it in a loop, or `run_until_idle`).
    `submit` is thread-safe; stepping is single-threaded by design (one
    device stream)."""

    #: the phases of one `step`, in order: the ``serve.step.<phase>``
    #: spans tile ``serve.step`` (docs/observability.md)
    STEP_PHASES = ("admit", "plan", "launch", "wait", "emit")

    def __init__(self, engine):
        self.engine = engine
        cfg = engine.serve_config
        self.max_slots = cfg.max_slots
        self.page_size = cfg.page_size
        self.prefill_chunk = cfg.prefill_chunk
        self.deadline_ms = float(getattr(cfg, "deadline_ms", 0) or 0)
        self.max_len = engine.max_len
        self.max_pages_per_seq = engine.max_pages_per_seq
        self._bind_groups()
        self.kv_pages_released = 0   # pages let go behind a group's window
        self._queue: deque = deque()
        self._slots: List[Optional[_Slot]] = [None] * self.max_slots
        self._lock = threading.Lock()
        self._admit_seq = itertools.count()
        # per-tenant QoS (docs/serving.md "Per-tenant QoS"): when
        # MXTPU_QOS/MXTPU_QOS_SPEC configure a plane, admission follows
        # weighted-fair virtual time across tenants and per-tenant
        # bulkheads cap slots/pages; unset -> plain FIFO, zero overhead
        self.qos_config = _qos.QoSConfig.from_env()
        self._wfq = (_qos.WeightedFairQueue(self.qos_config)
                     if self.qos_config is not None else None)
        self._steps = 0
        # disaggregated serving (docs/serving.md "Disaggregated
        # serving"): on a role='prefill' engine, a slot that has
        # finished its prompt prefill and streamed its first token(s)
        # vacates WITHOUT freeing pages — the request + its page list
        # park here until the fleet hands them to a decode engine.
        # On a decode engine, `adopt_prefilled` parks (req, pages, ctx)
        # triples whose pages are already owned by THIS allocator;
        # `_admit` seats them ahead of the plain queue.
        self.handoff: deque = deque()
        self._adopt_q: deque = deque()
        self.handoffs_out = 0    # slots detached for handoff
        self.handoffs_in = 0     # prefilled requests adopted
        # decode-fast-path accounting (docs/serving.md "Speculative
        # decoding & prefix caching")
        self.spec_proposed = 0       # draft tokens fed for verification
        self.spec_accepted = 0       # draft tokens that matched greedy
        self.tokens_emitted = 0      # tokens streamed (all requests)
        self.prefix_hit_tokens = 0   # prompt tokens attached from cache
        self.cow_forks = 0           # shared pages forked before a write
        # per-step phase log (`phase_stats`): each step's clock stamps at
        # its phase boundaries + its counts, traced or not; running
        # totals (`_totals`) give a step its counts by difference
        self._phase_log: deque = deque(maxlen=1024)
        self._phase_lock = threading.Lock()
        self._n_admitted = self._n_evicted = 0
        self._n_expired = self._n_finished = 0
        #: replica identity in a fleet (None outside one): tags request
        #: journal events, step spans, and the per-replica gauges
        self.name: Optional[str] = None
        #: fleet mode: a failed device step leaves the in-flight requests
        #: untouched for `salvage()` instead of failing them terminally
        self.salvage_on_error = False
        #: drain mode: submit/enqueue refuse new work; evicted actives
        #: still re-admit so every active stream runs to completion
        self.draining = False
        # set once by `salvage()` — this scheduler (and its replica) is
        # retired; a driver thread mid-step discards its results
        self._abandoned = False
        # serializes the host-side halves of step() against a
        # supervisor-thread salvage(); deliberately NOT held across the
        # device call, so salvaging a replica stuck in `_execute` never
        # blocks on the stuck step
        self._step_lock = threading.Lock()

    def _bind_groups(self) -> None:
        """Take the engine's cache groups (`kv_cache.CacheGroup`, the
        whole-context one first) and spell from them, once, what every
        step reads: the step's tag names and which groups let pages go.
        ``allocator`` is the whole-context group's: admission, the
        request caps and everything that SHARES pages (prefix cache,
        copy-on-write, handoff) count and address that group only,
        because a page may be shared only while every owner still holds
        it.  Again after the engine rebuilt its pools."""
        self.groups = self.engine.groups
        self.allocator = self.engine.allocator
        self._kv_tags = tuple("kv_pages_" + g.name for g in self.groups)
        self._windowed = tuple(i for i, g in enumerate(self.groups)
                               if g.window is not None)
        # one attention work list a (group, window of its layers)
        layers = self.engine.spec.layers
        self._work_lists = tuple(
            (g.name, window, g.walk) for g in self.groups
            for window in dict.fromkeys(layers[i].window for i in g.layers))

    # ------------------------------------------------------------------
    def validate_request(self, prompt, max_new_tokens: int) -> List[int]:
        """Normalize + validate a prompt against this scheduler's caps
        (context length, whole-pool fit).  Raises for a request that could
        NEVER be served — shared by `submit` and the fleet router's
        admission check.  Returns the normalized token list."""
        prompt = [int(t) for t in onp.asarray(prompt).reshape(-1)]
        if not prompt:
            raise MXNetError("empty prompt")
        if int(max_new_tokens) < 1:
            raise MXNetError(
                f"max_new_tokens must be >= 1, got {max_new_tokens}")
        total = len(prompt) + int(max_new_tokens)
        if total > self.max_len:
            raise MXNetError(
                f"request needs {total} tokens but the serving context "
                f"cap is {self.max_len} (MXTPU_SERVE_MAX_LEN / model "
                f"max_position)")
        need = self.allocator.pages_for(total)
        if need > self.allocator.total_pages:
            raise MXNetError(
                f"request needs {need} KV pages but the pool only has "
                f"{self.allocator.total_pages} — raise MXTPU_SERVE_PAGES")
        return prompt

    def submit(self, prompt, max_new_tokens: int = 20, greedy: bool = True,
               temperature: float = 1.0, eos_token_id=None,
               on_token=None, deadline_ms: Optional[float] = None
               ) -> ServeRequest:
        prompt = self.validate_request(prompt, max_new_tokens)
        req = ServeRequest(prompt, max_new_tokens, greedy=greedy,
                           temperature=temperature,
                           eos_token_id=eos_token_id, on_token=on_token,
                           deadline_ms=(self.deadline_ms
                                        if deadline_ms is None
                                        else deadline_ms))
        self._trace_submit(req)
        try:
            self.enqueue(req)
        except MXNetError:
            # draining/retired: close the just-opened spans — a refused
            # request must not leave a dangling open track in the trace
            _close_request_spans(req, "rejected")
            raise
        self._telemetry_request(req, "submitted", queued=len(self._queue))
        self._update_gauges()
        return req

    def enqueue(self, req: ServeRequest, front: bool = False) -> None:
        """Admit an EXISTING request into this scheduler's queue — the
        router's dispatch path, failover re-dispatch, and drain hand-back
        all land here.  A request that already generated tokens re-enters
        exactly like an evicted one: `_sequence()` folds them into the
        prefix the next prefill recomputes, so greedy streams continue
        bit-identical and never re-emit."""
        req.state = "queued"
        with self._lock:
            # flag check and append are ONE atomic section: salvage()
            # and drain's detach_queued() set their flag BEFORE draining
            # the queue under this same lock, so an enqueue that lands
            # after the drain must see the flag and raise — a request
            # can never slip into a retired scheduler's queue and strand
            if self.draining or self._abandoned:
                raise MXNetError(
                    f"replica {self.name or '<unnamed>'} is "
                    f"{'draining' if self.draining else 'retired'} and "
                    f"not accepting requests")
            if front:
                self._queue.appendleft(req)
            else:
                self._queue.append(req)

    # -- request-lifecycle spans (mx.tracing) --------------------------
    # Every request gets a root "serve.request" span on its own track
    # (one Perfetto row per request) whose children decompose TTFT:
    # "serve.queue" (submit -> admit, re-opened on eviction), one
    # "serve.prefill_chunk"/"serve.decode"/"serve.first_decode" span per
    # fused step the request took part in (tagged with slot and page
    # ids; the callbacks' time is inside the step's "serve.step.emit"
    # span).  All sites guard on _trace.enabled(): tracing off costs two
    # None attributes per request.

    def _trace_submit(self, req: ServeRequest) -> None:
        if not _trace.enabled():
            return
        tr = _trace.get_tracer("serve")
        track = f"serve req {req.id}"
        req._span = tr.start_span(
            "serve.request", track=track, request_id=req.id,
            prompt_tokens=len(req.prompt),
            max_new_tokens=req.max_new_tokens)
        req._queue_span = tr.start_span(
            "serve.queue", parent=req._span.context(), track=track,
            request_id=req.id)

    def _trace_admit(self, req: ServeRequest, slot: int,
                     pages: int) -> None:
        if req._queue_span is not None:
            req._queue_span.finish(slot=slot, pages=pages,
                                   readmit=bool(req.evictions))
            req._queue_span = None

    def _trace_requeue(self, req: ServeRequest, reason: str) -> None:
        _open_queue_span(req, reason)

    def _trace_close(self, req: ServeRequest, state: str,
                     **tags) -> None:
        _close_request_spans(req, state, **tags)

    # ------------------------------------------------------------------
    def set_qos(self, config) -> None:
        """Install (or clear) a QoS config programmatically — the fleet
        uses this so a config passed to `ServeFleet(qos_config=...)`
        reaches thread-transport replicas without the env var."""
        self.qos_config = config
        self._wfq = (_qos.WeightedFairQueue(config)
                     if config is not None else None)

    def _projected_pages(self, req: ServeRequest) -> int:
        """A request's FULL KV footprint (prompt + every token it may
        generate).  Bulkheads cap on this projection at admission, so a
        growing sequence can never push its tenant past the cap later."""
        return self.allocator.pages_for(
            len(req.prompt) + req.max_new_tokens + 1)

    def _tenant_at_cap(self, req: ServeRequest) -> bool:
        """Bulkhead check (holding self._lock): would seating `req` put
        its tenant over its max_slots / max_pages cap?"""
        pol = self.qos_config.policy_for(req.tenant)
        if pol.max_slots <= 0 and pol.max_pages <= 0:
            return False
        slots = pages = 0
        for s in self._slots:
            if s is not None and s.req.tenant == req.tenant:
                slots += 1
                pages += getattr(s, "qos_pages", len(s.pages))
        if pol.max_slots > 0 and slots >= pol.max_slots:
            return True
        return pol.max_pages > 0 and \
            pages + self._projected_pages(req) > pol.max_pages

    def _pick_next(self) -> Optional[int]:
        """Index of the next queued request to seat (holding
        self._lock).  FIFO without QoS.  With QoS: re-queued work that
        already generated tokens (eviction / failover re-admission)
        keeps absolute front priority — dropping IT would violate the
        never-drop rule; among fresh requests, the head-of-line request
        of the tenant with the smallest WFQ start tag wins, skipping
        tenants at a bulkhead cap.  None when nothing is seatable."""
        if not self._queue:
            return None
        if self._wfq is None:
            return 0
        best, best_tag = None, None
        seen = set()
        for i, req in enumerate(self._queue):
            if req.tokens or req.evictions:
                return i       # in-progress work: seat before any fresh
            key = req.tenant or _qos.DEFAULT_TENANT
            if key in seen:
                continue       # WFQ is per-tenant head-of-line
            seen.add(key)
            if self._tenant_at_cap(req):
                continue
            tag = self._wfq.start_tag(req.tenant)
            if best_tag is None or tag < best_tag:
                best, best_tag = i, tag
        return best

    def _free_slot_idx(self) -> Optional[int]:
        for i, s in enumerate(self._slots):
            if s is None:
                return i
        return None

    def _alloc_pages(self, n: int) -> Optional[List[int]]:
        """`PageAllocator.alloc` with prefix-cache pressure relief: on a
        shortfall, LRU-evict unreferenced prefix-cache entries to cover
        it, then retry once.  Cached-but-unused prefixes always yield to
        live sequences."""
        if n <= 0:
            return []
        pages = self.allocator.alloc(n)
        if pages is not None:
            return pages
        index = self.engine.prefix_index
        if index is None:
            return None
        index.evict_pages(n - self.allocator.free_pages)
        return self.allocator.alloc(n)

    def _admit(self) -> None:
        """FIFO admission under memory backpressure: a request enters a
        slot only when its CURRENT sequence (prompt + already-generated,
        for re-admits) plus one decode page fits the free list — partial
        admission would deadlock against other growing sequences.

        With the prefix cache enabled, admission first consults the
        `PrefixIndex`: cached prompt-prefix pages are ATTACHED by
        reference (share, not copy) and the matching prefill chunks are
        skipped entirely — the slot's write cursor starts past them.
        The match is capped at ``len(sequence) - 1`` so the last token
        is always re-fed (its forward pass produces the next token's
        logits)."""
        while True:
            with self._lock:
                # adopted prefilled requests seat FIRST: their pages are
                # already allocated here (handed off from a prefill
                # engine), so they only wait on a slot — the decode tier
                # never re-runs a prefill it was handed
                if self._adopt_q:
                    idx = self._free_slot_idx()
                    if idx is None:
                        return
                    req, pages, ctx_len = self._adopt_q.popleft()
                    slot = _Slot(req, idx, self.groups,
                                 self.max_pages_per_seq,
                                 next(self._admit_seq))
                    slot.pages.extend(pages)
                    slot.table[:len(slot.pages)] = slot.pages
                    slot.ctx = int(ctx_len)
                    # the handed-off pages carry the prompt KV; this
                    # engine never prefilled them, so it must not
                    # register them in ITS prefix index
                    slot.prefix_inserted = True
                    self._slots[idx] = slot
                    req.state = "running"
                    self.handoffs_in += 1
                    self._n_admitted += 1
                    self._trace_admit(req, idx, len(slot.pages))
                    self._telemetry_request(req, "adopted", slot=idx,
                                            pages=len(slot.pages),
                                            ctx=slot.ctx)
                    continue
                if not self._queue:
                    return
                idx = self._free_slot_idx()
                if idx is None:
                    return
                pick = self._pick_next()
                if pick is None:
                    return     # every seatable tenant is at a bulkhead
                req = self._queue[pick]
                seq = req._sequence()
                index = self.engine.prefix_index
                attached, hit = ([], 0)
                if index is not None:
                    attached, hit = index.lookup(seq[:-1])
                need = self.allocator.pages_for(len(seq) + 1)
                if not all(g.allocator.can_alloc(min(need, g.walk))
                           for g in self.groups[1:]):
                    return     # a windowed group is dry: wait for frees
                pages = self._alloc_pages(need - len(attached))
                if pages is None:
                    # OOM backpressure: wait for frees (the attached
                    # pages go back — the index still holds its own
                    # reference, so the next attempt re-attaches)
                    if attached:
                        self.allocator.free(attached)
                    return
                del self._queue[pick]
                slot = _Slot(req, idx, self.groups, self.max_pages_per_seq,
                             next(self._admit_seq))
                slot.pages.extend(attached + pages)
                slot.table[:len(slot.pages)] = slot.pages
                slot.ctx = hit
                slot.qos_pages = self._projected_pages(req)
                self._slots[idx] = slot
                if self._wfq is not None:
                    # WFQ charge = the work this admission buys: the
                    # sequence to (re-)prefill plus remaining decode
                    self._wfq.charge(
                        req.tenant,
                        len(seq) + req.max_new_tokens - len(req.tokens))
            req.state = "running"
            self._n_admitted += 1
            if hit:
                req.prefix_hits += hit
                self.prefix_hit_tokens += hit
                if _tele.enabled():
                    _tele.counter(
                        "serve_prefix_hit_tokens_total",
                        "Prompt tokens served from the cross-request "
                        "prefix cache (prefill skipped)").inc(hit)
            self._trace_admit(req, idx, len(slot.pages))
            self._telemetry_request(
                req, "readmitted" if req.evictions else "admitted",
                slot=idx, pages=len(slot.pages), prefix_hit=hit)

    def _release_slot(self, slot: _Slot) -> None:
        """Recycle a slot's KV pages and vacate it — the one way any
        request leaves the active set."""
        for run in slot.runs:
            run.free()
        self._slots[slot.slot_idx] = None

    def _evict(self, slot: _Slot, reason: str) -> None:
        """Recompute-preemption: free the slot's pages, re-queue the
        request at the FRONT with its generated tokens folded into the
        prefix it will re-prefill."""
        req = slot.req
        self._release_slot(slot)
        req.state = "queued"
        req.evictions += 1
        self._n_evicted += 1
        self._trace_requeue(req, reason)
        with self._lock:
            self._queue.appendleft(req)
        if _tele.enabled():
            _tele.counter("serve_evictions_total",
                          "Sequences evicted (pages recycled, request "
                          "re-queued for recompute)").inc()
        self._telemetry_request(req, "evicted", reason=reason,
                                generated=len(req.tokens))

    def _take_page(self, slot: _Slot, group) -> Optional[int]:
        """One page of `group`'s pool for `slot`, evicting the youngest
        OTHER active while the free list is dry; None when nobody is
        left to evict (the slot itself must yield).  The whole-context
        group's free list is also fed by dropping cached prefixes
        (`_alloc_pages`)."""
        alloc = self._alloc_pages if group is self.groups[0] \
            else group.allocator.alloc
        while True:
            got = alloc(1)
            if got is not None:
                return got[0]
            victims = [s for s in self._slots
                       if s is not None and s is not slot]
            if not victims:
                return None
            victims.sort(key=lambda s: s.admit_seq)
            self._evict(victims[-1], reason="page_pressure")

    def _ensure_capacity(self, slot: _Slot, upto_tokens: int) -> bool:
        """Grow `slot`'s run in every cache group to hold `upto_tokens`
        (in a windowed group, the pages from the slot's first live page
        on), evicting younger actives when a free list runs dry.
        Returns False when even eviction cannot help (the slot itself
        must yield)."""
        need = self.allocator.pages_for(upto_tokens)
        for run in slot.runs:
            # most steps no run is short: asked here, a call is saved
            if run.first + len(run.pages) < need and \
                    not run.grow(slot.ctx, need, self._take_page, slot):
                return False
        return True

    def _cow_guard(self, slot: _Slot, first: int, last: int) -> bool:
        """Copy-on-write before the fused step scatters into token
        positions ``[first, last]``: any page in that range still SHARED
        (attached from the prefix cache, or registered in it by this
        slot's own prompt) is forked — a fresh page allocated, device
        contents copied, the table repointed, and the shared original
        released to its remaining owners — so a write can never corrupt
        KV another sequence (or the cache) is reading.  False when the
        pool cannot supply a fork page even after prefix-cache eviction
        (the caller evicts this slot)."""
        ps = self.page_size
        for pg in range(first // ps, last // ps + 1):
            page = int(slot.table[pg])
            if self.allocator.refcount(page) <= 1:
                continue
            got = self.allocator.fork(page)
            if got is None:
                index = self.engine.prefix_index
                if index is not None and index.evict_pages(1):
                    got = self.allocator.fork(page)
                if got is None:
                    return False
            new, copied = got
            if copied:
                self.engine.copy_page(page, new)
                slot.table[pg] = new
                slot.pages[pg] = new
                self.cow_forks += 1
                if _tele.enabled():
                    _tele.counter(
                        "serve_kv_cow_forks_total",
                        "Shared KV pages forked (copied to a fresh "
                        "page) before a write").inc()
        return True

    def _trim_pages(self, slot: _Slot) -> None:
        """Roll back pages past the slot's (possibly rejected-draft-
        rolled-back) write cursor — keeping the page the next decode
        token lands in.  Freshly-allocated by construction (attached
        prefix pages always sit below the cursor), so they go straight
        back to the free list."""
        keep = self.allocator.pages_for(slot.ctx + 1)
        for run in slot.runs:
            run.trim(keep)

    # ------------------------------------------------------------------
    def _expire_deadlines(self) -> None:
        """Fail every queued/active request past its per-request
        deadline (``MXTPU_SERVE_DEADLINE_MS`` / ``submit(deadline_ms=)``)
        and recycle its pages — one stuck or abandoned client must never
        pin KV pages (or a queue position) forever."""
        now = time.perf_counter()

        def _expired(req):
            return req.deadline_due(now)

        with self._lock:
            dead = [r for r in self._queue if _expired(r)]
            if dead:
                gone = set(id(r) for r in dead)
                self._queue = deque(r for r in self._queue
                                    if id(r) not in gone)
        for req in dead:
            self._expire_req(req, "queued")
        expired_active = False
        for slot in list(self._slots):
            if slot is not None and _expired(slot.req):
                self._release_slot(slot)
                self._expire_req(slot.req, "active")
                expired_active = True
        # handoff-parked and adopt-parked requests hold pages too — an
        # abandoned client must not pin them through the handoff tier
        with self._lock:
            dead_h = [h for h in self.handoff if _expired(h["req"])]
            for h in dead_h:
                self.handoff.remove(h)
            dead_a = [t for t in self._adopt_q if _expired(t[0])]
            for t in dead_a:
                self._adopt_q.remove(t)
        for h in dead_h:
            self.allocator.free(h["pages"])
            self._expire_req(h["req"], "handoff")
            expired_active = True
        for req_a, pages_a, _ctx in dead_a:
            self.allocator.free(pages_a)
            self._expire_req(req_a, "handoff")
            expired_active = True
        if dead or expired_active:
            self._update_gauges()

    def _expire_req(self, req: ServeRequest, where: str) -> None:
        self._n_expired += 1
        expire_request(req, where, replica=self.name)

    # ------------------------------------------------------------------
    def step(self) -> bool:
        """Run one fused serving step over the active slots.  Returns
        False when there was nothing to do (no actives, empty queue).

        The host-side halves (admit/plan before, emit after) hold
        ``_step_lock``; the device call runs outside it so a fleet
        supervisor can `salvage()` a replica whose step has wedged.

        Every step reads the clock at its phase boundaries
        (`STEP_PHASES`: admit → plan → launch → wait → emit) and keeps
        the stamps with the step's counts in a bounded log
        (`phase_stats`, `engine.stats()["step_phases"]`) — where a stall
        went, in a run nobody traced.  Only while `tracing.capturing()`
        do the same stamps become a ``serve.step`` span tiled by five
        ``serve.step.<phase>`` children, in the ``serve`` ring and as
        annotations in the profiler's own trace."""
        t_in = time.perf_counter()
        with _trace.annotation("serve.step"):
            return self._step(t_in)

    def _step(self, t_in: float) -> bool:
        with self._step_lock:
            if self._abandoned:
                return False
            with _trace.annotation("serve.step.admit"):
                queued = len(self._queue)
                before = self._totals()
                self._expire_deadlines()
                self._admit()
            t_admit = time.perf_counter()
            with _trace.annotation("serve.step.plan"):
                batch = self._plan()
            if batch is None:
                self._update_gauges()
                return False
            C, plan, actives, arrays = batch
            kv_counts = {
                tag: sum(len(s.runs[gi].pages) for s in actives)
                for gi, tag in enumerate(self._kv_tags)}
            kv_counts["kv_write_bytes"] = self.engine.kv_write_bytes(C)
            if _trace.capturing():
                kv_counts.update(self._attn_items(arrays[2], arrays[4]))

        t_plan = time.perf_counter()
        try:
            # chaos point (docs/resilience.md): MXTPU_FAULT_SPEC
            # `replica_step` simulates a replica dying mid-step on live
            # traffic — slot.ctx has already advanced past tokens that
            # will never land, the hardest failover shape
            fault_point("replica_step")
            next_tokens, all_tok = self.engine._execute(*arrays, C)
        except Exception as exc:
            with self._step_lock:
                if self._abandoned:
                    return False
                if not self.salvage_on_error:
                    # single-engine mode: a failed device step is
                    # unrecoverable for every in-flight sequence (the
                    # donated pool buffers may be invalidated) — fail ALL
                    # requests (waiters in result() unblock with the
                    # error) instead of leaving them stuck forever
                    self._fail_all(exc)
                # fleet mode (salvage_on_error): leave every request
                # untouched — the driver catches this raise and the fleet
                # salvages them onto a surviving replica
            raise
        t_wait = time.perf_counter()
        # the launch/wait boundary is the engine's stamp (a stand-in
        # `_execute` that leaves none reads as all wait)
        t_launch = min(max(self.engine.launched_ts, t_plan), t_wait)
        with self._step_lock:
            if self._abandoned:
                # salvaged mid-execute: the requests now live on another
                # replica — emitting here would double-stream tokens
                return False
            with _trace.annotation("serve.step.emit"):
                step_ms = (t_wait - t_plan) * 1e3
                self._steps += 1
                _health.beat("serve.step")
                if _tele.enabled():
                    _tele.histogram(
                        "serve_step_ms",
                        "Wall time per fused serving step (prefill or "
                        "decode)").observe(step_ms)
                    _tele.counter("serve_steps_total",
                                  "Fused serving steps executed").inc()
                    # FLOP attribution: this width's executable cost +
                    # measured wall -> mfu_estimate{program="serve_step"}
                    _trace.note_step_cost(
                        f"serve_step_c{C}@{id(self.engine):x}",
                        step_ms / 1e3)
                drafted, accepted, emitted = self._emit_step(
                    plan, actives, next_tokens, all_tok, t_plan, t_wait)
                self._update_gauges()
            h2d_bytes, h2d_arrays = self.engine.launched_h2d
            self._note_step(
                C, (t_in, t_admit, t_plan, t_launch, t_wait,
                    time.perf_counter()),
                {"active": len(plan),
                 "tokens_fed": sum(pl["nt"] for pl in plan.values()),
                 "emitted": emitted, "drafted": drafted,
                 "accepted": accepted, "queue_depth": queued,
                 "h2d_bytes": h2d_bytes, "h2d_arrays": h2d_arrays,
                 **kv_counts, **self._moe_counts(),
                 **{k: now - was for k, was, now in zip(
                     self._COUNTED, before, self._totals())}})
        return True

    def _attn_items(self, start_pos, ctx_lens) -> dict:
        """Grid steps one layer's paged-attention call takes this step,
        a cache group: the live (slot, page) pairs of `live_page_range`,
        the expression the kernel's work list is built from, over the
        plan's own starts and contexts (idle slots count their one
        item).  ``attn_items_table``, slots x table width, is what a
        walk of the whole table would take: the ratio is the live
        share."""
        items = {"attn_items_table": self.max_slots * self.max_pages_per_seq}
        for group, window, walk in self._work_lists:
            items["attn_items_" + group] = int(live_page_range(
                ctx_lens, start_pos, window, self.page_size, walk)[1].sum())
        return items

    def _moe_counts(self) -> dict:
        """The last step's routing, from the counts that came back with
        its tokens (`engine.last_moe_counts`: expert layers x held
        experts): pairs routed to the experts held here, held experts
        (layer by layer) that got at least one, and the busiest held
        expert's pairs over the mean.  {} for a model without expert
        layers."""
        counts = getattr(self.engine, "last_moe_counts", None)
        if counts is None:
            return {}
        counts = onp.asarray(counts)
        mean = counts.mean()
        return {"moe_tokens_routed": int(counts.sum()),
                "moe_experts_touched": int((counts > 0).sum()),
                "moe_experts_held": int(counts.size),
                "moe_load_max_over_mean":
                    float(counts.max() / mean) if mean > 0 else 0.0}

    #: a step's counts taken as differences of running totals
    _COUNTED = ("admitted", "evicted", "expired", "finished",
                "prefix_hit", "cow_forks", "kv_pages_released")

    def _totals(self) -> tuple:
        return (self._n_admitted, self._n_evicted, self._n_expired,
                self._n_finished, self.prefix_hit_tokens, self.cow_forks,
                self.kv_pages_released)

    def _plan(self):
        """Build one step's numpy batch over the active slots: the chunk
        width, speculative drafts, page capacity (evicting where the
        pool is dry), the copy-on-write guard, and the per-slot feeds
        and page tables.  Returns ``(C, plan, actives, arrays)`` —
        `arrays` in `engine._execute`'s order, its fourth a tuple of one
        (slots, table width) page table a cache group — or None when no
        slot is left to run.  Holding ``_step_lock``."""
        actives = [s for s in self._slots if s is not None]
        if not actives:
            return None

        # plan the chunk width: any slot with >1 pending token
        # prefills, so the step runs at the prefill chunk width; a
        # pure-decode round runs the C=1 program — unless the
        # drafter proposed tokens, in which case it runs the k+1
        # verification width (no padded-lane compute otherwise).
        pending = {s.slot_idx: len(s.req._sequence()) - s.ctx
                   for s in actives}
        any_prefill = any(p > 1 for p in pending.values())

        # speculative drafts: any GREEDY slot whose feed reaches the
        # end of its sequence this round (pure decode, or the last
        # prefill chunk with spare width) carries up to k proposed
        # tokens after its real feed — verified by the same launch
        spec_k = self.engine.serve_config.spec_tokens
        drafter = self.engine.drafter
        proposals = {}
        if spec_k > 0 and drafter is not None:
            cmax = self.prefill_chunk if any_prefill else spec_k + 1
            for s in actives:
                req = s.req
                p = pending[s.slot_idx]
                if not req.greedy or not 1 <= p <= cmax - 1:
                    continue
                seq = req._sequence()
                k_eff = min(spec_k, cmax - p,
                            req.max_new_tokens - len(req.tokens) - 1,
                            self.max_len - len(seq))
                if k_eff <= 0:
                    continue
                d = drafter.propose(seq, k_eff)
                if d:
                    proposals[s.slot_idx] = \
                        [int(t) for t in d[:k_eff]]
        if any_prefill:
            C = self.prefill_chunk
        elif proposals:
            C = spec_k + 1
        else:
            C = 1

        # capacity: every slot must hold its chunk's tokens (drafts
        # included — rejected ones roll back through the free list
        # after verification); slots that cannot (even after
        # evicting younger actives) are evicted themselves this
        # round.  The COW guard then forks any still-shared page in
        # the write range before the step scatters into it.
        for gi in self._windowed:
            self.kv_pages_released += sum(
                s.runs[gi].release_before(s.ctx) for s in actives)
        for s in sorted(actives, key=lambda s: s.admit_seq):
            if self._slots[s.slot_idx] is not s:
                continue      # already evicted by a victim search
            nt = min(pending[s.slot_idx], C) \
                + len(proposals.get(s.slot_idx, ()))
            if not self._ensure_capacity(s, s.ctx + nt) or \
                    not self._cow_guard(s, s.ctx, s.ctx + nt - 1):
                self._evict(s, reason="no_capacity")
        actives = [s for s in self._slots if s is not None]
        if not actives:
            return None

        B = self.max_slots
        tok = onp.zeros((B, C), onp.int32)
        num_tokens = onp.zeros(B, onp.int32)
        start_pos = onp.zeros(B, onp.int32)
        tables = tuple(onp.zeros((B, self.max_pages_per_seq), onp.int32)
                       for _ in self.groups)
        ctx_lens = onp.zeros(B, onp.int32)
        temps = onp.ones(B, onp.float32)
        greedy = onp.ones(B, bool)
        plan = {}
        for s in actives:
            seq = s.req._sequence()
            nt_seq = min(len(seq) - s.ctx, C)
            draft = proposals.get(s.slot_idx, []) \
                if s.ctx + nt_seq == len(seq) else []
            feed = seq[s.ctx:s.ctx + nt_seq] + draft
            nt = len(feed)
            i = s.slot_idx
            tok[i, :nt] = feed
            num_tokens[i] = nt
            start_pos[i] = s.ctx
            ctx_lens[i] = s.ctx + nt
            temps[i] = s.req.temperature
            greedy[i] = s.req.greedy
            plan[i] = {"slot": s, "feed": feed, "nt": nt,
                       "nt_seq": nt_seq, "ctx0": s.ctx,
                       "draft": len(draft), "emitted": 0,
                       "consume": s.ctx + nt_seq == len(seq)}
            s.ctx += nt
        for gi, table in enumerate(tables):
            for s in actives:
                table[s.slot_idx] = s.runs[gi].table
        return C, plan, actives, (tok, num_tokens, start_pos, tables,
                                  ctx_lens, temps, greedy)

    def _emit_step(self, plan, actives, next_tokens, all_tok,
                   t_plan: float, t_wait: float):
        """After the device step: register just-prefilled prompts in the
        prefix cache, hand each slot its token(s) (`on_token` fires
        here), roll rejected drafts back, detach prefilled slots on a
        prefill-role engine.  Returns ``(drafted, accepted, emitted)``
        of this step.  Holding ``_step_lock``."""
        # register just-prefilled prompts in the prefix cache BEFORE
        # emitting (emits can finish a request and release its
        # pages): the slot's pages hold the complete prompt KV once
        # the write cursor passed the prompt
        index = self.engine.prefix_index
        if index is not None:
            for s in actives:
                if s.prefix_inserted or \
                        self._slots[s.slot_idx] is not s:
                    continue
                if s.ctx >= len(s.req.prompt):
                    index.insert(s.req.prompt, s.pages)
                    s.prefix_inserted = True

        # snapshot span parents before emitting: finishing a request
        # closes its root span, but the post-hoc phase spans below
        # still decompose its timeline
        parents = {}
        if _trace.enabled():
            for i, pl in plan.items():
                req = pl["slot"].req
                parents[i] = (None if req._span is None
                              else req._span.context(),
                              bool(req.tokens))

        # distribute tokens in admission order (stable streaming).
        # A speculating slot emits its whole accepted run — the fed
        # position's greedy token, then each draft that matched it —
        # and rolls its write cursor back past the rejected rest.
        drafter = self.engine.drafter
        drafted_step = accepted_step = emitted_total = 0
        for s in sorted(actives, key=lambda s: s.admit_seq):
            i = s.slot_idx
            pl = plan[i]
            if not pl["consume"]:
                continue      # mid-prefill: logits discarded
            if self._slots[i] is not s:
                continue      # expired/terminated while executing
            if all_tok is not None and s.req.greedy:
                feed, nt = pl["feed"], pl["nt"]
                # all_tok column t holds fed position nt - T + t
                # (the engine computes the verify argmax only for
                # the tail T = min(C, k+1) positions — all the emit
                # loop can ever read)
                T = all_tok.shape[1]
                emitted = 0
                for j in range(pl["nt_seq"] - 1, nt):
                    tokj = int(all_tok[i, j - nt + T])
                    self._emit(s, tokj)
                    emitted += 1
                    if self._slots[i] is not s or s.req.done():
                        break      # finished (max_new / eos)
                    if j + 1 < nt and feed[j + 1] != tokj:
                        break      # draft rejected: stop the run
                pl["emitted"] = emitted
                drafted_step += pl["draft"]
                accepted_step += emitted - 1
                if pl["draft"] and drafter is not None:
                    drafter.note_result(pl["draft"], emitted - 1)
                if self._slots[i] is s:
                    # roll back past rejected drafts: the cursor
                    # returns to the last ACCEPTED token's position
                    # and the pages holding only rejected KV go
                    # back to the free list
                    s.ctx = pl["ctx0"] + pl["nt_seq"] + emitted - 1
                    self._trim_pages(s)
            else:
                self._emit(s, int(next_tokens[i]))
                pl["emitted"] = 1
            emitted_total += pl["emitted"]
        self.tokens_emitted += emitted_total
        self.spec_proposed += drafted_step
        self.spec_accepted += accepted_step
        if _tele.enabled() and drafted_step:
            _tele.counter(
                "serve_spec_proposed_total",
                "Draft tokens fed for verification").inc(drafted_step)
            if accepted_step > 0:
                _tele.counter(
                    "serve_spec_accepted_total",
                    "Draft tokens accepted (matched the greedy "
                    "continuation)").inc(accepted_step)
        if self.engine.role == "prefill":
            self._detach_prefilled(actives)
        if _trace.enabled():
            self._trace_requests(plan, parents, t_plan, t_wait)
        return drafted_step, accepted_step, emitted_total

    def _note_step(self, C: int, stamps, counts: dict) -> None:
        """Keep one step's phase stamps and counts in the bounded log
        and, only while `tracing.capturing()`, record them as the
        ``serve.step`` span and its five ``serve.step.<phase>`` children
        (`tracing.record_phases`: the children tile the parent)."""
        with self._phase_lock:
            self._phase_log.append((self._steps, C, stamps, counts))
        if not _trace.capturing():
            return
        rep = {} if self.name is None else {"replica": self.name}
        on_step = ("active", "tokens_fed", "emitted", "admitted",
                   "evicted", "expired", "drafted", "accepted",
                   "prefix_hit") + tuple(
                       k for k in counts
                       if k.startswith(("moe_", "kv_", "attn_")))
        _trace.record_phases(
            _trace.get_tracer("serve"), "serve.step", self.STEP_PHASES,
            stamps,
            track=("serve steps" if self.name is None
                   else f"serve steps {self.name}"),
            tags={"step": self._steps, "chunk": C,
                  **{k: counts[k] for k in on_step}, **rep},
            phase_tags={
                "admit": {k: counts[k]
                          for k in ("queue_depth", "admitted")},
                "plan": {k: counts[k] for k in (
                    "cow_forks", "evicted", *self._kv_tags,
                    "kv_pages_released")},
                "launch": {k: counts[k]
                           for k in ("h2d_bytes", "h2d_arrays")},
                "emit": {k: counts[k] for k in ("emitted", "finished")}})

    def phase_stats(self, slowest: int = 5) -> dict:
        """Where the last steps' host time went, from the bounded phase
        log (filled on every step, traced or not):
        ``{"step_phases": {"steps": n, "step": {"median_ms", "max_ms"},
        "<phase>": {...}}, "slowest_steps": [{"step", "chunk", "ms",
        "<phase>_ms", ...counts}]}`` — the `slowest` longest steps with
        their phase split."""
        with self._phase_lock:
            log = list(self._phase_log)

        def split(stamps):
            return [(b - a) * 1e3 for a, b in zip(stamps, stamps[1:])]

        def summary(xs):
            return {"median_ms": round(statistics.median(xs), 4),
                    "max_ms": round(max(xs), 4)}
        phases = {"steps": len(log)}
        if log:
            phases["step"] = summary(
                [(st[-1] - st[0]) * 1e3 for _, _, st, _ in log])
            for name, xs in zip(self.STEP_PHASES,
                                zip(*(split(st) for _, _, st, _ in log))):
                phases[name] = summary(xs)
        longest = sorted(log, key=lambda r: r[2][0] - r[2][-1])[:slowest]
        return {
            "step_phases": phases,
            "slowest_steps": [
                {"step": step, "chunk": C,
                 "ms": round((st[-1] - st[0]) * 1e3, 4),
                 **{f"{name}_ms": round(ms, 4) for name, ms in
                    zip(self.STEP_PHASES, split(st))},
                 **counts}
                for step, C, st, counts in longest]}

    def _detach_prefilled(self, actives) -> None:
        """role='prefill' (disaggregation — docs/serving.md): every slot
        whose prompt KV is complete and whose first token(s) streamed
        vacates WITHOUT freeing its pages — request, page list, and
        write cursor park on ``self.handoff`` for the fleet to move to
        a decode engine.  The cursor sits at ``len(sequence) - 1``, so
        the adopting engine's next feed is exactly the last emitted
        token: greedy streams continue bit-identical (the PR 6/14
        invariant)."""
        for s in actives:
            if self._slots[s.slot_idx] is not s:
                continue                  # finished/evicted this step
            req = s.req
            if req.done() or s.ctx < len(req.prompt) or not req.tokens:
                continue                  # still prefilling (or done)
            self._slots[s.slot_idx] = None        # pages NOT freed
            req.state = "handoff"
            self.handoffs_out += 1
            with self._lock:
                self.handoff.append(
                    {"req": req, "pages": list(s.pages),
                     "ctx": int(s.ctx), "ts": time.perf_counter()})
            self._telemetry_request(req, "handoff_ready",
                                    pages=len(s.pages), ctx=int(s.ctx),
                                    generated=len(req.tokens))

    def _trace_requests(self, plan, parents, t0: float,
                        t1: float) -> None:
        """Post-hoc per-request phase spans for one fused step, behind
        `tracing.enabled()` alone (a profiler capture must not add one
        object per live slot to the step it measures): all slots share
        the device step's wall window `t0..t1` (launch start → tokens
        read back) — the spans decompose each request's OWN timeline,
        not the device's.  Runs AFTER emission, so the parent span
        contexts and pre-emit token counts come from the `parents`
        snapshot."""
        tr = _trace.get_tracer("serve")
        rep = {} if self.name is None else {"replica": self.name}
        for i, pl in plan.items():
            s = pl["slot"]
            req = s.req
            parent, had_tokens = parents.get(i, (None, True))
            if parent is None:
                continue
            nt = pl["nt"]
            if not pl["consume"]:
                name = "serve.prefill_chunk"
                first = False
            elif not had_tokens:
                # this step's logits produced the request's FIRST
                # token: a multi-token real feed is the last prefill
                # chunk, a single-token feed is the first decode step
                first = pl["emitted"] > 0
                name = ("serve.prefill_chunk" if pl["nt_seq"] > 1
                        else "serve.first_decode")
            else:
                first = False
                name = "serve.decode"
            spec_tags = {}
            if pl["draft"] or pl["emitted"] > 1:
                spec_tags = {"drafted": pl["draft"],
                             "accepted": max(0, pl["emitted"] - 1)}
            tr.record_span(
                name, t0, t1, parent=parent,
                track=f"serve req {req.id}", request_id=req.id,
                slot=i, pages=len(s.pages), ctx=pl["ctx0"] + nt,
                tokens_fed=nt, emitted=pl["emitted"], **spec_tags,
                **rep, **({"first_token": True} if first else {}))

    def _emit(self, slot: _Slot, token: int) -> None:
        req = slot.req
        if self._abandoned or req._epoch != slot.epoch:
            # this scheduler was retired (or the request was salvaged
            # onto another replica) while the step was in flight —
            # emitting now would double-stream tokens the survivor is
            # regenerating
            return
        if deliver_token(req, token, replica=self.name):
            self._finish(slot)

    def _fail_all(self, exc: BaseException) -> None:
        """Terminal cleanup after a failed device step: every active AND
        queued request fails (the pool state is suspect and a stuck
        `result()` waiter is worse than an error)."""
        err = f"{type(exc).__name__}: {exc}"
        for slot in list(self._slots):
            if slot is None:
                continue
            self._release_slot(slot)
            self._fail_req(slot.req, err)
        with self._lock:
            queued, self._queue = list(self._queue), deque()
        for req in queued:
            self._fail_req(req, err)
        self._update_gauges()

    def _fail_req(self, req: ServeRequest, err: str) -> None:
        self._terminate_req(req, err, state="failed", phase="failed",
                            error=err)

    def _terminate_req(self, req: ServeRequest, err: str, *, state: str,
                       phase: str, **extras) -> None:
        terminate_request(req, err, state=state, phase=phase,
                          replica=self.name, **extras)

    # ------------------------------------------------------------------
    # fleet hooks (mx.serve.ServeFleet — docs/serving.md)
    # ------------------------------------------------------------------
    def take_handoffs(self) -> List[dict]:
        """Pop every parked prefill-complete handoff item
        (``{"req", "pages", "ctx", "ts"}``).  The caller OWNS the pages
        afterwards: it must either move them to a decode engine (by
        reference when it shares this allocator, by content copy +
        `requeue` otherwise) or free them — they are no longer reachable
        from any slot."""
        with self._lock:
            out = list(self.handoff)
            self.handoff.clear()
        return out

    def adopt_prefilled(self, req: ServeRequest, pages: List[int],
                        ctx_len: int) -> None:
        """Seat a prefilled request on THIS engine (decode tier of a
        disaggregated fleet).  `pages` must already be owned by this
        scheduler's allocator — adopted by reference (same process,
        shared pool: the PR 14 refcount machinery) or freshly allocated
        + `engine.install_pages`-filled (cross-process).  The request
        is parked on the adopt queue and `_admit` seats it ahead of
        plain queued work; on failure the caller still owns the pages."""
        with self._lock:
            if self.draining or self._abandoned:
                raise MXNetError(
                    f"replica {self.name or '<unnamed>'} is "
                    f"{'draining' if self.draining else 'retired'} and "
                    f"not adopting handoffs")
            req.state = "queued"
            self._adopt_q.append((req, list(pages), int(ctx_len)))

    def requeue_handoff(self, item: dict, reason: str = "kv_handoff"
                        ) -> ServeRequest:
        """Abort ONE handoff item back to the queued tier: free its
        pages here and return the request with its generated tokens
        intact — `enqueue`/router re-dispatch then re-prefills
        ``prompt + generated`` (the ONE recovery rule), so a failed
        handoff re-queues at the prefill tier and the request is never
        dropped."""
        self.allocator.free(item["pages"])
        req = item["req"]
        req.state = "queued"
        self._trace_requeue(req, reason)
        self._telemetry_request(req, "handoff_requeued", reason=reason,
                                generated=len(req.tokens))
        return req

    @property
    def handoff_depth(self) -> int:
        with self._lock:
            return len(self.handoff)

    def detach_queued(self) -> List[ServeRequest]:
        """Remove and return every QUEUED request (none hold pages) —
        the drain path hands them back to the router for re-dispatch
        while this replica's actives run to completion."""
        with self._lock:
            out = list(self._queue)
            self._queue.clear()
        self._update_gauges()
        return out

    def salvage(self, lock_timeout: float = 5.0) -> List[ServeRequest]:
        """Retire this scheduler (replica death/stall) and collect every
        in-flight request WITHOUT terminating them: actives in admission
        order first (they hold streaming progress), then the queue.  KV
        pages are deliberately NOT freed — the whole replica (pool,
        allocator, executor) is being discarded, and a wedged driver
        thread may still hold internal references.

        Safe to call from the supervisor thread while the driver is
        stuck inside the device call: `_abandoned` is set under
        ``_step_lock`` (released around `_execute`), so the stuck step
        discards its results on wake instead of double-streaming."""
        got_lock = self._step_lock.acquire(timeout=lock_timeout)
        if not got_lock:
            # the replica wedged in HOST code (e.g. an on_token
            # callback) — proceed anyway: the epoch bump below turns the
            # wedged driver's remaining emits into no-ops, so the
            # survivor owns the request's stream exclusively
            import logging
            logging.getLogger(__name__).error(
                "salvage: replica %s step lock not released in %.1fs; "
                "salvaging without it", self.name, lock_timeout)
        try:
            self._abandoned = True
            actives = [s for s in self._slots if s is not None]
            actives.sort(key=lambda s: s.admit_seq)
            for s in actives:
                self._slots[s.slot_idx] = None
            with self._lock:
                queued = list(self._queue)
                self._queue.clear()
                # handoff/adopt-parked requests ride along (their pages
                # die with the replica like every active's do); they
                # carry generated tokens, so they sort with the actives
                parked = [h["req"] for h in self.handoff] \
                    + [t[0] for t in self._adopt_q]
                self.handoff.clear()
                self._adopt_q.clear()
            reqs = [s.req for s in actives] + parked + queued
            for r in reqs:
                # transfer stream ownership: any emit this replica still
                # has in flight for an old-epoch slot is discarded
                r._epoch += 1
                r.state = "queued"
            return reqs
        finally:
            if got_lock:
                self._step_lock.release()

    def _finish(self, slot: _Slot) -> None:
        req = slot.req
        self._release_slot(slot)
        if self._abandoned or req._epoch != slot.epoch:
            return          # salvaged mid-step: the survivor finishes it
        self._n_finished += 1
        finish_request(req, replica=self.name)

    # ------------------------------------------------------------------
    def run_until_idle(self, max_steps: int = 100000) -> int:
        """Pump `step()` until queue and slots drain; returns steps run."""
        n = 0
        while n < max_steps:
            if not self.step():
                with self._lock:
                    if not self._queue:
                        break
            n += 1
        return n

    @property
    def queue_depth(self) -> int:
        with self._lock:
            return len(self._queue)

    @property
    def active_count(self) -> int:
        return sum(1 for s in self._slots if s is not None)

    def spec_stats(self) -> dict:
        """Decode-fast-path accounting: speculation accept rate, tokens
        per fused step, prefix-cache hits, COW forks (docs/serving.md;
        `bench.py --serve --spec` and `make spec-smoke` read this)."""
        steps = max(1, self._steps)
        return {
            "proposed": self.spec_proposed,
            "accepted": self.spec_accepted,
            "accept_rate": (round(self.spec_accepted
                                  / self.spec_proposed, 4)
                            if self.spec_proposed else None),
            "steps": self._steps,
            "tokens": self.tokens_emitted,
            "tokens_per_step": round(self.tokens_emitted / steps, 4),
            "steps_per_token": (round(self._steps
                                      / self.tokens_emitted, 4)
                                if self.tokens_emitted else None),
            "prefix_hit_tokens": self.prefix_hit_tokens,
            "cow_forks": self.cow_forks,
            "kv_pages_shared": self.allocator.shared_pages(),
        }

    # ------------------------------------------------------------------
    def _update_gauges(self) -> None:
        if not _tele.enabled():
            return
        spec_on = self.engine.serve_config.spec_tokens > 0
        if self.name is not None:
            # fleet replica: per-replica labeled series (N schedulers in
            # one process must not fight over the global gauges; the
            # fleet supervisor owns the aggregates)
            _tele.gauge("serve_replica_queue_depth",
                        "Per-replica requests waiting for a slot/pages",
                        labelnames=("replica",)).set(
                            self.queue_depth, replica=self.name)
            _tele.gauge("serve_replica_active_slots",
                        "Per-replica slots decoding/prefilling",
                        labelnames=("replica",)).set(
                            self.active_count, replica=self.name)
            _tele.gauge("serve_replica_free_pages",
                        "Per-replica KV pages on the free list",
                        labelnames=("replica",)).set(
                            self.allocator.free_pages, replica=self.name)
            if self.engine.prefix_index is not None:
                _tele.gauge(
                    "serve_replica_kv_pages_shared",
                    "Per-replica KV pages with more than one owner",
                    labelnames=("replica",)).set(
                        self.allocator.shared_pages(),
                        replica=self.name)
            if spec_on and self.spec_proposed:
                _tele.gauge(
                    "serve_replica_spec_accept_rate",
                    "Per-replica fraction of drafted tokens accepted",
                    labelnames=("replica",)).set(
                        self.spec_accepted / self.spec_proposed,
                        replica=self.name)
            if self.engine.role != "both":
                _tele.gauge(
                    "serve_replica_handoff_pending",
                    "Per-replica prefilled requests parked awaiting "
                    "handoff to the decode tier",
                    labelnames=("replica",)).set(
                        len(self.handoff), replica=self.name)
            return
        _tele.gauge("serve_queue_depth",
                    "Requests waiting for a slot/pages").set(
                        self.queue_depth)
        _tele.gauge("serve_active_slots",
                    "Slots currently decoding/prefilling").set(
                        self.active_count)
        _tele.gauge("serve_page_occupancy_ratio",
                    "Fraction of allocatable KV pages in use").set(
                        self.allocator.occupancy())
        _tele.gauge("serve_free_pages",
                    "KV pages on the free list").set(
                        self.allocator.free_pages)
        if self.engine.prefix_index is not None:
            _tele.gauge(
                "serve_kv_pages_shared",
                "KV pages with more than one owner (prefix cache + "
                "attached sequences)").set(self.allocator.shared_pages())
        if spec_on:
            if self.spec_proposed:
                _tele.gauge(
                    "serve_spec_accept_rate",
                    "Fraction of drafted tokens accepted by "
                    "verification (cumulative)").set(
                        self.spec_accepted / self.spec_proposed)
            if self._steps:
                _tele.gauge(
                    "serve_tokens_per_step",
                    "Tokens emitted per fused step (cumulative; > 1 "
                    "means speculation is paying)").set(
                        self.tokens_emitted / self._steps)

    def _telemetry_request(self, req: ServeRequest, phase: str,
                           **fields) -> None:
        if _tele.enabled():
            if self.name is not None:
                fields.setdefault("replica", self.name)
            _tele.event("request", request_id=req.id, phase=phase,
                        **fields)
