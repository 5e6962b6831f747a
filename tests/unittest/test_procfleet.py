"""Process-fleet tests (docs/serving.md "Process fleet"): wire framing
and RPC retry/dedupe semantics, the parent-side stream ledger
(duplicate-drop, gap-stash, done-reconciliation, ledger salvage), the
respawn budget, and router deadline expiry / shed hints while a replica
is disconnected or respawning."""
import socket
import threading
import time
import types

import pytest

from mxnet_tpu.base import MXNetError

pytestmark = pytest.mark.serve


# ---------------------------------------------------------------------------
# wire protocol: framing + client RPC semantics
# ---------------------------------------------------------------------------

def _pair():
    a, b = socket.socketpair()
    return a, b


def test_wire_frame_roundtrip_and_eof():
    from mxnet_tpu.serve import wire
    a, b = _pair()
    try:
        n = wire.send_frame(a, {"verb": "x", "payload": [1, 2, 3]})
        assert n > 4
        assert wire.recv_frame(b, timeout=5) == {"verb": "x",
                                                 "payload": [1, 2, 3]}
        a.close()
        assert wire.recv_frame(b, timeout=5) is None   # clean EOF
    finally:
        b.close()


def test_wire_mid_frame_eof_is_an_error():
    from mxnet_tpu.serve import wire
    a, b = _pair()
    try:
        a.sendall(b"\x00\x00\x00\x10partial")   # 16-byte frame, 7 sent
        a.close()
        with pytest.raises(wire.WireError):
            wire.recv_frame(b, timeout=5)
    finally:
        b.close()


def test_wire_recv_timeout():
    from mxnet_tpu.serve import wire
    a, b = _pair()
    try:
        with pytest.raises(wire.WireTimeout):
            wire.recv_frame(b, timeout=0.05)
    finally:
        a.close()
        b.close()


def _serve_one(sock, reply):
    """Read frames until one arrives, answer each with reply(frame)."""
    from mxnet_tpu.serve import wire

    def loop():
        while True:
            try:
                frame = wire.recv_frame(sock)
            except wire.WireError:
                return
            if frame is None:
                return
            for resp in reply(frame):
                wire.send_frame(sock, resp)

    t = threading.Thread(target=loop, daemon=True)
    t.start()
    return t


def test_wire_client_call_and_remote_error():
    from mxnet_tpu.serve import wire
    a, b = _pair()
    try:
        _serve_one(b, lambda f: [
            {"id": f["id"], "ok": f["verb"] != "boom",
             "echo": f.get("x"), "error": "nope"}])
        c = wire.WireClient(a, replica="rX")
        assert c.call("health", x=7)["echo"] == 7
        with pytest.raises(wire.WireRemoteError) as ei:
            c.call("boom")
        assert "rX" in str(ei.value)
        assert c.calls == 2 and c.retried == 0
    finally:
        a.close()
        b.close()


def test_wire_client_discards_stale_responses():
    from mxnet_tpu.serve import wire
    a, b = _pair()
    try:
        # a stale response (wrong id) arrives first; the client must
        # keep reading until the echo of ITS call id
        _serve_one(b, lambda f: [{"id": -999, "ok": False},
                                 {"id": f["id"], "ok": True, "v": 1}])
        c = wire.WireClient(a)
        assert c.call("ping")["v"] == 1
    finally:
        a.close()
        b.close()


def test_wire_client_retries_injected_frame_drops(monkeypatch):
    from mxnet_tpu.serve import wire
    monkeypatch.setenv("MXTPU_FAULT_SPEC", "rpc_send@1,rpc_recv@1")
    a, b = _pair()
    try:
        _serve_one(b, lambda f: [{"id": f["id"], "ok": True}])
        c = wire.WireClient(a, retries=3)
        # first attempt dies on the armed send drop, the retry's recv
        # fires the armed recv drop, the third attempt lands
        assert c.call("submit", rid=1)["ok"] is True
        assert c.retried == 2
    finally:
        a.close()
        b.close()


def test_wire_fault_exit_is_never_downgraded(monkeypatch):
    from mxnet_tpu.resilience import FaultExit
    from mxnet_tpu.serve import wire
    monkeypatch.setenv("MXTPU_FAULT_SPEC", "rpc_send@1:exit")
    a, b = _pair()
    try:
        c = wire.WireClient(a, retries=3)
        with pytest.raises(FaultExit):
            c.call("submit", rid=1)
    finally:
        a.close()
        b.close()


# ---------------------------------------------------------------------------
# the stream ledger (_RemoteScheduler) — no worker process involved
# ---------------------------------------------------------------------------

class _FakeWire:
    """Stands in for a connected ProcessReplica: records RPCs."""

    def __init__(self):
        self.ready = threading.Event()
        self.ready.set()
        self.calls = []
        self.fail = None
        self.drain_reply = []

    def call(self, verb, **kw):
        if self.fail is not None:
            raise self.fail
        self.calls.append((verb, kw))
        return {"ok": True, "queued": self.drain_reply}


def _remote_sched(name="r0"):
    from mxnet_tpu.serve import ServeConfig
    from mxnet_tpu.serve.fleet import _RemoteEngine
    cfg = types.SimpleNamespace(max_position=64)
    eng = _RemoteEngine(cfg, ServeConfig(max_slots=2, page_size=4,
                                         num_pages=0, max_len=32), name)
    eng.scheduler.replica = _FakeWire()
    return eng.scheduler


def _req(prompt=(5, 9, 2), max_new=6, **kw):
    from mxnet_tpu.serve.scheduler import ServeRequest
    return ServeRequest(list(prompt), max_new, **kw)


def test_ledger_enqueue_sends_submit_rpc():
    s = _remote_sched()
    r = _req()
    s.enqueue(r)
    verb, kw = s.replica.calls[0]
    assert verb == "submit"
    assert kw["rid"] == r.id and kw["prompt"] == [5, 9, 2]
    assert kw["max_new"] == 6
    assert s.queue_depth == 1 and s.inflight == 1
    assert r.state == "queued"


def test_ledger_enqueue_parks_when_disconnected():
    s = _remote_sched()
    s.replica.ready.clear()    # worker warming up / respawning
    with pytest.raises(MXNetError):
        s.enqueue(_req())
    s.replica.ready.set()
    s.replica.fail = MXNetError("wire down")
    with pytest.raises(MXNetError):
        s.enqueue(_req())
    assert s.inflight == 0      # nothing ledgered on a failed dispatch


def test_ledger_applies_tokens_contiguously_never_twice():
    s = _remote_sched()
    seen = []
    r = _req(on_token=lambda t, req: seen.append(t))
    s.enqueue(r)
    s.on_token(r.id, 0, 10)
    s.on_token(r.id, 0, 10)        # duplicate (re-sent frame): dropped
    s.on_token(r.id, 2, 30)        # gap: stashed, NOT applied
    assert r.tokens == [10]
    s.on_token(r.id, 1, 20)        # fills the gap -> 20 then 30 apply
    assert r.tokens == [10, 20, 30] == seen
    s.on_token(999, 0, 7)          # unknown rid: ignored
    assert r.tokens == [10, 20, 30]


def test_ledger_token_completion_finishes_request():
    s = _remote_sched()
    r = _req(max_new=2)
    s.enqueue(r)
    s.on_token(r.id, 0, 10)
    s.on_token(r.id, 1, 20)
    assert r.state == "finished" and r.done()
    assert r.result(timeout=1) == [5, 9, 2, 10, 20]
    assert s.inflight == 0
    # late events after the finish are no-ops
    s.on_token(r.id, 1, 99)
    s.on_done(r.id, "finished", [10, 20], None, False)
    assert r.tokens == [10, 20]


def test_ledger_done_reconciles_raced_tail():
    # the done record carries the FULL token list: tokens whose tok
    # frames raced the close are delivered from it, exactly once
    s = _remote_sched()
    r = _req(max_new=4)
    s.enqueue(r)
    s.on_token(r.id, 0, 10)
    s.on_done(r.id, "finished", [10, 20, 30, 40], None, False)
    assert r.tokens == [10, 20, 30, 40]
    assert r.state == "finished"


def test_ledger_done_expired_and_failed():
    s = _remote_sched()
    r1, r2 = _req(), _req()
    s.enqueue(r1)
    s.enqueue(r2)
    s.on_done(r1.id, "failed", [], "deadline exceeded (5 ms)", True)
    assert r1.state == "failed" and "deadline exceeded" in r1.error
    s.on_done(r2.id, "failed", [], "worker blew up", False)
    assert r2.state == "failed" and "worker blew up" in r2.error


def test_ledger_salvage_progressed_first_epoch_bumped():
    s = _remote_sched()
    fresh, prog = _req(), _req(prompt=[7, 1])
    s.enqueue(fresh)
    s.enqueue(prog)
    s.on_token(prog.id, 0, 11)
    out = s.salvage()
    assert out == [prog, fresh]          # progressed streams first
    assert all(r._epoch == 1 and r.state == "queued" for r in out)
    assert s.inflight == 0
    # a retired proxy ignores late wire events and rejects new work
    s.on_token(prog.id, 1, 12)
    assert prog.tokens == [11]
    with pytest.raises(MXNetError):
        s.enqueue(_req())


def test_ledger_failover_refolds_progress_into_prompt():
    # the SIGKILL resume contract: the re-dispatch prompt is
    # prompt + emitted tokens, max_new shrinks by what already streamed
    s1, s2 = _remote_sched("r0"), _remote_sched("r1")
    r = _req(prompt=[5, 9, 2], max_new=6)
    s1.enqueue(r)
    s1.on_token(r.id, 0, 10)
    s1.on_token(r.id, 1, 20)
    (salvaged,) = s1.salvage()
    assert salvaged is r
    s2.enqueue(r)
    verb, kw = s2.replica.calls[0]
    assert kw["prompt"] == [5, 9, 2, 10, 20]
    assert kw["max_new"] == 4
    # the new worker's indices restart at 0; delivery continues the
    # stream without re-emitting
    s2.on_token(r.id, 0, 30)
    assert r.tokens == [10, 20, 30]


def test_ledger_drain_hands_back_only_queued():
    s = _remote_sched()
    queued, active = _req(), _req()
    s.enqueue(queued)
    s.enqueue(active)
    s.on_token(active.id, 0, 10)
    s.replica.drain_reply = [queued.id]
    handed = s.detach_queued()
    assert handed == [queued] and queued.state == "queued"
    assert s.inflight == 1               # the active stream stays


def test_remote_scheduler_validates_like_the_real_one():
    s = _remote_sched()
    with pytest.raises(MXNetError):
        s.validate_request([], 4)                       # empty prompt
    with pytest.raises(MXNetError):
        s.validate_request([1] * 64, 4)                 # > max_len
    assert s.validate_request([1, 2], 4) == [1, 2]


# ---------------------------------------------------------------------------
# respawn budget (fake replicas — no engines, no processes)
# ---------------------------------------------------------------------------

class _FakeDriveSched:
    def __init__(self):
        self.active_count = 0
        self.queue_depth = 0
        self.draining = False
        self._abandoned = False
        self.name = None
        self.salvage_on_error = True
        self.enqueued = []

    def enqueue(self, req, front=False):
        self.enqueued.append(req)

    def salvage(self, lock_timeout=5.0):
        self._abandoned = True
        return []

    def detach_queued(self):
        return []

    def validate_request(self, prompt, max_new_tokens):
        return [int(t) for t in prompt]


class _FakeDriveEngine:
    def __init__(self):
        self.scheduler = _FakeDriveSched()
        self.allocator = types.SimpleNamespace(free_pages=8,
                                               total_pages=8)
        self.serve_config = types.SimpleNamespace(max_slots=2)
        self._steps_executed = 0
        self._execs = {"step": object()}

    def warmup(self):
        return 0.0

    def adopt_executables(self, other):
        pass

    def step(self):
        self._steps_executed += 1
        return False


def _fake_fleet(monkeypatch, budget, n=2):
    from mxnet_tpu.serve import fleet as fleet_mod

    def make(self, idx, generation=0):
        rep = fleet_mod.Replica(f"r{idx}", _FakeDriveEngine())
        rep.generation = generation
        return rep

    monkeypatch.setattr(fleet_mod.ServeFleet, "_make_replica", make)
    f = fleet_mod.ServeFleet(object(), replicas=n,
                             respawn_budget=budget,
                             stall_timeout=5.0,
                             supervise_interval=0.01)
    return f


def _wait(cond, timeout=10.0):
    deadline = time.time() + timeout
    while not cond():
        assert time.time() < deadline, "condition never held"
        time.sleep(0.005)


def test_respawn_replaces_dead_replica_in_place(monkeypatch):
    fleet = _fake_fleet(monkeypatch, budget=1)
    with fleet:
        old = fleet.replicas[0]
        fleet.kill("r0", error="chaos")
        _wait(lambda: fleet.respawns == 1
              and fleet.replicas[0] is not old
              and fleet.replicas[0].state == "running")
        reborn = fleet.replicas[0]
        assert reborn.name == "r0" and reborn.generation == 1
        assert fleet.retired == [old] and old.state == "dead"
        # budget exhausted: the next death retires permanently
        fleet.kill("r0", error="chaos again")
        time.sleep(0.2)
        assert fleet.replicas[0].state == "dead"
        assert fleet.respawns == 1
        # the fleet still serves from the survivor
        assert fleet.replicas[1].state == "running"


def test_respawn_budget_zero_keeps_permanent_retire(monkeypatch):
    fleet = _fake_fleet(monkeypatch, budget=0)
    with fleet:
        fleet.kill("r0")
        time.sleep(0.2)
        assert fleet.replicas[0].state == "dead"
        assert fleet.respawns == 0 and fleet.retired == []


def test_closed_fleet_stays_closed(monkeypatch):
    fleet = _fake_fleet(monkeypatch, budget=5)
    fleet.start()
    fleet.close()
    with pytest.raises(MXNetError, match="closed"):
        fleet.start()
    # a post-close death never respawns
    assert fleet.respawns == 0


# ---------------------------------------------------------------------------
# router while a replica is disconnected/respawning (satellite 3)
# ---------------------------------------------------------------------------

class _DisconnectedSched(_FakeDriveSched):
    """A process replica whose worker is gone mid-respawn: running
    state, but every dispatch fails at the wire."""

    def enqueue(self, req, front=False):
        raise MXNetError("replica r0 is not connected yet")


def _disconnected_replica():
    rep = types.SimpleNamespace(
        name="r0", state="running",
        engine=types.SimpleNamespace(
            scheduler=_DisconnectedSched(),
            allocator=types.SimpleNamespace(free_pages=8, total_pages=8),
            serve_config=types.SimpleNamespace(max_slots=2)),
        notify=lambda: None)
    return rep


def test_router_parks_and_expires_exactly_once_while_disconnected():
    from mxnet_tpu.serve import RequestRouter
    rep = _disconnected_replica()
    router = RequestRouter(lambda: [rep], queue_bound=8)
    h = router.submit([1, 2], max_new_tokens=4, deadline_ms=30)
    assert router.queue_depth == 1        # parked, not dropped
    time.sleep(0.05)
    assert router.sweep_expired() == 1
    assert router.sweep_expired() == 0    # exactly once
    assert h.state == "failed"
    assert "deadline exceeded" in h.error
    assert "parked at the router" in h.error
    with pytest.raises(MXNetError):
        h.result(timeout=1)


def test_router_shed_hint_while_replica_respawning():
    from mxnet_tpu.serve import RequestRouter, ShedError
    rep = _disconnected_replica()
    # the respawning replica's last heartbeat left it saturated, so
    # every submit parks at the router; the bound then sheds with an
    # actionable retry hint
    rep.engine.scheduler.queue_depth = 2
    router = RequestRouter(lambda: [rep], queue_bound=2)
    router.submit([1], max_new_tokens=2)
    router.submit([2], max_new_tokens=2)
    with pytest.raises(ShedError) as ei:
        router.submit([3], max_new_tokens=2)
    assert ei.value.reason == "queue_full"
    assert ei.value.retry_after_ms > 0
    # the hint is actionable: once the replica reconnects, the parked
    # work drains and a retry is admitted
    rep.engine.scheduler = _FakeDriveSched()
    router.feed(rep)
    assert router.queue_depth == 0
    router.submit([3], max_new_tokens=2)


# ---------------------------------------------------------------------------
# spec dir round-trip (worker-side engine reconstruction)
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_spec_roundtrip(tmp_path):
    import numpy as onp

    import mxnet_tpu as mx
    from mxnet_tpu.models.gpt import GPTConfig, GPTForCausalLM
    from mxnet_tpu.serve import ServeConfig
    from mxnet_tpu.serve.decode import extract_decode_weights
    from mxnet_tpu.serve.worker import load_spec, write_spec

    cfg = GPTConfig(vocab_size=96, hidden_size=32, num_layers=2,
                    num_heads=4, intermediate_size=64, max_position=64,
                    dropout=0.0)
    model = GPTForCausalLM(cfg)
    model.initialize()
    model(mx.np.array([[1, 2]], dtype="int32"))
    sc = ServeConfig(max_slots=3, page_size=8, deadline_ms=123.0)
    write_spec(str(tmp_path), model, sc)

    shim, sc2 = load_spec(str(tmp_path))
    assert sc2 == sc
    assert vars(shim.cfg)["hidden_size"] == 32
    P0 = extract_decode_weights(model)
    P1 = extract_decode_weights(shim)    # the prebuilt-pytree shortcut
    assert P1 is shim._decode_weights
    for k in ("embed", "pos", "lnf_g", "lnf_b", "head"):
        if P0[k] is None:
            assert P1[k] is None
        else:
            onp.testing.assert_array_equal(onp.asarray(P0[k]),
                                           onp.asarray(P1[k]))
    assert len(P0["layers"]) == len(P1["layers"]) == 2
    for L0, L1 in zip(P0["layers"], P1["layers"]):
        assert set(L0) == set(L1)
        for k in L0:
            onp.testing.assert_array_equal(onp.asarray(L0[k]),
                                           onp.asarray(L1[k]))


def test_worker_refuses_to_serve_off_its_parents_platform():
    """A worker that ended up on another platform than its fleet parent
    (a chip belongs to one process: JAX leaves a process that could not
    claim it on the CPU with one log line) reports a fatal event and
    raises before it builds an engine."""
    from mxnet_tpu.serve.worker import Worker
    w = Worker.__new__(Worker)
    w.name, w.platform, sent = "r0", "tpu", []
    w._send = sent.append
    with pytest.raises(MXNetError, match="'cpu' but its fleet parent "
                                         "runs on 'tpu'"):
        w._check_platform()
    assert sent and sent[0]["ev"] == "fatal"
    w.platform, sent[:] = "cpu", []
    w._check_platform()              # same platform: nothing to report
    assert not sent
