"""Unified runtime telemetry: metrics registry primitives, Prometheus/JSON
export, memory monitor, run journal, HTTP exposition, and the framework
instrumentation that feeds them (train step, prefetcher, DataLoader,
checkpoints, fault registry, compile cache).  Runs on the virtual 8-device
CPU mesh; `telemetry` marker (tier-1)."""
import json
import os
import time
import urllib.request

import numpy as onp
import pytest

import jax
import jax.numpy as jnp

import mxnet_tpu as mx  # noqa: F401
from mxnet_tpu import optimizer as opt
from mxnet_tpu import telemetry as tele
from mxnet_tpu.gluon import nn
from mxnet_tpu.parallel import (DevicePrefetcher, make_mesh,
                                make_sharded_train_step)
from mxnet_tpu.utils.checkpoint import CheckpointManager

pytestmark = pytest.mark.telemetry


@pytest.fixture(autouse=True)
def _clean_telemetry():
    """Each test starts disabled with an empty registry — and leaves the
    process that way (telemetry state is process-wide)."""
    tele.disable()
    tele.registry().reset()
    yield
    tele.disable()
    tele.registry().reset()


# ---------------------------------------------------------------------------
# registry primitives
# ---------------------------------------------------------------------------

def test_counter_inc_and_value():
    c = tele.counter("c_total", "help")
    assert c.value() == 0
    c.inc()
    c.inc(2.5)
    assert c.value() == 3.5


def test_counter_rejects_decrease():
    c = tele.counter("c_down")
    with pytest.raises(ValueError, match="cannot decrease"):
        c.inc(-1)


def test_counter_labels_partition_series():
    c = tele.counter("c_lab", labelnames=("point",))
    c.inc(point="a")
    c.inc(3, point="b")
    assert c.value(point="a") == 1
    assert c.value(point="b") == 3
    with pytest.raises(ValueError, match="takes labels"):
        c.inc()  # label missing
    with pytest.raises(ValueError, match="takes labels"):
        c.inc(wrong="x")


def test_gauge_set_inc_dec():
    g = tele.gauge("g1")
    g.set(10)
    g.inc(5)
    g.dec(2)
    assert g.value() == 13


def test_histogram_buckets_cumulative_and_sum():
    h = tele.histogram("h_ms", buckets=(1, 10, 100))
    for v in (0.5, 5, 50, 5000):  # one per bucket incl. implicit +Inf
        h.observe(v)
    assert h.count() == 4
    assert h.sum() == pytest.approx(5055.5)
    (labels, series), = [(s["labels"], s) for s in
                         tele.snapshot()["h_ms"]["series"]]
    assert labels == {}
    assert series["buckets"] == {"1": 1, "10": 2, "100": 3, "+Inf": 4}


def test_registry_get_or_create_and_kind_mismatch():
    c1 = tele.counter("same_name")
    assert tele.counter("same_name") is c1
    with pytest.raises(ValueError, match="already registered"):
        tele.gauge("same_name")


def test_invalid_metric_and_label_names_raise():
    with pytest.raises(ValueError, match="invalid metric name"):
        tele.counter("bad-name")
    with pytest.raises(ValueError, match="invalid label name"):
        tele.counter("okname", labelnames=("bad-label",))


def test_registry_reset_clears():
    tele.counter("gone").inc()
    tele.registry().reset()
    assert "gone" not in tele.registry()


# ---------------------------------------------------------------------------
# export formats
# ---------------------------------------------------------------------------

def test_prometheus_exposition_shape():
    c = tele.counter("req_total", "requests", labelnames=("route",))
    c.inc(route='tr"ain\n')  # exercises label escaping
    tele.histogram("lat_ms", "latency", buckets=(1,)).observe(0.5)
    text = tele.to_prometheus()
    assert "# TYPE req_total counter" in text
    assert r'req_total{route="tr\"ain\n"} 1' in text
    assert "# HELP lat_ms latency" in text
    assert 'lat_ms_bucket{le="1"} 1' in text
    assert 'lat_ms_bucket{le="+Inf"} 1' in text
    assert "lat_ms_sum 0.5" in text and "lat_ms_count 1" in text


def test_prometheus_parses_with_stdlib_parser():
    """Cross-check against the pure-stdlib parser the smoke target uses."""
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "telemetry_smoke",
        os.path.join(os.path.dirname(__file__), "..", "..", "tools",
                     "telemetry_smoke.py"))
    smoke = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(smoke)
    tele.counter("parse_me", labelnames=("k",)).inc(k="v1")
    tele.histogram("parse_ms").observe(3.3)
    tele.gauge("parse_g").set(-2.5)
    parsed = smoke.parse_prometheus(tele.to_prometheus())
    assert parsed["parse_me"] == [({"k": "v1"}, 1.0)]
    assert ({}, -2.5) in parsed["parse_g"]
    assert any(lb.get("le") == "+Inf" and v == 1
               for lb, v in parsed["parse_ms_bucket"])


def test_json_export_round_trips():
    tele.gauge("j_g").set(4)
    doc = json.loads(tele.to_json())
    assert doc["metrics"]["j_g"]["type"] == "gauge"
    assert doc["metrics"]["j_g"]["series"] == [{"labels": {}, "value": 4.0}]


# ---------------------------------------------------------------------------
# enable/disable gating + journal
# ---------------------------------------------------------------------------

def test_disabled_by_default_and_toggle():
    assert not tele.enabled()
    tele.enable()
    assert tele.enabled()
    tele.disable()
    assert not tele.enabled()


def test_event_is_noop_when_disabled(tmp_path):
    tele.event("ghost", step=1)          # no journal, disabled: no crash
    tele.enable()                        # enabled but journal-less
    tele.event("ghost2", step=2)
    assert tele.journal() is None


def test_journal_rows_and_monotonic_seq(tmp_path):
    path = str(tmp_path / "j.jsonl")
    tele.enable(journal_path=path)
    tele.event("a", step=3, foo="bar")
    tele.event("b")                      # inherits step 3
    tele.event("c", step=7)
    tele.disable()
    rows = tele.RunJournal.read(path)
    assert [r["event"] for r in rows] == ["a", "b", "c"]
    assert [r["seq"] for r in rows] == [1, 2, 3]
    assert [r["step"] for r in rows] == [3, 3, 7]
    assert rows[0]["foo"] == "bar"
    assert all(isinstance(r["ts"], float) for r in rows)


def test_journal_record_after_close_is_dropped(tmp_path):
    j = tele.RunJournal(str(tmp_path / "closed.jsonl"))
    j.record("kept")
    j.close()
    j.record("dropped")
    assert [r["event"] for r in tele.RunJournal.read(j.path)] == ["kept"]


def test_enable_is_idempotent_and_merges_journal(tmp_path):
    tele.enable()
    assert tele.journal() is None
    tele.enable(journal_path=str(tmp_path / "late.jsonl"))
    tele.event("late")
    assert len(tele.RunJournal.read(tele.journal().path)) == 1


@pytest.mark.parametrize("env_val,want_enabled,want_journal", [
    ("1", True, False),
    ("false", False, False),
    ("JOURNAL", True, True),   # placeholder: a tmp .jsonl path
])
def test_env_auto_enable_semantics(tmp_path, env_val, want_enabled,
                                   want_journal):
    """The real import-time hook: MXTPU_TELEMETRY=1 enables, =false stays
    off, =<path.jsonl> enables + opens the journal there — checked in a
    fresh interpreter, where the import actually runs the hook."""
    import subprocess
    import sys
    jpath = str(tmp_path / "env.jsonl")
    if env_val == "JOURNAL":
        env_val = jpath
    env = dict(os.environ, MXTPU_TELEMETRY=env_val, JAX_PLATFORMS="cpu")
    code = (
        "import mxnet_tpu.telemetry as t; import json, sys; "
        "j = t.journal(); "
        "print(json.dumps({'enabled': t.enabled(), "
        "'journal': j.path if j else None}))")
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr[-500:]
    got = json.loads(out.stdout.strip().splitlines()[-1])
    assert got["enabled"] == want_enabled
    assert (got["journal"] == jpath) == want_journal


# ---------------------------------------------------------------------------
# memory monitor + HTTP server
# ---------------------------------------------------------------------------

def test_memory_monitor_sample_once_records_gauges():
    keep = jnp.ones((256, 256), jnp.float32)  # noqa: F841 — stays live
    out = tele.MemoryMonitor().sample_once()
    assert out["live_bytes"], "expected at least one device with live bytes"
    snap = tele.snapshot()
    assert any(s["value"] > 0
               for s in snap["device_live_bytes"]["series"])
    assert snap["host_rss_bytes"]["series"][0]["value"] > 0


def test_memory_monitor_background_thread():
    mm = tele.MemoryMonitor(interval=0.02)
    mm.start()
    deadline = time.monotonic() + 5.0
    while mm.samples < 2 and time.monotonic() < deadline:
        time.sleep(0.02)
    mm.stop()
    assert mm.samples >= 2
    n = mm.samples
    time.sleep(0.08)
    assert mm.samples == n  # stopped means stopped


def test_http_server_serves_prometheus_and_json():
    tele.counter("served_total").inc(5)
    tele.enable(port=0)  # ephemeral
    srv = tele.metrics_server()
    assert srv is not None and srv.port
    base = f"http://127.0.0.1:{srv.port}"
    text = urllib.request.urlopen(base + "/metrics").read().decode()
    assert "served_total 5" in text
    doc = json.loads(urllib.request.urlopen(
        base + "/metrics.json").read().decode())
    assert doc["metrics"]["served_total"]["series"][0]["value"] == 5
    with pytest.raises(urllib.error.HTTPError):
        urllib.request.urlopen(base + "/nope")
    tele.disable()


# ---------------------------------------------------------------------------
# framework instrumentation
# ---------------------------------------------------------------------------

def _loss_fn(out, x, y):
    return jnp.mean((out - y) ** 2)


def _make_step(optimizer=None, **kw):
    mx.random.seed(7)
    net = nn.Dense(4, in_units=8)
    net.initialize()
    mesh = make_mesh({"dp": 2}, jax.devices("cpu")[:2])
    return make_sharded_train_step(
        net, optimizer or opt.SGD(learning_rate=1e-2), _loss_fn, mesh,
        num_model_args=1, **kw)


def _data(n=8, seed=0):
    rng = onp.random.RandomState(seed)
    return (rng.uniform(-1, 1, (n, 8)).astype(onp.float32),
            rng.uniform(-1, 1, (n, 4)).astype(onp.float32))


def test_dispatch_records_histogram_gauge_counter(tmp_path):
    tele.enable(journal_path=str(tmp_path / "d.jsonl"))
    step = _make_step()
    xs, ys = _data()
    for _ in range(3):
        step.dispatch(xs, ys)
    snap = tele.snapshot()
    assert snap["step_dispatch_ms"]["series"][0]["count"] == 3
    assert snap["step_dispatch_ms"]["series"][0]["sum"] > 0
    assert snap["trace_count"]["series"][0]["value"] == 1
    assert "steps_in_flight" in snap  # gauge registered with some value
    rows = tele.RunJournal.read(tele.journal().path)
    dispatched = [r["step"] for r in rows if r["event"] == "step_dispatched"]
    assert dispatched == [1, 2, 3]


def test_instrumentation_noop_when_disabled():
    step = _make_step()
    xs, ys = _data()
    for _ in range(2):
        step.dispatch(xs, ys)
    assert "step_dispatch_ms" not in tele.registry()
    assert "trace_count" not in tele.registry()


def test_warmup_journals_compile_events(tmp_path):
    tele.enable(journal_path=str(tmp_path / "w.jsonl"))
    step = _make_step()
    xs, ys = _data()
    secs = step.warmup(xs, ys)
    rows = tele.RunJournal.read(tele.journal().path)
    events = [r["event"] for r in rows]
    assert "compile_start" in events and "compile_end" in events
    end = next(r for r in rows if r["event"] == "compile_end")
    assert end["seconds"] == pytest.approx(secs, rel=0.2, abs=0.05)
    assert "compile" in events  # the jit trace itself


def test_retrace_event_and_counter(tmp_path):
    tele.enable(journal_path=str(tmp_path / "r.jsonl"))
    # momentum: SGD gains a real state leaf whose dtype can be corrupted
    step = _make_step(optimizer=opt.SGD(learning_rate=1e-2, momentum=0.9))
    xs, ys = _data()
    step.dispatch(xs, ys)
    # documented silent-retrace failure mode: corrupt a state dtype
    name = step.diff_names[0]
    step.opt_state[name] = jax.tree_util.tree_map(
        lambda s: s.astype(jnp.bfloat16), step.opt_state[name])
    step.dispatch(xs, ys)
    assert tele.registry().get("trace_count").value() == 2
    rows = tele.RunJournal.read(tele.journal().path)
    retr = [r for r in rows if r["event"] == "retrace"]
    assert len(retr) == 1 and retr[0]["trace_count"] == 2
    assert retr[0]["drift"]  # names the drifted avals


def test_prefetcher_metrics(tmp_path):
    tele.enable()
    xs, ys = _data()
    src = [(xs, ys)] * 4
    with DevicePrefetcher(iter(src), depth=2) as pf:
        batches = list(pf)
    assert len(batches) == 4
    snap = tele.snapshot()
    assert snap["prefetch_wait_ms"]["series"][0]["count"] == 4
    assert "prefetch_occupancy" in snap


def test_checkpoint_write_restore_metrics(tmp_path):
    tele.enable(journal_path=str(tmp_path / "c.jsonl"))
    step = _make_step()
    xs, ys = _data()
    step.dispatch(xs, ys)
    mgr = CheckpointManager(str(tmp_path / "ckpts"), keep=2)
    mgr.save(step, 1)
    assert mgr.restore(step) == 1
    snap = tele.snapshot()
    assert snap["checkpoint_write_ms"]["series"][0]["count"] == 1
    assert snap["checkpoint_restore_ms"]["series"][0]["count"] == 1
    rows = tele.RunJournal.read(tele.journal().path)
    w = next(r for r in rows if r["event"] == "checkpoint_write")
    assert w["step"] == 1 and w["ms"] > 0 and not w["async_save"]
    r = next(r for r in rows if r["event"] == "checkpoint_restore")
    assert r["fallbacks"] == 0


def test_checkpoint_quarantine_counter(tmp_path):
    tele.enable(journal_path=str(tmp_path / "q.jsonl"))
    step = _make_step()
    mgr = CheckpointManager(str(tmp_path / "ckpts"), keep=3)
    xs, ys = _data()
    step.dispatch(xs, ys)
    mgr.save(step, 1)
    step.dispatch(xs, ys)
    p2 = mgr.save(step, 2)
    with open(p2, "r+b") as f:  # bit-rot the newest checkpoint
        f.seek(10)
        f.write(b"\xff\xff\xff\xff")
    assert mgr.restore(step) == 1  # fell back through the chain
    assert tele.registry().get("checkpoint_quarantines").value() == 1
    rows = tele.RunJournal.read(tele.journal().path)
    q = next(r for r in rows if r["event"] == "checkpoint_quarantine")
    assert "mismatch" in q["reason"]
    r = next(r for r in rows if r["event"] == "checkpoint_restore")
    assert r["fallbacks"] == 1


def test_fault_trigger_counter(monkeypatch):
    from mxnet_tpu import resilience
    tele.enable()
    monkeypatch.setenv(resilience.ENV_VAR, "tele_point@2:ValueError")
    reg = resilience.fault_registry()
    reg.fire("tele_point")  # hit 1: not armed
    assert tele.registry().get("fault_triggers") is None
    with pytest.raises(ValueError):
        reg.fire("tele_point")
    assert tele.registry().get("fault_triggers").value(
        point="tele_point") == 1


def test_compile_cache_listener_counts_hits_and_misses():
    tele.enable()
    tele._on_jax_event("/jax/compilation_cache/cache_misses")
    tele._on_jax_event("/jax/compilation_cache/cache_hits")
    tele._on_jax_event("/jax/compilation_cache/cache_hits")
    tele._on_jax_event("/jax/unrelated/event")
    assert tele.registry().get("compile_cache_misses").value() == 1
    assert tele.registry().get("compile_cache_hits").value() == 2
    tele.disable()
    tele._on_jax_event("/jax/compilation_cache/cache_misses")  # gated off
    assert tele.registry().get("compile_cache_misses").value() == 1


def test_enable_compile_cache_installs_listener(tmp_path, monkeypatch):
    from mxnet_tpu import runtime
    monkeypatch.setattr(tele, "_cc_listener_installed", False)
    calls = []
    monkeypatch.setattr(tele, "install_compile_cache_listener",
                        lambda: calls.append(1) or True)
    monkeypatch.setattr(runtime, "_enabled", False)
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "cc"))
    assert runtime.enable_compile_cache() == str(tmp_path / "cc")
    assert calls == [1]


# ---------------------------------------------------------------------------
# DataLoader worker supervision + the 10-step acceptance loop
# ---------------------------------------------------------------------------

class _TeleDataset:
    """Deterministic picklable dataset for spawn workers."""

    def __init__(self, n):
        self._n = n

    def __len__(self):
        return self._n

    def __getitem__(self, i):
        return onp.full((4,), i, onp.float32)


def _run_loader_epoch(worker_respawns=8):
    from mxnet_tpu.gluon.data import DataLoader
    dl = DataLoader(_TeleDataset(8), batch_size=2, num_workers=1,
                    thread_pool=False, timeout=60,
                    worker_respawns=worker_respawns)
    out = [onp.asarray(b.asnumpy()) for b in dl]
    dl._proc_pool.shutdown()
    return out


def test_dataloader_death_respawn_metrics(tmp_path, monkeypatch,
                                          shm_leak_check):
    tele.enable(journal_path=str(tmp_path / "dl.jsonl"))
    # every worker incarnation hard-exits on its 2nd batch
    monkeypatch.setenv("MXTPU_FAULT_SPEC", "worker_exec@2:exit")
    batches = _run_loader_epoch()
    assert len(batches) == 4
    snap = tele.snapshot()
    assert snap["dataloader_respawns"]["series"][0]["value"] >= 1
    assert snap["dataloader_worker_deaths"]["series"][0]["value"] >= 1
    assert snap["dataloader_batch_wait_ms"]["series"][0]["count"] == 4
    rows = tele.RunJournal.read(tele.journal().path)
    death = next(r for r in rows if r["event"] == "worker_death")
    assert death["exit_code"] == 86  # resilience.EXIT_CODE
    respawn = next(r for r in rows if r["event"] == "worker_respawn")
    assert respawn["resubmitted"] == death["lost_batches"]


def test_threadpool_loader_batch_wait_histogram():
    from mxnet_tpu.gluon.data import DataLoader
    tele.enable()
    dl = DataLoader(_TeleDataset(8), batch_size=2, num_workers=2,
                    thread_pool=True)
    assert len(list(dl)) == 4
    assert tele.snapshot()["dataloader_batch_wait_ms"]["series"][0][
        "count"] == 4


def test_ten_step_loop_acceptance(tmp_path, monkeypatch, shm_leak_check):
    """The ISSUE acceptance criterion end to end: a 10-step CPU training
    loop with telemetry enabled + one checkpoint save + one simulated
    worker death produces (a) a snapshot with non-zero step_dispatch_ms
    counts, a steps_in_flight gauge, and checkpoint/dataloader counters,
    and (b) a journal whose step ids are strictly monotonic with at least
    one compile and one checkpoint_write event."""
    journal_path = str(tmp_path / "accept.jsonl")
    tele.enable(journal_path=journal_path)

    # one simulated worker death while streaming real batches
    monkeypatch.setenv("MXTPU_FAULT_SPEC", "worker_exec@2:exit")
    loader_batches = _run_loader_epoch()
    assert len(loader_batches) == 4
    monkeypatch.delenv("MXTPU_FAULT_SPEC")

    step = _make_step()
    xs, ys = _data()
    step.warmup(xs, ys)
    mgr = CheckpointManager(str(tmp_path / "ckpts"), keep=2)
    for i in range(10):
        step.dispatch(*step.place_batch(xs, ys))
        if i == 4:
            mgr.save(step, step._t)

    snap = tele.snapshot()
    # (a) registry snapshot
    dispatch = snap["step_dispatch_ms"]["series"][0]
    assert dispatch["count"] == 10 and dispatch["sum"] > 0
    assert any(v > 0 for v in dispatch["buckets"].values())
    assert snap["steps_in_flight"]["series"][0]["value"] >= 0
    assert snap["checkpoint_write_ms"]["series"][0]["count"] == 1
    assert snap["dataloader_respawns"]["series"][0]["value"] >= 1
    assert snap["trace_count"]["series"][0]["value"] == 1
    # exposition of the whole run parses
    assert "step_dispatch_ms_bucket" in tele.to_prometheus()

    # (b) journal
    rows = tele.RunJournal.read(journal_path)
    seqs = [r["seq"] for r in rows]
    assert seqs == sorted(seqs) and len(set(seqs)) == len(seqs)
    dispatched = [r["step"] for r in rows if r["event"] == "step_dispatched"]
    assert dispatched == sorted(dispatched)
    assert all(b > a for a, b in zip(dispatched, dispatched[1:]))
    assert len(dispatched) == 10
    assert any(r["event"].startswith("compile") for r in rows)
    assert any(r["event"] == "checkpoint_write" for r in rows)
    assert any(r["event"] == "worker_death" for r in rows)


# ---------------------------------------------------------------------------
# PR 4 satellites: histogram bucket overrides, journal failure modes,
# atexit thread shutdown
# ---------------------------------------------------------------------------

def test_histogram_custom_buckets_override():
    h = tele.registry().histogram("gnorm", buckets=(0.1, 1.0, 10.0))
    assert h.buckets == (0.1, 1.0, 10.0, float("inf"))
    h.observe(0.5)
    assert h.count() == 1


def test_histogram_buckets_must_be_monotone():
    with pytest.raises(ValueError, match="strictly increasing"):
        tele.registry().histogram("bad_b", buckets=(1.0, 1.0, 2.0))
    with pytest.raises(ValueError, match="strictly increasing"):
        tele.registry().histogram("bad_b2", buckets=(5.0, 1.0))
    with pytest.raises(ValueError, match="at least one"):
        tele.registry().histogram("bad_b3", buckets=())


def test_histogram_reregister_conflicting_buckets_raises():
    tele.registry().histogram("h_conf", buckets=(1.0, 2.0))
    with pytest.raises(ValueError, match="already registered with"):
        tele.registry().histogram("h_conf", buckets=(1.0, 3.0))
    # same explicit buckets or no buckets at all: fine, same object
    h1 = tele.registry().histogram("h_conf", buckets=(1.0, 2.0))
    h2 = tele.registry().histogram("h_conf")
    assert h1 is h2
    # hot-path callers that omit buckets never conflict with a custom one
    assert tele.histogram("h_conf").buckets == (1.0, 2.0, float("inf"))


def test_histogram_default_buckets_when_unspecified():
    h = tele.histogram("h_default")
    assert h.buckets[:-1] == tele.DEFAULT_MS_BUCKETS


def test_journal_unwritable_path_degrades(tmp_path):
    """An unwritable journal path must disable the journal, not abort the
    training run that asked for observability (no raise mid-training)."""
    blocker = tmp_path / "file"
    blocker.write_text("x")          # a FILE where a directory is needed
    j = tele.RunJournal(str(blocker / "sub" / "j.jsonl"))
    assert j.disabled
    j.record("event_after_degrade", step=1)   # silent no-op, no raise
    j.close()


def test_enable_with_unwritable_journal_keeps_training(tmp_path):
    blocker = tmp_path / "f"
    blocker.write_text("x")
    tele.enable(journal_path=str(blocker / "nope" / "j.jsonl"))
    assert tele.enabled()
    assert tele.journal().disabled
    tele.event("anything", step=1)   # must not raise
    tele.counter("still_works").inc()
    assert tele.counter("still_works").value() == 1


def test_journal_no_rotation_unbounded_append(tmp_path):
    """Cap-behavior contract, stated as a test: the journal does NOT
    rotate — every row is retained in one append-only file (operators
    size the filesystem; the bounded view is the health flight-recorder
    ring).  If rotation is ever added this test must change with it."""
    path = str(tmp_path / "big.jsonl")
    j = tele.RunJournal(path)
    for i in range(500):
        j.record("e", step=i)
    j.close()
    rows = tele.RunJournal.read(path)
    assert len(rows) == 500                    # nothing dropped
    assert rows[0]["seq"] == 1 and rows[-1]["seq"] == 500
    assert not os.path.exists(path + ".1")     # no rotation artifacts


def test_journal_survives_write_error_midstream(tmp_path):
    path = str(tmp_path / "j.jsonl")
    j = tele.RunJournal(path)
    j.record("ok", step=1)
    j._f.close()                      # simulate the fd dying (full disk)
    j.record("after_dead_fd", step=2)  # swallowed, no raise
    j.close()
    assert [r["event"] for r in tele.RunJournal.read(path)] == ["ok"]


def test_atexit_shutdown_joins_threads():
    tele.enable(memmon_interval=0.05, port=0)
    mm = tele.memory_monitor()
    srv = tele.metrics_server()
    assert mm is not None and mm._thread.is_alive()
    assert srv is not None and srv._thread.is_alive()
    tele._atexit_shutdown()
    assert mm._thread is None or not mm._thread.is_alive()
    assert srv._thread is None
    assert not tele.enabled()


def test_enable_registers_atexit_once(monkeypatch):
    calls = []
    import atexit as _atexit
    monkeypatch.setattr(tele, "_atexit_registered", False)
    monkeypatch.setattr(_atexit, "register", lambda fn: calls.append(fn))
    tele.enable()
    tele.disable()
    tele.enable()
    assert calls == [tele._atexit_shutdown]
