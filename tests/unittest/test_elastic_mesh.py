"""Elastic mesh reformation tests (docs/resilience.md "Elastic
scale-out"): reshard round-trips across topologies (dp×tp incl.
ZeRO/FSDP), the ZeRO 1-D bucket vs the replicated path, bounded
coordination timeouts surfacing as `SuspectedHostLoss`, and the
heartbeat/membership controller driving shrink/grow reforms."""
import json
import os
import time
import zlib

import numpy as onp
import pytest

import jax
import jax.numpy as jnp

import mxnet_tpu as mx
from mxnet_tpu.base import MXNetError, SuspectedHostLoss
from mxnet_tpu import optimizer as opt
from mxnet_tpu import recovery
from mxnet_tpu.gluon import nn
from mxnet_tpu.parallel import (ElasticMeshController, PartitionSpec as P,
                                fit_axes, make_mesh,
                                make_sharded_train_step, member_sync,
                                retarget_spec)
from mxnet_tpu.parallel.train import _spec_axes
from mxnet_tpu.utils.checkpoint import CheckpointManager

pytestmark = pytest.mark.fault

DEVICES = jax.devices()


def _build_step(mesh, zero=False, fsdp=False, units=16, in_units=8,
                annotate=True):
    """Deterministic tiny step: param init is seeded by name (crc32, not
    the salted builtin hash), so two builds are bit-identical."""
    net = nn.Dense(units, in_units=in_units)
    net.initialize()
    for n, p in net.collect_params().items():
        v = onp.random.RandomState(
            zlib.crc32(n.encode()) % 2 ** 31).standard_normal(
                p.shape).astype("float32")
        p.set_data(mx.np.array(v))
        if annotate:
            if n.endswith("bias"):
                p.sharding = ("tp",)
            elif n.endswith("weight"):
                p.sharding = ("tp", None)
    step = make_sharded_train_step(
        net, opt.Adam(learning_rate=1e-2),
        lambda out, x, y: jnp.mean((out - y) ** 2), mesh,
        num_model_args=1, zero=zero, fsdp=fsdp)
    return step


def _batches(n, units=16, in_units=8, batch=8):
    rng = onp.random.RandomState(7)
    xs = rng.uniform(-1, 1, (batch, in_units)).astype("float32")
    ys = rng.uniform(-1, 1, (batch, units)).astype("float32")
    return [(xs * (1 + 0.01 * i), ys) for i in range(n)]


def _run(step, batches, start=0):
    out = []
    for i, (x, y) in enumerate(batches):
        key = jax.random.PRNGKey(1000 + start + i)
        out.append(float(step(x, y, rng_key=key)))
    return out


# ---------------------------------------------------------------------------
# axis planning / spec retargeting
# ---------------------------------------------------------------------------

def test_fit_axes():
    assert fit_axes(8, tp=2) == {"tp": 2, "sp": 1, "pp": 1, "ep": 1,
                                 "dp": 4}
    assert fit_axes(4, tp=2) == {"tp": 2, "sp": 1, "pp": 1, "ep": 1,
                                 "dp": 2}
    # a count the model axes don't divide degrades instead of refusing
    assert fit_axes(3, tp=2) == {"tp": 1, "sp": 1, "pp": 1, "ep": 1,
                                 "dp": 3}
    assert fit_axes(8, tp=2, ep=2) == {"tp": 2, "sp": 1, "pp": 1,
                                       "ep": 2, "dp": 2}
    with pytest.raises(MXNetError):
        fit_axes(0, tp=2)


def test_retarget_spec():
    mesh = make_mesh({"dp": 2}, DEVICES[:2])
    assert retarget_spec(P("dp", "sp"), mesh) == P("dp", None)
    assert retarget_spec(P(("dp", "tp")), mesh) == P("dp")
    assert retarget_spec(P("tp", None), mesh) == P(None, None)


# ---------------------------------------------------------------------------
# ZeRO 1-D bucket: sharded specs + bit-identity with the replicated path
# ---------------------------------------------------------------------------

def test_zero_bucket_shards_1d_leaves():
    """The MULTICHIP gap: tp-sharded bias state has no free dim for dp —
    it must land in a flattened P('dp') bucket, not stay replicated."""
    mesh = make_mesh({"dp": 2, "tp": 2}, DEVICES[:4])
    step = _build_step(mesh, zero=True)
    assert step._state_buckets["bias"], "1-D bias state not bucketed"
    for n in step.diff_names:
        for leaf in jax.tree_util.tree_leaves(step.opt_state[n]):
            if leaf.ndim == 0 or leaf.size < 2:
                continue
            assert "dp" in _spec_axes(leaf.sharding.spec), \
                (n, tuple(leaf.shape), leaf.sharding.spec)


def test_zero_bucket_roundtrip_matches_replicated_path():
    """zero=True (bucketed state) must train BIT-identically to
    zero=False (replicated state) — the bucket is storage layout, not
    math — and its checkpoints must store logical (unpadded) values that
    a replicated step can load."""
    mesh = make_mesh({"dp": 2, "tp": 2}, DEVICES[:4])
    batches = _batches(4)
    s_zero = _build_step(mesh, zero=True)
    s_repl = _build_step(mesh, zero=False)
    assert _run(s_zero, batches) == _run(s_repl, batches)
    for n in s_zero.param_names:
        onp.testing.assert_array_equal(
            onp.asarray(jax.device_get(s_zero.pvals[n])),
            onp.asarray(jax.device_get(s_repl.pvals[n])))
    for n in s_zero.diff_names:
        for a, b in zip(s_zero._logical_state_leaves(n),
                        s_repl._logical_state_leaves(n)):
            assert tuple(a.shape) == tuple(b.shape)
            onp.testing.assert_array_equal(
                onp.asarray(jax.device_get(a)),
                onp.asarray(jax.device_get(b)))


def test_zero_bucket_checkpoint_loads_into_replicated_step(tmp_path):
    mesh = make_mesh({"dp": 2, "tp": 2}, DEVICES[:4])
    batches = _batches(3)
    s_zero = _build_step(mesh, zero=True)
    _run(s_zero, batches)
    path = str(tmp_path / "zero.npz")
    s_zero.save(path)
    s_repl = _build_step(mesh, zero=False)
    s_repl.load(path)
    cont = _batches(2)
    assert _run(s_zero, cont, start=3) == _run(s_repl, cont, start=3)


# ---------------------------------------------------------------------------
# reshard round-trips: save under mesh A, restore under mesh B — and the
# LIVE reshard must match the checkpoint path bit-for-bit
# ---------------------------------------------------------------------------

_COMBOS = [
    # (axes_A, n_A, axes_B, n_B, zero, fsdp)
    ({"dp": 4, "tp": 2}, 8, {"dp": 2, "tp": 2}, 4, True, False),
    ({"dp": 8}, 8, {"dp": 4}, 4, False, False),
    ({"dp": 2, "tp": 2}, 4, {"dp": 4, "tp": 2}, 8, True, False),   # grow
    ({"dp": 4}, 4, {"dp": 2}, 2, True, True),                      # FSDP
]


@pytest.mark.parametrize("axes_a,na,axes_b,nb,zero,fsdp", _COMBOS)
def test_reshard_roundtrip_bit_identical(tmp_path, axes_a, na, axes_b,
                                         nb, zero, fsdp):
    """Train k steps under mesh A and checkpoint; then (1) restore into
    a FRESH step on mesh B, (2) live-reshard the original step A -> B.
    Both must produce the SAME bit-identical loss trajectory on mesh B —
    proving the gather→re-place path, the topology-agnostic checkpoint
    format, and the ShardingRules re-run agree exactly."""
    units, in_units = (128, 64) if fsdp else (16, 8)
    mesh_a = make_mesh(dict(axes_a), DEVICES[:na])
    mesh_b = make_mesh(dict(axes_b), DEVICES[:nb])
    warm = _batches(3, units=units, in_units=in_units)
    cont = _batches(4, units=units, in_units=in_units)

    step = _build_step(mesh_a, zero=zero, fsdp=fsdp, units=units,
                       in_units=in_units, annotate=not fsdp)
    _run(step, warm)
    mgr = CheckpointManager(str(tmp_path), keep=3)
    mgr.save(step, 3)

    fresh = _build_step(mesh_b, zero=zero, fsdp=fsdp, units=units,
                        in_units=in_units, annotate=not fsdp)
    assert mgr.restore(fresh) == 3
    ref = _run(fresh, cont, start=3)

    step.reshard(mesh_b)
    assert step.trace_count == 0        # compiled state fully reset
    live = _run(step, cont, start=3)
    assert step.trace_count == 1        # exactly one trace on topology B
    assert live == ref
    for n in step.param_names:
        onp.testing.assert_array_equal(
            onp.asarray(jax.device_get(step.pvals[n])),
            onp.asarray(jax.device_get(fresh.pvals[n])))


def test_reshard_rederives_auto_batch_specs_and_hp_cache():
    mesh_a = make_mesh({"dp": 2, "sp": 2}, DEVICES[:4])
    mesh_b = make_mesh({"dp": 2}, DEVICES[:2])
    net = nn.Dense(8, in_units=8)
    net.initialize()
    step = make_sharded_train_step(
        net, opt.SGD(learning_rate=1e-2),
        lambda out, x, y: jnp.mean((out - y) ** 2), mesh_a,
        num_model_args=1)
    x = onp.ones((4, 8), "float32")
    y = onp.ones((4, 8), "float32")
    float(step(x, y))
    assert "sp" in {a for s in step.batch_specs for a in _spec_axes(s)}
    step.reshard(mesh_b)
    assert step.batch_specs is None     # re-derived on first dispatch
    float(step(x, y))
    assert "sp" not in {a for s in step.batch_specs
                        for a in _spec_axes(s)}
    assert step.trace_count == 1


def test_reshard_gather_false_requires_restore(tmp_path):
    """The host-loss path: placements re-plan without a gather; a
    checkpoint restore then fully re-populates the step."""
    mesh_a = make_mesh({"dp": 4}, DEVICES[:4])
    mesh_b = make_mesh({"dp": 2}, DEVICES[:2])
    step = _build_step(mesh_a, zero=True, annotate=False)
    batches = _batches(3)
    _run(step, batches)
    mgr = CheckpointManager(str(tmp_path))
    mgr.save(step, 3)
    ref = _build_step(mesh_b, zero=True, annotate=False)
    mgr.restore(ref)
    cont = _batches(2)
    expect = _run(ref, cont, start=3)

    step.reshard(mesh_b, gather=False)
    assert mgr.restore(step, step=3) == 3
    assert _run(step, cont, start=3) == expect


def test_manifest_records_topology_and_cross_topology_event(tmp_path):
    from mxnet_tpu import telemetry as tele
    mesh_a = make_mesh({"dp": 4}, DEVICES[:4])
    mesh_b = make_mesh({"dp": 2}, DEVICES[:2])
    step = _build_step(mesh_a, annotate=False)
    mgr = CheckpointManager(str(tmp_path))
    path = mgr.save(step, 1)
    with open(path + ".manifest.json") as f:
        meta = json.load(f)
    assert meta["topology"]["axes"] == {"dp": 4}
    journal = str(tmp_path / "j.jsonl")
    tele.enable(journal_path=journal)
    try:
        other = _build_step(mesh_b, annotate=False)
        mgr.restore(other)
        rows = [json.loads(ln) for ln in open(journal) if ln.strip()]
        cross = [r for r in rows
                 if r.get("event") == "checkpoint_cross_topology"]
        assert cross and cross[0]["saved_axes"] == {"dp": 4} \
            and cross[0]["restored_axes"] == {"dp": 2}
    finally:
        tele.disable()


# ---------------------------------------------------------------------------
# bounded coordination rounds -> SuspectedHostLoss
# ---------------------------------------------------------------------------

def _hang_collective(monkeypatch):
    from jax.experimental import multihost_utils
    monkeypatch.setattr(jax, "process_count", lambda: 2)
    monkeypatch.setattr(multihost_utils, "process_allgather",
                        lambda x: time.sleep(30))


def test_sync_flags_timeout_raises_suspected_host_loss(monkeypatch):
    from mxnet_tpu import elastic
    _hang_collective(monkeypatch)
    t0 = time.monotonic()
    with pytest.raises(SuspectedHostLoss, match="suspected lost"):
        elastic.sync_flags(True, False, timeout=0.2)
    assert time.monotonic() - t0 < 5.0


def test_sync_flags_timeout_env(monkeypatch):
    assert recovery.sync_timeout() == recovery.DEFAULT_SYNC_TIMEOUT
    monkeypatch.setenv("MXTPU_ELASTIC_SYNC_TIMEOUT", "7.5")
    assert recovery.sync_timeout() == 7.5
    monkeypatch.setenv("MXTPU_ELASTIC_SYNC_TIMEOUT", "0")
    assert recovery.sync_timeout() is None    # bound disabled
    monkeypatch.setenv("MXTPU_ELASTIC_SYNC_TIMEOUT", "junk")
    assert recovery.sync_timeout() == recovery.DEFAULT_SYNC_TIMEOUT


def test_sync_flags_retry_semantics_survive_timeout_wrapper(monkeypatch):
    """The retry-then-MXNetError contract from PR 5 is unchanged when
    the collective fails FAST (no timeout involved)."""
    from mxnet_tpu import elastic
    from jax.experimental import multihost_utils

    def always_down(x):
        raise RuntimeError("transient collective error (injected)")

    monkeypatch.setattr(elastic, "_SYNC_BASE_DELAY", 0.001)
    monkeypatch.setattr(jax, "process_count", lambda: 2)
    monkeypatch.setattr(multihost_utils, "process_allgather", always_down)
    with pytest.raises(MXNetError, match="allgather failed"):
        elastic.sync_flags(False, timeout=5.0)


def test_agree_step_timeout_raises_suspected_host_loss(monkeypatch):
    _hang_collective(monkeypatch)
    with pytest.raises(SuspectedHostLoss, match="consensus"):
        recovery.agree_step(11, timeout=0.2)


def test_member_sync_single_process_and_fault_point(monkeypatch):
    view = member_sync(join=True)
    assert view.processes == 1 and view.join and not view.leave
    monkeypatch.setenv("MXTPU_FAULT_SPEC", "member_sync@1")
    from mxnet_tpu.resilience import FaultInjected
    with pytest.raises(FaultInjected):
        member_sync()


# ---------------------------------------------------------------------------
# the controller: heartbeats, membership, reform
# ---------------------------------------------------------------------------

def test_controller_detects_stale_host_and_reforms(tmp_path):
    mesh = make_mesh({"dp": 4}, DEVICES[:4])
    step = _build_step(mesh, zero=True, annotate=False)
    batches = _batches(3)
    _run(step, batches)
    mgr = CheckpointManager(str(tmp_path))
    mgr.save(step, 3)
    ctl = ElasticMeshController(
        step, manager=mgr,
        hosts={"h0": DEVICES[:2], "h1": DEVICES[2:4]},
        heartbeat_timeout_s=0.15)
    assert ctl.poll() is None
    time.sleep(0.3)
    ctl.heartbeat("h0")                 # h1 goes stale
    change = ctl.poll()
    assert change is not None and change.kind == "shrink"
    assert change.reason == "host_loss" and change.hosts == ("h1",)
    assert not change.live
    resume = ctl.reform(change, current_step=5)
    assert resume == 3                  # agreed/restored checkpoint step
    assert step.mesh.size == 2 and ctl.hosts() == {"h0": True,
                                                   "h1": False}
    # ...and the host comes back: grow reform carries live state
    ctl.request_join("h1")
    change = ctl.poll()
    assert change.kind == "grow" and change.live
    assert ctl.reform(change, current_step=3) == 3
    assert step.mesh.size == 4
    assert step.trace_count == 0
    cont = _batches(1)
    _run(step, cont, start=3)
    assert step.trace_count == 1


def test_controller_min_devices_floor():
    mesh = make_mesh({"dp": 2}, DEVICES[:2])
    step = _build_step(mesh, annotate=False)
    ctl = ElasticMeshController(
        step, hosts={"h0": [DEVICES[0]], "h1": [DEVICES[1]]},
        min_devices_n=2)
    with pytest.raises(MXNetError, match="MIN_DEVICES"):
        ctl.request_leave("h1")
    assert ctl.hosts() == {"h0": True, "h1": True}   # refusal is atomic


def test_controller_never_declares_all_hosts_lost():
    mesh = make_mesh({"dp": 2}, DEVICES[:2])
    step = _build_step(mesh, annotate=False)
    ctl = ElasticMeshController(
        step, hosts={"h0": [DEVICES[0]], "h1": [DEVICES[1]]},
        heartbeat_timeout_s=0.05)
    time.sleep(0.15)                    # BOTH heartbeats stale
    # a unanimous-stale round is deferred one window (it looks like a
    # local pause, and immediate picks risk sparing the corpse) ...
    assert ctl.poll() is None
    assert ctl.hosts() == {"h0": True, "h1": True}
    time.sleep(0.1)                     # ... still unanimous: fall back
    change = ctl.poll()
    assert change is not None
    alive = [h for h, a in ctl.hosts().items() if a]
    assert alive                        # a survivor always remains


def test_controller_suspected_loss_without_stale_host_is_inert():
    mesh = make_mesh({"dp": 2}, DEVICES[:2])
    step = _build_step(mesh, annotate=False)
    ctl = ElasticMeshController(
        step, hosts={"h0": [DEVICES[0]], "h1": [DEVICES[1]]},
        heartbeat_timeout_s=60.0)
    ctl.note_suspected_loss(exc=SuspectedHostLoss("flag sync timeout"))
    assert ctl.poll() is None           # no one to blame -> caller re-raises


def test_loop_consumes_suspected_loss(tmp_path):
    """ElasticLoop._on_suspected_loss: a flag-sync timeout becomes a
    shrink reform when a host's heartbeat is already stale."""
    from mxnet_tpu.elastic import ElasticLoop
    mesh = make_mesh({"dp": 2}, DEVICES[:2])
    step = _build_step(mesh, zero=True, annotate=False)
    _run(step, _batches(2))
    ctl = ElasticMeshController(
        step, hosts={"h0": [DEVICES[0]], "h1": [DEVICES[1]]},
        heartbeat_timeout_s=0.1)
    loop = ElasticLoop(step, str(tmp_path), save_every=2,
                       mesh_controller=ctl)
    assert ctl.manager is loop.manager  # loop wires its own manager
    loop.manager.save(step, 2)
    time.sleep(0.2)
    ctl.heartbeat("h0")
    resume = loop._on_suspected_loss(SuspectedHostLoss("timeout"), 4)
    assert resume == 2 and step.mesh.size == 1


def test_loop_end_to_end_shrink_and_grow(tmp_path):
    """Full loop: kill a simulated host mid-run (shrink + agreed-step
    resume), then re-add it (grow), with step continuity and one trace
    per topology — the in-process version of `make elastic-smoke`."""
    from mxnet_tpu.elastic import ElasticLoop
    mesh = make_mesh({"dp": 4}, DEVICES[:4])
    step = _build_step(mesh, zero=True, annotate=False)
    ctl = ElasticMeshController(
        step, hosts={"h0": DEVICES[:2], "h1": DEVICES[2:4]},
        heartbeat_timeout_s=1.0)
    loop = ElasticLoop(step, str(tmp_path), save_every=4, keep=10,
                       mesh_controller=ctl)
    batches = _batches(40)
    ran = []
    state = {"killed": False, "rejoined": False}

    def step_fn(i):
        ran.append(i + 1)
        x, y = batches[i]
        return step.dispatch(x, y, rng_key=jax.random.PRNGKey(i))

    def on_step(i, _loss):
        ctl.heartbeat("h0")
        if not state["killed"] or state["rejoined"]:
            ctl.heartbeat("h1")
        if i == 6 and not state["killed"]:
            state["killed"] = True
            time.sleep(1.2)             # h1's heartbeat goes stale
        if i == 10 and state["killed"] and not state["rejoined"]:
            state["rejoined"] = True    # the host comes back in service
            ctl.request_join("h1")

    out = loop.run(step_fn, total_steps=14, on_step=on_step)
    step.drain()
    assert out["status"] == "completed" and out["step"] == 14
    assert out["reforms"] == 2
    assert step.mesh.size == 4          # grew back
    assert step.trace_count == 1        # one trace on the final topology
    # continuity: replay covers 5..6 (restored at the step-4 save), and
    # every step id 1..14 ran at least once — none skipped
    assert set(ran) == set(range(1, 15))
