"""Ahead-of-time export & rewrite-pipeline tests (docs/export.md).

Round-trips: capture→save→load in a FRESH subprocess is bit-identical
to the live trace with zero Python-level retraces, for both the
capture mesh and a retargeted mesh (the property-test companion to
`test_elastic_mesh.py`'s reshard suite).  Failure matrix: stale
versions, wrong topologies, corrupt modules, and drifted avals/flags
all fail fast with clear `MXNetError`s.  Plus the remat-policy knob
(`npx.resolve_remat_policy`, `MXTPU_REMAT_POLICY`) and the offline
remat search itself.
"""
import json
import os
import subprocess
import sys
import zlib

import numpy as onp
import pytest

import jax
import jax.numpy as jnp

import mxnet_tpu as mx
from mxnet_tpu import numpy_extension as npx
from mxnet_tpu.base import MXNetError
from mxnet_tpu import optimizer as opt
from mxnet_tpu.export import (ExportArtifact, FORMAT_VERSION, PassManager,
                              RematSearchPass, ShardingRetargetPass,
                              PallasSubstitutionPass, capture,
                              capture_train_step, load, load_block,
                              topology_key)
from mxnet_tpu.gluon import nn
from mxnet_tpu.parallel import make_mesh, make_sharded_train_step

pytestmark = pytest.mark.export

DEVICES = jax.devices()
REPO = os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__))))

needs8 = pytest.mark.skipif(len(DEVICES) < 8,
                            reason="needs 8 (virtual) devices")


def _dense_block(units=16, in_units=8):
    """Deterministic tiny block (crc32-seeded params, the
    test_elastic_mesh idiom) so two processes build identical weights."""
    net = nn.Dense(units, in_units=in_units)
    net.initialize()
    for n, p in net.collect_params().items():
        v = onp.random.RandomState(
            zlib.crc32(n.encode()) % 2 ** 31).standard_normal(
                p.shape).astype("float32")
        p.set_data(mx.np.array(v))
    return net


def _dense_step(mesh, units=16, in_units=8, donate=True):
    net = _dense_block(units, in_units)
    return make_sharded_train_step(
        net, opt.Adam(learning_rate=1e-2),
        lambda out, x, y: jnp.mean((out - y) ** 2), mesh,
        num_model_args=1, donate=donate)


def _batch(units=16, in_units=8, batch=8):
    rng = onp.random.RandomState(7)
    return (mx.np.array(rng.uniform(-1, 1, (batch, in_units))
                        .astype("float32")),
            mx.np.array(rng.uniform(-1, 1, (batch, units))
                        .astype("float32")))


def _gpt_model(layers=2, hidden=16, vocab=64):
    from mxnet_tpu.models.gpt import GPTConfig, GPTForCausalLM
    from mxnet_tpu import random as mxrng
    mxrng.seed(0)
    cfg = GPTConfig(vocab_size=vocab, hidden_size=hidden,
                    num_layers=layers, num_heads=2,
                    intermediate_size=2 * hidden, max_position=32,
                    dropout=0.0)
    model = GPTForCausalLM(cfg)
    model.initialize()
    model(mx.np.array([[1, 2]], dtype="int32"))
    return model


# ---------------------------------------------------------------------------
# artifact format + failure matrix
# ---------------------------------------------------------------------------

@needs8
def test_artifact_round_trip_and_hashes(tmp_path):
    mesh = make_mesh({"dp": 4, "tp": 2}, DEVICES)
    step = _dense_step(mesh)
    x, y = _batch()
    path = str(tmp_path / "art")
    step.export(path, x, y)
    art = ExportArtifact.read(path)
    assert art.kind == "train_step"
    assert art.manifest["format_version"] == FORMAT_VERSION
    mkey = topology_key(step.topology())
    assert mkey in art.manifest["modules"]
    rec = art.manifest["modules"][mkey]
    assert rec["batch_specs"] is not None
    assert art.manifest["hash"] == art.artifact_hash()


@needs8
def test_stale_version_fails_fast(tmp_path):
    mesh = make_mesh({"dp": 4, "tp": 2}, DEVICES)
    step = _dense_step(mesh)
    x, y = _batch()
    path = str(tmp_path / "art")
    step.export(path, x, y)
    man = json.load(open(os.path.join(path, "manifest.json")))
    man["format_version"] = FORMAT_VERSION + 7
    json.dump(man, open(os.path.join(path, "manifest.json"), "w"))
    with pytest.raises(MXNetError, match="format_version"):
        ExportArtifact.read(path)


@needs8
def test_corrupt_module_fails_fast(tmp_path):
    mesh = make_mesh({"dp": 4, "tp": 2}, DEVICES)
    step = _dense_step(mesh)
    x, y = _batch()
    path = str(tmp_path / "art")
    step.export(path, x, y)
    mod = [f for f in os.listdir(path) if f.endswith(".stablehlo")][0]
    with open(os.path.join(path, mod), "r+b") as f:
        f.seek(0)
        f.write(b"\xde\xad\xbe\xef")
    with pytest.raises(MXNetError, match="corrupt"):
        ExportArtifact.read(path)


@needs8
def test_wrong_topology_fails_fast(tmp_path):
    mesh = make_mesh({"dp": 4, "tp": 2}, DEVICES)
    step = _dense_step(mesh)
    x, y = _batch()
    path = str(tmp_path / "art")
    step.export(path, x, y)
    la = load(path)
    with pytest.raises(MXNetError, match="topology"):
        la.artifact.module_bytes({"devices": 3, "axes": {"dp": 3}})
    # a step on a different mesh refuses the artifact
    mesh_b = make_mesh({"dp": 2, "tp": 2}, DEVICES[:4])
    step_b = _dense_step(mesh_b)
    with pytest.raises(MXNetError, match="topology"):
        step_b.load_export(path, x, y)


@needs8
def test_aval_and_flag_mismatch_fail_fast(tmp_path):
    mesh = make_mesh({"dp": 4, "tp": 2}, DEVICES)
    step = _dense_step(mesh)
    x, y = _batch()
    path = str(tmp_path / "art")
    step.export(path, x, y)
    # drifted batch aval
    xb, yb = _batch(batch=16)
    fresh = _dense_step(mesh)
    with pytest.raises(MXNetError, match="aval|leaf"):
        fresh.load_export(path, xb, yb)
    # program-shaping flag drift (donate)
    nd = _dense_step(mesh, donate=False)
    with pytest.raises(MXNetError, match="donate"):
        nd.load_export(path, x, y)
    # missing artifact
    with pytest.raises(MXNetError, match="manifest"):
        fresh.load_export(str(tmp_path / "nope"), x, y)


# ---------------------------------------------------------------------------
# zero-retrace round trips (fresh subprocess, same + retargeted mesh)
# ---------------------------------------------------------------------------

_CHILD = r"""
import json, os, sys, zlib
import numpy as onp
import jax, jax.numpy as jnp
import mxnet_tpu as mx
from mxnet_tpu import optimizer as opt
from mxnet_tpu.gluon import nn
from mxnet_tpu.parallel import make_mesh, make_sharded_train_step

art = sys.argv[1]

def build(mesh):
    net = nn.Dense(16, in_units=8)
    net.initialize()
    for n, p in net.collect_params().items():
        v = onp.random.RandomState(
            zlib.crc32(n.encode()) % 2 ** 31).standard_normal(
                p.shape).astype("float32")
        p.set_data(mx.np.array(v))
    return make_sharded_train_step(
        net, opt.Adam(learning_rate=1e-2),
        lambda out, x, y: jnp.mean((out - y) ** 2), mesh,
        num_model_args=1)

rng = onp.random.RandomState(7)
x = mx.np.array(rng.uniform(-1, 1, (8, 8)).astype("float32"))
y = mx.np.array(rng.uniform(-1, 1, (8, 16)).astype("float32"))

out = {}
for tag, axes, ndev in (("same", {"dp": 4, "tp": 2}, 8),
                        ("retarget", {"dp": 2, "tp": 2}, 4)):
    mesh = make_mesh(axes, jax.devices()[:ndev])
    step = build(mesh)
    step.load_export(art, x, y)
    losses = [float(jax.device_get(step.dispatch(x, y).loss))
              for _ in range(3)]
    assert step.trace_count == 0, (tag, step.trace_count)
    out[tag] = losses
print("CHILD_JSON:" + json.dumps(out))
"""


@pytest.mark.slow
@needs8
def test_fresh_subprocess_bit_identity_same_and_retargeted(tmp_path):
    """Acceptance: artifact captured in one process, loaded in a fresh
    subprocess, yields bit-identical losses with trace_count==0 — on
    the capture mesh AND on a retargeted mesh (each vs its own live
    trace here).

    `slow`-marked (tier-1 wall-clock budget): the fast-tier equivalent
    is `make export-smoke`, which does the fresh-process same-mesh
    round trip on every `make test`; this adds the retargeted-mesh
    subprocess variant."""
    mesh_a = make_mesh({"dp": 4, "tp": 2}, DEVICES)
    step = _dense_step(mesh_a)
    x, y = _batch()
    path = str(tmp_path / "art")
    step.export(path, x, y,
                passes=[ShardingRetargetPass({"dp": 2, "tp": 2})])

    # live references (fresh identically-seeded steps, same process)
    live = {}
    for tag, axes, ndev in (("same", {"dp": 4, "tp": 2}, 8),
                            ("retarget", {"dp": 2, "tp": 2}, 4)):
        ref = _dense_step(make_mesh(axes, DEVICES[:ndev]))
        live[tag] = [float(jax.device_get(ref.dispatch(x, y).loss))
                     for _ in range(3)]

    script = tmp_path / "child.py"
    script.write_text(_CHILD)
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    flags = env.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        env["XLA_FLAGS"] = flags + \
            " --xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    proc = subprocess.run([sys.executable, str(script), path],
                          capture_output=True, text=True, timeout=420,
                          env=env, cwd=REPO)
    assert proc.returncode == 0, proc.stderr[-3000:]
    child = next(json.loads(l[len("CHILD_JSON:"):])
                 for l in proc.stdout.splitlines()
                 if l.startswith("CHILD_JSON:"))
    assert child["same"] == live["same"]
    assert child["retarget"] == live["retarget"]


@needs8
def test_load_export_in_process_parity(tmp_path):
    """Same-process check (cheap): loaded executable == live trace
    bit-for-bit over 3 steps, trace_count stays 0."""
    mesh = make_mesh({"dp": 4, "tp": 2}, DEVICES)
    x, y = _batch()
    path = str(tmp_path / "art")
    _dense_step(mesh).export(path, x, y)

    live = _dense_step(mesh)
    ref = [float(jax.device_get(live.dispatch(x, y).loss))
           for _ in range(3)]
    loaded = _dense_step(mesh)
    loaded.load_export(path, x, y)
    got = [float(jax.device_get(loaded.dispatch(x, y).loss))
           for _ in range(3)]
    assert got == ref
    assert loaded.trace_count == 0
    assert live.trace_count == 1


@needs8
def test_live_warmup_after_artifact_load(tmp_path):
    """warmup() without an artifact on an artifact-loaded step must
    rebuild the live jit, not crash on the missing step_fn (review
    finding)."""
    mesh = make_mesh({"dp": 4, "tp": 2}, DEVICES)
    x, y = _batch()
    path = str(tmp_path / "art")
    _dense_step(mesh).export(path, x, y)
    step = _dense_step(mesh)
    step.load_export(path, x, y)
    assert step.trace_count == 0
    step._warmup_live((x, y))          # re-warm live explicitly
    assert step.trace_count == 1
    loss = float(jax.device_get(step.dispatch(x, y).loss))
    assert onp.isfinite(loss)


@needs8
def test_warmup_auto_capture_and_load(tmp_path, monkeypatch):
    """MXTPU_EXPORT=1: first warmup captures, an identical fresh step's
    warmup loads with zero traces."""
    monkeypatch.setenv("MXTPU_EXPORT", "1")
    monkeypatch.setenv("MXTPU_EXPORT_DIR", str(tmp_path / "store"))
    mesh = make_mesh({"dp": 4, "tp": 2}, DEVICES)
    x, y = _batch()
    first = _dense_step(mesh)
    first.warmup(x, y)
    arts = os.listdir(str(tmp_path / "store"))
    assert len(arts) == 1 and arts[0].startswith("train-")
    second = _dense_step(mesh)
    second.warmup(x, y)
    assert second.trace_count == 0
    l1 = float(jax.device_get(first.dispatch(x, y).loss))
    l2 = float(jax.device_get(second.dispatch(x, y).loss))
    assert l1 == l2
    assert second.trace_count == 0


@needs8
def test_failed_auto_load_leaves_step_clean(tmp_path, monkeypatch):
    """A stale auto-artifact (drifted batch avals) must not leak its
    batch specs into the live-trace fallback (review finding)."""
    monkeypatch.setenv("MXTPU_EXPORT", "1")
    monkeypatch.setenv("MXTPU_EXPORT_DIR", str(tmp_path / "store"))
    mesh = make_mesh({"dp": 4, "tp": 2}, DEVICES)
    x, y = _batch()
    first = _dense_step(mesh)
    first.warmup(x, y)                       # captures batch=8 artifact
    arts = os.listdir(str(tmp_path / "store"))
    # same signature dir, drifted batch: force the auto path to FIND a
    # mismatched artifact by renaming it onto the new signature
    xb, yb = _batch(batch=16)
    stale = _dense_step(mesh)
    sig_dir = stale._auto_artifact_path((xb, yb))
    os.rename(os.path.join(str(tmp_path / "store"), arts[0]), sig_dir)
    secs = stale.warmup(xb, yb)              # falls back to live trace
    assert secs >= 0 and stale.trace_count == 1
    loss = float(jax.device_get(stale.dispatch(xb, yb).loss))
    assert onp.isfinite(loss)


def test_engine_explicit_artifact_fails_fast(tmp_path):
    from mxnet_tpu.serve import InferenceEngine, ServeConfig
    model = _gpt_model()
    eng = InferenceEngine(model, ServeConfig(max_len=32, max_slots=2))
    with pytest.raises(MXNetError, match="manifest"):
        eng.warmup(artifact=str(tmp_path / "nope"))


# ---------------------------------------------------------------------------
# pass pipeline
# ---------------------------------------------------------------------------

@pytest.mark.slow
@needs8
def test_remat_search_tight_budget_picks_non_default(tmp_path):
    model = _gpt_model()
    rng = onp.random.RandomState(0)
    ids = mx.np.array(rng.randint(0, 64, (8, 8)), dtype="int32")
    labels = mx.np.array(rng.randint(0, 64, (8, 8)), dtype="int32")

    def loss_fn(out, input_ids, labels):
        o = out._data if hasattr(out, "_data") else out
        lo = jax.nn.log_softmax(o.astype(jnp.float32), axis=-1)
        tgt = jax.nn.one_hot(labels.astype(jnp.int32), o.shape[-1])
        return -jnp.mean(jnp.sum(lo * tgt, axis=-1))

    mesh = make_mesh({"dp": 4, "tp": 2}, DEVICES)
    step = make_sharded_train_step(model, opt.Adam(learning_rate=1e-3),
                                   loss_fn, mesh, num_model_args=1)
    cap = capture_train_step(step, ids, labels)
    stats = cap.compile_stats()
    from mxnet_tpu.export.passes import _analytic_saved_bytes
    rec = cap.artifact.module_record(step.topology())
    tight = (stats["argument_bytes"] or 0) + int(_analytic_saved_bytes(
        model.cfg, rec["batch_avals"], "dots_saveable")) + 1
    cap = PassManager([RematSearchPass(policies=("none", "dots_saveable"),
                                       hbm_budget=float(tight))]).run(cap)
    assert cap.artifact.manifest["remat_policy"] == "dots_saveable"
    assert model.cfg.remat == "dots_saveable"
    search = [p for p in cap.artifact.manifest["passes"]
              if p["name"] == "remat_search"][0]
    peaks = {c["policy"]: c["peak_bytes"] for c in search["candidates"]}
    assert peaks["none"] > peaks["dots_saveable"]
    assert not search["over_budget"]
    model.cfg.remat = False   # restore


@pytest.mark.slow
@needs8
def test_remat_search_no_budget_keeps_fastest(tmp_path):
    model = _gpt_model()
    rng = onp.random.RandomState(0)
    ids = mx.np.array(rng.randint(0, 64, (4, 8)), dtype="int32")
    labels = mx.np.array(rng.randint(0, 64, (4, 8)), dtype="int32")

    def loss_fn(out, input_ids, labels):
        o = out._data if hasattr(out, "_data") else out
        lo = jax.nn.log_softmax(o.astype(jnp.float32), axis=-1)
        tgt = jax.nn.one_hot(labels.astype(jnp.int32), o.shape[-1])
        return -jnp.mean(jnp.sum(lo * tgt, axis=-1))

    mesh = make_mesh({"dp": 1}, DEVICES[:1])
    step = make_sharded_train_step(model, opt.Adam(learning_rate=1e-3),
                                   loss_fn, mesh, num_model_args=1)
    cap = capture_train_step(step, ids, labels)
    cap = PassManager([RematSearchPass(policies=("none", "full"),
                                       hbm_budget=1e15)]).run(cap)
    assert cap.artifact.manifest["remat_policy"] == "none"
    assert model.cfg.remat is False


@needs8
def test_pallas_substitution_skips_on_cpu(tmp_path):
    mesh = make_mesh({"dp": 4, "tp": 2}, DEVICES)
    step = _dense_step(mesh)
    x, y = _batch()
    cap = capture_train_step(step, x, y)
    cap = PassManager([PallasSubstitutionPass()]).run(cap)
    rec = [p for p in cap.artifact.manifest["passes"]
           if p["name"] == "pallas_substitution"][0]
    assert rec.get("skipped") is True


def test_pass_type_checks():
    model = _gpt_model()
    bc = capture(model, mx.np.array([[1, 2, 3]], dtype="int32"))
    for p in (RematSearchPass(), ShardingRetargetPass({"dp": 1}),
              PallasSubstitutionPass()):
        with pytest.raises(MXNetError, match="train_step"):
            p(bc)


# ---------------------------------------------------------------------------
# block capture / load_block (SymbolBlock parity)
# ---------------------------------------------------------------------------

def test_load_block_runs_from_artifact_alone(tmp_path):
    model = _gpt_model()
    ids = mx.np.array([[3, 1, 4, 1, 5]], dtype="int32")
    path = str(tmp_path / "blk")
    capture(model, ids).save(path)
    lb = load_block(path)
    got = lb(ids)
    want = model(ids)
    # structure exactly; values to f32 round-off — the artifact's
    # StableHLO and the live block are compiled as different programs,
    # so XLA may fuse (and round) them differently in the last ulp
    assert got.shape == want.shape and got.dtype == want.dtype
    onp.testing.assert_allclose(onp.asarray(got._data),
                                onp.asarray(want._data),
                                rtol=1e-5, atol=1e-6)
    # params ride in the artifact
    assert os.path.isfile(os.path.join(path, "params.npz"))
    # kind guard
    with pytest.raises(MXNetError, match="kind"):
        from mxnet_tpu.export import load_block as _lb
        p2 = str(tmp_path / "tr")
        mesh = make_mesh({"dp": 1}, DEVICES[:1])
        _dense_step(mesh).export(p2, *_batch())
        _lb(p2)


# ---------------------------------------------------------------------------
# remat policy knob (satellite)
# ---------------------------------------------------------------------------

def test_resolve_remat_policy_values(monkeypatch):
    monkeypatch.delenv("MXTPU_REMAT_POLICY", raising=False)
    assert npx.resolve_remat_policy(False) == (False, None)
    assert npx.resolve_remat_policy(None) == (False, None)
    assert npx.resolve_remat_policy("none") == (False, None)
    assert npx.resolve_remat_policy(True) == (True, None)
    assert npx.resolve_remat_policy("full") == (True, None)
    on, pol = npx.resolve_remat_policy("dots_saveable")
    assert on and pol is jax.checkpoint_policies.dots_saveable
    with pytest.raises(MXNetError, match="unknown remat policy"):
        npx.resolve_remat_policy("definitely_not_a_policy")


def test_resolve_remat_policy_env_override(monkeypatch):
    monkeypatch.setenv("MXTPU_REMAT_POLICY", "dots_saveable")
    on, pol = npx.resolve_remat_policy(False)
    assert on and pol is jax.checkpoint_policies.dots_saveable
    # explicit remat_call(policy=...) strings ignore the env
    monkeypatch.setenv("MXTPU_REMAT_POLICY", "none")
    on, pol = npx.resolve_remat_policy("dots_saveable",
                                       env_override=False)
    assert on and pol is jax.checkpoint_policies.dots_saveable


@pytest.mark.slow
def test_gpt_trains_with_policy_string():
    model = _gpt_model()
    model.cfg.remat = "dots_saveable"
    try:
        rng = onp.random.RandomState(0)
        ids = mx.np.array(rng.randint(0, 64, (2, 8)), dtype="int32")
        labels = mx.np.array(rng.randint(0, 64, (2, 8)), dtype="int32")

        def loss_fn(out, input_ids, labels):
            o = out._data if hasattr(out, "_data") else out
            lo = jax.nn.log_softmax(o.astype(jnp.float32), axis=-1)
            tgt = jax.nn.one_hot(labels.astype(jnp.int32), o.shape[-1])
            return -jnp.mean(jnp.sum(lo * tgt, axis=-1))

        mesh = make_mesh({"dp": 1}, DEVICES[:1])
        step = make_sharded_train_step(
            model, opt.Adam(learning_rate=1e-3), loss_fn, mesh,
            num_model_args=1)
        loss = float(jax.device_get(step.dispatch(ids, labels).loss))
        assert onp.isfinite(loss)
    finally:
        model.cfg.remat = False
