"""Async execution pipeline: non-blocking dispatch, AOT warmup, retrace
guard, hp-scalar caching, and the tier-1-safe CPU overlap smoke benchmark
(`perf` marker).  Runs on the virtual 8-device CPU mesh."""
import logging
import time

import numpy as onp
import pytest

import jax
import jax.numpy as jnp

import mxnet_tpu as mx
from mxnet_tpu import optimizer as opt
from mxnet_tpu.gluon import nn
from mxnet_tpu.parallel import (AsyncMetricBuffer, DevicePrefetcher,
                                make_mesh, make_sharded_train_step)

pytestmark = pytest.mark.skipif(
    len(jax.devices("cpu")) < 2, reason="needs >=2 virtual devices")


def _loss_fn(out, x, y):
    return jnp.mean((out - y) ** 2)


def _make_step(in_units=8, units=4, lr=1e-2, optimizer=None, seed=42, **kw):
    mx.random.seed(seed)  # identical init across steps built in one test
    net = nn.Dense(units, in_units=in_units)
    net.initialize()
    mesh = make_mesh({"dp": 2}, jax.devices("cpu")[:2])
    return make_sharded_train_step(
        net, optimizer or opt.SGD(learning_rate=lr), _loss_fn,
        mesh, num_model_args=1, **kw)


def _data(n=8, in_units=8, units=4, seed=0):
    rng = onp.random.RandomState(seed)
    return (rng.uniform(-1, 1, (n, in_units)).astype(onp.float32),
            rng.uniform(-1, 1, (n, units)).astype(onp.float32))


# -- retrace guard -----------------------------------------------------


def test_same_shape_dtype_compiles_exactly_once():
    step = _make_step()
    xs, ys = _data()
    losses = [float(step(xs, ys)) for _ in range(10)]
    assert all(onp.isfinite(l) for l in losses)
    assert step.trace_count == 1


def test_dtype_drift_triggers_retrace_warning(caplog):
    step = _make_step(optimizer=opt.SGD(learning_rate=1e-2, momentum=0.9))
    xs, ys = _data()
    step(xs, ys)
    assert step.trace_count == 1
    # corrupt the optimizer state dtype — the documented silent-retrace
    # failure mode (train.py dtype notes): SGD momentum leaf to bf16
    name = step.diff_names[0]
    step.opt_state[name] = jax.tree_util.tree_map(
        lambda s: s.astype(jnp.bfloat16), step.opt_state[name])
    with caplog.at_level(logging.WARNING, logger="mxnet_tpu.parallel.train"):
        step(xs, ys)
    assert step.trace_count == 2
    msgs = [r.getMessage() for r in caplog.records if "RETRACE" in r.getMessage()]
    assert msgs, "retrace must warn"
    assert "bfloat16" in msgs[0]  # names the offending aval


def test_retrace_with_new_input_leaf_warns_not_crashes(caplog):
    """A retrace that ADDS a pytree leaf (clip_gradient None -> 1.0) must
    produce the '(new input)' warning, not a KeyError mid-trace."""
    step = _make_step()
    xs, ys = _data()
    step(xs, ys)
    step.optimizer.clip_gradient = 1.0  # hp gains a leaf
    with caplog.at_level(logging.WARNING, logger="mxnet_tpu.parallel.train"):
        l = float(step(xs, ys))
    assert onp.isfinite(l)
    assert step.trace_count == 2
    msgs = [r.getMessage() for r in caplog.records
            if "RETRACE" in r.getMessage()]
    assert msgs and "(new input)" in msgs[0]


def test_batch_dtype_drift_retraces_once_with_warning(caplog):
    step = _make_step()
    xs, ys = _data()
    step(xs, ys)
    with caplog.at_level(logging.WARNING, logger="mxnet_tpu.parallel.train"):
        step(xs.astype(onp.float64).astype(onp.float32),  # same avals: no retrace
             ys)
    assert step.trace_count == 1
    assert not any("RETRACE" in r.getMessage() for r in caplog.records)


# -- dispatch / handles ------------------------------------------------


def test_dispatch_returns_async_handle_and_matches_call():
    xs, ys = _data()
    s1, s2 = _make_step(), _make_step()
    key = jax.random.PRNGKey(7)
    l_sync = float(s1(xs, ys, rng_key=key))
    h = s2.dispatch(xs, ys, rng_key=key)
    assert h.step == 1 and h.dispatch_s >= 0.0
    assert h.result() == pytest.approx(l_sync, rel=1e-5)
    st = s2.dispatch_stats()
    assert st["dispatches"] == 1 and st["mean_ms"] > 0.0


def test_metric_buffer_keeps_steps_in_flight():
    step = _make_step()
    xs, ys = _data()
    buf = AsyncMetricBuffer(drain_every=4)
    for _ in range(10):
        buf.append(step.dispatch(xs, ys))
    assert buf.max_in_flight >= 2
    vals = buf.drain()
    assert len(vals) == 10 and all(onp.isfinite(v) for v in vals)
    assert step.steps_in_flight() >= 0  # prunes without blocking


def test_place_batch_skips_duplicate_placement():
    step = _make_step()
    xs, ys = _data()
    placed = step.place_batch(xs, ys)
    assert all(isinstance(b, jax.Array) for b in placed)
    assert [b.sharding for b in placed] == list(step._batch_shardings)
    # pre-placed arrays go through unchanged (no second copy)
    prepared = step._prepare_batch(placed)
    assert prepared[0] is placed[0] and prepared[1] is placed[1]
    l = float(step(*placed))
    assert onp.isfinite(l)
    assert step.trace_count == 1


def test_prefetcher_feeds_dispatch_end_to_end():
    step = _make_step()
    xs, ys = _data()
    src = ((xs, ys) for _ in range(6))
    buf = AsyncMetricBuffer(drain_every=3)
    with DevicePrefetcher(src, place=step.place_batch, depth=2) as pf:
        for b in pf:
            buf.append(step.dispatch(*b))
    assert len(buf.drain()) == 6
    assert step.trace_count == 1


# -- hyperparameter caching --------------------------------------------


def test_hp_cache_rebuilds_only_on_change():
    step = _make_step(lr=0.5)
    xs, ys = _data()
    step(xs, ys)
    dev1 = step._hp_cache._dev
    step(xs, ys)
    assert step._hp_cache._dev is dev1  # no per-step rebuild
    assert float(dev1["lr"]) == pytest.approx(0.5)
    step.optimizer.set_learning_rate(0.25)
    step(xs, ys)
    assert step._hp_cache._dev is not dev1
    assert float(step._hp_cache._dev["lr"]) == pytest.approx(0.25)
    assert step.trace_count == 1  # value change, not aval change


def test_hp_t_advances_on_device_and_survives_load(tmp_path):
    step = _make_step()
    xs, ys = _data()
    for _ in range(3):
        step(xs, ys)
    assert float(step._t_dev) == pytest.approx(3.0)
    ckpt = str(tmp_path / "s.npz")
    step.save(ckpt)
    step2 = _make_step()
    step2.load(ckpt)
    assert step2._t == 3
    step2(xs, ys)  # mirror mismatch forces host rebuild at t=4
    assert float(step2._t_dev) == pytest.approx(4.0)


def test_hp_t_host_refresh_at_window_boundary():
    """The device-side t chain re-seeds from the host counter every
    _T_HOST_REFRESH steps (f32 +1.0 saturates at 2**24), and tracks the
    true count across the boundary."""
    step = _make_step()
    xs, ys = _data()
    step(xs, ys)
    # jump the host counter to just before a refresh boundary
    step._t = step._T_HOST_REFRESH - 1
    step._t_mirror = step._t
    step._t_dev = jnp.asarray(123.0, jnp.float32)  # stale device chain
    step._t += 1  # simulate the next step's increment
    hp = step._hp()
    # boundary hit: value comes from the HOST counter, not stale_dev + 1
    assert float(hp["t"]) == float(step._T_HOST_REFRESH)
    step._t += 1
    hp = step._hp()  # off-boundary: device add resumes from the reseed
    assert float(hp["t"]) == float(step._T_HOST_REFRESH + 1)


def test_sgd_with_momentum_and_clip_still_converges():
    """Device-resident clip_gradient scalar: numerics unchanged."""
    net = nn.Dense(1, in_units=4, use_bias=False)
    net.initialize()
    mesh = make_mesh({"dp": 2}, jax.devices("cpu")[:2])
    step = make_sharded_train_step(
        net, opt.SGD(learning_rate=0.1, momentum=0.9, clip_gradient=1.0),
        _loss_fn, mesh, num_model_args=1)
    rng = onp.random.RandomState(1)
    xs = rng.uniform(-1, 1, (8, 4)).astype(onp.float32)
    w = rng.uniform(-1, 1, (4, 1)).astype(onp.float32)
    ys = xs @ w
    losses = [float(step(xs, ys)) for _ in range(30)]
    assert losses[-1] < losses[0] * 0.5
    assert step.trace_count == 1


# -- AOT warmup / compile cache ----------------------------------------


def test_warmup_compiles_without_stepping():
    step = _make_step()
    xs, ys = _data()
    secs = step.warmup(xs, ys)
    assert secs > 0.0 and step.compile_seconds == secs
    assert step._exec is not None
    assert step.trace_count == 1
    assert step._t == 0  # no step executed
    for _ in range(10):
        step(xs, ys)
    assert step.trace_count == 1  # AOT executable served every step
    assert step._t == 10


def test_warmup_fallback_on_aval_drift(caplog):
    step = _make_step()
    xs, ys = _data()
    step.warmup(xs, ys)
    with caplog.at_level(logging.WARNING, logger="mxnet_tpu.parallel.train"):
        l = float(step(xs, ys))  # matching avals: served by the AOT exec
        # genuinely drift the batch aval (half the batch rows):
        l2 = float(step(xs[:4], ys[:4]))
    assert onp.isfinite(l) and onp.isfinite(l2)
    assert step._exec is None  # dropped to the jit path
    assert step.trace_count == 2
    assert any("AOT-compiled step rejected" in r.getMessage()
               for r in caplog.records)


def test_compile_cache_resolution(tmp_path, monkeypatch):
    """The one placement rule (runtime.py): JAX_COMPILATION_CACHE_DIR set
    -> that directory, and our code never touches jax's cache-dir config
    (JAX reads the variable itself); unset -> <checkout>/.jax_cache, set
    in code.  Nothing is active (and autotune/export derive no
    directory) until an entry point enables it."""
    import os
    import types
    import mxnet_tpu
    from mxnet_tpu import runtime, telemetry
    from mxnet_tpu.export import export_dir
    from mxnet_tpu.ops.pallas import autotune
    updates, made = [], []
    monkeypatch.setattr(runtime, "jax", types.SimpleNamespace(
        config=types.SimpleNamespace(
            update=lambda k, v: updates.append((k, v)))))
    monkeypatch.setattr(runtime.os, "makedirs",
                        lambda p, exist_ok=False: made.append(p))
    monkeypatch.setattr(telemetry, "install_compile_cache_listener",
                        lambda: True)
    monkeypatch.setattr(runtime, "_enabled", False)
    for var in ("JAX_COMPILATION_CACHE_DIR", "MXTPU_AUTOTUNE_CACHE",
                "MXTPU_EXPORT_DIR"):
        monkeypatch.delenv(var, raising=False)
    assert runtime.compile_cache_dir() is None
    assert autotune.cache_dir() is None and export_dir() is None

    placed = str(tmp_path / "placed")
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", placed)
    assert runtime.enable_compile_cache() == placed
    assert runtime.compile_cache_dir() == placed
    assert not made
    assert "jax_compilation_cache_dir" not in dict(updates)
    assert dict(updates)["jax_persistent_cache_min_compile_time_secs"] == 0.0
    assert autotune.cache_dir() == os.path.join(placed, "autotune")
    assert export_dir() == os.path.join(placed, "export")

    del updates[:]
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    checkout = os.path.dirname(os.path.dirname(mxnet_tpu.__file__))
    default = os.path.join(checkout, ".jax_cache")
    assert runtime.enable_compile_cache() == default
    assert runtime.compile_cache_dir() == default
    assert made == [default]
    assert dict(updates)["jax_compilation_cache_dir"] == default


# -- CPU overlap smoke benchmark (acceptance criterion) ----------------


def _overlap_step(seed=42, donate=True):
    """A step heavy enough (two 256-wide dense layers, batch 512) that
    device compute dominates per-call overhead — the margin the overlap
    assertion rides on.  Tiny models make the comparison pure noise.
    The pipelined side runs donate=False: the CPU runtime blocks a
    dispatch whose donated input is still in flight, which would
    serialize back-to-back dispatches (see the donate note in train.py —
    TPU streams don't have this constraint)."""
    mx.random.seed(seed)
    net = nn.HybridSequential()
    net.add(nn.Dense(256, in_units=256, activation="relu"),
            nn.Dense(256, in_units=256))
    net.initialize()
    mesh = make_mesh({"dp": 2}, jax.devices("cpu")[:2])
    return make_sharded_train_step(net, opt.SGD(learning_rate=1e-2),
                                   _loss_fn, mesh, num_model_args=1,
                                   donate=donate)


@pytest.mark.perf
def test_perf_smoke_pipeline_overlap():
    """Tier-1-safe overlap proof: with DevicePrefetcher + dispatch(),
    (a) the step compiles exactly once across a 10-step run, (b) >=2
    steps ride in flight, and (c) the host-side gap between consecutive
    dispatches is measurably below the synchronous path's per-step wall
    time (the sync path drains the pipeline with a float() every step)."""
    rng = onp.random.RandomState(3)
    xs = rng.uniform(-1, 1, (512, 256)).astype(onp.float32)
    ys = rng.uniform(-1, 1, (512, 256)).astype(onp.float32)
    key = jax.random.PRNGKey(0)
    n_steps = 10

    # synchronous path: host blocks on the loss every step
    sync = _overlap_step()
    float(sync(xs, ys, rng_key=key))  # compile
    sync_steps = []
    for _ in range(n_steps):
        t0 = time.perf_counter()
        float(sync(xs, ys, rng_key=key))
        sync_steps.append(time.perf_counter() - t0)
    sync_step_s = sorted(sync_steps)[n_steps // 2]  # median: GC-robust

    # pipelined path: prefetch + non-blocking dispatch + deferred fetch
    pipe = _overlap_step(donate=False)
    pipe.warmup(xs, ys, rng_key=key)
    gaps, max_fly = [], 0
    buf = AsyncMetricBuffer(drain_every=5)
    src = ((xs, ys) for _ in range(n_steps))
    with DevicePrefetcher(src, place=pipe.place_batch, depth=2) as pf:
        last = None
        for b in pf:
            now = time.perf_counter()
            if last is not None:
                gaps.append(now - last)
            last = now
            buf.append(pipe.dispatch(*b, rng_key=key))
            # device truth only: dispatched-but-incomplete steps. The
            # deferred-fetch window would reach drain_every-1 even with
            # fully serialized dispatches — asserting on it is vacuous.
            max_fly = max(max_fly, pipe.steps_in_flight())
    vals = buf.drain()

    assert len(vals) == n_steps and all(onp.isfinite(v) for v in vals)
    assert pipe.trace_count == 1          # compiled exactly once
    assert max_fly >= 2                   # >=2 steps genuinely in flight
    gap = sorted(gaps)[len(gaps) // 2]
    assert gap < sync_step_s, (
        f"dispatch gap {gap * 1e3:.2f}ms not below sync step "
        f"{sync_step_s * 1e3:.2f}ms — no overlap")
    st = pipe.dispatch_stats()
    assert st["dispatches"] == n_steps
