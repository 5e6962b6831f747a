"""Fused Pallas kernel-set parity tests (interpret mode on CPU).

The EXACT kernel code in `ops/pallas/{fused_norm,moe_dispatch,
fused_optimizer}.py` runs through the Pallas interpreter against each
module's jnp reference (and, for MoE, the pre-fusion dense-einsum
formulation) over odd/padded shapes — plus the `MXTPU_PALLAS` dispatch
contract, the autotuner's search-then-persist loop, and the fused
train-step acceptance criteria (one trace over 10 steps, NaN-skip
bit-identity).  `pallas` marker (fast, CPU-only, tier-1);
docs/perf.md "Fused kernels & autotuning".
"""
import os

import numpy as onp
import pytest

os.environ["MXTPU_PALLAS_INTERPRET"] = "1"

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

import mxnet_tpu as mx  # noqa: E402
from mxnet_tpu import health, recovery  # noqa: E402
from mxnet_tpu import numpy_extension as npx  # noqa: E402
from mxnet_tpu import telemetry as tele  # noqa: E402
from mxnet_tpu.ops import pallas as pallas_pkg  # noqa: E402
from mxnet_tpu.ops.pallas import autotune  # noqa: E402
from mxnet_tpu.ops.pallas import fused_norm  # noqa: E402
from mxnet_tpu.ops.pallas import fused_optimizer  # noqa: E402
from mxnet_tpu.ops.pallas import moe_dispatch  # noqa: E402
from mxnet_tpu.optimizer import LAMB, SGD, Adam  # noqa: E402

pytestmark = pytest.mark.pallas


@pytest.fixture(autouse=True)
def _clean_state():
    """Health/recovery/telemetry are process-wide; the autotune memory
    cache would leak tuned configs between tests."""
    recovery.disable()
    health.disable()
    tele.disable()
    tele.registry().reset()
    autotune.clear_memory_cache()
    yield
    recovery.disable()
    health.disable()
    tele.disable()
    tele.registry().reset()
    autotune.clear_memory_cache()


def _rand(shape, dtype=jnp.float32, seed=0):
    rng = onp.random.RandomState(seed)
    return jnp.asarray(rng.standard_normal(shape), dtype)


# ---------------------------------------------------------------------------
# fused_norm: kernel vs jnp reference (f32/bf16, ragged/odd last dims)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("rows,h", [(5, 37), (9, 200), (64, 256)])
@pytest.mark.parametrize("dtype,atol", [(jnp.float32, 1e-5),
                                        (jnp.bfloat16, 3e-2)])
def test_norm_kernel_matches_reference(rows, h, dtype, atol):
    x = _rand((rows, h), dtype, seed=1)
    res = _rand((rows, h), dtype, seed=2)
    g = jnp.asarray(onp.random.RandomState(3).rand(h) + 0.5, dtype)
    b = _rand((h,), dtype, seed=4)
    # oracle in f32: the kernel computes statistics in f32, so a
    # low-precision reference would be the LESS accurate side
    xf, rf = x.astype(jnp.float32), res.astype(jnp.float32)
    gf, bf = g.astype(jnp.float32), b.astype(jnp.float32)

    y = fused_norm.fused_layer_norm(x, g, b, use_kernel=True)
    onp.testing.assert_allclose(
        onp.asarray(y, onp.float32),
        onp.asarray(fused_norm.layer_norm_reference(xf, gf, bf)),
        atol=atol)

    y = fused_norm.fused_rms_norm(x, g, use_kernel=True)
    onp.testing.assert_allclose(
        onp.asarray(y, onp.float32),
        onp.asarray(fused_norm.rms_norm_reference(xf, gf)), atol=atol)

    y, s = fused_norm.layer_norm_residual(x, res, g, b, use_kernel=True)
    yr, sr = fused_norm.layer_norm_reference(xf, gf, bf, residual=rf)
    onp.testing.assert_allclose(onp.asarray(y, onp.float32),
                                onp.asarray(yr), atol=atol)
    onp.testing.assert_allclose(onp.asarray(s, onp.float32),
                                onp.asarray(sr), atol=atol)

    y, s = fused_norm.rms_norm_residual(x, res, g, use_kernel=True)
    yr, sr = fused_norm.rms_norm_reference(xf, gf, residual=rf)
    onp.testing.assert_allclose(onp.asarray(y, onp.float32),
                                onp.asarray(yr), atol=atol)


def test_norm_gradients_match_reference():
    """custom_vjp: Pallas forward, jnp backward — both residual outputs
    carry cotangents."""
    x = _rand((6, 40), seed=5)
    res = _rand((6, 40), seed=6)
    g = jnp.asarray(onp.random.RandomState(7).rand(40) + 0.5, jnp.float32)
    b = jnp.zeros((40,), jnp.float32)

    def loss(fn):
        def inner(xv, rv, gv, bv):
            y, s = fn(xv, rv, gv, bv)
            return jnp.sum(y ** 2) + jnp.sum(s * 0.3)
        return inner

    k = loss(lambda *a: fused_norm.layer_norm_residual(
        *a, use_kernel=True))
    r = loss(lambda xv, rv, gv, bv: fused_norm.layer_norm_reference(
        xv, gv, bv, residual=rv))
    gk = jax.grad(k, argnums=(0, 1, 2, 3))(x, res, g, b)
    gr = jax.grad(r, argnums=(0, 1, 2, 3))(x, res, g, b)
    for a, want in zip(gk, gr):
        onp.testing.assert_allclose(onp.asarray(a), onp.asarray(want),
                                    atol=1e-4)


def test_npx_norm_entry_points_agree():
    """The npx ops (what gluon/GPT call) equal the module references on
    the CPU tier-1 path, and RMSNorm is exposed as an nn block."""
    from mxnet_tpu.gluon import nn
    x = _rand((4, 6, 32), seed=8)
    res = _rand((4, 6, 32), seed=9)
    g = jnp.asarray(onp.random.RandomState(1).rand(32) + 0.5, jnp.float32)
    b = _rand((32,), seed=2)

    y, s = npx.layer_norm_residual(x, res, g, b)
    yr, sr = fused_norm.layer_norm_reference(x, g, b, residual=res)
    onp.testing.assert_allclose(onp.asarray(y), onp.asarray(yr),
                                atol=1e-6)
    onp.testing.assert_allclose(onp.asarray(s), onp.asarray(sr),
                                atol=1e-6)
    onp.testing.assert_allclose(
        onp.asarray(npx.rms_norm(x, g)),
        onp.asarray(fused_norm.rms_norm_reference(x, g)), atol=1e-6)

    blk = nn.RMSNorm(in_channels=32)
    blk.initialize()
    out = blk(mx.np.array(onp.asarray(x)))
    onp.testing.assert_allclose(
        onp.asarray(out.asnumpy()),
        onp.asarray(fused_norm.rms_norm_reference(
            x, jnp.ones((32,), jnp.float32))), atol=1e-6)


# ---------------------------------------------------------------------------
# moe_dispatch: kernel vs reference vs the pre-fusion dense einsums
# ---------------------------------------------------------------------------

def _routing(t, e, c, seed=0):
    """Router-shaped assignments: pos is the token's arrival rank within
    its expert (unique per (expert, slot)); rank >= capacity drops."""
    rng = onp.random.RandomState(seed)
    expert_np = rng.randint(0, e, t)
    pos_np = onp.zeros(t, onp.int64)
    seen = onp.zeros(e, onp.int64)
    for i, ex in enumerate(expert_np):
        pos_np[i] = seen[ex]
        seen[ex] += 1
    kept = jnp.asarray(pos_np < c)
    pos = jnp.asarray(onp.where(pos_np < c, pos_np, 0), jnp.int32)
    return jnp.asarray(expert_np, jnp.int32), pos, kept


def _dense_dispatch_combine(x, expert, pos, kept, gate, down, e, c):
    """The legacy (T, E, C) one-hot formulation — the overflow-semantics
    oracle the blockwise kernels must match exactly."""
    onehot = jax.nn.one_hot(expert, e, dtype=x.dtype)
    disp = (onehot * kept[:, None].astype(x.dtype))[:, :, None] * \
        jax.nn.one_hot(pos, c, dtype=x.dtype)[:, None, :]
    buf = jnp.einsum("tec,th->ech", disp, x)
    out = jnp.einsum("tec,ech->th",
                     disp * gate[:, None, None].astype(x.dtype), down)
    return buf, out


@pytest.mark.parametrize("t,e,c,h", [(53, 4, 6, 128), (31, 3, 5, 64)])
def test_moe_kernel_matches_dense_einsum_with_overflow(t, e, c, h):
    x = _rand((t, h), seed=10)
    down = _rand((e, c, h), seed=11)
    gate = jnp.asarray(onp.random.RandomState(12).rand(t), jnp.float32)
    expert, pos, kept = _routing(t, e, c, seed=13)
    assert not bool(jnp.all(kept)), "want capacity overflow in this test"

    buf_d, out_d = _dense_dispatch_combine(x, expert, pos, kept, gate,
                                           down, e, c)
    for use_kernel in (True, False):
        buf = moe_dispatch.moe_dispatch(x, expert, pos, kept, e, c,
                                        use_kernel=use_kernel)
        out = moe_dispatch.moe_combine(down, expert, pos, kept, gate,
                                       use_kernel=use_kernel)
        onp.testing.assert_allclose(onp.asarray(buf), onp.asarray(buf_d),
                                    atol=1e-5)
        onp.testing.assert_allclose(onp.asarray(out), onp.asarray(out_d),
                                    atol=1e-5)
        # dropped tokens must be EXACT zero rows (the einsum contract)
        dropped = ~onp.asarray(kept)
        assert not onp.any(onp.asarray(out)[dropped])


def test_moe_kernel_gradients_match_dense():
    t, e, c, h = 24, 3, 4, 128
    x = _rand((t, h), seed=14)
    down_w = _rand((e, c, h), seed=15)
    gate = jnp.asarray(onp.random.RandomState(16).rand(t), jnp.float32)
    expert, pos, kept = _routing(t, e, c, seed=17)

    def f_kernel(xv, gv):
        buf = moe_dispatch.moe_dispatch(xv, expert, pos, kept, e, c,
                                        use_kernel=True)
        out = moe_dispatch.moe_combine(buf * 0.5 + down_w, expert, pos,
                                       kept, gv, use_kernel=True)
        return jnp.sum(out ** 2)

    def f_dense(xv, gv):
        buf, _ = _dense_dispatch_combine(xv, expert, pos, kept, gv,
                                         down_w, e, c)
        _, out = _dense_dispatch_combine(xv, expert, pos, kept, gv,
                                         buf * 0.5 + down_w, e, c)
        return jnp.sum(out ** 2)

    gk = jax.grad(f_kernel, argnums=(0, 1))(x, gate)
    gd = jax.grad(f_dense, argnums=(0, 1))(x, gate)
    for a, want in zip(gk, gd):
        onp.testing.assert_allclose(onp.asarray(a), onp.asarray(want),
                                    atol=1e-4)


def test_switch_moe_blockwise_equals_legacy_dense(monkeypatch):
    """End-to-end: MXTPU_PALLAS=off (dense einsums) and the default
    blockwise path produce the same layer output, overflow included."""
    from mxnet_tpu.parallel import switch_moe
    rng = onp.random.RandomState(18)
    b, l, h, i, e = 2, 16, 32, 48, 4
    x = jnp.asarray(rng.standard_normal((b, l, h)), jnp.float32)
    rw = jnp.asarray(rng.standard_normal((e, h)), jnp.float32)
    wu = jnp.asarray(rng.standard_normal((e, i, h)) * 0.1, jnp.float32)
    wd = jnp.asarray(rng.standard_normal((e, h, i)) * 0.1, jnp.float32)

    # capacity_factor 0.5 forces drops: overflow semantics must agree
    monkeypatch.setenv("MXTPU_PALLAS", "off")
    out_legacy, aux_legacy = switch_moe(x, rw, wu, wd,
                                        capacity_factor=0.5)
    monkeypatch.delenv("MXTPU_PALLAS")
    out_block, aux_block = switch_moe(x, rw, wu, wd, capacity_factor=0.5)
    onp.testing.assert_allclose(onp.asarray(out_block),
                                onp.asarray(out_legacy), atol=1e-5)
    onp.testing.assert_allclose(float(aux_block), float(aux_legacy),
                                rtol=1e-6)


# ---------------------------------------------------------------------------
# fused_optimizer: chunk kernel vs per-leaf reference + skip bit-identity
# ---------------------------------------------------------------------------

def _hp(clip=None):
    return {"lr": jnp.float32(0.01), "wd": jnp.float32(0.01),
            "rescale_grad": jnp.float32(1.0),
            "clip_gradient": None if clip is None else jnp.float32(clip),
            "t": jnp.float32(3.0)}


def _leaf_zoo(opt, dtype=jnp.float32, seed=0):
    """Odd leaf sizes force tile padding inside the packed chunk."""
    rng = onp.random.RandomState(seed)
    params = {n: jnp.asarray(rng.standard_normal(sz), dtype)
              for n, sz in (("w", 1000), ("b", 37), ("s", 8))}
    grads = {n: jnp.asarray(rng.standard_normal(v.size), dtype)
             for n, v in params.items()}
    states = {n: opt.create_state_jax(v.astype(jnp.float32))
              for n, v in params.items()}
    return params, grads, states


@pytest.mark.parametrize("make_opt", [
    lambda: Adam(learning_rate=0.01),
    lambda: SGD(learning_rate=0.01, momentum=0.9),
    lambda: LAMB(learning_rate=0.01)])
@pytest.mark.parametrize("clip", [None, 1.0])
def test_optimizer_kernel_matches_reference(make_opt, clip):
    opt = make_opt()
    params, grads, states = _leaf_zoo(opt)
    hp = _hp(clip)
    kp, ks = fused_optimizer.apply_updates(opt, params, grads, states,
                                           hp, skip=None,
                                           use_kernel=True)
    rp, rs = fused_optimizer.apply_updates(opt, params, grads, states,
                                           hp, skip=None,
                                           use_kernel=False)
    for n in params:
        onp.testing.assert_allclose(onp.asarray(kp[n]),
                                    onp.asarray(rp[n]), atol=2e-6)
    for a, want in zip(jax.tree_util.tree_leaves(ks),
                       jax.tree_util.tree_leaves(rs)):
        onp.testing.assert_allclose(onp.asarray(a), onp.asarray(want),
                                    atol=2e-6)


@pytest.mark.parametrize("make_opt", [
    lambda: Adam(learning_rate=0.01),
    lambda: SGD(learning_rate=0.01, momentum=0.9),
    lambda: LAMB(learning_rate=0.01)])
@pytest.mark.parametrize("use_kernel", [True, False])
def test_optimizer_skip_guard_is_bit_identical(make_opt, use_kernel):
    """The non-finite skip turns the whole update into the identity —
    params AND optimizer state keep their pre-step values bit-exactly,
    on both the in-register kernel guard and the reference select."""
    opt = make_opt()
    params, grads, states = _leaf_zoo(opt, seed=1)
    sp, ss = fused_optimizer.apply_updates(
        opt, params, grads, states, _hp(), skip=jnp.asarray(True),
        use_kernel=use_kernel)
    for n in params:
        onp.testing.assert_array_equal(onp.asarray(sp[n]),
                                       onp.asarray(params[n]))
    for a, want in zip(jax.tree_util.tree_leaves(ss),
                       jax.tree_util.tree_leaves(states)):
        onp.testing.assert_array_equal(onp.asarray(a), onp.asarray(want))
    # skip=False must be a real (changed) update, not identity
    up, _ = fused_optimizer.apply_updates(
        opt, params, grads, states, _hp(), skip=jnp.asarray(False),
        use_kernel=use_kernel)
    assert any(not onp.array_equal(onp.asarray(up[n]),
                                   onp.asarray(params[n]))
               for n in params)


def test_optimizer_mixed_dtype_chunks():
    """bf16 weights with fp32 Adam moments form their own chunk; output
    dtypes stay exactly as declared (the donation contract)."""
    opt = Adam(learning_rate=0.01)
    rng = onp.random.RandomState(2)
    params = {"wlo": jnp.asarray(rng.standard_normal(300), jnp.bfloat16),
              "whi": jnp.asarray(rng.standard_normal(200), jnp.float32),
              "blo": jnp.asarray(rng.standard_normal(9), jnp.bfloat16)}
    grads = {n: jnp.asarray(rng.standard_normal(v.size), v.dtype)
             for n, v in params.items()}
    states = {n: opt.create_state_jax(v.astype(jnp.float32))
              for n, v in params.items()}
    kp, ks = fused_optimizer.apply_updates(opt, params, grads, states,
                                           _hp(), skip=None,
                                           use_kernel=True)
    rp, rs = fused_optimizer.apply_updates(opt, params, grads, states,
                                           _hp(), skip=None,
                                           use_kernel=False)
    for n in params:
        assert kp[n].dtype == params[n].dtype
        onp.testing.assert_allclose(
            onp.asarray(kp[n], onp.float32),
            onp.asarray(rp[n], onp.float32), atol=5e-2)
    for a, want in zip(jax.tree_util.tree_leaves(ks),
                       jax.tree_util.tree_leaves(rs)):
        assert a.dtype == want.dtype


# ---------------------------------------------------------------------------
# MXTPU_PALLAS dispatch contract
# ---------------------------------------------------------------------------

def test_reference_mode_forces_fallback_everywhere(monkeypatch):
    monkeypatch.setenv("MXTPU_PALLAS", "reference")
    assert pallas_pkg.pallas_mode() == "reference"
    assert not pallas_pkg.kernel_active()
    assert not fused_norm.kernel_eligible(jnp.zeros((4, 8)))
    assert not fused_optimizer.kernel_route(Adam())
    # moe wrappers resolve use_kernel=None to the reference path
    x = _rand((6, 128), seed=3)
    expert, pos, kept = _routing(6, 2, 4, seed=4)
    out = moe_dispatch.moe_dispatch(x, expert, pos, kept, 2, 4)
    onp.testing.assert_array_equal(
        onp.asarray(out),
        onp.asarray(moe_dispatch.moe_dispatch_reference(
            x, expert, pos, kept, 2, 4)))


def test_pallas_mode_spellings(monkeypatch):
    for raw, want in (("off", "off"), ("0", "off"), ("REF", "reference"),
                      ("kernel", "kernel"), ("auto", "auto"),
                      ("bogus", "auto")):
        monkeypatch.setenv("MXTPU_PALLAS", raw)
        assert pallas_pkg.pallas_mode() == want
    monkeypatch.delenv("MXTPU_PALLAS")
    # auto on the CPU backend: reference path (interpret mode alone
    # must NOT flip auto to kernels — see ops/pallas/__init__)
    assert pallas_pkg.pallas_mode() == "auto"
    assert not pallas_pkg.kernel_active()


# ---------------------------------------------------------------------------
# autotuner: analytic prune + search-then-persist + warm starts
# ---------------------------------------------------------------------------

def test_autotune_search_persists_and_warm_starts(monkeypatch, tmp_path):
    monkeypatch.setenv("MXTPU_AUTOTUNE_CACHE", str(tmp_path))
    tele.enable()
    shapes, dtype = (64, 128), "float32"

    cold = autotune.tune("fused_norm", shapes, dtype, warmup=1, runs=2,
                         top_k=2)
    assert not cold.cache_hit and cold.source == "search"
    assert cold.trials >= 1
    path = tmp_path / "autotune_fused_norm.json"
    assert path.exists()
    entry = next(iter(__import__("json").loads(path.read_text()).values()))
    assert "config" in entry and "block_rows" in entry["config"]

    h0 = tele.counter("autotune_hits").value()
    warm = autotune.tune("fused_norm", shapes, dtype)
    assert warm.cache_hit and warm.trials == 0
    assert tele.counter("autotune_hits").value() == h0 + 1
    assert warm.config == cold.config

    # fresh memory cache: the DISK entry alone serves the key
    autotune.clear_memory_cache()
    disk = autotune.tune("fused_norm", shapes, dtype)
    assert disk.cache_hit and disk.trials == 0
    assert autotune.cached_config("fused_norm", shapes, dtype) is not None

    # ragged tails share the tuned bucket (shape_bucket rounds up)
    assert autotune.cached_config("fused_norm", (63, 127),
                                  dtype) == cold.config


def test_autotune_disabled_env(monkeypatch, tmp_path):
    monkeypatch.setenv("MXTPU_AUTOTUNE_CACHE", str(tmp_path))
    cfg = autotune.BlockConfig(block_rows=64)
    key = autotune._key("fused_norm", (8, 128), "float32",
                        autotune.device_kind())
    autotune._disk_store("fused_norm", key, cfg)
    assert autotune.cached_config("fused_norm", (8, 128)) == cfg
    monkeypatch.setenv("MXTPU_AUTOTUNE", "0")
    assert autotune.cached_config("fused_norm", (8, 128)) is None


def test_autotune_all_failed_search_is_not_persisted(monkeypatch,
                                                     tmp_path):
    """When every survivor fails to build/run, the key must stay cold
    (no memory/disk pin of a config that never even compiled)."""
    monkeypatch.setenv("MXTPU_AUTOTUNE_CACHE", str(tmp_path))

    def boom(config, shapes, dtype):
        raise RuntimeError("backend exploded")

    tun = autotune._REGISTRY["fused_norm"]
    monkeypatch.setattr(tun, "build", boom)
    res = autotune.tune("fused_norm", (16, 128), "float32", runs=1)
    assert not res.cache_hit and res.trials == 0
    assert autotune.cached_config("fused_norm", (16, 128)) is None
    assert not (tmp_path / "autotune_fused_norm.json").exists()


def test_recommended_page_size_picks_up_any_tuned_shape(monkeypatch,
                                                        tmp_path):
    """The serve page size is per-device: a config tuned under ANY
    serving shape must reach ServeConfig's default."""
    from mxnet_tpu.ops.pallas.paged_attention import recommended_page_size
    monkeypatch.setenv("MXTPU_AUTOTUNE_CACHE", str(tmp_path))
    assert recommended_page_size(16) == 16
    key = autotune._key("paged_attention", (8, 8, 8, 64, 512),
                        "float32", autotune.device_kind())
    autotune._disk_store("paged_attention", key,
                         autotune.BlockConfig(page_size=64))
    assert recommended_page_size(16) == 64
    monkeypatch.setenv("MXTPU_AUTOTUNE", "0")
    assert recommended_page_size(16) == 16


def test_autotune_miss_is_negative_cached_until_tune(monkeypatch,
                                                     tmp_path):
    """A miss is remembered in-process (no disk re-read per norm call);
    a tune() for the key clears it, clear_memory_cache resets."""
    monkeypatch.setenv("MXTPU_AUTOTUNE_CACHE", str(tmp_path))
    key = autotune._key("fused_norm", (16, 128), "float32",
                        autotune.device_kind())
    assert autotune.cached_config("fused_norm", (16, 128)) is None
    assert key in autotune._MEM_MISS
    # another process writing the file is invisible until a reset —
    # the documented per-process semantics
    autotune._disk_store("fused_norm", key,
                         autotune.BlockConfig(block_rows=64))
    assert autotune.cached_config("fused_norm", (16, 128)) is None
    autotune.clear_memory_cache()
    assert autotune.cached_config("fused_norm", (16, 128)) == \
        autotune.BlockConfig(block_rows=64)


def test_autotune_unknown_op_raises():
    from mxnet_tpu.base import MXNetError
    with pytest.raises(MXNetError, match="unknown tunable"):
        autotune.tune("not_an_op", (8,))


def test_autotune_roofline_ranks_candidates():
    """The analytic model must prefer fewer grid steps for a
    bandwidth-bound kernel (the pruning signal that shrinks searches)."""
    assert set(autotune.tunables()) >= {
        "fused_norm", "fused_optimizer", "moe_dispatch",
        "flash_attention", "paged_attention"}
    tun = autotune._REGISTRY["fused_norm"]
    small = autotune.predict_s(tun, autotune.BlockConfig(block_rows=8),
                               (4096, 1024), "float32", kind="cpu")
    large = autotune.predict_s(tun, autotune.BlockConfig(block_rows=512),
                               (4096, 1024), "float32", kind="cpu")
    assert large < small


# ---------------------------------------------------------------------------
# fused train step: one trace over 10 steps + NaN-skip unchanged
# ---------------------------------------------------------------------------

def _make_step(optimizer):
    from mxnet_tpu.gluon import nn
    from mxnet_tpu.parallel import make_mesh, make_sharded_train_step
    mx.random.seed(7)
    net = nn.Dense(4, in_units=8)
    net.initialize()
    mesh = make_mesh({"dp": 1}, jax.devices("cpu")[:1])
    return make_sharded_train_step(
        net, optimizer, lambda out, x, y: jnp.mean((out - y) ** 2),
        mesh, num_model_args=1)


def _batch(nan=False, seed=0):
    rng = onp.random.RandomState(seed)
    x = rng.uniform(-1, 1, (8, 8)).astype(onp.float32)
    y = rng.uniform(-1, 1, (8, 4)).astype(onp.float32)
    if nan:
        x = x * onp.float32("nan")
    return x, y


def test_fused_step_traces_once_and_matches_reference(monkeypatch):
    """The kernel-route step compiles ONE program over 10 steps and its
    weights track the reference-route step to float tolerance."""
    monkeypatch.setenv("MXTPU_PALLAS", "kernel")
    kstep = _make_step(Adam(learning_rate=1e-2))
    assert kstep._fused_opt_kernel
    monkeypatch.setenv("MXTPU_PALLAS", "reference")
    rstep = _make_step(Adam(learning_rate=1e-2))
    assert not rstep._fused_opt_kernel

    for i in range(10):
        x, y = _batch(seed=i)
        lk = float(kstep(x, y))
        lr = float(rstep(x, y))
        assert onp.isfinite(lk)
        onp.testing.assert_allclose(lk, lr, rtol=1e-4, atol=1e-5)
    assert kstep.trace_count == 1
    assert rstep.trace_count == 1
    for n in kstep.pvals:
        onp.testing.assert_allclose(
            onp.asarray(jax.device_get(kstep.pvals[n])),
            onp.asarray(jax.device_get(rstep.pvals[n])),
            rtol=1e-4, atol=1e-5)


def test_fused_step_nan_skip_preserves_weights(monkeypatch):
    """PR 5 semantics through the in-register kernel guard: a NaN batch
    leaves params bit-identical, the next clean batch applies, and the
    guard never costs a retrace."""
    monkeypatch.setenv("MXTPU_PALLAS", "kernel")
    recovery.enable()
    step = _make_step(SGD(learning_rate=1e-2, momentum=0.9))
    assert step._fused_opt_kernel and step._skip_nonfinite
    x, y = _batch()
    step(x, y)
    before = {n: onp.asarray(jax.device_get(v))
              for n, v in step.pvals.items()}
    step(*_batch(nan=True))
    for n, v in step.pvals.items():
        onp.testing.assert_array_equal(
            onp.asarray(jax.device_get(v)), before[n])
    step(x, y)
    assert any(not onp.array_equal(onp.asarray(jax.device_get(v)),
                                   before[n])
               for n, v in step.pvals.items())
    assert step.trace_count == 1


# ---------------------------------------------------------------------------
# paged K/V write: the Pallas call against the XLA scatter it replaces
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("page_in_lanes", [False, True],
                         ids=["rows_in_sublanes", "rows_in_lanes"])
@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "f32"])
@pytest.mark.parametrize("C", [1, 5, 16])
def test_paged_kv_write_equals_the_scatter_bit_for_bit(C, dtype,
                                                       page_in_lanes):
    """`paged_kv_write` (interpret mode: the exact kernel code) leaves the
    pool as the scatter route's `scatter_kv_write` does, bit for bit, in
    either page orientation: a chunk that starts a page, one that
    straddles a page boundary, ``0 < num_tokens < C``, the last logical
    page with the padded rows past it (clamped), an idle slot, a slot
    deep in a page; fewer kv heads than a GQA model's query heads,
    ``layer`` != 0 of a stacked pool.  Every page no slot names is left
    untouched, and the kernel never writes the null page (the scatter
    parks its masked rows there)."""
    from mxnet_tpu.ops.pallas.paged_attention import paged_kv_write
    from mxnet_tpu.serve.kv_cache import NULL_PAGE, scatter_kv_write
    rng = onp.random.RandomState(C)
    L, li, Hkv, D, ps, B, maxp = 3, 1, 2, 64, 128, 6, 3
    npages = B * maxp + 3                 # the null page + two unowned
    pools = {n: jnp.asarray(rng.standard_normal((L, Hkv, npages, ps, D)),
                            dtype) for n in "kv"}
    pt = jnp.asarray(rng.permutation(onp.arange(1, B * maxp + 1))
                     .reshape(B, maxp), jnp.int32)
    start = jnp.asarray([0, ps - 2, ps + 3, maxp * ps - 3, 7, 2 * ps - 1],
                        jnp.int32)
    nt = jnp.asarray([C, C, max(C - 2, 1), min(C, 3), 0, C], jnp.int32)
    kn = jnp.asarray(rng.standard_normal((B, Hkv, C, D)), jnp.float32)
    vn = jnp.asarray(rng.standard_normal((B, Hkv, C, D)), jnp.float32)

    want = dict(pools)
    scatter_kv_write(want, li, kn, vn, pt, start, nt, ps, False)
    got = dict(zip("kv", jax.jit(
        lambda k, v: paged_kv_write(k, v, kn, vn, li, pt, start, nt,
                                    null_page=NULL_PAGE,
                                    page_in_lanes=page_in_lanes))(
        pools["k"], pools["v"])))

    def bits(x):
        return onp.asarray(jax.lax.bitcast_convert_type(
            x, jnp.uint16 if x.dtype == jnp.bfloat16 else jnp.uint32))

    real = onp.arange(npages) != NULL_PAGE
    named = onp.zeros(npages, bool)
    named[onp.asarray(pt).ravel()] = True
    for n in "kv":
        g, w, o = bits(got[n]), bits(want[n]), bits(pools[n])
        assert got[n].dtype == dtype and got[n].shape == pools[n].shape
        onp.testing.assert_array_equal(g[:, :, real], w[:, :, real])
        onp.testing.assert_array_equal(g[:, :, ~named], o[:, :, ~named])
        others = onp.arange(L) != li
        onp.testing.assert_array_equal(g[others], o[others])
        assert (g[li] != o[li]).any()


# ---------------------------------------------------------------------------
# paged attention: the work list of live (slot, page) pairs, and the kernel
# that walks it, against the gather reference
# ---------------------------------------------------------------------------

# context lengths of a ragged batch over pages of 8 and a table of 6: idle
# slots first, last and between live ones; a context that ends on a page
# boundary; one page; the whole table; a chunk shorter than the step's width
_RAGGED_CTX = [0, 21, 0, 24, 5, 48, 35, 0]
_RAGGED_NT = [0, 16, 0, 16, 3, 16, 5, 0]


def _ragged_batch(C):
    ctx = onp.asarray(_RAGGED_CTX)
    nt = onp.minimum(onp.asarray(_RAGGED_NT), C)
    return ctx, ctx - nt, nt


def _visible_pages(ctx, start, nt, window, ps):
    """Brute force: the pages that hold a key some real query of the
    chunk sees, ascending."""
    seen = set()
    for qpos in range(start, start + nt):
        for kpos in range(ctx):
            if kpos <= qpos and (window is None or kpos >= qpos - window):
                seen.add(kpos // ps)
    return sorted(seen)


@pytest.mark.parametrize("window", [None, 0, 5, 11, 4095],
                         ids=lambda w: f"window_{w}")
@pytest.mark.parametrize("C", [1, 16])
def test_live_page_items_list_every_visible_page_once_in_order(C, window):
    """`live_page_items` against the enumeration: a slot's items are the
    pages some query of its chunk sees, each once, ascending, slot after
    slot; an idle slot has the one item that keeps its output written;
    the tail past `n_items` repeats the last item; ints and arrays give
    the same range."""
    from mxnet_tpu.serve.kv_cache import (live_page_items, live_page_range,
                                          window_first_page,
                                          window_walk_pages)
    ps, maxp = 8, 6
    ctx, start, nt = _ragged_batch(C)
    walk = maxp if window is None \
        else min(maxp, window_walk_pages(window, C, ps))
    slot, page, n = (onp.asarray(x) for x in live_page_items(
        jnp.asarray(ctx), jnp.asarray(start), window, ps, walk))
    assert slot.shape == page.shape == (len(ctx) * walk,)
    assert slot.dtype == page.dtype == onp.int32
    want = []
    for b in range(len(ctx)):
        pages = _visible_pages(ctx[b], start[b], nt[b], window, ps)
        assert len(pages) <= walk
        if not pages:       # idle: its first page, masked whole
            pages = [0 if window is None
                     else int(window_first_page(int(start[b]), window, ps))]
        want += [(b, pg) for pg in pages]
        first, count = live_page_range(int(ctx[b]), int(start[b]), window,
                                       ps, walk)
        assert (first, count) == (pages[0], len(pages))
    assert n == len(want) >= len(ctx)
    assert list(zip(slot[:n], page[:n])) == want
    assert (slot[n:] == want[-1][0]).all() and (page[n:] == want[-1][1]).all()
    first, count = live_page_range(ctx, start, window, ps, walk)
    assert isinstance(count, onp.ndarray) and count.sum() == n


def test_live_page_range_never_passes_the_walk_it_was_sized_for():
    """A context the caller's static bound cannot hold is cut to the
    bound: the work list never runs past its own length."""
    from mxnet_tpu.serve.kv_cache import live_page_items, live_page_range
    assert live_page_range(100, 99, None, 8, 4) == (0, 4)
    slot, page, n = live_page_items(jnp.asarray([100, 3]),
                                    jnp.asarray([99, 0]), None, 8, 4)
    assert int(n) == 5 and slot.tolist() == [0, 0, 0, 0, 1, 1, 1, 1]


@pytest.mark.parametrize("page_in_lanes", [False, True],
                         ids=["rows_in_sublanes", "rows_in_lanes"])
@pytest.mark.parametrize("window", [None, 11], ids=["full", "sliding"])
@pytest.mark.parametrize("rep", [1, 6], ids=["mha", "gqa6"])
@pytest.mark.parametrize("C", [1, 16])
def test_paged_attention_kernel_walks_live_pages_like_the_reference(
        C, rep, window, page_in_lanes):
    """`ragged_paged_attention` (interpret mode: the exact kernel code)
    over the ragged batch, against the gather reference: C = 1 and C = 16
    with ``num_tokens < C``, six query heads a kv head, both page
    orientations, ``layer`` != 0 of a stacked pool.  The sliding case
    gets the table as the scheduler hands it over (every page before a
    slot's first live one is the null page) and a work list of
    `window_walk_pages` pages a slot; the full case builds its own over
    the table's width.  Idle slots come out as exact zeros."""
    from mxnet_tpu.ops.pallas import paged_attention as pa
    from mxnet_tpu.serve.kv_cache import (NULL_PAGE, live_page_items,
                                          window_first_page,
                                          window_walk_pages)
    rng = onp.random.RandomState(7 * C + rep)
    ps, maxp, Hkv, D, L, li = 8, 6, 2, 16, 2, 1
    ctx, start, nt = _ragged_batch(C)
    B, H = len(ctx), Hkv * rep
    npages = B * maxp + 1
    q = jnp.asarray(rng.standard_normal((B, H, C, D)), jnp.float32)
    kp, vp = (jnp.asarray(rng.standard_normal((L, Hkv, npages, ps, D)),
                          jnp.float32) for _ in "kv")
    table = onp.asarray(1 + rng.permutation(B * maxp).reshape(B, maxp),
                        onp.int32)
    ctx_d, start_d = jnp.asarray(ctx, jnp.int32), jnp.asarray(start,
                                                              jnp.int32)
    want = pa.paged_attention_reference(
        q, kp[li], vp[li], jnp.asarray(table), ctx_d, start_d, window=window)
    work_list = None
    if window is not None:
        for b in range(B):
            first = int(window_first_page(int(start[b]), window, ps))
            table[b, :first] = NULL_PAGE
        assert (table[5, :2] == NULL_PAGE).all()    # first live page > 0
        work_list = live_page_items(ctx_d, start_d, window, ps,
                                    window_walk_pages(window, 16, ps))
    got = jax.jit(lambda q, k, v: pa.ragged_paged_attention(
        q, k, v, jnp.asarray(table), ctx_d, start_d, window=window,
        use_kernel=True, layer=li, page_in_lanes=page_in_lanes,
        work_list=work_list))(q, kp, vp)
    assert got.shape == q.shape and got.dtype == q.dtype
    for b in range(B):
        if nt[b] == 0:
            assert not onp.asarray(got[b]).any()
        onp.testing.assert_allclose(got[b, :, :nt[b]], want[b, :, :nt[b]],
                                    rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("window", [None, 3, 40, 4095],
                         ids=lambda w: f"window_{w}")
@pytest.mark.parametrize("seed", [0, 1, 2])
def test_live_page_items_match_an_enumeration_of_random_batches(seed, window):
    """Random contexts, starts and chunk lengths over a table of 9 pages
    of 8, with idle slots and one slot at the table's full width: the
    list is the NumPy enumeration of every slot's visible pages,
    ``n_items`` the sum of the counts, the tail a repeat of the last
    item, and no slot passes the walk the list was sized for."""
    from mxnet_tpu.serve.kv_cache import (live_page_items, live_page_range,
                                          window_first_page,
                                          window_walk_pages)
    rng = onp.random.default_rng([seed, 0 if window is None else window + 1])
    ps, maxp, B, C = 8, 9, 12, 16
    ctx = rng.integers(1, maxp * ps + 1, B)
    nt = onp.minimum(rng.integers(1, C + 1, B), ctx)
    idle = rng.random(B) < 0.25
    ctx[idle], nt[idle] = 0, 0
    ctx[B - 1], nt[B - 1] = maxp * ps, C          # the table's full width
    start = ctx - nt
    walk = maxp if window is None \
        else min(maxp, window_walk_pages(window, C, ps))
    want = []
    for b in range(B):
        pages = _visible_pages(ctx[b], start[b], nt[b], window, ps) or [
            0 if window is None
            else int(window_first_page(int(start[b]), window, ps))]
        assert len(pages) <= walk
        assert pages == list(range(pages[0], pages[0] + len(pages)))
        want += [(b, pg) for pg in pages]
    slot, page, n = (onp.asarray(x) for x in jax.jit(
        lambda c, s: live_page_items(c, s, window, ps, walk))(
            jnp.asarray(ctx, jnp.int32), jnp.asarray(start, jnp.int32)))
    assert slot.shape == page.shape == (B * walk,)
    first, count = live_page_range(ctx, start, window, ps, walk)
    assert n == count.sum() == len(want)
    assert list(zip(slot[:n], page[:n])) == want
    assert (slot[n:] == want[-1][0]).all() and (page[n:] == want[-1][1]).all()
    if window is None:
        assert count[B - 1] == walk == maxp


@pytest.mark.parametrize("C", [1, 16])
@pytest.mark.parametrize("widths,page_in_lanes", [
    ("gpt2", False), ("gpt2", True), ("afmoe_full", False),
    ("afmoe_sliding", False), ("gpt2_folded", False)], ids=[
        "gpt2-rows_in_sublanes", "gpt2-rows_in_lanes", "afmoe_full",
        "afmoe_sliding", "gpt2-folded"])
def test_paged_attention_kernel_matches_the_reference_at_published_widths(
        widths, page_in_lanes, C):
    """The exact kernel code (interpret mode) at the widths the benchmark
    serves, pages of 128: GPT-2-small's 12 heads of 64 over a table of 3,
    in both page orientations of the unfolded pool, and folded two kv
    heads a 128-lane row as the engine keeps it (`kv_heads_per_row`);
    `afmoe`'s 48 query / 8 kv heads of 128 over a table of 36, the full
    layer, and the sliding one under its window of 4095 with every
    released page of the table the null page and a work list of
    `window_walk_pages` a slot.  An idle slot, which comes out as exact
    zeros, a slot past a page edge, a slot at the table's end and one
    whose chunk is shorter than the step's width."""
    from mxnet_tpu.ops.pallas import paged_attention as pa
    from mxnet_tpu.serve.kv_cache import (NULL_PAGE, live_page_items,
                                          window_first_page,
                                          window_walk_pages)
    ps, window = 128, None
    g = 2 if widths == "gpt2_folded" else 1
    if widths.startswith("gpt2"):
        H, Hkv, D, maxp = 12, 12, 64, 3
        ctx = onp.asarray([0, 131, 384, 77])
    else:
        H, Hkv, D, maxp = 48, 8, 128, 36
        ctx = onp.asarray([0, 130, maxp * ps, 300])
        window = 4095 if widths == "afmoe_sliding" else None
    B = len(ctx)
    nt = onp.minimum([0, C, C, max(C // 2, 1)], ctx)
    start = ctx - nt
    rng = onp.random.RandomState(3)
    npages = B * maxp + 1
    q = jnp.asarray(rng.standard_normal((B, H, C, D)), jnp.float32)
    kp, vp = (jnp.asarray(rng.standard_normal((2, Hkv, npages, ps, D)),
                          jnp.float32) for _ in "kv")
    table = onp.asarray(1 + rng.permutation(B * maxp).reshape(B, maxp),
                        onp.int32)
    ctx_d, start_d = (jnp.asarray(x, jnp.int32) for x in (ctx, start))
    want = pa.paged_attention_reference(
        q, kp[1], vp[1], jnp.asarray(table), ctx_d, start_d, window=window)
    work_list = None
    if window is not None:
        for b in range(B):
            table[b, :int(window_first_page(int(start[b]), window, ps))] \
                = NULL_PAGE
        assert (table[2, :2] == NULL_PAGE).all()       # pages were released
        walk = window_walk_pages(window, 16, ps)
        assert walk == 34 < maxp
        work_list = live_page_items(ctx_d, start_d, window, ps, walk)
    got = pa.ragged_paged_attention(
        q, pa.fold_heads(kp, g), pa.fold_heads(vp, g), jnp.asarray(table),
        ctx_d, start_d, window=window, use_kernel=True, layer=1,
        page_in_lanes=page_in_lanes, work_list=work_list, heads_per_row=g)
    assert not onp.asarray(got[0]).any()
    for b in range(1, B):
        onp.testing.assert_allclose(got[b, :, :nt[b]], want[b, :, :nt[b]],
                                    rtol=2e-5, atol=2e-5)


# ---------------------------------------------------------------------------
# folded pools: 128 / D kv heads side by side in the lanes of a page row
# ---------------------------------------------------------------------------

def _bits(x):
    return onp.asarray(jax.lax.bitcast_convert_type(
        x, jnp.uint16 if x.dtype == jnp.bfloat16 else jnp.uint32))


@pytest.mark.parametrize("window", [None, 11], ids=["full", "sliding"])
@pytest.mark.parametrize("rep", [1, 3], ids=["mha", "gqa3"])
@pytest.mark.parametrize("C", [1, 16])
@pytest.mark.parametrize("D", [64, 32], ids=["g2", "g4"])
def test_folded_pool_write_then_attend_matches_the_references(D, C, rep,
                                                              window):
    """A bf16 pool folded ``g = 128 / D`` kv heads a row (g = 2 and 4):
    `paged_kv_write` (interpret mode: the exact kernel code, 16-row tiles
    of every row group) leaves it as the scatter route leaves it, bit for
    bit, and as the scatter leaves the unfolded pool, folded; every row no
    chunk row lands on, the null page's among them, is unchanged.  Then
    `ragged_paged_attention` over the written pool equals the gather
    reference over the unfolded one: C = 1 and C = 16 (a chunk straddling
    two pages, one deep in a page), an idle slot that touches the null
    page alone and comes out as exact zeros, three query heads a kv head,
    a window with the released pages of the table the null page."""
    from mxnet_tpu.ops.pallas import paged_attention as pa
    from mxnet_tpu.serve.kv_cache import (NULL_PAGE, live_page_items,
                                          scatter_kv_write,
                                          window_first_page,
                                          window_walk_pages)
    g = 128 // D
    L, li, Hkv, ps, maxp = 2, 1, 2 * g, 32, 3
    rng = onp.random.RandomState(D + 3 * C + rep)
    start = onp.asarray([0, ps - 5, 41, 0, 2 * ps + 3])
    nt = onp.minimum([C, C, max(C - 3, 1), 0, C], C)
    B = len(start)
    npages = B * maxp + 2                   # the null page + one unowned
    flat = {n: jnp.asarray(rng.standard_normal((L, Hkv, npages, ps, D)),
                           jnp.bfloat16) for n in "kv"}
    table = onp.asarray(1 + rng.permutation(B * maxp).reshape(B, maxp),
                        onp.int32)
    if window is not None:
        for b in range(B):
            table[b, :int(window_first_page(int(start[b]), window, ps))] = \
                NULL_PAGE
        assert table[4, 0] == NULL_PAGE             # released by then
    kn, vn = (jnp.asarray(rng.standard_normal((B, Hkv, C, D)), jnp.float32)
              for _ in "kv")
    pt, st, n_t = (jnp.asarray(x, jnp.int32) for x in (table, start, nt))

    folded = {n: pa.fold_heads(flat[n], g) for n in "kv"}
    assert folded["k"].shape == (L, Hkv // g, npages, ps, 128)
    assert (pa.unfold_heads(folded["k"], g) == flat["k"]).all()
    want = dict(folded)
    scatter_kv_write(want, li, kn, vn, pt, st, n_t, ps, False,
                     heads_per_row=g)
    scatter_kv_write(flat, li, kn, vn, pt, st, n_t, ps, False)
    got = dict(zip("kv", jax.jit(
        lambda k, v: pa.paged_kv_write(k, v, kn, vn, li, pt, st, n_t,
                                       null_page=NULL_PAGE,
                                       heads_per_row=g))(
        folded["k"], folded["v"])))
    written = onp.zeros((npages, ps), bool)
    for b in range(B):
        for p in range(start[b], start[b] + nt[b]):
            written[table[b, min(p // ps, maxp - 1)], p % ps] = True
    assert written[NULL_PAGE].sum() == 0
    real = onp.arange(npages) != NULL_PAGE
    for n in "kv":
        gb, ob = _bits(got[n]), _bits(folded[n])
        onp.testing.assert_array_equal(gb[:, :, real],
                                       _bits(want[n])[:, :, real])
        onp.testing.assert_array_equal(
            gb[:, :, real], _bits(pa.fold_heads(flat[n], g))[:, :, real])
        onp.testing.assert_array_equal(gb[li][:, ~written],
                                       ob[li][:, ~written])
        onp.testing.assert_array_equal(gb[1 - li], ob[1 - li])
        assert (gb[li][:, written] != ob[li][:, written]).all(-1).any()

    q = jnp.asarray(rng.standard_normal((B, Hkv * rep, C, D)), jnp.float32)
    ctx = st + n_t
    ref = pa.paged_attention_reference(
        q, flat["k"][li].astype(jnp.float32),
        flat["v"][li].astype(jnp.float32), pt, ctx, st, window=window)
    k32, v32 = (got[n].astype(jnp.float32) for n in "kv")
    onp.testing.assert_array_equal(pa.paged_attention_reference(
        q, k32[li], v32[li], pt, ctx, st, window=window, heads_per_row=g),
        ref)
    work_list = None if window is None else live_page_items(
        ctx, st, window, ps, window_walk_pages(window, 16, ps))
    out = jax.jit(lambda q, k, v: pa.ragged_paged_attention(
        q, k, v, pt, ctx, st, window=window, use_kernel=True, layer=li,
        work_list=work_list, heads_per_row=g))(q, k32, v32)
    assert out.shape == q.shape
    for b in range(B):
        if nt[b] == 0:
            assert not onp.asarray(out[b]).any()
        onp.testing.assert_allclose(out[b, :, :nt[b]], ref[b, :, :nt[b]],
                                    rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("n_kv,D,tp,kv_dtype,g", [
    (12, 64, 1, "bfloat16", 2), (8, 32, 1, "bfloat16", 4),
    (8, 128, 1, "bfloat16", 1), (3, 64, 1, "bfloat16", 1),
    (1, 64, 1, "bfloat16", 1), (4, 64, 2, "float32", 2),
    (2, 64, 2, "float32", 1), (12, 48, 1, "bfloat16", 1),
    (12, 64, 1, "int8", 1)], ids=[
        "gpt2", "d32", "d128", "odd_kv_heads", "mqa", "tp2_whole_rows",
        "tp2_cuts_a_row", "d48", "int8"])
def test_kv_heads_per_row_folds_whole_row_groups_only(n_kv, D, tp, kv_dtype,
                                                      g):
    """The fold follows from the shapes alone: ``128 / D`` kv heads a row
    where D is under 128 and divides it and the kv heads of a tp shard
    divide by that; one head a row for D = 128, for a count of kv heads
    (an odd one, MQA's one, one a shard under tp = 2) that would cut a
    row, for a D that does not divide 128, and for an int8 pool, whose
    scale planes stay one a head.  `KVPools` records g a group, shapes
    the arrays by it, and its `unfold` / `fold` carry a page payload to
    one head a row and back."""
    from mxnet_tpu.serve.kv_cache import (CacheGroup, KVPools,
                                          PageAllocator, kv_heads_per_row)
    assert kv_heads_per_row(n_kv, D, tp, kv_dtype == "int8") == g
    pools = KVPools.create(
        [CacheGroup("full", (0, 1), None, 5, 4, PageAllocator(5, 8))],
        8, n_kv, D, dtype=kv_dtype, tp=tp)
    assert pools.heads_per_row == {"full": g}
    assert pools.arrays["k"].shape == (2, n_kv // g, 5, 8, g * D)
    if kv_dtype == "int8":
        assert pools.arrays["k_scale"].shape == (2, n_kv, 5, 8)
        assert pools.unfold("k_scale", pools.arrays["k_scale"]) is \
            pools.arrays["k_scale"]
    x = _rand((2, n_kv, 3, 8, D))
    assert pools.fold("k", x).shape == (2, n_kv // g, 3, 8, g * D)
    assert (pools.unfold("v", pools.fold("v", x)) == x).all()


def test_pages_in_lanes_reads_the_arrays_own_layout():
    """The orientation is asked of the pool's device layout: row-major on
    the CPU (rows in sublanes); a tracer or anything without a layout is
    row-major too."""
    from mxnet_tpu.ops.pallas.paged_attention import pages_in_lanes
    from mxnet_tpu.serve.kv_cache import CacheGroup, KVPools, PageAllocator
    pools = KVPools.create(
        [CacheGroup("full", (0, 1), None, 5, 4, PageAllocator(5, 8))],
        8, 2, 16, dtype="bfloat16")
    assert pools.pages_in_lanes() is False
    assert pages_in_lanes(object()) is False

    class _Swapped:
        class format:
            class layout:
                major_to_minor = (0, 1, 2, 4, 3)
    assert pages_in_lanes(_Swapped()) is True
