"""AOT cross-lowering checks: Mosaic (TPU) lowering of the Pallas kernels
runs at `.lower(lowering_platforms=("tpu",))` time, so kernel-level TPU
compile breakage (unsupported ops, layout errors) surfaces on the CPU-only
CI host — without a chip. The round-3 in-kernel hash RNG and bias streaming
are exactly the kind of code this guards.
"""
import os

import numpy as onp
import pytest

import jax
import jax.numpy as jnp

from mxnet_tpu.ops.pallas.flash_attention import flash_attention


@pytest.fixture(autouse=True)
def _no_interpret():
    """Other modules flip MXTPU_PALLAS_INTERPRET=1 process-wide; lowering
    must see compiled-mode kernels (interpret mode emits no custom call)."""
    old = os.environ.pop("MXTPU_PALLAS_INTERPRET", None)
    yield
    if old is not None:
        os.environ["MXTPU_PALLAS_INTERPRET"] = old


def _lower_for_tpu(fn, *args):
    return jax.jit(fn).trace(*args).lower(
        lowering_platforms=("tpu",)).as_text()


def test_flash_kernel_masked_dropout_lowers_for_tpu():
    b, h, l, d = 2, 4, 128, 64
    q = jnp.ones((b, h, l, d), jnp.bfloat16)
    bias = jnp.zeros((b, 1, l), jnp.float32)

    def fwd(q, k, v, bias):
        return flash_attention(q, k, v, bias=bias, dropout_rate=0.1,
                               dropout_seed=7)

    txt = _lower_for_tpu(fwd, q, q, q, bias)
    assert txt.count("tpu_custom_call") == 1

    def train(q, k, v):
        def loss(q, k, v):
            return jnp.sum(fwd(q, k, v, bias).astype(jnp.float32) ** 2)
        return jax.grad(loss, argnums=(0, 1, 2))(q, k, v)

    txt = _lower_for_tpu(train, q, q, q)
    # forward (rematerialised in vjp) + dq + dkv kernels
    assert txt.count("tpu_custom_call") == 3


def test_flash_kernel_causal_lowers_for_tpu():
    b, h, l, d = 1, 2, 256, 128
    q = jnp.ones((b, h, l, d), jnp.bfloat16)

    def f(q, k, v):
        return flash_attention(q, k, v, causal=True)

    txt = _lower_for_tpu(f, q, q, q)
    assert txt.count("tpu_custom_call") == 1


def test_softmax_xent_lowers_for_tpu_at_real_vocab():
    """The DISPATCHING wrapper must emit the kernel for the exact shapes
    the bench uses — BERT's 30522 vocab does not tile to powers of two,
    so this guards the ceil-grid path end to end."""
    from mxnet_tpu.ops import attention as _att
    from mxnet_tpu.ops.pallas.softmax_xent import softmax_cross_entropy

    # the dispatcher consults the RUNTIME backend (cpu here); force the
    # TPU decision so lowering exercises the kernel path
    orig = _att._use_pallas
    _att._use_pallas = lambda: True
    try:
        n, v = 1280, 30522      # bench: batch 64 x n_mask 20, BERT vocab
        x = jnp.ones((n, v), jnp.bfloat16)
        lab = jnp.zeros((n,), jnp.int32)

        def f(x, lab):
            return jnp.mean(softmax_cross_entropy(x, lab))

        txt = _lower_for_tpu(f, x, lab)
        assert txt.count("tpu_custom_call") == 1

        def g(x, lab):
            return jax.grad(
                lambda x: jnp.mean(softmax_cross_entropy(x, lab)))(x)

        txt = _lower_for_tpu(g, x, lab)
        assert txt.count("tpu_custom_call") == 2     # fwd (rerun) + bwd

        # GPT-2's odd 50257 vocab too
        xg = jnp.ones((256, 50257), jnp.bfloat16)
        lg = jnp.zeros((256,), jnp.int32)
        txt = _lower_for_tpu(f, xg, lg)
        assert txt.count("tpu_custom_call") == 1
    finally:
        _att._use_pallas = orig


def test_flash_kernel_sliding_window_lowers_for_tpu():
    """Banded (sliding-window) kernel mode: forward and both backward
    kernels must pass Mosaic lowering — the band iota/compares and the
    block-skip predicates are TPU-side code paths."""
    b, h, l, d = 2, 4, 512, 64
    q = jnp.ones((b, h, l, d), jnp.bfloat16)

    def fwd(q, k, v):
        return flash_attention(q, k, v, window=128, causal=True)

    txt = _lower_for_tpu(fwd, q, q, q)
    assert txt.count("tpu_custom_call") == 1

    def train(q, k, v):
        def loss(q, k, v):
            return jnp.sum(fwd(q, k, v).astype(jnp.float32) ** 2)
        return jax.grad(loss, argnums=(0, 1, 2))(q, k, v)

    txt = _lower_for_tpu(train, q, q, q)
    assert txt.count("tpu_custom_call") == 3   # fwd + dq + dkv


def test_flash_gqa_grouped_kernel_lowers_for_tpu():
    """Grouped-KV (GQA) kernel mode: q folded to (B, g, rep*Lq, D), K/V
    streamed at g heads. Pins that the folded kernels (position-wrapped
    causal mask, per-segment row indexing) survive Mosaic lowering AND
    that no full-head K/V expansion appears in the lowered module."""
    b, h, g, l, d = 2, 8, 2, 512, 64
    q = jnp.ones((b, h, l, d), jnp.bfloat16)
    kv = jnp.ones((b, g, l, d), jnp.bfloat16)

    def fwd(q, k, v):
        return flash_attention(q, k, v, causal=True)

    txt = _lower_for_tpu(fwd, q, kv, kv)
    assert txt.count("tpu_custom_call") == 1
    # K/V at full heads would show up as a (b*h)xLxD = 16x512x64 tensor
    assert f"tensor<{b * h}x{l}x{d}xbf16" not in txt

    def train(q, k, v):
        def loss(q, k, v):
            return jnp.sum(fwd(q, k, v).astype(jnp.float32) ** 2)
        return jax.grad(loss, argnums=(0, 1, 2))(q, k, v)

    txt = _lower_for_tpu(train, q, kv, kv)
    assert txt.count("tpu_custom_call") == 3   # fwd + dq + dkv
    assert f"tensor<{b * h}x{l}x{d}xbf16" not in txt


@pytest.mark.slow
def test_full_gpt_train_step_composition_lowers_for_tpu():
    """The bench-suite GPT leg composition — RoPE + sliding window + GQA
    + remat + fused softmax-CE inside ONE sharded train step — must pass
    Mosaic lowering end to end (kernel-level TPU compile breakage in any
    piece surfaces here without a chip)."""
    import numpy as onp

    from mxnet_tpu.models.gpt import GPTConfig, GPTForCausalLM
    from mxnet_tpu.ops import attention as _att

    import mxnet_tpu as mx
    from mxnet_tpu import optimizer as opt
    from mxnet_tpu.parallel import make_mesh, make_sharded_train_step

    cfg = GPTConfig(vocab_size=50257, hidden_size=256, num_layers=2,
                    num_heads=8, num_kv_heads=2, intermediate_size=512,
                    max_position=512, dtype="bfloat16", remat=True,
                    rope=True, window=128)
    m = GPTForCausalLM(cfg)
    m.initialize()
    ids = mx.np.array(onp.zeros((2, 512), onp.int32))
    m(ids)   # deferred init runs EAGERLY — before forcing the kernel path

    def lm_loss(out, i):
        from mxnet_tpu.ops.pallas.softmax_xent import \
            softmax_cross_entropy
        return softmax_cross_entropy(out[:, :-1],
                                     i[:, 1:].astype(jnp.int32)).mean()

    mesh = make_mesh({"dp": 1}, jax.devices("cpu")[:1])
    step = make_sharded_train_step(m, opt.Adam(learning_rate=1e-4),
                                   lm_loss, mesh, num_model_args=1)
    step._build([ids._data], None)       # jitted fn without executing
    orig = _att._use_pallas
    _att._use_pallas = lambda: True      # force the kernel path off-TPU
    try:
        txt = step._step_fn.trace(
            step.pvals, step.opt_state,
            {"lr": jnp.float32(1e-4), "wd": jnp.float32(0.0),
             "rescale_grad": jnp.float32(1.0), "clip_gradient": None,
             "t": jnp.float32(0)},
            jax.random.PRNGKey(0), ids._data).lower(
                lowering_platforms=("tpu",)).as_text()
        # per layer: flash fwd + dq + dkv (banded, grouped); plus CE fwd+bwd
        n = txt.count("tpu_custom_call")
        assert n >= 2 * 3 + 2, f"expected >= 8 kernel custom calls, got {n}"
    finally:
        _att._use_pallas = orig


@pytest.mark.parametrize("bq,bk", [(512, 256), (256, 512), (512, 512)])
def test_flash_block_size_variants_lower_for_tpu(bq, bk):
    """The H2 ablation sweep's non-default (block_q, block_k) tilings must
    pass Mosaic lowering (lane/sublane layout constraints bind at 512)."""
    b, h, l, d = 1, 2, 1024, 64
    q = jnp.ones((b, h, l, d), jnp.bfloat16)

    def f(q, k, v):
        return flash_attention(q, k, v, causal=True, block_q=bq, block_k=bk)

    txt = _lower_for_tpu(f, q, q, q)
    assert txt.count("tpu_custom_call") == 1

    def train(q, k, v):
        def loss(q, k, v):
            return jnp.sum(f(q, k, v).astype(jnp.float32) ** 2)
        return jax.grad(loss, argnums=(0, 1, 2))(q, k, v)

    txt = _lower_for_tpu(train, q, q, q)
    assert txt.count("tpu_custom_call") == 3


# ---------------------------------------------------------------------------
# serving: the paged-attention kernel and the fused serve step (PR 21 — the
# kernel had only ever run in interpret mode and could not lower at all)
# ---------------------------------------------------------------------------

GPT2_SMALL = dict(H=12, Hkv=12, D=64)      # 12 x 768, 12 heads


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
@pytest.mark.parametrize("C", [1, 16])
def test_paged_attention_kernel_lowers_for_tpu_at_gpt2_shapes(dtype, C):
    """`ragged_paged_attention(use_kernel=True)` at GPT-2-small widths,
    the default TPU page size, decode (C = 1) and a prefill chunk: the
    head-major pool makes one (kv head, page) a (page_size, D) tile
    Mosaic can block — the old (pages, ps, Hkv, D) pool squeezed Hkv in
    the second-minor position and failed right here."""
    from mxnet_tpu.ops.pallas.paged_attention import (
        LANES, kernel_tileable, ragged_paged_attention)
    H, Hkv, D = (GPT2_SMALL[k] for k in ("H", "Hkv", "D"))
    B, ps, n_layers = 8, LANES, 2
    assert kernel_tileable(ps, D)
    maxp = 1024 // ps
    q = jnp.ones((B, H, C, D), dtype)
    pool = jnp.ones((n_layers, Hkv, B * maxp + 1, ps, D), dtype)
    pt = jnp.zeros((B, maxp), jnp.int32)
    ctx = jnp.full((B,), 40, jnp.int32)

    def f(q, kp, vp, pt, ctx, start):
        return ragged_paged_attention(q, kp, vp, pt, ctx, start,
                                      use_kernel=True, layer=1)

    txt = _lower_for_tpu(f, q, pool, pool, pt, ctx, ctx - C)
    assert txt.count("tpu_custom_call") == 1


@pytest.mark.parametrize("page_in_lanes", [False, True],
                         ids=["rows_in_sublanes", "rows_in_lanes"])
@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
@pytest.mark.parametrize("C", [1, 16])
def test_paged_kv_write_kernel_lowers_for_tpu_at_gpt2_shapes(dtype, C,
                                                             page_in_lanes):
    """`paged_kv_write` at GPT-2-small widths and the default TPU page
    size, decode and a prefill chunk, in both page orientations: one
    custom call, whose two results alias the pool operands."""
    from mxnet_tpu.ops.pallas.paged_attention import LANES, paged_kv_write
    Hkv, D = GPT2_SMALL["Hkv"], GPT2_SMALL["D"]
    B, ps, n_layers = 8, LANES, 2
    maxp = 1024 // ps
    pool = jnp.ones((n_layers, Hkv, B * maxp + 1, ps, D), dtype)
    new = jnp.ones((B, Hkv, C, D), jnp.float32)
    pt = jnp.zeros((B, maxp), jnp.int32)
    start = jnp.full((B,), 120, jnp.int32)

    def f(kp, vp, kn, vn, pt, start, nt):
        return paged_kv_write(kp, vp, kn, vn, 1, pt, start, nt,
                              page_in_lanes=page_in_lanes)

    txt = _lower_for_tpu(f, pool, pool, new, new, pt, start, start * 0 + C)
    assert txt.count("tpu_custom_call") == 1
    assert "output_operand_alias" in txt


def _gpt2_width_engine(monkeypatch, page_in_lanes, num_layers=1):
    """An engine at GPT-2-small widths (one layer unless told), traced as
    a TPU would trace it (the dispatch consults `jax.default_backend`).
    Rows in sublanes: GPT-2's own 12 kv heads of 64, folded two a 128-lane
    row (`kv_heads_per_row`), which a v5e keeps row-major.  Rows in
    lanes: a pool the engine cannot fold (3 kv heads of 64: a row of two
    would be cut), in the orientation a v5e keeps it.  Depth and vocab
    are cut: neither shapes the pool's calls."""
    import mxnet_tpu as mx
    from mxnet_tpu.models.gpt import GPTConfig, GPTForCausalLM
    from mxnet_tpu.serve import InferenceEngine, ServeConfig
    from mxnet_tpu.serve.kv_cache import KVPools
    cfg = GPTConfig(dtype="bfloat16", dropout=0.0, num_layers=num_layers,
                    vocab_size=1024,
                    num_kv_heads=3 if page_in_lanes else None)
    model = GPTForCausalLM(cfg)
    model.initialize()
    model(mx.np.array([[1, 2]], dtype="int32"))    # eager init on the cpu
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(KVPools, "pages_in_lanes",
                        lambda self: page_in_lanes)
    sc = ServeConfig(max_len=1024)
    if page_in_lanes:       # four times the pages: 12 kv heads' bytes
        sc = ServeConfig(max_len=1024, num_pages=4 * (
            sc.max_slots * 1024 // sc.page_size + 1))
    eng = InferenceEngine(model, sc)
    assert eng.pools.arrays["k"].shape[1::3] == (
        (3, 64) if page_in_lanes else (6, 128))
    return eng


@pytest.mark.parametrize("page_in_lanes", [False, True],
                         ids=["rows_in_sublanes", "rows_in_lanes"])
def test_gpt2_small_serve_step_lowers_with_paged_kernel(monkeypatch,
                                                        page_in_lanes):
    """The GPT-2-small-WIDTH serve step as a TPU would trace it: the
    default page size is one the kernels accept, and every compiled width
    carries two custom calls a layer (the K/V write, then the paged
    attention) and NO scatter or dynamic-update-slice over a pool-shaped
    operand: the pools pass through custom calls alone, so XLA:TPU has no
    layout of its own to give them (on the parent, one custom call a
    layer beside 2 scatters, and 96% of a decode step in whole-pool
    relayout copies)."""
    import re
    from mxnet_tpu.ops.pallas.paged_attention import LANES
    eng = _gpt2_width_engine(monkeypatch, page_in_lanes)
    n_layers = 1
    assert eng.serve_config.page_size == LANES
    pool = eng.pools.arrays["k"]
    # tensor<1x6x65x128x128xbf16>, or 1x3x65 and the last two dims in
    # either order
    dims = "x".join(map(str, pool.shape[:3])) + "x(128x%d|%dx128)xbf16" % (
        pool.shape[-1], pool.shape[-1])
    writes_pool = re.compile(
        r"stablehlo\.(scatter|dynamic_update_slice)[^\n]*tensor<" + dims)
    for C in eng._step_widths():
        txt = eng._step_fn(C).trace(*eng._step_avals(C)).lower(
            lowering_platforms=("tpu",)).as_text()
        assert txt.count("tpu_custom_call") == 2 * n_layers, (C, txt.count(
            "tpu_custom_call"))
        assert not writes_pool.search(txt), (C, writes_pool.search(txt))


def _count_kernel_bodies(monkeypatch):
    """Count the traces of the two paged kernels' bodies: every kernel
    `_make_rpa_kernel` / `_make_kv_write_kernel` builds from here on bumps
    its counter when Pallas traces it.  The inner jits' caches are
    cleared so that an earlier test's trace is not reused uncounted."""
    from mxnet_tpu.ops.pallas import paged_attention as pa
    traced = {"ragged_paged_attention": 0, "paged_kv_write": 0}

    def counting(make, name):
        def made(*a, **kw):
            body = make(*a, **kw)

            def counted(*refs):
                traced[name] += 1
                return body(*refs)
            return counted
        return made
    monkeypatch.setattr(pa, "_make_rpa_kernel", counting(
        pa._make_rpa_kernel, "ragged_paged_attention"))
    monkeypatch.setattr(pa, "_make_kv_write_kernel", counting(
        pa._make_kv_write_kernel, "paged_kv_write"))
    pa._rpa_pallas.clear_cache()
    pa._kv_write_pallas.clear_cache()
    return traced


@pytest.mark.parametrize("page_in_lanes", [False, True],
                         ids=["rows_in_sublanes", "rows_in_lanes"])
def test_serve_step_lowers_each_paged_kernel_once_a_width_not_once_a_layer(
        monkeypatch, page_in_lanes):
    """The guard on set-up.  A warm set-up compiles nothing, but it still
    traces the step in Python and lowers every Pallas body to Mosaic
    before the cache can be asked (PR 31 was refused for 3 s of exactly
    that).  A three-layer GPT-2-width engine lowered for the TPU holds ONE
    Mosaic body of `ragged_paged_attention` and one of `paged_kv_write` a
    width, each called once a layer, and Pallas traces each kernel body
    once a width: the layer index is a prefetched scalar, not a Python
    constant in an index map, so the layers share one inner jit's
    trace."""
    import re
    n_layers = 3
    eng = _gpt2_width_engine(monkeypatch, page_in_lanes, n_layers)
    traced = _count_kernel_bodies(monkeypatch)
    widths = eng._step_widths()
    for C in widths:
        txt = eng._step_fn(C).trace(*eng._step_avals(C)).lower(
            lowering_platforms=("tpu",)).as_text()
        assert txt.count("tpu_custom_call") == 2, (C, txt.count(
            "tpu_custom_call"))
        for fn in ("_rpa_pallas", "_kv_write_pallas"):
            assert len(re.findall(r"call @%s\b" % fn, txt)) == n_layers
    assert traced == {"ragged_paged_attention": len(widths),
                      "paged_kv_write": len(widths)}


def test_two_cache_groups_trace_the_attention_body_once_each(monkeypatch):
    """An `afmoe` engine (four sliding layers, one full) traces the
    attention kernel's body once a (width, cache group): the group's
    layers differ in the layer scalar alone."""
    import mxnet_tpu as mx
    from mxnet_tpu.models.afmoe import AfmoeConfig, AfmoeForCausalLM
    from mxnet_tpu.serve import InferenceEngine, ServeConfig
    m = AfmoeForCausalLM(AfmoeConfig(
        vocab_size=512, hidden_size=64, num_layers=5, num_heads=4,
        num_kv_heads=2, head_dim=16, intermediate_size=128,
        moe_intermediate_size=32, num_dense_layers=1, num_experts=8,
        num_experts_per_tok=2, layer_types=["sliding_attention"] * 4
        + ["full_attention"], sliding_window=8, max_position=256,
        experts_held=(2, 4), vocab_rows=(128, 96)))
    m.initialize()
    monkeypatch.setenv("MXTPU_PALLAS", "kernel")
    eng = InferenceEngine(m, ServeConfig(max_slots=2, page_size=8,
                                         prefill_chunk=16, max_len=64))
    traced = _count_kernel_bodies(monkeypatch)
    for C in eng._step_widths():
        txt = eng._step_fn(C).trace(*eng._step_avals(C)).lower(
            lowering_platforms=("tpu",)).as_text()
        assert "_rpa_pallas" in txt
    assert traced["ragged_paged_attention"] == 2 * len(eng._step_widths())
    assert traced["paged_kv_write"] == 2 * len(eng._step_widths())


@pytest.fixture(scope="module")
def v5e_chip():
    """One described (not attached) v5e chip to compile for; the test that
    asks is skipped where no such topology can be described."""
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


# the published widths of the benchmark's `afmoe` configuration: 48 query /
# 8 KV heads of 128, experts 3072 -> 2 x 3072 -> 3072, 16 held, pages of 128
AFMOE = dict(H=48, Hkv=8, D=128, E=3072, F=3072, held=16, window=4095)


@pytest.mark.parametrize("sliding", [False, True], ids=["full", "sliding"])
@pytest.mark.parametrize("C", [1, 16])
def test_paged_attention_kernel_lowers_at_afmoe_widths(C, sliding):
    """Head dim 128, six queries a KV head, a 128-page table; the sliding
    group's call walks a work list of 34 pages a slot."""
    from mxnet_tpu.ops.pallas.paged_attention import ragged_paged_attention
    from mxnet_tpu.serve.kv_cache import live_page_items, window_walk_pages
    H, Hkv, D = (AFMOE[k] for k in ("H", "Hkv", "D"))
    B, ps, maxp = 32, 128, 128
    q = jnp.ones((B, H, C, D), jnp.bfloat16)
    pool = jnp.ones((4, Hkv, 64, ps, D), jnp.bfloat16)
    pt = jnp.zeros((B, maxp), jnp.int32)
    ctx = jnp.full((B,), 9000, jnp.int32)
    walk = window_walk_pages(AFMOE["window"], 16, ps)
    assert walk == 34

    def f(q, kp, vp, pt, ctx, start):
        more = dict(window=AFMOE["window"], work_list=live_page_items(
            ctx, start, AFMOE["window"], ps, walk)) if sliding else {}
        return ragged_paged_attention(q, kp, vp, pt, ctx, start,
                                      use_kernel=True, layer=3, **more)

    txt = _lower_for_tpu(f, q, pool, pool, pt, ctx, ctx - C)
    assert txt.count("tpu_custom_call") == 1


@pytest.mark.parametrize("page_in_lanes", [False, True],
                         ids=["rows_in_sublanes", "rows_in_lanes"])
@pytest.mark.parametrize("C", [1, 16])
@pytest.mark.parametrize("widths", ["gpt2", "afmoe_full", "afmoe_sliding"])
def test_paged_attention_kernel_compiles_for_v5e_with_its_dynamic_grid(
        v5e_chip, widths, C, page_in_lanes):
    """`ragged_paged_attention` with its grid bound a traced count of live
    (slot, page) pairs and all kv heads of a page one block, compiled by
    Mosaic and XLA:TPU for the described chip at both configurations'
    widths (GPT-2-small: 64 slots, 12 heads of 64, f32 queries over a
    bf16 pool, a table of 8; afmoe: 32 slots, 48 query / 8 kv heads of
    128, a table of 128 and the sliding group's 34) in both page
    orientations: one custom call named for the metric that reads it,
    the online-softmax state of every head in scoped VMEM."""
    from mxnet_tpu.ops.pallas.paged_attention import ragged_paged_attention
    from mxnet_tpu.serve.kv_cache import live_page_items, window_walk_pages
    if widths == "gpt2":
        B, maxp, qdt, window = 64, 8, jnp.float32, None
        H, Hkv, D = (GPT2_SMALL[k] for k in ("H", "Hkv", "D"))
    else:
        B, maxp, qdt = 32, 128, jnp.bfloat16
        H, Hkv, D = (AFMOE[k] for k in ("H", "Hkv", "D"))
        window = AFMOE["window"] if widths == "afmoe_sliding" else None
    ps = 128

    def described(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=v5e_chip)

    def f(q, kp, vp, pt, ctx, start):
        work_list = None if window is None else live_page_items(
            ctx, start, window, ps, window_walk_pages(window, 16, ps))
        return ragged_paged_attention(
            q, kp, vp, pt, ctx, start, window=window, use_kernel=True,
            layer=1, page_in_lanes=page_in_lanes, work_list=work_list)

    pool = described((2, Hkv, 65, ps, D), jnp.bfloat16)
    compiled = jax.jit(f).trace(
        described((B, H, C, D), qdt), pool, pool,
        described((B, maxp), jnp.int32), described((B,), jnp.int32),
        described((B,), jnp.int32)).lower(
            lowering_platforms=("tpu",)).compile()
    txt = compiled.as_text()
    assert txt.count("tpu_custom_call") == 1
    assert "ragged_paged_attention" in txt


@pytest.mark.parametrize("n_out", [2 * AFMOE["F"], AFMOE["E"]],
                         ids=["w13", "w2"])
def test_grouped_expert_matmul_compiles_for_v5e_at_afmoe_widths(v5e_chip,
                                                                n_out):
    """`mx_moe_gmm` with its dynamic grid bound, compiled by Mosaic and
    XLA:TPU for the described chip: the row buffer of a C = 16 step (512
    rows x 4 experts a token, every expert's run padded to whole tiles)
    against 16 stacked expert matrices, 3 MB slabs two deep in VMEM."""
    from mxnet_tpu.ops.pallas import moe_gmm as G
    R = G.padded_rows(512 * 4, AFMOE["held"])

    def described(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=v5e_chip)

    def f(xs, w, rows):
        return G.grouped_matmul(xs, w, rows, use_kernel=True)

    compiled = jax.jit(f).trace(
        described((R, AFMOE["E"]), jnp.bfloat16),
        described((AFMOE["held"], AFMOE["E"], n_out), jnp.bfloat16),
        described((AFMOE["held"],), jnp.int32)).lower(
            lowering_platforms=("tpu",)).compile()
    txt = compiled.as_text()
    assert "mx_moe_gmm" in txt and txt.count("custom-call") >= 1
    assert compiled.memory_analysis().temp_size_in_bytes < 64 * 2 ** 20


def test_expert_layers_share_one_lowered_grouped_matmul_a_shape(v5e_chip):
    """Four expert layers make the same two `mx_moe_gmm` calls (W1|W3,
    W2): the lowered module holds two Mosaic bodies, not eight, and the
    compiled one still names every call for the metrics that find them
    by name."""
    import re
    from mxnet_tpu.ops.pallas import moe_gmm as G
    E, F, held, layers = 256, 128, 4, 4
    R = G.padded_rows(64, held)

    def described(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=v5e_chip)

    def f(xs, w13, w2, rows):
        for li in range(layers):
            h = G.grouped_matmul(xs, w13[li], rows, use_kernel=True)
            xs = G.grouped_matmul(h[:, :F].astype(xs.dtype), w2[li], rows,
                                  use_kernel=True).astype(xs.dtype)
        return xs

    lowered = jax.jit(f).trace(
        described((R, E), jnp.bfloat16),
        described((layers, held, E, 2 * F), jnp.bfloat16),
        described((layers, held, F, E), jnp.bfloat16),
        described((held,), jnp.int32)).lower(lowering_platforms=("tpu",))
    assert lowered.as_text().count("tpu_custom_call") == 2
    names = set(re.findall(r"%(mx_moe_gmm(?:\.\d+)?) = ",
                           lowered.compile().as_text()))
    assert len(names) == 2 * layers


def test_compiled_serve_step_copies_no_pool_in_the_devices_layout(
        monkeypatch, v5e_chip):
    """Mosaic and XLA:TPU run at `.compile()`.  A v5e keeps a bf16
    (..., 128, 64) pool as ``{3,4,2,1,0}`` (the page's rows in lanes; my
    chip run, PR 28).  With the step's pools pinned to that layout at both
    ends and the kernels in that orientation, the compiled module of every
    width holds no `copy` of a pool-sized array (the logical transposes
    around the calls are bitcasts) and its temporaries are a fraction of a
    pool.  Fed the other orientation the same pools cost a whole-pool copy
    at each end of the step, which is what `pages_in_lanes` is for.  The
    pool here is one the engine cannot fold (3 kv heads of 64): a folded
    one keeps its rows in sublanes."""
    import re
    from jax.experimental.layout import Format, Layout
    n_layers = 3
    eng = _gpt2_width_engine(monkeypatch, True, n_layers)
    pool = eng.pools.arrays["k"]
    pool_fmt = Format(Layout(major_to_minor=(0, 1, 2, 4, 3)), v5e_chip)

    def described(x):
        fmt = pool_fmt if x.shape == pool.shape else v5e_chip
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=fmt)

    pool_copy = re.compile(
        r"= bf16\[%s\]\S* copy\(" % ",".join(map(str, pool.shape)))
    for C in eng._step_widths():
        fn = jax.jit(eng._step_fn(C).__wrapped__, donate_argnums=(1,),
                     out_shardings=((pool_fmt, pool_fmt), v5e_chip,
                                    v5e_chip))
        avals = jax.tree_util.tree_map(described, eng._step_avals(C))
        compiled = fn.trace(*avals).lower(
            lowering_platforms=("tpu",)).compile()
        txt = compiled.as_text()
        assert txt.count("tpu_custom_call") == 2 * n_layers
        # the layers share one lowered function a kernel, and the compiled
        # step still names each call for the metric that finds it by name
        for kernel in ("ragged_paged_attention", "paged_kv_write"):
            assert len(set(re.findall(
                r"%%(%s\.\d+|%s) = " % (kernel, kernel), txt))) == n_layers
        assert not pool_copy.search(txt), (C, pool_copy.search(txt))
        assert compiled.memory_analysis().temp_size_in_bytes \
            < pool.size * pool.dtype.itemsize // 4


def test_compiled_folded_serve_step_copies_no_pool(monkeypatch, v5e_chip):
    """GPT-2-small's pool folded two kv heads a 128-lane row, `(layers, 6,
    pages, 128, 128)`, pinned row-major at both ends of the step (the
    layout a v5e gives a minor dim of 128): the compiled module of every
    width holds the two paged calls a layer, named for the metrics that
    find them, and no `copy` of a pool-sized array."""
    import re
    from jax.experimental.layout import Format, Layout
    n_layers = 2
    eng = _gpt2_width_engine(monkeypatch, False, n_layers)
    pool = eng.pools.arrays["k"]
    assert eng.pools.heads_per_row == {"full": 2}
    pool_fmt = Format(Layout(major_to_minor=(0, 1, 2, 3, 4)), v5e_chip)

    def described(x):
        fmt = pool_fmt if x.shape == pool.shape else v5e_chip
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=fmt)

    pool_copy = re.compile(
        r"= bf16\[%s\]\S* copy\(" % ",".join(map(str, pool.shape)))
    for C in eng._step_widths():
        fn = jax.jit(eng._step_fn(C).__wrapped__, donate_argnums=(1,),
                     out_shardings=((pool_fmt, pool_fmt), v5e_chip,
                                    v5e_chip))
        avals = jax.tree_util.tree_map(described, eng._step_avals(C))
        txt = fn.trace(*avals).lower(
            lowering_platforms=("tpu",)).compile().as_text()
        assert txt.count("tpu_custom_call") == 2 * n_layers
        for kernel in ("ragged_paged_attention", "paged_kv_write"):
            assert len(set(re.findall(
                r"%%(%s\.\d+|%s) = " % (kernel, kernel), txt))) == n_layers
        assert not pool_copy.search(txt), (C, pool_copy.search(txt))


@pytest.mark.parametrize("C", [1, 16])
def test_folded_paged_kernels_compile_for_v5e_at_gpt2_widths(v5e_chip, C):
    """Both paged kernels over GPT-2-small's folded pool (12 kv heads of
    64, two a 128-lane row), 64 slots and a table of 8, compiled by Mosaic
    and XLA:TPU for the described chip: the write's block is one 16-row
    tile of the six row groups, the attention's one page of them."""
    from mxnet_tpu.ops.pallas.paged_attention import (paged_kv_write,
                                                      ragged_paged_attention)
    H, D, B, maxp, ps = 12, 64, 64, 8, 128

    def described(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=v5e_chip)

    def f(q, kn, vn, kp, vp, pt, ctx, start, nt):
        kp, vp = paged_kv_write(kp, vp, kn, vn, 1, pt, start, nt,
                                heads_per_row=2)
        return ragged_paged_attention(
            q, kp, vp, pt, ctx, start, use_kernel=True, layer=1,
            heads_per_row=2), kp, vp

    pool = described((2, H // 2, 65, ps, 2 * D), jnp.bfloat16)
    new = described((B, H, C, D), jnp.float32)
    ints = described((B,), jnp.int32)
    txt = jax.jit(f).trace(
        described((B, H, C, D), jnp.float32), new, new, pool, pool,
        described((B, maxp), jnp.int32), ints, ints, ints).lower(
            lowering_platforms=("tpu",)).compile().as_text()
    assert txt.count("tpu_custom_call") == 2
    assert "ragged_paged_attention" in txt and "paged_kv_write" in txt


def test_gspmd_mesh_step_takes_references_and_shard_map_keeps_kernels(
        monkeypatch):
    """jax refuses to lower a Mosaic kernel in a program GSPMD partitions
    over several devices ("cannot be automatically partitioned"), so a
    `ShardedTrainStep` on a multi-device mesh decides for the jnp
    references up front; inside a shard_map body kernels lower again."""
    from mxnet_tpu.ops import pallas as _pallas
    from mxnet_tpu.ops import attention as _att

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert _pallas.kernel_active() and _att._use_pallas()
    with _pallas.partitioned_by_gspmd(4):
        assert not _pallas.kernel_active() and not _att._use_pallas()
        with _pallas.per_shard():
            assert _pallas.kernel_active() and _att._use_pallas()
        with _pallas.partitioned_by_gspmd(1):      # one device: no GSPMD
            assert _pallas.kernel_active()
    assert _pallas.kernel_active()


def test_chip_smoke_refuses_to_run_off_the_chip(tmp_path):
    """`chip_smoke.py` anywhere but on a TPU: non-zero exit naming the
    platform it found, no result line."""
    import subprocess
    import sys
    root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"))
    proc = subprocess.run([sys.executable, "chip_smoke.py"], cwd=root,
                          env=env, capture_output=True, text=True,
                          timeout=120)
    assert proc.returncode != 0
    assert "'cpu'" in proc.stderr and "not a TPU" in proc.stderr
    assert '"ok"' not in proc.stdout
