"""Inference-serving tests: paged KV cache allocator, ragged paged
attention (dense-reference and Pallas-interpret parity), the
continuous-batching scheduler, int8 KV quantization, and the per-request
telemetry contract (docs/serving.md)."""
import os

import numpy as onp
import pytest

import mxnet_tpu as mx
from mxnet_tpu.base import MXNetError

pytestmark = pytest.mark.serve


def _tiny_model(**kw):
    from mxnet_tpu.models.gpt import GPTConfig, GPTForCausalLM
    cfg = dict(vocab_size=96, hidden_size=32, num_layers=2, num_heads=4,
               intermediate_size=64, max_position=64, dropout=0.0)
    cfg.update(kw)
    m = GPTForCausalLM(GPTConfig(**cfg))
    m.initialize()
    m(mx.np.array([[1, 2]], dtype="int32"))
    return m


def _ref_generate(m, prompt, n):
    ids = mx.np.array([prompt], dtype="int32")
    return onp.asarray(m.generate(ids, max_new_tokens=n)
                       .asnumpy())[0].tolist()


# ---------------------------------------------------------------------------
# page allocator
# ---------------------------------------------------------------------------

def test_page_allocator_alloc_free_recycle():
    from mxnet_tpu.serve import PageAllocator
    a = PageAllocator(num_pages=6, page_size=4)
    assert a.total_pages == 5          # page 0 reserved (null)
    p1 = a.alloc(2)
    p2 = a.alloc(3)
    assert sorted(p1 + p2) == [1, 2, 3, 4, 5]
    assert 0 not in p1 + p2
    assert a.alloc(1) is None          # exhausted -> backpressure, not raise
    a.free(p1)
    assert a.free_pages == 2
    # LIFO recycle: the just-freed pages come back first
    p3 = a.alloc(2)
    assert sorted(p3) == sorted(p1)
    a.free(p3)
    a.free(p2)
    assert a.free_pages == 5
    assert a.occupancy() == 0.0


def test_page_allocator_guards():
    from mxnet_tpu.serve import PageAllocator
    a = PageAllocator(num_pages=4, page_size=2)
    p = a.alloc(1)
    a.free(p)
    with pytest.raises(MXNetError, match="double free"):
        a.free(p)
    with pytest.raises(MXNetError, match="null page"):
        a.free([0])
    with pytest.raises(MXNetError, match=">= 2 pages"):
        PageAllocator(num_pages=1, page_size=2)
    assert a.pages_for(1) == 1 and a.pages_for(2) == 1 \
        and a.pages_for(3) == 2


@pytest.mark.parametrize("window", [None, 7])
def test_page_run_grows_releases_trims_and_frees_over_a_group(window):
    """What a slot holds in one cache group (`kv_cache.PageRun`), the one
    set of page mechanics both kinds of group run through: the run grows
    to hold a chunk, lets go what lies before the group's first live page
    (nothing, where the group keeps the whole context), is trimmed past a
    rolled-back cursor and is freed whole; the table row is the null page
    wherever no page is held, and every page ends in the allocator."""
    from mxnet_tpu.serve import PageAllocator
    from mxnet_tpu.serve.kv_cache import (CacheGroup, PageRun,
                                          window_first_page)
    ps, maxp = 4, 16
    alloc = PageAllocator(num_pages=maxp + 1, page_size=ps)
    group = CacheGroup("full" if window is None else "w", (0,), window,
                       maxp + 1, maxp, alloc)
    assert group.pool_names == (("k", "v") if window is None
                                else ("k_w", "v_w"))
    run = PageRun(group, maxp)

    def take(who, of):
        assert who is run and of is group
        got = alloc.alloc(1)
        return None if got is None else got[0]

    def check():
        row = onp.zeros(maxp, onp.int32)
        row[run.first:run.first + len(run.pages)] = run.pages
        assert (run.table == row).all() and 0 not in run.pages
        assert alloc.free_pages == alloc.total_pages - len(run.pages)

    cursor = 0
    for chunk in (5, 6, 1, 1, 16, 3, 1):        # prefill, decode, prefill
        released = run.release_before(cursor)
        first = 0 if window is None \
            else int(window_first_page(cursor, window, ps))
        assert run.first == first or not run.pages
        assert released == 0 or window is not None
        assert run.grow(cursor, alloc.pages_for(cursor + chunk), take, run)
        cursor += chunk
        # from the first page a query at the chunk's start could see to
        # the page of its last token, and no other
        assert (run.first, run.first + len(run.pages)) == \
            (first, alloc.pages_for(cursor))
        check()
    assert cursor == 33 and (run.first > 0) == (window is not None)

    # drafts rejected: the cursor rolls back, the pages past the one the
    # next token lands in go back
    held = list(run.pages)
    assert run.grow(cursor, alloc.pages_for(cursor + 9), take, run)
    assert len(run.pages) > len(held)
    run.trim(alloc.pages_for(cursor + 1))
    assert run.pages == held
    check()

    # a dry free list: the run keeps what it got and says so
    hoard = alloc.alloc(alloc.free_pages - 1)
    assert not run.grow(cursor, alloc.pages_for(cursor + 3 * ps), take, run)
    assert len(run.pages) == len(held) + 1
    alloc.free(hoard)
    check()

    run.free()
    assert run.pages == [] and not run.table.any()
    assert alloc.free_pages == alloc.total_pages


# ---------------------------------------------------------------------------
# ragged paged attention: paged-vs-dense numerical parity
# ---------------------------------------------------------------------------

def _paged_setup(rng, B, H, Hkv, C, D, ps, npages, maxp):
    import jax.numpy as jnp
    q = jnp.asarray(rng.randn(B, H, C, D), jnp.float32)
    kp = jnp.asarray(rng.randn(Hkv, npages, ps, D), jnp.float32)
    vp = jnp.asarray(rng.randn(Hkv, npages, ps, D), jnp.float32)
    # distinct physical pages per slot, shuffled (non-contiguous layout)
    perm = rng.permutation(npages - 1)[:B * maxp] + 1
    pt = jnp.asarray(perm.reshape(B, maxp), jnp.int32)
    return q, kp, vp, pt


def _dense_oracle(q, kp, vp, pt, ctx, start, window=None):
    """Straight-line numpy-style oracle: gather pages, mask, softmax."""
    import jax
    import jax.numpy as jnp
    B, H, C, D = q.shape
    Hkv, ps = kp.shape[0], kp.shape[2]
    maxp = pt.shape[1]
    L = maxp * ps
    # head-major pool (Hkv, pages, ps, D) -> (B, Hkv, L, D)
    kc = kp[:, pt].reshape(Hkv, B, L, D).transpose(1, 0, 2, 3)
    vc = vp[:, pt].reshape(Hkv, B, L, D).transpose(1, 0, 2, 3)
    rep = H // Hkv
    kfull = jnp.repeat(kc, rep, axis=1)
    vfull = jnp.repeat(vc, rep, axis=1)
    s = jnp.einsum("bhcd,bhtd->bhct", q, kfull) / onp.sqrt(D)
    t_idx = jnp.arange(L)[None, None, None, :]
    pos = (start[:, None] + jnp.arange(C))[:, None, :, None]
    mask = (t_idx <= pos) & (t_idx < ctx[:, None, None, None])
    if window is not None:
        mask = mask & (t_idx >= pos - window)
    s = jnp.where(mask, s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhct,bhtd->bhcd", p, vfull)


@pytest.mark.parametrize("C,Hkv", [(4, 4), (4, 2), (1, 4), (1, 1)])
def test_paged_reference_matches_dense_oracle(C, Hkv):
    """Reference paged attention == dense full-gather attention for mixed
    ragged lengths (prefill C=4 and decode C=1, MHA and GQA)."""
    import jax.numpy as jnp
    from mxnet_tpu.ops.pallas.paged_attention import \
        paged_attention_reference
    rng = onp.random.RandomState(0)
    B, H, D, ps, npages, maxp = 3, 4, 16, 4, 16, 4
    q, kp, vp, pt = _paged_setup(rng, B, H, Hkv, C, D, ps, npages, maxp)
    start = jnp.asarray([0, 7, 12], jnp.int32)
    nt = jnp.asarray([C, max(1, C - 2), 1], jnp.int32)
    ctx = start + nt
    out = paged_attention_reference(q, kp, vp, pt, ctx, start)
    ref = _dense_oracle(q, kp, vp, pt, ctx, start)
    for b in range(B):
        n = int(nt[b])
        onp.testing.assert_allclose(out[b, :, :n], ref[b, :, :n],
                                    rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("window", [None, 3])
def test_paged_kernel_matches_reference_interpret(window, monkeypatch):
    """The Pallas kernel (interpret mode: exact kernel code on CPU) must
    match the reference path — mixed prefill+decode in one launch, GQA
    folding, page-table indirection, causal + sliding-window masks."""
    import jax.numpy as jnp
    from mxnet_tpu.ops.pallas import paged_attention as pa
    monkeypatch.setenv("MXTPU_PALLAS_INTERPRET", "1")
    rng = onp.random.RandomState(1)
    B, H, Hkv, C, D, ps, npages, maxp = 3, 4, 2, 4, 16, 8, 16, 4
    q, kp, vp, pt = _paged_setup(rng, B, H, Hkv, C, D, ps, npages, maxp)
    start = jnp.asarray([0, 5, 17], jnp.int32)
    nt = jnp.asarray([4, 4, 1], jnp.int32)
    ctx = start + nt
    ref = pa.paged_attention_reference(q, kp, vp, pt, ctx, start,
                                       window=window)
    out = pa.ragged_paged_attention(q, kp, vp, pt, ctx, start,
                                    window=window, use_kernel=True)
    for b in range(B):
        n = int(nt[b])
        onp.testing.assert_allclose(out[b, :, :n], ref[b, :, :n],
                                    rtol=2e-5, atol=2e-5)


def test_untileable_page_size_is_an_error_on_the_kernel_route(monkeypatch):
    """page_size > 128 but not a multiple of 128 cannot tile the kernel's
    lane-replicated stats: where the kernel is the route (a TPU, or
    MXTPU_PALLAS=kernel) that is a configuration error, never a silent
    detour through the dense-gather reference."""
    import jax.numpy as jnp
    from mxnet_tpu.ops.pallas import paged_attention as pa
    monkeypatch.setenv("MXTPU_PALLAS", "kernel")
    monkeypatch.setenv("MXTPU_PALLAS_INTERPRET", "1")
    rng = onp.random.RandomState(4)
    q, kp, vp, pt = _paged_setup(rng, 2, 2, 2, 1, 8, 192, 5, 2)
    start = jnp.asarray([0, 3], jnp.int32)
    with pytest.raises(ValueError, match="cannot tile page_size=192"):
        pa.ragged_paged_attention(q, kp, vp, pt, start + 1, start)


# ---------------------------------------------------------------------------
# int8 KV quantization
# ---------------------------------------------------------------------------

def test_int8_kv_roundtrip_tolerance():
    import jax.numpy as jnp
    from mxnet_tpu.contrib.quantization import quantize_kv, dequantize_kv
    rng = onp.random.RandomState(0)
    x = jnp.asarray(rng.randn(12, 3, 16) * 4.0, jnp.float32)
    q, s = quantize_kv(x)
    assert q.dtype == jnp.int8 and s.shape == (12, 3)
    rt = dequantize_kv(q, s)
    # symmetric per-vector int8: worst-case error is half an LSB of the
    # per-vector scale
    amax = onp.abs(onp.asarray(x)).max(axis=-1, keepdims=True)
    assert float(jnp.max(jnp.abs(rt - x) / amax)) <= 0.5 / 127 + 1e-6
    # zero vectors round-trip to zero (no div-by-zero scale)
    zq, zs = quantize_kv(jnp.zeros((3, 4)))
    assert float(jnp.max(jnp.abs(dequantize_kv(zq, zs)))) == 0.0


def test_int8_engine_decodes_closely():
    from mxnet_tpu.serve import InferenceEngine, ServeConfig
    m = _tiny_model(num_layers=1)
    prompt = [3, 9, 1, 7, 2]
    ref = _ref_generate(m, prompt, 6)
    eng = InferenceEngine(m, ServeConfig(max_slots=2, page_size=8,
                                         prefill_chunk=4, max_len=32,
                                         kv_dtype="int8"))
    import jax.numpy as jnp
    assert eng.quantized
    assert eng.pools.arrays["k"].dtype == jnp.int8
    assert "k_scale" in eng.pools.arrays
    out = eng.generate(prompt, max_new_tokens=6)
    # int8 KV is lossy: require the prompt intact, in-vocab tokens, and
    # strong-but-not-exact agreement with fp32 decode
    assert out[:len(prompt)] == prompt
    assert all(0 <= t < 96 for t in out)
    agree = sum(a == b for a, b in zip(out, ref)) / len(ref)
    assert agree >= 0.75, (out, ref)


# ---------------------------------------------------------------------------
# engine + scheduler
# ---------------------------------------------------------------------------

def test_engine_single_request_matches_generate():
    from mxnet_tpu.serve import InferenceEngine, ServeConfig
    m = _tiny_model()
    eng = InferenceEngine(m, ServeConfig(max_slots=2, page_size=8,
                                         prefill_chunk=4, max_len=32))
    for prompt in ([5], [3, 9, 1, 7, 2], list(range(10))):
        ref = _ref_generate(m, prompt, 7)
        assert eng.generate(prompt, max_new_tokens=7) == ref


def test_engine_concurrent_streaming_order_and_parity():
    """Mixed prompt lengths decode concurrently; each request's streamed
    tokens arrive in generation order and match its unbatched run."""
    from mxnet_tpu.serve import InferenceEngine, ServeConfig
    m = _tiny_model()
    rng = onp.random.RandomState(3)
    prompts = [rng.randint(0, 96, n).tolist() for n in (2, 7, 11, 4)]
    refs = [_ref_generate(m, p, 5) for p in prompts]
    eng = InferenceEngine(m, ServeConfig(max_slots=4, page_size=4,
                                         prefill_chunk=4, max_len=32))
    streams = {i: [] for i in range(len(prompts))}
    handles = [eng.submit(p, max_new_tokens=5,
                          on_token=lambda t, r, i=i: streams[i].append(t))
               for i, p in enumerate(prompts)]
    eng.run_until_idle()
    for i, (h, ref) in enumerate(zip(handles, refs)):
        assert h.result(timeout=0) == ref
        assert streams[i] == ref[len(prompts[i]):]
        assert h.state == "finished" and h.done()


@pytest.mark.parametrize("page_in_lanes", [False, True],
                         ids=["rows_in_sublanes", "rows_in_lanes"])
def test_engine_kernel_route_streams_equal_reference_route(page_in_lanes,
                                                           monkeypatch):
    """Greedy streams on the kernel route (interpret mode: `paged_kv_write`
    then `ragged_paged_attention`, in either page orientation) equal the
    reference route's (XLA scatter + dense gather): prompts whose prefill
    chunks straddle pages, decode across a page boundary, an idle slot."""
    from mxnet_tpu.ops.pallas import paged_attention as pa
    from mxnet_tpu.serve import InferenceEngine, ServeConfig
    from mxnet_tpu.serve.kv_cache import KVPools
    m = _tiny_model()
    rng = onp.random.RandomState(5)
    prompts = [rng.randint(0, 96, n).tolist() for n in (3, 13, 6)]
    sc = dict(max_slots=4, page_size=8, prefill_chunk=5, max_len=32)

    def streams():
        eng = InferenceEngine(m, ServeConfig(**sc))
        hs = [eng.submit(p, max_new_tokens=9) for p in prompts]
        eng.run_until_idle()
        return [h.result(timeout=0) for h in hs]

    want = streams()
    assert want == [_ref_generate(m, p, 9) for p in prompts]

    writes = []
    kernel_write = pa.paged_kv_write

    def counted(*a, **kw):
        writes.append(kw["page_in_lanes"])
        return kernel_write(*a, **kw)

    monkeypatch.setenv("MXTPU_PALLAS", "kernel")
    monkeypatch.setenv("MXTPU_PALLAS_INTERPRET", "1")
    monkeypatch.setattr(pa, "paged_kv_write", counted)
    monkeypatch.setattr(KVPools, "pages_in_lanes",
                        lambda self: page_in_lanes)
    assert streams() == want
    # one write a layer a compiled width, all in the pool's orientation
    assert len(writes) >= 2 and set(writes) == {page_in_lanes}


# a head dim under 128 lanes that divides them: 128 / D kv heads a pool row
_FOLDED = {"g2": dict(hidden_size=128, num_heads=2),      # D = 64
           "g4": dict(hidden_size=128, num_heads=4)}      # D = 32


@pytest.mark.parametrize("fold", sorted(_FOLDED))
def test_folded_pool_engine_streams_equal_generate_on_both_routes(
        fold, monkeypatch):
    """A model whose kv heads fold (`kv_cache.kv_heads_per_row`: g = 2
    for D = 64, 4 for D = 32) serves from a `(layers, Hkv / g, pages,
    page_size, 128)` pool on the reference route (XLA scatter + gather,
    which read the fold) and on the kernel route (interpret mode: the
    write and the attention with ``heads_per_row = g``), and both stream
    what unbatched `generate` does: prefill chunks that straddle pages,
    decode across a page boundary, an idle slot."""
    from mxnet_tpu.ops.pallas import paged_attention as pa
    from mxnet_tpu.serve import InferenceEngine, ServeConfig
    m = _tiny_model(**_FOLDED[fold])
    g = int(fold[1])
    rng = onp.random.RandomState(6)
    prompts = [rng.randint(0, 96, n).tolist() for n in (3, 21, 6)]
    want = [_ref_generate(m, p, 14) for p in prompts]
    sc = ServeConfig(max_slots=4, page_size=16, prefill_chunk=5, max_len=48)

    def streams():
        eng = InferenceEngine(m, sc)
        assert eng.pools.heads_per_row == {"full": g}
        assert eng.pools.arrays["k"].shape == (2, m.cfg.num_heads // g,
                                               eng.pools.num_pages, 16, 128)
        assert eng.stats()["kv_heads_per_row_full"] == g
        hs = [eng.submit(p, max_new_tokens=14) for p in prompts]
        eng.run_until_idle()
        return [h.result(timeout=0) for h in hs]

    assert streams() == want
    folds = []
    kernel_write = pa.paged_kv_write

    def counted(*a, **kw):
        folds.append(kw["heads_per_row"])
        return kernel_write(*a, **kw)

    monkeypatch.setenv("MXTPU_PALLAS", "kernel")
    monkeypatch.setenv("MXTPU_PALLAS_INTERPRET", "1")
    monkeypatch.setattr(pa, "paged_kv_write", counted)
    assert streams() == want
    assert len(folds) >= 2 and set(folds) == {g}


def test_folded_pool_copy_page_and_page_export_install_round_trip():
    """On a pool folded two kv heads a row the page axis is still axis 2:
    `copy_page` copies one page of every layer and nothing else;
    `export_pages` hands over one kv head a row (the payload a handoff
    ships, whatever either side's fold), the pool's own pages unfolded;
    `install_pages` of that payload into another engine lands the same
    bytes, which export again unchanged."""
    import jax
    import jax.numpy as jnp
    from mxnet_tpu.ops.pallas.paged_attention import unfold_heads
    from mxnet_tpu.serve import InferenceEngine, ServeConfig
    m = _tiny_model(**_FOLDED["g2"])
    sc = ServeConfig(max_slots=2, page_size=16, max_len=48)
    e1, e2 = InferenceEngine(m, sc), InferenceEngine(m, sc)
    rng = onp.random.RandomState(2)
    for name, a in e1.pools.arrays.items():
        e1.pools.arrays[name] = jnp.asarray(rng.standard_normal(a.shape),
                                            a.dtype)
    before = {n: onp.asarray(a) for n, a in e1.pools.arrays.items()}
    e1.copy_page(3, 5)
    for n, a in e1.pools.arrays.items():
        a = onp.asarray(a)
        onp.testing.assert_array_equal(a[:, :, 5], before[n][:, :, 3])
        others = onp.arange(a.shape[2]) != 5
        onp.testing.assert_array_equal(a[:, :, others],
                                       before[n][:, :, others])
    out = e1.export_pages([2, 5])
    assert set(out) == {"k", "v"}
    for n, x in out.items():
        assert x.shape == (2, 2, 2, 16, 64)
        onp.testing.assert_array_equal(x, onp.asarray(unfold_heads(
            jax.device_get(e1.pools.arrays[n][:, :, jnp.asarray([2, 5])]),
            2)))
    e2.install_pages([4, 1], out)
    for n in out:
        onp.testing.assert_array_equal(
            onp.asarray(e2.pools.arrays[n])[:, :, [4, 1]],
            onp.asarray(e1.pools.arrays[n])[:, :, [2, 5]])
    back = e2.export_pages([4, 1])
    for n in out:
        onp.testing.assert_array_equal(back[n], out[n])


@pytest.mark.parametrize("fold,g_tp2", [("g2", 2), ("g4", 1)],
                         ids=["both_fold", "tp2_cuts_a_row"])
def test_tp_sharded_folded_pool_engine_bit_identical(fold, g_tp2):
    """tp = 2 over a pool that folds: with 2 kv heads of 64 a shard the
    shards keep whole rows (g = 2 on both engines); with 2 kv heads of 32
    a shard a row of four would be cut, so the tp = 2 engine keeps one
    head a row while tp = 1 folds four.  Greedy streams stay
    bit-identical to tp = 1 either way, and a page payload exported by
    one engine installs into the other."""
    from mxnet_tpu.serve import InferenceEngine, ServeConfig
    kw = dict(_FOLDED[fold], num_heads=4, hidden_size=256 if g_tp2 == 2
              else 128)
    m = _tiny_model(**kw)
    prompts = [[1, 2, 3, 4, 5], [7, 8, 9], [10, 20, 30, 40]]
    sc = dict(max_slots=2, page_size=16, max_len=48)
    e1 = InferenceEngine(m, ServeConfig(**sc, tp=1), seed=0)
    e2 = InferenceEngine(m, ServeConfig(**sc, tp=2), seed=0)
    assert e2.tp == 2
    assert e1.pools.heads_per_row == {"full": 128 * 4 // kw["hidden_size"]}
    assert e2.pools.heads_per_row == {"full": g_tp2}
    for p in prompts:
        assert e2.generate(p, 10, greedy=True) == \
            e1.generate(p, 10, greedy=True)
    # pages 1 and 2 hold what e1's streams wrote: hand them over
    pages = [1, 2]
    out = e1.export_pages(pages)
    e2.install_pages(pages, out)
    back = e2.export_pages(pages)
    for n in out:
        onp.testing.assert_array_equal(back[n], out[n])


def test_kv_write_bytes_counts_the_write_kernels_blocks(monkeypatch):
    """The ``kv_write_bytes`` tag on every traced ``serve.step``, and
    ``kv_heads_per_row_full`` on ``serve.compile``: on the kernel route
    the bytes `paged_kv_write`'s grid moves, reckoned here from the block
    shapes of a folded f32 pool (8-row tiles, 128 lanes, one row group):
    layers x slots x (tiles x (K, V) x (in, out) x block + K and V's new
    rows); on the reference route, which scatters, 0."""
    from mxnet_tpu import tracing
    from mxnet_tpu.serve import InferenceEngine, ServeConfig
    m = _tiny_model(**_FOLDED["g2"])
    sc = ServeConfig(max_slots=3, page_size=16, prefill_chunk=4, max_len=48)

    def tags():
        tracing.reset()
        tracing.enable()
        try:
            eng = InferenceEngine(m, sc)
            eng.warmup()
            for p in ([1, 2, 3, 4, 5, 6, 7], [9, 8]):
                eng.submit(p, max_new_tokens=3)
            eng.run_until_idle()
            spans = tracing.get_tracer("serve").spans()
        finally:
            tracing.disable()
            tracing.reset()
        compiles = [s.tags for s in spans if s.name == "serve.compile"]
        assert compiles and all(t["kv_heads_per_row_full"] == 2
                                for t in compiles)
        return {s.tags["chunk"]: s.tags["kv_write_bytes"]
                for s in spans if s.name == "serve.step"}

    assert tags() == {1: 0, 4: 0}
    monkeypatch.setenv("MXTPU_PALLAS", "kernel")
    monkeypatch.setenv("MXTPU_PALLAS_INTERPRET", "1")
    layers, slots, rows, lanes, tile, f32 = 2, 3, 1, 128, 8, 4

    def reckoned(C):
        tiles = (C + tile - 2) // tile + 1
        block = rows * tile * lanes * f32
        new = rows * C * lanes * f32
        return layers * slots * (tiles * 2 * 2 * block + 2 * new)

    assert tags() == {1: reckoned(1), 4: reckoned(4)}
    assert reckoned(1) == 2 * 3 * (16384 + 1024)


def test_scheduler_admit_fifo_and_evict_youngest():
    """Admission is FIFO; page pressure evicts the YOUNGEST-admitted
    active (recompute preemption), which re-queues at the front and
    still completes correctly."""
    from mxnet_tpu.serve import InferenceEngine, ServeConfig
    m = _tiny_model(num_layers=1)
    p1, p2, p3 = [3, 9, 1, 7], [5, 2, 8], [4, 4]
    refs = [_ref_generate(m, p, 10) for p in (p1, p2, p3)]
    # one full-length sequence (14 tokens / ps 2 = 7 pages) nearly fills
    # the 8 allocatable pages: overlapping decodes must evict
    eng = InferenceEngine(m, ServeConfig(max_slots=2, page_size=2,
                                         num_pages=9, prefill_chunk=4,
                                         max_len=16))
    h1 = eng.submit(p1, max_new_tokens=10)
    h2 = eng.submit(p2, max_new_tokens=10)
    h3 = eng.submit(p3, max_new_tokens=10)
    eng.step()
    # FIFO: the first two submissions hold the two slots
    assert h1.state == "running" and h2.state == "running"
    assert h3.state == "queued"
    eng.run_until_idle()
    # eviction hit the younger of the colliding actives, never the oldest
    assert h1.evictions == 0
    assert h2.evictions + h3.evictions >= 1
    for h, ref in zip((h1, h2, h3), refs):
        assert h.result(timeout=0) == ref


def test_oom_admission_backpressure_and_validation():
    from mxnet_tpu.serve import InferenceEngine, ServeConfig
    m = _tiny_model(num_layers=1)
    eng = InferenceEngine(m, ServeConfig(max_slots=2, page_size=4,
                                         num_pages=4, prefill_chunk=4,
                                         max_len=16))
    # 3 allocatable pages = 12 tokens of KV; a request that cannot EVER
    # fit fails fast at submit
    with pytest.raises(MXNetError, match="KV pages"):
        eng.submit(list(range(8)), max_new_tokens=6)    # 14 tok -> 4 pages
    with pytest.raises(MXNetError, match="context cap"):
        eng.submit(list(range(12)), max_new_tokens=10)  # > max_len
    with pytest.raises(MXNetError, match="empty prompt"):
        eng.submit([], max_new_tokens=1)
    with pytest.raises(MXNetError, match="max_new_tokens"):
        eng.submit([1, 2], max_new_tokens=0)
    # a request that fits alone but not beside the running one waits in
    # the queue (admission backpressure), then runs after the first frees
    h1 = eng.submit(list(range(6)), max_new_tokens=4)   # 10 tok -> 3 pages
    h2 = eng.submit(list(range(4)), max_new_tokens=4)   # 8 tok -> 2 pages
    eng.step()
    assert h1.state == "running" and h2.state == "queued"
    eng.run_until_idle()
    assert h1.state == "finished" and h2.state == "finished"
    assert len(h1.tokens) == 4 and len(h2.tokens) == 4


def test_eos_token_stops_decode():
    from mxnet_tpu.serve import InferenceEngine, ServeConfig
    m = _tiny_model(num_layers=1)
    prompt = [3, 9, 1]
    ref = _ref_generate(m, prompt, 12)
    gen = ref[len(prompt):]
    eng = InferenceEngine(m, ServeConfig(max_slots=2, page_size=8,
                                         prefill_chunk=4, max_len=32))
    # eos never generated -> runs to max_new_tokens
    never = next(t for t in range(96) if t not in gen)
    h = eng.submit(prompt, max_new_tokens=12, eos_token_id=never)
    eng.run_until_idle()
    assert h.tokens == gen
    # eos == the first generated token -> stops immediately after it
    h2 = eng.submit(prompt, max_new_tokens=12, eos_token_id=gen[0])
    eng.run_until_idle()
    assert h2.tokens == gen[:1]


def test_failed_step_fails_all_requests(monkeypatch):
    """A device-step exception must not strand waiters: every active and
    queued request flips to 'failed', result() raises, pages return to
    the free list, and the exception still propagates to the caller."""
    from mxnet_tpu.serve import InferenceEngine, ServeConfig
    m = _tiny_model(num_layers=1)
    eng = InferenceEngine(m, ServeConfig(max_slots=1, page_size=8,
                                         prefill_chunk=4, max_len=32))
    h1 = eng.submit([3, 9, 1], max_new_tokens=4)
    h2 = eng.submit([5, 2], max_new_tokens=4)   # waits in the queue

    def boom(*a, **kw):
        raise RuntimeError("device exploded")

    monkeypatch.setattr(eng, "_execute", boom)
    with pytest.raises(RuntimeError, match="device exploded"):
        eng.step()
    for h in (h1, h2):
        assert h.state == "failed" and h.done()
        with pytest.raises(MXNetError, match="device exploded"):
            h.result(timeout=0)
    assert eng.allocator.free_pages == eng.allocator.total_pages


def test_serve_config_env_knobs(monkeypatch):
    from mxnet_tpu.serve import ServeConfig
    monkeypatch.setenv("MXTPU_SERVE_SLOTS", "3")
    monkeypatch.setenv("MXTPU_SERVE_PAGE_SIZE", "32")
    monkeypatch.setenv("MXTPU_SERVE_PREFILL_CHUNK", "8")
    monkeypatch.setenv("MXTPU_SERVE_MAX_LEN", "48")
    monkeypatch.setenv("MXTPU_SERVE_KV_DTYPE", "int8")
    sc = ServeConfig()
    assert (sc.max_slots, sc.page_size, sc.prefill_chunk, sc.max_len,
            sc.kv_dtype) == (3, 32, 8, 48, "int8")
    with pytest.raises(MXNetError):
        ServeConfig(max_slots=0)


# ---------------------------------------------------------------------------
# telemetry contract
# ---------------------------------------------------------------------------

def test_telemetry_emitted_per_request(tmp_path):
    from mxnet_tpu import telemetry as tele
    from mxnet_tpu.serve import InferenceEngine, ServeConfig
    m = _tiny_model(num_layers=1)
    journal = str(tmp_path / "serve.jsonl")
    tele.enable(journal_path=journal)
    try:
        reg = tele.registry()
        ttft0 = (reg.get("serve_ttft_ms").count()
                 if "serve_ttft_ms" in reg else 0)
        fin0 = (reg.get("serve_requests_total").value(state="finished")
                if "serve_requests_total" in reg else 0)
        eng = InferenceEngine(m, ServeConfig(max_slots=2, page_size=8,
                                             prefill_chunk=4, max_len=32))
        h1 = eng.submit([3, 9, 1], max_new_tokens=4)
        h2 = eng.submit([5, 2], max_new_tokens=4)
        eng.run_until_idle()
        assert h1.done() and h2.done()
        snap = tele.snapshot()
        assert reg.get("serve_ttft_ms").count() == ttft0 + 2
        assert reg.get("serve_request_latency_ms").count() >= 2
        assert reg.get("serve_requests_total").value(
            state="finished") == fin0 + 2
        assert reg.get("serve_tokens_generated_total").value() >= 8
        assert "serve_page_occupancy_ratio" in snap
        assert "serve_step_ms" in snap
        rows = tele.RunJournal.read(journal)
        req_rows = [r for r in rows if r.get("event") == "request"]
        by_id = {}
        for r in req_rows:
            by_id.setdefault(r["request_id"], []).append(r["phase"])
        assert set(by_id) == {h1.id, h2.id}
        for phases in by_id.values():
            for needed in ("submitted", "admitted", "first_token",
                           "finished"):
                assert needed in phases
        # the serving loop feeds the hang watchdog's heartbeat table
        from mxnet_tpu import health
        assert "serve.step" in health.heartbeat_ages()
    finally:
        tele.disable()


def test_kv_pools_donation_rebind():
    """The engine rebinds donated pool buffers each step — after a full
    request the pools object must still be usable (no deleted-buffer
    errors) and pages fully recycled."""
    from mxnet_tpu.serve import InferenceEngine, ServeConfig
    m = _tiny_model(num_layers=1)
    eng = InferenceEngine(m, ServeConfig(max_slots=2, page_size=4,
                                         prefill_chunk=4, max_len=16))
    eng.generate([1, 2, 3], max_new_tokens=4)
    eng.generate([4, 5], max_new_tokens=4)
    assert eng.allocator.free_pages == eng.allocator.total_pages
    # pool arrays are live (donation rebound correctly)
    assert eng.pools.arrays["k"].shape[0] == eng.cfg.num_layers
    float(eng.pools.arrays["k"].sum())   # would raise on a deleted buffer


# ---------------------------------------------------------------------------
# per-request deadlines (MXTPU_SERVE_DEADLINE_MS)
# ---------------------------------------------------------------------------

def test_deadline_expires_queued_and_active_requests():
    """A request past its deadline is expired whether it is still queued
    or already holds a slot — its pages return to the pool, waiters
    unblock with an error, and later requests are unaffected."""
    from mxnet_tpu.serve import InferenceEngine, ServeConfig
    m = _tiny_model(num_layers=1)
    eng = InferenceEngine(m, ServeConfig(max_slots=1, page_size=4,
                                         prefill_chunk=4, max_len=32,
                                         deadline_ms=10_000))
    h1 = eng.submit([1, 2, 3], max_new_tokens=8)
    h2 = eng.submit([4, 5], max_new_tokens=8)
    eng.step()
    assert h1.state == "running" and h2.state == "queued"
    # jump both requests past their 10s deadline (simulated stuck client)
    h1.submitted_ts -= 11.0
    h2.submitted_ts -= 11.0
    eng.step()
    assert h1.state == "failed" and h1.done()
    assert h2.state == "failed" and h2.done()
    with pytest.raises(MXNetError, match="deadline exceeded"):
        h1.result(timeout=0)
    with pytest.raises(MXNetError, match="deadline exceeded"):
        h2.result(timeout=0)
    # the expired active's pages were recycled -> a fresh request runs
    assert eng.allocator.free_pages == eng.allocator.total_pages
    h3 = eng.submit([6, 7], max_new_tokens=2)
    eng.run_until_idle()
    assert h3.state == "finished" and len(h3.tokens) == 2


def test_deadline_off_by_default_and_per_request_override():
    from mxnet_tpu.serve import InferenceEngine, ServeConfig
    m = _tiny_model(num_layers=1)
    eng = InferenceEngine(m, ServeConfig(max_slots=2, page_size=4,
                                         prefill_chunk=4, max_len=32))
    # config default 0 = unbounded: an ancient request still completes
    h1 = eng.submit([1, 2], max_new_tokens=2)
    h1.submitted_ts -= 3600.0
    # per-request override expires independently of the config default
    h2 = eng.submit([3, 4], max_new_tokens=2, deadline_ms=5_000)
    h2.submitted_ts -= 6.0
    eng.run_until_idle()
    assert h1.state == "finished"
    assert h2.state == "failed"


def _count_done_sets(req):
    """Instrument a request's completion event: every `set()` call is
    counted — the exactly-once contract says the total must be 1."""
    calls = []
    orig = req._done.set

    def counting():
        calls.append(1)
        orig()
    req._done.set = counting
    return calls


def _evict_mid_stream(eng, long_h, victim_h, max_steps=80):
    """Drive the engine until `victim_h` has been evicted and parked in
    the re-admission queue with streamed progress."""
    for _ in range(max_steps):
        eng.step()
        if victim_h.evictions >= 1 and victim_h.state == "queued":
            return
    raise AssertionError(
        f"victim was never evicted (evictions={victim_h.evictions}, "
        f"state={victim_h.state}) — pool sizing no longer forces "
        f"page pressure")


def _pressure_engine(m):
    """2 slots over a pool sized so two overlapping decodes MUST collide
    (the serve-smoke pressure recipe, shrunk)."""
    from mxnet_tpu.serve import InferenceEngine, ServeConfig
    eng = InferenceEngine(m, ServeConfig(max_slots=2, page_size=2,
                                         num_pages=9, prefill_chunk=4,
                                         max_len=16))
    eng.warmup()
    return eng


def test_deadline_expiry_evicted_requeued_exactly_once():
    """The deadline × eviction interplay (regression): a request whose
    deadline expires while PARKED in the re-admission queue after an
    eviction must release its pages (they went back at eviction — the
    pool must be whole afterwards, no double free) and unblock its
    waiter EXACTLY once, while the surviving stream is untouched."""
    m = _tiny_model(num_layers=1)
    eng = _pressure_engine(m)
    a = eng.submit([1, 2, 3], max_new_tokens=12)
    b = eng.submit([4, 5], max_new_tokens=12, deadline_ms=100_000)
    calls = _count_done_sets(b)
    _evict_mid_stream(eng, a, b)
    assert b.tokens, "victim should have streamed progress pre-eviction"
    # deadline lapses while parked in the re-admission queue
    b.submitted_ts -= 101.0
    eng.run_until_idle()
    assert b.state == "failed" and b.done()
    assert len(calls) == 1, f"waiter unblocked {len(calls)} times"
    with pytest.raises(MXNetError, match="deadline exceeded"):
        b.result(timeout=0)
    # the survivor finished normally; the pool is whole (eviction freed
    # b's pages once; expiry must not have freed anything again — the
    # allocator raises on double free, so reaching here proves it)
    assert a.state == "finished"
    assert len(a.tokens) == 12
    assert eng.allocator.free_pages == eng.allocator.total_pages


@pytest.mark.parametrize("where", ["queued", "active", "evicted"])
def test_deadline_expiry_exactly_once_in_every_state(where):
    """Expiry in queued / active / evicted-requeued states: one
    termination, one waiter unblock, one counter increment, pool whole."""
    from mxnet_tpu import telemetry as tele
    m = _tiny_model(num_layers=1)
    tele.enable()
    try:
        reg = tele.registry()

        def expired_count():
            c = reg.get("serve_deadline_expired_total")
            if c is None:
                return 0
            return sum(v for _, v in c._series())

        base = expired_count()
        eng = _pressure_engine(m)
        a = eng.submit([1, 2, 3], max_new_tokens=12)
        b = eng.submit([4, 5], max_new_tokens=12, deadline_ms=100_000)
        calls = _count_done_sets(b)
        if where == "queued":
            # b never admitted: slot pressure keeps it queued
            pass
        elif where == "active":
            for _ in range(30):
                eng.step()
                if b.state == "running":
                    break
            assert b.state == "running"
        else:
            _evict_mid_stream(eng, a, b)
        b.submitted_ts -= 101.0
        eng.run_until_idle()
        assert b.state == "failed" and b.done()
        assert len(calls) == 1, (where, len(calls))
        assert expired_count() == base + 1
        assert a.state == "finished"
        assert eng.allocator.free_pages == eng.allocator.total_pages
    finally:
        tele.disable()


def test_terminate_request_is_idempotent():
    """`terminate_request` is the ONE terminal path for non-finished
    outcomes; the first caller wins and every later call is a no-op —
    the guard that makes a scheduler sweep racing a router sweep safe."""
    from mxnet_tpu.serve.scheduler import ServeRequest, terminate_request
    req = ServeRequest([1, 2], max_new_tokens=4)
    calls = _count_done_sets(req)
    assert terminate_request(req, "first error", state="expired",
                             phase="deadline_expired") is True
    assert terminate_request(req, "second error", state="failed",
                             phase="failed") is False
    assert req.error == "first error"
    assert req.state == "failed" and len(calls) == 1


def test_deadline_env_knob_and_telemetry(monkeypatch, tmp_path):
    from mxnet_tpu import telemetry as tele
    from mxnet_tpu.serve import InferenceEngine, ServeConfig
    monkeypatch.setenv("MXTPU_SERVE_DEADLINE_MS", "7000")
    sc = ServeConfig()
    assert sc.deadline_ms == 7000
    m = _tiny_model(num_layers=1)
    journal = str(tmp_path / "deadline.jsonl")
    tele.enable(journal_path=journal)
    try:
        reg = tele.registry()
        base = (reg.get("serve_deadline_expired_total").value(where="queued")
                if "serve_deadline_expired_total" in reg else 0)
        eng = InferenceEngine(m, ServeConfig(max_slots=1, page_size=4,
                                             prefill_chunk=4, max_len=32,
                                             deadline_ms=7000))
        h1 = eng.submit([1, 2, 3], max_new_tokens=2)
        h2 = eng.submit([4, 5], max_new_tokens=2)
        h2.submitted_ts -= 8.0          # queued request goes stale
        eng.run_until_idle()
        assert h1.state == "finished" and h2.state == "failed"
        assert reg.get("serve_deadline_expired_total").value(
            where="queued") == base + 1
        import json
        rows = [json.loads(ln) for ln in open(journal) if ln.strip()]
        expired = [r for r in rows if r.get("event") == "request"
                   and r.get("phase") == "deadline_expired"]
        assert expired and expired[0]["request_id"] == h2.id
        assert expired[0]["where"] == "queued"
    finally:
        tele.disable()


# ---------------------------------------------------------------------------
# the packed launch: one host-to-device array and a device-carried key a step
# ---------------------------------------------------------------------------

def _tiny_afmoe():
    """Two cache groups (two window layers, one full) and an expert
    layer, small."""
    from mxnet_tpu.models.afmoe import AfmoeConfig, AfmoeForCausalLM
    m = AfmoeForCausalLM(AfmoeConfig(
        vocab_size=96, hidden_size=32, num_layers=3, num_heads=4,
        num_kv_heads=2, head_dim=8, intermediate_size=64,
        moe_intermediate_size=16, num_dense_layers=1, num_experts=4,
        num_experts_per_tok=2,
        layer_types=["sliding_attention"] * 2 + ["full_attention"],
        sliding_window=8, max_position=96))
    m.initialize()
    return m


def _launch_engines(case, n=1, seed=11):
    """`n` engines over ONE model, of one seed; the weights from a fixed
    seed too, so that the widths a run takes do not hang on the test's
    random one."""
    from mxnet_tpu.serve import InferenceEngine, ServeConfig
    mx.random.seed(7)
    m = _tiny_afmoe() if case == "afmoe_two_groups" else _tiny_model(
        max_position=96)
    return [InferenceEngine(m, ServeConfig(
        max_slots=2, page_size=4, prefill_chunk=4, max_len=96,
        spec_tokens=2 if case == "gpt2_spec" else 0), seed=seed)
        for _ in range(n)]


def _parents_launch(eng, widths):
    """`_execute` as it was before the launch was packed, over the same
    step body: the key split on the host, one `jnp.asarray` an array
    (seven; one more a further cache group), the sub-key handed in."""
    import jax
    import jax.numpy as jnp
    fns = {}

    def execute(tok, num_tokens, start_pos, tables, ctx_lens, temps,
                greedy_mask, C):
        widths.append(C)
        if C not in fns:
            fns[C] = jax.jit(eng._step_body(C), donate_argnums=(1,))
        eng._key, sub = jax.random.split(eng._key)
        out_pools, nxt, *rest = fns[C](
            eng.P, eng.pools.as_tuple(), jnp.asarray(tok),
            jnp.asarray(num_tokens), jnp.asarray(start_pos),
            tuple(map(jnp.asarray, tables)), jnp.asarray(ctx_lens),
            jnp.asarray(temps), jnp.asarray(greedy_mask), sub)
        eng.pools = eng.pools.replace(out_pools)
        all_tok = onp.asarray(rest.pop(0)) \
            if eng.serve_config.spec_tokens > 0 else None
        eng.last_moe_counts = onp.asarray(rest.pop(0)) if rest else None
        return onp.asarray(nxt), all_tok
    return execute


@pytest.mark.parametrize("case", ["gpt2", "afmoe_two_groups", "gpt2_spec"])
def test_packed_launch_streams_equal_the_parents_launch_bit_for_bit(case):
    """Greedy and sampled requests mixed, a fixed seed: the engine (one
    packed array, the key split inside the program and carried on the
    device) emits the streams of a reference that runs the same step body
    the way the parent launched it (host-side split chain, an array an
    input), over at least 20 steps of the chunk width and 20 of C = 1."""
    eng, ref = _launch_engines(case, 2)
    ref_widths, widths = [], []
    ref._execute = _parents_launch(ref, ref_widths)
    real = eng._execute
    eng._execute = lambda *a: (widths.append(a[-1]), real(*a))[1]
    rng = onp.random.RandomState(5)
    # the periodic prompt gives the n-gram drafter something to propose
    reqs = [(rng.randint(0, 96, 33).tolist(), 6, False, 0.8),
            ([7, 8, 9] * 7, 44, True, 1.0),
            (rng.randint(0, 96, 37).tolist(), 30, False, 1.3),
            (rng.randint(0, 96, 9).tolist(), 36, True, 1.0),
            (rng.randint(0, 96, 26).tolist(), 30, False, 0.6)]
    out = []
    for e in (eng, ref):
        hs = [e.submit(p, max_new_tokens=n, greedy=g, temperature=t)
              for p, n, g, t in reqs]
        e.run_until_idle()
        out.append([h.result(timeout=0) for h in hs])
    assert out[0] == out[1]
    assert widths == ref_widths
    assert widths.count(4) >= 20 and widths.count(1) >= 20, widths
    if case == "gpt2_spec":
        assert widths.count(3) >= 1, widths
    # the key the device carried is the host chain's, split by split
    assert onp.array_equal(onp.asarray(eng._key), onp.asarray(ref._key))
    # sampled streams did sample: they differ from their greedy runs
    assert out[0][0] != eng.generate(reqs[0][0], reqs[0][1])


@pytest.mark.parametrize("case", ["gpt2", "afmoe_two_groups", "gpt2_spec"])
def test_step_takes_weights_pools_one_packed_array_and_the_key(case):
    import jax
    import jax.numpy as jnp
    from mxnet_tpu import tracing
    eng, = _launch_engines(case)
    n_groups = 2 if case == "afmoe_two_groups" else 1
    assert len(eng.groups) == n_groups
    B, W = 2, eng.max_pages_per_seq
    rng = onp.random.RandomState(0)
    for C in eng._step_widths():
        P, pools, *rest = eng._step_avals(C)
        layout = eng._layout(C)
        packed, key = jax.tree_util.tree_leaves(rest)
        assert (packed.shape, packed.dtype) == ((layout.size,), jnp.int32)
        assert layout.size == B * (C + 5 + n_groups * W)
        assert (key.shape, key.dtype) == (eng._key.shape, eng._key.dtype)
        # the layout round-trips, `temps` by its bits and the bool mask
        x = (rng.randint(0, 96, (B, C)).astype(onp.int32),
             rng.randint(0, C + 1, B).astype(onp.int32),
             rng.randint(0, 90, B).astype(onp.int32),
             tuple(rng.randint(0, 50, (B, W)).astype(onp.int32)
                   for _ in range(n_groups)),
             rng.randint(0, 96, B).astype(onp.int32),
             onp.array([0.7, onp.float32(1) / 3], onp.float32),
             onp.array([True, False]))
        host = layout.pack(*x)
        assert (host.dtype, host.shape) == (onp.int32, (layout.size,))
        back = jax.jit(layout.unpack)(host)
        for a, b in zip(jax.tree_util.tree_leaves(x),
                        jax.tree_util.tree_leaves(back)):
            assert a.dtype == b.dtype and onp.array_equal(a, onp.asarray(b))
        with pytest.raises(MXNetError, match="layout"):
            layout.pack(x[0][:, :-1], *x[1:])
    # the launch span's counters: what the call was really handed
    tracing.disable()
    tracing.reset()
    tracing.enable()
    try:
        eng.generate(list(range(1, 8)), max_new_tokens=3)
        launches = [s for s in tracing.get_tracer("serve").spans()
                    if s.name == "serve.step.launch"]
    finally:
        tracing.disable()
        tracing.reset()
    assert {s.tags["h2d_arrays"] for s in launches} == {1}
    assert {s.tags["h2d_bytes"] for s in launches} <= {
        4 * eng._layout(C).size for C in eng._step_widths()}
    # (7 prompt tokens at chunk 4: two steps at the least; accepted drafts
    # can spare the rest)
    assert len(launches) == eng.stats()["steps_executed"] >= 2
    assert eng.launched_h2d == (launches[-1].tags["h2d_bytes"], 1)


def test_artifact_captured_before_the_packed_launch_is_refused(tmp_path):
    """A serve artifact whose step took its inputs one by one (as every
    capture before the packed launch did) no longer matches the step's
    avals: an explicit load raises, naming the leaf count."""
    import json
    eng, = _launch_engines("gpt2")
    path = eng.export(str(tmp_path / "art"))
    fresh, = _launch_engines("gpt2")
    fresh.load_export(path)                    # as captured: accepted
    mpath = os.path.join(path, "manifest.json")
    manifest = json.load(open(mpath))
    B, W = 2, eng.max_pages_per_seq
    n_old = 0
    for rec in manifest["modules"].values():
        C = rec["meta"]["chunk"]
        weights_and_pools = rec["in_avals"][:-2]
        rec["in_avals"] = weights_and_pools + [
            [[B, C], "int32"], [[B], "int32"], [[B], "int32"],
            [[B, W], "int32"], [[B], "int32"], [[B], "float32"],
            [[B], "bool"], [[2], "uint32"]]
        n_old = len(rec["in_avals"])
    json.dump(manifest, open(mpath, "w"))
    fresh, = _launch_engines("gpt2")
    with pytest.raises(MXNetError, match=f"captured with {n_old}"):
        fresh.warmup(artifact=path)
    assert not fresh._execs
