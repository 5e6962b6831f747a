"""`afmoe` (window and full attention layers mixed, gated QK-normed GQA,
sigmoid-routed experts with a shared expert) through the serving engine
against the plain float32 reference, at a small size on the CPU: hidden 64,
4/2 heads of 16, window 8, page 4, 8 experts top-2 + 1 shared, 1 dense + 4
expert layers, types s,s,s,s,f; seeded weights.

Every comparison here is float32 against float32 (the model's dtype is
float32 and `_mm` is exact for f32 weights), so what is left is the order of
summation: logits of size ~1 agree to 1e-4 with room (readings ~1e-5)."""
import dataclasses
import os

import numpy as onp
import pytest

import jax
import jax.numpy as jnp

import mxnet_tpu as mx

pytestmark = pytest.mark.serve

TOL = 1e-4
CFG = {
    "family": "afmoe", "hidden_size": 64, "head_dim": 16,
    "num_attention_heads": 4, "num_key_value_heads": 2,
    "intermediate_size": 128, "moe_intermediate_size": 32,
    "num_experts": 8, "num_experts_per_tok": 2, "num_shared_experts": 1,
    "route_scale": 2.448, "sliding_window": 8, "rope_theta": 10000,
    "rms_norm_eps": 1e-5, "max_position_embeddings": 256,
    "mup_enabled": True, "vocab_size": 96,
    "depth": {"num_hidden_layers": 5, "num_dense_layers": 1,
              "layer_types": ["sliding_attention"] * 4 + ["full_attention"]},
    "experts_held": {"first": 2, "count": 4},
    "vocabulary": {"first_row": 96, "rows": 96, "published": 768},
    "dtype": "float32"}


def _cfg(**over):
    c = dict(CFG)
    c.update(over)
    return c


def _model(cfg, seed=3):
    """(model, reference parameters): seeded weights under the family's
    names, set into the model and handed to the reference as they are."""
    from benchmark.families import afmoe as fam
    from benchmark.harness import weights as W
    from mxnet_tpu.models.afmoe import AfmoeForCausalLM
    model = AfmoeForCausalLM(fam.model_config(cfg))
    w = W.make(fam.param_spec(cfg), seed)
    params = model.collect_params()
    assert set(params) == set(w)
    for name, p in params.items():
        p.set_data(w[name])
    return model, W.as_float32(w)


def _engine(model, chunk, slots=2, max_len=96, **kw):
    from mxnet_tpu.serve import InferenceEngine, ServeConfig
    return InferenceEngine(model, ServeConfig(
        max_slots=slots, page_size=4, prefill_chunk=chunk, max_len=max_len,
        **kw))


def _ref_logits(p, cfg, seq):
    from benchmark.reference import afmoe as ref
    ids = jnp.asarray([seq], jnp.int32)
    pos = jnp.arange(len(seq))[None]
    return onp.asarray(ref.logits_at(p, cfg, ids, pos))[0]


@pytest.fixture(scope="module")
def built():
    return _model(CFG)


# ---------------------------------------------------------------------------
# (1) prefill-then-decode through the paged cache against the reference
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("chunk", [1, 3, 16])
def test_step_logits_match_the_reference_past_window_and_page(built, chunk):
    """Logits out of the step function: the decode core driven chunk by
    chunk over the two paged pools (prefill, then single tokens), past the
    window of 8 and past pages of 4, against the full forward."""
    from mxnet_tpu.serve.decode import (extract_decode_weights, lm_logits,
                                        transformer_step)
    from mxnet_tpu.serve import ServeConfig
    from mxnet_tpu.serve.kv_cache import (KVPools, make_paged_kv_fn,
                                          plan_cache_groups,
                                          window_first_page,
                                          window_walk_pages)
    model, p = built
    mcfg = model.cfg
    spec = mcfg.decode_spec()
    P = extract_decode_weights(model)
    seq = onp.random.default_rng(1).integers(0, 96, 41).tolist()
    want = _ref_logits(p, CFG, seq)
    ps, maxp = 4, 12
    groups = plan_cache_groups(
        spec, ServeConfig(max_slots=1, page_size=ps, num_pages=maxp + 1),
        maxp, 16)
    assert [(g.name, g.layers, g.window, g.num_pages, g.walk)
            for g in groups] == [
        ("full", (4,), None, maxp + 1, maxp),
        ("sliding", (0, 1, 2, 3), 7, window_walk_pages(7, 16, ps) + 1,
         window_walk_pages(7, 16, ps))]
    # every page of the one table is live here, so the windowed pool is
    # as large as the whole-context one
    groups = (groups[0], dataclasses.replace(groups[1],
                                             num_pages=maxp + 1))
    walks = {g.name: g.walk for g in groups}
    pools = KVPools.create(groups, ps, 2, 16)
    assert pools.full_names == ("k", "v")
    arrays = dict(pools.arrays)
    table = jnp.arange(1, maxp + 1, dtype=jnp.int32)[None]
    plan = spec.cache_plan()
    assert plan[0] == ("sliding", 0, 7) and plan[4] == ("full", 0, None)
    got, at = [], 0
    while at < len(seq):
        # 30 tokens of prefill in chunks, then single tokens
        c = min(chunk, 30 - at) if at < 30 else 1
        tok = jnp.asarray([seq[at:at + c]], jnp.int32)
        start = jnp.asarray([at], jnp.int32)
        # the sliding table with the pages behind the window taken out,
        # as the scheduler hands it over
        first = int(window_first_page(at, 7, ps))
        stable = table.at[0, :first].set(0)
        kv_fn = make_paged_kv_fn(
            arrays, {"full": table, "sliding": stable}, start,
            jnp.asarray([c], jnp.int32), jnp.asarray([at + c], jnp.int32),
            ps, False, layer_plan=plan, walks=walks)
        pos = start[:, None] + jnp.arange(c)[None]
        h = transformer_step(P, mcfg, tok, pos, kv_fn)
        got.append(onp.asarray(lm_logits(P, h, cast_inputs=True))[0])
        at += c
    got = onp.concatenate(got)
    assert got.shape == want[:len(got)].shape and len(got) == 41
    assert onp.abs(got - want).max() < TOL


@pytest.mark.parametrize("chunk", [1, 3, 16])
def test_engine_streams_have_no_token_gap(built, chunk):
    """`token_gap` as the benchmark takes it: no served token's logit lies
    below the reference's best by more than rounding, for prompts shorter
    and longer than the window, decoded past it."""
    model, p = built
    eng = _engine(model, chunk, slots=3)
    rng = onp.random.default_rng(chunk)
    prompts = [rng.integers(0, 96, n).tolist() for n in (5, 13, 30)]
    handles = [eng.submit(pr, max_new_tokens=12) for pr in prompts]
    eng.run_until_idle()
    for pr, h in zip(prompts, handles):
        seq = h.result(timeout=0)
        logits = _ref_logits(p, CFG, seq)
        for i in range(len(pr), len(seq)):
            row = logits[i - 1]
            assert row.max() - row[seq[i]] < TOL


def test_full_forward_of_the_model_is_the_reference(built):
    """`AfmoeForCausalLM.forward` (dense attention, no cache) against the
    reference over a whole sequence."""
    model, p = built
    seq = onp.random.default_rng(5).integers(0, 96, 33).tolist()
    got = model(mx.np.array([seq], dtype="int32"))._data[0]
    assert onp.abs(onp.asarray(got) - _ref_logits(p, CFG, seq)).max() < TOL


# ---------------------------------------------------------------------------
# (2) the shares add up
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("held", [1, 2, 4, 8])
def test_shares_of_an_expert_layer_add_up_to_the_uncut_layer(held):
    """The routed parts that all shares give, the shared expert counted
    once, equal the uncut reference layer: the program's `moe_ffn` told it
    holds experts [s*held, (s+1)*held), against the reference holding all
    eight."""
    from benchmark.reference import afmoe as ref
    from mxnet_tpu.serve.decode import DecodeSpec, moe_ffn, swiglu
    rng = onp.random.default_rng(held)
    n, e, f, k, n_exp = 24, 64, 32, 2, 8

    def arr(*shape, scale=0.05):
        return jnp.asarray(rng.standard_normal(shape) * scale, jnp.float32)

    x = arr(n, e, scale=1.0)
    router, bias = arr(n_exp, e), arr(n_exp, scale=0.02)
    w13, w2 = arr(n_exp, e, 2 * f), arr(n_exp, f, e)
    s13, s2 = arr(2 * f, e), arr(e, f)
    p = {"moe.router.weight": router, "moe.router_bias": bias,
         "moe.experts_w13": w13, "moe.experts_w2": w2,
         "moe.shared_w13.weight": s13, "moe.shared_w2.weight": s2}
    cfg = {"experts_held": {"first": 0, "count": n_exp},
           "num_experts_per_tok": k, "route_scale": 2.448}
    whole, _ = ref.expert_layer(x, p, "moe.", cfg, "float32")
    shared = swiglu(x, s13, s2)
    total, counted = shared, 0
    for first in range(0, n_exp, held):
        spec = DecodeSpec(layers=(), head_dim=16, eps=1e-5, n_experts=n_exp,
                          top_k=k, experts_held=(first, held),
                          route_scale=2.448)
        L = {"router": router, "router_bias": bias,
             "experts_w13": w13[first:first + held],
             "experts_w2": w2[first:first + held],
             "shared_w13": s13, "shared_w2": s2}
        out, counts = moe_ffn(x, L, spec)
        total = total + (out - shared)
        counted += int(counts.sum())
    assert counted == n * k                  # every pair, held exactly once
    assert onp.abs(onp.asarray(total - whole)).max() < TOL


def test_padded_rows_are_left_out_of_the_routing_counts():
    from mxnet_tpu.serve.decode import DecodeSpec, moe_ffn
    rng = onp.random.default_rng(0)
    n, e, f = 10, 64, 32
    L = {"router": jnp.asarray(rng.standard_normal((8, e)), jnp.float32),
         "router_bias": jnp.zeros(8),
         "experts_w13": jnp.ones((8, e, 2 * f)) * 0.01,
         "experts_w2": jnp.ones((8, f, e)) * 0.01,
         "shared_w13": jnp.ones((2 * f, e)) * 0.01,
         "shared_w2": jnp.ones((e, f)) * 0.01}
    spec = DecodeSpec(layers=(), head_dim=16, eps=1e-5, n_experts=8,
                      top_k=2, experts_held=(0, 8))
    x = jnp.asarray(rng.standard_normal((n, e)), jnp.float32)
    valid = jnp.arange(n) < 6
    out, counts = moe_ffn(x, L, spec, valid)
    assert int(counts.sum()) == 6 * 2
    whole, _ = moe_ffn(x, L, spec)
    assert onp.abs(onp.asarray(out - whole))[:6].max() < 1e-6


# ---------------------------------------------------------------------------
# (3) the grouped cache
# ---------------------------------------------------------------------------

def _drive(eng, prompts, new=10, watch=None):
    handles = [eng.submit(pr, max_new_tokens=new) for pr in prompts]
    steps = 0
    while eng.step():
        steps += 1
        if watch is not None:
            watch(eng)
        assert steps < 2000
    return [h.result(timeout=0) for h in handles]


@pytest.mark.parametrize("chunk", [1, 3])
def test_sliding_group_holds_a_window_and_reuses_released_pages(built, chunk):
    """A sliding layer never holds more than window / page + 2 pages a
    slot (chunks no wider than a page); pages released behind the window
    are handed to another slot; and the streams equal those of an engine
    that releases nothing."""
    from mxnet_tpu.serve import kv_cache
    model, _ = built
    rng = onp.random.default_rng(7)
    prompts = [rng.integers(0, 96, n).tolist() for n in (41, 9, 33, 26)]
    eng = _engine(model, chunk, slots=2)
    full, sliding = eng.groups
    assert (full.name, sliding.name) == ("full", "sliding")
    assert sliding.walk <= 8 // 4 + 2
    assert eng.pools.arrays[sliding.pool_names[0]].shape[2] \
        == sliding.num_pages == 2 * sliding.walk + 1
    owners, most = {}, [0]

    def watch(e):
        for s in e.scheduler._slots:
            if s is None:
                continue
            run = s.runs[1]
            assert run.group is sliding and s.runs[0].pages is s.pages
            most[0] = max(most[0], len(run.pages))
            for page in run.pages:
                owners.setdefault(page, set()).add(s.req.id)
            # the table row: the run's pages where it holds them, the
            # null page everywhere else
            row = onp.zeros_like(run.table)
            row[run.first:run.first + len(run.pages)] = run.pages
            assert (run.table == row).all()
    streams = _drive(eng, prompts, watch=watch)
    assert most[0] <= 8 // 4 + 2
    assert eng.scheduler.kv_pages_released > 0
    assert any(len(reqs) > 1 for reqs in owners.values())
    for g in eng.groups:                           # all came back
        assert g.allocator.free_pages == g.allocator.total_pages

    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(kv_cache, "window_walk_pages", lambda *a: 10 ** 6)
        mp.setattr(kv_cache.PageRun, "release_before",
                   lambda self, cursor: 0)
        keep = _engine(model, chunk, slots=2)
        assert keep.groups[1].walk == keep.max_pages_per_seq
        assert _drive(keep, prompts) == streams
        assert keep.scheduler.kv_pages_released == 0


def test_wide_chunks_hold_what_a_chunk_can_see(built):
    """With chunks wider than a page the bound is what a chunk's queries
    see: `window_walk_pages`."""
    from mxnet_tpu.serve.kv_cache import window_walk_pages
    model, _ = built
    eng = _engine(model, 16, slots=2)
    assert eng.groups[1].walk == window_walk_pages(7, 16, 4) == 7
    most = [0]

    def watch(e):
        most[0] = max([most[0]] + [len(s.runs[1].pages)
                                   for s in e.scheduler._slots if s])
    prompts = [onp.random.default_rng(2).integers(0, 96, 50).tolist()]
    _drive(eng, prompts, watch=watch)
    assert 0 < most[0] <= 7


def test_sliding_group_under_page_pressure_preempts_and_recovers(built):
    """A sliding pool too small for both slots at once: the younger slot is
    evicted, re-prefills, and both streams equal the roomy engine's."""
    model, _ = built
    rng = onp.random.default_rng(11)
    prompts = [rng.integers(0, 96, n).tolist() for n in (30, 28)]
    roomy = _drive(_engine(model, 3, slots=2), prompts)
    tight = _engine(model, 3, slots=2, num_pages=26)
    dry = tight.groups[1].allocator
    dry._free = dry._free[:5]
    assert _drive(tight, prompts) == roomy
    assert tight.scheduler._n_evicted > 0


def test_a_model_with_a_sliding_group_shares_no_prefix_and_hands_nothing_off(
        built):
    from mxnet_tpu.base import MXNetError
    model, _ = built
    eng = _engine(model, 4, prefix_cache=True)
    assert eng.prefix_index is None
    with pytest.raises(MXNetError, match="role='both'"):
        _engine(model, 4, role="prefill")
    assert eng.tp == 1


@pytest.mark.parametrize("cursor,window,page,first", [
    (0, 7, 4, 0), (7, 7, 4, 0), (8, 7, 4, 0), (11, 7, 4, 1), (12, 7, 4, 1),
    (15, 7, 4, 2), (4095, 4095, 128, 0), (4224, 4095, 128, 1),
    (12288, 4095, 128, 64)])
def test_window_first_page_is_the_first_page_a_query_can_see(cursor, window,
                                                             page, first):
    from mxnet_tpu.serve.kv_cache import window_first_page
    assert int(window_first_page(cursor, window, page)) == first
    assert int(window_first_page(jnp.asarray(cursor), window, page)) == first
    # every page before it lies wholly before cursor - window
    assert first * page <= max(0, cursor - window) < (first + 1) * page \
        or cursor <= window


def test_walk_pages_at_the_published_sizes():
    from mxnet_tpu.serve.kv_cache import window_walk_pages
    assert window_walk_pages(4095, 16, 128) == 4096 // 128 + 2 == 34
    assert window_walk_pages(4095, 1, 128) == 33


# ---------------------------------------------------------------------------
# (4) the kernel with a first live page, in interpret mode
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("start,chunk", [(0, 5), (7, 3), (8, 1), (9, 4),
                                         (23, 16), (40, 1)])
def test_kernel_with_first_live_page_matches_dense_attention(monkeypatch,
                                                             start, chunk):
    """`ragged_paged_attention` walking a work list of `window_walk_pages`
    pages a slot, from the slot's first live page (the pages before it
    point at the null page), against `_dense_attend(window=)` over the
    contiguous context: queries whose window starts exactly on, just
    before and just after a page edge."""
    monkeypatch.setenv("MXTPU_PALLAS_INTERPRET", "1")
    from mxnet_tpu.ops.pallas.paged_attention import (
        _dense_attend, ragged_paged_attention)
    from mxnet_tpu.serve.kv_cache import (live_page_items,
                                          window_first_page,
                                          window_walk_pages)
    ps, d, hkv, h, win = 8, 16, 2, 4, 7
    total = start + chunk
    maxp = -(-64 // ps)
    rng = onp.random.default_rng(start)
    kc = jnp.asarray(rng.standard_normal((1, hkv, maxp * ps, d)),
                     jnp.float32)
    vc = jnp.asarray(rng.standard_normal((1, hkv, maxp * ps, d)),
                     jnp.float32)
    q = jnp.asarray(rng.standard_normal((1, h, chunk, d)), jnp.float32)
    # the pool: logical page i at physical page i + 1; page 0 is null
    kpool = jnp.concatenate([jnp.zeros((hkv, 1, ps, d)),
                             kc[0].reshape(hkv, maxp, ps, d)], 1)[None]
    vpool = jnp.concatenate([jnp.zeros((hkv, 1, ps, d)),
                             vc[0].reshape(hkv, maxp, ps, d)], 1)[None]
    first = int(window_first_page(start, win, ps))
    table = jnp.arange(1, maxp + 1, dtype=jnp.int32).at[:first].set(0)[None]
    starts = jnp.asarray([start], jnp.int32)
    ctx = jnp.asarray([total], jnp.int32)
    work_list = live_page_items(ctx, starts, win, ps,
                                window_walk_pages(win, 16, ps))
    assert int(work_list[1][0]) == first
    out = ragged_paged_attention(
        q, kpool, vpool, table, ctx, starts, window=win, layer=0,
        use_kernel=True, work_list=work_list)
    qpos = starts[:, None] + jnp.arange(chunk)[None]
    want = _dense_attend(q, kc, vc, qpos, ctx_len=ctx, window=win)
    assert onp.abs(onp.asarray(out - want)).max() < 1e-5


def test_engine_kernel_route_streams_equal_reference_route(built,
                                                           monkeypatch):
    """Greedy streams with both cache groups on the kernel route
    (interpret mode: one work list a group and step, the sliding one of
    the group's `walk` pages a slot over a table whose released entries are
    the null page) equal the reference route's: prompts past the window,
    decoded across page edges, a slot idle at the end."""
    from mxnet_tpu.serve import InferenceEngine, ServeConfig
    model, _ = built
    rng = onp.random.default_rng(11)
    prompts = [rng.integers(0, 96, n).tolist() for n in (27, 6)]

    def engine():       # the kernels tile pages of 8, not the file's 4
        return InferenceEngine(model, ServeConfig(
            max_slots=3, page_size=8, prefill_chunk=5, max_len=96))

    want = _drive(engine(), prompts, new=7)
    monkeypatch.setenv("MXTPU_PALLAS", "kernel")
    monkeypatch.setenv("MXTPU_PALLAS_INTERPRET", "1")
    eng = engine()
    assert eng.groups[1].walk == 3 < eng.max_pages_per_seq
    assert _drive(eng, prompts, new=7) == want
    assert eng.scheduler.kv_pages_released > 0


@pytest.mark.parametrize("rows", [[5, 0, 33, 2], [0, 0, 0, 9], [64, 64, 1, 1]])
def test_grouped_matmul_kernel_matches_ragged_dot(monkeypatch, rows):
    monkeypatch.setenv("MXTPU_PALLAS_INTERPRET", "1")
    from mxnet_tpu.ops.pallas import moe_gmm as G
    rng = onp.random.default_rng(sum(rows))
    groups = onp.concatenate([onp.full(n, g) for g, n in enumerate(rows)]
                             + [onp.full(3, len(rows))])
    rng.shuffle(groups)
    dest, counts, group_rows = G.plan_rows(jnp.asarray(groups, jnp.int32),
                                           len(rows))
    assert counts.tolist() == rows
    R = G.padded_rows(len(groups), len(rows))
    x = jnp.asarray(rng.standard_normal((len(groups), 128)), jnp.float32)
    xs = jnp.zeros((R, 128)).at[dest].set(x, mode="drop")
    w = jnp.asarray(rng.standard_normal((len(rows), 128, 256)), jnp.float32)
    got = G.grouped_matmul(xs, w, group_rows, use_kernel=True)
    held = groups < len(rows)
    want = onp.einsum("pk,pkn->pn", onp.asarray(x)[held],
                      onp.asarray(w)[groups[held]])
    assert onp.abs(onp.asarray(got)[onp.asarray(dest)[held]] - want).max() \
        < 1e-3
    ref = G.grouped_matmul(xs, w, group_rows, use_kernel=False)
    assert onp.abs(onp.asarray(ref)[onp.asarray(dest)[held]] - want).max() \
        < 1e-3


# ---------------------------------------------------------------------------
# (5) GPT-2 through the refactored step
# ---------------------------------------------------------------------------

def _gpt(**kw):
    from mxnet_tpu.models.gpt import GPTConfig, GPTForCausalLM
    cfg = dict(vocab_size=96, hidden_size=32, num_layers=2, num_heads=4,
               intermediate_size=64, max_position=64, dropout=0.0)
    cfg.update(kw)
    m = GPTForCausalLM(GPTConfig(**cfg))
    m.initialize()
    m(mx.np.array([[1, 2]], dtype="int32"))
    return m


@pytest.mark.parametrize("kw,tp", [
    ({}, 1), ({}, 2), ({"rope": True, "num_kv_heads": 2}, 1),
    ({"rope": True, "num_kv_heads": 2}, 2), ({"window": 6}, 1)])
def test_gpt_through_the_per_layer_step_streams_what_generate_does(kw, tp):
    from mxnet_tpu.serve import InferenceEngine, ServeConfig
    m = _gpt(**kw)
    prompt = [3, 1, 4, 1, 5, 9, 2, 6, 5, 3, 5]
    want = onp.asarray(m.generate(mx.np.array([prompt], dtype="int32"),
                                  max_new_tokens=12).asnumpy())[0].tolist()
    eng = InferenceEngine(m, ServeConfig(max_slots=2, page_size=4,
                                         prefill_chunk=4, max_len=32, tp=tp))
    assert eng.tp == tp and [g.name for g in eng.groups] == ["full"]
    assert "free_pages_sliding" not in eng.stats()
    assert eng.pools.names == ("k", "v")
    assert eng.generate(prompt, max_new_tokens=12) == want


def test_gpt_spec_is_one_block_for_every_layer():
    from mxnet_tpu.serve.decode import decode_spec
    spec = decode_spec(_gpt(window=6).cfg)
    assert len(set(spec.layers)) == 1 and spec.cache_groups() == ("full",)
    assert spec.layers[0].window == 6 and spec.learned_positions
    from mxnet_tpu.models.afmoe import AfmoeConfig
    a = AfmoeConfig(num_layers=8, num_dense_layers=2).decode_spec()
    assert a.cache_groups() == ("full", "sliding")
    assert [ls.cache_group for ls in a.layers] == \
        ["sliding"] * 3 + ["full"] + ["sliding"] * 3 + ["full"]
    assert [ls.ffn for ls in a.layers] == ["swiglu"] * 2 + ["moe"] * 6
    assert a.layers[0].window == 4095 and a.layers[3].window is None
    assert a.layers[0].rope and not a.layers[3].rope
    # window layers alone: the whole-context group is still there, empty
    # (admission and the request caps count its pages)
    from mxnet_tpu.serve import ServeConfig
    from mxnet_tpu.serve.kv_cache import plan_cache_groups
    w = AfmoeConfig(num_layers=2, num_dense_layers=1,
                    layer_types=["sliding_attention"] * 2).decode_spec()
    assert w.cache_groups() == ("sliding",)
    assert [(g.name, g.layers) for g in plan_cache_groups(
        w, ServeConfig(max_slots=2, page_size=128), 8, 16)] == \
        [("full", ()), ("sliding", (0, 1))]


# ---------------------------------------------------------------------------
# counters
# ---------------------------------------------------------------------------

def test_step_counts_carry_routing_and_cache_group_counters(built):
    model, _ = built
    eng = _engine(model, 4, slots=2)
    prompts = [onp.random.default_rng(3).integers(0, 96, 30).tolist()]
    _drive(eng, prompts, new=6)
    slow = eng.stats()["slowest_steps"][0]
    for key in ("kv_pages_full", "kv_pages_sliding", "kv_pages_released",
                "moe_tokens_routed", "moe_experts_touched",
                "moe_experts_held", "moe_load_max_over_mean"):
        assert key in slow, key
    assert slow["moe_experts_held"] == 4 * 4       # 4 expert layers x 4 held
    assert 0 <= slow["moe_experts_touched"] <= 16
    assert eng.last_moe_counts.shape == (4, 4)
    assert eng.stats()["kv_pages_released"] > 0


def test_attn_items_count_each_groups_live_pages_while_capturing(built):
    """Two cache groups, two counters, each the `n_items` of the work
    list the kernel would walk: the full group's items grow with the
    context, the sliding group's stay within what a window and a chunk
    can span (the group's `walk` a slot)."""
    from mxnet_tpu import tracing
    from mxnet_tpu.serve.kv_cache import live_page_items
    model, _ = built
    eng = _engine(model, 4, slots=2)
    seen = []
    plan = eng.scheduler._plan

    def spy():
        out = plan()
        if out is not None:
            _, _, start, _, ctx = out[3][:5]
            # the kernel's own list: its traced bound is the grid
            seen.append(tuple(
                int(live_page_items(jnp.asarray(ctx), jnp.asarray(start),
                                    w, 4, walk)[2])
                for w, walk in ((g.window, g.walk) for g in eng.groups)))
        return out
    eng.scheduler._plan = spy
    tracing.enable()
    try:
        _drive(eng, [onp.random.default_rng(3).integers(0, 96, 30).tolist()],
               new=6)
        steps = [s.tags for s in tracing.get_tracer("serve").spans()
                 if s.name == "serve.step"]
    finally:
        tracing.disable()
    assert [(t["attn_items_full"], t["attn_items_sliding"])
            for t in steps] == seen
    assert max(f for f, _ in seen) == 1 + -(-35 // 4)     # an idle slot's 1
    assert max(w for _, w in seen) <= 1 + eng.groups[1].walk
    assert all(t["attn_items_table"] == 2 * eng.max_pages_per_seq
               for t in steps)


@pytest.mark.parametrize("scope", [
    "mx.serve.moe.route", "mx.serve.moe.experts", "mx.serve.moe.shared",
    "mx.serve.qkv", "mx.serve.pool_write", "mx.serve.paged_attn",
    "mx.serve.attn_out", "mx.serve.mlp"])
def test_afmoe_step_lowers_with_its_scopes(built, scope):
    """The expert layer's three scopes, and the block scopes every model
    shares, reach the lowered program's op locations."""
    import re
    model, _ = built
    eng = _engine(model, 4)
    txt = eng._step_fn(4).trace(*eng._step_avals(4)).lower().as_text(
        debug_info=True)
    assert re.search(r'[/("]' + re.escape(scope) + r'[/)"]', txt), scope
