"""Distributed tracing + FLOP-accounted performance attribution
(`mxnet_tpu.tracing`): span primitives, cross-thread handoff, Chrome
export, the per-executable cost registry, the MFU gauges, and the
two-subsystem (serve + train in one process) correlation contract.
`tracing` marker (tier-1, CPU)."""
import json
import threading
import time

import numpy as onp
import pytest

import jax
import jax.numpy as jnp

import mxnet_tpu as mx  # noqa: F401
from mxnet_tpu import optimizer as opt
from mxnet_tpu import telemetry as tele
from mxnet_tpu import tracing
from mxnet_tpu.base import MXNetError
from mxnet_tpu.gluon import nn
from mxnet_tpu.parallel import (DevicePrefetcher, make_mesh,
                                make_sharded_train_step)

pytestmark = pytest.mark.tracing


@pytest.fixture(autouse=True)
def _clean_tracing():
    """Each test starts with tracing+telemetry off and empty state, and
    leaves the process that way (both are process-wide)."""
    tele.disable()
    tele.registry().reset()
    tracing.disable()
    tracing.reset()
    tracing.account().clear()
    yield
    tele.disable()
    tele.registry().reset()
    tracing.disable()
    tracing.reset()
    tracing.account().clear()


def _tiny_step():
    net = nn.Dense(4, in_units=8)
    net.initialize()
    mesh = make_mesh({"dp": 1}, jax.devices()[:1])
    step = make_sharded_train_step(
        net, opt.SGD(learning_rate=1e-2),
        lambda out, x, y: jnp.mean((out - y) ** 2), mesh,
        num_model_args=1)
    rng = onp.random.RandomState(0)
    xs = rng.uniform(-1, 1, (8, 8)).astype("float32")
    ys = rng.uniform(-1, 1, (8, 4)).astype("float32")
    return step, xs, ys


# ---------------------------------------------------------------------------
# span primitives
# ---------------------------------------------------------------------------

def test_lexical_nesting_parents_and_trace_ids():
    tracing.enable()
    tr = tracing.get_tracer("t")
    with tr.span("outer") as outer:
        with tr.span("inner") as inner:
            assert inner.parent_id == outer.span_id
            assert inner.trace_id == outer.trace_id
    with tr.span("second_root") as root2:
        pass
    # a fresh root span opens a fresh trace id
    assert root2.trace_id != outer.trace_id
    assert root2.parent_id is None
    names = [s.name for s in tr.spans()]
    assert names == ["inner", "outer", "second_root"]  # finish order
    assert all(s.duration_ms >= 0 for s in tr.spans())


def test_manual_span_does_not_touch_stack():
    tracing.enable()
    tr = tracing.get_tracer("t")
    s = tr.start_span("req")
    assert tr.current() is None          # not pushed
    with tr.span("unrelated") as u:
        assert u.parent_id is None       # manual span is no parent
    child = tr.start_span("phase", parent=s.context())
    child.finish()
    s.finish()
    assert child.parent_id == s.span_id
    assert child.trace_id == s.trace_id


def test_cross_thread_handoff():
    tracing.enable()
    tr = tracing.get_tracer("t")
    got = {}

    with tr.span("consumer") as outer:
        ctx = tr.current_context()

        def worker():
            # worker thread has its OWN empty stack; the handoff context
            # is the only way to parent under the consumer
            assert tr.current() is None
            with tr.span("work", parent=ctx) as w:
                got["parent"] = w.parent_id
                got["trace"] = w.trace_id

        t = threading.Thread(target=worker)
        t.start()
        t.join()
    assert got["parent"] == outer.span_id
    assert got["trace"] == outer.trace_id


def test_two_tracers_isolated_id_spaces():
    tracing.enable()
    a, b = tracing.get_tracer("alpha"), tracing.get_tracer("beta")
    with a.span("x") as sa:
        # beta sees no current span from alpha's stack
        assert b.current() is None
        with b.span("y") as sb:
            assert sb.parent_id is None
            assert sa.trace_id != sb.trace_id
    assert sa.trace_id.startswith("alpha-")
    assert sb.trace_id.startswith("beta-")


def test_span_cap_bounds_memory():
    tracing.enable()
    tr = tracing.Tracer("capped", span_cap=10)
    for i in range(25):
        tr.record_span(f"s{i}", 0.0, 1e-6)
    assert len(tr.spans()) == 10
    assert tr.dropped == 15
    assert tr.spans()[-1].name == "s24"   # newest kept


def test_exception_tags_error_and_pops_stack():
    tracing.enable()
    tr = tracing.get_tracer("t")
    with pytest.raises(ValueError):
        with tr.span("boom"):
            raise ValueError("x")
    assert tr.current() is None
    (s,) = tr.spans()
    assert s.tags["error"] == "ValueError"


def test_disabled_fast_path_records_nothing():
    assert not tracing.enabled()
    step, xs, ys = _tiny_step()
    step.warmup(xs, ys)
    for _ in range(3):
        step.dispatch(xs, ys)
    step.drain()
    assert step.trace_count == 1
    assert tracing.get_tracer("train").spans() == []


# ---------------------------------------------------------------------------
# chrome export
# ---------------------------------------------------------------------------

def test_chrome_export_structure(tmp_path):
    tracing.enable(dir=str(tmp_path))
    tr = tracing.get_tracer("t")
    with tr.span("parent", foo="bar"):
        with tr.span("child"):
            pass
    tr.record_span("tracked", 0.0, 0.001, track="my track")
    path = tracing.export_chrome()
    doc = json.load(open(path))
    evs = doc["traceEvents"]
    metas = [e for e in evs if e["ph"] == "M"]
    xs = [e for e in evs if e["ph"] == "X"]
    assert {m["args"]["name"] for m in metas} == {"t", "my track"}
    assert len(xs) == 3
    by_name = {e["name"]: e for e in xs}
    assert by_name["child"]["args"]["parent_id"] == \
        by_name["parent"]["args"]["span_id"]
    assert by_name["parent"]["args"]["foo"] == "bar"
    # explicit track -> its own synthetic tid
    assert by_name["tracked"]["tid"] != by_name["parent"]["tid"]
    for e in xs:
        assert e["dur"] >= 0 and e["ts"] > 0


# ---------------------------------------------------------------------------
# cost accountant + MFU
# ---------------------------------------------------------------------------

def test_cost_accountant_records_and_estimates():
    compiled = jax.jit(lambda x: x @ x).lower(
        jnp.ones((64, 64), jnp.float32)).compile()
    e = tracing.record_executable("k", compiled, kind="test_step")
    assert e["features"]["flops"] > 0
    assert e["features"]["bytes_accessed"] > 0
    assert e["features"]["hbm_bytes_est"] > 0
    mfu = tracing.account().mfu("k", 1e-3)
    assert 0 < mfu["mfu_estimate"] < 1
    assert mfu["projected"] is True      # CPU backend -> projected peak
    assert tracing.account().mfu("missing", 1e-3) is None
    assert tracing.account().mfu("k", 0.0) is None


def test_peak_flops_table_and_env_override(monkeypatch):
    assert tracing.peak_flops("TPU v4") == 275e12
    assert tracing.peak_flops("TPU v5 lite") == 197e12
    with pytest.raises(MXNetError, match="no published peak"):
        tracing.peak_flops("unknown accelerator")
    monkeypatch.setenv("MXTPU_PEAK_TFLOPS", "100")
    assert tracing.peak_flops("TPU v4") == 100e12
    monkeypatch.delenv("MXTPU_PEAK_TFLOPS")
    monkeypatch.setenv("MXTPU_MFU_DEVICE_KIND", "v4")
    peak, kind = tracing.projected_peak_flops()
    assert peak == 275e12 and kind == "v4"


def test_note_step_cost_sets_labeled_gauges():
    tele.enable()
    compiled = jax.jit(lambda x: x @ x).lower(
        jnp.ones((32, 32), jnp.float32)).compile()
    tracing.record_executable("k2", compiled, kind="train_step")
    row = tracing.note_step_cost("k2", 5e-4)
    assert row["flops"] > 0
    assert row["mfu_estimate"] > 0
    assert row["measured_ms"] == pytest.approx(0.5)
    g = tele.registry().get("mfu_estimate")
    assert g.value(program="train_step") == pytest.approx(
        row["mfu_estimate"])
    assert tele.registry().get("step_flops") \
        .value(program="train_step") == row["flops"]
    # unknown key: no row, no gauge churn
    assert tracing.note_step_cost("nope", 1e-3) is None


def test_train_step_cost_capture_and_journal_corpus(tmp_path):
    journal = str(tmp_path / "j.jsonl")
    tele.enable(journal_path=journal)
    step, xs, ys = _tiny_step()
    step.warmup(xs, ys)
    feats = step.cost_features()
    assert feats["flops"] > 0
    for _ in range(4):
        step.dispatch(*step.place_batch(xs, ys))
    step.drain()
    rows = tele.RunJournal.read(journal)
    retired = [r for r in rows if r["event"] == "step_retired"]
    assert [r["step"] for r in retired] == [1, 2, 3, 4]
    for r in retired:
        assert r["cost"]["flops"] == feats["flops"]
        assert r["cost"]["measured_ms"] > 0
        assert r["cost"]["mfu_estimate"] > 0
        assert r["cost"]["mfu_projected"] is True
    mfu = step.mfu_estimate(1e-3)
    assert mfu["mfu_estimate"] > 0


# ---------------------------------------------------------------------------
# prefetcher handoff + pending gauge (satellites)
# ---------------------------------------------------------------------------

def test_prefetch_spans_nest_across_thread_handoff():
    tracing.enable()
    tr = tracing.get_tracer("data")
    src = [(onp.ones((2, 2)),) for _ in range(4)]
    with tr.span("epoch") as outer:
        with DevicePrefetcher(iter(src), depth=2) as pf:
            for _ in pf:
                pass
    places = [s for s in tr.spans() if s.name == "prefetch.place"]
    assert len(places) == 4
    # the worker thread's placement spans parent under the consumer
    # thread's open span, captured at construction (cross-thread handoff)
    assert all(s.parent_id == outer.span_id for s in places)
    assert all(s.trace_id == outer.trace_id for s in places)
    waits = [s for s in tr.spans() if s.name == "prefetch.wait"]
    assert len(waits) == 4


def test_prefetch_pending_gauge_exported():
    tele.enable()
    src = [(onp.ones((2,)),) for _ in range(6)]
    pf = DevicePrefetcher(iter(src), depth=2)
    try:
        it = iter(pf)
        next(it)
        g = tele.registry().get("prefetch_pending")
        assert g is not None
        assert g.value() >= 0
        # the gauge rides the standard exposition
        assert "prefetch_pending" in tele.to_prometheus()
    finally:
        pf.close()


# ---------------------------------------------------------------------------
# the concurrency contract: serve + train in ONE process
# ---------------------------------------------------------------------------

def test_concurrent_serve_and_train_no_cross_contamination(tmp_path):
    """Satellite: two tracers in one process — concurrent serve + train
    keep distinct trace ids, journal step ids stay correlated, and the
    request span trees stay complete."""
    from mxnet_tpu.models.gpt import GPTConfig, GPTForCausalLM
    from mxnet_tpu.serve import InferenceEngine, ServeConfig

    journal = str(tmp_path / "j.jsonl")
    tele.enable(journal_path=journal)
    tracing.enable(dir=str(tmp_path))

    cfg = GPTConfig(vocab_size=64, hidden_size=16, num_layers=1,
                    num_heads=2, intermediate_size=32, max_position=32,
                    dropout=0.0)
    model = GPTForCausalLM(cfg)
    model.initialize()
    model(mx.np.array([[1, 2]], dtype="int32"))
    eng = InferenceEngine(model, ServeConfig(
        max_len=24, max_slots=2, num_pages=9, page_size=4,
        prefill_chunk=4))
    eng.warmup()

    step, xs, ys = _tiny_step()
    step.warmup(xs, ys)

    errs = []

    def serve_loop():
        try:
            hs = [eng.submit([1, 2, 3], max_new_tokens=3)
                  for _ in range(2)]
            eng.run_until_idle()
            for h in hs:
                h.result(timeout=10)
        except Exception as e:   # pragma: no cover - failure reporting
            errs.append(e)

    def train_loop():
        try:
            for _ in range(4):
                step.dispatch(xs, ys)
                time.sleep(0.002)
            step.drain()
        except Exception as e:   # pragma: no cover - failure reporting
            errs.append(e)

    ts = [threading.Thread(target=serve_loop),
          threading.Thread(target=train_loop)]
    for t in ts:
        t.start()
    for t in ts:
        t.join(60)
    assert not errs, errs

    serve_spans = tracing.get_tracer("serve").spans()
    train_spans = tracing.get_tracer("train").spans()
    serve_tids = {s.trace_id for s in serve_spans}
    train_tids = {s.trace_id for s in train_spans}
    assert serve_tids and train_tids
    assert not serve_tids & train_tids
    assert all(t.startswith("serve-") for t in serve_tids)
    assert all(t.startswith("train-") for t in train_tids)

    # request trees complete despite the concurrent train traffic
    reqs = [s for s in serve_spans if s.name == "serve.request"]
    assert len(reqs) == 2
    for root in reqs:
        children = [s for s in serve_spans
                    if s.parent_id == root.span_id]
        kinds = {s.name for s in children}
        assert "serve.queue" in kinds
        assert kinds & {"serve.prefill_chunk", "serve.first_decode"}
        assert all(s.trace_id == root.trace_id for s in children)

    # journal correlation: train span step tags == journal retired ids
    rows = tele.RunJournal.read(journal)
    retired = sorted(r["step"] for r in rows
                     if r["event"] == "step_retired")
    span_steps = sorted(s.tags["step"] for s in train_spans
                        if s.name == "train.device")
    assert retired == span_steps == [1, 2, 3, 4]


# ---------------------------------------------------------------------------
# prometheus exposition hardening (satellite)
# ---------------------------------------------------------------------------

def _parse_prometheus(text):
    """Strict round-trip parser (the telemetry_smoke grammar)."""
    import re
    comment = re.compile(r"^# (HELP|TYPE) [a-zA-Z_:][a-zA-Z0-9_:]* ?.*$")
    sample = re.compile(
        r"^(?P<name>[a-zA-Z_:][a-zA-Z0-9_:]*)"
        r"(?:\{(?P<labels>[a-zA-Z_][a-zA-Z0-9_]*=\"(?:[^\"\\]|\\.)*\""
        r"(?:,[a-zA-Z_][a-zA-Z0-9_]*=\"(?:[^\"\\]|\\.)*\")*)\})?"
        r" (?P<value>[0-9.eE+-]+|NaN|\+Inf|-Inf)$")
    out = {}
    for lineno, line in enumerate(text.splitlines(), 1):
        if not line.strip():
            continue
        if line.startswith("#"):
            assert comment.match(line), f"line {lineno}: {line!r}"
            continue
        m = sample.match(line)
        assert m, f"line {lineno}: {line!r}"
        labels = {}
        if m.group("labels"):
            for k, v in re.findall(
                    r'([a-zA-Z_][a-zA-Z0-9_]*)="((?:[^"\\]|\\.)*)"',
                    m.group("labels")):
                labels[k] = (v.replace(r"\n", "\n").replace(r"\"", '"')
                             .replace(r"\\", "\\"))
        val = m.group("value")
        out.setdefault(m.group("name"), []).append(
            (labels, float("nan") if val == "NaN" else float(val)))
    return out


def test_prometheus_roundtrip_with_hostile_values():
    nasty = 'a\\b"c\nd'
    c = tele.counter("hard_total", 'help with "quotes"\nand\\slashes',
                     labelnames=("k",))
    c.inc(3, k=nasty)
    g = tele.gauge("weird_vals")
    g.set(float("inf"))
    h = tele.histogram("hard_ms", "hist help", buckets=(1.0, 10.0))
    h.observe(5)
    parsed = _parse_prometheus(tele.to_prometheus())
    # label value survives the round trip byte-for-byte
    (labels, val), = parsed["hard_total"]
    assert labels == {"k": nasty}
    assert val == 3
    # non-finite values use the spec spellings (repr() would emit 'inf')
    (_, gv), = parsed["weird_vals"]
    assert gv == float("inf")
    assert parsed["hard_ms_count"][0][1] == 1
    # TYPE/HELP emitted per family
    text = tele.to_prometheus()
    assert "# TYPE hard_total counter" in text
    assert "# TYPE hard_ms histogram" in text
    assert '# HELP hard_total help with "quotes"\\nand\\\\slashes' \
        in text


def test_prometheus_nan_gauge_spelling():
    tele.gauge("nan_g").set(float("nan"))
    text = tele.to_prometheus()
    assert "nan_g NaN" in text
    _parse_prometheus(text)   # grammar accepts it


# ---------------------------------------------------------------------------
# the serving step's phase spans: always stamped, recorded while capturing()
# ---------------------------------------------------------------------------

STEP_SPANS = ("serve.step", "serve.step.admit", "serve.step.plan",
              "serve.step.launch", "serve.step.wait", "serve.step.emit")


def _phase_engine():
    from mxnet_tpu.models.gpt import GPTConfig, GPTForCausalLM
    from mxnet_tpu.serve import InferenceEngine, ServeConfig
    cfg = GPTConfig(vocab_size=64, hidden_size=16, num_layers=1,
                    num_heads=2, intermediate_size=32, max_position=32,
                    dropout=0.0)
    model = GPTForCausalLM(cfg)
    model.initialize()
    model(mx.np.array([[1, 2]], dtype="int32"))
    eng = InferenceEngine(model, ServeConfig(
        max_len=24, max_slots=2, num_pages=9, page_size=4,
        prefill_chunk=4))
    eng.warmup()
    return eng


def _drive(eng, prompts=((1, 2, 3, 4, 5, 6), (7, 8, 9)), max_new=3):
    """Run the prompts to idle; returns (tokens delivered, plan feeds per
    step as the scheduler built them, steps run)."""
    delivered = []
    feeds = []
    plan = eng.scheduler._plan

    def spy():
        out = plan()
        if out is not None:
            feeds.append(sum(pl["nt"] for pl in out[1].values()))
        return out
    eng.scheduler._plan = spy
    for p in prompts:
        eng.submit(list(p), max_new_tokens=max_new,
                   on_token=lambda t, r: delivered.append(t))
    steps = eng.run_until_idle()
    eng.scheduler._plan = plan
    return delivered, feeds, steps


def _check_step_spans(spans, feeds, delivered, steps):
    """One `serve.step` a step, five children that tile it to 50 us,
    `tokens_fed` the plan's feeds and `emitted` the tokens delivered."""
    parents = [s for s in spans if s.name == "serve.step"]
    assert len(parents) == steps == len(feeds)
    for parent, fed in zip(parents, feeds):
        kids = sorted((s for s in spans if s.parent_id == parent.span_id),
                      key=lambda s: s.t0)
        assert [k.name for k in kids] == list(STEP_SPANS[1:])
        edges = [parent.t0] + [k.t1 for k in kids]
        assert abs(kids[0].t0 - parent.t0) < 50e-6
        assert abs(kids[-1].t1 - parent.t1) < 50e-6
        for a, b in zip(kids, kids[1:]):
            assert abs(a.t1 - b.t0) < 50e-6          # no gap, no overlap
        assert edges == sorted(edges)
        assert parent.tags["tokens_fed"] == fed
        assert kids[2].tags["h2d_bytes"] > 0
        assert kids[0].tags["admitted"] == parent.tags["admitted"]
        assert kids[4].tags["emitted"] == parent.tags["emitted"]
    assert sum(p.tags["emitted"] for p in parents) == len(delivered)
    assert sum(p.tags["admitted"] for p in parents) == 2
    assert sum(p.tags["finished"]
               for p in spans if p.name == "serve.step.emit") == 2
    assert [p.tags["step"] for p in parents] == list(
        range(parents[0].tags["step"], parents[0].tags["step"] + steps))


def test_untraced_step_allocates_no_span_and_still_fills_step_phases():
    """(a) With no profiler session and tracing off a step adds ZERO
    spans to the `serve` ring, and `stats()` still says where the steps'
    time went."""
    assert not tracing.capturing()
    eng = _phase_engine()
    delivered, feeds, steps = _drive(eng)
    assert tracing.get_tracer("serve").spans() == []
    st = eng.stats()
    phases = st["step_phases"]
    assert phases["steps"] == steps
    for name in ("step",) + eng.scheduler.STEP_PHASES:
        assert phases[name]["max_ms"] >= phases[name]["median_ms"] >= 0
    # the phases of a step add up to the step
    slow = st["slowest_steps"]
    assert 1 <= len(slow) <= 5
    assert slow == sorted(slow, key=lambda r: -r["ms"])
    for r in slow:
        split = sum(r[f"{p}_ms"] for p in eng.scheduler.STEP_PHASES)
        assert abs(split - r["ms"]) < 1e-2
        assert r["chunk"] in (1, 4) and r["tokens_fed"] > 0
    assert sum(feeds) == sum(
        c["tokens_fed"] for _, _, _, c in eng.scheduler._phase_log)
    json.dumps(st["step_phases"]), json.dumps(slow)   # wire-safe


@pytest.mark.parametrize("gate", ["profiler", "enable"])
def test_step_spans_tile_the_step_while_capturing(gate, tmp_path):
    """(b)+(c) Inside a `jax.profiler` session, and separately under
    `tracing.enable()`, every step yields one `serve.step` and five
    children that tile it; the per-request fan-out appears under
    `tracing.enable()` and NOT under a profiler session alone."""
    eng = _phase_engine()
    if gate == "profiler":
        jax.profiler.start_trace(str(tmp_path / "prof"))
        try:
            assert tracing.capturing() and not tracing.enabled()
            delivered, feeds, steps = _drive(eng)
        finally:
            jax.profiler.stop_trace()
        assert not tracing.capturing()
    else:
        tracing.enable()
        assert tracing.capturing()
        delivered, feeds, steps = _drive(eng)
    spans = tracing.get_tracer("serve").spans()
    _check_step_spans(spans, feeds, delivered, steps)
    fan_out = [s for s in spans if s.name in (
        "serve.prefill_chunk", "serve.first_decode", "serve.decode",
        "serve.request", "serve.queue")]
    if gate == "profiler":
        assert fan_out == []
        assert {s.name for s in spans} == set(STEP_SPANS)
    else:
        assert {"serve.request", "serve.queue",
                "serve.prefill_chunk"} <= {s.name for s in fan_out}
        # the per-token `serve.stream` span went: `serve.step.emit` holds
        # the callbacks' time
        assert "serve.stream" not in {s.name for s in spans}
    # after the capture: stamped, not recorded
    n = len(spans)
    tracing.disable()
    _drive(eng, prompts=((1, 2),), max_new=2)
    assert len(tracing.get_tracer("serve").spans()) == n


def _live_pages(ctx, start, nt, window, ps):
    """Pages holding a key some query of the chunk sees (brute force);
    an idle slot counts the one page that keeps its output written."""
    seen = {k // ps for qp in range(start, start + nt) for k in range(ctx)
            if k <= qp and (window is None or k >= qp - window)}
    return max(len(seen), 1)


def test_attn_items_counters_equal_the_enumeration_while_capturing(
        monkeypatch):
    """`attn_items_<group>` (here `attn_items_full` alone) and
    `attn_items_table` ride on every `serve.step` while capturing: the
    live (slot, page) pairs of the plan's own starts and contexts, one for
    an idle slot, against the table a walk of every page would take.  Not
    capturing, the counters are not computed at all."""
    eng = _phase_engine()
    sched = eng.scheduler
    plans = []
    plan = sched._plan

    def spy():
        out = plan()
        if out is not None:
            _, nt, start, _, ctx = out[3][:5]
            plans.append(sum(_live_pages(int(c), int(s), int(n), None, 4)
                             for c, s, n in zip(ctx, start, nt)))
        return out
    monkeypatch.setattr(sched, "_plan", spy)
    tracing.enable()
    _drive(eng, prompts=((1, 2, 3, 4, 5, 6, 7, 8, 9), (7, 8, 9)), max_new=4)
    steps = [s for s in tracing.get_tracer("serve").spans()
             if s.name == "serve.step"]
    assert [s.tags["attn_items_full"] for s in steps] == plans
    assert max(plans) > 2 == min(plans)        # past one page; both idle-or-one
    # one cache group: no tag of a group the model does not have
    assert not any(k.endswith("_sliding") for s in steps for k in s.tags)
    assert {s.tags["attn_items_table"] for s in steps} == {2 * 6}
    tracing.disable()
    monkeypatch.setattr(
        sched, "_attn_items",
        lambda *a: pytest.fail("counted while not capturing"))
    _drive(eng, prompts=((1, 2),), max_new=2)
    assert not any(k.startswith("attn_items")
                   for k in sched._phase_log[-1][3])


def test_record_phases_tiles_by_construction():
    tr = tracing.get_tracer("t")
    parent = tracing.record_phases(
        tr, "op", ("a", "b"), (1.0, 1.5, 4.0), tags={"n": 1},
        phase_tags={"b": {"bytes": 7}})
    a, b = [s for s in tr.spans() if s.parent_id == parent.span_id]
    assert (parent.t0, parent.t1, parent.tags) == (1.0, 4.0, {"n": 1})
    assert (a.name, a.t0, a.t1) == ("op.a", 1.0, 1.5)
    assert (b.name, b.t0, b.t1, b.tags) == ("op.b", 1.5, 4.0, {"bytes": 7})
    assert a.trace_id == b.trace_id == parent.trace_id
    with pytest.raises(ValueError, match="stamps"):
        tracing.record_phases(tr, "op", ("a", "b"), (1.0, 2.0))


# ---------------------------------------------------------------------------
# stable names on the device side: `mx.*` scopes and kernel `name=`s
# ---------------------------------------------------------------------------

def _lowered_text(jitted, *avals):
    return jitted.trace(*avals).lower().as_text(debug_info=True)


def _has_scope(txt, scope):
    """The scope as one component of an op's location path: `a/scope/op`,
    or `jvp(scope)` / `transpose(jvp(scope))` under autodiff."""
    import re
    return re.search(r'[/("]' + re.escape(scope) + r'[/)"]', txt)


def test_serve_step_lowers_with_every_mx_serve_scope():
    """(d) `transformer_step` inside the engine's fused step: every
    `mx.serve.*` block name reaches the lowered program's locations."""
    eng = _phase_engine()
    txt = _lowered_text(eng._step_fn(1), *eng._step_avals(1))
    for scope in ("mx.serve.embed", "mx.serve.qkv", "mx.serve.pool_write",
                  "mx.serve.paged_attn", "mx.serve.attn_out",
                  "mx.serve.mlp", "mx.serve.final_norm",
                  "mx.serve.sample"):
        assert _has_scope(txt, scope), scope


def test_bert_train_step_lowers_with_every_mx_scope():
    """(d) The BERT pretraining step: model blocks, the loss and the
    optimizer update (with its packing) carry their `mx.*` names."""
    from mxnet_tpu.gluon.block import HybridBlock
    from mxnet_tpu.models.bert import BertConfig, BertForPretraining

    class Pretrain(HybridBlock):
        def __init__(self):
            super().__init__()
            self.model = BertForPretraining(BertConfig(
                vocab_size=64, hidden_size=16, num_layers=1, num_heads=2,
                intermediate_size=32, max_position=16, dropout=0.0))

        def forward(self, ids, mpos):
            return self.model(ids, masked_positions=mpos)

    def loss_fn(out, ids, mpos, labels):
        mlm, nsp = out
        lse = jax.nn.logsumexp(mlm.astype(jnp.float32), axis=-1)
        picked = jnp.take_along_axis(
            mlm.astype(jnp.float32), labels[..., None], -1)[..., 0]
        return jnp.mean(lse - picked) + jnp.mean(nsp.astype(jnp.float32))

    model = Pretrain()
    model.initialize()
    ids = mx.np.array(onp.ones((2, 8)), dtype="int32")
    mpos = mx.np.array(onp.zeros((2, 2)), dtype="int32")
    labels = mx.np.array(onp.ones((2, 2)), dtype="int32")
    model(ids, mpos)
    mesh = make_mesh({"dp": 1}, jax.devices()[:1])
    step = make_sharded_train_step(
        model, opt.Adam(learning_rate=1e-3), loss_fn, mesh,
        num_model_args=2)
    step._fused_opt_kernel = True        # the packed path, traced only
    batch_vals = step._prepare_batch((ids, mpos, labels))
    args = (step.pvals, step.opt_state, step._hp(),
            jax.random.PRNGKey(0)) + tuple(batch_vals)
    avals = jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), args)
    txt = jax.jit(step._step_fn.__wrapped__).trace(*avals).lower(
        lowering_platforms=("tpu",)).as_text(debug_info=True)
    step._release_trace_guard()
    for scope in ("mx.embed", "mx.attn", "mx.ffn", "mx.norm", "mx.pooler",
                  "mx.mlm_head", "mx.nsp_head", "mx.loss", "mx.optimizer",
                  "mx.optimizer.pack"):
        assert _has_scope(txt, scope), scope
    assert "mx_fused_opt_update" in txt


PALLAS_NAMES = {
    "flash_attention.py": ["mx_flash_attn_fwd", "mx_flash_attn_bwd_dq",
                           "mx_flash_attn_bwd_dkv"],
    "fused_norm.py": ["mx_fused_norm"],
    "fused_optimizer.py": ["mx_fused_opt_update", "mx_fused_lamb_moments",
                           "mx_fused_lamb_apply", "mx_fused_opt_autotune"],
    "moe_dispatch.py": ["mx_moe_dispatch"],
    "moe_gmm.py": ["mx_moe_gmm"],
    "paged_attention.py": ["ragged_paged_attention", "paged_kv_write"],
    "quantized_matmul.py": ["mx_quant_matmul"],
    "softmax_xent.py": ["mx_softmax_xent_fwd", "mx_softmax_xent_bwd"],
}


def _pallas_call_names(path):
    """`name=` of every `pallas_call(...)` in a module (None: unnamed)."""
    import ast
    with open(path) as f:
        tree = ast.parse(f.read())
    out = []
    for node in ast.walk(tree):
        if isinstance(node, ast.Call) and getattr(
                node.func, "attr", getattr(node.func, "id", "")) \
                == "pallas_call":
            kw = {k.arg: k.value for k in node.keywords}
            name = kw.get("name")
            out.append(name.value if isinstance(name, ast.Constant)
                       else None)
    return out


def _pallas_dir():
    import os
    from mxnet_tpu.ops import pallas
    return os.path.dirname(pallas.__file__)


@pytest.mark.parametrize("module,name", [
    (m, n) for m, names in sorted(PALLAS_NAMES.items()) for n in names])
def test_pallas_call_site_has_its_stable_name(module, name):
    import os
    names = _pallas_call_names(os.path.join(_pallas_dir(), module))
    assert names.count(name) == 1, (module, names)


def test_every_pallas_call_is_named_and_names_are_distinct():
    import glob
    import os
    found = []
    for path in sorted(glob.glob(os.path.join(_pallas_dir(), "*.py"))):
        names = _pallas_call_names(path)
        assert None not in names, f"{path}: a pallas_call without name="
        found += names
    assert len(found) == len(set(found)) == sum(
        len(v) for v in PALLAS_NAMES.values())
