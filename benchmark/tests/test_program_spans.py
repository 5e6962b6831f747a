"""The readers of the program's phase spans (`metrics/_program_spans.py` and
the four `program_span` metrics, `paged_attn_ms`) on a synthetic ring and
trace, and on a ring and trace recorded on the chip."""
import json
import os

import pytest

from benchmark.metrics import (_program_spans as ps, paged_attn_ms,
                               serve_host_emit_ms, serve_host_launch_ms,
                               serve_host_prelaunch_ms,
                               serve_readback_exposed_ms)

HERE = os.path.dirname(os.path.abspath(__file__))
OFFSET_NS = 5_000_000_000.0       # trace clock = perf_counter * 1e9 + this
# one step, in ms from its start: admit 0.2, plan 0.5, launch 2.0 (planted),
# wait 10.0, emit 0.3; the device runs from 1.7 ms into the step for 10.4
PHASE_MS = {"admit": 0.2, "plan": 0.5, "launch": 2.0, "wait": 10.0,
            "emit": 0.3}
DEVICE_FROM_MS, DEVICE_MS = 1.7, 10.4
STEP_EVERY_S, N_STEPS = 0.020, 4


def _ring(n=N_STEPS, tokens=64, t_first=100.0):
    """`serve.step` + five children a step, as `ring_spans` returns them,
    with the benchmark's own stamps of the same steps."""
    spans, steps, sid = [], [], 1
    for i in range(n):
        t0 = t_first + i * STEP_EVERY_S
        t, parent = t0, sid
        sid += 1
        kids = []
        for ph in ps.PHASES:
            kids.append({"name": f"serve.step.{ph}", "t0": t,
                         "t1": t + PHASE_MS[ph] / 1e3, "id": sid,
                         "parent": parent, "tags": {}})
            t += PHASE_MS[ph] / 1e3
            sid += 1
        spans.append({"name": "serve.step", "t0": t0, "t1": t, "id": parent,
                      "parent": None, "tags": {"tokens_fed": tokens,
                                               "step": i + 1}})
        spans += kids
        steps.append({"t0": t0 - 2e-6, "t1": t + 3e-6, "tokens": tokens,
                      "width": 1})
    return spans, steps


def _trace(steps, jitter_ns=()):
    """Device plane with one `jit_step` run a step (two ops, the second the
    paged kernel) and the host plane's `bench.*` spans."""
    ops, mods, marks = [], [], []
    for i, s in enumerate(steps):
        b0 = s["t0"] * 1e9 + OFFSET_NS + (jitter_ns[i] if jitter_ns else 0.0)
        d0 = (s["t0"] + 2e-6) * 1e9 + OFFSET_NS + DEVICE_FROM_MS * 1e6
        mods.append([f"jit_step({i})", d0, DEVICE_MS * 1e6])
        ops.append(["%fusion.1 = f32[8]{0} fusion(f32[8]{0} %x)", d0,
                    (DEVICE_MS - 1.0) * 1e6])
        ops.append(["%ragged_paged_attention.3 = f32[8]{0} custom-call("
                    "f32[8]{0} %fusion.1)", d0 + (DEVICE_MS - 1.0) * 1e6,
                    1.0 * 1e6])
        ops.append(["%fusion.2 = f32[8]{0} fusion(f32[8]{0} "
                    "%ragged_paged_attention.3)", d0 + DEVICE_MS * 1e6 - 10,
                    10.0])
        marks.append(["bench.step", b0, (s["t1"] - s["t0"]) * 1e9])
    w0 = steps[0]["t0"] * 1e9 + OFFSET_NS - 1e6
    w1 = steps[-1]["t1"] * 1e9 + OFFSET_NS + 1e6
    return {"planes": [
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Ops", "events": ops},
            {"name": "XLA Modules", "events": mods}]},
        {"name": "/host:CPU", "lines": [{"name": "spans", "events": [
            ["bench.trace_window", w0, w1 - w0]] + marks}]}]}


def _ctx(spans, steps, trace, monkeypatch):
    monkeypatch.setattr(ps, "ring_spans", lambda: spans)
    return {"cell": None, "peaks": None, "counters": {}, "end_to_end": {},
            "trace": trace,
            "window": {"kind": "closed_loop", "steps": steps,
                       "traced_steps": len(steps)}}


def test_a_planted_two_ms_launch_reads_two_ms(monkeypatch, capsys):
    spans, steps = _ring()
    ctx = _ctx(spans, steps, _trace(steps, jitter_ns=(0, 300, -300, 0)),
               monkeypatch)
    assert serve_host_launch_ms.read(ctx) == pytest.approx(2.0, abs=1e-6)
    assert serve_host_prelaunch_ms.read(ctx) == pytest.approx(0.7, abs=1e-6)
    assert serve_host_emit_ms.read(ctx) == pytest.approx(0.3, abs=1e-6)
    # wait ends 12.7 ms into the step, the device at 1.7 + 10.4
    assert serve_readback_exposed_ms.read(ctx) == pytest.approx(
        12.7 - 12.1, abs=1e-3)
    assert paged_attn_ms.read(ctx) == pytest.approx(1.0)
    # the four add up to the host wall less the device time, plus the
    # part of launch during which the device already ran (2.7 - 1.7 ms)
    host_minus_device = sum(PHASE_MS.values()) + 0.005 - DEVICE_MS
    total = 2.0 + 0.7 + 0.3 + (12.7 - 12.1)
    assert total == pytest.approx(host_minus_device + 1.0, abs=0.01)
    # computed once a run, one `info spans` line
    err = capsys.readouterr().err
    assert err.count("info spans ") == 1
    info = json.loads(err.split("info spans ", 1)[1].splitlines()[0])
    assert info["steps"] == N_STEPS
    assert info["phase_ms_median"]["launch"] == pytest.approx(2.0, abs=1e-6)
    assert info["host_minus_device_ms_a_step"] == pytest.approx(
        host_minus_device, abs=1e-3)


def test_the_clock_is_mapped_by_the_median_offset(monkeypatch):
    spans, steps = _ring()
    # one bench.step mark 0.4 ms late: the median does not follow it
    trace = _trace(steps, jitter_ns=(0, 0, 400_000, 0))
    assert ps.clock_offset_ns(trace, steps) == pytest.approx(OFFSET_NS)
    got = ps.collect(_ctx(spans, steps, trace, monkeypatch))
    st = got["steps"][1]
    assert st["admit"][0] == pytest.approx(
        (100.0 + STEP_EVERY_S) * 1e9 + OFFSET_NS)
    assert [st[a][1] == st[b][0] for a, b in zip(ps.PHASES, ps.PHASES[1:])]
    # a trace with one mark too few maps nothing
    trace["planes"][1]["lines"][0]["events"].pop()
    assert ps.clock_offset_ns(trace, steps) is None


def test_idle_is_split_over_the_phases_it_overlaps(monkeypatch, capsys):
    spans, steps = _ring()
    ctx = _ctx(spans, steps, _trace(steps), monkeypatch)
    got = ps.collect(ctx)
    idle = ps.attribute_idle(ctx["trace"], got["steps"])
    n = N_STEPS
    # a step's gap: admit, plan and the first ms of launch before the device
    # starts; the tail of wait (0.6 ms) and emit after it ends
    assert idle["serve.step.admit"] == pytest.approx(n * 0.2e-3, rel=1e-3)
    assert idle["serve.step.plan"] == pytest.approx(n * 0.5e-3, rel=1e-3)
    assert idle["serve.step.launch"] == pytest.approx(n * 1.0e-3, rel=1e-3)
    assert idle["serve.step.wait"] == pytest.approx(n * 0.6e-3, rel=1e-3)
    assert idle["serve.step.emit"] == pytest.approx(n * 0.3e-3, rel=1e-3)
    between = (n - 1) * (STEP_EVERY_S - 13.0e-3) + 2e-3
    assert idle["unattributed"] == pytest.approx(between, rel=1e-2)
    window = (steps[-1]["t1"] - steps[0]["t0"]) + 2e-3
    assert sum(idle.values()) == pytest.approx(
        window - n * DEVICE_MS / 1e3, rel=1e-3)


@pytest.mark.parametrize("fault", [
    "no_spans", "a_step_missing", "tokens_fed_mismatch", "a_child_missing",
    "step_outside_its_benchmark_step", "one_device_run_too_few",
    "train_window"])
def test_what_cannot_be_matched_reads_none(fault, monkeypatch):
    spans, steps = _ring()
    trace = _trace(steps)
    window_kind = "closed_loop"
    if fault == "no_spans":                 # the parent commit's program
        spans = []
    elif fault == "a_step_missing":
        spans = [s for s in spans if s["id"] not in range(7, 13)]
    elif fault == "tokens_fed_mismatch":
        steps[2]["tokens"] += 1
    elif fault == "a_child_missing":
        spans = [s for s in spans if s["id"] != 3]
    elif fault == "step_outside_its_benchmark_step":
        steps[1]["t1"] -= 1e-3
    elif fault == "one_device_run_too_few":
        trace["planes"][0]["lines"][1]["events"].pop()
    elif fault == "train_window":
        window_kind = "train_job"
    ctx = _ctx(spans, steps, trace, monkeypatch)
    ctx["window"]["kind"] = window_kind
    for reader in (serve_host_prelaunch_ms, serve_host_launch_ms,
                   serve_readback_exposed_ms, serve_host_emit_ms):
        assert reader.read(ctx) is None


def test_spans_of_other_steps_in_the_ring_are_left_out(monkeypatch):
    """With `MXTPU_TRACE=1` the ring holds every step of the run and the
    per-request spans too; only the traced steps' are read."""
    spans, steps = _ring(n=6)
    spans.append({"name": "serve.decode", "t0": 100.0, "t1": 100.01,
                  "id": 999, "parent": 1, "tags": {"tokens_fed": 1}})
    traced = steps[1:5]
    got = ps.collect(_ctx(spans, traced, _trace(traced), monkeypatch))
    assert [st["tags"]["step"] for st in got["steps"]] == [2, 3, 4, 5]


def test_paged_attn_ms_finds_the_kernel_by_name_alone(monkeypatch):
    spans, steps = _ring()
    ctx = _ctx(spans, steps, _trace(steps), monkeypatch)
    assert paged_attn_ms.read(ctx) == pytest.approx(1.0)
    # an op that only CONSUMES the kernel's result is not the kernel, and
    # a program without the kernel reads nothing
    for ln in ctx["trace"]["planes"][0]["lines"]:
        ln["events"] = [e for e in ln["events"]
                        if not e[0].startswith("%ragged_paged_attention")]
    assert paged_attn_ms.read(ctx) is None


def test_the_ring_of_a_program_without_it_is_empty(monkeypatch):
    import mxnet_tpu.tracing as tracing
    monkeypatch.delattr(tracing, "get_tracer")
    assert ps.ring_spans() == []


def test_on_the_ring_and_trace_recorded_on_the_chip(monkeypatch):
    """`recorded_spans.json`: the `serve` ring, the benchmark's step stamps
    and the reduced trace (module runs, merged busy intervals, `bench.*`
    spans) of one traced run on the v5e; the numbers the run printed."""
    with open(os.path.join(HERE, "recorded_spans.json")) as f:
        rec = json.load(f)
    ctx = _ctx(rec["ring"], rec["steps"], rec["trace"], monkeypatch)
    want = rec["read"]
    for name, reader in (
            ("serve_host_prelaunch_ms", serve_host_prelaunch_ms),
            ("serve_host_launch_ms", serve_host_launch_ms),
            ("serve_readback_exposed_ms", serve_readback_exposed_ms),
            ("serve_host_emit_ms", serve_host_emit_ms)):
        assert reader.read(ctx) == pytest.approx(want[name], rel=1e-6), name
    total = sum(want[k] for k in want if k.startswith("serve_"))
    assert total == pytest.approx(want["host_minus_device_ms_a_step"],
                                  rel=0.10)
    idle = ps.attribute_idle(ctx["trace"], ps.collect(ctx)["steps"])
    assert idle.get("unattributed", 0.0) < 0.10 * sum(idle.values())
