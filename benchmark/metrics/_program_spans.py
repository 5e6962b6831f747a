"""The program's own phase spans of the traced engine steps, on the device
trace's clock: what `serve_host_share` is made of.

While a profiler session captures, `Scheduler.step` records one
``serve.step`` span per step and five children that tile it
(``serve.step.admit`` / ``.plan`` / ``.launch`` / ``.wait`` / ``.emit``, with
`tokens_fed` on the parent) into the process-global ring
``mxnet_tpu.tracing.get_tracer("serve")``, on `time.perf_counter`.  The ring
outlives the engine, which the driver has freed before any reader runs.
`collect` keeps the ``serve.step`` spans of the traced steps of
``window["steps"]`` and maps `perf_counter` onto the trace's clock by
pairing the i-th ``bench.step`` span of the trace with
``window["steps"][i]["t0"]`` (the median offset over the traced steps).

It answers None (each reader then leaves its metric out) unless there is
exactly one program step for every traced benchmark step, each inside that
step's ``[t0, t1]`` and with `tokens_fed` equal to the driver's mirrored
`tokens`: a program without these spans, a lost step or a miscounted one
reads as nothing, never as a number.
"""
from __future__ import annotations

import json
import sys

from benchmark.metrics._common import SERVE_PROGRAM
from benchmark.reduce import xplane
from benchmark.reduce.stats import median

PHASES = ("admit", "plan", "launch", "wait", "emit")
STEP = "serve.step"
BENCH_STEP = "bench.step"
_KEY = "_program_spans"
_SLACK_S = 50e-6          # a program step lies inside its benchmark step


def ring_spans() -> list:
    """[{"name", "t0", "t1", "id", "parent", "tags"}] of the program's
    `serve` ring, [] where the program has no such ring."""
    try:
        from mxnet_tpu import tracing
        spans = tracing.get_tracer("serve").spans()
    except (ImportError, AttributeError):
        return []
    return [{"name": s.name, "t0": s.t0, "t1": s.t1, "id": s.span_id,
             "parent": s.parent_id, "tags": dict(s.tags)} for s in spans]


def match_steps(spans: list, steps: list):
    """One ``serve.step`` with its five children for each of `steps`, in
    order, or None (see the module's docstring)."""
    if not steps:
        return None
    lo, hi = steps[0]["t0"] - _SLACK_S, steps[-1]["t1"] + _SLACK_S
    parents = sorted((s for s in spans if s["name"] == STEP
                      and lo <= s["t0"] and s["t1"] <= hi),
                     key=lambda s: s["t0"])
    if len(parents) != len(steps):
        return None
    kids = {}
    for s in spans:
        if s["parent"] is not None and s["name"].startswith(STEP + "."):
            kids.setdefault(s["parent"], {})[s["name"][len(STEP) + 1:]] = s
    out = []
    for p, rec in zip(parents, steps):
        mine = kids.get(p["id"], {})
        if set(mine) != set(PHASES) \
                or p["t0"] < rec["t0"] - _SLACK_S \
                or p["t1"] > rec["t1"] + _SLACK_S \
                or p["tags"].get("tokens_fed") != rec["tokens"]:
            return None
        out.append({"step": (p["t0"], p["t1"]), "tags": p["tags"],
                    **{ph: (mine[ph]["t0"], mine[ph]["t1"])
                       for ph in PHASES}})
    return out


def clock_offset_ns(trace: dict, steps: list):
    """Nanoseconds to add to `perf_counter * 1e9` to land on the trace's
    clock, or None unless the trace holds one ``bench.step`` a step."""
    marks = xplane.host_spans(trace, BENCH_STEP)
    if not steps or len(marks) != len(steps):
        return None
    return median(m[1] - s["t0"] * 1e9 for m, s in zip(marks, steps))


def attribute_idle(trace: dict, mapped: list) -> dict:
    """Device idle seconds of the traced window by program phase.  The
    `xplane.idle_gaps` rule (gaps between the merged device ops of one chip,
    those under 2 us summed as `between_ops`), except that a gap is SPLIT
    over the phases it overlaps and not given whole to the span at its
    midpoint: one gap a step runs from the device's last op through emit,
    the pause between steps, admit, plan and launch.  What no program phase
    covers is `unattributed`."""
    t0, t1 = xplane.window_of(trace)
    planes = xplane.device_planes(trace)
    if not planes:
        return {}
    busy = xplane.merged_intervals(
        xplane.clip(xplane.line_events(planes[0], xplane.OPS_LINE), t0, t1))
    edges = [t0] + [x for iv in busy for x in iv] + [t1]
    spans = [(f"{STEP}.{ph}", *st[ph]) for st in mapped for ph in PHASES]
    out = {}
    for i in range(0, len(edges), 2):
        a, b = edges[i], edges[i + 1]
        if b <= a:
            continue
        if b - a < 2000.0:
            out["between_ops"] = out.get("between_ops", 0.0) + (b - a) / 1e9
            continue
        left = b - a
        for name, s, e in spans:
            cut = min(b, e) - max(a, s)
            if cut > 0:
                out[name] = out.get(name, 0.0) + cut / 1e9
                left -= cut
        out["unattributed"] = out.get("unattributed", 0.0) + left / 1e9
    return out


def collect(ctx):
    """{"steps": the traced steps' phases as (start_ns, end_ns) on the
    trace's clock, "device_end_ns": the end of each step's program on the
    chip} — computed once a run, with one `info spans` line on standard
    error — or None."""
    if _KEY not in ctx:
        ctx[_KEY] = _collect(ctx)
    return ctx[_KEY]


def _collect(ctx):
    trace, win = ctx["trace"], ctx["window"]
    if trace is None or win.get("kind") != "closed_loop":
        return None
    steps = win["steps"][:win["traced_steps"]]
    matched = match_steps(ring_spans(), steps)
    offset = clock_offset_ns(trace, steps)
    runs = xplane.module_runs(trace, SERVE_PROGRAM)
    if matched is None or offset is None or len(runs) != len(steps):
        return None

    def on_trace(span):
        return (span[0] * 1e9 + offset, span[1] * 1e9 + offset)
    mapped = [{k: (v if k == "tags" else on_trace(v))
               for k, v in st.items()} for st in matched]
    got = {"steps": mapped, "device_end_ns": [r[1] + r[2] for r in runs]}
    idle = attribute_idle(trace, mapped)
    host = sum(s["t1"] - s["t0"] for s in steps)
    dev = sum(r[2] for r in runs) / 1e9
    total_idle = sum(idle.values())
    n = len(steps)
    print("info spans " + json.dumps({
        "steps": n,
        "phase_ms_median": {
            **{ph: phase_ms(got, ph) for ph in PHASES},
            "readback_exposed": readback_exposed_ms(got)},
        # means, for the check of the clock mapping: admit + plan + launch
        # + emit + readback_exposed = host wall - device time + the part of
        # launch during which the chip already ran
        "phase_ms_mean": {
            **{ph: sum(_phase_lengths(got, ph)) / n for ph in PHASES},
            "readback_exposed": sum(_readbacks(got)) / n,
            "launch_while_device_runs": sum(
                max(0.0, st["launch"][1] - r[1])
                for st, r in zip(mapped, runs)) / 1e6 / n},
        "host_minus_device_ms_a_step": 1e3 * (host - dev) / n,
        "idle_s_by_phase": idle,
        "unattributed_share": (idle.get("unattributed", 0.0) / total_idle
                               if total_idle else 0.0),
        "clock_offset_ns": offset}), file=sys.stderr)
    return got


def _phase_lengths(got, *phases) -> list:
    return [sum(st[ph][1] - st[ph][0] for ph in phases) / 1e6
            for st in got["steps"]]


def _readbacks(got) -> list:
    return [(st["wait"][1] - end) / 1e6
            for st, end in zip(got["steps"], got["device_end_ns"])]


def phase_ms(got, *phases) -> float:
    """Median over the traced steps of the summed length of `phases`."""
    return median(_phase_lengths(got, *phases))


def readback_exposed_ms(got) -> float:
    """Median of (end of ``serve.step.wait``) - (end of that step's program
    on the chip): the transfer back and the wake-up."""
    return median(_readbacks(got))
