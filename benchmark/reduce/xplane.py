"""From a profiler trace to busy/idle, per-op time and attributed idle gaps.

The reduction works on a neutral structure, so that it can be checked on a
small recorded trace kept as JSON::

    {"planes": [{"name": "/device:TPU:0",
                 "lines": [{"name": "XLA Ops",
                            "events": [[name, start_ns, dur_ns], ...]}]}]}

`load` builds that structure from an ``.xplane.pb`` with nothing but JAX.
Device planes are those named ``/device:TPU:<n>``; on each, the line
``XLA Ops`` holds one event per executed HLO op and ``XLA Modules`` one per
executed program.  Host spans written by ``jax.profiler.TraceAnnotation`` are
events on the lines of ``/host:CPU``, on the same clock.
"""
from __future__ import annotations

import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_PLANE = "/host:CPU"


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def load(path: str, keep_lines=(OPS_LINE, MODULES_LINE),
         host_prefix: str = "bench.") -> dict:
    """Neutral structure of the device planes' op and module lines and of
    the host spans whose name starts with `host_prefix`."""
    import jax
    data = jax.profiler.ProfileData.from_file(path)
    planes = []
    for plane in data.planes:
        if DEVICE_PLANE.match(plane.name):
            lines = [{"name": ln.name,
                      "events": [[e.name, float(e.start_ns),
                                  float(e.duration_ns)] for e in ln.events]}
                     for ln in plane.lines if ln.name in keep_lines]
            planes.append({"name": plane.name, "lines": lines})
        elif plane.name == HOST_PLANE:
            spans = [[e.name, float(e.start_ns), float(e.duration_ns)]
                     for ln in plane.lines for e in ln.events
                     if e.name.startswith(host_prefix)]
            planes.append({"name": plane.name,
                           "lines": [{"name": "spans", "events": spans}]})
    return {"planes": planes}


def device_planes(trace: dict) -> list:
    out = [p for p in trace["planes"] if DEVICE_PLANE.match(p["name"])]
    return sorted(out, key=lambda p: int(DEVICE_PLANE.match(p["name"])[1]))


def line_events(plane: dict, line_name: str) -> list:
    for ln in plane["lines"]:
        if ln["name"] == line_name:
            return sorted(ln["events"], key=lambda e: e[1])
    return []


def host_spans(trace: dict, name: str = None) -> list:
    out = []
    for p in trace["planes"]:
        if p["name"] == HOST_PLANE:
            for ln in p["lines"]:
                out.extend(e for e in ln["events"]
                           if name is None or e[0] == name)
    return sorted(out, key=lambda e: e[1])


def clip(events, t0: float, t1: float) -> list:
    """Events cut to [t0, t1]; those wholly outside are dropped."""
    out = []
    for name, s, d in events:
        a, b = max(s, t0), min(s + d, t1)
        if b > a:
            out.append([name, a, b - a])
    return out


def merged_intervals(events) -> list:
    """Union of the events' intervals as sorted disjoint [start, end]."""
    out = []
    for _, s, d in sorted(events, key=lambda e: e[1]):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], s + d)
        else:
            out.append([s, s + d])
    return out


def busy_ns(events) -> float:
    return sum(b - a for a, b in merged_intervals(events))


def window_of(trace: dict, span_name: str = "bench.trace_window"):
    """[t0, t1] of the traced window: the host span of that name if the
    trace has it, else first start to last end of all device ops."""
    spans = host_spans(trace, span_name)
    if spans:
        _, s, d = spans[0]
        return s, s + d
    evs = [e for p in device_planes(trace) for e in line_events(p, OPS_LINE)]
    if not evs:
        raise ValueError("the trace holds no device operation")
    return min(e[1] for e in evs), max(e[1] + e[2] for e in evs)


def busy_idle(trace: dict, window=None) -> dict:
    """Per chip: seconds in which an op ran (union of `XLA Ops` intervals
    inside the window) and the idle share; plus their mean and the worst."""
    t0, t1 = window or window_of(trace)
    chips = []
    for p in device_planes(trace):
        busy = busy_ns(clip(line_events(p, OPS_LINE), t0, t1))
        chips.append({"plane": p["name"], "busy_s": busy / 1e9,
                      "idle_share": 1.0 - busy / (t1 - t0)})
    if not chips:
        raise ValueError("the trace holds no device plane")
    return {"window_s": (t1 - t0) / 1e9, "chips": chips,
            "busy_s": sum(c["busy_s"] for c in chips) / len(chips),
            "max_idle_share": max(c["idle_share"] for c in chips)}


def op_seconds_by_name(events) -> dict:
    out = {}
    for name, _, d in events:
        out[name] = out.get(name, 0.0) + d / 1e9
    return out


def top_ops(trace: dict, window=None, n: int = 10) -> list:
    """[[name, seconds], ...] of the device ops that took most time, summed
    over chips and divided by their number."""
    t0, t1 = window or window_of(trace)
    planes = device_planes(trace)
    total = {}
    for p in planes:
        for k, v in op_seconds_by_name(
                clip(line_events(p, OPS_LINE), t0, t1)).items():
            total[k] = total.get(k, 0.0) + v / len(planes)
    return [[short_name(k), v] for k, v in
            sorted(total.items(), key=lambda kv: -kv[1])[:n]]


def idle_gaps(trace: dict, window=None, n: int = 10,
              plane_index: int = 0, short_ns: float = 2000.0) -> list:
    """[[what the host was doing, seconds], ...]: every gap between device
    ops on one chip goes to the innermost (shortest) host span that covers
    the gap's midpoint, `unattributed` if none does; summed by name, the
    largest first.  Gaps shorter than `short_ns` are launch-to-launch
    bubbles inside a program and are summed as `between_ops`."""
    t0, t1 = window or window_of(trace)
    planes = device_planes(trace)
    if not planes:
        return []
    busy = merged_intervals(
        clip(line_events(planes[plane_index], OPS_LINE), t0, t1))
    edges = [t0] + [x for iv in busy for x in iv] + [t1]
    gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]
    spans = [s for s in host_spans(trace) if s[0] != "bench.trace_window"]
    out = {}
    for a, b in gaps:
        if b - a < short_ns:
            out["between_ops"] = out.get("between_ops", 0.0) + (b - a) / 1e9
            continue
        mid = (a + b) / 2
        cover = [s for s in spans if s[1] <= mid <= s[1] + s[2]]
        name = min(cover, key=lambda s: s[2])[0] if cover else "unattributed"
        out[name] = out.get(name, 0.0) + (b - a) / 1e9
    return [[k, v] for k, v in sorted(out.items(), key=lambda kv: -kv[1])[:n]]


def module_runs(trace: dict, prefix: str, window=None,
                plane_index: int = 0) -> list:
    """Executed programs on one chip whose name starts with `prefix`, in
    order: [[name, start_ns, dur_ns], ...]."""
    planes = device_planes(trace)
    if not planes:
        return []
    evs = line_events(planes[plane_index], MODULES_LINE)
    if window is not None:
        evs = [e for e in evs if window[0] <= e[1] and e[1] + e[2] <= window[1]]
    return [e for e in evs if e[0].startswith(prefix)]


def ops_within(trace: dict, t0: float, t1: float, plane_index: int = 0) -> list:
    """Device ops of one chip that start inside [t0, t1)."""
    planes = device_planes(trace)
    if not planes:
        return []
    return [e for e in line_events(planes[plane_index], OPS_LINE)
            if t0 <= e[1] < t1]


# -- op names ----------------------------------------------------------------
# On a TPU an `XLA Ops` event is named by its HLO instruction:
#   %copy.12 = bf16[12,12,1025,128,64]{4,3,2,1,0:T(8,128)(2,1)} copy(%x)
#   %step.3 = (bf16[8,128]{...}, f32[8,128]{...}) custom-call(...)

_SHAPE = re.compile(r"\b([a-z]+\d+|pred)\[([\d,]*)\]")
_OPCODE = re.compile(r"^\s*([a-z][\w\-]*)\(")


def parse_op(text: str) -> dict:
    """{"name", "opcode", "results": [(dtype, dims), ...]} of one HLO
    instruction text; what cannot be parsed comes back with opcode ""."""
    name, sep, rest = text.partition(" = ")
    if not sep:
        return {"name": text.strip(), "opcode": "", "results": []}
    rest = rest.lstrip()
    if rest.startswith("("):
        depth = 0
        for i, ch in enumerate(rest):
            depth += ch == "("
            depth -= ch == ")"
            if depth == 0:
                break
        result_type, tail = rest[:i + 1], rest[i + 1:]
    else:
        result_type, _, tail = rest.partition(" ")
    m = _OPCODE.match(tail)
    results = []
    for dtype, dims in _SHAPE.findall(result_type):
        results.append((dtype, tuple(int(d) for d in dims.split(",") if d)))
    return {"name": name.strip(), "opcode": m.group(1) if m else "",
            "results": results}


def elements(dims) -> int:
    n = 1
    for d in dims:
        n *= d
    return n


def short_name(text: str, limit: int = 96) -> str:
    """`%copy.12 copy bf16[12,12,1025,128,64]`: enough to find the op."""
    op = parse_op(text)
    if not op["opcode"]:
        return text[:limit]
    shape = _SHAPE.search(text.partition(" = ")[2])
    return f"{op['name']} {op['opcode']} " \
        f"{shape.group(0) if shape else ''}"[:limit].strip()
