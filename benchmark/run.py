#!/usr/bin/env python3
"""The benchmark's one command.

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

A new process for every run: it looks for the chip (and exits non-zero with
no result line if JAX's default backend is not a TPU with the cell's chips),
builds the cell's program from the seed, warms this cell's shapes only,
measures for `--seconds`, reads the peak memory, frees the program, compares
what the timed path produced with the plain reference, and prints ONE JSON
object as the last line of standard output.  Everything else goes to
standard error.  See `benchmark/README.md`.
"""
from __future__ import annotations

import time
_T_PROCESS = time.perf_counter()

import argparse
import json
import math
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


class Context:
    """What a traffic kind's driver gets from the harness."""

    def __init__(self, cell, seed, seconds, trace, devices, out_dir,
                 t_process, control=0):
        self.cell, self.seed, self.seconds = cell, int(seed), float(seconds)
        self.trace, self.devices, self.out_dir = bool(trace), devices, out_dir
        self.t_process = t_process
        self.control = int(control)   # 1: show unjudged numbers; 2: + controls
        self.setup_s = None
        self.watch = None
        self.setup_cache = None

    def before_window(self):
        """The window's first instant is next: set-up ends here."""
        from mxnet_tpu import telemetry
        telemetry.disable()
        if self.watch is not None:
            self.setup_cache = self.watch.snapshot()
        self.setup_s = time.perf_counter() - self.t_process

    def after_window(self) -> int:
        """Compile requests (hits or misses) made inside the window."""
        if self.watch is None:
            return 0
        hits, misses = self.watch.snapshot()
        return (hits - self.setup_cache[0]) + (misses - self.setup_cache[1])

    def describe_device(self, *compiled) -> dict:
        """Call while the program's state is still alive; `compiled` are
        the timed executables."""
        from benchmark.harness.device import describe, temp_bytes
        return describe(self.devices,
                        max([temp_bytes(c) for c in compiled] or [0]))


def run_cell(root: str, workload: str, seed: int, seconds: float, trace: bool,
             require_chip: bool = True, control: int = 0,
             t_process: float = None) -> dict:
    """One run of one cell -> the result object (not printed)."""
    from benchmark.harness.cells import Cell
    cell = Cell(root, workload)

    import mxnet_tpu  # noqa: F401  (fails here in a tree without the program)
    from mxnet_tpu import telemetry
    from mxnet_tpu.runtime import enable_compile_cache
    import jax
    from benchmark.harness.device import CompileWatch, require_chips

    if require_chip:
        cache_dir = enable_compile_cache()
        devices = require_chips(cell.chips)
    else:                                   # a test's rehearsal on the CPU
        cache_dir = None
        devices = jax.devices()[:cell.chips]
    telemetry.enable()                      # arms the program's counters
    out_dir = os.path.join(root, "benchmark_out")
    os.makedirs(out_dir, exist_ok=True)
    ctx = Context(cell, seed, seconds, trace, devices, out_dir,
                  t_process if t_process is not None else time.perf_counter(),
                  control=control)
    ctx.watch = CompileWatch()
    print(f"info workload={workload} seed={seed} seconds={seconds} "
          f"trace={int(trace)} cache_dir={cache_dir} devices="
          f"{[d.device_kind for d in devices]}", file=sys.stderr)

    res = cell.kind().run(ctx)

    values = dict(res["end_to_end"])
    values["setup_s"] = ctx.setup_s
    metrics = {}
    if trace:
        from benchmark.reduce.peaks import peaks
        from benchmark.reduce import xplane
        rctx = {"cell": cell, "window": res["window"], "trace": res["trace"],
                "peaks": peaks(devices[0].device_kind) if require_chip
                else None,
                "counters": {"compile_cache_misses": ctx.setup_cache[1],
                             "compile_cache_hits": ctx.setup_cache[0]},
                "end_to_end": values}
        for m in cell.per_layer():
            v = cell.metric_reader(m["name"])(rctx)
            if v is not None:
                metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
        if res["trace"] is not None and xplane.device_planes(res["trace"]):
            bi = xplane.busy_idle(res["trace"])
            res["device"]["busy_s"] = bi["busy_s"]
            res["device"]["window_s"] = bi["window_s"]
            res["breakdown"] = {
                "device_ops": xplane.top_ops(res["trace"]),
                "idle_gaps": xplane.idle_gaps(res["trace"])}
    else:
        for m in cell.end_to_end():
            if m["name"] not in values:
                raise RuntimeError(f"the run of {workload} produced no "
                                   f"{m['name']}")
            metrics[m["name"]] = {"value": float(values[m["name"]]),
                                  "unit": m["unit"]}
    print("info " + json.dumps({"end_to_end": values, "window": {
        k: v for k, v in res["window"].items() if k != "steps"},
        "cache": {"hits": ctx.setup_cache[0], "misses": ctx.setup_cache[1]}}),
        file=sys.stderr)
    for name, (value, limit) in res["checks"].items():
        print(f"compared {name}={value} limit={limit}", file=sys.stderr)
    line = {"correct": res["correct"], "attempted": res["attempted"],
            "failed": res["failed"], "metrics": metrics,
            "device": res["device"]}
    if "breakdown" in res:
        line["breakdown"] = res["breakdown"]
    line["compared"] = {k: {"value": _finite(v[0]), "limit": v[1]}
                        for k, v in res["checks"].items()}
    return line


def _finite(x):
    """A number as JSON can hold it: null for inf and nan (a comparison that
    had nothing to compare, or blew up, is shown as null and is not
    correct)."""
    return x if x is None or math.isfinite(x) else None


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", type=int, choices=(0, 1, 2), default=0,
                    help="1: also show the numbers that have no limit; 2: "
                         "and the lower-precision control's and the faults' "
                         "readings (for setting limits; the driver never "
                         "passes it)")
    a = ap.parse_args(argv)
    from benchmark.harness.device import NoChip
    try:
        line = run_cell(ROOT, a.workload, a.seed, a.seconds, bool(a.trace),
                        control=a.control, t_process=_T_PROCESS)
    except NoChip as e:
        print(f"benchmark: {e}", file=sys.stderr)
        return 1
    sys.stderr.flush()
    print(json.dumps(line, allow_nan=False), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
