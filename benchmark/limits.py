#!/usr/bin/env python3
"""Readings for setting a cell's limits: the program on many seeds and the
lower-precision control (and a training cell's planted faults) on each, in
ONE process, so that set-up's compile is paid once.

    python3 benchmark/limits.py --workload <name> --seeds 1,2,3 --seconds 20

Prints one JSON line per seed (the run's `compared` numbers, the control's
and the faults' among them) and, last, for every number the largest reading
over the seeds.  `PERF.md` says how a limit follows from them.  Not part of
a benchmark run; the driver never calls it.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--controls", type=int, default=None,
                    help="read the control and the faults on the first N "
                         "seeds only (default: on all)")
    a = ap.parse_args(argv)
    from benchmark.run import run_cell
    rows = []
    for i, seed in enumerate(int(s) for s in a.seeds.split(",")):
        line = run_cell(ROOT, a.workload, seed, a.seconds, False,
                        control=2 if a.controls is None or i < a.controls else 1)
        row = {"seed": seed, "correct": line["correct"],
               "numbers": {k: v["value"]
                           for k, v in line["compared"].items()}}
        rows.append(row)
        print(json.dumps(row), flush=True)
    names = sorted({k for r in rows for k in r["numbers"]})
    print(json.dumps({"largest": {
        k: max(r["numbers"][k] for r in rows if k in r["numbers"])
        for k in names}, "smallest": {
        k: min(r["numbers"][k] for r in rows if k in r["numbers"])
        for k in names}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
