"""MP DataLoader scaling microbench (VERDICT r3 next-step #5).

Measures epoch wall-clock of a CPU-bound pure-Python per-item transform
through `gluon.data.DataLoader` at 1..N worker processes — the workload a
thread pool cannot scale past ~1 core (GIL).  Parity target: the
reference's multiprocessing loader speedup
(`python/mxnet/gluon/data/dataloader.py` worker pool).

Run:  python tools/mp_loader_scaling.py [--workers 1 2 4] [--items 32]
      [--work 300000] [--batch 4]
Prints one JSON line per worker count:
  {"workers": W, "epoch_seconds": T, "speedup_vs_1": S}

`tests/unittest/test_gluon_data.py::test_mp_dataloader_scales_past_gil`
drives this same code path with an asserted >1.4x at 2 workers, so the
scaling property executes in CI (4-vCPU runners), not just here.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


class CpuBoundDataset:
    """Pure-Python busy transform; scales only with real processes."""

    def __init__(self, n: int, work: int):
        self._n = n
        self._work = work

    def __len__(self):
        return self._n

    def __getitem__(self, i):
        import numpy as onp
        acc = float(i)
        for k in range(self._work):
            acc = (acc * 1.0000001 + k % 7) % 1e9
        return onp.asarray([acc], onp.float32)


def epoch_seconds(workers: int, items: int, work: int, batch: int) -> float:
    from mxnet_tpu.gluon.data import DataLoader
    ds = CpuBoundDataset(items, work)
    dl = DataLoader(ds, batch_size=batch, num_workers=workers,
                    thread_pool=False, timeout=600)
    list(dl)                       # warm epoch: worker spawn + imports
    t0 = time.perf_counter()
    list(dl)
    return time.perf_counter() - t0


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workers", type=int, nargs="+", default=[1, 2, 4])
    ap.add_argument("--items", type=int, default=32)
    ap.add_argument("--work", type=int, default=300000)
    ap.add_argument("--batch", type=int, default=4)
    args = ap.parse_args(argv)
    # hard-set, not setdefault, and HERE rather than at import (pytest
    # imports epoch_seconds — an import side effect would overwrite the
    # suite's ambient platform): the measurement is host-side, and
    # spawned workers must never try to claim an accelerator
    os.environ["JAX_PLATFORMS"] = "cpu"
    import jax
    jax.config.update("jax_platforms", "cpu")
    base = None
    for w in args.workers:
        t = epoch_seconds(w, args.items, args.work, args.batch)
        if base is None:
            base = t
        print(json.dumps({"workers": w, "epoch_seconds": round(t, 3),
                          "speedup_vs_1": round(base / t, 2),
                          "nproc": os.cpu_count()}), flush=True)


if __name__ == "__main__":
    main()
