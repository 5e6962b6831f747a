"""How the harness finds a cell's files.  Everything is looked up by the
names in ``BENCHMARK.json``; nothing here knows any cell.

- a workload names a `config` and a `traffic`;
- the configuration's sizes are in the `file` its ``configs`` entry gives
  (``benchmark/configs/<config>.json``); its ``family`` names the module
  ``benchmark/families/<family>.py`` that builds the program's objects and
  the module ``benchmark/reference/<family>.py`` that is compared with;
- the traffic mix is ``benchmark/traffic/<traffic>.json``; its ``kind`` names
  the general driver ``benchmark/kinds/<kind>.py``;
- a per-layer metric `m` is read by ``benchmark/metrics/<m>.py::read``.
"""
from __future__ import annotations

import importlib.util
import json
import os

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: str, name: str):
    if not os.path.isfile(path):
        raise FileNotFoundError(path)
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class Cell:
    """One entry of ``workloads`` with everything it points at."""

    def __init__(self, root: str, workload: str):
        self.root = os.path.abspath(root)
        self.bench = _load_json(os.path.join(self.root, "BENCHMARK.json"))
        by_name = {w["name"]: w for w in self.bench["workloads"]}
        if workload not in by_name:
            raise KeyError(f"no workload {workload!r} in BENCHMARK.json; "
                           f"it has {sorted(by_name)}")
        self.workload = by_name[workload]
        self.name = workload
        self.chips = int(self.workload["chips"])
        cfg_entry = {c["name"]: c for c in self.bench["configs"]}[
            self.workload["config"]]
        self.config = _load_json(os.path.join(self.root, cfg_entry["file"]))
        self.bench_dir = os.path.join(self.root, self.bench["paths"][0])
        self.traffic = _load_json(os.path.join(
            self.bench_dir, "traffic", self.workload["traffic"] + ".json"))

    def _module(self, sub: str, name: str):
        local = os.path.join(self.bench_dir, sub, name + ".py")
        if not os.path.isfile(local):      # a test's root borrows the code
            local = os.path.join(BENCH_DIR, sub, name + ".py")
        return load_module(local, f"benchmark_{sub}_{name}".replace(
            ".", "_").replace("-", "_"))

    def family(self):
        return self._module("families", self.config["family"])

    def reference(self):
        from importlib import import_module
        return import_module(f"benchmark.reference.{self.config['family']}")

    def kind(self):
        return self._module("kinds", self.traffic["kind"])

    def _metrics(self, group: str) -> list:
        out = []
        for m in self.bench[group]:
            if "workloads" in m and self.name not in m["workloads"]:
                continue
            out.append(m)
        return out

    def end_to_end(self) -> list:
        return self._metrics("end_to_end")

    def per_layer(self) -> list:
        return self._metrics("per_layer")

    def metric_reader(self, name: str):
        return self._module("metrics", name).read
