"""`BENCHMARK.json` against the contract's rules that a file can break
before any run: names, lengths, which cell reports what, every file found."""
import json
import os
import re

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
WIDTH = re.compile(r"(hidden|intermediate|latent|state|proj|_dim$|_rank$|"
                   r"head_size|head_dim|expan|experts_per)")


@pytest.fixture(scope="module")
def bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def _line(text, limit=200):
    return 1 <= len(text) <= limit and "\n" not in text and "\t" not in text


def test_top_level_keys_names_and_lengths(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) <= 65536
    assert 1 <= bench["run_seconds"] <= 51
    assert 338 * (bench["run_seconds"] + 60) + 24 * 180 + 1200 <= 43200
    assert all(_line(w) for w in bench["command"])
    assert any(w.startswith(bench["paths"][0] + "/")
               for w in bench["command"])
    for c in bench["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and _line(c["source"]) and _line(c["why"])
        assert c["file"].startswith(bench["paths"][0] + "/")
        assert not any(WIDTH.search(k) for k in c["reduced"])
        with open(os.path.join(ROOT, c["file"])) as f:
            assert set(c["reduced"]) == set(json.load(f)["reduced"])
    for w in bench["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["chips"] in (1, 4) and _line(w["why"])
    cells = [w["name"] for w in bench["workloads"]]
    assert len(set(cells)) == len(cells)
    assert sum(w["chips"] == 4 for w in bench["workloads"]) <= \
        max(1, len(cells) // 4)
    assert {w["config"] for w in bench["workloads"]} == \
        {c["name"] for c in bench["configs"]}


def test_every_metric_is_reported_where_it_says(bench):
    cells = {w["name"] for w in bench["workloads"]}
    e2e = {m["name"]: set(m.get("workloads", cells))
           for m in bench["end_to_end"]}
    assert e2e["setup_s"] == cells
    for m in bench["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                         "source"}
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        assert 0.01 <= m["bound"] <= 0.1
        assert m["source"] in ("host_clock", "device_trace")
        assert set(m.get("workloads", cells)) <= cells
    names = [m["name"] for m in bench["end_to_end"] + bench["per_layer"]]
    assert len(set(names)) == len(names)
    for m in bench["per_layer"]:
        assert set(m) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
        assert _line(m["layer"])
        assert set(m["workloads"]) <= e2e[m["moves"]], m["name"]
        assert os.path.isfile(os.path.join(
            ROOT, bench["paths"][0], "metrics", m["name"] + ".py"))
    for cell in cells:
        assert sum(cell in v for k, v in e2e.items() if k != "setup_s") >= 1
        assert any(cell in m["workloads"] for m in bench["per_layer"])
        kinds = [m["name"] for m in bench["per_layer"]
                 if cell in m["workloads"] and "mfu" in m["name"]]
        assert kinds, f"{cell} reports no whole-step mfu"


def test_every_cell_finds_its_files(bench):
    from benchmark.harness.cells import Cell
    for w in bench["workloads"]:
        cell = Cell(ROOT, w["name"])
        assert cell.traffic["kind"] and callable(cell.kind().run)
        assert cell.family() and cell.reference()
        assert cell.traffic["limits"], "a cell without limits proves nothing"
        for m in cell.per_layer():
            assert callable(cell.metric_reader(m["name"]))
