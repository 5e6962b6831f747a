"""A tiny `afmoe` cell end to end on the CPU (family, engine with the two
cache groups, kind, reference, the new readers), and the planted faults the
comparison has to catch."""
import json
import os

import pytest

from benchmark.run import run_cell
from benchmark.tests import tiny

LAYERS = ["sliding_attention"] * 4 + ["full_attention"]
AFMOE = {
    "family": "afmoe", "hidden_size": 64, "head_dim": 16,
    "num_attention_heads": 4, "num_key_value_heads": 2,
    "intermediate_size": 128, "moe_intermediate_size": 32,
    "num_hidden_layers": 60, "num_dense_layers": 6, "num_experts": 8,
    "num_experts_per_tok": 2, "num_shared_experts": 1, "route_scale": 2.448,
    "sliding_window": 8, "rope_theta": 10000, "rms_norm_eps": 1e-5,
    "max_position_embeddings": 256, "mup_enabled": True, "vocab_size": 96,
    "depth": {"num_hidden_layers": 5, "num_dense_layers": 1,
              "layer_types": LAYERS},
    "experts_held": {"first": 2, "count": 4},
    "vocabulary": {"first_row": 96, "rows": 96, "published": 768},
    "dtype": "float32",
    "engine": {"max_len": 96, "max_slots": 4, "page_size": 4,
               "prefill_chunk": 4},
    "reduced": {"depth": "", "experts_held": "", "vocabulary": ""}}
# float32 program against the float32 reference: what is left is the order
# of summation (1e-4 leaves room over the readings, ~1e-5); a routing flip
# or a planted fault moves a logit by whole tenths
MIX = {"kind": "closed_loop_blocked", "clients": 4, "prompt_lens": [6, 13, 22, 37],
       "output_lens": [9, 14, 11, 17], "ramp_steps": 6,
       "ramp_fractions": [0.5, 1.0, 0.25, 0.75], "sample_requests": 6,
       "reference_pad_to": 64,
       "limits": {"token_gap": 1e-4, "min_compared_tokens": 8,
                  "route_margin_eps": 1e-6, "max_left_out_share": 0.25}}
CELL = "tiny-afmoe.tiny-mixed"
NEW = ("serve_mfu_afmoe", "moe_expert_ms", "moe_gmm_roofline",
       "paged_attn_roofline", "moe_expert_load_max_over_mean")


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    root = tiny.make_root(str(tmp_path_factory.mktemp("bench_afmoe")),
                          extra_traffic={"tiny-mixed": MIX})
    with open(os.path.join(root, "benchmark", "configs",
                           "tiny-afmoe.json"), "w") as f:
        json.dump(AFMOE, f)
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as f:
        bench = json.load(f)
    bench["configs"].append({"name": "tiny-afmoe", "source": "tiny",
                             "why": "tiny", "reduced": [],
                             "file": "benchmark/configs/tiny-afmoe.json"})
    bench["workloads"] = [w for w in bench["workloads"]
                          if w["traffic"] != "tiny-mixed"]
    bench["workloads"].append({"name": CELL, "config": "tiny-afmoe",
                               "traffic": "tiny-mixed", "chips": 1,
                               "why": "tiny"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        m["workloads"] = [w for w in m["workloads"]
                          if not w.endswith(".tiny-mixed")]
        if m["name"] in ("setup_s", "serve_tokens_per_s", "pool_write_ms",
                         "serve_host_emit_ms", "compile_cache_misses") \
                or m["name"] in NEW:
            m["workloads"].append(CELL)
    with open(path, "w") as f:
        json.dump(bench, f)
    return root


def _run(root, seed=2**31 + 11, trace=False):
    return run_cell(root, CELL, seed, 0.5, trace, require_chip=False)


def test_tiny_afmoe_cell_agrees_with_its_reference(root):
    line = _run(root)
    assert line["correct"] and line["failed"] == 0 and line["attempted"] > 0
    assert line["compared"]["token_gap"]["value"] < 1e-4
    assert line["compared"]["left_out_share"]["value"] <= 0.25
    assert set(line["metrics"]) == {"serve_tokens_per_s", "setup_s"}


def test_positions_with_a_near_tie_are_left_out_and_their_share_is_held(
        root, monkeypatch):
    """With an epsilon as wide as the scores themselves every position
    whose last-in or first-out expert is held here is a near-tie: they are
    left out, counted, and their share fails its limit."""
    from benchmark.harness.cells import Cell
    real = Cell.__init__

    def wide(self, *a, **kw):
        real(self, *a, **kw)
        self.traffic["limits"] = dict(self.traffic["limits"],
                                      route_margin_eps=1.0)
    monkeypatch.setattr(Cell, "__init__", wide)
    line = _run(root)
    assert line["compared"]["left_out_share"]["value"] > 0.25
    assert not line["correct"]


def test_traced_run_off_the_chip_reports_the_routing_counter_only(root):
    """No device plane on the CPU: the device-trace readers and the shares
    of a peak read None and are left out; the program's routing counts are
    read only beside a device trace too (the spans are matched to it)."""
    line = _run(root, trace=True)
    assert line["correct"]
    assert not set(line["metrics"]) & set(NEW)
    assert "compile_cache_misses" in line["metrics"]


FAULTS = {
    "held_expert_dropped": ("mxnet_tpu.ops.pallas.moe_gmm", "plan_rows"),
    "top_3_for_top_4": ("mxnet_tpu.serve.decode", "moe_ffn"),
    "route_scale_left_out": ("mxnet_tpu.serve.decode", "moe_ffn"),
    "window_ignored_on_a_sliding_layer": (
        "mxnet_tpu.serve.kv_cache", "make_paged_kv_fn"),
    "rope_on_the_full_layer": ("mxnet_tpu.serve.decode", "decode_spec"),
}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_planted_fault_reads_above_the_limit(root, monkeypatch, fault):
    import dataclasses
    import importlib
    mod = importlib.import_module(FAULTS[fault][0])
    real = getattr(mod, FAULTS[fault][1])
    if fault == "held_expert_dropped":
        def planted(group_of_pair, n_groups, *a, **kw):
            import jax.numpy as jnp
            gone = jnp.where(group_of_pair == 1, n_groups, group_of_pair)
            return real(gone, n_groups, *a, **kw)
    elif fault == "top_3_for_top_4":          # here: top-1 for top-2
        def planted(x, L, spec, valid=None):
            return real(x, L, dataclasses.replace(
                spec, top_k=spec.top_k - 1), valid)
    elif fault == "route_scale_left_out":
        def planted(x, L, spec, valid=None):
            return real(x, L, dataclasses.replace(spec, route_scale=1.0),
                        valid)
    elif fault == "window_ignored_on_a_sliding_layer":
        def planted(*a, layer_plan=None, **kw):
            g, i, _ = layer_plan[1]
            plan = (layer_plan[0], (g, i, 10**6)) + tuple(layer_plan[2:])
            return real(*a, layer_plan=plan, **kw)
    else:
        def planted(cfg):
            spec = real(cfg)
            layers = tuple(dataclasses.replace(ls, rope=True)
                           for ls in spec.layers)
            return dataclasses.replace(spec, layers=layers)
    monkeypatch.setattr(mod, FAULTS[fault][1], planted)
    if fault == "rope_on_the_full_layer":
        import mxnet_tpu.serve.engine as E
        monkeypatch.setattr(E, "decode_spec", planted)
    elif fault == "window_ignored_on_a_sliding_layer":
        import mxnet_tpu.serve.engine as E
        monkeypatch.setattr(E, "make_paged_kv_fn", planted)
    line = _run(root)
    assert not line["correct"]
    assert line["compared"]["token_gap"]["value"] > \
        line["compared"]["token_gap"]["limit"]
