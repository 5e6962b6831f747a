"""The readers this PR adds, each on a small synthetic trace, with a case
that must read None; and `reduce/afmoe.py` against hand-worked numbers."""
import json
import os

import pytest

from benchmark.metrics import (_afmoe, _program_spans, moe_expert_load_max_over_mean,
                               moe_expert_ms, moe_gmm_roofline,
                               paged_attn_roofline, serve_mfu_afmoe,
                               train_collective_exposed_ms)
from benchmark.reduce import afmoe

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


@pytest.fixture(scope="module")
def cfg():
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "trinity-large-ep16.json")) as f:
        return json.load(f)


# -- reduce/afmoe.py against hand-worked numbers ------------------------------

def test_weight_and_pool_bytes_are_the_reckoned_ones(cfg):
    # attention 62.9 M a layer: 3072 x (2 x 6144 + 2 x 1024) + 6144 x 3072
    assert afmoe.attention_params(cfg) == 3072 * 14336 + 6144 * 3072 \
        == 62_914_560
    assert afmoe.expert_params(cfg) == 3 * 3072 * 3072 == 28_311_552
    # 5 x 62.9 M + dense 113.2 M + 64 held experts + 4 shared + 4 routers
    # + embedding and head 2 x 76.9 M, bf16; gains and biases float32
    params = 5 * 62_914_560 + 3 * 3072 * 12288 + 64 * 28_311_552 \
        + 4 * 28_311_552 + 4 * 256 * 3072 + 2 * 25024 * 3072
    small = 5 * (4 * 3072 + 2 * 128) + 3072 + 4 * 256
    assert afmoe.weight_bytes(cfg) == params * 2 + small * 4 == 5_020_062_720
    # a step that touched 59 of the 64 held experts reads 5 fewer
    assert afmoe.weight_bytes(cfg) - afmoe.weight_bytes(cfg, 59) \
        == 5 * 28_311_552 * 2
    assert afmoe.kv_bytes_per_token_layer(cfg) == 4096
    assert afmoe.pool_pages(cfg, "full") == 32 * 128 + 1
    assert afmoe.pool_pages(cfg, "sliding") == 32 * 34 + 1 == 1089
    assert afmoe.pool_bytes(cfg, "full") == 4097 * 128 * 4096
    assert round(afmoe.pool_bytes(cfg, "full") / 1e9, 2) == 2.15
    assert round(afmoe.pool_bytes(cfg, "sliding") / 1e9, 2) == 2.28
    live = afmoe.weight_bytes(cfg) + afmoe.pool_bytes(cfg, "full") \
        + afmoe.pool_bytes(cfg, "sliding")
    assert round(live / 1e9, 2) == 9.45


def test_step_counts_are_lower_bounds_under_the_window(cfg):
    # a sliding layer's query sees at least W / A of what a full one's does
    assert afmoe.window_share(cfg, 12544) == pytest.approx(4096 / 12544)
    assert afmoe.window_share(cfg, 2048) == 1.0
    per_key = 4 * 48 * 128
    assert afmoe.attention_flops(cfg, 1000, 12544) == pytest.approx(
        per_key * 1000 * (1 + 4 * 4096 / 12544))
    assert afmoe.kv_read_bytes(cfg, 1000, 4096) == 4096 * 1000 * 5
    # one token through the dense path: attention 5 x 62.9 M, dense FFN
    # 113.2 M, four shared experts and four routers, two FLOPs a weight
    f = afmoe.step_flops(cfg, 1, 0, 0, 0.0, 12544)
    assert f == 2 * (5 * 62_914_560 + 113_246_208 + 4 * 28_311_552
                     + 4 * 256 * 3072)
    assert afmoe.gmm_flops(cfg, 300) == 2 * 28_311_552 * 300
    assert afmoe.gmm_bytes(cfg, 0, 64) == 64 * 28_311_552 * 2


# -- the readers --------------------------------------------------------------

def _serve_ctx(cfg, n_steps=3, gmm_ms=0.6, rpa_ms=1.0, routing=True):
    ops, mods, steps = [], [], []
    for i in range(n_steps):
        t0 = 10.0 + i * 0.040
        d0 = t0 * 1e9
        mods.append([f"jit_step({i})", d0, 30e6])
        for li in range(8):
            ops.append([f"%mx_moe_gmm.{li} = f32[2560,6144]{{1,0}} "
                        "custom-call(s32[80]{0} %tg)", d0 + li * 1e6,
                        gmm_ms * 1e6])
        for li in range(5):
            ops.append([f"%ragged_paged_attention.{li} = "
                        "bf16[32,8,96,128]{3,2,1,0} custom-call(%q)",
                        d0 + 10e6 + li * 2e6, rpa_ms * 1e6])
        ops.append([f"%fusion.{i} = f32[8]{{0}} fusion(%x)", d0 + 25e6, 1e6])
        steps.append({"t0": t0, "t1": t0 + 0.035, "tokens": 300,
                      "width": 16, "attended": 600_000, "kv_read": 64_000,
                      "emitted": 14, "active": 32})
    trace = {"planes": [{"name": "/device:TPU:0", "lines": [
        {"name": "XLA Ops", "events": ops},
        {"name": "XLA Modules", "events": mods}]}]}
    tags = {"tokens_fed": 300, "moe_tokens_routed": 290,
            "moe_experts_touched": 59, "moe_experts_held": 64,
            "moe_load_max_over_mean": 4.5}
    if not routing:
        tags = {"tokens_fed": 300}
    cell = type("Cell", (), {
        "config": cfg, "traffic": {"reference_pad_to": 12544}})()
    ctx = {"cell": cell, "peaks": PEAKS, "counters": {}, "end_to_end": {},
           "trace": trace,
           "window": {"kind": "closed_loop", "steps": steps, "t0": 10.0,
                      "traced_steps": n_steps, "steady_from": 0}}
    # what `_program_spans.collect` would have matched
    ctx[_program_spans._KEY] = {"steps": [{"tags": dict(tags)}
                                          for _ in steps],
                                "device_end_ns": []}
    return ctx


def test_moe_readers_find_the_kernel_by_name_and_use_the_steps_counts(cfg):
    ctx = _serve_ctx(cfg)
    assert moe_expert_ms.read(ctx) == pytest.approx(8 * 0.6)
    # bytes-bound: 59 experts' matrices + the pairs' rows over 819 GB/s
    least = afmoe.gmm_bytes(cfg, 290, 59) / 819e9
    assert moe_gmm_roofline.read(ctx) == pytest.approx(
        100 * least / (8 * 0.6e-3))
    assert moe_gmm_roofline.read(ctx) < 100
    assert moe_expert_load_max_over_mean.read(ctx) == 4.5
    assert 0 < paged_attn_roofline.read(ctx) < 100
    mfu = serve_mfu_afmoe.read(ctx)
    b = afmoe.step_bytes(cfg, 300, 64_000, 59, 12544) / 819e9
    assert mfu == pytest.approx(100 * 3 * b / (ctx["window"]["steps"][-1][
        "t1"] - 10.0))


@pytest.mark.parametrize("fault", ["no_trace", "no_kernel", "no_routing",
                                   "train_cell", "gpt_cell"])
def test_moe_readers_read_nothing_where_there_is_nothing(cfg, fault):
    ctx = _serve_ctx(cfg, routing=fault != "no_routing")
    readers = [moe_expert_ms, moe_gmm_roofline, paged_attn_roofline,
               serve_mfu_afmoe, moe_expert_load_max_over_mean]
    if fault == "no_trace":
        ctx["trace"] = None
        ctx[_program_spans._KEY] = None
    elif fault == "no_kernel":
        for ln in ctx["trace"]["planes"][0]["lines"]:
            ln["events"] = [e for e in ln["events"]
                            if not e[0].startswith(("%mx_moe",
                                                    "%ragged_paged"))]
        readers = [moe_expert_ms, moe_gmm_roofline, paged_attn_roofline]
    elif fault == "no_routing":
        readers = [moe_gmm_roofline, serve_mfu_afmoe,
                   moe_expert_load_max_over_mean]
    elif fault == "train_cell":
        ctx["window"]["kind"] = "train_job"
        ctx[_program_spans._KEY] = None
    else:
        ctx["cell"].config = {"family": "gpt2"}
        readers = [serve_mfu_afmoe, paged_attn_roofline]
    assert [r.read(ctx) for r in readers] == [None] * len(readers)


def _train_ctx(events):
    mods = [["jit_step(0)", 0.0, 100e6]]
    trace = {"planes": [{"name": "/device:TPU:0", "lines": [
        {"name": "XLA Ops", "events": events},
        {"name": "XLA Modules", "events": mods}]}]}
    return {"cell": None, "peaks": PEAKS, "counters": {}, "end_to_end": {},
            "trace": trace, "window": {"kind": "train_job"}}


def test_collective_time_counts_only_what_no_other_op_covers():
    ar = "%all-reduce.3 = f32[768]{0} all-reduce(f32[768]{0} %g)"
    done = "%all-gather-done.1 = bf16[8,768]{1,0} all-gather-done(%s)"
    dot = "%fusion.9 = bf16[8,768]{1,0} fusion(%a, %b)"
    events = [[ar, 10e6, 4e6],            # alone: 4 ms exposed
              [dot, 20e6, 10e6],
              [done, 25e6, 10e6],         # 5 ms under the fusion, 5 alone
              [dot, 50e6, 5e6]]
    assert train_collective_exposed_ms.read(_train_ctx(events)) == \
        pytest.approx(9.0)
    assert train_collective_exposed_ms.is_collective(
        "%reduce-scatter.2 = f32[4]{0} fusion(%x), kind=kCustom")
    assert not train_collective_exposed_ms.is_collective(dot)


@pytest.mark.parametrize("fault", ["one_chip", "no_trace", "serve_cell"])
def test_collective_time_reads_nothing_where_there_is_nothing(fault):
    ctx = _train_ctx([["%fusion.1 = f32[8]{0} fusion(%x)", 1e6, 2e6]])
    if fault == "no_trace":
        ctx["trace"] = None
    elif fault == "serve_cell":
        ctx["window"]["kind"] = "closed_loop"
    assert train_collective_exposed_ms.read(ctx) is None
