"""The yardstick's arithmetic against hand-worked values."""
import json
import os

import pytest

from benchmark.reduce import flops, peaks, stats, xplane

HERE = os.path.dirname(os.path.abspath(__file__))
BERT = {"hidden_size": 768, "intermediate_size": 3072,
        "num_hidden_layers": 12, "vocab_size": 30522}
GPT2 = {"n_embd": 768, "n_layer": 12, "n_head": 12, "vocab_size": 50257,
        "n_positions": 1024, "n_inner": None}


def test_bert_flops_per_position_by_hand():
    h, i, v, L, m = 768, 3072, 30522, 128, 20
    layer = 2 * h * 3 * h + 2 * h * h + 4 * h * i + 4 * L * h
    fwd_seq = 12 * L * layer + m * (2 * h * h + 2 * h * v) + 2 * h * h + 4 * h
    assert flops.bert_forward_flops_per_sequence(BERT, L, m) == fwd_seq
    per_pos = flops.bert_train_flops_per_position(BERT, L, m)
    assert per_pos == pytest.approx(3 * fwd_seq / L)
    assert 0.54e9 < per_pos < 0.56e9          # "0.55 GFLOP a position"


def test_gpt2_weights_and_kv_by_hand():
    assert flops.gpt2_kv_bytes_per_token(GPT2) == 2 * 12 * 768 * 2 == 36864
    nbytes = flops.gpt2_weight_bytes(GPT2)
    assert 0.2485e9 < nbytes < 0.2495e9       # 124.4M parameters in bf16
    assert flops.gpt2_matmul_params(GPT2) == 12 * 12 * 768 * 768


def test_gpt2_step_is_memory_bound_in_decode_and_never_counts_padding():
    peak = peaks.peaks("TPU v5 lite")
    # 128 slots, one token each, 160 tokens of context each
    f = flops.gpt2_step_flops(GPT2, 128, 128 * 160, 128)
    b = flops.gpt2_step_bytes(GPT2, 128, 128 * 160)
    t, bound = flops.min_seconds(f, b, peak)
    assert bound == "memory"
    assert t == pytest.approx((flops.gpt2_weight_bytes(GPT2)
                               + 36864 * (128 * 160 + 128)) / 819e9)
    # an empty step needs only the weights: nothing is counted for idle slots
    assert flops.gpt2_step_flops(GPT2, 0, 0, 0) == 0.0


def test_attention_flops_forward_and_backward():
    fwd = flops.attention_flops(2, 3, 8, 8, 4)
    assert fwd == 2 * 2 * 2 * 3 * 8 * 8 * 4
    assert flops.attention_flops(2, 3, 8, 8, 4, backward=True) == 2 * fwd


def test_peaks_lookup_raises_on_unknown_device():
    assert peaks.peaks("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(peaks.UnknownDevice):
        peaks.peaks("TPU v99")
    with pytest.raises(peaks.UnknownDevice):
        peaks.peaks("cpu")


def test_percentile_on_a_known_sample():
    xs = list(range(1, 101))
    assert stats.percentile(xs, 95) == pytest.approx(95.05)
    assert stats.percentile(xs, 50) == pytest.approx(50.5)
    assert stats.percentile([3.0], 95) == 3.0
    assert stats.percentile([1, 2, 3, 4], 100) == 4
    with pytest.raises(ValueError):
        stats.percentile([], 95)


def test_iqr_spread_is_python_quartiles_over_median():
    xs = [10, 11, 12, 13, 14, 15]
    import statistics
    q = statistics.quantiles(xs, n=4)
    assert stats.iqr_spread(xs) == pytest.approx((q[2] - q[0]) / 12.5)


# -- the trace reduction on a hand-made trace ---------------------------------

def _trace():
    dev = {"name": "/device:TPU:0", "lines": [
        {"name": "XLA Ops", "events": [
            ["%a = f32[8]{0} add(%x, %y)", 100, 50],
            ["%b = f32[8]{0} multiply(%x, %y)", 140, 60],     # overlaps a
            ["%copy.1 = bf16[4,2]{1,0} copy(%p)", 400, 100],
            ["%a = f32[8]{0} add(%x, %y)", 700, 50]]},
        {"name": "XLA Modules", "events": [
            ["jit_step(1)", 100, 400], ["jit_other(2)", 690, 70]]}]}
    host = {"name": "/host:CPU", "lines": [{"name": "spans", "events": [
        ["bench.trace_window", 0, 1000], ["bench.step", 90, 420],
        ["bench.submit", 520, 150], ["bench.step", 680, 100]]}]}
    return {"planes": [dev, host]}


def test_busy_is_the_union_not_the_sum():
    bi = xplane.busy_idle(_trace())
    assert bi["window_s"] == pytest.approx(1000e-9)
    assert bi["busy_s"] == pytest.approx((100 + 100 + 50) * 1e-9)
    assert bi["max_idle_share"] == pytest.approx(0.75)


def test_per_op_sums_and_short_names():
    top = xplane.top_ops(_trace())
    assert top[0] == ["%a add f32[8]", pytest.approx(100e-9)]
    assert ["%copy.1 copy bf16[4,2]", pytest.approx(100e-9)] in top


def test_gaps_go_to_the_innermost_host_span():
    gaps = dict(xplane.idle_gaps(_trace(), short_ns=0))
    # 0-100 -> window start (unattributed by any inner span but bench.step
    # starts at 90; midpoint 50 is in none), 200-400 in bench.step,
    # 500-700 midpoint 600 in bench.submit, 750-1000 midpoint 875 in none
    assert gaps["bench.step"] == pytest.approx(200e-9)
    assert gaps["bench.submit"] == pytest.approx(200e-9)
    assert gaps["unattributed"] == pytest.approx(350e-9)


def test_module_runs_and_ops_within():
    tr = _trace()
    runs = xplane.module_runs(tr, "jit_step")
    assert runs == [["jit_step(1)", 100, 400]]
    inside = xplane.ops_within(tr, 100, 500)
    assert [e[0].split(" ")[0] for e in inside] == ["%a", "%b", "%copy.1"]


def test_parse_op_tuple_results_and_custom_call():
    op = xplane.parse_op(
        "%step.3 = (bf16[1043200,128]{1,0:T(8,128)(2,1)}, f32[1043200,128]"
        "{1,0:T(8,128)}) custom-call(bf16[8]{0} %x), custom_call_target=\"t\"")
    assert op["name"] == "%step.3" and op["opcode"] == "custom-call"
    assert op["results"] == [("bf16", (1043200, 128)), ("f32", (1043200, 128))]
    assert xplane.parse_op("not an instruction")["opcode"] == ""


def test_reduction_on_the_recorded_trace():
    """A slice of a trace recorded on the v5e (one C=1 serve step with its
    host spans, names cut to 200 characters): busy + idle = window, the
    program's run is found by name, and its ops sum to no more than it."""
    path = os.path.join(HERE, "recorded_trace.json")
    with open(path) as f:
        tr = json.load(f)
    runs = xplane.module_runs(tr, "jit_step")
    assert len(runs) >= 1
    _, s, d = runs[0]
    ops = xplane.ops_within(tr, s, s + d)
    assert ops and xplane.busy_ns(ops) <= d * 1.001
    bi = xplane.busy_idle(tr, window=(s, s + d))
    assert 0.0 <= bi["max_idle_share"] < 0.5
    assert bi["busy_s"] + bi["max_idle_share"] * bi["window_s"] == \
        pytest.approx(bi["window_s"])
    assert xplane.host_spans(tr, "bench.step")
