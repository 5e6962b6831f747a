"""`pool_write_ms` on a synthetic trace: the K/V write kernel is found by
its instruction's name alone, and a program without it reads nothing."""
import pytest

from benchmark.metrics import pool_write_ms

OFFSET_NS = 5_000_000_000.0
STEP_EVERY_S, N_STEPS, N_LAYERS = 0.020, 4, 3
DEVICE_MS, WRITE_MS = 10.0, 0.25


def _ctx():
    """One `jit_step` run a step; in each, a write kernel a layer, an op
    that only consumes its result, and the attention kernel."""
    ops, mods, steps = [], [], []
    for i in range(N_STEPS):
        t0 = 100.0 + i * STEP_EVERY_S
        d0 = t0 * 1e9 + OFFSET_NS
        mods.append([f"jit_step({i})", d0, DEVICE_MS * 1e6])
        for li in range(N_LAYERS):
            at = d0 + li * 2e6
            ops.append([f"%paged_kv_write.{li + 1} = (bf16[2,8]{{1,0}}, "
                        "bf16[2,8]{1,0}) custom-call(s32[4]{0} %pt, "
                        "bf16[2,8]{1,0} %k, bf16[2,8]{1,0} %v)", at,
                        WRITE_MS * 1e6])
            ops.append([f"%get-tuple-element.{li} = bf16[2,8]{{1,0}} "
                        f"get-tuple-element(%paged_kv_write.{li + 1})",
                        at + WRITE_MS * 1e6, 10.0])
            ops.append([f"%ragged_paged_attention.{li} = f32[8]{{0}} "
                        f"custom-call(bf16[2,8]{{1,0}} "
                        f"%get-tuple-element.{li})", at + 1e6, 0.5e6])
        steps.append({"t0": t0, "t1": t0 + DEVICE_MS / 1e3, "tokens": 64,
                      "width": 1})
    trace = {"planes": [{"name": "/device:TPU:0", "lines": [
        {"name": "XLA Ops", "events": ops},
        {"name": "XLA Modules", "events": mods}]}]}
    return {"cell": None, "peaks": None, "counters": {}, "end_to_end": {},
            "trace": trace,
            "window": {"kind": "closed_loop", "steps": steps,
                       "traced_steps": len(steps)}}


def test_pool_write_ms_finds_the_kernel_by_name_alone():
    ctx = _ctx()
    assert pool_write_ms.read(ctx) == pytest.approx(N_LAYERS * WRITE_MS)
    # a program that scatters (no such op) reads nothing, and so does a
    # cell that is not a serving one or a run without a trace
    for ln in ctx["trace"]["planes"][0]["lines"]:
        ln["events"] = [e for e in ln["events"]
                        if not e[0].startswith("%paged_kv_write")]
    assert pool_write_ms.read(ctx) is None


@pytest.mark.parametrize("fault", ["train_cell", "no_trace"])
def test_pool_write_ms_reads_nothing_where_there_is_nothing(fault):
    ctx = _ctx()
    if fault == "train_cell":
        ctx["window"]["kind"] = "train_job"
    else:
        ctx["trace"] = None
    assert pool_write_ms.read(ctx) is None
