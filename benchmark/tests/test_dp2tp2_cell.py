"""The four-chip training cell rehearsed on four virtual CPU devices (dp 2
x tp 2, `default_tp_rules`), and the planted fault it has to catch: the
data-parallel exchange left out, so that the step trains on replica 0's
half of the batch alone.  In a process of its own: the device count is
fixed before JAX starts."""
import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

SCRIPT = r"""
import json, sys
sys.path.insert(0, sys.argv[1])
from benchmark.run import run_cell
from benchmark.tests import tiny
root, fault = sys.argv[2], sys.argv[3] == "1"
mix = dict(tiny.TRAIN, mesh={"dp": 2, "tp": 2})
tiny.make_root(root, extra_traffic={"tiny-dp2tp2": mix})
path = root + "/BENCHMARK.json"
bench = json.load(open(path))
for w in bench["workloads"]:
    if w["traffic"] == "tiny-dp2tp2":
        w["chips"] = 4
json.dump(bench, open(path, "w"))
if fault:
    # no exchange between the two data-parallel replicas: each would keep
    # the gradient of its own half; replica 0's is what this step applies
    import jax.numpy as jnp
    from mxnet_tpu.parallel.train import ShardedTrainStep
    real = ShardedTrainStep.dispatch

    def unexchanged(self, *batch, **kw):
        return real(self, *(jnp.concatenate([b[:b.shape[0] // 2]] * 2)
                            for b in batch), **kw)
    ShardedTrainStep.dispatch = unexchanged
line = run_cell(root, "tiny-bert.tiny-dp2tp2", 2**31 + 5, 0.5, False,
                require_chip=False)
print(json.dumps(line))
"""


def _run(tmp_path, fault: bool) -> dict:
    out = subprocess.run(
        [sys.executable, "-c", SCRIPT, ROOT, str(tmp_path), str(int(fault))],
        capture_output=True, text=True, cwd=ROOT,
        env=dict(os.environ, JAX_PLATFORMS="cpu",
                 XLA_FLAGS="--xla_force_host_platform_device_count=4"))
    assert out.returncode == 0, out.stderr[-2000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


def test_dp2tp2_step_follows_the_reference(tmp_path):
    line = _run(tmp_path, fault=False)
    assert line["correct"] and line["failed"] == 0
    assert line["compared"]["grad_norm_gap"]["value"] < 1e-3
    assert set(line["metrics"]) == {"train_tokens_per_s_per_chip", "setup_s"}


def test_dp2tp2_missing_exchange_is_not_correct(tmp_path):
    line = _run(tmp_path, fault=True)
    assert not line["correct"]
    c = line["compared"]
    assert c["grad_norm_gap"]["value"] > c["grad_norm_gap"]["limit"]


def test_the_cells_traffic_is_the_one_chip_cells_but_for_batch_and_mesh():
    def load(name):
        with open(os.path.join(ROOT, "benchmark", "traffic", name)) as f:
            return json.load(f)
    one, four = load("pretrain-s128.json"), load("pretrain-s128-dp2tp2.json")
    assert four["global_batch"] == 512 and four["mesh"] == {"dp": 2, "tp": 2}
    same = set(one) - {"what", "global_batch", "mesh", "limits"}
    assert all(one[k] == four[k] for k in same)
    assert four["limits"]
