"""The rest of a run with the look for a chip skipped: a tiny benchmark in
a temporary root, the references against the repo's models, the control,
planted faults, and a cell, a mix and a metric added as files."""
import os

import numpy as onp
import pytest

from benchmark.run import run_cell
from benchmark.tests import tiny


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny.make_root(str(tmp_path_factory.mktemp("bench")))


def _run(root, workload, seed=2**31 + 7, trace=False, control=0):
    return run_cell(root, workload, seed, 0.5, trace, require_chip=False,
                    control=control)


def test_train_reference_follows_the_programs_step(root):
    """Loss, first gradient and parameter change of the repo's compiled
    step agree with the plain reference at float32 to rounding."""
    line = _run(root, "tiny-bert.tiny-train")
    assert line["correct"] and line["failed"] == 0
    c = line["compared"]
    assert c["loss1_rel"]["value"] < 1e-5
    assert c["grad_norm_gap"]["value"] < 1e-4
    assert c["dparam_norm_gap"]["value"] < 1e-3
    assert list(line)[-1] == "compared"
    assert set(line["metrics"]) == {"train_tokens_per_s_per_chip", "setup_s"}


@pytest.mark.parametrize("workload,seed", [("tiny-gpt2.tiny-waves", 2**31 + 7),
                                           ("tiny-gpt2.tiny-loop", 6)])
def test_serve_reference_agrees_with_prefill_and_paged_decode(root, workload,
                                                              seed):
    line = _run(root, workload, seed=seed, control=2)
    assert line["correct"] and line["failed"] == 0 and line["attempted"] > 0
    assert line["compared"]["token_gap"]["value"] < 1e-4
    assert {"serve_tokens_per_s", "itl_p95_ms", "setup_s"} <= \
        set(line["metrics"])
    # the control (fp8 operands) puts a token first that the reference does
    # not: it reads above the limit, so it would not be correct.  At this
    # size and over some 200 tokens it does so on some seeds only (these
    # are two of them); at the cells' own size it did on every seed tried
    # (PERF.md section 2)
    limit = line["compared"]["token_gap"]["limit"]
    assert line["compared"]["control_token_gap"]["value"] > limit


def test_train_control_and_half_batch_fault_read_above_the_limits(root):
    line = _run(root, "tiny-bert.tiny-train", control=2)
    c = line["compared"]
    limits = tiny.TRAIN["limits"]
    assert any(c[f"control_fp8.{k}"]["value"] > limits[k] for k in limits)
    assert any(c[f"fault_half_batch.{k}"]["value"] > limits[k]
               for k in limits)


def test_fault_step_returns_its_state_unchanged(root, monkeypatch):
    from mxnet_tpu.parallel.train import ShardedTrainStep
    real = ShardedTrainStep.dispatch

    def frozen(self, *batch, **kw):
        import jax.numpy as jnp
        import jax
        keep = jax.tree_util.tree_map(jnp.copy, (self.pvals, self.opt_state))
        handle = real(self, *batch, **kw)
        self.pvals, self.opt_state = keep
        return handle
    monkeypatch.setattr(ShardedTrainStep, "dispatch", frozen)
    line = _run(root, "tiny-bert.tiny-train")
    assert not line["correct"]
    assert line["compared"]["dparam_norm_gap"]["value"] == pytest.approx(1.0)


def test_fault_half_of_the_batch_left_out(root, monkeypatch):
    from mxnet_tpu.parallel.train import ShardedTrainStep
    real = ShardedTrainStep.dispatch

    def half(self, *batch, **kw):
        import jax.numpy as jnp
        batch = tuple(jnp.concatenate([b[:b.shape[0] // 2]] * 2)
                      for b in batch)
        return real(self, *batch, **kw)
    monkeypatch.setattr(ShardedTrainStep, "dispatch", half)
    line = _run(root, "tiny-bert.tiny-train")
    assert not line["correct"]
    assert line["compared"]["grad_norm_gap"]["value"] > \
        tiny.TRAIN["limits"]["grad_norm_gap"]


@pytest.mark.parametrize("workload", ["tiny-gpt2.tiny-waves",
                                      "tiny-gpt2.tiny-loop"])
def test_fault_a_token_altered_where_it_is_produced(root, monkeypatch,
                                                    workload):
    from mxnet_tpu.serve.engine import InferenceEngine
    real = InferenceEngine._execute

    def altered(self, *a, **kw):
        nxt, all_tok = real(self, *a, **kw)
        nxt = onp.array(nxt)
        nxt[:] = (nxt + 1) % self.cfg.vocab_size
        return nxt, all_tok
    monkeypatch.setattr(InferenceEngine, "_execute", altered)
    line = _run(root, workload)
    assert not line["correct"]
    assert line["compared"]["token_gap"]["value"] > \
        line["compared"]["token_gap"]["limit"]


def test_a_cell_a_mix_and_a_metric_are_added_by_files_alone(tmp_path):
    """New files plus entries in BENCHMARK.json: no file that was there is
    edited, and the harness finds all three by name."""
    mix = dict(tiny.WAVES, prompt_lens=[4, 6, 8, 10], output_lens=[3] * 4,
               limits={"token_gap": 1e-5, "min_compared_tokens": 3})
    reader = ("def read(ctx):\n"
              "    return float(len(ctx['window']['steps']))\n")
    root = tiny.make_root(str(tmp_path), extra_traffic={"dummy-mix": mix},
                          extra_metrics={"dummy_steps": reader})
    line = run_cell(root, "tiny-gpt2.dummy-mix", 3, 0.3, True,
                    require_chip=False)
    assert line["correct"]
    assert line["metrics"]["dummy_steps"]["value"] > 0
    assert "compile_cache_misses" in line["metrics"]


def test_a_run_in_a_tree_without_the_program_gives_no_result(tmp_path):
    """Only BENCHMARK.json and the files under `paths`: the run exits
    non-zero and prints no result line."""
    import shutil
    import subprocess
    import sys
    here = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    shutil.copy(os.path.join(os.path.dirname(here), "BENCHMARK.json"),
                tmp_path / "BENCHMARK.json")
    shutil.copytree(here, tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    out = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload",
         "bert-base.pretrain-s128", "--seed", "1", "--seconds", "1",
         "--trace", "0"], cwd=tmp_path, capture_output=True, text=True,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert out.returncode != 0 and out.stdout.strip() == ""


def test_off_the_chip_the_command_refuses(tmp_path):
    import subprocess
    import sys
    root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    out = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload",
         "gpt2-small.decode-heavy", "--seed", "1", "--seconds", "1",
         "--trace", "0"], cwd=root, capture_output=True, text=True,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert out.returncode == 1 and out.stdout.strip() == ""
    assert "not a TPU" in out.stderr
