"""Resilience primitives + framework-wide fault injection (SURVEY §5.3:
fault tolerance is the capability this port adds over the reference — and
it is only trustworthy if recovery is testable deterministically)."""
import os

import numpy as onp
import pytest

import mxnet_tpu as mx
from mxnet_tpu.resilience import (ENV_VAR, FaultInjected, FaultRegistry,
                                  fault_point, retry_with_backoff)

pytestmark = pytest.mark.fault


# ---------------------------------------------------------------------------
# retry_with_backoff
# ---------------------------------------------------------------------------

def test_retry_succeeds_after_transient():
    calls = {"n": 0}

    def flaky():
        calls["n"] += 1
        if calls["n"] < 3:
            raise OSError("blip")
        return "ok"

    slept = []
    assert retry_with_backoff(flaky, retries=3, base_delay=0.01,
                              sleep=slept.append) == "ok"
    assert calls["n"] == 3
    assert len(slept) == 2


def test_retry_backoff_is_exponential_and_capped():
    slept = []

    def always_fail():
        raise OSError("down")

    with pytest.raises(OSError, match="down"):
        retry_with_backoff(always_fail, retries=4, base_delay=0.1,
                           max_delay=0.25, jitter=0.0, sleep=slept.append)
    assert slept == [0.1, 0.2, 0.25, 0.25]   # doubles, then caps


def test_retry_jitter_bounded():
    slept = []

    def always_fail():
        raise OSError("down")

    with pytest.raises(OSError):
        retry_with_backoff(always_fail, retries=20, base_delay=0.1,
                           max_delay=0.1, jitter=0.5, sleep=slept.append)
    assert all(0.1 <= d <= 0.15 + 1e-12 for d in slept)


def test_retry_does_not_catch_unlisted():
    calls = {"n": 0}

    def wrong_kind():
        calls["n"] += 1
        raise ValueError("not transient")

    with pytest.raises(ValueError):
        retry_with_backoff(wrong_kind, retries=5, retry_on=(OSError,),
                           sleep=lambda _d: None)
    assert calls["n"] == 1   # no retries for unlisted exceptions


# ---------------------------------------------------------------------------
# fault spec / registry
# ---------------------------------------------------------------------------

def test_fault_spec_parse_and_fire():
    reg = FaultRegistry("ckpt_read@2,worker_exec@1:OSError")
    reg.fire("ckpt_read")                       # hit 1: unarmed
    with pytest.raises(FaultInjected, match="ckpt_read.*hit 2"):
        reg.fire("ckpt_read")                   # hit 2: armed
    reg.fire("ckpt_read")                       # fires at most once
    with pytest.raises(OSError, match="worker_exec"):
        reg.fire("worker_exec")
    reg.fire("unlisted_point")                  # unknown points just count
    assert reg.hits("unlisted_point") == 1


def test_fault_spec_rejects_typos():
    with pytest.raises(ValueError, match="point@hit"):
        FaultRegistry("ckpt_read")
    with pytest.raises(ValueError, match="hit count"):
        FaultRegistry("ckpt_read@x")
    with pytest.raises(ValueError, match="1-based"):
        FaultRegistry("ckpt_read@0")
    with pytest.raises(ValueError, match="unknown action"):
        FaultRegistry("ckpt_read@1:NoSuchError")


def test_fault_spec_duplicate_entry_last_action_wins():
    # duplicate point@hit entries overwrite silently — the LAST action
    # is the one that fires (one plan slot per (point, hit))
    reg = FaultRegistry("p@1:RuntimeError,p@1:OSError")
    with pytest.raises(OSError):
        reg.fire("p")


def test_fault_spec_negative_hit_rejected():
    with pytest.raises(ValueError, match="1-based"):
        FaultRegistry("p@-3")


def test_fault_point_tracks_env(monkeypatch):
    monkeypatch.delenv(ENV_VAR, raising=False)
    fault_point("p")                            # unarmed: no-op
    monkeypatch.setenv(ENV_VAR, "p@1")
    with pytest.raises(FaultInjected):
        fault_point("p")
    # changing the spec re-parses with fresh counters
    monkeypatch.setenv(ENV_VAR, "p@2")
    fault_point("p")                            # hit 1 of the NEW registry
    with pytest.raises(FaultInjected):
        fault_point("p")


# ---------------------------------------------------------------------------
# wired injection points
# ---------------------------------------------------------------------------

class CounterTarget:
    def __init__(self):
        self.state = onp.zeros(4)

    def apply(self, i):
        self.state = self.state * 0.9 + i

    def save(self, path):
        with open(path, "wb") as f:
            onp.savez(f, state=self.state)

    def load(self, path):
        with onp.load(path) as z:
            self.state = z["state"]


def test_ckpt_write_fault_injection(tmp_path, monkeypatch):
    from mxnet_tpu.utils import CheckpointManager
    monkeypatch.setenv(ENV_VAR, "ckpt_write@1:OSError")
    mgr = CheckpointManager(str(tmp_path))
    t = CounterTarget()
    with pytest.raises(OSError, match="ckpt_write"):
        mgr.save(t, 1)
    # no final checkpoint, no leftover temp file
    assert mgr.latest() is None
    assert [f for f in os.listdir(tmp_path) if not f.startswith(".")] == []
    monkeypatch.delenv(ENV_VAR)
    mgr.save(t, 1)
    assert mgr.latest()[0] == 1


def test_elastic_step_fault_injected_recovers(tmp_path, monkeypatch):
    from mxnet_tpu.elastic import ElasticLoop
    t_ref = CounterTarget()
    for i in range(8):
        t_ref.apply(i)

    monkeypatch.setenv(ENV_VAR, "elastic_step@4")
    t = CounterTarget()
    loop = ElasticLoop(t, str(tmp_path), save_every=2)
    out = loop.run(lambda i: t.apply(i), total_steps=8)
    assert out["status"] == "completed"
    assert out["restores"] == 1
    onp.testing.assert_array_equal(t.state, t_ref.state)


def test_sync_flag_retries_transient_collective(monkeypatch):
    import jax
    from jax.experimental import multihost_utils
    from mxnet_tpu import elastic
    calls = {"n": 0}

    def flaky(x):
        calls["n"] += 1
        if calls["n"] == 1:
            raise RuntimeError("collective timeout (injected)")
        return onp.asarray(x)

    monkeypatch.setattr(elastic, "_SYNC_BASE_DELAY", 0.001)
    monkeypatch.setattr(jax, "process_count", lambda: 2)
    monkeypatch.setattr(multihost_utils, "process_allgather", flaky)
    assert elastic.sync_flag(True) is True
    assert calls["n"] == 2


def test_sync_flag_raises_after_retry_budget(monkeypatch):
    import jax
    from jax.experimental import multihost_utils
    from mxnet_tpu import elastic

    def always_down(x):
        raise RuntimeError("transient collective error (injected)")

    monkeypatch.setattr(elastic, "_SYNC_BASE_DELAY", 0.001)
    monkeypatch.setattr(jax, "process_count", lambda: 2)
    monkeypatch.setattr(multihost_utils, "process_allgather", always_down)
    with pytest.raises(mx.MXNetError, match="allgather failed"):
        elastic.sync_flag(False)


# ---------------------------------------------------------------------------
# acceptance: corrupt-checkpoint-read + worker kill in ONE run, bit-exact
# ---------------------------------------------------------------------------

class _DetDataset:
    """Deterministic picklable dataset for spawn workers."""

    def __init__(self, n):
        self._n = n

    def __len__(self):
        return self._n

    def __getitem__(self, i):
        return onp.full((4,), i, onp.float32)


def _epoch_batches(worker_respawns=None):
    from mxnet_tpu.gluon.data import DataLoader
    dl = DataLoader(_DetDataset(16), batch_size=2, num_workers=2,
                    thread_pool=False, timeout=60,
                    worker_respawns=worker_respawns)
    out = [onp.asarray(b.asnumpy()) for b in dl]
    dl._proc_pool.shutdown()
    return out


def test_faulted_run_bitexact_with_clean_run(tmp_path, monkeypatch,
                                             shm_leak_check):
    """Acceptance criterion: with MXTPU_FAULT_SPEC injecting a corrupt
    checkpoint read AND worker kills in one run, DataLoader + ElasticLoop
    finish training bit-exact with the fault-free run."""
    from mxnet_tpu.elastic import ElasticLoop

    def train(batches, directory):
        t = CounterTarget()
        loop = ElasticLoop(t, directory, save_every=2)
        out = loop.run(
            lambda i: t.apply(float(batches[i % len(batches)].sum())),
            total_steps=6)
        return t.state, out

    # fault-free reference run
    monkeypatch.delenv(ENV_VAR, raising=False)
    clean_batches = _epoch_batches()
    clean_state, clean_out = train(clean_batches, str(tmp_path / "clean"))
    assert clean_out["restores"] == 0

    # faulted run: every worker incarnation hard-exits on its 2nd batch
    # (repeated kill/respawn/resubmit cycles), the 4th training step
    # attempt raises, and the recovery's first checkpoint read is
    # corrupted — exercising quarantine + fallback-chain restore
    monkeypatch.setenv(ENV_VAR,
                       "worker_exec@2:exit,elastic_step@4,ckpt_read@1")
    batches = _epoch_batches(worker_respawns=16)
    assert len(batches) == len(clean_batches) == 8
    for got, want in zip(batches, clean_batches):
        onp.testing.assert_array_equal(got, want)

    state, out = train(batches, str(tmp_path / "faulted"))
    assert out["status"] == "completed"
    assert out["restores"] == 1
    onp.testing.assert_array_equal(state, clean_state)
    # the corrupt-read quarantined a checkpoint on the way
    assert any(f.endswith(".corrupt")
               for f in os.listdir(tmp_path / "faulted"))
