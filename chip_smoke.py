#!/usr/bin/env python3
"""On-chip bring-up proof: the two hot paths, end to end, on a TPU.

    python3 chip_smoke.py

ONE process (a chip belongs to one process at a time), normal entry points,
full model width, random weights from a seed, no network, no subprocess:

- leg ``train``  — BERT-base pretraining (bf16, 12 x 768, V 30522, batch
  64 x seq 128, 20 masked positions, dropout on, fused CE, Adam) through
  `make_sharded_train_step` + `warmup` + 10 steps;
- leg ``serve``  — GPT-2 small (bf16, 12 x 768, V 50257, context 1024)
  through `InferenceEngine` with the default `ServeConfig` page size, 8
  staggered requests of 16-512 prompt tokens and 32 new tokens each;
- leg ``train4`` — only where `jax.device_count() >= 4`: the same BERT step
  on a dp2 x tp2 mesh with `default_tp_rules()`, global batch 128.

Each leg asserts what it produced (finite falling losses, token streams
against the unbatched `generate` oracle, kernels against their `jnp`
references ON the chip) and that the Pallas kernels are IN the compiled
executables — a kernel that gave way to a reference fails here, not in a
log.  A leg that fails raises; nothing is caught.  Each leg prints one JSON
line; the LAST stdout line is the verdict::

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}

Anywhere JAX's default backend is not a TPU the script exits non-zero,
naming what it found, before any leg runs.  Timings in the leg lines are
information only — PERF_LEDGER.jsonl is where speeds live.
"""
from __future__ import annotations

import gc
import json
import math
import re
import statistics
import sys
import time

import numpy as onp

SEED = 0

# -- stated tolerances ------------------------------------------------------
# Kernel route vs jnp-reference route of the SAME bf16 model on the chip:
# both round to bf16 (8 significant bits, ulp 2^-8 of the value) at every
# layer boundary but accumulate in different orders, so outputs agree to a
# few bf16 ulps of the output scale.  Bound = 16 ulps of max|reference|.
BF16_PARITY_ULPS = 16
# A greedy stream may leave the unbatched oracle only where the oracle's own
# top-2 logit gap is within this margin (bf16 logits of magnitude 2-4 are
# spaced 2^-6 apart; random weights make such near-ties common).
TOP2_MARGIN = 0.0625


def _emit(line: dict) -> None:
    print(json.dumps(line), flush=True)


def _cache_counts() -> dict:
    from mxnet_tpu import telemetry as tele

    def val(name):
        c = tele.registry().get(name)
        return int(c.value()) if c is not None else 0
    return {"hits": val("compile_cache_hits"),
            "misses": val("compile_cache_misses")}


def _leg_header(name: str, dev, cache_dir: str) -> dict:
    import jax
    return {"leg": name, "platform": dev.platform,
            "device_kind": dev.device_kind,
            "device_count": len(jax.devices()), "cache_dir": cache_dir}


def _peak_bytes(dev):
    stats = dev.memory_stats() or {}
    return stats.get("peak_bytes_in_use")


def _bf16_close(got, ref, what: str) -> float:
    """max|got - ref| within BF16_PARITY_ULPS bf16 ulps of max|ref|."""
    got = onp.asarray(got, onp.float32)
    ref = onp.asarray(ref, onp.float32)
    assert got.shape == ref.shape, (what, got.shape, ref.shape)
    assert onp.isfinite(got).all() and onp.isfinite(ref).all(), what
    scale = max(1.0, float(onp.abs(ref).max()))
    bound = BF16_PARITY_ULPS * 2.0 ** -8 * scale
    err = float(onp.abs(got - ref).max())
    assert err <= bound, (
        f"{what}: kernel route and jnp reference disagree by {err:.4g} "
        f"(> {bound:.4g} = {BF16_PARITY_ULPS} bf16 ulps of {scale:.3g})")
    return err


# ---------------------------------------------------------------------------
# leg: train (and train4)
# ---------------------------------------------------------------------------

def _bert_batch(cfg, batch: int, seq: int, n_mask: int):
    import mxnet_tpu as mx
    rng = onp.random.RandomState(SEED)
    ids = mx.np.array(rng.randint(0, cfg.vocab_size, (batch, seq)),
                      dtype="int32")
    # padded batches like real pretraining data (mean ~94% of seq)
    vlen = mx.np.array(rng.randint(int(0.85 * seq), seq + 1, (batch,)),
                       dtype="int32")
    mpos = mx.np.array(
        onp.sort(rng.rand(batch, seq).argsort(axis=1)[:, :n_mask], axis=1),
        dtype="int32")
    labels = mx.np.array(rng.randint(0, cfg.vocab_size, (batch, n_mask)),
                         dtype="int32")
    return ids, vlen, mpos, labels


def _bert_model(cfg):
    import mxnet_tpu as mx
    from mxnet_tpu.gluon.block import HybridBlock
    from mxnet_tpu.models.bert import BertForPretraining

    class Pretrain(HybridBlock):
        """Positional adapter: the sharded step passes batch args
        positionally; valid_length builds the padding attention mask."""

        def __init__(self, c):
            super().__init__()
            self.model = BertForPretraining(c)

        def forward(self, input_ids, valid_length, masked_positions):
            return self.model(input_ids, valid_length=valid_length,
                              masked_positions=masked_positions)

    mx.random.seed(SEED)
    model = Pretrain(cfg)
    model.initialize()
    return model


def _mlm_loss(out, input_ids, valid_length, masked_positions, lbl):
    import jax.numpy as jnp
    from mxnet_tpu.ops.pallas.softmax_xent import softmax_cross_entropy
    mlm, _nsp = out
    return jnp.mean(softmax_cross_entropy(mlm, lbl.astype(jnp.int32)))


def _bert_forward_parity(model, batch) -> float:
    """Predict-mode forward on a batch of 2: Pallas route vs the jnp
    references, both compiled for and run on this device."""
    import jax
    from mxnet_tpu.gluon.block import functional_call
    from mxnet_tpu.test_utils import environment

    ids, vlen, mpos, _ = (b._data[:2] for b in batch)
    params = {n: p._data._data for n, p in model.collect_params().items()
              if p._data is not None}
    key = jax.random.PRNGKey(SEED)

    def fwd(pv, i, v, m):
        (mlm, nsp), _ = functional_call(model, pv, i, v, m, training=False,
                                        rng_key=key)
        return mlm, nsp

    kern = jax.jit(fwd)(params, ids, vlen, mpos)
    # the dispatch switches are read at TRACE time: a second jit object
    # traced under them takes the reference route for every kernel
    with environment({"MXTPU_PALLAS": "reference",
                      "MXTPU_DISABLE_FLASH": "1"}):
        ref = jax.jit(lambda *a: fwd(*a))(params, ids, vlen, mpos)
    return max(_bf16_close(kern[0], ref[0], "BERT mlm logits"),
               _bf16_close(kern[1], ref[1], "BERT nsp logits"))


def leg_train(dev, cache_dir: str, cfg, batch: int, seq: int, n_mask: int,
              mesh_axes: dict, steps: int = 10, min_custom_calls: int = 0,
              name: str = "train") -> dict:
    import jax
    from mxnet_tpu import optimizer as opt
    from mxnet_tpu.parallel import make_mesh, make_sharded_train_step
    from mxnet_tpu.parallel.sharding import default_tp_rules

    out = _leg_header(name, dev, cache_dir)
    cache0 = _cache_counts()
    n_dev = math.prod(mesh_axes.values())
    sharded = n_dev > 1
    model = _bert_model(cfg)
    data = _bert_batch(cfg, batch, seq, n_mask)
    model(*(b[:2] for b in data[:3]))          # deferred init
    if not sharded:
        out["forward_parity_max_abs"] = round(
            _bert_forward_parity(model, data), 5)

    mesh = make_mesh(mesh_axes, jax.devices()[:n_dev])
    step = make_sharded_train_step(
        model, opt.Adam(learning_rate=1e-4), _mlm_loss, mesh,
        rules=default_tp_rules() if sharded else None, num_model_args=3)
    dtypes_before = {n: str(v.dtype) for n, v in step.pvals.items()}

    out["compile_seconds"] = round(step.warmup(*data), 2)
    losses, times = [], []
    for _ in range(steps):
        t0 = time.perf_counter()
        loss = jax.block_until_ready(step(*data))
        times.append((time.perf_counter() - t0) * 1e3)
        losses.append(float(loss))

    assert all(onp.isfinite(l) for l in losses), losses
    # ln(vocab) = 10.3 for 30522: a random-weight MLM loss sits there
    lo, hi = onp.log(cfg.vocab_size) - 1.4, onp.log(cfg.vocab_size) + 1.7
    assert lo <= losses[0] <= hi, (losses[0], lo, hi)
    assert losses[-1] < losses[0], losses
    assert step.trace_count == 1, step.trace_count
    dtypes_after = {n: str(v.dtype) for n, v in step.pvals.items()}
    assert dtypes_after == dtypes_before, {
        n: (dtypes_before[n], d) for n, d in dtypes_after.items()
        if d != dtypes_before[n]}
    n_calls = step._exec.as_text().count("tpu_custom_call")
    if not sharded:
        assert step._fused_opt_kernel is True
        # per layer flash fwd + dq + dkv; fused CE fwd + bwd
        assert n_calls >= min_custom_calls, (n_calls, min_custom_calls)
    else:
        devices = {s.device for v in step.pvals.values()
                   for s in v.addressable_shards}
        assert devices == set(mesh.devices.flat), devices
        tp_sharded = [(n, v) for n, v in step.pvals.items()
                      if "tp" in str(v.sharding.spec)]
        assert tp_sharded, "no parameter took a tp-sharded spec"
        n, v = tp_sharded[0]
        shard_shape = v.addressable_shards[0].data.shape
        assert onp.prod(shard_shape) < onp.prod(v.shape), (n, shard_shape)
        out["tp_sharded_example"] = {
            "name": n, "global": list(v.shape), "shard": list(shard_shape)}
        in_use = {str(d): (d.memory_stats() or {}).get("bytes_in_use")
                  for d in mesh.devices.flat}
        out["bytes_in_use"] = in_use
        # every chip holds at least its tp share of the weights
        share = sum(v.nbytes for v in step.pvals.values()) \
            // mesh_axes.get("tp", 1)
        assert all(b is not None and b >= share for b in in_use.values()), \
            (in_use, share)

    cache1 = _cache_counts()
    out.update({
        "mesh": mesh_axes, "layers": cfg.num_layers, "batch": batch,
        "loss_first": round(losses[0], 4), "loss_last": round(losses[-1], 4),
        "step_ms_median": round(statistics.median(times), 2),
        "custom_calls": n_calls, "fused_opt_kernel": step._fused_opt_kernel,
        "trace_count": step.trace_count,
        "cache": {k: cache1[k] - cache0[k] for k in cache1},
        "peak_bytes_in_use": _peak_bytes(dev),
    })
    return out


# ---------------------------------------------------------------------------
# leg: serve
# ---------------------------------------------------------------------------

_COPY_OP = re.compile(r"= (\w+)\[([\d,]+)\]\S* copy\(")


def _count_copies_of(hlo: str, array) -> int:
    """`copy` ops in compiled HLO text whose result has `array`'s dtype and
    element count, whatever shape XLA reshaped it to on the way."""
    name = onp.dtype(array.dtype).name
    want = ({"bfloat16": "bf16", "float32": "f32"}.get(name, name),
            int(array.size))
    return sum((dtype, math.prod(map(int, dims.split(",")))) == want
               for dtype, dims in _COPY_OP.findall(hlo))

def _paged_kernel_parity(eng) -> dict:
    """`ragged_paged_attention(use_kernel=True)` vs the dense-gather
    reference at the engine's own shapes (C = 1 and C = prefill_chunk),
    random bf16 pools, ragged context lengths — on this device."""
    import jax
    import jax.numpy as jnp
    from mxnet_tpu.ops.pallas.paged_attention import (
        paged_attention_reference, ragged_paged_attention)

    sc = eng.serve_config
    B, H, D = sc.max_slots, eng.cfg.num_heads, eng.head_dim
    Hkv, ps, maxp = eng.n_kv_heads, sc.page_size, eng.max_pages_per_seq
    dt = eng.pools.arrays["k"].dtype
    n_pages = eng.pools.num_pages
    rng = onp.random.RandomState(SEED)
    kp = jnp.asarray(rng.randn(Hkv, n_pages, ps, D), dt)
    vp = jnp.asarray(rng.randn(Hkv, n_pages, ps, D), dt)
    pt = jnp.asarray(
        1 + rng.permutation(n_pages - 1)[:B * maxp].reshape(B, maxp),
        jnp.int32)
    errs = {}
    for C in eng._step_widths():
        q = jnp.asarray(rng.randn(B, H, C, D), dt)
        # ragged: slot b starts somewhere in its b-th eighth of max_len
        start = jnp.asarray(
            [min(eng.max_len - C, b * eng.max_len // B + 3 * b)
             for b in range(B)], jnp.int32)
        ctx = start + C
        kern = jax.jit(lambda *a: ragged_paged_attention(
            *a, use_kernel=True,
            page_in_lanes=eng.pools.pages_in_lanes()))(
                q, kp, vp, pt, ctx, start)
        ref = jax.jit(paged_attention_reference)(q, kp, vp, pt, ctx, start)
        errs[f"c{C}"] = round(_bf16_close(
            kern, ref, f"paged attention C={C}"), 5)
    return errs


def _oracle_check(model, prompts, streams, max_new: int) -> dict:
    """Every engine stream against the unbatched `model.generate` oracle,
    token for token, wherever the oracle's top-2 logit gap (one batched
    forward over the oracle's own sequences) exceeds TOP2_MARGIN.  A
    mismatch inside the margin is a legal near-tie flip; the streams'
    contexts differ from there on, so that stream's comparison ends."""
    import jax
    import jax.numpy as jnp

    import mxnet_tpu as mx

    oracle = []
    for p in prompts:
        full = model.generate(mx.np.array([p], dtype="int32"),
                              max_new_tokens=max_new)
        oracle.append([int(t) for t in onp.asarray(full._data)[0]])
    # one padded batch: causal attention leaves real positions unaffected
    width = -(-max(len(o) for o in oracle) // 128) * 128
    padded = onp.zeros((len(oracle), width), onp.int32)
    for i, o in enumerate(oracle):
        padded[i, :len(o)] = o
    logits = model(mx.np.array(padded, dtype="int32"))._data
    # row r of stream i = the logits that pick generated token r
    picks = jnp.stack([logits[i, len(p) - 1:len(p) - 1 + max_new]
                       for i, p in enumerate(prompts)])
    top2 = onp.asarray(jax.lax.top_k(picks.astype(jnp.float32), 2)[0])
    gap = top2[..., 0] - top2[..., 1]                    # (N, max_new)

    visited = qualified = compared = flips = 0
    for i, (p, got, want) in enumerate(zip(prompts, streams, oracle)):
        assert got[:len(p)] == p, f"stream {i} does not echo its prompt"
        for j in range(len(p), len(p) + max_new):
            strong = gap[i, j - len(p)] > TOP2_MARGIN
            visited += 1
            qualified += bool(strong)
            if got[j] == want[j]:
                compared += bool(strong)
                continue
            assert not strong, (
                f"stream {i} leaves the generate oracle at position {j} "
                f"({got[j]} vs {want[j]}) where the oracle's top-2 gap "
                f"{gap[i, j - len(p)]:.4f} exceeds {TOP2_MARGIN}")
            flips += 1
            break
    stats = {"positions": len(prompts) * max_new, "visited": visited,
             "qualified": qualified, "matched_qualified": compared,
             "near_tie_flips": flips, "margin": TOP2_MARGIN,
             "gap_median": round(float(onp.median(gap)), 4)}
    # not vacuous: at least one request's worth of decided positions
    assert compared >= max_new, stats
    return stats


def leg_serve(dev, cache_dir: str, cfg, max_len: int, prompt_lens,
              max_new: int = 32, min_custom_calls: int = 0) -> dict:
    import mxnet_tpu as mx
    from mxnet_tpu.models.gpt import GPTForCausalLM
    from mxnet_tpu.serve import InferenceEngine, ServeConfig

    out = _leg_header("serve", dev, cache_dir)
    cache0 = _cache_counts()
    mx.random.seed(SEED)
    model = GPTForCausalLM(cfg)
    model.initialize()
    model(mx.np.array([[1, 2]], dtype="int32"))        # deferred init

    # no page-size override: the default must be one the kernel accepts
    eng = InferenceEngine(model, ServeConfig(max_len=max_len), seed=SEED)
    out["compile_seconds"] = round(eng.warmup(), 2)
    widths = eng._step_widths()
    execs = dict(eng._execs)
    assert sorted(execs) == widths, (sorted(execs), widths)
    hlo = {C: execs[C].as_text() for C in widths}
    calls = {f"c{C}": hlo[C].count("tpu_custom_call") for C in widths}
    assert all(n >= min_custom_calls for n in calls.values()), calls
    # whole-pool relayout copies XLA put around the kernels: none, the
    # donated pools pass through custom calls alone, in the layout this
    # device keeps them in (PR 28; 24 a width before)
    out["pool_relayout_copies"] = {
        f"c{C}": _count_copies_of(hlo[C], eng.pools.arrays["k"])
        for C in widths}
    assert not any(out["pool_relayout_copies"].values()), \
        out["pool_relayout_copies"]
    out["pages_in_lanes"] = eng.pools.pages_in_lanes()
    out["kernel_parity_max_abs"] = _paged_kernel_parity(eng)

    rng = onp.random.RandomState(SEED)
    prompts = [rng.randint(0, cfg.vocab_size, n).tolist()
               for n in prompt_lens]
    handles, step_ms = [], []
    for p in prompts:                  # staggered: two steps per arrival
        handles.append(eng.submit(p, max_new_tokens=max_new))
        for _ in range(2):
            t0 = time.perf_counter()
            eng.step()
            step_ms.append((time.perf_counter() - t0) * 1e3)
    eng.run_until_idle()

    streams = [h.result(timeout=0) for h in handles]
    for h, p in zip(handles, prompts):
        assert h.state == "finished", (h.state, h.error)
        assert len(h.tokens) == max_new, len(h.tokens)
        assert all(0 <= t < cfg.vocab_size for t in h.tokens)
    # no width compiled (or recompiled) while serving
    assert eng._execs == execs, (sorted(eng._execs), widths)
    out["oracle"] = _oracle_check(model, prompts, streams, max_new)

    cache1 = _cache_counts()
    out.update({
        "layers": cfg.num_layers, "page_size": eng.serve_config.page_size,
        "widths": widths, "custom_calls": calls,
        "requests": len(handles), "new_tokens": max_new,
        "steps_executed": eng.stats()["steps_executed"],
        "step_ms_median": round(statistics.median(step_ms), 2),
        "ttft_ms_median": round(statistics.median(
            h.ttft_s * 1e3 for h in handles), 2),
        "request_ms_median": round(statistics.median(
            h.latency_s * 1e3 for h in handles), 2),
        "cache": {k: cache1[k] - cache0[k] for k in cache1},
        "peak_bytes_in_use": _peak_bytes(dev),
    })
    return out


# ---------------------------------------------------------------------------

def main() -> int:
    # first act: place the compile cache, then look at the device
    from mxnet_tpu import telemetry
    from mxnet_tpu.runtime import enable_compile_cache
    cache_dir = enable_compile_cache()
    telemetry.enable()                # arms the cache hit/miss counters

    import jax
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: JAX's default backend is {dev.platform!r} "
              f"({dev.device_kind}, {len(jax.devices())} device(s)), not a "
              "TPU — nothing was run", file=sys.stderr)
        return 1

    from mxnet_tpu.models.bert import BertConfig
    from mxnet_tpu.models.gpt import GPTConfig

    bert = BertConfig(dtype="bfloat16")
    _emit(leg_train(dev, cache_dir, bert, batch=64, seq=128, n_mask=20,
                    mesh_axes={"dp": 1},
                    min_custom_calls=3 * bert.num_layers + 2))
    gc.collect()

    gpt = GPTConfig(dtype="bfloat16", dropout=0.0)
    _emit(leg_serve(dev, cache_dir, gpt, max_len=1024,
                    prompt_lens=(16, 96, 256, 512, 16, 96, 256, 512),
                    min_custom_calls=2 * gpt.num_layers))
    gc.collect()

    if jax.device_count() >= 4:
        _emit(leg_train(dev, cache_dir, bert, batch=128, seq=128, n_mask=20,
                        mesh_axes={"dp": 2, "tp": 2}, name="train4"))

    _emit({"ok": True, "device": {"platform": dev.platform,
                                  "kind": dev.device_kind,
                                  "count": len(jax.devices())}})
    return 0


if __name__ == "__main__":
    sys.exit(main())
