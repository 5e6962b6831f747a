"""Benchmark: BERT-base pretraining train-step on one TPU chip.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline", "extras",
"platform", "device_kind", "device_count"}.  North-star (BASELINE.json):
BERT-base pretraining at >=40% MFU on v5p-32; vs_baseline = measured_MFU /
0.40.  Also reports samples/sec/chip in extras.

Every mode measures in THIS process on JAX's default backend and refuses to
run unless that backend is a TPU (`_tpu_device`): a timing from any other
platform is not a speed (PERF.md), so there is no CPU mode, no fallback and
no carried result.  The one exception to "this process" is ``--coldstart``,
which measures fresh-process start-up and therefore runs its two children
one after the other from a parent that never touches JAX (a chip belongs to
one process at a time).
"""
from __future__ import annotations

import json
import os
import signal
import subprocess
import sys
import time

import numpy as _onp


def _tpu_device() -> dict:
    """The device every result line names.  Raises unless the default
    backend is a TPU, then turns the persistent compile cache on
    (`runtime.enable_compile_cache`) before the mode's first compile."""
    import jax
    devs = jax.devices()
    d = devs[0]
    if d.platform != "tpu":
        raise SystemExit(
            f"bench.py measures on a TPU only; JAX's default backend here "
            f"is {d.platform!r} ({d.device_kind}, {len(devs)} device(s)). "
            "Run it through the chip tool; tests and smokes run on the CPU.")
    from mxnet_tpu.runtime import enable_compile_cache
    enable_compile_cache()
    return {"platform": d.platform, "device_kind": d.device_kind,
            "device_count": len(devs)}


def _emit(result: dict, dev: dict) -> None:
    print(json.dumps({**result, **dev}))


def _measure() -> dict:
    import jax
    import jax.numpy as jnp

    import mxnet_tpu as mx
    from mxnet_tpu import optimizer as opt
    from mxnet_tpu.models.bert import BertConfig, BertForPretraining
    from mxnet_tpu.parallel import make_mesh, make_sharded_train_step

    # --telemetry (env MXTPU_TELEMETRY): instrumentation was auto-enabled
    # at import; attach a run journal BEFORE the measured loop so step/
    # compile events land somewhere inspectable (docs/observability.md)
    from mxnet_tpu import telemetry as _tele
    telemetry_on = _tele.enabled()
    if telemetry_on and _tele.journal() is None:
        _tele.enable(journal_path=os.path.join(
            os.path.dirname(os.path.abspath(__file__)), "bench_results",
            f"telemetry_journal_{os.getpid()}.jsonl"))

    dev = jax.devices()[0]

    # BERT-base; bf16 weights/compute for the MXU, seq 128 (phase-1
    # pretrain), MLM loss on masked positions only (the GluonNLP
    # create_pretraining_data shape: max_predictions_per_seq=20 at seq 128)
    batch = int(os.environ.get("MXTPU_BENCH_BATCH", 64))
    seq, n_mask = 128, 20
    cfg = BertConfig(dtype="bfloat16")

    from mxnet_tpu.gluon.block import HybridBlock

    class BenchBert(HybridBlock):
        """Positional adapter: the sharded step passes batch args
        positionally; pretraining uses (ids, valid_length,
        masked_positions) — valid_length builds the padding attention
        mask, so the bench measures the masked (production-shaped) path."""

        def __init__(self, c):
            super().__init__()
            self.model = BertForPretraining(c)

        def forward(self, input_ids, valid_length, masked_positions):
            return self.model(input_ids, valid_length=valid_length,
                              masked_positions=masked_positions)

    model = BenchBert(cfg)
    model.initialize()
    rng = _onp.random.RandomState(0)
    ids = mx.np.array(rng.randint(0, cfg.vocab_size, (batch, seq)),
                      dtype="int32")
    # padded batches like real pretraining data (mean ~94% of seq)
    vlen = mx.np.array(rng.randint(int(0.85 * seq), seq + 1, (batch,)),
                       dtype="int32")
    mpos = mx.np.array(
        _onp.sort(rng.rand(batch, seq).argsort(axis=1)[:, :n_mask], axis=1),
        dtype="int32")
    labels = mx.np.array(rng.randint(0, cfg.vocab_size, (batch, n_mask)),
                         dtype="int32")
    model(ids, vlen, mpos)  # deferred init

    def loss_fn(out, input_ids, valid_length, masked_positions, lbl):
        mlm, nsp = out
        # fused streaming CE (Pallas on TPU): no fp32 (tokens, vocab)
        # log-prob materialisation (ops/pallas/softmax_xent.py)
        from mxnet_tpu.ops.pallas.softmax_xent import softmax_cross_entropy
        return jnp.mean(softmax_cross_entropy(mlm, lbl.astype(jnp.int32)))

    mesh = make_mesh({"dp": 1}, jax.devices()[:1])
    step = make_sharded_train_step(model, opt.Adam(learning_rate=1e-4),
                                   loss_fn, mesh, num_model_args=3)

    from mxnet_tpu.parallel import AsyncMetricBuffer, DevicePrefetcher

    # warmup: AOT-compile (the binary comes back from the persistent
    # compile cache on a warm start), then two real steps
    compile_s = step.warmup(ids, vlen, mpos, labels)
    for _ in range(2):
        loss = step(ids, vlen, mpos, labels)
    jax.block_until_ready(loss)

    pipe = {"steps_in_flight_max": 0, "deferred_fetch_max": 0,
            "prefetch": None}

    def timed(n):
        # pipelined path: device prefetch on a background thread +
        # non-blocking dispatch + deferred metric fetches every 8 steps
        src = ((ids, vlen, mpos, labels) for _ in range(n))
        buf = AsyncMetricBuffer(drain_every=8)
        handle = None
        t0 = time.perf_counter()
        with DevicePrefetcher(src, place=step.place_batch) as pf:
            for b in pf:
                handle = step.dispatch(*b)
                buf.append(handle)
                # device truth: dispatched steps not yet complete. The
                # deferred-fetch window (buf.in_flight) is reported
                # separately — it reaches drain_every-1 even when every
                # dispatch blocks, so it must not masquerade as overlap.
                n_fly = step.steps_in_flight()
                if n_fly > pipe["steps_in_flight_max"]:
                    pipe["steps_in_flight_max"] = n_fly
                if buf.in_flight > pipe["deferred_fetch_max"]:
                    pipe["deferred_fetch_max"] = buf.in_flight
        buf.drain()
        loss = jax.block_until_ready(handle.loss)
        pipe["prefetch"] = pf.stats()
        return time.perf_counter() - t0, loss

    # two run lengths; slope removes the fixed dispatch/fetch overhead
    n1, n2 = 10, 50
    t1, _ = timed(n1)
    t2, loss = timed(n2)
    step_time = (t2 - t1) / (n2 - n1) if t2 > t1 else t2 / n2
    samples_per_sec = batch / step_time

    # train FLOPs: 3x forward; forward = matmul MACs * 2. The MLM head
    # (hidden->hidden + hidden->vocab) runs only on the n_mask gathered
    # positions — counting it per token would inflate MFU.
    h, l, i, V = (cfg.hidden_size, cfg.num_layers, cfg.intermediate_size,
                  cfg.vocab_size)
    fwd_per_token = 2 * l * (4 * h * h + 2 * h * i) + 4 * l * seq * h
    fwd_per_masked = 2 * (h * h + h * V)
    flops_per_step = 3 * batch * (fwd_per_token * seq
                                  + fwd_per_masked * n_mask)
    achieved = flops_per_step / step_time

    dstats = step.dispatch_stats()
    extras = {
        "samples_per_sec_per_chip": round(samples_per_sec, 2),
        "step_time_ms": round(step_time * 1e3, 2),
        "achieved_tflops": round(achieved / 1e12, 2),
        "batch": batch, "seq": seq, "n_mask": n_mask,
        "loss": float(loss),
        # async-pipeline health: host dispatch latency should sit far
        # below step_time_ms when overlap works; trace_count must be 1
        "steps_in_flight": pipe["steps_in_flight_max"],
        "deferred_fetch_max": pipe["deferred_fetch_max"],
        "dispatch_ms_mean": dstats["mean_ms"],
        "trace_count": step.trace_count,
        "compile_seconds": round(compile_s, 2),
        "prefetch": pipe["prefetch"],
    }
    # per-executable cost attribution (mx.tracing, captured at warmup):
    # XLA-counted flops/bytes + the always-on MFU estimate
    from mxnet_tpu import tracing as _tracing
    cost_feats = step.cost_features()
    if cost_feats:
        mfu_est = step.mfu_estimate(step_time)
        extras["cost"] = {
            "flops": cost_feats.get("flops"),
            "bytes_accessed": cost_feats.get("bytes_accessed"),
            "hbm_bytes_est": cost_feats.get("hbm_bytes_est"),
            "flops_analytic": flops_per_step,
            "mfu_estimate": (mfu_est["mfu_estimate"]
                             if mfu_est else None),
            "mfu_projected": (mfu_est["projected"]
                              if mfu_est else None),
            "peak_device_kind": (mfu_est["device_kind"]
                                 if mfu_est else None),
        }
    if telemetry_on:
        extras["telemetry"] = {"journal": getattr(_tele.journal(), "path",
                                                  None),
                               "snapshot": _tele.snapshot()}
    # single source of truth for the peak table: mxnet_tpu.tracing (the
    # MFU gauge and this bench must agree on the denominator; an unknown
    # device kind raises there)
    mfu = achieved / _tracing.peak_flops(dev.device_kind)
    return {
        "metric": "bert_base_pretrain_mfu",
        "value": round(mfu, 4),
        "unit": "MFU_fraction",
        "vs_baseline": round(mfu / 0.40, 4),
        "extras": extras,
    }


def _decode_rate_pcts(handles) -> dict:
    """Per-request DECODE tokens/sec (first token -> last token; the
    number speculation moves, reported per stream so a tail win is
    visible even when aggregate tokens/s is flat)."""
    rates = sorted(
        (len(h.tokens) - 1) / (h.finished_ts - h.first_token_ts)
        for h in handles
        if h.first_token_ts is not None and h.finished_ts is not None
        and len(h.tokens) > 1 and h.finished_ts > h.first_token_ts)

    def pct(p):
        if not rates:
            return None
        return round(rates[min(len(rates) - 1,
                               int(p * (len(rates) - 1)))], 2)

    return {"decode_tok_s_p50": pct(0.50), "decode_tok_s_p99": pct(0.99)}


def _spec_prompts(rng, cfg, n_req: int):
    """Shared-prefix workload mix: 3 prompt families sharing a long
    common prefix (the prefix-cache target) + unique tails, plus a few
    fully random prompts — the realistic many-users-one-template
    shape."""
    fams = [rng.randint(0, cfg.vocab_size, 24).tolist() for _ in range(3)]
    prompts = []
    for i in range(n_req):
        if i % 4 == 3:
            prompts.append(rng.randint(0, cfg.vocab_size,
                                       rng.randint(4, 32)).tolist())
        else:
            prompts.append(fams[i % 3]
                           + rng.randint(0, cfg.vocab_size,
                                         rng.randint(2, 8)).tolist())
    return prompts


def _measure_serve(spec: int = 0) -> dict:
    """`bench.py --serve [--spec k]`: throughput + tail-TTFT of the
    serving stack under simulated concurrent-request load.  Reports
    tokens/sec across the
    whole run and p50/p99 time-to-first-token over the request
    population — the two numbers the "millions of users" north star is
    graded on — plus per-request decode tokens/s percentiles.  With
    ``--spec k`` the engine runs k-token speculative decoding AND the
    cross-request prefix cache over a shared-prefix workload mix,
    reporting accept-rate / steps-per-token / prefix-hit extras
    (docs/serving.md "Speculative decoding & prefix caching")."""
    import jax

    import mxnet_tpu as mx
    from mxnet_tpu.models.gpt import GPTConfig, GPTForCausalLM
    from mxnet_tpu.serve import InferenceEngine, ServeConfig

    cfg = GPTConfig(vocab_size=32000, hidden_size=1024, num_layers=8,
                    num_heads=16, intermediate_size=4096,
                    max_position=1024, dropout=0.0, dtype="bfloat16")
    n_req, max_new, max_len = 64, 64, 512
    model = GPTForCausalLM(cfg)
    model.initialize()
    model(mx.np.array([[1, 2]], dtype="int32"))

    eng = InferenceEngine(model, ServeConfig(
        max_len=max_len, spec_tokens=spec, prefix_cache=spec > 0))
    compile_s = eng.warmup()

    rng = _onp.random.RandomState(0)
    if spec > 0:
        prompts = _spec_prompts(rng, cfg, n_req)
    else:
        prompts = [rng.randint(0, cfg.vocab_size,
                               rng.randint(4, 48)).tolist()
                   for _ in range(n_req)]
    # staggered arrival: a burst up front, then one request every other
    # step — the queue stays non-empty while slots churn (the
    # continuous-batching regime, not a static batch)
    handles = []
    t0 = time.perf_counter()
    for p in prompts[:8]:
        handles.append(eng.submit(p, max_new_tokens=max_new))
    arrivals = iter(prompts[8:])
    steps = 0
    while True:
        progressed = eng.step()
        steps += 1
        if steps % 2 == 0:
            nxt = next(arrivals, None)
            if nxt is not None:
                handles.append(eng.submit(nxt, max_new_tokens=max_new))
        if not progressed and len(handles) == n_req and \
                eng.scheduler.queue_depth == 0:
            break
        if steps > 100000:
            break
    wall = time.perf_counter() - t0
    toks = sum(len(h.tokens) for h in handles)
    ttfts = sorted(h.ttft_s * 1e3 for h in handles
                   if h.ttft_s is not None)

    def pct(p):
        if not ttfts:
            return None
        return round(ttfts[min(len(ttfts) - 1,
                               int(p * (len(ttfts) - 1)))], 2)

    from mxnet_tpu import telemetry as _tele
    from mxnet_tpu import tracing as _tracing
    extras = {
        "requests": n_req,
        "generated_tokens": toks,
        "ttft_p50_ms": pct(0.50),
        "ttft_p99_ms": pct(0.99),
        "steps": steps,
        "wall_s": round(wall, 3),
        "compile_seconds": round(compile_s, 2),
        "evictions": sum(h.evictions for h in handles),
        "page_size": eng.serve_config.page_size,
        "slots": eng.serve_config.max_slots,
        # actual fused launches per emitted token (the loop's `steps`
        # count includes idle polls during staggered arrivals)
        "steps_per_token": round(eng.scheduler._steps / max(1, toks), 4),
        **_decode_rate_pcts(handles),
    }
    if spec > 0:
        extras["spec"] = eng.scheduler.spec_stats()
        extras["spec"]["spec_tokens"] = spec
        if eng.prefix_index is not None:
            extras["prefix_cache"] = eng.prefix_index.stats()
    # quantized capacity table (ROADMAP item 2): weight bytes + MEASURED
    # max-concurrent-pages at each weight precision — engines are cheap
    # to construct (no warmup), and the auto pool sizing converts the
    # freed weight bytes into extra pages, so the capacity win is a
    # number, not a claim (docs/quantization.md)
    cap_table = {}
    for label, bits in (("f32", 0), ("int8", 8), ("int4", 4)):
        # quant_bits explicit per row: an ambient MXTPU_QUANT_BITS (the
        # ServeConfig default) must not quantize the f32 baseline row
        e = eng if eng.quant_bits == bits else InferenceEngine(
            model, ServeConfig(max_len=max_len, quant_bits=bits))
        st = e.stats()
        cap_table[label] = {
            "weight_bytes": st["weight_bytes"],
            "total_pages": e.allocator.total_pages,
            "bonus_pages": st["bonus_pages"],
        }
        if bits:
            cap_table[label]["weight_reduction"] = round(
                cap_table["f32"]["weight_bytes"]
                / max(1, st["weight_bytes"]), 3)
    extras["quant_capacity"] = cap_table
    # per-width serving-step cost (mx.tracing): XLA flops/bytes of both
    # compiled widths + an MFU estimate at the run's mean step cadence
    cost_by_width = eng.cost_features()
    if cost_by_width:
        mean_step_s = wall / max(1, steps)
        cost = {}
        for C, feats in sorted(cost_by_width.items()):
            entry = {"flops": feats.get("flops"),
                     "bytes_accessed": feats.get("bytes_accessed"),
                     "hbm_bytes_est": feats.get("hbm_bytes_est")}
            mfu = _tracing.estimate_mfu(feats.get("flops"), mean_step_s)
            if mfu is not None:
                entry["mfu_estimate"] = mfu["mfu_estimate"]
                entry["mfu_projected"] = mfu["projected"]
            cost[f"c{C}"] = entry
        extras["cost"] = cost
    if _tele.enabled():
        extras["telemetry"] = {"snapshot": _tele.snapshot()}
    return {
        "metric": "serve_tokens_per_sec",
        "value": round(toks / wall, 2),
        "unit": "tokens_per_sec",
        "vs_baseline": 0.0,   # north-star baseline is MFU-on-TPU
        "extras": extras,
    }


def _measure_serve_fleet(replicas: int, kill_at: float,
                         spec: int = 0,
                         kill_mode: str = "thread") -> dict:
    """`bench.py --serve --replicas N [--kill-at S] [--spec k]
    [--kill-mode thread|process]`: aggregate fleet throughput +
    tail-TTFT UNDER REPLICA LOSS (the ROADMAP item 1 metric).  One
    replica is killed `kill_at` seconds into the load window; its
    in-flight streams fail over to survivors, and the run must still
    report nonzero aggregate tokens/s and a finite p99 TTFT measured
    across the whole population — loss window included.  ``--spec k``
    turns on per-replica speculative decoding + prefix caching (with
    router prefix affinity) over the shared-prefix mix and reports the
    fleet-aggregate accept rate.  ``--kill-mode process`` runs the
    fleet on the process transport and SIGKILLs a worker instead —
    ledger failover + respawn; extras gain the failover loss window
    (ms between the kill and the next token streamed anywhere) and the
    respawn count."""
    import jax

    import mxnet_tpu as mx
    from mxnet_tpu.models.gpt import GPTConfig, GPTForCausalLM
    from mxnet_tpu.serve import ServeConfig, ServeFleet, ShedError

    cfg = GPTConfig(vocab_size=32000, hidden_size=1024, num_layers=8,
                    num_heads=16, intermediate_size=4096,
                    max_position=1024, dropout=0.0, dtype="bfloat16")
    n_req, max_new, max_len = 64, 64, 512
    model = GPTForCausalLM(cfg)
    model.initialize()
    model(mx.np.array([[1, 2]], dtype="int32"))

    fleet = ServeFleet(model, replicas=replicas,
                       config=ServeConfig(max_len=max_len,
                                          spec_tokens=spec,
                                          prefix_cache=spec > 0),
                       transport=kill_mode)
    compile_s = fleet.warmup()

    rng = _onp.random.RandomState(0)
    if spec > 0:
        prompts = _spec_prompts(rng, cfg, n_req)
    else:
        prompts = [rng.randint(0, cfg.vocab_size,
                               rng.randint(4, 48)).tolist()
                   for _ in range(n_req)]
    handles = []
    killed = None
    kill_ts = None
    # per-token wall timestamps: the failover loss window is the gap
    # between the kill and the next token streamed ANYWHERE in the fleet
    tok_times = []

    def _on_token(tok, req):
        tok_times.append(time.perf_counter())

    # pace arrivals so the load window straddles the kill: with
    # --kill-at S the last request arrives around 2S, guaranteeing the
    # loss lands mid-load however fast the backend decodes
    pace = (2.0 * kill_at / n_req) if kill_at else 0.0
    next_arrival = 0.0
    t0 = time.perf_counter()
    with fleet:
        arrivals = list(prompts)
        # burst, then staggered arrivals — the queue stays non-empty
        # while slots churn across all replicas
        while arrivals or not all(h.done() for h in handles):
            if killed is None and kill_at is not None and \
                    time.perf_counter() - t0 >= kill_at:
                # kill a loaded replica mid-window (prefer one holding
                # active streams so the failover path is exercised)
                busy = (lambda r: getattr(r.engine.scheduler, "inflight",
                                          None)
                        or r.engine.scheduler.active_count)
                victim = max(
                    (r for r in fleet.replicas if r.state == "running"),
                    key=busy, default=None)
                if victim is not None:
                    killed = victim.name
                    kill_ts = time.perf_counter()
                    if kill_mode == "process":
                        # the real thing: SIGKILL the worker — no
                        # scheduler survives, failover comes from the
                        # router's stream ledger and the worker respawns
                        os.kill(victim.pid, signal.SIGKILL)
                    else:
                        fleet.kill(victim.name,
                                   error="bench --kill-at replica loss")
            now = time.perf_counter() - t0
            if arrivals and now >= next_arrival:
                try:
                    handles.append(fleet.submit(
                        arrivals[0], max_new_tokens=max_new,
                        on_token=_on_token))
                    arrivals.pop(0)
                    next_arrival = now + pace
                except ShedError as e:
                    time.sleep(min(e.retry_after_ms, 100.0) / 1e3)
            else:
                time.sleep(0.002)
            if time.perf_counter() - t0 > 600:
                break
        for h in handles:
            h.result(timeout=120)
    wall = time.perf_counter() - t0
    toks = sum(len(h.tokens) for h in handles)
    ttfts = sorted(h.ttft_s * 1e3 for h in handles
                   if h.ttft_s is not None)

    def pct(p):
        if not ttfts:
            return None
        return round(ttfts[min(len(ttfts) - 1,
                               int(p * (len(ttfts) - 1)))], 2)

    stats = fleet.stats()
    extras = {
        "requests": n_req,
        "generated_tokens": toks,
        "ttft_p50_ms": pct(0.50),
        "ttft_p99_ms": pct(0.99),
        "wall_s": round(wall, 3),
        "compile_seconds": round(compile_s, 2),
        "replicas": replicas,
        "kill_at_s": kill_at,
        "kill_mode": kill_mode,
        "killed_replica": killed,
        "deaths": fleet.deaths,
        "respawns": fleet.respawns,
        "failovers": sum(h.failovers for h in handles),
        "evictions": sum(h.evictions for h in handles),
        "sheds": stats["router"]["sheds"],
        "routed": stats["router"]["routed"],
        "replica_states": {n: r["state"]
                           for n, r in stats["replicas"].items()},
        # ms from the kill to the next token streamed anywhere in the
        # fleet — the user-visible failover stall (None: no kill, or no
        # token landed after it)
        "failover_loss_window_ms": (round(
            (min(ts for ts in tok_times if ts > kill_ts) - kill_ts)
            * 1e3, 1)
            if kill_ts is not None
            and any(ts > kill_ts for ts in tok_times) else None),
        **_decode_rate_pcts(handles),
    }
    if stats.get("slo"):
        # burn-rate posture at end of run (MXTPU_SLO_SPEC objectives):
        # per-objective fast/slow burn + whether any alert fired
        extras["slo"] = {
            name: {"burn_fast": round(e["windows"]["fast"]["burn"], 3),
                   "burn_slow": round(e["windows"]["slow"]["burn"], 3),
                   "alerts": e["alerts"]}
            for name, e in stats["slo"].items()}
    if spec > 0:
        # fleet-aggregate speculation outcome (dead replicas included —
        # their accepted tokens were streamed before the loss)
        agg = {"proposed": 0, "accepted": 0, "steps": 0, "tokens": 0,
               "prefix_hit_tokens": 0, "cow_forks": 0}
        for rep in fleet.replicas:
            # process replicas run speculation inside the worker; their
            # proxy scheduler has no spec counters to aggregate
            if not hasattr(rep.engine.scheduler, "spec_stats"):
                continue
            ss = rep.engine.scheduler.spec_stats()
            for k in agg:
                agg[k] += ss[k] or 0
        agg["accept_rate"] = (round(agg["accepted"] / agg["proposed"], 4)
                              if agg["proposed"] else None)
        agg["steps_per_token"] = (round(agg["steps"]
                                        / agg["tokens"], 4)
                                  if agg["tokens"] else None)
        agg["spec_tokens"] = spec
        extras["spec"] = agg
    return {
        "metric": "serve_fleet_tokens_per_sec",
        "value": round(toks / wall, 2),
        "unit": "tokens_per_sec",
        "vs_baseline": 0.0,   # north-star baseline is MFU-on-TPU
        "extras": extras,
    }


def _measure_serve_replay(trace_path: str, replicas: int,
                          speed: float = 0.0,
                          kill_at: float = None,
                          kill_mode: str = "thread") -> dict:
    """`bench.py --serve --trace FILE [--speed X] [--kill-at S]
    [--replicas N] [--kill-mode thread|process]`: re-drive a recorded
    traffic journal or generated workload trace (docs/serving.md,
    "Flight recorder & replay") through a fresh fleet and report the
    divergence summary — matched vs divergent token-stream digests plus
    recorded-vs-replayed TTFT/latency percentiles.  The trace is served
    with the bench model, so digest verification only applies when the
    trace was recorded against it (a re-recorded bench trace, or one
    produced by ``--gen-trace`` + a previous ``--trace`` run)."""
    import jax

    import mxnet_tpu as mx
    from mxnet_tpu.models.gpt import GPTConfig, GPTForCausalLM
    from mxnet_tpu.serve import ServeConfig, ServeFleet
    from mxnet_tpu.serve import traffic as _traffic
    from mxnet_tpu.serve.replay import replay_trace

    meta, arrivals, outcomes = _traffic.read_trace(trace_path)
    if not arrivals:
        raise SystemExit(f"--trace {trace_path}: no arrival rows")
    cfg = GPTConfig(vocab_size=32000, hidden_size=1024, num_layers=8,
                    num_heads=16, intermediate_size=4096,
                    max_position=1024, dropout=0.0, dtype="bfloat16")
    max_len = 512
    top = max(max(a["prompt"], default=0) for a in arrivals)
    if top >= cfg.vocab_size:
        raise SystemExit(
            f"--trace {trace_path}: prompt token {top} >= bench vocab "
            f"{cfg.vocab_size} — this trace was not recorded against "
            f"the bench model")
    model = GPTForCausalLM(cfg)
    model.initialize()
    model(mx.np.array([[1, 2]], dtype="int32"))

    fleet = ServeFleet(model, replicas=replicas,
                       config=ServeConfig(max_len=max_len),
                       transport=kill_mode)
    compile_s = fleet.warmup()
    with fleet:
        report = replay_trace(fleet, (meta, arrivals, outcomes),
                              speed=speed, kill_at=kill_at,
                              timeout=600.0)
    extras = {
        "trace": os.path.abspath(trace_path),
        "mode": report["mode"],
        "requests": report["requests"],
        "submitted": report["submitted"],
        "digest_matched": len(report["matched"]),
        "digest_divergent": len(report["divergent"]),
        "unverified": len(report["unverified"]),
        "replay_failed": len(report["replay_failed"]),
        "shed_replay": len(report["shed_replay"]),
        "kill": report["kill"],
        "ttft_ms": report["ttft_ms"],
        "latency_ms": report["latency_ms"],
        "compile_seconds": round(compile_s, 2),
        "replicas": replicas,
        "kill_mode": kill_mode,
        "ok": report["ok"],
    }
    return {
        "metric": "serve_replay_wall_s",
        "value": report["replay_wall_s"],
        "unit": "seconds",
        "vs_baseline": 0.0,
        "extras": extras,
    }


def _pct_of(vals, p):
    vals = sorted(vals)
    if not vals:
        return None
    return round(vals[min(len(vals) - 1, int(p * (len(vals) - 1)))], 2)


def _run_disagg_phase(fleet, prompts, max_new: int) -> dict:
    """Submit one workload phase and drain it; returns the phase's
    aggregate tokens/s plus the handoff count it generated (counters on
    the fleet are cumulative, so the caller snapshots around us)."""
    from mxnet_tpu.serve import ShedError
    h0 = fleet.handoffs
    handles = []
    t0 = time.perf_counter()
    for p in prompts:
        while True:
            try:
                handles.append(fleet.submit(p, max_new_tokens=max_new))
                break
            except ShedError as e:
                time.sleep(min(e.retry_after_ms, 50.0) / 1e3)
    for h in handles:
        h.result(timeout=300)
    wall = time.perf_counter() - t0
    toks = sum(len(h.tokens) for h in handles)
    ttfts = [h.ttft_s * 1e3 for h in handles if h.ttft_s is not None]
    return {
        "requests": len(prompts),
        "generated_tokens": toks,
        "tokens_per_sec": round(toks / wall, 2),
        "wall_s": round(wall, 3),
        "ttft_p50_ms": _pct_of(ttfts, 0.50),
        "ttft_p99_ms": _pct_of(ttfts, 0.99),
        "handoffs": fleet.handoffs - h0,
    }


def _role_steps(fleet) -> dict:
    out = {}
    for rep in fleet.replicas:
        role = getattr(rep.engine, "role", "both")
        out[role] = out.get(role, 0) + getattr(
            rep.engine.scheduler, "_steps", 0)
    return out


def _measure_serve_disagg(disagg: str, tp: int) -> dict:
    """`bench.py --serve --disagg PxD [--tp N]`: prefill/decode
    disaggregation throughput (docs/serving.md "Disaggregated
    serving").  Runs a P-prefill/D-decode fleet (thread transport —
    the handoff semantics are identical to the process wire, without
    process-spawn noise in the numbers) through two workload phases:

    - **prefill-bound**: long prompts, tiny completions — the phase
      that saturates the prefill tier;
    - **decode-bound**: short prompts, long completions — the phase
      the tensor-parallel fused decode step is for.

    Reports per-phase aggregate tokens/s, handoff latency p50/p99,
    per-role step-share utilization, and the INDEPENDENT-SCALING
    check: the prefill-bound phase re-run with one extra prefill
    replica (decode tier untouched) — aggregate tokens/s should
    improve, the whole point of splitting the tiers."""
    import jax

    import mxnet_tpu as mx
    from mxnet_tpu.models.gpt import GPTConfig, GPTForCausalLM
    from mxnet_tpu.serve import ServeConfig, ServeFleet

    try:
        p_reps, d_reps = (int(x) for x in disagg.lower().split("x"))
    except ValueError:
        raise SystemExit(f"--disagg must be PxD (e.g. 1x2), "
                         f"got {disagg!r}")

    cfg = GPTConfig(vocab_size=512, hidden_size=64, num_layers=2,
                    num_heads=4, intermediate_size=128,
                    max_position=256, dropout=0.0)
    model = GPTForCausalLM(cfg)
    model.initialize()
    model(mx.np.array([[1, 2]], dtype="int32"))

    rng = _onp.random.RandomState(0)
    n_req = 12
    # prefill-bound: 48..64-token prompts, 4 new tokens each
    pre_prompts = [rng.randint(0, cfg.vocab_size,
                               rng.randint(48, 65)).tolist()
                   for _ in range(n_req)]
    # decode-bound: 4..8-token prompts, 32 new tokens each
    dec_prompts = [rng.randint(0, cfg.vocab_size,
                               rng.randint(4, 9)).tolist()
                   for _ in range(n_req)]

    sc = ServeConfig(max_slots=4, page_size=8, max_len=128,
                     prefill_chunk=16, tp=tp)

    def run_fleet(p, d):
        fleet = ServeFleet(model, config=sc, transport="thread",
                           disagg=(p, d))
        compile_s = fleet.warmup()
        with fleet:
            s0 = _role_steps(fleet)
            pre = _run_disagg_phase(fleet, pre_prompts, max_new=4)
            s1 = _role_steps(fleet)
            dec = _run_disagg_phase(fleet, dec_prompts, max_new=32)
            s2 = _role_steps(fleet)
            fleet.quiesce(30)
            stats = fleet.stats()
            hand_ms = list(fleet.handoff_ms)
        roles = sorted(s0)

        def share(a, b):
            tot = max(1, sum(b[r] - a.get(r, 0) for r in roles))
            return {r: round((b[r] - a.get(r, 0)) / tot, 3)
                    for r in roles}
        pre["role_step_share"] = share(s0, s1)
        dec["role_step_share"] = share(s1, s2)
        return {
            "phases": {"prefill_bound": pre, "decode_bound": dec},
            "compile_seconds": round(compile_s, 2),
            "handoffs": stats["handoffs"],
            "handoff_failures": stats["handoff_failures"],
            "handoff_ms_p50": _pct_of(hand_ms, 0.50),
            "handoff_ms_p99": _pct_of(hand_ms, 0.99),
            "tp_resolved": {n: r["tp"]
                            for n, r in stats["replicas"].items()},
        }

    base = run_fleet(p_reps, d_reps)
    # independent scaling: +1 PREFILL replica, decode tier untouched —
    # the prefill-bound phase is the one that should speed up
    scaled = run_fleet(p_reps + 1, d_reps)
    base_pre = base["phases"]["prefill_bound"]["tokens_per_sec"]
    scaled_pre = scaled["phases"]["prefill_bound"]["tokens_per_sec"]

    total_toks = sum(ph["generated_tokens"]
                     for ph in base["phases"].values())
    total_wall = sum(ph["wall_s"] for ph in base["phases"].values())
    extras = {
        "disagg": [p_reps, d_reps],
        "tp": tp,
        **base,
        "prefill_scaling": {
            "disagg": [p_reps + 1, d_reps],
            "prefill_bound_tokens_per_sec": scaled_pre,
            "base_tokens_per_sec": base_pre,
            "improvement": (round(scaled_pre / base_pre, 3)
                            if base_pre else None),
        },
    }
    return {
        "metric": "serve_disagg_tokens_per_sec",
        "value": round(total_toks / total_wall, 2) if total_wall else 0.0,
        "unit": "tokens_per_sec",
        "vs_baseline": 0.0,   # north-star baseline is MFU-on-TPU
        "extras": extras,
    }


def _measure_serve_tenants(replicas: int = 2, requests: int = 128,
                           seed: int = 0, speed: float = 1.0) -> dict:
    """`bench.py --serve --tenants [--requests N] [--replicas R]
    [--seed S] [--speed X]`: the noisy-neighbor containment headline
    (docs/serving.md "Per-tenant QoS").

    One seeded `WorkloadSpec` tenant mix — a protected ``gold`` tenant,
    an abusive ``abuser`` tenant (3x the arrival weight, every request
    inflated to the max output length: a deliberate priority-inversion
    attempt from the lowest class), and three short-lived ``churn-*``
    tenants — is driven through three fleets on the SAME trace:

    1. **solo**: only gold's arrivals, no contention — the reference
       tail,
    2. **qos on**: the full mix behind the QoS plane (gold
       interactive/weight 8; abuser best_effort behind a request-rate
       quota + 1-slot bulkhead),
    3. **qos off**: the full mix with ``MXTPU_QOS=0`` — what the same
       trace does to gold without the plane.

    Headline: gold's p99 TTFT degradation vs solo with QoS on (the
    contract is < 20% while the abuser absorbs >= 90% of the sheds);
    the QoS-off arm is reported alongside so the containment is
    attributable to the plane, not the trace."""
    import jax

    import mxnet_tpu as mx
    from mxnet_tpu.models.gpt import GPTConfig, GPTForCausalLM
    from mxnet_tpu.serve import ServeConfig, ServeFleet, ShedError
    from mxnet_tpu.serve import traffic as _traffic
    from mxnet_tpu.serve.qos import QoSConfig

    # phases must not journal into an ambient capture or pick up an
    # ambient QoS spec (the off arm sets its own)
    scoped = {}
    for var in ("MXTPU_TRAFFIC_JOURNAL", "MXTPU_QOS", "MXTPU_QOS_SPEC"):
        if var in os.environ:
            scoped[var] = os.environ.pop(var)

    spec = _traffic.WorkloadSpec(
        seed=seed, requests=requests, rate_rps=12.0, burst_factor=3.0,
        burst_period_s=4.0, prompt_max=24, output_max=16,
        deadline_ms=0.0,
        tenants={"abuser": 6.0, "churn-a": 0.5,
                 "churn-b": 0.5, "churn-c": 0.5})
    rows = _traffic.generate_workload(spec)
    abuse_new = 48                 # prompt_max 24 + 48 + 1 < max_len
    gold_prompt = 112              # gold TTFT is prefill-dominated (one
    #                                full chunk, several decode-steps
    #                                deep), so the p99 ratio measures
    #                                scheduling interference, not clock
    #                                jitter or fixed dispatch overhead
    for a in rows:
        if a["tenant"] == "abuser":
            # the abusive shape: every request demands 4x the mix's
            # max output — slot time the other tenants never asked for
            a["max_new"] = abuse_new
    # gold is a deterministic PROBE TRAIN overlaid on the mix, evenly
    # spaced so it never self-collides: its solo tail is then a stable
    # reference and any p99 movement in the mixed arms is interference
    # from the neighbors, not gold-on-gold burst luck
    span = rows[-1]["ts_mono"] if rows else 8.0
    n_gold = 16
    gap = span / n_gold
    for k in range(n_gold):
        rows.append({
            "kind": "arrival", "rid": requests + k + 1,
            "ts_wall": None, "ts_mono": round((k + 0.5) * gap, 6),
            "tenant": "gold",
            "prompt": [(7 * (k + i) + 13) % spec.vocab
                       for i in range(gold_prompt)],
            "max_new": 8, "temperature": 1.0, "greedy": True,
            "eos_token_id": None, "seed": k, "deadline_ms": 0.0})
    rows.sort(key=lambda a: a["ts_mono"])

    qos_cfg = QoSConfig.from_spec({
        "default": {"priority": "batch"},
        "tenants": {
            "gold": {"priority": "interactive", "weight": 8.0},
            "abuser": {"priority": "best_effort", "weight": 1.0,
                       "rps": 1.0, "burst_s": 1.0, "max_slots": 1}},
        "breaker": {"offenses": 0}})

    # heavier than the other toy serve configs on purpose: a 64-token
    # prefill must cost several decode steps, or the p99 ratio would
    # measure fixed dispatch overhead instead of interference
    cfg = GPTConfig(vocab_size=512, hidden_size=128, num_layers=3,
                    num_heads=4, intermediate_size=256,
                    max_position=256, dropout=0.0)
    model = GPTForCausalLM(cfg)
    model.initialize()
    model(mx.np.array([[1, 2]], dtype="int32"))
    # prefill_chunk covers the longest prompt in ONE chunk: an
    # interactive prefill then pays at most one step of queueing behind
    # a seated neighbor instead of one per chunk
    # 3 slots/replica with the abuser bulkheaded to 1: a protected
    # arrival always finds a free slot, so its tail is interference,
    # not slot starvation
    sc = ServeConfig(max_slots=3, page_size=8, num_pages=0,
                     prefill_chunk=112, max_len=176)

    def drive(fleet, only=None):
        """Timing-faithful drive with NO shed retries — a shed is the
        datapoint here, not an obstacle."""
        t0 = time.perf_counter()
        handles, sheds = [], {}
        for a in rows:
            t = a["tenant"]
            if only is not None and t not in only:
                continue
            due = t0 + a["ts_mono"] / max(speed, 1e-6)
            while True:
                now = time.perf_counter()
                if now >= due:
                    break
                time.sleep(min(0.02, due - now))
            try:
                handles.append((t, fleet.submit(
                    a["prompt"], max_new_tokens=a["max_new"],
                    greedy=True, tenant=t)))
            except ShedError as e:
                by = sheds.setdefault(t, {})
                by[e.reason] = by.get(e.reason, 0) + 1
        drain_to = time.perf_counter() + 300.0
        for t, h in handles:
            try:
                h.result(timeout=max(0.1,
                                     drain_to - time.perf_counter()))
            except Exception:
                pass
        per = {}
        for t, h in handles:
            row = per.setdefault(t, {"submitted": 0, "finished": 0,
                                     "tokens": 0, "ttfts": []})
            row["submitted"] += 1
            if h.state == "finished":
                row["finished"] += 1
            row["tokens"] += len(h.tokens)
            if h.ttft_s is not None:
                row["ttfts"].append(h.ttft_s * 1e3)
        out = {}
        for t in sorted(set(per) | set(sheds)):
            row = per.get(t, {"submitted": 0, "finished": 0,
                              "tokens": 0, "ttfts": []})
            out[t] = {
                "submitted": row["submitted"],
                "finished": row["finished"],
                "shed": sum(sheds.get(t, {}).values()),
                "shed_reasons": dict(sorted(
                    sheds.get(t, {}).items())),
                "generated_tokens": row["tokens"],
                "ttft_p50_ms": _pct_of(row["ttfts"], 0.50),
                "ttft_p99_ms": _pct_of(row["ttfts"], 0.99),
            }
        return out

    def phase(label, qos, only=None, qos_off=False):
        if qos_off:
            os.environ["MXTPU_QOS"] = "0"
        try:
            fleet = ServeFleet(model, replicas=replicas, config=sc,
                               qos_config=qos, stall_timeout=30.0)
            fleet.warmup()
            with fleet:
                # prime the decode widths OUTSIDE the timed window so
                # no phase's tail is first-compile cost in disguise
                for p, n in ((list(range(2, 10)), 4),
                             (list(range(2, 26)), abuse_new),
                             (list(range(2, 2 + gold_prompt)), 8)):
                    fleet.submit(p, max_new_tokens=n).result(timeout=60)
                table = drive(fleet, only=only)
                qstats = (fleet.stats() or {}).get("qos")
        finally:
            if qos_off:
                os.environ.pop("MXTPU_QOS", None)
        return {"tenants": table, "qos": qstats}

    solo = phase("solo", qos_cfg, only={"gold"})
    on = phase("qos_on", qos_cfg)
    off = phase("qos_off", None, qos_off=True)

    def p99(ph):
        return (ph["tenants"].get("gold") or {}).get("ttft_p99_ms")

    def degrade(ph):
        base, got = p99(solo), p99(ph)
        if not base or got is None:
            return None
        return round(100.0 * (got - base) / base, 1)

    total_sheds = sum(t["shed"] for t in on["tenants"].values())
    abuser_sheds = (on["tenants"].get("abuser") or {}).get("shed", 0)
    abuser_share = (round(abuser_sheds / total_sheds, 3)
                    if total_sheds else None)
    deg_on, deg_off = degrade(on), degrade(off)
    contained = (deg_on is not None and deg_on < 20.0
                 and abuser_share is not None and abuser_share >= 0.9)
    os.environ.update(scoped)
    extras = {
        "replicas": replicas,
        "requests": requests,
        "seed": seed,
        "speed": speed,
        "workload_tenants": spec.tenants,
        "solo": solo["tenants"],
        "qos_on": on["tenants"],
        "qos_off": off["tenants"],
        "qos_stats": on["qos"],
        "gold_ttft_p99_ms": {"solo": p99(solo), "qos_on": p99(on),
                             "qos_off": p99(off)},
        "gold_degradation_pct": {"qos_on": deg_on, "qos_off": deg_off},
        "abuser_shed_share_qos_on": abuser_share,
        "contained": contained,
    }
    return {
        "metric": "serve_tenant_gold_p99_degradation_pct",
        "value": deg_on if deg_on is not None else -1.0,
        "unit": "percent",
        "vs_baseline": 0.0,
        "extras": extras,
    }


def _measure_data() -> dict:
    """`bench.py --data`: throughput of the deterministic input pipeline
    (docs/data.md) — indexed RecordIO shards through the mixture
    interleave and sequence packer, consumed via `DevicePrefetcher`.
    Reports host samples/sec plus the two latency numbers that say where
    the bottleneck is: the pipeline's batch-build time (`data_wait_ms`)
    and the consumer's wait at the prefetcher hand-out."""
    import tempfile

    import jax

    from mxnet_tpu import recordio
    from mxnet_tpu.data import (DataPipeline, MixtureDataset,
                                ShardedRecordDataset)
    from mxnet_tpu.parallel.prefetch import DevicePrefetcher

    n_shards, docs_per_shard, batches = 4, 2000, 200
    batch, seq_len = 16, 256
    rng = _onp.random.RandomState(0)
    with tempfile.TemporaryDirectory(prefix="mxtpu_bench_data") as root:
        t0 = time.perf_counter()
        for corpus, count in (("a", n_shards), ("b", 2)):
            for s in range(count):
                rec = os.path.join(root, f"{corpus}-{s}.rec")
                w = recordio.MXIndexedRecordIO(
                    rec.replace(".rec", ".idx"), rec, "w")
                for i in range(docs_per_shard):
                    toks = rng.randint(
                        0, 32000, 16 + int(rng.randint(0, 240))
                    ).astype(_onp.int32)
                    w.write_idx(i, toks.tobytes())
                w.close()
        build_s = time.perf_counter() - t0

        mix = MixtureDataset(
            [ShardedRecordDataset(os.path.join(root, "a-*.rec")),
             ShardedRecordDataset(os.path.join(root, "b-*.rec"))],
            weights=[0.8, 0.2], seed=0)
        pipe = DataPipeline(mix, batch_size=batch, seed=0,
                            seq_len=seq_len)
        pf = DevicePrefetcher(
            pipe,
            place=lambda b: {k: jax.device_put(v) for k, v in b.items()},
            depth=2)
        # warmup (readers open, first window fills), then timed run
        for _ in range(10):
            next(pf)
        t1 = time.perf_counter()
        tokens = 0
        for _ in range(batches):
            got = next(pf)
            tokens += int(got["tokens"].size)
        wall = time.perf_counter() - t1
        pstats, fstats = pipe.stats(), pf.stats()
        pf.close()

    samples = batches * batch
    return {
        "metric": "data_samples_per_sec",
        "value": round(samples / wall, 2),
        "unit": "samples_per_sec",
        "vs_baseline": 0.0,   # north-star baseline is MFU-on-TPU
        "extras": {
            "batches": batches,
            "batch_size": batch,
            "seq_len": seq_len,
            "tokens_per_sec": round(tokens / wall, 1),
            "pipeline_wait_ms_mean": pstats["mean_wait_ms"],
            "prefetch_wait_ms_mean": fstats["mean_wait_ms"],
            "prefetch_occupancy_mean": fstats["mean_occupancy"],
            "corpus_build_s": round(build_s, 2),
            "wall_s": round(wall, 3),
        },
    }


def _measure_ops() -> dict:
    """`bench.py --ops`: per-kernel microbenchmarks for the fused Pallas
    set (docs/perf.md "Fused kernels & autotuning").

    Times each kernel's ACTIVE path (`ops.pallas.kernel_active`: Pallas
    on the TPU unless MXTPU_PALLAS says otherwise) against its
    forced-reference path, plus the pre-fusion legacy formulation where one exists (the
    dense MoE einsum pair, the unfused norm+residual chain), all
    through `opperf.time_callable` (median-of-k, synchronized).  The
    emitted JSON rides next to the standard bench fields so BENCH
    rounds can track kernel-level wins, not just end-to-end slope.
    """
    import jax
    import jax.numpy as jnp

    import mxnet_tpu  # noqa: F401  (backend + telemetry init)
    from mxnet_tpu.benchmark.opperf import time_callable
    from mxnet_tpu.ops import pallas as _pallas
    from mxnet_tpu.ops.pallas import fused_norm as _fnorm
    from mxnet_tpu.ops.pallas import moe_dispatch as _moed

    on_kernel_path = _pallas.kernel_active()
    rng = _onp.random.RandomState(0)
    f32 = jnp.float32
    ops: dict = {}

    from mxnet_tpu import tracing as _tracing

    def timed(fn, *args):
        jfn = jax.jit(fn)
        res = time_callable(lambda: jfn(*args), warmup=2, runs=5)
        # per-kernel cost attribution: the AOT lower/compile is served
        # from jit's cache (time_callable already compiled it), so this
        # costs one cost_analysis walk, not a second XLA compile
        try:
            feats = _tracing.cost_features_of(jfn.lower(*args).compile())
        except Exception:
            feats = None
        if feats:
            res["cost"] = {"flops": feats.get("flops"),
                           "bytes_accessed": feats.get("bytes_accessed")}
            mfu = _tracing.estimate_mfu(feats.get("flops"),
                                        res["median_ms"] / 1e3)
            if mfu is not None:
                res["cost"]["mfu_estimate"] = mfu["mfu_estimate"]
                res["cost"]["mfu_projected"] = mfu["projected"]
        return res

    # --- fused LayerNorm + residual ------------------------------------
    rows, h = 2048, 1024
    x = jnp.asarray(rng.randn(rows, h), f32)
    res = jnp.asarray(rng.randn(rows, h), f32)
    gam = jnp.ones((h,), f32)
    bet = jnp.zeros((h,), f32)

    def _ln_legacy(xv, rv, g, b):
        # pre-fusion chain: separate add, then the plain-op norm
        s = rv + xv
        mean = jnp.mean(s, axis=-1, keepdims=True)
        var = jnp.var(s, axis=-1, keepdims=True)
        return (s - mean) * jax.lax.rsqrt(var + 1e-5) * g + b, s

    ops["fused_norm"] = {
        "shape": [rows, h],
        "fused": timed(lambda a, r, g, b: _fnorm.layer_norm_residual(
            a, r, g, b, use_kernel=on_kernel_path), x, res, gam, bet),
        "reference": timed(lambda a, r, g, b: _fnorm.layer_norm_residual(
            a, r, g, b, use_kernel=False), x, res, gam, bet),
        "legacy": timed(_ln_legacy, x, res, gam, bet),
    }

    # --- blockwise MoE dispatch/combine --------------------------------
    t, e, cap, hm = 1024, 8, 192, 512
    xt = jnp.asarray(rng.randn(t, hm), f32)
    expert = jnp.asarray(rng.randint(0, e, t), jnp.int32)
    pos = jnp.asarray(rng.randint(0, cap, t), jnp.int32)
    kept = jnp.asarray(rng.rand(t) < 0.9)
    gate = jnp.asarray(rng.rand(t), f32)
    down = jnp.asarray(rng.randn(e, cap, hm), f32)

    # routing tensors ride as jit ARGUMENTS, never closure constants:
    # XLA would constant-fold the dispatch-tensor build (the very cost
    # the blockwise path removes) right out of the timed program
    def _moe_pair(use_kernel):
        def fn(xv, dn, ex, ps, kp, gt):
            buf = _moed.moe_dispatch(xv, ex, ps, kp, e, cap,
                                     use_kernel=use_kernel)
            out = _moed.moe_combine(dn, ex, ps, kp, gt,
                                    use_kernel=use_kernel)
            return buf, out
        return fn

    def _moe_dense(xv, dn, ex, ps, kp, gt):
        onehot = jax.nn.one_hot(ex, e, dtype=xv.dtype)
        disp = (onehot * kp[:, None].astype(xv.dtype))[:, :, None] * \
            jax.nn.one_hot(ps, cap, dtype=xv.dtype)[:, None, :]
        buf = jnp.einsum("tec,th->ech", disp, xv)
        out = jnp.einsum("tec,ech->th",
                         disp * gt[:, None, None].astype(xv.dtype), dn)
        return buf, out

    moe_args = (xt, down, expert, pos, kept, gate)
    ops["moe_dispatch"] = {
        "shape": [t, e, cap, hm],
        "fused": timed(_moe_pair(on_kernel_path), *moe_args),
        "reference": timed(_moe_pair(False), *moe_args),
        "legacy": timed(_moe_dense, *moe_args),
    }

    # --- fused multi-tensor optimizer ----------------------------------
    from mxnet_tpu.ops.pallas import fused_optimizer as _fopt
    from mxnet_tpu.optimizer import Adam
    opt = Adam(learning_rate=1e-3)
    # a transformer-ish leaf zoo: a few big matrices + a bias/scale tail
    sizes = [1 << 18] * 3 + [1 << 10] * 24
    params = {f"p{i}": jnp.asarray(rng.randn(n), f32)
              for i, n in enumerate(sizes)}
    grads = {k: jnp.asarray(rng.randn(v.size), f32)
             for k, v in params.items()}
    states = {k: (jnp.zeros_like(v), jnp.zeros_like(v))
              for k, v in params.items()}
    hp = {"lr": jnp.float32(1e-3), "wd": jnp.float32(0.0),
          "rescale_grad": jnp.float32(1.0), "clip_gradient": None,
          "t": jnp.float32(1.0)}
    skip = jnp.asarray(False)

    # hp and the skip flag are traced args like in the real step — a
    # closed-over concrete False would let XLA fold the skip selects away
    def _opt_fn(use_kernel):
        def fn(p, g, s, hpv, sk):
            return _fopt.apply_updates(opt, p, g, s, hpv, sk,
                                       use_kernel=use_kernel)
        return fn

    ops["fused_optimizer"] = {
        "shape": [int(sum(sizes)), len(sizes)],
        "fused": timed(_opt_fn(on_kernel_path and
                               _fopt.kernel_supported(opt)),
                       params, grads, states, hp, skip),
        "reference": timed(_opt_fn(False), params, grads, states, hp,
                           skip),
    }

    # --- flash attention (Pallas kernel only on the TPU backend) -------
    from mxnet_tpu.ops.attention import reference_attention
    b, nh, l, d = 4, 8, 512, 64
    q = jnp.asarray(rng.randn(b, nh, l, d), f32)
    k = jnp.asarray(rng.randn(b, nh, l, d), f32)
    v = jnp.asarray(rng.randn(b, nh, l, d), f32)
    ops["flash_attention"] = {
        "shape": [b, nh, l, d],
        "reference": timed(lambda a1, a2, a3: reference_attention(
            a1, a2, a3, causal=True), q, k, v),
    }
    if on_kernel_path:
        from mxnet_tpu.ops.pallas.flash_attention import flash_attention
        ops["flash_attention"]["fused"] = timed(
            lambda a1, a2, a3: flash_attention(a1, a2, a3, causal=True),
            q, k, v)

    # --- fused dequant-matmul (int8/int4 weight-only) ------------------
    from mxnet_tpu.ops.pallas import autotune as _at
    from mxnet_tpu.ops.pallas import quantized_matmul as _qmm
    qm, qn, qk = 256, 512, 512
    xq = jnp.asarray(rng.randn(qm, qk), f32)
    wq = jnp.asarray(rng.randn(qn, qk), f32)
    for bits in (8, 4):
        qt = _qmm.quantize_weight(wq, bits)
        # tuned block sizes: a warm second run must be a cache hit —
        # set MXTPU_AUTOTUNE_CACHE to persist across bench runs
        try:
            tr = _at.tune("quantized_matmul", (qm, qn, qk),
                          f"int{bits}", runs=2, top_k=2)
            tune_info = {"source": tr.source, "cache_hit": tr.cache_hit,
                         "trials": tr.trials}
        except Exception as e:   # tuning must never fail the bench
            tune_info = {"error": str(e).splitlines()[0]}

        # quantized planes ride as jit ARGUMENTS (the MoE rule): a
        # closed-over weight would let XLA constant-fold the dequant —
        # the very traffic the fused kernel deletes — out of the timing
        def _fused(a, qp, sp, b=bits, kk=qk):
            t = _qmm.QuantizedTensor(qp, sp, b, kk)
            return _qmm.quantized_matmul(a, t,
                                         use_kernel=on_kernel_path)

        def _deq_then_mm(a, qp, sp, b=bits, kk=qk):
            t = _qmm.QuantizedTensor(qp, sp, b, kk)
            return a @ _qmm.dequantize_weight(t).T

        rf = _qmm._roofline(
            _at.BlockConfig(block_m=128, block_n=128, block_k=512),
            (qm, qn, qk), f"int{bits}")
        ops[f"quantized_matmul_int{bits}"] = {
            "shape": [qm, qn, qk],
            "fused": timed(_fused, xq, qt.q, qt.scale),
            "reference": timed(_deq_then_mm, xq, qt.q, qt.scale),
            "f32": timed(lambda a, w: a @ w.T, xq, wq),
            "weight_bytes": qt.nbytes(),
            "weight_bytes_f32": int(wq.size) * 4,
            "weight_reduction": round(int(wq.size) * 4 / qt.nbytes(), 3),
            "bytes_moved_fused": int(rf["bytes"]),
            "bytes_moved_f32": int(qm * qk * 4 + qn * qk * 4
                                   + qm * qn * 4),
            "autotune": tune_info,
        }

    for entry in ops.values():
        f = entry.get("fused", {}).get("median_ms")
        r = entry.get("reference", {}).get("median_ms")
        if f and r:
            entry["speedup_vs_reference"] = round(r / f, 3)
        lg = entry.get("legacy", {}).get("median_ms")
        if f and lg:
            entry["speedup_vs_legacy"] = round(lg / f, 3)
        d = entry.get("f32", {}).get("median_ms")
        if f and d:
            entry["speedup_vs_f32"] = round(d / f, 3)

    return {
        "metric": "kernel_microbench",
        "value": round(ops["fused_norm"]["fused"]["median_ms"], 4),
        "unit": "ms_fused_norm_median",
        "vs_baseline": 0.0,   # north-star baseline is MFU-on-TPU
        "extras": {
            "ops": ops,
            "kernel_path": "pallas" if on_kernel_path else "reference",
            "pallas_mode": _pallas.pallas_mode(),
        },
    }


def _coldstart_child(role: str, art_dir: str) -> dict:
    """One cold-start measurement in THIS (fresh) process.

    ``live``: build a small GPT train step + serving engine, measure
    time-to-first-step/-token through trace+compile, then capture the
    export artifacts for the ``load`` child.  ``load``: same models,
    but warm-start from the artifacts — measure the same
    time-to-first-step with ZERO Python-level retraces (asserted)."""
    import jax
    import jax.numpy as jnp

    dev = _tpu_device()

    import mxnet_tpu as mx
    from mxnet_tpu import optimizer as opt
    from mxnet_tpu import telemetry as _tele
    from mxnet_tpu.models.gpt import GPTConfig, GPTForCausalLM
    from mxnet_tpu.parallel import make_mesh, make_sharded_train_step
    from mxnet_tpu.serve import InferenceEngine, ServeConfig

    telemetry_on = _tele.enabled()
    cfg = GPTConfig(vocab_size=512, hidden_size=64, num_layers=2,
                    num_heads=4, intermediate_size=128, max_position=128,
                    dropout=0.0)
    model = GPTForCausalLM(cfg)
    # deterministic init: live and load children must hold the same
    # weights for the loss/logit parity cross-check in extras
    from mxnet_tpu import random as _mxrng
    _mxrng.seed(0)
    model.initialize()
    rng = _onp.random.RandomState(0)
    ids = mx.np.array(rng.randint(0, 512, (8, 32)), dtype="int32")
    labels = mx.np.array(rng.randint(0, 512, (8, 32)), dtype="int32")
    model(ids)   # deferred init (outside the timed window for both roles)

    def loss_fn(out, input_ids, labels):
        from mxnet_tpu.ops.pallas.softmax_xent import softmax_cross_entropy
        o = out._data if hasattr(out, "_data") else out
        return jnp.mean(softmax_cross_entropy(o, labels.astype(jnp.int32)))

    mesh = make_mesh({"dp": 1}, jax.devices()[:1])
    step = make_sharded_train_step(model, opt.Adam(learning_rate=1e-3),
                                   loss_fn, mesh, num_model_args=1)
    train_art = os.path.join(art_dir, "train")
    serve_art = os.path.join(art_dir, "serve")

    # --- train: time to first retired step ----------------------------
    t0 = time.perf_counter()
    if role == "load":
        step.load_export(train_art, ids, labels)
    else:
        step.warmup(ids, labels)
    loss = float(jax.block_until_ready(step.dispatch(ids, labels).loss))
    train_ttfs = time.perf_counter() - t0

    # --- serve: time to first token -----------------------------------
    eng = InferenceEngine(model, ServeConfig(max_len=64, max_slots=4))
    t0 = time.perf_counter()
    if role == "load":
        eng.warmup(artifact=serve_art)
    else:
        eng.warmup()
    first = {}
    h = eng.submit(list(range(1, 9)), max_new_tokens=4,
                   on_token=lambda t, r: first.setdefault(
                       "t", time.perf_counter()))
    eng.run_until_idle()
    serve_ttft = first.get("t", time.perf_counter()) - t0
    tokens = h.result(timeout=0)

    if role == "live":
        step.export(train_art, ids, labels)
        eng.export(serve_art)

    out = {
        "role": role,
        "train_ttfs_s": round(train_ttfs, 3),
        "serve_ttft_s": round(serve_ttft, 3),
        "loss": loss,
        "tokens": tokens,
        "trace_count": step.trace_count,
        "compile_seconds": round(step.compile_seconds or 0.0, 3),
        **dev,
    }
    if telemetry_on:
        out["telemetry"] = {"snapshot": _tele.snapshot()}
    return out


def _measure_coldstart() -> dict:
    """`bench.py --coldstart`: time-to-first-step (train) and
    time-to-first-token (serve) for the live-trace path vs the
    export-artifact load path, each measured in a FRESH child process
    (docs/export.md).  The headline value is the train cold-start
    speedup; extras carry both raw timings plus the loaded path's
    ``trace_count`` (must be 0 — the zero-retrace contract).  This
    parent never touches JAX: each child claims the chip in turn and
    names the device it ran on."""
    import tempfile
    with tempfile.TemporaryDirectory(prefix="mxtpu_coldstart_") as art:
        results = {}
        for role in ("live", "load"):
            proc = subprocess.run(
                [sys.executable, os.path.abspath(__file__),
                 "--coldstart-child", role, art],
                capture_output=True, text=True, timeout=900,
                cwd=os.path.dirname(os.path.abspath(__file__)))
            if proc.returncode != 0:
                tail = (proc.stderr or proc.stdout or "").strip()
                raise RuntimeError(
                    f"coldstart {role} child failed: {tail[-800:]}")
            for line in reversed(proc.stdout.strip().splitlines()):
                if line.startswith("{"):
                    results[role] = json.loads(line)
                    break
    live, load = results["live"], results["load"]
    parity = (live["loss"] == load["loss"]
              and live["tokens"] == load["tokens"])
    speedup = (live["train_ttfs_s"] / load["train_ttfs_s"]
               if load["train_ttfs_s"] > 0 else 0.0)
    return {
        "metric": "coldstart_train_speedup",
        "value": round(speedup, 3),
        "unit": "live_ttfs_over_artifact_ttfs",
        "vs_baseline": 0.0,   # north-star baseline is MFU-on-TPU
        "extras": {
            "train_ttfs_live_s": live["train_ttfs_s"],
            "train_ttfs_load_s": load["train_ttfs_s"],
            "serve_ttft_live_s": live["serve_ttft_s"],
            "serve_ttft_load_s": load["serve_ttft_s"],
            "serve_ttft_speedup": round(
                live["serve_ttft_s"] / load["serve_ttft_s"], 3)
            if load["serve_ttft_s"] > 0 else 0.0,
            "loaded_trace_count": load["trace_count"],
            "parity": parity,
            "loss": load["loss"],
        },
        **{k: load[k] for k in ("platform", "device_kind", "device_count")},
    }


def _flag_operand(flag: str, default: str) -> str:
    """Value following `flag` in argv (or `default` when absent/bare)."""
    if flag not in sys.argv:
        return default
    idx = sys.argv.index(flag)
    if idx + 1 >= len(sys.argv) or sys.argv[idx + 1].startswith("--"):
        return default
    return sys.argv[idx + 1]


def main():
    if "--telemetry" in sys.argv:
        # auto-enables instrumentation at mxnet_tpu import (and travels
        # to the coldstart children through the environment)
        sys.argv.remove("--telemetry")
        os.environ["MXTPU_TELEMETRY"] = "1"
    if len(sys.argv) >= 4 and sys.argv[1] == "--coldstart-child":
        print(json.dumps(_coldstart_child(sys.argv[2], sys.argv[3])))
        return
    if "--coldstart" in sys.argv:
        # live-trace vs artifact-load time-to-first-step, fresh child
        # process per role (docs/export.md)
        print(json.dumps(_measure_coldstart()))
        return
    if "--gen-trace" in sys.argv:
        # deterministic workload generation (docs/serving.md "Flight
        # recorder & replay"): emit a journal-format trace as a pure
        # function of --seed — no device work, not a measurement
        from mxnet_tpu.serve import traffic as _traffic
        overrides = {}
        for flag, field, cast in (("--seed", "seed", int),
                                  ("--requests", "requests", int),
                                  ("--rps", "rate_rps", float),
                                  ("--burst", "burst_factor", float),
                                  ("--prefix-frac", "prefix_frac", float)):
            if flag in sys.argv:
                overrides[field] = cast(_flag_operand(flag, "0"))
        wspec = _traffic.WorkloadSpec.from_env(**overrides)
        path = _flag_operand("--gen-trace", "trace.jsonl")
        rows = _traffic.generate_workload(wspec)
        _traffic.write_trace(rows, path, wspec)
        print(json.dumps({"trace": os.path.abspath(path),
                          "requests": len(rows),
                          "seed": wspec.seed,
                          "span_s": round(rows[-1]["ts_mono"], 3)
                          if rows else 0.0}))
        return

    # every remaining mode measures in this process, on a TPU or not at all
    dev = _tpu_device()
    if "--ops" in sys.argv:
        # per-kernel microbenchmarks (fused vs reference vs legacy)
        _emit(_measure_ops(), dev)
    elif "--data" in sys.argv:
        # input-pipeline throughput (docs/data.md) — host-side work, with
        # device placement through the prefetcher
        _emit(_measure_data(), dev)
    elif "--serve" in sys.argv:
        # --spec k: k-token speculative decoding + cross-request
        # prefix caching over a shared-prefix workload mix
        # (docs/serving.md "Speculative decoding & prefix caching")
        spec = int(_flag_operand("--spec", "0")) \
            if "--spec" in sys.argv else 0
        kill_mode = _flag_operand("--kill-mode", "thread")
        if kill_mode not in ("thread", "process"):
            raise SystemExit(
                f"--kill-mode must be thread|process, got {kill_mode!r}")
        kill_at = (float(_flag_operand("--kill-at", "0"))
                   if "--kill-at" in sys.argv else None)
        if "--trace" in sys.argv:
            # replay mode: re-drive a recorded/generated trace and
            # report digest divergence (docs/serving.md "Flight
            # recorder & replay")
            _emit(_measure_serve_replay(
                _flag_operand("--trace", "trace.jsonl"),
                int(_flag_operand("--replicas", "2")),
                speed=float(_flag_operand("--speed", "0")),
                kill_at=kill_at, kill_mode=kill_mode), dev)
        elif "--disagg" in sys.argv:
            # prefill/decode disaggregation: P prefill + D decode
            # replicas, tp-sharded decode (docs/serving.md
            # "Disaggregated serving"); --tp defaults to 2 so the
            # tensor-parallel fused step is on the measured path
            _emit(_measure_serve_disagg(
                _flag_operand("--disagg", "1x2"),
                int(_flag_operand("--tp", "2"))), dev)
        elif "--tenants" in sys.argv:
            # multi-tenant QoS mode: solo / qos-on / qos-off arms
            # over one seeded tenant mix with an abusive tenant
            # (docs/serving.md "Per-tenant QoS"); headline is the
            # protected tenant's p99 TTFT degradation vs solo
            _emit(_measure_serve_tenants(
                replicas=int(_flag_operand("--replicas", "2")),
                requests=int(_flag_operand("--requests", "128")),
                seed=int(_flag_operand("--seed", "0")),
                speed=float(_flag_operand("--speed", "1.0"))), dev)
        elif "--replicas" in sys.argv:
            # fleet mode: aggregate tokens/s + tail TTFT under
            # replica loss (docs/serving.md "Fleet, failover &
            # overload"); --kill-at S kills a loaded replica S
            # seconds into the load window.  --kill-mode process:
            # process-transport fleet, the kill is a real SIGKILL on a
            # worker (ledger failover + respawn instead of in-process
            # salvage) — on one chip the workers cannot claim the
            # device this parent holds and die at start-up (ROADMAP C3)
            _emit(_measure_serve_fleet(
                int(_flag_operand("--replicas", "2")), kill_at,
                spec=spec, kill_mode=kill_mode), dev)
        else:
            _emit(_measure_serve(spec=spec), dev)
    else:
        _emit(_measure(), dev)


if __name__ == "__main__":
    main()
