"""Traffic kind `closed_loop_blocked`: `closed_loop` (no waves) for a model
whose weights fill a third of the chip.  Same traffic, same counting of
tokens: `Plan`, `Loop`, `Stream`, `HostWatch`, `longest` and `pick_sample`
are `closed_loop`'s own, imported unchanged, and the window below is its
window line for line.  Three things differ, all after the window:

- **the engine is really freed before the reference runs.**  In
  `closed_loop.run` the statement ``for s in every:`` leaves its last
  stream in `s`, an unfinished request whose `on_token` closure holds the
  `Loop` and through it the engine: 14.5 GB stayed allocated and the
  reference's weights did not fit (my chip run, PR 30).  Here the loop is
  cut from its engine and every stream is detached before the engine's
  last name goes.
- **the reference is handed the weights in their stored type** and widens
  them matrix by matrix where it uses them (`reference/afmoe.py::f32`):
  `closed_loop._limits_numbers` widens all of them in one piece, three
  times the stored bytes at its peak.

- **near-ties in the router are rounding, not faults.**  A bfloat16
  program and a float32 reference now and then choose a different last
  expert where the last chosen and the first not chosen score lie within
  bfloat16's step, and where one of the two is held here that position's
  FFN output changes by a whole expert (seed 2147486102 read `token_gap`
  0.4475 at one of 247 positions, 0.0 at the median, beside the fp8
  control's 0.796: my chip run, PR 30).  The reference also returns each
  compared position's smallest such routing margin over the expert layers;
  positions whose margin is under ``limits["route_margin_eps"]`` are left
  out of `token_gap`, their number is printed, their share is held under
  ``limits["max_left_out_share"]``, and ``min_compared_tokens`` counts the
  positions that are left.  Only the position's OWN margin counts: a flip
  at an earlier position reaches later ones through one key among
  thousands, and read 8e-4 (seed 2147486101).

Parameters: `closed_loop`'s, and the two limits above (without
``route_margin_eps`` nothing is left out).
"""
from __future__ import annotations

import functools
import gc
import json
import sys
import time

import numpy as onp

from benchmark.harness.profile import Tracer
from benchmark.kinds.closed_loop import (TRACE_STEPS, WARM_REQUESTS,
                                         HostWatch, Loop, Plan, Stream,
                                         _detach, longest, pick_sample)
from benchmark.reduce.stats import percentile


def limits_numbers(cell, seed, sample, precision_control=False):
    """`closed_loop._limits_numbers` with the weights in their stored
    type: the widest gap by which a served token's logit lies below the
    reference's best (`token_gap`); with `precision_control`, also the same
    for the token the fp8 control (and, for the record, int8) puts first
    at each of those positions."""
    import jax
    import jax.numpy as jnp
    from benchmark.harness import weights as W
    fam, ref = cell.family(), cell.reference()
    cfg, traffic = cell.config, cell.traffic
    pad = int(traffic["reference_pad_to"])
    max_o = max(len(s.handle.tokens) for s in sample)
    ids = onp.zeros((len(sample), pad), onp.int32)
    pos = onp.zeros((len(sample), max_o), onp.int32)
    tok = onp.zeros((len(sample), max_o), onp.int32)
    valid = onp.zeros((len(sample), max_o), bool)
    for r, s in enumerate(sample):
        out = [int(t) for t in s.handle.tokens]
        seq = list(s.prompt) + out
        if len(seq) > pad:
            raise ValueError(f"a served context of {len(seq)} tokens is "
                             f"longer than reference_pad_to={pad}")
        ids[r, :len(seq)] = seq
        pos[r, :len(out)] = len(s.prompt) - 1 + onp.arange(len(out))
        tok[r, :len(out)] = out
        valid[r, :len(out)] = True
    p = W.make(fam.param_spec(cfg), seed)
    eps = traffic["limits"].get("route_margin_eps")

    @jax.jit
    def gaps(p, ids, pos, tok):
        if eps is None:
            logits = ref.logits_at(p, cfg, ids, pos)
            margin = jnp.full(pos.shape, jnp.inf)
        else:
            logits, margin = ref.logits_and_margins_at(p, cfg, ids, pos)
        best = logits.max(-1)
        served = jnp.take_along_axis(logits, tok[..., None], -1)[..., 0]
        return best - served, logits, margin

    gap, logits, margin = gaps(p, jnp.asarray(ids), jnp.asarray(pos),
                               jnp.asarray(tok))
    gap, served_at = onp.asarray(gap), valid.copy()
    n_valid = int(valid.sum())
    if eps is not None:
        valid &= onp.asarray(margin) >= eps
    kept = gap[valid] if valid.any() else onp.asarray([1e30])
    out = {"token_gap": (float(kept.max()), None),
           "compared_tokens": int(valid.sum()),
           "left_out": n_valid - int(valid.sum()),
           "left_out_share": 1.0 - int(valid.sum()) / max(1, n_valid),
           "token_gap_with_near_ties": float(gap[served_at].max()),
           # how the gap is spread: one position's flip or all of them
           "token_gap_quantiles": [float(onp.quantile(kept, q))
                                   for q in (0.5, 0.9, 0.99)]}
    if precision_control:
        @functools.partial(jax.jit, static_argnames="precision")
        def control(p, ids, pos, valid, logits, precision):
            low = ref.logits_at(p, cfg, ids, pos, precision=precision)
            first = jnp.argmax(low, -1)
            at = jnp.take_along_axis(logits, first[..., None], -1)[..., 0]
            return jnp.where(valid, logits.max(-1) - at, 0.0).max()
        for name, precision in (("control_token_gap", "fp8"),
                                ("control_int8_token_gap", "int8")):
            out[name] = float(control(
                p, jnp.asarray(ids), jnp.asarray(pos), jnp.asarray(valid),
                logits, precision=precision))
    return out


def run(ctx) -> dict:
    cell, seed = ctx.cell, ctx.seed
    cfg, traffic = cell.config, cell.traffic
    fam = cell.family()
    n = int(traffic["clients"])

    eng, _compile_s = fam.build_engine(cfg, seed, ctx.devices)
    chunk = eng.serve_config.prefill_chunk
    widths0 = sorted(eng._execs)
    plan = Plan(traffic, cfg["vocab_size"], seed)
    loop = Loop(eng, chunk)

    # warm both programs once with the real shapes (every step has them)
    warm = onp.random.default_rng([int(seed), 0x77A2])
    for c in range(WARM_REQUESTS):
        loop.submit(Stream(c, warm.integers(
            0, cfg["vocab_size"], chunk + 3).tolist(), 3, ramp=True))
    while loop.live:
        loop.step()

    for c in range(n):
        loop.submit(plan.next(c, cut=True))
    for _ in range(int(traffic["ramp_steps"])):
        for s in loop.step()["ended"]:
            loop.submit(plan.next(s.client))
    ramp_steps = len(loop.steps)
    execs0 = dict(eng._execs)
    tracer = Tracer(ctx.trace, ctx.out_dir)
    ctx.before_window()

    # -- the window ---------------------------------------------------------
    steady_from = 0          # first step after the profiler stopped
    host = HostWatch()
    host.start()
    tracer.start()
    t0 = time.perf_counter()
    while True:
        rec = loop.step()
        if rec["t1"] - t0 > ctx.seconds:
            loop.steps.pop()
            cut_ended = rec["ended"]
            break
        for s in rec["ended"]:
            loop.submit(plan.next(s.client))
        if tracer.on and len(loop.steps) - ramp_steps == TRACE_STEPS:
            tracer.stop()
            steady_from = TRACE_STEPS
    tracer.stop()
    host_did = host.stop()
    compiles_in_window = ctx.after_window()
    steps = loop.steps[ramp_steps:]
    if not steps:
        raise RuntimeError("no engine step finished inside the window")
    t_end = steps[-1]["t1"]
    span_s = t_end - t0
    no_compile = (eng._execs == execs0 and sorted(eng._execs) == widths0
                  and compiles_in_window == 0)

    cut = set(map(id, cut_ended))
    ended = [s for s in loop.done if not s.ramp and id(s) not in cut
             and s.stamps and s.stamps[-1] >= t0]
    bad = [s for s in ended if s.handle.state != "finished"
           or len(s.handle.tokens) != s.max_new
           or not all(0 <= int(t) < cfg["vocab_size"]
                      for t in s.handle.tokens)]
    lost = [s for s in loop.done if not s.stamps and not s.ramp]
    finished = [s for s in ended if s not in bad]

    every = loop.done + loop.live
    gaps = [(b - a) * 1e3 for s in every
            for a, b in zip(s.stamps, s.stamps[1:]) if t0 <= b <= t_end]
    ttft = [(s.handle.first_token_ts - s.handle.submitted_ts) * 1e3
            for s in every if not s.ramp and s.handle.first_token_ts
            and t0 <= s.handle.first_token_ts <= t_end]
    wide = sum(r["emitted"] for r in steps if r["width"] > 1)
    total_emitted = sum(r["emitted"] for r in steps)
    print(f"info steps={len(steps)} wide_steps="
          f"{sum(r['width'] > 1 for r in steps)} "
          f"tokens_emitted_at_wide_steps={wide}/{total_emitted} "
          f"gaps={len(gaps)} ttft_samples={len(ttft)} "
          f"finished={len(finished)} mirror_corrections="
          f"{loop.mirror_corrections} ramp_steps={ramp_steps}",
          file=sys.stderr)
    print(f"info host {json.dumps({**longest(steps), **host_did})}",
          file=sys.stderr)
    recent = eng.scheduler.phase_stats(slowest=1024)["slowest_steps"]
    print("info engine " + json.dumps({
        **{k: v for k, v in eng.stats().items()
           if k in ("free_pages", "free_pages_sliding", "kv_pages_released",
                    "pool_bytes", "weight_bytes", "steps_executed")},
        # the routing of the last steps: what a seed's router favours
        **{k + "_median": percentile([r[k] for r in recent], 50)
           for k in ("moe_experts_touched", "moe_tokens_routed",
                     "moe_load_max_over_mean", "tokens_fed")
           if recent and k in recent[0]}}), file=sys.stderr)

    device = ctx.describe_device(*eng._execs.values())
    trace = tracer.read()
    sample = pick_sample(finished, int(traffic["sample_requests"]), seed)
    window = {"kind": "closed_loop", "span_s": span_s, "t0": t0,
              "steps": [{k: v for k, v in r.items() if k != "ended"}
                        for r in steps],
              "traced_steps": min(len(steps), TRACE_STEPS),
              "steady_from": steady_from if steady_from < len(steps) else 0,
              "tokens": sum(r["tokens"] for r in steps),
              "pool_elements": int(eng.pools.arrays["k"].size),
              "pool_dtype": str(eng.pools.arrays["k"].dtype),
              "max_slots": eng.serve_config.max_slots,
              "page_size": eng.serve_config.page_size,
              "mirror_corrections": loop.mirror_corrections}
    sample = [_detach(s) for s in sample]
    attempted, failed = len(ended) + len(lost), len(bad) + len(lost)
    # free the engine: no stream, record or loop may keep a request (its
    # callback holds the loop) once the engine's last name is gone
    loop.eng = None
    del eng, loop, plan, every, ended, finished, bad, lost, cut_ended, rec
    gc.collect()
    import jax
    jax.clear_caches()
    for d in ctx.devices:
        print(f"info memory_after_free {d.id} "
              f"{(d.memory_stats() or {}).get('bytes_in_use')}",
              file=sys.stderr)

    numbers = limits_numbers(cell, seed, sample, ctx.control >= 2) \
        if sample else {"token_gap": (float("inf"), None)}
    limits = traffic["limits"]
    shown = {"token_gap": [numbers["token_gap"][0], limits["token_gap"]],
             "compared_tokens": [numbers.get("compared_tokens", 0),
                                 limits["min_compared_tokens"]]}
    ok = ((limits["token_gap"] is None
           or shown["token_gap"][0] <= limits["token_gap"])
          and shown["compared_tokens"][0] >= limits["min_compared_tokens"])
    if limits.get("route_margin_eps") is not None:
        shown["left_out_share"] = [numbers.get("left_out_share", 1.0),
                                   limits["max_left_out_share"]]
        ok = ok and shown["left_out_share"][0] <= limits["max_left_out_share"]
        print(f"check left_out={numbers.get('left_out')} share="
              f"{shown['left_out_share'][0]:.4g} at_most="
              f"{limits['max_left_out_share']} (routing margin under "
              f"{limits['route_margin_eps']}); token_gap with them="
              f"{numbers.get('token_gap_with_near_ties')}", file=sys.stderr)
        if ctx.control >= 1:
            shown["token_gap_with_near_ties"] = [
                numbers.get("token_gap_with_near_ties"), None]
    print(f"check token_gap={shown['token_gap'][0]:.6g} "
          f"limit={limits['token_gap']} quantiles(0.5, 0.9, 0.99)="
          f"{numbers.get('token_gap_quantiles')}", file=sys.stderr)
    print(f"check compared_tokens={shown['compared_tokens'][0]} "
          f"at_least={limits['min_compared_tokens']}", file=sys.stderr)
    for name in ("control_token_gap", "control_int8_token_gap"):
        if name in numbers:
            print(f"control {name}={numbers[name]:.6g}", file=sys.stderr)
            shown[name] = [numbers[name], None]
    if not no_compile:
        print("check compiled_inside_window=1 limit=0", file=sys.stderr)
        shown["compiled_inside_window"] = [1, 0]

    e2e = {"serve_tokens_per_s": window["tokens"] / span_s}
    if gaps:
        e2e["itl_p95_ms"] = percentile(gaps, 95)
        window["itl_p50_ms"] = percentile(gaps, 50)
    if ttft:
        e2e["ttft_p95_ms"] = percentile(ttft, 95)
    return {
        "correct": bool(ok and no_compile and failed == 0),
        "attempted": attempted, "failed": failed,
        "end_to_end": e2e, "window": window, "trace": trace,
        "device": device, "checks": shown}
