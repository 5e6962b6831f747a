"""Traffic kinds `closed_loop` and `closed_loop_waves`: a fixed number of
clients, each waiting for its reply before it sends again.

Parameters (the traffic file): ``clients``; ``prompt_lens`` and
``output_lens``, two written-out lists of `clients` values that belong to the
cell, pair i being (prompt_lens[i], output_lens[i]); ``waves`` (all clients
send together and the next wave waits for the last stream: no ramp) or not
(each client sends its next request when its last one ends, after a ramp of
``ramp_steps`` engine steps whose first requests are cut to the client's
share ``ramp_fractions[i]`` of their lengths, which spreads the phases); ``sample_requests`` (compared with the reference);
``reference_pad_to``; ``limits``.  The seed chooses the token ids and the weights: never a
length, and not the order either (see `Plan`).

Tokens are counted at the engine step that processed them.  The engine
hands out tokens, not what it fed, so the driver mirrors the scheduler's
documented plan (a stream feeds min(pending, C) tokens a step, C being the
prefill chunk while any stream has more than one token pending, else 1) and
checks the mirror at every stream's first token: a stream whose prompt the
mirror did not see fully fed by then is corrected there and counted in
`mirror_corrections`.
"""
from __future__ import annotations

import functools
import gc
import json
import resource
import sys
import time
import types

import numpy as onp

from benchmark.harness.profile import Tracer, span
from benchmark.reduce.stats import percentile

TRACE_STEPS = 14
WARM_REQUESTS = 4


class Stream:
    __slots__ = ("client", "prompt", "max_new", "handle", "stamps", "ctx",
                 "n_out", "ramp")

    def __init__(self, client, prompt, max_new, ramp=False):
        self.client, self.prompt, self.max_new = client, prompt, max_new
        self.handle, self.stamps = None, []
        self.ctx, self.n_out, self.ramp = 0, 0, ramp


class Plan:
    """What each client sends: pair `(client + k) mod n` of the cell's pairs
    for its k-th request, so that every round of requests holds every pair
    once.  The seed chooses the token ids alone.  It chose the deal of the
    pairs and the ramp's cuts too, until two runs of one seed agreed to
    0.05% in tokens/s and two seeds differed by 1.9% (and by 11% in the
    first-token tail): the order is part of the work, so the file fixes it."""

    def __init__(self, traffic: dict, vocab: int, seed: int):
        self.n = int(traffic["clients"])
        pl, ol = traffic["prompt_lens"], traffic["output_lens"]
        if len(pl) != self.n or len(ol) != self.n:
            raise ValueError("prompt_lens and output_lens have one value a "
                             "client")
        self.pairs = [(int(p), int(o)) for p, o in zip(pl, ol)]
        self.stagger = [float(u) for u in traffic.get("ramp_fractions", [])]
        if self.stagger and (len(self.stagger) != self.n or not all(
                0 < u <= 1 for u in self.stagger)):
            raise ValueError("ramp_fractions has one value in (0, 1] a "
                             "client")
        self.rng = onp.random.default_rng([int(seed), 0x6774])
        self.vocab = vocab
        self.sent = [0] * self.n

    def next(self, client: int, cut: bool = False) -> Stream:
        k = self.sent[client]
        self.sent[client] += 1
        p, o = self.pairs[(client + k) % self.n]
        if cut:
            u = self.stagger[client]
            p, o = max(2, round(p * u)), max(1, round(o * u))
        ids = self.rng.integers(0, self.vocab, p).tolist()
        return Stream(client, ids, o, ramp=cut)


class Loop:
    """Drives `InferenceEngine.submit` + `step` and keeps the count."""

    def __init__(self, eng, chunk: int):
        self.eng, self.chunk = eng, chunk
        self.live = []
        self.steps = []          # one record per engine step
        self.done = []           # streams that ended, in order
        self.mirror_corrections = 0
        self._emitted = 0
        self._fix = 0

    def submit(self, s: Stream):
        def on_token(_tok, _req, s=s):
            s.stamps.append(time.perf_counter())
            self._emitted += 1
            if s.n_out == 0 and s.ctx != len(s.prompt):
                self.mirror_corrections += 1
                self._fix += len(s.prompt) - s.ctx
                s.ctx = len(s.prompt)
            s.n_out += 1
        with span("bench.submit"):
            s.handle = self.eng.submit(s.prompt, max_new_tokens=s.max_new,
                                       greedy=True, on_token=on_token)
        self.live.append(s)

    def step(self) -> dict:
        pend = [len(s.prompt) + s.n_out - s.ctx for s in self.live]
        width = self.chunk if any(p > 1 for p in pend) else 1
        tokens = attended = kv_read = 0
        for s, p in zip(self.live, pend):
            nt = min(p, width)
            attended += nt * s.ctx + nt * (nt + 1) // 2
            kv_read += s.ctx + nt
            tokens += nt
            s.ctx += nt
        self._emitted = self._fix = 0
        t0 = time.perf_counter()
        with span("bench.step"):
            ran = self.eng.step()
        t1 = time.perf_counter()
        if not ran:
            raise RuntimeError("the engine had nothing to do in a closed "
                               "loop: a stream was lost")
        rec = {"t0": t0, "t1": t1, "width": width,
               "tokens": tokens + self._fix, "emitted": self._emitted,
               "attended": attended, "kv_read": kv_read,
               "active": len(self.live)}
        self.steps.append(rec)
        ended = [s for s in self.live if s.handle.done()]
        if ended:
            self.live = [s for s in self.live if not s.handle.done()]
            self.done.extend(ended)
        rec["ended"] = ended
        return rec


class HostWatch:
    """What the host did over the window, for an `info` line: CPU time the
    machine lost to its hypervisor (`steal`), the process's own CPU time,
    faults and context switches, and Python's collections with the longest.
    A run that reads far off says by it where its time went; no metric
    reads it."""

    def __init__(self):
        self.gc_n, self.gc_s, self.gc_max, self._t = [0, 0, 0], 0.0, 0.0, 0.0

    def _on_gc(self, phase, info):
        if phase == "start":
            self._t = time.perf_counter()
        else:
            d = time.perf_counter() - self._t
            self.gc_n[info["generation"]] += 1
            self.gc_s += d
            self.gc_max = max(self.gc_max, d)

    @staticmethod
    def _cpu():
        try:
            with open("/proc/stat") as f:
                return [int(x) for x in f.readline().split()[1:9]]
        except (OSError, ValueError):
            return [0] * 8

    def start(self):
        self.cpu0, self.ru0 = self._cpu(), resource.getrusage(
            resource.RUSAGE_SELF)
        gc.callbacks.append(self._on_gc)

    def stop(self) -> dict:
        gc.callbacks.remove(self._on_gc)
        cpu, ru = self._cpu(), resource.getrusage(resource.RUSAGE_SELF)
        d = [b - a for a, b in zip(self.cpu0, cpu)]
        return {"machine_jiffies": dict(zip(
                    ("user", "nice", "system", "idle", "iowait", "irq",
                     "softirq", "steal"), d)),
                "process": {k: getattr(ru, k) - getattr(self.ru0, k)
                            for k in ("ru_utime", "ru_stime", "ru_minflt",
                                      "ru_majflt", "ru_nvcsw", "ru_nivcsw")},
                "gc": {"collections": self.gc_n, "seconds": self.gc_s,
                       "longest_s": self.gc_max}}


def longest(steps: list, k: int = 5) -> dict:
    """The window's `k` longest engine steps and longest pauses between two
    steps, as [index, ms], beside the medians: a few long stalls or many
    slow steps."""
    inside = [(r["t1"] - r["t0"]) * 1e3 for r in steps]
    between = [(b["t0"] - a["t1"]) * 1e3 for a, b in zip(steps, steps[1:])]

    def top(xs):
        return [[i, round(xs[i], 2)] for i in sorted(
            range(len(xs)), key=xs.__getitem__, reverse=True)[:k]]
    return {"step_ms_median": percentile(inside, 50) if inside else None,
            "between_ms_median": percentile(between, 50) if between else None,
            "longest_steps": top(inside), "longest_between": top(between)}


def _limits_numbers(cell, seed, sample, precision_control=False):
    """Reference over each sampled request's prompt and served tokens:
    the widest gap by which a served token's logit lies below the
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
    p = W.as_float32(W.make(fam.param_spec(cfg), seed))

    @jax.jit
    def gaps(p, ids, pos, tok, valid):
        logits = ref.logits_at(p, cfg, ids, pos)
        best = logits.max(-1)
        served = jnp.take_along_axis(logits, tok[..., None], -1)[..., 0]
        return jnp.where(valid, best - served, 0.0), logits

    gap, logits = gaps(p, jnp.asarray(ids), jnp.asarray(pos),
                       jnp.asarray(tok), jnp.asarray(valid))
    out = {"token_gap": (float(gap.max()), None),
           "compared_tokens": int(valid.sum())}
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


def pick_sample(done: list, n: int, seed: int) -> list:
    """`n` of the finished requests, drawn from the seed, the longest
    context among them."""
    if not done:
        return []
    longest = max(range(len(done)), key=lambda i: len(done[i].prompt)
                  + len(done[i].handle.tokens))
    rng = onp.random.default_rng([int(seed), 0x5A3B])
    rest = [i for i in rng.permutation(len(done)) if i != longest]
    return [done[i] for i in [longest] + rest[:max(0, n - 1)]]


def run(ctx, waves: bool = False) -> dict:
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

    if not waves:
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
    if waves:
        for c in range(n):
            loop.submit(plan.next(c))
    while True:
        rec = loop.step()
        if rec["t1"] - t0 > ctx.seconds:
            loop.steps.pop()
            cut_ended = rec["ended"]
            break
        for s in rec["ended"]:
            if not waves:
                loop.submit(plan.next(s.client))
        if waves and not loop.live:
            for c in range(n):
                loop.submit(plan.next(c))
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
    gaps = []
    step_width = [r["width"] for r in steps]
    for s in every:
        for a, b in zip(s.stamps, s.stamps[1:]):
            if t0 <= b <= t_end:
                gaps.append((b - a) * 1e3)
    ttft = [(s.handle.first_token_ts - s.handle.submitted_ts) * 1e3
            for s in every if not s.ramp and s.handle.first_token_ts
            and t0 <= s.handle.first_token_ts <= t_end]
    wide = sum(r["emitted"] for r in steps if r["width"] > 1)
    total_emitted = sum(r["emitted"] for r in steps)
    print(f"info steps={len(steps)} wide_steps="
          f"{sum(w > 1 for w in step_width)} tokens_emitted_at_wide_steps="
          f"{wide}/{total_emitted} gaps={len(gaps)} ttft_samples={len(ttft)} "
          f"finished={len(finished)} mirror_corrections="
          f"{loop.mirror_corrections} ramp_steps={ramp_steps}",
          file=sys.stderr)

    print(f"info host {json.dumps({**longest(steps), **host_did})}",
          file=sys.stderr)

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
    del eng, loop, plan, every, ended, finished
    gc.collect()

    numbers = _limits_numbers(cell, seed, sample, ctx.control >= 2) \
        if sample else {"token_gap": (float("inf"), None)}
    limits = traffic["limits"]
    shown = {"token_gap": [numbers["token_gap"][0], limits["token_gap"]],
             "compared_tokens": [numbers.get("compared_tokens", 0),
                                 limits["min_compared_tokens"]]}
    ok = ((limits["token_gap"] is None
           or shown["token_gap"][0] <= limits["token_gap"])
          and shown["compared_tokens"][0] >= limits["min_compared_tokens"])
    print(f"check token_gap={shown['token_gap'][0]:.6g} "
          f"limit={limits['token_gap']}", file=sys.stderr)
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


def _detach(s: Stream):
    """Keep a stream's prompt and tokens without the engine's request."""
    return types.SimpleNamespace(
        prompt=list(s.prompt), handle=types.SimpleNamespace(
            tokens=[int(t) for t in s.handle.tokens]))
