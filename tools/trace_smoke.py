"""Tracing + performance-attribution smoke (`make trace-smoke`,
docs/observability.md "Tracing & performance attribution").

A short CPU run drives BOTH instrumented subsystems in one process —
3 serve requests through the continuous-batching scheduler and 5 train
steps through `ShardedTrainStep` — then asserts the whole observability
contract:

* the exported Chrome/Perfetto JSON loads and contains a COMPLETE span
  tree per request (queue -> prefill -> decode -> stream under one
  request root, one trace id per request),
* TTFT decomposes (queue/prefill/first-decode child spans + a ttft_ms
  tag on the root),
* train spans carry step ids that match the run journal's
  step_dispatched/step_retired rows (cross-correlation),
* the serve and train tracers share nothing (distinct trace-id spaces),
* the always-on `mfu_estimate` gauge is NONZERO on CPU (projected peak;
  flops from XLA cost_analysis captured at warmup),
* `tools/diagnose.py --trace` renders the timeline without error.

Exits non-zero with a reason on any failure — cheap enough for CI.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import tempfile

os.environ.setdefault("JAX_PLATFORMS", "cpu")

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

SERVE_REQUESTS = 3
TRAIN_STEPS = 5


def fail(msg: str) -> int:
    print(f"FAIL: {msg}", file=sys.stderr)
    return 1


def main() -> int:
    import jax
    import jax.numpy as jnp
    import numpy as onp

    import mxnet_tpu as mx
    from mxnet_tpu import optimizer as opt
    from mxnet_tpu import telemetry, tracing
    from mxnet_tpu.gluon import nn
    from mxnet_tpu.models.gpt import GPTConfig, GPTForCausalLM
    from mxnet_tpu.parallel import make_mesh, make_sharded_train_step
    from mxnet_tpu.serve import InferenceEngine, ServeConfig

    workdir = tempfile.mkdtemp(prefix="mxtpu-trace-")
    journal_path = os.path.join(workdir, "journal.jsonl")
    telemetry.enable(journal_path=journal_path)
    tracing.enable(dir=workdir)

    # ---- serve: 3 requests through the scheduler ---------------------
    cfg = GPTConfig(vocab_size=128, hidden_size=32, num_layers=1,
                    num_heads=2, intermediate_size=64, max_position=64,
                    dropout=0.0)
    model = GPTForCausalLM(cfg)
    model.initialize()
    model(mx.np.array([[1, 2]], dtype="int32"))
    eng = InferenceEngine(model, ServeConfig(
        max_len=48, max_slots=2, num_pages=13, page_size=8,
        prefill_chunk=4))
    eng.warmup()
    streamed = {}
    handles = [
        eng.submit([1, 2, 3, 4, 5, 6], max_new_tokens=4,
                   on_token=lambda t, r: streamed.setdefault(r.id, [])
                   .append(t))
        for _ in range(SERVE_REQUESTS)]
    eng.run_until_idle()
    for h in handles:
        h.result(timeout=10)

    # ---- train: 5 steps with AOT warmup ------------------------------
    net = nn.Dense(4, in_units=8)
    net.initialize()
    mesh = make_mesh({"dp": 1}, jax.devices()[:1])
    step = make_sharded_train_step(
        net, opt.SGD(learning_rate=1e-2),
        lambda out, x, y: jnp.mean((out - y) ** 2), mesh,
        num_model_args=1)
    rng = onp.random.RandomState(0)
    xs = rng.uniform(-1, 1, (8, 8)).astype("float32")
    ys = rng.uniform(-1, 1, (8, 4)).astype("float32")
    step.warmup(xs, ys)
    for _ in range(TRAIN_STEPS):
        step.dispatch(*step.place_batch(xs, ys))
    step.drain()
    if step.trace_count != 1:
        return fail(f"trace_count {step.trace_count} != 1 — tracing "
                    "must never retrace the step")

    # ---- export + structural asserts ---------------------------------
    trace_path = tracing.export_chrome()
    with open(trace_path) as f:
        doc = json.load(f)              # must be loadable, plain JSON
    spans = [e for e in doc["traceEvents"] if e.get("ph") == "X"]
    if not spans:
        return fail("exported trace has no complete spans")

    # every scheduler step: one serve.step tiled by its five phases
    steps = [s for s in spans if s["name"] == "serve.step"]
    phases = {p: sum(s["name"] == f"serve.step.{p}" for s in spans)
              for p in ("admit", "plan", "launch", "wait", "emit")}
    if not steps or set(phases.values()) != {len(steps)}:
        return fail(f"{len(steps)} serve.step spans but phases {phases}")
    by_req: dict = {}
    for s in spans:
        rid = (s.get("args") or {}).get("request_id")
        if rid is not None:
            by_req.setdefault(rid, []).append(s)
    if len([r for r in by_req.values()
            if any(s["name"] == "serve.request" for s in r)]) \
            != SERVE_REQUESTS:
        return fail(f"expected {SERVE_REQUESTS} serve.request roots, "
                    f"request ids seen: {sorted(by_req)}")
    for rid, ss in by_req.items():
        names = {s["name"] for s in ss}
        need = {"serve.request", "serve.queue"}
        if not need <= names:
            return fail(f"request {rid} span tree incomplete: {names}")
        if not ({"serve.prefill_chunk", "serve.first_decode"} & names):
            return fail(f"request {rid} has no prefill span: {names}")
        if "serve.decode" not in names:
            return fail(f"request {rid} has no decode span: {names}")
        root = next(s for s in ss if s["name"] == "serve.request")
        args = root["args"]
        if args.get("state") != "finished":
            return fail(f"request {rid} root state {args.get('state')}")
        if not isinstance(args.get("ttft_ms"), (int, float)):
            return fail(f"request {rid} ttft_ms missing on root: {args}")
        # one trace id per request; every child hangs off the root tree
        tids = {s["args"]["trace_id"] for s in ss}
        if len(tids) != 1:
            return fail(f"request {rid} spans span {len(tids)} trace "
                        f"ids: {tids}")
        root_id = root["args"]["span_id"]
        children = [s for s in ss if s is not root]
        if not all(s["args"].get("parent_id") == root_id
                   for s in children):
            return fail(f"request {rid}: child spans not parented to "
                        "the request root")

    # no cross-contamination between the two tracers' id spaces
    serve_tids = {s["args"]["trace_id"] for s in spans
                  if s["cat"] == "serve"}
    train_tids = {s["args"]["trace_id"] for s in spans
                  if s["cat"] == "train"}
    if not serve_tids or not train_tids:
        return fail(f"missing a tracer: serve={len(serve_tids)} "
                    f"train={len(train_tids)} trace ids")
    if serve_tids & train_tids:
        return fail(f"serve/train trace ids overlap: "
                    f"{serve_tids & train_tids}")

    # train spans <-> journal step-id correlation
    dev_steps = sorted(s["args"]["step"] for s in spans
                       if s["name"] == "train.device")
    if dev_steps != list(range(1, TRAIN_STEPS + 1)):
        return fail(f"train.device steps {dev_steps} != "
                    f"{list(range(1, TRAIN_STEPS + 1))}")
    rows = telemetry.RunJournal.read(journal_path)
    retired = sorted(r["step"] for r in rows
                     if r["event"] == "step_retired")
    if retired != dev_steps:
        return fail(f"journal step_retired ids {retired} != train span "
                    f"steps {dev_steps}")
    costed = [r for r in rows if r["event"] == "step_retired"
              and isinstance(r.get("cost"), dict)]
    if not costed:
        return fail("no step_retired row carries the cost-feature "
                    "vector")
    if not costed[0]["cost"].get("flops"):
        return fail(f"cost vector has no flops: {costed[0]['cost']}")

    # always-on MFU gauge: nonzero on CPU (projected peak)
    g = telemetry.registry().get("mfu_estimate")
    if g is None:
        return fail("mfu_estimate gauge was never set")
    mfu = g.value(program="train_step")
    if not mfu > 0:
        return fail(f"mfu_estimate{{program=train_step}} = {mfu}, want "
                    "> 0 (CPU projected-peak proxy)")

    # diagnose renders the timeline
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "tools", "diagnose.py"),
         "--trace", trace_path], capture_output=True, text=True,
        timeout=60)
    if proc.returncode != 0 or "critical path" not in proc.stdout:
        return fail(f"diagnose --trace failed rc={proc.returncode}: "
                    f"{proc.stderr[-500:]}\n{proc.stdout[-500:]}")

    telemetry.disable()
    tracing.disable()
    print(f"trace smoke OK: {len(spans)} spans "
          f"({len(serve_tids)} serve / {len(train_tids)} train traces), "
          f"mfu_estimate {mfu:.3g} (projected), {trace_path}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
