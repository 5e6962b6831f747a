"""Traffic kind `train_job`: a pretraining job at a fixed global batch.

Parameters (the traffic file): ``global_batch``, ``seq_len``, ``n_masked``,
``valid_length_range``, ``pool_batches`` (device batches cycled),
``mesh`` (axis -> size), ``optimizer``, ``check_steps`` (followed by the
reference), ``reference_block_rows``, ``limits``.

Set-up builds ONE compiled step with its state, drives it through
`check_steps` steps by the window's own call (`dispatch`, then the loss),
reads the first gradient's norms out of Adam's first moment and the norm of
each parameter's change, and hands the same object to the window.  The window
keeps one dispatch ahead of the step it waits for.  After the window (peak
memory read, the program's state freed) the reference follows the same
steps in float32 and the readings are compared.
"""
from __future__ import annotations

import gc
import math
import statistics
import sys
import time

import numpy as onp

from benchmark.harness.profile import Tracer, span

TRACE_STEPS = 8


def program_readings(prog, n_steps: int) -> dict:
    """Drive the step `n_steps` times from its start and keep what is
    compared, on the host: the losses, the first gradient as the optimizer
    got it (Adam's first moment after one step, over 1 - beta1) and the
    parameters after the last step.  Each step goes through `dispatch` and
    waits for its loss."""
    step, strip = prog.step, prog.strip
    losses, grad = [], None
    for i in range(n_steps):
        with span("bench.check_step"):
            handle = step.dispatch(*prog.batches[i])
            losses.append(float(handle.loss))
        if i == 0:
            scale = 1.0 / (1.0 - prog.hp["beta1"])
            grad = {strip(n): onp.asarray(s[0], onp.float32) * scale
                    for n, s in step.opt_state.items()}
    return {"losses": losses, "grad": grad,
            "params": {strip(n): onp.asarray(v)
                       for n, v in step.pvals.items()}}


def _leaf_numbers(prog: dict, ref: dict, start: dict) -> dict:
    """Per leaf, on the device: the norms of both first gradients and of
    their difference, and the norms of both parameter changes over the
    elements that the reference's first gradient moves."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def leaf(gp, gr, pp, pr, p0):
        gp, gr, p0 = (x.astype(jnp.float32) for x in (gp, gr, p0))
        rms = jnp.sqrt(jnp.mean(jnp.square(gr)))
        moved = jnp.abs(gr) >= rms * 1e-3
        dp = jnp.where(moved, pp.astype(jnp.float32) - p0, 0.0)
        dr = jnp.where(moved, pr.astype(jnp.float32) - p0, 0.0)
        norm = jnp.linalg.norm
        return (norm(gp.ravel()), norm(gr.ravel()), norm((gp - gr).ravel()),
                norm(dp.ravel()), norm(dr.ravel()), norm((dp - dr).ravel()))

    out = {}
    for k in ref["grad"]:
        out[k] = tuple(float(x) for x in leaf(
            prog["grad"][k], ref["grad"][k], prog["params"][k],
            ref["params"][k], start[k]))
    return out


def worst_leaf(pairs: dict, diff: bool = False, keep=None):
    """max over leaves of the gap between two norms |a - b| (or, with
    `diff`, of the norm of the difference d) over max(b of the leaf, b of
    the median leaf).  `pairs`: {leaf: (a, b, d)}.  -> (gap, leaf)."""
    names = [k for k in pairs if keep is None or k in keep]
    floor = statistics.median(pairs[k][1] for k in names)
    worst, at = 0.0, None
    for k in names:
        a, b, d = pairs[k]
        g = (d if diff else abs(a - b)) / max(b, floor)
        if not math.isfinite(g):
            return float("inf"), k
        if g >= worst:
            worst, at = g, k
    return worst, at


def compare(prog: dict, ref: dict, start: dict) -> dict:
    """The numbers compared, each with the leaf it was worst at.

    - `loss<i>_rel`: each step's loss against the reference's;
    - `grad_norm_gap`: the first gradient's norm, by the worst leaf;
    - `grad_diff_rel`: the norm of the difference of the two first
      gradients, by the worst leaf (unbiased noise, which leaves a norm
      alone, shows here);
    - `dparam_norm_gap`: the norm of the parameters' change over the steps,
      by the worst leaf.  Leaves whose reference gradient is under a
      thousandth of the median leaf's are left out, and inside a leaf the
      elements whose reference gradient is under a thousandth of the leaf's
      root mean square (a key's bias inside the fused qkv bias): Adam moves
      those by round-off alone."""
    out = {}
    for i, (a, b) in enumerate(zip(prog["losses"], ref["losses"]), start=1):
        out[f"loss{i}_rel"] = (abs(a - b) / abs(b), None)
    leaves = _leaf_numbers(prog, ref, start)
    grads = {k: (v[0], v[1], v[2]) for k, v in leaves.items()}
    out["grad_norm_gap"] = worst_leaf(grads)
    out["grad_diff_rel"] = worst_leaf(grads, diff=True)
    med = statistics.median(v[1] for v in leaves.values())
    moved = {k for k, v in leaves.items() if v[1] >= med / 1000.0}
    changes = {k: (v[3], v[4], v[5]) for k, v in leaves.items()}
    out["dparam_norm_gap"] = worst_leaf(changes, keep=moved)
    out["dparam_diff_rel"] = worst_leaf(changes, diff=True, keep=moved)
    for name in ("grad_norm_gap", "grad_diff_rel", "dparam_norm_gap"):
        at = out[name][1]
        print(f"info {name} worst_leaf={at} norms(prog grad, ref grad, "
              f"diff, prog change, ref change, diff)="
              f"{tuple(float('%.4g' % x) for x in leaves[at])}",
              file=sys.stderr)
    return out


def reference_readings(cell, seed: int, n_steps: int, precision="float32",
                       host_batches=None, device=None, fault=None) -> dict:
    import jax
    import jax.numpy as jnp
    from benchmark.harness import weights as W
    fam, ref = cell.family(), cell.reference()
    cfg, job = cell.config, cell.traffic
    with jax.default_device(device or jax.devices()[0]):
        spec = fam.param_spec(cfg)
        p0 = W.as_float32(W.make(spec, seed))
        host = host_batches or fam.make_batches(cfg, job, seed)
        if fault == "half_batch":      # half left out, the mean over the rest
            host = [tuple(onp.concatenate([x[:len(x) // 2]] * 2) for x in hb)
                    for hb in host]
        batches = [tuple(jnp.asarray(x) for x in hb) for hb in host[:n_steps]]
        out = ref.train_steps(p0, cfg, batches, fam.adam_hp(job),
                              job["reference_block_rows"], precision,
                              storage={n: d for n, _, d, _ in spec})
        out["start"] = p0
        return out


def judge(numbers: dict, limits: dict, show_all: bool = False) -> tuple:
    """-> (correct, {name: [value, limit]}) over the numbers that the
    traffic file gives a limit; `show_all` also shows the others, with
    limit null and not judged (for setting limits)."""
    shown, ok = {}, True
    for name, (value, _at) in numbers.items():
        limit = limits.get(name)
        if limit is None and not show_all:
            continue
        shown[name] = [value, limit]
        if limit is not None and not (value <= limit):
            ok = False
    return ok, shown


def run(ctx) -> dict:
    import jax
    cell, seed = ctx.cell, ctx.seed
    cfg, job = cell.config, cell.traffic
    fam = cell.family()
    n_check = int(job["check_steps"])

    prog = fam.build_train(cfg, job, seed, ctx.devices)
    step = prog.step
    readings = program_readings(prog, n_check)
    pool = prog.batches
    traces0 = step.trace_count
    tracer = Tracer(ctx.trace, ctx.out_dir)
    ctx.before_window()

    # -- the window: one dispatch ahead of the step waited for -------------
    losses, ends = [], []
    steady_from = 0          # first step wholly after the profiler stopped
    i = n_check
    tracer.start()
    t0 = time.perf_counter()
    with span("bench.dispatch"):
        ahead = step.dispatch(*pool[i % len(pool)])
    while True:
        i += 1
        with span("bench.dispatch"):
            nxt = step.dispatch(*pool[i % len(pool)])
        with span("bench.wait"):
            jax.block_until_ready(ahead.loss)
        now = time.perf_counter()
        if now - t0 > ctx.seconds:
            break
        losses.append(ahead.loss)
        ends.append(now)
        ahead = nxt
        if tracer.on and len(ends) == TRACE_STEPS:
            tracer.stop()
            steady_from = len(ends) + 1
    jax.block_until_ready(nxt.loss)
    tracer.stop()
    compiles_in_window = ctx.after_window()
    if not ends:
        raise RuntimeError("no step finished inside the window")
    span_s = ends[-1] - t0
    values = [float(x) for x in losses]
    failed = sum(not math.isfinite(v) for v in values)
    no_compile = (step.trace_count == traces0 and compiles_in_window == 0)

    device = ctx.describe_device(step._exec)
    trace = tracer.read()
    host_batches = prog.host_batches
    tokens_per_step = prog.tokens_per_step
    del prog, step, pool, ahead, nxt, losses
    gc.collect()
    jax.clear_caches()

    ref = reference_readings(cell, seed, n_check, host_batches=host_batches,
                             device=ctx.devices[0])
    numbers = compare(readings, ref, ref["start"])
    ok, shown = judge(numbers, job["limits"], show_all=ctx.control >= 1)
    for name, (value, at) in numbers.items():
        print(f"info {name}={value:.6g} limit={job['limits'].get(name)}"
              + (f" worst_leaf={at}" if at else ""), file=sys.stderr)
    if ctx.control >= 2:
        for tag, kw in (("control_fp8", {"precision": "fp8"}),
                        ("fault_half_batch", {"fault": "half_batch"})):
            other = reference_readings(cell, seed, n_check, device=ctx.devices[0],
                                       host_batches=host_batches, **kw)
            for name, (value, at) in compare(other, ref,
                                             ref["start"]).items():
                print(f"{tag} {name}={value:.6g}", file=sys.stderr)
                shown[f"{tag}.{name}"] = [value, None]
    if not no_compile:
        print("check compiled_inside_window=1 limit=0", file=sys.stderr)
        shown["compiled_inside_window"] = [1, 0]

    tokens = len(ends) * tokens_per_step
    window = {"kind": "train_job", "n_steps": len(ends), "span_s": span_s,
              "tokens": tokens, "tokens_per_step": tokens_per_step,
              "chips": cell.chips, "traced_steps": min(len(ends), TRACE_STEPS),
              "loss_first": values[0], "loss_last": values[-1]}
    if 0 < steady_from < len(ends):
        # a traced run: rates for the whole-step metrics leave out the
        # traced steps and the profiler's stop, which stall the loop
        window["steady_steps"] = len(ends) - steady_from
        window["steady_span_s"] = ends[-1] - ends[steady_from - 1]
    else:
        window["steady_steps"], window["steady_span_s"] = len(ends), span_s
    return {
        "correct": bool(ok and no_compile and failed == 0),
        "attempted": len(ends), "failed": failed,
        "end_to_end": {
            "train_tokens_per_s_per_chip": tokens / span_s / cell.chips},
        "window": window, "trace": trace, "device": device, "checks": shown}
