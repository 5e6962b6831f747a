"""Sharded training step — the GSPMD replacement for the reference's
KVStore data-parallel pipeline (`src/kvstore/`, `gluon/trainer.py` push/pull).

One jitted function carries forward + backward + optimizer update for the
whole model, with parameters/optimizer state laid out by `ShardingRules` over
a named mesh (dp/tp/sp/...). XLA inserts the gradient psum over 'dp'
(all-reduce riding ICI), TP collectives around row/column-parallel matmuls,
and ring-attention ppermutes when sequence parallelism is active. Buffers are
donated, so weights update in place — the `static_alloc` end-state.
"""
from __future__ import annotations

import collections
import concurrent.futures as _cf
import functools
import threading
import time
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import logging

import numpy as onp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..base import MXNetError
from ..gluon.block import Block, functional_call
from ..gluon.parameter import Parameter
from ..optimizer import Optimizer
from ..ops.fused_optim import HpScalarCache
from ..ops import pallas as _pallas
from ..ops.pallas import fused_optimizer as _fused_opt
from .. import health as _health
from .. import profiler as _profiler
from .. import recovery as _recovery
from .. import telemetry as _tele
from .. import tracing as _trace
from .sharding import ShardingRules, default_tp_rules

__all__ = ["ShardedTrainStep", "StepHandle", "make_sharded_train_step"]

_log = logging.getLogger(__name__)


def _spec_axes(spec):
    """Flatten a PartitionSpec's entries to the set of mesh-axis names."""
    return {a for e in spec
            for a in ((e,) if isinstance(e, str) else (e or ()))}


def _put_global(x, sharding):
    """Place a host value onto a (possibly multi-process) sharding.

    Single-process meshes use plain device_put. When the mesh spans
    processes (SURVEY §5.8: one controller per host, SPMD over the global
    mesh), every process holds the identical GLOBAL value and contributes
    its addressable shards — the multi-controller idiom that replaces the
    reference's worker-local batch + ps-lite aggregation."""
    if getattr(sharding, "is_fully_addressable", True):
        return jax.device_put(x, sharding)
    arr = onp.asarray(x)
    return jax.make_array_from_callback(arr.shape, sharding,
                                        lambda idx: arr[idx])


class ShardedTrainStep:
    """Compiled data/tensor/sequence-parallel training step for a Gluon block.

    loss_fn(out, *batch_rest) -> scalar jax value, where `out` is the
    block's (jax-valued) output tree.
    """

    def __init__(self, block: Block, optimizer: Optimizer,
                 loss_fn: Callable, mesh: Mesh,
                 rules: Optional[ShardingRules] = None,
                 batch_specs: Optional[Tuple] = None,
                 num_model_args: Optional[int] = None,
                 grad_accum_dtype=jnp.float32, grad_accum: int = 1,
                 zero: bool = False, fsdp: bool = False,
                 donate: bool = True, grad_compress: Optional[str] = None):
        # ZeRO stage 1: shard optimizer state over the 'dp' axis instead
        # of replicating it (params stay replicated; XLA inserts the
        # reduce-scatter/all-gather around the sharded update). Cuts
        # optimizer-state HBM by the dp degree — for Adam on bf16 weights
        # that's 4x the weight bytes saved per extra dp shard.
        self.zero = zero
        # donate=True (default) updates weights in place — the
        # static_alloc end-state, halving peak param+state HBM.  CPU
        # caveat: the CPU runtime blocks a dispatch whose DONATED input is
        # still the in-flight output of the previous step, serializing
        # back-to-back dispatch()es; donate=False restores deep host-side
        # pipelining there (at 2x transient param footprint) — the CPU
        # overlap smoke uses it (docs/perf.md).
        self.donate = donate
        # FSDP (ZeRO stage 3): ALSO shard the parameters themselves over
        # 'dp' (first free divisible dim); XLA all-gathers each weight
        # just-in-time at its use and keeps gradients reduce-scattered.
        # Implies zero (sharded params get matching sharded state).
        self.fsdp = fsdp
        if fsdp:
            self.zero = True
        # accumulate gradients over this many microbatches per step (the
        # global batch splits on its leading dim; must divide it)
        if grad_accum < 1:
            raise MXNetError(f"grad_accum must be >= 1, got {grad_accum}")
        self.grad_accum = int(grad_accum)
        self.grad_accum_dtype = grad_accum_dtype
        # int8 gradient compression on the dp-axis reduction
        # (parallel/compress.py; MXNet survey layer-8 gradient-
        # compression parity).  Resolved ONCE at construction like the
        # probes — the quantize-dequantize round is traced into the
        # step, so flipping MXTPU_GRAD_COMPRESS mid-run never retraces.
        # Off by default: it deliberately trades bit-exactness with f32
        # training for 4x less gradient wire traffic.
        from . import compress as _compress
        self._grad_compress = _compress.resolve_grad_compress(
            grad_compress)
        self.block = block
        # how many leading batch args feed block.forward; the rest (labels
        # etc.) only reach loss_fn. None = all.
        self.num_model_args = num_model_args
        self.optimizer = optimizer
        self.loss_fn = loss_fn
        self.mesh = mesh
        self.rules = rules or default_tp_rules()
        self.batch_specs = batch_specs
        # the caller's ORIGINAL specs: reshard re-targets from these, so
        # a shrink that drops an axis doesn't ratchet the spec toward
        # replicated when the mesh later grows the axis back
        self._orig_batch_specs = batch_specs
        self._step_fn = None
        self._n_batch_args = None
        self._build_lock = threading.Lock()
        # async pipeline state: AOT-compiled executable (warmup()), trace
        # counter + last-seen avals (retrace guard), device-resident
        # hyperparameter cache, dispatch latencies, in-flight losses
        self._exec = None
        self._trace_count = 0
        self._trace_avals = None
        self._hp_cache = HpScalarCache()
        self._t_dev = None
        self._t_mirror = -1
        self._dispatch_s = collections.deque(maxlen=1024)
        self._inflight = collections.deque(maxlen=256)
        self.compile_seconds = None
        # performance attribution (mx.tracing): cost features are
        # recorded under this key at every compile site (AOT warmup,
        # export load) and combined with measured wall time at retire
        # into the mfu_estimate/step_flops/hbm_bytes_est gauges
        self._cost_key = f"train_step@{id(self):x}"
        self._last_retire_t: Optional[float] = None
        # numerics probes (MXTPU_HEALTH / health.enable): captured ONCE at
        # construction so the probe branch is a fixed part of the traced
        # program — with health off it is traced out entirely (zero extra
        # device computations, trace_count unchanged); enabling health
        # after construction requires a new step object
        self._health_probes = _health.probes_enabled()
        # tier-1 remediation (MXTPU_RECOVERY / recovery.enable): guard the
        # optimizer update with the non-finite probe INSIDE the jitted
        # step — a NaN/Inf gradient (or loss) applies the identity update
        # instead of poisoning the weights, and the host-side
        # RecoveryPolicy accounts the skip from the anomaly the probes
        # raise.  Captured once at construction like the probes: the
        # guard is a fixed part of the traced program (zero retraces,
        # and with recovery off it is traced out entirely).
        self._skip_nonfinite = (self._health_probes
                                and _recovery.skip_enabled())
        # stall-suppression guard entered at TRACE time (_note_trace) and
        # released when the triggering call returns: any path that
        # compiles — cold start, AOT fallback, mid-run aval-drift
        # retrace — blocks for up to minutes, and the hang watchdog must
        # not declare (or raise on) that expected silence
        self._trace_guard = None
        # stall dumps / crash bundles report this step's in-flight ids
        _health.register_inflight_source(self)

        params = {n: p for n, p in block.collect_params().items()
                  if p._data is not None}
        if not params:
            raise MXNetError("block has no initialized parameters; call "
                             "initialize() (and one forward for deferred "
                             "shapes) first")
        self.param_names = sorted(params)
        self.params = params
        self.diff_names = [n for n in self.param_names
                           if params[n].grad_req != "null"]

        # place parameters + optimizer state on the mesh. An explicit
        # Parameter(sharding=...) annotation wins over the rules table; a
        # large parameter matching no rule logs a warning instead of
        # silently replicating (round-1 verdict: silent fall-through).
        self.param_shardings = {
            n: self._resolve_sharding(n, params[n]) for n in self.param_names}
        # donation safety: device_put may ALIAS a same-device source
        # buffer (the CPU replicated-placement path does) — donating an
        # alias at step 1 would delete the caller's own param array out
        # from under every other holder (an InferenceEngine's extracted
        # weights, user references).  The step must OWN what it donates,
        # so the initial placement goes through an explicit copy.
        def _owned(x):
            return jnp.copy(x) if self.donate and isinstance(x, jax.Array) \
                else x
        self.pvals = {n: _put_global(_owned(params[n]._data._data),
                                     self.param_shardings[n])
                      for n in self.param_names}
        # optimizer state: each leaf shards like its parameter, ZeRO adds
        # a 'dp' axis where a dim allows it, and leaves with NO free
        # divisible dim (bias/scale vectors whose only dim is already
        # tp-sharded) are stored as a flattened dp-sharded BUCKET instead
        # of silently replicating (see _state_placement)
        self._state_buckets: Dict[str, Dict[int, Tuple[Tuple[int, ...],
                                                       int]]] = {}
        self.opt_state = {
            n: self._place_state_tree(
                n, optimizer.create_state_jax(_master_dtype(self.pvals[n])))
            for n in self.diff_names}
        self._t = 0
        # True when batch specs are derived (and re-derived on reshard)
        # from the mesh axes rather than caller-supplied
        self._auto_batch_specs = batch_specs is None
        # fused-optimizer route (captured ONCE, like the probes: the
        # choice is baked into the traced program, so flipping
        # MXTPU_PALLAS mid-run can never retrace a live step)
        self._fused_opt_kernel = self._resolve_fused_kernel()

    def _resolve_fused_kernel(self) -> bool:
        """Use the Pallas fused-optimizer kernels inside the jitted
        step?  Requires kernel mode + a kernel-eligible optimizer, and
        nothing sharded: the chunk pack concatenates leaves, which on a
        sharded layout would make GSPMD all-gather the tree every step
        (TODO(tpu): a segment-aware sharded pack, ROADMAP §5)."""
        with _pallas.partitioned_by_gspmd(self.mesh.size):
            if not _fused_opt.kernel_route(self.optimizer):
                return False
        if self.mesh.size == 1:
            return True
        if self.zero or self.fsdp:
            return False
        from jax.sharding import PartitionSpec as _P
        return all(s.spec == _P()
                   for s in self.param_shardings.values())

    # parameters below this size stay replicated under fsdp (per-use
    # all-gathers of tiny biases cost more than they save)
    FSDP_MIN_SIZE = 8192

    def _maybe_fsdp(self, sharding: NamedSharding, param) -> NamedSharding:
        if not self.fsdp or \
                int(onp.prod(param.shape)) < self.FSDP_MIN_SIZE:
            return sharding
        ns = _with_dp_axis(self.mesh, sharding.spec, param.shape)
        return ns if ns is not None else sharding

    def _state_placement(self, name, state_leaf):
        """``(sharding, bucket)`` for one optimizer-state leaf: like the
        parameter — plus, under ZeRO, the first unsharded divisible dim
        spread over 'dp' (the reduce-scatter/all-gather pattern XLA then
        emits is exactly ZeRO stage 1).

        When no dim can take the 'dp' axis (the 1-D gap the MULTICHIP
        logs showed: bias/scale vectors whose only dim is already
        tp-sharded, or dims dp doesn't divide), the leaf is stored as a
        **flattened concatenation bucket**: raveled, zero-padded to a
        multiple of dp, and sharded ``P('dp')``.  ``bucket`` is then
        ``(logical_shape, padded_size)``; the jitted step unpacks the
        logical view before the optimizer rule and repacks after, and
        checkpoints always store the logical (unpadded) value so the
        format stays topology-agnostic.  Scalars stay replicated (nothing
        to shard)."""
        param_sharding = self.param_shardings[name]
        param = self.params[name]
        base = _like_sharding(param_sharding, state_leaf, param)
        if not self.zero or "dp" not in self.mesh.axis_names:
            return base, None
        shape = tuple(getattr(state_leaf, "shape", ()))
        ns = _with_dp_axis(self.mesh, base.spec, shape)
        if ns is not None:
            return ns, None
        dp = dict(self.mesh.shape).get("dp", 1)
        if dp > 1 and shape and "dp" not in _spec_axes(base.spec):
            size = int(onp.prod(shape))
            padded = -(-size // dp) * dp
            return (NamedSharding(self.mesh, P("dp")),
                    (tuple(int(d) for d in shape), padded))
        return base, None

    def _place_state_tree(self, name, tree):
        """Device-place one parameter's optimizer-state tree (logical
        leaves), recording bucket metadata and packing bucketed leaves."""
        leaves, treedef = jax.tree_util.tree_flatten(tree)
        buckets: Dict[int, Tuple[Tuple[int, ...], int]] = {}
        placed = []
        for i, leaf in enumerate(leaves):
            sharding, bucket = self._state_placement(name, leaf)
            if bucket is not None:
                buckets[i] = bucket
                leaf = _pack_bucket(leaf, bucket)
            placed.append(_put_global(leaf, sharding))
        self._state_buckets[name] = buckets
        return jax.tree_util.tree_unflatten(treedef, placed)

    def _unpack_state_tree(self, name, tree):
        """Bucketed (packed) leaves -> logical shapes.  jit-safe: slices
        and reshapes trace into the step program."""
        buckets = self._state_buckets.get(name)
        if not buckets:
            return tree
        leaves, treedef = jax.tree_util.tree_flatten(tree)
        for i, (shape, _padded) in buckets.items():
            size = int(onp.prod(shape)) if shape else 1
            leaves[i] = leaves[i][:size].reshape(shape)
        return jax.tree_util.tree_unflatten(treedef, leaves)

    def _pack_state_tree(self, name, tree, constrain=False):
        """Logical leaves -> packed dp-sharded buckets (inverse of
        `_unpack_state_tree`).  `constrain=True` adds a sharding
        constraint inside jit so GSPMD keeps the bucket on 'dp'."""
        buckets = self._state_buckets.get(name)
        if not buckets:
            return tree
        leaves, treedef = jax.tree_util.tree_flatten(tree)
        for i, bucket in buckets.items():
            leaf = _pack_bucket(leaves[i], bucket)
            if constrain:
                leaf = jax.lax.with_sharding_constraint(
                    leaf, NamedSharding(self.mesh, P("dp")))
            leaves[i] = leaf
        return jax.tree_util.tree_unflatten(treedef, leaves)

    def _logical_state_leaves(self, name):
        """The flat leaf list of `opt_state[name]` with bucketed leaves
        unpacked to their logical shapes — what checkpoints store."""
        return jax.tree_util.tree_leaves(
            self._unpack_state_tree(name, self.opt_state[name]))

    def _resolve_sharding(self, name: str, param) -> NamedSharding:
        mesh = self.mesh
        ann = getattr(param, "sharding", None)
        if ann is not None:
            # explicit annotations are validated strictly: a typo must not
            # silently replicate a deliberately-sharded parameter
            if isinstance(ann, str):
                ann = (ann,)
            spec = ann if isinstance(ann, P) else P(*ann)
            if len(spec) > len(param.shape):
                raise MXNetError(
                    f"parameter {name}: sharding annotation {tuple(spec)} "
                    f"has rank {len(spec)} > parameter rank "
                    f"{len(param.shape)} (shape {tuple(param.shape)})")
            names = set(mesh.axis_names)
            from .mesh import AXES as _KNOWN_AXES
            from .sharding import retarget_spec
            for a in spec:
                for ax in ((a,) if isinstance(a, str) else tuple(a or ())):
                    # a standard parallelism axis this mesh runs at size 1
                    # (make_mesh drops those) degrades to replicated via
                    # retarget_spec, so the same model code works when the
                    # mesh shrinks; anything else is a typo
                    if ax not in names and ax not in _KNOWN_AXES:
                        raise MXNetError(
                            f"parameter {name}: sharding annotation names "
                            f"mesh axis {ax!r} but this mesh has axes "
                            f"{sorted(names)}")
            spec = retarget_spec(spec, mesh)
            return self._maybe_fsdp(NamedSharding(mesh, spec), param)
        sharding = self.rules.sharding_for(mesh, name, param.shape)
        # 'dp' replicates params by design; 'sp' shards activations, never
        # params — only true model axes (tp/ep/...) make replication a smell.
        # Checked BEFORE the fsdp augment: fsdp's dp axis doesn't cure
        # replication across tp/ep
        model_axes = [a for a in mesh.axis_names if a not in ("dp", "sp")
                      and mesh.shape[a] > 1]
        if sharding.spec == P() and model_axes and \
                int(onp.prod(param.shape)) >= 1_000_000:
            _log.warning(
                "parameter %s %s matched no sharding rule and will be "
                "REPLICATED across the %s mesh axes; annotate it with "
                "Parameter(sharding=...) or extend ShardingRules",
                name, tuple(param.shape), model_axes)
        return self._maybe_fsdp(sharding, param)

    # ------------------------------------------------------------------
    def _build(self, batch_vals, rng_key):
        mesh = self.mesh
        if self.batch_specs is None:
            # default: shard leading batch dim over 'dp' (+'sp' on axis 1 if
            # the mesh has it and the arg is rank>=2)
            axes = set(mesh.axis_names)
            specs = []
            for b in batch_vals:
                spec = [None] * b.ndim
                if b.ndim >= 1 and "dp" in axes:
                    spec[0] = "dp"
                if b.ndim >= 2 and "sp" in axes:
                    spec[1] = "sp"
                specs.append(P(*spec))
            self.batch_specs = tuple(specs)
        batch_shardings = tuple(NamedSharding(mesh, s)
                                for s in self.batch_specs)
        self._batch_shardings = batch_shardings

        block, loss_fn, optimizer = self.block, self.loss_fn, self.optimizer
        diff_names = self.diff_names

        n_model = self.num_model_args

        k = self.grad_accum
        accum_dtype = self.grad_accum_dtype

        outer = self

        def step(*args):
            # runs once per TRACE of the jitted step — the hook counts
            # compilations and warns (with the drifted avals) on a silent
            # retrace, the dtype-drift failure mode noted below
            outer._note_trace(args)
            # GSPMD partitions this program over the mesh: over more than
            # one device Mosaic kernels cannot be lowered outside a
            # shard_map, so their dispatch is decided here, up front
            with _pallas.partitioned_by_gspmd(mesh.size):
                return body(*args)

        def body(pvals, opt_state, hp, key, *batch):
            def compute_loss(diff_vals, mkey, *mb):
                pv = dict(pvals)
                pv.update(diff_vals)
                model_args = mb if n_model is None else mb[:n_model]
                out, aux = functional_call(block, pv, *model_args,
                                           training=True, rng_key=mkey)
                with jax.named_scope("mx.loss"):
                    loss = loss_fn(out, *mb)
                # a loss_fn written in mx.np ops returns a wrapped scalar;
                # unwrap so value_and_grad sees a jax value
                loss = getattr(loss, "_data", loss)
                return loss, aux

            diff_vals = {n: pvals[n] for n in diff_names}
            if k == 1:
                (loss, aux), grads = jax.value_and_grad(
                    compute_loss, has_aux=True)(diff_vals, key, *batch)
            else:
                # gradient accumulation: scan over k microbatches,
                # accumulating mean-of-means grads at accum_dtype — the
                # large-effective-batch path (reference Trainer's
                # update-skipping idiom, compiled into one program)
                micro = []
                for bi, b in enumerate(batch):
                    if b.ndim < 1 or b.shape[0] % k:
                        raise MXNetError(
                            f"grad_accum={k} must divide every batch "
                            f"arg's leading dim; got shape "
                            f"{tuple(b.shape)}")
                    mb = b.reshape((k, b.shape[0] // k)
                                   + tuple(b.shape[1:]))
                    # keep each microbatch dp-sharded on ITS batch dim —
                    # without the constraint GSPMD can move 'dp' onto the
                    # scan axis and every iteration pays a reshard
                    spec = (self.batch_specs[bi]
                            if self.batch_specs else None)
                    if spec is not None and "dp" in _spec_axes(spec):
                        mb = jax.lax.with_sharding_constraint(
                            mb, NamedSharding(mesh, P(None, *spec)))
                    micro.append(mb)
                micro = tuple(micro)
                keys = jax.random.split(key, k)

                def body(carry, xs):
                    acc, lsum = carry
                    mkey, mb = xs[0], xs[1:]
                    (loss, aux), grads = jax.value_and_grad(
                        compute_loss, has_aux=True)(diff_vals, mkey, *mb)
                    acc = jax.tree_util.tree_map(
                        lambda a, g: a + g.astype(accum_dtype), acc, grads)
                    return (acc, lsum + loss), aux

                init = (jax.tree_util.tree_map(
                    lambda v: jnp.zeros(v.shape, accum_dtype), diff_vals),
                    jnp.zeros((), accum_dtype))
                (acc, lsum), auxes = jax.lax.scan(
                    body, init, (keys,) + micro)
                grads = jax.tree_util.tree_map(
                    lambda a, v: (a / k).astype(v.dtype), acc, diff_vals)
                loss = (lsum / k).astype(jnp.float32)
                # running-stat writebacks: keep the final microbatch's
                aux = jax.tree_util.tree_map(lambda x: x[-1], auxes)
            probes = None
            if outer._health_probes:
                # numerics probes (docs/observability.md): cheap fused
                # reductions XLA folds into the step program — grad global
                # L2 norm + non-finite element count over the whole grad
                # tree.  Returned as async device scalars alongside the
                # loss, so they ride dispatch() with no extra device sync.
                leaves = jax.tree_util.tree_leaves(grads)
                gnorm = jnp.sqrt(sum(
                    jnp.sum(jnp.square(g.astype(jnp.float32)))
                    for g in leaves))
                # count in f32, not i32: an all-NaN gradient tree on a
                # >=2^31-element model would WRAP an int32 sum negative
                # and poison the host-side counter; f32 loses exactness
                # past 2^24 but stays positive, which is what the
                # anomaly rule needs (int64 needs x64 mode)
                nonfinite = sum(
                    jnp.sum((~jnp.isfinite(g)).astype(jnp.float32))
                    for g in leaves)
                probes = {"grad_norm": gnorm, "nonfinite": nonfinite}
            if outer._grad_compress == "int8":
                # int8 grad compression (parallel/compress.py): per-
                # bucket symmetric scale + stochastic rounding, f32
                # master accumulate.  AFTER the probes (they must see
                # the raw gradients) and BEFORE the skip guard reads
                # them for the update.  The rounding key folds off the
                # step key, so replicas stay deterministic and no two
                # steps share noise.
                from .compress import compress_tree
                grads = compress_tree(
                    grads, jax.random.fold_in(key, 0x67c8))
            skip = None
            if outer._skip_nonfinite:
                # tier-1 recovery: a non-finite gradient tree (or loss)
                # turns the whole update into the identity — weights,
                # optimizer state, and running stats all keep their
                # pre-step values.  jnp.where on a traced scalar, so the
                # skip costs one select per leaf and never a retrace.
                skip = jnp.logical_or(
                    probes["nonfinite"] > 0,
                    ~jnp.isfinite(loss.astype(jnp.float32)))
            # fused multi-tensor optimizer update (ops/pallas/
            # fused_optimizer, MXTPU_PALLAS): same-dtype leaves pack
            # into contiguous chunks with ONE kernel launch each (skip
            # guard applied in-register) when the kernel path is
            # active; otherwise the per-leaf reference applies
            # `optimizer._rule` + the identity-on-skip select with the
            # exact semantics the former inline ladder had (dtype
            # cast-backs included — donation still never retraces)
            new_p = dict(pvals)
            # ZeRO 1-D buckets: the rule sees logical shapes; the packed
            # dp-sharded representation is storage-only
            states = {n: outer._unpack_state_tree(n, opt_state[n])
                      for n in diff_names}
            with jax.named_scope("mx.optimizer"):
                upd_p, new_s = _fused_opt.apply_updates(
                    optimizer, {n: pvals[n] for n in diff_names}, grads,
                    states, hp, skip,
                    use_kernel=outer._fused_opt_kernel)
                new_s = {n: outer._pack_state_tree(n, new_s[n],
                                                   constrain=True)
                         for n in new_s}
            new_p.update(upd_p)
            if skip is not None:
                aux = {k: jnp.where(skip, pvals[k], v) if k in pvals else v
                       for k, v in aux.items()}
            new_p.update(aux)  # running-stat writebacks
            if probes is not None:
                return new_p, new_s, loss, probes
            return new_p, new_s, loss

        pspec = {n: self.param_shardings[n] for n in self.param_names}
        # state shardings come straight off the placed arrays — the
        # single source of truth `_place_state_tree` established (bucket
        # leaves carry their packed P('dp') sharding)
        sspec = {
            n: jax.tree_util.tree_map(lambda x: x.sharding,
                                      self.opt_state[n])
            for n in self.diff_names}
        repl = NamedSharding(mesh, P())
        out_shardings = (pspec, sspec, repl)
        if self._health_probes:
            out_shardings += ({"grad_norm": repl, "nonfinite": repl},)
        self._step_fn = jax.jit(
            step,
            in_shardings=(pspec, sspec, None, None) + batch_shardings,
            out_shardings=out_shardings,
            donate_argnums=(0, 1) if self.donate else ())

    def _check_global_batch(self, batch_vals) -> None:
        """First-step guard: on a mesh spanning processes, assert every
        process passed the same global batch (cheap checksum allgather)."""
        if all(getattr(s, "is_fully_addressable", True)
               for s in self._batch_shardings):
            return
        from jax.experimental import multihost_utils
        sums = onp.asarray(
            [float(jnp.sum(jnp.abs(jnp.asarray(b, jnp.float32))))
             for b in batch_vals], onp.float32)
        gathered = multihost_utils.process_allgather(sums)
        if not onp.allclose(gathered, gathered[0], rtol=1e-5):
            raise MXNetError(
                "ShardedTrainStep on a multi-process mesh requires every "
                "process to pass the IDENTICAL global batch (each host "
                "contributes its addressable shards). Got differing batch "
                f"checksums across processes: {gathered.tolist()}. If each "
                "worker loads its own shard, concatenate/allgather to the "
                "global batch first (or give every worker the same data "
                "stream + global indices).")

    # -- async step pipeline -------------------------------------------
    # The reference hides per-step host latency behind its dependency
    # engine (Engine::PushAsync).  Here the jitted step is already async
    # on the device side; the pieces below remove the HOST serialization
    # around it: batch placement moves to DevicePrefetcher threads
    # (place_batch), hyperparameter scalars stay device-resident (_hp),
    # dispatch() returns without fetching the loss, and warmup() AOT-
    # compiles so step 1 (and, with the persistent compile cache, a
    # restarted process) never trace-compiles inline.

    def _note_trace(self, args) -> None:
        """Runs at trace time (the step body is python-executed once per
        jit compilation).  Counts traces; on any trace after the first,
        warns with the argument avals that drifted — a silent retrace
        re-pays compile AND breaks donation (see the dtype note in the
        optimizer-update loop)."""
        # a trace is always followed by an XLA compile before the
        # triggering call returns: suppress stall detection until then
        # (released in dispatch/warmup's finally)
        if self._trace_guard is None:
            self._trace_guard = _health.suppress_stalls("trace_compile")
            self._trace_guard.__enter__()
        leaves = jax.tree_util.tree_flatten_with_path(args)[0]
        avals = {
            jax.tree_util.keystr(path): (
                tuple(getattr(leaf, "shape", ())),
                str(getattr(leaf, "dtype", type(leaf).__name__)))
            for path, leaf in leaves}
        prev, self._trace_avals = self._trace_avals, avals
        self._trace_count += 1
        if _tele.enabled():
            _tele.counter(
                "trace_count",
                "Step-function traces/compilations (1 = healthy "
                "steady state)").inc()
            _tele.event("compile", step=self._t,
                        trace_count=self._trace_count)
        if self._trace_count <= 1 or prev is None:
            return
        drift = [f"{k}: {prev[k][0]}/{prev[k][1]} -> {v[0]}/{v[1]}"
                 for k, v in avals.items()
                 if k in prev and prev[k] != v]
        drift += [f"{k}: (new input)" for k in avals if k not in prev]
        drift += [f"{k}: (dropped)" for k in prev if k not in avals]
        if _tele.enabled():
            _tele.event("retrace", step=self._t,
                        trace_count=self._trace_count,
                        drift=drift[:8])
        _log.warning(
            "ShardedTrainStep RETRACE #%d: the step function compiled "
            "again (every retrace re-pays XLA compile and allocates a "
            "second executable). Drifted avals (%d): %s",
            self._trace_count, len(drift),
            "; ".join(drift[:8]) + ("; ..." if len(drift) > 8 else "")
            if drift else "<none — new static closure?>")

    def _release_trace_guard(self) -> None:
        """Exit the stall-suppression window a trace opened (no-op when
        no trace ran)."""
        guard, self._trace_guard = self._trace_guard, None
        if guard is not None:
            guard.__exit__(None, None, None)

    @property
    def trace_count(self) -> int:
        """How many times the step function has been traced/compiled.
        Stays 1 for a healthy steady-state run (assert on it in tests)."""
        return self._trace_count

    # -- performance attribution (mx.tracing) ---------------------------
    def _record_cost(self, compiled, source: str) -> None:
        """Capture `compiled`'s XLA cost/memory analysis into the
        process cost registry (once per compile; never on the hot
        path)."""
        _trace.record_executable(
            self._cost_key, compiled, kind="train_step", source=source,
            axes=self.topology()["axes"])

    def cost_features(self) -> Optional[dict]:
        """The step executable's XLA cost-feature vector (flops, bytes
        accessed, argument/output/temp bytes, hbm_bytes_est), or None
        before any AOT compile/export load recorded one (the live-jit
        path exposes no compiled object to analyze — run `warmup()`)."""
        return _trace.account().features(self._cost_key)

    def mfu_estimate(self, measured_step_s: float) -> Optional[dict]:
        """MFU of one step taking `measured_step_s` wall seconds, from
        the recorded cost features (projected peak on non-TPU backends;
        docs/observability.md)."""
        return _trace.account().mfu(self._cost_key, measured_step_s)

    def _prepare_batch(self, batch):
        """Unwrap mx ndarrays, build the step on first use, and place every
        batch arg on its target sharding — skipping the copy for args that
        already sit there (a DevicePrefetcher hand-off)."""
        batch_vals = [b._data if hasattr(b, "_data")
                      else b if isinstance(b, jax.Array)
                      else onp.asarray(b)
                      for b in batch]
        if self._step_fn is None and self._exec is None:
            with self._build_lock:
                if self._step_fn is None and self._exec is None:
                    self._build(batch_vals, None)
                    self._check_global_batch(batch_vals)
        # remembered for batch-less `export()` calls (avals only)
        self._last_batch_avals = [
            (tuple(b.shape), onp.dtype(b.dtype)) for b in batch_vals]
        return [b if isinstance(b, jax.Array) and b.sharding == s
                else _put_global(b, s)
                for b, s in zip(batch_vals, self._batch_shardings)]

    def place_batch(self, *batch):
        """Device-place one batch onto the step's batch shardings (built
        from this batch if needed).  This is the `place=` hook for
        `DevicePrefetcher`: calling it on the prefetch thread moves the
        H2D copy off the training loop; `dispatch`/`__call__` then detect
        the placement and skip their own copy."""
        return tuple(self._prepare_batch(batch))

    def _hp(self):
        """Device-resident hyperparameter scalars (shared `HpScalarCache`:
        lr/wd/rescale/clip uploads happen only when the host-side values
        actually change, instead of five H2D transfers per step); the
        step counter `t` advances by a device-side add, so steady-state
        dispatch enqueues zero transfers.  A checkpoint load (or external
        _t rewrite) makes the mirror mismatch and forces a host rebuild."""
        hp = self._hp_cache.get(self.optimizer)
        if self._t_dev is not None and self._t_mirror == self._t:
            pass  # same step (repeated warmup) — reuse
        elif self._t_dev is not None and self._t_mirror + 1 == self._t \
                and self._t % self._T_HOST_REFRESH:
            # device-side increment; periodically re-seeded from the host
            # counter because f32 `x + 1.0` saturates at 2**24 — a pure
            # device chain would silently freeze t on very long runs
            self._t_dev = self._t_dev + 1.0
        else:
            self._t_dev = jnp.asarray(self._t, jnp.float32)
        self._t_mirror = self._t
        hp["t"] = self._t_dev
        return hp

    # re-upload `t` from the host every this many steps (guards the f32
    # device-add saturation at 2**24; one tiny H2D per window otherwise)
    _T_HOST_REFRESH = 4096

    def warmup(self, *batch, rng_key=None, artifact=None):
        """AOT warm start: trace + compile the step for this batch's avals
        WITHOUT executing it (`.lower().compile()`), so the first real
        step runs at steady-state speed.  With the persistent compile
        cache on (`runtime.enable_compile_cache`) the XLA binary is served
        from it on a restart — the multi-minute BERT compile
        happens once per cluster, not once per process.  Returns the
        compile wall-time in seconds (also kept as `compile_seconds`).

        ``artifact=<path>`` skips tracing entirely: the step loads the
        export artifact (`load_export`), so ``trace_count`` stays 0.
        With ``MXTPU_EXPORT=1`` and an export dir configured
        (docs/export.md) the lookup is automatic — a matching artifact
        is loaded, a missing one is captured+saved after the live
        compile, so replica N>1 of a fleet never traces.

        Does not consume an RNG draw: the key is only used for its aval."""
        if artifact is not None:
            return self.load_export(artifact, *batch)
        auto_path = self._auto_artifact_path(batch)
        if auto_path is not None:
            import os as _os
            if _os.path.isfile(_os.path.join(auto_path, "manifest.json")):
                try:
                    return self.load_export(auto_path, *batch)
                except MXNetError as e:
                    _log.warning(
                        "export artifact %s unusable (%s); tracing live",
                        auto_path, str(e).splitlines()[0])
        secs = self._warmup_live(batch, rng_key)
        if auto_path is not None:
            try:
                self.export(auto_path, *batch)
            except Exception:
                _log.exception("auto-capture to %s failed (training "
                               "continues uncaptured)", auto_path)
        return secs

    def _warmup_live(self, batch, rng_key=None):
        batch_vals = self._prepare_batch(batch)
        if self._step_fn is None:
            # artifact-loaded step being re-warmed live (new batch
            # shape, or the export flag dropped): _prepare_batch skipped
            # its build because _exec was set — build the jit now
            with self._build_lock:
                if self._step_fn is None:
                    self._build([onp.asarray(b) for b in batch_vals],
                                None)
        hp = self._hp()
        key = rng_key if rng_key is not None else jax.random.PRNGKey(0)
        args = (self.pvals, self.opt_state, hp, key) + tuple(batch_vals)
        avals = jax.tree_util.tree_map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), args)
        if _tele.enabled():
            _tele.event("compile_start", step=self._t, kind="aot_warmup")
        t0 = time.perf_counter()
        # a multi-minute XLA compile is expected silence, not a hang —
        # keep the stall watchdog quiet for its duration (explicitly:
        # `.compile()` still runs even when `.lower()` skipped the trace
        # that would have armed the _note_trace guard)
        c_span = _trace.get_tracer("train").span(
            "train.compile", step=self._t, kind="aot_warmup") \
            if _trace.enabled() else None
        try:
            with _health.suppress_stalls("aot_compile"):
                self._exec = self._step_fn.lower(*avals).compile()
        finally:
            self._release_trace_guard()
            if c_span is not None:
                c_span.__exit__(None, None, None)
        self.compile_seconds = time.perf_counter() - t0
        self._record_cost(self._exec, source="aot_warmup")
        if _tele.enabled():
            _tele.event("compile_end", step=self._t, kind="aot_warmup",
                        seconds=round(self.compile_seconds, 4))
        return self.compile_seconds

    def dispatch(self, *batch, rng_key=None) -> "StepHandle":
        """Non-blocking step: enqueue forward+backward+update and return a
        `StepHandle` whose `.loss` is the still-async device scalar —
        `float()`/`.result()` blocks, `AsyncMetricBuffer` defers the fetch
        so multiple steps stay in flight.  The step boundary is marked
        with `jax.profiler.StepTraceAnnotation`, so Perfetto/TensorBoard
        segment the XPlane trace per step and show prefetch overlap."""
        from .. import random as _rng
        _health.beat("train_step.dispatch")
        t0 = time.perf_counter()
        # span pair (mx.tracing): "train.dispatch" covers the host-side
        # enqueue, "train.device" the dispatch -> retire window (finished
        # in steps_in_flight).  Both tagged with the journal step id.
        # manual span (not the thread-local stack): an exception mid-
        # dispatch must not strand an open span under later dispatches
        d_span = _trace.get_tracer("train").start_span(
            "train.dispatch", track="train host", step=self._t + 1) \
            if _trace.enabled() else None
        batch_vals = self._prepare_batch(batch)
        self._t += 1
        hp = self._hp()
        key = rng_key if rng_key is not None else _rng.next_key()
        # any (re)trace inside these calls enters the stall-suppression
        # guard via _note_trace; the finally releases it once the
        # triggering call (trace + XLA compile) has returned
        try:
            with _profiler.step_annotation("mxtpu.train_step",
                                           step_num=self._t):
                if self._exec is not None:
                    try:
                        out = self._exec(self.pvals, self.opt_state, hp,
                                         key, *batch_vals)
                    except TypeError as e:
                        # aval drift vs the AOT executable: fall back to
                        # the jit path (which retraces — _note_trace warns
                        # with the diff). Input buffers are intact: the
                        # AOT call validates avals before launching, so
                        # donation has not consumed them yet.
                        _log.warning(
                            "AOT-compiled step rejected inputs (%s); "
                            "falling back to jit",
                            str(e).splitlines()[0])
                        self._exec = None
                        if self._step_fn is None:
                            # artifact-loaded step (load_export): there
                            # is no jit to fall back to yet — build one
                            # (a LIVE trace; loud, since the zero-
                            # retrace contract just broke on aval drift)
                            self._build(batch_vals, None)
                        out = self._step_fn(self.pvals, self.opt_state,
                                            hp, key, *batch_vals)
                else:
                    out = self._step_fn(self.pvals, self.opt_state, hp,
                                        key, *batch_vals)
        finally:
            self._release_trace_guard()
        if self._health_probes:
            self.pvals, self.opt_state, loss, probes = out
        else:
            self.pvals, self.opt_state, loss = out
            probes = None
        # rebind block Parameters to the fresh (non-donated) buffers so
        # eager reads (p.data()) stay valid — pointer update only
        self.sync_params_to_block()
        dt = time.perf_counter() - t0
        self._dispatch_s.append(dt)
        x_span = None
        if d_span is not None:
            x_span = _trace.get_tracer("train").start_span(
                "train.device", parent=d_span.context(),
                track="train device", step=self._t)
            d_span.finish(dispatch_ms=round(dt * 1e3, 3))
        self._inflight.append((self._t, loss, probes,
                               time.perf_counter(), x_span))
        if _tele.enabled():
            _tele.histogram(
                "step_dispatch_ms",
                "Host time per dispatch() call (not device step time; "
                "overlap works when this sits far below step time)"
            ).observe(dt * 1e3)
            _tele.event("step_dispatched", step=self._t,
                        dispatch_ms=round(dt * 1e3, 3))
            _tele.gauge(
                "steps_in_flight",
                "Dispatched steps whose loss has not landed on the host"
            ).set(self.steps_in_flight())
        elif self._health_probes:
            self.steps_in_flight()   # retire → feed the health monitor
        return StepHandle(loss, self._t, dt, probes=probes)

    def steps_in_flight(self) -> int:
        """Dispatched steps whose loss has not yet landed on the host —
        non-blocking (`jax.Array.is_ready`), pruning finished entries.
        Retired steps feed their (now host-cheap) probe values to the
        health monitor when numerics probes are on."""
        q = self._inflight
        batch = []
        while q:
            entry = q[0]
            try:
                ready = bool(entry[1].is_ready())
            except Exception:
                ready = True
            if not ready:
                break
            q.popleft()
            batch.append(entry)
        if batch:
            now = time.perf_counter()
            # measured step wall: retire-to-retire cadence in a
            # pipelined steady state (first-ever retire falls back to
            # dispatch->retire).  Steps retiring in the SAME poll share
            # the interval since the previous retire — a per-entry
            # timestamp would divide full step flops by microseconds
            # and write garbage MFU rows into the corpus.
            prev, self._last_retire_t = self._last_retire_t, now
            base = prev if prev is not None else batch[0][3]
            measured_s = max(0.0, now - base) / len(batch)
            for step_id, loss, probes, _t_disp, x_span in batch:
                _health.beat("train_step.retire")
                if x_span is not None:
                    x_span.finish(t1=now)
                if probes is not None:
                    self._observe_health(step_id, loss, probes)
                if _tele.enabled():
                    # each step record carries the executable's cost-
                    # feature vector + the measured wall time — the
                    # (features, ms) corpus a learned performance model
                    # trains on — and updates the always-on
                    # mfu_estimate/step_flops/hbm_bytes_est gauges
                    cost = _trace.note_step_cost(
                        self._cost_key, measured_s) \
                        if measured_s > 0 else None
                    if cost is not None:
                        _tele.event("step_retired", step=step_id,
                                    cost=cost)
                    else:
                        _tele.event("step_retired", step=step_id)
        return len(q)

    def drain(self, timeout: Optional[float] = None) -> int:
        """Block until every dispatched step has retired (its loss landed
        on the host and, with health probes on, fed the monitor), or the
        `timeout` deadline passes.  Returns the number of steps still in
        flight (0 = fully drained).

        The recovery paths call this before acting on training state: a
        rollback restore or an emergency preemption save under
        outstanding donated buffers would race the in-flight steps, and
        the retirements carry the probe values the health monitor (and
        the anomaly→remediation policy behind it) still needs to see."""
        deadline = None if timeout is None else time.monotonic() + timeout
        while self._inflight:
            loss = self._inflight[0][1]
            if deadline is None:
                try:
                    jax.block_until_ready(loss)
                except Exception:
                    pass
            else:
                while True:
                    try:
                        ready = bool(loss.is_ready())
                    except Exception:
                        ready = True
                    if ready:
                        break
                    if time.monotonic() >= deadline:
                        return self.steps_in_flight()
                    time.sleep(0.002)
            before = len(self._inflight)
            self.steps_in_flight()   # retires the ready head(s)
            if len(self._inflight) >= before:
                break  # no progress — avoid spinning on a wedged entry
        return self.steps_in_flight()

    @staticmethod
    def _observe_health(step_id, loss, probes) -> None:
        """Hand one retired step's probe scalars to the health monitor.
        The arrays are ready (the retire check just passed), so the
        device_get is a host copy, not a sync."""
        mon = _health.monitor()
        if mon is None:
            return
        try:
            mon.observe(step_id,
                        loss=float(jax.device_get(loss)),
                        grad_norm=float(jax.device_get(probes["grad_norm"])),
                        nonfinite=int(jax.device_get(probes["nonfinite"])))
        except Exception:   # monitoring must never take the step down
            _log.exception("health probe observation failed")

    def dispatch_stats(self) -> dict:
        """Host-side dispatch latency over the last <=1024 steps: the time
        the training loop spent per `dispatch()` call (NOT device step
        time — overlap is working when this is far below step time)."""
        d = list(self._dispatch_s)
        if not d:
            return {"dispatches": 0, "mean_ms": 0.0, "max_ms": 0.0}
        return {"dispatches": len(d),
                "mean_ms": round(sum(d) * 1e3 / len(d), 4),
                "max_ms": round(max(d) * 1e3, 4)}

    def __call__(self, *batch, rng_key=None):
        """Run one step; returns the (replicated) scalar loss as jax array
        (async — `float(loss)` blocks; prefer `dispatch()` +
        `AsyncMetricBuffer` in throughput loops).

        Multi-process meshes: every process must pass the identical GLOBAL
        batch (each contributes its addressable shards — see `_put_global`);
        the first step cross-checks this so the per-host-shard habit from
        the reference's KVStore path fails loudly instead of training on a
        silent patchwork of half-dropped data."""
        return self.dispatch(*batch, rng_key=rng_key).loss

    def sync_params_to_block(self):
        """Write the (sharded) trained values back into the Parameters."""
        for n in self.param_names:
            self.params[n]._data._data = self.pvals[n]

    # -- checkpoint/resume ----------------------------------------------
    # Parity: `gluon/trainer.py:510,537` (save_states/load_states) widened
    # to the full sharded training state — params + optimizer state + step
    # counter + host RNG — so a killed job resumes bit-exact (the recovery
    # story SURVEY.md §5.3 plans as a new capability).

    def save(self, path: str) -> None:
        """Checkpoint params, optimizer state, step count, and RNG to `path`
        (.npz). Sharded arrays are gathered to host; `load` re-shards."""
        self._drain_async_save()
        self._write_checkpoint(path, self._snapshot())

    def save_async(self, path: str):
        """Non-blocking checkpoint: snapshot the training state as
        device-side COPIES (async dispatches — cheap to enqueue) and
        gather + write in a background thread while training continues.
        Returns a handle; call `.result()` to wait and re-raise any
        writer error.  Copies, not references: the jitted step donates
        its param/state buffers (`donate_argnums`), so the next step()
        would invalidate snapshotted originals on TPU — the private
        copies are untouched by donation.  Costs one transient extra
        params+opt-state footprint in HBM until the write drains.  The
        reference has no analogue — its NDArrays are mutable, so
        `save_states` must stop the engine (SURVEY §5.4's recovery story
        without the stall).

        Only one async save runs at a time: a second call waits for the
        first.  Multi-process meshes fall back to a synchronous save —
        the cross-host allgather must not race training collectives."""
        multi = any(not getattr(s, "is_fully_addressable", True)
                    for s in self.param_shardings.values())
        if multi:
            self.save(path)
            done: _cf.Future = _cf.Future()
            done.set_result(path)
            return done
        return self._submit_async_save(path)

    def _submit_async_save(self, path: str):
        self._drain_async_save()
        snap = self._snapshot(copy=True)
        raw = _ckpt_pool().submit(self._write_checkpoint, path, snap)
        fut = _ObservedFuture()

        def _relay(f):
            e = f.exception()  # retrieves — the raw future never warns
            try:
                if e is None:
                    fut.set_result(path)
                else:
                    fut.set_exception(e)
            finally:
                fut.settled.set()

        raw.add_done_callback(_relay)
        self._ckpt_last = fut
        return fut

    _ckpt_last = None

    def _drain_async_save(self):
        """Wait for any in-flight async save; re-raise its error ONLY if
        no holder of the returned future retrieved it yet (backstop for
        saves the caller never polled).  An error that `CheckpointManager`
        (or any `.result()` caller) already consumed is NOT raised again —
        otherwise one failed background write would abort the NEXT
        save/save_async synchronously, escaping ElasticLoop's tolerant
        drain and defeating its documented max_restores failure budget."""
        fut, self._ckpt_last = self._ckpt_last, None
        if fut is None:
            return
        fut.settled.wait()
        if fut.error_retrieved:
            return
        fut.result()

    def _snapshot(self, copy: bool = False):
        """Consistent view of the current training state.  With
        `copy=True` every device array is copied (async dispatch) so the
        snapshot survives the next step's buffer donation."""
        from .. import random as _rng
        g = _rng.generator
        dup = (lambda x: jnp.copy(x)) if copy else (lambda x: x)
        # bucketed ZeRO leaves are snapshotted at their LOGICAL (unpadded)
        # shape, so the checkpoint format is topology-agnostic: the same
        # file restores under any mesh/dp (load re-packs for its layout)
        return {
            "pvals": {n: dup(v) for n, v in self.pvals.items()},
            "opt_state": {n: [dup(leaf) for leaf in
                              self._logical_state_leaves(n)]
                          for n in self.diff_names},
            "t": self._t,
            "rng_seed": g._seed,
            "rng_key": g._key,
        }

    def _write_checkpoint(self, path: str, snap) -> str:
        from ..util import npz_encode_entry

        def put(out, key, val):
            npz_encode_entry(out, key, onp.asarray(_gather_to_host(val)))

        out = {}
        for n in self.param_names:
            put(out, "p:" + n, snap["pvals"][n])
        for n in self.diff_names:
            for i, leaf in enumerate(snap["opt_state"][n]):
                put(out, f"s:{n}:{i}", leaf)
        out["meta:t"] = onp.asarray(snap["t"], onp.int64)
        out["meta:rng_seed"] = onp.asarray(snap["rng_seed"], onp.int64)
        if snap["rng_key"] is not None:
            put(out, "meta:rng_key", snap["rng_key"])
        # Multi-process meshes: every rank gathered the identical global
        # payload above (collectives), and every rank writes it — to a
        # pid-suffixed tmp so concurrent writers never interleave within
        # one file; the atomic replaces then race benignly (identical
        # content, last one wins, `path` is always complete). Skipping
        # the write on rank != 0 would break callers that hand each rank
        # its own tmp path and replace afterwards (CheckpointManager).
        import os
        tmp = f"{path}.tmp.{os.getpid()}"
        with open(tmp, "wb") as f:
            onp.savez(f, **out)
        os.replace(tmp, path)   # atomic: a crash never truncates `path`
        return path

    def load(self, path: str) -> None:
        """Restore a `save` checkpoint; arrays are re-placed with this
        step's shardings (the mesh/topology may differ from save time)."""
        from .. import random as _rng

        from ..util import npz_decode_entry
        with onp.load(path, allow_pickle=False) as z:
            raw = dict(npz_decode_entry(k, z[k]) for k in z.files)

        for n in self.param_names:
            if "p:" + n not in raw:
                raise MXNetError(f"checkpoint {path} missing parameter {n}")
            self.pvals[n] = _shard_from_host(raw["p:" + n],
                                             self.param_shardings[n])
        for n in self.diff_names:
            leaves, treedef = jax.tree_util.tree_flatten(self.opt_state[n])
            buckets = self._state_buckets.get(n, {})
            new_leaves = []
            for i, old in enumerate(leaves):
                key = f"s:{n}:{i}"
                if key not in raw:
                    raise MXNetError(
                        f"checkpoint {path} missing optimizer state {key} "
                        f"(optimizer type changed since save?)")
                val = raw[key]
                # restore at the CURRENT state dtype: a checkpoint written
                # before the fp32-master-state default would otherwise pin
                # bf16 m/v back onto a step compiled for fp32 state
                if hasattr(old, "dtype") and val.dtype != old.dtype:
                    val = val.astype(old.dtype)
                # checkpoints store the LOGICAL value; this step's layout
                # decides the on-device representation — so a file written
                # under any topology restores under this one
                bucket = buckets.get(i)
                if bucket is not None:
                    val = onp.asarray(_pack_bucket(onp.asarray(val),
                                                   bucket))
                    sharding = NamedSharding(self.mesh, P("dp"))
                else:
                    sharding, _ = self._state_placement(n, val)
                new_leaves.append(_shard_from_host(val, sharding))
            self.opt_state[n] = jax.tree_util.tree_unflatten(
                treedef, new_leaves)
        self._t = int(raw["meta:t"])
        g = _rng.generator
        g._seed = int(raw.get("meta:rng_seed", g._seed))
        if "meta:rng_key" in raw:
            g._key = jnp.asarray(raw["meta:rng_key"])
        else:
            # checkpoint predates any RNG draw: clear this process's
            # (possibly advanced) key so draws restart from PRNGKey(seed)
            g._key = None
        self.sync_params_to_block()

    # -- ahead-of-time export (docs/export.md) ---------------------------

    def export(self, path: str, *batch, passes=None) -> str:
        """Capture this step's FULL jitted program (forward + backward +
        optimizer update, grad-accum scan and skip-guard included) to a
        versioned StableHLO artifact at `path`, optionally running an
        offline rewrite pipeline (`export.passes`) first.  `batch`: an
        example batch; omitted, the last dispatched batch's avals are
        reused.  The live step is untouched (capture builds scratch
        programs and restores every piece of compiled-step state)."""
        from ..export import capture_train_step, PassManager
        cap = capture_train_step(self, *batch)
        if passes:
            cap = PassManager(passes).run(cap)
        return cap.save(path)

    def load_export(self, path: str, *batch) -> float:
        """Warm-start from an export artifact WITHOUT tracing: the
        module for this step's current topology is deserialized and
        AOT-compiled (the persistent compile cache serves the binary
        when warm), so ``trace_count`` stays 0.  Fails fast with a
        clear `MXNetError` on version / topology / aval / step-flag
        mismatches (docs/export.md failure matrix).  Returns the
        compile wall seconds (also kept as `compile_seconds`)."""
        import os as _os
        from ..export import load as _load, spec_from_json
        from ..export.capture import _train_avals, _step_flags
        la = _load(path)
        if la.kind != "train_step":
            raise MXNetError(
                f"load_export: artifact at {path} is kind={la.kind!r}, "
                "not a train_step capture")
        topo = self.topology()
        rec = la.artifact.module_record(topo)
        flags = _step_flags(self)
        for k, want in rec["meta"].items():
            # remat is NOT an equality gate: the artifact's baked policy
            # is authoritative (replicas can't know an offline search's
            # winner up front) — it is warned about and adopted below.
            # It IS part of export_signature, so the auto-capture path
            # never silently matches across differing local knobs.
            if k == "remat_policy":
                continue
            if k in flags and flags[k] != want:
                raise MXNetError(
                    f"export artifact {path} was captured with {k}="
                    f"{want!r} but this step runs {k}={flags[k]!r}; the "
                    "compiled program would not match — re-capture or "
                    "construct the step with matching settings")
        # flags the artifact's meta may simply not RECORD (captured by
        # an older build): absence means the capture ran the default,
        # so a step running non-default must still refuse — the loop
        # above only sees the artifact's keys, and silence here would
        # e.g. train uncompressed under grad_compress="int8"
        for k, default in (("grad_compress", "none"),):
            if k not in rec["meta"] and flags.get(k, default) != default:
                raise MXNetError(
                    f"export artifact {path} predates the {k} step flag "
                    f"(captured running the default {default!r}) but "
                    f"this step runs {k}={flags[k]!r}; the compiled "
                    "program would not match — re-capture")
        art_remat = rec["meta"].get("remat_policy")
        # batch specs/shardings come from the manifest (no _build runs).
        # Everything below validates into LOCALS first: a failed load
        # must leave the step untouched, or warmup()'s live-trace
        # fallback would build against the artifact's stale specs.
        if rec.get("batch_specs") is not None:
            specs = tuple(spec_from_json(s) for s in rec["batch_specs"])
        else:
            specs = self.batch_specs
        if specs is None:
            raise MXNetError(
                f"export artifact {path} predates batch_specs recording; "
                "re-capture it")
        shardings = tuple(NamedSharding(self.mesh, s) for s in specs)
        if batch:
            batch_vals = [b._data if hasattr(b, "_data")
                          else b if isinstance(b, jax.Array)
                          else onp.asarray(b) for b in batch]
        else:
            batch_vals = [onp.zeros(tuple(s), onp.dtype(d))
                          for s, d in rec["batch_avals"]]
        live = (self.pvals, self.opt_state, self._hp(),
                jax.random.PRNGKey(0)) + tuple(
                    jax.ShapeDtypeStruct(tuple(b.shape), b.dtype)
                    for b in batch_vals)
        la.artifact.check_avals(topo, live)
        exported = la.exported_for(topo)   # deserialize failure raises
        # aval/flag validation passed.  The remaining steps (global-
        # batch cross-check, AOT compile of the deserialized module)
        # need the loaded specs installed, but can still fail — e.g. a
        # module captured for another platform raising from lower() —
        # so roll the step back to its prior state on ANY failure:
        # warmup()'s live-trace fallback must never build against a
        # half-loaded artifact's specs.
        saved = (self.batch_specs,
                 getattr(self, "_batch_shardings", None),
                 getattr(self, "_last_batch_avals", None))
        self.batch_specs = specs
        self._batch_shardings = shardings
        self._last_batch_avals = [
            (tuple(b.shape), onp.dtype(b.dtype)) for b in batch_vals]
        try:
            # the live path's first _build runs the identical-global-
            # batch cross-check; the artifact path must too (a fleet
            # cold-starting from artifacts is exactly where a per-host-
            # shard data bug would otherwise train on a patchwork)
            if batch:
                self._check_global_batch(batch_vals)
            avals = _train_avals(self, batch_vals)
            if _tele.enabled():
                _tele.event("compile_start", step=self._t,
                            kind="export_load")
            t0 = time.perf_counter()
            with _health.suppress_stalls("export_load_compile"):
                compiled = jax.jit(
                    exported.call,
                    donate_argnums=(0, 1) if self.donate else ()
                ).lower(*avals).compile()
        except BaseException:
            (self.batch_specs, self._batch_shardings,
             self._last_batch_avals) = saved
            raise
        self.compile_seconds = time.perf_counter() - t0
        self._exec = compiled
        self._record_cost(compiled, source="export_load")
        self._step_fn = None     # no live jit: the artifact IS the program
        # adopt the artifact's baked remat policy into the model knob so
        # any LATER live retrace (aval drift, reshard) lowers the same
        # program — and warn when it differs from the local setting
        # (e.g. an artifact captured without remat loaded into a step
        # whose operator set remat to fit HBM: the loaded program wins)
        if art_remat is not None:
            from ..export.capture import _find_cfg, _resolved_remat
            local = _resolved_remat(self)
            if local != art_remat:
                _log.warning(
                    "export artifact %s bakes remat policy %r but this "
                    "model is configured %r; the artifact's program "
                    "wins (cfg.remat updated to match — watch HBM if "
                    "you relied on the local setting)",
                    path, art_remat, local)
            cfg = _find_cfg(self.block)
            if cfg is not None and hasattr(cfg, "remat"):
                cfg.remat = False if art_remat == "none" else art_remat
        if _tele.enabled():
            _tele.event("compile_end", step=self._t, kind="export_load",
                        seconds=round(self.compile_seconds, 4),
                        artifact=_os.path.basename(_os.path.abspath(path)))
        return self.compile_seconds

    def export_signature(self, batch=()) -> str:
        """Deterministic identity for auto-capture artifact names: the
        program is a function of param/state avals, batch avals, mesh
        topology, optimizer, step flags, backend, and jax version."""
        from ..export import signature
        from ..export.capture import _step_flags
        import jax as _jax
        pav = [(n, tuple(v.shape), str(v.dtype))
               for n, v in sorted(self.pvals.items())]
        if batch:
            bav = [(tuple(b.shape), str(onp.asarray(
                        b._data if hasattr(b, "_data") else b).dtype))
                   for b in batch]
        else:
            bav = [(tuple(s), str(d))
                   for s, d in getattr(self, "_last_batch_avals", ())]
        return signature([
            pav, bav, sorted(self.topology()["axes"].items()),
            self.topology()["devices"], _step_flags(self),
            _jax.__version__, _jax.default_backend()])

    def _auto_artifact_path(self, batch):
        """MXTPU_EXPORT=1 + an export dir -> this step's auto artifact
        directory; None when auto capture is off."""
        import os as _os
        from ..export import auto_capture_enabled, export_dir
        if not auto_capture_enabled():
            return None
        d = export_dir()
        if not d:
            return None
        return _os.path.join(d, f"train-{self.export_signature(batch)}")

    # -- elastic mesh reformation ----------------------------------------

    def topology(self) -> dict:
        """Topology descriptor stamped into checkpoint manifests (and
        compared by `CheckpointManager.restore` to announce a
        topology-agnostic restore): device count + named axis sizes."""
        return {"devices": int(self.mesh.size),
                "axes": {str(k): int(v)
                         for k, v in dict(self.mesh.shape).items()},
                "processes": int(jax.process_count())}

    def reshard(self, new_mesh: Mesh, rules=None,
                gather: bool = True) -> None:
        """Re-form this step onto `new_mesh` IN PLACE — the elastic
        mesh-reformation primitive (`parallel.elastic_mesh`): the
        Trainer / model / optimizer objects survive, only the device
        layout and the compiled executable change.

        1. drains in-flight dispatched steps and any async checkpoint
           write (donated buffers must settle before re-placement),
        2. with ``gather=True`` gathers the FULL param + optimizer-state
           tree to host (fault point ``reshard_gather``; bucketed ZeRO
           leaves are unpacked to their logical shapes first),
        3. swaps the mesh, re-runs `ShardingRules`/annotations against
           the new axes (`auto_mesh` dp absorption happened in the
           caller's mesh build; ZeRO dp-axis augments and 1-D buckets
           are re-planned for the new dp), re-derives auto batch specs,
           and re-places the state,
        4. resets the compiled-step state — ``trace_count`` restarts at 0
           so the first dispatch on the new topology traces exactly
           once; the AOT executable, aval guard, and device-resident hp
           cache are dropped (they referenced the old devices).

        ``gather=False`` is the **host-loss** path: a dead host's shards
        cannot be gathered, so placements/buckets are re-planned but the
        live values are left stale — the caller MUST restore a
        checkpoint into the step before dispatching (the
        topology-agnostic `load` re-places every array)."""
        from ..resilience import fault_point
        fault_point("mesh_reform")
        tr = _trace.get_tracer("elastic") if _trace.enabled() else None
        if tr is not None:
            with tr.span("elastic.drain", step=self._t):
                self.drain()
                self._drain_async_save()
        else:
            self.drain()
            self._drain_async_save()
        host_p = host_s = None
        if gather:
            fault_point("reshard_gather")

            def _gather_all():
                hp = {n: onp.asarray(_gather_to_host(v))
                      for n, v in self.pvals.items()}
                hs = {n: [onp.asarray(_gather_to_host(leaf))
                          for leaf in self._logical_state_leaves(n)]
                      for n in self.diff_names}
                return hp, hs

            if tr is not None:
                # with-block, not a bare __exit__: a SuspectedHostLoss
                # mid-gather must not strand an open span on the stack
                # (every later span would parent under the corpse)
                with tr.span("elastic.gather", step=self._t):
                    host_p, host_s = _gather_all()
            else:
                host_p, host_s = _gather_all()
        old_axes = {k: int(v) for k, v in dict(self.mesh.shape).items()}
        self.mesh = new_mesh
        if rules is not None:
            self.rules = rules
        if self._auto_batch_specs:
            self.batch_specs = None      # re-derived for the new axes
        elif self._orig_batch_specs is not None:
            from .sharding import retarget_spec
            self.batch_specs = tuple(
                retarget_spec(s, new_mesh)
                for s in self._orig_batch_specs)
        self.param_shardings = {
            n: self._resolve_sharding(n, self.params[n])
            for n in self.param_names}
        if gather:
            self.pvals = {
                n: _shard_from_host(host_p[n], self.param_shardings[n])
                for n in self.param_names}
            new_state = {}
            for n in self.diff_names:
                _, treedef = jax.tree_util.tree_flatten(self.opt_state[n])
                new_state[n] = self._place_state_tree(
                    n, jax.tree_util.tree_unflatten(treedef, host_s[n]))
            self.opt_state = new_state
        else:
            self._replan_state_buckets()
        # compiled-step reset: everything tied to the old topology
        self._step_fn = None
        self._exec = None
        self._trace_count = 0
        self._trace_avals = None
        self._hp_cache = HpScalarCache()
        self._t_dev = None
        self._t_mirror = -1
        self.compile_seconds = None
        # attribution state from the old topology: the cost features
        # describe the OLD program (re-recorded at the next warmup/
        # compile), and retire-to-retire cadence restarts
        _trace.account().discard(self._cost_key)
        self._last_retire_t = None
        self._fused_opt_kernel = self._resolve_fused_kernel()
        if gather:
            self.sync_params_to_block()
        if _tele.enabled():
            _tele.event("mesh_reshard", step=self._t, gather=gather,
                        old_axes=old_axes,
                        new_axes=self.topology()["axes"])

    def _replan_state_buckets(self) -> None:
        """Recompute bucket metadata for the current mesh WITHOUT moving
        data (the gather=False reshard): logical shapes come from the
        old bucket records / leaf shapes, so the following `load` places
        every leaf correctly for the new dp."""
        for n in self.diff_names:
            leaves, _ = jax.tree_util.tree_flatten(self.opt_state[n])
            old = self._state_buckets.get(n, {})
            new: Dict[int, Tuple[Tuple[int, ...], int]] = {}
            for i, leaf in enumerate(leaves):
                shape = old[i][0] if i in old else tuple(leaf.shape)
                aval = jax.ShapeDtypeStruct(shape, leaf.dtype)
                _, bucket = self._state_placement(n, aval)
                if bucket is not None:
                    new[i] = bucket
            self._state_buckets[n] = new


class StepHandle:
    """Async result of `ShardedTrainStep.dispatch`.

    `loss` is the not-yet-fetched replicated device scalar; `step` the
    1-based step index; `dispatch_s` the host time the dispatch call took.
    `result()` blocks and returns the float; `is_ready()` polls without
    blocking.  Feed handles straight into `AsyncMetricBuffer.append`.
    `probes` carries the async numerics-probe scalars
    (``{"grad_norm", "nonfinite"}``) when health probes are enabled,
    else None (docs/observability.md).
    """

    __slots__ = ("loss", "step", "dispatch_s", "probes")

    def __init__(self, loss, step: int, dispatch_s: float, probes=None):
        self.loss = loss
        self.step = step
        self.dispatch_s = dispatch_s
        self.probes = probes

    def is_ready(self) -> bool:
        try:
            return bool(self.loss.is_ready())
        except AttributeError:
            return True

    def result(self) -> float:
        return float(jax.device_get(self.loss))

    def __repr__(self):
        return (f"StepHandle(step={self.step}, "
                f"dispatch_ms={self.dispatch_s * 1e3:.3f})")


class _ObservedFuture(_cf.Future):
    """Future that records whether its exception was ever retrieved
    (`result()` raised it or `exception()` returned it).  Lets
    `_drain_async_save` deliver a failed write's error exactly once:
    consumers like CheckpointManager retrieve it through the future, and
    the drain backstop raises only for never-polled failures."""

    error_retrieved = False

    def __init__(self):
        super().__init__()
        # set by the producer AFTER set_result/set_exception returns, i.e.
        # after done-callbacks ran — the drain waits on this, not on the
        # future's state, so it can't observe a failure mid-delivery
        self.settled = threading.Event()

    def result(self, timeout=None):
        try:
            return super().result(timeout)
        except BaseException as e:
            # only the future's OWN error counts as retrieved — a wait
            # timeout / interrupt (even one racing the completion) must
            # not swallow the real failure from the later drain backstop
            try:
                own = super().exception(timeout=0) if self.done() else None
            except BaseException:
                own = None
            if own is not None and e is own:
                self.error_retrieved = True
            raise

    def exception(self, timeout=None):
        e = super().exception(timeout)
        if e is not None:
            self.error_retrieved = True
        return e

    def cancel(self):
        # the background write is not cancellable: a True here would let
        # _relay's set_result/set_exception raise InvalidStateError and
        # lose the write's real outcome
        return False


_CKPT_POOL = None


def _ckpt_pool():
    """Process-wide single-worker writer pool: shared across every
    ShardedTrainStep so repeated step construction (elastic restarts,
    sweeps) doesn't accumulate idle checkpoint threads."""
    global _CKPT_POOL
    if _CKPT_POOL is None:
        _CKPT_POOL = _cf.ThreadPoolExecutor(
            max_workers=1, thread_name_prefix="mxtpu-ckpt")
    return _CKPT_POOL


def _gather_to_host(x):
    """Fetch a (possibly multi-process-sharded) jax array to host numpy.
    Single-process arrays are fully addressable; multi-process global arrays
    need the allgather helper."""
    if not isinstance(x, jax.Array) or x.is_fully_addressable:
        return jax.device_get(x)
    from jax.experimental import multihost_utils
    return multihost_utils.process_allgather(x, tiled=True)


def _shard_from_host(arr, sharding):
    """Place a host array with `sharding`; works when the mesh spans
    multiple processes (each process fills only its addressable shards)."""
    a = jnp.asarray(arr) if jax.process_count() == 1 else arr
    if jax.process_count() == 1:
        return jax.device_put(a, sharding)
    arr = onp.asarray(arr)
    return jax.make_array_from_callback(arr.shape, sharding,
                                        lambda idx: arr[idx])


def _pack_bucket(leaf, bucket):
    """Flatten + zero-pad a logical optimizer-state leaf into its
    dp-bucket representation ``(padded_size,)``.  Works on host numpy
    (checkpoint load) and on traced jax values (inside the step)."""
    shape, padded = bucket
    size = int(onp.prod(shape)) if shape else 1
    if isinstance(leaf, onp.ndarray):
        flat = leaf.reshape(-1)
        if padded == size:
            return flat
        return onp.concatenate(
            [flat, onp.zeros(padded - size, leaf.dtype)])
    flat = jnp.ravel(leaf)
    if padded == size:
        return flat
    return jnp.pad(flat, (0, padded - size))


def _with_dp_axis(mesh: Mesh, spec, shape):
    """Add 'dp' to the first free divisible dim of `spec`; None when the
    mesh has no dp>1 axis, 'dp' is already used, or no dim divides."""
    dp = dict(mesh.shape).get("dp", 1)
    if dp <= 1 or not shape:
        return None
    spec = list(spec) + [None] * (len(shape) - len(spec))
    if "dp" in _spec_axes(spec):
        return None
    for i, dim in enumerate(shape):
        if spec[i] is None and dim % dp == 0:
            spec[i] = "dp"
            return NamedSharding(mesh, P(*spec))
    return None


def _master_dtype(w):
    """Optimizer state for 16-bit weights accumulates in fp32 (the
    multi-precision default; bf16 m/v drifts) — hand `create_state_jax` an
    fp32 ShapeDtypeStruct so `zeros_like` state comes out fp32 WITHOUT
    materializing an fp32 copy of the parameter (2x HBM spike at init)."""
    if jnp.issubdtype(w.dtype, jnp.floating) and \
            jnp.dtype(w.dtype).itemsize < 4:
        return jax.ShapeDtypeStruct(w.shape, jnp.float32)
    return w


def _like_sharding(param_sharding: NamedSharding, state_leaf, param):
    """Optimizer state shards like its parameter when shapes match, else
    replicated (e.g. row-wise accumulators)."""
    if hasattr(state_leaf, "shape") and tuple(state_leaf.shape) == \
            tuple(param.shape):
        return param_sharding
    return NamedSharding(param_sharding.mesh, P())


def make_sharded_train_step(block, optimizer, loss_fn, mesh, rules=None,
                            batch_specs=None, num_model_args=None,
                            zero=False, fsdp=False,
                            grad_accum=1, donate=True,
                            grad_compress=None) -> ShardedTrainStep:
    return ShardedTrainStep(block, optimizer, loss_fn, mesh, rules,
                            batch_specs, num_model_args, zero=zero,
                            fsdp=fsdp, grad_accum=grad_accum, donate=donate,
                            grad_compress=grad_compress)
