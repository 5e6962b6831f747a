"""Pallas TPU kernel set (flash attention, fused cross-entropy, paged
attention, fused norms, fused multi-tensor optimizer, blockwise MoE
dispatch) plus the block-size autotuner.

Dispatch policy — one env var, `MXTPU_PALLAS`, governs every kernel in
this package (docs/perf.md "Fused kernels & autotuning"):

- ``auto`` (default): Pallas kernels on a TPU backend, jnp reference
  implementations everywhere else.  Interpret mode alone does NOT flip
  `auto` to kernels: several test modules enable
  ``MXTPU_PALLAS_INTERPRET`` process-wide, and silently re-routing every
  later layer-norm/optimizer through the interpreter would turn the CPU
  suite into a Pallas-interpreter suite.
- ``kernel``: force the Pallas path (on CPU this requires
  ``MXTPU_PALLAS_INTERPRET=1`` — the interpret-mode parity harness).
- ``reference``: force the jnp reference path everywhere, even on TPU.
- ``off``: unfused legacy paths (dense MoE einsums, per-leaf optimizer
  updates, plain layer_norm) — the escape hatch when a fused rewrite is
  suspected of a regression.

Every kernel module ships a jnp reference implementation that is both
the CPU tier-1 path and the interpret-mode parity oracle (the
`paged_attention.py` pattern).
"""
from __future__ import annotations

import contextlib
import os
import threading

__all__ = ["pallas_mode", "kernel_active", "interpret_mode",
           "note_fused_launch", "tpu_compiler_params",
           "partitioned_by_gspmd", "per_shard", "partitionable"]

_trace_state = threading.local()


@contextlib.contextmanager
def _tracing_under_gspmd(flag: bool):
    prev = getattr(_trace_state, "gspmd", False)
    _trace_state.gspmd = flag
    try:
        yield
    finally:
        _trace_state.gspmd = prev


def partitioned_by_gspmd(n_devices: int):
    """Wrap the TRACE of a program GSPMD will partition over `n_devices`
    (`ShardedTrainStep` does).  jax refuses to lower a Mosaic kernel
    there — "Mosaic kernels cannot be automatically partitioned. Please
    wrap the call in a shard_map" — so over more than one device every
    auto dispatch in this package takes its jnp reference, which GSPMD
    partitions like any other op.  Decided here, before anything is
    traced, and visible as a compiled step with no custom calls."""
    return _tracing_under_gspmd(n_devices > 1)


def per_shard():
    """Wrap the trace of a `shard_map` body (`shard_map_nocheck` does):
    arrays there are per-device, so Mosaic kernels lower again."""
    return _tracing_under_gspmd(False)


def partitionable() -> bool:
    """Can a kernel dispatched right now be lowered?  False while
    tracing a GSPMD-partitioned program outside any shard_map — unless
    kernels run through the interpreter, which emits plain ops."""
    return interpret_mode() or not getattr(_trace_state, "gspmd", False)


def tpu_compiler_params(*dimension_semantics: str):
    """Mosaic compiler params naming each grid dimension's semantics
    ("parallel" / "arbitrary") — the one spelling every kernel in this
    package uses."""
    from jax.experimental.pallas import tpu as pltpu
    return pltpu.CompilerParams(
        dimension_semantics=tuple(dimension_semantics))


def interpret_mode() -> bool:
    """True when ``MXTPU_PALLAS_INTERPRET=1`` (kernels run through the
    Pallas interpreter — CPU testing of the exact kernel code)."""
    from ...base import getenv_bool
    return getenv_bool("MXTPU_PALLAS_INTERPRET", False)


def pallas_mode() -> str:
    """Resolve ``MXTPU_PALLAS`` to one of auto|kernel|reference|off."""
    v = os.environ.get("MXTPU_PALLAS", "auto").strip().lower()
    if v in ("off", "0", "false", "no"):
        return "off"
    if v in ("reference", "ref"):
        return "reference"
    if v in ("kernel", "force", "pallas"):
        return "kernel"
    return "auto"


def kernel_active() -> bool:
    """Should a fused op dispatch its Pallas kernel right now?

    ``kernel`` forces it; ``auto`` requires an actual TPU backend (see
    the module docstring for why interpret mode deliberately does not
    count) and a trace the kernel can be lowered in (`partitionable`);
    ``reference``/``off`` never."""
    mode = pallas_mode()
    if mode == "kernel":
        return True
    if mode in ("reference", "off"):
        return False
    import jax
    return jax.default_backend() == "tpu" and partitionable()


def note_fused_launch(op: str) -> None:
    """Count a fused-kernel instantiation in telemetry.

    Called where the kernel wrapper chooses the Pallas path — under jit
    that is trace time, so the counter reads "fused launches compiled
    into programs", not per-step executions (zero hot-path cost)."""
    from ... import telemetry as _tele
    if not _tele.enabled():
        return
    _tele.counter(
        "kernel_fused",
        "Fused Pallas kernel instantiations by op (counted at trace "
        "time)", labelnames=("op",)).inc(op=op)
