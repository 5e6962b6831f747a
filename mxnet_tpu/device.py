"""Device / Context layer.

TPU-native replacement for the reference's `Context` (`include/mxnet/base.h`,
`python/mxnet/device.py`): a `Device` names a logical placement (`cpu(0)`,
`tpu(0)`, `gpu(i)` kept as an alias for the accelerator) and maps onto a JAX
PjRt device. There is no stream/storage manager here — XLA/PjRt owns streams
and memory (SURVEY.md §7); what remains is placement choice and a
thread-local "current device" stack mirroring `with mx.Device(...)`.
"""
from __future__ import annotations

import threading
from typing import List, Optional

import jax

from .base import MXNetError

__all__ = [
    "Device", "Context", "cpu", "gpu", "tpu", "cpu_pinned",
    "current_device", "current_context", "num_gpus", "num_tpus", "num_devices",
]

_ACCEL_TYPES = ("tpu", "gpu", "cuda", "rocm")


def _jax_devices_by_platform():
    # local_devices: in a multi-controller (jax.distributed) job, global
    # jax.devices() includes other processes' devices, which this process
    # cannot address (device_put would fail)
    by_platform = {}
    for d in jax.local_devices():
        by_platform.setdefault(d.platform.lower(), []).append(d)
    return by_platform


class Device:
    """A logical device. device_type in {'cpu', 'tpu', 'gpu', 'cpu_pinned'}.

    'gpu' is accepted for source compatibility with reference code and maps to
    the accelerator platform actually present (TPU here).
    """

    _local = threading.local()

    def __init__(self, device_type: str, device_id: int = 0):
        if isinstance(device_type, Device):
            device_type, device_id = device_type.device_type, device_type.device_id
        device_type = device_type.lower()
        if device_type not in ("cpu", "tpu", "gpu", "cpu_pinned", "cpu_shared"):
            raise MXNetError(f"unknown device type {device_type!r}")
        self.device_type = device_type
        self.device_id = int(device_id)

    # -- resolution to a concrete PjRt device ------------------------------
    @property
    def jax_device(self):
        by_platform = _jax_devices_by_platform()
        want_accel = self.device_type in ("tpu", "gpu")
        if want_accel:
            for p in _ACCEL_TYPES:
                if p in by_platform:
                    pool = by_platform[p]
                    return pool[self.device_id % len(pool)]
            # no accelerator: fall back to cpu (keeps tests device-agnostic)
            pool = by_platform.get("cpu")
            if pool:
                return pool[self.device_id % len(pool)]
            raise MXNetError("no JAX devices available")
        # cpu platform may be uninitialised (e.g. JAX_PLATFORMS=tpu only):
        # fall back to the default local devices
        pool = by_platform.get("cpu") or jax.local_devices()
        return pool[self.device_id % len(pool)]

    # -- equality / hashing -------------------------------------------------
    def __eq__(self, other):
        if isinstance(other, str):
            try:
                other = Device(other)
            except MXNetError:
                return NotImplemented
        if not isinstance(other, Device):
            return NotImplemented
        a = "tpu" if self.device_type in ("tpu", "gpu") else "cpu"
        b = "tpu" if other.device_type in ("tpu", "gpu") else "cpu"
        return a == b and self.device_id == other.device_id

    def __hash__(self):
        a = "tpu" if self.device_type in ("tpu", "gpu") else "cpu"
        return hash((a, self.device_id))

    def __repr__(self):
        return f"{self.device_type}({self.device_id})"

    def __str__(self):
        return self.__repr__()

    # -- context manager ----------------------------------------------------
    def __enter__(self):
        stack = getattr(Device._local, "stack", None)
        if stack is None:
            stack = Device._local.stack = []
        stack.append(self)
        return self

    def __exit__(self, *exc):
        Device._local.stack.pop()
        return False

    @staticmethod
    def _current() -> "Device":
        stack = getattr(Device._local, "stack", None)
        if stack:
            return stack[-1]
        return _default_device()


# Context is the legacy alias (reference `python/mxnet/context.py`)
Context = Device
_DEFAULT: Optional[Device] = None


def _default_device() -> Device:
    """Default placement mirrors the JAX default backend: tpu(0) when an
    accelerator platform is initialised, else cpu(0). Resolved lazily (and
    cached) so importing the package never forces backend initialisation."""
    global _DEFAULT
    if _DEFAULT is None:
        try:
            plat = jax.devices()[0].platform.lower()
        except Exception:
            # backend not initialised yet (e.g. before
            # jax.distributed.initialize on a pod): don't cache the fallback
            return Device("cpu", 0)
        _DEFAULT = Device("tpu" if plat in _ACCEL_TYPES else "cpu", 0)
    return _DEFAULT


def cpu(device_id: int = 0) -> Device:
    return Device("cpu", device_id)


def cpu_pinned(device_id: int = 0) -> Device:
    return Device("cpu_pinned", device_id)


def gpu(device_id: int = 0) -> Device:
    return Device("gpu", device_id)


def tpu(device_id: int = 0) -> Device:
    return Device("tpu", device_id)


def current_device() -> Device:
    return Device._current()


def current_context() -> Device:
    return Device._current()


def num_devices() -> int:
    """Count of LOCAL (addressable) devices — consistent with
    `Device.jax_device` resolution; use `jax.device_count()` for the
    global count in multi-process jobs."""
    return len(jax.local_devices())


def _num_accel() -> int:
    by_platform = _jax_devices_by_platform()
    for p in _ACCEL_TYPES:
        if p in by_platform:
            return len(by_platform[p])
    return 0


def num_gpus() -> int:
    """Parity with `mx.device.num_gpus`; counts accelerator chips."""
    return _num_accel()


def num_tpus() -> int:
    return _num_accel()
