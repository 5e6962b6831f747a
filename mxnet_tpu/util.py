"""Utility helpers (parity: `python/mxnet/util.py` + ndarray save/load from
`src/ndarray/ndarray.cc` and `.npz` support from `src/serialization/cnpy.cc`)."""
from __future__ import annotations

import functools
import os
import threading
from typing import Dict, List, Union

import jax
import jax.numpy as jnp
import numpy as _onp

from .base import MXNetError

__all__ = [
    "save_arrays", "load_arrays", "use_np", "use_np_shape", "use_np_array",
    "is_np_array", "is_np_shape", "set_np", "reset_np", "np_shape", "np_array",
    "getenv", "setenv", "default_array",
    "x64_enabled", "set_x64", "x64_scope",
]


# -----------------------------------------------------------------------
# 64-bit float support (parity: the reference computes genuinely in f64 on
# CPU via mshadow dtype dispatch; under XLA the equivalent switch is
# `jax_enable_x64`).  Three ways in: the MXTPU_ENABLE_X64=1 env var at
# import, the global set_x64(True), or the scoped x64_scope() context.
# While x64 is DISABLED, explicit float64/complex128 requests raise
# loudly (base.check_x64_dtype) instead of silently truncating to f32.
# -----------------------------------------------------------------------

def x64_enabled() -> bool:
    """True when 64-bit floats are live (jax_enable_x64)."""
    return bool(jax.config.jax_enable_x64)


def set_x64(enabled: bool = True) -> None:
    """Globally enable/disable 64-bit float support (process-wide)."""
    jax.config.update("jax_enable_x64", bool(enabled))


def x64_scope(enabled: bool = True):
    """Scoped 64-bit float support::

        with mx.util.x64_scope():
            a = mx.np.array([1.0], dtype="float64")   # true f64

    Wraps JAX's scoped `enable_x64` config state; compiled functions are
    cached separately per setting, so toggling is jit-safe."""
    return jax.enable_x64(bool(enabled))


def npz_encode_entry(out: dict, key: str, arr) -> None:
    """Stage one host array for `np.savez`; npz has no bfloat16, so bf16
    values are stored as a uint16 view under a `__bf16__` name tag."""
    arr = _onp.asarray(arr)
    if arr.dtype == jnp.bfloat16:
        out["__bf16__" + key] = arr.view(_onp.uint16)
    else:
        out[key] = arr


def npz_decode_entry(key: str, value):
    """Inverse of `npz_encode_entry`: -> (original key, decoded array)."""
    if key.startswith("__bf16__"):
        return key[len("__bf16__"):], value.view(jnp.bfloat16)
    return key, value


def save_arrays(fname: str, data):
    """Save ndarray dict/list/single to `.npz` (or legacy param format)."""
    from .ndarray.ndarray import ndarray
    if isinstance(data, ndarray):
        data = {"arr_0": data}
    if isinstance(data, (list, tuple)):
        data = {f"arr_{i}": a for i, a in enumerate(data)}
    out = {}
    for k, v in data.items():
        npz_encode_entry(out, k, v.asnumpy() if isinstance(v, ndarray) else v)
    with open(fname, "wb") as f:
        _onp.savez(f, **out)


def load_arrays(fname: str):
    from .numpy import array
    out = {}
    with _onp.load(fname, allow_pickle=False) as z:
        for k in z.files:
            name, v = npz_decode_entry(k, z[k])
            out[name] = array(v)
    return out


# ---- numpy-semantics scopes (parity: `python/mxnet/util.py` np_shape /
# set_np / use_np).  The np front end (`mx.np`) is unconditionally
# np-semantics by design; the SHAPE flag below is real scoped state that
# the LEGACY `mx.nd` surface consults — with it off (the reference's
# import-time default) 0-d / zero-size creations raise, as 1.x did. ----

_np_shape_global = [False]          # process-wide flag (set_np_shape)
_np_shape_state = threading.local()  # per-thread scope override (np_shape)


def is_np_array():
    return True


def is_np_shape():
    override = getattr(_np_shape_state, "value", None)
    return _np_shape_global[0] if override is None else override


def set_np_shape(active):
    """Turn numpy shape semantics on/off globally (process-wide, visible
    to all threads); returns the previous state (parity: util.py
    set_np_shape).  The scoped `np_shape` context overrides per-thread."""
    prev = is_np_shape()
    _np_shape_global[0] = bool(active)
    return prev


def set_np(shape=True, array=True, dtype=False):
    if not shape and array:
        raise ValueError("NumPy-array semantics require NumPy-shape "
                         "semantics (reference set_np constraint)")
    set_np_shape(shape)


def reset_np():
    set_np_shape(False)


class np_shape:
    """Context manager / decorator scoping numpy shape semantics for the
    CURRENT thread (parity: util.py np_shape)."""

    def __init__(self, active=True):
        self._active = bool(active)
        self._prev = None

    def __enter__(self):
        self._prev = getattr(_np_shape_state, "value", None)
        _np_shape_state.value = self._active
        return self

    def __exit__(self, *a):
        _np_shape_state.value = self._prev
        return False

    def __call__(self, fn):
        @functools.wraps(fn)
        def wrapped(*args, **kwargs):
            with np_shape(self._active):
                return fn(*args, **kwargs)
        return wrapped


class np_array:
    """Array-semantics scope: always-on here (single ndarray type), kept
    as a context manager for API parity."""

    def __init__(self, active=True):
        pass

    def __enter__(self):
        return self

    def __exit__(self, *a):
        return False

    def __call__(self, fn):
        return fn


def use_np_shape(fn):
    return np_shape(True)(fn)


def use_np_array(fn):
    return fn


def use_np(fn):
    return use_np_array(use_np_shape(fn))


def use_np_default_dtype(fn):
    return fn


def getenv(name):
    return os.environ.get(name)


def setenv(name, value):
    os.environ[name] = value


def default_array(source_array, ctx=None, dtype=None):
    from .numpy import array
    return array(source_array, dtype=dtype, ctx=ctx)
