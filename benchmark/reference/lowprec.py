"""The control's arithmetic: a precision below bfloat16.

A matrix product whose two operands are rounded with one symmetric scale
per row of the contracted axis (per token for activations, per output
channel for weights), to

- ``fp8``: float8 e4m3 (3 bits of mantissa against bfloat16's 7), the row's
  largest magnitude mapped to the format's largest number.  This is the
  control the limits are set against;
- ``int8``: 8-bit integers ("W8A8"), the step a later PR would be tempted to
  take on a chip whose int8 peak is twice its bf16 peak.  With a scale a row
  its rounding error is about that of bfloat16 itself (7 bits and a sign
  against 7 bits of mantissa), so no limit separates it from the program's
  own rounding on every seed; its readings stand beside the control's in
  `PERF.md`.

The rounding is straight-through, so a gradient taken through it sees the
rounded operands and full-precision cotangents.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

FP8 = jnp.float8_e4m3fn
FP8_MAX = float(jnp.finfo(FP8).max)


def _row_scale(x, axis: int, top: float):
    scale = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / top
    return jnp.where(scale == 0, 1.0, scale)


def round_int8(x, axis: int = -1):
    scale = _row_scale(x, axis, 127.0)
    q = jnp.clip(jnp.round(x / scale), -127, 127) * scale
    return x + jax.lax.stop_gradient(q - x)


def round_fp8(x, axis: int = -1):
    scale = _row_scale(x, axis, FP8_MAX)
    q = (x / scale).astype(FP8).astype(x.dtype) * scale
    return x + jax.lax.stop_gradient(q - x)


_ROUND = {"int8": round_int8, "fp8": round_fp8}


def linear(x, w, b, precision: str):
    """x (..., in) @ w(out, in)^T + b, float32; `precision` is "float32"
    (the reference), "fp8" (the control) or "int8"."""
    if precision in _ROUND:
        x, w = _ROUND[precision](x), _ROUND[precision](w)
    elif precision != "float32":
        raise ValueError(f"unknown precision {precision!r}")
    y = jnp.einsum("...i,oi->...o", x, w,
                   precision=jax.lax.Precision.HIGHEST)
    return y if b is None else y + b
