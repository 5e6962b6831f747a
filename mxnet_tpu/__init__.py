"""mxnet_tpu — a TPU-native deep-learning framework with MXNet 2.x capabilities.

Import convention mirrors the reference (`python/mxnet/__init__.py:23-80`):

    import mxnet_tpu as mx
    x = mx.np.ones((2, 3), device=mx.tpu())
    with mx.autograd.record():
        y = (x * x).sum()
    y.backward()

Compute lowers to XLA on TPU via JAX; the runtime design is documented in
SURVEY.md §7 — there is deliberately no dependency engine, stream manager or
memory pool here (PjRt provides all three).
"""
from __future__ import annotations

__version__ = "0.1.0"

import os as _os

# 64-bit float support (docs/env_vars.md "MXTPU_ENABLE_X64"): the reference
# computes genuinely in f64 on CPU; here f64 rides jax_enable_x64. Without
# it, explicit float64 requests raise loudly (base.check_x64_dtype) —
# never a silent truncation. Scoped alternative: mx.util.x64_scope().
if _os.environ.get("MXTPU_ENABLE_X64", "").lower() in ("1", "true", "on"):
    import jax as _jax
    _jax.config.update("jax_enable_x64", True)
    del _jax
del _os

from .base import MXNetError, SuspectedHostLoss  # noqa: F401
from . import device  # noqa: F401
from .device import (  # noqa: F401
    Device, Context, cpu, gpu, tpu, cpu_pinned,
    current_device, current_context, num_gpus, num_tpus, num_devices,
)
from . import _tape  # noqa: F401
from . import ndarray  # noqa: F401
from . import ndarray as nd  # noqa: F401
from .ndarray.ndarray import NDArray  # noqa: F401
from . import numpy  # noqa: F401
from . import numpy as np  # noqa: F401
from . import numpy_extension  # noqa: F401
from . import numpy_extension as npx  # noqa: F401
from . import autograd  # noqa: F401
from . import random  # noqa: F401
from . import initializer  # noqa: F401
from . import initializer as init  # noqa: F401
from . import optimizer  # noqa: F401
from .optimizer import lr_scheduler  # noqa: F401  (mx.lr_scheduler parity)
from . import engine  # noqa: F401
from . import gluon  # noqa: F401
from . import kvstore  # noqa: F401
from . import kvstore as kv  # noqa: F401
from . import parallel  # noqa: F401
from . import profiler  # noqa: F401
from . import telemetry  # noqa: F401
from . import tracing  # noqa: F401
from . import health  # noqa: F401
from . import recovery  # noqa: F401
from . import amp  # noqa: F401
from . import serve  # noqa: F401
from . import export  # noqa: F401
from . import runtime  # noqa: F401
from . import util  # noqa: F401
from .util import (  # noqa: F401  (reference exposes these at top level)
    np_shape, np_array, use_np, use_np_shape, use_np_array,
    use_np_default_dtype, set_np, reset_np, set_np_shape,
    is_np_shape, is_np_array,
)
from . import test_utils  # noqa: F401
from . import recordio  # noqa: F401
from . import io  # noqa: F401
from . import data  # noqa: F401
from . import image  # noqa: F401
from . import ops  # noqa: F401
from . import models  # noqa: F401
from . import operator  # noqa: F401
from . import contrib  # noqa: F401
from . import symbol  # noqa: F401
from . import symbol as sym  # noqa: F401
from . import onnx  # noqa: F401
from . import library  # noqa: F401
from . import subgraph  # noqa: F401
from . import elastic  # noqa: F401
from . import resilience  # noqa: F401
from . import context  # noqa: F401  (legacy 1.x spelling of device)
from . import error  # noqa: F401
from . import log  # noqa: F401
from . import name  # noqa: F401
from . import attribute  # noqa: F401
from . import dlpack  # noqa: F401
from . import rtc  # noqa: F401
from . import callback  # noqa: F401
from . import model  # noqa: F401
from . import executor  # noqa: F401
from . import registry  # noqa: F401
from . import visualization  # noqa: F401
from . import visualization as viz  # noqa: F401
from . import container  # noqa: F401
from . import space  # noqa: F401
from .context import Context  # noqa: F401
from . import runtime as libinfo  # noqa: F401  (feature discovery alias)
from . import benchmark  # noqa: F401
from . import _native  # noqa: F401

device_module = device
