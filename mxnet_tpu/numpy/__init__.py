"""`mx.np` — NumPy-compatible array namespace, TPU-native.

Parity: `python/mxnet/numpy/` (multiarray.py:275 and the `_npi_*` op corpus in
`src/operator/numpy/`). Ops lower to `jax.numpy` (hence XLA); autograd runs
through the central `apply_op` dispatcher; dynamic-shape ops (`unique`,
`nonzero`, boolean masks) execute eagerly with host synchronisation — the same
behavior as the reference's shape-readback in `Invoke`
(`src/imperative/imperative.cc:128-135`) — and raise a clear error under jit.
"""
from __future__ import annotations

from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as _onp

from ..base import MXNetError, check_x64_dtype
from ..device import Device, current_device
from ..ndarray.ndarray import ndarray, apply_op, from_jax, _write_out
from ._wrap import wrap_fn

# -----------------------------------------------------------------------
# constants & dtypes
# -----------------------------------------------------------------------
pi = _onp.pi
e = _onp.e
euler_gamma = _onp.euler_gamma
inf = _onp.inf
nan = _onp.nan
newaxis = None
NINF = -_onp.inf
PZERO, NZERO = 0.0, -0.0

float16 = _onp.float16
float32 = _onp.float32
float64 = _onp.float64
bfloat16 = jnp.bfloat16
int8 = _onp.int8
int16 = _onp.int16
int32 = _onp.int32
int64 = _onp.int64
uint8 = _onp.uint8
uint16 = _onp.uint16
uint32 = _onp.uint32
uint64 = _onp.uint64
bool_ = _onp.bool_
bool = bool_  # noqa: A001 — reference exposes `np.bool` (numpy/utils.py:26)
complex64 = _onp.complex64
complex128 = _onp.complex128
intp = _onp.intp

# dtype families (parity: numpy/utils.py:177-201)
integer_dtypes = [int8, int16, int32, int64, uint8, uint16, uint32, uint64]
floating_dtypes = [float16, float32, float64]
numeric_dtypes = [*integer_dtypes, *floating_dtypes]
boolean_dtypes = [bool_]

_default_float = [float32]


def set_default_dtype(dtype):
    _default_float[0] = dtype


def default_dtype():
    return _default_float[0]


dtype = _onp.dtype
finfo = jnp.finfo
iinfo = jnp.iinfo

# -----------------------------------------------------------------------
# creation
# -----------------------------------------------------------------------

def _dev(device, ctx):
    d = device or ctx
    if d is None:
        return current_device()
    return Device(d) if not isinstance(d, Device) else d


def array(object, dtype=None, device=None, ctx=None, copy=True):
    dev = _dev(device, ctx)
    if isinstance(object, ndarray):
        data = object._data
        if dtype is not None:
            data = data.astype(dtype)
        elif copy:
            data = data + 0 if jnp.issubdtype(data.dtype, jnp.number) else jnp.array(data)
        return from_jax(data, dev)
    if dtype is None:
        npv = _onp.asarray(object)
        if npv.dtype == _onp.float64:
            dtype = _default_float[0]
        else:
            dtype = npv.dtype
    else:
        check_x64_dtype(dtype)
        # signed int32/int64 targets convert THROUGH numpy with the
        # dtype: out-of-range Python ints raise numpy's OverflowError
        # (loud) instead of silently wrapping in a later jnp downcast —
        # the documented large-tensor stance (docs/env_vars.md "Large
        # tensors").  Other integer dtypes keep wraparound (the
        # reference's semantics for e.g. np.array([-1], dtype="uint8")).
        try:
            npdt = jnp.dtype(dtype)
        except TypeError:
            npdt = None
        loud = npdt is not None and npdt.kind == "i" and npdt.itemsize >= 4
        npv = _onp.asarray(object, dtype=npdt if loud else None)
    data = jnp.asarray(npv, dtype=dtype)
    data = jax.device_put(data, dev.jax_device)
    return from_jax(data, dev)


def asarray(a, dtype=None, device=None, ctx=None):
    if isinstance(a, ndarray) and dtype is None:
        return a
    return array(a, dtype=dtype, device=device, ctx=ctx, copy=False)


def _creation(jfn):
    def fn(shape, dtype=None, order="C", device=None, ctx=None, **kw):
        if dtype is None:
            dtype = _default_float[0]
        else:
            check_x64_dtype(dtype)
        dev = _dev(device, ctx)
        if isinstance(shape, ndarray):
            shape = tuple(int(s) for s in shape.asnumpy())
        data = jfn(shape, dtype=dtype, **kw)
        data = jax.device_put(data, dev.jax_device)
        return from_jax(data, dev)
    return fn


zeros = _creation(jnp.zeros)
ones = _creation(jnp.ones)
empty = _creation(jnp.zeros)  # XLA has no uninitialised alloc


def full(shape, fill_value, dtype=None, order="C", device=None, ctx=None, out=None):
    check_x64_dtype(dtype)
    dev = _dev(device, ctx)
    if isinstance(fill_value, ndarray):
        fill_value = fill_value._data
    if dtype is None and not hasattr(fill_value, "dtype"):
        dtype = _default_float[0] if isinstance(fill_value, float) else None
    data = jnp.full(shape, fill_value, dtype=dtype)
    data = jax.device_put(data, dev.jax_device)
    return _write_out(from_jax(data, dev), out)


def zeros_like(a, dtype=None, order="C", device=None, ctx=None):
    check_x64_dtype(dtype)
    return apply_op(lambda x: jnp.zeros_like(x, dtype=dtype), (a,), {}, name="zeros_like")


def ones_like(a, dtype=None, order="C", device=None, ctx=None):
    check_x64_dtype(dtype)
    return apply_op(lambda x: jnp.ones_like(x, dtype=dtype), (a,), {}, name="ones_like")


def full_like(a, fill_value, dtype=None, order="C", device=None, ctx=None):
    check_x64_dtype(dtype)
    return apply_op(lambda x: jnp.full_like(x, fill_value, dtype=dtype), (a,), {},
                    name="full_like")


empty_like = zeros_like


def arange(start, stop=None, step=1, dtype=None, device=None, ctx=None):
    check_x64_dtype(dtype)
    dev = _dev(device, ctx)
    if dtype is None:
        # the reference's np.arange defaults to float32 for ANY input
        # (numpy/multiarray.py arange: "The default is `float32`"), unlike
        # numpy's int default — int output truncates downstream gradients
        dtype = _default_float[0]
    data = jnp.arange(start, stop, step, dtype=dtype)
    return from_jax(jax.device_put(data, dev.jax_device), dev)


def linspace(start, stop, num=50, endpoint=True, retstep=False, dtype=None,
             axis=0, device=None, ctx=None):
    check_x64_dtype(dtype)
    dev = _dev(device, ctx)
    if dtype is None:
        dtype = _default_float[0]
    r = jnp.linspace(start, stop, num, endpoint=endpoint, retstep=retstep,
                     dtype=dtype, axis=axis)
    if retstep:
        return from_jax(r[0], dev), float(r[1])
    return from_jax(r, dev)


def logspace(start, stop, num=50, endpoint=True, base=10.0, dtype=None,
             axis=0, device=None, ctx=None):
    check_x64_dtype(dtype)
    dev = _dev(device, ctx)
    if dtype is None:
        dtype = _default_float[0]
    return from_jax(jnp.logspace(start, stop, num, endpoint=endpoint,
                                 base=base, dtype=dtype, axis=axis), dev)


def eye(N, M=None, k=0, dtype=None, device=None, ctx=None):
    check_x64_dtype(dtype)
    dev = _dev(device, ctx)
    if dtype is None:
        dtype = _default_float[0]
    try:
        data = jnp.eye(N, M, k=k, dtype=dtype)
    except (TypeError, ValueError) as e:
        # negative/non-int dims are an MXNetError in the reference
        raise MXNetError(f"eye: {e}") from e
    return from_jax(data, dev)


def identity(n, dtype=None, device=None, ctx=None):
    return eye(n, dtype=dtype, device=device, ctx=ctx)


def tri(N, M=None, k=0, dtype=None):
    check_x64_dtype(dtype)
    return from_jax(jnp.tri(N, M, k, dtype or _default_float[0]), current_device())


def copy(a):
    return a.copy()


def meshgrid(*xi, **kwargs):
    vals = [x._data if isinstance(x, ndarray) else jnp.asarray(x) for x in xi]
    outs = jnp.meshgrid(*vals, **kwargs)
    dev = xi[0]._device if isinstance(xi[0], ndarray) else current_device()
    return [from_jax(o, dev) for o in outs]


def fromfunction(function, shape, dtype=None, **kwargs):
    check_x64_dtype(dtype)
    return array(_onp.fromfunction(function, shape, dtype=dtype or _default_float[0],
                                   **kwargs))


# -----------------------------------------------------------------------
# dynamic-shape ops: eager host-sync path (parity with reference blocking)
# -----------------------------------------------------------------------

def _host(a):
    if isinstance(a, ndarray):
        from ..ndarray.ndarray import is_tracer
        if is_tracer(a._data):
            raise MXNetError("data-dependent-shape op cannot run under jit "
                             "tracing; restructure with masks or run eagerly")
        return _onp.asarray(a._data), a._device
    return _onp.asarray(a), current_device()


def unique(ar, return_index=False, return_inverse=False, return_counts=False,
           axis=None):
    v, dev = _host(ar)
    r = _onp.unique(v, return_index=return_index, return_inverse=return_inverse,
                    return_counts=return_counts, axis=axis)
    if isinstance(r, tuple):
        return tuple(from_jax(jnp.asarray(x), dev) for x in r)
    return from_jax(jnp.asarray(r), dev)


def nonzero(a):
    v, dev = _host(a)
    return tuple(from_jax(jnp.asarray(x), dev) for x in _onp.nonzero(v))


def flatnonzero(a):
    v, dev = _host(a)
    return from_jax(jnp.asarray(_onp.flatnonzero(v)), dev)


def argwhere(a):
    v, dev = _host(a)
    return from_jax(jnp.asarray(_onp.argwhere(v)), dev)


def where(condition, x=None, y=None):
    if x is None and y is None:
        v, dev = _host(condition)
        return tuple(from_jax(jnp.asarray(i), dev) for i in _onp.where(v))
    arrs = [a for a in (condition, x, y) if isinstance(a, ndarray)]
    dev = arrs[0]._device if arrs else current_device()
    c = condition._data if isinstance(condition, ndarray) else condition
    fn_args = []
    positions = []
    vals = [c, x, y]
    for i, v in enumerate((condition, x, y)):
        if isinstance(v, ndarray):
            positions.append(i)
            fn_args.append(v)

    def call(*avals):
        vv = [c if not isinstance(condition, ndarray) else None,
              x if not isinstance(x, ndarray) else None,
              y if not isinstance(y, ndarray) else None]
        for p, av in zip(positions, avals):
            vv[p] = av
        return jnp.where(vv[0], vv[1], vv[2])

    return apply_op(call, fn_args, {}, name="where")


# -----------------------------------------------------------------------
# joining / splitting (sequence-arg ops)
# -----------------------------------------------------------------------

def _seq_op(jfn, name):
    def fn(seq, axis=0, out=None, **kw):
        seq = list(seq)
        dev = next((a._device for a in seq if isinstance(a, ndarray)),
                   current_device())
        arr_idx = [i for i, a in enumerate(seq) if isinstance(a, ndarray)]
        arrs = [seq[i] for i in arr_idx]

        def call(*avals):
            items = [a._data if isinstance(a, ndarray) else jnp.asarray(a)
                     for a in seq]
            for i, v in zip(arr_idx, avals):
                items[i] = v
            if axis is _NOAXIS:
                return jfn(items, **kw)
            return jfn(items, axis=axis, **kw)

        return _write_out(apply_op(call, arrs, {}, name=name), out)
    fn.__name__ = name
    return fn


_NOAXIS = object()
concatenate = _seq_op(jnp.concatenate, "concatenate")
stack = _seq_op(jnp.stack, "stack")


def _noaxis_seq_op(jfn, name):
    base = _seq_op(jfn, name)

    def fn(seq, out=None):
        return base(seq, axis=_NOAXIS, out=out)
    fn.__name__ = name
    return fn


vstack = _noaxis_seq_op(jnp.vstack, "vstack")
hstack = _noaxis_seq_op(jnp.hstack, "hstack")
dstack = _noaxis_seq_op(jnp.dstack, "dstack")
column_stack = _noaxis_seq_op(jnp.column_stack, "column_stack")


def split(ary, indices_or_sections, axis=0):
    if isinstance(indices_or_sections, ndarray):
        indices_or_sections = tuple(int(i) for i in indices_or_sections.asnumpy())
    outs = apply_op(
        lambda x: tuple(jnp.split(x, indices_or_sections, axis=axis)),
        (ary,), {}, name="split")
    return list(outs)


def array_split(ary, indices_or_sections, axis=0):
    outs = apply_op(
        lambda x: tuple(jnp.array_split(x, indices_or_sections, axis=axis)),
        (ary,), {}, name="array_split")
    return list(outs)


def hsplit(ary, n):
    return split(ary, n, axis=1 if ary.ndim > 1 else 0)


def vsplit(ary, n):
    return split(ary, n, axis=0)


def dsplit(ary, n):
    return split(ary, n, axis=2)


# -----------------------------------------------------------------------
# generated delegating wrappers
# -----------------------------------------------------------------------
_DELEGATE = [
    # elementwise math
    "add", "subtract", "multiply", "divide", "true_divide", "floor_divide",
    "mod", "remainder", "fmod", "power", "float_power", "negative", "positive",
    "absolute", "abs", "fabs", "sign", "rint", "conj", "conjugate",
    "exp", "expm1", "exp2", "log", "log2", "log10", "log1p",
    "sqrt", "cbrt", "square", "reciprocal",
    "sin", "cos", "tan", "arcsin", "arccos", "arctan", "arctan2",
    "sinh", "cosh", "tanh", "arcsinh", "arccosh", "arctanh",
    "degrees", "radians", "deg2rad", "rad2deg", "hypot",
    "maximum", "minimum", "fmax", "fmin", "clip",
    "ceil", "floor", "trunc", "round", "around", "fix",
    "logaddexp", "logaddexp2", "ldexp", "frexp", "copysign", "nextafter",
    "heaviside", "nan_to_num", "real", "imag", "angle", "i0", "sinc",
    "gcd", "lcm",
    # comparison / logic
    "equal", "not_equal", "less", "less_equal", "greater", "greater_equal",
    "logical_and", "logical_or", "logical_xor", "logical_not",
    "isfinite", "isinf", "isnan", "isneginf", "isposinf", "iscomplexobj",
    "isreal", "isrealobj", "iscomplex", "signbit",
    "array_equal", "array_equiv", "allclose", "isclose",
    # bitwise
    "bitwise_and", "bitwise_or", "bitwise_xor", "bitwise_not", "invert",
    "left_shift", "right_shift",
    # reductions
    "sum", "prod", "mean", "std", "var", "min", "max", "amin", "amax",
    "nansum", "nanprod", "nanmean", "nanstd", "nanvar", "nanmin", "nanmax",
    "all", "any", "ptp", "median", "nanmedian", "average", "quantile",
    "percentile", "nanquantile", "nanpercentile", "count_nonzero",
    "argmax", "argmin", "nanargmax", "nanargmin",
    "cumsum", "cumprod", "nancumsum", "nancumprod",
    "diff", "ediff1d", "gradient", "trapezoid",
    # linalg-ish top-level
    "dot", "vdot", "inner", "outer", "tensordot", "kron", "trace", "cross",
    "matmul", "einsum", "convolve", "correlate",
    # shape manipulation
    "reshape", "ravel", "transpose", "swapaxes", "moveaxis", "rollaxis",
    "expand_dims", "squeeze", "broadcast_to", "broadcast_arrays",
    "atleast_1d", "atleast_2d", "atleast_3d",
    "flip", "fliplr", "flipud", "rot90", "roll", "repeat", "tile",
    "append", "trim_zeros", "flipud",
    "tril", "triu", "diag", "diagflat", "diagonal", "extract",
    # indexing / selection
    "take", "take_along_axis", "put_along_axis", "choose", "compress",
    "searchsorted", "digitize", "select", "piecewise", "indices",
    "unravel_index", "ravel_multi_index", "tril_indices", "triu_indices",
    "diag_indices",
    # sorting
    "sort", "argsort", "lexsort", "partition", "argpartition",
    # statistics
    "bincount", "histogram", "histogram2d", "histogramdd", "histogram_bin_edges",
    "corrcoef", "cov",
    # misc
    "interp", "pad", "flatnonzero", "vander", "ones_like",
    "result_type", "promote_types", "shape", "ndim", "size", "iscomplexobj",
    "insert", "delete", "resize", "setdiff1d", "union1d", "intersect1d",
    "isin", "in1d", "fill_diagonal",
    # long-tail NumPy-compat surface (reference serves these via its onp
    # fallback table, `python/mxnet/numpy/fallback.py:25`; jnp implements
    # them natively so they stay on-device here)
    "apply_along_axis", "apply_over_axes", "divmod", "ix_", "modf",
    "packbits", "unpackbits", "poly", "polyadd", "polyder", "polydiv",
    "polyfit", "polyint", "polymul", "polysub", "polyval", "roots",
    "setxor1d", "spacing", "tril_indices_from", "unwrap",
]

_g = globals()
# jnp.fix is deprecated in jax 0.9 (removed in 0.10): same rounding
# toward zero as trunc
fix = wrap_fn(jnp.trunc, "fix")
for _name in _DELEGATE:
    if _name in _g:  # don't clobber custom impls
        continue
    _j = getattr(jnp, _name, None)
    if _j is None:
        continue
    _g[_name] = wrap_fn(_j, _name)

# numpy-only fallbacks for names jnp lacks
for _name in _DELEGATE:
    if _name not in _g:
        _nf = getattr(_onp, _name, None)
        if _nf is None:
            continue

        def _mk(nf, nm):
            def fn(*args, **kwargs):
                conv = [a.asnumpy() if isinstance(a, ndarray) else a for a in args]
                r = nf(*conv, **kwargs)
                if isinstance(r, tuple):
                    return tuple(from_jax(jnp.asarray(x), current_device())
                                 if isinstance(x, _onp.ndarray) else x for x in r)
                if isinstance(r, _onp.ndarray):
                    return from_jax(jnp.asarray(r), current_device())
                return r
            fn.__name__ = nm
            return fn
        _g[_name] = _mk(_nf, _name)


# meta queries return plain Python values, not wrapped arrays
def shape(a):
    return tuple(a.shape) if hasattr(a, "shape") else _onp.shape(a)


def ndim(a):
    return a.ndim if hasattr(a, "ndim") else _onp.ndim(a)


def size(a, axis=None):
    if axis is not None:
        return (a.shape if hasattr(a, "shape") else _onp.shape(a))[axis]
    return int(a.size) if hasattr(a, "size") else _onp.size(a)


def result_type(*args):
    return jnp.result_type(*[a._data if isinstance(a, ndarray) else a
                             for a in args])


def promote_types(type1, type2):
    return jnp.promote_types(type1, type2)


def iscomplexobj(x):
    return bool(jnp.iscomplexobj(x._data if isinstance(x, ndarray) else x))


def put_along_axis(arr, indices, values, axis):
    """In-place scatter (numpy semantics). Routed through apply_op +
    _rebind like __setitem__ so the autograd tape records the overwrite
    (SURVEY.md §7 mutability mapping)."""
    idx = indices._data if isinstance(indices, ndarray) \
        else jnp.asarray(indices)
    if isinstance(values, ndarray):
        out = apply_op(
            lambda x, v: jnp.put_along_axis(x, idx, v.astype(x.dtype),
                                            axis=axis, inplace=False),
            (arr, values), {}, name="put_along_axis")
    else:
        vv = jnp.asarray(values)
        out = apply_op(
            lambda x: jnp.put_along_axis(x, idx, vv.astype(x.dtype),
                                         axis=axis, inplace=False),
            (arr,), {}, name="put_along_axis")
    arr._rebind(out)


def fill_diagonal(a, val, wrap=False):
    if isinstance(val, ndarray):
        out = apply_op(
            lambda x, v: jnp.fill_diagonal(x, v.astype(x.dtype), wrap=wrap,
                                           inplace=False),
            (a, val), {}, name="fill_diagonal")
    else:
        out = apply_op(
            lambda x: jnp.fill_diagonal(x, val, wrap=wrap, inplace=False),
            (a,), {}, name="fill_diagonal")
    a._rebind(out)


def may_share_memory(a, b, max_work=None):
    return False  # functional arrays never alias at the Python level


shares_memory = may_share_memory


def bfloat16_cast(a):
    return a.astype(jnp.bfloat16)


# NumPy-compat aliases for names modern NumPy/jnp renamed or dropped
# (reference fallback table `python/mxnet/numpy/fallback.py:25`)
trapz = wrap_fn(jnp.trapezoid, "trapz")


def msort(a):
    """Sort along the first axis (removed in NumPy 2.0; kept for parity)."""
    return sort(a, axis=0)


def alltrue(a, axis=None, **kwargs):
    return all(a, axis=axis, **kwargs)


def min_scalar_type(a):
    return _onp.min_scalar_type(a.asnumpy() if isinstance(a, ndarray) else a)


# -----------------------------------------------------------------------
# submodules
# -----------------------------------------------------------------------
from . import linalg  # noqa: E402
from . import random  # noqa: E402
from . import fft  # noqa: E402

ndarray = ndarray  # re-export


def get_include():
    return _onp.get_include()


# -----------------------------------------------------------------------
# Array-API aliases + tail utilities (parity: the reference numpy
# surface exports these names — `python/mxnet/numpy/multiarray.py`
# __all__ / function_base.py; the aliases are NumPy 2.0 spellings)
# -----------------------------------------------------------------------
acos = arccos                 # noqa: F821
acosh = arccosh               # noqa: F821
asin = arcsin                 # noqa: F821
asinh = arcsinh               # noqa: F821
atan = arctan                 # noqa: F821
atan2 = arctan2               # noqa: F821
atanh = arctanh               # noqa: F821
bitwise_invert = invert       # noqa: F821
bitwise_left_shift = left_shift   # noqa: F821
bitwise_right_shift = right_shift  # noqa: F821
concat = concatenate
permute_dims = transpose      # noqa: F821
pow = power                   # noqa: F821
round_ = round                # noqa: F821
row_stack = vstack


def _window(jfn):
    def fn(M, dtype=None, device=None, ctx=None):
        dev = _dev(device, ctx)
        data = jfn(M).astype(dtype or _default_float[0])
        return from_jax(jax.device_put(data, dev.jax_device), dev)
    return fn


blackman = _window(jnp.blackman)
hamming = _window(jnp.hamming)
hanning = _window(jnp.hanning)


def diag_indices_from(arr):
    if arr.ndim < 2:
        raise MXNetError("diag_indices_from needs an array of at least "
                         f"2 dimensions, got {arr.ndim}-d")
    n = arr.shape[0]
    # NB: `any` here is mx.np's reduction (module shadowing) — use set()
    if len(set(arr.shape)) != 1:
        raise MXNetError("diag_indices_from needs a square array, got "
                         f"shape {arr.shape}")
    i = arange(n, dtype=_onp.int32)
    return tuple(i for _ in range(arr.ndim))


def triu_indices_from(arr, k=0):
    if arr.ndim != 2:
        raise MXNetError(f"triu_indices_from needs a 2-d array, got "
                         f"{arr.ndim}-d")
    dev = arr._device if isinstance(arr, ndarray) else current_device()
    return tuple(from_jax(jax.device_put(i, dev.jax_device), dev)
                 for i in jnp.triu_indices(arr.shape[0], k, arr.shape[1]))


def from_dlpack(x):
    """Import an array through the DLPack protocol (zero-copy where the
    producer's device is compatible with XLA's); delegates to mx.dlpack
    (which also adapts legacy raw capsules)."""
    from ..dlpack import from_dlpack as _fd
    return _fd(x)


def genfromtxt(*args, **kwargs):
    """numpy.genfromtxt -> device array (host parse, then transfer)."""
    return array(_onp.genfromtxt(*args, **kwargs))


def set_printoptions(*args, **kwargs):
    """Applies to the host repr (asnumpy()-backed printing)."""
    _onp.set_printoptions(*args, **kwargs)


_broadcast_to_gen = broadcast_to  # generated jnp alias


def broadcast_to(array, shape):
    """`np.broadcast_to` with the reference's npx dialect: a -2 entry
    copies the corresponding input dim (aligned from the RIGHT, like
    broadcasting itself)."""
    import builtins
    if isinstance(shape, int):
        shape = (shape,)
    if builtins.any(d == -2 for d in shape):
        in_shape = array.shape
        off = len(shape) - len(in_shape)
        resolved = []
        for i, d in enumerate(shape):
            if d == -2:
                if i - off < 0:
                    # reference NumpyBroadcastToShape: a -2 beyond the
                    # input's rank cannot be resolved
                    raise MXNetError(
                        "broadcast_to: the objective shape for "
                        "broadcasting array must be known; -2 at dim "
                        f"{i} has no corresponding input dim")
                resolved.append(in_shape[i - off])
            else:
                resolved.append(d)
        shape = tuple(resolved)
    return _broadcast_to_gen(array, shape)


_sum_gen = sum   # generated jnp alias
_mean_gen = mean


def _acc_f16(jfn_name, x, axis, dtype, out, keepdims, where=None,
             initial=None):
    """f16 reductions ACCUMULATE at f32 then cast (mshadow's acc-type
    rule, pinned by test_np_sum's acc_type expectations — run it with
    MXTPU_RUN_PARITY_WIP=1); other dtypes pass through the generated
    wrapper untouched (where=/initial= included)."""
    want = dtype
    if dtype is None and getattr(x, "dtype", None) is not None \
            and jnp.dtype(x.dtype) == jnp.float16:
        want = jnp.float16
    if want is not None and jnp.dtype(want) == jnp.float16:
        arrs = [x]
        has_where = where is not None
        if has_where:
            arrs.append(where)

        def fn(v, *maybe_w):
            kw = {"axis": axis, "keepdims": keepdims}
            if has_where:
                kw["where"] = maybe_w[0]
            r = getattr(jnp, jfn_name)(v.astype(jnp.float32), **kw)
            if initial is not None and jfn_name == "sum":
                r = r + jnp.asarray(initial, jnp.float32)
            # dtype=None means "same as input" — and the input seen HERE
            # may have been widened by the AMP cast hook (sum/mean sit on
            # the fp32 deny list), in which case the result must stay
            # wide; only an explicit dtype=float16 pins the output
            out_dt = jnp.float16 if dtype is not None else v.dtype
            return r.astype(out_dt)
        return _write_out(apply_op(fn, tuple(arrs), {}, name=jfn_name), out)
    gen = _sum_gen if jfn_name == "sum" else _mean_gen
    kw = {"axis": axis, "dtype": dtype, "out": out, "keepdims": keepdims}
    if where is not None:
        kw["where"] = where
    if initial is not None and jfn_name == "sum":
        kw["initial"] = initial
    return gen(x, **kw)


def sum(a, axis=None, dtype=None, out=None, keepdims=False, where=None,  # noqa: A001
        initial=None):
    return _acc_f16("sum", a, axis, dtype, out, keepdims, where, initial)


def mean(a, axis=None, dtype=None, out=None, keepdims=False, where=None):
    return _acc_f16("mean", a, axis, dtype, out, keepdims, where)
