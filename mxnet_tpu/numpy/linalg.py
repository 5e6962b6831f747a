"""`mx.np.linalg` — linear algebra (parity: `src/operator/numpy/linalg/`
kernels and the `python/mxnet/numpy/linalg.py` surface).

All kernels are XLA's native decompositions (MXNet used LAPACK/cuSOLVER).
Where the reference's semantics diverge from raw `jnp.linalg` the adapters
below restore them (behavior pinned by the ported reference tests in
`tests/parity/test_numpy_op_linalg.py`):

- string ords ``'inf'/'-inf'`` (numpy only takes ``np.inf``),
- ``svd`` returns the reduced (UT, L, V) triple of `linalg_gesvd`
  (`src/operator/tensor/la_op.h`): UT ``(..., m, m)``, L ``(..., m)``,
  V ``(..., m, n)`` — i.e. ``full_matrices=False``, which also keeps the
  decomposition differentiable,
- ``eigh/eigvalsh/cholesky`` take ``upper=`` (bool), not numpy's UPLO,
- ``matrix_rank`` takes ``hermitian=``; ``lstsq`` implements numpy's
  legacy ``rcond='warn'``/-1 contract including empty residuals,
- ``vector_norm``/``matrix_norm`` follow the reference's axis semantics
  (tuple axes flattened to one vector axis / required 2-tuple with
  ``ValueError`` otherwise).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ._wrap import wrap_fn
from ..ndarray.ndarray import ndarray as _ndarray

_ALIAS_NAMES = [
    "det", "eig", "eigvals", "cholesky", "pinv",
    "matrix_power", "multi_dot", "cond",
    "cross", "diagonal", "outer", "tensordot", "trace", "vecdot", "matmul",
    "matrix_transpose", "slogdet",
]

_g = globals()
for _name in _ALIAS_NAMES:
    _g[_name] = wrap_fn(getattr(jnp.linalg, _name), _name)


_matrix_transpose_w = _g["matrix_transpose"]


def matrix_transpose(x):
    # reference front end raises ValueError (not MXNetError) on sub-2D
    # input — validation precedes dispatch
    ndim = getattr(x, "ndim", None)
    if ndim is None:
        ndim = jnp.ndim(x)
    if ndim < 2:
        raise ValueError(
            f"matrix_transpose requires at least 2 dimensions; got {ndim=}")
    return _matrix_transpose_w(x)


def _map_ord(ord):
    if ord == "inf":
        return jnp.inf
    if ord == "-inf":
        return -jnp.inf
    return ord


def _norm_j(x, ord=None, axis=None, keepdims=False):
    return jnp.linalg.norm(x, ord=_map_ord(ord), axis=axis,
                           keepdims=keepdims)


norm = wrap_fn(_norm_j, "norm")


def _vector_norm_j(x, ord=None, axis=None, keepdims=False):
    # reference semantics (np_norm_op vector path, pinned by
    # test_np_linalg_vector_norm): a tuple axis moves those axes to the
    # FRONT and flattens them into one vector axis; keepdims then applies
    # to the flattened array — so the reduced dims collapse to a single
    # leading 1, they are NOT reinserted in place
    ord = 2 if ord is None else _map_ord(ord)
    if axis is None:
        return jnp.linalg.norm(x.reshape(-1), ord=ord, axis=0,
                               keepdims=keepdims)
    if isinstance(axis, tuple):
        red = tuple(a % x.ndim for a in axis)
        rest = tuple(i for i in range(x.ndim) if i not in red)
        moved = jnp.transpose(x, red + rest)
        flat = moved.reshape((-1,) + tuple(x.shape[i] for i in rest))
        return jnp.linalg.norm(flat, ord=ord, axis=0, keepdims=keepdims)
    return jnp.linalg.norm(x, ord=ord, axis=axis, keepdims=keepdims)


_vector_norm_w = wrap_fn(_vector_norm_j, "vector_norm")


def vector_norm(x, ord=None, axis=None, keepdims=False):
    return _vector_norm_w(x, ord=ord, axis=axis, keepdims=keepdims)


def matrix_norm(x, ord="fro", axis=(-2, -1), keepdims=False):
    # the reference raises ValueError from the python front end when axis
    # is not a 2-tuple (np_norm_op matrix path) — BEFORE dispatch, so it
    # must not surface as MXNetError
    if not isinstance(axis, tuple) or len(axis) != 2:
        raise ValueError(
            f"matrix_norm requires a 2-tuple axis; got {axis!r}")
    return norm(x, ord=ord, axis=axis, keepdims=keepdims)


def _refined_solve(a, b2):
    """LAPACK-grade solve: the ported reference tests compare f32
    results/gradients at rtol 1e-5 — achievable only if our answer is
    the correctly-rounded one.  With x64 available (CPU parity runs) the
    f32 system is solved in f64 and rounded once; otherwise (TPU jit,
    x64 off) LU + two iterative-refinement steps."""
    if a.dtype == jnp.float32 and jax.config.jax_enable_x64:
        x = jnp.linalg.solve(a.astype(jnp.float64), b2.astype(jnp.float64))
        return x.astype(jnp.float32)
    x = jnp.linalg.solve(a, b2)
    for _ in range(2):
        x = x + jnp.linalg.solve(a, b2 - a @ x)
    return x


@jax.custom_vjp
def _solve2d(a, b2):
    return _refined_solve(a, b2)


def _solve2d_fwd(a, b2):
    x = _refined_solve(a, b2)
    return x, (a, x)


def _solve2d_bwd(res, cot):
    # the textbook adjoint (the formula the reference's backward kernel
    # implements, la_op.h solve backward): gb = A^-T dX, gA = -gb X^T —
    # evaluated with the refined solver so it carries LAPACK-grade
    # accuracy like the forward
    a, x = res
    at = jnp.swapaxes(a, -1, -2)
    gb = _refined_solve(at, cot)
    ga = -gb @ jnp.swapaxes(x, -1, -2)
    return ga, gb


_solve2d.defvjp(_solve2d_fwd, _solve2d_bwd)


def _solve_j(a, b):
    vec = b.ndim == a.ndim - 1
    b2 = b[..., None] if vec else b
    x = _solve2d(a, b2)
    return x[..., 0] if vec else x


solve = wrap_fn(_solve_j, "solve")


def _inv_j(a):
    eye = jnp.broadcast_to(
        jnp.eye(a.shape[-1], dtype=a.dtype), a.shape)
    return _solve2d(a, eye)


inv = wrap_fn(_inv_j, "inv")


def _tensorinv_j(a, ind=2):
    # numpy's tensorinv (numpy/linalg/_linalg.py), on the refined solver
    import math as _math
    oldshape = a.shape
    invshape = oldshape[ind:] + oldshape[:ind]
    prod = _math.prod(oldshape[ind:])
    ia = _inv_j(a.reshape(prod, -1))
    return ia.reshape(*invshape)


tensorinv = wrap_fn(_tensorinv_j, "tensorinv")


def _tensorsolve_j(a, b, axes=None):
    # numpy's own algorithm (numpy/linalg/_linalg.py tensorsolve),
    # including the degenerate all-ones/0-d shapes jnp rejects
    if axes is not None:
        allaxes = list(range(a.ndim))
        for ax in axes:
            allaxes.remove(ax)
            allaxes.append(ax)
        a = jnp.transpose(a, allaxes)
    # the reference's shape rule (np_tensorsolve-inl.h, pinned by
    # test_np_linalg_tensorsolve) is literally the Python slice
    # a_trans.shape[-(a.ndim - b.ndim):] — INCLUDING the -0 case, where
    # a.ndim == b.ndim yields the WHOLE (all-ones) a-shape, and
    # a.ndim < b.ndim yields () — both beyond numpy's own contract
    q_shape = tuple(a.shape)[-(a.ndim - b.ndim):] if a.ndim != b.ndim \
        else tuple(a.shape)
    import math as _math
    prod_q = _math.prod(q_shape)
    a2 = a.reshape(prod_q, prod_q)
    x = _solve_j(a2, b.reshape(prod_q))
    return x.reshape(q_shape)


tensorsolve = wrap_fn(_tensorsolve_j, "tensorsolve")


def _copyltu(m):
    """tril(M) + strict-tril(M)^T — the reference's copyltu helper
    (la_op.h), the symmetrization QR/Cholesky backward needs."""
    low = jnp.tril(m)
    strict = jnp.tril(m, -1)
    return low + jnp.swapaxes(strict, -1, -2)


def _tsolve_rt(x, r):
    """x @ r^{-T} for upper-triangular r, via triangular solve."""
    from jax.scipy.linalg import solve_triangular
    return jnp.swapaxes(
        solve_triangular(r, jnp.swapaxes(x, -1, -2), lower=False), -1, -2)


@jax.custom_vjp
def _qr2(a):
    q, r = jnp.linalg.qr(a, mode="reduced")
    return (q, r)


def _qr2_fwd(a):
    q, r = jnp.linalg.qr(a, mode="reduced")
    return (q, r), (q, r)


def _qr2_bwd(res, cot):
    # the reference's qr backward (la_op-inl.h qr_backward), BOTH shape
    # regimes — JAX's own QR JVP is unimplemented for m < n:
    #   m >= n: dA = (dQ + Q copyltu(M)) R^-T,  M = R dR^T - dQ^T Q
    #   m <  n: split R = [U | V], A = [X | Y];  dQ' = dQ + Y dV^T;
    #           dX = (dQ' + Q copyltu(M)) U^-T, M = U dU^T - dQ'^T Q;
    #           dY = Q dV;  dA = [dX | dY]
    q, r = res
    dq, dr = cot
    m, n = q.shape[-2], r.shape[-1]
    qt = jnp.swapaxes(q, -1, -2)
    if m >= n:
        mm = r @ jnp.swapaxes(dr, -1, -2) - jnp.swapaxes(dq, -1, -2) @ q
        da = _tsolve_rt(dq + q @ _copyltu(mm), r)
        return (da,)
    u = r[..., :, :m]
    v = r[..., :, m:]
    du = dr[..., :, :m]
    dv = dr[..., :, m:]
    y = q @ v
    dq_ = dq + y @ jnp.swapaxes(dv, -1, -2)
    mm = u @ jnp.swapaxes(du, -1, -2) - jnp.swapaxes(dq_, -1, -2) @ q
    dx = _tsolve_rt(dq_ + q @ _copyltu(mm), u)
    dy = q @ dv
    return (jnp.concatenate([dx, dy], axis=-1),)


_qr2.defvjp(_qr2_fwd, _qr2_bwd)
_qr_reduced_w = wrap_fn(_qr2, "qr")
_qr_other_w = wrap_fn(jnp.linalg.qr, "qr")


def qr(a, mode="reduced"):
    if mode in ("reduced", "r"):
        out = _qr_reduced_w(a)
        return out[1] if mode == "r" else out
    return _qr_other_w(a, mode=mode)


def _svd_j(a):
    u, s, vh = jnp.linalg.svd(a, full_matrices=False)
    return (u, s, vh)


svd = wrap_fn(_svd_j, "svd")


def _svdvals_j(a):
    return jnp.linalg.svd(a, compute_uv=False)


svdvals = wrap_fn(_svdvals_j, "svdvals")


def _eigh_j(a, upper=False):
    w, v = jnp.linalg.eigh(a, UPLO="U" if upper else "L")
    return (w, v)


_eigh_w = wrap_fn(_eigh_j, "eigh")


def eigh(a, UPLO=None, upper=None):
    if UPLO is not None:
        upper = (UPLO == "U")
    return _eigh_w(a, upper=bool(upper))


def _eigvalsh_j(a, upper=False):
    return jnp.linalg.eigvalsh(a, UPLO="U" if upper else "L")


_eigvalsh_w = wrap_fn(_eigvalsh_j, "eigvalsh")


def eigvalsh(a, UPLO=None, upper=None):
    if UPLO is not None:
        upper = (UPLO == "U")
    return _eigvalsh_w(a, upper=bool(upper))


def _matrix_rank_j(M, tol=None, hermitian=False):
    if M.ndim < 2:
        return (jnp.any(M != 0)).astype(jnp.int64
                                        if jax.config.jax_enable_x64
                                        else jnp.int32)
    if hermitian:
        s = jnp.abs(jnp.linalg.eigvalsh(M))
    else:
        s = jnp.linalg.svd(M, compute_uv=False)
    if tol is None:
        tol = s.max(axis=-1, keepdims=True) * max(M.shape[-2:]) \
            * jnp.finfo(s.dtype).eps
    else:
        tol = jnp.asarray(tol)[..., None]
    return jnp.count_nonzero(s > tol, axis=-1)


matrix_rank = wrap_fn(_matrix_rank_j, "matrix_rank")


def _lstsq_j(a, b, rcond=None):
    # numpy contract (the reference routes straight to numpy.linalg.lstsq
    # semantics, np_lstsq-inl.h): rcond 'warn' == legacy -1 (machine
    # precision); residuals are EMPTY unless a has full rank and m > n
    m, n = a.shape[-2], a.shape[-1]
    b2 = b[:, None] if b.ndim == 1 else b
    eps = jnp.finfo(a.dtype).eps
    if rcond is None:
        rc = eps * max(m, n)
    elif isinstance(rcond, str) and rcond == "warn":
        rc = eps
    elif not (0 <= float(rcond) < 1):
        # empirically pinned against this environment's numpy (and the
        # ported reference test's rcond ~ U(100,200) cases): rcond >= 1
        # or < 0 behaves as machine precision (rank stays full), NOT as
        # an all-zeroing cutoff
        rc = eps
    else:
        rc = rcond
    # numpy's own SVD algorithm (gelsd-equivalent), so cutoff/rank agree
    # with onp.linalg.lstsq for ANY rcond (jnp.linalg.lstsq clamps
    # differently for rcond > 1)
    u, s, vh = jnp.linalg.svd(a, full_matrices=False)
    cutoff = jnp.asarray(rc, s.dtype) * (s.max() if s.size else
                                         jnp.asarray(0, s.dtype))
    mask = s > cutoff
    s_inv = jnp.where(mask, 1.0 / jnp.where(mask, s, 1.0), 0.0)
    x = vh.T.conj() @ (s_inv[:, None] * (u.T.conj() @ b2))
    rank = jnp.sum(mask).astype(jnp.int32)
    n_rhs = b2.shape[-1]
    resid = jnp.where(jnp.logical_and(rank == n, m > n),
                      jnp.sum(jnp.abs(b2 - a @ x) ** 2, axis=0),
                      jnp.full((n_rhs,), jnp.nan, a.dtype))
    if b.ndim == 1:
        x = x[..., 0]
    return x, resid, rank, s


def lstsq(a, b, rcond="warn"):
    out = _lstsq_w(a, b, rcond=rcond)
    x, resid, rank, s = out
    # rank is static per input on CPU-sync read; numpy returns shape-(0,)
    # residuals for rank-deficient / square / underdetermined systems —
    # a shape decision, so it must happen OUTSIDE jit on concrete values
    import numpy as _onp
    m, n = (a.shape[-2], a.shape[-1])
    full = int(_onp.asarray(rank.asnumpy() if hasattr(rank, "asnumpy")
                            else rank)) == n
    if not (full and m > n):
        from ..ndarray.ndarray import from_jax
        resid = from_jax(jnp.empty((0,), resid.dtype if hasattr(
            resid, "dtype") else jnp.float32))
    return x, resid, rank, s


_lstsq_w = wrap_fn(_lstsq_j, "lstsq")

__all__ = [
    "norm", "inv", "det", "slogdet", "svd", "svdvals", "eig", "eigh",
    "eigvals", "eigvalsh", "qr", "cholesky", "solve", "lstsq", "pinv",
    "matrix_rank", "matrix_power", "multi_dot", "tensorinv", "tensorsolve",
    "cond", "matrix_norm", "vector_norm", "cross", "diagonal", "outer",
    "tensordot", "trace", "vecdot", "matmul", "matrix_transpose",
]
