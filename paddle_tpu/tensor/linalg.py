"""Linear algebra ops — parity with python/paddle/tensor/linalg.py.
Backed by jnp.linalg / lax.linalg; on TPU, decompositions run through XLA's
native linalg lowering.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from ..core.tensor import Tensor, apply_op, to_tensor

__all__ = [
    "norm", "cholesky", "qr", "svd", "inv", "det", "slogdet", "eig", "eigh",
    "eigvals", "eigvalsh", "solve", "triangular_solve", "lstsq", "matrix_power",
    "pinv", "cross", "t", "dist", "cond", "matrix_rank", "mv", "histogram",
    "bincount", "cov", "corrcoef",
]


def _t(x):
    return x if isinstance(x, Tensor) else to_tensor(x)


def norm(x, p="fro", axis=None, keepdim=False, name=None):
    def f(a):
        if axis is None:
            flat = a.reshape(-1)
            if p == "fro" or p == 2:
                return jnp.sqrt(jnp.sum(flat * flat))
            if p == 1:
                return jnp.sum(jnp.abs(flat))
            if p == np.inf or p == "inf":
                return jnp.max(jnp.abs(flat))
            if p == -np.inf:
                return jnp.min(jnp.abs(flat))
            return jnp.sum(jnp.abs(flat) ** p) ** (1.0 / p)
        ax = tuple(axis) if isinstance(axis, (list, tuple)) else int(axis)
        if isinstance(ax, tuple) and p == "fro":
            return jnp.sqrt(jnp.sum(a * a, axis=ax, keepdims=keepdim))
        if p == np.inf or p == "inf":
            return jnp.max(jnp.abs(a), axis=ax, keepdims=keepdim)
        if p == -np.inf:
            return jnp.min(jnp.abs(a), axis=ax, keepdims=keepdim)
        if p == 0:
            return jnp.sum((a != 0).astype(a.dtype), axis=ax, keepdims=keepdim)
        return jnp.linalg.norm(a, ord=p if p != "fro" else None, axis=ax, keepdims=keepdim)

    return apply_op(f, _t(x))


def cholesky(x, upper=False, name=None):
    def f(a):
        l = jnp.linalg.cholesky(a)
        return jnp.swapaxes(l, -1, -2) if upper else l

    return apply_op(f, _t(x))


def qr(x, mode="reduced", name=None):
    return apply_op(lambda a: tuple(jnp.linalg.qr(a, mode=mode)), _t(x), multi_out=True)


def svd(x, full_matrices=False, name=None):
    # SVD-family lowerings are LAPACK-style iterations XLA:TPU handles
    # poorly — concrete eager calls on TPU route to the host CPU
    # backend like ``eig`` below.
    # The eager-TPU host fallback routes THROUGH apply_op (not around it,
    # which returned grad-less, unrecorded results): the op function
    # itself picks host CPU only for concrete non-grad values, so
    # static-program recording captures the op and replay/jit traces keep
    # the native lowering. When gradients are required, apply_op's vjp
    # trace sees tracers and also takes the native branch — grads flow
    # (the host fallback is unreachable there: a pure_callback SVD would
    # silently detach the graph instead).
    def f(a):
        from ..core.tensor import _is_tracer

        if not _is_tracer(a) and jax.default_backend() == "tpu":
            cpu = jax.devices("cpu")[0]
            with jax.default_device(cpu):
                res = jnp.linalg.svd(jax.device_put(a, cpu),
                                     full_matrices=full_matrices)
            return tuple(jax.device_put(np.asarray(r)) for r in res)
        return tuple(jnp.linalg.svd(a, full_matrices=full_matrices))

    return apply_op(f, _t(x), multi_out=True, op_name="svd")


def inv(x, name=None):
    return apply_op(jnp.linalg.inv, _t(x))


def det(x, name=None):
    return apply_op(jnp.linalg.det, _t(x))


def slogdet(x, name=None):
    def f(a):
        sign, logdet = jnp.linalg.slogdet(a)
        return jnp.stack([sign, logdet])

    return apply_op(f, _t(x))


def eig(x, name=None):
    # CPU-only in XLA; run via callback on host for parity
    arr = _t(x).numpy()
    w, v = np.linalg.eig(arr)
    from ..core.tensor import wrap_raw

    return wrap_raw(jnp.asarray(w)), wrap_raw(jnp.asarray(v))


def eigh(x, UPLO="L", name=None):
    return apply_op(lambda a: tuple(jnp.linalg.eigh(a, UPLO=UPLO)), _t(x), multi_out=True)


def eigvals(x, name=None):
    arr = _t(x).numpy()
    from ..core.tensor import wrap_raw

    return wrap_raw(jnp.asarray(np.linalg.eigvals(arr)))


def eigvalsh(x, UPLO="L", name=None):
    return apply_op(lambda a: jnp.linalg.eigvalsh(a, UPLO=UPLO), _t(x))


def solve(x, y, name=None):
    return apply_op(jnp.linalg.solve, _t(x), _t(y))


def triangular_solve(x, y, upper=True, transpose=False, unitriangular=False, name=None):
    def f(a, b):
        return jax.scipy.linalg.solve_triangular(
            a, b, lower=not upper, trans=1 if transpose else 0, unit_diagonal=unitriangular
        )

    return apply_op(f, _t(x), _t(y))


def lstsq(x, y, rcond=None, driver=None, name=None):
    def f(a, b):
        sol, res, rank, sv = jnp.linalg.lstsq(a, b, rcond=rcond)
        return sol, res, rank.astype(np.int64), sv

    return apply_op(f, _t(x), _t(y), multi_out=True)


def matrix_power(x, n, name=None):
    return apply_op(lambda a: jnp.linalg.matrix_power(a, n), _t(x))


def pinv(x, rcond=1e-15, hermitian=False, name=None):
    return apply_op(lambda a: jnp.linalg.pinv(a, rtol=rcond, hermitian=hermitian), _t(x))


def cross(x, y, axis=9, name=None):
    def f(a, b):
        ax = axis
        if ax == 9:  # paddle default: first axis with dim 3
            ax = next(i for i, s in enumerate(a.shape) if s == 3)
        return jnp.cross(a, b, axis=ax)

    return apply_op(f, _t(x), _t(y))


def t(x, name=None):
    x = _t(x)
    if x.ndim < 2:
        return x.clone()
    return apply_op(lambda a: jnp.swapaxes(a, -1, -2), x)


def dist(x, y, p=2, name=None):
    def f(a, b):
        d = (a - b).reshape(-1)
        if p == 0:
            return jnp.sum((d != 0).astype(a.dtype))
        if p == np.inf:
            return jnp.max(jnp.abs(d))
        if p == -np.inf:
            return jnp.min(jnp.abs(d))
        return jnp.sum(jnp.abs(d) ** p) ** (1.0 / p)

    return apply_op(f, _t(x), _t(y))


def cond(x, p=None, name=None):
    return apply_op(lambda a: jnp.linalg.cond(a, p=p), _t(x))


def matrix_rank(x, tol=None, hermitian=False, name=None):
    return apply_op(
        lambda a: jnp.linalg.matrix_rank(a, tol=tol).astype(np.int64), _t(x)
    )


def mv(x, vec, name=None):
    return apply_op(jnp.matmul, _t(x), _t(vec))


def histogram(input, bins=100, min=0, max=0, name=None):
    arr = _t(input).numpy().reshape(-1)
    lo, hi = (min, max) if (min != 0 or max != 0) else (arr.min(), arr.max())
    h, _ = np.histogram(arr, bins=bins, range=(lo, hi))
    from ..core.tensor import wrap_raw

    return wrap_raw(jnp.asarray(h.astype(np.int64)))


def bincount(x, weights=None, minlength=0, name=None):
    arr = _t(x).numpy()
    w = _t(weights).numpy() if weights is not None else None
    from ..core.tensor import wrap_raw

    return wrap_raw(jnp.asarray(np.bincount(arr, weights=w, minlength=minlength)))


def cov(x, rowvar=True, ddof=True, fweights=None, aweights=None, name=None):
    def f(a):
        return jnp.cov(
            a,
            rowvar=rowvar,
            ddof=1 if ddof else 0,
            fweights=None if fweights is None else jnp.asarray(fweights),
            aweights=None if aweights is None else jnp.asarray(aweights),
        )

    return apply_op(f, _t(x))


def corrcoef(x, rowvar=True, name=None):
    return apply_op(lambda a: jnp.corrcoef(a, rowvar=rowvar), _t(x))
