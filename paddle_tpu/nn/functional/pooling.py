"""Pooling functionals — parity with python/paddle/nn/functional/pooling.py.
Built on ``lax.reduce_window``, XLA's native windowed reduction (replaces the
reference's pool_op.cu / cuDNN pooling).
"""
from __future__ import annotations

import functools
import itertools
import os

import jax
import jax.numpy as jnp
import numpy as np

from ...core.enforce import enforce
from ...core.tensor import Tensor, apply_op, to_tensor

__all__ = [
    "avg_pool1d", "avg_pool2d", "avg_pool3d", "max_pool1d", "max_pool2d",
    "max_pool3d", "adaptive_avg_pool1d", "adaptive_avg_pool2d",
    "adaptive_avg_pool3d", "adaptive_max_pool1d", "adaptive_max_pool2d",
    "adaptive_max_pool3d",
]


def _t(x):
    return x if isinstance(x, Tensor) else to_tensor(x)


def _norm(v, n):
    if isinstance(v, (int, np.integer)):
        return (int(v),) * n
    return tuple(int(i) for i in v)


def _pool(x, kernel, stride, padding, n, op, channel_last, ceil_mode=False,
          exclusive=True):
    kernel = _norm(kernel, n)
    stride = _norm(stride if stride is not None else kernel, n)
    if isinstance(padding, str):
        pad_str = padding.upper()
        pads = None
    else:
        p = _norm(padding, n) if not isinstance(padding, (list,)) or all(
            isinstance(i, (int, np.integer)) for i in padding
        ) else None
        if p is None:
            pads = [tuple(int(i) for i in pr) for pr in padding]
        else:
            pads = [(int(i), int(i)) for i in p]
        pad_str = None

    def f(a):
        nd = a.ndim
        if channel_last:
            window = (1,) + kernel + (1,)
            strides = (1,) + stride + (1,)
            spatial = list(range(1, nd - 1))
        else:
            window = (1, 1) + kernel
            strides = (1, 1) + stride
            spatial = list(range(2, nd))
        if pad_str is not None:
            padding_cfg = pad_str
        else:
            full = [(0, 0)] * nd
            for i, ax in enumerate(spatial):
                lo, hi = pads[i]
                if ceil_mode:
                    in_s = a.shape[ax]
                    out_ceil = -(-(in_s + lo + hi - kernel[i]) // stride[i]) + 1
                    needed = (out_ceil - 1) * stride[i] + kernel[i] - in_s - lo
                    hi = max(hi, needed)
                full[ax] = (lo, hi)
            padding_cfg = full
        if op == "max":
            if (jnp.issubdtype(a.dtype, jnp.floating)
                    and isinstance(padding_cfg, list)
                    and os.environ.get("PADDLE_TPU_MANUAL_MAXPOOL", "0") == "1"):
                return _manual_maxpool(window, strides,
                                       tuple(padding_cfg))(a)
            init = -jnp.inf if jnp.issubdtype(a.dtype, jnp.floating) else jnp.iinfo(a.dtype).min
            return jax.lax.reduce_window(a, init, jax.lax.max, window, strides, padding_cfg)
        # avg: sum then divide by count (exclusive=True divides by valid count)
        s = jax.lax.reduce_window(a, 0.0, jax.lax.add, window, strides, padding_cfg)
        if exclusive and (pad_str is None and any(p != (0, 0) for p in (padding_cfg if isinstance(padding_cfg, list) else []))):
            ones = jnp.ones_like(a)
            cnt = jax.lax.reduce_window(ones, 0.0, jax.lax.add, window, strides, padding_cfg)
            return s / cnt
        return s / float(np.prod(kernel))

    return apply_op(f, _t(x))


@functools.lru_cache(maxsize=None)
def _manual_maxpool(window, strides, pads):
    """Floating max-pool with a value-equality backward. NEGATIVE RESULT —
    default OFF (opt in via PADDLE_TPU_MANUAL_MAXPOOL=1).

    Motivation: XLA differentiates ``reduce_window(max)`` into
    select-and-scatter — 1.43 ms/step of the round-4 ResNet-50
    profile. This rule instead routes gradients by
    VALUE EQUALITY: eq_u = (view_u == y) over the prod(window) strided
    views, dx accumulated either by dilated-pad scatter-back or by
    gathering the dilated y/scale grids. Ties split the gradient evenly
    (sum-preserving; XLA and the reference's cuDNN kernel pick one winner —
    identical on tie-free continuous inputs).

    Measured on v5e at the ResNet stem shape ([64,64,112,112] bf16, k3 s2
    p1), fwd+bwd chained 10× in one jit: XLA select-and-scatter ≈ 9 ms/iter
    incl. harness, pad-scatter formulation 76 ms, single-dilation gather
    formulation 52 ms — the shifted-window equality passes do NOT fuse into
    the two elementwise loops the arithmetic suggests on this emitter, so
    the manual rule loses 6-8× and end-to-end ResNet-50 dropped
    1581→1205 samples/s. Kept as an opt-in record of the experiment.

    Forward is the same ``reduce_window`` either way; when no gradient is
    taken the custom_vjp adds nothing.
    """

    @jax.custom_vjp
    def mp(a):
        return jax.lax.reduce_window(a, -jnp.inf, jax.lax.max, window,
                                     strides, list(pads))

    def fwd(a):
        y = mp(a)
        return y, (a, y)

    def bwd(res, dy):
        a, y = res
        nd = a.ndim
        ap = jax.lax.pad(a, jnp.asarray(-jnp.inf, a.dtype),
                         [(lo, hi, 0) for lo, hi in pads])
        dyf = dy.astype(jnp.float32)
        offsets = list(itertools.product(*(range(w) for w in window)))

        def view(u):
            limit = [u[d] + strides[d] * (y.shape[d] - 1) + 1
                     for d in range(nd)]
            return jax.lax.slice(ap, u, limit, strides)

        eqs = [view(u) == y for u in offsets]
        cnt = functools.reduce(
            jnp.add, (e.astype(jnp.float32) for e in eqs))
        scale = dyf / cnt
        dxp = None
        for u, eq in zip(offsets, eqs):
            part = jnp.where(eq, scale, 0.0)
            cfg = [(u[d],
                    ap.shape[d] - (u[d] + strides[d] * (y.shape[d] - 1) + 1),
                    strides[d] - 1) for d in range(nd)]
            scattered = jax.lax.pad(part, jnp.asarray(0.0, jnp.float32), cfg)
            dxp = scattered if dxp is None else dxp + scattered
        dx = jax.lax.slice(
            dxp, [lo for lo, _ in pads],
            [lo + s for (lo, _), s in zip(pads, a.shape)])
        return (dx.astype(a.dtype),)

    mp.defvjp(fwd, bwd)
    return mp


def max_pool1d(x, kernel_size, stride=None, padding=0, return_mask=False,
               ceil_mode=False, data_format="NCL", name=None):
    out = _pool(x, kernel_size, stride, padding, 1, "max", data_format == "NLC", ceil_mode)
    return out


def max_pool2d(x, kernel_size, stride=None, padding=0, return_mask=False,
               ceil_mode=False, data_format="NCHW", name=None):
    out = _pool(x, kernel_size, stride, padding, 2, "max", data_format == "NHWC", ceil_mode)
    if return_mask:
        idx = _max_pool_indices(_t(x), kernel_size, stride, padding, 2, data_format)
        return out, idx
    return out


def max_pool3d(x, kernel_size, stride=None, padding=0, return_mask=False,
               ceil_mode=False, data_format="NCDHW", name=None):
    return _pool(x, kernel_size, stride, padding, 3, "max", data_format == "NDHWC", ceil_mode)


def avg_pool1d(x, kernel_size, stride=None, padding=0, exclusive=True,
               ceil_mode=False, data_format="NCL", name=None):
    return _pool(x, kernel_size, stride, padding, 1, "avg", data_format == "NLC",
                 ceil_mode, exclusive)


def avg_pool2d(x, kernel_size, stride=None, padding=0, ceil_mode=False,
               exclusive=True, divisor_override=None, data_format="NCHW", name=None):
    return _pool(x, kernel_size, stride, padding, 2, "avg", data_format == "NHWC",
                 ceil_mode, exclusive)


def avg_pool3d(x, kernel_size, stride=None, padding=0, ceil_mode=False,
               exclusive=True, divisor_override=None, data_format="NCDHW", name=None):
    return _pool(x, kernel_size, stride, padding, 3, "avg", data_format == "NDHWC",
                 ceil_mode, exclusive)


def _max_pool_indices(x, kernel, stride, padding, n, data_format):
    """Flat spatial argmax indices for return_mask (paddle semantics)."""
    kernel_t = _norm(kernel, n)
    stride_t = _norm(stride if stride is not None else kernel, n)
    pad_t = _norm(padding, n)

    def f(a):
        spatial = a.shape[2:]
        # int32 indices: exact to 2^31 elements and TPU-native — float
        # carriers are either inexact past 2^24 (f32) or silently
        # degraded to f32 on TPU hardware (f64; tpu-lint R7)
        flat_idx = jnp.arange(int(np.prod(spatial)), dtype=jnp.int32).reshape(spatial)
        flat_idx = jnp.broadcast_to(flat_idx, a.shape)
        window = (1, 1) + kernel_t
        strides = (1, 1) + stride_t
        pads = [(0, 0), (0, 0)] + [(p, p) for p in pad_t]

        def reducer(acc, cur):
            av, ai = acc
            cv, ci = cur
            take_cur = cv > av
            return jnp.where(take_cur, cv, av), jnp.where(take_cur, ci, ai)

        init_v = jnp.asarray(-jnp.inf, a.dtype)
        init_i = jnp.asarray(-1, jnp.int32)
        vals, idxs = jax.lax.reduce_window(
            (a, flat_idx), (init_v, init_i),
            lambda xa, xb: reducer((xa[0], xa[1]), (xb[0], xb[1])),
            window, strides, pads,
        )
        return idxs.astype(jnp.int64)

    return apply_op(f, x)


def _adaptive(x, output_size, n, op, channel_last):
    if isinstance(output_size, (int, np.integer)):
        output_size = (int(output_size),) * n
    output_size = tuple(
        int(o) if o is not None else None for o in output_size
    )

    def f(a):
        spatial_axes = list(range(1, 1 + n)) if channel_last else list(range(2, 2 + n))
        out = a
        for i, ax in enumerate(spatial_axes):
            tgt = output_size[i]
            if tgt is None:
                continue
            in_s = out.shape[ax]
            # adaptive pooling: bin b covers [floor(b*in/out), ceil((b+1)*in/out))
            pieces = []
            for b in range(tgt):
                lo = (b * in_s) // tgt
                hi = -(-((b + 1) * in_s) // tgt)
                seg = jax.lax.slice_in_dim(out, lo, hi, axis=ax)
                red = jnp.max(seg, axis=ax, keepdims=True) if op == "max" else jnp.mean(
                    seg, axis=ax, keepdims=True
                )
                pieces.append(red)
            out = jnp.concatenate(pieces, axis=ax) if len(pieces) > 1 else pieces[0]
        return out

    return apply_op(f, _t(x))


def adaptive_avg_pool1d(x, output_size, name=None):
    return _adaptive(x, output_size, 1, "avg", False)


def adaptive_avg_pool2d(x, output_size, data_format="NCHW", name=None):
    return _adaptive(x, output_size, 2, "avg", data_format == "NHWC")


def adaptive_avg_pool3d(x, output_size, data_format="NCDHW", name=None):
    return _adaptive(x, output_size, 3, "avg", data_format == "NDHWC")


def adaptive_max_pool1d(x, output_size, return_mask=False, name=None):
    return _adaptive(x, output_size, 1, "max", False)


def adaptive_max_pool2d(x, output_size, return_mask=False, name=None):
    return _adaptive(x, output_size, 2, "max", False)


def adaptive_max_pool3d(x, output_size, return_mask=False, name=None):
    return _adaptive(x, output_size, 3, "max", False)
