"""Common functionals: linear, dropout, embedding, one_hot, interpolate, pad,
normalize, cosine_similarity — parity with python/paddle/nn/functional/common.py
and input.py in the reference.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from ...core import dtype as dtype_mod
from ...core import rng as rng_mod
from ...core.tensor import Tensor, apply_op, to_tensor

__all__ = [
    "linear", "dropout", "dropout2d", "dropout3d", "alpha_dropout", "embedding",
    "one_hot", "label_smooth", "pad", "interpolate", "upsample", "normalize",
    "cosine_similarity", "pixel_shuffle", "pixel_unshuffle", "channel_shuffle",
    "unfold", "fold", "bilinear", "rotary_embedding",
]

from ...tensor.manipulation import pad  # re-export (paddle exposes under F.pad)


def _t(x):
    return x if isinstance(x, Tensor) else to_tensor(x)


def linear(x, weight, bias=None, name=None):
    """y = x @ W + b with paddle's [in, out] weight layout — lowers to a
    single MXU matmul; XLA fuses the bias add.

    NOTE (profiled, v5e GPT-2 345M): leave the bias grad to jax's native
    vjp. A custom_vjp that reformulates db as a rank-1 MXU dot measured
    3k tok/s SLOWER end-to-end — the custom_vjp boundary breaks XLA's
    dW-matmul+Adam kOutput fusions, which outweighs the faster reduce."""
    from ...amp.auto_cast import maybe_cast_inputs

    if bias is None:
        return apply_op(
            lambda a, w: jnp.matmul(*maybe_cast_inputs("linear", a, w)), _t(x), weight
        )

    def f(a, w, b):
        a, w = maybe_cast_inputs("linear", a, w)
        out = jnp.matmul(a, w)
        return out + b.astype(out.dtype)

    return apply_op(f, _t(x), weight, bias)


def dropout(x, p=0.5, axis=None, training=True, mode="upscale_in_train", name=None):
    x = _t(x)
    if not training or p == 0.0:
        if mode == "downscale_in_infer" and not training:
            return apply_op(lambda a: a * (1.0 - p), x)
        return x
    if p == 1.0:
        return apply_op(lambda a: jnp.zeros_like(a), x)
    key = rng_mod.next_key()
    shape = tuple(x.shape)
    if axis is not None:
        axes = [axis] if isinstance(axis, int) else list(axis)
        shape = tuple(s if i in axes else 1 for i, s in enumerate(x.shape))
    keep = jax.random.bernoulli(key, 1.0 - p, shape)

    def f(a):
        m = keep.astype(a.dtype)
        if mode == "upscale_in_train":
            return a * m / (1.0 - p)
        return a * m  # downscale_in_infer mode: plain mask at train time

    return apply_op(f, x)


def dropout2d(x, p=0.5, training=True, data_format="NCHW", name=None):
    ch_axis = 1 if data_format == "NCHW" else 3
    return dropout(x, p, axis=[0, ch_axis], training=training)


def dropout3d(x, p=0.5, training=True, data_format="NCDHW", name=None):
    ch_axis = 1 if data_format == "NCDHW" else 4
    return dropout(x, p, axis=[0, ch_axis], training=training)


def alpha_dropout(x, p=0.5, training=True, name=None):
    x = _t(x)
    if not training or p == 0.0:
        return x
    alpha = 1.6732632423543772848170429916717
    scale = 1.0507009873554804934193349852946
    alpha_p = -alpha * scale
    key = rng_mod.next_key()
    keep = jax.random.bernoulli(key, 1.0 - p, tuple(x.shape))
    a_coef = (1.0 - p + p * alpha_p**2) ** -0.5
    b_coef = -a_coef * p * alpha_p

    def f(v):
        m = keep.astype(v.dtype)
        return a_coef * (v * m + alpha_p * (1 - m)) + b_coef

    return apply_op(f, x)


def embedding(x, weight, padding_idx=None, sparse=False, name=None):
    """Gather rows of ``weight``.

    ``sparse=True`` in eager mode produces a ``RowSparseGrad`` for the
    weight — the TPU-native SelectedRows equivalent
    (framework/selected_rows.h:1, operators/lookup_table_v2_op.*): the
    gradient stays (rows, values) through the optimizer, whose sparse path
    updates only touched rows (O(batch·seq·dim), not O(vocab·dim)).
    Under jit/tracing the dense gather + XLA scatter-add vjp is the fast
    path (the engines consume dense grads)."""

    def f(idx, w):
        out = jnp.take(w, idx, axis=0)
        if padding_idx is not None and padding_idx >= 0:
            mask = (idx != padding_idx)[..., None].astype(w.dtype)
            out = out * mask
        return out

    xt = _t(x).detach()
    if sparse:
        from ...core import tensor as tensor_mod
        from ...core.selected_rows import RowSparseGrad

        eager = not tensor_mod._is_tracer(xt._value)
        # leaf weights only: a RowSparseGrad cotangent cannot flow through
        # an upstream jax vjp (e.g. weight.astype(...) under AMP) — those
        # take the dense path
        record = (tensor_mod._grad_mode.enabled and eager
                  and isinstance(weight, Tensor) and not weight.stop_gradient
                  and weight._node is None
                  and tensor_mod._op_recorder is None)
        if record:
            idx_raw = xt._value
            w_raw = weight._value
            num_rows, dim = w_raw.shape
            out_raw = f(idx_raw, w_raw)

            def vjp_fn(ct):
                rows = idx_raw.reshape(-1).astype(jnp.int32)
                vals = ct.reshape(-1, dim)
                if padding_idx is not None and padding_idx >= 0:
                    # mask padded positions out of the sparse update
                    rows = jnp.where(rows == padding_idx,
                                     jnp.int32(num_rows), rows)
                return (RowSparseGrad(rows, vals, num_rows),)

            node = tensor_mod.Node([weight], vjp_fn,
                                   [(out_raw.shape, out_raw.dtype)],
                                   name="embedding_sparse_grad")
            out = Tensor(out_raw, stop_gradient=False)
            out._node = node
            out._idx = 0
            return out

    return apply_op(lambda idx, w: f(idx, w), xt, weight)


def one_hot(x, num_classes, name=None):
    return apply_op(
        lambda idx: jax.nn.one_hot(idx, num_classes, dtype=dtype_mod.get_default_dtype()),
        _t(x).detach(),
    )


def label_smooth(label, prior_dist=None, epsilon=0.1, name=None):
    def f(l):
        k = l.shape[-1]
        if prior_dist is not None:
            pd = prior_dist._value if isinstance(prior_dist, Tensor) else jnp.asarray(prior_dist)
            return (1.0 - epsilon) * l + epsilon * pd
        return (1.0 - epsilon) * l + epsilon / k

    return apply_op(f, _t(label))


def normalize(x, p=2, axis=1, epsilon=1e-12, name=None):
    def f(a):
        nrm = jnp.sum(jnp.abs(a) ** p, axis=axis, keepdims=True) ** (1.0 / p)
        return a / jnp.maximum(nrm, epsilon)

    return apply_op(f, _t(x))


def cosine_similarity(x1, x2, axis=1, eps=1e-8, name=None):
    def f(a, b):
        dot = jnp.sum(a * b, axis=axis)
        na = jnp.sqrt(jnp.sum(a * a, axis=axis))
        nb = jnp.sqrt(jnp.sum(b * b, axis=axis))
        return dot / jnp.maximum(na * nb, eps)

    return apply_op(f, _t(x1), _t(x2))


def pixel_shuffle(x, upscale_factor, data_format="NCHW", name=None):
    r = int(upscale_factor)

    def f(a):
        if data_format == "NCHW":
            n, c, h, w = a.shape
            oc = c // (r * r)
            a = a.reshape(n, oc, r, r, h, w)
            a = a.transpose(0, 1, 4, 2, 5, 3)
            return a.reshape(n, oc, h * r, w * r)
        n, h, w, c = a.shape
        oc = c // (r * r)
        a = a.reshape(n, h, w, r, r, oc)
        a = a.transpose(0, 1, 3, 2, 4, 5)
        return a.reshape(n, h * r, w * r, oc)

    return apply_op(f, _t(x))


def pixel_unshuffle(x, downscale_factor, data_format="NCHW", name=None):
    r = int(downscale_factor)

    def f(a):
        if data_format == "NCHW":
            n, c, h, w = a.shape
            oh, ow = h // r, w // r
            a = a.reshape(n, c, oh, r, ow, r)
            a = a.transpose(0, 1, 3, 5, 2, 4)
            return a.reshape(n, c * r * r, oh, ow)
        n, h, w, c = a.shape
        oh, ow = h // r, w // r
        a = a.reshape(n, oh, r, ow, r, c)
        a = a.transpose(0, 2, 4, 5, 1, 3)
        return a.reshape(n, oh, ow, c * r * r)

    return apply_op(f, _t(x))


def channel_shuffle(x, groups, data_format="NCHW", name=None):
    def f(a):
        if data_format == "NCHW":
            n, c, h, w = a.shape
            a = a.reshape(n, groups, c // groups, h, w)
            a = a.transpose(0, 2, 1, 3, 4)
            return a.reshape(n, c, h, w)
        n, h, w, c = a.shape
        a = a.reshape(n, h, w, groups, c // groups)
        a = a.transpose(0, 1, 2, 4, 3)
        return a.reshape(n, h, w, c)

    return apply_op(f, _t(x))


def interpolate(
    x,
    size=None,
    scale_factor=None,
    mode="nearest",
    align_corners=False,
    align_mode=0,
    data_format="NCHW",
    name=None,
):
    x = _t(x)
    spatial = x.shape[2:] if data_format.startswith("NC") else x.shape[1:-1]
    if size is None:
        if isinstance(scale_factor, (int, float)):
            scale_factor = [scale_factor] * len(spatial)
        size = [int(s * f) for s, f in zip(spatial, scale_factor)]
    else:
        if isinstance(size, Tensor):
            size = [int(v) for v in size.numpy()]
        size = [int(s.item()) if isinstance(s, Tensor) else int(s) for s in size]

    method = {
        "nearest": "nearest",
        "bilinear": "linear",
        "trilinear": "linear",
        "linear": "linear",
        "bicubic": "cubic",
        "area": "linear",
    }[mode.lower()]

    def f(a):
        if data_format.startswith("NC"):
            target = list(a.shape[:2]) + size
        else:
            target = [a.shape[0]] + size + [a.shape[-1]]
        if method == "nearest":
            return _nearest_resize(a, target, data_format)
        if align_corners:
            # jax.image.resize has no align_corners; emulate via linear scale
            return _align_corners_resize(a, target, data_format, method)
        return jax.image.resize(a, tuple(target), method=method)

    return apply_op(f, x)


def _nearest_resize(a, target, data_format):
    # floor-index nearest (paddle semantics with align_corners=False)
    idxs = []
    src_spatial_axes = range(2, a.ndim) if data_format.startswith("NC") else range(1, a.ndim - 1)
    out = a
    for ax in src_spatial_axes:
        in_s = a.shape[ax]
        out_s = target[ax]
        idx = jnp.clip(jnp.floor(jnp.arange(out_s) * (in_s / out_s)).astype(jnp.int32), 0, in_s - 1)
        out = jnp.take(out, idx, axis=ax)
    return out


def _align_corners_resize(a, target, data_format, method):
    axes = list(range(2, a.ndim)) if data_format.startswith("NC") else list(range(1, a.ndim - 1))
    out = a
    for ax in axes:
        in_s = out.shape[ax]
        out_s = target[ax]
        if out_s == 1 or in_s == 1:
            pos = jnp.zeros(out_s)
        else:
            pos = jnp.arange(out_s) * ((in_s - 1) / (out_s - 1))
        lo = jnp.floor(pos).astype(jnp.int32)
        hi = jnp.clip(lo + 1, 0, in_s - 1)
        w = (pos - lo).astype(out.dtype)
        shape = [1] * out.ndim
        shape[ax] = out_s
        w = w.reshape(shape)
        out = jnp.take(out, lo, axis=ax) * (1 - w) + jnp.take(out, hi, axis=ax) * w
    return out


def upsample(x, size=None, scale_factor=None, mode="nearest", align_corners=False,
             align_mode=0, data_format="NCHW", name=None):
    return interpolate(x, size, scale_factor, mode, align_corners, align_mode, data_format)


def unfold(x, kernel_sizes, strides=1, paddings=0, dilations=1, name=None):
    ks = [kernel_sizes] * 2 if isinstance(kernel_sizes, int) else list(kernel_sizes)
    st = [strides] * 2 if isinstance(strides, int) else list(strides)
    pd = [paddings] * 2 if isinstance(paddings, int) else list(paddings)
    dl = [dilations] * 2 if isinstance(dilations, int) else list(dilations)
    if len(pd) == 2:
        pd = [pd[0], pd[0], pd[1], pd[1]]

    def f(a):
        n, c, h, w = a.shape
        patches = jax.lax.conv_general_dilated_patches(
            a,
            filter_shape=ks,
            window_strides=st,
            padding=((pd[0], pd[1]), (pd[2], pd[3])),
            rhs_dilation=dl,
            dimension_numbers=("NCHW", "OIHW", "NCHW"),
        )
        # patches: [n, c*kh*kw, oh, ow] -> [n, c*kh*kw, oh*ow]
        return patches.reshape(n, patches.shape[1], -1)

    return apply_op(f, _t(x))


def fold(x, output_sizes, kernel_sizes, strides=1, paddings=0, dilations=1, name=None):
    os = [output_sizes] * 2 if isinstance(output_sizes, int) else list(output_sizes)
    ks = [kernel_sizes] * 2 if isinstance(kernel_sizes, int) else list(kernel_sizes)
    st = [strides] * 2 if isinstance(strides, int) else list(strides)
    pd = [paddings] * 2 if isinstance(paddings, int) else list(paddings)
    dl = [dilations] * 2 if isinstance(dilations, int) else list(dilations)
    if len(pd) == 2:
        pd = [pd[0], pd[0], pd[1], pd[1]]

    def f(a):
        n, ckk, l = a.shape
        c = ckk // (ks[0] * ks[1])
        oh = (os[0] + pd[0] + pd[1] - dl[0] * (ks[0] - 1) - 1) // st[0] + 1
        ow = (os[1] + pd[2] + pd[3] - dl[1] * (ks[1] - 1) - 1) // st[1] + 1
        cols = a.reshape(n, c, ks[0], ks[1], oh, ow)
        out = jnp.zeros((n, c, os[0] + pd[0] + pd[1], os[1] + pd[2] + pd[3]), a.dtype)
        for i in range(ks[0]):
            for j in range(ks[1]):
                hi = i * dl[0]
                wj = j * dl[1]
                out = out.at[
                    :, :, hi : hi + oh * st[0] : st[0], wj : wj + ow * st[1] : st[1]
                ].add(cols[:, :, i, j])
        return out[:, :, pd[0] : out.shape[2] - pd[1], pd[2] : out.shape[3] - pd[3]]

    return apply_op(f, _t(x))


def bilinear(x1, x2, weight, bias=None, name=None):
    def f(a, b, w, *rest):
        out = jnp.einsum("bi,oij,bj->bo", a, w, b)
        if rest:
            out = out + rest[0]
        return out

    if bias is not None:
        return apply_op(f, _t(x1), _t(x2), weight, bias)
    return apply_op(f, _t(x1), _t(x2), weight)


def diag_embed(input, offset=0, dim1=-2, dim2=-1):
    """Create batched matrices whose (dim1, dim2) planes carry ``input``'s
    last axis on the ``offset`` diagonal — parity with
    python/paddle/nn/functional/extension.py:29 (diag_embed op). One
    scatter-free construction: place on the trailing [n, n] plane via a
    static index set, then moveaxis to (dim1, dim2)."""
    x = _t(input)

    def f(a):
        m = a.shape[-1]
        n = m + abs(offset)
        out_ndim = a.ndim + 1
        d1 = dim1 % out_ndim
        d2 = dim2 % out_ndim
        if d1 == d2:
            raise ValueError("diag_embed: dim1 and dim2 must differ")
        idx = jnp.arange(m)
        rows = idx + max(-offset, 0)
        cols = idx + max(offset, 0)
        plane = jnp.zeros(a.shape[:-1] + (n, n), a.dtype)
        plane = plane.at[..., rows, cols].set(a)
        # trailing (r, c) plane -> the requested (dim1, dim2) positions
        # (moveaxis handles d1 > d2 — the row axis simply lands after the
        # column axis, which IS the reference's transposed-diagonal
        # behavior; verified against torch.diag_embed for dim1 > dim2)
        return jnp.moveaxis(plane, (-2, -1), (d1, d2))

    return apply_op(f, x)


__all__.append("diag_embed")


def _rotate_half(x, positions, theta):
    """Raw arrays: ``x`` [..., l, heads, d] rotated by its ``positions``
    [..., l], a pair (i, i + d/2) of a head's columns by the angle
    position * theta^(-2i/d): x cos + rotate_half(x) sin, the angles in
    float32 whatever ``x`` is, the result in ``x``'s dtype."""
    d = x.shape[-1]
    inv = jnp.asarray(theta, jnp.float32) ** (
        -jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    angle = positions.astype(jnp.float32)[..., None, None] * inv  # [.., l, 1, d/2]
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    x32 = x.astype(jnp.float32)
    lo, hi = x32[..., :d // 2], x32[..., d // 2:]
    return jnp.concatenate([lo * cos - hi * sin, hi * cos + lo * sin],
                           axis=-1).astype(x.dtype)


def rotary_embedding(x, positions, theta=10000.0, name=None):
    """Rotary position embedding over a head's whole width (the
    rotate-half pairing): ``x`` [b, l, heads, d], ``positions`` [b, l]
    whole numbers. Raw arrays inside a traced function come back raw."""
    if not isinstance(x, Tensor):
        return _rotate_half(x, positions, theta)
    pos = positions._value if isinstance(positions, Tensor) else positions
    return apply_op(lambda a: _rotate_half(a, jnp.asarray(pos), theta), x)
