"""Normalization functionals — parity with python/paddle/nn/functional/norm.py.
Replaces the reference's cuDNN batch-norm kernels (operators/batch_norm_op.cu)
with jnp reductions XLA fuses; running stats updated imperatively on the layer.
"""
from __future__ import annotations

import contextlib
import functools
import os

import jax
import jax.numpy as jnp
import numpy as np

from ...core.tensor import Tensor, apply_op, to_tensor

__all__ = ["batch_norm", "layer_norm", "rms_norm", "instance_norm",
           "group_norm", "local_response_norm", "manual_ln_scope"]

# The manual-LN VJP is a PER-WORKLOAD knob (+2.2% on GPT-2 345M, -24% on
# BERT-base under the fleet engine — the custom_vjp blocks a fusion BERT's
# step depends on). Models that measure a win scope it over their own
# forward with `manual_ln_scope(True)` (GPTConfig.manual_layer_norm does);
# the env var remains as a global override for experiments.
_MANUAL_LN_STACK: list = []


@contextlib.contextmanager
def manual_ln_scope(enabled: bool):
    """Scope the manual LayerNorm VJP to the enclosed trace (a model's
    forward), instead of flipping the process-wide env var."""
    _MANUAL_LN_STACK.append(bool(enabled))
    try:
        yield
    finally:
        _MANUAL_LN_STACK.pop()


def _manual_ln_enabled() -> bool:
    if _MANUAL_LN_STACK:
        return _MANUAL_LN_STACK[-1]
    return os.environ.get("PADDLE_TPU_MANUAL_LN", "0") == "1"


def _t(x):
    return x if isinstance(x, Tensor) else to_tensor(x)


def _bn_stats(af, axes):
    """Batch mean/var ([C]-shaped) from ONE variadic reduction pass.

    sum(x) and sum(x*x) over the same operand fuse into a single
    multi-output reduce on TPU, so stats cost one read of the activation
    instead of jnp.mean + jnp.var's two-to-three (r4 profile: 5.4 ms/step
    of convert_reduce fusions on ResNet-50 were exactly these passes).
    var = E[x^2] - mu^2, ALWAYS accumulated in f32 (in bf16 the
    uncentered form cancels catastrophically — mean 10/std 0.1 data
    rounds var to the 0-clamp — and sum(x*x) overflows fp16);
    clamped at 0 against residual cancellation."""
    af = af.astype(jnp.float32)
    n = 1.0
    for ax in axes:
        n *= af.shape[ax]
    s1 = jnp.sum(af, axis=axes)
    s2 = jnp.sum(af * af, axis=axes)
    mu = s1 / n
    var = jnp.maximum(s2 / n - mu * mu, 0.0)
    return mu, var


def _bn_fwd_impl(a, w, b, ch_axis, axes, epsilon):
    af = a.astype(jnp.float32)
    mu, var = _bn_stats(af, axes)
    rstd = jax.lax.rsqrt(var + epsilon)
    shape = [1] * a.ndim
    shape[ch_axis] = a.shape[ch_axis]
    # fold the normalize+affine into one per-channel scale/shift so the
    # output pass is a single fused multiply-add over a (no full-size f32
    # (af-mu) intermediate)
    k = w.astype(jnp.float32) * rstd
    c = b.astype(jnp.float32) - mu * k
    out = (af * k.reshape(shape) + c.reshape(shape)).astype(a.dtype)
    return out, (a, w, b, mu, rstd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _bn_manual(a, w, b, ch_axis, axes, epsilon):
    """Training-mode affine BatchNorm with a hand-written backward.

    Same rationale as ``_ln_manual``: autodiff's backward through the
    separate mean/var ops fuses poorly on TPU; the manual rule recomputes
    xhat from the saved f32 stats and produces dx/dw/db from one pass
    structure, with stats accumulated in f32. Batch stats for the
    running-stat update are NOT outputs — the caller computes them as
    separate grad-free reductions that CSE with this forward's own under
    jit (stat cotangents would otherwise ride every backward as
    unfoldable zero passes in eager mode)."""
    out, _ = _bn_fwd_impl(a, w, b, ch_axis, axes, epsilon)
    return out


def _bn_manual_fwd(a, w, b, ch_axis, axes, epsilon):
    return _bn_fwd_impl(a, w, b, ch_axis, axes, epsilon)


def _bn_manual_bwd(ch_axis, axes, epsilon, res, dy):
    # Two passes over (a, dy) total: pass 1 is the db/dw variadic reduce
    # (xhat recomputed from the saved [C] stats — no residual store); pass 2
    # the dx elementwise. The centering constants come from db/dw instead of
    # their own mean(g)/mean(g*xh) reductions: with per-channel w,
    # mean(g) = w*db/n and mean(g*xh) = w*dw/n.
    a, w, b, mu, rstd = res
    shape = [1] * a.ndim
    shape[ch_axis] = a.shape[ch_axis]
    n = 1.0
    for ax in axes:
        n *= a.shape[ax]
    af = a.astype(jnp.float32)
    xh = (af - mu.reshape(shape)) * rstd.reshape(shape)
    dyf = dy.astype(jnp.float32)
    db = jnp.sum(dyf, axis=axes)
    dw = jnp.sum(dyf * xh, axis=axes)
    k = (w.astype(jnp.float32) * rstd).reshape(shape)
    dx = (k * (dyf - (db / n).reshape(shape) - xh * (dw / n).reshape(shape))
          ).astype(a.dtype)
    return dx, dw.astype(w.dtype), db.astype(b.dtype)


_bn_manual.defvjp(_bn_manual_fwd, _bn_manual_bwd)


def batch_norm(
    x,
    running_mean,
    running_var,
    weight=None,
    bias=None,
    training=False,
    momentum=0.9,
    epsilon=1e-05,
    data_format="NCHW",
    use_global_stats=None,
    name=None,
):
    x = _t(x)
    ch_axis = 1 if data_format.startswith("NC") else x.ndim - 1
    reduce_axes = tuple(i for i in range(x.ndim) if i != ch_axis)
    use_batch_stats = training and not use_global_stats

    if use_batch_stats:
        manual = (weight is not None and bias is not None
                  and os.environ.get("PADDLE_TPU_MANUAL_BN", "1") == "1")
        # batch stats; update running stats imperatively (momentum
        # semantics match the reference: r = m*r + (1-m)*batch). On the
        # manual path these reductions CSE with _bn_manual's internal ones
        # under jit (identical expressions over the same operand).
        mean, var = apply_op(
            lambda a: _bn_stats(a, reduce_axes), x, multi_out=True)
        if running_mean is not None:
            # EMA in the running-stat buffer's own dtype: the f32 batch
            # stats would otherwise silently promote bf16/fp16 buffers
            # (dtype drift in state_dict + a retrace on the next step)
            running_mean._value = (
                momentum * running_mean._value
                + (1.0 - momentum)
                * mean._value.astype(running_mean._value.dtype)
            )
            running_var._value = (
                momentum * running_var._value
                + (1.0 - momentum)
                * var._value.astype(running_var._value.dtype)
            )
        if manual:
            return apply_op(
                lambda a, w, b: _bn_manual(a, w, b, ch_axis, reduce_axes,
                                           epsilon),
                x, weight, bias)
    else:
        mean, var = running_mean, running_var

    shape = [1] * x.ndim
    shape[ch_axis] = x.shape[ch_axis]

    cast_back = use_batch_stats  # train-mode stats are f32; keep the
    # output in the input's dtype (eval keeps its historical promotion
    # semantics when running stats are wider than the input)

    def f(a, m, v, *wb):
        m = m.reshape(shape)
        v = v.reshape(shape)
        out = (a - m) * jax.lax.rsqrt(v + epsilon)
        i = 0
        if weight is not None:
            out = out * wb[i].reshape(shape)
            i += 1
        if bias is not None:
            out = out + wb[i].reshape(shape)
        return out.astype(a.dtype) if cast_back else out

    args = [x, mean, var]
    if weight is not None:
        args.append(weight)
    if bias is not None:
        args.append(bias)
    return apply_op(f, *args)


def _ln_fwd_impl(a, w, b, epsilon):
    af = a.astype(jnp.float32)
    n = af.shape[-1]
    # one-pass row stats: sum(x) and sum(x·x) fuse into a single
    # multi-output reduce (one read of the activation); jnp.mean + jnp.var
    # is two sequential passes (var needs the mean first). Uncentered var
    # in f32 — same rationale and clamp as _bn_stats.
    # ASSUMPTION (documented in README "Observability"): E[x²]−E[x]²
    # cancels catastrophically when |mean| ≫ std (var ≈ difference of two
    # large near-equal f32 numbers). Safe here because LN inputs are
    # residual-stream activations with |mean|/std of order 1; feeding
    # un-normalized data with a huge DC offset through LayerNorm would
    # lose var precision (the clamp floors it at 0 rather than going
    # negative).
    s1 = jnp.sum(af, axis=-1, keepdims=True)
    s2 = jnp.sum(af * af, axis=-1, keepdims=True)
    mu = s1 / n
    var = jnp.maximum(s2 / n - mu * mu, 0.0)
    rstd = jax.lax.rsqrt(var + epsilon)
    out = ((af - mu) * rstd).astype(a.dtype) * w + b
    return out, (a, w, jnp.zeros((), b.dtype), mu, rstd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _ln_manual(a, w, b, epsilon):
    """Single-trailing-axis affine LayerNorm with a hand-written backward.

    Autodiff's LN backward emits separate mean/var transpose chains that XLA
    fuses poorly (measured 0.48 ms autodiff vs 0.34 ms manual per
    [8192,1024] bf16 LN fwd+bwd on v5e). The manual rule recomputes xhat
    from the saved f32 row stats (no xhat residual store) and emits
    dx/dw/db from one shared pass. Stats accumulate in f32 regardless of
    input dtype. custom_vjp inlines into the jaxpr, so XLA still fuses the
    LN into surrounding residual adds."""
    out, _ = _ln_fwd_impl(a, w, b, epsilon)
    return out


def _ln_manual_fwd(a, w, b, epsilon):
    return _ln_fwd_impl(a, w, b, epsilon)


def _ln_manual_bwd(epsilon, res, dy):
    a, w, b_proto, mu, rstd = res
    af = a.astype(jnp.float32)
    xh = (af - mu) * rstd
    g = dy.astype(jnp.float32) * w.astype(jnp.float32)
    c1 = jnp.mean(g, axis=-1, keepdims=True)
    c2 = jnp.mean(g * xh, axis=-1, keepdims=True)
    dx = (rstd * (g - c1 - xh * c2)).astype(a.dtype)
    dyf = dy.astype(jnp.float32)
    red = tuple(range(a.ndim - 1))
    dw = jnp.sum(dyf * xh, axis=red).astype(w.dtype)
    db = jnp.sum(dyf, axis=red).astype(b_proto.dtype)
    return dx, dw, db


_ln_manual.defvjp(_ln_manual_fwd, _ln_manual_bwd)


def group_rms(a, w, epsilon, groups):
    """Raw arrays: ``a`` over its root mean square within each of
    ``groups`` equal parts of the last axis (float32 statistic), times
    ``w`` (the whole axis wide, or None), in ``a``'s dtype. The parts are
    sliced and joined, so nothing asks for the groups as an axis."""
    parts = []
    for part in jnp.split(a.astype(jnp.float32), groups, axis=-1):
        parts.append(part * jax.lax.rsqrt(
            jnp.mean(jnp.square(part), axis=-1, keepdims=True) + epsilon))
    out = parts[0] if groups == 1 else jnp.concatenate(parts, axis=-1)
    if w is not None:
        out = out * w.astype(jnp.float32)
    return out.astype(a.dtype)


def rms_norm(x, weight=None, epsilon=1e-05, gate=None, groups=None,
             name=None):
    """x / rms(x) * weight over the last axis (as many trailing elements
    as ``weight`` has; all of them without one), the statistic in float32.
    With ``gate`` (``x``'s shape, or its last axes flattened) the result
    is multiplied by sigmoid(gate): the gated, head-wise form a linear
    attention layer puts on its output. That form computes on the gate's
    shape, [..., heads * d] with a head's d values side by side: the sums
    over a head are products with a 0/1 matrix (``ops.linear_attention.
    head_sums``), so that no operation asks for the heads as an axis (on a
    TPU that is a relayout of the whole tensor, there and back).

    With ``groups`` the last axis is normalised in that many equal parts,
    each by its own statistic, under one weight as wide as the axis (the
    grouped norm of a state-space mixer); no ``gate`` then."""

    def gated(a, w, g):
        from ...ops.linear_attention import head_sums, over_heads

        d = a.shape[-1]
        heads = g.shape[-1] // d
        a32 = a.astype(jnp.float32).reshape(g.shape)
        out = a32 * over_heads(jax.lax.rsqrt(
            head_sums(jnp.square(a32), heads) / d + epsilon), d)
        if w is not None:
            out = out * jnp.tile(w.astype(jnp.float32), heads)
        out = out * jax.nn.sigmoid(g.astype(jnp.float32))
        return out.astype(a.dtype).reshape(a.shape)

    def f(a, *rest):
        rest = list(rest)
        w = rest.pop(0) if weight is not None else None
        if gate is not None:
            return gated(a, w, rest[0])
        if groups is not None:
            return group_rms(a, w, epsilon, groups)
        a32 = a.astype(jnp.float32)
        out = a32 * jax.lax.rsqrt(
            jnp.mean(jnp.square(a32), axis=-1, keepdims=True) + epsilon)
        if w is not None:
            out = out * w.astype(jnp.float32)
        return out.astype(a.dtype)

    if gate is not None and groups is not None:
        raise ValueError("rms_norm: gate and groups are two forms; the "
                         "grouped one takes its gate before the norm")
    args = [_t(x)] + [t for t in (weight, gate) if t is not None]
    return apply_op(f, *args)


def layer_norm(x, normalized_shape, weight=None, bias=None, epsilon=1e-05, name=None):
    x = _t(x)
    if isinstance(normalized_shape, (int, np.integer)):
        normalized_shape = (int(normalized_shape),)
    n_norm = len(tuple(normalized_shape))
    axes = tuple(range(x.ndim - n_norm, x.ndim))

    def f(a, *wb):
        if (len(axes) == 1 and weight is not None and bias is not None
                and os.environ.get("PADDLE_TPU_FUSED_LN") == "1"
                and jax.default_backend() == "tpu"):
            # opt-in Pallas fwd/bwd kernels (ops/fused.py). Measured on v5e
            # GPT-2 345M: XLA's own LN fusions fold into the surrounding
            # residual adds and win end-to-end — the kernel is kept for wide
            # rows where XLA splits the reduction.
            from paddle_tpu.ops.fused import fused_layer_norm

            return fused_layer_norm(a, wb[0], wb[1], epsilon)
        # per-workload knob — see _MANUAL_LN_STACK above
        if (len(axes) == 1 and weight is not None and bias is not None
                and _manual_ln_enabled()):
            return _ln_manual(a, wb[0], wb[1], epsilon)
        # two-pass mean/var DELIBERATELY: on the autodiff path the
        # uncentered one-pass form was measured 2% WORSE end-to-end on
        # BERT-base (d(sum x²)/dx = 2x adds an extra full elementwise pass
        # to the backward that outweighs the forward's saved read). The
        # one-pass trick only pays where the backward is hand-written
        # (_ln_manual / _bn_manual).
        mean = jnp.mean(a, axis=axes, keepdims=True)
        var = jnp.var(a, axis=axes, keepdims=True)
        out = (a - mean) * jax.lax.rsqrt(var + epsilon)
        i = 0
        if weight is not None:
            out = out * wb[i]
            i += 1
        if bias is not None:
            out = out + wb[i]
        return out

    args = [x] + [w for w in (weight, bias) if w is not None]
    return apply_op(f, *args)


def instance_norm(
    x, running_mean=None, running_var=None, weight=None, bias=None,
    use_input_stats=True, momentum=0.9, eps=1e-05, data_format="NCHW", name=None,
):
    x = _t(x)
    ch_axis = 1 if data_format.startswith("NC") else x.ndim - 1
    spatial_axes = tuple(i for i in range(2, x.ndim)) if ch_axis == 1 else tuple(
        i for i in range(1, x.ndim - 1)
    )

    def f(a, *wb):
        mean = jnp.mean(a, axis=spatial_axes, keepdims=True)
        var = jnp.var(a, axis=spatial_axes, keepdims=True)
        out = (a - mean) * jax.lax.rsqrt(var + eps)
        shape = [1] * a.ndim
        shape[ch_axis] = a.shape[ch_axis]
        i = 0
        if weight is not None:
            out = out * wb[i].reshape(shape)
            i += 1
        if bias is not None:
            out = out + wb[i].reshape(shape)
        return out

    args = [x] + [w for w in (weight, bias) if w is not None]
    return apply_op(f, *args)


def group_norm(x, num_groups, epsilon=1e-05, weight=None, bias=None,
               data_format="NCHW", name=None):
    x = _t(x)
    channel_last = not data_format.startswith("NC")

    def f(a, *wb):
        if channel_last:
            a_m = jnp.moveaxis(a, -1, 1)
        else:
            a_m = a
        n, c = a_m.shape[:2]
        rest = a_m.shape[2:]
        g = a_m.reshape((n, num_groups, c // num_groups) + rest)
        axes = tuple(range(2, g.ndim))
        mean = jnp.mean(g, axis=axes, keepdims=True)
        var = jnp.var(g, axis=axes, keepdims=True)
        out = ((g - mean) * jax.lax.rsqrt(var + epsilon)).reshape(a_m.shape)
        shape = [1] * a_m.ndim
        shape[1] = c
        i = 0
        if weight is not None:
            out = out * wb[i].reshape(shape)
            i += 1
        if bias is not None:
            out = out + wb[i].reshape(shape)
        if channel_last:
            out = jnp.moveaxis(out, 1, -1)
        return out

    args = [x] + [w for w in (weight, bias) if w is not None]
    return apply_op(f, *args)


def local_response_norm(x, size, alpha=1e-4, beta=0.75, k=1.0, data_format="NCHW", name=None):
    def f(a):
        channel_last = not data_format.startswith("NC")
        if channel_last:
            a = jnp.moveaxis(a, -1, 1)
        sq = a * a
        c = a.shape[1]
        half = size // 2
        pad_width = [(0, 0)] * a.ndim
        pad_width[1] = (half, size - half - 1)
        padded = jnp.pad(sq, pad_width)
        acc = jnp.zeros_like(a)
        for i in range(size):
            acc = acc + jax.lax.slice_in_dim(padded, i, i + c, axis=1)
        out = a / (k + alpha * acc) ** beta
        if channel_last:
            out = jnp.moveaxis(out, 1, -1)
        return out

    return apply_op(f, _t(x))
