"""Parameter initializers — parity with python/paddle/nn/initializer/ and
fluid/initializer.py in the reference. Initializers are pure: they produce a
jax array from (shape, dtype, key) so parameter creation is reproducible and
usable inside staged init functions.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from ..core import dtype as dtype_mod
from ..core import rng as rng_mod

__all__ = [
    "Initializer", "Constant", "Normal", "TruncatedNormal", "Uniform",
    "XavierNormal", "XavierUniform", "KaimingNormal", "KaimingUniform",
    "Assign", "Dirac", "Orthogonal", "calculate_gain", "LazyGuard",
]

_LAZY = [0]  # depth of the LazyGuard contexts that are open


class LazyGuard:
    """``with LazyGuard(): model = Net()``: every parameter created inside
    holds only its shape and type (a ``jax.ShapeDtypeStruct``), nothing is
    drawn and no memory is taken, until ``jit.functionalize.set_params`` (or
    ``set_value``) gives it its value. For a model whose weights come from
    elsewhere and whose default initialisation would not fit beside them."""

    def __enter__(self):
        _LAZY[0] += 1
        return self

    def __exit__(self, *exc):
        _LAZY[0] -= 1


def calculate_gain(nonlinearity, param=None):
    gains = {
        "sigmoid": 1.0,
        "linear": 1.0,
        "conv1d": 1.0,
        "conv2d": 1.0,
        "conv3d": 1.0,
        "tanh": 5.0 / 3.0,
        "relu": math.sqrt(2.0),
        "leaky_relu": math.sqrt(2.0 / (1 + (param if param is not None else 0.01) ** 2)),
        "selu": 3.0 / 4.0,
    }
    return gains[nonlinearity]


def _fans(shape):
    shape = tuple(shape)
    if len(shape) == 0:
        return 1, 1
    if len(shape) == 1:
        return shape[0], shape[0]
    if len(shape) == 2:
        # paddle stores linear weights [in, out]
        return shape[0], shape[1]
    receptive = int(np.prod(shape[2:]))
    return shape[1] * receptive, shape[0] * receptive


class Initializer:
    def __call__(self, shape, dtype=None, key=None):
        dtype = dtype_mod.convert_dtype(dtype) or dtype_mod.get_default_dtype()
        if _LAZY[0]:
            return jax.ShapeDtypeStruct(tuple(int(s) for s in shape),
                                        jnp.dtype(dtype))
        if key is None:
            key = rng_mod.next_key()
        return self._generate(tuple(int(s) for s in shape), dtype, key)

    def _generate(self, shape, dtype, key):
        raise NotImplementedError


class Constant(Initializer):
    def __init__(self, value=0.0):
        self.value = value

    def _generate(self, shape, dtype, key):
        return jnp.full(shape, self.value, dtype)


class Normal(Initializer):
    def __init__(self, mean=0.0, std=1.0):
        self.mean, self.std = mean, std

    def _generate(self, shape, dtype, key):
        return jax.random.normal(key, shape, dtype) * self.std + self.mean


class TruncatedNormal(Initializer):
    def __init__(self, mean=0.0, std=1.0):
        self.mean, self.std = mean, std

    def _generate(self, shape, dtype, key):
        return (
            jax.random.truncated_normal(key, -2.0, 2.0, shape, dtype) * self.std
            + self.mean
        )


class Uniform(Initializer):
    def __init__(self, low=-1.0, high=1.0):
        self.low, self.high = low, high

    def _generate(self, shape, dtype, key):
        return jax.random.uniform(key, shape, dtype, self.low, self.high)


class XavierUniform(Initializer):
    def __init__(self, fan_in=None, fan_out=None, gain=1.0):
        self.fan_in, self.fan_out, self.gain = fan_in, fan_out, gain

    def _generate(self, shape, dtype, key):
        fi, fo = _fans(shape)
        fi = self.fan_in if self.fan_in is not None else fi
        fo = self.fan_out if self.fan_out is not None else fo
        limit = self.gain * math.sqrt(6.0 / (fi + fo))
        return jax.random.uniform(key, shape, dtype, -limit, limit)


class XavierNormal(Initializer):
    def __init__(self, fan_in=None, fan_out=None, gain=1.0):
        self.fan_in, self.fan_out, self.gain = fan_in, fan_out, gain

    def _generate(self, shape, dtype, key):
        fi, fo = _fans(shape)
        fi = self.fan_in if self.fan_in is not None else fi
        fo = self.fan_out if self.fan_out is not None else fo
        std = self.gain * math.sqrt(2.0 / (fi + fo))
        return jax.random.normal(key, shape, dtype) * std


class KaimingUniform(Initializer):
    def __init__(self, fan_in=None, negative_slope=0.0, nonlinearity="leaky_relu"):
        self.fan_in = fan_in
        self.negative_slope = negative_slope
        self.nonlinearity = nonlinearity

    def _generate(self, shape, dtype, key):
        fi, _ = _fans(shape)
        fi = self.fan_in if self.fan_in is not None else fi
        gain = calculate_gain(self.nonlinearity, self.negative_slope)
        limit = gain * math.sqrt(3.0 / fi)
        return jax.random.uniform(key, shape, dtype, -limit, limit)


class KaimingNormal(Initializer):
    def __init__(self, fan_in=None, negative_slope=0.0, nonlinearity="leaky_relu"):
        self.fan_in = fan_in
        self.negative_slope = negative_slope
        self.nonlinearity = nonlinearity

    def _generate(self, shape, dtype, key):
        fi, _ = _fans(shape)
        fi = self.fan_in if self.fan_in is not None else fi
        gain = calculate_gain(self.nonlinearity, self.negative_slope)
        std = gain / math.sqrt(fi)
        return jax.random.normal(key, shape, dtype) * std


class Assign(Initializer):
    def __init__(self, value):
        self.value = value

    def _generate(self, shape, dtype, key):
        from ..core.tensor import Tensor

        v = self.value
        if isinstance(v, Tensor):
            v = v._value
        arr = jnp.asarray(np.asarray(v), dtype)
        return arr.reshape(shape)


class Dirac(Initializer):
    def __init__(self, groups=1):
        self.groups = groups

    def _generate(self, shape, dtype, key):
        out = np.zeros(shape, dtype=np.float32)
        oc, ic = shape[0], shape[1]
        spatial = shape[2:]
        centers = tuple(s // 2 for s in spatial)
        per_group = oc // self.groups
        for g in range(self.groups):
            for i in range(min(per_group, ic)):
                out[(g * per_group + i, i) + centers] = 1.0
        return jnp.asarray(out, dtype)


class Orthogonal(Initializer):
    def __init__(self, gain=1.0):
        self.gain = gain

    def _generate(self, shape, dtype, key):
        rows = shape[0]
        cols = int(np.prod(shape[1:]))
        flat = jax.random.normal(key, (max(rows, cols), min(rows, cols)), jnp.float32)
        q, r = jnp.linalg.qr(flat)
        q = q * jnp.sign(jnp.diagonal(r))
        q = q.T if rows < cols else q
        return (self.gain * q[:rows, :cols]).reshape(shape).astype(dtype)


def _resolve(init, is_bias=False):
    """Accept Initializer | None; default Xavier for weights, zeros for bias —
    the reference's LayerHelperBase default (fluid/layer_helper_base.py)."""
    if init is None:
        return Constant(0.0) if is_bias else XavierUniform()
    return init
