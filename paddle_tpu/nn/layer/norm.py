"""Norm layers — parity with python/paddle/nn/layer/norm.py.

SyncBatchNorm note: on TPU under pjit, batch-norm statistics are computed over
the global (sharded) batch automatically when the reduction spans the data
axis, so SyncBatchNorm degenerates to BatchNorm inside a jitted step; the
eager implementation additionally psums stats over the dp mesh axis when a
distributed context is active (replaces reference's sync_batch_norm_op.cu).
"""
from __future__ import annotations

import numpy as np

from ...core import dtype as dtype_mod
from ...core.tensor import Tensor, wrap_raw
from .. import functional as F
from .. import initializer as I
from ..layer_base import Layer

__all__ = [
    "BatchNorm", "BatchNorm1D", "BatchNorm2D", "BatchNorm3D", "SyncBatchNorm",
    "LayerNorm", "RMSNorm", "GatedRMSNorm", "GroupNorm", "InstanceNorm1D", "InstanceNorm2D",
    "InstanceNorm3D", "LocalResponseNorm", "SpectralNorm",
]


class _BatchNormBase(Layer):
    def __init__(self, num_features, momentum=0.9, epsilon=1e-05,
                 weight_attr=None, bias_attr=None, data_format="NCHW",
                 use_global_stats=None, name=None):
        super().__init__()
        self._num_features = num_features
        self._momentum = momentum
        self._epsilon = epsilon
        self._data_format = data_format
        self._use_global_stats = use_global_stats
        self.weight = self.create_parameter(
            [num_features], attr=weight_attr, default_initializer=I.Constant(1.0)
        )
        self.bias = self.create_parameter([num_features], attr=bias_attr, is_bias=True)
        import jax.numpy as jnp

        self.register_buffer("_mean", wrap_raw(jnp.zeros([num_features], jnp.float32)))
        self.register_buffer("_variance", wrap_raw(jnp.ones([num_features], jnp.float32)))

    def forward(self, input):
        return F.batch_norm(
            input, self._mean, self._variance, self.weight, self.bias,
            training=self.training, momentum=self._momentum, epsilon=self._epsilon,
            data_format=self._data_format, use_global_stats=self._use_global_stats,
        )

    def extra_repr(self):
        return f"num_features={self._num_features}, momentum={self._momentum}"


class BatchNorm(_BatchNormBase):
    """fluid-style BatchNorm (accepts act=...)."""

    def __init__(self, num_channels, act=None, momentum=0.9, epsilon=1e-05,
                 param_attr=None, bias_attr=None, dtype="float32",
                 data_layout="NCHW", in_place=False, moving_mean_name=None,
                 moving_variance_name=None, do_model_average_for_mean_and_var=True,
                 use_global_stats=False, trainable_statistics=False):
        super().__init__(num_channels, momentum, epsilon, param_attr, bias_attr,
                         data_layout, use_global_stats)
        self._act = act

    def forward(self, input):
        out = super().forward(input)
        if self._act:
            out = getattr(F, self._act)(out)
        return out


class BatchNorm1D(_BatchNormBase):
    pass


class BatchNorm2D(_BatchNormBase):
    pass


class BatchNorm3D(_BatchNormBase):
    pass


class SyncBatchNorm(_BatchNormBase):
    """Cross-replica BN. Inside pjit the batch axis is global already; in
    eager DP mode stats are allreduced over the data-parallel group."""

    @classmethod
    def convert_sync_batchnorm(cls, layer):
        out = layer
        if isinstance(layer, _BatchNormBase) and not isinstance(layer, SyncBatchNorm):
            out = SyncBatchNorm(layer._num_features, layer._momentum, layer._epsilon,
                                None, None, layer._data_format)
            out.weight.set_value(layer.weight)
            out.bias.set_value(layer.bias)
            out._mean.set_value(layer._mean)
            out._variance.set_value(layer._variance)
        for name, sub in layer._sub_layers.items():
            out._sub_layers[name] = cls.convert_sync_batchnorm(sub)
        return out


class LayerNorm(Layer):
    def __init__(self, normalized_shape, epsilon=1e-05, weight_attr=None,
                 bias_attr=None, name=None):
        super().__init__()
        if isinstance(normalized_shape, (int, np.integer)):
            normalized_shape = [int(normalized_shape)]
        self._normalized_shape = list(normalized_shape)
        self._epsilon = epsilon
        if weight_attr is False:
            self.weight = None
        else:
            self.weight = self.create_parameter(
                self._normalized_shape, attr=weight_attr,
                default_initializer=I.Constant(1.0),
            )
        if bias_attr is False:
            self.bias = None
        else:
            self.bias = self.create_parameter(
                self._normalized_shape, attr=bias_attr, is_bias=True
            )

    def forward(self, input):
        return F.layer_norm(input, self._normalized_shape, self.weight, self.bias,
                            self._epsilon)

    def extra_repr(self):
        return f"normalized_shape={self._normalized_shape}, epsilon={self._epsilon}"


class RMSNorm(Layer):
    """x / rms(x) * weight over the last axis, no mean and no bias."""

    def __init__(self, hidden_size, epsilon=1e-05, weight_attr=None,
                 name=None):
        super().__init__()
        self._epsilon = epsilon
        self.weight = self.create_parameter(
            [int(hidden_size)], attr=weight_attr,
            default_initializer=I.Constant(1.0))

    def forward(self, input):
        return F.rms_norm(input, self.weight, self._epsilon)

    def extra_repr(self):
        return f"{self.weight.shape[0]}, epsilon={self._epsilon}"


class GatedRMSNorm(RMSNorm):
    """RMSNorm(x) * sigmoid(gate), the norm over the last axis of ``x``
    (a head's width, one learned weight shared by the heads); ``gate`` may
    come with the heads flattened, and is the shape the arithmetic runs
    in (``F.rms_norm``).

    With ``groups`` it is a state-space mixer's form instead: the gate
    comes first and through SiLU, RMSNorm_groups(x * silu(gate)) * weight,
    the norm over each of ``groups`` equal parts of the last axis and the
    weight ``hidden_size`` wide."""

    def __init__(self, hidden_size, epsilon=1e-05, weight_attr=None,
                 groups=None, name=None):
        super().__init__(hidden_size, epsilon, weight_attr)
        self._groups = groups

    def forward(self, input, gate):
        if self._groups is None:
            return F.rms_norm(input, self.weight, self._epsilon, gate=gate)
        return F.rms_norm(input * F.silu(gate), self.weight, self._epsilon,
                          groups=self._groups)


class GroupNorm(Layer):
    def __init__(self, num_groups, num_channels, epsilon=1e-05, weight_attr=None,
                 bias_attr=None, data_format="NCHW", name=None):
        super().__init__()
        self._num_groups = num_groups
        self._num_channels = num_channels
        self._epsilon = epsilon
        self._data_format = data_format
        self.weight = (
            None if weight_attr is False else self.create_parameter(
                [num_channels], attr=weight_attr, default_initializer=I.Constant(1.0)
            )
        )
        self.bias = (
            None if bias_attr is False else self.create_parameter(
                [num_channels], attr=bias_attr, is_bias=True
            )
        )

    def forward(self, input):
        return F.group_norm(input, self._num_groups, self._epsilon, self.weight,
                            self.bias, self._data_format)


class _InstanceNormBase(Layer):
    def __init__(self, num_features, epsilon=1e-05, momentum=0.9, weight_attr=None,
                 bias_attr=None, data_format="NCHW", name=None):
        super().__init__()
        self._num_features = num_features
        self._epsilon = epsilon
        self._data_format = data_format
        if weight_attr is False:
            self.scale = None
            self.bias = None
        else:
            self.scale = self.create_parameter(
                [num_features], attr=weight_attr, default_initializer=I.Constant(1.0)
            )
            self.bias = self.create_parameter([num_features], attr=bias_attr, is_bias=True)

    def forward(self, input):
        return F.instance_norm(input, weight=self.scale, bias=self.bias,
                               eps=self._epsilon, data_format=self._data_format)


class InstanceNorm1D(_InstanceNormBase):
    pass


class InstanceNorm2D(_InstanceNormBase):
    pass


class InstanceNorm3D(_InstanceNormBase):
    pass


class LocalResponseNorm(Layer):
    def __init__(self, size, alpha=0.0001, beta=0.75, k=1.0, data_format="NCHW", name=None):
        super().__init__()
        self.size = size
        self.alpha = alpha
        self.beta = beta
        self.k = k
        self.data_format = data_format

    def forward(self, input):
        return F.local_response_norm(input, self.size, self.alpha, self.beta,
                                     self.k, self.data_format)


class SpectralNorm(Layer):
    """Weight spectral normalization via power iteration
    (parity operators/spectral_norm_op)."""

    def __init__(self, weight_shape, dim=0, power_iters=1, eps=1e-12, dtype="float32"):
        super().__init__()
        self._dim = dim
        self._power_iters = power_iters
        self._eps = eps
        import jax.numpy as jnp

        h = int(weight_shape[dim])
        w = int(np.prod(weight_shape)) // h
        self.weight_u = self.create_parameter(
            [h], default_initializer=I.Normal(0.0, 1.0)
        )
        self.weight_v = self.create_parameter(
            [w], default_initializer=I.Normal(0.0, 1.0)
        )
        self.weight_u.stop_gradient = True
        self.weight_v.stop_gradient = True

    def forward(self, weight):
        import jax.numpy as jnp
        from ...core.tensor import apply_op

        dim = self._dim
        eps = self._eps
        iters = self._power_iters
        u0 = self.weight_u._value
        v0 = self.weight_v._value

        def f(w):
            w_m = jnp.moveaxis(w, dim, 0).reshape(w.shape[dim], -1)
            u, v = u0, v0
            for _ in range(iters):
                v = w_m.T @ u
                v = v / (jnp.linalg.norm(v) + eps)
                u = w_m @ v
                u = u / (jnp.linalg.norm(u) + eps)
            sigma = u @ w_m @ v
            return w / sigma

        out = apply_op(f, weight)
        return out
