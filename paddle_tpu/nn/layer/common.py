"""Common layers: Linear, Embedding, Dropout, Flatten, Pads, Upsample —
parity with python/paddle/nn/layer/common.py."""
from __future__ import annotations

import numpy as np

from ...core import dtype as dtype_mod
from ...core.tensor import Tensor
from .. import functional as F
from ..layer_base import Layer

__all__ = [
    "Linear", "SwiGLU", "Embedding", "Dropout", "Dropout2D", "Dropout3D", "AlphaDropout",
    "Flatten", "Upsample", "UpsamplingBilinear2D", "UpsamplingNearest2D",
    "Pad1D", "Pad2D", "Pad3D", "ZeroPad2D", "CosineSimilarity", "Bilinear",
    "Identity", "Unfold", "Fold", "PixelShuffle", "PixelUnshuffle", "ChannelShuffle",
]


class Identity(Layer):
    def __init__(self, *args, **kwargs):
        super().__init__()

    def forward(self, input):
        return input


class Linear(Layer):
    """y = xW + b, weight stored [in, out] (reference layout,
    python/paddle/nn/layer/common.py Linear)."""

    def __init__(self, in_features, out_features, weight_attr=None, bias_attr=None, name=None):
        super().__init__()
        self._in_features = in_features
        self._out_features = out_features
        self.weight = self.create_parameter([in_features, out_features], attr=weight_attr)
        self.bias = self.create_parameter([out_features], attr=bias_attr, is_bias=True)

    def forward(self, input):
        return F.linear(input, self.weight, self.bias)

    def extra_repr(self):
        return f"in_features={self._in_features}, out_features={self._out_features}"


class SwiGLU(Layer):
    """The gated MLP: down(silu(gate(x)) * up(x)), three matrices and no
    bias. ``weight_attr`` initialises all three. ``gate_scale`` and
    ``out_scale`` are a configuration's fixed multipliers, where it has
    them: down(silu(gate(x) * gate_scale) * up(x)) * out_scale."""

    def __init__(self, hidden_size, intermediate_size, weight_attr=None,
                 gate_scale=None, out_scale=None, name=None):
        super().__init__()
        self.gate_proj = Linear(hidden_size, intermediate_size, weight_attr,
                                bias_attr=False)
        self.up_proj = Linear(hidden_size, intermediate_size, weight_attr,
                              bias_attr=False)
        self.down_proj = Linear(intermediate_size, hidden_size, weight_attr,
                                bias_attr=False)
        self._gate_scale, self._out_scale = gate_scale, out_scale
        # TP: gate and up column-parallel, down row-parallel
        self.gate_proj.weight.tp_spec = (None, "mp")
        self.up_proj.weight.tp_spec = (None, "mp")
        self.down_proj.weight.tp_spec = ("mp", None)

    def forward(self, input):
        gate = self.gate_proj(input)
        if self._gate_scale is not None:
            gate = gate * self._gate_scale
        out = self.down_proj(F.silu(gate) * self.up_proj(input))
        return out if self._out_scale is None else out * self._out_scale


class Embedding(Layer):
    def __init__(self, num_embeddings, embedding_dim, padding_idx=None,
                 sparse=False, weight_attr=None, name=None):
        super().__init__()
        self._num_embeddings = num_embeddings
        self._embedding_dim = embedding_dim
        self._padding_idx = (
            None if padding_idx is None
            else padding_idx if padding_idx >= 0 else num_embeddings + padding_idx
        )
        self._sparse = sparse
        from .. import initializer as I

        self.weight = self.create_parameter(
            [num_embeddings, embedding_dim], attr=weight_attr,
            default_initializer=I.XavierNormal(),
        )
        if self._padding_idx is not None:
            val = self.weight.numpy().copy()  # numpy() view is read-only
            val[self._padding_idx] = 0
            self.weight.set_value(val)

    def forward(self, x):
        return F.embedding(x, self.weight, padding_idx=self._padding_idx,
                           sparse=self._sparse)

    def extra_repr(self):
        return f"{self._num_embeddings}, {self._embedding_dim}"


class Dropout(Layer):
    def __init__(self, p=0.5, axis=None, mode="upscale_in_train", name=None):
        super().__init__()
        self.p = p
        self.axis = axis
        self.mode = mode

    def forward(self, input):
        return F.dropout(input, self.p, axis=self.axis, training=self.training,
                         mode=self.mode)

    def extra_repr(self):
        return f"p={self.p}"


class Dropout2D(Layer):
    def __init__(self, p=0.5, data_format="NCHW", name=None):
        super().__init__()
        self.p = p
        self.data_format = data_format

    def forward(self, input):
        return F.dropout2d(input, self.p, training=self.training,
                           data_format=self.data_format)


class Dropout3D(Layer):
    def __init__(self, p=0.5, data_format="NCDHW", name=None):
        super().__init__()
        self.p = p
        self.data_format = data_format

    def forward(self, input):
        return F.dropout3d(input, self.p, training=self.training,
                           data_format=self.data_format)


class AlphaDropout(Layer):
    def __init__(self, p=0.5, name=None):
        super().__init__()
        self.p = p

    def forward(self, input):
        return F.alpha_dropout(input, self.p, training=self.training)


class Flatten(Layer):
    def __init__(self, start_axis=1, stop_axis=-1):
        super().__init__()
        self.start_axis = start_axis
        self.stop_axis = stop_axis

    def forward(self, input):
        from ...tensor.manipulation import flatten

        return flatten(input, self.start_axis, self.stop_axis)


class Upsample(Layer):
    def __init__(self, size=None, scale_factor=None, mode="nearest",
                 align_corners=False, align_mode=0, data_format="NCHW", name=None):
        super().__init__()
        self.size = size
        self.scale_factor = scale_factor
        self.mode = mode
        self.align_corners = align_corners
        self.align_mode = align_mode
        self.data_format = data_format

    def forward(self, x):
        return F.interpolate(x, self.size, self.scale_factor, self.mode,
                             self.align_corners, self.align_mode, self.data_format)


class UpsamplingNearest2D(Upsample):
    def __init__(self, size=None, scale_factor=None, data_format="NCHW", name=None):
        super().__init__(size, scale_factor, "nearest", False, 0, data_format)


class UpsamplingBilinear2D(Upsample):
    def __init__(self, size=None, scale_factor=None, data_format="NCHW", name=None):
        super().__init__(size, scale_factor, "bilinear", True, 0, data_format)


class _PadNd(Layer):
    _n = 2

    def __init__(self, padding, mode="constant", value=0.0, data_format=None, name=None):
        super().__init__()
        if isinstance(padding, int):
            padding = [padding] * (2 * self._n)
        self.padding = list(padding)
        self.mode = mode
        self.value = value
        self.data_format = data_format or ("NCL", "NCHW", "NCDHW")[self._n - 1]

    def forward(self, x):
        return F.pad(x, self.padding, self.mode, self.value, self.data_format)

    def extra_repr(self):
        return f"padding={self.padding}, mode={self.mode}"


class Pad1D(_PadNd):
    _n = 1


class Pad2D(_PadNd):
    _n = 2


class Pad3D(_PadNd):
    _n = 3


class ZeroPad2D(Pad2D):
    def __init__(self, padding, data_format="NCHW", name=None):
        super().__init__(padding, "constant", 0.0, data_format)


class CosineSimilarity(Layer):
    def __init__(self, axis=1, eps=1e-8):
        super().__init__()
        self.axis = axis
        self.eps = eps

    def forward(self, x1, x2):
        return F.cosine_similarity(x1, x2, self.axis, self.eps)


class Bilinear(Layer):
    def __init__(self, in1_features, in2_features, out_features,
                 weight_attr=None, bias_attr=None, name=None):
        super().__init__()
        self.weight = self.create_parameter(
            [out_features, in1_features, in2_features], attr=weight_attr
        )
        self.bias = self.create_parameter([out_features], attr=bias_attr, is_bias=True)

    def forward(self, x1, x2):
        return F.bilinear(x1, x2, self.weight, self.bias)


class Unfold(Layer):
    def __init__(self, kernel_sizes, strides=1, paddings=0, dilations=1, name=None):
        super().__init__()
        self.kernel_sizes = kernel_sizes
        self.strides = strides
        self.paddings = paddings
        self.dilations = dilations

    def forward(self, x):
        return F.unfold(x, self.kernel_sizes, self.strides, self.paddings, self.dilations)


class Fold(Layer):
    def __init__(self, output_sizes, kernel_sizes, strides=1, paddings=0,
                 dilations=1, name=None):
        super().__init__()
        self.output_sizes = output_sizes
        self.kernel_sizes = kernel_sizes
        self.strides = strides
        self.paddings = paddings
        self.dilations = dilations

    def forward(self, x):
        return F.fold(x, self.output_sizes, self.kernel_sizes, self.strides,
                      self.paddings, self.dilations)


class PixelShuffle(Layer):
    def __init__(self, upscale_factor, data_format="NCHW", name=None):
        super().__init__()
        self.upscale_factor = upscale_factor
        self.data_format = data_format

    def forward(self, x):
        return F.pixel_shuffle(x, self.upscale_factor, self.data_format)


class PixelUnshuffle(Layer):
    def __init__(self, downscale_factor, data_format="NCHW", name=None):
        super().__init__()
        self.downscale_factor = downscale_factor
        self.data_format = data_format

    def forward(self, x):
        return F.pixel_unshuffle(x, self.downscale_factor, self.data_format)


class ChannelShuffle(Layer):
    def __init__(self, groups, data_format="NCHW", name=None):
        super().__init__()
        self.groups = groups
        self.data_format = data_format

    def forward(self, x):
        return F.channel_shuffle(x, self.groups, self.data_format)
