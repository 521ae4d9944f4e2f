"""ResNet family — parity with python/paddle/vision/models/resnet.py
(resnet18/34/50/101/152). Conv+BN blocks lower to MXU convs with XLA-fused
batchnorm (replacing the reference's fused_bn_activation_op.cu path).
"""
from __future__ import annotations

import numpy as np

from ... import nn

__all__ = ["ResNet", "resnet18", "resnet34", "resnet50", "resnet101", "resnet152"]


class BasicBlock(nn.Layer):
    expansion = 1

    def __init__(self, inplanes, planes, stride=1, downsample=None, groups=1,
                 base_width=64, dilation=1, norm_layer=None):
        super().__init__()
        norm_layer = norm_layer or nn.BatchNorm2D
        self.conv1 = nn.Conv2D(inplanes, planes, 3, padding=1, stride=stride,
                               bias_attr=False)
        self.bn1 = norm_layer(planes)
        self.relu = nn.ReLU()
        self.conv2 = nn.Conv2D(planes, planes, 3, padding=1, bias_attr=False)
        self.bn2 = norm_layer(planes)
        self.downsample = downsample
        self.stride = stride

    def forward(self, x):
        identity = x
        out = self.relu(self.bn1(self.conv1(x)))
        out = self.bn2(self.conv2(out))
        if self.downsample is not None:
            identity = self.downsample(x)
        return self.relu(out + identity)


class BottleneckBlock(nn.Layer):
    expansion = 4

    def __init__(self, inplanes, planes, stride=1, downsample=None, groups=1,
                 base_width=64, dilation=1, norm_layer=None):
        super().__init__()
        norm_layer = norm_layer or nn.BatchNorm2D
        width = int(planes * (base_width / 64.0)) * groups
        self.conv1 = nn.Conv2D(inplanes, width, 1, bias_attr=False)
        self.bn1 = norm_layer(width)
        self.conv2 = nn.Conv2D(width, width, 3, padding=dilation, stride=stride,
                               groups=groups, dilation=dilation, bias_attr=False)
        self.bn2 = norm_layer(width)
        self.conv3 = nn.Conv2D(width, planes * self.expansion, 1, bias_attr=False)
        self.bn3 = norm_layer(planes * self.expansion)
        self.relu = nn.ReLU()
        self.downsample = downsample

    def forward(self, x):
        identity = x
        out = self.relu(self.bn1(self.conv1(x)))
        out = self.relu(self.bn2(self.conv2(out)))
        out = self.bn3(self.conv3(out))
        if self.downsample is not None:
            identity = self.downsample(x)
        return self.relu(out + identity)


class ResNet(nn.Layer):
    def __init__(self, block, depth=50, width=64, num_classes=1000, with_pool=True,
                 groups=1):
        super().__init__()
        layer_cfg = {
            18: [2, 2, 2, 2], 34: [3, 4, 6, 3], 50: [3, 4, 6, 3],
            101: [3, 4, 23, 3], 152: [3, 8, 36, 3],
        }
        layers = layer_cfg[depth]
        self.groups = groups
        self.base_width = width
        self.num_classes = num_classes
        self.with_pool = with_pool
        self._norm_layer = nn.BatchNorm2D
        self.inplanes = 64
        self.dilation = 1
        self.conv1 = nn.Conv2D(3, self.inplanes, 7, stride=2, padding=3,
                               bias_attr=False)
        self.bn1 = self._norm_layer(self.inplanes)
        self.relu = nn.ReLU()
        self.maxpool = nn.MaxPool2D(3, 2, 1)
        self.layer1 = self._make_layer(block, 64, layers[0])
        self.layer2 = self._make_layer(block, 128, layers[1], stride=2)
        self.layer3 = self._make_layer(block, 256, layers[2], stride=2)
        self.layer4 = self._make_layer(block, 512, layers[3], stride=2)
        if with_pool:
            self.avgpool = nn.AdaptiveAvgPool2D((1, 1))
        if num_classes > 0:
            self.fc = nn.Linear(512 * block.expansion, num_classes)

    def _stem(self, x):
        """The 7x7/2 stem; PADDLE_TPU_S2D_STEM=1 opts into the exact
        space-to-depth reformulation (vision.ops.space_to_depth_stem_conv
        — C_in=3 under-fills the MXU; s2d quadruples the contraction).
        Default OFF: measured ~5% SLOWER end-to-end on v5e (1492 vs 1564
        samples/s, b=64 bf16) — XLA already handles the stem well and
        the pad/regroup reshapes cost more than the conv saves;
        the classic trick is kept as a knob for topologies where it pays."""
        import os

        import jax

        w = getattr(self.conv1, "weight", None)
        if (os.environ.get("PADDLE_TPU_S2D_STEM", "0") == "1"
                and jax.default_backend() == "tpu"
                and x.ndim == 4 and x.shape[2] % 2 == 0
                and x.shape[3] % 2 == 0
                # the reformulation encodes EXACTLY 7x7/stride-2/pad-3
                # bias-free semantics: a customized stem (CIFAR 3x3 etc.)
                # must take the generic conv
                and w is not None and tuple(w.shape[2:]) == (7, 7)
                and self._stem_attr_is(self.conv1, "_stride", 2)
                and self._stem_attr_is(self.conv1, "_dilation", 1)
                and getattr(self.conv1, "_groups", 1) == 1
                and self._stem_attr_is(self.conv1, "_padding", 3)
                and getattr(self.conv1, "bias", None) is None):
            from ..ops import space_to_depth_stem_conv

            return space_to_depth_stem_conv(x, w)
        return self.conv1(x)

    @staticmethod
    def _stem_attr_is(conv, name, value):
        """True iff conv's attr equals ``value`` in every spatial position
        — accepts int, list, tuple, or nested forms; anything unparseable
        safely fails the guard (generic conv path)."""
        v = getattr(conv, name, None)
        if isinstance(v, (int, np.integer)):
            return int(v) == value
        try:
            arr = np.ravel(np.asarray(v))
            return arr.size > 0 and all(int(p) == value for p in arr)
        except Exception:
            return False

    def _make_layer(self, block, planes, blocks, stride=1):
        norm_layer = self._norm_layer
        downsample = None
        if stride != 1 or self.inplanes != planes * block.expansion:
            downsample = nn.Sequential(
                nn.Conv2D(self.inplanes, planes * block.expansion, 1, stride=stride,
                          bias_attr=False),
                norm_layer(planes * block.expansion),
            )
        layers = [block(self.inplanes, planes, stride, downsample, self.groups,
                        self.base_width, self.dilation, norm_layer)]
        self.inplanes = planes * block.expansion
        for _ in range(1, blocks):
            layers.append(block(self.inplanes, planes,
                                groups=self.groups, base_width=self.base_width,
                                norm_layer=norm_layer))
        return nn.Sequential(*layers)

    def forward(self, x):
        x = self.relu(self.bn1(self._stem(x)))
        x = self.maxpool(x)
        x = self.layer1(x)
        x = self.layer2(x)
        x = self.layer3(x)
        x = self.layer4(x)
        if self.with_pool:
            x = self.avgpool(x)
        if self.num_classes > 0:
            from ...tensor.manipulation import flatten

            x = flatten(x, 1, -1)
            x = self.fc(x)
        return x


def _resnet(block, depth, pretrained=False, **kwargs):
    model = ResNet(block, depth, **kwargs)
    if pretrained:
        raise NotImplementedError(
            "pretrained weights require network access; load a local "
            "checkpoint with model.set_state_dict(paddle_tpu.load(path))"
        )
    return model


def resnet18(pretrained=False, **kwargs):
    return _resnet(BasicBlock, 18, pretrained, **kwargs)


def resnet34(pretrained=False, **kwargs):
    return _resnet(BasicBlock, 34, pretrained, **kwargs)


def resnet50(pretrained=False, **kwargs):
    return _resnet(BottleneckBlock, 50, pretrained, **kwargs)


def resnet101(pretrained=False, **kwargs):
    return _resnet(BottleneckBlock, 101, pretrained, **kwargs)


def resnet152(pretrained=False, **kwargs):
    return _resnet(BottleneckBlock, 152, pretrained, **kwargs)
