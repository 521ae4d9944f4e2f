"""paddle_tpu — a TPU-native deep learning framework.

Capability parity with fluid-era PaddlePaddle (see /root/repo/SURVEY.md),
re-designed for TPU: jax/XLA for compute, pjit + named mesh axes for
distribution, Pallas for custom kernels. The public surface mirrors the
reference's ``paddle`` package so models port with an import swap.
"""
from __future__ import annotations

from . import core
from .core import (  # noqa: F401
    CPUPlace,
    CUDAPinnedPlace,
    CUDAPlace,
    Parameter,
    Place,
    TPUPlace,
    Tensor,
    bfloat16,
    bool_,
    complex64,
    complex128,
    float16,
    float32,
    float64,
    get_default_dtype,
    get_device,
    get_flags,
    int8,
    int16,
    int32,
    int64,
    is_compiled_with_cuda,
    is_compiled_with_tpu,
    is_grad_enabled,
    no_grad,
    enable_grad,
    seed,
    set_default_dtype,
    set_device,
    set_flags,
    set_grad_enabled,
    to_tensor,
    uint8,
)
from .core.rng import get_rng_state, set_rng_state  # noqa: F401
from .core.tensor import enable_grad as _enable_grad  # noqa: F401

from . import tensor  # noqa: E402  (attaches Tensor methods)
from .tensor import *  # noqa: E402,F401,F403

from . import autograd  # noqa: E402
from .autograd import grad  # noqa: E402,F401

# Subsystems below are imported lazily-by-layer as they land; each block is
# appended when its module exists so the package is importable mid-build.
from . import nn  # noqa: E402
from .nn.layer_base import Layer  # noqa: E402,F401
from .nn.initializer import LazyGuard  # noqa: E402,F401
from . import optimizer  # noqa: E402
from . import io  # noqa: E402
from . import metric  # noqa: E402
from . import amp  # noqa: E402
from . import jit  # noqa: E402
from .framework.io import save, load  # noqa: E402,F401
from . import framework  # noqa: E402
from . import static  # noqa: E402
from . import distributed  # noqa: E402
from . import vision  # noqa: E402
from . import text  # noqa: E402
from . import dataset  # noqa: E402
from . import utils  # noqa: E402
from . import profiler  # noqa: E402
from . import resilience  # noqa: E402
from . import hapi  # noqa: E402
from .hapi import Model  # noqa: E402,F401
from . import inference  # noqa: E402
from . import incubate  # noqa: E402
from . import quant  # noqa: E402
from . import distribution  # noqa: E402
from .hapi.summary import summary  # noqa: E402,F401
from .hapi.dynamic_flops import flops  # noqa: E402,F401
from . import callbacks  # noqa: E402
from . import device  # noqa: E402
from . import hub  # noqa: E402
from . import onnx  # noqa: E402
from . import reader  # noqa: E402
from . import sysconfig  # noqa: E402
from .batch import batch  # noqa: E402,F401


# dygraph-compat helpers
def disable_static(place=None):
    """Eager mode is the default (parity shim)."""
    return None


def enable_static():
    from .static import _enable_static_mode

    _enable_static_mode()


def disable_signal_handler():
    return None


def in_dynamic_mode() -> bool:
    from .static import _in_static_mode

    return not _in_static_mode()


# fluid-era export-parity aliases (reference python/paddle/__init__.py):
# dygraph mode toggles, device-place twins, RNG-state accessors, and
# Tensor/VarBase naming — all resolved onto the TPU-native equivalents
in_dygraph_mode = in_dynamic_mode
enable_dygraph = disable_static          # dygraph ON == static OFF
disable_dygraph = enable_static
DataParallel = nn.DataParallel
ParamAttr = nn.ParamAttr
VarBase = Tensor                          # fluid's eager tensor name
from .core.place import NPUPlace, XPUPlace  # noqa: E402,F401
from .core.dtype import convert_dtype as _convert_dtype  # noqa: E402
dtype = _convert_dtype                    # paddle.dtype('float32') coercion
from .tensor.math import floor_mod  # noqa: E402,F401
from .tensor.manipulation import crop as crop_tensor  # noqa: E402,F401


def check_shape(shape):
    """Validate a shape argument (fluid layer-helper parity): every entry
    an int (or -1/None for inferred dims)."""
    if shape is None:
        raise TypeError("shape must not be None")
    for s in (shape if isinstance(shape, (list, tuple)) else [shape]):
        if s is not None and not isinstance(s, (int,)):
            raise TypeError(f"shape entries must be int/None, got {type(s)}")
    return shape


def get_cudnn_version():
    """None — not compiled with cuDNN (the TPU build's truthful answer,
    same contract as the reference off-GPU)."""
    return None


def is_compiled_with_npu() -> bool:
    return False


def is_compiled_with_xpu() -> bool:
    return False


def get_cuda_rng_state():
    """Device RNG state (CUDA name kept for parity; returns the repo's
    device PRNG state list)."""
    from .core import rng as _rng

    return [_rng.default_generator().get_state()]


def set_cuda_rng_state(state_list):
    from .core import rng as _rng

    if not isinstance(state_list, (list, tuple)) or not state_list:
        raise ValueError("expects the list get_cuda_rng_state returned")
    _rng.default_generator().set_state(state_list[0])


__version__ = "0.1.0"
