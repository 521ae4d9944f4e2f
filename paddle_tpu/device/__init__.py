"""paddle.device — device query/selection module (parity:
/root/reference/python/paddle/device.py). The accelerator here is the
attached TPU; CUDA-named entry points report no CUDA devices, matching the
reference's behavior on a CPU-only build."""
from __future__ import annotations

import os

import jax

from ..core.place import (CPUPlace, CUDAPlace, Place, TPUPlace, get_device,
                          set_device)

__all__ = ["get_device", "set_device", "get_all_device_type",
           "get_all_custom_device_type", "get_available_device",
           "get_available_custom_device", "is_compiled_with_cuda",
           "is_compiled_with_rocm", "is_compiled_with_xpu",
           "is_compiled_with_npu", "device_count", "cuda", "XPUPlace",
           "configure_compilation_cache"]


# the cache directory is part of what the next process must find again: a
# path made from a temp name, pid or timestamp never hits
_DEFAULT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".compile_cache")


def configure_compilation_cache() -> str | None:
    """Place JAX's persistent compilation cache so a warm process restart
    skips XLA compilation (the reference has no equivalent — its per-op
    executor recompiles nothing, but every XLA program here costs seconds
    to minutes to build).

    Where ``JAX_COMPILATION_CACHE_DIR`` is set JAX's own handling stands
    and no directory is set in code. Otherwise the cache is the fixed
    ``.compile_cache/`` at the root of the checkout — except in a process
    pinned to the CPU platform (the test mesh, dry runs), which gets none:
    CPU compiles are cheap, and XLA:CPU in this jaxlib logs a spurious
    machine-feature mismatch at ERROR level for every entry it loads.
    The size and compile-time thresholds are dropped to zero: model init
    and eager mode are hundreds of small programs, exactly the long tail
    a restart replays. Returns the directory in effect
    (``jax.config.jax_compilation_cache_dir``)."""
    if not (os.environ.get("JAX_COMPILATION_CACHE_DIR")
            or jax.config.jax_platforms == "cpu"):
        jax.config.update("jax_compilation_cache_dir", _DEFAULT_CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return jax.config.jax_compilation_cache_dir


# at import, so every entry point (bench, serving, user scripts) shares one
# cache without code changes; touches jax.config only, never a backend
configure_compilation_cache()


def get_all_device_type():
    types = ["cpu"]
    if any(d.platform == "tpu" for d in jax.devices()):
        types.append("tpu")
    return types


def get_all_custom_device_type():
    return [t for t in get_all_device_type() if t not in ("cpu", "gpu")]


def get_available_device():
    return [f"{d.platform}:{d.id}" for d in jax.devices()]


def get_available_custom_device():
    return [d for d in get_available_device() if not d.startswith(("cpu",
                                                                   "gpu"))]


def is_compiled_with_cuda() -> bool:
    return False


def is_compiled_with_rocm() -> bool:
    return False


def is_compiled_with_xpu() -> bool:
    return False


def is_compiled_with_npu() -> bool:
    return False


def device_count() -> int:
    """Accelerator count visible to this process."""
    return len(jax.devices())


def XPUPlace(dev_id=0):  # signature parity; the accelerator is the TPU
    return TPUPlace(dev_id)


class _Cuda:
    """paddle.device.cuda namespace — CUDA is absent on this build, so
    counts are zero and synchronize is a barrier on the actual device
    (parity with the reference's graceful no-CUDA behavior)."""

    @staticmethod
    def device_count() -> int:
        return 0

    @staticmethod
    def synchronize(device=None):
        import jax.numpy as _jnp

        for d in jax.devices():
            # a tiny computation enqueued AFTER prior work on d's stream;
            # waiting for it waits for everything before it
            # tpu-lint: disable-next=R5 -- the barrier itself: one wait per device
            (jax.device_put(_jnp.zeros(()), d) + 1).block_until_ready()

    @staticmethod
    def empty_cache():
        pass


cuda = _Cuda()
