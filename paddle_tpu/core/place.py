"""Device/Place model.

The reference's Place is a typed device identity used as the kernel-dispatch
key (/root/reference/paddle/fluid/platform/place.h:128). On TPU, XLA owns
kernel dispatch, so Place here is a thin identity that maps onto a
``jax.Device`` and is used for explicit data placement (``to_tensor(place=)``,
``Tensor.cuda()``-style moves become device_put) and for API parity.
"""
from __future__ import annotations

import functools

import jax


class Place:
    """Base device identity."""

    _kind = "unknown"

    def __init__(self, device_id: int = 0):
        self._device_id = int(device_id)

    def get_device_id(self) -> int:
        return self._device_id

    def __eq__(self, other):
        return (
            isinstance(other, Place)
            and self._kind == other._kind
            and self._device_id == other._device_id
        )

    def __hash__(self):
        return hash((self._kind, self._device_id))

    def __repr__(self):
        return f"Place({self._kind}:{self._device_id})"

    # -- mapping onto jax devices -------------------------------------------
    def jax_device(self) -> jax.Device:
        devs = [d for d in jax.devices() if _kind_of(d) == self._kind]
        if not devs:
            if self._kind == "tpu" and _has_tpu():
                raise RuntimeError(
                    f"{self!r}: a TPU backend is present but is not the "
                    f"default backend ({jax.default_backend()!r})")
            # parity behaviour for the CPU test mesh: code written against
            # an accelerator place runs on the host when NO TPU exists
            devs = jax.devices("cpu")
        return devs[min(self._device_id, len(devs) - 1)]


class CPUPlace(Place):
    _kind = "cpu"

    def __init__(self):
        super().__init__(0)

    def __repr__(self):
        return "Place(cpu)"


class TPUPlace(Place):
    _kind = "tpu"

    def __repr__(self):
        return f"Place(tpu:{self._device_id})"


# Parity alias: code written against the reference uses CUDAPlace for "the
# accelerator"; here the accelerator is the TPU.
CUDAPlace = TPUPlace
XPUPlace = TPUPlace
NPUPlace = TPUPlace


class TPUPinnedPlace(Place):
    """Host-pinned staging buffers; on TPU this is plain host memory."""

    _kind = "cpu"

    def __repr__(self):
        return "Place(tpu_pinned)"


CUDAPinnedPlace = TPUPinnedPlace


def _kind_of(dev: jax.Device) -> str:
    return "tpu" if dev.platform == "tpu" else dev.platform


_current_device: str | None = None


@functools.lru_cache(maxsize=None)
def _has_tpu() -> bool:
    try:
        return len(jax.devices("tpu")) > 0
    except RuntimeError as e:
        # "Unknown backend" = this process has no TPU platform at all (the
        # CPU test mesh). Anything else is a TPU that is present and failed
        # to initialise: that must stop the program, not turn into "cpu"
        if "Unknown backend" in str(e):
            return False
        raise


def is_compiled_with_tpu() -> bool:  # parity with is_compiled_with_cuda
    return _has_tpu()


is_compiled_with_cuda = is_compiled_with_tpu
is_compiled_with_xpu = is_compiled_with_tpu


def set_device(device: str):
    """Set the default device, e.g. 'tpu', 'tpu:0', 'cpu'."""
    global _current_device
    name = device.split(":")[0]
    if name == "gpu":
        name = "tpu"  # parity mapping: the accelerator is the TPU
    if name not in ("cpu", "tpu"):
        raise ValueError(f"unsupported device {device!r}; use 'cpu' or 'tpu'")
    _current_device = device.replace("gpu", "tpu")
    return get_device()


def get_device() -> str:
    if _current_device is not None:
        return _current_device
    return "tpu:0" if _has_tpu() else "cpu"


def _default_place() -> Place:
    dev = get_device()
    if dev.startswith("tpu"):
        idx = int(dev.split(":")[1]) if ":" in dev else 0
        return TPUPlace(idx)
    return CPUPlace()


def _place_from_any(place) -> Place:
    if place is None:
        return _default_place()
    if isinstance(place, Place):
        return place
    if isinstance(place, str):
        name = place.split(":")[0]
        idx = int(place.split(":")[1]) if ":" in place else 0
        if name in ("tpu", "gpu", "xpu", "npu"):
            return TPUPlace(idx)
        return CPUPlace()
    if isinstance(place, jax.Device):
        return TPUPlace(place.id) if place.platform == "tpu" else CPUPlace()
    raise TypeError(f"cannot interpret {place!r} as a Place")
