"""The one table of device peaks, keyed by ``device_kind`` as JAX reports
it. A device that is not here is an error, never a default."""
from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {
        "flops_per_s": 197e12,       # bf16
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
        "source": "Google Cloud documentation, TPU v5e",
    },
}


def peak(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise LookupError(
            f"no published peak for device kind {device_kind!r}: add it to "
            f"benchmark/lib/peaks.py with its source") from None
