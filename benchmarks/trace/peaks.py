"""Published peaks of the chips this benchmark may run on, keyed by the
`device_kind` JAX reports. A kind that is not here is an error, never a
default.

Source: Google Cloud documentation, "TPU v5e" system architecture: 197
TFLOP/s bf16, 16 GB of HBM2e at 819 GB/s per chip.
"""
from __future__ import annotations

PEAKS = {
    # JAX's device_kind of a v5e chip
    "TPU v5 lite": {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12},
}


def of(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no published peaks for device kind {device_kind!r}; add it to "
            "benchmarks/trace/peaks.py with its source"
        ) from None
