"""What the run is on. A run without the accelerator its cell asks for
fails; it never falls back to the CPU."""
from __future__ import annotations

from typing import Any, Dict


class NoAccelerator(RuntimeError):
    pass


def require(chips: int):
    """The devices of a cell that needs `chips`, or NoAccelerator."""
    import jax

    devices = jax.devices()
    if devices[0].platform == "cpu":
        raise NoAccelerator("JAX found no accelerator (platform cpu)")
    if len(devices) < chips:
        raise NoAccelerator(f"the cell needs {chips} chips, JAX found {len(devices)}")
    return devices[:chips]


def describe(devices) -> Dict[str, Any]:
    """The `device` block of the result line: as JAX reports the device,
    with the peak memory of the fullest chip."""
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in devices]
    return {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
        "memory_peak_bytes": int(max(peaks)),
    }
