"""What a driver hands back: everything the result line and the per-layer
readers are made from."""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, Dict, Optional

from benchmarks.harness.spans import Recorder


@dataclass
class Context:
    """What a driver is given. `t0` is the process's start on
    `time.perf_counter`, for `setup_s`."""

    manifest: Any  # harness.manifest.Manifest
    config: Dict[str, Any]
    traffic: Dict[str, Any]
    seed: int
    seconds: float
    trace: bool
    t0: float
    devices: Any


@dataclass
class Record:
    correct: bool
    attempted: int
    failed: int
    #: end-to-end metric name -> value, taken by the driver on the host clock
    end_to_end: Dict[str, float]
    recorder: Recorder
    manifest: Any  # harness.manifest.Manifest: readers find their files by it
    config: Dict[str, Any]
    traffic: Dict[str, Any]
    devices: Any
    #: trace.reduce.Reduced of the traced window, or None without --trace 1
    trace: Optional[Any] = None
    #: why `correct` is False, for the lines above the result
    notes: Dict[str, Any] = field(default_factory=dict)
    #: every number `correct` was decided from: name -> {"value", "limit"}
    compared: Dict[str, Dict[str, float]] = field(default_factory=dict)
