"""Device-plane attribution: what compiled, and what the device held.

Two sources, both cold-path:

- **Compile-cause log** — `core/programs.py` reports every cache-entry
  growth (a real XLA compile) via `note_compile`; the ring here keeps
  the last N causes with program name, wall stamp, and compile ms, so
  "what recompiled and when" is answerable after the fact.
- **HBM watermark timeline** — a per-tick sample of the existing device
  gauges (`telemetry/device.device_memory_stats`), ring-buffered as
  ``(tick_id, bytes_in_use, peak_bytes)`` — the flight recorder freezes
  it next to the host events.

Device time per program is read from a profiler capture, not here:
`POST /debug/profile` writes the `.xplane.pb` that xprof/TensorBoard
opens, in which a device module carries its program's name
(`jit_sage_epoch_block`) and the tracer's spans lie beside it on the
device's clock (telemetry/tracing.py); a program's measured run time is
in the registry (`core/programs.Program.note_run`).
"""
from __future__ import annotations

import threading
import time
from collections import deque
from typing import List

from ..registry import REGISTRY
from . import events

_COMPILE_LOG_MAX = 256
_HBM_MAX = 1024

_lock = threading.Lock()
_compile_log: deque = deque(maxlen=_COMPILE_LOG_MAX)
_hbm: deque = deque(maxlen=_HBM_MAX)

_COMPILE_EVENTS = REGISTRY.counter(
    "kmamiz_prof_compile_events_total",
    "Compile-cause log entries recorded (program cache growth)",
)


def note_compile(program: str, compiles: int, elapsed_ms: float) -> None:
    """Compile-cause hook (called by core/programs.Program.__call__ when
    the jit cache grew). Compiles are cold by definition — the wall
    stamp is fine here."""
    entry = {
        "program": program,
        "compiles": int(compiles),
        "ms": round(float(elapsed_ms), 3),
        "wall_s": round(time.time(), 3),
        "tick": events._cur_tick,
    }
    with _lock:
        _compile_log.append(entry)
    _COMPILE_EVENTS.inc()
    events.emit("compile", int(elapsed_ms * 1e6))


def compile_log() -> List[dict]:
    with _lock:
        return list(_compile_log)


def _sample_hbm(tick_id: int) -> None:
    """Per-tick HBM watermark sample (events.on_tick_end hook)."""
    from ..device import device_memory_stats

    stats = device_memory_stats()
    if not stats:
        return
    with _lock:
        _hbm.append(
            (
                int(tick_id),
                int(stats.get("bytes_in_use", 0) or 0),
                int(stats.get("peak_bytes_in_use", 0) or 0),
            )
        )


events.on_tick_end(_sample_hbm)


def hbm_timeline() -> List[List[int]]:
    """(tick_id, bytes_in_use, peak_bytes) rows, oldest first."""
    with _lock:
        return [list(row) for row in _hbm]


def reset_for_tests() -> None:
    with _lock:
        _compile_log.clear()
        _hbm.clear()
