"""graftprof: continuous hot-path profiling for the serving plane.

Three attribution planes plus a crash-box, all bounded and all
off-hot-path:

- `events` — the lock-free host event ring (per-phase 4-tuples) and the
  sanctioned hot-path clocks.
- `native_counters` — the C++ parse/merge contention counters
  (per-shard parse ns, merge lock-wait ns, claim contention, intern
  probe stats) surfaced as registry families and per-tick ring deltas.
- `device_attr` — compile-cause log and HBM watermark timeline.
- `recorder` — the SLO-breach flight recorder (watchdog trip, breaker
  open, scenario gate failure freeze the last-N-ticks of evidence).
- `report` — profile condensation, text rendering, and per-phase
  regression diffing (tools/graftprof.py, /debug/graftprof).
"""
from __future__ import annotations

from . import device_attr, events, native_counters, recorder, report

__all__ = [
    "device_attr",
    "events",
    "native_counters",
    "recorder",
    "report",
    "reset_for_tests",
]


def reset_for_tests() -> None:
    """Clear every graftprof plane (wired into telemetry.reset_for_tests)."""
    events.reset_for_tests()
    native_counters.reset_for_tests()
    device_attr.reset_for_tests()
    recorder.reset_for_tests()
