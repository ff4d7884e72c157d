"""Process-wide resilience counters, backed by the telemetry registry.

One source of truth instead of counters scattered across modules: the
ingest ring's backpressure drops (processor._put), the operator's
external-DP fallback activations, watchdog trips + last-good serving
metadata, per-job scheduler failure streaks, and quarantine/WAL totals
all land here and surface together as the `resilience` section of
GET /health/timings (api/handlers/health.py) and the DP server's
/timings.

Since PR 6 the flat counters are registry handles
(kmamiz_tpu/telemetry/registry.py): `incr("ingestDropped")` bumps the
same Counter object `GET /metrics` renders as
`kmamiz_ingest_dropped_total`, so the Prometheus view, /health, and
/timings can never disagree — they read the identical cell. Known names
get module-scope handles (the hot ingest path never formats a label);
unknown names (retry.*, quarantined.*) register once on first use.

Job streaks and watchdog trip metadata stay structured dicts (they
carry strings/timestamps), mirrored into gauges at scrape time via a
registry callback.
"""
from __future__ import annotations

import threading
from typing import Dict, Optional

from kmamiz_tpu.telemetry import slo as _slo
from kmamiz_tpu.telemetry.profiling import events as prof_events
from kmamiz_tpu.telemetry.registry import REGISTRY

_LOCK = threading.Lock()

#: generic flat counters ride one labeled family...
_FAM = REGISTRY.counter_family(
    "kmamiz_resilience_total", "Flat resilience counters", ("counter",)
)
#: ...except the SLO-scorecard counters, which alias the scorecard's own
#: handles so rate numerators match /metrics exactly
_HANDLES: Dict[str, object] = {
    "ingestDropped": _slo.INGEST_DROPPED,
    "quarantined": _slo.QUARANTINED,
    "dpFallback": _FAM.handle("dpFallback"),
    "scorerHostFallback": _FAM.handle("scorerHostFallback"),
    "walRecords": _FAM.handle("walRecords"),
    "walAppendErrors": _FAM.handle("walAppendErrors"),
    "walReplays": _FAM.handle("walReplays"),
}

_WATCHDOG_TRIPS = REGISTRY.counter(
    "kmamiz_watchdog_trips_total", "Tick watchdog trips"
)

#: per-scheduler-job failure tracking: name -> {consecutiveFailures,
#: totalFailures, lastError, lastFailureAt}
_JOBS: Dict[str, dict] = {}

#: watchdog state: trips, per-reason counts, last trip, last-good tick
_WATCHDOG: Dict[str, object] = {
    "trips": 0,
    "byReason": {},
    "lastTripReason": None,
    "lastTripAt": None,
    "lastGoodVersion": None,
    "lastGoodLabelEpoch": None,
    "lastGoodAt": None,
}


def _handle(name: str):
    h = _HANDLES.get(name)
    if h is None:
        with _LOCK:
            h = _HANDLES.get(name)
            if h is None:
                # cold first-use registration (retry.*, quarantined.*);
                # cached, so steady state is a dict hit
                h = _FAM.handle(name)  # graftlint: disable=hot-path-metric-label -- first-use registration, cached in _HANDLES thereafter
                _HANDLES[name] = h
    return h


def incr(name: str, by: int = 1) -> int:
    """Bump a named counter; returns the new value."""
    h = _handle(name)
    h.inc(by)
    return int(h.value)


def get(name: str) -> int:
    h = _HANDLES.get(name)
    return int(h.value) if h is not None else 0


def job_failed(name: str, err: BaseException, now_ms: Optional[float] = None) -> None:
    """Record one scheduled-job failure (scheduler.py's except arms):
    the consecutive-failure streak and last error string make swallowed
    exceptions visible in /health instead of only in debug logs."""
    with _LOCK:
        entry = _JOBS.setdefault(
            name,
            {
                "consecutiveFailures": 0,
                "totalFailures": 0,
                "lastError": None,
                "lastFailureAt": None,
            },
        )
        entry["consecutiveFailures"] += 1
        entry["totalFailures"] += 1
        entry["lastError"] = f"{type(err).__name__}: {err}"[:500]
        entry["lastFailureAt"] = (
            now_ms if now_ms is not None else prof_events.wall_ms()
        )


def job_succeeded(name: str) -> None:
    """Reset a job's consecutive-failure streak (its history remains)."""
    with _LOCK:
        entry = _JOBS.get(name)
        if entry is not None:
            entry["consecutiveFailures"] = 0


def reset_job_streaks(names=None, prefix=None) -> None:
    """Drop per-job failure state for `names`, every job under `prefix`
    (the tenancy layer's ``<tenant>/`` namespace — one tenant's job
    restart resets only that tenant's streaks), or all jobs. Called by
    Scheduler.start() so a scheduler (re)start begins every registered
    job from a clean slate — a streak accumulated by a previous
    scheduler instance (in-process restart, handover, tests) must not
    leak into the new instance's /health as if the new jobs were
    failing."""
    with _LOCK:
        if names is None and prefix is None:
            _JOBS.clear()
            return
        for n in names or ():
            _JOBS.pop(n, None)
        if prefix is not None:
            for n in [k for k in _JOBS if k.startswith(prefix)]:
                _JOBS.pop(n, None)


def job_states() -> Dict[str, dict]:
    with _LOCK:
        return {name: dict(entry) for name, entry in _JOBS.items()}


def watchdog_tripped(reason: str, now_ms: Optional[float] = None) -> None:
    _WATCHDOG_TRIPS.inc()
    with _LOCK:
        _WATCHDOG["trips"] = int(_WATCHDOG["trips"]) + 1
        by = _WATCHDOG["byReason"]
        by[reason] = by.get(reason, 0) + 1
        _WATCHDOG["lastTripReason"] = reason
        _WATCHDOG["lastTripAt"] = (
            now_ms if now_ms is not None else prof_events.wall_ms()
        )
    # a trip is an SLO breach: freeze the graftprof evidence (lazy import
    # keeps the resilience layer free of profiling at module load;
    # record() debounces and never raises)
    from kmamiz_tpu.telemetry.profiling import recorder

    recorder.record("watchdog", reason)


def note_last_good(
    version: int, label_epoch: int, now_ms: Optional[float] = None
) -> None:
    """Record the (graph version, label epoch) of the newest fully
    successful collect tick — the payload the degraded path serves."""
    with _LOCK:
        _WATCHDOG["lastGoodVersion"] = int(version)
        _WATCHDOG["lastGoodLabelEpoch"] = int(label_epoch)
        _WATCHDOG["lastGoodAt"] = (
            now_ms if now_ms is not None else prof_events.wall_ms()
        )


def note_stale_serve() -> None:
    # same handle the SLO scorecard's stale-serve rate reads
    _slo.STALE_SERVES.inc()


def watchdog_state(now_ms: Optional[float] = None) -> dict:
    with _LOCK:
        out = {
            "trips": _WATCHDOG["trips"],
            "byReason": dict(_WATCHDOG["byReason"]),
            "lastTripReason": _WATCHDOG["lastTripReason"],
            "lastTripAt": _WATCHDOG["lastTripAt"],
            "lastGoodVersion": _WATCHDOG["lastGoodVersion"],
            "lastGoodLabelEpoch": _WATCHDOG["lastGoodLabelEpoch"],
            "lastGoodAt": _WATCHDOG["lastGoodAt"],
            "staleServes": int(_slo.STALE_SERVES.value),
        }
    if out["lastGoodAt"] is not None:
        now = now_ms if now_ms is not None else prof_events.wall_ms()
        out["lastGoodAgeMs"] = max(0.0, round(now - out["lastGoodAt"], 1))
    return out


def resilience_summary() -> dict:
    """The full `resilience` payload for the health handlers: breaker
    states, quarantine totals, watchdog/last-good, job streaks, and the
    flat counters (ingestDropped, dpFallback, ...)."""
    from kmamiz_tpu.resilience.breaker import breaker_states
    from kmamiz_tpu.resilience.quarantine import (
        quarantine_stats,
        tenant_quarantine_stats,
    )

    with _LOCK:
        counters = {
            name: int(h.value) for name, h in _HANDLES.items() if h.value
        }
    return {
        "breakers": breaker_states(),
        "quarantine": quarantine_stats(),
        "tenantQuarantine": tenant_quarantine_stats(),
        "watchdog": watchdog_state(),
        "jobs": job_states(),
        "counters": counters,
        "ingestDropped": counters.get("ingestDropped", 0),
        "dpFallback": counters.get("dpFallback", 0),
        # device scorer raised and the API answered from the host path
        "scorerHostFallback": counters.get("scorerHostFallback", 0),
    }


def _scrape_jobs() -> None:
    """Scrape-time mirror of the job streak dicts into gauges."""
    for name, entry in job_states().items():
        _JOB_STREAK.handle(name).set(entry["consecutiveFailures"])
        _JOB_FAILS.handle(name).set(entry["totalFailures"])


_JOB_STREAK = REGISTRY.gauge_family(
    "kmamiz_job_consecutive_failures", "Scheduler job failure streak", ("job",)
)
_JOB_FAILS = REGISTRY.gauge_family(
    "kmamiz_job_failures_total", "Scheduler job total failures", ("job",)
)
REGISTRY.register_callback(_scrape_jobs)


def reset_for_tests() -> None:
    """Zero every registry (test isolation only). Delegates the counter
    cells to the telemetry registry's reset so both views restart from
    the same zeros."""
    REGISTRY.reset_for_tests()
    with _LOCK:
        _JOBS.clear()
        _WATCHDOG.update(
            {
                "trips": 0,
                "byReason": {},
                "lastTripReason": None,
                "lastTripAt": None,
                "lastGoodVersion": None,
                "lastGoodLabelEpoch": None,
                "lastGoodAt": None,
            }
        )
