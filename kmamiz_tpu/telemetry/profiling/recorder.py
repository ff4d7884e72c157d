"""SLO-breach flight recorder: freeze the evidence when serving degrades.

When the tick watchdog fires, an upstream circuit breaker opens, or a
scenario gate fails, `record(trigger)` snapshots the last-N-ticks of
host events, the span-trace ring, the SLO scorecard rows (process-wide
and per-tenant), the native graftprof counters, the compile-cause log,
and the HBM watermark timeline into one JSON artifact under
``KMAMIZ_PROF_FLIGHT_DIR`` — the crash-box an operator (or the scenario
runner's stderr table) opens *after* the incident, instead of trying to
reproduce it.

Discipline: `record` never raises, debounces trigger storms
(``KMAMIZ_PROF_FLIGHT_DEBOUNCE_S``, breaker flaps would otherwise write
hundreds of artifacts), keeps bounded retention
(``KMAMIZ_PROF_FLIGHT_MAX`` newest artifacts survive), and writes
atomically (tmp + rename) so a reader never sees a torn file. Trigger
sites import this module lazily — the resilience layer must not pay for
profiling at import time.

Sweep safety: a caller may pass ``namespace`` (the soak runner uses
``<archetype>-<seed>``) to get ``flight-<namespace>-*.json`` names with
retention AND debounce applied per namespace — two scenario cells
failing back-to-back can never evict or suppress each other's evidence
box (docs/OBSERVABILITY.md).
"""
from __future__ import annotations

import itertools
import json
import logging
import os
import re
import threading
import time
from typing import Optional

from . import events

logger = logging.getLogger("kmamiz_tpu.telemetry.profiling")

ARTIFACT_KIND = "kmamiz-flight"
ARTIFACT_VERSION = 1

_lock = threading.Lock()
_last_dump_by_ns: dict = {}
_seq = itertools.count(1)

_SAFE_TRIGGER = re.compile(r"[^A-Za-z0-9_.-]+")
#: a legacy (un-namespaced) artifact: flight-<epoch ms>-<seq>-<slug>.json
_LEGACY_NAME = re.compile(r"^flight-\d{13}-")


def flight_dir() -> str:
    return os.environ.get("KMAMIZ_PROF_FLIGHT_DIR") or os.path.join(
        "kmamiz-data", "flight"
    )


def flight_ticks() -> int:
    try:
        return max(1, int(os.environ.get("KMAMIZ_PROF_FLIGHT_TICKS", "64")))
    except ValueError:
        return 64


def flight_max() -> int:
    try:
        return max(1, int(os.environ.get("KMAMIZ_PROF_FLIGHT_MAX", "16")))
    except ValueError:
        return 16


def _debounce_s() -> float:
    try:
        return max(
            0.0, float(os.environ.get("KMAMIZ_PROF_FLIGHT_DEBOUNCE_S", "5"))
        )
    except ValueError:
        return 5.0


def build_artifact(trigger: str, detail: str = "") -> dict:
    """The flight artifact dict (separate from I/O so tests and
    /debug/graftprof can inspect it without touching disk)."""
    from .. import slo, tracing
    from . import device_attr, native_counters

    keep = flight_ticks()
    return {
        "kind": ARTIFACT_KIND,
        "version": ARTIFACT_VERSION,
        "trigger": trigger,
        "detail": detail,
        "wall_s": round(time.time(), 3),
        "flight_ticks": keep,
        "events": [list(e) for e in events.snapshot(last_ticks=keep)],
        "traces": [
            {
                "traceId": tb.trace_id,
                "wallUs": tb.wall_us,
                "status": tb.status,
                "spans": [list(s) for s in tb.spans],
            }
            for tb in tracing.TRACER.traces()[-keep:]
        ],
        "scorecard": slo.SCORECARD.snapshot(),
        "tenants": slo.TENANTS.snapshot(),
        "native": native_counters.counters(),
        "compileLog": device_attr.compile_log(),
        "hbmTimeline": device_attr.hbm_timeline(),
    }


def record(
    trigger: str,
    detail: str = "",
    force: bool = False,
    namespace: Optional[str] = None,
) -> Optional[str]:
    """Dump a flight artifact; returns its path, or None when skipped
    (profiling off, debounced) or failed. NEVER raises — the trigger
    sites are the resilience layer's own failure paths. ``namespace``
    isolates a scenario cell's evidence: its own filename prefix, its
    own debounce clock, its own retention budget."""
    try:
        return _record(trigger, detail, force, namespace)
    except Exception as exc:  # noqa: BLE001 - recorder must not re-fail a failure path
        logger.warning("flight recorder dump failed: %s", exc)
        return None


def _safe_namespace(namespace: Optional[str]) -> Optional[str]:
    if namespace is None:
        return None
    ns = _SAFE_TRIGGER.sub("-", str(namespace)).strip("-")
    # a purely-numeric namespace could collide with the legacy
    # epoch-ms name pattern; anchor it with a letter
    return f"ns-{ns}" if not ns or ns.isdigit() else ns


def _record(
    trigger: str, detail: str, force: bool, namespace: Optional[str]
) -> Optional[str]:
    events.refresh_from_env()
    if not events.prof_enabled() and not force:
        return None
    ns = _safe_namespace(namespace)
    now = time.monotonic()
    with _lock:
        # a namespace that never dumped is not debounced: monotonic counts
        # from boot, so a default of 0.0 would swallow the first dump of a
        # host up for less than the debounce interval
        last = _last_dump_by_ns.get(ns)
        if not force and last is not None and (now - last) < _debounce_s():
            return None
        _last_dump_by_ns[ns] = now
        seq = next(_seq)
    artifact = build_artifact(trigger, detail)
    if ns is not None:
        artifact["namespace"] = ns
    out_dir = flight_dir()
    os.makedirs(out_dir, exist_ok=True)
    slug = _SAFE_TRIGGER.sub("-", trigger) or "trigger"
    stamp = f"{int(time.time() * 1000):013d}-{seq:04d}-{slug}.json"
    fname = f"flight-{ns}-{stamp}" if ns is not None else f"flight-{stamp}"
    path = os.path.join(out_dir, fname)
    tmp = path + ".tmp"
    with open(tmp, "w", encoding="utf-8") as f:
        json.dump(artifact, f, separators=(",", ":"))
    os.replace(tmp, path)
    _prune(out_dir, ns)
    return path


def _prune(out_dir: str, namespace: Optional[str] = None) -> None:
    """Bounded retention PER NAMESPACE: keep the newest flight_max()
    artifacts of this record's namespace (timestamped names sort
    chronologically within one namespace). Legacy un-namespaced
    artifacts form their own retention group, so a sweep's per-cell
    evidence never evicts an operator's ad-hoc dumps (or vice versa)."""
    if namespace is None:
        def mine(name: str) -> bool:
            return bool(_LEGACY_NAME.match(name))
    else:
        prefix = f"flight-{namespace}-"

        def mine(name: str) -> bool:
            return name.startswith(prefix) and bool(
                _LEGACY_NAME.match("flight-" + name[len(prefix):])
            )

    try:
        names = sorted(
            n
            for n in os.listdir(out_dir)
            if n.startswith("flight-") and n.endswith(".json") and mine(n)
        )
    except OSError:
        return
    for stale in names[: -flight_max()] if len(names) > flight_max() else []:
        try:
            os.remove(os.path.join(out_dir, stale))
        except OSError:
            pass


def reset_for_tests() -> None:
    global _seq
    with _lock:
        _last_dump_by_ns.clear()
        _seq = itertools.count(1)
