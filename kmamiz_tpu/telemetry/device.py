"""Device telemetry: which device this process holds, HBM residency
gauges and on-demand profiler capture.

`device_block()` is what every server reports about the accelerator it
runs on (DP `/timings`, API `/api/v1/health`): platform, device kind,
count, versions, whether the deployed path is sharding over a mesh, and
`memory_stats()` of EVERY local device — so "everything landed on
device 0" is seen rather than assumed.

Two gauge sources, merged at scrape time (never on the tick):

- `memory_stats()` of every local device, where the backend supports it
  (TPU does; CPU returns None) — bytes_in_use / peak / limit as
  `kmamiz_device_*{device=...}` gauges.
- Tracked arena sizes: device-resident subsystems (graph-store edge
  arena, endpoint metadata, staged streaming buffers, scorer caches)
  report their allocation sizes via `track_arena`, exported per-arena
  as `kmamiz_arena_bytes{arena=...}`. This is the fallback accounting
  when `memory_stats()` is unavailable, and the per-subsystem breakdown
  when it is.

Profiling: `capture_profile(duration_ms)` wraps `jax.profiler`
start/stop for `POST /debug/profile` — one capture at a time, written
under `KMAMIZ_PROFILE_DIR` (or an explicit directory).
"""
from __future__ import annotations

import os
import threading
import time
from typing import Callable, Dict, List, Optional

from .registry import REGISTRY

_ARENA_BYTES = REGISTRY.gauge_family(
    "kmamiz_arena_bytes",
    "Tracked device-resident allocation bytes per arena",
    ("arena",),
)
_DEV_IN_USE = REGISTRY.gauge_family(
    "kmamiz_device_bytes_in_use",
    "Device bytes in use (memory_stats)",
    ("device",),
)
_DEV_PEAK = REGISTRY.gauge_family(
    "kmamiz_device_bytes_peak",
    "Peak device bytes in use (memory_stats)",
    ("device",),
)
_DEV_LIMIT = REGISTRY.gauge_family(
    "kmamiz_device_bytes_limit",
    "Device memory limit (memory_stats)",
    ("device",),
)

_arena_sources: Dict[str, Callable[[], float]] = {}
_arena_handles: Dict[str, object] = {}
_arena_lock = threading.Lock()


def track_arena(name: str, size_fn: Callable[[], float]) -> None:
    """Register a pull source for one arena's byte size. Called at init
    scope by the owning subsystem; `size_fn` runs only at scrape time."""
    with _arena_lock:
        _arena_sources[name] = size_fn
        if name not in _arena_handles:
            _arena_handles[name] = _ARENA_BYTES.handle(name)


def local_memory_stats() -> List[dict]:
    """`memory_stats()` of every local device, one row per device
    (``{"id": ...}`` alone where the backend reports none)."""
    import jax

    return [
        {"id": int(d.id), **(d.memory_stats() or {})}
        for d in jax.local_devices()
    ]


def device_memory_stats() -> Optional[dict]:
    """Device 0's memory_stats (the per-tick HBM watermark sample)."""
    import jax

    return jax.local_devices()[0].memory_stats()


def _libtpu_version() -> Optional[str]:
    from importlib import metadata

    try:
        return metadata.version("libtpu")
    except metadata.PackageNotFoundError:
        return None


def device_block() -> dict:
    """The accelerator this process holds, as JAX reports it."""
    import jax
    import jaxlib

    from kmamiz_tpu.parallel.mesh import active_mesh

    first = jax.devices()[0]
    mesh = active_mesh()
    return {
        "platform": first.platform,
        "device_kind": first.device_kind,
        "count": len(jax.devices()),
        "jax": jax.__version__,
        "jaxlib": jaxlib.__version__,
        "libtpu": _libtpu_version(),
        # the deployed path shards over this mesh (None: single device)
        "mesh": (
            None
            if mesh is None
            else {name: int(n) for name, n in mesh.shape.items()}
        ),
        "memory": local_memory_stats(),
    }


def runtime_report() -> dict:
    """What this process runs on and what it runs, for the servers'
    timing routes and chip_smoke.py: the device as JAX reports it, the
    native parser's provenance, where compiled programs are cached, and
    which sparse kernels were routed where."""
    from kmamiz_tpu import native
    from kmamiz_tpu.core import compile_cache
    from kmamiz_tpu.ops import sparse

    return {
        "device": device_block(),
        "native": native.build_report(),
        "compileCache": compile_cache.stats(),
        "sparse": sparse.route_stats(),
    }


def _collect() -> None:
    with _arena_lock:
        items = list(_arena_sources.items())
    for name, fn in items:
        try:
            _arena_handles[name].set(float(fn()))
        except Exception:
            pass
    for row in local_memory_stats():
        dev = str(row["id"])
        _DEV_IN_USE.handle(dev).set(float(row.get("bytes_in_use", 0) or 0))
        _DEV_PEAK.handle(dev).set(float(row.get("peak_bytes_in_use", 0) or 0))
        _DEV_LIMIT.handle(dev).set(float(row.get("bytes_limit", 0) or 0))


REGISTRY.register_callback(_collect)


# -- on-demand profiler capture (POST /debug/profile) --------------------

_PROFILES = REGISTRY.counter(
    "kmamiz_profile_captures_total", "On-demand jax.profiler captures"
)


def profile_max_s() -> float:
    """KMAMIZ_PROFILE_MAX_S: the hard bound on one on-demand capture
    window (default 10 s) — a fat durationMs must not hold the profiler
    guard (and the capture thread) for a minute."""
    try:
        return max(0.001, float(os.environ.get("KMAMIZ_PROFILE_MAX_S", "10")))
    except ValueError:
        return 10.0


def capture_profile(duration_ms: int, out_dir: Optional[str] = None) -> dict:
    """Capture a jax.profiler trace for `duration_ms` to `out_dir`
    (default `KMAMIZ_PROFILE_DIR`, else ./kmamiz-data/profiles). Blocks
    the caller for the capture window, clamped to ``KMAMIZ_PROFILE_MAX_S``.

    One profiler session at a time, PROCESS-wide: the guard is shared
    with `core.profiling.trace` (jax.profiler cannot nest sessions, so a
    tick-scoped trace and an on-demand capture stacking would raise from
    inside the tick). A busy guard answers ``busy: True`` — the server
    maps it to 409."""
    from kmamiz_tpu.core import profiling as core_profiling

    target = out_dir or os.environ.get("KMAMIZ_PROFILE_DIR") or os.path.join(
        "kmamiz-data", "profiles"
    )
    duration_ms = max(1, min(int(duration_ms), int(profile_max_s() * 1000)))
    if not core_profiling._trace_guard.acquire(blocking=False):
        return {
            "ok": False,
            "busy": True,
            "error": "capture already in progress",
        }
    try:
        os.makedirs(target, exist_ok=True)
        import jax

        jax.profiler.start_trace(target)
        try:
            time.sleep(duration_ms / 1000.0)
        finally:
            jax.profiler.stop_trace()
        _PROFILES.inc()
        return {"ok": True, "dir": target, "duration_ms": duration_ms}
    except Exception as exc:  # profiler unavailable on some backends
        return {"ok": False, "error": f"{type(exc).__name__}: {exc}"}
    finally:
        core_profiling._trace_guard.release()
