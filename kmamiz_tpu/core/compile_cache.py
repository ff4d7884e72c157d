"""Persistent XLA compilation cache: one rule for where it lives.

Compiling the graph-union and scorer programs is the largest part of a
cold boot, so every entry point (dp_server.main, api.app.main,
fleet.worker.main, the tools, benchmarks/run.py, chip_smoke.py's children)
calls :func:`enable` before its first jit dispatch. The rule:

- ``JAX_COMPILATION_CACHE_DIR`` set: JAX itself reads it; this module
  does not touch ``jax_compilation_cache_dir``. Whoever runs the
  program places the cache (a mounted volume in the deployment, the
  chip tool's own directory on the bench machine).
- unset: the cache is ``<checkout>/.xla-cache`` (git-ignored). The
  directory name is part of JAX's cache key, so it is a fixed path —
  never a temp dir, a pid or a clock.

The shape-hint file (core/programs.py) lives beside whichever directory
this resolves to: hints name the programs, the cache holds them.

The persistent cache alone is NOT a fast restart: reloading a program
from disk still pays the jit trace+lower on first dispatch. The
registry's dispatch-replay prewarm moves that residue off the serving
path; this module only makes the replay load instead of compile.
"""
from __future__ import annotations

import logging
import os
import threading
from pathlib import Path

logger = logging.getLogger("kmamiz_tpu.compile_cache")

_ENV = "JAX_COMPILATION_CACHE_DIR"
_CHECKOUT = Path(__file__).resolve().parent.parent.parent

_lock = threading.Lock()
_enabled = False
#: JAX's own monitoring events, counted since enable(): requests that
#: consulted the cache, entries loaded from it, entries written to it
_counts = {"requests": 0, "hits": 0, "misses": 0}
_EVENTS = {
    "/jax/compilation_cache/compile_requests_use_cache": "requests",
    "/jax/compilation_cache/cache_hits": "hits",
    "/jax/compilation_cache/cache_misses": "misses",
}


def cache_dir() -> str:
    """The directory the persistent cache and the shape hints share."""
    return os.environ.get(_ENV) or str(_CHECKOUT / ".xla-cache")


def _on_event(event: str, **_kwargs) -> None:
    key = _EVENTS.get(event)
    if key is not None:
        with _lock:
            _counts[key] += 1


def enable() -> str:
    """Turn the persistent cache on at :func:`cache_dir`. Idempotent;
    call before the first jit dispatch. Returns the directory."""
    global _enabled
    directory = cache_dir()
    with _lock:
        if _enabled:
            return directory
        _enabled = True
    import jax

    if not os.environ.get(_ENV):
        os.makedirs(directory, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", directory)
    # cache everything: a first tick also runs a dozen sub-second
    # kernels whose compiles SUM to seconds — with the default 1 s floor
    # they would re-compile on every restart
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.monitoring.register_event_listener(_on_event)
    logger.info("persistent XLA compilation cache at %s", directory)
    return directory


def enabled() -> bool:
    """Whether :func:`enable` ran in this process (library use without
    it keeps neither a cache nor a hint file)."""
    with _lock:
        return _enabled


def stats() -> dict:
    """Cache placement and hit/miss counters for /timings and the smoke
    report. ``misses`` is JAX's name for entries compiled and written."""
    with _lock:
        return {
            "dir": cache_dir(),
            "placedBy": _ENV if os.environ.get(_ENV) else "checkout",
            "enabled": _enabled,
            **_counts,
        }
