"""graftscope: the self-tracing telemetry layer (docs/OBSERVABILITY.md).

Four parts behind one package:

- `registry`  — unified metrics registry (counters / gauges /
  fixed-bucket histograms, preallocated handles, Prometheus text
  exposition at `GET /metrics`).
- `tracing`   — span traces of each tick and each model refresh in a
  ring, exported as Zipkin v2 JSON at `GET /debug/traces`; the processor
  can re-ingest its own export (self-trace). Every span is also a
  `jax.profiler.TraceAnnotation`, so a profiler capture shows the
  program's spans on the device's clock.
- `device`    — HBM/arena residency gauges and the on-demand
  `POST /debug/profile` jax.profiler capture.
- `slo`       — the rolling SLO scorecard (`/timings`), whose keys
  `tools/slo_report.py` gates on.
- `profiling` — graftprof: the lock-free host event ring, native
  parse/merge contention counters, the compile-cause log and HBM
  timeline, and the SLO-breach flight recorder (`GET /debug/graftprof`,
  tools/graftprof.py).

`KMAMIZ_TELEMETRY=0` disables span capture; the metrics registry stays
live regardless (the resilience counters and `/timings` ride on it).
"""
from .registry import REGISTRY, MetricsRegistry  # noqa: F401
from .tracing import TRACER, phase_span, telemetry_enabled  # noqa: F401
from .slo import SCORECARD, TENANTS  # noqa: F401
from . import device  # noqa: F401  (registers its scrape callback)
from . import freshness  # noqa: F401  (registers its scrape callback)
from . import profiling  # noqa: F401  (registers its scrape callback + hooks)


def reset_for_tests() -> None:
    """Zero all metric values (keeping registered handles live), drop
    buffered traces, clear the scorecard windows (process-wide and
    per-tenant, including the tenant-label slug table), and empty the
    graftprof planes (event ring, native deltas, device logs)."""
    REGISTRY.reset_for_tests()
    TRACER.reset_for_tests()
    SCORECARD.reset_for_tests()
    TENANTS.reset_for_tests()
    freshness.reset_for_tests()
    profiling.reset_for_tests()
