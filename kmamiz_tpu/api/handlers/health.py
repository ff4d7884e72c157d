"""Health REST handler (reference src/handler/HealthService.ts).

Beyond the reference's bare liveness probe, GET /timings exposes the
process-wide step timer (per-phase tick timings: parse / pack / transfer
/ merge / scorers) and the device graph's scorer-cache counters, so the
pipeline can be inspected in production without a profiler attached.
"""
from __future__ import annotations

import time
from typing import Optional

from kmamiz_tpu.api.router import IRequestHandler, Request, Response
from kmamiz_tpu.core import programs
from kmamiz_tpu.core.profiling import step_timer
from kmamiz_tpu.resilience import metrics as res_metrics


class HealthHandler(IRequestHandler):
    def __init__(self, ctx: Optional[object] = None) -> None:
        super().__init__("health")
        self._ctx = ctx
        self.add_route("get", "/", self._health)
        self.add_route("get", "/timings", self._timings)

    def _health(self, req: Request) -> Response:
        """Liveness + readiness: while the boot prewarm plan is running
        (core/programs.py), status is WARMING and — unless
        KMAMIZ_PREWARM_READY_GATE=0 — the HTTP status is 503, which the
        deploy readinessProbe (deploy/kmamiz-tpu.yaml) reads as
        not-ready, keeping traffic off the compile walls."""
        warm = programs.warm_state()
        if warm.get("status") == "warming" and programs.ready_gate_enabled():
            return Response(
                status=503,
                payload={
                    "status": "WARMING",
                    "serverTime": int(time.time() * 1000),
                    "prewarm": warm,
                },
            )
        return Response(
            payload={
                "status": "UP",
                "serverTime": int(time.time() * 1000),
                "prewarm": warm,
                "device": self._device(),
                # resilience at a glance: breaker states, scheduler-job
                # failure streaks, quarantine totals, watchdog trips
                "resilience": res_metrics.resilience_summary(),
            }
        )

    def _device(self) -> Optional[dict]:
        """The accelerator behind the in-process DataProcessor; None for
        the modes that run none (serve-only, simulator) and never
        import jax."""
        if getattr(self._ctx, "processor", None) is None:
            return None
        from kmamiz_tpu.telemetry import device as tel_device

        return tel_device.device_block()

    def _graph_sizes(self, graph) -> dict:
        """Edge counts of the two graphs a realtime tick writes: the
        device store (the scorer routes as served) and the host
        dependency cache (what `?scorer=host` is labeled from).
        chip_smoke.py asserts that the ticks grew both, by the same
        number."""
        cache = getattr(self._ctx, "cache", None)
        dep = (
            cache.get_all().get("EndpointDependencies")
            if cache is not None
            else None
        )
        data = dep.get_data() if dep is not None else None
        records = data.dependencies if data is not None else []
        # distinct triples: a window leaves one record per SERVER span,
        # and the cache folds same-endpoint records only on its next merge
        host_edges = {
            (
                by["endpoint"]["uniqueEndpointName"],
                d["endpoint"]["uniqueEndpointName"],
                by["distance"],
            )
            for d in records
            for by in d["dependingBy"]
        }
        return {
            "deviceEdges": graph.n_edges,
            "hostEdges": len(host_edges),
            "hostEndpoints": len(
                {d["endpoint"]["uniqueEndpointName"] for d in records}
            ),
        }

    def _timings(self, req: Request) -> Response:
        payload = {
            "serverTime": int(time.time() * 1000),
            "phases": step_timer.summary(),
        }
        graph = getattr(
            getattr(self._ctx, "processor", None), "graph", None
        )
        if graph is not None and hasattr(graph, "scorer_cache_stats"):
            payload["scorerCache"] = graph.scorer_cache_stats()
            payload["graph"] = self._graph_sizes(graph)
        from kmamiz_tpu.models import serving

        payload["modelServe"] = serving.serve_stats()
        # per-program compile counters (compiles / compileMs / buckets):
        # a steady-state tick after warm-up must add 0 compiles
        payload["programs"] = programs.summary()
        # ingestDropped (ring backpressure), dpFallback, breakers, WAL,
        # quarantine, watchdog — the fault-layer counters (ISSUE 5)
        payload["resilience"] = res_metrics.resilience_summary()
        if getattr(self._ctx, "processor", None) is None:
            payload["device"] = None
        else:
            from kmamiz_tpu.telemetry import device as tel_device

            # device, native, compileCache, sparse
            payload.update(tel_device.runtime_report())
        return Response(payload=payload)
