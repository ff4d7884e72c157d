"""HTTP server speaking the external Data Processor protocol.

Drop-in sibling of the reference's Rust service
(/root/reference/kmamiz_data_processor/src/main.rs:28-79): GET / answers a
health string, POST / takes a TExternalDataProcessorRequest
({uniqueId, lookBack, time, existingDep}) and returns a
TExternalDataProcessorResponse ({uniqueId, combined, dependencies,
datatype, log}). Point the host app's EXTERNAL_DATA_PROCESSOR at this
address to run KMamiz with the TPU backend; its worker-fallback behavior
(ServiceOperator.ts:300-306) is preserved because any non-2xx/connection
error simply falls back.

Gzip request bodies (Content-Encoding: gzip) are accepted; responses are
gzip-compressed when the client advertises Accept-Encoding: gzip.
"""
from __future__ import annotations

import gzip
import json
import logging
import os
import threading
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from typing import Optional

from kmamiz_tpu import control as ctl_plane
from kmamiz_tpu import cost as cost_plane
from kmamiz_tpu import fleet as fleet_mod
from kmamiz_tpu.analysis import guards
from kmamiz_tpu.core import programs
from kmamiz_tpu.resilience import metrics as res_metrics
from kmamiz_tpu.resilience.watchdog import (
    REASON_FAULT,
    TickDeadlineExceeded,
    TickWatchdog,
)
from kmamiz_tpu.server import stream as stream_mod
from kmamiz_tpu.server.processor import DataProcessor
from kmamiz_tpu.telemetry import REGISTRY as TEL_REGISTRY
from kmamiz_tpu.telemetry import TRACER
from kmamiz_tpu.telemetry import freshness as tel_freshness
from kmamiz_tpu.telemetry.profiling import events as prof_events

logger = logging.getLogger("kmamiz_tpu.dp_server")


class _LastGoodTick:
    """The newest fully successful collect response and the graph
    coordinates it was computed at. When a tick overruns its watchdog
    deadline or faults, the server degrades to this payload — marked
    stale, never a 500 — instead of making the host app's poller eat an
    error and fall back to in-process computation. Serving it is pure
    host work on an already-encoded dict: no jax call, no compile
    (tools/chaos_probe.py asserts zero new compiles on the stale path)."""

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._payload: Optional[dict] = None
        self._at_ms: Optional[float] = None

    def update(self, payload: dict, version: int, label_epoch: int) -> None:
        now_ms = prof_events.wall_ms()
        with self._lock:
            self._payload = payload
            self._at_ms = now_ms
        res_metrics.note_last_good(version, label_epoch, now_ms)

    def serve_stale(self, unique_id: str, reason: str) -> Optional[dict]:
        """A copy of the last-good payload re-addressed to the current
        request, with explicit staleness metadata; None when no tick has
        succeeded yet (callers then keep the old 5xx contract)."""
        with self._lock:
            if self._payload is None:
                return None
            payload = dict(self._payload)
            at_ms = self._at_ms
        age_ms = max(0.0, prof_events.wall_ms() - at_ms)
        payload["uniqueId"] = unique_id
        payload["stale"] = True
        payload["staleAgeMs"] = round(age_ms, 1)
        payload["staleReason"] = reason
        res_metrics.note_stale_serve()
        return payload

    def serve_deferred(self, unique_id: str, control: dict) -> Optional[dict]:
        """graftpilot defer (docs/CONTROL.md): the controller predicted
        this tenant's next tick would breach SLO, so the tick is NOT
        executed — the last-good payload answers, marked ``deferred``
        with the controller's verdict attached. Deliberately distinct
        from serve_stale: a defer is a healthy, chosen degradation, so
        it touches neither the stale-serve counters nor the tenant
        stale scorecard (the scenario stale gates stay honest). None
        when no tick has succeeded yet — callers then fail open and
        admit the tick."""
        with self._lock:
            if self._payload is None:
                return None
            payload = dict(self._payload)
            at_ms = self._at_ms
        payload["uniqueId"] = unique_id
        payload["deferred"] = True
        payload["deferredAgeMs"] = round(
            max(0.0, prof_events.wall_ms() - at_ms), 1
        )
        payload["control"] = control
        return payload


class _EncodedPayloadCache:
    """Memo of encoded response bytes for version-keyed payloads.

    A tick response carries the FULL merged dependency graph; under the
    threading server a host-side retry (or parallel pollers) re-entered
    json.dumps + gzip per request thread for byte-identical output. The
    key rides the same (graph version, label epoch) pair the scorer
    cache uses, so any graph/label change naturally invalidates."""

    def __init__(self, max_entries: int = 4) -> None:
        self._lock = threading.Lock()
        self._max = max_entries
        self._entries: "dict[tuple, bytes]" = {}

    def get_or_encode(self, key: tuple, payload: dict, use_gzip: bool) -> bytes:
        full_key = key + (use_gzip,)
        with self._lock:
            body = self._entries.get(full_key)
        if body is not None:
            return body
        body = json.dumps(payload).encode()
        if use_gzip:
            body = gzip.compress(body)
        with self._lock:
            while len(self._entries) >= self._max:
                self._entries.pop(next(iter(self._entries)))
            self._entries[full_key] = body
        return body


def _make_runtime(tenant: str, proc: DataProcessor):
    """One tenant's serving state: its processor plus PER-TENANT edge
    layers — last-good payload, tick watchdog, encoded-payload cache.
    Per-instance state is the isolation: tenant A's overrun trips only
    A's in-flight-overlap detector, A's stale serve reads only A's
    last-good graph, and the encode memo cannot leak one tenant's
    dependency payload into another's response."""
    from kmamiz_tpu.tenancy.router import TenantRuntime

    last_good = _LastGoodTick()
    # env-driven deadline (KMAMIZ_TICK_DEADLINE_MS, 0 = off); a straggler
    # that finishes after the trip still refreshes this tenant's last_good
    watchdog = TickWatchdog(
        on_late_result=lambda result: last_good.update(
            result,
            proc.graph.version,
            proc.graph.label_epoch,
        )
        if isinstance(result, dict)
        else None
    )
    return TenantRuntime(
        tenant,
        proc,
        last_good=last_good,
        watchdog=watchdog,
        encoded_cache=_EncodedPayloadCache(),
    )


def make_handler(processor: DataProcessor, router=None):
    from kmamiz_tpu.tenancy.arena import (
        DEFAULT_TENANT,
        TenantLimitError,
        TenantNameError,
    )
    from kmamiz_tpu.tenancy.router import (
        TenantResolutionError,
        TickRouter,
        batch_window_ms,
        resolve_tenant,
    )
    from kmamiz_tpu.telemetry import slo as tel_slo

    if router is None:
        def _factory(tenant: str):
            if tenant == DEFAULT_TENANT:
                return _make_runtime(tenant, processor)
            proc = processor.sibling_for_tenant(tenant)
            # the tenant's own WAL namespace replays before first serve,
            # so a restarted server answers from its recovered graph
            recovered = proc.replay_wal()
            if recovered["replayed"]:
                logger.info("tenant %s wal replay: %s", tenant, recovered)
            return _make_runtime(tenant, proc)

        router = TickRouter(_factory)

    # fleet migration two-phase import: /fleet/wal-import replays the
    # shipped blob into a runtime that parks HERE; only the
    # coordinator's post-verification /fleet/wal-commit installs it into
    # the router (an aborted handoff discards it via /fleet/wal-abort,
    # never having touched the tenant's live runtime)
    pending_lock = threading.Lock()
    pending_imports: dict = {}

    class Handler(BaseHTTPRequestHandler):
        protocol_version = "HTTP/1.1"

        def log_message(self, fmt: str, *args) -> None:  # quiet default logs
            logger.debug("%s " + fmt, self.address_string(), *args)

        def _route(self):
            """(tenant, de-prefixed path) for this request, or None after
            answering 400 for an unroutable tenant name."""
            try:
                return resolve_tenant(self.headers, self.path)
            except (TenantResolutionError, TenantNameError) as e:
                self._send_json(400, {"error": str(e)})
                return None

        def _runtime(self, tenant: str):
            """The tenant's runtime (created on first request), or None
            after answering 429 (tenant limit) / 400 (bad name)."""
            try:
                return router.runtime(tenant)
            except TenantLimitError as e:
                self._send_json(429, {"error": str(e)})
                return None
            except TenantNameError as e:
                self._send_json(400, {"error": str(e)})
                return None

        def _send_json(
            self,
            status: int,
            payload: dict,
            cache_key: tuple = None,
            extra_headers: Optional[dict] = None,
            cache: "_EncodedPayloadCache | None" = None,
        ) -> None:
            accept = self.headers.get("Accept-Encoding", "")
            encoded = "gzip" in accept
            if cache_key is not None and cache is not None:
                body = cache.get_or_encode(cache_key, payload, encoded)
            else:
                body = json.dumps(payload).encode()
                if encoded:
                    body = gzip.compress(body)
            self.send_response(status)
            self.send_header("Content-Type", "application/json")
            if encoded:
                self.send_header("Content-Encoding", "gzip")
            if extra_headers:
                for name, value in extra_headers.items():
                    self.send_header(name, str(value))
            self.send_header("Content-Length", str(len(body)))
            self.end_headers()
            self.wfile.write(body)

        def _send_stale(self, stale_payload: dict) -> None:
            """Degraded serve: 200 + the last-good graph, staleness
            spelled out in both the payload and a response header."""
            self._send_json(
                200,
                stale_payload,
                extra_headers={
                    "X-KMamiz-Stale-Age-Ms": stale_payload["staleAgeMs"]
                },
            )

        def _send_bytes(
            self, status: int, body: bytes, content_type: str
        ) -> None:
            self.send_response(status)
            self.send_header("Content-Type", content_type)
            self.send_header("Content-Length", str(len(body)))
            self.end_headers()
            self.wfile.write(body)

        def do_GET(self) -> None:  # health check (main.rs:28-31)
            route = self._route()
            if route is None:
                return
            tenant, path = route
            path = path.split("?", 1)[0].rstrip("/")
            if path == "/fleet/signature":
                # the tenant's current graph content hash — the fleet
                # migration's bit-exactness oracle (docs/FLEET.md)
                from kmamiz_tpu.resilience.chaos import graph_signature

                rt = self._runtime(tenant)
                if rt is None:
                    return
                self._send_json(
                    200,
                    {
                        "tenant": tenant,
                        "signature": graph_signature(rt.processor.graph),
                    },
                )
                return
            if path == "/fleet/export":
                # name-based edge snapshot for the coordinator's
                # hierarchical fold (graph/store.export_named_edges)
                rt = self._runtime(tenant)
                if rt is None:
                    return
                self._send_json(
                    200, rt.processor.graph.export_named_edges()
                )
                return
            if path == "/fleet/wal":
                # the tenant's WAL namespace as one handoff blob
                rt = self._runtime(tenant)
                if rt is None:
                    return
                wal = rt.processor.wal
                if wal is None:
                    self._send_json(
                        409,
                        {"error": "WAL disabled (KMAMIZ_WAL=0): no handoff"},
                    )
                    return
                self._send_bytes(
                    200, wal.export_handoff(), "application/octet-stream"
                )
                return
            if path == "/timings":
                from kmamiz_tpu.analysis.concurrency import witness
                from kmamiz_tpu.core.profiling import step_timer
                from kmamiz_tpu.telemetry import device as tel_device

                self._send_json(
                    200,
                    {
                        # device, native, compileCache, sparse
                        **tel_device.runtime_report(),
                        "phases": step_timer.summary(),
                        "programs": programs.summary(),
                        "resilience": res_metrics.resilience_summary(),
                        "tenancy": router.summary(),
                        "tenants": tel_slo.TENANTS.snapshot(),
                        "control": ctl_plane.snapshot(),
                        "cost": cost_plane.snapshot(),
                        "freshness": tel_freshness.snapshot(),
                        "stream": stream_mod.stats(),
                        "fleet": fleet_mod.snapshot(),
                        "lockWitness": witness.snapshot(),
                    },
                )
                return
            if path == "/metrics":
                # Prometheus text exposition of the unified registry —
                # the same cells /timings reads (docs/OBSERVABILITY.md)
                body = TEL_REGISTRY.render().encode("utf-8")
                self.send_response(200)
                self.send_header(
                    "Content-Type", "text/plain; version=0.0.4; charset=utf-8"
                )
                self.send_header("Content-Length", str(len(body)))
                self.end_headers()
                self.wfile.write(body)
                return
            if path == "/debug/traces":
                # the tick-span ring as Zipkin v2 trace groups; POSTing
                # this body back to /ingest builds the pipeline's own
                # dependency graph (self-trace)
                self._send_json(200, TRACER.export_zipkin())
                return
            if path == "/model/stlgt":
                # continual-trainer health: ring depth, stale slots,
                # refresh counters, params version (docs/STLGT.md)
                from kmamiz_tpu.models.stlgt import trainer as stlgt_trainer

                self._send_json(200, stlgt_trainer.trainer_status())
                return
            if path == "/debug/graftprof":
                # the live graftprof profile: per-phase attribution of
                # recent ticks, native contention counters, device plane
                from kmamiz_tpu.telemetry.profiling import report as prof_report

                self._send_json(200, prof_report.build_profile())
                return
            warm = programs.warm_state()
            if (
                warm.get("status") == "warming"
                and programs.ready_gate_enabled()
            ):
                self._send_json(503, {"status": "WARMING", "prewarm": warm})
                return
            self._send_json(
                200,
                {
                    "status": "UP",
                    "service": "kmamiz-tpu-data-processor",
                    "prewarm": warm,
                },
            )

        def do_POST(self) -> None:
            try:
                length = int(self.headers.get("Content-Length", 0))
                raw = self.rfile.read(length)
                if self.headers.get("Content-Encoding") == "gzip":
                    raw = gzip.decompress(raw)
            except (ValueError, OSError, EOFError) as e:
                # EOFError: gzip.decompress raises it (not OSError) on a
                # truncated stream — without it a corrupt body killed the
                # connection instead of answering 400 (review r5)
                self._send_json(400, {"error": f"bad request: {e}"})
                return

            route = self._route()
            if route is None:
                return
            tenant, stripped = route
            post_path = stripped.split("?", 1)[0].rstrip("/")
            if post_path == "/debug/profile":
                # on-demand jax.profiler capture: {"durationMs": N,
                # "dir": optional} -> blocks for the window, answers
                # with the capture directory (one at a time)
                from kmamiz_tpu.telemetry import device as tel_device

                try:
                    req = json.loads(raw) if raw else {}
                except ValueError as e:
                    self._send_json(400, {"error": f"bad request: {e}"})
                    return
                out = tel_device.capture_profile(
                    req.get("durationMs", 100), req.get("dir")
                )
                self._send_json(200 if out.get("ok") else 409, out)
                return

            if post_path == "/fleet/drain":
                # migration step 1: quiesce the tenant at the graph's
                # stage_fence and answer the pre-drain signature +
                # durable record count the target must reproduce
                from kmamiz_tpu.resilience.chaos import graph_signature

                rt = self._runtime(tenant)
                if rt is None:
                    return
                rt.processor.graph.stage_fence()
                wal = rt.processor.wal
                self._send_json(
                    200,
                    {
                        "tenant": tenant,
                        "signature": graph_signature(rt.processor.graph),
                        "walRecords": (
                            wal.record_count() if wal is not None else 0
                        ),
                    },
                )
                return

            if post_path == "/fleet/wal-import":
                # migration step 3 (target side): fresh processor, fresh
                # WAL namespace, import the shipped blob, replay it in
                # order — the rebuilt runtime STAGES (phase one) until
                # the coordinator's verification commits it, so an
                # aborted migration never leaves a divergent graph live
                from kmamiz_tpu.resilience.chaos import graph_signature

                proc = processor.sibling_for_tenant(tenant)
                if proc.wal is None:
                    self._send_json(
                        409,
                        {"error": "WAL disabled (KMAMIZ_WAL=0): no import"},
                    )
                    return
                try:
                    proc.wal.truncate()
                    records = proc.wal.import_handoff(raw)
                    replayed = proc.replay_wal()
                except ValueError as e:
                    self._send_json(400, {"error": str(e)})
                    return
                with pending_lock:
                    stale = pending_imports.pop(tenant, None)
                    pending_imports[tenant] = _make_runtime(tenant, proc)
                if (
                    stale is not None
                    and stale.processor.wal is not None
                    and stale.processor.wal is not proc.wal
                ):
                    stale.processor.wal.close()
                self._send_json(
                    200,
                    {
                        "tenant": tenant,
                        "records": records,
                        "replayed": replayed["replayed"],
                        "spans": replayed["spans"],
                        "signature": graph_signature(proc.graph),
                    },
                )
                return

            if post_path == "/fleet/wal-commit":
                # migration step 4 (target side): the replay verified —
                # atomically install the staged runtime so the first
                # post-flip request serves the migrated graph
                with pending_lock:
                    rt = pending_imports.pop(tenant, None)
                if rt is None:
                    self._send_json(
                        409,
                        {"error": f"no pending import for tenant {tenant!r}"},
                    )
                    return
                router.install_runtime(tenant, rt)
                self._send_json(200, {"tenant": tenant, "installed": True})
                return

            if post_path == "/fleet/wal-abort":
                # abort path: discard the staged runtime; the tenant's
                # live runtime (if any) was never touched
                with pending_lock:
                    rt = pending_imports.pop(tenant, None)
                if rt is not None and rt.processor.wal is not None:
                    rt.processor.wal.close()
                self._send_json(
                    200, {"tenant": tenant, "dropped": rt is not None}
                )
                return

            if post_path == "/fleet/drop":
                # post-commit source cleanup: forget the migrated-away
                # tenant so exactly one worker keeps live state for it
                self._send_json(
                    200,
                    {"tenant": tenant, "dropped": router.drop_runtime(tenant)},
                )
                return

            if post_path == "/ingest":
                # uncapped raw ingest: body IS the Zipkin response bytes.
                # Large bodies split on trace-group boundaries and flow
                # through the pipelined path so the native parse of chunk
                # k+1 overlaps the device merge of chunk k. Span-id maps
                # are then scoped per chunk (the reference's own scope
                # under paginated fetches; see ingest_raw_stream).
                rt = self._runtime(tenant)
                if rt is None:
                    return
                try:
                    summary = None
                    try:
                        threshold = int(
                            os.environ.get(
                                "KMAMIZ_INGEST_STREAM_BYTES", 33554432
                            )
                        )
                    except ValueError:  # malformed env is not a client error
                        threshold = 33554432
                    # gate on the DECOMPRESSED size (gzip bodies shrink
                    # ~15x on the wire, exactly the payloads that want
                    # the pipelined path)
                    with TRACER.tick(root_name="dp-ingest"):
                        # columnar (KMZC) frames are indivisible: the
                        # group splitter only understands the JSON wire
                        if len(raw) >= threshold and raw[:4] != b"KMZC":
                            from kmamiz_tpu import native as native_mod
                            from kmamiz_tpu.server.processor import (
                                DEFAULT_STREAM_CHUNKS,
                            )

                            try:
                                n_chunks = int(
                                    os.environ.get(
                                        "KMAMIZ_INGEST_STREAM_CHUNKS",
                                        DEFAULT_STREAM_CHUNKS,
                                    )
                                )
                            except ValueError:
                                n_chunks = DEFAULT_STREAM_CHUNKS
                            chunks = native_mod.split_groups(raw, n_chunks)
                            if chunks is not None and len(chunks) > 1:
                                summary = rt.processor.ingest_raw_stream(
                                    chunks
                                )
                        if summary is None:
                            summary = rt.processor.ingest_raw_window(raw)
                except ValueError as e:
                    self._send_json(400, {"error": str(e)})
                    return
                except Exception as e:  # noqa: BLE001
                    logger.exception("raw ingest failed")
                    self._send_json(500, {"error": str(e)})
                    return
                self._send_json(200, summary)
                return

            try:
                request = json.loads(raw) if raw else {}
            except ValueError as e:
                self._send_json(400, {"error": f"bad request: {e}"})
                return
            rt = self._runtime(tenant)
            if rt is None:
                return

            # graftpilot admission (docs/CONTROL.md): the controller's
            # stored verdict — computed at the last fold boundary, read
            # here as one dict lookup — decides whether this tick runs.
            # shed -> explicit 429; defer -> last-good marked deferred
            # (the skipped window's spans stay queued upstream and drain
            # on the next admitted tick, so nothing is lost); no
            # last-good yet -> fail open and admit.
            verdict = ctl_plane.admission_verdict(tenant, request)
            if verdict is not None:
                if verdict["action"] == "shed":
                    self._send_json(
                        429,
                        {
                            "uniqueId": request.get("uniqueId", ""),
                            "error": "tick shed: forecasted p99 "
                            f"{verdict['forecastP99Ms']}ms exceeds SLO "
                            f"{verdict['sloMs']}ms (KMAMIZ_CONTROL)",
                            "control": verdict,
                        },
                        extra_headers={
                            "Retry-After": "1",
                            "X-KMamiz-Control": "shed",
                        },
                    )
                    return
                deferred = rt.last_good.serve_deferred(
                    request.get("uniqueId", ""), verdict
                )
                if deferred is not None:
                    self._send_json(
                        200,
                        deferred,
                        extra_headers={"X-KMamiz-Control": "defer"},
                    )
                    return

            def _tick() -> dict:
                # opt-in hot-path enforcement: KMAMIZ_TRANSFER_GUARD=1
                # runs the tick under jax.transfer_guard("disallow") and
                # diffs the program registry's compile counters
                with guards.maybe_guarded_tick() as guard_report:
                    if batch_window_ms() > 0:
                        # gather-window coalescing: concurrent same-bucket
                        # tenant ticks batch into ONE stacked dispatch
                        # (tenancy/router.py submit). The per-tick
                        # watchdog deadline spans the whole gathered
                        # batch in this mode.
                        result = router.submit(tenant, request)
                    elif stream_mod.stream_enabled():
                        # graftstream micro-tick: same stage order with
                        # the explicit merge->score fence and per-epoch
                        # watchdog deadline caching (server/stream.py)
                        result = stream_mod.engine_for(
                            rt.processor, rt.watchdog
                        ).collect(request)
                    else:
                        result = rt.processor.collect(request)
                if guard_report is not None and guard_report.recompiled:
                    logger.warning(
                        "collect tick recompiled programs: %s",
                        guard_report.new_compiles,
                    )
                return result

            streaming = stream_mod.stream_enabled()
            if streaming:
                # epoch accounting BEFORE the watchdog reads its
                # deadline: at an epoch boundary this re-reads the env
                # parse the deadline property serves for the whole epoch
                stream_mod.engine_for(
                    rt.processor, rt.watchdog
                ).note_micro_tick()
            else:
                # leaving stream mode must not strand a cached epoch
                # deadline on the serial path
                rt.watchdog.end_stream_epoch()
            try:
                response = rt.watchdog.run(
                    _tick,
                    overrun_reason=(
                        stream_mod.REASON_STREAM_OVERRUN
                        if streaming
                        else None
                    ),
                )
            except TickDeadlineExceeded as e:
                # tick overran its deadline (or a straggler is still in
                # flight): serve the tenant's last-good graph, explicitly
                # stale — never another tenant's payload
                logger.warning(
                    "collect tick degraded (tenant %s): %s", tenant, e
                )
                stale = rt.last_good.serve_stale(
                    request.get("uniqueId", ""), e.reason
                )
                if stale is not None:
                    tel_slo.TENANTS.note_stale(tenant)
                    self._send_stale(stale)
                    return
                self._send_json(503, {"error": str(e), "reason": e.reason})
                return
            except Exception as e:  # noqa: BLE001 - degrade, else fall back
                logger.exception("collect failed (tenant %s)", tenant)
                stale = rt.last_good.serve_stale(
                    request.get("uniqueId", ""), REASON_FAULT
                )
                if stale is not None:
                    res_metrics.watchdog_tripped(REASON_FAULT)
                    tel_slo.TENANTS.note_stale(tenant)
                    self._send_stale(stale)
                    return
                self._send_json(500, {"error": str(e)})
                return
            graph = rt.processor.graph
            rt.last_good.update(response, graph.version, graph.label_epoch)
            # version-keyed encode memo (per tenant): a retried uniqueId
            # against an unchanged graph re-sends the cached bytes instead
            # of re-encoding the full dependency payload per thread
            t_enc = prof_events.now_ms()
            self._send_json(
                200,
                response,
                cache_key=(
                    request.get("uniqueId", ""),
                    graph.version,
                    graph.label_epoch,
                ),
                cache=rt.encoded_cache,
            )
            # the encode happens after the tick's trace closed (and the
            # tick itself may have run on a watchdog worker thread), so
            # it attaches to the finished trace as a post-hoc span
            TRACER.annotate_last(
                "encode-serve", prof_events.now_ms() - t_enc
            )

    Handler.router = router  # tests and embedders reach the tick router here
    return Handler


class DataProcessorServer:
    def __init__(
        self,
        processor: DataProcessor,
        host: str = "0.0.0.0",
        port: int = 8600,
        router=None,
    ) -> None:
        # a caller-supplied TickRouter overrides the default per-tenant
        # sibling factory (the scenario runner mounts tenants with their
        # own controlled trace sources this way)
        self._server = ThreadingHTTPServer(
            (host, port), make_handler(processor, router=router)
        )
        self._thread: Optional[threading.Thread] = None

    @property
    def port(self) -> int:
        return self._server.server_address[1]

    def start(self) -> None:
        self._thread = threading.Thread(
            target=self._server.serve_forever, name="dp-server", daemon=True
        )
        self._thread.start()

    def stop(self) -> None:
        self._server.shutdown()
        self._server.server_close()

    def serve_forever(self) -> None:
        self._server.serve_forever()


def main() -> None:
    """Standalone external DP, env-configured like the Rust service
    (kmamiz_data_processor/src/env.rs): BIND_IP, DP_PORT, ZIPKIN_URL,
    KUBEAPI_HOST, IS_RUNNING_IN_K8S. Point a stock KMamiz install's
    EXTERNAL_DATA_PROCESSOR here."""
    import signal

    from kmamiz_tpu.core import compile_cache
    from kmamiz_tpu.ingestion.kubernetes import KubernetesClient
    from kmamiz_tpu.ingestion.zipkin import ZipkinClient

    logging.basicConfig(level=os.environ.get("LOG_LEVEL", "INFO").upper())
    compile_cache.enable()  # before the first jit dispatch
    # arm the lock witness BEFORE the processor exists so every lock the
    # serving stack creates is wrapped (KMAMIZ_LOCK_WITNESS=1; the
    # scenario runner does the same for soaks — docs/STATIC_ANALYSIS.md)
    from kmamiz_tpu.analysis.concurrency import witness as lock_witness

    if lock_witness.enabled():
        lock_witness.install()
    zipkin = ZipkinClient(os.environ.get("ZIPKIN_URL", ""))
    k8s = None
    kube_host = os.environ.get("KUBEAPI_HOST", "")
    if kube_host:
        if os.environ.get("IS_RUNNING_IN_K8S", "").lower() == "true":
            k8s = KubernetesClient.from_service_account(kube_host)
        else:
            k8s = KubernetesClient(kube_host)
    processor = DataProcessor(
        trace_source=lambda look_back, end_ts, limit: zipkin.get_trace_list(
            look_back, end_ts, limit
        ),
        k8s_source=k8s,
    )
    # crash recovery first: with KMAMIZ_WAL=1 the boot replays the ingest
    # WAL so the graph resumes bit-exact from wherever kill -9 landed
    recovered = processor.replay_wal()
    if recovered["replayed"]:
        logger.info("wal replay: %s", recovered)
    # boot prewarm plan (core/programs.py): replay persisted shape hints
    # (exact production buckets) or the default graph merge set, on a
    # background thread by default — GET / answers 503 WARMING until
    # done, so a readinessProbe holds traffic off the compile walls
    # (KMAMIZ_PREWARM=0 disables, =sync blocks boot)
    programs.boot_prewarm_from_env(graph=processor.graph)
    server = DataProcessorServer(
        processor,
        host=os.environ.get("BIND_IP", "0.0.0.0"),
        port=int(os.environ.get("DP_PORT", "8600")),
    )
    logger.info("external DP listening on %d", server.port)

    def _stop(signum, frame):
        # shutdown() blocks until serve_forever returns, and the handler
        # runs ON the serving thread: hand it to another one
        threading.Thread(target=server.stop, name="dp-stop").start()

    signal.signal(signal.SIGTERM, _stop)
    signal.signal(signal.SIGINT, _stop)
    server.serve_forever()
    logger.info("external DP stopped")


if __name__ == "__main__":
    main()
