"""The Data Processor pipeline: one realtime tick end to end.

TPU-backend equivalent of the reference's hot path — the Rust service's
collect_data (/root/reference/kmamiz_data_processor/src/data_processor.rs:75-126)
and the Node worker (src/services/worker/RealtimeWorkerImpl.ts):

  fetch traces -> dedup vs processed-trace map -> namespaces -> replicas ->
  envoy logs per pod -> combine logs -> realtime+combined data ->
  endpoint dependencies (+merge with existing) -> datatypes -> response

The numeric window statistics (counts, error classes, latency mean/CV,
latest timestamps) run on device via kmamiz_tpu.ops.window over the SoA
span batch; string-bound work (JSON body merging, schema inference) stays
on host, grouped per (endpoint, status). Every window also feeds the
persistent device edge store (kmamiz_tpu.graph.store) that serves the
graph scorers.
"""
from __future__ import annotations

import json
import os
import queue
import threading
from typing import Callable, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from kmamiz_tpu.core import programs
from kmamiz_tpu.core.envoy import EnvoyLogs
from kmamiz_tpu.core.spans import (
    KIND_SERVER,
    SpanBatch,
    _pad_size,
    spans_to_batch,
)
from kmamiz_tpu.core.timeutils import to_precise
from kmamiz_tpu.domain.endpoint_dependencies import EndpointDependencies
from kmamiz_tpu.domain.realtime import RealtimeDataList
from kmamiz_tpu.core import profiling
from kmamiz_tpu.core.profiling import step_timer
from kmamiz_tpu.domain.traces import Traces
from kmamiz_tpu.resilience import metrics as res_metrics
from kmamiz_tpu.resilience import quarantine as res_quarantine
from kmamiz_tpu.resilience.wal import IngestWAL
from kmamiz_tpu.telemetry import freshness as tel_freshness
from kmamiz_tpu.telemetry import slo as tel_slo
from kmamiz_tpu.telemetry.profiling import events as prof_events
from kmamiz_tpu.telemetry.tracing import TRACER, phase_span

# default pipeline width for chunked big-window ingest (DP-server body
# splits, paginated Zipkin backfills): enough chunks that the native
# parse of chunk k+1 fully hides the device merge of chunk k, few enough
# that per-chunk padding/assembly overhead stays small (measured sweet
# spot on the bench's 1.05M-span window; 2-8 all land within ~8%)
DEFAULT_STREAM_CHUNKS = 4
#: parsed-but-unmerged chunks the raw-ingest ring may hold (see
#: DataProcessor._stream_depth; env override KMAMIZ_INGEST_DEPTH)
DEFAULT_STREAM_DEPTH = 2
from kmamiz_tpu.graph import store as store_mod
from kmamiz_tpu.graph.store import EndpointGraph
from kmamiz_tpu.ops import window as window_ops

PROCESSED_TRACE_TTL_MS = 300_000  # Rust DP keeps the dedup map for 5 min
ZIPKIN_LIMIT = 2_500


def _host_edge_merge_enabled() -> bool:
    """KMAMIZ_HOST_EDGE_MERGE=0 restores the packed walk kernel for tick
    merges (kill switch for the host-edge reuse fast path)."""
    return os.environ.get("KMAMIZ_HOST_EDGE_MERGE", "1") != "0"


_GC_TUNED = False


def _tune_gc() -> None:
    """Raise the gen-0 collection threshold for the serving process. A
    2,500-trace tick allocates ~10^5 short-lived dicts (span copies, dep
    records, response JSON); CPython's default gen-0 threshold of 700
    triggers a young-generation scan every few hundred of them — ~45 ms
    of a steady tick went to collector sweeps that freed almost nothing
    mid-tick. KMAMIZ_GC_GEN0 overrides the threshold; 0 leaves the
    interpreter defaults untouched. Collection stays ENABLED — only the
    cadence changes, so cycles are still reclaimed between ticks."""
    global _GC_TUNED
    if _GC_TUNED:
        return
    _GC_TUNED = True
    try:
        gen0 = int(os.environ.get("KMAMIZ_GC_GEN0", 50_000))
    except ValueError:
        gen0 = 50_000
    if gen0 > 0:
        import gc

        _, gen1, gen2 = gc.get_threshold()
        gc.set_threshold(gen0, gen1, gen2)


@programs.register("processor.pack_stats")
@jax.jit
def _pack_stats(count, mean, cv, ts_rel):
    """Pack the per-segment stats into ONE device buffer so the host pays a
    single device->host fetch (a round trip per field would dominate
    these small transfers). int32 timestamps ride along losslessly via
    bitcast."""
    import jax.lax as lax

    ts_bits = lax.bitcast_convert_type(ts_rel, jnp.float32)
    return jnp.stack([count, mean, cv, ts_bits])


class _PreparedTick:
    """A tick's host-stage results between prepare_tick and finish_tick:
    the routing unit of the tenancy layer's stacked dispatch (several
    tenants prepare, one stacked device merge, then each finishes)."""

    __slots__ = (
        "request",
        "t_start",
        "wall_t0",
        "arrival_ns",
        "req_time",
        "trace_groups",
        "realtime",
        "stats_job",
        "dependencies",
        "window_edges",
        "batch",
        "merged",
    )

    def __init__(self, request: dict) -> None:
        self.request = request
        self.t_start = 0.0
        self.wall_t0 = 0.0
        # freshness watermark: stamped at native parse (prepare_tick),
        # carried through merge/score, observed when the response — the
        # forecast-visible state — is assembled (finish_tick)
        self.arrival_ns = 0
        self.req_time = 0
        self.trace_groups = []
        self.realtime = None
        self.stats_job = None
        self.dependencies = None
        self.window_edges = None
        self.batch = None
        self.merged = False


class DataProcessor:
    """One instance per DP service; holds the processed-trace dedup map and
    the persistent device graph."""

    def __init__(
        self,
        trace_source: Callable[[int, int, int], List[List[dict]]],
        k8s_source: Optional[object] = None,
        use_device_stats: bool = True,
        now_ms: Callable[[], float] = prof_events.wall_ms,
        tenant: str = "default",
        wal: object = "from_env",
    ) -> None:
        _tune_gc()
        self.tenant = tenant
        self._trace_source = trace_source
        self._k8s = k8s_source
        self._use_device_stats = use_device_stats
        self._now_ms = now_ms
        self._processed: Dict[str, float] = {}
        # incremental pre-encoded skip blob mirroring _processed's keys
        # (native/__init__.encode_skip_entry layout): the raw-ingest parse
        # passes it straight to the native scanner instead of re-encoding
        # a six-figure processed set on every chunk
        self._skip_entries = bytearray()
        # persistent native mirror of the skip entries (native.SkipSet):
        # the streaming parse passes the HANDLE, so the native side stops
        # rebuilding a hash set from the blob on every chunk. Lazily
        # created; _skip_gen bumps whenever the blob is REBUILT (prune)
        # so the sync logic knows appends-so-far are stale.
        self._native_skipset = None
        self._skipset_synced = 0  # bytes of _skip_entries already pushed
        self._skip_gen = 0
        self._skipset_gen = -1  # generation the native set reflects
        # persistent raw-ingest session (core.spans.RawIngestSession):
        # shape/status tables survive across chunks so warm pages carry
        # zero naming strings; lazily created, None when native is out
        self._raw_session = None
        # collect() runs on the scheduler/DP thread while /ingest backfills
        # arrive on other server threads; dedup-map transitions serialize
        # here (the graph store carries its own lock)
        self._dedup_lock = threading.Lock()
        self.graph = EndpointGraph(tenant=tenant)
        # online history-feature state (models/history.HistoryState),
        # created lazily on the first observed tick; ticks accumulate
        # into the current hour's bucket and fold on rollover. collect()
        # runs concurrently (operator loop + DP-server request threads),
        # so every transition serializes on _history_lock.
        self.history = None
        self.history_features = None  # last fold's [N, 8] columns
        self.history_model_features = None  # full [N, 18] model input
        self.history_predicted_hour = None
        # atomic fold-time snapshot for /model/forecast: features + the
        # matching graph edges + names, published as ONE dict so readers
        # never mix folds (replaced wholesale, read via one attribute)
        self.forecast_snapshot = None
        self._hour_bucket = None  # [abs_hour, count, e4, e5, lat, lat^2,
        #                            cls_count, cls_lat, cls_lat^2]
        self._history_lock = threading.Lock()
        self._last_replicas: Dict[str, float] = {}
        # crash-safe ingest WAL (resilience/wal.py), None unless
        # KMAMIZ_WAL=1: every successfully parsed ingest payload appends
        # BEFORE its graph merge, so a kill -9 mid-tick replays to a
        # bit-exact graph on restart (replay_wal). _wal_replaying
        # suppresses re-appends while the replay itself runs. A fleet
        # worker passes an explicit IngestWAL (or None) so each worker's
        # tenant processors log under the WORKER's namespace instead of
        # the env-wide one (fleet/worker.py); the "from_env" sentinel
        # keeps the env-configured default for every other caller.
        self._wal = IngestWAL.from_env(tenant=tenant) if wal == "from_env" else wal
        self._wal_replaying = False

    @property
    def wal(self) -> Optional[IngestWAL]:
        """This processor's ingest WAL (None when durability is off) —
        the fleet migration path exports/imports handoff blobs here."""
        return self._wal

    def sibling_for_tenant(self, tenant: str) -> "DataProcessor":
        """A fresh DataProcessor for another tenant sharing this one's
        sources and clock but NOTHING stateful: its own graph (admitted
        into the arena under `tenant`), its own WAL namespace, its own
        dedup map and history. The tenancy router's runtime factory uses
        this to bring tenants up from the default processor's wiring."""
        return DataProcessor(
            self._trace_source,
            k8s_source=self._k8s,
            use_device_stats=self._use_device_stats,
            now_ms=self._now_ms,
            tenant=tenant,
        )

    # -- trace dedup (data_processor.rs:30-73) -------------------------------

    def _filter_traces(self, traces: List[List[dict]], request_time: float):
        from kmamiz_tpu.native import encode_skip_entry

        with self._dedup_lock:
            kept = []
            for group in traces:
                if not group:
                    continue
                trace_id = group[0].get("traceId")
                if trace_id in self._processed:
                    continue
                self._processed[trace_id] = request_time
                self._skip_entries += encode_skip_entry(trace_id)
                kept.append(group)
            self._prune_processed_locked(request_time)
            return kept

    def _prune_processed_locked(self, now_ms: float) -> None:
        """TTL-prune the processed map; the cached skip blob rebuilds only
        when the prune actually removed entries."""
        from kmamiz_tpu.native import encode_skip_entry

        cutoff = now_ms - PROCESSED_TRACE_TTL_MS
        pruned = {k: v for k, v in self._processed.items() if v >= cutoff}
        if len(pruned) != len(self._processed):
            self._processed = pruned
            self._skip_entries = bytearray()
            for tid in pruned:
                self._skip_entries += encode_skip_entry(tid)
            self._skip_gen += 1  # native skip set must clear + resync

    def _skip_blob_locked(self) -> bytes:
        """Snapshot of the full native skip blob (header + entries)."""
        import struct

        return struct.pack("<I", len(self._processed)) + bytes(
            self._skip_entries
        )

    def _skipset_locked(self):
        """The persistent native skip set, synced to _skip_entries (caller
        holds _dedup_lock). Returns None when the extension is missing —
        callers then fall back to the per-parse blob snapshot. A prune
        rebuild (generation bump) clears and re-pushes the whole blob;
        otherwise only the appended delta crosses the boundary."""
        from kmamiz_tpu.native import SkipSet

        if self._native_skipset is None:
            ss = SkipSet()
            if ss.handle is None:
                return None
            self._native_skipset = ss
        ss = self._native_skipset
        if self._skipset_gen != self._skip_gen:
            ss.clear()
            self._skipset_synced = 0
            self._skipset_gen = self._skip_gen
        if self._skipset_synced < len(self._skip_entries):
            ss.extend(bytes(self._skip_entries[self._skipset_synced :]))
            self._skipset_synced = len(self._skip_entries)
        return ss

    def _raw_session_locked(self):
        """The persistent raw-ingest session (caller holds _dedup_lock
        for the lazy create; the session carries its own consumer
        lock). None when the native extension is unavailable."""
        if self._raw_session is None:
            from kmamiz_tpu.core.spans import RawIngestSession

            self._raw_session = RawIngestSession(self.graph.interner)
        return self._raw_session if self._raw_session.available else None

    # -- the tick ------------------------------------------------------------

    def collect(self, request: dict) -> dict:
        """TExternalDataProcessorRequest -> TExternalDataProcessorResponse.

        Each phase is step-timed (GET /timings on the DP server) and the
        device work can be captured with jax.profiler by setting
        KMAMIZ_PROFILE_DIR (SURVEY.md §5 tracing/profiling parity). With
        telemetry on, the tick records a span trace of its phases (ring
        exported at GET /debug/traces); span boundaries sit on fences the
        tick already has, so tracing adds no host syncs."""
        with TRACER.tick():  # no-op when dp_server already opened the trace
            return self._collect_traced(request)

    def _collect_traced(self, request: dict) -> dict:
        prep = self.prepare_tick(request)
        self.merge_prepared(prep)
        return self.finish_tick(prep)

    def prepare_tick(self, request: dict) -> "_PreparedTick":
        """The tick's host stages: fetch/dedup/WAL, cluster state, the
        device-stats dispatch, the dependency walk, and the span-batch
        build — everything up to (but NOT including) the graph merge.
        The tenancy router runs prepare for several tenants, stacks their
        merges into one device dispatch, then finishes each tick; the
        serial path is prepare -> merge_prepared -> finish_tick."""
        p = _PreparedTick(request)
        p.t_start = self._now_ms()  # domain time: dedup stamps, req default
        p.wall_t0 = prof_events.now_ms()
        p.arrival_ns = prof_events.now_ns()
        tel_slo.TICKS.inc()
        t_start = p.t_start
        look_back = request.get("lookBack", 30_000)
        req_time = request.get("time", int(t_start))
        p.req_time = req_time
        existing_dep = request.get("existingDep")

        with step_timer.phase("fetch_traces"), phase_span("parse"):
            trace_groups = self._trace_source(look_back, req_time, ZIPKIN_LIMIT)
            trace_groups = self._filter_traces(trace_groups, t_start)
        if trace_groups and self._wal is not None:
            # WAL the tick's kept (post-dedup) groups as raw Zipkin JSON
            # before any graph mutation; replay re-ingests them through
            # ingest_raw_window, which merges the same edges
            with phase_span("wal-append"):
                self._wal_append(json.dumps(trace_groups).encode("utf-8"))

        with phase_span("parse"):
            # still parse work: span dicts -> Traces + namespace scan
            traces = Traces(trace_groups)
            namespaces = {
                ns for ns in traces.extract_containing_namespaces() if ns
            }

        replicas: List[dict] = []
        structured_logs: List[dict] = []
        if self._k8s is not None:
            with step_timer.phase("fetch_cluster_state"):
                # concurrent fan-out: one pod listing per namespace in
                # parallel, then all pod logs in parallel — tick cost
                # ~max(pod) not Σ(pod) (data_processor.rs:58-73)
                replicas, pod_logs = self._k8s.get_replicas_and_envoy_logs(
                    namespaces
                )
                self._last_replicas.update(
                    {
                        r["uniqueServiceName"]: float(r.get("replicas", 1))
                        for r in replicas
                        if r.get("uniqueServiceName")
                    }
                )
                structured_logs = EnvoyLogs.combine_to_structured_envoy_logs(
                    pod_logs
                )

        # dispatch the device stats FIRST: the kernel runs and its packed
        # result streams back (copy_to_host_async) while the host walks
        # dependencies and merges bodies, hiding the device round trip
        with step_timer.phase("combine_window"), profiling.trace(
            "combine"
        ), phase_span("pack"):
            realtime = traces.combine_logs_to_realtime_data(
                structured_logs, replicas
            )
            records = realtime.to_json()
            stats_job = None
            if self._use_device_stats and trace_groups and records:
                stats_job = DeviceStatsJob(records)

        # the walk stage's phase name tracks the active walk backend so
        # graftprof --diff compares dense vs sparse runs phase-for-phase
        # instead of folding both into "walk" (ISSUE 13 satellite)
        walk_phase = (
            "walk_sparse" if store_mod._sparse_walk_default() else "walk"
        )
        with step_timer.phase("dependencies"), phase_span(walk_phase):
            dependencies = traces.to_endpoint_dependencies()
            # the raw pre-filter window edges; combine_with returns a new
            # instance without them, so capture before combining
            window_edges = getattr(dependencies, "window_edges", None)
            if existing_dep:
                dependencies = dependencies.combine_with(
                    EndpointDependencies(existing_dep)
                )

        p.trace_groups = trace_groups
        p.realtime = realtime
        p.stats_job = stats_job
        p.dependencies = dependencies
        p.window_edges = window_edges
        if trace_groups:
            with step_timer.phase("graph_merge"), phase_span("merge"):
                p.batch = spans_to_batch(
                    trace_groups, interner=self.graph.interner
                )
        return p

    def merge_prepared(self, p: "_PreparedTick") -> None:
        """The tick's graph merge (serial, single-tenant path). No-op if
        this tick already merged (the router's stacked path adopted a
        batched lane instead)."""
        if not p.trace_groups or p.merged:
            return
        with step_timer.phase("graph_merge"), profiling.trace(
            "graph_merge"
        ), phase_span("merge"):
            merged = None
            if p.window_edges is not None and _host_edge_merge_enabled():
                # reuse the host walk's edge set instead of re-deriving
                # it with the packed walk kernel; falls back when an
                # endpoint is missing from the graph interner
                merged = self.graph.merge_window_edges(
                    p.window_edges, p.batch
                )
            if merged is None:
                self.graph.merge_window(p.batch)
        p.merged = True
        with phase_span("scorers"):
            # history-feature accumulation: the serving feed of the model
            # scorers (models/history.py)
            self._observe_history(p.batch, p.req_time)

    def prepare_batched_merge(self, p: "_PreparedTick"):
        """The interned window columns for the router's stacked merge, or
        None when this tick cannot join a stack (no spans, no host edge
        set, the fast path disabled, or an endpoint missing from the
        interner) — the caller then takes merge_prepared serially."""
        if (
            not p.trace_groups
            or p.merged
            or p.window_edges is None
            or not _host_edge_merge_enabled()
        ):
            return None
        return self.graph.intern_window_edges(p.window_edges)

    def adopt_batched_merge(
        self, p, src_row, dst_row, dist_row, count, cols, expected_version
    ) -> None:
        """Adopt this tick's lane of a stacked same-bucket union as its
        merge (tenancy/router.py). Raises StoreVersionDrift when the
        graph moved past the stacked snapshot — the router falls back to
        merge_prepared, which is bit-exact (set union)."""
        src_l, dst_l, dist_l = cols
        with step_timer.phase("graph_merge"), phase_span("merge"):
            self.graph.adopt_batched_merged(
                src_row,
                dst_row,
                dist_row,
                count,
                p.batch,
                max(dist_l),
                min(dist_l),
                expected_version=expected_version,
            )
        p.merged = True
        self._observe_history(p.batch, p.req_time)

    def finish_tick(self, p: "_PreparedTick") -> dict:
        """The tick's response assembly: device-stats drain + host body
        merge + datatypes, scorecard observation (process-wide and
        per-tenant), response dict."""
        request = p.request
        trace_groups = p.trace_groups
        with step_timer.phase("combine_assemble"), profiling.trace(
            "combine_assemble"
        ), phase_span("assemble"):
            combined = self._combine(p.realtime, p.stats_job)
            datatypes = [
                d.to_json()
                for d in combined_list_datatypes(combined)
            ]

        elapsed = prof_events.now_ms() - p.wall_t0
        tel_slo.SCORECARD.observe_tick(elapsed)
        tel_slo.TENANTS.observe_tick(self.tenant, elapsed)
        if p.arrival_ns:
            # freshness plane: the watermark stamped at parse is now
            # forecast-visible; under the stream engine prepare(N+1)
            # overlaps merge(N), so this elapsed tracks true visibility
            # latency, not the serialized sum of stages
            fresh_ns = prof_events.now_ns() - p.arrival_ns
            tel_freshness.observe(fresh_ns / 1e6)
            prof_events.emit("freshness", fresh_ns)
        with phase_span("assemble"):
            # response-shape encoding is assembly work too (the HTTP
            # byte encode is the server's separate encode-serve span)
            return {
                "uniqueId": request.get("uniqueId", ""),
                "combined": combined.to_json(),
                "dependencies": p.dependencies.to_json(),
                "datatype": datatypes,
                "log": (
                    f"processed {sum(len(g) for g in trace_groups)} spans / "
                    f"{len(trace_groups)} traces in {elapsed:.1f}ms "
                    f"(device_stats={self._use_device_stats})"
                ),
            }

    # -- uncapped raw ingest (VERDICT r1 #1) ---------------------------------

    # -- online history features (models/history.HistoryState) ---------------

    #: empty-hour catch-up bound: past this, the delta/rolling context is
    #: stale regardless, so the stream just resumes at the current hour
    HISTORY_MAX_CATCHUP_HOURS = 48

    def _observe_history(self, batch, req_time_ms: float) -> None:
        """Accumulate this tick's per-endpoint SERVER-span stats into the
        current hour's bucket; when the hour rolls over, fold the
        completed bucket into the online history-feature state — the
        serving feed for the inductive model head (MODELS.md). The fold
        emits the feature columns predicting the NEW hour, kept on
        `history_features` for consumers.

        Temporal discipline (review findings): quiet hours fold as
        zero-activity buckets so the state sees every hour exactly once
        in order (the trainer's replay steps consecutive slots — skipped
        hours would skew deltas/rolling windows); a request whose clock
        runs BEHIND the current bucket accumulates into it instead of
        folding a partial hour early, and one whose clock runs AHEAD of
        the server clamps to the server clock — otherwise a single
        far-future timestamp (e.g. microseconds where milliseconds
        belong) would advance the bucket past wall time and freeze folds
        until the clock caught up (one skewed client cannot corrupt the
        hour-keyed profiles in either direction)."""
        from kmamiz_tpu.models.history import HistoryState

        n_ep = len(self.graph.interner.endpoints)
        abs_hour = int(min(req_time_ms, self._now_ms()) // 3_600_000)
        sel = batch.valid & (batch.kind == KIND_SERVER)
        eids = batch.endpoint_id[sel]
        # graftlint: disable=dtype-drift -- host-side hour-bucket accumulators; f64 keeps long-run sums exact
        err4 = (batch.status_class[sel] == 4).astype(np.float64)
        err5 = (batch.status_class[sel] == 5).astype(np.float64)  # graftlint: disable=dtype-drift -- host-side accumulator (see above)
        lat = np.asarray(batch.latency_ms, dtype=np.float64)[sel]  # graftlint: disable=dtype-drift -- host-side accumulator (see above)

        scls = np.clip(
            np.asarray(batch.status_class, dtype=np.int64)[sel], 0, 5
        )

        with self._history_lock:
            if self.history is None:
                self.history = HistoryState(n_ep)
            if self._hour_bucket is not None and abs_hour > self._hour_bucket[0]:
                completed_hour = self._hour_bucket[0]
                self._fold_hour_locked(*self._hour_bucket)
                # zero-activity folds for fully quiet hours in between
                # (each builds its own model-feature matrix too, so the
                # forecast snapshot always matches its labeled hour)
                gap_first = completed_hour + 1
                gap_last = abs_hour - 1
                if gap_last - gap_first + 1 > self.HISTORY_MAX_CATCHUP_HOURS:
                    gap_first = gap_last - self.HISTORY_MAX_CATCHUP_HOURS + 1
                m = self.history.num_endpoints
                for h in range(gap_first, gap_last + 1):
                    self._fold_hour_locked(
                        h,
                        np.zeros(m),
                        np.zeros(m),
                        np.zeros(m),
                        np.zeros(m),
                        np.zeros(m),
                        np.zeros((m, 6)),
                        np.zeros((m, 6)),
                        np.zeros((m, 6)),
                    )
                self._hour_bucket = None
            if self._hour_bucket is None:
                self._hour_bucket = [
                    abs_hour,
                    np.zeros(n_ep),  # count
                    np.zeros(n_ep),  # err4
                    np.zeros(n_ep),  # err5
                    np.zeros(n_ep),  # lat sum
                    np.zeros(n_ep),  # lat sum of squares
                    np.zeros((n_ep, 6)),  # per-status-class count
                    np.zeros((n_ep, 6)),  # per-status-class lat sum
                    np.zeros((n_ep, 6)),  # per-status-class lat sq sum
                ]
            bucket = self._hour_bucket
            if len(bucket[1]) < n_ep:  # new endpoints interned this tick
                grow = n_ep - len(bucket[1])
                for i in range(1, 6):
                    bucket[i] = np.concatenate([bucket[i], np.zeros(grow)])
                for i in range(6, 9):
                    bucket[i] = np.concatenate(
                        [bucket[i], np.zeros((grow, 6))]
                    )
            np.add.at(bucket[1], eids, 1.0)
            np.add.at(bucket[2], eids, err4)
            np.add.at(bucket[3], eids, err5)
            np.add.at(bucket[4], eids, lat)
            np.add.at(bucket[5], eids, lat * lat)
            np.add.at(bucket[6], (eids, scls), 1.0)
            np.add.at(bucket[7], (eids, scls), lat)
            np.add.at(bucket[8], (eids, scls), lat * lat)

    def _fold_hour_locked(
        self,
        hour,
        count,
        err4_sum,
        err5_sum,
        lat_sum,
        lat_sq_sum,
        cls_count,
        cls_lat,
        cls_lat_sq,
    ) -> None:
        """Fold one completed hour into the state (trainer-equivalent
        shares: 5xx/count, log1p mean latency, active = saw traffic),
        assemble the FULL model-feature matrix for the predicted hour,
        and publish an atomic forecast snapshot (features + the graph
        edges + names as of THIS fold — the serving input of the
        forecast route, immune to endpoints interned later). Caller
        holds _history_lock.

        Feature-fidelity notes: latency CV mirrors the trainer's
        count-weighted mean of per-(endpoint,status) within-window CVs,
        approximated at status-CLASS granularity (distinct statuses in
        one class pool together). request_rate/log_volume reflect the
        tick pipeline's deduped, ZIPKIN_LIMIT-capped trace stream — for
        production forecasting, train on data collected through this
        same pipeline so those columns share a distribution."""
        from kmamiz_tpu.models import graphsage
        from kmamiz_tpu.models.trainer import SLOT_SECONDS

        safe = np.maximum(count, 1.0)
        lat_mean = lat_sum / safe
        src, dst, _dist, mask = self.graph.edge_arrays()
        self.history.set_degrees(src, dst, mask, len(count))
        hist_cols = self.history.step(
            hour % 24,
            err5_sum / safe,
            np.log1p(lat_mean),
            count > 0,
        )
        self.history_features = hist_cols
        self.history_predicted_hour = (hour % 24 + 1) % 24
        # trainer-faithful CV: per-(endpoint,status-class) CV from the
        # sum-of-squares identity, count-weighted like _per_slot_stats
        cls_safe = np.maximum(cls_count, 1.0)
        cls_mean = cls_lat / cls_safe
        cls_var = np.maximum(cls_lat_sq / cls_safe - cls_mean * cls_mean, 0.0)
        cls_cv = np.sqrt(cls_var) / np.maximum(cls_mean, 1e-9)
        cv = (cls_count * cls_cv).sum(axis=1) / safe
        n = len(count)
        replicas = np.ones(n, dtype=np.float32)
        if self._last_replicas:
            interner = self.graph.interner
            for eid in range(n):
                svc_name = interner.services.lookup(interner.service_of(eid))
                replicas[eid] = self._last_replicas.get(svc_name, 1.0)
        base = graphsage.assemble_features(
            count / SLOT_SECONDS,
            err4_sum / safe,
            err5_sum / safe,
            np.log1p(lat_mean),
            cv,
            replicas,
            np.log1p(count),
            count > 0,
            hour_of_day=float(self.history_predicted_hour),
        )
        self.history_model_features = np.concatenate(
            [np.asarray(base), hist_cols], axis=1
        )
        interner = self.graph.interner
        self.forecast_snapshot = {
            "features": self.history_model_features,
            "src": src,
            "dst": dst,
            "mask": mask,
            "names": [interner.endpoints.lookup(i) for i in range(n)],
            "predicted_hour": self.history_predicted_hour,
            # the forecast-payload memo key, mirroring the scorer cache's
            # (version, label-epoch) discipline (graph/store.py): the
            # served forecast is a pure function of the graph state at
            # fold time plus which hour was folded
            "cache_key": (
                int(self.graph.version),
                int(getattr(self.graph, "_label_epoch", 0)),
                int(hour),
            ),
        }
        # STLGT continual-training hook (KMAMIZ_STLGT=1): each fold
        # becomes an online example and may trigger a stale-slot refresh
        # inside the "stlgt-refresh" tick phase. Gated + lazily imported
        # so the default pipeline pays one env read per fold; a trainer
        # failure must not take the fold down (watchdog posture).
        try:
            from kmamiz_tpu.models import stlgt as _stlgt

            _stlgt.on_fold(self.forecast_snapshot)
        except Exception:
            res_metrics.incr("stlgtFoldErrors")
        # graftpilot recompute (KMAMIZ_CONTROL=1, docs/CONTROL.md):
        # admission / warm-up / scheduling decisions are pure functions
        # of (forecast, config) recomputed only here at the fold
        # boundary — the warm tick reads a stored verdict and never
        # computes. Same containment posture as the STLGT hook: a
        # controller fault must not take the fold down.
        try:
            from kmamiz_tpu import control as _control

            _control.on_fold(self.tenant, self.forecast_snapshot)
        except Exception:
            res_metrics.incr("controlFoldErrors")
        # graftcost continual retrain (KMAMIZ_COST=1, docs/COST_MODEL.md):
        # refit the program-cost regressor from the registry's label rows
        # at the fold boundary. The fit is one fixed-shape warm program
        # (cost/model.py), so this is a bounded off-tick cost — and the
        # same containment posture as the two hooks above.
        try:
            from kmamiz_tpu import cost as _cost

            _cost.on_fold(self.tenant)
        except Exception:
            res_metrics.incr("costFoldErrors")

    # -- history persistence (VERDICT r4 #4) ---------------------------------

    #: endpoints per snapshot part: bounds any single store document to a
    #: few MB (Mongo caps BSON documents at 16 MB; one monolithic doc at
    #: 10k+ endpoints would brush against it)
    HISTORY_SNAPSHOT_CHUNK = 2048

    def snapshot_history(self) -> "Optional[list]":
        """Serializable snapshot of the whole online model state:
        HistoryState accumulators, the in-progress hour bucket, and the
        published forecast snapshot — everything keyed by endpoint NAME
        (ids shift across restarts). Returns a LIST of part documents
        (endpoint ranges of HISTORY_SNAPSHOT_CHUNK) so no single store
        document outgrows a backend's size cap; None before the first
        observed tick. Rides the dispatch cron + shutdown syncAll like
        every other live cache (CModelHistoryState).

        Lock discipline: only cheap array memcpys happen under
        _history_lock; the base64 encoding of what can be tens of MB runs
        after release, so a flush never stalls the realtime tick."""
        from kmamiz_tpu.models.history import HistoryState, encode_array

        with self._history_lock:
            if self.history is None:
                return None
            saved_at = self._now_ms()
            state_arrays = {
                f: np.array(getattr(self.history, f))
                for f in HistoryState._ARRAY_FIELDS
            }
            window = [np.array(w) for w in self.history._window]
            started = self.history._started
            n_state = self.history.num_endpoints
            bucket = (
                None
                if self._hour_bucket is None
                else [self._hour_bucket[0]]
                + [np.array(a) for a in self._hour_bucket[1:]]
            )
            hist_feats = (
                None
                if self.history_features is None
                else np.array(self.history_features)
            )
            model_feats = (
                None
                if self.history_model_features is None
                else np.array(self.history_model_features)
            )
            predicted_hour = self.history_predicted_hour
            # the forecast snapshot dict is replaced wholesale on fold and
            # its arrays never mutate: safe to reference outside the lock
            snap = self.forecast_snapshot
        interner = self.graph.interner
        n_names = max(n_state, len(bucket[1]) if bucket else 0)
        names = [interner.endpoints.lookup(i) for i in range(n_names)]
        chunk = self.HISTORY_SNAPSHOT_CHUNK
        parts = max(1, -(-max(n_names, 1) // chunk))
        docs = []
        for p in range(parts):
            lo, hi = p * chunk, min((p + 1) * chunk, n_names)
            doc = {
                "savedAt": saved_at,
                "part": p,
                "parts": parts,
                "names": names[lo:hi],
                "state": {
                    "n": max(0, min(n_state, hi) - lo),
                    "started": started,
                    "window": [
                        encode_array(w[..., lo:hi]) for w in window
                    ],
                    **{
                        f.lstrip("_"): encode_array(
                            state_arrays[f][..., lo:hi]
                        )
                        for f in HistoryState._ARRAY_FIELDS
                    },
                },
                "hourBucket": None,
                "forecast": None,
                "historyFeatures": None,
                "modelFeatures": None,
                "predictedHour": predicted_hour,
            }
            if bucket is not None:
                doc["hourBucket"] = {
                    "hour": int(bucket[0]),
                    "arrays": [encode_array(a[lo:hi]) for a in bucket[1:]],
                }
            if hist_feats is not None:
                doc["historyFeatures"] = encode_array(hist_feats[lo:hi])
            if model_feats is not None:
                doc["modelFeatures"] = encode_array(model_feats[lo:hi])
            if p == 0 and snap is not None:
                # edge arrays are not per-endpoint; they live on part 0
                doc["forecast"] = {
                    "features": encode_array(np.asarray(snap["features"])),
                    "src": encode_array(np.asarray(snap["src"])),
                    "dst": encode_array(np.asarray(snap["dst"])),
                    "mask": encode_array(np.asarray(snap["mask"])),
                    "names": list(snap["names"]),
                    "predictedHour": snap["predicted_hour"],
                }
            docs.append(doc)
        return docs

    @staticmethod
    def _assemble_snapshot_parts(docs) -> "Optional[dict]":
        """Pick the newest COMPLETE part set from stored snapshot
        documents and merge it back into one logical snapshot."""
        from kmamiz_tpu.models.history import decode_array

        groups: Dict[float, list] = {}
        for d in docs or []:
            groups.setdefault(d.get("savedAt", 0), []).append(d)
        for saved_at in sorted(groups, reverse=True):
            parts = sorted(groups[saved_at], key=lambda d: d.get("part", 0))
            want = parts[0].get("parts", len(parts))
            if len(parts) != want or [
                d.get("part", 0) for d in parts
            ] != list(range(want)):
                continue  # torn write: fall back to the next-newest set
            if want == 1:
                return parts[0]

            def cat(getter, axis):
                # returns the DECODED concatenation: downstream decode_array
                # passes ndarrays through, so the boot restore never
                # re-encodes the multi-MB snapshot just to re-decode it
                arrs = [decode_array(getter(d)) for d in parts]
                return np.concatenate(arrs, axis=axis)

            first = parts[0]
            merged = {
                "savedAt": saved_at,
                "names": [nm for d in parts for nm in d["names"]],
                "state": {
                    "n": sum(d["state"]["n"] for d in parts),
                    "started": first["state"]["started"],
                    "window": [
                        cat(lambda d, i=i: d["state"]["window"][i], -1)
                        for i in range(len(first["state"]["window"]))
                    ],
                    **{
                        k: cat(lambda d, k=k: d["state"][k], -1)
                        for k in first["state"]
                        if k not in ("n", "started", "window")
                    },
                },
                "hourBucket": None,
                "forecast": first.get("forecast"),
                "historyFeatures": None,
                "modelFeatures": None,
                "predictedHour": first.get("predictedHour"),
            }
            if first.get("hourBucket") is not None:
                merged["hourBucket"] = {
                    "hour": first["hourBucket"]["hour"],
                    "arrays": [
                        cat(lambda d, i=i: d["hourBucket"]["arrays"][i], 0)
                        for i in range(len(first["hourBucket"]["arrays"]))
                    ],
                }
            for key in ("historyFeatures", "modelFeatures"):
                if first.get(key) is not None:
                    merged[key] = cat(lambda d, k=key: d[k], 0)
            return merged
        return None

    @staticmethod
    def _scatter_rows(a: np.ndarray, ids: np.ndarray, n_new: int):
        """Re-key a per-endpoint row array: saved row i lands at row
        ids[i] of a fresh n_new-row layout (trailing dims preserved)."""
        out = np.zeros((n_new,) + a.shape[1:], dtype=a.dtype)
        k = min(len(a), len(ids))
        out[ids[:k]] = a[:k]
        return out

    def restore_history(self, docs) -> None:
        """Rebuild the online model state from stored snapshot_history
        documents (boot path; live state always wins over a late
        restore). Saved endpoint names re-intern in THIS process — ids
        shift across restarts — and every per-endpoint column scatters
        to its new id. The forecast snapshot restores verbatim (it is
        self-contained: its edge ids index its own names list), so
        /model/forecast serves immediately after a restart, bit-equal to
        pre-restart. A downtime gap folds later as the existing
        zero-activity catch-up when the first live tick arrives."""
        from kmamiz_tpu.models.history import HistoryState, decode_array

        if isinstance(docs, dict):
            docs = [docs]
        doc = self._assemble_snapshot_parts(docs)
        if doc is None:
            return
        with self._history_lock:
            if self.history is not None:
                return  # live state outranks a stored snapshot
            names = doc.get("names") or []
            interner = self.graph.interner
            ids = np.asarray(
                [interner.intern_endpoint(nm) for nm in names],
                dtype=np.int64,
            )
            n_new = len(interner.endpoints)
            state = HistoryState.from_doc(doc["state"])
            state.remap(ids, n_new)
            self.history = state
            bucket = doc.get("hourBucket")
            if bucket is not None:
                self._hour_bucket = [int(bucket["hour"])] + [
                    self._scatter_rows(decode_array(a), ids, n_new)
                    for a in bucket["arrays"]
                ]
            if doc.get("historyFeatures") is not None:
                self.history_features = self._scatter_rows(
                    decode_array(doc["historyFeatures"]), ids, n_new
                )
            if doc.get("modelFeatures") is not None:
                self.history_model_features = self._scatter_rows(
                    decode_array(doc["modelFeatures"]), ids, n_new
                )
            self.history_predicted_hour = doc.get("predictedHour")
            fc = doc.get("forecast")
            if fc is not None:
                self.forecast_snapshot = {
                    "features": decode_array(fc["features"]),
                    "src": decode_array(fc["src"]),
                    "dst": decode_array(fc["dst"]),
                    "mask": decode_array(fc["mask"]),
                    "names": list(fc["names"]),
                    "predicted_hour": fc["predictedHour"],
                }

    def _wal_append(self, raw: bytes) -> None:
        """Durably log one successfully parsed ingest payload before its
        graph merge. No-op when the WAL is off or during WAL replay. An
        append failure counts (`walAppendErrors`) but does not abort the
        ingest — availability over durability, matching the storage
        layer's fail-open posture."""
        if self._wal is None or self._wal_replaying:
            return
        try:
            self._wal.append(raw)
        except OSError:
            res_metrics.incr("walAppendErrors")

    def _divert_poison(self, raw: bytes, source: str) -> str:
        """Classify a payload the native parser rejected and move it to
        the quarantine. Returns the reason code; raises ValueError
        instead when the real cause is a missing native extension (the
        payload is fine — callers fall back to the capped JSON path)."""
        from kmamiz_tpu import native

        reason = res_quarantine.classify_payload(raw)
        if reason is None:
            if not native.available():
                raise ValueError("native span loader unavailable")
            reason = res_quarantine.REASON_PARSE_ERROR
        res_quarantine.quarantine_for(self.tenant).put(raw, reason, source=source)
        return reason

    def replay_wal(self) -> dict:
        """Rebuild ingest state from the WAL (boot path, after a crash).
        Each durable payload re-ingests through ingest_raw_window; the
        edge-store merge is deterministic and the fresh dedup map replays
        registrations in the original order, so the recovered graph is
        bit-exact with the pre-crash one (tools/chaos_probe.py pillar 4
        asserts the signature). Only parsed payloads were appended, but a
        payload that fails to re-parse quarantines instead of aborting
        the boot."""
        totals = {"replayed": 0, "spans": 0, "quarantined": 0}
        if self._wal is None:
            return totals
        self._wal_replaying = True
        try:
            for payload in self._wal.replay():
                out = self.ingest_raw_window(payload)
                totals["replayed"] += 1
                totals["spans"] += out.get("spans", 0)
                totals["quarantined"] += out.get("quarantined", 0)
        finally:
            self._wal_replaying = False
        res_metrics.incr("walReplays")
        return totals

    def _quarantined_summary(self, reason: str, wall_t0: float) -> dict:
        """ingest_raw_window's return shape for a fully diverted payload:
        zero new spans, the graph untouched."""
        return {
            "spans": 0,
            "traces": 0,
            "endpoints": len(self.graph.interner.endpoints),
            "edges": int(self.graph.n_edges),
            "quarantined": 1,
            "reason": reason,
            "ms": round(prof_events.now_ms() - wall_t0, 1),
        }

    def ingest_raw_window(self, raw: bytes) -> dict:
        """Raw Zipkin response bytes -> persistent device graph, uncapped.

        The realtime tick (collect) honors the reference's 2,500-trace cap;
        this is the scale path that lifts it: the native SoA loader
        (native/kmamiz_spans.cpp) scans the bytes straight into device
        arrays — no json.loads, no per-span dicts — applies the same
        processed-trace dedup, and merges the window into the HBM edge
        store serving the graph scorers. Feed it from
        ZipkinClient.get_trace_list_raw (POST /ingest on the DP server).

        A malformed payload (or one over the KMAMIZ_INGEST_MAX_BYTES
        cap) diverts to the quarantine with a reason code and returns a
        zero-span summary carrying ``quarantined``/``reason`` — the
        caller's pipeline keeps going. KMAMIZ_QUARANTINE=0 restores the
        old behavior (ValueError). A missing native extension still
        raises ValueError either way (callers fall back to collect)."""
        from kmamiz_tpu.core.spans import raw_spans_to_batch

        t_start = self._now_ms()  # domain time for the dedup registration
        wall_t0 = prof_events.now_ms()
        tel_slo.INGEST_PAYLOADS.inc()
        quarantine_on = res_quarantine.enabled()
        if quarantine_on and len(raw) > res_quarantine.max_payload_bytes():
            # size gate BEFORE the parse: a trace bomb never reaches the
            # native scanner, the interner, or the device
            with phase_span("quarantine"):
                res_quarantine.quarantine_for(self.tenant).put(
                    raw,
                    res_quarantine.REASON_TRACE_BOMB,
                    source="ingest_raw_window",
                )
            return self._quarantined_summary(
                res_quarantine.REASON_TRACE_BOMB, wall_t0
            )
        with self._dedup_lock:
            skipset = self._skipset_locked()
            skip_blob = None if skipset is not None else self._skip_blob_locked()
            session = self._raw_session_locked()
        with step_timer.phase("raw_ingest_parse"), phase_span("parse"):
            out = raw_spans_to_batch(
                raw,
                interner=self.graph.interner,
                skip_blob=skip_blob,
                skipset=skipset,
                session=session,
            )
        if out is None:
            if not quarantine_on:
                raise ValueError(
                    "native span loader unavailable or malformed payload"
                )
            with phase_span("quarantine"):
                reason = self._divert_poison(raw, "ingest_raw_window")
            return self._quarantined_summary(reason, wall_t0)
        batch, kept = out
        with phase_span("wal-append"):
            self._wal_append(raw)
        # dedup state during the (long) parse: the blob path snapshots
        # before parsing (a trace a concurrent collect() processes in
        # between merges twice — benign for the set-union edge store);
        # the persistent-skipset path sees mid-parse registrations live,
        # which only ever skips MORE duplicates. Registrations are never
        # lost to a concurrent dict rebuild either way.
        self._register_processed(kept, t_start)
        if batch.n_spans:
            with step_timer.phase("raw_ingest_graph"), profiling.trace(
                "raw_ingest_graph"
            ), phase_span("merge"):
                self.graph.merge_window(batch)
        return {
            "spans": batch.n_spans,
            "traces": len(kept),
            "endpoints": batch.num_endpoints,
            "edges": int(self.graph.n_edges),
            "ms": round(prof_events.now_ms() - wall_t0, 1),
        }

    def _register_processed(self, kept, when_ms: float) -> None:
        """Register kept trace ids in the processed map + TTL prune (the
        one definition both raw-ingest paths share). When the parse
        supplied the raw skip-entry bytes of the kept records
        (KeptTraceIds.blob) and every id is new — the steady streaming
        case — the blob appends as ONE slice instead of re-encoding
        each id."""
        from kmamiz_tpu.native import encode_skip_entry

        blob = getattr(kept, "blob", None)
        with self._dedup_lock:
            if (
                blob is not None
                and kept
                and all(t not in self._processed for t in kept)
            ):
                # prescan-deduped ids, all new: dict additions and blob
                # entries stay 1:1 (the blob layout is byte-identical to
                # encode_skip_entry, absent markers included)
                self._skip_entries += blob
                self._processed.update(zip(kept, [when_ms] * len(kept)))
            else:
                for tid in kept:
                    if tid not in self._processed:
                        self._skip_entries += encode_skip_entry(tid)
                    self._processed[tid] = when_ms
            self._prune_processed_locked(when_ms)

    # -- streaming raw ingest: depth-k ring, parse(k+1..k+depth) ahead -------

    @staticmethod
    def _stream_depth(depth: Optional[int] = None) -> int:
        """Bounded-ring depth for ingest_raw_stream: how many parsed
        chunks may sit between the fetch/parse stage and the
        pack/transfer stage. depth=1 reproduces the former one-in-flight
        pipeline; deeper rings let a fast parser absorb device-merge
        jitter (each waiting chunk pins its SpanBatch host arrays, so the
        bound is a memory knob too)."""
        if depth is None:
            try:
                depth = int(
                    os.environ.get("KMAMIZ_INGEST_DEPTH", DEFAULT_STREAM_DEPTH)
                )
            except ValueError:
                depth = DEFAULT_STREAM_DEPTH
        return max(1, depth)

    def ingest_raw_stream(self, chunks, depth: Optional[int] = None) -> dict:
        """Pipelined uncapped ingest over an iterable of raw Zipkin
        responses (e.g. paginated fetches, or km_split_groups over one
        giant buffer), structured as three decoupled stages around a
        bounded ring of `depth` parsed chunks (KMAMIZ_INGEST_DEPTH,
        default 2):

        1. fetch/parse (worker thread): pulls the next raw chunk — so a
           paginated source's HTTP fetch overlaps everything downstream —
           native-parses it (ctypes releases the GIL), registers its kept
           trace ids, and enqueues the batch;
        2. pack/transfer (this thread): pops batches in order, packs
           trace rows, and transfers + dispatches the walk kernel
           (merge_window stage=True);
        3. device-merge (device queue): staged windows collapse into
           async pre-unions while later chunks stream, and the final
           drain resolves ONE union sort over everything.

        With depth > 1 the parser can run ahead of a slow device merge by
        up to `depth` chunks instead of stalling after one, so parse wall
        time hides the device round trips (VERDICT r2 #1b generalized).

        Dedup semantics match chunk-by-chunk ingest_raw_window exactly:
        chunk k's kept trace ids register BEFORE chunk k+1's parse
        snapshots the processed set (both happen in order on the single
        fetch/parse worker). The span-id map (duplicate-id collapse +
        parent resolution) is scoped PER CHUNK — the same scope the
        reference has under paginated Zipkin fetches, where each page is
        a separate response with its own span map (Traces.ts builds its
        Map per response). Span ids are unique per trace in real Zipkin
        data and groups never split across chunks, so graph results
        (edges/endpoints) are identical to the one-shot path; only
        adversarial cross-trace id collisions can change the
        processed-row count.

        Failure semantics: per-chunk quarantine. A malformed chunk
        diverts to the quarantine with a reason code and the stream
        KEEPS GOING — the graph the surviving chunks build is bit-exact
        with ingesting only those chunks (tests/test_resilience.py).
        With KMAMIZ_QUARANTINE=0 the old per-chunk at-least-once abort
        returns: every chunk parsed before the poison merges and
        registers first, then the error raises. A missing native
        extension always aborts (nothing can parse).

        Returns the ingest_raw_window totals plus overlap accounting
        (parse_ms / merge_ms / saved_ms), `pipeline_depth` and the peak
        ring occupancy actually reached (`ring_peak`), and a per-chunk
        phase breakdown (`chunk_detail`: spans / parse_ms / merge_ms /
        transfer_ms per chunk, plus `drain_ms` for the final device
        sync) — enough to reconstruct the pipeline's critical path with
        the host->device copy priced at any bandwidth (the round-5 bench
        did exactly that)."""
        from kmamiz_tpu.core.spans import raw_spans_to_batch

        depth = self._stream_depth(depth)
        wall_t0 = prof_events.now_ms()  # wall accounting: monotonic, not
        # the injectable domain clock (a virtual clock frozen mid-call
        # would zero ms/saved_ms)
        parse_ms = 0.0
        merge_ms = 0.0
        totals = {"spans": 0, "traces": 0, "chunks": 0}
        quarantined = {"n": 0}
        chunk_detail = []
        ring: "queue.Queue" = queue.Queue(maxsize=depth)
        ring_peak = 0
        stop = threading.Event()  # consumer bail-out: unblock the worker

        def _put(item) -> bool:
            while not stop.is_set():
                try:
                    ring.put(item, timeout=0.1)
                    return True
                except queue.Full:
                    continue
            if item[0] == "chunk":
                # the consumer bailed with this parsed chunk in hand:
                # it never merges. Count it — a silently shrinking
                # window must be visible in /health/timings.
                res_metrics.incr("ingestDropped")
            return False

        def _producer() -> None:
            """Stage 1: fetch + parse + dedup-register, strictly in chunk
            order. parse_ms per chunk includes the source fetch (the
            iterator has exactly one consumer: this thread)."""
            quarantine_on = res_quarantine.enabled()
            size_cap = res_quarantine.max_payload_bytes()
            try:
                it = iter(chunks)
                while not stop.is_set():
                    try:
                        raw = next(it)
                    except StopIteration:
                        break
                    tel_slo.INGEST_PAYLOADS.inc()
                    if quarantine_on and len(raw) > size_cap:
                        res_quarantine.quarantine_for(self.tenant).put(
                            raw,
                            res_quarantine.REASON_TRACE_BOMB,
                            source="ingest_raw_stream",
                        )
                        quarantined["n"] += 1
                        continue
                    with self._dedup_lock:
                        skipset = self._skipset_locked()
                        skip_blob = (
                            None
                            if skipset is not None
                            else self._skip_blob_locked()
                        )
                        session = self._raw_session_locked()
                    t0 = prof_events.now_ms()
                    out = raw_spans_to_batch(
                        raw,
                        interner=self.graph.interner,
                        skip_blob=skip_blob,
                        skipset=skipset,
                        session=session,
                    )
                    dt = prof_events.now_ms() - t0
                    step_timer.record("ingest_parse", dt)
                    if out is None:
                        if quarantine_on:
                            # divert the poison chunk, keep streaming;
                            # _divert_poison re-raises only for a
                            # missing native extension, which aborts
                            # below like any source error
                            self._divert_poison(raw, "ingest_raw_stream")
                            quarantined["n"] += 1
                            continue
                        _put(
                            (
                                "error",
                                ValueError(
                                    "native span loader unavailable or "
                                    "malformed payload"
                                ),
                                dt,
                            )
                        )
                        return
                    batch, kept = out
                    self._wal_append(raw)
                    # registration precedes the next iteration's parse,
                    # so chunk k+1 snapshots a processed set that already
                    # includes chunk k — regardless of ring depth
                    self._register_processed(kept, self._now_ms())
                    if not _put(("chunk", (batch, kept), dt)):
                        return
            except BaseException as err:  # source iterator raised: the
                # former ThreadPoolExecutor surfaced it via fut.result()
                _put(("error", err, 0.0))
                return
            _put(("end", None, 0.0))

        worker = threading.Thread(
            target=_producer, name="ingest-raw-parse", daemon=True
        )
        worker.start()
        pending_err: Optional[BaseException] = None
        try:
            while True:
                ring_peak = max(ring_peak, ring.qsize())
                tag, payload, dt = ring.get()
                if tag == "end":
                    break
                parse_ms += dt
                if tag == "error":
                    pending_err = payload
                    break
                batch, kept = payload
                t0 = prof_events.now_ms()
                chunk_transfer_ms = 0.0
                if batch.n_spans:
                    with step_timer.phase("raw_ingest_graph"), profiling.trace(
                        "raw_ingest_graph"
                    ), phase_span("merge"):
                        # stage: walk-only dispatch per chunk, ONE union
                        # sort over all chunks at the drain below
                        chunk_transfer_ms = self.graph.merge_window(
                            batch, stage=True
                        )
                chunk_merge_ms = prof_events.now_ms() - t0
                step_timer.record("ingest_merge", chunk_merge_ms)
                merge_ms += chunk_merge_ms
                chunk_detail.append(
                    {
                        "spans": batch.n_spans,
                        "parse_ms": round(dt, 1),
                        "merge_ms": round(chunk_merge_ms, 1),
                        "transfer_ms": round(chunk_transfer_ms, 1),
                    }
                )
                totals["spans"] += batch.n_spans
                totals["traces"] += len(kept)
                totals["chunks"] += 1
        finally:
            stop.set()
            worker.join(timeout=30.0)
        if pending_err is not None:
            raise pending_err

        # the deferred merge chain resolves here: n_edges blocks on the
        # device queue, so charge it explicitly as the pipeline's drain —
        # also the stream's one pre-existing device fence, so the
        # host-transfer span boundary costs no extra sync
        t0 = prof_events.now_ms()
        with phase_span("host-transfer"):
            n_edges = int(self.graph.n_edges)
        drain_ms = prof_events.now_ms() - t0
        wall_ms = prof_events.now_ms() - wall_t0
        return {
            **totals,
            "quarantined": quarantined["n"],
            "endpoints": len(self.graph.interner.endpoints),
            "edges": n_edges,
            "chunk_detail": chunk_detail,
            "drain_ms": round(drain_ms, 1),
            "ms": round(wall_ms, 1),
            "parse_ms": round(parse_ms, 1),
            "merge_ms": round(merge_ms, 1),
            "saved_ms": round(max(0.0, parse_ms + merge_ms - wall_ms), 1),
            "pipeline_depth": depth,
            "ring_peak": ring_peak,
        }

    def ingest_from_zipkin(
        self,
        zipkin,
        look_back_ms: float,
        end_ts: "Optional[float]" = None,
        pages: int = DEFAULT_STREAM_CHUNKS,
    ) -> dict:
        """THE big-window route: paginated raw Zipkin fetch -> chunked
        native parse -> overlapped device merge, end to end. Each page's
        HTTP fetch + native parse runs on the pipeline's worker thread
        while the previous page packs/transfers/merges into the device
        graph (ingest_raw_stream). This composition replaces the
        reference's capped realtime tick for backfills and large windows
        (data_processor.rs:75-126 processes at most 2,500 traces per
        tick; this path is uncapped).

        Raises ValueError when the native loader is unavailable (callers
        fall back to the capped get_trace_list path)."""
        return self.ingest_raw_stream(
            zipkin.iter_trace_pages_raw(look_back_ms, end_ts, pages=pages)
        )

    # -- hybrid combine: device numeric stats + host body merge --------------

    def _combine(
        self, realtime: RealtimeDataList, stats_job: "Optional[DeviceStatsJob]"
    ) -> "CombinedRealtimeDataList":
        from kmamiz_tpu.domain.combined import CombinedRealtimeDataList

        if stats_job is None:
            return realtime.to_combined_realtime_data()

        records = realtime.to_json()  # free accessor, not a materialization

        # group records by (uniqueEndpointName, raw status) for body merging
        # and base fields; numeric stats come from the device kernel, whose
        # interner also keys segments by the raw status value
        groups: Dict[tuple, List[dict]] = {}
        for r in records:
            groups.setdefault((r["uniqueEndpointName"], r["status"]), []).append(r)

        # the batched native body merge runs BEFORE blocking on the device
        # result, so any residual transfer wait hides behind it
        from kmamiz_tpu.core import schema

        group_items = list(groups.items())
        merged_bodies = schema.merge_and_infer_bodies(
            schema.body_pairs_for_groups([rows for _key, rows in group_items])
        )

        # the one device->host fence the tick already pays: the packed
        # stats drain (copy_to_host_async started at dispatch) — the span
        # boundary rides it, adding no sync of its own
        with phase_span("host-transfer"):
            stats = stats_job.result()
        out: List[dict] = []
        for i, ((uen, status), rows) in enumerate(group_items):
            # both sides key segments by the RAW status value (spans without
            # http.status_code carry None), so two statuses that stringify
            # identically (None vs "None") stay distinct on host and device
            seg_stats = stats[(uen, status)]
            sample = rows[0]

            replica = rows[0].get("replica")
            for curr in rows[1:]:
                if replica and curr.get("replica"):
                    replica += curr["replica"]

            request_body, request_schema = merged_bodies[2 * i]
            response_body, response_schema = merged_bodies[2 * i + 1]
            out.append(
                {
                    "uniqueServiceName": sample["uniqueServiceName"],
                    "uniqueEndpointName": uen,
                    "service": sample["service"],
                    "namespace": sample["namespace"],
                    "version": sample["version"],
                    "method": sample["method"],
                    "status": status,
                    "combined": seg_stats["count"],
                    "requestBody": request_body,
                    "requestSchema": request_schema,
                    "responseBody": response_body,
                    "responseSchema": response_schema,
                    "avgReplica": (replica / len(rows)) if replica else None,
                    "latestTimestamp": seg_stats["latest_timestamp"],
                    "latency": {
                        "mean": to_precise(seg_stats["mean"]),
                        "cv": to_precise(seg_stats["cv"]),
                    },
                    "requestContentType": sample.get("requestContentType"),
                    "responseContentType": sample.get("responseContentType"),
                }
            )
        return CombinedRealtimeDataList(out)


class DeviceStatsJob:
    """Asynchronous device segment-stats over realtime records: the
    constructor dispatches the kernel and starts the packed result
    streaming back (copy_to_host_async); result() blocks only for
    whatever hasn't already overlapped with host work."""

    def __init__(self, records: List[dict]) -> None:
        from kmamiz_tpu.core.interning import StringInterner
        from kmamiz_tpu.ops.pallas_kernels import segment_backend

        endpoints = StringInterner()
        statuses = StringInterner()
        n = len(records)
        cap = 8
        while cap < n:
            cap *= 2

        eid = np.zeros(cap, dtype=np.int32)
        sid = np.zeros(cap, dtype=np.int32)
        scl = np.zeros(cap, dtype=np.int8)
        lat = np.zeros(cap, dtype=np.float32)
        ts_abs = np.zeros(n, dtype=np.int64)
        valid = np.zeros(cap, dtype=bool)
        # intern the RAW status value (None, int, or str are all hashable);
        # the status class still derives from its string form. Interning raw
        # keeps device segments aligned with the host's raw-status groupby.
        for i, r in enumerate(records):
            eid[i] = endpoints.intern(r["uniqueEndpointName"])
            sid[i] = statuses.intern(r["status"])
            s = str(r["status"])
            scl[i] = int(s[0]) if s[:1].isdigit() else 0
            lat[i] = r["latency"]
            ts_abs[i] = r["timestamp"]
            valid[i] = True
        self._ts_base = int(ts_abs.min()) if n else 0
        ts_rel = np.zeros(cap, dtype=np.int32)
        ts_rel[:n] = (ts_abs - self._ts_base).astype(np.int32)

        self._endpoints = endpoints
        self._statuses = statuses
        # shape-canonicalization (PR 3 audit): num_endpoints/num_statuses
        # are STATIC args of window_stats, so exact counts would compile
        # a fresh XLA program for every distinct (endpoint, status)
        # census — the recompiles no prewarm can anticipate. Pow2 buckets
        # bound the program set to O(log^2) and keep per-segment sums
        # bit-identical (padded segments receive no rows; result()
        # decodes with the bucketed stride and still iterates only the
        # real counts).
        num_endpoints = _pad_size(max(len(endpoints), 1))
        self._num_statuses = _pad_size(max(len(statuses), 1))

        from kmamiz_tpu.parallel.mesh import active_mesh

        mesh = active_mesh()
        if mesh is not None and cap % mesh.shape["spans"] == 0:
            # deployed multi-device path (VERDICT r4 #1): span rows
            # shard over the mesh, each chip computes its local segment
            # sums, one psum over ICI merges them — the collective
            # replacement for the reference's single-threaded
            # combine-merge (CombinedRealtimeDataList.ts:278-315)
            from jax.sharding import NamedSharding, PartitionSpec as P

            from kmamiz_tpu.parallel.mesh import sharded_window_stats

            sh = NamedSharding(mesh, P("spans"))
            put = lambda a: jax.device_put(np.asarray(a), sh)
            stats = sharded_window_stats(
                mesh,
                put(eid),
                put(sid),
                put(scl),
                put(lat.astype(np.float32)),
                put(ts_rel),
                put(valid),
                num_endpoints=num_endpoints,
                num_statuses=self._num_statuses,
                backend=segment_backend(),
            )
        else:
            # explicit device_put (not jnp.asarray): implicit transfers
            # trip jax.transfer_guard("disallow") on a real TPU tick
            stats = window_ops.window_stats(
                jax.device_put(eid),
                jax.device_put(sid),
                jax.device_put(scl),
                jax.device_put(lat.astype(np.float32)),
                jax.device_put(ts_rel),
                jax.device_put(valid),
                num_endpoints=num_endpoints,
                num_statuses=self._num_statuses,
                backend=segment_backend(),
            )
        # ONE packed buffer: individual np.asarray calls each pay a full
        # device-sync round trip
        self._packed = _pack_stats(
            stats.count.astype(jnp.float32),
            stats.latency_mean.astype(jnp.float32),
            stats.latency_cv.astype(jnp.float32),
            stats.latest_timestamp_rel,
        )
        if hasattr(self._packed, "copy_to_host_async"):
            self._packed.copy_to_host_async()

    def result(self) -> Dict[tuple, dict]:
        packed = jax.device_get(self._packed)  # graftlint: disable=host-sync-in-hot-path -- single packed fetch per tick, prefetched via copy_to_host_async
        count, mean, cv = packed[0], packed[1], packed[2]
        ts = packed[3].view(np.int32).astype(np.int64) + self._ts_base

        out: Dict[tuple, dict] = {}
        for e in range(len(self._endpoints)):
            for s in range(len(self._statuses)):
                seg = e * self._num_statuses + s
                if count[seg] > 0:
                    out[(self._endpoints.lookup(e), self._statuses.lookup(s))] = {
                        "count": int(count[seg]),
                        "mean": float(mean[seg]),
                        "cv": float(cv[seg]),
                        "latest_timestamp": int(ts[seg]),
                    }
        return out


def combined_list_datatypes(combined) -> list:
    """Datatype extraction from combined data (the per-window slice of
    CombinedRealtimeDataList.extractEndpointDataType)."""
    return combined.extract_endpoint_data_type()
