"""Benchmark: span-window ingest throughput + graph-metric refresh latency.

Prints ONE JSON line:
  {"metric": ..., "value": N, "unit": ..., "vs_baseline": N,
   "platform": ..., "device_kind": ..., "device_count": N, ...extras}

NOT RUN ON THE CHIP YET. PR 21 re-cut this file for a machine where one
process holds the accelerator (the process split, the refusal off a TPU,
block_until_ready walls, a failing section ends the run) and exercised
the result end to end only on the CPU, at reduced size, in a scratch
copy with the refusal bypassed; its chip time went to chip_smoke.py.
Failures that only a TPU produces in the `--section inproc` child are
therefore unseen, and so is the reading that the r06 'Array has been
deleted' was this file's (one init_params handed to two donating
trainers, fixed in the sage section) rather than the trainer's — the
trainer itself did run on the chip (chip_smoke.py phase C). The first
`benchmark` PR starts by running `python bench.py --section inproc`
there; delete this paragraph when it has.

One process per chip. `python bench.py` is a parent that never imports
JAX: it runs the in-process sections as ONE child (`--section inproc`,
the process that holds the accelerator) and then each subprocess
section (growth probe arms, warm-boot probe arms, chaos probe, scenario
soak, graftsoak sweep, fleet bench, counterfactual soak) as a sibling,
in turn — at no moment do two live processes hold JAX on the chip. A
child that measures the device inherits the platform; the children that
are CPU processes by design (soak pool workers, kill-9 crash children,
fleet workers) pin JAX_PLATFORMS=cpu themselves and say so in their
output. The device sections refuse to run when the backend is not a
TPU, and a failing section ends the run non-zero — nothing is stored as
an `*_error` key and printed past.

The HEADLINE metric is the deployed big-window ingest path: paginated raw
Zipkin JSON chunks through DataProcessor.ingest_raw_stream — native SoA
parse of chunk k+1 (native/kmamiz_spans.cpp, GIL released) overlapping
chunk k's intern/pack + device window-merge into the persistent endpoint
graph — exactly the route POST /ingest and the first-time-setup backfill
run in production (server/processor.py, server/dp_server.py). The wall
is the wall: the host->device copy is inside it. The serial one-shot
path, the device-only kernels, and the 2,500-trace DP tick are extras.

Workload (BASELINE.json configs): a MicroViSim-scale synthetic mesh with
1k services / 10k endpoints and a 1M-span window — the reference caps at
2,500 traces per 5 s tick (~<20k spans/sec sustained; see BASELINE.md), and
the north-star target is >=1M spans/sec with p50 full risk+instability graph
refresh < 50 ms at 10k endpoints.

Estimators: throughput metrics report BEST-of-N with the full rep list
and median in the extras; latency metrics (graph refresh, HTTP p50)
keep the median. Each estimator is labeled in the extras.

Timing method: host clock around work that ends in
jax.block_until_ready (or a host fetch of the result). Device kernels
are the registered jitted programs called with device-resident
arguments, one dispatch per rep, after one unrecorded compile run.
"""
from __future__ import annotations

import argparse
import json
import os
import shutil
import subprocess
import sys
import tempfile
import time
from pathlib import Path

import numpy as np

N_SPANS = 1 << 20  # ~1M spans per window
N_ENDPOINTS = 10_000
N_SERVICES = 1_000
N_STATUSES = 8
SPANS_PER_TRACE = 7
GRAPH_EDGES = 50_000
BASELINE_SPANS_PER_SEC = 1_000_000.0  # BASELINE.json north star


def _reps(run, reps: int = 5):
    """Wall times of `reps` runs of run() (which must block on real
    results), after one unrecorded warmup/compile run."""
    run()
    times = []
    for _ in range(reps):
        t0 = time.perf_counter()
        run()
        times.append(time.perf_counter() - t0)
    return times


def _timed(run, reps: int = 5):
    """BEST-of-reps wall time (the throughput estimator, VERDICT r3 #1).
    Callers that want "typical" latency use _timed_median."""
    return float(min(_reps(run, reps)))


def _timed_median(run, reps: int = 5):
    """median-of-reps wall time: the right estimator for latency metrics
    where a typical run, not peak capability, is the claim."""
    return float(np.median(_reps(run, reps)))


# the bench's synthetic raw-Zipkin windows come from the shared generator
# (legacy 200-svc/50-url defaults reproduce the historical bench shape
# byte for byte; urls_per_service>0 selects the BASELINE 10k-endpoint
# shape). Re-exported so tools/profile_parse.py keeps profiling the exact
# workload the headline measures.
from kmamiz_tpu.synth import make_raw_window  # noqa: E402


def inproc_main() -> dict:
    """The in-process sections, in the ONE child that holds the chip."""
    global BENCH_T0
    BENCH_T0 = time.perf_counter()
    from kmamiz_tpu.core import compile_cache

    # the persistent cache at the one place core/compile_cache.py rules:
    # steady-state programs load from disk instead of paying the union
    # compiles every run; a fresh directory records the cold walls
    compile_cache.enable()
    import jax
    import jax.numpy as jnp

    device = jax.devices()[0]
    device_header = {
        "platform": device.platform,
        "device_kind": device.device_kind,
        "device_count": len(jax.devices()),
    }
    if device.platform != "tpu":
        raise SystemExit(
            f"bench.py: the device sections measure a TPU and this process "
            f"runs on {device.platform!r} ({device.device_kind}); refusing "
            f"to file its walls under device metric names"
        )

    from kmamiz_tpu.core.spans import pack_trace_rows
    from kmamiz_tpu.ops import scorers, window

    rng = np.random.default_rng(0)

    # ---- window pipeline inputs: 1M-span synthetic window ------------------
    endpoint_id = rng.integers(0, N_ENDPOINTS, N_SPANS, dtype=np.int32)
    status_id = jnp.asarray(rng.integers(0, N_STATUSES, N_SPANS, dtype=np.int32))
    status_class = jnp.asarray(
        rng.choice([2, 4, 5], N_SPANS, p=[0.95, 0.04, 0.01]).astype(np.int8)
    )
    latency = jnp.asarray(rng.gamma(2.0, 50.0, N_SPANS).astype(np.float32))
    ts_rel = jnp.asarray(rng.integers(0, 30_000_000, N_SPANS, dtype=np.int32))
    valid = jnp.ones(N_SPANS, dtype=bool)

    # forest of ~7-span traces, alternating CLIENT/SERVER, trace-row packed
    # for the MXU ancestor walk (the production merge path layout)
    trace_of = (np.arange(N_SPANS) // SPANS_PER_TRACE).astype(np.int32)
    parent = np.arange(-1, N_SPANS - 1, dtype=np.int32)
    parent[::SPANS_PER_TRACE] = -1
    kind = np.full(N_SPANS, 1, dtype=np.int8)
    kind[1::2] = 2

    def host_pack():
        packed = pack_trace_rows(trace_of, N_SPANS, parent)
        return packed, packed.parent_slots(parent)

    packing_host_ms = _timed(lambda: host_pack(), reps=3) * 1000
    packed, pslot = host_pack()

    from kmamiz_tpu.core.spans import _pad_size as _pow2

    bench_depth = min(
        window.MAX_DEPTH, _pow2(max(1, packed.max_trace_len - 1), minimum=4)
    )
    parent_slot2 = jnp.asarray(packed.pack(pslot, -1))
    kind2 = jnp.asarray(packed.pack(kind, 0))
    valid2 = jnp.asarray(packed.pack(np.ones(N_SPANS, bool), False))
    ep2 = jnp.asarray(packed.pack(endpoint_id, 0))
    endpoint_id = jnp.asarray(endpoint_id)

    def digest(parts):
        return sum(jnp.sum(p.astype(jnp.float32)) for p in parts)

    parent_d = jnp.asarray(parent)
    kind_d = jnp.asarray(kind)
    all_valid_d = jnp.ones(N_SPANS, bool)

    def window_once():
        stats = window.window_stats(
            endpoint_id,
            status_id,
            status_class,
            latency,
            ts_rel,
            valid,
            num_endpoints=N_ENDPOINTS,
            num_statuses=N_STATUSES,
        )
        # production merge policy: walk depth capped to the window's
        # longest chain, pow2-bucketed (graph/store.py merge_window)
        edges = window.dependency_edges_packed(
            parent_slot2, kind2, valid2, ep2, max_depth=bench_depth
        )
        jax.block_until_ready((stats, edges))

    # ---- custom-kernel story: MXU packed walk vs flat gather walk ----------
    # the trace-row-packed ancestor walk (one-hot einsums on the MXU) is the
    # production default on a TPU; the flat gather walk is what a naive
    # translation would do. Like-for-like: SAME depth cap.
    def mxu_walk():
        jax.block_until_ready(
            window.dependency_edges_packed(
                parent_slot2, kind2, valid2, ep2, max_depth=bench_depth
            )
        )

    def flat_walk():
        jax.block_until_ready(
            window.dependency_edges(
                parent_d, kind_d, all_valid_d, endpoint_id, max_depth=bench_depth
            )
        )

    walk_mxu_ms = _timed(mxu_walk, reps=3) * 1000
    walk_flat_ms = _timed(flat_walk, reps=3) * 1000

    # the SHARDED walks (parallel/mesh.py) on a 1-device mesh: the per-chip
    # cost of the sharded code path
    from kmamiz_tpu.parallel import mesh as pmesh

    mesh1 = pmesh.make_mesh(1)

    def sharded_flat_walk():
        jax.block_until_ready(
            pmesh.sharded_dependency_edges(
                mesh1, parent_d, kind_d, all_valid_d, endpoint_id,
                max_depth=bench_depth,
            )
        )

    def sharded_packed_walk():
        jax.block_until_ready(
            pmesh.sharded_dependency_edges_packed(
                mesh1, parent_slot2, kind2, valid2, ep2, max_depth=bench_depth
            )
        )

    walk_sharded_packed_ms = _timed(sharded_packed_walk, reps=3) * 1000
    walk_sharded_flat_ms = _timed(sharded_flat_walk, reps=3) * 1000

    # sustained ingest charges the per-window host packing cost the
    # production merge path pays, not just the device kernels
    ingest_dt = _timed(window_once) + packing_host_ms / 1000
    spans_per_sec = N_SPANS / ingest_dt

    # ---- end-to-end ingest: raw Zipkin bytes -> window stats ---------------
    # The kernel number above excludes the host-side conversion of raw
    # Zipkin JSON. This metric charges the WHOLE path on every rep: native
    # JSON scan (native/kmamiz_spans.cpp) -> SoA batch + interning ->
    # host->device transfer -> window stats + MXU dependency walk -> result
    # fetch. Span shape mirrors an Istio sidecar span (istio tags, status,
    # url; make_raw_window at module level, shared with
    # tools/profile_parse.py so parse profiles stay comparable to the
    # headline); bytes/span is reported alongside.
    from kmamiz_tpu import native as native_mod
    from kmamiz_tpu.core.spans import raw_spans_to_batch

    if not native_mod.available():
        raise RuntimeError(
            "bench.py: the native span loader is not loaded; the ingest "
            "sections would measure the Python fallback"
        )

    E2E_TRACES = 150_000  # x7 spans = 1.05M spans per window
    # BASELINE workload shape (VERDICT r4 #3): the full 1k-service /
    # 10k-endpoint MicroViSim-scale mesh with >=100k distinct edges, so
    # interning, shape tables, and the union sort carry production
    # cardinality (the legacy 200-svc/50-url shape rides along as a
    # continuity extra below)
    URLS_PER_SVC = N_ENDPOINTS // N_SERVICES
    raw_window = make_raw_window(
        E2E_TRACES,
        SPANS_PER_TRACE,
        n_services=N_SERVICES,
        urls_per_service=URLS_PER_SVC,
    )
    e2e_n_spans = E2E_TRACES * SPANS_PER_TRACE
    e2e_bytes_per_span = len(raw_window) / e2e_n_spans

    # segment counts are a jit-static shape: learn them from one probe parse
    # (fresh interner per rep -> identical counts every rep)
    _probe = raw_spans_to_batch(raw_window)
    E2E_NUM_ENDPOINTS = _probe[0].num_endpoints
    E2E_NUM_STATUSES = _probe[0].num_statuses
    del _probe

    @jax.jit
    def e2e_device(eid, sid, scl, lat, ts, val, pslot2, kind2, valid2, ep2):
        stats = window.window_stats(
            eid,
            sid,
            scl,
            lat,
            ts,
            val,
            num_endpoints=E2E_NUM_ENDPOINTS,
            num_statuses=E2E_NUM_STATUSES,
        )
        edges = window.dependency_edges_packed(
            pslot2, kind2, valid2, ep2, max_depth=8
        )
        return digest(tuple(stats)) + digest(tuple(edges))

    def raw_e2e_once():
        """One full ingest, phase-timed: (parse_s, pack_s, transfer_s,
        device_s)."""
        t0 = time.perf_counter()
        batch, _kept = raw_spans_to_batch(raw_window)
        t1 = time.perf_counter()
        packed = pack_trace_rows(
            batch.trace_of, batch.n_spans, batch.parent_idx
        )
        pslot = packed.parent_slots(batch.parent_idx)
        host_arrays = [
            batch.endpoint_id,
            batch.status_id,
            batch.status_class,
            batch.latency_ms.astype(np.float32),
            batch.timestamp_rel,
            batch.valid,
            packed.pack(pslot, -1),
            packed.pack(batch.kind[: batch.n_spans], 0),
            packed.pack(np.ones(batch.n_spans, bool), False),
            packed.pack(batch.endpoint_id[: batch.n_spans], 0),
        ]
        t2 = time.perf_counter()
        dev_arrays = jax.block_until_ready(
            [jax.device_put(a) for a in host_arrays]
        )
        t3 = time.perf_counter()
        float(e2e_device(*dev_arrays))  # compute + scalar fetch
        t4 = time.perf_counter()
        return (t1 - t0, t2 - t1, t3 - t2, t4 - t3)

    raw_e2e_once()  # warms the compile
    # 5 reps, BEST rep's phases (min total wall); the full rep list is
    # reported so the spread is visible
    e2e_reps = [raw_e2e_once() for _ in range(5)]
    e2e_wall_reps_ms = [round(sum(r) * 1000, 1) for r in e2e_reps]
    e2e_phases = min(e2e_reps, key=sum)

    # ---- native parse thread scaling ---------------------------------------
    # the parallel scan (prescan + worker ranges + atomic id table): walls
    # per thread count with the phase breakdown, on however many cores
    # this host has (e2e_host_cores). Best-of-2 per thread count.
    parse_scaling = {}
    for T in (1, 2, 4):
        best = None
        for _ in range(2):
            t0 = time.perf_counter()
            out = native_mod.parse_spans(raw_window, threads=T)
            wall = time.perf_counter() - t0
            if best is None or wall < best[0]:
                best = (wall, out["timings"])
        wall, tm = best
        parse_scaling[f"t{T}"] = {
            "wall_ms": round(wall * 1000, 1),
            "prescan_ms": round(tm["prescan_us"] / 1000, 1),
            "parse_busy_max_ms": round(tm["parse_us"] / 1000, 1),
            "merge_ms": round(tm["merge_us"] / 1000, 1),
        }

    # ---- columnar wire format (KMZC) on the identical window ---------------
    # production pays the encode on the filter side (envoy/filter/main.go,
    # amortized across sidecars); the server-side cost is ONLY the decode,
    # so the frame is built once uncounted and the native decoder is timed
    # against the JSON scan of the same spans (docs/INGEST_WIRE.md).
    # Best-of-3.
    from kmamiz_tpu.core import wire as wire_mod

    kmzc_frame = wire_mod.encode_groups(json.loads(raw_window))
    col_best = None
    for _ in range(3):
        t0 = time.perf_counter()
        native_mod.parse_spans(kmzc_frame)
        wall = time.perf_counter() - t0
        if col_best is None or wall < col_best:
            col_best = wall
    json_parse_s, pack_s, transfer_s, device_s = e2e_phases
    wire_extras = {
        "e2e_wire_json_bytes": len(raw_window),
        "e2e_wire_columnar_bytes": len(kmzc_frame),
        "e2e_wire_bytes_ratio": round(len(raw_window) / len(kmzc_frame), 2),
        "e2e_columnar_parse_ms": round(col_best * 1000, 1),
        "e2e_columnar_parse_speedup_vs_json": round(
            json_parse_s / col_best, 2
        ),
        # serial-path rate with the columnar decode substituted for the
        # JSON scan (pack, transfer and device phases unchanged)
        "e2e_columnar_serial_spans_per_sec": round(
            e2e_n_spans / (col_best + pack_s + transfer_s + device_s), 0
        ),
    }
    del kmzc_frame

    # ---- THE HEADLINE: deployed pipelined streaming ingest -----------------
    # DataProcessor.ingest_raw_stream over paginated raw chunks — the
    # exact production route (POST /ingest, first-time-setup backfill):
    # native parse of chunk k+1 on the worker thread overlaps chunk k's
    # pack + transfer + device merge into the persistent endpoint graph.
    # Chunks model paginated Zipkin fetches; same total span population
    # as the serial e2e. Counted reps feed ONE persistent processor fresh
    # windows (distinct trace ids, identical naming shapes) — the
    # steady-state production mix; the cold first window (boot interning
    # + compile walls) and the r4-style fresh-processor legacy shape are
    # reported alongside. The rate is spans over the measured wall.
    from kmamiz_tpu.server.processor import (
        DEFAULT_STREAM_CHUNKS,
        DataProcessor,
    )

    N_CHUNKS = DEFAULT_STREAM_CHUNKS
    chunk_traces = E2E_TRACES // N_CHUNKS

    def make_stream_chunks(prefix: str, baseline: bool = True):
        kw = (
            dict(n_services=N_SERVICES, urls_per_service=URLS_PER_SVC)
            if baseline
            else {}
        )
        return [
            make_raw_window(
                chunk_traces,
                SPANS_PER_TRACE,
                t_start=i * chunk_traces,
                trace_prefix=prefix,
                **kw,
            )
            for i in range(N_CHUNKS)
        ]

    def stream_once(dp, chunks):
        t0 = time.perf_counter()
        summary = dp.ingest_raw_stream(iter(chunks))
        return time.perf_counter() - t0, summary

    # STEADY-STATE methodology: production serves windows from a
    # PERSISTENT processor — XLA programs compiled, naming shapes
    # interned at boot, every window deduping as new traces. Each rep
    # feeds the same processor a fresh window with distinct trace ids
    # but identical naming shapes (trace_prefix), exactly the
    # steady-state mix; the cold first window (boot interning + compile
    # walls included) is reported alongside, as is the r4-style
    # legacy-shape fresh-processor run for continuity.
    # virtual clock: advancing past the 5-min dedup TTL between reps
    # keeps the processed-trace map at its production steady size
    # (~one window of ids) instead of accumulating every rep's ids —
    # the skip-set cost each parse pays stays the steady-state one
    bench_clock = {"ms": 1_700_000_000_000.0}
    dp_stream = DataProcessor(
        trace_source=lambda lb, t, lim: [],
        now_ms=lambda: bench_clock["ms"],
    )
    cold_wall_s, _cold_summary = stream_once(
        dp_stream, make_stream_chunks("c")
    )
    stream_cold_extras = {
        "e2e_stream_cold_wall_ms": round(cold_wall_s * 1000, 1),
    }
    # one uncounted steady rep absorbs the steady-shape union compile:
    # the cold window's drain unions run at the initial store
    # capacities, steady windows at the grown one — a different program
    # that would otherwise bill its compile wall to the first counted rep
    bench_clock["ms"] += 301_000  # TTL-prune the cold window's ids
    stream_once(dp_stream, make_stream_chunks("s"))
    # 6 counted reps, best wall (full rep list reported)
    stream_walls_ms = []
    stream_best = None
    for k in range(6):
        bench_clock["ms"] += 301_000
        chunks = make_stream_chunks(f"r{k}x")
        wall_s, summary = stream_once(dp_stream, chunks)
        del chunks
        stream_walls_ms.append(round(wall_s * 1000, 1))
        if stream_best is None or wall_s < stream_best[0]:
            stream_best = (wall_s, summary)

    # double-buffered upload pipeline counters over the whole steady
    # run: blocked_ms is the wall the host ACTUALLY spent waiting on
    # transfers
    up = dp_stream.graph.upload_stats()
    stream_upload_extras = {
        "e2e_upload_depth": up["depth"],
        "e2e_upload_count": up["uploads"],
        "e2e_upload_peak_in_flight": up["peak_in_flight"],
        "e2e_upload_blocked_ms": round(up["blocked_ms"], 1),
    }

    # legacy-shape continuity (the r3/r4 headline methodology: fresh
    # processor + graph every rep, 200-svc/50-url window)
    legacy_chunks = make_stream_chunks("w", baseline=False)

    def legacy_once():
        dp = DataProcessor(trace_source=lambda lb, t, lim: [])
        return stream_once(dp, legacy_chunks)

    legacy_once()  # warm legacy-shape programs
    legacy_runs = [legacy_once() for _ in range(3)]
    legacy_wall_s, lsummary = min(legacy_runs, key=lambda r: r[0])
    stream_legacy_extras = {
        "e2e_stream_legacy_spans_per_sec": round(
            lsummary["spans"] / legacy_wall_s, 0
        ),
        "e2e_stream_legacy_wall_reps_ms": [
            round(w * 1000, 1) for w, _ in legacy_runs
        ],
        "e2e_stream_legacy_endpoints": lsummary["endpoints"],
        "e2e_stream_legacy_edges": lsummary["edges"],
    }
    del legacy_chunks

    # ---- graftstream micro-tick freshness (ISSUE 16) -----------------------
    # The overlapped micro-tick engine (server/stream.py) vs the serial
    # collect tick over the scenario factory's burst + diurnal traffic
    # curves: per-curve span-arrival -> forecast-visible p99 from the
    # telemetry freshness plane (worst curve is the gated headline,
    # absolute ceiling 250 ms in tools/slo_report.py), the stream/serial
    # wall ratio, and the steady-state recompile count after a serial
    # warm epoch (must be zero).
    import random as _stream_rand

    from kmamiz_tpu.core import programs as _programs
    from kmamiz_tpu.scenarios.traffic import sample_traffic
    from kmamiz_tpu.server.stream import StreamEngine
    from kmamiz_tpu.telemetry import freshness as tel_freshness

    STREAM_TICKS = 24
    STREAM_SPANS_PER_TRACE = 5

    _stream_feed: list = []

    def _stream_src(lb, t, lim):
        # only the engine's single producer thread pops, in order
        return _stream_feed.pop(0) if _stream_feed else []

    dp_tick = DataProcessor(
        trace_source=_stream_src, use_device_stats=False
    )

    def _tick_windows(curve, prefix):
        return [
            json.loads(
                make_raw_window(
                    int(n),
                    STREAM_SPANS_PER_TRACE,
                    t_start=i * 1_000,
                    trace_prefix=f"{prefix}{i}",
                )
            )
            for i, n in enumerate(curve)
        ]

    def _tick_requests(prefix, count, t_base):
        return [
            {
                "uniqueId": f"{prefix}{i}",
                "lookBack": 30_000,
                "time": t_base + i,
            }
            for i in range(count)
        ]

    def _run_serial(windows, prefix):
        _stream_feed.extend(windows)
        reqs = _tick_requests(prefix, len(windows), 1_000_000)
        t0 = time.perf_counter()
        for req in reqs:
            dp_tick.collect(req)
        return time.perf_counter() - t0

    def _run_stream(windows, prefix):
        _stream_feed.extend(windows)
        reqs = _tick_requests(prefix, len(windows), 2_000_000)
        eng = StreamEngine(dp_tick)
        t0 = time.perf_counter()
        eng.run_stream(reqs)
        return time.perf_counter() - t0

    stream_curves = {
        "burst": sample_traffic(
            "burst", STREAM_TICKS, _stream_rand.Random(7)
        ),
        "diurnal": sample_traffic(
            "diurnal", STREAM_TICKS, _stream_rand.Random(11)
        ),
    }
    # warm epoch: every window shape of both curves through the
    # serial parity path, so the measured runs below are steady
    # state for BOTH engines (same programs, same bucket shapes)
    for cname, curve in stream_curves.items():
        _run_serial(_tick_windows(curve, f"mtw-{cname}-"), f"mtw-{cname}-")
    stream_prog_snap = _programs.snapshot()

    stream_fresh_p99 = {}
    stream_speedup = {}
    for cname, curve in stream_curves.items():
        serial_s = _run_serial(
            _tick_windows(curve, f"mts-{cname}-"), f"mts-{cname}-"
        )
        tel_freshness.reset_for_tests()
        stream_s = _run_stream(
            _tick_windows(curve, f"mtp-{cname}-"), f"mtp-{cname}-"
        )
        fr = tel_freshness.snapshot()
        stream_fresh_p99[cname] = fr["freshness_ms_p99"]
        stream_speedup[cname] = serial_s / max(stream_s, 1e-9)
    stream_new_compiles = {
        k: v
        for k, v in _programs.new_compiles_since(stream_prog_snap).items()
        if v
    }
    stream_tick_extras = {
        # worst curve is the gate: the SLO holds under both shapes
        "stream_freshness_ms_p99": round(
            max(stream_fresh_p99.values()), 2
        ),
        "stream_freshness_by_curve_ms_p99": {
            k: round(v, 2) for k, v in stream_fresh_p99.items()
        },
        "stream_vs_batch_speedup": round(
            min(stream_speedup.values()), 3
        ),
        "stream_steady_recompiles": sum(stream_new_compiles.values()),
        "stream_zero_recompiles_pass": not stream_new_compiles,
        "stream_ticks_per_curve": STREAM_TICKS,
    }
    del dp_tick

    # ---- graph metric refresh @10k endpoints -------------------------------
    ep_service = jnp.asarray(
        rng.integers(0, N_SERVICES, N_ENDPOINTS, dtype=np.int32)
    )
    ep_ml = jnp.asarray(rng.integers(0, 4096, N_ENDPOINTS, dtype=np.int32))
    ep_record = jnp.ones(N_ENDPOINTS, dtype=bool)
    src = jnp.asarray(rng.integers(0, N_ENDPOINTS, GRAPH_EDGES, dtype=np.int32))
    dst = jnp.asarray(rng.integers(0, N_ENDPOINTS, GRAPH_EDGES, dtype=np.int32))
    dist = jnp.asarray(rng.integers(1, 8, GRAPH_EDGES, dtype=np.int32))
    emask = jnp.ones(GRAPH_EDGES, dtype=bool)
    req_count = jnp.asarray(rng.gamma(2.0, 100.0, N_SERVICES).astype(np.float32))
    err_count = req_count * 0.01
    cv_w = req_count * 0.5
    replicas = jnp.ones(N_SERVICES, dtype=jnp.float32)
    active = jnp.ones(N_SERVICES, dtype=bool)

    def refresh_once():
        s = scorers.service_scores(
            src,
            dst,
            dist,
            emask,
            ep_service,
            ep_ml,
            ep_record,
            num_services=N_SERVICES,
        )
        coh = scorers.usage_cohesion(
            src,
            dst,
            dist,
            emask,
            ep_service,
            ep_record,
            num_services=N_SERVICES,
        )
        risk = scorers.risk_scores(
            s.relying_factor,
            s.acs,
            replicas,
            req_count,
            err_count,
            cv_w,
            active,
        )
        jax.block_until_ready((s, coh, risk))

    # latency metric: median (a p50 claim is about the typical run)
    refresh_ms = _timed_median(refresh_once, reps=7) * 1000

    # ---- scorers AT THE HTTP SURFACE (VERDICT r1 #2) -----------------------
    # real ApiServer + GraphHandler served from a 10k-endpoint device graph:
    # what an API consumer actually waits for on GET /graph/instability
    import urllib.request as _urlreq

    from kmamiz_tpu.api.app import build_router
    from kmamiz_tpu.api.router import ApiServer
    from kmamiz_tpu.config import Settings
    from kmamiz_tpu.core.interning import EndpointInterner
    from kmamiz_tpu.graph.store import EndpointGraph
    from kmamiz_tpu.ops.sortutil import SENTINEL
    from kmamiz_tpu.server.initializer import AppContext, Initializer
    from kmamiz_tpu.server.processor import DataProcessor
    from kmamiz_tpu.server.storage import MemoryStore

    interner = EndpointInterner()
    for e in range(N_ENDPOINTS):
        svc = e % N_SERVICES
        interner.intern_endpoint(
            f"svc{svc}\tns{svc % 8}\tv1\tGET\thttp://svc{svc}/api/ep{e}",
            {"uniqueEndpointName": f"ep{e}", "timestamp": 0},
        )
    big_graph = EndpointGraph(interner=interner, capacity=_pow2(GRAPH_EDGES))
    ecap = big_graph.capacity
    e_src = np.full(ecap, SENTINEL, dtype=np.int32)
    e_dst = np.full(ecap, SENTINEL, dtype=np.int32)
    e_dist = np.full(ecap, SENTINEL, dtype=np.int32)
    e_src[:GRAPH_EDGES] = rng.integers(0, N_ENDPOINTS, GRAPH_EDGES)
    e_dst[:GRAPH_EDGES] = rng.integers(0, N_ENDPOINTS, GRAPH_EDGES)
    e_dist[:GRAPH_EDGES] = rng.integers(1, 8, GRAPH_EDGES)
    big_graph._src = jnp.asarray(e_src)
    big_graph._dst = jnp.asarray(e_dst)
    big_graph._dist = jnp.asarray(e_dist)
    big_graph._n_edges = GRAPH_EDGES
    big_graph._ensure_ep_arrays(N_ENDPOINTS)
    big_graph._ep_record[:] = True

    api_settings = Settings()
    api_settings.external_data_processor = ""
    dp = DataProcessor(trace_source=lambda lb, t, lim: [])
    dp.graph = big_graph
    ctx = AppContext.build(
        app_settings=api_settings, store=MemoryStore(), processor=dp
    )
    Initializer(ctx).register_data_caches()
    api = ApiServer(build_router(ctx), host="127.0.0.1", port=0)
    api.start()
    try:
        url = f"http://127.0.0.1:{api.port}/api/v1/graph/instability"

        def http_get():
            with _urlreq.urlopen(url) as r:
                assert r.status == 200
                r.read()

        http_api_refresh_ms = _timed_median(http_get, reps=5) * 1000
    finally:
        api.stop()

    # ---- graph-store scaling: 100k endpoints / ~5M edges -------------------
    # characterizes the capacity-doubling policy past the 10k-endpoint
    # operating point (VERDICT r3 #6): per-union merge wall through the
    # doublings, distinct compiled union programs, and the scorer
    # refresh at the final scale. Edge batches are generated ON DEVICE
    # (no reason to pay a host copy for random test data); the union runs
    # the store's real merge kernel + capacity policy via merge_edges.
    # Only ~3 union programs compile across the whole growth (capacities
    # double); the refresh measures the BASELINE-worded "risk+instability
    # refresh" on the 4M-capacity snapshot (the 8M-wide final arrays take
    # about twice as long to compile for the same per-edge answer), and a
    # time-budget guard skips the whole section rather than risk starving
    # the headline artifact.
    scale_extras = {}
    bench_elapsed_s = time.perf_counter() - BENCH_T0
    try:
        bench_budget_s = int(os.environ.get("KMAMIZ_BENCH_BUDGET_S", 3000))
    except ValueError:
        bench_budget_s = 3000
    run_scale = (
        os.environ.get("KMAMIZ_BENCH_SCALE100K", "1") != "0"
        and bench_elapsed_s < bench_budget_s - 600
    )
    if not run_scale:
        scale_extras["graph_scale_skipped"] = (
            "disabled" if os.environ.get("KMAMIZ_BENCH_SCALE100K") == "0"
            else f"time budget ({bench_elapsed_s:.0f}s elapsed)"
        )
    else:
        from kmamiz_tpu.graph.store import EndpointGraph, _merge_edges

        N_EP_BIG = 100_000
        N_SVC_BIG = 10_000
        STEP = 1 << 20  # ~1M candidate edges per union, fixed shape
        STEPS = 5  # ~5.2M distinct edges by the end

        big = EndpointGraph(capacity=1 << 20)
        key = jax.random.PRNGKey(7)

        merge_walls = []
        caps = []
        refresh_snapshot = None
        for step in range(STEPS):
            key, k1, k2, k3 = jax.random.split(key, 4)
            src_b = jax.random.randint(k1, (STEP,), 0, N_EP_BIG, jnp.int32)
            dst_b = jax.random.randint(k2, (STEP,), 0, N_EP_BIG, jnp.int32)
            dist_b = jax.random.randint(k3, (STEP,), 1, 8, jnp.int32)
            jax.block_until_ready([src_b, dst_b, dist_b])
            t0 = time.perf_counter()
            big.merge_edges(src_b, dst_b, dist_b)
            n_after = big.n_edges  # drains the deferred count
            merge_walls.append(round((time.perf_counter() - t0) * 1000, 1))
            caps.append(int(big.capacity))
            if refresh_snapshot is None and int(big.capacity) >= (1 << 22):
                # scorer-refresh point: the 4M-capacity store (the 8M-wide
                # final arrays compile ~2x longer for the same per-edge
                # answer; millions of real edges at 100k endpoints)
                refresh_snapshot = (big.edge_arrays(), n_after)
        scale_extras = {
            "graph_scale_endpoints": N_EP_BIG,
            "graph_scale_edges_final": int(big.n_edges),
            "graph_scale_capacities": caps,
            "graph_scale_merge_walls_ms": merge_walls,
            # distinct compiled union programs across the WHOLE bench run
            # (10k section + this growth curve): the capacity policy's
            # compile bill
            "graph_scale_union_programs": int(_merge_edges._cache_size()),
        }

        # risk+instability refresh at the 100k-endpoint scale (the
        # BASELINE target's wording; timed like the 10k metric, which
        # also folds in cohesion)
        (src_f, dst_f, dist_f, mask_f), snap_edges = refresh_snapshot
        # the store's tracked dist bounds -> the sparse scorer's static
        # promise (3 here: merged dists are 1..7); None keeps the
        # legacy lexsort path, so the metric reflects whichever
        # backend KMAMIZ_SPARSE selects
        dist_bits_big = big._scorer_dist_bits()
        ep_service_b = jnp.asarray(
            rng.integers(0, N_SVC_BIG, N_EP_BIG, dtype=np.int32)
        )
        ep_ml_b = jnp.asarray(rng.integers(0, 65536, N_EP_BIG, dtype=np.int32))
        ep_record_b = jnp.ones(N_EP_BIG, dtype=bool)
        replicas_b = jnp.ones(N_SVC_BIG, dtype=jnp.float32)
        req_b = jnp.asarray(
            rng.gamma(2.0, 100.0, N_SVC_BIG).astype(np.float32)
        )
        def refresh_big_once():
            s = scorers.service_scores(
                src_f,
                dst_f,
                dist_f,
                mask_f,
                ep_service_b,
                ep_ml_b,
                ep_record_b,
                num_services=N_SVC_BIG,
                dist_bits=dist_bits_big,
            )
            risk = scorers.risk_scores(
                s.relying_factor,
                s.acs,
                replicas_b,
                req_b,
                req_b * 0.01,
                req_b * 0.5,
                jnp.ones(N_SVC_BIG, dtype=bool),
            )
            jax.block_until_ready((s, risk))

        scale_extras["graph_refresh_ms_100k"] = round(
            _timed_median(refresh_big_once, reps=3) * 1000, 2
        )
        scale_extras["graph_refresh_100k_edges"] = int(snap_edges)
        del big, src_f, dst_f, dist_f, mask_f

    # ---- capacity growth: repack vs segment-append A/B ---------------------
    # one capacity doubling on a small warm store under each growth mode
    # (KMAMIZ_STORE_GROW). The repack crossing recompiles graph.fit_edges
    # at the doubled width; the segment crossing re-splits into the
    # always-present overflow tail with zero new programs. The wall-clock
    # gap IS the compile bill the segment policy removes from the hot
    # path (2k-wide arrays here; it grows with the array width).
    grow_extras = {
        "graph_capacity_grow_ms": None,
        "graph_capacity_grow_repack_ms": None,
    }
    GROW_ROWS, GROW_BATCHES = 300, 4  # 3 warm merges, 4th crosses 1024

    def _grow_batches():
        # globally-distinct (src, dst) pairs so dedup never collapses
        # the count: 1200 edges after batch 4 > cap 1024, within the
        # 256-row tail (no consolidation; repack doubles to 2048)
        for i in range(GROW_BATCHES):
            k = np.arange(i * GROW_ROWS, (i + 1) * GROW_ROWS)
            yield (
                (k % 797).astype(np.int32),
                (k // 797).astype(np.int32),
                np.full(GROW_ROWS, 1 + i % 7, dtype=np.int32),
            )

    for mode, grow_key in (
        ("repack", "graph_capacity_grow_repack_ms"),
        ("segment", "graph_capacity_grow_ms"),
    ):
        gg = EndpointGraph(capacity=1024, grow=mode)
        *warm, crossing = list(_grow_batches())
        for s_b, d_b, ds_b in warm:
            gg.merge_edges(s_b, d_b, ds_b)
            gg.n_edges  # drain the deferred count
        t0 = time.perf_counter()
        gg.merge_edges(*crossing)
        gg.n_edges
        grow_extras[grow_key] = round((time.perf_counter() - t0) * 1000, 2)
        del gg
    if grow_extras["graph_capacity_grow_ms"]:
        grow_extras["graph_capacity_grow_speedup"] = round(
            grow_extras["graph_capacity_grow_repack_ms"]
            / grow_extras["graph_capacity_grow_ms"],
            1,
        )

    # ---- end-to-end DP tick at the reference's own scale -------------------
    # the reference caps realtime ticks at 2,500 traces / 5 s; this times the
    # FULL DataProcessor.collect (host parse + device kernels + response
    # assembly) on a 2,500-trace window, the product-level SLA
    from kmamiz_tpu.server.processor import DataProcessor

    base_spans = [
        {
            "traceId": "t0",
            "id": f"s{j}",
            "parentId": f"s{j-1}" if j else None,
            "kind": "SERVER" if j % 2 == 0 else "CLIENT",
            "name": f"svc{j % 5}.ns.svc.cluster.local:80/*",
            "timestamp": 1_700_000_000_000_000 + j,
            "duration": 1000 + j,
            "tags": {
                "http.method": "GET",
                "http.status_code": "200",
                "http.url": f"http://svc{j % 5}.ns.svc.cluster.local/api/{j % 7}",
                "istio.canonical_revision": "v1",
                "istio.canonical_service": f"svc{j % 5}",
                "istio.mesh_id": "cluster.local",
                "istio.namespace": "ns",
            },
        }
        for j in range(7)
    ]

    def tick_traces(tick_id):
        groups = []
        for t in range(2500):
            g = []
            for s in base_spans:
                c = dict(s)
                c["id"] = f"{tick_id}-{t}-{s['id']}"
                c["traceId"] = f"{tick_id}-t{t}"
                if c["parentId"]:
                    c["parentId"] = f"{tick_id}-{t}-{c['parentId']}"
                if t % 17 == 0 and s["kind"] == "SERVER":
                    c = {**c, "tags": {**c["tags"], "http.status_code": "503"}}
                g.append(c)
            groups.append(g)
        return groups

    # pre-generate every rep's window OUTSIDE the timed region: the metric
    # charges only DataProcessor.collect, not test-data synthesis. Four
    # timed legs below (cold, cached, telemetry-off, prof-off) each burn
    # 1 warmup + 5 reps = 24 windows.
    prebuilt = [tick_traces(i) for i in range(24)]

    def source(_lb, _t, _lim):
        return prebuilt.pop(0)

    dp = DataProcessor(trace_source=source, use_device_stats=True)
    rep_counter = {"n": 0}

    def one_tick():
        rep_counter["n"] += 1
        dp.collect(
            {"uniqueId": f"b{rep_counter['n']}", "lookBack": 30_000, "time": rep_counter["n"]}
        )

    # latency metric vs the reference's 5 s tick budget: median
    dp_tick_ms = _timed_median(one_tick, reps=5) * 1000  # first call warms

    # steady-state tick: same workload shape, but every warmable layer is
    # hot — endpoint-info/record templates, XLA executables, the graph's
    # device-resident scorer tables — i.e. production cadence after boot
    dp_tick_cached_ms = _timed_median(one_tick, reps=5) * 1000

    # telemetry overhead: the same warm tick with span tracing gated off
    # (KMAMIZ_TELEMETRY=0). The acceptance bound is tracing-on within 5%
    # of this number; both medians ride identical prebuilt windows
    _tel_prev = os.environ.get("KMAMIZ_TELEMETRY")
    os.environ["KMAMIZ_TELEMETRY"] = "0"
    try:
        dp_tick_telemetry_off_ms = _timed_median(one_tick, reps=5) * 1000
    finally:
        if _tel_prev is None:
            os.environ.pop("KMAMIZ_TELEMETRY", None)
        else:
            os.environ["KMAMIZ_TELEMETRY"] = _tel_prev

    # graftprof overhead proof: the same warm tick with the profiler
    # event ring gated off (KMAMIZ_PROF=0, tracing still ON). Acceptance:
    # the prof-on steady tick (dp_tick_cached_ms) within 3% of this.
    _prof_prev = os.environ.get("KMAMIZ_PROF")
    os.environ["KMAMIZ_PROF"] = "0"
    try:
        dp_tick_prof_off_ms = _timed_median(one_tick, reps=5) * 1000
    finally:
        if _prof_prev is None:
            os.environ.pop("KMAMIZ_PROF", None)
        else:
            os.environ["KMAMIZ_PROF"] = _prof_prev

    # per-phase attribution keys from the graftprof host event ring,
    # ALWAYS present (0.0 when a phase recorded nothing, so slo_report
    # can gate them across rounds without key-existence special cases).
    # One small native raw-ingest under a traced tick first, so the
    # native merge/lock-wait delta events have a sample at the deployed
    # parse-thread setting.
    from kmamiz_tpu.telemetry.profiling import events as prof_ring
    from kmamiz_tpu.telemetry.tracing import TRACER as _PROF_TRACER

    with _PROF_TRACER.tick(root_name="dp-ingest"):
        dp.ingest_raw_window(
            make_raw_window(200, 10, t_start=990_000, trace_prefix="prof-")
        )
    prof_phase_keys = {
        "prof_parse_ms_p95": prof_ring.phase_p95_ms("parse"),
        "prof_merge_lockwait_ms_p95": prof_ring.phase_p95_ms(
            "native-merge-lockwait"
        ),
        "prof_transfer_ms_p95": prof_ring.phase_p95_ms("host-transfer"),
        "prof_device_walk_ms_p95": prof_ring.phase_p95_ms("walk"),
        # sparse-walk attribution rides its own phase name (the processor
        # switches the walk span to "walk_sparse" under KMAMIZ_SPARSE) so
        # graftprof --diff can compare walk backends; 0.0 when the dense
        # walk served this run
        "prof_device_walk_sparse_ms_p95": prof_ring.phase_p95_ms(
            "walk_sparse"
        ),
        # graftstream freshness plane: arrival->visible watermark events
        # emitted by finish_tick (serial and stream paths both stamp);
        # p99 because the SLO is a tail bound, not a typical-case one
        "prof_freshness_ms_p99": prof_ring.phase_percentile_ms(
            "freshness", 0.99
        ),
    }

    # scorer read path between merges: the first read after a merge
    # computes (full or dirty-incremental), every repeated HTTP read is an
    # O(1) memo hit on (cache key, graph version)
    scorer_now_ms = float(dp._now_ms())
    dp.graph.service_scores(now_ms=scorer_now_ms)  # compute + fill memo
    scorer_cached_read_ms = (
        _timed_median(
            lambda: dp.graph.service_scores(now_ms=scorer_now_ms), reps=5
        )
        * 1000
    )
    scorer_stats = dp.graph.scorer_cache_stats()

    # ---- GraphSAGE training/serving (models/stacked.py + serving.py) -------
    # scan-fused epoch (ONE jitted lax.scan over device-resident stacked
    # slots) vs the legacy per-slot host loop it replaced, at the BASELINE
    # graph shape (1k svc / 10k endpoints / 50k edges, 24 hourly slots,
    # hidden=32), plus the served jitted forecast forward. Budget-guarded.
    sage_extras = {}
    try:
        sage_budget_ok = (
            time.perf_counter() - BENCH_T0
            < int(os.environ.get("KMAMIZ_BENCH_BUDGET_S", 3000)) - 1500
        )
    except ValueError:
        sage_budget_ok = True
    if sage_budget_ok:
        from kmamiz_tpu.models import graphsage as sage_model
        from kmamiz_tpu.models import serving as sage_serving
        from kmamiz_tpu.models import stacked as sage_stacked
        from kmamiz_tpu.models import trainer as sage_trainer

        SAGE_S, SAGE_H, SAGE_EP = 24, 32, 8
        sage_rng = np.random.default_rng(11)
        sage_ds = sage_trainer.GraphDataset(
            endpoint_names=[f"ep{i}" for i in range(N_ENDPOINTS)],
            src=jnp.asarray(
                sage_rng.integers(
                    0, N_ENDPOINTS, GRAPH_EDGES, dtype=np.int32
                )
            ),
            dst=jnp.asarray(
                sage_rng.integers(
                    0, N_ENDPOINTS, GRAPH_EDGES, dtype=np.int32
                )
            ),
            edge_mask=jnp.ones(GRAPH_EDGES, dtype=bool),
            features=[
                jnp.asarray(
                    sage_rng.normal(
                        size=(N_ENDPOINTS, sage_model.NUM_FEATURES)
                    ).astype(np.float32)
                )
                for _ in range(SAGE_S)
            ],
            target_latency=[
                jnp.asarray(
                    sage_rng.normal(size=N_ENDPOINTS).astype(np.float32)
                )
                for _ in range(SAGE_S)
            ],
            target_anomaly=[
                jnp.asarray(
                    (sage_rng.random(N_ENDPOINTS) < 0.1).astype(
                        np.float32
                    )
                )
                for _ in range(SAGE_S)
            ],
            node_mask=[
                jnp.asarray(sage_rng.random(N_ENDPOINTS) < 0.95)
                for _ in range(SAGE_S)
            ],
            slot_keys=[f"s{i}" for i in range(SAGE_S)],
        )
        sage_pw = 4.0
        sage_lr = 1e-2
        sage_opt = sage_model.make_optimizer(sage_lr)

        def sage_init():
            # both trainers DONATE params/opt_state: each consumer
            # gets its own buffers (sharing one init between the
            # legacy loop and the fused block hands the second a
            # deleted array)
            p0 = sage_model.init_params(
                jax.random.PRNGKey(3), hidden=SAGE_H
            )
            return {"p": p0, "s": sage_opt.init(p0)}

        # legacy per-slot host loop: one jitted step dispatch + host
        # loss fetch per slot per epoch — exactly trainer.train's
        # pre-fusion control flow
        sage_step = sage_model.make_train_step(sage_opt, pos_weight=sage_pw)
        lstate = sage_init()

        def sage_legacy_epoch():
            p, s = lstate["p"], lstate["s"]
            for i in range(SAGE_S):
                p, s, loss, _aux = sage_step(
                    p,
                    s,
                    sage_ds.features[i],
                    sage_ds.src,
                    sage_ds.dst,
                    sage_ds.edge_mask,
                    sage_ds.target_latency[i],
                    sage_ds.target_anomaly[i],
                    sage_ds.node_mask[i],
                )
                float(loss)
            lstate["p"], lstate["s"] = p, s

        sage_legacy_epoch_ms = _timed(sage_legacy_epoch, reps=2) * 1000

        # scan-fused: whole SAGE_EP-epoch block as ONE program over the
        # stacked device-resident dataset; params/opt state donated and
        # threaded across calls
        sage_st = sage_stacked.stack_dataset(sage_ds)
        sage_runner = sage_stacked.epoch_runner(sage_model, sage_lr, sage_pw)
        fstate = sage_init()

        def sage_fused_block():
            p, s, block = sage_runner(
                fstate["p"],
                fstate["s"],
                sage_st.features,
                sage_st.target_latency,
                sage_st.target_anomaly,
                sage_st.node_mask,
                sage_st.src,
                sage_st.dst,
                sage_st.edge_mask,
                SAGE_EP,
            )
            jax.block_until_ready(block)
            fstate["p"], fstate["s"] = p, s

        sage_epoch_ms = _timed(sage_fused_block, reps=2) * 1000 / SAGE_EP

        # served inference: the jitted shape-stable forward behind
        # POST /model/forecast (bucket padding + upload + fetch charged)
        sage_feats_np = np.asarray(sage_ds.features[0])
        sage_src_np = np.asarray(sage_ds.src)
        sage_dst_np = np.asarray(sage_ds.dst)
        sage_mask_np = np.asarray(sage_ds.edge_mask)

        sage_infer_ms = (
            _timed_median(
                lambda: sage_serving.forecast_forward(
                    fstate["p"],
                    sage_feats_np,
                    sage_src_np,
                    sage_dst_np,
                    sage_mask_np,
                    sage_model,
                ),
                reps=5,
            )
            * 1000
        )
        sage_extras = {
            "sage_epoch_ms": round(sage_epoch_ms, 1),
            "sage_epoch_legacy_ms": round(sage_legacy_epoch_ms, 1),
            "sage_fused_speedup": round(
                sage_legacy_epoch_ms / max(sage_epoch_ms, 1e-9), 1
            ),
            "sage_train_slots_per_s": round(
                SAGE_S / max(sage_epoch_ms / 1000.0, 1e-9), 1
            ),
            "sage_infer_ms": round(sage_infer_ms, 2),
            "sage_shape": {
                "nodes": N_ENDPOINTS,
                "edges": GRAPH_EDGES,
                "slots": SAGE_S,
                "hidden": SAGE_H,
                "bucket_nodes": sage_st.bucket_nodes,
                "bucket_edges": sage_st.bucket_edges,
            },
        }
    # ---- STLGT continual quantile model (ISSUE 10) -------------------------
    # the linear graph transformer's two hot-path latencies — the per-fold
    # train tick (observe_fold: window -> ring example + scan-fused
    # epoch-block refresh) and the served quantile forward behind
    # GET /model/forecast?quantile= — plus its p99 coverage from a short
    # prequential replay over scenario-factory labeled windows (the
    # tools/eval_stlgt.py methodology, compressed). The three keys are
    # ALWAYS present (None on skip) so a regression can never
    # hide inside a missing key; KMAMIZ_BENCH_STLGT=0 skips. Gated by
    # tools/slo_report.py: the latency pair as higher-is-worse, the
    # coverage as a float floor.
    stlgt_extras = {
        "stlgt_train_tick_ms": None,
        "stlgt_infer_ms": None,
        "stlgt_p99_coverage": None,
    }
    try:
        stlgt_budget_ok = (
            time.perf_counter() - BENCH_T0
            < int(os.environ.get("KMAMIZ_BENCH_BUDGET_S", 3000)) - 1400
        )
    except ValueError:
        stlgt_budget_ok = True
    if os.environ.get("KMAMIZ_BENCH_STLGT", "1") != "0" and stlgt_budget_ok:
        from kmamiz_tpu.models.stlgt import serving as stlgt_serving
        from kmamiz_tpu.models.stlgt.trainer import ContinualTrainer
        from kmamiz_tpu.scenarios import build_scenario, labeled_windows

        STLGT_TICKS, STLGT_WARMUP = 24, 4
        stlgt_data = labeled_windows(
            build_scenario("cascade-fanout", 0, 0, STLGT_TICKS)
        )
        stlgt_windows = stlgt_data["windows"]
        stlgt_trainer = ContinualTrainer(
            depth=8, refresh_every=1, epochs=2, hidden=16, lr=0.02
        )
        fold_walls = []
        stlgt_cov = []
        for t, w in enumerate(stlgt_windows):
            snap = {
                "features": w["features"],
                "src": stlgt_data["src"],
                "dst": stlgt_data["dst"],
                "mask": stlgt_data["mask"],
                "names": stlgt_data["names"],
                "predicted_hour": (t + 1) % 24,
                "cache_key": (1, 0, t),
            }
            t0 = time.perf_counter()
            stlgt_trainer.observe_fold(snap)
            if t >= STLGT_WARMUP:
                # ring bucket + epoch-block program are warm by now:
                # these walls are the steady-state fold tick
                fold_walls.append(time.perf_counter() - t0)
            live = stlgt_trainer.serving()
            if (
                live is None
                or t < STLGT_WARMUP
                or t + 1 >= len(stlgt_windows)
            ):
                continue
            nxt = stlgt_windows[t + 1]
            act = w["active"] & nxt["active"]
            if not act.any():
                continue
            q_ms, _prob, _gate = stlgt_serving.quantile_forward(
                live["params"],
                w["features"],
                stlgt_data["src"],
                stlgt_data["dst"],
                stlgt_data["mask"],
                live["model"],
            )
            stlgt_cov.append(
                float(np.mean(nxt["latency_ms"][act] <= q_ms[act, 2]))
            )

        # served inference: the jitted shape-stable quantile forward
        # behind the route (bucket padding + upload + fetch charged)
        stlgt_live = stlgt_trainer.serving()
        stlgt_last = stlgt_windows[-1]
        stlgt_infer_ms = (
            _timed_median(
                lambda: stlgt_serving.quantile_forward(
                    stlgt_live["params"],
                    stlgt_last["features"],
                    stlgt_data["src"],
                    stlgt_data["dst"],
                    stlgt_data["mask"],
                    stlgt_live["model"],
                ),
                reps=5,
            )
            * 1000
        )
        stlgt_extras = {
            # fold tick and infer are latency metrics: median
            "stlgt_train_tick_ms": (
                round(float(np.median(fold_walls)) * 1000, 2)
                if fold_walls
                else None
            ),
            "stlgt_infer_ms": round(stlgt_infer_ms, 2),
            "stlgt_p99_coverage": (
                round(float(np.mean(stlgt_cov)), 4) if stlgt_cov else None
            ),
            "stlgt_scored_ticks": len(stlgt_cov),
            "stlgt_trainer": stlgt_trainer.status(),
        }
    # ---- tenancy: stacked multi-tenant serving (ISSUE 7) -------------------
    # 8 same-bucket tenants, two claims: (1) the device stage the router
    # batches — window union + service scorers — is one stacked dispatch
    # instead of 8 serialized ones; (2) a 9th tenant joining the warm
    # bucket compiles NOTHING (shape-keyed module-level programs). The
    # four keys are ALWAYS present (None on skip) so a regression
    # can never hide inside a missing key; KMAMIZ_BENCH_TENANCY=0 skips.
    tenancy_extras = {
        "tenant_batched_tick_ms_8": None,
        "tenant_serial_tick_ms_8": None,
        "tenant_batch_speedup": None,
        "tenant_join_compile_count": None,
    }
    try:
        tenancy_budget_ok = (
            time.perf_counter() - BENCH_T0
            < int(os.environ.get("KMAMIZ_BENCH_BUDGET_S", 3000)) - 400
        )
    except ValueError:
        tenancy_budget_ok = True
    if os.environ.get("KMAMIZ_BENCH_TENANCY", "1") != "0" and tenancy_budget_ok:
        from kmamiz_tpu.core import programs
        from kmamiz_tpu.graph.store import (
            _edge_mask,
            _fit_edges,
            _merge_edges,
        )
        from kmamiz_tpu.ops import scorers as scorer_ops
        from kmamiz_tpu.ops.sortutil import SENTINEL as _SENT
        from kmamiz_tpu.server.processor import DataProcessor as _DP
        from kmamiz_tpu.tenancy import (
            TenantRuntime,
            TickRouter,
            batched_merge_edges,
            batched_service_scores,
        )

        # small-bucket shapes: fixture-scale tenants (the pdas mesh is
        # 3 services / ~a dozen edges) live in the smallest arena
        # bucket, where per-tick dispatch + sync overhead dominates —
        # exactly the regime tenant batching amortizes
        N_T = 8
        T_CAP, T_WCAP, T_EPCAP, T_NSVC = 32, 16, 64, 8
        rng = np.random.default_rng(7)

        def edge_cols(n_valid, cap, salt):
            src = np.full(cap, _SENT, dtype=np.int32)
            dst = np.full(cap, _SENT, dtype=np.int32)
            dist = np.full(cap, _SENT, dtype=np.int32)
            src[:n_valid] = rng.integers(0, T_EPCAP, n_valid) ^ salt
            dst[:n_valid] = rng.integers(0, T_EPCAP, n_valid)
            dist[:n_valid] = rng.integers(1, 8, n_valid)
            src[:n_valid] %= T_EPCAP
            return src, dst, dist

        stores = [edge_cols(24, T_CAP, t) for t in range(N_T)]
        windows = [edge_cols(10, T_WCAP, t + 100) for t in range(N_T)]
        ep_service = (
            np.arange(T_EPCAP, dtype=np.int32) % T_NSVC
        )
        ep_ml = np.arange(T_EPCAP, dtype=np.int32)
        ep_rec = np.ones(T_EPCAP, dtype=bool)

        def dev(cols):
            return [jax.device_put(a) for a in cols]

        st = [dev(c) for c in stores]
        wi = [dev(c) for c in windows]
        ep_s, ep_m, ep_r = dev((ep_service, ep_ml, ep_rec))
        stack = lambda i: jnp.stack([t[i] for t in st])
        wstack = lambda i: jnp.stack([w[i] for w in wi])
        S, D, DS = stack(0), stack(1), stack(2)
        WS, WD, WDS = wstack(0), wstack(1), wstack(2)
        M, WM = S != _SENT, WS != _SENT
        ep_S = jnp.stack([ep_s] * N_T)
        ep_M = jnp.stack([ep_m] * N_T)
        ep_R = jnp.stack([ep_r] * N_T)

        def serial_round():
            # one full blocking tick per tenant, exactly like the
            # router's serial fallback: merge, fetch the valid count
            # (_apply_merged's capacity policy), re-fit to the bucket,
            # score, then pull every ServiceScores field to host for
            # response building — the NEXT tenant's tick cannot start
            # until this one's response is materialized
            for t in range(N_T):
                s, d, ds, v = _merge_edges(
                    st[t][0], st[t][1], st[t][2], _edge_mask(st[t][0]),
                    wi[t][0], wi[t][1], wi[t][2], _edge_mask(wi[t][0]),
                )
                int(jax.device_get(v.sum()))
                s, d, ds = _fit_edges(s, d, ds, cap=T_CAP)
                sc = scorer_ops.service_scores(
                    s, d, ds, _edge_mask(s), ep_s, ep_m, ep_r,
                    num_services=T_NSVC,
                )
                for f in sc:
                    jax.device_get(f)

        def batched_round():
            # ONE stacked dispatch for all 8 tenants: one count-vector
            # fetch, one stacked-tuple fetch
            s, d, ds, v, c = batched_merge_edges(
                S, D, DS, M, WS, WD, WDS, WM
            )
            jax.device_get(c)
            sc = batched_service_scores(
                s, d, ds, v, ep_S, ep_M, ep_R, num_services=T_NSVC
            )
            jax.device_get(sc)

        serial_ms = _timed_median(serial_round, reps=7) * 1000
        batched_ms = _timed_median(batched_round, reps=7) * 1000
        tenancy_extras["tenant_serial_tick_ms_8"] = round(serial_ms, 2)
        tenancy_extras["tenant_batched_tick_ms_8"] = round(batched_ms, 2)
        tenancy_extras["tenant_batch_speedup"] = round(
            serial_ms / max(batched_ms, 1e-9), 2
        )

        # zero-compile join: warm a bucket with 8 real tenant ticks,
        # then run a brand-new 9th tenant's FULL collect and diff the
        # program registry's compile counters
        join_spans = [
            [
                {
                    "traceId": "j{}",
                    "id": "a",
                    "parentId": None,
                    "kind": "SERVER",
                    "name": f"svc{k}.ns.svc.cluster.local:80/*",
                    "timestamp": 1_700_000_000_000_000,
                    "duration": 900,
                    "tags": {
                        "http.method": "GET",
                        "http.status_code": "200",
                        "http.url": f"http://svc{k}.ns/api",
                        "istio.canonical_revision": "v1",
                        "istio.canonical_service": f"svc{k}",
                        "istio.mesh_id": "cluster.local",
                        "istio.namespace": "ns",
                    },
                }
            ]
            for k in range(3)
        ]

        def join_source(tenant):
            tick = {"n": 0}

            def source(_lb, _t, _lim):
                tick["n"] += 1
                out = []
                for g in join_spans:
                    c = [dict(s) for s in g]
                    for s in c:
                        s["traceId"] = f"{tenant}-{tick['n']}-{s['traceId']}"
                        s["id"] = f"{tenant}-{tick['n']}-{s['id']}"
                    out.append(c)
                return out

            return source

        jrouter = TickRouter(
            lambda tenant: TenantRuntime(
                tenant=tenant,
                processor=_DP(
                    trace_source=join_source(tenant),
                    k8s_source=None,
                    use_device_stats=False,
                    tenant=tenant,
                ),
            )
        )
        jreq = lambda i: {
            "uniqueId": f"j{i}", "lookBack": 30_000, "time": 1_700_000_000_000
        }
        jrouter.batched_collect(
            [(f"bench-t{t}", jreq(t)) for t in range(N_T)]
        )
        compiles_before = programs.summary()["totalCompiles"]
        jrouter.batched_collect([("bench-joiner", jreq(99))])
        tenancy_extras["tenant_join_compile_count"] = (
            programs.summary()["totalCompiles"] - compiles_before
        )
    # ---- graftpilot control plane (ISSUE 11) -------------------------------
    # the controller's two in-process latencies — the fold-boundary
    # decision recompute (Controller.ingest over synthetic forecast views)
    # and the serving-edge admission read the POST handler pays per tick.
    # (The counterfactual gate runs as its own sibling process; see
    # SUBPROCESS_SECTIONS.) KMAMIZ_BENCH_CONTROL=0 skips. Gated by
    # tools/slo_report.py as higher-is-worse.
    control_extras = {
        "control_decision_ms": None,
        "control_tick_overhead_ms": None,
    }
    if os.environ.get("KMAMIZ_BENCH_CONTROL", "1") != "0":
        from kmamiz_tpu import control as ctl_plane

        saved_ctl = {
            k: os.environ.get(k)
            for k in ("KMAMIZ_CONTROL", "KMAMIZ_CONTROL_SLO_MS")
        }
        os.environ["KMAMIZ_CONTROL"] = "1"
        os.environ["KMAMIZ_CONTROL_SLO_MS"] = "250"
        try:
            ctl_plane.reset_for_tests()
            decide_walls = []
            for i in range(64):
                view = ctl_plane.ForecastView(
                    tenant="bench",
                    p99_ms=120.0 + (i % 7) * 40.0,
                    cost_ms=900.0 + i,
                    attributions=(
                        ("svc-a", "svc-b", 0.4 + (i % 3) * 0.2),
                    ),
                )
                t0 = time.perf_counter()
                ctl_plane.ingest_forecast(view)
                decide_walls.append((time.perf_counter() - t0) * 1000)
            # the admission read is sub-µs: time a 1000-call loop and
            # charge the mean per call (single-call walls are all
            # clock resolution)
            tick_req = {"uniqueId": "bench", "lookBack": 30_000}
            reads = 1000
            t0 = time.perf_counter()
            for _ in range(reads):
                ctl_plane.admission_verdict("bench", tick_req)
            overhead_ms = (time.perf_counter() - t0) * 1000 / reads
        finally:
            for k, v in saved_ctl.items():
                if v is None:
                    os.environ.pop(k, None)
                else:
                    os.environ[k] = v
            ctl_plane.reset_for_tests()
        control_extras = {
            "control_decision_ms": round(float(np.median(decide_walls)), 4),
            "control_tick_overhead_ms": round(overhead_ms, 5),
        }

    parse_s, pack_s, transfer_s, device_s = e2e_phases
    e2e_spans_per_sec = e2e_n_spans / (parse_s + pack_s + transfer_s + device_s)
    best_wall_s, summary = stream_best
    # the stream's OWN measured span count (dedup/odd-divisor safe)
    stream_rate = summary["spans"] / best_wall_s
    headline = {
        "metric": (
            "END-TO-END pipelined span ingest on the deployed route: "
            "paginated raw Zipkin JSON -> DataProcessor."
            "ingest_raw_stream (chunked native parse overlapping "
            "device window-merge into the persistent endpoint "
            "graph) — 1.05M-span window at BASELINE shape (1k "
            "services / 10k endpoints / >=100k distinct edges), "
            "steady-state persistent processor; spans over the "
            "measured wall, nothing excluded"
        ),
        "value": round(stream_rate, 0),
        "vs_baseline": round(stream_rate / BASELINE_SPANS_PER_SEC, 3),
    }
    e2e_extras = {
        "e2e_serial_spans_per_sec": round(e2e_spans_per_sec, 0),
        "e2e_parse_ms": round(parse_s * 1000, 1),
        "e2e_pack_ms": round(pack_s * 1000, 1),
        "e2e_transfer_ms": round(transfer_s * 1000, 1),
        "e2e_device_ms": round(device_s * 1000, 1),
        "e2e_serial_wall_reps_ms": e2e_wall_reps_ms,
        "parse_thread_scaling": parse_scaling,
        **wire_extras,
        "e2e_stream_spans_per_sec": round(stream_rate, 0),
        "e2e_stream_wall_ms": round(best_wall_s * 1000, 1),
        "e2e_stream_chunks": N_CHUNKS,
        "e2e_stream_pipeline_depth": summary.get("pipeline_depth"),
        "e2e_stream_ring_peak": summary.get("ring_peak"),
        "e2e_stream_drain_ms": summary["drain_ms"],
        "e2e_stream_chunk_detail": summary["chunk_detail"],
        "e2e_stream_wall_reps_ms": stream_walls_ms,
        "e2e_stream_edges": summary["edges"],
        "e2e_stream_endpoints": summary["endpoints"],
        **stream_cold_extras,
        **stream_legacy_extras,
        **stream_upload_extras,
    }
    # static-analysis cost: one full graftlint pass over the package
    # (what the tier-1 repo-clean test and --strict CI pay)
    t0 = time.perf_counter()
    from kmamiz_tpu.analysis import framework as lint_framework

    lint_result = lint_framework.lint_repo()
    graftlint_repo_ms = (time.perf_counter() - t0) * 1000

    # graftrace: the 3 concurrency rules alone (lock-model build is the
    # dominant cost; tools/graftrace.py --strict runs exactly this)
    from tools.graftrace import CONCURRENCY_RULES

    t0 = time.perf_counter()
    trace_result = lint_framework.lint_repo(list(CONCURRENCY_RULES))
    graftrace_repo_ms = (time.perf_counter() - t0) * 1000

    # SLO scorecard over this run's DP ticks (telemetry/slo.py): bench is
    # the first consumer of the headline keys ROADMAP item 5 asks for;
    # tools/slo_report.py --check gates regressions against these
    from kmamiz_tpu.telemetry import slo as tel_slo

    slo_extras = {
        f"slo_{k}": v for k, v in tel_slo.SCORECARD.snapshot().items()
    }

    return {
        **headline,
        "unit": "spans/sec",
        **device_header,
        "graftlint_repo_ms": round(graftlint_repo_ms, 1),
        "graftlint_findings": len(lint_result.findings),
        "graftlint_suppressed": len(lint_result.suppressed),
        "graftrace_repo_ms": round(graftrace_repo_ms, 1),
        "graftrace_findings": len(trace_result.findings),
        "graftrace_suppressed": len(trace_result.suppressed),
        "device_kernels_spans_per_sec": round(spans_per_sec, 0),
        **e2e_extras,
        "e2e_bytes_per_span": round(e2e_bytes_per_span, 0),
        "e2e_host_cores": os.cpu_count(),
        "p50_graph_refresh_ms_10k_endpoints": round(refresh_ms, 2),
        **scale_extras,
        # graph-scale headline keys (ROADMAP item 2): always present, None
        # when the optional 100k section was skipped, so a regression can
        # never hide inside a missing key
        "graph_refresh_ms_100k": scale_extras.get("graph_refresh_ms_100k"),
        "graph_merge_wall_ms_100k": (
            max(scale_extras["graph_scale_merge_walls_ms"])
            if scale_extras.get("graph_scale_merge_walls_ms")
            else None
        ),
        "graph_refresh_pass": bool(refresh_ms <= 50.0),
        **grow_extras,
        "http_instability_10k_endpoints_ms": round(http_api_refresh_ms, 1),
        "walk_mxu_packed_ms": round(walk_mxu_ms, 1),
        "walk_flat_gather_ms": round(walk_flat_ms, 1),
        "walk_mxu_speedup": round(walk_flat_ms / max(walk_mxu_ms, 1e-9), 1),
        "walk_sharded_packed_1dev_ms": round(walk_sharded_packed_ms, 1),
        "walk_sharded_flat_1dev_ms": round(walk_sharded_flat_ms, 1),
        "graph_refresh_target_ms": 50.0,
        "n_spans": N_SPANS,
        "n_endpoints": N_ENDPOINTS,
        "n_services": N_SERVICES,
        "dp_tick_ms_2500_traces": round(dp_tick_ms, 1),
        "dp_tick_cached_ms": round(dp_tick_cached_ms, 1),
        "dp_tick_telemetry_off_ms": round(dp_tick_telemetry_off_ms, 1),
        "dp_tick_prof_off_ms": round(dp_tick_prof_off_ms, 1),
        **prof_phase_keys,
        **stream_tick_extras,
        **slo_extras,
        "dp_scorer_cached_read_ms": round(scorer_cached_read_ms, 3),
        "dp_scorer_cache_hit_rate": scorer_stats.get("hit_rate"),
        "dp_scorer_cache_stats": scorer_stats,
        "dp_tick_budget_ms": 5000.0,  # the reference's realtime cadence
        **sage_extras,
        **stlgt_extras,
        **tenancy_extras,
        **control_extras,
        "packing_host_ms": round(packing_host_ms, 1),
        # raw env setting (0 = auto) AND the resolved worker count the
        # native scan actually runs with on this host
        "native_parse_threads": native_mod.parse_threads(),
        "native_parse_threads_effective": native_mod.effective_parse_threads(),
        "compile_cache": compile_cache.stats(),
        "timing_method": (
            "headline: deployed streaming route (DataProcessor."
            "ingest_raw_stream over paginated chunks at the deployed "
            "default width) at BASELINE workload shape (1k svc / 10k "
            "endpoints / >=100k edges), STEADY-STATE: one persistent "
            "processor serves every rep a fresh window with distinct "
            "trace ids and identical naming shapes — production after "
            "boot; cold first window in e2e_stream_cold_*, r4-style "
            "legacy shape (fresh processor per rep) in "
            "e2e_stream_legacy_*; a virtual clock advances past the "
            "5-min dedup TTL between reps so the processed-trace map "
            "holds its production steady size. Best-of-6 measured wall, "
            "host->device copy included; rep lists in extras. "
            "Throughput estimators are BEST-of-N; latency metrics (graph "
            "refresh p50, HTTP, DP tick) are median-of-N. Serial one-shot "
            "path in e2e_serial_* (phases e2e_parse/pack/transfer/device); "
            "device kernels: the registered jitted programs, one dispatch "
            "per rep, host clock around block_until_ready; columnar (KMZC) "
            "decode of the identical window in e2e_wire_*/e2e_columnar_* "
            "(encode uncounted — the filter pays it), double-buffered "
            "upload pipeline counters in e2e_upload_* (blocked_ms = host "
            "wall actually spent waiting on transfers). Persistent XLA "
            "compilation cache ON at the place core/compile_cache.py "
            "rules (JAX_COMPILATION_CACHE_DIR, else <checkout>/.xla-cache)"
        ),
    }


# ---------------------------------------------------------------------------
# the parent: one process per chip
# ---------------------------------------------------------------------------

_ROOT = Path(__file__).resolve().parent

#: name -> (skip switch or None, seconds of budget the section must still
#: have, argv). Run by the parent AFTER the in-process child has exited,
#: each in turn, each inheriting the platform; the processes these tools
#: spawn that are CPU by design pin JAX_PLATFORMS=cpu themselves.
SUBPROCESS_SECTIONS = (
    # graftcost predictive prewarm, crossing stall A/B: the same
    # segment-store consolidation with prewarm OFF then ON, one process
    # per arm (compile caches are process-global — an in-process A/B
    # would leak warmth from the first arm into the second)
    ("growth_off", None, 0,
     [sys.executable, "-m", "kmamiz_tpu.cost.growth_probe", "--prewarm", "off"]),
    ("growth_on", None, 0,
     [sys.executable, "-m", "kmamiz_tpu.cost.growth_probe", "--prewarm", "on"]),
    # restart warmth (VERDICT r4 #5b): two fresh processes share one
    # persistent cache directory: the cold arm (directory emptied first)
    # pays the prewarm compile walls into it, the restart arm reloads
    ("warm_boot_cold", None, 1300, [sys.executable, "tools/warm_boot_probe.py"]),
    ("warm_boot_restart", None, 700, [sys.executable, "tools/warm_boot_probe.py"]),
    # chaos resilience (ISSUE 5): all four fault-layer invariants plus
    # kill -> bit-exact-restore wall and degraded (stale) serve latency
    ("chaos", None, 700, [sys.executable, "tools/chaos_probe.py", "--seed", "0"]),
    # closed-loop soak matrix (ISSUE 8): first three archetypes
    ("scenarios", "KMAMIZ_BENCH_SCENARIOS", 300,
     [sys.executable, "tools/scenario_soak.py", "--seed", "0", "--matrix", "3",
      "--ticks", "6"]),
    # graftsoak sweep smoke: 5 cost-ordered cells, 2 workers, 1 poison
    ("soak", "KMAMIZ_BENCH_SOAK", 290,
     [sys.executable, "tools/graftsoak.py", "--cells", "5", "--ticks", "4",
      "--workers", "2", "--poison", "1", "--soak-dir", "{soak_dir}"]),
    # graftfleet scale-out: four worker processes behind HTTPTransport
    ("fleet", "KMAMIZ_BENCH_FLEET", 275,
     [sys.executable, "tools/fleet_bench.py", "--frames", "16"]),
    # graftpilot counterfactual gate (ISSUE 11)
    ("counterfactual", "KMAMIZ_BENCH_CONTROL", 250,
     [sys.executable, "tools/scenario_soak.py", "--counterfactual", "--seed",
      "0", "--ticks", "8"]),
)


def _last_json_line(text: str) -> dict:
    for line in reversed(text.strip().splitlines()):
        if line.startswith("{"):
            return json.loads(line)
    raise ValueError("no JSON line in the section's output")


def _run_section(name: str, argv, env, timeout_s: float) -> dict:
    """One child, to completion; its last stdout line is its result. A
    non-zero exit or unparsable output is the bench's failure."""
    proc = subprocess.run(
        argv,
        cwd=str(_ROOT),
        env=env,
        stdout=subprocess.PIPE,
        stderr=None,  # the child's diagnostics go straight to ours
        text=True,
        timeout=timeout_s,
    )
    if proc.returncode != 0:
        raise SystemExit(
            f"bench.py: section {name!r} exited with code {proc.returncode}:\n"
            f"{proc.stdout[-2000:]}"
        )
    return _last_json_line(proc.stdout)


def _fold_sections(sections: dict) -> dict:
    """The subprocess sections' results under the flat keys
    tools/slo_report.py gates."""
    out = {}
    if "growth_on" in sections:
        off_arm, on_arm = sections["growth_off"], sections["growth_on"]
        out.update(
            {
                "capacity_growth_stall_ms": on_arm["stall_ms"],
                "capacity_growth_stall_off_ms": off_arm["stall_ms"],
                "capacity_growth_stall_reduction": round(
                    off_arm["stall_ms"] / max(on_arm["stall_ms"], 1e-9), 1
                ),
                "capacity_growth_mid_compiles": on_arm["mid_compiles"],
                "capacity_growth_bit_exact": (
                    off_arm["signature"] == on_arm["signature"]
                ),
                "cost_prewarm_hit_rate": on_arm.get("hit_rate"),
                "capacity_growth_steady_ms": on_arm.get("steady_ms"),
            }
        )
    for tag in ("cold", "restart"):
        probe = sections.get(f"warm_boot_{tag}")
        if probe is None:
            continue
        out[f"warm_boot_{tag}_prewarm_s"] = probe["prewarm_s"]
        out[f"warm_boot_{tag}_first_tick_ms"] = probe["first_tick_ms"]
        # per-program compile counts the probe's ticks still paid: after
        # a hint-driven prewarm the restart run must report 0
        out[f"warm_boot_{tag}_tick_compiles"] = probe.get(
            "first_tick_new_compiles", 0
        ) + probe.get("second_tick_new_compiles", 0)
        out[f"warm_boot_{tag}_prewarm_coverage"] = probe.get("prewarm_report", {})
        out[f"warm_boot_{tag}_programs"] = probe.get("programs", {})
        out[f"warm_boot_{tag}_cache"] = probe.get("compile_cache", {})
    restart = sections.get("warm_boot_restart")
    if restart is not None:
        out["warm_first_tick_ms"] = restart["first_tick_ms"]
        # restart contract: a warm process's first tick stays within 2x
        # the steady-state tick — the shape-hint prewarm already replayed
        # every (program, bucket) the previous process compiled
        out["warm_boot_first_tick_target_ms"] = round(
            2 * restart["second_tick_ms"], 1
        )
        out["warm_boot_steady_state_recompiles"] = out[
            "warm_boot_restart_tick_compiles"
        ]
    probe = sections.get("chaos")
    if probe is not None:
        out.update(
            {
                "chaos_probe_ok": probe["ok"],
                "chaos_recovery_ms": probe["chaos_recovery_ms"],
                "degraded_serve_ms": probe["degraded_serve_ms"],
                "chaos_quarantined": probe["quarantine"]["quarantined"],
            }
        )
    soak = sections.get("scenarios")
    if soak is not None:
        out.update(
            {
                "scenario_matrix_pass": soak["scenario_matrix_pass"],
                "scenario_worst_p99_tick_ms": soak["scenario_worst_p99_tick_ms"],
                "scenario_worst_recovery_ms": soak["scenario_worst_recovery_ms"],
                "scenario_lost_spans": soak["scenario_lost_spans"],
                "scenario_matrix_size": len(soak["scenarios"]),
            }
        )
    sweep = sections.get("soak")
    if sweep is not None:
        out.update(
            {
                "soak_smoke_pass_rate": sweep["soak_pass_rate"],
                "soak_triaged_fraction": sweep["soak_triaged_fraction"],
                "soak_cells_per_min": sweep["soak_cells_per_min"],
                "soak_smoke_cells": sweep["cells_total"],
                "soak_smoke_bugs": len(sweep["bugs"]),
                "soak_worker_platform": sweep.get("platform"),
            }
        )
    fleet = sections.get("fleet")
    if fleet is not None:
        if "fleet_bench_error" in fleet:
            raise SystemExit(
                f"bench.py: fleet section failed: {fleet['fleet_bench_error']}"
            )
        out.update({k: v for k, v in fleet.items() if k != "platform"})
        out["fleet_worker_platform"] = fleet.get("platform")
    cf = sections.get("counterfactual")
    if cf is not None:
        out["control_counterfactual_prevented"] = cf[
            "control_counterfactual_prevented"
        ]
        out["control_counterfactual_pass"] = cf["counterfactual_pass"]
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument(
        "--section",
        choices=("inproc",),
        help="run one section in THIS process (the child that holds the chip)",
    )
    args = ap.parse_args(argv)
    if args.section == "inproc":
        print(json.dumps(inproc_main()))
        return 0

    # the parent: stays off JAX, runs the children one at a time
    from kmamiz_tpu.core import compile_cache  # JAX-free until enable()

    t0 = time.perf_counter()
    try:
        budget_s = int(os.environ.get("KMAMIZ_BENCH_BUDGET_S", 3000))
    except ValueError:
        budget_s = 3000
    result = _run_section(
        "inproc",
        [sys.executable, str(_ROOT / "bench.py"), "--section", "inproc"],
        dict(os.environ),
        timeout_s=3600,
    )
    # the warm-boot probe's cache: a FIXED subdirectory of the one cache
    # directory (the name is part of JAX's cache key), emptied for the
    # cold arm
    probe_cache = os.path.join(compile_cache.cache_dir(), "warm-boot-probe")
    sections = {}
    with tempfile.TemporaryDirectory(prefix="kmamiz-bench-soak-") as soak_dir:
        for name, switch, headroom_s, cmd in SUBPROCESS_SECTIONS:
            if switch and os.environ.get(switch, "1") == "0":
                continue
            if time.perf_counter() - t0 > budget_s - headroom_s:
                sections.setdefault("skipped_for_budget", []).append(name)
                continue
            if name == "warm_boot_restart" and "warm_boot_cold" not in sections:
                continue  # a restart arm needs the cache its cold arm filled
            env = dict(os.environ)
            if name.startswith("growth_"):
                # both arms fully cold and hint-free: the OFF arm must
                # actually pay the crossing compile it is measuring
                env.pop("JAX_COMPILATION_CACHE_DIR", None)
                env.pop("KMAMIZ_SHAPE_HINTS", None)
            if name.startswith("warm_boot_"):
                if name == "warm_boot_cold":
                    shutil.rmtree(probe_cache, ignore_errors=True)
                os.makedirs(probe_cache, exist_ok=True)
                env["JAX_COMPILATION_CACHE_DIR"] = probe_cache
            cmd = [c.format(soak_dir=soak_dir) for c in cmd]
            sections[name] = _run_section(name, cmd, env, timeout_s=900)
    skipped = sections.pop("skipped_for_budget", [])
    result.update(_fold_sections(sections))
    result["sections_skipped_for_budget"] = skipped
    result["fleet_host_cores"] = os.cpu_count()
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
