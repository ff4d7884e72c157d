"""Chaos probe: prove the four resilience-pillar invariants under a seed.

Everything the fault layer promises (kmamiz_tpu/resilience/,
docs/RESILIENCE.md) is asserted here against the REAL pipeline — native
parse, device graph merge, the DP HTTP server — with faults drawn from a
seeded FaultPlan so a failure reproduces exactly:

  1. quarantine bit-exactness — a chunk stream poisoned per the plan
     (truncated JSON, invalid UTF-8, schema drift, trace bombs, drops)
     ingests to a graph bit-identical (graph_signature) to ingesting
     only the untouched chunks; every poisoned delivery lands in the
     quarantine with a reason code;
  2. breaker state machine — `threshold` consecutive failures OPEN the
     breaker (short-circuits without touching the upstream), cooldown
     admits a HALF-OPEN probe, a failed probe re-opens, a good one
     closes;
  3. degraded serve — with KMAMIZ_TICK_DEADLINE_MS set and the trace
     source hung, POST / on the DP server answers 200 with the
     last-good graph, `stale: true`, the X-KMamiz-Stale-Age-Ms header,
     ZERO new compiles (program-registry snapshot diff), and no 5xx;
  4. crash-safe recovery — a child process ingests with KMAMIZ_WAL=1
     and SIGKILLs itself between the WAL append and the graph merge of
     its final window; a fresh processor's replay_wal() restores a
     graph bit-identical to ingesting every window.

stdout carries ONE JSON line: {"seed": ..., "ok": ..., per-pillar
results, "chaos_recovery_ms": ..., "degraded_serve_ms": ...}. The
human-readable pillar table goes to stderr. Exit 0 iff every pillar
holds. Run it as a subprocess (the round-5 bench did, for its chaos extras);
`--child-kill` is the internal crash-child mode (never returns).
"""
from __future__ import annotations

import argparse
import json
import os
import signal
import subprocess
import sys
import tempfile
import time
import urllib.request

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# clean chunks must fit under this while the plan's "bomb" payloads
# (~4.1 KB, chaos.mutate_payload) overflow it
SIZE_CAP_BYTES = 4000


def _mk_span(tid: str, sid: str, parent=None, svc="svc", url=None) -> dict:
    return {
        "traceId": tid,
        "id": sid,
        "parentId": parent,
        "kind": "SERVER",
        "name": f"{svc}.ns.svc.cluster.local:80/*",
        "timestamp": 1_700_000_000_000_000,
        "duration": 1000,
        "tags": {
            "http.method": "GET",
            "http.status_code": "200",
            "http.url": url or f"http://{svc}.ns/api",
            "istio.canonical_revision": "v1",
            "istio.canonical_service": svc,
            "istio.mesh_id": "cluster.local",
            "istio.namespace": "ns",
        },
    }


def _clean_groups(n_traces: int, prefix: str):
    """n_traces two-span traces fanning out to 5 downstream services —
    enough edge diversity that a silently lost or duplicated trace
    moves the graph signature."""
    groups = []
    for t in range(n_traces):
        tid = f"{prefix}{t}"
        parent = _mk_span(tid, f"{tid}p")
        child = _mk_span(
            tid,
            f"{tid}c",
            parent=f"{tid}p",
            svc=f"down{t % 5}",
            url=f"http://down{t % 5}.ns/api/{t % 3}",
        )
        groups.append([parent, child])
    return groups


def _clean_chunks(n_traces=40, per_chunk=2, prefix="t"):
    groups = _clean_groups(n_traces, prefix)
    chunks = [
        json.dumps(groups[i : i + per_chunk]).encode()
        for i in range(0, len(groups), per_chunk)
    ]
    oversized = [len(c) for c in chunks if len(c) >= SIZE_CAP_BYTES]
    if oversized:
        raise RuntimeError(
            f"clean chunks must stay under the probe size cap: {oversized}"
        )
    return chunks


def _fresh_processor():
    from kmamiz_tpu.server.processor import DataProcessor

    return DataProcessor(trace_source=lambda *a: [], use_device_stats=False)


# -- pillar 1: poison-input quarantine ---------------------------------------


def pillar_quarantine(seed: int, tmpdir: str) -> dict:
    os.environ["KMAMIZ_QUARANTINE_DIR"] = os.path.join(tmpdir, "quarantine")
    os.environ["KMAMIZ_INGEST_MAX_BYTES"] = str(SIZE_CAP_BYTES)
    from kmamiz_tpu.resilience import quarantine as res_quarantine
    from kmamiz_tpu.resilience.chaos import (
        FaultPlan,
        chaos_chunks,
        graph_signature,
    )

    chunks = _clean_chunks()
    delivered, clean_indices = chaos_chunks(chunks, FaultPlan(seed))

    chaos_dp = _fresh_processor()
    quarantined = 0
    for raw in delivered:
        quarantined += chaos_dp.ingest_raw_window(raw).get("quarantined", 0)
    chaos_sig = graph_signature(chaos_dp.graph)

    clean_dp = _fresh_processor()
    for i in clean_indices:
        clean_dp.ingest_raw_window(chunks[i])
    clean_sig = graph_signature(clean_dp.graph)

    stats = res_quarantine.quarantine_stats()
    poisoned = len(delivered) - len(clean_indices)
    return {
        "ok": (
            chaos_sig == clean_sig
            and poisoned > 0
            and quarantined == poisoned
            and stats["count"] == poisoned
        ),
        "chunks": len(chunks),
        "delivered": len(delivered),
        "clean": len(clean_indices),
        "quarantined": quarantined,
        "byReason": stats["byReason"],
        "signature": chaos_sig,
        "bitExact": chaos_sig == clean_sig,
    }


# -- pillar 2: circuit breaker state machine ---------------------------------


def pillar_breaker() -> dict:
    from kmamiz_tpu.resilience.breaker import (
        HALF_OPEN,
        OPEN,
        BreakerOpenError,
        CircuitBreaker,
    )

    clock = {"t": 0.0}
    breaker = CircuitBreaker(
        "chaos-probe", threshold=3, cooldown_s=5.0, now=lambda: clock["t"]
    )

    def failing():
        raise ConnectionError("chaos: injected upstream failure")

    for _ in range(breaker.threshold):
        try:
            breaker.call(failing)
        except ConnectionError:
            pass
    opened = breaker.state == OPEN

    # open: short-circuits without touching the upstream
    upstream_calls = {"n": 0}

    def probe():
        upstream_calls["n"] += 1
        return "ok"

    short_circuited = False
    try:
        breaker.call(probe)
    except BreakerOpenError:
        short_circuited = upstream_calls["n"] == 0

    clock["t"] += breaker.cooldown_s
    half_opened = breaker.state == HALF_OPEN

    # a failed half-open probe re-opens and restarts the cooldown
    try:
        breaker.call(failing)
    except ConnectionError:
        pass
    reopened = breaker.state == OPEN

    clock["t"] += breaker.cooldown_s
    breaker.call(probe)
    closed = breaker.state == "closed" and upstream_calls["n"] == 1

    return {
        "ok": all([opened, short_circuited, half_opened, reopened, closed]),
        "opened_after_threshold": opened,
        "short_circuited": short_circuited,
        "half_opened_after_cooldown": half_opened,
        "reopened_on_probe_failure": reopened,
        "closed_on_probe_success": closed,
        "snapshot": breaker.snapshot(),
    }


# -- pillar 3: tick watchdog + stale-graph degradation -----------------------


def _post(port: int, unique_id: str, timeout_s: float = 30.0):
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}/",
        data=json.dumps(
            {"uniqueId": unique_id, "lookBack": 30_000, "time": 1_000_000}
        ).encode(),
        headers={"Content-Type": "application/json"},
        method="POST",
    )
    t0 = time.perf_counter()
    with urllib.request.urlopen(req, timeout=timeout_s) as resp:
        body = json.loads(resp.read())
        elapsed_ms = (time.perf_counter() - t0) * 1000
        return resp.status, resp.headers, body, elapsed_ms


def pillar_degraded_serve() -> dict:
    from kmamiz_tpu.core import programs
    from kmamiz_tpu.server.dp_server import DataProcessorServer
    from kmamiz_tpu.server.processor import DataProcessor

    window = _clean_groups(10, prefix="dg")
    hang = {"s": 0.0}

    def source(_lb, _t, _lim):
        if hang["s"]:
            time.sleep(hang["s"])
        return window

    processor = DataProcessor(trace_source=source)
    server = DataProcessorServer(processor, host="127.0.0.1", port=0)
    server.start()
    try:
        # warm tick with the watchdog off: its compiles may legitimately
        # exceed any realistic deadline, and the pillar is about what
        # happens AFTER a good tick exists
        os.environ["KMAMIZ_TICK_DEADLINE_MS"] = "0"
        status, _, body, _ = _post(server.port, "chaos-warm")
        warm_ok = status == 200 and not body.get("stale")

        # overrunning tick: the source hangs well past the deadline
        os.environ["KMAMIZ_TICK_DEADLINE_MS"] = "250"
        hang["s"] = 2.0
        snapshot = programs.snapshot()
        status, headers, body, degraded_ms = _post(server.port, "chaos-stale")
        new_compiles = sum(programs.new_compiles_since(snapshot).values())
        stale_ok = (
            status == 200
            and body.get("stale") is True
            and body.get("uniqueId") == "chaos-stale"
            and body.get("staleReason") == "deadline"
            and headers.get("X-KMamiz-Stale-Age-Ms") is not None
        )

        # let the abandoned straggler drain, then prove recovery: with
        # the deadline lifted the next tick serves fresh again
        time.sleep(hang["s"] + 0.5)
        hang["s"] = 0.0
        os.environ["KMAMIZ_TICK_DEADLINE_MS"] = "0"
        status, _, body, _ = _post(server.port, "chaos-recovered")
        recovered_ok = status == 200 and not body.get("stale")
    finally:
        os.environ["KMAMIZ_TICK_DEADLINE_MS"] = "0"
        server.stop()

    return {
        "ok": warm_ok and stale_ok and new_compiles == 0 and recovered_ok,
        "warm_tick": warm_ok,
        "stale_served": stale_ok,
        "stale_new_compiles": new_compiles,
        "recovered_after_straggler": recovered_ok,
        "degraded_serve_ms": round(degraded_ms, 1),
    }


# -- pillar 4: kill -9 mid-ingest -> WAL replay ------------------------------


def run_child_kill() -> None:
    """Crash child (parent sets KMAMIZ_WAL=1 + the WAL dir): ingest all
    windows but the last, WAL the last one, then die before its merge —
    the exact crash point ingest_raw_window's append-before-merge
    ordering exists for. Never returns."""
    chunks = _clean_chunks(prefix="w")
    dp = _fresh_processor()
    for raw in chunks[:-1]:
        dp.ingest_raw_window(raw)
    dp._wal_append(chunks[-1])
    os.kill(os.getpid(), signal.SIGKILL)


def pillar_wal_recovery(seed: int, tmpdir: str) -> dict:
    from kmamiz_tpu.resilience.chaos import graph_signature

    wal_dir = os.path.join(tmpdir, "wal")
    child_env = {
        **os.environ,
        "KMAMIZ_WAL": "1",
        "KMAMIZ_WAL_DIR": wal_dir,
        # deliberately a CPU process: this probe holds the accelerator
        # (one process per chip) while the crash child runs
        "JAX_PLATFORMS": "cpu",
    }
    child = subprocess.run(
        [
            sys.executable,
            os.path.abspath(__file__),
            "--child-kill",
            "--seed",
            str(seed),
        ],
        env=child_env,
        capture_output=True,
        timeout=600,
    )
    killed = child.returncode == -signal.SIGKILL

    chunks = _clean_chunks(prefix="w")

    # reference: every window ingested in-process, WAL off so the
    # recovery dir only holds what the child wrote before dying
    os.environ["KMAMIZ_WAL"] = "0"
    reference = _fresh_processor()
    for raw in chunks:
        reference.ingest_raw_window(raw)
    reference_sig = graph_signature(reference.graph)

    os.environ["KMAMIZ_WAL"] = "1"
    os.environ["KMAMIZ_WAL_DIR"] = wal_dir
    try:
        t0 = time.perf_counter()
        recovered = _fresh_processor()
        replay = recovered.replay_wal()
        recovery_ms = (time.perf_counter() - t0) * 1000
    finally:
        os.environ["KMAMIZ_WAL"] = "0"
    recovered_sig = graph_signature(recovered.graph)

    return {
        "ok": (
            killed
            and replay["replayed"] == len(chunks)
            and recovered_sig == reference_sig
        ),
        "child_platform": "cpu",
        "child_sigkilled": killed,
        "wal_records_replayed": replay["replayed"],
        "windows": len(chunks),
        "bitExact": recovered_sig == reference_sig,
        "signature": recovered_sig,
        "chaos_recovery_ms": round(recovery_ms, 1),
    }


# -- driver ------------------------------------------------------------------


def main() -> int:
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--seed", type=int, default=0)
    parser.add_argument(
        "--matrix",
        type=int,
        default=None,
        metavar="N",
        help="run the seed-dependent pillars (quarantine, WAL recovery) "
        "across N consecutive seeds starting at --seed; the seedless "
        "breaker and degraded-serve pillars run once",
    )
    parser.add_argument(
        "--child-kill",
        action="store_true",
        help="internal: crash-child mode for the WAL pillar (never returns)",
    )
    args = parser.parse_args()

    if args.child_kill:
        run_child_kill()
        return 1  # unreachable

    results = {"seed": args.seed}
    with tempfile.TemporaryDirectory(prefix="kmamiz-chaos-") as tmpdir:
        if args.matrix is None:
            results["quarantine"] = pillar_quarantine(args.seed, tmpdir)
            results["breaker"] = pillar_breaker()
            results["degraded_serve"] = pillar_degraded_serve()
            results["wal_recovery"] = pillar_wal_recovery(args.seed, tmpdir)
        else:
            # per-seed tmp subdirs keep quarantine/WAL artifacts apart;
            # the cached quarantine instance is rebound per seed so each
            # iteration's count starts at zero under its own dir
            from kmamiz_tpu.resilience import quarantine as res_quarantine

            seeds = list(range(args.seed, args.seed + max(1, args.matrix)))
            per_seed = []
            for seed in seeds:
                seed_dir = os.path.join(tmpdir, f"seed{seed}")
                os.makedirs(seed_dir, exist_ok=True)
                res_quarantine.reset_for_tests()
                per_seed.append(
                    {
                        "seed": seed,
                        "quarantine": pillar_quarantine(seed, seed_dir),
                        "wal_recovery": pillar_wal_recovery(seed, seed_dir),
                    }
                )
            results["matrix"] = per_seed
            results["matrix_seeds"] = seeds
            # aggregate view: worst case across seeds for the seeded
            # pillars, the seedless pillars once
            results["quarantine"] = {
                "ok": all(r["quarantine"]["ok"] for r in per_seed),
                "seeds_passed": sum(
                    1 for r in per_seed if r["quarantine"]["ok"]
                ),
                "quarantined": sum(
                    r["quarantine"]["quarantined"] for r in per_seed
                ),
            }
            results["breaker"] = pillar_breaker()
            results["degraded_serve"] = pillar_degraded_serve()
            results["wal_recovery"] = {
                "ok": all(r["wal_recovery"]["ok"] for r in per_seed),
                "seeds_passed": sum(
                    1 for r in per_seed if r["wal_recovery"]["ok"]
                ),
                "chaos_recovery_ms": max(
                    r["wal_recovery"]["chaos_recovery_ms"] for r in per_seed
                ),
            }

    pillars = ("quarantine", "breaker", "degraded_serve", "wal_recovery")
    results["ok"] = all(results[p]["ok"] for p in pillars)
    # the two headline extras, hoisted to the top level
    results["chaos_recovery_ms"] = results["wal_recovery"]["chaos_recovery_ms"]
    results["degraded_serve_ms"] = results["degraded_serve"][
        "degraded_serve_ms"
    ]

    width = max(len(p) for p in pillars)
    for p in pillars:
        state = "PASS" if results[p]["ok"] else "FAIL"
        detail = {
            k: v
            for k, v in results[p].items()
            if k not in ("ok", "signature", "snapshot", "byReason")
        }
        print(f"{p:<{width}}  {state}  {detail}", file=sys.stderr)

    print(json.dumps(results))
    return 0 if results["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
