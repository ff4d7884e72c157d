#!/usr/bin/env python3
"""chip_smoke.py — does the system still start on the chip?

Drives the deployed main path once, end to end, through the entry points
a user would call, at the full width of BASELINE config 4 (1,000
services / 10,000 endpoints / >=100k distinct edges, a 1.05M-span
window), and checks what comes out by the repo's own means. Run with no
arguments from the root of a checkout; it refuses to run without an
accelerator (every child gets JAX_PLATFORMS=tpu, so JAX itself raises).

  A  external DP      python -m kmamiz_tpu.server.dp_server
                      boot + prewarm, four >32 MiB POST /ingest bodies
                      (the streaming route and the packed MXU walk),
                      three DP-protocol ticks, then /timings, /metrics,
                      /debug/graftprof
  B  API server       python -m kmamiz_tpu.api.app (the image's CMD)
                      first-time-setup backfill through
                      processor.ingest_from_zipkin, realtime ticks on
                      the cron, then the three scorer routes as served
                      (device) against ?scorer=host (host oracle)
  C  model head       BASELINE config 5: the scan-fused GraphSAGE
                      trainer takes epoch blocks on the 10k-endpoint /
                      50k-edge / 24-slot graph, saves and restores an
                      orbax checkpoint, forecast_forward answers from it
  D  kernels          segment_stats_matmul through window_stats, and the
                      refresh's planned neighbour sum, planned attention
                      and planned gated sum over an edge plan, compiled
                      by Mosaic and compared with their XLA twins

One process per chip: this parent never imports JAX (it imports
kmamiz_tpu.synth, which is JAX-free, and the stdlib); it serves a stub
Zipkin and a stub Kubernetes API on localhost and runs the phases as
children ONE AT A TIME. Any failed assertion, non-200, child crash or
timeout ends the run non-zero; no phase is wrapped in a catch that lets
the run exit 0.

The stub answers by the SHAPE of a query, not by its time range: a
limit<=2,500 query is a realtime tick, a multi-day page query is the
backfill, and the day-sized "today" query returns the whole history
while the 30-day one returns nothing. That placement is deliberate: the
host pipeline folds a window with the reference's combineWith, whose
Map.set overwrite drops edges of same-window duplicate records (the
device store keeps the union — graph/store.py, "Intentional
deviation"); only records arriving on the `other` side of combineWith
are unioned losslessly, and first-time setup puts today's traces there.
For the same reason a tick window re-observes paths the history already
holds (fresh trace ids), except for its last trace, which runs through
services nothing else names (new_path_trace): every endpoint in it is
the SERVER of exactly one span of the window, so the fold keeps all of
its edges. Each tick therefore ADDS edges, the smoke asserts that the
device graph and the host graph both grew by exactly that many, and
?scorer=host IS the exact oracle of the device graph at 10k endpoints,
tick-added edges included.

Output: the report (per-phase pass/fail, device, versions, the program
registry's calls/compiles/compileMs, persistent-cache hits and misses,
per-device memory_stats) is written to <out>/chip_smoke_report.json.
Wall times in it are smoke observations, not metrics. On success the
last line of stdout is
{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}.
"""
from __future__ import annotations

import argparse
import hashlib
import json
import os
import re
import signal
import socket
import subprocess
import sys
import tempfile
import threading
import time
import traceback
import urllib.error
import urllib.request
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from pathlib import Path
from urllib.parse import parse_qs, urlparse

ROOT = Path(__file__).resolve().parent

#: the whole run must end inside the driver's 1,200 s on one chip,
#: compilation included (--deadline gives a larger host longer)
DEADLINE_S = 1150.0

FULL = {
    "n_services": 1000,
    "urls_per_service": 10,
    "history_traces": 150_000,  # x7 spans = 1,050,000 spans
    "spans_per": 7,
    "bodies": 4,
    "tick_traces": 2500,
    "ticks": 3,
    "min_endpoints": 10_000,
    "min_edges": 100_000,
    "stream_bytes": None,  # the deployed 32 MiB threshold
    "realtime_interval": None,  # the deployed 5 s cron
    "sage": {"nodes": 10_000, "edges": 50_000, "slots": 24, "hidden": 32},
    "stats": {"records": 16_384, "endpoints": 8192, "statuses": 2},
    "plan": {"nodes": 1000, "edges": 8192, "feat": 64},
}

#: What the FULL-size run must produce, whatever it runs on. The inputs
#: are seeded, the graph is a set and the scorer payloads are integers and
#: float64 ratios of integers, so these are constants. PR 21's chip runs
#: (one v5e chip, a four-chip host) and an 8-core CPU all produced the
#: same values for the inputs of that moment (PERF.md); the tick windows
#: have since gained their new-path trace, and the values below are the
#: CPU's for the present inputs (18 edges, 12 endpoints and 12 services
#: more), not yet reproduced on a chip. A change to synth.make_raw_window
#: or to the tick windows changes them; anything else that does is a
#: wrong graph.
EXPECTED_FULL = {
    "graph": {
        "edges": 166_018,  # 166,000 from the history + 6 per tick
        "endpointsNamed": 10_012,
        "signature": (
            "fe5944df2a56f112e8ac03e373cfd926e47fa66890922d687a4b940a1ecf30de"
        ),
    },
    "scorers": {
        "instability": (
            "64a6185b8854a4965be9736537f969a86a0a248973e10a84d9e4af3c538a4d09"
        ),
        "coupling": (
            "b8c9216e4303ba9ef2d78b0e2a00811e455fcc7f23ecf24317cd25fd510ac63c"
        ),
        "cohesion": (
            "8e3d332965cf74871704457c349a22dc57ff59d5020224a44422696fa902f771"
        ),
    },
}

#: test-only size (tests/test_chip_bringup.py): same code, same checks
TINY = {
    "n_services": 30,
    "urls_per_service": 4,
    "history_traces": 1200,
    "spans_per": 7,
    "bodies": 4,
    "tick_traces": 60,
    "ticks": 3,
    "min_endpoints": 100,
    "min_edges": 300,
    "stream_bytes": 65_536,
    "realtime_interval": "* * * * * *",  # every second: the test waits on it
    "sage": {"nodes": 200, "edges": 600, "slots": 8, "hidden": 8},
    "stats": {"records": 512, "endpoints": 64, "statuses": 2},
    "plan": {"nodes": 96, "edges": 400, "feat": 8},
}


class SmokeFailure(Exception):
    """A check failed; the message says which."""


def check(cond, message: str) -> None:
    if not cond:
        raise SmokeFailure(message)


class Clock:
    def __init__(self, deadline_s: float = DEADLINE_S) -> None:
        self.t0 = time.monotonic()
        self.deadline_s = deadline_s

    def elapsed(self) -> float:
        return time.monotonic() - self.t0

    def remaining(self) -> float:
        return self.deadline_s - self.elapsed()

    def budget(self, want_s: float) -> float:
        """A timeout of at most `want_s` that still fits the deadline."""
        left = self.remaining()
        check(left > 1.0, f"out of time after {self.elapsed():.0f}s")
        return min(want_s, left)


# ---------------------------------------------------------------------------
# stub Zipkin + Kubernetes API (parent process, stdlib only)
# ---------------------------------------------------------------------------


def join_json_arrays(bodies) -> bytes:
    """b'[a]' + b'[b]' -> b'[a,b]' without parsing."""
    inner = [b[1:-1] for b in bodies if len(b) > 2]
    return b"[" + b",".join(inner) + b"]"


class StubMesh:
    """What the stub servers answer from. `history` is the backfill,
    split in pages; `tick_windows` are served one per realtime query
    once `ticks_enabled`, then the mesh goes quiet."""

    def __init__(self, history, tick_windows, namespaces) -> None:
        self.history = history
        self.history_joined = join_json_arrays(history)
        self.tick_windows = list(tick_windows)
        self.namespaces = namespaces
        self.ticks_enabled = False
        self.lock = threading.Lock()
        self.page_requests = 0
        self.ticks_served = 0
        self.quiet_ticks_after = 0  # empty answers after the last window
        self.errors = []

    def traces(self, lookback_ms: int, limit: int) -> bytes:
        day = 86_400_000
        with self.lock:
            if limit <= 2500:  # a realtime tick (ZIPKIN_LIMIT)
                if self.ticks_enabled and self.tick_windows:
                    self.ticks_served += 1
                    return self.tick_windows.pop(0)
                if self.ticks_enabled:
                    self.quiet_ticks_after += 1
                return b"[]"
            if lookback_ms >= 29 * day:  # "everything before today"
                return b"[]"
            if lookback_ms >= day:  # one backfill page, oldest first
                page = self.history[self.page_requests % len(self.history)]
                self.page_requests += 1
                return page
            return self.history_joined  # "today"


def make_stub_handler(mesh: StubMesh):
    class Handler(BaseHTTPRequestHandler):
        protocol_version = "HTTP/1.1"

        def log_message(self, fmt, *args) -> None:
            pass

        def _send(self, status: int, body: bytes) -> None:
            self.send_response(status)
            self.send_header("Content-Type", "application/json")
            self.send_header("Content-Length", str(len(body)))
            self.end_headers()
            self.wfile.write(body)

        def do_GET(self) -> None:
            url = urlparse(self.path)
            parts = [p for p in url.path.split("/") if p]
            try:
                if url.path == "/zipkin/api/v2/traces":
                    q = parse_qs(url.query)
                    body = mesh.traces(
                        int(q["lookback"][0]), int(q["limit"][0])
                    )
                    self._send(200, body)
                elif url.path == "/zipkin/api/v2/services":
                    self._send(200, b"[]")
                elif parts[:2] == ["api", "v1"] and parts[2:] == ["namespaces"]:
                    items = [{"metadata": {"name": n}} for n in mesh.namespaces]
                    self._send(200, json.dumps({"items": items}).encode())
                elif parts[:3] == ["api", "v1", "namespaces"] and len(parts) == 5:
                    # pods / services of a namespace: an empty cluster
                    self._send(200, b'{"items": []}')
                else:
                    mesh.errors.append(f"unexpected GET {self.path}")
                    self._send(404, b"{}")
            except Exception as err:  # noqa: BLE001 - report, then fail the run
                mesh.errors.append(f"stub failed on {self.path}: {err!r}")
                self._send(500, b"{}")

    return Handler


# ---------------------------------------------------------------------------
# child processes and HTTP
# ---------------------------------------------------------------------------


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def child_env(platform: str, extra: dict) -> dict:
    """The environment of a phase's child: the caller's, minus every
    KMAMIZ_* setting (the smoke runs the defaults), with the platform
    pinned so JAX raises when it is not there."""
    env = {k: v for k, v in os.environ.items() if not k.startswith("KMAMIZ_")}
    env["JAX_PLATFORMS"] = platform
    env["PYTHONUNBUFFERED"] = "1"
    env.update({k: str(v) for k, v in extra.items()})
    return env


class Child:
    """One child process with its output in a log file; always stopped."""

    def __init__(self, name: str, cmd, env: dict, out_dir: Path) -> None:
        self.name = name
        self.log_path = out_dir / f"{name}.log"
        self._log = open(self.log_path, "wb")
        self.proc = subprocess.Popen(
            cmd,
            cwd=str(ROOT),
            env=env,
            stdout=self._log,
            stderr=subprocess.STDOUT,
            start_new_session=True,
        )

    def log_tail(self, n: int = 4000) -> str:
        self._log.flush()
        data = self.log_path.read_bytes()
        return data[-n:].decode("utf-8", "replace")

    def check_alive(self) -> None:
        rc = self.proc.poll()
        if rc is not None:
            raise SmokeFailure(
                f"{self.name} exited with code {rc}:\n{self.log_tail()}"
            )

    def terminate_cleanly(self, timeout_s: float) -> None:
        """SIGTERM and require a clean exit (code 0)."""
        self.check_alive()
        self.proc.send_signal(signal.SIGTERM)
        try:
            rc = self.proc.wait(timeout=timeout_s)
        except subprocess.TimeoutExpired:
            raise SmokeFailure(
                f"{self.name} did not exit {timeout_s:.0f}s after SIGTERM:\n"
                f"{self.log_tail()}"
            ) from None
        check(
            rc == 0,
            f"{self.name} exited with code {rc} on SIGTERM:\n{self.log_tail()}",
        )

    def kill(self) -> None:
        if self.proc.poll() is None:
            try:
                os.killpg(self.proc.pid, signal.SIGKILL)
            except ProcessLookupError:
                pass
            self.proc.wait(timeout=30)
        self._log.close()


def http(method: str, url: str, body: bytes = None, timeout: float = 60.0):
    """(status, parsed-or-raw body). Non-2xx statuses are returned, not
    raised; transport errors raise."""
    req = urllib.request.Request(url, data=body, method=method)
    if body is not None:
        req.add_header("Content-Type", "application/json")
    try:
        with urllib.request.urlopen(req, timeout=timeout) as res:
            status, raw, ctype = res.status, res.read(), res.headers.get(
                "Content-Type", ""
            )
    except urllib.error.HTTPError as err:
        status, raw, ctype = err.code, err.read(), err.headers.get(
            "Content-Type", ""
        )
    if "json" in ctype:
        return status, json.loads(raw) if raw else None
    return status, raw


def wait_ready(child: Child, url: str, clock: Clock, want_s: float) -> dict:
    """Poll a health route until it answers 200 UP; 503 WARMING and a
    refused connection mean "not yet"."""
    deadline = time.monotonic() + clock.budget(want_s)
    last = "no answer"
    while time.monotonic() < deadline:
        child.check_alive()
        try:
            status, body = http("GET", url, timeout=10.0)
        except (OSError, ValueError) as err:
            last = repr(err)
        else:
            if status == 200 and isinstance(body, dict) and body.get("status") == "UP":
                return body
            last = f"{status} {str(body)[:200]}"
        time.sleep(0.5)
    raise SmokeFailure(
        f"{child.name} not ready after {want_s:.0f}s ({last}):\n{child.log_tail()}"
    )


def check_prewarm(health: dict, who: str) -> dict:
    warm = health.get("prewarm") or {}
    check(
        warm.get("status") == "ready",
        f"{who}: prewarm status is {warm.get('status')!r}, not 'ready': {warm}",
    )
    report = warm.get("report") or {}
    check(
        report.get("failed", 0) == 0,
        f"{who}: prewarm reports failures: {report}",
    )
    return warm


def check_device(device: dict, platform: str, who: str) -> None:
    check(isinstance(device, dict), f"{who}: no device block")
    check(
        device.get("platform") == platform,
        f"{who}: runs on {device.get('platform')!r}, expected {platform!r}",
    )
    check(
        device.get("count", 0) >= 1 and device.get("device_kind"),
        f"{who}: incomplete device block {device}",
    )
    check(
        len(device.get("memory", [])) >= 1,
        f"{who}: device block lists no local device memory",
    )


def program_calls(timings: dict, name: str) -> int:
    progs = timings["programs"]["programs"]
    return int(progs.get(name, {}).get("calls", 0))


def program_table(timings: dict) -> dict:
    return {
        name: {k: p[k] for k in ("calls", "compiles", "compileMs")}
        for name, p in timings["programs"]["programs"].items()
        if p["calls"]
    }


def check_path_taken(timings: dict, platform: str, who: str) -> dict:
    """Which walk / stats / merge programs ran: the mesh programs when the
    child shards over a mesh, the single-device ones otherwise, and the
    MXU walk (never the flat off-TPU variant) on a TPU."""
    mesh = timings["device"].get("mesh")
    calls = lambda name: program_calls(timings, name)  # noqa: E731
    taken = {"mesh": mesh}
    if mesh is not None:
        for name in ("mesh.sharded_window_edges_compact", "mesh.sharded_window_stats"):
            taken[name] = calls(name)
            check(calls(name) > 0, f"{who}: mesh {mesh} active but {name} never ran")
        check(
            calls("graph.window_edges_compact") == 0,
            f"{who}: mesh active but the single-device staged walk ran",
        )
    else:
        check(
            timings["device"]["count"] == 1,
            f"{who}: {timings['device']['count']} devices but no active mesh",
        )
        for name in ("graph.window_edges_compact", "window.stats"):
            taken[name] = calls(name)
            check(calls(name) > 0, f"{who}: {name} never ran")
    mxu, flat = (
        calls("window.dependency_edges_packed"),
        calls("window.dependency_edges_packed_sparse"),
    )
    taken["window.dependency_edges_packed"] = mxu
    taken["window.dependency_edges_packed_sparse"] = flat
    if platform == "tpu":
        check(mxu > 0, f"{who}: the packed MXU walk never ran")
        check(flat == 0, f"{who}: the flat off-TPU walk ran {flat}x")
    else:  # --tiny on a CPU: the store picks the flat variant by itself
        check(mxu + flat > 0, f"{who}: no packed walk ran at all")
    return taken


def check_resilience(res: dict, who: str) -> None:
    check(
        res["watchdog"]["trips"] == 0,
        f"{who}: watchdog tripped: {res['watchdog']}",
    )
    check(
        res["counters"].get("quarantined", 0) == 0,
        f"{who}: payloads were quarantined: {res['quarantine']}",
    )
    for key in ("dpFallback", "ingestDropped", "scorerHostFallback"):
        check(res.get(key, 0) == 0, f"{who}: {key} = {res.get(key)}")
    failing = {
        name: job
        for name, job in res.get("jobs", {}).items()
        if job.get("totalFailures")
    }
    check(not failing, f"{who}: scheduled jobs failed: {failing}")


def check_native(native: dict, who: str) -> dict:
    """The parser is the native one, built from the sources beside this
    script (their hash is computed HERE, in the parent, from the files
    git commits — kmamiz_tpu.native is stdlib-only until it loads)."""
    from kmamiz_tpu.native import source_hash

    here = source_hash()
    check(
        native.get("available") is True,
        f"{who}: native parser not loaded (Python fallback): {native}",
    )
    built_from = (native.get("buildInfo") or {}).get("sources")
    check(
        built_from == here,
        f"{who}: native library built from {built_from}, "
        f"sources here hash to {here}",
    )
    return {"sourceHash": here, "buildInfo": native["buildInfo"]}


def check_cache(cache: dict, who: str) -> dict:
    from kmamiz_tpu.core import compile_cache  # JAX-free until enable()

    want = compile_cache.cache_dir()
    check(cache.get("enabled") is True, f"{who}: compile cache is off: {cache}")
    check(
        os.path.realpath(cache["dir"]) == os.path.realpath(want),
        f"{who}: compile cache at {cache['dir']}, expected {want}",
    )
    return cache


def named_signature(export: dict) -> str:
    """Order- and interner-independent hash of a graph: sha256 over its
    sorted (caller, callee, distance) NAME triples (/fleet/export)."""
    names = export["names"]
    triples = sorted(
        (names[s], names[d], int(c))
        for s, d, c in zip(export["src"], export["dst"], export["dist"])
    )
    digest = hashlib.sha256()
    for s, d, c in triples:
        digest.update(f"{s}\n{d}\n{c}\n".encode())
    return digest.hexdigest()


# ---------------------------------------------------------------------------
# phase A — the external DP server
# ---------------------------------------------------------------------------


def phase_dp(ctx) -> dict:
    sizes, clock, platform = ctx["sizes"], ctx["clock"], ctx["platform"]
    mesh = StubMesh([], ctx["dp_tick_windows"], ctx["namespaces"])
    mesh.ticks_enabled = True
    stub = ThreadingHTTPServer(("127.0.0.1", 0), make_stub_handler(mesh))
    threading.Thread(target=stub.serve_forever, daemon=True).start()
    port = free_port()
    extra = {
        "ZIPKIN_URL": f"http://127.0.0.1:{stub.server_address[1]}",
        "DP_PORT": port,
        "BIND_IP": "127.0.0.1",
    }
    if sizes["stream_bytes"]:
        extra["KMAMIZ_INGEST_STREAM_BYTES"] = sizes["stream_bytes"]
    child = Child(
        "phaseA_dp_server",
        [sys.executable, "-m", "kmamiz_tpu.server.dp_server"],
        child_env(platform, extra),
        ctx["out_dir"],
    )
    base = f"http://127.0.0.1:{port}"
    out = {}
    try:
        t0 = clock.elapsed()
        health = wait_ready(child, base + "/", clock, 600.0)
        out["prewarm"] = check_prewarm(health, "DP")
        out["bootObservedS"] = round(clock.elapsed() - t0, 1)

        # four bodies over the streaming threshold: ingest_raw_stream
        t0 = clock.elapsed()
        spans = traces = 0
        out["ingest"] = []
        for i, body in enumerate(ctx["history"]):
            status, summary = http(
                "POST", base + "/ingest", body, clock.budget(400.0)
            )
            child.check_alive()
            check(status == 200, f"POST /ingest #{i}: {status} {summary}")
            check(
                summary.get("spans", 0) > 0 and summary.get("traces", 0) > 0,
                f"POST /ingest #{i} ingested nothing: {summary}",
            )
            check(
                summary.get("quarantined", 0) == 0,
                f"POST /ingest #{i} quarantined chunks: {summary}",
            )
            check(
                summary.get("chunks", 0) > 1 and "pipeline_depth" in summary,
                f"POST /ingest #{i} ({len(body)} bytes) did not take the "
                f"streaming route: {summary}",
            )
            spans += summary["spans"]
            traces += summary["traces"]
            out["ingest"].append(
                {
                    k: summary[k]
                    for k in ("spans", "traces", "chunks", "endpoints", "edges", "ms")
                }
            )
        want_spans = sizes["history_traces"] * sizes["spans_per"]
        check(
            spans == want_spans and traces == sizes["history_traces"],
            f"ingested {spans} spans / {traces} traces, sent {want_spans} / "
            f"{sizes['history_traces']}",
        )
        last = out["ingest"][-1]
        check(
            last["endpoints"] >= sizes["min_endpoints"]
            and last["edges"] >= sizes["min_edges"],
            f"graph narrower than the configuration: {last}",
        )

        out["ingestObservedS"] = round(clock.elapsed() - t0, 1)

        # three DP-protocol ticks, as the host app sends them
        t0 = clock.elapsed()
        existing = None
        compiles_before_last = None
        for i in range(sizes["ticks"]):
            if i == sizes["ticks"] - 1:
                _, t = http("GET", base + "/timings", timeout=clock.budget(60.0))
                compiles_before_last = t["programs"]["totalCompiles"]
            request = {
                "uniqueId": f"smoke-{i}",
                "lookBack": 30_000,
                "time": int(time.time() * 1000),
                "existingDep": existing,
            }
            status, tick = http(
                "POST", base + "/", json.dumps(request).encode(), clock.budget(400.0)
            )
            child.check_alive()
            check(status == 200, f"tick {i}: {status} {str(tick)[:300]}")
            check(
                "stale" not in tick and "deferred" not in tick,
                f"tick {i} was answered from the last-good payload: "
                f"{ {k: tick[k] for k in tick if k.startswith(('stale', 'deferred'))} }",
            )
            check(tick.get("uniqueId") == request["uniqueId"], f"tick {i}: wrong id")
            check(
                tick.get("combined") and tick.get("dependencies"),
                f"tick {i}: empty combined/dependencies ({tick.get('log')})",
            )
            existing = tick["dependencies"]
        check(not mesh.tick_windows, "the DP never asked Zipkin for a tick window")
        out["ticksObservedS"] = round(clock.elapsed() - t0, 1)

        _, timings = http("GET", base + "/timings", timeout=clock.budget(60.0))
        check(
            timings["programs"]["totalCompiles"] == compiles_before_last,
            f"the last tick compiled "
            f"{timings['programs']['totalCompiles'] - compiles_before_last} "
            f"new program(s)",
        )
        check_device(timings["device"], platform, "DP")
        out["device"] = timings["device"]
        out["path"] = check_path_taken(timings, platform, "DP")
        check(
            program_calls(timings, "graph.merge_edges") > 0,
            "DP: graph.merge_edges never ran (tick merge)",
        )
        check_resilience(timings["resilience"], "DP")
        out["native"] = check_native(timings["native"], "DP")
        out["compileCache"] = check_cache(timings["compileCache"], "DP")
        out["programs"] = program_table(timings)
        out["totalCompiles"] = timings["programs"]["totalCompiles"]

        status, metrics = http("GET", base + "/metrics", timeout=clock.budget(60.0))
        text = metrics.decode() if isinstance(metrics, bytes) else str(metrics)
        check(
            status == 200 and "kmamiz_program_calls_total" in text,
            f"GET /metrics: {status}",
        )
        check(
            "kmamiz_watchdog_trips_total 0" in text,
            "GET /metrics: watchdog trips are not 0",
        )
        status, prof = http(
            "GET", base + "/debug/graftprof", timeout=clock.budget(60.0)
        )
        check(
            status == 200 and prof.get("kind") == "kmamiz-graftprof",
            f"GET /debug/graftprof: {status}",
        )
        status, export = http(
            "GET", base + "/fleet/export", timeout=clock.budget(120.0)
        )
        check(status == 200, f"GET /fleet/export: {status}")
        out["graph"] = {
            "edges": len(export["src"]),
            "endpointsNamed": len(export["names"]),
            "signature": named_signature(export),
        }
        check(
            out["graph"]["edges"] >= sizes["min_edges"],
            f"exported graph has {out['graph']['edges']} edges",
        )
        # each tick's new-path trace landed in the device graph, whole
        names = export["names"]
        out["tickNewEdges"] = []
        for i, want in enumerate(ctx["dp_tick_new_edges"]):
            mark = f"dp{i}-svc"
            got = sum(
                1
                for a, b in zip(export["src"], export["dst"])
                if mark in names[a] and mark in names[b]
            )
            check(got == want, f"tick {i} added {got} of its {want} new edges")
            out["tickNewEdges"].append(got)
        check(
            out["graph"]["edges"] >= last["edges"] + sum(out["tickNewEdges"]),
            f"graph went from {last['edges']} edges to {out['graph']['edges']} "
            f"across ticks that added {sum(out['tickNewEdges'])}",
        )
        if sizes is FULL:
            check(
                out["graph"] == EXPECTED_FULL["graph"],
                f"graph {out['graph']} is not the reference graph "
                f"{EXPECTED_FULL['graph']}",
            )
        check(not mesh.errors, f"stub errors: {mesh.errors}")
        child.terminate_cleanly(clock.budget(60.0))
    finally:
        child.kill()
        stub.shutdown()
        stub.server_close()
    return out


# ---------------------------------------------------------------------------
# phase B — the API server with the in-process processor
# ---------------------------------------------------------------------------


def canonical_scores(kind: str, rows) -> list:
    """A scorer payload in comparable form: the cohesion consumers list
    is emitted in device (sorted) vs host (insertion) order."""
    if kind != "cohesion":
        return rows
    return [
        {
            **row,
            "consumers": sorted(
                row["consumers"], key=lambda c: c["uniqueServiceName"]
            ),
        }
        for row in rows
    ]


def scores_differ(dev, host) -> str:
    """'' when two canonical payloads agree: structure, strings and ints
    exactly, floats to 1e-12 relative (both sides compute float64 ratios
    of the same integers; only the summation order may differ)."""
    import math

    def walk(a, b, where):
        if isinstance(a, bool) or isinstance(b, bool) or a is None or b is None:
            return "" if a == b else f"{where}: {a!r} != {b!r}"
        if isinstance(a, float) or isinstance(b, float):
            if isinstance(a, (int, float)) and isinstance(b, (int, float)):
                if math.isclose(a, b, rel_tol=1e-12, abs_tol=0.0):
                    return ""
            return f"{where}: {a!r} != {b!r}"
        if isinstance(a, dict) and isinstance(b, dict):
            if a.keys() != b.keys():
                return f"{where}: keys {sorted(a)} != {sorted(b)}"
            for k in a:
                diff = walk(a[k], b[k], f"{where}.{k}")
                if diff:
                    return diff
            return ""
        if isinstance(a, list) and isinstance(b, list):
            if len(a) != len(b):
                return f"{where}: {len(a)} rows != {len(b)} rows"
            for i, (x, y) in enumerate(zip(a, b)):
                diff = walk(x, y, f"{where}[{i}]")
                if diff:
                    return diff
            return ""
        return "" if a == b else f"{where}: {a!r} != {b!r}"

    return walk(dev, host, "payload")


def payload_hash(rows) -> str:
    """Hash of a scorer payload with floats rounded to 12 significant
    digits (so a last-bit summation-order difference does not move it)."""

    def norm(x):
        if isinstance(x, float):
            return float(f"{x:.12g}")
        if isinstance(x, dict):
            return {k: norm(v) for k, v in sorted(x.items())}
        if isinstance(x, list):
            return [norm(v) for v in x]
        return x

    blob = json.dumps(norm(rows), sort_keys=True, separators=(",", ":"))
    return hashlib.sha256(blob.encode()).hexdigest()


def phase_api(ctx) -> dict:
    sizes, clock, platform = ctx["sizes"], ctx["clock"], ctx["platform"]
    mesh = StubMesh(ctx["history"], ctx["api_tick_windows"], ctx["namespaces"])
    stub = ThreadingHTTPServer(("127.0.0.1", 0), make_stub_handler(mesh))
    threading.Thread(target=stub.serve_forever, daemon=True).start()
    stub_url = f"http://127.0.0.1:{stub.server_address[1]}"
    port = free_port()
    extra = {
        "ZIPKIN_URL": stub_url,
        "KUBEAPI_HOST": stub_url,
        "PORT": port,
        "STORAGE_URI": "memory://",
        "EXTERNAL_DATA_PROCESSOR": "",
    }
    if sizes["realtime_interval"]:
        extra["REALTIME_INTERVAL"] = sizes["realtime_interval"]
    child = Child(
        "phaseB_api_server",
        [sys.executable, "-m", "kmamiz_tpu.api.app"],
        child_env(platform, extra),
        ctx["out_dir"],
    )
    base = f"http://127.0.0.1:{port}/api/v1"
    out = {}
    try:
        t0 = clock.elapsed()
        health = wait_ready(child, base + "/health", clock, 800.0)
        out["prewarm"] = check_prewarm(health, "API")
        out["bootObservedS"] = round(clock.elapsed() - t0, 1)
        check_device(health["device"], platform, "API")
        out["device"] = health["device"]
        check(
            mesh.page_requests == len(ctx["history"]),
            f"first-time setup fetched {mesh.page_requests} backfill pages, "
            f"not {len(ctx['history'])} (processor.ingest_from_zipkin)",
        )

        def graph_sizes() -> dict:
            _, t = http("GET", base + "/health/timings", timeout=clock.budget(60.0))
            return t["graph"]

        before = graph_sizes()
        check(
            before["deviceEdges"] == before["hostEdges"] >= sizes["min_edges"],
            f"after the backfill the device and host graphs differ: {before}",
        )

        # the mesh wakes up: realtime ticks on the cron pick the windows up
        t0 = clock.elapsed()
        with mesh.lock:
            mesh.ticks_enabled = True
        deadline = time.monotonic() + clock.budget(240.0)
        while True:
            child.check_alive()
            with mesh.lock:
                # the job loop is serial: one more (quiet) query after the
                # last window means that window's tick has fully landed
                done = not mesh.tick_windows and mesh.quiet_ticks_after >= 1
            if done:
                break
            check(
                time.monotonic() < deadline,
                f"cron ticks consumed {mesh.ticks_served} of "
                f"{sizes['ticks']} windows:\n{child.log_tail()}",
            )
            time.sleep(0.5)
        out["cronTicks"] = mesh.ticks_served
        out["ticksObservedS"] = round(clock.elapsed() - t0, 1)
        # the ticks grew BOTH graphs, by exactly their new-path edges: the
        # scorer parity below then covers edges a realtime tick merged
        after = graph_sizes()
        want = sum(ctx["api_tick_new_edges"])
        for side in ("deviceEdges", "hostEdges"):
            check(
                after[side] - before[side] == want,
                f"{sizes['ticks']} cron ticks carried {want} new edges; "
                f"{side} went {before[side]} -> {after[side]}",
            )
        out["graph"] = {"beforeTicks": before, "afterTicks": after}

        out["scorers"] = {}
        for kind in ("instability", "coupling", "cohesion"):
            t0 = clock.elapsed()
            status, dev = http(
                "GET", f"{base}/graph/{kind}", timeout=clock.budget(400.0)
            )
            check(status == 200 and dev, f"GET /graph/{kind}: {status}")
            t1 = clock.elapsed()
            status, host = http(
                "GET", f"{base}/graph/{kind}?scorer=host", timeout=clock.budget(400.0)
            )
            check(status == 200 and host, f"GET /graph/{kind}?scorer=host: {status}")
            dev, host = canonical_scores(kind, dev), canonical_scores(kind, host)
            diff = scores_differ(dev, host)
            if diff:  # keep both sides: the message names one field only
                for side, rows in (("device", dev), ("host", host)):
                    path = ctx["out_dir"] / f"scorer_{kind}_{side}.json"
                    path.write_text(json.dumps(rows))
            check(not diff, f"/graph/{kind}: device != host oracle: {diff}")
            out["scorers"][kind] = {
                "rows": len(dev),
                "hash": payload_hash(dev),
                "deviceObservedS": round(t1 - t0, 1),
                "hostObservedS": round(clock.elapsed() - t1, 1),
            }
        check(
            out["scorers"]["instability"]["rows"] >= sizes["n_services"],
            f"scorers cover {out['scorers']['instability']['rows']} services",
        )
        if sizes is FULL:
            got = {k: v["hash"] for k, v in out["scorers"].items()}
            check(
                got == EXPECTED_FULL["scorers"],
                f"scorer payloads {got} are not the reference payloads",
            )

        _, timings = http(
            "GET", base + "/health/timings", timeout=clock.budget(60.0)
        )
        check_resilience(timings["resilience"], "API")
        out["path"] = check_path_taken(timings, platform, "API")
        # ops.scorers.service_scores picks one of two single-device routes
        # from what the store can promise; the mesh has its own program
        device_scorers = (
            ("mesh.sharded_service_scores",)
            if timings["device"].get("mesh") is not None
            else ("scorers.service_scores_sparse", "scorers.service_scores")
        )
        for names in (device_scorers, ("scorers.usage_cohesion",)):
            ran = {n: program_calls(timings, n) for n in names}
            check(
                sum(ran.values()) > 0,
                f"API: {' / '.join(names)} never ran (scorers served from "
                f"the host?)",
            )
            out["path"].update(ran)
        check(
            timings["scorerCache"]["misses"] > 0,
            f"API: device scorer cache never computed: {timings['scorerCache']}",
        )
        out["native"] = check_native(timings["native"], "API")
        out["compileCache"] = check_cache(timings["compileCache"], "API")
        out["programs"] = program_table(timings)
        out["totalCompiles"] = timings["programs"]["totalCompiles"]
        out["memory"] = timings["device"]["memory"]
        check(not mesh.errors, f"stub errors: {mesh.errors}")
        child.terminate_cleanly(clock.budget(120.0))
    finally:
        child.kill()
        stub.shutdown()
        stub.server_close()
    return out


# ---------------------------------------------------------------------------
# phases C and D — in-process children (this file, --child)
# ---------------------------------------------------------------------------


def run_child_phase(ctx, name: str, letter: str, extra_env: dict, want_s: float):
    clock = ctx["clock"]
    child = Child(
        name,
        [
            sys.executable,
            str(ROOT / "chip_smoke.py"),
            "--child",
            letter,
            "--sizes",
            json.dumps(ctx["sizes"]),
        ],
        child_env(ctx["platform"], extra_env),
        ctx["out_dir"],
    )
    try:
        try:
            rc = child.proc.wait(timeout=clock.budget(want_s))
        except subprocess.TimeoutExpired:
            raise SmokeFailure(
                f"{name} still running after {want_s:.0f}s:\n{child.log_tail()}"
            ) from None
        check(rc == 0, f"{name} exited with code {rc}:\n{child.log_tail()}")
        last = child.log_tail(1 << 20).strip().splitlines()[-1]
        out = json.loads(last)
    finally:
        child.kill()
    check_device(out["device"], ctx["platform"], name)
    return out


def phase_models(ctx) -> dict:
    return run_child_phase(ctx, "phaseC_models", "C", {}, 600.0)


def phase_kernels(ctx) -> dict:
    return run_child_phase(ctx, "phaseD_kernels", "D", {}, 500.0)


def _child_common():
    """Shared head of the in-process children: cache on, device block."""
    from kmamiz_tpu.core import compile_cache

    compile_cache.enable()
    from kmamiz_tpu.telemetry import device as tel_device

    return tel_device.device_block()


def _child_tail(out: dict) -> None:
    from kmamiz_tpu.core import compile_cache, programs
    from kmamiz_tpu.telemetry import device as tel_device

    summary = programs.summary()
    out["programs"] = program_table({"programs": summary})
    out["totalCompiles"] = summary["totalCompiles"]
    out["compileCache"] = compile_cache.stats()
    out["memory"] = tel_device.local_memory_stats()
    print(json.dumps(out), flush=True)


def child_models(sizes: dict) -> None:
    """Phase C in the child that holds the chip: models.trainer.train —
    the entry tools/eval_models_large.py drives — on the config-5 graph."""
    import jax
    import numpy as np

    out = {"device": _child_common()}
    from kmamiz_tpu.models import checkpoint as ckpt
    from kmamiz_tpu.models import graphsage, serving, trainer
    from kmamiz_tpu.parallel import mesh as pmesh

    s = sizes["sage"]
    n, e, slots, hidden = s["nodes"], s["edges"], s["slots"], s["hidden"]
    rng = np.random.default_rng(11)
    dataset = trainer.GraphDataset(
        endpoint_names=[f"ep{i}" for i in range(n)],
        src=jax.device_put(rng.integers(0, n, e, dtype=np.int32)),
        dst=jax.device_put(rng.integers(0, n, e, dtype=np.int32)),
        edge_mask=jax.device_put(np.ones(e, dtype=bool)),
        features=[
            jax.device_put(
                rng.normal(size=(n, graphsage.NUM_FEATURES)).astype(np.float32)
            )
            for _ in range(slots)
        ],
        target_latency=[
            jax.device_put(rng.normal(size=n).astype(np.float32))
            for _ in range(slots)
        ],
        target_anomaly=[
            jax.device_put((rng.random(n) < 0.1).astype(np.float32))
            for _ in range(slots)
        ],
        node_mask=[jax.device_put(rng.random(n) < 0.95) for _ in range(slots)],
        slot_keys=[f"s{i}" for i in range(slots)],
    )

    n_dev = len(jax.devices())
    mesh = None
    train_kw = {}
    if n_dev > 1:
        # the only multi-chip training path: slot microbatches sharded
        # over the mesh (parallel/mesh.make_sharded_slot_grad)
        check(slots % n_dev == 0, f"{slots} slots do not shard over {n_dev}")
        mesh = pmesh.make_mesh(n_dev, axis="slots")
        train_kw = {"mesh": mesh, "batch_slots": n_dev}
    out["mesh"] = None if mesh is None else dict(mesh.shape)

    with tempfile.TemporaryDirectory(prefix="kmamiz-smoke-ckpt-") as ckpt_dir:
        # two epoch blocks of two epochs, a checkpoint after each
        first = trainer.train(
            dataset,
            epochs=4,
            hidden=hidden,
            checkpoint_dir=ckpt_dir,
            checkpoint_every=2,
            **train_kw,
        )
        check(len(first.losses) == 4, f"4 epochs gave {len(first.losses)} losses")
        check(
            all(np.isfinite(first.losses)), f"training losses: {first.losses}"
        )
        check(ckpt.latest_complete_step(ckpt_dir) == 4, "no checkpoint at step 4")
        # a second run RESTORES step 4 and takes one more block
        second = trainer.train(
            dataset,
            epochs=6,
            hidden=hidden,
            checkpoint_dir=ckpt_dir,
            checkpoint_every=2,
            **train_kw,
        )
        check(
            len(second.losses) == 2,
            f"resume from step 4 to 6 ran {len(second.losses)} epochs",
        )
        check(all(np.isfinite(second.losses)), f"resumed losses: {second.losses}")
        template = graphsage.init_params(
            jax.random.PRNGKey(0), hidden=hidden,
            num_features=graphsage.NUM_FEATURES,
        )
        restored = ckpt.restore_checkpoint(
            ckpt_dir, template, graphsage.make_optimizer(1e-2).init(template)
        )
        check(restored is not None, "checkpoint did not restore")
        params, _opt_state, meta = restored
        check(meta.get("step") == 6, f"restored step {meta.get('step')}, not 6")
        for a, b in zip(
            jax.tree_util.tree_leaves(params),
            jax.tree_util.tree_leaves(second.params),
        ):
            check(
                np.array_equal(np.asarray(a), np.asarray(b)),
                "restored params differ from the params that were saved",
            )
    out["losses"] = [round(float(x), 6) for x in first.losses + second.losses]

    # the served forward answers from the restored params, and agrees with
    # the plain model forward on the same inputs
    feats = np.asarray(dataset.features[0])
    src, dst = np.asarray(dataset.src), np.asarray(dataset.dst)
    mask = np.asarray(dataset.edge_mask)
    lat_ms, prob = serving.forecast_forward(
        params, feats, src, dst, mask, graphsage
    )
    check(lat_ms.shape == (n,) and prob.shape == (n,), "forecast shape")
    check(
        bool(np.isfinite(lat_ms).all() and np.isfinite(prob).all()),
        "forecast_forward returned non-finite values",
    )
    ref_lat, ref_logit = graphsage.forward(
        params, dataset.features[0], dataset.src, dataset.dst, dataset.edge_mask
    )
    np.testing.assert_allclose(
        lat_ms, np.expm1(np.asarray(ref_lat)), rtol=1e-4, atol=1e-4
    )
    np.testing.assert_allclose(
        prob, 1.0 / (1.0 + np.exp(-np.asarray(ref_logit))), rtol=1e-4, atol=1e-5
    )

    if mesh is not None:
        # ROADMAP D0 on real devices: the sharded slot gradient equals the
        # single-device weighted mean, padded (zero-weight) slots included
        from kmamiz_tpu.models import common, stacked

        grad_fn = jax.value_and_grad(
            common.make_loss_fn(graphsage.forward, 3.0), has_aux=True
        )
        st = stacked.stack_dataset(dataset)
        feats_b, tl, ta, nm, w = stacked.batch_slots_arrays(st, n_dev)
        w0 = w[0].at[n_dev - 1].set(0.0)  # one padded slot in the batch
        got, got_loss, _, _ = pmesh.make_sharded_slot_grad(
            mesh, grad_fn, axis="slots"
        )(params, feats_b[0], tl[0], ta[0], nm[0], st.src, st.dst, st.edge_mask, w0)
        grads, losses = [], []
        for i in range(n_dev - 1):
            (loss, _aux), g = grad_fn(
                params, feats_b[0][i], st.src, st.dst, st.edge_mask,
                tl[0][i], ta[0][i], nm[0][i],
            )
            grads.append(g)
            losses.append(float(loss))
        want = jax.tree_util.tree_map(lambda *xs: sum(xs) / (n_dev - 1), *grads)
        for a, b in zip(
            jax.tree_util.tree_leaves(got), jax.tree_util.tree_leaves(want)
        ):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-6
            )
        np.testing.assert_allclose(
            float(got_loss), sum(losses) / (n_dev - 1), rtol=1e-4
        )
        out["shardedSlotGradParity"] = True
    _child_tail(out)


def child_kernels(sizes: dict) -> None:
    """Phase D in the child that holds the chip: segment_stats_matmul
    through window_stats, and the planned neighbour sum and the planned
    attention of the refresh, against the XLA path, at the tolerances
    tests/test_ops_window.py, tests/test_edge_plan.py and
    tests/test_planned_attention.py pin."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    out = {"device": _child_common()}
    from kmamiz_tpu.ops import sparse, window

    on_tpu = jax.default_backend() == "tpu"

    def mosaic(lowered) -> bool:
        return "tpu_custom_call" in lowered.as_text()

    # -- segment_stats_matmul through window_stats at the tick shape --------
    st = sizes["stats"]
    rng = np.random.default_rng(0)
    n = st["records"]
    stats_in = dict(
        endpoint_id=jnp.asarray(rng.integers(0, st["endpoints"], n, dtype=np.int32)),
        status_id=jnp.asarray(rng.integers(0, st["statuses"], n, dtype=np.int32)),
        status_class=jnp.asarray(rng.choice([2, 4, 5], n).astype(np.int8)),
        latency_ms=jnp.asarray(rng.gamma(2.0, 50.0, n).astype(np.float32)),
        timestamp_rel=jnp.asarray(rng.integers(0, 30_000_000, n, dtype=np.int32)),
        valid_server=jnp.asarray(rng.random(n) < 0.9),
        num_endpoints=st["endpoints"],
        num_statuses=st["statuses"],
    )
    stats_backend = "pallas" if on_tpu else "pallas_interpret"
    check(
        mosaic(window.window_stats.lower(**stats_in, backend=stats_backend))
        == on_tpu,
        "window_stats(backend='pallas') holds no Mosaic kernel on this TPU"
        if on_tpu
        else "interpret-mode window_stats lowered to a Mosaic kernel",
    )
    xla = window.window_stats(**stats_in, backend="xla")
    pal = window.window_stats(**stats_in, backend=stats_backend)
    for field in ("count", "error_4xx", "error_5xx", "latest_timestamp_rel"):
        np.testing.assert_array_equal(
            np.asarray(getattr(xla, field)), np.asarray(getattr(pal, field)), field
        )
    np.testing.assert_allclose(
        np.asarray(xla.latency_mean), np.asarray(pal.latency_mean), rtol=1e-5
    )
    np.testing.assert_allclose(
        np.asarray(xla.latency_cv), np.asarray(pal.latency_cv), rtol=1e-4, atol=1e-6
    )
    out["segment_stats_matmul"] = "mosaic" if on_tpu else "interpret"

    # -- an edge list at config 3 and its plan --------------------------------
    pz = sizes["plan"]
    nodes, edges, feat = pz["nodes"], pz["edges"], pz["feat"]
    rng = np.random.default_rng(2)
    h = jnp.asarray(rng.normal(size=(nodes, feat)).astype(np.float32))
    src = jnp.asarray(rng.integers(0, nodes, edges).astype(np.int32))
    dst = jnp.asarray(rng.integers(0, nodes, edges).astype(np.int32))
    mask = jnp.asarray(rng.random(edges) < 0.8)

    # -- the refresh's default since PR 27 and PR 28: the planned neighbour
    # sum and the planned attention over an edge plan, Mosaic against the
    # XLA items, values and one VJP each, at the width `feat` ---------------
    kernel_impl = "pallas" if on_tpu else "pallas_interpret"
    plan = jax.tree_util.tree_map(
        jnp.asarray,
        sparse.build_edge_plan(np.asarray(src), np.asarray(dst), np.asarray(mask), nodes)[0],
    )
    s_t = jnp.asarray(rng.normal(size=(2, nodes, 2)).astype(np.float32))
    ct = jnp.asarray(rng.normal(size=(nodes, feat)).astype(np.float32))

    def planned(impl):
        total, pull_sum = jax.vjp(lambda x: sparse.planned_neighbor_sum(plan, x, impl), h)
        att, pull_att = jax.vjp(
            lambda x, s, t: sparse.planned_attention(plan, x, s, t, 0.2, impl),
            h, s_t[0], s_t[1],
        )
        return [np.asarray(a) for a in (total, *pull_sum(ct), att, *pull_att(ct))]

    check(
        sparse.planned_impl() == ("pallas" if on_tpu else "xla"),
        f"planned_impl() is {sparse.planned_impl()!r} on {jax.default_backend()}",
    )
    check(
        mosaic(
            jax.jit(jax.grad(lambda x: sparse.planned_neighbor_sum(plan, x).sum())).lower(h)
        ) == on_tpu,
        "the planned sum a caller gets holds no Mosaic kernel on this TPU",
    )
    check(
        mosaic(
            jax.jit(
                jax.grad(lambda x: sparse.planned_attention(
                    plan, x, s_t[0], s_t[1], 0.2, kernel_impl).sum())
            ).lower(h)
        ) == on_tpu,
        "the planned attention holds no Mosaic kernel on this TPU",
    )
    for name, got, want in zip(
        ("sum", "sum.d_h", "attention", "attention.d_hw", "attention.d_s", "attention.d_t"),
        planned(kernel_impl), planned("xla"),
    ):
        scale = max(float(np.abs(want).max()), 1.0)
        np.testing.assert_allclose(got, want, rtol=0, atol=5e-5 * scale, err_msg=name)
    out["planned_neighbor_sum"] = out["planned_attention"] = (
        "mosaic" if on_tpu else "interpret"
    )

    # -- the attention at a width whose spare lanes straddle the first 128:
    # 124 floats and the neighbour's eight scalars make 256-lane rows, so
    # `_max`'s ring holds 256-lane blocks and the scalars are rows 124..131
    # of the transposed block (PR 31). All five walks against the XLA items
    wide = jnp.asarray(rng.normal(size=(nodes, 124)).astype(np.float32))
    ct_wide = jnp.asarray(rng.normal(size=(nodes, 124)).astype(np.float32))

    def attention_wide(impl):
        att, pull = jax.vjp(
            lambda x, s, t: sparse.planned_attention(plan, x, s, t, 0.2, impl),
            wide, s_t[0], s_t[1],
        )
        return [np.asarray(a) for a in (att, *pull(ct_wide))]

    for name, got, want in zip(
        ("attention.w124", "attention.w124.d_hw", "attention.w124.d_s", "attention.w124.d_t"),
        attention_wide(kernel_impl), attention_wide("xla"),
    ):
        scale = max(float(np.abs(want).max()), 1.0)
        np.testing.assert_allclose(got, want, rtol=0, atol=5e-5 * scale, err_msg=name)
    out["planned_attention_w124"] = out["planned_attention"]

    # -- STLGT's gated neighbour sum over the same plan (PR 33): both walks,
    # value and the gradients to q, k, v and b_edge, against the XLA items
    from kmamiz_tpu.ops import sparse_gated

    qkv = jnp.asarray(rng.normal(size=(3, nodes, feat)).astype(np.float32))
    b_edge = jnp.asarray([0.3], jnp.float32)

    def gated(impl):
        bias, pull = jax.vjp(
            lambda q, k, v, b: sparse_gated.planned_gated_sum(plan, q, k, v, b, impl),
            qkv[0], qkv[1], qkv[2], b_edge,
        )
        return [np.asarray(a) for a in (bias, *pull(ct))]

    for name, got, want in zip(
        ("gated", "gated.d_q", "gated.d_k", "gated.d_v", "gated.d_b"),
        gated(kernel_impl), gated("xla"),
    ):
        scale = max(float(np.abs(want).max()), 1.0)
        np.testing.assert_allclose(got, want, rtol=0, atol=5e-5 * scale, err_msg=name)
    out["planned_gated_sum"] = out["planned_attention"]

    # -- PNA's multi-aggregate over the same plan (PR 39): the three walks,
    # the four aggregates and the gradient to the message rows (a shared
    # maximum's split equally), against the XLA items; an extreme to the bit
    from kmamiz_tpu.ops import sparse_pna

    def aggregate(impl):
        tables, pull = jax.vjp(lambda m: sparse_pna.planned_aggregate(plan, m, impl), qkv[0])
        return [np.asarray(a) for a in (*tables, *pull((ct, ct, ct, ct)))]

    for name, got, want in zip(
        ("aggregate.sum", "aggregate.squares", "aggregate.max", "aggregate.min", "aggregate.d_m"),
        aggregate(kernel_impl), aggregate("xla"),
    ):
        if name in ("aggregate.max", "aggregate.min"):
            np.testing.assert_array_equal(got, want, name)
            continue
        scale = max(float(np.abs(want).max()), 1.0)
        np.testing.assert_allclose(got, want, rtol=0, atol=5e-5 * scale, err_msg=name)
    out["planned_aggregate"] = out["planned_attention"]

    # -- the width the epoch block's slot group sums at (PR 29): seven slots'
    # 18 features side by side. Mosaic against the XLA items, and every
    # slot's columns against the kernel's sum of that slot alone, bit for bit
    slots = rng.normal(size=(7, nodes, 18)).astype(np.float32)
    table = jnp.asarray(np.moveaxis(slots, 0, 1).reshape(nodes, 126))
    packed = np.asarray(sparse.planned_neighbor_sum(plan, table, kernel_impl))
    want = np.asarray(sparse.planned_neighbor_sum(plan, table, "xla"))
    scale = max(float(np.abs(want).max()), 1.0)
    np.testing.assert_allclose(packed, want, rtol=0, atol=5e-5 * scale, err_msg="sum.w126")
    for j in range(7):
        alone = sparse.planned_neighbor_sum(plan, jnp.asarray(slots[j]), kernel_impl)
        np.testing.assert_array_equal(
            packed[:, j * 18 : (j + 1) * 18], np.asarray(alone), f"slot {j} of the packed sum"
        )
    out["planned_neighbor_sum_w126"] = out["planned_neighbor_sum"]
    out["routes"] = sparse.route_stats()
    _child_tail(out)


# ---------------------------------------------------------------------------
# the parent
# ---------------------------------------------------------------------------

PHASES = {
    "A": ("external DP server", phase_dp),
    "B": ("API server, device scorers vs host oracle", phase_api),
    "C": ("GraphSAGE trainer, checkpoint, forecast", phase_models),
    "D": ("Pallas kernels vs XLA", phase_kernels),
}


def new_path_trace(synth, sizes: dict, shape: dict, t: int, tag: str):
    """One trace through services nothing else names: synth's chain `t`
    with every `svcN` renamed `<tag>-svcN`. All of its endpoints and
    edges are new to the graph, and each endpoint is the SERVER of one
    span only, which is what the host's combineWith needs to keep them
    all (module docstring). Returns (a one-group JSON array, the number
    of edges it adds)."""
    raw = synth.make_raw_window(
        1, sizes["spans_per"], t_start=t, trace_prefix=f"{tag}-", **shape
    )
    raw = re.sub(rb"svc(\d+)", tag.encode() + rb"-svc\1", raw)
    servers = [
        (span["localEndpoint"]["serviceName"], span["tags"]["http.url"])
        for span in json.loads(raw)[0]
        if span["kind"] == "SERVER"
    ]
    check(
        len(servers) > 1 and len(set(servers)) == len(servers),
        f"trace {t} repeats a SERVER endpoint: {servers}",
    )
    # a chain: each SERVER span depends on every SERVER span above it
    return raw, len(servers) * (len(servers) - 1) // 2


def build_inputs(sizes: dict) -> dict:
    """The seeded mesh traffic every phase reads (JAX-free)."""
    from kmamiz_tpu import synth

    shape = dict(
        n_services=sizes["n_services"], urls_per_service=sizes["urls_per_service"]
    )
    per_body = sizes["history_traces"] // sizes["bodies"]
    history = [
        synth.make_raw_window(
            per_body,
            sizes["spans_per"],
            t_start=i * per_body,
            trace_prefix=f"h{i}-",
            **shape,
        )
        for i in range(sizes["bodies"])
    ]
    tick = sizes["tick_traces"]

    def tick_windows(tag: str, t_first: int):
        """`ticks` windows of `tick` traces under fresh trace ids: synth
        traces from `t_first` on, the last one a new_path_trace."""
        windows, new_edges = [], []
        for i in range(sizes["ticks"]):
            fresh, n_new = new_path_trace(
                synth, sizes, shape, 2 * sizes["history_traces"] + i, f"{tag}{i}"
            )
            seen = synth.make_raw_window(
                tick - 1,
                sizes["spans_per"],
                t_start=t_first + i * tick,
                trace_prefix=f"{tag}{i}-",
                **shape,
            )
            windows.append(join_json_arrays([seen, fresh]))
            new_edges.append(n_new)
        return windows, new_edges

    # the API's windows re-observe history paths (module docstring). The
    # DP's start beyond the history: new paths at --tiny, re-observed ones
    # at the full size, whose history already spans the generator's period
    dp_ticks, dp_new = tick_windows("dp", sizes["history_traces"])
    api_ticks, api_new = tick_windows("api", 0)
    return {
        "history": history,
        "dp_tick_windows": dp_ticks,
        "dp_tick_new_edges": dp_new,
        "api_tick_windows": api_ticks,
        "api_tick_new_edges": api_new,
        "namespaces": [f"ns{i}" for i in range(8)],
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--phases", default="ABCD", help="subset to run, e.g. AD")
    ap.add_argument(
        "--out", default=str(ROOT / "chiprun_out"), help="report directory"
    )
    ap.add_argument(
        "--tiny",
        action="store_true",
        help="TEST ONLY: tiny sizes, on the caller's JAX_PLATFORMS "
        "(tests: cpu, Pallas interpreted)",
    )
    ap.add_argument(
        "--deadline",
        type=float,
        default=DEADLINE_S,
        help="seconds the whole run may take (default: the one-chip contract)",
    )
    ap.add_argument("--child", choices=("C", "D"), help=argparse.SUPPRESS)
    ap.add_argument("--sizes", help=argparse.SUPPRESS)
    args = ap.parse_args(argv)

    if args.child:  # the process that holds the chip for phase C or D
        {"C": child_models, "D": child_kernels}[args.child](json.loads(args.sizes))
        return 0

    sizes = TINY if args.tiny else FULL
    # the chip contract pins the platform; only the test-only size takes
    # the caller's (tests run it with JAX_PLATFORMS=cpu)
    platform = os.environ.get("JAX_PLATFORMS", "tpu") if args.tiny else "tpu"
    out_dir = Path(args.out)
    out_dir.mkdir(parents=True, exist_ok=True)
    clock = Clock(args.deadline)
    report = {"sizes": "tiny" if args.tiny else "full", "phases": {}}
    try:
        inputs = build_inputs(sizes)
    except ImportError as err:
        print(
            f"[chip_smoke] not in a kmamiz-tpu checkout ({err}): nothing to smoke",
            file=sys.stderr,
        )
        return 2
    ctx = {
        "sizes": sizes,
        "platform": platform,
        "clock": clock,
        "out_dir": out_dir,
        **inputs,
    }
    report["inputBuildObservedS"] = round(clock.elapsed(), 1)

    failed = []
    for letter in args.phases:
        title, fn = PHASES[letter]
        print(f"[chip_smoke] phase {letter}: {title}", file=sys.stderr, flush=True)
        t0 = clock.elapsed()
        try:
            result = fn(ctx)
            result["passed"] = True
        except Exception as err:  # noqa: BLE001 - recorded; the run exits non-zero
            result = {
                "passed": False,
                "error": f"{type(err).__name__}: {err}",
                "traceback": traceback.format_exc(),
            }
            failed.append(letter)
            print(
                f"[chip_smoke] phase {letter} FAILED: {result['error']}",
                file=sys.stderr,
                flush=True,
            )
        result["observedS"] = round(clock.elapsed() - t0, 1)
        report["phases"][letter] = result
        if "device" in result and "device" not in report:
            report["device"] = result["device"]
        if not result["passed"] and "device" not in report:
            break  # no phase has reached a device: there is none to smoke

    report["ok"] = not failed and len(report["phases"]) == len(args.phases)
    report["observedS"] = round(clock.elapsed(), 1)
    (out_dir / "chip_smoke_report.json").write_text(json.dumps(report, indent=1))
    if not report["ok"]:
        print(
            f"[chip_smoke] FAILED (phases {''.join(failed)}); report in "
            f"{out_dir / 'chip_smoke_report.json'}",
            file=sys.stderr,
        )
        return 1
    summary = {
        letter: {
            "observedS": r["observedS"],
            "totalCompiles": r.get("totalCompiles"),
            "cacheHits": (r.get("compileCache") or {}).get("hits"),
            "cacheMisses": (r.get("compileCache") or {}).get("misses"),
        }
        for letter, r in report["phases"].items()
    }
    print(json.dumps({"chip_smoke": summary, "versions": {
        k: report["device"].get(k) for k in ("jax", "jaxlib", "libtpu")
    }}))
    device = report["device"]
    print(
        json.dumps(
            {
                "ok": True,
                "device": {
                    "platform": device["platform"],
                    "kind": device["device_kind"],
                    "count": device["count"],
                },
            }
        )
    )
    return 0


if __name__ == "__main__":
    sys.exit(main())
