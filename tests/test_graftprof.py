"""graftprof (kmamiz_tpu/telemetry/profiling/): host event ring, native
counter parity, SLO-breach flight recorder, attribution report + diff
gate, the HTTP surface, and the warm transfer-guarded tick with the
profiler on.

The report/diff tests run on synthetic event rows (deterministic math);
the native and scenario tests gate on the extension like the rest of
the closed-loop suite.
"""
import json
import os
import urllib.error
import urllib.request

import pytest

from kmamiz_tpu import native
from kmamiz_tpu.analysis import guards
from kmamiz_tpu.telemetry.profiling import (
    events,
    native_counters,
    recorder,
    report,
)
from kmamiz_tpu.telemetry.tracing import TRACER


MS = 1_000_000  # ns per ms — event durations are nanoseconds


def _tick(phases, root="dp-tick", root_ms=10.0):
    """Drive one synthetic tick through the live ring."""
    events.note_tick_start()
    for name, ms in phases:
        events.emit(name, int(ms * MS))
    events.note_tick_end(root, int(root_ms * MS))


def _rows(ticks, phases, root_ms=10.0):
    """Synthetic event rows (name, tick, end_ns, dur_ns) for build_profile."""
    rows = []
    for t in range(1, ticks + 1):
        for i, (name, ms) in enumerate(phases):
            rows.append((name, t, t * 1000 + i, int(ms * MS)))
        rows.append(("dp-tick", t, t * 1000 + 999, int(root_ms * MS)))
    return rows


class TestEventRing:
    def test_emit_snapshot_roundtrip(self):
        _tick([("parse", 2.0), ("merge", 3.0)])
        snap = events.snapshot()
        names = [e[0] for e in snap]
        assert names == ["parse", "merge", "dp-tick"]
        name, tick, end_ns, dur_ns = snap[0]
        assert tick >= 1 and end_ns > 0 and dur_ns == 2 * MS

    def test_last_ticks_window_scopes_to_newest(self):
        for _ in range(3):
            _tick([("parse", 1.0)])
        last = events.snapshot(last_ticks=1)
        assert {e[1] for e in last} == {max(e[1] for e in events.snapshot())}
        assert len(last) == 2  # one phase + one root

    def test_env_gate_drops_events_and_record(self, monkeypatch, tmp_path):
        monkeypatch.setenv("KMAMIZ_PROF", "0")
        monkeypatch.setenv("KMAMIZ_PROF_FLIGHT_DIR", str(tmp_path))
        _tick([("parse", 1.0)])
        assert events.snapshot() == []
        assert recorder.record("watchdog", "gated-off") is None
        assert list(tmp_path.iterdir()) == []

    def test_ring_capacity_floor_and_wrap(self, monkeypatch):
        monkeypatch.setenv("KMAMIZ_PROF_RING", "7")
        events.reset_for_tests()
        assert len(events._ring) == 64  # floor
        for i in range(80):
            events.emit("parse", i)
        assert len(events.snapshot()) == 64  # oldest overwritten, no growth

    def test_phase_p95_absent_is_zero(self):
        assert events.phase_p95_ms("no-such-phase") == 0.0
        _tick([("walk", 4.0)])
        assert events.phase_p95_ms("walk") == pytest.approx(4.0, abs=1e-6)


class TestNativeCounters:
    def test_python_fallback_zeros_never_raises(self, monkeypatch):
        monkeypatch.setattr(native, "_load", lambda: None)
        snap = native_counters.counters()
        assert snap["available"] is False
        for key in ("parses", "spans", "merge_ns", "merge_lock_wait_ns",
                    "merge_queue_depth_peak", "claim_contended",
                    "intern_probes", "intern_hits"):
            assert snap[key] == 0
        assert snap["shards"] == []
        native_counters.poll(1)  # must not raise, must not emit
        assert events.snapshot() == []

    def test_native_parity_after_real_parse(self):
        if not native.available():
            pytest.skip("native extension unavailable")
        from kmamiz_tpu.server.processor import DataProcessor
        from kmamiz_tpu.synth import make_raw_window

        native.prof_reset()
        dp = DataProcessor(trace_source=lambda lb, t, lim: [])
        events.note_tick_start()
        dp.ingest_raw_window(make_raw_window(40, 4, t_start=0))
        snap = native_counters.counters()
        assert snap["available"] is True
        assert snap["parses"] >= 1
        assert snap["spans"] > 0
        assert len(snap["shards"]) == snap["shards_used"]
        # the per-tick delta hook lands the merge wall in the ring
        native_counters.poll(events._cur_tick)
        names = {e[0] for e in events.snapshot()}
        assert "native-merge" in names


class TestFlightRecorder:
    @pytest.fixture(autouse=True)
    def _flight_dir(self, monkeypatch, tmp_path):
        monkeypatch.setenv("KMAMIZ_PROF_FLIGHT_DIR", str(tmp_path))
        self.flight = tmp_path

    def test_artifact_well_formed_and_condensable(self):
        _tick([("parse", 2.0), ("merge", 5.0)])
        path = recorder.record("watchdog", "tick-overrun", force=True)
        assert path is not None and os.path.exists(path)
        doc = json.loads(open(path).read())
        assert doc["kind"] == recorder.ARTIFACT_KIND == "kmamiz-flight"
        assert doc["version"] == 1
        assert doc["trigger"] == "watchdog"
        assert doc["detail"] == "tick-overrun"
        for key in ("events", "traces", "scorecard", "tenants", "native",
                    "compileLog", "hbmTimeline", "flight_ticks"):
            assert key in doc, key
        prof = report.from_any(doc)
        assert prof["kind"] == report.PROFILE_KIND
        assert prof["ticks"] == 1
        assert set(prof["phases"]) == {"parse", "merge", "dp-tick"}

    def test_debounce_suppresses_storms_force_bypasses(self, monkeypatch):
        monkeypatch.setenv("KMAMIZ_PROF_FLIGHT_DEBOUNCE_S", "600")
        assert recorder.record("breaker-open", "zipkin") is not None
        assert recorder.record("breaker-open", "zipkin") is None
        assert recorder.record("breaker-open", "zipkin", force=True) is not None

    def test_first_dump_is_not_debounced_on_a_fresh_boot(self, monkeypatch):
        """time.monotonic() counts from boot on Linux: with an uptime under
        the debounce interval the FIRST dump of a namespace must still be
        written (the cold-start breach is what the recorder exists for)."""
        monkeypatch.setenv("KMAMIZ_PROF_FLIGHT_DEBOUNCE_S", "600")
        readings = [3.0, 4.0, 5.0, 700.0]  # one per record() below
        monkeypatch.setattr(
            recorder.time,
            "monotonic",
            lambda: readings.pop(0) if len(readings) > 1 else readings[0],
        )
        assert recorder.record("watchdog", "cold-start") is not None
        assert recorder.record("watchdog", "cold-start") is None
        # another namespace has a debounce clock of its own
        assert recorder.record("watchdog", "cell", namespace="cell-1") is not None
        assert recorder.record("watchdog", "cold-start") is not None  # 700 s

    def test_retention_prunes_to_newest(self, monkeypatch):
        monkeypatch.setenv("KMAMIZ_PROF_FLIGHT_MAX", "2")
        paths = [
            recorder.record("watchdog", f"n{i}", force=True) for i in range(4)
        ]
        assert all(paths)
        kept = sorted(p.name for p in self.flight.glob("flight-*.json"))
        assert len(kept) == 2
        assert kept == sorted(os.path.basename(p) for p in paths[-2:])

    def test_record_never_raises(self, monkeypatch):
        monkeypatch.setattr(
            recorder, "build_artifact",
            lambda *a, **k: (_ for _ in ()).throw(RuntimeError("boom")),
        )
        assert recorder.record("watchdog", "broken", force=True) is None

    def test_seeded_event_stream_condenses_deterministically(self):
        """Same seeded chaos (fixed event script) -> identical artifact
        evidence and identical condensed profile, run to run."""
        import random

        def run():
            events.reset_for_tests()
            rng = random.Random(1234)
            for _ in range(8):
                phases = [
                    (name, rng.randrange(1, 9))
                    for name in ("parse", "merge", "walk")
                ]
                _tick(phases, root_ms=sum(ms for _n, ms in phases) + 1)
            art = recorder.build_artifact("chaos", "seed-1234")
            evidence = [(e[0], e[1], e[3]) for e in art["events"]]
            return evidence, report.from_any(art)

        first_ev, first_prof = run()
        second_ev, second_prof = run()
        assert first_ev == second_ev
        assert first_prof["phases"] == second_prof["phases"]
        assert first_prof["attribution_ratio"] == second_prof["attribution_ratio"]

    def test_watchdog_trip_and_breaker_open_freeze_evidence(self):
        from kmamiz_tpu.resilience import metrics
        from kmamiz_tpu.resilience.breaker import CircuitBreaker

        _tick([("merge", 3.0)])
        metrics.watchdog_tripped("deadline")
        dumps = list(self.flight.glob("flight-*-watchdog.json"))
        assert len(dumps) == 1
        br = CircuitBreaker("zipkin-test", threshold=1, cooldown_s=30)
        br.record_failure()  # trips open -> records (debounced vs above)
        recorder.reset_for_tests()  # clear debounce; prove the trigger fires
        br2 = CircuitBreaker("dp-test", threshold=1, cooldown_s=30)
        br2.record_failure()
        assert list(self.flight.glob("flight-*-breaker-open.json"))


class TestScenarioGateFailure:
    def test_forced_loss_dumps_flight_artifact(self, monkeypatch, tmp_path):
        """A seeded scenario whose gate fails (forced lost spans — the
        tick-stall class of breach) must leave a well-formed flight
        artifact and carry its path on the scorecard."""
        if not native.available():
            pytest.skip("native extension unavailable")
        from kmamiz_tpu.scenarios import runner
        from kmamiz_tpu.scenarios.factory import build_scenario

        monkeypatch.setenv("KMAMIZ_PROF_FLIGHT_DIR", str(tmp_path))
        monkeypatch.setattr(
            runner, "_lost_spans",
            lambda spec, state, procs: (3, ["forced-tick-stall"]),
        )
        spec = build_scenario("steady-chain", 0, 0, 2)
        card = runner.run_scenario(spec)
        assert card["pass"] is False
        assert card["gates"]["zero_lost_spans"] is False
        path = card["flight_artifact"]
        assert path and os.path.exists(path)
        doc = json.loads(open(path).read())
        assert doc["kind"] == "kmamiz-flight"
        assert doc["trigger"] == f"scenario-{spec.name}"
        assert "zero_lost_spans" in doc["detail"]
        assert report.from_any(doc)["kind"] == report.PROFILE_KIND


class TestHTTPSurface:
    @pytest.fixture()
    def server(self):
        from kmamiz_tpu.server.dp_server import DataProcessorServer
        from kmamiz_tpu.server.processor import DataProcessor

        dp = DataProcessor(trace_source=lambda lb, t, lim: [])
        srv = DataProcessorServer(dp, host="127.0.0.1", port=0)
        srv.start()
        yield f"http://127.0.0.1:{srv.port}"
        srv.stop()

    def test_debug_graftprof_serves_live_profile(self, server):
        _tick([("parse", 2.0)])
        doc = json.loads(urllib.request.urlopen(f"{server}/debug/graftprof").read())
        assert doc["kind"] == report.PROFILE_KIND
        assert "parse" in doc["phases"]
        assert "native" in doc and "device" in doc

    def test_debug_profile_busy_is_409(self, server):
        from kmamiz_tpu.core import profiling as core_profiling

        assert core_profiling._trace_guard.acquire(blocking=False)
        try:
            req = urllib.request.Request(
                f"{server}/debug/profile",
                data=json.dumps({"durationMs": 50}).encode(),
                headers={"Content-Type": "application/json"},
            )
            with pytest.raises(urllib.error.HTTPError) as err:
                urllib.request.urlopen(req)
            assert err.value.code == 409
            body = json.loads(err.value.read())
            assert body["busy"] is True and body["ok"] is False
        finally:
            core_profiling._trace_guard.release()

    def test_profile_window_clamped_by_env(self, monkeypatch, tmp_path):
        from kmamiz_tpu.telemetry import device as tel_device

        monkeypatch.setenv("KMAMIZ_PROFILE_MAX_S", "0.002")
        assert tel_device.profile_max_s() == 0.002
        monkeypatch.setenv("KMAMIZ_PROFILE_MAX_S", "-5")
        assert tel_device.profile_max_s() == 0.001  # floor, never zero
        monkeypatch.setenv("KMAMIZ_PROFILE_MAX_S", "garbage")
        assert tel_device.profile_max_s() == 10.0  # default on parse failure
        monkeypatch.setenv("KMAMIZ_PROFILE_MAX_S", "0.01")
        out = tel_device.capture_profile(60_000, str(tmp_path))
        assert out["ok"] is True
        assert out["duration_ms"] == 10  # a fat durationMs cannot pin the device


class TestReportAttribution:
    def test_attribution_math_and_cap(self):
        prof = report.build_profile(
            event_rows=_rows(3, [("parse", 4.0), ("merge", 5.0)], root_ms=10.0),
            native={}, compile_log=[], hbm_timeline=[],
        )
        assert prof["ticks"] == 3
        assert prof["wall_ms"] == pytest.approx(30.0)
        assert prof["attribution_ratio"] == pytest.approx(0.9)
        # nested/overlapping spans can sum past the root: capped per tick
        over = report.build_profile(
            event_rows=_rows(2, [("parse", 8.0), ("merge", 8.0)], root_ms=10.0),
            native={}, compile_log=[], hbm_timeline=[],
        )
        assert over["attribution_ratio"] == 1.0

    def test_native_and_compile_events_not_double_counted(self):
        rows = _rows(1, [("merge", 9.0)], root_ms=10.0)
        rows.append(("native-merge", 1, 5000, int(20.0 * MS)))
        rows.append(("compile", 1, 6000, int(50.0 * MS)))
        prof = report.build_profile(
            event_rows=rows, native={}, compile_log=[], hbm_timeline=[],
        )
        # they overlap host phases, so they inform but never attribute
        assert prof["attribution_ratio"] == pytest.approx(0.9)
        assert "native-merge" in prof["phases"]

    def test_warm_ticks_attribute_majority_of_wall(self):
        """Live integration: warm collect ticks explain most of their
        wall through named phases (the bench's seed-0 run holds >=0.9;
        this in-suite bound is looser to stay timing-robust)."""
        from kmamiz_tpu.server.processor import DataProcessor
        from kmamiz_tpu.synth import make_raw_window

        windows = [
            json.loads(make_raw_window(40, 4, t_start=t)) for t in (0, 5_000)
        ]
        dp = DataProcessor(trace_source=lambda lb, t, lim: windows[0])
        dp.collect({"uniqueId": "warm", "lookBack": 30_000, "time": 1_000})
        events.reset_for_tests()
        dp2 = DataProcessor(trace_source=lambda lb, t, lim: windows[1])
        with TRACER.tick():
            dp2.collect({"uniqueId": "t", "lookBack": 30_000, "time": 6_000})
        prof = report.build_profile()
        assert prof["ticks"] == 1
        assert prof["attribution_ratio"] >= 0.5, prof
        assert "merge" in prof["phases"]

    def test_from_any_rejects_unknown_kind(self):
        with pytest.raises(ValueError, match="unrecognized artifact kind"):
            report.from_any({"kind": "not-a-profile"})
        with pytest.raises(ValueError):
            report.from_any([1, 2, 3])

    def test_render_mentions_phases_and_attribution(self):
        prof = report.build_profile(
            event_rows=_rows(2, [("parse", 4.0)], root_ms=10.0),
            native={"available": True, "parses": 3, "spans": 10,
                    "merge_ns": 5 * MS, "merge_lock_wait_ns": MS,
                    "merge_queue_depth_peak": 2, "claim_contended": 0,
                    "intern_probes": 10, "intern_hits": 4,
                    "shards": [{"parse_ns": MS, "wait_ns": 0, "spans": 5}]},
            compile_log=[], hbm_timeline=[],
        )
        text = report.render(prof)
        assert "parse" in text and "attributed" in text
        assert "shard 0" in text and "lock-wait" in text


class TestDiffGate:
    def _profile(self, merge_ms):
        return report.build_profile(
            event_rows=_rows(4, [("parse", 2.0), ("merge", merge_ms)]),
            native={}, compile_log=[], hbm_timeline=[],
        )

    def test_doctored_candidate_regresses(self):
        base, cand = self._profile(5.0), self._profile(9.0)
        regressions = report.diff(base, cand)
        phases = [r["phase"] for r in regressions]
        assert phases == ["merge"]
        row = regressions[0]
        assert row["candidate_p95_ms"] > row["baseline_p95_ms"]
        assert row["threshold"] == report.DEFAULT_THRESHOLDS["merge"]

    def test_within_threshold_is_quiet(self):
        assert report.diff(self._profile(5.0), self._profile(5.2)) == []

    def test_cli_diff_exits_nonzero_on_regression(self, tmp_path, capsys):
        from tools.graftprof import main

        base, cand = tmp_path / "base.json", tmp_path / "cand.json"
        base.write_text(json.dumps(self._profile(5.0)))
        cand.write_text(json.dumps(self._profile(9.0)))
        assert main(["--diff", str(base), str(cand)]) == 1
        doc = json.loads(capsys.readouterr().out.strip())
        assert [r["phase"] for r in doc["regressions"]] == ["merge"]
        assert main(["--diff", str(base), str(base)]) == 0

    def test_slo_report_gates_prof_keys_per_phase(self):
        import tools.slo_report as slo_report

        for key in ("prof_parse_ms_p95", "prof_merge_lockwait_ms_p95",
                    "prof_transfer_ms_p95", "prof_device_walk_ms_p95"):
            assert key in slo_report.gated_keys()
        base = {"prof_merge_lockwait_ms_p95": 10.0, "prof_parse_ms_p95": 10.0}
        # +40% lock-wait sits under its loose 0.50 bar even though the
        # CLI-wide threshold is 0.10; +40% parse breaches its 0.25 bar
        cand = {"prof_merge_lockwait_ms_p95": 14.0, "prof_parse_ms_p95": 14.0}
        regressions, compared = slo_report.check(cand, base, 0.10)
        assert sorted(compared) == sorted(base)
        assert [k for k, _o, _n in regressions] == ["prof_parse_ms_p95"]


class TestGuardedTickWithProfilerOn:
    def test_warm_guarded_tick_pins_zero_new_compiles(self, monkeypatch):
        """graftprof on (ring + tracer) adds no device work: a warm tick
        under transfer_guard('disallow') still compiles nothing."""
        monkeypatch.setenv("KMAMIZ_MESH", "0")
        monkeypatch.setenv("KMAMIZ_PROF", "1")
        from kmamiz_tpu.server.processor import DataProcessor
        from kmamiz_tpu.synth import make_raw_window

        for seed_t in (0, 10_000):
            window = json.loads(make_raw_window(60, 5, t_start=seed_t))
            dp = DataProcessor(trace_source=lambda lb, t, lim: window)
            with TRACER.tick():
                dp.collect(
                    {"uniqueId": f"warm{seed_t}", "lookBack": 30_000,
                     "time": 1_000_000 + seed_t}
                )

        window = json.loads(make_raw_window(60, 5, t_start=20_000))
        dp = DataProcessor(trace_source=lambda lb, t, lim: window)
        events.reset_for_tests()
        with guards.hot_path_guard("disallow") as guard_report:
            with TRACER.tick():
                dp.collect(
                    {"uniqueId": "guarded", "lookBack": 30_000,
                     "time": 2_000_000}
                )
        assert guard_report.new_compiles == {}, guard_report.new_compiles
        names = {e[0] for e in events.snapshot()}
        assert "dp-tick" in names and "merge" in names
