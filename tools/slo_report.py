"""SLO scorecard report + regression gate over bench artifacts.

Renders the graftscope scorecard keys (telemetry/slo.py) from bench
result JSON, and — with --check — compares a candidate result against
the latest BENCH_r*.json baseline, exiting nonzero when any
higher-is-worse SLO key regresses beyond the threshold. Runnable as a
tier-1-adjacent gate:

    python tools/slo_report.py                     # render latest artifact
    python tools/slo_report.py --check new.json    # gate new vs latest
    python tools/slo_report.py --check             # gate latest vs previous

Artifact shapes accepted: the driver's {cmd, rc, parsed, tail} wrapper
(parsed dict preferred, else the last JSON line found in tail) or a bare
bench.py result object.
"""
from __future__ import annotations

import argparse
import glob
import json
import os
import re
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from kmamiz_tpu.telemetry.profiling.report import (  # noqa: E402
    DEFAULT_THRESHOLDS as _PROF_THRESHOLDS,
)
from kmamiz_tpu.telemetry.slo import SLO_KEYS_HIGHER_IS_WORSE  # noqa: E402

# bench keys gated alongside the scorecard: the tick-latency headline
# pair, the 100k-endpoint refresh (ROADMAP item 2), the tenancy pair —
# the stacked 8-tenant dispatch latency and the join-compile counter (a
# warm-bucket join must stay at zero compiles) — and the scenario-soak
# headline trio (ISSUE 8: worst p99 tick, worst recovery-to-fresh,
# total lost spans across the always-on matrix)
_EXTRA_GATED = (
    "dp_tick_ms_2500_traces",
    "dp_tick_cached_ms",
    "graph_refresh_ms_100k",
    # worst single merge wall across the 100k-endpoint scale section
    # (ISSUE 13): the segment-append growth path must not trade refresh
    # latency for merge-wall regressions
    "graph_merge_wall_ms_100k",
    "tenant_batched_tick_ms_8",
    "tenant_join_compile_count",
    "scenario_worst_p99_tick_ms",
    "scenario_worst_recovery_ms",
    "scenario_lost_spans",
    # graftprof per-phase attribution p95s (bench always emits them,
    # 0.0 when a phase had no samples) — a per-phase regression fails
    # the round even when the headline tick medians stay flat
    "prof_parse_ms_p95",
    "prof_merge_lockwait_ms_p95",
    "prof_transfer_ms_p95",
    "prof_device_walk_ms_p95",
    # sparse flat-gather walk backend (ISSUE 13): its own phase name so
    # --diff compares walk backends instead of folding both into one
    "prof_device_walk_sparse_ms_p95",
    # STLGT continual-model latency pair (ISSUE 10): the per-fold train
    # tick and the served quantile forward behind /model/forecast
    "stlgt_train_tick_ms",
    "stlgt_infer_ms",
    # graftpilot latency pair (ISSUE 11): the fold-boundary decision
    # recompute and the serving-edge admission read (must stay within
    # 3% of dp_tick — bench asserts the ratio, this gates the drift)
    "control_decision_ms",
    "control_tick_overhead_ms",
    # graftcost crossing pair (ROADMAP item 6): the segment crossing
    # wall on a warm store and the prewarm-ON consolidation stall (the
    # A/B's treated arm — it must stay at steady-merge cost, not drift
    # back toward the OFF arm's compile wall)
    "graph_capacity_grow_ms",
    "capacity_growth_stall_ms",
    # graftstream freshness pair (ISSUE 16): the worst arrival->visible
    # p99 across the burst + diurnal curves (also hard-capped below at
    # _FRESHNESS_CEILING_MS) and the graftprof plane's own p99; the
    # steady-recompile count rides the integer slack (one-compile drift
    # already fails)
    "stream_freshness_ms_p99",
    "prof_freshness_ms_p99",
    "stream_steady_recompiles",
    # graftfleet (ROADMAP item 2 / docs/FLEET.md): spans dropped across
    # the bench's live migration — the drain-queue handoff promises
    # zero, so ANY loss is a regression (integer slack already makes
    # one lost span fail)
    "fleet_migration_lost_spans",
    # graftrace (ISSUE 19 / docs/STATIC_ANALYSIS.md): the concurrency
    # lint pass must stay cheap enough to run pre-merge, and findings
    # must stay at ZERO — integer slack already makes one finding fail
    "graftrace_repo_ms",
    "graftrace_findings",
)
# boolean pass/fail keys: any True -> False flip is a regression (bool
# is an int subclass, so the numeric threshold check would wave a
# True -> False transition through as 1.0 -> 0.0 "improvement")
_BOOL_GATED = (
    "scenario_matrix_pass",
    "graph_refresh_pass",
    # the transfer-guarded warm stream must keep compiling NOTHING
    "stream_zero_recompiles_pass",
    # the bench's fleet migration (drain -> WAL handoff -> replay ->
    # ring flip) must keep landing bit-exact with zero loss
    "fleet_migration_pass",
)
# higher-is-BETTER float floors: the numeric check above only catches
# increases, so a coverage collapse would read as an "improvement".
# stlgt_p99_coverage is a [0,1] calibration rate where relative
# thresholds are meaningless near 1.0 — the gate is absolute: new below
# old minus the slack regresses
_FLOOR_GATED = (
    "stlgt_p99_coverage",
    "control_counterfactual_prevented",
    # predictive-prewarm hit rate over the bench A/B's consolidations:
    # a collapse to cold crossings must fail the round even though the
    # numeric check would read 1.0 -> 0.0 as an improvement
    "cost_prewarm_hit_rate",
    # stream-vs-serial wall ratio: the overlap collapsing back to the
    # serial wall reads as a lower number — gate it as a floor
    "stream_vs_batch_speedup",
    # graftfleet scaling pair: the 4-worker aggregate throughput and
    # its per-worker efficiency vs the 1-worker baseline — a scaling
    # collapse reads as lower numbers, so both gate as floors (and the
    # efficiency also has the candidate-local absolute check below,
    # host-core-guarded)
    "fleet_spans_per_sec_4",
    "fleet_scale_efficiency",
    # graftsoak sweep smoke: the mini-sweep's non-poison pass rate and
    # its triaged fraction (every failure must carry a triage blame) —
    # both [0,1] rates where a collapse reads as a lower number, so
    # both gate as floors
    "soak_smoke_pass_rate",
    "soak_triaged_fraction",
)
_ABS_SLACK_FLOOR = 0.02
# absolute slack per key class: rates jitter in the 3rd decimal on tiny
# denominators, recompile counts are integers, latencies get 0.5 ms
_ABS_SLACK_RATE = 0.005
_ABS_SLACK_COUNT = 1.0
_ABS_SLACK_MS = 0.5
# per-phase relative thresholds for the prof_* keys: shared with
# tools/graftprof.py --diff so a phase regresses at the same bar whether
# gated per-round here or artifact-vs-artifact there. Where a phase has
# its own threshold (merge lock-wait jitters most) it overrides the
# CLI-wide --threshold.
_PROF_KEY_PHASE = {
    "prof_parse_ms_p95": "parse",
    "prof_merge_lockwait_ms_p95": "native-merge-lockwait",
    "prof_transfer_ms_p95": "host-transfer",
    "prof_device_walk_ms_p95": "walk",
    "prof_device_walk_sparse_ms_p95": "walk_sparse",
}
# parse thread-scaling gate (ISSUE 12): the t2 merge regression (a
# shared atomic intern table serializing the merge) showed up as
# wall(t2) >> wall(t1) — 4.3x on BENCH_r03 — long before any p95 moved.
# The expected shape is host-dependent, so the artifact's own
# e2e_host_cores picks the check: with real cores, t1 -> t2 -> t4 wall
# must stay monotone NON-INCREASING within jitter slack; on a 1-core
# box extra threads only timeslice (they cannot speed up), so the gate
# instead bounds every tN wall to a fixed multiple of t1 — catching the
# contention collapse while tolerating scheduler overhead. Both checks
# are candidate-local: no baseline needed, so one bad round can never
# become the new baseline.
_SCALING_KEY = "parse_thread_scaling_1core"
_SCALING_REL_SLACK = 0.15  # best-of-2 walls still jitter on a busy box
_SCALING_ABS_SLACK_MS = 2.0
_SCALING_1CORE_FACTOR = 1.5  # timeslice overhead ceiling vs the t1 wall

# graftstream freshness SLO (ISSUE 16): span-arrival -> forecast-visible
# p99 must stay under this ceiling under the burst + diurnal curves.
# Candidate-local and absolute — a slow creep that stays within the
# relative threshold each round must still fail the moment it crosses.
_FRESHNESS_CEILING_MS = 250.0
_FRESHNESS_KEY = "stream_freshness_ms_p99"


def check_freshness_ceiling(result: dict):
    """Violation strings when the candidate's stream freshness p99
    breaches the absolute SLO ([] when healthy or the key is absent —
    a failed bench section emits None, which the driver flags)."""
    p99 = result.get(_FRESHNESS_KEY)
    if not isinstance(p99, (int, float)) or isinstance(p99, bool):
        return []
    if p99 >= _FRESHNESS_CEILING_MS:
        return [
            f"{_FRESHNESS_KEY} breached the absolute SLO: {p99}ms >= "
            f"{_FRESHNESS_CEILING_MS}ms ceiling"
        ]
    return []


# graftfleet scale-out gate (ROADMAP item 2): 4 workers must hold >= 3x
# the single-worker ingest rate, i.e. per-worker efficiency >= 0.75. The
# expected shape is host-dependent exactly like the parse-scaling gate
# above: 4 worker processes on a 1-core box only timeslice (no speedup
# is physically available), so the absolute floor only arms when the
# artifact's own host-core count could seat the workers. The floor-gated
# baseline comparison above still catches relative collapses everywhere.
_FLEET_EFFICIENCY_KEY = "fleet_scale_efficiency"
_FLEET_EFFICIENCY_FLOOR = 0.75
_FLEET_MIN_CORES = 4


def check_fleet_scale(result: dict):
    """Violation strings when the candidate's fleet efficiency misses
    the absolute scale-out floor ([] when healthy, absent — a skipped
    fleet section emits None — or the host cannot seat 4 workers)."""
    cores = result.get("fleet_host_cores", result.get("e2e_host_cores"))
    if not isinstance(cores, int) or cores < _FLEET_MIN_CORES:
        return []
    eff = result.get(_FLEET_EFFICIENCY_KEY)
    if not isinstance(eff, (int, float)) or isinstance(eff, bool):
        return []
    if eff < _FLEET_EFFICIENCY_FLOOR:
        return [
            f"{_FLEET_EFFICIENCY_KEY} below the scale-out floor on a "
            f"{cores}-core host: {eff} < {_FLEET_EFFICIENCY_FLOOR} "
            f"(4-worker aggregate must hold >= 3x one worker)"
        ]
    return []


def check_thread_scaling(result: dict):
    """Violation strings for pathological parse-scaling walls ([] when
    healthy, absent, or fewer than two thread counts recorded)."""
    scaling = result.get(_SCALING_KEY)
    if not isinstance(scaling, dict):
        return []
    walls = []
    for label, row in scaling.items():
        if not (isinstance(label, str) and label[:1] == "t"):
            continue
        try:
            threads = int(label[1:])
            wall = float(row["wall_ms"])
        except (KeyError, TypeError, ValueError):
            return [f"{_SCALING_KEY}[{label}] is malformed: {row!r}"]
        walls.append((threads, wall))
    walls.sort()
    violations = []
    multicore = result.get("e2e_host_cores", 0) and result["e2e_host_cores"] > 1
    if multicore:
        for (t_lo, w_lo), (t_hi, w_hi) in zip(walls, walls[1:]):
            if w_hi > w_lo * (1.0 + _SCALING_REL_SLACK) + _SCALING_ABS_SLACK_MS:
                violations.append(
                    f"{_SCALING_KEY} not monotone: t{t_hi} wall "
                    f"{w_hi}ms > t{t_lo} wall {w_lo}ms (+"
                    f"{(w_hi - w_lo) / max(w_lo, 1e-9) * 100:.0f}%, slack "
                    f"{_SCALING_REL_SLACK * 100:.0f}% + "
                    f"{_SCALING_ABS_SLACK_MS}ms)"
                )
    elif walls:
        _, w_base = walls[0]
        bound = w_base * _SCALING_1CORE_FACTOR + _SCALING_ABS_SLACK_MS
        for t_hi, w_hi in walls[1:]:
            if w_hi > bound:
                violations.append(
                    f"{_SCALING_KEY} contention blowup on 1-core host: "
                    f"t{t_hi} wall {w_hi}ms > {_SCALING_1CORE_FACTOR}x "
                    f"t{walls[0][0]} wall {w_base}ms + "
                    f"{_SCALING_ABS_SLACK_MS}ms"
                )
    return violations


def gated_keys():
    return (
        ["slo_" + k for k in SLO_KEYS_HIGHER_IS_WORSE]
        + list(_EXTRA_GATED)
        + list(_BOOL_GATED)
        + list(_FLOOR_GATED)
    )


def _abs_slack(key: str) -> float:
    if key.endswith("_rate"):
        return _ABS_SLACK_RATE
    if key.endswith("_count"):
        return _ABS_SLACK_COUNT
    return _ABS_SLACK_MS


def _extract_result(doc: dict):
    """Bench result object from either artifact shape."""
    if not isinstance(doc, dict):
        return None
    if "parsed" in doc or "tail" in doc:  # driver wrapper
        if isinstance(doc.get("parsed"), dict):
            return doc["parsed"]
        tail = doc.get("tail") or ""
        for line in reversed(tail.strip().splitlines()):
            line = line.strip()
            if line.startswith("{"):
                try:
                    return json.loads(line)
                except ValueError:
                    return None
        return None
    return doc


def load_result(path: str):
    with open(path) as f:
        return _extract_result(json.load(f))


def find_artifacts(root: str):
    """BENCH_r*.json sorted oldest -> newest by round number."""

    def round_no(p):
        m = re.search(r"BENCH_r(\d+)\.json$", p)
        return int(m.group(1)) if m else -1

    return sorted(glob.glob(os.path.join(root, "BENCH_r*.json")), key=round_no)


def render(result: dict, label: str) -> str:
    lines = [f"SLO scorecard — {label}"]
    for key in gated_keys():
        val = result.get(key)
        lines.append(f"  {key:28s} {val if val is not None else '(absent)'}")
    return "\n".join(lines)


def check(candidate: dict, baseline: dict, threshold: float):
    """(regressions, compared): each regression is (key, old, new)."""
    regressions, compared = [], []
    for key in gated_keys():
        new, old = candidate.get(key), baseline.get(key)
        if not isinstance(new, (int, float)) or not isinstance(
            old, (int, float)
        ):
            continue  # absent on either side: nothing to gate
        compared.append(key)
        if key in _BOOL_GATED:
            if bool(old) and not bool(new):
                regressions.append((key, old, new))
            continue
        if key in _FLOOR_GATED:
            if new < old - _ABS_SLACK_FLOOR:
                regressions.append((key, old, new))
            continue
        rel = threshold
        phase = _PROF_KEY_PHASE.get(key)
        if phase is not None:
            rel = max(
                rel,
                _PROF_THRESHOLDS.get(phase, _PROF_THRESHOLDS["default"]),
            )
        if new > old * (1.0 + rel) + _abs_slack(key):
            regressions.append((key, old, new))
    return regressions, compared


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument(
        "--check",
        nargs="?",
        const="",
        default=None,
        metavar="CANDIDATE_JSON",
        help="gate CANDIDATE (default: latest artifact) against the "
        "previous BENCH_r*.json; exit 1 on any SLO regression",
    )
    ap.add_argument(
        "--threshold",
        type=float,
        default=0.10,
        help="relative regression threshold (default 0.10 = +10%%)",
    )
    ap.add_argument(
        "--root",
        default=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        help="directory holding BENCH_r*.json artifacts",
    )
    args = ap.parse_args(argv)

    artifacts = find_artifacts(args.root)

    def newest_parseable(pool):
        """(result, path, remaining-older-pool) — some rounds' wrappers
        hold only a truncated tail with no JSON line; walk past them."""
        for i in range(len(pool) - 1, -1, -1):
            got = load_result(pool[i])
            if got is not None:
                return got, pool[i], pool[:i]
        return None, None, []

    if args.check is None:
        result, path, _ = newest_parseable(artifacts)
        if result is None:
            print("no parseable BENCH_r*.json artifacts found", file=sys.stderr)
            return 2
        print(render(result, os.path.basename(path)))
        return 0

    # --check: candidate vs the newest parseable artifact strictly before it
    if args.check:
        candidate = load_result(args.check)
        cand_label = args.check
        baseline_pool = artifacts
        if candidate is None:
            print(f"could not parse candidate {cand_label}", file=sys.stderr)
            return 2
    else:
        # gating is strict about the candidate: a null-parsed wrapper is
        # a broken recording, not a skippable round (BENCH_r04/r05 were
        # silently walked past for two PRs) — rerecord it, don't gate
        # around it. Only BASELINE selection may walk past historical
        # unparseable rounds.
        if not artifacts:
            print("no BENCH_r*.json artifacts found", file=sys.stderr)
            return 2
        cand_path = artifacts[-1]
        candidate = load_result(cand_path)
        if candidate is None:
            print(
                f"{os.path.basename(cand_path)}: no parseable bench result "
                '("parsed": null and no JSON line in tail) — re-record the '
                "round instead of gating past it",
                file=sys.stderr,
            )
            return 2
        cand_label = os.path.basename(cand_path)
        baseline_pool = artifacts[:-1]
        if not baseline_pool:
            print("need >=2 artifacts for --check without a candidate")
            return 0
    baseline = None
    base_label = None
    for path in reversed(baseline_pool):
        got = load_result(path)
        if got is not None:
            baseline, base_label = got, os.path.basename(path)
            break
    if baseline is None:
        print("no parseable baseline artifact; nothing to gate")
        return 0

    regressions, compared = check(candidate, baseline, args.threshold)
    # candidate-local invariants, gated regardless of baseline overlap
    scaling_violations = check_thread_scaling(candidate)
    scaling_violations += check_freshness_ceiling(candidate)
    scaling_violations += check_fleet_scale(candidate)
    print(render(candidate, cand_label))
    print(f"baseline: {base_label}; compared {len(compared)} key(s)")
    for msg in scaling_violations:
        print(f"REGRESSION {msg}")
    if not compared:
        print("no overlapping SLO keys (baseline predates graftscope)")
        return 1 if scaling_violations else 0
    for key, old, new in regressions:
        print(
            f"REGRESSION {key}: {old} -> {new} "
            f"({(new - old) / max(abs(old), 1e-9) * 100:+.1f}%, "
            f"threshold {args.threshold * 100:.0f}%)"
        )
    if regressions or scaling_violations:
        return 1
    print("all gated SLO keys within threshold")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
