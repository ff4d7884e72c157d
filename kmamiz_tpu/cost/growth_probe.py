"""Capacity-growth stall probe: one consolidation, prewarm ON vs OFF.

The measured claim behind ROADMAP item 6: with graftcost's predictive
prewarm armed, a segment-store consolidation (graph/store.py: ``valid >
main + tail``) dispatches only warm programs, so the crossing merge
costs the same as any steady-state merge; cold, the same merge eats the
multi-program compile wall. This module drives ONE deterministic edge
ramp across the threshold on a bare ``EndpointGraph`` and reports the
crossing batch's wall time, its program-registry compile delta, and the
final graph signature — run it twice as subprocesses (compile
caches are process-global; an in-process A/B would leak warmth from the
first arm into the second) and compare the signatures, so the A/B
compares identical work (the round-5 bench did).

    python -m kmamiz_tpu.cost.growth_probe --prewarm on
    python -m kmamiz_tpu.cost.growth_probe --prewarm off --capacity 256

prints one JSON line: {"platform", "stall_ms", "steady_ms",
"mid_compiles", "signature", "crossed", "hit", ...}.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from typing import Optional

#: ramp geometry at the default capacity 1024 (+256 tail, threshold
#: 1280): five 300-row batches cross at batch 5 (1500 valid -> 2048
#: main), with the forecast imminent from batch 3 — two clean
#: between-batch prewarm windows before the crossing
DEFAULT_ROWS = 300


def _batches(n_batches: int, rows: int):
    """Globally-distinct (src, dst, dist) int32 triples per batch, so
    the union's dedup never collapses the ramp (the round-5 bench's
    generator idiom). Pure arithmetic — both arms see identical bytes."""
    import numpy as np

    for i in range(n_batches):
        k = np.arange(i * rows, (i + 1) * rows)
        yield (
            (k % 797).astype(np.int32),
            (k // 797).astype(np.int32),
            np.full(rows, 1 + i % 7, dtype=np.int32),
        )


def run_probe(
    prewarm_on: bool,
    capacity: int = 1024,
    rows: Optional[int] = None,
) -> dict:
    """Drive the ramp; return the probe report. Sets the cost-plane env
    knobs for THIS process (the caller isolates arms via subprocesses)."""
    import os

    os.environ["KMAMIZ_COST"] = "1" if prewarm_on else "0"
    os.environ["KMAMIZ_COST_PREWARM"] = "sync"
    from kmamiz_tpu import cost
    from kmamiz_tpu.core import programs
    from kmamiz_tpu.graph.store import EndpointGraph
    from kmamiz_tpu.resilience.chaos import graph_signature

    cost.reset_for_tests()
    gg = EndpointGraph(capacity=capacity, tenant="probe", grow="segment")
    tail = gg.tail_capacity
    threshold = capacity + tail
    rows = rows if rows is not None else max(64, (threshold * 300) // 1280)
    # enough batches to cross once, plus one post-crossing steady batch
    n_batches = threshold // rows + 3

    report = {
        "prewarm": prewarm_on,
        "capacity": capacity,
        "tail": tail,
        "rows": rows,
        "batches": n_batches,
        "stall_ms": None,
        "steady_ms": None,
        "mid_compiles": None,
        "crossed": False,
    }
    walls = []
    for i, (s_b, d_b, ds_b) in enumerate(_batches(n_batches, rows)):
        cap_before = gg.capacity
        snap = programs.snapshot()
        t0 = time.perf_counter()
        gg.merge_edges(s_b, d_b, ds_b)
        cap_after = gg.capacity  # finalize: the consolidation lands here
        wall_ms = (time.perf_counter() - t0) * 1000
        grew = sum(programs.new_compiles_since(snap).values())
        walls.append((wall_ms, grew, cap_before, cap_after))
        if cap_after > cap_before and not report["crossed"]:
            report["crossed"] = True
            report["stall_ms"] = round(wall_ms, 2)
            report["mid_compiles"] = grew
            report["crossing_batch"] = i
            report["to_capacity"] = cap_after
        if prewarm_on:
            cost.run_pending_prewarms()
    # steady cost baseline: the warm batches' median (crossing excluded)
    steady = sorted(
        w for w, _g, cb, ca in walls[1:] if cb == ca
    )
    if steady:
        report["steady_ms"] = round(steady[len(steady) // 2], 2)
    import jax

    report["platform"] = jax.default_backend()
    report["n_edges"] = gg.n_edges
    report["signature"] = graph_signature(gg)
    if prewarm_on:
        snap = cost.snapshot()
        report["hit"] = bool((snap.get("lastCrossing") or {}).get("hit"))
        report["prewarm_rounds"] = snap.get("prewarmRounds", 0)
        report["hit_rate"] = snap.get("hitRate")
    return report


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--prewarm", choices=("on", "off"), required=True)
    ap.add_argument("--capacity", type=int, default=1024)
    ap.add_argument("--rows", type=int, default=None)
    args = ap.parse_args(argv)
    report = run_probe(
        args.prewarm == "on", capacity=args.capacity, rows=args.rows
    )
    print(json.dumps(report, sort_keys=True))
    return 0 if report["crossed"] else 1


if __name__ == "__main__":
    sys.exit(main())
