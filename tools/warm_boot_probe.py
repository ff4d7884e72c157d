"""Restart-warmth probe: boot a fresh process, pre-warm, time the first tick.

Run twice against the same persistent cache directory (the one
core/compile_cache.py resolves: JAX_COMPILATION_CACHE_DIR if set, else
<checkout>/.xla-cache) to measure the production restart story
(VERDICT r4 #5b):

  run 1 (cold cache): the boot prewarm plan pays the real compile walls,
  once, and autosaves the exercised bucket shapes into the shape-hint
  file beside the cache (core/programs.py);
  run 2 (warm cache): the plan replays exactly those hints — populating
  the jit dispatch caches from the persistent XLA cache — and the first
  tick runs with zero compile exposure.

stdout carries ONE JSON line: {"platform": ..., "prewarm_s": ...,
"first_tick_ms": ..., "second_tick_ms": ..., "first_tick_new_compiles":
..., "second_tick_new_compiles": ..., "compile_cache": {dir, hits,
misses}, "programs": {...}}. The per-program compile-count / compile-ms
table goes to stderr. Run it twice as sibling processes, both arms
pointed at a fixed subdirectory emptied before the cold arm (the round-5
bench did); it is also a deployable smoke check
(JAX_COMPILATION_CACHE_DIR=/var/cache/kmamiz python
tools/warm_boot_probe.py).
"""
from __future__ import annotations

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _print_program_table(summary: dict) -> None:
    """Per-program compile telemetry, aligned, on stderr (stdout is the
    one-JSON-line machine contract)."""
    rows = [
        (name, st)
        for name, st in sorted(summary["programs"].items())
        if st["calls"] or st["prewarmed"]
    ]
    if not rows:
        return
    width = max(len(name) for name, _ in rows)
    print(
        f"{'program':<{width}}  calls  compiles  compile_ms  "
        "prewarmed  prewarm_ms  buckets",
        file=sys.stderr,
    )
    for name, st in rows:
        print(
            f"{name:<{width}}  {st['calls']:>5}  {st['compiles']:>8}  "
            f"{st['compileMs']:>10.1f}  {st['prewarmed']:>9}  "
            f"{st['prewarmMs']:>10.1f}  {len(st.get('buckets', [])):>7}",
            file=sys.stderr,
        )
    print(
        f"total: {summary['totalCompiles']} compiles, "
        f"{summary['totalCompileMs']:.1f} ms",
        file=sys.stderr,
    )


def main() -> None:
    from kmamiz_tpu.core import compile_cache, programs

    compile_cache.enable()

    from kmamiz_tpu.server.processor import DataProcessor
    from kmamiz_tpu.synth import make_raw_window

    # the reference-cadence tick: 2,500 traces x 7 spans
    window = json.loads(make_raw_window(2_500, 7))
    dp = DataProcessor(trace_source=lambda lb, t, lim: window)

    # boot prewarm plan: replay persisted shape hints when the previous
    # run recorded them, else the graph-store default buckets — the same
    # plan the server mains dispatch through boot_prewarm_from_env
    t0 = time.perf_counter()
    report = programs.run_prewarm(graph=dp.graph)
    prewarm_s = time.perf_counter() - t0

    snap = programs.snapshot()
    t0 = time.perf_counter()
    dp.collect({"uniqueId": "warm-1", "lookBack": 30_000, "time": 1_000_000})
    # drain the deferred merge INSIDE the timer: the staged union is the
    # device work the pre-warm exists to keep compile-free, and the
    # second tick below charges it identically (comparable numbers)
    dp.graph.n_edges
    first_tick_ms = (time.perf_counter() - t0) * 1000
    first_tick_new = programs.new_compiles_since(snap)

    window2 = json.loads(make_raw_window(2_500, 7, t_start=10_000))
    dp2 = DataProcessor(trace_source=lambda lb, t, lim: window2)
    snap = programs.snapshot()
    t0 = time.perf_counter()
    dp2.collect({"uniqueId": "warm-2", "lookBack": 30_000, "time": 2_000_000})
    dp2.graph.n_edges
    second_tick_ms = (time.perf_counter() - t0) * 1000
    second_tick_new = programs.new_compiles_since(snap)

    summary = programs.summary()
    _print_program_table(summary)
    import jax

    print(
        json.dumps(
            {
                "platform": jax.default_backend(),
                "compile_cache": compile_cache.stats(),
                "prewarm_s": round(prewarm_s, 1),
                "prewarm_programs": report["warmed"]
                + report["defaultGraphPrograms"],
                "prewarm_report": report,
                "first_tick_ms": round(first_tick_ms, 1),
                "second_tick_ms": round(second_tick_ms, 1),
                # steady-state contract: compiles a warm process still
                # paid INSIDE the timed ticks (0 when hints covered all)
                "first_tick_new_compiles": sum(first_tick_new.values()),
                "second_tick_new_compiles": sum(second_tick_new.values()),
                "programs": {
                    name: {
                        "compiles": st["compiles"],
                        "compileMs": round(st["compileMs"], 1),
                        "prewarmed": st["prewarmed"],
                    }
                    for name, st in sorted(summary["programs"].items())
                    if st["calls"] or st["prewarmed"]
                },
            }
        )
    )


if __name__ == "__main__":
    main()
