"""Driver of the `refresh` kind of traffic: a closed loop with one caller,
`trainer.train(dataset, epochs=k, ...)` back to back over the history that
set-up stacked on the device once.

A refresh is a job one scheduler runs, not independent arrivals, so the loop
is closed. A call is one dispatch of a whole epoch block and cannot be
counted part-way: the run starts no new call once `seconds` have passed, and
the rate is whole calls over the time to the end of the last one.

Everything about a cell comes from its files: the sizes and the model from
the configuration, the loop's parameters from the traffic mix.
"""
from __future__ import annotations

import importlib
import math
import shutil
import tempfile
import time
from typing import Optional

from benchmarks.harness.record import Context, Record
from benchmarks.harness.spans import Recorder
from benchmarks.reference import check as ref_check


def run(ctx: Context) -> Record:
    import jax

    from kmamiz_tpu.core import compile_cache, programs
    from kmamiz_tpu.models import stacked, trainer

    compile_cache.enable()  # JAX_COMPILATION_CACHE_DIR, else <checkout>/.xla-cache
    cfg, mix = ctx.config, ctx.traffic
    model = importlib.import_module(cfg["model_module"])
    generator = ctx.manifest.load_module(f"gen/{cfg['generator']}.py")
    rec = Recorder()
    notes = {}

    def call(dataset):
        return trainer.train(
            dataset,
            epochs=int(mix["epochs_per_call"]),
            hidden=int(cfg["hidden"]),
            lr=float(cfg["lr"]),
            seed=ctx.seed,
            model=model,
            use_node_embeddings=bool(cfg["node_embeddings"]),
            batch_slots=int(cfg["batch_slots"]),
        )

    # -- set-up: data, stack, upload, the check, one warm call --------------
    with rec.span("setup.generate"):
        dataset = generator.generate(cfg, ctx.seed)
    with rec.span("setup.stack_upload"):
        st = stacked.stack_dataset(dataset)  # memoised on the dataset
        jax.block_until_ready((st.features, st.target_latency,
                               st.target_anomaly, st.node_mask, st.src, st.dst))
    layout = st.layout()
    if (layout["bucket_nodes"], layout["bucket_edges"], layout["num_slots"]) != (
        int(cfg["node_bucket"]), int(cfg["edge_bucket"]), int(cfg["slots"])
    ):
        raise RuntimeError(f"the program stacked to {layout}, not to the configuration's buckets")

    with rec.span("setup.check"):
        verdict = ref_check.against_reference(
            cfg, lambda n: generator.head(dataset, n), mix, ctx.seed, model, call
        )
    notes["reference"] = verdict.detail
    correct = verdict.ok
    compared = verdict.compared()

    with rec.span("setup.warm_call"):
        first = ref_check.triple(call(dataset))
    if not all(math.isfinite(v) for v in first):
        correct = False
        notes["warm_call"] = f"losses not finite: {first}"

    rec.counters["setup.compile_ms"] = sum(
        p.stats()["compileMs"] for p in programs.all_programs().values()
    )
    snapshot = programs.snapshot()

    # -- the window ---------------------------------------------------------
    slots = int(cfg["slots"]) * int(mix["epochs_per_call"])
    trace_dir: Optional[str] = None
    if ctx.trace:
        from benchmarks.trace import capture

        trace_dir = tempfile.mkdtemp(prefix="bench-trace-")
        capture.start(trace_dir)
    attempted = failed = whole = 0
    start = time.perf_counter()
    setup_s = start - ctx.t0
    end_of_last = start

    def window_open() -> bool:
        # a traced window is a fixed number of whole calls: a trace of all
        # `seconds` would be too large to bring back and to read
        if ctx.trace:
            return attempted < int(mix["traced_calls"])
        return time.perf_counter() - start < ctx.seconds

    try:
        while window_open():
            attempted += 1
            try:
                with rec.span("refresh.call"):
                    got = ref_check.triple(call(dataset))
            except Exception as e:  # noqa: BLE001 - a failed call is counted, and ends the run
                failed += 1
                notes["call_error"] = repr(e)
                break
            end_of_last = time.perf_counter()
            if ref_check.same_computation(got, first):
                whole += 1
            else:
                failed += 1
                notes.setdefault("call_mismatch", []).append(got)
    finally:
        if ctx.trace:
            capture.stop()
    elapsed = end_of_last - start

    rec.counters["window.calls"] = whole
    rec.counters["window.slot_updates"] = whole * slots
    rec.counters["window.compiles"] = sum(programs.new_compiles_since(snapshot).values())
    rec.counters["window.elapsed_s"] = elapsed
    notes["programs"] = {
        k: p.stats() for k, p in programs.all_programs().items() if p.calls
    }
    notes["compile_cache"] = compile_cache.stats()

    record = Record(
        correct=bool(correct and failed == 0 and whole > 0),
        attempted=attempted,
        failed=failed,
        end_to_end={
            "refresh_slot_updates_per_s": whole * slots / elapsed if elapsed > 0 else 0.0,
            "setup_s": setup_s,
        },
        recorder=rec,
        manifest=ctx.manifest,
        config=cfg,
        traffic=mix,
        devices=ctx.devices,
        notes=notes,
        # exact: a call whose losses are not the warm call's (SAME_RTOL), or that raised
        compared={**compared, "window.calls_failed": {"value": failed, "limit": 0}},
    )
    if trace_dir is not None:
        from benchmarks.trace import reduce as trace_reduce

        try:
            record.trace = trace_reduce.reduce_dir(trace_dir, window_span="refresh.call")
        finally:
            shutil.rmtree(trace_dir, ignore_errors=True)
    return record
