"""Layer: kernels. The planned multi-aggregate's share of its memory roofline:
the least bytes the aggregates of one slot update must move (the
`aggregate_sums` and `aggregate_scalars` terms of `trace/work/pna.py`, a lower
bound counted from the configuration's shapes, whatever implements them) over
the published HBM bandwidth of the device kind (`trace/peaks.py`), divided by
the self time per slot update of the kernels that make them
(`kernel.aggregate_ms_per_slot`). Bandwidth bounds them: the sums, squares and
comparisons are 1.5 GFLOP an update. The count is a lower bound and the
kernels read every gathered row themselves, at the 128 lanes a row fills (five
arrays of 537 MB a layer where the count has three rows of 64 floats an edge
and direction), so the share cannot pass 100%."""
from benchmarks.trace import peaks


def read(record):
    updates = record.recorder.counters.get("window.slot_updates", 0)
    self_ns = record.manifest.load_module(
        "layer_metrics/kernel.aggregate_ms_per_slot.py"
    ).aggregate_self_ns(record)
    if not self_ns or not updates:
        return None
    work = record.manifest.load_module(f"trace/work/{record.config['family']}.py")
    terms = getattr(work, "terms", None)
    if terms is None:
        return None
    terms = terms(record.config)
    if "aggregate_sums" not in terms:
        return None
    least_s = (terms["aggregate_sums"] + terms["aggregate_scalars"]) / peaks.of(
        record.devices[0].device_kind
    )["hbm_bytes_per_s"]
    return 100.0 * least_s / (self_ns / 1e9 / updates)
