"""Layer: kernels. The planned dynamic attention's share of its memory
roofline: the least bytes its reductions of one slot update must move (the
`gatv2_sums` and `gatv2_scores` terms of `trace/work/gatv2.py`, a lower bound
counted from the configuration's shapes, whatever implements them) over the
published HBM bandwidth of the device kind (`trace/peaks.py`), divided by the
self time per slot update of the walks that make them
(`kernel.gatv2_ms_per_slot`). Bandwidth bounds them: the scores and the
weighted sums are 2.3 GFLOP an update. The walks read every gathered row at
the 128 lanes it fills, in five walks a layer where the count has six row
passes an edge and direction, so the share cannot pass 100%."""
from benchmarks.trace import peaks


def read(record):
    updates = record.recorder.counters.get("window.slot_updates", 0)
    self_ns = record.manifest.load_module(
        "layer_metrics/kernel.gatv2_ms_per_slot.py"
    ).gatv2_self_ns(record)
    if not self_ns or not updates:
        return None
    work = record.manifest.load_module(f"trace/work/{record.config['family']}.py")
    terms = getattr(work, "terms", None)
    if terms is None:
        return None
    terms = terms(record.config)
    if "gatv2_sums" not in terms:
        return None
    least_s = (terms["gatv2_sums"] + terms["gatv2_scores"]) / peaks.of(
        record.devices[0].device_kind
    )["hbm_bytes_per_s"]
    return 100.0 * least_s / (self_ns / 1e9 / updates)
