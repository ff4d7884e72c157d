"""Layer: kernels. The planned gated sum's share of its memory roofline: the
least bytes the gated reductions of one slot update must move (the
`gated_sums` and `gated_scalars` terms of `trace/work/stlgt.py`, a lower
bound counted from the configuration's shapes, whatever implements them)
over the published HBM bandwidth of the device kind (`trace/peaks.py`),
divided by the self time per slot update of the kernels that make them
(`kernel.gated_sum_ms_per_slot`). Bandwidth bounds them: the gate's dots and
the weighted sums are 0.6 GFLOP an update."""
from benchmarks.trace import peaks


def read(record):
    updates = record.recorder.counters.get("window.slot_updates", 0)
    self_ns = record.manifest.load_module(
        "layer_metrics/kernel.gated_sum_ms_per_slot.py"
    ).gated_self_ns(record)
    if not self_ns or not updates:
        return None
    work = record.manifest.load_module(f"trace/work/{record.config['family']}.py")
    terms = getattr(work, "terms", None)
    if terms is None:
        return None
    terms = terms(record.config)
    if "gated_sums" not in terms:
        return None
    least_s = (terms["gated_sums"] + terms["gated_scalars"]) / peaks.of(
        record.devices[0].device_kind
    )["hbm_bytes_per_s"]
    return 100.0 * least_s / (self_ns / 1e9 / updates)
