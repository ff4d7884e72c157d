"""Layer: collectives. The least time a chip's interconnect could take to
bring it what a slot update makes it receive (`trace/work/<family>.py`:
`slot_update_ici_bytes` over the published ICI rate of the device kind, with
its source there), over `collective.ms_per_slot`, the time the chip stood in
collective ops. A family whose work file counts no ICI bytes (every one-chip
configuration) reads nothing."""


def read(record):
    updates = record.recorder.counters.get("window.slot_updates", 0)
    if record.trace is None or not updates:
        return None
    work = record.manifest.load_module(f"trace/work/{record.config['family']}.py")
    if not hasattr(work, "slot_update_ici_bytes"):
        return None
    ms = record.manifest.load_module("layer_metrics/collective.ms_per_slot.py")
    self_ns = ms.collective_self_ns(record)
    if not self_ns:
        return None
    least_s = work.slot_update_ici_bytes(record.config) / work.ici_bytes_per_s(
        record.devices[0].device_kind
    )
    return 100.0 * least_s / (self_ns / 1e9 / updates)
