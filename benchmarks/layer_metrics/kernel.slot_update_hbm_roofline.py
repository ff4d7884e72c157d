"""Layer: kernels. The least time the chip's memory could take for one slot
update, over the device time it took: bytes one update must move (a function
of the configuration's shapes, `trace/work/<family>.py`, a lower bound) over
the published HBM bandwidth of the device kind (`trace/peaks.py`; an unknown
kind is an error), divided by the measured device-busy time per update.
Bandwidth bounds it: the update's 4-6 GFLOP would take 0.03 ms at the
matrix unit's peak, its bytes over 1 ms."""
from benchmarks.trace import peaks


def read(record):
    updates = record.recorder.counters.get("window.slot_updates", 0)
    if record.trace is None or not updates or not record.trace.busy_s:
        return None
    work = record.manifest.load_module(f"trace/work/{record.config['family']}.py")
    least_s = work.slot_update_bytes(record.config) / peaks.of(
        record.devices[0].device_kind
    )["hbm_bytes_per_s"]
    return 100.0 * least_s / (record.trace.busy_s / updates)
