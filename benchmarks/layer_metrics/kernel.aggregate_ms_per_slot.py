"""Layer: kernels. Self time, per slot update, of the device ops of the
planned multi-aggregate (`ops/sparse_pna.planned_aggregate`): the Mosaic
kernels that make PNA's sum, sum of squares, maximum and minimum over the
stack's edge plan and their VJP. They are found by the name they carry in the
device trace, `planned_aggregate*` (`planned_aggregate`, `..._ties`,
`..._backward`), with whatever JAX wraps around it (`jvp(...)`,
`transpose(...)`). The row gathers that feed them are
`kernel.gather_ms_per_slot`'s. A program with no such op (another head, or a
commit from before the kernels) reads nothing."""

NAME = "planned_aggregate"


def aggregate_self_ns(record):
    """Self time of the multi-aggregate's kernels in the traced window, on one
    device; None where the trace holds none."""
    if record.trace is None:
        return None
    found = [ev.self_ns for ev in record.trace.ops if NAME in ev.name]
    if not found:
        return None
    return sum(found) / max(record.trace.devices, 1)


def read(record):
    updates = record.recorder.counters.get("window.slot_updates", 0)
    self_ns = aggregate_self_ns(record)
    if self_ns is None or not updates:
        return None
    return self_ns / 1e6 / updates
