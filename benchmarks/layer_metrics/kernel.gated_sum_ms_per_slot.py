"""Layer: kernels. Self time, per slot update, of the device ops of the
planned gated sum (`ops/sparse_gated.planned_gated_sum`): the Mosaic kernels
that make STLGT's sigmoid-gated neighbour bias and its three-table VJP over
the stack's edge plan. They are found by the name they carry in the device
trace, `planned_gated_*` (`..._sum`, `..._backward`), with whatever JAX wraps
around it (`jvp(...)`, `transpose(...)`). The three row gathers that feed them
are `kernel.scatter_gather_share`'s. A program with no such op (another
head, or a commit from before the kernels) reads nothing."""

NAME = "planned_gated"


def gated_self_ns(record):
    """Self time of the gated-sum kernels in the traced window, on one
    device; None where the trace holds none."""
    if record.trace is None:
        return None
    found = [ev.self_ns for ev in record.trace.ops if NAME in ev.name]
    if not found:
        return None
    return sum(found) / max(record.trace.devices, 1)


def read(record):
    updates = record.recorder.counters.get("window.slot_updates", 0)
    self_ns = gated_self_ns(record)
    if self_ns is None or not updates:
        return None
    return self_ns / 1e6 / updates
