"""Layer: kernels. Self time, per slot update, of the device ops of the
planned attention (`ops/sparse.planned_attention`): the five Mosaic kernels
that make GAT's directed segment softmax, its weighted sum and the edge-score
product, forward and backward, over the stack's edge plan. They are found by
the name they carry in the device trace, `planned_attention_*`
(`..._max`, `..._softmax`, `..._sum`, `..._edge_dot`, `..._backward`), with
whatever JAX wraps around it (`jvp(...)`, `transpose(...)`). The row gathers
that feed them are `kernel.scatter_gather_share`'s. A program with no such
op (another head, or a commit from before the kernels) reads nothing."""

NAME = "planned_attention"


def attention_self_ns(record):
    """Self time of the attention kernels in the traced window, on one
    device; None where the trace holds none."""
    if record.trace is None:
        return None
    found = [ev.self_ns for ev in record.trace.ops if NAME in ev.name]
    if not found:
        return None
    return sum(found) / max(record.trace.devices, 1)


def read(record):
    updates = record.recorder.counters.get("window.slot_updates", 0)
    self_ns = attention_self_ns(record)
    if self_ns is None or not updates:
        return None
    return self_ns / 1e6 / updates
