"""Layer: kernels. Self time, per slot update, of the device ops of the
planned dynamic attention (`ops/sparse_gatv2.planned_gatv2`): the Mosaic
walks that make GATv2's per-entry scores, its two directed softmaxes and
weighted sums over the stack's edge plan, and their VJP. They are found by
the name they carry in the device trace, `planned_gatv2*` (`..._score`,
`..._softmax`, `..._sum`, `..._edge_dot`, `..._backward`), with whatever JAX
wraps around it. GAT's walks are `planned_attention_*` and are not read here;
the row gathers that feed these are `kernel.gather_ms_per_slot`'s. A program
with no such op (another head, or a commit from before the walks) reads
nothing."""

NAME = "planned_gatv2"


def gatv2_self_ns(record):
    """Self time of the dynamic attention's walks in the traced window, on
    one device; None where the trace holds none."""
    if record.trace is None:
        return None
    found = [ev.self_ns for ev in record.trace.ops if NAME in ev.name]
    if not found:
        return None
    return sum(found) / max(record.trace.devices, 1)


def read(record):
    updates = record.recorder.counters.get("window.slot_updates", 0)
    self_ns = gatv2_self_ns(record)
    if self_ns is None or not updates:
        return None
    return self_ns / 1e6 / updates
