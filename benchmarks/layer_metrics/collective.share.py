"""Layer: collectives. `collective.ms_per_slot`'s time as a share of the
device's busy time: how much of a chip's work in a node-sharded refresh is
standing in a collective that compute did not hide."""


def read(record):
    if record.trace is None or not record.trace.busy_ns:
        return None
    ms = record.manifest.load_module("layer_metrics/collective.ms_per_slot.py")
    self_ns = ms.collective_self_ns(record)
    if self_ns is None:
        return None
    return 100.0 * self_ns / record.trace.busy_ns
