"""Layer: kernels. Self time, per slot update, of the device ops that make
the neighbour sums and the degrees of `graphsage.neighbor_mean`: XLA's
scatter fusions (`segment_sum` over edge rows, `neighbor_degree`'s two 1-D
scatters) and the planned reducer's Mosaic kernel, which the profiler shows
as a `custom-call` (`ops/sparse.planned_neighbor_sum`). The row gathers that
feed them are not counted: they are `kernel.scatter_gather_share`'s other
half. Which op is which is read from its HLO text (`trace/reduce.py`,
`op_kind`), so the metric reads one quantity whichever implements the sums:
the whole of the scatters where nothing is planned, the kernel plus whatever
scatter is left where it is."""

KINDS = ("scatter", "custom-call")


def read(record):
    updates = record.recorder.counters.get("window.slot_updates", 0)
    if record.trace is None or not updates or not record.trace.ops:
        return None
    self_ns = sum(ev.self_ns for ev in record.trace.ops if ev.category in KINDS)
    return self_ns / max(record.trace.devices, 1) / 1e6 / updates
