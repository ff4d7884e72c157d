"""Layer: epoch block. The instrument's own residue: self time of the epoch
block's ops that the program's scope table does not hold (what XLA put in
itself: copies, a loop's own counter and slices) or holds under no phase of
the taxonomy, over the device-busy time of the window, in percent. It is to
the scope-read metrics what `trainer.uncovered_ms_per_call` is to the host's
spans: work added to the block outside every scope shows here. It is also the
check that the table is of the executable that ran, whose names alone match."""
from benchmarks.harness import program_scopes as scopes


def read(record):
    self_ns = scopes.device_mean_ns(record, (scopes.UNSCOPED,))
    if self_ns is None or not record.trace.busy_ns:
        return None
    return 100.0 * self_ns / record.trace.busy_ns
