"""Layer: kernels. Share of the device's busy time spent in gather and
scatter operations: XLA's lowering of `graphsage.neighbor_mean`'s row
gathers and segment sums (and, for GAT, of the segment softmax's
`segment_max` and `segment_sum`). Which device op is one is read from its
HLO text (`trace/reduce.py`, `op_kind`). Self time, so the loop that
contains a scatter is not counted for it."""

KINDS = ("gather", "scatter")


def read(record):
    if record.trace is None or not record.trace.ops:
        return None
    return 100.0 * record.trace.share_of_busy(lambda ev: ev.category in KINDS)
