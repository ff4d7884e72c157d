"""Layer: set-up, stack. The `refresh.stack.plan` span of the build in
set-up: the host sort of the edge list into the edge plan (owner, neighbour,
direction, the tiled work list) and the hand-over of its arrays to the
device, a part of `setup.stack_upload_s` after the host fill. A stack whose
plan came from a memo records no such span, and the metric reads nothing."""
from benchmarks.harness import program_spans as ps


def read(record):
    plans = ps.find(record, "refresh.stack.plan", within="setup.stack_upload")
    return sum(ps.dur_ms(p) for p in plans) / 1e3 if plans else None
