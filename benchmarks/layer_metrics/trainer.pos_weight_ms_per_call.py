"""Layer: trainer host. The `refresh.pos_weight` span of one call: the
base-rate loop that pulls every slot's anomaly targets and node mask back
to the host to weigh the positive class."""
from benchmarks.harness import program_spans as ps


def read(record):
    return ps.per_call(record, lambda call: ps.child_ms(call, "refresh.pos_weight"))
