"""Layer: trainer host. The `refresh.init` span of one call: `init_params`,
`make_optimizer` and `optimizer.init`, a handful of small device programs
dispatched one after the other before the epoch block."""
from benchmarks.harness import program_spans as ps


def read(record):
    return ps.per_call(record, lambda call: ps.child_ms(call, "refresh.init"))
