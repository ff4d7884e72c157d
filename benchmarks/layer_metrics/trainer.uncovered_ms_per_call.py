"""Layer: trainer host. Self time of `refresh.train`: what of one call no
child span covers. It guards the coverage of the program's spans: host work
added to `train()` outside every span shows here, not in a named stretch."""
from benchmarks.harness import program_spans as ps


def read(record):
    return ps.per_call(record, ps.self_ms)
