"""Layer: kernels. Self time, a device and a slot update, of the block's ops
under the program's scope phase `gather`: the row gathers that feed a planned
reduction, forward and backward (`ops/sparse.py`, `ops/sparse_gated.py`), by
the program's own scope table (`harness/program_scopes.py`) and not by XLA's
names, which `kernel.scatter_gather_share` goes by."""
from benchmarks.harness import program_scopes as scopes


def read(record):
    return scopes.ms_per_slot(record, ("gather",))
