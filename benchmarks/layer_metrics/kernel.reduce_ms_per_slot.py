"""Layer: kernels. Self time, a device and a slot update, of the block's ops
under the program's scope phase `reduce`: the Mosaic reductions and walks over
the edge plan (the planned neighbour sum's reducer, the attention's five
walks, the gated sum's two) and, over a history cut by nodes, adding the
sources' parts. By the program's own scope table
(`harness/program_scopes.py`), so it holds no collective, whatever XLA names
one (`kernel.neighbor_sum_ms_per_slot` goes by `trace/reduce.op_kind`, which
reads a fused `async-collective-done` as a scatter)."""
from benchmarks.harness import program_scopes as scopes


def read(record):
    return scopes.ms_per_slot(record, ("reduce",))
