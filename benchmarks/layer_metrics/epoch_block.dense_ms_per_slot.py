"""Layer: epoch block. Self time, a device and a slot update, of the block's
ops under the program's scope phases `dense` (everything of a head that is
neither a gather, a reduction nor a collective: projections, activations, the
degree division, STLGT's global linear attention and FFN, GAT's `hw`, `s`, `t`
products, XLA's glue around the kernels) and `group` (the slot group's slice,
its `[N, group * F]` table and the slices of its sums). Which op belongs to
which phase is the program's own word (`harness/program_scopes.py`); a commit
that names no scopes reads nothing."""
from benchmarks.harness import program_scopes as scopes


def read(record):
    return scopes.ms_per_slot(record, ("dense", "group"))
