"""Layer: epoch block. Self time, a device and a slot update, of the block's
ops under the program's scope phases `loss` (the masked sums of
`common.make_loss_fn` or of the head's own loss, and the losses' stacking)
and `optimizer` (`optimizer.update` and `optax.apply_updates` of a slot's
step), by the program's own scope table (`harness/program_scopes.py`)."""
from benchmarks.harness import program_scopes as scopes


def read(record):
    return scopes.ms_per_slot(record, ("loss", "optimizer"))
