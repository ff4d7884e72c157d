"""Layer: epoch block. Device-busy time of the window per slot update: the
window holds nothing but whole calls of the epoch-block program
(`models/stacked.py`, `models.sage_epoch_block[...]`) and the handful of
small programs `train()` runs before it (init, optimizer state)."""


def read(record):
    updates = record.recorder.counters.get("window.slot_updates", 0)
    if record.trace is None or not updates:
        return None
    return record.trace.busy_s * 1e3 / updates
