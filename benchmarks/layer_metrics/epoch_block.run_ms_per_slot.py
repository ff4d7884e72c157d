"""Layer: epoch block. The run time the `core/programs` registry gained in
the window (`Program.note_run`: wall from the dispatch of an epoch block to
its losses on the host, reported by `train()` after the fence it always
had), per slot update the runs held. The program's own reading of what
`epoch_block.device_ms_per_slot` reads from the device trace."""
from benchmarks.harness import program_spans as ps


def read(record):
    runs = ps.runs_within(record, within="refresh.call")
    updates = sum(units for _ms, units in runs)
    if not updates:
        return None
    return sum(ms for ms, _units in runs) / updates
