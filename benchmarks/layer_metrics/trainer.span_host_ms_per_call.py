"""Layer: trainer host. Host time of one `trainer.train()` call by the
program's own clock: its `refresh.train` span minus `refresh.loss_fetch`,
the one stretch in which the host only waits for the device. What
`trainer.host_ms_per_call` gets by subtracting device-busy time from the
window, measured where the work happens."""
from benchmarks.harness import program_spans as ps


def read(record):
    return ps.per_call(
        record, lambda call: ps.dur_ms(call) - ps.child_ms(call, "refresh.loss_fetch")
    )
