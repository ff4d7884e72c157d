"""Layer: set-up, stack. The `refresh.stack.host_fill` span of the build in
set-up: the numpy loop that copies every slot into the padded host arrays,
the host's part of `setup.stack_upload_s` before the first `device_put`."""
from benchmarks.harness import program_spans as ps


def read(record):
    fills = ps.find(record, "refresh.stack.host_fill", within="setup.stack_upload")
    return sum(ps.dur_ms(f) for f in fills) / 1e3 if fills else None
