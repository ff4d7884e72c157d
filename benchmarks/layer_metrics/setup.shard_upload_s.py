"""Layer: set-up, stack. The `refresh.stack.device_put` spans of the build in
set-up that carry a `shard` count: a node-sharded stack hands each shard to
its device and waits for the transfer before it fills the next
(`models/stacked.py`), so the spans end with the transfers and their sum is
the upload. A stack on one device records one span without the count (it
ends where `jnp.asarray` returns, not with the transfer) and reads nothing."""
from benchmarks.harness import program_spans as ps


def read(record):
    puts = [
        (tb, i)
        for tb, i in ps.find(record, "refresh.stack.device_put", within="setup.stack_upload")
        if "shard" in tb.counts.get(i, {})
    ]
    return sum(ps.dur_ms(p) for p in puts) / 1e3 if puts else None
