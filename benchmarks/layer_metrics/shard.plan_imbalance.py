"""Layer: set-up, stack. The largest shard's real plan entries over the mean
shard's, from the counts on the `refresh.stack.plan` span of the build in
set-up (`shard_entries`, one a shard: `models/stacked.py`). A walk's time
goes with its entries and every chip waits for the slowest at each
all-gather, so 1.0 is even and anything above it is time the other chips
stand idle. A stack on one device, or a plan that came from a memo, records
no such counts and the metric reads nothing."""
from benchmarks.harness import program_spans as ps


def read(record):
    for tb, i in ps.find(record, "refresh.stack.plan", within="setup.stack_upload"):
        entries = tb.counts.get(i, {}).get("shard_entries")
        if entries and sum(entries):
            return max(entries) * len(entries) / sum(entries)
    return None
