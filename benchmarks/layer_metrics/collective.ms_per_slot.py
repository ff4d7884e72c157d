"""Layer: collectives. Self time, per slot update and per device, of the
device ops that move rows between chips: what a node-sharded refresh spends
in its all-gathers (a layer's neighbour table, `ops/sparse.
sharded_neighbor_sum`) and in the all-reduce of its losses and gradients,
read from the "XLA Ops" line of each device. By the reducer's own rule
(`trace/reduce.py`) the "Async XLA Ops" line, the DMAs in flight beside the
compute, is not read: so this is the part of the collectives that compute
did NOT hide, the time a chip stood in a collective op.

Which op is one is read from its HLO text: its opcode (`op_kind`) or its
instruction's name starts with `all-gather`, `all-reduce`, `reduce-scatter`,
`collective-permute` (with `-start` and `-done`: an async pair's waiting is
in its `-done`), or `async-collective`, the name XLA's TPU backend gives the
`-start` and `-done` of a collective it has fused with its neighbours. A
program on one chip has no such op and the metric reads nothing."""

PREFIXES = ("all-gather", "all-reduce", "reduce-scatter", "collective-permute", "async-collective")


def is_collective(ev) -> bool:
    return ev.category.startswith(PREFIXES) or ev.name.lstrip("%").startswith(PREFIXES)


def collective_self_ns(record):
    """Self time of the collective ops in the traced window, a device; None
    where the trace holds none."""
    if record.trace is None:
        return None
    found = [ev.self_ns for ev in record.trace.ops if is_collective(ev)]
    if not found:
        return None
    return sum(found) / max(record.trace.devices, 1)


def read(record):
    updates = record.recorder.counters.get("window.slot_updates", 0)
    self_ns = collective_self_ns(record)
    if self_ns is None or not updates:
        return None
    return self_ns / 1e6 / updates
