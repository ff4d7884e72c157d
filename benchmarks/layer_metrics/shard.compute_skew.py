"""Layer: collectives. Over a history cut by nodes: the largest, over the
devices, of the self time of the block's ops OUTSIDE the scope phase
`collective`, over the devices' mean. Every chip waits for the fullest at
each all-gather, so what a chip computes less than the fullest it spends
standing in a collective op: at 1.0 `collective.ms_per_slot` is the wire's,
above it part of it is waiting. One device, or a commit that names no scopes,
reads nothing."""
from benchmarks.harness import program_scopes as scopes


def read(record):
    found = scopes.by_device(record)
    if not found or len(found) < 2:
        return None
    compute = [sum(ns for phase, ns in of.items() if phase != "collective") for of in found.values()]
    mean = sum(compute) / len(compute)
    return max(compute) / mean if mean else None
