"""Bytes ONE chip must move for one GraphSAGE slot update of a history that
is sharded by nodes over the chips of one host (`mv400k-sage`), from the
configuration's shapes alone: through its own HBM, and over ICI.

HBM. A chip holds a `1 / chips` share of the nodes and, the node ranges being
cut where the plan's entries divide evenly, of the edges' entries: so its
share of every term of the one-chip count (`trace/work/graphsage.py`, whose
assumptions stand: real endpoints and real edges only, every gather fused
with its sum, each activation touched once where it is made and once where
it is used). The optimizer is NOT shared: parameters and their moments are
replicated, and every chip updates all of them. On top, what only a sharded
layer moves: each all-gather reads the chip's own rows once to send them and
writes the rows it receives (the read of the gathered table, one row an
entry, is in the neighbour sums' term already). Three tables are gathered in
a slot update: layer 2's `h1` `[N, hidden]`, its cotangent, and, once a slot
group, layer 1's features of the group's slots, `[N, features]` a slot.

ICI. A chip must receive the other chips' rows of each of those tables,
`(chips - 1) / chips` of them, and the other chips' parameter gradients (a
ring all-reduce receives `2 (chips - 1) / chips` of the vector). In float32:
the configuration states that what crosses is what is stored.

Rate: a v5e chip's published chip-to-chip interconnect is 1,600 Gbit/s = 200
GB/s (Google Cloud documentation, "TPU v5e", as the `on-chip-measurement`
guide quotes it beside the 819 GB/s of HBM that `trace/peaks.py` holds; that
file's table has no such column, and this PR may not edit it). It is the sum
over a chip's four ports; a 2x2 host wires two of them to a neighbour each, so
a share of this rate over 50% is not to be expected here, and none over 100%.
"""
from __future__ import annotations

from benchmarks.trace.work import graphsage as one_chip

FLOAT = one_chip.FLOAT

#: published chip-to-chip rate of a chip, by the `device_kind` JAX reports;
#: a kind that is not here is an error, never a default
ICI_BYTES_PER_S = {"TPU v5 lite": 1600e9 / 8}


def ici_bytes_per_s(device_kind: str) -> float:
    try:
        return ICI_BYTES_PER_S[device_kind]
    except KeyError:
        raise KeyError(
            f"no published ICI rate for device kind {device_kind!r}; add it to "
            "benchmarks/trace/work/graphsage_sharded.py with its source"
        ) from None


def gathered_tables(config: dict) -> dict:
    """Bytes of each table a slot update all-gathers, whole (real endpoints)."""
    n, f, h = int(config["endpoints"]), int(config["num_features"]), int(config["hidden"])
    return {
        "h1": n * h * FLOAT,
        "h1_cotangent": n * h * FLOAT,
        # the group's table holds 128 // features slots' features, and serves
        # as many slot updates: a slot update's share is one slot's features
        "features_of_the_slot": n * f * FLOAT,
    }


def terms(config: dict) -> dict:
    chips = int(config["chips"])
    shared = {k: v for k, v in one_chip.terms(config).items() if k != "optimizer"}
    out = {k: v // chips for k, v in shared.items()}
    out["optimizer"] = one_chip.terms(config)["optimizer"]
    # sent rows read once (1 / chips of a table), received rows written once
    out["gathered_tables"] = sum(gathered_tables(config).values())
    return out


def slot_update_bytes(config: dict) -> int:
    """What ONE chip's HBM must move: the reader divides by one chip's rate
    and by the busy time of a chip (`kernel.slot_update_hbm_roofline`)."""
    return sum(terms(config).values())


def slot_update_ici_bytes(config: dict) -> int:
    """What ONE chip must receive over ICI in a slot update."""
    chips = int(config["chips"])
    f, h = int(config["num_features"]), int(config["hidden"])
    params = 2 * f * h + 2 * h * h + 2 * h + 2 * (h + 1) + 2 * f
    tables = sum(gathered_tables(config).values())
    return (chips - 1) * tables // chips + 2 * (chips - 1) * params * FLOAT // chips
