"""Bytes and operations one GraphSAGE slot update must move, from the
configuration's shapes alone (forward, backward and the optimizer of one
hourly slot; `reference/graphsage.py` is the mathematics).

The count is a LOWER bound on HBM traffic, so that a share of the roofline
made from it cannot be flattered: it assumes an implementation that fuses
every gather with its scatter (one sparse product per edge direction: read
the two index columns, read one row per edge, write one row per node), that
keeps every `[N, width]` activation in HBM (at 100,000 x 64 floats it does
not fit on the chip) and touches it once where it is made and once wherever
it is used, and that skips padding: real endpoints and real edges only.
"""
from __future__ import annotations

FLOAT = 4
INDEX = 4


def spmm_bytes(nodes: int, edges: int, width: int) -> int:
    """One direction of a neighbour sum at `width`."""
    return edges * 2 * INDEX + edges * width * FLOAT + nodes * width * FLOAT


def terms(config: dict) -> dict:
    n, e = int(config["endpoints"]), int(config["edges"])
    f, h = int(config["num_features"]), int(config["hidden"])
    wide = n * h * FLOAT  # one [N, hidden] tensor
    narrow = n * f * FLOAT  # one [N, features] tensor
    params = 2 * f * h + 2 * h * h + 2 * h + 2 * (h + 1) + 2 * f
    return {
        # forward: both directions at the feature width, then at the hidden
        # width; backward: only layer 2 sends a gradient through the graph
        # (layer 1's input is data)
        "neighbour_sums": 2 * spmm_bytes(n, e, f) + 4 * spmm_bytes(n, e, h),
        # forward: read x and agg1, write h1; read h1 and agg2, write h2;
        # read h2 and x for the two readouts
        "dense_forward": 3 * narrow + 5 * wide,
        # backward: d_h2 and d_h1 written and read; h2 and h1 read for the
        # relu masks; h1, agg2, x, agg1 read for the weight gradients; the
        # gradient into agg2 written
        "dense_backward": 2 * narrow + 9 * wide,
        # targets, mask, two predictions and their gradients
        "readout": n * (2 * FLOAT + 1) + 4 * n * FLOAT,
        # adamw: read params, grads and two moments, write params and moments
        "optimizer": 7 * params * FLOAT,
    }


def slot_update_bytes(config: dict) -> int:
    return sum(terms(config).values())


def slot_update_flops(config: dict) -> int:
    """Multiply-adds count two. Forward products, twice that again backward
    (layer 1 needs no gradient to its input, so its backward is once)."""
    n, e = int(config["endpoints"]), int(config["edges"])
    f, h = int(config["num_features"]), int(config["hidden"])
    layer1 = 2 * n * f * h * 2  # self and neighbour products
    layer2 = 2 * n * h * h * 2
    readouts = 2 * n * (h + f) * 2
    sums = 2 * e * (f + h) * 2  # the adds of the neighbour sums, forward
    return (layer1 * 2) + (layer2 * 3) + (readouts * 3) + sums + 2 * e * h * 2
