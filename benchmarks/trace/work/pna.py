"""Bytes and operations one PNA slot update must move, from the
configuration's shapes alone (`reference/pna.py` is the mathematics).

A LOWER bound on HBM traffic, on the assumptions of `work/graphsage.py` and
`work/gat.py`: a sparse pass reads its index pair and one gathered row per
edge and direction, and moves each per-node table once; a dense stage reads
its input and writes its output once; padding is skipped; whatever implements
them. Both layers aggregate rows of the hidden width (`m = h W_m`), and both
are differentiated: layer 1's message depends on `w_msg_1`.

- forward, a layer: ONE pass over the edges in each direction makes all four
  aggregates of the row it reads (sum, sum of squares, maximum, minimum), and
  writes four tables;
- backward, a layer: the cotangent of a neighbour's row is a transposed sum
  over the edges: per edge and direction the neighbour's own row is read once
  more (for `2 m g_sq` and to see whether it holds the owner's maximum or
  minimum) and its cotangent row updated, the owner's six tables (four
  cotangents, the two extremes) are read once a node, the result written once.
  The ties of a maximum are counted in the forward's pass by an implementation
  that is as good as it can be, so they move no row of their own;
- the scalars: the degree and the scaler `log(d + 1) / delta` a node, forward
  and backward, and the two tie counts a node and lane that the shares
  `g_max / ties`, `g_min / ties` read.

The update's input `[a | s a | a / s]` (12 x hidden a node) is made on the
chip from the four tables by whoever fuses it, so the dense stage reads the
four tables and not twelve.
"""
from __future__ import annotations

FLOAT = 4
INDEX = 4
LAYERS = 2
DIRECTIONS = 2
AGGREGATORS = 4  # mean, deviation, maximum, minimum
SCALERS = 3


def edge_pass(edges: int, width: int, rows: int) -> int:
    """One direction of one pass over the edges: the index pair and `rows`
    rows of `width` per edge."""
    return edges * 2 * INDEX + rows * edges * width * FLOAT


def parameters(f: int, h: int) -> int:
    layer = lambda d: 2 * d * h + AGGREGATORS * SCALERS * h * h + h  # noqa: E731 - W_m, W_s, W_a, b
    return layer(f) + layer(h) + 2 * (h + 1) + 2 * f


def terms(config: dict) -> dict:
    n, e = int(config["endpoints"]), int(config["edges"])
    f, h = int(config["num_features"]), int(config["hidden"])
    wide = n * h * FLOAT  # one [N, hidden] tensor
    narrow = n * f * FLOAT  # one [N, features] tensor
    forward = DIRECTIONS * edge_pass(e, h, 1) + AGGREGATORS * wide
    backward = DIRECTIONS * edge_pass(e, h, 2) + (AGGREGATORS + 2) * wide + wide
    return {
        "aggregate_sums": LAYERS * (forward + backward),
        # degree and scaler read and written a node, forward and backward; two tie counts a node and lane
        "aggregate_scalars": LAYERS * (4 * n * FLOAT + 2 * wide),
        # layer 1: read x, write m1; read x and the four tables, write h1. Layer 2: the same of h1. Readouts: h2 and x
        "dense_forward": 3 * narrow + 15 * wide,
        # layer 2: d h2 written and read, h2 read for the relu, the four tables' cotangents written, h1 and the four
        # tables read for the weight gradients, d m2 read, d h1 written and read, h1 read for the relu; layer 1: the
        # four cotangents written, x (twice) and the four tables read for the weight gradients, d m1 read
        "dense_backward": 2 * narrow + 25 * wide,
        # targets, mask, two predictions and their gradients
        "readout": n * (2 * FLOAT + 1) + 4 * n * FLOAT,
        # adamw: read params, grads and two moments, write params and moments
        "optimizer": 7 * parameters(f, h) * FLOAT,
    }


def slot_update_bytes(config: dict) -> int:
    return sum(terms(config).values())


def slot_update_flops(config: dict) -> int:
    """Multiply-adds count two; forward products, twice that again backward
    (layer 1's input is data, so its backward is once)."""
    n, e = int(config["endpoints"]), int(config["edges"])
    f, h = int(config["num_features"]), int(config["hidden"])
    wide_in = AGGREGATORS * SCALERS * h
    layer1 = 2 * n * (2 * f + wide_in) * h  # message, self and update products
    layer2 = 2 * n * (2 * h + wide_in) * h
    readouts = 2 * n * (h + f) * 2
    reductions = DIRECTIONS * e * h * 4 * LAYERS  # an add, a square and add, a maximum, a minimum per gathered element
    return layer1 * 2 + layer2 * 3 + readouts * 3 + reductions * 3
