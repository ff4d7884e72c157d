"""Bytes and operations one GAT slot update must move, from the
configuration's shapes alone (`reference/gat.py` is the mathematics).

A LOWER bound on HBM traffic, on the same assumptions as
`work/graphsage.py`. Attention aggregates `hw = h @ W`, so both layers move
rows of the hidden width. Per edge direction and layer:

- forward: the scores need two scalars per edge (`hw . a` is made once per
  node), the softmax two passes over the edges (max, then sum of
  exponentials), and the weighted sum one sparse product;
- backward: the gradient to the senders' states is one sparse product the
  other way, the gradient to the weights `alpha` needs the dot of two rows
  per edge (a second sparse-product's worth of row reads), and the softmax
  and score gradients three more scalar passes. Layer 1 is differentiated
  too: its `hw` depends on `w_1`.
"""
from __future__ import annotations

FLOAT = 4
INDEX = 4


def spmm_bytes(nodes: int, edges: int, width: int) -> int:
    return edges * 2 * INDEX + edges * width * FLOAT + nodes * width * FLOAT


def edge_scalar_pass(nodes: int, edges: int) -> int:
    """Read two index columns and two node scalars per edge, write a scalar
    per node or per edge."""
    return edges * 2 * INDEX + edges * 2 * FLOAT + max(nodes, edges) * FLOAT


def terms(config: dict) -> dict:
    n, e = int(config["endpoints"]), int(config["edges"])
    f, h = int(config["num_features"]), int(config["hidden"])
    wide = n * h * FLOAT
    narrow = n * f * FLOAT
    params = f * h + h * h + 8 * h + 2 * h + 2 * (h + 1) + 2 * f
    directions = 2 * 2  # layers x edge directions
    return {
        "attention_sums": directions * 3 * spmm_bytes(n, e, h),
        "attention_scalars": directions * 6 * edge_scalar_pass(n, e),
        # forward: read x, write hw1, read hw1 + two sums, write h1; the same
        # at layer 2; read h2 and x for the readouts
        "dense_forward": 2 * narrow + 11 * wide,
        # backward: gradients of h2, hw2, h1, hw1 written and read; h2 and h1
        # read for the elu; h1 and x read for the weight gradients
        "dense_backward": 1 * narrow + 12 * wide,
        "readout": n * (2 * FLOAT + 1) + 4 * n * FLOAT,
        "optimizer": 7 * params * FLOAT,
    }


def slot_update_bytes(config: dict) -> int:
    return sum(terms(config).values())


def slot_update_flops(config: dict) -> int:
    n, e = int(config["endpoints"]), int(config["edges"])
    f, h = int(config["num_features"]), int(config["hidden"])
    products = (2 * n * f * h + 2 * n * h * h) * 3  # hw of both layers, fwd + bwd
    readouts = 2 * n * (h + f) * 2 * 3
    scores = 2 * 2 * 2 * n * h * 2 * 3  # hw . a, two vectors, two directions
    sums = 2 * 2 * e * h * 2 * 3  # weighted sums and their two gradients
    return products + readouts + scores + sums
