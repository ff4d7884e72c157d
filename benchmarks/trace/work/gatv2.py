"""Bytes and operations one GATv2 slot update must move, from the
configuration's shapes alone (`reference/gatv2.py` is the mathematics).

A LOWER bound on HBM traffic, on the assumptions of `work/graphsage.py` and
`work/gat.py`: a sparse pass reads its index pair and one gathered row per
edge and direction, and moves each per-node table once; a dense stage reads
its input and writes its output once; padding is skipped; whatever implements
them. Both layers attend over rows of the hidden width (`xl = h W_l`,
`xr = h W_r`), and both are differentiated: layer 1's rows depend on `w_l_1`
and `w_r_1`. Per edge direction and layer:

- the sums, as GAT's (`work/gat.py`'s `attention_sums`): the forward's
  weighted sum of the neighbours' `xl` rows, the backward's transposed sum of
  the owners' cotangent rows, and `d alpha`'s dot product of two rows an edge;
- the scores, which GAT makes from two scalars a node and GATv2 cannot: the
  forward's `z = xl[j] + xr[i]` reads a neighbour's ROW an edge and the
  owner's row once a node; the backward needs `z` twice more, for the edge's
  own `d z` (which goes to the owner, `d xr[i]`) and for the edge seen from
  its other end (whose `d z` goes to the neighbour, `d xl[j]`): three row
  passes. The softmax's scalar passes (the maximum, the exponentials' sum,
  alpha, `d alpha`, `c` and `d score`) are counted as `work/gat.py` counts
  them.
"""
from __future__ import annotations

FLOAT = 4
INDEX = 4
LAYERS = 2
DIRECTIONS = 2


def spmm_bytes(nodes: int, edges: int, width: int) -> int:
    """One direction of one row pass: the index pair and a row per edge, a row per node."""
    return edges * 2 * INDEX + edges * width * FLOAT + nodes * width * FLOAT


def edge_scalar_pass(nodes: int, edges: int) -> int:
    """Read two index columns and two scalars per edge, write a scalar per
    node or per edge."""
    return edges * 2 * INDEX + edges * 2 * FLOAT + max(nodes, edges) * FLOAT


def terms(config: dict) -> dict:
    n, e = int(config["endpoints"]), int(config["edges"])
    f, h = int(config["num_features"]), int(config["hidden"])
    wide = n * h * FLOAT
    narrow = n * f * FLOAT
    params = 2 * f * h + 2 * h * h + 2 * (2 * h + h) + 2 * (h + 1) + 2 * f
    passes = LAYERS * DIRECTIONS
    return {
        "gatv2_sums": passes * 3 * spmm_bytes(n, e, h),
        "gatv2_scores": passes * 3 * spmm_bytes(n, e, h) + passes * 6 * edge_scalar_pass(n, e),
        # a layer: read its input, write xl and xr, read xl and the attention's sum, write h'. Readouts: h2 and x
        "dense_forward": 2 * narrow + 11 * wide,
        # a layer: d h' written and read, h' read for the elu, d xl and d xr written and read, the input read for
        # the weight gradients (layer 1's input is data: no gradient of its own)
        "dense_backward": narrow + 14 * wide,
        # targets, mask, two predictions and their gradients
        "readout": n * (2 * FLOAT + 1) + 4 * n * FLOAT,
        # adamw: read params, grads and two moments, write params and moments
        "optimizer": 7 * params * FLOAT,
    }


def slot_update_bytes(config: dict) -> int:
    return sum(terms(config).values())


def slot_update_flops(config: dict) -> int:
    """Multiply-adds count two; forward, and twice that again backward."""
    n, e = int(config["endpoints"]), int(config["edges"])
    f, h = int(config["num_features"]), int(config["hidden"])
    products = (2 * 2 * n * f * h + 2 * 2 * n * h * h) * 3  # xl and xr of both layers, fwd + bwd
    readouts = 2 * n * (h + f) * 2 * 3
    scores = LAYERS * DIRECTIONS * e * h * 4 * 3  # z, leaky, the product with a_d and its sum, an element
    sums = LAYERS * DIRECTIONS * e * h * 2 * 3  # weighted sums and their two gradients
    return products + readouts + scores + sums
