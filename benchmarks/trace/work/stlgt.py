"""Bytes and operations one STLGT slot update must move, from the
configuration's shapes alone (`reference/stlgt.py` is the mathematics).

A LOWER bound on HBM traffic, on the assumptions of `work/gat.py` and
`work/graphsage.py`: a sparse product reads its index pair and one gathered
row per edge and moves one row per node; a dense stage reads its input and
writes its output once; whatever implements them. One block, so one set of
gated reductions, counted per EDGE (both directions share the gate):

- forward: the gate needs the dot of two rows per edge (one sparse product's
  worth of row reads), each direction's weighted sum is one sparse product;
- backward: the gradient to the values is one sparse product per direction
  the other way, the gradient to a gate needs `<g[receiver], v[sender]>` per
  direction (two more), and the gradients to q and to k are one sparse
  product each (`d a * k[callee]` into the caller, `d a * q[caller]` into
  the callee): nine in all. The gate, its derivative, the two degree sums and
  their gradients are passes over a scalar per edge.
- the global linear attention reads k and v once for `k.T @ v` and the
  normaliser, q once for `q @ kv`, and writes the result; backward it reads
  the result's gradient, q, k and v and writes three gradients.
"""
from __future__ import annotations

FLOAT = 4
INDEX = 4


def spmm_bytes(nodes: int, edges: int, width: int) -> int:
    return edges * 2 * INDEX + edges * width * FLOAT + nodes * width * FLOAT


def edge_scalar_pass(nodes: int, edges: int) -> int:
    """Read two index columns and two scalars per edge, write a scalar per
    node or per edge."""
    return edges * 2 * INDEX + edges * 2 * FLOAT + max(nodes, edges) * FLOAT


def parameters(f: int, h: int) -> int:
    block = f * h + h + 4 * h * h + 1 + 2 * (h * h + h)
    readouts = 3 * h + 3 + 3 * f + h + 1 + f
    return block + readouts


def terms(config: dict) -> dict:
    n, e = int(config["endpoints"]), int(config["edges"])
    f, h = int(config["num_features"]), int(config["hidden"])
    wide = n * h * FLOAT
    narrow = n * f * FLOAT
    return {
        "gated_sums": 9 * spmm_bytes(n, e, h),
        "gated_scalars": 6 * edge_scalar_pass(n, e),
        # read x, write h; read h, write q, k, v; read the two channels and h,
        # write h1; read h1, write the FFN's middle; read it and h1, write h2
        "dense_forward": 1 * narrow + 14 * wide,
        # the gradients of h2, the middle, h1, the mixed channels, q, k, v and
        # h written and read; h, h1 and the middle read again for the weight
        # gradients and the relus; x read for d W_in
        "dense_backward": 1 * narrow + 19 * wide,
        "linear_attention": (4 + 7) * wide,
        # targets and the mask read, four outputs written, their gradients
        # written and read; x read for the two skips, forward and backward
        "readout": n * (2 * FLOAT + 1) + 3 * 4 * n * FLOAT + 2 * narrow,
        "optimizer": 7 * parameters(f, h) * FLOAT,
    }


def slot_update_bytes(config: dict) -> int:
    return sum(terms(config).values())


def slot_update_flops(config: dict) -> int:
    n, e = int(config["endpoints"]), int(config["edges"])
    f, h = int(config["num_features"]), int(config["hidden"])
    products = (2 * n * f * h + 6 * 2 * n * h * h) * 3  # W_in; W_q, W_k, W_v, W_o, W_f1, W_f2
    attention = (2 * 2 * n * h * h + 2 * 2 * n * h) * 3  # k.T @ v, q @ kv, sum k, q @ z
    readouts = 2 * n * (h + f) * 4 * 3
    gated = (2 * e * h + 2 * 2 * e * h) * 3  # the gate's dot, two weighted sums
    return products + attention + readouts + gated
