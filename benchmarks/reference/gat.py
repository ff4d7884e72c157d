"""Plain graph-attention forward (Velickovic et al., arXiv:1710.10903, one
head), written from the docstrings of `kmamiz_tpu/models/gat.py` and
independent of its code: no edge mask, no bucket padding, no segment ops.

Per layer: `hw = h @ W`. For every edge u->v the score is
`leaky_relu(hw[u] . a_src + hw[v] . a_dst, 0.2)`, normalised by a softmax
over all edges INTO v, and v receives `sum alpha * hw[u]`. The reverse
direction (v's state into u, softmax over all edges OUT of u) has its own
attention vectors. The layer is `elu(hw + forward + reverse + b)` (the
repo's departure from the paper: two directions, and the self term added
outside the softmax). Readouts as in GraphSAGE, with the feature skip.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

LEAK = 0.2


def attend(hw, sender, receiver, a_send, a_recv):
    """Each receiver's softmax-weighted sum of its senders' states."""
    n = hw.shape[0]
    score = jax.nn.leaky_relu(
        hw[sender] @ a_send + hw[receiver] @ a_recv, negative_slope=LEAK
    )
    top = jnp.full(n, -jnp.inf, score.dtype).at[receiver].max(score)
    weight = jnp.exp(score - top[receiver])
    total = jnp.zeros(n, score.dtype).at[receiver].add(weight)
    alpha = weight / total[receiver]
    return jnp.zeros_like(hw).at[receiver].add(hw[sender] * alpha[:, None])


def layer(h, src, dst, w, a_s, a_d, a_sr, a_dr, b):
    hw = h @ w
    return jax.nn.elu(
        hw + attend(hw, src, dst, a_s, a_d) + attend(hw, dst, src, a_sr, a_dr) + b
    )


def forward(p: dict, x, src, dst):
    """(latency prediction [N], anomaly logit [N])."""
    h1 = layer(x, src, dst, p["w_1"], p["a_src_1"], p["a_dst_1"],
               p["a_src_1r"], p["a_dst_1r"], p["b_1"])
    h2 = layer(h1, src, dst, p["w_2"], p["a_src_2"], p["a_dst_2"],
               p["a_src_2r"], p["a_dst_2r"], p["b_2"])
    latency = h2 @ p["w_latency"] + x @ p["w_latency_skip"] + p["b_latency"]
    logit = h2 @ p["w_anomaly"] + x @ p["w_anomaly_skip"] + p["b_anomaly"]
    return latency[:, 0], logit[:, 0]
