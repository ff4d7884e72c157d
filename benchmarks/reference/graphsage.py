"""Plain GraphSAGE forward (Hamilton et al., arXiv:1706.02216, mean
aggregator), written from the docstrings of `kmamiz_tpu/models/graphsage.py`
and independent of its code: no edge mask, no bucket padding, no segment
ops, no fused kernel.

A node's neighbours are its callers AND its callees (both directions of
every distance-1 edge). Two layers; each is
`relu(h @ W_self + mean_neighbours(h) @ W_neigh + b)`; a node with no
neighbour aggregates zeros. Both readouts add a linear skip from the raw
features (the repo's departure from the paper: persistence dominates both
targets, so the trunk learns residuals).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def neighbour_mean(h, src, dst):
    n = h.shape[0]
    total = jnp.zeros_like(h).at[src].add(h[dst]).at[dst].add(h[src])
    degree = jnp.zeros(n, h.dtype).at[src].add(1.0).at[dst].add(1.0)
    return total / jnp.maximum(degree, 1.0)[:, None]


def forward(p: dict, x, src, dst):
    """(latency prediction [N], anomaly logit [N])."""
    h1 = jax.nn.relu(
        x @ p["w_self_1"] + neighbour_mean(x, src, dst) @ p["w_neigh_1"] + p["b_1"]
    )
    h2 = jax.nn.relu(
        h1 @ p["w_self_2"] + neighbour_mean(h1, src, dst) @ p["w_neigh_2"] + p["b_2"]
    )
    latency = h2 @ p["w_latency"] + x @ p["w_latency_skip"] + p["b_latency"]
    logit = h2 @ p["w_anomaly"] + x @ p["w_anomaly_skip"] + p["b_anomaly"]
    return latency[:, 0], logit[:, 0]
