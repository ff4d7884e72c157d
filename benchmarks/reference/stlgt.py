"""Plain STLGT forward and loss (STLGT: A Scalable Trace-Based Linear Graph
Transformer for Tail Latency Prediction in Microservices, arXiv:2604.26422),
written from the docstrings of `kmamiz_tpu/models/stlgt/model.py` and
independent of its code: no lane mask, no bucket padding, no edge mask, no
edge plan, no segment ops; the sums over edges are `.at[].add`.

One block. `h = relu(x @ W_in + b_in)`; `q = phi(h @ W_q)`, `k = phi(h @ W_k)`,
`v = h @ W_v`, `phi = elu + 1`. Two channels are added and projected:

- the global linear attention `q @ (k.T @ v) / (q @ sum k + 1e-6)` over every
  endpoint: softmax-free, linear in the number of endpoints;
- the neighbour bias: every edge u -> v (u calls v) has the gate
  `sigmoid(q[u] . k[v] / sqrt(H) + b_edge)`; v receives `gate * v[u]` and u
  receives `gate * v[v]` (callers and callees are both signal), and an
  endpoint's sum is divided by `max(sum of its gates, 1)`.

`h1 = h + relu((attention + bias) @ W_o)`, `h2 = h1 + relu(relu(h1 @ W_f1 +
b_f1) @ W_f2 + b_f2)`. Readouts: `raw = h2 @ W_quant + x @ W_quant_skip +
b_quant`; p50 = raw[0], p95 = p50 + softplus(raw[1]), p99 = p95 +
softplus(raw[2]), so the levels cannot cross; the anomaly logit is `h2 @
W_anomaly + x @ W_anomaly_skip + b_anomaly`.

The loss is the family's own (`make_loss`): over the endpoints active in the
next slot, the pinball loss `max(tau * d, (tau - 1) * d)`, `d = target -
prediction`, summed over tau = 0.50, 0.95, 0.99, plus the trainer's weighted
sigmoid cross-entropy of the anomaly logit (`reference/train.py`).

Departures of the repo's head from the paper, as its docstrings give them (the
paper itself is not in the repository): one block where the paper stacks
them; the neighbour structure enters as an ADDITIVE gated bias beside the
global attention, not as a mask of it, normalised by the gates' own sum
floored at 1; both edge directions carry messages under one gate; the
readouts take a linear skip from the raw features (persistence dominates the
next hour's latency, so the trunk learns residuals; both skips start at
zero); the anomaly logit is the family's second head, not the paper's; the
program zeroes q, k, v and the state of an all-zero feature row (bucket
padding), which no row of a real history is, so the reference has no such
mask.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

QUANTILES = (0.50, 0.95, 0.99)

# How the check reads FORWARD for this family: by `reference/check.py`'s
# defaults, ONE reading (slot 0) within 1e-6 at the program's own precision.
# The family states no `FORWARD` and no `FORWARD_READINGS` of its own, because
# the defaults hold with room. Read on the v5e at the cell's width in PR 33;
# each reading is the largest relative difference of the first slot's three
# losses at "default", over a one-slot history (slots 0, 1, 2 each alone):
#
#   the sound program, 75 seeds    60 seeds x 3 slots: 180 readings 0 ..
#                                  7.02e-7; slot 0 over all 75 (15 of them
#                                  whole runs of the cell): 0 .. 6.91e-7
#   control: rows, 12 seeds        36 readings 2.68e-6 .. 2.6e-5 (slot 0:
#                                  5.16e-6 .. 1.75e-5): 12 of 12 fail
#   control: dot too, 12 seeds     36 readings 2.53e-6 .. 2.6e-5 (slot 0:
#                                  5.05e-6 .. 1.75e-5): 12 of 12 fail
#   control: pass, 12 seeds        36 readings 1.88e-6 .. 1.1e-4 (slot 0:
#                                  5.51e-6 .. 1.1e-4): 12 of 12 fail
#   control: sums, 12 seeds        36 readings 4.05e-6 .. 1.9e-4 (slot 0:
#                                  1.44e-5 .. 1.1e-4): 12 of 12 fail
#
# so 1e-6 stands 1.4 times over the largest sound reading and 5 times under
# the smallest the controls gave it (1.9 times under the smallest of any
# slot). The sound readings are a spread of a few units in the last place
# (float32's is 1.2e-7; the median reading is 1.7e-7), not events. The controls are
# this forward in the program's place with one thing below float32
# (`jax.lax.reduce_precision`): "rows", the rows `v[sender]` that the gated sum
# weighs rounded to bfloat16 (one MXU pass a sum for the three that float32
# rows take: the cut a later PR is tempted by); "dot too", the rows of the
# gate's dot product as well. Neither is a matrix product of the configuration,
# and the program keeps both float32-exact (`ops/sparse_gated.py`). "pass": the
# two products that read the all-endpoint sums, `q @ kv` and `q @ z`, as the
# precision in force makes them (one bfloat16 pass on the chip), against this
# reference, which makes them float32; "sums": `kv` and `z` rounded to bfloat16
# before an exact product. These two show that the check HOLDS the float32 that
# the configuration states for the two products (`exact_products`): every
# element of `kv` is then off by up to 2^-9, not one element by an ulp.
#
# It did NOT hold before the two products that read the all-endpoint sums were
# made exact (`forward` below, and `models/stlgt/model.encode`): with `q @ kv`
# and `q @ z` as one bfloat16 pass on both sides the sound program read 0 ..
# 3.53e-6 over 36 readings of 12 other seeds, 5 of them over 1e-6, the middle
# of three up to 2.99e-6 (one seed had two of three over), and the controls
# 9.6e-7 .. 3.3e-5, middle of three from 4.06e-6: no bound stands between.
# `kv = k.T @ v` and `z = sum k` are sums over EVERY endpoint, so they are a
# common term of every endpoint's state: program and reference sum them in
# another order, agree to an ulp of float32, and an element within an ulp of a
# bfloat16 rounding boundary then rounds the other way for all 100,000 rows of
# `q @ kv` at once (2^-9 of that term in every state, 1e-6 .. 4e-6 of the
# loss, one reading in seven). An event, not a spread, and nothing in the
# kernels: so the cause was cured (six passes of a `[N, 64] x [64, 64]`
# product cost nothing) and no limit widened. `k.T @ v` itself rounds each
# endpoint's row, which averages out over the endpoints, and stays one pass.
#
# A history stored in bfloat16 is none of the check's to see for this family
# (as for GAT), and the configuration states no such guarantee: on the chip the
# first slot's losses are the same BITS on the clean and on the rounded history
# (6 readings of 6; and 12 of 12 before the cure). The features meet nothing
# before `x @ W_in`, one bfloat16 pass that rounds them anyway, and the two
# feature skips start at zero.


def phi(x):
    """elu + 1: the positive feature map of kernelized linear attention."""
    return jnp.where(x > 0, x + 1.0, jnp.exp(jnp.minimum(x, 0.0)))


def neighbour_bias(q, k, v, b_edge, src, dst):
    """Each endpoint's gate-weighted mean of its callers' and callees' values."""
    n, width = q.shape
    gate = jax.nn.sigmoid((q[src] * k[dst]).sum(axis=1) / jnp.sqrt(jnp.float32(width)) + b_edge[0])
    total = jnp.zeros_like(v).at[dst].add(gate[:, None] * v[src]).at[src].add(gate[:, None] * v[dst])
    weight = jnp.zeros(n, v.dtype).at[dst].add(gate).at[src].add(gate)
    return total / jnp.maximum(weight, 1.0)[:, None]


def forward(p: dict, x, src, dst):
    """(latency quantiles [N, 3] in the target's units, anomaly logit [N])."""
    h = jax.nn.relu(x @ p["w_in"] + p["b_in"])
    q, k, v = phi(h @ p["w_q"]), phi(h @ p["w_k"]), h @ p["w_v"]
    # the two products that read a sum over every endpoint are float32 products
    # at any precision in force, as the program makes them (the note above)
    exact = jax.lax.Precision.HIGHEST
    attention = jnp.matmul(q, k.T @ v, precision=exact) / (jnp.matmul(q, k.sum(axis=0), precision=exact) + 1e-6)[:, None]
    mixed = attention + neighbour_bias(q, k, v, p["b_edge"], src, dst)
    h1 = h + jax.nn.relu(mixed @ p["w_o"])
    h2 = h1 + jax.nn.relu(jax.nn.relu(h1 @ p["w_f1"] + p["b_f1"]) @ p["w_f2"] + p["b_f2"])
    raw = h2 @ p["w_quant"] + x @ p["w_quant_skip"] + p["b_quant"]
    p50 = raw[:, 0]
    p95 = p50 + jax.nn.softplus(raw[:, 1])
    p99 = p95 + jax.nn.softplus(raw[:, 2])
    logit = h2 @ p["w_anomaly"] + x @ p["w_anomaly_skip"] + p["b_anomaly"]
    return jnp.stack([p50, p95, p99], axis=1), logit[:, 0]


def make_loss(weight: float):
    """(total, (quantile loss, anomaly loss)): what `trainer.train` reports as
    `losses`, `latency_losses`, `anomaly_losses` for this head."""
    taus = jnp.asarray(QUANTILES, jnp.float32)

    def loss(params, x, src, dst, target_latency, target_anomaly, active):
        quantiles, logit = forward(params, x, src, dst)
        w = active.astype(jnp.float32)
        count = jnp.maximum(w.sum(), 1.0)
        d = target_latency[:, None] - quantiles
        quantile_loss = jnp.sum(w[:, None] * jnp.maximum(taus * d, (taus - 1.0) * d)) / count
        bce = (
            jnp.maximum(logit, 0.0)
            - logit * target_anomaly
            + jnp.log1p(jnp.exp(-jnp.abs(logit)))
        )
        class_weight = 1.0 + (weight - 1.0) * target_anomaly
        anomaly_loss = jnp.sum(w * class_weight * bce) / count
        return quantile_loss + anomaly_loss, (quantile_loss, anomaly_loss)

    return loss
