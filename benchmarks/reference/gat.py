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

# How the check reads FORWARD for this family (`reference/check.py` holds the
# defaults, one reading under 1e-6, and the rule). Read on the v5e at the
# cell's width in PR 32: each reading is the largest relative difference of the
# first slot's three losses at the program's own precision, over a one-slot
# history (slots 0, 1, 2 each alone); "middle" is the middle one of a seed's
# three, which is what the rule compares.
#
#   the sound program, 87 seeds    readings 0 .. 3.56e-6, 19 of 261 over 1e-6,
#                                  4 over 2.5e-6; 2 seeds with two of three
#                                  over 1e-6 (2147494001: 3.18e-6, 1.23e-6, 0;
#                                  2147496039: 8.6e-7, 2.01e-6, 1.20e-6);
#                                  middle 0 .. 1.23e-6
#   control: messages, 20 seeds    readings 6.6e-6 .. 1.7e-4, middle 1.25e-5 ..
#                                  1.1e-4
#   control: scores too, 20 seeds  readings 1.3e-6 .. 1.5e-4, middle 1.38e-5 ..
#                                  1.2e-4
#   layer outputs, 20 seeds        readings 9.5e-7 .. 2.5e-5, middle 3.22e-6 ..
#                                  2.2e-5: under 4e-6 on 3 seeds of 20
#   layer 1's output alone, 14     0 on all 42 readings
#
# Why the sound program reads over 1e-6: program and reference agree to an ulp
# of float32 in layer 1's output, and layer 2 multiplies `h1 @ W2` in ONE
# bfloat16 pass on both sides, so an element of h1 within an ulp of a bfloat16
# rounding boundary rounds the other way; at a hub of thousands of edges every
# caller's prediction moves the same way and the loss by 1e-6 to 4e-6. It is
# an event, not a spread (most readings are under 3e-7), it comes with the
# seed's graph and init more than with the slot, and nothing in the kernels is
# approximate.
#
# The controls are the plain forward in the program's place with one thing
# rounded to bfloat16 (`jax.lax.reduce_precision`). "Messages": the rows that
# the attention's weighted sums take, the cut a later PR is tempted by (one MXU
# pass a weighted sum for the three that float32 rows take); "scores too": the
# rows of its scores as well. Both are below what the configuration states
# (`matmul_precision`: a float32 PRODUCT is one bfloat16 pass; the attention's
# sums are no product, and the program keeps them float32-exact), both move
# every reading, and the MIDDLE of three separates them from the sound program
# by ten times where one reading does not (3.56e-6 against 6.6e-6 and 1.3e-6):
# three readings, and a bound between 1.23e-6 and 1.25e-5 with three times of
# room on either side.
#
# "Layer outputs" (each layer's output rounded) is NOT below what the
# configuration states, and the bound does not hold it: the parent's one
# reading (slot 0) under 1e-6 refused it on 20 seeds of 20 (and the sound
# program on 8 of 87), this bound refuses it on 17 seeds of 20. Layer 1's
# output enters nothing but `h1 @ W2`, which rounds it to bfloat16 itself:
# rounded beforehand it reads 0, the same bits. The whole shift is layer 2's
# output, which enters nothing but the two readouts `h2 @ w` of shape [64, 1]:
# float32 products, so one bfloat16 pass by the configuration's own words,
# which XLA happens to evaluate in float32 because a product with one column is
# cheaper off the MXU. Rounding h2 is the stated precision applied to one more
# product; its noise averages out over 95,000 endpoints to 1e-6 .. 2.5e-5 of
# the loss, which overlaps the sound program's events (middle 3.22e-6 against
# 1.23e-6 is under three times, so no bound stands between them), and no other
# number that `trainer.train` returns sees it (after one adamw update a
# parameter holds the sign of its gradient).
#
# A history stored in bfloat16 is none of the check's to see for this family,
# and the configuration states no such guarantee (struck in PR 32): its
# features meet nothing before `x @ W1`, which rounds them to bfloat16 anyway,
# and the readouts' skips start at zero, so on the chip the first slot's
# losses are the same bits (18 seeds x 3 slots). Of the 18 leaves only the two
# skip weights differ after the update, by 2e-7 .. 2e-6 of the learning rate
# (ulps), and each leaf's difference from the reference is the same number
# on the clean and on the rounded history (4 seeds x 3 slots, worst leaf and
# the two skips alike): a reading by the worst leaf does not see it either.
FORWARD = {"highest": (1e-2, 5e-1), "default": (4e-6, 2.5e-1)}
FORWARD_READINGS = 3


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
