"""Plain Principal Neighbourhood Aggregation forward (Corso et al., NeurIPS
2020, arXiv:2004.05718; PyTorch Geometric `PNAConv`), written from the
docstring of `kmamiz_tpu/models/pna.py` and independent of its code: float32,
`jax.numpy` and `jax.ops.segment_*`, no edge mask, no bucket padding, no plan,
no kernel.

A node's neighbourhood N(i) is its callers AND its callees (both directions of
every distance-1 edge), a multiset: an endpoint that is both makes two entries.
d_i = |N(i)|, clamped below at 1 where it divides or scales. Per layer:

    m_j  = h_j W_m                                                       (every product a float32 one)
    mu_i = sum_j m_j / d_i        sd_i = sqrt(relu(sum_j m_j^2 / d_i - mu_i^2) + 1e-5)
    mx_i = max_j m_j              mn_i = min_j m_j
    s_i  = log(d_i + 1) / delta   a_i  = [mu | sd | mx | mn]_i
    h_i' = relu(h_i W_s + [a_i | s_i a_i | a_i / s_i] W_a + b)

two layers, then GraphSAGE's readouts with their feature skips.

Ties and empty sets are part of the mathematics. `jax.ops.segment_max` of an
empty segment is -inf (`segment_min`: +inf): both are masked to 0 by the
degree, here, in the open. Its gradient hands a maximum that several entries
share to them in equal parts (values [1, 3, 3, 2] in one segment: gradient
[0, 0.5, 0.5, 0]); the program's kernels must agree with it.

Departures from the paper and from `PNAConv`, each the repository's:

- the neighbourhood is undirected (callers and callees), as GraphSAGE's cell
  takes it, so that the two cells differ in the aggregator alone;
- the message is `W_m h_j` alone: the paper's M(h_i, h_j) is linear, and its
  h_i part leaves every aggregator as a constant, so it is the update's own
  `h_i W_s` term;
- `delta` is the mean of log(d + 1) over the endpoints that HAVE a neighbour,
  where `PNAConv` takes it over every node of the training graphs, isolated
  ones too: an endpoint without a neighbour aggregates nothing, and counted
  this way the constant does not move with the rows that pad a node bucket
  in the served forward (213 of the cell's 100,000 endpoints are isolated:
  0.2% of the constant);
- towers 1, one linear layer before and after the aggregation, no batch or
  graph normalisation, no residual, hidden 64 and two layers as the
  deployment's siblings serve: the paper's own settings are not in the
  repository;
- the readouts add a linear skip from the raw features, as its siblings' do.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

EPS = 1e-5
#: every matrix product is a float32 product whatever precision is in force
#: around it, in the reference and in the program alike (the configuration's
#: `exact_products` says why), so this family's "default" and "highest" are one
#: computation, and the check's two comparisons read the same numbers
EXACT = jax.lax.Precision.HIGHEST
dot = functools.partial(jnp.dot, precision=EXACT)

# The family states no FORWARD bounds of its own: the default rule of
# `reference/check.py` (one reading, slot 0, within 1e-6) stands. Read on the
# v5e at the cell's width in PR 39 (each reading the largest relative
# difference of the first slot's three losses over a one-slot history; three
# a seed, slots 0, 1, 2 each alone; "highest" and "default" are one
# computation here, so the two comparisons read the same numbers up to the
# order of two compilations' sums):
#
#   the sound program, 37 seeds    111 readings 0 .. 2.37e-7 (slot 0: 2.22e-7);
#                                  schedule.*.loss at most 8.4e-5 (of 1e-3 and
#                                  5e-2; median 4.5e-7), the parameters 0.0105
#                                  (of 0.25)
#   control: bfloat16 rows, 37     `forward_bfloat16_rows` below in the
#   seeds                          program's place: 111 readings 5.8e-5 ..
#                                  1.6e-3, slot 0 7.5e-5 .. 1.6e-3: over 1e-6
#                                  on 37 seeds of 37, by 74 times at the least
#                                  (its three-slot loss parts by 3.6e-4 ..
#                                  0.13)
#
#   a history stored in bfloat16,  the PROGRAM on features rounded to bfloat16
#   12 seeds (read with `h W_s`    (the reference on the clean ones): slot 0
#   and the readouts still at one  4.5e-6 .. 3.6e-4, over 1e-6 on 12 of 12:
#   bfloat16 pass)                 the message product reads the features at
#                                  float32, so the check holds the history's
#                                  storage for this head (the configuration
#                                  states it)
#
# The bound sits 4 times over the one and 74 times under the other.
#
# That is so BECAUSE every product is float32 (`EXACT`), and there are two
# reasons, read in this order. (1) The two products beside the aggregation.
# With both as one bfloat16 pass, in program and reference alike (12 seeds,
# the same harness): readings 0 .. 2.34e-5, 11 of 36 over 1e-6, the middle of
# a seed's three up to 6.6e-6; with the update alone float32, all 24 readings
# under 2.1e-7. A maximum hands ONE neighbour's value on unaveraged and the
# scalers amplify it up to 4.5 times, so where program and reference round an
# element of the update's input to bfloat16 on either side of a rounding
# boundary (their sums differ in the last bit), every caller of a hub moves
# the same way: GAT's events (`reference/gat.py`), three times as often and
# six times as large. (2) The rest (`h W_s`, the readouts, the feature skips).
# adamw's first update at lr 1e-2 moves the weight of each of the update's 768
# inputs by a full lr at once: the reference's own losses over the first six
# slots of one history read 13.4, 5575, 48.8, 20.2, 9.7, 4.5. SCHEDULE's three
# slots hold that second one, and what two correct implementations differ by
# after the first slot is multiplied some 200 times in it (the sound program's
# 2.4e-7 becomes 8.4e-5). So two correct implementations one of which rounds
# (the program at "default" against this reference at "highest") parted by up
# to 5.9e-2 of the three-slot loss with every product at one bfloat16 pass,
# and, with the two products beside the aggregation float32 and the small ones
# not, by 1.6e-2 at most on 36 seeds and by 7.2e-2 on one of the driver's
# (seed 1229403837, `correct` false): over SCHEDULE's 5e-2, which is the
# harness's and no family's to widen. The head states every product at
# float32, as `mv100k-stlgt` states two of its own, and pays for them
# (PERF.md).


def aggregates(m, src, dst, gathered=lambda rows: rows):
    """(mean, deviation, maximum, minimum) of each node's neighbours' rows,
    [N, 4 W], and the degree [N, 1]. `gathered` is the identity (the control
    below passes another)."""
    n = m.shape[0]
    receiver = jnp.concatenate([src, dst])
    rows = gathered(m[jnp.concatenate([dst, src])])
    degree = jax.ops.segment_sum(jnp.ones(receiver.shape, m.dtype), receiver, num_segments=n)[:, None]
    d = jnp.maximum(degree, 1.0)
    mean = jax.ops.segment_sum(rows, receiver, num_segments=n) / d
    squares = jax.ops.segment_sum(rows * rows, receiver, num_segments=n) / d
    deviation = jnp.sqrt(jax.nn.relu(squares - mean * mean) + EPS)
    # an empty neighbourhood's maximum is -inf and its minimum +inf: 0 by the degree
    top = jnp.where(degree > 0, jax.ops.segment_max(rows, receiver, num_segments=n), 0.0)
    bottom = jnp.where(degree > 0, jax.ops.segment_min(rows, receiver, num_segments=n), 0.0)
    return jnp.concatenate([mean, deviation, top, bottom], axis=1), degree


def layer(h, src, dst, w_m, w_s, w_a, b, **how):
    a, degree = aggregates(dot(h, w_m), src, dst, **how)
    logs = jnp.log(jnp.maximum(degree, 1.0) + 1.0)
    held = degree > 0
    delta = jnp.sum(jnp.where(held, logs, 0.0)) / jnp.maximum(jnp.sum(held), 1)
    s = logs / jnp.where(delta > 0, delta, 1.0)
    update = dot(jnp.concatenate([a, s * a, a / s], axis=1), w_a)
    return jax.nn.relu(dot(h, w_s) + update + b)


def forward(p: dict, x, src, dst, **how):
    """(latency prediction [N], anomaly logit [N])."""
    h1 = layer(x, src, dst, p["w_msg_1"], p["w_self_1"], p["w_agg_1"], p["b_1"], **how)
    h2 = layer(h1, src, dst, p["w_msg_2"], p["w_self_2"], p["w_agg_2"], p["b_2"], **how)
    latency = dot(h2, p["w_latency"]) + dot(x, p["w_latency_skip"]) + p["b_latency"]
    logit = dot(h2, p["w_anomaly"]) + dot(x, p["w_anomaly_skip"]) + p["b_anomaly"]
    return latency[:, 0], logit[:, 0]


# -- the check's control: what cheaper gathered rows would compute -------------


@jax.custom_vjp
def _bfloat16_rows(rows):
    """Gathered rows as a bfloat16 gather would deliver them; the cotangent passes.
    Not `astype`: on the chip XLA drops a convert pair (excess precision)."""
    return jax.lax.reduce_precision(rows, exponent_bits=8, mantissa_bits=7)


_bfloat16_rows.defvjp(lambda rows: (_bfloat16_rows(rows), None), lambda _, g: (g,))


def forward_bfloat16_rows(p: dict, x, src, dst):
    """NOT the reference: `forward` with the gathered message rows of both
    layers rounded to bfloat16 before they are summed, squared and compared:
    what a gather of half the bytes, or sums in ONE bfloat16 pass for the three
    that float32 rows take, would compute. The check must read `correct` false
    with this in the program's place (on the chip at the cell's width: the
    readings above)."""
    return forward(p, x, src, dst, gathered=_bfloat16_rows)
