"""Plain GraphSAGE forward for the node-sharded configuration, `mv400k-sage`.

The plain reference is unsharded by nature: it holds the whole graph and one
`[N, width]` table a layer on one device, and knows no mesh, no plan and no
all-gather. So this is the family's COPY of `reference/graphsage.py`, the same
mathematics line for line (Hamilton et al., arXiv:1706.02216, mean aggregator
over both directions of every distance-1 edge, two layers, a linear skip from
the raw features into both readouts), written from the docstrings of
`kmamiz_tpu/models/graphsage.py` and independent of its code. What the
sharded program must reproduce is exactly this: how the rows are cut over
chips is no part of the result.

It states no FORWARD bounds of its own. The sharded program adds each of the
loss's three sums from four partial sums (`common.make_loss_fn`, `axis_name`),
where the one-chip program adds 131,072-row blocks in XLA's own order: a
different order of the same float32 additions, not another precision. Read on
the v5e at the cell's width (PERF.md section 6, PR 35): the sound program's
largest `forward.default.loss` and the smallest of its control (this
reference in the program's place with every gathered table rounded to
bfloat16, `forward_bfloat16_wire` below) lie on either side of the default bound,
1e-6, so the default FORWARD rule (`reference/check.py`) stands.
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


# -- the check's control: what a cheaper wire would compute --------------------


def _bfloat16(v):
    # not `astype`: on the chip XLA drops a convert pair (excess precision)
    return jax.lax.reduce_precision(v, exponent_bits=8, mantissa_bits=7)


@jax.custom_vjp
def _sent(h):
    """A table as a bfloat16 wire would deliver it."""
    return _bfloat16(h)


_sent.defvjp(lambda h: (_bfloat16(h), None), lambda _, g: (g,))


@jax.custom_vjp
def _received(total):
    """A neighbour sum whose cotangent is gathered over a bfloat16 wire."""
    return total


_received.defvjp(lambda total: (total, None), lambda _, g: (_bfloat16(g),))


def forward_bfloat16_wire(p: dict, x, src, dst):
    """NOT the reference: `forward` with every table that the sharded program
    all-gathers (layer 1's features, layer 2's `h1`, and the cotangent of each
    neighbour sum on its way back) rounded to bfloat16, which is what sending
    half the bytes over ICI would compute. The check must read `correct` false
    with this in the program's place (`benchmarks/tests/`, and on the chip at
    the cell's width: PERF.md section 6, PR 35)."""

    def mean_over_the_wire(h, src, dst):
        n = h.shape[0]
        low = _sent(h)
        total = _received(jnp.zeros_like(h).at[src].add(low[dst]).at[dst].add(low[src]))
        degree = jnp.zeros(n, h.dtype).at[src].add(1.0).at[dst].add(1.0)
        return total / jnp.maximum(degree, 1.0)[:, None]

    h1 = jax.nn.relu(
        x @ p["w_self_1"] + mean_over_the_wire(x, src, dst) @ p["w_neigh_1"] + p["b_1"]
    )
    h2 = jax.nn.relu(
        h1 @ p["w_self_2"] + mean_over_the_wire(h1, src, dst) @ p["w_neigh_2"] + p["b_2"]
    )
    latency = h2 @ p["w_latency"] + x @ p["w_latency_skip"] + p["b_latency"]
    logit = h2 @ p["w_anomaly"] + x @ p["w_anomaly_skip"] + p["b_anomaly"]
    return latency[:, 0], logit[:, 0]
