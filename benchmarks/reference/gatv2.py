"""Plain GATv2 forward (Brody, Alon and Yahav, "How Attentive are Graph
Attention Networks?", ICLR 2022, arXiv:2105.14491; PyTorch Geometric
`GATv2Conv`), written from the equations in the docstring of
`kmamiz_tpu/models/gatv2.py` and independent of its code: float32,
`jax.numpy` and `.at[]` sums, no edge mask, no bucket padding, no plan, no
kernel.

Per layer: `xl = h @ W_l`, `xr = h @ W_r`. For every edge u -> v (u calls v)
two scores are made, one for each end that receives:

- v receives from its caller u: `a_in . leaky_relu(xl[u] + xr[v], 0.2)`,
  normalised by a softmax over all edges INTO v;
- u receives from its callee v: `a_out . leaky_relu(xl[v] + xr[u], 0.2)`,
  normalised by a softmax over all edges OUT of u;

and a receiver gets `sum alpha * xl[sender]`. The score is an elementwise
product summed over the width (`jnp.sum(leaky(z) * a, -1)`), so that no
matrix-vector product, and no bfloat16 pass of one, enters it. The layer is
`elu(xl + from_callers + from_callees + b)`; two layers, then GraphSAGE's
readouts with their feature skips.

Departures from `GATv2Conv`, each the repository's:

- both edge directions, each with its own attention vector (`a[0]` for the
  edges out of the receiver, `a[1]` for the edges into it), as `mv100k-gat`
  takes them: callers and callees are both signal;
- `W_l` and `W_r` shared by the two directions (`share_weights` false: the
  sender's and the receiver's projections differ, and neither is per
  direction);
- the self term `xl[i]` added outside the softmax, where `GATv2Conv` adds a
  self-loop inside it (`add_self_loops`), as `mv100k-gat` does;
- one head, no dropout, no bias inside the attention, hidden 64 and two layers
  as the deployment's siblings serve;
- the readouts add a linear skip from the raw features, as its siblings' do.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

LEAK = 0.2
#: every matrix product is a float32 product whatever precision is in force
#: around it, in the reference and in the program alike (the configuration's
#: `exact_products` says why), so this family's "default" and "highest" are one
#: computation, and the check's two comparisons read the same numbers
EXACT = jax.lax.Precision.HIGHEST
dot = functools.partial(jnp.dot, precision=EXACT)

# How the check reads FORWARD for this family: the middle of three readings
# (slots 0, 1, 2, each alone) within 5e-7, where the default is one reading
# (slot 0) within 1e-6 (`reference/check.py`). Read on the v5e at the cell's
# width; each reading is the largest relative difference of the first slot's
# three losses at the program's own precision over a one-slot history, the
# "middle" the middle one of a seed's three:
#
#   the sound program, every       108 readings of 36 seeds 0 .. 2.54e-7 (78
#   product float32 (as stated)    of them 0: two compilations' sums in
#                                  another order), middle 0 .. 1.02e-7; six
#                                  whole runs of the cell under this rule:
#                                  middle 0 .. 1.39e-7; slot 0 alone of 10
#                                  more whole runs 0 .. 8.8e-8
#   control: z rounded to          `forward_bfloat16_z` in the program's
#   bfloat16, 14 seeds             place: 42 readings 5.4e-7 .. 9.2e-6, middle
#                                  1.32e-6 .. 4.60e-6; slot 0 alone 8.56e-7 ..
#                                  8.24e-6: UNDER 1e-6 on one seed of 14
#   control: the gathered xl rows  `forward_bfloat16_rows`: 27 readings 1.5e-6
#   rounded to bfloat16, 9 seeds   .. 7.2e-5, middle 9.31e-6 .. 5.05e-5
#   control: every product at one  the program with its products at one
#   bfloat16 pass (the precision   bfloat16 pass against this reference:
#   below the stated one), 8 seeds slot 0 1.9e-4 .. 1.3e-3
#
# So the default would have held the sound program with room (4.6 times) and
# let the bfloat16-`z` control through on a seed in fourteen: `z` is rounded
# where `leaky` and `a` already weigh it down, and its error is a random sign
# an element of 64, so the score moves by a fraction of an ulp of bfloat16 and
# the loss by about 1e-6. The middle of three within 5e-7 stands 3.6 times
# over the sound program's largest middle and 2.6 times under the bfloat16-z
# control's smallest; the bfloat16-rows control reads 19 times the bound at the
# least, the one-pass products 380 times on slot 0.
#
# With every product at one bfloat16 pass in program and reference alike (as
# `mv100k-gat` runs) the sound program read 0 .. 6.71e-7 over 24 readings of 8
# seeds, slot 0 up to 6.56e-7: GAT's events (`reference/gat.py`), an element
# of layer 1's output within an ulp of a bfloat16 rounding boundary rounding
# the other way in `h1 W_l`. Hence `EXACT`, and this family's "highest" and
# "default" are one computation.
FORWARD = {"highest": (1e-2, 5e-1), "default": (5e-7, 2.5e-1)}
FORWARD_READINGS = 3


def _same(rows):
    return rows


def attend(xl, xr, sender, receiver, a, gathered=_same, rounded=_same):
    """Each receiver's softmax-weighted sum of its senders' `xl` rows, scored
    by both ends. `gathered` and `rounded` are the identity (the controls
    below pass others): of the senders' rows, and of the score's `z`."""
    n = xl.shape[0]
    rows = gathered(xl[sender])
    z = rounded(rows + xr[receiver])
    score = jnp.sum(jax.nn.leaky_relu(z, negative_slope=LEAK) * a, axis=-1)
    top = jnp.full(n, -jnp.inf, score.dtype).at[receiver].max(score)
    weight = jnp.exp(score - top[receiver])
    total = jnp.zeros(n, score.dtype).at[receiver].add(weight)
    alpha = weight / total[receiver]
    return jnp.zeros_like(xl).at[receiver].add(rows * alpha[:, None])


def layer(h, src, dst, w_l, w_r, a, b, **how):
    xl, xr = dot(h, w_l), dot(h, w_r)
    from_callers = attend(xl, xr, src, dst, a[1], **how)
    from_callees = attend(xl, xr, dst, src, a[0], **how)
    return jax.nn.elu(xl + from_callers + from_callees + b)


def forward(p: dict, x, src, dst, **how):
    """(latency prediction [N], anomaly logit [N])."""
    h1 = layer(x, src, dst, p["w_l_1"], p["w_r_1"], p["a_1"], p["b_1"], **how)
    h2 = layer(h1, src, dst, p["w_l_2"], p["w_r_2"], p["a_2"], p["b_2"], **how)
    latency = dot(h2, p["w_latency"]) + dot(x, p["w_latency_skip"]) + p["b_latency"]
    logit = dot(h2, p["w_anomaly"]) + dot(x, p["w_anomaly_skip"]) + p["b_anomaly"]
    return latency[:, 0], logit[:, 0]


# -- the check's controls: what a bfloat16 score or bfloat16 rows would compute --


@jax.custom_vjp
def _bfloat16(z):
    """A row as bfloat16 holds it; the cotangent passes. Not `astype`: on the
    chip XLA drops a convert pair (excess precision)."""
    return jax.lax.reduce_precision(z, exponent_bits=8, mantissa_bits=7)


_bfloat16.defvjp(lambda z: (_bfloat16(z), None), lambda _, g: (g,))


def forward_bfloat16_z(p: dict, x, src, dst):
    """NOT the reference: `forward` with every entry's `z = xl[j] + xr[i]`
    rounded to bfloat16 before `leaky_relu` and the weighted sum over the
    width: what a walk that made its per-entry vector work in bfloat16 (half
    the VPU's passes) would compute. The check must read `correct` false with
    this in the program's place."""
    return forward(p, x, src, dst, rounded=_bfloat16)


def forward_bfloat16_rows(p: dict, x, src, dst):
    """NOT the reference: `forward` with the gathered `xl` rows of both layers
    rounded to bfloat16 before they enter the score and the weighted sum: what
    a gather of half the bytes would compute. The check must read `correct`
    false with this in the program's place."""
    return forward(p, x, src, dst, gathered=_bfloat16)
