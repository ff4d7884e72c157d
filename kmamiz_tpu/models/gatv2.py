"""GATv2 latency/anomaly head: attention whose weight depends on both ends of
the call.

Same task and feature/target contract as kmamiz_tpu.models.graphsage, and
GAT's layout (kmamiz_tpu.models.gat: both edge directions, each with its own
attention vector, the self term outside the softmax), but with the DYNAMIC
score of Brody, Alon and Yahav, "How Attentive are Graph Attention Networks?"
(ICLR 2022, arXiv:2105.14491; PyTorch Geometric `GATv2Conv`). GAT's score
`leaky(a_s . W h_j + a_d . W h_i)` is monotone in the neighbour's own part,
so every caller ranks a hub's callees in one fixed order; GATv2 applies the
nonlinearity before the dot product, so the ranking moves with the caller's
state. Per layer, over the entries e = (i, j, d) of both edge directions
(d = 0: an edge out of i, j its callee; d = 1: an edge into i, j its caller):

    xl = h W_l          xr = h W_r                         [N, H] each
    score_e = a_d . leaky_relu(xl[j] + xr[i], 0.2)
    alpha_e = softmax of score over the entries of (i, d)
    h'_i    = elu(xl[i] + sum_e alpha_e xl[j] + b)

One head, no dropout, `W_l` and `W_r` shared by the two directions
(`share_weights` false in PyG's terms), and GraphSAGE's readouts with their
feature skips.

API mirrors graphsage (init_params / forward / loss_fn / make_optimizer /
make_train_step) so the trainer, checkpointing, and evaluation reuse. With
the stack's edge plan (`plan=`, the training refresh) a layer's two softmaxes
and weighted sums are one pass over the plan's sorted entries
(`ops/sparse_gatv2.planned_gatv2`); without one (the tick's one-off graphs,
the batched evaluation) XLA's segment reductions over the edge list as it
comes.
"""
from __future__ import annotations

from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp

from kmamiz_tpu.models import common
from kmamiz_tpu.models.gat import _segment_softmax
from kmamiz_tpu.models.graphsage import EMB_DIM, NUM_FEATURES
from kmamiz_tpu.ops import edge_plan, sparse_gatv2

NAME = "gatv2"
#: `forward` takes the stack's edge plan as `plan=` (models/stacked.plan_for).
#: No `TAKES_NEIGHBOR_SUM_1`: the score is nonlinear in layer 1's input, so no
#: slot group can hoist a sum. No `TAKES_NODE_SHARDS`: a softmax over an
#: owner's entries knows no mesh axis yet (ROADMAP R2).
TAKES_PLAN = True
LEAK = 0.2
#: every matrix product of the head is a float32 product (six bfloat16 passes where XLA's default on a TPU is one),
#: forward and, through `dot`'s own transposition, backward, in the program and in the plain reference alike. At one
#: pass the check's one-slot comparison read up to 6.7e-7 of its 1e-6 bound on the chip at the 100k-endpoint cell: an
#: element of layer 1's output within an ulp of a bfloat16 rounding boundary rounds the other way in `h1 W_l` on one
#: side, and at a hub every caller's score moves with it (PERF.md). At float32 the comparison reads the order of two
#: compilations' sums, 2.2e-7 at most, and the products cost a few per cent of a slot update.
EXACT = jax.lax.Precision.HIGHEST
_dot = partial(jnp.dot, precision=EXACT)


class GatV2Params(NamedTuple):
    w_l_1: jnp.ndarray  # [F, H] the neighbour's side, and the message
    w_r_1: jnp.ndarray  # [F, H] the owner's side of the score
    a_1: jnp.ndarray  # [2, H] a row per direction: 0 edges out of the owner, 1 edges into it
    b_1: jnp.ndarray  # [H]
    w_l_2: jnp.ndarray  # [H, H]
    w_r_2: jnp.ndarray  # [H, H]
    a_2: jnp.ndarray  # [2, H]
    b_2: jnp.ndarray  # [H]
    w_latency: jnp.ndarray  # [H, 1]
    b_latency: jnp.ndarray  # [1]
    w_anomaly: jnp.ndarray  # [H, 1]
    b_anomaly: jnp.ndarray  # [1]
    w_latency_skip: jnp.ndarray  # [F, 1]
    w_anomaly_skip: jnp.ndarray  # [F, 1]
    embedding: object  # [num_nodes, EMB_DIM] learned node identity, or None


def init_params(
    rng: jax.Array,
    hidden: int = 64,
    num_features: int = NUM_FEATURES,
    num_nodes: int = 0,
) -> GatV2Params:
    k = jax.random.split(rng, 9)
    in_dim = num_features + (EMB_DIM if num_nodes else 0)

    def glorot(key, shape):
        scale = jnp.sqrt(2.0 / (shape[0] + shape[-1]))
        return jax.random.normal(key, shape, dtype=jnp.float32) * scale

    def att(key):
        return jax.random.normal(key, (2, hidden), dtype=jnp.float32) * 0.1

    return GatV2Params(
        w_l_1=glorot(k[0], (in_dim, hidden)),
        w_r_1=glorot(k[1], (in_dim, hidden)),
        a_1=att(k[2]),
        b_1=jnp.zeros(hidden, dtype=jnp.float32),
        w_l_2=glorot(k[3], (hidden, hidden)),
        w_r_2=glorot(k[4], (hidden, hidden)),
        a_2=att(k[5]),
        b_2=jnp.zeros(hidden, dtype=jnp.float32),
        w_latency=glorot(k[6], (hidden, 1)),
        b_latency=jnp.zeros(1, dtype=jnp.float32),
        w_anomaly=glorot(k[7], (hidden, 1)),
        b_anomaly=jnp.zeros(1, dtype=jnp.float32),
        # wide-and-deep input skips (see graphsage.init_params)
        w_latency_skip=jnp.zeros((num_features, 1), dtype=jnp.float32),
        w_anomaly_skip=jnp.zeros((num_features, 1), dtype=jnp.float32),
        embedding=(
            jax.random.normal(k[8], (num_nodes, EMB_DIM), dtype=jnp.float32) * 0.1
            if num_nodes
            else None  # None, not [0, D]: orbax cannot save zero-size arrays
        ),
    )


def _attend(xl, xr, owner, neighbour, edge_mask, a):
    """One direction over an edge list as it comes: each owner's softmax-weighted
    sum of its neighbours' `xl` rows, scored by both ends. Returns [N, H]."""
    n = xl.shape[0]
    own = jnp.minimum(jnp.where(edge_mask, owner, n - 1), n - 1)
    nbr = jnp.minimum(jnp.where(edge_mask, neighbour, n - 1), n - 1)
    score = jnp.sum(jax.nn.leaky_relu(xl[nbr] + xr[own], negative_slope=LEAK) * a, axis=-1)
    alpha = _segment_softmax(score, own, n, edge_mask)
    return jax.ops.segment_sum(xl[nbr] * (alpha * edge_mask)[:, None], own, num_segments=n)


def _layer(h, src, dst, edge_mask, w_l, w_r, a, b, plan=None):
    xl, xr = _dot(h, w_l), _dot(h, w_r)
    if plan is not None:
        # both directions in one pass over the plan's entries (owner, neighbour, direction)
        return jax.nn.elu(xl + sparse_gatv2.planned_gatv2(plan, xl, xr, a, LEAK) + b)
    out = _attend(xl, xr, src, dst, edge_mask, a[0])  # edges out of the owner: its callees
    into = _attend(xl, xr, dst, src, edge_mask, a[1])  # edges into it: its callers
    return jax.nn.elu(xl + out + into + b)


def forward(
    params: GatV2Params,
    features: jnp.ndarray,  # [N, NUM_FEATURES]
    src_ep: jnp.ndarray,
    dst_ep: jnp.ndarray,
    edge_mask: jnp.ndarray,
    plan: edge_plan.EdgePlan = None,
):
    """Two GATv2 layers -> (latency prediction [N], anomaly logits [N]).

    `plan` is the edge plan of (src_ep, dst_ep, edge_mask) where the caller
    has prepared one (the training refresh: models/stacked.py): each layer's
    two segment softmaxes and weighted sums are then one pass over the plan's
    sorted entries (sparse_gatv2.planned_gatv2), the same mathematics in
    another order. Without one the edge list is reduced as it comes."""
    # the device's names for these stretches (docs/OBSERVABILITY.md): a layer is
    # `dense` but for what the planned attention names `gather` and `reduce` beneath it
    with jax.named_scope("gatv2/layer1/dense"):
        x = common.concat_embedding(features, params.embedding)
        h1 = _layer(x, src_ep, dst_ep, edge_mask, params.w_l_1, params.w_r_1, params.a_1, params.b_1, plan)
    with jax.named_scope("gatv2/layer2/dense"):
        h2 = _layer(h1, src_ep, dst_ep, edge_mask, params.w_l_2, params.w_r_2, params.a_2, params.b_2, plan)
    with jax.named_scope("gatv2/readout/dense"):
        latency = (
            _dot(h2, params.w_latency) + _dot(features, params.w_latency_skip) + params.b_latency
        )[:, 0]
        anomaly_logit = (
            _dot(h2, params.w_anomaly) + _dot(features, params.w_anomaly_skip) + params.b_anomaly
        )[:, 0]
    return latency, anomaly_logit


loss_fn = common.make_loss_fn(forward)  # unweighted default
make_optimizer = common.make_optimizer


def make_train_step(optimizer, pos_weight: float = 1.0):
    if pos_weight == 1.0:
        return common.make_train_step(optimizer, loss_fn)
    return common.make_train_step(optimizer, common.make_loss_fn(forward, pos_weight))
