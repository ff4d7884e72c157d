"""Principal Neighbourhood Aggregation (PNA) latency/anomaly head: the
third message-passing family over the endpoint-dependency graph.

Same task and feature/target contract as kmamiz_tpu.models.graphsage, but an
endpoint does not see its neighbours' MEAN alone. A caller's latency is set
by its slowest callee, a partial outage shows as dispersion among an
endpoint's neighbours, and a mean over a hub's thousands of callers is
another kind of number than a mean over a leaf's one: Corso et al.,
"Principal Neighbourhood Aggregation for Graph Nets" (NeurIPS 2020,
arXiv:2004.05718; PyTorch Geometric `PNAConv`) aggregates with four
aggregators (mean, standard deviation, maximum, minimum) times three degree
scalers (identity, amplification, attenuation). Per layer, with N(i) the
callers and callees of i together (a multiset: an endpoint that is both makes
two entries, as in GraphSAGE's mean) and d_i = |N(i)|, clamped below at 1
where it divides or scales:

    m_j  = h_j W_m
    mu_i = sum_j m_j / d_i        sd_i = sqrt(relu(sum_j m_j^2 / d_i - mu_i^2) + 1e-5)
    mx_i = max_j m_j              mn_i = min_j m_j            (0 where N(i) is empty)
    s_i  = log(d_i + 1) / delta   a_i  = [mu | sd | mx | mn]_i
    h_i' = relu(h_i W_s + [a_i | s_i a_i | a_i / s_i] W_a + b)

The paper's message M(h_i, h_j) = W_o h_i + W_m h_j is linear, and its h_i
part leaves every aggregator as a constant (it moves mean, maximum and
minimum by W_o h_i and the deviation not at all): it is folded into the
update's own h_i term and not computed per edge. One tower, one linear layer
before and after the aggregation, no normalisation, no residual. Every
matrix product of the head is a float32 product (`EXACT`, below). The
update's product reads the four aggregates once, against W_a's three row
blocks side by side (`_update`): its [N, 12H] input is never made.

`delta` is the mean of log(d + 1) over the endpoints that HAVE a neighbour
(PNAConv takes it over every node of the training graphs, isolated ones
too). An endpoint without one aggregates nothing, so no scaler reaches it;
and the rows that pad a node bucket (models/stacked.py, models/serving.py)
have none, so counted that way the constant is the graph's and not the
bucket's: the served forward, the batched evaluation and the refresh read
the same number from the same topology. It is a constant of the topology:
the refresh takes it from the stack's edge plan, where it is made once
(`edge_plan.EdgePlan.mean_log_degree`, `edge_plan.mean_log_degree`), in the
device's own float32 logarithms, as the scalers' numerators are.

A maximum that several neighbours share hands its gradient to them in equal
parts (`jax.ops.segment_max`'s own rule, and the planned kernels').

API mirrors graphsage (init_params / forward / loss_fn / make_optimizer /
make_train_step) so the trainer, checkpointing, and evaluation reuse. With
the stack's edge plan (`plan=`, the training refresh) a layer is one row
gather and one multi-output walk (`ops/sparse_pna.planned_aggregate`);
without one (the tick's one-off graphs, the batched evaluation) XLA's
segment reductions over the edge list as it comes.
"""
from __future__ import annotations

from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp

from kmamiz_tpu.models import common
from kmamiz_tpu.models.graphsage import EMB_DIM, NUM_FEATURES
from kmamiz_tpu.ops import edge_plan, sparse_pna

NAME = "pna"
#: `forward` takes the stack's edge plan as `plan=` (models/stacked.plan_for).
#: No `TAKES_NEIGHBOR_SUM_1`: a maximum does not commute with layer 1's
#: product, so no slot group can hoist it. No `TAKES_NODE_SHARDS`: a maximum
#: over an owner's entries knows no mesh axis yet (ROADMAP R2).
TAKES_PLAN = True
EPS = 1e-5  # under the deviation's root, as PNAConv's
#: every matrix product of the head is a float32 product (six bfloat16 passes where XLA's default on a TPU is one),
#: forward and, through `dot`'s own transposition, backward. Two reasons, both read on the chip at the 100k-endpoint
#: cell (PERF.md, PR 39). Beside the aggregation (`h W_m`, `[a | s a | a / s] W_a`): a maximum hands ONE neighbour's
#: value on unaveraged and the scalers amplify it up to 4.5 times, so a bfloat16 rounding there is not noise that a
#: mean dilutes, and a program and a reference that both round flip rounding boundaries at hubs on one reading in
#: three. Everywhere else (`h W_s`, the readouts, the feature skips; a twelfth of the update's work): adamw's first
#: update at lr 1e-2 moves the weight of each of the update's 768 inputs by a full lr at once, so the SECOND slot's loss
#: is hundreds of times the first's before the head settles (13.4, 5575, 48.8, 20.2, 9.7, 4.5 over a history's first six
#: slots), and whatever two correct implementations differ by after one slot is multiplied some 200 times in the next.
#: With those small products at one bfloat16 pass the three-slot loss parted from the float32 mathematics by 1.6e-2 at
#: most on 36 seeds and by 7.2e-2 on the next; at float32 by 8.4e-5 at most on 37.
EXACT = jax.lax.Precision.HIGHEST
AGGREGATES = 4 * 3  # aggregators x scalers
_dot = partial(jnp.dot, precision=EXACT)


class PnaParams(NamedTuple):
    w_msg_1: jnp.ndarray  # [F, H] the message
    w_self_1: jnp.ndarray  # [F, H]
    w_agg_1: jnp.ndarray  # [12 H, H] over [a | s a | a / s]
    b_1: jnp.ndarray  # [H]
    w_msg_2: jnp.ndarray  # [H, H]
    w_self_2: jnp.ndarray  # [H, H]
    w_agg_2: jnp.ndarray  # [12 H, H]
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
) -> PnaParams:
    k = jax.random.split(rng, 9)
    in_dim = num_features + (EMB_DIM if num_nodes else 0)

    def glorot(key, shape):
        scale = jnp.sqrt(2.0 / (shape[0] + shape[1]))
        return jax.random.normal(key, shape, dtype=jnp.float32) * scale

    return PnaParams(
        w_msg_1=glorot(k[0], (in_dim, hidden)),
        w_self_1=glorot(k[1], (in_dim, hidden)),
        w_agg_1=glorot(k[2], (AGGREGATES * hidden, hidden)),
        b_1=jnp.zeros(hidden, dtype=jnp.float32),
        w_msg_2=glorot(k[3], (hidden, hidden)),
        w_self_2=glorot(k[4], (hidden, hidden)),
        w_agg_2=glorot(k[5], (AGGREGATES * hidden, hidden)),
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


def _edge_aggregate(m, src_ep, dst_ep, edge_mask):
    """(sum, sum of squares, maximum, minimum) of the neighbours' rows of `m`
    over both edge directions, and the degree, from an edge list as it comes:
    XLA's segment reductions. A masked edge lands in a segment past the end."""
    n = m.shape[0]
    owner = jnp.concatenate([jnp.where(edge_mask, src_ep, n), jnp.where(edge_mask, dst_ep, n)])
    rows = m[jnp.minimum(jnp.concatenate([dst_ep, src_ep]), n - 1)]
    over = dict(segment_ids=owner, num_segments=n + 1)
    degree = jax.ops.segment_sum(jnp.ones(owner.shape, m.dtype), **over)
    tables = sparse_pna.segment_aggregates(rows, degree[:, None] > 0, **over)
    return tuple(a[:-1] for a in tables), degree[:-1]


def _layer(h, src_ep, dst_ep, edge_mask, w_msg, w_self, w_agg, b, plan):
    m = _dot(h, w_msg)
    if plan is not None:
        (total, squares, top, bottom), degree = sparse_pna.planned_aggregate(plan, m), plan.degree[: h.shape[0]]
        delta = plan.mean_log_degree
    else:
        (total, squares, top, bottom), degree = _edge_aggregate(m, src_ep, dst_ep, edge_mask)
        delta = edge_plan.mean_log_degree(degree)
    d = jnp.maximum(degree, 1.0)[:, None]
    mean = total / d
    deviation = jnp.sqrt(jax.nn.relu(squares / d - mean * mean) + EPS)
    a = jnp.concatenate([mean, deviation, top, bottom], axis=1)
    scale = jnp.log(d + 1.0) / delta
    return jax.nn.relu(_dot(h, w_self) + _update(a, scale, w_agg) + b)


def _update(a, scale, w_agg):
    """`[a | s a | a / s] W_a` as `a W_0 + s (a W_1) + (a W_2) / s`, `W_k` the k-th of W_a's three row blocks: ONE
    float32 product of the four aggregates `a` [N, 4H] against the blocks side by side (`W_cat` [4H, 3H]), then a
    per-node combine. The [N, 12H] input is never made, kept or differentiated: autodiff's `d a` is
    `[g | s g | g / s] W_cat^T`, and `d W_a` is `a^T [g | s g | g / s]` laid back into W_a's rows. The same
    mathematics, rounded in another order."""
    width, hidden = a.shape[1], w_agg.shape[1]
    w_cat = w_agg.reshape(3, width, hidden).transpose(1, 0, 2).reshape(width, 3 * hidden)  # [W_0 | W_1 | W_2]
    parts = _dot(a, w_cat)
    return parts[:, :hidden] + scale * parts[:, hidden : 2 * hidden] + parts[:, 2 * hidden :] / scale


def forward(
    params: PnaParams,
    features: jnp.ndarray,  # [N, NUM_FEATURES]
    src_ep: jnp.ndarray,
    dst_ep: jnp.ndarray,
    edge_mask: jnp.ndarray,
    plan: edge_plan.EdgePlan = None,
):
    """Two PNA layers -> (latency prediction [N], anomaly logits [N]).

    `plan` is the edge plan of (src_ep, dst_ep, edge_mask) where the caller
    has prepared one (the training refresh: models/stacked.py): each layer's
    four aggregates are then one row gather and one walk of the plan's
    sorted entries (sparse_pna.planned_aggregate), the degree and `delta` the
    plan's. Without one the edge list is reduced as it comes."""
    # the device's names for these stretches (docs/OBSERVABILITY.md): a layer is
    # `dense` but for what the planned aggregate names `gather` and `reduce` beneath it
    with jax.named_scope("pna/layer1/dense"):
        x = common.concat_embedding(features, params.embedding)
        h1 = _layer(
            x, src_ep, dst_ep, edge_mask,
            params.w_msg_1, params.w_self_1, params.w_agg_1, params.b_1, plan,
        )
    with jax.named_scope("pna/layer2/dense"):
        h2 = _layer(
            h1, src_ep, dst_ep, edge_mask,
            params.w_msg_2, params.w_self_2, params.w_agg_2, params.b_2, plan,
        )
    with jax.named_scope("pna/readout/dense"):
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
