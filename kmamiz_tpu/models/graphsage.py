"""GraphSAGE latency/anomaly head over the endpoint-dependency graph.

The accelerator-justifying model from BASELINE.json: a 2-layer
neighbor-mean GraphSAGE over the capacity-padded edge store
(kmamiz_tpu.graph.store), with per-endpoint features from the window
statistics (request rate, 4xx/5xx rates, latency mean/CV, replica count)
predicting next-window latency (regression) and anomaly probability
(binary logit). Trains with optax; evaluated on MicroViSim-style fault
windows (kmamiz_tpu.simulator).

Aggregation uses both edge directions at distance 1 (callers and callees
are both signal for an endpoint's health) as segment means — the same
SpMM shape as the scorers, so one compiled program family serves both.
"""
from __future__ import annotations

from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp

from kmamiz_tpu.ops import edge_plan, sparse_sum

NUM_FEATURES = 10  # incl. sin/cos hour-of-day
#: what the fused trainer may hand `forward` (models/stacked.py reads these,
#: not the signature): the stack's edge plan as `plan=`, and layer 1's
#: neighbour sum of the features as `neighbor_sum_1=` (the slot group)
TAKES_PLAN = True
TAKES_NEIGHBOR_SUM_1 = True


def assemble_features(
    request_rate,
    err4_share,
    err5_share,
    log_latency,
    latency_cv,
    replicas,
    log_volume,
    active,
    hour_of_day: float,
):
    """THE feature-column layout, shared by the trainer's per-slot builder
    and the tick's hour fold — one definition so the two can never skew
    (train/serve skew is silent and deadly for the hour features).

    Host-side numpy on purpose: the hour fold runs under
    jax.transfer_guard("disallow") when KMAMIZ_TRANSFER_GUARD=1, and the
    previous eager-jnp form implicitly uploaded every host column (and
    the baked sin/cos constants) to the device per fold. Consumers that
    train/serve on device convert explicitly at their bucket-padding
    step."""
    import numpy as np

    angle = 2.0 * np.pi * float(hour_of_day) / 24.0
    rate = np.asarray(request_rate, dtype=np.float32)
    return np.stack(
        [
            rate,
            np.asarray(err4_share, dtype=np.float32),
            np.asarray(err5_share, dtype=np.float32),
            np.asarray(log_latency, dtype=np.float32),
            np.asarray(latency_cv, dtype=np.float32),
            np.asarray(replicas, dtype=np.float32),
            np.asarray(log_volume, dtype=np.float32),
            np.asarray(active, dtype=np.float32),
            np.full_like(rate, np.float32(np.sin(angle))),
            np.full_like(rate, np.float32(np.cos(angle))),
        ],
        axis=1,
    )


class SageParams(NamedTuple):
    w_self_1: jnp.ndarray  # [F, H]
    w_neigh_1: jnp.ndarray  # [F, H]
    b_1: jnp.ndarray  # [H]
    w_self_2: jnp.ndarray  # [H, H]
    w_neigh_2: jnp.ndarray  # [H, H]
    b_2: jnp.ndarray  # [H]
    w_latency: jnp.ndarray  # [H, 1]
    b_latency: jnp.ndarray  # [1]
    w_anomaly: jnp.ndarray  # [H, 1]
    b_anomaly: jnp.ndarray  # [1]
    w_latency_skip: jnp.ndarray  # [F, 1]
    w_anomaly_skip: jnp.ndarray  # [F, 1]
    embedding: object  # [num_nodes, EMB_DIM] learned node identity, or None
    # ([0, EMB_DIM] disables: identity-free features cannot express
    # per-node periodic behavior like "db-query errors nightly")


EMB_DIM = 8  # learned node-identity embedding width


def init_params(
    rng: jax.Array,
    hidden: int = 64,
    num_features: int = NUM_FEATURES,
    num_nodes: int = 0,
) -> SageParams:
    """num_nodes > 0 adds a learned per-node embedding, concatenated to
    the input features of layer 1 (the readout skips stay feature-only)."""
    k = jax.random.split(rng, 7)
    in_dim = num_features + (EMB_DIM if num_nodes else 0)

    def glorot(key, shape):
        scale = jnp.sqrt(2.0 / (shape[0] + shape[1]))
        return jax.random.normal(key, shape, dtype=jnp.float32) * scale

    return SageParams(
        w_self_1=glorot(k[0], (in_dim, hidden)),
        w_neigh_1=glorot(k[1], (in_dim, hidden)),
        b_1=jnp.zeros(hidden, dtype=jnp.float32),
        w_self_2=glorot(k[2], (hidden, hidden)),
        w_neigh_2=glorot(k[3], (hidden, hidden)),
        b_2=jnp.zeros(hidden, dtype=jnp.float32),
        w_latency=glorot(k[4], (hidden, 1)),
        b_latency=jnp.zeros(1, dtype=jnp.float32),
        w_anomaly=glorot(k[5], (hidden, 1)),
        b_anomaly=jnp.zeros(1, dtype=jnp.float32),
        # wide-and-deep input skips: persistence (next ~ current) is the
        # dominant mode of both targets, so the readout sees the raw
        # features directly and the GNN trunk learns residuals
        w_latency_skip=jnp.zeros((num_features, 1), dtype=jnp.float32),
        w_anomaly_skip=jnp.zeros((num_features, 1), dtype=jnp.float32),
        embedding=(
            jax.random.normal(k[6], (num_nodes, EMB_DIM), dtype=jnp.float32)
            * 0.1
            if num_nodes
            else None  # None, not [0, D]: orbax cannot save zero-size arrays
        ),
    )


def neighbor_degree(
    num_nodes: int,
    src_ep: jnp.ndarray,  # [E]
    dst_ep: jnp.ndarray,  # [E]
    edge_mask: jnp.ndarray,  # [E]
    dtype=jnp.float32,
) -> jnp.ndarray:
    """Per-node masked degree over both edge directions [N].

    Depends only on the edge topology, not the layer states — forward
    computes it ONCE and both SAGE layers divide by it, instead of each
    neighbor_mean re-running two segment_sums of the same mask."""
    n = num_nodes
    src = jnp.where(edge_mask, src_ep, n)
    dst = jnp.where(edge_mask, dst_ep, n)
    deg = jax.ops.segment_sum(
        edge_mask.astype(dtype), src, num_segments=n + 1
    )[:-1]
    return deg + jax.ops.segment_sum(
        edge_mask.astype(dtype), dst, num_segments=n + 1
    )[:-1]


def neighbor_mean(
    h: jnp.ndarray,  # [N, F]
    src_ep: jnp.ndarray,  # [E]
    dst_ep: jnp.ndarray,  # [E]
    edge_mask: jnp.ndarray,  # [E]
    deg: jnp.ndarray = None,  # [N] precomputed neighbor_degree
    plan: edge_plan.EdgePlan = None,  # the topology prepared per dataset
    neighbor_sum: jnp.ndarray = None,  # [N, F] the sum of h's neighbour rows
) -> jnp.ndarray:
    """Mean of neighbor states over both edge directions (segment mean).

    deg omitted keeps the self-contained single-layer form; callers with
    several layers over one topology (forward) pass the hoisted degree.

    `neighbor_sum` is the numerator where the caller has made it already:
    the epoch block sums the neighbour rows of several slots' features in
    one planned sum (models/stacked.py, the slot group) and hands each slot
    its columns. Only the division is left to do here.

    A caller that holds an edge plan of this topology (the training
    refresh: models/stacked.py builds one per dataset) passes it, and the
    sum is one row gather and one owner-sorted reduction with its own VJP
    (sparse_sum.planned_neighbor_sum), the degree the plan's. Without a plan
    (the tick's one-off graphs) the edge list is reduced as it comes, by
    XLA's masked gathers and segment sums."""
    n = h.shape[0]
    if neighbor_sum is not None:
        if deg is None:
            deg = neighbor_degree(n, src_ep, dst_ep, edge_mask, dtype=h.dtype)
        return neighbor_sum / jnp.maximum(deg, 1.0)[:, None]
    if plan is not None:
        agg = sparse_sum.planned_neighbor_sum(plan, h)
        if deg is None:
            deg = plan.degree.astype(h.dtype)
        return agg / jnp.maximum(deg, 1.0)[:, None]
    src = jnp.where(edge_mask, src_ep, n)
    dst = jnp.where(edge_mask, dst_ep, n)
    dst_h = h[jnp.minimum(dst, n - 1)] * edge_mask[:, None]
    src_h = h[jnp.minimum(src, n - 1)] * edge_mask[:, None]
    agg = jax.ops.segment_sum(dst_h, src, num_segments=n + 1)[:-1]
    agg = agg + jax.ops.segment_sum(src_h, dst, num_segments=n + 1)[:-1]
    if deg is None:
        deg = neighbor_degree(n, src_ep, dst_ep, edge_mask, dtype=h.dtype)
    return agg / jnp.maximum(deg, 1.0)[:, None]


def forward(
    params: SageParams,
    features: jnp.ndarray,  # [N, NUM_FEATURES]
    src_ep: jnp.ndarray,
    dst_ep: jnp.ndarray,
    edge_mask: jnp.ndarray,
    plan: edge_plan.EdgePlan = None,
    neighbor_sum_1: jnp.ndarray = None,
):
    """Two SAGE layers -> (latency prediction [N], anomaly logits [N]).

    `plan` is the edge plan of (src_ep, dst_ep, edge_mask) where the caller
    has prepared one (neighbor_mean); the result is the same sums in
    another order.

    `neighbor_sum_1` is layer 1's neighbour sum of the input, [N, F], where
    the caller has made it: without node embeddings the input is data, so
    that sum depends on no parameter and takes no gradient, and the epoch
    block makes it for a group of slots at once (models/stacked.py). It
    stands for the sum of `features` alone, so a head with embeddings (whose
    layer-1 input holds parameters) must not be given one. Layer 2 always
    makes its own: `h1` depends on the parameters."""
    # the device's names for these stretches (docs/OBSERVABILITY.md): a layer is
    # `dense` but for what a planned sum names `gather` and `reduce` beneath it
    with jax.named_scope("graphsage/layer1/dense"):
        x = _common.concat_embedding(features, params.embedding)
        if plan is None:
            deg = neighbor_degree(features.shape[0], src_ep, dst_ep, edge_mask)
        else:
            deg = plan.degree
        agg1 = neighbor_mean(x, src_ep, dst_ep, edge_mask, deg, plan, neighbor_sum_1)
        h1 = jax.nn.relu(
            x @ params.w_self_1 + agg1 @ params.w_neigh_1 + params.b_1
        )
    with jax.named_scope("graphsage/layer2/dense"):
        agg2 = neighbor_mean(h1, src_ep, dst_ep, edge_mask, deg, plan)
        h2 = jax.nn.relu(h1 @ params.w_self_2 + agg2 @ params.w_neigh_2 + params.b_2)
    with jax.named_scope("graphsage/readout/dense"):
        latency = (
            h2 @ params.w_latency + features @ params.w_latency_skip + params.b_latency
        )[:, 0]
        anomaly_logit = (
            h2 @ params.w_anomaly + features @ params.w_anomaly_skip + params.b_anomaly
        )[:, 0]
    return latency, anomaly_logit


# loss / optimizer / train step are the family-shared scaffolding
from kmamiz_tpu.models import common as _common  # noqa: E402

loss_fn = _common.make_loss_fn(forward)  # unweighted default
make_optimizer = _common.make_optimizer
#: the fused trainer may cut this head's history by nodes over a mesh
#: (models/stacked.py): every reduction over the graph is a planned sum, which
#: all-gathers its table, and the loss is the family's, which sums over the axis
TAKES_NODE_SHARDS = True


def make_train_step(optimizer, pos_weight: float = 1.0):
    """Jitted (params, opt_state, batch...) -> (params, opt_state, loss, aux)."""
    if pos_weight == 1.0:
        return _common.make_train_step(optimizer, loss_fn)
    return _common.make_train_step(optimizer, _common.make_loss_fn(forward, pos_weight))


def features_from_stats(
    count: jnp.ndarray,  # [E*S] per-(endpoint,status) counts
    error_4xx: jnp.ndarray,
    error_5xx: jnp.ndarray,
    latency_mean: jnp.ndarray,
    latency_cv: jnp.ndarray,
    replicas: jnp.ndarray,  # [N]
    num_endpoints: int,
    num_statuses: int,
    window_seconds: float = 30.0,
    *,
    hour_of_day: float,  # required: silent 0.0 would skew the trained
    # sin/cos features against real slot hours (train/serve skew)
) -> jnp.ndarray:
    """Fold per-(endpoint,status) window stats into [N, NUM_FEATURES]."""
    shape = (num_endpoints, num_statuses)
    c = count.reshape(shape)
    e4 = error_4xx.reshape(shape)
    e5 = error_5xx.reshape(shape)
    lm = latency_mean.reshape(shape)
    cv = latency_cv.reshape(shape)

    total = c.sum(axis=1)
    safe = jnp.maximum(total, 1.0)
    # count-weighted means across status groups
    mean_latency = (lm * c).sum(axis=1) / safe
    mean_cv = (cv * c).sum(axis=1) / safe
    return assemble_features(
        total / window_seconds,
        e4.sum(axis=1) / safe,
        e5.sum(axis=1) / safe,
        jnp.log1p(mean_latency),
        mean_cv,
        replicas[:num_endpoints],
        jnp.log1p(total),
        total > 0,
        hour_of_day=hour_of_day,
    )


#: the head's name in a checkpoint's metadata and on its spans (models/heads.py)
NAME = "graphsage"
