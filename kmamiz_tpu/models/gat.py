"""Graph-attention (GAT) latency/anomaly head — the second model family
over the endpoint-dependency graph.

Same task and feature/target contract as kmamiz_tpu.models.graphsage
(next-window latency regression + anomaly logits over the capacity-padded
edge store), but neighbors aggregate through EDGE ATTENTION instead of a
mean: per directed edge, a score a^T[Wh_src || Wh_dst] passes LeakyReLU
and normalizes with a numerically-stable SEGMENT SOFTMAX over each
destination's incoming edges (segment_max for the shift, segment_sum for
the partition) — the attention math lands on the same segment-reduction
shape as the scorers and window kernels, so the TPU program family is
shared. Both edge directions contribute (callers and callees are both
signal), each with its own attention vector.

API mirrors graphsage (init_params / forward / loss_fn / make_optimizer /
make_train_step) so the trainer, checkpointing, and evaluation reuse.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from kmamiz_tpu.models import common
from kmamiz_tpu.models.graphsage import EMB_DIM, NUM_FEATURES
from kmamiz_tpu.ops import edge_plan, sparse_gat

LEAK = 0.2
#: `forward` takes the stack's edge plan as `plan=` (models/stacked.plan_for)
TAKES_PLAN = True


class GatParams(NamedTuple):
    w_1: jnp.ndarray  # [F, H]
    a_src_1: jnp.ndarray  # [H] attention vector, source half (fwd direction)
    a_dst_1: jnp.ndarray  # [H]
    a_src_1r: jnp.ndarray  # [H] reverse direction
    a_dst_1r: jnp.ndarray  # [H]
    b_1: jnp.ndarray  # [H]
    w_2: jnp.ndarray  # [H, H]
    a_src_2: jnp.ndarray  # [H]
    a_dst_2: jnp.ndarray  # [H]
    a_src_2r: jnp.ndarray  # [H]
    a_dst_2r: jnp.ndarray  # [H]
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
) -> GatParams:
    k = jax.random.split(rng, 13)
    in_dim = num_features + (EMB_DIM if num_nodes else 0)

    def glorot(key, shape):
        scale = jnp.sqrt(2.0 / (shape[0] + shape[-1]))
        return jax.random.normal(key, shape, dtype=jnp.float32) * scale

    def att(key, h):
        return jax.random.normal(key, (h,), dtype=jnp.float32) * 0.1

    return GatParams(
        w_1=glorot(k[0], (in_dim, hidden)),
        a_src_1=att(k[1], hidden),
        a_dst_1=att(k[2], hidden),
        a_src_1r=att(k[3], hidden),
        a_dst_1r=att(k[4], hidden),
        b_1=jnp.zeros(hidden, dtype=jnp.float32),
        w_2=glorot(k[5], (hidden, hidden)),
        a_src_2=att(k[6], hidden),
        a_dst_2=att(k[7], hidden),
        a_src_2r=att(k[8], hidden),
        a_dst_2r=att(k[9], hidden),
        b_2=jnp.zeros(hidden, dtype=jnp.float32),
        w_latency=glorot(k[10], (hidden, 1)),
        b_latency=jnp.zeros(1, dtype=jnp.float32),
        w_anomaly=glorot(k[11], (hidden, 1)),
        b_anomaly=jnp.zeros(1, dtype=jnp.float32),
        # wide-and-deep input skips (see graphsage.init_params)
        w_latency_skip=jnp.zeros((num_features, 1), dtype=jnp.float32),
        w_anomaly_skip=jnp.zeros((num_features, 1), dtype=jnp.float32),
        embedding=(
            jax.random.normal(k[12], (num_nodes, EMB_DIM), dtype=jnp.float32)
            * 0.1
            if num_nodes
            else None  # None, not [0, D]: orbax cannot save zero-size arrays
        ),
    )


def _segment_softmax(scores, seg, num_segments, mask):
    """Numerically stable softmax of edge scores within each segment;
    masked edges contribute zero weight.

    The exponent is clipped to <= 0 BEFORE exp: for real rows the shift
    already makes it non-positive, and for masked rows it prevents the
    untaken where-branch from overflowing to inf — 0 * inf cotangents
    would otherwise turn the whole gradient NaN whenever a segment
    contains only masked edges (e.g. capacity padding clamped to node
    n-1 when that node has no real edge)."""
    neg = jnp.finfo(scores.dtype).min
    shift = jax.ops.segment_max(
        jnp.where(mask, scores, neg), seg, num_segments=num_segments
    )
    shift = jnp.where(shift > neg / 2, shift, 0.0)  # empty segments
    delta = jnp.clip(scores - shift[seg], -60.0, 0.0)
    expd = jnp.where(mask, jnp.exp(delta), 0.0)
    denom = jax.ops.segment_sum(expd, seg, num_segments=num_segments)
    return expd / jnp.maximum(denom[seg], 1e-30)


def _attend(h, src, dst, edge_mask, a_src, a_dst):
    """One attention direction: aggregate h[src] into dst with softmax
    weights over each dst's incoming edges. Returns [N, H]."""
    n = h.shape[0]
    src_c = jnp.minimum(jnp.where(edge_mask, src, n - 1), n - 1)
    dst_c = jnp.minimum(jnp.where(edge_mask, dst, n - 1), n - 1)
    scores = jax.nn.leaky_relu(
        h[src_c] @ a_src + h[dst_c] @ a_dst, negative_slope=LEAK
    )
    alpha = _segment_softmax(scores, dst_c, n, edge_mask)
    msgs = h[src_c] * (alpha * edge_mask)[:, None]
    return jax.ops.segment_sum(msgs, dst_c, num_segments=n)


def _layer(h, src, dst, edge_mask, w, a_s, a_d, a_sr, a_dr, b, plan=None):
    hw = h @ w
    if plan is not None:
        # both directions in one pass over the plan's entries (owner,
        # neighbour, direction): the neighbour's half of the score is made
        # per node, not per edge. Direction 0 is an edge out of the owner
        # (the reverse attention: the owner is the edge's source), 1 an edge
        # into it (the forward attention). Four matrix-vector products, as
        # `_attend` makes per edge: XLA's TPU backend reduces those in
        # float32, where a [H, 2] product would go through the MXU in one
        # bfloat16 pass and move the loss by 1e-5 (PERF.md, PR 28)
        s = jnp.stack([hw @ a_sr, hw @ a_s], axis=1)  # [N, 2], of the neighbour
        t = jnp.stack([hw @ a_dr, hw @ a_d], axis=1)  # [N, 2], of the owner
        return jax.nn.elu(hw + sparse_gat.planned_attention(plan, hw, s, t, LEAK) + b)
    fwd = _attend(hw, src, dst, edge_mask, a_s, a_d)
    rev = _attend(hw, dst, src, edge_mask, a_sr, a_dr)
    return jax.nn.elu(hw + fwd + rev + b)


def forward(
    params: GatParams,
    features: jnp.ndarray,  # [N, NUM_FEATURES]
    src_ep: jnp.ndarray,
    dst_ep: jnp.ndarray,
    edge_mask: jnp.ndarray,
    plan: edge_plan.EdgePlan = None,
):
    """Two attention layers -> (latency prediction [N], anomaly logits [N]).

    `plan` is the edge plan of (src_ep, dst_ep, edge_mask) where the caller
    has prepared one (the training refresh: models/stacked.py): each layer's
    two segment softmaxes and weighted sums are then one pass over the
    plan's sorted entries (sparse_gat.planned_attention), the same mathematics
    in another order. Without one the edge list is reduced as it comes."""
    # the device's names for these stretches (docs/OBSERVABILITY.md): a layer is
    # `dense` but for what the planned attention names `gather` and `reduce` beneath it
    with jax.named_scope("gat/layer1/dense"):
        x = common.concat_embedding(features, params.embedding)
        h1 = _layer(
            x, src_ep, dst_ep, edge_mask,
            params.w_1, params.a_src_1, params.a_dst_1,
            params.a_src_1r, params.a_dst_1r, params.b_1, plan,
        )
    with jax.named_scope("gat/layer2/dense"):
        h2 = _layer(
            h1, src_ep, dst_ep, edge_mask,
            params.w_2, params.a_src_2, params.a_dst_2,
            params.a_src_2r, params.a_dst_2r, params.b_2, plan,
        )
    with jax.named_scope("gat/readout/dense"):
        latency = (
            h2 @ params.w_latency + features @ params.w_latency_skip + params.b_latency
        )[:, 0]
        anomaly_logit = (
            h2 @ params.w_anomaly + features @ params.w_anomaly_skip + params.b_anomaly
        )[:, 0]
    return latency, anomaly_logit


loss_fn = common.make_loss_fn(forward)  # unweighted default
make_optimizer = common.make_optimizer


def make_train_step(optimizer, pos_weight: float = 1.0):
    if pos_weight == 1.0:
        return common.make_train_step(optimizer, loss_fn)
    return common.make_train_step(optimizer, common.make_loss_fn(forward, pos_weight))


#: the head's name in a checkpoint's metadata and on its spans (models/heads.py)
NAME = "gat"
