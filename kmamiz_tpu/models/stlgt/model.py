"""STLGT head: linear graph transformer for tail-latency quantiles.

The STLGT paper (PAPERS.md, "A Scalable Trace-Based Linear Graph
Transformer for Tail Latency Prediction in Microservices") replaces
softmax attention with a kernelized feature map so one attention layer
over N endpoint slots costs O(N·H²) instead of O(N²·H) — the property
that lets the block run over the same pow2 capacity-bucketed slot layout
the stacked trainer and the graph store already use, with padded lanes
masked out of both the attention normalizer and the loss.

Two structural channels feed each endpoint's representation:

- **global linear attention**: phi(q)·(phi(k)ᵀv) over every active slot
  (phi = elu+1, the standard positive feature map), normalized by
  phi(q)·Σphi(k) — mesh-wide context at linear cost;
- **neighbor bias from the CSR edge list**: a gated message per
  dependency edge (sigmoid-scored q·k affinity, masked by the edge
  mask), segment-summed over both directions — the graph structure
  enters as an additive attention bias, and the per-edge gate doubles
  as the ATTRIBUTION score the eval protocol grades (which upstream
  edge the model blames for a forecast tail). Where the caller holds
  the EDGE PLAN of the edge list (the training refresh over a stacked
  history: models/stacked.py), the bias is one reduction over the
  plan's sorted entries (ops/sparse_gated.planned_gated_sum: row
  gathers and one Mosaic walk a pass, no scatter), the same mathematics
  in another order of the sums; without one (serving, the vmapped
  paths, the continual trainer's ring) it is the segment sums below.

Heads: a monotone quantile stack (p50 raw, p95 = p50 + softplus, p99 =
p95 + softplus — quantile crossing is impossible by construction) over
log1p latency, trained with pinball loss, plus the family-standard
anomaly logit. ``forward`` returns (p50, anomaly_logit) so the module
drops into every existing model-module surface (serving.forecast_forward,
stacked.predict_all); ``forward_quantiles`` is the full STLGT surface.

Interface contract (mirrors graphsage.py): NUM_FEATURES, init_params,
forward, make_optimizer, make_train_step — the module IS the model,
keyed by its import path in the program registry families. What the
fused trainer asks of a head it reads here, stated once: NAME (the
checkpoint's and the span's word for the head), TAKES_PLAN (`forward`
and the loss take the stack's edge plan as `plan=`), and `make_loss_fn`
with LOSS, the head's OWN loss (pinball over the three levels), which
`stacked.epoch_runner`'s block and `stlgt_epoch_runner`'s both call; a
head that states none trains under `common.make_loss_fn`.
"""
from __future__ import annotations

from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp
import optax

from kmamiz_tpu.models import common as _common
from kmamiz_tpu.models.graphsage import NUM_FEATURES, assemble_features  # noqa: F401 - re-export: one feature layout for every head
from kmamiz_tpu.ops import sparse, sparse_gated

NAME = "stlgt"
#: `forward`, `forward_quantiles`, `encode` and the loss take `plan=`
TAKES_PLAN = True
#: what `make_loss_fn` makes, for the `refresh.train` span
LOSS = "pinball+bce"

#: forecast quantile levels, in emitted column order (p50, p95, p99)
QUANTILES: Tuple[float, ...] = (0.50, 0.95, 0.99)
NUM_QUANTILES = len(QUANTILES)


class StlgtParams(NamedTuple):
    w_in: jnp.ndarray  # [F, H] input projection
    b_in: jnp.ndarray  # [H]
    w_q: jnp.ndarray  # [H, H] attention query
    w_k: jnp.ndarray  # [H, H] attention key
    w_v: jnp.ndarray  # [H, H] attention value
    w_o: jnp.ndarray  # [H, H] attention output
    b_edge: jnp.ndarray  # [1] edge-gate bias
    w_f1: jnp.ndarray  # [H, H] FFN
    b_f1: jnp.ndarray  # [H]
    w_f2: jnp.ndarray  # [H, H]
    b_f2: jnp.ndarray  # [H]
    w_quant: jnp.ndarray  # [H, NUM_QUANTILES] quantile head
    b_quant: jnp.ndarray  # [NUM_QUANTILES]
    w_quant_skip: jnp.ndarray  # [F, NUM_QUANTILES] wide-and-deep skip
    w_anomaly: jnp.ndarray  # [H, 1]
    b_anomaly: jnp.ndarray  # [1]
    w_anomaly_skip: jnp.ndarray  # [F, 1]


def init_params(
    rng: jax.Array,
    hidden: int = 32,
    num_features: int = NUM_FEATURES,
    num_nodes: int = 0,
) -> StlgtParams:
    """num_nodes is accepted for model-module interface parity and
    ignored: STLGT is identity-free by design (the same inductive
    argument as MODELS.md round 4 — a live endpoint set grows)."""
    del num_nodes
    k = jax.random.split(rng, 8)

    def glorot(key, shape):
        scale = jnp.sqrt(2.0 / (shape[0] + shape[1]))
        return jax.random.normal(key, shape, dtype=jnp.float32) * scale

    h = hidden
    return StlgtParams(
        w_in=glorot(k[0], (num_features, h)),
        b_in=jnp.zeros(h, dtype=jnp.float32),
        w_q=glorot(k[1], (h, h)),
        w_k=glorot(k[2], (h, h)),
        w_v=glorot(k[3], (h, h)),
        w_o=glorot(k[4], (h, h)),
        b_edge=jnp.zeros(1, dtype=jnp.float32),
        w_f1=glorot(k[5], (h, h)),
        b_f1=jnp.zeros(h, dtype=jnp.float32),
        w_f2=glorot(k[6], (h, h)),
        b_f2=jnp.zeros(h, dtype=jnp.float32),
        w_quant=glorot(k[7], (h, NUM_QUANTILES)),
        b_quant=jnp.zeros(NUM_QUANTILES, dtype=jnp.float32),
        # persistence skip: next-hour latency ~ current latency is the
        # dominant mode, so the quantile readout sees raw features
        w_quant_skip=jnp.zeros((num_features, NUM_QUANTILES), dtype=jnp.float32),
        w_anomaly=glorot(k[0], (h, 1)),
        b_anomaly=jnp.zeros(1, dtype=jnp.float32),
        w_anomaly_skip=jnp.zeros((num_features, 1), dtype=jnp.float32),
    )


def _phi(x: jnp.ndarray) -> jnp.ndarray:
    """elu+1: the positive feature map of kernelized linear attention."""
    return jax.nn.elu(x) + 1.0


def encode(
    params: StlgtParams,
    features: jnp.ndarray,  # [N, F] (bucket-padded rows all-zero)
    src_ep: jnp.ndarray,  # [E]
    dst_ep: jnp.ndarray,  # [E]
    edge_mask: jnp.ndarray,  # [E]
    plan: sparse.EdgePlan = None,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """One linear-transformer block -> (node states [N, H], edge gates
    [E]). Padded lanes (all-zero feature rows — the pow2 bucket padding
    is zero-filled everywhere in this repo) are masked out of the
    attention sums; padded edges out of the bias by edge_mask.

    `plan` is the edge plan of (src_ep, dst_ep, edge_mask) where the
    caller has prepared one: the neighbour bias is then
    `sparse_gated.planned_gated_sum` over its entries. The gates are per
    EDGE either way, in the edge list's order: an edge's gate is a
    function of q[src] and k[dst] alone (the plan's two entries of an
    edge hold it too), so with a plan they are still made from the edge
    list, for whoever asks; under the loss nobody does, and XLA drops
    them."""
    n = features.shape[0]
    # the device's names for the stretches below (docs/OBSERVABILITY.md): each is
    # `dense` but for what the gated sum names `gather` and `reduce` beneath it
    with jax.named_scope("stlgt/input/dense"):
        # lane mask: a padded slot has an all-zero feature row; real slots
        # always carry at least the hour-of-day cos column
        lane = (jnp.abs(features).sum(axis=1) > 0).astype(jnp.float32)

        x = jax.nn.relu(features @ params.w_in + params.b_in)
        q = _phi(x @ params.w_q) * lane[:, None]
        k = _phi(x @ params.w_k) * lane[:, None]
        v = (x @ params.w_v) * lane[:, None]

    # global linear attention: O(N·H²) — softmax-free. kv and z are sums
    # over EVERY endpoint, so they sit in every endpoint's state: where a
    # float32 product is one bfloat16 pass (a TPU's default), an element of
    # kv within an ulp of a bfloat16 rounding boundary moves all N rows of
    # q @ kv at once by 2^-9 of that term, and two correct implementations
    # that sum kv in another order disagree by 1e-6 .. 4e-6 of the loss on one
    # slot in seven (PERF.md, PR 33). The two products that READ the sums
    # are therefore made at float32 ([N, H] x [H, H]: six passes of nothing);
    # k.T @ v itself rounds per endpoint, which averages out, and stays.
    with jax.named_scope("stlgt/linear_attention/dense"):
        kv = k.T @ v  # [H, H]
        z = k.sum(axis=0)  # [H]
        exact = jax.lax.Precision.HIGHEST
        attn = jnp.matmul(q, kv, precision=exact) / (jnp.matmul(q, z, precision=exact) + 1e-6)[:, None]

    # neighbor bias from the CSR edge list: gated messages over both
    # directions (callers and callees are both signal), sentinel-indexed
    # like graphsage.neighbor_mean so padded edges contribute nothing.
    with jax.named_scope("stlgt/neighbor_bias/dense"):
        em = edge_mask.astype(jnp.float32)
        src_c = jnp.minimum(src_ep, n - 1)
        dst_c = jnp.minimum(dst_ep, n - 1)
        affinity = (q[src_c] * k[dst_c]).sum(axis=1) / jnp.sqrt(
            jnp.float32(q.shape[1])
        )
        gate = jax.nn.sigmoid(affinity + params.b_edge[0]) * em
        if plan is not None:
            bias = sparse_gated.planned_gated_sum(plan, q, k, v, params.b_edge)
        else:
            src_s = jnp.where(edge_mask, src_ep, n)
            dst_s = jnp.where(edge_mask, dst_ep, n)
            msg_fwd = v[src_c] * gate[:, None]
            msg_bwd = v[dst_c] * gate[:, None]
            bias = jax.ops.segment_sum(msg_fwd, dst_s, num_segments=n + 1)[:-1]
            bias = bias + jax.ops.segment_sum(msg_bwd, src_s, num_segments=n + 1)[:-1]
            deg = jax.ops.segment_sum(gate, dst_s, num_segments=n + 1)[:-1]
            deg = deg + jax.ops.segment_sum(gate, src_s, num_segments=n + 1)[:-1]
            bias = bias / jnp.maximum(deg, 1.0)[:, None]

    with jax.named_scope("stlgt/ffn/dense"):
        h1 = x + jax.nn.relu((attn + bias) @ params.w_o)
        h2 = h1 + jax.nn.relu(
            jax.nn.relu(h1 @ params.w_f1 + params.b_f1) @ params.w_f2 + params.b_f2
        )
        return h2 * lane[:, None], gate


def forward_quantiles(
    params: StlgtParams,
    features: jnp.ndarray,
    src_ep: jnp.ndarray,
    dst_ep: jnp.ndarray,
    edge_mask: jnp.ndarray,
    plan: sparse.EdgePlan = None,
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Full STLGT surface -> (latency quantiles [N, NUM_QUANTILES] in
    log1p-ms, anomaly logits [N], per-edge attribution gates [E]).

    Quantile columns are monotone by construction: p50 is the raw head,
    each later level adds a softplus increment — a crossed quantile pair
    cannot be emitted, so coverage scoring never needs to re-sort."""
    h, gate = encode(params, features, src_ep, dst_ep, edge_mask, plan)
    with jax.named_scope("stlgt/readout/dense"):
        raw = h @ params.w_quant + features @ params.w_quant_skip + params.b_quant
        q50 = raw[:, 0]
        q95 = q50 + jax.nn.softplus(raw[:, 1])
        q99 = q95 + jax.nn.softplus(raw[:, 2])
        quantiles = jnp.stack([q50, q95, q99], axis=1)
        anomaly_logit = (
            h @ params.w_anomaly + features @ params.w_anomaly_skip + params.b_anomaly
        )[:, 0]
    return quantiles, anomaly_logit, gate


def forward(
    params: StlgtParams,
    features: jnp.ndarray,
    src_ep: jnp.ndarray,
    dst_ep: jnp.ndarray,
    edge_mask: jnp.ndarray,
    plan: sparse.EdgePlan = None,
):
    """Model-module compatibility surface: (p50 latency, anomaly logit) —
    the (latency, logit) pair every existing consumer expects
    (serving.forecast_forward, stacked.predict_all, common loss)."""
    quantiles, anomaly_logit, _gate = forward_quantiles(
        params, features, src_ep, dst_ep, edge_mask, plan
    )
    return quantiles[:, 0], anomaly_logit


def make_pinball_loss_fn(
    pos_weight: float = 1.0, quantiles: Tuple[float, ...] = QUANTILES
):
    """Masked pinball (quantile) loss over the three levels + the
    family-standard weighted BCE anomaly term. Signature matches
    common.make_loss_fn's product, with the forward's `plan=` after it:
    the head's own loss, which the fused epoch block
    (stacked.epoch_runner, as `make_loss_fn`) and the continual
    trainer's (stlgt_epoch_runner) both train under. The triple is
    (total, (quantile loss, anomaly loss)): `trainer.train` reports the
    quantile loss as `latency_losses`."""
    taus = jnp.asarray(quantiles, dtype=jnp.float32)

    def loss_fn(
        params,
        features,
        src_ep,
        dst_ep,
        edge_mask,
        target_latency,
        target_anomaly,
        node_mask,
        plan=None,
    ):
        pred_q, anomaly_logit, _gate = forward_quantiles(
            params, features, src_ep, dst_ep, edge_mask, plan
        )
        with jax.named_scope("loss"):
            w = node_mask.astype(jnp.float32)
            denom = jnp.maximum(w.sum(), 1.0)
            diff = target_latency[:, None] - pred_q  # [N, Q]
            pinball = jnp.maximum(taus * diff, (taus - 1.0) * diff)
            quant_loss = jnp.sum(w[:, None] * pinball) / denom
            class_w = 1.0 + (pos_weight - 1.0) * target_anomaly
            anomaly_loss = (
                jnp.sum(
                    w
                    * class_w
                    * optax.sigmoid_binary_cross_entropy(
                        anomaly_logit, target_anomaly
                    )
                )
                / denom
            )
            return quant_loss + anomaly_loss, (quant_loss, anomaly_loss)

    return loss_fn


make_loss_fn = make_pinball_loss_fn
make_optimizer = _common.make_optimizer


def make_train_step(optimizer, pos_weight: float = 1.0):
    return _common.make_train_step(optimizer, make_loss_fn(pos_weight))
