"""Shared training scaffolding for the graph model families.

Every head (GraphSAGE, GAT) predicts (latency [N], anomaly logits [N])
from (features, src, dst, edge_mask); the loss, optimizer, and jitted
train step are identical and live here so the families cannot drift.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import optax


def make_loss_fn(forward, pos_weight: float = 1.0, axis_name=None):
    """Masked MSE (latency) + masked sigmoid BCE (anomaly) over a head's forward. pos_weight scales
    the positive-class BCE term: anomalies are rare (a few fault-window slots per day), and unweighted
    BCE drives the head into predicting the base rate, never crossing any useful threshold.
    `axis_name`: each device of that mesh axis holds a share of the nodes (`_over`, below)."""
    share, total = _over(axis_name)

    def loss_fn(
        params,
        features,
        src_ep,
        dst_ep,
        edge_mask,
        target_latency,
        target_anomaly,
        node_mask,
    ):
        pred_latency, anomaly_logit = forward(
            share(params), features, src_ep, dst_ep, edge_mask
        )
        with jax.named_scope("loss"):  # the masked sums; `total`'s adding over devices is `collective` beneath
            w = node_mask.astype(jnp.float32)
            denom = jnp.maximum(total(w.sum()), 1.0)
            latency_loss = total(jnp.sum(w * (pred_latency - target_latency) ** 2)) / denom
            class_w = 1.0 + (pos_weight - 1.0) * target_anomaly
            anomaly_loss = (
                total(jnp.sum(
                    w
                    * class_w
                    * optax.sigmoid_binary_cross_entropy(anomaly_logit, target_anomaly)
                ))
                / denom
            )
            return latency_loss + anomaly_loss, (latency_loss, anomaly_loss)

    return loss_fn


def concat_embedding(features: jnp.ndarray, embedding) -> jnp.ndarray:
    """Concatenate the learned node-identity embedding to the feature
    block, zero-padding it when the features carry bucket-padded node rows
    (models/stacked.py): padded nodes are masked out of the loss and have
    no edges, so a zero identity is exact."""
    if embedding is None:
        return features
    pad = features.shape[0] - embedding.shape[0]
    if pad:
        embedding = jnp.pad(embedding, ((0, pad), (0, 0)))
    return jnp.concatenate([features, embedding], axis=1)


def make_optimizer(lr: float = 1e-3):
    return optax.adamw(lr, weight_decay=1e-4)


def make_train_step(optimizer, loss_fn):
    """Jitted (params, opt_state, batch...) -> (params, opt_state, loss, aux).

    params/opt_state are donated: callers rebind both from the return
    value, so the update writes in place instead of double-buffering
    the model on device (no-op on CPU, where donation is ignored)."""

    @partial(jax.jit, donate_argnums=(0, 1))
    def train_step(
        params,
        opt_state,
        features,
        src_ep,
        dst_ep,
        edge_mask,
        target_latency,
        target_anomaly,
        node_mask,
    ):
        grad_fn = jax.value_and_grad(loss_fn, has_aux=True)
        (loss, aux), grads = grad_fn(
            params,
            features,
            src_ep,
            dst_ep,
            edge_mask,
            target_latency,
            target_anomaly,
            node_mask,
        )
        updates, opt_state = optimizer.update(grads, opt_state, params)
        params = optax.apply_updates(params, updates)
        return params, opt_state, loss, aux

    return train_step


def _over(axis_name):
    """(share, total) of a loss whose nodes are cut over the devices of a mesh
    axis: `share` hands a device the replicated parameters and sums the
    devices' gradients, `total` adds the devices' partial sums and counts, so
    a masked mean over nodes is total(sum) / total(count), the same number on
    every device, and its gradient the whole one. With no axis both are the
    identity and the loss traces to what it always did."""
    if axis_name is None:
        return (lambda x: x), (lambda x: x)
    from kmamiz_tpu.parallel import mesh

    return partial(mesh.shared, axis=axis_name), partial(mesh.total, axis=axis_name)
