"""Plain reference of one refresh: a Python loop of per-slot updates.

One optimizer update per hourly slot, in slot order, from a given init:
the schedule `trainer.train(batch_slots=1)` promises. float32 throughout,
matrix products at `precision` ("highest" for the ground truth: on a TPU a
float32 product otherwise runs as one bfloat16 pass), its own
`optax.adamw(lr, weight_decay=1e-4)` (the optimizer `models/common.py`
names), no scan, no stacking, no padding.

What a family's file, `reference/<family>.py`, may hold:

- `forward(params, x, src, dst)`: required. With the default loss it returns
  (latency prediction [N], anomaly logit [N]); with a loss of the family's
  own, whatever that loss takes from it.
- `make_loss(weight)`: optional, the family's own loss. `weight` is
  `pos_weight(dataset)` below; it returns
  `loss(params, x, src, dst, target_latency, target_anomaly, active) ->
  (total, (first, second))`, the triple `trainer.train` reports as `losses`,
  `latency_losses`, `anomaly_losses`. A family without one gets
  `make_loss(forward, weight)` of this file, the trainer's contract
  (`models/common.py` docstring): over the endpoints active in the next
  slot, mean squared error of the latency head plus sigmoid cross-entropy of
  the anomaly head with the positive class weighted by `pos_weight` =
  1 / (share of active endpoints that are anomalous over all slots given),
  clipped to [1, 20].
- `FORWARD`, `FORWARD_READINGS`: optional, the family's own bounds of the
  one-slot comparison and how many one-slot histories it reads
  (`reference/check.py`, which holds the defaults and the rule, and says what a
  family owes before it states its own).
"""
from __future__ import annotations

import functools
import importlib
from typing import Dict, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np
import optax

WEIGHT_DECAY = 1e-4


def pos_weight(dataset) -> float:
    positives = sum(
        float((np.asarray(a) * np.asarray(m)).sum())
        for a, m in zip(dataset.target_anomaly, dataset.node_mask)
    )
    active = sum(float(np.asarray(m).sum()) for m in dataset.node_mask)
    if not positives or not active:
        return 1.0
    return float(np.clip(active / positives, 1.0, 20.0))


def make_loss(forward, weight: float):
    def loss(params, x, src, dst, target_latency, target_anomaly, active):
        latency, logit = forward(params, x, src, dst)
        w = active.astype(jnp.float32)
        count = jnp.maximum(w.sum(), 1.0)
        latency_loss = jnp.sum(w * (latency - target_latency) ** 2) / count
        # sigmoid cross-entropy, the form that cannot overflow
        bce = (
            jnp.maximum(logit, 0.0)
            - logit * target_anomaly
            + jnp.log1p(jnp.exp(-jnp.abs(logit)))
        )
        class_weight = 1.0 + (weight - 1.0) * target_anomaly
        anomaly_loss = jnp.sum(w * class_weight * bce) / count
        return latency_loss + anomaly_loss, (latency_loss, anomaly_loss)

    return loss


@functools.lru_cache(maxsize=None)
def compiled(module, weight: float, lr: float):
    """The optimizer and the jitted update of one family's module, weight and
    rate. Kept while the check runs (`check.against_reference` clears it): it
    calls `train` four to six times a run on slots of one shape, and traces and
    loads the step once a precision (the precision in force is part of jit's
    key), not once a call."""
    if hasattr(module, "make_loss"):
        loss_fn = module.make_loss(weight)
    else:
        loss_fn = make_loss(module.forward, weight)
    optimizer = optax.adamw(lr, weight_decay=WEIGHT_DECAY)
    grad = jax.value_and_grad(loss_fn, has_aux=True)

    @jax.jit
    def step(params, state, *slot):
        (loss, (first, second)), g = grad(params, *slot)
        updates, state = optimizer.update(g, state, params)
        return optax.apply_updates(params, updates), state, (loss, first, second)

    return optimizer, step


def train(
    family: str,
    init: Dict[str, np.ndarray],
    dataset,
    lr: float,
    precision: str = "highest",
) -> Tuple[Dict[str, np.ndarray], List[Tuple[float, float, float]]]:
    """One epoch over `dataset` (host arrays) from `init`. Returns the
    final params and each slot's (loss, latency loss, anomaly loss).
    `family` names a module of this directory with a `forward` and, where
    the head's loss is its own, a `make_loss` (the module's docstring)."""
    module = importlib.import_module(f"benchmarks.reference.{family}")
    optimizer, step = compiled(module, pos_weight(dataset), float(lr))

    params = {k: jnp.asarray(v, jnp.float32) for k, v in init.items()}
    state = optimizer.init(params)
    src = jnp.asarray(dataset.src, jnp.int32)
    dst = jnp.asarray(dataset.dst, jnp.int32)
    losses = []
    with jax.default_matmul_precision(precision):
        for s in range(len(dataset.features)):
            params, state, triple = step(
                params,
                state,
                jnp.asarray(dataset.features[s], jnp.float32),
                src,
                dst,
                jnp.asarray(dataset.target_latency[s], jnp.float32),
                jnp.asarray(dataset.target_anomaly[s], jnp.float32),
                jnp.asarray(dataset.node_mask[s]),
            )
            losses.append(tuple(float(v) for v in triple))
    return {k: np.asarray(v) for k, v in params.items()}, losses
