"""Seeded generator: the hourly history of a service mesh, as the trainer's
`GraphDataset` with HOST (numpy) per-slot arrays.

Why host arrays: `stacked.stack_dataset` reads every slot with `np.asarray`;
slot lists that live on the device (as `chip_smoke.py` phase C builds them)
are pulled back one by one and the device then holds the history twice.

Every seed gives the same SIZES in another order, so that a seed changes
neither the work nor the compiled program:

- the in- and out-degree sequences are fixed functions of the configuration
  (`assumed.in_degree`, `assumed.out_degree`); the seed decides which
  endpoint gets which degree and which caller meets which callee;
- each slot has exactly `round(active_share * endpoints)` active endpoints
  and exactly `round(anomaly_rate * active)` anomalous ones among them. The
  trainer bakes `pos_weight = total / positives` into its program
  (`models/stacked.py`, the instance key), so a count that moved with the
  seed would compile a new epoch block for every seed.

Topology: callees are drawn from a Zipf-like in-degree sequence (a few hot
endpoints, gateways, auth, stores, collect most in-edges, as the Alibaba
microservice traces show), callers from a log-normal out-degree sequence
with the configured mean. Edges are distinct, free of self-loops, and
grouped by caller, the order `trainer.dataset_from_simulation` emits.
"""
from __future__ import annotations

from concurrent.futures import ThreadPoolExecutor
from typing import List, Tuple

import numpy as np
from scipy.special import ndtri

from kmamiz_tpu.models.trainer import GraphDataset

#: threads that fill the feature block; each owns a spawned child stream
#: and a fixed range of slots, so the result does not depend on scheduling
_FILL_THREADS = 4


def _largest_remainder(weights: np.ndarray, total: int) -> np.ndarray:
    """Non-negative integers proportional to `weights` that sum to `total`."""
    share = weights / weights.sum() * total
    base = np.floor(share).astype(np.int64)
    short = total - int(base.sum())
    if short:
        order = np.argsort(-(share - base), kind="stable")
        base[order[:short]] += 1
    return base


def degree_sequences(n: int, e: int, assumed: dict) -> Tuple[np.ndarray, np.ndarray]:
    """(out_degree [n], in_degree [n]), both summing to e, heaviest first.
    Pure functions of the configuration: no seed."""
    zipf = assumed["in_degree"]
    rank = np.arange(n, dtype=np.float64)
    in_deg = _largest_remainder(
        1.0 / (rank + float(zipf["offset"])) ** float(zipf["exponent"]), e
    )
    sigma = float(assumed["out_degree"]["sigma"])
    # log-normal quantiles at the mid-points of n equal slices, heaviest first
    out_deg = _largest_remainder(np.exp(sigma * ndtri((n - rank - 0.5) / n)), e)
    return out_deg, in_deg


def _edges(n: int, e: int, assumed: dict, rng: np.random.Generator):
    """Configuration model over the fixed degree sequences, repaired by
    callee swaps (which keep both sequences) until every edge is distinct
    and none is a self-loop."""
    out_deg, in_deg = degree_sequences(n, e, assumed)
    src = np.repeat(rng.permutation(n), out_deg).astype(np.int64)
    dst = np.repeat(rng.permutation(n), in_deg).astype(np.int64)
    dst = dst[rng.permutation(e)]
    for _ in range(200):
        key = src * n + dst
        order = np.argsort(key, kind="stable")
        dup = np.zeros(e, dtype=bool)
        dup[order[1:]] = key[order[1:]] == key[order[:-1]]
        bad = np.flatnonzero(dup | (src == dst))
        if bad.size == 0:
            break
        # shuffle the offending callees among themselves and as many random
        # other edges: a permutation of callees, so both sequences are kept
        pool = np.union1d(bad, rng.integers(0, e, bad.size))
        dst[pool] = dst[rng.permutation(pool)]
    else:
        raise RuntimeError("edge repair did not converge; degree skew too steep")
    order = np.argsort(src * n + dst, kind="stable")
    return src[order].astype(np.int32), dst[order].astype(np.int32)


def _fill_slots(args) -> None:
    child, feats, t_lat, t_ano, mask, lo, hi, n_inactive, n_anomalous = args
    rng = np.random.default_rng(child)
    rng.standard_normal(out=feats[lo:hi], dtype=np.float32)
    rng.standard_normal(out=t_lat[lo:hi], dtype=np.float32)
    n = feats.shape[1]
    for s in range(lo, hi):
        perm = rng.permutation(n)
        mask[s, perm[:n_inactive]] = False
        t_ano[s, perm[n_inactive : n_inactive + n_anomalous]] = 1.0


def generate(config: dict, seed: int) -> GraphDataset:
    """The configuration's history from `seed`: a pure function of both."""
    n = int(config["endpoints"])
    e = int(config["edges"])
    slots = int(config["slots"])
    f = int(config["num_features"])
    assumed = config["assumed"]
    n_active = int(round(float(assumed["active_share"]) * n))
    n_anomalous = int(round(float(assumed["anomaly_base_rate"]) * n_active))

    root = np.random.SeedSequence(int(seed))
    topo_seq, *fill_seqs = root.spawn(1 + _FILL_THREADS)
    src, dst = _edges(n, e, assumed, np.random.default_rng(topo_seq))

    feats = np.empty((slots, n, f), dtype=np.float32)
    t_lat = np.empty((slots, n), dtype=np.float32)
    t_ano = np.zeros((slots, n), dtype=np.float32)
    mask = np.ones((slots, n), dtype=bool)
    cuts = np.linspace(0, slots, _FILL_THREADS + 1).astype(int)
    jobs = [
        (fill_seqs[i], feats, t_lat, t_ano, mask, int(cuts[i]), int(cuts[i + 1]),
         n - n_active, n_anomalous)
        for i in range(_FILL_THREADS)
    ]
    with ThreadPoolExecutor(_FILL_THREADS) as pool:
        list(pool.map(_fill_slots, jobs))

    def per_slot(a: np.ndarray) -> List[np.ndarray]:
        return [a[s] for s in range(slots)]

    return GraphDataset(
        endpoint_names=[f"ep{i:06d}" for i in range(n)],
        src=src,
        dst=dst,
        edge_mask=np.ones(e, dtype=bool),
        features=per_slot(feats),
        target_latency=per_slot(t_lat),
        target_anomaly=per_slot(t_ano),
        node_mask=per_slot(mask),
        slot_keys=[f"slot{s:04d}" for s in range(slots)],
    )


def head(dataset: GraphDataset, slots: int) -> GraphDataset:
    """The first `slots` slots of `dataset` over the same graph (views)."""
    return GraphDataset(
        endpoint_names=dataset.endpoint_names,
        src=dataset.src,
        dst=dataset.dst,
        edge_mask=dataset.edge_mask,
        features=dataset.features[:slots],
        target_latency=dataset.target_latency[:slots],
        target_anomaly=dataset.target_anomaly[:slots],
        node_mask=dataset.node_mask[:slots],
        slot_keys=dataset.slot_keys[:slots],
    )
