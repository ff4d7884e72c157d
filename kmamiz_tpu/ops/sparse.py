"""The served path's sparse backend knob, the planned reductions' counts as `/timings` reads them, and the
scorers' counting primitives.

Backend knob (mirrored in config.Settings):

- ``KMAMIZ_SPARSE=sparse`` (default): scorers use the packed-key sparse
  counting path, the dependency walk picks the flat-gather variant on
  CPU hosts (the MXU packed walk stays default on TPU, where it measures
  >=50x faster), and the training refresh hands each head that takes one
  the stack's edge plan (ops/edge_plan.py).
- ``KMAMIZ_SPARSE=xla``: every consumer keeps the legacy dense/XLA path
  bit-for-bit (the fallback the parity tests pin against); no edge plan
  is handed to a model (``models/stacked.plan_for``).

Sparse counting primitives for the scorer rewrite (``dense_rank_pairs``,
``run_start_index``, ``exclusive_cumsum``): the scorers replace the 8M-row
5-key lexsort with packed-int32 single-key UNSTABLE sorts per direction table
(unstable 1-key sort of 4M rows measures ~0.3 s vs ~1.8 s/pass stable and
~6.7 s for the 5-key comparator, same box) — see scorers.py for the counting
core built on these.

Parity contract (pinned by tests/test_ops_sparse.py and the per-consumer
parity tests): integer-derived lanes are bit-exact across backends;
float reductions whose addend ORDER changes (relying factor, the planned
sums) are pinned at fp32 tolerance.
"""
from __future__ import annotations

import os
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from kmamiz_tpu.ops import walk

_VALID_BACKENDS = ("xla", "sparse")

_backend_cache: Optional[str] = None


def backend() -> str:
    """Process-wide sparse backend, cached after first read (the store and
    scorers bake it into registered-program dispatch; tests flipping the
    env var must call reset_for_tests — conftest does)."""
    global _backend_cache
    if _backend_cache is None:
        val = os.environ.get("KMAMIZ_SPARSE", "sparse").strip().lower()
        if val not in _VALID_BACKENDS:
            raise ValueError(
                f"KMAMIZ_SPARSE={val!r} not in {_VALID_BACKENDS}"
            )
        _backend_cache = val
    return _backend_cache


def reset_for_tests() -> None:
    """Drop the cached knob read (tests monkeypatching KMAMIZ_SPARSE) and
    the routing counters."""
    global _backend_cache
    _backend_cache = None
    walk.reset_counts()


def use_sparse() -> bool:
    """Sparse counting/walk paths enabled (any backend but xla)."""
    return backend() != "xla"


def route_stats() -> dict:
    """Backend, the planned reductions' counters, the walks' MXU products and kept block arrays (/timings)."""
    return {"backend": backend(), **walk.route_counts()}


def dense_rank_pairs(
    a: jnp.ndarray, b: jnp.ndarray
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Dense rank of (a, b) pairs: returns (gid[N] int32, a_of_gid[N])
    where gid is the 0-based rank of row (a[i], b[i]) in the sorted
    distinct-pair order and a_of_gid[g] recovers a for group g (slots
    past the group count are 0). The rank order is (a, b)-lexicographic,
    so within any fixed a the gid is monotone in b and CONTIGUOUS per a —
    the property the sparse scorer's packed by-side keys rely on. One
    2-key sort + one scatter over N rows (~10 ms at 100k endpoints,
    measured same-box vs ~6.7 s for the 8M-row 5-key lexsort it replaces).
    """
    n = a.shape[0]
    iota = jnp.arange(n, dtype=jnp.int32)
    s_a, s_b, s_i = jax.lax.sort(
        (a.astype(jnp.int32), b.astype(jnp.int32), iota), num_keys=2
    )
    first = jnp.concatenate(
        [
            jnp.ones(1, dtype=bool),
            (s_a[1:] != s_a[:-1]) | (s_b[1:] != s_b[:-1]),
        ]
    )
    rank_sorted = jnp.cumsum(first.astype(jnp.int32)) - 1
    gid = jnp.zeros(n, jnp.int32).at[s_i].set(rank_sorted)
    # idempotent per-group scatter: every row of group g writes the same a
    a_of_gid = jnp.zeros(n, jnp.int32).at[rank_sorted].max(s_a)
    return gid, a_of_gid


def run_start_index(first: jnp.ndarray) -> jnp.ndarray:
    """For each row of a sorted table, the index of its run's first row
    (``first`` marks run boundaries). A cummax over (first ? i : -1) —
    no scatter, no segment ids. Rows before any boundary clamp to 0."""
    n = first.shape[0]
    iota = jnp.arange(n, dtype=jnp.int32)
    return jnp.maximum(
        jax.lax.cummax(jnp.where(first, iota, jnp.int32(-1))), 0
    )


def exclusive_cumsum(flags: jnp.ndarray) -> jnp.ndarray:
    """int32 exclusive prefix sum with a trailing total, length N+1:
    out[i] = number of set flags strictly before i. Boundary differences
    out[hi] - out[lo] over it are bit-exact distinct counts."""
    return jnp.concatenate(
        [jnp.zeros(1, jnp.int32), jnp.cumsum(flags.astype(jnp.int32))]
    )

