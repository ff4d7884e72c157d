"""graftsparse: fused SDDMM/SpMM kernels over the flat CSR edge arrays.

The device-compute spine has four consumers of per-edge gather ->
elementwise -> segment-reduce chains: the service scorers
(ops/scorers.py), the packed ancestor walk (graph/store.py windows), the
GraphSAGE ``neighbor_mean`` and the STLGT sigmoid-gated neighbor bias.
At the 100k-endpoint / 4M-edge regime the XLA formulations either
materialize padded-dense intermediates (the [T, L, L] one-hot walk) or
pay a 5-key comparator lexsort over 8M direction rows (~6.7 s of the
8.9 s refresh, measured same-box). This module is the shared sparse
backend behind all four:

- **Fused SDDMM/SpMM Pallas kernels** (FusedMM, arXiv:2011.06391; dense-
  hardware sparse GNN training, arXiv:1906.11786): one kernel does
  edge-gather (one-hot MXU matmul against the node table), the per-edge
  elementwise SDDMM half (dot + sigmoid gate), and the SpMM
  segment-reduce back to endpoint rows — blocked over EDGE TILES with the
  node table resident in VMEM, so no [E, H] message array ever lands in
  HBM and the padded-dense adjacency is never materialized. Used by the
  STLGT neighbor bias (gated mode) and GraphSAGE neighbor sums (plain
  mode) when the backend is ``pallas``/``pallas_interpret``.
- **The planned neighbour sum** (``EdgePlan``, ``planned_neighbor_sum``):
  where a caller runs one topology many times (the training refresh: 432
  slots a call over one edge list), the topology is sorted ONCE by owner
  into an edge plan and ``A @ h`` is one row gather and one tiled
  reduction of consecutive rows, with its own VJP (A is symmetric: the
  backward is the forward) — O(E) one-hot work, no node-table cap, no
  scatter. A Pallas kernel on the TPU, the same items in plain XLA
  elsewhere. It engages by what the caller passes (a plan), under every
  backend but ``xla``.
- **Sparse counting primitives** for the scorer rewrite
  (``dense_rank_pairs``, ``run_start_index``): the scorers replace the
  8M-row 5-key lexsort with packed-int32 single-key UNSTABLE sorts per
  direction table (unstable 1-key sort of 4M rows measures ~0.3 s vs
  ~1.8 s/pass stable and ~6.7 s for the 5-key comparator, same box) —
  see scorers.py for the counting core built on these.

Backend knob (mirrored in config.Settings):

- ``KMAMIZ_SPARSE=sparse`` (default): scorers use the packed-key sparse
  counting path, the dependency walk picks the flat-gather variant on
  CPU hosts (the MXU packed walk stays default on TPU, where it measures
  >=50x faster); GraphSAGE/STLGT keep their gather/segment-sum XLA code
  for a graph used once (the tick), and GraphSAGE's training refresh
  takes the planned sum over the stack's edge plan.
- ``KMAMIZ_SPARSE=pallas``: additionally routes the STLGT bias and
  GraphSAGE neighbor sums through the fused Pallas kernel, compiled by
  Mosaic — on a backend Mosaic cannot target the kernel RAISES; it never
  runs interpreted under this name. A node table past the VMEM budget
  (``fused_route``) gives way to the XLA formulation, and every such
  give-way is counted (``route_stats``, shown in /timings).
- ``KMAMIZ_SPARSE=pallas_interpret``: the same kernels in interpret mode
  (CI/CPU parity testing) — the ONLY setting that interprets.
- ``KMAMIZ_SPARSE=xla``: every consumer keeps the legacy dense/XLA path
  bit-for-bit (the fallback the parity tests pin against); no edge plan
  is handed to a model (``models/stacked.plan_for``).

``KMAMIZ_SPARSE_TILE`` sets the edge-tile block (default 256, a
multiple of the 128-lane width); ``KMAMIZ_SPARSE_NODE_MAX`` bounds the
VMEM-resident node table for the fused kernels (default 2048 rows; at
tile=256 that is four ~2 MB one-hot tiles plus the double-buffered node
tables — ``_fused_call`` sizes the kernel's VMEM limit from the shapes).

Parity contract (pinned by tests/test_ops_sparse.py and the per-consumer
parity tests): integer-derived lanes are bit-exact across backends;
float reductions whose addend ORDER changes (relying factor, fused-kernel
matmul accumulation) are pinned at fp32 tolerance.
"""
from __future__ import annotations

import os
import threading
from functools import partial
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from kmamiz_tpu.core import programs

_VALID_BACKENDS = ("xla", "sparse", "pallas", "pallas_interpret")

_backend_cache: Optional[str] = None
_tile_cache: Optional[int] = None
_node_max_cache: Optional[int] = None

_route_lock = threading.Lock()
#: fused-kernel routing decisions since process start (trace-time
#: counts: the consumers decide inside their jit traces)
_route_counts = {"fused": 0, "gaveWay": 0, "lastGaveWayNodes": 0, "planned": 0}


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


def tile_size() -> int:
    """Edge-tile block for the fused kernels (KMAMIZ_SPARSE_TILE)."""
    global _tile_cache
    if _tile_cache is None:
        t = int(os.environ.get("KMAMIZ_SPARSE_TILE", "256"))
        if t < 128 or t % 128:
            raise ValueError(
                f"KMAMIZ_SPARSE_TILE={t} must be a multiple of 128"
            )
        _tile_cache = t
    return _tile_cache


def node_budget() -> int:
    """Max VMEM-resident node-table rows for the fused kernels."""
    global _node_max_cache
    if _node_max_cache is None:
        _node_max_cache = int(os.environ.get("KMAMIZ_SPARSE_NODE_MAX", "2048"))
    return _node_max_cache


def reset_for_tests() -> None:
    """Drop the cached knob reads (tests monkeypatching KMAMIZ_SPARSE*)
    and the routing counters."""
    global _backend_cache, _tile_cache, _node_max_cache
    _backend_cache = None
    _tile_cache = None
    _node_max_cache = None
    with _route_lock:
        _route_counts.update(fused=0, gaveWay=0, lastGaveWayNodes=0, planned=0)


def use_sparse() -> bool:
    """Sparse counting/walk paths enabled (any backend but xla)."""
    return backend() != "xla"


def fused_enabled() -> bool:
    """Fused Pallas SDDMM/SpMM kernels requested for the model consumers."""
    return backend() in ("pallas", "pallas_interpret")


def fused_interpret() -> bool:
    """Interpret-mode flag for the fused kernels: only the
    pallas_interpret backend interprets. ``pallas`` always hands the
    kernel to Mosaic, so selecting it where Mosaic cannot compile is an
    error the caller sees, not a silent change of what runs."""
    return backend() == "pallas_interpret"


def fused_route(num_nodes: int) -> bool:
    """Whether a model consumer takes the fused kernel for a node table
    of ``num_nodes`` rows. Under a pallas backend a table past the VMEM
    budget gives way to the XLA gather/segment-sum formulation; both
    outcomes are counted so the give-way is visible (``route_stats``)."""
    if not fused_enabled():
        return False
    fits = num_nodes <= node_budget()
    with _route_lock:
        if fits:
            _route_counts["fused"] += 1
        else:
            _route_counts["gaveWay"] += 1
            _route_counts["lastGaveWayNodes"] = int(num_nodes)
    return fits


def route_stats() -> dict:
    """Backend selection and fused-kernel routing counters (/timings)."""
    with _route_lock:
        counts = dict(_route_counts)
    return {
        "backend": backend(),
        "interpret": fused_interpret(),
        "tile": tile_size(),
        "nodeBudget": node_budget(),
        **counts,
    }


def _pad_to(n: int, mult: int) -> int:
    return -(-n // mult) * mult


# ---------------------------------------------------------------------------
# fused SDDMM/SpMM kernel (edge-tile grid, VMEM-resident node table)
# ---------------------------------------------------------------------------
#
# grid = (e_pad // tile,), "arbitrary": the bias output accumulates across
# every edge tile into the same [N, H] VMEM block (initialized at tile 0),
# while the per-edge gate writes one [tile, 1] block per step. Gathers and
# scatters both ride the MXU as one-hot matmuls over masks built in-kernel
# from broadcasted_iota — the only O(E*N) objects are VMEM tiles, never an
# HBM array.
#
# Layout: nothing moves between the lane and the sublane axis. Mosaic does
# not require that — the PR 13 form (1-D edge vectors, one pair of one-hots
# contracted over the edge axis, M=1 degree products) compiles and agrees
# with XLA too, with half the one-hot tiles and no ones column (PERF.md,
# PR 21). This form stays because it is the one chip_smoke.py phase D has
# run; time both before preferring either.
# Everything per-edge is a COLUMN ([tile, 1], edges on sublanes) — the
# gather one-hots [tile, N] compare an id column against a lane iota, and
# the gate falls out of a lane reduction as a column. The
# scatter needs the transposed one-hots [N, tile]; those are built directly
# from the same ids passed a second time as a ROW ([1, tile]) against a
# sublane iota, so the scatter is a plain [N, tile] @ [tile, H] matmul
# rather than a contraction over the leading axis of both operands. The
# degree reduction rides the same matmul: the value table carries a column
# of ones at index h, so column h of the scattered sum IS the gate-weighted
# degree (a whole extra 128-lane block when h is a multiple of 128).


def _fused_kernel(
    src_row_ref,
    dst_row_ref,
    src_col_ref,
    dst_col_ref,
    mask_ref,
    v_ref,
    *rest,
    gated: bool,
    inv_sqrt_h: float,
):
    if gated:
        q_ref, k_ref, b_ref, bias_ref, gate_ref = rest
    else:
        bias_ref, gate_ref = rest

    @pl.when(pl.program_id(0) == 0)
    def _init():
        bias_ref[...] = jnp.zeros_like(bias_ref)

    tile = src_col_ref.shape[0]
    n_pad = v_ref.shape[0]
    lane_ids = jax.lax.broadcasted_iota(jnp.int32, (tile, n_pad), 1)
    row_ids = jax.lax.broadcasted_iota(jnp.int32, (n_pad, tile), 0)
    # parked ids (n_pad) match no iota entry -> all-zero one-hot rows, so
    # invalid edges gather zeros and scatter nothing
    oh_src = (src_col_ref[...] == lane_ids).astype(jnp.float32)  # [T, N]
    oh_dst = (dst_col_ref[...] == lane_ids).astype(jnp.float32)
    oh_src_t = (src_row_ref[...] == row_ids).astype(jnp.float32)  # [N, T]
    oh_dst_t = (dst_row_ref[...] == row_ids).astype(jnp.float32)

    # f32 tables through the MXU: HIGHEST keeps the one-hot extraction
    # f32-exact (the default would round table values to bf16)
    dot = partial(
        jnp.dot,
        preferred_element_type=jnp.float32,
        precision=jax.lax.Precision.HIGHEST,
    )
    v_src = dot(oh_src, v_ref[...])  # [T, H] edge-gather (SpMM in)
    v_dst = dot(oh_dst, v_ref[...])

    m = mask_ref[...]  # [T, 1] f32
    if gated:
        q_e = dot(oh_src, q_ref[...])
        k_e = dot(oh_dst, k_ref[...])
        # SDDMM half: per-edge scaled dot + sigmoid gate on the VPU
        aff = jnp.sum(q_e * k_e, axis=1, keepdims=True) * inv_sqrt_h
        g = jax.nn.sigmoid(aff + b_ref[0, 0]) * m
    else:
        g = m
    gate_ref[...] = g

    # SpMM half: segment-reduce both directions back to endpoint rows
    bias_ref[...] += dot(oh_dst_t, g * v_src) + dot(oh_src_t, g * v_dst)


def _fused_vmem_bytes(tile: int, n_pad: int, h_pad: int, gated: bool) -> int:
    """VMEM the fused kernel needs, from its shapes: four f32 one-hot
    tiles, the node tables (inputs are double-buffered by the pipeline
    even at a constant block index), the resident accumulator, and the
    [tile, h_pad] gathered/gated intermediates."""
    one_hots = 4 * tile * n_pad * 4
    tables = (3 if gated else 1) * 2 * n_pad * h_pad * 4
    accumulator = 2 * n_pad * h_pad * 4
    edge_values = 8 * tile * h_pad * 4
    return one_hots + tables + accumulator + edge_values


def _fused_call(
    src_ep,
    dst_ep,
    edge_mask,
    v,
    q,
    k,
    b_edge,
    gated: bool,
    tile: int,
    interpret: bool,
):
    n, h = v.shape
    e = src_ep.shape[0]
    e_pad = _pad_to(max(e, 1), tile)
    n_pad = _pad_to(n + 1, 128)  # +1 spill column keeps the park id in-grid
    h_pad = _pad_to(h + 1, 128)  # +1: the ones column that yields the degree

    def _park(ep):
        ep = jnp.where(edge_mask, jnp.clip(ep, 0, n - 1), n_pad)
        return jnp.pad(
            ep.astype(jnp.int32), (0, e_pad - e), constant_values=n_pad
        )

    src_p = _park(src_ep)
    dst_p = _park(dst_ep)
    mask_p = jnp.pad(edge_mask.astype(jnp.float32), (0, e_pad - e))

    def _table(t, ones_col: bool = False):
        t = t.astype(jnp.float32)
        if ones_col:
            t = jnp.concatenate([t, jnp.ones((n, 1), jnp.float32)], axis=1)
        return jnp.pad(t, ((0, n_pad - n), (0, h_pad - t.shape[1])))

    row_spec = pl.BlockSpec((1, tile), lambda i: (0, i))
    col_spec = pl.BlockSpec((tile, 1), lambda i: (i, 0))
    table_spec = pl.BlockSpec((n_pad, h_pad), lambda i: (0, 0))

    in_specs = [row_spec, row_spec, col_spec, col_spec, col_spec, table_spec]
    operands = [
        src_p[None, :],
        dst_p[None, :],
        src_p[:, None],
        dst_p[:, None],
        mask_p[:, None],
        _table(v, ones_col=True),
    ]
    if gated:
        in_specs += [
            table_spec,
            table_spec,
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ]
        operands += [
            _table(q),
            _table(k),
            b_edge.reshape(1, 1).astype(jnp.float32),
        ]

    vmem_limit = min(
        100 << 20,
        max(32 << 20, 2 * _fused_vmem_bytes(tile, n_pad, h_pad, gated)),
    )
    bias, gate = pl.pallas_call(
        partial(
            _fused_kernel,
            gated=gated,
            inv_sqrt_h=1.0 / float(max(h, 1)) ** 0.5,
        ),
        grid=(e_pad // tile,),
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((n_pad, h_pad), lambda i: (0, 0)),
            pl.BlockSpec((tile, 1), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n_pad, h_pad), jnp.float32),
            jax.ShapeDtypeStruct((e_pad, 1), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=vmem_limit,
        ),
        interpret=interpret,
    )(*operands)
    return bias[:n, :h], bias[:n, h], gate[:e, 0]


@programs.register("sparse.fused_gated_bias")
@partial(jax.jit, static_argnames=("tile", "interpret"))
def fused_gated_bias(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    b_edge: jnp.ndarray,
    src_ep: jnp.ndarray,
    dst_ep: jnp.ndarray,
    edge_mask: jnp.ndarray,
    tile: int = 256,
    interpret: bool = False,
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Fused STLGT neighbor bias: SDDMM gate
    ``sigmoid((q[src] . k[dst]) / sqrt(H) + b_edge) * mask`` and the
    bidirectional gated SpMM in one kernel.

    Returns (bias_sum[N, H], gate_deg[N], gate[E]) — UN-normalized sums;
    the model divides by max(gate_deg, 1) exactly as the XLA path does.
    """
    return _fused_call(
        src_ep, dst_ep, edge_mask, v, q, k, b_edge,
        gated=True, tile=tile, interpret=interpret,
    )


@programs.register("sparse.fused_neighbor_sums")
@partial(jax.jit, static_argnames=("tile", "interpret"))
def fused_neighbor_sums(
    h: jnp.ndarray,
    src_ep: jnp.ndarray,
    dst_ep: jnp.ndarray,
    edge_mask: jnp.ndarray,
    tile: int = 256,
    interpret: bool = False,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Fused GraphSAGE neighbor aggregation: bidirectional masked SpMM
    plus the degree reduction in one kernel.

    Returns (agg[N, F], deg[N]); ``neighbor_mean`` divides agg by
    max(deg, 1) exactly as the XLA path does.
    """
    agg, deg, _gate = _fused_call(
        src_ep, dst_ep, edge_mask, h, None, None, None,
        gated=False, tile=tile, interpret=interpret,
    )
    return agg, deg


# ---------------------------------------------------------------------------
# planned neighbour sum: a per-dataset edge plan and a sorted, tiled SpMM
# ---------------------------------------------------------------------------
#
# A training refresh runs the same edge list through every slot of every
# call, so what depends on the topology alone is prepared ONCE, on the host,
# where the stack is built (models/stacked.py): the undirected neighbour list
# sorted by owner, the degree, and the work list of a tiled reduction. With
# the list sorted, `agg = A @ h` (A the symmetric 0/1 adjacency) is one row
# gather and one reduction of CONSECUTIVE rows into node tiles: a node tile
# meets only the few edge blocks that overlap it, so the one-hot products
# are O(E), not the O(E x N) of the fused kernel above. A is symmetric, so
# the cotangent of h is the same product of the cotangent of agg: the VJP is
# the forward, nothing is saved, and no scatter is left in either pass.

#: rows of one output tile and entries of one edge block. A product is
#: [PLAN_NODE_TILE, PLAN_EDGE_BLOCK] @ [PLAN_EDGE_BLOCK, width]; the one-hot
#: work grows with entries x PLAN_NODE_TILE + nodes x PLAN_EDGE_BLOCK and the
#: number of grid steps falls with both.
PLAN_NODE_TILE = 128
PLAN_EDGE_BLOCK = 512


class EdgePlan(NamedTuple):
    """The topology of one stacked dataset, prepared for `planned_neighbor_sum`.
    Shapes are a function of the node and edge buckets alone.

    An entry is one real edge seen from one end: (owner, neighbour). Entries
    are sorted by owner; masked and padding edges are parked past the end
    with an owner no tile holds. An item is one (node tile, edge block) pair
    whose product contributes to the tile; every tile has at least one item
    (an empty tile's product is all zeros, and writes them), so there are at
    most node_tiles + edge_blocks, and the list is padded to that with no-ops.
    """

    owner: jnp.ndarray  # [1, L] int32, ascending; L = 2 * edge bucket, blocked
    neighbour: jnp.ndarray  # [L] int32: the row each entry adds to its owner
    degree: jnp.ndarray  # [Nb] float32: entries per owner (row-pointer steps)
    item_tile: jnp.ndarray  # [I] int32, ascending
    item_block: jnp.ndarray  # [I] int32
    item_flag: jnp.ndarray  # [I] int32: 1 first of its tile, 0 adds, -1 no-op


def plan_shapes(bucket_nodes: int, bucket_edges: int) -> Tuple[int, int, int]:
    """(entries L, node tiles, items I) of the plan of a bucket pair."""
    entries = _pad_to(max(2 * bucket_edges, 1), PLAN_EDGE_BLOCK)
    node_tiles = -(-max(bucket_nodes, 1) // PLAN_NODE_TILE)
    return entries, node_tiles, node_tiles + entries // PLAN_EDGE_BLOCK


def build_edge_plan(src, dst, edge_mask, bucket_nodes: int):
    """Host arrays of one dataset's (bucket-padded) edge list -> (EdgePlan of
    numpy arrays, real entries, real items). One stable sort of 2 x edges
    keys; an edge whose mask is False or whose end lies outside the bucket
    contributes nothing, as in `graphsage.neighbor_mean`."""
    src = np.asarray(src, dtype=np.int64)
    dst = np.asarray(dst, dtype=np.int64)
    nb = int(bucket_nodes)
    real = (
        np.asarray(edge_mask, dtype=bool)
        & (src >= 0) & (src < nb) & (dst >= 0) & (dst < nb)
    )
    entries, node_tiles, items = plan_shapes(nb, src.shape[0])
    tn, be = PLAN_NODE_TILE, PLAN_EDGE_BLOCK
    edge_blocks = entries // be

    own = np.concatenate([src[real], dst[real]])
    nei = np.concatenate([dst[real], src[real]])
    order = np.argsort(own, kind="stable")
    n_real = int(own.shape[0])
    owner = np.full(entries, node_tiles * tn, dtype=np.int32)  # parked
    neighbour = np.zeros(entries, dtype=np.int32)
    owner[:n_real] = own[order]
    neighbour[:n_real] = nei[order]

    counts = np.bincount(own, minlength=nb)  # every owner is < nb
    row_ptr = np.zeros(node_tiles * tn + 1, dtype=np.int64)
    np.cumsum(counts, out=row_ptr[1 : nb + 1])
    row_ptr[nb + 1 :] = n_real

    # the blocks that hold entries of each tile; an empty tile takes the one
    # block its (empty) range starts in, where no owner matches its rows
    lo, hi = row_ptr[:-1:tn], row_ptr[tn::tn]
    first = np.minimum(lo // be, edge_blocks - 1)
    last = np.where(hi > lo, (hi - 1) // be, first)
    per_tile = last - first + 1
    n_items = int(per_tile.sum())
    starts = np.cumsum(per_tile) - per_tile
    item_tile = np.full(items, node_tiles - 1, dtype=np.int32)
    item_block = np.empty(items, dtype=np.int32)
    item_flag = np.full(items, -1, dtype=np.int32)
    tiles = np.repeat(np.arange(node_tiles), per_tile)
    item_tile[:n_items] = tiles
    item_block[:n_items] = first[tiles] + np.arange(n_items) - starts[tiles]
    item_block[n_items:] = item_block[n_items - 1]  # no new block to fetch
    item_flag[:n_items] = 0
    item_flag[starts] = 1
    plan = EdgePlan(
        owner=owner[None, :],
        neighbour=neighbour,
        degree=counts.astype(np.float32),
        item_tile=item_tile,
        item_block=item_block,
        item_flag=item_flag,
    )
    return plan, n_real, n_items


def _planned_kernel(tile_ref, block_ref, flag_ref, owner_ref, msg_ref, out_ref):
    """One item: out[tile] (+)= onehot(owner block against the tile's rows)
    @ message block. The output tile stays in VMEM across the consecutive
    items of its tile; the grid is sequential, so the order of the sums is
    fixed and two runs give the same bits.

    The product is float32-exact in three bfloat16 passes: the one-hot is
    exact in bfloat16, and a float32 value is the sum of three bfloat16
    pieces (8 + 8 + 8 bits of mantissa), each product accumulated in
    float32. On the v5e that is 1.93 ms a sum at the 100k-endpoint bucket
    against 2.26 ms at Precision.HIGHEST, which splits the one-hot too
    (PERF.md, PR 27)."""
    del block_ref  # read by the index maps
    i = pl.program_id(0)
    flag = flag_ref[i]

    @pl.when(flag == 1)
    def _first():
        out_ref[...] = jnp.zeros_like(out_ref)

    @pl.when(flag >= 0)
    def _add():
        tn, be = out_ref.shape[0], owner_ref.shape[1]
        rows = jax.lax.broadcasted_iota(jnp.int32, (tn, be), 0) + tile_ref[i] * tn
        one_hot = (owner_ref[...] == rows).astype(jnp.bfloat16)
        m = msg_ref[...]
        hi = m.astype(jnp.bfloat16)
        rest = m - hi.astype(jnp.float32)
        mid = rest.astype(jnp.bfloat16)
        lo = (rest - mid.astype(jnp.float32)).astype(jnp.bfloat16)
        dot = partial(jnp.dot, preferred_element_type=jnp.float32)
        out_ref[...] += dot(one_hot, hi) + dot(one_hot, mid) + dot(one_hot, lo)


def _node_tiles(plan: EdgePlan) -> int:
    return plan.item_tile.shape[0] - plan.owner.shape[1] // PLAN_EDGE_BLOCK


def _planned_reduce_pallas(plan: EdgePlan, messages, interpret: bool):
    width = messages.shape[1]
    tn, be = PLAN_NODE_TILE, PLAN_EDGE_BLOCK
    return pl.pallas_call(
        _planned_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(plan.item_tile.shape[0],),
            in_specs=[
                pl.BlockSpec((1, be), lambda i, tile, block, flag: (0, block[i])),
                pl.BlockSpec((be, width), lambda i, tile, block, flag: (block[i], 0)),
            ],
            out_specs=pl.BlockSpec(
                (tn, width), lambda i, tile, block, flag: (tile[i], 0)
            ),
        ),
        out_shape=jax.ShapeDtypeStruct((_node_tiles(plan) * tn, width), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)
        ),
        name="planned_neighbor_sum",
        interpret=interpret,
    )(plan.item_tile, plan.item_block, plan.item_flag, plan.owner, messages)


def _planned_reduce_xla(plan: EdgePlan, messages):
    """The same items in plain XLA: a batched one-hot product per item, then
    a sorted sum of the items' tiles (node_tiles + edge_blocks rows, not one
    per edge). The path off the TPU, and the oracle of the kernel."""
    width = messages.shape[1]
    tn, be = PLAN_NODE_TILE, PLAN_EDGE_BLOCK
    node_tiles = _node_tiles(plan)
    owners = plan.owner.reshape(-1, be)[plan.item_block]  # [I, be]
    blocks = messages.reshape(-1, be, width)[plan.item_block]  # [I, be, W]
    rows = plan.item_tile[:, None] * tn + jnp.arange(tn, dtype=jnp.int32)
    one_hot = (owners[:, None, :] == rows[:, :, None]) & (
        plan.item_flag >= 0
    )[:, None, None]
    partial_tiles = jnp.einsum(
        "itb,ibw->itw",
        one_hot.astype(jnp.float32),
        blocks,
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32,
    )
    tiles = jax.ops.segment_sum(
        partial_tiles,
        plan.item_tile,
        num_segments=node_tiles,
        indices_are_sorted=True,
    )
    return tiles.reshape(node_tiles * tn, width)


def planned_impl() -> str:
    """Which reducer a planned sum traces to: the Mosaic kernel on a TPU (or
    wherever KMAMIZ_SPARSE=pallas asks for it, and then it raises where
    Mosaic cannot compile), the kernel interpreted under pallas_interpret,
    plain XLA elsewhere."""
    if fused_interpret():
        return "pallas_interpret"
    if backend() == "pallas" or jax.default_backend() == "tpu":
        return "pallas"
    return "xla"


def _planned_sum(plan: EdgePlan, h, impl: str):
    messages = h.astype(jnp.float32)[plan.neighbour]  # [L, W]; parked read row 0
    if impl == "xla":
        out = _planned_reduce_xla(plan, messages)
    else:
        out = _planned_reduce_pallas(plan, messages, impl == "pallas_interpret")
    return out[: h.shape[0]].astype(h.dtype)


@partial(jax.custom_vjp, nondiff_argnums=(2,))
def _planned_sum_vjp(plan: EdgePlan, h, impl: str):
    return _planned_sum(plan, h, impl)


def _planned_sum_fwd(plan, h, impl):
    return _planned_sum(plan, h, impl), plan


def _planned_sum_bwd(impl, plan, g):
    # A is symmetric: d(A @ h) pulls back through A itself
    return None, _planned_sum(plan, g, impl)


_planned_sum_vjp.defvjp(_planned_sum_fwd, _planned_sum_bwd)


def planned_neighbor_sum(plan: EdgePlan, h: jnp.ndarray, impl: Optional[str] = None):
    """Sum of neighbour rows over both edge directions, [N, W] -> [N, W]: what
    `neighbor_mean`'s two gathers, mask multiplies and segment sums make,
    from a prepared plan. `impl` is for tests and timing; callers leave it
    to `planned_impl`. Counted in `route_stats()["planned"]` (trace time)."""
    with _route_lock:
        _route_counts["planned"] += 1
    return _planned_sum_vjp(plan, h, impl or planned_impl())


# ---------------------------------------------------------------------------
# sparse counting primitives (scorer building blocks, plain XLA)
# ---------------------------------------------------------------------------


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
