"""graftsparse: the edge plan's reductions, and the scorers' counting primitives.

Per-edge gather -> elementwise -> segment-reduce chains run in two places:
the model plane (GraphSAGE's neighbour mean, GAT's attention) and the
served path's scorers and ancestor walk (ops/scorers.py, graph/store.py
windows). This module holds what they share:

- **The planned neighbour sum** (``EdgePlan``, ``planned_neighbor_sum``):
  where a caller runs one topology many times (the training refresh: 432
  slots a call over one edge list), the topology is sorted ONCE by owner
  into an edge plan and ``A @ h`` is one row gather and one tiled
  reduction of consecutive rows, with its own VJP (A is symmetric: the
  backward is the forward) — O(E) one-hot work, no node-table cap, no
  scatter. A Pallas kernel on the TPU, the same items in plain XLA
  elsewhere. It engages by what the caller passes (a plan), under every
  backend but ``xla``. GraphSAGE's refresh takes it.
- **The planned attention** (``planned_attention``): GAT's refresh on the
  same plan. An entry also says which direction of its edge it is, so the
  two directed segment softmaxes of a layer and their weighted sums are
  one pass over the sorted entries: a softmax over consecutive runs, a
  weighted planned sum, the per-entry product ``<g[owner], hw[neighbour]>``
  of the backward pass, with their VJP. Five Mosaic kernels on the TPU,
  sorted segment reductions in plain XLA elsewhere; no scatter and no 1-D
  gather in either pass.
- **Sparse counting primitives** for the scorer rewrite
  (``dense_rank_pairs``, ``run_start_index``): the scorers replace the
  8M-row 5-key lexsort with packed-int32 single-key UNSTABLE sorts per
  direction table (unstable 1-key sort of 4M rows measures ~0.3 s vs
  ~1.8 s/pass stable and ~6.7 s for the 5-key comparator, same box) —
  see scorers.py for the counting core built on these.

A caller of the model plane that holds no plan (the tick's one-off graphs,
`dp_epoch_runner`, `predict_all`, the legacy per-slot loop, STLGT's ring)
reduces its edge list with XLA's gathers and segment sums in the model's own
file (STLGT's refresh on a plan: ops/sparse_gated.py). No environment name picks
between the plan's Pallas kernels and their XLA twins: the platform does
(``planned_impl``).

Backend knob (mirrored in config.Settings):

- ``KMAMIZ_SPARSE=sparse`` (default): scorers use the packed-key sparse
  counting path, the dependency walk picks the flat-gather variant on
  CPU hosts (the MXU packed walk stays default on TPU, where it measures
  >=50x faster), and the training refresh of GraphSAGE and of GAT takes
  the planned sum and the planned attention over the stack's edge plan.
- ``KMAMIZ_SPARSE=xla``: every consumer keeps the legacy dense/XLA path
  bit-for-bit (the fallback the parity tests pin against); no edge plan
  is handed to a model (``models/stacked.plan_for``).

Parity contract (pinned by tests/test_ops_sparse.py and the per-consumer
parity tests): integer-derived lanes are bit-exact across backends;
float reductions whose addend ORDER changes (relying factor, the planned
sums) are pinned at fp32 tolerance.
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

_VALID_BACKENDS = ("xla", "sparse")

_backend_cache: Optional[str] = None

_route_lock = threading.Lock()
#: reductions over an edge plan since process start (trace-time counts:
#: the consumers decide inside their jit traces); `_walk_call`'s products
_route_counts = {"planned": 0, "attention": 0, "gatv2": 0, "sharded": 0, "mxu_products": {}}


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
    with _route_lock:
        _route_counts.update(planned=0, attention=0, gatv2=0, sharded=0, mxu_products={})


def use_sparse() -> bool:
    """Sparse counting/walk paths enabled (any backend but xla)."""
    return backend() != "xla"


def route_stats() -> dict:
    """Backend, the planned reductions' counters, the walks' MXU products (/timings)."""
    with _route_lock:
        counts = dict(_route_counts)
    return {"backend": backend(), **counts}


def _pad_to(n: int, mult: int) -> int:
    return -(-n // mult) * mult


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
# are O(E), not the O(E x N) of a one-hot against the whole node table. A is
# symmetric, so the cotangent of h is the same product of the cotangent of
# agg: the VJP is the forward, nothing is saved, and no scatter is left in
# either pass.

#: rows of one output tile and entries of one edge block. A product is
#: [PLAN_NODE_TILE, PLAN_EDGE_BLOCK] @ [PLAN_EDGE_BLOCK, width]; the one-hot
#: work grows with entries x PLAN_NODE_TILE + nodes x PLAN_EDGE_BLOCK and the
#: number of grid steps falls with both.
PLAN_NODE_TILE = 128
PLAN_EDGE_BLOCK = 512


class EdgePlan(NamedTuple):
    """The topology of one stacked dataset, prepared for `planned_neighbor_sum`.
    Shapes are a function of the node and edge buckets alone.

    An entry is one real edge seen from one end: (owner, neighbour,
    direction), direction 0 where the owner is the edge's source (an edge OUT
    of the owner) and 1 where it is the destination (an edge INTO it). Entries
    are sorted by owner and, within an owner, by direction; masked and padding
    edges are parked past the end with an owner no tile holds. Every real
    edge makes two entries that mirror each other: (u, v, 0) and (v, u, 1).
    An item is one (node tile, edge block) pair whose product contributes to
    the tile; every tile has at least one item (an empty tile's product is
    all zeros, and writes them), so there are at most node_tiles +
    edge_blocks, and the list is padded to that with no-ops. The blocks of
    consecutive items never fall, so a per-entry result can be written block
    by block as a per-node result is tile by tile.
    """

    owner: jnp.ndarray  # [1, L] int32, ascending; L = 2 * edge bucket, blocked
    neighbour: jnp.ndarray  # [L] int32: the row each entry adds to its owner
    degree: jnp.ndarray  # [Nb] float32: entries per owner (row-pointer steps)
    item_tile: jnp.ndarray  # [I] int32, ascending
    item_block: jnp.ndarray  # [I] int32
    item_flag: jnp.ndarray  # [I] int32: 1 first of its tile, 0 adds, -1 no-op
    direction: jnp.ndarray  # [1, L] int32: 0 owner is the source, 1 the destination
    mean_log_degree: jnp.ndarray  # [] float32: `mean_log_degree(degree)`, a constant of the topology (PNA's delta)


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
    direction = np.zeros(entries, dtype=np.int32)
    owner[:n_real] = own[order]
    neighbour[:n_real] = nei[order]
    # the sort is stable and the sources come first: out-edges, then in-edges
    direction[:n_real] = order >= n_real // 2

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
        direction=direction[None, :],
        mean_log_degree=np.asarray(mean_log_degree(counts)),
    )
    return plan, n_real, n_items


def _split3(x):
    """A float32 array as three bfloat16 pieces that sum to it exactly."""
    hi = x.astype(jnp.bfloat16)
    rest = x - hi.astype(jnp.float32)
    mid = rest.astype(jnp.bfloat16)
    lo = (rest - mid.astype(jnp.float32)).astype(jnp.bfloat16)
    return hi, mid, lo


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
        hi, mid, lo = _split3(msg_ref[...])
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
    """Which reducer a planned sum traces to: the Mosaic kernel on a TPU,
    plain XLA elsewhere. The platform alone decides; a test reaches the
    interpreted kernel through the callers' `impl` argument."""
    return "pallas" if jax.default_backend() == "tpu" else "xla"


def _planned_sum(plan: EdgePlan, h, impl: str):
    # `gather` and `reduce`: the phases a device trace is summed by (docs/OBSERVABILITY.md), here and below
    with jax.named_scope("gather"):
        messages = h.astype(jnp.float32)[plan.neighbour]  # [L, W]; parked read row 0
    with jax.named_scope("reduce"):
        if impl == "xla":
            out = _planned_reduce_xla(plan, messages)
        else:
            out = _planned_reduce_pallas(plan, messages, impl == "pallas_interpret")
        return out[: min(h.shape[0], plan.degree.shape[0])].astype(h.dtype)  # a shard's plan owns fewer rows than its table holds


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
    """Sum of neighbour rows over both edge directions, [N, W] -> [N, W], from a prepared plan
    (a `ShardPlan`: of this device's rows, below). `impl` is for tests; counted in `route_stats()`."""
    with _route_lock:
        _route_counts["planned"] += 1
    if isinstance(plan, ShardPlan):
        return sharded_neighbor_sum(plan, h, impl)
    return _planned_sum_vjp(plan, h, impl or planned_impl())


# ---------------------------------------------------------------------------
# planned attention: a directed segment softmax and its weighted sum on the plan
# ---------------------------------------------------------------------------
#
# GAT's layer over both edge directions is ONE pass over the plan's entries:
# entry e = (owner i, neighbour j, direction d) scores
# `leaky_relu(s[j, d] + t[i, d])`, the softmax runs over the consecutive
# entries of one (owner, direction), and `out[i] = sum_e alpha_e * hw[j]`.
# On the TPU that is five walks of the plan's items, forward and backward,
# each a Mosaic kernel the device trace names:
#
#   planned_attention_max       z_e and the largest score of each run
#   planned_attention_softmax   p_e = exp(score_e - max) and each run's sum
#   planned_attention_sum       alpha_e = p_e / sum, out = sum alpha_e hw[j]
#   planned_attention_edge_dot  d alpha_e = <g[i], hw[j]>, and sum alpha d alpha
#   planned_attention_backward  the softmax's and leaky-relu's gradients, the
#                               sorted sums d s, d t, and the transposed
#                               weighted sum d hw[j] = sum alpha_e g[i]
#
# Inside a kernel a per-entry scalar lives in a ROW ([8, block], entries on
# lanes) and a per-node scalar of the tile in a row too ([8, tile]); the
# item's one-hot [tile, block] carries one into the other on the MXU (exact:
# the one-hot is exact in bfloat16, the value goes as three bfloat16 pieces).
# What belongs to the NEIGHBOUR travels with its gathered row: the message
# array is [entries, 128 lanes] whatever the width (PERF.md), so the lanes
# past the width hold the neighbour's scalars, and the kernel turns those
# columns into rows by transposing the block. So there is no 1-D gather, and
# no scatter.
#
# What a walk costs (PERF.md, PR 31) is its MXU passes and its grid steps,
# not its vector work, which hides behind them: a pass pays for the
# [128, 128] tiles it loads as the stationary operand however few rows stream
# against them, and a step pays about 0.1 ms a walk for every block it
# fetches or writes, whatever the block's size. So:
#
# - the per-entry scalars are ONE operand, the entries' state [8, entries]:
#   the plan's owner and direction (as bits) and a row for each of z, p,
#   alpha, d alpha. A walk reads a block of it and hands it on with its own
#   row added; `_backward` reads five rows in one fetch. The residual of the
#   forward pass is the state after `_sum` (z and alpha) and the float32
#   messages, once;
# - `_expand` sends the three pieces of a row table through the one-hot in
#   one pass, stacked; the neighbour's scalars come out of the float32 block
#   by a transposition, which the MXU took twelve tile loads for;
# - a WEIGHTED sum of gathered rows (`_weighted_sum`: `_sum`'s out and
#   `_backward`'s d hw here, three more in ops/sparse_gated.py) multiplies on
#   the VPU, one float32 multiplication an element as the oracle and the
#   plain references make it, and sends only the one-hot through the MXU,
#   against the exact split of the product: three passes. Until PR 34 the
#   weight went through the MXU too, as a split [tile, block] tile, in six
#   (a term was then kept to 2^-24 of the SUM; now it is rounded once to
#   float32, as the reference's is). The rows meet their weights transposed
#   (a lane an entry, so the [1, block] weight row broadcasts along sublanes):
#   `_backward` transposes its block anyway, `_sum` now does. The sums leave
#   their walks as they come out of `_reduce`, [lanes, nodes], and XLA
#   transposes them once: the tile transposed back in every item cost 0.2 ms
#   a walk, and XLA's glue around a [nodes, lanes] output 2 ms a layer more
#   (PERF.md, PR 34; the gated walks' too since PR 38: under the layout their
#   block has now, 0.34 ms a slot update less). The weight as a column
#   against untransposed rows cost more;
# - a per-entry DOT product (`_entry_dot`: `_edge_dot`'s <g[i], hw[j]>,
#   `_backward`'s <hw[i], g[j]>, the gated walks' two) is no [tile, block]
#   matrix product for the one-hot to pick from. An entry has ONE owner, so
#   the owner's row goes to its entries through the one-hot, exactly
#   (`_expand` at 128 rows: one product, 4 stationary tile loads and 1,536
#   streamed rows), and meets the gathered row on the VPU, one float32
#   multiplication an element and a float32 sum over the sublanes, as the
#   oracle and the plain references make it. Until PR 38 `_dot6` made all
#   128 x 512 products of an item in six passes (24 tile loads, 3,072 rows)
#   and threw 127 of every 128 away: 1.1 ms a slot update in `_edge_dot`
#   and 1.4 in `_backward`. Rows an item expands anyway ride in the same
#   product (`_backward`'s s, c and ones: 0.23 ms; the gated backward's
#   g_den measured nothing and stays apart). The owner's rows arrive as
#   `node_rows` [lanes, nodes]: transposed in the item on the XLU they cost
#   a walk 0.2 ms more AND XLA's part 0.8 ms a slot update (in STLGT 4.7:
#   one [nodes, 128] operand holds the whole block's [nodes, 64] arrays to
#   the layout that pads 64 floats to 128 lanes; PERF.md, PR 38);
# - `_reduce` keeps its three passes and their order: stacking those changes
#   the bits;
# - a float32 block is split where it is used, once an item. Splitting it
#   once a block or a tile into VMEM, or once a layer into HBM, measured
#   SLOWER on the v5e (the split hides behind the MXU; a conditional region
#   and 805 MB of pieces do not);
# - `_max`, whose step is shorter than the latency of its message block's
#   fetch, keeps a ring of three blocks in VMEM and fetches two ahead.
#
# The transposed sums (d hw[j] and d s[j, d] run over the entries whose
# NEIGHBOUR is j) need no permutation either: every entry has a mirror, the
# same edge seen from its other end, with owner and neighbour swapped and
# the other direction. The sum over "entries whose neighbour is j" is the
# sum over the mirrors of "entries whose owner is j", and a mirror's alpha
# is recomputed where it is needed, bit for bit, from what the walk has at
# hand: s of the owner, and t, max and sum of the neighbour (in the lanes of
# its gathered row).

ATT_ROWS = 8  # sublanes of a row table: at most eight scalars per entry or node
ATT_SPARE = 8  # lanes kept past the width for the neighbour's scalars
#: rows of the entries' state; a walk adds one to zeros, and the plan's owner
#: and direction ride below them as bits
ROW_Z, ROW_P, ROW_ALPHA, ROW_DALPHA, ROW_OWNER, ROW_DIR = 0, 1, 2, 3, 6, 7
MSG_RING = 3  # message blocks `_max` holds in VMEM: the item's, and two on their way
_NEG = -1e30  # below every score; an empty run's maximum
_NN = (((1,), (0,)), ((), ()))  # [m, k] @ [k, n]
_NT = (((1,), (1,)), ((), ()))  # [m, k] @ [n, k]^T


class _MxuCalls(threading.local):
    n = 0  # `_mxu` calls this thread has traced: `_walk_call` reads it around a kernel


_mxu_calls = _MxuCalls()


def _mxu(a, b, dims):
    _mxu_calls.n += 1
    return jax.lax.dot_general(a, b, dims, preferred_element_type=jnp.float32)


def _expand(node_rows, hot):
    """[R, tile] node rows -> [R, block] entry rows by owner (0 for an entry
    no row of the tile owns). ONE pass of the one-hot through the MXU, the
    three pieces of the rows below one another, where a pass a piece loads the
    same [tile, block] one-hot three times: an entry has one owner, so each
    result is one piece itself and the sum is the three separate passes' to
    the bit."""
    r = node_rows.shape[0]
    stacked = jnp.concatenate([p.astype(jnp.float32) for p in _split3(node_rows)], axis=0)
    x = _mxu(stacked.astype(jnp.bfloat16), hot, _NN)  # [3R, block]
    return sum((x[2 * r : 3 * r], x[r : 2 * r], x[0:r]))


def _reduce(entry_rows, hot):
    """[R, block] entry rows -> [R, tile]: each node's sum over its entries."""
    return sum(_mxu(piece, hot, _NT) for piece in reversed(_split3(entry_rows)))


def _neighbour_rows(msg_ref, first: int):
    """Columns first .. first + 7 of a float32 [block, lanes] message block as
    [8, block] rows: the neighbour's scalars, to the bit, by a transposition."""
    return msg_ref[...].T[first : first + ATT_ROWS, :]


def _rows(*vectors):
    """[1, n] rows stacked into an [ATT_ROWS, n] table, zeros below."""
    n = vectors[0].shape[1]
    rid = jax.lax.broadcasted_iota(jnp.int32, (ATT_ROWS, n), 0)
    out = jnp.zeros((ATT_ROWS, n), jnp.float32)
    for k, v in enumerate(vectors):
        out = jnp.where(rid == k, v, out)
    return out


def _by_direction(d, x0, x1):
    return jnp.where(d == 0, x0, x1)


def _leaky(z, leak):
    return jnp.where(z >= 0, z, leak * z)


def _rows_by_direction(d, x):
    """A per-entry row as two, one per direction: what `_reduce` turns into
    each node's sum over its out-entries and over its in-entries."""
    return _rows(jnp.where(d == 0, x, 0.0), jnp.where(d == 1, x, 0.0))


def _weighted_sum(rows_t, weight, hot):
    """[R, block] gathered rows, transposed (a lane an entry), and their
    [1, block] weights -> [R, tile]: each node's sum of `weight_e * row_e`
    over its entries. The product is one float32 multiplication an element on
    the VPU, as `_attention_xla` and the plain references make it; only the
    one-hot, exact in bfloat16, goes through the MXU, against the exact split
    of the product: `_reduce`'s three passes, where a split [tile, block]
    weight tile against the split rows spent six (until PR 34)."""
    return _reduce(rows_t * weight, hot)


def _entry_dot(own_rows, entry_rows_t):
    """[R, block] rows of each entry's OWNER, as `_expand` carries them through
    the one-hot (exactly: an entry has one owner), and the [R, block] rows of
    the entries themselves, transposed (a lane an entry) -> [1, block]: each
    entry's dot product, +0.0 where no row of the tile owns it. One float32
    multiplication an element on the VPU and a float32 sum over the sublanes,
    as `_attention_xla`, `_gated_xla` and the plain references make it: by
    halves down to a vreg's eight sublanes, a pairwise sum in an order the
    source fixes. Until PR 38 a split [tile, R] tile against the split rows
    made every owner's product with every entry in six passes, and the one-hot
    picked one in 128."""
    terms = own_rows * entry_rows_t
    while terms.shape[0] % (2 * ATT_ROWS) == 0:
        terms = terms[: terms.shape[0] // 2] + terms[terms.shape[0] // 2 :]
    return jnp.sum(terms, axis=0, keepdims=True)


def plan_blocks(plan: EdgePlan, items: int) -> int:
    """Edge blocks the `items` real items of a host plan visit: the message
    blocks a walk fetches, where its grid steps are the items. Their blocks
    start at 0 and rise by at most one (a tile starts in the block the tile
    before it ended in, or in the next), so the last one says how many."""
    return int(np.asarray(plan.item_block)[items - 1]) + 1 if items else 0


def _new_block(block_ref):
    """Whether this item is the first of its edge block."""
    i = pl.program_id(0)
    return (i == 0) | (block_ref[i] != block_ref[jnp.maximum(i - 1, 0)])


def _message_block(block_ref, msg_hbm, ring, sems):
    """The item's [block, lanes] message block, from a ring in VMEM that the
    walk fills itself, `MSG_RING - 1` blocks ahead of the one in use: the
    pipeline's one block ahead leaves a short step waiting on the fetch. It
    leans on the plan: the real items' blocks start at 0 and rise by at most
    one, and the no-ops after them repeat the last."""
    i = pl.program_id(0)
    b = block_ref[i]
    n_blocks = block_ref[pl.num_programs(0) - 1] + 1  # the no-ops repeat the last real block
    be = ring.shape[1]

    def copy(k):
        slot = k % MSG_RING
        return pltpu.make_async_copy(
            msg_hbm.at[pl.ds(pl.multiple_of(k * be, be), be), :], ring.at[slot], sems.at[slot]
        )

    @pl.when(i == 0)
    def _first():
        for k in range(MSG_RING - 1):
            @pl.when(k < n_blocks)
            def _start():
                copy(k).start()

    @pl.when(_new_block(block_ref))
    def _fetch():
        @pl.when(b + MSG_RING - 1 < n_blocks)
        def _ahead():
            copy(b + MSG_RING - 1).start()

        copy(b).wait()

    return ring.at[b % MSG_RING]


def _row(state_ref, row: int):
    return state_ref[row : row + 1, :]


def _add_row(next_ref, row: int, x):
    """A walk's own row of the state: the sum over the block's items, each of
    which holds the entries of its tile and zeros elsewhere."""
    next_ref[row : row + 1, :] += x


def _item(tile_ref, block_ref, flag_ref, state_ref, next_ref=None, by_tile=(), fill=0.0):
    """What every walk starts from. The entries' state is ONE [ATT_ROWS, L]
    operand that a walk reads a block of and hands on with its own row added
    (`next_ref`: copied on the first visit of the edge block, so that the row
    the walk accumulates starts at zero and the others travel on); the plan's
    owner and direction ride in it as bits. Fills the per-node outputs
    `by_tile` on the first item of their tile. Returns (whether the item is
    real and not padding, its one-hot [tile, block] as a mask, the entries'
    direction [1, block])."""
    i = pl.program_id(0)
    flag = flag_ref[i]
    tn, be = PLAN_NODE_TILE, state_ref.shape[1]
    as_int = partial(jax.lax.bitcast_convert_type, new_dtype=jnp.int32)
    row_in_tile = as_int(_row(state_ref, ROW_OWNER)) - tile_ref[i] * tn  # [1, block]
    one_hot = row_in_tile == jax.lax.broadcasted_iota(jnp.int32, (tn, be), 0)

    if next_ref is not None:
        @pl.when(_new_block(block_ref))
        def _hand_on():
            next_ref[...] = state_ref[...]

    @pl.when(flag == 1)
    def _new_tile():
        for ref in by_tile:
            ref[...] = jnp.full_like(ref, fill)

    return flag >= 0, one_hot, as_int(_row(state_ref, ROW_DIR))


def _attention_max_kernel(
    tile_ref, block_ref, flag_ref, state_ref, msg_ref, trow_ref,
    next_ref, top_ref, ring, sems, *, width: int, leak: float,
):
    msg_ref = _message_block(block_ref, msg_ref, ring, sems)
    real, one_hot, d = _item(tile_ref, block_ref, flag_ref, state_ref, next_ref, (top_ref,), _NEG)

    @pl.when(real)
    def _walk():
        hot = one_hot.astype(jnp.bfloat16)
        nbr = _neighbour_rows(msg_ref, width)  # s of the neighbour
        own = _expand(trow_ref[...], hot)  # t of the owner, and a row of ones
        inside = own[2:3] > 0.5
        z = _by_direction(d, nbr[0:1] + own[0:1], nbr[1:2] + own[1:2])
        _add_row(next_ref, ROW_Z, jnp.where(inside, z, 0.0))
        score = _leaky(z, leak)
        tops = []
        for k in (0, 1):
            mine = jnp.where(inside & (d == k), score, _NEG)
            tops.append(jnp.max(jnp.where(one_hot, mine, _NEG), axis=1, keepdims=True))
        lane = jax.lax.broadcasted_iota(jnp.int32, top_ref.shape, 1)
        top_ref[...] = jnp.maximum(top_ref[...], jnp.where(lane == 0, tops[0], tops[1]))


def _attention_softmax_kernel(
    tile_ref, block_ref, flag_ref, state_ref, mrow_ref,
    next_ref, total_ref, *, leak: float,
):
    real, one_hot, d = _item(tile_ref, block_ref, flag_ref, state_ref, next_ref, (total_ref,))

    @pl.when(real)
    def _walk():
        hot = one_hot.astype(jnp.bfloat16)
        own = _expand(mrow_ref[...], hot)  # the run's maximum, and a row of ones
        inside = own[2:3] > 0.5
        shift = _by_direction(d, own[0:1], own[1:2])
        delta = jnp.clip(_leaky(_row(state_ref, ROW_Z), leak) - shift, -60.0, 0.0)
        p = jnp.where(inside, jnp.exp(delta), 0.0)
        _add_row(next_ref, ROW_P, p)
        total_ref[...] += _reduce(_rows_by_direction(d, p), hot)


def _attention_sum_kernel(
    tile_ref, block_ref, flag_ref, state_ref, lrow_ref, msg_ref,
    next_ref, out_ref,
):
    real, one_hot, d = _item(tile_ref, block_ref, flag_ref, state_ref, next_ref, (out_ref,))

    @pl.when(real)
    def _walk():
        hot = one_hot.astype(jnp.bfloat16)
        own = _expand(lrow_ref[...], hot)  # the run's sum, and a row of ones
        inside = own[2:3] > 0.5
        total = jnp.maximum(_by_direction(d, own[0:1], own[1:2]), 1e-30)
        alpha = jnp.where(inside, _row(state_ref, ROW_P) / total, 0.0)
        _add_row(next_ref, ROW_ALPHA, alpha)
        out_ref[...] += _weighted_sum(msg_ref[...].T, alpha, hot)


def _attention_edge_dot_kernel(
    tile_ref, block_ref, flag_ref, state_ref, msg_ref, grow_ref,
    next_ref, c_ref,
):
    real, one_hot, d = _item(tile_ref, block_ref, flag_ref, state_ref, next_ref, (c_ref,))

    @pl.when(real)
    def _walk():
        hot = one_hot.astype(jnp.bfloat16)
        g, msg_t = grow_ref[...], msg_ref[...].T  # g of the tile's nodes; hw of the neighbours, a lane an entry
        dalpha = _entry_dot(_expand(g, hot), msg_t)  # <g[owner], hw[neighbour]>
        _add_row(next_ref, ROW_DALPHA, dalpha)
        y = _row(state_ref, ROW_ALPHA) * dalpha  # 0 for an entry of another tile
        c_ref[...] += _reduce(_rows_by_direction(d, y), hot)


def _attention_backward_kernel(
    tile_ref, block_ref, flag_ref, state_ref,
    msg_ref, hwrow_ref, nrow_ref, dhw_ref, dst_ref, *, width: int, leak: float,
):
    real, one_hot, d = _item(tile_ref, block_ref, flag_ref, state_ref, None, (dhw_ref, dst_ref))

    @pl.when(real)
    def _walk():
        hot = one_hot.astype(jnp.bfloat16)
        msg_t = msg_ref[...].T  # g of the neighbour, then its t, max, sum, c
        nbr = msg_t[width : width + ATT_ROWS, :]
        # hw of the owner and, in the same product, its s and c and a row of ones
        lanes = hwrow_ref.shape[0]
        own = _expand(jnp.concatenate([hwrow_ref[...], nrow_ref[...]], axis=0), hot)
        dalpha_m, own = _entry_dot(own[:lanes], msg_t), own[lanes:]  # <hw[owner], g[neighbour]>
        inside = own[4:5] > 0.5
        # this entry's own softmax: d z = alpha (d alpha - c) leaky'(z)
        z = _row(state_ref, ROW_Z)
        c = _by_direction(d, own[2:3], own[3:4])
        alpha, dalpha = _row(state_ref, ROW_ALPHA), _row(state_ref, ROW_DALPHA)
        dz = jnp.where(inside, alpha * (dalpha - c) * jnp.where(z >= 0, 1.0, leak), 0.0)
        # its mirror's, in the other direction: s of the owner, the rest the
        # neighbour's (rows t0 t1 max0 max1 sum0 sum1 c0 c1)
        zm = _by_direction(d, own[1:2] + nbr[1:2], own[0:1] + nbr[0:1])
        shift = _by_direction(d, nbr[3:4], nbr[2:3])
        total = jnp.maximum(_by_direction(d, nbr[5:6], nbr[4:5]), 1e-30)
        cm = _by_direction(d, nbr[7:8], nbr[6:7])
        pm = jnp.exp(jnp.clip(_leaky(zm, leak) - shift, -60.0, 0.0))
        alpha_m = jnp.where(inside, pm / total, 0.0)
        dzm = alpha_m * (dalpha_m - cm) * jnp.where(zm >= 0, 1.0, leak)
        # the mirror's direction is 1 - d: its d s lands in the other row
        dst_ref[...] += _reduce(
            _rows(
                jnp.where(d == 1, dzm, 0.0), jnp.where(d == 0, dzm, 0.0),
                jnp.where(d == 0, dz, 0.0), jnp.where(d == 1, dz, 0.0),
            ),
            hot,
        )
        dhw_ref[...] += _weighted_sum(msg_t, alpha_m, hot)


def _walk_call(plan: EdgePlan, kernel, name: str, inputs, outputs, interpret: bool):
    """One walk of the plan's items. `inputs` and `outputs` are (kind, array
    or lane width) pairs; the kind says how a block follows the item: "entry"
    [R, L] by edge block, "message" [L, lanes] by edge block, "node_rows"
    [R, nodes] by tile, "node" [nodes, lanes] by tile. A "message_ring" input
    stays in HBM whole: the kernel fetches its blocks itself
    (`_message_block`), into a ring and with the semaphores it is handed
    after its outputs."""
    tn, be = PLAN_NODE_TILE, PLAN_EDGE_BLOCK
    entries, nodes = plan.owner.shape[1], _node_tiles(plan) * tn

    def spec(kind, lanes):
        if kind == "entry":
            return pl.BlockSpec((lanes, be), lambda i, tile, block, flag: (0, block[i]))
        if kind == "message":
            return pl.BlockSpec((be, lanes), lambda i, tile, block, flag: (block[i], 0))
        if kind == "message_ring":
            return pl.BlockSpec(memory_space=pl.ANY)
        if kind == "node_rows":
            return pl.BlockSpec((lanes, tn), lambda i, tile, block, flag: (0, tile[i]))
        return pl.BlockSpec((tn, lanes), lambda i, tile, block, flag: (tile[i], 0))

    def shape(kind, lanes):
        if kind == "entry":
            return (lanes, entries)
        if kind == "node_rows":
            return (lanes, nodes)
        return (nodes, lanes)

    def lanes_of(kind, a):
        return a.shape[0] if kind in ("entry", "node_rows") else a.shape[1]

    def counted(*refs):
        before = _mxu_calls.n
        kernel(*refs)
        with _route_lock:  # a new dict: `route_stats()` hands out the old one
            _route_counts["mxu_products"] = {**_route_counts["mxu_products"], name: _mxu_calls.n - before}

    walk = pl.pallas_call(
        counted,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(plan.item_tile.shape[0],),
            in_specs=[spec(kind, lanes_of(kind, a)) for kind, a in inputs],
            out_specs=[spec(kind, lanes) for kind, lanes in outputs],
            scratch_shapes=[
                scratch
                for kind, a in inputs
                if kind == "message_ring"
                for scratch in (
                    pltpu.VMEM((MSG_RING, be, a.shape[1]), jnp.float32),
                    pltpu.SemaphoreType.DMA((MSG_RING,)),
                )
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct(shape(kind, lanes), jnp.float32)
            for kind, lanes in outputs
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",), vmem_limit_bytes=64 << 20
        ),
        name=name,
        interpret=interpret,
    )
    with jax.named_scope("reduce"):
        return walk(plan.item_tile, plan.item_block, plan.item_flag, *(a for _kind, a in inputs))


def _node_table(nodes: int, lanes: int, *parts):
    """[n, w] parts side by side, padded to [nodes, lanes] with zeros."""
    table = jnp.concatenate([p.astype(jnp.float32) for p in parts], axis=1)
    return jnp.pad(table, ((0, nodes - table.shape[0]), (0, lanes - table.shape[1])))


def _gather_rows(table, neighbour):
    """`table[neighbour]` at the table's full lane width. Behind a barrier:
    XLA otherwise gathers the columns that are not padding and pads the
    [entries, lanes] result in a pass of its own, which costs more than the
    gather saves (a gathered row fills its 128 lanes in memory either way)."""
    with jax.named_scope("gather"):
        return jax.lax.optimization_barrier(table)[neighbour]


def _node_rows(nodes: int, *columns):
    """[n] columns as the rows of an [ATT_ROWS, nodes] table; after them a
    row of ones, which a walk expands into "some row of this tile owns the
    entry"; zeros below and past n. From columns, also where a walk has just
    made the values as rows: stacked from slices of a kernel's row output
    the table cost the next walk 0.4 ms more on the v5e (PERF.md, PR 28)."""
    rows = [jnp.pad(c.astype(jnp.float32), (0, nodes - c.shape[0])) for c in columns]
    rows.append(jnp.ones(nodes, jnp.float32))
    rows += [jnp.zeros(nodes, jnp.float32)] * (ATT_ROWS - len(rows))
    return jnp.stack(rows)


def _entry_state(plan: EdgePlan):
    """The state of the entries before the first walk, [ATT_ROWS, L]: the
    plan's owner and direction as bits in its last two rows, zeros above."""
    bits = jax.lax.bitcast_convert_type(
        jnp.concatenate([plan.owner, plan.direction], axis=0), jnp.float32
    )
    return jnp.pad(bits, ((ATT_ROWS - 2, 0), (0, 0)))


def _attention_shapes(plan: EdgePlan, hw):
    nodes = _node_tiles(plan) * PLAN_NODE_TILE
    return nodes, _pad_to(hw.shape[1] + ATT_SPARE, 128)


def _attention_pallas_fwd(plan: EdgePlan, hw, s, t, leak: float, interpret: bool):
    n, width = hw.shape
    nodes, lanes = _attention_shapes(plan, hw)
    msg = _gather_rows(_node_table(nodes, lanes, hw, s), plan.neighbour)  # [L, lanes]
    state, top = _walk_call(
        plan, partial(_attention_max_kernel, width=width, leak=leak),
        "planned_attention_max",
        [
            ("entry", _entry_state(plan)), ("message_ring", msg),
            ("node_rows", _node_rows(nodes, t[:, 0], t[:, 1])),
        ],
        [("entry", ATT_ROWS), ("node", 2)], interpret,
    )
    top = jnp.where(top > _NEG / 2, top, 0.0)[:n]  # an empty run shifts by 0
    state, total = _walk_call(
        plan, partial(_attention_softmax_kernel, leak=leak),
        "planned_attention_softmax",
        [("entry", state), ("node_rows", _node_rows(nodes, top[:, 0], top[:, 1]))],
        [("entry", ATT_ROWS), ("node_rows", ATT_ROWS)], interpret,
    )
    total = total[:2, :n].T  # [n, 2]
    state, out = _walk_call(
        plan, _attention_sum_kernel, "planned_attention_sum",
        [
            ("entry", state),
            ("node_rows", _node_rows(nodes, total[:, 0], total[:, 1])),
            ("message", msg),
        ],
        [("entry", ATT_ROWS), ("node_rows", lanes)], interpret,
    )
    saved = (hw, s, t, msg, state, top, total)
    return out[:width, :n].T.astype(hw.dtype), saved


def _attention_pallas_bwd(plan: EdgePlan, leak: float, interpret: bool, saved, g):
    hw, s, t, msg, state, top, total = saved
    n, width = hw.shape
    nodes, lanes = _attention_shapes(plan, hw)
    g = g.astype(jnp.float32)
    state, c = _walk_call(
        plan, _attention_edge_dot_kernel, "planned_attention_edge_dot",
        [("entry", state), ("message", msg), ("node_rows", _node_table(nodes, lanes, g).T)],
        [("entry", ATT_ROWS), ("node_rows", ATT_ROWS)], interpret,
    )
    c = c[:2, :n].T  # [n, 2]: the sum of alpha * d alpha over each run
    g_msg = _gather_rows(_node_table(nodes, lanes, g, t, top, total, c), plan.neighbour)
    dhw, dst = _walk_call(
        plan, partial(_attention_backward_kernel, width=width, leak=leak),
        "planned_attention_backward",
        [
            ("entry", state), ("message", g_msg),
            ("node_rows", _node_table(nodes, lanes, hw).T),
            ("node_rows", _node_rows(nodes, s[:, 0], s[:, 1], c[:, 0], c[:, 1])),
        ],
        [("node_rows", lanes), ("node_rows", ATT_ROWS)], interpret,
    )
    dst = dst[:4, :n].T
    return (
        dhw[:width, :n].T.astype(hw.dtype),
        dst[:, 0:2].astype(s.dtype),
        dst[:, 2:4].astype(t.dtype),
    )


@partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def _attention_pallas(plan: EdgePlan, hw, s, t, leak: float, interpret: bool):
    return _attention_pallas_fwd(plan, hw, s, t, leak, interpret)[0]


def _attention_pallas_fwd_rule(plan, hw, s, t, leak, interpret):
    out, saved = _attention_pallas_fwd(plan, hw, s, t, leak, interpret)
    return out, (plan, saved)


def _attention_pallas_bwd_rule(leak, interpret, saved, g):
    plan, rest = saved
    return (None, *_attention_pallas_bwd(plan, leak, interpret, rest, g))


_attention_pallas.defvjp(_attention_pallas_fwd_rule, _attention_pallas_bwd_rule)


def _attention_xla(plan: EdgePlan, hw, s, t, leak: float):
    """The same mathematics in plain XLA, as sorted segment reductions over
    the plan's (owner, direction) runs, differentiated by JAX: the path off
    the TPU, and the oracle of the kernels."""
    n = hw.shape[0]
    nodes = _node_tiles(plan) * PLAN_NODE_TILE
    owner, nbr, d = plan.owner[0], plan.neighbour, plan.direction[0]
    real = owner < nodes
    own = jnp.minimum(owner, n - 1)
    score = _leaky(s[nbr, d] + t[own, d], leak)
    run = jnp.where(real, owner * 2 + d, 2 * nodes)  # ascending
    seg = partial(jax.ops.segment_sum, num_segments=2 * nodes + 1, indices_are_sorted=True)
    neg = jnp.finfo(score.dtype).min
    top = jax.ops.segment_max(
        jnp.where(real, score, neg), run, num_segments=2 * nodes + 1,
        indices_are_sorted=True,
    )
    top = jnp.where(top > neg / 2, top, 0.0)
    p = jnp.where(real, jnp.exp(jnp.clip(score - top[run], -60.0, 0.0)), 0.0)
    alpha = p / jnp.maximum(seg(p, run)[run], 1e-30)
    with jax.named_scope("reduce"):
        weights = alpha[:, None]
        with jax.named_scope("gather"):
            rows = hw[nbr]
        out = jax.ops.segment_sum(
            weights * rows, jnp.where(real, owner, nodes),
            num_segments=nodes + 1, indices_are_sorted=True,
        )
        return out[:n]


def planned_attention(
    plan: EdgePlan, hw, s, t, leak: float = 0.2, impl: Optional[str] = None
):
    """GAT's aggregation over both edge directions from a prepared plan,
    `[N, W] -> [N, W]`: `out[i] = sum alpha_e * hw[j]` over the entries
    (i, j, d) of i, `alpha` the softmax of `leaky_relu(s[j, d] + t[i, d])`
    over the entries of one (i, d). `s` and `t` are `[N, 2]`, a column per
    direction (0: edges out of the owner, 1: edges into it). Empty runs and
    the exponent are treated as `gat._segment_softmax` treats them. `impl`
    as for `planned_neighbor_sum`. Counted in `route_stats()["planned"]`, as
    every reduction over a plan is, and in `["attention"]` (trace time)."""
    with _route_lock:
        _route_counts["planned"] += 1
        _route_counts["attention"] += 1
    impl = impl or planned_impl()
    if impl == "xla":
        return _attention_xla(plan, hw, s, t, leak)
    return _attention_pallas(plan, hw, s, t, leak, impl == "pallas_interpret")


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


# ---------------------------------------------------------------------------
# the planned neighbour sum over a history sharded by nodes
# ---------------------------------------------------------------------------
#
# Where one device cannot hold a history (models/stacked.py decides), the
# node axis is cut into `shards` ranges of rows, one a device of a mesh. A
# device holds the rows of its range of every slot and the entries whose
# OWNER it holds, cut once more by the SOURCE: the shard their neighbour's
# row lives on. That is `shards` sub-plans of one shape, each the plan of the
# device's rows over ONE source's table: the owner a row of the shard, the
# neighbour a row of the source's `[rows, W]` table. A layer's sum is then an
# all-gather of the devices' rows (float32, as they are stored: what crosses
# between chips is the configuration's precision) into `[shards, rows, W]`,
# and source by source a row gather from that source's table, the planned
# reduction of its entries, and the parts added. A is symmetric, so the
# cotangent of the local rows is the same thing of the cotangent:
# all-gathered, summed over the SAME sub-plans. No scatter, no
# reduce-scatter, and nothing but the tables crosses.
#
# Why a source at a time (PERF.md, PR 33 and PR 36): a row gather costs
# 1.8 ns a row while its TABLE lies in the chip's fast memory (128 MiB) and
# 10 ns from HBM. One source's table at 128 lanes is 64 MiB at the cells'
# size; the whole mesh's is 256 MiB. XLA keeps a table there (`S(1)` in the
# compiled layout) only while one is alive at a time, so the sources are
# chained by `optimization_barrier`: a source's gather is done before the
# next source's table is cut out of the all-gathered array. Every entry is
# still summed once, in float32, through the same reducer; an owner's sum
# comes out as `shards` partial sums added, an order and not a precision.
# A head that reduces over ALL of an owner's entries with a softmax or a
# gate would meet them in `shards` pieces and has to merge a running maximum
# and sum across the sources (ROADMAP R2).
#
# The ranges are cut where the plan's ENTRIES divide evenly, not the nodes: a
# walk's time goes with its entries, every device waits for the slowest at
# each all-gather, and endpoints' degrees are heavy-tailed. A range holds at
# most `bucket_nodes // shards` nodes, its rows start at a multiple of that,
# and the rows past its nodes are padding that owns nothing.


@jax.tree_util.register_pytree_node_class
class ShardPlan:
    """One device's sub-plans inside a `shard_map` over `axis`: what
    `planned_neighbor_sum` takes where the rows it is given are the device's
    share of the nodes. `plan` is an EdgePlan whose leaves, but for the owner's
    degree and its mean, have a leading axis over the sources (`build_shard_plans`). The
    axis is static; the plan's arrays are the leaves."""

    def __init__(self, plan: EdgePlan, axis: str):
        self.plan, self.axis = plan, axis

    @property
    def degree(self):
        return self.plan.degree

    def tree_flatten(self):
        return (self.plan,), self.axis

    @classmethod
    def tree_unflatten(cls, axis, children):
        return cls(children[0], axis)


def _source_plan(plan: EdgePlan, source: int) -> EdgePlan:
    """One source's sub-plan of a device's plan: every leaf but the owner's two has
    a leading `[shards]` axis over the shard its entries' NEIGHBOURS live on."""
    whole = ("degree", "mean_log_degree")  # the owner's, whatever the source
    return EdgePlan(*(a if name in whole else a[source] for name, a in zip(EdgePlan._fields, plan)))


def _sharded_sum(plan: EdgePlan, h, impl: str, axis: str):
    with jax.named_scope("collective"):
        tables = jax.lax.all_gather(h, axis, axis=0, tiled=False)  # [shards, n, W], rows as stored
    out = None
    for source in range(plan.neighbour.shape[0]):
        mine = _source_plan(plan, source)
        with jax.named_scope("collective"):
            table = tables[source]  # the slice out of the gathered array
        part = _planned_sum(mine, table, impl)  # gathers from ONE shard's table
        # the chain: a source is done with its table before the next source's is cut out of `tables`
        part, tables = jax.lax.optimization_barrier((part, tables))
        with jax.named_scope("reduce"):
            out = part if out is None else out + part
    return out  # [n, W]: the rows this device owns


@partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def _sharded_sum_vjp(plan: EdgePlan, h, impl: str, axis: str):
    return _sharded_sum(plan, h, impl, axis)


def _sharded_sum_fwd(plan, h, impl, axis):
    return _sharded_sum(plan, h, impl, axis), plan


def _sharded_sum_bwd(impl, axis, plan, g):
    # A is symmetric: the rows of A @ G that this device owns, G all-gathered
    return None, _sharded_sum(plan, g, impl, axis)


_sharded_sum_vjp.defvjp(_sharded_sum_fwd, _sharded_sum_bwd)


def sharded_neighbor_sum(plan: ShardPlan, h: jnp.ndarray, impl: Optional[str] = None):
    """`planned_neighbor_sum` of a node-sharded table, inside the `shard_map`
    over `plan.axis`: `h` is this device's rows `[n, W]`, the result the sums
    of their neighbours' rows wherever those live. One all-gather, and a row
    gather and a planned reduction a source, forward and backward. Counted
    in `route_stats()["sharded"]`."""
    with _route_lock:
        _route_counts["sharded"] += 1
    return _sharded_sum_vjp(plan.plan, h, impl or planned_impl(), plan.axis)


def shard_cuts(degree: np.ndarray, shards: int, rows: int) -> np.ndarray:
    """`[shards + 1]` node indices that cut `len(degree)` nodes into ranges
    of even total degree, none longer than `rows`. A node's entries all go
    with it, so the ranges' entries are even to within one node's degree."""
    n = int(degree.shape[0])
    if n > shards * rows:
        raise ValueError(f"{n} nodes do not fit {shards} shards of {rows} rows")
    reach = np.cumsum(degree, dtype=np.int64)
    total = int(reach[-1]) if n else 0
    cuts = np.zeros(shards + 1, dtype=np.int64)
    cuts[shards] = n
    for d in range(1, shards):
        even = int(np.searchsorted(reach, total * d / shards, side="right")) if total else n * d // shards
        # no range longer than its rows, and the nodes left must fit the ranges left
        cuts[d] = np.clip(even, max(cuts[d - 1], n - (shards - d) * rows), min(cuts[d - 1] + rows, n))
    return cuts


def _work_list(counts: np.ndarray, entries: int):
    """The items of one plan from its owners' entry counts (`build_edge_plan`
    says what an item is): (item_tile, item_block, item_flag, real items)."""
    tn, be = PLAN_NODE_TILE, PLAN_EDGE_BLOCK
    node_tiles, edge_blocks = -(-counts.shape[0] // tn), entries // be
    row_ptr = np.zeros(node_tiles * tn + 1, dtype=np.int64)
    np.cumsum(counts, out=row_ptr[1 : counts.shape[0] + 1])
    row_ptr[counts.shape[0] + 1 :] = row_ptr[counts.shape[0]]
    lo, hi = row_ptr[:-1:tn], row_ptr[tn::tn]
    first = np.minimum(lo // be, edge_blocks - 1)
    last = np.where(hi > lo, (hi - 1) // be, first)
    per_tile = last - first + 1
    n_items = int(per_tile.sum())
    starts = np.cumsum(per_tile) - per_tile
    items = node_tiles + edge_blocks
    item_tile = np.full(items, node_tiles - 1, dtype=np.int32)
    item_block = np.empty(items, dtype=np.int32)
    item_flag = np.full(items, -1, dtype=np.int32)
    tiles = np.repeat(np.arange(node_tiles), per_tile)
    item_tile[:n_items] = tiles
    item_block[:n_items] = first[tiles] + np.arange(n_items) - starts[tiles]
    item_block[n_items:] = item_block[n_items - 1]
    item_flag[:n_items] = 0
    item_flag[starts] = 1
    return item_tile, item_block, item_flag, n_items


def sub_plans(plans: EdgePlan):
    """The sub-plans of `build_shard_plans`' host plans, in the order of its
    counts: one a shard, or one an (owner, source) pair, owner-major."""
    shards = plans.neighbour.shape[0]
    for d in range(shards):
        one = jax.tree_util.tree_map(lambda a: a[d], plans)
        yield from [one] if shards == 1 else (_source_plan(one, s) for s in range(shards))


def build_shard_plans(src, dst, edge_mask, num_nodes: int, bucket_nodes: int, shards: int):
    """Host arrays of a (bucket-padded) edge list over `num_nodes` nodes ->
    (EdgePlan of numpy arrays with a leading `[shards]` axis, the node cuts
    `[shards + 1]`, the real entries and the real items of each sub-plan).

    Node i of range d lives in row `d * (bucket_nodes // shards) + i - cuts[d]`
    of the table. With one shard the one plan IS `build_edge_plan`'s (it makes
    the sort), and the counts are one number each. With more, shard d's
    entries (owner in its range) are cut once more by the SOURCE, the shard
    their neighbour lives on: `shards` sub-plans on a second axis, leaves
    `[shards (owner's), shards (source), ...]`, each the plan of the owner's
    rows over ONE source's `[bucket_nodes // shards, W]` table: owner a row
    of the shard, neighbour a row of the source's table, sorted within the
    source as `build_edge_plan` sorts, its own work list. `degree` stays the
    owner's whole degree, `[shards, rows]` (a mean divides by it). The counts
    are flat, owner-major: `[d * shards + s]`. All sub-plans have one shape:
    that of the plan of `bucket_nodes // shards` nodes and `edge bucket //
    shards // shards` edges while the fullest (owner, source) pair fits it,
    which even cuts see to (degrees are symmetric, so the sources' shares of
    a shard's entries are even too), and wider by eighths of it where a hub
    does not let them."""
    src = np.asarray(src, dtype=np.int64)
    dst = np.asarray(dst, dtype=np.int64)
    n, nb = int(num_nodes), int(bucket_nodes)
    rows = nb // shards
    real = np.asarray(edge_mask, dtype=bool) & (src >= 0) & (src < n) & (dst >= 0) & (dst < n)
    degree = np.bincount(np.concatenate([src[real], dst[real]]), minlength=n)[:n]
    cuts = shard_cuts(degree, shards, rows)
    shard_of = np.searchsorted(cuts, np.arange(n), side="right") - 1
    row_of = np.concatenate([shard_of * rows + np.arange(n) - cuts[shard_of], np.full(1, nb)])
    at = lambda ends: row_of[np.where((ends >= 0) & (ends < n), ends, n)]  # noqa: E731 - out of range: out of the bucket
    whole, n_real, n_items = build_edge_plan(at(src), at(dst), edge_mask, nb)
    if shards == 1:
        return jax.tree_util.tree_map(lambda a: a[None], whole), cuts, [n_real], [n_items]

    owner, neighbour = whole.owner[0, :n_real], whole.neighbour[:n_real]
    pair = owner // rows * shards + neighbour // rows
    order = np.argsort(pair, kind="stable")  # within a pair: by owner, then direction, as `whole` is
    bounds = np.searchsorted(pair[order], np.arange(shards * shards + 1))
    held = np.diff(bounds)
    base, _tiles, _items = plan_shapes(rows, src.shape[0] // shards // shards)
    entries = max(base, _pad_to(int(held.max()), max(base // 8, PLAN_EDGE_BLOCK)))
    parked = -(-rows // PLAN_NODE_TILE) * PLAN_NODE_TILE
    subs, items = [], []
    for i in range(shards * shards):
        d, s = divmod(i, shards)
        mine = order[bounds[i] : bounds[i + 1]]
        own = np.full(entries, parked, dtype=np.int32)
        nei = np.zeros(entries, dtype=np.int32)
        direction = np.zeros(entries, dtype=np.int32)
        own[: mine.size] = owner[mine] - d * rows
        nei[: mine.size] = neighbour[mine] - s * rows
        direction[: mine.size] = whole.direction[0, mine]
        *work, n_items = _work_list(np.bincount(own[: mine.size], minlength=rows), entries)
        subs.append((own[None, :], nei, *work, direction[None, :]))
        items.append(n_items)
    own, nei, item_tile, item_block, item_flag, direction = (
        np.stack(a).reshape(shards, shards, *a[0].shape) for a in zip(*subs)
    )
    plans = EdgePlan(
        own, nei, whole.degree.reshape(shards, rows), item_tile, item_block, item_flag, direction,
        np.full(shards, whole.mean_log_degree),
    )
    return plans, cuts, held.tolist(), items


def mean_log_degree(degree) -> jnp.ndarray:
    """The mean of log(d + 1) over the endpoints that have a neighbour, 1 where
    none has: the constant PNA's degree scalers `log(d + 1) / delta` divide by
    (models/pna.py). Made ONCE a topology, where its plan is built, and in the
    arithmetic of the device that divides its own logarithms by it: the same
    `jnp.log`, in float32, so that the scalers' mean over those endpoints is 1
    as that device counts (on the v5e its sum of 100,000 logarithms lies 1.4e-5
    from the float64 one, which moves the first slot's loss by 2e-5: PERF.md,
    PR 39). Endpoints without a neighbour are left out because they aggregate
    nothing, and so that the rows which pad a node bucket do not enter it."""
    degree = jnp.asarray(degree, jnp.float32)
    held = degree > 0
    total = jnp.sum(jnp.where(held, jnp.log(degree + 1.0), 0.0))
    return jnp.where(jnp.any(held), total / jnp.maximum(jnp.sum(held), 1), 1.0)
