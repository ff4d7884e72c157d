"""The edge plan: a topology sorted once by owner, on the host, for the reductions over it.

A training refresh runs the same edge list through every slot of every call
(432 slots a call over one edge list), so what depends on the topology alone
is prepared ONCE, on the host, where the stack is built (models/stacked.py):
the undirected neighbour list sorted by owner, the degree, and the work list
of a tiled reduction. With the list sorted, `agg = A @ h` (A the symmetric 0/1
adjacency) is one row gather and one reduction of CONSECUTIVE rows into node
tiles: a node tile meets only the few edge blocks that overlap it, so the
one-hot products are O(E), not the O(E x N) of a one-hot against the whole
node table (ops/sparse_sum.py, and the walks of ops/walk.py).

Where one device cannot hold a history (models/stacked.py decides), the node
axis is cut into `shards` ranges of rows, one a device of a mesh, and a
device's plan is cut once more by the SOURCE, the shard its neighbours' rows
live on (`build_shard_plans`). The ranges are cut where the plan's ENTRIES
divide evenly, not the nodes: a walk's time goes with its entries, every
device waits for the slowest at each all-gather, and endpoints' degrees are
heavy-tailed. A range holds at most `bucket_nodes // shards` nodes, its rows
start at a multiple of that, and the rows past its nodes are padding that
owns nothing.

Numpy on the host, and no Pallas: what a kernel reads of a plan is its arrays.
"""
from __future__ import annotations

from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp
import numpy as np

#: rows of one output tile and entries of one edge block. A product is
#: [PLAN_NODE_TILE, PLAN_EDGE_BLOCK] @ [PLAN_EDGE_BLOCK, width]; the one-hot
#: work grows with entries x PLAN_NODE_TILE + nodes x PLAN_EDGE_BLOCK and the
#: number of grid steps falls with both.
PLAN_NODE_TILE = 128
PLAN_EDGE_BLOCK = 512


def pad_to(n: int, mult: int) -> int:
    return -(-n // mult) * mult


class EdgePlan(NamedTuple):
    """The topology of one stacked dataset, prepared for the planned reductions.
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
    entries = pad_to(max(2 * bucket_edges, 1), PLAN_EDGE_BLOCK)
    node_tiles = -(-max(bucket_nodes, 1) // PLAN_NODE_TILE)
    return entries, node_tiles, node_tiles + entries // PLAN_EDGE_BLOCK


def plan_tiles(plan: EdgePlan) -> int:
    """Node tiles of a plan: its items, less one an edge block."""
    return plan.item_tile.shape[0] - plan.owner.shape[1] // PLAN_EDGE_BLOCK


def _work_list(counts: np.ndarray, entries: int):
    """The items of one plan from its owners' entry counts (`EdgePlan` says
    what an item is): (item_tile, item_block, item_flag, real items). The
    blocks that hold entries of each tile; an empty tile takes the one block
    its (empty) range starts in, where no owner matches its rows."""
    tn, be = PLAN_NODE_TILE, PLAN_EDGE_BLOCK
    node_tiles, edge_blocks = -(-max(counts.shape[0], 1) // tn), entries // be
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
    item_block[n_items:] = item_block[n_items - 1]  # no new block to fetch
    item_flag[:n_items] = 0
    item_flag[starts] = 1
    return item_tile, item_block, item_flag, n_items


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
    entries, node_tiles, _items = plan_shapes(nb, src.shape[0])

    own = np.concatenate([src[real], dst[real]])
    nei = np.concatenate([dst[real], src[real]])
    order = np.argsort(own, kind="stable")
    n_real = int(own.shape[0])
    owner = np.full(entries, node_tiles * PLAN_NODE_TILE, dtype=np.int32)  # parked
    neighbour = np.zeros(entries, dtype=np.int32)
    direction = np.zeros(entries, dtype=np.int32)
    owner[:n_real] = own[order]
    neighbour[:n_real] = nei[order]
    # the sort is stable and the sources come first: out-edges, then in-edges
    direction[:n_real] = order >= n_real // 2

    counts = np.bincount(own, minlength=nb)  # every owner is < nb
    item_tile, item_block, item_flag, n_items = _work_list(counts, entries)
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


def plan_blocks(plan: EdgePlan, items: int) -> int:
    """Edge blocks the `items` real items of a host plan visit: the message
    blocks a walk fetches, where its grid steps are the items. Their blocks
    start at 0 and rise by at most one (a tile starts in the block the tile
    before it ended in, or in the next), so the last one says how many."""
    return int(np.asarray(plan.item_block)[items - 1]) + 1 if items else 0


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


def source_plan(plan: EdgePlan, source: int) -> EdgePlan:
    """One source's sub-plan of a device's plan: every leaf but the owner's two has
    a leading `[shards]` axis over the shard its entries' NEIGHBOURS live on."""
    whole = ("degree", "mean_log_degree")  # the owner's, whatever the source
    return EdgePlan(*(a if name in whole else a[source] for name, a in zip(EdgePlan._fields, plan)))


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


def sub_plans(plans: EdgePlan):
    """The sub-plans of `build_shard_plans`' host plans, in the order of its
    counts: one a shard, or one an (owner, source) pair, owner-major."""
    shards = plans.neighbour.shape[0]
    for d in range(shards):
        one = jax.tree_util.tree_map(lambda a: a[d], plans)
        yield from [one] if shards == 1 else (source_plan(one, s) for s in range(shards))


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
    entries = max(base, pad_to(int(held.max()), max(base // 8, PLAN_EDGE_BLOCK)))
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
