"""The planned neighbour sum: `A @ h` over an edge plan, on one device and over a history sharded by nodes.

With the plan's entries sorted by owner (ops/edge_plan.py), `A @ h` is one row
gather and one tiled reduction of consecutive rows: O(E) one-hot work, no
node-table cap, no scatter. A is symmetric, so the cotangent of h is the same
product of the cotangent of agg: the VJP is the forward, nothing is saved,
and no scatter is left in either pass. A Pallas kernel on the TPU, the same
items in plain XLA elsewhere (`walk.planned_impl`). GraphSAGE's refresh takes
it, and so does the slot group's packed layer-1 sum (models/stacked.py).

Over a history sharded by nodes a device holds the rows of its range of every
slot and the entries whose OWNER it holds, cut once more by the SOURCE: the
shard their neighbour's row lives on (`edge_plan.build_shard_plans`). That is
`shards` sub-plans of one shape, each the plan of the device's rows over ONE
source's table: the owner a row of the shard, the neighbour a row of the
source's `[rows, W]` table. A layer's sum is then an all-gather of the
devices' rows (float32, as they are stored: what crosses between chips is the
configuration's precision) into `[shards, rows, W]`, and source by source a
row gather from that source's table, the planned reduction of its entries,
and the parts added. A is symmetric, so the cotangent of the local rows is
the same thing of the cotangent: all-gathered, summed over the SAME
sub-plans. No scatter, no reduce-scatter, and nothing but the tables crosses.

Why a source at a time (PERF.md, PR 33 and PR 36): a row gather costs 1.8 ns a
row while its TABLE lies in the chip's fast memory (128 MiB) and 10 ns from
HBM. One source's table at 128 lanes is 64 MiB at the cells' size; the whole
mesh's is 256 MiB. XLA keeps a table there (`S(1)` in the compiled layout)
only while one is alive at a time, so the sources are chained by
`optimization_barrier`: a source's gather is done before the next source's
table is cut out of the all-gathered array. Every entry is still summed once,
in float32, through the same reducer; an owner's sum comes out as `shards`
partial sums added, an order and not a precision. A head that reduces over
ALL of an owner's entries with a softmax or a gate would meet them in
`shards` pieces and has to merge a running maximum and sum across the sources
(ROADMAP R2).
"""
from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from kmamiz_tpu.ops import walk
from kmamiz_tpu.ops.edge_plan import PLAN_EDGE_BLOCK, PLAN_NODE_TILE, EdgePlan, ShardPlan, plan_tiles, source_plan
from kmamiz_tpu.ops.walk import split3


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
        hi, mid, lo = split3(msg_ref[...])
        dot = partial(jnp.dot, preferred_element_type=jnp.float32)
        out_ref[...] += dot(one_hot, hi) + dot(one_hot, mid) + dot(one_hot, lo)


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
        out_shape=jax.ShapeDtypeStruct((plan_tiles(plan) * tn, width), jnp.float32),
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
    node_tiles = plan_tiles(plan)
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


def _planned_sum(plan: EdgePlan, h, impl: str):
    # `gather` and `reduce`: the phases a device trace is summed by (docs/OBSERVABILITY.md), here and below
    with jax.named_scope("gather"):
        messages = h.astype(jnp.float32)[plan.neighbour]  # [L, W]; parked read row 0
    with jax.named_scope("reduce"):
        if impl == "xla":
            out = _planned_reduce_xla(plan, messages)
        else:
            out = _planned_reduce_pallas(plan, messages, impl == "pallas_interpret")
        return out[: h.shape[0]].astype(h.dtype)  # the plan's owners are h's rows


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
    walk.count("planned")
    if isinstance(plan, ShardPlan):
        return sharded_neighbor_sum(plan, h, impl)
    return _planned_sum_vjp(plan, h, impl or walk.planned_impl())


def _sharded_sum(plan: EdgePlan, h, impl: str, axis: str):
    with jax.named_scope("collective"):
        tables = jax.lax.all_gather(h, axis, axis=0, tiled=False)  # [shards, n, W], rows as stored
    out = None
    for source in range(plan.neighbour.shape[0]):
        mine = source_plan(plan, source)
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
    walk.count("sharded")
    return _sharded_sum_vjp(plan.plan, h, impl or walk.planned_impl(), plan.axis)
