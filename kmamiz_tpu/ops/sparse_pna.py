"""The multi-aggregate on the edge plan: PNA's sum, sum of squares, maximum and minimum.

Per plan entry e = (owner o, neighbour n) of `ops/sparse.py`'s `EdgePlan` and a
table of message rows `m [N, W]`, four tables come out of ONE row gather and ONE
walk of the plan's items:

    sum[o] = sum_e m[n]    sq[o] = sum_e m[n]^2    max[o] = max_e m[n]    min[o] = min_e m[n]

(an owner with no entry reads 0 in all four), which is what Principal
Neighbourhood Aggregation (Corso et al., arXiv:2004.05718; `models/pna.py`)
makes its mean, standard deviation, maximum and minimum of. Every other
reduction over the plan is a sum through the item's one-hot on the MXU
(`sparse.planned_neighbor_sum`, `planned_attention`'s `_sum`,
`sparse_gated.planned_gated_sum`). A maximum is not a sum.

On the TPU it is four row gathers and three walks, each a Mosaic kernel that the
device trace names:

    planned_aggregate           the four tables (forward)
    planned_aggregate_ties      how many of an owner's entries hold its maximum,
                                and its minimum, lane by lane (backward)
    planned_aggregate_backward  d m[o]: the transposed sum, by the mirror

- The gathered row is `[m | -m]`, the 128 lanes a gathered row fills whatever
  its width: the lower half makes the minimum a maximum (`min m = -max -m`, a
  negation is exact), so the walk knows one running maximum, and the squares
  are the upper half's own (`(-m)^2 = m^2`), made on the VPU where they are
  summed. Sum and sum of squares go through the one-hot as the siblings' sums
  do: the block transposed (a lane an entry), the exact split of a float32 in
  three bfloat16 pieces, three MXU passes (`sparse._reduce`), `[sum | sq]` as
  one `[128, nodes]` table of node rows.
- The maximum cannot go through the MXU. The plan's entries are sorted by
  owner, so an owner's entries are consecutive lanes of the transposed block:
  a segmented running maximum along the lanes, by shifts of 1, 2, .. 256
  (`pltpu.roll` on the XLU, nine steps a block of 512; an entry takes its
  neighbour's value at distance s where that entry has the same owner, which,
  the entries being sorted, holds for all between), leaves each run's maximum
  on the run's LAST entry of the block. That one entry an owner goes through
  the one-hot like a sum of one term (exact: the three pieces of one float32),
  beside a one-pass count that says which owners the block holds at all, and
  the tile keeps the larger of what it had and what came: a hub whose entries
  span many blocks is merged block by block in the tile that stays in VMEM,
  with no carry between grid steps. `[max | -min]` is the second table of node
  rows; an owner no block holds keeps the fill, and XLA masks it to 0 by the
  plan's degree.
- The VJP. The cotangent of `m[n]` sums, over the entries whose NEIGHBOUR is
  n, `g_sum[o] + 2 m[n] g_sq[o] + g_max[o] [m[n] == max[o]] / ties_max[o] +
  g_min[o] [m[n] == min[o]] / ties_min[o]`: a maximum that several entries
  share hands its gradient to them in equal parts, which is what
  `jax.ops.segment_max`'s own gradient does and what the plain reference
  (`benchmarks/reference/pna.py`) therefore does. The ties are a count over
  an OWNER's entries (`planned_aggregate_ties`: the owner's `[max | -min]`
  expanded to its entries through the one-hot, compared with the forward's
  saved `[m | -m]` rows, the matches, exact in bfloat16, counted in one pass).
  The sum itself is transposed and needs no permutation: every entry has a
  mirror, the same edge seen from its other end, so "the entries whose
  neighbour is n" are the mirrors of the entries n OWNS, and
  `planned_aggregate_backward` walks those: the owner's own `[m | -m]`
  expanded to its entries, and the NEIGHBOUR's six tables gathered as three
  128-lane rows, `[g_sum | g_sq]`, `[g_max / ties | g_min / ties]`, `[max |
  -min]`: compare, weigh, add, and one three-pass sum through the one-hot.
  Each table is 64 MiB at the cells' size and the chip's fast memory holds one
  at a time (PERF.md, PR 33 and PR 36: 1.8 ns a gathered row from there, 10 ns
  from HBM), so the three gathers are chained by `optimization_barrier`: a
  table is made when the gather before it is done.

Off the TPU the same mathematics is plain XLA over the plan's sorted entries
(`_aggregate_xla`: `jax.ops.segment_sum`, `segment_max`, `segment_min`),
differentiated by JAX: the CPU's path and the tests' oracle.
`sparse.planned_impl()` picks, by platform alone.

A module of its own, as `ops/sparse_gated.py` is: a Mosaic kernel's file and
line are in the compile cache's key, and nothing here moves a line of another
kernel's file.
"""
from __future__ import annotations

from functools import partial
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from kmamiz_tpu.ops import sparse
from kmamiz_tpu.ops.sparse import (
    _NEG,
    _NT,
    PLAN_NODE_TILE,
    ROW_OWNER,
    EdgePlan,
    _entry_state,
    _expand,
    _gather_rows,
    _item,
    _mxu,
    _node_tiles,
    _pad_to,
    _reduce,
    _row,
    _rows,
    _walk_call,
)
from kmamiz_tpu.ops.sparse_gated import _halves


def _owner(state):
    """[1, block] int32: the owner of each entry of a block of the entries' state."""
    return jax.lax.bitcast_convert_type(_row(state, ROW_OWNER), jnp.int32)


def _running_max(x, state):
    """[R, block] rows, a lane an entry, -> (each entry's maximum over the
    entries of its owner up to itself within the block, [1, block] whether the
    entry is its owner's last of the block). The entries are sorted by owner:
    where the entry s lanes before has the same owner, so have all between."""
    be = x.shape[1]
    owner = _owner(state)
    lane = jax.lax.broadcasted_iota(jnp.int32, owner.shape, 1)
    s = 1
    while s < be:
        same = (_owner(pltpu.roll(state, s, axis=1)) == owner) & (lane >= s)  # the roll wraps: a hub fills a block
        x = jnp.maximum(x, jnp.where(same, pltpu.roll(x, s, axis=1), _NEG))
        s *= 2
    last = (_owner(pltpu.roll(state, be - 1, axis=1)) != owner) | (lane == be - 1)
    return x, last


def _aggregate_kernel(tile_ref, block_ref, flag_ref, state_ref, msg_ref, sums_ref, tops_ref, *, half: int):
    real, one_hot, _d = _item(tile_ref, block_ref, flag_ref, state_ref, None, (sums_ref,))

    @pl.when(flag_ref[pl.program_id(0)] == 1)
    def _new_tile():
        tops_ref[...] = jnp.full_like(tops_ref, _NEG)

    @pl.when(real)
    def _walk():
        hot = one_hot.astype(jnp.bfloat16)
        x = msg_ref[...].T  # [m | -m] of the neighbour, a lane an entry
        upper = jax.lax.broadcasted_iota(jnp.int32, x.shape, 0) < half
        sums_ref[...] += _reduce(jnp.where(upper, x, x * x), hot)  # [sum | sum of squares]
        run, last = _running_max(x, state_ref[...])
        # the owners this block holds: each has ONE last entry in it
        held = _mxu(_rows(last.astype(jnp.float32)).astype(jnp.bfloat16), hot, _NT)[0:1] > 0.5
        top = _reduce(jnp.where(last, run, 0.0), hot)  # one term an owner: the term itself
        tops_ref[...] = jnp.where(held, jnp.maximum(tops_ref[...], top), tops_ref[...])


def _aggregate_ties_kernel(tile_ref, block_ref, flag_ref, state_ref, msg_ref, toprow_ref, ties_ref):
    real, one_hot, _d = _item(tile_ref, block_ref, flag_ref, state_ref, None, (ties_ref,))

    @pl.when(real)
    def _walk():
        hot = one_hot.astype(jnp.bfloat16)
        at_top = msg_ref[...].T == _expand(toprow_ref[...], hot)  # [m | -m][n] against [max | -min][o]
        ties_ref[...] += _mxu(at_top.astype(jnp.float32).astype(jnp.bfloat16), hot, _NT)


def _aggregate_backward_kernel(
    tile_ref, block_ref, flag_ref, state_ref, nsum_ref, nshare_ref, ntop_ref, mrow_ref, dm_ref, *, half: int,
):
    real, one_hot, _d = _item(tile_ref, block_ref, flag_ref, state_ref, None, (dm_ref,))

    @pl.when(real)
    def _walk():
        hot = one_hot.astype(jnp.bfloat16)
        own = _expand(mrow_ref[...], hot)  # [m | -m] of the entry's owner: the mirror's neighbour
        g = nsum_ref[...].T  # [g_sum | g_sq] of the neighbour: the mirror's owner
        hit = jnp.where(own == ntop_ref[...].T, nshare_ref[...].T, 0.0)  # its [g_max | g_min] / ties, where m is its extreme
        dm_ref[...] += _reduce(g[:half] + 2.0 * own[:half] * g[half:] + hit[:half] + hit[half:], hot)


def _shapes(plan: EdgePlan, width: int) -> Tuple[int, int]:
    """(nodes the tiles cover, lanes of one half of a gathered row)."""
    return _node_tiles(plan) * PLAN_NODE_TILE, _pad_to(2 * width, 128) // 2


def _held(plan: EdgePlan, n: int):
    """[n, 1]: the owners that have an entry."""
    return plan.degree[:n, None] > 0


def _aggregate_pallas_fwd(plan: EdgePlan, m, interpret: bool):
    n, width = m.shape
    nodes, half = _shapes(plan, width)
    msg = _gather_rows(_halves(nodes, half, m, -m), plan.neighbour)  # [L, 2 half], float32
    sums, tops = _walk_call(
        plan, partial(_aggregate_kernel, half=half), "planned_aggregate",
        [("entry", _entry_state(plan)), ("message", msg)],
        [("node_rows", 2 * half), ("node_rows", 2 * half)], interpret,
    )
    held = _held(plan, n)
    out = (
        sums[:width, :n].T,
        sums[half : half + width, :n].T,
        jnp.where(held, tops[:width, :n].T, 0.0),
        jnp.where(held, -tops[half : half + width, :n].T, 0.0),
    )
    return tuple(a.astype(m.dtype) for a in out), (m, msg, tops)


def _aggregate_pallas_bwd(plan: EdgePlan, interpret: bool, saved, cotangents):
    m, msg, tops = saved
    g_sum, g_sq, g_max, g_min = (g.astype(jnp.float32) for g in cotangents)
    n, width = m.shape
    nodes, half = _shapes(plan, width)
    state = _entry_state(plan)
    ties = _walk_call(
        plan, _aggregate_ties_kernel, "planned_aggregate_ties",
        [("entry", state), ("message", msg), ("node_rows", tops)],
        [("node_rows", 2 * half)], interpret,
    )[0]
    ties = jnp.maximum(ties, 1.0)  # an owner with no entry shares nothing out
    # one table at a time in the fast memory (the module's docstring): a table
    # is made when the gather before it is done, the walk's own operand last
    nsum = _gather_rows(_halves(nodes, half, g_sum, g_sq), plan.neighbour)
    nsum, g_max, g_min, ties = jax.lax.optimization_barrier((nsum, g_max, g_min, ties))
    shares = (g_max / ties[:width, :n].T, g_min / ties[half : half + width, :n].T)
    nshare = _gather_rows(_halves(nodes, half, *shares), plan.neighbour)
    nshare, tops = jax.lax.optimization_barrier((nshare, tops))
    ntop = _gather_rows(tops.T, plan.neighbour)
    ntop, m = jax.lax.optimization_barrier((ntop, m))
    dm = _walk_call(
        plan, partial(_aggregate_backward_kernel, half=half), "planned_aggregate_backward",
        [
            ("entry", state), ("message", nsum), ("message", nshare), ("message", ntop),
            ("node_rows", _halves(nodes, half, m, -m).T),
        ],
        [("node_rows", half)], interpret,
    )[0]
    return dm[:width, :n].T.astype(m.dtype)


@partial(jax.custom_vjp, nondiff_argnums=(2,))
def _aggregate_pallas(plan: EdgePlan, m, interpret: bool):
    return _aggregate_pallas_fwd(plan, m, interpret)[0]


def _aggregate_pallas_fwd_rule(plan, m, interpret):
    out, saved = _aggregate_pallas_fwd(plan, m, interpret)
    return out, (plan, saved)


def _aggregate_pallas_bwd_rule(interpret, saved, cotangents):
    plan, rest = saved
    return None, _aggregate_pallas_bwd(plan, interpret, rest, cotangents)


_aggregate_pallas.defvjp(_aggregate_pallas_fwd_rule, _aggregate_pallas_bwd_rule)


def segment_aggregates(rows, held, **segments):
    """(sum, sum of squares, maximum, minimum) of `rows` by segment, as
    `jax.ops.segment_*` make them (`segments`: their `segment_ids`,
    `num_segments`, ..), and 0 where `held` says a segment is empty: its
    maximum is -inf there and its minimum +inf. `held` is `[segments, 1]`."""
    total, squares = jax.ops.segment_sum(rows, **segments), jax.ops.segment_sum(rows * rows, **segments)
    top, bottom = jax.ops.segment_max(rows, **segments), jax.ops.segment_min(rows, **segments)
    return total, squares, jnp.where(held, top, 0.0), jnp.where(held, bottom, 0.0)


def _aggregate_xla(plan: EdgePlan, m):
    """The same mathematics in plain XLA, as sorted segment reductions over
    the plan's entries, differentiated by JAX (`segment_max`'s own gradient
    splits a shared maximum equally): the path off the TPU, and the oracle of
    the kernels. An empty segment's maximum is -inf, and is masked by the degree."""
    n = m.shape[0]
    nodes = _node_tiles(plan) * PLAN_NODE_TILE
    owner = plan.owner[0]
    held = jnp.pad(plan.degree, (0, nodes + 1 - plan.degree.shape[0]))[:, None] > 0
    with jax.named_scope("reduce"):  # the phases of the kernels' path (docs/OBSERVABILITY.md), on this one too
        with jax.named_scope("gather"):
            rows = m[plan.neighbour]
        tables = segment_aggregates(
            rows, held, segment_ids=jnp.where(owner < nodes, owner, nodes), num_segments=nodes + 1,
            indices_are_sorted=True,
        )
        return tuple(a[:n] for a in tables)


def planned_aggregate(plan: EdgePlan, m, impl: Optional[str] = None):
    """PNA's four aggregates over both edge directions from a prepared plan:
    `[N, W]` message rows -> (sum, sum of squares, maximum, minimum), each
    `[N, W]`, over the entries (o, n) of o, a multiset: an endpoint that is
    both caller and callee of o makes two. An owner with no entry reads 0 in
    all four. A maximum that several entries share splits its gradient among
    them equally. `impl` as for `sparse.planned_neighbor_sum`. Counted in
    `sparse.route_stats()["planned"]` (trace time)."""
    if isinstance(plan, sparse.ShardPlan):
        raise NotImplementedError(
            "a maximum over an owner's entries knows no mesh axis yet: a node-sharded plan meets them "
            "in pieces, a source chip each, and has to merge a running maximum across the sources"
        )
    with sparse._route_lock:
        sparse._route_counts["planned"] += 1
    impl = impl or sparse.planned_impl()
    if impl == "xla":
        return _aggregate_xla(plan, m)
    return _aggregate_pallas(plan, m, impl == "pallas_interpret")
