"""The gated neighbour sum on the edge plan: STLGT's neighbour bias.

Per plan entry e = (owner o, neighbour n, direction d) of `ops/sparse.py`'s
`EdgePlan` (d = 0: o is the edge's source, the caller; 1: its destination):

    gate_e  = sigmoid((q[o] . k[n] if d == 0 else q[n] . k[o]) / sqrt(H) + b_edge)
    bias[o] = sum_e gate_e * v[n] / max(sum_e gate_e, 1)

which is what `models/stlgt/model.encode` makes of an edge list with two
gathers of `q`, two of `k`, two of `v` and four unsorted segment sums. Unlike
GAT's attention (`sparse.planned_attention`) there is no softmax, so no
maximum and no normaliser walk; but the weight of an entry is a PRODUCT OF
TWO GATHERED ROWS where GAT's score is a sum of two per-node scalars, both
entries of an edge carry the same gate, the normaliser is the gates' own sum,
and the VJP writes three tables (d q, d k, d v) and d b_edge.

On the TPU it is three row gathers and two walks of the plan's items, each a
Mosaic kernel that the device trace names:

    planned_gated_sum       gate_e, sum gate_e * v[n] and sum gate_e (forward)
    planned_gated_backward  d gate_e of the entry and of its mirror, then
                            d v[o] = sum gate_e * g[n], d q[o] and d k[o]

- The gathers, every one of a `[nodes, 128]` table at H = 64: forward
  `[q | k]` of the neighbour and `[0 | v]`; backward `[g | g_den]`. A row
  costs 1.8 ns (PERF.md) where XLA holds the TABLE in the chip's fast memory
  while it gathers, and 10 ns where the table stays in HBM: the v5e's 128 MiB
  hold one such table (67 MB) at a time. So the forward's two gathers are
  chained, the second table made when the first gather is done and the walk's
  own `[q | k]` operand made again after the second (two barriers in
  `_gated_pallas_fwd`; with both tables alive at once, or with the walk's
  operand fetched ahead beside the second, one of the gathers took 10.8 ms
  for 1.9), and ONE gather of `[x | v]` rows from a table stacked by
  direction, `[q | v]` over `[k | v]` (134 MB), took 10.8 ms too (PR 33).
- The dot product of an entry is GAT's `_edge_dot`'s (`sparse._entry_dot`,
  PR 38): the owner's row `[q | k]`, handed in as node rows `[128, nodes]`,
  goes to its entries through the one-hot in ONE stacked MXU product, exactly,
  and meets the block's rows on the VPU, transposed, each entry's under the
  half its direction reads (`_by_half`: k of the neighbour meets the owner's
  q where the owner calls, q meets k where it is called): one float32
  multiplication an element and a float32 sum over the sublanes, as
  `_gated_xla` and `benchmarks/reference/stlgt.py` make it. Until PR 38 it
  was all 128 x 512 products of the tile against the block in six bfloat16
  passes of depth 128 (`sparse._dot6`), of which the one-hot picked one in 128.
  No bfloat16 ROW enters it or the weighted sums, because neither is a matrix
  product of the model (a gated sum fed bfloat16 rows moves the first slot's
  loss by what `benchmarks/reference/stlgt.py` records). A weighted sum
  (`num`, `d v`, `[d q | d k]`) multiplies on the VPU too, and only the
  one-hot, exact in bfloat16, goes through the MXU, against the exact split
  of the product (`sparse._weighted_sum`, three passes; six until PR 34,
  when the weight went through the MXU too).
  Every per-node operand and result of the two walks is node rows `[lanes,
  nodes]`, as GAT's are: with the owner's tables handed in that way XLA keeps
  the block's `[nodes, 64]` arrays in the layout whose minor dimension is the
  nodes, and `num` and `d v` left as they come out of `_reduce` are slices of
  it (0.34 ms a slot update less than transposed back in the item, PERF.md,
  PR 38; under the other layout, in PR 34, it was the other way round by 0.28).
- The transposed sums need no permutation: every entry has a mirror, the same
  edge seen from its other end, with the same gate. `d v[j]`, a sum over the
  entries whose NEIGHBOUR is j, is the sum over the entries OWNED by j of
  `gate * g[neighbour]`: the forward's weighted sum with `g` for `v`. An
  edge's `d gate` is its two entries' `<g[o], v[n]> + g_den[o]`, and both are
  at hand in either entry's item: one `_entry_dot` of `[v | g]` of the tile
  (node rows again: as a `[nodes, 128]` operand it held XLA's whole backward
  pass to the layout that pads 64 floats to 128 lanes, 4.7 ms a slot update)
  against `[g | v]` of the block (`_g_over_v`: the lanes `g` leaves free are
  those `v` was gathered into; both blocks are transposed for it). `d q[o]`
  sums `d a_e * k[n]` over o's out-entries
  and `d k[o]` sums `d a_e * q[n]` over its in-entries: ONE weighted sum of
  the block's `_by_half` rows, written transposed (`[2 halves, nodes]`), so
  no second pass over the messages.
  `d b_edge` is the sum of `d a_e` over the out-entries, one per edge.

Off the TPU the same mathematics is plain XLA over the plan's sorted entries
(`_gated_xla`), differentiated by JAX: the CPU's path and the tests' oracle.
`sparse.planned_impl()` picks, by platform alone.

A module of its own (ROADMAP D1), and because a Mosaic kernel's file and
line are in the compile cache's key: nothing here moves a line of
`ops/sparse.py`.
"""
from __future__ import annotations

from functools import partial
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from kmamiz_tpu.ops import sparse
from kmamiz_tpu.ops.sparse import (
    ATT_ROWS,
    PLAN_NODE_TILE,
    ROW_OWNER,
    EdgePlan,
    _add_row,
    _entry_dot,
    _entry_state,
    _expand,
    _gather_rows,
    _item,
    _node_rows,
    _node_tiles,
    _pad_to,
    _reduce,
    _row,
    _rows,
    _walk_call,
    _weighted_sum,
)

#: rows of the entries' state that these walks write or read: the gate
#: (forward), d a (backward), and b_edge on every entry (a parameter, so no
#: constant of the kernel); the plan's owner and direction ride below them
ROW_GATE, ROW_DA, ROW_B = 0, 1, 2


def _halves(nodes: int, half: int, left, right):
    """[n, w] `left` and `right` side by side, each padded to `half` lanes,
    rows padded to `nodes`: a table of two halves. None is a half of zeros."""
    def pad(a):
        if a is None:
            return jnp.zeros((nodes, half), jnp.float32)
        a = a.astype(jnp.float32)
        return jnp.pad(a, ((0, nodes - a.shape[0]), (0, half - a.shape[1])))

    return jnp.concatenate([pad(left), pad(right)], axis=1)


def _state(plan: EdgePlan, b):
    """The entries' state before the forward walk: b_edge on every entry."""
    state = _entry_state(plan)
    rows = jax.lax.broadcasted_iota(jnp.int32, state.shape, 0)
    return jnp.where(rows == ROW_B, b.astype(jnp.float32), state)


def _real(plan: EdgePlan):
    """[L]: the entries a tile owns. The parked ones past them lie in blocks
    that no item visits, so no walk ever writes their rows of the state."""
    return plan.owner[0] < _node_tiles(plan) * PLAN_NODE_TILE


def _inside(tile_ref, state_ref):
    """[1, block]: the entries some row of the item's tile owns."""
    tn = PLAN_NODE_TILE
    owner = jax.lax.bitcast_convert_type(_row(state_ref, ROW_OWNER), jnp.int32)
    row = owner - tile_ref[pl.program_id(0)] * tn
    return (row >= 0) & (row < tn)


def _by_half(qk, d, half: int):
    """A block of gathered `[q | k]` rows as the neighbours' halves of the
    entries' dot products: transposed, `[2 half, block]`, rows 0 .. half - 1
    (which meet the owner's q) hold k[n] of the out-entries and zeros
    elsewhere, the rows below (which meet the owner's k) q[n] of the
    in-entries."""
    t = qk.T
    return jnp.concatenate(
        [jnp.where(d == 0, t[half:, :], 0.0), jnp.where(d == 1, t[:half, :], 0.0)], axis=0
    )


def _g_over_v(ng_t, nv_t, half: int):
    """Blocks of gathered `[g | g_den]` and `[0 | v]` rows, transposed, as the
    neighbours' side of the backward's dot product: `[2 half, block]`, g[n]
    (which meets the owner's v) over v[n] (which meets its g)."""
    row = jax.lax.broadcasted_iota(jnp.int32, ng_t.shape, 0)
    return jnp.where(row < half, ng_t, nv_t)


def _gated_sum_kernel(
    tile_ref, block_ref, flag_ref, state_ref, nqk_ref, nv_ref, qkrow_ref,
    next_ref, num_ref, den_ref, *, half: int, scale: float,
):
    real, one_hot, d = _item(tile_ref, block_ref, flag_ref, state_ref, next_ref, (num_ref, den_ref))
    inside = _inside(tile_ref, state_ref)

    @pl.when(real)
    def _walk():
        hot = one_hot.astype(jnp.bfloat16)
        mine, theirs = qkrow_ref[...], _by_half(nqk_ref[...], d, half)
        dots = _entry_dot(_expand(mine, hot), theirs)
        a = dots * scale + _row(state_ref, ROW_B)
        gate = jnp.where(inside, 1.0 / (1.0 + jnp.exp(-a)), 0.0)
        _add_row(next_ref, ROW_GATE, gate)
        num_ref[...] += _weighted_sum(nv_ref[...].T, gate, hot)
        den_ref[...] += _reduce(_rows(gate), hot)


def _gated_backward_kernel(
    tile_ref, block_ref, flag_ref, state_ref, nqk_ref, nv_ref, ng_ref, vgrow_ref, grow_ref,
    next_ref, dv_ref, dqk_ref, *, half: int, scale: float,
):
    real, one_hot, d = _item(tile_ref, block_ref, flag_ref, state_ref, next_ref, (dv_ref, dqk_ref))
    inside = _inside(tile_ref, state_ref)

    @pl.when(real)
    def _walk():
        hot = one_hot.astype(jnp.bfloat16)
        ng_t = ng_ref[...].T  # [g | g_den] of the neighbour; `nv_ref` holds its [0 | v]
        # <v[o], g[n]> + <g[o], v[n]>: the entry's d gate and its mirror's
        mine, theirs = vgrow_ref[...], _g_over_v(ng_t, nv_ref[...].T, half)
        dots = _entry_dot(_expand(mine, hot), theirs)
        den = _expand(grow_ref[...], hot)[0:1] + ng_t[half : half + 1, :]  # g_den[o] + g_den[n]
        gate = _row(state_ref, ROW_GATE)
        da = jnp.where(inside, (dots + den) * gate * (1.0 - gate), 0.0)
        _add_row(next_ref, ROW_DA, da)
        dv_ref[...] += _weighted_sum(ng_t, gate, hot)
        # [d q | d k] of the tile, transposed
        dqk_ref[...] += _weighted_sum(_by_half(nqk_ref[...], d, half), da * scale, hot)


def _shapes(plan: EdgePlan, width: int) -> Tuple[int, int, float]:
    """(nodes the tiles cover, lanes of one half of a row, the dot product's scale)."""
    return _node_tiles(plan) * PLAN_NODE_TILE, _pad_to(2 * width, 128) // 2, float(width) ** -0.5


def _gated_pallas_fwd(plan: EdgePlan, q, k, v, b, interpret: bool):
    n, width = q.shape
    nodes, half, scale = _shapes(plan, width)
    # one table at a time (the module's docstring): v waits for the first
    # gather, and the walk's own `[q | k]` is made after the second
    nqk = _gather_rows(_halves(nodes, half, q, k), plan.neighbour)
    nqk, v = jax.lax.optimization_barrier((nqk, v))
    nv = _gather_rows(_halves(nodes, half, None, v), plan.neighbour)
    nv, q, k = jax.lax.optimization_barrier((nv, q, k))
    state, num, den = _walk_call(
        plan, partial(_gated_sum_kernel, half=half, scale=scale), "planned_gated_sum",
        [("entry", _state(plan, b)), ("message", nqk), ("message", nv), ("node_rows", _halves(nodes, half, q, k).T)],
        [("entry", ATT_ROWS), ("node_rows", 2 * half), ("node_rows", ATT_ROWS)], interpret,
    )
    return (num[half : half + width, :n].T, den[0, :n]), (q, v, nqk, nv, state)


def _gated_pallas_bwd(plan: EdgePlan, interpret: bool, saved, cotangents):
    q, v, nqk, nv, state = saved
    g, g_den = cotangents
    n, width = q.shape
    nodes, half, scale = _shapes(plan, width)
    g = g.astype(jnp.float32)
    ng = _gather_rows(_halves(nodes, half, g, g_den[:, None]), plan.neighbour)
    state, dv, dqk = _walk_call(
        plan, partial(_gated_backward_kernel, half=half, scale=scale), "planned_gated_backward",
        [
            ("entry", state), ("message", nqk), ("message", nv), ("message", ng),
            ("node_rows", _halves(nodes, half, v, g).T), ("node_rows", _node_rows(nodes, g_den)),
        ],
        [("entry", ATT_ROWS), ("node_rows", 2 * half), ("node_rows", 2 * half)], interpret,
    )
    # one out-entry an edge, and its d a is the edge's
    db = jnp.sum(jnp.where(_real(plan) & (plan.direction[0] == 0), state[ROW_DA], 0.0))
    return (
        dqk[:width, :n].T.astype(q.dtype),
        dqk[half : half + width, :n].T.astype(q.dtype),
        dv[:width, :n].T.astype(v.dtype),
        db,
    )


@partial(jax.custom_vjp, nondiff_argnums=(5,))
def _gated_pallas(plan: EdgePlan, q, k, v, b, interpret: bool):
    return _gated_pallas_fwd(plan, q, k, v, b, interpret)[0]


def _gated_pallas_fwd_rule(plan, q, k, v, b, interpret):
    out, saved = _gated_pallas_fwd(plan, q, k, v, b, interpret)
    return out, (plan, saved)


def _gated_pallas_bwd_rule(interpret, saved, cotangents):
    plan, rest = saved
    return (None, *_gated_pallas_bwd(plan, interpret, rest, cotangents))


_gated_pallas.defvjp(_gated_pallas_fwd_rule, _gated_pallas_bwd_rule)


def _gated_xla(plan: EdgePlan, q, k, v, b):
    """The same mathematics in plain XLA, as sorted segment sums over the
    plan's entries, differentiated by JAX: the path off the TPU, and the
    oracle of the kernels."""
    n, width = q.shape
    nodes, _half, scale = _shapes(plan, width)
    owner, nbr, d = plan.owner[0], plan.neighbour, plan.direction[0]
    real = _real(plan)
    own = jnp.minimum(owner, n - 1)
    caller = (d == 0)[:, None]
    with jax.named_scope("reduce"):  # the phases of the kernels' path (docs/OBSERVABILITY.md), on this one too
        with jax.named_scope("gather"):
            mine, theirs = jnp.where(caller, q[own], k[own]), jnp.where(caller, k[nbr], q[nbr])
        dots = (mine * theirs).sum(axis=1)
        gate = jnp.where(real, jax.nn.sigmoid(dots * scale + b), 0.0)
        seg = partial(
            jax.ops.segment_sum, segment_ids=jnp.where(real, owner, nodes),
            num_segments=nodes + 1, indices_are_sorted=True,
        )
        weights = gate[:, None]
        with jax.named_scope("gather"):
            rows = v[nbr]
        return seg(weights * rows)[:n], seg(gate)[:n]


def planned_gated_sum(plan: EdgePlan, q, k, v, b_edge, impl: Optional[str] = None) -> jnp.ndarray:
    """STLGT's neighbour bias over both edge directions from a prepared plan:
    `[N, H]`, `bias[o] = sum gate_e v[n] / max(sum gate_e, 1)` over the entries
    (o, n, d) of o, `gate_e = sigmoid((q[o] . k[n] if d == 0 else q[n] . k[o])
    / sqrt(H) + b_edge)`; `b_edge` holds one number. An edge's two entries hold
    the same gate, the edge's attribution; whoever wants it per EDGE makes it
    from the edge list (`models/stlgt/model.encode`), since the plan keeps no
    way back from an entry to its edge. `impl` as for
    `sparse.planned_neighbor_sum`. Counted in
    `sparse.route_stats()["planned"]` (trace time)."""
    with sparse._route_lock:
        sparse._route_counts["planned"] += 1
    impl = impl or sparse.planned_impl()
    b = jnp.reshape(b_edge, ())
    if impl == "xla":
        num, den = _gated_xla(plan, q, k, v, b)
    else:
        num, den = _gated_pallas(plan, q, k, v, b, impl == "pallas_interpret")
    return num / jnp.maximum(den, 1.0)[:, None]
