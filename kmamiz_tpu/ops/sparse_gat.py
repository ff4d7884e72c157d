"""The planned attention: GAT's directed segment softmax and its weighted sum on the edge plan.

GAT's layer over both edge directions is ONE pass over the plan's entries
(ops/edge_plan.py): entry e = (owner i, neighbour j, direction d) scores
`leaky_relu(s[j, d] + t[i, d])`, the softmax runs over the consecutive
entries of one (owner, direction), and `out[i] = sum_e alpha_e * hw[j]`.
On the TPU that is five walks of the plan's items (ops/walk.py), forward and
backward, each a Mosaic kernel the device trace names:

  planned_attention_max       z_e and the largest score of each run
  planned_attention_softmax   p_e = exp(score_e - max) and each run's sum
  planned_attention_sum       alpha_e = p_e / sum, out = sum alpha_e hw[j]
  planned_attention_edge_dot  d alpha_e = <g[i], hw[j]>, and sum alpha d alpha
  planned_attention_backward  the softmax's and leaky-relu's gradients, the
                              sorted sums d s, d t, and the transposed
                              weighted sum d hw[j] = sum alpha_e g[i]

The entries' state holds z, p, alpha and d alpha (`walk.ROW_*`); the residual
of the forward pass is the state after `_sum` (z and alpha) and the float32
messages, once. `_max`, whose step is shorter than the latency of its message
block's fetch, reads its messages through `walk.message_block`'s ring. The
transposed sums (d hw[j] and d s[j, d]) run over the mirrors of the entries j
owns, and a mirror's alpha is recomputed where it is needed, bit for bit, from
what the walk has at hand: s of the owner, and t, max and sum of the
neighbour (in the lanes of its gathered row). No scatter and no 1-D gather in
either pass. Off the TPU, sorted segment reductions in plain XLA
(`_attention_xla`): the CPU's path and the kernels' oracle. GATv2's walks
(ops/sparse_gatv2.py) run three of these kernel bodies under their own names.
"""
from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from kmamiz_tpu.ops import walk
from kmamiz_tpu.ops.edge_plan import PLAN_NODE_TILE, EdgePlan, pad_to, plan_tiles
from kmamiz_tpu.ops.walk import (
    ATT_ROWS, ATT_SPARE, NEG, ROW_ALPHA, ROW_DALPHA, ROW_P, ROW_Z, add_row, by_direction, entry_dot, entry_state,
    expand, gather_rows, item, leaky, message_block, node_rows, node_table, reduce, row, rows, rows_by_direction,
    walk_call, weighted_sum,
)


def _neighbour_rows(msg_ref, first: int):
    """Columns first .. first + 7 of a float32 [block, lanes] message block as
    [8, block] rows: the neighbour's scalars, to the bit, by a transposition."""
    return msg_ref[...].T[first : first + ATT_ROWS, :]


def _attention_max_kernel(
    tile_ref, block_ref, flag_ref, state_ref, msg_ref, trow_ref,
    next_ref, top_ref, ring, sems, *, width: int, leak: float,
):
    msg_ref = message_block(block_ref, msg_ref, ring, sems)
    real, one_hot, d = item(tile_ref, block_ref, flag_ref, state_ref, next_ref, (top_ref,), NEG)

    @pl.when(real)
    def _walk():
        hot = one_hot.astype(jnp.bfloat16)
        nbr = _neighbour_rows(msg_ref, width)  # s of the neighbour
        own = expand(trow_ref[...], hot)  # t of the owner, and a row of ones
        inside = own[2:3] > 0.5
        z = by_direction(d, nbr[0:1] + own[0:1], nbr[1:2] + own[1:2])
        add_row(next_ref, ROW_Z, jnp.where(inside, z, 0.0))
        score = leaky(z, leak)
        tops = []
        for k in (0, 1):
            mine = jnp.where(inside & (d == k), score, NEG)
            tops.append(jnp.max(jnp.where(one_hot, mine, NEG), axis=1, keepdims=True))
        lane = jax.lax.broadcasted_iota(jnp.int32, top_ref.shape, 1)
        top_ref[...] = jnp.maximum(top_ref[...], jnp.where(lane == 0, tops[0], tops[1]))


def attention_softmax_kernel(
    tile_ref, block_ref, flag_ref, state_ref, mrow_ref,
    next_ref, total_ref, *, leak: float,
):
    real, one_hot, d = item(tile_ref, block_ref, flag_ref, state_ref, next_ref, (total_ref,))

    @pl.when(real)
    def _walk():
        hot = one_hot.astype(jnp.bfloat16)
        own = expand(mrow_ref[...], hot)  # the run's maximum, and a row of ones
        inside = own[2:3] > 0.5
        shift = by_direction(d, own[0:1], own[1:2])
        delta = jnp.clip(leaky(row(state_ref, ROW_Z), leak) - shift, -60.0, 0.0)
        p = jnp.where(inside, jnp.exp(delta), 0.0)
        add_row(next_ref, ROW_P, p)
        total_ref[...] += reduce(rows_by_direction(d, p), hot)


def attention_sum_kernel(
    tile_ref, block_ref, flag_ref, state_ref, lrow_ref, msg_ref,
    next_ref, out_ref,
):
    real, one_hot, d = item(tile_ref, block_ref, flag_ref, state_ref, next_ref, (out_ref,))

    @pl.when(real)
    def _walk():
        hot = one_hot.astype(jnp.bfloat16)
        own = expand(lrow_ref[...], hot)  # the run's sum, and a row of ones
        inside = own[2:3] > 0.5
        total = jnp.maximum(by_direction(d, own[0:1], own[1:2]), 1e-30)
        alpha = jnp.where(inside, row(state_ref, ROW_P) / total, 0.0)
        add_row(next_ref, ROW_ALPHA, alpha)
        out_ref[...] += weighted_sum(msg_ref[...].T, alpha, hot)


def attention_edge_dot_kernel(
    tile_ref, block_ref, flag_ref, state_ref, msg_ref, grow_ref,
    next_ref, c_ref,
):
    real, one_hot, d = item(tile_ref, block_ref, flag_ref, state_ref, next_ref, (c_ref,))

    @pl.when(real)
    def _walk():
        hot = one_hot.astype(jnp.bfloat16)
        g, msg_t = grow_ref[...], msg_ref[...].T  # g of the tile's nodes; hw of the neighbours, a lane an entry
        dalpha = entry_dot(expand(g, hot), msg_t)  # <g[owner], hw[neighbour]>
        add_row(next_ref, ROW_DALPHA, dalpha)
        y = row(state_ref, ROW_ALPHA) * dalpha  # 0 for an entry of another tile
        c_ref[...] += reduce(rows_by_direction(d, y), hot)


def _attention_backward_kernel(
    tile_ref, block_ref, flag_ref, state_ref,
    msg_ref, hwrow_ref, nrow_ref, dhw_ref, dst_ref, *, width: int, leak: float,
):
    real, one_hot, d = item(tile_ref, block_ref, flag_ref, state_ref, None, (dhw_ref, dst_ref))

    @pl.when(real)
    def _walk():
        hot = one_hot.astype(jnp.bfloat16)
        msg_t = msg_ref[...].T  # g of the neighbour, then its t, max, sum, c
        nbr = msg_t[width : width + ATT_ROWS, :]
        # hw of the owner and, in the same product, its s and c and a row of ones
        lanes = hwrow_ref.shape[0]
        own = expand(jnp.concatenate([hwrow_ref[...], nrow_ref[...]], axis=0), hot)
        dalpha_m, own = entry_dot(own[:lanes], msg_t), own[lanes:]  # <hw[owner], g[neighbour]>
        inside = own[4:5] > 0.5
        # this entry's own softmax: d z = alpha (d alpha - c) leaky'(z)
        z = row(state_ref, ROW_Z)
        c = by_direction(d, own[2:3], own[3:4])
        alpha, dalpha = row(state_ref, ROW_ALPHA), row(state_ref, ROW_DALPHA)
        dz = jnp.where(inside, alpha * (dalpha - c) * jnp.where(z >= 0, 1.0, leak), 0.0)
        # its mirror's, in the other direction: s of the owner, the rest the
        # neighbour's (rows t0 t1 max0 max1 sum0 sum1 c0 c1)
        zm = by_direction(d, own[1:2] + nbr[1:2], own[0:1] + nbr[0:1])
        shift = by_direction(d, nbr[3:4], nbr[2:3])
        total = jnp.maximum(by_direction(d, nbr[5:6], nbr[4:5]), 1e-30)
        cm = by_direction(d, nbr[7:8], nbr[6:7])
        pm = jnp.exp(jnp.clip(leaky(zm, leak) - shift, -60.0, 0.0))
        alpha_m = jnp.where(inside, pm / total, 0.0)
        dzm = alpha_m * (dalpha_m - cm) * jnp.where(zm >= 0, 1.0, leak)
        # the mirror's direction is 1 - d: its d s lands in the other row
        dst_ref[...] += reduce(
            rows(
                jnp.where(d == 1, dzm, 0.0), jnp.where(d == 0, dzm, 0.0),
                jnp.where(d == 0, dz, 0.0), jnp.where(d == 1, dz, 0.0),
            ),
            hot,
        )
        dhw_ref[...] += weighted_sum(msg_t, alpha_m, hot)


def _attention_shapes(plan: EdgePlan, hw):
    nodes = plan_tiles(plan) * PLAN_NODE_TILE
    return nodes, pad_to(hw.shape[1] + ATT_SPARE, 128)


def _attention_pallas_fwd(plan: EdgePlan, hw, s, t, leak: float, interpret: bool):
    n, width = hw.shape
    nodes, lanes = _attention_shapes(plan, hw)
    msg = gather_rows(node_table(nodes, lanes, hw, s), plan.neighbour)  # [L, lanes]
    state, top = walk_call(
        plan, partial(_attention_max_kernel, width=width, leak=leak),
        "planned_attention_max",
        [
            ("entry", entry_state(plan)), ("message_ring", msg),
            ("node_rows", node_rows(nodes, t[:, 0], t[:, 1])),
        ],
        [("entry", ATT_ROWS), ("node", 2)], interpret,
    )
    top = jnp.where(top > NEG / 2, top, 0.0)[:n]  # an empty run shifts by 0
    state, total = walk_call(
        plan, partial(attention_softmax_kernel, leak=leak),
        "planned_attention_softmax",
        [("entry", state), ("node_rows", node_rows(nodes, top[:, 0], top[:, 1]))],
        [("entry", ATT_ROWS), ("node_rows", ATT_ROWS)], interpret,
    )
    total = total[:2, :n].T  # [n, 2]
    state, out = walk_call(
        plan, attention_sum_kernel, "planned_attention_sum",
        [
            ("entry", state),
            ("node_rows", node_rows(nodes, total[:, 0], total[:, 1])),
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
    state, c = walk_call(
        plan, attention_edge_dot_kernel, "planned_attention_edge_dot",
        [("entry", state), ("message", msg), ("node_rows", node_table(nodes, lanes, g).T)],
        [("entry", ATT_ROWS), ("node_rows", ATT_ROWS)], interpret,
    )
    c = c[:2, :n].T  # [n, 2]: the sum of alpha * d alpha over each run
    g_msg = gather_rows(node_table(nodes, lanes, g, t, top, total, c), plan.neighbour)
    dhw, dst = walk_call(
        plan, partial(_attention_backward_kernel, width=width, leak=leak),
        "planned_attention_backward",
        [
            ("entry", state), ("message", g_msg),
            ("node_rows", node_table(nodes, lanes, hw).T),
            ("node_rows", node_rows(nodes, s[:, 0], s[:, 1], c[:, 0], c[:, 1])),
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
    nodes = plan_tiles(plan) * PLAN_NODE_TILE
    owner, nbr, d = plan.owner[0], plan.neighbour, plan.direction[0]
    real = owner < nodes
    own = jnp.minimum(owner, n - 1)
    score = leaky(s[nbr, d] + t[own, d], leak)
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
            gathered = hw[nbr]
        out = jax.ops.segment_sum(
            weights * gathered, jnp.where(real, owner, nodes),
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
    as for `sparse_sum.planned_neighbor_sum`. Counted in `route_stats()["planned"]`, as
    every reduction over a plan is, and in `["attention"]` (trace time)."""
    walk.count("planned", "attention")
    impl = impl or walk.planned_impl()
    if impl == "xla":
        return _attention_xla(plan, hw, s, t, leak)
    return _attention_pallas(plan, hw, s, t, leak, impl == "pallas_interpret")
