"""Dynamic attention on the edge plan: GATv2's score over both edge directions.

Per plan entry e = (owner i, neighbour j, direction d) of `ops/edge_plan.py`'s
`EdgePlan` (d = 0: i is the edge's source, the caller; 1: its destination) and
two tables of rows `xl = h W_l`, `xr = h W_r` `[N, W]`:

    z_e     = xl[j] + xr[i]                                  W floats an ENTRY
    score_e = sum_k a_d[k] leaky_relu(z_e[k])
    alpha_e = softmax of score over the entries of (i, d)
    out[i]  = sum_e alpha_e xl[j]

which is Brody, Alon and Yahav's GATv2 (arXiv:2105.14491; PyTorch Geometric
`GATv2Conv`), `models/gatv2.py`'s aggregation. GAT's score
(`sparse_gat.planned_attention`) is `leaky(s[j, d] + t[i, d])`, two numbers made
once a node; here the nonlinearity comes BEFORE the dot product with `a_d`,
so the score is no sum of per-node parts and is made entry by entry from a
W-wide row.

On the TPU it is two row gathers and five walks of the plan's items a call,
forward and backward, each a Mosaic kernel the device trace names:

    planned_gatv2_score      z_e, the score and the largest score of each run
    planned_gatv2_softmax    p_e = exp(score_e - max) and each run's sum
    planned_gatv2_sum        alpha_e = p_e / sum, out = sum alpha_e xl[j]
    planned_gatv2_edge_dot   d alpha_e = <g[i], xl[j]>, and c = sum alpha d alpha
    planned_gatv2_backward   the 64-wide d z of the entry and of its mirror:
                             d xr[i], d xl[i] and d a_0, d a_1

- The gathers, each of a `[nodes, 128]` table at W = 64: forward `[xl | xr]`
  of the neighbour (saved for the backward pass, as GAT's forward table is);
  backward `[g | max0 max1 sum0 sum1 c0 c1]` of the neighbour (70 lanes).
- `_score` expands the owner's `xr` row through the item's one-hot (exact:
  `walk.expand`, one stacked MXU product of the three bfloat16 pieces),
  adds the neighbour's gathered `xl`, transposed (a lane an entry), applies
  `leaky` and weighs by `a_d` on the VPU, and sums over the sublanes
  (`walk.entry_dot`: a float32 multiplication an element and a float32 sum
  in an order the source fixes). The score goes into the entries' state, the
  row where GAT's walks keep `z`; `z` itself, W floats an entry, is made
  again where the backward pass needs it rather than written.
- `_softmax`, `_sum` and `_edge_dot` are GAT's own kernel bodies
  (`sparse_gat.attention_softmax_kernel`, `attention_sum_kernel`,
  `attention_edge_dot_kernel`) under this family's names; the softmax walk,
  which makes `leaky(z)` of the stored row, is handed a slope of 1, so it
  reads the stored score as it is.
- `a` is a parameter, the same for every entry of a direction. A walk reads
  it as node rows `[W, nodes]` whose every tile holds `a_0` in its first
  column and `a_1` in its second (32 MB a table at W = 64, one fetch a tile),
  and selects a column an entry by its direction.
- The VJP. With `g = dL/d out`, `d alpha_e = <g[i], xl[j]>`, `c_{i,d} =
  sum_e alpha_e d alpha_e` and `d score_e = alpha_e (d alpha_e - c_{i,d})`,
  an entry's cotangent is W floats wide: `d z_e = d score_e a_d leaky'(z_e)`
  (leaky' is 1 at 0, as `jax.nn.leaky_relu`'s), where every walk before left
  one number an entry. It is reduced three ways:
  - to the OWNER, `d xr[i] = sum_e d z_e`, through the one-hot;
  - to the NEIGHBOUR, `d xl[j] += alpha_e g[i] + d z_e`, a transposed sum that
    needs no permutation: every entry has a mirror, the same edge seen from
    its other end, with owner and neighbour swapped and the other direction,
    so `_backward` makes, beside each entry's own `d z`, its MIRROR's, from
    what the item has at hand: `xl` of the owner (expanded) and `xr` of the
    neighbour (in the saved forward table) give the mirror's `z` and score,
    to the bit the forward's; the neighbour's max, sum and c (in the second
    table) its alpha and `d score`; its `d alpha` is `<xl of the owner, g of
    the neighbour>`. The two W-row results, `d xr` and `d xl`, go through the
    one-hot as ONE stacked `[2W, block]` sum (`walk.reduce`: three passes);
  - to the PARAMETER, `d a_d = sum_e d score_e leaky(z_e)` over every entry of
    direction d: each item sums its own entries' terms along the lanes (no
    MXU) into the first two columns of its tile's `[W, 128]` block, and XLA
    adds the tiles' columns once a call (`_tile_columns`).
  Float32 throughout the vector work: `z`, `leaky`, the score and `d z` never
  see bfloat16; only the exact one-hot goes through the MXU.

Off the TPU the same mathematics is plain XLA over the plan's sorted entries
(`_gatv2_xla`), differentiated by JAX: the CPU's path and the tests' oracle.
`walk.planned_impl()` picks, by platform alone.
"""
from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from kmamiz_tpu.ops import walk
from kmamiz_tpu.ops.edge_plan import PLAN_NODE_TILE, EdgePlan, pad_to, plan_tiles
from kmamiz_tpu.ops.sparse_gat import attention_edge_dot_kernel, attention_softmax_kernel, attention_sum_kernel
from kmamiz_tpu.ops.walk import (
    ATT_ROWS, ATT_SPARE, NEG, ROW_ALPHA, ROW_DALPHA, ROW_Z, add_row, by_direction, entry_dot, entry_state, expand,
    gather_rows, item, leaky, node_rows, node_table, reduce, row, walk_call,
)


def _a_rows(a_ref):
    """(a_0, a_1) as `[W, 1]` columns of a tile's block of `_a_table`."""
    block = a_ref[...]
    return block[:, 0:1], block[:, 1:2]


def _score_kernel(
    tile_ref, block_ref, flag_ref, state_ref, msg_ref, orow_ref, a_ref,
    next_ref, top_ref, *, width: int, leak: float,
):
    real, one_hot, d = item(tile_ref, block_ref, flag_ref, state_ref, next_ref, (top_ref,), NEG)

    @pl.when(real)
    def _walk():
        hot = one_hot.astype(jnp.bfloat16)
        x = msg_ref[...].T  # xl, then xr, of the neighbour: a lane an entry
        own = expand(orow_ref[...], hot)  # xr of the owner, and a row of ones
        inside = own[width : width + 1] > 0.5
        z = x[:width] + own[:width]
        score = entry_dot(by_direction(d, *_a_rows(a_ref)), leaky(z, leak))
        add_row(next_ref, ROW_Z, jnp.where(inside, score, 0.0))
        tops = []
        for k in (0, 1):
            mine = jnp.where(inside & (d == k), score, NEG)
            tops.append(jnp.max(jnp.where(one_hot, mine, NEG), axis=1, keepdims=True))
        lane = jax.lax.broadcasted_iota(jnp.int32, top_ref.shape, 1)
        top_ref[...] = jnp.maximum(top_ref[...], jnp.where(lane == 0, tops[0], tops[1]))


def _backward_kernel(
    tile_ref, block_ref, flag_ref, state_ref, msg_ref, gmsg_ref, orow_ref, a_ref,
    dx_ref, da_ref, *, width: int, leak: float,
):
    real, one_hot, d = item(tile_ref, block_ref, flag_ref, state_ref, None, (dx_ref, da_ref))

    @pl.when(real)
    def _walk():
        w = width
        hot = one_hot.astype(jnp.bfloat16)
        x = msg_ref[...].T  # xl, then xr, of the neighbour
        y = gmsg_ref[...].T  # g of the neighbour, then its max0 max1 sum0 sum1 c0 c1
        nbr = y[w : w + ATT_ROWS]
        own = expand(orow_ref[...], hot)  # xr and xl of the owner, its c0 c1, a row of ones
        inside = own[2 * w + 2 : 2 * w + 3] > 0.5
        a_0, a_1 = _a_rows(a_ref)
        # this entry's own softmax: d score = alpha (d alpha - c), d z = d score a_d leaky'(z)
        z = x[:w] + own[:w]
        slope = jnp.where(z >= 0, 1.0, leak)
        c = by_direction(d, own[2 * w : 2 * w + 1], own[2 * w + 1 : 2 * w + 2])
        dscore = jnp.where(inside, row(state_ref, ROW_ALPHA) * (row(state_ref, ROW_DALPHA) - c), 0.0)
        dz = dscore * by_direction(d, a_0, a_1) * slope
        # d a_d: the item's entries of direction d, summed along the lanes into column d of the tile's block
        terms = dscore * leaky(z, leak)
        lane = jax.lax.broadcasted_iota(jnp.int32, da_ref.shape, 1)
        da_ref[...] += jnp.where(
            lane == 0, jnp.sum(jnp.where(d == 0, terms, 0.0), axis=1, keepdims=True),
            jnp.where(lane == 1, jnp.sum(jnp.where(d == 1, terms, 0.0), axis=1, keepdims=True), 0.0),
        )
        # its mirror's, in the other direction: xl of the owner and xr of the neighbour make its z, the rest is
        # the neighbour's (rows max0 max1 sum0 sum1 c0 c1)
        a_m = by_direction(d, a_1, a_0)
        zm = own[w : 2 * w] + x[w : 2 * w]
        score_m = entry_dot(a_m, leaky(zm, leak))
        shift = by_direction(d, nbr[1:2], nbr[0:1])
        total = jnp.maximum(by_direction(d, nbr[3:4], nbr[2:3]), 1e-30)
        cm = by_direction(d, nbr[5:6], nbr[4:5])
        alpha_m = jnp.where(inside, jnp.exp(jnp.clip(score_m - shift, -60.0, 0.0)) / total, 0.0)
        dscore_m = alpha_m * (entry_dot(own[w : 2 * w], y[:w]) - cm)  # d alpha: <xl of the owner, g of the neighbour>
        dxl = alpha_m * y[:w] + dscore_m * a_m * jnp.where(zm >= 0, 1.0, leak)
        dx_ref[...] += reduce(jnp.concatenate([dz, dxl], axis=0), hot)  # [d xr | d xl] of the tile's nodes


def _shapes(plan: EdgePlan, width: int):
    """(nodes the tiles cover, the padded width, lanes of the forward table, of the backward's)."""
    w = pad_to(width, 8)
    return plan_tiles(plan) * PLAN_NODE_TILE, w, pad_to(2 * w, 128), pad_to(w + ATT_SPARE, 128)


def _padded(x, w: int):
    """[n, W] rows padded with zeros to `w` columns: a zero column scores 0 and weighs nothing."""
    return jnp.pad(x, ((0, 0), (0, w - x.shape[1])))


def _a_table(a, nodes: int, w: int):
    """`a` [2, W] as node rows `[w, nodes]`: every tile's block holds a_0 in its first column and a_1 in its second."""
    block = jnp.pad(a.astype(jnp.float32).T, ((0, w - a.shape[1]), (0, PLAN_NODE_TILE - 2)))
    return jnp.tile(block, (1, nodes // PLAN_NODE_TILE))


def _tile_columns(table):
    """[w, nodes] node rows whose tiles hold a sum in their first two columns -> [2, w], the sums over the tiles."""
    w = table.shape[0]
    return table.reshape(w, -1, PLAN_NODE_TILE)[:, :, :2].sum(axis=1).T


def _gatv2_pallas_fwd(plan: EdgePlan, xl, xr, a, leak: float, interpret: bool):
    n, width = xl.shape
    nodes, w, lanes, _ = _shapes(plan, width)
    msg = gather_rows(node_table(nodes, lanes, _padded(xl, w), xr), plan.neighbour)
    state, top = walk_call(
        plan, partial(_score_kernel, width=w, leak=leak), "planned_gatv2_score",
        [
            ("entry", entry_state(plan)), ("message", msg),
            ("node_rows", node_table(nodes, w + ATT_ROWS, _padded(xr, w), jnp.ones((n, 1), jnp.float32)).T),
            ("node_rows", _a_table(a, nodes, w)),
        ],
        [("entry", ATT_ROWS), ("node", 2)], interpret,
    )
    top = jnp.where(top > NEG / 2, top, 0.0)[:n]  # an empty run shifts by 0
    state, total = walk_call(
        plan, partial(attention_softmax_kernel, leak=1.0),  # slope 1: the stored score as it is
        "planned_gatv2_softmax",
        [("entry", state), ("node_rows", node_rows(nodes, top[:, 0], top[:, 1]))],
        [("entry", ATT_ROWS), ("node_rows", ATT_ROWS)], interpret,
    )
    total = total[:2, :n].T  # [n, 2]
    state, out = walk_call(
        plan, attention_sum_kernel, "planned_gatv2_sum",
        [("entry", state), ("node_rows", node_rows(nodes, total[:, 0], total[:, 1])), ("message", msg)],
        [("entry", ATT_ROWS), ("node_rows", lanes)], interpret,
    )
    saved = (xl, xr, a, msg, state, top, total)
    return out[:width, :n].T.astype(xl.dtype), saved


def _gatv2_pallas_bwd(plan: EdgePlan, leak: float, interpret: bool, saved, g):
    xl, xr, a, msg, state, top, total = saved
    n, width = xl.shape
    nodes, w, lanes, g_lanes = _shapes(plan, width)
    g = g.astype(jnp.float32)
    state, c = walk_call(
        plan, attention_edge_dot_kernel, "planned_gatv2_edge_dot",
        [("entry", state), ("message", msg), ("node_rows", node_table(nodes, lanes, g).T)],
        [("entry", ATT_ROWS), ("node_rows", ATT_ROWS)], interpret,
    )
    c = c[:2, :n].T  # [n, 2]: the sum of alpha * d alpha over each run
    g_msg = gather_rows(node_table(nodes, g_lanes, _padded(g, w), top, total, c), plan.neighbour)
    dx, da = walk_call(
        plan, partial(_backward_kernel, width=w, leak=leak), "planned_gatv2_backward",
        [
            ("entry", state), ("message", msg), ("message", g_msg),
            ("node_rows", node_table(
                nodes, 2 * w + ATT_ROWS, _padded(xr, w), _padded(xl, w), c, jnp.ones((n, 1), jnp.float32)
            ).T),
            ("node_rows", _a_table(a, nodes, w)),
        ],
        [("node_rows", 2 * w), ("node_rows", w)], interpret,
    )
    return (
        dx[w : w + width, :n].T.astype(xl.dtype),
        dx[:width, :n].T.astype(xr.dtype),
        _tile_columns(da)[:, :width].astype(a.dtype),
    )


@partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def _gatv2_pallas(plan: EdgePlan, xl, xr, a, leak: float, interpret: bool):
    return _gatv2_pallas_fwd(plan, xl, xr, a, leak, interpret)[0]


def _gatv2_pallas_fwd_rule(plan, xl, xr, a, leak, interpret):
    out, saved = _gatv2_pallas_fwd(plan, xl, xr, a, leak, interpret)
    return out, (plan, saved)


def _gatv2_pallas_bwd_rule(leak, interpret, saved, g):
    plan, rest = saved
    return (None, *_gatv2_pallas_bwd(plan, leak, interpret, rest, g))


_gatv2_pallas.defvjp(_gatv2_pallas_fwd_rule, _gatv2_pallas_bwd_rule)


def _gatv2_xla(plan: EdgePlan, xl, xr, a, leak: float):
    """The same mathematics in plain XLA, as sorted segment reductions over
    the plan's (owner, direction) runs, differentiated by JAX: the path off
    the TPU, and the oracle of the kernels. The score is an elementwise
    product summed over the width, as the walks and the plain reference make
    it, not a matrix-vector product."""
    n = xl.shape[0]
    nodes = plan_tiles(plan) * PLAN_NODE_TILE
    owner, nbr, d = plan.owner[0], plan.neighbour, plan.direction[0]
    real = owner < nodes
    with jax.named_scope("reduce"):
        with jax.named_scope("gather"):
            rows, mine = xl[nbr], xr[jnp.minimum(owner, n - 1)]
        score = jnp.sum(leaky(rows + mine, leak) * a[d], axis=-1)
        run = jnp.where(real, owner * 2 + d, 2 * nodes)  # ascending
        neg = jnp.finfo(score.dtype).min
        top = jax.ops.segment_max(
            jnp.where(real, score, neg), run, num_segments=2 * nodes + 1, indices_are_sorted=True
        )
        top = jnp.where(top > neg / 2, top, 0.0)  # an empty run shifts by 0
        p = jnp.where(real, jnp.exp(jnp.clip(score - top[run], -60.0, 0.0)), 0.0)
        total = jax.ops.segment_sum(p, run, num_segments=2 * nodes + 1, indices_are_sorted=True)
        alpha = p / jnp.maximum(total[run], 1e-30)
        out = jax.ops.segment_sum(
            alpha[:, None] * rows, jnp.where(real, owner, nodes), num_segments=nodes + 1, indices_are_sorted=True
        )
        return out[:n]


def planned_gatv2(plan: EdgePlan, xl, xr, a, leak: float = 0.2, impl: Optional[str] = None):
    """GATv2's aggregation over both edge directions from a prepared plan,
    `[N, W] -> [N, W]`: `out[i] = sum alpha_e * xl[j]` over the entries
    (i, j, d) of i, `alpha` the softmax of `a_d . leaky_relu(xl[j] + xr[i])`
    over the entries of one (i, d). `a` is `[2, W]`, a row per direction (0:
    edges out of the owner, 1: edges into it). Empty runs and the exponent
    are treated as `gat._segment_softmax` treats them. `impl` as for
    `sparse_sum.planned_neighbor_sum`. Counted in `sparse.route_stats()`
    `["planned"]`, as every reduction over a plan is, and in `["gatv2"]`
    (trace time)."""
    walk.count("planned", "gatv2")
    impl = impl or walk.planned_impl()
    if impl == "xla":
        return _gatv2_xla(plan, xl, xr, a, leak)
    return _gatv2_pallas(plan, xl, xr, a, leak, impl == "pallas_interpret")
