"""The planned attention (ops/sparse_gat.planned_attention): GAT's directed
segment softmax, its weighted sum and their VJP over the stack's edge plan, in
plain XLA and as the Pallas kernels (interpreted here), against the unsorted
formulation of `gat._attend`, against the plain reference of the benchmark,
and through `gat.forward`, the stack and the epoch block."""
from __future__ import annotations

import hashlib
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.reference import gat as reference_gat
from kmamiz_tpu.models import common, gat, stacked, trainer
from kmamiz_tpu.ops import edge_plan, sparse, sparse_gat, sparse_sum, walk
from kmamiz_tpu.telemetry.tracing import TRACER
from test_edge_plan import BE, CASES, IMPLS, TN, _case, _dataset, _device

WIDTH = 16


def _plan(name):
    src, dst, mask, nb = _case(name)
    return src, dst, mask, nb, _device(edge_plan.build_edge_plan(src, dst, mask, nb)[0])


def _inputs(nb, seed=1, width=WIDTH):
    rng = np.random.default_rng(seed)
    hw = jnp.asarray(rng.normal(size=(nb, width)).astype(np.float32))
    vectors = tuple(jnp.asarray(rng.normal(size=width).astype(np.float32)) for _ in range(4))
    ct = jnp.asarray(rng.normal(size=(nb, width)).astype(np.float32))
    return hw, vectors, ct


def _unsorted(hw, vectors, src, dst, mask):
    """Both directions of a layer as `gat._layer` makes them without a plan."""
    a_s, a_d, a_sr, a_dr = vectors
    src, dst, mask = jnp.asarray(src), jnp.asarray(dst), jnp.asarray(mask)
    return gat._attend(hw, src, dst, mask, a_s, a_d) + gat._attend(hw, dst, src, mask, a_sr, a_dr)


def _planned(hw, vectors, plan, impl):
    a_s, a_d, a_sr, a_dr = vectors
    s = jnp.stack([hw @ a_sr, hw @ a_s], axis=1)
    t = jnp.stack([hw @ a_dr, hw @ a_d], axis=1)
    return sparse_gat.planned_attention(plan, hw, s, t, gat.LEAK, impl)


def _close(got, want, tol, what=""):
    scale = max(float(np.abs(np.asarray(want)).max()), 1.0)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=0, atol=tol * scale, err_msg=what)


class TestTheDirectedPlan:
    @pytest.mark.parametrize("name", CASES)
    def test_entries_are_sorted_by_owner_then_direction_and_say_which_end_they_are(self, name):
        src, dst, mask, nb = _case(name)
        plan, entries, _ = edge_plan.build_edge_plan(src, dst, mask, nb)
        owner, nbr, d = plan.owner[0, :entries], plan.neighbour[:entries], plan.direction[0, :entries]
        assert set(np.unique(d).tolist()) <= {0, 1} and (plan.direction[0, entries:] == 0).all()
        assert (np.diff(owner.astype(np.int64) * 2 + d) >= 0).all()
        # direction 0: the owner is the edge's source; 1: its destination
        out_edges = sorted(zip(owner[d == 0].tolist(), nbr[d == 0].tolist()))
        in_edges = sorted(zip(nbr[d == 1].tolist(), owner[d == 1].tolist()))
        real = sorted(zip(src[mask].tolist(), dst[mask].tolist()))
        assert out_edges == in_edges == real

    @pytest.mark.parametrize("name", CASES)
    def test_the_mirror_is_an_involution_that_swaps_direction(self, name):
        """Every entry (i, j, d) has one mirror (j, i, 1 - d): the same edge
        seen from its other end. The backward pass sums over mirrors in place
        of a permutation, so the pairing has to be one to one."""
        src, dst, mask, nb = _case(name)
        plan, entries, _ = edge_plan.build_edge_plan(src, dst, mask, nb)
        owner, nbr, d = plan.owner[0, :entries], plan.neighbour[:entries], plan.direction[0, :entries]
        here = np.lexsort((d, nbr, owner))  # by (owner, neighbour, direction)
        there = np.lexsort((1 - d, owner, nbr))  # by (neighbour, owner, other direction)
        mirror = np.empty(entries, np.int64)
        mirror[here] = there  # repeated edges pair up in order
        np.testing.assert_array_equal(owner[mirror], nbr)
        np.testing.assert_array_equal(nbr[mirror], owner)
        np.testing.assert_array_equal(d[mirror], 1 - d)
        np.testing.assert_array_equal(mirror[mirror], np.arange(entries))

    def test_the_block_of_consecutive_items_never_falls(self):
        for name in CASES:
            src, dst, mask, nb = _case(name)
            plan, _, _ = edge_plan.build_edge_plan(src, dst, mask, nb)
            assert (np.diff(plan.item_block) >= 0).all(), name

    @pytest.mark.parametrize("name", CASES + ("hub", "shared_block", "three_by_three"))
    def test_the_real_items_visit_blocks_0_to_plan_blocks_one_step_at_a_time(self, name):
        """What `_max`'s ring of message blocks leans on when it fetches two
        blocks ahead, and what `plan_blocks` counts: the real items' blocks
        start at 0 and rise by at most one, the no-ops repeat the last."""
        src, dst, mask, nb = _GRAPHS[name]() if name in _GRAPHS else _case(name)
        plan, _, items = edge_plan.build_edge_plan(src, dst, mask, nb)
        real = plan.item_block[:items]
        assert real[0] == 0 and set(np.diff(real).tolist()) <= {0, 1}
        assert (plan.item_block[items:] == real[-1]).all()
        assert edge_plan.plan_blocks(plan, items) == len(np.unique(real)) == real[-1] + 1


def _hub_graph():
    """Node 3 is called over 4 x BE + 37 edges and calls one node itself: its
    run of in-edges crosses five edge blocks."""
    n, e = 256, 4 * BE + 37
    rng = np.random.default_rng(6)
    src = np.concatenate([rng.integers(4, n, e), [3]]).astype(np.int32)
    dst = np.concatenate([np.full(e, 3), [200]]).astype(np.int32)
    return src, dst, np.ones(e + 1, bool), n


def _shared_block_graph():
    """Eight tiles, 400 entries: every tile's item meets the one edge block,
    and the fourth tile (rows 384..511) holds no edge at all."""
    nb, e, eb = 8 * TN, 200, 256
    rng = np.random.default_rng(13)
    live = np.concatenate([np.arange(0, 3 * TN), np.arange(4 * TN, nb)])
    src, dst = (rng.choice(live, e).astype(np.int32) for _ in range(2))
    pad = np.zeros(eb - e, np.int32)
    mask = np.concatenate([np.ones(e, bool), np.zeros(eb - e, bool)])
    return np.concatenate([src, pad]), np.concatenate([dst, pad]), mask, nb


def _three_by_three_graph():
    """Node 5 calls 2 x BE + 100 nodes, so the first tile's out-entries span
    three edge blocks; the handful of entries of the other three tiles all
    lie in the third block, which four tiles' items meet."""
    nb, heavy = 4 * TN, 2 * BE + 100
    rng = np.random.default_rng(14)
    src = np.concatenate([np.full(heavy, 5), rng.integers(TN, nb, 20)]).astype(np.int32)
    dst = np.concatenate([rng.integers(0, TN, heavy), rng.integers(TN, nb, 20)]).astype(np.int32)
    pad = np.zeros(2048 - src.shape[0], np.int32)
    mask = np.concatenate([np.ones(src.shape[0], bool), np.zeros(pad.shape[0], bool)])
    return np.concatenate([src, pad]), np.concatenate([dst, pad]), mask, nb


_GRAPHS = {"hub": _hub_graph, "shared_block": _shared_block_graph, "three_by_three": _three_by_three_graph}

#: sha256 (first 16 hex digits) of the bytes of `planned_attention`'s value and
#: of its gradients to hw, s and t, interpreted kernels on the CPU. Those of
#: the value and of d hw are PR 34's (its final tree, parent commit 014ad83:
#: the weighted sums multiply on the VPU and go through the one-hot in three
#: passes) and reproduce: no dot product feeds them (a mirror's alpha is
#: recomputed, not read). Those of d s and d t stood from PR 30 (89811fd) to
#: PR 37 and are PR 38's now, recorded on its final tree (parent commit
#: 4a46e2e): `d alpha = <g[i], hw[j]>` and the mirror's `<hw[i], g[j]>` are
#: float32 sums of float32 products, by halves (`walk.entry_dot`), where `_dot6`
#: kept a product to 2^-24 of the sum; `alpha`, `c` and `walk.reduce` as before
_DIGESTS = {
    "hub": ("aebc210415e35939", "c5139565ff963696", "6dbcbb9687956b8f", "bb48a2701f031b4a"),
    "shared_block": ("8e60f886afeae48d", "583ee12ca7e2e9fe", "4a6926adbe225e42", "cc246e6e1cc11a2c"),
}
_DIGEST_SEEDS = {"hub": 21, "shared_block": 22}


def _direct(graph, seed, impl, width=WIDTH):
    """(value, d hw, d s, d t) of `planned_attention` itself on seeded inputs."""
    src, dst, mask, nb = graph
    plan = _device(edge_plan.build_edge_plan(src, dst, mask, nb)[0])
    rng = np.random.default_rng(seed)
    hw, ct = (jnp.asarray(rng.normal(size=(nb, width)).astype(np.float32)) for _ in range(2))
    s, t = (jnp.asarray(rng.normal(size=(nb, 2)).astype(np.float32)) for _ in range(2))
    out, pull = jax.vjp(lambda h, s_, t_: sparse_gat.planned_attention(plan, h, s_, t_, 0.2, impl), hw, s, t)
    return [np.asarray(x) for x in (out, *pull(ct))]


def _float64_value_and_d_hw(graph, seed, width=WIDTH):
    """`_direct`'s value and d hw in float64 numpy, entry by entry: the
    softmax of each (owner, direction) run, `out[i] = sum alpha_e hw[j]` and
    `d hw[j] = sum alpha_e ct[i]` over the entries (i, j, d)."""
    src, dst, mask, nb = graph
    plan, entries, _ = edge_plan.build_edge_plan(src, dst, mask, nb)
    rng = np.random.default_rng(seed)
    hw, ct = (rng.normal(size=(nb, width)).astype(np.float32).astype(np.float64) for _ in range(2))
    s, t = (rng.normal(size=(nb, 2)).astype(np.float32).astype(np.float64) for _ in range(2))
    own, nbr, d = plan.owner[0, :entries], plan.neighbour[:entries], plan.direction[0, :entries]
    z = s[nbr, d] + t[own, d]
    score = np.where(z >= 0, z, np.float64(np.float32(0.2)) * z)
    run = own.astype(np.int64) * 2 + d
    top = np.full(2 * nb, -np.inf)
    np.maximum.at(top, run, score)
    p = np.exp(np.clip(score - top[run], -60.0, 0.0))
    total = np.zeros(2 * nb)
    np.add.at(total, run, p)
    alpha = p / total[run]
    out, d_hw = np.zeros((nb, width)), np.zeros((nb, width))
    np.add.at(out, own, alpha[:, None] * hw[nbr])
    np.add.at(d_hw, nbr, alpha[:, None] * ct[own])
    return out, d_hw


class TestTheKernelsKeepTheirBits:
    @pytest.mark.parametrize("name", sorted(_DIGESTS))
    def test_value_and_gradients_reproduce_the_digests_recorded_on_the_parent(self, name):
        got = _direct(_GRAPHS[name](), _DIGEST_SEEDS[name], "pallas_interpret")
        digests = tuple(hashlib.sha256(x.tobytes()).hexdigest()[:16] for x in got)
        assert digests == _DIGESTS[name]

    @pytest.mark.parametrize("name", ("hub", "shared_block", "three_by_three", "zipf_heavy"))
    def test_the_weighted_sums_are_as_close_to_a_float64_sum_as_the_oracles(self, name):
        """The value and d hw, whose products are float32 multiplications on
        the VPU summed through the one-hot: against the same sums in float64
        they are no further off than `_attention_xla`'s (largest and root
        mean square), and the two agree. The oracle adds a run's terms one
        after another, so over the 1,124 in-entries of `three_by_three`'s hub
        its own d hw is 2.5e-6 off: hence 5e-6 between the two there."""
        graph = _GRAPHS[name]() if name in _GRAPHS else _case(name)
        seed = 24
        kernel, xla = (_direct(graph, seed, impl) for impl in ("pallas_interpret", "xla"))
        exact = _float64_value_and_d_hw(graph, seed)
        for k, x, want, what, agree in zip(kernel, xla, exact, ("value", "d hw"), (2e-6, 5e-6)):
            _close(k, want, 2e-6, what)
            _close(k, x, agree, what)
            off_kernel, off_xla = (np.abs(a.astype(np.float64) - want) for a in (k, x))
            assert off_kernel.max() <= off_xla.max(), what
            assert np.sqrt((off_kernel**2).mean()) <= np.sqrt((off_xla**2).mean()), what

    def test_a_block_met_by_three_tiles_and_a_tile_that_meets_three_blocks(self):
        graph = _three_by_three_graph()
        plan, _, items = edge_plan.build_edge_plan(*graph)
        tiles, blocks = plan.item_tile[:items], plan.item_block[:items]
        assert np.bincount(tiles).max() >= 3  # a tile by items of three blocks
        assert np.bincount(blocks).max() >= 3  # a block by items of three tiles
        got, want = (_direct(graph, 23, impl) for impl in ("pallas_interpret", "xla"))
        for g, w, what in zip(got, want, ("value", "d hw", "d s", "d t")):
            _close(g, w, 5e-5, what)

    def test_the_saved_residual_holds_the_messages_once_and_one_state_of_the_entries(self):
        """Backward gets the float32 messages once (no split copy beside
        them: the pieces are made where they are used) and ONE [8, entries]
        state with z and alpha in it, not an array a scalar."""
        src, dst, mask, nb, plan = _plan("masked")
        hw, _, _ = _inputs(nb)
        s = t = jnp.zeros((nb, 2), jnp.float32)
        _, saved = sparse_gat._attention_pallas_fwd(plan, hw, s, t, 0.2, True)
        entries = plan.neighbour.shape[0]
        per_entry = {a.shape: a for a in saved if entries in a.shape}
        assert sorted(per_entry) == [(walk.ATT_ROWS, entries), (entries, 128)]
        assert all(a.dtype == jnp.float32 for a in per_entry.values())
        assert len([a for a in saved if entries in a.shape]) == 2
        state = np.asarray(per_entry[(walk.ATT_ROWS, entries)])
        real = 2 * int(mask.sum())
        # the blocks an item visits (no walk writes the others, none reads them)
        seen = (int(np.asarray(plan.item_block).max()) + 1) * BE
        state = state[:, :seen]
        # the plan's owner and direction ride in it as bits, untouched by three walks
        np.testing.assert_array_equal(state[walk.ROW_OWNER].view(np.int32), np.asarray(plan.owner)[0, :seen])
        np.testing.assert_array_equal(state[walk.ROW_DIR].view(np.int32), np.asarray(plan.direction)[0, :seen])
        assert (state[walk.ROW_ALPHA, :real] > 0).all() and (state[walk.ROW_ALPHA, real:] == 0).all()
        assert (state[walk.ROW_DALPHA] == 0).all()  # the backward pass's row


def _item_dots(host_plan, items, owner_table, entry_rows_t_of):
    """What a walk's items make of `walk.entry_dot`, item by item as a
    kernel calls it: the tile's rows of the `[nodes, R]` owner table
    transposed and expanded through the item's one-hot in bfloat16, against
    the block's rows transposed (`entry_rows_t_of(block, d)`, `[R, block]`). Returns the entries' sums
    over the items `[L]` and every item's own `[1, block]` row with the mask of
    the entries its tile owns."""
    owner, d = np.asarray(host_plan.owner), np.asarray(host_plan.direction)
    dot = jax.jit(lambda owner_rows_t, entry_rows_t, hot: walk.entry_dot(walk.expand(owner_rows_t, hot), entry_rows_t))
    total, per_item = np.zeros(owner.shape[1], np.float32), []
    for i in range(items):
        tile, block = int(host_plan.item_tile[i]), int(host_plan.item_block[i])
        at = slice(block * BE, (block + 1) * BE)
        mine = owner[0, at][None, :] - tile * TN == np.arange(TN)[:, None]  # [tile, block]
        row = np.asarray(dot(
            jnp.asarray(owner_table[tile * TN : (tile + 1) * TN]).T,
            entry_rows_t_of(block, jnp.asarray(d[:, at])),
            jnp.asarray(mine).astype(jnp.bfloat16),
        ))
        total[at] += row[0]
        per_item.append((row, mine.any(axis=0)))
    return total, per_item


def _blocks_transposed(rows):
    """`entry_rows_t_of` of `_item_dots` for rows a walk takes as they were gathered, `[L, lanes]`."""
    rows = np.asarray(rows)
    return lambda block, _d: jnp.asarray(rows[block * BE : (block + 1) * BE]).T


def _dot_is_as_close_as_the_oracles(got, oracle, owner_rows, entry_rows):
    """A walk's per-entry dot products `[E]` against the float64 sum of the
    same float32 operands `[E, R]`: the root mean square no further off than
    the oracle's float32 `sum(a * b, axis=1)`, and every entry, the largest
    with them, inside the bound of the sum's own shape: one rounding of the
    product, four halvings of 128 rows and seven adds over a vreg's eight
    sublanes, 2^-24 each, of the sum of the terms' sizes. The LARGEST is not
    held to the oracle's largest: the two are float32 sums of the same
    products in different orders, and which holds the worst of a few thousand
    entries turns with the seed (PERF.md, PR 38, has the readings)."""
    a, b = owner_rows.astype(np.float64), entry_rows.astype(np.float64)
    want = (a * b).sum(axis=1)
    off, off_oracle = np.abs(got.astype(np.float64) - want), np.abs(oracle.astype(np.float64) - want)
    assert np.sqrt((off**2).mean()) <= np.sqrt((off_oracle**2).mean())
    assert (off <= 12 * 2.0**-24 * np.abs(a * b).sum(axis=1) + 1e-30).all()


_DOT_GRAPHS = ("hub", "shared_block", "three_by_three", "zipf_heavy")


class TestTheEntryDot:
    """PR 38: a walk's per-entry dot product is the owner's row expanded
    through the one-hot (one stacked MXU product, exact) times the entry's row
    on the VPU, summed over the sublanes in float32, where `_dot6` made all
    tile x block products in six passes and the one-hot picked."""

    @staticmethod
    def _operands(name, seed=26, width=64):
        graph = _GRAPHS[name]() if name in _GRAPHS else _case(name)
        host, entries, items = edge_plan.build_edge_plan(*graph)
        plan, nb = _device(host), graph[3]
        rng = np.random.default_rng(seed)
        hw, g = (jnp.asarray(rng.normal(size=(nb, width)).astype(np.float32)) for _ in range(2))
        scalars = [jnp.asarray(rng.normal(size=(nb, 2)).astype(np.float32)) for _ in range(4)]
        nodes, lanes = sparse_gat._attention_shapes(plan, hw)
        return host, entries, items, plan, hw, g, scalars, nodes, lanes

    @pytest.mark.parametrize("name", _DOT_GRAPHS)
    def test_edge_dots_product_of_g_of_the_owner_and_hw_of_the_neighbour(self, name):
        host, entries, items, plan, hw, g, scalars, nodes, lanes = self._operands(name)
        msg = walk.gather_rows(walk.node_table(nodes, lanes, hw, scalars[0]), plan.neighbour)
        got, _ = _item_dots(host, items, np.asarray(walk.node_table(nodes, lanes, g)), _blocks_transposed(msg))
        own, nbr = host.owner[0, :entries], host.neighbour[:entries]
        oracle = np.asarray((g[own] * hw[nbr]).sum(axis=1))
        _dot_is_as_close_as_the_oracles(got[:entries], oracle, np.asarray(g)[own], np.asarray(hw)[nbr])
        assert not got[entries:].any()

    @pytest.mark.parametrize("name", _DOT_GRAPHS)
    def test_backwards_product_of_hw_of_the_owner_and_g_of_the_neighbour(self, name):
        """The block's rows are `[g | t, max, sum, c]` of the neighbour: the
        lanes past the width meet zeros of the owner's table."""
        host, entries, items, plan, hw, g, scalars, nodes, lanes = self._operands(name, seed=27)
        msg = walk.gather_rows(walk.node_table(nodes, lanes, g, *scalars), plan.neighbour)
        got, _ = _item_dots(host, items, np.asarray(walk.node_table(nodes, lanes, hw)), _blocks_transposed(msg))
        own, nbr = host.owner[0, :entries], host.neighbour[:entries]
        oracle = np.asarray((hw[own] * g[nbr]).sum(axis=1))
        _dot_is_as_close_as_the_oracles(got[:entries], oracle, np.asarray(hw)[own], np.asarray(g)[nbr])

    def test_the_walk_writes_what_its_items_make_bit_for_bit(self):
        """`planned_attention_edge_dot`, interpreted, leaves each entry's dot
        product in its row of the state: the items' own, a block met by four
        tiles (`three_by_three`) summed from one product and three zeros."""
        host, entries, items, plan, hw, g, scalars, nodes, lanes = self._operands("three_by_three", width=WIDTH)
        _, saved = sparse_gat._attention_pallas_fwd(plan, hw, scalars[0], scalars[1], 0.2, True)
        msg, state = saved[3], saved[4]
        state, _c = walk.walk_call(
            plan, sparse_gat.attention_edge_dot_kernel, "planned_attention_edge_dot",
            [("entry", state), ("message", msg), ("node_rows", walk.node_table(nodes, lanes, g).T)],
            [("entry", walk.ATT_ROWS), ("node_rows", walk.ATT_ROWS)], True,
        )
        got, _ = _item_dots(host, items, np.asarray(walk.node_table(nodes, lanes, g)), _blocks_transposed(msg))
        np.testing.assert_array_equal(np.asarray(state)[walk.ROW_DALPHA, :entries], got[:entries])

    @pytest.mark.parametrize("name", ("shared_block", "three_by_three"))
    def test_an_entry_of_another_tile_dots_to_exactly_zero_and_two_runs_give_the_same_bits(self, name):
        host, entries, items, plan, hw, g, scalars, nodes, lanes = self._operands(name, seed=28)
        msg = walk.gather_rows(walk.node_table(nodes, lanes, hw, scalars[0]), plan.neighbour)
        table = np.asarray(walk.node_table(nodes, lanes, g))
        (first, per_item), (second, _) = (_item_dots(host, items, table, _blocks_transposed(msg)) for _ in range(2))
        np.testing.assert_array_equal(first.view(np.int32), second.view(np.int32))
        strangers = 0
        for row, mine in per_item:
            assert not row[0, ~mine].view(np.int32).any()  # +0.0, not a small number and not -0.0
            strangers += int((~mine).sum())
        assert strangers > BE  # the blocks several tiles meet hold entries of each of them


class TestPlannedAttention:
    @pytest.mark.parametrize("impl", IMPLS)
    @pytest.mark.parametrize("name", CASES)
    def test_values_against_the_unsorted_formulation(self, name, impl):
        src, dst, mask, nb, plan = _plan(name)
        hw, vectors, _ = _inputs(nb)
        got = _planned(hw, vectors, plan, impl)
        want = _unsorted(hw, vectors, src, dst, mask)
        assert got.shape == want.shape and got.dtype == want.dtype
        _close(got, want, 1e-5)
        # a node with no edge at all receives exact zeros
        np.testing.assert_array_equal(np.asarray(got)[np.asarray(plan.degree) == 0], 0.0)

    @pytest.mark.parametrize("impl", IMPLS)
    @pytest.mark.parametrize(
        "name", ("random", "masked", "padded", "heavier_than_a_block", "tile_with_no_edges", "self_loops_and_repeats")
    )
    def test_gradients_against_autodiff_of_the_unsorted_formulation(self, name, impl):
        src, dst, mask, nb, plan = _plan(name)
        hw, vectors, ct = _inputs(nb, seed=2)
        _, pull = jax.vjp(lambda h, a: _planned(h, a, plan, impl), hw, vectors)
        _, pull_unsorted = jax.vjp(lambda h, a: _unsorted(h, a, src, dst, mask), hw, vectors)
        got, want = pull(ct), pull_unsorted(ct)
        # a heavy owner sums 1,200 terms in another order than the scatters do
        _close(got[0], want[0], 5e-5, "d hw")
        for g, w, which in zip(got[1], want[1], ("a_s", "a_d", "a_sr", "a_dr")):
            _close(g, w, 5e-5, f"d {which}")

    def test_each_run_is_a_softmax_whatever_the_scores(self):
        """With every row of hw equal to ones, a node's output is the number
        of its directions that hold an edge: each run's weights sum to one."""
        src, dst, mask, nb, plan = _plan("masked")
        rng = np.random.default_rng(3)
        s = jnp.asarray(rng.normal(size=(nb, 2)).astype(np.float32) * 30.0)  # peaked and flat runs
        t = jnp.asarray(rng.normal(size=(nb, 2)).astype(np.float32))
        has_out = np.bincount(src[mask], minlength=nb) > 0
        has_in = np.bincount(dst[mask], minlength=nb) > 0
        want = (has_out.astype(np.float32) + has_in.astype(np.float32))[:, None] * np.ones((1, 4), np.float32)
        for impl in IMPLS:
            got = sparse_gat.planned_attention(plan, jnp.ones((nb, 4), jnp.float32), s, t, 0.2, impl)
            np.testing.assert_allclose(np.asarray(got), want, rtol=0, atol=2e-6)

    @pytest.mark.parametrize("impl", IMPLS)
    def test_a_node_with_no_edge_in_one_direction(self, impl):
        # 0 -> 1, 0 -> 2, 3 -> 1: node 0 has no in-edge, nodes 1 and 2 no out-edge
        src = np.array([0, 0, 3, 0], np.int32)
        dst = np.array([1, 2, 1, 0], np.int32)
        mask = np.array([True, True, True, False])
        plan = _device(edge_plan.build_edge_plan(src, dst, mask, 8)[0])
        hw, vectors, ct = _inputs(8, seed=4)
        got, pull = jax.vjp(lambda h, a: _planned(h, a, plan, impl), hw, vectors)
        want, pull_unsorted = jax.vjp(lambda h, a: _unsorted(h, a, src, dst, mask), hw, vectors)
        _close(got, want, 1e-6)
        np.testing.assert_allclose(np.asarray(got[2]), np.asarray(hw[0]), rtol=1e-6)  # one in-edge: alpha = 1
        for g, w in zip(jax.tree_util.tree_leaves(pull(ct)), jax.tree_util.tree_leaves(pull_unsorted(ct))):
            assert np.isfinite(np.asarray(g)).all()
            _close(g, w, 1e-5)

    @pytest.mark.parametrize("impl", IMPLS)
    def test_a_run_of_masked_edges_only_gives_no_nan_in_the_gradient(self, impl):
        """Every edge into node 5 is masked, and the bucket's padding is
        clamped onto the last node: the unsorted softmax needs its clip for
        this; on the plan such a run holds no entry at all."""
        src = np.array([1, 2, 3, 0, 0, 0, 0, 0], np.int32)
        dst = np.array([5, 5, 5, 1, 0, 0, 0, 0], np.int32)
        mask = np.array([False, False, False, True, False, False, False, False])
        plan = _device(edge_plan.build_edge_plan(src, dst, mask, 8)[0])
        hw, vectors, ct = _inputs(8, seed=5)
        got, pull = jax.vjp(lambda h, a: _planned(h, a, plan, impl), hw, vectors)
        want, pull_unsorted = jax.vjp(lambda h, a: _unsorted(h, a, src, dst, mask), hw, vectors)
        np.testing.assert_array_equal(np.asarray(got[5]), 0.0)
        for g, w in zip(jax.tree_util.tree_leaves(pull(ct)), jax.tree_util.tree_leaves(pull_unsorted(ct))):
            assert np.isfinite(np.asarray(g)).all()
            _close(g, w, 1e-5)
        _close(got, want, 1e-6)

    def test_a_hub_whose_entries_span_many_edge_blocks(self):
        """Node 3 is called over 4 x BE + 37 edges and calls one node itself:
        its run of in-edges crosses five edge blocks, so its maximum, its sum
        and its weighted sum are carried from item to item."""
        src, dst, mask, n = _hub_graph()
        host, entries, items = edge_plan.build_edge_plan(src, dst, mask, n)
        assert host.degree[3] == mask.shape[0] and items >= 6
        plan = _device(host)
        hw, vectors, ct = _inputs(n, seed=7)
        want, pull_unsorted = jax.vjp(lambda h, a: _unsorted(h, a, src, dst, mask), hw, vectors)
        for impl in IMPLS:
            got, pull = jax.vjp(lambda h, a: _planned(h, a, plan, impl), hw, vectors)
            _close(got, want, 1e-5, impl)
            for g, w in zip(jax.tree_util.tree_leaves(pull(ct)), jax.tree_util.tree_leaves(pull_unsorted(ct))):
                _close(g, w, 5e-5, impl)

    @pytest.mark.parametrize("impl", IMPLS)
    def test_two_runs_give_the_same_bits(self, impl):
        src, dst, mask, nb, plan = _plan("heavier_than_a_block")
        hw, vectors, ct = _inputs(nb, seed=8)
        runs = []
        for _ in range(2):
            out, pull = jax.vjp(lambda h, a: _planned(h, a, plan, impl), hw, vectors)
            runs.append([np.asarray(x) for x in jax.tree_util.tree_leaves((out, pull(ct)))])
        for a, b in zip(*runs):
            np.testing.assert_array_equal(a, b)

    def test_kernels_and_xla_agree_closer_than_either_does_with_the_scatters(self):
        src, dst, mask, nb, plan = _plan("zipf_heavy")
        hw, vectors, _ = _inputs(nb, seed=9)
        a, b = (np.asarray(_planned(hw, vectors, plan, impl)) for impl in IMPLS)
        _close(a, b, 2e-6)

    def test_a_width_past_the_first_128_lanes(self):
        """The neighbour's scalars travel in the lanes past the width: 124
        floats leave no room in the first 128, so the rows take 256."""
        src, dst, mask, nb, plan = _plan("masked")
        hw, vectors, ct = _inputs(nb, seed=10, width=124)
        want, pull_unsorted = jax.vjp(lambda h, a: _unsorted(h, a, src, dst, mask), hw, vectors)
        got, pull = jax.vjp(lambda h, a: _planned(h, a, plan, "pallas_interpret"), hw, vectors)
        _close(got, want, 1e-5)
        for g, w in zip(jax.tree_util.tree_leaves(pull(ct)), jax.tree_util.tree_leaves(pull_unsorted(ct))):
            _close(g, w, 5e-5)

    def test_counts_in_route_stats_beside_planned(self):
        _src, _dst, _mask, nb, plan = _plan("padded")
        assert sparse.route_stats()["attention"] == 0
        sparse_gat.planned_attention(plan, jnp.ones((nb, 4)), jnp.zeros((nb, 2)), jnp.zeros((nb, 2)), 0.2, "xla")
        stats = sparse.route_stats()
        assert stats["attention"] == 1 and stats["planned"] == 1
        sparse_sum.planned_neighbor_sum(plan, jnp.ones((nb, 4)), "xla")
        stats = sparse.route_stats()
        assert stats["attention"] == 1 and stats["planned"] == 2
        sparse.reset_for_tests()
        assert sparse.route_stats()["attention"] == 0

    def test_route_stats_counts_the_mxu_products_of_each_of_the_seven_walks(self):
        """`walk.mxu` calls of each walk's kernel when it was last traced: an
        expand is one, a `walk.reduce` or a weighted sum three (six until PR 34),
        a per-entry dot product ONE since PR 38 (`walk.entry_dot`: the owner's
        rows stacked through the one-hot; `_dot6` spent six), and none more
        where the rows an item expands anyway ride in it (`_backward`'s). The
        three `dot`s of `planned_neighbor_sum`'s kernel are its own and not
        counted."""
        from kmamiz_tpu.ops import sparse_gated

        _src, _dst, _mask, nb, plan = _plan("padded")
        assert sparse.route_stats()["mxu_products"] == {}
        hw, s, t = jnp.ones((nb, 4)), jnp.zeros((nb, 2)), jnp.zeros((nb, 2))
        attention = lambda h, s_, t_: sparse_gat.planned_attention(plan, h, s_, t_, 0.2, "pallas_interpret").sum()  # noqa: E731
        jax.eval_shape(attention, hw, s, t)
        forward = {"planned_attention_max": 1, "planned_attention_softmax": 4, "planned_attention_sum": 4}
        assert sparse.route_stats()["mxu_products"] == forward
        jax.eval_shape(jax.grad(attention, argnums=(0, 1, 2)), hw, s, t)
        gat_walks = {**forward, "planned_attention_edge_dot": 4, "planned_attention_backward": 7}
        assert sparse.route_stats()["mxu_products"] == gat_walks
        gated = lambda q, k, v, b: sparse_gated.planned_gated_sum(plan, q, k, v, b, "pallas_interpret").sum()  # noqa: E731
        jax.eval_shape(jax.grad(gated, argnums=(0, 1, 2, 3)), hw, hw, hw, jnp.zeros((1,)))
        all_seven = {**gat_walks, "planned_gated_sum": 7, "planned_gated_backward": 8}
        assert sparse.route_stats()["mxu_products"] == all_seven
        jax.eval_shape(lambda h: sparse_sum.planned_neighbor_sum(plan, h, "pallas_interpret"), hw)
        sparse_gat.planned_attention(plan, hw, s, t, 0.2, "xla")  # XLA's formulation traces no kernel
        assert sparse.route_stats()["mxu_products"] == all_seven
        sparse.reset_for_tests()
        assert sparse.route_stats()["mxu_products"] == {}

    def test_no_scatter_and_no_1d_gather_over_the_entries_in_the_kernel_path(self):
        """What the TPU runs, lowered here with the kernels interpreted: the
        interpreter's own loops aside, the glue XLA is left with holds two
        row gathers a layer pass and nothing indexed entry by entry."""
        _src, _dst, _mask, nb, plan = _plan("random")
        hw, vectors, _ = _inputs(nb)
        entries = plan.neighbour.shape[0]

        def glue_only(plan_, _kernel, _name, inputs, outputs, _interpret):
            """A walk's outputs without its body, so that only XLA's glue lowers."""
            nodes = edge_plan.plan_tiles(plan_) * TN
            shapes = {"entry": (None, entries), "node_rows": (None, nodes), "node": (nodes, None)}
            alive = sum(jnp.sum(a.astype(jnp.float32)) for _kind, a in inputs)
            return [
                jnp.zeros([lanes if dim is None else dim for dim in shapes[kind]], jnp.float32) + alive
                for kind, lanes in outputs
            ]

        real = sparse_gat.walk_call
        sparse_gat.walk_call = glue_only
        try:
            loss = lambda h, a: (_planned(h, a, plan, "pallas_interpret") ** 2).sum()  # noqa: E731
            text = jax.jit(jax.grad(loss, argnums=(0, 1))).lower(hw, vectors).as_text()
        finally:
            sparse_gat.walk_call = real
        assert "scatter" not in text
        gathers = [line for line in text.splitlines() if "gather" in line and "stablehlo.gather" in line]
        assert len(gathers) == 2  # [hw | s] forward, [g | t, max, sum, c] backward
        for line in gathers:
            assert f"tensor<{entries}x128xf32>" in line, line


def _gat_params(num_features, hidden=8, num_nodes=0, seed=0):
    return gat.init_params(
        jax.random.PRNGKey(seed), hidden=hidden, num_features=num_features, num_nodes=num_nodes
    )


def _as_reference_params(params):
    return {k: v for k, v in params._asdict().items() if v is not None}


class TestGatForwardWithAPlan:
    @pytest.mark.parametrize("plan_reducer", IMPLS, indirect=True)
    @pytest.mark.parametrize("embeddings", (False, True), ids=("features", "embeddings"))
    def test_loss_and_gradient_match_the_forward_without_a_plan(self, plan_reducer, embeddings):
        src, dst, mask, nb, plan = _plan("masked")
        rng = np.random.default_rng(11)
        x = jnp.asarray(rng.normal(size=(nb, 18)).astype(np.float32))
        tl = jnp.asarray(rng.normal(size=nb).astype(np.float32))
        ta = jnp.asarray((rng.random(nb) < 0.2).astype(np.float32))
        nm = jnp.asarray(rng.random(nb) < 0.9)
        params = _gat_params(18, num_nodes=nb if embeddings else 0)
        args = (x, jnp.asarray(src), jnp.asarray(dst), jnp.asarray(mask), tl, ta, nm)
        want, want_grad = jax.value_and_grad(gat.loss_fn, has_aux=True)(params, *args)

        planned = common.make_loss_fn(partial(gat.forward, plan=plan))
        got, got_grad = jax.value_and_grad(planned, has_aux=True)(params, *args)
        assert sparse.route_stats()["attention"] == 2  # both layers, at trace time
        np.testing.assert_allclose(np.asarray(got[0]), np.asarray(want[0]), rtol=2e-6)
        for name, a, b in zip(params._fields, got_grad, want_grad):
            if a is None:
                assert b is None
                continue
            np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-4, atol=2e-6, err_msg=name)

    @pytest.mark.parametrize("plan_reducer", IMPLS, indirect=True)
    def test_values_and_gradients_of_every_parameter_against_the_plain_reference(self, plan_reducer):
        """`benchmarks/reference/gat.py` knows no mask and no padding: it is
        given the real edges, the system the bucket-padded list and its plan."""
        src, dst, mask, nb, plan = _plan("masked")
        rng = np.random.default_rng(12)
        x = jnp.asarray(rng.normal(size=(nb, 18)).astype(np.float32))
        w_lat = jnp.asarray(rng.normal(size=nb).astype(np.float32))
        w_logit = jnp.asarray(rng.normal(size=nb).astype(np.float32))
        params = _gat_params(18, hidden=16, seed=3)
        # move the zero-initialised leaves off zero, so their gradients are exercised
        params = params._replace(
            b_1=params.b_1 + 0.1, b_2=params.b_2 - 0.1,
            w_latency_skip=params.w_latency_skip + 0.05, w_anomaly_skip=params.w_anomaly_skip - 0.05,
        )
        real_src, real_dst = jnp.asarray(src[mask]), jnp.asarray(dst[mask])

        def system(p):
            lat, logit = gat.forward(p, x, jnp.asarray(src), jnp.asarray(dst), jnp.asarray(mask), plan=plan)
            return jnp.sum(lat * w_lat) + jnp.sum(logit * w_logit), (lat, logit)

        def reference(p):
            lat, logit = reference_gat.forward(p, x, real_src, real_dst)
            return jnp.sum(lat * w_lat) + jnp.sum(logit * w_logit), (lat, logit)

        (_, got_out), got_grad = jax.value_and_grad(system, has_aux=True)(params)
        (_, want_out), want_grad = jax.value_and_grad(reference, has_aux=True)(_as_reference_params(params))
        for g, w in zip(got_out, want_out):
            _close(g, w, 2e-6)
        for name in want_grad:
            _close(getattr(got_grad, name), want_grad[name], 2e-5, name)

    def test_no_plan_keeps_todays_formulation(self):
        src, dst, mask, nb = _case("random")
        x = jnp.ones((nb, 10), jnp.float32)
        gat.forward(_gat_params(10), x, jnp.asarray(src), jnp.asarray(dst), jnp.asarray(mask))
        stats = sparse.route_stats()
        assert stats["planned"] == stats["attention"] == 0


class TestGatTrainingThroughThePlan:
    @pytest.mark.parametrize("plan_reducer", IMPLS, indirect=True)
    def test_epoch_block_with_a_plan_matches_the_per_slot_loop_over_three_epochs(self, plan_reducer):
        ds = _dataset()
        legacy = trainer.train(ds, epochs=3, hidden=8, seed=0, fused=False, model=gat)
        assert sparse.route_stats()["attention"] == 0
        fused = trainer.train(ds, epochs=3, hidden=8, seed=0, fused=True, model=gat)
        assert sparse.route_stats()["attention"] > 0
        np.testing.assert_allclose(fused.losses, legacy.losses, rtol=1e-4, atol=1e-5)
        for a, b in zip(
            jax.tree_util.tree_leaves(fused.params), jax.tree_util.tree_leaves(legacy.params)
        ):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-3, atol=1e-5)

    def test_the_plan_span_counts_the_entries_of_each_direction_and_the_runs(self):
        ds = _dataset()
        st = stacked.stack_dataset(ds)
        real = np.asarray(ds.edge_mask)
        src, dst = np.asarray(ds.src)[real], np.asarray(ds.dst)[real]
        runs = len(set(src.tolist())) + len(set(dst.tolist()))
        assert st.plan_runs == runs and st.plan_entries == 2 * int(real.sum())
        assert st.plan_blocks == edge_plan.plan_blocks(st.plan, st.plan_items) == 1
        noted = {}
        for tb in TRACER.traces():
            for i, span in enumerate(tb.spans):
                if span[0] in ("refresh.stack", "refresh.stack.plan"):
                    noted[span[0]] = dict(tb.counts.get(i, {}))
        plan_counts = noted["refresh.stack.plan"]
        assert plan_counts["entries_out"] == plan_counts["entries_in"] == int(real.sum())
        assert plan_counts["runs"] == runs and noted["refresh.stack"]["plan_runs"] == runs
        assert plan_counts["blocks"] == noted["refresh.stack"]["plan_blocks"] == st.plan_blocks
        assert plan_counts["mxu_products"] == {}  # no walk traced yet in this process

    def test_the_plan_span_carries_the_walks_mxu_products_as_last_traced(self):
        """The count is static, so it is one at trace time: a process that
        has traced walks before it builds a plan finds their products on the
        plan's span, beside the entries they will walk."""
        _src, _dst, _mask, nb, plan = _plan("padded")
        hw, st_ = jnp.ones((nb, 4)), jnp.zeros((nb, 2))
        attention = lambda h: sparse_gat.planned_attention(plan, h, st_, st_, 0.2, "pallas_interpret").sum()  # noqa: E731
        jax.eval_shape(jax.grad(attention), hw)
        stacked.stack_dataset(_dataset())
        noted = [
            dict(tb.counts.get(i, {}))
            for tb in TRACER.traces() for i, span in enumerate(tb.spans) if span[0] == "refresh.stack.plan"
        ]
        assert noted[-1]["mxu_products"] == sparse.route_stats()["mxu_products"] == {
            "planned_attention_max": 1, "planned_attention_softmax": 4, "planned_attention_sum": 4,
            "planned_attention_edge_dot": 4, "planned_attention_backward": 7,
        }
