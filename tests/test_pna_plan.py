"""The PNA head on the stack's edge plan: the planned multi-aggregate
(ops/sparse_pna.py: the XLA oracle and the Mosaic walks, interpreted here)
against float64 segment reductions, ties counted exactly and empty
neighbourhoods included; `pna.forward` with a plan against itself without
one and against the plain reference (benchmarks/reference/pna.py); and `trainer.train(model=pna)`
through `stacked.epoch_runner`'s block: the reference's schedule, a
checkpoint saved and resumed, the span and the checkpoint's name."""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.reference import pna as reference_pna
from benchmarks.reference import train as reference_train
from kmamiz_tpu.models import checkpoint, common, pna, stacked, trainer
from kmamiz_tpu.ops import sparse, sparse_pna
from kmamiz_tpu.telemetry.tracing import TRACER

from test_edge_plan import BE, _case
from test_stlgt_plan import _dataset, _graph, _plan

IMPLS = ("xla", "pallas_interpret")
#: the siblings' three, one whose hub's entries span three edge blocks, and a
#: multigraph of six endpoints (every neighbour many times over: ties certain)
GRAPHS = ("hub_and_isolated", "wide_bucket", "two_tiles", "heavier_than_a_block", "self_loops_and_repeats")


def _topology(name):
    """(src, dst, edge_mask, bucket_nodes) of a graph of either test file, or this one's."""
    if name == "maxima_in_later_blocks":
        return _two_hubs()
    if name in TILES_A_BLOCK:
        return _tiles_a_block(name)
    if name in ("heavier_than_a_block", "self_loops_and_repeats"):
        return _case(name)
    src, dst, mask, _n, nb = _graph(name)
    return src, dst, mask, nb


def _two_hubs():
    """Owner 7's entries span three edge blocks and owner 300's two; 600 endpoints, so that a hub meets a
    neighbour row in one block and seldom again."""
    rng = np.random.default_rng(40)
    n, nb, e, eb = 600, 1024, 1800, 2048
    src, dst = rng.integers(0, n, e), rng.integers(0, n, e)
    src[: 2 * BE + 100] = 7
    src[2 * BE + 100 : 2 * BE + 550] = 300
    pad = eb - e
    return (
        np.concatenate([src, np.zeros(pad, int)]).astype(np.int32),
        np.concatenate([dst, np.zeros(pad, int)]).astype(np.int32),
        np.concatenate([np.ones(e, bool), np.zeros(pad, bool)]),
        nb,
    )


#: plans whose busiest edge block meets this many node tiles: the walks make a block's own work on its first item
#: and every other item of the block reads it (`sparse_pna`'s docstring); a hub's entries across three blocks
TILES_A_BLOCK = {"a_tile_a_block": 1, "two_tiles_a_block": 2, "four_tiles_a_block": 4, "heavier_than_a_block": 1}


def _tiles_a_block(name):
    """(src, dst, edge_mask, bucket_nodes): each block one tile's (three blocks of one tile), the first block two
    tiles', or one block four tiles' (512 owners of degree 1); each bucket wide enough that no-op items follow."""
    if name == "heavier_than_a_block":
        return _case(name)
    rng = np.random.default_rng(44)
    if name == "a_tile_a_block":
        n, nb, e, eb = 120, 128, 600, 1024
        src, dst = rng.integers(0, n, e), rng.integers(0, n, e)
    elif name == "two_tiles_a_block":
        n, nb, e, eb = 256, 256, 400, 512
        src, dst = rng.integers(0, n, e), rng.integers(0, n, e)
    else:  # each owner's one entry: the block holds 512 owners, the four tiles of 128
        n, nb, e, eb = 512, 512, 256, 512
        src, dst = np.arange(0, n, 2), np.arange(1, n, 2)
    pad = eb - e
    return (
        np.concatenate([src, np.zeros(pad, int)]).astype(np.int32),
        np.concatenate([dst, np.zeros(pad, int)]).astype(np.int32),
        np.concatenate([np.ones(e, bool), np.zeros(pad, bool)]),
        nb,
    )


def _maxima_in_later_blocks(host, entries, m):
    """`m` with the two merges of the counted walk planted, each case asserted to hold:
    - owner 7 (three blocks): column 0's maximum 9 in its first AND its last block, the tile's count kept and the
      block's added (`old == new`);
    - owner 300 (two blocks): column 1's maximum 9 only in its second block, where its first holds 5 twice, a
      smaller maximum whose count the tile drops (`top == new` alone); column 2 is column 1 negated, the same
      replacement in the minimum's half."""
    owner, neighbour = host.owner[0, :entries], host.neighbour[:entries]
    block = np.arange(entries) // BE

    def rows_of(o, b):
        return neighbour[(owner == o) & (block == b)]

    hub_blocks, other_blocks = np.unique(block[owner == 7]), np.unique(block[owner == 300])
    assert hub_blocks.size >= 3 and other_blocks.size == 2
    first, later = rows_of(300, other_blocks[0]), rows_of(300, other_blocks[1])
    only_later = np.setdiff1d(later, first)
    m = m.copy()
    m[rows_of(7, hub_blocks[0])[0], 0] = m[rows_of(7, hub_blocks[-1])[-1], 0] = 9.0
    m[np.setdiff1d(first, later)[:2], 1] = 5.0
    m[only_later[0], 1] = 9.0
    m[:, 2] = -m[:, 1]
    rows = m[neighbour]
    assert np.unique(block[(owner == 7) & (rows[:, 0] == 9.0)]).size >= 2  # the maximum in two blocks
    assert rows[(owner == 7), 0].max() == 9.0
    at_top = (owner == 300) & (rows[:, 1] == 9.0)
    assert rows[owner == 300, 1].max() == 9.0 and set(block[at_top]) == {other_blocks[1]}  # in the later block alone
    assert ((owner == 300) & (block == other_blocks[0]) & (rows[:, 1] == 5.0)).sum() >= 2  # the earlier, smaller one
    return m


def _messages(nb, width, seed=0):
    """Message rows in which ties are certain: a column of zeros, a block of
    identical rows, and values on a coarse grid (a maximum shared by chance)."""
    rng = np.random.default_rng(seed)
    m = rng.normal(size=(nb, width)).astype(np.float32)
    m[:, 1] = 0.0
    m[:, 2] = np.round(m[:, 2])
    m[4:12] = m[4]
    weights = [jnp.asarray(rng.normal(size=(nb, width)).astype(np.float32)) for _ in range(4)]
    return jnp.asarray(m), weights


def _float64_aggregates_and_gradient(plan, entries, m, weights):
    """(sum, squares, maximum, minimum) and the gradient of `sum_k (a_k *
    w_k).sum()` to m, in float64 numpy, entry by entry as `sparse_pna`'s
    docstring writes them: a shared extreme splits its gradient equally."""
    m = np.asarray(m).astype(np.float64)
    g_sum, g_sq, g_max, g_min = (np.asarray(w).astype(np.float64) for w in weights)
    o, n = plan.owner[0, :entries], plan.neighbour[:entries]
    rows = m[n]
    held = np.bincount(o, minlength=m.shape[0])[:, None] > 0
    total, squares = np.zeros_like(m), np.zeros_like(m)
    top, bottom = np.full_like(m, -np.inf), np.full_like(m, np.inf)
    np.add.at(total, o, rows)
    np.add.at(squares, o, rows * rows)
    np.maximum.at(top, o, rows)
    np.minimum.at(bottom, o, rows)
    at_top, at_bottom = rows == top[o], rows == bottom[o]
    ties_top, ties_bottom = np.zeros_like(m), np.zeros_like(m)
    np.add.at(ties_top, o, at_top)
    np.add.at(ties_bottom, o, at_bottom)
    grad = np.zeros_like(m)
    np.add.at(
        grad, n,
        g_sum[o] + 2.0 * rows * g_sq[o]
        + at_top * g_max[o] / np.maximum(ties_top[o], 1) + at_bottom * g_min[o] / np.maximum(ties_bottom[o], 1),
    )
    return (total, squares, np.where(held, top, 0.0), np.where(held, bottom, 0.0)), grad, (ties_top, ties_bottom)


def _assert_near_float64(host, entries, m, got, got_grad, want, want_grad):
    """The kernels' four aggregates and gradient against the float64 oracle's: the sums within the roundings they
    make, the extremes to the bit."""
    # a float32 sum of float32 terms: a rounding an entry and three for the pieces, each of what it rounds
    rows, owners = np.abs(np.asarray(m, np.float64))[host.neighbour[:entries]], host.owner[0, :entries]
    for a, c, terms in zip(got[:2], want[:2], (rows, 2 * rows * rows)):  # a square is rounded before it is summed
        size = np.zeros_like(c)
        np.add.at(size, owners, terms)
        assert (np.abs(a - c) <= (np.asarray(host.degree)[:, None] + 3) * 2.0**-24 * size).all()
    for a, c in zip(got[2:], want[2:]):  # an extreme is one of the rows, to the bit; 0 where there is none
        np.testing.assert_array_equal(a, c.astype(np.float32))
    size = np.zeros_like(want_grad)
    np.add.at(size, host.neighbour[:entries], 1.0)
    np.testing.assert_allclose(got_grad, want_grad, rtol=1e-5, atol=1e-5 * (1.0 + size.max()))


def _aggregates_and_gradient(plan, m, weights, impl):
    def weighed(m):
        out = sparse_pna.planned_aggregate(plan, m, impl)
        return sum((a * w).sum() for a, w in zip(out, weights)), out

    (_value, out), grad = jax.jit(jax.value_and_grad(weighed, has_aux=True))(m)
    return [np.asarray(a) for a in out], np.asarray(grad)


class TestPlannedAggregate:
    @pytest.mark.parametrize("impl", IMPLS)
    @pytest.mark.parametrize(
        "name,width",
        [(name, 64) for name in GRAPHS] + [("hub_and_isolated", 8), ("heavier_than_a_block", 40)],
    )
    def test_values_and_the_gradient_against_float64_segment_reductions(self, name, width, impl):
        src, dst, mask, nb = _topology(name)
        host, entries, _items = sparse.build_edge_plan(src, dst, mask, nb)
        m, weights = _messages(nb, width, seed=len(name))
        want, want_grad, (ties_top, _ties_bottom) = _float64_aggregates_and_gradient(host, entries, m, weights)
        assert (ties_top > 1).any()  # the case holds a shared maximum
        got, got_grad = _aggregates_and_gradient(_plan(src, dst, mask, nb), m, weights, impl)
        _assert_near_float64(host, entries, m, got, got_grad, want, want_grad)

    def test_a_hubs_entries_span_several_blocks_and_its_maximum_is_merged(self):
        src, dst, mask, nb = _topology("heavier_than_a_block")
        host, entries, items = sparse.build_edge_plan(src, dst, mask, nb)
        hub_blocks = np.unique(np.flatnonzero(host.owner[0, :entries] == 7) // BE)
        assert hub_blocks.size >= 3 and items > 1
        m = np.zeros((nb, 8), np.float32)
        # the hub's largest and smallest neighbour rows lie in its FIRST block: every later block must keep them
        first = host.neighbour[np.flatnonzero(host.owner[0, :entries] == 7)[:BE]]
        m[first[3]], m[first[5]] = 9.0, -7.0
        out = sparse_pna.planned_aggregate(_plan(src, dst, mask, nb), jnp.asarray(m), "pallas_interpret")
        assert float(out[2][7, 0]) == 9.0 and float(out[3][7, 0]) == -7.0

    @pytest.mark.parametrize("impl", IMPLS)
    def test_an_owner_with_no_entry_reads_zero_and_padding_enters_no_aggregate(self, impl):
        src, dst, mask, n, nb = _graph("hub_and_isolated")
        host, entries, _items = sparse.build_edge_plan(src, dst, mask, nb)
        assert entries < host.owner.shape[1] and not mask.all()  # parked entries and masked edges
        m, weights = _messages(nb, 8)
        m = m.at[0].set(1e6)  # the row every parked entry gathers
        out, grad = _aggregates_and_gradient(_plan(src, dst, mask, nb), m, weights, impl)
        empty = np.asarray(host.degree) == 0
        assert empty[n - 5 : n].all() and empty[n:].all()  # the isolated endpoints and the bucket's padding
        for a in out:
            assert not a[empty].any()
        assert not grad[empty].any()  # nobody's neighbour
        touched = np.zeros(nb, bool)
        touched[host.neighbour[:entries][host.owner[0, :entries] != 0]] = True
        if not touched[0]:  # row 0 is a real neighbour only through its own edges
            assert np.abs(out[2]).max() < 1e6

    def test_the_kernels_and_the_oracle_agree(self):
        src, dst, mask, nb = _topology("two_tiles")
        plan = _plan(src, dst, mask, nb)
        m, weights = _messages(nb, 64, seed=3)
        xla, xla_grad = _aggregates_and_gradient(plan, m, weights, "xla")
        kernels, kernels_grad = _aggregates_and_gradient(plan, m, weights, "pallas_interpret")
        for a, c in zip(kernels[:2], xla[:2]):
            np.testing.assert_allclose(a, c, rtol=1e-5, atol=1e-5)
        for a, c in zip(kernels[2:], xla[2:]):
            np.testing.assert_array_equal(a, c)
        np.testing.assert_allclose(kernels_grad, xla_grad, rtol=1e-5, atol=1e-4)

    def test_it_is_counted_as_a_planned_reduction_and_raises_off_the_tpu_as_its_siblings(self):
        src, dst, mask, nb = _topology("wide_bucket")
        plan = _plan(src, dst, mask, nb)
        m, _weights = _messages(nb, 8)
        sparse_pna.planned_aggregate(plan, m)
        assert sparse.route_stats() == {"backend": "sparse", "planned": 1, "attention": 0, "gatv2": 0, "sharded": 0, "mxu_products": {}, "block_arrays": {}}
        with pytest.raises(Exception):  # Mosaic cannot target a CPU: nothing interprets silently
            jax.block_until_ready(sparse_pna.planned_aggregate(plan, m, "pallas"))
        with pytest.raises(NotImplementedError, match="merge a running maximum across the sources"):
            sparse_pna.planned_aggregate(sparse.ShardPlan(plan, "nodes"), m)

    @pytest.mark.parametrize(
        "differentiated,want",
        [
            (True, {"planned_aggregate_counted": 9, "planned_aggregate_backward": 4}),  # 7, an expand and a count
            (False, {"planned_aggregate": 7}),  # the custom VJP's primal counts no ties
        ],
        ids=("forward_rule", "primal"),
    )
    def test_the_walks_count_their_mxu_products(self, differentiated, want):
        src, dst, mask, nb = _topology("wide_bucket")
        plan = _plan(src, dst, mask, nb)
        m, weights = _messages(nb, 8)
        if differentiated:
            _aggregates_and_gradient(plan, m, weights, "pallas_interpret")
        else:
            jax.jit(partial(sparse_pna.planned_aggregate, impl="pallas_interpret"))(plan, m)
        assert sparse.route_stats()["mxu_products"] == want

    @pytest.mark.parametrize("name", ("maxima_in_later_blocks",) + GRAPHS)
    def test_the_forward_rule_counts_the_ties_of_float64_exactly(self, name):
        """The ties the counted forward walk saves for the mirror walk, merged block by block and tile by tile,
        against the float64 count over the owner's whole run of entries; and the gradient they divide, against
        the float64 oracle's."""
        src, dst, mask, nb = _topology(name)
        host, entries, _items = sparse.build_edge_plan(src, dst, mask, nb)
        m, weights = _messages(nb, 8, seed=len(name))
        if name == "maxima_in_later_blocks":
            m = _maxima_in_later_blocks(host, entries, np.asarray(m))
        _want, want_grad, (ties_top, ties_bottom) = _float64_aggregates_and_gradient(host, entries, m, weights)
        forward_rule = jax.jit(sparse_pna._aggregate_pallas_fwd_rule, static_argnums=2)
        _out, (_plan_saved, (_m, _tops, ties)) = forward_rule(_plan(src, dst, mask, nb), jnp.asarray(m), True)
        ties, half = np.asarray(ties), np.asarray(ties).shape[0] // 2
        np.testing.assert_array_equal(ties[:8, :nb].T, ties_top)
        np.testing.assert_array_equal(ties[half : half + 8, :nb].T, ties_bottom)
        _got, got_grad = _aggregates_and_gradient(_plan(src, dst, mask, nb), jnp.asarray(m), weights, "pallas_interpret")
        size = np.zeros_like(want_grad)
        np.add.at(size, host.neighbour[:entries], 1.0)
        np.testing.assert_allclose(got_grad, want_grad, rtol=1e-5, atol=1e-5 * (1.0 + size.max()))


class TestABlocksWorkOnce:
    @pytest.mark.parametrize("name", sorted(TILES_A_BLOCK))
    def test_every_item_of_a_block_reads_the_work_its_first_item_made(self, name):
        """The interpreted walks on plans whose blocks meet one tile, two and four, a hub's entries across blocks,
        no-op items after the last block: the four aggregates, the ties and the gradient against float64 and
        against the XLA oracle."""
        src, dst, mask, nb = _topology(name)
        host, entries, items = sparse.build_edge_plan(src, dst, mask, nb)
        blocks = np.asarray(host.item_block)
        assert np.bincount(blocks[:items]).max() == TILES_A_BLOCK[name]  # the tiles the busiest block meets
        assert items < blocks.size and (blocks[items:] == blocks[items - 1]).all()  # the no-ops repeat the last block
        if name == "heavier_than_a_block":
            assert np.unique(np.flatnonzero(host.owner[0, :entries] == 7) // BE).size >= 3
        m, weights = _messages(nb, 8, seed=len(name))
        want, want_grad, (ties_top, ties_bottom) = _float64_aggregates_and_gradient(host, entries, m, weights)
        plan = _plan(src, dst, mask, nb)
        got, got_grad = _aggregates_and_gradient(plan, m, weights, "pallas_interpret")
        _assert_near_float64(host, entries, m, got, got_grad, want, want_grad)
        xla, xla_grad = _aggregates_and_gradient(plan, m, weights, "xla")
        for a, c in zip(got[:2], xla[:2]):
            np.testing.assert_allclose(a, c, rtol=1e-5, atol=1e-5)
        for a, c in zip(got[2:], xla[2:]):
            np.testing.assert_array_equal(a, c)
        np.testing.assert_allclose(got_grad, xla_grad, rtol=1e-5, atol=1e-4)
        forward_rule = jax.jit(sparse_pna._aggregate_pallas_fwd_rule, static_argnums=2)
        _out, (_plan_saved, (_m, _tops, ties)) = forward_rule(plan, m, True)
        ties, half = np.asarray(ties), np.asarray(ties).shape[0] // 2
        np.testing.assert_array_equal(ties[:8, :nb].T, ties_top)
        np.testing.assert_array_equal(ties[half : half + 8, :nb].T, ties_bottom)

    @pytest.mark.parametrize(
        "differentiated,want",
        [
            # six bfloat16 pieces, the last entries, and for the count [m | -m]; the backward walk keeps nothing
            (True, {"planned_aggregate_counted": 8}),
            (False, {"planned_aggregate": 7}),
        ],
        ids=("forward_rule", "primal"),
    )
    def test_route_stats_names_the_walks_that_keep_a_blocks_work(self, differentiated, want):
        src, dst, mask, nb = _topology("wide_bucket")
        plan = _plan(src, dst, mask, nb)
        m, weights = _messages(nb, 8)
        assert sparse.route_stats()["block_arrays"] == {}
        if differentiated:
            _aggregates_and_gradient(plan, m, weights, "pallas_interpret")
        else:
            jax.jit(partial(sparse_pna.planned_aggregate, impl="pallas_interpret"))(plan, m)
        assert sparse.route_stats()["block_arrays"] == want

    def test_the_other_walks_keep_nothing(self):
        src, dst, mask, nb = _topology("wide_bucket")
        plan = _plan(src, dst, mask, nb)
        h = jnp.ones((nb, 8))
        jax.grad(lambda h: sparse.planned_neighbor_sum(plan, h, "pallas_interpret").sum())(h)
        jax.grad(lambda h: sparse.planned_attention(plan, h, h[:, :2], h[:, :2], 0.2, "pallas_interpret").sum())(h)
        assert sparse.route_stats()["mxu_products"] and sparse.route_stats()["block_arrays"] == {}

    @pytest.mark.parametrize("n_nodes,share", ((100, 0.0), (150, 0.25)))
    def test_the_plan_span_notes_the_share_of_items_that_find_their_blocks_work_made(self, n_nodes, share):
        """(items - blocks) / items: 0 where every block meets one tile (one tile of 128 here), a quarter where
        two of three blocks meet one tile and the third two (two tiles of 128)."""
        st = stacked.stack_dataset(_dataset(n_nodes=n_nodes, n_edges=4 * n_nodes))
        blocks = np.asarray(st.plan.item_block)[: st.plan_items]
        noted = [
            dict(tb.counts.get(i, {}))
            for tb in TRACER.traces() for i, span in enumerate(tb.spans) if span[0] == "refresh.stack.plan"
        ][-1]
        assert noted["items"] == st.plan_items and noted["blocks"] == np.unique(blocks).size == st.plan_blocks
        assert noted["block_reuse_share"] == pytest.approx((st.plan_items - st.plan_blocks) / st.plan_items) == share


class TestThePlansDelta:
    @pytest.mark.parametrize("name", GRAPHS)
    def test_it_is_the_mean_log_degree_of_the_endpoints_that_have_a_neighbour(self, name):
        src, dst, mask, nb = _topology(name)
        host = sparse.build_edge_plan(src, dst, mask, nb)[0]
        degree = np.asarray(host.degree, np.float64)
        want = np.log1p(degree[degree > 0]).mean()
        assert float(host.mean_log_degree) == pytest.approx(want, rel=1e-6)
        assert float(sparse.mean_log_degree(host.degree)) == pytest.approx(want, rel=1e-6)

    def test_a_graph_without_an_edge_scales_by_one(self):
        src, dst, mask, nb = _case("empty")
        assert float(sparse.build_edge_plan(src, dst, mask, nb)[0].mean_log_degree) == 1.0
        assert float(sparse.mean_log_degree(np.zeros(8))) == 1.0

    def test_the_rows_that_pad_a_node_bucket_do_not_move_a_forecast(self):
        """The served forward (models/serving.py) and the batched evaluation pad the nodes to a bucket and hand no
        plan: the scaler's constant is the graph's, so the real rows' predictions are the unpadded graph's."""
        ds = _dataset(n_slots=1)
        n = ds.num_nodes
        params = pna.init_params(jax.random.PRNGKey(2), hidden=8, num_features=18)
        graph = (jnp.asarray(ds.src), jnp.asarray(ds.dst), jnp.asarray(ds.edge_mask))
        bare = pna.forward(params, jnp.asarray(ds.features[0]), *graph)
        padded = pna.forward(params, jnp.pad(jnp.asarray(ds.features[0]), ((0, 256 - n), (0, 0))), *graph)
        for a, c in zip(padded, bare):
            np.testing.assert_allclose(np.asarray(a)[:n], np.asarray(c), rtol=1e-5, atol=1e-6)


def _off_zero(params):
    """The zero-initialised leaves off zero, so their gradients are exercised."""
    return params._replace(
        b_1=params.b_1 + 0.1, b_2=params.b_2 - 0.1, w_latency_skip=params.w_latency_skip + 0.05,
        w_anomaly_skip=params.w_anomaly_skip - 0.05,
    )


#: of a leaf's largest gradient. The deviation is `sqrt(relu(E[m^2] - mu^2) + 1e-5)` as the paper and `PNAConv` write
#: it: where an endpoint's neighbours nearly agree the difference cancels (1.5401 - 1.5398 on one endpoint and lane of
#: this toy), its float32 rounding is 4e-4 of what is left, and the root's slope, up to 158, carries that into the
#: gradient: two compilations of ONE formula (eager and jitted XLA on this CPU) differ by 8e-4 of a leaf's largest.
_GRADIENT_ATOL = 2e-3


def _size(gradient) -> float:
    return max(float(np.abs(np.asarray(gradient)).max()), 1e-3)


class TestHeadOnThePlan:
    @pytest.mark.parametrize("plan_reducer", IMPLS, indirect=True)
    def test_loss_and_gradient_of_every_parameter_match_the_forward_without_a_plan(self, plan_reducer):
        ds = _dataset()
        st = stacked.stack_dataset(ds)
        params = _off_zero(pna.init_params(jax.random.PRNGKey(3), hidden=16, num_features=18))
        slot = (st.features[0], st.src, st.dst, st.edge_mask, st.target_latency[0], st.target_anomaly[0], st.node_mask[0])
        want, want_grad = jax.jit(jax.value_and_grad(common.make_loss_fn(pna.forward, 3.0), has_aux=True))(params, *slot)
        planned = stacked.head_loss_fn(pna, 3.0, plan=st.plan)
        got, got_grad = jax.jit(jax.value_and_grad(planned, has_aux=True))(params, *slot)
        assert sparse.route_stats()["planned"] == 2  # a layer each
        np.testing.assert_allclose(np.asarray(got[0]), np.asarray(want[0]), rtol=2e-6)
        for name, a, c in zip(params._fields[:-1], got_grad, want_grad):
            np.testing.assert_allclose(np.asarray(a), np.asarray(c), rtol=2e-4, atol=_GRADIENT_ATOL * _size(c), err_msg=name)

    @pytest.mark.parametrize("plan_reducer", IMPLS, indirect=True)
    @pytest.mark.parametrize("planned", (True, False), ids=("plan", "no_plan"))
    def test_loss_and_gradient_of_every_parameter_match_the_plain_reference(self, plan_reducer, planned):
        """`benchmarks/reference/pna.forward` on the bare graph (no padding, no mask, `jax.ops.segment_*`) under the
        reference's own loss, against the head on the stack's padded arrays."""
        ds = _dataset()
        st = stacked.stack_dataset(ds)
        n = ds.num_nodes
        params = _off_zero(pna.init_params(jax.random.PRNGKey(5), hidden=16, num_features=18))
        slot = (st.features[0], st.src, st.dst, st.edge_mask, st.target_latency[0], st.target_anomaly[0], st.node_mask[0])
        loss = stacked.head_loss_fn(pna, 3.0, **({"plan": st.plan} if planned else {}))
        (got, _aux), got_grad = jax.jit(jax.value_and_grad(loss, has_aux=True))(params, *slot)
        plain = {k: jnp.asarray(v) for k, v in params._asdict().items() if v is not None}
        (want, _aux), want_grad = jax.value_and_grad(reference_train.make_loss(reference_pna.forward, 3.0), has_aux=True)(
            plain, jnp.asarray(ds.features[0]), jnp.asarray(ds.src), jnp.asarray(ds.dst),
            jnp.asarray(ds.target_latency[0]), jnp.asarray(ds.target_anomaly[0]), jnp.asarray(ds.node_mask[0]),
        )
        assert n < st.bucket_nodes
        np.testing.assert_allclose(float(got), float(want), rtol=2e-6)
        for name in plain:
            a, c = np.asarray(getattr(got_grad, name)), np.asarray(want_grad[name])
            np.testing.assert_allclose(a, c, rtol=2e-4, atol=_GRADIENT_ATOL * _size(c), err_msg=name)

    @pytest.mark.parametrize("side", ("program", "reference"))
    def test_every_matrix_product_is_float32_forward_and_backward(self, side):
        """adamw's first update overshoots (the second slot's loss is hundreds of times the first's), so one product
        left at the chip's default, a single bfloat16 pass, parts program and reference by more than the check's bound
        on a seed in forty (PERF.md, PR 39): every `dot_general` of the loss and of its gradient says HIGHEST."""
        ds = _dataset()
        st = stacked.stack_dataset(ds)
        params = pna.init_params(jax.random.PRNGKey(0), hidden=8, num_features=18)
        if side == "program":
            slot = (st.features[0], st.src, st.dst, st.edge_mask, st.target_latency[0], st.target_anomaly[0], st.node_mask[0])
            traced = jax.make_jaxpr(jax.grad(common.make_loss_fn(pna.forward, 3.0), has_aux=True))(params, *slot)
        else:
            plain = {k: v for k, v in params._asdict().items() if v is not None}
            slot = (ds.features[0], ds.src, ds.dst, ds.target_latency[0], ds.target_anomaly[0], ds.node_mask[0])
            loss = reference_train.make_loss(reference_pna.forward, 3.0)
            traced = jax.make_jaxpr(jax.grad(loss, has_aux=True))(plain, *map(jnp.asarray, slot))

        def products(jaxpr):
            for eqn in jaxpr.eqns:
                if eqn.primitive.name == "dot_general":
                    yield eqn.params["precision"]
                for inner in jax.core.jaxprs_in_params(eqn.params):
                    yield from products(inner)

        found = list(products(traced.jaxpr))
        highest = jax.lax.Precision.HIGHEST
        assert len(found) == 10 + 10 + 6  # ten products, their weights' gradients, their inputs' (the features take none)
        assert all(p in (highest, (highest, highest)) for p in found), found

    @pytest.mark.parametrize("planned", (True, False), ids=("plan", "no_plan"))
    def test_no_value_is_as_wide_as_the_updates_concatenated_input(self, planned):
        """The update reads the four aggregates once (`pna._update`): no value of the loss or of its gradient is
        12 x hidden wide, the width of `[a | s a | a / s]`, which the cell's block once made, kept and split as
        [131072, 768] in each layer (PERF.md, section 5)."""
        hidden = 8
        st = stacked.stack_dataset(_dataset())
        params = pna.init_params(jax.random.PRNGKey(0), hidden=hidden, num_features=18)
        slot = (st.features[0], st.src, st.dst, st.edge_mask, st.target_latency[0], st.target_anomaly[0], st.node_mask[0])
        loss = stacked.head_loss_fn(pna, 3.0, **({"plan": st.plan} if planned else {}))
        traced = jax.make_jaxpr(jax.grad(loss, has_aux=True))(params, *slot)

        def shapes(jaxpr):
            for eqn in jaxpr.eqns:
                yield from (v.aval.shape for v in eqn.outvars if hasattr(v.aval, "shape"))
                for inner in jax.core.jaxprs_in_params(eqn.params):
                    yield from shapes(inner)

        found = set(shapes(traced.jaxpr))
        assert (st.bucket_nodes, 4 * hidden) in found and (st.bucket_nodes, 3 * hidden) in found  # a, and a W_cat
        assert not [s for s in found if s and s[-1] == 12 * hidden], found

    def test_the_head_says_what_it_takes(self):
        assert pna.NAME == "pna" and pna.TAKES_PLAN
        assert not getattr(pna, "TAKES_NEIGHBOR_SUM_1", False) and not getattr(pna, "TAKES_NODE_SHARDS", False)
        st = stacked.stack_dataset(_dataset())
        params = pna.init_params(jax.random.PRNGKey(0), hidden=8, num_features=18)
        assert stacked.plan_for(pna, st) is st.plan
        assert stacked.slot_group(pna, params, st.features, st.plan) == 0  # a maximum does not commute with a product
        assert params.w_agg_1.shape == (12 * 8, 8) and params.w_msg_1.shape == (18, 8)


#: the scalers the update meets: every owner at the degree clamp (`d = 1`), a hub's (up to 4.5 at the cell's largest
#: hubs), and owners with no neighbour, whose aggregates are zero but for the deviation's `sqrt(EPS)`
UPDATE_CASES = ("degree_clamp", "hub", "isolated")
#: float32 epsilons of the sum of the absolute terms an element adds (no cancellation can hide an error under it)
_UPDATE_ULPS = 4


def _update_case(name, seed=0, n=64, hidden=8):
    """(a [n, 4H], scale [n, 1], w_agg [12H, H], a cotangent [n, H]) as the layer makes them."""
    rng = np.random.default_rng(seed)
    a = rng.normal(size=(n, 4 * hidden)).astype(np.float32)
    degree, delta = np.ones(n), 1.0
    if name == "hub":
        degree = np.round(np.geomspace(1, 5000, n))
        delta = np.log(5001.0) / 4.5  # the largest hub's scale is 4.5
    elif name == "isolated":
        a[::2] = 0.0  # no neighbour: nothing aggregated, and `d` clamped to 1
        a[::2, hidden : 2 * hidden] = np.sqrt(pna.EPS)
    scale = jnp.log(jnp.maximum(jnp.asarray(degree, jnp.float32), 1.0)[:, None] + 1.0) / jnp.float32(delta)
    w_agg = (rng.normal(size=(12 * hidden, hidden)) * np.sqrt(2.0 / (13 * hidden))).astype(np.float32)
    g = rng.normal(size=(n, hidden)).astype(np.float32)
    return jnp.asarray(a), scale, jnp.asarray(w_agg), jnp.asarray(g)


def _concatenated_update(a, scale, w_agg):
    """The layer's update as the paper and the reference write it: one product over `[a | s a | a / s]`."""
    return jnp.dot(jnp.concatenate([a, scale * a, a / scale], axis=1), w_agg, precision=jax.lax.Precision.HIGHEST)


def _update_and_vjp(update, a, scale, w_agg, g):
    out, vjp = jax.vjp(lambda a, w: update(a, scale, w), a, w_agg)
    return (out, *vjp(g))


class TestFactoredUpdate:
    @pytest.mark.parametrize("seed", (0, 1, 2))
    @pytest.mark.parametrize("case", UPDATE_CASES)
    def test_it_is_the_concatenated_product_forward_and_backward(self, case, seed):
        """`pna._update` (one product over the four aggregates, then the per-node combine) against the product over
        `[a | s a | a / s]`: the output and the VJP to `a` and to `w_agg`, each element within a few float32 ulps of
        the sum of the absolute terms it adds (the same two computations over |a|, |w_agg| and |g|)."""
        a, scale, w_agg, g = _update_case(case, seed)
        assert float(scale.max()) == pytest.approx(4.5 if case == "hub" else np.log(2.0), rel=1e-6)
        got = jax.jit(partial(_update_and_vjp, pna._update))(a, scale, w_agg, g)
        want = jax.jit(partial(_update_and_vjp, _concatenated_update))(a, scale, w_agg, g)
        size = _update_and_vjp(_concatenated_update, jnp.abs(a), scale, jnp.abs(w_agg), jnp.abs(g))
        for name, x, y, m in zip(("update", "d a", "d w_agg"), got, want, size):
            assert x.shape == y.shape
            bound = _UPDATE_ULPS * np.finfo(np.float32).eps * np.asarray(m)
            assert (np.abs(np.asarray(x) - np.asarray(y)) <= bound).all(), name


def _reference_run(ds, seed, hidden):
    init = {
        k: np.asarray(v)
        for k, v in pna.init_params(jax.random.PRNGKey(seed), hidden=hidden, num_features=18)._asdict().items()
        if v is not None
    }
    params, per_slot = reference_train.train("pna", init, ds, 1e-2, precision="highest")
    return init, params, np.mean(np.asarray(per_slot, np.float64), axis=0)


class TestTrainingThroughTheEpochBlock:
    @pytest.mark.parametrize("plan_reducer", IMPLS, indirect=True)
    def test_three_slots_match_the_plain_reference(self, plan_reducer):
        """`benchmarks/reference/train.py`'s sequential schedule over `reference/pna.py`: losses and parameters."""
        ds = _dataset(n_slots=3)
        got = trainer.train(ds, epochs=1, hidden=16, lr=1e-2, seed=4, model=pna, batch_slots=1)
        assert sparse.route_stats()["planned"] > 0
        init, want_params, want = _reference_run(ds, 4, 16)
        np.testing.assert_allclose([got.losses[-1], got.latency_losses[-1], got.anomaly_losses[-1]], want, rtol=2e-5)
        got_params = {k: np.asarray(v) for k, v in got.params._asdict().items() if v is not None}
        assert set(got_params) == set(want_params)  # the reference's dict uses PnaParams' names
        diff = np.concatenate([(got_params[k] - want_params[k]).ravel() for k in want_params])
        moved = np.concatenate([(want_params[k] - init[k]).ravel() for k in want_params])
        assert np.linalg.norm(diff) <= 2e-2 * np.linalg.norm(moved)

    def test_two_epochs_with_and_without_a_plan_and_through_the_legacy_loop(self, monkeypatch):
        ds = _dataset()
        planned = trainer.train(ds, epochs=2, hidden=8, seed=1, model=pna)
        assert sparse.route_stats()["planned"] > 0
        assert np.isfinite(planned.losses).all() and planned.losses[-1] < planned.losses[0]
        monkeypatch.setenv("KMAMIZ_SPARSE", "xla")  # hands no plan to anything
        sparse.reset_for_tests()
        stacked.epoch_runner.cache_clear()
        assert stacked.plan_for(pna, stacked.stack_dataset(ds)) is None
        plain = trainer.train(ds, epochs=2, hidden=8, seed=1, model=pna)
        assert sparse.route_stats()["planned"] == 0
        stacked.epoch_runner.cache_clear()
        for a, c in ((planned.losses, plain.losses), (planned.latency_losses, plain.latency_losses),
                     (planned.anomaly_losses, plain.anomaly_losses)):
            np.testing.assert_allclose(a, c, rtol=1e-4, atol=1e-6)
        legacy = trainer.train(ds, epochs=2, hidden=8, seed=1, model=pna, fused=False)
        np.testing.assert_allclose(legacy.losses, plain.losses, rtol=1e-4, atol=1e-6)

    def test_a_checkpoint_saved_and_resumed_gives_the_same_losses(self, tmp_path):
        ds = _dataset(n_slots=3)
        whole_dir, cut_dir = str(tmp_path / "whole"), str(tmp_path / "cut")
        whole = trainer.train(ds, epochs=2, hidden=8, seed=2, model=pna, checkpoint_dir=whole_dir, checkpoint_every=1)
        head = trainer.train(ds, epochs=1, hidden=8, seed=2, model=pna, checkpoint_dir=cut_dir, checkpoint_every=1)
        tail = trainer.train(ds, epochs=2, hidden=8, seed=2, model=pna, checkpoint_dir=cut_dir, checkpoint_every=1)
        assert len(tail.losses) == 1 and whole.losses == head.losses + tail.losses
        for a, c in zip(jax.tree_util.tree_leaves(whole.params), jax.tree_util.tree_leaves(tail.params)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(c))
        with pytest.raises(ValueError, match="trained with model=pna"):
            trainer.train(ds, epochs=3, hidden=8, seed=2, checkpoint_dir=cut_dir)  # another head's refresh

    def test_the_span_the_counters_and_the_checkpoint_name_the_head(self, tmp_path):
        from kmamiz_tpu.core import programs

        ds = _dataset(n_slots=2)
        trainer.train(ds, epochs=1, hidden=8, model=pna, checkpoint_dir=str(tmp_path))
        assert checkpoint.load_metadata(str(tmp_path))["model"] == "pna"
        tb = [tb for tb in TRACER.traces() if tb.spans[0][0] == "refresh.train"][-1]
        assert tb.counts[0]["model"] == "pna" and tb.counts[0]["loss"] == "mse+bce"
        block = [c for i, c in tb.counts.items() if tb.spans[i][0] == "refresh.epoch_block"]
        assert block and block[0]["planned"] == 1 and block[0]["slot_group"] == 0
        assert any(k.startswith("models.sage_epoch_block[kmamiz_tpu.models.pna|") for k in programs.all_programs())

    def test_the_microbatch_block_and_the_batched_forward_reduce_the_edge_list(self):
        ds = _dataset()
        r = trainer.train(ds, epochs=2, hidden=8, seed=1, model=pna, batch_slots=2)
        assert sparse.route_stats()["planned"] == 0 and np.isfinite(r.losses).all()
        latency, logit = stacked.predict_all(r.params, ds, pna)
        assert latency.shape == logit.shape == (len(ds.features), ds.num_nodes) and np.isfinite(latency).all()
