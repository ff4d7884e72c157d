"""The per-dataset edge plan and the planned neighbour sum (ops/sparse.py):
the topology sorted once by owner, a tiled reduction over it in plain XLA
and as a Pallas kernel (interpreted here), its own VJP, and the wiring into
graphsage.forward, the stack and the epoch block."""
from __future__ import annotations

import zlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from kmamiz_tpu.core import programs
from kmamiz_tpu.models import gat, graphsage, stacked, trainer
from kmamiz_tpu.ops import sparse
from kmamiz_tpu.telemetry import REGISTRY

TN, BE = sparse.PLAN_NODE_TILE, sparse.PLAN_EDGE_BLOCK
IMPLS = ("xla", "pallas_interpret")


def _zipf(rng, n, e):
    weights = 1.0 / (np.arange(n) + 2.0)
    return rng.choice(n, size=e, p=weights / weights.sum())


def _case(name):
    """(src, dst, edge_mask, bucket_nodes), bucket-padded as the stack pads."""
    rng = np.random.default_rng(zlib.crc32(name.encode()))
    if name == "random":
        n, nb, e, eb = 300, 512, 1500, 2048
        src, dst, mask = rng.integers(0, n, e), rng.integers(0, n, e), np.ones(e, bool)
    elif name == "zipf_heavy":  # endpoint 0 holds about a third of the entries
        n, nb, e, eb = 900, 1024, 6000, 8192
        src, dst, mask = rng.integers(0, n, e), _zipf(rng, n, e), np.ones(e, bool)
    elif name == "masked":
        n, nb, e, eb = 200, 256, 1000, 1024
        src, dst, mask = rng.integers(0, n, e), rng.integers(0, n, e), rng.random(e) < 0.6
    elif name == "padded":  # a handful of edges in a wide bucket
        n, nb, e, eb = 40, 64, 9, 4096
        src, dst, mask = rng.integers(0, n, e), rng.integers(0, n, e), np.ones(e, bool)
    elif name == "empty":
        n, nb, e, eb = 20, 32, 0, 8
        src, dst, mask = np.zeros(0, int), np.zeros(0, int), np.ones(0, bool)
    elif name == "heavier_than_a_block":  # one owner spans three edge blocks
        n, nb, e, eb = 50, 64, 1400, 2048
        src, dst, mask = rng.integers(0, n, e), rng.integers(0, n, e), np.ones(e, bool)
        src[: 2 * BE + 100] = 7
    elif name == "tile_with_no_edges":  # rows 128..383 hold no edge at all
        n, nb, e, eb = 600, 1024, 700, 1024
        src = np.where(rng.random(e) < 0.5, rng.integers(0, TN, e), rng.integers(3 * TN, n, e))
        dst = np.where(rng.random(e) < 0.5, rng.integers(0, TN, e), rng.integers(3 * TN, n, e))
        mask = np.ones(e, bool)
    elif name == "self_loops_and_repeats":
        n, nb, e, eb = 30, 32, 400, 512
        src, dst, mask = rng.integers(0, 6, e), rng.integers(0, 6, e), np.ones(e, bool)
    elif name == "every_entry_real":  # 2 x edges fills the last block to its end
        n, nb, e, eb = 100, 128, 512, 512
        src, dst, mask = rng.integers(0, n, e), rng.integers(0, n, e), np.ones(e, bool)
    else:
        raise KeyError(name)
    pad = eb - e
    return (
        np.concatenate([src, np.zeros(pad, int)]).astype(np.int32),
        np.concatenate([dst, np.zeros(pad, int)]).astype(np.int32),
        np.concatenate([mask, np.zeros(pad, bool)]),
        nb,
    )


CASES = (
    "random", "zipf_heavy", "masked", "padded", "empty", "heavier_than_a_block",
    "tile_with_no_edges", "self_loops_and_repeats", "every_entry_real",
)


def _device(plan):
    return jax.tree_util.tree_map(jnp.asarray, plan)


def _legacy_sum(h, src, dst, mask):
    n = h.shape[0]
    ones = jnp.ones(n, h.dtype)  # a degree of one: the mean is the sum
    return graphsage.neighbor_mean(h, jnp.asarray(src), jnp.asarray(dst), jnp.asarray(mask), ones)


class TestBuildEdgePlan:
    @pytest.mark.parametrize("name", CASES)
    def test_shapes_are_a_function_of_the_buckets(self, name):
        src, dst, mask, nb = _case(name)
        plan, entries, items = sparse.build_edge_plan(src, dst, mask, nb)
        total, node_tiles, bound = sparse.plan_shapes(nb, src.shape[0])
        assert plan.owner.shape == (1, total) and plan.neighbour.shape == (total,)
        assert plan.degree.shape == (nb,) and plan.degree.dtype == np.float32
        assert plan.item_tile.shape == plan.item_block.shape == plan.item_flag.shape == (bound,)
        assert plan.direction.shape == plan.owner.shape and plan.direction.dtype == np.int32
        assert total % BE == 0 and node_tiles == -(-nb // TN)
        assert entries == 2 * int(mask.sum()) and node_tiles <= items <= bound

    @pytest.mark.parametrize("name", CASES)
    def test_degree_equals_neighbor_degree_exactly(self, name):
        src, dst, mask, nb = _case(name)
        plan, _, _ = sparse.build_edge_plan(src, dst, mask, nb)
        want = graphsage.neighbor_degree(nb, jnp.asarray(src), jnp.asarray(dst), jnp.asarray(mask))
        np.testing.assert_array_equal(plan.degree, np.asarray(want))

    @pytest.mark.parametrize("name", CASES)
    def test_every_real_edge_appears_once_per_direction_sorted_by_owner(self, name):
        src, dst, mask, nb = _case(name)
        plan, entries, _ = sparse.build_edge_plan(src, dst, mask, nb)
        owner, neighbour = plan.owner[0], plan.neighbour
        assert (np.diff(owner) >= 0).all()
        assert (owner[entries:] >= -(-nb // TN) * TN).all()  # parked past every tile
        got = sorted(zip(owner[:entries].tolist(), neighbour[:entries].tolist()))
        want = sorted(
            list(zip(src[mask].tolist(), dst[mask].tolist()))
            + list(zip(dst[mask].tolist(), src[mask].tolist()))
        )
        assert got == want

    @pytest.mark.parametrize("name", CASES)
    def test_work_list_covers_every_entry_and_every_tile(self, name):
        src, dst, mask, nb = _case(name)
        plan, entries, items = sparse.build_edge_plan(src, dst, mask, nb)
        tile, block, flag = plan.item_tile, plan.item_block, plan.item_flag
        assert (np.diff(tile) >= 0).all()
        assert (flag[:items] >= 0).all() and (flag[items:] == -1).all()
        # exactly one first item a tile, at the start of its run: it zeroes it
        firsts = tile[:items][flag[:items] == 1]
        np.testing.assert_array_equal(firsts, np.arange(-(-nb // TN)))
        assert (flag[:items][np.r_[True, np.diff(tile[:items]) > 0]] == 1).all()
        # no-ops stay on the last real item's blocks: nothing new is fetched
        assert (tile[items:] == tile[items - 1]).all() and (block[items:] == block[items - 1]).all()
        assert (block >= 0).all() and (block < plan.owner.shape[1] // BE).all()
        have = set(zip(tile[:items].tolist(), block[:items].tolist()))
        assert len(have) == items  # no product twice
        owner = plan.owner[0, :entries]
        need = set(zip((owner // TN).tolist(), (np.arange(entries) // BE).tolist()))
        assert need <= have

    def test_a_heavy_owner_spans_blocks_and_an_empty_tile_still_has_an_item(self):
        src, dst, mask, nb = _case("heavier_than_a_block")
        plan, _, items = sparse.build_edge_plan(src, dst, mask, nb)
        assert plan.degree[7] > 2 * BE and items >= 3  # one tile, at least three blocks
        src, dst, mask, nb = _case("tile_with_no_edges")
        plan, _, items = sparse.build_edge_plan(src, dst, mask, nb)
        assert plan.degree[TN : 3 * TN].sum() == 0
        assert {1, 2} <= set(plan.item_tile[:items].tolist())

    def test_an_edge_out_of_the_bucket_contributes_nothing(self):
        src = np.array([0, 1, 99, 2], np.int32)
        dst = np.array([1, 2, 3, -1], np.int32)
        plan, entries, _ = sparse.build_edge_plan(src, dst, np.ones(4, bool), 8)
        assert entries == 4
        np.testing.assert_array_equal(plan.degree, [1, 2, 1, 0, 0, 0, 0, 0])


class TestPlannedNeighborSum:
    @pytest.mark.parametrize("impl", IMPLS)
    @pytest.mark.parametrize("width", (18, 64))
    @pytest.mark.parametrize("name", CASES)
    def test_against_segment_sum(self, name, width, impl):
        src, dst, mask, nb = _case(name)
        plan, _, _ = sparse.build_edge_plan(src, dst, mask, nb)
        h = jnp.asarray(np.random.default_rng(1).normal(size=(nb, width)).astype(np.float32))
        got = np.asarray(sparse.planned_neighbor_sum(_device(plan), h, impl))
        want = np.asarray(_legacy_sum(h, src, dst, mask))
        assert got.shape == want.shape and got.dtype == want.dtype
        # the same float32 addends in another order: rounding of partial
        # sums as large as the largest row (a heavy owner adds 1,200 rows)
        scale = max(float(np.abs(want).max()), 1.0)
        np.testing.assert_allclose(got, want, rtol=0, atol=1e-5 * scale)
        # rows with no neighbour are exact zeros, not rounding
        np.testing.assert_array_equal(got[plan.degree == 0], 0.0)

    @pytest.mark.parametrize(
        "name,width",
        [("random", 18), ("heavier_than_a_block", 18), ("tile_with_no_edges", 18),
         ("random", 126)],  # seven slots' features side by side: the slot group's table
    )
    def test_kernel_and_xla_reducer_sum_the_same_items(self, name, width):
        """Item by item the same products; the kernel adds its three bfloat16
        passes one after the other, so the last bits may differ. Two runs of
        either give the same bits."""
        src, dst, mask, nb = _case(name)
        plan = _device(sparse.build_edge_plan(src, dst, mask, nb)[0])
        h = jnp.asarray(np.random.default_rng(2).normal(size=(nb, width)).astype(np.float32))
        a, b = (np.asarray(sparse.planned_neighbor_sum(plan, h, impl)) for impl in IMPLS)
        np.testing.assert_allclose(a, b, rtol=0, atol=1e-6 * max(float(np.abs(a).max()), 1.0))
        for impl, first in zip(IMPLS, (a, b)):
            again = np.asarray(sparse.planned_neighbor_sum(plan, h, impl))
            np.testing.assert_array_equal(again, first)

    @pytest.mark.parametrize("name", ("masked", "heavier_than_a_block"))
    def test_the_kernels_columns_are_independent_bit_for_bit(self, name):
        """What the epoch block's slot group rests on: a column of a packed
        sum is the sum the kernel makes of that column alone. (XLA's matrix
        product on a CPU blocks by the width, so its last bit is not.)"""
        src, dst, mask, nb = _case(name)
        plan = _device(sparse.build_edge_plan(src, dst, mask, nb)[0])
        slots = np.random.default_rng(6).normal(size=(7, nb, 18)).astype(np.float32)
        table = jnp.asarray(np.moveaxis(slots, 0, 1).reshape(nb, 126))
        packed = np.asarray(sparse.planned_neighbor_sum(plan, table, "pallas_interpret"))
        for j in (0, 3, 6):
            alone = sparse.planned_neighbor_sum(plan, jnp.asarray(slots[j]), "pallas_interpret")
            np.testing.assert_array_equal(packed[:, j * 18 : (j + 1) * 18], np.asarray(alone))

    def test_products_are_float32_exact(self):
        """One neighbour each: the sum IS the neighbour's row, every bit of
        its float32 mantissa (a single bfloat16 pass would keep eight)."""
        n = 256
        src = np.arange(0, n, 2, dtype=np.int32)
        dst = src + 1
        plan = _device(sparse.build_edge_plan(src, dst, np.ones(n // 2, bool), n)[0])
        h = jnp.asarray((np.random.default_rng(3).random((n, 64)) + 1.0).astype(np.float32))
        swapped = np.asarray(h).reshape(n // 2, 2, 64)[:, ::-1].reshape(n, 64)
        for impl in IMPLS:
            np.testing.assert_array_equal(
                np.asarray(sparse.planned_neighbor_sum(plan, h, impl)), swapped
            )

    @pytest.mark.parametrize("impl", IMPLS)
    def test_vjp_is_the_forward_and_matches_autodiff_of_the_legacy_sum(self, impl):
        src, dst, mask, nb = _case("masked")
        plan = _device(sparse.build_edge_plan(src, dst, mask, nb)[0])
        rng = np.random.default_rng(4)
        h = jnp.asarray(rng.normal(size=(nb, 18)).astype(np.float32))
        ct = jnp.asarray(rng.normal(size=(nb, 18)).astype(np.float32))
        out, pull = jax.vjp(lambda x: sparse.planned_neighbor_sum(plan, x, impl), h)
        (got,) = pull(ct)
        np.testing.assert_array_equal(
            np.asarray(got), np.asarray(sparse.planned_neighbor_sum(plan, ct, impl))
        )
        _, pull_legacy = jax.vjp(lambda x: _legacy_sum(x, src, dst, mask), h)
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(pull_legacy(ct)[0]), rtol=0, atol=1e-4
        )

    def test_no_scatter_over_edge_rows_is_left_forward_or_backward(self):
        src, dst, mask, nb = _case("random")
        plan = _device(sparse.build_edge_plan(src, dst, mask, nb)[0])
        h = jnp.ones((nb, 18), jnp.float32)
        loss = lambda x: (sparse.planned_neighbor_sum(plan, x, "xla") ** 2).sum()  # noqa: E731
        text = jax.jit(jax.grad(loss)).lower(h).as_text()
        entries = plan.neighbour.shape[0]
        for line in text.splitlines():
            if "scatter" in line:
                assert f"{entries}x" not in line and f"{src.shape[0]}x" not in line, line

    def test_counts_in_route_stats(self):
        src, dst, mask, nb = _case("padded")
        plan = _device(sparse.build_edge_plan(src, dst, mask, nb)[0])
        assert sparse.route_stats()["planned"] == 0
        sparse.planned_neighbor_sum(plan, jnp.ones((nb, 4)), "xla")
        assert sparse.route_stats()["planned"] == 1
        sparse.reset_for_tests()
        assert sparse.route_stats()["planned"] == 0

    def test_no_value_of_the_backend_knob_selects_the_reducer(self, monkeypatch):
        assert sparse.planned_impl() == "xla"  # a CPU, no knob
        for value in sparse._VALID_BACKENDS:
            monkeypatch.setenv("KMAMIZ_SPARSE", value)
            sparse.reset_for_tests()
            assert sparse.planned_impl() == "xla"


def _params(num_features, hidden=8, num_nodes=0, seed=0):
    return graphsage.init_params(
        jax.random.PRNGKey(seed), hidden=hidden, num_features=num_features, num_nodes=num_nodes
    )


class TestForwardWithAPlan:
    @pytest.mark.parametrize("plan_reducer", IMPLS, indirect=True)
    @pytest.mark.parametrize("embeddings", (False, True), ids=("features", "embeddings"))
    def test_loss_and_gradient_match_todays_forward(self, plan_reducer, embeddings):
        src, dst, mask, nb = _case("masked")
        plan = _device(sparse.build_edge_plan(src, dst, mask, nb)[0])
        rng = np.random.default_rng(5)
        x = jnp.asarray(rng.normal(size=(nb, 18)).astype(np.float32))
        tl = jnp.asarray(rng.normal(size=nb).astype(np.float32))
        ta = jnp.asarray((rng.random(nb) < 0.2).astype(np.float32))
        nm = jnp.asarray(rng.random(nb) < 0.9)
        params = _params(18, num_nodes=nb if embeddings else 0)
        args = (x, jnp.asarray(src), jnp.asarray(dst), jnp.asarray(mask), tl, ta, nm)
        want, want_grad = jax.value_and_grad(graphsage.loss_fn, has_aux=True)(params, *args)

        from functools import partial

        from kmamiz_tpu.models import common

        planned = common.make_loss_fn(partial(graphsage.forward, plan=plan))
        got, got_grad = jax.value_and_grad(planned, has_aux=True)(params, *args)
        assert sparse.route_stats()["planned"] == 2  # both layers, at trace time
        np.testing.assert_allclose(np.asarray(got[0]), np.asarray(want[0]), rtol=2e-6)
        for name, a, b in zip(params._fields, got_grad, want_grad):
            if a is None:
                assert b is None
                continue
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-6, err_msg=name
            )

    def test_no_plan_keeps_todays_formulation(self):
        src, dst, mask, nb = _case("random")
        x = jnp.ones((nb, 10), jnp.float32)
        graphsage.forward(_params(10), x, jnp.asarray(src), jnp.asarray(dst), jnp.asarray(mask))
        assert sparse.route_stats()["planned"] == 0


def _dataset(n_nodes=40, n_edges=120, n_slots=4, seed=0):
    rng = np.random.default_rng(seed)
    return trainer.GraphDataset(
        endpoint_names=[f"ep{i}" for i in range(n_nodes)],
        src=rng.integers(0, n_nodes, n_edges, dtype=np.int32),
        dst=rng.integers(0, n_nodes, n_edges, dtype=np.int32),
        edge_mask=rng.random(n_edges) < 0.9,
        features=[rng.normal(size=(n_nodes, 10)).astype(np.float32) for _ in range(n_slots)],
        target_latency=[rng.normal(size=n_nodes).astype(np.float32) for _ in range(n_slots)],
        target_anomaly=[(rng.random(n_nodes) < 0.2).astype(np.float32) for _ in range(n_slots)],
        node_mask=[rng.random(n_nodes) < 0.9 for _ in range(n_slots)],
        slot_keys=[f"s{i}" for i in range(n_slots)],
    )


def _head(ds, n):
    return trainer.GraphDataset(
        endpoint_names=ds.endpoint_names, src=ds.src, dst=ds.dst, edge_mask=ds.edge_mask,
        features=ds.features[:n], target_latency=ds.target_latency[:n],
        target_anomaly=ds.target_anomaly[:n], node_mask=ds.node_mask[:n],
        slot_keys=ds.slot_keys[:n],
    )


def _epoch_block_counts():
    """The counts of every `refresh.epoch_block` span recorded, oldest first."""
    from kmamiz_tpu.telemetry.tracing import TRACER

    return [
        dict(tb.counts.get(i, {}))
        for tb in TRACER.traces()
        for i, span in enumerate(tb.spans)
        if span[0] == "refresh.epoch_block"
    ]


def _counter(name):
    for line in REGISTRY.render().splitlines():
        if line.startswith(name + " "):
            return float(line.split()[-1])
    raise AssertionError(f"{name} is not in /metrics")


class TestStackCarriesThePlan:
    def test_the_stack_holds_the_plan_of_its_padded_edge_list(self):
        ds = _dataset()
        st = stacked.stack_dataset(ds)
        want, entries, items = sparse.build_edge_plan(
            np.asarray(st.src), np.asarray(st.dst), np.asarray(st.edge_mask), st.bucket_nodes
        )
        assert (st.plan_entries, st.plan_items) == (entries, items)
        assert entries == 2 * int(np.asarray(ds.edge_mask).sum())
        for got, w in zip(st.plan, want):
            np.testing.assert_array_equal(np.asarray(got), w)
        shapes = sparse.plan_shapes(st.bucket_nodes, st.bucket_edges)
        assert st.plan.owner.shape == (1, shapes[0]) and st.plan.item_tile.shape == (shapes[2],)

    def test_builds_and_hits_are_counted_and_datasets_over_one_graph_share_a_plan(self):
        ds = _dataset()
        st = stacked.stack_dataset(ds)
        assert _counter("kmamiz_model_edge_plan_builds_total") == 1
        assert _counter("kmamiz_model_edge_plan_hits_total") == 0
        assert stacked.stack_dataset(ds) is st  # the stack's own memo
        assert _counter("kmamiz_model_edge_plan_hits_total") == 1
        head = stacked.stack_dataset(_head(ds, 2))  # another stack, the same arrays
        assert head is not st and head.plan is st.plan
        assert _counter("kmamiz_model_edge_plan_builds_total") == 1
        assert _counter("kmamiz_model_edge_plan_hits_total") == 2
        other = stacked.stack_dataset(_dataset(seed=1))  # another graph
        assert other.plan is not st.plan
        assert _counter("kmamiz_model_edge_plan_builds_total") == 2

    def test_plan_for_goes_by_what_the_head_says_it_takes_and_by_the_legacy_knob(self, monkeypatch):
        import inspect
        import types

        from kmamiz_tpu.models.stlgt import model as stlgt_model

        st = stacked.stack_dataset(_dataset())
        assert stacked.plan_for(graphsage, st) is st.plan
        assert stacked.plan_for(gat, st) is st.plan
        assert stacked.plan_for(stlgt_model, st) is st.plan  # the gated sum runs over it (PR 33)
        # said once, on the head, and true of its forward; the signature is not read
        for model in (graphsage, gat, stlgt_model):
            assert model.TAKES_PLAN and "plan" in inspect.signature(model.forward).parameters
        assert "plan" in inspect.signature(stlgt_model.make_loss_fn(1.0)).parameters
        assert graphsage.TAKES_NEIGHBOR_SUM_1 and "neighbor_sum_1" in inspect.signature(graphsage.forward).parameters
        assert not hasattr(gat, "TAKES_NEIGHBOR_SUM_1") and not hasattr(stlgt_model, "TAKES_NEIGHBOR_SUM_1")
        silent = types.SimpleNamespace(forward=graphsage.forward)  # a head that says nothing is handed none
        assert stacked.plan_for(silent, st) is None
        assert stacked.slot_group(silent, _params(st.features.shape[2]), st.features, st.plan) == 0
        monkeypatch.setenv("KMAMIZ_SPARSE", "xla")
        sparse.reset_for_tests()
        assert stacked.plan_for(graphsage, st) is None
        assert stacked.plan_for(gat, st) is None
        assert stacked.plan_for(stlgt_model, st) is None


class TestTrainingThroughThePlan:
    def test_a_refresh_engages_the_plan_with_no_knob_set(self, monkeypatch):
        monkeypatch.delenv("KMAMIZ_SPARSE", raising=False)
        sparse.reset_for_tests()
        stacked.epoch_runner.cache_clear()  # a fresh trace, so the route is counted
        r = trainer.train(_dataset(), epochs=2, hidden=8)
        assert np.isfinite(r.losses).all()
        assert sparse.route_stats()["planned"] > 0
        assert _counter("kmamiz_model_edge_plan_builds_total") == 1

    @pytest.mark.parametrize("plan_reducer", IMPLS, indirect=True)
    def test_epoch_block_with_a_plan_matches_the_legacy_per_slot_loop(self, plan_reducer):
        ds = _dataset()
        # the oracle holds no plan: XLA's gathers and segment sums
        legacy = trainer.train(ds, epochs=3, hidden=8, seed=0, fused=False)
        assert sparse.route_stats()["planned"] == 0
        fused = trainer.train(ds, epochs=3, hidden=8, seed=0, fused=True)
        assert sparse.route_stats()["planned"] > 0
        np.testing.assert_allclose(fused.losses, legacy.losses, rtol=1e-4, atol=1e-5)
        for a, b in zip(
            jax.tree_util.tree_leaves(fused.params), jax.tree_util.tree_leaves(legacy.params)
        ):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-3, atol=1e-5)

    def test_the_xla_knob_keeps_the_legacy_formulation_everywhere(self, monkeypatch):
        monkeypatch.setenv("KMAMIZ_SPARSE", "xla")
        sparse.reset_for_tests()
        stacked.epoch_runner.cache_clear()
        r = trainer.train(_dataset(), epochs=1, hidden=8)
        assert np.isfinite(r.losses).all()
        assert sparse.route_stats()["planned"] == 0

    @pytest.mark.parametrize(
        "path", ("dp_epoch_runner", "predict_all", "gat_dp_epoch_runner", "gat_predict_all", "gat_unfused")
    )
    def test_paths_that_pass_no_plan_keep_todays_code(self, path):
        stacked.epoch_runner.cache_clear()
        stacked.dp_epoch_runner.cache_clear()
        stacked._batched_forward.cache_clear()
        ds = _dataset()
        model = gat if path.startswith("gat_") else graphsage
        if path.endswith("dp_epoch_runner"):
            r = trainer.train(ds, epochs=1, hidden=8, batch_slots=2, model=model)
            assert np.isfinite(r.losses).all()
        elif path.endswith("predict_all"):
            params = model.init_params(jax.random.PRNGKey(0), hidden=8, num_features=10)
            lat, logit = stacked.predict_all(params, ds, model)
            assert lat.shape == logit.shape == (4, 40)
        else:
            r = trainer.train(ds, epochs=1, hidden=8, model=gat, fused=False)
            assert np.isfinite(r.losses).all()
        assert sparse.route_stats()["planned"] == sparse.route_stats()["attention"] == 0

    @pytest.mark.parametrize("knob,engaged", ((None, True), ("xla", False)))
    def test_a_gat_refresh_engages_the_plan_with_no_knob_and_not_under_xla(
        self, monkeypatch, knob, engaged
    ):
        """The case that used to pin GAT to the code without a plan."""
        if knob is None:
            monkeypatch.delenv("KMAMIZ_SPARSE", raising=False)
        else:
            monkeypatch.setenv("KMAMIZ_SPARSE", knob)
        sparse.reset_for_tests()
        stacked.epoch_runner.cache_clear()
        r = trainer.train(_dataset(), epochs=1, hidden=8, model=gat)
        assert np.isfinite(r.losses).all()
        stats = sparse.route_stats()
        assert (stats["planned"] > 0) is engaged and (stats["attention"] > 0) is engaged
        assert stats["planned"] == stats["attention"]  # a layer each, and nothing else
        counts = _epoch_block_counts()
        assert counts and counts[-1]["planned"] == int(engaged)


# -- the slot group: layer 1's data-only sum for several slots in one ------------


def _wide_dataset(n_slots, width, n_nodes=150, n_edges=600, seed=0):
    rng = np.random.default_rng(seed)
    ds = _dataset(n_nodes=n_nodes, n_edges=n_edges, n_slots=n_slots, seed=seed)
    ds.features = [rng.normal(size=(n_nodes, width)).astype(np.float32) for _ in range(n_slots)]
    return ds


def _run_block(ds, n_epochs, **block_args):
    """One call of GraphSAGE's epoch block on a dataset's stack -> (leaves, losses)."""
    st = stacked.stack_dataset(ds)
    params = _params(int(st.features.shape[2]))
    opt_state = graphsage.make_optimizer(1e-2).init(params)
    params, _, losses = stacked.epoch_runner(graphsage, 1e-2, 3.0)(
        params, opt_state, st.features, st.target_latency, st.target_anomaly, st.node_mask,
        st.src, st.dst, st.edge_mask, n_epochs, stacked.plan_for(graphsage, st), **block_args,
    )
    return [np.asarray(a) for a in jax.tree_util.tree_leaves(params)], np.asarray(losses)


def _parent_epoch_block(model, lr, pos_weight):
    """The epoch block as PR 28 left it, for the programs that must not
    change: one flat scan over the slots, the plan closed over. A head that
    states a loss of its own (PR 33) has that in the family's place, the
    plan bound to it by keyword."""
    import functools

    import optax

    from kmamiz_tpu.models import common

    optimizer = model.make_optimizer(lr)
    if hasattr(model, "make_loss_fn"):
        grad_fn = jax.value_and_grad(model.make_loss_fn(pos_weight), has_aux=True)
    else:
        grad_fn = jax.value_and_grad(common.make_loss_fn(model.forward, pos_weight), has_aux=True)

    def sage_epoch_block(
        params, opt_state, features, target_latency, target_anomaly, node_mask,
        src, dst, edge_mask, n_epochs, plan=None,
    ):
        slot_grad = grad_fn
        if plan is not None and not hasattr(model, "make_loss_fn"):
            slot_grad = jax.value_and_grad(
                common.make_loss_fn(functools.partial(model.forward, plan=plan), pos_weight),
                has_aux=True,
            )

        def slot_step(carry, xs):
            nonlocal slot_grad
            if plan is not None and hasattr(model, "make_loss_fn"):
                # made where the block makes it: its constants (the levels) are the scan's
                slot_grad = jax.value_and_grad(
                    functools.partial(model.make_loss_fn(pos_weight), plan=plan), has_aux=True
                )
            p, s = carry
            f, tl, ta, nm = xs
            (loss, (lat_l, ano_l)), grads = slot_grad(p, f, src, dst, edge_mask, tl, ta, nm)
            updates, s = optimizer.update(grads, s, p)
            p = optax.apply_updates(p, updates)
            return (p, s), jnp.stack([loss, lat_l, ano_l])

        def epoch_step(carry, _):
            carry, per_slot = jax.lax.scan(
                slot_step, carry, (features, target_latency, target_anomaly, node_mask)
            )
            return carry, per_slot.mean(axis=0)

        (params, opt_state), losses = jax.lax.scan(
            epoch_step, (params, opt_state), None, length=n_epochs
        )
        return params, opt_state, losses

    return jax.jit(
        sage_epoch_block, static_argnames=("n_epochs",), donate_argnames=("params", "opt_state")
    )


class TestSlotGroup:
    @pytest.mark.parametrize("n_epochs", (1, 2))
    @pytest.mark.parametrize(
        "n_slots,width,forced,group",
        [(1, 18, None, 0), (5, 18, None, 5), (7, 18, None, 7), (8, 18, None, 7),
         (15, 18, None, 7), (9, 70, None, 0), (8, 18, 3, 3), (7, 18, 2, 2)],
    )
    @pytest.mark.parametrize("plan_reducer", IMPLS, indirect=True)
    def test_grouped_block_equals_the_per_slot_block(
        self, plan_reducer, n_slots, width, forced, group, n_epochs
    ):
        """One update a slot, in slot order, from the same layer-1 sums: bit
        for bit on the kernel (its columns are independent), to the last
        bits on a CPU's XLA reducer (its matrix product blocks by width)."""
        ds = _wide_dataset(n_slots, width)
        st = stacked.stack_dataset(ds)
        params = _params(width)
        if forced is None:
            assert stacked.slot_group(graphsage, params, st.features, st.plan) == group
        grouped = _run_block(ds, n_epochs, group=forced)
        # at trace time: the packed sum and layer 2's, or both layers' (the
        # VJP's transposed sum is the rule's own call and never was counted)
        assert sparse.route_stats()["planned"] == 2
        per_slot = _run_block(ds, n_epochs, group=0)
        assert sparse.route_stats()["planned"] == 4
        for a, b in zip(grouped[0] + [grouped[1]], per_slot[0] + [per_slot[1]]):
            if plan_reducer == "pallas_interpret":
                np.testing.assert_array_equal(a, b)
            else:
                np.testing.assert_allclose(a, b, rtol=2e-5, atol=2e-6)
        assert grouped[1].shape == (n_epochs, 3) and np.isfinite(grouped[1]).all()

    @pytest.mark.parametrize("case", ("embeddings", "no_plan", "gat", "stlgt"))
    def test_who_offers_no_data_only_sum_runs_the_program_of_the_parent(self, monkeypatch, case):
        """Node embeddings put parameters into layer 1's input, a call
        without a plan has nothing to sum over, GAT multiplies by W1 before it
        touches an edge, and so does STLGT (by W_in; it takes the plan since
        PR 33, for its gated sum, under its own loss): the block is the flat
        scan it was, jaxpr for jaxpr."""
        from kmamiz_tpu.models.stlgt import model as stlgt_model

        if case == "no_plan":
            monkeypatch.setenv("KMAMIZ_SPARSE", "xla")
            sparse.reset_for_tests()
        stacked.epoch_runner.cache_clear()
        model = {"gat": gat, "stlgt": stlgt_model}.get(case, graphsage)
        ds = _wide_dataset(9, 18)
        st = stacked.stack_dataset(ds)
        plan = stacked.plan_for(model, st)
        assert (plan is None) is (case == "no_plan")
        assert hasattr(model, "make_loss_fn") is (case == "stlgt")
        kw = {"num_nodes": st.num_nodes} if case == "embeddings" else {}
        params = model.init_params(jax.random.PRNGKey(0), hidden=8, num_features=18, **kw)
        assert stacked.slot_group(model, params, st.features, plan) == 0
        opt_state = model.make_optimizer(1e-2).init(params)
        args = (
            params, opt_state, st.features, st.target_latency, st.target_anomaly,
            st.node_mask, st.src, st.dst, st.edge_mask,
        )
        got = jax.make_jaxpr(
            lambda *a: stacked.epoch_runner(model, 1e-2, 3.0).fn(*a, 2, plan)
        )(*args)
        want = jax.make_jaxpr(
            lambda *a: _parent_epoch_block(model, 1e-2, 3.0)(*a, 2, plan)
        )(*args)
        assert str(got) == str(want)

    def test_the_refresh_counts_its_group_on_the_epoch_block_span(self, monkeypatch):
        """The cell's shapes at a small node count: 18 features, more than
        seven slots, no embeddings, no knob."""
        last_counts = lambda: _epoch_block_counts()[-1]  # noqa: E731
        monkeypatch.delenv("KMAMIZ_SPARSE", raising=False)
        sparse.reset_for_tests()
        stacked.epoch_runner.cache_clear()
        ds = _wide_dataset(9, 18)
        r = trainer.train(ds, epochs=2, hidden=8)
        assert np.isfinite(r.losses).all()
        assert last_counts() == {"epochs": 2, "slot_updates": 18, "planned": 1, "slot_group": 7}
        assert sparse.route_stats()["planned"] == 2  # the packed sum, and layer 2's
        trainer.train(ds, epochs=1, hidden=8, use_node_embeddings=True)
        assert last_counts()["planned"] == 1 and last_counts()["slot_group"] == 0
        trainer.train(ds, epochs=1, hidden=8, model=gat)
        assert last_counts()["planned"] == 1 and last_counts()["slot_group"] == 0
        trainer.train(ds, epochs=1, hidden=8, batch_slots=2)
        assert last_counts()["planned"] == 0 and last_counts()["slot_group"] == 0
        monkeypatch.setenv("KMAMIZ_SPARSE", "xla")
        sparse.reset_for_tests()
        stacked.epoch_runner.cache_clear()
        trainer.train(ds, epochs=1, hidden=8)
        assert last_counts()["planned"] == 0 and last_counts()["slot_group"] == 0


# -- the kernel at the cell's shapes, through the chip's own compiler ---------


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - no TPU compiler here: nothing to check
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding

    return SingleDeviceSharding(topo.devices[0])


def _described_plan(arg, nb, entries, items):
    return sparse.EdgePlan(
        owner=arg((1, entries), jnp.int32), neighbour=arg((entries,), jnp.int32),
        degree=arg((nb,), jnp.float32), item_tile=arg((items,), jnp.int32),
        item_block=arg((items,), jnp.int32), item_flag=arg((items,), jnp.int32),
        direction=arg((1, entries), jnp.int32), mean_log_degree=arg((), jnp.float32),
    )


@pytest.mark.parametrize("width", (18, 64, 126))  # 126: seven slots' layer-1 sums in one
def test_kernel_compiles_for_the_v5e_at_the_cells_shapes(one_chip, width):
    from jax.experimental.compilation_cache import compilation_cache

    nb, eb = 131072, 524288
    entries, _tiles, items = sparse.plan_shapes(nb, eb)
    assert (entries, items) == (1048576, 1024 + 2048)

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    plan = _described_plan(arg, nb, entries, items)
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)  # a described chip reads none back
    compilation_cache.reset_cache()
    try:
        compiled = (
            jax.jit(lambda p, h: sparse.planned_neighbor_sum(p, h, "pallas"))
            .lower(plan, arg((nb, width), jnp.float32))
            .compile()
        )
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()
    text = compiled.as_text()
    assert "tpu_custom_call" in text and "planned_neighbor_sum" in text
    assert "scatter" not in text


@pytest.mark.parametrize("width", (64, 124))
def test_attention_kernels_compile_for_the_v5e_at_the_cells_shapes(one_chip, width):
    """The five walks of `planned_attention`, forward and backward, at the
    width of `mv100k-gat`, and at one whose spare lanes straddle the first
    128 (the neighbour's scalars are then rows 124..131 of a transposed
    [256, block] tile, and `_max`'s ring holds 256-lane blocks): what Mosaic
    refuses here costs no chip time."""
    from jax.experimental.compilation_cache import compilation_cache

    nb, eb = 131072, 524288
    entries, _tiles, items = sparse.plan_shapes(nb, eb)

    def arg(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def loss(hw, s, t, plan):
        return (sparse.planned_attention(plan, hw, s, t, 0.2, "pallas") ** 2).sum()

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        compiled = (
            jax.jit(jax.grad(loss, argnums=(0, 1, 2)))
            .lower(arg((nb, width)), arg((nb, 2)), arg((nb, 2)), _described_plan(arg, nb, entries, items))
            .compile()
        )
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()
    text = compiled.as_text()
    for name in ("max", "softmax", "sum", "edge_dot", "backward"):
        assert f"planned_attention_{name}" in text
    assert "scatter" not in text
    # the only gathers left are the two row gathers, at the full lane width
    gathers = [line for line in text.splitlines() if " gather(" in line]
    lanes = 128 if width == 64 else 256
    assert len(gathers) == 2 and all(f"f32[{entries},{lanes}]" in line for line in gathers)


@pytest.mark.parametrize("width", (64, 100))
def test_gated_sum_kernels_compile_for_the_v5e_at_the_cells_shapes(one_chip, width):
    """The two walks of `sparse_gated.planned_gated_sum`, forward and backward,
    at the width of `mv100k-stlgt` (a row `[q | k]` fills the 128 lanes) and
    at one whose halves are 128 lanes each."""
    from jax.experimental.compilation_cache import compilation_cache

    from kmamiz_tpu.ops import sparse_gated

    nb, eb = 131072, 524288
    entries, _tiles, items = sparse.plan_shapes(nb, eb)

    def arg(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def loss(q, k, v, b, plan):
        return (sparse_gated.planned_gated_sum(plan, q, k, v, b, "pallas") ** 2).sum()

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        compiled = (
            jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3)))
            .lower(arg((nb, width)), arg((nb, width)), arg((nb, width)), arg((1,)),
                   _described_plan(arg, nb, entries, items))
            .compile()
        )
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()
    text = compiled.as_text()
    assert "planned_gated_sum" in text and "planned_gated_backward" in text
    assert "scatter" not in text
    # three row gathers, each from a table of the node bucket's rows at the
    # full lane width (a table of twice the rows gathers five times slower)
    gathers = [line for line in text.splitlines() if " gather(" in line]
    lanes = 128 if width == 64 else 256
    assert len(gathers) == 3 and all(f"f32[{entries},{lanes}]" in line for line in gathers)
    assert f"f32[{2 * nb}," not in text
    if width == 64:
        # each gather's TABLE (67 MB) is held in the chip's fast memory, `S(1)`, while it gathers: from
        # there a row costs 1.8 ns, from HBM 10 ns (PERF.md, PR 33). The forward's two are chained for it
        tables = [
            next(line for line in body.splitlines() if " parameter(0)" in line)
            for body in text.split("\n}\n") if " gather(" in body
        ]
        assert len(tables) == 3 and all(f"f32[{nb},{lanes}]" in t and "S(1)}" in t for t in tables), tables


@pytest.mark.parametrize("width", (64, 40))
def test_aggregate_kernels_compile_for_the_v5e_at_the_cells_shapes(one_chip, width):
    """The two walks of a differentiated `sparse_pna.planned_aggregate`, the counted forward and the mirror, at the
    width of `mv100k-pna` (a row `[m | -m]` fills the 128 lanes) and at one whose halves are padded: the lane shifts
    of the running maximum, the `[64, 128]` output tile of the mirror walk and its three message blocks are what
    interpreting does not refuse."""
    from jax.experimental.compilation_cache import compilation_cache

    from kmamiz_tpu.ops import sparse_pna

    nb, eb = 131072, 524288
    entries, _tiles, items = sparse.plan_shapes(nb, eb)

    def arg(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def loss(m, plan):
        return sum((a ** 2).sum() for a in sparse_pna.planned_aggregate(plan, m, "pallas"))

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        compiled = jax.jit(jax.grad(loss)).lower(arg((nb, width)), _described_plan(arg, nb, entries, items)).compile()
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == 2
    for name in ("planned_aggregate_counted", "planned_aggregate_backward"):
        assert f'"{name}"' in text or f"{name}." in text or f"{name} " in text, name
    assert "planned_aggregate_ties" not in text  # the ties are counted in the forward walk
    assert "scatter" not in text
    # four row gathers (one forward, three backward), each from a table of the node bucket's rows at the full lane
    # width, and each TABLE (67 MB) held in the chip's fast memory, `S(1)`, while it gathers: the backward's three
    # are chained for it (1.8 ns a row from there, 10 ns from HBM: PERF.md, PR 33)
    gathers = [line for line in text.splitlines() if " gather(" in line]
    assert len(gathers) == 4 and all(f"f32[{entries},128]" in line for line in gathers), gathers
    tables = [
        next(line for line in body.splitlines() if " parameter(0)" in line)
        for body in text.split("\n}\n") if " gather(" in body
    ]
    assert len(tables) == 4 and all(f"f32[{nb},128]" in t and "S(1)}" in t for t in tables), tables


@pytest.mark.parametrize("width", (64, 40))
def test_gatv2_walks_compile_for_the_v5e_at_the_cells_shapes(one_chip, width):
    """The five walks of a differentiated `sparse_gatv2.planned_gatv2`, at the width of `mv100k-gatv2` (a row
    `[xl | xr]` fills the 128 lanes) and at one that is padded to a multiple of eight: the `[64, 512]` per-entry
    vector work, the `a` columns of a tile's block, the lane sums of `d a` and the two message blocks of the backward
    walk are what interpreting does not refuse. Two row gathers, both at the full lane width, and no scatter."""
    from jax.experimental.compilation_cache import compilation_cache

    from kmamiz_tpu.ops import sparse_gatv2

    nb, eb = 131072, 524288
    entries, _tiles, items = sparse.plan_shapes(nb, eb)

    def arg(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def loss(xl, xr, a, plan):
        return (sparse_gatv2.planned_gatv2(plan, xl, xr, a, 0.2, "pallas") ** 2).sum()

    # three of the walks are GAT's kernel bodies, and a kernel's bytecode holds the call stack of whoever traced its
    # body FIRST in the process (GAT's `planned_attention` in a process that refreshed GAT): trace afresh, as a process
    # that refreshes this head alone does
    jax.clear_caches()
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        compiled = (
            jax.jit(jax.grad(loss, argnums=(0, 1, 2)))
            .lower(arg((nb, width)), arg((nb, width)), arg((2, width)), _described_plan(arg, nb, entries, items))
            .compile()
        )
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == 5
    for name in ("score", "softmax", "sum", "edge_dot", "backward"):
        assert f"planned_gatv2_{name}" in text
    assert "planned_attention" not in text and "scatter" not in text
    gathers = [line for line in text.splitlines() if " gather(" in line]
    assert len(gathers) == 2 and all(f"f32[{entries},128]" in line for line in gathers), gathers


def test_the_aggregate_primal_compiles_for_the_v5e_and_counts_no_ties(one_chip):
    """A forward that takes no VJP (serving, `predict_all`, evaluation) runs the custom VJP's primal: ONE walk,
    `planned_aggregate`, with two outputs, and not the forward rule's counted walk."""
    from jax.experimental.compilation_cache import compilation_cache

    from kmamiz_tpu.ops import sparse_pna

    nb, eb = 131072, 524288
    entries, _tiles, items = sparse.plan_shapes(nb, eb)

    def arg(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        compiled = jax.jit(lambda m, plan: sparse_pna.planned_aggregate(plan, m, "pallas")).lower(
            arg((nb, 64)), _described_plan(arg, nb, entries, items)
        ).compile()
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == 1 and '"planned_aggregate"' in text
    assert "planned_aggregate_counted" not in text and "planned_aggregate_ties" not in text
    assert len([line for line in text.splitlines() if " gather(" in line]) == 1


def test_node_sharded_block_compiles_for_the_v5e_host_at_the_cells_shapes(topo):
    """`mv400k-sage`'s epoch block for the four chips of a described host: the
    one-device block's body under `shard_map`, 432 slots x 524,288 nodes cut
    four ways, a chip's plan cut once more by the chip its neighbours live
    on. What the chip's compiler makes of it: a Mosaic reducer a source and
    sum, the tables all-gathered in float32, nothing reduce-scattered, every
    row gather from ONE source's table of 131,072 rows that lies in the fast
    memory (`S(1)`: 1.8 ns a row; the 524,288-row table in HBM cost 10.3,
    PERF.md PR 36), and a chip's share inside a chip's memory."""
    import re

    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    mesh = Mesh(np.asarray(topo.devices), ("nodes",))
    slots, nb, eb, width, shards = 432, 524288, 2097152, 18, 4
    rows = nb // shards
    entries, _tiles, items = sparse.plan_shapes(rows, eb // shards // shards)
    assert (entries, items) == (262144, 1024 + 512)  # a quarter of the 100k cell's entries a source

    def arg(shape, dtype, spec=P()):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=NamedSharding(mesh, spec))

    def leaf(shape, dtype):  # [owner's shard, source, ...]; the degree and its mean are the owner's whole
        lead = (shards,) if shape in ((rows,), ()) else (shards, shards)
        return arg(lead + shape, dtype, P("nodes"))

    plan = _described_plan(leaf, rows, entries, items)
    params = jax.eval_shape(lambda: graphsage.init_params(jax.random.PRNGKey(0), hidden=64, num_features=width))
    opt_state = jax.eval_shape(lambda p: graphsage.make_optimizer(1e-2).init(p), params)
    whole = lambda tree: jax.tree_util.tree_map(lambda a: arg(a.shape, a.dtype), tree)  # noqa: E731
    cut = P(None, "nodes")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)  # a described chip reads none back
    compilation_cache.reset_cache()
    real_impl, sparse.planned_impl = sparse.planned_impl, lambda: "pallas"  # `jax.default_backend()` sees the CPU
    try:
        compiled = stacked.node_sharded_epoch_runner(graphsage, 1e-2, 10.0, mesh).fn.lower(
            whole(params), whole(opt_state), arg((slots, nb, width), jnp.float32, cut),
            arg((slots, nb), jnp.float32, cut), arg((slots, nb), jnp.float32, cut), arg((slots, nb), jnp.bool_, cut),
            arg((eb,), jnp.int32), arg((eb,), jnp.int32), arg((eb,), jnp.bool_), 1, plan,
        ).compile()
    finally:
        sparse.planned_impl = real_impl
        stacked.node_sharded_epoch_runner.cache_clear()
        stacked.epoch_runner.cache_clear()
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()
    text = compiled.as_text()
    # the group's sum, layer 2's and its cotangent's: a reducer a source each
    assert text.count("tpu_custom_call") == 3 * shards and "planned_neighbor_sum" in text
    # the chip's program under the program's own names (PR 37): every reducer under phase `reduce`, every collective
    # XLA made of the all-gathers and the summed loss (fused `async-collective-*` among them) under `collective`
    table = programs.scope_table_of(text)
    phases = {name: phase for name, (_path, phase, _backward) in table.items()}
    assert {p for p in phases.values()} == {"gather", "reduce", "collective", "dense", "loss", "optimizer", "group"}
    assert [p for n, p in phases.items() if n.startswith("planned_neighbor_sum")] == ["reduce"] * 3 * shards
    wires = [n for n in phases if n.startswith(("all-gather", "all-reduce", "psum", "async-collective"))]
    assert len(wires) >= 4 and {phases[n] for n in wires} == {"collective"}, wires
    gathered = re.findall(r"= (\w+)\[([\d,]+)\]\S* all-gather\(", text)
    assert gathered and {g[0] for g in gathered} == {"f32"}
    # layer 2's tables and their cotangents', the slot group's: every chip's rows, as `[4, rows, W]` or the same bytes flat
    assert {g[1].split(",")[-1] for g in gathered} == {"64", "126"}
    assert all(g[1].split(",")[:-1] in ([str(shards), str(rows)], [str(nb)]) for g in gathered), gathered
    assert "reduce-scatter" not in text and "all-to-all" not in text
    # every row gather reads ONE source's table, and that table lies in the fast memory
    tables = [
        next(line for line in body.splitlines() if " parameter(0)" in line)
        for body in text.split("\n}\n") if " gather(" in body
    ]
    assert len(tables) == 3 * shards and f"f32[{nb}," not in "".join(tables), tables
    assert all(re.search(rf"f32\[{rows},(64|126)\]\S*S\(1\)\}}", t) for t in tables), tables
    assert not any(f"[{nb}," in line for line in text.splitlines() if " gather(" in line)
    memory = compiled.memory_analysis()
    stack = slots * rows * 81
    assert memory.argument_size_in_bytes >= stack > 4 * 2**30
    assert memory.argument_size_in_bytes + memory.temp_size_in_bytes < 8 * 2**30  # half a chip


# -- the one-chip blocks stay the programs they were ---------------------------

#: sha256 of each one-chip epoch block as it LOWERS for a described v5e at the cells' shapes (`_block_fingerprint`).
#: A Mosaic kernel's source locations are in its bytecode and so in the compile cache's key: a PR that moves a line at
#: or above a kernel of `ops/sparse.py`, `ops/sparse_gated.py`, a head's forward or the block's body in `models/stacked.py`
#: changes these, pays a cold compile in every one-chip cell (13 s in PR 30) and says so (PR 37: the named scopes in
#: the block, the heads and the reductions moved lines in all of them); one that means to leave the one-chip path
#: alone (PR 35, PR 36: the sharded path) keeps them. PR 38 changed GAT's and STLGT's walks (their per-entry dot
#: products) and kept GraphSAGE's. PR 39 gave the plan a leaf (`mean_log_degree`) and so moved every kernel of
#: `ops/sparse.py` two lines down: all three changed, GAT's and STLGT's in their locations alone, GraphSAGE's also by
#: one scalar that its slot group's loops hand on with the plan (the compiled block is the parent's instruction for
#: instruction, compared in PR 39); `pna` is new. PR 40 changed `pna`'s alone (`ops/sparse_pna.py`: the ties counted in
#: the forward walk, one walk fewer). `gatv2` came in its own file and left the other four as they were. The update's
#: product over the four aggregates alone (`pna._update`) changed `pna`'s alone. PNA's walks keeping an edge block's own
#: work in VMEM scratch (`_walk_call`'s `scratch`, added in place: no line of `ops/sparse.py` moved) changed `pna`'s
#: alone. The failing assertion prints the new value.
ONE_CHIP_BLOCKS = {
    "graphsage": "3cf6ea2861cdd0862ac02579c2d28aed16fd6a901eabcf7fa37d751c8433e982",
    "gat": "21cc8349f75ef8676b9eabc9a03a4af0aa7c195d7cfb616f0290854bc641defa",
    "stlgt": "191c1ef0f1b612b63642205f0b64b05a82871602b6030360a4a3393929436f70",
    "pna": "64c79a1aea080b56adef8d51933527cee346bfad1e961a9dce94bdb4ddcadec5",
    "gatv2": "2014547782fb49c992aa04ff65d94525ed145b94b1f311210b0b58bafd6fba27",
}


def _block_fingerprint(text: str) -> str:
    """A lowered block's StableHLO with every Mosaic kernel's bytecode read back as text, and the kernels' source
    locations inside the package (file, line, columns) relative to the checkout, so that it is the same wherever
    the checkout lies and whoever called (the caller's own frames are in the bytecode too, and are left out)."""
    import base64
    import hashlib
    import re
    from pathlib import Path

    from jax._src.lib.mlir import ir

    root = str(Path(sparse.__file__).resolve().parents[2])
    places = set()

    def body(match):
        ctx = ir.Context()
        ctx.allow_unregistered_dialects = True  # the serialised kernel is in Mosaic's versioned dialect
        with ctx:
            op = ir.Module.parse(base64.b64decode(match.group(1))).operation
            asm, located = op.get_asm(enable_debug_info=False), op.get_asm(enable_debug_info=True)
        places.update(re.findall(r'loc\("' + re.escape(root) + r'/(kmamiz_tpu/[^"]+)":([\d: to]+)\)', located))
        return "body: " + hashlib.sha256(asm.encode()).hexdigest()

    plain = re.sub(r'body\\22: \\22([A-Za-z0-9+/=]+)\\22', body, text)
    assert places and root not in plain
    return hashlib.sha256((plain + repr(sorted(places))).encode()).hexdigest()


def _lower_one_chip_block(one_chip, model):
    """A one-chip cell's epoch block lowered for a described v5e at the cells' shapes (432 slots, 131,072 nodes, a plan
    of 1,048,576 entries)."""
    slots, nb, eb, width = 432, 131072, 524288, 18
    entries, _tiles, items = sparse.plan_shapes(nb, eb)

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    params = jax.eval_shape(lambda: model.init_params(jax.random.PRNGKey(0), hidden=64, num_features=width))
    opt_state = jax.eval_shape(lambda p: model.make_optimizer(1e-2).init(p), params)
    whole = lambda tree: jax.tree_util.tree_map(lambda a: arg(a.shape, a.dtype), tree)  # noqa: E731
    real_impl, sparse.planned_impl = sparse.planned_impl, lambda: "pallas"  # `jax.default_backend()` sees the CPU
    # a helper two families share (`_weighted_sum`) keeps the call stack of whoever traced it FIRST in the process, and
    # that stack is in the kernel's bytecode: trace afresh, as a process that refreshes one head does
    jax.clear_caches()
    try:
        return stacked.epoch_runner(model, 1e-2, 10.0).fn.lower(
            whole(params), whole(opt_state), arg((slots, nb, width), jnp.float32),
            arg((slots, nb), jnp.float32), arg((slots, nb), jnp.float32), arg((slots, nb), jnp.bool_),
            arg((eb,), jnp.int32), arg((eb,), jnp.int32), arg((eb,), jnp.bool_), 1, _described_plan(arg, nb, entries, items),
        )
    finally:
        sparse.planned_impl = real_impl
        stacked.epoch_runner.cache_clear()


@pytest.mark.parametrize("head", sorted(ONE_CHIP_BLOCKS))
def test_the_one_chip_blocks_lower_to_what_they_lowered_to(one_chip, head):
    """The one-chip cells' epoch blocks at the cells' shapes, the kernels' locations included: what was once compared
    by hand against the parent commits."""
    from kmamiz_tpu.models import gat, gatv2, pna
    from kmamiz_tpu.models.stlgt import model as stlgt_model

    heads = {"graphsage": graphsage, "gat": gat, "gatv2": gatv2, "pna": pna, "stlgt": stlgt_model}
    text = _lower_one_chip_block(one_chip, heads[head]).as_text()
    assert "tpu_custom_call" in text
    assert _block_fingerprint(text) == ONE_CHIP_BLOCKS[head]


def _compiled_one_chip_block(one_chip, model) -> str:
    """The optimized HLO of a one-chip cell's epoch block, compiled for a described v5e."""
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)  # a described chip reads none back
    compilation_cache.reset_cache()
    try:
        return _lower_one_chip_block(one_chip, model).compile().as_text()
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()


def test_stlgt_block_keeps_its_node_arrays_in_the_layout_that_pads_nothing(one_chip):
    """What the chip's compiler makes of `mv100k-stlgt`'s block around the two gated walks: every `f32[131072,64]`
    array (q, k, v, the bias, their cotangents: three hundred instructions) in the layout whose minor dimension is the
    NODES, `{0,1}`. Until PR 38 the walks took `[q | k]` and `[v | g]` of the owner as `[nodes, 128]` operands, and that
    held 284 of the 298 to `{1,0}`, where 64 floats are padded to the 128 lanes of a tile: 4.65 ms a slot update of
    XLA's own part (PERF.md, PR 38), two thirds of that PR's gain in the cell. One such operand brings it back."""
    import re

    from kmamiz_tpu.models.stlgt import model as stlgt_model

    text = _compiled_one_chip_block(one_chip, stlgt_model)
    layouts = re.findall(r"= f32\[131072,64\]\{([0-9,]+)", text)
    assert len(layouts) > 250 and set(layouts) == {"0,1"}, {layout: layouts.count(layout) for layout in set(layouts)}


def test_pna_block_never_makes_the_updates_concatenated_input(one_chip):
    """What the chip's compiler makes of `mv100k-pna`'s block around the update: no `f32[131072,768]` array. The
    update's input `[a | s a | a / s]` was one in each layer, made by in-place slices, kept for `d w_agg`, and met by a
    cotangent of the same shape (58 such instructions, 402 MB each). Now the four aggregates feed ONE product of
    192 columns a layer and the cotangent `[g | s g | g / s]` one of 256, each fused with what makes its operand and
    each in the layout whose minor dimension is the nodes."""
    import re

    from kmamiz_tpu.models import pna

    text = _compiled_one_chip_block(one_chip, pna)
    assert "f32[131072,768]" not in text
    products = re.findall(r"= f32\[131072,(192|256|768)\]\{([0-9,]+)[^}]*\} convolution\(", text)
    assert sorted(products) == [("192", "0,1")] * 2 + [("256", "0,1")] * 2, products
