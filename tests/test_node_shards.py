"""A history that one device cannot hold: the stack cut by NODES over the
local devices (models/stacked.py), a plan a shard (ops/edge_plan.build_shard_plans),
the neighbour table all-gathered (ops/sparse_sum.sharded_neighbor_sum), the epoch
block under `shard_map` with the one-device block's own body. On the eight
host devices `conftest.py` gives, at a small size: 1,000 endpoints in a 1,024
bucket, four shards of 256 rows, a hub whose entries weigh a shard down, and
(in the cases below) a shard that holds nothing.

What decides the layout is the data (`parallel/mesh.node_shards`); a test
that wants a small history sharded says so by patching what that rule reads,
never through an argument of the program."""
from __future__ import annotations

import dataclasses
import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax import shard_map
from jax.sharding import PartitionSpec as P

from kmamiz_tpu.models import common, gat, gatv2, graphsage, pna, stacked, trainer
from kmamiz_tpu.ops import edge_plan, sparse, sparse_sum
from kmamiz_tpu.parallel import mesh as mesh_mod
from kmamiz_tpu.telemetry.tracing import TRACER

ROOT = Path(__file__).resolve().parent.parent
AXIS = mesh_mod.NODES_AXIS


def _graph(name, seed=0):
    """(src, dst, mask, nodes, node bucket), bucket-padded as the stack pads."""
    rng = np.random.default_rng(seed)
    if name == "hub":  # node 250 owns 300 out-edges: its entries straddle what even node counts would cut
        n, nb, e, eb = 1000, 1024, 5000, 8192
        src = rng.integers(0, n, e)
        dst = (src + 1 + rng.integers(0, n - 1, e)) % n
        src[:300], dst[:300] = 250, np.arange(300, 600)
    elif name == "roomy_hub":  # the same hub with rows to spare, as a deployment's bucket has
        n, nb, e, eb = 800, 1024, 5000, 8192
        src = rng.integers(0, n, e)
        dst = (src + 1 + rng.integers(0, n - 1, e)) % n
        src[:600], dst[:600] = 150, np.arange(200, 800)
    elif name == "empty_shard":  # sixty nodes: the last of four ranges holds none of them
        n, nb, e, eb = 60, 1024, 200, 256
        src, dst = rng.integers(0, n, e), rng.integers(0, n, e)
    elif name == "full_bucket":  # no spare row: the cuts cannot follow the entries far
        n, nb, e, eb = 512, 512, 3000, 4096
        src, dst = rng.integers(0, n, e), rng.integers(0, 40, e)
    elif name == "no_edges":
        n, nb, e, eb = 300, 512, 0, 8
        src, dst = np.zeros(0, int), np.zeros(0, int)
    else:
        raise KeyError(name)
    pad = eb - e
    return (
        np.concatenate([src, np.zeros(pad, int)]).astype(np.int32),
        np.concatenate([dst, np.zeros(pad, int)]).astype(np.int32),
        np.concatenate([np.ones(e, bool), np.zeros(pad, bool)]),
        n, nb,
    )


GRAPHS = ("hub", "empty_shard", "full_bucket", "no_edges")


def _rows(cuts, n, rows):
    """Where node i lies in the table of a plan cut at `cuts`."""
    shard_of = np.searchsorted(cuts, np.arange(n), side="right") - 1
    return shard_of * rows + np.arange(n) - np.asarray(cuts)[shard_of]


def _exact_sum(h, src, dst, mask):
    out = np.zeros(h.shape, np.float64)
    s, d = src[mask], dst[mask]
    np.add.at(out, s, h[d].astype(np.float64))
    np.add.at(out, d, h[s].astype(np.float64))
    return out


def _laid_out(name, shards, width=8, seed=1):
    """A graph's shard plans (device arrays, a leading shard axis) and a
    random table, as the whole `[Nb, W]` in the plans' row order and as the
    nodes' own `[N, W]`."""
    src, dst, mask, n, nb = _graph(name)
    plans, cuts, entries, items = edge_plan.build_shard_plans(src, dst, mask, n, nb, shards)
    h = np.random.default_rng(seed).normal(size=(n, width)).astype(np.float32)
    table = np.zeros((nb, width), np.float32)
    table[_rows(cuts, n, nb // shards)] = h
    return (src, dst, mask, n, nb), jax.tree_util.tree_map(jnp.asarray, plans), cuts, h, table


def _over_the_mesh(shards, fn, *specs):
    return shard_map(
        fn, mesh=mesh_mod.nodes_mesh(shards), in_specs=specs, out_specs=P(AXIS), check_vma=False
    )


def _first(plans):
    return jax.tree_util.tree_map(lambda a: a[0], plans)


# -- the plan of a shard -------------------------------------------------------


class TestShardPlans:
    @pytest.mark.parametrize("name", GRAPHS + ("roomy_hub",))
    def test_one_shard_is_the_unsharded_plan(self, name):
        """Every leaf, the item list among them: one function makes the items of both (a hub's too)."""
        src, dst, mask, n, nb = _graph(name)
        plans, cuts, entries, items = edge_plan.build_shard_plans(src, dst, mask, n, nb, 1)
        whole, n_entries, n_items = edge_plan.build_edge_plan(src, dst, mask, nb)
        assert cuts.tolist() == [0, n] and entries == [n_entries] and items == [n_items]
        for got, want in zip(plans, whole):
            assert got.shape == (1,) + want.shape and got.dtype == want.dtype
            np.testing.assert_array_equal(got[0], want)

    @pytest.mark.parametrize("name", GRAPHS + ("roomy_hub",))
    @pytest.mark.parametrize("shards", (2, 4))
    def test_every_entry_lies_with_its_owner_once_and_shapes_are_one(self, name, shards):
        """A shard's entries (owner in its range) cut once more by the SOURCE,
        the shard their neighbour lives on: every real entry in exactly one
        (owner, source) sub-plan, the neighbour a row of that source's table."""
        src, dst, mask, n, nb = _graph(name)
        plans, cuts, entries, items = edge_plan.build_shard_plans(src, dst, mask, n, nb, shards)
        rows = nb // shards
        row_of = _rows(cuts, n, rows)
        assert cuts[0] == 0 and cuts[-1] == n and (np.diff(cuts) >= 0).all() and (np.diff(cuts) <= rows).all()
        want = sorted(
            [(row_of[s], row_of[d], 0) for s, d in zip(src[mask], dst[mask])]
            + [(row_of[d], row_of[s], 1) for s, d in zip(src[mask], dst[mask])]
        )
        assert len(entries) == len(items) == shards * shards  # flat, owner-major
        assert plans.degree.shape == (shards, rows) and plans.neighbour.shape[:2] == (shards, shards)
        got = []
        for i, one in enumerate(edge_plan.sub_plans(plans)):
            d, source = divmod(i, shards)
            k, held = entries[i], cuts[d + 1] - cuts[d]
            owner, neighbour = one.owner[0], one.neighbour
            assert (np.diff(owner[:k]) >= 0).all() and (owner[:k] < held).all()  # owners ascend within a source
            assert (np.diff(owner[:k] * 2 + one.direction[0, :k]) >= 0).all()  # and the directions within an owner
            assert (owner[k:] == rows).all()  # parked past every tile
            assert (neighbour[:k] < cuts[source + 1] - cuts[source]).all() and (neighbour[k:] == 0).all()  # a row of the SOURCE's table
            got += [(d * rows + o, source * rows + nb_, dr) for o, nb_, dr in zip(owner[:k], neighbour[:k], one.direction[0, :k])]
            assert (one.item_flag[: items[i]] >= 0).all() and (one.item_flag[items[i]:] == -1).all()
            np.testing.assert_array_equal(one.degree, plans.degree[d])  # a shard's whole degree, whatever the source
            if k == 0:  # nothing of this source for this owner: a plan that writes zeros
                zeros = sparse_sum._planned_reduce_xla(
                    jax.tree_util.tree_map(jnp.asarray, one), jnp.ones((owner.shape[0], 4), jnp.float32))
                assert zeros.shape[0] >= rows and not np.asarray(zeros).any()
        assert sorted(got) == want and sum(entries) == 2 * int(mask.sum())
        by_owner = np.reshape(entries, (shards, shards)).sum(axis=1)
        for d in range(shards):  # the sources' entries add up to the owner's, and to its degree
            assert by_owner[d] == plans.degree[d].sum() == sum(1 for o, _n, _d in want if o // rows == d)
        # one shape, a function of the buckets while the fullest pair fits it, wider by eighths where a hub does not let it
        base, tiles, n_items = edge_plan.plan_shapes(rows, src.shape[0] // shards // shards)
        step = max(base // 8, edge_plan.PLAN_EDGE_BLOCK)
        width = plans.owner.shape[3]
        assert plans.owner.shape == (shards, shards, 1, width) and plans.item_tile.shape == (shards, shards, tiles + width // edge_plan.PLAN_EDGE_BLOCK)
        assert width == max(base, -(-max(entries) // step) * step)
        assert (width > base) == (name == "full_bucket")  # 40 nodes draw every edge: a pair outgrows the base

    def test_the_cuts_follow_the_entries_not_the_nodes(self):
        src, dst, mask, n, nb = _graph("roomy_hub")
        _plans, cuts, by_source, _items = edge_plan.build_shard_plans(src, dst, mask, n, nb, 4)
        entries = np.reshape(by_source, (4, 4)).sum(axis=1).tolist()  # an owner's, over its sources
        degree = np.bincount(np.concatenate([src[mask], dst[mask]]), minlength=n)
        assert degree.max() > 600 and np.diff(cuts).max() < 256
        assert max(entries) - min(entries) <= degree.max()  # even to within the heaviest node's entries
        by_nodes = [int(degree[lo:hi].sum()) for lo, hi in zip(range(0, n, 200), range(200, n + 1, 200))]
        assert max(by_nodes) * 4 / sum(by_nodes) > 1.1 > 1.01 > max(entries) * 4 / sum(entries)

    def test_a_range_never_outgrows_its_rows_and_the_rest_still_fits(self):
        degree = np.zeros(512, np.int64)
        degree[:40] = 100  # all the entries in the first forty nodes of a bucket without a spare row
        cuts = edge_plan.shard_cuts(degree, 4, 128)
        assert cuts.tolist() == [0, 128, 256, 384, 512]
        cuts = edge_plan.shard_cuts(np.ones(300, np.int64), 4, 128)
        assert cuts.tolist() == [0, 75, 150, 225, 300]
        assert edge_plan.shard_cuts(np.zeros(10, np.int64), 4, 128).tolist() == [0, 2, 5, 7, 10]
        with pytest.raises(ValueError, match="do not fit"):
            edge_plan.shard_cuts(np.ones(600, np.int64), 4, 128)


# -- the sharded sum -------------------------------------------------------------


class TestShardedSum:
    @pytest.mark.parametrize("name", GRAPHS)
    def test_the_shards_rows_concatenated_are_the_unsharded_sum(self, name):
        """The share test: what the four devices make, side by side, IS the
        sum one device makes of the whole table, and the exact one."""
        (src, dst, mask, n, nb), plans, cuts, h, table = _laid_out(name, 4)
        sharded = _over_the_mesh(
            4, lambda p, rows: sparse_sum.planned_neighbor_sum(edge_plan.ShardPlan(_first(p), AXIS), rows), P(AXIS), P(AXIS)
        )
        got = np.asarray(jax.jit(sharded)(plans, jnp.asarray(table)))
        assert got.shape == table.shape
        assert sparse.route_stats()["sharded"] == sparse.route_stats()["planned"] == 1
        one_plan, *_ = edge_plan.build_shard_plans(src, dst, mask, n, nb, 1)
        whole = np.asarray(sparse_sum.planned_neighbor_sum(_first(jax.tree_util.tree_map(jnp.asarray, one_plan)), jnp.asarray(np.pad(h, ((0, nb - n), (0, 0))))))
        row_of = _rows(cuts, n, nb // 4)
        np.testing.assert_allclose(got[row_of], whole[:n], rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(got[row_of], _exact_sum(h, src, dst, mask), rtol=1e-5, atol=1e-5)
        padding = np.ones(nb, bool)
        padding[row_of] = False
        assert not got[padding].any()

    @pytest.mark.parametrize("name", ("hub", "empty_shard"))
    def test_the_vjp_is_the_all_gather_and_the_same_plan(self, name):
        """A is symmetric: the cotangent of the local rows is the sharded sum
        of the cotangent, and equals autodiff of the exact sum."""
        (src, dst, mask, n, nb), plans, cuts, h, table = _laid_out(name, 4)
        g = np.random.default_rng(5).normal(size=table.shape).astype(np.float32)

        def pulled_back(p, rows, ct):
            plan = edge_plan.ShardPlan(_first(p), AXIS)
            out, vjp = jax.vjp(lambda r: sparse_sum.planned_neighbor_sum(plan, r), rows)
            return jnp.stack([out, vjp(ct)[0], sparse_sum.planned_neighbor_sum(plan, ct)])[None]

        out, d_rows, forward_of_ct = np.moveaxis(np.asarray(
            jax.jit(_over_the_mesh(4, pulled_back, P(AXIS), P(AXIS), P(AXIS)))(plans, jnp.asarray(table), jnp.asarray(g))
        ), 1, 0).reshape(3, *table.shape)
        np.testing.assert_array_equal(d_rows, forward_of_ct)
        row_of = _rows(cuts, n, nb // 4)
        np.testing.assert_allclose(d_rows[row_of], _exact_sum(g[row_of], src, dst, mask), rtol=1e-5, atol=1e-5)
        hlo = jax.jit(_over_the_mesh(4, pulled_back, P(AXIS), P(AXIS), P(AXIS))).lower(plans, jnp.asarray(table), jnp.asarray(g)).as_text()
        # the table forward, the cotangent backward and once more for `forward_of_ct`; nothing is sent back
        assert hlo.count("stablehlo.all_gather") == 3 and "reduce_scatter" not in hlo and "all_reduce" not in hlo

    def test_the_interpreted_kernel_sums_a_sub_plan_over_its_sources_table(self):
        """The Mosaic reducer on one (owner, source) sub-plan against that
        source's own table; the sources' parts add up to the owner's rows."""
        (src, dst, mask, n, nb), plans, cuts, h, table = _laid_out("hub", 4, width=18)
        rows = nb // 4
        want = _exact_sum(h, src, dst, mask)
        for d in (0, 3):
            total = np.zeros((rows, 18), np.float32)
            for source in range(4):
                one = edge_plan.source_plan(jax.tree_util.tree_map(lambda a: a[d], plans), source)
                mine = jnp.asarray(table[source * rows : (source + 1) * rows])
                got = np.asarray(sparse_sum.planned_neighbor_sum(one, mine, "pallas_interpret"))
                again = np.asarray(sparse_sum.planned_neighbor_sum(one, mine, "xla"))
                assert got.shape == (rows, 18)
                np.testing.assert_allclose(got, again, rtol=1e-5, atol=1e-5)
                total += got
            k = cuts[d + 1] - cuts[d]
            np.testing.assert_allclose(total[:k], want[cuts[d] : cuts[d + 1]], rtol=1e-5, atol=1e-5)
            assert not total[k:].any()

    @pytest.mark.parametrize("impl", ("xla", "pallas_interpret"))
    def test_the_sharded_sum_walks_the_sources_through_either_reducer(self, impl):
        """`sharded_neighbor_sum` itself, the Mosaic reducer interpreted: four
        gathers from four tables, four reductions, the parts added."""
        (src, dst, mask, n, nb), plans, cuts, h, table = _laid_out("hub", 4, width=18)
        sharded = _over_the_mesh(
            4, lambda p, rows: sparse_sum.planned_neighbor_sum(edge_plan.ShardPlan(_first(p), AXIS), rows, impl), P(AXIS), P(AXIS)
        )
        lowered = jax.jit(sharded).lower(plans, jnp.asarray(table)).as_text()
        assert lowered.count("stablehlo.all_gather") == 1 and lowered.count("optimization_barrier") == 4
        got = np.asarray(jax.jit(sharded)(plans, jnp.asarray(table)))
        np.testing.assert_allclose(got[_rows(cuts, n, nb // 4)], _exact_sum(h, src, dst, mask), rtol=1e-5, atol=1e-5)

    def test_every_row_that_crosses_is_float32(self):
        (_g, plans, _cuts, _h, table) = _laid_out("hub", 4)
        fn = _over_the_mesh(
            4, lambda p, rows: sum(jax.value_and_grad(
                lambda r: (sparse_sum.planned_neighbor_sum(edge_plan.ShardPlan(_first(p), AXIS), r) ** 2).sum())(rows)),
            P(AXIS), P(AXIS),
        )
        gathers = [ln for ln in jax.jit(fn).lower(plans, jnp.asarray(table)).as_text().splitlines() if "all_gather" in ln]
        assert len(gathers) == 2 and all("xf32>" in ln and "bf16" not in ln for ln in gathers)


# -- the loss and the block ------------------------------------------------------


def _dataset(n=1000, e=5000, slots=9, width=18, seed=0):
    rng = np.random.default_rng(seed)
    src = rng.integers(0, n, e).astype(np.int32)
    dst = ((src + 1 + rng.integers(0, n - 1, e)) % n).astype(np.int32)
    hub = min(300, e // 3)
    src[:hub], dst[:hub] = n // 4, np.arange(n // 4 + 50, n // 4 + 50 + hub) % n
    keep = np.unique(src.astype(np.int64) * n + dst, return_index=True)[1]
    src, dst = src[keep], dst[keep]
    return trainer.GraphDataset(
        endpoint_names=[f"ep{i}" for i in range(n)], src=src, dst=dst, edge_mask=np.ones(len(src), bool),
        features=[rng.normal(size=(n, width)).astype(np.float32) for _ in range(slots)],
        target_latency=[rng.normal(size=n).astype(np.float32) for _ in range(slots)],
        target_anomaly=[(rng.random(n) < 0.1).astype(np.float32) for _ in range(slots)],
        node_mask=[rng.random(n) < 0.95 for _ in range(slots)],
        slot_keys=[f"s{i}" for i in range(slots)],
    )


def _again(ds):
    """The same history in arrays of its own: no memo of `ds` knows it."""
    return dataclasses.replace(ds, src=ds.src.copy(), dst=ds.dst.copy(), edge_mask=ds.edge_mask.copy())


@pytest.fixture
def four_shards(monkeypatch):
    """Every history stacked in this test is too large for one device and
    fits four: the rule's own reading of the device, patched."""
    monkeypatch.setattr(mesh_mod, "node_shards", lambda nbytes: 4)
    yield 4
    stacked.node_sharded_epoch_runner.cache_clear()


class TestShardedLoss:
    def test_partial_losses_and_gradients_add_up_to_the_one_device_blocks(self, four_shards):
        """The guide's share test, for the whole slot update: each device's
        share of the loss and of the parameter gradient, added, is what one
        device computes for the whole graph."""
        ds = _dataset(slots=1)
        st = stacked.stack_dataset(ds)
        assert st.shards == 4
        params = graphsage.init_params(jax.random.PRNGKey(0), hidden=16, num_features=18)

        def share(params, f, tl, ta, nm, plan):
            def partial_loss(p):
                lat, logit = graphsage.forward(p, f[0], None, None, None, plan=edge_plan.ShardPlan(_first(plan), AXIS))
                w = nm[0].astype(jnp.float32)
                count = jnp.maximum(jax.lax.psum(w.sum(), AXIS), 1.0)  # a count: no gradient goes through it
                bce = optax.sigmoid_binary_cross_entropy(logit, ta[0])
                return (jnp.sum(w * (lat - tl[0]) ** 2) + jnp.sum(w * (1.0 + 2.0 * ta[0]) * bce)) / count

            loss, grads = jax.value_and_grad(partial_loss)(params)
            return jax.tree_util.tree_map(lambda a: a[None], (loss, grads))

        rows = P(None, AXIS)
        losses, grads = jax.jit(shard_map(
            share, mesh=st.mesh, in_specs=(P(), rows, rows, rows, rows, P(AXIS)), out_specs=P(AXIS), check_vma=False,
        ))(params, st.features, st.target_latency, st.target_anomaly, st.node_mask, st.plan)
        whole = _one_device_stack(ds)
        loss_fn = common.make_loss_fn(lambda *a: graphsage.forward(*a, plan=whole.plan), 3.0)
        (want, _aux), want_grads = jax.value_and_grad(loss_fn, has_aux=True)(
            params, whole.features[0], whole.src, whole.dst, whole.edge_mask,
            whole.target_latency[0], whole.target_anomaly[0], whole.node_mask[0],
        )
        assert losses.shape == (4,) and (np.asarray(losses) > 0).all()
        np.testing.assert_allclose(np.asarray(losses).sum(), float(want), rtol=2e-6)
        for got, ref in zip(jax.tree_util.tree_leaves(grads), jax.tree_util.tree_leaves(want_grads)):
            assert got.shape == (4,) + ref.shape
            np.testing.assert_allclose(np.asarray(got).sum(axis=0), np.asarray(ref), rtol=2e-5, atol=2e-7)

    def test_the_familys_loss_over_the_axis_is_the_whole_loss_on_every_device(self, four_shards):
        ds = _dataset(slots=1)
        st = stacked.stack_dataset(ds)
        params = graphsage.init_params(jax.random.PRNGKey(1), hidden=16, num_features=18)

        def device(params, f, tl, ta, nm, plan):
            loss_fn = stacked.head_loss_fn(graphsage, 3.0, plan=edge_plan.ShardPlan(_first(plan), AXIS))
            (loss, (lat, ano)), grads = jax.value_and_grad(loss_fn, has_aux=True)(
                params, f[0], None, None, None, tl[0], ta[0], nm[0]
            )
            return jax.tree_util.tree_map(lambda a: a[None], (jnp.stack([loss, lat, ano]), grads))

        rows = P(None, AXIS)
        losses, grads = jax.jit(shard_map(
            device, mesh=st.mesh, in_specs=(P(), rows, rows, rows, rows, P(AXIS)), out_specs=P(AXIS), check_vma=False,
        ))(params, st.features, st.target_latency, st.target_anomaly, st.node_mask, st.plan)
        whole = _one_device_stack(ds)
        (want, (lat, ano)), want_grads = jax.value_and_grad(stacked.head_loss_fn(graphsage, 3.0, plan=whole.plan), has_aux=True)(
            params, whole.features[0], whole.src, whole.dst, whole.edge_mask,
            whole.target_latency[0], whole.target_anomaly[0], whole.node_mask[0],
        )
        losses = np.asarray(losses)
        assert (losses == losses[0]).all()  # the same number on every device
        np.testing.assert_allclose(losses[0], [float(want), float(lat), float(ano)], rtol=2e-6)
        for got, ref in zip(jax.tree_util.tree_leaves(grads), jax.tree_util.tree_leaves(want_grads)):
            got = np.asarray(got)
            assert (got == got[0]).all()  # and the whole gradient
            np.testing.assert_allclose(got[0], np.asarray(ref), rtol=2e-5, atol=2e-7)

    def test_without_an_axis_the_loss_traces_to_what_it_was(self):
        ds = _dataset(n=60, e=200, slots=1)
        st = stacked.stack_dataset(ds)
        params = graphsage.init_params(jax.random.PRNGKey(0), hidden=8, num_features=18)
        args = (params, st.features[0], st.src, st.dst, st.edge_mask, st.target_latency[0], st.target_anomaly[0], st.node_mask[0])
        jaxpr = str(jax.make_jaxpr(common.make_loss_fn(graphsage.forward, 3.0))(*args))
        assert "psum" not in jaxpr and "custom_vjp" not in jaxpr
        assert jaxpr == str(jax.make_jaxpr(common.make_loss_fn(graphsage.forward, 3.0, axis_name=None))(*args))


def _one_device_stack(ds):
    """A copy of `ds` stacked on one device, whatever the test patched."""
    patched, mesh_mod.node_shards = mesh_mod.node_shards, lambda nbytes: 1
    try:
        st = stacked.stack_dataset(_again(ds))
    finally:
        mesh_mod.node_shards = patched
    assert st.shards == 1 and st.mesh is None
    return st


# -- the refresh -----------------------------------------------------------------


def _refresh_counts(name):
    tb = [tb for tb in TRACER.traces() if tb.spans[0][0] == "refresh.train"][-1]
    return [dict(tb.counts.get(i, {})) for i, span in enumerate(tb.spans) if span[0] == name]


def _leaves(params):
    return [np.asarray(a) for a in jax.tree_util.tree_leaves(params)]


class TestShardedRefresh:
    @pytest.mark.parametrize("slots,group", [(9, 7), (1, 0)])
    def test_train_sharded_is_train_on_one_device(self, monkeypatch, slots, group):
        """`trainer.train`, called as the benchmark's driver calls it: the
        same losses and parameters whether the history lies on one device or
        on four, with the slot group and without."""
        ds = _dataset(slots=slots)
        one = trainer.train(ds, epochs=2, hidden=16, lr=1e-2, seed=3)
        assert _refresh_counts("refresh.train")[0]["layout"] == "device"
        monkeypatch.setattr(mesh_mod, "node_shards", lambda nbytes: 4)
        sparse.reset_for_tests()
        four = trainer.train(_again(ds), epochs=2, hidden=16, lr=1e-2, seed=3)
        stacked.node_sharded_epoch_runner.cache_clear()
        counts = _refresh_counts("refresh.train")[0]
        assert (counts["shards"], counts["nodes_per_shard"], counts["layout"]) == (4, 256, "nodes")
        assert _refresh_counts("refresh.epoch_block")[0] == {
            "epochs": 2, "slot_updates": 2 * slots, "planned": 1, "slot_group": group,
        }
        stats = sparse.route_stats()
        assert stats["sharded"] == stats["planned"] == 2  # at trace time: layer 1's (or the group's) and layer 2's
        np.testing.assert_allclose(four.losses, one.losses, rtol=2e-6)
        np.testing.assert_allclose(four.anomaly_losses, one.anomaly_losses, rtol=2e-6)
        for got, want in zip(_leaves(four.params), _leaves(one.params)):
            np.testing.assert_allclose(got, want, rtol=1e-4, atol=2e-6)

    @pytest.mark.parametrize("group", (None, 0))
    def test_train_sharded_follows_the_plain_reference(self, four_shards, monkeypatch, group):
        """Against `benchmarks/reference/train.py` (a Python loop of per-slot
        updates, unsharded by nature): one update a slot, in slot order, over
        all endpoints, the slot group on and off."""
        from benchmarks.reference import check, train as ref_train

        if group == 0:
            monkeypatch.setattr(stacked, "slot_group", lambda *a, **k: 0)
        ds = _dataset(slots=9)
        got = trainer.train(ds, epochs=1, hidden=16, lr=1e-2, seed=4)
        assert stacked.stack_dataset(ds).shards == 4
        assert _refresh_counts("refresh.epoch_block")[0]["slot_group"] == (7 if group is None else 0)
        init = check.to_host(graphsage.init_params(jax.random.PRNGKey(4), hidden=16, num_features=18))
        want_params, per_slot = ref_train.train("graphsage_sharded", init, ds, 1e-2, precision="highest")
        want = np.mean(np.asarray(per_slot, np.float64), axis=0)
        np.testing.assert_allclose(check.triple(got), want, rtol=1e-5)
        got_params = check.to_host(got.params)
        moved = np.sqrt(sum(((want_params[k] - init[k]) ** 2).sum() for k in init))
        off = np.sqrt(sum(((got_params[k] - want_params[k]) ** 2).sum() for k in init))
        assert off / moved < 1e-3

    def test_the_head_of_a_sharded_history_is_sharded_as_it_is(self, monkeypatch):
        """Datasets over one graph share a plan, and with it its layout: the
        check's short head runs the program the window runs."""
        ds = _dataset(slots=9)
        nbytes = 9 * 1024 * (4 * 18 + 9)
        monkeypatch.setattr(mesh_mod, "device_bytes_limit", lambda device=None: nbytes // 2 + 4096)
        st = stacked.stack_dataset(ds)
        assert st.shards == 4  # a quarter fits half a device, a half does not
        head = trainer.GraphDataset(
            endpoint_names=ds.endpoint_names, src=ds.src, dst=ds.dst, edge_mask=ds.edge_mask,
            features=ds.features[:2], target_latency=ds.target_latency[:2],
            target_anomaly=ds.target_anomaly[:2], node_mask=ds.node_mask[:2], slot_keys=ds.slot_keys[:2],
        )
        assert mesh_mod.node_shards(2 * 1024 * (4 * 18 + 9)) == 1  # by its own bytes it would lie on one
        st_head = stacked.stack_dataset(head)
        assert st_head.shards == 4 and st_head.plan is st.plan and st_head.node_cuts == st.node_cuts
        assert stacked.stack_dataset(_again(head)).shards == 1  # arrays of its own: no plan to follow
        trainer.train(head, epochs=1, hidden=8, seed=0)
        assert _refresh_counts("refresh.train")[0]["shards"] == 4
        stacked.node_sharded_epoch_runner.cache_clear()

    def test_what_cannot_shard_yet_is_refused_by_name(self, four_shards):
        from kmamiz_tpu.models.stlgt import model as stlgt_model

        ds = _dataset(n=300, e=900, slots=2)
        with pytest.raises(NotImplementedError, match=r"gat cannot train over a history cut by nodes over 4 devices"):
            trainer.train(ds, epochs=1, hidden=8, model=gat)
        with pytest.raises(NotImplementedError, match=r"stlgt cannot train over a history cut by nodes"):
            trainer.train(ds, epochs=1, hidden=8, model=stlgt_model)
        with pytest.raises(NotImplementedError, match=r"pna cannot train over a history cut by nodes over 4 devices"):
            trainer.train(ds, epochs=1, hidden=8, model=pna)  # a maximum over an owner's entries knows no mesh axis
        with pytest.raises(NotImplementedError, match=r"gatv2 cannot train over a history cut by nodes over 4 devices"):
            trainer.train(ds, epochs=1, hidden=8, model=gatv2)  # a softmax over an owner's entries knows no mesh axis
        with pytest.raises(NotImplementedError, match="node embeddings cannot train"):
            trainer.train(ds, epochs=1, hidden=8, use_node_embeddings=True)
        with pytest.raises(NotImplementedError, match="slot microbatches"):
            trainer.train(ds, epochs=1, hidden=8, batch_slots=2)
        with pytest.raises(NotImplementedError, match="no batched forward"):
            stacked.predict_all(graphsage.init_params(jax.random.PRNGKey(0), hidden=8, num_features=18), ds, graphsage)

    def test_the_legacy_knob_hands_no_plan_and_a_sharded_stack_says_so(self, four_shards, monkeypatch):
        monkeypatch.setenv("KMAMIZ_SPARSE", "xla")
        sparse.reset_for_tests()
        with pytest.raises(NotImplementedError, match="KMAMIZ_SPARSE=xla hands none over"):
            trainer.train(_dataset(n=300, e=900, slots=2), epochs=1, hidden=8)


# -- the stack and the rule ------------------------------------------------------


class TestLayout:
    def test_the_rule(self, monkeypatch):
        """The fewest power of two of local devices whose share is at most
        half a device's memory: what one device holds stays on one."""
        gib = 2**30
        monkeypatch.setattr(mesh_mod, "device_bytes_limit", lambda device=None: int(15.75 * gib))
        assert len(jax.local_devices()) == 8
        assert mesh_mod.node_shards(4_586_471_424) == 1  # mv100k-*: 432 slots
        assert mesh_mod.node_shards(7_644_119_040) == 1  # the same at 720 slots
        assert mesh_mod.node_shards(18_345_885_696) == 4  # mv400k-sage: half of it passes half a device
        assert mesh_mod.node_shards(30_576_476_160) == 4  # at 720 slots
        assert mesh_mod.node_shards(int(7.875 * gib)) == 1 and mesh_mod.node_shards(int(7.875 * gib) + 1) == 2
        with pytest.raises(RuntimeError, match=r"needs 16 devices .* this machine has 8"):
            mesh_mod.node_shards(100 * gib)
        monkeypatch.setattr(mesh_mod, "device_bytes_limit", lambda device=None: None)
        assert mesh_mod.node_shards(10**15) == 1  # a device that reports nothing: the host's memory is its own

    def test_a_cpu_reports_no_limit_and_every_stack_lies_on_one_device(self):
        assert mesh_mod.device_bytes_limit() is None
        st = stacked.stack_dataset(_dataset(n=300, e=900, slots=2))
        assert st.shards == 1 and st.mesh is None and st.node_cuts == (0, 300)
        assert not hasattr(st.features.sharding, "mesh") or len(st.features.sharding.device_set) == 1

    def test_the_stack_lies_shard_by_shard_and_is_the_history(self, four_shards):
        ds = _dataset(slots=3)
        st = stacked.stack_dataset(ds)
        assert (st.shards, st.bucket_nodes, st.layout()["bucket_nodes"]) == (4, 1024, 1024)
        assert st.features.shape == (3, 1024, 18) and st.node_mask.shape == (3, 1024)
        assert [s.data.shape for s in st.features.addressable_shards] == [(3, 256, 18)] * 4
        assert len({s.device for s in st.features.addressable_shards}) == 4
        assert st.plan.owner.shape[0] == 4 and [s.data.shape[0] for s in st.plan.neighbour.addressable_shards] == [1] * 4
        row_of = _rows(st.node_cuts, 1000, 256)
        for s in range(3):
            np.testing.assert_array_equal(np.asarray(st.features)[s][row_of], ds.features[s])
            np.testing.assert_array_equal(np.asarray(st.node_mask)[s][row_of], ds.node_mask[s])
            np.testing.assert_array_equal(np.asarray(st.target_latency)[s][row_of], ds.target_latency[s])
        padding = np.ones(1024, bool)
        padding[row_of] = False
        assert not np.asarray(st.node_mask)[:, padding].any() and not np.asarray(st.features)[:, padding].any()
        assert st.plan_entries == 2 * len(ds.src) and st.node_cuts[0] == 0 and st.node_cuts[-1] == 1000
        assert stacked.stack_dataset(ds) is st  # memoised on the dataset as ever

    def test_spans_and_counts_of_a_sharded_build(self, four_shards):
        ds = _dataset(slots=3)
        st = stacked.stack_dataset(ds)
        tb = TRACER.traces()[-1]
        assert [s[0] for s in tb.spans] == ["refresh.stack", "refresh.stack.plan"] + [
            "refresh.stack.host_fill", "refresh.stack.device_put"
        ] * 4
        by_name = {}
        for i, span in enumerate(tb.spans):
            by_name.setdefault(span[0], []).append(tb.counts.get(i, {}))
        plan = by_name["refresh.stack.plan"][0]
        assert plan["shards"] == 4 and sum(plan["shard_entries"]) == plan["entries"] == 2 * len(ds.src)
        assert len(plan["shard_items"]) == len(plan["shard_blocks"]) == 4 and sum(plan["shard_nodes"]) == 1000
        # the cut by sources: a table a source, the (owner, source) pairs' real entries and items, flat and owner-major
        assert plan["source_tables"] == 4 and len(plan["source_entries"]) == len(plan["source_items"]) == 16
        assert np.reshape(plan["source_entries"], (4, 4)).sum(axis=1).tolist() == plan["shard_entries"]
        assert np.reshape(plan["source_items"], (4, 4)).sum(axis=1).tolist() == plan["shard_items"] and sum(plan["source_items"]) == plan["items"]
        assert st.plan.owner.shape[:2] == (4, 4) and st.plan.degree.shape == (4, 256)
        assert [c["shard"] for c in by_name["refresh.stack.device_put"]] == [0, 1, 2, 3]
        assert by_name["refresh.stack"][0]["shards"] == 4 and by_name["refresh.stack"][0]["nodes_per_shard"] == 256
        fills = [c["bytes"] for c in by_name["refresh.stack.host_fill"]]
        st = stacked.stack_dataset(ds)
        whole = sum(int(np.asarray(a).nbytes) for a in (
            st.features, st.target_latency, st.target_anomaly, st.node_mask, st.src, st.dst, st.edge_mask))
        assert sum(fills) == whole == by_name["refresh.stack"][0]["bytes"]

    def test_the_sharded_block_is_a_registered_program_that_counts_its_runs(self, four_shards):
        from kmamiz_tpu.core import programs

        ds = _dataset(n=300, e=900, slots=2)
        trainer.train(ds, epochs=1, hidden=8, seed=0, lr=0.0123)  # a rate no other test trains at: a key of its own
        snapshot = programs.snapshot()
        trainer.train(ds, epochs=1, hidden=8, seed=0, lr=0.0123)
        assert sum(programs.new_compiles_since(snapshot).values()) == 0  # warm: no compile in a second call
        mine = [p for name, p in programs.all_programs().items()
                if name.startswith("models.sage_epoch_block[kmamiz_tpu.models.graphsage|0.0123|") and name.endswith("|nodes4]")]
        assert len(mine) == 1 and mine[0].stats()["runs"] >= 2 and mine[0].recent_runs()[-1][2] == 2
        assert stacked._resolve_epoch_runner("kmamiz_tpu.models.graphsage|0.01|10.0|nodes4") is None


# -- the cell at a tiny size, through the benchmark's own command ------------------


def test_a_tiny_node_sharded_cell_runs_through_the_benchmarks_command(tmp_path, capsys, monkeypatch):
    """`benchmarks/run.py` as the driver runs it, with a configuration of the
    new family at a size a CPU trains in a second, on four of the host's
    devices: no argument says "sharded", the check runs the sharded program on
    its three-slot heads too, and the result is `correct`."""
    from benchmarks import run
    from benchmarks.harness import device
    from kmamiz_tpu.core import compile_cache

    monkeypatch.setattr(device, "require", lambda chips: jax.devices()[:chips])
    # `run.main` turns the process's persistent compile cache on, for good: not in a worker that goes on
    monkeypatch.setattr(compile_cache, "enable", compile_cache.cache_dir)
    full = json.loads((ROOT / "benchmarks" / "configs" / "mv400k-sage.json").read_text())
    cfg = {**full, "name": "tiny", "endpoints": 900, "node_bucket": 1024, "edges": 4000, "edge_bucket": 4096, "slots": 8}
    nbytes = cfg["slots"] * cfg["node_bucket"] * 81
    monkeypatch.setattr(mesh_mod, "device_bytes_limit", lambda device=None: nbytes // 2 + 4096)
    (tmp_path / "tiny.json").write_text(json.dumps(cfg))
    doc = json.loads((ROOT / "BENCHMARK.json").read_text())
    doc["paths"] = [str(ROOT / "benchmarks")]
    doc["configs"] = [{"name": "tiny", "source": "t", "file": str(tmp_path / "tiny.json"), "reduced": [], "why": "t"}]
    doc["workloads"] = [{"name": "mv400k-sage.refresh", "config": "tiny", "traffic": "refresh", "chips": 4, "why": "t"}]
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(doc))
    for traced in (0, 1):
        code = run.main(["--manifest", str(tmp_path / "BENCHMARK.json"), "--workload", "mv400k-sage.refresh",
                         "--seed", str(2**31 + 35), "--seconds", "0.2", "--trace", str(traced)])
        assert code == 0
        line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
        assert line["correct"] is True and line["failed"] == 0 and line["device"]["count"] == 4
        assert all(n["value"] <= n["limit"] for n in line["compared"].values())
        if traced:  # a CPU's trace holds no device plane: the span readers read, the trace readers are silent
            assert line["metrics"]["shard.plan_imbalance"]["value"] >= 1.0
            assert line["metrics"]["setup.shard_upload_s"]["value"] > 0
            assert line["metrics"]["setup.plan_s"]["value"] > 0
            assert line["metrics"]["epoch_block.compiles"]["value"] == 0
            assert line["metrics"]["epoch_block.run_ms_per_slot"]["value"] > 0
            assert "collective.ms_per_slot" not in line["metrics"]
    assert _refresh_counts("refresh.train")[0]["shards"] == 4
    stacked.node_sharded_epoch_runner.cache_clear()
