"""The PNA head on the stack's edge plan: the planned multi-aggregate
(ops/sparse_pna.py: the XLA oracle and the three Mosaic kernels, interpreted
here) against float64 segment reductions, ties and empty neighbourhoods
included; `pna.forward` with a plan against itself without one and against the
plain reference (benchmarks/reference/pna.py); and `trainer.train(model=pna)`
through `stacked.epoch_runner`'s block: the reference's schedule, a
checkpoint saved and resumed, the span and the checkpoint's name."""
from __future__ import annotations

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
    """(src, dst, edge_mask, bucket_nodes) of a graph of either test file."""
    if name in ("heavier_than_a_block", "self_loops_and_repeats"):
        return _case(name)
    src, dst, mask, _n, nb = _graph(name)
    return src, dst, mask, nb


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
        assert sparse.route_stats() == {"backend": "sparse", "planned": 1, "attention": 0, "sharded": 0, "mxu_products": {}}
        with pytest.raises(Exception):  # Mosaic cannot target a CPU: nothing interprets silently
            jax.block_until_ready(sparse_pna.planned_aggregate(plan, m, "pallas"))
        with pytest.raises(NotImplementedError, match="merge a running maximum across the sources"):
            sparse_pna.planned_aggregate(sparse.ShardPlan(plan, "nodes"), m)

    def test_the_walks_count_their_mxu_products(self):
        src, dst, mask, nb = _topology("wide_bucket")
        m, weights = _messages(nb, 8)
        _aggregates_and_gradient(_plan(src, dst, mask, nb), m, weights, "pallas_interpret")
        assert sparse.route_stats()["mxu_products"] == {
            "planned_aggregate": 7, "planned_aggregate_ties": 2, "planned_aggregate_backward": 4,
        }


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

    def test_the_head_says_what_it_takes(self):
        assert pna.NAME == "pna" and pna.TAKES_PLAN
        assert not getattr(pna, "TAKES_NEIGHBOR_SUM_1", False) and not getattr(pna, "TAKES_NODE_SHARDS", False)
        st = stacked.stack_dataset(_dataset())
        params = pna.init_params(jax.random.PRNGKey(0), hidden=8, num_features=18)
        assert stacked.plan_for(pna, st) is st.plan
        assert stacked.slot_group(pna, params, st.features, st.plan) == 0  # a maximum does not commute with a product
        assert params.w_agg_1.shape == (12 * 8, 8) and params.w_msg_1.shape == (18, 8)


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
