"""The GATv2 head on the stack's edge plan: the planned dynamic attention
(ops/sparse_gatv2.py: the XLA oracle and the Mosaic walks, interpreted here)
against the plain reference (benchmarks/reference/gatv2.py) on seeded random
rows, forward and every cotangent, hubs, isolated endpoints and empty runs
included; `gatv2.forward` with a plan against itself without one and against
the reference; and `trainer.train(model=gatv2)` through `stacked.epoch_runner`'s
block: the reference's schedule, the span and the checkpoint's name."""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.reference import check
from benchmarks.reference import gatv2 as reference_gatv2
from benchmarks.reference import train as reference_train
from kmamiz_tpu.models import checkpoint, common, gatv2, stacked, trainer
from kmamiz_tpu.ops import edge_plan, sparse, sparse_gatv2
from kmamiz_tpu.telemetry.tracing import TRACER

from test_edge_plan import BE, _case
from test_stlgt_plan import _dataset, _graph, _plan

IMPLS = ("xla", "pallas_interpret")
WALKS = ("score", "softmax", "sum", "edge_dot", "backward")


def _topology(name):
    """(src, dst, edge_mask, bucket_nodes) of a graph of the sibling test files."""
    if name == "heavier_than_a_block":
        return _case(name)
    src, dst, mask, _n, nb = _graph(name)
    return src, dst, mask, nb


def _rows(nb, width, seed=0):
    rng = np.random.default_rng(seed)
    xl, xr, g = (jnp.asarray(rng.normal(size=(nb, width)).astype(np.float32)) for _ in range(3))
    return xl, xr, jnp.asarray(rng.normal(size=(2, width)).astype(np.float32)), g


def _reference_attention(src, dst, mask):
    """Both directions of `reference/gatv2.attend` over the real edges: [N, W]."""
    src, dst = jnp.asarray(src[mask]), jnp.asarray(dst[mask])

    def attention(xl, xr, a):
        return reference_gatv2.attend(xl, xr, src, dst, a[1]) + reference_gatv2.attend(xl, xr, dst, src, a[0])

    return attention


def _value_and_cotangents(attention, xl, xr, a, g):
    out, pull = jax.vjp(attention, xl, xr, a)
    return [np.asarray(v) for v in (out, *pull(g))]


class TestPlannedGatv2:
    @pytest.mark.parametrize("impl", IMPLS)
    @pytest.mark.parametrize("name,width", [("hub_and_isolated", 8), ("heavier_than_a_block", 64), ("two_tiles", 12)])
    def test_value_and_every_cotangent_match_the_plain_reference(self, name, width, impl):
        src, dst, mask, nb = _topology(name)
        host, entries, _items = edge_plan.build_edge_plan(src, dst, mask, nb)
        owner, direction = host.owner[0, :entries], host.direction[0, :entries]
        if name == "hub_and_isolated":
            # an empty run in each direction: an endpoint that only calls, one that is only called, and some with neither
            calls, called = np.unique(owner[direction == 0]), np.unique(owner[direction == 1])
            assert np.setdiff1d(calls, called).size and np.setdiff1d(called, calls).size
            assert (np.asarray(host.degree) == 0).any()
        xl, xr, a, g = _rows(nb, width, seed=width)
        want = _value_and_cotangents(_reference_attention(src, dst, mask), xl, xr, a, g)
        plan = _plan(src, dst, mask, nb)
        got = _value_and_cotangents(partial(sparse_gatv2.planned_gatv2, plan, impl=impl), xl, xr, a, g)
        # float32 sums in another order: a hub's 1,200 terms leave a few ulps of its largest (a bfloat16 row: 2^-9)
        for what, x, y in zip(("out", "d_xl", "d_xr", "d_a"), got, want):
            scale = max(float(np.abs(y).max()), 1.0)
            np.testing.assert_allclose(x, y, rtol=0, atol=2e-5 * scale, err_msg=what)
        if name == "heavier_than_a_block":  # the hub's entries span several edge blocks and are merged
            assert np.unique(np.flatnonzero(owner == 7) // BE).size >= 3

    def test_the_walks_and_the_oracle_agree(self):
        src, dst, mask, nb = _topology("two_tiles")
        plan = _plan(src, dst, mask, nb)
        xl, xr, a, g = _rows(nb, 64, seed=3)
        xla = _value_and_cotangents(partial(sparse_gatv2.planned_gatv2, plan, impl="xla"), xl, xr, a, g)
        walks = _value_and_cotangents(partial(sparse_gatv2.planned_gatv2, plan, impl="pallas_interpret"), xl, xr, a, g)
        for what, x, y in zip(("out", "d_xl", "d_xr", "d_a"), walks, xla):
            np.testing.assert_allclose(x, y, rtol=0, atol=2e-6 * max(float(np.abs(y).max()), 1.0), err_msg=what)

    @pytest.mark.parametrize(
        "differentiated,want",
        [
            # an expand each; `walk.reduce`'s three passes in the other four
            (True, {f"planned_gatv2_{w}": 1 if w == "score" else 4 for w in WALKS}),
            (False, {"planned_gatv2_score": 1, "planned_gatv2_softmax": 4, "planned_gatv2_sum": 4}),
        ],
        ids=("forward_rule", "primal"),
    )
    def test_the_walks_count_their_mxu_products_under_their_own_names(self, differentiated, want):
        src, dst, mask, nb = _topology("hub_and_isolated")
        plan = _plan(src, dst, mask, nb)
        xl, xr, a, g = _rows(nb, 8)
        if differentiated:
            _value_and_cotangents(partial(sparse_gatv2.planned_gatv2, plan, impl="pallas_interpret"), xl, xr, a, g)
        else:
            jax.jit(partial(sparse_gatv2.planned_gatv2, impl="pallas_interpret"))(plan, xl, xr, a)
        stats = sparse.route_stats()
        assert stats["mxu_products"] == want  # no `planned_attention_*`: GAT's readers find none of these
        assert stats["planned"] == stats["gatv2"] == 1 and stats["attention"] == 0

    def test_it_raises_off_the_tpu_as_its_siblings(self):
        src, dst, mask, nb = _topology("hub_and_isolated")
        xl, xr, a, _g = _rows(nb, 8)
        with pytest.raises(Exception):  # Mosaic cannot target a CPU: nothing interprets silently
            jax.block_until_ready(sparse_gatv2.planned_gatv2(_plan(src, dst, mask, nb), xl, xr, a, impl="pallas"))


def _off_zero(params):
    """The zero-initialised leaves off zero, so their gradients are exercised."""
    return params._replace(
        b_1=params.b_1 + 0.1, b_2=params.b_2 - 0.1, w_latency_skip=params.w_latency_skip + 0.05,
        w_anomaly_skip=params.w_anomaly_skip - 0.05,
    )


def _slot(st):
    return (st.features[0], st.src, st.dst, st.edge_mask, st.target_latency[0], st.target_anomaly[0], st.node_mask[0])


class TestHeadOnThePlan:
    @pytest.mark.parametrize("plan_reducer", IMPLS, indirect=True)
    @pytest.mark.parametrize("planned", (True, False), ids=("plan", "no_plan"))
    def test_loss_and_gradient_of_every_parameter_match_the_plain_reference(self, plan_reducer, planned):
        """`benchmarks/reference/gatv2.forward` on the bare graph (no padding, no mask, `.at[]` sums) under the
        reference's own loss, against the head on the stack's padded arrays, with the plan and without."""
        ds = _dataset()
        st = stacked.stack_dataset(ds)
        params = _off_zero(gatv2.init_params(jax.random.PRNGKey(5), hidden=16, num_features=18))
        loss = stacked.head_loss_fn(gatv2, 3.0, **({"plan": st.plan} if planned else {}))
        (got, _aux), got_grad = jax.jit(jax.value_and_grad(loss, has_aux=True))(params, *_slot(st))
        assert sparse.route_stats()["gatv2"] == (2 if planned else 0)  # a layer each
        plain = {k: jnp.asarray(v) for k, v in params._asdict().items() if v is not None}
        with jax.default_matmul_precision("highest"):
            (want, _aux), want_grad = jax.value_and_grad(
                reference_train.make_loss(reference_gatv2.forward, 3.0), has_aux=True
            )(
                plain, jnp.asarray(ds.features[0]), jnp.asarray(ds.src), jnp.asarray(ds.dst),
                jnp.asarray(ds.target_latency[0]), jnp.asarray(ds.target_anomaly[0]), jnp.asarray(ds.node_mask[0]),
            )
        assert ds.num_nodes < st.bucket_nodes
        np.testing.assert_allclose(float(got), float(want), rtol=2e-6)
        for name in plain:
            a, c = np.asarray(getattr(got_grad, name)), np.asarray(want_grad[name])
            np.testing.assert_allclose(a, c, rtol=2e-4, atol=2e-4 * max(float(np.abs(c).max()), 1e-3), err_msg=name)

    def test_the_head_says_what_it_takes(self):
        assert gatv2.NAME == "gatv2" and gatv2.TAKES_PLAN
        assert not getattr(gatv2, "TAKES_NEIGHBOR_SUM_1", False) and not getattr(gatv2, "TAKES_NODE_SHARDS", False)
        st = stacked.stack_dataset(_dataset())
        params = gatv2.init_params(jax.random.PRNGKey(0), hidden=8, num_features=18)
        assert stacked.plan_for(gatv2, st) is st.plan
        assert stacked.slot_group(gatv2, params, st.features, st.plan) == 0  # the score is nonlinear in layer 1's input
        assert params.a_1.shape == params.a_2.shape == (2, 8) and params.w_r_1.shape == (18, 8)


def _reference_run(ds, seed, hidden):
    init = {
        k: np.asarray(v)
        for k, v in gatv2.init_params(jax.random.PRNGKey(seed), hidden=hidden, num_features=18)._asdict().items()
        if v is not None
    }
    params, per_slot = reference_train.train("gatv2", init, ds, 1e-2, precision="highest")
    return init, params, np.mean(np.asarray(per_slot, np.float64), axis=0)


class TestTheCheck:
    """What decides the cell's `correct` for this family (`benchmarks/reference/check.py`): its own FORWARD rule,
    set between the chip's readings (`benchmarks/reference/gatv2.py`), and, at a CPU's size, the reference in the
    program's place passes it where a bfloat16 control does not."""

    def test_the_family_states_the_middle_of_three_between_its_readings(self):
        loss, param = reference_gatv2.FORWARD["default"]
        # the sound program's largest middle 1.39e-7, the bfloat16-z control's smallest 1.32e-6 (v5e, the cell's width)
        assert reference_gatv2.FORWARD_READINGS == 3 and 3 * 1.39e-7 <= loss <= 1.32e-6 / 2
        assert reference_gatv2.FORWARD["highest"] == check.FORWARD["highest"] and param == check.FORWARD["default"][1]

    @pytest.mark.parametrize("control", ("forward_bfloat16_rows", "forward_bfloat16_z"))
    def test_the_reference_in_the_programs_place_passes_and_a_bfloat16_control_does_not(self, monkeypatch, control):
        import collections
        import json
        import sys
        import types
        from pathlib import Path

        from benchmarks.gen import mesh_history

        cfg = json.loads((Path(__file__).parent.parent / "benchmarks/configs/mv100k-gatv2.json").read_text())
        cfg.update(endpoints=256, node_bucket=256, edges=1200, edge_bucket=2048, slots=3)  # the cell's mesh, cut
        full = mesh_history.generate(cfg, 9)
        init = check.to_host(gatv2.init_params(jax.random.PRNGKey(9), hidden=64, num_features=18, num_nodes=0))
        low = types.ModuleType("benchmarks.reference.gatv2_low")
        low.forward = getattr(reference_gatv2, control)
        monkeypatch.setitem(sys.modules, low.__name__, low)

        def in_the_programs_place(family):
            def call(dataset):
                params, per_slot = reference_train.train(family, init, dataset, 1e-2, precision="default")
                mean = np.mean(np.asarray(per_slot, dtype=np.float64), axis=0)
                shaped = collections.namedtuple("Params", sorted(params))(**params)
                return types.SimpleNamespace(
                    losses=[mean[0]], latency_losses=[mean[1]], anomaly_losses=[mean[2]], params=shaped
                )

            mix = {"check_slots": 3, "forward_check_slots": 1}
            return check.against_reference(cfg, lambda n: mesh_history.head(full, n), mix, 9, gatv2, call)

        assert in_the_programs_place("gatv2").ok
        verdict = in_the_programs_place("gatv2_low")
        number = verdict.compared()["forward.default.loss"]
        assert not verdict.ok and number["limit"] == 5e-7 and number["value"] > 3 * number["limit"]


class TestTrainingThroughTheEpochBlock:
    @pytest.mark.parametrize("plan_reducer", IMPLS, indirect=True)
    def test_three_slots_match_the_plain_reference(self, plan_reducer):
        """`benchmarks/reference/train.py`'s sequential schedule over `reference/gatv2.py`: losses and parameters."""
        ds = _dataset(n_slots=3)
        got = trainer.train(ds, epochs=1, hidden=16, lr=1e-2, seed=4, model=gatv2, batch_slots=1)
        assert sparse.route_stats()["gatv2"] > 0
        init, want_params, want = _reference_run(ds, 4, 16)
        np.testing.assert_allclose([got.losses[-1], got.latency_losses[-1], got.anomaly_losses[-1]], want, rtol=2e-5)
        got_params = {k: np.asarray(v) for k, v in got.params._asdict().items() if v is not None}
        assert set(got_params) == set(want_params)  # the reference's dict uses GatV2Params' names
        diff = np.concatenate([(got_params[k] - want_params[k]).ravel() for k in want_params])
        moved = np.concatenate([(want_params[k] - init[k]).ravel() for k in want_params])
        assert np.linalg.norm(diff) <= 2e-2 * np.linalg.norm(moved)

    def test_the_span_the_counters_and_the_checkpoint_name_the_head(self, tmp_path):
        from kmamiz_tpu.core import programs

        ds = _dataset(n_slots=2)
        trainer.train(ds, epochs=1, hidden=8, model=gatv2, checkpoint_dir=str(tmp_path))
        assert checkpoint.load_metadata(str(tmp_path))["model"] == "gatv2"
        tb = [tb for tb in TRACER.traces() if tb.spans[0][0] == "refresh.train"][-1]
        assert tb.counts[0]["model"] == "gatv2" and tb.counts[0]["loss"] == "mse+bce"
        block = [c for i, c in tb.counts.items() if tb.spans[i][0] == "refresh.epoch_block"]
        assert block and block[0]["planned"] == 1 and block[0]["slot_group"] == 0
        assert any(k.startswith("models.sage_epoch_block[kmamiz_tpu.models.gatv2|") for k in programs.all_programs())
        with pytest.raises(ValueError, match="trained with model=gatv2"):
            trainer.train(ds, epochs=2, hidden=8, checkpoint_dir=str(tmp_path))  # another head's refresh

    def test_the_batched_forward_and_the_microbatch_block_reduce_the_edge_list(self):
        ds = _dataset()
        r = trainer.train(ds, epochs=1, hidden=8, seed=1, model=gatv2, batch_slots=2)
        assert sparse.route_stats()["gatv2"] == 0 and np.isfinite(r.losses).all()
        latency, logit = stacked.predict_all(r.params, ds, gatv2)
        assert latency.shape == logit.shape == (len(ds.features), ds.num_nodes) and np.isfinite(latency).all()
        plain = common.make_loss_fn(gatv2.forward, 3.0)  # the served path: no plan
        assert np.isfinite(float(plain(r.params, *_slot(stacked.stack_dataset(ds)))[0]))
