"""The quantile head on the stack's edge plan and under its own loss: the
planned gated sum (ops/sparse_gated.py: the XLA oracle and the Mosaic kernels,
interpreted here) against `stlgt.model.encode`'s segment sums, and
`trainer.train(model=stlgt.model)` through `stacked.epoch_runner`'s block
against the plain reference (benchmarks/reference/stlgt.py)."""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.reference import train as reference_train
from kmamiz_tpu.core.spans import _pad_size
from kmamiz_tpu.models import common, gat, graphsage, stacked, trainer
from kmamiz_tpu.models.stlgt import model as stlgt
from kmamiz_tpu.models.stlgt import trainer as stlgt_trainer
from kmamiz_tpu.ops import sparse, sparse_gated
from kmamiz_tpu.telemetry.tracing import TRACER

from test_edge_plan import BE, _case
from test_planned_attention import _DOT_GRAPHS, _GRAPHS, _dot_is_as_close_as_the_oracles, _item_dots

IMPLS = ("xla", "pallas_interpret")


def _graph(name):
    """(src, dst, edge_mask, real nodes, bucket_nodes): bucket-padded as the
    stack pads, the node bucket larger than the node count."""
    rng = np.random.default_rng(len(name))
    if name == "hub_and_isolated":
        # endpoint 3 calls a third of the mesh and is called by a sixth; the
        # last five endpoints have no edge at all; some edges are masked
        n, e = 300, 900
        src, dst = rng.integers(0, n - 5, e), rng.integers(0, n - 5, e)
        src[:300], dst[300:450] = 3, 3
        mask = (src != dst) & (rng.random(e) < 0.9)
    elif name == "wide_bucket":  # a handful of edges, most of both buckets padding
        n, e = 40, 9
        src, dst = rng.integers(0, n, e), (rng.integers(1, n, e) + np.arange(e)) % n
        mask = src != dst
    elif name == "two_tiles":  # owners in more than one node tile, entries in several blocks
        n, e = 500, 1400
        src, dst = rng.integers(0, n, e), rng.integers(0, n, e)
        mask = src != dst
    else:
        raise KeyError(name)
    nb, eb = _pad_size(n), _pad_size(e)
    assert nb > n
    pad = eb - e
    return (
        np.concatenate([src, np.zeros(pad, int)]).astype(np.int32),
        np.concatenate([dst, np.zeros(pad, int)]).astype(np.int32),
        np.concatenate([mask, np.zeros(pad, bool)]),
        n,
        nb,
    )


GRAPHS = ("hub_and_isolated", "wide_bucket", "two_tiles")


def _plan(src, dst, mask, nb):
    return jax.tree_util.tree_map(jnp.asarray, sparse.build_edge_plan(src, dst, mask, nb)[0])


def _segment_bias(q, k, v, b_edge, src, dst, mask):
    """`encode`'s formulation: per-edge gates, four unsorted segment sums."""
    n = q.shape[0]
    em = mask.astype(jnp.float32)
    gate = jax.nn.sigmoid((q[src] * k[dst]).sum(axis=1) / jnp.sqrt(jnp.float32(q.shape[1])) + b_edge[0]) * em
    src_s, dst_s = jnp.where(mask, src, n), jnp.where(mask, dst, n)
    seg = partial(jax.ops.segment_sum, num_segments=n + 1)
    bias = seg(v[src] * gate[:, None], dst_s)[:-1] + seg(v[dst] * gate[:, None], src_s)[:-1]
    deg = seg(gate, dst_s)[:-1] + seg(gate, src_s)[:-1]
    return bias / jnp.maximum(deg, 1.0)[:, None], gate


def _tables(nb, width, seed=0):
    rng = np.random.default_rng(seed)
    q, k, v, w = (jnp.asarray(rng.normal(size=(nb, width)).astype(np.float32)) for _ in range(4))
    return q, k, v, jnp.asarray([0.3], jnp.float32), w


def _float64_bias_and_gradients(plan, entries, q, k, v, b, w):
    """The bias and the gradients of `(bias * w).sum()` to q, k and v in
    float64 numpy, entry by entry as `sparse_gated`'s docstring writes them."""
    q, k, v, w = (np.asarray(a).astype(np.float64) for a in (q, k, v, w))
    o, n, d = plan.owner[0, :entries], plan.neighbour[:entries], plan.direction[0, :entries]
    scale = np.float64(np.float32(float(q.shape[1]) ** -0.5))
    caller = (d == 0)[:, None]
    mine, theirs = np.where(caller, q[o], k[o]), np.where(caller, k[n], q[n])
    gate = 1.0 / (1.0 + np.exp(-((mine * theirs).sum(axis=1) * scale + np.float64(np.asarray(b)[0]))))
    num, den = np.zeros_like(v), np.zeros(v.shape[0])
    np.add.at(num, o, gate[:, None] * v[n])
    np.add.at(den, o, gate)
    m = np.maximum(den, 1.0)
    g_num = w / m[:, None]
    g_den = np.where(den > 1.0, -(w * num).sum(axis=1) / m**2, 0.0)
    da = ((g_num[o] * v[n]).sum(axis=1) + g_den[o]) * gate * (1.0 - gate) * scale
    d_q, d_k, d_v = np.zeros_like(q), np.zeros_like(k), np.zeros_like(v)
    np.add.at(d_q, np.where(d == 0, o, n), da[:, None] * np.where(caller, k[n], k[o]))
    np.add.at(d_k, np.where(d == 0, n, o), da[:, None] * np.where(caller, q[o], q[n]))
    np.add.at(d_v, n, gate[:, None] * g_num[o])
    return num / m[:, None], d_q, d_k, d_v


def _bound_on_dq_and_dk(plan, entries, q, k, v, b, w):
    """How far the KERNELS' float32 `d q` and `d k` of `(bias * w).sum()` can
    lie from the float64 ones, element by element, a priori: every rounding of
    the path counted at 2^-24 of what it rounds and carried forward to first
    order, nothing measured. A per-entry dot product is `sparse._entry_dot`'s
    sum (12 roundings deep: the product, four halvings of 128 rows, seven adds
    over a vreg's sublanes); a sum over a node's entries through the one-hot
    is at most one rounding an entry and three for the pieces (`deg + 3`); an
    exponential is given two. Between the walks, `num / max(den, 1)` and its
    cotangents are XLA's, on both paths."""
    u = 2.0**-24
    q, k, v, w = (np.asarray(a).astype(np.float64) for a in (q, k, v, w))
    o, n, d = plan.owner[0, :entries], plan.neighbour[:entries], plan.direction[0, :entries]
    width, scale = q.shape[1], np.float64(np.float32(float(q.shape[1]) ** -0.5))
    caller = (d == 0)[:, None]
    deg = np.bincount(o, minlength=q.shape[0]).astype(np.float64)

    def node_sum(x, e_x):  # each node's sum over its entries: the value's, the error's and the summation's own
        total, err, size = (np.zeros((q.shape[0],) + x.shape[1:]) for _ in range(3))
        for into, what in ((total, x), (err, e_x), (size, np.abs(x))):
            np.add.at(into, o, what)
        return total, err + (deg + 3).reshape((-1,) + (1,) * (x.ndim - 1)) * u * size

    # the forward walk: a = <q, k> / sqrt(H) + b, its sigmoid, and the two sums
    terms = np.where(caller, q[o], k[o]) * np.where(caller, k[n], q[n])
    s, e_s = terms.sum(axis=1), 12 * u * np.abs(terms).sum(axis=1)
    a = s * scale + np.float64(np.asarray(b)[0])
    e_a = e_s * scale + u * (np.abs(s) * scale + np.abs(a))
    gate = 1.0 / (1.0 + np.exp(-a))
    e_gate = gate * (1.0 - gate) * e_a + 4 * u * gate  # the exponential, the 1 added, the division
    num, e_num = node_sum(gate[:, None] * v[n], (e_gate[:, None] + u * gate[:, None]) * np.abs(v[n]))
    den, e_den = node_sum(gate, e_gate)
    # between the walks: the cotangents of num and den
    m = np.maximum(den, 1.0)
    g_num = w / m[:, None]
    e_g_num = np.abs(w) * (e_den / m**2)[:, None] + u * np.abs(g_num)
    p, size_p = (w * num).sum(axis=1), np.abs(w * num).sum(axis=1)
    g_den = np.where(den > 1.0, -p / m**2, 0.0)
    e_g_den = ((np.abs(w) * e_num).sum(axis=1) + (width + 3) * u * size_p) / m**2 + 2 * np.abs(p) * e_den / m**3
    # the backward walk: an edge's d a from either of its entries, then [d q | d k]
    x = (v[o] * g_num[n] + g_num[o] * v[n]).sum(axis=1) + g_den[o] + g_den[n]
    e_x = (
        12 * u * (np.abs(v[o] * g_num[n]) + np.abs(g_num[o] * v[n])).sum(axis=1)
        + (np.abs(v[o]) * e_g_num[n] + e_g_num[o] * np.abs(v[n])).sum(axis=1)
        + e_g_den[o] + e_g_den[n] + u * (np.abs(g_den[o] + g_den[n]) + np.abs(x))
    )
    slope = gate * (1.0 - gate)
    da = x * slope * scale
    e_da = (np.abs(x) * (e_gate + 2 * u * slope) + slope * e_x) * scale + 2 * u * np.abs(da)
    bounds = []
    for mine, rows in ((d == 0, k[n]), (d == 1, q[n])):  # d q sums over a node's out-entries, d k over its in-entries
        weight, e_weight = np.where(mine, da, 0.0)[:, None], np.where(mine, e_da, 0.0)[:, None]
        bounds.append(node_sum(weight * rows, (e_weight + u * np.abs(weight)) * np.abs(rows))[1])
    return bounds


class TestPlannedGatedSum:
    @pytest.mark.parametrize(
        "name,width", [("hub_and_isolated", 64), ("wide_bucket", 8), ("two_tiles", 64)]
    )
    def test_the_weighted_sums_are_as_close_to_a_float64_sum_as_the_oracles(self, name, width):
        """The bias, d v and [d q | d k], whose products are float32
        multiplications on the VPU summed through the one-hot (PR 34): against
        float64 they are no further off than `_gated_xla`'s, and the two agree
        at the tolerance they agreed at when the products were six-pass. Since
        PR 38 the gate's and d a's dot products are float32 sums of float32
        products, as the oracle's are and in another order
        (`TestTheGatedWalksEntryDots`): [d q | d k], which both feed, stay
        under the oracle's root mean square, and their LARGEST error is held
        to what the path's own roundings allow (`_bound_on_dq_and_dk`), not to
        the oracle's largest: which of two float32 sums holds the single worst
        of a few thousand elements turns with the seed, at the parent as here
        (PERF.md, PR 38: over 40 seeds of `two_tiles` the kernels' largest
        passed the oracle's in 7 at the parent and in 10 here, and no element
        of 120 runs passed 11% of the bound)."""
        src, dst, mask, _n, nb = _graph(name)
        host, entries, _items = sparse.build_edge_plan(src, dst, mask, nb)
        plan = jax.tree_util.tree_map(jnp.asarray, host)
        q, k, v, b, w = _tables(nb, width, seed=2)
        exact = _float64_bias_and_gradients(host, entries, q, k, v, b, w)
        bound = dict(zip(("d q", "d k"), _bound_on_dq_and_dk(host, entries, q, k, v, b, w)))

        def all_four(impl):
            loss = lambda q, k, v: (sparse_gated.planned_gated_sum(plan, q, k, v, b, impl) * w).sum()  # noqa: E731
            bias = sparse_gated.planned_gated_sum(plan, q, k, v, b, impl)
            return [np.asarray(a) for a in (bias, *jax.jit(jax.grad(loss, argnums=(0, 1, 2)))(q, k, v))]

        for kernel, xla, want, what in zip(
            all_four("pallas_interpret"), all_four("xla"), exact, ("bias", "d q", "d k", "d v")
        ):
            np.testing.assert_allclose(kernel, xla, rtol=2e-6, atol=2e-6, err_msg=what)
            off_kernel, off_xla = (np.abs(a.astype(np.float64) - want) for a in (kernel, xla))
            if what in bound:
                assert (off_kernel <= bound[what]).all(), what
            else:
                assert off_kernel.max() <= off_xla.max(), what
            assert np.sqrt((off_kernel**2).mean()) <= np.sqrt((off_xla**2).mean()), what

    @pytest.mark.parametrize("impl", IMPLS)
    @pytest.mark.parametrize(
        "name,width", [("hub_and_isolated", 8), ("hub_and_isolated", 64), ("wide_bucket", 8), ("two_tiles", 64)]
    )
    def test_values_and_all_four_gradients_against_the_segment_sums(self, name, width, impl):
        src, dst, mask, _n, nb = _graph(name)
        plan = _plan(src, dst, mask, nb)
        q, k, v, b, w = _tables(nb, width)
        edges = (jnp.asarray(src), jnp.asarray(dst), jnp.asarray(mask))

        def planned(q, k, v, b):
            return (sparse_gated.planned_gated_sum(plan, q, k, v, b, impl) * w).sum()

        def segments(q, k, v, b):
            return (_segment_bias(q, k, v, b, *edges)[0] * w).sum()

        want_bias = jax.jit(lambda *a: _segment_bias(*a, *edges)[0])(q, k, v, b)
        got_bias = jax.jit(lambda *a: sparse_gated.planned_gated_sum(plan, *a, impl))(q, k, v, b)
        np.testing.assert_allclose(np.asarray(got_bias), np.asarray(want_bias), rtol=2e-5, atol=2e-6)
        want = jax.jit(jax.grad(segments, argnums=(0, 1, 2, 3)))(q, k, v, b)
        got = jax.jit(jax.grad(planned, argnums=(0, 1, 2, 3)))(q, k, v, b)
        for what, a, c in zip(("d q", "d k", "d v", "d b_edge"), got, want):
            assert a.shape == c.shape, what
            scale = max(float(jnp.abs(c).max()), 1.0)
            np.testing.assert_allclose(np.asarray(a), np.asarray(c), rtol=2e-5, atol=5e-6 * scale, err_msg=what)

    @pytest.mark.parametrize("impl", IMPLS)
    def test_an_edges_two_entries_hold_the_edges_gate(self, impl):
        """One edge u -> v alone: each end's bias is the gate times the other
        end's value (a gate under 1 is divided by 1), and the gate is
        sigmoid(q[u] . k[v] / sqrt(H) + b_edge) seen from either end."""
        nb, width, u, v_ = 16, 8, 5, 11
        plan = _plan(np.asarray([u], np.int32), np.asarray([v_], np.int32), np.asarray([True]), nb)
        q, k, v, b, _w = _tables(nb, width)
        bias = np.asarray(sparse_gated.planned_gated_sum(plan, q, k, v, b, impl))
        gate = float(jax.nn.sigmoid((q[u] * k[v_]).sum() / np.sqrt(width) + b[0]))
        np.testing.assert_allclose(bias[v_], gate * np.asarray(v[u]), rtol=2e-6, atol=1e-6)
        np.testing.assert_allclose(bias[u], gate * np.asarray(v[v_]), rtol=2e-6, atol=1e-6)
        others = np.delete(np.arange(nb), [u, v_])
        assert not bias[others].any()

    def test_the_kernels_and_the_oracle_agree(self):
        src, dst, mask, _n, nb = _graph("two_tiles")
        plan = _plan(src, dst, mask, nb)
        q, k, v, b, _w = _tables(nb, 64, seed=1)
        xla = sparse_gated.planned_gated_sum(plan, q, k, v, b, "xla")
        kernel = sparse_gated.planned_gated_sum(plan, q, k, v, b, "pallas_interpret")
        assert kernel.shape == xla.shape == (nb, 64)
        np.testing.assert_allclose(np.asarray(kernel), np.asarray(xla), rtol=2e-6, atol=2e-6)

    def test_it_is_counted_as_a_planned_reduction_and_raises_off_the_tpu_as_its_siblings(self):
        src, dst, mask, _n, nb = _graph("wide_bucket")
        plan = _plan(src, dst, mask, nb)
        q, k, v, b, _w = _tables(nb, 8)
        sparse_gated.planned_gated_sum(plan, q, k, v, b)
        assert sparse.route_stats() == {"backend": "sparse", "planned": 1, "attention": 0, "sharded": 0, "mxu_products": {}}
        with pytest.raises(Exception):  # Mosaic cannot target a CPU: nothing interprets silently
            jax.block_until_ready(sparse_gated.planned_gated_sum(plan, q, k, v, b, "pallas"))


class TestTheGatedWalksEntryDots:
    """PR 38: the two gated walks' per-entry dot products through
    `sparse._entry_dot`, on the operands the kernels hand it: the owner's
    `[q | k]` (forward) and `[v | g]` (backward) as node rows, the block's
    gathered rows transposed and laid under the half each entry reads."""

    @staticmethod
    def _operands(name, seed, width=64):
        graph = _GRAPHS[name]() if name in _GRAPHS else _case(name)
        host, entries, items = sparse.build_edge_plan(*graph)
        plan = jax.tree_util.tree_map(jnp.asarray, host)
        q, k, v, _b, g = _tables(graph[3], width, seed=seed)
        nodes, half, _scale = sparse_gated._shapes(plan, width)
        own, nbr, d = host.owner[0, :entries], host.neighbour[:entries], host.direction[0, :entries]
        return host, entries, items, plan, (q, k, v, g), nodes, half, own, nbr, (d == 0)[:, None]

    @pytest.mark.parametrize("name", _DOT_GRAPHS)
    def test_the_gates_product_of_q_and_k_under_the_half_each_direction_reads(self, name):
        host, entries, items, plan, (q, k, _v, _g), nodes, half, own, nbr, caller = self._operands(name, 5)
        nqk = np.asarray(sparse._gather_rows(sparse_gated._halves(nodes, half, q, k), plan.neighbour))
        got, _ = _item_dots(
            host, items, np.asarray(sparse_gated._halves(nodes, half, q, k)),
            lambda block, d: sparse_gated._by_half(jnp.asarray(nqk[block * BE : (block + 1) * BE]), d, half),
        )
        mine, theirs = jnp.where(caller, q[own], k[own]), jnp.where(caller, k[nbr], q[nbr])
        oracle = np.asarray((mine * theirs).sum(axis=1))  # `_gated_xla`'s own line
        _dot_is_as_close_as_the_oracles(got[:entries], oracle, np.asarray(mine), np.asarray(theirs))
        assert not got[entries:].any()

    @pytest.mark.parametrize("name", _DOT_GRAPHS)
    def test_the_backwards_product_of_v_g_of_the_owner_and_g_v_of_the_neighbour(self, name):
        """`<v[o], g[n]> + <g[o], v[n]>` in one sum of 128 terms: an edge's d
        gate from either of its entries."""
        host, entries, items, plan, (_q, _k, v, g), nodes, half, own, nbr, _caller = self._operands(name, 6)
        ng = np.asarray(sparse._gather_rows(sparse_gated._halves(nodes, half, g, g[:, :1]), plan.neighbour))
        nv = np.asarray(sparse._gather_rows(sparse_gated._halves(nodes, half, None, v), plan.neighbour))

        def g_over_v(block, _d):
            at = slice(block * BE, (block + 1) * BE)
            return sparse_gated._g_over_v(jnp.asarray(ng[at]).T, jnp.asarray(nv[at]).T, half)

        got, _ = _item_dots(host, items, np.asarray(sparse_gated._halves(nodes, half, v, g)), g_over_v)
        mine = jnp.concatenate([v[own], g[own]], axis=1)
        theirs = jnp.concatenate([g[nbr], v[nbr]], axis=1)
        oracle = np.asarray((mine * theirs).sum(axis=1))
        _dot_is_as_close_as_the_oracles(got[:entries], oracle, np.asarray(mine), np.asarray(theirs))

    def test_the_forward_walk_leaves_the_gate_of_its_items_dot_products(self):
        """`planned_gated_sum`, interpreted, keeps each entry's gate in the
        state it saves: the sigmoid of the items' own dot products, bit for
        bit, on a graph whose third block four tiles meet."""
        host, entries, items, plan, (q, k, v, _g), nodes, half, own, nbr, _caller = self._operands("three_by_three", 7)
        b = jnp.asarray(0.3, jnp.float32)
        _out, saved = sparse_gated._gated_pallas_fwd(plan, q, k, v, b, True)
        nqk, state = np.asarray(saved[2]), np.asarray(saved[4])
        dots, _ = _item_dots(
            host, items, np.asarray(sparse_gated._halves(nodes, half, q, k)),
            lambda block, d: sparse_gated._by_half(jnp.asarray(nqk[block * BE : (block + 1) * BE]), d, half),
        )
        a = jnp.asarray(dots[:entries]) * jnp.float32(64.0 ** -0.5) + b
        np.testing.assert_array_equal(state[sparse_gated.ROW_GATE, :entries], np.asarray(1.0 / (1.0 + jnp.exp(-a))))


def _dataset(n_nodes=150, n_edges=600, n_slots=4, width=18, seed=0):
    rng = np.random.default_rng(seed)
    src = rng.integers(0, n_nodes, n_edges).astype(np.int32)
    dst = ((src + 1 + rng.integers(0, n_nodes - 1, n_edges)) % n_nodes).astype(np.int32)  # no self-loop
    src[:80] = 5  # a hub
    dst[:80] = np.arange(6, 86)
    keep = np.unique(src.astype(np.int64) * n_nodes + dst, return_index=True)[1]
    src, dst = src[np.sort(keep)], dst[np.sort(keep)]
    return trainer.GraphDataset(
        endpoint_names=[f"ep{i}" for i in range(n_nodes)],
        src=src,
        dst=dst,
        edge_mask=np.ones(src.shape[0], bool),
        features=[rng.normal(size=(n_nodes, width)).astype(np.float32) for _ in range(n_slots)],
        target_latency=[rng.normal(size=n_nodes).astype(np.float32) for _ in range(n_slots)],
        target_anomaly=[(rng.random(n_nodes) < 0.1).astype(np.float32) for _ in range(n_slots)],
        node_mask=[rng.random(n_nodes) < 0.9 for _ in range(n_slots)],
        slot_keys=[f"slot{i}" for i in range(n_slots)],
    )


class TestHeadOnThePlan:
    @pytest.mark.parametrize("plan_reducer", IMPLS, indirect=True)
    def test_loss_and_gradient_of_every_parameter_match_the_forward_without_a_plan(self, plan_reducer):
        ds = _dataset()
        st = stacked.stack_dataset(ds)
        params = stlgt.init_params(jax.random.PRNGKey(3), hidden=16, num_features=18)
        # the zero-initialised leaves off zero, so their gradients are exercised
        params = params._replace(
            b_edge=params.b_edge + 0.2, w_quant_skip=params.w_quant_skip + 0.05,
            w_anomaly_skip=params.w_anomaly_skip - 0.05, b_in=params.b_in + 0.1,
        )
        slot = (st.features[0], st.src, st.dst, st.edge_mask, st.target_latency[0], st.target_anomaly[0], st.node_mask[0])
        loss = stlgt.make_loss_fn(3.0)
        want, want_grad = jax.jit(jax.value_and_grad(loss, has_aux=True))(params, *slot)
        got, got_grad = jax.jit(jax.value_and_grad(partial(loss, plan=st.plan), has_aux=True))(params, *slot)
        assert sparse.route_stats()["planned"] == 1
        np.testing.assert_allclose(np.asarray(got[0]), np.asarray(want[0]), rtol=2e-6)
        for name, a, c in zip(params._fields, got_grad, want_grad):
            scale = max(float(jnp.abs(c).max()), 1e-3)
            np.testing.assert_allclose(np.asarray(a), np.asarray(c), rtol=2e-4, atol=2e-5 * scale, err_msg=name)

    def test_the_attribution_is_per_edge_with_and_without_a_plan(self):
        ds = _dataset()
        st = stacked.stack_dataset(ds)
        params = stlgt.init_params(jax.random.PRNGKey(1), hidden=8, num_features=18)
        plain = stlgt.forward_quantiles(params, st.features[0], st.src, st.dst, st.edge_mask)
        planned = stlgt.forward_quantiles(params, st.features[0], st.src, st.dst, st.edge_mask, st.plan)
        assert planned[2].shape == plain[2].shape == st.src.shape
        np.testing.assert_array_equal(np.asarray(planned[2]), np.asarray(plain[2]))
        for a, c in zip(planned[:2], plain[:2]):
            np.testing.assert_allclose(np.asarray(a), np.asarray(c), rtol=2e-5, atol=2e-6)
        # the legacy pair every consumer of a model module expects
        p50, logit = stlgt.forward(params, st.features[0], st.src, st.dst, st.edge_mask, plan=st.plan)
        np.testing.assert_array_equal(np.asarray(p50), np.asarray(planned[0][:, 0]))
        np.testing.assert_array_equal(np.asarray(logit), np.asarray(planned[1]))


class TestTheHeadStatesItsLoss:
    def test_one_loss_function_for_the_epoch_block_and_the_continual_trainer(self):
        assert stlgt.make_loss_fn is stlgt.make_pinball_loss_fn
        assert stlgt.TAKES_PLAN and stlgt.NAME == "stlgt" and not hasattr(stlgt, "TAKES_NEIGHBOR_SUM_1")
        calls = []
        real = stlgt.make_loss_fn

        def counted(*args):
            calls.append(args)
            return real(*args)

        stacked.epoch_runner.cache_clear()
        stlgt_trainer.stlgt_epoch_runner.cache_clear()
        try:
            stlgt.make_loss_fn = counted
            stacked.epoch_runner(stlgt, 1e-2, 3.0)
            stlgt_trainer.stlgt_epoch_runner(stlgt, 1e-2, 3.0, stlgt.QUANTILES)
        finally:
            stlgt.make_loss_fn = real
            stacked.epoch_runner.cache_clear()
            stlgt_trainer.stlgt_epoch_runner.cache_clear()
        assert calls == [(3.0,), (3.0, stlgt.QUANTILES)]

    @pytest.mark.parametrize("model", (graphsage, gat), ids=("graphsage", "gat"))
    def test_a_head_that_states_none_trains_under_the_familys_loss(self, model):
        """The default branch: the mean squared error and weighted
        cross-entropy of `common.make_loss_fn` over the head's forward, with
        the block's plan bound to the forward."""
        assert not hasattr(model, "make_loss_fn")
        ds = _dataset()
        st = stacked.stack_dataset(ds)
        params = model.init_params(jax.random.PRNGKey(0), hidden=8, num_features=18)
        slot = (st.features[0], st.src, st.dst, st.edge_mask, st.target_latency[0], st.target_anomaly[0], st.node_mask[0])
        for bound in ({}, {"plan": st.plan}):
            got = stacked.head_loss_fn(model, 3.0, **bound)(params, *slot)
            want = common.make_loss_fn(partial(model.forward, **bound), 3.0)(params, *slot)
            assert float(got[0]) == float(want[0]) and [float(v) for v in got[1]] == [float(v) for v in want[1]]
        r = trainer.train(ds, epochs=2, hidden=8, model=model)
        assert np.isfinite(r.losses).all() and r.losses[1] < r.losses[0]

    def test_the_heads_own_loss_takes_the_blocks_plan_by_keyword(self):
        ds = _dataset()
        st = stacked.stack_dataset(ds)
        params = stlgt.init_params(jax.random.PRNGKey(0), hidden=8, num_features=18)
        slot = (st.features[0], st.src, st.dst, st.edge_mask, st.target_latency[0], st.target_anomaly[0], st.node_mask[0])
        total, (quantile, anomaly) = stacked.head_loss_fn(stlgt, 3.0, plan=st.plan)(params, *slot)
        assert sparse.route_stats()["planned"] == 1
        # not the squared error of p50 a head without a loss of its own gets
        family = common.make_loss_fn(stlgt.forward, 3.0)(params, *slot)
        assert float(total) == pytest.approx(float(quantile) + float(anomaly), rel=1e-6)
        assert float(anomaly) == pytest.approx(float(family[1][1]), rel=1e-5)
        assert abs(float(quantile) - float(family[1][0])) > 0.05 * float(family[1][0])


def _reference_run(ds, seed, hidden, lr=1e-2):
    init = {
        k: np.asarray(v)
        for k, v in stlgt.init_params(jax.random.PRNGKey(seed), hidden=hidden, num_features=18)._asdict().items()
    }
    params, per_slot = reference_train.train("stlgt", init, ds, lr)
    reference_train.compiled.cache_clear()
    return init, params, np.mean(np.asarray(per_slot, dtype=np.float64), axis=0)


class TestTrainingThroughTheEpochBlock:
    @pytest.mark.parametrize("plan_reducer", IMPLS, indirect=True)
    def test_three_slots_match_the_plain_reference_under_the_familys_own_loss(self, plan_reducer):
        """`benchmarks/reference/train.py`'s sequential schedule under
        `reference/stlgt.py`'s pinball loss: losses and parameters."""
        ds = _dataset(n_slots=3)
        got = trainer.train(ds, epochs=1, hidden=16, lr=1e-2, seed=4, model=stlgt, batch_slots=1)
        assert sparse.route_stats()["planned"] > 0
        init, want_params, want = _reference_run(ds, 4, 16)
        triple = [got.losses[-1], got.latency_losses[-1], got.anomaly_losses[-1]]
        np.testing.assert_allclose(triple, want, rtol=2e-5)
        got_params = {k: np.asarray(v) for k, v in got.params._asdict().items()}
        assert set(got_params) == set(want_params)  # the reference's dict uses StlgtParams' names
        diff = np.concatenate([(got_params[k] - want_params[k]).ravel() for k in want_params])
        moved = np.concatenate([(want_params[k] - init[k]).ravel() for k in want_params])
        assert np.linalg.norm(diff) <= 2e-2 * np.linalg.norm(moved)

    def test_with_and_without_a_plan_the_same_losses(self, monkeypatch):
        ds = _dataset()
        planned = trainer.train(ds, epochs=2, hidden=8, seed=1, model=stlgt)
        assert sparse.route_stats()["planned"] > 0
        monkeypatch.setenv("KMAMIZ_SPARSE", "xla")  # hands no plan to anything
        sparse.reset_for_tests()
        stacked.epoch_runner.cache_clear()
        assert stacked.plan_for(stlgt, stacked.stack_dataset(ds)) is None
        plain = trainer.train(ds, epochs=2, hidden=8, seed=1, model=stlgt)
        assert sparse.route_stats()["planned"] == 0
        stacked.epoch_runner.cache_clear()
        for a, c in ((planned.losses, plain.losses), (planned.latency_losses, plain.latency_losses),
                     (planned.anomaly_losses, plain.anomaly_losses)):
            np.testing.assert_allclose(a, c, rtol=1e-4, atol=1e-6)
        # and the legacy per-slot loop, which holds no plan either
        legacy = trainer.train(ds, epochs=2, hidden=8, seed=1, model=stlgt, fused=False)
        np.testing.assert_allclose(legacy.losses, plain.losses, rtol=1e-4, atol=1e-6)

    def test_quantiles_never_cross_after_training(self):
        ds = _dataset()
        r = trainer.train(ds, epochs=3, hidden=8, lr=5e-2, seed=2, model=stlgt)
        assert r.losses[-1] < r.losses[0] and r.latency_losses[-1] < r.latency_losses[0]
        st = stacked.stack_dataset(ds)
        for s in range(st.num_slots):
            q, _logit, _gate = stlgt.forward_quantiles(r.params, st.features[s], st.src, st.dst, st.edge_mask, st.plan)
            q = np.asarray(q)
            assert (q[:, 0] <= q[:, 1]).all() and (q[:, 1] <= q[:, 2]).all()

    def test_the_microbatch_block_trains_under_the_heads_loss_without_a_plan(self):
        ds = _dataset()
        r = trainer.train(ds, epochs=2, hidden=8, seed=1, model=stlgt, batch_slots=2)
        assert sparse.route_stats()["planned"] == 0  # the vmapped grads reduce the edge list
        one = trainer.train(ds, epochs=1, hidden=8, seed=1, model=stlgt)
        # the first epoch's quantile loss is of the same size as the sequential block's
        assert np.isfinite(r.losses).all() and r.latency_losses[0] == pytest.approx(one.latency_losses[0], rel=0.2)

    def test_the_span_and_the_checkpoint_name_the_head_and_its_loss(self, tmp_path):
        from kmamiz_tpu.core import programs
        from kmamiz_tpu.models import checkpoint

        ds = _dataset(n_slots=2)
        trainer.train(ds, epochs=1, hidden=8, model=stlgt, checkpoint_dir=str(tmp_path))
        assert checkpoint.load_metadata(str(tmp_path))["model"] == "stlgt"
        tb = [tb for tb in TRACER.traces() if tb.spans[0][0] == "refresh.train"][-1]
        assert tb.counts[0]["model"] == "stlgt" and tb.counts[0]["loss"] == "pinball+bce"
        block = [c for i, c in tb.counts.items() if tb.spans[i][0] == "refresh.epoch_block"]
        assert block and block[0]["planned"] == 1 and block[0]["slot_group"] == 0
        # the program's key names the head's module
        assert any(
            k.startswith("models.sage_epoch_block[kmamiz_tpu.models.stlgt.model|") for k in programs.all_programs()
        )
        # a sibling's span names the family's loss
        trainer.train(ds, epochs=1, hidden=8)
        tb = [tb for tb in TRACER.traces() if tb.spans[0][0] == "refresh.train"][-1]
        assert tb.counts[0]["model"] == "graphsage" and tb.counts[0]["loss"] == "mse+bce"
