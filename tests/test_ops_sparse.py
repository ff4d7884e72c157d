"""graftsparse parity + segment-growth acceptance (ISSUE 13).

Per-consumer parity against the legacy XLA paths: service scorers
(bit-exact integer lanes, fp32-tolerance relying factor across all three
sparse rf branches), the packed dependency walk (edge-multiset equality),
and the model plane's two neighbour reductions (GraphSAGE
``neighbor_mean`` with a plan against without, the STLGT gated neighbour
bias against a per-edge loop). Plus the
segment-append capacity growth contract: one capacity crossing completes
with ZERO new compiles of any registered program, while the legacy
repack mode recompiles — and both modes hold identical edge sets.
"""
import collections

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from kmamiz_tpu.analysis import guards
from kmamiz_tpu.core import programs
from kmamiz_tpu.graph.store import EndpointGraph
from kmamiz_tpu.ops import scorers, sparse, window

EXACT_LANES = (
    "instability_on",
    "instability_by",
    "instability",
    "ais",
    "ads",
    "acs",
    "is_gateway",
)


def _scorer_case(seed, n_ep, n_svc, cap, frac_valid=0.8, dist_hi=8, dist_lo=0):
    rng = np.random.default_rng(seed)
    return (
        jnp.asarray(rng.integers(0, n_ep, cap).astype(np.int32)),
        jnp.asarray(rng.integers(0, n_ep, cap).astype(np.int32)),
        jnp.asarray(rng.integers(dist_lo, dist_hi, cap).astype(np.int32)),
        jnp.asarray(rng.random(cap) < frac_valid),
        jnp.asarray(rng.integers(0, n_svc, n_ep).astype(np.int32)),
        jnp.asarray(rng.integers(0, 50, n_ep).astype(np.int32)),
        jnp.asarray(rng.random(n_ep) < 0.7),
    )


def _assert_scores_match(legacy, got, ctx=""):
    for lane in EXACT_LANES:
        a = np.asarray(getattr(legacy, lane))
        b = np.asarray(getattr(got, lane))
        assert (a == b).all(), f"{ctx} lane {lane}"
    rl = np.asarray(legacy.relying_factor)
    rs = np.asarray(got.relying_factor)
    assert np.allclose(rl, rs, rtol=1e-5, atol=1e-5), (
        f"{ctx} relying_factor max err {np.abs(rl - rs).max()}"
    )


class TestScorerParity:
    """service_scores_sparse vs the legacy lexsort pipeline."""

    @pytest.mark.parametrize(
        "seed,n_svc,n_ep,cap,zero_dist",
        [
            (0, 7, 20, 128, True),
            (1, 16, 64, 256, False),
            (2, 33, 100, 500, True),  # non-pow2 capacity
            (3, 100, 333, 1024, False),
            (4, 5, 8, 16, True),
            (5, 64, 257, 777, False),  # non-pow2 capacity + ep count
        ],
    )
    def test_partition_path_parity(self, seed, n_svc, n_ep, cap, zero_dist):
        args = _scorer_case(seed, n_ep, n_svc, cap, dist_lo=0 if zero_dist else 1)
        legacy = scorers.service_scores_xla(*args, num_services=n_svc)
        got = scorers.service_scores_sparse(
            *args, num_services=n_svc, dist_bits=3
        )
        _assert_scores_match(legacy, got, f"seed {seed}")

    def test_dist_bits4_fallback_parity(self):
        # dist up to 15: the per-distance dcap-loop payload fallback
        for seed in (0, 1):
            args = _scorer_case(seed, 100, 17, 500, dist_hi=16)
            legacy = scorers.service_scores_xla(*args, num_services=17)
            got = scorers.service_scores_sparse(
                *args, num_services=17, dist_bits=4
            )
            _assert_scores_match(legacy, got, f"dist_bits=4 seed {seed}")

    def test_w420_payload_fallback_parity(self):
        # dist_bits=3 but 2*S*n_ep overflows int32: the partition packing
        # is rejected and the single-pass w420 payload branch runs
        n_svc, n_ep, cap = 30_000, 40_000, 4096
        assert 2 * n_svc * n_ep >= 2**31 - 1
        args = _scorer_case(5, n_ep, n_svc, cap, dist_lo=1)
        legacy = scorers.service_scores_xla(*args, num_services=n_svc)
        got = scorers.service_scores_sparse(
            *args, num_services=n_svc, dist_bits=3
        )
        _assert_scores_match(legacy, got, "w420 fallback")

    def test_empty_graph_all_lanes_zero(self):
        args = _scorer_case(99, 16, 5, 64, frac_valid=0.0)
        legacy = scorers.service_scores_xla(*args, num_services=5)
        got = scorers.service_scores_sparse(*args, num_services=5, dist_bits=3)
        for lane in EXACT_LANES + ("relying_factor",):
            a = np.asarray(getattr(legacy, lane))
            b = np.asarray(getattr(got, lane))
            assert (a == b).all(), lane

    def test_padding_invariance(self):
        # the same valid edges at two capacities score identically
        base = _scorer_case(11, 64, 16, 500, frac_valid=1.0, dist_lo=1)
        src, dst, dist, mask = (np.asarray(a) for a in base[:4])
        pad = 1024 - 500
        wide = (
            jnp.asarray(np.concatenate([src, np.zeros(pad, np.int32)])),
            jnp.asarray(np.concatenate([dst, np.zeros(pad, np.int32)])),
            jnp.asarray(np.concatenate([dist, np.zeros(pad, np.int32)])),
            jnp.asarray(np.concatenate([mask, np.zeros(pad, bool)])),
        ) + base[4:]
        a = scorers.service_scores_sparse(*base, num_services=16, dist_bits=3)
        b = scorers.service_scores_sparse(*wide, num_services=16, dist_bits=3)
        _assert_scores_match(a, b, "padding")

    def test_dispatcher_routes_on_knob_and_promise(self, monkeypatch):
        args = _scorer_case(3, 100, 17, 256, dist_lo=1)
        sparse_name = "scorers.service_scores_sparse"
        legacy_name = "scorers.service_scores"

        def calls():
            reg = programs.all_programs()
            return {
                n: reg[n].calls for n in (sparse_name, legacy_name) if n in reg
            }

        monkeypatch.setenv("KMAMIZ_SPARSE", "sparse")
        sparse.reset_for_tests()
        before = calls()
        scorers.service_scores(*args, num_services=17, dist_bits=3)
        after = calls()
        assert after[sparse_name] > before.get(sparse_name, 0)

        # no dist_bits promise -> legacy even with the knob on
        before = calls()
        scorers.service_scores(*args, num_services=17)
        after = calls()
        assert after[legacy_name] > before.get(legacy_name, 0)

        monkeypatch.setenv("KMAMIZ_SPARSE", "xla")
        sparse.reset_for_tests()
        before = calls()
        scorers.service_scores(*args, num_services=17, dist_bits=3)
        after = calls()
        assert after[legacy_name] > before[legacy_name]
        assert after[sparse_name] == before[sparse_name]


class TestWalkParity:
    """dependency_edges_packed_sparse emits the packed walk's multiset."""

    @staticmethod
    def _multiset(e):
        anc = np.asarray(e.ancestor_ep).reshape(-1)
        desc = np.asarray(e.descendant_ep).reshape(-1)
        dist = np.asarray(e.distance).reshape(-1)
        flat = np.asarray(e.mask).reshape(-1)
        return collections.Counter(
            zip(anc[flat].tolist(), desc[flat].tolist(), dist[flat].tolist())
        )

    def test_random_forests_match_dense_walk(self):
        from kmamiz_tpu.core import spans as spans_mod
        from kmamiz_tpu.core.spans import pack_trace_rows

        rng = np.random.default_rng(21)
        for _ in range(3):
            sizes = rng.integers(1, 64, rng.integers(3, 30)).tolist()
            n = int(sum(sizes))
            trace_of = np.repeat(
                np.arange(len(sizes), dtype=np.int32), sizes
            )
            parent = np.full(n, -1, dtype=np.int32)
            kind = np.zeros(n, dtype=np.int8)
            start = 0
            for size in sizes:
                for j in range(1, size):
                    parent[start + j] = start + int(rng.integers(0, j))
                kind[start : start + size] = np.where(
                    rng.random(size) < 0.4,
                    spans_mod.KIND_CLIENT,
                    spans_mod.KIND_SERVER,
                )
                start += size
            ep = rng.integers(0, 500, n).astype(np.int32)
            packed = pack_trace_rows(trace_of, n, parent)
            assert packed is not None
            inputs = (
                jnp.asarray(packed.pack(packed.parent_slots(parent), -1)),
                jnp.asarray(packed.pack(kind, 0)),
                jnp.asarray(packed.pack(np.ones(n, bool), False)),
                jnp.asarray(packed.pack(ep, 0)),
            )
            dense = window.dependency_edges_packed(*inputs)
            got = window.dependency_edges_packed_sparse(*inputs)
            assert self._multiset(got) == self._multiset(dense)


class TestModelPlaneReductions:
    """What is left of the model plane's neighbour reductions: the plan's
    where the caller holds a plan, XLA's gathers and segment sums where it
    does not, and no environment value that chooses a third."""

    @staticmethod
    def _graph(seed, n, e, f):
        rng = np.random.default_rng(seed)
        return (
            jnp.asarray(rng.normal(size=(n, f)).astype(np.float32)),
            jnp.asarray(rng.integers(0, n, e).astype(np.int32)),
            jnp.asarray(rng.integers(0, n, e).astype(np.int32)),
            jnp.asarray(rng.random(e) < 0.8),
        )

    @pytest.mark.parametrize("value", ("pallas", "pallas_interpret"))
    def test_backend_refuses_the_removed_values_by_name(self, monkeypatch, value):
        monkeypatch.setenv("KMAMIZ_SPARSE", value)
        sparse.reset_for_tests()
        with pytest.raises(ValueError) as err:
            sparse.backend()
        message = str(err.value)
        assert repr(value) in message
        assert "'xla'" in message and "'sparse'" in message
        assert message.count("pallas") == 1  # the refused value, not an option

    @pytest.mark.parametrize(
        "platform, env, want",
        (("cpu", None, "xla"), ("tpu", None, "pallas"), ("tpu", "xla", "pallas")),
        ids=("cpu", "tpu", "tpu-env-xla"),
    )
    def test_planned_impl_follows_the_platform_alone(
        self, monkeypatch, platform, env, want
    ):
        if env is None:
            monkeypatch.delenv("KMAMIZ_SPARSE", raising=False)
        else:
            monkeypatch.setenv("KMAMIZ_SPARSE", env)
        sparse.reset_for_tests()
        monkeypatch.setattr(jax, "default_backend", lambda: platform)
        assert sparse.planned_impl() == want
        if env is not None:  # and the knob off the TPU changes nothing either
            monkeypatch.setattr(jax, "default_backend", lambda: "cpu")
            assert sparse.planned_impl() == "xla"

    @pytest.mark.parametrize("nodes", (48, 2048, 4096))
    def test_neighbor_mean_with_a_plan_against_without(self, nodes):
        """Values and the gradient of a scalar loss, on both sides of the
        2,048 rows the removed kernel's node table was held to."""
        from kmamiz_tpu.models import graphsage

        h, src, dst, mask = self._graph(nodes, nodes, 4 * nodes, 12)
        plan = jax.tree_util.tree_map(
            jnp.asarray,
            sparse.build_edge_plan(
                np.asarray(src), np.asarray(dst), np.asarray(mask), nodes
            )[0],
        )
        weights = jnp.asarray(
            np.random.default_rng(1).normal(size=h.shape).astype(np.float32)
        )

        def loss(x, plan_):
            mean = graphsage.neighbor_mean(x, src, dst, mask, plan=plan_)
            return (mean * weights).sum(), mean

        (_, want), want_grad = jax.value_and_grad(loss, has_aux=True)(h, None)
        (_, got), got_grad = jax.value_and_grad(loss, has_aux=True)(h, plan)
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(got_grad, want_grad, rtol=1e-5, atol=1e-5)
        assert np.abs(np.asarray(want_grad)).max() > 0


def _stlgt_case():
    """A 12-node bucket (9 real rows), 24 edge slots: masked edges, padding
    edges parked past the bucket, a self loop, a repeated edge, and node 8
    on no edge at all."""
    from kmamiz_tpu.models.stlgt import model as stlgt

    rng = np.random.default_rng(7)
    n, real, e, hidden = 12, 9, 24, 8
    feats = np.zeros((n, stlgt.NUM_FEATURES), np.float32)
    feats[:real] = rng.normal(size=(real, stlgt.NUM_FEATURES))
    src = rng.integers(0, 8, e).astype(np.int32)
    dst = rng.integers(0, 8, e).astype(np.int32)
    src[0], dst[0] = 3, 3  # a self loop
    src[1], dst[1] = src[2], dst[2]  # the same edge twice
    mask = rng.random(e) < 0.75
    mask[:3] = True
    src[-4:], dst[-4:], mask[-4:] = n, n, False  # bucket padding
    params = stlgt.init_params(jax.random.PRNGKey(3), hidden=hidden)
    params = params._replace(b_edge=jnp.asarray([0.3], jnp.float32))
    return stlgt, params, feats, src, dst, mask


class TestStlgtNeighborBias:
    """The gated neighbour bias of `stlgt.model`, the model's own XLA path
    (what the removed gated kernel was compared with), against a per-edge
    numpy loop."""

    def test_encode_against_a_per_edge_loop(self):
        stlgt, params, feats, src, dst, mask = _stlgt_case()
        p = jax.tree_util.tree_map(lambda a: np.asarray(a, np.float64), params)
        relu = lambda a: np.maximum(a, 0.0)  # noqa: E731
        phi = lambda a: np.where(a > 0, a, np.expm1(a)) + 1.0  # noqa: E731
        n, hidden = feats.shape[0], p.w_q.shape[1]
        f64 = feats.astype(np.float64)
        lane = (np.abs(f64).sum(axis=1) > 0).astype(np.float64)
        x = relu(f64 @ p.w_in + p.b_in)
        q = phi(x @ p.w_q) * lane[:, None]
        k = phi(x @ p.w_k) * lane[:, None]
        v = (x @ p.w_v) * lane[:, None]
        attn = (q @ (k.T @ v)) / (q @ k.sum(axis=0) + 1e-6)[:, None]
        gate = np.zeros(len(src))
        bias, deg = np.zeros((n, hidden)), np.zeros(n)
        for e, (u, w, on) in enumerate(zip(src, dst, mask)):
            if not on:
                continue
            g = 1.0 / (1.0 + np.exp(-(q[u] @ k[w] / np.sqrt(hidden) + p.b_edge[0])))
            gate[e] = g
            bias[w] += g * v[u]  # the caller's message, to the callee
            bias[u] += g * v[w]  # and the callee's, back
            deg[w] += g
            deg[u] += g
        bias /= np.maximum(deg, 1.0)[:, None]
        h1 = x + relu((attn + bias) @ p.w_o)
        h2 = h1 + relu(relu(h1 @ p.w_f1 + p.b_f1) @ p.w_f2 + p.b_f2)
        want = h2 * lane[:, None]

        got, got_gate = stlgt.encode(
            params, jnp.asarray(feats), jnp.asarray(src), jnp.asarray(dst),
            jnp.asarray(mask),
        )
        np.testing.assert_allclose(got_gate, gate, rtol=1e-5, atol=1e-6)
        np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5)
        assert (np.asarray(got_gate)[~mask] == 0).all()
        assert deg[8] == 0 and np.abs(bias[8]).max() == 0  # the isolated node
        assert np.abs(bias[:8]).max() > 0  # the bias is not a bystander here
        assert (np.asarray(got)[9:] == 0).all()  # padded lanes stay dark

    def test_gradient_through_forward_is_finite(self):
        stlgt, params, feats, src, dst, mask = _stlgt_case()
        args = tuple(jnp.asarray(a) for a in (feats, src, dst, mask))

        def loss(p):
            latency, logit = stlgt.forward(p, *args)
            return (latency**2).sum() + (logit**2).sum()

        grads = jax.grad(loss)(params)
        for name, g in grads._asdict().items():
            assert np.isfinite(np.asarray(g)).all(), name
        # the gate and the messages are on the path of the loss
        assert float(np.abs(np.asarray(grads.b_edge)).max()) > 0
        assert float(np.abs(np.asarray(grads.w_v)).max()) > 0


def _distinct_batches(n_batches, rows=300):
    """Batches of `rows` globally-distinct (src, dst, dist) triples, all
    sharing one pow2 input cap so every merge runs one union program."""
    for i in range(n_batches):
        k = np.arange(i * rows, (i + 1) * rows, dtype=np.int32)
        yield k % 797, k // 797, np.full(rows, 1 + i % 7, np.int32)


def _edge_set(g):
    src, dst, dist, mask = (np.asarray(a) for a in g.edge_arrays())
    return set(zip(src[mask], dst[mask], dist[mask]))


class TestSegmentGrowth:
    """Incremental capacity growth (KMAMIZ_STORE_GROW=segment)."""

    def test_capacity_crossing_compiles_nothing(self):
        # 1024-main store with a 256-row tail: 3 warm merges reach 900
        # edges, the 4th crosses the main capacity (1200 edges). The
        # crossing tick must re-run only warm programs.
        g = EndpointGraph(capacity=1024, tenant="seg_zero", grow="segment")
        snap = None
        for i, (s, d, ds) in enumerate(_distinct_batches(4)):
            if i == 3:
                assert g.n_edges == 900 < g.capacity
                snap = programs.snapshot()
            g.merge_edges(s, d, ds)
            _ = g.n_edges  # finalize the deferred count
        assert g.n_edges == 1200 > g.capacity
        assert g.capacity == 1024 and g.tail_capacity == 256
        assert programs.new_compiles_since(snap) == {}

    def test_repack_crossing_recompiles(self):
        # the legacy mode's contrast: the same crossing compiles at the
        # doubled capacity (what segment mode exists to avoid)
        g = EndpointGraph(capacity=1024, tenant="seg_repack", grow="repack")
        snap = None
        for i, (s, d, ds) in enumerate(_distinct_batches(4)):
            if i == 3:
                snap = programs.snapshot()
            g.merge_edges(s, d, ds)
            _ = g.n_edges
        assert g.capacity == 2048 and g.tail_capacity == 0
        assert programs.new_compiles_since(snap) != {}

    def test_mode_parity(self):
        sets = {}
        for grow in ("repack", "segment"):
            g = EndpointGraph(
                capacity=1024, tenant=f"seg_par_{grow}", grow=grow
            )
            for s, d, ds in _distinct_batches(4):
                g.merge_edges(s, d, ds)
            sets[grow] = _edge_set(g)
            assert g.n_edges == 1200
        assert sets["repack"] == sets["segment"]

    def test_tail_overflow_consolidates(self):
        # growth past main+tail falls back to a full repack (the rare
        # amortized event) without losing edges
        g = EndpointGraph(capacity=256, tenant="seg_consol", grow="segment")
        rng = np.random.default_rng(7)
        ref = set()
        for _ in range(4):
            s = rng.integers(0, 5000, 700).astype(np.int32)
            d = rng.integers(0, 5000, 700).astype(np.int32)
            ds = rng.integers(1, 8, 700).astype(np.int32)
            ref |= set(zip(s, d, ds))
            g.merge_edges(s, d, ds)
        assert _edge_set(g) == ref
        assert g.n_edges == len(ref)
        assert g.n_edges <= g.capacity + g.tail_capacity
        assert g.tail_capacity == max(256, g.capacity >> 3)

    def test_grow_knob_and_ctor(self, monkeypatch):
        monkeypatch.setenv("KMAMIZ_STORE_GROW", "repack")
        assert EndpointGraph(tenant="knob_a").tail_capacity == 0
        monkeypatch.setenv("KMAMIZ_STORE_GROW", "segment")
        assert EndpointGraph(tenant="knob_b").tail_capacity == 256
        # ctor overrides the env
        g = EndpointGraph(tenant="knob_c", grow="repack")
        assert g.tail_capacity == 0
        with pytest.raises(ValueError):
            EndpointGraph(tenant="knob_d", grow="bogus")

    def test_warm_sparse_tick_transfer_clean(self, monkeypatch):
        # the store + sparse scorer steady state survives
        # transfer_guard("disallow") with zero new compiles: warm two
        # merge/score rounds, then guard the third
        monkeypatch.setenv("KMAMIZ_MESH", "0")
        monkeypatch.setenv("KMAMIZ_SPARSE", "sparse")
        sparse.reset_for_tests()
        g = EndpointGraph(capacity=1024, tenant="seg_guard", grow="segment")
        for ep in range(830):
            g.interner.intern_endpoint(
                f"svc{ep % 13}\tns\tv1\tGET\thttp://h/e{ep}",
                {"uniqueServiceName": f"svc{ep % 13}\tns\tv1", "method": "GET",
                 "labelName": f"/e{ep % 40}", "timestamp": 1},
            )
        from kmamiz_tpu.ops.sortutil import SENTINEL

        def pad512(a):
            out = np.full(512, SENTINEL, np.int32)
            out[: a.size] = a
            return out

        # every round merges an identically-shaped 512-wide batch so the
        # guarded round's program set is exactly the warm rounds'
        batches = [
            [pad512(a) for a in (s % 830, d % 830, ds)]
            for s, d, ds in _distinct_batches(3, rows=280)
        ]
        for s, d, ds in batches[:2]:
            g.merge_edges(s, d, ds)
            g.service_scores()
        # upload the guarded round's batch up front: the guard checks
        # the STORE + SCORER steady state, not the test's own staging
        dev = [jax.device_put(a) for a in batches[2]]
        snap = programs.snapshot()
        with guards.hot_path_guard("disallow") as report:
            g.merge_edges(*dev)
            g.service_scores()
        assert report.new_compiles == {}, report.new_compiles
        assert programs.new_compiles_since(snap) == {}
