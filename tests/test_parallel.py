"""Sharded window pipeline over the 8-device CPU mesh must equal the
single-device pipeline."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from kmamiz_tpu.core.spans import KIND_SERVER, spans_to_batch
from kmamiz_tpu.parallel import mesh as pmesh
from kmamiz_tpu.ops import window


@pytest.fixture(scope="module")
def mesh8():
    assert len(jax.devices()) >= 8, "conftest must provide 8 virtual devices"
    return pmesh.make_mesh(8)


def test_sharded_stats_match_single_device(bookinfo_traces, mesh8):
    shards = pmesh.shard_window(bookinfo_traces, 8)
    num_endpoints = len(shards.batches[0].interner.endpoints)
    num_statuses = max(len(shards.batches[0].statuses), 1)

    valid_server = shards.valid & (shards.kind == KIND_SERVER)
    stats = pmesh.sharded_window_stats(
        mesh8,
        jnp.asarray(shards.rt_endpoint_id),
        jnp.asarray(shards.status_id),
        jnp.asarray(shards.status_class),
        jnp.asarray(shards.latency_ms),
        jnp.asarray(shards.timestamp_rel),
        jnp.asarray(valid_server),
        num_endpoints=num_endpoints,
        num_statuses=num_statuses,
    )

    # single-device reference over the same global arrays
    single = window.window_stats(
        jnp.asarray(shards.rt_endpoint_id),
        jnp.asarray(shards.status_id),
        jnp.asarray(shards.status_class),
        jnp.asarray(shards.latency_ms.astype(np.float64)),
        jnp.asarray(shards.timestamp_rel),
        jnp.asarray(valid_server),
        num_endpoints=num_endpoints,
        num_statuses=num_statuses,
    )
    np.testing.assert_array_equal(np.asarray(stats.count), np.asarray(single.count))
    np.testing.assert_array_equal(
        np.asarray(stats.error_4xx), np.asarray(single.error_4xx)
    )
    np.testing.assert_allclose(
        np.asarray(stats.latency_mean), np.asarray(single.latency_mean), rtol=1e-5
    )
    np.testing.assert_allclose(
        np.asarray(stats.latency_cv), np.asarray(single.latency_cv), atol=2e-3
    )
    assert float(np.asarray(stats.count).sum()) == sum(
        1 for g in bookinfo_traces for s in g if s["kind"] == "SERVER"
    )


def test_sharded_edges_match_host(bookinfo_traces, mesh8):
    from kmamiz_tpu.domain.traces import Traces

    shards = pmesh.shard_window(bookinfo_traces, 8)
    anc, desc, dist, mask = pmesh.sharded_dependency_edges(
        mesh8,
        jnp.asarray(shards.parent_idx),
        jnp.asarray(shards.kind),
        jnp.asarray(shards.valid),
        jnp.asarray(shards.endpoint_id),
    )
    lookup = shards.batches[0].interner.endpoints.lookup
    anc, desc, dist, mask = (np.asarray(x) for x in (anc, desc, dist, mask))
    device_edges = {
        (lookup(int(d)), lookup(int(a)), int(dd))
        for a, d, dd in zip(anc[mask], desc[mask], dist[mask])
    }

    host_edges = set()
    for d in Traces(bookinfo_traces).to_endpoint_dependencies().to_json():
        name = d["endpoint"]["uniqueEndpointName"]
        for b in d["dependingOn"]:
            # owner is the ancestor; dependingOn targets are descendants
            host_edges.add((b["endpoint"]["uniqueEndpointName"], name, b["distance"]))
    assert device_edges == host_edges


class TestRingCollectives:
    """Explicit ppermute ring collectives must match psum/pmax on the
    8-device CPU mesh."""

    def _mesh(self):
        from kmamiz_tpu.parallel import mesh as pmesh

        return pmesh.make_mesh(8)

    def test_ring_all_reduce_matches_psum(self):
        import jax
        from jax.sharding import PartitionSpec as P
        from kmamiz_tpu.parallel.mesh import shard_map

        from kmamiz_tpu.parallel import mesh as pmesh

        mesh = self._mesh()
        n = 8
        rng = np.random.default_rng(0)
        x = rng.normal(size=(n, 64)).astype(np.float32)

        def ring(xs):
            return pmesh.ring_all_reduce(xs.reshape(-1), "spans", n)

        def ref(xs):
            return jax.lax.psum(xs.reshape(-1), "spans")

        run = lambda fn: np.asarray(
            shard_map(
                fn, mesh=mesh, in_specs=(P("spans"),), out_specs=P(),
                check_vma=False,  # ring output replication is dynamic
            )(jnp.asarray(x))
        )
        np.testing.assert_allclose(run(ring), run(ref), rtol=1e-5, atol=1e-6)

    def test_ring_max(self):
        import jax
        from jax.sharding import PartitionSpec as P
        from kmamiz_tpu.parallel.mesh import shard_map

        from kmamiz_tpu.parallel import mesh as pmesh

        mesh = self._mesh()
        n = 8
        rng = np.random.default_rng(1)
        x = rng.integers(0, 1000, size=(n, 48)).astype(np.int32)

        def ring(xs):
            return pmesh.ring_all_reduce(xs.reshape(-1), "spans", n, op="max")

        def ref(xs):
            return jax.lax.pmax(xs.reshape(-1), "spans")

        run = lambda fn: np.asarray(
            shard_map(
                fn, mesh=mesh, in_specs=(P("spans"),), out_specs=P(),
                check_vma=False,  # ring output replication is dynamic
            )(jnp.asarray(x))
        )
        np.testing.assert_array_equal(run(ring), run(ref))

    def test_ring_reduce_scatter_ownership(self):
        """Device i must own fully reduced chunk i after reduce-scatter."""
        import jax
        from jax.sharding import PartitionSpec as P
        from kmamiz_tpu.parallel.mesh import shard_map

        from kmamiz_tpu.parallel import mesh as pmesh

        mesh = self._mesh()
        n = 8
        rng = np.random.default_rng(2)
        # each device contributes a different full-length partial
        x = rng.normal(size=(n, n * 16)).astype(np.float32)

        def rs(xs):
            return pmesh.ring_reduce_scatter(xs.reshape(-1), "spans", n)

        out = np.asarray(
            shard_map(
                rs, mesh=mesh, in_specs=(P("spans"),), out_specs=P("spans")
            )(jnp.asarray(x.reshape(-1)))
        )
        want = x.sum(axis=0)  # concatenated owned chunks == full reduction
        np.testing.assert_allclose(out, want, rtol=1e-5)

    def test_sharded_window_stats_ring_matches_psum(self, bookinfo_traces):
        from kmamiz_tpu.parallel import mesh as pmesh

        mesh = pmesh.make_mesh(8)
        # bookinfo only: the pdas fixture was captured weeks apart and the
        # int32 rel-timestamp window guard rejects a combined batch
        window = pmesh.shard_window(bookinfo_traces, 8)
        vs = window.valid & (window.kind == 1)
        args = (
            jnp.asarray(window.rt_endpoint_id),
            jnp.asarray(window.status_id),
            jnp.asarray(window.status_class),
            jnp.asarray(window.latency_ms),
            jnp.asarray(window.timestamp_rel),
            jnp.asarray(vs),
        )
        ne = len(window.batches[0].interner.endpoints)
        ns = max(len(window.batches[0].statuses), 1)
        a = pmesh.sharded_window_stats(
            mesh, *args, num_endpoints=ne, num_statuses=ns, merge="psum"
        )
        b = pmesh.sharded_window_stats(
            mesh, *args, num_endpoints=ne, num_statuses=ns, merge="ring"
        )
        for fa, fb in zip(a, b):
            np.testing.assert_allclose(
                np.asarray(fa), np.asarray(fb), rtol=1e-5, atol=1e-6
            )


class TestHierarchicalMerge:
    """The ICI-within-host / DCN-across-host hierarchical all-reduce must
    equal a flat psum on a 2x4 ('host', 'spans') mesh."""

    def _mesh2d(self):
        import jax
        from jax.sharding import Mesh

        devices = np.asarray(jax.devices()[:8]).reshape(2, 4)
        return Mesh(devices, ("host", "spans"))

    def test_hierarchical_all_reduce_matches_psum(self):
        import jax
        from jax.sharding import PartitionSpec as P
        from kmamiz_tpu.parallel.mesh import shard_map

        from kmamiz_tpu.parallel import mesh as pmesh

        mesh = self._mesh2d()
        rng = np.random.default_rng(3)
        x = rng.normal(size=(8, 64)).astype(np.float32)

        def hier(xs):
            return pmesh.hierarchical_all_reduce(
                xs.reshape(-1), "spans", 4, "host"
            )

        def ref(xs):
            flat = xs.reshape(-1)
            return jax.lax.psum(jax.lax.psum(flat, "spans"), "host")

        run = lambda fn: np.asarray(
            shard_map(
                fn,
                mesh=mesh,
                in_specs=(P(("host", "spans")),),
                out_specs=P(),
                check_vma=False,
            )(jnp.asarray(x.reshape(-1)))
        )
        np.testing.assert_allclose(run(hier), run(ref), rtol=1e-5, atol=1e-6)

    def test_sharded_window_stats_hierarchical(self, bookinfo_traces):
        from kmamiz_tpu.parallel import mesh as pmesh

        mesh2d = self._mesh2d()
        mesh1d = pmesh.make_mesh(8)
        window = pmesh.shard_window(bookinfo_traces, 8)
        vs = window.valid & (window.kind == 1)
        args = (
            jnp.asarray(window.rt_endpoint_id),
            jnp.asarray(window.status_id),
            jnp.asarray(window.status_class),
            jnp.asarray(window.latency_ms),
            jnp.asarray(window.timestamp_rel),
            jnp.asarray(vs),
        )
        ne = len(window.batches[0].interner.endpoints)
        ns = max(len(window.batches[0].statuses), 1)
        flat = pmesh.sharded_window_stats(
            mesh1d, *args, num_endpoints=ne, num_statuses=ns, merge="psum"
        )
        hier = pmesh.sharded_window_stats(
            mesh2d, *args, num_endpoints=ne, num_statuses=ns,
            merge="hierarchical", axis="spans",
        )
        for fa, fb in zip(flat, hier):
            np.testing.assert_allclose(
                np.asarray(fa), np.asarray(fb), rtol=1e-5, atol=1e-6
            )


class TestShardedEquivalenceFuzz:
    """Randomized windows: the sharded pipeline (any merge mode) must
    reproduce the single-device window_stats on the same spans."""

    @pytest.mark.parametrize("merge", ["psum", "ring"])
    def test_random_windows(self, merge):
        import random

        # str seeding is deterministic (unlike salted hash()), so failures
        # reproduce across interpreter runs
        rng = random.Random(merge)
        ts_base = 1_700_000_000_000_000
        groups = []
        for t in range(rng.randint(12, 30)):
            size = rng.randint(1, 9)
            group = []
            for j in range(size):
                svc = f"svc{rng.randint(0, 3)}"
                group.append(
                    {
                        "traceId": f"t{t}",
                        "id": f"{t}-{j}",
                        "parentId": f"{t}-{j-1}" if j else None,
                        "kind": rng.choice(["SERVER", "CLIENT"]),
                        "name": f"{svc}.ns.svc.cluster.local:80/*",
                        "timestamp": ts_base + rng.randint(0, 20_000_000),
                        # includes a high-magnitude low-spread regime where
                        # the naive E[x^2]-E[x]^2 variance collapses in f32
                        "duration": (
                            800_000_000 + rng.randint(0, 200_000)
                            if rng.random() < 0.3
                            else rng.randint(100, 900_000)
                        ),
                        "tags": {
                            "http.method": "GET",
                            "http.status_code": rng.choice(["200", "404", "500"]),
                            "http.url": f"http://{svc}.ns.svc.cluster.local/a",
                            "istio.canonical_revision": "v1",
                            "istio.canonical_service": svc,
                            "istio.mesh_id": "c",
                            "istio.namespace": "ns",
                        },
                    }
                )
            groups.append(group)
        # deterministic empty segments: svc9's endpoint only ever reports
        # 200, so its (endpoint, 404/500) segments are guaranteed empty
        groups.append(
            [
                {
                    "traceId": "t-only200",
                    "id": "only200-0",
                    "parentId": None,
                    "kind": "SERVER",
                    "name": "svc9.ns.svc.cluster.local:80/*",
                    "timestamp": ts_base + 1000,
                    "duration": 5000,
                    "tags": {
                        "http.method": "GET",
                        "http.status_code": "200",
                        "http.url": "http://svc9.ns.svc.cluster.local/a",
                        "istio.canonical_revision": "v1",
                        "istio.canonical_service": "svc9",
                        "istio.mesh_id": "c",
                        "istio.namespace": "ns",
                    },
                }
            ]
        )

        mesh = pmesh.make_mesh(8)
        w = pmesh.shard_window(groups, 8)
        vs = w.valid & (w.kind == 1)
        ne = len(w.batches[0].interner.endpoints)
        ns = max(len(w.batches[0].statuses), 1)
        sharded = pmesh.sharded_window_stats(
            mesh,
            jnp.asarray(w.rt_endpoint_id),
            jnp.asarray(w.status_id),
            jnp.asarray(w.status_class),
            jnp.asarray(w.latency_ms),
            jnp.asarray(w.timestamp_rel),
            jnp.asarray(vs),
            num_endpoints=ne,
            num_statuses=ns,
            merge=merge,
        )
        flat = window.window_stats(
            jnp.asarray(w.rt_endpoint_id),
            jnp.asarray(w.status_id),
            jnp.asarray(w.status_class),
            jnp.asarray(w.latency_ms.astype(np.float64)),
            jnp.asarray(w.timestamp_rel),
            jnp.asarray(vs),
            num_endpoints=ne,
            num_statuses=ns,
        )
        # the guard under test must actually be exercised: random data over
        # 4 services x 3 statuses always leaves some (endpoint,status)
        # combination empty
        assert bool((np.asarray(flat.count) == 0).any())
        np.testing.assert_array_equal(
            np.asarray(sharded.count), np.asarray(flat.count)
        )
        np.testing.assert_array_equal(
            np.asarray(sharded.error_5xx), np.asarray(flat.error_5xx)
        )
        np.testing.assert_array_equal(
            np.asarray(sharded.latest_timestamp_rel),
            np.asarray(flat.latest_timestamp_rel),
        )
        np.testing.assert_allclose(
            np.asarray(sharded.latency_mean),
            np.asarray(flat.latency_mean),
            rtol=1e-4,
            atol=1e-5,
        )
        # CV must hold up too: the sharded path uses the same two-pass
        # residual variance as the single-device kernel
        np.testing.assert_allclose(
            np.asarray(sharded.latency_cv),
            np.asarray(flat.latency_cv),
            rtol=1e-3,
            atol=1e-5,
        )


def test_sharded_packed_walk_matches_flat(bookinfo_traces, mesh8):
    """VERDICT r2 #4: the sharded path gets the MXU packed walk; its edge
    set must equal the flat sharded gather walk AND the host oracle."""
    from kmamiz_tpu.domain.traces import Traces

    shards = pmesh.shard_window(bookinfo_traces, 8)
    packed = pmesh.shard_window_packed(shards)
    assert packed is not None
    pslot2, kind2, valid2, ep2, depth = packed
    anc, desc, dist, mask = pmesh.sharded_dependency_edges_packed(
        mesh8,
        jnp.asarray(pslot2),
        jnp.asarray(kind2),
        jnp.asarray(valid2),
        jnp.asarray(ep2),
        max_depth=depth,
    )
    anc, desc, dist, mask = (np.asarray(x) for x in (anc, desc, dist, mask))
    packed_edges = {
        (int(a), int(d), int(dd))
        for a, d, dd in zip(anc[mask], desc[mask], dist[mask])
    }

    f_anc, f_desc, f_dist, f_mask = pmesh.sharded_dependency_edges(
        mesh8,
        jnp.asarray(shards.parent_idx),
        jnp.asarray(shards.kind),
        jnp.asarray(shards.valid),
        jnp.asarray(shards.endpoint_id),
    )
    f_anc, f_desc, f_dist, f_mask = (
        np.asarray(x) for x in (f_anc, f_desc, f_dist, f_mask)
    )
    flat_edges = {
        (int(a), int(d), int(dd))
        for a, d, dd in zip(f_anc[f_mask], f_desc[f_mask], f_dist[f_mask])
    }
    assert packed_edges == flat_edges

    lookup = shards.batches[0].interner.endpoints.lookup
    host_edges = set()
    for d in Traces(bookinfo_traces).to_endpoint_dependencies().to_json():
        name = d["endpoint"]["uniqueEndpointName"]
        for b in d["dependingOn"]:
            host_edges.add(
                (b["endpoint"]["uniqueEndpointName"], name, b["distance"])
            )
    named = {
        (lookup(d), lookup(a), dd) for a, d, dd in packed_edges
    }
    assert named == host_edges


def test_sharded_packed_walk_random_windows(mesh8):
    """Fuzz: random forests through the packed sharded walk vs the flat
    sharded walk (edge multisets must agree per shard layout)."""
    rng = np.random.default_rng(5)
    for _ in range(3):
        groups = []
        for t in range(rng.integers(8, 40)):
            n = int(rng.integers(1, 10))
            group = []
            for j in range(n):
                group.append(
                    {
                        "traceId": f"t{t}",
                        "id": f"{t}-{j}",
                        "parentId": f"{t}-{rng.integers(0, j)}" if j else None,
                        "kind": rng.choice(["SERVER", "CLIENT"]),
                        "name": f"svc{rng.integers(0, 6)}.ns.svc.cluster.local:80/*",
                        "timestamp": 1_700_000_000_000_000 + int(rng.integers(0, 10**6)),
                        "duration": int(rng.integers(100, 10_000)),
                        "tags": {
                            "http.method": "GET",
                            "http.status_code": "200",
                            "http.url": f"http://svc{rng.integers(0, 6)}.ns/api",
                            "istio.canonical_service": f"svc{rng.integers(0, 6)}",
                            "istio.namespace": "ns",
                            "istio.canonical_revision": "v1",
                            "istio.mesh_id": "m",
                        },
                    }
                )
            groups.append(group)
        shards = pmesh.shard_window(groups, 8)
        packed = pmesh.shard_window_packed(shards)
        assert packed is not None
        pslot2, kind2, valid2, ep2, depth = packed
        anc, desc, dist, mask = pmesh.sharded_dependency_edges_packed(
            mesh8, jnp.asarray(pslot2), jnp.asarray(kind2),
            jnp.asarray(valid2), jnp.asarray(ep2), max_depth=depth,
        )
        anc, desc, dist, mask = (np.asarray(x) for x in (anc, desc, dist, mask))
        packed_edges = sorted(
            (int(a), int(d), int(dd))
            for a, d, dd in zip(anc[mask], desc[mask], dist[mask])
        )
        f = pmesh.sharded_dependency_edges(
            mesh8,
            jnp.asarray(shards.parent_idx),
            jnp.asarray(shards.kind),
            jnp.asarray(shards.valid),
            jnp.asarray(shards.endpoint_id),
        )
        f_anc, f_desc, f_dist, f_mask = (np.asarray(x) for x in f)
        flat_edges = sorted(
            (int(a), int(d), int(dd))
            for a, d, dd in zip(f_anc[f_mask], f_desc[f_mask], f_dist[f_mask])
        )
        assert packed_edges == flat_edges


class TestDeployedMeshPath:
    """The DEPLOYED ingest path over the mesh (VERDICT r4 #1): not the
    mesh primitives, but DataProcessor.ingest_raw_stream and the graph
    store's staged merges sharding across all 8 virtual devices, with
    bit-identical results to the single-device run."""

    def _edge_set(self, graph):
        s, d, ds, m = (np.asarray(x) for x in graph.edge_arrays())
        return {
            (int(a), int(b), int(c)) for a, b, c in zip(s[m], d[m], ds[m])
        }

    def _ingest(self, chunks, monkeypatch, mesh_on):
        from kmamiz_tpu.server.processor import DataProcessor

        monkeypatch.setenv("KMAMIZ_MESH", "1" if mesh_on else "0")
        dp = DataProcessor(trace_source=lambda *a: [], use_device_stats=False)
        result = dp.ingest_raw_stream(list(chunks))
        return dp, result

    @pytest.fixture(scope="class")
    def raw_chunks(self):
        from kmamiz_tpu.synth import make_raw_chunks

        pytest.importorskip("kmamiz_tpu.native")
        from kmamiz_tpu import native

        if not native.available():
            pytest.skip("native span loader unavailable")
        return make_raw_chunks(
            2000, 7, 3, n_services=50, urls_per_service=8
        )

    def test_ingest_raw_stream_mesh_parity(self, raw_chunks, monkeypatch):
        dp1, r1 = self._ingest(raw_chunks, monkeypatch, mesh_on=False)
        dp8, r8 = self._ingest(raw_chunks, monkeypatch, mesh_on=True)
        for k in ("spans", "traces", "endpoints", "edges"):
            assert r1[k] == r8[k], (k, r1[k], r8[k])
        assert self._edge_set(dp1.graph) == self._edge_set(dp8.graph)
        # the sharded run really staged mesh entries: the store's
        # deploy gate saw >= 8 packed rows per chunk
        assert r8["spans"] == 14_000

    def test_truncated_prefix_rewalks_sharded(self, raw_chunks, monkeypatch):
        """A stage cap far below the window's distinct edges forces the
        drain's re-walk fallback through the SHARDED walk kernel; the
        result must still be the exact edge union."""
        dp1, _ = self._ingest(raw_chunks, monkeypatch, mesh_on=False)
        monkeypatch.setenv("KMAMIZ_STAGE_CAP", "4")
        dp8, _ = self._ingest(raw_chunks, monkeypatch, mesh_on=True)
        assert self._edge_set(dp1.graph) == self._edge_set(dp8.graph)

    def test_device_stats_job_mesh_parity(self, bookinfo_traces, monkeypatch):
        """collect()'s async device stats take the sharded path on a
        multi-device mesh and must match the single-device kernel."""
        from kmamiz_tpu.domain.traces import Traces
        from kmamiz_tpu.server.processor import DeviceStatsJob

        records = Traces(bookinfo_traces).combine_logs_to_realtime_data(
            [], []
        ).to_json()
        monkeypatch.setenv("KMAMIZ_MESH", "0")
        single = DeviceStatsJob(records).result()
        monkeypatch.setenv("KMAMIZ_MESH", "1")
        sharded = DeviceStatsJob(records).result()
        assert set(single) == set(sharded)
        for key, want in single.items():
            got = sharded[key]
            assert got["count"] == want["count"]
            assert got["latest_timestamp"] == want["latest_timestamp"]
            np.testing.assert_allclose(got["mean"], want["mean"], rtol=1e-5)
            np.testing.assert_allclose(
                got["cv"], want["cv"], atol=2e-3
            )

    def test_collect_tick_mesh_parity(self, pdas_traces, monkeypatch):
        """The full realtime tick (collect) produces the same combined
        rows and dependencies under the mesh as single-device."""
        from kmamiz_tpu.server.processor import DataProcessor

        def run(mesh_on):
            monkeypatch.setenv("KMAMIZ_MESH", "1" if mesh_on else "0")
            dp = DataProcessor(
                trace_source=lambda *a: [list(pdas_traces)],
                use_device_stats=True,
            )
            return dp.collect(
                {"uniqueId": "t", "lookBack": 30_000, "time": 1_000_000}
            )

        r1, r8 = run(False), run(True)
        key = lambda r: (r["uniqueEndpointName"], str(r["status"]))
        c1 = {key(r): r for r in r1["combined"]}
        c8 = {key(r): r for r in r8["combined"]}
        assert set(c1) == set(c8)
        for k in c1:
            assert c1[k]["combined"] == c8[k]["combined"]
            np.testing.assert_allclose(
                c1[k]["latency"]["mean"], c8[k]["latency"]["mean"], rtol=1e-5
            )
        assert len(r1["dependencies"]) == len(r8["dependencies"])


def test_sharded_stats_pallas_backend_matches(bookinfo_traces, mesh8):
    """KMAMIZ_SEGMENT_BACKEND must select the MXU matmul kernel on the
    mesh exactly as on one chip: per-shard pallas segment sums + psum
    merge equals the default scatter path."""
    from kmamiz_tpu.core.spans import KIND_SERVER

    shards = pmesh.shard_window(bookinfo_traces, 8)
    num_endpoints = len(shards.batches[0].interner.endpoints)
    num_statuses = max(len(shards.batches[0].statuses), 1)
    valid_server = shards.valid & (shards.kind == KIND_SERVER)
    args = (
        jnp.asarray(shards.rt_endpoint_id),
        jnp.asarray(shards.status_id),
        jnp.asarray(shards.status_class),
        jnp.asarray(shards.latency_ms),
        jnp.asarray(shards.timestamp_rel),
        jnp.asarray(valid_server),
    )
    xla = pmesh.sharded_window_stats(
        mesh8, *args, num_endpoints=num_endpoints, num_statuses=num_statuses
    )
    pal = pmesh.sharded_window_stats(
        mesh8,
        *args,
        num_endpoints=num_endpoints,
        num_statuses=num_statuses,
        backend="pallas_interpret",
    )
    np.testing.assert_array_equal(np.asarray(xla.count), np.asarray(pal.count))
    np.testing.assert_array_equal(
        np.asarray(xla.latest_timestamp_rel),
        np.asarray(pal.latest_timestamp_rel),
    )
    np.testing.assert_allclose(
        np.asarray(xla.latency_mean), np.asarray(pal.latency_mean), rtol=1e-5
    )
    np.testing.assert_allclose(
        np.asarray(xla.latency_cv), np.asarray(pal.latency_cv), atol=2e-3
    )


def test_sharded_service_scores_parity(mesh8):
    """The mesh-sharded scorer (edge->tuple expansion + local dedup sort
    per shard, degree psum over ICI, shared counting core) must equal
    the single-device scorer exactly on every field."""
    from kmamiz_tpu.ops import scorers

    rng = np.random.default_rng(3)
    CAP, EDGES, N_EP, N_SVC = 1 << 12, 3000, 512, 64
    SEN = np.iinfo(np.int32).max
    src = np.full(CAP, SEN, np.int32)
    src[:EDGES] = rng.integers(0, N_EP, EDGES)
    dst = np.full(CAP, SEN, np.int32)
    dst[:EDGES] = rng.integers(0, N_EP, EDGES)
    dist = np.ones(CAP, np.int32)
    dist[:EDGES] = rng.integers(1, 6, EDGES)
    mask = np.zeros(CAP, bool)
    mask[:EDGES] = True
    eps = rng.integers(0, N_SVC, N_EP).astype(np.int32)
    epm = rng.integers(0, 300, N_EP).astype(np.int32)
    epr = rng.random(N_EP) < 0.8
    args = tuple(
        jnp.asarray(a) for a in (src, dst, dist, mask, eps, epm, epr)
    )
    single = scorers.service_scores(*args, num_services=N_SVC)
    shard = pmesh.sharded_service_scores(mesh8, *args, num_services=N_SVC)
    for name in single._fields:
        np.testing.assert_allclose(
            np.asarray(getattr(single, name)),
            np.asarray(getattr(shard, name)),
            rtol=1e-6,
            err_msg=name,
        )


def test_store_serves_sharded_scorer_on_mesh(pdas_traces, monkeypatch):
    """EndpointGraph.service_scores takes the sharded path when the mesh
    is active and must agree with the forced single-device path on the
    same graph."""
    from kmamiz_tpu.core.spans import spans_to_batch
    from kmamiz_tpu.graph.store import EndpointGraph

    g = EndpointGraph(capacity=64)  # small cap: 64 rows shard over 8
    g.merge_window(spans_to_batch([pdas_traces], interner=g.interner))
    monkeypatch.setenv("KMAMIZ_MESH", "0")
    single = g.service_scores()
    monkeypatch.setenv("KMAMIZ_MESH", "1")
    shard = g.service_scores()
    for name in single._fields:
        np.testing.assert_allclose(
            np.asarray(getattr(single, name)),
            np.asarray(getattr(shard, name)),
            rtol=1e-6,
            err_msg=name,
        )


class TestSlotDataParallel:
    """GraphSAGE slot-batch data parallelism (models/stacked.py +
    make_sharded_slot_grad): grads psum-merged over the mesh must equal
    the same microbatch on one device."""

    def _dataset(self, n_slots=8):
        from kmamiz_tpu.models import graphsage, trainer

        rng = np.random.default_rng(4)
        n_nodes, n_edges = 12, 20
        return trainer.GraphDataset(
            endpoint_names=[f"ep{i}" for i in range(n_nodes)],
            src=jnp.asarray(rng.integers(0, n_nodes, n_edges, dtype=np.int32)),
            dst=jnp.asarray(rng.integers(0, n_nodes, n_edges, dtype=np.int32)),
            edge_mask=jnp.ones(n_edges, dtype=bool),
            features=[
                jnp.asarray(
                    rng.normal(
                        size=(n_nodes, graphsage.NUM_FEATURES)
                    ).astype(np.float32)
                )
                for _ in range(n_slots)
            ],
            target_latency=[
                jnp.asarray(rng.normal(size=n_nodes).astype(np.float32))
                for _ in range(n_slots)
            ],
            target_anomaly=[
                jnp.asarray((rng.random(n_nodes) < 0.2).astype(np.float32))
                for _ in range(n_slots)
            ],
            node_mask=[
                jnp.asarray(rng.random(n_nodes) < 0.9)
                for _ in range(n_slots)
            ],
            slot_keys=[f"s{i}" for i in range(n_slots)],
        )

    def test_sharded_slot_grads_match_single_device(self):
        from kmamiz_tpu.models import common, graphsage, stacked

        ds = self._dataset()
        st = stacked.stack_dataset(ds)
        mesh = pmesh.make_mesh(8, axis="slots")
        params = graphsage.init_params(jax.random.PRNGKey(0), hidden=8)
        grad_fn = jax.value_and_grad(
            common.make_loss_fn(graphsage.forward, 3.0), has_aux=True
        )
        bg = pmesh.make_sharded_slot_grad(mesh, grad_fn, axis="slots")
        feats, tl, ta, nm, w = stacked.batch_slots_arrays(st, 8)
        g_mesh, loss_mesh, _, _ = bg(
            params, feats[0], tl[0], ta[0], nm[0],
            st.src, st.dst, st.edge_mask, w[0],
        )

        # single-device reference: weighted per-slot grads, averaged
        gs, ls = [], []
        for i in range(8):
            (loss, _), g = grad_fn(
                params, feats[0][i], st.src, st.dst, st.edge_mask,
                tl[0][i], ta[0][i], nm[0][i],
            )
            gs.append(g)
            ls.append(float(loss))
        g_ref = jax.tree_util.tree_map(lambda *xs: sum(xs) / 8.0, *gs)
        for a, b in zip(
            jax.tree_util.tree_leaves(g_mesh),
            jax.tree_util.tree_leaves(g_ref),
        ):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-6
            )
        np.testing.assert_allclose(
            float(loss_mesh), sum(ls) / 8.0, rtol=1e-5
        )

        # padded slots carry weight 0 and must contribute nothing: each
        # device weights ITS slots before anything crosses devices (the
        # gradient of replicated params is taken w.r.t. a per-device
        # view, parallel/mesh.py). Uniform weights cannot tell that apart
        # from reducing the raw per-slot grads over the axis first.
        weights = np.array([1, 1, 0, 1, 0, 0, 1, 1], dtype=np.float32)
        g_mesh, loss_mesh, _, _ = bg(
            params, feats[0], tl[0], ta[0], nm[0],
            st.src, st.dst, st.edge_mask, jnp.asarray(weights),
        )
        kept = [i for i in range(8) if weights[i]]
        g_ref = jax.tree_util.tree_map(
            lambda *xs: sum(xs) / len(kept), *[gs[i] for i in kept]
        )
        for a, b in zip(
            jax.tree_util.tree_leaves(g_mesh),
            jax.tree_util.tree_leaves(g_ref),
        ):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-6
            )
        np.testing.assert_allclose(
            float(loss_mesh), sum(ls[i] for i in kept) / len(kept), rtol=1e-5
        )

    def test_mesh_training_matches_one_device(self):
        from kmamiz_tpu.models import trainer

        ds = self._dataset()
        mesh = pmesh.make_mesh(8, axis="slots")
        r1 = trainer.train(
            ds, epochs=3, hidden=8, fused=True, batch_slots=8
        )
        rN = trainer.train(
            ds, epochs=3, hidden=8, fused=True, batch_slots=8, mesh=mesh
        )
        np.testing.assert_allclose(
            rN.losses, r1.losses, rtol=1e-4, atol=1e-5
        )
        for a, b in zip(
            jax.tree_util.tree_leaves(r1.params),
            jax.tree_util.tree_leaves(rN.params),
        ):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=1e-3, atol=1e-5
            )

    def test_indivisible_batch_rejected(self):
        from kmamiz_tpu.models import common, graphsage, stacked

        ds = self._dataset(n_slots=6)
        st = stacked.stack_dataset(ds)
        mesh = pmesh.make_mesh(8, axis="slots")
        grad_fn = jax.value_and_grad(
            common.make_loss_fn(graphsage.forward, 1.0), has_aux=True
        )
        bg = pmesh.make_sharded_slot_grad(mesh, grad_fn, axis="slots")
        feats, tl, ta, nm, w = stacked.batch_slots_arrays(st, 6)
        with pytest.raises(ValueError, match="does not shard"):
            bg(
                params := graphsage.init_params(jax.random.PRNGKey(0), hidden=8),
                feats[0], tl[0], ta[0], nm[0],
                st.src, st.dst, st.edge_mask, w[0],
            )
