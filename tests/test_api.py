"""REST API surface: routes, handlers, and HTTP round-trips.

Parity targets: src/handler/*.ts route behaviors over the same PDAS
fixture data the reference's own tests use.
"""
import gzip
import json
import urllib.error
import urllib.request

import pytest

from kmamiz_tpu.api.app import Application, build_router
from kmamiz_tpu.api.router import ApiServer, Router, compile_path
from kmamiz_tpu.config import Settings
from kmamiz_tpu.server.initializer import AppContext, Initializer
from kmamiz_tpu.server.processor import DataProcessor
from kmamiz_tpu.server.storage import MemoryStore

FIXTURE_NOW_MS = 1646208500000


def make_ctx(pdas_traces, simulator_mode=False, testing=False):
    s = Settings()
    s.simulator_mode = simulator_mode
    s.enable_testing_endpoints = testing
    s.external_data_processor = ""
    processor = DataProcessor(
        trace_source=lambda look_back, time, limit: [pdas_traces],
        k8s_source=None,
    )
    ctx = AppContext.build(app_settings=s, store=MemoryStore(), processor=processor)
    ctx.service_utils._now_ms = lambda: FIXTURE_NOW_MS
    Initializer(ctx).register_data_caches()
    return ctx


@pytest.fixture()
def ctx(pdas_traces):
    c = make_ctx(pdas_traces, testing=True)
    c.operator.retrieve_realtime_data()
    c.operator.create_historical_and_aggregated_data(1646208400000)
    # a second tick so graph caches are warm after the aggregation reset
    c.processor._processed.clear()
    c.operator.retrieve_realtime_data()
    return c


@pytest.fixture()
def router(ctx):
    return build_router(ctx)


def get(router, path):
    return router.dispatch("GET", path)


class TestPathCompile:
    def test_required_param(self):
        p = compile_path("/api/v1/graph/requests/:uniqueName")
        assert p.match("/api/v1/graph/requests/svc%09ns").group("uniqueName")
        assert not p.match("/api/v1/graph/requests/")

    def test_optional_param(self):
        p = compile_path("/api/v1/graph/line/:namespace?")
        assert p.match("/api/v1/graph/line").groupdict()["namespace"] is None
        assert p.match("/api/v1/graph/line/ns").group("namespace") == "ns"


class TestGraphRoutes:
    def test_endpoint_dependency_graph(self, router):
        res = get(router, "/api/v1/graph/dependency/endpoint")
        assert res.status == 200
        assert res.payload["nodes"] and res.payload["links"]
        # null root node present (EndpointDependencies.toGraphData)
        assert any(n["id"] == "null" for n in res.payload["nodes"])

    def test_service_dependency_graph(self, router):
        res = get(router, "/api/v1/graph/dependency/service")
        assert res.status == 200
        for n in res.payload["nodes"]:
            assert n["id"] == n["group"]

    def test_namespace_filter(self, router):
        res = get(router, "/api/v1/graph/dependency/endpoint/nonexistent")
        # namespace with no endpoints -> empty graph, not error
        assert res.status == 200

    def test_chords(self, router):
        direct = get(router, "/api/v1/graph/chord/direct")
        indirect = get(router, "/api/v1/graph/chord/indirect")
        assert direct.status == 200 and indirect.status == 200
        assert {"nodes", "links"} <= set(direct.payload)

    def test_line_chart(self, router):
        res = get(router, "/api/v1/graph/line")
        assert res.status == 200
        assert res.payload["dates"] and res.payload["services"]
        n_services = len(res.payload["services"])
        for metric in res.payload["metrics"]:
            assert len(metric) == n_services
            assert all(len(m) == 6 for m in metric)

    def test_statistics(self, router):
        res = get(router, "/api/v1/graph/statistics")
        assert res.status == 200
        assert res.payload
        row = res.payload[0]
        assert {
            "uniqueServiceName",
            "name",
            "latencyMean",
            "serverErrorRate",
            "requestErrorsRate",
        } <= set(row)

    def test_scorers(self, router):
        cohesion = get(router, "/api/v1/graph/cohesion")
        instability = get(router, "/api/v1/graph/instability")
        coupling = get(router, "/api/v1/graph/coupling")
        assert cohesion.status == instability.status == coupling.status == 200
        assert {"dataCohesion", "usageCohesion", "totalInterfaceCohesion"} <= set(
            cohesion.payload[0]
        )
        assert {"dependingBy", "dependingOn", "instability"} <= set(
            instability.payload[0]
        )
        assert {"ais", "ads", "acs"} <= set(coupling.payload[0])

    def test_scorer_routes_device_equals_host(self, router, ctx):
        """The scorer routes are served from the device graph (VERDICT r1
        #2); `?scorer=host` forces the host oracle — payloads must match
        exactly (consumers list order excepted: the device emits it
        lexsorted, the host in insertion order)."""
        assert ctx.processor.graph.n_edges > 0  # device path is live

        # prove the device path serves the default route: a poisoned host
        # cache would change the host answer but not the device one
        calls = {"n": 0}
        orig = ctx.processor.graph.service_scores

        def spy(*a, **k):
            calls["n"] += 1
            return orig(*a, **k)

        ctx.processor.graph.service_scores = spy
        try:
            for route in ("instability", "coupling"):
                dev = get(router, f"/api/v1/graph/{route}")
                host = get(router, f"/api/v1/graph/{route}?scorer=host")
                assert dev.status == host.status == 200
                assert dev.payload == host.payload, route
            assert calls["n"] == 2
        finally:
            ctx.processor.graph.service_scores = orig

        dev = get(router, "/api/v1/graph/cohesion")
        host = get(router, "/api/v1/graph/cohesion?scorer=host")
        assert dev.status == host.status == 200

        def canon(payload):
            return [
                {
                    **row,
                    "consumers": sorted(
                        row["consumers"], key=lambda c: c["uniqueServiceName"]
                    ),
                }
                for row in payload
            ]

        assert canon(dev.payload) == canon(host.payload)

    def test_scorer_routes_device_namespace_filter(self, router, ctx):
        dev = get(router, "/api/v1/graph/instability/pdas")
        host = get(router, "/api/v1/graph/instability/pdas?scorer=host")
        assert dev.payload == host.payload
        assert dev.payload  # pdas services present
        assert all("\tpdas\t" in r["uniqueServiceName"] for r in dev.payload)
        none = get(router, "/api/v1/graph/instability/nope")
        assert none.payload == []

    def test_request_chart(self, router, ctx):
        svc = ctx.cache.get("CombinedRealtimeData").get_data().to_json()[0][
            "uniqueServiceName"
        ]
        res = get(router, f"/api/v1/graph/requests/{svc.replace(chr(9), '%09')}")
        assert res.status == 200
        assert res.payload["totalRequestCount"] >= 0
        assert res.payload["risks"] is not None  # service-level includes risks


class TestDataRoutes:
    def test_aggregate(self, router):
        res = get(router, "/api/v1/data/aggregate")
        assert res.status == 200
        assert res.payload["services"]

    def test_aggregate_filter(self, router):
        res = get(router, "/api/v1/data/aggregate?filter=user-service")
        names = {s["uniqueServiceName"] for s in res.payload["services"]}
        assert all(n.startswith("user-service") for n in names)

    def test_history(self, router):
        res = get(router, "/api/v1/data/history")
        assert res.status == 200 and res.payload

    def test_service_display_info(self, router):
        res = get(router, "/api/v1/data/serviceDisplayInfo")
        assert res.status == 200
        assert all("endpointCount" in s for s in res.payload)

    def test_label_map(self, router):
        res = get(router, "/api/v1/data/label")
        assert res.status == 200
        assert isinstance(res.payload, list)

    def test_user_label_crud(self, router, ctx):
        missing = get(router, "/api/v1/data/label/user")
        assert missing.status == 404

        label = {
            "labels": [
                {
                    "label": "/custom/{}",
                    "samples": [],
                    "uniqueServiceName": "user-service\tpdas\tlatest",
                    "method": "GET",
                    "block": False,
                }
            ]
        }
        created = router.dispatch(
            "POST", "/api/v1/data/label/user", json.dumps(label).encode()
        )
        assert created.status == 201
        fetched = get(router, "/api/v1/data/label/user")
        assert fetched.status == 200 and fetched.payload["labels"]

        deleted = router.dispatch(
            "DELETE",
            "/api/v1/data/label/user",
            json.dumps(
                {
                    "label": "/custom/{}",
                    "uniqueServiceName": "user-service\tpdas\tlatest",
                    "method": "GET",
                }
            ).encode(),
        )
        assert deleted.status == 204

    def test_interface_crud(self, router):
        tagged = {
            "uniqueLabelName": "svc\tns\tv\tGET\t/x",
            "userLabel": "v1",
            "requestSchema": "",
            "responseSchema": "",
        }
        assert (
            router.dispatch(
                "POST", "/api/v1/data/interface", json.dumps(tagged).encode()
            ).status
            == 201
        )
        got = get(
            router,
            "/api/v1/data/interface?uniqueLabelName=svc%09ns%09v%09GET%09/x",
        )
        assert got.status == 200 and len(got.payload) == 1
        gone = router.dispatch(
            "DELETE",
            "/api/v1/data/interface",
            json.dumps(
                {"uniqueLabelName": "svc\tns\tv\tGET\t/x", "userLabel": "v1"}
            ).encode(),
        )
        assert gone.status == 204

    def test_datatype_by_label(self, router, ctx):
        dts = ctx.cache.get("EndpointDataType").get_data()
        raw = dts[0].to_json()
        label = ctx.cache.get("LabelMapping").get_label(raw["uniqueEndpointName"])
        unique_label = f"{raw['uniqueServiceName']}\t{raw['method']}\t{label}"
        from urllib.parse import quote

        res = get(
            router,
            "/api/v1/data/datatype/" + quote(unique_label, safe=""),
        )
        assert res.status == 200
        assert res.payload["labelName"] == label

    def test_sync_and_export(self, router, ctx):
        assert router.dispatch("POST", "/api/v1/data/sync").status == 200
        assert ctx.store.find_all("EndpointDependencies")
        res = get(router, "/api/v1/data/export")
        assert res.status == 200
        assert res.content_type == "application/tar+gzip"
        assert res.raw_body[:2] == b"\x1f\x8b"  # gzip magic

    def test_testing_endpoints(self, router, ctx):
        export = get(router, "/api/v1/data/export")
        assert router.dispatch("DELETE", "/api/v1/data/clear").status == 200
        assert ctx.store.get_aggregated_data() is None
        assert (
            router.dispatch(
                "POST", "/api/v1/data/import", export.raw_body
            ).status
            == 201
        )
        assert (
            router.dispatch("POST", "/api/v1/data/aggregate").status == 204
        )


class TestSwaggerRoutes:
    SVC = "user-service%09pdas%09latest"

    def test_get_swagger(self, router):
        res = get(router, f"/api/v1/swagger/{self.SVC}")
        assert res.status == 200
        assert res.payload["openapi"] == "3.0.1"
        assert res.payload["paths"]

    def test_get_swagger_yaml(self, router):
        res = get(router, f"/api/v1/swagger/yaml/{self.SVC}")
        assert res.status == 200
        assert res.content_type == "text/yaml"
        assert b"openapi" in res.raw_body

    def test_tag_lifecycle(self, router, ctx):
        doc = get(router, f"/api/v1/swagger/{self.SVC}").payload
        tagged = {
            "uniqueServiceName": "user-service\tpdas\tlatest",
            "tag": "v1.0",
            "openApiDocument": json.dumps(doc),
        }
        assert (
            router.dispatch(
                "POST", "/api/v1/swagger/tags", json.dumps(tagged).encode()
            ).status
            == 200
        )
        tags = get(router, f"/api/v1/swagger/tags/{self.SVC}")
        assert tags.payload == ["v1.0"]
        # tagging froze interfaces bound to the swagger
        bound = [
            i
            for i in ctx.cache.get("TaggedInterfaces").get_data()
            if i.get("boundToSwagger")
        ]
        assert bound
        # fetching by tag returns the frozen doc with version = tag
        frozen = get(router, f"/api/v1/swagger/{self.SVC}?tag=v1.0")
        assert frozen.payload["info"]["version"] == "v1.0"

        assert (
            router.dispatch(
                "DELETE",
                "/api/v1/swagger/tags",
                json.dumps(
                    {
                        "uniqueServiceName": "user-service\tpdas\tlatest",
                        "tag": "v1.0",
                    }
                ).encode(),
            ).status
            == 200
        )
        assert get(router, f"/api/v1/swagger/tags/{self.SVC}").payload == []
        assert not [
            i
            for i in ctx.cache.get("TaggedInterfaces").get_data()
            if i.get("boundToSwagger")
        ]


class TestAlertRoutes:
    def test_violation_empty(self, router):
        res = get(router, "/api/v1/alert/violation")
        assert res.status == 200
        assert res.payload == []

    def test_violation_detection(self, ctx, router):
        # fabricate history: stable risk then a 3-sigma spike in the latest bucket
        svc = "user-service\tpdas\tlatest"
        docs = []
        for i, risk in enumerate([0.2] * 20 + [0.9]):
            docs.append(
                {
                    "date": FIXTURE_NOW_MS - (21 - i) * 60_000,
                    "services": [
                        {
                            "uniqueServiceName": svc,
                            "service": "user-service",
                            "namespace": "pdas",
                            "version": "latest",
                            "date": FIXTURE_NOW_MS - (21 - i) * 60_000,
                            "requests": 10,
                            "requestErrors": 0,
                            "serverErrors": 0,
                            "latencyCV": 0.1,
                            "latencyMean": 10,
                            "risk": risk,
                            "endpoints": [],
                        }
                    ],
                }
            )
        ctx.store.clear_collection("HistoricalData")
        ctx.store.insert_many("HistoricalData", docs)
        ctx.cache.get("LookBackRealtimeData")._touch()

        res = get(router, "/api/v1/alert/violation")
        assert res.status == 200
        assert len(res.payload) == 1
        v = res.payload[0]
        assert v["uniqueServiceName"] == svc
        assert v["timeoutAt"] > v["occursAt"]
        # the dashboard (dist/index.html renderAlerts) reads these two
        assert v["displayName"] == "user-service.pdas (latest)"
        assert "highlightNodeName" in v


class TestComparatorRoutes:
    def test_diff_lifecycle(self, router):
        assert get(router, "/api/v1/comparator/tags").payload == []
        created = router.dispatch(
            "POST",
            "/api/v1/comparator/diffData",
            json.dumps({"tag": "snap1"}).encode(),
        )
        assert created.status == 200
        tags = get(router, "/api/v1/comparator/tags").payload
        assert [t["tag"] for t in tags] == ["snap1"]

        diff = get(router, "/api/v1/comparator/diffData?tag=snap1")
        assert diff.payload["graphData"]["nodes"]
        assert diff.payload["instabilityData"]

        latest = get(router, "/api/v1/comparator/diffData")
        assert latest.payload["graphData"]["nodes"]
        assert latest.payload["endpointDataTypesMap"]

        deleted = router.dispatch(
            "DELETE",
            "/api/v1/comparator/diffData",
            json.dumps({"tag": "snap1"}).encode(),
        )
        assert deleted.status == 200
        assert get(router, "/api/v1/comparator/tags").payload == []


class TestMiscRoutes:
    def test_configuration(self, router):
        res = get(router, "/api/v1/configuration/config")
        assert res.payload == {"SimulatorMode": False}

    def test_health(self, router):
        res = get(router, "/api/v1/health")
        assert res.payload["status"] == "UP"

    def test_unknown_route_404(self, router):
        assert get(router, "/api/v1/nope").status == 404

    def test_wrong_method_405(self, router):
        assert router.dispatch("DELETE", "/api/v1/health").status == 405


class TestHttpServer:
    def test_round_trip_with_gzip(self, router):
        server = ApiServer(router, host="127.0.0.1", port=0)
        server.start()
        try:
            req = urllib.request.Request(
                f"http://127.0.0.1:{server.port}/api/v1/graph/dependency/endpoint",
                headers={"Accept-Encoding": "gzip"},
            )
            with urllib.request.urlopen(req, timeout=10) as res:
                assert res.status == 200
                assert "max-age=5" in res.headers.get("Cache-Control", "")
                raw = res.read()
                if res.headers.get("Content-Encoding") == "gzip":
                    raw = gzip.decompress(raw)
                payload = json.loads(raw)
            assert payload["nodes"]
            # 404 path
            try:
                urllib.request.urlopen(
                    f"http://127.0.0.1:{server.port}/api/v1/nope", timeout=10
                )
                raised = False
            except urllib.error.HTTPError as e:
                raised = e.code == 404
            assert raised
        finally:
            server.stop()


class TestApplication:
    def test_full_startup_and_teardown(self, pdas_traces):
        s = Settings()
        s.external_data_processor = ""
        s.read_only_mode = True  # no scheduler threads in tests
        s.storage_uri = "memory://"
        processor = DataProcessor(
            trace_source=lambda lb, t, lim: [pdas_traces], k8s_source=None
        )
        ctx = AppContext.build(
            app_settings=s, store=MemoryStore(), processor=processor
        )
        app = Application(app_settings=s, ctx=ctx)
        app.start_up()
        app.listen(host="127.0.0.1", port=0)
        try:
            with urllib.request.urlopen(
                f"http://127.0.0.1:{app.server.port}/api/v1/health", timeout=10
            ) as res:
                assert json.loads(res.read())["status"] == "UP"
        finally:
            app.tear_down()


    def test_schedules_start_only_after_first_time_setup(
        self, pdas_traces, monkeypatch
    ):
        """A realtime tick that ran during first-time setup could write
        its pre-setup view of the caches back over the backfill: the
        jobs are registered before the setup and started after it."""
        from kmamiz_tpu.server.initializer import Initializer

        s = Settings()
        s.external_data_processor = ""
        s.storage_uri = "memory://"
        processor = DataProcessor(
            trace_source=lambda lb, t, lim: [], k8s_source=None
        )
        ctx = AppContext.build(
            app_settings=s, store=MemoryStore(), processor=processor
        )
        seen = {}

        def setup(self):
            seen["jobs"] = set(ctx.scheduler.jobs)
            seen["started_during_setup"] = ctx.scheduler._started

        monkeypatch.setattr(Initializer, "first_time_setup", setup)
        app = Application(app_settings=s, ctx=ctx)
        app.start_up()
        try:
            assert seen["jobs"] == {"aggregation", "realtime", "dispatch"}
            assert seen["started_during_setup"] is False
            assert ctx.scheduler._started is True
        finally:
            ctx.scheduler.stop()


class TestStaticServing:
    """The entry point serves the SPA build and the Envoy filter binary
    (reference index.ts:46-53)."""

    def _router(self, **kw):
        from kmamiz_tpu.api.router import Router

        return Router(api_version="1", **kw)

    def test_spa_files_and_fallback(self, tmp_path):
        dist = tmp_path / "dist"
        dist.mkdir()
        (dist / "index.html").write_text("<html>app</html>")
        (dist / "main.js").write_text("console.log(1)")
        router = self._router(static_dir=str(dist))

        r = router.dispatch("GET", "/")
        assert r.status == 200 and b"app" in r.raw_body
        assert r.content_type == "text/html"
        r = router.dispatch("GET", "/main.js")
        assert r.status == 200 and r.content_type == "application/javascript"
        # SPA client-side route falls back to the shell
        r = router.dispatch("GET", "/insight/dependency")
        assert r.status == 200 and b"app" in r.raw_body
        # missing asset with extension is a real 404
        assert router.dispatch("GET", "/missing.js").status == 404
        # API prefix never falls through to static
        assert router.dispatch("GET", "/api/v1/nope").status == 404

    def test_traversal_confined(self, tmp_path):
        dist = tmp_path / "dist"
        dist.mkdir()
        (dist / "index.html").write_text("shell")
        (tmp_path / "secret.txt").write_text("nope")
        router = self._router(static_dir=str(dist))
        r = router.dispatch("GET", "/../secret.txt")
        assert r.status != 200 or b"nope" not in (r.raw_body or b"")

    def test_wasm_binary(self, tmp_path):
        wasm = tmp_path / "filter.wasm"
        wasm.write_bytes(b"\x00asm...")
        router = self._router(wasm_path=str(wasm))
        r = router.dispatch("GET", "/wasm")
        assert r.status == 200
        assert r.content_type == "application/wasm"
        assert r.raw_body.startswith(b"\x00asm")

    def test_no_static_configured(self):
        from kmamiz_tpu.api.router import Router

        router = Router(api_version="1")
        assert router.dispatch("GET", "/anything").status == 404


class TestScorerPayloadCache:
    """VERDICT r2 #2: scorer payloads cache keyed by graph version +
    label freshness; merges invalidate automatically."""

    def test_repeat_requests_serve_cached_payload(self, router):
        for route in ("instability", "coupling", "cohesion"):
            r1 = get(router, f"/api/v1/graph/{route}")
            r2 = get(router, f"/api/v1/graph/{route}")
            assert r2.payload is r1.payload, route

    def test_graph_merge_invalidates(self, ctx, router):
        r1 = get(router, "/api/v1/graph/instability")
        ctx.processor._processed.clear()
        ctx.operator.retrieve_realtime_data()  # merges a window
        r2 = get(router, "/api/v1/graph/instability")
        assert r2.payload is not r1.payload
        assert r2.payload == r1.payload  # same window content, fresh build

    def test_label_update_invalidates(self, ctx, router):
        r1 = get(router, "/api/v1/graph/cohesion")
        label_map = ctx.cache.get("LabelMapping")
        label_map.set_data(None)  # recompute labels -> last_update bumps
        r2 = get(router, "/api/v1/graph/cohesion")
        assert r2.payload is not r1.payload

    def test_host_oracle_never_cached(self, router):
        r1 = get(router, "/api/v1/graph/instability?scorer=host")
        r2 = get(router, "/api/v1/graph/instability?scorer=host")
        assert r2.payload is not r1.payload

    def test_deprecated_threshold_disables_cache(self, router, monkeypatch):
        from kmamiz_tpu.config import settings

        monkeypatch.setattr(
            settings, "deprecated_endpoint_threshold", "1d"
        )
        r1 = get(router, "/api/v1/graph/instability")
        r2 = get(router, "/api/v1/graph/instability")
        assert r2.payload is not r1.payload


class TestDashboardContract:
    """dist/index.html is the in-tree SPA; these pin (a) that the router
    serves it and (b) that every endpoint the dashboard fetches returns
    the exact fields its JS reads (no JS runtime ships in CI, so the
    data contract is the testable surface)."""

    def test_static_serving(self, ctx):
        import os

        from kmamiz_tpu.api.app import build_router as _build

        ctx.settings.static_dir = os.path.join(
            os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
            "dist",
        )
        router = _build(ctx)
        r = router.dispatch("GET", "/")
        assert r.status == 200
        body = r.raw_body.decode()
        for el_id in (
            "tiles", "depgraph", "alerts", "linechart", "instability",
            "cohesion", "coupling", "stats", "ns-select", "health-text",
        ):
            assert f'id="{el_id}"' in body, el_id
        # SPA fallback for client routes
        assert router.dispatch("GET", "/insights").status == 200

    def test_fetched_shapes(self, router):
        svc = get(router, "/api/v1/data/serviceDisplayInfo").payload
        assert svc and {"service", "namespace", "endpointCount"} <= set(svc[0])

        dep = get(router, "/api/v1/graph/dependency/service").payload
        assert {"nodes", "links"} <= set(dep)
        assert {"id", "name"} <= set(dep["nodes"][0])
        assert {"source", "target"} <= set(dep["links"][0])

        line = get(router, "/api/v1/graph/line").payload
        assert {"dates", "services", "metrics"} <= set(line)
        assert len(line["metrics"][0][0]) == 6
        # the dashboard indexes the vector POSITIONALLY:
        # [requests, requestErrors, serverErrors, cv, mean, risk] — pin the
        # order by cross-checking position 0/4 against the historical docs
        rows = line["metrics"][0]
        svc_names = line["services"]
        assert all(r[0] == int(r[0]) and r[0] >= 0 for r in rows)  # counts
        # latencyMean (pos 4) must match the statistics endpoint's means
        stats_by_name = {
            s["name"]: s
            for s in get(router, "/api/v1/graph/statistics").payload
        }
        import math

        for name, r in zip(svc_names, rows):
            if name in stats_by_name and r[0] > 0:
                assert math.isclose(
                    r[4], stats_by_name[name]["latencyMean"], rel_tol=1e-6
                ), (name, r)

        instab = get(router, "/api/v1/graph/instability").payload
        assert {"name", "instability", "dependingOn", "dependingBy"} <= set(
            instab[0]
        )
        coh = get(router, "/api/v1/graph/cohesion").payload
        assert {
            "name", "totalInterfaceCohesion", "usageCohesion", "dataCohesion"
        } <= set(coh[0])
        coup = get(router, "/api/v1/graph/coupling").payload
        assert {"name", "ais", "ads", "acs"} <= set(coup[0])

        stats = get(router, "/api/v1/graph/statistics").payload
        assert {
            "name", "latencyMean", "serverErrorRate", "requestErrorsRate"
        } <= set(stats[0])

        alerts = get(router, "/api/v1/alert/violation").payload
        assert isinstance(alerts, list)  # row fields pinned in TestAlertRoutes.test_violation_detection

    def test_round4_sections_served(self, ctx):
        import os

        from kmamiz_tpu.api.app import build_router as _build

        ctx.settings.static_dir = os.path.join(
            os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
            "dist",
        )
        router = _build(ctx)
        body = router.dispatch("GET", "/").raw_body.decode()
        for el_id in (
            "chord", "swagger-select", "swagger", "compare-select",
            "compare", "compare-snap",
        ):
            assert f'id="{el_id}"' in body, el_id

    def test_chord_shapes(self, router):
        # renderChord reads nodes[].id and links[].{from,to,value}
        for kind in ("direct", "indirect"):
            chord = get(router, f"/api/v1/graph/chord/{kind}").payload
            assert {"nodes", "links"} <= set(chord)
            assert chord["nodes"], kind
            assert {"id", "name"} <= set(chord["nodes"][0])
            assert {"from", "to", "value"} <= set(chord["links"][0])
        # indirect includes at least every direct link
        direct = get(router, "/api/v1/graph/chord/direct").payload
        indirect = get(router, "/api/v1/graph/chord/indirect").payload
        d_pairs = {(l["from"], l["to"]) for l in direct["links"]}
        i_pairs = {(l["from"], l["to"]) for l in indirect["links"]}
        assert d_pairs <= i_pairs

    def test_swagger_viewer_shapes(self, router):
        # the viewer picks services from serviceDisplayInfo and fetches
        # /swagger/:usn expecting an OpenAPI doc with paths/info
        svc = get(router, "/api/v1/data/serviceDisplayInfo").payload
        assert svc and svc[0]["uniqueServiceName"]
        usn = svc[0]["uniqueServiceName"]
        from urllib.parse import quote

        doc = get(router, f"/api/v1/swagger/{quote(usn, safe='')}").payload
        assert doc["openapi"].startswith("3.")
        assert {"title", "version"} <= set(doc["info"])
        assert doc["paths"]
        path, methods = next(iter(doc["paths"].items()))
        assert path.startswith("/")
        method, op = next(iter(methods.items()))
        assert "responses" in op
        # the yaml link the viewer renders must also serve
        y = get(router, f"/api/v1/swagger/yaml/{quote(usn, safe='')}")
        assert y.status == 200

    def test_comparator_diff_shapes(self, router):
        # snapshot via POST, list via /tags, diff both tagged and live
        assert router.dispatch(
            "POST", "/api/v1/comparator/diffData",
            body=json.dumps({"tag": "dash-test"}).encode(),
        ).status == 200
        tags = get(router, "/api/v1/comparator/tags").payload
        assert any(t["tag"] == "dash-test" and "time" in t for t in tags)
        for q in ("?tag=dash-test", ""):
            diff = get(router, "/api/v1/comparator/diffData" + q).payload
            assert {
                "graphData", "cohesionData", "couplingData",
                "instabilityData",
            } <= set(diff)
            assert {"nodes", "links"} <= set(diff["graphData"])
            if diff["instabilityData"]:
                row = diff["instabilityData"][0]
                assert {"uniqueServiceName", "name", "instability"} <= set(row)
            if diff["couplingData"]:
                assert {"uniqueServiceName", "acs"} <= set(diff["couplingData"][0])
            if diff["cohesionData"]:
                assert {
                    "uniqueServiceName", "totalInterfaceCohesion"
                } <= set(diff["cohesionData"][0])

    def test_forecast_section_served(self, ctx):
        import os

        from kmamiz_tpu.api.app import build_router as _build

        ctx.settings.static_dir = os.path.join(
            os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
            "dist",
        )
        router = _build(ctx)
        body = router.dispatch("GET", "/").raw_body.decode()
        assert 'id="sec-forecast"' in body
        assert 'id="forecast"' in body

    def test_forecast_shapes(self, pdas_traces):
        """renderForecast reads modelLoaded/error from /model/status and
        endpoints[].{uniqueEndpointName, anomalyProbability,
        predictedLatencyMs} + predictedHour from /model/forecast — pin
        those fields against the committed 10k-endpoint checkpoint."""
        import os

        from kmamiz_tpu.api.app import build_router as _build
        from kmamiz_tpu.server.initializer import AppContext, Initializer
        from kmamiz_tpu.server.processor import DataProcessor
        from kmamiz_tpu.server.storage import MemoryStore

        dp = DataProcessor(
            trace_source=_prefixed_trace_source(pdas_traces, "d"),
            use_device_stats=False,
        )
        settings = Settings()
        settings.external_data_processor = ""
        settings.model_dir = os.path.join(
            os.path.dirname(os.path.abspath(__file__)),
            "fixtures",
            "model10k",
        )
        ctx = AppContext.build(
            app_settings=settings, store=MemoryStore(), processor=dp
        )
        Initializer(ctx).register_data_caches()
        model_router = _build(ctx)

        status = model_router.dispatch("GET", "/api/v1/model/status").payload
        assert {"modelLoaded", "error", "featureHourReady"} <= set(status)
        assert status["modelLoaded"] is True

        H = 3_600_000
        dp.collect({"uniqueId": "a", "lookBack": 30_000, "time": 910 * H})
        dp.collect({"uniqueId": "b", "lookBack": 30_000, "time": 911 * H})
        fc = model_router.dispatch("GET", "/api/v1/model/forecast").payload
        assert {"endpoints", "predictedHour"} <= set(fc)
        assert fc["endpoints"]
        assert {
            "uniqueEndpointName", "anomalyProbability", "predictedLatencyMs"
        } <= set(fc["endpoints"][0])

        # polls between folds serve the memoized payload (dashboards
        # refresh every few seconds; the forecast changes hourly), and a
        # new fold invalidates it
        fc2 = model_router.dispatch("GET", "/api/v1/model/forecast").payload
        assert fc2 is fc
        dp.collect({"uniqueId": "c", "lookBack": 30_000, "time": 912 * H})
        fc3 = model_router.dispatch("GET", "/api/v1/model/forecast").payload
        assert fc3 is not fc
        # the tick at hour 912 folds the COMPLETED hour 911
        assert fc3["predictedHour"] == (911 % 24 + 1) % 24

    def test_js_dom_ids_and_routes_are_consistent(self, router):
        """Static cross-check of the dashboard's inline JS (no JS runtime
        ships in this image): every DOM id the script references must
        exist in the markup, and every API path it fetches must resolve
        to a registered route of the right METHOD — a typo in either
        renders a silently blank section in production."""
        import re
        from pathlib import Path

        html = (
            Path(__file__).resolve().parent.parent / "dist" / "index.html"
        ).read_text(encoding="utf-8")
        dom_ids = set(re.findall(r'id="([^"]+)"', html))
        # $("x"), getElementById("x"), and querySelector[All]("#x ...")
        # references in the script (the selector's leading #id must exist)
        refs = (
            re.findall(r'\$\("([^"]+)"\)', html)
            + re.findall(r'getElementById\("([^"]+)"\)', html)
            + re.findall(r'querySelector(?:All)?\("#([\w-]+)', html)
        )
        for ref in refs:
            assert ref in dom_ids, f"JS references missing DOM id {ref!r}"

        def route_exists(path: str, method: str, dynamic_tail: bool) -> bool:
            """A registered route of `method` serves `path`. A literal
            path may only extend into OPTIONAL param segments (":x?");
            a path built with a dynamic JS suffix ("+ usn") may extend
            into required ones too."""
            path = path.split("?", 1)[0].rstrip("/")
            for r in router._routes:
                if r.method != method.upper():
                    continue
                raw = r.raw_path.rstrip("/")
                if raw == path:
                    return True
                if raw.startswith(path + "/"):
                    tail = raw[len(path) + 1 :]
                    segs = tail.split("/")
                    if dynamic_tail and segs[0].startswith(":"):
                        return True
                    if all(
                        s.startswith(":") and s.endswith("?") for s in segs
                    ):
                        return True
            return False

        # jget("...") GETs; a trailing '/' or a '+'-concatenation marks a
        # dynamic suffix (ns / usn / tag appended at runtime)
        for path, cont in re.findall(r'jget\("(/[^"]+)"( *\+)?', html):
            dyn = bool(cont) or path.endswith("/")
            assert route_exists("/api/v1" + path, "GET", dyn), path
        # fetch(API + "...", {...}) — method-aware: scan a window after
        # each call site for a method: "X" literal, bounded by the NEXT
        # fetch call so adjacent calls cannot cross-contaminate
        for m in re.finditer(r'fetch\(API \+ "(/[^"]+)"', html):
            window = html[m.end() : m.end() + 400]
            nxt = window.find("fetch(")
            if nxt != -1:
                window = window[:nxt]
            method_m = re.search(r'method:\s*"([A-Z]+)"', window)
            method = method_m.group(1) if method_m else "GET"
            assert route_exists("/api/v1" + m.group(1), method, False), (
                method,
                m.group(1),
            )


from conftest import prefixed_trace_source as _prefixed_trace_source


def _train_tiny_checkpoint(
    checkpoint_dir, epochs=1, augmented=True, **train_kw
):
    """Train the smallest viable head on the simulator fault mesh and
    write a checkpoint — the shared setup of every TestModelRoutes case."""
    import numpy as np

    from kmamiz_tpu.models import history, trainer
    from test_trainer import FAULT_YAML
    from kmamiz_tpu.simulator.simulator import Simulator

    sim = Simulator().generate_simulation_data(
        FAULT_YAML, 0.0, rng=np.random.default_rng(7)
    )
    ds = trainer.dataset_from_simulation(
        sim.endpoint_dependencies,
        sim.realtime_data_per_slot,
        sim.replica_counts,
    )
    if augmented:
        ds = history.augment_with_history(ds)
    trainer.train(
        ds, epochs=epochs, hidden=8, seed=0,
        checkpoint_dir=str(checkpoint_dir), checkpoint_every=0, **train_kw,
    )


class TestModelRoutes:
    """Forecast routes: a checkpointed head served against the features
    the realtime tick produces online (handlers/model.py)."""

    def test_status_unconfigured(self, router):
        res = get(router, "/api/v1/model/status")
        assert res.status == 200
        assert res.payload["modelLoaded"] is False
        assert "KMAMIZ_MODEL_DIR" in res.payload["error"]
        res = get(router, "/api/v1/model/forecast")
        assert res.status == 503

    def test_forecast_end_to_end(self, pdas_traces, tmp_path):
        """Train a tiny augmented-feature head on simulated faults, save
        a checkpoint, tick a processor across an hour boundary, and read
        the forecast through the HTTP surface."""
        from kmamiz_tpu.api.app import build_router as _build
        from kmamiz_tpu.server.initializer import AppContext, Initializer
        from kmamiz_tpu.server.processor import DataProcessor
        from kmamiz_tpu.server.storage import MemoryStore

        _train_tiny_checkpoint(tmp_path, epochs=4)

        dp = DataProcessor(
            trace_source=_prefixed_trace_source(pdas_traces, "f"),
            use_device_stats=False,
        )
        settings = Settings()
        settings.external_data_processor = ""
        settings.model_dir = str(tmp_path)
        ctx = AppContext.build(
            app_settings=settings, store=MemoryStore(), processor=dp
        )
        Initializer(ctx).register_data_caches()
        model_router = _build(ctx)

        H = 3_600_000
        t0 = 900 * H
        dp.collect({"uniqueId": "m1", "lookBack": 30_000, "time": t0})
        # before the first completed hour: model loads, features pending
        res = model_router.dispatch("GET", "/api/v1/model/forecast")
        assert res.status == 503
        status = model_router.dispatch("GET", "/api/v1/model/status").payload
        assert status["modelLoaded"] is True
        assert status["checkpoint"]["numFeatures"] == 18

        dp.collect({"uniqueId": "m2", "lookBack": 30_000, "time": t0 + H})
        res = model_router.dispatch("GET", "/api/v1/model/forecast")
        assert res.status == 200, res.payload
        body = res.payload
        assert body["predictedHour"] == (900 % 24 + 1) % 24
        eps = body["endpoints"]
        assert eps and len(eps) == len(dp.graph.interner.endpoints)
        for row in eps:
            assert 0.0 <= row["anomalyProbability"] <= 1.0
            assert row["predictedLatencyMs"] >= 0.0
            assert "\t" in row["uniqueEndpointName"]
        # sorted most-suspicious first
        probs = [r["anomalyProbability"] for r in eps]
        assert probs == sorted(probs, reverse=True)

    @pytest.mark.parametrize("head", ("gat", "pna"))
    def test_a_checkpoint_is_served_by_the_head_its_metadata_names(self, pdas_traces, tmp_path, head):
        """`trainer.train(model=<head>)` writes the head's name into the checkpoint's metadata; the handler loads
        that head's parameters and serves its forward (bucket-padded, no plan: models/serving.py)."""
        import importlib

        from kmamiz_tpu.api.app import build_router as _build
        from kmamiz_tpu.server.initializer import AppContext, Initializer
        from kmamiz_tpu.server.processor import DataProcessor
        from kmamiz_tpu.server.storage import MemoryStore

        model = importlib.import_module(f"kmamiz_tpu.models.{head}")
        _train_tiny_checkpoint(tmp_path, epochs=2, model=model)
        dp = DataProcessor(trace_source=_prefixed_trace_source(pdas_traces, head), use_device_stats=False)
        settings = Settings()
        settings.external_data_processor = ""
        settings.model_dir = str(tmp_path)
        ctx = AppContext.build(app_settings=settings, store=MemoryStore(), processor=dp)
        Initializer(ctx).register_data_caches()
        model_router = _build(ctx)
        H = 3_600_000
        dp.collect({"uniqueId": "h1", "lookBack": 30_000, "time": 910 * H})
        dp.collect({"uniqueId": "h2", "lookBack": 30_000, "time": 911 * H})
        status = model_router.dispatch("GET", "/api/v1/model/status").payload
        assert status["modelLoaded"] is True and status["checkpoint"]["model"] == head
        res = model_router.dispatch("GET", "/api/v1/model/forecast")
        assert res.status == 200, res.payload
        eps = res.payload["endpoints"]
        assert eps and len(eps) == len(dp.graph.interner.endpoints)
        assert all(0.0 <= row["anomalyProbability"] <= 1.0 and row["predictedLatencyMs"] >= 0.0 for row in eps)

    def test_forecast_memo_label_epoch_invalidation(
        self, pdas_traces, tmp_path
    ):
        """The forecast memo keys on the fold's (graph version,
        label epoch, hour) cache_key: a label-epoch bump must evict the
        cached payload, the recompute must reuse the already-compiled
        bucket program (zero new jit compiles — same shapes), and a
        same-key poll must serve the identical payload object."""
        from kmamiz_tpu.api.app import build_router as _build
        from kmamiz_tpu.core import programs
        from kmamiz_tpu.server.initializer import AppContext, Initializer
        from kmamiz_tpu.server.processor import DataProcessor
        from kmamiz_tpu.server.storage import MemoryStore

        _train_tiny_checkpoint(tmp_path, epochs=1)
        dp = DataProcessor(
            trace_source=_prefixed_trace_source(pdas_traces, "memo"),
            use_device_stats=False,
        )
        settings = Settings()
        settings.external_data_processor = ""
        settings.model_dir = str(tmp_path)
        ctx = AppContext.build(
            app_settings=settings, store=MemoryStore(), processor=dp
        )
        Initializer(ctx).register_data_caches()
        model_router = _build(ctx)

        H = 3_600_000
        dp.collect({"uniqueId": "k1", "lookBack": 30_000, "time": 920 * H})
        dp.collect({"uniqueId": "k2", "lookBack": 30_000, "time": 921 * H})
        fc = model_router.dispatch("GET", "/api/v1/model/forecast").payload

        # same key, same snapshot: memoized object, zero compiles
        prog_snap = programs.snapshot()
        fc2 = model_router.dispatch("GET", "/api/v1/model/forecast").payload
        assert fc2 is fc
        assert programs.new_compiles_since(prog_snap) == {}

        # a label-epoch bump (what a label-advancing fold publishes)
        # evicts: the payload is recomputed — but against the SAME
        # capacity buckets, so still zero new compiles
        snap = dp.forecast_snapshot
        version, label_epoch, hour = snap["cache_key"]
        bumped = dict(snap)
        bumped["cache_key"] = (version, label_epoch + 1, hour)
        dp.forecast_snapshot = bumped
        prog_snap = programs.snapshot()
        fc3 = model_router.dispatch("GET", "/api/v1/model/forecast").payload
        assert fc3 is not fc
        assert programs.new_compiles_since(prog_snap) == {}
        # and the bumped key memoizes in turn
        fc4 = model_router.dispatch("GET", "/api/v1/model/forecast").payload
        assert fc4 is fc3

    def test_empty_checkpoint_dir_retries(self, tmp_path, monkeypatch):
        """A missing first checkpoint is TRANSIENT: the handler must
        re-attempt the load once the trainer writes one, instead of
        pinning a 503 until process restart (ADVICE r4)."""
        from kmamiz_tpu.api.app import build_router as _build
        from kmamiz_tpu.api.handlers.model import ModelHandler
        from kmamiz_tpu.server.initializer import AppContext, Initializer
        from kmamiz_tpu.server.processor import DataProcessor
        from kmamiz_tpu.server.storage import MemoryStore

        monkeypatch.setattr(ModelHandler, "RETRY_SECONDS", 0.0)
        settings = Settings()
        settings.external_data_processor = ""
        settings.model_dir = str(tmp_path)  # exists but empty
        dp = DataProcessor(trace_source=lambda *a: [], use_device_stats=False)
        ctx = AppContext.build(
            app_settings=settings, store=MemoryStore(), processor=dp
        )
        Initializer(ctx).register_data_caches()
        model_router = _build(ctx)
        status = model_router.dispatch("GET", "/api/v1/model/status").payload
        assert status["modelLoaded"] is False
        assert "no complete checkpoint" in status["error"]

        # the trainer writes its first checkpoint AFTER the server booted
        _train_tiny_checkpoint(tmp_path)
        status = model_router.dispatch("GET", "/api/v1/model/status").payload
        assert status["modelLoaded"] is True, status
        assert status["error"] is None

    def test_embedding_checkpoint_rejected(self, pdas_traces, tmp_path):
        from kmamiz_tpu.api.app import build_router as _build
        from kmamiz_tpu.server.initializer import AppContext, Initializer
        from kmamiz_tpu.server.processor import DataProcessor
        from kmamiz_tpu.server.storage import MemoryStore

        _train_tiny_checkpoint(
            tmp_path, augmented=False, use_node_embeddings=True
        )
        settings = Settings()
        settings.external_data_processor = ""
        settings.model_dir = str(tmp_path)
        dp = DataProcessor(trace_source=lambda *a: [], use_device_stats=False)
        ctx = AppContext.build(
            app_settings=settings, store=MemoryStore(), processor=dp
        )
        Initializer(ctx).register_data_caches()
        model_router = _build(ctx)
        status = model_router.dispatch("GET", "/api/v1/model/status").payload
        assert status["modelLoaded"] is False
        assert "identity" in status["error"]


class TestServeOnlyBootWeight:
    """Serve-only boot must answer health fast (VERDICT r4 #7): no device
    work can ever happen in that mode, so nothing on its import closure
    may pull jax (in environments without an interpreter-level preload,
    jax import alone costs seconds) and nothing at boot may trigger the
    native-extension build."""

    def test_serve_only_import_closure_is_jax_free(self):
        """Static audit: walk the import graph of kmamiz_tpu.api.app
        (the serve-only entry) and assert no reachable first-party
        module has a TOP-LEVEL jax import — device modules must be
        imported lazily from the paths that use them."""
        import ast
        import os

        pkg_root = os.path.dirname(
            os.path.dirname(os.path.abspath(__file__))
        )

        def module_path(mod):
            base = os.path.join(pkg_root, mod.replace(".", os.sep))
            for cand in (base + ".py", os.path.join(base, "__init__.py")):
                if os.path.isfile(cand):
                    return cand
            return None

        def top_level_imports(path):
            tree = ast.parse(open(path).read())
            out = set()
            for node in tree.body:
                if isinstance(node, ast.Import):
                    out.update(a.name for a in node.names)
                elif isinstance(node, ast.ImportFrom) and node.module:
                    out.add(node.module)
            return out

        seen, stack = set(), ["kmamiz_tpu.api.app"]
        offenders = []
        while stack:
            mod = stack.pop()
            if mod in seen:
                continue
            seen.add(mod)
            path = module_path(mod)
            if path is None:
                continue  # stdlib / third-party
            for imp in top_level_imports(path):
                if imp == "jax" or imp.startswith("jax."):
                    offenders.append(mod)
                elif imp.startswith("kmamiz_tpu"):
                    stack.append(imp)
        assert not offenders, (
            f"serve-only import closure pulls jax via: {offenders}"
        )

    def test_read_only_skips_native_probe(self, monkeypatch):
        """Read-only mode never ingests raw spans; boot must not pay the
        native-extension build probe."""
        from kmamiz_tpu import native
        from kmamiz_tpu.api import app as app_mod

        called = []
        monkeypatch.setattr(
            native, "available", lambda: called.append(1) or True
        )
        settings = Settings()
        settings.read_only_mode = True
        settings.serve_only = False
        settings.simulator_mode = False
        settings.external_data_processor = ""
        settings.storage_uri = "memory://"
        ctx = app_mod.build_production_context(settings)
        assert called == []
        assert ctx.processor is not None  # clients still built (sync handshake)


class TestSwaggerTagLabels:
    SVC = "user-service%09pdas%09latest"

    def test_frozen_interfaces_carry_resolved_labels(self, router, ctx):
        """Regression (review r5): tagging resolves each datatype's
        label through the label map (the way get_swagger does) — the
        cached datatypes carry no labelName field, and reading it
        yielded one None-keyed bucket merging every endpoint's schemas
        with uniqueLabelName '...\\tNone'."""
        import json as _json

        doc = get(router, f"/api/v1/swagger/{self.SVC}").payload
        tagged = {
            "uniqueServiceName": "user-service\tpdas\tlatest",
            "tag": "vlabels",
            "openApiDocument": _json.dumps(doc),
        }
        assert (
            router.dispatch(
                "POST", "/api/v1/swagger/tags", _json.dumps(tagged).encode()
            ).status
            == 200
        )
        bound = [
            i
            for i in ctx.cache.get("TaggedInterfaces").get_data()
            if i.get("boundToSwagger")
        ]
        assert bound
        labels = {i["uniqueLabelName"].split("\t")[-1] for i in bound}
        assert "None" not in labels  # every frozen interface got a label
        # the labels match the label map's view of this service
        label_map = ctx.cache.get("LabelMapping")
        expected = {
            label_map.get_label(d.to_json()["uniqueEndpointName"])
            for d in ctx.cache.get("EndpointDataType").get_data()
            if d.to_json()["uniqueServiceName"]
            == "user-service\tpdas\tlatest"
        }
        assert labels == {str(e) for e in expected if e is not None} or (
            labels and labels.issubset({str(e) for e in expected})
        )
        router.dispatch(
            "DELETE",
            "/api/v1/swagger/tags",
            _json.dumps(
                {
                    "uniqueServiceName": "user-service\tpdas\tlatest",
                    "tag": "vlabels",
                }
            ).encode(),
        )


class TestConcurrentCacheMutation:
    def test_parallel_tagged_interface_adds_lose_nothing(self, ctx):
        """Regression (review r5): compound read-modify-write updates on
        the tagged caches serialize on the per-cache update lock — two
        concurrent adds previously both read the same list and the
        second set_data silently discarded the first item. 8 threads x
        25 adds must all survive, across three cache kinds. A tiny GIL
        switch interval forces preemption INSIDE the read-modify-write
        window, which reliably loses items on the unlocked code."""
        import sys
        import threading

        old_interval = sys.getswitchinterval()
        sys.setswitchinterval(1e-5)
        interfaces = ctx.cache.get("TaggedInterfaces")
        swaggers = ctx.cache.get("TaggedSwaggers")
        labels = ctx.cache.get("UserDefinedLabel")
        n_threads, per = 8, 300

        def work(t):
            for i in range(per):
                interfaces.add(
                    {
                        "uniqueLabelName": f"svc\tGET\tl{t}-{i}",
                        "userLabel": f"u{t}-{i}",
                        "requestSchema": "",
                        "responseSchema": "",
                    }
                )
                swaggers.add(
                    {
                        "uniqueServiceName": f"s{t}\tns\tv",
                        "tag": f"tag{t}-{i}",
                        "openApiDocument": "{}",
                    }
                )
                labels.add(
                    {
                        "labels": [
                            {
                                "label": f"L{t}-{i}",
                                "uniqueServiceName": f"s{t}\tns\tv",
                                "method": "GET",
                                "samples": [],
                            }
                        ]
                    }
                )

        threads = [
            threading.Thread(target=work, args=(t,)) for t in range(n_threads)
        ]
        try:
            for th in threads:
                th.start()
            for th in threads:
                th.join()
        finally:
            sys.setswitchinterval(old_interval)

        assert len(interfaces.get_data()) == n_threads * per
        assert len(swaggers.get_data()) == n_threads * per
        assert len(labels.get_data()["labels"]) == n_threads * per


class TestRouterHttpSemantics:
    """Review r5: the HTTP layer must match the reference's Express
    stack — single query decode, double path-param decode, chunked
    request bodies, CORS on every response, OPTIONS preflight, HEAD."""

    def _server(self):
        from kmamiz_tpu.api.router import (
            ApiServer,
            IRequestHandler,
            Response,
            Router,
        )

        class H(IRequestHandler):
            def __init__(self):
                super().__init__("t")
                self.add_route(
                    "get",
                    "/echo",
                    lambda req: Response(payload={"q": req.query}),
                )
                self.add_route(
                    "get",
                    "/p/:name",
                    lambda req: Response(payload={"p": req.params["name"]}),
                )
                self.add_route(
                    "post",
                    "/body",
                    lambda req: Response(
                        payload={"len": len(req.body or b"")}
                    ),
                )

        r = Router()
        r.add_handler(H())
        srv = ApiServer(r, host="127.0.0.1", port=0)
        srv.start()
        return srv, srv._server.server_address[1]

    def test_http_layer_matches_express(self):
        import socket
        import urllib.request

        srv, port = self._server()
        base = f"http://127.0.0.1:{port}/api/v1/t"
        try:
            # query: decoded exactly ONCE (parse_qs); %2520 -> "%20"
            with urllib.request.urlopen(base + "/echo?tag=50%2520off") as r:
                assert json.loads(r.read())["q"]["tag"] == "50%20off"
            # path params: decoded TWICE (Express + handler convention)
            with urllib.request.urlopen(base + "/p/a%2509b") as r:
                assert json.loads(r.read())["p"] == "a\tb"
            # HEAD: true content-length, no body, CORS header
            req = urllib.request.Request(base + "/echo", method="HEAD")
            with urllib.request.urlopen(req) as r:
                assert int(r.headers["Content-Length"]) > 0
                assert r.read() == b""
                assert r.headers["Access-Control-Allow-Origin"] == "*"
            # OPTIONS preflight answers 204 + CORS
            req = urllib.request.Request(base + "/echo", method="OPTIONS")
            with urllib.request.urlopen(req) as r:
                assert r.status == 204
                assert r.headers["Access-Control-Allow-Origin"] == "*"
            # chunked request body
            s = socket.create_connection(("127.0.0.1", port))
            s.sendall(
                b"POST /api/v1/t/body HTTP/1.1\r\nHost: x\r\n"
                b"Transfer-Encoding: chunked\r\n\r\n"
                b"5\r\nhello\r\n3\r\nabc\r\n0\r\n\r\n"
            )
            # headers and body may land in separate TCP segments
            s.settimeout(5)
            got = b""
            while b'"len": 8' not in got:
                chunk = s.recv(65536)
                assert chunk, f"connection closed early: {got!r}"
                got += chunk
            s.close()
        finally:
            srv.stop()
