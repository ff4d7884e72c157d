"""Orchestration layer: operator schedules, service utils, init, import/export.

Mirrors the reference behaviors in src/services/ServiceOperator.ts,
ServiceUtils.ts, Initializer.ts, and ImportExportHandler.ts over the
in-process TPU DataProcessor.
"""
import pytest

from kmamiz_tpu.config import Settings
from kmamiz_tpu.server.import_export import ImportExportHandler
from kmamiz_tpu.server.initializer import AppContext, Initializer
from kmamiz_tpu.server.processor import DataProcessor
from kmamiz_tpu.server.storage import MemoryStore


# a "now" in the fixtures' era so 30-day retention windows keep them visible
FIXTURE_NOW_MS = 1646208500000


def make_ctx(pdas_traces, simulator_mode=False, read_only=False):
    s = Settings()
    s.simulator_mode = simulator_mode
    s.read_only_mode = read_only
    s.external_data_processor = ""
    processor = DataProcessor(
        trace_source=lambda look_back, time, limit: [pdas_traces],
        k8s_source=None,
    )
    ctx = AppContext.build(
        app_settings=s, store=MemoryStore(), processor=processor
    )
    ctx.service_utils._now_ms = lambda: FIXTURE_NOW_MS
    Initializer(ctx).register_data_caches()
    return ctx


@pytest.fixture()
def ctx(pdas_traces):
    return make_ctx(pdas_traces)


class TestRealtimeSchedule:
    def test_tick_populates_caches(self, ctx):
        ctx.operator.retrieve_realtime_data()
        combined = ctx.cache.get("CombinedRealtimeData").get_data()
        deps = ctx.cache.get("EndpointDependencies").get_data()
        labeled = ctx.cache.get("LabeledEndpointDependencies").get_data()
        dts = ctx.cache.get("EndpointDataType").get_data()
        assert combined and len(combined.to_json()) == 3
        assert deps and len(deps.to_json()) == 4
        assert labeled and len(labeled.to_json()) == 4
        assert dts
        # datatype schemas got requestParams re-derived (ServiceOperator.ts:267-271)
        for dt in dts:
            assert "requestParams" in dt.to_json()["schemas"][0]

    def test_second_tick_dedups(self, ctx):
        ctx.operator.retrieve_realtime_data()
        first = ctx.cache.get("CombinedRealtimeData").get_data().to_json()
        ctx.operator.retrieve_realtime_data()
        second = ctx.cache.get("CombinedRealtimeData").get_data().to_json()
        # same traces filtered by the processed-trace map; cache merge is a no-op
        assert sum(r["combined"] for r in first) == sum(
            r["combined"] for r in second
        )

    def test_external_fallback(self, ctx):
        # unreachable external DP -> falls back to the in-process processor
        ctx.operator._external_dp_url = "http://127.0.0.1:9/dead"
        ctx.operator.retrieve_realtime_data()
        assert ctx.cache.get("CombinedRealtimeData").get_data() is not None


class TestAggregationSchedule:
    def test_creates_historical_and_aggregated(self, ctx):
        ctx.operator.retrieve_realtime_data()
        ctx.operator.create_historical_and_aggregated_data(1646208400000)

        historical = ctx.store.find_all("HistoricalData")
        assert len(historical) == 1
        services = historical[0]["services"]
        assert services and all("risk" in s for s in services)

        aggregated = ctx.store.get_aggregated_data()
        assert aggregated and aggregated["services"]

        # realtime cache reset after aggregation (ServiceOperator.ts:142-145)
        assert ctx.cache.get("CombinedRealtimeData").get_data() is None

    def test_aggregate_combines_with_previous(self, ctx, pdas_traces):
        ctx.operator.retrieve_realtime_data()
        ctx.operator.create_historical_and_aggregated_data(1646208400000)
        first = ctx.store.get_aggregated_data()

        # new window of the same traffic
        ctx.processor._processed.clear()
        ctx.operator.retrieve_realtime_data()
        ctx.operator.create_historical_and_aggregated_data(1646208700000)
        second = ctx.store.get_aggregated_data()

        req_first = sum(
            e["totalRequests"] for s in first["services"] for e in s["endpoints"]
        )
        req_second = sum(
            e["totalRequests"] for s in second["services"] for e in s["endpoints"]
        )
        assert req_second == 2 * req_first
        # running aggregate stays a single upserted document
        assert len(ctx.store.find_all("AggregatedData")) == 1

    def test_look_back_window_populated(self, ctx):
        ctx.operator.retrieve_realtime_data()
        ctx.operator.create_historical_and_aggregated_data(1646208400000)
        look_back = ctx.cache.get("LookBackRealtimeData")._data
        assert 1646208400000 in look_back

    def test_empty_cache_skips(self, ctx):
        ctx.operator.create_historical_and_aggregated_data()
        assert ctx.store.find_all("HistoricalData") == []


class TestServiceUtils:
    def test_update_label_builds_mapping(self, ctx):
        ctx.operator.retrieve_realtime_data()
        label_map = ctx.cache.get("LabelMapping").get_data()
        assert label_map is not None

    def test_historical_gap_fill(self, ctx):
        ctx.operator.retrieve_realtime_data()
        ctx.operator.create_historical_and_aggregated_data(1646208400000)
        # fabricate a second bucket missing every service
        ctx.store.insert_many(
            "HistoricalData", [{"date": 1646208460000, "services": []}]
        )
        filled = ctx.service_utils.get_realtime_historical_data()
        assert len(filled) == 2
        names = [
            {s["uniqueServiceName"] for s in h["services"]} for h in filled
        ]
        # the empty bucket got padded with zeroed copies of its neighbor
        assert names[0] == names[1]
        padded = filled[1]["services"][0]
        assert padded["requests"] == 0 and padded["risk"] == 0

    def test_realtime_aggregated_with_not_before(self, ctx):
        ctx.operator.retrieve_realtime_data()
        ctx.operator.create_historical_and_aggregated_data(1646208400000)
        agg = ctx.service_utils.get_realtime_aggregated_data(
            time_offset_ms=86_400_000
        )
        assert agg and agg["services"]


class TestImportExport:
    def test_round_trip(self, ctx):
        ctx.operator.retrieve_realtime_data()
        ctx.operator.create_historical_and_aggregated_data(1646208400000)

        handler = ImportExportHandler(ctx, now_ms=lambda: FIXTURE_NOW_MS)
        blob = handler.export_tgz()
        pairs = handler.read_tgz(blob)
        names = {name for name, _ in pairs}
        assert {"AggregatedData", "HistoricalData", "EndpointDependencies"} <= names

        handler.clear_data()
        assert ctx.store.get_aggregated_data() is None

        assert handler.import_data(pairs)
        assert ctx.store.get_aggregated_data() is not None
        assert ctx.store.find_all("HistoricalData")
        assert ctx.cache.get("EndpointDependencies").get_data() is not None
        # LookBackRealtimeData is re-registered even though it never exports
        assert ctx.cache.get("LookBackRealtimeData") is not None

    def test_production_import_skips_collections(self, ctx):
        ctx.operator.retrieve_realtime_data()
        ctx.operator.create_historical_and_aggregated_data(1646208400000)
        handler = ImportExportHandler(ctx, now_ms=lambda: FIXTURE_NOW_MS)
        pairs = handler.export_data()

        handler.clear_data()
        handler.import_data_from_production_environment(pairs)
        assert ctx.store.get_aggregated_data() is None
        assert ctx.store.find_all("HistoricalData") == []
        assert ctx.cache.get("EndpointDependencies").get_data() is not None


class TestInitializer:
    def test_production_startup_read_only(self, pdas_traces):
        ctx = make_ctx(pdas_traces, read_only=True)
        ctx.cache.clear()
        Initializer(ctx).production_server_startup()
        # read-only: caches registered + loaded, no schedules
        assert ctx.scheduler.jobs == []
        assert ctx.cache.get("CombinedRealtimeData") is not None

    def test_production_startup_registers_schedules(self, pdas_traces):
        ctx = make_ctx(pdas_traces)
        ctx.cache.clear()
        init = Initializer(ctx)
        init.production_server_startup()
        try:
            assert set(ctx.scheduler.jobs) == {
                "aggregation",
                "realtime",
                "dispatch",
            }
            # registered, not started: api/app.py starts them after
            # first-time setup
            assert ctx.scheduler._started is False
        finally:
            ctx.scheduler.stop()

    def test_simulator_mode_registers_extra_caches(self, pdas_traces):
        ctx = make_ctx(pdas_traces, simulator_mode=True)
        assert ctx.cache.get("TaggedSimulationYAML") is not None
        assert ctx.cache.get("SimulatedHistoricalData") is not None

    def test_first_time_setup(self, pdas_traces):
        ctx = make_ctx(pdas_traces)

        class FakeZipkin:
            def get_trace_list(self, look_back, end_ts=None, limit=2500):
                return [pdas_traces]

        ctx.zipkin_client = FakeZipkin()
        Initializer(ctx).first_time_setup()
        assert ctx.store.find_all("HistoricalData")
        assert ctx.store.get_aggregated_data() is not None
        assert ctx.cache.get("EndpointDependencies").get_data() is not None

    def test_force_recreate_endpoint_dependencies(self, pdas_traces):
        ctx = make_ctx(pdas_traces)

        class FakeZipkin:
            def get_trace_list(self, look_back, end_ts=None, limit=2500):
                return [pdas_traces]

        ctx.zipkin_client = FakeZipkin()
        Initializer(ctx).force_recreate_endpoint_dependencies()
        assert ctx.store.find_all("EndpointDependencies")
        assert ctx.cache.get("LabeledEndpointDependencies").get_data() is not None
