"""Test configuration: run JAX on a virtual 8-device CPU mesh.

Multi-chip TPU hardware is unavailable in CI; sharding correctness is
validated against 8 virtual CPU devices (the driver separately dry-runs the
multi-chip path via __graft_entry__.dryrun_multichip).
"""
import json
import os
from pathlib import Path

# force CPU: tests validate sharding on 8 virtual CPU devices whatever the
# host holds (the chip is reached only through chip_smoke.py)
os.environ["JAX_PLATFORMS"] = "cpu"
xla_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in xla_flags:
    os.environ["XLA_FLAGS"] = (
        xla_flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import pytest


def pytest_configure(config):
    # tier-1 runs with -m 'not slow' (ROADMAP.md); long training-epoch
    # tests opt out of it with this marker
    config.addinivalue_line(
        "markers", "slow: long-running test excluded from the tier-1 sweep"
    )


@pytest.fixture(autouse=True)
def _reset_resilience_state():
    """The resilience layer keeps process-wide registries (circuit
    breakers, counters, the default quarantine binding). A breaker a
    test trips must not short-circuit the next test's upstream calls, so
    every test starts from a clean slate."""
    from kmamiz_tpu import control, cost, fleet, scenarios, telemetry, tenancy
    from kmamiz_tpu.models import stlgt
    from kmamiz_tpu.ops import sparse
    from kmamiz_tpu.resilience import breaker, metrics, quarantine
    from kmamiz_tpu.server import stream

    breaker.reset_for_tests()
    metrics.reset_for_tests()
    quarantine.reset_for_tests()
    telemetry.reset_for_tests()
    tenancy.reset_for_tests()
    scenarios.reset_for_tests()
    stlgt.reset_for_tests()
    control.reset_for_tests()
    cost.reset_for_tests()
    # graftstream module counters (micro-ticks, fences, high water)
    stream.reset_for_tests()
    # the sparse backend knob is cached after first read; a test that
    # monkeypatches KMAMIZ_SPARSE* must not leak its choice forward
    sparse.reset_for_tests()
    # graftfleet module counters (frames routed/queued, folds, migrations)
    fleet.reset_for_tests()
    # graftsoak completed-sweep registry
    from kmamiz_tpu import soak

    soak.reset_for_tests()
    # graftrace lock witness: uninstall the threading.Lock/RLock patch
    # and drop witnessed order edges so one armed test can't leak edges
    # (or the patch itself) into the next test's coverage check
    from kmamiz_tpu.analysis.concurrency import witness

    witness.reset_for_tests()
    yield


@pytest.fixture
def plan_reducer(request, monkeypatch):
    """Which reducer the planned sums and attentions traced in this test
    run, for `parametrize("plan_reducer", ..., indirect=True)`: "xla", what
    `walk.planned_impl` answers on a CPU, or "pallas_interpret", the Pallas
    kernels interpreted. No environment value selects the second, so it is
    patched in; the epoch block's cached trace goes, so the choice is seen."""
    from kmamiz_tpu.models import stacked
    from kmamiz_tpu.ops import walk

    if request.param != "xla":
        monkeypatch.setattr(walk, "planned_impl", lambda: request.param)
    assert walk.planned_impl() == request.param
    stacked.epoch_runner.cache_clear()
    yield request.param
    stacked.epoch_runner.cache_clear()  # no patched trace outlives the test


FIXTURES = Path(__file__).parent / "fixtures"


def load_fixture(name: str):
    return json.loads((FIXTURES / f"{name}.json").read_text())


@pytest.fixture(scope="session")
def pdas_traces():
    return load_fixture("pdas_traces")


@pytest.fixture(scope="session")
def bookinfo_traces():
    return load_fixture("bookinfo_traces")


@pytest.fixture(scope="session")
def pdas_realtime_data():
    return load_fixture("pdas_realtime_data")


@pytest.fixture(scope="session")
def pdas_endpoint_dependencies():
    return load_fixture("pdas_endpoint_dependencies")


@pytest.fixture(scope="session")
def bookinfo_endpoint_dependencies():
    return load_fixture("bookinfo_endpoint_dependencies")


@pytest.fixture(scope="session")
def pdas_envoy_log_lines():
    return load_fixture("pdas_envoy_log_lines")


def prefixed_trace_source(pdas_traces, prefix):
    """Trace source emitting the pdas fixture with fresh ids per tick
    (dedup keeps every tick's spans) — shared scaffold of the forecast /
    history tests across files."""
    seen = {"n": 0}

    def source(_lb, _t, _lim):
        seen["n"] += 1
        ng = []
        for s in pdas_traces:
            c = dict(s)
            c["traceId"] = f"{prefix}{seen['n']}-{s.get('traceId')}"
            c["id"] = f"{prefix}{seen['n']}-{s.get('id')}"
            if c.get("parentId"):
                c["parentId"] = f"{prefix}{seen['n']}-{c['parentId']}"
            ng.append(c)
        return [ng]

    return source
