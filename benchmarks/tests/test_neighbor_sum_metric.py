"""`kernel.neighbor_sum_ms_per_slot` on the trace recorded on the v5e: with
nothing planned it is the whole of the scatters, and a Mosaic kernel's
`custom-call` counts with them."""
from pathlib import Path
from types import SimpleNamespace

import pytest

from benchmarks.harness.manifest import Manifest
from benchmarks.trace import reduce as R

ROOT = Path(__file__).resolve().parent.parent.parent
RECORDED = ROOT / "benchmarks" / "trace" / "recorded" / "sage_8slot_v5e.json"

KERNEL = ('%custom-call.7 = f32[131072,64]{1,0:T(8,128)} custom-call(s32[3072]{0:T(1024)} %p.1, '
          's32[1,1048576]{1,0:T(1,128)} %p.4, f32[1048576,64]{1,0:T(8,128)} %fusion.9), '
          'custom_call_target="tpu_custom_call", operand_layout_constraints={}')


def _record(trace, updates):
    return SimpleNamespace(
        trace=trace, recorder=SimpleNamespace(counters={"window.slot_updates": updates})
    )


def _reader():
    return Manifest(ROOT / "BENCHMARK.json").load_module(
        "layer_metrics/kernel.neighbor_sum_ms_per_slot.py"
    )


def test_it_is_in_the_manifest_for_every_cell_that_reports_the_rate():
    manifest = Manifest(ROOT / "BENCHMARK.json")
    (entry,) = [m for m in manifest.doc["per_layer"] if m["name"] == "kernel.neighbor_sum_ms_per_slot"]
    assert entry["layer"] == "kernels" and entry["moves"] == "refresh_slot_updates_per_s"
    assert entry["better"] == "lower" and "workloads" not in entry


def test_unplanned_it_is_the_scatters_of_the_recorded_trace():
    recorded = R.reduce_events(R.load_json(str(RECORDED)), "bench.call")
    got = _reader().read(_record(recorded, 8))
    scatter_ns = sum(e.self_ns for e in recorded.ops if e.category == "scatter")
    assert got == pytest.approx(scatter_ns / 1e6 / 8)
    # 76.5% of 515 ms over 8 slots: 49 ms a slot update
    assert got == pytest.approx(49.25, abs=0.05)


def test_a_mosaic_kernel_counts_and_nothing_to_read_is_none():
    assert R.op_kind(KERNEL) == "custom-call"
    raw = {
        "ops": [[R.short_name(KERNEL), 100, 3_000_000, 0, R.op_kind(KERNEL)],
                ["%fusion.1 f32[8]", 3_000_200, 1_000_000, 0, "gather"]],
        "modules": [], "spans": [["refresh.call", 0, 5_000_000, 0, ""]],
    }
    reduced = R.reduce_events(raw, "refresh.call")
    reader = _reader()
    assert reader.read(_record(reduced, 2)) == pytest.approx(1.5)
    assert reader.read(_record(None, 2)) is None
    assert reader.read(_record(reduced, 0)) is None
