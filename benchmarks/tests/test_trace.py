"""The reduction from a device trace to numbers, checked against a trace
recorded on the v5e (`trace/recorded/`), and the arithmetic of the bytes a
slot update must move, at the cell's shapes."""
import json
from pathlib import Path

import pytest

from benchmarks.harness.manifest import Manifest
from benchmarks.trace import peaks, reduce as R

ROOT = Path(__file__).resolve().parent.parent.parent
RECORDED = ROOT / "benchmarks" / "trace" / "recorded" / "sage_8slot_v5e.json"

# device-op names as the v5e's profiler gives them (PR 24's probe)
GATHER = ("%fusion.253 = f32[524288,18]{1,0:T(8,128)} fusion(f32[131072,18]{1,0:T(8,128)S(1)} "
          "%bitcast.164, s32[524288]{0:T(1024)S(1)} %broadcast_clamp_fusion.17), kind=kCustom, "
          "calls=%fused_computation.clone.clone")
SCATTER = ("%fusion.256 = f32[131073,18]{1,0:T(8,128)} fusion(s32[524288]{0:T(1024)S(1)} "
           "%get-tuple-element.999, f32[524288,18]{1,0:T(8,128)} %get-tuple-element.1000, "
           "s32[524288]{0:T(1024)S(1)} %broadcast_clamp_fusion.21, f32[]{:T(128)} "
           "%constant.155..sunk), kind=kCustom, calls=%fused_computation.30.clone.clone")
SCATTER_1D = ("%fusion.257 = f32[131073]{0:T(1024)S(1)} fusion(s32[524288]{0:T(1024)S(1)} "
              "%custom-call.68, f32[524288]{0:T(1024)S(1)} %copy-done.4, f32[]{:T(128)} "
              "%constant.155..sunk), kind=kCustom, calls=%fused_computation.138.clone.clone")
LOOP = ("%broadcast_multiply_fusion.6 = (f32[524288,18]{1,0:T(8,128)}, f32[524288,18]{1,0:T(8,128)}) "
        "fusion(f32[524288,18]{1,0:T(8,128)} %fusion.253, f32[524288]{0:T(1024)S(1)} %copy-done.4, "
        "f32[524288,18]{1,0:T(8,128)} %fusion.254), kind=kLoop, calls=%fused_computation.37.clone.clone")
SORT = ("%sort.11 = (s32[524288]{0:T(1024)S(1)}, s32[524288]{0:T(1024)S(1)}) sort(s32[524288]"
        "{0:T(1024)S(1)} %copy-done.11, s32[524288]{0:T(1024)S(1)} %iota.11), dimensions={0}, "
        "to_apply=%compare")


def test_op_kind_reads_the_hlo_text():
    assert R.op_kind(GATHER) == "gather"
    assert R.op_kind(SCATTER) == "scatter"
    assert R.op_kind(SCATTER_1D) == "scatter"
    assert R.op_kind(LOOP) == "fusion"
    assert R.op_kind(SORT) == "sort"
    assert R.op_kind("%while.84 = (s32[]{:T(128)}, f32[18,64]{1,0:T(8,128)}) while((s32[]{:T(128)}") == "while"
    assert R.op_kind("ThunkExecutor::Execute") == ""
    assert R.short_name(SCATTER) == "%fusion.256 f32[131073,18]"


def test_interval_arithmetic():
    assert R.union_ns([(0, 10), (5, 20), (30, 40), (32, 35)]) == 30
    assert R.gaps([(5, 10), (8, 12), (20, 30)], (0, 40)) == [(0, 5), (12, 20), (30, 40)]
    assert R.gaps([], (3, 9)) == [(3, 9)]
    parent, child, grandchild, later = (
        R.Event("while", 0, 100), R.Event("a", 10, 30), R.Event("b", 15, 5), R.Event("c", 50, 20))
    R.with_self_time([later, grandchild, parent, child])
    assert (parent.self_ns, child.self_ns, grandchild.self_ns, later.self_ns) == (50, 25, 5, 20)
    clipped = R.clip([R.Event("x", 0, 10), R.Event("y", 8, 10), R.Event("z", 30, 5)], (5, 12))
    assert [(e.name, e.start_ns, e.dur_ns) for e in clipped] == [("x", 5, 5), ("y", 8, 4)]


@pytest.fixture(scope="module")
def recorded():
    return R.reduce_events(R.load_json(str(RECORDED)), "bench.call")


def test_recorded_trace_busy_time_and_programs(recorded):
    # one call over 8 slots that also compiled: 11.5 s of window, 0.515 s busy
    assert recorded.devices == 1
    assert recorded.window_s == pytest.approx(11.527918727, abs=1e-9)
    assert recorded.busy_ns == 515_276_910
    # nested ops do not count twice: busy is the sum of self times
    assert sum(e.self_ns for e in recorded.ops) == 515_276_910
    loop = max(recorded.ops, key=lambda e: e.dur_ns)
    assert loop.category == "while" and loop.dur_ns > 0.99 * 515_276_910 and loop.self_ns == 103_012
    programs = recorded.program_ns()
    assert programs["jit_run"] == 515_249_123  # the epoch block, by its module's name
    # a module's event spans its ops and their launch: within a thousandth
    assert sum(programs.values()) == pytest.approx(recorded.busy_ns, rel=1e-3)
    assert max(programs, key=programs.get) == "jit_run"


def test_recorded_trace_gather_and_scatter_shares(recorded):
    scatter = recorded.share_of_busy(lambda e: e.category == "scatter")
    gather = recorded.share_of_busy(lambda e: e.category == "gather")
    assert scatter == pytest.approx(0.76467, abs=1e-5)
    assert gather == pytest.approx(0.08791, abs=1e-5)
    # a GraphSAGE update: 6 row scatters + 2 degree scatters, 4 gathers forward + 2 backward
    assert sum(e.category == "scatter" for e in recorded.ops) == 8 * 8
    assert sum(e.category == "gather" for e in recorded.ops) == 8 * 6


def test_recorded_trace_idle_gaps_and_breakdown(recorded):
    idle = recorded.idle_gaps()
    assert sum(b - a for a, b in idle) == recorded.window[1] - recorded.window[0] - recorded.busy_ns
    assert max(b - a for a, b in idle) == 10_972_226_158  # the compile, inside the call
    assert recorded.covering_span(recorded.window[0] + 1) == "bench.call"
    assert recorded.covering_span(recorded.window[1] + 1) == "outside spans"
    out = recorded.breakdown()
    assert set(out) == {"device_ops", "idle_gaps"}
    assert len(out["device_ops"]) == 10 and len(out["idle_gaps"]) == 10
    assert out["device_ops"][0] == ["scatter:%fusion.255 f32[131073,18]", pytest.approx(0.055549281)]
    assert out["idle_gaps"][0] == ["bench.call: between device ops", pytest.approx(10.972226158)]
    assert json.loads(json.dumps(out)) == out


def test_no_window_span_is_an_error():
    raw = R.load_json(str(RECORDED))
    with pytest.raises(ValueError):
        R.reduce_events(raw, "refresh.call")


def test_bytes_of_a_slot_update_at_the_cells_shapes():
    manifest = Manifest(ROOT / "BENCHMARK.json")
    cfg = manifest.config("mv100k-sage")
    sage = manifest.load_module("trace/work/graphsage.py")
    n, e, f, h = 100_000, 500_000, 18, 64
    assert sage.spmm_bytes(n, e, h) == e * 8 + e * h * 4 + n * h * 4 == 157_600_000
    terms = sage.terms(cfg)
    assert terms["neighbour_sums"] == 2 * 47_200_000 + 4 * 157_600_000
    assert terms["dense_forward"] == 3 * n * f * 4 + 5 * n * h * 4
    assert terms["dense_backward"] == 2 * n * f * 4 + 9 * n * h * 4
    assert sage.slot_update_bytes(cfg) == sum(terms.values()) == 1_122_002_120
    # at the v5e's 819 GB/s that is 1.37 ms; the matrix unit would need 0.03 ms
    v5e = peaks.of("TPU v5 lite")
    assert sage.slot_update_bytes(cfg) / v5e["hbm_bytes_per_s"] == pytest.approx(1.36997e-3, rel=1e-4)
    assert sage.slot_update_flops(cfg) / v5e["bf16_flops_per_s"] < 5e-5

    gat = manifest.load_module("trace/work/gat.py")
    cfg_gat = dict(cfg, family="gat")
    terms = gat.terms(cfg_gat)
    assert terms["attention_sums"] == 12 * 157_600_000
    assert terms["attention_scalars"] == 24 * (e * 8 + e * 8 + e * 4)
    assert gat.slot_update_bytes(cfg_gat) == sum(terms.values()) == 2_744_269_512
    assert gat.slot_update_bytes(cfg_gat) > 2 * sage.slot_update_bytes(cfg)


def test_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError):
        peaks.of("TPU v9 imaginary")
