"""The per-layer readers that take the program's own spans and run times
(`harness/program_spans.py`): each on a hand-made record and hand-made
traces, and all six in the line of the tiny cell traced on the CPU (they are
host spans and a registry counter, so a CPU records them)."""
import copy
import json
from pathlib import Path
from types import SimpleNamespace

import pytest

from benchmarks import run
from benchmarks.harness import device
from benchmarks.harness.manifest import Manifest
from benchmarks.harness.record import Record
from benchmarks.harness.spans import Recorder, Span

ROOT = Path(__file__).resolve().parent.parent.parent
BENCH = ROOT / "benchmarks"
MS = 1_000_000  # ns

NEW = {
    "trainer.span_host_ms_per_call": "ms",
    "trainer.pos_weight_ms_per_call": "ms",
    "trainer.init_ms_per_call": "ms",
    "trainer.uncovered_ms_per_call": "ms",
    "epoch_block.run_ms_per_slot": "ms",
    "setup.stack_host_fill_s": "s",
}


def _record():
    """Set-up stacked from 10 s to 15 s; the window held two calls, from
    100 s to 130 s and from 130 s to 160 s."""
    rec = Recorder()
    rec.spans += [
        Span("setup.stack_upload", 10.0, 15.0),
        Span("setup.warm_call", 60.0, 90.0),
        Span("refresh.call", 100.0, 130.0),
        Span("refresh.call", 130.0, 160.0),
    ]
    manifest = Manifest(ROOT / "BENCHMARK.json")
    return Record(correct=True, attempted=2, failed=0, end_to_end={}, recorder=rec,
                  manifest=manifest, config={}, traffic={}, devices=None)


def _train_trace(t0_s, init_ms, pos_ms, fetch_ms, uncovered_ms):
    """A `refresh.train` trace as the tracer keeps it: (name, start_ns,
    dur_ns, parent) relative to `t0_ns`."""
    at = uncovered_ms * MS
    spans = [None]
    for name, ms in (("refresh.init", init_ms), ("refresh.pos_weight", pos_ms),
                     ("refresh.stack", 0.05), ("refresh.epoch_block", 0.5),
                     ("refresh.loss_fetch", fetch_ms)):
        spans.append((name, at, int(ms * MS), 0))
        at += int(ms * MS)
    spans[0] = ("refresh.train", 0, at, -1)
    return SimpleNamespace(t0_ns=int(t0_s * 1e9), spans=spans)


def _stack_trace(t0_s, fill_ms, put_ms):
    return SimpleNamespace(t0_ns=int(t0_s * 1e9), spans=[
        ("refresh.stack", 0, int((fill_ms + put_ms) * MS), -1),
        ("refresh.stack.host_fill", 0, int(fill_ms * MS), 0),
        ("refresh.stack.device_put", int(fill_ms * MS), int(put_ms * MS), 0),
    ])


TRACES = [
    _stack_trace(10.001, fill_ms=1800.0, put_ms=2700.0),    # set-up's build
    _train_trace(60.0, 900.0, 50.0, 27000.0, 1.0),          # the warm call: outside the window
    _train_trace(100.001, 30.0, 46.0, 27800.0, 1.0),
    _train_trace(130.001, 32.0, 48.0, 27810.0, 2.0),
]
RUNS = {
    "models.sage_epoch_block[m|0.01|10.0]": SimpleNamespace(
        recent_runs=lambda: [(89.9, 27900.0, 432), (129.9, 27820.0, 432), (159.9, 27830.0, 432)]
    ),
    "graph.merge": SimpleNamespace(),  # a program that reports no run
}


@pytest.fixture
def program(monkeypatch):
    """The program's ring and registry, holding what the test puts there."""
    from kmamiz_tpu.core import programs
    from kmamiz_tpu.telemetry import tracing

    state = SimpleNamespace(traces=list(TRACES), runs=dict(RUNS))
    monkeypatch.setattr(tracing.TRACER, "traces", lambda: state.traces)
    monkeypatch.setattr(programs, "all_programs", lambda: state.runs)
    return state


def _read(name, record):
    return record.manifest.load_module(f"layer_metrics/{name}.py").read(record)


@pytest.mark.parametrize("name,want", [
    # refresh.train minus refresh.loss_fetch: 30+46+0.05+0.5+1 and 32+48+0.05+0.5+2
    ("trainer.span_host_ms_per_call", (77.55 + 82.55) / 2),
    ("trainer.pos_weight_ms_per_call", 47.0),
    ("trainer.init_ms_per_call", 31.0),
    ("trainer.uncovered_ms_per_call", 1.5),
    # the two runs that ended inside the window, not the warm call's
    ("epoch_block.run_ms_per_slot", (27820.0 + 27830.0) / 864),
    ("setup.stack_host_fill_s", 1.8),
])
def test_reader_reads_the_programs_spans_of_its_window(program, name, want):
    assert _read(name, _record()) == pytest.approx(want, rel=1e-9)


@pytest.mark.parametrize("name", sorted(NEW))
def test_reader_finds_nothing_and_returns_none(program, name):
    # telemetry off, or a commit from before the spans: the ring holds ticks
    # only, the registry's programs report no run
    program.traces = [SimpleNamespace(t0_ns=int(100.5e9), spans=[("dp-tick", 0, 5 * MS, -1)])]
    program.runs = {"graph.merge": SimpleNamespace()}
    assert _read(name, _record()) is None
    # spans there are, but none started inside a window or set-up span
    program.traces = [_train_trace(60.0, 900.0, 50.0, 27000.0, 1.0), _stack_trace(200.0, 1.0, 1.0)]
    program.runs = {"p": SimpleNamespace(recent_runs=lambda: [(89.9, 27900.0, 432)])}
    assert _read(name, _record()) is None


def test_the_six_entries_are_appended_and_nothing_else_changed():
    doc = json.loads((ROOT / "BENCHMARK.json").read_text())
    names = [m["name"] for m in doc["per_layer"]]
    assert len(names) == len(set(names))
    # appended after PR 24's eight, in this order; later PRs append after them
    assert names[8:14] == list(NEW)
    six = [m for m in doc["per_layer"] if m["name"] in NEW]
    for m in six:
        assert set(m) == {"name", "unit", "better", "source", "layer", "moves"}  # no `workloads`
        assert m["unit"] == NEW[m["name"]] and m["better"] == "lower"
        assert m["source"] == ("program_counter" if m["name"].startswith("epoch_block.") else "program_span")
        assert m["moves"] == ("setup_s" if m["name"].startswith("setup.") else "refresh_slot_updates_per_s")
    layers = {m["layer"] for m in doc["per_layer"][:8]}
    assert {m["layer"] for m in six} <= layers  # the accepted names, letter for letter


def test_tiny_cell_traced_on_the_cpu_reports_the_six_metrics(tmp_path, capsys, monkeypatch, tiny_config):
    import jax

    monkeypatch.setattr(device, "require", lambda chips: jax.devices()[:chips])
    cfg = copy.deepcopy(tiny_config)
    (tmp_path / "tiny.json").write_text(json.dumps(cfg))
    doc = json.loads((ROOT / "BENCHMARK.json").read_text())
    doc["paths"] = [str(BENCH)]
    doc["configs"] = [{"name": "tiny", "source": "test", "file": str(tmp_path / "tiny.json"), "reduced": [], "why": "t"}]
    doc["workloads"] = [{"name": "tiny.refresh", "config": "tiny", "traffic": "refresh", "chips": 1, "why": "t"}]
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(doc))
    code = run.main(["--manifest", str(tmp_path / "BENCHMARK.json"), "--workload", "tiny.refresh",
                     "--seed", str(2**31 + 4242), "--seconds", "0.2", "--trace", "1"])
    assert code == 0
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert line["correct"] is True
    for name, unit in NEW.items():
        assert name in line["metrics"], name
        assert line["metrics"][name]["unit"] == unit and line["metrics"][name]["value"] >= 0
    m = {k: v["value"] for k, v in line["metrics"].items()}
    # the parts of a call lie inside the whole, and the build's fill inside the build
    assert m["trainer.pos_weight_ms_per_call"] + m["trainer.init_ms_per_call"] <= m["trainer.span_host_ms_per_call"]
    assert m["trainer.uncovered_ms_per_call"] <= m["trainer.span_host_ms_per_call"]
    assert m["setup.stack_host_fill_s"] <= m["setup.stack_upload_s"]
    # the idle gaps are named by the program's innermost span, not by the
    # benchmark's span around the whole call
    names = {g[0].split(":")[0] for g in line["breakdown"]["idle_gaps"]}
    assert names and names <= {
        "refresh.init", "refresh.pos_weight", "refresh.stack", "refresh.epoch_block",
        "refresh.loss_fetch", "refresh.train", "refresh.call", "outside spans",
    }
