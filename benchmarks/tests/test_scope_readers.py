"""The per-layer readers that sum the traced window's device ops by the
program's own scope phases (`harness/program_scopes.py`, PR 37): each on a
small hand-made trace (two devices, a module event of the epoch block on
each, ops inside and outside it, one op the table does not hold, one it
holds under no phase) against a hand-made table, and all of them silent on
a commit whose registry keeps no table."""
import json
from pathlib import Path
from types import SimpleNamespace

import pytest

from benchmarks.harness import program_scopes as scopes
from benchmarks.harness.manifest import Manifest
from benchmarks.harness.record import Record
from benchmarks.harness.spans import Recorder, Span
from benchmarks.trace.reduce import Event, Reduced, with_self_time

ROOT = Path(__file__).resolve().parent.parent.parent
MS = 1_000_000  # ns
UPDATES = 10

NEW = {
    "epoch_block.dense_ms_per_slot": ("ms", "epoch block"),
    "epoch_block.loss_optimizer_ms_per_slot": ("ms", "epoch block"),
    "kernel.gather_ms_per_slot": ("ms", "kernels"),
    "kernel.reduce_ms_per_slot": ("ms", "kernels"),
    "epoch_block.unscoped_share": ("%", "epoch block"),
    "shard.compute_skew": ("ratio", "collectives"),
}
CELLS = ["mv100k-sage.refresh", "mv100k-gat.refresh", "mv100k-stlgt.refresh", "mv400k-sage.refresh"]

#: what `Program.scope_tables()` hands out, a signature: instruction -> (scope path, phase, backward)
TABLE = {
    "fusion.1": ("graphsage/layer2/dense", "dense", False),
    "fusion.2": ("graphsage/layer2/dense/gather", "gather", False),
    "fusion.3": ("graphsage/layer2/dense/gather", "gather", True),
    "planned_neighbor_sum.4": ("graphsage/layer2/dense/reduce", "reduce", False),
    "async-collective-done.5": ("graphsage/layer2/dense/collective", "collective", False),
    "fusion.6": ("loss", "loss", False),
    "fusion.7": ("optimizer", "optimizer", False),
    "fusion.8": ("group", "group", False),
    "fusion.9": ("elsewhere", None, False),  # traced by the program under no phase of the taxonomy
    "while.1": ("", None, False),  # never in a real table (control flow): its self time is residue either way
}
#: the same block compiled for other shapes (a check's, a warm-up's): the same names but for a numbering that
#: shifted by one from `fusion.3` on, so nearly every op of the trace is in it, and under the wrong phase
OTHER_SIGNATURE = {
    "fusion.1": ("graphsage/layer2/dense", "dense", False),
    "fusion.2": ("graphsage/layer2/dense/gather", "gather", False),
    "fusion.3": ("graphsage/layer2/dense", "dense", False),
    "fusion.4": ("graphsage/layer2/dense/gather", "gather", True),
    "planned_neighbor_sum.4": ("graphsage/layer2/dense/reduce", "reduce", False),
    "async-collective-done.5": ("graphsage/layer2/dense/collective", "collective", False),
    "fusion.7": ("loss", "loss", False),
    "fusion.8": ("optimizer", "optimizer", False),
    "fusion.9": ("group", "group", False),
    "fusion.10": ("elsewhere", None, False),
}

#: (instruction, start ms, duration ms) of one device's block, which runs from 100 to 200 ms; `skew` stretches the
#: device's compute (and shortens its wait in the collective), as a fuller shard's does
def _block(device, skew=0.0):
    ops = [
        ("%while.1 (s32[], f32[8])", 100, 100),  # the loop: everything below is nested in it, 2 ms are its own
        ("%fusion.1 f32[131072,64]", 101, 10 + skew),
        ("%fusion.2 f32[1048576,64]", 112 + skew, 20),
        ("%fusion.3 f32[1048576,64]", 133 + skew, 5),
        ("%planned_neighbor_sum.4 f32[131072,64]", 139 + skew, 30),
        ("%async-collective-done.5 f32[4,32768,64]", 170 + skew, 10 - skew),
        ("%fusion.6 f32[]", 181, 3),
        ("%fusion.7 f32[64,64]", 185, 4),
        ("%fusion.8 f32[131072,126]", 190, 2),
        ("%fusion.9 f32[8]", 193, 1),
        ("%copy.10 f32[131072,64]", 195, 3),  # XLA's own: in no table
    ]
    return [Event(name, int(start * MS), int(dur * MS), device, "") for name, start, dur in ops]


def _record(table=TABLE, devices=2, skew=4.0):
    rec = Recorder()
    rec.spans += [Span("setup.warm_call", 0.0, 0.05), Span("refresh.call", 0.09, 0.21)]
    rec.counters["window.slot_updates"] = UPDATES
    ops, modules = [], []
    for d in range(devices):
        # `refresh.init`'s small programs have a %fusion.1 of their own: outside the block's module, never counted
        ops.append(Event("%fusion.1 f32[18,64]", 95 * MS, 2 * MS, d, ""))
        modules.append(Event("jit__normal(123)", 95 * MS, 2 * MS, d, ""))
        ops += _block(d, skew if d == 1 else 0.0)
        modules.append(Event("jit_sage_epoch_block(4567)", 100 * MS, 100 * MS, d, ""))
    trace = Reduced(window=(90 * MS, 210 * MS), ops=with_self_time(ops), modules=modules, spans=[], devices=devices)
    record = Record(correct=True, attempted=1, failed=0, end_to_end={}, recorder=rec,
                    manifest=Manifest(ROOT / "BENCHMARK.json"), config={}, traffic={}, devices=None, trace=trace)
    return record


@pytest.fixture
def block(monkeypatch):
    """The registry holds one epoch block whose run ended in the window and whose one signature's table is TABLE."""
    from kmamiz_tpu.core import programs

    state = SimpleNamespace(asked=0, tables=[TABLE])

    def scope_tables():
        state.asked += 1
        return state.tables

    state.programs = {
        "graph.merge": SimpleNamespace(recent_runs=lambda: []),
        "models.sage_epoch_block[check|0.01|10.0]": SimpleNamespace(  # the check's: ran in set-up alone
            recent_runs=lambda: [(0.04, 30.0, 3)], scope_tables=lambda: [{"fusion.1": ("x", "loss", False)}]),
        "models.sage_epoch_block[m|0.01|10.0]": SimpleNamespace(
            recent_runs=lambda: [(0.05, 110.0, UPDATES), (0.2, 100.0, UPDATES)], scope_tables=scope_tables),
    }
    monkeypatch.setattr(programs, "all_programs", lambda: state.programs)
    return state


def _read(name, record):
    return record.manifest.load_module(f"layer_metrics/{name}.py").read(record)


# a device: dense 10 (+4 on the fuller), gather 25, reduce 30, collective 10 (-4), loss 3, optimizer 4, group 2;
# unscoped: the loop's own 2 - ... = 100 - 98 nested + fusion.9's 1 + copy.10's 3
NESTED = {0: 10 + 20 + 5 + 30 + 10 + 3 + 4 + 2 + 1 + 3, 1: 10 + 4 + 20 + 5 + 30 + 6 + 3 + 4 + 2 + 1 + 3}
UNSCOPED = {d: (100 - NESTED[d]) + 1 + 3 for d in (0, 1)}
BUSY = 102.0  # a device: the block's 100 ms and the 2 ms of the small program before it


@pytest.mark.parametrize("name,want", [
    ("epoch_block.dense_ms_per_slot", ((10 + 2) + (14 + 2)) / 2 / UPDATES),
    ("epoch_block.loss_optimizer_ms_per_slot", 7 / UPDATES),
    ("kernel.gather_ms_per_slot", 25 / UPDATES),
    ("kernel.reduce_ms_per_slot", 30 / UPDATES),  # no collective in it, whatever XLA names one
    ("epoch_block.unscoped_share", 100 * (UNSCOPED[0] + UNSCOPED[1]) / 2 / BUSY),
    # compute outside `collective`: 90 ms on the one, 94 on the fuller; the mean 92
    ("shard.compute_skew", 94 / 92),
])
def test_reader_sums_the_blocks_ops_by_the_programs_phases(block, name, want):
    assert _read(name, _record()) == pytest.approx(want, rel=1e-9)


def test_the_phases_and_the_residue_add_up_to_the_blocks_busy_time(block):
    record = _record()
    found = scopes.by_device(record)
    assert set(found) == {0, 1}
    for device, phases in found.items():
        assert set(phases) == set(scopes.PHASES) | {scopes.UNSCOPED}
        assert sum(phases.values()) == pytest.approx(100 * MS)  # the block's module event, to the nanosecond
    seven = scopes.ms_per_slot(record, scopes.PHASES)
    residue = _read("epoch_block.unscoped_share", record) / 100 * record.trace.busy_ns / 1e6 / UPDATES
    block_ms = 100 / UPDATES
    assert seven + residue == pytest.approx(block_ms)
    # and within 2% of `epoch_block.device_ms_per_slot`, which also holds the small programs before the block
    assert seven + residue == pytest.approx(_read("epoch_block.device_ms_per_slot", record), rel=0.02)
    assert block.asked == 1  # six readers, one table: made once a record


@pytest.mark.parametrize("name", sorted(NEW))
def test_reader_is_silent_without_a_table_a_trace_or_a_block(block, name):
    # a commit from before the scopes: its programs have no `scope_tables` (the parent under these files)
    block.programs = {"models.sage_epoch_block[m|0.01|10.0]": SimpleNamespace(recent_runs=lambda: [(0.2, 100.0, UPDATES)])}
    assert _read(name, _record()) is None
    # a program that has compiled nothing hands out no table
    block.programs = {"models.sage_epoch_block[m|0.01|10.0]": SimpleNamespace(
        recent_runs=lambda: [(0.2, 100.0, UPDATES)], scope_tables=lambda: [])}
    assert _read(name, _record()) is None
    # no run of a block ended inside the window
    block.programs = {"models.sage_epoch_block[m|0.01|10.0]": SimpleNamespace(
        recent_runs=lambda: [(0.05, 100.0, UPDATES)], scope_tables=lambda: [TABLE])}
    assert _read(name, _record()) is None
    # --trace 0
    block.programs = {"models.sage_epoch_block[m|0.01|10.0]": SimpleNamespace(
        recent_runs=lambda: [(0.2, 100.0, UPDATES)], scope_tables=lambda: [TABLE])}
    untraced = _record()
    untraced.trace = None
    assert _read(name, untraced) is None


@pytest.mark.parametrize("tables", [[OTHER_SIGNATURE, TABLE], [TABLE, OTHER_SIGNATURE]], ids=["newest", "older"])
def test_the_table_is_the_one_of_the_signature_that_ran(block, tables):
    """A block compiles anew for other shapes, and the other signature's table holds nearly every traced name under
    a numbering of its own: the readers take the table whose names are the trace's both ways, wherever it stands."""
    block.tables = tables
    record = _record()
    assert _read("kernel.gather_ms_per_slot", record) == pytest.approx(25 / UPDATES)
    assert _read("epoch_block.loss_optimizer_ms_per_slot", record) == pytest.approx(7 / UPDATES)
    # what the other signature's table alone would have said, had nothing looked both ways: 20 of the 25
    wrong = {d: sum(ns for dev, name, ns in scopes.block_ops(record) if dev == d and
                    OTHER_SIGNATURE.get(name, ("", None))[1] == "gather") for d in (0, 1)}
    assert wrong == {0: 20 * MS, 1: 20 * MS}


def test_a_table_of_another_program_gives_no_number(block):
    """Names of which the trace holds none are no table of the block that ran: no number, not a residue of 100%."""
    block.tables = [{f"fusion.{i}": ("head/dense", "dense", False) for i in range(100, 140)}]
    for name in NEW:
        assert _read(name, _record()) is None
    block.tables.append(TABLE)  # beside the right one it is passed over
    assert _read("kernel.reduce_ms_per_slot", _record()) == pytest.approx(30 / UPDATES)


def test_a_row_that_no_call_ran_is_no_fault(block):
    block.tables = [{**TABLE, "fusion.100": ("head/dense", "dense", False)}]  # a branch not taken
    assert _read("kernel.reduce_ms_per_slot", _record()) == pytest.approx(30 / UPDATES)


def test_one_device_has_no_skew(block):
    record = _record(devices=1)
    assert _read("shard.compute_skew", record) is None
    assert _read("kernel.gather_ms_per_slot", record) == pytest.approx(25 / UPDATES)


#: PR 35's five, which `test_node_sharded_cell.py` pins as the LAST five: this PR's six come after them (the
#: driver reads an entry put in the middle as a change to what was there), so that assert fails from here on and
#: cuts off the guards after it. They are held here: what they pinned, and that nothing before the six moved.
BEFORE = ["collective.ms_per_slot", "collective.share", "collective.ici_roofline", "shard.plan_imbalance",
          "setup.shard_upload_s"]


def test_the_six_entries_follow_what_was_there_and_nothing_else_changed():
    doc = json.loads((ROOT / "BENCHMARK.json").read_text())
    names = [m["name"] for m in doc["per_layer"]]
    first = names.index("epoch_block.dense_ms_per_slot")
    assert len(names) == len(set(names)) and names[first:first + 6] == list(NEW)
    assert first == 25 and names[first - 5:first] == BEFORE  # the parent's twenty-five, in their places
    for m in doc["per_layer"][first:first + 6]:
        unit, layer = NEW[m["name"]]
        assert set(m) == {"name", "unit", "better", "source", "layer", "moves", "workloads"}
        assert (m["unit"], m["layer"], m["better"], m["source"]) == (unit, layer, "lower", "device_trace")
        assert m["moves"] == "refresh_slot_updates_per_s"
        assert m["workloads"] == (["mv400k-sage.refresh"] if m["name"] == "shard.compute_skew" else CELLS)
        assert (ROOT / "benchmarks" / "layer_metrics" / f"{m['name']}.py").is_file()
    assert {m["layer"] for m in doc["per_layer"][first:first + 6]} <= {m["layer"] for m in doc["per_layer"][:first]}


def test_the_yardstick_is_the_parents():
    """What `test_node_sharded_cell.py::test_the_manifest_gained_entries_and_lost_none` pinned after the assert
    that six more entries now fail."""
    doc = json.loads((ROOT / "BENCHMARK.json").read_text())
    assert [c["name"] for c in doc["configs"]][-1] == "mv400k-sage" and len(doc["configs"]) == 4
    assert [w["name"] for w in doc["workloads"]] == CELLS
    (plan_s,) = [m for m in doc["per_layer"] if m["name"] == "setup.plan_s"]
    assert plan_s["workloads"] == CELLS[1:]
    assert doc["run_seconds"] == 51 and [m["bound"] for m in doc["end_to_end"]] == [0.01, 0.1]
