"""`mv400k-sage.refresh`, the node-sharded cell: its files resolve by name, its
work file counts a chip's share and what only a sharded layer moves, its
readers read the device trace and the program's spans (and nothing where
there is nothing to read), and the check's control, the reference with a
bfloat16 wire in the program's place, is not `correct`."""
import collections
import json
import types
from pathlib import Path
from types import SimpleNamespace

import numpy as np
import pytest

from benchmarks.gen import mesh_history
from benchmarks.harness.manifest import Manifest
from benchmarks.reference import check, graphsage, graphsage_sharded, train as ref_train
from benchmarks.trace import reduce as R
from benchmarks.trace.work import graphsage as one_chip_work, graphsage_sharded as work

ROOT = Path(__file__).resolve().parent.parent.parent
RECORDED = ROOT / "benchmarks" / "trace" / "recorded" / "sage_8slot_v5e.json"
CELL = "mv400k-sage.refresh"
NEW_METRICS = (
    "collective.ms_per_slot", "collective.share", "collective.ici_roofline",
    "shard.plan_imbalance", "setup.shard_upload_s",
)
MIX = {"check_slots": 3, "forward_check_slots": 1}


@pytest.fixture(scope="module")
def manifest():
    return Manifest(ROOT / "BENCHMARK.json")


def test_every_file_of_the_cell_resolves_and_the_sizes_are_the_issues(manifest):
    cell = manifest.workload(CELL)
    cfg = manifest.config(cell["config"])
    mix = manifest.load_json(f"traffic/{cell['traffic']}.json")
    assert (cell["chips"], cell["traffic"], cfg["chips"], cfg["family"]) == (4, "refresh", 4, "graphsage_sharded")
    assert hasattr(manifest.load_module(f"drivers/{mix['driver']}.py"), "run")
    assert hasattr(manifest.load_module(f"gen/{cfg['generator']}.py"), "generate")
    assert hasattr(manifest.load_module(f"reference/{cfg['family']}.py"), "forward")
    assert manifest.load_module(f"trace/work/{cfg['family']}.py").slot_update_bytes(cfg) > 0
    reported = {m["name"] for m in manifest.metrics("per_layer", CELL)}
    assert set(NEW_METRICS) | {"setup.plan_s", "kernel.slot_update_hbm_roofline", "epoch_block.compiles"} <= reported
    for name in reported:
        assert hasattr(manifest.load_module(f"layer_metrics/{name}.py"), "read")
    for m in manifest.doc["per_layer"]:
        if m["name"] in NEW_METRICS:
            assert m["workloads"] == [CELL] and m["moves"] in ("refresh_slot_updates_per_s", "setup_s")
    # one four-chip cell of four: a quarter, rounded down, is one
    assert [w["chips"] for w in manifest.doc["workloads"]].count(4) == 1 <= len(manifest.doc["workloads"]) // 4
    # the cut is the siblings', and the bytes are the issue's
    sibling = manifest.config("mv100k-sage")
    assert set(cfg["reduced"]) == set(sibling["reduced"]) == set(manifest._entry("configs", "mv400k-sage")["reduced"])
    for key in ("num_features", "hidden", "layers", "node_embeddings", "retention_days", "slots", "batch_slots",
                "lr", "weight_decay", "storage_dtype", "epochs_per_refresh", "generator", "model_module"):
        assert cfg[key] == sibling[key], key
    assert {**cfg["assumed"], "endpoints_per_service": 0} == {**sibling["assumed"], "endpoints_per_service": 0}
    assert (cfg["endpoints"], cfg["node_bucket"], cfg["edges"], cfg["edge_bucket"]) == (400_000, 524_288, 2_000_000, 2_097_152)
    assert cfg["edges"] // cfg["endpoints"] == sibling["edges"] // sibling["endpoints"] == 5
    whole = cfg["slots"] * cfg["node_bucket"] * 81
    assert whole == 18_345_885_696 > 16 * 2**30 and whole // 4 == 4_586_471_424 > 4 * 2**30
    assert sibling["guarantees"] == cfg["guarantees"][:3] and "float32" in cfg["guarantees"][3] and "ICI" in cfg["guarantees"][3]


def test_the_work_files_terms_add_up(manifest):
    """A chip's HBM: a quarter of the one-chip terms (the optimizer whole:
    it is replicated) plus the tables it gathers; its ICI: three quarters of
    those tables and the other chips' gradients."""
    cfg = manifest.config("mv400k-sage")
    whole, mine = one_chip_work.terms(cfg), work.terms(cfg)
    assert set(mine) == set(whole) | {"gathered_tables"}
    for key in whole:
        assert mine[key] == (whole[key] if key == "optimizer" else whole[key] // 4), key
    n, f, h = 400_000, 18, 64
    tables = work.gathered_tables(cfg)
    assert tables == {"h1": n * h * 4, "h1_cotangent": n * h * 4, "features_of_the_slot": n * f * 4}
    assert mine["gathered_tables"] == sum(tables.values()) == 233_600_000
    assert work.slot_update_bytes(cfg) == sum(mine.values()) == 1_355_602_120
    params = 2 * f * h + 2 * h * h + 2 * h + 2 * (h + 1) + 2 * f
    assert work.slot_update_ici_bytes(cfg) == 3 * 233_600_000 // 4 + 2 * 3 * params * 4 // 4
    assert work.ici_bytes_per_s("TPU v5 lite") == 200e9
    with pytest.raises(KeyError, match="no published ICI rate"):
        work.ici_bytes_per_s("TPU v9")
    # with one chip it is the one-chip count and a table that goes nowhere
    alone = {**cfg, "chips": 1}
    assert work.slot_update_ici_bytes(alone) == 0
    assert work.slot_update_bytes(alone) == one_chip_work.slot_update_bytes(cfg) + 233_600_000


GATHER_DONE = ('%all-gather-done.2 = f32[524288,126]{1,0:T(8,128)} all-gather-done((f32[131072,126]{1,0:T(8,128)}, '
               'f32[524288,126]{1,0:T(8,128)}) %all-gather-start.2)')
FUSED_START = ('%async-collective-start = (f32[131072,64]{1,0:T(8,128)}, f32[524288,64]{1,0:T(8,128)}) '
               'fusion(f32[131072,64]{1,0:T(8,128)} %get-tuple-element.2173), kind=kCustom, calls=%fused_computation.149')
REDUCE = ('%all-reduce.2 = (f32[18,64]{1,0:T(8,128)S(1)}, f32[64]{0:T(128)S(1)}) all-reduce(f32[18,64]{1,0:T(8,128)S(1)} '
          '%fusion.5, f32[64]{0:T(128)S(1)} %fusion.6), channel_id=1, replica_groups={{0,1,2,3}}, to_apply=%region_3.0')
SUM = ('%custom-call.7 = f32[131072,64]{1,0:T(8,128)} custom-call(s32[3072]{0:T(1024)} %p.1), '
       'custom_call_target="tpu_custom_call"')


def _record(manifest, trace, updates=2, family="graphsage_sharded"):
    cfg = {**manifest.config("mv400k-sage"), "family": family}
    return SimpleNamespace(
        trace=trace, manifest=manifest, config=cfg,
        recorder=SimpleNamespace(counters={"window.slot_updates": updates}, named=lambda name: []),
        devices=[SimpleNamespace(device_kind="TPU v5 lite")],
    )


def _two_devices():
    """Two slot updates on two devices: each stands 1.0 ms in an all-gather's
    `-done`, 0.5 ms in a fused collective's start, 0.25 ms in an all-reduce, and
    works 4 ms in a planned sum."""
    ops = []
    for device in (0, 1):
        at = 1_000
        for text, dur in ((GATHER_DONE, 2_000_000), (FUSED_START, 1_000_000), (REDUCE, 500_000), (SUM, 8_000_000)):
            ops.append([R.short_name(text), at, dur, device, R.op_kind(text)])
            at += dur + 10
    raw = {"ops": ops, "modules": [], "spans": [["refresh.call", 0, 12_000_000, 0, ""]]}
    return R.reduce_events(raw, "refresh.call")


def test_the_collective_readers_on_a_trace_with_collectives(manifest):
    assert (R.op_kind(GATHER_DONE), R.op_kind(FUSED_START), R.op_kind(REDUCE)) == ("all-gather-done", "fusion", "all-reduce")
    record = _record(manifest, _two_devices())
    read = lambda name: manifest.load_module(f"layer_metrics/{name}.py").read(record)  # noqa: E731
    assert read("collective.ms_per_slot") == pytest.approx(1.75)  # a device, a slot update
    assert read("collective.share") == pytest.approx(100 * 3.5 / 11.5)
    least_ms = work.slot_update_ici_bytes(record.config) / 200e9 * 1e3
    assert read("collective.ici_roofline") == pytest.approx(100 * least_ms / 1.75)
    assert 0 < read("collective.ici_roofline") < 100
    # the chip's own share of the bytes through the reader that was there
    roofline = manifest.load_module("layer_metrics/kernel.slot_update_hbm_roofline.py").read(record)
    assert roofline == pytest.approx(100 * (1_355_602_120 / 819e9) / (11.5e-3 / 2))


@pytest.mark.parametrize("name", NEW_METRICS)
def test_a_new_reader_reads_nothing_where_there_is_nothing_and_never_raises(manifest, name):
    reader = manifest.load_module(f"layer_metrics/{name}.py")
    assert reader.read(_record(manifest, None)) is None  # no trace: `--trace 0`, or a CPU
    recorded = R.reduce_events(R.load_json(str(RECORDED)), "bench.call")  # one chip, PR 24: no collective, no shard
    assert reader.read(_record(manifest, recorded, 8, family="graphsage")) is None
    assert reader.read(_record(manifest, recorded, 0)) is None


def test_the_span_readers_read_the_counts_of_a_sharded_build(manifest, monkeypatch):
    """`shard.plan_imbalance` and `setup.shard_upload_s` from a trace as
    `models/stacked.py` records one: the plan span's `shard_entries`, and the
    `device_put` spans that carry a `shard`."""
    from benchmarks.harness import program_spans as ps
    from kmamiz_tpu.telemetry.tracing import TRACER

    ms = 1_000_000
    spans = [("refresh.stack", 0, 100 * ms, -1), ("refresh.stack.plan", ms, 9 * ms, 0)]
    counts = {1: {"shards": 4, "shard_entries": [1_000_000, 1_010_000, 990_000, 1_040_000]}}
    for d in range(4):
        spans += [("refresh.stack.host_fill", (10 + 20 * d) * ms, 12 * ms, 0),
                  ("refresh.stack.device_put", (22 + 20 * d) * ms, 8 * ms, 0)]
        counts[len(spans) - 1] = {"bytes": 1, "shard": d}
    sharded = types.SimpleNamespace(spans=spans, counts=counts, t0_ns=0)
    one_device = types.SimpleNamespace(
        spans=[("refresh.stack", 0, 9 * ms, -1), ("refresh.stack.plan", ms, ms, 0), ("refresh.stack.device_put", 3 * ms, ms, 0)],
        counts={1: {"entries": 10}, 2: {"bytes": 1}}, t0_ns=0,
    )
    record = _record(manifest, None)
    record.recorder.named = lambda name: [SimpleNamespace(start_s=0.0, end_s=1.0)] if name == "setup.stack_upload" else []
    read = lambda name: manifest.load_module(f"layer_metrics/{name}.py").read(record)  # noqa: E731
    monkeypatch.setattr(TRACER, "traces", lambda: [sharded])
    assert read("shard.plan_imbalance") == pytest.approx(1_040_000 * 4 / 4_040_000)
    assert read("setup.shard_upload_s") == pytest.approx(0.032)
    assert read("setup.plan_s") == pytest.approx(0.009)
    assert ps.find(record, "refresh.stack.device_put", within="setup.stack_upload")
    monkeypatch.setattr(TRACER, "traces", lambda: [one_device])
    assert read("shard.plan_imbalance") is None and read("setup.shard_upload_s") is None


def test_the_family_is_graphsages_mathematics_and_its_control_is_not():
    import jax

    rng = np.random.default_rng(0)
    n, e = 300, 1500
    p = {k: rng.normal(size=s).astype(np.float32) * 0.3 for k, s in {
        "w_self_1": (18, 64), "w_neigh_1": (18, 64), "b_1": (64,), "w_self_2": (64, 64), "w_neigh_2": (64, 64),
        "b_2": (64,), "w_latency": (64, 1), "b_latency": (1,), "w_anomaly": (64, 1), "b_anomaly": (1,),
        "w_latency_skip": (18, 1), "w_anomaly_skip": (18, 1)}.items()}
    x = rng.normal(size=(n, 18)).astype(np.float32)
    src, dst = rng.integers(0, n, e), rng.integers(0, n, e)
    with jax.default_matmul_precision("highest"):
        want = graphsage.forward(p, x, src, dst)
        got = graphsage_sharded.forward(p, x, src, dst)
        low = graphsage_sharded.forward_bfloat16_wire(p, x, src, dst)
    for a, b, c in zip(got, want, low):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))  # the same mathematics, line for line
        assert 1e-4 < np.abs(np.asarray(c) - np.asarray(b)).max() < 0.1  # a bfloat16 wire is another result
    assert not hasattr(graphsage_sharded, "FORWARD") and not hasattr(graphsage_sharded, "make_loss")


def test_the_bfloat16_wire_in_the_programs_place_is_not_correct(monkeypatch, tiny_config):
    """The check's control for this family: the reference itself passes in the
    program's place, and with every gathered table rounded to bfloat16 it
    fails FORWARD at the default bound, which the family keeps."""
    import sys

    import jax

    from kmamiz_tpu.models import graphsage as model

    wire = types.ModuleType("benchmarks.reference.toy_wire16")
    wire.forward = graphsage_sharded.forward_bfloat16_wire
    monkeypatch.setitem(sys.modules, wire.__name__, wire)
    tiny_config["family"] = "graphsage_sharded"
    full = mesh_history.generate(tiny_config, 9)
    init = check.to_host(model.init_params(jax.random.PRNGKey(9), hidden=64, num_features=18, num_nodes=0))

    def in_the_programs_place(family):
        def call(dataset):
            params, per_slot = ref_train.train(family, init, dataset, 1e-2, precision="default")
            mean = np.mean(np.asarray(per_slot, dtype=np.float64), axis=0)
            shaped = collections.namedtuple("Params", sorted(params))(**params)
            return types.SimpleNamespace(losses=[mean[0]], latency_losses=[mean[1]], anomaly_losses=[mean[2]],
                                         params=shaped)

        return check.against_reference(tiny_config, lambda n: mesh_history.head(full, n), MIX, 9, model, call)

    assert in_the_programs_place("graphsage_sharded").ok
    control = in_the_programs_place("toy_wire16")
    number = control.compared()["forward.default.loss"]
    assert not control.ok and number["limit"] == check.FORWARD["default"][0] and number["value"] > 3 * number["limit"]


def test_the_manifest_gained_entries_and_lost_none():
    doc = json.loads((ROOT / "BENCHMARK.json").read_text())
    assert [c["name"] for c in doc["configs"]][-1] == "mv400k-sage" and len(doc["configs"]) == 4
    assert [w["name"] for w in doc["workloads"]][-1] == CELL and len(doc["workloads"]) == 4
    assert [m["name"] for m in doc["per_layer"]][-5:] == list(NEW_METRICS)
    (plan_s,) = [m for m in doc["per_layer"] if m["name"] == "setup.plan_s"]
    assert plan_s["workloads"] == ["mv100k-gat.refresh", "mv100k-stlgt.refresh", CELL]
    assert doc["run_seconds"] == 51 and [m["bound"] for m in doc["end_to_end"]] == [0.01, 0.1]
