"""`run.py` is driven by data: a configuration, a traffic mix, a driver and a
per-layer reader added as NEW files (in a directory the manifest lists) run
without an edit to any file that is there. And the last line it prints is
the contract's object, with exactly its keys."""
import copy
import hashlib
import json
from pathlib import Path

import pytest

from benchmarks import run
from benchmarks.harness import device
from benchmarks.harness.manifest import Manifest

ROOT = Path(__file__).resolve().parent.parent.parent
BENCH = ROOT / "benchmarks"

NEW_DRIVER = '''
from benchmarks.harness.record import Record
from benchmarks.harness.spans import Recorder

def run(ctx):
    rec = Recorder()
    with rec.span("probe.work"):
        total = sum(range(int(ctx.traffic["count"])))
    rec.counters["probe.items"] = ctx.traffic["count"]
    return Record(correct=total == ctx.config["expect"], attempted=1, failed=0,
                  end_to_end={"items_per_s": 1.0, "setup_s": 0.5}, recorder=rec,
                  manifest=ctx.manifest, config=ctx.config, traffic=ctx.traffic,
                  devices=ctx.devices)
'''
NEW_READER = '''
def read(record):
    return float(record.recorder.counters["probe.items"])
'''
SILENT_READER = '''
def read(record):
    return None
'''


def _tree_digest():
    h = hashlib.sha256()
    for p in sorted(BENCH.rglob("*")):
        if p.is_file() and "__pycache__" not in p.parts:
            h.update(str(p.relative_to(BENCH)).encode())
            h.update(p.read_bytes())
    return h.hexdigest()


@pytest.fixture
def cpu_devices(monkeypatch):
    import jax

    monkeypatch.setattr(device, "require", lambda chips: jax.devices()[:chips])


def _last_line(capsys):
    out = capsys.readouterr().out.strip().splitlines()
    return json.loads(out[-1])


def test_new_config_mix_driver_and_reader_run_as_new_files(tmp_path, capsys, cpu_devices):
    before = _tree_digest()
    extra = tmp_path / "extra"
    for sub in ("configs", "traffic", "drivers", "layer_metrics"):
        (extra / sub).mkdir(parents=True)
    (extra / "configs" / "toy.json").write_text(json.dumps({"expect": 45}))
    (extra / "traffic" / "count.json").write_text(json.dumps({"driver": "counter", "count": 10}))
    (extra / "drivers" / "counter.py").write_text(NEW_DRIVER)
    (extra / "layer_metrics" / "probe.items.py").write_text(NEW_READER)
    (extra / "layer_metrics" / "probe.nothing.py").write_text(SILENT_READER)
    doc = json.loads((ROOT / "BENCHMARK.json").read_text())
    doc["paths"] = [str(BENCH), str(extra)]
    doc["configs"].append({"name": "toy", "source": "test", "file": str(extra / "configs" / "toy.json"),
                           "reduced": [], "why": "test"})
    doc["workloads"].append({"name": "toy.count", "config": "toy", "traffic": "count", "chips": 1, "why": "test"})
    doc["end_to_end"].append({"name": "items_per_s", "unit": "items/s", "better": "higher", "bound": 0.05,
                              "source": "host_clock", "workloads": ["toy.count"]})
    for m in doc["end_to_end"] + doc["per_layer"]:
        if m["name"] != "setup_s" and "workloads" not in m:
            m["workloads"] = [w["name"] for w in doc["workloads"] if w["name"] != "toy.count"]
    for name in ("probe.items", "probe.nothing"):
        doc["per_layer"].append({"name": name, "unit": "count", "better": "higher", "source": "program_counter",
                                 "layer": "probe", "moves": "items_per_s", "workloads": ["toy.count"]})
    manifest = tmp_path / "BENCHMARK.json"
    manifest.write_text(json.dumps(doc))

    argv = ["--manifest", str(manifest), "--workload", "toy.count", "--seed", "1", "--seconds", "1"]
    assert run.main(argv + ["--trace", "0"]) == 0
    line = _last_line(capsys)
    assert line["correct"] is True and line["attempted"] == 1 and line["failed"] == 0
    assert line["metrics"] == {"items_per_s": {"value": 1.0, "unit": "items/s"},
                               "setup_s": {"value": 0.5, "unit": "s"}}
    assert run.main(argv + ["--trace", "1"]) == 0
    line = _last_line(capsys)
    # the reader that found nothing is left out of the line
    assert line["metrics"] == {"probe.items": {"value": 10.0, "unit": "count"}}
    assert _tree_digest() == before


def test_run_holds_no_cell(tmp_path):
    text = (BENCH / "run.py").read_text()
    doc = json.loads((ROOT / "BENCHMARK.json").read_text())
    names = [e["name"] for k in ("configs", "workloads", "end_to_end", "per_layer") for e in doc[k]]
    names += [w["traffic"] for w in doc["workloads"]]
    assert not [n for n in names if n in text]
    assert "if workload ==" not in text and "131072" not in text


def test_manifest_entries_resolve_to_files():
    manifest = Manifest(ROOT / "BENCHMARK.json")
    for cell in manifest.doc["workloads"]:
        cfg = manifest.config(cell["config"])
        mix = manifest.load_json(f"traffic/{cell['traffic']}.json")
        assert hasattr(manifest.load_module(f"drivers/{mix['driver']}.py"), "run")
        assert hasattr(manifest.load_module(f"gen/{cfg['generator']}.py"), "generate")
        work = manifest.load_module(f"trace/work/{cfg['family']}.py")
        assert work.slot_update_bytes(cfg) > 0
        for m in manifest.metrics("per_layer", cell["name"]):
            assert hasattr(manifest.load_module(f"layer_metrics/{m['name']}.py"), "read")
        # the file shows the floor's arithmetic and its sizes are section 3's
        stack = cfg["slots"] * cfg["node_bucket"] * 81
        assert stack == 4_586_471_424 and stack > 4 * 2**30  # clears the floor by itself
        assert str(cfg["slots"]) in cfg["device_memory"]["stack"]
        assert cfg["slots"] == 24 * cfg["retention_days"]
        assert set(cfg["reduced"]) == set(manifest._entry("configs", cell["config"])["reduced"])
        assert (cfg["endpoints"], cfg["edges"], cfg["num_features"], cfg["hidden"]) == (100_000, 500_000, 18, 64)
        assert cfg["chips"] == cell["chips"] == 1 and "chips_why" in cfg and "host_memory" in cfg
    with pytest.raises(KeyError):
        manifest.workload("no-such-cell")
    with pytest.raises(FileNotFoundError):
        manifest.find("traffic/no-such-mix.json")


def test_no_accelerator_is_refused_without_a_result(capsys):
    # JAX is held to the CPU here: the run must fail and print no result
    code = run.main(["--workload", "mv100k-sage.refresh", "--seed", "1", "--seconds", "1"])
    assert code != 0
    assert capsys.readouterr().out.strip() == ""


@pytest.mark.parametrize("family,traced", [("graphsage", 0), ("gat", 0), ("graphsage", 1)])
def test_last_line_has_exactly_the_contract_keys(tmp_path, capsys, cpu_devices, tiny_config, family, traced):
    cfg = copy.deepcopy(tiny_config)
    cfg.update(family=family, model_module=f"kmamiz_tpu.models.{family}", name="tiny")
    (tmp_path / "tiny.json").write_text(json.dumps(cfg))
    doc = json.loads((ROOT / "BENCHMARK.json").read_text())
    doc["paths"] = [str(BENCH)]
    doc["configs"] = [{"name": "tiny", "source": "test", "file": str(tmp_path / "tiny.json"), "reduced": [], "why": "t"}]
    doc["workloads"] = [{"name": "tiny.refresh", "config": "tiny", "traffic": "refresh", "chips": 1, "why": "t"}]
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(doc))
    code = run.main(["--manifest", str(tmp_path / "BENCHMARK.json"), "--workload", "tiny.refresh",
                     "--seed", str(2**31 + 12345), "--seconds", "0.2", "--trace", str(traced)])
    assert code == 0
    captured = capsys.readouterr()
    line = json.loads(captured.out.strip().splitlines()[-1])
    want = {"correct", "attempted", "failed", "metrics", "device", "compared"} | ({"breakdown"} if traced else set())
    assert set(line) == want and list(line)[-1] == "compared"
    # each number `correct` was decided from, beside its limit, and under it
    assert {"schedule.default.loss", "forward.default.loss", "forward.highest.param", "window.calls_failed"} <= set(line["compared"])
    assert all(set(n) == {"value", "limit"} and n["value"] <= n["limit"] for n in line["compared"].values())
    err = captured.err.strip().splitlines()
    assert err[-len(line["compared"]):] == [
        f"compared {k}: {n['value']!r} limit {n['limit']!r}" for k, n in line["compared"].items()
    ]
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] >= 1
    device_keys = {"platform", "kind", "count", "memory_peak_bytes"} | ({"busy_s", "window_s"} if traced else set())
    assert set(line["device"]) == device_keys
    declared = {m["name"]: m["unit"] for m in doc["per_layer" if traced else "end_to_end"]}
    assert set(line["metrics"]) <= set(declared)
    if not traced:
        assert set(line["metrics"]) == set(declared)
        assert line["metrics"]["refresh_slot_updates_per_s"]["value"] > 0
    for name, m in line["metrics"].items():
        assert set(m) == {"value", "unit"} and m["unit"] == declared[name]
    if traced:
        assert set(line["breakdown"]) == {"device_ops", "idle_gaps"}
        assert line["metrics"]["epoch_block.compiles"]["value"] == 0


def _broken(fault):
    """`trainer.train` with the timed path broken underneath, one fault of
    those a training cell can have."""
    import dataclasses

    import jax
    import numpy as np

    from kmamiz_tpu.models import trainer

    real = trainer.train

    def train(dataset, **kw):
        if fault == "state returned unchanged":
            result = real(dataset, **kw)
            model = kw["model"]
            init = model.init_params(jax.random.PRNGKey(kw["seed"]), hidden=kw["hidden"],
                                     num_features=dataset.features[0].shape[1], num_nodes=0)
            return dataclasses.replace(result, params=init)
        if fault == "half of the endpoints left out":
            half = [np.where(np.arange(m.shape[0]) % 2 == 0, np.asarray(m), False) for m in dataset.node_mask]
            return real(dataclasses.replace(dataset, node_mask=half), **kw)
        if fault == "a loss altered where it is reported":
            result = real(dataset, **kw)
            return dataclasses.replace(result, losses=[v * 1.001 for v in result.losses])
        raise AssertionError(fault)

    return train


@pytest.mark.parametrize("fault", ["state returned unchanged", "half of the endpoints left out",
                                   "a loss altered where it is reported"])
@pytest.mark.parametrize("family", ["graphsage", "gat"])
def test_a_run_on_a_broken_program_is_not_correct(tmp_path, capsys, cpu_devices, monkeypatch, tiny_config,
                                                  family, fault):
    from kmamiz_tpu.models import trainer

    cfg = copy.deepcopy(tiny_config)
    cfg.update(family=family, model_module=f"kmamiz_tpu.models.{family}", name="tiny")
    (tmp_path / "tiny.json").write_text(json.dumps(cfg))
    doc = json.loads((ROOT / "BENCHMARK.json").read_text())
    doc["paths"] = [str(BENCH)]
    doc["configs"] = [{"name": "tiny", "source": "test", "file": str(tmp_path / "tiny.json"), "reduced": [], "why": "t"}]
    doc["workloads"] = [{"name": "tiny.refresh", "config": "tiny", "traffic": "refresh", "chips": 1, "why": "t"}]
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(doc))
    monkeypatch.setattr(trainer, "train", _broken(fault))
    code = run.main(["--manifest", str(tmp_path / "BENCHMARK.json"), "--workload", "tiny.refresh",
                     "--seed", "77", "--seconds", "0.2", "--trace", "0"])
    assert code == 0
    line = _last_line(capsys)
    assert line["correct"] is False
    over = [k for k, n in line["compared"].items() if n["value"] is None or n["value"] > n["limit"]]
    assert over, "correct is false, so some number compared is over its limit"
