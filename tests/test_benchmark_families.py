"""What decides a cell's `correct`, in tier-1: the cases of
`benchmarks/tests/test_family_rules.py` and the broken-program cases of
`benchmarks/tests/test_harness.py` (they run on the CPU in seconds at a tiny
size; the driver's command does not collect `benchmarks/tests/`), imported
as they are, and the same questions asked of the third family: the check
takes `reference/stlgt.py`'s `make_loss` and its FORWARD rule, a program that
trains another loss or leaves a quantile level out is not `correct`, and a
gated sum fed bfloat16 rows fails FORWARD."""
from __future__ import annotations

import collections
import copy
import importlib.util
import json
import sys
import types
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parent.parent
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))


def _load(name: str):
    """A file of `benchmarks/tests/` as a module (the directory is no package)."""
    path = ROOT / "benchmarks" / "tests" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"benchmarks_tests_{name}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


_rules = _load("test_family_rules")
_harness = _load("test_harness")
TINY = _load("conftest").TINY

cpu_devices = _harness.cpu_devices  # the fixture, as the harness's tests define it


@pytest.fixture
def tiny_config():
    return copy.deepcopy(TINY)


@pytest.fixture(autouse=True)
def _the_worker_keeps_no_persistent_cache(monkeypatch):
    """`run.main` turns the process's persistent compile cache on
    (`drivers/refresh.py`: `compile_cache.enable()`), for good. In its own
    process that is the point; a tier-1 worker goes on to other files, which
    must find the cache as they left it (tests/test_programs.py)."""
    from kmamiz_tpu.core import compile_cache

    monkeypatch.setattr(compile_cache, "enable", compile_cache.cache_dir)


# the cases as they are, parametrised as they are: two families
test_a_family_without_a_loss_gets_make_loss_to_the_bit = _rules.test_a_family_without_a_loss_gets_make_loss_to_the_bit
test_a_family_with_its_own_loss_is_trained_with_it = _rules.test_a_family_with_its_own_loss_is_trained_with_it
test_the_check_fails_a_program_that_trains_another_loss = _rules.test_the_check_fails_a_program_that_trains_another_loss
test_forward_is_held_to_the_familys_own_bounds = _rules.test_forward_is_held_to_the_familys_own_bounds
test_which_family_states_a_rule_of_its_own = _rules.test_which_family_states_a_rule_of_its_own
test_the_forward_rule = _rules.test_the_forward_rule
test_gats_control_fails_its_forward_bound = _rules.test_gats_control_fails_its_forward_bound
test_the_reference_traces_its_step_once_a_precision_not_once_a_call = (
    _rules.test_the_reference_traces_its_step_once_a_precision_not_once_a_call
)
test_nothing_of_the_reference_is_kept_once_the_check_is_over = (
    _rules.test_nothing_of_the_reference_is_kept_once_the_check_is_over
)
test_a_run_on_a_broken_program_is_not_correct = _harness.test_a_run_on_a_broken_program_is_not_correct


# -- the third family -----------------------------------------------------------

from benchmarks import run  # noqa: E402
from benchmarks.gen import mesh_history  # noqa: E402
from benchmarks.reference import check, stlgt as reference_stlgt, train as ref_train  # noqa: E402

MIX = {"check_slots": 3, "forward_check_slots": 1}


def _stlgt_config(tiny_config):
    tiny_config.update(family="stlgt", model_module="kmamiz_tpu.models.stlgt.model", name="tiny")
    return tiny_config


def _program(seed):
    from kmamiz_tpu.models import trainer
    from kmamiz_tpu.models.stlgt import model

    def call(dataset):
        return trainer.train(dataset, epochs=1, hidden=64, lr=1e-2, seed=seed, model=model)

    return call


def test_the_check_takes_the_new_familys_loss_and_its_forward_rule(monkeypatch, tiny_config):
    from kmamiz_tpu.models.stlgt import model

    cfg = _stlgt_config(tiny_config)
    asked = []
    real = reference_stlgt.make_loss

    def make_loss(weight):
        asked.append(weight)
        return real(weight)

    monkeypatch.setattr(reference_stlgt, "make_loss", make_loss)
    full = mesh_history.generate(cfg, 5)
    verdict = check.against_reference(cfg, lambda n: mesh_history.head(full, n), MIX, 5, model, _program(5))
    assert verdict.ok
    # the family's loss was asked for, with the trainer's weight, and trained under
    assert {ref_train.pos_weight(mesh_history.head(full, 3)), ref_train.pos_weight(mesh_history.head(full, 1))} <= set(asked)
    total, quantile, anomaly = verdict.detail["schedule"]["highest"]["reference_losses"]
    assert total == pytest.approx(quantile + anomaly, rel=1e-6)
    # its FORWARD rule is the one compared: the bound and the number of readings
    bounds = getattr(reference_stlgt, "FORWARD", check.FORWARD)
    readings = getattr(reference_stlgt, "FORWARD_READINGS", check.FORWARD_READINGS)
    assert verdict.compared()["forward.default.loss"]["limit"] == bounds["default"][0]
    assert len(verdict.detail["forward"]["readings"]) == readings
    # and it is held to it: a stricter rule of the family's refuses the same program
    monkeypatch.setattr(reference_stlgt, "FORWARD", dict(bounds, default=(-1.0, bounds["default"][1])), raising=False)
    held = check.against_reference(cfg, lambda n: mesh_history.head(full, n), MIX, 5, model, _program(5))
    assert not held.ok and held.detail["schedule"]["ok"] and not held.detail["forward"]["ok"]


def test_the_program_under_the_familys_default_loss_is_not_correct_for_the_quantile_family(monkeypatch, tiny_config):
    """What the parent commit does with this head: mean squared error on p50
    alone. The reference holds it to the pinball loss."""
    from kmamiz_tpu.models import stacked
    from kmamiz_tpu.models.stlgt import model

    cfg = _stlgt_config(tiny_config)
    full = mesh_history.generate(cfg, 5)
    monkeypatch.delattr(model, "make_loss_fn")
    stacked.epoch_runner.cache_clear()
    try:
        verdict = check.against_reference(cfg, lambda n: mesh_history.head(full, n), MIX, 5, model, _program(5))
    finally:
        stacked.epoch_runner.cache_clear()
    assert not verdict.ok and not verdict.detail["schedule"]["ok"] and not verdict.detail["forward"]["ok"]


def _bfloat16_rows(what):
    """The family's controls: the plain forward with the rows the gated sum
    weighs rounded to bfloat16 ("rows"), and the rows of the gate's dot
    product too ("dot too")."""
    import jax
    import jax.numpy as jnp

    def bf16(v):
        return jax.lax.reduce_precision(v, exponent_bits=8, mantissa_bits=7)

    def neighbour_bias(q, k, v, b_edge, src, dst):
        n, width = q.shape
        qd, kd = (bf16(q), bf16(k)) if what == "dot too" else (q, k)
        gate = jax.nn.sigmoid((qd[src] * kd[dst]).sum(axis=1) / jnp.sqrt(jnp.float32(width)) + b_edge[0])
        low = bf16(v)
        total = jnp.zeros_like(v).at[dst].add(gate[:, None] * low[src]).at[src].add(gate[:, None] * low[dst])
        weight = jnp.zeros(n, v.dtype).at[dst].add(gate).at[src].add(gate)
        return total / jnp.maximum(weight, 1.0)[:, None]

    plain_forward, plain_bias = reference_stlgt.forward, reference_stlgt.neighbour_bias

    def forward(p, x, src, dst):
        # `forward` finds `neighbour_bias` in its module at trace time
        reference_stlgt.neighbour_bias = neighbour_bias
        try:
            return plain_forward(p, x, src, dst)
        finally:
            reference_stlgt.neighbour_bias = plain_bias

    def make_loss(weight):
        inner = reference_stlgt.make_loss(weight)

        def loss(*args):  # `loss` finds `forward` in its module at trace time
            reference_stlgt.forward = forward
            try:
                return inner(*args)
            finally:
                reference_stlgt.forward = plain_forward

        return loss

    return forward, make_loss


@pytest.mark.parametrize("what", ["rows", "dot too"])
def test_a_gated_sum_fed_bfloat16_rows_fails_the_familys_forward_bound(monkeypatch, tiny_config, what):
    from kmamiz_tpu.models.stlgt import model

    cfg = _stlgt_config(tiny_config)
    forward, make_loss = _bfloat16_rows(what)
    _rules._register(monkeypatch, "toy_stlgt16", forward=forward, make_loss=make_loss)
    full = mesh_history.generate(cfg, 9)
    init = _rules._init(model, 9)

    def in_the_programs_place(family):
        def call(dataset):
            params, per_slot = ref_train.train(family, init, dataset, 1e-2, precision="default")
            mean = np.mean(np.asarray(per_slot, dtype=np.float64), axis=0)
            shaped = collections.namedtuple("Params", sorted(params))(**params)
            return types.SimpleNamespace(losses=[mean[0]], latency_losses=[mean[1]], anomaly_losses=[mean[2]],
                                         params=shaped)

        return check.against_reference(cfg, lambda n: mesh_history.head(full, n), MIX, 9, model, call)

    assert in_the_programs_place("stlgt").ok
    control = in_the_programs_place("toy_stlgt16")
    number = control.compared()["forward.default.loss"]
    assert not control.ok and not control.detail["forward"]["ok"]
    assert number["value"] > 3 * number["limit"]


def _one_level_only(model):
    """The program's loss with the pinball term at 0.50 alone."""
    import jax.numpy as jnp
    import optax

    def make(pos_weight):
        def loss_fn(params, f, src, dst, em, tl, ta, nm, plan=None):
            quantiles, logit, _ = model.forward_quantiles(params, f, src, dst, em, plan)
            w = nm.astype(jnp.float32)
            count = jnp.maximum(w.sum(), 1.0)
            d = tl - quantiles[:, 0]
            first = jnp.sum(w * jnp.maximum(0.5 * d, -0.5 * d)) / count
            weight = 1.0 + (pos_weight - 1.0) * ta
            second = jnp.sum(w * weight * optax.sigmoid_binary_cross_entropy(logit, ta)) / count
            return first + second, (first, second)

        return loss_fn

    return make


@pytest.mark.parametrize("fault", ["state returned unchanged", "half of the endpoints left out",
                                   "a loss altered where it is reported", "the loss at one level only"])
def test_a_run_on_a_broken_quantile_program_is_not_correct(tmp_path, capsys, cpu_devices, monkeypatch, tiny_config, fault):
    from kmamiz_tpu.models import stacked, trainer
    from kmamiz_tpu.models.stlgt import model

    cfg = _stlgt_config(tiny_config)
    (tmp_path / "tiny.json").write_text(json.dumps(cfg))
    doc = json.loads((ROOT / "BENCHMARK.json").read_text())
    doc["paths"] = [str(ROOT / "benchmarks")]
    doc["configs"] = [{"name": "tiny", "source": "test", "file": str(tmp_path / "tiny.json"), "reduced": [], "why": "t"}]
    doc["workloads"] = [{"name": "tiny.refresh", "config": "tiny", "traffic": "refresh", "chips": 1, "why": "t"}]
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(doc))
    stacked.epoch_runner.cache_clear()
    if fault == "the loss at one level only":
        monkeypatch.setattr(model, "make_loss_fn", _one_level_only(model))
    else:
        monkeypatch.setattr(trainer, "train", _harness._broken(fault))
    try:
        code = run.main(["--manifest", str(tmp_path / "BENCHMARK.json"), "--workload", "tiny.refresh",
                         "--seed", "77", "--seconds", "0.2", "--trace", "0"])
    finally:
        stacked.epoch_runner.cache_clear()
    assert code == 0
    line = _harness._last_line(capsys)
    assert line["correct"] is False
    over = [k for k, n in line["compared"].items() if n["value"] is None or n["value"] > n["limit"]]
    assert over, "correct is false, so some number compared is over its limit"


def test_a_sound_quantile_run_is_correct_and_its_traced_line_reads_the_new_kernels_metrics_or_nothing(
    tmp_path, capsys, cpu_devices, tiny_config
):
    """On a CPU there is no device op to read: the two readers this family
    adds return nothing and the line leaves them out, as it does on a commit
    from before the kernels."""
    cfg = _stlgt_config(tiny_config)
    (tmp_path / "tiny.json").write_text(json.dumps(cfg))
    doc = json.loads((ROOT / "BENCHMARK.json").read_text())
    doc["paths"] = [str(ROOT / "benchmarks")]
    doc["configs"] = [{"name": "tiny", "source": "test", "file": str(tmp_path / "tiny.json"), "reduced": [], "why": "t"}]
    doc["workloads"] = [{"name": "tiny.refresh", "config": "tiny", "traffic": "refresh", "chips": 1, "why": "t"}]
    for metric in doc["per_layer"]:
        if "workloads" in metric:
            metric["workloads"] = ["tiny.refresh"]
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(doc))
    code = run.main(["--manifest", str(tmp_path / "BENCHMARK.json"), "--workload", "tiny.refresh",
                     "--seed", str(2**31 + 4321), "--seconds", "0.2", "--trace", "1"])
    assert code == 0
    line = _harness._last_line(capsys)
    assert line["correct"] is True and line["failed"] == 0
    assert all(n["value"] <= n["limit"] for n in line["compared"].values())
    assert not {"kernel.gated_sum_ms_per_slot", "kernel.gated_sum_hbm_roofline"} & set(line["metrics"])
    assert line["metrics"]["epoch_block.compiles"]["value"] == 0


def test_the_manifest_holds_the_new_configuration_and_its_cell():
    doc = json.loads((ROOT / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in doc["workloads"]}
    assert list(cells)[:3] == ["mv100k-sage.refresh", "mv100k-gat.refresh", "mv100k-stlgt.refresh"]
    assert cells["mv100k-stlgt.refresh"] == dict(
        cells["mv100k-stlgt.refresh"], config="mv100k-stlgt", traffic="refresh", chips=1
    )
    entry = {c["name"]: c for c in doc["configs"]}["mv100k-stlgt"]
    assert entry["reduced"] == ["epochs_per_refresh", "retention_days", "slots"]
    assert all(len(e.get("why", "")) <= 200 and len(e.get("source", "")) <= 200 for e in doc["configs"] + doc["workloads"])
    cfg = json.loads((ROOT / entry["file"]).read_text())
    sage = json.loads((ROOT / "benchmarks/configs/mv100k-sage.json").read_text())
    assert cfg["family"] == "stlgt" and cfg["model_module"] == "kmamiz_tpu.models.stlgt.model"
    assert cfg["source"] == entry["source"] and set(cfg["reduced"]) == set(entry["reduced"])
    for key in ("endpoints", "node_bucket", "edges", "edge_bucket", "num_features", "hidden", "slots", "batch_slots",
                "node_embeddings", "weight_decay", "retention_days", "generator", "lr"):
        assert cfg[key] == sage[key], key  # every shape and the rate of the sibling kept: they differ in the head alone
    assert cfg["lr"] == 0.01  # ISSUE 33's call, `trainer.train`'s own default
    assert cfg["quantiles"] == list(reference_stlgt.QUANTILES)
    # what no source in the repository bears is listed as assumed: the width, the rate, the repo's one block
    assert cfg["layers"] == 1 and {"hidden", "lr", "layers"} <= set(cfg["assumed"])
    new = [m for m in doc["per_layer"] if m.get("workloads") == ["mv100k-stlgt.refresh"]]
    assert [m["name"] for m in new] == ["kernel.gated_sum_ms_per_slot", "kernel.gated_sum_hbm_roofline"]
    # the cell builds the edge plan in set-up as GAT's does, so the accepted metric of that span lists it too
    plan_s = {m["name"]: m for m in doc["per_layer"]}["setup.plan_s"]
    assert plan_s["workloads"][:2] == ["mv100k-gat.refresh", "mv100k-stlgt.refresh"]  # later cells append
    # the work file's terms, and no share can pass 100% by arithmetic alone: the gated terms are part of the whole
    work = _load_work("stlgt")
    terms = work.terms(cfg)
    assert set(terms) == {"gated_sums", "gated_scalars", "dense_forward", "dense_backward", "linear_attention",
                          "readout", "optimizer"}
    assert work.slot_update_bytes(cfg) == sum(terms.values()) > terms["gated_sums"] + terms["gated_scalars"] > 0


def _load_work(family):
    spec = importlib.util.spec_from_file_location(f"work_{family}", ROOT / "benchmarks/trace/work" / f"{family}.py")
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module
