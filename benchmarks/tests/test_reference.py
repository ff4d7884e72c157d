"""The plain reference agrees with `trainer.train` for both families (on
the CPU both multiply in float32, so the agreement is to rounding)."""
import importlib

import numpy as np
import pytest

from benchmarks.gen import mesh_history
from benchmarks.reference import check, train as ref_train


@pytest.mark.parametrize("family", ["graphsage", "gat"])
def test_reference_equals_trainer(tiny_config, family):
    import jax

    from kmamiz_tpu.models import trainer

    model = importlib.import_module(f"kmamiz_tpu.models.{family}")
    ds = mesh_history.head(mesh_history.generate(tiny_config, 11), 3)
    init = check.to_host(
        model.init_params(jax.random.PRNGKey(11), hidden=64, num_features=18, num_nodes=0)
    )
    want_params, per_slot = ref_train.train(family, init, ds, 1e-2)
    got = trainer.train(ds, epochs=1, hidden=64, lr=1e-2, seed=11, model=model)
    assert np.allclose(
        [got.losses[0], got.latency_losses[0], got.anomaly_losses[0]],
        np.mean(per_slot, axis=0),
        rtol=1e-5,
    )
    got_params = check.to_host(got.params)
    assert set(got_params) == set(want_params)
    diff = np.concatenate([(got_params[k] - want_params[k]).ravel() for k in init])
    moved = np.concatenate([(want_params[k] - init[k]).ravel() for k in init])
    # the update moved them (agreement is not that of two untouched inits),
    # and the two ends agree to a ten-thousandth of that movement
    assert np.abs(moved).max() > 1e-2
    assert np.linalg.norm(diff) < 1e-4 * np.linalg.norm(moved)
    assert np.abs(diff).max() < 1e-3


@pytest.mark.parametrize("family", ["graphsage", "gat"])
def test_check_passes_the_program_and_fails_a_wrong_one(tiny_config, family):
    from kmamiz_tpu.models import trainer

    model = importlib.import_module(f"kmamiz_tpu.models.{family}")
    tiny_config["family"] = family
    full = mesh_history.generate(tiny_config, 5)
    mix = {"check_slots": 3, "forward_check_slots": 1}

    def head(n):
        return mesh_history.head(full, n)

    def call(dataset, lr=1e-2):
        return trainer.train(dataset, epochs=1, hidden=64, lr=lr, seed=5, model=model)

    def verdict(program):
        return check.against_reference(tiny_config, head, mix, 5, model, program)

    good = verdict(call)
    assert good.ok and good.detail["schedule"]["ok"] and good.detail["forward"]["ok"]
    # a program that skips a slot is a different result, not a faster one
    skips = verdict(lambda d: call(mesh_history.head(d, max(len(d.features) - 1, 1))))
    assert not skips.ok and not skips.detail["schedule"]["ok"]
    assert not verdict(lambda d: call(d, lr=2e-2)).ok

    # a history stored in bfloat16 fails the one-slot comparison
    def bf16_store(d):
        import jax.numpy as jnp

        rounded = mesh_history.head(d, len(d.features))
        rounded.features = [
            np.asarray(jnp.asarray(f).astype(jnp.bfloat16).astype(jnp.float32)) for f in d.features
        ]
        return call(rounded)

    stored = verdict(bf16_store)
    assert not stored.ok and not stored.detail["forward"]["ok"]


def test_same_computation_is_tight():
    assert check.same_computation([1.0, 2.0, 3.0], [1.0, 2.0, 3.0])
    assert not check.same_computation([1.0, 2.0, 3.001], [1.0, 2.0, 3.0])
    assert not check.same_computation([float("nan"), 2.0, 3.0], [1.0, 2.0, 3.0])
