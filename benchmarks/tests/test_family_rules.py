"""What decides `correct` comes from the head's family: `reference/<family>.py`
may bring its own loss (`make_loss(weight)`) and its own FORWARD bounds, and a
family that brings neither gets `train.make_loss` and `check.FORWARD`."""
import collections
import importlib
import sys
import types

import numpy as np
import pytest

from benchmarks.gen import mesh_history
from benchmarks.reference import check, gat, graphsage, train as ref_train

MIX = {"check_slots": 3, "forward_check_slots": 1}


def _register(monkeypatch, name, **attrs):
    """A family registered for the test alone, as a file of
    `benchmarks/reference/` would be found."""
    module = types.ModuleType(f"benchmarks.reference.{name}")
    for key, value in attrs.items():
        setattr(module, key, value)
    monkeypatch.setitem(sys.modules, module.__name__, module)
    return module


def _absolute_error(forward):
    """A loss that is not the trainer's: absolute error of the latency head,
    the anomaly head unweighted."""
    import jax.numpy as jnp

    def make_loss(weight):
        def loss(params, x, src, dst, target_latency, target_anomaly, active):
            latency, logit = forward(params, x, src, dst)
            w = active.astype(jnp.float32)
            count = jnp.maximum(w.sum(), 1.0)
            first = jnp.sum(w * jnp.abs(latency - target_latency)) / count
            second = jnp.sum(w * (jnp.maximum(logit, 0.0) - logit * target_anomaly
                                  + jnp.log1p(jnp.exp(-jnp.abs(logit))))) / count
            return first + second, (first, second)

        return loss

    return make_loss


def _init(model, seed):
    import jax

    return check.to_host(model.init_params(jax.random.PRNGKey(seed), hidden=64, num_features=18, num_nodes=0))


@pytest.mark.parametrize("family", ["graphsage", "gat"])
def test_a_family_without_a_loss_gets_make_loss_to_the_bit(monkeypatch, tiny_config, family):
    plain = importlib.import_module(f"benchmarks.reference.{family}")
    assert not hasattr(plain, "make_loss")
    seen = []

    def make_loss(weight):
        seen.append(weight)
        return ref_train.make_loss(plain.forward, weight)

    _register(monkeypatch, "toy_same", forward=plain.forward, make_loss=make_loss)
    ds = mesh_history.head(mesh_history.generate(tiny_config, 3), 3)
    init = _init(importlib.import_module(f"kmamiz_tpu.models.{family}"), 3)
    want_params, want = ref_train.train(family, init, ds, 1e-2)
    got_params, got = ref_train.train("toy_same", init, ds, 1e-2)
    assert seen == [ref_train.pos_weight(ds)]  # the family's loss was asked for, with the weight
    assert got == want and len(got) == 3
    assert all(np.array_equal(got_params[k], want_params[k]) for k in want_params)


def test_a_family_with_its_own_loss_is_trained_with_it(monkeypatch, tiny_config):
    import jax
    import jax.numpy as jnp

    _register(monkeypatch, "toy_abs", forward=graphsage.forward, make_loss=_absolute_error(graphsage.forward))
    ds = mesh_history.head(mesh_history.generate(tiny_config, 3), 2)
    from kmamiz_tpu.models import graphsage as program_sage

    init = _init(program_sage, 3)
    _, got = ref_train.train("toy_abs", init, ds, 1e-2)
    _, default = ref_train.train("graphsage", init, ds, 1e-2)
    # the first slot's losses are the forward pass on the init alone: the toy's
    # loss by hand, and not the squared error a family without one gets
    loss = _absolute_error(graphsage.forward)(ref_train.pos_weight(ds))
    with jax.default_matmul_precision("highest"):
        total, (first, second) = loss(
            {k: jnp.asarray(v) for k, v in init.items()}, jnp.asarray(ds.features[0]), jnp.asarray(ds.src),
            jnp.asarray(ds.dst), jnp.asarray(ds.target_latency[0]), jnp.asarray(ds.target_anomaly[0]),
            jnp.asarray(ds.node_mask[0]))
    assert np.allclose(got[0], [float(total), float(first), float(second)], rtol=1e-6)
    assert abs(got[0][1] - default[0][1]) > 0.05 * default[0][1]
    assert got[0][0] == pytest.approx(got[0][1] + got[0][2], rel=1e-6)


def test_the_check_fails_a_program_that_trains_another_loss(monkeypatch, tiny_config):
    """The reference holds the head to ITS loss: the program, which trains
    squared error, is not `ok` for a family whose loss is absolute error."""
    from kmamiz_tpu.models import graphsage as model, trainer

    _register(monkeypatch, "toy_abs", forward=graphsage.forward, make_loss=_absolute_error(graphsage.forward))
    full = mesh_history.generate(tiny_config, 5)

    def call(dataset):
        return trainer.train(dataset, epochs=1, hidden=64, lr=1e-2, seed=5, model=model)

    def verdict(family):
        tiny_config["family"] = family
        return check.against_reference(tiny_config, lambda n: mesh_history.head(full, n), MIX, 5, model, call)

    assert verdict("graphsage").ok
    other = verdict("toy_abs")
    assert not other.ok and not other.detail["schedule"]["ok"] and not other.detail["forward"]["ok"]


def test_forward_is_held_to_the_familys_own_bounds(monkeypatch, tiny_config):
    from kmamiz_tpu.models import graphsage as model, trainer

    full = mesh_history.generate(tiny_config, 5)

    def call(dataset):
        return trainer.train(dataset, epochs=1, hidden=64, lr=1e-2, seed=5, model=model)

    def verdict(family):
        tiny_config["family"] = family
        return check.against_reference(tiny_config, lambda n: mesh_history.head(full, n), MIX, 5, model, call)

    # a family that states none is held to the default
    plain = verdict("graphsage")
    assert plain.ok and plain.compared()["forward.default.loss"]["limit"] == check.FORWARD["default"][0] == 1e-6
    # one that states its own is held to that, in FORWARD alone
    strict = dict(check.FORWARD, default=(-1.0, check.FORWARD["default"][1]))
    _register(monkeypatch, "toy_strict", forward=graphsage.forward, FORWARD=strict)
    held = verdict("toy_strict")
    assert not held.ok and not held.detail["forward"]["ok"] and held.detail["schedule"]["ok"]
    assert held.compared()["forward.default.loss"]["limit"] == -1.0
    assert held.compared()["schedule.default.loss"]["limit"] == check.SCHEDULE["default"][0]


def test_which_family_states_a_rule_of_its_own():
    # GraphSAGE: one reading under the default 1e-6
    assert not hasattr(graphsage, "FORWARD") and not hasattr(graphsage, "FORWARD_READINGS")
    assert check.FORWARD_READINGS == 1 and check.FORWARD["default"][0] == 1e-6
    # GAT: the middle of three, under a bound between its sound readings
    # (1.23e-6) and its controls' (1.25e-5), gat.py
    loss, param = gat.FORWARD["default"]
    assert gat.FORWARD_READINGS == 3 and 3 * 1.23e-6 <= loss <= 1.25e-5 / 3
    assert param == check.FORWARD["default"][1] and gat.FORWARD["highest"] == check.FORWARD["highest"]


def _readings(*losses, bound=1e-6, param=1e-3, highest=True):
    own = [{"loss_rel": v, "loss_rtol": bound, "param_rel": param, "param_tol": 0.25} for v in losses]
    return {"losses": [1.0, 0.6, 0.4], "highest": {"ok": highest}, "default": own[0], "readings": own}


@pytest.mark.parametrize("detail,ok", [
    (_readings(2e-7), True),                         # one reading: within its bound
    (_readings(3e-6), False),                        # one reading: over it
    (_readings(3e-6, 2e-7, 0.0), True),              # two of three within: the middle one is
    (_readings(2e-7, 9e-7, 3e-6), True),             # whichever slot the far one is
    (_readings(3e-6, 2e-6, 0.0), False),             # two of three over
    (_readings(2e-7, 2e-7, 2e-3), True),             # one far off alone is SCHEDULE's to see, not FORWARD's
    (_readings(2e-7, 2e-3, 2e-3), False),            # two far off
    (_readings(2e-7, float("nan"), 2e-7), False),    # a nan is over every bound
    (_readings(2e-7, 2e-7, 2e-7, param=0.3), False), # a parameter difference over its bound
    (_readings(2e-7, 2e-7, 2e-7, highest=False), False),
])
def test_the_forward_rule(detail, ok):
    assert check.forward_ok(detail) is ok


def _low_precision(what):
    """GAT's controls: the plain forward with one thing rounded to bfloat16.
    "messages": the attention's weighted sums take bfloat16 rows (one MXU
    pass a sum for the three that float32 rows take: the cut a later PR is
    tempted by); "scores too": its scores as well; "layer outputs": each
    layer's output, which on the chip touches the readouts alone (gat.py)."""
    import jax
    import jax.numpy as jnp

    def bf16(v):
        # not `astype`: on the chip XLA drops a convert pair (excess precision)
        return jax.lax.reduce_precision(v, exponent_bits=8, mantissa_bits=7)

    def attend(hw, sender, receiver, a_send, a_recv):
        n = hw.shape[0]
        low = bf16(hw)
        sc = low if what == "scores too" else hw
        score = jax.nn.leaky_relu(sc[sender] @ a_send + sc[receiver] @ a_recv, negative_slope=gat.LEAK)
        top = jnp.full(n, -jnp.inf, score.dtype).at[receiver].max(score)
        weight = jnp.exp(score - top[receiver])
        total = jnp.zeros(n, score.dtype).at[receiver].add(weight)
        alpha = weight / total[receiver]
        return jnp.zeros_like(hw).at[receiver].add(low[sender] * alpha[:, None])

    plain_layer = gat.layer

    def layer(*args):
        return bf16(plain_layer(*args))

    def forward(p, x, src, dst):
        # `gat.forward` and `gat.layer` find `layer` and `attend` in their module at trace time
        name, low = ("layer", layer) if what == "layer outputs" else ("attend", attend)
        original = getattr(gat, name)
        setattr(gat, name, low)
        try:
            return gat.forward(p, x, src, dst)
        finally:
            setattr(gat, name, original)

    return forward


@pytest.mark.parametrize("what", ["messages", "scores too", "layer outputs"])
def test_gats_control_fails_its_forward_bound(monkeypatch, tiny_config, what):
    """The reference in the program's place, one precision lower: not `ok`,
    by FORWARD at the family's own bound."""
    from kmamiz_tpu.models import gat as model

    _register(monkeypatch, "toy_gat16", forward=_low_precision(what))
    tiny_config["family"] = "gat"
    full = mesh_history.generate(tiny_config, 9)
    init = _init(model, 9)

    def in_the_programs_place(family):
        def call(dataset):
            params, per_slot = ref_train.train(family, init, dataset, 1e-2, precision="default")
            mean = np.mean(np.asarray(per_slot, dtype=np.float64), axis=0)
            shaped = collections.namedtuple("Params", sorted(params))(**params)
            return types.SimpleNamespace(losses=[mean[0]], latency_losses=[mean[1]], anomaly_losses=[mean[2]],
                                         params=shaped)

        return check.against_reference(tiny_config, lambda n: mesh_history.head(full, n), MIX, 9, model, call)

    assert in_the_programs_place("gat").ok
    control = in_the_programs_place("toy_gat16")
    number = control.compared()["forward.default.loss"]
    assert not control.ok and number["limit"] == gat.FORWARD["default"][0] and number["value"] > 3 * number["limit"]


def test_the_reference_traces_its_step_once_a_precision_not_once_a_call(monkeypatch, tiny_config):
    traces = []

    def forward(p, x, src, dst):
        import jax

        traces.append(jax.config.jax_default_matmul_precision)
        return graphsage.forward(p, x, src, dst)

    _register(monkeypatch, "toy_counted", forward=forward)
    from kmamiz_tpu.models import graphsage as model

    full = mesh_history.generate(tiny_config, 3)
    init = _init(model, 3)
    three, one = mesh_history.head(full, 3), mesh_history.head(full, 1)
    first = ref_train.train("toy_counted", init, three, 1e-2, precision="highest")
    ref_train.train("toy_counted", init, one, 1e-2, precision="highest")
    assert traces == ["highest"]
    ref_train.train("toy_counted", init, one, 1e-2, precision="default")
    assert traces == ["highest", "default"]  # the precision in force is part of the key
    again = ref_train.train("toy_counted", init, three, 1e-2, precision="highest")
    assert traces == ["highest", "default"] and again[1] == first[1]
    assert all(np.array_equal(again[0][k], first[0][k]) for k in first[0])
    ref_train.compiled.cache_clear()


def test_nothing_of_the_reference_is_kept_once_the_check_is_over(tiny_config):
    from kmamiz_tpu.models import graphsage as model, trainer

    full = mesh_history.generate(tiny_config, 5)

    def call(dataset):
        assert ref_train.compiled.cache_info().currsize <= 1  # one family, one weight, one rate
        return trainer.train(dataset, epochs=1, hidden=64, lr=1e-2, seed=5, model=model)

    assert check.against_reference(tiny_config, lambda n: mesh_history.head(full, n), MIX, 5, model, call).ok
    assert ref_train.compiled.cache_info().currsize == 0
