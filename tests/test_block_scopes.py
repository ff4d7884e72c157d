"""Inside the one dispatch: the epoch block names its stretches with
`jax.named_scope` (one taxonomy, `programs.SCOPE_PHASES`; docs/OBSERVABILITY.md),
the registry reads them back from the compiled block (`Program.scope_table`),
and a slow call leaves a record (`trainer._slow_call`). On the CPU, at toy
sizes: the four heads on one device, GraphSAGE with node embeddings (no slot
group), and GraphSAGE over four of the host's devices."""
from __future__ import annotations

import logging
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from kmamiz_tpu.core import programs
from kmamiz_tpu.models import gat, graphsage, pna, stacked, trainer
from kmamiz_tpu.models.stlgt import model as stlgt_model
from kmamiz_tpu.parallel import mesh as mesh_mod
from kmamiz_tpu.telemetry.tracing import TRACER, operation_span, phase_span

#: head -> (module, node embeddings, shards, the phases its block has)
EVERY = {"gather", "reduce", "dense", "loss", "optimizer"}
BLOCKS = {
    "graphsage": (graphsage, False, 1, EVERY | {"group"}),
    "graphsage_embedding": (graphsage, True, 1, EVERY),
    "gat": (gat, False, 1, EVERY),
    "pna": (pna, False, 1, EVERY),
    "stlgt": (stlgt_model, False, 1, EVERY),
    "graphsage_nodes4": (graphsage, False, 4, EVERY | {"group", "collective"}),
}


def _dataset(n=600, e=2500, slots=9, width=18, seed=0):
    rng = np.random.default_rng(seed)
    src = rng.integers(0, n, e).astype(np.int32)
    dst = ((src + 1 + rng.integers(0, n - 1, e)) % n).astype(np.int32)
    keep = np.unique(src.astype(np.int64) * n + dst, return_index=True)[1]
    src, dst = src[keep], dst[keep]
    return trainer.GraphDataset(
        endpoint_names=[f"ep{i}" for i in range(n)], src=src, dst=dst, edge_mask=np.ones(len(src), bool),
        features=[rng.normal(size=(n, width)).astype(np.float32) for _ in range(slots)],
        target_latency=[rng.normal(size=n).astype(np.float32) for _ in range(slots)],
        target_anomaly=[(rng.random(n) < 0.1).astype(np.float32) for _ in range(slots)],
        node_mask=[rng.random(n) < 0.95 for _ in range(slots)],
        slot_keys=[f"s{i}" for i in range(slots)],
    )


_TABLES: dict = {}


def _table(head: str):
    """The scope table of `head`'s toy block, after ONE call of it (made
    once a process: the cases of a head share it)."""
    if head in _TABLES:
        return _TABLES[head]
    model, embeddings, shards, _phases = BLOCKS[head]
    real_rule = mesh_mod.node_shards
    mesh_mod.node_shards = lambda nbytes: shards  # the layout is the data's: a toy says so by the rule's reading
    try:
        ds = _dataset()
        st = stacked.stack_dataset(ds)
        params = model.init_params(
            jax.random.PRNGKey(0), hidden=16, num_features=18, num_nodes=ds.num_nodes if embeddings else 0
        )
        runner = stacked.runner_for(st, model, 1e-2, 9.5)
        out = runner(
            params, model.make_optimizer(1e-2).init(params), st.features, st.target_latency, st.target_anomaly,
            st.node_mask, st.src, st.dst, st.edge_mask, 1, stacked.plan_for(model, st),
        )
        assert np.isfinite(np.asarray(out[2])).all()
        _TABLES[head] = runner.scope_table()
    finally:
        mesh_mod.node_shards = real_rule
        stacked.node_sharded_epoch_runner.cache_clear()
    return _TABLES[head]


@pytest.mark.parametrize("head", sorted(BLOCKS))
class TestTheBlocksScopeTable:
    def test_holds_every_phase_the_head_has_and_no_other(self, head):
        table = _table(head)
        assert table
        assert {phase for _path, phase, _backward in table.values()} - {None} == BLOCKS[head][3]

    def test_marks_the_backward_pass(self, head):
        table = _table(head)
        backward = {phase for _path, phase, is_backward in table.values() if is_backward}
        forward = {phase for _path, phase, is_backward in table.values() if not is_backward}
        # the reductions' VJP rules open their scopes themselves; the optimizer has no backward pass
        assert {"reduce", "dense"} <= backward and "optimizer" not in backward
        assert {"reduce", "dense", "loss", "optimizer"} <= forward

    def test_leaves_under_five_percent_of_the_instructions_without_a_phase(self, head):
        table = _table(head)
        unscoped = [name for name, (_path, phase, _backward) in table.items() if phase is None]
        assert len(unscoped) < 0.05 * len(table), unscoped

    def test_names_the_head_above_the_phase(self, head):
        root = BLOCKS[head][0].__name__.rsplit(".", 1)[-1].replace("model", "stlgt")
        paths = {path for path, _phase, _backward in _table(head).values()}
        assert any(path.startswith(f"{root}/") for path in paths), sorted(paths)
        assert {"loss", "optimizer"} <= paths  # the block's own stretches stand at the root


@pytest.mark.parametrize(
    "op_name,want",
    [
        ("transpose(jvp(a/b/gather))/mul", ("a/b/gather", "gather", True)),  # older JAX: the whole path wrapped
        ("jit(sage_epoch_block)/while/body/closed_call/transpose(jvp(graphsage))/layer2/dense/reduce/pallas_call",
         ("graphsage/layer2/dense/reduce", "reduce", True)),
        ("jit(sage_epoch_block)/while/body/closed_call/jvp(graphsage)/layer2/dense/dot_general",
         ("graphsage/layer2/dense", "dense", False)),
        ("jit(sage_epoch_block)/shard_map/while/body/closed_call/loss/collective/psum", ("loss/collective", "collective", False)),
        ("jit(f)/while/body/closed_call/group/gather/jit(_take)/gather", ("group/gather", "gather", False)),
        ("jit(f)/jvp(stlgt)/ffn/dense/jit(relu)/max", ("stlgt/ffn/dense", "dense", False)),
        ("jit(f)/while/body/closed_call/optimizer/vmap(jit(inner))/mul", ("optimizer", "optimizer", False)),
        ("jit(f)/while/body/closed_call/elsewhere/gather", ("elsewhere", None, False)),  # the primitive is no scope
        ("jit(f)/while/body/dynamic_slice", ("", None, False)),
        ("", ("", None, False)),
    ],
)
def test_an_op_name_gives_its_scope_its_phase_and_its_direction(op_name, want):
    assert programs.scope_of(op_name) == want


HLO = """HloModule jit_f, is_scheduled=true

%fused_computation (param_0.1: f32[8]) -> f32[8] {
  %param_0.1 = f32[8]{0} parameter(0)
  ROOT %add.1 = f32[8]{0} add(%param_0.1, %param_0.1), metadata={op_name="jit(f)/while/body/closed_call/head/dense/add" source_file="x.py" source_line=3}
}

%region_0.2 (a: f32[], b: f32[]) -> f32[] {
  %a = f32[] parameter(0)
  %b = f32[] parameter(1)
  ROOT %add.2 = f32[] add(%a, %b), metadata={op_name="jit(f)/while/body/closed_call/loss/reduce_sum"}
}

%body (p: (s32[], f32[8])) -> (s32[], f32[8]) {
  %p = (s32[], f32[8]{0}) parameter(0)
  %get-tuple-element.1 = s32[] get-tuple-element(%p), index=0
  %constant.1 = s32[] constant(1)
  %add.3 = s32[] add(%get-tuple-element.1, %constant.1), metadata={op_name="jit(f)/while/body/add"}
  %get-tuple-element.2 = f32[8]{0} get-tuple-element(%p), index=1
  %copy.1 = f32[8]{0} copy(%get-tuple-element.2)
  %fusion.1 = f32[8]{0} fusion(%copy.1), kind=kLoop, calls=%fused_computation, metadata={op_name="jit(f)/while/body/closed_call/head/dense/add" source_file="x.py" source_line=3}
  %reduce.1 = f32[] reduce(%fusion.1, %constant.0), dimensions={0}, to_apply=%region_0.2, metadata={op_name="jit(f)/while/body/closed_call/transpose(jvp(loss))/reduce_sum"}
  %broadcast.1 = f32[8]{0} broadcast(%constant.0), dimensions={}, metadata={op_name="jit(f)/while/body/closed_call"}
  %all-gather.1 = f32[32]{0} all-gather(%fusion.1), dimensions={0}, metadata={op_name="jit(f)/while/body/closed_call/head/dense/collective/all_gather"}
  %custom-call.1 = f32[8]{0} custom-call(%fusion.1), custom_call_target="tpu_custom_call", metadata={op_name="jit(f)/while/body/closed_call/stray/pallas_call"}
  ROOT %tuple.1 = (s32[], f32[8]{0}) tuple(%add.3, %fusion.1)
}

ENTRY %main (x: f32[8]) -> f32[8] {
  %x = f32[8]{0} parameter(0)
  %while.1 = (s32[], f32[8]{0}) while(%tuple.0), condition=%cond, body=%body, metadata={op_name="jit(f)/while"}
  ROOT %get-tuple-element.3 = f32[8]{0} get-tuple-element(%while.1), index=1
}
"""


def test_a_table_holds_what_a_trace_times_and_the_program_traced():
    """No fusion's body, no reducer, no parameter, tuple, constant or control flow, nothing of a loop's own and
    nothing XLA put in without metadata; a fusion under its own metadata (its root's)."""
    assert programs.scope_table_of(HLO) == {
        "fusion.1": ("head/dense", "dense", False),
        "reduce.1": ("loss", "loss", True),
        "all-gather.1": ("head/dense/collective", "collective", False),
        "custom-call.1": ("stray", None, False),
    }


def _counting(jitted):
    """`jitted` behind a proxy that counts its lowerings."""
    lowered = []

    class Proxy:
        def __call__(self, *args, **kwargs):
            return jitted(*args, **kwargs)

        def __getattr__(self, item):
            return getattr(jitted, item)

        def lower(self, *args, **kwargs):
            lowered.append(1)
            return jitted.lower(*args, **kwargs)

    return Proxy(), lowered


class TestAbstractArguments:
    def test_kept_on_a_compiling_call_only(self):
        @jax.jit
        def fn(x, plan=None):
            with jax.named_scope("dense"):
                return x * 2 + (plan[0] if plan is not None else 0)

        prog = programs.register("test.scopes_kept", fn)
        assert prog.scope_table() == {}  # nothing has compiled: nothing is known
        prog(jnp.zeros((8,), jnp.float32), plan=(jnp.ones(()),))
        ((args, kwargs), _table), = prog._compiled_calls
        assert isinstance(args[0], jax.ShapeDtypeStruct) and args[0].shape == (8,)
        assert isinstance(kwargs["plan"][0], jax.ShapeDtypeStruct)  # a pytree the hint encoder would refuse or not
        kept = prog._compiled_calls[0]
        prog(jnp.ones((8,), jnp.float32), plan=(jnp.ones(()),))  # warm: the same signature, nothing kept anew
        assert prog._compiled_calls == [kept] and prog._compiled_calls[0] is kept and prog.compiles == 1
        prog(jnp.zeros((16,), jnp.float32))  # a new bucket compiles: one more signature, and the newest is scope_table's
        assert [call[0][0].shape for call, _table in prog._compiled_calls] == [(8,), (16,)] and prog.compiles == 2

    def test_every_compiled_signature_has_a_table_of_its_own(self):
        @jax.jit
        def fn(x, wide=None):
            with jax.named_scope("dense"):
                y = jnp.tanh(x)
            if wide is None:
                return y
            with jax.named_scope("loss"):
                return y.sum() + wide.sum()

        proxy, lowered = _counting(fn)
        prog = programs.register("test.scopes_signatures", proxy)
        prog(jnp.zeros((8,), jnp.float32))
        prog(jnp.zeros((8,), jnp.float32), wide=jnp.ones((4, 4)))
        assert prog.scope_table() is prog.scope_table() and len(lowered) == 1  # the newest alone: one lowering
        first, second = prog.scope_tables()  # oldest first; the newest's is not made again
        assert len(lowered) == 2 and second is prog.scope_table()
        assert {row[1] for row in first.values()} == {"dense"} and "loss" in {row[1] for row in second.values()}

    def test_a_signature_that_cannot_be_kept_loses_nothing_of_the_call(self, monkeypatch):
        prog = programs.register("test.scopes_unkept", jax.jit(lambda x: x + 1))
        monkeypatch.setattr(programs, "_abstract", lambda x: 1 / 0)
        assert float(prog(jnp.zeros(()))) == 1.0 and prog.compiles == 1 and prog.scope_table() == {}

    def test_static_arguments_stay_as_they_are(self):
        import functools

        @functools.partial(jax.jit, static_argnames=("n",))
        def fn(x, n):
            with jax.named_scope("dense"):
                return x * n

        prog = programs.register("test.scopes_static", fn)
        prog(jnp.zeros((4,), jnp.float32), n=3)
        assert prog._compiled_calls[0][0][1] == {"n": 3}
        assert {phase for _p, phase, _b in prog.scope_table().values()} == {"dense"}

    def test_scope_table_is_made_once_for_two_asks_and_never_before(self):
        @jax.jit
        def fn(x):
            with jax.named_scope("head/dense"):
                y = jnp.tanh(x)
            with jax.named_scope("loss"):
                return y.sum()

        proxy, lowered = _counting(fn)
        prog = programs.register("test.scopes_once", proxy)
        prog(jnp.zeros((8,), jnp.float32))
        prog(jnp.ones((8,), jnp.float32))
        assert not lowered  # nothing is computed until somebody asks
        first = prog.scope_table()
        assert prog.scope_table() is first and len(lowered) == 1
        # XLA may fuse the two stretches into one instruction, which then is its root's
        assert first and {row[:2] for row in first.values()} <= {("head/dense", "dense"), ("loss", "loss")}


def test_children_ms_says_where_the_open_span_went():
    assert TRACER.children_ms() == {}
    with operation_span("refresh.train"):
        with phase_span("refresh.init"):
            pass
        with phase_span("refresh.init"):
            pass
        with phase_span("refresh.pos_weight"):
            with phase_span("refresh.stack"):  # a grandchild: its parent's
                pass
        spent = TRACER.children_ms()
    assert set(spent) == {"refresh.init", "refresh.pos_weight", "self"}
    assert all(ms >= 0 for ms in spent.values())


class TestSlowCall:
    @staticmethod
    def _program(*previous_ms, units=432):
        return SimpleNamespace(
            name="models.sage_epoch_block[fake|0.01|10.0]",
            recent_runs=lambda: [(float(i), ms, units) for i, ms in enumerate(previous_ms)],
        )

    @pytest.mark.parametrize("run_ms,slow", [(140.0, True), (110.0, False), (125.0, False), (126.0, True)])  # 126: over both
    def test_a_run_over_the_ratio_logs_one_line_and_counts_one(self, caplog, monkeypatch, tmp_path, run_ms, slow):
        monkeypatch.setenv("KMAMIZ_PROF_FLIGHT_DIR", str(tmp_path))
        monkeypatch.setenv("KMAMIZ_PROF_FLIGHT_DEBOUNCE_S", "0")
        before = trainer._SLOW_CALLS.value
        with caplog.at_level(logging.WARNING, logger="kmamiz_tpu.models.trainer"):
            with operation_span("refresh.train"):
                with phase_span("refresh.pos_weight"):
                    pass
                trainer._slow_call(self._program(100.0, 101.0, 99.0), run_ms, 432, trainer._call_start())
        lines = [r.getMessage() for r in caplog.records if r.name == "kmamiz_tpu.models.trainer"]
        assert len(lines) == int(slow) and trainer._SLOW_CALLS.value - before == int(slow)
        flights = list(tmp_path.glob("flight-*refresh-slow-call.json"))
        assert len(flights) == int(slow)
        # the record's own cost (the artifact is a file) lies in a phase of the refresh, as every millisecond of one does
        spans = [name for name, _start, _dur, parent in TRACER.traces()[-1].spans if parent == 0]
        assert spans == ["refresh.pos_weight"] + ["refresh.slow_call"] * int(slow)
        if slow:
            for field in ("refresh.pos_weight", "'self'", "compiles={}", "gc_collections=[", "bytes_in_use=", "1.40 times"):
                assert field in lines[0] or run_ms != 140.0, (field, lines[0])

    def test_fewer_than_three_previous_runs_of_its_size_say_nothing(self, caplog):
        with caplog.at_level(logging.WARNING, logger="kmamiz_tpu.models.trainer"):
            trainer._slow_call(self._program(100.0, 101.0), 500.0, 432, trainer._call_start())
            # a toy's milliseconds jitter by more than a quarter: under SLOW_CALL_MIN_EXCESS_MS over the median, nothing
            trainer._slow_call(self._program(4.0, 4.1, 3.9), 20.0, 432, trainer._call_start())
            # three runs, but of another size (the check's three-slot heads): no yardstick for this one
            trainer._slow_call(self._program(1.0, 1.0, 1.0, units=3), 500.0, 432, trainer._call_start())
        assert not caplog.records

    def test_train_checks_every_fused_run(self, monkeypatch):
        seen = []
        monkeypatch.setattr(trainer, "_slow_call", lambda runner, run_ms, units, start: seen.append((runner.name, units)))
        trainer.train(_dataset(n=60, e=200, slots=3), epochs=2, hidden=8, seed=1)
        assert len(seen) == 1 and seen[0][1] == 6 and seen[0][0].startswith("models.sage_epoch_block[")


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    try:
        return SingleDeviceSharding(topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2").devices[0])
    except Exception as e:  # noqa: BLE001 - no TPU compiler here: nothing to check
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


#: head -> (module, the Mosaic kernels a compiled block holds: forward and backward)
KERNELS = {"graphsage": (graphsage, 3), "gat": (gat, 10), "pna": (pna, 6), "stlgt": (stlgt_model, 2)}


@pytest.mark.parametrize("head", sorted(KERNELS))
def test_the_chips_own_program_reads_under_the_programs_names(one_chip, head):
    """The one-chip cells' blocks as the chip's compiler makes them (a described v5e, the cells' shapes): every
    Mosaic kernel under phase `reduce` with its own name on its path, the row gathers under `gather`, every phase
    the head has, and under 5% of the table's instructions without one. (The node-sharded block's table, with its
    collectives: `tests/test_edge_plan.py::test_node_sharded_block_compiles_for_the_v5e_host_at_the_cells_shapes`.)"""
    from jax.experimental.compilation_cache import compilation_cache

    from kmamiz_tpu.ops import sparse

    model, kernels = KERNELS[head]
    slots, nb, eb, width = 432, 131072, 524288, 18
    entries, _tiles, items = sparse.plan_shapes(nb, eb)

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    plan = sparse.EdgePlan(
        owner=arg((1, entries), jnp.int32), neighbour=arg((entries,), jnp.int32), degree=arg((nb,), jnp.float32),
        item_tile=arg((items,), jnp.int32), item_block=arg((items,), jnp.int32), item_flag=arg((items,), jnp.int32),
        direction=arg((1, entries), jnp.int32), mean_log_degree=arg((), jnp.float32),
    )
    params = jax.eval_shape(lambda: model.init_params(jax.random.PRNGKey(0), hidden=64, num_features=width))
    opt_state = jax.eval_shape(lambda p: model.make_optimizer(1e-2).init(p), params)
    whole = lambda tree: jax.tree_util.tree_map(lambda a: arg(a.shape, a.dtype), tree)  # noqa: E731
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)  # a described chip reads none back
    compilation_cache.reset_cache()
    real_impl, sparse.planned_impl = sparse.planned_impl, lambda: "pallas"  # `jax.default_backend()` sees the CPU
    try:
        text = stacked.epoch_runner(model, 1e-2, 10.0).fn.lower(
            whole(params), whole(opt_state), arg((slots, nb, width), jnp.float32),
            arg((slots, nb), jnp.float32), arg((slots, nb), jnp.float32), arg((slots, nb), jnp.bool_),
            arg((eb,), jnp.int32), arg((eb,), jnp.int32), arg((eb,), jnp.bool_), 1, plan,
        ).compile().as_text()
    finally:
        sparse.planned_impl = real_impl
        stacked.epoch_runner.cache_clear()
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()
    table = programs.scope_table_of(text)
    walks = {name: row for name, row in table.items() if name.startswith("planned_")}
    assert len(walks) == kernels == text.count("tpu_custom_call")
    for name, (path, phase, _backward) in walks.items():
        assert phase == "reduce" and path.rsplit("/", 1)[-1] == name.split(".")[0], (name, path)
    phases = [phase for _path, phase, _backward in table.values()]
    assert set(phases) - {None} == BLOCKS[head][3]
    assert phases.count(None) < 0.05 * len(phases)
    assert sum(backward for _path, phase, backward in table.values() if phase == "gather") >= 1
