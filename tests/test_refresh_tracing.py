"""The model refresh on the program's own tracer (telemetry/tracing.py):
`trainer.train` and `stacked.stack_dataset` record `refresh.*` spans and
counts into the trace ring, the same spans are `TraceAnnotation`s in a
profiler capture, the epoch-block programs carry the name of their
registry entry, and the registry keeps a measured run time."""
from __future__ import annotations

import glob
import os
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from kmamiz_tpu.core import programs
from kmamiz_tpu.models import graphsage, stacked, trainer
from kmamiz_tpu.telemetry import REGISTRY, TRACER
from kmamiz_tpu.telemetry.profiling import events, report
from kmamiz_tpu.telemetry.tracing import PHASES, operation_span, phase_span

FUSED_CHILDREN = [
    "refresh.init",
    "refresh.pos_weight",
    "refresh.stack",
    "refresh.epoch_block",
    "refresh.loss_fetch",
]


def _dataset(n_nodes=16, n_edges=24, n_slots=5, seed=0):
    rng = np.random.default_rng(seed)
    return trainer.GraphDataset(
        endpoint_names=[f"ep{i}" for i in range(n_nodes)],
        src=jnp.asarray(rng.integers(0, n_nodes, n_edges, dtype=np.int32)),
        dst=jnp.asarray(rng.integers(0, n_nodes, n_edges, dtype=np.int32)),
        edge_mask=jnp.ones(n_edges, dtype=bool),
        features=[
            jnp.asarray(
                rng.normal(size=(n_nodes, graphsage.NUM_FEATURES)).astype(np.float32)
            )
            for _ in range(n_slots)
        ],
        target_latency=[
            jnp.asarray(rng.normal(size=n_nodes).astype(np.float32))
            for _ in range(n_slots)
        ],
        target_anomaly=[
            jnp.asarray((rng.random(n_nodes) < 0.2).astype(np.float32))
            for _ in range(n_slots)
        ],
        node_mask=[jnp.asarray(rng.random(n_nodes) < 0.9) for _ in range(n_slots)],
        slot_keys=[f"s{i}" for i in range(n_slots)],
    )


def _train(dataset, **kw):
    kw.setdefault("epochs", 2)
    kw.setdefault("hidden", 8)
    return trainer.train(dataset, **kw)


def _children(tb, parent=0):
    return [(i, s) for i, s in enumerate(tb.spans) if s[3] == parent and i != parent]


def _names(tb, parent=0):
    return [s[0] for _i, s in _children(tb, parent)]


def _assert_nested(tb):
    """Every span lies inside its parent, and no parent's direct children
    outlast it (self time >= 0)."""
    for i, (name, start, dur, parent) in enumerate(tb.spans):
        assert dur >= 0, name
        if parent >= 0:
            _pn, pstart, pdur, _pp = tb.spans[parent]
            assert pstart <= start and start + dur <= pstart + pdur, name
        covered = sum(s[2] for _j, s in _children(tb, i))
        assert dur - covered >= 0, name


def _counter(name):
    for line in REGISTRY.render().splitlines():
        if line.startswith(name + " "):
            return float(line.split()[-1])
    raise AssertionError(f"{name} is not in /metrics")


def _epoch_block_program(dataset):
    st = stacked.stack_dataset(dataset)
    del st
    names = [
        n for n, p in programs.all_programs().items()
        if n.startswith("models.sage_epoch_block[") and p.calls
    ]
    assert names
    # the block the dataset's refresh ran last: another test in this process may have registered an instance after it
    last_end = lambda p: max((end for end, _ms, _units in p.recent_runs()), default=float("-inf"))  # noqa: E731
    return max((programs.get(n) for n in names), key=last_end)


class TestRefreshTrace:
    def test_fused_train_alone_is_one_trace_rooted_at_refresh_train(self):
        ds = _dataset()
        _train(ds)
        traces = TRACER.traces()
        assert len(traces) == 1
        tb = traces[0]
        assert tb.spans[0][0] == "refresh.train" and tb.spans[0][3] == -1
        assert _names(tb) == FUSED_CHILDREN
        stack_idx = [i for i, s in _children(tb) if s[0] == "refresh.stack"][0]
        assert _names(tb, stack_idx) == [  # the plan first: it says where the node axis is cut
            "refresh.stack.plan",
            "refresh.stack.host_fill",
            "refresh.stack.device_put",
        ]
        _assert_nested(tb)
        st_nodes = stacked.stack_dataset(ds).bucket_nodes
        assert tb.counts[0] == {
            "model": "graphsage", "loss": "mse+bce", "epochs": 2, "slots": 5, "batch_slots": 1, "fused": 1,
            "shards": 1, "nodes_per_shard": st_nodes, "layout": "device",
        }
        by_name = {tb.spans[i][0]: c for i, c in tb.counts.items()}
        assert by_name["refresh.pos_weight"]["slots"] == 5
        assert 1.0 <= by_name["refresh.pos_weight"]["value"] <= 20.0
        assert by_name["refresh.stack"]["hit"] == 0
        st = stacked.stack_dataset(ds)
        want = sum(
            int(np.asarray(a).nbytes)
            for a in (st.features, st.target_latency, st.target_anomaly,
                      st.node_mask, st.src, st.dst, st.edge_mask)
        )
        assert by_name["refresh.stack"]["bytes"] == want
        assert by_name["refresh.stack.host_fill"]["bytes"] == want
        assert by_name["refresh.epoch_block"] == {
            "epochs": 2, "slot_updates": 10, "planned": 1,
            "slot_group": 5,  # 128 // 10 features is 12, and there are five slots
        }
        real_edges = int(np.asarray(st.edge_mask).sum())
        assert by_name["refresh.stack"]["plan_entries"] == 2 * real_edges
        assert by_name["refresh.stack.plan"]["entries"] == 2 * real_edges
        assert by_name["refresh.stack"]["plan_items"] == st.plan_items >= 1
        assert by_name["refresh.stack"]["plan_blocks"] == st.plan_blocks >= 1
        assert by_name["refresh.stack.plan"]["blocks"] == st.plan_blocks <= st.plan_items

    def test_second_refresh_hits_the_memoised_stack(self):
        ds = _dataset()
        _train(ds)
        _train(ds)
        tb = TRACER.traces()[-1]
        assert _names(tb) == FUSED_CHILDREN
        stack_idx = [i for i, s in _children(tb) if s[0] == "refresh.stack"][0]
        st = stacked.stack_dataset(ds)
        assert _names(tb, stack_idx) == [] and tb.counts[stack_idx] == {
            "hit": 1, "plan_entries": st.plan_entries, "plan_items": st.plan_items,
            "plan_blocks": st.plan_blocks, "plan_runs": st.plan_runs,
        }

    def test_train_inside_a_tick_nests_under_it_and_opens_no_second_trace(self):
        ds = _dataset()
        with TRACER.tick():
            with phase_span("merge"):
                pass
            _train(ds)
        traces = TRACER.traces()
        assert len(traces) == 1
        tb = traces[0]
        assert tb.spans[0][0] == "dp-tick"
        assert _names(tb) == ["merge", "refresh.train"]
        train_idx = [i for i, s in _children(tb) if s[0] == "refresh.train"][0]
        assert _names(tb, train_idx) == FUSED_CHILDREN
        _assert_nested(tb)
        # as a child the refresh is no root event of the ring: the tick is
        # the denominator, the refresh's phases explain it
        ring = [e[0] for e in events.snapshot()]
        assert "refresh.train" not in ring and "refresh.pos_weight" in ring

    def test_checkpoint_dir_adds_resume_and_checkpoint_save(self, tmp_path):
        ds = _dataset()
        _train(ds, epochs=2, checkpoint_dir=str(tmp_path), checkpoint_every=1)
        tb = TRACER.traces()[-1]
        assert _names(tb) == [
            "refresh.init", "refresh.resume", "refresh.pos_weight", "refresh.stack",
            "refresh.epoch_block", "refresh.loss_fetch", "refresh.checkpoint_save",
            "refresh.epoch_block", "refresh.loss_fetch", "refresh.checkpoint_save",
        ]
        by_name = [(tb.spans[i][0], c) for i, c in sorted(tb.counts.items())]
        assert ("refresh.resume", {"resumed_from": 0}) in by_name
        assert [c for n, c in by_name if n == "refresh.checkpoint_save"] == [
            {"step": 1}, {"step": 2},
        ]
        _assert_nested(tb)
        _train(ds, epochs=3, checkpoint_dir=str(tmp_path), checkpoint_every=1)
        tb = TRACER.traces()[-1]
        resumed = [c for i, c in tb.counts.items() if tb.spans[i][0] == "refresh.resume"]
        assert resumed == [{"resumed_from": 2}]
        assert _names(tb).count("refresh.epoch_block") == 1

    def test_legacy_loop_records_one_span_an_epoch(self):
        ds = _dataset()
        _train(ds, epochs=3, fused=False)
        tb = TRACER.traces()[-1]
        assert _names(tb) == [
            "refresh.init", "refresh.pos_weight",
            "refresh.legacy_epoch", "refresh.legacy_epoch", "refresh.legacy_epoch",
        ]
        assert tb.counts[0]["fused"] == 0
        assert [c for i, c in tb.counts.items() if tb.spans[i][0] == "refresh.legacy_epoch"] == [
            {"slots": 5}
        ] * 3
        _assert_nested(tb)

    def test_stack_dataset_alone_is_a_trace_of_its_own(self):
        ds = _dataset()
        stacked.stack_dataset(ds)
        stacked.stack_dataset(ds)
        build, hit = TRACER.traces()
        assert [s[0] for s in build.spans] == [
            "refresh.stack", "refresh.stack.plan", "refresh.stack.host_fill",
            "refresh.stack.device_put",
        ]
        assert [s[0] for s in hit.spans] == ["refresh.stack"]
        assert build.counts[0]["hit"] == 0 and hit.counts[0]["hit"] == 1
        assert _counter("kmamiz_model_stack_builds_total") == 1
        assert _counter("kmamiz_model_stack_hits_total") == 1
        assert _counter("kmamiz_model_edge_plan_builds_total") == 1
        assert _counter("kmamiz_model_edge_plan_hits_total") == 1

    def test_telemetry_off_records_nothing_and_trains_the_same(self, monkeypatch):
        ds = _dataset()
        on = _train(ds)
        assert len(TRACER.traces()) == 1
        monkeypatch.setenv("KMAMIZ_TELEMETRY", "0")
        off = _train(_dataset())
        assert len(TRACER.traces()) == 1  # nothing new
        assert off.losses == on.losses
        assert off.latency_losses == on.latency_losses

    def test_refresh_names_have_histograms_and_the_program_avoids_the_benchmarks_name(self):
        wanted = {
            "refresh.train", "refresh.init", "refresh.resume", "refresh.pos_weight",
            "refresh.stack", "refresh.stack.plan", "refresh.stack.host_fill",
            "refresh.stack.device_put", "refresh.epoch_block", "refresh.loss_fetch", "refresh.checkpoint_save",
            "refresh.legacy_epoch",
        }
        assert wanted <= set(PHASES)
        assert "refresh.call" not in PHASES  # the benchmark's window marker
        _train(_dataset())
        text = REGISTRY.render()
        for phase in FUSED_CHILDREN + ["refresh.train"]:
            assert f'kmamiz_tick_span_ms_count{{phase="{phase}"}} 1' in text, phase

    def test_counts_ride_the_zipkin_export_as_tags(self):
        _train(_dataset())
        (group,) = TRACER.export_zipkin()
        root = [s for s in group if s["parentId"] is None][0]
        assert root["localEndpoint"]["serviceName"] == "refresh-train"
        assert root["tags"]["kmamiz.model"] == "graphsage"
        assert root["tags"]["kmamiz.slots"] == "5"
        block = [s for s in group if s["localEndpoint"]["serviceName"] == "refresh-epoch-block"][0]
        assert block["tags"]["kmamiz.slot_updates"] == "10"


class TestOperationSpan:
    def test_root_then_child(self):
        with operation_span("refresh.train"):
            with operation_span("refresh.stack"):
                pass
        (tb,) = TRACER.traces()
        assert [(s[0], s[3]) for s in tb.spans] == [("refresh.train", -1), ("refresh.stack", 0)]

    def test_a_raising_body_still_files_the_trace(self):
        with pytest.raises(RuntimeError):
            with operation_span("refresh.train"):
                with phase_span("refresh.init"):
                    raise RuntimeError("boom")
        (tb,) = TRACER.traces()
        assert [s[0] for s in tb.spans] == ["refresh.train", "refresh.init"]
        assert all(s[2] >= 0 for s in tb.spans)
        assert TRACER.current() is None

    def test_a_refresh_on_another_thread_keeps_its_events_apart_from_the_ticks(self):
        """`note_tick_start`'s tick id is process-global: a refresh that
        made its own id the current one would tag the events of a tick
        running meanwhile. It takes an id without publishing it, and each
        trace's events carry their builder's id."""
        assert {"refresh.train", "refresh.stack"} <= set(events.ROOT_EVENTS)
        started, release = threading.Event(), threading.Event()

        def refresh():
            with operation_span("refresh.train"):
                with phase_span("refresh.pos_weight"):
                    started.set()
                    assert release.wait(10)

        t = threading.Thread(target=refresh)
        with TRACER.tick() as tick_tb:
            current = events._cur_tick
            t.start()
            assert started.wait(10)
            assert events._cur_tick == current  # the refresh did not move it
            with phase_span("merge"):
                pass
            release.set()
            t.join(10)
            assert not t.is_alive()
            with phase_span("scorers"):
                pass
        refresh_tb = [tb for tb in TRACER.traces() if tb.spans[0][0] == "refresh.train"][0]
        assert refresh_tb.tick_id != tick_tb.tick_id
        ids = {name: tick for name, tick, _end, _dur in events.snapshot()}
        assert ids["refresh.train"] == ids["refresh.pos_weight"] == refresh_tb.tick_id
        assert ids["merge"] == ids["scorers"] == ids["dp-tick"] == tick_tb.tick_id
        profile = report.build_profile()
        assert profile["ticks"] == 2  # two denominators: the tick and the refresh

    def test_a_refresh_alone_is_attributed_by_its_phases(self):
        _train(_dataset())
        profile = report.build_profile()
        assert profile["ticks"] == 1
        assert "refresh.train" in profile["phases"]
        assert profile["attribution_ratio"] > 0.9


class TestProfilerSession:
    def test_refresh_spans_lie_nested_in_the_host_plane(self, tmp_path):
        """Under a jax.profiler session the tracer's spans are in the
        xplane's host plane (so on the device's clock), nested as in the
        ring."""
        from jax.profiler import ProfileData

        ds = _dataset()
        _train(ds)  # compile outside the capture
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        options.host_tracer_level = 2
        jax.profiler.start_trace(str(tmp_path), profiler_options=options)
        try:
            _train(ds)
        finally:
            jax.profiler.stop_trace()
        (path,) = glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"), recursive=True)
        found = {}
        for plane in ProfileData.from_file(path).planes:
            if not plane.name.startswith("/host:"):
                continue
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith("refresh."):
                        found.setdefault(ev.name, []).append(
                            (ev.start_ns, ev.start_ns + ev.duration_ns)
                        )
        tb = TRACER.traces()[-1]
        assert sorted(found) == sorted({s[0] for s in tb.spans})
        assert all(len(v) == 1 for v in found.values())
        for name, _start, _dur, parent in tb.spans[1:]:
            inner, outer = found[name][0], found[tb.spans[parent][0]][0]
            assert outer[0] <= inner[0] and inner[1] <= outer[1], name


class TestProgramNames:
    @pytest.mark.parametrize("base", ["sage_epoch_block", "sage_dp_epoch_block", "batched_forward"])
    def test_the_device_module_is_named_after_the_registry_entry(self, base):
        ds = _dataset()
        st = stacked.stack_dataset(ds)
        params = graphsage.init_params(
            jax.random.PRNGKey(0), hidden=8, num_features=graphsage.NUM_FEATURES, num_nodes=0
        )
        opt_state = graphsage.make_optimizer(1e-2).init(params)
        edges = (st.src, st.dst, st.edge_mask)
        if base == "sage_epoch_block":
            prog = stacked.epoch_runner(graphsage, 1e-2, 3.0)
            args = (params, opt_state, st.features, st.target_latency,
                    st.target_anomaly, st.node_mask, *edges)
            lowered = prog.lower(*args, n_epochs=1)
        elif base == "sage_dp_epoch_block":
            prog = stacked.dp_epoch_runner(graphsage, 1e-2, 3.0)
            args = (params, opt_state, *stacked.batch_slots_arrays(st, 2), *edges)
            lowered = prog.lower(*args, n_epochs=1)
        else:
            prog = stacked._batched_forward(graphsage)
            lowered = prog.lower(params, st.features, *edges)
        assert prog.name.startswith(f"models.{base}[")
        assert f"module @jit_{base} " in lowered.as_text()


class TestRegistryRunTime:
    def test_one_run_per_block_and_the_counters_advance(self):
        ds = _dataset()
        _train(ds, epochs=1)  # compiles; a first run of its own
        prog = _epoch_block_program(ds)
        before = prog.stats()
        blocks0 = _counter("kmamiz_model_refresh_epoch_blocks_total")
        slots0 = _counter("kmamiz_model_refresh_slot_updates_total")
        calls0 = _counter("kmamiz_model_refresh_total")
        _train(ds, epochs=1)
        _train(ds, epochs=1)
        after = prog.stats()
        assert after["runs"] == before["runs"] + 2
        assert after["runMs"] > before["runMs"]
        assert after["lastRunMs"] > 0
        last_two = prog.recent_runs()[-2:]
        assert [u for _end, _ms, u in last_two] == [5, 5]
        assert abs(sum(ms for _e, ms, _u in last_two) - (after["runMs"] - before["runMs"])) < 0.01
        # the run is the dispatch AND the wait for the losses: never less
        # than the two spans the trainer puts around them
        tb = TRACER.traces()[-1]
        spans = {s[0]: s[2] for s in tb.spans}
        covered_ms = (spans["refresh.epoch_block"] + spans["refresh.loss_fetch"]) / 1e6
        assert after["lastRunMs"] >= covered_ms
        # runEwmaMs is still the wall of a warm dispatch, which note_run
        # does not touch
        assert after["runEwmaMs"] > 0
        ewma = prog.run_ewma_ms
        prog.note_run(1234.0, 7)
        assert prog.run_ewma_ms == ewma and prog.stats()["runs"] == after["runs"] + 1
        assert _counter("kmamiz_model_refresh_epoch_blocks_total") == blocks0 + 2
        assert _counter("kmamiz_model_refresh_slot_updates_total") == slots0 + 10
        assert _counter("kmamiz_model_refresh_total") == calls0 + 2

    def test_blocks_between_checkpoints_are_runs_of_their_own(self, tmp_path):
        ds = _dataset(seed=3)
        _train(ds, epochs=1)
        prog = _epoch_block_program(ds)
        runs = prog.stats()["runs"]
        _train(ds, epochs=4, checkpoint_dir=str(tmp_path), checkpoint_every=2)
        assert prog.stats()["runs"] == runs + 2
        assert [u for _e, _ms, u in prog.recent_runs()[-2:]] == [10, 10]
