"""GraphSAGE trainer over simulator-generated fault windows
(SURVEY.md §7 step 7): dataset construction, training convergence, and
fault-window detection on held-out slots."""
from __future__ import annotations

import numpy as np
import pytest

from kmamiz_tpu.models import trainer
from kmamiz_tpu.simulator.simulator import Simulator

FAULT_YAML = """
servicesInfo:
  - namespace: mesh
    services:
      - serviceName: front
        versions:
          - version: v1
            replica: 2
            endpoints:
              - endpointId: front-get
                endpointInfo: { path: /front, method: get }
      - serviceName: mid
        versions:
          - version: v1
            replica: 1
            endpoints:
              - endpointId: mid-get
                endpointInfo: { path: /mid, method: get }
      - serviceName: back
        versions:
          - version: v1
            replica: 1
            endpoints:
              - endpointId: back-get
                endpointInfo: { path: /back, method: get }
endpointDependencies:
  - endpointId: front-get
    isExternal: true
    dependOn:
      - endpointId: mid-get
  - endpointId: mid-get
    dependOn:
      - endpointId: back-get
loadSimulation:
  config:
    simulationDurationInDays: 2
    overloadErrorRateIncreaseFactor: 3
  serviceMetrics: []
  endpointMetrics:
    - endpointId: front-get
      delay: { latencyMs: 20, jitterMs: 4 }
      errorRatePercent: 1
      expectedExternalDailyRequestCount: 4800
    - endpointId: mid-get
      delay: { latencyMs: 10, jitterMs: 2 }
      errorRatePercent: 1
    - endpointId: back-get
      delay: { latencyMs: 5, jitterMs: 1 }
      errorRatePercent: 1
  faultInjection:
    - type: increase-error-rate
      targets:
        services: []
        endpoints:
          - endpointId: back-get
      timePeriods:
        - startTime: { day: 1, hour: 6 }
          durationHours: 5
          probabilityPercent: 100
        - startTime: { day: 2, hour: 6 }
          durationHours: 5
          probabilityPercent: 100
      increaseErrorRatePercent: 80
"""


@pytest.fixture(scope="module")
def simulation():
    result = Simulator().generate_simulation_data(
        FAULT_YAML, 0.0, rng=np.random.default_rng(7)
    )
    assert result.validation_error_message == ""
    assert result.converting_error_message == ""
    return result


@pytest.fixture(scope="module")
def dataset(simulation):
    return trainer.dataset_from_simulation(
        simulation.endpoint_dependencies,
        simulation.realtime_data_per_slot,
        simulation.replica_counts,
    )


class TestDataset:
    def test_shapes(self, dataset):
        assert dataset.num_nodes == 3
        assert len(dataset.features) == 47  # 48 slots -> 47 (t, t+1) pairs
        assert dataset.features[0].shape == (3, trainer.graphsage.NUM_FEATURES)
        assert int(dataset.edge_mask.sum()) == 2  # front->mid, mid->back

    def test_fault_slots_labeled_anomalous(self, dataset):
        back = next(
            i for i, n in enumerate(dataset.endpoint_names) if "back" in n
        )
        by_slot = dict(zip(dataset.slot_keys, dataset.target_anomaly))
        # slot "0-5-0" predicts slot 0-6-0, inside the fault window
        assert float(by_slot["0-5-0"][back]) == 1.0
        assert float(by_slot["0-7-0"][back]) == 1.0
        # far from the fault window: clean
        assert float(by_slot["0-15-0"][back]) == 0.0

    def test_error_share_feature_reflects_fault(self, dataset):
        back = next(
            i for i, n in enumerate(dataset.endpoint_names) if "back" in n
        )
        by_slot = dict(zip(dataset.slot_keys, dataset.features))
        assert float(by_slot["0-7-0"][back][2]) > 0.5  # 5xx share during fault
        assert float(by_slot["0-15-0"][back][2]) < 0.1


class TestTraining:
    def test_loss_decreases_and_faults_detected(self, simulation):
        result, metrics, dataset = trainer.train_on_simulation(
            simulation.endpoint_dependencies,
            simulation.realtime_data_per_slot,
            simulation.replica_counts,
            train_fraction=0.5,  # day 1 trains, day 2 evaluates
            epochs=40,
            hidden=16,
            seed=0,
        )
        assert result.losses[-1] < result.losses[0]
        assert np.isfinite(result.losses[-1])
        # the held-out day-2 fault window must be detected better than chance
        assert metrics.anomaly_recall > 0.5, metrics
        assert metrics.anomaly_accuracy > metrics.anomaly_base_rate, metrics
        # the flagged endpoints are the faulted one (and its dependents)
        flagged = {n for names in metrics.per_slot_flagged.values() for n in names}
        assert any("back" in n for n in flagged)


class TestCheckpointResume:
    def test_save_restore_roundtrip(self, tmp_path):
        import jax
        import numpy as np

        from kmamiz_tpu.models import checkpoint, graphsage

        params = graphsage.init_params(jax.random.PRNGKey(3), hidden=16)
        optimizer = graphsage.make_optimizer()
        opt_state = optimizer.init(params)

        path = checkpoint.save_checkpoint(
            str(tmp_path), params, opt_state, step=7, metadata={"loss": 1.25}
        )
        assert path.endswith("step_7")
        assert checkpoint.latest_step(str(tmp_path)) == 7

        restored = checkpoint.restore_checkpoint(
            str(tmp_path), params, optimizer.init(params)
        )
        assert restored is not None
        r_params, r_opt, meta = restored
        assert int(meta["step"]) == 7
        assert float(meta["loss"]) == 1.25
        for a, b in zip(params, r_params):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

        # resumed training step runs
        step_fn = graphsage.make_train_step(optimizer)
        rng = np.random.default_rng(0)
        feats = jax.numpy.asarray(
            rng.normal(size=(32, graphsage.NUM_FEATURES)).astype(np.float32)
        )
        src = jax.numpy.asarray(rng.integers(0, 32, 64, dtype=np.int32))
        dst = jax.numpy.asarray(rng.integers(0, 32, 64, dtype=np.int32))
        mask = jax.numpy.ones(64, dtype=bool)
        tl = jax.numpy.asarray(rng.normal(size=32).astype(np.float32))
        ta = jax.numpy.zeros(32, dtype=jax.numpy.float32)
        nm = jax.numpy.ones(32, dtype=bool)
        out = step_fn(r_params, r_opt, feats, src, dst, mask, tl, ta, nm)
        assert np.isfinite(float(out[2]))

    def test_restore_empty_dir(self, tmp_path):
        from kmamiz_tpu.models import checkpoint

        import jax

        from kmamiz_tpu.models import graphsage

        params = graphsage.init_params(jax.random.PRNGKey(0), hidden=8)
        optimizer = graphsage.make_optimizer()
        assert (
            checkpoint.restore_checkpoint(
                str(tmp_path), params, optimizer.init(params)
            )
            is None
        )
        assert checkpoint.latest_step(str(tmp_path / "missing")) is None

    def test_multiple_steps_latest_wins(self, tmp_path):
        import jax

        from kmamiz_tpu.models import checkpoint, graphsage

        params = graphsage.init_params(jax.random.PRNGKey(1), hidden=8)
        optimizer = graphsage.make_optimizer()
        opt_state = optimizer.init(params)
        for s in (1, 5, 3):
            checkpoint.save_checkpoint(str(tmp_path), params, opt_state, step=s)
        assert checkpoint.latest_step(str(tmp_path)) == 5
        _, _, meta = checkpoint.restore_checkpoint(
            str(tmp_path), params, optimizer.init(params)
        )
        assert int(meta["step"]) == 5

    def test_train_resume_from_checkpoint(self, tmp_path):
        import numpy as np

        from kmamiz_tpu.models import checkpoint, trainer

        rng = np.random.default_rng(0)
        n_nodes, n_edges, n_slots = 16, 24, 2
        from kmamiz_tpu.models import graphsage
        import jax.numpy as jnp

        ds = trainer.GraphDataset(
            features=[
                jnp.asarray(rng.normal(size=(n_nodes, graphsage.NUM_FEATURES)).astype(np.float32))
                for _ in range(n_slots)
            ],
            src=jnp.asarray(rng.integers(0, n_nodes, n_edges, dtype=np.int32)),
            dst=jnp.asarray(rng.integers(0, n_nodes, n_edges, dtype=np.int32)),
            edge_mask=jnp.ones(n_edges, dtype=bool),
            target_latency=[
                jnp.asarray(rng.normal(size=n_nodes).astype(np.float32))
                for _ in range(n_slots)
            ],
            target_anomaly=[
                jnp.zeros(n_nodes, dtype=jnp.float32) for _ in range(n_slots)
            ],
            node_mask=[jnp.ones(n_nodes, dtype=bool) for _ in range(n_slots)],
            endpoint_names=[f"ep{i}" for i in range(n_nodes)],
            slot_keys=[f"s{i}" for i in range(n_slots)],
        )
        d = str(tmp_path / "ckpt")
        r1 = trainer.train(ds, epochs=4, hidden=8, checkpoint_dir=d, checkpoint_every=2)
        assert checkpoint.latest_step(d) == 4
        # resuming when fully trained is a no-op (no epochs left)
        r2 = trainer.train(ds, epochs=4, hidden=8, checkpoint_dir=d)
        assert r2.losses == []
        for a, b in zip(r1.params, r2.params):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        # a longer run continues from epoch 4
        r3 = trainer.train(ds, epochs=6, hidden=8, checkpoint_dir=d, checkpoint_every=2)
        assert len(r3.losses) == 2
        assert checkpoint.latest_step(d) == 6

    def test_resume_rejects_hyperparameter_mismatch(self, tmp_path):
        import jax
        import pytest

        from kmamiz_tpu.models import checkpoint, graphsage, trainer

        params = graphsage.init_params(jax.random.PRNGKey(0), hidden=8)
        optimizer = graphsage.make_optimizer()
        checkpoint.save_checkpoint(
            str(tmp_path), params, optimizer.init(params), step=2,
            metadata={
                "hidden": 8,
                "lr": 1e-2,
                "seed": 0,
                "model": "graphsage",
                "num_features": graphsage.NUM_FEATURES,
            },
        )
        ds = None  # train validates metadata before touching the dataset
        with pytest.raises(ValueError, match="hidden=8"):
            trainer.train(ds, epochs=4, hidden=16, checkpoint_dir=str(tmp_path))

    def test_resume_rejects_pre_upgrade_checkpoint(self, tmp_path):
        """Checkpoints saved before the 10-feature layout (no num_features
        in metadata) cannot restore into the current param tree; the
        rejection must be explicit, not an orbax shape error."""
        import jax
        import pytest

        from kmamiz_tpu.models import checkpoint, graphsage, trainer

        params = graphsage.init_params(jax.random.PRNGKey(0), hidden=8)
        optimizer = graphsage.make_optimizer()
        checkpoint.save_checkpoint(
            str(tmp_path), params, optimizer.init(params), step=2,
            metadata={"hidden": 8, "lr": 1e-2, "seed": 0},
        )
        with pytest.raises(ValueError, match="10-feature layout"):
            trainer.train(None, epochs=4, hidden=8, checkpoint_dir=str(tmp_path))

    def test_node_embeddings_opt_in_trains_and_resumes(self, tmp_path, simulation):
        """use_node_embeddings=True learns a per-node table and the
        checkpoint round-trips it (num_nodes validated in metadata)."""
        import numpy as np

        from kmamiz_tpu.models import trainer

        ds = trainer.dataset_from_simulation(
            simulation.endpoint_dependencies,
            simulation.realtime_data_per_slot,
            simulation.replica_counts,
        )
        result = trainer.train(
            ds,
            epochs=2,
            hidden=8,
            use_node_embeddings=True,
            checkpoint_dir=str(tmp_path),
        )
        emb = np.asarray(result.params.embedding)
        assert emb.shape == (ds.num_nodes, 8)
        # resuming with a different embedding setting is rejected
        import pytest

        with pytest.raises(ValueError, match="num_nodes"):
            trainer.train(ds, epochs=3, hidden=8, checkpoint_dir=str(tmp_path))
        # matching settings resume cleanly
        result2 = trainer.train(
            ds,
            epochs=3,
            hidden=8,
            use_node_embeddings=True,
            checkpoint_dir=str(tmp_path),
        )
        assert result2.params.embedding is not None

    def test_gat_checkpoint_restores_gat_params(self, tmp_path):
        """restore rebuilds the TEMPLATE's param type: a GAT checkpoint
        round-trips through GatParams, not SageParams."""
        import jax
        import numpy as np

        from kmamiz_tpu.models import checkpoint, gat

        params = gat.init_params(jax.random.PRNGKey(3), hidden=8)
        optimizer = gat.make_optimizer()
        opt_state = optimizer.init(params)
        checkpoint.save_checkpoint(
            str(tmp_path), params, opt_state, step=1, metadata={"model": "gat"}
        )
        restored = checkpoint.restore_checkpoint(
            str(tmp_path), params, opt_state, step=1
        )
        assert restored is not None
        r_params, _state, _meta = restored
        assert type(r_params) is gat.GatParams
        assert np.allclose(np.asarray(r_params.w_1), np.asarray(params.w_1))

    def test_stray_file_does_not_mask_checkpoints(self, tmp_path):
        import jax

        from kmamiz_tpu.models import checkpoint, graphsage

        params = graphsage.init_params(jax.random.PRNGKey(0), hidden=8)
        optimizer = graphsage.make_optimizer()
        checkpoint.save_checkpoint(str(tmp_path), params, optimizer.init(params), step=4)
        (tmp_path / "step_99").write_text("stray artifact, not a checkpoint")
        assert checkpoint.latest_step(str(tmp_path)) == 4
        restored = checkpoint.restore_checkpoint(
            str(tmp_path), params, optimizer.init(params)
        )
        assert restored is not None and int(restored[2]["step"]) == 4

    def test_incomplete_save_falls_back(self, tmp_path):
        """A checkpoint dir missing its metadata sidecar (crash mid-save)
        must not brick resume: the previous complete step wins; with no
        complete step, training starts fresh."""
        import os
        import jax

        from kmamiz_tpu.models import checkpoint, graphsage

        params = graphsage.init_params(jax.random.PRNGKey(0), hidden=8)
        optimizer = graphsage.make_optimizer()
        checkpoint.save_checkpoint(
            str(tmp_path), params, optimizer.init(params), step=2,
            metadata={"hidden": 8, "lr": 1e-2, "seed": 0},
        )
        checkpoint.save_checkpoint(
            str(tmp_path), params, optimizer.init(params), step=4,
            metadata={"hidden": 8, "lr": 1e-2, "seed": 0},
        )
        os.remove(str(tmp_path / "step_4.meta.json"))  # simulate the crash
        assert checkpoint.latest_step(str(tmp_path)) == 4
        assert checkpoint.latest_complete_step(str(tmp_path)) == 2
        os.remove(str(tmp_path / "step_2.meta.json"))
        assert checkpoint.latest_complete_step(str(tmp_path)) is None


def _last_refresh_spans():
    """Names of the spans of the newest trace rooted at `refresh.train`."""
    from kmamiz_tpu.telemetry.tracing import TRACER

    newest = [tb for tb in TRACER.traces() if tb.spans and tb.spans[0][0] == "refresh.train"]
    return {span[0] for span in newest[-1].spans}


def _synthetic_dataset(n_nodes=16, n_edges=24, n_slots=5, seed=0, anomaly=0.2):
    import jax.numpy as jnp

    from kmamiz_tpu.models import graphsage

    rng = np.random.default_rng(seed)
    return trainer.GraphDataset(
        endpoint_names=[f"ep{i}" for i in range(n_nodes)],
        src=jnp.asarray(rng.integers(0, n_nodes, n_edges, dtype=np.int32)),
        dst=jnp.asarray(rng.integers(0, n_nodes, n_edges, dtype=np.int32)),
        edge_mask=jnp.ones(n_edges, dtype=bool),
        features=[
            jnp.asarray(
                rng.normal(size=(n_nodes, graphsage.NUM_FEATURES)).astype(
                    np.float32
                )
            )
            for _ in range(n_slots)
        ],
        target_latency=[
            jnp.asarray(rng.normal(size=n_nodes).astype(np.float32))
            for _ in range(n_slots)
        ],
        target_anomaly=[
            jnp.asarray((rng.random(n_nodes) < anomaly).astype(np.float32))
            for _ in range(n_slots)
        ],
        node_mask=[
            jnp.asarray(rng.random(n_nodes) < 0.9) for _ in range(n_slots)
        ],
        slot_keys=[f"s{i}" for i in range(n_slots)],
    )


class TestStackedDataset:
    """Device residency (models/stacked.py): capacity-bucket padding and
    the one-upload stacked layout behind the scan-fused trainer."""

    def test_buckets_and_masks(self):
        from kmamiz_tpu.models import stacked

        ds = _synthetic_dataset(n_nodes=10, n_edges=14, n_slots=6)
        st = stacked.stack_dataset(ds)
        # pow2 capacity buckets (graph-store discipline); slots stay exact
        assert st.bucket_nodes == 16 and st.bucket_edges == 16
        assert st.num_slots == 6 and st.num_nodes == 10 and st.num_edges == 14
        assert st.features.shape == (6, 16, 10)
        assert st.node_mask.shape == (6, 16)
        # padded rows/edges are masked out
        assert not np.asarray(st.node_mask)[:, 10:].any()
        assert not np.asarray(st.edge_mask)[14:].any()
        # real content round-trips
        for i in range(6):
            np.testing.assert_array_equal(
                np.asarray(st.features[i, :10]), np.asarray(ds.features[i])
            )
        # repeated stacking reuses the single upload
        assert stacked.stack_dataset(ds) is st

    def test_layout_without_stacking(self):
        from kmamiz_tpu.models import stacked

        ds = _synthetic_dataset(n_nodes=10, n_edges=14, n_slots=6)
        assert stacked.dataset_layout(ds) == {
            "bucket_nodes": 16,
            "bucket_edges": 16,
            "num_slots": 6,
            "num_nodes": 10,
        }

    def test_batched_forward_matches_per_slot(self):
        import jax

        from kmamiz_tpu.models import graphsage, stacked

        ds = _synthetic_dataset()
        params = graphsage.init_params(jax.random.PRNGKey(1), hidden=8)
        lat, logit = stacked.predict_all(params, ds, graphsage)
        assert lat.shape == (5, 16)
        for i in range(5):
            ref_lat, ref_logit = graphsage.forward(
                params, ds.features[i], ds.src, ds.dst, ds.edge_mask
            )
            np.testing.assert_allclose(
                lat[i], np.asarray(ref_lat), rtol=1e-5, atol=1e-6
            )
            np.testing.assert_allclose(
                logit[i], np.asarray(ref_logit), rtol=1e-5, atol=1e-6
            )


class TestFusedTraining:
    """Scan-fused epochs (models/stacked.py): the single jitted program
    must reproduce the legacy host loop's update schedule."""

    def test_fused_matches_legacy_loop(self):
        import jax

        ds = _synthetic_dataset()
        r_legacy = trainer.train(ds, epochs=6, hidden=8, seed=0, fused=False)
        r_fused = trainer.train(ds, epochs=6, hidden=8, seed=0, fused=True)
        # same seed, same schedule: losses agree within fp32 tolerance
        # (only padded-array reduction order differs)
        np.testing.assert_allclose(
            r_fused.losses, r_legacy.losses, rtol=1e-4, atol=1e-5
        )
        np.testing.assert_allclose(
            r_fused.latency_losses, r_legacy.latency_losses, rtol=1e-4, atol=1e-5
        )
        for a, b in zip(
            jax.tree_util.tree_leaves(r_fused.params),
            jax.tree_util.tree_leaves(r_legacy.params),
        ):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=1e-3, atol=1e-5
            )

    @pytest.mark.parametrize(
        "n_nodes,n_edges", [(16, 24), (300, 1500)], ids=["one_tile", "three_tiles"]
    )
    def test_fused_runs_through_the_edge_plan_and_matches_legacy(
        self, n_nodes, n_edges
    ):
        """The epoch block takes the stack's edge plan (models/stacked.py
        plan_for): the neighbour sums are the planned, owner-sorted
        reduction with its own VJP, the legacy loop's are the per-edge
        segment sums, and the schedule is the same."""
        import jax

        from kmamiz_tpu.models import stacked
        from kmamiz_tpu.ops import sparse

        stacked.epoch_runner.cache_clear()  # count this trace's routing
        ds = _synthetic_dataset(n_nodes=n_nodes, n_edges=n_edges, n_slots=4)
        r_legacy = trainer.train(ds, epochs=4, hidden=8, seed=0, fused=False)
        assert sparse.route_stats()["planned"] == 0
        r_fused = trainer.train(ds, epochs=4, hidden=8, seed=0, fused=True)
        assert sparse.route_stats()["planned"] > 0
        st = stacked.stack_dataset(ds)
        assert st.plan is not None and st.plan_entries == 2 * n_edges
        np.testing.assert_allclose(
            r_fused.losses, r_legacy.losses, rtol=1e-4, atol=1e-5
        )
        np.testing.assert_allclose(
            r_fused.anomaly_losses, r_legacy.anomaly_losses, rtol=1e-4, atol=1e-5
        )
        for a, b in zip(
            jax.tree_util.tree_leaves(r_fused.params),
            jax.tree_util.tree_leaves(r_legacy.params),
        ):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=1e-3, atol=1e-5
            )

    def test_fused_matches_legacy_with_embeddings(self):
        ds = _synthetic_dataset()
        r_l = trainer.train(
            ds, epochs=3, hidden=8, fused=False, use_node_embeddings=True
        )
        r_f = trainer.train(
            ds, epochs=3, hidden=8, fused=True, use_node_embeddings=True
        )
        np.testing.assert_allclose(r_f.losses, r_l.losses, rtol=1e-4, atol=1e-5)
        # padded rows never receive embedding gradient: table stays [N, D]
        assert np.asarray(r_f.params.embedding).shape == (ds.num_nodes, 8)

    @pytest.mark.parametrize("value", ("0", "1"))
    def test_the_parameter_decides_and_no_environment_value(self, monkeypatch, value):
        """The variable that used to choose is gone: `fused=False` is the legacy
        loop and no `fused` argument the epoch block, whatever the environment
        holds. (Its name in parts: test_chip_bringup.py's tree check greps.)"""
        from kmamiz_tpu.models import stacked

        monkeypatch.setenv("KMAMIZ_SAGE" + "_FUSED", value)
        ds = _synthetic_dataset(n_slots=2)
        r = trainer.train(ds, epochs=1, hidden=8, fused=False)
        # the legacy path builds no device stack and runs no epoch block
        assert not hasattr(ds, "_stacked_cache")
        assert np.isfinite(r.losses[-1])
        assert _last_refresh_spans() & {"refresh.legacy_epoch", "refresh.epoch_block"} == {
            "refresh.legacy_epoch"
        }
        r = trainer.train(ds, epochs=1, hidden=8)
        assert ds._stacked_cache is stacked.stack_dataset(ds)
        assert np.isfinite(r.losses[-1])
        assert _last_refresh_spans() & {"refresh.legacy_epoch", "refresh.epoch_block"} == {
            "refresh.epoch_block"
        }

    def test_dp_batched_runner_trains(self):
        ds = _synthetic_dataset(n_slots=6)
        r = trainer.train(ds, epochs=5, hidden=8, fused=True, batch_slots=2)
        assert len(r.losses) == 5
        assert np.isfinite(r.losses).all()
        assert r.losses[-1] < r.losses[0]

    def test_resume_mid_run_is_bit_exact(self, tmp_path):
        """Regression: a run resumed from a mid-run checkpoint must replay
        the identical epoch-block sequence — bit-equal losses and params
        vs the uninterrupted run."""
        import jax

        ds = _synthetic_dataset()
        d1, d2 = str(tmp_path / "a"), str(tmp_path / "b")
        r_full = trainer.train(
            ds, epochs=6, hidden=8, checkpoint_dir=d1, checkpoint_every=2
        )
        r_head = trainer.train(
            ds, epochs=4, hidden=8, checkpoint_dir=d2, checkpoint_every=2
        )
        r_tail = trainer.train(
            ds, epochs=6, hidden=8, checkpoint_dir=d2, checkpoint_every=2
        )
        assert len(r_tail.losses) == 2
        assert r_full.losses == r_head.losses + r_tail.losses
        for a, b in zip(
            jax.tree_util.tree_leaves(r_full.params),
            jax.tree_util.tree_leaves(r_tail.params),
        ):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_resume_rejects_stacked_layout_mismatch(self, tmp_path):
        ds = _synthetic_dataset(n_nodes=10, n_edges=14, n_slots=4)
        d = str(tmp_path)
        trainer.train(ds, epochs=2, hidden=8, checkpoint_dir=d)
        # same endpoint count but an edge set in the next capacity bucket
        ds2 = _synthetic_dataset(n_nodes=10, n_edges=40, n_slots=4)
        with pytest.raises(ValueError, match="stacked layout"):
            trainer.train(ds2, epochs=4, hidden=8, checkpoint_dir=d)

    def test_checkpoint_metadata_records_layout(self, tmp_path):
        from kmamiz_tpu.models import checkpoint, stacked

        ds = _synthetic_dataset(n_nodes=10, n_edges=14, n_slots=4)
        trainer.train(ds, epochs=2, hidden=8, checkpoint_dir=str(tmp_path))
        meta = checkpoint.load_metadata(str(tmp_path), 2)
        assert dict(meta["stacked"]) == stacked.dataset_layout(ds)

    def test_evaluate_matches_legacy_scoring(self):
        """The vmapped stacked evaluation must reproduce the per-slot
        forward loop's metrics exactly (same thresholding math)."""
        import jax

        from kmamiz_tpu.models import graphsage

        ds = _synthetic_dataset(n_slots=6, anomaly=0.3)
        r = trainer.train(ds, epochs=3, hidden=8)
        got = trainer.evaluate(r.params, ds, threshold=0.4)

        def legacy_predict(i):
            lat, logit = graphsage.forward(
                r.params, ds.features[i], ds.src, ds.dst, ds.edge_mask
            )
            return lat, np.asarray(jax.nn.sigmoid(logit)) > 0.4

        want = trainer._score_predictions(ds, legacy_predict)
        assert got.per_slot_flagged == want.per_slot_flagged
        np.testing.assert_allclose(
            got.latency_mse, want.latency_mse, rtol=1e-6
        )
        assert got.anomaly_precision == want.anomaly_precision
        assert got.anomaly_recall == want.anomaly_recall

    def test_an_endpoint_outside_the_mask_cannot_poison_the_scores(self):
        """An endpoint that is inactive in the next slot is outside the loss, so nothing holds its prediction: a
        log-latency of 95 overflows `expm1`, and the mask must select, not multiply (0 x inf is no number; PR 39:
        the PNA head's degree scalers put one such endpoint at 91.6 on the 1k-endpoint evaluation)."""
        ds = _synthetic_dataset(n_slots=2)
        ds.node_mask[0] = ds.node_mask[0].at[3].set(False)

        def predict(i):
            lat = np.asarray(ds.target_latency[i]) + 0.5
            lat[3] = 95.0 if i == 0 else lat[3]
            return lat, np.zeros(lat.shape, bool)

        got = trainer._score_predictions(ds, predict)
        assert np.isfinite(got.latency_mae_ms) and np.isfinite(got.latency_mse)
        assert got.latency_mse == pytest.approx(0.25, rel=1e-5)

    @pytest.mark.slow
    def test_fused_convergence_on_simulation(self, simulation):
        """Long-epoch convergence check on the simulator mesh — slow
        sweep only; tier-1 covers the same path with few epochs."""
        result, metrics, _ds = trainer.train_on_simulation(
            simulation.endpoint_dependencies,
            simulation.realtime_data_per_slot,
            simulation.replica_counts,
            train_fraction=0.5,
            epochs=80,
            hidden=16,
            seed=0,
        )
        assert result.losses[-1] < result.losses[0]
        assert metrics.anomaly_recall > 0.5


class TestHistoryFeatures:
    """Identity-free inductive features (models/history.py): causality,
    shapes, and the endpoint-holdout masking the inductive protocol
    rides (VERDICT r3 #4)."""

    def test_shapes_and_width(self, dataset):
        from kmamiz_tpu.models import history

        aug = history.augment_with_history(dataset)
        base_w = np.asarray(dataset.features[0]).shape[1]
        for f in aug.features:
            assert np.asarray(f).shape == (
                dataset.num_nodes,
                base_w + history.NUM_HISTORY_FEATURES,
            )
        assert len(aug.features) == len(dataset.features)
        # targets/masks/graph untouched
        assert aug.slot_keys == dataset.slot_keys
        assert (np.asarray(aug.src) == np.asarray(dataset.src)).all()

    def test_causality_future_cannot_change_past_features(self, dataset):
        from dataclasses import replace

        from kmamiz_tpu.models import history

        aug_full = history.augment_with_history(dataset)
        # truncate the dataset: identical history for the surviving slots
        cut = len(dataset.features) // 2
        truncated = replace(
            dataset,
            features=dataset.features[:cut],
            target_latency=dataset.target_latency[:cut],
            target_anomaly=dataset.target_anomaly[:cut],
            node_mask=dataset.node_mask[:cut],
            slot_keys=dataset.slot_keys[:cut],
        )
        aug_cut = history.augment_with_history(truncated)
        for t in range(cut):
            assert (
                np.asarray(aug_full.features[t])
                == np.asarray(aug_cut.features[t])
            ).all(), f"slot {t} features depend on the future"

    def test_profile_sees_past_same_hour_labels(self, dataset):
        from kmamiz_tpu.models import history

        aug = history.augment_with_history(dataset)
        base_w = np.asarray(dataset.features[0]).shape[1]
        # the FAULT_YAML error window recurs on both simulated days at
        # the same hours on back-get: by the SECOND day (slot-key day
        # index 1) the past-label-rate column must be positive for that
        # endpoint at the recurring hours
        back = next(
            i for i, n in enumerate(dataset.endpoint_names) if "back" in n
        )
        col = base_w  # first history column = past label rate
        day2 = [
            t
            for t, key in enumerate(dataset.slot_keys)
            if trainer.parse_slot_key(key)[0] == 1
            and np.asarray(dataset.target_anomaly[t])[back] > 0
        ]
        assert day2, "fixture should have second-day fault slots"
        seen = [float(np.asarray(aug.features[t])[back, col]) for t in day2]
        assert max(seen) > 0.5, seen  # day-1 history predicts day 2

    def test_err_profile_keyed_by_observed_hour(self, dataset):
        # regression (review finding): the 5xx-share profile column must
        # carry traffic OBSERVED at the predicted hour on prior days —
        # not the hour before it. back-get's 5xx spikes during hours 6-10
        # (the fault window shifted by the next-slot labeling); a day-2
        # slot predicting an in-window hour must see a positive profile.
        from kmamiz_tpu.models import history

        aug = history.augment_with_history(dataset)
        base_w = np.asarray(dataset.features[0]).shape[1]
        back = next(
            i for i, n in enumerate(dataset.endpoint_names) if "back" in n
        )
        err_col = base_w + 1
        # find a day-2 example whose PREDICTED hour saw high 5xx on day 1
        bad_hours = {
            (trainer.parse_slot_key(k)[1])
            for t, k in enumerate(dataset.slot_keys)
            if trainer.parse_slot_key(k)[0] == 0
            and np.asarray(dataset.features[t])[back, 2] > 0.3
        }
        assert bad_hours, "day-1 must have observed 5xx slots"
        hits = [
            float(np.asarray(aug.features[t])[back, err_col])
            for t, k in enumerate(dataset.slot_keys)
            if trainer.parse_slot_key(k)[0] == 1
            and (trainer.parse_slot_key(k)[1] + 1) % 24 in bad_hours
        ]
        assert hits and max(hits) > 0.3, hits

    def test_degree_columns_are_static_log_degrees(self, dataset):
        from kmamiz_tpu.models import history

        aug = history.augment_with_history(dataset)
        base_w = np.asarray(dataset.features[0]).shape[1]
        deg_in_col = base_w + 6
        deg_out_col = base_w + 7
        f0 = np.asarray(aug.features[0])
        f_last = np.asarray(aug.features[-1])
        assert (f0[:, deg_in_col] == f_last[:, deg_in_col]).all()
        src = np.asarray(dataset.src)[np.asarray(dataset.edge_mask)]
        out_deg = np.bincount(src, minlength=dataset.num_nodes)
        assert np.allclose(f0[:, deg_out_col], np.log1p(out_deg))

    def test_mask_endpoints_restricts_losses_and_metrics(self, dataset):
        from kmamiz_tpu.models import history

        held = history.split_endpoints(dataset.num_nodes, 0.34, seed=3)
        kept_view = history.mask_endpoints(dataset, ~held)
        for t in range(len(dataset.features)):
            m = np.asarray(kept_view.node_mask[t])
            assert not m[held].any()
            base = np.asarray(dataset.node_mask[t])
            assert (m == (base & ~held)).all()
        # split is deterministic and sized correctly
        again = history.split_endpoints(dataset.num_nodes, 0.34, seed=3)
        assert (held == again).all()
        assert held.sum() == max(1, round(dataset.num_nodes * 0.34))

    def test_train_accepts_augmented_width(self, dataset):
        from kmamiz_tpu.models import history

        aug = history.augment_with_history(dataset)
        res = trainer.train(aug, epochs=2, hidden=8, seed=0)
        # params sized to the augmented width, loss finite
        assert res.params.w_self_1.shape[0] == np.asarray(
            aug.features[0]
        ).shape[1]
        assert np.isfinite(res.losses[-1])


class TestHistoryState:
    """Serving-side rolling state (models/history.HistoryState): replay
    equivalence with the trainer's augmentation, cold-start growth, and
    degree refresh — zero train/serve skew by construction."""

    def test_replay_reproduces_trainer_features_exactly(self, dataset):
        from kmamiz_tpu.models import history

        aug = history.augment_with_history(dataset)
        base_w = np.asarray(dataset.features[0]).shape[1]

        state = history.HistoryState(dataset.num_nodes)
        state.set_degrees(
            dataset.src, dataset.dst, dataset.edge_mask, dataset.num_nodes
        )
        for t in range(len(dataset.features)):
            base = np.asarray(dataset.features[t])
            hour = trainer.parse_slot_key(dataset.slot_keys[t])[1]
            cols = state.step(hour, base[:, 2], base[:, 3], base[:, 7])
            want = np.asarray(aug.features[t])[:, base_w:]
            # bit-for-bit: train-time augmentation IS a replay of this
            # state, so any inequality is real train/serve skew
            assert (cols == want).all(), f"slot {t} skew"

    def test_cold_start_endpoint_grows_in(self, dataset):
        from kmamiz_tpu.models import history

        state = history.HistoryState(2)
        c1 = state.step(5, [0.5, 0.0], [1.0, 1.0], [1, 1])
        assert c1.shape == (2, history.NUM_HISTORY_FEATURES)
        # a third endpoint appears mid-stream: state widens, empty profile
        c2 = state.step(6, [0.5, 0.0, 0.2], [1.0, 1.0, 1.0], [1, 1, 1])
        assert c2.shape == (3, history.NUM_HISTORY_FEATURES)
        assert c2[2, 0] == 0.0 and c2[2, 2] == 0.0  # no history yet
        # after a full day incl. a FOLDED hour-5 5xx bucket, the
        # recurring fault shows in the profile when predicting hour 5
        # again (read at the hour-4 step)
        for h in range(7, 24 + 7):
            state.step(h % 24, [0.5 if h % 24 == 5 else 0.0, 0.0, 0.0],
                       [1.0] * 3, [1] * 3)
        # stream is now at hour 6; wind forward to an hour-4 bucket
        for h in range(7, 24 + 5):
            state.step(h % 24, [0.0, 0.0, 0.0], [1.0] * 3, [1] * 3)
        cols = state.step(4, [0.0, 0.0, 0.0], [1.0] * 3, [1] * 3)
        assert cols[0, 0] > 0.3  # past label rate at predicted hour 5
        assert cols[0, 1] > 0.15  # past observed 5xx share at hour 5
        assert cols[1, 0] == 0.0  # the clean endpoint's profile stays clean

    def test_degrees_from_live_graph(self):
        from kmamiz_tpu.models import history

        state = history.HistoryState(3)
        state.set_degrees(
            np.array([0, 0, 1]), np.array([1, 2, 2]),
            np.array([True, True, True]), 3,
        )
        cols = state.step(0, [0.0] * 3, [0.0] * 3, [1] * 3)
        assert np.isclose(cols[0, 7], np.log1p(2))  # out-degree of node 0
        assert np.isclose(cols[2, 6], np.log1p(2))  # in-degree of node 2

    @staticmethod
    def _feed(state, n, steps, seed):
        rng = np.random.default_rng(seed)
        for t in range(steps):
            state.step(
                t % 24,
                rng.random(n).astype(np.float32) * 0.3,
                rng.random(n).astype(np.float32),
                (rng.random(n) > 0.2).astype(np.float32),
            )

    def test_remap_then_grow_same_tick(self):
        """Restart re-keying: a snapshot remapped by permutation into a
        WIDER id space, immediately followed by a step that grows the
        state further (new endpoints joined while the process was
        down), must emit exactly the columns of a reference state that
        lived in the final layout all along."""
        from kmamiz_tpu.models import history

        saved = history.HistoryState(5)
        self._feed(saved, 5, 6, seed=1)
        ids = np.array([3, 0, 6, 2, 7], dtype=np.int64)
        saved.remap(ids, 8)
        assert saved.num_endpoints == 8

        # reference: the same stream replayed directly at the new ids
        ref = history.HistoryState(8)
        rng = np.random.default_rng(1)
        for t in range(6):
            err5 = np.zeros(8, np.float32)
            lat = np.zeros(8, np.float32)
            act = np.zeros(8, np.float32)
            err5[ids] = rng.random(5).astype(np.float32) * 0.3
            lat[ids] = rng.random(5).astype(np.float32)
            act[ids] = (rng.random(5) > 0.2).astype(np.float32)
            ref.step(t % 24, err5, lat, act)

        # the very next bucket arrives with 10 endpoints: remap and
        # grow land in the SAME tick
        rng2 = np.random.default_rng(9)
        err5 = rng2.random(10).astype(np.float32) * 0.3
        lat = rng2.random(10).astype(np.float32)
        act = np.ones(10, np.float32)
        got = saved.step(6, err5, lat, act)
        want = ref.step(6, err5, lat, act)
        assert got.shape == (10, history.NUM_HISTORY_FEATURES)
        np.testing.assert_array_equal(got, want)

    def test_remap_rejects_bad_ids(self):
        """A negative id would wrap around into another endpoint's
        column, a duplicate would drop a profile (last write wins), an
        out-of-range id would fail mid-loop — all must raise BEFORE any
        field mutates, so days of profile survive a bad restart doc."""
        from kmamiz_tpu.models import history

        for bad, n_new in (
            (np.array([0, 5, 1]), 4),   # out of range
            (np.array([0, -1, 1]), 4),  # negative: silent wraparound
            (np.array([0, 1, 1]), 4),   # duplicate: silent profile loss
        ):
            state = history.HistoryState(3)
            self._feed(state, 3, 4, seed=2)
            before = {
                f: getattr(state, f).copy()
                for f in history.HistoryState._ARRAY_FIELDS
            }
            with pytest.raises(ValueError):
                state.remap(bad, n_new)
            assert state.num_endpoints == 3
            for f, a in before.items():
                np.testing.assert_array_equal(getattr(state, f), a)
