"""graftcost (kmamiz_tpu/cost/): feature determinism, the three spec
transposition rules, growth forecasting against the store's
consolidation policy, ranked prewarm ordering, persisted compile/run-ms
labels, the boot prewarm entry points, the cost-plane gating contract,
and the capacity-growth stall probe."""
from __future__ import annotations

import json
import random
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from kmamiz_tpu import cost, native
from kmamiz_tpu.core import programs
from kmamiz_tpu.cost import features, prewarm
from kmamiz_tpu.cost.model import CostModel, training_rows
from kmamiz_tpu.tenancy import growth

REPO_ROOT = Path(__file__).resolve().parent.parent


def _arr(*dims, dtype="float32"):
    return {"__arr__": [list(dims), dtype, False]}


def _spec(args, kwargs=None):
    return (list(args), dict(kwargs or {}))


@pytest.fixture
def fresh_warm_state(monkeypatch):
    """Isolate the module-level warm state from other tests."""
    monkeypatch.setattr(programs, "_warm", {"status": "cold"})
    monkeypatch.setattr(programs, "_warm_thread", None)


def _fresh_program(name: str) -> programs.Program:
    """A registry entry backed by a brand-new jit (own dispatch cache)."""

    @programs.register(name)
    @jax.jit
    def fn(x):
        return x * 2

    return fn


# -- feature extraction -------------------------------------------------------


class TestFeatures:
    def test_vector_is_deterministic(self):
        spec = _spec([_arr(1280), _arr(1280, dtype="int32")], {"cap": 1024})
        v1 = features.feature_vector("graph.merge_edges", spec)
        v2 = features.feature_vector("graph.merge_edges", spec)
        assert v1.dtype == np.float32 and v1.shape == (features.DIM,)
        assert np.array_equal(v1, v2)
        assert v1[0] == 1.0  # bias

    def test_capacity_bucket_proxy_moves_with_the_bucket(self):
        small = features.feature_vector("graph.x", _spec([_arr(1024)]))
        big = features.feature_vector("graph.x", _spec([_arr(2048)]))
        # feature 11 is log2 of the largest pow2 dim >= 256
        assert big[11] > small[11]
        assert not np.array_equal(small, big)

    def test_family_one_hot_is_stable(self):
        a = features.feature_vector("graph.merge", _spec([_arr(8)]))
        b = features.feature_vector("graph.split", _spec([_arr(8)]))
        hot_a = np.flatnonzero(a[12:])
        hot_b = np.flatnonzero(b[12:])
        assert len(hot_a) == len(hot_b) == 1  # exactly one family slot
        assert hot_a[0] == hot_b[0]  # same dotted prefix, same slot

    def test_feature_table_stacks(self):
        pairs = [("a.p", _spec([_arr(4)])), ("b.p", _spec([_arr(8)]))]
        table = features.feature_table(pairs)
        assert table.shape == (2, features.DIM)
        assert features.feature_table([]).shape == (0, features.DIM)

    def test_spec_dims_collects_arrays_and_positive_statics(self):
        spec = _spec([_arr(1280, 4)], {"cap": 1024, "flag": True, "neg": -3})
        dims = features.spec_dims(spec)
        assert sorted(dims) == [4, 1024, 1280]  # bools/negatives excluded


# -- spec transposition (cost/prewarm.py) -------------------------------------


class TestTransposition:
    MAPPING = prewarm.growth_mapping(1024, 256, 2048, 256)

    def test_growth_mapping_drops_identity_entries(self):
        # the tail stays 256 wide: unrelated 256s must not rewrite
        assert self.MAPPING == {1024: 2048, 1280: 2304}

    def test_exact_rule_rewrites_dims_and_statics(self):
        spec = _spec([_arr(1024), _arr(1280, 4)], {"cap": 1024, "tail": 256})
        out = prewarm.transpose_spec(spec, self.MAPPING)
        assert out == ([_arr(2048), _arr(2304, 4)], {"cap": 2048, "tail": 256})

    def test_flat_delta_shifts_only_past_old_flat_width(self):
        spec = _spec([_arr(1300), _arr(512)])
        out = prewarm.transpose_spec(spec, self.MAPPING, delta=(1280, 2304))
        # 1300 > 1280 shifts by the flat growth; 512 is untouched
        assert out == ([_arr(1300 + 1024), _arr(512)], {})

    def test_statics_only_leaves_arrays_untouched(self):
        spec = _spec([_arr(1024)], {"cap": 1024})
        out = prewarm.transpose_spec(spec, self.MAPPING, statics_only=True)
        assert out == ([_arr(1024)], {"cap": 2048})

    def test_booleans_survive_int_mapping(self):
        spec = _spec([_arr(1024)], {"flag": True, "n": 1024})
        out = prewarm.transpose_spec(spec, self.MAPPING)
        assert out[1] == {"flag": True, "n": 2048}

    def test_predictive_pairs_scopes_delta_to_graph_family(self):
        g = _fresh_program("graph.tcost_delta")
        s = _fresh_program("scorers.tcost_delta")
        g(jnp.zeros(1300, jnp.float32))
        s(jnp.zeros(1300, jnp.float32))
        pairs = prewarm.predictive_pairs(self.MAPPING, delta=(1280, 2304))
        mine = {n: sp for n, sp in pairs if n.endswith(".tcost_delta")}
        # graph family: 1300 > old flat 1280 shifts; scorers: no rule
        # touches 1300, the identity transpose is dropped from the plan
        assert "graph.tcost_delta" in mine
        assert mine["graph.tcost_delta"][0][0]["__arr__"][0] == [2324]
        assert "scorers.tcost_delta" not in mine

    def test_transposed_spec_replays_through_prewarm(self):
        prog = _fresh_program("graph.tcost_replay")
        prog(jnp.zeros(1024, jnp.float32))
        assert prog.compiles == 1
        warped = prewarm.transpose_spec(prog.specs()[0], self.MAPPING)
        warmed, failed = prewarm.execute([("graph.tcost_replay", warped)])
        assert (warmed, failed) == (1, 0)
        # the prewarmed bucket is a cache hit for live traffic
        snap = programs.snapshot()
        prog(jnp.zeros(2048, jnp.float32))
        assert programs.new_compiles_since(snap) == {}


# -- growth forecasting (tenancy/growth.py) -----------------------------------


class TestGrowthForecast:
    def test_forecast_matches_store_consolidation_policy(self):
        tr = growth.GrowthTracker()
        tr.observe("t", 600, 1024, 256)
        tr.observe("t", 900, 1024, 256)
        fc = tr.forecast("t", tail_shift=3)
        assert fc.slope_per_merge == 300.0
        assert fc.threshold == 1280
        assert fc.merges_to_crossing == 2
        assert fc.imminent(3) and not fc.imminent(1)
        # graph/store.py policy: _pow2 main, tail max(256, main >> 3)
        assert (fc.new_main, fc.new_tail) == (2048, 256)

    def test_single_point_has_no_forecast(self):
        tr = growth.GrowthTracker()
        tr.observe("t", 600, 1024, 256)
        assert tr.forecast("t") is None
        assert tr.forecast("unknown") is None

    def test_already_over_threshold_is_zero_merges(self):
        tr = growth.GrowthTracker()
        tr.observe("t", 1290, 1024, 256)
        tr.observe("t", 1300, 1024, 256)
        assert tr.forecast("t").merges_to_crossing == 0

    def test_flat_growth_never_crosses(self):
        tr = growth.GrowthTracker()
        tr.observe("t", 600, 1024, 256)
        tr.observe("t", 600, 1024, 256)
        fc = tr.forecast("t")
        assert fc.merges_to_crossing is None
        assert not fc.imminent(100)

    def test_reset_clears_rings(self):
        tr = growth.GrowthTracker()
        tr.observe("t", 600, 1024, 256)
        tr.reset()
        assert tr.tenants() == ()


# -- cost model + ranked ordering ---------------------------------------------


def _width_rows(name="graph.tcost_rank"):
    return [
        (name, _spec([_arr(w)]), float(w), 0.1)
        for w in (64, 128, 256, 512, 1024, 2048, 4096)
    ]


class TestCostModel:
    def test_untrained_predicts_none(self):
        m = CostModel()
        assert not m.trained()
        assert m.predict("a.p", _spec([_arr(8)])) is None
        assert m.predict_many([("a.p", _spec([_arr(8)]))]) is None

    def test_fit_learns_width_ordering(self):
        m = CostModel()
        report = m.fit(_width_rows())
        assert report["examples"] == 7
        small = m.predict("graph.tcost_rank", _spec([_arr(64)]))
        big = m.predict("graph.tcost_rank", _spec([_arr(4096)]))
        assert big[0] > small[0]  # compile-ms ordering follows width

    def test_fit_is_one_fixed_shape_forever(self):
        m = CostModel()
        m.fit(_width_rows()[:3])
        snap = programs.snapshot()
        m.fit(_width_rows())  # more rows, same padded example cap
        grew = programs.new_compiles_since(snap)
        assert grew.get("cost.ridge_fit", 0) == 0

    def test_ranked_order_prefers_predicted_expensive(self):
        m = CostModel()
        m.fit(_width_rows())
        small = ("graph.tcost_rank", _spec([_arr(64)]))
        big = ("graph.tcost_rank", _spec([_arr(4096)]))
        assert prewarm.rank_by_predicted_compile([small, big], m)[0] == big

    def test_ranked_order_label_fallback_then_name_order(self):
        pairs = [("b.p", _spec([_arr(8)])), ("a.p", _spec([_arr(8)]))]
        labels = {"a.p": [(_spec([_arr(8)]), 50.0, 0.1)]}
        ranked = prewarm.rank_by_predicted_compile(pairs, None, labels)
        assert [n for n, _s in ranked] == ["a.p", "b.p"]  # labelled first
        unranked = prewarm.rank_by_predicted_compile(pairs, None)
        assert [n for n, _s in unranked] == ["a.p", "b.p"]  # name order

    def test_training_rows_dedup_persisted_wins(self):
        spec = _spec([_arr(8)])
        persisted = {"test.tcost_dedup": [(spec, 7.0, 0.2)]}
        rows = training_rows(persisted)
        mine = [r for r in rows if r[0] == "test.tcost_dedup"]
        assert mine == [("test.tcost_dedup", spec, 7.0, 0.2)]


# -- persisted labels (shape-hint satellite) ----------------------------------


class TestLabelPersistence:
    def test_labels_roundtrip_through_hint_file(self, tmp_path, monkeypatch):
        path = tmp_path / "hints.json"
        monkeypatch.setenv("KMAMIZ_SHAPE_HINTS", str(path))
        prog = _fresh_program("test.tcost_labels")
        prog(jnp.zeros(16, jnp.float32))
        assert programs.save_hints() == str(path)
        # older readers: "programs" untouched, version unchanged
        payload = json.loads(path.read_text())
        assert payload["version"] == 1
        assert "test.tcost_labels" in payload["programs"]
        loaded = programs.load_labels()
        rows = loaded["test.tcost_labels"]
        assert len(rows) == 1
        spec, compile_ms, run_ms = rows[0]
        assert compile_ms > 0.0
        assert json.dumps(list(spec), sort_keys=True) == json.dumps(
            list(prog.specs()[0]), sort_keys=True
        )

    def test_pre_label_hint_file_loads_empty(self, tmp_path, monkeypatch):
        path = tmp_path / "hints.json"
        path.write_text(json.dumps({"version": 1, "programs": {}}))
        monkeypatch.setenv("KMAMIZ_SHAPE_HINTS", str(path))
        assert programs.load_labels() == {}

    def test_adopt_labels_feeds_training_at_boot(self):
        prog = _fresh_program("test.tcost_adopt")
        spec = _spec([_arr(8)])
        programs.adopt_labels({"test.tcost_adopt": [(spec, 12.5, 0.5)]})
        rows = prog.labels()
        assert rows == [(spec, 12.5, 0.5)]
        # live observation of the same bucket wins over a re-adopt
        programs.adopt_labels({"test.tcost_adopt": [(spec, 99.0, 9.0)]})
        assert prog.labels() == [(spec, 12.5, 0.5)]


# -- boot prewarm entry points ------------------------------------------------


class TestPrewarmPaths:
    def test_run_prewarm_is_ranked_and_counts_misses(
        self, fresh_warm_state, tmp_path, monkeypatch
    ):
        monkeypatch.setenv("KMAMIZ_SHAPE_HINTS", str(tmp_path / "none.json"))
        prog = _fresh_program("test.tcost_boot")
        prog(jnp.zeros(8, jnp.float32))
        spec = prog.specs()[0]
        report = programs.run_prewarm(
            hints={"test.tcost_boot": [spec], "test.tcost_ghost": [spec]}
        )
        assert report["ranked"] is True
        assert report["warmed"] >= 1
        assert report["failed"] >= 1  # the unregistered hint name
        assert prog.prewarmed >= 1

    def test_background_prewarm_reaches_ready_and_is_idempotent(
        self, fresh_warm_state, tmp_path, monkeypatch
    ):
        monkeypatch.setenv("KMAMIZ_SHAPE_HINTS", str(tmp_path / "none.json"))
        t = programs.start_background_prewarm()
        assert t is not None
        t.join(60)
        state = programs.warm_state()
        assert state["status"] == "ready"
        assert state["report"]["ranked"] is True
        assert programs.start_background_prewarm() is t  # no restart
        assert programs.warm_state()["status"] == "ready"

    def test_boot_env_disabled(self, fresh_warm_state, monkeypatch):
        monkeypatch.setenv("KMAMIZ_PREWARM", "0")
        programs.boot_prewarm_from_env()
        assert programs.warm_state()["status"] == "disabled"

    def test_boot_env_sync(self, fresh_warm_state, tmp_path, monkeypatch):
        monkeypatch.setenv("KMAMIZ_PREWARM", "sync")
        monkeypatch.setenv("KMAMIZ_SHAPE_HINTS", str(tmp_path / "none.json"))
        programs.boot_prewarm_from_env()
        state = programs.warm_state()
        assert state["status"] == "ready"
        assert state["report"]["ranked"] is True


# -- the cost plane (gating, crossing accounting) -----------------------------


class TestCostPlane:
    def test_disabled_by_default_and_inert(self, monkeypatch):
        monkeypatch.delenv("KMAMIZ_COST", raising=False)
        cost.reset_for_tests()
        assert not cost.enabled()
        cost.observe_merge("t", 600, 1024, 256)
        assert cost._COST is None  # gated hooks never build the plane
        assert cost.run_pending_prewarms() == {
            "rounds": 0,
            "warmed": 0,
            "failed": 0,
        }
        assert cost.predicted_tenant_costs() == {}
        assert cost.refresh() is None
        assert cost.snapshot()["enabled"] is False

    def test_sync_crossing_prewarms_and_scores_a_hit(self, monkeypatch):
        monkeypatch.setenv("KMAMIZ_COST", "1")
        monkeypatch.setenv("KMAMIZ_COST_PREWARM", "sync")
        monkeypatch.setenv(
            "KMAMIZ_SHAPE_HINTS", "/nonexistent/tcost/hints.json"
        )
        cost.reset_for_tests()
        cost.observe_merge("t", 600, 1024, 256)
        cost.observe_merge("t", 1100, 1024, 256)  # slope 500: imminent
        drained = cost.run_pending_prewarms()
        assert drained["rounds"] == 1
        # the consolidation lands on the bucket the forecast warmed
        cost.note_capacity_change("t", 1024, 2048, 256)
        snap = cost.snapshot()
        assert snap["prewarmRounds"] == 1
        assert snap["prewarmHits"] == 1 and snap["prewarmMisses"] == 0
        assert snap["hitRate"] == 1.0
        assert snap["lastCrossing"] == {
            "tenant": "t",
            "fromMain": 1024,
            "toMain": 2048,
            "toTail": 256,
            "hit": True,
        }
        assert cost.run_pending_prewarms()["rounds"] == 0  # drained

    def test_cold_crossing_scores_a_miss(self, monkeypatch):
        monkeypatch.setenv("KMAMIZ_COST", "1")
        monkeypatch.setenv("KMAMIZ_COST_PREWARM", "0")
        cost.reset_for_tests()
        cost.note_capacity_change("t", 1024, 2048, 256)
        snap = cost.snapshot()
        assert snap["prewarmMisses"] == 1
        assert snap["hitRate"] == 0.0
        assert snap["lastCrossing"]["hit"] is False


# -- the stall probe (two arms, a process each) ------------------------------------


class TestGrowthProbe:
    def test_prewarmed_crossing_compiles_nothing(self, monkeypatch):
        # run_probe writes these; monkeypatch restores them afterwards
        monkeypatch.setenv("KMAMIZ_COST", "1")
        monkeypatch.setenv("KMAMIZ_COST_PREWARM", "sync")
        monkeypatch.delenv("KMAMIZ_SHAPE_HINTS", raising=False)
        from kmamiz_tpu.cost.growth_probe import run_probe

        report = run_probe(True, capacity=512)
        assert report["crossed"], report
        assert report["to_capacity"] == 1024
        assert report["mid_compiles"] == 0
        assert report["hit"] is True
        assert report["hit_rate"] == 1.0
        assert report["signature"]
        assert report["steady_ms"] is not None


# -- capacity-growth storyline ------------------------------------------------


class TestGrowthStoryline:
    def test_archetype_and_storyline_registered(self):
        from kmamiz_tpu.scenarios import ARCHETYPES
        from kmamiz_tpu.scenarios.storyline import STORYLINE_KINDS

        assert "capacity-growth" in STORYLINE_KINDS
        assert any(n == "capacity-growth-chain" for n, _t in ARCHETYPES)

    def _event(self):
        from kmamiz_tpu.scenarios.storyline import compose_capacity_growth
        from kmamiz_tpu.scenarios.topology import sample_topology

        topo = sample_topology("chain", random.Random(3), "ns")
        return topo, compose_capacity_growth(topo, random.Random(5), 10)

    def test_compose_is_deterministic_and_crosses_the_bucket(self):
        from kmamiz_tpu.scenarios.storyline import (
            GROWTH_TOTAL_ENDPOINTS,
            compose_capacity_growth,
        )

        topo, ev = self._event()
        again = compose_capacity_growth(topo, random.Random(5), 10)
        assert ev == again
        per_tick = ev.params[2]
        # the full ramp mints enough endpoints to cross 1024 + 256
        assert per_tick * ev.duration >= GROWTH_TOTAL_ENDPOINTS > 1280
        # the ramp ends before the soak so post-crossing steady state
        # is measured too
        assert ev.at_tick + ev.duration <= 10 - 2

    def test_twins_match_ramp_shape_with_disjoint_endpoints(self):
        from kmamiz_tpu.scenarios.storyline import (
            growth_groups,
            growth_twin_groups,
        )

        topo, ev = self._event()
        tick = ev.at_tick + 1
        ramp = growth_groups(ev, topo, "p", tick)
        twins = growth_twin_groups(ev, topo, "p", tick)
        per_tick = ev.params[2]
        assert len(ramp) == len(twins) == per_tick
        assert sorted(map(len, ramp)) == sorted(map(len, twins))

        def leaf_urls(groups, marker):
            return {
                s["tags"]["http.url"]
                for g in groups
                for s in g
                if marker in s["tags"]["http.url"]
            }

        # the twins mint per_tick brand-new endpoints of their own (the
        # merge kernels bucket on the window's new-unique-edge count)
        grow = leaf_urls(ramp, "/grow/")
        warm = leaf_urls(twins, "/warm/")
        assert len(grow) == len(warm) == per_tick
        assert not grow & warm
        # successive ramp ticks keep minting fresh endpoints
        next_grow = leaf_urls(growth_groups(ev, topo, "p", tick + 1), "/grow/")
        assert not grow & next_grow
        # inactive ticks emit nothing
        assert growth_groups(ev, topo, "p", 0) == []
        assert growth_twin_groups(ev, topo, "p", 0) == []


# -- slow: the full closed-loop scenario gate ---------------------------------


@pytest.mark.slow
def test_capacity_growth_scenario_gate():
    """One real capacity-growth soak: the tenant crosses a bucket
    boundary mid-soak with ZERO mid-tick compiles (the ROADMAP item-6
    acceptance) and the crossing lands on a predictively warmed
    bucket."""
    if not native.available():
        pytest.skip("native extension unavailable")
    from kmamiz_tpu.scenarios import build_scenario, run_scenario

    spec = build_scenario("capacity-growth-chain", 0, 7, 10)
    card = run_scenario(spec)
    assert card["pass"], card["gates"]
    assert card["mid_tick_compiles"] == 0, card["mid_tick_detail"]
    assert card["gates"]["bucket_crossed"]
    assert card["gates"]["zero_steady_recompiles"]
    tenant = spec.tenants[0].tenant
    pre, post = card["capacity"][tenant]
    assert post > pre
    assert card["cost"]["lastCrossing"]["hit"] is True
    assert card["cost"]["hitRate"] == 1.0
