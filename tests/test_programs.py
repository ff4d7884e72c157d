"""Program registry (core/programs.py): compile telemetry, shape-hint
persistence + prewarm replay, steady-state zero-recompile contract, the
pow2 bucketing parity of DeviceStatsJob's static args, and the jit-site
guard that keeps every `jax.jit` under kmamiz_tpu/ either registered or
explicitly allowlisted."""
from __future__ import annotations

import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from kmamiz_tpu.core import programs

REPO_ROOT = Path(__file__).resolve().parent.parent


@pytest.fixture
def fresh_warm_state(monkeypatch):
    """Isolate the module-level warm state from other tests."""
    monkeypatch.setattr(programs, "_warm", {"status": "cold"})
    monkeypatch.setattr(programs, "_warm_thread", None)


def _fresh_program(name: str, static: bool = False) -> programs.Program:
    """A registry entry backed by a brand-new jit (own dispatch cache)."""
    if static:

        @programs.register(name)
        @jax.jit
        def fn(x, scale=2):
            return x * scale

    else:

        @programs.register(name)
        @jax.jit
        def fn(x):
            return x * 2

    return fn


class TestTelemetry:
    def test_compile_counted_once_per_bucket(self):
        prog = _fresh_program("test.telemetry_bucket")
        prog(jnp.zeros(8, jnp.float32))
        assert (prog.calls, prog.compiles) == (1, 1)
        assert prog.compile_ms > 0
        prog(jnp.ones(8, jnp.float32))  # same bucket: cache hit
        assert (prog.calls, prog.compiles) == (2, 1)
        prog(jnp.zeros(16, jnp.float32))  # new bucket
        assert prog.compiles == 2
        assert len(prog.stats()["buckets"]) == 2

    def test_non_jit_callable_tracks_calls_only(self):
        prog = programs.register("test.plain", lambda x: x + 1)
        assert prog(1) == 2
        st = prog.stats()
        assert (st["calls"], st["compiles"], st["cacheSize"]) == (1, 0, None)

    def test_attribute_delegation(self):
        prog = _fresh_program("test.delegation")
        assert prog._cache_size() == 0  # jit's own attribute, read through

    def test_snapshot_diff(self):
        prog = _fresh_program("test.snapshot")
        snap = programs.snapshot()
        prog(jnp.zeros(4, jnp.float32))
        assert programs.new_compiles_since(snap) == {"test.snapshot": 1}
        snap = programs.snapshot()
        prog(jnp.zeros(4, jnp.float32))
        assert programs.new_compiles_since(snap) == {}

    def test_summary_totals(self):
        prog = _fresh_program("test.summary")
        prog(jnp.zeros(4, jnp.float32))
        summ = programs.summary()
        assert summ["programs"]["test.summary"]["compiles"] == 1
        assert summ["totalCompiles"] >= 1
        assert "warm" in summ


class TestSpecRoundtrip:
    def test_array_tuple_namedtuple_scalars(self):
        from kmamiz_tpu.ops.window import PackedEdges

        nt = PackedEdges(
            *[jnp.zeros((4, 8), jnp.int32) for _ in range(4)],
            jnp.zeros((4, 8), jnp.int32),
        )
        enc = programs._encode(
            (jnp.zeros((2, 3), jnp.float32), nt, 7, "xla", None)
        )
        dec = programs._decode_zeros(enc)
        arr, nt2, seven, backend, none = dec
        assert arr.shape == (2, 3) and arr.dtype == jnp.float32
        assert isinstance(nt2, PackedEdges)
        assert nt2.mask.shape == (4, 8)
        assert (seven, backend, none) == (7, "xla", None)
        # the canonical JSON is the bucket identity: stable across encode
        assert json.dumps(enc, sort_keys=True) == json.dumps(
            programs._encode(programs._decode_zeros(enc)), sort_keys=True
        )

    def test_weak_scalar_replays_as_literal(self):
        dec = programs._decode_zeros({"__arr__": [[], "int32", True]})
        assert dec == 0 and type(dec) is int
        dec = programs._decode_zeros({"__arr__": [[], "float32", True]})
        assert dec == 0.0 and type(dec) is float

    def test_opaque_leaf_rejected(self):
        with pytest.raises(programs.UnencodableSpec):
            programs._encode(object())

    def test_recorded_spec_matches_live_cache_key(self):
        """A prewarm replay of the recorded spec must land in the same
        jit cache entry the live call compiled (zero growth after)."""
        prog = _fresh_program("test.replay_src", static=True)
        prog(jnp.zeros((8,), jnp.float32), scale=3)
        [spec] = prog.specs()

        twin = _fresh_program("test.replay_dst", static=True)
        assert twin.prewarm_spec(spec)
        assert (twin.prewarmed, twin.compiles) == (1, 1)
        snap = programs.snapshot()
        twin(jnp.ones((8,), jnp.float32), scale=3)  # live call: cache hit
        assert programs.new_compiles_since(snap) == {}


class TestHints:
    def test_autosave_load_roundtrip(self, tmp_path, monkeypatch):
        path = tmp_path / "hints.json"
        monkeypatch.setenv("KMAMIZ_SHAPE_HINTS", str(path))
        prog = _fresh_program("test.hints_roundtrip")
        prog(jnp.zeros(32, jnp.float32))  # compile event -> autosave
        assert path.exists()
        hints = programs.load_hints()
        assert [tuple(s) for s in prog.specs()] == hints[
            "test.hints_roundtrip"
        ]

    def test_unconfigured_hints_are_inert(self, monkeypatch):
        """A process that never enabled the persistent cache (library
        use, this test run) keeps no hint file: hints live beside the
        cache (tests/test_chip_bringup.py covers the enabled case)."""
        from kmamiz_tpu.core import compile_cache

        monkeypatch.delenv("KMAMIZ_SHAPE_HINTS", raising=False)
        assert not compile_cache.enabled()
        assert programs.hints_path() is None
        assert programs.save_hints() is None
        assert programs.load_hints() == {}

    def test_bad_hint_file_tolerated(self, tmp_path, monkeypatch):
        path = tmp_path / "hints.json"
        path.write_text("{not json")
        monkeypatch.setenv("KMAMIZ_SHAPE_HINTS", str(path))
        assert programs.load_hints() == {}

    def test_run_prewarm_replays_hints(self, tmp_path, monkeypatch):
        path = tmp_path / "hints.json"
        monkeypatch.setenv("KMAMIZ_SHAPE_HINTS", str(path))
        src = _fresh_program("test.prewarm_replay")
        src(jnp.zeros(16, jnp.float32))

        # a "restarted" program: same name, new jit, empty cache
        dst = _fresh_program("test.prewarm_replay")
        assert dst is not src and dst._cache_size() == 0
        report = programs.run_prewarm()
        assert report["failed"] == 0
        assert dst._cache_size() == 1  # dispatch cache, not just AOT
        snap = programs.snapshot()
        dst(jnp.ones(16, jnp.float32))
        assert programs.new_compiles_since(snap) == {}

    def test_unknown_hint_counts_failed(self, tmp_path, monkeypatch):
        path = tmp_path / "hints.json"
        path.write_text(
            json.dumps(
                {
                    "version": 1,
                    "programs": {"test.never_registered_xyz": [[[], {}]]},
                }
            )
        )
        monkeypatch.setenv("KMAMIZ_SHAPE_HINTS", str(path))
        report = programs.run_prewarm()
        assert report["failed"] >= 1


    def test_a_hint_file_naming_a_vanished_program_is_skipped(
        self, tmp_path, monkeypatch
    ):
        """Every deployed `.xla-cache` still holds hints for the two fused
        sparse kernels PR 30 removed: prewarm passes them over (counted,
        never raised), replays the rest, and the next save forgets them."""
        path = tmp_path / "hints.json"
        monkeypatch.setenv("KMAMIZ_SHAPE_HINTS", str(path))
        src = _fresh_program("test.prewarm_beside_stale")
        src(jnp.zeros(16, jnp.float32))
        payload = json.loads(path.read_text())
        spec = payload["programs"]["test.prewarm_beside_stale"][0]
        # spelled in parts: the tree check of test_chip_bringup.py greps for them
        stale = ("sparse.fused_" + "gated_bias", "sparse.fused_" + "neighbor_sums")
        for name in stale:
            assert programs.get(name) is None
            payload["programs"][name] = [spec]
            payload["labels"][name] = [{"spec": spec, "compileMs": 9.0, "runMs": 1.0}]
        path.write_text(json.dumps(payload))

        dst = _fresh_program("test.prewarm_beside_stale")
        report = programs.run_prewarm()
        assert report["hintedPrograms"] >= 3 and report["failed"] == 2
        assert report["warmed"] >= 1 and dst._cache_size() == 1
        programs.save_hints()
        kept = json.loads(path.read_text())
        for name in stale:
            assert name not in kept["programs"] and name not in kept["labels"]
        assert "test.prewarm_beside_stale" in kept["programs"]


class TestWarmStateGate:
    def test_boot_disabled(self, fresh_warm_state, monkeypatch):
        monkeypatch.setenv("KMAMIZ_PREWARM", "0")
        programs.boot_prewarm_from_env()
        assert programs.warm_state()["status"] == "disabled"

    def test_boot_sync(self, fresh_warm_state, tmp_path, monkeypatch):
        monkeypatch.setenv("KMAMIZ_PREWARM", "sync")
        monkeypatch.setenv(
            "KMAMIZ_SHAPE_HINTS", str(tmp_path / "hints.json")
        )
        programs.boot_prewarm_from_env()
        state = programs.warm_state()
        assert state["status"] == "ready"
        assert "report" in state

    def test_background_thread_reaches_ready(
        self, fresh_warm_state, tmp_path, monkeypatch
    ):
        monkeypatch.delenv("KMAMIZ_PREWARM", raising=False)
        monkeypatch.setenv(
            "KMAMIZ_SHAPE_HINTS", str(tmp_path / "hints.json")
        )
        thread = programs.start_background_prewarm()
        thread.join(timeout=60)
        assert programs.warm_state()["status"] == "ready"

    def test_ready_gate_env(self, monkeypatch):
        monkeypatch.delenv("KMAMIZ_PREWARM_READY_GATE", raising=False)
        assert programs.ready_gate_enabled()
        monkeypatch.setenv("KMAMIZ_PREWARM_READY_GATE", "0")
        assert not programs.ready_gate_enabled()

    def test_health_answers_503_while_warming(
        self, fresh_warm_state, monkeypatch
    ):
        from kmamiz_tpu.api.handlers.health import HealthHandler

        handler = HealthHandler()
        programs._warm.update({"status": "warming"})
        resp = handler._health(None)
        assert resp.status == 503
        assert resp.payload["status"] == "WARMING"
        programs._warm.update({"status": "ready"})
        resp = handler._health(None)
        assert resp.status == 200
        assert resp.payload["status"] == "UP"
        assert resp.payload["prewarm"]["status"] == "ready"


class TestSteadyStateTick:
    def test_second_tick_compiles_nothing(self, monkeypatch):
        # the conftest's virtual 8-device mesh would route the stats job
        # through the sharded path; this test pins the single-device one
        monkeypatch.setenv("KMAMIZ_MESH", "0")
        from kmamiz_tpu.server.processor import DataProcessor
        from kmamiz_tpu.synth import make_raw_window

        def tick(dp, uid, t):
            dp.collect({"uniqueId": uid, "lookBack": 30_000, "time": t})
            dp.graph.n_edges  # drain the deferred merge

        window = json.loads(make_raw_window(60, 5))
        dp = DataProcessor(trace_source=lambda lb, t, lim: window)
        tick(dp, "warmup", 1_000_000)

        # a DIFFERENT window of the same cadence on a fresh processor:
        # every shape must land in an already-compiled bucket
        window2 = json.loads(make_raw_window(60, 5, t_start=10_000))
        dp2 = DataProcessor(trace_source=lambda lb, t, lim: window2)
        snap = programs.snapshot()
        tick(dp2, "steady", 2_000_000)
        assert programs.new_compiles_since(snap) == {}


class TestStatsBucketingParity:
    def test_padded_statics_bit_exact(self):
        """window_stats with pow2-padded num_endpoints/num_statuses must
        reproduce the exact-static result on every real segment — the
        invariant DeviceStatsJob's shape canonicalization relies on."""
        from kmamiz_tpu.core.spans import _pad_size
        from kmamiz_tpu.ops.window import window_stats

        rng = np.random.default_rng(0)
        n, n_ep, n_st = 64, 5, 3  # deliberately not powers of two
        eid = jnp.asarray(rng.integers(0, n_ep, n), jnp.int32)
        sid = jnp.asarray(rng.integers(0, n_st, n), jnp.int32)
        scl = jnp.asarray(rng.integers(2, 6, n), jnp.int8)
        lat = jnp.asarray(rng.uniform(1, 1000, n).astype(np.float32))
        ts = jnp.asarray(rng.integers(0, 10_000, n), jnp.int32)
        valid = jnp.asarray(rng.random(n) < 0.9)

        exact = window_stats(
            eid, sid, scl, lat, ts, valid,
            num_endpoints=n_ep, num_statuses=n_st,
        )
        pe, ps = _pad_size(n_ep), _pad_size(n_st)
        padded = window_stats(
            eid, sid, scl, lat, ts, valid,
            num_endpoints=pe, num_statuses=ps,
        )
        for e in range(n_ep):
            for s in range(n_st):
                a, b = e * n_st + s, e * ps + s
                for field in exact._fields:
                    va = np.asarray(getattr(exact, field))[a]
                    vb = np.asarray(getattr(padded, field))[b]
                    assert va == vb or (np.isnan(va) and np.isnan(vb)), (
                        field, e, s,
                    )


class TestEncodedPayloadCache:
    def test_memoizes_by_key_and_encoding(self):
        from kmamiz_tpu.server.dp_server import _EncodedPayloadCache

        cache = _EncodedPayloadCache(max_entries=2)
        payload = {"combined": list(range(100))}
        first = cache.get_or_encode(("id", 1, 0), payload, False)
        again = cache.get_or_encode(("id", 1, 0), payload, False)
        assert again is first  # same bytes object: no re-encode
        assert json.loads(first) == payload
        gz = cache.get_or_encode(("id", 1, 0), payload, True)
        assert gz is not first and gz[:2] == b"\x1f\x8b"
        # a new graph version is a different key
        v2 = cache.get_or_encode(("id", 2, 0), {"combined": []}, False)
        assert v2 != first

    def test_eviction_cap(self):
        from kmamiz_tpu.server.dp_server import _EncodedPayloadCache

        cache = _EncodedPayloadCache(max_entries=2)
        for v in range(5):
            cache.get_or_encode(("id", v, 0), {"v": v}, False)
        assert len(cache._entries) <= 2


# ---------------------------------------------------------------------------
# jit-site guard (delegates to graftlint's unregistered-jit rule: one
# scanner — the old per-test regex walker lives on as the rule's AST
# implementation in kmamiz_tpu/analysis/rules.py)
# ---------------------------------------------------------------------------


class TestJitSiteGuard:
    def test_every_jit_site_registered_or_allowlisted(self):
        """New jitted entry points must join the program registry (or the
        explicit allowlist with a reason): an unregistered jit is a
        compile wall the boot prewarm plan cannot see. The same rule also
        rejects stale table entries, so the tables track reality in both
        directions."""
        from kmamiz_tpu.analysis import framework

        result = framework.lint_paths(
            str(REPO_ROOT), ["kmamiz_tpu"], rules=["unregistered-jit"]
        )
        offenders = [f.render() for f in result.findings]
        assert not offenders, (
            "jax.jit sites out of sync with programs.REGISTERED_JIT_SITES /"
            f" ALLOWLISTED_JIT_SITES: {offenders}"
        )

    def test_rule_sees_the_known_sites(self):
        """Sanity: the AST scanner actually resolves the registered sites
        (guards against a silently-empty walk making the test vacuous)."""
        from kmamiz_tpu.analysis import rules as lint_rules
        from kmamiz_tpu.analysis.framework import ModuleInfo

        rel = "kmamiz_tpu/graph/store.py"
        mod = ModuleInfo(rel, (REPO_ROOT / rel).read_text())
        names = {s.name for s in lint_rules.jit_sites(mod)}
        assert programs.REGISTERED_JIT_SITES[rel] <= names
