"""What PR 21 (chip bring-up) added, checked on the CPU: the one
compile-cache rule, the device block every server reports, a Pallas
kernel that raises instead of silently interpreting, launchers whose
parents stay off JAX, a tree that names nothing PR 30 removed, and
chip_smoke.py itself — refusing a CPU with no
arguments, and its four phases driven at a tiny size through the
test-only --tiny argument."""
from __future__ import annotations

import json
import os
import subprocess
import sys
import urllib.request
from pathlib import Path

import jax.numpy as jnp
import numpy as np
import pytest

ROOT = Path(__file__).resolve().parent.parent


def _run(code_or_argv, env_extra=None, env_drop=(), timeout=300):
    env = {k: v for k, v in os.environ.items() if k not in env_drop}
    env.update(env_extra or {})
    argv = (
        [sys.executable, "-c", code_or_argv]
        if isinstance(code_or_argv, str)
        else code_or_argv
    )
    return subprocess.run(
        argv, cwd=str(ROOT), env=env, capture_output=True, text=True,
        timeout=timeout,
    )


# -- one rule for where the persistent compilation cache lives ---------------

_CACHE_PROBE = """
import json, sys
import jax
updates = []
real_update = jax.config.update
def spy(name, value):
    updates.append(name)
    return real_update(name, value)
jax.config.update = spy
from kmamiz_tpu.core import compile_cache, programs
directory = compile_cache.enable()
compile_cache.enable()  # idempotent
import jax.numpy as jnp
jax.jit(lambda x: x * 2 + 1)(jnp.arange(8.0)).block_until_ready()
print(json.dumps({
    "dir": directory,
    "config_dir": jax.config.jax_compilation_cache_dir,
    "touched_dir_option": "jax_compilation_cache_dir" in updates,
    "hints": programs.hints_path(),
    "stats": compile_cache.stats(),
}))
"""


class TestCompileCacheRule:
    def test_env_set_means_code_leaves_the_directory_alone(self, tmp_path):
        placed = tmp_path / "x"
        res = _run(
            _CACHE_PROBE,
            {"JAX_COMPILATION_CACHE_DIR": str(placed), "JAX_PLATFORMS": "cpu"},
            env_drop=("KMAMIZ_SHAPE_HINTS",),
        )
        assert res.returncode == 0, res.stderr[-2000:]
        out = json.loads(res.stdout.strip().splitlines()[-1])
        assert out["dir"] == out["config_dir"] == str(placed)
        assert out["touched_dir_option"] is False
        assert out["stats"]["placedBy"] == "JAX_COMPILATION_CACHE_DIR"
        assert out["hints"] == str(placed / "shape_hints.json")
        # JAX itself put the compiled program there
        assert out["stats"]["misses"] >= 1
        assert any(placed.iterdir())

    def test_env_unset_means_checkout_dot_xla_cache(self):
        res = _run(
            _CACHE_PROBE,
            {"JAX_PLATFORMS": "cpu"},
            env_drop=("JAX_COMPILATION_CACHE_DIR", "KMAMIZ_SHAPE_HINTS"),
        )
        assert res.returncode == 0, res.stderr[-2000:]
        out = json.loads(res.stdout.strip().splitlines()[-1])
        want = str(ROOT / ".xla-cache")
        assert out["dir"] == out["config_dir"] == want
        assert out["touched_dir_option"] is True
        assert out["stats"]["placedBy"] == "checkout"
        assert out["hints"] == os.path.join(want, "shape_hints.json")

    def test_no_cache_path_is_built_from_tempfile_pid_or_clock(self):
        """The directory name is part of JAX's cache key: the rule's one
        function may read the environment and the checkout, nothing else."""
        src = (ROOT / "kmamiz_tpu" / "core" / "compile_cache.py").read_text()
        for banned in ("tempfile", "getpid", "time."):
            assert banned not in src, banned
        # and nobody else sets the option
        offenders = [
            str(p.relative_to(ROOT))
            for p in ROOT.rglob("*.py")
            if ".scratch" not in p.parts
            and p.name not in ("compile_cache.py", "test_chip_bringup.py")
            and "jax_compilation_cache_dir" in p.read_text()
        ]
        assert offenders == []


# -- every server names its device -------------------------------------------


def _get_json(url: str) -> dict:
    with urllib.request.urlopen(url, timeout=30) as res:
        return json.loads(res.read())


class TestDeviceBlock:
    def test_dp_timings_names_the_device(self):
        import jax

        from kmamiz_tpu.server.dp_server import DataProcessorServer
        from kmamiz_tpu.server.processor import DataProcessor

        server = DataProcessorServer(
            DataProcessor(trace_source=lambda *a: []), host="127.0.0.1", port=0
        )
        server.start()
        try:
            timings = _get_json(f"http://127.0.0.1:{server.port}/timings")
        finally:
            server.stop()
        device = timings["device"]
        assert device["platform"] == "cpu"
        assert device["device_kind"] == jax.devices()[0].device_kind
        assert device["count"] == len(jax.devices()) == 8
        # EVERY local device, not device 0 only
        assert [row["id"] for row in device["memory"]] == list(range(8))
        assert device["mesh"] == {"spans": 8}  # active_mesh() took them all
        assert device["jax"] == jax.__version__
        # the other blocks chip_smoke.py reads
        assert timings["native"]["sourceHash"]
        assert timings["compileCache"]["dir"]
        assert timings["sparse"]["backend"] == "sparse"

    def test_mesh_off_reports_no_mesh(self, monkeypatch):
        from kmamiz_tpu.telemetry import device as tel_device

        monkeypatch.setenv("KMAMIZ_MESH", "0")
        assert tel_device.device_block()["mesh"] is None

    def test_api_health_names_the_device(self, monkeypatch):
        from kmamiz_tpu.api.handlers.health import HealthHandler
        from kmamiz_tpu.api.router import Request

        class Ctx:
            processor = object()

        req = Request(method="GET", path="/", params={}, query={}, body=None)
        with_proc = HealthHandler(Ctx())
        payload = with_proc._health(req).payload
        assert payload["device"]["platform"] == "cpu"
        assert len(payload["device"]["memory"]) == 8
        timings = with_proc._timings(req).payload
        assert timings["device"]["count"] == 8
        assert "native" in timings and "compileCache" in timings

        assert "graph" not in timings  # this processor has no graph

        # serve-only / simulator: no processor, no device, no jax import
        Ctx.processor = None
        without = HealthHandler(Ctx())
        assert without._health(req).payload["device"] is None
        assert "native" not in without._timings(req).payload


    def test_api_timings_count_both_graphs(self, pdas_traces):
        """chip_smoke.py phase B watches a realtime tick grow the device
        graph and the host dependency cache by the same number of edges."""
        from kmamiz_tpu.api.handlers.health import HealthHandler
        from kmamiz_tpu.api.router import Request
        from kmamiz_tpu.config import Settings
        from kmamiz_tpu.server.initializer import AppContext, Initializer
        from kmamiz_tpu.server.processor import DataProcessor
        from kmamiz_tpu.server.storage import MemoryStore

        s = Settings()
        s.external_data_processor = ""
        s.storage_uri = "memory://"
        processor = DataProcessor(
            trace_source=lambda lb, t, lim: [pdas_traces], k8s_source=None
        )
        ctx = AppContext.build(app_settings=s, store=MemoryStore(), processor=processor)
        Initializer(ctx).register_data_caches()
        handler = HealthHandler(ctx)
        req = Request(method="GET", path="/", params={}, query={}, body=None)
        assert handler._timings(req).payload["graph"] == {
            "deviceEdges": 0, "hostEdges": 0, "hostEndpoints": 0,
        }
        ctx.operator.retrieve_realtime_data()
        sizes = handler._timings(req).payload["graph"]
        assert sizes["deviceEdges"] == sizes["hostEdges"] > 0
        assert sizes["hostEndpoints"] > 0


# -- no fallback that hides the device ---------------------------------------


class TestNoSilentInterpret:
    def test_window_stats_pallas_on_cpu_raises(self):
        from kmamiz_tpu.ops import window

        n = 64
        with pytest.raises(Exception):
            stats = window.window_stats(
                jnp.zeros(n, jnp.int32),
                jnp.zeros(n, jnp.int32),
                jnp.full(n, 2, jnp.int8),
                jnp.ones(n, jnp.float32),
                jnp.zeros(n, jnp.int32),
                jnp.ones(n, bool),
                num_endpoints=4,
                num_statuses=2,
                backend="pallas",
            )
            np.asarray(stats.count)

    def test_device_scorer_fallback_is_counted(self, monkeypatch):
        """api/handlers/graph.py still answers from the host when the
        device scorer raises — but no longer silently."""
        from kmamiz_tpu.api.handlers.graph import GraphHandler
        from kmamiz_tpu.resilience import metrics as res_metrics

        class Boom:
            n_edges = 1

            def invalidate_labels(self):
                pass

            def service_scores(self, *_a):
                raise RuntimeError("device scorer down")

        class Cache:
            last_update = None

            def get_data(self, *_a):
                return None

            def get_label(self, _name):
                return None

        class Ctx:
            class processor:
                graph = Boom()

            class cache:
                @staticmethod
                def get(_name):
                    return Cache()

        handler = GraphHandler(Ctx())
        assert handler._build_service_instability(None, False) == []
        assert res_metrics.resilience_summary()["scorerHostFallback"] == 1


# -- one process per chip: parents stay off JAX ------------------------------


class TestParentsStayOffJax:
    def test_deliberate_cpu_children_pin_their_platform(self):
        """The launchers whose children are CPU processes by design set
        JAX_PLATFORMS=cpu unconditionally instead of defaulting to it."""
        for rel in (
            "kmamiz_tpu/soak/engine.py",
            "kmamiz_tpu/scenarios/runner.py",
            "tools/fleet_bench.py",
            "tools/chaos_probe.py",
        ):
            src = (ROOT / rel).read_text()
            assert 'os.environ.get("JAX_PLATFORMS", "cpu")' not in src, rel
            assert '"JAX_PLATFORMS": "cpu"' in src or (
                'env["JAX_PLATFORMS"] = "cpu"' in src
            ), rel


    def test_tree_names_no_removed_command_symbol_or_variable(self):
        """One benchmark (benchmarks/run.py) and two neighbour reductions in
        the model plane: no source file, deployment file or document a
        newcomer reads still sends them to what PR 30 removed."""
        import re

        removed = re.compile(
            r"python3?\s+(\./)?bench\.py|bench_driver|probe_headline"
            r"|fused_neighbor_sums|fused_gated_bias|fused_route|fused_enabled"
            r"|fused_interpret|_fused_call|_fused_kernel|KMAMIZ_SPARSE_TILE"
            r"|KMAMIZ_SPARSE_NODE_MAX|KMAMIZ_SAGE_FUSED|sparse_tile\b"
            r"|gaveWay|lastGaveWayNodes|nodeBudget"
        )
        skip = {
            ".git", ".scratch", ".xla-cache", ".pytest_cache", "__pycache__",
            "chiprun_out", "kmamiz-data", "build", "dist", "node_modules",
        }
        files = [ROOT / "README.md", ROOT / "MODELS.md"]
        files += sorted((ROOT / "docs").glob("*.md"))
        files += [ROOT / ".claude" / "skills" / "verify" / "SKILL.md"]
        for top, dirs, names in os.walk(ROOT):
            dirs[:] = [d for d in dirs if d not in skip]
            under_deploy = Path(top).relative_to(ROOT).parts[:1] == ("deploy",)
            files += [
                Path(top) / n for n in names if n.endswith(".py") or under_deploy
            ]
        assert len(files) > 300  # the walk found the tree
        hits = []
        for path in files:
            if path == Path(__file__).resolve() or not path.exists():
                continue
            text = path.read_text(errors="replace")
            hits += [
                f"{path.relative_to(ROOT)}: {m.group(0)}" for m in removed.finditer(text)
            ]
        assert not hits, hits
        for gone in ("bench.py", "tools/bench_driver.py", "tools/probe_headline.py"):
            assert not (ROOT / gone).exists(), gone


# -- chip_smoke.py -------------------------------------------------------------


class TestChipSmoke:
    def test_no_arguments_refuses_a_cpu(self, tmp_path):
        res = _run(
            [sys.executable, "chip_smoke.py", "--out", str(tmp_path)],
            {"JAX_PLATFORMS": "cpu"},
            timeout=600,
        )
        assert res.returncode != 0
        assert res.stdout.strip() == ""  # no result line
        # JAX's own message names the platform it could not find
        assert "tpu" in res.stderr.lower()
        assert "phase A FAILED" in res.stderr
        report = json.loads((tmp_path / "chip_smoke_report.json").read_text())
        assert report["ok"] is False and "device" not in report

    def test_outside_a_checkout_exits_nonzero_with_no_result(self, tmp_path):
        alone = tmp_path / "alone"
        alone.mkdir()
        (alone / "chip_smoke.py").write_text((ROOT / "chip_smoke.py").read_text())
        res = subprocess.run(
            [sys.executable, "chip_smoke.py"],
            cwd=str(alone), capture_output=True, text=True, timeout=120,
            env={k: v for k, v in os.environ.items() if k != "PYTHONPATH"},
        )
        assert res.returncode != 0
        assert res.stdout.strip() == ""

    def test_four_phases_at_tiny_size_and_parent_off_jax(self, tmp_path):
        """The phase functions end to end on the CPU (8 virtual devices,
        so the mesh assertions run): real dp_server / api.app children,
        the trainer + checkpoint + forecast child, the kernel child with
        Pallas interpreted."""
        code = (
            "import sys, chip_smoke\n"
            f"rc = chip_smoke.main(['--tiny', '--out', {str(tmp_path)!r}])\n"
            "print('JAXFREE', not any(m == 'jax' or m.startswith('jax.') "
            "or m.startswith('jaxlib') for m in sys.modules), file=sys.stderr)\n"
            "sys.exit(rc)\n"
        )
        res = _run(
            code,
            {"JAX_COMPILATION_CACHE_DIR": str(tmp_path / "xla")},
            timeout=600,
        )
        assert res.returncode == 0, res.stderr[-4000:]
        assert "JAXFREE True" in res.stderr
        last = json.loads(res.stdout.strip().splitlines()[-1])
        assert last == {
            "ok": True,
            "device": {"platform": "cpu", "kind": "cpu", "count": 8},
        }
        report = json.loads((tmp_path / "chip_smoke_report.json").read_text())
        assert report["ok"] and set(report["phases"]) == set("ABCD")
        a, b, c, d = (report["phases"][k] for k in "ABCD")
        assert all(p["passed"] for p in (a, b, c, d))
        # the mesh path, by call counts
        assert a["device"]["mesh"] == {"spans": 8}
        assert a["path"]["mesh.sharded_window_edges_compact"] > 0
        assert a["path"]["mesh.sharded_window_stats"] > 0
        assert b["path"]["mesh.sharded_service_scores"] > 0
        assert c["mesh"] == {"slots": 8} and c["shardedSlotGradParity"]
        # the cache was placed from outside and shared by the children
        assert a["compileCache"]["placedBy"] == "JAX_COMPILATION_CACHE_DIR"
        assert a["compileCache"]["dir"] == str(tmp_path / "xla")
        assert b["compileCache"]["hits"] > 0
        # native parser built from the sources in this checkout
        assert a["native"]["buildInfo"]["sources"] == a["native"]["sourceHash"]
        # kernels: interpreted here, and the planned reductions counted;
        # nothing is left of the fused pair
        assert d["segment_stats_matmul"] == d["planned_neighbor_sum"] == "interpret"
        assert d["planned_attention"] == d["planned_neighbor_sum_w126"] == "interpret"
        assert d["planned_attention_w124"] == "interpret"  # the spare lanes past the first 128
        assert d["planned_gated_sum"] == "interpret"  # STLGT's two walks against the XLA items
        assert d["planned_aggregate"] == "interpret"  # PNA's three walks against the XLA items
        assert set(d["routes"]) == {"backend", "planned", "attention", "sharded", "mxu_products"}
        assert d["routes"]["mxu_products"]["planned_attention_sum"] == 4  # three passes and the expand
        assert d["routes"]["planned"] > d["routes"]["attention"] > 0
        assert not [k for k in d if "fused" in k]
        assert len(a["graph"]["signature"]) == 64
        assert set(b["scorers"]) == {"instability", "coupling", "cohesion"}
        # every tick ADDED edges, and the API's ticks grew the device
        # graph and the host graph by the same number
        assert a["tickNewEdges"] == [6, 6, 6]
        before, after = b["graph"]["beforeTicks"], b["graph"]["afterTicks"]
        assert before["deviceEdges"] == before["hostEdges"]
        assert after["deviceEdges"] == after["hostEdges"] == before["hostEdges"] + 18
