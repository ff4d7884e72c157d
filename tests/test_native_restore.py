"""Provenance rule of the native extension loader: the library is keyed
on a content hash of the committed sources plus the compiler flags
(build_info.json). A library whose record does not match the files beside
it — edited source, other flags, another host's -march=native build, no
record at all — is rebuilt when a toolchain exists and is NEVER loaded
when it does not (the pure-Python path serves instead). Also covers the
on-disk negative-cache that keeps a known-failing build from re-running
the full compiler wall in every fresh process."""
from __future__ import annotations

import json
import shutil

import pytest

from kmamiz_tpu import native


@pytest.fixture
def sandbox(tmp_path, monkeypatch):
    """Point the loader at a private build dir with clean module state."""
    build_dir = tmp_path / "build"
    build_dir.mkdir()
    monkeypatch.setattr(native, "_BUILD_DIR", build_dir)
    monkeypatch.setattr(native, "_LIB_PATH", build_dir / "libkmamiz_native.so")
    monkeypatch.setattr(
        native, "_BUILD_INFO_PATH", build_dir / "build_info.json"
    )
    monkeypatch.setattr(
        native, "_FAIL_INFO_PATH", build_dir / "build_failed.json"
    )
    monkeypatch.setattr(native, "_lib", None)
    monkeypatch.setattr(native, "_load_failed", False)
    return build_dir


def _plant_so(build_dir, **record_overrides) -> None:
    """Simulate a library left on disk by something else (a disk copy, a
    build cache restore): a real .so plus a provenance record that is
    this host's own key with `record_overrides` applied."""
    real = native._REPO_ROOT / "native" / "build" / "libkmamiz_native.so"
    if real.exists():
        shutil.copy(real, build_dir / "libkmamiz_native.so")
    else:  # toolchain-less CI: any file marks "some .so is there"
        (build_dir / "libkmamiz_native.so").write_bytes(b"\x7fELF-stub")
    record = {**native._build_key("native"), **record_overrides}
    (build_dir / "build_info.json").write_text(json.dumps(record))


class TestBuildKey:
    def test_own_key_matches(self, sandbox):
        _plant_so(sandbox)
        assert native._build_matches()

    def test_source_edit_changes_the_hash(self, sandbox, tmp_path, monkeypatch):
        _plant_so(sandbox)
        edited = tmp_path / "kmamiz_native.cpp"
        edited.write_bytes(native._SOURCES[0].read_bytes() + b"\n// edit\n")
        monkeypatch.setattr(
            native, "_SOURCES", [edited, *native._SOURCES[1:]]
        )
        assert not native._build_matches()

    def test_other_flags_do_not_match(self, sandbox):
        _plant_so(sandbox, flags=["-O0"])
        assert not native._build_matches()

    def test_native_so_from_other_cpu_does_not_match(self, sandbox):
        _plant_so(sandbox, cpu="other-host-flags")
        assert not native._build_matches()

    def test_generic_build_is_portable(self, sandbox):
        # a -march-less .so cannot SIGILL on a smaller host: its key
        # carries no cpu signature at all
        _plant_so(sandbox, **native._build_key("generic"))
        assert native._build_matches()

    def test_missing_record_does_not_match(self, sandbox):
        _plant_so(sandbox)
        (sandbox / "build_info.json").unlink()
        assert not native._build_matches()

    def test_mtime_is_not_part_of_the_key(self, sandbox):
        """A checkout copied by a tool gets fresh mtimes everywhere; the
        key is content, so the copy's library still matches."""
        import os

        _plant_so(sandbox)
        os.utime(sandbox / "libkmamiz_native.so", (1, 1))  # "older" .so
        assert native._build_matches()


class TestLoadPaths:
    def test_mismatch_without_toolchain_refuses_to_load(
        self, sandbox, monkeypatch
    ):
        """Mismatching .so + no compiler: the loader must refuse the .so
        and every public entry point must degrade to None (the
        pure-Python fallback), not raise."""
        _plant_so(sandbox, sources="0" * 64)
        monkeypatch.setattr(native, "_build", lambda: False)
        assert native._load() is None
        assert not native.available()
        assert native._load_failed  # sticky: probed once per process
        assert native.build_report()["available"] is False
        # ingest-path entry points fall back instead of crashing
        assert native.strip_istio_proxy_prefix(["line"]) is None
        assert native.parse_envoy_lines(["line"]) is None
        assert native.split_groups(b"[]", 2) is None
        assert native.process_body_groups([([], [])]) is None

    def test_unknown_provenance_without_toolchain_refuses_to_load(
        self, sandbox, monkeypatch
    ):
        """A .so with no record at all used to load "as-is" when no
        rebuild was possible; it no longer does."""
        _plant_so(sandbox)
        (sandbox / "build_info.json").unlink()
        monkeypatch.setattr(native, "_build", lambda: False)
        assert native._load() is None

    def test_mismatch_with_toolchain_rebuilds(self, sandbox):
        """Mismatching .so + working compiler: the loader rebuilds from
        the sources on disk and the record it writes is this host's key."""
        _plant_so(sandbox, sources="0" * 64)
        lib = native._load()
        if lib is None:  # environment genuinely lacks a toolchain
            pytest.skip("no C++ toolchain available")
        info = native.build_info()
        assert info == native._build_key(info["march"])
        assert info["sources"] == native.source_hash()
        assert native.strip_istio_proxy_prefix([]) == []
        report = native.build_report()
        assert report["available"]
        assert report["buildInfo"]["sources"] == report["sourceHash"]

    def test_matching_so_loads_without_building(self, sandbox, monkeypatch):
        real_dir = native._REPO_ROOT / "native" / "build"
        try:
            real_info = json.loads((real_dir / "build_info.json").read_text())
        except (OSError, ValueError):
            pytest.skip("no prebuilt native library")
        if real_info != native._build_key(real_info.get("march", "native")):
            pytest.skip("prebuilt native library is not this checkout's")
        _plant_so(sandbox, **real_info)

        def no_build():
            raise AssertionError("a matching library must not rebuild")

        monkeypatch.setattr(native, "_build", no_build)
        assert native._load() is not None


class TestBuildFailureNegativeCache:
    def test_failure_recorded_and_skipped(self, sandbox, monkeypatch):
        calls = []

        def failing_run(*args, **kwargs):
            calls.append(args)
            raise native.subprocess.SubprocessError("no compiler")

        monkeypatch.setattr(native.subprocess, "run", failing_run)
        assert not native._build()
        assert calls  # first process really attempts the compile
        assert (sandbox / "build_failed.json").exists()

        calls.clear()
        assert not native._build()  # marker short-circuits
        assert calls == []

    def test_source_change_invalidates_marker(self, sandbox):
        (sandbox / "build_failed.json").write_text(
            json.dumps({"cpu": native._cpu_signature(), "sources": "0" * 64})
        )
        assert not native._build_known_failed()

    def test_other_host_marker_ignored(self, sandbox):
        (sandbox / "build_failed.json").write_text(
            json.dumps({"cpu": "other", "sources": native.source_hash()})
        )
        assert not native._build_known_failed()

    def test_successful_build_clears_marker(self, sandbox):
        (sandbox / "build_failed.json").write_text(
            json.dumps({"cpu": native._cpu_signature(), "sources": "0" * 64})
        )
        if not native._build():
            pytest.skip("no C++ toolchain available")
        assert not (sandbox / "build_failed.json").exists()
