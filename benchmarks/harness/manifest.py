"""`BENCHMARK.json` and the files it names.

Everything that belongs to one configuration, one traffic mix, one driver
or one per-layer metric is a file of its own, found here by name in the
directories the manifest lists under `paths`:

    configs/<config>.json          the manifest names the file itself
    traffic/<mix>.json             parameters of a mix; names its driver
    drivers/<kind>.py              run(ctx) -> Record, one per kind of traffic
    gen/<generator>.py             generate(config, seed), named by the config
    layer_metrics/<metric>.py      read(record) -> float or None
    trace/work/<family>.py         operations and bytes of a family's kernel
    reference/<family>.py          the family's plain forward and, where they
                                   are its own, its loss and its FORWARD
                                   bounds (reference/train.py says which)

A later PR adds a file and an entry and edits nothing that is here.
"""
from __future__ import annotations

import importlib.util
import json
import sys
from pathlib import Path
from typing import Any, Dict, List


class Manifest:
    def __init__(self, path: Path) -> None:
        self.path = Path(path).resolve()
        self.root = self.path.parent
        with open(self.path, encoding="utf-8") as f:
            self.doc: Dict[str, Any] = json.load(f)
        # an absolute entry stands as it is (the tests add a directory so)
        self.dirs: List[Path] = [self.root / p for p in self.doc["paths"]]

    # -- entries -----------------------------------------------------------
    def _entry(self, key: str, name: str) -> Dict[str, Any]:
        for entry in self.doc[key]:
            if entry["name"] == name:
                return entry
        known = ", ".join(e["name"] for e in self.doc[key])
        raise KeyError(f"{key}: no entry named {name!r} (have: {known})")

    def workload(self, name: str) -> Dict[str, Any]:
        return self._entry("workloads", name)

    def config(self, name: str) -> Dict[str, Any]:
        """The configuration file's contents, as it is run."""
        entry = self._entry("configs", name)
        with open(self.root / entry["file"], encoding="utf-8") as f:
            return json.load(f)

    def metrics(self, key: str, workload: str) -> List[Dict[str, Any]]:
        """The metrics under `key` that this cell reports: those that list
        it under `workloads`, and those that list none."""
        return [
            m
            for m in self.doc[key]
            if "workloads" not in m or workload in m["workloads"]
        ]

    # -- files by name -----------------------------------------------------
    def find(self, relative: str) -> Path:
        for d in self.dirs:
            candidate = d / relative
            if candidate.is_file():
                return candidate
        raise FileNotFoundError(
            f"{relative} is in none of {[str(d) for d in self.dirs]}"
        )

    def load_json(self, relative: str) -> Dict[str, Any]:
        with open(self.find(relative), encoding="utf-8") as f:
            return json.load(f)

    def load_module(self, relative: str):
        """Import a file by path: metric names carry dots, and a directory
        a later PR adds is no package."""
        path = self.find(relative)
        name = "benchmarks_file." + "".join(
            c if c.isalnum() else "_" for c in str(path.relative_to(path.anchor))
        )
        if name in sys.modules:
            return sys.modules[name]
        spec = importlib.util.spec_from_file_location(name, path)
        module = importlib.util.module_from_spec(spec)
        sys.modules[name] = module
        try:
            spec.loader.exec_module(module)
        except BaseException:
            del sys.modules[name]
            raise
        return module
