"""The kernel layer's module boundaries (kmamiz_tpu/ops/): what each module may import and touch, read from the source.

`edge_plan` makes the plan on the host and stays free of Pallas; `walk` is the framework every family's walks share
and knows no family and no model; a module reaches another `ops` module through its public names alone; the route
counters are `walk`'s, behind `walk.count` and `walk.route_counts`; and `planned_impl` is read through `walk`'s
attribute, the one place a test patches it.
"""
from __future__ import annotations

import ast
from pathlib import Path

import pytest

PACKAGE = Path(__file__).resolve().parents[1] / "kmamiz_tpu"
OPS = PACKAGE / "ops"
FAMILIES = {"sparse", "sparse_sum", "sparse_gat", "sparse_gated", "sparse_gatv2", "sparse_pna"}
COUNTERS = {"_counts", "_lock", "_mxu_calls", "_route_counts", "_route_lock"}


def _tree(path: Path) -> ast.Module:
    return ast.parse(path.read_text(), filename=str(path))


def _imports(tree: ast.Module):
    """(module, name or None) for every import: `from a.b import c` -> ("a.b", "c"), `import a.b` -> ("a.b", None)."""
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            yield from ((alias.name, None) for alias in node.names)
        elif isinstance(node, ast.ImportFrom) and node.module:
            yield from ((node.module, alias.name) for alias in node.names)


def _ops_modules(tree: ast.Module) -> set:
    """The `ops` modules a file binds by name (`from kmamiz_tpu.ops import walk`)."""
    return {name for module, name in _imports(tree) if module == "kmamiz_tpu.ops" and name}


def _edge_plan_imports_no_pallas():
    return [(m, n) for m, n in _imports(_tree(OPS / "edge_plan.py")) if "pallas" in f"{m}.{n}"]


def _walk_imports_no_family_and_no_model():
    bad = []
    for module, name in _imports(_tree(OPS / "walk.py")):
        owner = name if module == "kmamiz_tpu.ops" else module.rsplit(".", 1)[-1]
        if module.startswith("kmamiz_tpu.models") or (module.startswith("kmamiz_tpu.ops") and owner in FAMILIES):
            bad.append((module, name))
    return bad


def _no_private_name_from_another_ops_module():
    bad = []
    for path in PACKAGE.rglob("*.py"):
        tree, here = _tree(path), path.stem if path.parent == OPS else None
        for module, name in _imports(tree):
            if module.startswith("kmamiz_tpu.ops.") and name and name.startswith("_") and module.rsplit(".", 1)[1] != here:
                bad.append(f"{path.relative_to(PACKAGE)}: from {module} import {name}")
        bound = _ops_modules(tree)
        for node in ast.walk(tree):
            if (
                isinstance(node, ast.Attribute) and isinstance(node.value, ast.Name) and node.value.id in bound
                and node.attr.startswith("_") and node.value.id != here
            ):
                bad.append(f"{path.relative_to(PACKAGE)}:{node.lineno}: {node.value.id}.{node.attr}")
    return bad


def _only_walk_touches_the_counters():
    """Beyond the private attributes the rule above forbids everywhere: no other `ops` module keeps a counter or a
    lock of its own under these names, and `walk` does."""
    bad, walk_names = [], set()
    for path in OPS.glob("*.py"):
        for node in ast.walk(_tree(path)):
            name = node.attr if isinstance(node, ast.Attribute) else node.id if isinstance(node, ast.Name) else None
            if name not in COUNTERS:
                continue
            if path.stem == "walk":
                walk_names.add(name)
            else:
                bad.append(f"{path.name}:{node.lineno}: {name}")
    return bad + [f"walk.py keeps no {name}" for name in {"_counts", "_lock", "_mxu_calls"} - walk_names]


def _planned_impl_is_read_through_walk():
    return [
        str(path.relative_to(PACKAGE))
        for path in PACKAGE.rglob("*.py")
        for module, name in _imports(_tree(path))
        if name == "planned_impl"
    ]


@pytest.mark.parametrize(
    "rule",
    (
        _edge_plan_imports_no_pallas,
        _walk_imports_no_family_and_no_model,
        _no_private_name_from_another_ops_module,
        _only_walk_touches_the_counters,
        _planned_impl_is_read_through_walk,
    ),
    ids=lambda rule: rule.__name__.strip("_"),
)
def test_the_kernel_layer_keeps_its_module_boundaries(rule):
    assert rule() == []
