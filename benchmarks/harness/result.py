"""The one JSON object a run prints last.

With `--trace 0` its metrics are the cell's end-to-end metrics, taken by the
driver; with `--trace 1` its per-layer metrics, each from its own reader
(`layer_metrics/<name>.py`, `read(record) -> float or None`). A reader that
finds nothing to read returns None and its metric is left out of the line.
The line's last key, `compared`, holds each number that `correct` was decided
from beside its limit.
"""
from __future__ import annotations

import json
import math
from typing import Any, Dict

from benchmarks.harness import device
from benchmarks.harness.record import Record


def _metrics(manifest, cell: Dict[str, Any], record: Record, traced: bool):
    out: Dict[str, Dict[str, Any]] = {}
    if not traced:
        for m in manifest.metrics("end_to_end", cell["name"]):
            if m["name"] in record.end_to_end:
                out[m["name"]] = {"value": record.end_to_end[m["name"]], "unit": m["unit"]}
        return out
    for m in manifest.metrics("per_layer", cell["name"]):
        reader = manifest.load_module(f"layer_metrics/{m['name']}.py")
        value = reader.read(record)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def result_line(manifest, cell: Dict[str, Any], record: Record, traced: bool) -> str:
    line: Dict[str, Any] = {
        "correct": record.correct,
        "attempted": record.attempted,
        "failed": record.failed,
        "metrics": _metrics(manifest, cell, record, traced),
        "device": device.describe(record.devices),
    }
    if traced and record.trace is not None:
        line["device"]["busy_s"] = record.trace.busy_s
        line["device"]["window_s"] = record.trace.window_s
        line["breakdown"] = record.trace.breakdown()
    # last, so that a record cut at its end keeps it; JSON has no nan or inf
    line["compared"] = {
        name: {k: v if math.isfinite(v) else None for k, v in number.items()}
        for name, number in record.compared.items()
    }
    return json.dumps(line)
