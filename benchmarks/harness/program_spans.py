"""The program's own spans and run times, as the per-layer readers take them.

`kmamiz_tpu/telemetry/tracing.py` keeps each finished trace in a ring
(`TRACER.traces()`): spans as (name, start_ns, dur_ns, parent index) on
`time.perf_counter_ns`, relative to the trace's `t0_ns`. The benchmark's
recorder spans (`harness/spans.py`) are on `time.perf_counter`, the same
clock, so a program span belongs to the window when its start lies inside a
recorder span named `refresh.call`, and to set-up inside `setup.stack_upload`.

A program that records no such span (telemetry off, or a commit from before
the spans) gives an empty list, and the reader returns None.
"""
from __future__ import annotations

from typing import Any, List, Tuple

#: one program span: (the trace it is in, its index there)
Found = Tuple[Any, int]


def _inside(record, within: str, at_s: float) -> bool:
    return any(s.start_s <= at_s <= s.end_s for s in record.recorder.named(within))


def find(record, name: str, within: str) -> List[Found]:
    """The program's spans named `name` that started inside a recorder span
    named `within`."""
    from kmamiz_tpu.telemetry.tracing import TRACER

    out: List[Found] = []
    for tb in TRACER.traces():
        for i, (span_name, start_ns, dur_ns, _parent) in enumerate(tb.spans):
            if span_name == name and dur_ns >= 0 and _inside(
                record, within, (tb.t0_ns + start_ns) / 1e9
            ):
                out.append((tb, i))
    return out


def dur_ms(found: Found) -> float:
    tb, i = found
    return tb.spans[i][2] / 1e6


def children(found: Found) -> List[Found]:
    tb, i = found
    return [(tb, j) for j, s in enumerate(tb.spans) if s[3] == i and j != i]


def child_ms(found: Found, name: str) -> float:
    """Time of the direct children of `found` that are named `name`."""
    return sum(dur_ms(c) for c in children(found) if c[0].spans[c[1]][0] == name)


def self_ms(found: Found) -> float:
    """A span's duration minus that of its direct children."""
    return dur_ms(found) - sum(dur_ms(c) for c in children(found))


def per_call(record, value) -> "float | None":
    """Mean of `value(span)` over the `refresh.train` spans of the window,
    or None where the program recorded none."""
    calls = find(record, "refresh.train", within="refresh.call")
    if not calls:
        return None
    return sum(value(c) for c in calls) / len(calls)


def runs_within(record, within: str) -> List[Tuple[float, int]]:
    """(run_ms, units) of every run that a program of the `core/programs`
    registry reported (`Program.note_run`) and that ended inside a recorder
    span named `within`."""
    from kmamiz_tpu.core import programs

    out: List[Tuple[float, int]] = []
    for program in programs.all_programs().values():
        recent = getattr(program, "recent_runs", None)
        if recent is None:
            continue
        out.extend(
            (run_ms, units) for end_s, run_ms, units in recent()
            if _inside(record, within, end_s)
        )
    return out
