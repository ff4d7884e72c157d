"""From a profiler trace to the numbers the per-layer metrics read.

Device-op durations summed and credited to programs by name, busy time as
the UNION of the intervals in which an operation ran, self time of nested
operations, idle gaps named by the host span that covers them, and shares by
kind of operation: the benchmark's own reduction, nothing of the program's
(its `telemetry/profiling/device_attr.py` attributes a live capture to the
operator's `/profile`; no function of it is used or copied here). It reads the
profiler's own `.xplane.pb` (jax 0.9 writes no `.trace.json`) through
`jax.profiler.ProfileData`, or the same events from a JSON file, which is
how the recorded trace beside this file is kept.

The events of a trace, as this module names them:

- device ops: the line "XLA Ops" of each plane "/device:TPU:<i>": one event
  per executed HLO instruction, nested where an instruction (a `while`)
  contains others. Start and duration are nanoseconds. The v5e's profiler
  names an event by the instruction's whole HLO text and gives it no kind,
  so `op_kind` reads the kind from that text ("Async XLA Ops", the DMAs in
  flight beside the compute, are not device-busy time and are not read).
- device modules: the line "XLA Modules" of the same planes: one event per
  executed program, named `jit_<function>(<fingerprint>)`.
- host spans: events of the planes "/host:*" whose name is that of a
  benchmark span (`harness/spans.py` writes each as a `TraceAnnotation`).

All three are on one clock.
"""
from __future__ import annotations

import glob
import json
import os
import re
from dataclasses import dataclass
from functools import cached_property
from typing import Callable, Dict, Iterable, List, Optional, Sequence, Tuple

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
SPAN_PREFIXES = ("setup.", "refresh.", "bench.")


@dataclass
class Event:
    name: str
    start_ns: int
    dur_ns: int
    device: int = 0  # index of the device plane; 0 for host events
    category: str = ""  # `op_kind` of a device op
    self_ns: int = 0  # duration minus that of the events nested inside

    @property
    def end_ns(self) -> int:
        return self.start_ns + self.dur_ns


# -- what an event is, from its HLO text ---------------------------------------

_OPCODE = re.compile(r"\s([a-z][a-z0-9\-]*)\(")
_LEAD = re.compile(r"\[(\d+)")
_INDEX_OPERAND = re.compile(r"s32\[(\d+)")


def op_kind(text: str) -> str:
    """The kind of a device op, from its HLO text
    `%name = <output shape> <opcode>(<operands>), kind=..., calls=...`.

    XLA's TPU backend emits a gather or a scatter as a custom fusion
    (`kind=kCustom`) that takes an `s32[...]` index operand: one output row
    per index is a gather, one operand row per index a scatter (segment_sum
    and segment_max lower to it). Every other op is its opcode, and a name
    that is no HLO text is kind "" (other backends, recorded fixtures)."""
    head, sep, rest = text.partition(" = ")
    if not sep:
        return ""
    m = _OPCODE.search(" " + rest)
    if m is None:
        return ""
    opcode = m.group(1)
    if opcode != "fusion" or "kind=kCustom" not in rest:
        return opcode
    out_shape, operands = rest[: m.start()], rest[m.end() - 1 :]
    index = _INDEX_OPERAND.search(operands)
    lead = _LEAD.search(out_shape)
    if index is None or lead is None:
        return "fusion"
    return "gather" if lead.group(1) == index.group(1) else "scatter"


def short_name(text: str) -> str:
    """`%fusion.273 f32[131072,64]`: the instruction's name and the shape it
    makes, without layouts."""
    head, sep, rest = text.partition(" = ")
    if not sep:
        return text[:80]
    m = _OPCODE.search(" " + rest)
    shape = re.sub(r"\{[^}]*\}", "", rest[: m.start()] if m else "").strip()
    return f"{head} {shape}"[:80]


# -- reading -----------------------------------------------------------------


def read_xplane(path: str) -> Dict[str, list]:
    """An `.xplane.pb` as {"ops": [...], "modules": [...], "spans": [...]},
    each a list of [name, start_ns, dur_ns, device, category]."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    out: Dict[str, list] = {"ops": [], "modules": [], "spans": []}
    device_index = 0
    for plane in data.planes:
        if plane.name.startswith("/device:") and "TPU" in plane.name.upper():
            lines = {ln.name: ln for ln in plane.lines}
            if OPS_LINE not in lines:
                continue
            for ev in lines[OPS_LINE].events:
                out["ops"].append(
                    [short_name(ev.name), int(ev.start_ns), int(ev.duration_ns),
                     device_index, op_kind(ev.name)]
                )
            if MODULES_LINE in lines:
                for ev in lines[MODULES_LINE].events:
                    out["modules"].append(
                        [ev.name, int(ev.start_ns), int(ev.duration_ns), device_index, ""]
                    )
            device_index += 1
        elif plane.name.startswith("/host:"):
            for ln in plane.lines:
                for ev in ln.events:
                    if ev.name.startswith(SPAN_PREFIXES):
                        out["spans"].append(
                            [ev.name, int(ev.start_ns), int(ev.duration_ns), 0, ""]
                        )
    return out


def find_xplane(directory: str) -> Optional[str]:
    found = sorted(glob.glob(os.path.join(directory, "**", "*.xplane.pb"), recursive=True))
    return found[-1] if found else None


# -- arithmetic --------------------------------------------------------------


def union_ns(intervals: Iterable[Tuple[int, int]]) -> int:
    """Total length covered by `intervals` ([start, end) in ns)."""
    total = 0
    edge = None
    for start, end in sorted(intervals):
        if edge is None or start > edge:
            total += end - start
            edge = end
        elif end > edge:
            total += end - edge
            edge = end
    return total


def gaps(intervals: Iterable[Tuple[int, int]], window: Tuple[int, int]) -> List[Tuple[int, int]]:
    """The parts of `window` that no interval covers."""
    out = []
    edge = window[0]
    for start, end in sorted(intervals):
        if start > edge:
            out.append((edge, min(start, window[1])))
        edge = max(edge, end)
        if edge >= window[1]:
            break
    if edge < window[1]:
        out.append((edge, window[1]))
    return [(a, b) for a, b in out if b > a]


def with_self_time(events: Sequence[Event]) -> List[Event]:
    """Set each event's `self_ns`: its duration less that of its direct
    children (events of the same device that lie inside it)."""
    out: List[Event] = []
    by_device: Dict[int, List[Event]] = {}
    for ev in events:
        by_device.setdefault(ev.device, []).append(ev)
    for evs in by_device.values():
        evs.sort(key=lambda e: (e.start_ns, -e.dur_ns))
        stack: List[Event] = []
        for ev in evs:
            ev.self_ns = ev.dur_ns
            while stack and ev.start_ns >= stack[-1].end_ns:
                stack.pop()
            if stack:
                stack[-1].self_ns -= ev.dur_ns
            stack.append(ev)
            out.append(ev)
    return out


def clip(events: Iterable[Event], window: Tuple[int, int]) -> List[Event]:
    out = []
    for ev in events:
        start, end = max(ev.start_ns, window[0]), min(ev.end_ns, window[1])
        if end > start:
            out.append(Event(ev.name, start, end - start, ev.device, ev.category))
    return out


# -- the reduced trace -------------------------------------------------------


@dataclass
class Reduced:
    """One traced window. Times in ns on the trace's clock."""

    window: Tuple[int, int]
    ops: List[Event]  # device ops inside the window, with self time
    modules: List[Event]  # executed programs inside the window
    spans: List[Event]  # host spans of the benchmark that touch the window
    devices: int = 1

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) / 1e9

    @cached_property
    def busy_ns(self) -> float:
        """Time in which an operation ran on the device: the union of the
        ops' intervals, averaged over the devices used."""
        per_device: Dict[int, List[Tuple[int, int]]] = {}
        for ev in self.ops:
            per_device.setdefault(ev.device, []).append((ev.start_ns, ev.end_ns))
        return sum(union_ns(v) for v in per_device.values()) / max(self.devices, 1)

    @property
    def busy_s(self) -> float:
        return self.busy_ns / 1e9

    def share_of_busy(self, wanted: Callable[[Event], bool]) -> float:
        total = sum(ev.self_ns for ev in self.ops)
        return sum(ev.self_ns for ev in self.ops if wanted(ev)) / total if total else 0.0

    def program_ns(self) -> Dict[str, float]:
        """Device time of each executed program, by its module's name
        without the fingerprint: `jit_run(123)` -> `jit_run`."""
        out: Dict[str, float] = {}
        for ev in self.modules:
            name = ev.name.split("(")[0]
            out[name] = out.get(name, 0.0) + ev.dur_ns / max(self.devices, 1)
        return out

    def idle_gaps(self) -> List[Tuple[int, int]]:
        """Device 0's idle stretches inside the window."""
        return gaps(((e.start_ns, e.end_ns) for e in self.ops if e.device == 0), self.window)

    def covering_span(self, at_ns: int) -> str:
        """The innermost benchmark span that covers `at_ns`."""
        inside = [s for s in self.spans if s.start_ns <= at_ns < s.end_ns]
        return min(inside, key=lambda s: s.dur_ns).name if inside else "outside spans"

    def breakdown(self) -> Dict[str, list]:
        """The ten device operations that took most self time, and the ten
        longest idle gaps, each named by the host span that covered its
        middle and by where in that span it lies."""
        by_name: Dict[str, float] = {}
        for ev in self.ops:
            label = f"{ev.category}:{ev.name}" if ev.category else ev.name
            by_name[label] = by_name.get(label, 0.0) + ev.self_ns / max(self.devices, 1)
        device_ops = sorted(by_name.items(), key=lambda kv: -kv[1])[:10]
        named = []
        for start, end in sorted(self.idle_gaps(), key=lambda g: g[0] - g[1])[:10]:
            middle = (start + end) // 2
            span = self.covering_span(middle)
            where = "before first device op" if start == self.window[0] else (
                "after last device op" if end == self.window[1] else "between device ops")
            named.append([f"{span}: {where}", (end - start) / 1e9])
        return {
            "device_ops": [[name, ns / 1e9] for name, ns in device_ops],
            "idle_gaps": named,
        }


def reduce_events(raw: Dict[str, list], window_span: str) -> Reduced:
    """`raw` as `read_xplane` gives it. The window runs from the start of the
    first host span named `window_span` to the end of the last."""

    def events(key: str) -> List[Event]:
        return [Event(n, int(s), int(d), int(dev), cat) for n, s, d, dev, cat in raw[key]]

    spans = events("spans")
    marks = [s for s in spans if s.name == window_span]
    if not marks:
        raise ValueError(f"the trace holds no host span named {window_span!r}")
    window = (min(s.start_ns for s in marks), max(s.end_ns for s in marks))
    ops = events("ops")
    devices = len({e.device for e in ops}) or 1
    return Reduced(
        window=window,
        ops=with_self_time(clip(ops, window)),
        modules=clip(events("modules"), window),
        spans=[s for s in spans if s.end_ns > window[0] and s.start_ns < window[1]],
        devices=devices,
    )


def reduce_dir(directory: str, window_span: str) -> Optional[Reduced]:
    """The profiler's output directory -> Reduced, or None where the
    profiler wrote no trace."""
    path = find_xplane(directory)
    if path is None:
        return None
    return reduce_events(read_xplane(path), window_span)


def load_json(path: str) -> Dict[str, list]:
    with open(path, encoding="utf-8") as f:
        return json.load(f)
