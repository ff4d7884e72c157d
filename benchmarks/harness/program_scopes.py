"""The epoch block's device time under the program's own names.

Since PR 37 the program names the stretches of its epoch block with
`jax.named_scope`, one small fixed taxonomy (`PHASES`; docs/OBSERVABILITY.md
says what lies in each), and its registry says which instruction of a
compiled block belongs to which: `Program.scope_tables()` of
`models.sage_epoch_block[...]`, one `{instruction name: (scope path, phase,
backward)}` a signature that compiled, each read from the metadata of that
signature's executable. This module joins the table of the executable that
RAN with the traced window's device ops (`record.trace.ops`: an op is named
`%fusion.137 f32[...]`, its instruction's name first) for the ops that lie
inside a module event `jit_sage_epoch_block` on their device: the small
programs `refresh.init` runs have a `%fusion.1` of their own and are no part
of the block.

Which executable ran, the trace says: a block compiles anew for other shapes
(a check's, a warm-up's), and two signatures share most instruction names
under another numbering, so a table of the wrong one would match nearly every
op and put its time in the wrong phase. `scope_table` takes the table whose
names are the trace's both ways: the fewest traced ops that it lacks and rows
of it that were never traced (the registry keeps a program's newest sixteen
signatures, and a run compiles two or three).

All times are self time. An op of the block that the table does not hold (a
copy XLA put in, a loop's own counter and slices) or holds under no phase of
the taxonomy is `UNSCOPED`: the instrument's own residue, which
`epoch_block.unscoped_share` reports.

On a commit whose registry keeps no tables (before PR 37), or without a
trace, every function here returns None and the readers leave their metrics
out.
"""
from __future__ import annotations

from typing import Dict, Iterable, List, Optional, Tuple

PHASES = ("gather", "reduce", "collective", "dense", "loss", "optimizer", "group")
UNSCOPED = "unscoped"
BLOCK_MODULE = "jit_sage_epoch_block"
BLOCK_PROGRAM = "models.sage_epoch_block["

#: device -> phase (or UNSCOPED) -> self time in ns, of one record
ByDevice = Dict[int, Dict[str, float]]


def instruction(op_name: str) -> str:
    """`%fusion.137 f32[131072,64]` -> `fusion.137`."""
    return op_name.split(" ", 1)[0].lstrip("%")


def block_ops(record) -> List[Tuple[int, str, float]]:
    """(device, instruction, self ns) of every traced op inside a module
    event of the epoch block on its device."""
    blocks: Dict[int, list] = {}
    for module in record.trace.modules:
        if module.name.split("(")[0] == BLOCK_MODULE:
            blocks.setdefault(module.device, []).append((module.start_ns, module.end_ns))
    return [
        (ev.device, instruction(ev.name), ev.self_ns)
        for ev in record.trace.ops
        if any(start <= ev.start_ns < end for start, end in blocks.get(ev.device, ()))
    ]


def scope_table(record, traced: Iterable[str]) -> Optional[dict]:
    """The scope table of the executable whose instructions `traced` names:
    of the registered block whose runs (`Program.note_run`) ended inside the
    window, the table of the signature that matches the trace best both ways
    (of two that match alike, the newer). None where the registry keeps no
    tables, no block ran, or no table holds a single traced name."""
    from kmamiz_tpu.core import programs

    calls = record.recorder.named("refresh.call")
    traced = set(traced)
    for name, program in sorted(programs.all_programs().items()):
        tables_of = getattr(program, "scope_tables", None)
        if not name.startswith(BLOCK_PROGRAM) or tables_of is None:
            continue
        if any(s.start_s <= end_s <= s.end_s for end_s, _ms, _units in program.recent_runs() for s in calls):
            tables = [t for t in tables_of() if traced & t.keys()]
            return min(reversed(tables), key=lambda t: len(traced ^ t.keys()), default=None)
    return None


def by_device(record) -> Optional[ByDevice]:
    """Self time of the block's ops by device and phase; made once a record."""
    cached = getattr(record, "_scope_ns", None)
    if cached is not None:
        return cached or None
    if record.trace is None:
        return None
    ops = block_ops(record)
    table = scope_table(record, (name for _device, name, _ns in ops)) or {}
    out: ByDevice = {}
    for device, name, self_ns in ops if table else ():
        phase = table.get(name, (None, None, None))[1]
        phases = out.setdefault(device, {})
        key = phase if phase in PHASES else UNSCOPED
        phases[key] = phases.get(key, 0.0) + self_ns
    record._scope_ns = out  # empty where there is no table: asked once a record either way
    return out or None


def device_mean_ns(record, phases: Iterable[str]) -> Optional[float]:
    """Self time of `phases`, a device (the mean over the devices that ran
    the block)."""
    found = by_device(record)
    if not found:
        return None
    wanted = tuple(phases)
    return sum(sum(of.get(p, 0.0) for p in wanted) for of in found.values()) / len(found)


def ms_per_slot(record, phases: Iterable[str]) -> Optional[float]:
    """Self time of `phases`, a device and a slot update, in ms."""
    updates = record.recorder.counters.get("window.slot_updates", 0)
    self_ns = device_mean_ns(record, phases)
    if self_ns is None or not updates:
        return None
    return self_ns / 1e6 / updates
