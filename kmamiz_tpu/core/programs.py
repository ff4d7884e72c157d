"""Central registry of hot jitted programs: telemetry, shape hints, prewarm.

Every jitted entry point that can sit on the serving path registers here
(graph merges, the packed ancestor walk, window stats, the scorers, the
stacked GraphSAGE epoch block, the forecast forward). Registration wraps
the jitted callable in a :class:`Program` proxy that

- counts per-program compiles and compile milliseconds (a dispatch whose
  jit cache grew paid a trace/lower/compile wall — the /health/timings
  ``programs`` section exposes the counters, and a steady-state tick
  after warm-up must add 0);
- records the exact argument *spec* (shapes + dtypes + static values) of
  every newly compiled entry as a **shape hint**, persisted next to the
  persistent XLA cache (core.compile_cache), so a restarted process can
  prewarm exactly the (program, bucket) pairs production traffic
  exercised;
- keeps, of every call that compiled, the call's abstract arguments, and
  on demand reads that signature's named scopes back from its executable
  (:meth:`Program.scope_tables`: which HLO instruction belongs to which
  ``jax.named_scope`` phase of ``SCOPE_PHASES``), so that a device trace
  can be summed by the program's own names;
- replays those specs at boot with zero-filled arguments
  (:meth:`Program.prewarm_spec`). A replayed dispatch populates the jit
  *dispatch* cache — unlike ``fn.lower(...).compile()``, which AOT-fills
  only the persistent XLA cache and still leaves the first live call a
  multi-second trace+lower wall (measured on jax 0.4.37: lower+compile
  leaves ``_cache_size()`` at 0; the first call re-traces).

Boot flow (dp_server.main / api.app): ``start_background_prewarm()``
runs the plan on a daemon thread; ``warm_state()`` drives the /health
readiness gate (503 + status "WARMING" until done, see
api/handlers/health.py and deploy/kmamiz-tpu.yaml's readinessProbe).

Env:
- ``KMAMIZ_SHAPE_HINTS``: hint-file path (default ``shape_hints.json``
  beside the persistent cache, wherever core.compile_cache placed it;
  a process that never enabled the cache keeps no hints).
- ``KMAMIZ_PREWARM``: "0" disables boot prewarm, "sync" blocks boot on
  it, anything else (default "1") prewarms on a background thread.
- ``KMAMIZ_PREWARM_READY_GATE``: "0" keeps /health answering 200 while
  warming (gate off); default "1" answers 503.
"""
from __future__ import annotations

import importlib
import json
import logging
import os
import re
import threading
import time
from collections import deque
from typing import Any, Callable, Dict, List, Optional, Tuple

from kmamiz_tpu.core import compile_cache

logger = logging.getLogger("kmamiz_tpu.programs")

# graftprof compile-cause hook: every real compile (cache-entry growth)
# lands in the device-attribution log with its program name and wall
# cost. Guarded — the registry must keep working under a partial
# telemetry install (core is importable before/without telemetry).
try:
    from kmamiz_tpu.telemetry.profiling import device_attr as _prof_device_attr
except Exception:  # noqa: BLE001 - profiling is optional at this layer
    _prof_device_attr = None

_MAX_HINTS_PER_PROGRAM = 16
_RECENT_RUNS = 64

#: the phases a program names its device time by: the LAST `jax.named_scope`
#: component of an op that is one of these is its phase (docs/OBSERVABILITY.md
#: says what lies in each; `scope_of` reads them back from the compiled HLO)
SCOPE_PHASES = ("gather", "reduce", "collective", "dense", "loss", "optimizer", "group")

_registry_lock = threading.Lock()
_REGISTRY: Dict[str, "Program"] = {}
#: family base name -> resolver(key) -> Program; dynamic programs
#: (per-model jits built by lru_cache factories) register instances
#: under "base[key]" and a resolver so a restart can rebuild them from
#: a persisted hint before any live call exists.
_FAMILIES: Dict[str, Callable[[str], Optional["Program"]]] = {}


class UnencodableSpec(ValueError):
    """Argument not expressible as a shape hint (opaque object leaf)."""


# ---------------------------------------------------------------------------
# argument-spec encode/decode
#
# A spec is the JSON-able skeleton of one dispatch's (args, kwargs):
# array leaves become {"__arr__": [shape, dtype, weak]}, tuples and
# namedtuples keep their container identity (the jit cache keys on the
# pytree structure, so a tuple→list roundtrip would miss the cache),
# and plain Python scalars stay literal — replaying a literal through
# the jit boundary reproduces the live call's weak-type/static-arg
# cache key exactly.
# ---------------------------------------------------------------------------


def _encode(x: Any) -> Any:
    if x is None or isinstance(x, (bool, int, float, str)):
        return x
    if hasattr(x, "shape") and hasattr(x, "dtype"):
        return {
            "__arr__": [
                [int(d) for d in x.shape],
                str(x.dtype),
                bool(getattr(x, "weak_type", False)),
            ]
        }
    if isinstance(x, tuple):
        fields = getattr(x, "_fields", None)
        if fields is not None:  # namedtuple: keep the class for the pytree
            cls = type(x)
            return {
                "__nt__": [cls.__module__, cls.__qualname__],
                "items": [_encode(v) for v in x],
            }
        return {"__tuple__": [_encode(v) for v in x]}
    if isinstance(x, list):
        return [_encode(v) for v in x]
    if isinstance(x, dict):
        if not all(isinstance(k, str) for k in x):
            raise UnencodableSpec(f"non-string dict keys: {list(x)[:3]}")
        return {str(k): _encode(v) for k, v in x.items()}
    raise UnencodableSpec(f"opaque leaf {type(x).__name__}")


def _resolve_qualname(module: str, qualname: str):
    obj = importlib.import_module(module)
    for part in qualname.split("."):
        obj = getattr(obj, part)
    return obj


def _decode_zeros(x: Any) -> Any:
    """Spec -> concrete zero-filled arguments for a prewarm dispatch."""
    if x is None or isinstance(x, (bool, int, float, str)):
        return x
    if isinstance(x, list):
        return [_decode_zeros(v) for v in x]
    if isinstance(x, dict):
        if "__arr__" in x:
            shape, dtype, weak = x["__arr__"]
            if weak and not shape:
                # weak-typed scalar: replay as the Python literal that
                # produced it, so the cache key matches the live call
                kind = str(dtype)
                if kind.startswith("bool"):
                    return False
                if kind.startswith(("int", "uint")):
                    return 0
                return 0.0
            import jax.numpy as jnp

            return jnp.zeros(tuple(shape), dtype=str(dtype))
        if "__tuple__" in x:
            return tuple(_decode_zeros(v) for v in x["__tuple__"])
        if "__nt__" in x:
            cls = _resolve_qualname(*x["__nt__"])
            return cls(*[_decode_zeros(v) for v in x["items"]])
        return {k: _decode_zeros(v) for k, v in x.items()}
    raise UnencodableSpec(f"bad spec node {type(x).__name__}")


def _bucket_label(spec: Any) -> str:
    """Compact human-readable bucket descriptor for telemetry tables:
    array shapes and static scalars, pytree internals elided."""
    args, kwargs = spec

    def leaf(x):
        if isinstance(x, dict):
            if "__arr__" in x:
                shape, dtype, _ = x["__arr__"]
                return "x".join(str(d) for d in shape) or "scalar"
            return "tree"
        if isinstance(x, (list,)):
            return "tree"
        return repr(x)

    parts = [leaf(a) for a in args]
    parts += [f"{k}={leaf(v)}" for k, v in sorted(kwargs.items())]
    return "(" + ",".join(parts) + ")"


# ---------------------------------------------------------------------------
# named scopes, read back from a compiled program
#
# `jax.named_scope` costs nothing at run time: it is the `op_name` in the
# metadata of every HLO instruction traced under it, which XLA's passes carry
# along (a fusion takes its root's) and do not read. So the optimized HLO says
# which instruction belongs to which stretch of the program, in the program's
# own words, and a device trace's events (named by instruction) can be summed
# by them.
# ---------------------------------------------------------------------------

#: what JAX wraps around a scope when it differentiates or batches the code
#: under it: `transpose(jvp(graphsage))/layer2/...` (a wrapper holds the scope
#: that follows it; older versions wrapped the whole path)
_TRANSFORMS = frozenset({"jvp", "transpose", "vmap"})
#: path components that are the tracer's, not a scope a program opened
_STRUCTURAL = re.compile(r"^(jit\(.*\)|while|body|cond|closed_call|shard_map)$")
_INSTRUCTION = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+) = (.*)$")
_COMPUTATION = re.compile(r"^\s*(?:ENTRY\s+)?%?([\w.\-]+) \(.*\{\s*$")
_OPCODE = re.compile(r"\s([a-z][a-z0-9\-]*)\(")
_OP_NAME = re.compile(r'metadata=\{[^}]*?op_name="([^"]*)"')
#: a computation that runs INSIDE one instruction, and is timed as that one: a
#: fusion's body, a reducer (`call` alone applies a computation as a program does)
_INNER = re.compile(r"(?:\bcalls|\bto_apply)=%?([\w.\-]+)")
#: instructions that move or name values and run nothing: in no table
_NO_WORK = frozenset(
    {"parameter", "tuple", "get-tuple-element", "constant", "bitcast", "while", "conditional", "call"}
)
_SPLAT = re.compile(r"\sbroadcast\(%?constant[\w.\-]*\)")  # a constant, spelled out
#: an `op_name` that no line of the program wrote: a loop's own counter, test,
#: slice of its inputs and stack of its outputs stand right under `body` or
#: `cond` (what a scan's body traced stands under `closed_call`), the
#: partitioner's own arithmetic right under `shard_map` or under no path at
#: all, and where XLA merged instructions the name may end in no primitive
_NOT_THE_PROGRAMS = re.compile(
    r"(?:^|/)(?:body|cond|shard_map)/[^/]+$|^[^/]*$|(?:^|/)(?:closed_call|body|cond|shard_map|jit\([^/]*\))$"
)


ScopeTable = Dict[str, Tuple[str, Optional[str], bool]]


def scope_of(op_name: str) -> Tuple[str, Optional[str], bool]:
    """An instruction's `op_name` -> (scope path, phase, backward).

    The path is what the program's `jax.named_scope`s spell, without the
    tracer's own components (`jit(..)`, `while/body`, `shard_map`), without
    JAX's wrapping (`transpose(jvp(a))/b/gather/mul` -> `a/b/gather`, and
    backward: an op under a `transpose` is the backward pass's; the tests
    read it to pin that a custom-VJP rule opens its scope itself) and without
    the last component, the primitive's name. The phase is the last component
    of the path that `SCOPE_PHASES` holds, None where there is none."""
    out, opened, word, backward = [], [], "", False
    for ch in op_name:
        if ch == "(":
            wrapper = word in _TRANSFORMS
            backward = backward or word == "transpose"
            opened.append(wrapper)
            out.append("" if wrapper else word + "(")
            word = ""
        elif ch == ")":
            out.append(word)
            word = ""
            if not opened or not opened.pop():
                out.append(")")
        elif ch == "/":
            out.append(word + "/")
            word = ""
        else:
            word += ch
    out.append(word)
    path = [c for c in "".join(out).split("/")[:-1] if c and not _STRUCTURAL.match(c)]
    phase = next((c for c in reversed(path) if c in SCOPE_PHASES), None)
    return "/".join(path), phase, backward


def scope_table_of(hlo_text: str) -> ScopeTable:
    """Optimized HLO text -> {instruction name: (scope path, phase, backward)}
    for every instruction that a line of the program traced and that a device
    runs and a trace times as one: none of a fusion's body or of a reducer, no
    parameter, tuple, constant or control flow (a loop's own counter, test,
    slices and stacks among it). An instruction is credited to the scope ITS
    metadata carries: a fusion to its root's. What XLA put in itself (a copy,
    with no metadata) is in no table: a reader counts its time as unscoped, as
    it does a row whose phase is None, which is what a scope has to cure."""
    table: ScopeTable = {}
    inside: Dict[str, str] = {}  # instruction -> the computation it stands in
    inner, computation = set(), ""
    for line in hlo_text.splitlines():
        header = _COMPUTATION.match(line)
        if header is not None:
            computation = header.group(1)
            continue
        m = _INSTRUCTION.match(line)
        if m is None:
            continue
        name, rest = m.groups()
        opcode = _OPCODE.search(" " + rest)
        if opcode is None:
            continue
        if opcode.group(1) != "call":
            inner.update(_INNER.findall(rest))
        if opcode.group(1) in _NO_WORK or _SPLAT.search(" " + rest):
            continue
        named = _OP_NAME.search(rest)
        if named is None or _NOT_THE_PROGRAMS.search(named.group(1)):
            continue
        table[name] = scope_of(named.group(1))
        inside[name] = computation
    return {name: row for name, row in table.items() if inside[name] not in inner}


def _abstract(x: Any) -> Any:
    """An array leaf as its `jax.ShapeDtypeStruct`; anything else (a static
    argument) as it is. A committed array keeps its sharding; one that lies
    wherever JAX put it keeps none, as jit itself sees it, so that lowering
    the abstract call again finds jit's own lowering and executable."""
    if not (hasattr(x, "shape") and hasattr(x, "dtype")):
        return x
    import jax

    sharding = x.sharding if getattr(x, "committed", False) else None
    return jax.ShapeDtypeStruct(
        x.shape, x.dtype, sharding=sharding, weak_type=bool(getattr(x, "weak_type", False))
    )


# ---------------------------------------------------------------------------
# Program proxy
# ---------------------------------------------------------------------------


class Program:
    """Instrumented wrapper around one jitted callable.

    Transparent for callers: ``__call__`` delegates, and jit attributes
    (``lower``, ``_cache_size`` — the tests read it) pass through via
    ``__getattr__``. Telemetry costs two ``_cache_size()`` reads and one
    timer per dispatch.
    """

    def __init__(self, name: str, fn: Callable) -> None:
        self.name = name
        self.fn = fn
        self._lock = threading.Lock()
        self.calls = 0
        self.compiles = 0
        self.compile_ms = 0.0
        self.last_compile_ms = 0.0
        self.prewarmed = 0
        self.prewarm_ms = 0.0
        # warm-DISPATCH wall EWMA (graftcost's label): the time the call
        # took to return, not the time the program ran on the device
        self.run_ewma_ms = 0.0
        # measured run time, reported by a caller that already fences
        # (note_run): wall from the dispatch to the result on the host
        self.runs = 0
        self.run_ms = 0.0
        self.last_run_ms = 0.0
        # the last runs as (end_s on perf_counter, run_ms, units), for a
        # reader that wants those of one window
        self._recent_runs: deque = deque(maxlen=_RECENT_RUNS)
        self._specs: Dict[str, Any] = {}  # canonical json -> spec
        # canonical json -> (spec, compile_ms, run_ms): the cost-model
        # training labels (run_ms 0.0 until a warm call lands)
        self._labels: Dict[str, Tuple[Any, float, float]] = {}
        self._suppress_record = False
        # [abstract (args, kwargs), scope table once somebody has asked] of
        # every call that compiled, a signature once, oldest first
        self._compiled_calls: List[list] = []

    # -- delegation ---------------------------------------------------------
    def __call__(self, *args, **kwargs):
        before = self._cache_entries()
        t0 = time.perf_counter()
        out = self.fn(*args, **kwargs)
        elapsed_ms = (time.perf_counter() - t0) * 1000.0
        grew = 0
        if before is not None:
            after = self._cache_entries()
            if after is not None and after > before:
                grew = after - before
        with self._lock:
            self.calls += 1
            if grew:
                self.compiles += grew
                self.compile_ms += elapsed_ms
                self.last_compile_ms = elapsed_ms
            elif before is not None:
                # warm dispatch: the wall of the call's return, which the
                # graftcost regressor trains its run-ms head on (a
                # program's measured run time is note_run's)
                self.run_ewma_ms = (
                    elapsed_ms
                    if self.run_ewma_ms == 0.0
                    else 0.8 * self.run_ewma_ms + 0.2 * elapsed_ms
                )
        if grew:
            if _prof_device_attr is not None:
                _prof_device_attr.note_compile(self.name, grew, elapsed_ms)
            try:
                self._keep_abstract(args, kwargs)
            except Exception as e:  # noqa: BLE001 - the call itself succeeded
                logger.debug("%s: signature not kept: %s", self.name, e)
            if not self._suppress_record:
                self._record_spec(args, kwargs, compile_ms=elapsed_ms)
        return out

    def __getattr__(self, item):
        return getattr(self.fn, item)

    # -- measured run time --------------------------------------------------
    def note_run(self, run_ms: float, units: int = 0) -> None:
        """Report one run of this program, by the caller that already
        waits for its result and as soon as it has it (the registry adds
        no fence of its own): `run_ms` is the wall from the dispatch to
        the result on the host, `units` the work the run held (the slot
        updates of an epoch block)."""
        end_s = time.perf_counter()  # graftlint: disable=hot-path-clock -- once per fenced run, beside the caller's own wait
        with self._lock:
            self.runs += 1
            self.run_ms += run_ms
            self.last_run_ms = run_ms
            self._recent_runs.append((end_s, run_ms, units))

    def recent_runs(self) -> List[Tuple[float, float, int]]:
        """The last reported runs, oldest first: (end_s on
        `time.perf_counter`, run_ms, units)."""
        with self._lock:
            return list(self._recent_runs)

    def _cache_entries(self) -> Optional[int]:
        try:
            return int(self.fn._cache_size())
        except Exception:  # noqa: BLE001 - non-jit callables track calls only
            return None

    # -- named scopes -------------------------------------------------------
    def _keep_abstract(self, args, kwargs) -> None:
        """Keep the signature of a call that compiled (never of a warm
        dispatch): every array leaf as a `jax.ShapeDtypeStruct`, static
        arguments as they are. No buffer is held, donated or not."""
        import jax

        if not jax.core.trace_ctx.is_top_level():
            return  # inner-jit retrace: the leaves are tracers
        kept = jax.tree_util.tree_map(_abstract, (tuple(args), dict(kwargs)))
        with self._lock:
            if all(kept != known for known, _table in self._compiled_calls):
                self._compiled_calls.append([kept, None])
                del self._compiled_calls[:-_MAX_HINTS_PER_PROGRAM]

    def _scope_table_of(self, kept: list) -> ScopeTable:
        if kept[1] is None:
            args, kwargs = kept[0]
            kept[1] = scope_table_of(self.fn.lower(*args, **kwargs).compile().as_text())
        return kept[1]

    def scope_tables(self) -> List[ScopeTable]:
        """{instruction name: (scope path, phase, backward)} (`scope_table_of`) of
        every signature of this program that a call compiled, oldest first:
        a program compiles anew for other shapes, and the executables of two
        signatures share most instruction names with another numbering, so a
        reader of a device trace takes the table whose names are the trace's
        (benchmarks/harness/program_scopes). A table is made on the first ask
        and kept. Lowering a kept signature finds jit's own lowering of that
        call and with it the executable that runs (nothing is compiled or
        loaded twice); where jit has dropped it, the program is lowered and
        compiled again, a load from the compile cache where one is set up."""
        with self._lock:
            kept = list(self._compiled_calls)
        return [self._scope_table_of(entry) for entry in kept]

    def scope_table(self) -> ScopeTable:
        """The scope table of the newest signature that compiled, and of no
        other; empty where no call has compiled."""
        with self._lock:
            newest = self._compiled_calls[-1:]
        return self._scope_table_of(newest[0]) if newest else {}

    # -- shape hints --------------------------------------------------------
    def _record_spec(self, args, kwargs, compile_ms: float = 0.0) -> None:
        import jax

        if not jax.core.trace_ctx.is_top_level():
            return  # inner-jit retrace: not a top-level dispatch shape
        try:
            spec = (
                [_encode(a) for a in args],
                {k: _encode(v) for k, v in sorted(kwargs.items())},
            )
        except UnencodableSpec:
            return
        key = json.dumps(spec, sort_keys=True)
        with self._lock:
            if compile_ms > 0.0 and (
                key in self._labels or len(self._labels) < _MAX_HINTS_PER_PROGRAM
            ):
                # keep the max observed wall per bucket: a cache-evicted
                # recompile of a known spec still paid the full trace
                prev = self._labels.get(key)
                if prev is None or compile_ms > prev[1]:
                    self._labels[key] = (spec, compile_ms, 0.0)
            if key in self._specs:
                return
            if len(self._specs) >= _MAX_HINTS_PER_PROGRAM:
                return
            self._specs[key] = spec
        _autosave_hints()

    def specs(self) -> List[Any]:
        with self._lock:
            return list(self._specs.values())

    def adopt_specs(self, specs: List[Any]) -> None:
        """Merge persisted hint specs (restart path) without re-saving."""
        with self._lock:
            for spec in specs:
                key = json.dumps(spec, sort_keys=True)
                if (
                    key not in self._specs
                    and len(self._specs) < _MAX_HINTS_PER_PROGRAM
                ):
                    self._specs[key] = spec

    # -- cost labels (graftcost training rows) ------------------------------
    def labels(self) -> List[Tuple[Any, float, float]]:
        """(spec, compile_ms, run_ms) rows observed by this process plus
        adopted history. A live row whose warm wall hasn't landed yet
        borrows the program-level EWMA. `run_ms` here is the wall of a
        warm DISPATCH, not the measured run time `note_run` keeps."""
        with self._lock:
            ewma = self.run_ewma_ms
            return [
                (spec, compile_ms, run_ms if run_ms > 0.0 else ewma)
                for spec, compile_ms, run_ms in self._labels.values()
            ]

    def adopt_labels(self, labelled: List[Tuple[Any, float, float]]) -> None:
        """Merge persisted label rows (restart path): live observations
        of the same bucket win."""
        with self._lock:
            for spec, compile_ms, run_ms in labelled:
                key = json.dumps(spec, sort_keys=True)
                if (
                    key not in self._labels
                    and len(self._labels) < _MAX_HINTS_PER_PROGRAM
                ):
                    self._labels[key] = (
                        spec,
                        float(compile_ms),
                        float(run_ms),
                    )

    # -- prewarm ------------------------------------------------------------
    def prewarm_spec(self, spec: Any) -> bool:
        """Dispatch this program once with zero-filled arguments matching
        ``spec``, so the jit dispatch cache (and the persistent XLA
        cache) hold the program before live traffic arrives. Pure
        kernels only — outputs are discarded."""
        try:
            args, kwargs = spec
            concrete_args = [_decode_zeros(a) for a in args]
            concrete_kwargs = {k: _decode_zeros(v) for k, v in kwargs.items()}
        except Exception as e:  # noqa: BLE001 - stale/foreign hint
            logger.warning("%s: undecodable hint (%s)", self.name, e)
            return False
        t0 = time.perf_counter()  # graftlint: disable=hot-path-clock -- boot-time prewarm accounting, off the tick
        self._suppress_record = True
        try:
            import jax

            out = self(*concrete_args, **concrete_kwargs)
            # graftlint: disable=host-sync-in-hot-path -- prewarm deliberately blocks at boot, off the tick
            jax.block_until_ready(out)
        except Exception as e:  # noqa: BLE001 - a bad hint must not kill boot
            logger.warning("%s: prewarm failed (%s)", self.name, e)
            return False
        finally:
            self._suppress_record = False
        with self._lock:
            self.prewarmed += 1
            self.prewarm_ms += (time.perf_counter() - t0) * 1000.0  # graftlint: disable=hot-path-clock -- boot-time prewarm accounting, off the tick
        self.adopt_specs([spec])
        return True

    # -- reporting ----------------------------------------------------------
    def stats(self) -> dict:
        """The /timings row. `runEwmaMs` is the wall of a warm DISPATCH
        (the call's return: under a millisecond for an epoch block that
        runs for seconds); `runs` / `runMs` / `lastRunMs` are the
        measured run time that fencing callers report (`note_run`)."""
        with self._lock:
            return {
                "calls": self.calls,
                "compiles": self.compiles,
                "compileMs": round(self.compile_ms, 1),
                "lastCompileMs": round(self.last_compile_ms, 1),
                "prewarmed": self.prewarmed,
                "prewarmMs": round(self.prewarm_ms, 1),
                "runEwmaMs": round(self.run_ewma_ms, 3),
                "runs": self.runs,
                "runMs": round(self.run_ms, 3),
                "lastRunMs": round(self.last_run_ms, 3),
                "cacheSize": self._cache_entries(),
                "buckets": [_bucket_label(s) for s in self._specs.values()],
            }


# ---------------------------------------------------------------------------
# registration
# ---------------------------------------------------------------------------


def register(name: str, fn: Optional[Callable] = None):
    """Register a jitted callable under ``name``; usable as a decorator::

        @programs.register("graph.merge_edges")
        @jax.jit
        def _merge_edges(...): ...
    """
    def _wrap(f: Callable) -> Program:
        with _registry_lock:
            existing = _REGISTRY.get(name)
            if existing is not None and existing.fn is f:
                return existing
            prog = Program(name, f)
            _REGISTRY[name] = prog
            return prog

    return _wrap if fn is None else _wrap(fn)


def register_instance(base: str, key: str, fn: Callable) -> Program:
    """Register a dynamically created jit (one per model/config) under
    ``base[key]``. Idempotent per (name, fn)."""
    return register(f"{base}[{key}]", fn)


def register_family(base: str, resolver: Callable[[str], Optional[Program]]):
    """Install a resolver that can rebuild ``base[key]`` instances from a
    persisted hint at boot (before any live call constructs them)."""
    with _registry_lock:
        _FAMILIES[base] = resolver


def get(name: str) -> Optional[Program]:
    with _registry_lock:
        prog = _REGISTRY.get(name)
    if prog is not None:
        return prog
    if name.endswith("]") and "[" in name:
        base, key = name[:-1].split("[", 1)
        with _registry_lock:
            resolver = _FAMILIES.get(base)
        if resolver is not None:
            try:
                return resolver(key)
            except Exception as e:  # noqa: BLE001 - unresolvable hint
                logger.warning("cannot rebuild %s: %s", name, e)
    return None


def all_programs() -> Dict[str, Program]:
    with _registry_lock:
        return dict(_REGISTRY)


def _ensure_registered() -> None:
    """Import every module that registers hot programs, so summaries,
    hints, and the prewarm plan see the full registry regardless of
    which subsystem the process booted first."""
    for mod in (
        "kmamiz_tpu.graph.store",
        "kmamiz_tpu.ops.window",
        "kmamiz_tpu.ops.scorers",
        "kmamiz_tpu.server.processor",
        "kmamiz_tpu.models.serving",
        "kmamiz_tpu.models.stacked",
        "kmamiz_tpu.models.stlgt.trainer",
        "kmamiz_tpu.models.stlgt.serving",
        "kmamiz_tpu.cost.model",
    ):
        try:
            importlib.import_module(mod)
        except Exception as e:  # noqa: BLE001 - optional dep gated elsewhere
            logger.debug("registry import %s failed: %s", mod, e)


# ---------------------------------------------------------------------------
# telemetry summaries
# ---------------------------------------------------------------------------


def summary() -> dict:
    """Per-program counters for /health/timings and the warm-boot probe."""
    progs = {name: p.stats() for name, p in sorted(all_programs().items())}
    return {
        "programs": progs,
        "totalCompiles": sum(p["compiles"] for p in progs.values()),
        "totalCompileMs": round(
            sum(p["compileMs"] for p in progs.values()), 1
        ),
        "warm": warm_state(),
    }


def _scrape_programs() -> None:
    """Scrape-time mirror of the per-program counters into the telemetry
    registry — /metrics pulls the same `stats()` numbers /timings shows,
    with zero hot-path writes (the registry callback runs at render
    only)."""
    from kmamiz_tpu.telemetry.registry import REGISTRY

    calls = REGISTRY.gauge_family(
        "kmamiz_program_calls_total", "Registered-program dispatches", ("program",)
    )
    compiles = REGISTRY.gauge_family(
        "kmamiz_program_compiles_total", "Registered-program XLA compiles", ("program",)
    )
    compile_ms = REGISTRY.gauge_family(
        "kmamiz_program_compile_ms_total", "Cumulative compile wall (ms)", ("program",)
    )
    for name, p in all_programs().items():
        st = p.stats()
        calls.handle(name).set(st["calls"])
        compiles.handle(name).set(st["compiles"])
        compile_ms.handle(name).set(st["compileMs"])


def _register_scrape_callback() -> None:
    from kmamiz_tpu.telemetry.registry import REGISTRY

    REGISTRY.register_callback(_scrape_programs)


_register_scrape_callback()


def snapshot() -> Dict[str, int]:
    """Compile-count snapshot; diff with :func:`new_compiles_since`."""
    return {name: p.compiles for name, p in all_programs().items()}


def new_compiles_since(snap: Dict[str, int]) -> Dict[str, int]:
    """Programs that compiled since ``snap`` (steady state must be {})."""
    out = {}
    for name, p in all_programs().items():
        delta = p.compiles - snap.get(name, 0)
        if delta > 0:
            out[name] = delta
    return out


# ---------------------------------------------------------------------------
# persisted shape hints
# ---------------------------------------------------------------------------

_hints_lock = threading.Lock()
_HINTS_VERSION = 1


def hints_path() -> Optional[str]:
    path = os.environ.get("KMAMIZ_SHAPE_HINTS")
    if path:
        return path
    if compile_cache.enabled():
        return os.path.join(compile_cache.cache_dir(), "shape_hints.json")
    return None


def save_hints(path: Optional[str] = None) -> Optional[str]:
    """Write every program's observed specs (atomic replace). Returns the
    path written, or None when hints are unconfigured."""
    path = path or hints_path()
    if not path:
        return None
    payload = {
        "version": _HINTS_VERSION,
        "programs": {
            name: p.specs()
            for name, p in sorted(all_programs().items())
            if p.specs()
        },
        # sibling key, same version: readers of "programs" (including
        # older processes — load_hints filters on len(spec) == 2 and
        # never looks here) are unaffected. These are the graftcost
        # training rows that survive a restart.
        "labels": {
            name: [
                {"spec": spec, "compileMs": round(c, 3), "runMs": round(r, 3)}
                for spec, c, r in p.labels()
            ]
            for name, p in sorted(all_programs().items())
            if p.labels()
        },
    }
    tmp = f"{path}.tmp.{os.getpid()}"
    with _hints_lock:
        os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
        with open(tmp, "w") as f:
            json.dump(payload, f)
        os.replace(tmp, path)
    return path


def load_hints(path: Optional[str] = None) -> Dict[str, List[Any]]:
    path = path or hints_path()
    if not path or not os.path.exists(path):
        return {}
    try:
        with open(path) as f:
            payload = json.load(f)
        if payload.get("version") != _HINTS_VERSION:
            return {}
        out = {}
        for name, specs in payload.get("programs", {}).items():
            out[name] = [
                (spec[0], spec[1]) for spec in specs if len(spec) == 2
            ]
        return out
    except (OSError, ValueError, TypeError) as e:
        logger.warning("bad shape-hint file %s: %s", path, e)
        return {}


def load_labels(
    path: Optional[str] = None,
) -> Dict[str, List[Tuple[Any, float, float]]]:
    """Persisted cost labels: {name: [(spec, compile_ms, run_ms)]}.
    Empty when unconfigured, absent (pre-label hint file), or bad."""
    path = path or hints_path()
    if not path or not os.path.exists(path):
        return {}
    try:
        with open(path) as f:
            payload = json.load(f)
        if payload.get("version") != _HINTS_VERSION:
            return {}
        out: Dict[str, List[Tuple[Any, float, float]]] = {}
        for name, rows in payload.get("labels", {}).items():
            keep = []
            for row in rows:
                spec = row.get("spec")
                if not (isinstance(spec, list) and len(spec) == 2):
                    continue
                keep.append(
                    (
                        (spec[0], spec[1]),
                        float(row.get("compileMs", 0.0)),
                        float(row.get("runMs", 0.0)),
                    )
                )
            if keep:
                out[name] = keep
        return out
    except (OSError, ValueError, TypeError) as e:
        logger.warning("bad shape-hint labels in %s: %s", path, e)
        return {}


def adopt_labels(
    labelled: Dict[str, List[Tuple[Any, float, float]]]
) -> None:
    """Feed persisted label history back into the live programs so the
    cost model trains from day-one history at boot."""
    for name, rows in labelled.items():
        prog = get(name)
        if prog is not None:
            prog.adopt_labels(rows)


def _autosave_hints() -> None:
    """Persist on every NEW bucket observation (rare by construction:
    pow2 bucketing bounds distinct specs to O(log) per program)."""
    try:
        save_hints()
    except OSError as e:
        logger.warning("shape-hint save failed: %s", e)


# ---------------------------------------------------------------------------
# boot prewarm plan + readiness state
# ---------------------------------------------------------------------------

_warm_lock = threading.Lock()
_warm: Dict[str, Any] = {"status": "cold"}
_warm_thread: Optional[threading.Thread] = None


def warm_state() -> dict:
    with _warm_lock:
        return dict(_warm)


def is_warming() -> bool:
    return warm_state().get("status") == "warming"


def ready_gate_enabled() -> bool:
    return os.environ.get("KMAMIZ_PREWARM_READY_GATE", "1") != "0"


def run_prewarm(
    graph=None, hints: Optional[Dict[str, List[Any]]] = None
) -> dict:
    """Execute the boot prewarm plan synchronously:

    1. replay every persisted (program, spec) hint — the exact buckets
       the previous process compiled for production traffic;
    2. for the graph-store merge family only, when NO hint covered it,
       fall back to ``graph.prewarm_compile()`` default (rows, depth)
       buckets (everything else is hint-driven: defaults for scorer or
       model programs would guess capacities the deployment never uses).

    Returns a report dict (also stored in :func:`warm_state`).
    """
    _ensure_registered()
    t0 = time.perf_counter()  # graftlint: disable=hot-path-clock -- boot-time prewarm accounting, off the tick
    # the native extension's one-time lazy build (or its cached-failure
    # probe) otherwise lands inside the first tick's combine phase — it
    # is boot work, so the plan pays it here alongside the XLA warms
    try:
        from kmamiz_tpu import native

        native.available()
    except Exception:  # noqa: BLE001 - never let the probe block boot
        logger.exception("native prewarm probe failed")
    hints = load_hints() if hints is None else hints
    labels = load_labels()
    adopt_labels(labels)
    report = {
        "hintedPrograms": len(hints),
        "warmed": 0,
        "failed": 0,
        "ranked": False,
        "defaultGraphPrograms": 0,
    }
    pairs: List[Tuple[str, Any]] = []
    for name, specs in sorted(hints.items()):
        if get(name) is None:
            report["failed"] += len(specs)
            logger.warning("hint for unregistered program %s", name)
            continue
        pairs.extend((name, spec) for spec in specs)
    # graftcost boot ranking: longest predicted compile first, so
    # readiness is bounded by the expensive programs instead of queuing
    # them behind trivia. Falls back to the stable name order on any
    # failure — ranking must never block a cold boot.
    try:
        from kmamiz_tpu import cost as _cost

        pairs = _cost.ranked_prewarm_order(pairs, labels)
        report["ranked"] = True
    except Exception:  # noqa: BLE001 - name-ordered replay still correct
        logger.exception("prewarm ranking failed; using name order")
    for name, spec in pairs:
        prog = get(name)
        if prog is not None and prog.prewarm_spec(spec):
            report["warmed"] += 1
        else:
            report["failed"] += 1
    graph_hinted = any(n.startswith("graph.") for n in hints)
    if graph is not None and not graph_hinted:
        try:
            report["defaultGraphPrograms"] = graph.prewarm_compile()
        except Exception as e:  # noqa: BLE001 - boot must survive
            # survive, but visibly: /health's prewarm report carries the
            # failure, so a probe (chip_smoke.py) can refuse the boot
            logger.exception("default graph prewarm failed")
            report["failed"] += 1
            report["defaultGraphError"] = f"{type(e).__name__}: {e}"[:500]
    report["elapsedS"] = round(time.perf_counter() - t0, 2)  # graftlint: disable=hot-path-clock -- boot-time prewarm accounting, off the tick
    return report


def start_background_prewarm(graph=None) -> Optional[threading.Thread]:
    """Run the prewarm plan on a daemon thread; /health reports WARMING
    (503 when the ready gate is on) until it completes. Idempotent."""
    global _warm_thread
    with _warm_lock:
        if _warm["status"] in ("warming", "ready", "error"):
            return _warm_thread
        _warm.clear()
        _warm.update({"status": "warming", "startedAt": time.time()})  # graftlint: disable=hot-path-clock -- boot wall stamp for /health warm state, off the tick

    def _run() -> None:
        status = "ready"
        report: Dict[str, Any] = {}
        try:
            report = run_prewarm(graph=graph)
        except Exception as e:  # noqa: BLE001 - serve degraded, don't die
            logger.exception("background prewarm failed")
            status, report = "error", {"error": str(e)}
        with _warm_lock:
            _warm["status"] = status
            _warm["report"] = report
        logger.info("prewarm %s: %s", status, report)

    _warm_thread = threading.Thread(
        target=_run, name="kmamiz-prewarm", daemon=True
    )
    _warm_thread.start()
    return _warm_thread


def boot_prewarm_from_env(graph=None) -> None:
    """KMAMIZ_PREWARM dispatcher for server mains: "0" off, "sync"
    blocking, default background + readiness gate."""
    mode = os.environ.get("KMAMIZ_PREWARM", "1")
    if mode == "0":
        with _warm_lock:
            _warm.update({"status": "disabled"})
        return
    if mode == "sync":
        with _warm_lock:
            _warm.update({"status": "warming", "startedAt": time.time()})  # graftlint: disable=hot-path-clock -- boot wall stamp for /health warm state, off the tick
        report = run_prewarm(graph=graph)
        with _warm_lock:
            _warm.update({"status": "ready", "report": report})
        return
    start_background_prewarm(graph=graph)


# ---------------------------------------------------------------------------
# jit-site inventory (tier-1 guard test: tests/test_programs.py)
#
# Every `jax.jit` call site under kmamiz_tpu/ must appear in exactly one
# of these tables, keyed "relative/path.py" -> {function name}. REGISTERED
# sites are wrapped in a Program above/in their module; ALLOWLISTED sites
# carry the reason they are exempt from registry coverage.
# ---------------------------------------------------------------------------

REGISTERED_JIT_SITES: Dict[str, set] = {
    "kmamiz_tpu/graph/store.py": {
        "_merge_edges",
        "_window_merge",
        "_window_edges_packed",
        "_window_edges_compact",
        "_window_merge_packed",
        "_edge_mask",
        "_fit_edges",
        "_split_segments",
        "_bulk_dist_bounds",
        "_cat_segments",
    },
    # multi-chip programs: registered for their call/compile counters
    # (chip_smoke.py reads them to see the mesh path was taken). Their
    # specs carry a Mesh, which no hint can encode, so they stay out of
    # the hint file and are prewarmed via the sharded branch of
    # EndpointGraph.prewarm_compile.
    "kmamiz_tpu/parallel/mesh.py": {
        "sharded_window_stats",
        "sharded_dependency_edges",
        "sharded_dependency_edges_packed",
        "sharded_window_edges_compact",
        "sharded_service_scores",
    },
    "kmamiz_tpu/ops/window.py": {
        "skip_client_parents",
        "dependency_edges",
        "dependency_edges_packed",
        "window_stats",
        "service_stats",
    },
    "kmamiz_tpu/ops/scorers.py": {
        "service_scores_xla",
        "service_scores_sparse",
        "usage_cohesion",
        "risk_scores",
        "dirty_edge_subset",
        "merge_service_lanes",
    },
    "kmamiz_tpu/server/processor.py": {"_pack_stats"},
    # graftcost continual trainer (registered as cost.ridge_fit)
    "kmamiz_tpu/cost/model.py": {"_ridge_fit"},
    # scanner resolves inline jits to the nearest def: "fwd" is the
    # body _jitted_forward jits (registered as models.forecast_forward)
    "kmamiz_tpu/models/serving.py": {"fwd"},
    # named after their registry base, so that the device module reads
    # jit_<base> in a profiler capture
    "kmamiz_tpu/models/stacked.py": {
        "sage_epoch_block",
        "sage_dp_epoch_block",
        "batched_forward",
    },
    # STLGT: "run" is the continual-refresh epoch block (registered as
    # models.stlgt_epoch_block), "fwd" the quantile serving forward
    # (models.stlgt_quantile_forward)
    "kmamiz_tpu/models/stlgt/trainer.py": {"run"},
    "kmamiz_tpu/models/stlgt/serving.py": {"fwd"},
}

ALLOWLISTED_JIT_SITES: Dict[str, Dict[str, str]] = {
    "kmamiz_tpu/ops/pallas_kernels.py": {
        "segment_stats_matmul": "inner kernel: dispatched only inside "
        "window_stats' trace (registered there)",
    },
    "kmamiz_tpu/models/common.py": {
        "train_step": "legacy per-slot trainer loop "
        "(train(fused=False), the parity reference), off the serving path",
    },
}
