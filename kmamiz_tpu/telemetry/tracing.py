"""Per-tick span tracing: the collect tick as a trace of its own phases.

Each DP collect tick (and each raw-ingest window) opens a trace; the
pipeline phases — parse / quarantine / WAL append / merge / pack /
host→device transfer / walk / scorers / encode-serve — record spans into
a preallocated builder. Device phases take their span boundaries at
points the tick ALREADY synchronizes (`block_until_ready` fences that
exist for correctness), so tracing adds zero host syncs and zero device
round-trips; span timing is host `perf_counter_ns` only.

A model refresh (`models/trainer.train`, `models/stacked.stack_dataset`)
is traced the same way through `operation_span`: the root of a trace of
its own when no trace is open on the thread (a tool, a scheduler
thread, the benchmark), a child of the tick when one is. Its spans are
named `refresh.*` and may carry counts (`TRACER.note`).

Every span also opens a `jax.profiler.TraceAnnotation` of its name for
its lifetime: under a profiler session (`POST /debug/profile`) the
program's spans lie in the trace's host plane on the device's clock;
with no session the annotation costs under a microsecond.

Finished traces land in a ring (`KMAMIZ_TRACE_RING` traces, default
256) and export as Zipkin v2 JSON trace groups at `GET /debug/traces` —
in exactly the Istio-sidecar span shape the ingest path parses
(`synth.make_raw_window`), so the processor can re-ingest its own
export and build a dependency graph of its own pipeline (dogfooding:
the self-trace round-trip test).

Overhead: when disabled (`KMAMIZ_TELEMETRY=0`) `tick()`/`span()` yield
immediately with no allocation. When enabled, a span is one list append
of a 4-tuple and one annotation; Zipkin formatting happens only at
export time, never on the tick.
"""
from __future__ import annotations

import os
import threading
import time
from collections import deque
from contextlib import contextmanager
from typing import Dict, List, Optional, Tuple

from .profiling import events as prof_events
from .registry import REGISTRY

# span taxonomy: canonical phase names (docs/OBSERVABILITY.md)
PHASES = (
    "parse",
    "quarantine",
    "wal-append",
    "merge",
    "assemble",
    "pack",
    "host-transfer",
    "walk",
    # same tick stage as "walk" but under the KMAMIZ_SPARSE flat-gather
    # walk dispatch (graph/store._sparse_walk_default) — a distinct name
    # so graftprof --diff can compare walk backends instead of folding
    # both into one phase
    "walk_sparse",
    "scorers",
    "encode-serve",
    # STLGT continual-training refresh (models/stlgt/trainer.py): a
    # first-class tick phase so online training shows up in warm tick
    # attribution instead of hiding in the unattributed residue
    "stlgt-refresh",
    # graftpilot decision recompute (control/, docs/CONTROL.md): runs
    # at the fold boundary (forecast forward + admission/warm-up/
    # scheduling decisions), a first-class phase so controller cost is
    # attributable and gated like any other
    "control-decide",
    # the model refresh (models/trainer.train, models/stacked.stack_dataset;
    # docs/OBSERVABILITY.md has what each covers)
    "refresh.train",
    "refresh.init",
    "refresh.resume",
    "refresh.pos_weight",
    "refresh.stack",
    "refresh.stack.host_fill",
    "refresh.stack.plan",
    "refresh.stack.device_put",
    "refresh.epoch_block",
    "refresh.loss_fetch",
    "refresh.slow_call",
    "refresh.checkpoint_save",
    "refresh.legacy_epoch",
)

_SELFTRACE_NAMESPACE = "graftscope"
_ROOT_SERVICE = "dp-tick"


def _ring_size() -> int:
    try:
        return max(1, int(os.environ.get("KMAMIZ_TRACE_RING", "256")))
    except ValueError:
        return 256


# jax.profiler.TraceAnnotation, resolved by the first span: telemetry/
# imports jax inside functions only, and a span pays no import lookup
_TraceAnnotation = None


def _annotation(name: str):
    global _TraceAnnotation
    if _TraceAnnotation is None:
        from jax.profiler import TraceAnnotation

        _TraceAnnotation = TraceAnnotation
    return _TraceAnnotation(name)


def telemetry_enabled() -> bool:
    """KMAMIZ_TELEMETRY gate, default ON. Re-read per tick (not per
    span) so tests and operators can flip it without a restart."""
    return os.environ.get("KMAMIZ_TELEMETRY", "1") not in ("0", "false", "")


class _TraceBuilder:
    """One in-flight trace: spans as (name, start_ns, dur_ns, parent_idx).

    Built once per tick; appends are the only hot-path operation.
    """

    __slots__ = (
        "trace_id",
        "tick_id",
        "wall_us",
        "t0_ns",
        "spans",
        "counts",
        "_stack",
        "status",
    )

    def __init__(self, trace_id: str, root_name: str, tick_id: int = 0) -> None:
        self.trace_id = trace_id
        # the graftprof tick id this trace's events carry: its own, not
        # the process's current one, which a trace on another thread moves
        self.tick_id = tick_id
        self.wall_us = time.time_ns() // 1000
        self.t0_ns = time.perf_counter_ns()
        # span 0 is the root; dur filled at close
        self.spans: List[Tuple[str, int, int, int]] = [(root_name, 0, -1, -1)]
        # span index -> counts noted while it was open (TickTracer.note)
        self.counts: Dict[int, dict] = {}
        self._stack = [0]
        self.status = "200"

    def open_span(self, name: str) -> int:
        idx = len(self.spans)
        self.spans.append(
            (name, time.perf_counter_ns() - self.t0_ns, -1, self._stack[-1])
        )
        self._stack.append(idx)
        return idx

    def close_span(self, idx: int) -> None:
        name, start, _, parent = self.spans[idx]
        self.spans[idx] = (
            name,
            start,
            time.perf_counter_ns() - self.t0_ns - start,
            parent,
        )
        if self._stack and self._stack[-1] == idx:
            self._stack.pop()

    def close(self) -> None:
        name, start, _, parent = self.spans[0]
        self.spans[0] = (
            name,
            start,
            time.perf_counter_ns() - self.t0_ns,
            parent,
        )


class TickTracer:
    """Ring of finished tick traces + the per-thread open builder."""

    def __init__(self) -> None:
        self._ring: deque = deque(maxlen=_ring_size())
        self._lock = threading.Lock()
        self._seq = 0
        self._tls = threading.local()

    # -- hot path --------------------------------------------------------
    def current(self) -> Optional[_TraceBuilder]:
        return getattr(self._tls, "builder", None)

    @contextmanager
    def tick(self, root_name: str = _ROOT_SERVICE):
        """Open a trace for one tick. No-op (yields None) when telemetry
        is off or a trace is already open on this thread (re-entrancy:
        ingest-inside-collect keeps one trace)."""
        if not telemetry_enabled() or self.current() is not None:
            yield None
            return
        with self._trace(root_name, prof_events.note_tick_start()) as builder:
            try:
                yield builder
            finally:
                builder.close()
                prof_events.note_tick_end(
                    root_name, builder.spans[0][2], builder.tick_id
                )

    @contextmanager
    def _trace(self, root_name: str, tick_id: int):
        """Open a trace on this thread; the caller closes the builder
        (it reports the root's duration) and this files it in the ring."""
        with self._lock:
            self._seq += 1
            trace_id = f"graftscope-{self._seq}"
        builder = _TraceBuilder(trace_id, root_name, tick_id)
        self._tls.builder = builder
        try:
            with _annotation(root_name):
                yield builder
        finally:
            self._tls.builder = None
            with self._lock:
                self._ring.append(builder)

    @contextmanager
    def span(self, name: str):
        """Record one phase span on the current trace (no-op outside a
        trace or with telemetry off)."""
        builder = self.current()
        if builder is None:
            yield
            return
        idx = builder.open_span(name)
        try:
            with _annotation(name):
                yield
        finally:
            builder.close_span(idx)

    def note(self, **counts) -> None:
        """Attach counts to the innermost open span of this thread's
        trace (slots read, bytes stacked, hit or build); exported as
        Zipkin tags. No-op outside a trace."""
        builder = self.current()
        if builder is not None and builder._stack:
            builder.counts.setdefault(builder._stack[-1], {}).update(counts)

    def children_ms(self) -> Dict[str, float]:
        """Where the innermost open span of this thread's trace has spent
        its time so far: the durations (ms) of its closed direct children
        by name and, under "self", what of it they do not cover. Empty
        outside a trace. For a record of ONE slow call (models/trainer)."""
        builder = self.current()
        if builder is None or not builder._stack:
            return {}
        idx = builder._stack[-1]
        out: Dict[str, float] = {}
        for name, _start, dur_ns, parent in builder.spans:
            if parent == idx and dur_ns >= 0:
                out[name] = out.get(name, 0.0) + dur_ns / 1e6
        so_far_ns = time.perf_counter_ns() - builder.t0_ns - builder.spans[idx][1]
        out["self"] = so_far_ns / 1e6 - sum(out.values())
        return out

    def annotate_last(self, name: str, dur_ms: float) -> None:
        """Append a post-tick span (e.g. encode-serve, which happens
        after the tick's trace closed — possibly on a different thread
        when the watchdog ran the tick on a worker) to the most recent
        trace in the ring, parented on its root."""
        if not telemetry_enabled():
            return
        with self._lock:
            if not self._ring:
                return
            tb = self._ring[-1]
            _rn, rstart, rdur, _rp = tb.spans[0]
            start = rstart + (rdur if rdur >= 0 else 0)
            tb.spans.append((name, start, max(0, int(dur_ms * 1e6)), 0))
        prof_events.emit(name, max(0, int(dur_ms * 1e6)))
        h = SPAN_HANDLES.get(name)
        if h is not None:
            h.observe(dur_ms)

    # -- export (cold path) ----------------------------------------------
    def traces(self) -> List[_TraceBuilder]:
        with self._lock:
            return list(self._ring)

    def export_zipkin(self) -> List[List[dict]]:
        """Ring contents as Zipkin v2 JSON trace groups, in the
        Istio-sidecar span shape the raw-ingest path parses — feeding
        this back into `ingest_raw_window` yields the pipeline's own
        dependency graph."""
        groups = []
        for tb in self.traces():
            group = []
            for i, (name, start_ns, dur_ns, parent) in enumerate(tb.spans):
                svc = name.replace("_", "-").replace(".", "-")
                ns = _SELFTRACE_NAMESPACE
                url = f"http://{svc}.{ns}.svc.cluster.local/tick/{svc}"
                counts = {
                    f"kmamiz.{k}": str(v)
                    for k, v in tb.counts.get(i, {}).items()
                }
                group.append(
                    {
                        "traceId": tb.trace_id,
                        "id": f"{tb.trace_id}-{i}",
                        "parentId": f"{tb.trace_id}-{parent}" if parent >= 0 else None,
                        "kind": "SERVER",
                        "name": f"{svc}.{ns}.svc.cluster.local:80/*",
                        "timestamp": tb.wall_us + start_ns // 1000,
                        "duration": max(1, dur_ns // 1000),
                        "localEndpoint": {"serviceName": svc},
                        "tags": {
                            "component": "proxy",
                            "http.method": "POST",
                            "http.protocol": "HTTP/1.1",
                            "http.status_code": tb.status,
                            "http.url": url,
                            "istio.canonical_revision": "latest",
                            "istio.canonical_service": svc,
                            "istio.mesh_id": "cluster.local",
                            "istio.namespace": ns,
                            "response_flags": "-",
                            "upstream_cluster": "inbound|9080||",
                            **counts,
                        },
                    }
                )
            if group:
                groups.append(group)
        return groups

    def reset_for_tests(self) -> None:
        with self._lock:
            self._ring = deque(maxlen=_ring_size())
            self._seq = 0
        self._tls = threading.local()


# the process-wide tracer (mirrors REGISTRY's singleton pattern)
TRACER = TickTracer()

# span-latency histogram: one preallocated handle per canonical phase —
# the tick looks handles up by identity, never by formatted label
_SPAN_MS = REGISTRY.histogram_family(
    "kmamiz_tick_span_ms",
    "Per-phase span latency within one collect tick (ms)",
    ("phase",),
)
SPAN_HANDLES = {p: _SPAN_MS.handle(p) for p in PHASES}


@contextmanager
def phase_span(name: str):
    """Span + histogram observation for one canonical phase. The handle
    dict is module-scope; unknown names trace but skip the histogram."""
    builder = TRACER.current()
    if builder is None:
        yield
        return
    idx = builder.open_span(name)
    try:
        with _annotation(name):
            yield
    finally:
        builder.close_span(idx)
        dur_ns = builder.spans[idx][2]
        prof_events.emit(name, dur_ns, builder.tick_id)
        _observe(name, dur_ns)


def _observe(name: str, dur_ns: int) -> None:
    h = SPAN_HANDLES.get(name)
    if h is not None:
        h.observe(dur_ns / 1e6)


@contextmanager
def operation_span(name: str):
    """A span that is the ROOT of a new trace when none is open on this
    thread, and a CHILD of the open trace when one is: a model refresh
    called from a tick nests under it, called from a tool or a scheduler
    thread it is a trace of its own. Observed in the histogram either
    way; a no-op with telemetry off.

    A root is no tick: it takes a tick id of its own without making it
    the process's current one and runs no per-tick hook, so a tick that
    runs meanwhile on another thread keeps its events and its
    native-counter deltas. The graftprof ring gets the span as a root
    event only (`ROOT_EVENTS` names it as a denominator): as a child it
    is explained by the phases inside it, and counting it as well would
    count them twice."""
    builder = TRACER.current()
    if builder is not None:
        idx = builder.open_span(name)
        try:
            with _annotation(name):
                yield
        finally:
            builder.close_span(idx)
            _observe(name, builder.spans[idx][2])
        return
    if not telemetry_enabled():
        yield
        return
    with TRACER._trace(name, prof_events.new_tick_id()) as builder:
        try:
            yield
        finally:
            builder.close()
            dur_ns = builder.spans[0][2]
            prof_events.emit(name, dur_ns, builder.tick_id)
            _observe(name, dur_ns)
