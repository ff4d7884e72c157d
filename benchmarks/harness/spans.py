"""Spans and counters the benchmark records around its calls into the
program. The program's own spans (`refresh.*`, since PR 26) reach the readers
through `harness/program_spans.py`.

A span is kept in memory on the host's monotonic clock and, while the
profiler runs, also written into its trace as a `TraceAnnotation` of the
same name, so the device trace and the spans share one clock there.
"""
from __future__ import annotations

import time
from contextlib import contextmanager
from dataclasses import dataclass, field
from typing import Dict, Iterator, List


@dataclass
class Span:
    name: str
    start_s: float
    end_s: float

    @property
    def seconds(self) -> float:
        return self.end_s - self.start_s


@dataclass
class Recorder:
    spans: List[Span] = field(default_factory=list)
    counters: Dict[str, float] = field(default_factory=dict)

    @contextmanager
    def span(self, name: str) -> Iterator[None]:
        import jax

        start = time.perf_counter()
        try:
            with jax.profiler.TraceAnnotation(name):
                yield
        finally:
            self.spans.append(Span(name, start, time.perf_counter()))

    def named(self, name: str) -> List[Span]:
        return [s for s in self.spans if s.name == name]
