"""Probe for the bench headline: the steady-state deployed streaming
ingest (bench.py's HEADLINE section), with the full per-chunk phase
breakdown printed per rep — for finding where the wall goes without
running the whole bench. Runs on whatever device JAX finds and says so.

Usage: python tools/probe_headline.py [reps] [chunks]
"""
from __future__ import annotations

import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from kmamiz_tpu.synth import make_raw_window  # noqa: E402


def main() -> None:
    reps = int(sys.argv[1]) if len(sys.argv) > 1 else 4
    from kmamiz_tpu.server.processor import (
        DEFAULT_STREAM_CHUNKS,
        DataProcessor,
    )

    n_chunks = int(sys.argv[2]) if len(sys.argv) > 2 else DEFAULT_STREAM_CHUNKS
    e2e_traces = 150_000
    chunk_traces = e2e_traces // n_chunks
    n_services, urls_per_svc = 1_000, 10

    def make_chunks(prefix: str):
        return [
            make_raw_window(
                chunk_traces,
                7,
                t_start=i * chunk_traces,
                trace_prefix=prefix,
                n_services=n_services,
                urls_per_service=urls_per_svc,
            )
            for i in range(n_chunks)
        ]

    bench_clock = {"ms": 1_700_000_000_000.0}
    dp = DataProcessor(
        trace_source=lambda lb, t, lim: [],
        now_ms=lambda: bench_clock["ms"],
    )
    import jax

    print(f"device: {jax.devices()[0].platform} {jax.devices()[0].device_kind}")
    t0 = time.perf_counter()
    dp.ingest_raw_stream(iter(make_chunks("c")))
    print(f"cold: wall {(time.perf_counter() - t0) * 1000:.0f} ms")
    bench_clock["ms"] += 301_000
    t0 = time.perf_counter()
    dp.ingest_raw_stream(iter(make_chunks("s")))
    print(f"steady-warmup: wall {(time.perf_counter() - t0) * 1000:.0f} ms")
    n_spans = e2e_traces * 7
    for k in range(reps):
        bench_clock["ms"] += 301_000
        chunks = make_chunks(f"r{k}x")
        t0 = time.perf_counter()
        s = dp.ingest_raw_stream(iter(chunks))
        wall_ms = (time.perf_counter() - t0) * 1000
        print(
            f"rep {k}: wall {wall_ms:.0f} ms  "
            f"-> {n_spans / wall_ms * 1000 / 1e6:.2f}M spans/s  "
            f"drain {s['drain_ms']:.0f} ms"
        )
        for d in s["chunk_detail"]:
            print(
                f"    spans {d['spans']:7d}  parse {d['parse_ms']:7.1f}  "
                f"merge {d['merge_ms']:7.1f}  transfer {d['transfer_ms']:7.1f}"
            )


if __name__ == "__main__":
    main()
