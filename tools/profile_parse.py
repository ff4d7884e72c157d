"""Dev-box profiling harness for the native span parse.

Times kmamiz_tpu.native.parse_spans on the bench's 1.05M-span synthetic
window across thread counts, printing per-rep walls plus the native phase
breakdown, min and median. No jax import needed (kmamiz_tpu.synth is
jax-free; make_raw_window is the generator chip_smoke.py ingests, so
the profiled workload IS the smoke's workload).
"""
from __future__ import annotations

import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from kmamiz_tpu.synth import make_raw_window  # noqa: E402
from kmamiz_tpu import native as native_mod  # noqa: E402


def main() -> None:
    n_traces = int(sys.argv[1]) if len(sys.argv) > 1 else 150_000
    reps = int(sys.argv[2]) if len(sys.argv) > 2 else 5
    threads = [int(t) for t in sys.argv[3].split(",")] if len(sys.argv) > 3 else [1, 2, 4]
    t0 = time.perf_counter()
    # the bench headline's BASELINE workload shape (1k svc / 10 urls
    # each) so the profiled parse IS the headline parse
    raw = make_raw_window(n_traces, 7, n_services=1000, urls_per_service=10)
    print(f"window: {n_traces * 7} spans, {len(raw)/1e6:.1f} MB "
          f"(gen {time.perf_counter()-t0:.1f}s)")
    for T in threads:
        walls, tms = [], []
        for _ in range(reps):
            t0 = time.perf_counter()
            out = native_mod.parse_spans(raw, threads=T)
            walls.append((time.perf_counter() - t0) * 1000)
            if out is None:
                print("native loader unavailable")
                return
            tms.append(out["timings"])
        walls_s = sorted(walls)
        best = walls.index(min(walls))
        tm = tms[best]
        print(
            f"t{T}: min {walls_s[0]:7.1f} ms  med {walls_s[len(walls_s)//2]:7.1f}"
            f"  max {walls_s[-1]:7.1f}  | best rep: prescan {tm['prescan_us']/1000:6.1f}"
            f"  parse {tm['parse_us']/1000:6.1f}  merge {tm['merge_us']/1000:6.1f}"
            f"  (native threads {tm['threads']})"
        )
        print(f"     reps: {[round(w) for w in walls]}")


if __name__ == "__main__":
    main()
