"""Double-buffered host->device uploads (KMAMIZ_UPLOAD_DEPTH).

`jax.device_put` is asynchronous: it enqueues the copy and returns a
future-like Array immediately, and any kernel dispatched on that array
is sequenced after the copy on the DEVICE stream — the host never has
to wait for the bytes to land before dispatching. The legacy ingest
path nevertheless called `jax.block_until_ready` right after every
`device_put` so `transfer_ms` measured the raw copy: dead host time
per big window during which the device sat idle too.

`UploadPipeline` keeps up to `depth` upload GROUPS in flight instead:
window N's copy streams while the host packs window N+1 and the device
walks window N-1. The host blocks only when the in-flight window is
full — and then only on the OLDEST group, which by that point has had
one-or-more whole windows of wall time to complete. `transfer_ms`
becomes the wait the host ACTUALLY paid (the pipeline's stall), which
is the number the ingest wall sees; what the host spent blocked on
transfers is visible as `upload_stats()["blocked_ms"]`.

depth 0 restores the legacy synchronous behavior bit-for-bit (the
device arrays a group returns are identical either way — only the WHEN
of the host-side wait moves, never device values, so graph results are
unaffected by the knob).

The pipeline is NOT thread-safe on its own; GraphStore owns one and
touches it only under the store lock (the same discipline as the
staged-window list).
"""
from __future__ import annotations

import os
from collections import deque
from typing import Optional

from kmamiz_tpu.telemetry.profiling import events as prof_events

#: two windows in flight hides one full copy behind one full
#: pack+dispatch without pinning more than two windows of host+device
#: staging memory — the classic double buffer
DEFAULT_DEPTH = 2


def upload_depth(depth: Optional[int] = None) -> int:
    """The configured in-flight window count (KMAMIZ_UPLOAD_DEPTH,
    default 2, floor 0 = legacy synchronous uploads)."""
    if depth is not None:
        return max(0, int(depth))
    try:
        return max(0, int(os.environ.get("KMAMIZ_UPLOAD_DEPTH", DEFAULT_DEPTH)))
    except ValueError:
        return DEFAULT_DEPTH


class UploadPipeline:
    """Depth-bounded window of in-flight host->device upload groups."""

    def __init__(self, depth: Optional[int] = None) -> None:
        self.depth = upload_depth(depth)
        self._in_flight: deque = deque()
        self.uploads = 0
        self.blocked_ms = 0.0
        self.peak_in_flight = 0
        # stage hand-off fences noted by the stream engine (graftstream):
        # each one is a merge->score boundary that drained this pipeline
        self.fences = 0

    def note_fence(self) -> None:
        """Count one explicit stage hand-off fence (GraphStore
        stage_fence); the drain itself is the caller's, this only keeps
        the pipelining observable in stats()."""
        self.fences += 1

    def put(self, host_arrays, sharding=None):
        """Issue one group of device_puts; returns (device_arrays,
        blocked_ms). blocked_ms is the host wait this call actually
        paid: the full copy at depth 0, only the pipeline stall (retire
        of groups past `depth`) otherwise."""
        import jax

        t0 = prof_events.now_ms()
        if sharding is None:
            out = [jax.device_put(a) for a in host_arrays]
        else:
            out = [jax.device_put(a, sharding) for a in host_arrays]
        self.uploads += 1
        if self.depth <= 0:
            # legacy path: the copy must finish before the host moves on
            # graftlint: disable=host-sync-in-hot-path -- KMAMIZ_UPLOAD_DEPTH=0 compat: blocking IS the requested behavior and the measurement
            jax.block_until_ready(out)
            return out, prof_events.now_ms() - t0
        self._in_flight.append(out)
        while len(self._in_flight) > self.depth:
            # graftlint: disable=host-sync-in-hot-path -- pipeline retire: bounded backpressure on the OLDEST in-flight copy, the one wait double buffering cannot hide
            jax.block_until_ready(self._in_flight.popleft())
        self.peak_in_flight = max(self.peak_in_flight, len(self._in_flight))
        blocked = prof_events.now_ms() - t0
        self.blocked_ms += blocked
        return out, blocked

    def drain(self) -> float:
        """Retire every in-flight group; returns the ms spent waiting.
        Called at the stream's existing device fence (finalize/read), so
        in steady state the copies are long done and this is ~free."""
        if not self._in_flight:
            return 0.0
        import jax

        t0 = prof_events.now_ms()
        while self._in_flight:
            # graftlint: disable=host-sync-in-hot-path -- drain runs at the pre-existing read fence, not inside the per-window loop
            jax.block_until_ready(self._in_flight.popleft())
        waited = prof_events.now_ms() - t0
        self.blocked_ms += waited
        return waited

    def stats(self) -> dict:
        # depth 0 is the legacy synchronous mode: put() blocks inline and
        # never accounts blocked_ms, so per-upload stall rates are only
        # meaningful when pipelined — report the mode explicitly and keep
        # every derived rate guarded (uploads can be 0 on a fresh store)
        pipelined = self.depth > 0
        return {
            "depth": self.depth,
            "mode": "pipelined" if pipelined else "sync",
            "uploads": self.uploads,
            "in_flight": len(self._in_flight),
            "peak_in_flight": self.peak_in_flight,
            "blocked_ms": round(self.blocked_ms, 1),
            "fences": self.fences,
            "blocked_ms_per_upload": (
                round(self.blocked_ms / self.uploads, 3)
                if pipelined and self.uploads
                else 0.0
            ),
        }
