"""graftstream: the overlapped micro-tick pipeline (KMAMIZ_STREAM).

The serial tick runs parse -> merge -> score as one sequential wall:
freshness is bounded by the SUM of the stages, not by the slowest one.
This engine pipelines ACROSS tick windows instead — while window N
merges and scores on device, window N+1 parses on the native shards and
uploads through the store's double-buffer `UploadPipeline`, and window
N+2 accumulates at the source:

    producer thread    |  caller thread (consumer)
    -------------------+---------------------------------
    prepare_tick(N+2)  |  merge_prepared(N+1)
      parse / dedup    |  graph.stage_fence()   <- hand-off
      WAL append       |  finish_tick(N+1)      <- score/serve
      span batch       |

Stage hand-off contract (why this is bit-exact vs KMAMIZ_STREAM=0,
pinned by tests/test_stream.py):

- ALL endpoint interning happens inside prepare_tick (spans_to_batch),
  which the producer runs strictly in request order — id assignment is
  identical to the serial path;
- WAL appends and the dedup-map updates also live in prepare_tick, so
  WAL ordering and the processed-set evolution match serially;
- the merge side only LOOKS UP interner state (merge_window_edges /
  intern_window_edges return None before any mutation on a miss) under
  the store lock, so a concurrent prepare can extend the interner
  without perturbing an in-flight merge;
- merges run on the consumer strictly in order, and `stage_fence()`
  (GraphStore) retires every in-flight upload + deferred merge before
  the score stage reads the graph — the explicit merge->score fence.

Freshness: prepare_tick stamps the arrival watermark and finish_tick
observes arrival->visible on the telemetry freshness plane; overlap
shows up there directly (the p99 approaches max(stage) instead of
sum(stages)).

Degraded mode: the engine does not weaken the watchdog — an overrunning
micro-tick still trips `TickDeadlineExceeded`, with the reason renamed
``stream-overrun`` so the stale payload says which mode degraded; the
server's last-good machinery serves exactly as before. The deadline env
parse is cached per stream EPOCH (KMAMIZ_STREAM_EPOCH_TICKS micro-ticks)
instead of per tick — see TickWatchdog.begin_stream_epoch.

``KMAMIZ_STREAM=0`` (the default) keeps the legacy serial tick as the
parity reference.
"""
from __future__ import annotations

import os
import queue
import threading
from typing import List, Optional, Sequence

#: watchdog trip reason for an overrunning micro-tick: same degrade
#: path as REASON_DEADLINE, distinct label so staleReason tells the
#: operator the stream engine (not the batch tick) missed its budget
REASON_STREAM_OVERRUN = "stream-overrun"

DEFAULT_DEPTH = 2
MAX_DEPTH = 8
DEFAULT_EPOCH_TICKS = 32


def stream_enabled(default: str = "0") -> bool:
    """KMAMIZ_STREAM gate (default OFF: the serial tick is the parity
    reference and stays the tier-1 behavior)."""
    return os.environ.get("KMAMIZ_STREAM", default) not in ("0", "false", "")


def stream_depth() -> int:
    """Prepared-tick hand-off queue bound (KMAMIZ_STREAM_DEPTH, default
    2, clamped to [1, 8]): how many windows may sit parsed-but-unmerged.
    Depth 1 still overlaps one prepare with one merge; deeper only buys
    burst absorption at the cost of staler watermarks in the queue."""
    try:
        depth = int(os.environ.get("KMAMIZ_STREAM_DEPTH", DEFAULT_DEPTH))
    except ValueError:
        depth = DEFAULT_DEPTH
    return max(1, min(MAX_DEPTH, depth))


def stream_epoch_ticks() -> int:
    """Micro-ticks per stream epoch (KMAMIZ_STREAM_EPOCH_TICKS, default
    32, floor 1): the cadence at which the watchdog re-reads
    KMAMIZ_TICK_DEADLINE_MS under streaming."""
    try:
        ticks = int(
            os.environ.get("KMAMIZ_STREAM_EPOCH_TICKS", DEFAULT_EPOCH_TICKS)
        )
    except ValueError:
        ticks = DEFAULT_EPOCH_TICKS
    return max(1, ticks)


# -- module stats (conftest autouse reset) ------------------------------------

_stats_lock = threading.Lock()
_stats = {"micro_ticks": 0, "streams": 0, "fences": 0, "queue_high_water": 0}


def stats() -> dict:
    with _stats_lock:
        return dict(_stats)


def _note(key: str, value: int = 1, high_water: bool = False) -> None:
    with _stats_lock:
        if high_water:
            _stats[key] = max(_stats[key], value)
        else:
            _stats[key] += value


def reset_for_tests() -> None:
    with _stats_lock:
        for key in _stats:
            _stats[key] = 0


class StreamEngine:
    """Micro-tick driver for ONE DataProcessor (one tenant's graph).

    `collect(request)` is the server's per-request entry: same
    prepare/merge/finish as the serial tick plus the explicit stage
    fence and the epoch accounting — under HTTP each request is one
    micro-tick and the OS/network overlaps arrivals. `run_stream`
    drives a known request sequence with true producer/consumer
    overlap (the scenario runner uses it)."""

    def __init__(self, processor, watchdog=None) -> None:
        self.processor = processor
        self.watchdog = watchdog
        self._tick_no = 0
        self._epoch_lock = threading.Lock()

    # -- epoch accounting -----------------------------------------------------

    def note_micro_tick(self) -> int:
        """Count one micro-tick; at every epoch boundary (including the
        first tick) refresh the watchdog's cached deadline parse."""
        with self._epoch_lock:
            boundary = self._tick_no % stream_epoch_ticks() == 0
            self._tick_no += 1
        _note("micro_ticks")
        if boundary and self.watchdog is not None:
            self.watchdog.begin_stream_epoch()
        return self._tick_no

    # -- single-request path (dp_server) --------------------------------------

    def collect(self, request: dict) -> dict:
        """One micro-tick: serial-identical stage order with the
        explicit merge->score fence. Bit-exactness vs processor.collect
        is structural — same calls, same thread, same order. Epoch
        accounting is the DRIVER's job (note_micro_tick before the
        watchdog reads its deadline), not this stage path's."""
        proc = self.processor
        prep = proc.prepare_tick(request)
        proc.merge_prepared(prep)
        proc.graph.stage_fence()
        _note("fences")
        return proc.finish_tick(prep)

    # -- overlapped sequence path (bench / scenarios) -------------------------

    def run_stream(self, requests: Sequence[dict]) -> List[dict]:
        """Drive the request sequence through the three-stage pipeline.
        Responses come back in request order; the merged graph, WAL and
        per-tenant graph_signature are bit-exact with running the same
        sequence through the serial tick (KMAMIZ_STREAM=0)."""
        proc = self.processor
        depth = stream_depth()
        q: "queue.Queue" = queue.Queue(maxsize=depth)
        stop = threading.Event()

        def _put(item) -> bool:
            # bounded hand-off that stays responsive to consumer death:
            # a plain blocking put would deadlock the producer if the
            # consumer raised with the queue full
            while not stop.is_set():
                try:
                    q.put(item, timeout=0.1)
                    return True
                except queue.Full:
                    continue
            return False

        def _producer() -> None:
            try:
                for i, request in enumerate(requests):
                    # prepare stage: parse/dedup/WAL/intern in strict
                    # request order on this one thread — the ordering
                    # half of the bit-exactness contract
                    prep = proc.prepare_tick(request)
                    if not _put(("tick", i, prep)):
                        return
            except BaseException as err:  # delivered to the consumer
                _put(("error", None, err))
                return
            _put(("end", None, None))

        producer = threading.Thread(
            target=_producer, name="kmamiz-stream-prepare", daemon=True
        )
        producer.start()
        _note("streams")

        responses: List[dict] = []
        try:
            while True:
                _note("queue_high_water", q.qsize(), high_water=True)
                tag, _i, payload = q.get()
                if tag == "end":
                    break
                if tag == "error":
                    raise payload
                self.note_micro_tick()
                # merge stage: strictly in order, then the explicit
                # hand-off fence before score/serve reads the graph
                proc.merge_prepared(payload)
                proc.graph.stage_fence()
                _note("fences")
                responses.append(proc.finish_tick(payload))
        finally:
            stop.set()
            producer.join(timeout=5.0)
            if self.watchdog is not None:
                self.watchdog.end_stream_epoch()
        return responses


def engine_for(processor, watchdog=None) -> StreamEngine:
    """The processor's lazily-attached engine (one per tenant runtime —
    TenantRuntime has fixed slots, the processor is the natural host)."""
    eng = getattr(processor, "_stream_engine", None)
    if eng is None:
        eng = StreamEngine(processor, watchdog=watchdog)
        processor._stream_engine = eng
    elif watchdog is not None and eng.watchdog is None:
        eng.watchdog = watchdog
    return eng
