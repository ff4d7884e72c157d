"""HBM-resident endpoint-dependency graph store.

The persistent equivalent of the reference's EndpointDependencies cache
(/root/reference/src/classes/Cacheable/CEndpointDependencies.ts) redesigned
for the device: the edge set lives as capacity-padded int32 column arrays
(src_ep, dst_ep, distance); window merges (the reference's set-union
combineWith, EndpointDependencies.ts:499-563) are lexsort+unique kernels;
scorers read the arrays in place (kmamiz_tpu.ops.scorers). Capacities grow
by doubling so XLA compiles a bounded number of program shapes. No int64
anywhere — the production TPU path runs with x64 disabled.

Intentional deviation from the reference: merging keeps the full edge union.
The reference's combineWith overwrites same-window duplicate records
(JS Map.set), silently dropping edges observed in the overwritten record.
"""
from __future__ import annotations

import logging
import os
import threading
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from kmamiz_tpu.core import programs
from kmamiz_tpu.core.interning import EndpointInterner, StringInterner
from kmamiz_tpu.core.profiling import step_timer
from kmamiz_tpu.core.spans import (
    KIND_SERVER,
    ROW_SLOTS,
    SpanBatch,
    _pad_size as _pow2,
    pack_trace_rows,
)
from kmamiz_tpu.ops import scorers as scorer_ops
from kmamiz_tpu.ops.double_buffer import UploadPipeline
from kmamiz_tpu.telemetry.profiling import events as prof_events
from kmamiz_tpu.telemetry.tracing import phase_span
from kmamiz_tpu.ops import sparse
from kmamiz_tpu.ops import window as window_ops
from kmamiz_tpu.ops.sortutil import (
    EDGE_KEY_MAX_DIST,
    EDGE_KEY_MAX_EP,
    SENTINEL,
    compact_unique,
    compact_unique_edges_packed,
)

logger = logging.getLogger("kmamiz_tpu.graph.store")


@programs.register("graph.edge_mask")
@jax.jit
def _edge_mask(col):
    """Valid-edge mask for a SENTINEL-padded column, computed inside jit
    so the hot tick never pays an eager op whose baked host constant is
    an implicit host->device transfer (trips jax.transfer_guard)."""
    return col != SENTINEL


@programs.register("graph.fit_edges")
@partial(jax.jit, static_argnames=("cap",))
def _fit_edges(src, dst, dist, cap):
    """Slice or SENTINEL-pad merged edge columns to exactly `cap` rows
    (the next pow2 capacity). Jitted for the same transfer-guard reason
    as _edge_mask: eager jnp.full/slice ops upload host constants per
    capacity event, which trips jax.transfer_guard on the hot tick."""
    n = int(src.shape[0])
    if cap <= n:
        # compact_unique packs valid edges first, so the prefix is exact
        return src[:cap], dst[:cap], dist[:cap]
    fill = jnp.full(cap - n, SENTINEL, dtype=jnp.int32)
    return (
        jnp.concatenate([src, fill]),
        jnp.concatenate([dst, fill]),
        jnp.concatenate([dist, fill]),
    )


@programs.register("graph.merge_edges")
@jax.jit
def _merge_edges(src_a, dst_a, dist_a, mask_a, src_b, dst_b, dist_b, mask_b):
    src = jnp.concatenate([src_a, src_b])
    dst = jnp.concatenate([dst_a, dst_b])
    dist = jnp.concatenate([dist_a, dist_b])
    mask = jnp.concatenate([mask_a, mask_b])
    (s, d, ds), valid = compact_unique((src, dst, dist), mask)
    return s, d, ds, valid


@programs.register("graph.split_segments")
@partial(jax.jit, static_argnames=("cap", "tail_cap"))
def _split_segments(src, dst, dist, cap, tail_cap):
    """Split a merged edge set into a `cap`-row main segment plus a
    `tail_cap`-row overflow tail — the segment-append growth path.
    compact_unique packs valid edges first, so slicing at `cap` is
    exact; rows past cap+tail_cap are SENTINEL by construction (the
    caller consolidates before the tail can overflow). Both output
    shapes are static, so a capacity crossing re-runs this same warm
    program instead of recompiling the store's program set."""
    main = _fit_edges(src, dst, dist, cap=cap)
    if int(src.shape[0]) <= cap:
        fill = jnp.full(tail_cap, SENTINEL, dtype=jnp.int32)
        return (*main, fill, fill, fill)
    tail = _fit_edges(src[cap:], dst[cap:], dist[cap:], cap=tail_cap)
    return (*main, *tail)


@programs.register("graph.bulk_dist_bounds")
@jax.jit
def _bulk_dist_bounds(dist, mask):
    """Masked (min, max) distance of a bulk edge batch — the packed-key
    drain gate's bounds update, jitted so a device-resident bulk merge
    stays transfer-clean under jax.transfer_guard (the eager form baked
    the neutral element as an implicit host->device constant)."""
    masked = jnp.where(mask, dist, 1)
    return jnp.stack([jnp.min(masked), jnp.max(masked)])


@programs.register("graph.cat_segments")
@jax.jit
def _cat_segments(src, dst, dist, t_src, t_dst, t_dist):
    """Flatten the main + tail segments into the single column view
    consumers (scorers, walk unions, edge_arrays) read. Jitted so the
    snapshot never pays an eager concat whose baked constants trip
    jax.transfer_guard on the hot tick."""
    s = jnp.concatenate([src, t_src])
    d = jnp.concatenate([dst, t_dst])
    ds = jnp.concatenate([dist, t_dist])
    return s, d, ds, s != SENTINEL


@programs.register("graph.window_merge")
@partial(jax.jit, static_argnames=("max_depth",))
def _window_merge(
    parent_idx,
    kind,
    valid,
    endpoint_id,
    src,
    dst,
    dist,
    mask,
    max_depth=window_ops.MAX_DEPTH,
):
    """Fused window edge-extraction + set-union merge.

    One jitted program per (batch-capacity, store-capacity, depth-bucket)
    so a realtime tick costs a single device round trip: the only host
    sync is the returned valid-edge count scalar."""
    edges = window_ops.dependency_edges(
        parent_idx, kind, valid, endpoint_id, max_depth=max_depth
    )
    s, d, ds, v = _merge_edges(
        src,
        dst,
        dist,
        mask,
        edges.ancestor_ep.reshape(-1),
        edges.descendant_ep.reshape(-1),
        edges.distance.reshape(-1),
        edges.mask.reshape(-1),
    )
    return s, d, ds, v, v.sum()


def _sparse_walk_default() -> bool:
    """Whether the store's packed walks take the flat-gather sparse
    variant: on under any non-xla KMAMIZ_SPARSE backend on non-TPU hosts
    (the one-hot einsum's O(T*L*L) flops only pay off on the MXU)."""
    return sparse.use_sparse() and jax.default_backend() != "tpu"


def _grow_mode_default() -> str:
    """KMAMIZ_STORE_GROW: 'segment' (default) pins the main edge arrays
    at a fixed capacity and absorbs growth into a pre-allocated overflow
    tail segment, so crossing a capacity boundary re-runs only programs
    that are already warm (zero new compiles on the crossing tick);
    'repack' is the legacy policy — full re-pad to the next pow2 per
    doubling, recompiling every capacity-shaped program mid-serve."""
    v = os.environ.get("KMAMIZ_STORE_GROW", "segment").strip().lower()
    return v if v in ("segment", "repack") else "segment"


def _tail_shift() -> int:
    """KMAMIZ_STORE_TAIL_SHIFT: tail capacity = main >> shift (default
    3 -> 12.5% headroom before a consolidation repack)."""
    try:
        return max(0, int(os.environ.get("KMAMIZ_STORE_TAIL_SHIFT", "3")))
    except ValueError:
        return 3


def _walk_packed(sparse_walk: bool):
    """Select the packed ancestor-walk kernel: the MXU one-hot einsum
    (TPU default) or the flat-gather sparse variant (bit-exact, no
    [T, L, L] adjacency — what CPU hosts want). The choice is a STATIC
    jit arg on every window program so both variants compile as distinct
    registered programs and graftprof attributes them separately."""
    return (
        window_ops.dependency_edges_packed_sparse
        if sparse_walk
        else window_ops.dependency_edges_packed
    )


@programs.register("graph.window_edges_packed")
@partial(jax.jit, static_argnames=("max_depth", "sparse_walk"))
def _window_edges_packed(
    parent_slot, kind, valid, endpoint_id, max_depth, sparse_walk=False
):
    """Walk-only kernel: this window's flat (ancestor, descendant,
    distance, mask) candidate columns, store untouched. The staged-merge
    overflow fallback re-walks a window through this when its compacted
    prefix truncated (see _drain_staged_locked)."""
    edges = _walk_packed(sparse_walk)(
        parent_slot, kind, valid, endpoint_id, max_depth=max_depth
    )
    return (
        edges.ancestor_ep.reshape(-1),
        edges.descendant_ep.reshape(-1),
        edges.distance.reshape(-1),
        edges.mask.reshape(-1),
    )


@programs.register("graph.window_edges_compact")
@partial(
    jax.jit,
    static_argnames=("max_depth", "stage_cap", "packed_key", "sparse_walk"),
)
def _window_edges_compact(
    parent_slot,
    kind,
    valid,
    endpoint_id,
    max_depth,
    stage_cap,
    packed_key,
    sparse_walk=False,
):
    """Staged-merge kernel for the streaming path: walk this window's
    candidates and self-compact them to a sorted unique prefix, sliced to
    stage_cap rows. Dispatched async per chunk, the sort runs on device
    WHILE the host parses the next chunk; the drain then unions the tiny
    compacted prefixes instead of the full padded candidate arrays
    (~16x fewer rows at bench scale). Returns (src, dst, dist, count);
    count is the TRUE unique total — count > stage_cap means the prefix
    truncated and the drain must re-walk this window (rare: it takes a
    window carrying >stage_cap distinct edges).

    packed_key selects the single-int32-key sort (2x cheaper); the caller
    guarantees the id/dist bounds (sortutil.EDGE_KEY_*)."""
    edges = _walk_packed(sparse_walk)(
        parent_slot, kind, valid, endpoint_id, max_depth=max_depth
    )
    cols = (
        edges.ancestor_ep.reshape(-1),
        edges.descendant_ep.reshape(-1),
        edges.distance.reshape(-1),
    )
    mask = edges.mask.reshape(-1)
    if packed_key:
        (s, d, ds), v = compact_unique_edges_packed(*cols, mask)
    else:
        (s, d, ds), v = compact_unique(cols, mask)
    return s[:stage_cap], d[:stage_cap], ds[:stage_cap], v.sum()


@programs.register("graph.window_merge_packed")
@partial(jax.jit, static_argnames=("max_depth", "sparse_walk"))
def _window_merge_packed(
    parent_slot,
    kind,
    valid,
    endpoint_id,
    src,
    dst,
    dist,
    mask,
    max_depth,
    sparse_walk=False,
):
    """_window_merge over trace-packed [T, L] rows: the ancestor walk runs
    as batched one-hot einsums on the MXU (dependency_edges_packed), ~10x
    cheaper than the flat gather walk at 1M spans; sparse_walk swaps in
    the flat-gather variant for CPU hosts (bit-exact, see _walk_packed).
    max_depth is capped to the window's longest possible chain
    (pow2-bucketed so XLA compiles a bounded number of depths)."""
    edges = _walk_packed(sparse_walk)(
        parent_slot, kind, valid, endpoint_id, max_depth=max_depth
    )
    s, d, ds, v = _merge_edges(
        src,
        dst,
        dist,
        mask,
        edges.ancestor_ep.reshape(-1),
        edges.descendant_ep.reshape(-1),
        edges.distance.reshape(-1),
        edges.mask.reshape(-1),
    )
    return s, d, ds, v, v.sum()


class StoreVersionDrift(RuntimeError):
    """A stacked-merge lane was built from an arena snapshot the store
    has since moved past (concurrent merge between snapshot and adopt).
    The caller re-merges its window serially against the current store —
    merges are set unions, so the fallback stays bit-exact."""


class EndpointGraph:
    """Capacity-padded edge set keyed (src_ep -> dst_ep, distance).

    Edge semantics: src depends-ON dst (src is the CLIENT-side ancestor).

    Capacity policy (the round-5 bench's graph_scale_* extras took it to
    100k endpoints / ~5.2M edges): edge arrays are padded to
    power-of-2 capacities. Two growth modes (KMAMIZ_STORE_GROW / the
    `grow` ctor arg):

    - 'segment' (default, ISSUE 13): the main arrays stay at a fixed
      pow2 capacity C and every store also carries a SENTINEL-padded
      overflow tail of T = C >> KMAMIZ_STORE_TAIL_SHIFT rows (min 256).
      Unions and consumer snapshots always read the flat C+T view
      (graph.cat_segments), and every merge re-splits the union output
      back into (C, T) via graph.split_segments — so a merge whose
      valid count crosses C runs EXACTLY the same warm programs as any
      other merge: the capacity crossing is compile-free. Only when the
      tail itself would overflow (valid > C + T, i.e. >12.5% growth at
      the default shift) does the store consolidate to the next pow2
      main — the one recompiling event, ~8x rarer than the legacy
      per-doubling repack, and one prewarm_compile can precompile its
      shapes ahead of time while the tail absorbs growth.
    - 'repack': the legacy policy — grow by doubling when a union's
      valid count exceeds the current capacity (_apply_merged), full
      re-pad + program-set recompile per doubling.

    Consequences (both modes):
    - XLA program count is O(log(max_edges) * distinct window shapes):
      each (window-bucket, store-capacity) pair compiles once, and
      capacities only double, so a store that grows to E edges passes
      through ~log2(E) capacities total — compiles amortize to zero on a
      long-running server.
    - Merge cost is O((cap + window) log(cap + window)) per union — the
      sort dominates; per-doubling wall times are reported by the bench.
    - Capacity never shrinks (the padded arrays are the high-water mark):
      HBM for 2^23 edges is 3 int32 columns = ~100 MB, well inside a
      single chip; shrink-on-idle is deliberately omitted to keep the
      program-shape set stable.
    - Growth 1M -> 5.2M edges at 100k endpoints passes through 3 union
      programs in total; their compile and run walls on the chip are
      not measured yet (PERF.md).
    """

    def __init__(
        self,
        interner: Optional[EndpointInterner] = None,
        ml_interner: Optional[StringInterner] = None,
        capacity: int = 1024,
        tenant: str = "default",
        grow: Optional[str] = None,
    ) -> None:
        self.tenant = tenant
        self.interner = interner or EndpointInterner()
        self.ml_interner = ml_interner or StringInterner()
        self._src = jnp.full(capacity, SENTINEL, dtype=jnp.int32)
        self._dst = jnp.full(capacity, SENTINEL, dtype=jnp.int32)
        self._dist = jnp.full(capacity, SENTINEL, dtype=jnp.int32)
        # segment growth mode: the (src, dst, dist) overflow tail that
        # absorbs capacity crossings compile-free (class docstring);
        # None under the legacy repack policy
        self._grow = (grow or _grow_mode_default()).strip().lower()
        if self._grow not in ("segment", "repack"):
            raise ValueError(f"unknown grow mode: {self._grow!r}")
        if self._grow == "segment":
            fill = jnp.full(self._tail_cap(capacity), SENTINEL, jnp.int32)
            self._tail = (fill, fill, fill)
        else:
            self._tail = None
        self._n_edges = 0
        # host->device copy time of the LAST merge_window call (ms),
        # for casual introspection only — concurrent mergers use
        # merge_window's per-call return value for accounting.
        self.last_transfer_ms = 0.0
        # double-buffered uploads (ops/double_buffer.py): up to
        # KMAMIZ_UPLOAD_DEPTH window-input groups stream host->device
        # while the host packs the next window; touched only under
        # self._lock, drained at the finalize/read fence
        self._uploads = UploadPipeline()
        self._pending = None  # deferred (src, dst, dist, count) of last merge
        # staged windows (compacted src/dst/dist prefixes + pinned walk
        # inputs) awaiting the batched drain union; bounded by
        # _stage_max_rows
        self._staged = []
        self._staged_rows = 0
        # mid-stream pre-union (streaming drain overlap): earlier staged
        # windows collapse into ONE dispatched-but-unfetched union while
        # later chunks still parse on the host, so the stream's final
        # drain unions a small tail instead of every window at once.
        # _preunion holds (src, dst, dist) valid-first/SENTINEL-padded
        # device arrays that already INCLUDE the store's edges;
        # _preunion_count is its async valid-count scalar (sliced into
        # the next union once landed); _preunion_checks carries the
        # deferred truncation checks (count, cap, dev_in, depth, mesh)
        # whose pinned inputs must re-walk at the drain if truncated.
        self._preunion = None
        self._preunion_count = None
        self._preunion_checks = []
        # rows pinned by _preunion_checks' walk inputs: counts toward the
        # _stage_max_rows backstop (the pre-union zeroes _staged_rows, so
        # without this an unread stream's deferred checks would pin
        # windows x padded-input HBM unbounded — the ADVICE r4 invariant)
        self._preunion_rows = 0
        # distance bounds ever merged (host-tracked): gate the
        # packed-single-key sort fast path at the drain. Walk kernels
        # only emit dist >= 1; warm-start records can carry anything
        # (dist < 1 would wrap the packed key), so loads widen the range.
        self._max_dist = 0
        self._min_dist = 1
        # monotonic state-change counter: API layers key scorer-payload
        # caches on it (bumped by merges and warm-start loads)
        self._version = 0
        # -- scorer caching (ISSUE 1 tentpole) --------------------------
        # label-epoch: bumped by invalidate_labels so cached scorer
        # outputs keyed on it can never survive a label-mapping change
        self._label_epoch = 0
        # device-resident mirrors of the per-endpoint scorer-input
        # tables / fresh mask (keyed snapshots; one upload per table
        # change instead of one per scorer call)
        self._ep_tables_dev = None
        self._fresh_dev = None
        # output memo: full cache key -> ServiceScores/CohesionScores.
        # Entries of older graph versions are pruned on miss, so repeated
        # HTTP reads between merges are O(1) dict hits.
        self._scorer_memo = {}
        # incremental-recompute bases: base key (everything but version)
        # -> (version, outputs); consulted when the dirty-service journal
        # covers the gap
        self._scorer_prev = {}
        # dirty-service journal: (version, frozenset(service_ids)) per
        # window merge. Bounded; merges the journal cannot attribute
        # (bulk edges, warm-start loads, label changes) raise the floor
        # so bases older than it always take the full recompute.
        self._dirty_journal = []
        self._dirty_floor = 0
        # observability: hit/miss/upload/incremental counters (read by
        # the health handler and the bench smoke test)
        self.scorer_stats = {
            "hits": 0,
            "misses": 0,
            "uploads": 0,
            "incremental": 0,
            "full": 0,
        }
        # per-endpoint host-side metadata, padded on demand
        self._ep_record = np.zeros(0, dtype=bool)
        self._ep_last_ts = np.zeros(0, dtype=np.float64)
        # the DP tick mutates from a scheduler thread while API threads
        # read scorers (handlers/graph.py); every state transition and
        # snapshot happens under this reentrant lock. Device kernels run
        # OUTSIDE the lock on immutable jnp snapshots.
        self._lock = threading.RLock()
        _track_store_arenas(self)
        # every graph self-registers into the process-wide tenant arena:
        # an EndpointGraph IS the arena's (tenant, version) index target.
        # Held by weakref there, so short-lived graphs don't accumulate;
        # re-admitting "default" (tests, benches) just replaces the slot.
        from kmamiz_tpu.tenancy.arena import default_arena

        default_arena().admit(tenant, self)

    def arena_bytes(self) -> Dict[str, int]:
        """Tracked device-allocation sizes per arena, for the telemetry
        HBM gauges. Reads `.nbytes` off array handles only (shape
        metadata — no device sync, runs at scrape time anyway)."""

        def nb(arr) -> int:
            try:
                return int(arr.nbytes)
            except Exception:
                return 0

        with self._lock:
            edges = nb(self._src) + nb(self._dst) + nb(self._dist)
            if self._tail is not None:
                edges += sum(nb(a) for a in self._tail)
            staged = sum(
                nb(a)
                for entry in self._staged
                for a in entry
                if hasattr(a, "nbytes")
            )
            if self._preunion is not None:
                staged += sum(nb(a) for a in self._preunion)
            tables = 0
            if self._ep_tables_dev is not None:
                snap = self._ep_tables_dev
                tbls = snap[1] if isinstance(snap, tuple) else snap
                try:
                    tables = sum(nb(a) for a in tbls if hasattr(a, "nbytes"))
                except TypeError:
                    tables = 0
        return {"edges": edges, "staged": staged, "scorer_tables": tables}

    # -- capacity management -------------------------------------------------

    @staticmethod
    def _tail_cap(cap: int) -> int:
        """Tail-segment rows for a main capacity (segment growth mode):
        cap >> KMAMIZ_STORE_TAIL_SHIFT, floored at 256."""
        return max(256, cap >> _tail_shift())

    @property
    def capacity(self) -> int:
        """Main-segment capacity (the pow2 policy capacity). In segment
        growth mode the store can hold up to capacity + tail_capacity
        edges before consolidating."""
        self._finalize_pending()
        return int(self._src.shape[0])

    @property
    def tail_capacity(self) -> int:
        """Overflow-tail rows (segment growth mode); 0 under repack."""
        self._finalize_pending()
        return int(self._tail[0].shape[0]) if self._tail is not None else 0

    @property
    def n_edges(self) -> int:
        self._finalize_pending()
        return self._n_edges

    @property
    def version(self) -> int:
        """Monotonic counter of graph state changes (merges/loads)."""
        with self._lock:
            return self._version

    @property
    def label_epoch(self) -> int:
        """Monotonic counter of label-mapping changes; (version,
        label_epoch) keys every derived payload (scorer caches, encoded
        HTTP responses)."""
        with self._lock:
            return self._label_epoch

    def _ensure_ep_arrays(self, n: int) -> None:
        if len(self._ep_record) < n:
            grow = n - len(self._ep_record)
            self._ep_record = np.concatenate(
                [self._ep_record, np.zeros(grow, dtype=bool)]
            )
            self._ep_last_ts = np.concatenate(
                # graftlint: disable=dtype-drift -- host-side mirror; epoch-ms exceeds f32 integer range
                [self._ep_last_ts, np.zeros(grow, dtype=np.float64)]
            )

    # -- ingestion -----------------------------------------------------------

    def _to_device(self, *host_arrays):
        """Enqueue host arrays to the device; returns (arrays, wait_ms).
        The copy itself is asynchronous — the device sequences any kernel
        dispatched on these arrays after the bytes land, so the host
        never needs them ready. `wait_ms` is the stall this call actually
        paid: at KMAMIZ_UPLOAD_DEPTH=0 the full copy (legacy synchronous
        behavior, the raw-bandwidth measurement), otherwise only the
        pipeline's backpressure on the OLDEST in-flight window (window
        N's copy overlaps window N-1's kernel and window N+1's host-side
        pack)."""
        # explicit device_put (not jnp.asarray): the implicit-transfer
        # form trips jax.transfer_guard("disallow") on a real TPU
        out, ms = self._uploads.put(host_arrays)
        self.last_transfer_ms = ms
        step_timer.record("transfer", ms)
        return out, ms

    def _to_device_sharded(self, mesh, *host_arrays):
        """_to_device onto the deployed mesh: each [rows, ROW_SLOTS]
        array lands row-sharded over the spans axis, so the walk kernel
        runs on every device's local rows with no resharding."""
        from jax.sharding import NamedSharding, PartitionSpec as P

        sh = NamedSharding(mesh, P("spans", None))
        out, ms = self._uploads.put(host_arrays, sharding=sh)
        self.last_transfer_ms = ms
        step_timer.record("transfer", ms)
        return out, ms

    def upload_stats(self) -> dict:
        """Upload-pipeline counters for /timings and the bench (depth,
        uploads, in_flight, peak_in_flight, blocked_ms)."""
        with self._lock:
            return self._uploads.stats()

    @staticmethod
    def _deploy_mesh(n_rows: int):
        """The active deployed mesh when this window is worth sharding
        (at least one packed trace row per device), else None. Window
        merges consult this per call, so a v5e-8 serving process shards
        every big window across all chips automatically while the
        single-chip dev box keeps the single-device kernels
        (VERDICT r4 #1)."""
        from kmamiz_tpu.parallel.mesh import active_mesh

        mesh = active_mesh()
        if mesh is None or n_rows < mesh.shape["spans"]:
            return None
        return mesh

    @staticmethod
    def _pad_rows_for(mesh, arr, fill):
        """Pad a [rows, ROW_SLOTS] host array's leading dim to a multiple
        of the mesh's device count (no-op for pow2 device counts, since
        pack_trace_rows already pow2-pads rows)."""
        n_dev = mesh.shape["spans"]
        rows = arr.shape[0]
        target = -(-rows // n_dev) * n_dev
        if target == rows:
            return arr
        out = np.full((target, arr.shape[1]), fill, dtype=arr.dtype)
        out[:rows] = arr
        return out

    def merge_window(self, batch: SpanBatch, stage: bool = False) -> float:
        """Union this window's dependency edges into the store and update
        per-endpoint record/last-usage metadata. Returns THIS call's
        host->device copy time in ms (per-call, so concurrent mergers
        can't clobber each other's accounting; `last_transfer_ms` keeps
        the most recent value for casual introspection).

        stage=True (the streaming-ingest path) dispatches only the cheap
        ancestor-walk kernel and STAGES its candidate edges; the union
        sort runs once over all staged windows at the next read
        (_finalize_pending), so k chunks cost one big sort instead of k
        serialized ones. stage=False (ticks, one-shot ingest) keeps the
        fused walk+union kernel: one device program per window."""
        with self._lock:
            return self._merge_window_locked(batch, stage)

    def _merge_window_locked(self, batch: SpanBatch, stage: bool = False) -> float:
        self._version += 1
        self._note_dirty_locked(batch)
        packed = pack_trace_rows(
            batch.trace_of, batch.n_spans, batch.parent_idx
        )
        if stage and packed is not None:
            depth = min(
                window_ops.MAX_DEPTH,
                _pow2(max(1, packed.max_trace_len - 1), minimum=4),
            )
            host_in = (
                packed.pack(packed.parent_slots(batch.parent_idx), -1),
                packed.pack(batch.kind, 0),
                packed.pack(batch.valid, False),
                packed.pack(batch.endpoint_id, 0),
            )
            self._max_dist = max(self._max_dist, depth)
            packed_key = (
                len(self.interner.endpoints) <= EDGE_KEY_MAX_EP
                and depth <= EDGE_KEY_MAX_DIST
            )
            mesh = self._deploy_mesh(host_in[0].shape[0])
            if mesh is not None:
                from kmamiz_tpu.parallel.mesh import (
                    sharded_window_edges_compact,
                )

                fills = (-1, 0, False, 0)
                dev_in, transfer_ms = self._to_device_sharded(
                    mesh,
                    *(
                        self._pad_rows_for(mesh, a, f)
                        for a, f in zip(host_in, fills)
                    ),
                )
                s, d, ds, count = sharded_window_edges_compact(
                    mesh,
                    *dev_in,
                    max_depth=depth,
                    stage_cap=self._stage_cap(),
                    packed_key=packed_key,
                )
            else:
                dev_in, transfer_ms = self._to_device(*host_in)
                s, d, ds, count = _window_edges_compact(
                    *dev_in,
                    max_depth=depth,
                    stage_cap=self._stage_cap(),
                    packed_key=packed_key,
                    sparse_walk=_sparse_walk_default(),
                )
            if hasattr(count, "copy_to_host_async"):
                count.copy_to_host_async()
            self._staged.append((s, d, ds, count, dev_in, depth, mesh))
            # the pinned walk inputs (kept for the truncated-prefix
            # re-walk fallback) dominate a large window's staged HBM, so
            # they count toward the drain backstop too: one packed slot
            # (~10 B across the four arrays) ≈ one compacted edge row
            # (3 int32). Counting only the stage_cap prefix would let a
            # long stream of big windows pin windows x padded-input
            # bytes before tripping (ADVICE r4).
            self._staged_rows += int(s.shape[0]) + int(dev_in[0].size)
            self._update_ep_metadata(batch)
            # backstop: an unread stream must not grow HBM unboundedly
            # (pre-union-deferred checks pin their walk inputs too)
            if self._staged_rows + self._preunion_rows > self._stage_max_rows():
                self._finalize_pending_locked()
            elif self._preunion is not None or len(self._staged) >= 2:
                # drain overlap: collapse what's staged into one async
                # union now, while the stream's next chunk parses on the
                # host — the final drain then adopts the last pre-union
                # instead of sorting every window at once
                self._preunion_staged_locked()
            return transfer_ms
        self._finalize_pending_locked()
        if packed is not None:
            # ancestor chains cannot outrun the longest trace; cap the walk
            # depth (pow2 buckets keep recompilation bounded)
            depth = min(
                window_ops.MAX_DEPTH,
                _pow2(max(1, packed.max_trace_len - 1), minimum=4),
            )
            dev_in, transfer_ms = self._to_device(
                packed.pack(packed.parent_slots(batch.parent_idx), -1),
                packed.pack(batch.kind, 0),
                packed.pack(batch.valid, False),
                packed.pack(batch.endpoint_id, 0),
            )
            self._max_dist = max(self._max_dist, depth)
            src, dst, dist, _valid, valid_count = _window_merge_packed(
                *dev_in,
                *self._store_cols_locked(),
                max_depth=depth,
                sparse_walk=_sparse_walk_default(),
            )
        else:  # overlong trace / cross-trace parent: flat gather fallback
            # size the walk to the window's TRUE longest parent chain
            # (pow2-bucketed, floored at the packed path's default): the
            # deep-trace case is exactly what routes here, and a fixed
            # cap silently dropped ancestors past it while the reference
            # walk is unbounded (review r5). The O(n) host chain scan is
            # fine on this rare path.
            from kmamiz_tpu.core.spans import max_ancestor_chain

            depth = _pow2(
                max(max_ancestor_chain(batch.parent_idx, batch.n_spans), 1),
                minimum=window_ops.MAX_DEPTH,
            )
            self._max_dist = max(self._max_dist, depth)
            dev_in, transfer_ms = self._to_device(
                batch.parent_idx, batch.kind, batch.valid, batch.endpoint_id
            )
            src, dst, dist, _valid, valid_count = _window_merge(
                *dev_in,
                *self._store_cols_locked(),
                max_depth=depth,
            )
        # Defer the count sync: dispatch is async, so the tick returns without
        # blocking on the device round trip; the copy streams back in the
        # background and _finalize_pending() resolves it on next access.
        if hasattr(valid_count, "copy_to_host_async"):
            valid_count.copy_to_host_async()
        self._pending = (src, dst, dist, valid_count)
        self._update_ep_metadata(batch)
        return transfer_ms

    def merge_window_edges(self, edges, batch: SpanBatch):
        """Host-edge fast path for tick merges: union a window's
        already-computed (caller_uen, callee_uen, distance) triples — the
        edge set the host dependency walk just produced for this same
        window — instead of re-deriving it with the packed walk kernel.
        Every walked (ancestor, server, distance) pair appears in some
        SERVER record's dependingBy list, so the triples cover exactly
        the rows the kernel would emit; the device union kernel is shared
        with load_dependencies, keeping the merged arrays bit-exact.

        Returns this call's host->device copy ms, or None when an
        endpoint name is missing from the interner — resolved BEFORE any
        state change, so the caller can fall back to merge_window with
        the store untouched."""
        with self._lock:
            eps = self.interner.endpoints
            src_l, dst_l, dist_l = [], [], []
            for caller, callee, dist in edges:
                s_id = eps.get(caller)
                d_id = eps.get(callee)
                if s_id is None or d_id is None:
                    return None
                src_l.append(s_id)
                dst_l.append(d_id)
                dist_l.append(dist)
            self._version += 1
            self._note_dirty_locked(batch)
            self._update_ep_metadata(batch)
            if not src_l:
                return 0.0
            self._finalize_pending_locked()
            self._max_dist = max(self._max_dist, max(dist_l))
            self._min_dist = min(self._min_dist, min(dist_l))
            cap = _pow2(len(src_l))
            src = np.full(cap, SENTINEL, dtype=np.int32)
            dst = np.full(cap, SENTINEL, dtype=np.int32)
            dist = np.full(cap, SENTINEL, dtype=np.int32)
            src[: len(src_l)] = src_l
            dst[: len(dst_l)] = dst_l
            dist[: len(dist_l)] = dist_l
            (d_src, d_dst, d_dist), transfer_ms = self._to_device(
                src, dst, dist
            )
            s, d, ds, v = _merge_edges(
                *self._store_cols_locked(),
                d_src,
                d_dst,
                d_dist,
                _edge_mask(d_src),
            )
            valid_count = v.sum()
            if hasattr(valid_count, "copy_to_host_async"):
                valid_count.copy_to_host_async()
            self._pending = (s, d, ds, valid_count)
            return transfer_ms

    def capacity_bucket(self) -> int:
        """The pow2 main-segment capacity this graph's padded arrays
        occupy — the tenant arena's bucketing key
        (kmamiz_tpu/tenancy/arena.py): same-bucket graphs dispatch
        identical compiled program shapes. In segment growth mode the
        tail capacity is a pure function of the main capacity (and the
        process-wide KMAMIZ_STORE_TAIL_SHIFT), so the main capacity
        alone still keys the shape set; mixing grow modes across
        same-bucket tenants of one arena is unsupported."""
        return self.capacity

    def intern_window_edges(self, edges):
        """Read-only intern of a window's (caller_uen, callee_uen,
        distance) triples into id columns — the host half of
        merge_window_edges, WITHOUT any state change. Returns
        (src_ids, dst_ids, dist) int lists, or None when the window is
        empty or an endpoint is missing from the interner (the caller
        falls back to the walk-kernel merge path). Used by the tenancy
        router to build stacked same-bucket windows before committing
        any per-tenant merge."""
        with self._lock:
            eps = self.interner.endpoints
            src_l, dst_l, dist_l = [], [], []
            for caller, callee, dist in edges:
                s_id = eps.get(caller)
                d_id = eps.get(callee)
                if s_id is None or d_id is None:
                    return None
                src_l.append(s_id)
                dst_l.append(d_id)
                dist_l.append(dist)
        if not src_l:
            return None
        return src_l, dst_l, dist_l

    def adopt_batched_merged(
        self,
        src,
        dst,
        dist,
        valid_count,
        batch: SpanBatch,
        max_dist: int,
        min_dist: int,
        expected_version=None,
    ):
        """Adopt one lane of a stacked same-bucket union
        (tenancy.batch.batched_merge_edges) as this tick's merge,
        mirroring merge_window_edges' bookkeeping exactly: version bump,
        dirty-journal note, endpoint metadata, distance bounds, deferred
        count resolution. The lane was computed OUTSIDE the lock from an
        arena snapshot, so adoption is valid only if the store still sits
        at the snapshot's version with nothing staged or pending —
        anything else raises StoreVersionDrift and the caller re-merges
        serially (set union: idempotent, so the fallback is bit-exact)."""
        with self._lock:
            drifted = (
                expected_version is not None
                and self._version != expected_version
            )
            if drifted or self._pending is not None or self._staged or (
                self._preunion is not None
            ):
                raise StoreVersionDrift(
                    f"store v{self._version} (expected v{expected_version}); "
                    "stacked lane is stale"
                )
            self._version += 1
            self._note_dirty_locked(batch)
            self._update_ep_metadata(batch)
            self._max_dist = max(self._max_dist, max_dist)
            self._min_dist = min(self._min_dist, min_dist)
            if hasattr(valid_count, "copy_to_host_async"):
                valid_count.copy_to_host_async()
            self._pending = (src, dst, dist, valid_count)

    def _update_ep_metadata(self, batch: SpanBatch) -> None:
        """Per-endpoint record/last-usage metadata (host-side, no device
        sync); shared by the fused and staged merge paths."""
        n_ep = len(self.interner.endpoints)
        self._ensure_ep_arrays(n_ep)
        server_eps = batch.endpoint_id[batch.valid & (batch.kind == KIND_SERVER)]
        self._ep_record[server_eps] = True
        if batch.interner is self.interner:
            # same interner: endpoint ids line up, so the recency update
            # is one vectorized max over the interner's timestamp mirror
            # (monotone — reading a few concurrent refreshes early is
            # harmless) instead of a 10k+ info-dict walk per window
            ts = batch.interner.info_timestamps()
            k = min(ts.size, n_ep)
            if k:
                np.maximum(
                    self._ep_last_ts[:k], ts[:k], out=self._ep_last_ts[:k]
                )
            return
        for info in batch.endpoint_infos:
            eid = self.interner.endpoints.get(info["uniqueEndpointName"])
            if eid is not None and eid < n_ep:
                self._ep_last_ts[eid] = max(
                    self._ep_last_ts[eid], info["timestamp"]
                )

    @staticmethod
    def _stage_max_rows() -> int:
        """Staged-prefix row cap before an inline drain (bounds HBM for
        an unread stream; each staged window also pins its walk inputs
        for the overflow fallback)."""
        try:
            return int(os.environ.get("KMAMIZ_STAGE_MAX_ROWS", 1 << 24))
        except ValueError:
            return 1 << 24

    @staticmethod
    def _stage_cap() -> int:
        """Per-window compacted-prefix width (static kernel shape). A
        window carrying more distinct edges than this still merges
        correctly via the drain's re-walk fallback — this cap only sets
        the fast path's width. Default 2^18: a production-diversity
        window (10k endpoints, >100k distinct edges per page) fits the
        fast path with room; the HBM cost is 3 int32 columns per staged
        window (~3 MB)."""
        try:
            return int(os.environ.get("KMAMIZ_STAGE_CAP", 1 << 18))
        except ValueError:
            return 1 << 18

    def _finalize_pending(self) -> None:
        """Resolve the deferred merge: fetch the edge count and re-pad the
        merged arrays to the next power-of-2 capacity."""
        with self._lock:
            self._finalize_pending_locked()

    def stage_fence(self) -> dict:
        """Explicit stage hand-off fence for the micro-tick stream engine
        (server/stream.py): retire every in-flight upload and resolve any
        deferred merge BEFORE the score/serve stage reads the graph,
        while the next window's prepare stage is already parsing on the
        native shards. This is the same fence `_finalize_pending` applies
        lazily at read time — naming it keeps the merge->score hand-off
        auditable (and counted in upload stats) instead of implicit.
        Returns a small snapshot for the engine's stage accounting."""
        with self._lock:
            self._uploads.note_fence()
            self._finalize_pending_locked()
            return {
                "version": self._version,
                "in_flight": self._uploads.stats()["in_flight"],
            }

    def _finalize_pending_locked(self) -> None:
        # retire any still-streaming uploads first: this IS the read
        # fence the pipeline defers its waits to (in steady state the
        # copies landed chunks ago and this returns immediately)
        self._uploads.drain()
        if self._staged or self._preunion is not None:
            self._drain_staged_locked()  # resolves _pending too
            return
        if self._pending is None:
            return
        pending, self._pending = self._pending, None
        self._apply_merged(*pending)

    def _store_cols_locked(self):
        """The store's flat (src, dst, dist, mask) column view — what
        union kernels and consumer snapshots read. The main arrays in
        repack mode; the warm graph.cat_segments concat of main + tail
        in segment mode, so tail-resident edges are visible everywhere
        the main ones are."""
        if self._tail is None:
            return self._src, self._dst, self._dist, _edge_mask(self._src)
        return _cat_segments(self._src, self._dst, self._dist, *self._tail)

    def _apply_merged(self, src, dst, dist, valid_count) -> None:
        """Adopt a merged edge set: fetch the count, then re-split into
        the fixed (main, tail) segments (segment mode — every array
        shape stays constant across a capacity crossing, so the
        crossing compiles nothing new; consolidation to a larger main
        happens only when the tail would overflow) or re-pad to the
        next power-of-2 capacity (repack mode)."""
        # graftlint: disable=host-sync-in-hot-path -- one async-prefetched scalar per merge drives the capacity policy
        valid_count = int(jax.device_get(valid_count))
        if self._tail is not None:
            # both widths are pow2 by construction (_pow2 main, max(256,
            # main >> shift) tail); the bucketing here is an identity
            # that pins the invariant
            cap = _pow2(int(self._src.shape[0]))
            tail_cap = _pow2(int(self._tail[0].shape[0]))
            old_cap, old_tail = cap, tail_cap
            if valid_count > cap + tail_cap:
                # tail exhausted: consolidate into the next pow2 main —
                # the one recompiling event of segment mode (rare and
                # amortized; valid > cap + tail implies the new cap is
                # at least a doubling, so capacity stays monotone)
                cap = _pow2(valid_count)
                tail_cap = self._tail_cap(cap)
            self._note_growth(valid_count, old_cap, old_tail, cap, tail_cap)
            out = _split_segments(src, dst, dist, cap=cap, tail_cap=tail_cap)
            self._src, self._dst, self._dist = out[:3]
            self._tail = out[3:]
            self._n_edges = valid_count
            return
        new_cap = _pow2(valid_count, minimum=int(self._src.shape[0]))
        merged_len = int(src.shape[0])
        if new_cap == merged_len:
            self._src, self._dst, self._dist = src, dst, dist
        else:
            self._src, self._dst, self._dist = _fit_edges(
                src, dst, dist, cap=new_cap
            )
        self._n_edges = valid_count

    def _note_growth(
        self, valid: int, old_cap: int, old_tail: int, cap: int, tail_cap: int
    ) -> None:
        """graftcost hook (segment mode only): every finalized merge
        feeds the per-tenant growth forecaster with the valid count the
        capacity policy already fetched, and a consolidation reports
        whether predictive prewarm warmed the target bucket first. Env-
        gated lazy import, swallow-all: the cost plane observes the
        store, never steers it — and never holds it up."""
        try:
            from kmamiz_tpu import cost as _cost

            if not _cost.enabled():
                return
            _cost.observe_merge(self.tenant, valid, old_cap, old_tail)
            if cap != old_cap or tail_cap != old_tail:
                _cost.note_capacity_change(self.tenant, old_cap, cap, tail_cap)
        except Exception:  # noqa: BLE001 - observers must not break merges
            logger.exception("growth-note hook failed")

    def _base_edge_cols(self):
        """Starting columns for a union: the pre-union result when one
        exists (it already contains the store's edges; its async count
        slices it to a pow2 bucket once landed), else the store arrays."""
        if self._preunion is not None:
            s0, d0, ds0 = self._preunion
            c = self._preunion_count
            if c is not None:
                # the count copy was dispatched a full chunk ago, so this
                # wait is ~a scalar round trip; slicing UNCONDITIONALLY
                # keeps the chained-union widths deterministic (one small
                # program set, no mid-bench recompiles on count-arrival
                # races)
                k = min(
                    int(s0.shape[0]),
                    # graftlint: disable=host-sync-in-hot-path -- deferred staged count, already landed via copy_to_host_async
                    _pow2(max(int(jax.device_get(c)), 1), minimum=256),
                )
                if k < int(s0.shape[0]):
                    s0, d0, ds0 = s0[:k], d0[:k], ds0[:k]
            return [s0], [d0], [ds0], [_edge_mask(s0)]
        src, dst, dist, mask = self._store_cols_locked()
        return [src], [dst], [dist], [mask]

    def _preunion_staged_locked(self) -> None:
        """Collapse the staged windows so far into one dispatched-but-
        unfetched union (drain overlap): the device sorts while the host
        parses the next chunk, and the stream's final drain unions only
        the tail. No device sync happens here — ready counts slice,
        not-ready ones defer their truncation checks to the drain."""
        if not self._staged or self._pending is not None:
            return
        staged, self._staged = self._staged, []
        self._staged_rows = 0
        srcs, dsts, dists, masks = self._base_edge_cols()
        # resolve carried-over truncation checks whose counts have landed
        # since the last pre-union: non-truncated ones RELEASE their
        # pinned walk inputs now (bounding pinned HBM to the in-flight
        # tail), truncated ones re-walk into this union
        still_deferred = []
        for chk in self._preunion_checks:
            count_c, cap_c, dev_in_c, depth_c, mesh_c = chk
            if hasattr(count_c, "is_ready") and not count_c.is_ready():
                still_deferred.append(chk)
                continue
            self._preunion_rows -= int(dev_in_c[0].size)
            # graftlint: disable=host-sync-in-hot-path -- truncation check on a prefetched per-window count
            if (jax.device_get(count_c) > cap_c).any():
                s_, d_, ds_, m_ = self._rewalk_staged(dev_in_c, depth_c, mesh_c)
                srcs.append(s_)
                dsts.append(d_)
                dists.append(ds_)
                masks.append(m_)
        self._preunion_checks = still_deferred
        deferred = []
        self._collect_staged_cols(staged, srcs, dsts, dists, masks, deferred)
        (s, d, ds), v = self._union_edge_cols(srcs, dsts, dists, masks)
        count = v.sum()
        if hasattr(count, "copy_to_host_async"):
            count.copy_to_host_async()
        self._preunion = (s, d, ds)
        self._preunion_count = count
        self._preunion_checks.extend(deferred)
        self._preunion_rows += sum(int(c[2][0].size) for c in deferred)

    def _drain_staged_locked(self) -> None:
        """ONE set-union over the store + every staged window's compacted
        prefix: the batched equivalent of k fused merges, with the big
        per-window sorts already done asynchronously at stage time. Runs
        whenever staged windows exist and anything reads the store (or
        the staging cap trips). A window whose prefix truncated
        (count > stage_cap) re-walks here from its pinned inputs —
        correctness never depends on the cap."""
        staged, self._staged = self._staged, []
        self._staged_rows = 0
        # resolve any fused-path pending merge FIRST so the union below
        # sees the freshest store arrays
        if self._pending is not None:
            pending, self._pending = self._pending, None
            self._apply_merged(*pending)
        if not staged and self._preunion is not None:
            # nothing new since the last pre-union: ADOPT it as the
            # merged result instead of re-sorting it (the streaming
            # drain's common case — only its count fetch remains)
            s, d, ds = self._preunion
            count = self._preunion_count
            checks = self._preunion_checks
            self._preunion = None
            self._preunion_count = None
            self._preunion_checks = []
            self._preunion_rows = 0
            rewalk = [
                (dev_in, depth, mesh)
                for c, cap, dev_in, depth, mesh in checks
                if (jax.device_get(c) > cap).any()  # graftlint: disable=host-sync-in-hot-path -- prefetched count, truncated-walk gate
            ]
            if rewalk:
                extra = [self._rewalk_staged(*r) for r in rewalk]
                (s, d, ds), v = self._union_edge_cols(
                    [s] + [e[0] for e in extra],
                    [d] + [e[1] for e in extra],
                    [ds] + [e[2] for e in extra],
                    [_edge_mask(s)] + [e[3] for e in extra],
                )
                count = v.sum()
            self._apply_merged(s, d, ds, count)
            return
        srcs, dsts, dists, masks = self._base_edge_cols()
        deferred = list(self._preunion_checks)
        self._preunion = None
        self._preunion_count = None
        self._preunion_checks = []
        self._preunion_rows = 0
        self._collect_staged_cols(staged, srcs, dsts, dists, masks, deferred)
        (s, d, ds), v = self._union_edge_cols(srcs, dsts, dists, masks)
        count_sum = v.sum()
        if hasattr(count_sum, "copy_to_host_async"):
            count_sum.copy_to_host_async()
        # resolve the deferred truncation checks (their copies now
        # overlap the union's execution instead of preceding it)
        rewalk = [
            (dev_in, depth, mesh)
            for count, cap, dev_in, depth, mesh in deferred
            if (jax.device_get(count) > cap).any()  # graftlint: disable=host-sync-in-hot-path -- prefetched count, truncated-walk gate
        ]
        if rewalk:
            extra = [self._rewalk_staged(*r) for r in rewalk]
            (s, d, ds), v = self._union_edge_cols(
                [s] + [e[0] for e in extra],
                [d] + [e[1] for e in extra],
                [ds] + [e[2] for e in extra],
                [v] + [e[3] for e in extra],
            )
            count_sum = v.sum()
        self._apply_merged(s, d, ds, count_sum)

    def _collect_staged_cols(
        self, staged, srcs, dsts, dists, masks, deferred
    ) -> None:
        """Append each staged window's compacted prefix to the union
        columns: landed counts slice the prefix to its true pow2 width
        (or re-walk immediately when truncated); in-flight counts join
        at full width and push their truncation check into `deferred`."""
        for s, d, ds, count, dev_in, depth, mesh in staged:
            # per-shard prefix width: sharded entries carry one stage_cap
            # prefix per device and an [n_dev] count vector
            cap = int(s.shape[0])
            if mesh is not None:
                cap //= mesh.shape["spans"]
            if not (
                hasattr(count, "is_ready") and not count.is_ready()
            ):
                counts = jax.device_get(count)  # graftlint: disable=host-sync-in-hot-path -- is_ready()-gated: only reads counts that already landed
                if (counts > cap).any():  # truncated: re-walk now
                    s, d, ds, m = self._rewalk_staged(dev_in, depth, mesh)
                    srcs.append(s)
                    dsts.append(d)
                    dists.append(ds)
                    masks.append(m)
                    continue
                # slice the prefix down to its TRUE unique count: a
                # window with 1k distinct edges contributes ~1k rows to
                # the union sort instead of stage_cap of SENTINEL
                # padding. Pow2-bucketed widths keep the union program
                # count bounded.
                k = min(cap, _pow2(max(int(counts.max()), 1), minimum=256))
                if k < cap:
                    if mesh is None:
                        s, d, ds = s[:k], d[:k], ds[:k]
                    else:
                        n_dev = mesh.shape["spans"]
                        s, d, ds = (
                            a.reshape(n_dev, -1)[:, :k].reshape(-1)
                            for a in (s, d, ds)
                        )
            else:
                # the count copy has not landed yet (the final chunk of
                # a stream: its walk kernel is still in the device
                # queue). Blocking here would serialize one extra device
                # round trip before the union could even dispatch —
                # instead the FULL prefix joins the union now and the
                # truncation check resolves afterwards, overlapped with
                # the union's own execution; a truncated prefix (rare:
                # >stage_cap distinct edges in one window) re-walks and
                # re-unions below.
                deferred.append((count, cap, dev_in, depth, mesh))
            srcs.append(s)
            dsts.append(d)
            dists.append(ds)
            masks.append(s != SENTINEL)

    def _union_edge_cols(self, cols_src, cols_dst, cols_dist, cols_mask):
        src = jnp.concatenate(cols_src)
        dst = jnp.concatenate(cols_dst)
        dist = jnp.concatenate(cols_dist)
        mask = jnp.concatenate(cols_mask)
        if (
            len(self.interner.endpoints) <= EDGE_KEY_MAX_EP
            and self._min_dist >= 1
            and self._max_dist <= EDGE_KEY_MAX_DIST
        ):
            return compact_unique_edges_packed(src, dst, dist, mask)
        return compact_unique((src, dst, dist), mask)

    @staticmethod
    def _rewalk_staged(dev_in, depth, mesh):
        """Full (uncompacted) candidate walk of a staged window whose
        compacted prefix truncated — correctness never depends on the
        stage cap."""
        if mesh is None:
            return _window_edges_packed(
                *dev_in, max_depth=depth, sparse_walk=_sparse_walk_default()
            )
        from kmamiz_tpu.parallel.mesh import sharded_dependency_edges_packed

        a_, d_, ds_, m_ = sharded_dependency_edges_packed(
            mesh, *dev_in, max_depth=depth
        )
        return (
            a_.reshape(-1),
            d_.reshape(-1),
            ds_.reshape(-1),
            m_.reshape(-1),
        )

    #: default pre-warm program hints: (packed_rows, walk_depth) buckets.
    #: 512 rows covers the reference-cadence 2,500-trace tick (17.5k
    #: spans at ~8 traces per 64-slot row); 8192 rows covers a 262k-span
    #: streaming chunk at the deployed 4-chunk default. Depth 8 is the
    #: pow2 bucket of typical trace depth.
    PREWARM_HINTS = ((512, 8), (8192, 8))

    def prewarm_compile(self, hints=None) -> int:
        """AOT-compile the merge programs for the CURRENT store capacity
        and the given (rows, depth) buckets, so a production boot pays
        its compile walls BEFORE the first tick instead of mid-request
        (VERDICT r4 #5b; the union programs compile in tens of seconds
        on a v5e — PERF.md).
        Combined with the persistent compilation cache
        (core.compile_cache), a restart reloads these from disk in
        seconds. Uses jit lowering only — nothing executes, the store
        never mutates. Returns the number of programs compiled."""
        import jax

        with self._lock:
            self._finalize_pending_locked()
            # segment mode: unions read the flat main+tail view, so the
            # lowered store-column width includes the tail
            cap = int(self._src.shape[0])
            if self._tail is not None:
                cap += int(self._tail[0].shape[0])
            packed_key = (
                len(self.interner.endpoints) <= EDGE_KEY_MAX_EP
                and self._min_dist >= 1
                and self._max_dist <= EDGE_KEY_MAX_DIST
            )
        mesh = None
        count = 0
        for rows, depth in hints or self.PREWARM_HINTS:
            mesh = self._deploy_mesh(rows)
            win = [
                jax.ShapeDtypeStruct((rows, ROW_SLOTS), dt)
                for dt in (jnp.int32, jnp.int8, jnp.bool_, jnp.int32)
            ]
            store_cols = [
                jax.ShapeDtypeStruct((cap,), jnp.int32) for _ in range(3)
            ] + [jax.ShapeDtypeStruct((cap,), jnp.bool_)]
            # the same walk variant the live merges dispatch, or the
            # warm-up compiles programs this platform never runs
            sparse_walk = _sparse_walk_default()
            _window_merge_packed.lower(
                *win, *store_cols, max_depth=depth, sparse_walk=sparse_walk
            ).compile()
            count += 1
            if mesh is None:
                _window_edges_compact.lower(
                    *win,
                    max_depth=depth,
                    stage_cap=self._stage_cap(),
                    packed_key=packed_key,
                    sparse_walk=sparse_walk,
                ).compile()
            else:
                from kmamiz_tpu.parallel.mesh import (
                    sharded_window_edges_compact,
                )

                n_dev = mesh.shape["spans"]
                srows = -(-rows // n_dev) * n_dev
                swin = [
                    jax.ShapeDtypeStruct((srows, ROW_SLOTS), dt)
                    for dt in (jnp.int32, jnp.int8, jnp.bool_, jnp.int32)
                ]
                sharded_window_edges_compact.lower(
                    mesh,
                    *swin,
                    max_depth=depth,
                    stage_cap=self._stage_cap(),
                    packed_key=packed_key,
                ).compile()
            count += 1
        return count

    def edge_arrays(self):
        """(src_ep, dst_ep, dist, mask) snapshot of the stored edges
        (immutable jnp arrays: safe to use after the lock releases)."""
        with self._lock:
            self._finalize_pending_locked()
            # _store_cols_locked, not eager ops: the fold path runs
            # under jax.transfer_guard("disallow") and an eager compare
            # or concat uploads baked host constants
            return self._store_cols_locked()

    def invalidate_labels(self) -> None:
        """Call when the label mapping changes; per-endpoint tables rebuild
        on the next scorer call. Bumps the label epoch so every cached
        scorer output and device-resident input table keyed on the old
        mapping is unreachable from here on."""
        with self._lock:
            self._ep_tables_cache = None
            self._label_epoch += 1
            self._mark_dirty_full_locked()

    # -- dirty-service journal (incremental recompute bookkeeping) -----------

    def _mark_dirty_full_locked(self) -> None:
        """Forget incremental bases: the next scorer call takes the full
        recompute. Used by every mutation the journal cannot attribute to
        a concrete service set (bulk edge unions, warm-start loads, label
        remaps)."""
        self._dirty_journal.clear()
        self._dirty_floor = self._version
        self._scorer_memo.clear()
        self._scorer_prev.clear()
        self._ep_tables_dev = None
        self._fresh_dev = None

    def _note_dirty_locked(self, batch: SpanBatch) -> None:
        """Journal the services touched by a window merge under the
        version the merge produced. A bounded journal: overflow raises
        the floor, so very old incremental bases degrade to the full
        recompute instead of growing host memory."""
        ep_svc = np.asarray(self.interner.endpoint_service_ids, dtype=np.int32)
        ids = batch.endpoint_id[batch.valid]
        ids = ids[(ids >= 0) & (ids < ep_svc.shape[0])]
        touched = frozenset(int(s) for s in np.unique(ep_svc[ids]))
        self._dirty_journal.append((self._version, touched))
        cap = self._dirty_journal_cap()
        while len(self._dirty_journal) > cap:
            self._dirty_floor = self._dirty_journal.pop(0)[0]

    @staticmethod
    def _dirty_journal_cap() -> int:
        try:
            return max(1, int(os.environ.get("KMAMIZ_DIRTY_JOURNAL_MAX", "256")))
        except ValueError:
            return 256

    @staticmethod
    def _dirty_fraction_threshold() -> float:
        """Dirty-service fraction above which incremental recompute stops
        paying for itself (subset compaction + lane merge approach the
        full kernel's cost). Env-tunable; 0 disables the incremental
        path, 1 always allows it."""
        try:
            return float(os.environ.get("KMAMIZ_DIRTY_FRACTION", "0.25"))
        except ValueError:
            return 0.25

    def _ep_tables(self, label_of=None):
        """Padded per-endpoint service/ml/record arrays (+ padded size).

        Cached between scorer calls — rebuilt only when the intern table or
        record set grows (or after invalidate_labels)."""
        with self._lock:
            return self._ep_tables_locked(label_of)

    def _ep_tables_locked(self, label_of=None):
        n_ep = len(self.interner.endpoints)
        self._ensure_ep_arrays(n_ep)
        cache_key = (n_ep, int(self._ep_record[:n_ep].sum()), label_of is not None)
        cached = getattr(self, "_ep_tables_cache", None)
        if cached is not None and cached[0] == cache_key:
            return cached[1]
        ep_cap = _pow2(max(n_ep, 1))
        ep_service = np.zeros(ep_cap, dtype=np.int32)
        ep_ml = np.zeros(ep_cap, dtype=np.int32)
        ep_record = np.zeros(ep_cap, dtype=bool)
        ep_service[:n_ep] = self.interner.endpoint_service_ids
        ep_record[:n_ep] = self._ep_record[:n_ep]
        for eid in range(n_ep):
            name = self.interner.endpoints.lookup(eid)
            parts = name.split("\t")
            method = parts[3] if len(parts) > 3 else ""
            # without a label the endpoint is its own granularity (the
            # reference's unlabeled view keys by the endpoint name); a
            # label collapses same-(method, label) endpoints
            label = (label_of(name) if label_of else None) or name
            ep_ml[eid] = self.ml_interner.intern(f"{method}\t{label}")
        result = (ep_service, ep_ml, ep_record, ep_cap)
        self._ep_tables_cache = (cache_key, result)
        return result

    # -- scorers -------------------------------------------------------------

    def _fresh_mask(self, ep_cap: int, now_ms=None) -> np.ndarray:
        with self._lock:
            return self._fresh_mask_locked(ep_cap, now_ms)

    def _fresh_mask_locked(self, ep_cap: int, now_ms=None) -> np.ndarray:
        """bool[ep_cap]: endpoints whose last usage is within the
        deprecated-endpoint threshold (EndpointDependencies.ts:44-74; the
        host path prunes stale records AND links to them — the device twin
        masks the same endpoints out of records and edges). All-True when
        the threshold is unset."""
        from kmamiz_tpu.config import parse_threshold_ms, settings

        fresh = np.ones(ep_cap, dtype=bool)
        deprecated_ms = parse_threshold_ms(settings.deprecated_endpoint_threshold)
        if deprecated_ms:
            cutoff = (now_ms if now_ms is not None else prof_events.wall_ms()) - deprecated_ms
            # under the caller's lock: n_ep cannot outgrow ep_cap here
            n_ep = min(len(self.interner.endpoints), ep_cap)
            self._ensure_ep_arrays(n_ep)
            fresh[:n_ep] = self._ep_last_ts[:n_ep] >= cutoff
        return fresh

    def _scorer_inputs(self, label_of=None, now_ms=None):
        # ONE lock hold across the whole snapshot: a concurrent ingest can
        # intern endpoints between piecewise acquisitions, leaving n_ep >
        # ep_cap when the fresh mask sizes from a stale table (ADVICE r2)
        with self._lock:
            self._finalize_pending_locked()
            src, dst, dist, mask = self._store_cols_locked()
            ep_service, ep_ml, ep_record, ep_cap = self._ep_tables_locked(
                label_of
            )
            fresh = self._fresh_mask_locked(ep_cap, now_ms)
        if not fresh.all():
            fresh_j = jax.device_put(fresh)
            mask = (
                mask
                & fresh_j[jnp.clip(src, 0, ep_cap - 1)]
                & fresh_j[jnp.clip(dst, 0, ep_cap - 1)]
            )
            ep_record = ep_record & fresh
        svc_cap = _pow2(max(len(self.interner.services), 1))
        return src, dst, dist, mask, ep_service, ep_ml, ep_record, svc_cap

    def _scorer_dist_bits(self) -> "int | None":
        """STATIC dist-bound promise for the sparse scorer dispatch,
        derived from the tracked _min_dist/_max_dist bounds: 3 when every
        distance this store has ever merged fits 0 <= d < 8 (the fast
        single-pass relying-factor form), 4 up to d < 16 (covers the
        depth-8 walk bucket and EDGE_KEY_MAX_DIST; the scorer takes its
        per-distance fallback), else None -> legacy path. _max_dist is a
        conservative UPPER bound (walk depths), so widening never lies."""
        if self._min_dist < 0:
            return None
        if self._max_dist < 8:
            return 3
        if self._max_dist < 16:
            return 4
        return None

    def service_scores(self, label_of=None, now_ms=None) -> scorer_ops.ServiceScores:
        """Cached service scorers. Repeated reads between merges are O(1)
        memo hits; small merges take the dirty-service incremental path;
        everything else falls back to the full kernel (bit-exact either
        way — see service_scores_uncached for the reference pipeline).

        Cache-contract note (inherited from _ep_tables_locked): distinct
        label MAPPINGS are distinguished only via the label epoch —
        swapping the mapping requires invalidate_labels(), which bumps it.
        """
        return self._scored("svc", label_of, now_ms)

    def service_scores_uncached(
        self, label_of=None, now_ms=None
    ) -> scorer_ops.ServiceScores:
        """The seed's per-call pipeline (host-table snapshot + fresh
        upload + full kernel), bypassing every cache layer. Kept as the
        parity oracle for the cached path."""
        src, dst, dist, mask, ep_service, ep_ml, ep_record, svc_cap = (
            self._scorer_inputs(label_of, now_ms)
        )
        # deployed multi-device path (VERDICT r4 #5a): the edge->tuple
        # expansion and local dedup sort shard across the mesh, degree
        # partials psum over ICI; exact parity with the single-device
        # scorer (parallel.mesh.sharded_service_scores)
        mesh = self._deploy_mesh(int(src.shape[0]))
        if mesh is not None and int(src.shape[0]) % mesh.shape["spans"] == 0:
            from kmamiz_tpu.parallel.mesh import sharded_service_scores

            return sharded_service_scores(
                mesh,
                src,
                dst,
                dist,
                mask,
                jax.device_put(ep_service),
                jax.device_put(ep_ml),
                jax.device_put(ep_record),
                num_services=svc_cap,
            )
        return scorer_ops.service_scores(
            src,
            dst,
            dist,
            mask,
            jax.device_put(ep_service),
            jax.device_put(ep_ml),
            jax.device_put(ep_record),
            num_services=svc_cap,
            dist_bits=self._scorer_dist_bits(),
        )

    def usage_cohesion(self, now_ms=None) -> scorer_ops.CohesionScores:
        """Cached cohesion scorers: output memo + device-resident input
        tables. No incremental path — the cohesion outputs carry
        capacity-length pair ROW TABLES (lexsorted over the whole edge
        set), which a per-service lane splice cannot patch — so a version
        change takes the full kernel over cached device inputs."""
        return self._scored("coh", None, now_ms)

    def usage_cohesion_uncached(self, now_ms=None) -> scorer_ops.CohesionScores:
        """Cache-bypassing parity oracle (see service_scores_uncached)."""
        src, dst, dist, mask, ep_service, _ep_ml, ep_record, svc_cap = (
            self._scorer_inputs(None, now_ms)
        )
        return scorer_ops.usage_cohesion(
            src,
            dst,
            dist,
            mask,
            jax.device_put(ep_service),
            jax.device_put(ep_record),
            num_services=svc_cap,
        )

    # -- cached scorer pipeline (ISSUE 1 tentpole) ---------------------------

    def scorer_cache_stats(self) -> dict:
        """Counters for the scorer cache layers: memo hits/misses, host->
        device uploads on the scorer path, incremental vs full
        recomputes. Read by the health handler and bench."""
        with self._lock:
            stats = dict(self.scorer_stats)
            stats["memo_entries"] = len(self._scorer_memo)
            stats["journal_len"] = len(self._dirty_journal)
        total = stats["hits"] + stats["misses"]
        stats["hit_rate"] = (stats["hits"] / total) if total else 0.0
        return stats

    def _count_uploads(self, arrays):
        """Explicit device_put with upload accounting: every host->device
        copy on the scorer path routes through here so the cache counters
        (and the tier-1 zero-upload smoke test) see them all."""
        out = [jax.device_put(a) for a in arrays]
        with self._lock:
            self.scorer_stats["uploads"] += len(out)
        return out

    def _scorer_snapshot(self, label_of, now_ms):
        """ONE lock hold across the whole snapshot (same rationale as
        _scorer_inputs) returning immutable edge arrays, host tables, and
        every cache-key ingredient: graph version, label epoch, fresh-
        mask fingerprint, dirty journal + floor."""
        with self._lock:
            self._finalize_pending_locked()
            src, dst, dist, mask = self._store_cols_locked()
            ep_service, ep_ml, ep_record, ep_cap = self._ep_tables_locked(
                label_of
            )
            tab_key = self._ep_tables_cache[0] + (self._label_epoch,)
            fresh = self._fresh_mask_locked(ep_cap, now_ms)
            svc_cap = _pow2(max(len(self.interner.services), 1))
            return dict(
                src=src,
                dst=dst,
                dist=dist,
                mask=mask,
                ep_service=ep_service,
                ep_ml=ep_ml,
                ep_record=ep_record,
                ep_cap=ep_cap,
                tab_key=tab_key,
                fresh=fresh,
                # a no-op horizon hashes to None so the common case adds
                # nothing to the key; an active horizon fingerprints the
                # mask bytes, so endpoints aging past the cutoff change
                # the key and naturally expire stale cached outputs
                fresh_fp=None if fresh.all() else hash(fresh.tobytes()),
                svc_cap=svc_cap,
                n_services=len(self.interner.services),
                version=self._version,
                label_epoch=self._label_epoch,
                journal=list(self._dirty_journal),
                floor=self._dirty_floor,
            )

    def _device_tables(self, snap):
        """Device-resident mirrors of the per-endpoint tables, uploaded
        once per table change instead of once per scorer call; the
        fresh-horizon gate (edge mask and record bits) applies on device
        so it costs no extra upload."""
        cached = self._ep_tables_dev
        if cached is not None and cached[0] == snap["tab_key"]:
            ep_service_d, ep_ml_d, ep_record_d = cached[1]
        else:
            ep_service_d, ep_ml_d, ep_record_d = self._count_uploads(
                (snap["ep_service"], snap["ep_ml"], snap["ep_record"])
            )
            with self._lock:
                self._ep_tables_dev = (
                    snap["tab_key"],
                    (ep_service_d, ep_ml_d, ep_record_d),
                )
        mask = snap["mask"]
        if snap["fresh_fp"] is not None:
            ep_cap = snap["ep_cap"]
            fkey = (ep_cap, snap["fresh_fp"])
            fcached = self._fresh_dev
            if fcached is not None and fcached[0] == fkey:
                fresh_d = fcached[1]
            else:
                (fresh_d,) = self._count_uploads((snap["fresh"],))
                with self._lock:
                    self._fresh_dev = (fkey, fresh_d)
            mask = (
                mask
                & fresh_d[jnp.clip(snap["src"], 0, ep_cap - 1)]
                & fresh_d[jnp.clip(snap["dst"], 0, ep_cap - 1)]
            )
            ep_record_d = ep_record_d & fresh_d
        return ep_service_d, ep_ml_d, ep_record_d, mask

    def _scored(self, kind: str, label_of, now_ms):
        """Memo -> incremental -> full resolution for both scorer kinds.

        Cache key tuple: (kind, label_epoch, labeled?, svc_cap, ep_cap,
        fresh_fp, mesh_fp) + graph version. Every invalidation source is
        a key ingredient: merges bump the version, invalidate_labels
        bumps the epoch, fresh-horizon expiry changes the mask
        fingerprint, capacity growth changes the caps, and a mesh
        deploy/undeploy (or an edge capacity no longer divisible by the
        device count) changes mesh_fp — so the sharded path consults the
        same key and can never serve a single-device entry or vice versa.
        """
        with phase_span("scorers"):
            return self._scored_inner(kind, label_of, now_ms)

    def _scored_inner(self, kind: str, label_of, now_ms):
        snap = self._scorer_snapshot(label_of, now_ms)
        cap = int(snap["src"].shape[0])
        mesh = self._deploy_mesh(cap) if kind == "svc" else None
        use_mesh = mesh is not None and cap % mesh.shape["spans"] == 0
        base_key = (
            kind,
            snap["label_epoch"],
            label_of is not None,
            snap["svc_cap"],
            snap["ep_cap"],
            snap["fresh_fp"],
            int(mesh.shape["spans"]) if use_mesh else None,
        )
        memo_key = base_key + (snap["version"],)
        with self._lock:
            hit = self._scorer_memo.get(memo_key)
            if hit is not None:
                self.scorer_stats["hits"] += 1
        if hit is not None:
            return hit
        with step_timer.phase("scorers"):
            result = self._compute_scores(
                kind, snap, base_key, mesh if use_mesh else None
            )
        with self._lock:
            self.scorer_stats["misses"] += 1
            if len(self._scorer_memo) >= 64:
                self._scorer_memo.clear()
            else:
                # keys embed the version, so entries from older graph
                # states are unreachable — prune them on the way in
                for k in [
                    k
                    for k in self._scorer_memo
                    if k[-1] != snap["version"]
                ]:
                    del self._scorer_memo[k]
            self._scorer_memo[memo_key] = result
            if len(self._scorer_prev) >= 32:
                self._scorer_prev.clear()
            # graftlint: disable=shape-hazard -- key ingredient is the mesh axis size (bounded), not an array shape
            self._scorer_prev[base_key] = (snap["version"], result)
        return result

    def _compute_scores(self, kind, snap, base_key, mesh):
        src, dst, dist = snap["src"], snap["dst"], snap["dist"]
        svc_cap = snap["svc_cap"]
        ep_service_d, ep_ml_d, ep_record_d, mask = self._device_tables(snap)
        if mesh is not None:
            from kmamiz_tpu.parallel.mesh import sharded_service_scores

            with self._lock:
                self.scorer_stats["full"] += 1
            return sharded_service_scores(
                mesh,
                src,
                dst,
                dist,
                mask,
                ep_service_d,
                ep_ml_d,
                ep_record_d,
                num_services=svc_cap,
            )
        with self._lock:
            prev = self._scorer_prev.get(base_key)
        if prev is not None:
            inc = self._incremental_scores(
                kind, snap, prev, mask, ep_service_d, ep_ml_d, ep_record_d
            )
            if inc is not None:
                return inc
        with self._lock:
            self.scorer_stats["full"] += 1
        if kind == "svc":
            return scorer_ops.service_scores(
                src,
                dst,
                dist,
                mask,
                ep_service_d,
                ep_ml_d,
                ep_record_d,
                num_services=svc_cap,
                dist_bits=self._scorer_dist_bits(),
            )
        return scorer_ops.usage_cohesion(
            src,
            dst,
            dist,
            mask,
            ep_service_d,
            ep_record_d,
            num_services=svc_cap,
        )

    def _incremental_scores(
        self, kind, snap, prev, mask, ep_service_d, ep_ml_d, ep_record_d
    ):
        """Dirty-service incremental recompute: score only the edges
        incident to services the journal marks dirty since the cached
        base, then splice their lanes into the base (bit-exact — see the
        module note on ops.scorers.dirty_edge_subset). Returns None when
        ineligible, which sends the caller to the full recompute."""
        prev_version, prev_scores = prev
        if prev_version >= snap["version"] or prev_version < snap["floor"]:
            return None
        dirty = set()
        for v, svcs in snap["journal"]:
            if v > prev_version:
                dirty |= svcs
        if not dirty:
            # merges since the base touched no service (empty windows):
            # the edge VALUES are unchanged, so the base is still exact
            with self._lock:
                self.scorer_stats["incremental"] += 1
            return prev_scores
        if kind != "svc":
            return None
        threshold = self._dirty_fraction_threshold()
        if len(dirty) > threshold * max(snap["n_services"], 1):
            return None
        svc_cap = snap["svc_cap"]
        dirty_host = np.zeros(svc_cap, dtype=bool)
        dirty_host[list(dirty)] = True
        (dirty_d,) = self._count_uploads((dirty_host,))
        sub_s, sub_d, sub_ds, kept = scorer_ops.dirty_edge_subset(
            snap["src"], snap["dst"], snap["dist"], mask, ep_service_d, dirty_d
        )
        k = int(kept)  # the path's ONE host<-device scalar sync
        cap = int(snap["src"].shape[0])
        sub_cap = _pow2(max(k, 1), minimum=min(256, cap))
        if sub_cap >= cap:
            return None  # subset as large as the store: full wins
        sub_s = sub_s[:sub_cap]
        sub_d = sub_d[:sub_cap]
        sub_ds = sub_ds[:sub_cap]
        inc = scorer_ops.service_scores(
            sub_s,
            sub_d,
            sub_ds,
            sub_s != SENTINEL,
            ep_service_d,
            ep_ml_d,
            ep_record_d,
            num_services=svc_cap,
            dist_bits=self._scorer_dist_bits(),
        )
        with self._lock:
            self.scorer_stats["incremental"] += 1
        return scorer_ops.merge_service_lanes(dirty_d, inc, prev_scores)

    def merge_edges(self, src, dst, dist, valid=None) -> None:
        """Bulk set-union of raw (src, dst, dist) edge arrays into the
        store — the import/warm-start/bench path. Device-resident inputs
        are welcome (no host round trip); the same fused union kernel and
        deferred-count capacity policy as window merges apply."""
        with self._lock:
            self._version += 1
            # bulk edges aren't attributable to a service set without a
            # host round trip: degrade incremental bases to full
            self._mark_dirty_full_locked()
            self._finalize_pending_locked()
            src = jnp.asarray(src, dtype=jnp.int32)
            dst = jnp.asarray(dst, dtype=jnp.int32)
            dist = jnp.asarray(dist, dtype=jnp.int32)
            mask = (
                jnp.asarray(valid, dtype=bool)
                if valid is not None
                else _edge_mask(src)
            )
            # pow2-pad the inputs so variable-length batches share union
            # programs (same rationale as load_dependencies: each
            # distinct shape is another union program to compile)
            cap = _pow2(max(int(src.shape[0]), 1))
            if cap != int(src.shape[0]):
                pad = jnp.full(cap - int(src.shape[0]), SENTINEL, jnp.int32)
                src = jnp.concatenate([src, pad])
                dst = jnp.concatenate([dst, pad])
                dist = jnp.concatenate([dist, pad])
                mask = jnp.concatenate(
                    [mask, jnp.zeros(cap - int(mask.shape[0]), bool)]
                )
            # keep the packed-key drain gate honest: bulk edges carry
            # caller-provided distances (ONE explicit device fetch for
            # both bounds; the masked min/max runs jitted so a
            # device-resident batch merges transfer-clean)
            lo, hi = jax.device_get(_bulk_dist_bounds(dist, mask))
            self._max_dist = max(self._max_dist, int(hi))
            self._min_dist = min(self._min_dist, int(lo))
            s, d, ds, v = _merge_edges(
                *self._store_cols_locked(),
                src,
                dst,
                dist,
                mask,
            )
            count = v.sum()
            if hasattr(count, "copy_to_host_async"):
                count.copy_to_host_async()
            self._pending = (s, d, ds, count)

    # -- cross-process fold (graftfleet, docs/FLEET.md) ----------------------

    def export_named_edges(self) -> dict:
        """Name-based edge snapshot for the fleet's hierarchical merge:
        ``{"names", "src", "dst", "dist"}`` where src/dst index into
        ``names`` (uniqueEndpointName strings), NOT into this store's
        interner ids. Interner ids are assignment-order-local to a
        process, so a cross-process fold must ship names and let the
        importing store re-intern under its own order."""
        src, dst, dist, mask = (np.asarray(a) for a in self.edge_arrays())
        live = np.nonzero(mask)[0]
        used = sorted({int(src[i]) for i in live} | {int(dst[i]) for i in live})
        compact = {eid: idx for idx, eid in enumerate(used)}
        return {
            "names": [self.interner.endpoints.lookup(eid) for eid in used],
            "src": [compact[int(src[i])] for i in live],
            "dst": [compact[int(dst[i])] for i in live],
            "dist": [int(dist[i]) for i in live],
        }

    def fold_named_edges(self, export: dict) -> int:
        """Fold a worker's exported edge snapshot into this store: intern
        the shipped endpoint names (id order local to THIS store), then
        bulk set-union through merge_edges — the pow2-padded path, so a
        fold whose padded shape was rehearsed dispatches only warm union
        programs (a worker joining the fleet compiles nothing). Returns
        the number of live edges folded."""
        names = list(export.get("names", ()))
        src_idx = np.asarray(export.get("src", ()), dtype=np.int64)
        dst_idx = np.asarray(export.get("dst", ()), dtype=np.int64)
        dist = np.asarray(export.get("dist", ()), dtype=np.int32)
        if not (src_idx.shape == dst_idx.shape == dist.shape):
            raise ValueError("named-edge export columns disagree on length")
        if src_idx.size:
            if not names:
                raise ValueError(
                    "named-edge export has edges but no name table"
                )
            lo = int(min(src_idx.min(), dst_idx.min()))
            hi = int(max(src_idx.max(), dst_idx.max()))
            if lo < 0 or hi >= len(names):
                raise ValueError(
                    "named-edge export indexes past its name table"
                )
        ids = np.fromiter(
            (self.interner.intern_endpoint(str(n)) for n in names),
            dtype=np.int32,
            count=len(names),
        )
        with self._lock:
            self._ensure_ep_arrays(len(self.interner.endpoints))
        if src_idx.size == 0:
            return 0
        self.merge_edges(ids[src_idx], ids[dst_idx], dist)
        return int(src_idx.size)

    # -- warm start from the persisted dependency cache ----------------------

    def load_dependencies(self, records) -> None:
        """Rebuild the device edge store from cached dependency records
        (the persisted EndpointDependencies JSON): after a restart the
        process-lifetime graph is empty while the cache was restored from
        storage, so the API's device scorer path warm-starts from it.
        Records' dependingOn/dependingBy entries become (src, dst, dist)
        edges; every record endpoint is marked as a record holder."""
        with self._lock:
            self._load_dependencies_locked(records)

    def _load_dependencies_locked(self, records) -> None:
        self._version += 1
        # record bits / recency can change even when no edges load (the
        # early return below), so mark full BEFORE the edge scan — the
        # trailing invalidate_labels only covers the edge-bearing path
        self._mark_dirty_full_locked()
        src_l, dst_l, dist_l = [], [], []
        for r in records:
            info = r.get("endpoint", {})
            uen = info.get("uniqueEndpointName")
            if uen is None:
                continue
            eid = self.interner.intern_endpoint(uen, info)
            for d in r.get("dependingOn", []):
                dep_info = d.get("endpoint", {})
                dep_uen = dep_info.get("uniqueEndpointName")
                if dep_uen is None:
                    continue
                dep_id = self.interner.intern_endpoint(dep_uen, dep_info)
                src_l.append(eid)
                dst_l.append(dep_id)
                dist_l.append(d.get("distance", 1))
            for d in r.get("dependingBy", []):
                dep_info = d.get("endpoint", {})
                dep_uen = dep_info.get("uniqueEndpointName")
                if dep_uen is None:
                    continue
                dep_id = self.interner.intern_endpoint(dep_uen, dep_info)
                src_l.append(dep_id)
                dst_l.append(eid)
                dist_l.append(d.get("distance", 1))
            n_ep = len(self.interner.endpoints)
            self._ensure_ep_arrays(n_ep)
            self._ep_record[eid] = True
            last_used = r.get("lastUsageTimestamp") or info.get("timestamp") or 0
            self._ep_last_ts[eid] = max(self._ep_last_ts[eid], last_used)
        if not src_l:
            return
        self._finalize_pending()
        # loaded records carry arbitrary distances; keep the packed-key
        # gate honest on BOTH bounds (dist < 1 would wrap the key)
        self._max_dist = max(self._max_dist, max(dist_l))
        self._min_dist = min(self._min_dist, min(dist_l))
        cap = _pow2(len(src_l))
        src = np.full(cap, SENTINEL, dtype=np.int32)
        dst = np.full(cap, SENTINEL, dtype=np.int32)
        dist = np.full(cap, SENTINEL, dtype=np.int32)
        src[: len(src_l)] = src_l
        dst[: len(dst_l)] = dst_l
        dist[: len(dist_l)] = dist_l
        s, d, ds, v = _merge_edges(
            *self._store_cols_locked(),
            jnp.asarray(src),
            jnp.asarray(dst),
            jnp.asarray(dist),
            jnp.asarray(src != SENTINEL),
        )
        self._pending = (s, d, ds, v.sum())
        self.invalidate_labels()

    def active_services(self, now_ms=None) -> np.ndarray:
        """bool[num_services]: services owning at least one non-deprecated
        endpoint record. Vectorized over the interner's endpoint->service
        relation — the former per-endpoint Python loop cost tens of ms
        per scorer API call at 100k endpoints, held under the store lock
        (review r5)."""
        with self._lock:
            n_ep = len(self.interner.endpoints)
            self._ensure_ep_arrays(n_ep)
            fresh = self._fresh_mask(_pow2(max(n_ep, 1)), now_ms)
            out = np.zeros(len(self.interner.services), dtype=bool)
            if n_ep:
                ep_svc = np.asarray(
                    self.interner.endpoint_service_ids[:n_ep], dtype=np.int64
                )
                live = np.asarray(self._ep_record[:n_ep]) & np.asarray(
                    fresh[:n_ep]
                )
                out[ep_svc[live]] = True
            return out


# ---------------------------------------------------------------------------
# telemetry: HBM/arena residency gauges
# ---------------------------------------------------------------------------

_ARENA_STORES = []  # weakrefs of live EndpointGraph instances
_ARENA_LOCK = threading.Lock()
_ARENA_REGISTERED = False


def _track_store_arenas(store: "EndpointGraph") -> None:
    """Register `store` with the telemetry arena gauges. All live stores
    sum into one kmamiz_arena_bytes{arena=graph.*} reading at scrape
    time — the hot merge path never reports anything."""
    import weakref

    from kmamiz_tpu.telemetry import device as _tel_device

    global _ARENA_REGISTERED
    with _ARENA_LOCK:
        _ARENA_STORES.append(weakref.ref(store))
        if _ARENA_REGISTERED:
            return
        _ARENA_REGISTERED = True

    def _sum(key: str):
        def read() -> int:
            total = 0
            with _ARENA_LOCK:
                refs = list(_ARENA_STORES)
            live = []
            for r in refs:
                s = r()
                if s is None:
                    continue
                live.append(r)
                total += s.arena_bytes().get(key, 0)
            if len(live) != len(refs):
                with _ARENA_LOCK:
                    _ARENA_STORES[:] = [r for r in _ARENA_STORES if r() is not None]
            return total

        return read

    for key in ("edges", "staged", "scorer_tables"):
        _tel_device.track_arena(f"graph.{key}", _sum(key))
