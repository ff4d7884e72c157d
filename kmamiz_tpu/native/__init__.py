"""ctypes binding for the native C++ data-loader hot path.

Loads (building on first use if needed) `native/kmamiz_native.cpp` — the
C++ twin of the reference's Rust log parser (log_matcher.rs) — and exposes
drop-in equivalents of the Python implementations in
`kmamiz_tpu.core.envoy`. Every entry point degrades to the pure-Python
path when the toolchain or library is unavailable, so the framework never
hard-requires the extension. The library is keyed on a content hash of the
committed sources plus the compiler flags (build_info.json): any
difference rebuilds, and a library whose record does not match is never
loaded — what runs was built here, from the files beside it. Call
`available()` once at startup to keep the one-time compile off the
request path.
"""
from __future__ import annotations

import ctypes
import logging
import os
import struct
import subprocess
import threading
from pathlib import Path
from typing import List, Optional, Sequence, Tuple

logger = logging.getLogger("kmamiz_tpu.native")

_FIELD_SEP = "\x1f"
_RECORD_SEP = "\x1e"

_REPO_ROOT = Path(__file__).resolve().parent.parent.parent
_SOURCES = [
    _REPO_ROOT / "native" / "kmamiz_native.cpp",
    _REPO_ROOT / "native" / "kmamiz_json.cpp",
    _REPO_ROOT / "native" / "kmamiz_spans.cpp",
]
_BUILD_DIR = _REPO_ROOT / "native" / "build"
_LIB_PATH = _BUILD_DIR / "libkmamiz_native.so"
_BUILD_INFO_PATH = _BUILD_DIR / "build_info.json"
_FAIL_INFO_PATH = _BUILD_DIR / "build_failed.json"

_lock = threading.Lock()
_lib: Optional[ctypes.CDLL] = None
_load_failed = False

#: compiler flags common to both arch variants; part of the build key
_BASE_FLAGS = ("-O3", "-shared", "-fPIC", "-pthread", "-std=c++17")
_ARCH_FLAGS = {"native": ("-march=native",), "generic": ()}


def _cpu_signature() -> str:
    """Stable fingerprint of this host's ISA (the cpu flags line): a
    -march=native .so copied onto a smaller-ISA host would SIGILL on
    first call — no content hash can catch that, so the build key of a
    native build carries this signature too."""
    import hashlib

    try:
        with open("/proc/cpuinfo") as f:
            for line in f:
                if line.startswith("flags"):
                    return hashlib.sha256(line.encode()).hexdigest()
    except OSError:
        pass
    import platform

    return platform.machine()


def source_hash() -> str:
    """sha256 over the committed sources (name + bytes, in order)."""
    import hashlib

    digest = hashlib.sha256()
    for src in _SOURCES:
        digest.update(src.name.encode())
        digest.update(b"\0")
        digest.update(src.read_bytes())
        digest.update(b"\0")
    return digest.hexdigest()


def _build_key(march: str) -> dict:
    """What a library must have been built from to be loadable here:
    these sources, these flags, and — for a -march=native build — this
    host's ISA."""
    return {
        "sources": source_hash(),
        "compiler": os.environ.get("CXX", "g++"),
        "flags": list(_BASE_FLAGS + _ARCH_FLAGS[march]),
        "march": march,
        "cpu": _cpu_signature() if march == "native" else None,
    }


def build_info() -> Optional[dict]:
    """The provenance record written next to the library, or None."""
    import json

    try:
        info = json.loads(_BUILD_INFO_PATH.read_text())
    except (OSError, ValueError):
        return None
    return info if isinstance(info, dict) else None


def _build_matches() -> bool:
    """True only when a library exists AND its provenance record equals
    the key of the files on disk. A missing record, an edited source, a
    different flag set or another host's -march=native build all fail —
    such a library is never loaded, it is rebuilt."""
    if not _LIB_PATH.exists():
        return False
    info = build_info()
    if info is None or info.get("march") not in _ARCH_FLAGS:
        return False
    try:
        return info == _build_key(info["march"])
    except OSError:  # sources unreadable: nothing to match against
        return False


def _build_known_failed() -> bool:
    """True when a previous process already paid the compile attempt for
    exactly these sources on exactly this host and it failed: every fresh
    process would otherwise re-run the full g++ wall (~10 s) inside its
    first tick just to rediscover the same failure."""
    import json

    try:
        info = json.loads(_FAIL_INFO_PATH.read_text())
        return (
            info.get("cpu") == _cpu_signature()
            and info.get("sources") == source_hash()
        )
    except (OSError, ValueError):
        return False


def _build() -> bool:
    import json

    if not all(src.exists() for src in _SOURCES):
        return False
    if _build_known_failed():
        return False
    _BUILD_DIR.mkdir(parents=True, exist_ok=True)
    # the old record goes first: a build that dies half-way must not
    # leave a new .so under the previous key
    _BUILD_INFO_PATH.unlink(missing_ok=True)

    # -march=native first: the .so is built on the host that runs it (the
    # DP deployment builds in its own image), and the hash/number/memcpy
    # paths gain a few percent beyond the hand-dispatched AVX2 scans.
    # Portable fallback when the toolchain rejects it.
    # compile beside the target and rename into place: concurrent cold
    # processes (fleet workers, soak pool) may all build at once, and a
    # loader must never map a half-written library
    tmp_lib = _LIB_PATH.with_name(f"{_LIB_PATH.name}.{os.getpid()}.tmp")
    last_err: Optional[BaseException] = None
    for march in ("native", "generic"):
        key = _build_key(march)
        cmd = [
            key["compiler"],
            *key["flags"],
            "-o",
            str(tmp_lib),
            *[str(src) for src in _SOURCES],
        ]
        try:
            subprocess.run(cmd, check=True, capture_output=True, timeout=300)
        except (subprocess.SubprocessError, OSError) as err:
            last_err = err
            tmp_lib.unlink(missing_ok=True)
            continue
        try:
            os.replace(tmp_lib, _LIB_PATH)
            _BUILD_INFO_PATH.write_text(json.dumps(key))
            _FAIL_INFO_PATH.unlink(missing_ok=True)
        except OSError as err:
            last_err = err
            break  # unrecordable build: unloadable by the rule above
        return True
    logger.warning(
        "native build failed, using pure-Python path: %s", last_err
    )
    try:  # negative-cache the failure so the next process skips the wall
        _FAIL_INFO_PATH.write_text(
            json.dumps({"cpu": _cpu_signature(), "sources": source_hash()})
        )
    except OSError:
        pass
    return False


def _load() -> Optional[ctypes.CDLL]:
    global _lib, _load_failed
    if _lib is not None or _load_failed:
        return _lib
    with _lock:
        if _lib is not None or _load_failed:
            return _lib
        # the library on disk is whatever a previous process (or a disk
        # copy from another checkout) left there: load it only when its
        # record matches the sources beside it, else rebuild — and when
        # that is impossible (no toolchain), degrade to the Python path
        # rather than run code of unknown provenance
        if not _build_matches() and not _build():
            _load_failed = True
            return None
        lib = _open_and_bind()
        if lib is None:
            _load_failed = True
            return None
        _lib = lib
        return _lib


def build_report() -> dict:
    """Native-loader state for /timings and the chip smoke: whether the
    library is loaded and the record it was built under."""
    loaded = available()
    return {
        "available": loaded,
        "sourceHash": source_hash(),
        "buildInfo": build_info() if loaded else None,
    }


def _open_and_bind() -> Optional[ctypes.CDLL]:
    try:
        lib = ctypes.CDLL(str(_LIB_PATH))
        for name in (
            "km_parse_envoy_lines",
            "km_strip_istio_prefix",
            "km_process_body_groups",
        ):
            fn = getattr(lib, name)
            fn.argtypes = [
                ctypes.c_char_p,
                ctypes.c_size_t,
                ctypes.POINTER(ctypes.c_size_t),
            ]
            fn.restype = ctypes.c_void_p
        lib.km_parse_spans.argtypes = [
            ctypes.c_char_p,
            ctypes.c_size_t,
            ctypes.c_char_p,
            ctypes.c_size_t,
            ctypes.POINTER(ctypes.c_size_t),
        ]
        lib.km_parse_spans.restype = ctypes.c_void_p
        lib.km_parse_spans_mt.argtypes = [
            ctypes.c_char_p,
            ctypes.c_size_t,
            ctypes.c_char_p,
            ctypes.c_size_t,
            ctypes.c_int,
            ctypes.POINTER(ctypes.c_size_t),
        ]
        lib.km_parse_spans_mt.restype = ctypes.c_void_p
        lib.km_split_groups.argtypes = [
            ctypes.c_char_p,
            ctypes.c_size_t,
            ctypes.c_int,
            ctypes.POINTER(ctypes.c_size_t),
        ]
        lib.km_split_groups.restype = ctypes.c_void_p
        lib.km_skipset_new.argtypes = []
        lib.km_skipset_new.restype = ctypes.c_void_p
        lib.km_skipset_free.argtypes = [ctypes.c_void_p]
        lib.km_skipset_free.restype = None
        lib.km_skipset_extend.argtypes = [
            ctypes.c_void_p,
            ctypes.c_char_p,
            ctypes.c_size_t,
        ]
        lib.km_skipset_extend.restype = ctypes.c_longlong
        lib.km_skipset_clear.argtypes = [ctypes.c_void_p]
        lib.km_skipset_clear.restype = None
        lib.km_skipset_size.argtypes = [ctypes.c_void_p]
        lib.km_skipset_size.restype = ctypes.c_ulonglong
        lib.km_parse_spans_hs.argtypes = [
            ctypes.c_void_p,
            ctypes.c_char_p,
            ctypes.c_size_t,
            ctypes.c_int,
            ctypes.POINTER(ctypes.c_size_t),
        ]
        lib.km_parse_spans_hs.restype = ctypes.c_void_p
        lib.km_session_new.argtypes = []
        lib.km_session_new.restype = ctypes.c_void_p
        lib.km_session_free.argtypes = [ctypes.c_void_p]
        lib.km_session_free.restype = None
        lib.km_session_ack.argtypes = [
            ctypes.c_void_p,
            ctypes.c_uint32,
            ctypes.c_uint32,
        ]
        lib.km_session_ack.restype = None
        lib.km_parse_spans_sess.argtypes = [
            ctypes.c_void_p,
            ctypes.c_void_p,
            ctypes.c_char_p,
            ctypes.c_size_t,
            ctypes.c_int,
            ctypes.POINTER(ctypes.c_size_t),
        ]
        lib.km_parse_spans_sess.restype = ctypes.c_void_p
        lib.km_free.argtypes = [ctypes.c_void_p]
        lib.km_free.restype = None
        # graftprof counter exports: OPTIONAL — a prebuilt .so that
        # predates them must still serve the parse path (prof_counters()
        # then degrades to the zero snapshot)
        try:
            lib.km_prof_snapshot.argtypes = [
                ctypes.POINTER(ctypes.c_size_t)
            ]
            lib.km_prof_snapshot.restype = ctypes.c_void_p
            lib.km_prof_reset.argtypes = []
            lib.km_prof_reset.restype = None
        except AttributeError:
            logger.warning(
                "libkmamiz_native.so predates graftprof counters; "
                "native profiling reports zeros"
            )
        # columnar wire capability + parse-shard knob: OPTIONAL — a .so
        # without km_wire_caps predates the "KMZC" frame format (the
        # binding then transcodes frames to JSON in Python)
        try:
            lib.km_wire_caps.argtypes = []
            lib.km_wire_caps.restype = ctypes.c_uint
            lib.km_set_parse_shards.argtypes = [ctypes.c_int]
            lib.km_set_parse_shards.restype = None
            shards = os.environ.get("KMAMIZ_PARSE_SHARDS")
            if shards:
                lib.km_set_parse_shards(int(shards))
        except (AttributeError, ValueError):
            logger.warning(
                "libkmamiz_native.so predates the columnar wire; "
                "KMZC frames transcode through Python"
            )
        return lib
    except (OSError, AttributeError) as err:
        logger.warning("native load failed: %s", err)
        return None


def available() -> bool:
    return _load() is not None


def supports_columnar() -> bool:
    """True when the loaded .so decodes "KMZC" columnar frames natively
    (km_wire_caps bit 0). False -> parse_spans transcodes frames to
    Zipkin JSON through kmamiz_tpu.core.wire first."""
    lib = _load()
    return lib is not None and hasattr(lib, "km_wire_caps")


# -- graftprof native counters (telemetry/profiling) -------------------------

_PROF_SCALARS_V1 = (
    "parses",
    "spans",
    "merge_ns",
    "merge_lock_wait_ns",
    "merge_queue_depth_peak",
    "claim_contended",
    "intern_probes",
    "intern_hits",
)
# v2 appends the shard-table fold counters (lock-free merge rework);
# graftlint cross-checks these names against the ProfCounters struct in
# native/kmamiz_spans.cpp (prof-counter-wire rule).
_PROF_SCALARS = _PROF_SCALARS_V1 + ("fold_ns", "fold_chunks")
_PROF_HEADER_LEN = 8 + 8 * len(_PROF_SCALARS_V1)


def _prof_zero() -> dict:
    out = {"available": False, "version": 0, "shards_used": 0, "shards": []}
    for key in _PROF_SCALARS:
        out[key] = 0
    return out


def prof_counters() -> dict:
    """Cumulative graftprof counter snapshot from the native parse/merge
    pipeline (see km_prof_snapshot in native/kmamiz_spans.cpp).

    Never raises: without the library — or with a stale prebuilt .so
    missing the symbols — the zero snapshot returns (available=False)."""
    try:
        lib = _load()
        if lib is None or not hasattr(lib, "km_prof_snapshot"):
            return _prof_zero()
        out_len = ctypes.c_size_t(0)
        ptr = lib.km_prof_snapshot(ctypes.byref(out_len))
        if not ptr:
            return _prof_zero()
        try:
            raw = ctypes.string_at(ptr, out_len.value)
        finally:
            lib.km_free(ptr)
        if len(raw) < _PROF_HEADER_LEN:
            return _prof_zero()
        out = _prof_zero()
        out["available"] = True
        out["version"], out["shards_used"] = struct.unpack_from("<II", raw, 0)
        names = _PROF_SCALARS if out["version"] >= 2 else _PROF_SCALARS_V1
        if len(raw) < 8 + 8 * len(names):
            names = _PROF_SCALARS_V1
        scalars = struct.unpack_from(f"<{len(names)}Q", raw, 8)
        for key, val in zip(names, scalars):
            out[key] = val
        off = 8 + 8 * len(names)
        for _ in range(out["shards_used"]):
            if off + 24 > len(raw):
                break
            parse_ns, wait_ns, spans = struct.unpack_from("<3Q", raw, off)
            out["shards"].append(
                {"parse_ns": parse_ns, "wait_ns": wait_ns, "spans": spans}
            )
            off += 24
        return out
    except Exception:  # noqa: BLE001 - profiling must never break ingest
        return _prof_zero()


def prof_reset() -> None:
    """Zero the native graftprof counters (tests, flight-recorder cuts).
    No-op without the library or the symbol."""
    try:
        lib = _load()
        if lib is not None and hasattr(lib, "km_prof_reset"):
            lib.km_prof_reset()
    except Exception:  # noqa: BLE001 - profiling must never break ingest
        pass


def _call_buffer_fn(fn, payload: bytes, *extra) -> Optional[str]:
    lib = _load()
    if lib is None:
        return None
    out_len = ctypes.c_size_t(0)
    ptr = fn(payload, len(payload), *extra, ctypes.byref(out_len))
    if not ptr:
        return None
    try:
        return ctypes.string_at(ptr, out_len.value).decode("utf-8", "replace")
    finally:
        lib.km_free(ptr)


def strip_istio_proxy_prefix(lines: List[str]) -> Optional[List[str]]:
    """Native twin of core.envoy.strip_istio_proxy_prefix; None -> fall back."""
    lib = _load()
    if lib is None:
        return None
    raw = _call_buffer_fn(lib.km_strip_istio_prefix, "\n".join(lines).encode())
    if raw is None:
        return None
    return raw.split("\n")[:-1] if raw else []


def parse_envoy_lines(lines: List[str]) -> Optional[List[dict]]:
    """Native twin of the per-line parse inside core.envoy.parse_envoy_logs:
    returns raw field dicts (no namespace/pod/id-map decoration), or None
    when the extension is unavailable."""
    lib = _load()
    if lib is None:
        return None
    raw = _call_buffer_fn(lib.km_parse_envoy_lines, "\n".join(lines).encode())
    if raw is None:
        return None
    records = []
    for record in raw.split(_RECORD_SEP):
        if not record:
            continue
        fields = record.split(_FIELD_SEP)
        if len(fields) != 12:
            continue
        (
            time_str,
            log_type,
            request_id,
            trace_id,
            span_id,
            parent_span_id,
            method,
            path,
            status,
            content_type,
            body,
            body_present,
        ) = fields
        if not path:  # the method/path regex requires a non-empty path
            method = ""
        records.append(
            {
                "time": time_str,
                "type": log_type,
                "requestId": request_id,
                "traceId": trace_id,
                "spanId": span_id,
                "parentSpanId": parent_span_id,
                "method": method or None,
                "path": path or None,
                "status": status or None,
                "contentType": content_type or None,
                "body": body if body_present == "1" else None,
            }
        )
    return records


# ---------------------------------------------------------------------------
# raw Zipkin JSON -> SoA span arrays (native/kmamiz_spans.cpp)
# ---------------------------------------------------------------------------

# naming-shape presence bits (must match kmamiz_spans.cpp)
SHAPE_HAS_METHOD = 1 << 2
SHAPE_HAS_SVC = 1 << 3
SHAPE_HAS_NS = 1 << 4
SHAPE_HAS_REV = 1 << 5
SHAPE_HAS_MESH = 1 << 6


def parse_threads() -> int:
    """Worker count for the native span scan: KMAMIZ_PARSE_THREADS, else 0
    (auto = hardware concurrency, capped at 16 in the extension)."""
    try:
        return int(os.environ.get("KMAMIZ_PARSE_THREADS", "0"))
    except ValueError:
        return 0


def effective_parse_threads() -> int:
    """The worker count the native scan actually runs with: the raw
    setting when explicit, else the same hardware-concurrency-capped-at-16
    resolution kmamiz_spans.cpp applies to 0/auto. Benchmarks report this
    instead of the raw env so results are comparable across machines."""
    raw = parse_threads()
    if raw > 0:
        return raw
    return max(1, min(os.cpu_count() or 1, 16))


def encode_skip_entry(tid) -> bytes:
    """One skip-set entry in the km_parse_spans_mt blob layout
    (u8 present + u32 len + utf8 bytes; None markers encode as absent).
    Callers that parse repeatedly against a growing processed set cache
    these encodings instead of re-walking the whole set every call
    (DataProcessor keeps an incremental blob)."""
    if tid is None:
        return struct.pack("<BI", 0, 0)
    b = str(tid).encode("utf-8", "surrogatepass")
    return struct.pack("<BI", 1, len(b)) + b


class SkipSet:
    """Persistent native processed-trace set (km_skipset_* C API).

    Replaces the per-parse skip blob on the streaming path: the
    DataProcessor extends it incrementally as traces register
    (`extend` takes the same skip-entry bytes `encode_skip_entry`
    produces, sans count header) and passes the handle to every parse —
    so the parse stops re-encoding and re-hashing the whole processed
    set per chunk. Falls back transparently: when the extension is
    unavailable, `handle` is None and callers use the blob path.
    Thread-safe on the native side (per-probe mutex)."""

    __slots__ = ("_lib", "_handle")

    def __init__(self) -> None:
        self._lib = _load()
        self._handle = self._lib.km_skipset_new() if self._lib else None

    @property
    def handle(self):
        return self._handle

    def extend(self, entries: bytes) -> int:
        """Add skip-entry records; returns records walked (-1 = malformed)."""
        if self._handle is None or not entries:
            return 0
        return int(
            self._lib.km_skipset_extend(
                self._handle, bytes(entries), len(entries)
            )
        )

    def clear(self) -> None:
        if self._handle is not None:
            self._lib.km_skipset_clear(self._handle)

    def __len__(self) -> int:
        if self._handle is None:
            return 0
        return int(self._lib.km_skipset_size(self._handle))

    def __del__(self) -> None:
        handle, self._handle = self._handle, None
        if handle is not None and self._lib is not None:
            try:
                self._lib.km_skipset_free(handle)
            except (OSError, AttributeError):  # interpreter teardown
                pass


def _unpack_timings(prescan_us: int, parse_us: int, merge_packed: int) -> dict:
    # threads<<25 | merge_us (25-bit µs, ~33 s cap) — see kmamiz_spans.cpp
    return {
        "prescan_us": prescan_us,
        "parse_us": parse_us,
        "merge_us": merge_packed & 0x01FFFFFF,
        "threads": merge_packed >> 25,
    }


def _read_shape_records(buf, pos: int, count: int):
    """`count` serialized shape records (u8 url_present + u8 bits + 7x
    length-prefixed field bytes) -> (records, new_pos). Fields stay raw
    BYTES tuples: consumers cache resolutions keyed on them and decode
    only on a cache miss."""
    shapes = []
    for _ in range(count):
        url_present = buf[pos] != 0
        bits = buf[pos + 1]
        pos += 2
        fields = []
        for _f in range(7):
            (flen,) = struct.unpack_from("<I", buf, pos)
            pos += 4
            fields.append(bytes(buf[pos : pos + flen]))
            pos += flen
        shapes.append((tuple(fields), url_present, bits))
    return shapes, pos


def _read_status_records(buf, pos: int, count: int):
    statuses = []
    for _ in range(count):
        (slen,) = struct.unpack_from("<I", buf, pos)
        pos += 4
        statuses.append(buf[pos : pos + slen].decode("utf-8", "surrogatepass"))
        pos += slen
    return statuses, pos


def _decode_session_payload(buf) -> Optional[dict]:
    """Decode the session wire format (header ok=2): span columns carry
    session-GLOBAL shape/status ids; shape/status strings appear only
    for the unacked tail [base..total). Raises like the v1 decode on
    malformed buffers (the caller's except clauses handle both)."""
    import numpy as np

    (
        _fmt,
        n,
        shapes_total,
        statuses_total,
        shape_base,
        status_base,
        n_groups,
        prescan_us,
        parse_us,
        merge_packed,
    ) = struct.unpack_from("<10I", buf, 0)
    timings = _unpack_timings(prescan_us, parse_us, merge_packed)
    pos = 40
    latency_ms = np.frombuffer(buf, np.float64, n, pos)
    pos += 8 * n
    timestamp_raw = np.frombuffer(buf, np.float64, n, pos)
    pos += 8 * n
    shape_max_ts_ms = np.frombuffer(buf, np.float64, shapes_total, pos)
    pos += 8 * shapes_total
    parent_idx = np.frombuffer(buf, np.int32, n, pos)
    pos += 4 * n
    shape_id = np.frombuffer(buf, np.int32, n, pos)
    pos += 4 * n
    status_id = np.frombuffer(buf, np.int32, n, pos)
    pos += 4 * n
    trace_of = np.frombuffer(buf, np.int32, n, pos)
    pos += 4 * n
    kind = np.frombuffer(buf, np.int8, n, pos)
    pos += n

    new_shapes, pos = _read_shape_records(buf, pos, shapes_total - shape_base)
    new_statuses, pos = _read_status_records(
        buf, pos, statuses_total - status_base
    )

    # kept trace ids, vectorized: presence + length arrays give every
    # record's offset in one cumsum; the ASCII fast path decodes the
    # whole interleaved section once and slices strings out of it (tids
    # are hex in real Zipkin data). The interleaved records are
    # byte-identical to encode_skip_entry layout, so the raw slice also
    # serves as the caller's incremental dedup-blob append.
    present = np.frombuffer(buf, np.uint8, n_groups, pos)
    pos += n_groups
    tlens = np.frombuffer(buf, np.uint32, n_groups, pos).astype(np.int64)
    pos += 4 * n_groups
    blob_len = 5 * n_groups + int(tlens.sum())
    kept_blob = buf[pos : pos + blob_len]
    if len(kept_blob) != blob_len:
        raise ValueError("truncated kept-trace-id section")
    pos += blob_len
    starts = 5 * (np.arange(n_groups, dtype=np.int64) + 1)
    starts[1:] += np.cumsum(tlens[:-1])
    ends = starts + tlens
    present_l = (present != 0).tolist()
    if kept_blob.isascii():
        s = kept_blob.decode("ascii")
        trace_ids = [
            s[a:b] if p else None
            for a, b, p in zip(starts.tolist(), ends.tolist(), present_l)
        ]
    else:
        trace_ids = [
            kept_blob[a:b].decode("utf-8", "surrogatepass") if p else None
            for a, b, p in zip(starts.tolist(), ends.tolist(), present_l)
        ]

    return {
        "n_spans": int(n),
        "kind": kind,
        "parent_idx": parent_idx,
        "shape_id": shape_id,
        "status_id": status_id,
        "trace_of": trace_of,
        "latency_ms": latency_ms,
        "timestamp_us": timestamp_raw.astype(np.int64),
        "shape_max_ts_ms": shape_max_ts_ms,
        "trace_ids": trace_ids,
        "trace_ids_blob": kept_blob,
        "timings": timings,
        "session_format": True,
        "shape_base": int(shape_base),
        "shapes_total": int(shapes_total),
        "status_base": int(status_base),
        "statuses_total": int(statuses_total),
        "new_shapes": new_shapes,
        "new_statuses": new_statuses,
    }


class ParseSession:
    """Persistent native parse session (km_session_* C API).

    Keeps the shape/status intern tables alive across parse calls so a
    chunked stream stops re-serializing and re-decoding ~10k identical
    naming shapes per page: spans arrive with session-global ids and
    only NEW (unacknowledged) shapes/statuses carry strings. The caller
    acks after successfully consuming a payload; a rejected payload
    (e.g. invalid UTF-8 in a field) is simply never acked and its
    additions re-emit next call."""

    __slots__ = ("_lib", "_handle")

    def __init__(self) -> None:
        self._lib = _load()
        self._handle = self._lib.km_session_new() if self._lib else None

    @property
    def handle(self):
        return self._handle

    def ack(self, shapes_known: int, statuses_known: int) -> None:
        if self._handle is not None:
            self._lib.km_session_ack(
                self._handle, int(shapes_known), int(statuses_known)
            )

    def __del__(self) -> None:
        handle, self._handle = self._handle, None
        if handle is not None and self._lib is not None:
            try:
                self._lib.km_session_free(handle)
            except (OSError, AttributeError):  # interpreter teardown
                pass


def parse_spans(
    raw: bytes,
    skip_trace_ids: Sequence = (),
    threads: Optional[int] = None,
    skip_blob: Optional[bytes] = None,
    skipset: "Optional[SkipSet]" = None,
    session: "Optional[ParseSession]" = None,
) -> Optional[dict]:
    """Scan a raw Zipkin JSON response ([[span,...],...]) into SoA arrays.

    skip_trace_ids: already-processed trace ids (may contain None, matching
    DataProcessor._filter_traces semantics); groups whose first span carries
    one are dropped whole.

    threads: native worker count (None -> KMAMIZ_PARSE_THREADS env, 0 ->
    auto). The parallel scan preserves exact sequential semantics: group
    dedup runs in document order during the prescan, and duplicate span
    ids resolve first-position/last-wins via a document-order fixup.

    skip_blob: pre-encoded full skip blob (u32 count + encode_skip_entry
    per id) that REPLACES skip_trace_ids when given — callers with a
    large, slowly-growing processed set pass a cached blob so each parse
    doesn't re-encode the whole set.

    Returns None when the extension is unavailable or the input is
    malformed (callers fall back to json.loads + spans_to_batch), else a
    dict with numpy arrays (kind/parent_idx/shape_id/status_id/trace_of/
    latency_ms/timestamp_us), the distinct naming shapes
    [(fields7, url_present, presence_bits)], shape_max_ts_ms, distinct
    status strings, the kept trace ids (None markers preserved), and a
    "timings" dict (native phase wall times, for honest bench accounting).
    """
    import numpy as np

    lib = _load()
    if lib is None:
        return None
    if threads is None:
        threads = parse_threads()
    out_len = ctypes.c_size_t(0)
    # the json buffer crosses ctypes without a copy (c_char_p on bytes)
    raw = bytes(raw) if not isinstance(raw, bytes) else raw
    if raw[:4] == b"KMZC" and not hasattr(lib, "km_wire_caps"):
        # stale prebuilt .so without the columnar decoder: transcode the
        # frame to Zipkin JSON in Python (same rows, host-speed only)
        from kmamiz_tpu.core import wire

        raw = wire.columnar_to_json(raw)
        if raw is None:
            return None
    # explicit blob-style skip args take precedence over the persistent
    # handles: a caller that passes skip_trace_ids/skip_blob means THAT
    # set, and silently consulting a different (handle) set instead
    # would merge traces the caller asked to skip
    if skip_trace_ids or skip_blob is not None:
        session = None
        skipset = None
    if session is not None and session.handle is not None:
        # persistent-session path: global ids + delta shape emission
        ptr = lib.km_parse_spans_sess(
            session.handle,
            skipset.handle if skipset is not None else None,
            raw,
            len(raw),
            int(threads),
            ctypes.byref(out_len),
        )
    elif skipset is not None and skipset.handle is not None:
        # persistent-set path: no per-call blob at all
        ptr = lib.km_parse_spans_hs(
            skipset.handle,
            raw,
            len(raw),
            int(threads),
            ctypes.byref(out_len),
        )
    else:
        if skip_blob is None:
            skip_blob = bytearray(struct.pack("<I", len(skip_trace_ids)))
            for t in skip_trace_ids:
                skip_blob += encode_skip_entry(t)
        ptr = lib.km_parse_spans_mt(
            bytes(skip_blob),
            len(skip_blob),
            raw,
            len(raw),
            int(threads),
            ctypes.byref(out_len),
        )
    if not ptr:
        return None
    try:
        buf = ctypes.string_at(ptr, out_len.value)
    finally:
        lib.km_free(ptr)

    try:
        (fmt,) = struct.unpack_from("<I", buf, 0)
        if fmt == 2:
            return _decode_session_payload(buf)
        (
            ok,
            n,
            n_shapes,
            n_statuses,
            n_groups,
            prescan_us,
            parse_us,
            merge_packed,
        ) = struct.unpack_from("<8I", buf, 0)
        if ok != 1:
            return None
        timings = _unpack_timings(prescan_us, parse_us, merge_packed)
        pos = 32
        # read-only VIEWS over `buf` (which the arrays keep alive via
        # .base): raw_spans_to_batch copies once into its padded arrays,
        # so eager copies here would be a second full pass
        latency_ms = np.frombuffer(buf, np.float64, n, pos)
        pos += 8 * n
        timestamp_raw = np.frombuffer(buf, np.float64, n, pos)
        pos += 8 * n
        shape_max_ts_ms = np.frombuffer(buf, np.float64, n_shapes, pos)
        pos += 8 * n_shapes
        parent_idx = np.frombuffer(buf, np.int32, n, pos)
        pos += 4 * n
        shape_id = np.frombuffer(buf, np.int32, n, pos)
        pos += 4 * n
        status_id = np.frombuffer(buf, np.int32, n, pos)
        pos += 4 * n
        trace_of = np.frombuffer(buf, np.int32, n, pos)
        pos += 4 * n
        kind = np.frombuffer(buf, np.int8, n, pos)
        pos += n

        shapes, pos = _read_shape_records(buf, pos, n_shapes)
        statuses, pos = _read_status_records(buf, pos, n_statuses)

        trace_ids = []
        for _ in range(n_groups):
            present = buf[pos] != 0
            (tlen,) = struct.unpack_from("<I", buf, pos + 1)
            pos += 5
            tid = buf[pos : pos + tlen].decode("utf-8", "surrogatepass")
            pos += tlen
            trace_ids.append(tid if present else None)
    except UnicodeDecodeError:
        # string fields carried invalid UTF-8: JSON must be UTF-8, so the
        # payload is malformed — reject, exactly like the json.loads path
        logger.warning("span payload contains invalid UTF-8; rejected")
        return None
    except (struct.error, IndexError, ValueError):
        # ValueError: np.frombuffer on a truncated buffer (stale .so ABI)
        logger.warning("native span decode failed, using Python path")
        return None

    return {
        "n_spans": int(n),
        "kind": kind,
        "parent_idx": parent_idx,
        "shape_id": shape_id,
        "status_id": status_id,
        "trace_of": trace_of,
        "latency_ms": latency_ms,
        "timestamp_us": timestamp_raw.astype(np.int64),
        "shapes": shapes,
        "shape_max_ts_ms": shape_max_ts_ms,
        "statuses": statuses,
        "trace_ids": trace_ids,
        "timings": timings,
    }


def split_groups(raw: bytes, n_chunks: int) -> Optional[List[bytes]]:
    """Split a raw Zipkin response into <= n_chunks standalone responses,
    each covering whole trace groups (for the streaming ingest pipeline).
    Returns None when the extension is unavailable or the input is
    malformed."""
    lib = _load()
    if lib is None:
        return None
    raw = bytes(raw) if not isinstance(raw, bytes) else raw
    out_len = ctypes.c_size_t(0)
    ptr = lib.km_split_groups(raw, len(raw), int(n_chunks), ctypes.byref(out_len))
    if not ptr:
        return None
    try:
        buf = ctypes.string_at(ptr, out_len.value)
    finally:
        lib.km_free(ptr)
    try:
        (n_ranges,) = struct.unpack_from("<I", buf, 0)
        chunks = []
        pos = 4
        for _ in range(n_ranges):
            begin, end = struct.unpack_from("<2Q", buf, pos)
            pos += 16
            chunks.append(b"[" + raw[begin:end] + b"]")
        return chunks
    except (struct.error, IndexError):
        return None


# ---------------------------------------------------------------------------
# batched JSON body merge + schema inference (native/kmamiz_json.cpp, the
# C++ twin of the reference's Rust json_utils.rs)
# ---------------------------------------------------------------------------

BodyGroup = Tuple[Sequence[Optional[str]], bool]  # (bodies, want_interface)


def process_body_groups(
    groups: Sequence[BodyGroup],
) -> Optional[List[Optional[Tuple[Optional[str], Optional[str], bool]]]]:
    """Fold merge_string_body over each group's bodies and (optionally) infer
    the merged body's interface string, all in one native call.

    Returns one entry per group:
      (merged_body_or_None, interface_or_None, interface_needs_python)
    or None for a group the native side delegates back to pure Python
    (excessive nesting). Returns None overall when the extension is
    unavailable or the call fails.
    """
    lib = _load()
    if lib is None:
        return None
    buf = bytearray()
    buf += struct.pack("<I", len(groups))
    for bodies, want_interface in groups:
        buf.append(1 if want_interface else 0)
        buf += struct.pack("<I", len(bodies))
        for body in bodies:
            if body is None:
                buf.append(0)
            else:
                raw = body.encode("utf-8", "surrogatepass")
                buf.append(1)
                buf += struct.pack("<I", len(raw))
                buf += raw

    out_len = ctypes.c_size_t(0)
    payload = bytes(buf)
    ptr = lib.km_process_body_groups(payload, len(payload), ctypes.byref(out_len))
    if not ptr:
        return None
    try:
        raw_out = ctypes.string_at(ptr, out_len.value)
    finally:
        lib.km_free(ptr)

    try:
        pos = 0
        (n_groups,) = struct.unpack_from("<I", raw_out, pos)
        pos += 4
        results: List[Optional[Tuple[Optional[str], Optional[str], bool]]] = []
        for _ in range(n_groups):
            status = raw_out[pos]
            pos += 1
            if status == 1:  # python-fallback group
                results.append(None)
                continue
            merged: Optional[str] = None
            if raw_out[pos]:
                pos += 1
                (mlen,) = struct.unpack_from("<I", raw_out, pos)
                pos += 4
                merged = raw_out[pos : pos + mlen].decode("utf-8", "surrogatepass")
                pos += mlen
            else:
                pos += 1
            iface_flag = raw_out[pos]
            pos += 1
            interface: Optional[str] = None
            if iface_flag == 1:
                (ilen,) = struct.unpack_from("<I", raw_out, pos)
                pos += 4
                interface = raw_out[pos : pos + ilen].decode(
                    "utf-8", "surrogatepass"
                )
                pos += ilen
            results.append((merged, interface, iface_flag == 2))
        return results
    except (struct.error, IndexError):
        logger.warning("native body-group decode failed, using Python path")
        return None
