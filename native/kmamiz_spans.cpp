// Raw Zipkin JSON -> SoA span arrays: the native ingest hot path.
//
// C++ twin of the per-span work in kmamiz_tpu/core/spans.py::spans_to_batch
// and kmamiz_tpu/server/processor.py::_filter_traces, matching the role of
// the reference's Rust deserialization stack
// (/root/reference/kmamiz_data_processor/src/http_client/zipkin.rs:32-43 +
// src/data/trace.rs:261-299). The Python path walks a dict per span
// (~400k spans/s); this scanner walks the raw response bytes once and emits
// fixed-width arrays plus small dedup tables, leaving only O(#endpoints)
// string work (URL explode, interning) to Python -- which keeps naming
// semantics byte-identical to the host implementation.
//
// Parallel structure (round 3): the single hot loop is split into phases so
// the scan scales across cores the way the reference scaled by rewriting
// its DP in Rust (/root/reference/deploy/README-DP.md):
//   1. prescan (sequential): a string-aware bracket walk finds top-level
//      trace-group boundaries and applies the processed-trace dedup in
//      document order -- exact _filter_traces semantics.
//   2. parse (parallel): kept groups are sliced into contiguous,
//      byte-balanced ranges; each worker parses its range with a private
//      arena + shape/status tables. With n_threads == 1 the prescan and
//      parse fuse back into one pass (no second walk over the bytes).
//   3. span-id table (parallel): span ids are interned AFTER the parse
//      into a shared open-addressing table with atomic claims, in blocks
//      with software prefetch -- the ~50 MB random-access table walks out
//      of the scan loop and its cache misses overlap (MLP) instead of
//      serializing behind string work. Duplicate ids (same id claimed by
//      two rows) are recorded and resolved in document order afterwards:
//      first position wins, last-written fields win, dead rows compact
//      away, and the shape/status tables rebuild over surviving rows --
//      byte-identical to the sequential last-wins semantics (the JS Map
//      semantics of Traces.ts:119-126).
//   4. parent resolution (parallel): read-only prefetched probes.
//   5. serialize.
//
// Performance notes: string
// scanning rides glibc memchr (AVX2/512); keys dispatch on a
// length-switch; integer JSON numbers take a no-strtod fast path; naming
// shapes and statuses intern DURING the parse (small, cache-resident
// tables).
//
// Input payload (little-endian):
//   u32 n_skip                     -- processed-trace dedup entries
//   per entry: u8 present, u32 len, bytes   (present=0 encodes Python None)
//   remaining bytes: the raw Zipkin JSON response [[span,...],...]
//
// Output buffer (km_free to release), all little-endian:
//   header: u32 ok, u32 n_spans, u32 n_shapes, u32 n_statuses,
//           u32 n_groups, u32 prescan_us, u32 parse_us,
//           u32 (threads<<25 | merge_us)                  (32 bytes)
//   f64 latency_ms[n_spans]
//   f64 timestamp_us[n_spans]     -- raw JSON number (int64-cast in numpy)
//   f64 shape_max_ts_ms[n_shapes]
//   i32 parent_idx[n_spans]       -- resolved in-window, -1 = none
//   i32 shape_id[n_spans]
//   i32 status_id[n_spans]
//   i32 trace_of[n_spans]         -- kept-group index (first-position wins)
//   i8  kind[n_spans]             -- 0 other / 1 SERVER / 2 CLIENT
//   shapes: per shape: u8 url_present, u8 field_present_bits, then 7
//           fields (name, http.url, http.method, istio.canonical_service,
//           istio.namespace, istio.canonical_revision, istio.mesh_id):
//           u32 len + bytes each (missing fields emit len 0)
//   statuses: per status: u32 len + bytes  (missing tag folded to "")
//   kept trace ids: per group: u8 present, u32 len, bytes
//
// Semantics mirrored from the Python host path:
// - span map: duplicate span ids keep their FIRST position (ordering,
//   trace_of) with LAST-wins field values.
// - group dedup: a group whose first span's traceId is in the skip set or
//   already appeared in this response is dropped whole; empty groups drop
//   without registering (DataProcessor._filter_traces).
// - the naming-shape KEY folds a missing http.url with "" (the Python
//   cache key defaults it), but whether the first-seen span actually had
//   the tag is reported via url_present so the realtime-space naming
//   (js_str(None) == "undefined") reproduces first-seen behavior.

#include <algorithm>
#include <atomic>
#include <chrono>
#include <cstdint>
#include <cstdlib>
#include <cstring>
#include <memory>
#include <mutex>
#include <string>
#include <thread>
#include <utility>
#include <vector>

namespace {

using sv = std::string_view;

inline uint64_t now_us() {
  return static_cast<uint64_t>(
      std::chrono::duration_cast<std::chrono::microseconds>(
          std::chrono::steady_clock::now().time_since_epoch())
          .count());
}

inline void cpu_relax() {
#if defined(__x86_64__) || defined(__i386__)
  __builtin_ia32_pause();
#endif
}

// -- graftprof native counters ----------------------------------------------
// Cumulative attribution counters for the parse/merge pipeline, snapshotted
// over the ctypes boundary by km_prof_snapshot (telemetry/profiling reads
// them once per tick). Shard-granular: per-worker parse time and the time
// each worker then spent waiting at the assemble barrier for the slowest
// shard ("merge lock-wait" — the t2 contention wall as a per-shard number).
// Writers flush under g_prof.mu once per parse; the per-span hot loops only
// bump thread-local/table-local counters.

constexpr uint32_t kProfMaxShards = 64;  // pick_threads caps at 64
constexpr uint32_t kProfWireVersion = 2;

struct ProfCounters {
  std::mutex mu;
  // cumulative scalars (since load or km_prof_reset). The wire serializes
  // these in declaration order; a new scalar appends AFTER the existing
  // ones and bumps kProfWireVersion (the Python decoder is version-aware,
  // and the graftlint prof-counter-wire rule cross-checks the names
  // against _PROF_SCALARS in kmamiz_tpu/native/__init__.py).
  uint64_t parses = 0;
  uint64_t spans = 0;
  uint64_t merge_ns = 0;            // assemble wall time
  uint64_t merge_lock_wait_ns = 0;  // sum of per-worker barrier waits
  uint64_t merge_queue_depth_peak = 0;  // max workers pending at assemble
  uint64_t claim_contended = 0;     // 0 since the lock-free shard fold
  uint64_t intern_probes = 0;       // shape/status intern slot inspections
  uint64_t intern_hits = 0;         // interns resolved to an existing id
  uint64_t fold_ns = 0;             // sequential shard-table fold wall
  uint64_t fold_chunks = 0;         // work-stealing chunks folded
  // last parse, per shard
  uint32_t shards_used = 0;
  uint64_t shard_parse_ns[kProfMaxShards] = {0};
  uint64_t shard_wait_ns[kProfMaxShards] = {0};
  uint64_t shard_spans[kProfMaxShards] = {0};
};

ProfCounters g_prof;

// -- arena for decoded (escaped) strings ------------------------------------

struct Arena {
  std::vector<std::unique_ptr<char[]>> blocks;
  size_t used = 0, cap = 0;
  char* cur = nullptr;
  char* alloc(size_t n) {
    if (used + n > cap) {
      size_t sz = n > (1u << 16) ? n : (1u << 16);
      blocks.emplace_back(new char[sz]);
      cur = blocks.back().get();
      cap = sz;
      used = 0;
    }
    char* p = cur + used;
    used += n;
    return p;
  }
};

// word-at-a-time FNV variant (internal identity only; never serialized)
inline uint64_t hash_sv(sv s) {
  uint64_t h = 1469598103934665603ull ^ (s.size() * 0x9E3779B97F4A7C15ull);
  const char* p = s.data();
  size_t n = s.size();
  while (n >= 8) {
    uint64_t w;
    std::memcpy(&w, p, 8);
    h ^= w;
    h *= 1099511628211ull;
    p += 8;
    n -= 8;
  }
  if (n) {
    uint64_t w = 0;
    std::memcpy(&w, p, n);
    h ^= w;
    h *= 1099511628211ull;
  }
  // avalanche (murmur3 fmix64): without it the table-mask bits depend only
  // on the first bytes of each word and same-prefix keys probe O(n)
  h ^= h >> 33;
  h *= 0xff51afd7ed558ccdull;
  h ^= h >> 33;
  h *= 0xc4ceb9fe1a85ec53ull;
  h ^= h >> 33;
  return h;
}

// SWAR: bytes of `w` equal to `pat`-byte -> high bit set in result
inline uint64_t swar_eq(uint64_t w, uint64_t pat) {
  uint64_t x = w ^ pat;
  return (x - 0x0101010101010101ull) & ~x & 0x8080808080808080ull;
}

constexpr uint64_t kQuotePat = 0x2222222222222222ull;   // '"'
constexpr uint64_t kBslashPat = 0x5C5C5C5C5C5C5C5Cull;  // '\\'

// -- wide scans with runtime dispatch ---------------------------------------
// The string/value scans touch every input byte; on AVX-512 hosts a 64-byte
// masked-compare iteration replaces 8 SWAR word steps. Dispatch is a
// one-time cpuid check into function pointers; the SWAR forms are the
// portable fallback (and the tail loop near the buffer end).

// first '"' or '\\' at/after q; returns end when absent
static const char* scan_special_swar(const char* q, const char* end) {
  while (end - q >= 8) {
    uint64_t w;
    std::memcpy(&w, q, 8);
    uint64_t m = swar_eq(w, kQuotePat) | swar_eq(w, kBslashPat);
    if (m) return q + (__builtin_ctzll(m) >> 3);
    q += 8;
  }
  while (q < end && *q != '"' && *q != '\\') ++q;
  return q;
}

// first structural byte ('"', '{', '}', '[', ']') at/after q, else end
static const char* scan_structural_swar(const char* q, const char* end) {
  while (end - q >= 8) {
    uint64_t w;
    std::memcpy(&w, q, 8);
    uint64_t wl = w | 0x2020202020202020ull;
    uint64_t m = swar_eq(wl, 0x7B7B7B7B7B7B7B7Bull) |
                 swar_eq(wl, 0x7D7D7D7D7D7D7D7Dull) | swar_eq(w, kQuotePat);
    if (m) return q + (__builtin_ctzll(m) >> 3);
    q += 8;
  }
  while (q < end && *q != '"' && *q != '{' && *q != '}' && *q != '[' &&
         *q != ']')
    ++q;
  return q;
}

#if defined(__x86_64__)
#include <immintrin.h>

__attribute__((target("avx2"))) static const char* scan_special_avx2(
    const char* q, const char* end) {
  const __m256i vq = _mm256_set1_epi8('"');
  const __m256i vb = _mm256_set1_epi8('\\');
  while (end - q >= 32) {
    __m256i v = _mm256_loadu_si256(reinterpret_cast<const __m256i*>(q));
    uint32_t m = static_cast<uint32_t>(
        _mm256_movemask_epi8(_mm256_or_si256(_mm256_cmpeq_epi8(v, vq),
                                             _mm256_cmpeq_epi8(v, vb))));
    if (m) return q + __builtin_ctz(m);
    q += 32;
  }
  return scan_special_swar(q, end);
}

__attribute__((target("avx2"))) static const char* scan_structural_avx2(
    const char* q, const char* end) {
  const __m256i vq = _mm256_set1_epi8('"');
  const __m256i vo = _mm256_set1_epi8('{');
  const __m256i vc = _mm256_set1_epi8('}');
  const __m256i lower = _mm256_set1_epi8(0x20);
  while (end - q >= 32) {
    __m256i v = _mm256_loadu_si256(reinterpret_cast<const __m256i*>(q));
    __m256i vl = _mm256_or_si256(v, lower);
    __m256i hit = _mm256_or_si256(
        _mm256_or_si256(_mm256_cmpeq_epi8(vl, vo), _mm256_cmpeq_epi8(vl, vc)),
        _mm256_cmpeq_epi8(v, vq));
    uint32_t m = static_cast<uint32_t>(_mm256_movemask_epi8(hit));
    if (m) return q + __builtin_ctz(m);
    q += 32;
  }
  return scan_structural_swar(q, end);
}
#endif

using scan_fn = const char* (*)(const char*, const char*);
scan_fn g_scan_special = scan_special_swar;
scan_fn g_scan_structural = scan_structural_swar;

// -- block classification for the group prescan -----------------------------
// simdjson-style stage 1, reduced to what trace-group splitting needs:
// per 64-byte block, bitmasks of '"', '\\', '[', ']' -> resolve escapes,
// derive the in-string mask by prefix-XOR of unescaped quotes (with
// carries across blocks), and emit the positions of brackets OUTSIDE
// strings. One branchless linear pass instead of re-scanning every byte
// through the Scanner's per-group skip walk — this is the serial
// fraction of the multi-threaded parse.

struct BlockMasks {
  uint64_t quote, bslash, open, close;
};

static inline uint64_t movemask8(uint64_t m_high) {
  // SWAR compare result (high bit per byte) -> 8-bit mask
  return (m_high >> 7) * 0x0102040810204080ull >> 56;
}

static void classify_swar(const char* p, BlockMasks* out) {
  uint64_t q = 0, b = 0, o = 0, c = 0;
  for (int w = 0; w < 8; ++w) {
    uint64_t word;
    std::memcpy(&word, p + w * 8, 8);
    q |= movemask8(swar_eq(word, kQuotePat)) << (w * 8);
    b |= movemask8(swar_eq(word, kBslashPat)) << (w * 8);
    o |= movemask8(swar_eq(word, 0x5B5B5B5B5B5B5B5Bull)) << (w * 8);
    c |= movemask8(swar_eq(word, 0x5D5D5D5D5D5D5D5Dull)) << (w * 8);
  }
  out->quote = q;
  out->bslash = b;
  out->open = o;
  out->close = c;
}

#if defined(__x86_64__)
// NOTE: no lambdas here — closures do not inherit the target attribute
__attribute__((target("avx2"))) static uint64_t mask64_avx2(
    __m256i lo, __m256i hi, __m256i needle) {
  uint64_t mlo = static_cast<uint32_t>(
      _mm256_movemask_epi8(_mm256_cmpeq_epi8(lo, needle)));
  uint64_t mhi = static_cast<uint32_t>(
      _mm256_movemask_epi8(_mm256_cmpeq_epi8(hi, needle)));
  return mlo | (mhi << 32);
}

__attribute__((target("avx2"))) static void classify_avx2(const char* p,
                                                          BlockMasks* out) {
  __m256i lo = _mm256_loadu_si256(reinterpret_cast<const __m256i*>(p));
  __m256i hi = _mm256_loadu_si256(reinterpret_cast<const __m256i*>(p + 32));
  out->quote = mask64_avx2(lo, hi, _mm256_set1_epi8('"'));
  out->bslash = mask64_avx2(lo, hi, _mm256_set1_epi8('\\'));
  out->open = mask64_avx2(lo, hi, _mm256_set1_epi8('['));
  out->close = mask64_avx2(lo, hi, _mm256_set1_epi8(']'));
}
#endif

using classify_fn = void (*)(const char*, BlockMasks*);
classify_fn g_classify = classify_swar;

__attribute__((constructor)) static void init_scan_dispatch() {
#if defined(__x86_64__)
  if (__builtin_cpu_supports("avx2")) {
    g_scan_special = scan_special_avx2;
    g_scan_structural = scan_structural_avx2;
    g_classify = classify_avx2;
  }
#endif
}

inline uint64_t prefix_xor64(uint64_t x) {
  x ^= x << 1;
  x ^= x << 2;
  x ^= x << 4;
  x ^= x << 8;
  x ^= x << 16;
  x ^= x << 32;
  return x;
}

// emit [begin, end) byte ranges of the top-level array's elements that are
// themselves arrays (trace groups). Returns false on malformed bracket
// structure; out_end gets the offset just past the top-level ']'.
// Elements that are NOT arrays leave gaps the caller validates.
static bool scan_group_ranges(const char* json, size_t len,
                              std::vector<std::pair<size_t, size_t>>* groups,
                              size_t* top_open, size_t* top_close) {
  uint64_t prev_in_string = 0;   // all-ones when carrying inside a string
  uint64_t prev_escaped = 0;     // bit 0: first char of block is escaped
  int depth = 0;
  bool seen_top = false;
  size_t group_start = 0;
  *top_open = len;
  *top_close = len;

  alignas(64) char tail[64];
  for (size_t base = 0; base < len; base += 64) {
    BlockMasks m;
    if (len - base >= 64) {
      g_classify(json + base, &m);
    } else {
      size_t n = len - base;
      std::memset(tail, 0, sizeof(tail));
      std::memcpy(tail, json + base, n);
      g_classify(tail, &m);
    }
    // resolve escaped characters: the canonical simdjson odd-length
    // backslash-run scan (json_string_scanner::find_escaped), with
    // prev_escaped carrying a run's escape across the block edge
    uint64_t bs = m.bslash & ~prev_escaped;
    uint64_t follows_escape = (bs << 1) | prev_escaped;
    constexpr uint64_t kEvenBits = 0x5555555555555555ull;
    uint64_t odd_starts = bs & ~kEvenBits & ~follows_escape;
    uint64_t seq_on_even;
    prev_escaped =
        __builtin_add_overflow(odd_starts, bs, &seq_on_even) ? 1 : 0;
    uint64_t escaped = ((kEvenBits ^ (seq_on_even << 1)) & follows_escape);
    uint64_t quotes = m.quote & ~escaped;
    uint64_t in_string = prefix_xor64(quotes) ^ prev_in_string;
    prev_in_string = static_cast<uint64_t>(static_cast<int64_t>(in_string) >> 63);
    uint64_t structural = (m.open | m.close) & ~in_string & ~escaped;
    // quoted regions: a bracket AT a quote position is impossible; the
    // in_string mask includes the opening quote and excludes the closing
    // one, which is fine because brackets are never quote bytes
    while (structural) {
      int bit = __builtin_ctzll(structural);
      structural &= structural - 1;
      size_t pos = base + static_cast<size_t>(bit);
      if (pos >= len) break;
      bool is_open = (m.open >> bit) & 1;
      if (is_open) {
        ++depth;
        if (depth == 1) {
          if (seen_top) return false;  // second top-level array
          seen_top = true;
          *top_open = pos;
        } else if (depth == 2) {
          group_start = pos;
        }
      } else {
        if (depth <= 0) return false;
        --depth;
        if (depth == 1) {
          groups->emplace_back(group_start, pos + 1);
        } else if (depth == 0) {
          *top_close = pos + 1;
          return seen_top;
        }
      }
    }
  }
  return false;  // top-level array never closed
}

inline bool only_ws_and_commas(const char* p, const char* end,
                               int expected_commas) {
  int commas = 0;
  for (; p < end; ++p) {
    char ch = *p;
    if (ch == ',') {
      ++commas;
    } else if (ch != ' ' && ch != '\t' && ch != '\n' && ch != '\r') {
      return false;
    }
  }
  return commas == expected_commas;
}

// the ranges from scan_group_ranges cover only ARRAY elements; everything
// between them must be exactly the separating commas (+ws), or the input
// carried non-array elements / garbage the sequential walk would reject.
// Shared by prescan_fast and km_split_groups so the two stay in lockstep.
static bool validate_group_gaps(
    const char* json, const std::vector<std::pair<size_t, size_t>>& ranges,
    size_t top_open, size_t top_close) {
  if (!only_ws_and_commas(json, json + top_open, 0)) return false;
  if (ranges.empty())
    return only_ws_and_commas(json + top_open + 1, json + top_close - 1, 0);
  if (!only_ws_and_commas(json + top_open + 1, json + ranges[0].first, 0))
    return false;
  for (size_t g = 1; g < ranges.size(); ++g) {
    if (!only_ws_and_commas(json + ranges[g - 1].second,
                            json + ranges[g].first, 1))
      return false;
  }
  return only_ws_and_commas(json + ranges.back().second,
                            json + top_close - 1, 0);
}

// -- open-addressing string_view -> int32 map -------------------------------
// One packed 24-byte slot per entry (cached hash + ptr/len + value): a probe
// costs one cache line, and equality checks compare the 64-bit hash before
// touching key bytes. Used for the small sequential tables (trace-id dedup,
// statuses); the big span-id table is the atomic SpanIdTable below.

struct SvMap {
  struct Slot {
    uint64_t hash;  // 0 = empty (hash_sv never returns 0; see intern)
    const char* ptr;
    uint32_t len;
    int32_t val;
  };
  std::vector<Slot> slots;
  size_t mask = 0, count = 0;
  mutable uint64_t probes = 0, hits = 0;  // graftprof intern stats

  explicit SvMap(size_t initial = 64) {
    size_t n = 16;
    while (n < initial * 2) n <<= 1;
    slots.assign(n, Slot{0, nullptr, 0, 0});
    mask = n - 1;
  }

  static inline uint64_t key_hash(sv key) {
    uint64_t h = hash_sv(key);
    return h | 1;  // reserve 0 for empty slots
  }

  void grow() {
    size_t n = (mask + 1) * 2;
    std::vector<Slot> ns(n, Slot{0, nullptr, 0, 0});
    for (size_t i = 0; i <= mask; ++i) {
      if (!slots[i].hash) continue;
      size_t j = slots[i].hash & (n - 1);
      while (ns[j].hash) j = (j + 1) & (n - 1);
      ns[j] = slots[i];
    }
    slots.swap(ns);
    mask = n - 1;
  }

  static inline bool slot_eq(const Slot& s, uint64_t h, sv key) {
    return s.hash == h && s.len == key.size() &&
           std::memcmp(s.ptr, key.data(), key.size()) == 0;
  }

  int32_t* find(sv key) {
    uint64_t h = key_hash(key);
    size_t j = h & mask;
    while (slots[j].hash) {
      ++probes;
      if (slot_eq(slots[j], h, key)) {
        ++hits;
        return &slots[j].val;
      }
      j = (j + 1) & mask;
    }
    return nullptr;
  }

  const int32_t* find(sv key) const {
    return const_cast<SvMap*>(this)->find(key);
  }

  int32_t intern(sv key, int32_t next_val, bool* inserted) {
    if (count * 2 >= mask) grow();
    uint64_t h = key_hash(key);
    size_t j = h & mask;
    while (slots[j].hash) {
      ++probes;
      if (slot_eq(slots[j], h, key)) {
        ++hits;
        *inserted = false;
        return slots[j].val;
      }
      j = (j + 1) & mask;
    }
    slots[j] = Slot{h, key.data(), static_cast<uint32_t>(key.size()), next_val};
    ++count;
    *inserted = true;
    return next_val;
  }
};

// -- naming shapes ----------------------------------------------------------

// field order: name, url, method, svc, ns, rev, mesh
constexpr int kShapeFields = 7;
constexpr uint8_t kHasMethod = 1 << 2;
constexpr uint8_t kHasSvc = 1 << 3;
constexpr uint8_t kHasNs = 1 << 4;
constexpr uint8_t kHasRev = 1 << 5;
constexpr uint8_t kHasMesh = 1 << 6;
constexpr uint8_t kKeyBits = kHasMethod | kHasSvc | kHasNs | kHasRev | kHasMesh;

struct Shape {
  sv f[kShapeFields];
  uint8_t key_present = 0;  // optional-field presence (part of identity)
  uint8_t url_present = 0;  // first-seen http.url presence (payload only)
  double max_ts_ms = 0.0;
  bool has_ts = false;
};

inline bool shape_eq(const Shape& a, const Shape& b) {
  if (a.key_present != b.key_present) return false;
  for (int i = 0; i < kShapeFields; ++i)
    if (a.f[i] != b.f[i]) return false;
  return true;
}

// shape identity hash over (name, url, presence bits) ONLY: those two
// fields distinguish almost all real shapes, the per-span hot loop
// already has their hashes at hand (ShapeCache), and equal-hash
// collisions between shapes differing only in svc/ns/rev/mesh stay
// correct — the tables verify with full shape_eq and probe past
// mismatches. Hashing 2 fields instead of 7 is the point: every span
// used to pay the 7-string walk on a ShapeCache miss.
inline uint64_t shape_hash(const Shape& s) {
  return hash_sv(s.f[0]) * 31 + hash_sv(s.f[1]) + s.key_present;
}

struct ShapeTable {
  std::vector<Shape> shapes;
  std::vector<int32_t> slot_id;
  std::vector<uint64_t> slot_hash;
  size_t mask;
  uint64_t probes = 0, hits = 0;  // graftprof intern stats

  ShapeTable() : slot_id(256, -1), slot_hash(256, 0), mask(255) {}

  void clear() {
    shapes.clear();
    std::fill(slot_id.begin(), slot_id.end(), -1);
  }

  void grow() {
    size_t n = (mask + 1) * 2;
    std::vector<int32_t> sid(n, -1);
    std::vector<uint64_t> sh(n, 0);
    for (size_t i = 0; i <= mask; ++i) {
      if (slot_id[i] < 0) continue;
      size_t j = slot_hash[i] & (n - 1);
      while (sid[j] >= 0) j = (j + 1) & (n - 1);
      sid[j] = slot_id[i];
      sh[j] = slot_hash[i];
    }
    slot_id.swap(sid);
    slot_hash.swap(sh);
    mask = n - 1;
  }

  int32_t intern(const Shape& s) { return intern(s, shape_hash(s)); }

  // hot-path form: the caller (parse_group_spans) already computed the
  // (name, url, bits) hash for its direct-mapped cache; reuse it
  int32_t intern(const Shape& s, uint64_t h) {
    if (shapes.size() * 2 >= mask) grow();
    size_t j = h & mask;
    while (slot_id[j] >= 0) {
      ++probes;
      if (slot_hash[j] == h && shape_eq(shapes[slot_id[j]], s)) {
        ++hits;
        return slot_id[j];
      }
      j = (j + 1) & mask;
    }
    int32_t id = static_cast<int32_t>(shapes.size());
    shapes.push_back(s);
    slot_id[j] = id;
    slot_hash[j] = h;
    return id;
  }
};

// -- JSON scanner -----------------------------------------------------------

struct Scanner {
  const char* p;
  const char* end;
  Arena* arena;
  bool ok = true;

  void ws() {
    while (p < end && (*p == ' ' || *p == '\t' || *p == '\n' || *p == '\r'))
      ++p;
  }

  bool eat(char c) {
    ws();
    if (p < end && *p == c) {
      ++p;
      return true;
    }
    ok = false;
    return false;
  }

  bool peek(char c) {
    ws();
    return p < end && *p == c;
  }

  // first '"' or '\\' at/after q (dispatched wide scan)
  const char* scan_special(const char* q) const {
    return g_scan_special(q, end);  // == end when not found
  }

  // decoded string; zero-copy when escape-free (the common case)
  sv str() {
    ws();
    if (p >= end || *p != '"') {
      ok = false;
      return {};
    }
    ++p;
    // inline one-word fast path: short fields (kinds, methods, statuses,
    // most names) terminate within 8 bytes — resolving them here skips
    // the dispatched wide-scan's indirect call, which at ~18 string
    // scans per span is measurable
    if (end - p >= 8) {
      uint64_t w;
      std::memcpy(&w, p, 8);
      uint64_t m = swar_eq(w, kQuotePat) | swar_eq(w, kBslashPat);
      if (m) {
        const char* q = p + (__builtin_ctzll(m) >> 3);
        if (*q == '"') {
          sv out(p, static_cast<size_t>(q - p));
          p = q + 1;
          return out;
        }
        return str_slow();
      }
      const char* q = scan_special(p + 8);  // no specials in [p, p+8)
      if (q >= end) {
        ok = false;
        return {};
      }
      if (*q == '"') {
        sv out(p, static_cast<size_t>(q - p));
        p = q + 1;
        return out;
      }
      return str_slow();
    }
    const char* q = scan_special(p);
    if (q >= end) {
      ok = false;
      return {};
    }
    if (*q == '"') {
      sv out(p, static_cast<size_t>(q - p));
      p = q + 1;
      return out;
    }
    return str_slow();
  }

  // escape-bearing string decode; p sits just after the opening quote
  sv str_slow() {
    std::string buf;
    while (p < end && *p != '"') {
      if (*p != '\\') {
        buf.push_back(*p++);
        continue;
      }
      ++p;
      if (p >= end) {
        ok = false;
        return {};
      }
      char c = *p++;
      switch (c) {
        case '"': buf.push_back('"'); break;
        case '\\': buf.push_back('\\'); break;
        case '/': buf.push_back('/'); break;
        case 'b': buf.push_back('\b'); break;
        case 'f': buf.push_back('\f'); break;
        case 'n': buf.push_back('\n'); break;
        case 'r': buf.push_back('\r'); break;
        case 't': buf.push_back('\t'); break;
        case 'u': {
          auto hex4 = [&](const char* q) -> int {
            int v = 0;
            for (int i = 0; i < 4; ++i) {
              char h = q[i];
              v <<= 4;
              if (h >= '0' && h <= '9') v |= h - '0';
              else if (h >= 'a' && h <= 'f') v |= h - 'a' + 10;
              else if (h >= 'A' && h <= 'F') v |= h - 'A' + 10;
              else return -1;
            }
            return v;
          };
          if (end - p < 4) {
            ok = false;
            return {};
          }
          int cp = hex4(p);
          if (cp < 0) {
            ok = false;
            return {};
          }
          p += 4;
          if (cp >= 0xD800 && cp <= 0xDBFF && end - p >= 6 && p[0] == '\\' &&
              p[1] == 'u') {
            int lo = hex4(p + 2);
            if (lo >= 0xDC00 && lo <= 0xDFFF) {
              cp = 0x10000 + ((cp - 0xD800) << 10) + (lo - 0xDC00);
              p += 6;
            }
          }
          if (cp < 0x80) {
            buf.push_back(static_cast<char>(cp));
          } else if (cp < 0x800) {
            buf.push_back(static_cast<char>(0xC0 | (cp >> 6)));
            buf.push_back(static_cast<char>(0x80 | (cp & 0x3F)));
          } else if (cp < 0x10000) {
            buf.push_back(static_cast<char>(0xE0 | (cp >> 12)));
            buf.push_back(static_cast<char>(0x80 | ((cp >> 6) & 0x3F)));
            buf.push_back(static_cast<char>(0x80 | (cp & 0x3F)));
          } else {
            buf.push_back(static_cast<char>(0xF0 | (cp >> 18)));
            buf.push_back(static_cast<char>(0x80 | ((cp >> 12) & 0x3F)));
            buf.push_back(static_cast<char>(0x80 | ((cp >> 6) & 0x3F)));
            buf.push_back(static_cast<char>(0x80 | (cp & 0x3F)));
          }
          break;
        }
        default:
          ok = false;
          return {};
      }
    }
    if (p >= end) {
      ok = false;
      return {};
    }
    ++p;
    char* mem = arena->alloc(buf.size());
    std::memcpy(mem, buf.data(), buf.size());
    return sv(mem, buf.size());
  }

  // skip a string; assumes *p=='"'
  void skip_string_raw() {
    ++p;
    for (;;) {
      const char* q = scan_special(p);
      if (q >= end) {
        ok = false;
        return;
      }
      if (*q == '"') {
        p = q + 1;
        return;
      }
      p = q + 2;  // backslash: skip the escaped character
      if (p > end) {
        ok = false;
        return;
      }
    }
  }

  // skip a {...} or [...] wholesale; SWAR block scan for structural bytes.
  // '{'/'[' and '}'/']' differ only in bit 5, so (w | 0x20..) needs two
  // patterns; '"' matches on the raw word (0x02 false-positives fall
  // through the switch harmlessly).
  void skip_container() {
    int depth = 0;
    const char* q = p;
    while (q < end) {
      q = g_scan_structural(q, end);
      if (q >= end) break;
      char c = *q;
      switch (c) {
        case '"':
          p = q;
          skip_string_raw();
          if (!ok) return;
          q = p;
          break;
        case '{':
        case '[':
          ++depth;
          ++q;
          break;
        case '}':
        case ']':
          --depth;
          ++q;
          if (depth == 0) {
            p = q;
            return;
          }
          break;
        default:
          ++q;  // SWAR false positive (e.g. 0x02): not structural
          break;
      }
    }
    ok = false;
  }

  void skip_value() {
    ws();
    if (p >= end) {
      ok = false;
      return;
    }
    char c = *p;
    if (c == '"') {
      skip_string_raw();
    } else if (c == '{' || c == '[') {
      skip_container();
    } else {
      const char* start = p;
      while (p < end && *p != ',' && *p != '}' && *p != ']' && *p != ' ' &&
             *p != '\n' && *p != '\t' && *p != '\r')
        ++p;
      if (p == start) ok = false;  // empty value: malformed JSON
    }
  }

  // JSON number -> double; plain integers avoid strtod
  double number() {
    ws();
    const char* start = p;
    bool neg = false;
    if (p < end && *p == '-') {
      neg = true;
      ++p;
    }
    uint64_t acc = 0;
    int digits = 0;
    while (p < end && *p >= '0' && *p <= '9') {
      acc = acc * 10 + static_cast<uint64_t>(*p - '0');
      ++digits;
      ++p;
    }
    if (digits > 0 && digits <= 18 &&
        (p >= end || (*p != '.' && *p != 'e' && *p != 'E'))) {
      double v = static_cast<double>(acc);
      return neg ? -v : v;
    }
    // fractional / exponent / huge: defer to strtod
    while (p < end &&
           ((*p >= '0' && *p <= '9') || *p == '+' || *p == '-' || *p == '.' ||
            *p == 'e' || *p == 'E'))
      ++p;
    if (p == start) {
      ok = false;
      return 0.0;
    }
    char tmp[64];
    size_t len = static_cast<size_t>(p - start);
    if (len >= sizeof(tmp)) len = sizeof(tmp) - 1;
    std::memcpy(tmp, start, len);
    tmp[len] = 0;
    return std::strtod(tmp, nullptr);
  }
};

// -- span records -----------------------------------------------------------

struct SpanRec {
  sv id, parent_id;
  sv name, url, method, svc, ns, rev, mesh;
  sv status;
  uint8_t present = 0;
  bool url_present = false;
  bool status_present = false;
  bool has_parent = false;
  int8_t kind = 0;
  double latency_ms = 0.0;
  double timestamp_raw = 0.0;
};

// span/tag key handlers for the order-prediction fast path
enum SpanKey : int8_t {
  SK_OTHER = 0,
  SK_ID,
  SK_TRACE,
  SK_PARENT,
  SK_KIND,
  SK_NAME,
  SK_TS,
  SK_DUR,
  SK_TAGS,
};
enum TagKey : int8_t {
  TK_OTHER = 0,
  TK_URL,
  TK_METHOD,
  TK_STATUS,
  TK_SVC,
  TK_NS,
  TK_REV,
  TK_MESH,
};

// one predicted (key bytes, handler) slot per key position; spans from one
// producer serialize keys in a fixed order, so after the first span nearly
// every key resolves with a single memcmp instead of a scan +
// length-switch. A miss tolerates one skipped slot (optional keys like
// parentId), falling back to slow dispatch without corrupting the
// learned sequence.
struct KeyPredictor {
  struct Entry {
    sv key;
    int8_t handler;
  };
  std::vector<Entry> seq;
  size_t pos = 0;

  void begin() { pos = 0; }

  // try the predicted key at p (just after the opening '"'); advances p
  // past `key"` on a hit and returns the handler, else returns -1
  int predict(const char*& p, const char* end) {
    for (size_t look = pos; look < pos + 2 && look < seq.size(); ++look) {
      const Entry& e = seq[look];
      size_t len = e.key.size();
      if (static_cast<size_t>(end - p) > len && p[len] == '"' &&
          std::memcmp(p, e.key.data(), len) == 0) {
        pos = look + 1;
        p += len + 1;
        return e.handler;
      }
    }
    return -1;
  }

  // append to the learned tail (only grows; misses elsewhere are fine)
  void learn(sv key, int8_t handler) {
    if (pos == seq.size()) {
      seq.push_back(Entry{key, handler});
      ++pos;
    }
  }
};

inline int8_t tag_handler(sv key) {
  switch (key.size()) {
    case 8: return key == "http.url" ? TK_URL : TK_OTHER;
    case 11: return key == "http.method" ? TK_METHOD : TK_OTHER;
    case 13: return key == "istio.mesh_id" ? TK_MESH : TK_OTHER;
    case 15: return key == "istio.namespace" ? TK_NS : TK_OTHER;
    case 16: return key == "http.status_code" ? TK_STATUS : TK_OTHER;
    case 23: return key == "istio.canonical_service" ? TK_SVC : TK_OTHER;
    case 24: return key == "istio.canonical_revision" ? TK_REV : TK_OTHER;
    default: return TK_OTHER;
  }
}

inline int8_t span_handler(sv key) {
  switch (key.size()) {
    case 2: return key == "id" ? SK_ID : SK_OTHER;
    case 4:
      if (key == "kind") return SK_KIND;
      if (key == "name") return SK_NAME;
      if (key == "tags") return SK_TAGS;
      return SK_OTHER;
    case 7: return key == "traceId" ? SK_TRACE : SK_OTHER;
    case 8:
      if (key == "parentId") return SK_PARENT;
      if (key == "duration") return SK_DUR;
      return SK_OTHER;
    case 9: return key == "timestamp" ? SK_TS : SK_OTHER;
    default: return SK_OTHER;
  }
}

bool parse_tags(Scanner& s, SpanRec* rec, KeyPredictor& pred) {
  if (!s.eat('{')) return false;
  pred.begin();
  bool first = true;
  while (s.ok) {
    s.ws();
    if (s.peek('}')) {
      ++s.p;
      return true;
    }
    if (!first && !s.eat(',')) return false;
    first = false;
    s.ws();
    if (s.p >= s.end || *s.p != '"') {
      s.ok = false;
      return false;
    }
    ++s.p;
    int h = pred.predict(s.p, s.end);
    if (h < 0) {
      --s.p;
      sv key = s.str();
      if (!s.ok) return false;
      h = tag_handler(key);
      pred.learn(key, static_cast<int8_t>(h));
    }
    if (!s.eat(':')) return false;
    s.ws();
    if (s.p < s.end && *s.p != '"') {
      s.skip_value();  // non-string tag: Zipkin tags are strings
      continue;
    }
    switch (h) {
      case TK_URL:
        rec->url = s.str();
        rec->url_present = true;
        break;
      case TK_METHOD:
        rec->method = s.str();
        rec->present |= kHasMethod;
        break;
      case TK_STATUS:
        rec->status = s.str();
        rec->status_present = true;
        break;
      case TK_SVC:
        rec->svc = s.str();
        rec->present |= kHasSvc;
        break;
      case TK_NS:
        rec->ns = s.str();
        rec->present |= kHasNs;
        break;
      case TK_REV:
        rec->rev = s.str();
        rec->present |= kHasRev;
        break;
      case TK_MESH:
        rec->mesh = s.str();
        rec->present |= kHasMesh;
        break;
      default:
        s.skip_string_raw();
        break;
    }
  }
  return s.ok;
}

bool parse_span(Scanner& s, SpanRec* rec, KeyPredictor& span_pred,
                KeyPredictor& tag_pred) {
  if (!s.eat('{')) return false;
  span_pred.begin();
  bool first = true;
  while (s.ok) {
    s.ws();
    if (s.peek('}')) {
      ++s.p;
      break;
    }
    if (!first && !s.eat(',')) return false;
    first = false;
    s.ws();
    if (s.p >= s.end || *s.p != '"') {
      s.ok = false;
      return false;
    }
    ++s.p;
    int h = span_pred.predict(s.p, s.end);
    if (h < 0) {
      --s.p;
      sv key = s.str();
      if (!s.ok) return false;
      h = span_handler(key);
      span_pred.learn(key, static_cast<int8_t>(h));
    }
    if (!s.eat(':')) return false;
    switch (h) {
      case SK_ID:
        s.ws();
        if (s.p < s.end && *s.p == '"') {
          rec->id = s.str();
          continue;
        }
        break;
      case SK_KIND:
        s.ws();
        if (s.p < s.end && *s.p == '"') {
          sv k = s.str();
          rec->kind = (k == "SERVER") ? 1 : (k == "CLIENT") ? 2 : 0;
          continue;
        }
        break;
      case SK_NAME:
        s.ws();
        if (s.p < s.end && *s.p == '"') {
          rec->name = s.str();
          continue;
        }
        break;
      case SK_TAGS:
        s.ws();
        if (s.p < s.end && *s.p == '{') {
          if (!parse_tags(s, rec, tag_pred)) return false;
          continue;
        }
        break;
      case SK_PARENT:
        s.ws();
        if (s.p < s.end && *s.p == '"') {
          rec->parent_id = s.str();
          rec->has_parent = true;
          continue;
        }
        break;
      case SK_DUR:
        rec->latency_ms = s.number() / 1000.0;
        continue;
      case SK_TS:
        rec->timestamp_raw = s.number();
        continue;
      default:
        break;
    }
    s.skip_value();
  }
  return s.ok;
}

// peek the first span object's traceId without consuming input
bool peek_trace_id(Scanner probe, sv* out, bool* present) {
  *present = false;
  if (!probe.eat('{')) return false;
  bool first = true;
  while (probe.ok) {
    probe.ws();
    if (probe.peek('}')) return true;
    if (!first && !probe.eat(',')) return false;
    first = false;
    sv key = probe.str();
    if (!probe.eat(':')) return false;
    if (key == "traceId") {
      probe.ws();
      if (probe.p < probe.end && *probe.p == '"') {
        *out = probe.str();
        *present = true;
      }
      return probe.ok;
    }
    probe.skip_value();
  }
  return probe.ok;
}

// sentinel for "traceId is Python None" in the seen-set
const sv kNoneSentinel("\x01\x01\x01none", 7);

// -- persistent skip set (km_skipset_* C API) -------------------------------
// The processed-trace dedup set as a long-lived native object: the caller
// (DataProcessor) extends it incrementally as traces register and passes
// the HANDLE to each parse, instead of re-encoding and re-hashing the
// whole (100k+-entry) set into a fresh blob+SvMap on every chunk — that
// rebuild was ~20 ms of every streamed chunk's critical path at the
// production dedup size. Id bytes copy into the set's own arena (the
// caller's buffers may move); absent ids collapse onto kNoneSentinel,
// exactly like the blob path's (sv, present=false) entries. Lookups
// lock per probe (uncontended ~ns) so a concurrent registration from
// the realtime tick never waits on a multi-hundred-ms parse.
struct SkipSet {
  mutable std::mutex mu;
  Arena arena;
  SvMap map{4096};
  uint64_t count = 0;  // distinct ids (diagnostics)

  bool contains(sv key) const {
    std::lock_guard<std::mutex> g(mu);
    return map.find(key) != nullptr;
  }

  // entries: consecutive skip-entry records (u8 present + u32 len +
  // bytes). Returns the number of records walked, or -1 on malformed.
  int64_t extend(const char* entries, size_t len) {
    std::lock_guard<std::mutex> g(mu);
    const uint8_t* q = reinterpret_cast<const uint8_t*>(entries);
    size_t pos = 0;
    int64_t walked = 0;
    while (pos < len) {
      if (pos + 5 > len) return -1;
      bool present = q[pos] != 0;
      uint32_t n;
      std::memcpy(&n, q + pos + 1, 4);
      pos += 5;
      if (pos + n > len) return -1;
      sv key = present ? sv(entries + pos, n) : kNoneSentinel;
      pos += n;
      ++walked;
      if (map.find(key) != nullptr) continue;
      if (present && n > 0) {
        char* mem = arena.alloc(n);
        std::memcpy(mem, key.data(), n);
        key = sv(mem, n);
      }
      bool ins;
      map.intern(key, 1, &ins);
      if (ins) ++count;
    }
    return walked;
  }

  void clear() {
    std::lock_guard<std::mutex> g(mu);
    map = SvMap(4096);
    arena = Arena();
    count = 0;
  }
};

// -- phase 1: prescan -------------------------------------------------------

struct GroupRange {
  const char* begin;  // at the group's '['
  const char* end;    // one past the group's ']'
  sv tid;
  bool tid_present;
};

// per-thread parse output: rows + small private tables
// -- lock-free per-shard span-id table --------------------------------------
// Plain open addressing, NO atomics: each parse worker builds one of
// these privately for its chunk (zero sharing), and the assemble phase
// folds the per-chunk tables into one final FlatIdTable in a single
// sequential pass (document order, so first-position-wins dedup falls
// out of insertion order). This replaces the old shared atomic
// SpanIdTable whose CAS claims + row spin-waits were the t2 merge wall.

constexpr size_t kPrefetchBlock = 32;

struct FlatIdTable {
  std::vector<uint64_t> hashes;  // 0 = empty (SvMap::key_hash sets |1)
  std::vector<int32_t> rows;
  size_t mask = 0;

  void init(size_t n_rows) {
    size_t n = 64;
    while (n < n_rows * 2) n <<= 1;
    hashes.assign(n, 0);
    rows.assign(n, -1);
    mask = n - 1;
  }

  // returns -1 when `row` claimed the slot, else the slot index of the
  // existing claim (a duplicate id)
  int64_t insert(sv key, uint64_t h, int32_t row, const sv* ids) {
    size_t j = h & mask;
    for (;;) {
      uint64_t cur = hashes[j];
      if (cur == 0) {
        hashes[j] = h;
        rows[j] = row;
        return -1;
      }
      if (cur == h) {
        const sv& k = ids[rows[j]];
        // empty ids carry nullptr data; memcmp(nullptr, ..., 0) is UB
        if (k.size() == key.size() &&
            (key.empty() ||
             std::memcmp(k.data(), key.data(), key.size()) == 0))
          return static_cast<int64_t>(j);
        // same hash, different key: keep probing
      }
      j = (j + 1) & mask;
    }
  }

  // read-only lookup; -1 when absent
  int32_t find(sv key, uint64_t h, const sv* ids) const {
    if (hashes.empty()) return -1;
    size_t j = h & mask;
    for (;;) {
      uint64_t cur = hashes[j];
      if (cur == 0) return -1;
      if (cur == h) {
        int32_t r = rows[j];
        if (r >= 0) {
          const sv& k = ids[r];
          if (k.size() == key.size() &&
              (key.empty() ||
               std::memcmp(k.data(), key.data(), key.size()) == 0))
            return r;
        }
      }
      j = (j + 1) & mask;
    }
  }
};

struct ThreadOut {
  // per-span COLUMNS (SoA): a SpanRec is ~200 B of mostly naming svs
  // that die the moment the shape interns — pushing whole records wrote
  // 4x the bytes the pipeline ever reads back, and the assemble phase
  // then re-gathered ids/parents into flat vectors anyway
  std::vector<sv> ids;
  std::vector<sv> parents;
  std::vector<uint8_t> hasp;
  std::vector<int8_t> kind;
  std::vector<double> latency_ms;
  std::vector<double> timestamp_raw;
  std::vector<int32_t> trace_of;   // GLOBAL kept-group index
  std::vector<int32_t> shape_id;   // local shape ids
  std::vector<int32_t> status_id;  // local status ids
  std::vector<uint64_t> id_hash;   // per-row span-id hash (fold reuses)
  std::vector<int32_t> parent_idx; // chunk-local resolution; -2 = retry
  ShapeTable shapes;
  std::vector<sv> statuses;
  Arena arena;
  // chunk-private span-id table + intra-chunk duplicate claims, built
  // during the parallel phase by finish_chunk (zero shared state)
  FlatIdTable tab;
  std::vector<std::pair<int64_t, int32_t>> local_dups;
  uint32_t worker = 0;  // which work-stealing worker parsed this chunk
  bool ok = true;
  uint64_t busy_us = 0;
  uint64_t done_us = 0;  // graftprof: when this chunk's parse finished
  uint64_t intern_probes = 0, intern_hits = 0;  // graftprof intern stats

  size_t size() const { return ids.size(); }

  void reserve(size_t n);  // via zip_span_cols below
};

// THE one enumeration of the per-span columns, generic over the two
// structs that carry them (ThreadOut and Assembled share member names):
// every bulk operation — reserve, move, cross-struct copy, last-wins
// fixup, compaction — instantiates this, so a new column added to the
// structs can never be silently missed at one of the sites.
template <typename A, typename B, typename F>
void zip_span_cols(A& a, B& b, F&& f) {
  f(a.ids, b.ids);
  f(a.parents, b.parents);
  f(a.hasp, b.hasp);
  f(a.kind, b.kind);
  f(a.latency_ms, b.latency_ms);
  f(a.timestamp_raw, b.timestamp_raw);
  f(a.trace_of, b.trace_of);
  f(a.shape_id, b.shape_id);
  f(a.status_id, b.status_id);
  f(a.id_hash, b.id_hash);
  f(a.parent_idx, b.parent_idx);
}

inline void ThreadOut::reserve(size_t n) {
  zip_span_cols(*this, *this, [n](auto& c, auto&) { c.reserve(n); });
}

// direct-mapped shape-id cache: most windows carry a few hundred distinct
// shapes but EVERY span pays the 7-string shape_hash without it. The cache
// indexes on a 2-string hash (name+url distinguish almost all shapes) and
// verifies with full shape_eq, so it is purely an optimization.
struct ShapeCache {
  // 32k slots: the BASELINE production shape carries ~10k distinct
  // endpoints per window — a 2k cache thrashed (~80% miss measured via
  // gprof), sending every miss through the table probe. 32k direct-
  // mapped (384 KiB, L2-resident) keeps the hit rate high at 10k+
  // distinct shapes while staying cheap to reset.
  static constexpr size_t kSize = 32768;
  struct Entry {
    uint64_t h2 = 0;
    int32_t id = -1;
  };
  std::vector<Entry> entries{kSize};
};

// shape + status intern + column push for ONE span record — the single
// emission path shared by the JSON scanner and the columnar-frame decoder
// (bit-exact parity between the two wire formats rides on this being the
// only place a row enters the thread-local tables). The (big) span-id
// table is deferred to the prefetched finish_chunk phase.
inline void emit_span(ThreadOut* to, const SpanRec& rec, int32_t global_group,
                      SvMap& status_map, sv& last_status,
                      int32_t& last_status_id, ShapeCache& shape_cache) {
  bool ins;
  Shape sh;
  sh.f[0] = rec.name;
  sh.f[1] = rec.url;
  sh.f[2] = rec.method;
  sh.f[3] = rec.svc;
  sh.f[4] = rec.ns;
  sh.f[5] = rec.rev;
  sh.f[6] = rec.mesh;
  sh.key_present = rec.present & kKeyBits;
  sh.url_present = rec.url_present ? 1 : 0;
  int32_t sid = -1;
  // identical to shape_hash(sh): the cache key IS the table hash, so
  // a miss reuses it and never re-hashes the long fields
  uint64_t h2 = hash_sv(rec.name) * 31 + hash_sv(rec.url) +
                (rec.present & kKeyBits);
  ShapeCache::Entry& ce =
      shape_cache.entries[h2 & (ShapeCache::kSize - 1)];
  if (ce.h2 == h2 && ce.id >= 0 &&
      shape_eq(to->shapes.shapes[ce.id], sh)) {
    sid = ce.id;
  } else {
    sid = to->shapes.intern(sh, h2);
    ce.h2 = h2;
    ce.id = sid;
  }
  Shape& stored = to->shapes.shapes[sid];
  double ts_ms = rec.timestamp_raw / 1000.0;
  if (!stored.has_ts || ts_ms > stored.max_ts_ms) {
    stored.max_ts_ms = ts_ms;
    stored.has_ts = true;
  }
  sv st = rec.status_present ? rec.status : sv("", 0);
  int32_t stid;
  if (last_status_id >= 0 && st == last_status) {
    stid = last_status_id;
  } else {
    stid = status_map.intern(st, static_cast<int32_t>(to->statuses.size()),
                             &ins);
    if (ins) to->statuses.push_back(st);
    last_status = st;
    last_status_id = stid;
  }
  to->ids.push_back(rec.id);
  to->parents.push_back(rec.parent_id);
  to->hasp.push_back(rec.has_parent ? 1 : 0);
  to->kind.push_back(rec.kind);
  to->latency_ms.push_back(rec.latency_ms);
  to->timestamp_raw.push_back(rec.timestamp_raw);
  to->trace_of.push_back(global_group);
  to->shape_id.push_back(sid);
  to->status_id.push_back(stid);
}

// parse the spans of one kept group into `to` (local tables)
bool parse_group_spans(Scanner& s, int32_t global_group, ThreadOut* to,
                       KeyPredictor& span_pred, KeyPredictor& tag_pred,
                       SvMap& status_map, sv& last_status,
                       int32_t& last_status_id, ShapeCache& shape_cache) {
  if (!s.eat('[')) return false;
  bool first_span = true;
  while (s.ok) {
    s.ws();
    if (s.peek(']')) {
      ++s.p;
      return true;
    }
    if (!first_span && !s.eat(',')) return false;
    first_span = false;
    SpanRec rec;
    if (!parse_span(s, &rec, span_pred, tag_pred)) return false;
    emit_span(to, rec, global_group, status_map, last_status,
              last_status_id, shape_cache);
  }
  return s.ok;
}

// walk the top-level array: dedup groups in document order. When
// `inline_out` is non-null (sequential mode) kept groups parse immediately
// (single pass); otherwise their byte ranges are recorded for the workers.
struct PrescanResult {
  std::vector<GroupRange> kept;
  bool ok = false;
};

// fast path for the worker mode: ONE branchless structural pass finds all
// group ranges (scan_group_ranges), gaps are validated to be exactly the
// separating commas (so malformed non-array elements still fail like the
// sequential walk), then only each group's head is probed for its traceId
PrescanResult prescan_fast(const char* json, size_t json_len,
                           const std::vector<std::pair<sv, bool>>& skip,
                           Arena* arena, const SkipSet* ss = nullptr) {
  PrescanResult out;
  std::vector<std::pair<size_t, size_t>> ranges;
  size_t top_open, top_close;
  if (!scan_group_ranges(json, json_len, &ranges, &top_open, &top_close))
    return out;
  if (!validate_group_gaps(json, ranges, top_open, top_close)) return out;
  if (ranges.empty()) {
    out.ok = true;
    return out;
  }

  SvMap seen(skip.size() + 64);
  bool ins;
  for (auto& e : skip)
    seen.intern(e.second ? e.first : kNoneSentinel, 1, &ins);
  for (auto& r : ranges) {
    Scanner probe{json + r.first, json + r.second, arena};
    probe.eat('[');
    probe.ws();
    if (probe.peek(']')) continue;  // empty group: skipped, not registered
    sv tid;
    bool tid_present = false;
    if (!peek_trace_id(probe, &tid, &tid_present)) return out;
    sv seen_key = tid_present ? tid : kNoneSentinel;
    if (seen.find(seen_key) != nullptr ||
        (ss != nullptr && ss->contains(seen_key)))
      continue;
    seen.intern(seen_key, 1, &ins);
    out.kept.push_back(
        GroupRange{json + r.first, json + r.second, tid, tid_present});
  }
  out.ok = true;
  return out;
}

PrescanResult prescan(const char* json, size_t json_len,
                      const std::vector<std::pair<sv, bool>>& skip,
                      Arena* arena, ThreadOut* inline_out,
                      const SkipSet* ss = nullptr) {
  PrescanResult out;
  Scanner s{json, json + json_len, arena};
  SvMap seen(skip.size() + 64);
  bool ins;
  for (auto& e : skip)
    seen.intern(e.second ? e.first : kNoneSentinel, 1, &ins);

  KeyPredictor span_pred, tag_pred;
  SvMap status_map(64);
  sv last_status;
  int32_t last_status_id = -1;
  auto shape_cache = std::make_unique<ShapeCache>();
  if (inline_out) {
    inline_out->reserve(json_len / 400 + 16);
  }

  if (!s.eat('[')) return out;
  bool first_group = true;
  while (s.ok) {
    s.ws();
    if (s.peek(']')) {
      ++s.p;
      break;
    }
    if (!first_group && !s.eat(',')) return out;
    first_group = false;
    s.ws();
    if (!s.peek('[')) return out;
    {
      Scanner probe = s;
      probe.eat('[');
      probe.ws();
      if (probe.peek(']')) {
        ++probe.p;
        s = probe;  // empty group: skipped, not registered
        continue;
      }
    }
    sv tid;
    bool tid_present = false;
    {
      Scanner probe = s;
      probe.eat('[');
      if (!peek_trace_id(probe, &tid, &tid_present)) return out;
    }
    sv seen_key = tid_present ? tid : kNoneSentinel;
    if (seen.find(seen_key) != nullptr ||
        (ss != nullptr && ss->contains(seen_key))) {
      s.skip_value();  // whole group already processed
      if (!s.ok) return out;
      continue;
    }
    seen.intern(seen_key, 1, &ins);
    int32_t gidx = static_cast<int32_t>(out.kept.size());
    const char* gbegin = s.p;
    if (inline_out) {
      if (!parse_group_spans(s, gidx, inline_out, span_pred, tag_pred,
                             status_map, last_status, last_status_id,
                             *shape_cache))
        return out;
      out.kept.push_back(GroupRange{gbegin, s.p, tid, tid_present});
    } else {
      s.skip_value();
      if (!s.ok) return out;
      out.kept.push_back(GroupRange{gbegin, s.p, tid, tid_present});
    }
  }
  out.ok = s.ok;
  return out;
}

// -- persistent parse session (km_session_* C API) --------------------------
// Cross-call shape/status tables: a chunked stream re-encounters the same
// ~10k naming shapes on every page, and re-serializing + re-decoding +
// re-resolving them per chunk cost more host time than the parse's own
// scanning at production endpoint diversity. A session interns shapes and
// statuses into PERSISTENT tables (field bytes deep-copied into the
// session arena — the input json buffer dies with the call), emits spans
// with session-global ids, and serializes only the shapes/statuses the
// consumer has not yet acknowledged (km_session_ack): the warm-path
// payload carries zero shape strings. The ack is explicit so a consumer
// that rejects a payload (e.g. invalid UTF-8 in a field) simply never
// acks — the next parse re-emits the unacknowledged tail.
struct ParseSession {
  std::mutex mu;  // one parse at a time per session
  Arena arena;
  ShapeTable shapes;
  std::vector<double> shape_max_ts;  // cumulative per-shape max (ms)
  std::vector<uint8_t> shape_has_ts;
  SvMap status_map{64};
  std::vector<sv> statuses;
  size_t shapes_acked = 0;
  size_t statuses_acked = 0;

  sv copy_sv(sv s) {
    if (s.empty()) return sv("", 0);
    char* mem = arena.alloc(s.size());
    std::memcpy(mem, s.data(), s.size());
    return sv(mem, s.size());
  }

  // intern a window-local shape; deep-copies on first sight
  int32_t adopt(const Shape& local) {
    uint64_t h = shape_hash(local);
    int32_t before = static_cast<int32_t>(shapes.shapes.size());
    int32_t gid = shapes.intern(local, h);
    if (gid >= before) {
      // freshly inserted: the stored svs still point at the caller's
      // buffer — replace them with arena copies (the table's hash only
      // covers f[0]/f[1]/bits, which copy to identical bytes, so slot
      // hashes stay valid)
      Shape& stored = shapes.shapes[gid];
      for (int i = 0; i < kShapeFields; ++i) stored.f[i] = copy_sv(stored.f[i]);
      shape_max_ts.push_back(0.0);
      shape_has_ts.push_back(0);
    }
    if (local.has_ts &&
        (!shape_has_ts[gid] || local.max_ts_ms > shape_max_ts[gid])) {
      shape_max_ts[gid] = local.max_ts_ms;
      shape_has_ts[gid] = 1;
    }
    return gid;
  }

  int32_t adopt_status(sv st) {
    const int32_t* hit = status_map.find(st);
    if (hit != nullptr) return *hit;
    sv copy = copy_sv(st);
    bool ins;
    int32_t gid =
        status_map.intern(copy, static_cast<int32_t>(statuses.size()), &ins);
    if (ins) statuses.push_back(copy);
    return gid;
  }
};

// -- phase 2: parallel group parsing ----------------------------------------

// build the chunk-private span-id table and resolve same-chunk parents —
// all inside the parallel phase, zero shared state. Every row keeps its
// id hash (id_hash column) so the assemble fold never re-hashes, and a
// parent that resolves inside its own chunk (the overwhelming case: a
// parent lives in its own trace group, and groups never split across
// chunks) skips the global table entirely. parent_idx -2 marks the rare
// cross-chunk reference the assemble phase retries against the folded
// table.
void finish_chunk(ThreadOut* to) {
  size_t cnt = to->size();
  to->id_hash.resize(cnt);
  to->parent_idx.assign(cnt, -1);
  to->tab.init(cnt);
  if (cnt == 0) return;
  const sv* ids = to->ids.data();
  uint64_t* hs = to->id_hash.data();
  for (size_t b = 0; b < cnt; b += kPrefetchBlock) {
    size_t e = b + kPrefetchBlock < cnt ? b + kPrefetchBlock : cnt;
    for (size_t i = b; i < e; ++i) {
      hs[i] = SvMap::key_hash(ids[i]);
      __builtin_prefetch(&to->tab.hashes[hs[i] & to->tab.mask], 1, 1);
    }
    for (size_t i = b; i < e; ++i) {
      int64_t slot =
          to->tab.insert(ids[i], hs[i], static_cast<int32_t>(i), ids);
      if (slot >= 0)
        to->local_dups.emplace_back(slot, static_cast<int32_t>(i));
    }
  }
  const sv* parents = to->parents.data();
  const uint8_t* hasp = to->hasp.data();
  uint64_t phash[kPrefetchBlock];
  for (size_t b = 0; b < cnt; b += kPrefetchBlock) {
    size_t e = b + kPrefetchBlock < cnt ? b + kPrefetchBlock : cnt;
    for (size_t i = b; i < e; ++i) {
      if (!hasp[i]) {
        phash[i - b] = 0;
        continue;
      }
      phash[i - b] = SvMap::key_hash(parents[i]);
      __builtin_prefetch(&to->tab.hashes[phash[i - b] & to->tab.mask], 0, 1);
    }
    for (size_t i = b; i < e; ++i) {
      if (!hasp[i]) continue;
      int32_t r = to->tab.find(parents[i], phash[i - b], ids);
      to->parent_idx[i] = r >= 0 ? r : -2;
    }
  }
}

void parse_range(const std::vector<GroupRange>& kept, size_t g0, size_t g1,
                 ThreadOut* to) {
  uint64_t t0 = now_us();
  KeyPredictor span_pred, tag_pred;
  SvMap status_map(64);
  sv last_status;
  int32_t last_status_id = -1;
  auto shape_cache = std::make_unique<ShapeCache>();
  size_t bytes = 0;
  for (size_t g = g0; g < g1; ++g)
    bytes += static_cast<size_t>(kept[g].end - kept[g].begin);
  to->reserve(bytes / 400 + 16);
  for (size_t g = g0; g < g1; ++g) {
    Scanner s{kept[g].begin, kept[g].end, &to->arena};
    if (!parse_group_spans(s, static_cast<int32_t>(g), to, span_pred,
                           tag_pred, status_map, last_status,
                           last_status_id, *shape_cache)) {
      to->ok = false;
      break;
    }
  }
  if (to->ok) finish_chunk(to);
  to->intern_probes += status_map.probes;
  to->intern_hits += status_map.hits;
  to->done_us = now_us();
  to->busy_us = to->done_us - t0;
}

// -- assembled result (pre-serialization) -----------------------------------

struct Assembled {
  size_t n = 0;
  // flat per-span columns, document order (moved/copied from ThreadOut)
  std::vector<sv> ids;
  std::vector<sv> parents;
  std::vector<uint8_t> hasp;
  std::vector<int8_t> kind;
  std::vector<double> latency_ms;
  std::vector<double> timestamp_raw;
  std::vector<int32_t> trace_of;
  std::vector<int32_t> shape_id;   // global ids
  std::vector<int32_t> status_id;  // global ids
  std::vector<uint64_t> id_hash;   // per-row span-id hash (from the chunks)
  std::vector<int32_t> parent_idx;
  ShapeTable shapes;        // global
  std::vector<sv> statuses;  // global

  // adapters over the single zip_span_cols enumeration
  template <typename F>
  void span_cols(F&& f) {
    zip_span_cols(*this, *this, [&f](auto& c, auto&) { f(c); });
  }

  template <typename F>
  void zip_cols(ThreadOut& t, F&& f) {
    zip_span_cols(*this, t, std::forward<F>(f));
  }
  std::vector<GroupRange> kept;
  bool ok = false;
  uint32_t prescan_us = 0, parse_us = 0, merge_us = 0;
  uint32_t threads = 1;
};

// merge chunk outputs + fold span tables + dedup fixup + parents.
// `outs` holds one ThreadOut per work-stealing CHUNK (ascending document
// order); `n_workers` is the worker-thread count and `worker_done` (when
// non-empty) each worker's barrier-arrival timestamp for the graftprof
// skew accounting. `outs` rows are consumed (moved into the flat arrays).
void assemble(std::vector<ThreadOut>& outs, PrescanResult&& ps,
              Assembled* as, unsigned n_workers,
              const std::vector<uint64_t>& worker_done) {
  uint64_t m0 = now_us();
  as->kept = std::move(ps.kept);

  size_t n = 0;
  for (auto& t : outs) n += t.size();
  as->n = n;

  // graftprof: fold each chunk's shape-table probe stats into its
  // ThreadOut — and pin its span count — before the columns/tables
  // move/merge below (the single-chunk path moves them out wholesale)
  std::vector<uint64_t> shard_sizes(outs.size(), 0);
  for (size_t ti = 0; ti < outs.size(); ++ti) {
    ThreadOut& t = outs[ti];
    shard_sizes[ti] = t.size();
    t.intern_probes += t.shapes.probes;
    t.intern_hits += t.shapes.hits;
    // zero the table's own stats so a move into as->shapes (single-chunk
    // path) can't double-count them in the final flush
    t.shapes.probes = t.shapes.hits = 0;
  }

  if (outs.size() == 1) {
    // single worker: its tables ARE the global tables (ids assigned in
    // document order already) -- move, don't copy the span columns
    ThreadOut& t = outs[0];
    as->zip_cols(t, [](auto& dst, auto& src) { dst = std::move(src); });
    as->shapes = std::move(t.shapes);
    as->statuses = std::move(t.statuses);
  } else {
    // global shape/status tables in document order (threads own
    // contiguous document ranges, merged ascending -> first-appearance
    // order matches the sequential scan); the tables are small, so this
    // stays sequential
    std::vector<std::vector<int32_t>> shape_remaps(outs.size());
    std::vector<std::vector<int32_t>> status_remaps(outs.size());
    {
      SvMap status_map(64);
      bool ins;
      for (size_t ti = 0; ti < outs.size(); ++ti) {
        auto& t = outs[ti];
        shape_remaps[ti].resize(t.shapes.shapes.size());
        for (size_t i = 0; i < t.shapes.shapes.size(); ++i) {
          const Shape& sh = t.shapes.shapes[i];
          int32_t gid = as->shapes.intern(sh);
          Shape& stored = as->shapes.shapes[gid];
          if (sh.has_ts &&
              (!stored.has_ts || sh.max_ts_ms > stored.max_ts_ms)) {
            stored.max_ts_ms = sh.max_ts_ms;
            stored.has_ts = true;
          }
          shape_remaps[ti][i] = gid;
        }
        status_remaps[ti].resize(t.statuses.size());
        for (size_t i = 0; i < t.statuses.size(); ++i) {
          int32_t gid = status_map.intern(
              t.statuses[i], static_cast<int32_t>(as->statuses.size()),
              &ins);
          if (ins) as->statuses.push_back(t.statuses[i]);
          status_remaps[ti][i] = gid;
        }
      }
    }

    // the document-order column copy parallelizes: each worker owns a
    // disjoint slice (bases from the prefix sum), remapping shape /
    // status ids in place after the raw copy
    as->span_cols([n](auto& c) { c.resize(n); });
    std::vector<size_t> bases(outs.size() + 1, 0);
    for (size_t ti = 0; ti < outs.size(); ++ti)
      bases[ti + 1] = bases[ti] + outs[ti].size();
    auto copy_slice = [&](size_t ti) {
      auto& t = outs[ti];
      size_t base = bases[ti];
      const auto& shape_remap = shape_remaps[ti];
      const auto& status_remap = status_remaps[ti];
      size_t cnt = t.size();
      as->zip_cols(t, [base](auto& dst, auto& src) {
        std::copy(src.begin(), src.end(), dst.begin() + base);
      });
      for (size_t i = 0; i < cnt; ++i) {
        as->shape_id[base + i] = shape_remap[as->shape_id[base + i]];
        as->status_id[base + i] = status_remap[as->status_id[base + i]];
        // chunk-local parent rows shift by the chunk's document base
        // (-1 absent and -2 retry-globally pass through unchanged)
        if (as->parent_idx[base + i] >= 0)
          as->parent_idx[base + i] += static_cast<int32_t>(base);
      }
    };
    if (n < 4096) {  // small windows: spawn cost dwarfs the copy
      for (size_t ti = 0; ti < outs.size(); ++ti) copy_slice(ti);
    } else {
      std::vector<std::thread> ths;
      for (size_t ti = 1; ti < outs.size(); ++ti)
        if (outs[ti].size()) ths.emplace_back(copy_slice, ti);
      copy_slice(0);
      for (auto& th : ths) th.join();
    }
  }

  // the table phases read the assembled columns directly
  std::vector<sv>& ids = as->ids;
  std::vector<sv>& parents = as->parents;
  std::vector<uint8_t>& hasp = as->hasp;

  // single-pass fold of the per-chunk id tables into one flat table: no
  // atomics, no CAS, no spin-waits. The parallel phase already hashed
  // every id (id_hash column) and detected intra-chunk duplicates, so
  // the fold is one sequential prefetched insert per row in document
  // order; a collision here IS a cross-chunk duplicate. With a single
  // chunk the chunk table simply becomes the global table.
  uint64_t f0 = now_us();
  FlatIdTable table;
  std::vector<std::pair<int64_t, int32_t>> dups;
  if (outs.size() == 1) {
    table = std::move(outs[0].tab);
    dups = std::move(outs[0].local_dups);
  } else {
    table.init(n);
    const uint64_t* hs = as->id_hash.data();
    const sv* idp = ids.data();
    for (size_t b = 0; b < n; b += kPrefetchBlock) {
      size_t e = b + kPrefetchBlock < n ? b + kPrefetchBlock : n;
      for (size_t i = b; i < e; ++i)
        __builtin_prefetch(&table.hashes[hs[i] & table.mask], 1, 1);
      for (size_t i = b; i < e; ++i) {
        int64_t slot =
            table.insert(idp[i], hs[i], static_cast<int32_t>(i), idp);
        if (slot >= 0) dups.emplace_back(slot, static_cast<int32_t>(i));
      }
    }
  }
  uint64_t fold_us = now_us() - f0;

  // duplicate fixup in document order: first position survives, last
  // written fields win, later rows die
  std::vector<uint8_t> dead;
  std::vector<int32_t> winner_pre;  // dead pre-compaction row -> winner row
  std::vector<int32_t> remap;       // pre- -> post-compaction rows
  bool had_duplicates = !dups.empty();
  if (had_duplicates) {
    dead.assign(n, 0);
    winner_pre.assign(n, -1);
    // gather claimants per slot
    std::vector<std::pair<int64_t, int32_t>> all = dups;
    for (auto& d : dups) all.emplace_back(d.first, table.rows[d.first]);
    std::sort(all.begin(), all.end());
    all.erase(std::unique(all.begin(), all.end()), all.end());
    size_t i = 0;
    while (i < all.size()) {
      size_t j = i;
      int32_t first = all[i].second, last = all[i].second;
      while (j < all.size() && all[j].first == all[i].first) {
        first = std::min(first, all[j].second);
        last = std::max(last, all[j].second);
        ++j;
      }
      // survivor keeps its position/trace_of; fields come from the last
      for (size_t k = i; k < j; ++k)
        if (all[k].second != first) {
          dead[all[k].second] = 1;
          winner_pre[all[k].second] = first;
        }
      if (last != first) {
        // survivor keeps its position and GROUP; every other field
        // comes from the last occurrence (JS-Map last-wins)
        int32_t keep_group = as->trace_of[first];
        as->span_cols([&](auto& c) { c[first] = c[last]; });
        as->trace_of[first] = keep_group;
      }
      table.rows[all[i].first] = first;
      i = j;
    }
    // compaction: drop dead rows (renumbers everything after them)
    remap.assign(n, -1);
    size_t w = 0;
    for (size_t r = 0; r < n; ++r) {
      if (dead[r]) continue;
      remap[r] = static_cast<int32_t>(w);
      if (w != r) {
        as->span_cols([&](auto& c) { c[w] = c[r]; });
      }
      ++w;
    }
    as->span_cols([w](auto& c) { c.resize(w); });
    as->n = w;
    n = w;
    // rebuild table rows through the remap
    for (size_t s2 = 0; s2 <= table.mask; ++s2) {
      int32_t r = table.rows[s2];
      if (r >= 0) table.rows[s2] = remap[r];
    }
    // last-wins overwrites may have left shape/status tables holding
    // values seen only in dead records; rebuild over the FINAL rows
    // (same rare path as the sequential scan). Shape identity rides the
    // old ids — a row's old shape_id denotes exactly the fields the old
    // intern saw — and per-shape max_ts re-accumulates from surviving
    // rows only (a dead-record timestamp must not linger).
    ShapeTable old_shapes = std::move(as->shapes);
    std::vector<sv> old_statuses = std::move(as->statuses);
    as->shapes = ShapeTable();
    as->statuses.clear();
    SvMap rebuilt_status(64);
    bool ins;
    for (size_t r = 0; r < n; ++r) {
      Shape clean = old_shapes.shapes[as->shape_id[r]];
      clean.has_ts = false;
      clean.max_ts_ms = 0.0;
      int32_t sid = as->shapes.intern(clean);
      as->shape_id[r] = sid;
      Shape& stored = as->shapes.shapes[sid];
      double ts_ms = as->timestamp_raw[r] / 1000.0;
      if (!stored.has_ts || ts_ms > stored.max_ts_ms) {
        stored.max_ts_ms = ts_ms;
        stored.has_ts = true;
      }
      sv st = old_statuses[as->status_id[r]];
      int32_t stid = rebuilt_status.intern(
          st, static_cast<int32_t>(as->statuses.size()), &ins);
      if (ins) as->statuses.push_back(st);
      as->status_id[r] = stid;
    }
  }

  // parent fixup: chunk-local resolutions reference pre-compaction rows;
  // route them through the remap (a resolution landing on a dead
  // duplicate redirects to that id's survivor — exactly what a global
  // lookup would have returned)
  if (had_duplicates) {
    for (size_t r = 0; r < n; ++r) {
      int32_t p = as->parent_idx[r];
      if (p < 0) continue;
      int32_t p2 = remap[p];
      if (p2 < 0) p2 = remap[winner_pre[p]];
      as->parent_idx[r] = p2;
    }
  }
  // the rare cross-chunk references (-2: parent id absent from its own
  // chunk) retry against the folded table — ~0 rows in practice, since
  // a parent lives inside its own trace group
  for (size_t r = 0; r < n; ++r) {
    if (as->parent_idx[r] != -2) continue;
    uint64_t h = SvMap::key_hash(parents[r]);
    as->parent_idx[r] =
        hasp[r] ? table.find(parents[r], h, ids.data()) : -1;
  }

  as->ok = true;
  as->merge_us = static_cast<uint32_t>(now_us() - m0);

  // graftprof flush: one locked update per parse. Per-shard "merge
  // lock-wait" is the barrier skew — how long each finished WORKER sat
  // at the assemble barrier for the slowest one. Chunks aggregate onto
  // their owning worker; with work-stealing the skew is bounded by one
  // chunk's wall, so this plane reads ~0 on a balanced window (zero in
  // sequential mode, where worker_done carries no timestamps).
  {
    std::vector<uint64_t> wbusy(n_workers, 0), wspans(n_workers, 0);
    std::vector<uint64_t> wdone(n_workers, 0);
    for (size_t wi = 0; wi < worker_done.size() && wi < wdone.size(); ++wi)
      wdone[wi] = worker_done[wi];
    for (size_t ti = 0; ti < outs.size(); ++ti) {
      uint32_t wi = outs[ti].worker < n_workers ? outs[ti].worker : 0;
      wbusy[wi] += outs[ti].busy_us;
      wspans[wi] += shard_sizes[ti];
      if (worker_done.empty())
        wdone[wi] = std::max(wdone[wi], outs[ti].done_us);
    }
    uint64_t done_max = 0;
    for (uint64_t d : wdone) done_max = std::max(done_max, d);
    std::lock_guard<std::mutex> g(g_prof.mu);
    g_prof.parses += 1;
    g_prof.spans += n;
    g_prof.merge_ns += static_cast<uint64_t>(as->merge_us) * 1000;
    g_prof.fold_ns += fold_us * 1000;
    g_prof.fold_chunks += outs.size();
    g_prof.intern_probes += as->shapes.probes;
    g_prof.intern_hits += as->shapes.hits;
    for (auto& t : outs) {
      g_prof.intern_probes += t.intern_probes;
      g_prof.intern_hits += t.intern_hits;
    }
    uint64_t pending = n_workers;
    if (pending > g_prof.merge_queue_depth_peak)
      g_prof.merge_queue_depth_peak = pending;
    g_prof.shards_used =
        static_cast<uint32_t>(std::min<uint32_t>(n_workers, kProfMaxShards));
    for (uint32_t ti = 0; ti < kProfMaxShards; ++ti) {
      if (ti < n_workers) {
        uint64_t wait_us =
            (wdone[ti] != 0 && done_max > wdone[ti]) ? done_max - wdone[ti]
                                                     : 0;
        g_prof.shard_parse_ns[ti] = wbusy[ti] * 1000;
        g_prof.shard_wait_ns[ti] = wait_us * 1000;
        g_prof.shard_spans[ti] = wspans[ti];
        g_prof.merge_lock_wait_ns += wait_us * 1000;
      } else {
        g_prof.shard_parse_ns[ti] = 0;
        g_prof.shard_wait_ns[ti] = 0;
        g_prof.shard_spans[ti] = 0;
      }
    }
  }
}

unsigned pick_threads(int requested) {
  if (requested > 0) return static_cast<unsigned>(std::min(requested, 64));
  unsigned hw = std::thread::hardware_concurrency();
  return hw ? std::min(hw, 16u) : 1u;
}

// header packing for the threads+merge_us field: 7 bits of thread count
// (pick_threads caps at 64) + 25 bits of microseconds (~33 s cap)
constexpr uint32_t kMergeUsBits = 25;
constexpr uint32_t kMergeUsMask = (1u << kMergeUsBits) - 1;

// -- columnar wire frame ("KMZC") -------------------------------------------
// Compact SoA binary frame emitted by the Envoy WASM filter so production
// ingest skips Zipkin JSON entirely (docs/INGEST_WIRE.md is the spec;
// kmamiz_tpu/core/wire.py carries the reference Python codec). Layout
// (little-endian):
//   0  "KMZC"          magic
//   4  u8  version     (1)
//   5  u8  flags       (0, reserved)
//   6  u16 reserved    (0)
//   8  u32 body_len    byte length of everything after the 16-byte header
//   12 u32 crc32(body) IEEE polynomial (zlib.crc32 / Go hash/crc32)
//   16 body:
//     u32 n_strings, then per string u32 len + bytes (the string table)
//     u32 n_groups,  then per group i32 tid_sid (-1 = absent) + u32 n_spans
//     u32 n_spans_total, then fixed-width SoA columns, each n_spans_total
//     entries in document order:
//       i32 id_sid, i32 parent_sid, i32 name_sid, i32 url_sid,
//       i32 method_sid, i32 svc_sid, i32 ns_sid, i32 rev_sid, i32 mesh_sid,
//       i32 status_sid, i8 kind (0 | 1 SERVER | 2 CLIENT), i64 timestamp_us,
//       i64 duration_us
// A sid of -1 means the field is ABSENT (distinct from an empty string,
// matching the JSON path's presence bits). Any malformed byte — bad magic,
// unknown version, short body, CRC mismatch, out-of-range sid, bad kind —
// rejects the whole frame (nullptr return -> quarantine), exactly like
// malformed JSON.

constexpr uint32_t kColMagic = 0x435A4D4B;  // "KMZC" read as LE u32
constexpr uint8_t kColVersion = 1;

// work-stealing chunk granularity: chunks-per-worker factor (default 4;
// KMAMIZ_PARSE_SHARDS through the Python binding's km_set_parse_shards).
// Higher = finer stealing = lower barrier skew, at slightly more
// per-chunk table/fold overhead.
std::atomic<int> g_chunk_factor{4};

struct Crc32Table {
  uint32_t t[256];
  Crc32Table() {
    for (uint32_t i = 0; i < 256; ++i) {
      uint32_t c = i;
      for (int k = 0; k < 8; ++k)
        c = (c & 1) ? 0xEDB88320u ^ (c >> 1) : c >> 1;
      t[i] = c;
    }
  }
};

uint32_t crc32_ieee(const uint8_t* p, size_t n) {
  static const Crc32Table tab;  // magic-static: thread-safe init
  uint32_t c = 0xFFFFFFFFu;
  for (size_t i = 0; i < n; ++i) c = tab.t[(c ^ p[i]) & 0xFF] ^ (c >> 8);
  return c ^ 0xFFFFFFFFu;
}

struct ColReader {
  const uint8_t* p;
  const uint8_t* end;
  bool ok = true;
  size_t left() const { return static_cast<size_t>(end - p); }
  bool need(size_t n) {
    if (left() < n) ok = false;
    return ok;
  }
  uint32_t u32() {
    if (!need(4)) return 0;
    uint32_t v;
    std::memcpy(&v, p, 4);
    p += 4;
    return v;
  }
  const uint8_t* bytes(size_t n) {
    if (!need(n)) return nullptr;
    const uint8_t* q = p;
    p += n;
    return q;
  }
};

// decode one columnar frame into the SAME assembled result the JSON
// pipeline produces: rows route through emit_span (shared with the JSON
// scanner), group dedup mirrors prescan (intra-payload seen set + skip
// table/SkipSet, kNoneSentinel for absent trace ids, empty groups skipped
// unregistered), and the output serializes through the unchanged v1 /
// session wire — so JSON and columnar ingest are bit-exact by
// construction, not by parallel implementations.
bool parse_columnar_window(const char* buf, size_t len,
                           const std::vector<std::pair<sv, bool>>& skip,
                           const SkipSet* ss, std::vector<ThreadOut>& outs,
                           Assembled* as) {
  uint64_t p0 = now_us();
  if (len < 16) return false;
  const uint8_t* u = reinterpret_cast<const uint8_t*>(buf);
  if (u[4] != kColVersion || u[5] != 0) return false;
  uint32_t body_len, crc;
  std::memcpy(&body_len, u + 8, 4);
  std::memcpy(&crc, u + 12, 4);
  if (static_cast<size_t>(body_len) + 16 != len) return false;
  if (crc32_ieee(u + 16, body_len) != crc) return false;

  ColReader r{u + 16, u + len};
  uint32_t n_strings = r.u32();
  if (!r.ok || n_strings > r.left() / 4) return false;
  std::vector<sv> strs;
  strs.reserve(n_strings);
  for (uint32_t i = 0; i < n_strings; ++i) {
    uint32_t sl = r.u32();
    const uint8_t* q = r.bytes(sl);
    if (!r.ok) return false;
    strs.push_back(sv(reinterpret_cast<const char*>(q), sl));
  }
  int64_t nstr = static_cast<int64_t>(n_strings);

  uint32_t n_groups = r.u32();
  if (!r.ok || n_groups > r.left() / 8) return false;
  std::vector<std::pair<int32_t, uint32_t>> groups;
  groups.reserve(n_groups);
  uint64_t span_sum = 0;
  for (uint32_t g = 0; g < n_groups; ++g) {
    int32_t tid_sid = static_cast<int32_t>(r.u32());
    uint32_t cnt = r.u32();
    if (tid_sid < -1 || tid_sid >= nstr) return false;
    groups.emplace_back(tid_sid, cnt);
    span_sum += cnt;
  }
  uint32_t n_total = r.u32();
  if (!r.ok || span_sum != n_total) return false;
  // fixed-width columns: 10 x i32 + 1 x i8 + 2 x i64 = 57 bytes per span,
  // and they must consume the body EXACTLY (no trailing garbage)
  if (r.left() != static_cast<size_t>(n_total) * 57) return false;
  const uint8_t* col_i32[10];
  for (int c = 0; c < 10; ++c)
    col_i32[c] = r.bytes(static_cast<size_t>(n_total) * 4);
  const uint8_t* col_kind = r.bytes(n_total);
  const uint8_t* col_ts = r.bytes(static_cast<size_t>(n_total) * 8);
  const uint8_t* col_dur = r.bytes(static_cast<size_t>(n_total) * 8);
  if (!r.ok) return false;

  auto rd_i32 = [](const uint8_t* col, size_t i) {
    int32_t v;
    std::memcpy(&v, col + i * 4, 4);
    return v;
  };
  auto rd_i64 = [](const uint8_t* col, size_t i) {
    int64_t v;
    std::memcpy(&v, col + i * 8, 8);
    return v;
  };
  // validate every sid/kind up front (skipped groups included): a frame
  // either decodes whole or rejects whole
  for (int c = 0; c < 10; ++c)
    for (size_t i = 0; i < n_total; ++i) {
      int32_t v = rd_i32(col_i32[c], i);
      if (v < -1 || v >= nstr) return false;
    }
  for (size_t i = 0; i < n_total; ++i)
    if (col_kind[i] > 2) return false;
  auto sid_sv = [&](int32_t sid) { return sid >= 0 ? strs[sid] : sv("", 0); };

  outs.resize(1);
  ThreadOut* to = &outs[0];
  to->reserve(n_total);
  PrescanResult ps;
  SvMap seen(skip.size() + 64);
  bool ins;
  for (auto& e : skip)
    seen.intern(e.second ? e.first : kNoneSentinel, 1, &ins);
  SvMap status_map(64);
  sv last_status;
  int32_t last_status_id = -1;
  auto shape_cache = std::make_unique<ShapeCache>();

  size_t row = 0;
  for (auto& gr : groups) {
    size_t base = row;
    uint32_t cnt = gr.second;
    row += cnt;
    if (cnt == 0) continue;  // empty group: skipped, not registered
    bool tid_present = gr.first >= 0;
    sv tid = tid_present ? strs[gr.first] : sv("", 0);
    sv seen_key = tid_present ? tid : kNoneSentinel;
    if (seen.find(seen_key) != nullptr ||
        (ss != nullptr && ss->contains(seen_key)))
      continue;  // whole group already processed
    seen.intern(seen_key, 1, &ins);
    int32_t gidx = static_cast<int32_t>(ps.kept.size());
    ps.kept.push_back(GroupRange{buf, buf, tid, tid_present});
    for (size_t i = base; i < base + cnt; ++i) {
      SpanRec rec;
      rec.id = sid_sv(rd_i32(col_i32[0], i));
      int32_t sid = rd_i32(col_i32[1], i);
      rec.has_parent = sid >= 0;
      rec.parent_id = sid_sv(sid);
      rec.name = sid_sv(rd_i32(col_i32[2], i));
      sid = rd_i32(col_i32[3], i);
      rec.url_present = sid >= 0;
      rec.url = sid_sv(sid);
      sid = rd_i32(col_i32[4], i);
      if (sid >= 0) rec.present |= kHasMethod;
      rec.method = sid_sv(sid);
      sid = rd_i32(col_i32[5], i);
      if (sid >= 0) rec.present |= kHasSvc;
      rec.svc = sid_sv(sid);
      sid = rd_i32(col_i32[6], i);
      if (sid >= 0) rec.present |= kHasNs;
      rec.ns = sid_sv(sid);
      sid = rd_i32(col_i32[7], i);
      if (sid >= 0) rec.present |= kHasRev;
      rec.rev = sid_sv(sid);
      sid = rd_i32(col_i32[8], i);
      if (sid >= 0) rec.present |= kHasMesh;
      rec.mesh = sid_sv(sid);
      sid = rd_i32(col_i32[9], i);
      rec.status_present = sid >= 0;
      rec.status = sid_sv(sid);
      rec.kind = static_cast<int8_t>(col_kind[i]);
      rec.timestamp_raw = static_cast<double>(rd_i64(col_ts, i));
      rec.latency_ms = static_cast<double>(rd_i64(col_dur, i)) / 1000.0;
      emit_span(to, rec, gidx, status_map, last_status, last_status_id,
                *shape_cache);
    }
  }
  finish_chunk(to);
  to->intern_probes += status_map.probes;
  to->intern_hits += status_map.hits;
  ps.ok = true;
  as->prescan_us = 0;
  as->parse_us = static_cast<uint32_t>(now_us() - p0);
  assemble(outs, std::move(ps), as, 1, {});
  return as->ok;
}

bool parse_pipeline(const char* json, size_t json_len,
                    const std::vector<std::pair<sv, bool>>& skip,
                    Arena* arena, std::vector<ThreadOut>& outs,
                    Assembled* as, int n_threads_req,
                    const SkipSet* ss = nullptr) {
  // columnar fast path: EVERY entry point (blob / skipset / session)
  // accepts "KMZC" frames through the same funnel — a JSON body can
  // never start with 'K', so the magic is unambiguous
  if (json_len >= 4) {
    uint32_t m;
    std::memcpy(&m, json, 4);
    if (m == kColMagic) {
      as->threads = 1;  // one sequential decode pass (no JSON to scan)
      return parse_columnar_window(json, json_len, skip, ss, outs, as);
    }
  }
  unsigned n_threads = pick_threads(n_threads_req);
  as->threads = n_threads;

  uint64_t p0 = now_us();
  if (n_threads <= 1) {
    // sequential mode: single fused pass (no separate prescan walk)
    outs.resize(1);
    PrescanResult ps = prescan(json, json_len, skip, arena, &outs[0], ss);
    if (!ps.ok || !outs[0].ok) return false;
    finish_chunk(&outs[0]);  // id table + local parents, still parse time
    as->prescan_us = 0;
    as->parse_us = static_cast<uint32_t>(now_us() - p0);
    assemble(outs, std::move(ps), as, 1, {});
    return as->ok;
  }

  PrescanResult ps = prescan_fast(json, json_len, skip, arena, ss);
  if (!ps.ok) return false;
  uint64_t p1 = now_us();
  as->prescan_us = static_cast<uint32_t>(p1 - p0);

  // contiguous, byte-balanced group ranges preserve document order.
  // Work-stealing: ~4 chunks per worker claimed off a shared cursor, so
  // the barrier skew (graftprof "merge lock-wait") is bounded by ONE
  // chunk's wall instead of one worker's whole range — a worker that
  // drew cheap groups steals the tail instead of idling at the barrier.
  size_t total_bytes = 0;
  for (auto& g : ps.kept)
    total_bytes += static_cast<size_t>(g.end - g.begin);
  size_t n_groups = ps.kept.size();
  unsigned workers =
      static_cast<unsigned>(std::min<size_t>(n_threads, n_groups ? n_groups : 1));
  size_t factor = static_cast<size_t>(
      std::max(1, g_chunk_factor.load(std::memory_order_relaxed)));
  size_t n_chunks = std::min<size_t>(
      std::min<size_t>(static_cast<size_t>(workers) * factor,
                       n_groups ? n_groups : 1),
      kProfMaxShards);
  if (n_chunks < workers) n_chunks = workers;
  outs.resize(n_chunks);
  std::vector<size_t> cuts(n_chunks + 1, n_groups);
  cuts[0] = 0;
  size_t acc = 0, w = 1;
  size_t per = total_bytes / n_chunks + 1;
  for (size_t g = 0; g < n_groups && w < n_chunks; ++g) {
    acc += static_cast<size_t>(ps.kept[g].end - ps.kept[g].begin);
    if (acc >= per * w) cuts[w++] = g + 1;
  }
  std::atomic<size_t> cursor{0};
  std::vector<uint64_t> worker_done(workers, 0);
  auto worker_fn = [&](unsigned wi) {
    for (;;) {
      size_t c = cursor.fetch_add(1, std::memory_order_relaxed);
      if (c >= n_chunks) break;
      outs[c].worker = wi;
      if (cuts[c] < cuts[c + 1])
        parse_range(ps.kept, cuts[c], cuts[c + 1], &outs[c]);
    }
    worker_done[wi] = now_us();
  };
  std::vector<std::thread> ths;
  for (unsigned t = 1; t < workers; ++t) ths.emplace_back(worker_fn, t);
  worker_fn(0);
  for (auto& th : ths) th.join();
  for (auto& t : outs)
    if (!t.ok) return false;
  std::vector<uint64_t> wbusy(workers, 0);
  for (auto& t : outs)
    wbusy[t.worker < workers ? t.worker : 0] += t.busy_us;
  uint64_t busy_max = 0;
  for (uint64_t b : wbusy) busy_max = std::max(busy_max, b);
  as->parse_us = static_cast<uint32_t>(busy_max);

  assemble(outs, std::move(ps), as, workers, worker_done);
  return as->ok;
}

inline void put_u32(std::vector<uint8_t>& b, uint32_t v) {
  b.push_back(v & 0xFF);
  b.push_back((v >> 8) & 0xFF);
  b.push_back((v >> 16) & 0xFF);
  b.push_back((v >> 24) & 0xFF);
}

inline void put_sv(std::vector<uint8_t>& b, sv s) {
  put_u32(b, static_cast<uint32_t>(s.size()));
  b.insert(b.end(), s.begin(), s.end());
}

unsigned char* serialize(const Assembled& as, size_t* out_len) {
  size_t n = as.n;
  size_t n_shapes = as.shapes.shapes.size();

  // exact size up front: one malloc, one pass, no vector regrow + final
  // copy (the output is ~35 MB at 1M spans)
  size_t sz = 32 + n * (8 + 8 + 4 + 4 + 4 + 4 + 1) + n_shapes * 8;
  for (const Shape& sh : as.shapes.shapes) {
    sz += 2 + kShapeFields * 4;
    for (int i = 0; i < kShapeFields; ++i) sz += sh.f[i].size();
  }
  for (sv st : as.statuses) sz += 4 + st.size();
  for (auto& g : as.kept) sz += 5 + g.tid.size();

  unsigned char* buf = static_cast<unsigned char*>(std::malloc(sz));
  if (buf == nullptr) return nullptr;
  unsigned char* w = buf;
  auto w_u32 = [&](uint32_t v) {
    std::memcpy(w, &v, 4);
    w += 4;
  };
  auto w_sv = [&](sv s) {
    w_u32(static_cast<uint32_t>(s.size()));
    if (!s.empty()) std::memcpy(w, s.data(), s.size());
    w += s.size();
  };

  w_u32(1);  // ok
  w_u32(static_cast<uint32_t>(n));
  w_u32(static_cast<uint32_t>(n_shapes));
  w_u32(static_cast<uint32_t>(as.statuses.size()));
  w_u32(static_cast<uint32_t>(as.kept.size()));
  w_u32(as.prescan_us);
  w_u32(as.parse_us);
  w_u32((as.threads << kMergeUsBits) |
        std::min(as.merge_us, kMergeUsMask));

  if (n) {
    std::memcpy(w, as.latency_ms.data(), n * 8);
    std::memcpy(w + n * 8, as.timestamp_raw.data(), n * 8);
  }
  w += n * 16;
  for (size_t i = 0; i < n_shapes; ++i) {
    std::memcpy(w, &as.shapes.shapes[i].max_ts_ms, 8);
    w += 8;
  }
  if (n) std::memcpy(w, as.parent_idx.data(), n * 4);
  w += n * 4;
  if (n) std::memcpy(w, as.shape_id.data(), n * 4);
  w += n * 4;
  if (n) std::memcpy(w, as.status_id.data(), n * 4);
  w += n * 4;
  if (n) std::memcpy(w, as.trace_of.data(), n * 4);
  w += n * 4;
  if (n) std::memcpy(w, as.kind.data(), n);
  w += n;
  for (const Shape& sh : as.shapes.shapes) {
    *w++ = sh.url_present;
    *w++ = sh.key_present;
    for (int i = 0; i < kShapeFields; ++i) w_sv(sh.f[i]);
  }
  for (sv st : as.statuses) w_sv(st);
  for (size_t g = 0; g < as.kept.size(); ++g) {
    *w++ = as.kept[g].tid_present ? 1 : 0;
    w_sv(as.kept[g].tid);
  }

  *out_len = static_cast<size_t>(w - buf);
  return buf;
}

// session wire format (header ok=2): span columns carry session-global
// ids; shape strings emit ONLY for shapes the consumer has not acked
// (warm chunks: none). shape_max_ts is the session's cumulative
// per-shape max — equivalent for the consumer's freshest-timestamp
// logic, which is a monotone max.
unsigned char* serialize_session(const Assembled& as, const ParseSession& ss,
                                 size_t* out_len) {
  size_t n = as.n;
  size_t shapes_total = ss.shapes.shapes.size();
  size_t statuses_total = ss.statuses.size();
  size_t shape_base = ss.shapes_acked;
  size_t status_base = ss.statuses_acked;

  size_t sz = 40 + n * (8 + 8 + 4 + 4 + 4 + 4 + 1) + shapes_total * 8;
  for (size_t i = shape_base; i < shapes_total; ++i) {
    sz += 2 + kShapeFields * 4;
    for (int f = 0; f < kShapeFields; ++f) sz += ss.shapes.shapes[i].f[f].size();
  }
  for (size_t i = status_base; i < statuses_total; ++i)
    sz += 4 + ss.statuses[i].size();
  // kept section: presence + length ARRAYS (vectorized consumer offsets)
  // followed by the interleaved skip-entry records — the records double
  // as the consumer's incremental dedup-blob append, byte-identical to
  // encode_skip_entry layout
  for (auto& g : as.kept) sz += 1 + 4 + 5 + g.tid.size();

  unsigned char* buf = static_cast<unsigned char*>(std::malloc(sz));
  if (buf == nullptr) return nullptr;
  unsigned char* w = buf;
  auto w_u32 = [&](uint32_t v) {
    std::memcpy(w, &v, 4);
    w += 4;
  };
  auto w_sv = [&](sv s) {
    w_u32(static_cast<uint32_t>(s.size()));
    if (!s.empty()) std::memcpy(w, s.data(), s.size());
    w += s.size();
  };

  w_u32(2);  // ok marker doubles as the format version
  w_u32(static_cast<uint32_t>(n));
  w_u32(static_cast<uint32_t>(shapes_total));
  w_u32(static_cast<uint32_t>(statuses_total));
  w_u32(static_cast<uint32_t>(shape_base));
  w_u32(static_cast<uint32_t>(status_base));
  w_u32(static_cast<uint32_t>(as.kept.size()));
  w_u32(as.prescan_us);
  w_u32(as.parse_us);
  w_u32((as.threads << kMergeUsBits) | std::min(as.merge_us, kMergeUsMask));

  if (n) {
    std::memcpy(w, as.latency_ms.data(), n * 8);
    std::memcpy(w + n * 8, as.timestamp_raw.data(), n * 8);
  }
  w += n * 16;
  if (shapes_total) std::memcpy(w, ss.shape_max_ts.data(), shapes_total * 8);
  w += shapes_total * 8;
  if (n) std::memcpy(w, as.parent_idx.data(), n * 4);
  w += n * 4;
  if (n) std::memcpy(w, as.shape_id.data(), n * 4);
  w += n * 4;
  if (n) std::memcpy(w, as.status_id.data(), n * 4);
  w += n * 4;
  if (n) std::memcpy(w, as.trace_of.data(), n * 4);
  w += n * 4;
  if (n) std::memcpy(w, as.kind.data(), n);
  w += n;
  for (size_t i = shape_base; i < shapes_total; ++i) {
    const Shape& sh = ss.shapes.shapes[i];
    *w++ = sh.url_present;
    *w++ = sh.key_present;
    for (int f = 0; f < kShapeFields; ++f) w_sv(sh.f[f]);
  }
  for (size_t i = status_base; i < statuses_total; ++i) w_sv(ss.statuses[i]);
  for (size_t g = 0; g < as.kept.size(); ++g)
    *w++ = as.kept[g].tid_present ? 1 : 0;
  for (size_t g = 0; g < as.kept.size(); ++g)
    w_u32(static_cast<uint32_t>(as.kept[g].tid.size()));
  for (size_t g = 0; g < as.kept.size(); ++g) {
    *w++ = as.kept[g].tid_present ? 1 : 0;
    w_sv(as.kept[g].tid);
  }

  *out_len = static_cast<size_t>(w - buf);
  return buf;
}

}  // namespace

extern "C" {

// skip_blob: u32 n_skip then per entry u8 present + u32 len + bytes.
// json: the raw Zipkin response, passed separately so the (large) buffer
// crosses the ctypes boundary without a copy. n_threads: 0 = auto
// (hardware concurrency, capped at 16), else the exact worker count.
unsigned char* km_parse_spans_mt(const char* skip_blob, size_t skip_len,
                                 const char* json, size_t json_len,
                                 int n_threads, size_t* out_len) {
  *out_len = 0;
  if (skip_len < 4) return nullptr;
  const uint8_t* q = reinterpret_cast<const uint8_t*>(skip_blob);
  uint32_t n_skip;
  std::memcpy(&n_skip, q, 4);
  size_t pos = 4;
  std::vector<std::pair<sv, bool>> skip;
  skip.reserve(n_skip);
  for (uint32_t i = 0; i < n_skip; ++i) {
    if (pos + 5 > skip_len) return nullptr;
    bool present = q[pos] != 0;
    uint32_t len;
    std::memcpy(&len, q + pos + 1, 4);
    pos += 5;
    if (pos + len > skip_len) return nullptr;
    skip.emplace_back(sv(skip_blob + pos, len), present);
    pos += len;
  }

  Arena arena;
  std::vector<ThreadOut> outs;
  Assembled as;
  if (!parse_pipeline(json, json_len, skip, &arena, outs, &as, n_threads))
    return nullptr;
  return serialize(as, out_len);
}

// -- persistent skip-set handle (see SkipSet above) -------------------------

void* km_skipset_new() { return new (std::nothrow) SkipSet(); }

void km_skipset_free(void* h) { delete static_cast<SkipSet*>(h); }

long long km_skipset_extend(void* h, const char* entries, size_t len) {
  if (h == nullptr) return -1;
  return static_cast<SkipSet*>(h)->extend(entries, len);
}

void km_skipset_clear(void* h) {
  if (h != nullptr) static_cast<SkipSet*>(h)->clear();
}

unsigned long long km_skipset_size(void* h) {
  if (h == nullptr) return 0;
  SkipSet* ss = static_cast<SkipSet*>(h);
  std::lock_guard<std::mutex> g(ss->mu);
  return ss->count;
}

// parse against a persistent skip set INSTEAD of a per-call blob: the
// set is consulted read-only (kept ids do NOT auto-register — the
// caller registers after the fact, preserving the blob path's
// at-least-once semantics and its ordering with the dedup lock).
unsigned char* km_parse_spans_hs(void* h, const char* json, size_t json_len,
                                 int n_threads, size_t* out_len) {
  *out_len = 0;
  static const std::vector<std::pair<sv, bool>> kNoSkip;
  Arena arena;
  std::vector<ThreadOut> outs;
  Assembled as;
  if (!parse_pipeline(json, json_len, kNoSkip, &arena, outs, &as, n_threads,
                      static_cast<const SkipSet*>(h)))
    return nullptr;
  return serialize(as, out_len);
}

// -- persistent parse session (see ParseSession above) ----------------------

void* km_session_new() { return new (std::nothrow) ParseSession(); }

void km_session_free(void* h) { delete static_cast<ParseSession*>(h); }

// consumer acknowledges it decoded shapes/statuses up to these counts;
// until then every parse re-emits the unacked tail (monotone)
void km_session_ack(void* h, uint32_t shapes_known, uint32_t statuses_known) {
  ParseSession* sess = static_cast<ParseSession*>(h);
  if (sess == nullptr) return;
  std::lock_guard<std::mutex> g(sess->mu);
  sess->shapes_acked =
      std::min<size_t>(std::max<size_t>(sess->shapes_acked, shapes_known),
                       sess->shapes.shapes.size());
  sess->statuses_acked =
      std::min<size_t>(std::max<size_t>(sess->statuses_acked, statuses_known),
                       sess->statuses.size());
}

// session parse: window-local tables remap onto the session's persistent
// ones, spans emit session-global ids, and only unacked shape/status
// strings serialize (format ok=2). skip_h may be null.
unsigned char* km_parse_spans_sess(void* sess_h, void* skip_h,
                                   const char* json, size_t json_len,
                                   int n_threads, size_t* out_len) {
  *out_len = 0;
  ParseSession* sess = static_cast<ParseSession*>(sess_h);
  if (sess == nullptr) return nullptr;
  std::lock_guard<std::mutex> g(sess->mu);
  static const std::vector<std::pair<sv, bool>> kNoSkip;
  Arena arena;
  std::vector<ThreadOut> outs;
  Assembled as;
  if (!parse_pipeline(json, json_len, kNoSkip, &arena, outs, &as, n_threads,
                      static_cast<const SkipSet*>(skip_h)))
    return nullptr;
  std::vector<int32_t> shape_remap(as.shapes.shapes.size());
  for (size_t i = 0; i < as.shapes.shapes.size(); ++i)
    shape_remap[i] = sess->adopt(as.shapes.shapes[i]);
  std::vector<int32_t> status_remap(as.statuses.size());
  for (size_t i = 0; i < as.statuses.size(); ++i)
    status_remap[i] = sess->adopt_status(as.statuses[i]);
  for (size_t i = 0; i < as.n; ++i) {
    as.shape_id[i] = shape_remap[as.shape_id[i]];
    as.status_id[i] = status_remap[as.status_id[i]];
  }
  return serialize_session(as, *sess, out_len);
}

unsigned char* km_parse_spans(const char* skip_blob, size_t skip_len,
                              const char* json, size_t json_len,
                              size_t* out_len) {
  return km_parse_spans_mt(skip_blob, skip_len, json, json_len, 0, out_len);
}

// capability probe for the Python binding: bit 0 = columnar ("KMZC")
// frames accepted by every parse entry point. A stale prebuilt .so
// missing this symbol predates the columnar wire — the binding then
// transcodes frames to Zipkin JSON in Python before parsing.
unsigned int km_wire_caps() { return 1u; }

// KMAMIZ_PARSE_SHARDS: work-stealing chunks-per-worker factor (1..64)
void km_set_parse_shards(int factor) {
  if (factor >= 1 && factor <= 64)
    g_chunk_factor.store(factor, std::memory_order_relaxed);
}

// -- graftprof counter snapshot ---------------------------------------------
// Wire (little-endian, km_free to release):
//   u32 version, u32 shards_used,
//   u64 parses, spans, merge_ns, merge_lock_wait_ns,
//       merge_queue_depth_peak, claim_contended, intern_probes, intern_hits,
//       fold_ns, fold_chunks,                      (v2+)
//   then shards_used * (u64 parse_ns, u64 wait_ns, u64 spans)
unsigned char* km_prof_snapshot(size_t* out_len) {
  *out_len = 0;
  std::lock_guard<std::mutex> g(g_prof.mu);
  size_t sz = 8 + 8 * 10 + static_cast<size_t>(g_prof.shards_used) * 24;
  unsigned char* buf = static_cast<unsigned char*>(std::malloc(sz));
  if (buf == nullptr) return nullptr;
  unsigned char* w = buf;
  auto w_u32 = [&](uint32_t v) {
    std::memcpy(w, &v, 4);
    w += 4;
  };
  auto w_u64 = [&](uint64_t v) {
    std::memcpy(w, &v, 8);
    w += 8;
  };
  w_u32(kProfWireVersion);
  w_u32(g_prof.shards_used);
  w_u64(g_prof.parses);
  w_u64(g_prof.spans);
  w_u64(g_prof.merge_ns);
  w_u64(g_prof.merge_lock_wait_ns);
  w_u64(g_prof.merge_queue_depth_peak);
  w_u64(g_prof.claim_contended);
  w_u64(g_prof.intern_probes);
  w_u64(g_prof.intern_hits);
  w_u64(g_prof.fold_ns);
  w_u64(g_prof.fold_chunks);
  for (uint32_t ti = 0; ti < g_prof.shards_used; ++ti) {
    w_u64(g_prof.shard_parse_ns[ti]);
    w_u64(g_prof.shard_wait_ns[ti]);
    w_u64(g_prof.shard_spans[ti]);
  }
  *out_len = sz;
  return buf;
}

void km_prof_reset() {
  std::lock_guard<std::mutex> g(g_prof.mu);
  g_prof.parses = 0;
  g_prof.spans = 0;
  g_prof.merge_ns = 0;
  g_prof.merge_lock_wait_ns = 0;
  g_prof.merge_queue_depth_peak = 0;
  g_prof.claim_contended = 0;
  g_prof.intern_probes = 0;
  g_prof.intern_hits = 0;
  g_prof.fold_ns = 0;
  g_prof.fold_chunks = 0;
  g_prof.shards_used = 0;
  for (uint32_t ti = 0; ti < kProfMaxShards; ++ti) {
    g_prof.shard_parse_ns[ti] = 0;
    g_prof.shard_wait_ns[ti] = 0;
    g_prof.shard_spans[ti] = 0;
  }
}

// group-aligned split points for streaming ingest: walks the top-level
// array (string-aware) and emits <= n_chunks byte ranges, each covering
// whole trace groups. Output: u32 n_ranges, then per range u64 begin,
// u64 end (offsets into json — u64 because the uncapped ingest path can
// legitimately carry >4 GiB bodies; each json[begin:end] re-wraps as
// "[" + groups + "]" on the Python side). Returns nullptr on malformed
// input.
unsigned char* km_split_groups(const char* json, size_t json_len,
                               int n_chunks, size_t* out_len) {
  *out_len = 0;
  if (n_chunks < 1) n_chunks = 1;
  std::vector<std::pair<size_t, size_t>> ranges;
  size_t top_open, top_close;
  if (!scan_group_ranges(json, json_len, &ranges, &top_open, &top_close))
    return nullptr;
  if (!validate_group_gaps(json, ranges, top_open, top_close)) return nullptr;
  std::vector<std::pair<uint64_t, uint64_t>> groups;
  groups.reserve(ranges.size());
  for (auto& r : ranges)
    groups.emplace_back(static_cast<uint64_t>(r.first),
                        static_cast<uint64_t>(r.second));

  size_t per = (groups.size() + n_chunks - 1) /
               static_cast<size_t>(n_chunks);
  if (per == 0) per = 1;
  std::vector<uint8_t> out;
  size_t n_ranges = groups.empty() ? 0 : (groups.size() + per - 1) / per;
  put_u32(out, static_cast<uint32_t>(n_ranges));
  auto put_u64 = [&](uint64_t v) {
    for (int b = 0; b < 8; ++b) out.push_back((v >> (8 * b)) & 0xFF);
  };
  for (size_t i = 0; i < groups.size(); i += per) {
    size_t j = std::min(groups.size(), i + per);
    put_u64(groups[i].first);
    put_u64(groups[j - 1].second);
  }
  unsigned char* buf = static_cast<unsigned char*>(std::malloc(out.size()));
  if (buf == nullptr) return nullptr;
  std::memcpy(buf, out.data(), out.size());
  *out_len = out.size();
  return buf;
}

}  // extern "C"
