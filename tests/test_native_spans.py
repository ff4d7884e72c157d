"""Native raw-JSON span loader parity (VERDICT r1 #1).

raw_spans_to_batch (native/kmamiz_spans.cpp) must be byte-identical to
spans_to_batch(json.loads(raw)) composed with DataProcessor._filter_traces
dedup semantics — same arrays, same interner tables, same endpoint infos —
on the reference's captured fixtures, on synthetic windows, and under fuzz.
"""
from __future__ import annotations

import json
import random

import numpy as np
import pytest

from conftest import load_fixture

from kmamiz_tpu import native
from kmamiz_tpu.core.interning import EndpointInterner, StringInterner
from kmamiz_tpu.core.spans import raw_spans_to_batch, spans_to_batch

pytestmark = pytest.mark.skipif(
    not native.available(), reason="native extension unavailable"
)

ARRAY_FIELDS = [
    "valid",
    "kind",
    "parent_idx",
    "endpoint_id",
    "service_id",
    "rt_endpoint_id",
    "rt_service_id",
    "status_id",
    "status_class",
    "latency_ms",
    "timestamp_us",
    "timestamp_rel",
    "trace_of",
]


def assert_batches_equal(host, nat):
    assert host.n_spans == nat.n_spans
    assert host.ts_base_us == nat.ts_base_us
    for f in ARRAY_FIELDS:
        a, b = getattr(host, f), getattr(nat, f)
        assert np.array_equal(a, b), f"{f}: {a} != {b}"
    assert host.interner.endpoints.strings == nat.interner.endpoints.strings
    assert host.interner.services.strings == nat.interner.services.strings
    assert (
        host.interner.endpoint_service_ids == nat.interner.endpoint_service_ids
    )
    assert host.statuses.strings == nat.statuses.strings
    assert host.endpoint_infos == nat.endpoint_infos


def roundtrip(groups, **kw):
    """Run both paths over the same window and compare."""
    raw = json.dumps(groups).encode()
    host = spans_to_batch(groups, **kw)
    out = raw_spans_to_batch(raw, **kw)
    assert out is not None
    nat, kept = out
    assert_batches_equal(host, nat)
    return nat, kept


class TestFixtureParity:
    @pytest.mark.parametrize(
        "fixture", ["pdas_traces", "pdas2_traces", "bookinfo_traces"]
    )
    def test_reference_fixtures(self, fixture):
        data = load_fixture(fixture)
        # pdas fixtures are one trace group; bookinfo is a list of groups
        groups = data if isinstance(data[0], list) else [data]
        roundtrip(groups)

    def test_sequential_windows_share_interner(self):
        # two ticks over a persistent interner (the production graph-merge
        # usage): both paths must grow the tables identically
        hi, hs = EndpointInterner(), StringInterner()
        ni, ns = EndpointInterner(), StringInterner()
        for fixture in ["pdas_traces", "pdas2_traces"]:
            groups = [load_fixture(fixture)]
            host = spans_to_batch(groups, interner=hi, statuses=hs)
            nat, _ = raw_spans_to_batch(
                json.dumps(groups).encode(), interner=ni, statuses=ns
            )
            assert_batches_equal(host, nat)


def mk_span(tid, sid, parent=None, **over):
    """Module-level span factory shared by the dedup/MT/stream tests."""
    s = {
        "traceId": tid,
        "id": sid,
        "parentId": parent,
        "kind": "SERVER",
        "name": "svc.ns.svc.cluster.local:80/*",
        "timestamp": 1_700_000_000_000_000,
        "duration": 1000,
        "tags": {
            "http.method": "GET",
            "http.status_code": "200",
            "http.url": "http://svc.ns.svc.cluster.local/api",
            "istio.canonical_revision": "v1",
            "istio.canonical_service": "svc",
            "istio.mesh_id": "cluster.local",
            "istio.namespace": "ns",
        },
    }
    s.update(over)
    return s


class TestDedupSemantics:
    def mk_span(self, tid, sid, parent=None, **over):
        return mk_span(tid, sid, parent, **over)

    def test_skip_set_drops_groups(self):
        g1 = [self.mk_span("t1", "a")]
        g2 = [self.mk_span("t2", "b")]
        raw = json.dumps([g1, g2]).encode()
        nat, kept = raw_spans_to_batch(raw, skip_trace_ids=["t1"])
        assert kept == ["t2"]
        assert nat.n_spans == 1
        # parity: the host path sees only the non-skipped group
        host = spans_to_batch([g2])
        assert_batches_equal(host, nat)

    def test_duplicate_trace_id_in_response(self):
        g1 = [self.mk_span("t1", "a")]
        g2 = [self.mk_span("t1", "b")]  # same trace again -> dropped
        nat, kept = raw_spans_to_batch(json.dumps([g1, g2]).encode())
        assert kept == ["t1"]
        assert nat.n_spans == 1

    def test_missing_trace_id_sentinel(self):
        # _filter_traces: group[0].get("traceId") is None -> registered as
        # None; the SECOND id-less group is skipped
        s1 = self.mk_span("x", "a")
        del s1["traceId"]
        s2 = self.mk_span("x", "b")
        del s2["traceId"]
        nat, kept = raw_spans_to_batch(json.dumps([[s1], [s2]]).encode())
        assert kept == [None]
        assert nat.n_spans == 1
        # and a pre-seeded None skip drops both
        nat2, kept2 = raw_spans_to_batch(
            json.dumps([[s1], [s2]]).encode(), skip_trace_ids=[None]
        )
        assert kept2 == [] and nat2.n_spans == 0

    def test_empty_groups_skip_without_registering(self):
        g = [self.mk_span("t1", "a")]
        nat, kept = raw_spans_to_batch(json.dumps([[], g, []]).encode())
        assert kept == ["t1"]
        assert nat.n_spans == 1
        assert nat.trace_of[0] == 0  # kept-group indexing skips empties

    def test_duplicate_span_ids_last_wins_first_position(self):
        # same span id in two kept groups: JS-Map semantics
        a1 = self.mk_span("t1", "dup", timestamp=1_700_000_000_000_000)
        b = self.mk_span("t1", "other")
        a2 = self.mk_span(
            "t2",
            "dup",
            timestamp=1_700_000_000_500_000,
            tags={
                **a1["tags"],
                "http.status_code": "503",
                "http.url": "http://svc2.ns.svc.cluster.local/other",
                "istio.canonical_service": "svc2",
            },
        )
        groups = [[a1, b], [a2]]
        nat, kept = roundtrip(groups)
        assert kept == ["t1", "t2"]
        assert nat.n_spans == 2
        assert nat.trace_of[0] == 0  # first position kept
        # last-wins values: the 503 status of a2
        assert nat.statuses.lookup(int(nat.status_id[0])) == "503"
        # dead record's status ("200" via a1) still interned through span b;
        # but a value seen ONLY in a dead record must not be interned:
        only_dead = [
            [self.mk_span("u1", "d", tags={**a1["tags"], "http.status_code": "418"})],
            [self.mk_span("u2", "d")],  # overwrites; 418 never survives
        ]
        nat2, _ = roundtrip(only_dead)
        assert "418" not in nat2.statuses.strings

    def test_parent_resolution_across_groups(self):
        g1 = [self.mk_span("t1", "a"), self.mk_span("t1", "b", parent="a")]
        g2 = [self.mk_span("t2", "c", parent="zz")]  # unresolvable
        nat, _ = roundtrip([g1, g2])
        assert nat.parent_idx[1] == 0
        assert nat.parent_idx[2] == -1


class TestJsonEdgeCases:
    def test_escapes_in_strings(self):
        span = {
            "traceId": "esc\\u0074-1",
            "id": "a\\nb",
            "kind": "SERVER",
            "name": "svc.ns.svc.cluster.local:80/\\u002A",
            "timestamp": 1_700_000_000_000_000,
            "duration": 5,
            "tags": {
                "http.url": "http://x/\\uD83D\\uDE00/path",
                "http.method": "GET",
                "http.status_code": "200",
            },
        }
        raw = ("[[" + json.dumps(span).replace("\\\\u", "\\u") + "]]").encode()
        groups = json.loads(raw)
        host = spans_to_batch(groups)
        nat, kept = raw_spans_to_batch(raw)
        assert_batches_equal(host, nat)
        assert kept == [groups[0][0]["traceId"]]

    def test_whitespace_and_number_forms(self):
        raw = b"""[ [ { "traceId" : "t1" , "id" : "a" ,
            "kind" : "SERVER" , "name" : "n" ,
            "timestamp" : 1.7e15 , "duration" : 1500.5 ,
            "tags" : { "http.status_code" : "200" } } ] ]"""
        groups = json.loads(raw)
        host = spans_to_batch(groups)
        nat, _ = raw_spans_to_batch(raw)
        assert_batches_equal(host, nat)

    def test_non_string_tags_and_extra_fields(self):
        span = {
            "traceId": "t1",
            "id": "a",
            "kind": "SERVER",
            "name": "n",
            "timestamp": 1,
            "duration": 2,
            "annotations": [{"timestamp": 5, "value": "x,[]{}\"quote\""}],
            "localEndpoint": {"serviceName": "svc", "port": 80},
            "tags": {"http.status_code": "200", "request_size": "51"},
            "shared": True,
        }
        roundtrip([[span]])

    def test_null_and_missing_parent(self):
        s1 = {"traceId": "t", "id": "a", "parentId": None, "timestamp": 1}
        s2 = {"traceId": "t", "id": "b", "timestamp": 1}
        roundtrip([[s1, s2]])

    def test_structural_chars_inside_skipped_values(self):
        # skipped strings/objects carrying JSON structural characters and
        # escape sequences must not desync the scanner
        span = {
            "traceId": "t1",
            "id": "a",
            "kind": "SERVER",
            "name": "n",
            "timestamp": 5,
            "duration": -1.5e-3,
            "localEndpoint": {"ipv4": "10.0.0.1", "note": '}],[{"id":"fake"}'},
            "annotations": [{"value": 'quote \\" and ]} inside'}],
            "tags": {
                "http.status_code": "200",
                "weird": "[Request a/b/c/d] {not json}",
                "depth": {"a": [{"b": [[]]}]},
            },
        }
        span2 = {"traceId": "t1", "id": "b", "timestamp": 6}
        raw = json.dumps([[span, span2]]).encode()
        groups = json.loads(raw)
        host = spans_to_batch(groups)
        out = raw_spans_to_batch(raw)
        assert out is not None
        assert_batches_equal(host, out[0])

    def test_unicode_separators_and_big_numbers(self):
        span = {
            "traceId": "t sep",
            "id": "x",
            "name": "svc line",
            "timestamp": 9_007_199_254_740_991,  # 2^53-1, exact in double
            "duration": 1e18,  # forces the strtod slow path
            "tags": {"http.url": "http://h/p?q=", "http.status_code": "200"},
        }
        raw = json.dumps([[span]]).encode()
        host = spans_to_batch(json.loads(raw))
        out = raw_spans_to_batch(raw)
        assert out is not None
        assert_batches_equal(host, out[0])

    def test_malformed_returns_none(self):
        assert raw_spans_to_batch(b"[[{") is None
        assert raw_spans_to_batch(b"not json") is None
        assert raw_spans_to_batch(b'[[{"id": }]]') is None

    def test_empty_response(self):
        nat, kept = raw_spans_to_batch(b"[]")
        assert nat.n_spans == 0 and kept == []


class TestRawIngestSurface:
    """The production consumer of the loader: DataProcessor.ingest_raw_window
    + POST /ingest on the DP server (the uncapped scale path)."""

    def test_processor_raw_ingest_feeds_graph_with_dedup(self):
        from kmamiz_tpu.server.processor import DataProcessor

        raw = json.dumps([load_fixture("pdas_traces")]).encode()
        dp = DataProcessor(trace_source=lambda lb, t, lim: [])
        s1 = dp.ingest_raw_window(raw)
        assert s1["spans"] == 8 and s1["traces"] == 1
        assert dp.graph.n_edges > 0
        # same window again: processed-trace dedup drops everything
        s2 = dp.ingest_raw_window(raw)
        assert s2["spans"] == 0 and s2["traces"] == 0

    def test_raw_ingest_then_collect_share_dedup_map(self):
        from kmamiz_tpu.server.processor import DataProcessor

        group = load_fixture("pdas_traces")
        dp = DataProcessor(trace_source=lambda lb, t, lim: [group])
        dp.ingest_raw_window(json.dumps([group]).encode())
        # the realtime tick sees the trace as already processed
        response = dp.collect({"uniqueId": "x", "time": 1646208339000})
        assert response["combined"] == []

    def test_http_ingest_route(self, monkeypatch, tmp_path):
        import urllib.request

        from kmamiz_tpu.server.dp_server import DataProcessorServer
        from kmamiz_tpu.server.processor import DataProcessor

        monkeypatch.setenv("KMAMIZ_QUARANTINE_DIR", str(tmp_path / "q"))
        dp = DataProcessor(trace_source=lambda lb, t, lim: [])
        server = DataProcessorServer(dp, host="127.0.0.1", port=0)
        server.start()
        try:
            raw = json.dumps([load_fixture("pdas_traces")]).encode()
            req = urllib.request.Request(
                f"http://127.0.0.1:{server.port}/ingest", data=raw
            )
            summary = json.loads(urllib.request.urlopen(req).read())
            assert summary["spans"] == 8 and summary["edges"] > 0
            # malformed body -> quarantined, graph untouched, 200
            bad = urllib.request.Request(
                f"http://127.0.0.1:{server.port}/ingest", data=b"nope"
            )
            summary = json.loads(urllib.request.urlopen(bad).read())
            assert summary["quarantined"] == 1 and summary["spans"] == 0
            # with the quarantine disabled, the legacy 400 contract holds
            monkeypatch.setenv("KMAMIZ_QUARANTINE", "0")
            try:
                urllib.request.urlopen(bad)
                raise AssertionError("expected 400")
            except urllib.error.HTTPError as e:
                assert e.code == 400
        finally:
            server.stop()


class TestConcurrentIngest:
    def test_parallel_ingest_and_collect_lose_nothing(self):
        """/ingest backfills race the realtime tick on a ThreadingHTTPServer;
        the dedup map and edge store are lock-protected — no window may
        vanish and every distinct trace is counted exactly once."""
        import threading

        from kmamiz_tpu.server.processor import DataProcessor

        def span(tag, t, j, kind_):
            svc = f"svc{(t + j) % 3}"
            return {
                "traceId": f"{tag}-{t}",
                "id": f"{tag}-{t}-{j}",
                "parentId": f"{tag}-{t}-{j-1}" if j else None,
                "kind": kind_,
                "name": f"{svc}.ns.svc.cluster.local:80/*",
                "timestamp": 1_700_000_000_000_000 + t,
                "duration": 100,
                "tags": {
                    "http.method": "GET",
                    "http.status_code": "200",
                    "http.url": f"http://{svc}.ns.svc.cluster.local/api",
                    "istio.canonical_service": svc,
                    "istio.namespace": "ns",
                    "istio.canonical_revision": "v1",
                },
            }

        def window(tag, n_traces=20):
            # SERVER -> CLIENT -> SERVER chains so every trace yields edges
            return [
                [span(tag, t, 0, "SERVER"), span(tag, t, 1, "CLIENT"),
                 span(tag, t, 2, "SERVER")]
                for t in range(n_traces)
            ]

        dp = DataProcessor(trace_source=lambda lb, t, lim: [])
        totals = []
        errors = []

        def worker(k):
            try:
                for i in range(5):
                    s = dp.ingest_raw_window(
                        json.dumps(window(f"w{k}-{i}")).encode()
                    )
                    totals.append(s["traces"])
            except Exception as e:  # noqa: BLE001
                errors.append(e)

        threads = [threading.Thread(target=worker, args=(k,)) for k in range(4)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert not errors
        assert sum(totals) == 4 * 5 * 20  # every distinct trace counted once
        assert len(dp._processed) == 4 * 5 * 20
        assert dp.graph.n_edges > 0
        # re-ingesting any window is fully deduplicated
        s = dp.ingest_raw_window(json.dumps(window("w0-0")).encode())
        assert s["traces"] == 0


class TestFuzzParity:
    def test_random_windows(self):
        rng = random.Random(7)
        methods = ["GET", "POST", None]
        urls = [
            "http://a.ns.svc.cluster.local/api/v1",
            "http://b.ns2.svc.cluster.local:8080/x?q=1",
            "",
            None,
        ]
        statuses = ["200", "204", "404", "503", None]
        names = ["a.ns.svc.cluster.local:80/*", "static/main.css", ""]
        for trial in range(12):
            groups = []
            for t in range(rng.randint(0, 12)):
                group = []
                ids = []
                for j in range(rng.randint(0, 9)):
                    sid = f"{trial}-{t}-{j}" if rng.random() < 0.9 else "dup"
                    tags = {}
                    for key, choices in [
                        ("http.method", methods),
                        ("http.url", urls),
                        ("http.status_code", statuses),
                        ("istio.canonical_service", ["s1", "s2", None]),
                        ("istio.namespace", ["ns", None]),
                        ("istio.canonical_revision", ["v1", None]),
                        ("istio.mesh_id", ["mesh", None]),
                    ]:
                        v = rng.choice(choices)
                        if v is not None:
                            tags[key] = v
                    span = {
                        "traceId": f"{trial}-t{t}",
                        "id": sid,
                        "kind": rng.choice(["SERVER", "CLIENT", "PRODUCER", None]),
                        "name": rng.choice(names),
                        "timestamp": 1_700_000_000_000_000 + rng.randint(0, 10**9),
                        "duration": rng.randint(0, 10**7),
                        "tags": tags,
                    }
                    if span["kind"] is None:
                        del span["kind"]
                    if ids and rng.random() < 0.5:
                        span["parentId"] = rng.choice(ids + ["missing"])
                    ids.append(sid)
                    group.append(span)
                groups.append(group)
            # host path must see the same group-level dedup the native
            # parser applies
            seen, kept_groups = set(), []
            for g in groups:
                if not g:
                    continue
                tid = g[0].get("traceId")
                if tid in seen:
                    continue
                seen.add(tid)
                kept_groups.append(g)
            raw = json.dumps(groups).encode()
            host = spans_to_batch(kept_groups)
            out = raw_spans_to_batch(raw)
            assert out is not None
            nat, kept = out
            assert kept == [g[0].get("traceId") for g in kept_groups]
            assert_batches_equal(host, nat)

class TestParallelParse:
    """The multi-threaded scan (prescan + worker ranges + atomic span-id
    table + document-order dup fixup) must be byte-identical to the
    sequential single-pass mode."""

    def _compare_outputs(self, raw, skip=()):
        seq = native.parse_spans(raw, list(skip), threads=1)
        mt = native.parse_spans(raw, list(skip), threads=4)
        assert (seq is None) == (mt is None)
        if seq is None:
            return
        for key in (
            "n_spans",
            "shapes",
            "statuses",
            "trace_ids",
        ):
            assert seq[key] == mt[key], key
        for key in (
            "kind",
            "parent_idx",
            "shape_id",
            "status_id",
            "trace_of",
            "latency_ms",
            "timestamp_us",
            "shape_max_ts_ms",
        ):
            assert np.array_equal(seq[key], mt[key]), key
        assert mt["timings"]["threads"] >= 1

    def test_fixtures_mt(self):
        for fixture in ["pdas_traces", "pdas2_traces", "bookinfo_traces"]:
            data = load_fixture(fixture)
            groups = data if isinstance(data[0], list) else [data]
            self._compare_outputs(json.dumps(groups).encode())

    def test_many_groups_with_cross_group_duplicate_ids(self):
        # span id "shared" recurs in far-apart groups: the atomic-table
        # fixup must collapse them first-position/last-wins exactly like
        # the sequential scan, then compact and rebuild tables
        mk = mk_span
        groups = []
        for t in range(40):
            sid = "shared" if t % 7 == 0 else f"s{t}"
            child = mk(f"t{t}", f"c{t}", parent=sid)
            child["duration"] = 1000 + t
            groups.append([mk(f"t{t}", sid, duration=500 + t), child])
        self._compare_outputs(json.dumps(groups).encode())

    def test_skip_set_and_empty_groups_mt(self):
        mk = mk_span
        groups = []
        for t in range(30):
            groups.append([] if t % 5 == 0 else [mk(f"t{t}", f"s{t}")])
            if t % 6 == 0:
                groups.append([mk(f"t{t}", f"dup{t}")])  # dup trace id
        skip = [f"t{t}" for t in range(0, 30, 3)] + [None]
        self._compare_outputs(json.dumps(groups).encode(), skip=skip)

    def test_fuzz_mt(self):
        rng = random.Random(21)
        mk = mk_span
        for trial in range(8):
            groups = []
            for t in range(rng.randint(0, 25)):
                group = []
                for j in range(rng.randint(0, 6)):
                    sid = (
                        rng.choice(["dupA", "dupB"])
                        if rng.random() < 0.15
                        else f"{trial}-{t}-{j}"
                    )
                    over = {"duration": rng.randint(0, 10**6)}
                    if rng.random() < 0.4:
                        over["parentId"] = rng.choice(
                            [f"{trial}-{t}-0", "dupA", "missing"]
                        )
                    group.append(mk(f"{trial}-t{t}", sid, **over))
                groups.append(group)
            self._compare_outputs(json.dumps(groups).encode())


    def test_mt_structural_scan_vs_adversarial_strings(self):
        # strings stuffed with brackets, escaped quotes, and backslash runs:
        # the block-classified prescan must mask them exactly like the
        # sequential scanner
        mk = mk_span
        groups = []
        evil_names = [
            'a]b[c',
            'quote\\"inside',
            'double\\\\backslash"then]bracket'.replace('"', ''),
            'run\\\\\\"x][',
            '[[[]]]',
            'comma,]"like'.replace('"', ''),
        ]
        for t, name in enumerate(evil_names * 5):
            s = mk(f"evil{t}", f"id{t}")
            s["name"] = name
            s["tags"]["http.url"] = f"http://h/[{name}]?q=\\]"
            groups.append([s])
        raw = json.dumps(groups).encode()
        self._compare_outputs(raw)
        # and split_groups agrees with the group count
        chunks = native.split_groups(raw, 5)
        assert chunks is not None
        assert sum(len(json.loads(c)) for c in chunks) == len(groups)

    def test_mt_whitespace_heavy_layout(self):
        mk = mk_span
        groups = [[mk(f"w{t}", f"s{t}")] for t in range(9)]
        pretty = json.dumps(groups, indent=3).encode()
        self._compare_outputs(pretty)

    def test_parity_with_host_under_threads_env(self, monkeypatch):
        # the full raw_spans_to_batch path (naming, interning) with the MT
        # scanner underneath must still match the pure-Python host path
        monkeypatch.setenv("KMAMIZ_PARSE_THREADS", "4")
        data = load_fixture("bookinfo_traces")
        groups = data if isinstance(data[0], list) else [data]
        roundtrip(groups)


class TestStreamingIngest:
    def test_split_groups_covers_whole_groups(self):
        mk = mk_span
        groups = [[mk(f"t{t}", f"s{t}")] for t in range(17)]
        raw = json.dumps(groups).encode()
        chunks = native.split_groups(raw, 4)
        assert chunks is not None
        assert 1 <= len(chunks) <= 4
        total = 0
        for chunk in chunks:
            parsed = json.loads(chunk)  # each chunk is a standalone response
            total += len(parsed)
        assert total == 17

    def test_split_groups_malformed(self):
        assert native.split_groups(b'[[{"truncated', 4) is None

    def test_stream_matches_window_ingest(self):
        from kmamiz_tpu.server.processor import DataProcessor

        mk = mk_span
        groups = []
        for t in range(50):
            parent = mk(f"t{t}", f"p{t}")
            child = mk(
                f"t{t}",
                f"c{t}",
                parent=f"p{t}",
                kind="CLIENT",
                name=f"down{t % 5}.ns.svc.cluster.local:80/*",
            )
            child["tags"]["istio.canonical_service"] = f"down{t % 5}"
            groups.append([parent, child])
        raw = json.dumps(groups).encode()

        one = DataProcessor(trace_source=lambda *a: [], use_device_stats=False)
        whole = one.ingest_raw_window(raw)

        two = DataProcessor(trace_source=lambda *a: [], use_device_stats=False)
        chunks = native.split_groups(raw, 6)
        assert chunks is not None and len(chunks) > 1
        streamed = two.ingest_raw_stream(chunks)

        assert streamed["spans"] == whole["spans"] == 100
        assert streamed["traces"] == whole["traces"] == 50
        assert streamed["edges"] == whole["edges"]
        assert streamed["endpoints"] == whole["endpoints"]
        assert streamed["chunks"] == len(chunks)
        # dedup maps converge: a second pass ingests nothing
        again = two.ingest_raw_stream([raw])
        assert again["spans"] == 0 and again["traces"] == 0

    def test_stream_chunk_detail_accounting(self):
        # the per-chunk phase breakdown the bench's critical-path headline
        # is built from: every chunk reports parse/merge/transfer >= 0,
        # spans sum to the total, and drain_ms is present
        from kmamiz_tpu.server.processor import DataProcessor

        mk = mk_span
        groups = [[mk(f"t{t}", f"s{t}")] for t in range(40)]
        raw = json.dumps(groups).encode()
        chunks = native.split_groups(raw, 4)
        assert chunks is not None and len(chunks) > 1
        dp = DataProcessor(trace_source=lambda *a: [], use_device_stats=False)
        out = dp.ingest_raw_stream(chunks)
        detail = out["chunk_detail"]
        assert len(detail) == out["chunks"]
        assert sum(d["spans"] for d in detail) == out["spans"]
        for d in detail:
            assert d["parse_ms"] >= 0
            assert d["merge_ms"] >= d["transfer_ms"] >= 0
        assert out["drain_ms"] >= 0

    def test_stream_dedup_across_chunks(self):
        from kmamiz_tpu.server.processor import DataProcessor

        mk = mk_span
        # the same trace id appears in chunk 1 and chunk 2: the second
        # occurrence must drop (kept ids register before the next parse)
        c1 = json.dumps([[mk("tX", "a")], [mk("tY", "b")]]).encode()
        c2 = json.dumps([[mk("tX", "c")], [mk("tZ", "d")]]).encode()
        dp = DataProcessor(trace_source=lambda *a: [], use_device_stats=False)
        out = dp.ingest_raw_stream([c1, c2])
        assert out["traces"] == 3
        assert out["spans"] == 3

    def test_stream_span_id_scope_is_per_chunk(self):
        # adversarial: the SAME span ids recur in different trace groups.
        # One-shot ingest collapses them window-wide; the streamed path
        # scopes the span map per chunk (the reference's per-response
        # scope under paginated fetches). Graph results must still agree.
        from kmamiz_tpu.server.processor import DataProcessor

        mk = mk_span
        groups = [[mk(f"t{t}", "sameid")] for t in range(24)]
        raw = json.dumps(groups).encode()

        one = DataProcessor(trace_source=lambda *a: [], use_device_stats=False)
        whole = one.ingest_raw_window(raw)
        two = DataProcessor(trace_source=lambda *a: [], use_device_stats=False)
        chunks = native.split_groups(raw, 4)
        streamed = two.ingest_raw_stream(chunks)

        assert whole["spans"] == 1      # window-wide collapse
        assert streamed["spans"] == 4   # one survivor per chunk
        assert streamed["traces"] == whole["traces"] == 24
        assert streamed["edges"] == whole["edges"]
        assert streamed["endpoints"] == whole["endpoints"]


def test_bracket_balanced_invalid_groups_agree_across_modes():
    """Dropped (dedup-hit) groups are validated to bracket/string balance
    only — in BOTH modes (the sequential walk's skip_value never parsed
    grammar either); kept groups parse fully and reject bad JSON in both."""
    from kmamiz_tpu import native

    # duplicate group is bracket-balanced but grammatically invalid: it is
    # DROPPED by dedup, so both modes accept the payload identically
    dropped_bad = b'[[{"traceId":"t","id":"a"}],[{"traceId":"t"} {"x":1}]]'
    seq = native.parse_spans(dropped_bad, [], threads=1)
    mt = native.parse_spans(dropped_bad, [], threads=4)
    assert seq is not None and mt is not None
    assert seq["n_spans"] == mt["n_spans"] == 1

    # the same malformation in a KEPT group fails in both modes
    kept_bad = b'[[{"traceId":"x"} {"y":1}]]'
    assert native.parse_spans(kept_bad, [], threads=1) is None
    assert native.parse_spans(kept_bad, [], threads=4) is None


def test_mass_duplicate_span_ids_compaction():
    """Stress the document-order dup fixup + compaction: thousands of
    colliding span ids across groups, in both scan modes."""
    from kmamiz_tpu import native

    mk = mk_span
    groups = []
    for t in range(600):
        # every third group reuses one of 50 shared ids -> heavy overflow
        sid = f"shared{t % 50}" if t % 3 == 0 else f"uniq{t}"
        dur = 100 + t
        groups.append([mk(f"t{t}", sid, duration=dur)])
    raw = json.dumps(groups).encode()
    seq = native.parse_spans(raw, [], threads=1)
    mt = native.parse_spans(raw, [], threads=4)
    assert seq is not None and mt is not None
    # 200 shared-id groups collapse to 50 surviving rows + 400 unique
    assert seq["n_spans"] == mt["n_spans"] == 450
    for key in ("latency_ms", "trace_of", "shape_id", "status_id"):
        assert np.array_equal(seq[key], mt[key]), key
    # last-wins: each shared id carries the LAST occurrence's duration
    host = spans_to_batch(_collapse_host(groups))
    assert np.array_equal(seq["latency_ms"], host.latency_ms[: len(seq["latency_ms"])])


def _collapse_host(groups):
    """Host-side model of whole-window span-map semantics: first position,
    last-wins fields."""
    order = []
    by_id = {}
    for g in groups:
        for s in g:
            if s["id"] in by_id:
                by_id[s["id"]] = s
            else:
                by_id[s["id"]] = s
                order.append(s["id"])
    # rebuild one span per surviving id, each in its own group to keep
    # trace_of monotone like the window (one span per group here)
    return [[by_id[i]] for i in order]


def test_mt_large_fuzz_window():
    """A bigger randomized window (10k spans) through both scan modes."""
    from kmamiz_tpu import native

    rng = random.Random(99)
    mk = mk_span
    groups = []
    for t in range(1500):
        n = rng.randint(1, 12)
        group = []
        for j in range(n):
            over = {
                "duration": rng.randint(1, 10**6),
                "kind": rng.choice(["SERVER", "CLIENT", "PRODUCER"]),
            }
            if j and rng.random() < 0.7:
                over["parentId"] = f"{t}-{rng.randrange(j)}"
            s = mk(f"t{t}", f"{t}-{j}", **over)
            s["name"] = f"svc{rng.randrange(40)}.ns{rng.randrange(4)}.svc.cluster.local:80/*"
            s["tags"]["http.url"] = f"http://svc{rng.randrange(40)}/api/{rng.randrange(30)}"
            if rng.random() < 0.1:
                del s["tags"]["http.status_code"]
            group.append(s)
        groups.append(group)
    raw = json.dumps(groups).encode()
    seq = native.parse_spans(raw, [], threads=1)
    mt = native.parse_spans(raw, [], threads=4)
    assert seq is not None and mt is not None
    assert seq["n_spans"] == mt["n_spans"]
    for key in ("kind", "parent_idx", "shape_id", "status_id", "trace_of",
                "latency_ms", "timestamp_us", "shape_max_ts_ms"):
        assert np.array_equal(seq[key], mt[key]), key
    assert seq["shapes"] == mt["shapes"]
    assert seq["statuses"] == mt["statuses"]
    assert seq["trace_ids"] == mt["trace_ids"]


def test_stream_malformed_later_chunk_at_least_once(monkeypatch):
    """ingest_raw_stream's legacy failure semantics (KMAMIZ_QUARANTINE=0):
    a malformed later chunk raises AFTER earlier chunks merged and
    registered (per-chunk at-least-once); the one-shot path stays
    all-or-nothing. With the quarantine enabled (default) the malformed
    chunk diverts instead — pinned in test_resilience.py."""
    from kmamiz_tpu.server.processor import DataProcessor

    monkeypatch.setenv("KMAMIZ_QUARANTINE", "0")
    mk = mk_span
    good = json.dumps([[mk("tA", "a")], [mk("tB", "b")]]).encode()
    bad = b'[[{"traceId": "tC", "id": '  # truncated
    dp = DataProcessor(trace_source=lambda *a: [], use_device_stats=False)
    with pytest.raises(ValueError):
        dp.ingest_raw_stream([good, bad])
    # chunk 1 landed and registered before the failure
    assert dp.graph.interner and len(dp.graph.interner.endpoints) > 0
    with dp._dedup_lock:
        assert "tA" in dp._processed and "tB" in dp._processed

    # one-shot on the same malformed payload: nothing mutates
    dp2 = DataProcessor(trace_source=lambda *a: [], use_device_stats=False)
    with pytest.raises(ValueError):
        dp2.ingest_raw_window(bad)
    with dp2._dedup_lock:
        assert not dp2._processed


def test_fuzz_mutated_bytes_never_crash():
    """Malformed, truncated, byte-flipped, and structural-char-injected
    payloads: both scan modes must return None or a well-formed result —
    never crash — and invalid UTF-8 rejects like the json.loads path."""
    from kmamiz_tpu import native

    rng = random.Random(77)
    base = json.dumps([[mk_span("t1", "a", duration=5)],
                       [mk_span("t2", "b", parent="a")]]).encode()
    for _ in range(300):
        mode = rng.randrange(4)
        if mode == 0:
            buf = bytes(rng.randrange(256) for _ in range(rng.randrange(0, 160)))
        elif mode == 1:
            buf = base[: rng.randrange(len(base) + 1)]
        elif mode == 2:
            b = bytearray(base)
            for _ in range(rng.randrange(1, 6)):
                b[rng.randrange(len(b))] = rng.randrange(256)
            buf = bytes(b)
        else:
            b = bytearray(base)
            for _ in range(rng.randrange(1, 8)):
                b.insert(rng.randrange(len(b)), rng.choice(b'[]{}",\\\x00\x01'))
            buf = bytes(b)
        for threads in (1, 4):
            out = native.parse_spans(buf, ["skip", None], threads=threads)
            if out is not None:
                assert out["n_spans"] == len(out["kind"])

    # the invalid-UTF-8 rejection matches json.loads behavior
    bad_utf8 = base.replace(b'"200"', b'"2\xb20"')
    assert native.parse_spans(bad_utf8, []) is None
    with pytest.raises(UnicodeDecodeError):
        json.loads(bad_utf8)


def test_malformed_utf8_rejects_without_interner_mutation():
    """A payload whose span naming bytes are invalid UTF-8 must reject
    with the documented None return BEFORE any shape interns — a raised
    decode error mid-loop would leave phantom endpoints in the shared
    interner from a rejected payload (review r5)."""
    from kmamiz_tpu.core.interning import EndpointInterner
    from kmamiz_tpu.core.spans import raw_spans_to_batch
    from kmamiz_tpu.synth import make_raw_window

    raw = make_raw_window(50, 7)
    bad = raw.replace(b"svc1.ns1", b"svc\xb2.ns1", 1)
    interner = EndpointInterner()
    assert raw_spans_to_batch(bad, interner=interner) is None
    assert len(interner.endpoints) == 0


class TestSkipSetHandle:
    """Persistent native skip set (km_skipset_*): the streaming dedup
    path's replacement for re-encoding the processed-trace blob per
    chunk (processor passes the handle; data_processor.rs:30-56 is the
    Arc<Mutex<HashMap>> dedup this mirrors)."""

    def test_extend_dedup_and_parse(self):
        ss = native.SkipSet()
        assert ss.handle is not None
        entries = (
            native.encode_skip_entry("tA")
            + native.encode_skip_entry(None)
            + native.encode_skip_entry("tA")  # duplicate: not re-counted
        )
        assert ss.extend(bytes(entries)) == 3
        assert len(ss) == 2  # tA + the None sentinel
        raw = json.dumps(
            [[mk_span("tA", "a")], [mk_span("tB", "b")]]
        ).encode()
        parsed = native.parse_spans(raw, skipset=ss)
        assert parsed["trace_ids"] == ["tB"]
        ss.clear()
        assert len(ss) == 0
        parsed = native.parse_spans(raw, skipset=ss)
        assert parsed["trace_ids"] == ["tA", "tB"]

    def test_none_sentinel_collapses_absent_ids(self):
        ss = native.SkipSet()
        ss.extend(bytes(native.encode_skip_entry(None)))
        raw = json.dumps(
            [[{k: v for k, v in mk_span("x", "a").items() if k != "traceId"}]]
        ).encode()
        parsed = native.parse_spans(raw, skipset=ss)
        assert parsed["trace_ids"] == []  # absent-id group skipped

    def test_malformed_extend_rejected(self):
        ss = native.SkipSet()
        assert ss.extend(b"\x01\xff\xff\xff\xff") == -1  # truncated
        assert len(ss) == 0


class TestParseSessionPath:
    """Persistent parse session: cross-chunk shape/status tables with
    delta string emission (the warm-path payload carries zero naming
    strings). Parity against the per-call path is exact — interners
    built in the same order produce identical ids and infos."""

    def _window(self, prefix, n=40):
        groups = []
        for t in range(n):
            parent = mk_span(f"{prefix}{t}", f"p{t}")
            child = mk_span(
                f"{prefix}{t}",
                f"c{t}",
                parent=f"p{t}",
                kind="CLIENT",
                name=f"down{t % 7}.ns.svc.cluster.local:80/*",
                timestamp=1_700_000_000_000_000 + t * 1000,
            )
            child["tags"]["istio.canonical_service"] = f"down{t % 7}"
            groups.append([parent, child])
        return json.dumps(groups).encode()

    def test_batch_parity_with_plain_path(self):
        import numpy as np

        from kmamiz_tpu.core.interning import EndpointInterner
        from kmamiz_tpu.core.spans import RawIngestSession

        raw1 = self._window("w1")
        raw2 = self._window("w2")

        i1 = EndpointInterner()
        b1a, k1a = raw_spans_to_batch(raw1, interner=i1)
        b1b, k1b = raw_spans_to_batch(raw2, interner=i1)

        i2 = EndpointInterner()
        sess = RawIngestSession(i2)
        assert sess.available
        b2a, k2a = raw_spans_to_batch(raw1, interner=i2, session=sess)
        b2b, k2b = raw_spans_to_batch(raw2, interner=i2, session=sess)

        assert list(k1a) == list(k2a) and list(k1b) == list(k2b)
        for ref, got in ((b1a, b2a), (b1b, b2b)):
            for f in (
                "kind",
                "parent_idx",
                "endpoint_id",
                "service_id",
                "rt_endpoint_id",
                "rt_service_id",
                "status_class",
                "latency_ms",
                "timestamp_us",
                "trace_of",
                "valid",
            ):
                assert np.array_equal(
                    getattr(ref, f), getattr(got, f)
                ), f
        assert i1.endpoints.strings == i2.endpoints.strings
        assert i1.endpoint_infos == i2.endpoint_infos
        # status STRINGS per id must agree even though the session shares
        # one interner across windows
        s1 = [b1b.statuses.lookup(int(i)) for i in b1b.status_id[: b1b.n_spans]]
        s2 = [b2b.statuses.lookup(int(i)) for i in b2b.status_id[: b2b.n_spans]]
        assert s1 == s2

    def test_warm_chunk_emits_no_shape_strings(self):
        from kmamiz_tpu.core.interning import EndpointInterner
        from kmamiz_tpu.core.spans import RawIngestSession

        i = EndpointInterner()
        sess = RawIngestSession(i)
        raw_spans_to_batch(self._window("a"), interner=i, session=sess)
        parsed = native.parse_spans(
            self._window("b"), session=sess.native
        )
        assert parsed["session_format"]
        assert parsed["new_shapes"] == []  # all shapes already acked
        assert parsed["new_statuses"] == []

    def test_unacked_shapes_reemit(self):
        from kmamiz_tpu.core.interning import EndpointInterner
        from kmamiz_tpu.core.spans import RawIngestSession

        i = EndpointInterner()
        sess = RawIngestSession(i)
        # raw native call WITHOUT ack: the next call re-emits
        p1 = native.parse_spans(self._window("a"), session=sess.native)
        assert len(p1["new_shapes"]) > 0
        p2 = native.parse_spans(self._window("a2"), session=sess.native)
        assert len(p2["new_shapes"]) >= len(p1["new_shapes"])
        assert p2["shape_base"] == 0  # nothing acked yet

    def test_malformed_payload_resets_session(self):
        from kmamiz_tpu.core.interning import EndpointInterner
        from kmamiz_tpu.core.spans import RawIngestSession

        i = EndpointInterner()
        sess = RawIngestSession(i)
        native1 = sess.native
        assert (
            raw_spans_to_batch(b"[[{oops", interner=i, session=sess) is None
        )
        assert sess.native is not native1  # fresh native session
        out = raw_spans_to_batch(
            self._window("ok"), interner=i, session=sess
        )
        assert out is not None and out[0].n_spans == 80

    def test_kept_blob_matches_encode_skip_entry(self):
        from kmamiz_tpu.core.interning import EndpointInterner
        from kmamiz_tpu.core.spans import RawIngestSession

        i = EndpointInterner()
        sess = RawIngestSession(i)
        _b, kept = raw_spans_to_batch(
            self._window("x"), interner=i, session=sess
        )
        expect = b"".join(native.encode_skip_entry(t) for t in kept)
        assert bytes(kept.blob) == expect


class TestProcessorSessionIntegration:
    def test_register_processed_blob_fast_path(self):
        """The blob fast path and the per-id path must leave identical
        dedup state (dict keys, blob contents, count header)."""
        from kmamiz_tpu.core.spans import KeptTraceIds
        from kmamiz_tpu.server.processor import DataProcessor

        ids = ["tA", "tB", None]
        blob = b"".join(native.encode_skip_entry(t) for t in ids)

        fast = DataProcessor(trace_source=lambda *a: [], use_device_stats=False)
        fast._register_processed(KeptTraceIds(ids, blob), 1000.0)

        slow = DataProcessor(trace_source=lambda *a: [], use_device_stats=False)
        slow._register_processed(list(ids), 1000.0)

        assert fast._processed == slow._processed
        with fast._dedup_lock, slow._dedup_lock:
            assert fast._skip_blob_locked() == slow._skip_blob_locked()

    def test_skipset_resync_after_prune(self):
        """TTL prune rebuilds the blob and bumps the generation: the
        native skip set must clear + resync, so pruned ids parse again."""
        from kmamiz_tpu.server.processor import (
            PROCESSED_TRACE_TTL_MS,
            DataProcessor,
        )

        clock = {"ms": 1_000_000.0}
        dp = DataProcessor(
            trace_source=lambda *a: [],
            use_device_stats=False,
            now_ms=lambda: clock["ms"],
        )
        raw = json.dumps([[mk_span("tOld", "a")]]).encode()
        out = dp.ingest_raw_window(raw)
        assert out["traces"] == 1
        # within TTL: the same trace dedups away
        again = dp.ingest_raw_window(raw)
        assert again["traces"] == 0
        # past TTL, first pass: the dedup snapshot predates the prune
        # (pruning runs at registration, mirroring the Rust DP's
        # end-of-tick cleanup, data_processor.rs:58-73) — still deduped,
        # but THIS pass's registration prunes and bumps the generation
        clock["ms"] += PROCESSED_TRACE_TTL_MS + 1_000
        assert dp.ingest_raw_window(raw)["traces"] == 0
        # second pass: the native set must have cleared + resynced to
        # the rebuilt (now-empty) blob — without the generation bump it
        # would still hold tOld and dedup forever
        fresh = dp.ingest_raw_window(raw)
        assert fresh["traces"] == 1


def test_fuzz_mutated_bytes_session_never_crashes():
    """The session entry point (km_parse_spans_sess) on the same
    adversarial byte soup as the per-call fuzz: the session must either
    reject (None), or return a well-formed payload whose ids stay inside
    the session tables — and one long-lived session survives the whole
    barrage with interleaved valid windows still parsing correctly."""
    from kmamiz_tpu import native
    from kmamiz_tpu.core.interning import EndpointInterner
    from kmamiz_tpu.core.spans import RawIngestSession, raw_spans_to_batch

    rng = random.Random(78)
    base = json.dumps(
        [[mk_span("t1", "a", duration=5)], [mk_span("t2", "b", parent="a")]]
    ).encode()
    interner = EndpointInterner()
    sess = RawIngestSession(interner)
    if not sess.available:
        pytest.skip("native extension unavailable")
    ok_rounds = 0
    for i in range(200):
        mode = rng.randrange(4)
        if mode == 0:
            buf = bytes(rng.randrange(256) for _ in range(rng.randrange(0, 160)))
        elif mode == 1:
            buf = base[: rng.randrange(len(base) + 1)]
        elif mode == 2:
            b = bytearray(base)
            for _ in range(rng.randrange(1, 6)):
                b[rng.randrange(len(b))] = rng.randrange(256)
            buf = bytes(b)
        else:
            b = bytearray(base)
            for _ in range(rng.randrange(1, 8)):
                b.insert(rng.randrange(len(b)), rng.choice(b'[]{}",\\\x00\x01'))
            buf = bytes(b)
        try:
            out = raw_spans_to_batch(buf, interner=interner, session=sess)
        except ValueError:
            # the documented overlong-window contract (a mutated
            # timestamp can stretch the window past int32 µs; both
            # ingest paths raise, callers split the batch) — the
            # session must stay consistent afterwards, which the
            # valid-window checks below prove
            out = None
        if out is not None:
            batch, kept = out
            assert batch.n_spans == int(batch.valid.sum())
        # every few rounds, a VALID window with fresh ids must still
        # parse exactly through whatever state the garbage left behind
        if i % 40 == 0:
            good = json.dumps(
                [[mk_span(f"g{i}", "a", duration=5)]]
            ).encode()
            res = raw_spans_to_batch(good, interner=interner, session=sess)
            assert res is not None and res[0].n_spans == 1
            assert list(res[1]) == [f"g{i}"]
            ok_rounds += 1
    assert ok_rounds == 5


class TestSessionTimestampRefresh:
    def test_refresh_cas_rejects_stale_expectation(self):
        """refresh_info_timestamps(expected_ts=...) must apply only when
        the info's current timestamp equals the expectation — a failed
        position reports back (the caller's slow path re-applies full
        content) and the info stays untouched."""
        import numpy as np

        from kmamiz_tpu.core.interning import EndpointInterner

        i = EndpointInterner()
        eid = i.intern_endpoint(
            "a\tns\tv\tGET\tu", {"uniqueEndpointName": "a", "timestamp": 100}
        )
        # expectation matches: applies
        failed = i.refresh_info_timestamps(
            np.array([eid]), np.array([170.0]), expected_ts=np.array([100.0])
        )
        assert failed == [] and i.info_of(eid)["timestamp"] == 170.0
        # expectation stale (another writer moved it): rejected untouched
        failed = i.refresh_info_timestamps(
            np.array([eid]), np.array([200.0]), expected_ts=np.array([100.0])
        )
        assert failed == [0] and i.info_of(eid)["timestamp"] == 170.0

    def test_interleaved_writer_content_wins_back(self):
        """A dict-path writer replacing the info CONTENT between session
        windows must not have the session's in-place stamp bless the
        foreign content: the session detects the moved timestamp and
        re-applies its own winning shape's full info."""
        import json as _json

        from kmamiz_tpu.core.interning import EndpointInterner
        from kmamiz_tpu.core.spans import RawIngestSession

        def window(prefix, ts_us):
            return _json.dumps(
                [[mk_span(f"{prefix}", "a", timestamp=ts_us)]]
            ).encode()

        i = EndpointInterner()
        sess = RawIngestSession(i)
        if not sess.available:
            pytest.skip("native extension unavailable")
        out = raw_spans_to_batch(
            window("w1", 1_700_000_000_000_000), interner=i, session=sess
        )
        assert out is not None
        eid = out[0].endpoint_id[0]
        original = dict(i.info_of(int(eid)))
        # foreign writer replaces the info with different content, newer ts
        i.intern_endpoint(
            original["uniqueEndpointName"],
            {**original, "url": "http://foreign", "timestamp": original["timestamp"] + 1},
        )
        # session's next window wins with a strictly newer timestamp:
        # full content must re-apply (not just a stamp on foreign data)
        out2 = raw_spans_to_batch(
            window("w2", 1_700_000_003_000_000), interner=i, session=sess
        )
        assert out2 is not None
        info = i.info_of(int(eid))
        assert info["url"] == original["url"]  # session shape's content
        assert info["timestamp"] > original["timestamp"]
