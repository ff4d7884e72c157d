"""Synthetic raw-Zipkin workload generation.

One generator shared by chip_smoke.py, the driver's
multi-chip dryrun (__graft_entry__.dryrun_multichip), and the parallel
tests: Istio-sidecar-shaped span groups serialized exactly like a Zipkin
`GET /api/v2/traces` response body, so the native SoA loader
(native/kmamiz_spans.cpp) and the deployed streaming route
(server/processor.DataProcessor.ingest_raw_stream) run the same code
they run in production.

Diversity is configurable because throughput claims depend on it
(VERDICT r4): `n_services`/`urls_per_service` set the intern-table and
edge cardinality the window carries. The BASELINE.json mesh shape is
1k services x 10 urls each = 10k distinct endpoints; the legacy bench
shape (200 services / 50 shared url templates) is kept for continuity.
"""
from __future__ import annotations

import json


def make_raw_window(
    n_traces: int,
    spans_per: int,
    t_start: int = 0,
    n_services: int = 200,
    n_namespaces: int = 8,
    urls_per_service: int = 0,
    n_url_templates: int = 50,
    trace_prefix: str = "w",
) -> bytes:
    """Serialized trace groups: `n_traces` chains of `spans_per` spans.

    With urls_per_service == 0 (legacy shape), every service shares the
    same `n_url_templates` url pool — endpoint diversity collapses to
    the template count. With urls_per_service > 0 (BASELINE shape),
    each service owns its own url set (distinct endpoints =
    n_services * urls_per_service) and traces walk a STRUCTURED call
    mesh: the entry service comes from the trace id and each hop calls
    one of ~32 fixed callees of the current service — per-service
    fan-out like a real mesh, not random adjacency. At the bench's
    1k-svc/10-url config and ~150k traces this yields the full 10k
    endpoints and >=100k distinct (ancestor, descendant, distance)
    edges (production cardinality for the interner, shape tables, and
    union sort).

    `trace_prefix` varies the trace ids without changing the naming
    shapes: steady-state benchmarking feeds a persistent processor
    fresh windows that dedup as new traces while every naming shape
    hits the warm interner — exactly like production windows after
    boot.
    """
    groups = []
    for t in range(t_start, t_start + n_traces):
        group = []
        svc_chain = t % n_services
        for j in range(spans_per):
            if urls_per_service:
                svc = svc_chain
                ep = (t // 7 + 3 * j) % urls_per_service
                svc_chain = (svc_chain * 31 + (t + j) % 32 + 1) % n_services
                # a service lives in ONE namespace (real meshes pin a
                # workload to its namespace); a per-hop namespace would
                # silently multiply the distinct service count
                ns = svc % n_namespaces
            else:
                svc = (t + j) % n_services
                ep = (t * 7 + j) % n_url_templates
                ns = j % n_namespaces
            group.append(
                {
                    "traceId": f"{trace_prefix}{t}",
                    "id": f"{t}-{j}",
                    "parentId": f"{t}-{j-1}" if j else None,
                    "kind": "SERVER" if j % 2 == 0 else "CLIENT",
                    "name": f"svc{svc}.ns{ns}.svc.cluster.local:80/*",
                    "timestamp": 1_700_000_000_000_000 + t * 900 + j,
                    "duration": 1000 + (t + j) % 5000,
                    "localEndpoint": {"serviceName": f"svc{svc}"},
                    "tags": {
                        "component": "proxy",
                        "http.method": "GET",
                        "http.protocol": "HTTP/1.1",
                        "http.status_code": "503" if t % 50 == 0 else "200",
                        "http.url": (
                            f"http://svc{svc}.ns{ns}"
                            f".svc.cluster.local/api/v1/ep{ep}"
                        ),
                        "istio.canonical_revision": "latest",
                        "istio.canonical_service": f"svc{svc}",
                        "istio.mesh_id": "cluster.local",
                        "istio.namespace": f"ns{ns}",
                        "response_flags": "-",
                        "upstream_cluster": "inbound|9080||",
                    },
                }
            )
        groups.append(group)
    return json.dumps(groups).encode()


def make_raw_chunks(
    n_traces: int, spans_per: int, chunks: int, **shape_kw
) -> list:
    """The same window split into `chunks` serialized pages (whole traces
    per page), the layout ingest_raw_stream consumes."""
    per = n_traces // chunks
    out = []
    start = 0
    for c in range(chunks):
        n = per if c < chunks - 1 else n_traces - start
        out.append(
            make_raw_window(n, spans_per, t_start=start, **shape_kw)
        )
        start += n
    return out
