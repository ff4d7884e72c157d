"""Archetype 10: the fleet-migration soak (docs/FLEET.md).

Drives a 4-worker fleet through the seeded ``fleet-migration`` scenario:
three tenants consistent-hash-spread across the ring take steady
traffic; at the storyline's ``tenant-migration`` tick the coordinator
live-migrates tenant ``alpha`` to the placement plane's least-loaded
pick — with one window deliberately arriving MID-HANDOFF (injected
between drain and WAL export), so the drain queue's zero-loss promise is
exercised, not assumed. Scored like every runner scorecard:

- **zero lost spans** — every trace id the driver routed (including the
  mid-handoff window) is in the final owner's dedup registry;
- **bit-exact** — each tenant's live graph signature equals a serial
  reference replay of its full ordered ingest log on a fresh processor;
- **zero steady recompiles** — after the rehearsal phase's program
  snapshot, the soak (migration replay and the coordinator's
  hierarchical fold included) dispatches only warm programs;
- **fold consistency** — the two-level merge's aggregate edge count
  equals the sum of the per-tenant stores (tenants' namespaces are
  disjoint, so the fold must neither lose nor invent edges).

Workers are in-process (``LocalTransport``) by default so the soak fits
the tier-1 budget; the coordination logic — ring, drain queue, handoff
protocol, fold — is byte-identical to the multi-process deployment,
which ``tools/fleet_bench.py`` exercises with real subprocess
workers over ``HTTPTransport``.
"""
from __future__ import annotations

import json
import os
from typing import Dict, List

from kmamiz_tpu import fleet as fleet_mod
from kmamiz_tpu.fleet import migration as migration_mod
from kmamiz_tpu.fleet import placement
from kmamiz_tpu.fleet.coordinator import FleetCoordinator, LocalTransport
from kmamiz_tpu.fleet.ring import HashRing
from kmamiz_tpu.telemetry.profiling import events as prof_events
from kmamiz_tpu.fleet.worker import FleetWorker

class _MidHandoffTransport:
    """Transport proxy that fires a callback between the migration's
    drain and WAL-export steps — the deterministic stand-in for a frame
    racing the handoff. The callback routes a real window through the
    coordinator, which MUST park it in the drain queue and release it to
    whichever side the migration resolves to."""

    def __init__(self, inner, on_export) -> None:
        self._inner = inner
        self._on_export = on_export

    def __getattr__(self, name):
        return getattr(self._inner, name)

    def wal_export(self, worker_id: str, tenant: str) -> bytes:
        self._on_export()
        return self._inner.wal_export(worker_id, tenant)


def run_fleet_scenario(
    spec, tmpdir: str, verbose: bool = False
) -> dict:
    """Run the fleet-migration scenario; returns a runner-shaped card."""
    from kmamiz_tpu.core import programs
    from kmamiz_tpu.graph.store import EndpointGraph
    from kmamiz_tpu.resilience.chaos import graph_signature
    from kmamiz_tpu.scenarios.factory import spec_signature
    from kmamiz_tpu.scenarios.topology import trace_group
    from kmamiz_tpu.telemetry.slo import percentile

    t_start_ms = prof_events.now_ms()
    size = max(2, fleet_mod.fleet_size()) if fleet_mod.enabled() else 4
    ring = HashRing(
        [f"w{i}" for i in range(size)],
        vnodes=fleet_mod.fleet_vnodes(),
        seed=fleet_mod.fleet_seed(),
    )
    workers = {
        w: FleetWorker(w, wal_root=os.path.join(tmpdir, "fleet-wal"))
        for w in ring.workers
    }
    coordinator = FleetCoordinator(ring, LocalTransport(workers))

    tenant_names = [p.tenant for p in spec.tenants]
    state: dict = {
        "latencies": [],
        "posts": 0,
        "errors": [],
        # per-tenant ordered ingest log (raw bytes, arrival order) — the
        # serial reference replays exactly this
        "expected": {t: [] for t in tenant_names},
        "expected_traces": {t: [] for t in tenant_names},
        "snapshot": None,
        "migration": None,
        "queued_mid_handoff": 0,
    }

    def window_bytes(plan, tick: int, count: int) -> bytes:
        prefix = f"{spec.name}-{plan.tenant}"
        return json.dumps(
            [trace_group(plan.topology, prefix, tick, i) for i in range(count)]
        ).encode()

    def route(plan, raw: bytes) -> None:
        tenant = plan.tenant
        state["expected"][tenant].append(raw)
        for group in json.loads(raw):
            state["expected_traces"][tenant].append(group[0]["traceId"])
        t0 = prof_events.now_ms()
        summary = coordinator.route_ingest(tenant, raw)
        state["latencies"].append(prof_events.now_ms() - t0)
        state["posts"] += 1
        if summary is not None and summary.get("quarantined"):
            state["errors"].append(
                f"{tenant}: window quarantined ({summary.get('reason')})"
            )

    migration_event = next(
        (
            ev
            for _t, ev in spec.events()
            if ev.kind == "tenant-migration"
        ),
        None,
    )
    migrating_tenant = next(
        (
            p.tenant
            for p in spec.tenants
            if any(ev.kind == "tenant-migration" for ev in p.events)
        ),
        None,
    )

    def fire_migration(tick: int) -> None:
        tenant = migrating_tenant
        target = placement.pick_target(
            coordinator.ring,
            tenant,
            tenant_names,
            overrides=coordinator.snapshot()["overrides"],
        )
        if target == coordinator.owner(tenant):
            # the least-loaded pick is the current owner: move to the
            # deterministic next worker so the soak always migrates
            others = [w for w in ring.workers if w != target]
            target = others[0]
        plan = next(p for p in spec.tenants if p.tenant == tenant)

        def mid_handoff_window() -> None:
            # distinct trace prefix: this window is EXTRA traffic racing
            # the handoff, not a duplicate of the tick's regular window
            raw = json.dumps(
                [trace_group(plan.topology, f"{spec.name}-{tenant}-mid", tick, 0)]
            ).encode()
            state["expected"][tenant].append(raw)
            for group in json.loads(raw):
                state["expected_traces"][tenant].append(group[0]["traceId"])
            queued = coordinator.route_ingest(tenant, raw)
            state["posts"] += 1
            if queued is not None:
                state["errors"].append(
                    "mid-handoff window bypassed the drain queue"
                )
            else:
                state["queued_mid_handoff"] += 1

        real_transport = coordinator.transport
        coordinator.swap_transport(
            _MidHandoffTransport(real_transport, mid_handoff_window)
        )
        try:
            state["migration"] = migration_mod.migrate_tenant(
                coordinator, tenant, target
            )
        except migration_mod.MigrationError as err:
            state["errors"].append(f"migration failed: {err}")
        finally:
            coordinator.swap_transport(real_transport)

    def rehearse(plan) -> None:
        """Pre-soak shape rehearsal, runner-style (steady recompiles
        must be ZERO from the snapshot on). Ordering matters: the
        terminal-shape warmup pushes EVERY topology path first, so the
        tenant's graph holds its full edge set at final capacity, and
        only then are the tick-window span shapes replayed — each
        (window shape, store capacity) pair the soak and the migration
        replay will dispatch lands its compile here. Rehearsal windows
        route through the coordinator like real traffic and join the
        expected log, so the bit-exactness oracle replays them too."""
        topo = plan.topology
        warm = [
            trace_group(topo, f"{spec.name}-warm", 0, p_i)
            for p_i in range(len(topo.paths))
        ]
        route(plan, json.dumps(warm).encode())
        rehearsed = set()
        shapes = [
            # the mid-handoff injection window is a single path-0 group
            [trace_group(topo, f"{spec.name}-wm", 0, 0)]
        ]
        for t in range(spec.n_ticks):
            count = plan.traffic[t % len(plan.traffic)]
            shapes.append(
                [
                    trace_group(topo, f"{spec.name}-wr{t}", t, i)
                    for i in range(count)
                ]
            )
        for groups in shapes:
            shape_key = tuple(sorted(len(g) for g in groups))
            if not groups or shape_key in rehearsed:
                continue
            rehearsed.add(shape_key)
            route(plan, json.dumps(groups).encode())

    try:
        for plan in spec.tenants:
            rehearse(plan)
        # force every deferred window merge to land (and compile) now,
        # so the snapshot below truly marks steady state
        for plan in spec.tenants:
            owner = workers[coordinator.owner(plan.tenant)]
            _ = owner.processor(plan.tenant).graph.capacity
        # trial fold into a throwaway aggregate: the edge sets are final
        # after the terminal-shape warmup, so this dispatches exactly
        # the union shapes the measured post-soak fold will
        coordinator.fold(tenant_names, EndpointGraph())
        state["snapshot"] = programs.snapshot()
        for tick in range(spec.n_ticks):
            if (
                migration_event is not None
                and tick == migration_event.at_tick
                and migrating_tenant is not None
            ):
                fire_migration(tick)
            for plan in spec.tenants:
                count = plan.traffic[tick % len(plan.traffic)]
                route(plan, window_bytes(plan, tick, count))
    except Exception as err:  # noqa: BLE001 - scorecard, not crash
        state["errors"].append(f"{type(err).__name__}: {err}")

    # aggregate fold (hierarchical level two) INSIDE the gated region:
    # it must ride the rehearsed warm union programs
    aggregate = EndpointGraph()
    try:
        folded_edges = coordinator.fold(tenant_names, aggregate)
    except Exception as err:  # noqa: BLE001
        folded_edges = -1
        state["errors"].append(f"fold failed: {err}")
    steady_recompiles = (
        sum(programs.new_compiles_since(state["snapshot"]).values())
        if state["snapshot"] is not None
        else -1
    )

    live_sigs: Dict[str, str] = {}
    live_edges: Dict[str, int] = {}
    lost_spans = 0
    missing: List[str] = []
    for plan in spec.tenants:
        owner = workers[coordinator.owner(plan.tenant)]
        proc = owner.processor(plan.tenant)
        live_sigs[plan.tenant] = graph_signature(proc.graph)
        live_edges[plan.tenant] = int(proc.graph.n_edges)
        with proc._dedup_lock:
            processed = set(proc._processed)
        for tid in state["expected_traces"][plan.tenant]:
            if tid not in processed:
                lost_spans += 1
                missing.append(f"{plan.tenant}:{tid}")

    ref_sigs = _reference_signatures(spec, state)

    mig = state["migration"]
    gates = {
        "no_errors": not state["errors"],
        "bit_exact": all(
            live_sigs[t] == ref_sigs[t] for t in tenant_names
        ),
        "zero_lost_spans": lost_spans == 0,
        "zero_steady_recompiles": steady_recompiles == 0,
        "migration_committed": bool(mig and mig.get("ok")),
        "mid_handoff_queued": (
            state["queued_mid_handoff"] >= 1
            and bool(mig and mig.get("queuedReleased", 0) >= 1)
        ),
        "fold_consistent": folded_edges == sum(live_edges.values()),
    }
    from kmamiz_tpu.analysis.concurrency import witness

    lock_witness = None
    if witness.installed():
        report = witness.check()
        gates["lock_witness_acyclic"] = report.acyclic
        # a witnessed edge the static model missed is an extractor blind
        # spot — the soak fails so the model gets fixed, not ignored
        gates["lock_witness_covered"] = (
            not report.uncovered and not report.unknown_sites
        )
        lock_witness = {
            "edges": report.edge_count,
            "acquires": report.acquire_count,
            "cycles": report.cycles,
            "uncovered": [list(p) for p in report.uncovered],
            "unknownSites": report.unknown_sites,
            "peerEdges": report.peer_edges,
        }
    lat = sorted(state["latencies"])
    card = {
        "name": spec.name,
        "archetype": spec.archetype,
        "spec_signature": spec_signature(spec),
        "n_ticks": spec.n_ticks,
        "tenants": tenant_names,
        "posts": state["posts"],
        "stale_serves": 0,
        "stale_rate": 0.0,
        "p50_tick_ms": round(percentile(lat, 0.50), 2),
        "p95_tick_ms": round(percentile(lat, 0.95), 2),
        "p99_tick_ms": round(percentile(lat, 0.99), 2),
        "lost_spans": lost_spans,
        "missing_traces": missing[:8],
        "quarantined": 0,
        "expected_poisons": 0,
        "recovery_ms": 0.0,
        "recoveries": {},
        "steady_recompiles": steady_recompiles,
        "mid_tick_compiles": 0,
        "signatures": live_sigs,
        "migration": mig,
        "fleet": {
            **fleet_mod.snapshot(),
            "coordinator": coordinator.snapshot(),
            "foldedEdges": folded_edges,
            "workers": {w: workers[w].summary() for w in ring.workers},
        },
        "wal": None,
        "lock_witness": lock_witness,
        "errors": state["errors"][:4],
        "gates": gates,
        "pass": all(gates.values()),
        "wall_s": round((prof_events.now_ms() - t_start_ms) / 1000.0, 1),
    }
    if not card["pass"]:
        from kmamiz_tpu.telemetry.profiling import recorder

        failed = sorted(g for g, ok in gates.items() if not ok)
        card["flight_artifact"] = recorder.record(
            f"scenario-{spec.name}", ",".join(failed), force=True
        )
    if verbose:
        import sys

        print(
            f"{spec.name}: pass={card['pass']} gates={gates}",
            file=sys.stderr,
        )
    return card


def _reference_signatures(spec, state: dict) -> Dict[str, str]:
    """Serial bit-exactness oracle: replay each tenant's full ordered
    ingest log on a fresh single-process DataProcessor (WAL off) — the
    fleet's drain/handoff/replay choreography must land every tenant on
    exactly this graph."""
    from kmamiz_tpu.resilience.chaos import graph_signature
    from kmamiz_tpu.scenarios.runner import scoped_env
    from kmamiz_tpu.server.processor import DataProcessor

    sigs: Dict[str, str] = {}
    with scoped_env({"KMAMIZ_INGEST_MAX_BYTES": None, "KMAMIZ_WAL": "0"}):
        for plan in spec.tenants:
            ref = DataProcessor(
                trace_source=lambda _lb, _t, _lim: [],
                use_device_stats=False,
            )
            for raw in state["expected"][plan.tenant]:
                ref.ingest_raw_window(raw)
            sigs[plan.tenant] = graph_signature(ref.graph)
    return sigs
