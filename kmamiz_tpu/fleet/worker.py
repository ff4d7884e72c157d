"""One fleet front end: per-tenant processors under a worker identity.

A ``FleetWorker`` is the unit the ring assigns tenants to. Each owned
tenant gets its own ``DataProcessor`` (the full PR-12 ingest path:
sharded native parse, KMZC decode, quarantine, graph merge) whose WAL
logs under the WORKER's namespace — ``<wal-root>/workers/<worker-id>/
tenants/<tenant>`` — so a migration ships exactly one directory's worth
of records and two workers never contend on one WAL file.

The class runs in two modes:

- **in-process** (tests, the default scenario soak): N ``FleetWorker``
  instances in one process behind a ``LocalTransport`` — every routing,
  fold, and migration decision is identical to the multi-process
  deployment, without N jax startups per test.
- **subprocess** (bench, ``KMAMIZ_FLEET_PROC=1`` soaks): ``main()``
  boots a real ``DataProcessorServer`` per worker; the coordinator
  speaks the ``/fleet/*`` routes over HTTP (``HTTPTransport``).
"""
from __future__ import annotations

import os
import threading
from typing import Callable, Dict, List, Optional

from kmamiz_tpu.fleet.ring import RingError
from kmamiz_tpu.resilience.chaos import graph_signature
from kmamiz_tpu.resilience.wal import IngestWAL
from kmamiz_tpu.tenancy.arena import valid_tenant


def _stub_source(_look_back: int, _end_ts: int, _limit: int) -> List[list]:
    """Fleet workers are ingest-driven; the poll source stays empty."""
    return []


class FleetWorker:
    """Per-tenant processors + WAL namespaces under one worker id."""

    def __init__(
        self,
        worker_id: str,
        wal_root: Optional[str] = None,
        trace_source: Optional[Callable] = None,
    ) -> None:
        if not isinstance(worker_id, str) or not valid_tenant(worker_id):
            raise RingError(f"invalid worker id: {worker_id!r}")
        self.worker_id = worker_id
        self._wal_root = wal_root
        self._trace_source = trace_source or _stub_source
        # tenant processors are created lazily on first frame; creation
        # and the migration-time swap both serialize here
        self._lock = threading.RLock()
        self._procs: Dict[str, "DataProcessor"] = {}
        # replayed-but-unverified migration imports stage here until the
        # coordinator's signature check commits (or aborts) them — an
        # aborted handoff never leaves a divergent graph serving
        self._pending_imports: Dict[str, "DataProcessor"] = {}
        self._frames = 0
        self._spans = 0

    # -- tenant processors ---------------------------------------------------

    def _tenant_wal(self, tenant: str) -> Optional[IngestWAL]:
        if self._wal_root is None:
            return None
        return IngestWAL(
            os.path.join(
                self._wal_root, "workers", self.worker_id, "tenants", tenant
            )
        )

    def _fresh_processor(self, tenant: str) -> "DataProcessor":
        from kmamiz_tpu.server.processor import DataProcessor

        return DataProcessor(
            self._trace_source,
            use_device_stats=False,
            tenant=tenant,
            wal=self._tenant_wal(tenant),
        )

    def processor(self, tenant: str) -> "DataProcessor":
        """Get-or-create the tenant's processor (ring owners only — the
        coordinator enforces placement, the worker just serves)."""
        with self._lock:
            proc = self._procs.get(tenant)
            if proc is None:
                proc = self._fresh_processor(tenant)
                self._procs[tenant] = proc
            return proc

    def tenants(self) -> List[str]:
        with self._lock:
            return sorted(self._procs)

    def drop_tenant(self, tenant: str) -> dict:
        """Forget a migrated-away tenant (its WAL directory stays on
        disk as the abort-path safety net until the next import)."""
        with self._lock:
            proc = self._procs.pop(tenant, None)
        if proc is not None and proc.wal is not None:
            proc.wal.close()
        return {
            "tenant": tenant,
            "worker": self.worker_id,
            "dropped": proc is not None,
        }

    # -- ingest / fold surface ----------------------------------------------

    def ingest(self, tenant: str, raw: bytes) -> dict:
        summary = self.processor(tenant).ingest_raw_window(raw)
        with self._lock:
            self._frames += 1
            self._spans += int(summary.get("spans", 0))
        return summary

    def signature(self, tenant: str) -> str:
        return graph_signature(self.processor(tenant).graph)

    def export_edges(self, tenant: str) -> dict:
        return self.processor(tenant).graph.export_named_edges()

    # -- migration surface (fleet/migration.py drives these) -----------------

    def drain(self, tenant: str) -> dict:
        """Quiesce a tenant for handoff: retire in-flight merges at the
        graph's stage_fence, then report the pre-drain signature and the
        durable record count the target must reproduce."""
        proc = self.processor(tenant)
        proc.graph.stage_fence()
        wal = proc.wal
        return {
            "tenant": tenant,
            "worker": self.worker_id,
            "signature": graph_signature(proc.graph),
            "walRecords": wal.record_count() if wal is not None else 0,
        }

    def wal_export(self, tenant: str) -> bytes:
        wal = self.processor(tenant).wal
        if wal is None:
            raise RuntimeError(
                f"tenant {tenant!r} has no WAL on worker {self.worker_id!r}"
                " (migration needs durability; set a wal_root)"
            )
        return wal.export_handoff()

    def wal_import(self, tenant: str, data: bytes) -> dict:
        """Receive a migrating tenant: a FRESH processor (empty dedup
        map, empty graph, truncated WAL namespace) imports the shipped
        records and replays them in order — id assignment follows replay
        order, so the rebuilt graph's signature is bit-exact with the
        source's pre-drain one. The rebuilt processor only STAGES here
        (phase one): it starts serving when the coordinator's
        signature/record-count verification calls commit_import, and an
        aborted migration discards it via abort_import without ever
        touching the tenant's live entry."""
        proc = self._fresh_processor(tenant)
        if proc.wal is None:
            raise RuntimeError(
                f"worker {self.worker_id!r} has no wal_root; cannot import"
            )
        proc.wal.truncate()
        imported = proc.wal.import_handoff(data)
        replayed = proc.replay_wal()
        with self._lock:
            stale = self._pending_imports.pop(tenant, None)
            self._pending_imports[tenant] = proc
        if (
            stale is not None
            and stale.wal is not None
            and stale.wal is not proc.wal
        ):
            stale.wal.close()
        return {
            "tenant": tenant,
            "worker": self.worker_id,
            "records": imported,
            "replayed": replayed["replayed"],
            "spans": replayed["spans"],
            "signature": graph_signature(proc.graph),
        }

    def commit_import(self, tenant: str) -> dict:
        """Phase two: the coordinator verified the replay — install the
        staged processor as the tenant's live entry (replacing any stale
        one) so the first post-flip frame serves the migrated graph."""
        with self._lock:
            proc = self._pending_imports.pop(tenant, None)
            if proc is None:
                raise RingError(
                    f"no pending import for tenant {tenant!r} on worker "
                    f"{self.worker_id!r}"
                )
            old = self._procs.get(tenant)
            self._procs[tenant] = proc
        if old is not None and old.wal is not None and old.wal is not proc.wal:
            old.wal.close()
        return {"tenant": tenant, "worker": self.worker_id, "installed": True}

    def abort_import(self, tenant: str) -> dict:
        """The migration aborted: discard the staged processor. The
        tenant's live entry (if any) was never touched, so this worker
        keeps serving exactly what it served before the handoff."""
        with self._lock:
            proc = self._pending_imports.pop(tenant, None)
        if proc is not None and proc.wal is not None:
            proc.wal.close()
        return {
            "tenant": tenant,
            "worker": self.worker_id,
            "dropped": proc is not None,
        }

    def summary(self) -> dict:
        with self._lock:
            return {
                "worker": self.worker_id,
                "tenants": sorted(self._procs),
                "frames": self._frames,
                "spans": self._spans,
            }


def main(argv: Optional[List[str]] = None) -> None:
    """Subprocess worker entry: a DataProcessorServer whose /fleet/*
    routes serve this worker's slice. The parent namespaces durability
    by pointing KMAMIZ_WAL_DIR at the worker's own directory before
    spawn, so from_env-created tenant WALs land per-worker exactly like
    the in-process _tenant_wal layout."""
    import argparse
    import logging

    from kmamiz_tpu.core import compile_cache
    from kmamiz_tpu.server.dp_server import DataProcessorServer
    from kmamiz_tpu.server.processor import DataProcessor

    ap = argparse.ArgumentParser(description="kmamiz fleet worker")
    ap.add_argument("--worker-id", required=True)
    ap.add_argument("--port", type=int, default=0)
    ap.add_argument("--host", default="127.0.0.1")
    args = ap.parse_args(argv)

    logging.basicConfig(level=os.environ.get("LOG_LEVEL", "WARNING").upper())
    compile_cache.enable()  # before the first jit dispatch
    processor = DataProcessor(_stub_source, use_device_stats=False)
    recovered = processor.replay_wal()
    if recovered["replayed"]:
        logging.getLogger("kmamiz_tpu.fleet.worker").info(
            "worker %s wal replay: %s", args.worker_id, recovered
        )
    server = DataProcessorServer(processor, host=args.host, port=args.port)
    # the parent discovers the bound port from this line (ephemeral-port
    # friendly, same contract as the scenario runner's child processes)
    print(f"FLEET_WORKER_READY {args.worker_id} {server.port}", flush=True)
    server.serve_forever()


if __name__ == "__main__":
    main()
