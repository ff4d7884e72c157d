"""Forecast routes: serve the trained graph head against live features.

The model families (models/graphsage.py, models/gat.py) train offline on
simulator or replayed data (tools/eval_models_large.py, MODELS.md); this
handler closes the loop by running a checkpointed head against the
features the realtime tick produces online (DataProcessor._observe_history
-> history_model_features) over the live dependency graph:

- `GET /model/status` — checkpoint metadata + feature freshness.
- `GET /model/forecast` — per-endpoint anomaly probability and predicted
  latency for the upcoming hour. With the STLGT continual trainer live
  (KMAMIZ_STLGT=1, docs/STLGT.md) the route grows `?quantile=` (p50|
  p95|p99|all) and `?horizon=` (hours) parameters and a `stlgt` payload
  section: per-endpoint latency quantiles plus the top per-edge
  attribution scores; with no checkpoint configured the live STLGT
  params serve the legacy shape too (model "stlgt-live").

Configuration: KMAMIZ_MODEL_DIR points at a trainer checkpoint directory
(models/checkpoint.py). Only identity-free heads serve here (num_nodes=0
in the checkpoint): node-identity embeddings are transductive and cannot
be aligned with a live, growing endpoint set — the inductive history
features exist precisely so the deployable model does not need them
(MODELS.md round 4).
"""
from __future__ import annotations

import json
import logging
import threading
import time
from typing import Optional

import numpy as np

from kmamiz_tpu.api.router import IRequestHandler, Request, Response
from kmamiz_tpu.server.initializer import AppContext

logger = logging.getLogger("kmamiz_tpu.api.model")


class ModelHandler(IRequestHandler):
    def __init__(self, ctx: AppContext) -> None:
        super().__init__("model")
        self._ctx = ctx
        self._lock = threading.Lock()
        self._loaded = None  # (params, meta, model_module) | None
        self._load_error: Optional[str] = None
        # a missing/empty checkpoint directory, a mid-rewrite sidecar, or
        # a vanished step directory are TRANSIENT (the trainer may not
        # have written — or be rewriting — its step): such failures
        # re-attempt on later requests, rate-limited, instead of pinning
        # a 503 until restart. Terminal errors (no model dir configured,
        # embedding checkpoints, unexpected exceptions) cache permanently.
        self._error_transient = False
        self._next_retry = 0.0
        # (snapshot-identity, payload): forecasts change once per hour
        # fold; polls in between serve the memoized payload
        self._forecast_cache = None

        self.add_route("get", "/status", self._status)
        self.add_route("get", "/forecast", self._forecast)

    RETRY_SECONDS = 5.0

    def _mark_transient(self, msg: str) -> None:
        """Record a transient load failure (rate-limited retry). Caller
        holds self._lock; returns None so `return self._mark_transient(...)`
        reads as the failure exit."""
        self._load_error = msg
        self._error_transient = True
        self._next_retry = time.monotonic() + self.RETRY_SECONDS
        return None

    # -- checkpoint loading (lazy, once) -------------------------------------

    def _load(self):
        with self._lock:
            if self._loaded is not None:
                return self._loaded
            if self._load_error is not None and (
                not self._error_transient
                or time.monotonic() < self._next_retry
            ):
                return None
            directory = self._ctx.settings.model_dir
            if not directory:
                self._load_error = "KMAMIZ_MODEL_DIR not configured"
                return None
            # every path below is terminal unless it explicitly marks
            # itself transient; without this reset, a raising load after
            # a prior transient failure would inherit transient=True with
            # an expired retry deadline — re-attempting the full load on
            # EVERY request with no rate limit
            self._error_transient = False
            try:
                import jax

                from kmamiz_tpu.models import checkpoint as ckpt
                from kmamiz_tpu.models import gat, graphsage, pna
                from kmamiz_tpu.models.stlgt import model as stlgt_model

                step = ckpt.latest_complete_step(directory)
                if step is None:
                    return self._mark_transient(
                        f"no complete checkpoint in {directory}"
                    )
                meta = ckpt.load_metadata(directory, step) or {}
                if not meta:
                    # sidecar vanished between listing and read: the
                    # trainer is mid-rewrite of this step — same
                    # transient class as "not written yet"
                    return self._mark_transient(
                        f"checkpoint step {step} metadata unreadable "
                        f"(trainer mid-write?)"
                    )
                if int(meta.get("num_nodes", 0)):
                    self._load_error = (
                        "checkpoint uses node-identity embeddings; only "
                        "identity-free heads serve against a live endpoint "
                        "set (retrain without --embeddings)"
                    )
                    return None
                # the head the checkpoint names (trainer.train's metadata);
                # a quantile head serves its p50 through this legacy shape
                model = {"gat": gat, "pna": pna, "stlgt": stlgt_model}.get(
                    meta.get("model"), graphsage
                )
                template = model.init_params(
                    jax.random.PRNGKey(0),
                    hidden=int(meta["hidden"]),
                    num_features=int(meta["num_features"]),
                    num_nodes=0,
                )
                optimizer = model.make_optimizer(float(meta.get("lr", 1e-3)))
                restored = ckpt.restore_checkpoint(
                    directory, template, optimizer.init(template), step=step
                )
                if restored is None:
                    # the step directory disappeared between listing and
                    # restore (trainer re-saving the same step): transient
                    # — a complete checkpoint reappears moments later
                    return self._mark_transient(
                        f"restore failed for {directory}"
                    )
                params, _opt, meta = restored
                self._loaded = (params, dict(meta), model)
                self._load_error = None  # clear a prior transient failure
                logger.info(
                    "forecast model loaded from %s step %s", directory, step
                )
            except OSError as err:
                # filesystem races with a concurrently-writing trainer
                # (step dir pruned mid-restore, etc) are the same
                # transient class as "not written yet"
                logger.warning("forecast model load raced a writer: %s", err)
                return self._mark_transient(f"model load raced a writer: {err}")
            except Exception as err:  # noqa: BLE001 - surfaced via /status
                self._load_error = f"model load failed: {err}"
                logger.exception("forecast model load failed")
            return self._loaded

    # -- routes --------------------------------------------------------------

    def _status(self, req: Request) -> Response:
        loaded = self._load()
        dp = self._ctx.processor
        snap = getattr(dp, "forecast_snapshot", None) if dp else None
        payload = {
            "modelLoaded": loaded is not None,
            "modelDir": self._ctx.settings.model_dir,
            "error": self._load_error,
            "featureHourReady": snap is not None,
            "predictedHour": snap["predicted_hour"] if snap else None,
            "numEndpoints": int(snap["features"].shape[0]) if snap else 0,
        }
        if loaded is not None:
            _params, meta, model = loaded
            payload["checkpoint"] = {
                "model": meta.get("model"),
                "step": meta.get("step"),
                "hidden": meta.get("hidden"),
                "numFeatures": meta.get("num_features"),
                "loss": meta.get("loss"),
            }
        return Response(payload=payload)

    #: quantile selector values the route accepts (column order matches
    #: models/stlgt/model.QUANTILES)
    _QUANTILE_COLS = {"p50": 0, "p95": 1, "p99": 2}
    #: attribution edges returned per forecast (highest STLGT edge gate)
    _TOP_EDGES = 20

    def _forecast(self, req: Request) -> Response:
        # live STLGT params (continual trainer's last-good) serve the
        # quantile surface — and the whole route when no checkpoint is
        # configured; a checkpointed head alone serves the legacy shape
        from kmamiz_tpu.models import stlgt as stlgt_pkg

        live = stlgt_pkg.serving_params()
        loaded = self._load()
        if loaded is None and live is None:
            return Response(
                status=503, payload={"error": self._load_error}
            )
        qsel = (req.query.get("quantile") or "all").lower()
        if qsel != "all" and qsel not in self._QUANTILE_COLS:
            return Response(
                status=400,
                payload={
                    "error": f"unknown quantile {qsel!r} "
                    "(p50|p95|p99|all)"
                },
            )
        horizon = req.query_int("horizon") or 1
        horizon = max(1, int(horizon))
        # sqrt-H widening has no natural ceiling: an absurd H would
        # widen p99 past any plausible latency (and make forecast-driven
        # admission control shed everything). Beyond the configured max
        # the request is a caller error, not a forecast.
        hmax = stlgt_pkg.horizon_max()
        if horizon > hmax:
            return Response(
                status=400,
                payload={
                    "error": f"horizon {horizon} exceeds "
                    f"KMAMIZ_STLGT_HORIZON_MAX={hmax}: sqrt-horizon "
                    "widening is not meaningful that far out"
                },
            )
        if (qsel != "all" or horizon != 1) and live is None:
            # the quantile/horizon surface is STLGT's: without a
            # refreshed trainer there is no last-good to fall back to
            return Response(
                status=503,
                payload={
                    "error": "quantile/horizon forecasts need the STLGT "
                    "continual trainer (KMAMIZ_STLGT=1) to have completed "
                    "a refresh"
                },
            )
        dp = self._ctx.processor
        # ONE attribute read: the fold publishes features + matching
        # edges + names + hour together, so no torn mixtures and no
        # clamped edge ids from endpoints interned after the fold
        snap = getattr(dp, "forecast_snapshot", None) if dp else None
        if snap is None:
            return Response(
                status=503,
                payload={
                    "error": "no completed feature hour yet (the first "
                    "forecast is available after one full hour of ticks)"
                },
            )
        # memoize per published snapshot: the fold replaces the snapshot
        # dict wholesale once per hour, while dashboards poll every few
        # seconds — re-running the model forward + full-endpoint JSON
        # assembly per poll would be thousands of redundant forwards per
        # hour at 10k endpoints. Keyed on the fold's (graph version,
        # label epoch, hour) cache_key — the scorer cache's keying
        # discipline — with snapshot identity as both tiebreak and
        # fallback for restored snapshots that predate the key.
        snap_key = snap.get("cache_key") or id(snap)
        # the memo key grows the STLGT dimensions: a trainer refresh
        # (params version bump) or a different quantile/horizon selection
        # must recompute, while same-key polls stay memoized with zero
        # forwards and zero compiles
        memo_key = (
            snap_key,
            live["version"] if live is not None else 0,
            qsel,
            horizon,
        )
        cached = self._forecast_cache
        if cached is not None and cached[4] == memo_key:
            # pre-encoded (and pre-gzipped) bytes ride the response so
            # polls skip both the ~1 MB json.dumps and the per-request
            # gzip; .payload stays for in-process dispatch consumers
            return Response(
                payload=cached[1], raw_body=cached[2], raw_gzip=cached[3]
            )
        feats = snap["features"]
        names = snap["names"]

        stlgt_section = None
        q_ms = s_prob = gate = None
        if live is not None:
            from kmamiz_tpu.models.stlgt import serving as stlgt_serving

            q_ms, s_prob, gate = stlgt_serving.quantile_forward(
                live["params"],
                feats,
                snap["src"],
                snap["dst"],
                snap["mask"],
                live["model"],
            )
            if horizon > 1:
                # multi-hour horizon: widen the tail spread by the
                # independent-increments heuristic (sqrt scaling of the
                # above-median excess; docs/STLGT.md#horizon) — p50 is
                # carried flat, the tail columns grow
                scale = float(np.sqrt(horizon))
                q_ms = q_ms.copy()
                q_ms[:, 1:] = q_ms[:, :1] + (
                    q_ms[:, 1:] - q_ms[:, :1]
                ) * scale
            cols = (
                self._QUANTILE_COLS
                if qsel == "all"
                else {qsel: self._QUANTILE_COLS[qsel]}
            )
            stlgt_endpoints = [
                {
                    "uniqueEndpointName": names[i],
                    "anomalyProbability": round(float(s_prob[i]), 4),
                    "latencyQuantilesMs": {
                        level: round(float(max(q_ms[i, c], 0.0)), 2)
                        for level, c in cols.items()
                    },
                }
                for i in np.argsort(-s_prob)
            ]
            edge_mask = np.asarray(snap["mask"], dtype=bool)
            src_ids = np.asarray(snap["src"])
            dst_ids = np.asarray(snap["dst"])
            n = len(names)
            attributions = []
            for e in np.argsort(-gate):
                if len(attributions) >= self._TOP_EDGES:
                    break
                e = int(e)
                if not edge_mask[e]:
                    continue
                s, d = int(src_ids[e]), int(dst_ids[e])
                if s >= n or d >= n:
                    continue
                attributions.append(
                    {
                        "source": names[s],
                        "target": names[d],
                        "score": round(float(gate[e]), 4),
                    }
                )
            stlgt_section = {
                "paramsVersion": live["version"],
                "quantile": qsel,
                "horizon": horizon,
                "quantileLevels": list(live["quantiles"]),
                "endpoints": stlgt_endpoints,
                "attributions": attributions,
            }

        if loaded is not None:
            params, meta, model = loaded
            if feats.shape[1] != int(meta["num_features"]):
                return Response(
                    status=409,
                    payload={
                        "error": (
                            # graftlint: disable=shape-hazard -- 409 reject payload, a diagnostic not a cache key
                            f"feature width {feats.shape[1]} != checkpoint's "
                            f"{meta['num_features']} (train with the matching "
                            "feature layout)"
                        )
                    },
                )
            from kmamiz_tpu.models import serving

            # bucket-padded jitted forward (models/serving.py): the compiled
            # program is keyed by pow2 capacity buckets, so a growing endpoint
            # set recompiles O(log N) times instead of every fold; timings
            # land on /timings as model_forward + modelServe
            lat_ms, prob = serving.forecast_forward(
                params, feats, snap["src"], snap["dst"], snap["mask"], model
            )
            model_name = meta.get("model")
        else:
            # no checkpoint configured: the live STLGT head serves the
            # legacy shape too (p50 column + its anomaly probability)
            lat_ms, prob = q_ms[:, 0], s_prob
            model_name = "stlgt-live"
        order = np.argsort(-prob)
        endpoints = [
            {
                "uniqueEndpointName": names[i],
                "anomalyProbability": round(float(prob[i]), 4),
                "predictedLatencyMs": round(float(max(lat_ms[i], 0.0)), 2),
            }
            for i in order
        ]
        payload = {
            "predictedHour": snap["predicted_hour"],
            "model": model_name,
            "endpoints": endpoints,
        }
        if stlgt_section is not None:
            payload["stlgt"] = stlgt_section
        import gzip

        encoded = json.dumps(payload).encode()
        zipped = gzip.compress(encoded)
        self._forecast_cache = (snap, payload, encoded, zipped, memo_key)
        return Response(payload=payload, raw_body=encoded, raw_gzip=zipped)
