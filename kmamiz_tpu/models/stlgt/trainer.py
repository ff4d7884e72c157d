"""STLGT continual trainer: online refresh driven from the collect tick.

Every hour fold (server/processor._fold_hour_locked) publishes a
forecast snapshot — features, CSR edges, names, cache_key. This module
turns consecutive snapshots into supervised examples (window t's
features predict window t+1's observed latency/anomaly — the same
next-hour framing HistoryState already uses for its label folds), keeps
them in a bounded ring, and refreshes ONE shared set of STLGT params
with a scan-fused donated-carry epoch block (stacked.epoch_runner's
exact pattern) over the ring.

Staleness drives the work, not the clock:

- the newest example's ring slot is always stale (it has never been
  trained on);
- DIRTY SERVICES mark their slots stale: an endpoint whose feature row
  changed since the previous fold (or that just appeared) marks every
  ring slot it participates in, so a quiet mesh refreshes one window
  while an incident replays its whole blast radius;
- a graph-version bump (topology change) marks everything stale.

Inside the epoch block each ring slot carries a 0/1 weight and the
update is SELECT-MERGED per slot: `p = where(w, p_updated, p_old)`.
This is not an optimization nicety — adamw with zero grads is NOT a
no-op (weight decay and moment decay still mutate params), so skipping
non-stale slots must skip the whole optimizer update, not just zero
the gradients.

Zero-steady-state-recompile discipline: ring capacity, node count and
edge count all pad to pow2 buckets (core.spans._pad_size), n_epochs is
static, and the jitted block registers in the program registry
("models.stlgt_epoch_block" with a family resolver) so warm boot
prewarms it and the registry snapshot-diff gates hold with continual
training enabled.

Failure containment mirrors the tick watchdog: a refresh that raises
keeps the last-good params serving, bumps the staleness gauge, and the
next fold tries again — training can degrade, serving cannot.
"""
from __future__ import annotations

import functools
import os
import threading
from typing import Dict, Optional, Tuple

import numpy as np

from kmamiz_tpu.core import programs
from kmamiz_tpu.core.spans import _pad_size
from kmamiz_tpu.telemetry.registry import REGISTRY
from kmamiz_tpu.telemetry.tracing import phase_span

# feature-column offsets in the assembled base layout
# (graphsage.assemble_features): err5 share and log1p latency are the
# label sources, active the example mask
_COL_ERR5 = 2
_COL_LOG_LATENCY = 3
_COL_ACTIVE = 7
#: err5 share above which the next-window anomaly label is 1 (matches
#: the trainer-side ANOMALY_ERROR_SHARE labeling convention)
ANOMALY_ERROR_SHARE = 0.10

# -- per-model SLO rows (telemetry satellite) -------------------------------
#: continual-training refreshes completed, per model head
MODEL_TRAIN_TICKS = REGISTRY.counter_family(
    "kmamiz_model_train_ticks_total",
    "Continual-training refreshes completed, per model",
    ("model",),
)
#: folds observed since the serving params last refreshed, per model —
#: 0 is fresh; a climbing value means serving is falling back to
#: last-good exactly like the tick watchdog's stale serves
MODEL_FORECAST_STALENESS = REGISTRY.gauge_family(
    "kmamiz_model_forecast_staleness_ticks",
    "Folds since the model's serving params last refreshed",
    ("model",),
)
# preallocated per-model handles: the fold path increments these, never
# a formatted-label lookup (graftscope hot-path discipline)
_STLGT_TICKS = MODEL_TRAIN_TICKS.handle("stlgt")
_STLGT_STALENESS = MODEL_FORECAST_STALENESS.handle("stlgt")


def _env_int(name: str, default: int) -> int:
    try:
        return int(os.environ.get(name, str(default)))
    except ValueError:
        return default


def _env_float(name: str, default: float) -> float:
    try:
        return float(os.environ.get(name, str(default)))
    except ValueError:
        return default


def enabled() -> bool:
    """KMAMIZ_STLGT gate, default OFF (the head is additive; the
    GraphSAGE pipeline stays the paper-parity default)."""
    return os.environ.get("KMAMIZ_STLGT", "0") not in ("0", "false", "")


def horizon_max() -> int:
    """KMAMIZ_STLGT_HORIZON_MAX (default 24): upper clamp on the
    ``/model/forecast?horizon=`` sqrt-widening AND on the control
    plane's KMAMIZ_CONTROL_HORIZON. Beyond this the widened p99 grows
    past any plausible latency — the route 400s rather than serving a
    forecast that would make admission control shed everything."""
    return max(1, _env_int("KMAMIZ_STLGT_HORIZON_MAX", 24))


def configured_quantiles() -> Tuple[float, ...]:
    """KMAMIZ_STLGT_QUANTILES as a sorted tuple, default (.5,.95,.99)."""
    raw = os.environ.get("KMAMIZ_STLGT_QUANTILES", "")
    if not raw:
        from kmamiz_tpu.models.stlgt import model as _model

        return _model.QUANTILES
    try:
        vals = tuple(sorted(float(v) for v in raw.split(",") if v.strip()))
        return vals if len(vals) == 3 else (0.50, 0.95, 0.99)
    except ValueError:
        return (0.50, 0.95, 0.99)


# ---------------------------------------------------------------------------
# scan-fused epoch block (registered program family)
# ---------------------------------------------------------------------------


def _resolve_epoch_runner(key: str):
    """Hint resolver for 'models.stlgt_epoch_block[<module>|lr|pw|q,q,q]':
    rebuild the jitted refresh block for a persisted training config so
    warm boot prewarms it before the first fold arrives."""
    import importlib

    mod, lr, pw, qs = key.split("|")
    if not mod.startswith("kmamiz_tpu.models."):
        return None
    return stlgt_epoch_runner(
        importlib.import_module(mod),
        float(lr),
        float(pw),
        tuple(float(q) for q in qs.split(",")),
    )


@functools.lru_cache(maxsize=16)
def stlgt_epoch_runner(model, lr: float, pos_weight: float, quantiles):
    """One jitted donated-carry program refreshing shared STLGT params
    over the stacked example ring: scan over epochs around a scan over
    ring slots, each slot's optimizer update select-merged by its 0/1
    stale weight (see module docstring for why zeroing grads instead
    would corrupt non-stale training state)."""
    import jax
    import jax.numpy as jnp
    import optax

    optimizer = model.make_optimizer(lr)
    # the head's own loss: the function stacked.epoch_runner's block calls
    loss_fn = model.make_loss_fn(pos_weight, tuple(quantiles))
    grad_fn = jax.value_and_grad(loss_fn, has_aux=True)

    @functools.partial(
        jax.jit,
        static_argnames=("n_epochs",),
        donate_argnames=("params", "opt_state"),
    )
    def run(
        params,
        opt_state,
        features,  # [S, Nb, F]
        target_latency,  # [S, Nb]
        target_anomaly,  # [S, Nb]
        node_mask,  # [S, Nb]
        src,  # [S, Eb]
        dst,  # [S, Eb]
        edge_mask,  # [S, Eb]
        slot_weight,  # [S] float32, 1.0 = stale slot participates
        n_epochs: int,
    ):
        def slot_step(carry, xs):
            p, s = carry
            f, tl, ta, nm, sc, dc, em, w = xs
            (loss, (q_l, a_l)), grads = grad_fn(p, f, sc, dc, em, tl, ta, nm)
            updates, s_new = optimizer.update(grads, s, p)
            p_new = optax.apply_updates(p, updates)
            keep = w > 0.0
            p = jax.tree_util.tree_map(
                lambda a, b: jnp.where(keep, a, b), p_new, p
            )
            s = jax.tree_util.tree_map(
                lambda a, b: jnp.where(keep, a, b), s_new, s
            )
            return (p, s), jnp.stack([loss, q_l, a_l]) * w

        def epoch_step(carry, _):
            carry, per_slot = jax.lax.scan(
                slot_step,
                carry,
                (
                    features,
                    target_latency,
                    target_anomaly,
                    node_mask,
                    src,
                    dst,
                    edge_mask,
                    slot_weight,
                ),
            )
            return carry, per_slot.sum(axis=0) / jnp.maximum(
                slot_weight.sum(), 1.0
            )

        (params, opt_state), losses = jax.lax.scan(
            epoch_step, (params, opt_state), None, length=n_epochs
        )
        return params, opt_state, losses

    return programs.register_instance(
        "models.stlgt_epoch_block",
        f"{model.__name__}|{lr}|{pos_weight}|"
        + ",".join(str(float(q)) for q in quantiles),
        run,
    )


programs.register_family("models.stlgt_epoch_block", _resolve_epoch_runner)


# ---------------------------------------------------------------------------
# continual trainer
# ---------------------------------------------------------------------------


class ContinualTrainer:
    """Bounded example ring + stale tracking + refresh scheduling for one
    STLGT head. All mutable state lives behind `_lock`; the processor
    calls `observe_fold` from its fold path (already single-flight under
    the history lock), tests and the eval tool drive instances directly."""

    def __init__(
        self,
        depth: int = 8,
        refresh_every: int = 1,
        epochs: int = 2,
        hidden: int = 32,
        lr: float = 0.05,
        pos_weight: float = 1.0,
        quantiles: Optional[Tuple[float, ...]] = None,
        seed: int = 0,
    ) -> None:
        from kmamiz_tpu.models.stlgt import model as _model

        self.model = _model
        self.depth = max(1, int(depth))
        self.refresh_every = max(1, int(refresh_every))
        self.epochs = max(1, int(epochs))
        self.hidden = int(hidden)
        self.lr = float(lr)
        self.pos_weight = float(pos_weight)
        self.quantiles = tuple(quantiles or _model.QUANTILES)
        self.seed = int(seed)

        self._lock = threading.Lock()
        self._ring: list = []  # example dicts, oldest first
        self._stale: list = []  # parallel 0/1 flags
        self._pending: Optional[dict] = None  # last fold awaiting its label
        self._params = None  # device pytree (training + serving)
        self._opt_state = None
        self._params_version = 0  # bumps per successful refresh
        self._folds_seen = 0
        self._folds_since_refresh = 0
        self._refreshes = 0
        self._refresh_failures = 0
        self._last_loss: Optional[float] = None
        self._last_error: Optional[str] = None
        self._ticks_handle = _STLGT_TICKS
        self._staleness_handle = _STLGT_STALENESS

    # -- snapshot intake ----------------------------------------------------

    @staticmethod
    def _window_from_snapshot(snap: dict) -> dict:
        feats = np.asarray(snap["features"], dtype=np.float32)
        return {
            "features": feats,
            "src": np.asarray(snap["src"], dtype=np.int32),
            "dst": np.asarray(snap["dst"], dtype=np.int32),
            "mask": np.asarray(snap["mask"], dtype=bool),
            "version": int(snap.get("cache_key", (0, 0, 0))[0]),
        }

    def observe_fold(self, snap: dict) -> Optional[dict]:
        """One hour fold observed: label the pending window with this
        fold's outcomes, append the example, propagate staleness, and
        refresh if the cadence says so. Returns the refresh report when
        one ran, else None."""
        with self._lock:
            win = self._window_from_snapshot(snap)
            self._folds_seen += 1
            prev = self._pending
            self._pending = win
            if prev is not None:
                self._append_example_locked(prev, win)
                self._folds_since_refresh += 1
            self._staleness_handle.set(float(self._folds_since_refresh))
            if not any(self._stale):
                return None
            if self._params is not None and (
                self._folds_since_refresh < self.refresh_every
            ):
                return None
            return self._refresh_locked()

    def _append_example_locked(self, prev: dict, cur: dict) -> None:
        n_cur = cur["features"].shape[0]
        n_prev = prev["features"].shape[0]
        f = cur["features"].shape[1]
        # the endpoint id space only grows between folds (the interner
        # appends); pad the older window up to the newer count
        feats = np.zeros((n_cur, f), dtype=np.float32)
        feats[: min(n_prev, n_cur)] = prev["features"][: min(n_prev, n_cur)]
        t_lat = cur["features"][:, _COL_LOG_LATENCY].astype(np.float32)
        t_anom = (
            cur["features"][:, _COL_ERR5] > ANOMALY_ERROR_SHARE
        ).astype(np.float32)
        active_prev = np.zeros(n_cur, dtype=bool)
        active_prev[: min(n_prev, n_cur)] = (
            prev["features"][: min(n_prev, n_cur), _COL_ACTIVE] > 0
        )
        node_mask = active_prev & (cur["features"][:, _COL_ACTIVE] > 0)
        example = {
            "features": feats,
            "src": prev["src"],
            "dst": prev["dst"],
            "mask": prev["mask"],
            "target_latency": t_lat,
            "target_anomaly": t_anom,
            "node_mask": node_mask,
        }
        # dirty endpoints: rows that changed since the previous fold (or
        # appeared) — their slots go stale across the whole ring
        k = min(n_prev, n_cur)
        dirty = np.ones(n_cur, dtype=bool)
        dirty[:k] = (
            np.abs(cur["features"][:k] - prev["features"][:k]).sum(axis=1) > 0
        )
        version_bump = cur["version"] != prev["version"]
        for i, ex in enumerate(self._ring):
            if version_bump:
                self._stale[i] = True
                continue
            m = ex["node_mask"]
            kk = min(m.shape[0], n_cur)
            if bool((m[:kk] & dirty[:kk]).any()):
                self._stale[i] = True
        self._ring.append(example)
        self._stale.append(True)  # never-trained window is always stale
        while len(self._ring) > self.depth:
            self._ring.pop(0)
            self._stale.pop(0)

    # -- refresh ------------------------------------------------------------

    def _refresh_locked(self) -> dict:
        try:
            with phase_span("stlgt-refresh"):
                report = self._run_epoch_block_locked()
        except Exception as exc:  # noqa: BLE001 - watchdog-style containment
            # last-good params keep serving; staleness keeps climbing
            self._refresh_failures += 1
            self._last_error = f"{type(exc).__name__}: {exc}"
            self._staleness_handle.set(float(self._folds_since_refresh))
            return {"ok": False, "error": self._last_error}
        self._refreshes += 1
        self._folds_since_refresh = 0
        self._params_version += 1
        self._last_error = None
        self._stale = [False] * len(self._ring)
        self._ticks_handle.inc()
        self._staleness_handle.set(0.0)
        report["ok"] = True
        report["version"] = self._params_version
        return report

    def _run_epoch_block_locked(self) -> dict:
        import jax

        s_real = len(self._ring)
        s_cap = _pad_size(max(s_real, 1))
        nb = _pad_size(max(ex["features"].shape[0] for ex in self._ring))
        eb = _pad_size(max(int(ex["src"].shape[0]) for ex in self._ring))
        f = self._ring[0]["features"].shape[1]

        feats = np.zeros((s_cap, nb, f), dtype=np.float32)
        t_lat = np.zeros((s_cap, nb), dtype=np.float32)
        t_anom = np.zeros((s_cap, nb), dtype=np.float32)
        n_mask = np.zeros((s_cap, nb), dtype=bool)
        src = np.zeros((s_cap, eb), dtype=np.int32)
        dst = np.zeros((s_cap, eb), dtype=np.int32)
        e_mask = np.zeros((s_cap, eb), dtype=bool)
        slot_w = np.zeros(s_cap, dtype=np.float32)
        for i, ex in enumerate(self._ring):
            n = ex["features"].shape[0]
            e = int(ex["src"].shape[0])
            feats[i, :n] = ex["features"]
            t_lat[i, :n] = ex["target_latency"]
            t_anom[i, :n] = ex["target_anomaly"]
            n_mask[i, :n] = ex["node_mask"]
            src[i, :e] = ex["src"]
            dst[i, :e] = ex["dst"]
            e_mask[i, :e] = ex["mask"]
            slot_w[i] = 1.0 if self._stale[i] else 0.0

        if self._params is None:
            self._params = jax.device_put(
                self.model.init_params(
                    jax.random.PRNGKey(self.seed),
                    hidden=self.hidden,
                    num_features=f,
                )
            )
            self._opt_state = jax.device_put(
                self.model.make_optimizer(self.lr).init(self._params)
            )

        runner = stlgt_epoch_runner(
            self.model, self.lr, self.pos_weight, self.quantiles
        )
        # explicit transfers: the fold path runs under
        # jax.transfer_guard("disallow") when KMAMIZ_TRANSFER_GUARD=1
        self._params, self._opt_state, losses = runner(
            self._params,
            self._opt_state,
            jax.device_put(feats),
            jax.device_put(t_lat),
            jax.device_put(t_anom),
            jax.device_put(n_mask),
            jax.device_put(src),
            jax.device_put(dst),
            jax.device_put(e_mask),
            jax.device_put(slot_w),
            n_epochs=self.epochs,
        )
        losses = jax.device_get(losses)  # graftlint: disable=host-sync-in-hot-path -- one loss fetch per refresh (per fold at most), not per tick
        self._last_loss = float(losses[-1, 0])
        return {
            "slots": s_real,
            "stale_slots": int(sum(1 for w in slot_w if w > 0)),
            "bucket": [int(s_cap), int(nb), int(eb)],
            "loss": self._last_loss,
        }

    def refresh(self) -> dict:
        """Force a refresh now (tests / eval tool)."""
        with self._lock:
            if not self._ring:
                return {"ok": False, "error": "no examples"}
            return self._refresh_locked()

    # -- serving surface ----------------------------------------------------

    def serving(self) -> Optional[dict]:
        """Last-good params for the forecast route, or None before the
        first successful refresh. The version keys the handler's memo
        alongside the snapshot cache_key."""
        with self._lock:
            if self._params is None or self._params_version == 0:
                return None
            return {
                "params": self._params,
                "version": self._params_version,
                "quantiles": self.quantiles,
                "model": self.model,
            }

    def status(self) -> Dict[str, object]:
        with self._lock:
            return {
                "enabled": enabled(),
                "depth": self.depth,
                "refreshEvery": self.refresh_every,
                "epochs": self.epochs,
                "quantiles": list(self.quantiles),
                "foldsSeen": self._folds_seen,
                "examples": len(self._ring),
                "staleSlots": int(sum(1 for s in self._stale if s)),
                "refreshes": self._refreshes,
                "refreshFailures": self._refresh_failures,
                "paramsVersion": self._params_version,
                "stalenessTicks": self._folds_since_refresh,
                "lastLoss": self._last_loss,
                "lastError": self._last_error,
            }


# ---------------------------------------------------------------------------
# process-wide trainer singleton (env-configured; processor hook entry)
# ---------------------------------------------------------------------------

_TRAINER: Optional[ContinualTrainer] = None
_TRAINER_LOCK = threading.Lock()


def get_trainer() -> ContinualTrainer:
    global _TRAINER
    with _TRAINER_LOCK:
        if _TRAINER is None:
            _TRAINER = ContinualTrainer(
                depth=_env_int("KMAMIZ_STLGT_HISTORY", 8),
                refresh_every=_env_int("KMAMIZ_STLGT_REFRESH", 1),
                epochs=_env_int("KMAMIZ_STLGT_EPOCHS", 2),
                hidden=_env_int("KMAMIZ_STLGT_HIDDEN", 32),
                lr=_env_float("KMAMIZ_STLGT_LR", 0.05),
                quantiles=configured_quantiles(),
            )
        return _TRAINER


def on_fold(snap: dict) -> None:
    """Processor fold hook (server/processor._fold_hour_locked tail):
    no-op unless KMAMIZ_STLGT=1, so the default pipeline pays one env
    read per fold."""
    if not enabled():
        return
    get_trainer().observe_fold(snap)


def trainer_status() -> Dict[str, object]:
    """GET /model/stlgt payload: config + ring + refresh health."""
    with _TRAINER_LOCK:
        t = _TRAINER
    if t is None:
        return {"enabled": enabled(), "foldsSeen": 0, "paramsVersion": 0}
    return t.status()


def serving_params() -> Optional[dict]:
    """Last-good serving params of the process trainer (None when the
    trainer never refreshed — the handler falls back to checkpoints)."""
    with _TRAINER_LOCK:
        t = _TRAINER
    return t.serving() if t is not None else None


def reset_for_tests() -> None:
    global _TRAINER
    with _TRAINER_LOCK:
        _TRAINER = None
    _STLGT_STALENESS.set(0.0)
