"""GraphSAGE training pipeline over simulator-generated fault windows.

Closes the loop the build plan calls for (SURVEY.md §7 step 7): the
MicroViSim-equivalent simulator synthesizes a mesh with time-windowed
faults (kmamiz_tpu.simulator), each hourly slot becomes one training
example — per-endpoint features from that slot's combined realtime data,
targets from the NEXT slot (log-latency regression + anomaly
classification) — and the 2-layer GraphSAGE head trains full-graph with
optax. Evaluation reports how well the head flags endpoints inside
injected fault windows it never saw labels for.

Anomaly ground truth is derived from the data itself (next-slot error
share above a threshold), so the pipeline needs no manual labeling and
works on any simulation config.
"""
from __future__ import annotations

import gc
import logging
import statistics
from dataclasses import dataclass, field
from typing import Dict, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from kmamiz_tpu.core import programs
from kmamiz_tpu.models import graphsage
from kmamiz_tpu.models import stacked as stacked_mod
from kmamiz_tpu.simulator.naming import extract_unique_service_name
from kmamiz_tpu.simulator.slot_metrics import parse_slot_key
from kmamiz_tpu.telemetry.profiling import events as prof_events
from kmamiz_tpu.telemetry.registry import REGISTRY
from kmamiz_tpu.telemetry.tracing import TRACER, operation_span, phase_span

logger = logging.getLogger("kmamiz_tpu.models.trainer")

_REFRESHES = REGISTRY.counter(
    "kmamiz_model_refresh_total", "Model refreshes started (trainer.train calls)"
)
_SLOT_UPDATES = REGISTRY.counter(
    "kmamiz_model_refresh_slot_updates_total",
    "Slots taken through an optimizer update by model refreshes (epochs x slots)",
)
_EPOCH_BLOCKS = REGISTRY.counter(
    "kmamiz_model_refresh_epoch_blocks_total",
    "Fused epoch-block programs dispatched by model refreshes",
)

_SLOW_CALLS = REGISTRY.counter(
    "kmamiz_model_refresh_slow_calls_total",
    "Epoch-block runs that took over SLOW_CALL_RATIO times the median of the program's previous runs of their size",
)
SLOW_CALL_RATIO = 1.25  # of the median of at least SLOW_CALL_MIN_RUNS previous runs
SLOW_CALL_MIN_RUNS = 3
SLOW_CALL_MIN_EXCESS_MS = 25.0  # a run of milliseconds (a toy mesh) jitters by more than a quarter and means nothing

ANOMALY_ERROR_SHARE = 0.10  # next-slot 5xx share that counts as anomalous
SLOT_SECONDS = 3600.0  # simulator slots are hourly


@dataclass
class GraphDataset:
    """Per-slot full-graph examples over a fixed endpoint set."""

    endpoint_names: List[str]
    src: jnp.ndarray  # [E] distance-1 edges
    dst: jnp.ndarray  # [E]
    edge_mask: jnp.ndarray  # [E]
    features: List[jnp.ndarray]  # per slot [N, F]
    target_latency: List[jnp.ndarray]  # per slot [N] (log1p ms, next slot)
    target_anomaly: List[jnp.ndarray]  # per slot [N] {0,1} (next slot)
    node_mask: List[jnp.ndarray]  # per slot [N] endpoints active next slot
    slot_keys: List[str] = field(default_factory=list)

    @property
    def num_nodes(self) -> int:
        return len(self.endpoint_names)


def _slot_order(keys) -> List[str]:
    return sorted(keys, key=parse_slot_key)


def _per_slot_stats(
    rows: List[dict], index: Dict[str, int], n: int
) -> Tuple[np.ndarray, ...]:
    """rows of TCombinedRealtimeData -> per-endpoint (count, err4xx,
    err5xx, latency_mean, latency_cv, active)."""
    count = np.zeros(n, dtype=np.float64)
    err4 = np.zeros(n, dtype=np.float64)
    err5 = np.zeros(n, dtype=np.float64)
    lat_weighted = np.zeros(n, dtype=np.float64)
    cv_weighted = np.zeros(n, dtype=np.float64)
    for row in rows:
        i = index.get(row["uniqueEndpointName"])
        if i is None:
            continue
        c = float(row["combined"])
        count[i] += c
        status = str(row["status"])
        if status.startswith("4"):
            err4[i] += c
        elif status.startswith("5"):
            err5[i] += c
        lat_weighted[i] += c * float(row["latency"].get("mean") or 0.0)
        cv_weighted[i] += c * float(row["latency"].get("cv") or 0.0)
    safe = np.maximum(count, 1.0)
    return count, err4, err5, lat_weighted / safe, cv_weighted / safe, count > 0


def dataset_from_simulation(
    endpoint_dependencies: List[dict],
    realtime_data_per_slot: Dict[str, List[dict]],
    replica_counts: List[dict],
) -> GraphDataset:
    """SimulationResult pieces -> consecutive-slot (features, next-slot
    targets) examples over the distance-1 dependency graph."""
    names = sorted(
        {dep["endpoint"]["uniqueEndpointName"] for dep in endpoint_dependencies}
    )
    index = {name: i for i, name in enumerate(names)}
    n = len(names)

    src_list, dst_list = [], []
    for dep in endpoint_dependencies:
        a = index[dep["endpoint"]["uniqueEndpointName"]]
        for d in dep.get("dependingOn", []):
            if d.get("distance") == 1:
                b = index.get(d["endpoint"]["uniqueEndpointName"])
                if b is not None:
                    src_list.append(a)
                    dst_list.append(b)
    if not src_list:  # keep shapes non-empty for jit friendliness
        src_list, dst_list = [0], [0]
        edge_mask = jnp.zeros(1, dtype=bool)
    else:
        edge_mask = jnp.ones(len(src_list), dtype=bool)

    replicas = np.ones(n, dtype=np.float32)
    service_replicas = {
        r["uniqueServiceName"]: float(r["replicas"]) for r in replica_counts
    }
    for name, i in index.items():
        replicas[i] = service_replicas.get(extract_unique_service_name(name), 1.0)

    order = _slot_order(realtime_data_per_slot)
    per_slot = [
        _per_slot_stats(realtime_data_per_slot[key], index, n) for key in order
    ]

    dataset = GraphDataset(
        endpoint_names=names,
        src=jnp.asarray(src_list, dtype=jnp.int32),
        dst=jnp.asarray(dst_list, dtype=jnp.int32),
        edge_mask=edge_mask,
        features=[],
        target_latency=[],
        target_anomaly=[],
        node_mask=[],
        slot_keys=[],
    )

    for t in range(len(order) - 1):
        count, err4, err5, lat, cv, active = per_slot[t]
        n_count, _n_err4, n_err5, n_lat, _n_cv, n_active = per_slot[t + 1]
        # hour-of-day of the PREDICTED slot: recurring operational faults
        # (nightly jobs, scheduled scale-downs) are periodic, and the
        # persistence baseline is blind to them
        _, next_hour, _ = parse_slot_key(order[t + 1])
        features = graphsage.assemble_features(
            count / SLOT_SECONDS,
            err4 / np.maximum(count, 1.0),
            err5 / np.maximum(count, 1.0),
            np.log1p(lat),  # same space as the regression target
            cv,
            replicas,
            np.log1p(count),
            active,
            hour_of_day=float(next_hour),
        )
        err_share_next = n_err5 / np.maximum(n_count, 1.0)
        dataset.features.append(features)
        dataset.target_latency.append(
            jnp.asarray(np.log1p(n_lat).astype(np.float32))
        )
        dataset.target_anomaly.append(
            jnp.asarray((err_share_next > ANOMALY_ERROR_SHARE).astype(np.float32))
        )
        dataset.node_mask.append(jnp.asarray(n_active))
        dataset.slot_keys.append(order[t])
    return dataset


@dataclass
class TrainResult:
    params: graphsage.SageParams
    losses: List[float]
    latency_losses: List[float]
    anomaly_losses: List[float]


def _epoch_blocks(start: int, total: int, every: int) -> List[Tuple[int, int]]:
    """Epoch ranges between checkpoint boundaries: [start, total) cut at
    multiples of `every` (every<=0: one block). The FUSED path runs one
    jitted program per block, so a resumed run replays the identical
    block sequence a fresh run would from that epoch — bit-exact resume."""
    blocks = []
    e = start
    while e < total:
        nxt = min((e // every + 1) * every, total) if every > 0 else total
        blocks.append((e, nxt))
        e = nxt
    return blocks


def _call_start():
    """What `_slow_call` compares the end of a call with: the registry's compile counts
    and the garbage collector's collections a generation."""
    return programs.snapshot(), [g["collections"] for g in gc.get_stats()]


def _slow_call(runner, run_ms: float, units: int, start) -> None:
    """Leave a record of an epoch-block run that took over SLOW_CALL_RATIO times the median
    of the program's previous runs of the same `units` (before `note_run` adds this one) and
    at least SLOW_CALL_MIN_EXCESS_MS longer:
    ONE warning line that says where the call's time went, so that a stalled call of a run
    nobody traced says whether it was the host's (a span, a collection, a compile) or lay
    between dispatch and losses; the slow-call counter; and the same text to the flight
    recorder (debounced and gated as every trigger is)."""
    previous = [ms for _end_s, ms, u in runner.recent_runs() if u == units]
    median = statistics.median(previous) if len(previous) >= SLOW_CALL_MIN_RUNS else float("inf")
    if run_ms <= max(SLOW_CALL_RATIO * median, median + SLOW_CALL_MIN_EXCESS_MS):
        return
    from kmamiz_tpu.telemetry import device as tel_device
    from kmamiz_tpu.telemetry.profiling import recorder

    compiled, collections = start
    spans = {name: round(ms, 1) for name, ms in TRACER.children_ms().items()}
    with phase_span("refresh.slow_call"):  # the record's own cost (a flight artifact is a file) lies in a phase too
        gc_now = [g["collections"] - before for g, before in zip(gc.get_stats(), collections)]
        detail = (
            f"slow refresh call: {runner.name} ran {run_ms:.1f} ms for {units} slot updates, "
            f"{run_ms / median:.2f} times the median of its {len(previous)} previous runs; "
            f"spans_ms={spans} compiles={programs.new_compiles_since(compiled)} gc_collections={gc_now} "
            f"bytes_in_use={(tel_device.device_memory_stats() or {}).get('bytes_in_use')}"
        )
        logger.warning(detail)
        _SLOW_CALLS.inc()
        recorder.record("refresh-slow-call", detail)


@operation_span("refresh.train")
def train(
    dataset: GraphDataset,
    epochs: int = 30,
    hidden: int = 32,
    lr: float = 1e-2,
    seed: int = 0,
    checkpoint_dir: str = "",
    checkpoint_every: int = 10,
    model=graphsage,
    use_node_embeddings: bool = False,
    fused: bool = True,
    batch_slots: int = 1,
    mesh=None,
) -> TrainResult:
    """Full-graph training, one step per slot per epoch.

    fused (default on; fused=False for the legacy host loop) stacks the
    dataset device-resident (models/stacked.py) and runs whole epoch blocks
    as ONE jitted lax.scan with donated params/optimizer state — the
    per-slot update schedule is identical to the legacy loop, so
    losses/params agree within fp32 tolerance.

    batch_slots > 1 switches to slot-minibatch SGD (per-batch averaged
    grads, one update per batch); with `mesh` the batch axis additionally
    shards across the mesh devices with psum'd grads
    (parallel/mesh.make_sharded_slot_grad) — same updates as the
    unsharded batch, any device count.

    With checkpoint_dir set, training resumes from the latest saved epoch
    (kmamiz_tpu.models.checkpoint) and snapshots every checkpoint_every
    epochs (0 = only at the end) plus at the end. Resuming validates the
    saved hyperparameters against the requested ones, and the saved
    stacked layout (node/edge buckets, slot count) against the dataset's.

    Traced as `refresh.train`: a trace of its own, or a child of the
    tick that called it (telemetry/tracing.operation_span), with a
    `refresh.*` span around every stretch below. Every span ends where
    the code already returns or already waits; none adds a sync."""
    from kmamiz_tpu.models import checkpoint as ckpt

    # a head in a package of its own names itself (stlgt/model.py: "stlgt")
    model_name = getattr(model, "NAME", model.__name__.rsplit(".", 1)[-1])
    num_slots = len(dataset.features) if dataset is not None else 0
    _REFRESHES.inc()
    call_start = _call_start()
    TRACER.note(
        model=model_name,
        loss=getattr(model, "LOSS", "mse+bce"),  # the head's own, where it states one
        epochs=epochs,
        slots=num_slots,
        batch_slots=batch_slots,
        fused=int(bool(fused)),
    )

    # node-identity embeddings are OPT-IN: on the small simulator meshes
    # they overfit (held-out F1 drops ~0.02 and latency MAE inflates ~17x
    # in the r2 experiment, MODELS.md); larger production graphs may want
    # them for periodic per-node behavior
    num_nodes = (
        dataset.num_nodes if (use_node_embeddings and dataset is not None) else 0
    )
    # feature width comes from the data: history-augmented datasets
    # (models/history.py) carry extra identity-free columns beyond the
    # base assemble_features layout
    num_features = (
        int(dataset.features[0].shape[1])
        if dataset is not None and dataset.features
        else model.NUM_FEATURES
    )
    with phase_span("refresh.init"):
        params = model.init_params(
            jax.random.PRNGKey(seed),
            hidden=hidden,
            num_features=num_features,
            num_nodes=num_nodes,
        )
        optimizer = model.make_optimizer(lr)
        opt_state = optimizer.init(params)

    start_epoch = 0
    if checkpoint_dir:
        with phase_span("refresh.resume"):
            # resolve the resume step ONCE (guard/validate/restore must agree
            # even if another instance writes meanwhile); incomplete saves
            # (dir without sidecar) fall back to the previous complete step
            resume_step = ckpt.latest_complete_step(checkpoint_dir)
            if resume_step is None and ckpt.latest_step(checkpoint_dir) is not None:
                logger.warning(
                    "checkpoint dir %s has only incomplete saves; starting fresh",
                    checkpoint_dir,
                )
            if resume_step is not None:
                # validate hyperparameters BEFORE restoring: orbax would
                # silently return the saved shapes against a mismatched template
                meta = ckpt.load_metadata(checkpoint_dir, resume_step) or {}
                if meta.get("num_features") is None:
                    raise ValueError(
                        f"checkpoint {checkpoint_dir} step {resume_step} was "
                        "saved before the 10-feature layout (no num_features in "
                        "metadata) and cannot restore into the current model; "
                        "delete the directory or retrain"
                    )
                for name, want in (
                    ("hidden", hidden),
                    ("lr", lr),
                    ("seed", seed),
                    ("model", model_name),
                    ("num_features", num_features),
                    ("num_nodes", num_nodes),
                ):
                    saved = meta.get(name)
                    if saved is None:
                        raise ValueError(
                            f"checkpoint {checkpoint_dir} step {resume_step} "
                            f"metadata lacks '{name}'; was it saved outside "
                            "trainer.train()?"
                        )
                    if saved != want:
                        raise ValueError(
                            f"checkpoint {checkpoint_dir} was trained with "
                            f"{name}={saved}, requested {name}={want}"
                        )
                # the stacked layout (node/edge capacity buckets + slot count)
                # is part of the training schedule: resuming against a dataset
                # that stacks differently would silently change which compiled
                # program and which slot sequence the remaining epochs run
                saved_layout = meta.get("stacked")
                if saved_layout is not None and dataset is not None:
                    current_layout = stacked_mod.dataset_layout(dataset)
                    if dict(saved_layout) != current_layout:
                        raise ValueError(
                            f"checkpoint {checkpoint_dir} step {resume_step} was "
                            f"saved with stacked layout {dict(saved_layout)} but "
                            f"the dataset stacks to {current_layout}; resume "
                            "needs the same node/edge buckets and slot count "
                            "(retrain, or rebuild the matching dataset)"
                        )
                restored = ckpt.restore_checkpoint(
                    checkpoint_dir, params, opt_state, step=resume_step
                )
                if restored is not None:
                    params, opt_state, meta = restored
                    start_epoch = int(meta.get("step", 0))
            TRACER.note(resumed_from=start_epoch)

    # balance the rare positive class: weight by the inverse base rate of
    # the training slots (clipped; 1.0 when no positives exist)
    with phase_span("refresh.pos_weight"):
        pos = sum(
            float((np.asarray(a) * np.asarray(m)).sum())
            for a, m in zip(dataset.target_anomaly, dataset.node_mask)
        )
        tot = sum(float(np.asarray(m).sum()) for m in dataset.node_mask)
        base_rate = pos / tot if tot else 0.0
        pos_weight = (
            float(np.clip(1.0 / base_rate, 1.0, 20.0)) if base_rate else 1.0
        )
        TRACER.note(slots=len(dataset.node_mask), value=pos_weight)

    def metadata(last_loss):
        return {
            "loss": last_loss,
            "hidden": hidden,
            "lr": lr,
            "seed": seed,
            "model": model_name,
            "num_features": num_features,
            "num_nodes": num_nodes,
            "stacked": stacked_mod.dataset_layout(dataset),
        }

    def save(step, last_loss):
        with phase_span("refresh.checkpoint_save"):
            ckpt.save_checkpoint(
                checkpoint_dir,
                params,
                opt_state,
                step=step,
                metadata=metadata(last_loss),
            )
            TRACER.note(step=step)

    losses, lat_losses, ano_losses = [], [], []
    if fused and dataset.features:
        st = stacked_mod.stack_dataset(dataset)
        if batch_slots > 1 or mesh is not None:
            axis = mesh.axis_names[0] if mesh is not None else "slots"
            batch = max(batch_slots, mesh.shape[axis] if mesh is not None else 1)
            runner = stacked_mod.dp_epoch_runner(
                model, lr, pos_weight, mesh=mesh, axis=axis
            )
            batched = stacked_mod.batch_slots_arrays(st, batch)
            plan = None  # the vmapped per-slot grads reduce the edge list
            group = 0

            def run_block(p, s, n_ep):
                return runner(p, s, *batched, st.src, st.dst, st.edge_mask, n_ep)

        else:
            runner = stacked_mod.runner_for(st, model, lr, pos_weight)
            plan = stacked_mod.plan_for(model, st)
            group = stacked_mod.slot_group(model, params, st.features, plan)

            def run_block(p, s, n_ep):
                return runner(
                    p,
                    s,
                    st.features,
                    st.target_latency,
                    st.target_anomaly,
                    st.node_mask,
                    st.src,
                    st.dst,
                    st.edge_mask,
                    n_ep,
                    plan,
                )

        # where the stack lies is the stack's own decision (stack_dataset)
        stacked_mod.require_node_shards(st, model, model_name, params, plan)
        TRACER.note(
            shards=st.shards,
            nodes_per_shard=st.bucket_nodes // st.shards,
            layout="nodes" if st.shards > 1 else "device",
        )
        save_every = checkpoint_every if checkpoint_dir else 0
        for e0, e1 in _epoch_blocks(start_epoch, epochs, save_every):
            slot_updates = (e1 - e0) * st.num_slots
            dispatched_ns = prof_events.now_ns()
            with phase_span("refresh.epoch_block"):
                params, opt_state, block = run_block(params, opt_state, e1 - e0)
                TRACER.note(
                    epochs=e1 - e0,
                    slot_updates=slot_updates,
                    planned=int(plan is not None),
                    slot_group=group,
                )
            # the fence that was always here: the host waits for the device
            with phase_span("refresh.loss_fetch"):
                block = np.asarray(block, dtype=np.float64)  # [e1-e0, 3]
            if isinstance(runner, programs.Program):  # a mesh runner is none
                run_ms = (prof_events.now_ns() - dispatched_ns) / 1e6
                _slow_call(runner, run_ms, slot_updates, call_start)
                runner.note_run(run_ms, slot_updates)
            _EPOCH_BLOCKS.inc()
            _SLOT_UPDATES.inc(slot_updates)
            losses.extend(block[:, 0].tolist())
            lat_losses.extend(block[:, 1].tolist())
            ano_losses.extend(block[:, 2].tolist())
            if checkpoint_dir:
                save(e1, losses[-1])
        return TrainResult(params, losses, lat_losses, ano_losses)

    step = model.make_train_step(optimizer, pos_weight=pos_weight)
    for epoch in range(start_epoch, epochs):
        epoch_loss = epoch_lat = epoch_ano = 0.0
        with phase_span("refresh.legacy_epoch"):
            for i in range(num_slots):
                params, opt_state, loss, (lat_l, ano_l) = step(
                    params,
                    opt_state,
                    dataset.features[i],
                    dataset.src,
                    dataset.dst,
                    dataset.edge_mask,
                    dataset.target_latency[i],
                    dataset.target_anomaly[i],
                    dataset.node_mask[i],
                )
                epoch_loss += float(loss)
                epoch_lat += float(lat_l)
                epoch_ano += float(ano_l)
            TRACER.note(slots=num_slots)
        _SLOT_UPDATES.inc(num_slots)
        slots = max(num_slots, 1)
        losses.append(epoch_loss / slots)
        lat_losses.append(epoch_lat / slots)
        ano_losses.append(epoch_ano / slots)
        if checkpoint_dir and (
            (checkpoint_every > 0 and (epoch + 1) % checkpoint_every == 0)
            or epoch + 1 == epochs
        ):
            save(epoch + 1, losses[-1])
    return TrainResult(params, losses, lat_losses, ano_losses)


@dataclass
class EvalResult:
    latency_mse: float
    anomaly_accuracy: float
    anomaly_precision: float
    anomaly_recall: float
    anomaly_base_rate: float
    per_slot_flagged: Dict[str, List[str]]  # slotKey -> flagged endpoints
    in_sample: bool = False  # True when evaluated on the training slots
    anomaly_f1: float = 0.0
    latency_mae_ms: float = 0.0  # mean |expm1(pred) - expm1(target)| in ms
    threshold: float = 0.5  # decision threshold (train-set calibrated)


def _score_predictions(dataset, predict) -> EvalResult:
    """Shared metric accumulation: `predict(i) -> (latency_log1p [N],
    anomaly_pos bool [N])` per slot."""
    tp = fp = fn = tn = 0
    sq_err_sum = 0.0
    abs_ms_sum = 0.0
    weight_sum = 0.0
    positives = 0
    total = 0
    flagged: Dict[str, List[str]] = {}
    for i in range(len(dataset.features)):
        pred_latency, pred_pos_raw = predict(i)
        mask = np.asarray(dataset.node_mask[i])
        pred_pos = np.asarray(pred_pos_raw) & mask
        truth = np.asarray(dataset.target_anomaly[i]).astype(bool) & mask

        tp += int((pred_pos & truth).sum())
        fp += int((pred_pos & ~truth).sum())
        fn += int((~pred_pos & truth).sum())
        tn += int((~pred_pos & ~truth & mask).sum())
        positives += int(truth.sum())
        total += int(mask.sum())

        pred_log = np.asarray(pred_latency)
        target_log = np.asarray(dataset.target_latency[i])
        err = pred_log - target_log
        # `where`, not a product: an endpoint outside the mask is outside the loss
        # too, its prediction is unconstrained, and 0 x expm1's overflow is no number
        sq_err_sum += float(np.where(mask, err**2, 0.0).sum())
        with np.errstate(over="ignore", invalid="ignore"):
            abs_ms = np.abs(np.expm1(pred_log) - np.expm1(target_log))
        abs_ms_sum += float(np.where(mask, abs_ms, 0.0).sum())
        weight_sum += float(mask.sum())

        names = [
            dataset.endpoint_names[j] for j in np.flatnonzero(pred_pos)
        ]
        if names:
            flagged[dataset.slot_keys[i]] = names

    precision = tp / max(tp + fp, 1)
    recall = tp / max(tp + fn, 1)
    return EvalResult(
        latency_mse=sq_err_sum / max(weight_sum, 1.0),
        anomaly_accuracy=(tp + tn) / max(total, 1),
        anomaly_precision=precision,
        anomaly_recall=recall,
        anomaly_base_rate=positives / max(total, 1),
        per_slot_flagged=flagged,
        anomaly_f1=(
            2 * precision * recall / (precision + recall)
            if precision + recall
            else 0.0
        ),
        latency_mae_ms=abs_ms_sum / max(weight_sum, 1.0),
    )


def evaluate(
    params,
    dataset: GraphDataset,
    threshold: float = 0.5,
    model=graphsage,
) -> EvalResult:
    """All slots run as ONE vmapped jitted forward over the stacked
    dataset (models/stacked.py) instead of a per-slot Python loop."""
    preds = stacked_mod.predict_all(params, dataset, model)
    if preds is not None:
        latencies, logits = preds
        probs = np.asarray(jax.nn.sigmoid(jnp.asarray(logits)))

    def predict(i):
        return latencies[i], probs[i] > threshold

    result = _score_predictions(dataset, predict)
    result.threshold = threshold
    return result


def evaluate_baseline(dataset: GraphDataset) -> EvalResult:
    """Persistence baseline the heads must beat: next-slot anomaly =
    current-slot 5xx share above the labeling threshold (feature col 2);
    next-slot latency = current-slot latency mean (feature col 3)."""

    def predict(i):
        feats = np.asarray(dataset.features[i])
        return feats[:, 3], feats[:, 2] > ANOMALY_ERROR_SHARE

    return _score_predictions(dataset, predict)


def evaluate_naive(dataset: GraphDataset, rate: float = 0.0, seed: int = 0) -> EvalResult:
    """Truly naive baselines: flag nothing (rate=0), everything (rate=1),
    or random at `rate`; latency = the dataset's global mean target."""
    rng = np.random.default_rng(seed)
    all_targets = np.concatenate(
        [
            np.asarray(t)[np.asarray(m).astype(bool)]
            for t, m in zip(dataset.target_latency, dataset.node_mask)
        ]
    ) if dataset.features else np.zeros(1)
    mean_latency = float(all_targets.mean()) if all_targets.size else 0.0

    def predict(i):
        n = np.asarray(dataset.features[i]).shape[0]
        if rate <= 0:
            flags = np.zeros(n, dtype=bool)
        elif rate >= 1:
            flags = np.ones(n, dtype=bool)
        else:
            flags = rng.random(n) < rate
        return np.full(n, mean_latency, dtype=np.float32), flags

    return _score_predictions(dataset, predict)


def temporal_split(
    dataset: GraphDataset, train_fraction: float = 0.75
) -> Tuple[GraphDataset, GraphDataset]:
    """First-slots train set / remaining-slots eval set — the ONE split
    definition shared by train_on_simulation and tools/eval_models.py."""
    cut = max(1, int(len(dataset.features) * train_fraction))

    def subset(lo, hi):
        return GraphDataset(
            endpoint_names=dataset.endpoint_names,
            src=dataset.src,
            dst=dataset.dst,
            edge_mask=dataset.edge_mask,
            features=dataset.features[lo:hi],
            target_latency=dataset.target_latency[lo:hi],
            target_anomaly=dataset.target_anomaly[lo:hi],
            node_mask=dataset.node_mask[lo:hi],
            slot_keys=dataset.slot_keys[lo:hi],
        )

    return subset(0, cut), subset(cut, None)


def train_on_simulation(
    endpoint_dependencies: List[dict],
    realtime_data_per_slot: Dict[str, List[dict]],
    replica_counts: List[dict],
    train_fraction: float = 0.75,
    epochs: int = 30,
    hidden: int = 32,
    seed: int = 0,
    model=graphsage,
    use_node_embeddings: bool = False,
) -> Tuple[TrainResult, EvalResult, GraphDataset]:
    """Temporal split: train on the first slots, evaluate on the rest
    (fault windows land wherever the config put them)."""
    dataset = dataset_from_simulation(
        endpoint_dependencies, realtime_data_per_slot, replica_counts
    )
    train_set, eval_set = temporal_split(dataset, train_fraction)
    result = train(
        train_set,
        epochs=epochs,
        hidden=hidden,
        seed=seed,
        model=model,
        use_node_embeddings=use_node_embeddings,
    )
    threshold = calibrate_threshold(result.params, train_set, model=model)
    if eval_set.features:
        metrics = evaluate(result.params, eval_set, threshold=threshold, model=model)
    else:  # nothing held out: report train-set metrics, explicitly marked
        metrics = evaluate(result.params, train_set, threshold=threshold, model=model)
        metrics.in_sample = True
    metrics.threshold = threshold
    return result, metrics, dataset


def calibrate_threshold(
    params, dataset: GraphDataset, model=graphsage, grid=None
) -> float:
    """Pick the decision threshold maximizing F1 on the TRAINING slots —
    standard practice for imbalanced detection; the held-out evaluation
    never sees its own labels. Falls back to 0.5 when no threshold
    achieves positive F1 (e.g. a clean run with no anomalies), so a
    degenerate grid point cannot flood inference with false positives.
    Forward passes run once; only the thresholding sweeps."""
    if grid is None:
        grid = [i / 20 for i in range(1, 20)]
    preds = stacked_mod.predict_all(params, dataset, model)
    if preds is None:
        return 0.5
    probs = np.asarray(jax.nn.sigmoid(jnp.asarray(preds[1])))  # [S, N]
    best_t, best_f1 = 0.5, 0.0
    for t in grid:
        tp = fp = fn = 0
        for i, prob in enumerate(probs):
            mask = np.asarray(dataset.node_mask[i]).astype(bool)
            pred = (prob > t) & mask
            truth = np.asarray(dataset.target_anomaly[i]).astype(bool) & mask
            tp += int((pred & truth).sum())
            fp += int((pred & ~truth).sum())
            fn += int((~pred & truth).sum())
        precision = tp / max(tp + fp, 1)
        recall = tp / max(tp + fn, 1)
        f1 = (
            2 * precision * recall / (precision + recall)
            if precision + recall
            else 0.0
        )
        if f1 > best_f1:
            best_t, best_f1 = t, f1
    return best_t
