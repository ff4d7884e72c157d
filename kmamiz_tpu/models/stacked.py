"""Device-resident stacked dataset + scan-fused GraphSAGE epochs.

The host-driven trainer (models/trainer.py pre-stack) ran one jitted step
per slot per epoch over ragged per-slot arrays: S dispatches per epoch,
each paying a host round trip, plus a fresh host->device upload of every
slot on every use — exactly the dispatch-bound pattern dense accelerators
punish ("Fast Training of Sparse Graph Neural Networks on Dense
Hardware", PAPERS.md). This module makes the dataset and the epoch loop
device-native:

- `stack_dataset` pads a GraphDataset's slots to CAPACITY BUCKETS — node
  and edge counts rounded up to powers of two, the same discipline the
  graph store applies to its edge arrays (graph/store.py) and the span
  batches to their rows (core/spans._pad_size) — and stacks all slots
  into [S, N, ...] device arrays uploaded ONCE. Bucketing keeps compiled
  programs reusable as graphs grow; padded nodes/edges are masked so real
  outputs are unchanged.
- `epoch_runner` returns a single jitted program running WHOLE EPOCHS:
  `lax.scan` over the stacked slots (one optimizer update per slot, the
  legacy loop's exact schedule) nested in a scan over epochs, with
  params/optimizer state donated — n_epochs * n_slots steps in ONE
  dispatch instead of n_epochs * n_slots dispatches.
- `dp_epoch_runner` is the data-parallel variant: slots grouped into
  microbatches whose per-slot grads are vmapped and averaged (and, with
  a mesh, sharded across devices with psum'd grads via
  parallel/mesh.make_sharded_slot_grad) before a single update — the
  multi-chip training path, verified by __graft_entry__.dryrun_multichip
  and tests/test_parallel.py.
- `predict_all` vmaps a head's forward over every stacked slot in one
  jitted call — the batched evaluation path shared by trainer.evaluate
  and trainer.calibrate_threshold.
- every stack carries the EDGE PLAN of its topology (ops/edge_plan.py:
  the neighbour list sorted by owner and, within an owner, by the edge's
  direction, the degree, a tiled reducer's work list), built once on the
  host and memoised by the identity of the edge arrays, so datasets over
  one graph (a history and its head, a train and a test split) share one.
  `epoch_runner`'s block takes it as a loop constant beside
  src/dst/edge_mask and hands it to a head that says it takes one
  (`TAKES_PLAN`, read by `plan_for`): GraphSAGE sums neighbour rows over
  it (`sparse_sum.planned_neighbor_sum`), GAT runs its directed segment
  softmax and weighted sums over it (`sparse_gat.planned_attention`), STLGT
  its sigmoid-gated neighbour bias (`sparse_gated.planned_gated_sum`), PNA
  its mean, deviation, maximum and minimum (`sparse_pna.planned_aggregate`;
  the plan carries the scalers' constant, `mean_log_degree`). The
  vmapped paths (`dp_epoch_runner`, `predict_all`) pass none and reduce the
  edge list as it comes.
- the HEAD'S OWN LOSS: a head module may state `make_loss_fn(pos_weight)`,
  whose product takes what `common.make_loss_fn`'s takes and the forward's
  extra arguments by keyword (STLGT: the pinball loss over its three
  quantiles); both epoch blocks train under it (`head_loss_fn`). A head
  that states none trains under `common.make_loss_fn(model.forward, ...)`.
  Either way a block returns (total, first, second) per epoch.
- the SLOT GROUP: GraphSAGE's first layer sums the neighbours' features,
  which are data (no parameter in them, no gradient through them) and 18
  floats wide where the chip pads a gathered row to 128 lanes. So
  `epoch_runner`'s block makes that sum for `slot_group` = 128 // F
  consecutive slots at once, one `[Nb, G * F]` table, one gather and one
  planned sum, and each slot's update reads its own F columns
  (`graphsage.forward(..., neighbor_sum_1=)`): still one optimizer update
  per slot, in slot order, from bit for bit the same sums (the reducer's
  columns are independent). It engages where the block can see that it
  may: the head says its `forward` takes the sum (`TAKES_NEIGHBOR_SUM_1`),
  the parameters hold no node embedding, and there is a plan; every other
  call runs the flat scan.
- the NODE SHARDS: a history that one device cannot hold is cut by NODES
  over the local devices (`parallel/mesh.node_shards`: the fewest power of
  two whose share is at most half a device's memory; one wherever one
  holds it, and then everything here is what it was). The rule is the
  data's: `stack_dataset` weighs the stack against the device and no caller
  says anything. Each device gets its rows of every slot `[S, Nb / D, ..]`,
  filled and handed over shard by shard (the whole never lies on one device,
  nor twice on the host), and ITS edge plan: the entries whose owner it holds,
  a sub-plan a source device (`edge_plan.build_shard_plans`; the node ranges
  are cut where the entries divide evenly). Datasets over one graph share a plan and with it
  its layout, so the head of a sharded history is sharded as it is.
  `node_sharded_epoch_runner` runs the one-device block's own body under
  `shard_map` over the `nodes` axis: parameters, optimizer state and losses
  replicated, a layer's neighbour tables all-gathered in float32 and gathered
  from one source's at a time (`sparse_sum.sharded_neighbor_sum`), the loss's sums and counts and the
  parameter gradients summed over the devices (`common.make_loss_fn`'s
  `axis_name`). The schedule does not change: one update a slot, in slot
  order, over all endpoints; the slot group works on the sharded table.
  GraphSAGE says it can (`TAKES_NODE_SHARDS`); the heads that do not yet
  are refused by name (`trainer.train`), as are the vmapped paths.
  What crosses between devices in a slot update is layer 2's table and its
  cotangent, `[Nb, hidden]` float32 each, the slot group's `[Nb, G * F]`
  once a group, and a sum of the parameter gradients; the features, the
  targets and the plans never move. A shard's `refresh.stack.device_put`
  ends with its transfer, so the host holds one shard's copy at a time.

- the DEVICE'S NAMES: the block's body, the heads' forwards and the planned
  reductions stand under `jax.named_scope`s of one small taxonomy
  (`core/programs.SCOPE_PHASES`: gather, reduce, collective, dense, loss,
  optimizer, group; docs/OBSERVABILITY.md says what lies in each). They are
  metadata of the compiled program and cost nothing; `Program.scope_table()`
  of a block reads them back, so a device trace's time can be summed by the
  program's own words. Always there: no option, no branch.

Bit discipline: with the default batch size of 1 the scan body performs
the identical per-slot update sequence as the legacy Python loop; only
array padding (masked, zero-contribution) and float32 loss averaging
differ, so losses and params agree within fp32 tolerance
(tests/test_trainer.py::TestFusedTraining).
"""
from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax import shard_map
from jax.sharding import NamedSharding, PartitionSpec as P

from kmamiz_tpu.core import programs
from kmamiz_tpu.core.spans import _pad_size
from kmamiz_tpu.models import common
from kmamiz_tpu.ops import edge_plan, sparse, sparse_sum
from kmamiz_tpu.parallel import mesh as mesh_mod
from kmamiz_tpu.telemetry.registry import REGISTRY
from kmamiz_tpu.telemetry.tracing import TRACER, operation_span, phase_span

_STACK_BUILDS = REGISTRY.counter(
    "kmamiz_model_stack_builds_total",
    "stack_dataset calls that built and uploaded the stack",
)
_STACK_HITS = REGISTRY.counter(
    "kmamiz_model_stack_hits_total",
    "stack_dataset calls served by the stack memoised on the dataset",
)
_PLAN_BUILDS = REGISTRY.counter(
    "kmamiz_model_edge_plan_builds_total",
    "stack_dataset calls that sorted a topology into an edge plan and uploaded it",
)
_PLAN_HITS = REGISTRY.counter(
    "kmamiz_model_edge_plan_hits_total",
    "stack_dataset calls whose edge plan came from a memo (the stack's, "
    "or that of another dataset over the same edge arrays)",
)


def _resolve_epoch_runner(key: str):
    """Hint resolver for 'models.sage_epoch_block[<module>|lr|pos_weight]':
    rebuild the jitted epoch block for a persisted training config."""
    import importlib

    mod, lr, pw, *nodes = key.split("|")
    if nodes or not mod.startswith("kmamiz_tpu.models."):
        return None  # a node-sharded block is bound to its mesh: no replay from a hint
    return epoch_runner(importlib.import_module(mod), float(lr), float(pw))


def _resolve_dp_epoch_runner(key: str):
    import importlib

    mod, lr, pw, axis = key.split("|")
    if not mod.startswith("kmamiz_tpu.models."):
        return None
    return dp_epoch_runner(
        importlib.import_module(mod), float(lr), float(pw), axis=axis
    )


def _resolve_batched_forward(key: str):
    import importlib

    if not key.startswith("kmamiz_tpu.models."):
        return None
    return _batched_forward(importlib.import_module(key))


programs.register_family("models.sage_epoch_block", _resolve_epoch_runner)
programs.register_family(
    "models.sage_dp_epoch_block", _resolve_dp_epoch_runner
)
programs.register_family("models.batched_forward", _resolve_batched_forward)


@dataclass
class StackedDataset:
    """All slots of a GraphDataset as bucket-padded device arrays."""

    features: jnp.ndarray  # [S, Nb, F] float32
    target_latency: jnp.ndarray  # [S, Nb] float32
    target_anomaly: jnp.ndarray  # [S, Nb] float32
    node_mask: jnp.ndarray  # [S, Nb] bool (False on padded nodes)
    src: jnp.ndarray  # [Eb] int32
    dst: jnp.ndarray  # [Eb] int32
    edge_mask: jnp.ndarray  # [Eb] bool (False on padded edges)
    num_slots: int  # real S
    num_nodes: int  # real N (<= bucket_nodes)
    num_edges: int  # real E (<= bucket_edges)
    bucket_nodes: int
    bucket_edges: int
    plan: Optional[edge_plan.EdgePlan] = None  # of (src, dst, edge_mask); sharded: [shards, ...], a device a plan
    plan_entries: int = 0  # real (owner, neighbour) entries: 2 x real edges
    plan_items: int = 0  # real (node tile, edge block) products of one sum
    plan_blocks: int = 0  # edge blocks those items visit: message blocks a walk fetches
    plan_runs: int = 0  # (owner, direction) runs that hold an entry: the softmaxes
    shards: int = 1  # devices the node axis is cut over (1: everything on one, as ever)
    node_cuts: Tuple[int, ...] = ()  # [shards + 1]: shard d holds nodes cuts[d] .. cuts[d + 1] - 1
    #: the `nodes` mesh of a sharded stack, a device a shard; None on one device
    mesh: Optional[jax.sharding.Mesh] = None

    def layout(self) -> dict:
        """The shape contract a checkpoint records (and resume validates):
        compiled programs and the slot schedule are keyed by exactly
        these."""
        return {
            "bucket_nodes": int(self.bucket_nodes),
            "bucket_edges": int(self.bucket_edges),
            "num_slots": int(self.num_slots),
            "num_nodes": int(self.num_nodes),
        }


def dataset_layout(dataset) -> dict:
    """A GraphDataset's stacked layout WITHOUT building/uploading the
    stack — cheap enough for checkpoint-resume validation."""
    n = dataset.num_nodes
    e = int(np.asarray(dataset.src).shape[0])
    return {
        "bucket_nodes": _pad_size(n),
        "bucket_edges": _pad_size(e),
        "num_slots": len(dataset.features),
        "num_nodes": n,
    }


def stack_dataset(dataset) -> StackedDataset:
    """GraphDataset (per-slot list layout) -> one device-resident stack.

    Memoized on the dataset instance: repeated train/evaluate/calibrate
    calls over the same dataset reuse the single upload instead of
    re-staging S slots each time. Node and edge counts pad to power-of-two
    buckets (graph-store capacity discipline) with False masks, so padded
    rows contribute nothing and bucket-shaped programs are shared across
    datasets of the same bucket.

    Traced as `refresh.stack` (a trace of its own when called alone, a
    child of `refresh.train` inside a refresh), a build split into
    `refresh.stack.plan` (the edge plan, where no memo holds it) and, once a
    shard, `refresh.stack.host_fill` and `refresh.stack.device_put`. On one
    device the spans end where the calls return: the wait for the transfer
    is whoever blocks next; a shard's `device_put` ends with its transfer."""
    with operation_span("refresh.stack"):
        cached = getattr(dataset, "_stacked_cache", None)
        if cached is not None and cached.layout() == dataset_layout(dataset):
            _STACK_HITS.inc()
            _PLAN_HITS.inc()
            TRACER.note(
                hit=1,
                plan_entries=cached.plan_entries,
                plan_items=cached.plan_items,
                plan_blocks=cached.plan_blocks,
                plan_runs=cached.plan_runs,
            )
            return cached
        _STACK_BUILDS.inc()
        stacked = _build_stack(dataset)
        try:
            dataset._stacked_cache = stacked
        except (AttributeError, TypeError):  # frozen/slotted containers
            pass
        return stacked


#: edge plans by the identity of the edge arrays they were made from, the
#: node bucket and the shards: [(src, dst, edge_mask, bucket_nodes, _Planned)],
#: newest last. The arrays are held so that their ids stay theirs; like the
#: stack's memo it trusts that nobody writes into them.
_PLAN_MEMO: list = []
_PLAN_MEMO_SIZE = 4


class _Planned(NamedTuple):
    """A topology's plan as a stack holds it."""

    plan: edge_plan.EdgePlan  # on the device; with a leading [shards] axis over the mesh where shards > 1
    counts: Tuple[int, int, int, int]  # real entries, items, blocks, (owner, direction) runs, over all shards
    shards: int
    #: [shards + 1] node indices: shard d holds nodes cuts[d] .. cuts[d + 1] - 1, from row d * (bucket // shards)
    cuts: Tuple[int, ...]


def _edge_plan(dataset, src, dst, e_mask, n: int, nb: int, shards: int) -> _Planned:
    """The plan of a dataset's padded edge list, for at least `shards` node
    shards: a memoised plan of this topology decides (the widest), so that
    datasets over one graph lie on the devices alike. The span counts what
    the directed head walks: the entries of each direction (an edge out of
    its owner, an edge into it), the (owner, direction) runs, one softmax
    each, the edge blocks the items visit, and each shard's share of them."""
    key = (dataset.src, dataset.dst, dataset.edge_mask)
    held = [
        planned for *arrays, held_nb, planned in _PLAN_MEMO
        if held_nb == nb and planned.shards >= shards and all(a is b for a, b in zip(arrays, key))
    ]
    if held:
        _PLAN_HITS.inc()
        return max(held, key=lambda planned: planned.shards)
    _PLAN_BUILDS.inc()
    with phase_span("refresh.stack.plan"):
        host, cuts, entries_by, items_by = edge_plan.build_shard_plans(src, dst, e_mask, n, nb, shards)
        runs, entries_in, blocks_by = 0, 0, []
        for one, entries, items in zip(edge_plan.sub_plans(host), entries_by, items_by):  # a sub-plan an (owner, source) pair
            run_key = one.owner[0, :entries] * 2 + one.direction[0, :entries]
            runs += int(np.count_nonzero(np.diff(run_key))) + 1 if entries else 0
            entries_in += int(one.direction[0, :entries].sum())
            blocks_by.append(edge_plan.plan_blocks(one, items))
        if shards == 1:
            plan = jax.tree_util.tree_map(lambda a: jnp.asarray(a[0]), host)
        else:
            over = NamedSharding(mesh_mod.nodes_mesh(shards), P(mesh_mod.NODES_AXIS))
            plan = jax.tree_util.tree_map(lambda a: jax.device_put(a, over), host)
        counts = (sum(entries_by), sum(items_by), sum(blocks_by), runs)
        TRACER.note(
            entries=counts[0],
            items=counts[1],
            blocks=counts[2],
            block_reuse_share=round((counts[1] - counts[2]) / max(counts[1], 1), 4),  # items that find their block's work made
            entries_out=counts[0] - entries_in, entries_in=entries_in,
            runs=runs, mxu_products=sparse.route_stats()["mxu_products"],
        )
        if shards > 1:
            by_owner = lambda a: np.reshape(a, (shards, shards)).sum(axis=1).tolist()  # noqa: E731 - the counts are owner-major
            TRACER.note(
                shards=shards, shard_entries=by_owner(entries_by), shard_items=by_owner(items_by),
                shard_blocks=by_owner(blocks_by), shard_nodes=np.diff(cuts).tolist(),
                source_tables=shards, source_entries=list(entries_by), source_items=list(items_by),
            )
    planned = _Planned(plan, counts, shards, tuple(int(c) for c in cuts))
    _PLAN_MEMO.append((*key, nb, planned))
    del _PLAN_MEMO[:-_PLAN_MEMO_SIZE]
    return planned


def plan_for(model, stacked: StackedDataset) -> Optional[edge_plan.EdgePlan]:
    """The stack's edge plan, for a head that says its `forward` (and its
    own loss, where it states one) takes one as `plan=`: `TAKES_PLAN` on the
    head's module (GraphSAGE, GAT, STLGT, PNA). None for a head that says nothing
    and under KMAMIZ_SPARSE=xla (the legacy formulation everywhere). What
    `train()` hands the epoch block."""
    if not sparse.use_sparse() or not getattr(model, "TAKES_PLAN", False):
        return None
    return stacked.plan


def head_loss_fn(model, pos_weight: float, **forward_args):
    """The loss an epoch block differentiates: the head's own where its
    module states one (`make_loss_fn(pos_weight)`), else the family's mean
    squared error and weighted cross-entropy over `model.forward`
    (`common.make_loss_fn`). `forward_args` (the block's `plan`, a slot
    group's `neighbor_sum_1`) are bound by keyword: to the forward, or to a
    head's own loss, which hands them to its forward. Under a
    `edge_plan.ShardPlan` the family's loss sums over the plan's mesh axis."""
    make = getattr(model, "make_loss_fn", None)
    # a `edge_plan.ShardPlan` names the mesh axis the nodes are cut over
    axis = getattr(forward_args.get("plan"), "axis", None)
    if make is None:
        forward = model.forward
        if forward_args:
            forward = functools.partial(forward, **forward_args)
        return common.make_loss_fn(forward, pos_weight, axis_name=axis)
    if axis is not None:
        raise NotImplementedError(f"{model.__name__}'s own loss knows no mesh axis yet")
    loss_fn = make(pos_weight)
    return functools.partial(loss_fn, **forward_args) if forward_args else loss_fn


#: lanes of a row on the chip: a gathered message row and a reducer's block
#: are padded to this many floats whatever their width (PERF.md, PR 27)
ROW_LANES = 128


def slot_group(model, params, features, plan) -> int:
    """How many consecutive slots share one planned sum of layer 1 in the
    epoch block, 0 where every slot makes its own (`epoch_runner`).

    The block groups where it can see that the sum is of data alone: the
    head says its `forward` takes the sum from its caller as `neighbor_sum_1`
    (`TAKES_NEIGHBOR_SUM_1` on its module), the
    parameters hold no node embedding (with one the layer's input holds
    parameters and a gradient goes through the graph), and there is a plan
    to sum over. The size comes from the feature width F and the chip's
    128 lanes alone, 128 // F (7 at F = 18), at most the slots there are;
    a group of one is no group."""
    if plan is None or getattr(params, "embedding", None) is not None:
        return 0
    if not getattr(model, "TAKES_NEIGHBOR_SUM_1", False):
        return 0
    n_slots, _, width = features.shape
    group = min(ROW_LANES // max(width, 1), n_slots)
    return group if group > 1 else 0


# ---------------------------------------------------------------------------
# scan-fused epochs (sequential per-slot schedule, B = 1)
# ---------------------------------------------------------------------------


@functools.lru_cache(maxsize=32)
def epoch_runner(model, lr: float, pos_weight: float):
    """(params, opt_state, stacked arrays, n_epochs) -> (params, opt_state,
    losses [n_epochs, 3]) as ONE jitted program: scan over epochs around a
    scan over slots, one optimizer update per slot — the legacy loop's
    schedule without its per-slot dispatch and transfers. params/opt_state
    are donated (they live and die on device across the whole run).

    The loss is the head's own where it states one (`head_loss_fn`).
    `plan` (the stack's EdgePlan, `plan_for`) is a constant of both scans
    like src/dst/edge_mask, handed to `model.forward` as its `plan`; None
    keeps the forward's own reduction of the edge list, and is another
    program of the same family.

    With a slot group (`slot_group`; `group` overrides it for tests and
    timing, 0 for none, as `impl` does for `planned_neighbor_sum`) an epoch
    is a scan over the groups around a loop over each group's slots: the
    group's layer-1 neighbour sums are made in one planned sum of width
    group x F and handed to each slot's forward as `neighbor_sum_1`. The
    last group of an epoch reaches back to stay inside the stack and starts
    its loop at its own first slot, so any slot count runs the one program;
    the groups are made again every epoch.

    Memoized per (model, lr, pos_weight) so repeated train() calls in one
    process reuse the compiled program family (jit then keys on the
    bucket shapes)."""
    optimizer = model.make_optimizer(lr)
    grad_fn = jax.value_and_grad(head_loss_fn(model, pos_weight), has_aux=True)

    @functools.partial(
        jax.jit,
        static_argnames=("n_epochs", "group"),
        donate_argnames=("params", "opt_state"),
    )
    def sage_epoch_block(
        params,
        opt_state,
        features,
        target_latency,
        target_anomaly,
        node_mask,
        src,
        dst,
        edge_mask,
        n_epochs: int,
        plan=None,
        group: Optional[int] = None,
    ):
        if group is None:
            group = slot_group(model, params, features, plan)

        def slot_step(carry, xs, **summed):
            p, s = carry
            f, tl, ta, nm = xs
            slot_grad = grad_fn
            if plan is not None:
                slot_grad = jax.value_and_grad(
                    head_loss_fn(model, pos_weight, plan=plan, **summed),
                    has_aux=True,
                )
            (loss, (lat_l, ano_l)), grads = slot_grad(
                p, f, src, dst, edge_mask, tl, ta, nm
            )
            with jax.named_scope("optimizer"):
                updates, s = optimizer.update(grads, s, p)
                p = optax.apply_updates(p, updates)
            with jax.named_scope("loss"):
                return (p, s), jnp.stack([loss, lat_l, ano_l])

        def epoch_step(carry, _):
            carry, per_slot = jax.lax.scan(
                slot_step,
                carry,
                (features, target_latency, target_anomaly, node_mask),
            )
            with jax.named_scope("loss"):
                return carry, per_slot.mean(axis=0)

        n_slots, n_nodes, width = features.shape
        at = functools.partial(jax.lax.dynamic_index_in_dim, keepdims=False)

        def group_step(carry, first):
            """The slots [first, first + group) of one epoch: their layer-1
            neighbour sums in one planned sum, then their updates in order."""
            # the last group reaches back to stay inside the stack, and its
            # loop starts at the first slot that is the group's own
            with jax.named_scope("group"):  # its gather and its reducer name themselves beneath it
                start = jnp.minimum(first, n_slots - group)
                packed = jax.lax.dynamic_slice_in_dim(features, start, group)
                table = jnp.moveaxis(packed, 0, 1).reshape(n_nodes, group * width)
                sums = sparse_sum.planned_neighbor_sum(plan, table)  # [Nb, group * F]
                own = first - start  # where the loop below starts

            def member_step(j, carry):
                carry, per_slot = carry
                with jax.named_scope("group"):
                    t = start + j
                    # the features from the group's slice, not from the stack:
                    # with per-slot reads of the stack beside the group's, XLA
                    # re-laid the whole stack slot-major first (5.4 GB, PERF.md)
                    xs = (
                        at(packed, j),
                        at(target_latency, t),
                        at(target_anomaly, t),
                        at(node_mask, t),
                    )
                    mine = jax.lax.dynamic_slice_in_dim(sums, j * width, width, axis=1)
                carry, losses = slot_step(carry, xs, neighbor_sum_1=mine)
                with jax.named_scope("loss"):
                    return carry, per_slot.at[t].set(losses)

            return jax.lax.fori_loop(own, group, member_step, carry), None

        def grouped_epoch_step(carry, _):
            with jax.named_scope("group"):
                firsts = jnp.arange(0, n_slots, group, dtype=jnp.int32)
            with jax.named_scope("loss"):
                per_slot = jnp.zeros((n_slots, 3), jnp.float32)
            (carry, per_slot), _ = jax.lax.scan(group_step, (carry, per_slot), firsts)
            with jax.named_scope("loss"):
                return carry, per_slot.mean(axis=0)

        (params, opt_state), losses = jax.lax.scan(
            grouped_epoch_step if group else epoch_step,
            (params, opt_state),
            None,
            length=n_epochs,
        )
        return params, opt_state, losses

    return programs.register_instance(
        "models.sage_epoch_block",
        f"{model.__name__}|{lr}|{pos_weight}",
        sage_epoch_block,
    )


# ---------------------------------------------------------------------------
# the same epochs over a history sharded by nodes
# ---------------------------------------------------------------------------


@functools.lru_cache(maxsize=8)
def node_sharded_epoch_runner(model, lr: float, pos_weight: float, mesh):
    """`epoch_runner`'s program for a stack cut by nodes over `mesh`: the
    one-device block's own body, a device a shard, under `shard_map`.

    Takes what that block takes, the stack's arrays and its plan as
    `stack_dataset` laid them over the mesh (`[S, Nb, ..]` cut along nodes,
    the plan with a leading device axis), and returns the same: parameters,
    optimizer state and losses replicated. Inside, a device sees its rows
    and its plan as a `edge_plan.ShardPlan`, which is all the body needs to
    know: the planned sums all-gather their table, the loss adds the
    devices' sums and counts, the gradients come out summed, so every
    device makes the same update, one a slot, in slot order. One dispatch a
    call, registered like the block it wraps (`|nodes<D>` on its key)."""
    axis, = mesh.axis_names
    block = epoch_runner(model, lr, pos_weight).fn.__wrapped__
    rows, whole = P(None, axis), P()

    @functools.partial(
        jax.jit,
        static_argnames=("n_epochs", "group"),
        donate_argnames=("params", "opt_state"),
    )
    def sage_epoch_block(
        params, opt_state, features, target_latency, target_anomaly, node_mask,
        src, dst, edge_mask, n_epochs: int, plan=None, group: Optional[int] = None,
    ):
        if plan is None:
            raise ValueError("a node-sharded stack trains over its edge plan; none was handed over")
        if group is None:
            group = slot_group(model, params, features, plan)

        def shard(params, opt_state, features, target_latency, target_anomaly, node_mask,
                  src, dst, edge_mask, plan):
            mine = edge_plan.ShardPlan(jax.tree_util.tree_map(lambda a: a[0], plan), axis)
            return block(
                params, opt_state, features, target_latency, target_anomaly, node_mask,
                src, dst, edge_mask, n_epochs, mine, group,
            )

        return shard_map(
            shard, mesh=mesh,
            in_specs=(whole, whole, rows, rows, rows, rows, whole, whole, whole, P(axis)),
            out_specs=whole, check_vma=False,
        )(params, opt_state, features, target_latency, target_anomaly, node_mask,
          src, dst, edge_mask, plan)

    return programs.register_instance(
        "models.sage_epoch_block",
        f"{model.__name__}|{lr}|{pos_weight}|nodes{mesh.devices.size}",
        sage_epoch_block,
    )


def runner_for(stacked: StackedDataset, model, lr: float, pos_weight: float):
    """The epoch block of a stack as it lies: `epoch_runner`'s on one device,
    `node_sharded_epoch_runner`'s where `stack_dataset` cut it by nodes."""
    if stacked.mesh is None:
        return epoch_runner(model, lr, pos_weight)
    return node_sharded_epoch_runner(model, lr, pos_weight, stacked.mesh)


def require_node_shards(stacked: StackedDataset, model, name: str, params, plan) -> None:
    """Refuse, by name, what cannot train over a node-sharded stack yet;
    nothing to say about a stack on one device."""
    if stacked.shards == 1:
        return
    where = f"a history cut by nodes over {stacked.shards} devices"
    if not getattr(model, "TAKES_NODE_SHARDS", False):
        raise NotImplementedError(
            f"{name} cannot train over {where} yet: its reductions over the graph "
            "know no mesh axis (GraphSAGE's do)"
        )
    if getattr(params, "embedding", None) is not None:
        raise NotImplementedError(
            f"node embeddings cannot train over {where} yet: the embedding table is a "
            "parameter with a row a node, and parameters are replicated"
        )
    if plan is None:
        raise NotImplementedError(
            f"{where} trains over its edge plan alone, and KMAMIZ_SPARSE=xla hands none over"
        )


# ---------------------------------------------------------------------------
# building the stack: the host fill and the hand-over, shard by shard
# ---------------------------------------------------------------------------


def _build_stack(dataset) -> StackedDataset:
    s = len(dataset.features)
    n = dataset.num_nodes
    f = (
        int(np.asarray(dataset.features[0]).shape[1])
        if s
        else 0
    )
    e = int(np.asarray(dataset.src).shape[0])
    nb, eb = _pad_size(n), _pad_size(e)

    src = np.zeros(eb, dtype=np.int32)
    dst = np.zeros(eb, dtype=np.int32)
    e_mask = np.zeros(eb, dtype=bool)
    src[:e] = np.asarray(dataset.src, dtype=np.int32)
    dst[:e] = np.asarray(dataset.dst, dtype=np.int32)
    e_mask[:e] = np.asarray(dataset.edge_mask, dtype=bool)
    # the layout is the data's: the stack's bytes against a device's memory
    planned = _edge_plan(
        dataset, src, dst, e_mask, n, nb, mesh_mod.node_shards(s * nb * (4 * f + 9))
    )
    shards, cuts = planned.shards, planned.cuts
    mesh = mesh_mod.nodes_mesh(shards) if shards > 1 else None
    rows = nb // shards
    nbytes = src.nbytes + dst.nbytes + e_mask.nbytes
    parts = []
    for d in range(shards):
        lo, hi = cuts[d], cuts[d + 1]
        with phase_span("refresh.stack.host_fill"):
            feats = np.zeros((s, rows, f), dtype=np.float32)
            t_lat = np.zeros((s, rows), dtype=np.float32)
            t_ano = np.zeros((s, rows), dtype=np.float32)
            n_mask = np.zeros((s, rows), dtype=bool)
            for i in range(s):
                feats[i, : hi - lo] = np.asarray(dataset.features[i], dtype=np.float32)[lo:hi]
                t_lat[i, : hi - lo] = np.asarray(dataset.target_latency[i], dtype=np.float32)[lo:hi]
                t_ano[i, : hi - lo] = np.asarray(dataset.target_anomaly[i], dtype=np.float32)[lo:hi]
                n_mask[i, : hi - lo] = np.asarray(dataset.node_mask[i], dtype=bool)[lo:hi]
            filled = sum(a.nbytes for a in (feats, t_lat, t_ano, n_mask))
            nbytes += filled
            TRACER.note(bytes=filled + (src.nbytes + dst.nbytes + e_mask.nbytes if d == 0 else 0))
        with phase_span("refresh.stack.device_put"):
            if mesh is None:
                parts.append(tuple(jnp.asarray(a) for a in (feats, t_lat, t_ano, n_mask)))
                TRACER.note(bytes=nbytes)
            else:
                # a shard to its device, and there before the next is filled:
                # the host never holds two, and the span ends with the transfer
                parts.append(tuple(jax.device_put(a, mesh.devices[d]) for a in (feats, t_lat, t_ano, n_mask)))
                jax.block_until_ready(parts[-1])
                TRACER.note(bytes=filled, shard=d)
            if d == 0:  # the edge list rides with the first: whole on every device of a mesh
                to = jnp.asarray if mesh is None else functools.partial(jax.device_put, device=NamedSharding(mesh, P()))
                edges = tuple(to(a) for a in (src, dst, e_mask))
        del feats, t_lat, t_ano, n_mask
    if mesh is None:
        (features, target_latency, target_anomaly, node_mask), = parts
    else:
        by_nodes = NamedSharding(mesh, P(None, mesh_mod.NODES_AXIS))
        features, target_latency, target_anomaly, node_mask = (
            jax.make_array_from_single_device_arrays((s, nb) + part[0].shape[2:], by_nodes, list(part))
            for part in zip(*parts)
        )
    plan_entries, plan_items, plan_blocks, plan_runs = planned.counts
    stacked = StackedDataset(
        features=features,
        target_latency=target_latency,
        target_anomaly=target_anomaly,
        node_mask=node_mask,
        src=edges[0],
        dst=edges[1],
        edge_mask=edges[2],
        num_slots=s,
        num_nodes=n,
        num_edges=e,
        bucket_nodes=nb,
        bucket_edges=eb,
        plan=planned.plan,
        plan_entries=plan_entries,
        plan_items=plan_items,
        plan_blocks=plan_blocks,
        plan_runs=plan_runs,
        shards=shards,
        node_cuts=cuts,
        mesh=mesh,
    )
    TRACER.note(  # on refresh.stack
        hit=0,
        bytes=nbytes,
        plan_entries=plan_entries,
        plan_items=plan_items,
        plan_blocks=plan_blocks,
        plan_runs=plan_runs,
    )
    if shards > 1:
        TRACER.note(shards=shards, nodes_per_shard=rows)
    return stacked


# ---------------------------------------------------------------------------
# data-parallel epochs (slot microbatches, optionally mesh-sharded)
# ---------------------------------------------------------------------------


def batch_slots_arrays(
    stacked: StackedDataset, batch: int
) -> Tuple[jnp.ndarray, ...]:
    """Regroup the stacked slot arrays into [n_batches, batch, ...] with a
    per-slot weight array ([n_batches, batch], 0.0 on padding slots) so
    the last partial batch contributes only its real slots."""
    if stacked.shards > 1:
        raise NotImplementedError(
            "slot microbatches regroup the whole stack on one device; a history cut by "
            f"nodes over {stacked.shards} devices trains one slot an update (batch_slots=1, no mesh)"
        )
    s = stacked.num_slots
    nb = -(-s // batch)  # ceil
    pad = nb * batch - s

    def group(a):
        if pad:
            a = jnp.concatenate(
                [a, jnp.zeros((pad,) + a.shape[1:], a.dtype)], axis=0
            )
        return a.reshape((nb, batch) + a.shape[1:])

    weights = jnp.concatenate(
        [jnp.ones(s, jnp.float32), jnp.zeros(pad, jnp.float32)]
    ).reshape(nb, batch)
    return (
        group(stacked.features),
        group(stacked.target_latency),
        group(stacked.target_anomaly),
        group(stacked.node_mask),
        weights,
    )


@functools.lru_cache(maxsize=32)
def dp_epoch_runner(
    model,
    lr: float,
    pos_weight: float,
    mesh=None,
    axis: str = "slots",
):
    """Scan-fused epochs over SLOT MICROBATCHES: per-slot grads inside a
    batch are computed together (vmap) and averaged by slot weight before
    ONE optimizer update — minibatch SGD over slots rather than the
    sequential schedule, trading bit-parity with the legacy loop for a
    batch axis that shards.

    With `mesh`, the batch axis is sharded across the mesh's devices and
    grads merge with a psum over ICI (parallel/mesh.make_sharded_slot_grad);
    params stay replicated, so the returned update is identical to the
    unsharded microbatch on one device (tests/test_parallel.py asserts
    this grad parity)."""
    optimizer = model.make_optimizer(lr)
    grad_fn = jax.value_and_grad(head_loss_fn(model, pos_weight), has_aux=True)

    if mesh is not None:
        from kmamiz_tpu.parallel.mesh import make_sharded_slot_grad

        batch_grads = make_sharded_slot_grad(mesh, grad_fn, axis=axis)
    else:

        def batch_grads(params, feats, tl, ta, nm, src, dst, em, w):
            def per_slot(f, l, a, m, wi):
                (loss, (lat_l, ano_l)), g = grad_fn(
                    params, f, src, dst, em, l, a, m
                )
                g = jax.tree_util.tree_map(lambda x: x * wi, g)
                return g, loss * wi, lat_l * wi, ano_l * wi

            gs, ls, lat, ano = jax.vmap(per_slot)(feats, tl, ta, nm, w)
            wsum = jnp.maximum(w.sum(), 1.0)
            g = jax.tree_util.tree_map(lambda x: x.sum(0) / wsum, gs)
            return g, ls.sum() / wsum, lat.sum() / wsum, ano.sum() / wsum

    @functools.partial(
        jax.jit,
        static_argnames=("n_epochs",),
        donate_argnames=("params", "opt_state"),
    )
    def sage_dp_epoch_block(
        params,
        opt_state,
        b_features,  # [n_batches, B, Nb, F]
        b_target_latency,
        b_target_anomaly,
        b_node_mask,
        b_weights,  # [n_batches, B]
        src,
        dst,
        edge_mask,
        n_epochs: int,
    ):
        def batch_step(carry, xs):
            p, s = carry
            f, tl, ta, nm, w = xs
            g, loss, lat_l, ano_l = batch_grads(
                p, f, tl, ta, nm, src, dst, edge_mask, w
            )
            updates, s = optimizer.update(g, s, p)
            p = optax.apply_updates(p, updates)
            return (p, s), jnp.stack([loss, lat_l, ano_l]) * w.sum()

        def epoch_step(carry, _):
            carry, per_batch = jax.lax.scan(
                batch_step,
                carry,
                (
                    b_features,
                    b_target_latency,
                    b_target_anomaly,
                    b_node_mask,
                    b_weights,
                ),
            )
            # slot-weighted epoch mean: partial final batches count only
            # their real slots
            return carry, per_batch.sum(axis=0) / jnp.maximum(
                b_weights.sum(), 1.0
            )

        (params, opt_state), losses = jax.lax.scan(
            epoch_step, (params, opt_state), None, length=n_epochs
        )
        return params, opt_state, losses

    # mesh-sharded runners stay unregistered (device-bound programs can't
    # replay from a hint on a different topology); single-device
    # microbatch runs register like the sequential block
    if mesh is None:
        return programs.register_instance(
            "models.sage_dp_epoch_block",
            f"{model.__name__}|{lr}|{pos_weight}|{axis}",
            sage_dp_epoch_block,
        )
    return sage_dp_epoch_block


# ---------------------------------------------------------------------------
# batched evaluation forward
# ---------------------------------------------------------------------------


@functools.lru_cache(maxsize=16)
def _batched_forward(model):
    # a function of its own, named after its registry base as the epoch
    # blocks are: the device module reads jit_batched_forward in a trace
    @jax.jit
    def batched_forward(params, features, src, dst, edge_mask):
        return jax.vmap(model.forward, in_axes=(None, 0, None, None, None))(
            params, features, src, dst, edge_mask
        )

    return programs.register_instance(
        "models.batched_forward", model.__name__, batched_forward
    )


def predict_all(
    params, dataset, model
) -> Optional[Tuple[np.ndarray, np.ndarray]]:
    """One vmapped jitted forward over EVERY slot of the dataset ->
    (pred_latency [S, N], anomaly_logits [S, N]) as host arrays, sliced
    back to the real node count. None for an empty dataset."""
    if not len(dataset.features):
        return None
    st = stack_dataset(dataset)
    if st.shards > 1:
        raise NotImplementedError(
            f"no batched forward over a history cut by nodes over {st.shards} devices yet"
        )
    lat, logit = _batched_forward(model)(
        params, st.features, st.src, st.dst, st.edge_mask
    )
    n = st.num_nodes
    return np.asarray(lat)[:, :n], np.asarray(logit)[:, :n]
