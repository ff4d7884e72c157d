"""The walk: one pass of a Mosaic kernel over an edge plan's items, and the helpers every family's walks share.

A walk's grid steps are the plan's items (ops/edge_plan.py), each one (node
tile, edge block) pair. Inside a kernel a per-entry scalar lives in a ROW
([8, block], entries on lanes) and a per-node scalar of the tile in a row too
([8, tile]); the item's one-hot [tile, block] carries one into the other on
the MXU (exact: the one-hot is exact in bfloat16, the value goes as three
bfloat16 pieces). What belongs to the NEIGHBOUR travels with its gathered
row: the message array is [entries, 128 lanes] whatever the width (PERF.md),
so the lanes past the width hold the neighbour's scalars, and the kernel
turns those columns into rows by transposing the block. So there is no 1-D
gather, and no scatter. The transposed sums (over the entries whose NEIGHBOUR
is j) need no permutation either: every entry has a mirror, the same edge
seen from its other end, so they are sums over the mirrors of the entries j
OWNS, made where the walk has the mirror's operands at hand.

What a walk costs (PERF.md, PR 31) is its MXU passes and its grid steps,
not its vector work, which hides behind them: a pass pays for the
[128, 128] tiles it loads as the stationary operand however few rows stream
against them, and a step pays about 0.1 ms a walk for every block it
fetches or writes, whatever the block's size. So:

- the per-entry scalars are ONE operand, the entries' state [8, entries]:
  the plan's owner and direction (as bits) and a row for each of a family's
  per-entry values (`ROW_*`). A walk reads a block of it and hands it on with
  its own row added (`item`);
- `expand` sends the three pieces of a row table through the one-hot in
  one pass, stacked; the neighbour's scalars come out of the float32 block
  by a transposition, which the MXU took twelve tile loads for;
- a WEIGHTED sum of gathered rows (`weighted_sum`) multiplies on the VPU, one
  float32 multiplication an element as the oracles and the plain references
  make it, and sends only the one-hot through the MXU, against the exact
  split of the product: three passes. Until PR 34 the weight went through the
  MXU too, as a split [tile, block] tile, in six (a term was then kept to
  2^-24 of the SUM; now it is rounded once to float32, as the reference's
  is). The rows meet their weights transposed (a lane an entry, so the
  [1, block] weight row broadcasts along sublanes). The sums leave their
  walks as they come out of `reduce`, [lanes, nodes], and XLA transposes them
  once: the tile transposed back in every item cost 0.2 ms a walk, and XLA's
  glue around a [nodes, lanes] output 2 ms a layer more (PERF.md, PR 34; the
  gated walks' too since PR 38: under the layout their block has now,
  0.34 ms a slot update less). The weight as a column against untransposed
  rows cost more;
- a per-entry DOT product (`entry_dot`) is no [tile, block] matrix product
  for the one-hot to pick from. An entry has ONE owner, so the owner's row
  goes to its entries through the one-hot, exactly (`expand` at 128 rows:
  one product, 4 stationary tile loads and 1,536 streamed rows), and meets
  the gathered row on the VPU, one float32 multiplication an element and a
  float32 sum over the sublanes, as the oracles and the plain references
  make it. Until PR 38 `_dot6` made all 128 x 512 products of an item in six
  passes (24 tile loads, 3,072 rows) and threw 127 of every 128 away: 1.1 ms
  a slot update in GAT's `_edge_dot` and 1.4 in its `_backward`. Rows an item
  expands anyway ride in the same product (GAT's backward's s, c and ones:
  0.23 ms; the gated backward's g_den measured nothing and stays apart). The
  owner's rows arrive as `node_rows` [lanes, nodes]: transposed in the item
  on the XLU they cost a walk 0.2 ms more AND XLA's part 0.8 ms a slot update
  (in STLGT 4.7: one [nodes, 128] operand holds the whole block's [nodes, 64]
  arrays to the layout that pads 64 floats to 128 lanes; PERF.md, PR 38);
- `reduce` keeps its three passes and their order: stacking those changes
  the bits;
- a float32 block is split where it is used, once an item. Splitting it
  once a block or a tile into VMEM, or once a layer into HBM, measured
  SLOWER on the v5e (the split hides behind the MXU; a conditional region
  and 805 MB of pieces do not);
- a walk whose step is shorter than the latency of its message block's
  fetch keeps a ring of three blocks in VMEM and fetches two ahead
  (`message_block`).

No environment name picks between a family's Pallas kernels and their XLA
twins: the platform does (`planned_impl`). The reductions over a plan are
counted here, at trace time (`count`, `route_counts`), and read through
`ops/sparse.route_stats()`.
"""
from __future__ import annotations

import contextlib
import threading
from functools import partial

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from kmamiz_tpu.ops.edge_plan import PLAN_EDGE_BLOCK, PLAN_NODE_TILE, EdgePlan, plan_tiles

ATT_ROWS = 8  # sublanes of a row table: at most eight scalars per entry or node
ATT_SPARE = 8  # lanes kept past the width for the neighbour's scalars
#: rows of the entries' state; a walk adds one to zeros, and the plan's owner
#: and direction ride below them as bits
ROW_Z, ROW_P, ROW_ALPHA, ROW_DALPHA, ROW_OWNER, ROW_DIR = 0, 1, 2, 3, 6, 7
MSG_RING = 3  # message blocks `message_block` holds in VMEM: the item's, and two on their way
NEG = -1e30  # below every score; an empty run's maximum
NN = (((1,), (0,)), ((), ()))  # [m, k] @ [k, n]
NT = (((1,), (1,)), ((), ()))  # [m, k] @ [n, k]^T

_ROUTES = ("planned", "attention", "gatv2", "sharded")
_lock = threading.Lock()
#: reductions over an edge plan since process start (trace-time counts: the consumers decide inside their
#: jit traces); `walk_call`'s products, and the arrays a walk keeps an edge block (`scratch`)
_counts: dict = {}


def reset_counts() -> None:
    with _lock:
        _counts.update(dict.fromkeys(_ROUTES, 0), mxu_products={}, block_arrays={})


reset_counts()


def count(*routes: str) -> None:
    """One more reduction over a plan, under each of `routes`."""
    with _lock:
        for route in routes:
            _counts[route] += 1


def route_counts() -> dict:
    """The counts, and each walk's MXU products and kept block arrays (new dicts on every change: the reader's
    copy stays as it was read)."""
    with _lock:
        return dict(_counts)


class _MxuCalls(threading.local):
    n = 0  # `mxu` calls this thread has traced: `walk_call` reads it around a kernel


_mxu_calls = _MxuCalls()


@contextlib.contextmanager
def uncounted():
    """The `mxu` calls traced inside are not counted: a second body of an item, of which one runs."""
    before = _mxu_calls.n
    yield
    _mxu_calls.n = before


def planned_impl() -> str:
    """Which reducer a planned reduction traces to: the Mosaic kernels on a TPU,
    plain XLA elsewhere. The platform alone decides; a test reaches the
    interpreted kernels through the callers' `impl` argument."""
    return "pallas" if jax.default_backend() == "tpu" else "xla"


def split3(x):
    """A float32 array as three bfloat16 pieces that sum to it exactly."""
    hi = x.astype(jnp.bfloat16)
    rest = x - hi.astype(jnp.float32)
    mid = rest.astype(jnp.bfloat16)
    lo = (rest - mid.astype(jnp.float32)).astype(jnp.bfloat16)
    return hi, mid, lo


def mxu(a, b, dims):
    _mxu_calls.n += 1
    return jax.lax.dot_general(a, b, dims, preferred_element_type=jnp.float32)


def expand(node_rows, hot):
    """[R, tile] node rows -> [R, block] entry rows by owner (0 for an entry
    no row of the tile owns). ONE pass of the one-hot through the MXU, the
    three pieces of the rows below one another, where a pass a piece loads the
    same [tile, block] one-hot three times: an entry has one owner, so each
    result is one piece itself and the sum is the three separate passes' to
    the bit."""
    r = node_rows.shape[0]
    stacked = jnp.concatenate([p.astype(jnp.float32) for p in split3(node_rows)], axis=0)
    x = mxu(stacked.astype(jnp.bfloat16), hot, NN)  # [3R, block]
    return sum((x[2 * r : 3 * r], x[r : 2 * r], x[0:r]))


def reduce(entry_rows, hot):
    """[R, block] entry rows -> [R, tile]: each node's sum over its entries."""
    return sum(mxu(piece, hot, NT) for piece in reversed(split3(entry_rows)))


def rows(*vectors):
    """[1, n] rows stacked into an [ATT_ROWS, n] table, zeros below."""
    n = vectors[0].shape[1]
    rid = jax.lax.broadcasted_iota(jnp.int32, (ATT_ROWS, n), 0)
    out = jnp.zeros((ATT_ROWS, n), jnp.float32)
    for k, v in enumerate(vectors):
        out = jnp.where(rid == k, v, out)
    return out


def by_direction(d, x0, x1):
    return jnp.where(d == 0, x0, x1)


def leaky(z, leak):
    return jnp.where(z >= 0, z, leak * z)


def rows_by_direction(d, x):
    """A per-entry row as two, one per direction: what `reduce` turns into
    each node's sum over its out-entries and over its in-entries."""
    return rows(jnp.where(d == 0, x, 0.0), jnp.where(d == 1, x, 0.0))


def weighted_sum(rows_t, weight, hot):
    """[R, block] gathered rows, transposed (a lane an entry), and their
    [1, block] weights -> [R, tile]: each node's sum of `weight_e * row_e`
    over its entries. The product is one float32 multiplication an element on
    the VPU, as the XLA oracles and the plain references make it; only the
    one-hot, exact in bfloat16, goes through the MXU, against the exact split
    of the product: `reduce`'s three passes, where a split [tile, block]
    weight tile against the split rows spent six (until PR 34)."""
    return reduce(rows_t * weight, hot)


def entry_dot(own_rows, entry_rows_t):
    """[R, block] rows of each entry's OWNER, as `expand` carries them through
    the one-hot (exactly: an entry has one owner), and the [R, block] rows of
    the entries themselves, transposed (a lane an entry) -> [1, block]: each
    entry's dot product, +0.0 where no row of the tile owns it. One float32
    multiplication an element on the VPU and a float32 sum over the sublanes,
    as the XLA oracles and the plain references make it: by
    halves down to a vreg's eight sublanes, a pairwise sum in an order the
    source fixes. Until PR 38 a split [tile, R] tile against the split rows
    made every owner's product with every entry in six passes, and the one-hot
    picked one in 128."""
    terms = own_rows * entry_rows_t
    while terms.shape[0] % (2 * ATT_ROWS) == 0:
        terms = terms[: terms.shape[0] // 2] + terms[terms.shape[0] // 2 :]
    return jnp.sum(terms, axis=0, keepdims=True)


def new_block(block_ref):
    """Whether this item is the first of its edge block."""
    i = pl.program_id(0)
    return (i == 0) | (block_ref[i] != block_ref[jnp.maximum(i - 1, 0)])


def message_block(block_ref, msg_hbm, ring, sems):
    """The item's [block, lanes] message block, from a ring in VMEM that the
    walk fills itself, `MSG_RING - 1` blocks ahead of the one in use: the
    pipeline's one block ahead leaves a short step waiting on the fetch. It
    leans on the plan: the real items' blocks start at 0 and rise by at most
    one, and the no-ops after them repeat the last."""
    i = pl.program_id(0)
    b = block_ref[i]
    n_blocks = block_ref[pl.num_programs(0) - 1] + 1  # the no-ops repeat the last real block
    be = ring.shape[1]

    def copy(k):
        slot = k % MSG_RING
        return pltpu.make_async_copy(
            msg_hbm.at[pl.ds(pl.multiple_of(k * be, be), be), :], ring.at[slot], sems.at[slot]
        )

    @pl.when(i == 0)
    def _first():
        for k in range(MSG_RING - 1):
            @pl.when(k < n_blocks)
            def _start():
                copy(k).start()

    @pl.when(new_block(block_ref))
    def _fetch():
        @pl.when(b + MSG_RING - 1 < n_blocks)
        def _ahead():
            copy(b + MSG_RING - 1).start()

        copy(b).wait()

    return ring.at[b % MSG_RING]


def row(state_ref, at: int):
    return state_ref[at : at + 1, :]


def add_row(next_ref, at: int, x):
    """A walk's own row of the state: the sum over the block's items, each of
    which holds the entries of its tile and zeros elsewhere."""
    next_ref[at : at + 1, :] += x


def item(tile_ref, block_ref, flag_ref, state_ref, next_ref=None, by_tile=(), fill=0.0):
    """What every walk starts from. The entries' state is ONE [ATT_ROWS, L]
    operand that a walk reads a block of and hands on with its own row added
    (`next_ref`: copied on the first visit of the edge block, so that the row
    the walk accumulates starts at zero and the others travel on); the plan's
    owner and direction ride in it as bits. Fills the per-node outputs
    `by_tile` on the first item of their tile. Returns (whether the item is
    real and not padding, its one-hot [tile, block] as a mask, the entries'
    direction [1, block])."""
    i = pl.program_id(0)
    flag = flag_ref[i]
    tn, be = PLAN_NODE_TILE, state_ref.shape[1]
    as_int = partial(jax.lax.bitcast_convert_type, new_dtype=jnp.int32)
    row_in_tile = as_int(row(state_ref, ROW_OWNER)) - tile_ref[i] * tn  # [1, block]
    one_hot = row_in_tile == jax.lax.broadcasted_iota(jnp.int32, (tn, be), 0)

    if next_ref is not None:
        @pl.when(new_block(block_ref))
        def _hand_on():
            next_ref[...] = state_ref[...]

    @pl.when(flag == 1)
    def _new_tile():
        for ref in by_tile:
            ref[...] = jnp.full_like(ref, fill)

    return flag >= 0, one_hot, as_int(row(state_ref, ROW_DIR))


def walk_call(plan: EdgePlan, kernel, name: str, inputs, outputs, interpret: bool, scratch=()):
    """One walk of the plan's items. `inputs` and `outputs` are (kind, array or lane width) pairs; the kind says how a
    block follows the item: "entry" [R, L] by edge block, "message" [L, lanes] by edge block, "node_rows" [R, nodes] by
    tile, "node" [nodes, lanes] by tile. A "message_ring" input stays in HBM whole: the kernel fetches its blocks
    itself (`message_block`), into a ring and with the semaphores it is handed after its outputs. `scratch`: (rows,
    dtype) pairs, [rows, block] arrays in VMEM handed last, which outlive the item: what a walk makes once an edge
    block. Counted under `route_counts()`' "mxu_products" and, where it keeps arrays, "block_arrays"."""
    with _lock:  # a new dict, as for the products; a walk that keeps nothing is not named
        _counts["block_arrays"] = {**_counts["block_arrays"], **({name: len(scratch)} if scratch else {})}
    tn, be = PLAN_NODE_TILE, PLAN_EDGE_BLOCK
    entries, nodes = plan.owner.shape[1], plan_tiles(plan) * tn

    def spec(kind, lanes):
        if kind == "entry":
            return pl.BlockSpec((lanes, be), lambda i, tile, block, flag: (0, block[i]))
        if kind == "message":
            return pl.BlockSpec((be, lanes), lambda i, tile, block, flag: (block[i], 0))
        if kind == "message_ring":
            return pl.BlockSpec(memory_space=pl.ANY)
        if kind == "node_rows":
            return pl.BlockSpec((lanes, tn), lambda i, tile, block, flag: (0, tile[i]))
        return pl.BlockSpec((tn, lanes), lambda i, tile, block, flag: (tile[i], 0))

    def shape(kind, lanes):
        if kind == "entry":
            return (lanes, entries)
        if kind == "node_rows":
            return (lanes, nodes)
        return (nodes, lanes)

    def lanes_of(kind, a):
        return a.shape[0] if kind in ("entry", "node_rows") else a.shape[1]

    def counted(*refs):
        before = _mxu_calls.n
        kernel(*refs)
        with _lock:  # a new dict: `route_counts()` hands out the old one
            _counts["mxu_products"] = {**_counts["mxu_products"], name: _mxu_calls.n - before}

    walk = pl.pallas_call(
        counted,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(plan.item_tile.shape[0],),
            in_specs=[spec(kind, lanes_of(kind, a)) for kind, a in inputs],
            out_specs=[spec(kind, lanes) for kind, lanes in outputs],
            scratch_shapes=[
                scratch
                for kind, a in inputs
                if kind == "message_ring"
                for scratch in (
                    pltpu.VMEM((MSG_RING, be, a.shape[1]), jnp.float32),
                    pltpu.SemaphoreType.DMA((MSG_RING,)),
                )
            ] + [pltpu.VMEM((n_rows, be), dtype) for n_rows, dtype in scratch],
        ),
        out_shape=[
            jax.ShapeDtypeStruct(shape(kind, lanes), jnp.float32)
            for kind, lanes in outputs
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",), vmem_limit_bytes=64 << 20
        ),
        name=name,
        interpret=interpret,
    )
    with jax.named_scope("reduce"):
        return walk(plan.item_tile, plan.item_block, plan.item_flag, *(a for _kind, a in inputs))


def node_table(nodes: int, lanes: int, *parts):
    """[n, w] parts side by side, padded to [nodes, lanes] with zeros."""
    table = jnp.concatenate([p.astype(jnp.float32) for p in parts], axis=1)
    return jnp.pad(table, ((0, nodes - table.shape[0]), (0, lanes - table.shape[1])))


def halves(nodes: int, half: int, left, right):
    """[n, w] `left` and `right` side by side, each padded to `half` lanes,
    rows padded to `nodes`: a table of two halves. None is a half of zeros."""
    def pad(a):
        if a is None:
            return jnp.zeros((nodes, half), jnp.float32)
        a = a.astype(jnp.float32)
        return jnp.pad(a, ((0, nodes - a.shape[0]), (0, half - a.shape[1])))

    return jnp.concatenate([pad(left), pad(right)], axis=1)


def gather_rows(table, neighbour):
    """`table[neighbour]` at the table's full lane width. Behind a barrier:
    XLA otherwise gathers the columns that are not padding and pads the
    [entries, lanes] result in a pass of its own, which costs more than the
    gather saves (a gathered row fills its 128 lanes in memory either way)."""
    with jax.named_scope("gather"):
        return jax.lax.optimization_barrier(table)[neighbour]


def node_rows(nodes: int, *columns):
    """[n] columns as the rows of an [ATT_ROWS, nodes] table; after them a
    row of ones, which a walk expands into "some row of this tile owns the
    entry"; zeros below and past n. From columns, also where a walk has just
    made the values as rows: stacked from slices of a kernel's row output
    the table cost the next walk 0.4 ms more on the v5e (PERF.md, PR 28)."""
    out = [jnp.pad(c.astype(jnp.float32), (0, nodes - c.shape[0])) for c in columns]
    out.append(jnp.ones(nodes, jnp.float32))
    out += [jnp.zeros(nodes, jnp.float32)] * (ATT_ROWS - len(out))
    return jnp.stack(out)


def entry_state(plan: EdgePlan):
    """The state of the entries before the first walk, [ATT_ROWS, L]: the
    plan's owner and direction as bits in its last two rows, zeros above."""
    bits = jax.lax.bitcast_convert_type(
        jnp.concatenate([plan.owner, plan.direction], axis=0), jnp.float32
    )
    return jnp.pad(bits, ((ATT_ROWS - 2, 0), (0, 0)))
