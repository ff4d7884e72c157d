"""Pallas TPU kernels for the window-pipeline hot ops.

The span-window groupby (window_stats) is a segment reduction: ~1M spans
scatter-add into ~80k (endpoint, status) segments. XLA lowers
jax.ops.segment_sum to scatter, which the TPU executes with serialized
index handling; this module reformulates the reduction as ONE-HOT MATMUL
so it rides the MXU instead:

    partial[m, S_blk] += values[m, K_blk] @ one_hot[K_blk, S_blk]

with the grid arranged (segment blocks outer/parallel, span blocks
inner/arbitrary) so each output tile accumulates in VMEM across span
blocks. The timestamp max reduction shares the same one-hot mask on the
VPU. This is the classic TPU sparse-reduction shape (SpMM via dense
masking — see PAPERS.md) applied to the reference's hottest loop
(kmamiz_data_processor/src/data/realtime_data.rs:31-121 groupby).

Use KMAMIZ_SEGMENT_BACKEND=pallas to switch the DataProcessor stats path
(server/processor.py consults segment_backend()); window_stats also takes
`backend=` directly. 'pallas' compiles under Mosaic and raises where
Mosaic cannot target the backend; only 'pallas_interpret' interprets.

Design note: the dense one-hot does N*S work against the scatter's N, so
XLA's scatter stays the default at the production shape (1M spans x 80k
segments) until a chip measurement says otherwise. The MXU idea carries
over where the operand structure fits the systolic array: the
trace-row-packed ancestor walk (window.dependency_edges_packed) is built
on this kernel's one-hot-einsum pattern with row-LOCAL (64-slot)
one-hots. Numerical note: matmul accumulation reassociates float adds,
so sums can differ from the scatter path by float32 rounding
(tests/test_ops_window.py asserts tight rtol, counts and maxes exact).
"""
from __future__ import annotations

import os
from functools import partial
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# block sizes: K spans x BS segments per tile; both ride the f32 (8, 128)
# tiling and keep the one-hot tile (K*BS*4B = 1MB) well inside VMEM
SPAN_BLOCK = 512
SEG_BLOCK = 512


def segment_backend(default: str = "xla") -> str:
    """Process-wide segment-reduction backend: 'xla' (scatter) or 'pallas'
    (one-hot MXU matmul). Overridable via KMAMIZ_SEGMENT_BACKEND."""
    return os.environ.get("KMAMIZ_SEGMENT_BACKEND", default)


def _segment_stats_kernel(seg_ref, vals_ref, ts_ref, sums_ref, maxs_ref):
    n_idx = pl.program_id(1)

    @pl.when(n_idx == 0)
    def _init():
        sums_ref[...] = jnp.zeros_like(sums_ref)
        maxs_ref[...] = jnp.zeros_like(maxs_ref)

    # per-span ids arrive as a COLUMN (spans on sublanes), so the one-hot
    # is a lane-broadcast compare and nothing moves between the lane and
    # the sublane axis. Not forced by Mosaic: the earlier form (ids read
    # as a 1-D vector from a (1, K) block, 3 unpadded stat rows) compiles
    # and agrees with XLA too (probe, PERF.md PR 21); neither was timed
    seg_base = pl.program_id(0) * SEG_BLOCK
    # one_hot[k, s] = 1 iff span k belongs to segment (seg_base + s)
    local = jax.lax.broadcasted_iota(jnp.int32, (SPAN_BLOCK, SEG_BLOCK), 1)
    hit = seg_ref[...] == seg_base + local  # [K, BS]

    # all m stat rows reduce in one MXU pass: [m, K] @ [K, BS] -> [m, BS].
    # HIGHEST precision: the default lowers f32 matmul to bf16 MXU passes,
    # which costs ~0.5% relative error on latency sums
    sums_ref[...] += jnp.dot(
        vals_ref[...],
        hit.astype(jnp.float32),
        preferred_element_type=jnp.float32,
        precision=jax.lax.Precision.HIGHEST,
    )

    # timestamp max on the VPU over the same mask, in int32 (f32 would
    # round offsets above 2^24); identity 0: rel timestamps are
    # non-negative and empty segments report 0
    masked = jnp.where(hit, ts_ref[...], 0)
    maxs_ref[...] = jnp.maximum(
        maxs_ref[...], jnp.max(masked, axis=0, keepdims=True)
    )


@partial(jax.jit, static_argnames=("num_segments", "interpret"))
def segment_stats_matmul(
    values: jnp.ndarray,
    seg: jnp.ndarray,
    ts: jnp.ndarray,
    num_segments: int,
    interpret: bool = False,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Segment-sum every row of values[m, N] and segment-max ts[N] by
    seg[N] int32 ids in [0, num_segments); rows with seg >= num_segments
    are dropped (the caller parks padded/invalid spans there).

    Returns (sums[m, num_segments] f32, ts_max[num_segments] int32).
    """
    m, n = values.shape
    m_pad = -(-m // 8) * 8  # whole f32 sublane tiles for the MXU operand
    n_pad = -(-n // SPAN_BLOCK) * SPAN_BLOCK
    # at least one spill block so parked ids stay in-range of the iota grid
    s_pad = -(-(num_segments + 1) // SEG_BLOCK) * SEG_BLOCK

    values = jnp.pad(
        values.astype(jnp.float32), ((0, m_pad - m), (0, n_pad - n))
    )
    # padded spans park at num_segments (first spill slot)
    seg = jnp.pad(
        seg.astype(jnp.int32), (0, n_pad - n), constant_values=num_segments
    )
    seg = jnp.where(seg >= num_segments, num_segments, seg)
    ts = jnp.pad(ts.astype(jnp.int32), (0, n_pad - n))

    grid = (s_pad // SEG_BLOCK, n_pad // SPAN_BLOCK)
    sums, maxs = pl.pallas_call(
        _segment_stats_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((SPAN_BLOCK, 1), lambda s, n_: (n_, 0)),
            pl.BlockSpec((m_pad, SPAN_BLOCK), lambda s, n_: (0, n_)),
            pl.BlockSpec((SPAN_BLOCK, 1), lambda s, n_: (n_, 0)),
        ],
        out_specs=[
            pl.BlockSpec((m_pad, SEG_BLOCK), lambda s, n_: (0, s)),
            pl.BlockSpec((1, SEG_BLOCK), lambda s, n_: (0, s)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((m_pad, s_pad), jnp.float32),
            jax.ShapeDtypeStruct((1, s_pad), jnp.int32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")
        ),
        interpret=interpret,
    )(seg[:, None], values, ts[:, None])
    return sums[:m, :num_segments], maxs[0, :num_segments]
