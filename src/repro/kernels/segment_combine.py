"""Pallas TPU kernel for the Scatter-Combine ⊕ (paper §4's combine).

TPU adaptation of the paper's active-message combine: instead of per-message
atomic updates behind vLock (CPU), the irregular scatter becomes **block-local
one-hot reductions** over dst-sorted edges:

  * edges are sorted by destination (done once at graph ingress, like the
    paper's CSR build, §6.1.1);
  * a BLOCK SCHEDULE lists the (dst block, edge block) pairs whose ranges
    intersect, ordered by dst block — the CSR row-index analogue.  The grid
    is one step per scheduled visit, so the work is proportional to the
    visited pairs (≤ n_edge_blocks + n_dst_blocks for sorted edges), never
    to n_dst_blocks × the widest row;
  * each visit builds the `[BV, BE]` one-hot of its edge block against its
    dst block and reduces it: `msgs @ onehotᵀ` on the MXU for sum ⊕, a
    masked lane reduction on the VPU for min/max ⊕ (one payload lane at a
    time).  Consecutive visits of one dst block accumulate into the same
    VMEM output block, which is written back when the dst block changes.

Edges run along the 128-wide lane axis: D-wide messages are carried
transposed as `[D, E]`, while scalar messages and destinations stay the
`[E]` columns they are, so no operand is padded, transposed or relaid
out per call; the kernel masks the lanes of the last edge block that run
past E.

THREE functions produce the same `[2, G]` int32 schedule (row 0: dst block,
row 1: edge block; see docs/kernels.md):

  build_block_table    — host-side, at ingress, over a STATIC dst-sorted
                         edge column (the dense-path schedule a partition
                         carries as `DevicePartition.combine_table`);
  dynamic_block_table  — the same construction IN-GRAPH each superstep for
                         a data-dependent (gathered, then dst-sorted) tile —
                         the default for the frontier-compacted combine;
  full_block_table     — every (dst block, edge block) pair, kept only for
                         `dynamic=False` (the documented escape hatch when
                         the pruning pass is disabled).

Every dst block appears at least once (a visit with edge block
`n_edge_blocks` — the skip sentinel — initializes an untouched block to the
identity), and trailing padding visits repeat the last dst block with the
sentinel, so a schedule can be padded to a static length.

The schedule is scalar-prefetched into SMEM (1 MiB on v5e); schedules longer
than `MAX_VISITS` run as a sequence of kernel calls over consecutive slices,
each aliasing the previous call's output, and a dst block cut by a slice
boundary resumes from that output.

VMEM per grid step (defaults BE=1024, BV=256, f32): the double-buffered
blocks `2·(BE + D₈·BE + 2·D₈·BV)` words (row counts pad to 8 sublanes:
D₈ = D rounded up to 8, a scalar payload's block is 1-D; the output
block and the resumed-output input) plus the `[BV, BE]` one-hot and its
masked copy, 2·BV·BE words — about 2.1 MiB at D=1 and 2.8 MiB at D=64,
inside the 16 MiB default scoped VMEM.  The min/max body reduces one
payload lane at a time, so no temporary grows with D.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import on_backend

_OP_IDENTITY = {"sum": 0.0, "min": jnp.inf, "max": -jnp.inf}

# Out-of-range destination sentinel: padded edges (and invalid tile lanes)
# carry a dst no real segment block can intersect, so both the pruning pass
# and the in-kernel one-hot drop them.
_DST_SENTINEL = np.int32(2**31 - 1)

BLOCK_E = 1024
BLOCK_V = 256
# Visits per kernel call: the [3, MAX_VISITS] int32 schedule slice (dst
# block, edge block, init mode) takes 384 KiB of the 1 MiB SMEM.
MAX_VISITS = 32768


def _kernel(sched_ref, dst_ref, msgs_ref, prev_ref, out_ref, *, op: str,
            block_v: int, n_edge_blocks: int, num_edges: int):
    g = pl.program_id(0)
    vb = sched_ref[0, g]
    eb = sched_ref[1, g]
    mode = sched_ref[2, g]   # 1: first visit of vb, 2: resume, 0: continue

    @pl.when(mode == 1)
    def _init():
        out_ref[...] = jnp.full(out_ref.shape, _OP_IDENTITY[op],
                                out_ref.dtype)

    @pl.when(mode == 2)
    def _resume():
        out_ref[...] = prev_ref[...]

    @pl.when(eb < n_edge_blocks)
    def _accumulate():
        block_e = dst_ref.shape[0]
        dst = dst_ref[...].reshape(1, block_e)               # [1, BE]
        ragged = num_edges % block_e != 0
        if ragged:
            # the last edge block runs past E: its lanes there hold
            # whatever the buffer held, so they hit no row and add nothing
            lane_id = jax.lax.broadcasted_iota(jnp.int32, (1, block_e), 1)
            real = lane_id < num_edges - eb * block_e
            dst = jnp.where(real, dst, -1)
        rows = jax.lax.broadcasted_iota(
            jnp.int32, (block_v, block_e), 0) + vb * block_v
        hit = rows == dst                                    # [BV, BE]
        scalar = msgs_ref.ndim == 1                          # [BE] or [D, BE]
        if op == "sum":
            msgs = (msgs_ref[...].reshape(1, block_e) if scalar
                    else msgs_ref[...])
            if ragged:
                msgs = jnp.where(real, msgs, 0.0)
            out_ref[...] += jax.lax.dot_general(
                msgs, hit.astype(jnp.float32),
                (((1,), (1,)), ((), ())),
                precision=jax.lax.Precision.HIGHEST,
                preferred_element_type=jnp.float32)         # [D, BV] on MXU
            return
        reduce = jnp.min if op == "min" else jnp.max
        fold = jnp.minimum if op == "min" else jnp.maximum

        def lane(d, carry):
            msg = (msgs_ref[...].reshape(1, block_e) if scalar
                   else msgs_ref[pl.ds(d, 1), :])            # [1, BE]
            col = reduce(jnp.where(hit, msg, _OP_IDENTITY[op]), axis=1,
                         keepdims=True)                      # [BV, 1]
            row = jnp.transpose(jnp.broadcast_to(col, (block_v, 128)))[:1]
            out_ref[pl.ds(d, 1), :] = fold(out_ref[pl.ds(d, 1), :], row)
            return carry

        jax.lax.fori_loop(0, out_ref.shape[0], lane, 0)   # D lanes


def _schedule(xp, dst_sorted, num_segments: int, block_e: int,
              block_v: int, size: int):
    """The `[2, size]` block schedule of a dst-sorted column, in numpy
    (`xp=np`, ingress) or traced jnp (`xp=jnp`, per superstep).

    Per edge block j, the dst-block range [fb_j, lb_j] of its real
    destinations (`< num_segments`); all-sentinel blocks sort last and get
    an out-of-range range.  Both bounds are nondecreasing, so dst block i
    is hit by exactly the edge blocks in [searchsorted(lb, i, left),
    searchsorted(fb, i, right)).  Each dst block takes max(1, hits) visits;
    visit g belongs to the dst block whose visit range holds g.
    """
    e = dst_sorted.shape[0]
    n_e = max(1, -(-e // block_e))
    n_v = -(-num_segments // block_v)
    d = xp.pad(dst_sorted.astype(np.int32), (0, n_e * block_e - e),
               constant_values=_DST_SENTINEL).reshape(n_e, block_e)
    real = d < num_segments
    none = np.int32(_DST_SENTINEL // block_v)
    fb = xp.where(real, d, _DST_SENTINEL).min(axis=1) // block_v
    lb = xp.where(real.any(axis=1),
                  xp.where(real, d, -1).max(axis=1) // block_v, none)
    blocks = xp.arange(n_v, dtype=np.int32)
    lo = xp.searchsorted(lb, blocks, side="left").astype(np.int32)
    hits = xp.searchsorted(fb, blocks, side="right").astype(np.int32) - lo
    hits = xp.maximum(hits, 0)
    ends = xp.cumsum(xp.maximum(hits, 1)).astype(np.int32)   # inclusive
    g = xp.arange(size, dtype=np.int32)
    vb = xp.minimum(xp.searchsorted(ends, g, side="right"),
                    n_v - 1).astype(np.int32)
    k = g - (ends[vb] - xp.maximum(hits[vb], 1))
    eb = xp.where(k < hits[vb], lo[vb] + k, n_e).astype(np.int32)
    return xp.stack([vb, eb])


def build_block_table(dst_sorted: np.ndarray, num_segments: int,
                      block_e: int = BLOCK_E,
                      block_v: int = BLOCK_V) -> np.ndarray:
    """Host-side ingress step: the block schedule of a STATIC dst-sorted
    column, padded to the static length `n_edge_blocks + n_dst_blocks`
    (the sorted-column bound), so partitions with equal padded edge counts
    and segment spaces carry equal-shape schedules (stackable per shard)."""
    dst_sorted = np.asarray(dst_sorted)
    if np.any(np.diff(dst_sorted) < 0):
        raise ValueError("build_block_table needs a dst-sorted column")
    return _schedule(np, dst_sorted, num_segments, block_e, block_v,
                     table_length(dst_sorted.shape[0], num_segments,
                                  block_e, block_v))


def table_length(num_edges: int, num_segments: int, block_e: int = BLOCK_E,
                 block_v: int = BLOCK_V) -> int:
    """Static schedule length for a dst-sorted column: every visit either
    opens a dst block or crosses into the next edge block."""
    return max(1, -(-num_edges // block_e)) + -(-num_segments // block_v)


def dynamic_block_table(dst: jnp.ndarray, num_segments: int,
                        block_e: int = BLOCK_E,
                        block_v: int = BLOCK_V) -> jnp.ndarray:
    """ON-DEVICE per-superstep schedule for DATA-DEPENDENT destinations.

    `dst [E] int32` is a gathered tile's destination column, SORTED
    ascending, with invalid lanes carrying a sentinel `>= num_segments`
    (they sort past every real destination).  The same construction as the
    ingress-time `build_block_table` runs in-graph (blocked reductions and
    binary searches); all-sentinel edge blocks intersect nothing and are
    never visited.  The length is the static sorted-column bound, so the
    shape is jit-stable; pruning shows up as fewer real visits and more
    trailing sentinel visits, not as a smaller grid.  Returns `[2, G]`.
    """
    return _schedule(jnp, dst, num_segments, block_e, block_v,
                     table_length(dst.shape[0], num_segments, block_e,
                                  block_v))


def block_table_occupancy(table, n_edge_blocks: int) -> float:
    """Visited-pair fraction of a block schedule vs the FULL table: the
    share of the `n_v * n_edge_blocks` (dst block, edge block) pairs the
    kernel actually computes (visits below the `n_edge_blocks` skip
    sentinel).  1.0 is the degenerate `full_block_table`; the pruning
    diagnostics in `partition_quality` and `bench_frontier` report this
    number."""
    table = np.asarray(table)
    visited = int(np.sum(table[1] < n_edge_blocks))
    n_v = int(table[0].max()) + 1
    return visited / (n_v * max(n_edge_blocks, 1))


def full_block_table(num_edges: int, num_segments: int,
                     block_e: int = BLOCK_E,
                     block_v: int = BLOCK_V) -> np.ndarray:
    """Degenerate schedule: every dst block visits every edge block.

    Kept only as the documented fallback when the dynamic pruning pass is
    disabled (`KernelPlan(dynamic_table=False)` /
    `tile_segment_combine_pallas(.., dynamic=False)`): same kernel, no
    skipping, no sort — rows whose dst falls outside the current block
    contribute nothing.
    """
    n_e = max(1, -(-num_edges // block_e))
    n_v = -(-num_segments // block_v)
    return np.stack([np.repeat(np.arange(n_v, dtype=np.int32), n_e),
                     np.tile(np.arange(n_e, dtype=np.int32), n_v)])


def tile_segment_combine_pallas(msgs: jnp.ndarray, dst: jnp.ndarray,
                                num_segments: int, op: str = "sum",
                                block_e: int = BLOCK_E,
                                block_v: int = BLOCK_V,
                                dynamic: bool = True) -> jnp.ndarray:
    """Segment-combine a gathered frontier tile (msgs [E, D] float32,
    dst [E] int32, BOTH data-dependent).

    With `dynamic=True` (default) the tile is dst-sorted on device and the
    kernel runs over the per-superstep `dynamic_block_table` — restoring
    the ingress-style sparsity skipping for tiles whose dst is gathered per
    superstep.  Invalid lanes must carry `dst >= num_segments` so the sort
    pushes them past every real destination and the pruning drops their
    blocks.  `dynamic=False` falls back to the degenerate
    `full_block_table` (every pair visited; no sort) — the escape hatch
    when the pruning pass itself is under test or disabled.

    The dst-sort re-orders messages within a segment: min/max ⊕ stay
    bitwise-identical to the XLA scatter-reduce; sums agree to float
    tolerance (the same reorder caveat every compacted strategy already
    carries).
    """
    dst = dst.astype(jnp.int32)
    if dynamic:
        order = jnp.argsort(dst)
        dst = dst[order]
        msgs = msgs[order]
        table = dynamic_block_table(dst, num_segments, block_e, block_v)
    else:
        table = jnp.asarray(full_block_table(msgs.shape[0], num_segments,
                                             block_e, block_v))
    return segment_combine_pallas(msgs, dst, table, num_segments, op,
                                  block_e=block_e, block_v=block_v)


def _combine_call(sched, dst, msgs, prev, *, op: str, block_e: int,
                  block_v: int, n_edge_blocks: int, interpret: bool):
    """One kernel call over a `[3, C]` schedule slice; `dst` is `[E]`,
    `msgs` `[E]` (scalar payload) or `[D, E]`; `prev` ([D, V_pad]) is
    aliased to the output, so dst blocks this slice never visits keep
    their values."""
    d = prev.shape[0]

    def eblock(g, s):
        return (jnp.minimum(s[1, g], n_edge_blocks - 1),)

    def vblock(g, s):
        return 0, s[0, g]

    msgs_spec = (pl.BlockSpec((block_e,), eblock) if msgs.ndim == 1 else
                 pl.BlockSpec((d, block_e), lambda g, s: (0,) + eblock(g, s)))
    return pl.pallas_call(
        functools.partial(_kernel, op=op, block_v=block_v,
                          n_edge_blocks=n_edge_blocks,
                          num_edges=dst.shape[0]),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(sched.shape[1],),
            in_specs=[pl.BlockSpec((block_e,), eblock), msgs_spec,
                      pl.BlockSpec((d, block_v), vblock)],
            out_specs=pl.BlockSpec((d, block_v), vblock),
        ),
        out_shape=jax.ShapeDtypeStruct(prev.shape, jnp.float32),
        input_output_aliases={3: 0},
        interpret=interpret,
    )(sched, dst, msgs, prev)


@functools.partial(jax.jit, static_argnames=("num_segments", "op", "block_e",
                                             "block_v"))
def segment_combine_pallas(msgs: jnp.ndarray, dst: jnp.ndarray,
                           table: jnp.ndarray, num_segments: int,
                           op: str = "sum", block_e: int = BLOCK_E,
                           block_v: int = BLOCK_V) -> jnp.ndarray:
    """msgs [E] or [E, D] (dst-sorted), dst [E] int32, table `[2, G]` from
    any of the schedule functions above.  Returns [num_segments] or
    [num_segments, D] float32.

    Operands are not padded: `dst` and a scalar payload enter the kernel
    as the `[E]` columns they are, and a D-wide payload as its `[D, E]`
    transpose.  The last edge block may run past E; the kernel masks
    those lanes.

    Compiled by Mosaic when lowered for a TPU and run by the Pallas
    interpreter on CPU (`repro.kernels.on_backend`)."""
    e = msgs.shape[0]
    scalar = msgs.ndim == 1
    msgs = msgs.astype(jnp.float32)
    msgs_t = msgs if scalar else msgs.T
    dst = dst.astype(jnp.int32)
    if e < block_e:   # a block wider than the whole column: pad, < BE lanes
        msgs_t = jnp.pad(msgs_t, ((0, 0),) * (msgs_t.ndim - 1) +
                         ((0, block_e - e),))
        dst = jnp.pad(dst, (0, block_e - e), constant_values=_DST_SENTINEL)
    d_feat = 1 if scalar else msgs_t.shape[0]
    n_e = -(-dst.shape[0] // block_e)
    n_v = -(-num_segments // block_v)
    vb, eb = table[0].astype(jnp.int32), table[1].astype(jnp.int32)
    g = vb.shape[0]
    chunk = min(g, MAX_VISITS)
    n_chunks = -(-g // chunk)
    tail = n_chunks * chunk - g
    vb = jnp.pad(vb, (0, tail), mode="edge")
    eb = jnp.pad(eb, (0, tail), constant_values=n_e)
    first = jnp.concatenate([jnp.ones((1,), bool), vb[1:] != vb[:-1]])
    starts = jnp.arange(vb.shape[0]) % chunk == 0
    mode = jnp.where(first, 1, jnp.where(starts, 2, 0)).astype(jnp.int32)
    sched = jnp.stack([vb, eb, mode]).reshape(3, n_chunks, chunk)
    out = jnp.full((d_feat, n_v * block_v), _OP_IDENTITY[op], jnp.float32)
    call = functools.partial(_combine_call, op=op, block_e=block_e,
                             block_v=block_v, n_edge_blocks=n_e)

    def run(c, acc):
        return on_backend(call, sched[:, c], dst, msgs_t, acc)

    out = jax.lax.fori_loop(0, n_chunks, run, out) if n_chunks > 1 \
        else run(0, out)
    return out[0, :num_segments] if scalar else out[:, :num_segments].T
