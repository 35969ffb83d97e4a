"""Frontier-compacted scatter-combine with degree-bucketed tiles.

The dense scatter path scans EVERY edge each superstep and masks by
`active_scatter[src]` — on a scale-free graph a BFS superstep with a 1%
frontier wastes 99% of its gather bandwidth (the inactive-vertex overhead
that dominates vertex-centric runtimes).  This module compacts instead:

  1. `compact_indices(active, cap)` (`jnp.nonzero` with a static size)
     extracts at most `cap` active slots (fixed capacity keeps the shape
     static for jit);
  2. CSR `indptr` (built at ingress, `graph.structures.csr_layout`) gives
     each frontier slot's out-edge range; ranges are gathered into a padded
     edge tile via the src-sorted position index `csr_eidx` — destinations
     and edge props still read the canonical (dst-sorted) columns, so
     callers that rewrite `dst` (the overlap exchange's remote/local split)
     stay consistent;
  3. tile messages feed the SAME `segment_combine` ⊕ as the dense path.

A single `[cap, max_deg]` tile (`compact_scatter_combine`, kept as the
"flat" ablation strategy) pads every frontier slot to the partition's max
out-degree — ONE power-law hub inflates every row, to the point where the
padded tile out-scans the dense path and compaction had to be statically
gated off (`cap * max_deg >= E`).  The default path is therefore
DEGREE-BUCKETED (`bucketed_scatter_combine`): ingress bins slots by local
out-degree (`graph.structures.degree_buckets`, bounds ≈ ⌈log2 d⌉ collapsed
to ≤8/≤32/≤128/≤512/rest), and each bucket gathers its own
`[cap_b, max_deg_b]` tile.  Hub buckets hold few members, so their tile degrades to a per-hub
edge-range scan instead of poisoning `max_deg` for everyone — the static
hub gate disappears for power-law graphs.

Strategy selection is a `lax.cond` per superstep on the live frontier
count: dense above the density crossover, compacted below.  OVERFLOW is
guarded per bucket: a bucket whose live members exceed `cap_b` (a hub
activating every leaf of a star in one step) degrades to a dense scan
RESTRICTED to that bucket's sources — the other buckets stay compact, and
no vertex is ever dropped.

The compacted combine's kernel route is the plan's kernel stage
(`repro.core.plan.KernelPlan`): the XLA scatter-reduce by default; with
`use_pallas` the Pallas tile combine
(`kernels.segment_combine.tile_segment_combine_pallas`, interpret-mode on
CPU), which re-prunes its (dst block, edge block) prefetch table ON DEVICE
each superstep (`dynamic_block_table` — the tile's `dst` is data-dependent,
so the ingress-time static table cannot apply) unless the plan disables the
pruning pass (`dynamic_table=False`, the documented full-table fallback).
Invalid tile lanes carry the `num_segments` destination sentinel, which
every route drops: XLA scatter-reduces drop out-of-range indices, and the
pruning pass sorts sentinels past every real destination.

Edge tiles compose with the exchange layer's edge splits: a
`DevicePartition` whose columns hold only a destination CLASS — the
pipelined exchange's per-destination-shard remote tile or master-local tile
(`agent_graph.split_edge_tiles`), or the in-superstep `dst`-rewrite of
`AgentExchange(overlap=True)` — flows through unchanged, because
`gather_frontier_edge_tile` resolves CSR positions via `csr_eidx` into
whatever `dst`/`edge_props` columns the partition carries, and the ⊕
segment space is the caller's `num_segments` (compact combiner/master
spaces for the split tiles, full slot space otherwise).

Device work runs under two named scopes, which a profile shows in each
op's name stack: `gre.scatter` (the route predicates, compaction, the
tile gathers and the messages) and `gre.combine` (the ⊕ reductions, the
Pallas tile combine with its pruning pass, and the fold of the bucket
partials).  The route predicates (`fits_capacity`, `bucket_route`) are
shared with `superstep_counts`, which counts a superstep's work for
`EngineState.counters`, so a count always describes the branch that ran.
"""
from __future__ import annotations

import functools
from typing import TYPE_CHECKING, Optional, Sequence

import jax
import jax.numpy as jnp

from repro.core.plan import XLA_KERNEL, KernelPlan
from repro.core.vertex_program import segment_combine

if TYPE_CHECKING:  # pragma: no cover - typing only, avoids a cycle
    from repro.core.engine import DevicePartition, EngineState
    from repro.core.plan import FrontierPlan
    from repro.core.vertex_program import VertexProgram

# Density threshold for auto strategy selection: compact below ~6% active
# (the literature's crossover for frontier-aware traversal sits at 5-10%).
FRONTIER_DENSITY = 1.0 / 16.0

# Columns of `EngineState.counters`, one row per superstep.
COUNTERS = ("active_vertices", "active_out_edges", "edges_scanned")

# Calibrated capacity head-room: cap = GROWTH x the largest frontier
# observed during the probe supersteps (frontiers grow superstep over
# superstep; the overflow guard keeps larger-than-expected ones correct).
CAP_GROWTH = 4


def default_cap(num_slots: int,
                frontier_hist: Optional[Sequence[int]] = None) -> int:
    """Default frontier capacity, rounded up to a multiple of 8.

    With `frontier_hist` — live frontier sizes observed on the first
    superstep(s) (`GREEngine.calibrate_frontier_cap`) — the capacity is
    `CAP_GROWTH x` the largest observed size: a single-source traversal on
    a large shard starts from a handful of active slots, and sizing off the
    LIVE density instead of `num_slots` avoids compiling (and gathering
    into) a tile orders of magnitude wider than any real frontier.
    Without a histogram, falls back to the density threshold as a fixed
    fraction of `num_slots`.
    """
    if frontier_hist:
        cap = max(8, CAP_GROWTH * int(max(frontier_hist)))
    else:
        cap = max(8, int(num_slots * FRONTIER_DENSITY))
    return min(num_slots, -(-cap // 8) * 8)


def bucket_caps(sizes: Sequence[int], cap: int) -> tuple:
    """Split the global frontier capacity across buckets proportionally to
    membership.

    A frontier of ≤ `cap` live slots mixed like the degree distribution
    then fits every bucket's quota, and the worst-case tile work
    `sum_b cap_b * max_deg_b` stays ~`cap * mean_deg` instead of
    `cap * max_deg` per bucket (cap-sized tiles for two live hubs are how
    a bucketed gather quietly degenerates back to the dense scan).  Each
    nonempty bucket keeps a small floor so hubs always fit a few members;
    quotas are lane-rounded and clamped to the bucket size.  A bucket
    whose LIVE count exceeds its quota degrades to its restricted dense
    scan (`bucketed_scatter_combine`) — capacity skew costs performance,
    never correctness.
    """
    total = sum(sizes)
    if total == 0:
        return tuple(0 for _ in sizes)
    caps = []
    for s in sizes:
        if s == 0:
            caps.append(0)
            continue
        quota = -(-cap * s // total)            # ceil, proportional share
        quota = -(-quota // 8) * 8              # lane-friendly
        caps.append(min(s, max(quota, 8)))
    return tuple(caps)


def gather_frontier_edge_tile(part: "DevicePartition", frontier: jnp.ndarray,
                              cap: int, max_deg: Optional[int] = None):
    """Gather the frontier slots' out-edge ranges into a padded edge tile.

    `frontier` is the fixed-capacity active-slot list (`[cap]`, fill value
    `part.num_slots` — its `indptr` lookup clamps to a zero-length range).
    `max_deg` bounds the tile width (default: the partition-wide
    `csr_max_deg`; bucketed callers pass their bucket's own bound).
    Returns `(eid, valid)`: `eid [cap, max_deg]` are POSITIONS into the
    partition's canonical edge columns (`part.dst[eid]`,
    `part.edge_props[...][eid]`), `valid` masks the ragged lanes.  Because
    positions — not copies — are returned, the tile follows whatever
    destination columns the partition carries: the full dst-sorted slot
    space, the pipelined exchange's compact per-destination-class tiles,
    or the overlap exchange's in-superstep `dst` rewrite.
    """
    slots = part.num_slots
    if max_deg is None:
        max_deg = part.csr_max_deg
    start = part.csr_indptr[frontier]                    # clamped gather
    end = part.csr_indptr[jnp.minimum(frontier + 1, slots)]
    deg = end - start                                    # [cap], 0 on fills
    col = jnp.arange(max_deg, dtype=jnp.int32)
    valid = col[None, :] < deg[:, None]                  # [cap, max_deg]
    pos = jnp.where(valid, start[:, None] + col[None, :], 0)
    return part.csr_eidx[pos], valid


def compact_indices(mask: jnp.ndarray, size: int,
                    fill_value: int) -> jnp.ndarray:
    """`jnp.nonzero(mask, size=size, fill_value=fill_value)[0]`, built from
    a two-level prefix sum (1024-wide rows, then the row totals) and one
    int32 scatter.  Same result; the TPU compiler takes 7–28 s for the flat
    prefix sum `jnp.nonzero` lowers to at 10^5–10^6 slots, and under 2 s
    for this one."""
    n, width = mask.shape[0], 1024
    rows = -(-n // width)
    m = jnp.pad(mask.astype(jnp.int32), (0, rows * width - n))
    inner = jnp.cumsum(m.reshape(rows, width), axis=1)
    offset = jnp.cumsum(inner[:, -1]) - inner[:, -1]
    pos = (inner + offset[:, None]).reshape(-1)[:n] - 1
    return jnp.full((size,), fill_value, jnp.int32).at[
        jnp.where(mask, pos, size)].set(jnp.arange(n, dtype=jnp.int32),
                                         mode="drop")


def _tile_combine(program: "VertexProgram", msgs: jnp.ndarray,
                  dst: jnp.ndarray, num_segments: int,
                  kernel: KernelPlan = XLA_KERNEL) -> jnp.ndarray:
    """⊕-reduce a gathered tile's messages through the plan's kernel stage.

    `dst` carries the `num_segments` sentinel on invalid lanes (both
    routes drop them).  The tile's `dst` is data-dependent, so the Pallas
    route re-prunes its block table ON DEVICE each superstep
    (`dynamic_block_table`) instead of using the ingress-time static table
    of the dense path; `kernel.dynamic_table=False` falls back to the
    degenerate full table."""
    p = program
    if not kernel.use_pallas:
        return segment_combine(msgs, dst, num_segments, p.monoid,
                               indices_are_sorted=False)
    from repro.kernels.segment_combine import tile_segment_combine_pallas
    payload = msgs.shape[1:]
    flat = msgs.reshape(msgs.shape[0], -1).astype(jnp.float32)
    out = tile_segment_combine_pallas(flat, dst.astype(jnp.int32),
                                      num_segments, p.monoid.name,
                                      dynamic=kernel.dynamic_table)
    return out.reshape((num_segments,) + payload).astype(p.msg_dtype)


def compact_scatter_combine(program: "VertexProgram", part: "DevicePartition",
                            state: "EngineState", num_segments: int,
                            cap: int, max_deg: Optional[int] = None,
                            frontier_mask: Optional[jnp.ndarray] = None,
                            kernel: KernelPlan = XLA_KERNEL) -> jnp.ndarray:
    """⊕-combine emitted only from the ≤ `cap` live slots' out-edges.

    `frontier_mask` restricts the frontier beyond `active_scatter` (the
    bucketed path passes `active & (bucket_id == b)`).  Bitwise-equal to
    the dense masked scan whenever the live mask fits in `cap` (for min/max
    monoids exactly; sum monoids up to float reorder of the segment
    reduction).  Callers must guard `|frontier| <= cap`.
    """
    p = program
    if max_deg is None:
        max_deg = part.csr_max_deg
    with jax.named_scope("gre.scatter"):
        mask = state.active_scatter if frontier_mask is None else frontier_mask
        frontier = compact_indices(mask, cap, part.num_slots)
        eid, valid = gather_frontier_edge_tile(part, frontier, cap, max_deg)
        # invalid lanes carry identity msgs AND the out-of-range dst sentinel:
        # XLA scatter-reduces drop them, and the Pallas dynamic pruning pass
        # sorts them past every real destination so their blocks prune away
        dst = jnp.where(valid, part.dst[eid], num_segments)
        gathered = jnp.take(state.scatter_data, frontier, axis=0,
                            fill_value=p.monoid.identity)    # [cap, *S]
        tile = jnp.broadcast_to(gathered[:, None],
                                (cap, max_deg) + gathered.shape[1:])
        flat = tile.reshape((cap * max_deg,) + gathered.shape[1:])
        eprop = (part.edge_props[p.needs_edge_prop][eid].reshape(-1)
                 if p.needs_edge_prop else None)
        msgs = p.scatter_msg(flat, eprop)
        vmask = valid.reshape((-1,) + (1,) * (msgs.ndim - 1))
        msgs = jnp.where(vmask, msgs.astype(p.msg_dtype), p.monoid.identity)
    with jax.named_scope("gre.combine"):
        return _tile_combine(program, msgs, dst.reshape(-1), num_segments,
                             kernel=kernel)


def dense_masked_combine(program: "VertexProgram", part: "DevicePartition",
                         state: "EngineState", num_segments: int,
                         src_mask: jnp.ndarray) -> jnp.ndarray:
    """Dense every-edge scan with an explicit source-activity mask.

    The per-bucket OVERFLOW path: when bucket b's live members exceed its
    capacity, its contribution is recomputed as a dense scan restricted to
    `active & (bucket_id == b)` — all other buckets stay compact.
    """
    p = program
    eprop = (part.edge_props[p.needs_edge_prop]
             if p.needs_edge_prop else None)
    with jax.named_scope("gre.scatter"):
        gathered = jnp.take(state.scatter_data, part.src, axis=0,
                            fill_value=p.monoid.identity)
        msgs = p.scatter_msg(gathered, eprop)
        live = jnp.take(src_mask, part.src, axis=0,
                        fill_value=False) & part.edge_mask
        live = live.reshape(live.shape + (1,) * (msgs.ndim - live.ndim))
        msgs = jnp.where(live, msgs.astype(p.msg_dtype), p.monoid.identity)
    with jax.named_scope("gre.combine"):
        return segment_combine(msgs, part.dst, num_segments, p.monoid,
                               indices_are_sorted=part.edges_sorted_by_dst)


def bucketed_scatter_combine(program: "VertexProgram",
                             part: "DevicePartition", state: "EngineState",
                             num_segments: int, caps: Sequence[int],
                             kernel: KernelPlan = XLA_KERNEL) -> jnp.ndarray:
    """Degree-bucketed compacted ⊕ over the live frontier.

    `bucket_id` partitions slots with out-edges, so summing the per-bucket
    partial combines touches every active out-edge exactly once.  Each
    bucket either gathers its own `[cap_b, max_deg_b]` tile (live members
    fit) or — per-bucket `lax.cond` — degrades to a bucket-restricted
    dense scan (overflow).  Degree-0 slots carry `bucket_id == -1`: they
    can never emit a message, so no bucket spends capacity on them.
    """
    p = program
    partials = []
    for b, cap_b, max_deg_b in tiled_buckets(part, caps):
        with jax.named_scope("gre.scatter"):
            mask_b, fits = bucket_route(part, state.active_scatter, b, cap_b)
        partials.append(jax.lax.cond(
            fits,
            lambda m, c=cap_b, d=max_deg_b: compact_scatter_combine(
                program, part, state, num_segments, c, max_deg=d,
                frontier_mask=m, kernel=kernel),
            lambda m: dense_masked_combine(program, part, state,
                                           num_segments, m),
            mask_b))
    with jax.named_scope("gre.combine"):
        return functools.reduce(p.monoid.op, partials)


def tiled_buckets(part: "DevicePartition", caps: Sequence[int]) -> list:
    """`(b, cap_b, max_deg_b)` of each degree bucket that gathers a
    tile; a statically empty bucket (no capacity or no out-edges) has
    none."""
    return [(b, cap_b, max_deg_b) for b, (cap_b, max_deg_b)
            in enumerate(zip(caps, part.bucket_max_deg))
            if cap_b > 0 and max_deg_b > 0]


def bucket_route(part: "DevicePartition", active: jnp.ndarray, b: int,
                 cap_b: int) -> tuple:
    """`(mask_b, fits)`: bucket b's live members, and whether they fit
    its capacity — the predicate that picks its tile over its
    bucket-restricted dense scan."""
    mask_b = active & (part.bucket_id == b)
    return mask_b, jnp.sum(mask_b) <= cap_b


def fits_capacity(plan: "FrontierPlan", active: jnp.ndarray) -> jnp.ndarray:
    """Whether the live frontier fits the plan's whole compacted capacity
    — the predicate that picks the compacted route over the dense scan
    (the density crossover and the whole-frontier overflow guard)."""
    kind, caps = plan
    return jnp.sum(active) <= (caps if kind == "flat" else sum(caps))


def superstep_counts(plan: "FrontierPlan", part: "DevicePartition",
                     active: jnp.ndarray) -> jnp.ndarray:
    """One row of `EngineState.counters` for the superstep that scatters
    from `active` under `plan` (int32 `[3]`, columns `COUNTERS`):

      active_vertices   live frontier slots;
      active_out_edges  their out-degrees summed (CSR `indptr`): the edges
                        the superstep must read whatever route runs it;
      edges_scanned     the edges the chosen route reads — `E_pad` for the
                        dense scan; for the compacted route `cap * max_deg`
                        (flat) or, per bucket, `cap_b * max_deg_b` when its
                        members fit and `E_pad` for its restricted dense
                        scan when they overflow.

    The route comes from the same predicates the scatter stage branches
    on (`fits_capacity`, `bucket_route`).  Each count stays below 2**31
    while (buckets + 1) * E_pad does.
    """
    e_pad = part.src.shape[0]
    needed = jnp.sum(jnp.where(active, jnp.diff(part.csr_indptr), 0))
    if plan.kind == "dense":
        scanned = jnp.int32(e_pad)
    else:
        if plan.kind == "flat":
            tiles = plan.caps * part.csr_max_deg
        else:
            tiles = sum(
                jnp.where(bucket_route(part, active, b, cap_b)[1],
                          cap_b * max_deg_b, e_pad)
                for b, cap_b, max_deg_b in tiled_buckets(part, plan.caps))
        scanned = jnp.where(fits_capacity(plan, active), tiles, e_pad)
    return jnp.stack([jnp.sum(active, dtype=jnp.int32), needed,
                      scanned]).astype(jnp.int32)


def bucketed_tile_occupancy(part: "DevicePartition", active: jnp.ndarray,
                            caps: Sequence[int],
                            num_segments: Optional[int] = None,
                            block_e: int = 256, block_v: int = 256) -> tuple:
    """Measured dynamic-block-table occupancy for a live frontier.

    Replays the bucketed gather for `active` (each bucket's `[cap_b,
    max_deg_b]` tile, invalid lanes sentineled) and builds each tile's
    per-superstep `dynamic_block_table`, returning ``(visited, total)``
    (dst block, edge block) pair counts summed over buckets — `total` is
    what the degenerate full table would visit.  Diagnostic only (eager;
    `benchmarks/bench_frontier.py` emits `visited / total` as
    `block_table_occupancy`); the in-graph pruning pass inside the kernel
    route computes the same tables.
    """
    from repro.kernels.segment_combine import dynamic_block_table
    nseg = num_segments or part.num_slots
    visited = total = 0
    for b, cap_b, max_deg_b in tiled_buckets(part, caps):
        mask_b = active & (part.bucket_id == b)
        frontier = compact_indices(mask_b, cap_b, part.num_slots)
        eid, valid = gather_frontier_edge_tile(part, frontier, cap_b,
                                               max_deg_b)
        dst = jnp.sort(jnp.where(valid, part.dst[eid], nseg).reshape(-1))
        table = dynamic_block_table(dst, nseg, block_e, block_v)
        n_e = -(-dst.shape[0] // block_e)
        visited += int(jnp.sum(table[1] < n_e))
        total += -(-nseg // block_v) * n_e
    return visited, total


def frontier_scatter_combine(program: "VertexProgram",
                             part: "DevicePartition", state: "EngineState",
                             num_segments: int, plan: "FrontierPlan",
                             dense_fn,
                             kernel: KernelPlan = XLA_KERNEL) -> jnp.ndarray:
    """Per-superstep strategy selection with capacity/overflow guards.

    `plan` is the static per-partition resolution
    (`repro.core.plan.resolve_frontier`, kind "flat" or "bucketed" — the
    dense kind never reaches here).  `dense_fn()` must produce the dense
    masked combine over the same `num_segments`; it is taken whenever the
    live frontier exceeds the total compacted capacity (density crossover
    AND whole-frontier overflow protection in one predicate — per-bucket
    skew overflow is guarded inside the bucketed branch).  `kernel` is the
    plan's combine-kernel stage, threaded into the tile combines.
    """
    kind, caps = plan
    with jax.named_scope("gre.scatter"):
        compact = fits_capacity(plan, state.active_scatter)
    if kind == "flat":
        return jax.lax.cond(
            compact,
            lambda _: compact_scatter_combine(program, part, state,
                                              num_segments, caps,
                                              kernel=kernel),
            lambda _: dense_fn(),
            operand=None)
    return jax.lax.cond(
        compact,
        lambda _: bucketed_scatter_combine(program, part, state,
                                           num_segments, caps,
                                           kernel=kernel),
        lambda _: dense_fn(),
        operand=None)
