"""GRE BSP engine: executes VertexPrograms in supersteps (paper Alg. 2).

There is ONE canonical superstep, parameterized by an ExchangeBackend
(`repro.core.exchange`):

  refresh          — the backend pushes master scatter state to any remote
      readers (identity on a single shard);
  scatter-combine  — every scatter-active vertex emits active messages along
      its out-edges; messages execute ⊕ at their destinations immediately
      (one fused gather → message → segment-reduce, no edge-state storage);
      the backend folds remote partial combines into master slots;
  apply            — every vertex whose combine_data changed recomputes
      vertex_data and decides whether to stay scatter-active
      (assert_to_halt).

Message payloads are first-class feature vectors: state arrays are
`[slots, *payload_shape]` and the same superstep drives scalar traversal
(SSSP, payload `()`), multi-stage vector programs (Brandes σ, payload
`(3,)`) and GNN feature aggregation (payload `(D,)`).

The distributed engine (`repro.core.dist_engine`) runs this same superstep
per shard with an AgentExchange or DenseExchange backend under shard_map.

HOW a run executes — which frontier strategy scans the edges, whether the
exchange runs as one synchronous reduce or as the pipelined local-phase /
deferred-merge shape, and which combine kernel folds the messages — is a
`SuperstepPlan` (`repro.core.plan`), resolved once per (engine, partition)
and driven by ONE loop, `plan.execute_plan`.  `GREEngine.run` and the
distributed `DistGREEngine.make_run` both call that executor; there is no
separate pipelined loop.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.exchange import NULL_EXCHANGE, ExchangeBackend
from repro.core.plan import KernelPlan, SuperstepPlan, execute_plan
from repro.core.vertex_program import VertexProgram, segment_combine


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class DevicePartition:
    """Static per-shard topology (column storage, local 32-bit ids).

    `num_slots` = masters + agents + 1 padding sink; padded edges point at
    the sink so combines on padding never touch real state (paper §6.1.1
    renumbers masters first, then agents; the sink is our addition for XLA
    static shapes).
    """

    # Edge columns are OPTIONAL: a partition that only anchors slot statics
    # and aux for the apply phase (the canonical part under the pipelined
    # exchange, whose edge scans all run on the split tiles) carries None
    # instead of paying device memory for columns nothing reads.
    src: Optional[jnp.ndarray] = None         # [E_pad] int32 local src slot
    dst: Optional[jnp.ndarray] = None         # [E_pad] int32 local dst slot
    edge_mask: Optional[jnp.ndarray] = None   # [E_pad] bool, False on padding
    # The slot sizing stays REQUIRED (keyword-only, no default): omitting it
    # must fail at construction, not as an opaque zero-shape trace error.
    num_masters: int = dataclasses.field(kw_only=True,
                                         metadata=dict(static=True))
    num_slots: int = dataclasses.field(kw_only=True,
                                       metadata=dict(static=True))
    edges_sorted_by_dst: bool = dataclasses.field(kw_only=True,
                                                  metadata=dict(static=True))
    edge_props: Dict[str, jnp.ndarray] = dataclasses.field(default_factory=dict)
    aux: Dict[str, jnp.ndarray] = dataclasses.field(default_factory=dict)
    # Src-sorted CSR secondary index (graph.structures.csr_layout) — the
    # substrate of the frontier-compacted scatter (core/frontier.py).  None
    # disables compaction for this partition.
    csr_indptr: Optional[jnp.ndarray] = None   # [num_slots + 1]
    csr_eidx: Optional[jnp.ndarray] = None     # [E_pad] pos in dst-sorted cols
    csr_max_deg: int = dataclasses.field(default=0,
                                         metadata=dict(static=True))
    # Degree-bucket binning (graph.structures.degree_buckets): slots binned
    # by local out-degree so the compacted frontier gathers one tight
    # [cap_b, max_deg_b] tile per bucket instead of padding everything to
    # the hub degree.  None/empty disables bucketed compaction.
    bucket_id: Optional[jnp.ndarray] = None    # [num_slots] int32, -1 = deg 0
    bucket_sizes: tuple = dataclasses.field(default=(),
                                            metadata=dict(static=True))
    bucket_max_deg: tuple = dataclasses.field(default=(),
                                              metadata=dict(static=True))
    # Ingress-time Pallas block schedule of the dst-sorted `dst` column over
    # `num_slots` (kernels.segment_combine.build_block_table) — what the
    # dense scan's Pallas route visits (`resolve_combine_route`).  None on
    # partitions whose edges are not dst-sorted; that route then refuses
    # to run when forced and is never resolved to.
    combine_table: Optional[jnp.ndarray] = None   # [2, G] int32

    @staticmethod
    def from_graph(graph, pad_to: Optional[int] = None,
                   sort_by_dst: bool = True, transpose: bool = False,
                   bucket_bounds: Optional[tuple] = None,
                   edge_slack: int = 0, chunk_size: Optional[int] = None):
        """Whole graph on one shard (no agents; slots = V + sink).

        `transpose=True` builds the partition of the reversed graph — the
        backward-traversal substrate for multi-stage algorithms (paper §4.2:
        Brandes' δ accumulation runs on the transposed graph).

        `bucket_bounds` overrides the default degree-bucket ladder
        (`graph.structures.DEFAULT_BUCKET_BOUNDS`) — the plan autotuner
        (repro.tuning) probes candidate ladders by rebuilding the
        partition per bounds, and a tuned `SuperstepPlan` carrying
        non-None `bucket_bounds` expects a partition built with them.

        `edge_slack` pads the edge columns with that many extra masked
        slots so future `apply_edge_delta` batches can append in place
        without regrowing the static edge length (= without an XLA
        retrace).  See docs/incremental.md.

        `graph` may also be an `EdgeChunkSource` (or any in-memory Graph
        with `chunk_size` set): the padded edge columns then fill
        directly from the chunk stream at a cursor and the dst sort runs
        in place over the filled prefix — bitwise-identical columns, but
        peak host state is the padded output columns plus ONE chunk, with
        no intermediate full edge-list copy (docs/partitioning.md).

        The four host phases run under `repro.spans` spans, which a
        profile shows and `spans.recording()` keeps: `gre.ingress.sort`
        (the dst sort), `gre.ingress.csr` (`csr_layout`),
        `gre.ingress.buckets` (`degree_buckets`) and
        `gre.ingress.block_table` (`build_block_table`).
        """
        from repro.graph.structures import (DEFAULT_BUCKET_BOUNDS,
                                            csr_layout, degree_buckets,
                                            pad_edges, sort_edges_by_dst)
        from repro.kernels.segment_combine import build_block_table
        from repro.spans import span
        source = graph if hasattr(graph, "chunks") else (
            graph.chunk_source(chunk_size) if chunk_size else None)
        if source is not None:
            v, e = source.num_vertices, source.num_edges
            e_pad = pad_to or (e + edge_slack)
            assert e_pad >= e, (e_pad, e)
            psrc = np.full(e_pad, v, dtype=np.int32)
            pdst = np.full(e_pad, v, dtype=np.int32)
            mask = np.zeros(e_pad, dtype=bool)
            mask[:e] = True
            props = {k: np.zeros(e_pad, dtype=dt)
                     for k, dt in source.prop_dtypes.items()}
            out_deg = np.zeros(v, dtype=np.int64)
            cur = 0
            for chunk in source.chunks():
                s, d = ((chunk.dst, chunk.src) if transpose
                        else (chunk.src, chunk.dst))
                hi = cur + chunk.num_edges
                psrc[cur:hi] = s
                pdst[cur:hi] = d
                for k in props:
                    props[k][cur:hi] = chunk.props[k]
                out_deg += np.bincount(s, minlength=v)
                cur = hi
            if sort_by_dst:
                with span("gre.ingress.sort"):
                    order = np.argsort(pdst[:e], kind="stable")
                    psrc[:e] = psrc[:e][order]
                    pdst[:e] = pdst[:e][order]
                    for k in props:
                        props[k][:e] = props[k][:e][order]
            out_deg = out_deg.astype(np.float32)
        else:
            if transpose:
                graph = graph.reversed()
            src, dst, props = graph.src, graph.dst, dict(graph.edge_props)
            if sort_by_dst:
                with span("gre.ingress.sort"):
                    src, dst, props, _ = sort_edges_by_dst(src, dst, props)
            v = graph.num_vertices
            e_pad = pad_to or (graph.num_edges + edge_slack)
            psrc, pdst, mask = pad_edges(src, dst, e_pad, pad_vertex=v)
            props = {k: np.pad(p, (0, e_pad - graph.num_edges))
                     for k, p in props.items()}
            out_deg = graph.out_degree().astype(np.float32)
        with span("gre.ingress.csr"):
            indptr, eidx, max_deg = csr_layout(psrc, mask, v + 1)
        with span("gre.ingress.buckets"):
            bucket_id, sizes, max_degs = degree_buckets(
                indptr, v + 1, bounds=tuple(bucket_bounds or
                                            DEFAULT_BUCKET_BOUNDS))
        table = None
        if sort_by_dst:
            with span("gre.ingress.block_table"):
                table = build_block_table(pdst, v + 1)
            table = jnp.asarray(table)
        return DevicePartition(
            src=jnp.asarray(psrc), dst=jnp.asarray(pdst),
            edge_mask=jnp.asarray(mask), num_masters=v, num_slots=v + 1,
            edges_sorted_by_dst=sort_by_dst,
            edge_props={k: jnp.asarray(p) for k, p in props.items()},
            aux={"out_degree": jnp.asarray(out_deg),
                 "global_id": jnp.arange(v, dtype=jnp.float32)},
            csr_indptr=jnp.asarray(indptr), csr_eidx=jnp.asarray(eidx),
            csr_max_deg=max_deg,
            bucket_id=jnp.asarray(bucket_id), bucket_sizes=sizes,
            bucket_max_deg=max_degs, combine_table=table,
        )

    def apply_edge_delta(self, delta, bucket_bounds: Optional[tuple] = None,
                         pad_multiple: int = 8):
        """Delta ingress (docs/incremental.md): retire + append edges in the
        padded columns without rebuilding the partition from a Graph.

        Removed edges become TOMBSTONES — folded into `edge_mask` as False
        and repointed at the sink slot (`src = dst = num_masters`), so even
        the dense-frontier scan (which skips the mask, relying on the sink's
        identity-pinned scatter row) never re-delivers them.  Added edges
        consume masked slack slots at the tail.  Live edges are then
        re-sorted by destination on the host, preserving the
        `edges_sorted_by_dst` contract of the segment combine, and the
        CSR/bucket secondary indices are rebuilt over the same padded
        length.

        The STATIC facets (`csr_max_deg`, `bucket_sizes`, `bucket_max_deg`)
        merge monotonically (elementwise max with the previous partition):
        larger tile caps are pure padding, and keeping them monotone means a
        sequence of small deltas reuses one jitted trace instead of
        recompiling per batch.  Only when the live edge count outgrows the
        padded columns do we COMPACT: regrow the edge length with ×1.25
        headroom (rounded up to `pad_multiple`) — the one recompile point,
        flagged in the report.

        Returns ``(new_partition, DeltaReport)``; `self` is not mutated.
        """
        from repro.graph.structures import (DEFAULT_BUCKET_BOUNDS,
                                            DeltaReport, csr_layout,
                                            degree_buckets, removal_selector,
                                            sort_edges_by_dst,
                                            validate_edge_delta)
        from repro.kernels.segment_combine import build_block_table
        assert self.src is not None, \
            "tile-only partition carries no edge columns to mutate"
        n, slots = self.num_masters, self.num_slots
        sink = n  # single-shard layout: masters [0, n), sink at n
        src = np.asarray(self.src)
        dst = np.asarray(self.dst)
        mask = np.asarray(self.edge_mask)
        props = {k: np.asarray(v) for k, v in self.edge_props.items()}
        # ---- validate up front (single-shard layout: master slot == the
        # original vertex id, so slot-space keys ARE original-id keys)
        validate_edge_delta(
            delta, n,
            live_keys=(src[mask].astype(np.int64) * np.int64(n) +
                       dst[mask].astype(np.int64)))
        # ---- retire: every live instance of each removed (src, dst) pair
        rem = removal_selector(src.astype(np.int64), dst.astype(np.int64),
                               delta.rem_src, delta.rem_dst, slots) & mask
        removed_src = src[rem].astype(np.int64)
        removed_dst = dst[rem].astype(np.int64)
        keep = mask & ~rem
        # ---- stage adds
        if delta.num_adds:
            for k in props:
                if k not in delta.add_props:
                    raise KeyError(f"delta adds missing edge prop {k!r}")
        live_src = np.concatenate([src[keep],
                                   delta.add_src.astype(np.int32)])
        live_dst = np.concatenate([dst[keep],
                                   delta.add_dst.astype(np.int32)])
        live_props = {
            k: np.concatenate([v[keep],
                               np.asarray(delta.add_props[k], v.dtype)
                               if delta.num_adds else v[:0]])
            for k, v in props.items()}
        e_live = int(live_src.shape[0])
        e_pad = int(src.shape[0])
        compacted = False
        if e_live > e_pad:  # slack exhausted: the one recompile point
            e_pad = max(e_live, int(e_pad * 1.25))
            e_pad = -(-e_pad // pad_multiple) * pad_multiple
            compacted = True
        if self.edges_sorted_by_dst:
            live_src, live_dst, live_props, _ = sort_edges_by_dst(
                live_src, live_dst, live_props)
        psrc = np.full(e_pad, sink, np.int32)
        pdst = np.full(e_pad, sink, np.int32)
        pmask = np.zeros(e_pad, dtype=bool)
        psrc[:e_live] = live_src
        pdst[:e_live] = live_dst
        pmask[:e_live] = True
        pprops = {}
        for k, v in live_props.items():
            col = np.zeros((e_pad,) + v.shape[1:], dtype=v.dtype)
            col[:e_live] = v
            pprops[k] = col
        indptr, eidx, max_deg = csr_layout(psrc, pmask, slots)
        bucket_id, sizes, max_degs = degree_buckets(
            indptr, slots,
            bounds=tuple(bucket_bounds or DEFAULT_BUCKET_BOUNDS))
        # monotone static merge (see docstring): max keeps traces stable
        max_deg = max(max_deg, self.csr_max_deg)
        if len(sizes) == len(self.bucket_sizes):
            sizes = tuple(max(a, b)
                          for a, b in zip(sizes, self.bucket_sizes))
            max_degs = tuple(max(a, b)
                             for a, b in zip(max_degs, self.bucket_max_deg))
        out_deg = np.bincount(live_src, minlength=slots)[:n]
        aux = dict(self.aux)
        aux["out_degree"] = jnp.asarray(out_deg.astype(np.float32))
        new = dataclasses.replace(
            self,
            src=jnp.asarray(psrc), dst=jnp.asarray(pdst),
            edge_mask=jnp.asarray(pmask),
            edge_props={k: jnp.asarray(v) for k, v in pprops.items()},
            aux=aux,
            csr_indptr=jnp.asarray(indptr), csr_eidx=jnp.asarray(eidx),
            csr_max_deg=max_deg,
            bucket_id=jnp.asarray(bucket_id), bucket_sizes=sizes,
            bucket_max_deg=max_degs,
            combine_table=(jnp.asarray(build_block_table(pdst, slots))
                           if self.edges_sorted_by_dst else None))
        report = DeltaReport(added_src=delta.add_src.copy(),
                             added_dst=delta.add_dst.copy(),
                             removed_src=removed_src,
                             removed_dst=removed_dst,
                             compacted=compacted)
        return new, report


def resolve_combine_route(program: VertexProgram, part: DevicePartition,
                          num_segments: int) -> str:
    """The dense scan's default combine route for one call: "pallas" where
    the Pallas block kernel applies, else "xla".

    The kernel applies to dst-sorted edges whose ingress-time
    `combine_table` spans this call's segment space (its length is
    `table_length(E, num_segments)`), a scalar float32 payload and a sum,
    min or max monoid.  "pallas" runs the kernel where the program is
    lowered for a TPU; every other platform lowers the XLA scatter-reduce
    (`segment_combine(use_pallas=None)`).  Everything else — unsorted or
    re-pointed `dst` columns (`AgentExchange(overlap=True)`), a table
    built for another segment space, D-wide payloads, integer payloads —
    resolves to "xla".  Each shard of `DistGREEngine` resolves the same
    way over its own columns: the agent shard over its slot space, the
    pipelined and async tiles over their compact spaces.
    """
    from repro.kernels.segment_combine import table_length
    table = part.combine_table
    fits = (part.edges_sorted_by_dst and table is not None
            and part.dst is not None
            and table.shape[-1] == table_length(part.dst.shape[-1],
                                                num_segments))
    scalar = (program.payload_shape == ()
              and jnp.dtype(program.msg_dtype) == jnp.float32)
    kernel_op = program.monoid.name in ("sum", "min", "max")
    return "pallas" if fits and scalar and kernel_op else "xla"


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class EngineState:
    """Runtime vertex states (paper §6.1.3), flat column arrays per slot.

    `lane_active` is the OPTIONAL per-payload-lane halt tracker ([D] bool,
    None outside serving): today's global halt runs the batch until the
    SLOWEST lane converges, but multi-source programs exposing
    `VertexProgram.lane_activates` get per-lane improvement reduced into
    this field by `apply` each superstep — a False entry means that lane's
    query reached its fixed point (monotone programs: quiet stays quiet)
    and the serving layer (repro.serving.graph_scheduler) may retire it
    and reseed the lane between supersteps.  Enabled via
    `init_state(..., lane_tracking=True)`; None keeps the classic pytree
    structure (zero cost, zero recompilation for non-serving runs).

    `counters` is the OPTIONAL per-superstep work record ([rows, 3] int32,
    None by default, which keeps the pytree and the compiled run as they
    are): row i holds superstep i's active vertices, active out-edges (the
    out-degrees of the active sources summed) and the edges the chosen
    scatter route scanned (`frontier.superstep_counts`, columns named by
    `frontier.COUNTERS`).  Rows of supersteps that did not run stay 0.
    Enabled via `init_state(..., counters=rows)`; single-shard runs only.
    """

    vertex_data: jnp.ndarray     # [num_masters, *V]
    scatter_data: jnp.ndarray    # [num_slots, *S] (agents hold forwarded copies)
    active_scatter: jnp.ndarray  # [num_slots] bool
    step: jnp.ndarray            # scalar int32 superstep counter
    lane_active: Optional[jnp.ndarray] = None  # [D] bool, serving only
    counters: Optional[jnp.ndarray] = None     # [rows, 3] int32, opt-in


def seed_operands(program: VertexProgram, source, lane_tracking: bool = False):
    """`init_state`'s `source` on the host, as ``(ids, lanes, lane_row)``:
    the original ids to seed (-1 leaves a lane unseeded), the lane each
    seeds (None for a scalar source, which seeds its whole row) and the
    `lane_active` row (seeded lanes start active), each None when absent."""
    ids = lanes = row = None
    if source is not None and np.ndim(source) == 0:
        ids = np.array([int(source)])
    elif source is not None:
        ids = np.array([-1 if sv is None or int(sv) < 0 else int(sv)
                        for sv in source])
        lanes = jnp.arange(ids.size)
    if lane_tracking:
        width = program.payload_shape[0] if program.payload_shape else None
        if (program.lane_activates is None or width is None
                or ids is not None and (lanes is None or ids.size != width)):
            raise ValueError("lane_tracking needs a program with "
                             "`lane_activates` (payload_shape=(D,)) and no "
                             "source or a sequence of D sources")
        row = jnp.asarray(np.zeros(width, bool) if ids is None else ids >= 0)
    return ids, lanes, row


class EngineLifecycle:
    """The state lifecycle around the superstep, written once for both
    engines: plan adoption and the tuned-plan cache, applying a delta, the
    warm start and the serving admit step.  A `target` is a
    `DevicePartition` (`GREEngine`) or an `AgentGraph` (`DistGREEngine`);
    each engine supplies only its layout (`_cache_key`, `_apply_edge_delta`,
    `_original_layout`, `_seed_slots`, `_admit_step`).  The per-shard state
    pieces (`fresh_state`, `seed`, `admit`) are `GREEngine`'s, which the
    mesh applies per shard."""

    def _init_plan(self, plan, plan_cache) -> None:
        """`plan=SuperstepPlan(...)` overrides the knob-by-knob arguments
        now; `plan="auto-tuned"` consults the tuned-plan cache (PlanCache at
        `plan_cache`, else the default location) the first time a target is
        in hand, before any trace fixes the static shapes: a hit adopts the
        stored plan without any probe execution, a miss keeps the knobs."""
        self._plan_cache = plan_cache
        self._auto_plan_pending = plan == "auto-tuned"
        self._plan_key = None     # the consulted key, for `refresh_plan`
        if plan is not None and not self._auto_plan_pending:
            self.adopt_plan(plan)

    def _consult_plan(self, target) -> None:
        self._auto_plan_pending = False
        self._rekey(self._cache_key(target))

    def refresh_plan(self, target) -> bool:
        """Re-key a consulted tuned plan after a graph mutation.

        The fingerprint quantizes its facets (log2 edge counts, skew
        bins), so a small delta is ABSORBED — same key, the adopted plan
        stands and no retrace happens.  A large delta shifts a bin: the
        stale key (plans tuned for the pre-mutation graph silently
        governing the mutated one) is dropped, the cache is consulted under
        the new key (hit = adopt, miss = keep current knobs), and the new
        key becomes current.  Returns True when the key changed.  No-op
        unless this engine ever consulted the cache (`plan="auto-tuned"`).
        """
        if self._plan_key is None:
            return False
        return self._rekey(self._cache_key(target))

    def _rekey(self, key: str) -> bool:
        if key == self._plan_key:
            return False
        self._plan_key = key
        from repro.tuning import PlanCache
        cache = self._plan_cache
        if not isinstance(cache, PlanCache):
            cache = PlanCache(cache)
        plan = cache.lookup(key)
        if plan is not None:
            self.adopt_plan(plan)
        return True

    def apply_delta(self, target, delta):
        """Apply an EdgeDelta to `target` (docs/incremental.md) and re-key
        a consulted tuned plan against the result (`refresh_plan`) before
        anything traces on it.  Returns ``(new_target, DeltaReport)``."""
        new, report = self._apply_edge_delta(target, delta)
        self.refresh_plan(new)
        return new, report

    # ------------------------------------------------------------ incremental
    def warm_start_state(self, target, prev_state: EngineState, report,
                         source=None, lane_tracking: bool = False
                         ) -> EngineState:
        """Seed a re-convergence run on the MUTATED target from the
        previous fixed point (repro.core.incremental; docs/incremental.md).

        Iterative programs (PageRank) carry the previous values forward
        under fresh init activity.  Halting min-monoid traversals reset the
        entries the surviving edges no longer certify (the program's
        `invalidation` policy), and only add-endpoints, in-neighbors of
        resets and self-seeding resets start active; an empty delta yields
        an empty frontier.  The policy runs in ORIGINAL vertex order
        (`incremental.warm_start_columns`); the layout maps master rows out
        and back — the identity on one shard, `old2new` on a mesh (whose
        `agent_graph.apply_edge_delta` preserves master placement)."""
        from repro.core import incremental
        p = self.program
        incremental.check_supported(p, report)
        state0 = self.init_state(target, source=source,
                                 lane_tracking=lane_tracking)
        # the master rows: [:n] of a shard's arrays, [:, :cap] of a stack
        lead = state0.step.ndim
        rows = (slice(None),) * lead + (
            slice(None, state0.vertex_data.shape[lead]),)
        if not p.halts:
            return dataclasses.replace(
                state0, vertex_data=prev_state.vertex_data,
                scatter_data=state0.scatter_data.at[rows].set(
                    prev_state.scatter_data[rows]))
        order, edges, aux = self._original_layout(target)

        def columns(a):     # master rows -> [V, ...] in original order
            a = np.asarray(a)[rows]
            return a.reshape((-1,) + a.shape[lead + 1:])[order]

        def place(cols, base):   # original-order columns into master rows
            out = np.array(base)
            block = np.array(out[rows])
            block.reshape((-1,) + block.shape[lead + 1:])[order] = cols
            out[rows] = block
            return out

        vd, sd, act = incremental.warm_start_columns(
            p, report, source, edges, aux,
            prev=(columns(prev_state.vertex_data),
                  columns(prev_state.scatter_data)),
            init=(columns(state0.vertex_data),
                  columns(state0.scatter_data)))
        return dataclasses.replace(
            state0,
            vertex_data=jnp.asarray(place(vd, state0.vertex_data),
                                    prev_state.vertex_data.dtype),
            scatter_data=jnp.asarray(place(sd, state0.scatter_data),
                                     p.msg_dtype),
            active_scatter=jnp.asarray(place(
                act, np.zeros(state0.active_scatter.shape, bool))))

    # ---------------------------------------------------------------- serving
    def make_admit(self, target):
        """The serving admit step: ONE jitted static-shape call,
        ``admit(state, sources, lanes)`` over `[D]`-wide host arrays.
        `lanes[i]` names a lane to reset (sentinel D = no-op), `sources[i]`
        the ORIGINAL vertex id to seed into it (-1 = reset only: eviction),
        and the lane's `lane_active` bit becomes whether it was seeded.
        Sources map to seed slots whose out-of-bounds-HIGH sentinel
        `mode="drop"` discards, so admission never recompiles."""
        fn, aux = self._admit_step(target)

        def admit(state, sources, lanes):
            sources = np.asarray(sources)
            return fn(aux, state,
                      jnp.asarray(self._seed_slots(target, sources)),
                      jnp.asarray(lanes, jnp.int32),
                      jnp.asarray(sources >= 0))

        return admit


class GREEngine(EngineLifecycle):
    """Drives a VertexProgram over one DevicePartition.

    `frontier` selects the scatter strategy (core/frontier.py):

      "auto"    — per-superstep `lax.cond`: dense scan when the frontier is
                  large, degree-BUCKETED compacted gather when it fits (≈
                  the 5-10% density crossover).  Each degree bucket gathers
                  its own tight `[cap_b, max_deg_b]` tile, so power-law
                  hubs no longer poison `max_deg` for every frontier slot;
                  the only remaining static skip is the degenerate case
                  where even the worst-case bucket tiles would out-scan
                  the dense path (tiny graphs).
      "compact" — always attempt bucketed compaction (tests/micro-
                  benchmarks); per-bucket overflow guards still degrade an
                  overflowing bucket to a bucket-restricted dense scan.
      "flat"    — the PRE-bucketing compacted path: one padded
                  `[cap, max_deg]` tile over the whole frontier, statically
                  gated off when `cap * max_deg >= E` (kept as the
                  benchmark ablation showing why bucketing exists).
      "dense"   — the original every-edge masked scan.

    `use_pallas` picks the combine kernel.  Unset (None), the dense scan
    resolves its route per call (`resolve_combine_route`): the Pallas
    block kernel where it applies and the program is lowered for a TPU,
    the XLA scatter-reduce otherwise; the compacted tiles keep XLA.
    True or False forces one route on both.

    Engines in `dense_frontier` mode (iterative programs like PageRank,
    where every vertex stays active) and partitions without a CSR layout
    always take the dense path.  Level-synchronous iterative programs that
    opt INTO activity masks (`dense_frontier=False`, e.g. Brandes' backward
    δ whose frontier is one depth level) do compact; for their sum monoids
    the strategies agree to float tolerance (the segment reduction
    reorders), not bitwise like min/max.
    """

    FRONTIERS = ("auto", "dense", "compact", "flat")

    def __init__(self, program: VertexProgram,
                 use_pallas: Optional[bool] = None,
                 dense_frontier: Optional[bool] = None,
                 frontier: str = "auto", frontier_cap: Optional[int] = None,
                 dynamic_table: bool = True, plan=None, plan_cache=None):
        assert frontier in self.FRONTIERS, frontier
        self.program = program
        self.use_pallas = use_pallas
        # Pallas tile combine: on-device dynamic_block_table pruning pass
        # (default) vs the degenerate full-table fallback (docs/kernels.md).
        self.dynamic_table = dynamic_table
        self.frontier = frontier
        self.frontier_cap = frontier_cap
        # Iterative programs (halts=False, e.g. PageRank) keep every vertex
        # active (paper §4.1), so per-edge activity masks are pure overhead;
        # dense mode skips them (the sink slot's scatter_data is pinned to
        # the monoid identity so padded edges still contribute nothing).
        self.dense_frontier = (dense_frontier if dense_frontier is not None
                               else not program.halts)
        # `bucket_bounds` records the degree-bucket ladder an adopted tuned
        # plan was probed against (None = partition default); callers
        # rebuild matching partitions via
        # DevicePartition.from_graph(bucket_bounds=...).
        self.bucket_bounds = None
        self.frontier_hist = None   # set by calibrate_frontier_cap
        self._plan_hist = None      # the cache key's frontier facet
        self._init_plan(plan, plan_cache)

    @property
    def shard_engine(self) -> "GREEngine":
        return self   # the engine whose knobs every shard runs

    def adopt_plan(self, plan: SuperstepPlan) -> None:
        """Take a composed SuperstepPlan's stages as this engine's knobs
        (the inverse of `make_plan`), the kernel route included: an unset
        route (None) stays unset.  Must run before the first jitted `run`
        trace — the adopted frontier capacity and kernel route are static
        compile-time decisions (same contract as
        `calibrate_frontier_cap`)."""
        assert plan.strategy in self.FRONTIERS, plan.strategy
        self.frontier = plan.strategy
        self.frontier_cap = plan.frontier_cap
        self.dense_frontier = plan.dense_frontier
        self.use_pallas = plan.kernel.use_pallas
        self.dynamic_table = plan.kernel.dynamic_table
        self.bucket_bounds = plan.bucket_bounds

    def _cache_key(self, part: DevicePartition) -> str:
        # with the frontier histogram probed at consult time (`init_state`)
        from repro.tuning import plan_cache_key
        return plan_cache_key(part=part, program=self.program, mesh_size=1,
                              frontier_hist=self._plan_hist)

    def _apply_edge_delta(self, part: DevicePartition, delta):
        return part.apply_edge_delta(delta, bucket_bounds=self.bucket_bounds)

    def _original_layout(self, part: DevicePartition):
        # one shard's layout is the original order: master slot v is vertex v
        mask = np.asarray(part.edge_mask)
        prop = self.program.needs_edge_prop
        edges = (np.asarray(part.src)[mask].astype(np.int64),
                 np.asarray(part.dst)[mask].astype(np.int64),
                 np.asarray(part.edge_props[prop])[mask] if prop else None)
        return np.arange(part.num_masters), edges, part.aux

    def make_plan(self, phases: str = "sync",
                  staleness: int = 0) -> SuperstepPlan:
        """The engine's SuperstepPlan (repro.core.plan): frontier strategy
        request + kernel stage.  `phases` RECORDS the exchange phase shape
        (with `staleness` = the async ring depth k, 0 otherwise) so the
        composed mode is inspectable as one static object (the executor
        itself drives whichever shape the backend's phase protocol
        implements — see `plan.execute_plan`).  Rebuilt on demand so
        `calibrate_frontier_cap`'s capacity update is honored."""
        return SuperstepPlan(
            strategy=self.frontier, frontier_cap=self.frontier_cap,
            dense_frontier=self.dense_frontier, phases=phases,
            staleness=staleness,
            kernel=KernelPlan(use_pallas=self.use_pallas,
                              dynamic_table=self.dynamic_table))

    def _frontier_plan(self, part: DevicePartition):
        """Legacy shim over `plan.resolve_frontier`: None for the dense
        path (compile no compacted branch), else the FrontierPlan tuple
        (``("flat", cap)`` / ``("bucketed", caps)``)."""
        fp = self.make_plan().frontier(part)
        return None if fp.kind == "dense" else fp

    def calibrate_frontier_cap(self, part: DevicePartition,
                               state: EngineState, probe_steps: int = 2,
                               ) -> list:
        """Derive `frontier_cap` from the LIVE frontier sizes of the first
        superstep(s) instead of a fixed fraction of `num_slots` (which
        over-allocates on large shards — see `frontier.default_cap`).

        Runs up to `probe_steps` dense supersteps (the state is not
        consumed; callers re-run from the same initial state) and records
        the frontier-size histogram — the PROBE state is threaded through
        ONE jit-compiled superstep, so an N-step probe costs one trace
        plus N executions instead of N eager op-by-op dispatches.  Must
        be called BEFORE the first jitted `run` trace: the capacity is a
        static compile-time shape.  Sets `self.frontier_cap` and returns
        the measured histogram (also kept on `self.frontier_hist`) — the
        tuner's graph fingerprint reuses it as its frontier-density
        estimate rather than re-probing.
        """
        from repro.core.frontier import default_cap
        self.frontier_hist = self.probe_frontier_hist(part, state,
                                                      probe_steps)
        self.frontier_cap = default_cap(part.num_slots,
                                        frontier_hist=self.frontier_hist)
        return self.frontier_hist

    def probe_frontier_hist(self, part: DevicePartition, state: EngineState,
                            probe_steps: int = 2) -> list:
        """The shared probe harness's frontier measurement: run up to
        `probe_steps` dense supersteps from `state` (not consumed) and
        return the live frontier-size histogram `[|F_0|, |F_1|, ...]`.
        One dense-strategy superstep is jitted once and reused across
        probe steps."""
        probe = GREEngine(self.program, dense_frontier=self.dense_frontier,
                          frontier="dense")
        step = jax.jit(lambda s: probe.superstep(part, s))
        hist, s = [], state
        for _ in range(probe_steps):
            n = int(jnp.sum(s.active_scatter))
            if n == 0:
                break
            hist.append(n)
            s = step(s)
        return hist

    # ------------------------------------------------------------------ init
    def fresh_state(self, num_masters: int, num_slots: int, aux,
                    counters: int = 0) -> EngineState:
        """One shard's state before seeding: the program's initial values
        and activity on the masters, the monoid identity elsewhere."""
        p = self.program
        n = num_masters
        vertex_data = p.init_vertex_data(n, aux)
        sd0 = jnp.asarray(p.init_scatter_data(n, aux), p.msg_dtype)
        scatter_data = jnp.full((num_slots,) + sd0.shape[1:],
                                p.monoid.identity, p.msg_dtype).at[:n].set(sd0)
        active = jnp.zeros(num_slots, dtype=bool).at[:n].set(
            p.init_active(n, aux))
        return EngineState(
            vertex_data, scatter_data, active, jnp.zeros((), jnp.int32),
            counters=jnp.zeros((counters, 3), jnp.int32) if counters
            else None)

    def seed(self, state: EngineState, slots, lanes, aux) -> EngineState:
        """The seeding step: seed each `(slots[i], lanes[i])` pair (whole
        rows for `lanes=None`) and activate its slot; a slot at or past
        `num_slots` is the sentinel and drops.  It goes through the
        program's `seed_sources` hook when it has one (PPR stages its
        first push), else sets 0.0 at `[slot, lane]`."""
        p = self.program
        vd, sd = state.vertex_data, state.scatter_data
        if lanes is None:
            vd = vd.at[slots].set(0.0, mode="drop")
            sd = sd.at[slots].set(0.0, mode="drop")
        elif p.seed_sources is not None:
            vd, sd = p.seed_sources(vd, sd, slots, lanes, aux)
        else:
            vd = vd.at[slots, lanes].set(0.0, mode="drop")
            sd = sd.at[slots, lanes].set(0.0, mode="drop")
        return dataclasses.replace(
            state, vertex_data=vd, scatter_data=sd,
            active_scatter=state.active_scatter.at[slots].set(
                True, mode="drop"))

    def shard_state(self, num_masters: int, num_slots: int, aux,
                    slots=None, lanes=None, lane_row=None,
                    counters: int = 0) -> EngineState:
        """`fresh_state`, then — given seed slots — `seed` from an empty
        frontier (`DistGREEngine.init_state` vmaps this over shards)."""
        state = dataclasses.replace(
            self.fresh_state(num_masters, num_slots, aux, counters),
            lane_active=lane_row)
        if slots is None:
            return state
        return self.seed(dataclasses.replace(
            state, active_scatter=jnp.zeros(num_slots, dtype=bool)),
            slots, lanes, aux)

    def init_state(self, part: DevicePartition, source=None,
                   lane_tracking: bool = False,
                   counters: int = 0) -> EngineState:
        """`source` may be a single vertex id, or — for multi-source batched
        traversal programs with `payload_shape=(D,)` — a length-D sequence:
        source d seeds payload lane d, so ONE pass answers D roots.

        Multi-source seeding is LANE-MASKED: entries that are None or
        negative leave their lane unseeded (identity values, inactive) —
        the serving layer starts with fewer queries than lanes and admits
        into the free lanes later (`seed_operands`, `seed`).

        `lane_tracking=True` attaches the per-lane halt tracker
        (`EngineState.lane_active`, seeded lanes start active); requires a
        program exposing `lane_activates` and no source or D sources.

        `counters=rows` attaches the per-superstep work record
        (`EngineState.counters`, `[rows, 3]` int32 zeros); `run` refuses
        fewer rows than its `max_steps`.  It reads the partition's CSR
        layout, so a partition without one is refused.
        """
        ids, lanes, row = seed_operands(self.program, source, lane_tracking)
        if counters and part.csr_indptr is None:
            raise ValueError("counters need the partition's CSR layout "
                             "(csr_indptr), which this partition lacks")
        slots = (None if ids is None
                 else jnp.asarray(self._seed_slots(part, ids)))
        state = self.shard_state(part.num_masters, part.num_slots, part.aux,
                                 slots, lanes, row, counters)
        if self._auto_plan_pending:
            # plan="auto-tuned": the seeded state is the last eager point
            # before a jitted run trace fixes the static tile shapes, and
            # the cache key's frontier-density facet needs it
            self._plan_hist = self.probe_frontier_hist(part, state)
            self._consult_plan(part)
        return state

    def _seed_slots(self, part: DevicePartition, ids) -> np.ndarray:
        # vertex v's slot is v; -1 becomes the sentinel `num_slots`
        return np.where(ids >= 0, ids, part.num_slots).astype(np.int32)

    def rerun_incremental(self, part: DevicePartition, prev_state: EngineState,
                          delta, *, source=None, max_steps: int = 100,
                          lane_tracking: bool = False):
        """Apply an EdgeDelta and re-converge from `prev_state`'s fixed
        point through the unchanged plan executor.

        Returns ``(new_partition, final_state, report)``.  The final state
        is bitwise-equal to a cold `run` on the mutated graph for halting
        min-monoid programs (tests/test_conformance.py locks this down);
        iterative programs re-converge to the same tolerance they always
        carry.  Supersteps and edge scans are proportional to the
        perturbation, not the graph (benchmarks/bench_incremental.py).
        """
        new_part, report = self.apply_delta(part, delta)
        state = self.warm_start_state(new_part, prev_state, report,
                                      source=source,
                                      lane_tracking=lane_tracking)
        return new_part, self.run(new_part, state, max_steps), report

    # ---------------------------------------------------------------- serving
    def device_topology(self, part: DevicePartition) -> DevicePartition:
        return part   # what the tick runs over

    def to_original(self, part: DevicePartition, vertex_data) -> np.ndarray:
        return np.asarray(jax.device_get(vertex_data))   # slot v = vertex v

    def make_superstep(self, part: DevicePartition, steps_per_tick: int = 1):
        """The jitted SERVING TICK, ``tick(part, state)``: `steps_per_tick`
        supersteps, no convergence loop.  The partition is an ARGUMENT: jit
        embeds closed-over arrays as constants, a copy of the topology."""
        def tick(part, state):
            for _ in range(steps_per_tick):
                state = self.superstep(part, state)
            return state

        return jax.jit(tick)

    def admit(self, aux, state: EngineState, slots, lanes,
              flags) -> EngineState:
        """One shard's serving admit step (see `make_admit`): reset the
        named lanes to their fresh values, normalize stale seed rows, then
        `seed`; `flags` become the lanes' `lane_active` bits."""
        p = self.program
        D = lanes.shape[0]
        identity = p.monoid.identity
        fresh = self.fresh_state(state.vertex_data.shape[0],
                                 state.scatter_data.shape[0], aux)
        mask = jnp.zeros(D, dtype=bool).at[lanes].set(True, mode="drop")
        vd = state.vertex_data
        vd = jnp.where(mask.reshape((1, D) + (1,) * (vd.ndim - 2)),
                       fresh.vertex_data, vd)
        sd = jnp.where(mask[None, :], fresh.scatter_data, state.scatter_data)
        # Activating the seed vertex makes it scatter EVERY lane of its row
        # next superstep.  An inactive vertex's row is stale — its values
        # were already delivered (sum monoids would double-count them) — so
        # normalize it to the identity; an ACTIVE vertex's row was
        # rewritten by the last apply and is still undelivered, so it must
        # be kept.
        rows = jnp.take(sd, slots, axis=0, mode="fill", fill_value=identity)
        keep = jnp.take(state.active_scatter, slots, mode="fill",
                        fill_value=False)
        rows = jnp.where(keep.reshape((D,) + (1,) * (rows.ndim - 1)),
                         rows, identity)
        sd = sd.at[slots].set(rows, mode="drop")
        state = self.seed(dataclasses.replace(state, vertex_data=vd,
                                              scatter_data=sd),
                          slots, lanes, aux)
        return dataclasses.replace(
            state, lane_active=state.lane_active.at[lanes].set(
                flags, mode="drop"))

    def _admit_step(self, part: DevicePartition):
        return jax.jit(self.admit), part.aux   # aux an argument, as above

    # ------------------------------------------------------- scatter-combine
    def scatter_combine(self, part: DevicePartition, state: EngineState,
                        num_segments: Optional[int] = None) -> jnp.ndarray:
        """Phase 1: active messages on all out-edges of active vertices.

        Returns the ⊕-accumulated combine_data over `num_segments` slots
        ([num_segments, *payload_shape]; defaults to all local slots).

        Dispatches between the dense every-edge scan and the
        frontier-compacted CSR-range gather (core/frontier.py) via the
        plan's scatter stage (`SuperstepPlan.scatter_combine`); exchange
        backends call THIS, so compaction slots in without touching them.
        """
        return self.make_plan().scatter_combine(self, part, state,
                                                num_segments)

    def dense_scatter_combine(self, part: DevicePartition, state: EngineState,
                              num_segments: Optional[int] = None
                              ) -> jnp.ndarray:
        """The dense strategy: scan every edge, mask inactive sources."""
        assert part.src is not None, \
            "partition carries no edge columns (tile-only topology)"
        p = self.program
        eprop = (part.edge_props[p.needs_edge_prop]
                 if p.needs_edge_prop else None)
        with jax.named_scope("gre.scatter"):
            gathered = jnp.take(state.scatter_data, part.src, axis=0,
                                fill_value=p.monoid.identity)
            msgs = p.scatter_msg(gathered, eprop)
            if self.dense_frontier:
                msgs = msgs.astype(p.msg_dtype)
            else:
                live = jnp.take(state.active_scatter, part.src, axis=0,
                                fill_value=False) & part.edge_mask
                live = live.reshape(live.shape + (1,) * (msgs.ndim - live.ndim))
                msgs = jnp.where(live, msgs.astype(p.msg_dtype),
                                 p.monoid.identity)
        nseg = num_segments or part.num_slots
        table, use_pallas = None, False
        if self.combine_route(part, nseg) == "pallas":
            # unset (None): the kernel where the program is lowered for a TPU
            table = self._combine_table(part, nseg)
            use_pallas = self.use_pallas
        with jax.named_scope("gre.combine"):
            return segment_combine(
                msgs, part.dst, nseg, p.monoid,
                indices_are_sorted=part.edges_sorted_by_dst,
                use_pallas=use_pallas, table=table)

    def combine_route(self, part: DevicePartition,
                      num_segments: Optional[int] = None) -> str:
        """The dense scan's combine route, "pallas" or "xla": forced by an
        explicit `use_pallas`, else `resolve_combine_route`."""
        if self.use_pallas is not None:
            return "pallas" if self.use_pallas else "xla"
        return resolve_combine_route(self.program, part,
                                     num_segments or part.num_slots)

    @staticmethod
    def _combine_table(part: DevicePartition, num_segments: int):
        """The dense scan's Pallas block schedule: the partition's
        ingress-time table, checked against this call's segment space.  A
        partition whose edges are not dst-sorted (or that carries no table)
        gets None, which the kernel wrapper accepts only for a concrete
        `dst` column — never a quiet reference fallback."""
        if part.combine_table is None or not part.edges_sorted_by_dst:
            return None
        from repro.kernels.segment_combine import table_length
        want = table_length(part.dst.shape[-1], num_segments)
        if part.combine_table.shape[-1] != want:
            raise ValueError(
                f"partition's block table has {part.combine_table.shape[-1]}"
                f" visits; a {num_segments}-segment combine over "
                f"{part.dst.shape[-1]} edges needs {want} — it was built "
                f"for another segment space")
        return part.combine_table

    # ------------------------------------------------------------------ apply
    @jax.named_scope("gre.apply")
    def apply(self, part: DevicePartition, state: EngineState,
              combined: jnp.ndarray) -> EngineState:
        """Phase 2: fold combine_data into vertex_data; assert_to_halt.

        `aux` reaching apply_fn carries the superstep counter under "step" —
        level-synchronous programs (Brandes' backward δ) schedule themselves
        off it without bespoke drivers.
        """
        p = self.program
        n = part.num_masters
        combined_m = combined[:n]
        aux = dict(part.aux)
        aux["step"] = state.step
        act_apply = p.combine_activates(state.vertex_data, combined_m)
        new_vd, new_sd, act_scatter = p.apply_fn(state.vertex_data,
                                                 combined_m, aux)
        bva = act_apply.reshape(act_apply.shape + (1,) * (new_vd.ndim - act_apply.ndim))
        vertex_data = jnp.where(bva, new_vd, state.vertex_data)
        bsa = act_apply.reshape(act_apply.shape + (1,) * (new_sd.ndim - act_apply.ndim))
        scatter_data = state.scatter_data.at[:n].set(
            jnp.where(bsa, new_sd.astype(p.msg_dtype),
                      state.scatter_data[:n]))
        if p.halts:  # traversal: only improved vertices scatter next round
            next_active = act_apply & act_scatter
        else:        # iterative: activity is whatever apply asserts
            next_active = act_scatter
        active = jnp.zeros_like(state.active_scatter).at[:n].set(next_active)
        # per-lane halt tracking (serving): reduce the program's per-lane
        # improvement over the masters — lane d quiet this superstep means
        # its query converged (monotone lanes cannot reawaken on their own)
        lane_active = state.lane_active
        if lane_active is not None and p.lane_activates is not None:
            lane_active = jnp.any(p.lane_activates(state.vertex_data,
                                                   combined_m), axis=0)
        return EngineState(vertex_data, scatter_data, active, state.step + 1,
                           lane_active, state.counters)

    # ------------------------------------------------------------- superstep
    def superstep(self, part: DevicePartition, state: EngineState,
                  exchange: ExchangeBackend = NULL_EXCHANGE) -> EngineState:
        """THE superstep: refresh → scatter-combine/reduce → apply.

        Single-shard and distributed execution differ only in `exchange`.
        Delegates to the plan layer's phase-protocol form
        (`plan.execute_superstep`) so a single eager superstep — the
        serving tick — takes the same local_phase/merge path on every
        backend, including the pipelined split tiles.
        """
        from repro.core.plan import execute_superstep
        return execute_superstep(self, part, state, exchange)

    # -------------------------------------------------------------------- run
    @partial(jax.jit, static_argnums=(0, 3))
    def run(self, part: DevicePartition, state: EngineState,
            max_steps: int = 100) -> EngineState:
        """BSP loop: terminate when no vertex is scatter-active (paper §4.1)
        or after `max_steps` supersteps.

        Single-shard entry to the plan executor (`plan.execute_plan`) with
        the NullExchange — the SAME driver loop the distributed engine
        runs under shard_map with real backends (sync or pipelined phase
        shapes).
        """
        return execute_plan(self, part, state, NULL_EXCHANGE,
                            max_steps=max_steps)

    # ------------------------------------------------- GAS baseline (ablation)
    def gas_superstep(self, part: DevicePartition, state: EngineState,
                      edge_state: jnp.ndarray) -> tuple:
        """Two-sided GAS emulation (paper §2.2 motivation, Fig. 2 left).

        Phase S-1 scatter: materialize per-edge messages into `edge_state`
        (the intermediate storage Scatter-Combine eliminates).  Phase S
        gather: poll in-edges and reduce.  Used only by the GAS-vs-SC
        ablation benchmark; numerically identical, strictly more memory
        traffic (one extra [E] store + load).
        """
        p = self.program
        eprop = (part.edge_props[p.needs_edge_prop]
                 if p.needs_edge_prop else None)
        gathered = jnp.take(state.scatter_data, part.src, axis=0,
                            fill_value=p.monoid.identity)
        msgs = p.scatter_msg(gathered, eprop)
        live = jnp.take(state.active_scatter, part.src, axis=0,
                        fill_value=False) & part.edge_mask
        new_edge_state = jnp.where(live, msgs.astype(p.msg_dtype),
                                   p.monoid.identity)
        # --- super-step boundary: edge_state persists ---
        combined = segment_combine(
            new_edge_state, part.dst, part.num_slots, p.monoid,
            indices_are_sorted=part.edges_sorted_by_dst)
        return self.apply(part, state, combined), new_edge_state
