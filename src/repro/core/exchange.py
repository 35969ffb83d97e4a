"""ExchangeBackend: the pluggable communication substrate of the engine.

The GRE computation model (paper §4, Alg. 2) is one canonical superstep —
refresh scatter state, fused scatter-combine, apply — independent of HOW
partial combines cross device boundaries.  This module isolates that seam:

  NullExchange   — single shard: every destination is local, nothing moves.
  AgentExchange  — the paper's Agent-Graph (§5): masters push ONE message per
                   (master, peer) to scatter agents before the local phase;
                   combiners push ONE ⊕-reduced message per agent to their
                   master after it.  |V_s| + |V_c| messages per superstep.
                   `overlap=True` issues the flush for remote-destined edges
                   before local-destined edges compute (§6.2's communication/
                   computation overlap, as an XLA scheduling hint).
  DenseExchange  — hash-partition/Pregel baseline: ⊕-reduce the full
                   relabeled vertex vector with a collective (psum/pmin/pmax).
  PipelinedAgentExchange — the Agent-Graph protocol restructured for
                   communication/computation overlap (paper §6.2): edges are
                   split ONCE at ingress into remote-destined and
                   local-destined tiles (`agent_graph.split_edge_tiles`);
                   each superstep ⊕-combines the remote tile first, issues
                   the flush collective, then combines the local tile while
                   the collective is in flight.  The two partial combines
                   ride a two-slot `Mailbox` so the merge can be deferred to
                   the top of the NEXT superstep (the plan executor,
                   `repro.core.plan.execute_plan`).
  AsyncAgentExchange — bounded-staleness execution for MONOTONE programs
                   (`VertexProgram.monotone`: halting ⊕ = min/max): the
                   Mailbox generalizes to a k-deep ring of remote-tile
                   partials, the scatter refresh and combiner flush
                   collectives run once per k supersteps instead of every
                   superstep, and local updates keep applying eagerly in
                   between — each shard runs up to `staleness_bound = k`
                   supersteps ahead on stale remote state.  The fixed
                   point matches the synchronous schedule exactly
                   (delayed delivery of a valid min/max bound only
                   re-tightens later); the trajectory does not, which is
                   why non-monotone (sum) programs must refuse this
                   backend.

The two Agent-Graph collectives name their device work for a profile:
`refresh_scatter_agents` runs under `jax.named_scope("gre.exchange.refresh")`
and `flush_combiners` under `"gre.exchange.flush"` (gathers, `all_to_all`,
slot scatter or segment fold), beside the engine's `gre.scatter`,
`gre.combine` and `gre.apply`; the fold of local and flushed partials is
`gre.combine`.

All backends speak first-class feature-vector payloads: state and message
arrays are `[slots, *payload_shape]`; scalars are the `payload_shape=()`
special case.  Backends are plain callables on jnp arrays, usable inside
`shard_map` (Agent/Dense/Pipelined) or outside any mesh (Null).

A doctest for the master-slot mask helper (masters are renumbered first,
agents live high — paper §6.1.1):

    >>> import jax.numpy as jnp
    >>> bool(_master_mask(jnp.zeros((4, 2)), 2)[2, 0])
    False
    >>> [bool(b) for b in _master_mask(jnp.zeros(3), 2)]
    [True, True, False]
"""
from __future__ import annotations

import dataclasses
from typing import TYPE_CHECKING, Optional, Protocol, runtime_checkable

import jax
import jax.numpy as jnp

from repro.core.vertex_program import Monoid, segment_combine

if TYPE_CHECKING:  # pragma: no cover - typing only, avoids a cycle
    from repro.core.engine import DevicePartition, EngineState, GREEngine


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class PipelineTiles:
    """Device-local remote/local edge tiles for PipelinedAgentExchange.

    Built at ingress from `agent_graph.split_edge_tiles`: `part_remote`
    carries the combiner-destined edges with dst relabeled into the compact
    combiner space `[0, num_combiners]`, `part_local` the master-destined
    edges (`[0, num_masters]`); index `num_combiners`/`num_masters` is the
    padding identity slot of each tile.  The exchange indices are the same
    per-peer layout as `ShardTopology`'s, remapped into those compact
    spaces.
    """

    part_remote: "DevicePartition"   # combiner-destined edge tile
    part_local: "DevicePartition"    # master-destined edge tile
    comb_send_compact: jnp.ndarray   # [k, x_pad] into the remote ⊕ array
    comb_recv_master: jnp.ndarray    # [k, x_pad] master slot; fill = cap
    num_combiners: int = dataclasses.field(metadata=dict(static=True))


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class Mailbox:
    """Two-slot superstep buffer carried through the pipelined loop.

    Slot `flushed` holds the in-flight remote contributions (the flush
    collective's landing buffer); slot `local` holds the local-tile partial
    ⊕.  `PipelinedAgentExchange.merge` folds the two at the top of the next
    superstep — legal because ⊕ is commutative/associative, so remote and
    local partials can be combined in either order.
    """

    local: jnp.ndarray    # [num_masters + 1, *payload]
    flushed: jnp.ndarray  # [num_masters + 1, *payload]


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class AsyncRing:
    """k-deep generalization of `Mailbox` for bounded-staleness supersteps.

    `ring[i]` holds the remote-tile partial ⊕ (compact combiner space)
    produced at the superstep with `step % k == i`; at the window boundary
    (`step % k == k - 1`) all k entries ⊕-fold and flush in ONE collective,
    landing in `landed` for the next merge, and the ring resets to
    identity.  `local` is the eager local-tile partial (merged every
    superstep).  `dirty` records whether any master improved since the
    last scatter refresh — in-flight information the termination predicate
    must count: a shard is quiescent only when its frontier is empty AND
    every ring entry is identity AND no un-refreshed improvement is held
    (`AsyncAgentExchange.carry_pending`).
    """

    local: jnp.ndarray    # [num_masters + 1, *payload]
    landed: jnp.ndarray   # [num_masters + 1, *payload]
    ring: jnp.ndarray     # [k, num_combiners + 1, *payload]
    dirty: jnp.ndarray    # scalar bool: master improved since last refresh


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class ShardTopology:
    """Device-local (inside shard_map) view of one AgentGraph partition."""

    part: "DevicePartition"        # local slots + edges
    comb_send_slot: jnp.ndarray    # [k, x_pad]
    comb_recv_master: jnp.ndarray  # [k, x_pad]
    scat_send_master: jnp.ndarray  # [k, x_pad]
    scat_recv_slot: jnp.ndarray    # [k, x_pad]
    tiles: Optional[PipelineTiles] = None  # pipelined-exchange edge split


def _master_mask(combined: jnp.ndarray, num_masters: int) -> jnp.ndarray:
    """[slots] -> broadcastable-to-payload bool mask of master slots."""
    m = jnp.arange(combined.shape[0]) < num_masters
    return m.reshape(m.shape + (1,) * (combined.ndim - 1))


@jax.named_scope("gre.exchange.refresh")
def refresh_scatter_agents(topo: ShardTopology, scatter_data: jnp.ndarray,
                           active: jnp.ndarray, axes,
                           dense: bool = False):
    """Exchange 1 (master → scatter agent): ONE message per (master, peer).

    Works for scalar or feature-vector `scatter_data` ([slots] or
    [slots, *D]).  Returns refreshed (scatter_data, active).  With
    `dense=True` (iterative programs: every vertex active) the activity
    payload is skipped — half the exchange ops.
    """
    vals = jnp.take(scatter_data, topo.scat_send_master, axis=0)   # [k, x, *D]
    rec_v = jax.lax.all_to_all(vals, axes, split_axis=0, concat_axis=0,
                               tiled=True)
    slots = topo.scat_recv_slot.reshape(-1)
    flat_v = rec_v.reshape((-1,) + rec_v.shape[2:])
    sd = scatter_data.at[slots].set(flat_v.astype(scatter_data.dtype),
                                    mode="drop")
    if dense:
        return sd, active
    acts = jnp.take(active, topo.scat_send_master, axis=0)         # [k, x]
    rec_a = jax.lax.all_to_all(acts, axes, split_axis=0, concat_axis=0,
                               tiled=True)
    # scattered as int32: the TPU compiler lowers a sub-32-bit scatter
    # through a sort, ~8 s of compile per scatter at 10^5 indices
    act = active.astype(jnp.int32).at[slots].set(
        rec_a.reshape(-1).astype(jnp.int32), mode="drop") > 0
    return sd, act


@jax.named_scope("gre.exchange.flush")
def flush_combiners(topo: ShardTopology, combined: jnp.ndarray, axes,
                    monoid: Monoid, send_slot: Optional[jnp.ndarray] = None,
                    recv_master: Optional[jnp.ndarray] = None,
                    num_segments: Optional[int] = None) -> jnp.ndarray:
    """Exchange 2 (combiner → master): ONE ⊕-reduced value per agent.

    Returns a [num_segments, *D] array of remote contributions folded into
    local master slots (identity elsewhere).  By default `combined` is the
    full slot space and the topology's exchange indices apply; the
    pipelined backend passes its compact-space indices and the
    `[num_masters + 1]` segment count instead (`PipelineTiles`).
    """
    send = topo.comb_send_slot if send_slot is None else send_slot
    recv = topo.comb_recv_master if recv_master is None else recv_master
    vals = jnp.take(combined, send, axis=0)                         # [k, x, *D]
    rec = jax.lax.all_to_all(vals, axes, split_axis=0, concat_axis=0,
                             tiled=True)
    flat = rec.reshape((-1,) + rec.shape[2:])
    return segment_combine(flat.astype(combined.dtype), recv.reshape(-1),
                           num_segments or topo.part.num_slots, monoid)


@runtime_checkable
class ExchangeBackend(Protocol):
    """The seam between the canonical superstep and the network.

    `refresh` runs before the local scatter-combine (push master scatter
    state to remote readers); `reduce` produces the fully ⊕-combined
    array the apply phase folds — at least `[num_masters, *payload]` rows
    (apply reads only master slots; Null/Agent/Dense return the full
    `[num_slots]` slot space, the pipelined backend the compact
    `[num_masters + 1]` master space).

    Every backend additionally speaks the PHASE protocol the plan executor
    drives (`repro.core.plan.execute_plan`): `local_phase` produces a
    per-superstep carry (receiving the PREVIOUS carry, which only the
    async shape reads — its ring persists across supersteps), `merge`
    folds it into the combined array apply consumes, `carry_init` builds
    the carry's identity-valued shape placeholder for the loop seed, and
    `carry_pending` reports whether the carry still holds in-flight
    contributions the termination predicate must wait for (identity-False
    for sync/pipelined: their carries are fully consumed by the very next
    merge).  `phases` names the shape ("sync": the carry IS the reduce
    output and merge is the identity; "pipelined": the carry is a two-slot
    `Mailbox` whose flush collective overlaps the next local combine;
    "async": the carry is a k-deep `AsyncRing` flushed once per k
    supersteps).
    """

    phases: str

    def refresh(self, state: "EngineState") -> "EngineState": ...

    def reduce(self, engine: "GREEngine", part: "DevicePartition",
               state: "EngineState") -> jnp.ndarray: ...

    def local_phase(self, engine: "GREEngine", part: "DevicePartition",
                    state: "EngineState", carry=None): ...

    def merge(self, carry) -> jnp.ndarray: ...

    def carry_init(self, engine: "GREEngine", part: "DevicePartition"): ...

    def carry_pending(self, carry) -> jnp.ndarray: ...


class _SyncPhase:
    """Default sync phase shape: the whole ⊕-reduce is the local phase and
    the merge is the identity, so the plan executor's deferred-merge loop
    degenerates op-for-op to the classic refresh → reduce → apply
    superstep."""

    phases = "sync"

    def local_phase(self, engine, part, state, carry=None):
        return self.reduce(engine, part, state)

    def merge(self, carry):
        return carry

    def carry_init(self, engine, part):
        p = engine.program
        return jnp.full((part.num_slots,) + tuple(p.payload_shape),
                        p.monoid.identity, p.msg_dtype)

    def carry_pending(self, carry):
        # sync/pipelined carries are fully consumed by the next merge:
        # nothing in them can outlive the frontier-emptiness check
        return jnp.zeros((), dtype=bool)


class NullExchange(_SyncPhase):
    """Single shard: all destinations are local; refresh is the identity."""

    def refresh(self, state):
        return state

    def reduce(self, engine, part, state):
        return engine.scatter_combine(part, state)


NULL_EXCHANGE = NullExchange()


class _RefreshingExchange(_SyncPhase):
    """Shared base for backends that refresh scatter agents before the
    local phase (the first half of the Agent-Graph protocol)."""

    def __init__(self, topo: ShardTopology, axes, monoid: Monoid,
                 dense_frontier: bool = False):
        self.topo = topo
        self.axes = axes
        self.monoid = monoid
        self.dense_frontier = dense_frontier

    def refresh(self, state):
        from repro.core.engine import EngineState
        sd, act = refresh_scatter_agents(self.topo, state.scatter_data,
                                         state.active_scatter, self.axes,
                                         dense=self.dense_frontier)
        return EngineState(state.vertex_data, sd, act, state.step,
                           state.lane_active)


class AgentExchange(_RefreshingExchange):
    """Agent-Graph exchange (paper §5): scatter refresh + combiner flush."""

    def __init__(self, topo: ShardTopology, axes, monoid: Monoid,
                 dense_frontier: bool = False, overlap: bool = False):
        super().__init__(topo, axes, monoid, dense_frontier)
        self.overlap = overlap

    def reduce(self, engine, part, state):
        monoid = self.monoid
        if self.overlap:
            # remote-destined edges first; their flush overlaps local compute
            sink = part.num_slots - 1
            is_remote = part.dst >= part.num_masters  # agents live high
            remote_part = dataclasses.replace(
                part, dst=jnp.where(is_remote, part.dst, sink),
                edges_sorted_by_dst=False)
            local_part = dataclasses.replace(
                part, dst=jnp.where(is_remote, sink, part.dst),
                edges_sorted_by_dst=False)
            combined_remote = engine.scatter_combine(remote_part, state)
            flushed = flush_combiners(self.topo, combined_remote, self.axes,
                                      monoid)
            combined_local = engine.scatter_combine(local_part, state)
            with jax.named_scope("gre.combine"):
                return monoid.op(combined_local, flushed)
        combined = engine.scatter_combine(part, state)
        flushed = flush_combiners(self.topo, combined, self.axes, monoid)
        # master slots take direct local + flushed remote contributions
        with jax.named_scope("gre.combine"):
            local = jnp.where(_master_mask(combined, part.num_masters),
                              combined, monoid.identity)
            return monoid.op(local, flushed)


class DenseExchange(_RefreshingExchange):
    """Pregel-style baseline: collective ⊕ over the full relabeled vector.

    Strictly more traffic than AgentExchange (every device reduces the whole
    [k·cap, *payload] vector); kept as the communication baseline for
    benchmarks and rooflines.
    """

    def __init__(self, topo: ShardTopology, axes, monoid: Monoid,
                 my_row: jnp.ndarray, dense_frontier: bool = False):
        super().__init__(topo, axes, monoid, dense_frontier)
        self.my_row = my_row

    def reduce(self, engine, part, state):
        monoid = self.monoid
        topo = self.topo
        k = jax.lax.psum(1, self.axes)
        cap = part.num_masters
        combined_loc = engine.scatter_combine(part, state)  # [slots, *D]
        payload = combined_loc.shape[1:]
        dtype = combined_loc.dtype
        # project local master slots back to the global vector [k*cap, *D]
        myslice = self.my_row * cap
        global_vec = jnp.full((k * cap,) + payload, monoid.identity, dtype)
        global_vec = global_vec.at[myslice + jnp.arange(cap)].set(
            combined_loc[:cap])
        # combiner slots scatter their partial ⊕ at their global master id
        comb_vals = jnp.take(combined_loc, topo.comb_send_slot, axis=0,
                             fill_value=monoid.identity)  # [k, x, *D]
        recv = jax.lax.all_to_all(topo.comb_recv_master, self.axes, 0, 0,
                                  tiled=True)
        tgt = jnp.arange(k)[:, None] * cap + recv
        tgt = jnp.where(recv >= cap, k * cap, tgt)  # drop padding to sink
        global_vec = segment_combine(
            jnp.concatenate([global_vec,
                             comb_vals.reshape((-1,) + payload)]),
            jnp.concatenate([jnp.arange(k * cap), tgt.reshape(-1)]),
            k * cap + 1, monoid)[:k * cap]
        if monoid.name == "sum":
            total = jax.lax.psum(global_vec, self.axes)
        elif monoid.name == "min":
            total = jax.lax.pmin(global_vec, self.axes)
        else:
            total = jax.lax.pmax(global_vec, self.axes)
        mine = jax.lax.dynamic_slice_in_dim(total, myslice, cap, axis=0)
        return jnp.full((part.num_slots,) + payload, monoid.identity,
                        dtype).at[:cap].set(mine)


class PipelinedAgentExchange(_RefreshingExchange):
    """Double-buffered Agent-Graph exchange (paper §6.2 overlap, pipelined).

    Protocol per superstep, over the static ingress-time edge split
    (`ShardTopology.tiles`):

      local_phase  — ⊕-combine the remote-destined tile into the compact
                     combiner space, ISSUE the flush collective, then
                     ⊕-combine the local-destined tile while the collective
                     is in flight; both partials return in a `Mailbox`.
      merge        — fold `Mailbox.local ⊕ Mailbox.flushed` into the master
                     contributions; deferred to the top of the next
                     superstep by the plan executor
                     (`repro.core.plan.execute_plan`), which carries the
                     mailbox through the loop.

    Compared to `AgentExchange(overlap=True)` — which rewrites `dst` to
    split the SAME edge array twice, scanning 2·E edges per superstep —
    the tiles scan each edge exactly once and ⊕-reduce into
    `[num_masters + 1]` / `[num_combiners + 1]` segment spaces instead of
    the full `[num_slots]` slot space.  Results are bitwise-identical to
    the synchronous `AgentExchange` for min/max monoids (the tiles preserve
    the canonical per-segment reduction order; sums agree to the same order
    too, but cross-backend float guarantees stay at tolerance).

    `reduce` merges immediately, so the backend also drops into the
    standard synchronous superstep (used by the equivalence tests to
    isolate the loop restructure from the edge split).
    """

    phases = "pipelined"

    def __init__(self, topo: ShardTopology, axes, monoid: Monoid,
                 dense_frontier: bool = False):
        super().__init__(topo, axes, monoid, dense_frontier)
        assert topo.tiles is not None, \
            "PipelinedAgentExchange needs ShardTopology.tiles " \
            "(agent_graph.split_edge_tiles)"
        self.tiles = topo.tiles

    def local_phase(self, engine: "GREEngine", part: "DevicePartition",
                    state: "EngineState", carry=None) -> Mailbox:
        """Remote-tile combine + flush issue, then local-tile combine.

        The flush is `flush_combiners` with the compact-space indices: the
        send gather reads the compact combiner ⊕ array and the receive
        folds into `[num_masters + 1]` (identity slot last) — same wire
        traffic, ONE ⊕-reduced message per combiner agent.  Edge scans run
        on the split tiles only; `part` (the canonical partition, which
        carries no edge columns under this backend) is unused.
        """
        t = self.tiles
        masters = self.topo.part.num_masters
        remote = engine.scatter_combine(t.part_remote, state,
                                        num_segments=t.num_combiners + 1)
        flushed = flush_combiners(self.topo, remote, self.axes, self.monoid,
                                  send_slot=t.comb_send_compact,
                                  recv_master=t.comb_recv_master,
                                  num_segments=masters + 1)
        local = engine.scatter_combine(t.part_local, state,
                                       num_segments=masters + 1)
        return Mailbox(local=local, flushed=flushed)

    def merge(self, mailbox: Mailbox) -> jnp.ndarray:
        """⊕ the two mailbox slots: [num_masters + 1, *payload]."""
        return self.monoid.op(mailbox.local, mailbox.flushed)

    def carry_init(self, engine, part):
        p = engine.program
        idm = jnp.full((part.num_masters + 1,) + tuple(p.payload_shape),
                       p.monoid.identity, p.msg_dtype)
        return Mailbox(local=idm, flushed=idm)

    def reduce(self, engine, part, state):
        return self.merge(self.local_phase(engine, part, state))


class AsyncAgentExchange(_RefreshingExchange):
    """Bounded-staleness Agent-Graph exchange: collectives once per k steps.

    Valid ONLY for monotone programs (`VertexProgram.monotone`: halting
    ⊕ = min/max) — every message is a valid bound computed by the same ops
    the synchronous schedule would run, so delaying its delivery changes
    the trajectory but not the unique fixed point.  The engine refuses to
    construct this backend for sum-monoid programs (a partial folded
    against a stale accumulator is double-counted, not re-tightened).

    Protocol per superstep, over the same static ingress edge split as
    the pipelined backend (`ShardTopology.tiles`), with
    `staleness_bound = k`:

      refresh      — the scatter-agent refresh collective runs only at
                     `step % k == 0`; in between, shards scatter from the
                     STALE agent copies.  Because a master's activity flag
                     clears one superstep after it improves, the refresh
                     re-derives agent activity from VALUE CHANGE (received
                     copy != held copy): any improvement since the last
                     refresh — whenever it happened inside the window —
                     scatters exactly once after landing.
      local_phase  — the remote-tile partial is ⊕-combined EVERY superstep
                     into ring slot `step % k`; at the window boundary
                     (`step % k == k - 1`) the k ring entries ⊕-fold and
                     flush in ONE collective (1/k of the pipelined
                     backend's flush traffic), landing for the next merge;
                     the local-tile partial is computed every superstep
                     and merged eagerly — intra-shard propagation runs at
                     full speed, only shard crossings wait (≤ k - 1
                     supersteps in the ring + ≤ k - 1 until the next
                     refresh).
      merge        — `local ⊕ landed`, every superstep (landed is identity
                     except just after a boundary flush).

    Both `step % k` predicates are mesh-uniform (superstep counters
    advance in lockstep inside `plan.execute_plan`'s while-loop), so the
    collectives under their `lax.cond`s stay matched across shards — the
    same discipline as the executor's own continuation cond.

    Termination counts the in-flight state (`carry_pending`): a shard is
    quiescent only when its frontier is empty AND all k ring entries are
    identity AND no master improved since the last refresh (`dirty`) —
    without the last term an improvement whose only cross-shard readers
    are scatter agents on OTHER shards could be stranded between
    refreshes.  `k = 1` degenerates to the pipelined cadence with an
    eager local merge.
    """

    phases = "async"

    def __init__(self, topo: ShardTopology, axes, monoid: Monoid,
                 dense_frontier: bool = False, staleness: int = 2):
        super().__init__(topo, axes, monoid, dense_frontier)
        assert topo.tiles is not None, \
            "AsyncAgentExchange needs ShardTopology.tiles " \
            "(agent_graph.split_edge_tiles)"
        assert staleness >= 1, staleness
        self.tiles = topo.tiles
        self.staleness = staleness

    def refresh(self, state):
        from repro.core.engine import EngineState

        def do(s):
            old_sd = s.scatter_data
            sd, act = refresh_scatter_agents(self.topo, s.scatter_data,
                                             s.active_scatter, self.axes,
                                             dense=self.dense_frontier)
            if not self.dense_frontier:
                # value-change activation: masters that improved mid-window
                # have long-cleared activity flags, but the agents still
                # hold the previous refresh's copy, so != finds them.  Only
                # agent slots can differ (refresh writes nothing else).
                changed = sd != old_sd
                if changed.ndim > 1:
                    changed = jnp.any(
                        changed, axis=tuple(range(1, changed.ndim)))
                act = act | changed
            return EngineState(s.vertex_data, sd, act, s.step,
                               s.lane_active)

        return jax.lax.cond(state.step % self.staleness == 0,
                            do, lambda s: s, state)

    def local_phase(self, engine: "GREEngine", part: "DevicePartition",
                    state: "EngineState", carry=None) -> AsyncRing:
        assert carry is not None, \
            "async local_phase needs the prior AsyncRing carry " \
            "(driven by plan.execute_plan; the serving tick refuses async)"
        t = self.tiles
        k = self.staleness
        masters = self.topo.part.num_masters
        remote = engine.scatter_combine(t.part_remote, state,
                                        num_segments=t.num_combiners + 1)
        slot = state.step % k
        ring = jax.lax.dynamic_update_index_in_dim(carry.ring, remote,
                                                   slot, axis=0)

        def flush(r):
            folded = r[0]
            for i in range(1, k):
                folded = self.monoid.op(folded, r[i])
            landed = flush_combiners(self.topo, folded, self.axes,
                                     self.monoid,
                                     send_slot=t.comb_send_compact,
                                     recv_master=t.comb_recv_master,
                                     num_segments=masters + 1)
            return landed, jnp.full_like(r, self.monoid.identity)

        def hold(r):
            idm = jnp.full((masters + 1,) + r.shape[2:],
                           self.monoid.identity, r.dtype)
            return idm, r

        landed, ring = jax.lax.cond(slot == k - 1, flush, hold, ring)
        local = engine.scatter_combine(t.part_local, state,
                                       num_segments=masters + 1)
        # improvements land on masters as activity the superstep after
        # they happen; at a refresh step everything so far was just pushed
        dirty = jnp.where(state.step % k == 0, False,
                          carry.dirty
                          | jnp.any(state.active_scatter[:masters]))
        return AsyncRing(local=local, landed=landed, ring=ring, dirty=dirty)

    def merge(self, carry: AsyncRing) -> jnp.ndarray:
        return self.monoid.op(carry.local, carry.landed)

    def carry_init(self, engine, part):
        p = engine.program
        masters = self.topo.part.num_masters
        payload = tuple(p.payload_shape)
        idm = jnp.full((masters + 1,) + payload, p.monoid.identity,
                       p.msg_dtype)
        ring = jnp.full((self.staleness, self.tiles.num_combiners + 1)
                        + payload, p.monoid.identity, p.msg_dtype)
        return AsyncRing(local=idm, landed=idm, ring=ring,
                         dirty=jnp.zeros((), dtype=bool))

    def carry_pending(self, carry: AsyncRing) -> jnp.ndarray:
        return jnp.any(carry.ring != self.monoid.identity) | carry.dirty

    def reduce(self, engine, part, state):
        raise NotImplementedError(
            "AsyncAgentExchange has no single-superstep reduce: partials "
            "live in the k-deep ring across supersteps.  Use the plan "
            "executor (DistGREEngine.make_run); the serving tick refuses "
            "exchange='async'.")
