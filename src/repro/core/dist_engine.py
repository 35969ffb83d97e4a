"""Distributed GRE engine: the canonical superstep under shard_map.

Each device owns one agent-graph partition (masters + agents + edge shard)
and runs `GREEngine.superstep` — the SAME code path as the single-shard
engine — with an ExchangeBackend (`repro.core.exchange`, where each is
described) supplying the communication: "agent" (the paper's Agent-Graph,
§5; `overlap=True` issues the remote flush first), "dense" (the
hash-partition baseline), "pipelined" (the Agent-Graph over static
remote/local edge tiles, the flush overlapping the local combine, §6.2),
"async" (bounded staleness, monotone programs only) and "null" (a 1-device
mesh, to A/B the shard_map plumbing).

Every backend runs through the SAME driver loop (`plan.execute_plan`), and
the state lifecycle — seeding, the serving admit step, the warm start and
tuned-plan adoption — is the single-shard engine's applied per shard
(`engine.EngineLifecycle`).  This module owns only what exists across
chips: backend selection, the host→device topology layout, and the map
between original vertex ids and (shard, slot).
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core.agent_graph import AgentGraph, split_edge_tiles
from repro.core.engine import (DevicePartition, EngineLifecycle,
                               EngineState, GREEngine, seed_operands)
from repro.core.exchange import (AgentExchange, AsyncAgentExchange,
                                 DenseExchange, NullExchange,
                                 PipelinedAgentExchange, PipelineTiles,
                                 ShardTopology, flush_combiners,
                                 refresh_scatter_agents)
from repro.core.plan import execute_plan, execute_superstep
from repro.core.vertex_program import VertexProgram
from repro.dist.sharding import shard_map
from repro.kernels.segment_combine import build_block_table
from repro.spans import span


def _squeeze0(tree):
    """Drop the leading stacked axis of a device-local shard_map operand."""
    return jax.tree.map(
        lambda a: a[0] if hasattr(a, "ndim") and a.ndim > 0 else a, tree)


def _unsqueeze0(tree):
    return jax.tree.map(lambda a: a[None] if hasattr(a, "ndim") else a, tree)


def _block_tables(dst: np.ndarray, num_segments: int) -> np.ndarray:
    """Per-shard Pallas block schedules of stacked dst-sorted columns
    `[k, E]` -> `[k, 2, G]` (equal E gives equal G, so they stack)."""
    return np.stack([build_block_table(row, num_segments) for row in dst])

__all__ = ["DistGREEngine", "PipelineTiles", "PipelinedAgentExchange",
           "ShardTopology", "flush_combiners", "refresh_scatter_agents",
           "split_edge_tiles"]


def _check_async_eligible(program: VertexProgram) -> None:
    """Bounded staleness is sound only when delayed delivery cannot change
    the fixed point (`VertexProgram.monotone`): min/max messages are bounds
    that re-tighten on late arrival, but a sum-monoid message folded
    against a stale accumulator is double-counted."""
    if not program.monotone:
        raise ValueError(
            f"exchange='async' requires a monotone program (halting with "
            f"an idempotent min/max monoid); {program.name!r} uses "
            f"monoid={program.monoid.name!r}, halts={program.halts} — "
            f"bounded-staleness delivery would corrupt its fixed point. "
            f"Use exchange='agent' or 'pipelined' instead.")


class DistGREEngine(EngineLifecycle):
    """Runs a VertexProgram over an AgentGraph on a device mesh."""

    EXCHANGES = ("agent", "dense", "null", "pipelined", "async")

    def __init__(self, program: VertexProgram, mesh: Mesh,
                 axis_names: Tuple[str, ...] = ("graph",),
                 exchange: str = "agent", overlap: bool = False,
                 use_pallas: Optional[bool] = None, frontier: str = "auto",
                 frontier_cap: Optional[int] = None,
                 dynamic_table: bool = True, plan=None, plan_cache=None,
                 staleness: int = 2):
        assert exchange in self.EXCHANGES, exchange
        # NullExchange never communicates: correct only on a 1-device mesh
        # (useful to A/B the shard_map plumbing against GREEngine).
        assert exchange != "null" or mesh.size == 1, \
            "exchange='null' drops all cross-shard traffic; needs a 1-device mesh"
        if exchange == "async":
            _check_async_eligible(program)
            if staleness < 1:
                raise ValueError(
                    f"exchange='async' needs staleness >= 1, got {staleness}")
        self.staleness = staleness
        self.program = program
        self.mesh = mesh
        self.axes = axis_names
        self.exchange = exchange
        self.overlap = overlap
        # frontier/frontier_cap select the per-shard scatter strategy
        # (engine.py); the lax.cond is shard-local and branch bodies have no
        # collectives, so shards may diverge dense-vs-compact per superstep.
        # use_pallas unset: each shard's dense combine resolves its route
        # per call over the shard's own columns (GREEngine.combine_route).
        self.local = GREEngine(program, use_pallas=use_pallas,
                               frontier=frontier, frontier_cap=frontier_cap,
                               dynamic_table=dynamic_table)
        # plan="auto-tuned" consults the cache keyed by (agent-graph
        # fingerprint, program payload, MESH SIZE) the first time an
        # AgentGraph is in hand (device_topology/init_state/make_run)
        self._init_plan(plan, plan_cache)

    @property
    def shard_engine(self) -> GREEngine:
        """The engine whose frontier and kernel knobs every shard runs."""
        return self.local

    def adopt_plan(self, plan) -> None:
        """Take a composed SuperstepPlan mesh-wide: the frontier/kernel
        stages land on the local engine (`GREEngine.adopt_plan`) and the
        phase shape selects the exchange variant — "pipelined" switches
        to the split-tile PipelinedAgentExchange, "async" to the k-deep
        AsyncAgentExchange (monotone programs only — refuses otherwise,
        so a tuned-cache plan can never smuggle staleness under a sum
        monoid), "sync" demotes either back to the sync AgentExchange
        (dense/null baselines are left alone: the plan tunes the
        Agent-Graph protocol, not the baseline)."""
        self.local.adopt_plan(plan)
        if plan.phases == "pipelined":
            self.exchange = "pipelined"
        elif plan.phases == "async":
            _check_async_eligible(self.program)
            self.exchange = "async"
            self.staleness = plan.staleness
        elif self.exchange in ("pipelined", "async"):
            self.exchange = "agent"

    def _cache_key(self, ag: AgentGraph) -> str:
        """The tuned-plan cache key folds in the mesh size, the agent
        graph's remote-destination edge fraction, and the partitioner that
        produced the placement (`AgentGraph.partitioner`) — the facets a
        single-shard tuning run can't see, and the one that keeps a plan
        tuned on a greedy placement from answering for an HDRF one."""
        from repro.tuning import plan_cache_key
        return plan_cache_key(agent_graph=ag, program=self.program,
                              mesh_size=self.mesh.size)

    def _apply_edge_delta(self, ag: AgentGraph, delta):
        from repro.core.agent_graph import apply_edge_delta
        return apply_edge_delta(ag, delta)

    def _original_layout(self, ag: AgentGraph):
        """Master row `old2new[v]` of the stacked `[k, cap]` rows holds
        vertex v; `slot_to_original` names every edge end."""
        from repro.core.agent_graph import slot_to_original
        s2o = slot_to_original(ag)
        m = ag.edge_mask
        prop = self.program.needs_edge_prop
        edges = (np.take_along_axis(s2o, ag.src, axis=1)[m],
                 np.take_along_axis(s2o, ag.dst, axis=1)[m],
                 np.asarray(ag.edge_props[prop])[m] if prop else None)
        aux = {"out_degree": jnp.asarray(np.asarray(ag.out_degree).reshape(
                   ag.k * ag.cap)[ag.old2new]),
               "global_id": jnp.arange(ag.num_vertices, dtype=jnp.float32)}
        return ag.old2new, edges, aux

    @staticmethod
    def _aux(ag: AgentGraph) -> dict:
        """The apply-phase aux of every shard, host-stacked `[k, cap]`."""
        return {"out_degree": ag.out_degree,
                "global_id": ag.new2old.reshape(ag.k, ag.cap).astype(
                    np.float32)}

    @property
    def plan(self):
        """The ONE mesh-uniform plan this engine executes (introspection:
        shard_map traces a single program, so frontier/kernel stages —
        like every static tile shape — are identical on every shard, and
        `phases` records the shape the selected backend's phase protocol
        will drive).  Rebuilt from the local engine on access so a
        `calibrate_frontier_cap` run between construction and `make_run`
        is honored (matching `GREEngine.make_plan`)."""
        if self.exchange == "async":
            return self.local.make_plan(phases="async",
                                        staleness=self.staleness)
        return self.local.make_plan(
            phases="pipelined" if self.exchange == "pipelined" else "sync")

    # ------------------------------------------------------ backend selection
    def make_exchange(self, topo: ShardTopology):
        """Instantiate the configured ExchangeBackend for one device's
        topology (called inside shard_map; `my_row` is the mesh position)."""
        if self.exchange == "null":
            return NullExchange()
        if self.exchange == "dense":
            return DenseExchange(topo, self.axes, self.program.monoid,
                                 my_row=jax.lax.axis_index(self.axes),
                                 dense_frontier=self.local.dense_frontier)
        if self.exchange == "pipelined":
            return PipelinedAgentExchange(topo, self.axes,
                                          self.program.monoid,
                                          dense_frontier=self.local.dense_frontier)
        if self.exchange == "async":
            return AsyncAgentExchange(topo, self.axes, self.program.monoid,
                                      dense_frontier=self.local.dense_frontier,
                                      staleness=self.staleness)
        return AgentExchange(topo, self.axes, self.program.monoid,
                             dense_frontier=self.local.dense_frontier,
                             overlap=self.overlap)

    # ----------------------------------------------------------- host → device
    @span("gre.ingress.topology")
    def device_topology(self, ag: AgentGraph):
        """The partitions laid shard after shard: each host-stacked
        `[k, n, ...]` array becomes `[k * n, ...]`, and shard_map hands
        block i, partition i's own `[n, ...]` array, to device i
        (`_put_shards`).

        The host span `gre.ingress.topology` (`repro.spans`) covers the
        host work and the enqueued copies, not their completion: a caller
        that times ingress ends it in `block_until_ready`.

        With `exchange="pipelined"` or `exchange="async"` every edge scan
        runs on the split tiles (`ShardTopology.tiles`); the canonical part
        then carries NO edge columns at all (`DevicePartition` edge columns
        are optional) — only the slot statics + aux that apply needs.
        Shipping the full columns twice would double per-device edge
        memory for arrays the split-tile paths never read.
        """
        if self._auto_plan_pending:
            self._consult_plan(ag)
        put = self._put_shards
        aux = {name: put(a) for name, a in self._aux(ag).items()}
        if self.exchange in ("pipelined", "async"):
            part = DevicePartition(
                num_masters=ag.cap, num_slots=ag.num_slots,
                edges_sorted_by_dst=True, aux=aux,
            )
            tiles = self._pipeline_tiles(ag)
        else:
            part = DevicePartition(
                src=put(ag.src), dst=put(ag.dst),
                edge_mask=put(ag.edge_mask),
                num_masters=ag.cap, num_slots=ag.num_slots,
                edges_sorted_by_dst=True,
                edge_props={n: put(v) for n, v in ag.edge_props.items()},
                aux=aux,
                csr_indptr=put(ag.csr_indptr),
                csr_eidx=put(ag.csr_eidx),
                csr_max_deg=ag.csr_max_deg,
                bucket_id=put(ag.bucket_id),
                bucket_sizes=ag.bucket_sizes,
                bucket_max_deg=ag.bucket_max_deg,
                combine_table=put(_block_tables(ag.dst, ag.num_slots)),
            )
            tiles = None
        return ShardTopology(
            part=part,
            comb_send_slot=put(ag.comb_send_slot),
            comb_recv_master=put(ag.comb_recv_master),
            scat_send_master=put(ag.scat_send_master),
            scat_recv_slot=put(ag.scat_recv_slot),
            tiles=tiles,
        )

    @property
    def _row_sharding(self) -> NamedSharding:
        """Row i of a stacked `[k, ...]` array lives on mesh device i."""
        return NamedSharding(self.mesh, P(self.axes if len(self.axes) > 1
                                          else self.axes[0]))

    def _put_shards(self, stacked):
        """Place a host-stacked `[k, n, ...]` array as `[k * n, ...]`, block
        i on mesh device i.  Inside shard_map a device's block is then the
        shard's array itself: a stacked `[1, E]` edge column would need a
        relayout to the `[E]` the gather and the Pallas combine read."""
        a = np.asarray(stacked)
        return jax.device_put(a.reshape((-1,) + a.shape[2:]),
                              self._row_sharding)

    def _pipeline_tiles(self, ag: AgentGraph) -> PipelineTiles:
        """Remote/local edge tiles + compact-space exchange indices, laid
        shard after shard as `device_topology`'s arrays are.

        Exchange-index remapping rides the slot layout: combiner slots start
        at `cap + s_pad` and the padding fill is the sink
        (`cap + s_pad + c_pad`), so a uniform subtraction sends real slots
        to `[0, c_pad)` and fills to exactly `c_pad` — the remote tile's
        identity slot.  Receive-side master slots keep their index; sink
        fills clamp to `cap`, the local identity slot.
        """
        split = split_edge_tiles(ag)
        comb_base = ag.cap + ag.s_pad
        put = self._put_shards

        def tile_part(t, num_segments):
            # the tile's ⊕ runs over its compact segment space (see
            # PipelinedAgentExchange.local_phase), so its Pallas block
            # schedule is built over that space too
            return DevicePartition(
                src=put(t.src), dst=put(t.dst),
                edge_mask=put(t.mask),
                num_masters=ag.cap, num_slots=ag.num_slots,
                edges_sorted_by_dst=True,
                edge_props={n: put(v) for n, v in t.props.items()},
                csr_indptr=put(t.csr_indptr),
                csr_eidx=put(t.csr_eidx),
                csr_max_deg=t.csr_max_deg,
                bucket_id=put(t.bucket_id),
                bucket_sizes=t.bucket_sizes,
                bucket_max_deg=t.bucket_max_deg,
                combine_table=put(_block_tables(t.dst, num_segments)),
            )

        return PipelineTiles(
            part_remote=tile_part(split.remote, ag.c_pad + 1),
            part_local=tile_part(split.local, ag.cap + 1),
            comb_send_compact=put(ag.comb_send_slot - comb_base),
            comb_recv_master=put(np.minimum(ag.comb_recv_master, ag.cap)),
            num_combiners=ag.c_pad,
        )

    def init_state(self, ag: AgentGraph, source=None,
                   lane_tracking: bool = False):
        """Stacked initial state [k, ...]; `source` is an ORIGINAL vertex id,
        or — for `payload_shape=(D,)` multi-source programs — a length-D
        sequence of original ids (see `GREEngine.init_state`).

        Each shard's state is `GREEngine.shard_state` over its own aux and
        seed slots (`_seed_slots`: the owner's local slot, the sentinel on
        every other shard), run for all shards under one `jax.vmap` and
        placed row-per-device in one `device_put`.  `lane_tracking=True`
        attaches the per-lane halt vector (replicated `[k, D]` bool, kept
        mesh-global by the serving tick's pmax)."""
        if self._auto_plan_pending:
            self._consult_plan(ag)
        ids, lanes, row = seed_operands(self.program, source, lane_tracking)
        aux = {name: jnp.asarray(a) for name, a in self._aux(ag).items()}
        slots = (None if ids is None
                 else jnp.asarray(self._seed_slots(ag, ids)))
        state = jax.vmap(lambda a, s: self.local.shard_state(
            ag.cap, ag.num_slots, a, s, lanes, row))(aux, slots)
        # mask padding masters (no original vertex)
        real = jnp.asarray(ag.new2old.reshape(ag.k, ag.cap) >= 0)
        act = state.active_scatter.at[:, :ag.cap].set(
            state.active_scatter[:, :ag.cap] & real)
        return jax.device_put(
            dataclasses.replace(state, active_scatter=act),
            self._row_sharding)

    @staticmethod
    def _seed_slots(ag: AgentGraph, ids) -> np.ndarray:
        """Original ids -> per-shard seed slots `[k, len(ids)]`: vertex v's
        local master slot on the shard that owns it, the sentinel
        `num_slots` on every other shard and for -1."""
        out = np.full((ag.k, ids.size), ag.num_slots, np.int32)
        seeded = np.flatnonzero(ids >= 0)
        g = ag.old2new[ids[seeded]]
        out[g // ag.cap, seeded] = g % ag.cap
        return out

    def to_original(self, ag: AgentGraph, vertex_data) -> np.ndarray:
        """Stacked `[k, cap, ...]` master rows -> `[V, ...]` on the host in
        ORIGINAL vertex order."""
        vd = np.asarray(jax.device_get(vertex_data))
        return vd.reshape((ag.k * ag.cap,) + vd.shape[2:])[ag.old2new]

    def rerun_incremental(self, ag: AgentGraph, prev_state: EngineState,
                          delta, *, source=None, max_steps: int = 100):
        """Apply an EdgeDelta to the agent graph and re-converge the mesh
        run from `prev_state`'s fixed point.  Returns
        ``(new_ag, result_in_original_order, final_state, report)`` —
        bitwise-equal to a cold `run` on the mutated graph for halting
        min-monoid programs (tests/test_conformance.py)."""
        new_ag, report = self.apply_delta(ag, delta)
        state = self.warm_start_state(new_ag, prev_state, report,
                                      source=source)
        topo = self.device_topology(new_ag)
        out = jax.device_get(self.make_run(new_ag, max_steps)(topo, state))
        return new_ag, self.to_original(new_ag, out.vertex_data), out, report

    def _admit_step(self, ag: AgentGraph):
        """`GREEngine.admit` per shard under `jax.vmap`, inside the
        engine's shard_map so every stacked operand keeps the state's
        row-per-device placement.  `lane_active` stays replicated: every
        shard applies the same lane update."""
        rows = self._row_sharding.spec
        step = jax.vmap(self.local.admit, in_axes=(0, 0, 0, None, None))
        fn = jax.jit(shard_map(step, mesh=self.mesh,
                               in_specs=(rows, rows, rows, P(), P()),
                               out_specs=rows))
        # placed row-per-device, so no shard is staged on the first device
        return fn, jax.device_put(self._aux(ag), self._row_sharding)

    # ------------------------------------------------------------------ tick
    def make_superstep(self, ag: AgentGraph, steps_per_tick: int = 1):
        """Build the jitted SERVING TICK: `steps_per_tick` supersteps over
        the mesh with NO convergence loop around them — the serving layer
        (repro.serving.graph_scheduler) owns the loop so it can retire and
        admit payload lanes between ticks at static shape.

        Each tick runs `plan.execute_superstep` per shard (per-tick merge:
        a Mailbox carried across ticks would hold partial combines of a
        retired query, so the pipelined backend still overlaps its flush
        with the local-tile combine INSIDE the tick but never defers the
        merge past it) and globalizes the per-lane halt vector with a
        pmax, keeping `lane_active` replicated and host-readable.

        `exchange="async"` cannot serve ticks: its ring holds remote
        partials for up to `staleness` supersteps, and dropping them at a
        tick boundary would lose messages outright (not merely defer
        them)."""
        if self.exchange == "async":
            raise ValueError(
                "exchange='async' cannot drive the serving tick: the "
                "staleness ring carries un-flushed remote partials across "
                "supersteps, and a per-tick merge would drop them. Use "
                "exchange='agent' or 'pipelined' for serving.")
        if self._auto_plan_pending:
            self._consult_plan(ag)
        spec_leading = self._row_sharding.spec

        def tick_shard(topo_l, state_stack):
            s = _squeeze0(state_stack)
            backend = self.make_exchange(topo_l)
            for _ in range(steps_per_tick):
                s = execute_superstep(self.local, topo_l.part, s, backend)
            if s.lane_active is not None:
                la = jax.lax.pmax(s.lane_active.astype(jnp.int32),
                                  self.axes) > 0
                s = dataclasses.replace(s, lane_active=la)
            return _unsqueeze0(s)

        sharded = shard_map(tick_shard, mesh=self.mesh,
                            in_specs=(spec_leading, spec_leading),
                            out_specs=spec_leading)
        return jax.jit(sharded)

    # ------------------------------------------------------------------- run
    def make_run(self, ag: AgentGraph, max_steps: int = 100):
        """Build the jitted distributed run function over the mesh."""
        if self._auto_plan_pending:
            self._consult_plan(ag)
        spec_leading = self._row_sharding.spec
        squeeze0, unsqueeze0 = _squeeze0, _unsqueeze0

        def glob_any(local):
            # Globalizer over the shard-local liveness bool (frontier OR
            # in-flight exchange carry — see plan.execute_plan): the pmax
            # keeps the loop predicate mesh-uniform so collectives inside
            # the phase stay matched across shards.
            return jax.lax.pmax(local.astype(jnp.int32), self.axes) > 0

        def run_shard(topo_l, state_stack):
            # the topology arrives laid shard after shard (device_topology):
            # only the stacked state drops its leading axis
            state_l = squeeze0(state_stack)
            backend = self.make_exchange(topo_l)
            # the ONE driver loop (plan.execute_plan): the phase shape
            # rides the backend, the termination predicate is the
            # mesh-global pmax so collectives stay matched across shards
            out = execute_plan(self.local, topo_l.part, state_l, backend,
                               max_steps=max_steps, any_active=glob_any)
            return unsqueeze0(out)

        sharded = shard_map(run_shard, mesh=self.mesh,
                            in_specs=(spec_leading, spec_leading),
                            out_specs=spec_leading)
        return jax.jit(sharded)

    def run(self, ag: AgentGraph, source=None,
            max_steps: int = 100) -> Tuple[np.ndarray, EngineState]:
        """Execute; returns (vertex_data in ORIGINAL vertex order, state)."""
        topo = self.device_topology(ag)
        state = self.init_state(ag, source=source)
        fn = self.make_run(ag, max_steps=max_steps)
        out = jax.device_get(fn(topo, state))
        return self.to_original(ag, out.vertex_data), out
