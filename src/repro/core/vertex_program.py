"""Scatter-Combine abstraction (paper §4, Alg. 1).

A `VertexProgram` instantiates the four primitives:

  scatter(u, v, e)   — generates an active message `msg = s(u.scatter_data,
                       e.state)` (here `scatter_msg`);
  combine(msg)       — folds the message into the destination's combine_data
                       with a commutative+associative generalized sum ⊕
                       (here a `Monoid`), optionally activating apply;
  apply(v)           — recomputes vertex_data from the accumulated sum and
                       optionally re-activates scatter;
  assert_to_halt(v)  — deactivates scatter (traversal algorithms) or keeps
                       the vertex active (iterative algorithms).

On TPU the data race the paper handles with vLock does not exist: the whole
scatter-combine phase is one fused `gather → message → segment-reduce`
dataflow op, race-free and deterministic by construction.

A worked example — in-degree counting as a one-superstep program.  Every
vertex starts active and scatters the constant 1 along its out-edges; ⊕ is
sum, so each vertex's accumulator ends up holding its in-degree; apply
stores it and deactivates (`halts=True` + all-False activation ends the
run after one superstep):

    >>> import numpy as np
    >>> import jax.numpy as jnp
    >>> from repro.core.vertex_program import MONOIDS, VertexProgram
    >>> indegree = VertexProgram(
    ...     name="indegree", monoid=MONOIDS["sum"],
    ...     scatter_msg=lambda src_scatter, eprop: jnp.ones_like(src_scatter),
    ...     apply_fn=lambda vd, combined, aux: (
    ...         combined, combined, jnp.zeros_like(combined, dtype=bool)),
    ...     init_vertex_data=lambda n, aux: jnp.zeros(n, jnp.float32),
    ...     init_scatter_data=lambda n, aux: jnp.zeros(n, jnp.float32),
    ...     init_active=lambda n, aux: jnp.ones(n, dtype=bool))
    >>> from repro.core.engine import DevicePartition, GREEngine
    >>> from repro.graph.structures import Graph
    >>> g = Graph(3, np.array([0, 0, 1]), np.array([1, 2, 2]))
    >>> part = DevicePartition.from_graph(g)
    >>> eng = GREEngine(indegree)
    >>> out = eng.run(part, eng.init_state(part), max_steps=5)
    >>> np.asarray(out.vertex_data)          # in-degrees of vertices 0,1,2
    array([0., 1., 2.], dtype=float32)
    >>> int(out.step)                        # halted after one superstep
    1

The same program object runs unchanged on a multi-device mesh through
`DistGREEngine` with any ExchangeBackend (`repro.core.exchange`), and
with any frontier strategy (`repro.core.frontier`).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class Monoid:
    """Commutative+associative generalized sum ⊕ with identity (paper §2.2)."""

    name: str
    identity: float
    op: Callable[[jnp.ndarray, jnp.ndarray], jnp.ndarray]

    def segment_reduce(self, msgs: jnp.ndarray, dst: jnp.ndarray,
                       num_segments: int, indices_are_sorted: bool = False
                       ) -> jnp.ndarray:
        if self.name == "sum":
            return jax.ops.segment_sum(msgs, dst, num_segments,
                                       indices_are_sorted=indices_are_sorted)
        if self.name == "min":
            return jax.ops.segment_min(msgs, dst, num_segments,
                                       indices_are_sorted=indices_are_sorted)
        if self.name == "max":
            return jax.ops.segment_max(msgs, dst, num_segments,
                                       indices_are_sorted=indices_are_sorted)
        raise ValueError(self.name)


MONOIDS: Dict[str, Monoid] = {
    "sum": Monoid("sum", 0.0, jnp.add),
    "min": Monoid("min", jnp.inf, jnp.minimum),
    "max": Monoid("max", -jnp.inf, jnp.maximum),
}


def segment_combine(msgs: jnp.ndarray, dst: jnp.ndarray, num_segments: int,
                    monoid: Monoid, indices_are_sorted: bool = False,
                    use_pallas: Optional[bool] = False,
                    table=None) -> jnp.ndarray:
    """One-sided combine of active messages at their destinations.

    This is the Scatter-Combine hot path.  The XLA path lowers to a fused
    scatter-reduce; the Pallas path (TPU target) tiles dst-sorted edges into
    VMEM blocks and turns the irregular reduction into block-local one-hot
    MXU matmuls (sum) or masked VPU reductions (min/max), visiting the
    blocks that `table` (the ingress-time block schedule of `dst`) lists.
    `use_pallas=None` takes the Pallas path where the program is lowered
    for a TPU and the XLA path on every other platform.
    """
    def xla(m, d, t):
        return monoid.segment_reduce(m, d, num_segments, indices_are_sorted)

    def pallas(m, d, t):
        from repro.kernels import ops as kernel_ops
        return kernel_ops.segment_combine(m, d, num_segments, monoid.name,
                                          table=t)

    if use_pallas is None:
        return jax.lax.platform_dependent(msgs, dst, table, default=xla,
                                          tpu=pallas)
    return (pallas if use_pallas else xla)(msgs, dst, table)


@dataclasses.dataclass(frozen=True)
class VertexProgram:
    """User-defined vertex computation in the Scatter-Combine model.

    State layout follows paper §6.1.3 (flat column arrays indexed by local
    vertex id):

      vertex_data   — result state, owned by masters, updated by `apply`;
      scatter_data  — the datum a vertex scatters, refreshed by `apply`
                      (and, for scatter agents, by the master's message);
      combine_data  — the ⊕ accumulator, reset after each apply.

    Message payloads are first-class `[slots, *payload_shape]` feature
    vectors; the scalar programs of the paper are the `payload_shape = ()`
    special case.  `payload_shape`/`msg_dtype` form the payload spec that
    init, scatter, combine, and apply all consume uniformly: init_scatter
    returns `[n, *payload-or-scatter shape]`, scatter_msg maps gathered
    scatter data `[E, *S]` to messages `[E, *payload_shape]`, the ⊕
    accumulator is `[slots, *payload_shape]`, and apply folds it.

    `scatter_msg(src_scatter_data, edge_prop)` builds message payloads for a
    batch of edges at once (the engine has already gathered source data).
    `apply_fn(vertex_data, combined, aux)` returns
    `(new_vertex_data, new_scatter_data, activate_scatter)`; the engine
    injects the superstep counter into `aux["step"]` so level-synchronous
    programs can schedule themselves.
    Init functions receive `(n, aux)` where aux holds static per-partition
    columns such as `out_degree`.
    """

    name: str
    monoid: Monoid
    scatter_msg: Callable[[jnp.ndarray, Optional[jnp.ndarray]], jnp.ndarray]
    apply_fn: Callable[[jnp.ndarray, jnp.ndarray, Any], tuple]
    init_vertex_data: Callable[[int, Dict[str, jnp.ndarray]], jnp.ndarray]
    init_scatter_data: Callable[[int, Dict[str, jnp.ndarray]], jnp.ndarray]
    init_active: Callable[[int, Dict[str, jnp.ndarray]], jnp.ndarray]
    # `combine_activates(old_vertex_data, combined) -> bool[V]`: whether the
    # accumulated message actually changes the vertex (paper's
    # `activate_apply`).  Vertices without any improving message skip apply.
    combine_activates: Callable[[jnp.ndarray, jnp.ndarray], jnp.ndarray] = (
        lambda old, combined: jnp.ones(old.shape[0], dtype=bool))
    # Iterative programs (PageRank) keep scattering; traversal programs halt.
    halts: bool = True
    needs_edge_prop: Optional[str] = None
    # Payload spec: trailing feature shape of messages/⊕ accumulator.
    # () = scalar (PageRank, SSSP); (D,) = feature vectors (GNN aggregation,
    # Brandes σ, batched multi-source BFS).
    payload_shape: Tuple[int, ...] = ()
    msg_dtype: Any = jnp.float32
    # ------------------------------------------------------------ lane hooks
    # Multi-source programs treat the D payload lanes as independent queries
    # (one root per lane).  The three optional hooks below make lanes
    # individually observable and reseedable — the substrate of the serving
    # layer's lane recycling (repro.serving.graph_scheduler):
    #
    # `lane_activates(old_vertex_data, combined) -> bool[n, D]`: per-LANE
    # analogue of `combine_activates` — which (vertex, lane) pairs improved
    # this superstep.  The engine reduces `any` over vertices into
    # `EngineState.lane_active`; a lane with no improvement anywhere has
    # converged (monotone programs: a quiet lane stays quiet).
    lane_activates: Optional[Callable[[jnp.ndarray, jnp.ndarray],
                                      jnp.ndarray]] = None
    # `seed_sources(vertex_data, scatter_data, src, lanes, aux)` seeds root
    # `src[i]` into payload lane `lanes[i]` and returns the updated
    # `(vertex_data, scatter_data)`.  `src`/`lanes` are int32 arrays with
    # OUT-OF-BOUNDS sentinels marking no-op entries (use
    # `.set(..., mode="drop")`), so admission stays one static-shape jitted
    # call.  None = the traversal default (`value 0.0` at `[src, lane]`).
    seed_sources: Optional[Callable] = None
    # `lane_view(vertex_data, lane) -> [n]`: extract lane `lane`'s per-vertex
    # result (default: column `vertex_data[:, lane]`; PPR stores (p, r)
    # pairs and views the estimate).
    lane_view: Optional[Callable[[jnp.ndarray, int], jnp.ndarray]] = None

    @property
    def monotone(self) -> bool:
        """Whether delayed/re-ordered message delivery cannot change the
        fixed point: every message under an idempotent select monoid
        (⊕ = min/max) is a valid bound that a later delivery only
        re-tightens, so bounded-staleness execution
        (`exchange="async"`, repro.core.exchange.AsyncAgentExchange)
        converges to the same values as the synchronous schedule.  True
        for the halting label-correcting traversals (BFS/SSSP/CC); False
        for sum-monoid programs (PageRank/PPR/GNN aggregation), where a
        message folded against a stale accumulator is double-counted —
        those must refuse async execution loudly."""
        return self.halts and self.monoid.name in ("min", "max")
    # ------------------------------------------------------------ incremental
    # Removal-invalidation policy for warm-started re-convergence after an
    # edge delta (repro.core.incremental):
    #   "path"      — support-based worklist (strictly-increasing messages:
    #                 BFS/SSSP);
    #   "component" — forward-reachability reset (cyclic support: CC);
    #   None        — removals are not incrementally recoverable (warm
    #                 start over a delta with removals raises).
    # Pure adds never need a policy (min re-delivery is idempotent).
    invalidation: Optional[str] = None
