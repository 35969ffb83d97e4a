"""Superstep execution plans: ONE driver loop for every engine mode.

Before this layer the per-superstep decision logic was smeared across the
stack: `GREEngine._frontier_plan` picked the frontier strategy, `run` vs
`run_pipelined` were two hand-maintained loops for the two exchange phase
shapes, `_tile_combine` hard-coded the kernel route, and `DistGREEngine`
re-derived all three when selecting a backend.  A `SuperstepPlan` composes
the three orthogonal decisions into one static object, resolved once per
(engine, partition):

  frontier stage — `dense` every-edge scan, `flat` single-tile compaction,
      or degree-`bucketed` tiles, with the static capacity split
      (`resolve_frontier`, previously `GREEngine._frontier_plan`);
  phase shape    — `sync` (the whole reduce is one phase) or `pipelined`
      (local-phase / deferred merge, the double-buffered exchange); every
      ExchangeBackend speaks the same `local_phase`/`merge`/`carry_init`
      protocol, so ONE loop (`execute_plan`) drives both shapes;
  kernel stage   — XLA segment ops or the Pallas tile combine, and for
      Pallas whether the on-device `dynamic_block_table` pruning pass runs
      or the degenerate `full_block_table` fallback (`KernelPlan`).

`execute_plan` is the single BSP loop: the superstep is cut into
phase / merge+apply stages with the phase carry threaded across iterations,
so a pipelined backend's flush collective issued in superstep i overlaps
the local-tile combine and merges at the top of i+1 (paper §6.2), while a
sync backend's carry is simply its fully ⊕-reduced array and the same loop
degenerates to refresh → reduce → apply.  The apply count and final state
match the classic synchronous loop exactly (the same ⊕ folds happen, some
deferred one iteration), and the phase runs under a `lax.cond` on the
continuation predicate — computed ONCE post-apply, mesh-uniform when the
caller supplies the global `any_active` — so no trailing edge scan or
flush collective whose result would be discarded ever executes and the
collectives inside the phase stay matched across shards.
"""
from __future__ import annotations

import dataclasses
from typing import TYPE_CHECKING, NamedTuple, Optional

import jax
import jax.numpy as jnp

if TYPE_CHECKING:  # pragma: no cover - typing only, avoids a cycle
    from repro.core.engine import DevicePartition, EngineState, GREEngine

PHASES = ("sync", "pipelined", "async")


class FrontierPlan(NamedTuple):
    """Static per-partition frontier resolution.

    `kind` is "dense" (caps None), "flat" (caps = the single tile capacity)
    or "bucketed" (caps = one capacity per degree bucket).  A NamedTuple so
    legacy call sites comparing against ``("flat", cap)`` tuples keep
    working.
    """

    kind: str
    caps: object = None


@dataclasses.dataclass(frozen=True)
class KernelPlan:
    """The combine-kernel stage of a plan.

    `use_pallas` is the engine's kernel route (`GREEngine.use_pallas`):
    False forces the XLA scatter-reduce (`segment_combine`), True the
    Pallas kernels, and None leaves it unset — the dense scan then resolves
    its route per call (`resolve_combine_route`) and gathered frontier
    tiles keep XLA.  With `use_pallas=True` the tiles route through the
    Pallas tile combine; `dynamic_table` selects the on-device
    per-superstep `dynamic_block_table` pruning pass (default) vs the
    degenerate `full_block_table` fallback (every dst block visits every
    edge block — kept only as the documented escape hatch, see
    docs/kernels.md).
    """

    use_pallas: Optional[bool] = False
    dynamic_table: bool = True


XLA_KERNEL = KernelPlan(use_pallas=False)


def resolve_frontier(strategy: str, frontier_cap: Optional[int],
                     dense_frontier: bool,
                     part: "DevicePartition") -> FrontierPlan:
    """Static (trace-time) frontier-strategy resolution for one partition.

    Returns kind "dense" (compile the dense path only), "flat" for the
    legacy single-tile compaction, or "bucketed" with one capacity per
    degree bucket.  Buckets kill the old `cap * max_deg >= E` hub gate:
    the bound compared against the dense scan is `sum_b cap_b * max_deg_b`,
    which stays small on power-law graphs because the hub bucket holds few
    members.
    """
    if strategy == "dense" or dense_frontier:
        return FrontierPlan("dense")  # iterative: frontier is everything
    if part.csr_indptr is None or part.csr_max_deg <= 0:
        return FrontierPlan("dense")
    from repro.core.frontier import bucket_caps, default_cap
    cap = min(frontier_cap or default_cap(part.num_slots), part.num_slots)
    bucketed = (strategy != "flat" and part.bucket_id is not None
                and len(part.bucket_max_deg) > 0
                and any(part.bucket_sizes))
    if not bucketed:
        if (strategy == "auto"
                and cap * part.csr_max_deg >= part.src.shape[0]):
            return FrontierPlan("dense")  # padded tile ≥ dense scan
        return FrontierPlan("flat", cap)
    caps = bucket_caps(part.bucket_sizes, cap)
    worst = sum(c * d for c, d in zip(caps, part.bucket_max_deg))
    if strategy == "auto" and worst >= part.src.shape[0]:
        return FrontierPlan("dense")  # full bucket tiles out-scan dense
    return FrontierPlan("bucketed", caps)


@dataclasses.dataclass(frozen=True)
class SuperstepPlan:
    """One engine mode, fully resolved: frontier strategy request, phase
    shape, and kernel stage.  Static/hashable so it can parameterize jitted
    drivers; the per-partition frontier resolution happens at trace time
    via `frontier(part)` (pipelined backends carry TWO edge-tile
    partitions, each resolving its own tile shapes).

    `bucket_bounds` is INGRESS metadata, not a runtime knob: the degree
    binning is baked into a partition when it is built
    (`graph.structures.degree_buckets`), so a plan carrying non-None
    bounds says "this plan was tuned against a partition binned with
    these bounds" — the autotuner's evaluator (repro.tuning) rebuilds
    partitions per candidate bounds, and engines adopting a tuned plan
    record the bounds so callers can rebuild matching partitions
    (`DevicePartition.from_graph(..., bucket_bounds=...)`).  None means
    "whatever the partition was built with" (the default bounds).
    """

    strategy: str = "auto"
    frontier_cap: Optional[int] = None
    dense_frontier: bool = False
    phases: str = "sync"
    kernel: KernelPlan = XLA_KERNEL
    bucket_bounds: Optional[tuple] = None
    # Bounded-staleness window k for phases="async" (the AsyncAgentExchange
    # ring depth; exchange collectives run once per k supersteps).  0 for
    # the synchronous shapes — a non-zero staleness on a sync/pipelined
    # plan would silently record a knob nothing executes.
    staleness: int = 0

    def __post_init__(self):
        assert self.phases in PHASES, self.phases
        if self.phases == "async" and self.staleness < 1:
            raise ValueError("phases='async' needs staleness >= 1 "
                             f"(got {self.staleness})")
        if self.phases != "async" and self.staleness != 0:
            raise ValueError(f"staleness={self.staleness} is only "
                             "meaningful with phases='async'")
        if self.bucket_bounds is not None:
            # normalize to a hashable int tuple (JSON round-trips lists)
            object.__setattr__(self, "bucket_bounds",
                               tuple(int(b) for b in self.bucket_bounds))

    # ---------------------------------------------------------- serialization
    def to_json(self) -> dict:
        """Plain-JSON form for the persistent plan cache
        (repro.tuning.cache).  Nested `kernel` keeps the kernel stage's
        fields grouped; `bucket_bounds` serializes as a list/None."""
        return {
            "strategy": self.strategy,
            "frontier_cap": self.frontier_cap,
            "dense_frontier": self.dense_frontier,
            "phases": self.phases,
            "kernel": {"use_pallas": self.kernel.use_pallas,
                       "dynamic_table": self.kernel.dynamic_table},
            "bucket_bounds": (None if self.bucket_bounds is None
                              else list(self.bucket_bounds)),
            "staleness": self.staleness,
        }

    @classmethod
    def from_json(cls, data: dict) -> "SuperstepPlan":
        """Inverse of `to_json`.  UNKNOWN fields are rejected, not
        ignored: a cache entry written by a future plan schema must fail
        loudly rather than silently execute with half its knobs dropped
        (the cache stores a schema version too, but field-level rejection
        catches hand-edited files)."""
        known = {"strategy", "frontier_cap", "dense_frontier", "phases",
                 "kernel", "bucket_bounds", "staleness"}
        unknown = set(data) - known
        if unknown:
            raise ValueError(f"SuperstepPlan.from_json: unknown field(s) "
                             f"{sorted(unknown)}")
        kdata = dict(data.get("kernel") or {})
        kunknown = set(kdata) - {"use_pallas", "dynamic_table"}
        if kunknown:
            raise ValueError(f"SuperstepPlan.from_json: unknown kernel "
                             f"field(s) {sorted(kunknown)}")
        route = kdata.get("use_pallas", False)
        kernel = KernelPlan(use_pallas=None if route is None else bool(route),
                            dynamic_table=bool(kdata.get("dynamic_table",
                                                         True)))
        cap = data.get("frontier_cap")
        bounds = data.get("bucket_bounds")
        return cls(strategy=data.get("strategy", "auto"),
                   frontier_cap=None if cap is None else int(cap),
                   dense_frontier=bool(data.get("dense_frontier", False)),
                   phases=data.get("phases", "sync"),
                   kernel=kernel,
                   bucket_bounds=None if bounds is None else tuple(bounds),
                   staleness=int(data.get("staleness", 0)))

    def frontier(self, part: "DevicePartition") -> FrontierPlan:
        return resolve_frontier(self.strategy, self.frontier_cap,
                                self.dense_frontier, part)

    # ------------------------------------------------- scatter-combine stage
    def scatter_combine(self, engine: "GREEngine", part: "DevicePartition",
                        state: "EngineState",
                        num_segments: Optional[int] = None) -> jnp.ndarray:
        """The plan's scatter-combine stage: resolve the partition's
        frontier plan and dispatch dense scan vs compacted gather, with the
        kernel stage threaded through to the tile combine."""
        nseg = num_segments or part.num_slots
        fp = self.frontier(part)
        if fp.kind == "dense":
            return engine.dense_scatter_combine(part, state, nseg)
        from repro.core.frontier import frontier_scatter_combine
        return frontier_scatter_combine(
            engine.program, part, state, nseg, fp,
            dense_fn=lambda: engine.dense_scatter_combine(part, state, nseg),
            kernel=self.kernel)


def execute_superstep(engine: "GREEngine", part: "DevicePartition",
                      state: "EngineState", exchange) -> "EngineState":
    """ONE superstep through the phase protocol — the SERVING TICK.

    The continuous-batching scheduler (repro.serving.graph_scheduler)
    needs to stop BETWEEN supersteps, at static shape, to retire
    converged payload lanes and admit queued queries into the freed
    slots; `execute_plan`'s while-loop only stops at quiescence.  This is
    the single-superstep cut of the same stage decomposition:
    refresh → local_phase → merge → apply, for every backend.

    Sync backends are op-for-op `refresh → reduce → apply`.  For the
    pipelined backend the flush collective still overlaps the local-tile
    combine INSIDE the tick (that is the overlap window), but the merge
    is not deferred across ticks: a carried Mailbox would hold partial
    combines of a lane's RETIRED query at the moment the scheduler
    reseeds it, corrupting the admitted query — per-tick merge keeps the
    lane-recycling invariant (every ⊕ fold visible to a lane happened
    before the lane was reseeded) at the cost of the one-superstep
    deferral, and stays bitwise ⊕-equivalent to the deferred loop.

    Per-lane halt rides the state: when `EngineState.lane_active` is
    attached, `apply` refreshes it from the program's `lane_activates`,
    so after each tick the scheduler reads exactly which lanes still
    improve (False = that lane's query converged).
    """
    state = exchange.refresh(state)
    carry = exchange.local_phase(engine, part, state)
    return engine.apply(part, state, exchange.merge(carry))


def execute_plan(engine: "GREEngine", part: "DevicePartition",
                 state: "EngineState", exchange,
                 max_steps: int = 100, any_active=None) -> "EngineState":
    """THE driver loop: run `engine.program` to quiescence under the
    engine's SuperstepPlan.

    The plan is fully determined by its two inputs — the engine owns the
    frontier/kernel stages (`engine.make_plan`, reached through
    `engine.scatter_combine` inside every backend's phase) and the
    backend's `phases` attribute names the phase shape — so the executor
    takes no separate plan argument there could be a stale copy of.

    The classic synchronous loop is refresh → reduce → apply with the
    exchange's collective a barrier inside every superstep.  Here the
    superstep is cut into stages and re-seamed across iterations:

      carry_i = (state_i refreshed, phase carry of superstep i)
      body:    merge carry → apply_i → refresh_{i+1}
               → phase_{i+1} (under the continuation cond)

    For a sync backend the phase carry IS the fully ⊕-reduced combine
    array and `merge` is the identity — the loop is op-for-op the old
    `GREEngine.run`.  For a pipelined backend the carry is the two-slot
    `Mailbox` and the flush collective issued inside `local_phase` has the
    whole local-tile combine between it and its consumer (the merge at the
    top of the next iteration) — the largest legal overlap window, since
    `refresh_{i+1}` transitively depends on the flushed values through
    `apply_i`.  ⊕-equivalence is exact either way: the same partial
    combines are folded, only later.

    `any_active` GLOBALIZES the termination predicate: it receives the
    shard-local "still work here" bool (frontier non-empty OR the
    backend's carry still holds in-flight contributions,
    `exchange.carry_pending`) and returns the mesh-global verdict — the
    distributed engine passes a pmax so all shards exit together and the
    collectives inside the phase stay matched; None is the single-shard
    identity.  The predicate is computed once per iteration (post-apply,
    carried into the loop cond) and is mesh-uniform, so every shard takes
    the same branch.  Evaluating it on the pre-refresh state is sound:
    apply zeroes agent-slot activity, so the global any over masters is
    what refresh would mirror.  Counting the carry matters only for the
    async shape: its ring holds remote partials flushed once per k
    supersteps, and its `dirty` bit holds improvements the next refresh
    has yet to push — an empty frontier with either set is not
    quiescence.  (The landed/local slots never need counting: merge
    consumes them before the predicate runs.)

    A state carrying `counters` (`GREEngine.init_state(counters=rows)`)
    gets row `step` written at the top of each phase that runs, from the
    refreshed frontier and the plan's frontier resolution
    (`frontier.superstep_counts`); a state without them compiles no
    counting at all.
    """
    if state.counters is not None and state.counters.shape[0] < max_steps:
        raise ValueError(f"counters hold {state.counters.shape[0]} rows; a "
                         f"run of up to {max_steps} supersteps needs that "
                         f"many")
    globalize = any_active or (lambda local: local)
    pending = getattr(exchange, "carry_pending",
                      lambda carry: jnp.zeros((), dtype=bool))

    def keep_going(s, carry):
        local = jnp.any(s.active_scatter) | pending(carry)
        return (s.step < max_steps) & globalize(local)

    def phase(s, carry):
        s = exchange.refresh(s)
        if s.counters is not None:
            from repro.core.frontier import superstep_counts
            with jax.named_scope("gre.scatter"):
                row = superstep_counts(engine.make_plan().frontier(part),
                                       part, s.active_scatter)
                s = dataclasses.replace(
                    s, counters=s.counters.at[s.step].set(row))
        return s, exchange.local_phase(engine, part, s, carry)

    def phase_if(go, s, carry):
        return jax.lax.cond(go, phase, lambda ss, cc: (ss, cc), s, carry)

    def body(c):
        s, carry, _ = c
        s = engine.apply(part, s, exchange.merge(carry))
        go = keep_going(s, carry)
        return phase_if(go, s, carry) + (go,)

    with jax.named_scope("gre.combine"):   # the ⊕ identity accumulator
        carry_init = exchange.carry_init(engine, part)
    go0 = keep_going(state, carry_init)
    carry0 = phase_if(go0, state, carry_init) + (go0,)
    final, _, _ = jax.lax.while_loop(lambda c: c[2], body, carry0)
    return final
