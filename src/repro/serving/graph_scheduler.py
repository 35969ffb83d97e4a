"""Multi-tenant traversal serving: continuous query batching over payload
lanes (docs/serving.md).

The engine's multi-source programs already answer D roots in one pass by
batching them into the `[slots, D]` payload lanes — but a STATIC batch runs
until its slowest query converges, so mixed short/long traffic pays the
worst lane's supersteps for every admission.  `GraphQueryBatcher` turns the
lanes into a continuously-batched serving pool instead:

  admit   — a queued query is seeded into a free lane by ONE jitted
            static-shape call (`[D]`-wide index arrays with out-of-bounds
            sentinels, `mode="drop"`), so admission never recompiles;
  tick    — `steps_per_tick` supersteps advance ALL resident lanes through
            the one canonical superstep (`plan.execute_superstep`, any
            exchange backend, single-shard or mesh);
  retire  — between ticks the host reads `EngineState.lane_active` (per-lane
            halt, reduced by `apply` from `VertexProgram.lane_activates`),
            fetches converged lanes' results, and recycles their lanes for
            the next queued queries.  Budget-exceeded queries are EVICTED:
            the lane is reset without reseeding and the query marked failed.

Recycling is bitwise-safe: a reset lane holds monoid-identity scatter state,
so vertices still active on behalf of OTHER lanes deliver identity values
into it (`min(x, inf) = x`; `x + 0.0 = x`) — a recycled lane's answer is
bit-identical to a fresh single-query batch (tests/test_serving.py proves
this on the null, agent, and pipelined backends).

The jitted tick and admit functions see ONE pytree structure (lane_active
always `[D]` bool, index operands always `[D]` int32), so an arbitrarily
long query stream triggers exactly two compilations, total.
"""
from __future__ import annotations

import dataclasses
import time
from collections import deque
from typing import Dict, List, Optional, Sequence

import jax
import numpy as np

__all__ = ["GraphQueryBatcher", "Query", "ServingFrontend", "poisson_ticks"]


@dataclasses.dataclass
class Query:
    """One traversal request riding a payload lane.

    Lifecycle: queued → running → done | evicted.  Timing fields are wall
    clock (`time.perf_counter`); `supersteps_used` counts supersteps from
    admission — the scheduler-level SLO latency that is independent of
    machine speed.
    """

    uid: int
    source: int
    kind: str = "bfs"
    max_supersteps: Optional[int] = None   # budget; None = run to convergence
    status: str = "queued"
    result: Optional[np.ndarray] = None
    lane: Optional[int] = None
    submitted_at: float = 0.0
    admitted_at: float = 0.0
    finished_at: float = 0.0
    supersteps_used: int = 0

    @property
    def latency_s(self) -> float:
        return self.finished_at - self.submitted_at

    @property
    def wait_s(self) -> float:
        return self.admitted_at - self.submitted_at


def _percentile(sorted_vals: Sequence[float], q: float) -> float:
    """Linear-interpolation percentile over an ALREADY-SORTED sequence
    (numpy's default ``method="linear"``).  The nearest-rank shortcut this
    replaces rounded `q*(n-1)` to an index, which collapses p95 to the max
    for n ≲ 20 samples and misreports it at most other sizes."""
    if not sorted_vals:
        return float("nan")
    pos = q * (len(sorted_vals) - 1)
    lo = int(pos)
    hi = min(lo + 1, len(sorted_vals) - 1)
    frac = pos - lo
    return float(sorted_vals[lo]) * (1.0 - frac) + float(sorted_vals[hi]) * frac


def poisson_ticks(num_queries: int, rate_per_tick: float,
                  rng: np.random.Generator) -> np.ndarray:
    """Arrival tick for each of `num_queries` queries under a Poisson
    process with `rate_per_tick` expected arrivals per serving tick
    (exponential inter-arrival gaps, cumulated and floored)."""
    gaps = rng.exponential(scale=1.0 / rate_per_tick, size=num_queries)
    return np.floor(np.cumsum(gaps)).astype(np.int64)


class GraphQueryBatcher:
    """Continuous batching of traversal queries over one engine's lanes.

    `engine` is a `GREEngine` (with a `DevicePartition` target) or a
    `DistGREEngine` (with an `AgentGraph` target); the program must be a
    multi-source variant exposing `lane_activates` (e.g.
    `bfs_program(D)`, `sssp_program(D)`, `ppr_push_program(D)`).  The
    batcher never branches on which: each engine builds the tick
    (`make_superstep`, run over `device_topology(target)`), the admit step
    (`make_admit`), reads `vertex_data` back in original order
    (`to_original`) and swaps the target after a delta (`apply_delta`).

    Public protocol: `submit()` enqueues; `pump()` retires/evicts/admits
    (host-side, between ticks); `tick()` advances every resident lane by
    `steps_per_tick` supersteps; `run()` loops pump/tick until drained.
    """

    def __init__(self, engine, target, *, steps_per_tick: int = 1,
                 default_budget: Optional[int] = None,
                 clock=time.perf_counter):
        p = engine.program
        if not p.payload_shape or p.lane_activates is None:
            raise ValueError(
                "serving needs a multi-source program with lane_activates "
                f"(got {p.name!r} with payload_shape={p.payload_shape})")
        self.engine = engine
        self.program = p
        self.num_lanes = p.payload_shape[0]
        self.steps_per_tick = steps_per_tick
        self.default_budget = default_budget
        self.clock = clock
        self._target = target
        self._build()
        # After init_state/device_topology: any plan="auto-tuned" cache hit
        # has been adopted by now, and the jitted tick/admit fns above trace
        # lazily on first call — so the clamp below still lands before any
        # trace reads the frontier knobs.
        self._clamp_sum_monoid_plan()
        self.queue: deque = deque()
        self.finished: List[Query] = []
        self._lane_query: List[Optional[Query]] = [None] * self.num_lanes
        self._pending_deltas: List = []   # "finish"-policy deltas awaiting swap
        self._uid = 0
        self.ticks = 0
        self.supersteps = 0
        self._busy_lane_ticks = 0
        self._first_submit: Optional[float] = None

    def _clamp_sum_monoid_plan(self) -> None:
        """Pin sum-monoid programs (PPR et al.) to the dense-frontier plan.

        Recycled-lane bitwise equality for fp sums needs an
        ORDER-INDEPENDENT schedule: the dense every-edge scan visits edges
        in one fixed order every superstep, so a recycled lane accumulates
        the exact float sequence a fresh batch would.  A compacted frontier
        reorders message delivery by frontier occupancy — which depends on
        which OTHER queries share the batch — silently breaking that
        equality.  The engine's own default pins this, but a
        `plan="auto-tuned"` cache hit or an explicit `adopt_plan` call can
        hand the batcher a compacted plan (tuned on some sparse-frontier
        scenario); clamp it back before any tick function traces.

        Only the frontier STRATEGY is clamped — the masked dense scan is
        already order-fixed.  `dense_frontier` (skip activity masks
        entirely) is a semantic knob owned by the program: forcing it on a
        halting program like PPR push breaks lane retirement, so it is
        reset to the program's own default instead."""
        if self.program.monoid.name != "sum":
            return
        local = self.engine.shard_engine
        local.frontier = "dense"
        local.frontier_cap = None
        local.dense_frontier = not self.program.halts

    def _build(self) -> None:
        """Build the jitted tick and admit fns and a fresh lane state for
        the current target."""
        engine, target = self.engine, self._target
        self._topo = engine.device_topology(target)
        self._tick_fn = engine.make_superstep(
            target, steps_per_tick=self.steps_per_tick)
        self._admit_fn = engine.make_admit(target)
        self.state = engine.init_state(
            target, source=[None] * self.num_lanes, lane_tracking=True)

    # --------------------------------------------------------------- serving
    def submit(self, source: int, *, kind: Optional[str] = None,
               max_supersteps: Optional[int] = None) -> Query:
        q = Query(uid=self._uid, source=int(source),
                  kind=kind or self.program.name,
                  max_supersteps=(max_supersteps if max_supersteps is not None
                                  else self.default_budget),
                  submitted_at=self.clock())
        self._uid += 1
        if self._first_submit is None:
            self._first_submit = q.submitted_at
        self.queue.append(q)
        return q

    @property
    def busy(self) -> bool:
        return any(q is not None for q in self._lane_query)

    @property
    def idle(self) -> bool:
        return not self.busy and not self.queue

    def _lane_active_host(self) -> np.ndarray:
        la = np.asarray(jax.device_get(self.state.lane_active))
        return la.reshape(-1, self.num_lanes)[0]   # a mesh replicates it

    def _lane_result(self, vd_host: np.ndarray, lane: int) -> np.ndarray:
        if self.program.lane_view is not None:
            return np.asarray(self.program.lane_view(vd_host, lane))
        return vd_host[:, lane].copy()

    def pump(self) -> List[Query]:
        """Retire converged lanes, evict over-budget ones, land any pending
        graph delta once the lanes drain, admit from the queue — host-side,
        between ticks; ends with at most ONE jitted static-shape admit call
        covering every lane transition."""
        D = self.num_lanes
        finished: List[Query] = []
        la = self._lane_active_host()
        vd_host = None
        ops: Dict[int, int] = {}   # lane -> source (-1 = reset only)
        now = self.clock()
        for d in range(D):
            q = self._lane_query[d]
            if q is None:
                continue
            if not la[d]:            # converged: fetch result, free the lane
                if vd_host is None:
                    vd_host = self.engine.to_original(
                        self._target, self.state.vertex_data)
                q.result = self._lane_result(vd_host, d)
                q.status, q.finished_at = "done", now
                finished.append(q)
                self._lane_query[d] = None
            elif (q.max_supersteps is not None
                  and q.supersteps_used >= q.max_supersteps):
                q.status, q.finished_at = "evicted", now   # budget exceeded
                finished.append(q)
                self._lane_query[d] = None
                ops[d] = -1                  # reset the lane, seed nothing
        # "finish"-policy deltas land here: every resident lane has drained
        # (their results above were fetched from the pre-delta snapshot),
        # so the swap is between ticks by construction — never torn.  A
        # still-pending delta holds admissions so it lands in bounded time.
        if self._pending_deltas and not self.busy:
            self._swap_target()
            ops = {}   # stale resets target the replaced state; drop them
        for d in range(D):
            if self._pending_deltas:
                break                # hold admissions until the delta lands
            if self._lane_query[d] is None and self.queue:
                q = self.queue.popleft()
                q.status, q.lane, q.admitted_at = "running", d, now
                q.supersteps_used = 0
                self._lane_query[d] = q
                ops[d] = q.source            # admit overrides evict
        if ops:
            self._apply_ops(ops)
        self.finished.extend(finished)
        return finished

    def _apply_ops(self, ops: Dict[int, int]) -> None:
        """ONE jitted admit call applying `lane -> source` transitions
        (source -1 = reset without seeding)."""
        D = self.num_lanes
        lanes = np.full(D, D, np.int32)              # sentinel lane = D
        sources = np.full(D, -1, np.int64)
        for i, (d, s) in enumerate(ops.items()):
            lanes[i], sources[i] = d, s
        self.state = self._admit_fn(self.state, sources, lanes)

    # ------------------------------------------------------- graph mutation
    def apply_delta(self, delta, *, policy: str = "finish") -> None:
        """Land an `EdgeDelta` on a live batcher (docs/incremental.md).

        Ticks are whole-state jitted calls over an immutable topology
        snapshot, so a delta NEVER lands mid-tick — a torn read (a query
        observing half the mutation) cannot exist by construction.  The
        policy decides what happens to queries resident in lanes:

          "finish" — residents run to completion on the pre-delta
              snapshot; the swap happens at the first `pump()` after the
              last resident drains.  Admissions are HELD while a delta is
              pending, bounding the wait by the slowest resident.
          "reseed" — the swap happens now; residents are re-seeded from
              superstep 0 on the mutated graph in their lanes (fresh
              init values, so no invalidation pass is needed — any
              program the batcher can serve supports this).  Their
              `supersteps_used` keeps accumulating toward the budget.

        Either way, queries admitted after this call run on the mutated
        graph, and recycled-lane results stay bitwise-equal to fresh runs
        (tests/test_serving.py).
        """
        assert policy in ("finish", "reseed"), policy
        self._pending_deltas.append(delta)
        if policy == "finish":
            if not self.busy:
                self._swap_target()
            return
        residents = [(d, q) for d, q in enumerate(self._lane_query)
                     if q is not None]
        self._swap_target()
        if residents:
            self._apply_ops({d: q.source for d, q in residents})

    def _swap_target(self) -> None:
        """Apply every pending delta to the topology and rebuild the jitted
        tick/admit functions + a fresh lane state.  Callers guarantee no
        lane holds a query whose state must survive (drained, or about to
        be re-seeded)."""
        deltas, self._pending_deltas = self._pending_deltas, []
        for delta in deltas:
            # the engine re-keys a tuned plan against the mutated graph
            # before the new tick function traces
            self._target, _ = self.engine.apply_delta(self._target, delta)
        self._build()
        # re-keyed cache hits can adopt a compacted plan for the mutated
        # graph; sum-monoid lanes must stay dense (see
        # `_clamp_sum_monoid_plan`).
        self._clamp_sum_monoid_plan()

    def tick(self) -> None:
        """Advance every resident lane by `steps_per_tick` supersteps."""
        self._busy_lane_ticks += sum(
            q is not None for q in self._lane_query)
        self.state = self._tick_fn(self._topo, self.state)
        self.ticks += 1
        self.supersteps += self.steps_per_tick
        for q in self._lane_query:
            if q is not None:
                q.supersteps_used += self.steps_per_tick

    def run(self, max_ticks: int = 100_000) -> List[Query]:
        """Pump/tick until queue and lanes drain; returns queries finished
        during this call (done or evicted), in completion order."""
        out = list(self.pump())
        while self.busy and self.ticks < max_ticks:
            self.tick()
            out.extend(self.pump())
        return out

    # --------------------------------------------------------------- metrics
    def metrics(self) -> Dict[str, float]:
        """SLO metrics over everything finished so far (docs/serving.md)."""
        done = [q for q in self.finished if q.status == "done"]
        lat = sorted(q.latency_s for q in done)
        steps = sorted(float(q.supersteps_used) for q in done)
        waits = [q.wait_s for q in done]
        span = (max(q.finished_at for q in done) - self._first_submit
                if done and self._first_submit is not None else 0.0)
        cap = self.ticks * self.num_lanes
        return {
            "queries_done": float(len(done)),
            "queries_evicted": float(
                sum(q.status == "evicted" for q in self.finished)),
            "ticks": float(self.ticks),
            "supersteps": float(self.supersteps),
            "lane_occupancy": self._busy_lane_ticks / cap if cap else 0.0,
            "qps": len(done) / span if span > 0 else float("nan"),
            "latency_p50_s": _percentile(lat, 0.50),
            "latency_p95_s": _percentile(lat, 0.95),
            "latency_mean_s": float(np.mean(lat)) if lat else float("nan"),
            "queue_wait_mean_s": (float(np.mean(waits)) if waits
                                  else float("nan")),
            "supersteps_p50": _percentile(steps, 0.50),
            "supersteps_p95": _percentile(steps, 0.95),
        }


class ServingFrontend:
    """Routes a mixed-kind query stream to per-kind batchers.

    Payload lanes batch queries of ONE program, so a deployment serving
    BFS + SSSP + PPR runs one `GraphQueryBatcher` per kind; the frontend
    owns submission routing and a fair round-robin tick loop (each busy
    batcher advances one tick per round)."""

    def __init__(self, batchers: Dict[str, GraphQueryBatcher]):
        self.batchers = batchers

    def submit(self, kind: str, source: int, **kw) -> Query:
        return self.batchers[kind].submit(source, kind=kind, **kw)

    @property
    def idle(self) -> bool:
        return all(b.idle for b in self.batchers.values())

    def step(self) -> List[Query]:
        """One round: pump every batcher, tick the busy ones."""
        out: List[Query] = []
        for b in self.batchers.values():
            out.extend(b.pump())
            if b.busy:
                b.tick()
        return out

    def run(self, max_rounds: int = 100_000) -> List[Query]:
        out: List[Query] = []
        for _ in range(max_rounds):
            out.extend(self.step())
            if self.idle:
                break
        for b in self.batchers.values():
            out.extend(b.pump())
        return out

    def metrics(self) -> Dict[str, Dict[str, float]]:
        return {kind: b.metrics() for kind, b in self.batchers.items()}
