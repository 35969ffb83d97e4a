"""Cross-backend x cross-strategy conformance matrix.

ONE suite asserting BITWISE-equal results across the combinatorial surface

    {null, agent, dense, pipelined} exchange backends
  x {dense, flat, compact, auto} frontier strategies
  x {XLA, Pallas-dynamic-table, Pallas-full-table} combine kernels
  x {single-source, multi-source} payloads

on random power-law (R-MAT) and circulant graphs, replacing the ad-hoc
per-pair checks that previously accreted across `test_exchange.py`,
`test_frontier.py` and `test_pipeline_overlap.py`.  The reference is
always the single-shard dense-strategy NullExchange run; min-monoid
traversal programs (BFS/SSSP/CC) must match it bitwise — min is exactly
associative/commutative, so neither the exchange's two-stage ⊕, the
bucketed tiles' per-bucket partial order, nor the Pallas dynamic pruning
pass's on-device dst sort can leak through.  Every combination runs
through the ONE plan executor (`repro.core.plan.execute_plan`): there is
no separate pipelined loop to diverge from.

The in-process matrix covers the null backend (every strategy and kernel,
interpret-mode Pallas) and the pipelined backend on a 1-device mesh
(split tiles + deferred merge, degenerate flush).  The real multi-shard
matrix needs the 8-device XLA_FLAGS set before jax initializes, so it
runs in a subprocess and is marked `slow`.  A kernel-level section checks
the on-device `dynamic_block_table` pruning pass against the full table
and the XLA oracle directly; each hypothesis test has a fixed-seed twin
so the matrix still runs where `hypothesis` is absent.
"""
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

import jax

from repro.core import algorithms
from repro.core.agent_graph import build_agent_graph
from repro.core.dist_engine import DistGREEngine
from repro.core.engine import DevicePartition, GREEngine
from repro.core.partition import greedy_partition
from repro.graph.generators import circulant_graph, rmat_edges

SRC = str(Path(__file__).resolve().parent.parent / "src")

STRATEGIES = ("dense", "compact", "auto", "flat")
MULTI_SOURCES = [0, 3, 17]


def _graph(kind: str, scale: int, edge_factor: int, seed: int):
    if kind == "circulant":
        return circulant_graph(1 << scale, degree=edge_factor, weights=True,
                               seed=seed)
    return rmat_edges(scale=scale, edge_factor=edge_factor, seed=seed,
                      weights=True).dedup()


def _single_shard(program, part, source=None, frontier="dense", cap=None,
                  max_steps=300):
    eng = GREEngine(program, frontier=frontier, frontier_cap=cap)
    out = eng.run(part, eng.init_state(part, source=source), max_steps)
    return np.asarray(out.vertex_data)


def _dist_k1(program, g, exchange="pipelined", source=None, max_steps=300,
             **kw):
    """One of the distributed exchanges on a 1-device mesh (degenerate
    collectives, real phase shape — including the async staleness ring)."""
    ag = build_agent_graph(g, greedy_partition(g, 1, batch_size=64), 1)
    mesh = jax.make_mesh((1,), ("graph",))
    eng = DistGREEngine(program, mesh, ("graph",), exchange=exchange, **kw)
    out, _ = eng.run(ag, source=source, max_steps=max_steps)
    return out


def _pipelined(program, g, source=None, max_steps=300, **kw):
    return _dist_k1(program, g, exchange="pipelined", source=source,
                    max_steps=max_steps, **kw)


def _fix(x):
    return np.nan_to_num(x, posinf=-1.0)


# ------------------------------------------------ in-process strategy matrix
def _check_null_matrix(kind, scale, edge_factor, seed, source, strategy,
                       cap):
    """Single shard: `strategy` == dense, bitwise, for single-source BFS
    and multi-source SSSP (caps small enough to force mid-run overflow
    fallbacks ride the per-bucket guards)."""
    g = _graph(kind, scale, edge_factor, seed)
    part = DevicePartition.from_graph(g)
    bfs_ref = _single_shard(algorithms.bfs_program(), part, source=source)
    got = _single_shard(algorithms.bfs_program(), part, source=source,
                        frontier=strategy, cap=cap)
    np.testing.assert_array_equal(got, bfs_ref)
    ms = algorithms.sssp_program(num_sources=len(MULTI_SOURCES))
    ms_ref = _single_shard(ms, part, source=MULTI_SOURCES)
    got = _single_shard(ms, part, source=MULTI_SOURCES,
                        frontier=strategy, cap=cap)
    np.testing.assert_array_equal(got, ms_ref)


def _check_pipelined_k1(kind, scale, edge_factor, seed, source, strategy):
    """Pipelined backend (split tiles + deferred merge) on a 1-device
    mesh: `strategy` == the single-shard dense reference, bitwise, for
    BFS and SSSP."""
    g = _graph(kind, scale, edge_factor, seed)
    part = DevicePartition.from_graph(g)
    for prog in (algorithms.bfs_program(), algorithms.sssp_program()):
        ref = _single_shard(prog, part, source=source)
        got = _pipelined(prog, g, source=source, frontier=strategy,
                         frontier_cap=64)
        np.testing.assert_array_equal(_fix(got), _fix(ref))


def _check_null_pallas(kind, scale, edge_factor, seed, source, strategy,
                       cap, dynamic):
    """The Pallas row: `use_pallas=True` (interpret mode) over the same
    strategies, bitwise against BOTH the XLA engine at the same strategy
    and the dense reference — with the on-device dynamic block table
    (`dynamic=True`, the default) and the degenerate full-table fallback
    (`dynamic=False`)."""
    g = _graph(kind, scale, edge_factor, seed)
    part = DevicePartition.from_graph(g)
    for prog in (algorithms.bfs_program(),
                 algorithms.sssp_program(num_sources=len(MULTI_SOURCES))):
        multi = prog.payload_shape != ()
        src = MULTI_SOURCES if multi else source
        ref = _single_shard(prog, part, source=src)
        xla = _single_shard(prog, part, source=src, frontier=strategy,
                            cap=cap)
        eng = GREEngine(prog, frontier=strategy, frontier_cap=cap,
                        use_pallas=True, dynamic_table=dynamic)
        got = np.asarray(eng.run(part, eng.init_state(part, source=src),
                                 300).vertex_data)
        np.testing.assert_array_equal(got, xla)
        np.testing.assert_array_equal(got, ref)


@pytest.mark.parametrize("strategy", STRATEGIES)
@pytest.mark.parametrize("kind", ["rmat", "circulant"])
def test_null_backend_strategy_matrix(kind, strategy):
    _check_null_matrix(kind, 7, 8, 5, 0, strategy, cap=32)


@pytest.mark.parametrize("dynamic", [True, False],
                         ids=["dynamic-table", "full-table"])
@pytest.mark.parametrize("strategy", ("compact", "auto", "flat"))
def test_null_backend_pallas_matrix(strategy, dynamic):
    _check_null_pallas("rmat", 7, 8, 5, 0, strategy, 32, dynamic)


@pytest.mark.parametrize("strategy", ("dense", "compact", "auto"))
def test_pipelined_k1_strategy_matrix(strategy):
    _check_pipelined_k1("rmat", 7, 8, 5, 0, strategy)


# {agent, pipelined, async-k2, async-k4} rows of the backend matrix: the
# async rows drive the bounded-staleness ring (refresh collective every k
# supersteps, k-deep remote-partial ring, in-flight slots counted by the
# termination predicate) and must land on the SAME fixed point — values,
# not trajectories.
K1_BACKENDS = [("agent", {}), ("pipelined", {}),
               ("async", {"staleness": 2}), ("async", {"staleness": 4})]
K1_IDS = ["agent", "pipelined", "async-k2", "async-k4"]


@pytest.mark.parametrize("strategy", ("dense", "compact", "auto"))
@pytest.mark.parametrize("backend,opts", K1_BACKENDS, ids=K1_IDS)
def test_backend_k1_strategy_matrix(backend, opts, strategy):
    g = _graph("rmat", 7, 8, 5)
    part = DevicePartition.from_graph(g)
    for prog in (algorithms.bfs_program(), algorithms.sssp_program()):
        ref = _single_shard(prog, part, source=0)
        got = _dist_k1(prog, g, exchange=backend, source=0,
                       frontier=strategy, frontier_cap=64, **opts)
        np.testing.assert_array_equal(_fix(got), _fix(ref))


@pytest.mark.parametrize("backend,opts", K1_BACKENDS, ids=K1_IDS)
def test_backend_k1_cc(backend, opts):
    """CC (every vertex initially active, undirected) across the same
    backend rows — the all-slots-live stress for the async ring fold."""
    g = rmat_edges(scale=6, edge_factor=4, seed=5).dedup().as_undirected()
    part = DevicePartition.from_graph(g)
    ref = _single_shard(algorithms.cc_program(), part)
    got = _dist_k1(algorithms.cc_program(), g, exchange=backend,
                   frontier="auto", frontier_cap=64, **opts)
    np.testing.assert_array_equal(_fix(got), _fix(ref))


def test_async_refuses_sum_monoid_programs():
    """Bounded staleness is only sound for idempotent min/max fixed points
    (`VertexProgram.monotone`): a sum-monoid program would double-count
    every re-delivered partial.  All three ingress points must refuse
    loudly — constructor, adopt_plan, and the tuner's candidate axis is
    pruned (covered in test_tuning)."""
    from repro.core.plan import SuperstepPlan
    mesh = jax.make_mesh((1,), ("graph",))
    pr = algorithms.pagerank_program()
    with pytest.raises(ValueError, match="monotone"):
        DistGREEngine(pr, mesh, ("graph",), exchange="async", staleness=2)
    ppr = algorithms.ppr_push_program(2)
    with pytest.raises(ValueError, match="monotone"):
        DistGREEngine(ppr, mesh, ("graph",), exchange="async", staleness=2)
    eng = DistGREEngine(pr, mesh, ("graph",), exchange="agent")
    with pytest.raises(ValueError, match="monotone"):
        eng.adopt_plan(SuperstepPlan(phases="async", staleness=2))


def test_async_staleness_validation():
    """exchange='async' needs a ring depth >= 1; the serving tick cannot
    run async at all (un-flushed ring partials would be dropped across
    ticks)."""
    mesh = jax.make_mesh((1,), ("graph",))
    bfs = algorithms.bfs_program()
    with pytest.raises(ValueError, match="staleness"):
        DistGREEngine(bfs, mesh, ("graph",), exchange="async", staleness=0)
    g = _graph("rmat", 6, 4, 3)
    ag = build_agent_graph(g, greedy_partition(g, 1, batch_size=64), 1)
    eng = DistGREEngine(bfs, mesh, ("graph",), exchange="async", staleness=2)
    eng.device_topology(ag)
    with pytest.raises(ValueError, match="serving"):
        eng.make_superstep(ag)


def test_pipelined_k1_pallas():
    """Pallas tile combine (dynamic table) through the pipelined backend's
    split edge tiles on a 1-device mesh: bitwise vs the dense XLA
    reference."""
    g = _graph("rmat", 7, 8, 5)
    part = DevicePartition.from_graph(g)
    prog = algorithms.sssp_program()
    ref = _single_shard(prog, part, source=0)
    got = _pipelined(prog, g, source=0, frontier="compact", frontier_cap=64,
                     use_pallas=True)
    np.testing.assert_array_equal(_fix(got), _fix(ref))


try:
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:  # pragma: no cover - optional dependency
    HAVE_HYPOTHESIS = False


if HAVE_HYPOTHESIS:

    @settings(max_examples=12, deadline=None)
    @given(kind=st.sampled_from(["rmat", "circulant"]),
           scale=st.integers(5, 7), edge_factor=st.integers(2, 8),
           seed=st.integers(0, 999), source=st.integers(0, 31),
           strategy=st.sampled_from(STRATEGIES),
           cap=st.sampled_from([None, 8, 64]))
    def test_null_backend_strategy_matrix_random(kind, scale, edge_factor,
                                                 seed, source, strategy,
                                                 cap):
        _check_null_matrix(kind, scale, edge_factor, seed, source, strategy,
                           cap)

    @settings(max_examples=8, deadline=None)
    @given(kind=st.sampled_from(["rmat", "circulant"]),
           scale=st.integers(5, 7), edge_factor=st.integers(2, 8),
           seed=st.integers(0, 999), source=st.integers(0, 31),
           strategy=st.sampled_from(("dense", "compact", "auto")))
    def test_pipelined_k1_strategy_matrix_random(kind, scale, edge_factor,
                                                 seed, source, strategy):
        _check_pipelined_k1(kind, scale, edge_factor, seed, source, strategy)

    # fixed-seed twin: test_null_backend_pallas_matrix
    @settings(max_examples=6, deadline=None)
    @given(kind=st.sampled_from(["rmat", "circulant"]),
           scale=st.integers(5, 7), edge_factor=st.integers(2, 8),
           seed=st.integers(0, 999), source=st.integers(0, 31),
           strategy=st.sampled_from(("compact", "auto", "flat")),
           dynamic=st.booleans())
    def test_null_backend_pallas_matrix_random(kind, scale, edge_factor,
                                               seed, source, strategy,
                                               dynamic):
        _check_null_pallas(kind, scale, edge_factor, seed, source, strategy,
                           32, dynamic)

    # fixed-seed twin: test_dynamic_block_table_fixed
    @settings(max_examples=15, deadline=None)
    @given(e=st.integers(1, 600), v=st.integers(1, 300),
           d=st.sampled_from([1, 4, 8]), op=st.sampled_from(["min", "sum"]),
           valid_frac=st.floats(0.0, 1.0), seed=st.integers(0, 2**16))
    def test_dynamic_block_table_random(e, v, d, op, valid_frac, seed):
        _check_dynamic_table(e, v, d, op, valid_frac, seed)


@pytest.mark.parametrize("strategy", STRATEGIES)
def test_cc_strategy_matrix(strategy):
    """CC (min monoid, every vertex initially active — the all-buckets-live
    stress for the bucketed gather): strategies agree bitwise."""
    g = rmat_edges(scale=6, edge_factor=4, seed=5).dedup().as_undirected()
    part = DevicePartition.from_graph(g)
    ref = _single_shard(algorithms.cc_program(), part)
    got = _single_shard(algorithms.cc_program(), part, frontier=strategy,
                        cap=16)
    np.testing.assert_array_equal(got, ref)


# ------------------------------------------- mutation conformance (warm)
MUT_BACKENDS = ("null", "agent", "dense", "pipelined")
MUT_STRATEGIES = ("dense", "compact", "auto")


def _mutation_delta(g, seed, frac=0.08, undirected=False):
    """A fixed-seed churn batch: retire `frac` of the live edges and add
    the same number of fresh ones (symmetric pairs when `undirected`, so
    CC's both-directions invariant holds).  Weights are small integers —
    exact in f32, so warm-vs-cold comparisons stay bitwise."""
    from repro.graph.structures import EdgeDelta
    rng = np.random.default_rng(seed)
    src, dst = np.asarray(g.src), np.asarray(g.dst)
    n = g.num_vertices
    if undirected:
        fwd = np.flatnonzero(src < dst)
        m = max(1, int(fwd.size * frac))
        pick = rng.choice(fwd, size=m, replace=False)
        rem_s = np.concatenate([src[pick], dst[pick]])
        rem_d = np.concatenate([dst[pick], src[pick]])
        u = rng.integers(0, n, size=m)
        v = (u + 1 + rng.integers(0, n - 1, size=m)) % n   # never u == v
        # dedup by unordered pair: the symmetric concat below would turn a
        # repeated {u, v} into in-batch duplicate rows, which ingress rejects
        lo, hi = np.minimum(u, v), np.maximum(u, v)
        _, first = np.unique(lo.astype(np.int64) * n + hi, return_index=True)
        keep = np.sort(first)
        u, v = u[keep], v[keep]
        add_s, add_d = np.concatenate([u, v]), np.concatenate([v, u])
        m_prop = keep.size
    else:
        m = max(1, int(g.num_edges * frac))
        pick = rng.choice(g.num_edges, size=m, replace=False)
        rem_s, rem_d = src[pick], dst[pick]
        add_s = rng.integers(0, n, size=m)
        add_d = rng.integers(0, n, size=m)
        _, first = np.unique(add_s.astype(np.int64) * n + add_d,
                             return_index=True)
        keep = np.sort(first)
        add_s, add_d = add_s[keep], add_d[keep]
        m_prop = keep.size
    props = {}
    for key in g.edge_props:
        w = rng.integers(1, 100, size=m_prop).astype(np.float32)
        props[key] = np.concatenate([w, w]) if undirected else w
    return EdgeDelta(add_src=add_s, add_dst=add_d, add_props=props,
                     rem_src=rem_s, rem_dst=rem_d)


def _warm_single(prog, g, delta, source, strategy, max_steps=300):
    eng = GREEngine(prog, frontier=strategy, frontier_cap=32)
    part = DevicePartition.from_graph(g)
    prev = eng.run(part, eng.init_state(part, source=source), max_steps)
    _, out, _ = eng.rerun_incremental(part, prev, delta, source=source,
                                      max_steps=max_steps)
    return np.asarray(out.vertex_data)


def _warm_dist(prog, g, delta, source, backend, strategy, max_steps=300):
    ag = build_agent_graph(g, greedy_partition(g, 1, batch_size=64), 1)
    mesh = jax.make_mesh((1,), ("graph",))
    eng = DistGREEngine(prog, mesh, ("graph",), exchange=backend,
                        frontier=strategy, frontier_cap=64)
    _, prev = eng.run(ag, source=source, max_steps=max_steps)
    _, result, _, _ = eng.rerun_incremental(ag, prev, delta, source=source,
                                            max_steps=max_steps)
    return result


@pytest.mark.parametrize("strategy", MUT_STRATEGIES)
@pytest.mark.parametrize("backend", MUT_BACKENDS)
def test_mutation_warm_equals_cold(backend, strategy):
    """THE incremental-re-convergence invariant (docs/incremental.md): a
    warm start from the pre-delta fixed point must land on BITWISE the
    same fixed point as a cold recompute of the mutated graph — min is
    idempotent and the fixed point unique, so seeding only the affected
    region may change the path, never the answer.  Single-source BFS and
    multi-source SSSP, every backend x frontier strategy."""
    g = _graph("rmat", 6, 4, 11)
    delta = _mutation_delta(g, seed=21)
    part2 = DevicePartition.from_graph(g.apply_edge_delta(delta))
    for prog, src in ((algorithms.bfs_program(), 0),
                      (algorithms.sssp_program(
                          num_sources=len(MULTI_SOURCES)), MULTI_SOURCES)):
        ref = _single_shard(prog, part2, source=src)   # cold recompute
        if backend == "null":
            got = _warm_single(prog, g, delta, src, strategy)
        else:
            got = _warm_dist(prog, g, delta, src, backend, strategy)
        np.testing.assert_array_equal(_fix(got), _fix(ref))


@pytest.mark.parametrize("strategy", MUT_STRATEGIES)
@pytest.mark.parametrize("backend", MUT_BACKENDS)
def test_mutation_warm_equals_cold_cc(backend, strategy):
    """CC under mutation: label propagation's support is CYCLIC, so
    removals invalidate by reachability over the pre-delta edge set
    (`invalidation="component"`) — the warm fixed point must still equal
    the cold recompute bitwise on every backend x strategy."""
    g = rmat_edges(scale=6, edge_factor=4, seed=5).dedup().as_undirected()
    delta = _mutation_delta(g, seed=33, undirected=True)
    part2 = DevicePartition.from_graph(g.apply_edge_delta(delta))
    prog = algorithms.cc_program()
    ref = _single_shard(prog, part2)
    if backend == "null":
        got = _warm_single(prog, g, delta, None, strategy)
    else:
        got = _warm_dist(prog, g, delta, None, backend, strategy)
    np.testing.assert_array_equal(_fix(got), _fix(ref))


# ------------------------------------------------------- plan composition
def test_superstep_plan_composition():
    """The plan surface: engines expose the composed mode as ONE static
    object — frontier strategy request, kernel stage, and the phase shape
    the selected backend's protocol drives — and the recorded phase shape
    matches the backend's `phases` attribute."""
    import jax
    from repro.core.exchange import NULL_EXCHANGE
    from repro.core.plan import KernelPlan
    prog = algorithms.bfs_program()
    eng = GREEngine(prog, frontier="compact", use_pallas=True,
                    dynamic_table=False, frontier_cap=64)
    plan = eng.make_plan()
    assert plan.phases == NULL_EXCHANGE.phases == "sync"
    assert plan.strategy == "compact" and plan.frontier_cap == 64
    assert plan.kernel == KernelPlan(use_pallas=True, dynamic_table=False)
    # the frontier stage resolves per partition (bucketed on this graph)
    part = DevicePartition.from_graph(_graph("rmat", 7, 8, 5))
    fp = plan.frontier(part)
    assert fp.kind == "bucketed" and sum(fp.caps) > 0
    mesh = jax.make_mesh((1,), ("graph",))
    for exchange, phases in (("pipelined", "pipelined"), ("agent", "sync")):
        dist = DistGREEngine(prog, mesh, ("graph",), exchange=exchange)
        assert dist.plan.phases == phases
        backend_cls = {"pipelined": "PipelinedAgentExchange",
                       "agent": "AgentExchange"}[exchange]
        from repro.core import exchange as ex
        assert getattr(ex, backend_cls).phases == phases
    # calibration between construction and run is honored: the plan is
    # rebuilt on access, never a stale frozen copy
    dist = DistGREEngine(prog, mesh, ("graph",), exchange="agent")
    dist.local.frontier_cap = 8
    assert dist.plan.frontier_cap == 8


# ----------------------------------------------------- plan serialization
def test_superstep_plan_json_round_trip():
    """Every plan the search space can emit must survive
    to_json -> (real JSON text) -> from_json EQUAL — the persistent plan
    cache (repro.tuning.cache) stores nothing else."""
    import json

    from repro.core.plan import KernelPlan, SuperstepPlan
    plans = [
        SuperstepPlan(),
        SuperstepPlan(strategy="flat", frontier_cap=64),
        SuperstepPlan(strategy="compact", frontier_cap=128,
                      bucket_bounds=(4, 16, 64, 256)),
        SuperstepPlan(strategy="dense", dense_frontier=True,
                      phases="pipelined",
                      kernel=KernelPlan(use_pallas=True,
                                        dynamic_table=False)),
        SuperstepPlan(phases="async", staleness=2),
        SuperstepPlan(strategy="compact", frontier_cap=64,
                      phases="async", staleness=4),
    ]
    for plan in plans:
        wire = json.loads(json.dumps(plan.to_json()))
        assert SuperstepPlan.from_json(wire) == plan, plan


def test_superstep_plan_staleness_validation():
    """`staleness` is the async ring depth: phases='async' needs >= 1,
    every other phase shape must carry 0 — a cached plan can't smuggle a
    stale ring depth into a sync engine."""
    from repro.core.plan import SuperstepPlan
    with pytest.raises(ValueError, match="staleness"):
        SuperstepPlan(phases="async", staleness=0)
    with pytest.raises(ValueError, match="staleness"):
        SuperstepPlan(phases="sync", staleness=2)
    with pytest.raises(ValueError, match="staleness"):
        SuperstepPlan(phases="pipelined", staleness=2)
    good = SuperstepPlan(phases="async", staleness=3).to_json()
    assert good["staleness"] == 3
    from_wire = SuperstepPlan.from_json(good)
    assert from_wire.staleness == 3 and from_wire.phases == "async"


def test_superstep_plan_json_rejects_unknown_fields():
    """Schema drift fails loudly at load time — at the plan level AND
    inside the nested kernel dict — instead of silently dropping a knob
    a future version considered load-bearing."""
    from repro.core.plan import SuperstepPlan
    good = SuperstepPlan(strategy="flat", frontier_cap=64).to_json()
    with pytest.raises(ValueError, match="unknown"):
        SuperstepPlan.from_json({**good, "exchange_fanout": 4})
    with pytest.raises(ValueError, match="unknown"):
        SuperstepPlan.from_json(
            {**good, "kernel": {**good["kernel"], "vector_width": 8}})


def test_cached_plan_executes_bitwise_identical(tmp_path):
    """A plan round-tripped through the persistent cache file must drive
    `execute_plan` to BITWISE-identical results vs the in-memory
    original: adopting a cached plan may never change semantics, only
    speed."""
    from repro.core.plan import SuperstepPlan
    from repro.tuning import PlanCache
    plan = SuperstepPlan(strategy="compact", frontier_cap=64)
    cache = PlanCache(tmp_path / "plans.json")
    cache.store("k", plan, probe_us=1.0)
    reloaded = PlanCache(tmp_path / "plans.json").lookup("k")
    assert reloaded == plan

    g = _graph("rmat", 7, 8, 3)
    prog = algorithms.sssp_program()
    finals = []
    for p in (plan, reloaded):
        eng = GREEngine(prog, plan=p)
        part = DevicePartition.from_graph(g, bucket_bounds=p.bucket_bounds)
        finals.append(eng.run(part, eng.init_state(part, source=0), 64))
    np.testing.assert_array_equal(np.asarray(finals[0].vertex_data),
                                  np.asarray(finals[1].vertex_data))


# ------------------------------------------- dynamic block table (kernels)
def _check_dynamic_table(e, v, d, op, valid_frac, seed, block=64):
    """The on-device pruning pass vs the full table vs the XLA oracle, on
    a tile with `valid_frac` real lanes and sentinel (`dst == v`) padding:
    min/max must be bitwise, sum to float tolerance (the dst-sort
    reorders); the dynamic table must visit a subset of the full table's
    pairs that still covers every real edge block."""
    import jax.numpy as jnp
    from repro.kernels import ref
    from repro.kernels.segment_combine import (dynamic_block_table,
                                               tile_segment_combine_pallas)
    rng = np.random.default_rng(seed)
    valid = rng.random(e) < valid_frac
    dst = np.where(valid, rng.integers(0, v, e), v).astype(np.int32)
    ident = {"sum": 0.0, "min": np.inf, "max": -np.inf}[op]
    msgs = rng.normal(size=(e, d)).astype(np.float32)
    msgs[~valid] = ident
    kw = dict(block_e=block, block_v=block)
    dyn = tile_segment_combine_pallas(jnp.asarray(msgs), jnp.asarray(dst),
                                      v, op, **kw)
    full = tile_segment_combine_pallas(jnp.asarray(msgs), jnp.asarray(dst),
                                       v, op, dynamic=False, **kw)
    want = ref.segment_combine_ref(jnp.asarray(msgs),
                                   jnp.asarray(np.where(valid, dst, 0)),
                                   v, op)
    fix = lambda x: np.nan_to_num(np.asarray(x), posinf=1e30, neginf=-1e30)
    if op == "sum":
        np.testing.assert_allclose(fix(dyn), fix(want), rtol=1e-5,
                                   atol=1e-5)
        np.testing.assert_allclose(fix(full), fix(want), rtol=1e-5,
                                   atol=1e-5)
    else:
        np.testing.assert_array_equal(fix(dyn), fix(want))
        np.testing.assert_array_equal(fix(full), fix(want))
    # coverage: every (dst block, edge block) pair with a real dst in the
    # dst block's range is among the sorted tile's visits of that block
    ds = np.sort(dst)
    n_e = -(-e // block)
    table = np.asarray(dynamic_block_table(jnp.asarray(ds), v, block, block))
    dpad = np.concatenate([ds, np.full(n_e * block - e, v, np.int32)])
    dpad = dpad.reshape(n_e, block)
    for i in range(-(-v // block)):
        lo, hi = i * block, (i + 1) * block
        need = {j for j in range(n_e)   # real dsts only: sentinels (>= v)
                if ((dpad[j] >= lo) & (dpad[j] < hi)
                    & (dpad[j] < v)).any()}
        have = {int(x) for x in table[1][table[0] == i] if x < n_e}
        assert need <= have
    # pruning: all-sentinel edge blocks never appear anywhere
    empty = {j for j in range(n_e) if (dpad[j] >= v).all()}
    seen = {int(x) for x in table[1] if x < n_e}
    assert not (empty & seen)


@pytest.mark.parametrize("op", ["sum", "min", "max"])
@pytest.mark.parametrize("e,v,d,valid_frac",
                         [(1000, 300, 8, 0.3), (513, 64, 1, 0.05),
                          (256, 256, 4, 1.0), (77, 33, 16, 0.5)])
def test_dynamic_block_table_fixed(e, v, d, valid_frac, op):
    _check_dynamic_table(e, v, d, op, valid_frac, seed=0)


# ------------------------------------------- multi-shard matrix (subprocess)
SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import sys
sys.path.insert(0, "__SRC__")
import numpy as np
import jax

from repro.graph.generators import circulant_graph, rmat_edges
from repro.core.engine import GREEngine, DevicePartition
from repro.core.partition import hash_partition
from repro.core.agent_graph import build_agent_graph
from repro.core.dist_engine import DistGREEngine
from repro.core import algorithms

k = 8
mesh = jax.make_mesh((8,), ("graph",))
fix = lambda x: np.nan_to_num(x, posinf=-1.0)
failures = []

BACKENDS = ("agent", "dense", "pipelined")
STRATEGIES = ("dense", "flat", "compact", "auto")
MULTI = [0, 7, 33, 101]

def reference(program, part, source=None, max_steps=300):
    eng = GREEngine(program, frontier="dense")
    st = eng.run(part, eng.init_state(part, source=source), max_steps)
    return np.asarray(st.vertex_data)

def dist(program, ag, backend, strategy, source=None, max_steps=300, **kw):
    eng = DistGREEngine(program, mesh, ("graph",), exchange=backend,
                        frontier=strategy, frontier_cap=64, **kw)
    out, _ = eng.run(ag, source=source, max_steps=max_steps)
    return out

# Full matrix on the power-law graph: {agent, dense, pipelined}
# x {dense, compact, auto} x {single-source SSSP, multi-source BFS},
# all bitwise vs the single-shard dense reference.
g = rmat_edges(scale=7, edge_factor=8, seed=5, weights=True).dedup()
ag = build_agent_graph(g, hash_partition(g, k), k)
sp = DevicePartition.from_graph(g)
ss_ref = reference(algorithms.sssp_program(), sp, source=0)
ms_prog = algorithms.bfs_program(num_sources=len(MULTI))
ms_ref = np.stack([reference(algorithms.bfs_program(), sp, source=s,
                             max_steps=100) for s in MULTI], axis=1)
for backend in BACKENDS:
    for strategy in STRATEGIES:
        got = dist(algorithms.sssp_program(), ag, backend, strategy,
                   source=0)
        if not np.array_equal(fix(got), fix(ss_ref)):
            failures.append(f"rmat sssp {backend}/{strategy}")
        got = dist(ms_prog, ag, backend, strategy, source=MULTI,
                   max_steps=100)
        if not np.array_equal(fix(got), fix(ms_ref)):
            failures.append(f"rmat bfs-multi {backend}/{strategy}")

# AgentExchange(overlap=True) rewrites part.dst per superstep — the one
# backend variant outside the main matrix whose interaction with the
# compacted gather (csr_eidx position indirection) needs its own row.
got = dist(algorithms.sssp_program(), ag, "agent", "compact", source=0,
           overlap=True)
if not np.array_equal(fix(got), fix(ss_ref)):
    failures.append("rmat sssp agent-overlap/compact")

# The Pallas row (interpret mode): the tile combine's on-device dynamic
# block table under shard_map, through both the sync agent backend and the
# pipelined split tiles — bitwise vs the XLA dense reference.
for backend in ("agent", "pipelined"):
    got = dist(algorithms.sssp_program(), ag, backend, "compact", source=0,
               use_pallas=True)
    if not np.array_equal(fix(got), fix(ss_ref)):
        failures.append(f"rmat sssp {backend}/compact/pallas-dynamic")

# Async rows: bounded-staleness ring over REAL 8-shard crossings — the
# refresh collective fires every k supersteps, remote partials ride the
# k-deep ring, and the fixed point must still land bitwise on the sync
# reference (supersteps inflate ~k-fold per shard crossing; raise the
# step budget accordingly).
for st, strategy in ((2, "auto"), (2, "dense"), (4, "auto")):
    got = dist(algorithms.sssp_program(), ag, "async", strategy, source=0,
               staleness=st, max_steps=1200)
    if not np.array_equal(fix(got), fix(ss_ref)):
        failures.append(f"rmat sssp async-k{st}/{strategy}")
got = dist(ms_prog, ag, "async", "auto", source=MULTI, staleness=2,
           max_steps=800)
if not np.array_equal(fix(got), fix(ms_ref)):
    failures.append("rmat bfs-multi async-k2/auto")

# Circulant sub-matrix: the uniform-degree regime (single bucket live).
gc = circulant_graph(1 << 11, degree=8, weights=True, seed=1)
agc = build_agent_graph(gc, hash_partition(gc, k), k)
spc = DevicePartition.from_graph(gc)
cref = reference(algorithms.sssp_program(), spc, source=3, max_steps=600)
for backend in BACKENDS:
    got = dist(algorithms.sssp_program(), agc, backend, "auto", source=3,
               max_steps=600)
    if not np.array_equal(fix(got), fix(cref)):
        failures.append(f"circulant sssp {backend}/auto")
got = dist(algorithms.sssp_program(), agc, "async", "auto", source=3,
           staleness=2, max_steps=2400)
if not np.array_equal(fix(got), fix(cref)):
    failures.append("circulant sssp async-k2/auto")

# Mutation row: warm-start re-convergence after an edge delta on the REAL
# 8-shard mesh (the hash partition's tight pads exercise the compaction
# fallback in agent_graph.apply_edge_delta) — bitwise vs the cold
# single-shard dense recompute of the mutated graph.
from repro.graph.structures import EdgeDelta
rng = np.random.default_rng(21)
m = max(1, g.num_edges // 20)
pick = rng.choice(g.num_edges, size=m, replace=False)
add_s = rng.integers(0, g.num_vertices, size=m)
add_d = rng.integers(0, g.num_vertices, size=m)
# in-batch duplicate (src, dst) rows are rejected by delta ingress
_, first = np.unique(add_s.astype(np.int64) * g.num_vertices + add_d,
                     return_index=True)
keep = np.sort(first)
add_s, add_d = add_s[keep], add_d[keep]
delta = EdgeDelta(
    add_src=add_s, add_dst=add_d,
    add_props={"weight": rng.integers(1, 100, size=keep.size)
               .astype(np.float32)},
    rem_src=np.asarray(g.src)[pick], rem_dst=np.asarray(g.dst)[pick])
cold = reference(algorithms.sssp_program(),
                 DevicePartition.from_graph(g.apply_edge_delta(delta)),
                 source=0)
for backend in BACKENDS:
    eng = DistGREEngine(algorithms.sssp_program(), mesh, ("graph",),
                        exchange=backend, frontier="auto", frontier_cap=64)
    _, prev = eng.run(ag, source=0, max_steps=300)
    _, warm, _, _ = eng.rerun_incremental(ag, prev, delta, source=0,
                                          max_steps=300)
    if not np.array_equal(fix(warm), fix(cold)):
        failures.append(f"mutation warm sssp {backend}")

assert not failures, failures
print("CONFORMANCE_OK")
"""


@pytest.mark.slow
def test_conformance_matrix_8_devices(tmp_path):
    script = tmp_path / "conformance_check.py"
    script.write_text(SCRIPT.replace("__SRC__", SRC))
    proc = subprocess.run([sys.executable, str(script)], capture_output=True,
                          text=True, timeout=900)
    assert proc.returncode == 0, proc.stderr[-4000:]
    assert "CONFORMANCE_OK" in proc.stdout
