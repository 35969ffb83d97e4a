"""Degree-bucketed frontier compaction: tile coverage, overflow semantics,
capacity calibration, and multi-source payload batching.

Strategy-equivalence across the full {backend} x {strategy} x {sources}
surface lives in `tests/test_conformance.py`; this module keeps the
frontier-specific properties: the bucketed gather PARTITIONS the edge set,
per-bucket overflow degrades only the overflowing bucket, the calibrated
capacity tracks the live frontier instead of `num_slots`, and the
payload-batched multi-source/multi-stage programs agree with their
per-source references.
"""
import numpy as np
import pytest

import jax.numpy as jnp

from repro.core import algorithms
from repro.core.engine import DevicePartition, EngineState, GREEngine
from repro.core.frontier import (bucket_caps, bucketed_scatter_combine,
                                 compact_indices, default_cap,
                                 gather_frontier_edge_tile)
from repro.graph.generators import circulant_graph, rmat_edges
from repro.graph.structures import Graph

try:
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:  # pragma: no cover - optional dependency
    HAVE_HYPOTHESIS = False


def _run(program, part, source=None, frontier="auto", cap=None,
         max_steps=300):
    eng = GREEngine(program, frontier=frontier, frontier_cap=cap)
    out = eng.run(part, eng.init_state(part, source=source), max_steps)
    return np.asarray(out.vertex_data)


@pytest.mark.parametrize("n,size,density", [
    (5, 3, 0.5), (1024, 100, 0.05), (1025, 2000, 0.3), (3000, 7, 0.9),
    (1, 1, 1.0), (4096, 10, 0.0)])
def test_compact_indices_matches_nonzero(n, size, density):
    """The frontier's compaction equals `jnp.nonzero` with a static size:
    the first `size` live slots in ascending order, then the fill value —
    across row boundaries of its two-level prefix sum, overflow (more live
    slots than `size`) and an empty mask."""
    mask = jnp.asarray(np.random.default_rng(n).random(n) < density)
    got = compact_indices(mask, size, n)
    want = jnp.nonzero(mask, size=size, fill_value=n)[0]
    assert got.dtype == want.dtype
    np.testing.assert_array_equal(got, want)


def _assert_strategies_agree(program, part, source=None, cap=None):
    dense = _run(program, part, source=source, frontier="dense")
    compact = _run(program, part, source=source, frontier="compact", cap=cap)
    np.testing.assert_array_equal(dense, compact)


def _star_graph(n: int) -> Graph:
    """Hub 0 -> every leaf, every leaf -> hub (so leaves scatter too)."""
    src = np.zeros(n - 1, dtype=np.int64)
    dst = np.arange(1, n, dtype=np.int64)
    return Graph(n, np.concatenate([src, dst]), np.concatenate([dst, src]))


# ----------------------------------------------- bucketed tile edge coverage
def _assert_buckets_partition_edges(g):
    part = DevicePartition.from_graph(g)
    bucket_id = np.asarray(part.bucket_id)
    deg = np.diff(np.asarray(part.csr_indptr))
    # degree-0 slots are in NO bucket (they can never emit a message)
    np.testing.assert_array_equal(bucket_id == -1, deg == 0)
    seen = set()
    for b, (size, max_deg) in enumerate(zip(part.bucket_sizes,
                                            part.bucket_max_deg)):
        members = np.flatnonzero(bucket_id == b)
        assert members.shape[0] == size
        if size == 0:
            continue
        eid, valid = gather_frontier_edge_tile(
            part, jnp.asarray(members, jnp.int32), size, max_deg)
        eids = np.asarray(eid)[np.asarray(valid)]
        fresh = set(eids.tolist())
        assert len(fresh) == eids.shape[0], "duplicate eid within a bucket"
        assert not (seen & fresh), "eid claimed by two buckets"
        seen |= fresh
    assert seen == set(range(g.num_edges))


def test_bucketed_gather_partitions_edges_star():
    _assert_buckets_partition_edges(_star_graph(300))


if HAVE_HYPOTHESIS:

    @settings(max_examples=15, deadline=None)
    @given(scale=st.integers(5, 8), edge_factor=st.integers(2, 8),
           seed=st.integers(0, 999))
    def test_bucketed_gather_partitions_edges(scale, edge_factor, seed):
        """Per-bucket eid sets partition range(E): every real edge is
        gathered by EXACTLY ONE bucket's tile when that bucket's full
        membership is on the frontier."""
        g = rmat_edges(scale=scale, edge_factor=edge_factor, seed=seed).dedup()
        _assert_buckets_partition_edges(g)


# --------------------------------------------------- overflow / star graphs
def test_star_graph_overflow_falls_back_to_dense():
    """Hub activates EVERY leaf in one superstep — the leaf bucket's live
    frontier (V-1 vertices) overflows any small capacity.  The per-bucket
    guard must degrade that bucket to its restricted dense scan instead of
    silently dropping vertices."""
    n = 257
    part = DevicePartition.from_graph(_star_graph(n))
    depth = _run(algorithms.bfs_program(), part, source=0,
                 frontier="compact", cap=8, max_steps=10)
    want = np.concatenate([[0.0], np.ones(n - 1, np.float32)])
    np.testing.assert_array_equal(depth, want)


def test_bucket_overflow_mixed_branches():
    """One bucket exceeds its cap while the others stay compact: the
    overflowing bucket's partial ⊕ comes from the bucket-restricted dense
    scan, the rest from their tiles — the total must equal the dense scan
    bitwise (min monoid)."""
    n = 300  # hub deg 299 -> bucket 2 (<=512); 299 leaves deg 1 -> bucket 0
    part = DevicePartition.from_graph(_star_graph(n))
    prog = algorithms.bfs_program()
    caps = bucket_caps(part.bucket_sizes, 8)
    # the scenario really exercises BOTH branches: leaves overflow, hub fits
    bucket_id = np.asarray(part.bucket_id)
    leaves_b = int(bucket_id[1])
    hub_b = int(bucket_id[0])
    assert leaves_b != hub_b
    assert part.bucket_sizes[leaves_b] > caps[leaves_b]
    assert part.bucket_sizes[hub_b] <= caps[hub_b]
    # every real slot live, distinct scatter values so the ⊕ is nontrivial
    eng = GREEngine(prog, frontier="dense")
    st0 = eng.init_state(part)
    state = EngineState(
        st0.vertex_data,
        st0.scatter_data.at[:n].set(jnp.arange(n, dtype=jnp.float32)),
        jnp.zeros(part.num_slots, dtype=bool).at[:n].set(True),
        st0.step)
    dense = eng.dense_scatter_combine(part, state, part.num_slots)
    bucketed = bucketed_scatter_combine(prog, part, state, part.num_slots,
                                        caps)
    np.testing.assert_array_equal(np.asarray(bucketed), np.asarray(dense))


def test_compact_cond_branches_per_superstep():
    """On a circulant graph with cap < frontier for SSSP but not BFS, both
    still match dense exactly (per-superstep cond, not per-run)."""
    g = circulant_graph(512, degree=8, weights=True, seed=1)
    part = DevicePartition.from_graph(g)
    _assert_strategies_agree(algorithms.sssp_program(), part, source=3,
                             cap=16)


# --------------------------------------------------- static plan resolution
def test_bucketed_plan_replaces_hub_gate_on_power_law():
    """The old static `cap * max_deg >= E` gate forced power-law graphs
    dense (one hub poisons the single tile's `max_deg`); bucketed tiles
    bound the worst case by `sum_b cap_b * max_deg_b`, so auto now
    compiles the compacted path on the SAME graph where the flat bound
    still gates."""
    from repro.graph.generators import barabasi_albert_graph
    g = barabasi_albert_graph(4096, m=8, seed=3).dedup()
    part = DevicePartition.from_graph(g)
    prog = algorithms.bfs_program()
    cap = default_cap(part.num_slots)
    # the flat single-tile bound is pathological: hub degree x cap >= E ...
    assert cap * part.csr_max_deg >= part.src.shape[0]
    assert GREEngine(prog, frontier="flat")._frontier_plan(part) == \
        ("flat", cap)  # forced flat skips the gate (overflow guard covers)
    # ... but the bucketed bound stays well under the dense scan
    plan = GREEngine(prog, frontier="auto")._frontier_plan(part)
    assert plan is not None and plan[0] == "bucketed"
    worst = sum(c * d for c, d in zip(plan[1], part.bucket_max_deg))
    assert worst < part.src.shape[0]
    depth = _run(prog, part, source=0, frontier="auto")
    np.testing.assert_array_equal(depth, _run(prog, part, source=0,
                                              frontier="dense"))


def test_degenerate_tiny_graph_stays_dense():
    """A directed star so small that even full bucket tiles out-scan the
    dense path: auto must compile the dense branch only (and be correct)."""
    n = 64
    src = np.zeros(n - 1, dtype=np.int64)
    dst = np.arange(1, n, dtype=np.int64)
    part = DevicePartition.from_graph(Graph(n, src, dst))
    eng = GREEngine(algorithms.bfs_program(), frontier="auto")
    assert eng._frontier_plan(part) is None
    depth = _run(algorithms.bfs_program(), part, source=0, frontier="auto")
    want = np.concatenate([[0.0], np.ones(n - 1, np.float32)])
    np.testing.assert_array_equal(depth, want)


# ----------------------------------------------------- capacity calibration
def test_calibrated_cap_tracks_live_frontier():
    """`default_cap` from the live first-superstep histogram: BFS from a
    LEAF of a large star sees frontiers of size 1, so the calibrated cap
    must be far below the fixed `num_slots/16` fraction (which
    over-allocates on large shards) — and the run stays exact even when
    the hub later floods every leaf past the calibrated cap."""
    n = 4097
    part = DevicePartition.from_graph(_star_graph(n))
    prog = algorithms.bfs_program()
    eng = GREEngine(prog, frontier="compact")
    state = eng.init_state(part, source=1)     # a leaf
    hist = eng.calibrate_frontier_cap(part, state)
    assert hist == [1, 1]                      # leaf -> hub: size-1 fronts
    cap = eng.frontier_cap
    assert cap <= 16, cap                      # 4x the observed size-1 front
    assert cap < default_cap(part.num_slots)   # fixed fraction: 256
    assert eng.frontier_hist == hist           # the tuner's density facet
    out = eng.run(part, state, 10)
    want = np.full(n, 2.0, np.float32)
    want[1], want[0] = 0.0, 1.0
    np.testing.assert_array_equal(np.asarray(out.vertex_data), want)


def test_default_cap_histogram_and_fallback():
    assert default_cap(4096) == 256            # fixed-fraction fallback
    assert default_cap(4096, frontier_hist=[1, 3]) == 16   # 4*3 -> round 8
    assert default_cap(64, frontier_hist=[200]) == 64      # clamped to slots


# ------------------------------------------------------------ multi-source
@pytest.mark.parametrize("maker,weights", [
    (algorithms.bfs_program, False),
    (algorithms.sssp_program, True),
])
def test_multi_source_matches_independent_runs(maker, weights):
    g = rmat_edges(scale=7, edge_factor=8, seed=6, weights=True).dedup()
    part = DevicePartition.from_graph(g)
    sources = [0, 3, 17, 42]
    batched = _run(maker(num_sources=len(sources)), part, source=sources)
    singles = np.stack([_run(maker(), part, source=s) for s in sources],
                       axis=1)
    np.testing.assert_array_equal(batched, singles)


def test_multi_source_bfs_compact_matches_dense():
    g = rmat_edges(scale=7, edge_factor=8, seed=7).dedup()
    part = DevicePartition.from_graph(g)
    prog = algorithms.bfs_program(num_sources=3)
    _assert_strategies_agree(prog, part, source=[1, 2, 3], cap=32)


def test_multi_source_repeated_and_isolated_roots():
    """Duplicate roots give identical lanes; a sink-only root's lane stays
    inf everywhere but at the root itself."""
    g = rmat_edges(scale=6, edge_factor=4, seed=8).dedup()
    # vertex with no out-edges (if none exists, add an isolated one)
    outdeg = g.out_degree()
    sinks = np.flatnonzero(outdeg == 0)
    sink = int(sinks[0]) if sinks.size else g.num_vertices - 1
    part = DevicePartition.from_graph(g)
    sources = [0, 0, sink]
    out = _run(algorithms.bfs_program(num_sources=3), part, source=sources)
    np.testing.assert_array_equal(out[:, 0], out[:, 1])
    reach = np.flatnonzero(~np.isinf(out[:, 2]))
    assert sink in reach


# ----------------------------------------------------- multistage payloads
def test_bc_stages_compact_matches_dense_to_float_tolerance():
    """Sum-monoid stages through the compacted path: Brandes forward σ
    (halting) and backward δ (iterative but level-synchronous with
    dense_frontier=False) must match the dense strategy to float tolerance
    (the segment reduction reorders sum, unlike min/max)."""
    import dataclasses
    from repro.core.multistage import bc_backward_program, bc_forward_program

    g = circulant_graph(256, degree=4)
    D = 3
    sources = jnp.array([0, 11, 57], jnp.int32)
    lanes = jnp.arange(D)
    fwd_part = DevicePartition.from_graph(g)
    bwd_part = DevicePartition.from_graph(g, transpose=True)
    results = {}
    for strategy in ("dense", "compact"):
        fwd = GREEngine(bc_forward_program(D), frontier=strategy)
        bwd = GREEngine(bc_backward_program(D), dense_frontier=False,
                        frontier=strategy)
        assert (fwd._frontier_plan(fwd_part) is not None) == \
            (strategy == "compact")
        st = fwd.init_state(fwd_part)
        st = EngineState(
            st.vertex_data.at[sources, lanes].set(
                jnp.array([0.0, 1.0], jnp.float32)),
            st.scatter_data.at[sources, lanes].set(
                jnp.array([1.0, 1.0, 1.0], jnp.float32)),
            jnp.zeros(fwd_part.num_slots, dtype=bool).at[sources].set(True),
            st.step)
        out = fwd.run(fwd_part, st, 100)
        depth, sigma = out.vertex_data[..., 0], out.vertex_data[..., 1]
        dmax = jnp.max(jnp.where(jnp.isinf(depth), -1.0, depth))
        part_b = dataclasses.replace(
            bwd_part, aux={**bwd_part.aux, "depth": depth, "sigma": sigma,
                           "dmax": dmax})
        delta = bwd.run(part_b, bwd.init_state(part_b), 101).vertex_data
        results[strategy] = (np.asarray(out.vertex_data), np.asarray(delta))
    fix = lambda x: np.nan_to_num(x, posinf=1e30)
    np.testing.assert_allclose(fix(results["dense"][0]),
                               fix(results["compact"][0]),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(results["dense"][1], results["compact"][1],
                               rtol=1e-5, atol=1e-5)


def test_bc_batched_lanes_match_per_source_pipeline():
    """Payload-batched Brandes == per-source runs of the same programs."""
    from repro.core.multistage import betweenness_centrality
    import networkx as nx
    g = rmat_edges(scale=6, edge_factor=4, seed=9).dedup()
    nxg = nx.DiGraph()
    nxg.add_nodes_from(range(g.num_vertices))
    nxg.add_edges_from(zip(g.src.tolist(), g.dst.tolist()))
    want = nx.betweenness_centrality(nxg, normalized=False)
    ref = np.array([want[i] for i in range(g.num_vertices)])
    # batch smaller than |V| forces multiple payload batches + ragged tail
    got = betweenness_centrality(g, batch=24)
    np.testing.assert_allclose(got, ref, rtol=1e-4, atol=1e-4)


# ------------------------------------------------------- pallas tile combine
@pytest.mark.parametrize("dynamic", [True, False],
                         ids=["dynamic-table", "full-table"])
def test_bucketed_pallas_tile_combine_matches_xla(dynamic):
    """use_pallas routes the bucketed tiles through the Pallas tile combine
    (interpret mode on CPU) — by default over the on-device
    `dynamic_block_table` pruning pass, with the degenerate full table as
    the `dynamic_table=False` fallback: bitwise vs the dense reference for
    the min monoid either way."""
    g = rmat_edges(scale=6, edge_factor=8, seed=11, weights=True).dedup()
    part = DevicePartition.from_graph(g)
    dense = _run(algorithms.sssp_program(), part, source=0, frontier="dense")
    eng = GREEngine(algorithms.sssp_program(), frontier="compact",
                    use_pallas=True, dynamic_table=dynamic)
    out = eng.run(part, eng.init_state(part, source=0), 300)
    np.testing.assert_array_equal(np.asarray(out.vertex_data), dense)
