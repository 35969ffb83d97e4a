"""The engine's own instrumentation: the per-superstep work counters
(`EngineState.counters`) and the ingress spans (`repro.spans`).

The counters are checked row by row against a numpy recount of each
superstep's active set, on every frontier route; the route a recount
charges is re-derived here from the plan's static capacities, so a count
that disagreed with the branch that ran would show.
"""
import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import spans
from repro.core import algorithms
from repro.core.engine import DevicePartition, GREEngine
from repro.core.frontier import COUNTERS
from repro.graph.generators import rmat_edges
from repro.graph.structures import Graph

ROWS = 40
# frontier capacities on `cc_part`: the early supersteps take the dense
# scan and the later ones compact, with every bucket fitting its share, or
# with one bucket overflowing while the whole frontier fits
CAP_FITS, CAP_OVERFLOWS, CAP_FLAT = 160, 256, 64


@pytest.fixture(scope="module")
def cc_part():
    g = rmat_edges(scale=9, edge_factor=8, seed=5).dedup()
    src = np.concatenate([g.src, g.dst])
    dst = np.concatenate([g.dst, g.src])
    return DevicePartition.from_graph(
        Graph(g.num_vertices, src, dst).dedup())


def active_sets(engine, part, state, max_steps):
    """Each superstep's frontier, from single jitted supersteps."""
    step = jax.jit(lambda s: engine.superstep(part, s))
    sets = []
    while len(sets) < max_steps and bool(jnp.any(state.active_scatter)):
        sets.append(np.asarray(state.active_scatter))
        state = step(state)
    return sets


def recount(part, plan, active) -> tuple:
    """(active vertices, active out-edges, edges scanned) of one superstep,
    in numpy, and the route: "dense", "compact", or "overflow" when the
    frontier compacts but a bucket overflows its capacity."""
    src = np.asarray(part.src)
    mask = np.asarray(part.edge_mask)
    deg = np.bincount(src[mask], minlength=part.num_slots)
    e_pad = src.shape[0]
    row = [int(active.sum()), int(deg[active].sum())]
    if plan is None:
        return row + [e_pad], "dense"
    kind, caps = plan
    if active.sum() > (caps if kind == "flat" else sum(caps)):
        return row + [e_pad], "dense"
    if kind == "flat":
        return row + [caps * part.csr_max_deg], "compact"
    bucket = np.asarray(part.bucket_id)
    scanned, route = 0, "compact"
    for b, (cap_b, max_deg_b) in enumerate(zip(caps, part.bucket_max_deg)):
        if cap_b <= 0 or max_deg_b <= 0:
            continue
        if (active & (bucket == b)).sum() <= cap_b:
            scanned += cap_b * max_deg_b
        else:
            scanned, route = scanned + e_pad, "overflow"
    return row + [scanned], route


@pytest.mark.parametrize("frontier,cap,kind,routes", [
    ("dense", None, None, {"dense"}),
    ("compact", CAP_FITS, "bucketed", {"dense", "compact"}),
    ("compact", CAP_OVERFLOWS, "bucketed", {"dense", "compact", "overflow"}),
    ("flat", CAP_FLAT, "flat", {"dense", "compact"}),
])
def test_counters_match_a_recount_on_every_route(cc_part, frontier, cap,
                                                 kind, routes):
    part = cc_part
    engine = GREEngine(algorithms.cc_program(), frontier=frontier,
                       frontier_cap=cap)
    plan = engine._frontier_plan(part)
    out = engine.run(part, engine.init_state(part, counters=ROWS), ROWS)
    steps = int(out.step)
    sets = active_sets(engine, part, engine.init_state(part), ROWS)
    assert len(sets) == steps > 2
    got = np.asarray(out.counters)
    assert got.shape == (ROWS, len(COUNTERS)) and got.dtype == np.int32
    want, taken = zip(*(recount(part, plan, a) for a in sets))
    np.testing.assert_array_equal(got[:steps], np.array(want))
    assert not got[steps:].any()
    # the parameters drive the run down each route the case names
    assert (plan and plan.kind) == kind
    assert set(taken) == routes


def test_counters_leave_the_run_and_the_state_as_they_were(cc_part):
    part = cc_part
    engine = GREEngine(algorithms.cc_program())
    plain = engine.init_state(part)
    counted = engine.init_state(part, counters=ROWS)
    assert plain.counters is None
    assert len(jax.tree.leaves(plain)) == 4
    for a, b in zip(jax.tree.leaves(plain), jax.tree.leaves(counted)[:4]):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    out, out_c = (engine.run(part, s, ROWS) for s in (plain, counted))
    assert out.counters is None
    for name in ("vertex_data", "scatter_data", "active_scatter", "step"):
        np.testing.assert_array_equal(np.asarray(getattr(out, name)),
                                      np.asarray(getattr(out_c, name)))


def test_counters_need_rows_for_every_superstep_and_a_csr(cc_part):
    engine = GREEngine(algorithms.cc_program())
    with pytest.raises(ValueError, match="rows"):
        engine.run(cc_part, engine.init_state(cc_part, counters=5), 6)
    no_csr = dataclasses.replace(cc_part, csr_indptr=None)
    with pytest.raises(ValueError, match="CSR"):
        engine.init_state(no_csr, counters=5)


def test_pagerank_scans_exactly_the_edges_it_needs():
    """Every vertex active and no padding: edges scanned per edge needed
    reads exactly 1."""
    g = rmat_edges(scale=9, edge_factor=8, seed=2).dedup()
    part = DevicePartition.from_graph(g)
    engine = GREEngine(algorithms.pagerank_program())
    out = engine.run(part, engine.init_state(part, counters=30), 30)
    got = np.asarray(out.counters)
    assert int(out.step) == 30 and part.src.shape[0] == g.num_edges
    np.testing.assert_array_equal(
        got, np.tile([g.num_vertices, g.num_edges, g.num_edges], (30, 1)))
    assert got[:, 2].sum() / got[:, 1].sum() == 1.0


INGRESS = ["gre.ingress.sort", "gre.ingress.csr", "gre.ingress.buckets",
           "gre.ingress.block_table"]


@pytest.mark.parametrize("chunk_size", [None, 100])
def test_recording_keeps_the_four_ingress_spans(chunk_size):
    g = rmat_edges(scale=8, edge_factor=8, seed=3).dedup()
    DevicePartition.from_graph(g, chunk_size=chunk_size)   # nothing kept
    with spans.recording() as recorded:
        t0 = time.perf_counter_ns()
        DevicePartition.from_graph(g, chunk_size=chunk_size)
        t1 = time.perf_counter_ns()
    DevicePartition.from_graph(g, chunk_size=chunk_size)
    assert [name for name, _, _ in recorded] == INGRESS
    ends = [t0] + [x for _, s, e in recorded for x in (s, e)] + [t1]
    assert ends == sorted(ends)           # in order, inside the call


def test_spans_outside_recording_are_not_kept():
    with spans.recording() as outer:
        with spans.span("a"):
            pass
        with spans.recording() as inner:
            with spans.span("b"):
                pass
        with spans.span("c"):
            pass
    with spans.span("d"):
        pass
    assert [n for n, _, _ in outer] == ["a", "c"]
    assert [n for n, _, _ in inner] == ["b"]


@pytest.mark.parametrize("by_name", [False, True])
def test_partitioning_and_topology_keep_their_spans(by_name):
    """HDRF, the agent graph and the device topology are one span each, in
    order; a partitioner named to `build_agent_graph` runs before its span
    opens, so the two never nest."""
    from repro.core.agent_graph import build_agent_graph
    from repro.core.dist_engine import DistGREEngine
    from repro.core.partition_stream import hdrf_partition
    g = rmat_edges(scale=8, edge_factor=8, seed=3).dedup()
    engine = DistGREEngine(algorithms.pagerank_program(),
                           jax.make_mesh((1,), ("graph",)))
    with spans.recording() as recorded:
        t0 = time.perf_counter_ns()
        placement = "hdrf" if by_name else hdrf_partition(g, 4)
        ag = build_agent_graph(g, placement, 4)
        engine.device_topology(ag)
        t1 = time.perf_counter_ns()
    assert [name for name, _, _ in recorded] == [
        "gre.ingress.hdrf", "gre.ingress.agent_graph", "gre.ingress.topology"]
    ends = [t0] + [x for _, s, e in recorded for x in (s, e)] + [t1]
    assert ends == sorted(ends)


def test_agent_graph_counters_match_a_recount():
    """`AgentGraph.counters` against a numpy recount of the placement: an
    agent is a (partition, vertex) pair where the partition holds an
    out-edge (scatter) or an in-edge (combiner) of a vertex it does not
    own; each exchange buffer is the widest peer pair, padded to 8."""
    from repro.core.agent_graph import build_agent_graph
    from repro.core.partition_stream import hdrf_partition
    g = rmat_edges(scale=8, edge_factor=8, seed=3).dedup()
    k = 4
    part = hdrf_partition(g, k, batch_size=64)
    ag = build_agent_graph(g, part, k)
    owner = ag.old2new // ag.cap
    scat = {(int(p), int(u)) for p, u in zip(part, g.src) if owner[u] != p}
    comb = {(int(p), int(v)) for p, v in zip(part, g.dst) if owner[v] != p}
    pairs = lambda agents: np.bincount(
        [p * k + owner[v] for p, v in agents], minlength=k * k)
    pad = lambda n: -(-max(1, int(n)) // 8) * 8
    V = g.num_vertices
    assert ag.counters() == {
        "masters": V, "scatter_agents": len(scat),
        "combiner_agents": len(comb),
        "exchange_rows": k * k * (pad(pairs(scat).max())
                                  + pad(pairs(comb).max())),
        "replication_factor": (V + len(scat) + len(comb)) / V}
