"""Single-shard Scatter-Combine engine vs exact oracles (networkx/numpy)."""
import dataclasses
import re

import networkx as nx
import numpy as np
import pytest

import jax.numpy as jnp

from repro.core import algorithms
from repro.core.engine import (DevicePartition, GREEngine,
                               resolve_combine_route)
from repro.core.plan import KernelPlan
from repro.graph.generators import ring_graph, rmat_edges
from repro.graph.structures import EdgeDelta
from repro.kernels.segment_combine import BLOCK_V


@pytest.fixture(scope="module")
def graph():
    return rmat_edges(scale=8, edge_factor=8, seed=1, weights=True).dedup()


@pytest.fixture(scope="module")
def nxg(graph):
    g = nx.DiGraph()
    g.add_nodes_from(range(graph.num_vertices))
    for s, d, w in zip(graph.src, graph.dst, graph.edge_props["weight"]):
        g.add_edge(int(s), int(d), weight=float(w))
    return g


def test_pagerank_matches_paper_formula(graph):
    """GRE's PageRank is the fixed point of Eq. 2 (non-normalized form)."""
    part = DevicePartition.from_graph(graph)
    eng = GREEngine(algorithms.pagerank_program())
    out = eng.run(part, eng.init_state(part), max_steps=50)
    pr = np.asarray(out.vertex_data)

    prv = np.ones(graph.num_vertices, np.float32)
    outdeg = np.maximum(graph.out_degree(), 1).astype(np.float32)
    for _ in range(50):
        s = np.zeros(graph.num_vertices, np.float32)
        np.add.at(s, graph.dst, (prv / outdeg)[graph.src])
        prv = 0.15 + 0.85 * s
    np.testing.assert_allclose(pr, prv, rtol=1e-4, atol=1e-4)


def test_sssp_matches_dijkstra(graph, nxg):
    part = DevicePartition.from_graph(graph)
    eng = GREEngine(algorithms.sssp_program())
    out = eng.run(part, eng.init_state(part, source=0), max_steps=300)
    dist = np.asarray(out.vertex_data)
    ref = np.full(graph.num_vertices, np.inf)
    for v, d in nx.single_source_dijkstra_path_length(
            nxg, 0, weight="weight").items():
        ref[v] = d
    assert np.array_equal(np.isinf(ref), np.isinf(dist))
    mask = ~np.isinf(ref)
    np.testing.assert_allclose(dist[mask], ref[mask], rtol=1e-6)


def test_sssp_halts_before_max_steps(graph):
    part = DevicePartition.from_graph(graph)
    eng = GREEngine(algorithms.sssp_program())
    out = eng.run(part, eng.init_state(part, source=0), max_steps=10_000)
    assert int(out.step) < 10_000  # assert_to_halt terminated the BSP loop


def test_cc_matches_networkx(graph, nxg):
    gu = graph.as_undirected()
    part = DevicePartition.from_graph(gu)
    eng = GREEngine(algorithms.cc_program())
    out = eng.run(part, eng.init_state(part), max_steps=500)
    label = np.asarray(out.vertex_data).astype(np.int64)
    for comp in nx.connected_components(nxg.to_undirected()):
        labels = {label[v] for v in comp}
        assert labels == {min(comp)}


def test_bfs_matches_networkx(graph, nxg):
    part = DevicePartition.from_graph(graph)
    eng = GREEngine(algorithms.bfs_program())
    out = eng.run(part, eng.init_state(part, source=0), max_steps=200)
    depth = np.asarray(out.vertex_data)
    ref = np.full(graph.num_vertices, np.inf)
    for v, d in nx.single_source_shortest_path_length(nxg, 0).items():
        ref[v] = d
    assert np.array_equal(np.where(np.isinf(ref), -1, ref),
                          np.where(np.isinf(depth), -1, depth))


def test_gas_equals_scatter_combine(graph):
    """Paper §2.2: the fused one-sided path computes the same result as the
    two-phase GAS emulation with intermediate edge storage."""
    part = DevicePartition.from_graph(graph)
    eng = GREEngine(algorithms.pagerank_program())
    st_sc = eng.init_state(part)
    st_gas = eng.init_state(part)
    edge_state = jnp.zeros(part.src.shape[0], jnp.float32)
    for _ in range(5):
        st_sc = eng.superstep(part, st_sc)
        (st_gas, edge_state) = eng.gas_superstep(part, st_gas, edge_state)
    np.testing.assert_allclose(np.asarray(st_sc.vertex_data),
                               np.asarray(st_gas.vertex_data), rtol=1e-6)


def test_degree_program(graph):
    part = DevicePartition.from_graph(graph)
    eng = GREEngine(algorithms.degree_program())
    st = eng.superstep(part, eng.init_state(part))
    np.testing.assert_array_equal(np.asarray(st.vertex_data),
                                  graph.in_degree().astype(np.float32))


def test_ring_sssp_exact_steps():
    """On a directed ring the frontier advances one vertex per superstep."""
    g = ring_graph(16, weights=True)
    part = DevicePartition.from_graph(g)
    eng = GREEngine(algorithms.sssp_program())
    out = eng.run(part, eng.init_state(part, source=0), max_steps=100)
    np.testing.assert_allclose(np.asarray(out.vertex_data),
                               np.arange(16, dtype=np.float32))


def test_engine_with_pallas_kernel_matches_xla(graph):
    """The Pallas segment_combine kernel (interpret mode) slots into the
    engine via use_pallas and reproduces the XLA path exactly."""
    part = DevicePartition.from_graph(graph)
    eng_x = GREEngine(algorithms.pagerank_program(), use_pallas=False)
    eng_p = GREEngine(algorithms.pagerank_program(), use_pallas=True)
    st_x = eng_x.init_state(part)
    st_p = eng_p.init_state(part)
    for _ in range(3):
        st_x = eng_x.superstep(part, st_x)
        st_p = eng_p.superstep(part, st_p)
    np.testing.assert_allclose(np.asarray(st_x.vertex_data),
                               np.asarray(st_p.vertex_data),
                               rtol=1e-5, atol=1e-5)


def _delta_part(graph):
    """The graph's partition after a delta that adds two edges."""
    part = DevicePartition.from_graph(graph, edge_slack=8)
    delta = EdgeDelta(add_src=[0, 1], add_dst=[2, 3],
                      add_props={"weight": np.ones(2, np.float32)})
    return part.apply_edge_delta(delta)[0]


# program, partition, segment space past the slots, source, route
ROUTE_CASES = {
    "pagerank": (algorithms.pagerank_program, DevicePartition.from_graph,
                 0, None, "pallas"),
    "cc": (algorithms.cc_program, DevicePartition.from_graph, 0, None,
           "pallas"),
    "max-monoid": (lambda: dataclasses.replace(
        algorithms.sssp_program(), monoid=algorithms.MONOIDS["max"]),
        DevicePartition.from_graph, 0, 0, "pallas"),
    "after-delta": (algorithms.pagerank_program, _delta_part, 0, None,
                    "pallas"),
    "d16-lanes": (lambda: algorithms.bfs_program(num_sources=16),
                  DevicePartition.from_graph, 0, list(range(16)), "xla"),
    "int-payload": (lambda: dataclasses.replace(
        algorithms.degree_program(), msg_dtype=jnp.int32),
        DevicePartition.from_graph, 0, None, "xla"),
    "unsorted": (algorithms.pagerank_program,
                 lambda g: DevicePartition.from_graph(g, sort_by_dst=False),
                 0, None, "xla"),
    "other-segment-space": (algorithms.pagerank_program,
                            DevicePartition.from_graph, 2 * BLOCK_V, None,
                            "xla"),
}


@pytest.mark.parametrize("case", list(ROUTE_CASES))
def test_dense_combine_route_resolution(graph, case):
    """The default engine's dense combine takes the Pallas kernel only for
    dst-sorted edges whose block table spans the call's segment space, a
    scalar float32 payload and a sum/min/max monoid; everything else
    resolves to XLA without raising.  The compacted tiles keep XLA, and an
    explicit `use_pallas` forces its route.  On the CPU both routes lower
    the XLA scatter-reduce, so the default engine's combine equals the
    forced XLA engine's bitwise."""
    make_program, make_part, extra, source, want = ROUTE_CASES[case]
    program, part = make_program(), make_part(graph)
    nseg = part.num_slots + extra
    assert resolve_combine_route(program, part, nseg) == want
    eng = GREEngine(program)
    assert eng.combine_route(part, nseg) == want
    assert eng.make_plan().kernel == KernelPlan(use_pallas=None)
    assert GREEngine(program, use_pallas=True).combine_route(
        part, nseg) == "pallas"
    assert GREEngine(program, use_pallas=False).combine_route(
        part, nseg) == "xla"
    xla = GREEngine(program, use_pallas=False)
    got, ref = (e.dense_scatter_combine(part, e.init_state(part, source),
                                        nseg) for e in (eng, xla))
    assert got.shape == (nseg,) + tuple(program.payload_shape)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))


@pytest.mark.parametrize("name", ["pagerank", "cc"])
def test_default_engine_lowers_no_kernel_on_cpu(graph, name):
    """Lowered for the CPU, the default engine's run holds the forced XLA
    engine's instructions and nothing more (the kernel is lowered only for
    a TPU, so no interpreted kernel slows the CPU path); the block table
    enters as one more, unused, parameter."""
    part = DevicePartition.from_graph(graph)
    program = getattr(algorithms, f"{name}_program")()
    ops = []
    for use_pallas in (None, False):
        eng = GREEngine(program, use_pallas=use_pallas)
        hlo = GREEngine.run.lower(eng, part, eng.init_state(part),
                                  30).compile().as_text()
        ops.append(sorted(re.findall(r"= \S+ ([\w-]+)\(", hlo)))
    assert ops[0].count("parameter") == ops[1].count("parameter") + 1
    strip = lambda o: [op for op in o if op != "parameter"]
    assert strip(ops[0]) == strip(ops[1])
