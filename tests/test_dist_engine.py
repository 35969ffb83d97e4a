"""Distributed engine == single-shard engine, on 8 simulated devices.

Runs in a subprocess because the 8-device XLA_FLAGS must be set before jax
initializes (tests themselves keep the default 1-device runtime)."""
import json
import subprocess
import sys
from pathlib import Path

import pytest

SRC = str(Path(__file__).resolve().parent.parent / "src")

SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import sys
sys.path.insert(0, "__SRC__")
import numpy as np
import jax

from repro.graph.generators import rmat_edges
from repro.core.engine import GREEngine, DevicePartition
from repro.core.partition import greedy_partition
from repro.core.agent_graph import build_agent_graph
from repro.core.dist_engine import DistGREEngine
from repro.core import algorithms

g = rmat_edges(scale=8, edge_factor=8, seed=3, weights=True).dedup()
k = 8
ag = build_agent_graph(g, greedy_partition(g, k, batch_size=64), k)
mesh = jax.make_mesh((8,), ("graph",))
sp = DevicePartition.from_graph(g)

failures = []
for mode, overlap in (("agent", False), ("agent", True), ("dense", False)):
    eng = DistGREEngine(algorithms.pagerank_program(), mesh, ("graph",),
                        exchange=mode, overlap=overlap)
    pr, _ = eng.run(ag, max_steps=20)
    se = GREEngine(algorithms.pagerank_program())
    st = se.run(sp, se.init_state(sp), max_steps=20)
    if not np.allclose(pr, np.asarray(st.vertex_data), rtol=1e-4, atol=1e-4):
        failures.append(f"pagerank {mode} overlap={overlap}")

    eng = DistGREEngine(algorithms.sssp_program(), mesh, ("graph",),
                        exchange=mode, overlap=overlap)
    dist, _ = eng.run(ag, source=0, max_steps=300)
    se = GREEngine(algorithms.sssp_program())
    st = se.run(sp, se.init_state(sp, source=0), max_steps=300)
    ref = np.asarray(st.vertex_data)
    if not np.allclose(np.where(np.isinf(ref), -1, ref),
                       np.where(np.isinf(dist), -1, dist)):
        failures.append(f"sssp {mode} overlap={overlap}")

# CC on the undirected graph, agent mode
gu = g.as_undirected().dedup()
agu = build_agent_graph(gu, greedy_partition(gu, k, batch_size=64), k)
eng = DistGREEngine(algorithms.cc_program(), mesh, ("graph",))
label, _ = eng.run(agu, max_steps=300)
se = GREEngine(algorithms.cc_program())
spu = DevicePartition.from_graph(gu)
st = se.run(spu, se.init_state(spu), max_steps=300)
if not np.array_equal(label, np.asarray(st.vertex_data)):
    failures.append("cc agent")

assert not failures, failures
print("DIST_OK")
"""


@pytest.mark.slow
def test_distributed_engine_equals_single_shard(tmp_path):
    script = tmp_path / "dist_check.py"
    script.write_text(SCRIPT.replace("__SRC__", SRC))
    proc = subprocess.run([sys.executable, str(script)], capture_output=True,
                          text=True, timeout=900)
    assert proc.returncode == 0, proc.stderr[-4000:]
    assert "DIST_OK" in proc.stdout


ROUTES_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import dataclasses
import json
import sys
sys.path.insert(0, "__SRC__")
import numpy as np
import jax
import jax.numpy as jnp

from repro.core import algorithms
from repro.core.agent_graph import build_agent_graph, split_edge_tiles
from repro.core.dist_engine import DistGREEngine
from repro.core.partition_stream import hdrf_partition
from repro.core.plan import KernelPlan
from repro.graph.generators import rmat_edges

k = 4
mesh = jax.make_mesh((k,), ("graph",))
g = rmat_edges(scale=8, edge_factor=8, seed=5, weights=True).dedup()
gu = g.as_undirected().dedup()
ag = build_agent_graph(g, hdrf_partition(g, k), k)
agu = build_agent_graph(gu, hdrf_partition(gu, k), k)


def routes(eng, graph, source=None):
    # (edges, segment space, route) of every dense combine a shard traces
    seen, resolve = [], eng.local.combine_route

    def spy(part, num_segments=None):
        route = resolve(part, num_segments)
        seen.append((part.dst.shape[-1], num_segments or part.num_slots,
                     route))
        return route

    eng.local.combine_route = spy
    eng.make_run(graph, max_steps=2).lower(
        eng.device_topology(graph), eng.init_state(graph, source=source))
    return sorted(set(seen))


def shard_route(eng, graph):
    # the route shard 0's dense combine resolves over its own columns, for
    # a program whose run does not trace end to end (the integer variant
    # of the degree program: float32 state meets int32 messages)
    topo = eng.device_topology(graph)
    part = jax.tree.map(lambda a: jax.ShapeDtypeStruct(
        (a.shape[0] // k,) + a.shape[1:], a.dtype), topo).part
    return [(part.dst.shape[-1], part.num_slots,
             eng.local.combine_route(part))]


def tuned_xla(program, **kw):
    plan = DistGREEngine(program, mesh, **kw).plan
    return dict(kw, plan=dataclasses.replace(
        plan, kernel=KernelPlan(use_pallas=False)))


pr, cc = algorithms.pagerank_program(), algorithms.cc_program()
CASES = {
    "agent-pagerank": (pr, ag, None, {}),
    "agent-cc": (cc, agu, None, {}),
    "dense-pagerank": (pr, ag, None, {"exchange": "dense"}),
    "pipelined-pagerank": (pr, ag, None, {"exchange": "pipelined"}),
    "async-cc": (cc, agu, None, {"exchange": "async"}),
    "overlap": (pr, ag, None, {"overlap": True}),
    "d16-lanes": (algorithms.bfs_program(num_sources=16), ag,
                  list(range(16)), {}),
    "forced-xla": (pr, ag, None, {"use_pallas": False}),
    "tuned-xla": (pr, ag, None, tuned_xla(pr)),
}


def tiles(a):
    split = split_edge_tiles(a)
    return [[split.remote.dst.shape[1], a.c_pad + 1],
            [split.local.dst.shape[1], a.cap + 1]]


out = {"slots": [[ag.e_pad, ag.num_slots]],
       "slots_sym": [[agu.e_pad, agu.num_slots]],
       "tiles": tiles(ag), "tiles_sym": tiles(agu), "routes": {}, "same": {}}
for name, (program, graph, source, kw) in CASES.items():
    out["routes"][name] = routes(DistGREEngine(program, mesh, **kw), graph,
                                 source)
out["routes"]["int-payload"] = shard_route(DistGREEngine(
    dataclasses.replace(algorithms.degree_program(), msg_dtype=jnp.int32),
    mesh), ag)
for name, program, graph in (("pagerank", pr, ag), ("cc", cc, agu)):
    got, ref = (DistGREEngine(program, mesh, use_pallas=u).run(
        graph, max_steps=30)[0] for u in (None, False))
    out["same"][name] = bool(np.array_equal(got, ref))
print("ROUTES " + json.dumps(out))
"""


@pytest.fixture(scope="module")
def shard_routes(tmp_path_factory):
    """What each case's shards resolve, traced on 4 simulated devices."""
    script = tmp_path_factory.mktemp("routes") / "routes.py"
    script.write_text(ROUTES_SCRIPT.replace("__SRC__", SRC))
    proc = subprocess.run([sys.executable, str(script)], capture_output=True,
                          text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-4000:]
    line = [ln for ln in proc.stdout.splitlines() if ln.startswith("ROUTES ")]
    return json.loads(line[-1][len("ROUTES "):])


# case -> (combines, route): "slots" is the shard's edge columns over its
# slot space, "tiles" the split's remote and local tiles over their compact
# spaces ("_sym" on the symmetrized graph CC runs on)
ROUTE_CASES = {
    "agent-pagerank": ("slots", "pallas"),
    "agent-cc": ("slots_sym", "pallas"),
    "dense-pagerank": ("slots", "pallas"),
    "pipelined-pagerank": ("tiles", "pallas"),
    "async-cc": ("tiles_sym", "pallas"),
    "overlap": ("slots", "xla"),
    "d16-lanes": ("slots", "xla"),
    "int-payload": ("slots", "xla"),
    "forced-xla": ("slots", "xla"),
    "tuned-xla": ("slots", "xla"),
}


@pytest.mark.parametrize("case", list(ROUTE_CASES))
def test_shard_combine_route_resolution(shard_routes, case):
    """`DistGREEngine` forces no route: each shard's dense combine resolves
    per call.  The sync agent, dense, pipelined and async exchanges hand it
    dst-sorted columns whose block tables span the call's segment space, so
    a scalar float32 sum or min resolves to the kernel; the overlap's
    re-pointed `dst`, D=16 lanes and an integer payload resolve to XLA, as
    do an explicit `use_pallas=False` and a tuned plan's False."""
    combines, want = ROUTE_CASES[case]
    assert shard_routes["routes"][case] == sorted(
        [e, nseg, want] for e, nseg in shard_routes[combines])


@pytest.mark.parametrize("name", ["pagerank", "cc"])
def test_default_shards_match_forced_xla_bitwise_on_cpu(shard_routes, name):
    """On the CPU the kernel route lowers XLA's scatter-reduce, so the
    default engine's `vertex_data` equals the forced-XLA engine's bit for
    bit."""
    assert shard_routes["same"][name]
