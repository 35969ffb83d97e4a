"""Pipelined exchange == synchronous exchange, on every benchmark program.

Equivalence contract (docs/exchange.md): the pipelined schedule defers the
merge of remote ⊕ partials to the top of the next superstep but folds the
SAME partials — min-monoid traversal (BFS/SSSP/CC) must be BITWISE
identical to the synchronous backends and the single-shard engine;
sum-monoid (PageRank) agrees to a stated float tolerance across backends,
the synchronous AgentExchange included (the two-stage ⊕ and the segment
sums over differently sized spaces may reorder float adds).

The in-process tests run the full pipelined machinery — `split_edge_tiles`,
`PipelinedAgentExchange`, the plan executor's deferred-merge loop
(`repro.core.plan.execute_plan`) under `shard_map` — on a 1-device mesh
(remote tile empty, flush collective degenerate).  The
multi-shard case needs the 8-device XLA_FLAGS set before jax initializes,
so it runs in a subprocess (slow suite), exercising real cross-shard
flushes and multi-source vector payloads; pipelined x frontier-strategy
rows live in the `tests/test_conformance.py` matrix.
"""
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

import jax

from repro.core import algorithms
from repro.core.agent_graph import build_agent_graph, split_edge_tiles
from repro.core.dist_engine import DistGREEngine
from repro.core.engine import DevicePartition, GREEngine
from repro.core.partition import greedy_partition, hash_partition
from repro.graph.generators import rmat_edges

SRC = str(Path(__file__).resolve().parent.parent / "src")


def _single_shard(program, g, source=None, max_steps=300):
    part = DevicePartition.from_graph(g)
    eng = GREEngine(program)
    st = eng.run(part, eng.init_state(part, source=source), max_steps)
    return np.asarray(st.vertex_data)


def _pipelined(program, g, source=None, max_steps=300, **kw):
    ag = build_agent_graph(g, greedy_partition(g, 1, batch_size=64), 1)
    mesh = jax.make_mesh((1,), ("graph",))
    eng = DistGREEngine(program, mesh, ("graph",), exchange="pipelined", **kw)
    out, _ = eng.run(ag, source=source, max_steps=max_steps)
    return out


def _fix(x):
    return np.nan_to_num(x, posinf=-1.0)


# --------------------------------------------------------- edge-tile split
def test_split_edge_tiles_partitions_every_real_edge():
    """Remote + local tiles cover the edge shard exactly once, destinations
    relabeled into the compact combiner/master spaces."""
    g = rmat_edges(scale=7, edge_factor=8, seed=2).dedup()
    k = 4
    ag = build_agent_graph(g, hash_partition(g, k), k)
    split = split_edge_tiles(ag)
    remote, local = split.remote, split.local
    for i in range(k):
        n_r = int(remote.mask[i].sum())
        n_l = int(local.mask[i].sum())
        assert n_r + n_l == int(ag.edge_mask[i].sum())
        assert (remote.dst[i][remote.mask[i]] < ag.c_pad).all()
        assert (local.dst[i][local.mask[i]] < ag.cap).all()
        # padding lands on each tile's identity slot
        assert (remote.dst[i][~remote.mask[i]] == ag.c_pad).all()
        assert (local.dst[i][~local.mask[i]] == ag.cap).all()
        # tiles keep the canonical dst-sorted order (bitwise-sum contract)
        assert (np.diff(remote.dst[i]) >= 0).all()
        assert (np.diff(local.dst[i]) >= 0).all()
    assert 0.0 < split.remote_fraction < 1.0


def test_split_remote_fraction_matches_partition_quality():
    """With a shared owner vector (build_agent_graph additionally rebalances
    overflowing partitions), the ingress split's remote fraction IS the
    partition-quality metric."""
    from repro.core.partition import (assign_owners, partition_quality,
                                     rebalance_owners)
    g = rmat_edges(scale=7, edge_factor=8, seed=3).dedup()
    k = 4
    edge_part = hash_partition(g, k)
    cap = -(-g.num_vertices // k)          # masters per partition,
    cap = -(-cap // 8) * 8                 # padded as in build_agent_graph
    owner = rebalance_owners(assign_owners(g, edge_part, k), k, cap)
    ag = build_agent_graph(g, edge_part, k, owner=owner)
    split = split_edge_tiles(ag)
    q = partition_quality(g, edge_part, owner=owner, k=k)
    assert split.remote_fraction == pytest.approx(
        q.remote_dst_edge_fraction, abs=1e-9)


# ----------------------------------------- pipelined vs single-shard (k=1)
def test_sssp_pipelined_bitwise():
    g = rmat_edges(scale=7, edge_factor=8, seed=4, weights=True).dedup()
    ref = _single_shard(algorithms.sssp_program(), g, source=0)
    got = _pipelined(algorithms.sssp_program(), g, source=0)
    np.testing.assert_array_equal(_fix(got), _fix(ref))


def test_cc_pipelined_bitwise():
    g = rmat_edges(scale=6, edge_factor=8, seed=5).dedup().as_undirected()
    ref = _single_shard(algorithms.cc_program(), g)
    got = _pipelined(algorithms.cc_program(), g)
    np.testing.assert_array_equal(got, ref)


def test_pagerank_pipelined_close():
    g = rmat_edges(scale=7, edge_factor=8, seed=6).dedup()
    ref = _single_shard(algorithms.pagerank_program(), g, max_steps=20)
    got = _pipelined(algorithms.pagerank_program(), g, max_steps=20)
    np.testing.assert_allclose(got, ref, rtol=1e-5, atol=1e-6)


def test_bfs_multi_source_pipelined_bitwise():
    g = rmat_edges(scale=6, edge_factor=8, seed=7).dedup()
    sources = [0, 5, 17]
    ref = np.stack([_single_shard(algorithms.bfs_program(), g, source=s)
                    for s in sources], axis=1)
    got = _pipelined(algorithms.bfs_program(num_sources=3), g,
                     source=sources)
    np.testing.assert_array_equal(_fix(got), _fix(ref))


# Pipelined x frontier-strategy equivalence (incl. the compacted gather on
# the split tiles and random power-law sweeps) lives in the systematic
# matrix of tests/test_conformance.py.


# ------------------------------------------------- multi-shard (subprocess)
SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import sys
sys.path.insert(0, "__SRC__")
import numpy as np
import jax

from repro.graph.generators import rmat_edges
from repro.core.engine import GREEngine, DevicePartition
from repro.core.partition import hash_partition
from repro.core.agent_graph import build_agent_graph, split_edge_tiles
from repro.core.dist_engine import DistGREEngine
from repro.core import algorithms

k = 8
g = rmat_edges(scale=8, edge_factor=8, seed=5, weights=True).dedup()
# hash partition: high remote-edge fraction, the pipelined flush's regime
edge_part = hash_partition(g, k)
ag = build_agent_graph(g, edge_part, k)
assert split_edge_tiles(ag).remote_fraction > 0.3
mesh = jax.make_mesh((8,), ("graph",))
sp = DevicePartition.from_graph(g)

failures = []

def sync_vs_pipelined(program, agraph, source=None, max_steps=300, **kw):
    outs = {}
    for mode in ("agent", "pipelined"):
        eng = DistGREEngine(program, mesh, ("graph",), exchange=mode, **kw)
        outs[mode], _ = eng.run(agraph, source=source, max_steps=max_steps)
    return outs["agent"], outs["pipelined"]

fix = lambda x: np.nan_to_num(x, posinf=-1.0)

# SSSP: bitwise across sync/pipelined AND vs the single-shard engine.
se = GREEngine(algorithms.sssp_program())
ref = np.asarray(se.run(sp, se.init_state(sp, source=0), 300).vertex_data)
sync, pipe = sync_vs_pipelined(algorithms.sssp_program(), ag, source=0)
if not np.array_equal(fix(pipe), fix(sync)):
    failures.append("sssp pipelined != sync agent")
if not np.array_equal(fix(pipe), fix(ref)):
    failures.append("sssp pipelined != single-shard")

# (compact-frontier x pipelined rows live in test_conformance.py's matrix)

# PageRank (sum monoid): within float tolerance of the sync agent and of
# the single shard.  The backends fold the same partials, but XLA's segment
# sums over the full slot space and over the compact tile spaces may
# associate the adds differently (observed: <= 1 ulp apart, both equally
# close to a float64 run of the recurrence).
pe = GREEngine(algorithms.pagerank_program())
pref = np.asarray(pe.run(sp, pe.init_state(sp), 20).vertex_data)
sync, pipe = sync_vs_pipelined(algorithms.pagerank_program(), ag,
                               max_steps=20)
if not np.allclose(pipe, sync, rtol=1e-6, atol=1e-6):
    failures.append("pagerank pipelined != sync agent (tolerance)")
if not np.allclose(pipe, pref, rtol=1e-5, atol=1e-6):
    failures.append("pagerank pipelined != single-shard (tolerance)")

# Multi-source batched BFS: (D,) payloads through the pipelined flush.
D, sources = 4, [0, 7, 33, 101]
sync, pipe = sync_vs_pipelined(algorithms.bfs_program(num_sources=D), ag,
                               source=sources, max_steps=100)
if not np.array_equal(fix(pipe), fix(sync)):
    failures.append("bfs multi-source pipelined != sync agent")

# CC on the undirected graph.
gu = g.as_undirected().dedup()
agu = build_agent_graph(gu, hash_partition(gu, k), k)
spu = DevicePartition.from_graph(gu)
ce = GREEngine(algorithms.cc_program())
cref = np.asarray(ce.run(spu, ce.init_state(spu), 300).vertex_data)
sync, pipe = sync_vs_pipelined(algorithms.cc_program(), agu)
if not np.array_equal(pipe, sync) or not np.array_equal(pipe, cref):
    failures.append("cc pipelined mismatch")

assert not failures, failures
print("PIPELINE_OK")
"""


@pytest.mark.slow
def test_pipelined_multi_shard_agrees(tmp_path):
    script = tmp_path / "pipeline_check.py"
    script.write_text(SCRIPT.replace("__SRC__", SRC))
    proc = subprocess.run([sys.executable, str(script)], capture_output=True,
                          text=True, timeout=900)
    assert proc.returncode == 0, proc.stderr[-4000:]
    assert "PIPELINE_OK" in proc.stdout
