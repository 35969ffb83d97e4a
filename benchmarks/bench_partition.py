"""Paper Fig. 11 / 12 / 13: Agent-Graph partition quality, plus the
replication-aware streaming partitioner race (docs/partitioning.md).

  Fig. 11a/b — agents per vertex + equivalent edge-cut vs the random-hash
               edge-cut line, across graphs;
  Fig. 12/13 — cut-factor scaling over k=2..16 partitions for a social-like
               (balanced degrees) and a web-like (fan-in) graph, with the
               PowerGraph vertex-cut (2·mirrors/V) comparison and the
               scatter/combiner skew (12b/13b);
  §5.1      — communication: agent messages vs vertex-cut 2R.

GRE-S = exact serial stream (batch 1); GRE-P = parallel loaders (batch 256).
HDRF  = degree-aware streaming placement (`repro.core.partition_stream`):
partial-degree-weighted affinity replicates hubs first, so the combiner
cut — `remote_dst_edge_fraction`, the exchange traffic the runtime pays
per superstep — drops well below the presence-only greedy heuristic on
power-law graphs.  The parent asserts the payoff floor (`RDF_FLOOR`,
default ≥15% lower remote-dst fraction than greedy at the web-like k=16
point) and `run_dist` records the end-to-end effect: the same BFS on a
device mesh moves measurably fewer exchange bytes on the HDRF placement.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import time
from pathlib import Path

from benchmarks.common import emit, refuse_cpu_children_on_tpu
from repro.core.partition import (greedy_partition, hash_edge_cut,
                                  partition_quality)
from repro.core.partition_stream import hdrf_partition
from repro.graph.generators import rmat_edges

ROOT = Path(__file__).resolve().parent.parent

# acceptance floor: HDRF's remote-dst fraction vs greedy at the web-like
# k=16 point (observed ~0.47 hdrf vs ~0.92 greedy — a 46% drop)
RDF_FLOOR = 0.15


def graphs(scale: int = 12):
    social = rmat_edges(scale=scale, edge_factor=16, seed=0).dedup()
    web = rmat_edges(scale=scale, edge_factor=16, seed=1).dedup().reversed()
    return [("social", social), ("web", web)]


def run(scale: int = 12, ks=(4, 8, 16), rdf_floor: float = RDF_FLOOR):
    """Quality + wall-clock rows for greedy (GRE-S/GRE-P) and HDRF; the
    web-like k=16 HDRF-vs-greedy remote-dst fraction is the gate."""
    gated = {}
    for gname, g in graphs(scale):
        for k in ks:
            hline = hash_edge_cut(g, k)
            base_rdf = None
            for mode, batch in (("S", 1), ("P", 256)):
                if batch == 1 and g.num_edges > 40000 and k > 4:
                    continue  # exact stream is slow; sample one point
                t0 = time.time()
                part = greedy_partition(g, k, batch_size=batch)
                us = (time.time() - t0) * 1e6
                q = partition_quality(g, part)
                if mode == "P":
                    base_rdf = q.remote_dst_edge_fraction
                emit(f"partition_{gname}_k{k}_GRE-{mode}", us,
                     f"agents_per_vertex={q.agents_per_vertex:.3f};"
                     f"equiv_edge_cut={q.equivalent_edge_cut:.3f};"
                     f"hash_cut={hline:.3f};"
                     f"improvement={hline / max(q.equivalent_edge_cut, 1e-9):.2f}x;"
                     f"scatter_rate={q.scatter_rate:.2f};"
                     f"cut_factor={q.agents_per_vertex:.3f};"
                     f"vertexcut_factor={q.vertexcut_cut_factor:.3f};"
                     f"agent_comm={q.agent_comm};"
                     f"vertexcut_comm={q.vertexcut_comm};"
                     f"remote_dst={q.remote_dst_edge_fraction:.4f};"
                     f"repl_factor={q.replication_factor:.3f};"
                     f"balance={q.edge_balance:.3f}")
            stats = {}
            t0 = time.time()
            part = hdrf_partition(g, k, stats=stats)
            us = (time.time() - t0) * 1e6
            q = partition_quality(
                g, part, partitioner_state_bytes=stats["state_bytes"])
            rdf_drop = (1.0 - q.remote_dst_edge_fraction / max(base_rdf, 1e-9)
                        if base_rdf else 0.0)
            emit(f"partition_{gname}_k{k}_HDRF", us,
                 f"remote_dst={q.remote_dst_edge_fraction:.4f};"
                 f"repl_factor={q.replication_factor:.3f};"
                 f"agent_comm={q.agent_comm};"
                 f"balance={q.edge_balance:.3f};"
                 f"state_bytes={stats['state_bytes']};"
                 f"rdf_vs_greedy={-rdf_drop * 100:+.1f}%")
            if gname == "web" and base_rdf:
                gated[k] = (q.remote_dst_edge_fraction, base_rdf, rdf_drop)
    k_gate = max(gated) if gated else None
    if k_gate is not None:
        hdrf_rdf, greedy_rdf, drop = gated[k_gate]
        assert drop >= rdf_floor, (
            f"HDRF remote_dst_edge_fraction {hdrf_rdf:.4f} is only "
            f"{drop * 100:.1f}% below greedy's {greedy_rdf:.4f} at the "
            f"web-like k={k_gate} point (need >= {rdf_floor * 100:.0f}%)")
    return gated


DIST_CHILD = r"""
import os
os.environ["XLA_FLAGS"] = ("--xla_force_host_platform_device_count=%(k)d "
                           "--xla_cpu_multi_thread_eigen=false "
                           "intra_op_parallelism_threads=1")
os.environ.setdefault("JAX_PLATFORMS", "cpu")
import json
import time
import numpy as np
import jax

from repro.graph.generators import rmat_edges
from repro.core.agent_graph import build_agent_graph
from repro.core.dist_engine import DistGREEngine
from repro.core import algorithms

scale, k, iters = %(scale)d, %(k)d, %(iters)d
g = rmat_edges(scale=scale, edge_factor=16, seed=1).dedup().reversed()
mesh = jax.make_mesh((k,), ("graph",))

runs = {}
for name in ("greedy", "hdrf"):
    ag = build_agent_graph(g, name, k)
    # per-superstep exchange traffic of this placement: one f32 payload per
    # live combiner flush + scatter refresh message (the padded collective
    # buffers are the static upper bound the mesh actually allocates)
    msgs = int(np.sum(ag.num_combiner) + np.sum(ag.num_scatter))
    padded = 2 * k * k * (ag.c_x_pad + ag.s_x_pad) * 4
    eng = DistGREEngine(algorithms.bfs_program(), mesh, ("graph",),
                        exchange="agent", frontier="dense")
    topo = eng.device_topology(ag)
    state = eng.init_state(ag, source=0)
    fn = eng.make_run(ag, max_steps=64)
    final = jax.block_until_ready(fn(topo, state))  # compile + warm
    steps = int(np.asarray(final.step).max())
    runs[name] = (fn, topo, state, steps, msgs, padded)

samples = {m: [] for m in runs}
for _ in range(iters):
    for m, (fn, topo, state, *_ ) in runs.items():
        t0 = time.perf_counter()
        jax.block_until_ready(fn(topo, state))
        samples[m].append(time.perf_counter() - t0)
for m, (fn, topo, state, steps, msgs, padded) in runs.items():
    us = sorted(samples[m])[len(samples[m]) // 2] * 1e6
    print("RESULT " + json.dumps(
        {"mode": m, "us_per_run": us, "supersteps": steps,
         "exchange_msgs_per_step": msgs, "exchange_bytes_per_step": 4 * msgs,
         "padded_exchange_bytes": padded, "E": g.num_edges}), flush=True)
"""


def run_dist(scale: int = 10, k: int = 4, iters: int = 5):
    """End-to-end distributed BFS, greedy vs HDRF placement of the SAME
    web-like graph on the same mesh: fewer combiner/scatter agents means
    fewer exchange messages per superstep (emitted as
    `exchange_bytes_per_step`; the parent asserts the HDRF reduction) and
    the wall-clock rows record what that buys (`gate=False` — simulated
    devices on shared CI hosts are scheduler-bimodal; the within-run
    comparison is the signal)."""
    refuse_cpu_children_on_tpu("bench_partition.run_dist")
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(
        [str(ROOT), str(ROOT / "src"), env.get("PYTHONPATH", "")])
    proc = subprocess.run(
        [sys.executable, "-c", DIST_CHILD % dict(scale=scale, k=k,
                                                 iters=iters)],
        capture_output=True, text=True, timeout=1800, env=env, cwd=ROOT)
    if proc.returncode != 0:
        raise RuntimeError(f"bench child failed:\n{proc.stderr[-4000:]}")
    rows = {r["mode"]: r for r in
            (json.loads(line.split(" ", 1)[1])
             for line in proc.stdout.splitlines()
             if line.startswith("RESULT "))}
    g_row, h_row = rows["greedy"], rows["hdrf"]
    for name, r in rows.items():
        other = h_row if name == "greedy" else g_row
        emit(f"partition_dist_bfs_{name}_k{k}", r["us_per_run"],
             f"supersteps={r['supersteps']};"
             f"exchange_bytes_per_step={r['exchange_bytes_per_step']};"
             f"padded_exchange_bytes={r['padded_exchange_bytes']};"
             f"vs_other={r['exchange_bytes_per_step'] / max(other['exchange_bytes_per_step'], 1):.2f}x",
             edges=r["E"] * max(r["supersteps"], 1), gate=False)
    assert (h_row["exchange_bytes_per_step"]
            < g_row["exchange_bytes_per_step"]), (
        f"HDRF moved {h_row['exchange_bytes_per_step']} exchange B/step vs "
        f"greedy's {g_row['exchange_bytes_per_step']} — no reduction")
    return rows


def main():
    run()
    run_dist()


if __name__ == "__main__":
    main()
