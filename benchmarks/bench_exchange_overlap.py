"""Pipelined vs synchronous exchange: superstep throughput under remote load.

Races the three Agent-Graph exchange schedules on a multi-shard PageRank
run (dense frontier — every edge active, so the combiner flush carries its
full payload every superstep):

  sync       — AgentExchange: one full-E scatter-combine, then the flush
               collective as a mid-superstep barrier;
  overlap2x  — AgentExchange(overlap=True): the pre-split schedule that
               rewrites `dst` to issue the flush early, at the cost of
               scanning the SAME edge array twice (2·E work);
  pipelined  — PipelinedAgentExchange over the static ingress edge split
               (`agent_graph.split_edge_tiles`) through the plan
               executor's deferred-merge loop (`repro.core.plan`):
               E edge-scans, compact ⊕ segment spaces, flush merged at
               the top of the next superstep.

The graph is hash-partitioned so a large fraction of edges terminate at
combiner agents (reported as `remote_frac`) — the regime the paper's §6.2
overlap targets.  Runs in a subprocess because the multi-device XLA_FLAGS
must be set before jax initializes.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
from pathlib import Path

from benchmarks.common import emit, refuse_cpu_children_on_tpu

ROOT = Path(__file__).resolve().parent.parent

CHILD = r"""
import os
# One intra-op thread per simulated device: the k shards then execute truly
# concurrently (multi-threaded eigen oversubscribes small hosts and turns
# the schedule comparison into scheduler noise), which is what makes the
# flush-stall-vs-overlap difference measurable on CPU.
os.environ["XLA_FLAGS"] = ("--xla_force_host_platform_device_count=%(k)d "
                           "--xla_cpu_multi_thread_eigen=false "
                           "intra_op_parallelism_threads=1")
os.environ.setdefault("JAX_PLATFORMS", "cpu")
import json
import time
import jax

from repro.graph.generators import rmat_edges
from repro.core.partition import hash_partition
from repro.core.agent_graph import build_agent_graph, split_edge_tiles
from repro.core.dist_engine import DistGREEngine
from repro.core import algorithms

scale, k, steps, iters = %(scale)d, %(k)d, %(steps)d, %(iters)d
g = rmat_edges(scale=scale, edge_factor=8, seed=11).dedup()
ag = build_agent_graph(g, hash_partition(g, k), k)
remote_frac = split_edge_tiles(ag).remote_fraction
mesh = jax.make_mesh((k,), ("graph",))

MODES = (("sync", False), ("overlap2x", True), ("pipelined", False))
fns = {}
for mode, overlap in MODES:
    eng = DistGREEngine(algorithms.pagerank_program(), mesh, ("graph",),
                        exchange="pipelined" if mode == "pipelined"
                        else "agent", overlap=overlap)
    topo = eng.device_topology(ag)
    state = eng.init_state(ag)
    fn = eng.make_run(ag, max_steps=steps)
    jax.block_until_ready(fn(topo, state))  # compile + warm
    fns[mode] = (fn, topo, state)

# Interleave measurement rounds across the schedules so machine-load drift
# (shared runners, 2-core laptops hosting k simulated devices) hits every
# mode equally; per-mode median over rounds.
samples = {mode: [] for mode, _ in MODES}
for _ in range(iters):
    for mode, _ in MODES:
        fn, topo, state = fns[mode]
        t0 = time.perf_counter()
        jax.block_until_ready(fn(topo, state))
        samples[mode].append(time.perf_counter() - t0)

# whole-run medians: us_per_call then clears the CI gate's noise floor
# (per-superstep numbers would sit under --min-us and never gate)
us = {m: sorted(s)[len(s) // 2] * 1e6 for m, s in samples.items()}
for mode, _ in MODES:
    print("RESULT " + json.dumps(
        {"mode": mode, "us_per_run": us[mode], "steps": steps,
         "remote_frac": remote_frac, "E": g.num_edges}), flush=True)
print("RESULT " + json.dumps(
    {"mode": "summary",
     "speedup_vs_sync": us["sync"] / us["pipelined"],
     "speedup_vs_overlap": us["overlap2x"] / us["pipelined"]}), flush=True)
"""


def run(scale: int = 12, k: int = 2, steps: int = 24, iters: int = 9):
    refuse_cpu_children_on_tpu("bench_exchange_overlap")
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(
        [str(ROOT), str(ROOT / "src"), env.get("PYTHONPATH", "")])
    proc = subprocess.run(
        [sys.executable, "-c",
         CHILD % dict(scale=scale, k=k, steps=steps, iters=iters)],
        capture_output=True, text=True, timeout=1800, env=env, cwd=ROOT)
    if proc.returncode != 0:
        raise RuntimeError(f"bench child failed:\n{proc.stderr[-4000:]}")
    rows = [json.loads(line.split(" ", 1)[1])
            for line in proc.stdout.splitlines() if line.startswith("RESULT ")]
    summary = next(r for r in rows if r["mode"] == "summary")
    for r in rows:
        if r["mode"] == "summary":
            continue
        per_step = r["us_per_run"] / r["steps"]
        derived = (f"remote_frac={r['remote_frac']:.2f};k={k};"
                   f"supersteps={r['steps']};us_per_step={per_step:.1f}")
        if r["mode"] == "pipelined":
            derived += (f";speedup_vs_sync={summary['speedup_vs_sync']:.2f}"
                        f";speedup_vs_overlap="
                        f"{summary['speedup_vs_overlap']:.2f}")
        # gate=False: absolute times of k simulated devices on small CI
        # hosts are scheduler-bimodal run to run; the entries trend-track
        # (and fail compare.py if dropped) but don't ratio-gate.  The
        # schedule comparison itself is the interleaved within-run medians
        # in the derived speedups.
        emit(f"exchange_{r['mode']}_rmat{scale}_k{k}",
             r["us_per_run"], derived, edges=r["E"] * r["steps"],
             gate=False)
    return summary


def main():
    run()


if __name__ == "__main__":
    main()
