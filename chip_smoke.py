#!/usr/bin/env python3
"""Chip smoke run: the GRE engine's main path on a TPU, checked against a
plain numpy/scipy reference.

    python chip_smoke.py              # one chip: batch analytics + serving
    python chip_smoke.py --chips 4    # four chips: DistGREEngine only

The graph is a Graph500 R-MAT graph (a=0.57, b=c=0.19, edge factor 16,
integer weights in [1, 65535]; `configs/gre_paper.py`) at `--scale`
(default 22: 4.19M vertices, ~64M edges after dedup — the size of the
soc-LiveJournal graph), generated from `--seed`.  CC runs on its
symmetrized copy.

One chip: PageRank (30 supersteps), SSSP from vertex 0 (or, when the
seeded permutation leaves vertex 0 without out-edges, the lowest vertex id
that has some) and CC through
`DevicePartition.from_graph` + `GREEngine.run`, each with the default plan
(XLA combine) and again with `use_pallas=True` (the compiled Pallas
combine, checked present in the program); then mixed BFS / SSSP / PPR
queries through `GraphQueryBatcher` with more queries than its 16 lanes, so
lanes are recycled.  Four chips: the symmetrized graph HDRF-partitioned
at k=4 (one partition serves all three programs), PageRank / SSSP / CC
through `DistGREEngine` on the "agent" and "pipelined" exchanges, plus
"async" (k=2) for SSSP and CC.

Every answer is compared with an implementation that shares no engine
code: BFS, SSSP and CC bitwise; PageRank and PPR within the tolerances
printed.  Per-phase lines are smoke readings (compile and run seconds,
supersteps, peak device bytes), not benchmark numbers.  The last stdout
line is the JSON verdict.  The script exits non-zero, printing no verdict,
when JAX finds no TPU or any phase or check fails.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from concurrent.futures import ThreadPoolExecutor
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

PR_STEPS = 30
PR_RTOL = 2e-4      # PageRank: |got - ref| <= PR_RTOL * ref (ref >= 0.15)
PPR_ALPHA, PPR_EPS = 0.15, 1e-4   # ppr_push_program defaults
PPR_ATOL = 2 * PPR_EPS            # PPR: |got - ref| <= PPR_ATOL
LANES = 16
QUERIES = {"bfs": 20, "sssp": 8, "ppr": 20}
FAILURES: list = []


def log(*parts) -> None:
    print(*parts, flush=True)


def check(ok: bool, what: str) -> None:
    log(f"  check {'PASS' if ok else 'FAIL'}: {what}")
    if not ok:
        FAILURES.append(what)


def peak_bytes(devices) -> list:
    return [d.memory_stats()["peak_bytes_in_use"] for d in devices]


# ---------------------------------------------------------------- reference
def csr(src: np.ndarray, dst: np.ndarray, n: int, w=None):
    """Out-edge CSR (indptr, neighbors, weights) of a COO edge list."""
    order = np.argsort(src, kind="stable")
    indptr = np.zeros(n + 1, np.int64)
    np.cumsum(np.bincount(src, minlength=n), out=indptr[1:])
    return indptr, dst[order], None if w is None else w[order]


def out_edges(indptr: np.ndarray, frontier: np.ndarray):
    """Positions of the frontier vertices' out-edges in CSR order."""
    start = indptr[frontier]
    count = indptr[frontier + 1] - start
    offs = np.repeat(start - np.cumsum(count) + count, count)
    return offs + np.arange(int(count.sum())), count


def ref_pagerank(src, dst, n, steps):
    deg = np.maximum(np.bincount(src, minlength=n), 1).astype(np.float64)
    pr = np.ones(n)
    for _ in range(steps):
        pr = 0.15 + 0.85 * np.bincount(dst, weights=(pr / deg)[src],
                                       minlength=n)
    return pr


def ref_bfs(indptr, nbr, n, source):
    depth = np.full(n, np.inf, np.float32)
    depth[source] = 0.0
    frontier, level = np.array([source]), 0
    while frontier.size:
        pos, _ = out_edges(indptr, frontier)
        nxt = np.unique(nbr[pos])
        frontier = nxt[np.isinf(depth[nxt])]
        level += 1
        depth[frontier] = level
    return depth


def ref_sssp(src, dst, w, n, sources):
    from scipy.sparse import csr_matrix
    from scipy.sparse.csgraph import dijkstra
    m = csr_matrix((w.astype(np.float64), (src, dst)), shape=(n, n))
    return dijkstra(m, directed=True, indices=sources).astype(np.float32)


def ref_cc(src, dst, n):
    from scipy.sparse import csr_matrix
    from scipy.sparse.csgraph import connected_components
    m = csr_matrix((np.ones(src.shape[0], np.int8), (src, dst)),
                   shape=(n, n))
    k, comp = connected_components(m, directed=False)
    low = np.full(k, n, np.int64)
    np.minimum.at(low, comp, np.arange(n))
    return low[comp].astype(np.float32)


def ref_ppr(indptr, nbr, n, source):
    """Synchronous forward push: every vertex whose held residual exceeds
    eps pushes alpha of it into its estimate and spreads the rest evenly
    over its out-edges; the others hold theirs."""
    deg = np.maximum(np.diff(indptr), 1).astype(np.float64)
    p = np.zeros(n)
    r = np.zeros(n)
    p[source] = PPR_ALPHA
    push = np.array([source])
    share = np.array([(1 - PPR_ALPHA) / deg[source]])
    while push.size:
        pos, count = out_edges(indptr, push)
        recv, inv = np.unique(nbr[pos], return_inverse=True)
        total = r[recv] + np.bincount(inv, weights=np.repeat(share, count))
        fire = total > PPR_EPS
        p[recv[fire]] += PPR_ALPHA * total[fire]
        r[recv] = np.where(fire, 0.0, total)
        push = recv[fire]
        share = (1 - PPR_ALPHA) * total[fire] / deg[push]
    return p


def batch_refs(g, gu, root):
    """PageRank and SSSP on `g`, CC on `gu`."""
    t0 = time.perf_counter()
    n = g.num_vertices
    want = {"pagerank": ref_pagerank(g.src, g.dst, n, PR_STEPS),
            "sssp": ref_sssp(g.src, g.dst, g.edge_props["weight"], n,
                             [root])[0],
            "cc": ref_cc(gu.src, gu.dst, n)}
    log(f"reference: pagerank/sssp/cc {time.perf_counter() - t0:.1f}s; "
        f"sssp from {root} reaches {int(np.isfinite(want['sssp']).sum())} "
        f"vertices, {len(np.unique(want['cc']))} components")
    return want


def serving_refs(g, indptr, nbr, sources):
    """Per-query answers for the serving phase, by kind."""
    t0 = time.perf_counter()
    n = g.num_vertices
    want = {"bfs": [ref_bfs(indptr, nbr, n, s) for s in sources["bfs"]],
            "sssp": list(ref_sssp(g.src, g.dst, g.edge_props["weight"], n,
                                  sources["sssp"])),
            "ppr": [ref_ppr(indptr, nbr, n, s) for s in sources["ppr"]]}
    log(f"reference: serving {time.perf_counter() - t0:.1f}s")
    return want


# ------------------------------------------------------------- engine runs
def sssp_source(g) -> int:
    """Vertex 0, or the lowest vertex id with out-edges."""
    return int(np.flatnonzero(np.bincount(g.src, minlength=g.num_vertices)
                              > 0)[0])


def run_engine(label, program, part, source, max_steps, use_pallas, devices):
    """Compile and run one `GREEngine.run`; returns host vertex_data."""
    import jax
    from repro.core.engine import GREEngine
    eng = GREEngine(program, use_pallas=use_pallas)
    state = eng.init_state(part, source=source)
    t0 = time.perf_counter()
    compiled = GREEngine.run.lower(eng, part, state, max_steps).compile()
    t_compile = time.perf_counter() - t0
    check(("tpu_custom_call" in compiled.as_text()) == use_pallas,
          f"{label}: Pallas kernel {'present' if use_pallas else 'absent'}"
          f" in the compiled program")
    t0 = time.perf_counter()
    out = jax.block_until_ready(compiled(part, state))
    t_run = time.perf_counter() - t0
    log(f"smoke reading {label}: compile_s={t_compile:.3f} "
        f"run_s={t_run:.3f} supersteps={int(out.step)} "
        f"peak_bytes_in_use={peak_bytes(devices)}")
    return np.asarray(out.vertex_data)


def check_exact(label, got, want):
    bad = int(np.sum(got != want))
    check(bad == 0, f"{label}: bitwise equal to the reference "
          f"({bad} of {want.size} differ)")


def check_pagerank(label, got, want):
    err = np.abs(got.astype(np.float64) - want)
    rel = float(np.max(err / want))
    check(rel <= PR_RTOL, f"{label}: max abs err {float(err.max()):.3e}, "
          f"max rel err {rel:.3e} <= {PR_RTOL:g}")


def one_chip(g, gu, devices, seed, refs):
    import jax
    from repro.core import algorithms
    from repro.core.engine import DevicePartition, GREEngine
    from repro.serving.graph_scheduler import GraphQueryBatcher

    # references run on the `refs` thread while the chip works
    root = sssp_source(g)
    indptr, nbr, _ = csr(g.src, g.dst, g.num_vertices)
    rng = np.random.default_rng(seed)
    roots = rng.choice(np.flatnonzero(np.diff(indptr) > 0),
                       sum(QUERIES.values()), replace=False)
    sources, at = {}, 0
    for kind, count in QUERIES.items():
        sources[kind], at = roots[at:at + count], at + count
    want = refs.submit(batch_refs, g, gu, root)
    want_serving = refs.submit(serving_refs, g, indptr, nbr, sources)

    t0 = time.perf_counter()
    part = DevicePartition.from_graph(g)
    part_u = DevicePartition.from_graph(gu)
    jax.block_until_ready((part, part_u))
    log(f"set-up: from_graph x2 {time.perf_counter() - t0:.1f}s")

    for use_pallas in (False, True):
        route = "pallas" if use_pallas else "xla"
        got = run_engine(f"pagerank/{route}", algorithms.pagerank_program(),
                         part, None, PR_STEPS, use_pallas, devices)
        check_pagerank(f"pagerank/{route}", got, want.result()["pagerank"])
        got = run_engine(f"sssp/{route}", algorithms.sssp_program(), part,
                         root, 10_000, use_pallas, devices)
        check_exact(f"sssp/{route}", got, want.result()["sssp"])
        got = run_engine(f"cc/{route}", algorithms.cc_program(), part_u,
                         None, 10_000, use_pallas, devices)
        check_exact(f"cc/{route}", got, want.result()["cc"])
    del part_u

    # serving: more queries per kind than lanes, so lanes are recycled
    kinds = {"bfs": algorithms.bfs_program(LANES),
             "sssp": algorithms.sssp_program(LANES),
             "ppr": algorithms.ppr_push_program(LANES, PPR_ALPHA, PPR_EPS)}
    for kind, program in kinds.items():
        batcher = GraphQueryBatcher(GREEngine(program), part)
        t0 = time.perf_counter()
        batcher.tick()                   # empty lanes: compiles the tick
        jax.block_until_ready(batcher.state)
        t_compile = time.perf_counter() - t0
        queries = [batcher.submit(int(s)) for s in sources[kind]]
        t0 = time.perf_counter()
        batcher.run()
        t_run = time.perf_counter() - t0
        m = batcher.metrics()
        log(f"smoke reading serve/{kind}: queries={len(queries)} "
            f"lanes={LANES} compile_s={t_compile:.3f} run_s={t_run:.3f} "
            f"ticks={int(m['ticks'])} supersteps={int(m['supersteps'])} "
            f"latency_p50_s={m['latency_p50_s']:.3f} "
            f"latency_p95_s={m['latency_p95_s']:.3f} "
            f"peak_bytes_in_use={peak_bytes(devices)}")
        check(all(q.status == "done" for q in queries),
              f"serve/{kind}: all {len(queries)} queries done")
        for q, s, ref in zip(queries, sources[kind],
                             want_serving.result()[kind]):
            if kind == "ppr":
                err = float(np.max(np.abs(q.result - ref)))
                check(err <= PPR_ATOL, f"serve/ppr root {s}: max abs err "
                      f"{err:.3e} <= {PPR_ATOL:g}")
            else:
                check_exact(f"serve/{kind} root {s}", q.result, ref)
        del batcher


def four_chips(gu, devices, refs):
    import jax
    from repro.core import algorithms
    from repro.core.agent_graph import build_agent_graph
    from repro.core.dist_engine import DistGREEngine
    from repro.core.partition_stream import hdrf_partition

    root = sssp_source(gu)
    want = refs.submit(batch_refs, gu, gu, root)
    mesh = jax.make_mesh((4,), ("graph",), devices=devices[:4])
    t0 = time.perf_counter()
    graph = build_agent_graph(gu, hdrf_partition(gu, 4, batch_size=4096), 4)
    log(f"set-up: hdrf k=4 + build_agent_graph "
        f"{time.perf_counter() - t0:.1f}s")

    # one topology per exchange family (pipelined and async share the
    # split edge tiles), one state per run
    t0 = time.perf_counter()
    topos, jobs = {}, []
    for name, source, steps, exchanges in [
            ("pagerank", None, PR_STEPS, ("agent", "pipelined")),
            ("sssp", root, 10_000, ("agent", "pipelined", "async")),
            ("cc", None, 10_000, ("agent", "pipelined", "async"))]:
        program = getattr(algorithms, f"{name}_program")()
        for exchange in exchanges:
            eng = DistGREEngine(program, mesh, ("graph",), exchange=exchange,
                                staleness=2)
            family = exchange == "agent"
            if family not in topos:
                topos[family] = eng.device_topology(graph)
            jobs.append((f"{name}/{exchange}", name, eng, topos[family],
                         eng.init_state(graph, source=source), steps))
    rows = {len(a.sharding.device_set)
            for job in jobs for a in jax.tree.leaves(job[3:5])}
    check(rows == {4}, "every topology and state array spread over the 4 "
          "devices, one block each")
    log(f"set-up: device topologies + states {time.perf_counter() - t0:.1f}s")

    def compile_job(job):
        t0 = time.perf_counter()
        eng, topo, state, steps = job[2:]
        compiled = eng.make_run(graph, max_steps=steps).lower(
            topo, state).compile()
        return compiled, time.perf_counter() - t0

    t0 = time.perf_counter()
    with ThreadPoolExecutor(len(jobs)) as pool:
        programs = list(pool.map(compile_job, jobs))
    log(f"set-up: {len(jobs)} programs compiled in parallel in "
        f"{time.perf_counter() - t0:.1f}s")

    for (label, name, _, topo, state, _), (compiled, t_compile) in zip(
            jobs, programs):
        t0 = time.perf_counter()
        out = jax.block_until_ready(compiled(topo, state))
        t_run = time.perf_counter() - t0
        log(f"smoke reading {label}: compile_s={t_compile:.3f} "
            f"run_s={t_run:.3f} supersteps={int(out.step.max())} "
            f"peak_bytes_in_use_per_device={peak_bytes(devices[:4])}")
        vd = np.asarray(out.vertex_data).reshape(graph.k * graph.cap)
        got = vd[graph.old2new]
        if name == "pagerank":
            check_pagerank(label, got, want.result()[name])
        else:
            check_exact(label, got, want.result()[name])
        del out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--scale", type=int, default=22)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    from repro.compile_cache import enable_compile_cache
    cache = enable_compile_cache()
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        log(f"chip_smoke needs a TPU; JAX found {devices[0].platform}")
        return 2
    if len(devices) < args.chips:
        log(f"--chips {args.chips} needs {args.chips} devices; "
            f"found {len(devices)}")
        return 2
    log(f"device: {devices[0].device_kind} x{len(devices)}; compile cache "
        f"{cache}")

    from repro.graph.generators import rmat_edges
    t0 = time.perf_counter()
    g = rmat_edges(args.scale, edge_factor=16, seed=args.seed,
                   weights=True).dedup()
    gu = g.as_undirected().dedup()
    log(f"set-up: R-MAT scale {args.scale} seed {args.seed}: "
        f"V={g.num_vertices} E={g.num_edges} (undirected E={gu.num_edges}) "
        f"in {time.perf_counter() - t0:.1f}s")
    with ThreadPoolExecutor(1) as refs:
        if args.chips == 1:
            one_chip(g, gu, devices, args.seed, refs)
        else:
            four_chips(gu, devices, refs)
    if FAILURES:
        log(f"{len(FAILURES)} check(s) failed: {FAILURES}")
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
