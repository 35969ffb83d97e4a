#!/usr/bin/env python3
"""Several chips: a graph HDRF-partitioned into an Agent-Graph, jobs of
`DistGREEngine`'s run over the agent exchange.

Set-up (all before the window, counted in `setup_s`):
  the configuration's generator makes the graph from the seed and copies
  it to the host; the configuration's streaming partitioner places every
  edge on one of `partitions` chips (host span `gre.ingress.hdrf`),
  `build_agent_graph` adds the scatter and combiner agents
  (`gre.ingress.agent_graph`) and `DistGREEngine.device_topology` lays one
  partition on each chip (`gre.ingress.topology`); the three end in
  `block_until_ready` (`ingress_s`).  `init_state` runs once, and the
  run is lowered and compiled ahead for the topology (`compile_s`).

Window: `single.py`'s.  A job is `init_state` + the compiled run +
`block_until_ready`, back to back while fewer than `seconds` have passed.

Check: after the window the peak bytes of the fullest chip are read, each
job's stacked `vertex_data` is copied to the host in original vertex order
(`old2new`) and the device state is dropped; then the program file's
plain reference and comparison judge every job.

The record carries three more readings for the metric readers: `spans`,
the seconds of each host span of set-up (`repro.spans.recording()`);
`counters`, the Agent-Graph's static sizes (`AgentGraph.counters`); and
under `--trace 1`, `scopes`, the device self seconds of each named scope
inside the window, mean over the chips.  A program without those spans,
counters or scopes leaves them empty, and the readers return None.

Run as a script, it records one traced window at a small scale, and keeps
its trace, for the tests (`chipbench/testdata/`):

    python3 chipbench/runners/agent.py --workload rmat22-hdrf4.pagerank \\
        --scale 12 --seed 1 --out chiprun_out/trace
"""
from __future__ import annotations

import dataclasses
import gzip
import shutil
import sys
import tempfile
import time
from pathlib import Path

import numpy as np

if __name__ == "__main__":
    sys.path[:0] = [str(Path(__file__).resolve().parents[2]),
                    str(Path(__file__).resolve().parents[2] / "src")]

from chipbench.harness import RunRecord  # noqa: E402
from chipbench.runners.single import compiles, peak_bytes  # noqa: E402
from chipbench.scopes import scope_times  # noqa: E402
from chipbench.trace import WINDOW, events, reduce  # noqa: E402
from chipbench.trace import start as start_trace  # noqa: E402


@dataclasses.dataclass
class AgentRunRecord(RunRecord):
    """A run record with the readings of the Agent-Graph's layers."""

    spans: dict = dataclasses.field(default_factory=dict)     # name -> s
    counters: dict = dataclasses.field(default_factory=dict)  # name -> n
    scopes: dict = dataclasses.field(default_factory=dict)    # name -> s


@dataclasses.dataclass
class Prepared:
    """What set-up leaves for the window."""

    edges: object
    graph: object          # the AgentGraph, on the host
    engine: object
    topo: object
    compiled: object
    source: object
    ingress_s: float
    compile_s: float
    spans: dict
    counters: dict


def prepare(cell, seed: int, devices, log) -> Prepared:
    """Set-up: generate, partition, agent graph, topology, compiled run."""
    import jax
    from repro import spans
    from repro.core.agent_graph import build_agent_graph
    from repro.core.dist_engine import DistGREEngine
    from repro.core.partition_stream import partition_edges
    from repro.graph.structures import Graph

    config, k = cell.config, cell.config["partitions"]
    t = time.perf_counter()
    edges = cell.generator.generate(config, seed)
    log(f"generate: V={edges.num_vertices} E={edges.num_edges} in "
        f"{time.perf_counter() - t:.3f}s; generator peak_bytes_in_use="
        f"{peak_bytes(devices)}")
    props = {} if edges.weight is None else {"weight": edges.weight}
    graph = Graph(edges.num_vertices, edges.src, edges.dst, props)
    program, source = cell.program.build(cell.traffic, edges)
    mesh = jax.make_mesh((k,), ("graph",), devices=devices[:k])
    engine = DistGREEngine(program, mesh, ("graph",),
                           exchange=config["exchange"])
    t = time.perf_counter()
    with spans.recording() as recorded:
        placement = partition_edges(graph, k, method=config["partitioner"],
                                    lam=config["hdrf_lambda"],
                                    batch_size=config["hdrf_batch"])
        ag = build_agent_graph(graph, placement, k,
                               partitioner=config["partitioner"])
        topo = jax.block_until_ready(engine.device_topology(ag))
    ingress_s = time.perf_counter() - t
    del graph, placement
    span_s = {}
    for name, start, end in recorded:
        span_s[name] = span_s.get(name, 0.0) + (end - start) * 1e-9
    counters = ag.counters() if hasattr(ag, "counters") else {}
    state = jax.block_until_ready(engine.init_state(ag, source=source))
    t = time.perf_counter()
    compiled = engine.make_run(ag, cell.traffic["max_steps"]).lower(
        topo, state).compile()
    compile_s = time.perf_counter() - t
    log(f"ingress_s={ingress_s:.3f} compile_s={compile_s:.3f} "
        f"spans={span_s} counters={counters}")
    return Prepared(edges, ag, engine, topo, compiled, source, ingress_s,
                    compile_s, span_s, counters)


def jobs(prep: Prepared, seconds: float):
    """The window: jobs back to back while fewer than `seconds` have
    passed.  Returns each job's stacked (vertex_data, step) on the chips
    and the seconds from the window's start to each job's end."""
    import jax
    outs, ends = [], []
    t_window = time.perf_counter()
    with jax.profiler.TraceAnnotation("chipbench.window"):
        while not outs or time.perf_counter() - t_window < seconds:
            with jax.profiler.TraceAnnotation("chipbench.init_state"):
                state = prep.engine.init_state(prep.graph,
                                               source=prep.source)
            with jax.profiler.TraceAnnotation("chipbench.dispatch"):
                out = prep.compiled(prep.topo, state)
            with jax.profiler.TraceAnnotation("chipbench.wait"):
                jax.block_until_ready(out)
            ends.append(time.perf_counter() - t_window)
            outs.append((out.vertex_data, out.step))
            del state, out
    return outs, ends


def trace_readings(xplane: Path, device_ids):
    """(summary, scopes) of a traced window: `chipbench.trace.reduce` over
    every chip, and the self seconds of each named scope inside the
    window, mean over the chips."""
    summary = reduce(xplane, device_ids)
    spans, _ = events(xplane)
    (lo, hi), = [(s, e) for name, s, e in spans if name == WINDOW]
    scopes = {name: sec / len(device_ids) for name, sec in
              scope_times(xplane, device_ids, lo, hi).items()}
    return summary, scopes


def run(cell, seed: int, seconds: float, trace: bool, t_start: float,
        peak: dict, log, keep: Path = None) -> AgentRunRecord:
    """One run; with `keep`, a traced window's `.xplane.pb` is also
    written there, gzipped."""
    import jax

    devices = jax.devices()[:cell.config["partitions"]]
    prep = prepare(cell, seed, devices, log)
    in_window = [0]
    listener = compiles(in_window)
    jax.monitoring.register_event_duration_secs_listener(listener)
    trace_dir = tempfile.mkdtemp(prefix="chipbench-trace-") if trace else None
    if trace:
        start_trace(trace_dir)
    setup_s = time.perf_counter() - t_start
    outs, ends = jobs(prep, seconds)
    if trace:
        jax.profiler.stop_trace()
    jax.monitoring.unregister_event_duration_listener(listener)
    record = AgentRunRecord(
        setup_s=setup_s, ingress_s=prep.ingress_s, compile_s=prep.compile_s,
        window_s=ends[-1],
        supersteps=[int(np.max(np.asarray(step))) for _, step in outs],
        peak_bytes=peak_bytes(devices), num_vertices=prep.edges.num_vertices,
        num_edges=prep.edges.num_edges, compared={}, failed=0, peak=peak,
        spans=prep.spans, counters=prep.counters)
    log(f"window: {record.jobs} job(s), the last ending at {ends[-1]:.6f}s;"
        f" compiles in the window: {in_window[0]}; "
        f"peak_bytes_in_use={record.peak_bytes}")

    # each stacked [k, cap] column in original vertex order
    got = [np.asarray(vd).reshape(-1)[prep.graph.old2new] for vd, _ in outs]
    edges = prep.edges
    del outs, prep                 # the device state goes before the check
    t = time.perf_counter()
    want = cell.program.reference(edges, cell.traffic)
    log(f"reference: {time.perf_counter() - t:.3f}s")
    limits = cell.limits
    readings = [cell.program.compare(g, want) for g in got]
    record.failed = sum(any(not r[k] <= limits[k] for k in limits)
                        for r in readings)
    record.compared = {k: (max(r[k] for r in readings), limits[k])
                       for k in limits}
    if trace:
        try:
            xplane = next(Path(trace_dir).rglob("*.xplane.pb"))
            record.trace, record.scopes = trace_readings(
                xplane, [d.id for d in devices])
            log(f"scopes (s in the window, mean over chips): "
                f"{record.scopes}")
            if keep is not None:
                Path(keep).mkdir(parents=True, exist_ok=True)
                with open(xplane, "rb") as src, gzip.open(
                        Path(keep) / "window.xplane.pb.gz", "wb") as dst:
                    shutil.copyfileobj(src, dst)
        finally:
            shutil.rmtree(trace_dir)
    return record


def main(argv=None) -> int:
    import argparse
    import json

    from chipbench import harness

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", default="rmat22-hdrf4.pagerank")
    ap.add_argument("--scale", type=int, required=True)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--out", type=Path, required=True)
    args = ap.parse_args(argv)
    root = Path(__file__).resolve().parents[2]
    bench = harness.load_benchmark(root)
    cell = harness.load_cell(root, root / "chipbench", bench, args.workload)
    cell = dataclasses.replace(cell, config={**cell.config,
                                             "scale": args.scale})
    peaks = json.loads((root / "chipbench" / "peaks.json").read_text())
    devices, peak = harness.accelerator(cell.chips, peaks)
    record = run(cell, args.seed, 0.0, True, time.perf_counter(), peak,
                 print, keep=args.out)
    facts = {"recorded": f"{devices[0].device_kind} x{cell.chips}, "
                         f"chipbench/runners/agent.py --scale {args.scale}"
                         f" --seed {args.seed}",
             "supersteps": record.supersteps, "busy_s": record.trace.busy_s,
             "window_s": record.trace.window_s, "scopes": record.scopes,
             "spans": record.spans, "counters": record.counters,
             "compared": record.compared}
    (args.out / "window.json").write_text(json.dumps(facts, indent=1) + "\n")
    print(json.dumps(facts))
    return 0


if __name__ == "__main__":
    sys.exit(main())
