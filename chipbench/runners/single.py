"""One chip: a graph on one `DevicePartition`, jobs of `GREEngine.run`.

Set-up (all before the window, counted in `setup_s`):
  the configuration's generator makes the graph from the seed on the
  device and copies it to the host; `DevicePartition.from_graph` builds
  the partition (`ingress_s`, ending in `block_until_ready`); the engine
  takes its default plan (frontier "auto", XLA combine); `init_state`
  runs once, and `GREEngine.run` is lowered and compiled ahead
  (`compile_s`; a load from the persistent cache after the first run).

Window: a job is `init_state` + the compiled run + `block_until_ready`.
Jobs start back to back while fewer than `seconds` have passed since the
window opened, so the window holds at least one whole job.

Check: after the window the peak device bytes are read, each job's
`vertex_data` is copied to the host and the device state is dropped; then
the program file's plain reference and comparison judge every job against
the configuration's limits.
"""
from __future__ import annotations

import dataclasses
import shutil
import tempfile
import time
from pathlib import Path

import numpy as np

from chipbench.harness import RunRecord
from chipbench.trace import reduce, start as start_trace


def peak_bytes(devices):
    """peak_bytes_in_use of the fullest chip (None where not reported)."""
    stats = [d.memory_stats() for d in devices]
    if any(s is None for s in stats):
        return None
    return max(s["peak_bytes_in_use"] for s in stats)


def compiles(counter: list):
    """Counts backend compiles into `counter[0]` while registered."""
    def listener(event, duration, **kwargs):
        if event == "/jax/core/compile/backend_compile_duration":
            counter[0] += 1
    return listener


@dataclasses.dataclass
class Prepared:
    """What set-up leaves for the window."""

    edges: object
    part: object
    engine: object
    compiled: object
    source: object
    ingress_s: float
    compile_s: float


def compile_engine(cell, edges, part):
    """The engine under its default plan for the cell's program, and
    `GREEngine.run` lowered and compiled ahead for `part`: (engine,
    compiled run, source, seconds the compile or cache load took)."""
    import jax
    from repro.core.engine import GREEngine
    program, source = cell.program.build(cell.traffic, edges)
    engine = GREEngine(program)
    state = jax.block_until_ready(engine.init_state(part, source=source))
    t = time.perf_counter()
    compiled = GREEngine.run.lower(engine, part, state,
                                   cell.traffic["max_steps"]).compile()
    return engine, compiled, source, time.perf_counter() - t


def prepare(cell, seed: int, devices, log) -> Prepared:
    """Set-up: generate, ingress, engine and its compiled run."""
    import jax
    from repro.core.engine import DevicePartition
    from repro.graph.structures import Graph

    t = time.perf_counter()
    edges = cell.generator.generate(cell.config, seed)
    log(f"generate: V={edges.num_vertices} E={edges.num_edges} in "
        f"{time.perf_counter() - t:.3f}s; generator peak_bytes_in_use="
        f"{peak_bytes(devices)}")
    props = {} if edges.weight is None else {"weight": edges.weight}
    graph = Graph(edges.num_vertices, edges.src, edges.dst, props)
    t = time.perf_counter()
    part = jax.block_until_ready(DevicePartition.from_graph(graph))
    ingress_s = time.perf_counter() - t
    del graph
    engine, compiled, source, compile_s = compile_engine(cell, edges, part)
    log(f"ingress_s={ingress_s:.3f} compile_s={compile_s:.3f}")
    return Prepared(edges, part, engine, compiled, source, ingress_s,
                    compile_s)


def jobs(prep: Prepared, seconds: float):
    """The window: jobs back to back while fewer than `seconds` have
    passed.  Returns each job's (vertex_data, step) on the device and the
    seconds from the window's start to each job's end."""
    import jax
    outs, ends = [], []
    t_window = time.perf_counter()
    with jax.profiler.TraceAnnotation("chipbench.window"):
        while not outs or time.perf_counter() - t_window < seconds:
            with jax.profiler.TraceAnnotation("chipbench.init_state"):
                state = prep.engine.init_state(prep.part, source=prep.source)
            with jax.profiler.TraceAnnotation("chipbench.dispatch"):
                out = prep.compiled(prep.part, state)
            with jax.profiler.TraceAnnotation("chipbench.wait"):
                jax.block_until_ready(out)
            ends.append(time.perf_counter() - t_window)
            outs.append((out.vertex_data, out.step))
            del state, out
    return outs, ends


def run(cell, seed: int, seconds: float, trace: bool, t_start: float,
        peak: dict, log) -> RunRecord:
    import jax

    devices = jax.devices()[:1]
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
    record = RunRecord(
        setup_s=setup_s, ingress_s=prep.ingress_s, compile_s=prep.compile_s,
        window_s=ends[-1], supersteps=[int(step) for _, step in outs],
        peak_bytes=peak_bytes(devices), num_vertices=prep.edges.num_vertices,
        num_edges=prep.edges.num_edges, compared={}, failed=0, peak=peak)
    log(f"window: {record.jobs} job(s), the last ending at {ends[-1]:.6f}s;"
        f" compiles in the window: {in_window[0]}; "
        f"peak_bytes_in_use={record.peak_bytes}")

    got = [np.asarray(vd) for vd, _ in outs]
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
            record.trace = reduce(xplane, [d.id for d in devices])
        finally:
            shutil.rmtree(trace_dir)
    return record
