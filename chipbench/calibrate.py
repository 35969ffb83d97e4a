#!/usr/bin/env python3
"""Readings that set a cell's limits, and a small trace for the tests.

Not part of a benchmark run; run on the chip by hand.

    python3 chipbench/calibrate.py readings --workload rmat22.pagerank \\
        --seeds 1,2,3 --control-seeds 1,2,3 --out calibrate_out/calibrate

For each seed, in one process: the cell's set-up and one job through the
same compiled run the window drives, at the cell's own size, compared
with the plain reference (the program's reading); for each control seed
the program file's `control`, the reference in the precision below the
configuration's, compared the same way (the control's reading).  The
limit of each compared number lies between the largest program reading
and the smallest control reading.

    python3 chipbench/calibrate.py faults --workload rmat22.pagerank \\
        --seeds 1 --out calibrate_out/faults

runs the cell's set-up once, then for each fault of `chipbench/faults.py`
an engine compiled with the fault planted, one job on the same partition,
compared with the reference as a run compares it (each fault's reading).

    python3 chipbench/calibrate.py trace --workload rmat22.pagerank \\
        --scale 12 --seconds 0 --out calibrate_out/trace

runs one traced window at a smaller scale and keeps its `.xplane.pb`
(gzipped) and what the host and the reduction read of it.
"""
import argparse
import dataclasses
import gzip
import json
import shutil
import sys
import tempfile
import time
from pathlib import Path

BENCH_DIR = Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from chipbench import harness  # noqa: E402


def log(*parts) -> None:
    print(*parts, flush=True)


def readings(cell, seeds, control_seeds, out: Path, devices) -> None:
    import numpy as np
    rows = []
    for seed in sorted(set(seeds) | set(control_seeds)):
        t0 = time.perf_counter()
        row = {"seed": seed}
        if seed in seeds:
            prep = cell.runner.prepare(cell, seed, devices, log)
            outs, ends = cell.runner.jobs(prep, 0.0)
            row.update(job_s=ends[-1], supersteps=int(outs[0][1]),
                       ingress_s=prep.ingress_s, compile_s=prep.compile_s,
                       num_edges=prep.edges.num_edges)
            got = np.asarray(outs[0][0])
            edges = prep.edges
            del prep, outs
        else:
            edges = cell.generator.generate(cell.config, seed)
        want = cell.program.reference(edges, cell.traffic)
        if seed in seeds:
            row["program"] = cell.program.compare(got, want)
        if seed in control_seeds:
            row["control"] = cell.program.compare(
                cell.program.control(edges, cell.traffic), want)
        row["seconds"] = time.perf_counter() - t0
        log(json.dumps(row))
        rows.append(row)
    out.mkdir(parents=True, exist_ok=True)
    (out / f"{cell.name}.json").write_text(json.dumps(rows, indent=1))
    for name in cell.limits:
        prog = [r["program"][name] for r in rows if "program" in r]
        ctrl = [r["control"][name] for r in rows if "control" in r]
        log(f"{name}: program max {max(prog, default=None)!r} over "
            f"{len(prog)} seeds; control min {min(ctrl, default=None)!r} "
            f"over {len(ctrl)} seeds; limit now {cell.limits[name]!r}")


def fault_readings(cell, seed: int, out: Path, devices) -> None:
    import dataclasses

    import numpy as np
    from chipbench.faults import FAULTS
    prep = cell.runner.prepare(cell, seed, devices, log)
    want = cell.program.reference(prep.edges, cell.traffic)
    rows = []
    for name, fault in FAULTS.items():
        with fault():
            engine, compiled, source, _ = cell.runner.compile_engine(
                cell, prep.edges, prep.part)
        broken = dataclasses.replace(prep, engine=engine, compiled=compiled,
                                     source=source)
        outs, ends = cell.runner.jobs(broken, 0.0)
        row = {"seed": seed, "fault": name, "job_s": ends[-1],
               "supersteps": int(outs[0][1]),
               "reading": cell.program.compare(np.asarray(outs[0][0]), want),
               "limits": cell.limits}
        del broken, outs
        log(json.dumps(row))
        rows.append(row)
    out.mkdir(parents=True, exist_ok=True)
    (out / f"{cell.name}.faults.json").write_text(json.dumps(rows, indent=1))


def record_trace(cell, seconds: float, out: Path, devices) -> None:
    import jax
    from chipbench import trace
    prep = cell.runner.prepare(cell, 1, devices, log)
    tmp = tempfile.mkdtemp(prefix="chipbench-trace-")
    try:
        trace.start(tmp)
        outs, ends = cell.runner.jobs(prep, seconds)
        jax.profiler.stop_trace()
        xplane = next(Path(tmp).rglob("*.xplane.pb"))
        out.mkdir(parents=True, exist_ok=True)
        name = f"{cell.name}.scale{cell.config['scale']}"
        with open(xplane, "rb") as src, \
                gzip.open(out / f"{name}.xplane.pb.gz", "wb") as dst:
            shutil.copyfileobj(src, dst)
        summary = trace.reduce(xplane, [d.id for d in devices])
        facts = {"recorded": f"{devices[0].device_kind}, chipbench/"
                             f"calibrate.py trace --scale "
                             f"{cell.config['scale']} --seconds {seconds:g}",
                 "supersteps": [int(s) for _, s in outs], "host_ends": ends,
                 "busy_s": summary.busy_s, "window_s": summary.window_s}
        (out / f"{name}.json").write_text(json.dumps(facts, indent=1) + "\n")
        log(json.dumps(facts))
    finally:
        shutil.rmtree(tmp)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("mode", choices=("readings", "faults", "trace"))
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--scale", type=int)
    ap.add_argument("--seconds", type=float, default=0.0)
    ap.add_argument("--out", type=Path, required=True)
    args = ap.parse_args(argv)

    bench = harness.load_benchmark(ROOT)
    cell = harness.load_cell(ROOT, BENCH_DIR, bench, args.workload)
    if args.scale:
        cell = dataclasses.replace(cell, config={**cell.config,
                                                 "scale": args.scale})
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    import jax
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    peaks = json.loads((BENCH_DIR / "peaks.json").read_text())
    try:
        devices, _ = harness.accelerator(cell.chips, peaks)
    except harness.NoAccelerator as e:
        print(e, file=sys.stderr)
        return 2
    devices = devices[:cell.chips]
    ints = lambda s: [int(x) for x in s.split(",") if x]
    if args.mode == "readings":
        readings(cell, ints(args.seeds), ints(args.control_seeds), args.out,
                 devices)
    elif args.mode == "faults":
        fault_readings(cell, ints(args.seeds)[0], args.out, devices)
    else:
        record_trace(cell, args.seconds, args.out, devices)
    return 0


if __name__ == "__main__":
    sys.exit(main())
