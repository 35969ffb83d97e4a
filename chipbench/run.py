#!/usr/bin/env python3
"""Run one cell of BENCHMARK.json once, on the TPU this machine holds.

    python3 chipbench/run.py --workload rmat22.pagerank --seed 7 \\
        --seconds 10 --trace 0

The cell's configuration, traffic, generator, program, runner and metric
readers are files found by name (`chipbench/harness.py`).  Set-up runs
from process start to the window's start (`setup_s`); the window measures
whole jobs for at least `--seconds`; then the program's output is checked
against a plain reference.  `--trace 1` records the window with the
profiler and reports the per-layer metrics instead of the end-to-end ones.

Progress goes to earlier stdout lines; the numbers compared, each beside
its limit, are the last lines on stderr; the last stdout line is the
result object.  Without a TPU, with fewer chips than the cell needs, or
on a chip without a row in `peaks.json`, the command exits 2 and prints
no result.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH_DIR = Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from chipbench import harness  # noqa: E402


def log(*parts) -> None:
    print(*parts, flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    bench = harness.load_benchmark(ROOT)
    cell = harness.load_cell(ROOT, BENCH_DIR, bench, args.workload)
    entries = harness.metrics_for(bench, cell.name, bool(args.trace))
    peaks = json.loads((BENCH_DIR / "peaks.json").read_text())

    from repro.compile_cache import enable_compile_cache
    cache = enable_compile_cache()
    import jax
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    try:
        devices, peak = harness.accelerator(cell.chips, peaks)
    except harness.NoAccelerator as e:
        print(e, file=sys.stderr, flush=True)
        return 2
    log(f"cell {cell.name} seed {args.seed}: {devices[0].device_kind} "
        f"x{len(devices)}; compile cache {cache}")

    record = cell.runner.run(cell, args.seed, args.seconds,
                             bool(args.trace), T_START, peak, log)
    metrics = harness.read_metrics(BENCH_DIR, entries, record, cell)
    line = harness.result(record, metrics, devices)
    for name, (value, limit) in record.compared.items():
        print(f"compared {name}: {value!r} (limit {limit!r})",
              file=sys.stderr, flush=True)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
