"""Benchmark harness: one module per paper table/figure.

  python -m benchmarks.run                         # all
  python -m benchmarks.run pagerank                # one
  python -m benchmarks.run --smoke                 # CI: tiny config per suite
  python -m benchmarks.run --smoke --json OUT.json # CI: + perf artifact

``--json`` writes the machine-readable results (per-benchmark
us_per_call and, where meaningful, ns/edge) for the CI regression gate
(`benchmarks/compare.py` against the committed BENCH_baseline.json).
Human-readable ``name,us_per_call,derived`` CSV always goes to stdout.
"""
import json
import platform
import sys

import jax

from benchmarks import (bench_async, bench_exchange_overlap, bench_frontier,
                        bench_gas_vs_sc, bench_incremental, bench_memory,
                        bench_pagerank, bench_partition, bench_serving,
                        bench_traversal, bench_tuning, bench_vector_combine,
                        bench_weak, common)
from repro.compile_cache import enable_compile_cache

SUITES = {
    "pagerank": bench_pagerank.main,     # Table 5 / Fig. 8a-b
    "traversal": bench_traversal.main,   # Fig. 8c-d
    "frontier": bench_frontier.main,     # dense vs compacted frontier
    "exchange_overlap": bench_exchange_overlap.main,  # §6.2 pipelined flush
    "async": bench_async.main,           # bounded-staleness ring vs sync
    "weak": bench_weak.main,             # Fig. 10
    "partition": bench_partition.main,   # Fig. 11/12/13 + §5.1
    "memory": bench_memory.main,         # §7.1.2 memory claim
    "gas_vs_sc": bench_gas_vs_sc.main,   # §2.2 motivation
    "vector": bench_vector_combine.main, # D=64 feature-vector payloads
    "tuning": bench_tuning.main,         # plan autotuner vs defaults
    # serving is ALSO a standalone CI job (`python -m benchmarks.bench_serving
    # --smoke --json ...` gated with `compare.py --only serving_`); the full
    # suite runs it at full scale here
    "serving": bench_serving.main,       # continuous batching vs re-init
    "incremental": bench_incremental.main,  # warm start vs cold restart
}

# Reduced-scale configs for the CI smoke run (seconds, not minutes); suites
# without an entry fall back to their full run.
SMOKE = {
    "pagerank": lambda: bench_pagerank.run(scale=8, iters=2),
    # powerlaw iters=7: the bucketed entry's many small per-bucket ops are
    # scheduler-sensitive on 2-core hosts; a wider median keeps the gated
    # value out of the bimodal tails
    "frontier": lambda: (bench_frontier.run(scale=12, iters=2),
                         bench_frontier.run_powerlaw(scale=11, iters=7),
                         bench_frontier.run_powerlaw_pallas(scale=11,
                                                            iters=3)),
    "exchange_overlap": lambda: bench_exchange_overlap.run(scale=10, k=2,
                                                           steps=24, iters=9),
    # the >= 1.3x flush-amortization floor is asserted inside the bench
    "async": lambda: bench_async.run(n=512, iters=3, n_ba=256),
    "vector": lambda: bench_vector_combine.run(scale=8, d_feat=64, iters=2),
    # powerlaw iters=7: the tuned-vs-default comparison is interleaved,
    # but the ~3ms BA runs still need a wide median on 2-core hosts
    "tuning": lambda: (bench_tuning.run(scale=11, iters=3),
                       bench_tuning.run_powerlaw(scale=10, iters=7)),
    # the >= 3x edge-scan payoff floor is asserted inside the bench
    "incremental": lambda: (bench_incremental.run(scale=10, iters=3),
                            bench_incremental.run_circulant(scale=10,
                                                            iters=3)),
    # the >= 15% HDRF-vs-greedy remote-dst floor is asserted inside run();
    # run_dist's exchange-volume reduction is asserted inside run_dist()
    "partition": lambda: (bench_partition.run(scale=11, ks=(4, 16)),
                          bench_partition.run_dist(scale=9, k=4, iters=3)),
    # byte models + chunked==monolithic ingress assert inside run()
    "memory": lambda: bench_memory.run(scale=11, k=16, chunk_size=1 << 13),
}


def main() -> None:
    args = sys.argv[1:]
    smoke = "--smoke" in args
    if smoke:
        args.remove("--smoke")
    json_path = None
    if "--json" in args:
        i = args.index("--json")
        try:
            json_path = args[i + 1]
        except IndexError:
            sys.exit("--json needs an output path")
        del args[i:i + 2]
    enable_compile_cache()
    wanted = args or list(SMOKE if smoke else SUITES)
    unknown = [n for n in wanted if n not in SUITES]
    if unknown:
        sys.exit(f"unknown suite(s) {unknown}; choose from {list(SUITES)}")
    print("name,us_per_call,derived")
    for name in wanted:
        if smoke and name in SMOKE:
            SMOKE[name]()
        else:
            SUITES[name]()
    if json_path:
        dev = jax.devices()[0]
        payload = {
            "mode": "smoke" if smoke else "full",
            "python": platform.python_version(),
            "machine": platform.machine(),
            "platform": dev.platform,
            "device_kind": dev.device_kind,
            "device_count": jax.device_count(),
            "results": common.RESULTS,
        }
        with open(json_path, "w") as f:
            json.dump(payload, f, indent=2)
            f.write("\n")
        print(f"wrote {len(common.RESULTS)} results to {json_path}",
              file=sys.stderr)


if __name__ == "__main__":
    main()
