"""The chip benchmark of the GRE engine: one cell of `BENCHMARK.json`, run
once on a TPU, from data files (see `chipbench/run.py`)."""
