"""End-to-end behaviour: the training loss actually goes down, serving
generates, and the distributed graph engine solves a real workload through
the full public API (the paper's PageRank-on-R-MAT scenario, CPU-scaled)."""
import numpy as np


def test_lm_training_reduces_loss():
    from repro.launch import train
    loss = train.main(["--arch", "smollm-135m", "--steps", "60",
                       "--batch", "8", "--seq", "64", "--lr", "1e-2"])
    assert loss < 6.5  # ln(1024)=6.93 at random init; must have learned


def test_serving_generates_tokens():
    from repro.launch import serve
    gen = serve.main(["--arch", "smollm-135m", "--batch", "2",
                      "--prompt-len", "16", "--gen", "4"])
    assert gen.shape == (2, 4)
    assert np.asarray(gen).min() >= 0


def test_paper_workload_end_to_end():
    """Paper §7 scenario at CPU scale: greedy-partition an R-MAT graph,
    build the agent-graph, run PageRank + SSSP via the public API, and check
    the partition-quality claims hold on this graph."""
    from repro.core import algorithms
    from repro.core.agent_graph import build_agent_graph
    from repro.core.engine import DevicePartition, GREEngine
    from repro.core.partition import (greedy_partition, hash_partition,
                                      partition_quality)
    from repro.graph.generators import rmat_edges

    g = rmat_edges(scale=9, edge_factor=8, seed=0, weights=True).dedup()
    part = greedy_partition(g, 8, batch_size=64)
    q = partition_quality(g, part)
    qh = partition_quality(g, hash_partition(g, 8))
    assert q.equivalent_edge_cut < qh.equivalent_edge_cut   # Fig. 11b
    assert q.agent_comm <= q.vertexcut_comm                 # §5.1 bound
    ag = build_agent_graph(g, part, 8)
    assert int(ag.edge_mask.sum()) == g.num_edges

    sp = DevicePartition.from_graph(g)
    eng = GREEngine(algorithms.pagerank_program())
    out = eng.run(sp, eng.init_state(sp), max_steps=30)
    pr = np.asarray(out.vertex_data)
    assert np.isfinite(pr).all() and pr.min() >= 0.15 - 1e-5


def test_compile_cache_location(monkeypatch, tmp_path):
    """The persistent compile cache goes where JAX_COMPILATION_CACHE_DIR
    says (JAX reads the variable itself, so nothing is set in code), and
    otherwise to the fixed `.jax_cache` at the repository root."""
    from pathlib import Path

    import jax
    from repro import compile_cache
    was = jax.config.jax_compilation_cache_dir
    try:
        jax.config.update("jax_compilation_cache_dir", None)
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        repo_cache = Path(__file__).resolve().parents[1] / ".jax_cache"
        assert compile_cache.REPO_CACHE == repo_cache
        assert compile_cache.enable_compile_cache() == str(repo_cache)
        assert jax.config.jax_compilation_cache_dir == str(repo_cache)

        jax.config.update("jax_compilation_cache_dir", None)
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        assert compile_cache.enable_compile_cache() == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir is None
    finally:
        jax.config.update("jax_compilation_cache_dir", was)
