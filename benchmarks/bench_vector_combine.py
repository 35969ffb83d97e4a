"""Vector-payload aggregation benchmark (the GNN/BC workload family).

Times one engine superstep of `gnn_aggregate_program` — a [E, D] → [V, D]
scatter-combine with D-dimensional feature payloads — through both combine
paths:

  xla    — fused gather → segment-sum (the default hot path);
  pallas — `segment_combine_pallas`: dst-sorted edge blocks reduced by
           block-local one-hot matmuls on the MXU (interpret mode on CPU,
           so the CPU number measures dispatch overhead, not MXU speed).

The D=64 payload is the acceptance shape: engine messages are feature
vectors, scalars are just D=().
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import emit, time_fn
from repro.core.algorithms import gnn_aggregate_program
from repro.core.engine import DevicePartition, EngineState, GREEngine
from repro.graph.generators import rmat_edges


def _state(part, h):
    v, d = part.num_masters, h.shape[-1]
    sd = jnp.zeros((part.num_slots, d), h.dtype).at[:v].set(h)
    return EngineState(
        vertex_data=jnp.zeros((v, d), h.dtype), scatter_data=sd,
        active_scatter=jnp.ones(part.num_slots, dtype=bool).at[v].set(False),
        step=jnp.zeros((), jnp.int32))


def run(scale: int = 10, edge_factor: int = 8, d_feat: int = 64,
        iters: int = 5, pallas: bool = True):
    g = rmat_edges(scale=scale, edge_factor=edge_factor, seed=0).dedup()
    part = DevicePartition.from_graph(g)
    rng = np.random.default_rng(0)
    h = jnp.asarray(rng.normal(size=(g.num_vertices, d_feat)), jnp.float32)
    program = gnn_aggregate_program(d_feat)
    paths = [("xla", GREEngine(program, use_pallas=False))]
    if pallas:
        paths.append(("pallas", GREEngine(program, use_pallas=True)))
    out = {}
    for name, eng in paths:
        step = jax.jit(lambda s, e=eng: e.superstep(part, s))
        us = time_fn(step, _state(part, h), iters=iters)
        eps = g.num_edges * d_feat / (us / 1e6)
        emit(f"vector_combine_d{d_feat}_rmat{scale}_{name}", us,
             f"V={g.num_vertices};E={g.num_edges};payload_elems_per_s={eps:.3g}",
             edges=g.num_edges)
        out[name] = us
    return out


def main():
    run(scale=10)
    run(scale=12, pallas=False)  # larger graph, XLA path only (CPU interpret
    #                              mode makes Pallas timing meaningless there)


if __name__ == "__main__":
    main()
