"""PageRank: a fixed number of supersteps with every vertex active.

Program: `repro.core.algorithms.pagerank_program()` (GRE paper Fig. 3a),
under the engine's default plan.

Reference: the float64 power iteration pr <- 0.15 + 0.85 * A^T (pr / outdeg)
from pr = 1, over the same edges, as `ref_pagerank` of `chip_smoke.py`
computes it, with the product taken by a scipy sparse matrix.

Compared: `max_rel_err`, the largest |got - ref| / ref over all vertices
(ref >= 0.15 everywhere).

Control: the same reference with each superstep's messages pr / outdeg
held in bfloat16, the storage a later change might choose for the
gathered values; sums stay in float64, so every other difference from
the reference is left out.
"""
from __future__ import annotations

import numpy as np

DAMPING = 0.85


def build(traffic: dict, edges):
    """The engine program and its source (none: every vertex starts)."""
    from repro.core.algorithms import pagerank_program
    return pagerank_program(), None


def reference(edges, traffic: dict, message_dtype=np.float64) -> np.ndarray:
    from scipy.sparse import csr_matrix
    n = edges.num_vertices
    deg = np.maximum(np.bincount(edges.src, minlength=n), 1).astype(
        np.float64)
    into = csr_matrix((np.ones(edges.num_edges), (edges.dst, edges.src)),
                      shape=(n, n))
    pr = np.ones(n)
    for _ in range(traffic["max_steps"]):
        msg = (pr / deg).astype(message_dtype).astype(np.float64)
        pr = (1.0 - DAMPING) + DAMPING * (into @ msg)
    return pr


def control(edges, traffic: dict) -> np.ndarray:
    import ml_dtypes
    return reference(edges, traffic, message_dtype=ml_dtypes.bfloat16)


def compare(got: np.ndarray, want: np.ndarray) -> dict:
    rel = np.abs(got.astype(np.float64) - want) / want
    worst = float(np.max(rel))
    return {"max_rel_err": worst if np.isfinite(worst) else float("inf")}


def superstep_bytes(num_vertices: int, num_edges: int) -> int:
    """HBM bytes one dense PageRank superstep needs, whatever route runs it.

    4 B per edge: its source id (a destination-sorted layout names the
    destination by its segment), plus per vertex a 4 B segment offset and
    four 4 B values: the outgoing share read for the gather, the
    out-degree read and the new rank and new share written.  This is the
    least any route must move: one that keeps the 16 MB value array in
    on-chip memory still reads every source id once.

        bytes = 4 E + 20 V + 4
    """
    return 4 * num_edges + 20 * num_vertices + 4
