"""Connected components by min-label propagation, run to its fixed point.

Program: `repro.core.algorithms.cc_program()` (GRE paper Fig. 3c), on a
symmetrized graph, under the engine's default plan: the active set shrinks
from every vertex to a few, and the plan decides per partition whether
the later supersteps compact.

Reference: scipy's connected components of the same edges, each vertex
labelled with the smallest vertex id in its component, as `ref_cc` of
`chip_smoke.py` computes it.

Compared: `labels_wrong`, the number of vertices whose label differs from
the reference's; float32 holds every id below 2**24 exactly, so the limit
is 0.

Control: the same reference with the labels held in bfloat16, the
narrower state a later change might choose: each component's smallest
label is then taken over the bfloat16 ids.
"""
from __future__ import annotations

import numpy as np


def build(traffic: dict, edges):
    """The engine program and its source (none: every vertex starts)."""
    from repro.core.algorithms import cc_program
    return cc_program(), None


def reference(edges, traffic: dict, label_dtype=np.float32) -> np.ndarray:
    from scipy.sparse import csr_matrix
    from scipy.sparse.csgraph import connected_components
    n = edges.num_vertices
    adj = csr_matrix((np.ones(edges.num_edges, np.int8),
                      (edges.src, edges.dst)), shape=(n, n))
    count, comp = connected_components(adj, directed=False)
    ids = np.arange(n, dtype=np.float32).astype(label_dtype).astype(
        np.float32)
    low = np.full(count, np.inf, np.float32)
    np.minimum.at(low, comp, ids)
    return low[comp]


def control(edges, traffic: dict) -> np.ndarray:
    import ml_dtypes
    return reference(edges, traffic, label_dtype=ml_dtypes.bfloat16)


def compare(got: np.ndarray, want: np.ndarray) -> dict:
    return {"labels_wrong": int(np.count_nonzero(got != want))}
