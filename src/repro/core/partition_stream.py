"""Replication-aware streaming edge partitioning with bounded state.

The paper's greedy loader heuristic (Eq. 8, `repro.core.partition`) scores
only *presence* — has partition i seen this src/dst before — which on
power-law graphs replicates hubs and tails indiscriminately.  HDRF
("High-Degree Replicated First", Petroni et al.; the degree-aware family
surveyed in "Distributed Edge Partitioning for Graph Processing") weights
the affinity term by the endpoints' PARTIAL DEGREES observed so far in the
stream: when an edge must split a vertex across partitions, prefer
replicating the higher-degree endpoint — its replicas amortize over many
edges, while low-degree vertices stay whole.  Lower replication is lower
Agent-Graph cut: fewer combiners/scatters, fewer remote-destination edges
(`partition_quality.remote_dst_edge_fraction`), less exchange traffic.

Loader state is BOUNDED and packed (docs/partitioning.md):

  * per-vertex partition membership — one bitset row per vertex,
    ``ceil(k / 64)`` uint64 words: ``V * ceil(k/64) * 8`` bytes;
  * partial degree counters — ``V`` int32: ``4 * V`` bytes;
  * per-partition edge counts — ``k`` int64.

Total ``V*ceil(k/64)*8 + 4*V + 8*k`` bytes (`hdrf_state_bytes`), the
O(V·k/8 + V + k) bound the memory benchmark asserts — against the
O(2·k·V) bools the un-packed greedy loader used to carry.

Everything here is host-side numpy streaming over the chunk-source
protocol (`graph.structures.EdgeChunkSource`): the partitioner reads the
edge stream once, chunk by chunk, and never needs the whole edge list in
memory — the same pipeline the chunked `build_agent_graph` ingress rides.
"""
from __future__ import annotations

from typing import Dict, Optional

import numpy as np

from repro.graph.structures import as_chunk_source
from repro.spans import span

HDRF_EPS = 1.0  # balance-term regularizer (Δ-analog of partition.DELTA)


# --------------------------------------------------------------- bitsets
def make_bitset(rows: int, bits: int) -> np.ndarray:
    """Packed boolean matrix `[rows, bits]` as `[rows, ceil(bits/64)]`
    uint64 — bit ``b`` of row ``r`` lives in word ``b >> 6``."""
    return np.zeros((rows, (bits + 63) >> 6), dtype=np.uint64)


def bitset_rows(bs: np.ndarray, rows: np.ndarray, bits: int) -> np.ndarray:
    """Gather `[bits, len(rows)]` 0/1 membership for a batch of rows."""
    j = np.arange(bits)
    words = bs[rows][:, j >> 6]                      # [b, bits] uint64
    return ((words >> (j & 63).astype(np.uint64)) & np.uint64(1)).T


def bitset_set(bs: np.ndarray, rows: np.ndarray, bit: np.ndarray) -> None:
    """Set per-row bits in place (`bit[i]` of `rows[i]`); duplicate
    (row, bit) pairs within the batch OR harmlessly."""
    np.bitwise_or.at(bs, (rows, bit >> 6),
                     np.uint64(1) << (bit & 63).astype(np.uint64))


def bitset_popcount(bs: np.ndarray) -> int:
    """Total set bits (Σ_v |A(v)| — the partitioner's replica count)."""
    return int(np.unpackbits(bs.view(np.uint8)).sum())


# ------------------------------------------------------ state-byte models
def hdrf_state_bytes(num_vertices: int, k: int) -> int:
    """The documented HDRF loader-state bound: packed membership bitset +
    int32 partial degrees + int64 partition loads."""
    return (num_vertices * ((k + 63) >> 6) * 8     # membership bitset
            + 4 * num_vertices                     # partial degrees
            + 8 * k)                               # edge loads


def greedy_state_bytes(num_vertices: int, k: int,
                       num_loaders: int = 1) -> int:
    """Per the packed rewrite of `partition.greedy_partition`: TWO packed
    `[k, ceil(V/64)]` bitsets (src/dst presence) + loads, per loader."""
    return num_loaders * (2 * k * ((num_vertices + 63) >> 6) * 8 + 8 * k)


# ------------------------------------------------------------------ HDRF
@span("gre.ingress.hdrf")
def hdrf_partition(graph, k: int, *, lam: float = 1.0,
                   batch_size: int = 256, seed: int = 0,
                   chunk_size: Optional[int] = None,
                   stats: Optional[Dict] = None) -> np.ndarray:
    """HDRF streaming edge placement.

    For edge (u, v) with partial degrees δ(u), δ(v) — counts of stream
    occurrences so far — and θ = δ(u) / (δ(u) + δ(v)):

      score(i) = g(u,i) + g(v,i) + λ · (Max − Ne(i)) / (ε + Max − Min)

      g(u,i) = 1 + (1 − θ)  if i ∈ A(u) else 0      (A = replica set)
      g(v,i) = 1 + θ        if i ∈ A(v) else 0

    The degree normalization is the whole trick: an existing replica of
    the LOWER-degree endpoint scores higher, so ties split by replicating
    the hub — whose copies amortize over its many remaining edges —
    while tail vertices stay on one partition.  λ trades replication for
    balance: λ→0 is pure affinity (lowest replication, worst balance),
    large λ approaches round-robin (perfect balance, hash-like
    replication); replication is monotone non-decreasing in λ.

    `graph` may be a `Graph` or any `EdgeChunkSource`; edges stream chunk
    by chunk and, inside each chunk, score in batches of `batch_size`
    (degrees and replica sets update per batch — `batch_size=1` is the
    exact per-edge stream, matching GRE-S vs GRE-P in the greedy loader).
    Deterministic for a fixed seed (the tiny rng tie-break is the only
    randomness).  `stats`, when given, is filled with the measured
    `state_bytes`, `replication` (Σ|A(v)|), and `replication_factor`.
    The whole placement is the host span `gre.ingress.hdrf` (`repro.spans`).
    """
    source = as_chunk_source(graph, chunk_size or (1 << 18))
    V, E = source.num_vertices, source.num_edges
    part = np.zeros(E, dtype=np.int32)
    member = make_bitset(V, k)                    # A(v): replica bitsets
    deg = np.zeros(V, dtype=np.int32)             # partial degrees
    ne = np.zeros(k, dtype=np.int64)              # per-partition edges
    rng = np.random.default_rng(seed)
    for chunk in source.chunks():
        for lo in range(0, chunk.num_edges, batch_size):
            u = chunk.src[lo:lo + batch_size]
            v = chunk.dst[lo:lo + batch_size]
            np.add.at(deg, u, 1)
            np.add.at(deg, v, 1)
            du = deg[u].astype(np.float64)
            theta = du / (du + deg[v])            # [b]
            g_u = bitset_rows(member, u, k) * (2.0 - theta)   # [k, b]
            g_v = bitset_rows(member, v, k) * (1.0 + theta)
            mx, mn = ne.max(), ne.min()
            bal = lam * (mx - ne) / (HDRF_EPS + mx - mn)      # [k]
            score = g_u + g_v + bal[:, None]
            score += rng.random(score.shape) * 1e-9           # tie-break
            idx = np.argmax(score, axis=0).astype(np.int32)
            part[chunk.offset + lo:chunk.offset + lo + u.shape[0]] = idx
            bitset_set(member, u, idx)
            bitset_set(member, v, idx)
            np.add.at(ne, idx, 1)
    if stats is not None:
        rep = bitset_popcount(member)
        stats["state_bytes"] = member.nbytes + deg.nbytes + ne.nbytes
        stats["replication"] = rep
        stats["replication_factor"] = rep / max(V, 1)
    return part


# -------------------------------------------------------------- registry
def _greedy(graph, k, **kw):
    from repro.core.partition import greedy_partition
    return greedy_partition(graph, k, **kw)


def _hash(graph, k, **kw):
    from repro.core.partition import hash_partition
    return hash_partition(graph, k, **kw)


PARTITIONERS = {
    "hdrf": hdrf_partition,   # replication-aware degree-weighted streaming
    "greedy": _greedy,        # the paper's Eq. 8 presence heuristic
    "hash": _hash,            # random vertex sharding baseline
}


def partition_edges(graph, k: int, method: str = "hdrf",
                    **kw) -> np.ndarray:
    """Name-dispatched edge partitioning — the hook `build_agent_graph`
    uses when handed a partitioner NAME instead of a placement array (the
    name is then recorded on `AgentGraph.partitioner` and folded into the
    tuned-plan cache key, `repro.tuning.fingerprint`)."""
    if method not in PARTITIONERS:
        raise ValueError(f"unknown partitioner {method!r}; "
                         f"choose from {sorted(PARTITIONERS)}")
    return PARTITIONERS[method](graph, k, **kw)
