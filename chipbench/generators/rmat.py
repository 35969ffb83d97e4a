"""Graph500 R-MAT graphs, made on the device from a seed.

The Kronecker process (Graph500 specification; GRE paper Sec. 7): each of
`edge_factor * 2**scale` edges picks its (src, dst) quadrant bit by bit,
with probabilities a, b, c and d = 1 - a - b - c.  Its draws come from the
configuration's fixed `structure_seed`, so every run measures the same
graph structure.  Every vertex label is then permuted, as Graph500 and
GAP do.  The permutation comes from the configuration's `label_seed`
where it names one (one fixed instance, as GAP's Kron graph is; the
number of supersteps of min-label CC follows where label 0 lands), and
from `--seed` otherwise.  `--seed` always draws the edge weights.

Self loops and repeated edges are dropped after an on-device sort by
(src, dst); with `symmetrize`, each undirected pair is kept once and
emitted in both directions (the GAP / Graphalytics input for CC).  One
jitted call makes the edges; the host copies them once.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.harness import EdgeList


def seed_key(seed: int):
    """A PRNG key from any whole number: the low and high 32 bits both
    count (a plain `jax.random.key` keeps only 32 of them)."""
    seed %= 1 << 64
    return jax.random.fold_in(jax.random.key(seed & 0xFFFFFFFF), seed >> 32)


@functools.partial(jax.jit, static_argnames=("scale", "edge_factor", "a",
                                             "b", "c", "symmetrize"))
def rmat_edges(structure_key, label_key, *, scale: int, edge_factor: int,
               a: float, b: float, c: float, symmetrize: bool):
    """Sorted (src, dst) of every generated edge, and the mask of those
    kept: the first of each (src, dst) pair, no self loops."""
    n, m = 1 << scale, edge_factor << scale
    ab = a + b

    def one_bit(i, edges):
        src, dst = edges
        key = jax.random.fold_in(structure_key, i)
        # two separate draws fuse into the bit update; one [2, m] draw
        # would be materialised on the device (0.5 GB at scale 22)
        src_bit = jax.random.uniform(jax.random.fold_in(key, 0), (m,)) >= ab
        dst_bit = (jax.random.uniform(jax.random.fold_in(key, 1), (m,))
                   >= jnp.where(src_bit, c / (1.0 - ab), a / ab))
        return (src | (src_bit.astype(jnp.int32) << i),
                dst | (dst_bit.astype(jnp.int32) << i))

    zero = jnp.zeros(m, jnp.int32)
    src, dst = jax.lax.fori_loop(0, scale, one_bit, (zero, zero))
    label = jax.random.permutation(label_key, n).astype(jnp.int32)
    src, dst = label[src], label[dst]
    if symmetrize:
        src, dst = jnp.minimum(src, dst), jnp.maximum(src, dst)
    src, dst = jax.lax.sort((src, dst), num_keys=2)
    first = jnp.concatenate([jnp.ones(1, bool),
                             (src[1:] != src[:-1]) | (dst[1:] != dst[:-1])])
    return src, dst, first & (src != dst)


@functools.partial(jax.jit, static_argnames=("count", "low", "high"))
def edge_weights(key, *, count: int, low: int, high: int):
    """Integer weights in [low, high], as float32."""
    return jax.random.randint(key, (count,), low, high + 1,
                              jnp.int32).astype(jnp.float32)


def generate(config: dict, seed: int) -> EdgeList:
    a, b, c, d = (config[k] for k in ("a", "b", "c", "d"))
    if abs(a + b + c + d - 1.0) > 1e-9:
        raise ValueError(f"R-MAT a+b+c+d = {a + b + c + d}, not 1")
    label_key = jax.random.split(seed_key(config.get("label_seed", seed)))[0]
    weight_key = jax.random.split(seed_key(seed))[1]
    src, dst, keep = jax.device_get(rmat_edges(
        seed_key(config["structure_seed"]), label_key,
        scale=config["scale"], edge_factor=config["edge_factor"],
        a=a, b=b, c=c, symmetrize=config["symmetrize"]))
    kept = np.flatnonzero(keep)
    src, dst = src[kept], dst[kept]
    if config["symmetrize"]:
        src, dst = np.concatenate([src, dst]), np.concatenate([dst, src])
    weight = None
    if config.get("weights"):
        low, high = config["weights"]
        weight = np.asarray(edge_weights(weight_key, count=src.shape[0],
                                         low=low, high=high))
    return EdgeList(1 << config["scale"], src, dst, weight)
