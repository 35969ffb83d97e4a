"""jit'd wrappers dispatching to the Pallas kernels — Mosaic-compiled on a
TPU, interpreted on CPU (`repro.kernels.on_backend`).  These are the call
sites models use via `use_pallas` flags.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.flash_attention import flash_attention_pallas
from repro.kernels.segment_combine import (BLOCK_E, BLOCK_V,
                                           build_block_table,
                                           segment_combine_pallas)


def segment_combine(msgs: jnp.ndarray, dst: jnp.ndarray, num_segments: int,
                    op: str = "sum", table: Optional[jnp.ndarray] = None,
                    block_e: int = BLOCK_E,
                    block_v: int = BLOCK_V) -> jnp.ndarray:
    """Scatter-combine ⊕ along dst-sorted edges: msgs [E] or [E, D] to
    [num_segments] or [num_segments, D].

    `table` is the ingress-time block schedule of the (dst-sorted) `dst`
    column (segment_combine.build_block_table).  Without one, `dst` must be
    concrete: it is sorted on the host and its schedule built here — graph
    workloads have static topology, built once at ingress.  A traced `dst`
    without a table is an error: the kernel cannot schedule blocks it
    cannot see.
    """
    if table is None:
        if isinstance(dst, jax.core.Tracer):
            raise ValueError(
                "Pallas segment_combine got a traced dst column and no "
                "block table: build the schedule at ingress "
                "(segment_combine.build_block_table) and pass `table=`")
        dst_np = np.asarray(dst)
        order = np.argsort(dst_np, kind="stable")
        dst_np = dst_np[order]
        msgs = msgs[jnp.asarray(order)]
        dst = jnp.asarray(dst_np)
        table = jnp.asarray(build_block_table(dst_np, num_segments,
                                              block_e, block_v))
    out = segment_combine_pallas(msgs, dst, table, num_segments, op,
                                 block_e=block_e, block_v=block_v)
    return out.astype(msgs.dtype)


def flash_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                    causal: bool = True, block_q: int = 128,
                    block_k: int = 512) -> jnp.ndarray:
    """GQA wrapper: q [B, Sq, Kv, G, H], k/v [B, Sk, Kv, H] — expands kv
    heads across the group dim and flattens (B, Kv, G) into the kernel's
    batch axis."""
    B, Sq, Kv, G, H = q.shape
    Sk = k.shape[1]
    qf = q.transpose(0, 2, 3, 1, 4).reshape(B * Kv * G, Sq, H)
    kf = jnp.broadcast_to(k.transpose(0, 2, 1, 3)[:, :, None],
                          (B, Kv, G, Sk, H)).reshape(B * Kv * G, Sk, H)
    vf = jnp.broadcast_to(v.transpose(0, 2, 1, 3)[:, :, None],
                          (B, Kv, G, Sk, H)).reshape(B * Kv * G, Sk, H)
    o = flash_attention_pallas(qf, kf, vf, causal=causal, block_q=block_q,
                               block_k=block_k)
    return o.reshape(B, Kv, G, Sq, H).transpose(0, 3, 1, 2, 4)


def embedding_bag(table: jnp.ndarray, ids: jnp.ndarray, bag_ids: jnp.ndarray,
                  num_bags: int, weights=None,
                  seg_table=None) -> jnp.ndarray:
    """EmbeddingBag = XLA gather (vocab-scale tables stay in HBM; TPU has no
    VMEM-resident gather for 10⁷-row tables) + Pallas segment-combine for the
    bag reduction (the hot ⊕)."""
    rows = jnp.take(table, ids, axis=0)
    if weights is not None:
        rows = rows * weights[:, None]
    return segment_combine(rows, bag_ids, num_bags, "sum", table=seg_table)
