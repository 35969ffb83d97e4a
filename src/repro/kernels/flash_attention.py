"""Pallas TPU flash attention (forward) for the LM architectures.

Blocked online-softmax (FlashAttention, arXiv:2205.14135, adapted to the TPU
memory hierarchy): grid (batch·kv_head·group, q blocks, kv blocks); the kv
dimension is the innermost (sequential) grid axis so the output block and the
running (m, l) statistics live in VMEM scratch across kv steps.  Causal
masking skips fully-masked kv blocks via `pl.when` (no wasted MXU work).

Block sizes default to (128, 512): q/k tiles are multiples of the 128-lane
MXU; VMEM per step = Bq·D + Bk·D + Bq·Bk floats ≪ 16 MB.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import on_backend

NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
            scale: float, causal: bool, block_q: int, block_k: int,
            n_k: int):
    iq = pl.program_id(1)
    ik = pl.program_id(2)

    @pl.when(ik == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    run = (not causal) or (iq * block_q + block_q - 1 >= ik * block_k)

    @pl.when(run)
    def _step():
        q = q_ref[0]                                        # [Bq, D]
        k = k_ref[0]                                        # [Bk, D]
        v = v_ref[0]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if causal:
            qpos = iq * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            kpos = ik * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            s = jnp.where(qpos >= kpos, s, NEG_INF)
        m_prev, l_prev = m_scr[...], l_scr[...]
        m_new = jnp.maximum(m_prev, s.max(axis=1))
        p = jnp.exp(s - m_new[:, None])
        corr = jnp.exp(m_prev - m_new)
        l_scr[...] = l_prev * corr + p.sum(axis=1)
        pv = jax.lax.dot_general(p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        acc_scr[...] = acc_scr[...] * corr[:, None] + pv
        m_scr[...] = m_new

    @pl.when(ik == n_k - 1)
    def _finalize():
        l = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0] = (acc_scr[...] / l[:, None]).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("causal", "block_q", "block_k"))
def flash_attention_pallas(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                           causal: bool = True, block_q: int = 128,
                           block_k: int = 512) -> jnp.ndarray:
    """q [BH, Sq, D], k/v [BH, Sk, D] (heads flattened into batch; GQA is
    handled by the ops.py wrapper which expands kv heads).  Compiled on a
    TPU, interpreted on CPU (`repro.kernels.on_backend`)."""
    bh, sq, d = q.shape
    sk = k.shape[1]
    block_q = min(block_q, sq)
    block_k = min(block_k, sk)
    assert sq % block_q == 0 and sk % block_k == 0, (sq, block_q, sk, block_k)
    n_q, n_k = sq // block_q, sk // block_k
    scale = 1.0 / np.sqrt(d)
    return on_backend(functools.partial(
        _call, scale=scale, causal=causal, block_q=block_q, block_k=block_k,
        n_q=n_q, n_k=n_k), q, k, v)


def _call(q, k, v, *, scale: float, causal: bool, block_q: int,
          block_k: int, n_q: int, n_k: int, interpret: bool):
    bh, sq, d = q.shape
    return pl.pallas_call(
        functools.partial(_kernel, scale=scale, causal=causal,
                          block_q=block_q, block_k=block_k, n_k=n_k),
        grid=(bh, n_q, n_k),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, sq, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q,), jnp.float32),
            pltpu.VMEM((block_q,), jnp.float32),
            pltpu.VMEM((block_q, d), jnp.float32),
        ],
        interpret=interpret,
    )(q, k, v)
