"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps + hypothesis."""
import numpy as np
import pytest

pytest.importorskip("hypothesis")
from hypothesis import given, settings, strategies as st

import jax.numpy as jnp

from repro.core.vertex_program import MONOIDS
from repro.kernels import ops, ref
from repro.kernels.segment_combine import (BLOCK_E, build_block_table,
                                           segment_combine_pallas)

RNG = np.random.default_rng(0)


@pytest.mark.parametrize("e,d,v", [(1000, 8, 64), (512, 1, 300),
                                   (2048, 128, 512), (77, 16, 33),
                                   (256, 32, 256), (4096, 64, 128)])
@pytest.mark.parametrize("op", ["sum", "min", "max"])
def test_segment_combine_sweep(e, d, v, op):
    dst = np.sort(RNG.integers(0, v, e)).astype(np.int32)
    msgs = jnp.asarray(RNG.normal(size=(e, d)), jnp.float32)
    out = ops.segment_combine(msgs, jnp.asarray(dst), v, op)
    want = ref.segment_combine_ref(msgs, jnp.asarray(dst), v, op)
    fix = lambda x: jnp.nan_to_num(x, posinf=1e30, neginf=-1e30)
    np.testing.assert_allclose(fix(out), fix(want), rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_segment_combine_dtypes(dtype):
    dst = np.sort(RNG.integers(0, 50, 400)).astype(np.int32)
    msgs = jnp.asarray(RNG.normal(size=(400, 16)), dtype)
    out = ops.segment_combine(msgs, jnp.asarray(dst), 50, "sum")
    want = ref.segment_combine_ref(msgs.astype(jnp.float32),
                                   jnp.asarray(dst), 50, "sum")
    np.testing.assert_allclose(np.asarray(out, np.float32), want,
                               rtol=2e-2, atol=2e-2)


@settings(max_examples=20, deadline=None)
@given(e=st.integers(1, 500), v=st.integers(1, 200),
       d=st.sampled_from([1, 4, 32]), seed=st.integers(0, 2**16))
def test_segment_combine_hypothesis(e, v, d, seed):
    rng = np.random.default_rng(seed)
    dst = np.sort(rng.integers(0, v, e)).astype(np.int32)
    msgs = jnp.asarray(rng.normal(size=(e, d)), jnp.float32)
    out = ops.segment_combine(msgs, jnp.asarray(dst), v, "sum")
    want = ref.segment_combine_ref(msgs, jnp.asarray(dst), v, "sum")
    np.testing.assert_allclose(out, want, rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("masked", [False, True])
@pytest.mark.parametrize("op", ["sum", "min", "max"])
@pytest.mark.parametrize("e", [3 * BLOCK_E + 489, 2 * BLOCK_E + 1])
def test_segment_combine_ragged_scalar(e, op, masked):
    """Scalar messages over an E that is not a multiple of BLOCK_E enter
    the kernel unpadded, and the last edge block's lanes past E (NaN in the
    interpreter) add nothing: the combine equals the XLA scatter-reduce,
    exactly for min and max.  `masked` replaces half the messages by the
    identity, as the dense scan does for inactive sources."""
    v = 700
    rng = np.random.default_rng(e)
    dst = np.sort(rng.integers(0, v + 1, e)).astype(np.int32)
    msgs = rng.normal(size=e).astype(np.float32)
    if masked:
        msgs = np.where(rng.random(e) < 0.5, msgs, MONOIDS[op].identity)
    msgs, dst_j = jnp.asarray(msgs, jnp.float32), jnp.asarray(dst)
    got = ops.segment_combine(msgs, dst_j, v + 1, op,
                              table=jnp.asarray(build_block_table(dst,
                                                                  v + 1)))
    want = MONOIDS[op].segment_reduce(msgs, dst_j, v + 1, True)
    assert got.shape == (v + 1,)
    if op == "sum":
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    else:
        np.testing.assert_array_equal(got, want)


def test_block_table_covers_all_edges():
    dst = np.sort(RNG.integers(0, 1000, 5000)).astype(np.int32)
    table = build_block_table(dst, 1000, block_e=256, block_v=128)
    n_e = -(-5000 // 256)
    n_v = -(-1000 // 128)
    # visits are grouped by dst block, in order, each block at least once
    assert np.all(np.diff(table[0]) >= 0)
    assert set(table[0].tolist()) == set(range(n_v))
    # every edge block with any dst in a v-range is visited for that block
    for i in range(n_v):
        lo, hi = i * 128, (i + 1) * 128
        need = {int(j) for j in range(n_e)
                if ((dst[j * 256:(j + 1) * 256] >= lo)
                    & (dst[j * 256:(j + 1) * 256] < hi)).any()}
        have = {int(x) for x in table[1][table[0] == i] if x < n_e}
        assert need <= have


@pytest.mark.parametrize("b,sq,sk,kv,g,h,causal",
                         [(2, 128, 128, 2, 2, 64, True),
                          (1, 256, 256, 1, 4, 32, True),
                          (2, 128, 128, 2, 1, 64, False),
                          (1, 64, 192, 2, 2, 32, False)])
def test_flash_attention_sweep(b, sq, sk, kv, g, h, causal):
    q = jnp.asarray(RNG.normal(size=(b, sq, kv, g, h)), jnp.float32)
    k = jnp.asarray(RNG.normal(size=(b, sk, kv, h)), jnp.float32)
    v = jnp.asarray(RNG.normal(size=(b, sk, kv, h)), jnp.float32)
    out = ops.flash_attention(q, k, v, causal=causal, block_q=64, block_k=64)
    qf = q.transpose(0, 2, 3, 1, 4).reshape(b * kv * g, sq, h)
    kf = jnp.broadcast_to(k.transpose(0, 2, 1, 3)[:, :, None],
                          (b, kv, g, sk, h)).reshape(-1, sk, h)
    vf = jnp.broadcast_to(v.transpose(0, 2, 1, 3)[:, :, None],
                          (b, kv, g, sk, h)).reshape(-1, sk, h)
    want = ref.flash_attention_ref(qf, kf, vf, causal).reshape(
        b, kv, g, sq, h).transpose(0, 3, 1, 2, 4)
    np.testing.assert_allclose(out, want, rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_dtypes(dtype):
    q = jnp.asarray(RNG.normal(size=(1, 128, 1, 2, 32)), dtype)
    k = jnp.asarray(RNG.normal(size=(1, 128, 1, 32)), dtype)
    v = jnp.asarray(RNG.normal(size=(1, 128, 1, 32)), dtype)
    out = ops.flash_attention(q, k, v, block_q=64, block_k=64)
    qf = q.astype(jnp.float32).transpose(0, 2, 3, 1, 4).reshape(2, 128, 32)
    kf = jnp.broadcast_to(k.astype(jnp.float32).transpose(0, 2, 1, 3)[:, :, None],
                          (1, 1, 2, 128, 32)).reshape(2, 128, 32)
    vf = jnp.broadcast_to(v.astype(jnp.float32).transpose(0, 2, 1, 3)[:, :, None],
                          (1, 1, 2, 128, 32)).reshape(2, 128, 32)
    want = ref.flash_attention_ref(qf, kf, vf, True).reshape(
        1, 1, 2, 128, 32).transpose(0, 3, 1, 2, 4)
    tol = 3e-2 if dtype == jnp.bfloat16 else 3e-5
    np.testing.assert_allclose(np.asarray(out, np.float32), want,
                               rtol=tol, atol=tol)


def test_embedding_bag_weighted():
    table = jnp.asarray(RNG.normal(size=(500, 16)), jnp.float32)
    ids = jnp.asarray(RNG.integers(0, 500, 200).astype(np.int32))
    bags = jnp.asarray(np.sort(RNG.integers(0, 40, 200)).astype(np.int32))
    w = jnp.asarray(RNG.normal(size=200), jnp.float32)
    out = ops.embedding_bag(table, ids, bags, 40, weights=w)
    want = ref.embedding_bag_ref(table, ids, bags, 40, weights=w)
    np.testing.assert_allclose(out, want, rtol=1e-5, atol=1e-5)
