"""Pallas kernel validation (interpret mode) against pure-jnp oracles:
shape/dtype sweeps + hypothesis-driven page-table cases."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.kernels.flash_attention import ops as flash_ops
from repro.kernels.flash_attention.ref import flash_attention_ref
from repro.kernels.paged_attention import ops as paged_ops
from repro.kernels.paged_attention.ref import paged_attention_ref


def _rand(key, shape, dtype):
    return jax.random.normal(jax.random.PRNGKey(key), shape).astype(dtype)


# -- flash attention: shape / dtype / window sweep ---------------------------

@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("b,s,nq,nkv,h", [
    (1, 128, 4, 4, 64),      # MHA
    (2, 256, 8, 2, 64),      # GQA 4:1
    (1, 192, 4, 1, 32),      # MQA, non-multiple seq vs blocks
    (1, 64, 2, 2, 128),      # small seq
])
def test_flash_matches_ref(b, s, nq, nkv, h, dtype):
    q = _rand(0, (b, s, nq, h), dtype)
    k = _rand(1, (b, s, nkv, h), dtype)
    v = _rand(2, (b, s, nkv, h), dtype)
    out = flash_ops.flash_attention(q, k, v, block_q=64, block_kv=64,
                                    interpret=True)
    ref = flash_attention_ref(q, k, v)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               rtol=tol, atol=tol)


@pytest.mark.parametrize("window", [1, 7, 64, 200])
def test_flash_sliding_window(window):
    b, s, nq, nkv, h = 1, 200, 4, 2, 32
    q = _rand(3, (b, s, nq, h), jnp.float32)
    k = _rand(4, (b, s, nkv, h), jnp.float32)
    v = _rand(5, (b, s, nkv, h), jnp.float32)
    out = flash_ops.flash_attention(q, k, v, window=window, block_q=64,
                                    block_kv=64, interpret=True)
    ref = flash_attention_ref(q, k, v, window=window)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_flash_noncausal():
    b, s, nq, nkv, h = 2, 128, 4, 4, 64
    q = _rand(6, (b, s, nq, h), jnp.float32)
    k = _rand(7, (b, s, nkv, h), jnp.float32)
    v = _rand(8, (b, s, nkv, h), jnp.float32)
    out = flash_ops.flash_attention(q, k, v, causal=False, block_q=64,
                                    block_kv=64, interpret=True)
    ref = flash_attention_ref(q, k, v, causal=False)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


# -- paged attention ----------------------------------------------------------

@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("b,nq,nkv,h,ps,pages_per_seq,pool", [
    (2, 4, 2, 64, 16, 4, 16),
    (1, 8, 8, 32, 8, 8, 64),
    (3, 6, 2, 64, 32, 2, 8),
])
def test_paged_matches_ref(b, nq, nkv, h, ps, pages_per_seq, pool, dtype):
    rng = np.random.default_rng(b * 7 + nq)
    q = _rand(9, (b, nq, h), dtype)
    k_pool = _rand(10, (pool, ps, nkv, h), dtype)
    v_pool = _rand(11, (pool, ps, nkv, h), dtype)
    # distinct pages per sequence (realistic allocator behaviour)
    table = np.stack([rng.choice(pool, pages_per_seq, replace=False)
                      for _ in range(b)]).astype(np.int32)
    lens = rng.integers(1, ps * pages_per_seq + 1, b).astype(np.int32)
    out = paged_ops.paged_attention(q, k_pool, v_pool, jnp.asarray(table),
                                    jnp.asarray(lens), interpret=True)
    ref = paged_attention_ref(q, k_pool, v_pool, jnp.asarray(table),
                              jnp.asarray(lens))
    tol = 3e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               rtol=tol, atol=tol)


@given(st.integers(min_value=1, max_value=4),     # batch
       st.integers(min_value=1, max_value=6),     # pages per seq
       st.integers(min_value=0, max_value=10_000))
@settings(max_examples=12, deadline=None)
def test_paged_property_random_tables(b, mp, seed):
    """Property: kernel == oracle for arbitrary tables/lengths (incl. len
    boundaries at page edges)."""
    nq, nkv, h, ps, pool = 4, 2, 32, 8, 12
    rng = np.random.default_rng(seed)
    q = _rand(seed % 97, (b, nq, h), jnp.float32)
    k_pool = _rand(seed % 89 + 1, (pool, ps, nkv, h), jnp.float32)
    v_pool = _rand(seed % 83 + 2, (pool, ps, nkv, h), jnp.float32)
    table = rng.integers(0, pool, (b, mp)).astype(np.int32)
    # hit page-boundary lengths often
    lens = np.minimum(rng.integers(1, mp * ps + 1, b)
                      // ps * ps + rng.integers(0, 2, b) * rng.integers(
                          1, ps + 1, b), mp * ps).astype(np.int32)
    lens = np.maximum(lens, 1).astype(np.int32)
    out = paged_ops.paged_attention(q, k_pool, v_pool, jnp.asarray(table),
                                    jnp.asarray(lens), interpret=True)
    ref = paged_attention_ref(q, k_pool, v_pool, jnp.asarray(table),
                              jnp.asarray(lens))
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=3e-5, atol=3e-5)


# -- batched prefill-mode paged attention (speculative verify / fused prefill)

@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("b,t,nq,nkv,h,ps,mp,pool", [
    (1, 4, 4, 2, 64, 8, 3, 16),      # single sequence, GQA
    (3, 5, 4, 2, 32, 4, 6, 24),      # batch with different q_starts
    (2, 1, 8, 8, 32, 8, 4, 16),      # T=1 degenerate (pure decode shape)
])
def test_paged_prefill_batch_matches_ref(b, t, nq, nkv, h, ps, mp, pool,
                                         dtype):
    rng = np.random.default_rng(b * 11 + t)
    q = _rand(12, (b, t, nq, h), dtype)
    k_pool = _rand(13, (pool, ps, nkv, h), dtype)
    v_pool = _rand(14, (pool, ps, nkv, h), dtype)
    table = np.stack([rng.choice(pool, mp, replace=False)
                      for _ in range(b)]).astype(np.int32)
    q_start = rng.integers(0, mp * ps - t + 1, b).astype(np.int32)
    out = paged_ops.paged_prefill_attention_batch(
        q, k_pool, v_pool, jnp.asarray(table), jnp.asarray(q_start),
        interpret=True)
    ref = paged_ops.paged_prefill_attention_batch(
        q, k_pool, v_pool, jnp.asarray(table), jnp.asarray(q_start),
        impl="reference")
    tol = 3e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               rtol=tol, atol=tol)


def test_paged_prefill_batch_trailing_page_invariance():
    """The rollback bit-identity property (DESIGN.md §7): extending a page
    table with lookahead pages whose keys are causally masked must not
    change the result in the last bit — the online-softmax page walk makes
    a fully-masked page an exact no-op."""
    nq, nkv, h, ps, pool = 4, 2, 32, 4, 16
    q = _rand(15, (1, 3, nq, h), jnp.float32)
    k_pool = _rand(16, (pool, ps, nkv, h), jnp.float32)
    v_pool = _rand(17, (pool, ps, nkv, h), jnp.float32)
    tbl = jnp.asarray([[5, 9, 2]], jnp.int32)           # covers 12 positions
    ext = jnp.asarray([[5, 9, 2, 7, 11]], jnp.int32)    # + lookahead pages
    qs = jnp.asarray([9], jnp.int32)                    # queries at 9..11
    base = paged_ops.paged_prefill_attention_batch(q, k_pool, v_pool, tbl,
                                                   qs, impl="reference")
    wide = paged_ops.paged_prefill_attention_batch(q, k_pool, v_pool, ext,
                                                   qs, impl="reference")
    assert (np.asarray(base) == np.asarray(wide)).all()
    # decode op agrees bitwise with verify row 0 (token-identity under the
    # scheduler relies on the two paths computing the same attention)
    dec = paged_ops.paged_attention(q[:, 0], k_pool, v_pool, tbl,
                                    jnp.asarray([10], jnp.int32),
                                    impl="reference")
    assert (np.asarray(dec[0]) == np.asarray(base[0, 0])).all()
