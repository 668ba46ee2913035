"""Paged decode-attention Pallas TPU kernel — the BWAP KV-cache consumer.

The KV pool is a page-granular buffer whose pages the BWAP placement layer
(serve/kvcache.py) distributes across memory domains with Alg.-1 weighted
interleaving; this kernel walks a sequence's page table (scalar-prefetched so
the next page's DMA is issued while the current tile computes) and performs
online-softmax attention per page.

VMEM working set per step: q [nq,h] + one K page + one V page
(page_size x nkv x h each) + fp32 accumulators — sized for ~16 MiB VMEM with
page_size 64..256 at h<=256.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1.0e38


def _paged_kernel(table_ref, lens_ref, q_ref, k_ref, v_ref, o_ref,
                  m_ref, l_ref, acc_ref, *, page_size: int, groups: int,
                  scale: float):
    b = pl.program_id(0)
    pi = pl.program_id(1)
    np_ = pl.num_programs(1)

    @pl.when(pi == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    seq_len = lens_ref[b]
    page_start = pi * page_size

    @pl.when(page_start < seq_len)
    def _compute():
        q = q_ref[0].astype(jnp.float32)            # [nq, h]
        k = k_ref[0].astype(jnp.float32)            # [ps, nkv, h]
        v = v_ref[0].astype(jnp.float32)
        nq, h = q.shape
        nkv = k.shape[1]
        qg = q.reshape(nkv, groups, h)
        s = jax.lax.dot_general(
            qg, k, (((2,), (2,)), ((0,), (1,))))    # [nkv, g, ps]
        s = s * scale
        pos = page_start + jax.lax.broadcasted_iota(
            jnp.int32, (nkv, groups, page_size), 2)
        s = jnp.where(pos < seq_len, s, NEG_INF)

        m_prev = m_ref[...]                          # [nkv, g, 1]
        l_prev = l_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=2, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)                       # [nkv, g, ps]
        l_ref[...] = l_prev * alpha + jnp.sum(p, axis=2, keepdims=True)
        pv = jax.lax.dot_general(
            p, v, (((2,), (0,)), ((0,), (1,))))      # [nkv, g, h]
        acc_ref[...] = acc_ref[...] * alpha + pv
        m_ref[...] = m_new

    @pl.when(pi == np_ - 1)
    def _finish():
        l = l_ref[...]
        out = acc_ref[...] / jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = out.reshape(o_ref.shape[1:]).astype(o_ref.dtype)


def _paged_prefill_kernel(table_ref, qstart_ref, q_ref, k_ref, v_ref, o_ref,
                          m_ref, l_ref, acc_ref, *, page_size: int,
                          groups: int, chunk: int, scale: float):
    """Prefill-mode page walk: a [T,nq,h] query chunk of ONE sequence
    accumulates online softmax over its pages; query t at absolute position
    qstart+t sees keys at positions <= its own. The grid dimension walks
    pages exactly like the decode kernel; queries fold into the accumulator
    rows ([nkv, T*g, ·]) so both kernels share the update algebra."""
    pi = pl.program_id(0)
    np_ = pl.num_programs(0)

    @pl.when(pi == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q_start = qstart_ref[0]
    page_start = pi * page_size

    # a page participates iff it holds a key visible to the *last* query
    @pl.when(page_start <= q_start + chunk - 1)
    def _compute():
        q = q_ref[...].astype(jnp.float32)           # [T, nq, h]
        k = k_ref[0].astype(jnp.float32)             # [ps, nkv, h]
        v = v_ref[0].astype(jnp.float32)
        t, nq, h = q.shape
        nkv = k.shape[1]
        qg = jnp.transpose(q.reshape(t, nkv, groups, h),
                           (1, 0, 2, 3)).reshape(nkv, t * groups, h)
        s = jax.lax.dot_general(
            qg, k, (((2,), (2,)), ((0,), (1,))))     # [nkv, T*g, ps]
        s = s * scale
        kpos = page_start + jax.lax.broadcasted_iota(
            jnp.int32, (nkv, t * groups, page_size), 2)
        qpos = q_start + jax.lax.broadcasted_iota(
            jnp.int32, (nkv, t * groups, page_size), 1) // groups
        s = jnp.where(kpos <= qpos, s, NEG_INF)

        m_prev = m_ref[...]                          # [nkv, T*g, 1]
        l_prev = l_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=2, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        l_ref[...] = l_prev * alpha + jnp.sum(p, axis=2, keepdims=True)
        pv = jax.lax.dot_general(
            p, v, (((2,), (0,)), ((0,), (1,))))      # [nkv, T*g, h]
        acc_ref[...] = acc_ref[...] * alpha + pv
        m_ref[...] = m_new

    @pl.when(pi == np_ - 1)
    def _finish():
        l = l_ref[...]
        out = acc_ref[...] / jnp.where(l == 0.0, 1.0, l)
        t, nq, h = o_ref.shape
        nkv = out.shape[0]
        out = jnp.transpose(out.reshape(nkv, t, groups, h), (1, 0, 2, 3))
        o_ref[...] = out.reshape(t, nq, h).astype(o_ref.dtype)


def _paged_prefill_batch_kernel(table_ref, qstart_ref, q_ref, k_ref, v_ref,
                                o_ref, m_ref, l_ref, acc_ref, *,
                                page_size: int, groups: int, chunk: int,
                                scale: float):
    """Batched prefill-mode page walk: grid (b, mp) — sequence b's [T,nq,h]
    chunk at absolute start ``qstart_ref[b]`` accumulates online softmax
    over *its own* page table row, exactly the single-sequence prefill
    kernel per grid row. One launch fuses same-step chunks of different
    sequences (batched incremental prefill) and the speculative verify
    step's draft chunks."""
    b = pl.program_id(0)
    pi = pl.program_id(1)
    np_ = pl.num_programs(1)

    @pl.when(pi == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q_start = qstart_ref[b]
    page_start = pi * page_size

    # a page participates iff it holds a key visible to the last query
    @pl.when(page_start <= q_start + chunk - 1)
    def _compute():
        q = q_ref[0].astype(jnp.float32)             # [T, nq, h]
        k = k_ref[0].astype(jnp.float32)             # [ps, nkv, h]
        v = v_ref[0].astype(jnp.float32)
        t, nq, h = q.shape
        nkv = k.shape[1]
        qg = jnp.transpose(q.reshape(t, nkv, groups, h),
                           (1, 0, 2, 3)).reshape(nkv, t * groups, h)
        s = jax.lax.dot_general(
            qg, k, (((2,), (2,)), ((0,), (1,))))     # [nkv, T*g, ps]
        s = s * scale
        kpos = page_start + jax.lax.broadcasted_iota(
            jnp.int32, (nkv, t * groups, page_size), 2)
        qpos = q_start + jax.lax.broadcasted_iota(
            jnp.int32, (nkv, t * groups, page_size), 1) // groups
        s = jnp.where(kpos <= qpos, s, NEG_INF)

        m_prev = m_ref[...]                          # [nkv, T*g, 1]
        l_prev = l_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=2, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        l_ref[...] = l_prev * alpha + jnp.sum(p, axis=2, keepdims=True)
        pv = jax.lax.dot_general(
            p, v, (((2,), (0,)), ((0,), (1,))))      # [nkv, T*g, h]
        acc_ref[...] = acc_ref[...] * alpha + pv
        m_ref[...] = m_new

    @pl.when(pi == np_ - 1)
    def _finish():
        l = l_ref[...]
        out = acc_ref[...] / jnp.where(l == 0.0, 1.0, l)
        _, t, nq, h = o_ref.shape
        nkv = out.shape[0]
        out = jnp.transpose(out.reshape(nkv, t, groups, h), (1, 0, 2, 3))
        o_ref[0] = out.reshape(t, nq, h).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def paged_prefill_attention_batch(q, k_pool, v_pool, page_table, q_start, *,
                                  interpret: bool = False):
    """q [B,T,nq,h] (per-sequence chunks, padded to a common T); pools
    [P,ps,nkv,h]; page_table [B,mp] (pad with page 0); q_start [B] traced
    -> [B,T,nq,h]."""
    b, t, nq, h = q.shape
    ps, nkv = k_pool.shape[1], k_pool.shape[2]
    mp = page_table.shape[1]
    groups = nq // nkv

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, mp),
        in_specs=[
            pl.BlockSpec((1, t, nq, h), lambda b, p, tbl, qs: (b, 0, 0, 0)),
            pl.BlockSpec((1, ps, nkv, h),
                         lambda b, p, tbl, qs: (tbl[b, p], 0, 0, 0)),
            pl.BlockSpec((1, ps, nkv, h),
                         lambda b, p, tbl, qs: (tbl[b, p], 0, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, t, nq, h),
                               lambda b, p, tbl, qs: (b, 0, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((nkv, t * groups, 1), jnp.float32),   # m
            pltpu.VMEM((nkv, t * groups, 1), jnp.float32),   # l
            pltpu.VMEM((nkv, t * groups, h), jnp.float32),   # acc
        ],
    )
    kernel = functools.partial(_paged_prefill_batch_kernel, page_size=ps,
                               groups=groups, chunk=t,
                               scale=1.0 / np.sqrt(h))
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, t, nq, h), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(page_table, jnp.asarray(q_start, jnp.int32).reshape(b),
      q, k_pool, v_pool)
    return out


@functools.partial(jax.jit, static_argnames=("interpret",))
def paged_prefill_attention(q, k_pool, v_pool, page_table, q_start, *,
                            interpret: bool = False):
    """q [T,nq,h] (one sequence's chunk); pools [P,ps,nkv,h]; page_table
    [mp] covering positions [0, q_start+T); q_start traced scalar ->
    [T,nq,h]."""
    t, nq, h = q.shape
    ps, nkv = k_pool.shape[1], k_pool.shape[2]
    mp = page_table.shape[0]
    groups = nq // nkv

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(mp,),
        in_specs=[
            pl.BlockSpec((t, nq, h), lambda p, tbl, qs: (0, 0, 0)),
            pl.BlockSpec((1, ps, nkv, h),
                         lambda p, tbl, qs: (tbl[p], 0, 0, 0)),
            pl.BlockSpec((1, ps, nkv, h),
                         lambda p, tbl, qs: (tbl[p], 0, 0, 0)),
        ],
        out_specs=pl.BlockSpec((t, nq, h), lambda p, tbl, qs: (0, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((nkv, t * groups, 1), jnp.float32),   # m
            pltpu.VMEM((nkv, t * groups, 1), jnp.float32),   # l
            pltpu.VMEM((nkv, t * groups, h), jnp.float32),   # acc
        ],
    )
    kernel = functools.partial(_paged_prefill_kernel, page_size=ps,
                               groups=groups, chunk=t,
                               scale=1.0 / np.sqrt(h))
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((t, nq, h), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(page_table, jnp.asarray(q_start, jnp.int32).reshape(1),
      q, k_pool, v_pool)
    return out


@functools.partial(jax.jit, static_argnames=("interpret",))
def paged_attention(q, k_pool, v_pool, page_table, lens, *,
                    interpret: bool = False):
    """q [B,nq,h]; pools [P,ps,nkv,h]; page_table [B,mp] (pad with page 0);
    lens [B] -> [B,nq,h]."""
    b, nq, h = q.shape
    ps, nkv = k_pool.shape[1], k_pool.shape[2]
    mp = page_table.shape[1]
    groups = nq // nkv

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, mp),
        in_specs=[
            pl.BlockSpec((1, nq, h), lambda b, p, tbl, ln: (b, 0, 0)),
            pl.BlockSpec((1, ps, nkv, h),
                         lambda b, p, tbl, ln: (tbl[b, p], 0, 0, 0)),
            pl.BlockSpec((1, ps, nkv, h),
                         lambda b, p, tbl, ln: (tbl[b, p], 0, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, nq, h), lambda b, p, tbl, ln: (b, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((nkv, groups, 1), jnp.float32),   # m
            pltpu.VMEM((nkv, groups, 1), jnp.float32),   # l
            pltpu.VMEM((nkv, groups, h), jnp.float32),   # acc
        ],
    )
    kernel = functools.partial(_paged_kernel, page_size=ps, groups=groups,
                               scale=1.0 / np.sqrt(h))
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, nq, h), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(page_table, lens, q, k_pool, v_pool)
    return out
