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


# -- latent (MLA) decode ------------------------------------------------------

def _latent_kernel(table_ref, lens_ref, layer_ref, ql_ref, qp_ref, kpe_ref,
                   ckv_ref, o_ref, m_ref, l_ref, acc_ref, *, page_size: int,
                   rope: int, scale: float):
    """One page of one sequence's absorbed MLA decode: every query head
    reads the page's shared latent ``ckv`` [ps, r] as key (with the rope
    key) and as value. The rope-key page is stored folded
    ([ps / f, f * rope]; token ``i * ps / f + j`` in row ``j``, lanes
    ``i * rope ..``); the query's rope part comes repeated ``f`` times, so
    one product against the page's rows, each kept to its token's lanes,
    scores every token with no relayout."""
    del layer_ref                                    # used by the index maps
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
        ql = ql_ref[0]                               # [H, r]
        qp = qp_ref[0]                               # [H, f * rope]
        ckv = ckv_ref[0]                             # [ps, r]
        kpe = kpe_ref[0].astype(jnp.float32)         # [ps / f, f * rope]
        rows, width = kpe.shape
        if rows != page_size:
            lane = jax.lax.broadcasted_iota(jnp.int32, (rows, width), 1)
            kpe = jnp.concatenate(
                [jnp.where(lane // rope == i, kpe, 0.0)
                 for i in range(page_size // rows)], axis=0)  # [ps, f*rope]
        dims = (((1,), (1,)), ((), ()))
        s = (jax.lax.dot_general(ql, ckv, dims,
                                 preferred_element_type=jnp.float32)
             + jax.lax.dot_general(qp, kpe.astype(qp.dtype), dims,
                                   preferred_element_type=jnp.float32))
        s = s * scale                                # [H, ps]
        pos = page_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(pos < seq_len, s, NEG_INF)

        m_prev = m_ref[...]                          # [H, 1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
        pv = jax.lax.dot_general(p.astype(ckv.dtype), ckv,
                                 (((1,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        acc_ref[...] = acc_ref[...] * alpha + pv     # [H, r]
        m_ref[...] = m_new

    @pl.when(pi == np_ - 1)
    def _finish():
        l = l_ref[...]
        o_ref[0] = (acc_ref[...] / jnp.where(l == 0.0, 1.0, l)
                    ).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("scale", "interpret"))
def paged_latent_attention(q_lat, q_pe, kpe_pool, ckv_pool, page_table, lens,
                           layer, *, scale: float, interpret: bool = False):
    """Absorbed MLA decode over the stacked latent pools: q_lat [B,H,r];
    q_pe [B,H,rope]; kpe_pool [L,P,ps/f,f*rope] (folded rope keys);
    ckv_pool [L,P,ps,r]; page_table [B,mp] (pad with page 0); lens [B];
    layer traced scalar -> [B,H,r], the latent of each head's output.

    ``layer`` is prefetched with the table, and the pools' index maps pick
    the layer's pages, so no layer of the pool is sliced out first."""
    b, h, r = q_lat.shape
    rope = q_pe.shape[-1]
    rows, width = kpe_pool.shape[2:]
    ps = ckv_pool.shape[2]
    mp = page_table.shape[1]
    qp = jnp.tile(q_pe, (1, 1, width // rope))       # [B, H, f * rope]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(b, mp),
        in_specs=[
            pl.BlockSpec((1, h, r), lambda b, p, tbl, ln, ly: (b, 0, 0)),
            pl.BlockSpec((1, h, width), lambda b, p, tbl, ln, ly: (b, 0, 0)),
            pl.BlockSpec((None, 1, rows, width),
                         lambda b, p, tbl, ln, ly: (ly[0], tbl[b, p], 0, 0)),
            pl.BlockSpec((None, 1, ps, r),
                         lambda b, p, tbl, ln, ly: (ly[0], tbl[b, p], 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, h, r), lambda b, p, tbl, ln, ly: (b, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((h, 1), jnp.float32),         # m
            pltpu.VMEM((h, 1), jnp.float32),         # l
            pltpu.VMEM((h, r), jnp.float32),         # acc
        ],
    )
    kernel = functools.partial(_latent_kernel, page_size=ps, rope=rope,
                               scale=scale)
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, h, r), q_lat.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(page_table, lens, jnp.asarray(layer, jnp.int32).reshape(1),
      q_lat, qp, kpe_pool, ckv_pool)
