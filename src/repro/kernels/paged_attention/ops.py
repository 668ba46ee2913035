"""jit'd public wrapper for paged decode attention."""

from __future__ import annotations

import functools

import jax

from repro.kernels.paged_attention.kernel import paged_attention as _kernel
from repro.kernels.paged_attention.kernel import (
    paged_latent_attention as _latent_kernel)
from repro.kernels.paged_attention.kernel import (
    paged_prefill_attention as _prefill_kernel)
from repro.kernels.paged_attention.kernel import (
    paged_prefill_attention_batch as _prefill_batch_kernel)
from repro.kernels.paged_attention.ref import (
    paged_attention_ref, paged_latent_attention_ref,
    paged_latent_prefill_ref, paged_prefill_attention_batch_ref,
    paged_prefill_attention_ref)


@functools.partial(jax.jit, static_argnames=("impl", "interpret"))
def paged_attention(q, k_pool, v_pool, page_table, lens, *,
                    impl: str = "pallas", interpret: bool = False):
    """Decode attention over a paged KV pool.

    impl="pallas": the TPU kernel (interpret=True executes it on CPU).
    impl="reference": the pure-jnp oracle (used by the CPU serve engine).
    """
    if impl == "reference":
        return paged_attention_ref(q, k_pool, v_pool, page_table, lens)
    return _kernel(q, k_pool, v_pool, page_table, lens, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("impl", "interpret"))
def paged_prefill_attention(q, k_pool, v_pool, page_table, q_start, *,
                            impl: str = "pallas", interpret: bool = False):
    """Prefill-mode attention: one sequence's query chunk [T,nq,h] over its
    page table [mp], causal at absolute positions ``q_start + t``. Prior
    chunks' K/V is *read from the pool* (the O(n) incremental-prefill path —
    DESIGN.md §6); the chunk's own K/V must be scattered into its pages
    before the call."""
    if impl == "reference":
        return paged_prefill_attention_ref(q, k_pool, v_pool, page_table,
                                           q_start)
    return _prefill_kernel(q, k_pool, v_pool, page_table, q_start,
                           interpret=interpret)


@functools.partial(jax.jit, static_argnames=("impl", "interpret"))
def paged_prefill_attention_batch(q, k_pool, v_pool, page_table, q_start, *,
                                  impl: str = "pallas",
                                  interpret: bool = False):
    """Batched prefill-mode attention: B sequences' query chunks [B,T,nq,h]
    (padded to a common T) over per-sequence page tables [B,mp], causal at
    absolute positions ``q_start[b] + t``. One launch fuses same-step
    prefill chunks of different sequences and the speculative verify step's
    draft chunks (DESIGN.md §7); each chunk's own K/V must be scattered
    into its pages before the call."""
    if impl == "reference":
        return paged_prefill_attention_batch_ref(q, k_pool, v_pool,
                                                 page_table, q_start)
    return _prefill_batch_kernel(q, k_pool, v_pool, page_table, q_start,
                                 interpret=interpret)


@functools.partial(jax.jit, static_argnames=("scale", "impl", "interpret"))
def paged_latent_attention(q_lat, q_pe, kpe_pool, ckv_pool, page_table, lens,
                           layer, *, scale: float, impl: str = "pallas",
                           interpret: bool = False):
    """Absorbed MLA decode over the stacked latent pools (DeepSeek-V3): every
    query head of q_lat [B,H,r] / q_pe [B,H,rope] reads one shared latent
    key and value per token of layer ``layer``. Returns the heads' output
    latent [B,H,r]; ``W_UV`` and ``wo`` stay with the caller.

    impl="pallas": the TPU kernel (interpret=True executes it on CPU).
    impl="reference": the pure-jnp oracle."""
    if impl == "reference":
        return paged_latent_attention_ref(q_lat, q_pe, kpe_pool, ckv_pool,
                                          page_table, lens, layer, scale)
    return _latent_kernel(q_lat, q_pe, kpe_pool, ckv_pool, page_table, lens,
                          layer, scale=scale, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("scale",))
def paged_latent_prefill(q_lat, q_pe, kpe_pool, ckv_pool, page_table,
                         q_start, layer, *, scale: float):
    """Prefill over the stacked latent pools, in jnp on every platform:
    chunks q_lat [B,T,H,r] / q_pe [B,T,H,rope] at positions
    ``q_start[b] + t`` read their sequences' pages of layer ``layer``
    (prior chunks' and their own rows, written first). -> [B,T,H,r]."""
    return paged_latent_prefill_ref(q_lat, q_pe, kpe_pool, ckv_pool,
                                    page_table, q_start, layer, scale)
