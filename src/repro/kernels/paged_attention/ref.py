"""Pure-jnp oracles for paged attention over a BWAP-placed page pool.

All three oracles walk the page table with the *same online-softmax
per-page accumulation the Pallas kernels use* rather than materializing one
dense [S] score row. Beyond matching the kernels' reduction structure, this
buys an exactness property the serving stack depends on: a fully-masked
trailing page updates the running (m, l, acc) state by *exactly* nothing
(alpha = exp(0) = 1, every prob = exp(-inf) = 0), so attention output is
bit-invariant to trailing table padding. Batch-padded decode tables, fused
prefill chunks of different lengths, and — critically — the speculative
verify step's lookahead pages (DESIGN.md §7: pages allocated for draft
tokens that may be rolled back) therefore cannot perturb committed results
even in the last bit; a dense softmax changes its reduction grouping with
the table width and breaks the rollback bit-identity guarantee.
"""

from __future__ import annotations

import jax.numpy as jnp
import numpy as np

NEG_INF = -2.0e38


def _page_walk(qf, k_pool, v_pool, page_table, mask_fn):
    """Online-softmax accumulation over one batched page table.

    qf [B, R, h] float32 query rows; page_table [B, mp]; ``mask_fn(b_pos)``
    maps per-page key positions [B, ps] to a validity mask [B, R, ps].
    Returns [B, R, h] float32 (unnormalized rows divided at the end).
    """
    b, r, h = qf.shape
    ps = k_pool.shape[1]
    nkv = k_pool.shape[2]
    mp = page_table.shape[1]
    g = r // nkv                      # query rows per KV head
    q5 = qf.reshape(b, nkv, g, h)
    scale = 1.0 / np.sqrt(h)
    m = jnp.full((b, nkv, g, 1), NEG_INF, jnp.float32)
    l = jnp.zeros((b, nkv, g, 1), jnp.float32)
    acc = jnp.zeros((b, nkv, g, h), jnp.float32)
    for pi in range(mp):
        k = k_pool[page_table[:, pi]].astype(jnp.float32)   # [B,ps,nkv,h]
        v = v_pool[page_table[:, pi]].astype(jnp.float32)
        s = jnp.einsum("bngh,bpnh->bngp", q5, k) * scale    # [B,nkv,g,ps]
        pos = pi * ps + jnp.arange(ps)[None, :]             # [B,ps]
        ok = mask_fn(pos).reshape(b, nkv, g, ps)
        s = jnp.where(ok, s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new)
        l = l * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc = acc * alpha + jnp.einsum("bngp,bpnh->bngh", p, v)
        m = m_new
    out = acc / jnp.where(l == 0.0, 1.0, l)
    return out.reshape(b, r, h)


def paged_attention_ref(q, k_pool, v_pool, page_table, lens):
    """q [B,nq,h]; pools [P,ps,nkv,h]; page_table [B,mp]; lens [B] -> [B,nq,h].

    Decode attention: query b sees pool positions < lens[b] through its
    page-table row — the semantics the kernel must match.
    """
    b, nq, h = q.shape
    g = nq // k_pool.shape[2]

    def mask(pos):                                   # pos [B,ps]
        ok = pos < lens[:, None]
        return jnp.broadcast_to(ok[:, None, :], (b, nq, pos.shape[1]))

    out = _page_walk(q.astype(jnp.float32), k_pool, v_pool, page_table,
                     mask)
    return out.astype(q.dtype)


def paged_prefill_attention_batch_ref(q, k_pool, v_pool, page_table,
                                      q_start):
    """Batched prefill-mode oracle: B sequences' query chunks, each at its
    own absolute start position, over their own page tables in one call.
    q [B,T,nq,h]; pools [P,ps,nkv,h]; page_table [B,mp]; q_start [B].
    Query (b, t) sits at position ``q_start[b] + t`` and sees pool positions
    <= its own through sequence b's table. This single shape serves both
    fused same-step chunked prefill of different sequences (pad short
    chunks; padded queries read garbage that callers discard) and the
    multi-token speculative *verify* step (chunk = last token + draft).
    Returns [B,T,nq,h].
    """
    b, t, nq, h = q.shape
    nkv = k_pool.shape[2]
    g = nq // nkv
    # rows grouped by KV head, then query position, then group — the
    # [nkv, T*g] layout the kernel accumulates in
    qf = jnp.transpose(q.reshape(b, t, nkv, g, h),
                       (0, 2, 1, 3, 4)).reshape(b, nkv * t * g, h)
    qpos = q_start[:, None] + jnp.repeat(jnp.arange(t), g)[None, :]  # [B,T*g]

    def mask(pos):                                   # pos [B,ps]
        ok = pos[:, None, :] <= qpos[:, :, None]     # [B,T*g,ps]
        return jnp.broadcast_to(ok[:, None, :, :],
                                (b, nkv, t * g, pos.shape[1])) \
            .reshape(b, nkv * t * g, pos.shape[1])

    out = _page_walk(qf.astype(jnp.float32), k_pool, v_pool, page_table,
                     mask)
    out = jnp.transpose(out.reshape(b, nkv, t, g, h), (0, 2, 1, 3, 4))
    return out.reshape(b, t, nq, h).astype(q.dtype)


def paged_prefill_attention_ref(q, k_pool, v_pool, page_table, q_start):
    """Prefill-mode oracle: one sequence's query *chunk* attends over its
    logically-mapped pool pages. q [T,nq,h]; pools [P,ps,nkv,h]; page_table
    [mp]; query t sits at absolute position ``q_start + t`` and sees pool
    positions <= its own (prior chunks' K/V — already resident via the page
    table — plus the causal intra-chunk triangle). This is what makes
    chunked prefill O(chunk) instead of recomputing the prefix: the chunk's
    own K/V is scattered into the pool *before* the call, so one gather
    covers old and new keys alike. Returns [T,nq,h].
    """
    out = paged_prefill_attention_batch_ref(
        q[None], k_pool, v_pool, page_table[None],
        jnp.asarray(q_start, jnp.int32).reshape(1))
    return out[0]


# -- latent (MLA) pools ----------------------------------------------------------

def unfold_rope_page(kpe_page, rope: int):
    """A folded rope-key page [..., ps/f, f*rope] (token ``i * ps/f + j`` in
    row ``j``, lanes ``i * rope ..``) as [..., ps, rope] in token order."""
    *lead, rows, width = kpe_page.shape
    f = width // rope
    x = kpe_page.reshape(*lead, rows, f, rope)
    x = jnp.swapaxes(x, -3, -2)
    return x.reshape(*lead, rows * f, rope)


def _latent_walk(q_lat, q_pe, kpe_pool, ckv_pool, page_table, layer, scale,
                 mask_fn):
    """Online-softmax page walk of an absorbed MLA attention. q_lat
    [B,R,r], q_pe [B,R,rope] float32 query rows; ``mask_fn(pos)`` maps key
    positions [B,ps] to [B,R,ps]. Returns [B,R,r] float32."""
    b, rr, r = q_lat.shape
    rope = q_pe.shape[-1]
    ps = ckv_pool.shape[2]
    m = jnp.full((b, rr, 1), NEG_INF, jnp.float32)
    l = jnp.zeros((b, rr, 1), jnp.float32)
    acc = jnp.zeros((b, rr, r), jnp.float32)
    for pi in range(page_table.shape[1]):
        # gathered with the layer index: no copy of the layer's pool
        ckv = ckv_pool[layer, page_table[:, pi]].astype(
            jnp.float32)                                         # [B,ps,r]
        kpe = unfold_rope_page(kpe_pool[layer, page_table[:, pi]],
                               rope).astype(jnp.float32)         # [B,ps,rope]
        s = (jnp.einsum("bqr,bpr->bqp", q_lat, ckv)
             + jnp.einsum("bqe,bpe->bqp", q_pe, kpe)) * scale
        pos = pi * ps + jnp.arange(ps)[None, :]
        s = jnp.where(mask_fn(pos), s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new)
        l = l * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc = acc * alpha + jnp.einsum("bqp,bpr->bqr", p, ckv)
        m = m_new
    return acc / jnp.where(l == 0.0, 1.0, l)


def paged_latent_attention_ref(q_lat, q_pe, kpe_pool, ckv_pool, page_table,
                               lens, layer, scale):
    """Decode oracle of ``kernel.paged_latent_attention``: q_lat [B,H,r],
    q_pe [B,H,rope]; pools [L,P,...] stacked; query b sees positions
    < lens[b] of layer ``layer`` through its table row. -> [B,H,r]."""
    h = q_lat.shape[1]

    def mask(pos):
        ok = pos < lens[:, None]
        return jnp.broadcast_to(ok[:, None, :], (ok.shape[0], h, ok.shape[1]))

    out = _latent_walk(q_lat.astype(jnp.float32), q_pe.astype(jnp.float32),
                       kpe_pool, ckv_pool, page_table, layer, scale, mask)
    return out.astype(q_lat.dtype)


def paged_latent_prefill_ref(q_lat, q_pe, kpe_pool, ckv_pool, page_table,
                             q_start, layer, scale):
    """Prefill over the latent pools: B sequences' query chunks q_lat
    [B,T,H,r], q_pe [B,T,H,rope], query (b, t) at position
    ``q_start[b] + t`` seeing positions <= its own through table row b
    (its chunk's rows written before the call). -> [B,T,H,r]."""
    b, t, h, r = q_lat.shape
    qpos = q_start[:, None] + jnp.repeat(jnp.arange(t), h)[None, :]

    def mask(pos):                                   # [B,ps] -> [B,T*H,ps]
        return pos[:, None, :] <= qpos[:, :, None]

    out = _latent_walk(q_lat.reshape(b, t * h, r).astype(jnp.float32),
                       q_pe.reshape(b, t * h, -1).astype(jnp.float32),
                       kpe_pool, ckv_pool, page_table, layer, scale, mask)
    return out.reshape(b, t, h, r).astype(q_lat.dtype)
