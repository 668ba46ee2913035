"""Attention: GQA/MQA, sliding-window, MLA (DeepSeek), KV caches.

Reference implementations are pure jnp (the dry-run lowers these — identical
math to the Pallas kernels, which target TPU and are validated separately in
interpret mode; see DESIGN.md §6). ``impl="flash"`` routes full-sequence
attention through the Pallas flash kernel on TPU.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.models import layers
from repro.models.config import ModelConfig
from repro.models.layers import Params, dense_init, dtype_of

NEG_INF = -2.0e38


# ---------------------------------------------------------------------------
# Parameters
# ---------------------------------------------------------------------------

def attn_params(key, cfg: ModelConfig) -> Params:
    dt = dtype_of(cfg.param_dtype)
    d, hd = cfg.d_model, cfg.head_dim_
    nq, nkv = cfg.num_heads, cfg.num_kv_heads
    if cfg.mla:
        m = cfg.mla
        ks = jax.random.split(key, 8)
        return {
            "wq_a": dense_init(ks[0], d, m.q_lora_rank, dt),
            "q_norm": jnp.zeros((m.q_lora_rank,), dt),
            "wq_b": dense_init(ks[1], m.q_lora_rank,
                               nq * (m.qk_nope_head_dim + m.qk_rope_head_dim),
                               dt),
            "wkv_a": dense_init(ks[2], d, m.kv_lora_rank + m.qk_rope_head_dim,
                                dt),
            "kv_norm": jnp.zeros((m.kv_lora_rank,), dt),
            "wkv_b": dense_init(ks[3], m.kv_lora_rank,
                                nq * (m.qk_nope_head_dim + m.v_head_dim), dt),
            "wo": dense_init(ks[4], nq * m.v_head_dim, d, dt),
        }
    ks = jax.random.split(key, 4)
    p = {
        "wq": dense_init(ks[0], d, nq * hd, dt),
        "wk": dense_init(ks[1], d, nkv * hd, dt),
        "wv": dense_init(ks[2], d, nkv * hd, dt),
        "wo": dense_init(ks[3], nq * hd, d, dt),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((nq * hd,), dt)
        p["bk"] = jnp.zeros((nkv * hd,), dt)
        p["bv"] = jnp.zeros((nkv * hd,), dt)
    return p


def init_cache(cfg: ModelConfig, batch: int, capacity: int, layer_axes=()):
    """KV cache pytree (per layer; callers stack over layers).

    ``pos`` records the absolute position held by each slot (-1 = empty),
    which uniformly supports linear caches and ring buffers for
    sliding-window layers (capacity = window).
    """
    cdt = dtype_of(cfg.compute_dtype)
    shape = lambda *s: layer_axes + s
    if cfg.mla:
        m = cfg.mla
        return {
            "ckv": jnp.zeros(shape(batch, capacity, m.kv_lora_rank), cdt),
            "kpe": jnp.zeros(shape(batch, capacity, m.qk_rope_head_dim), cdt),
            "pos": jnp.full(shape(batch, capacity), -1, jnp.int32),
        }
    return {
        "k": jnp.zeros(shape(batch, capacity, cfg.num_kv_heads,
                             cfg.head_dim_), cdt),
        "v": jnp.zeros(shape(batch, capacity, cfg.num_kv_heads,
                             cfg.head_dim_), cdt),
        "pos": jnp.full(shape(batch, capacity), -1, jnp.int32),
    }


# ---------------------------------------------------------------------------
# Masking
# ---------------------------------------------------------------------------

def _window_bias(q_pos, k_pos, window, causal: bool):
    """[..., S_q, S_k] additive bias from absolute positions.

    window is a traced or static int32 scalar; 0 means full attention."""
    dq = q_pos[..., :, None]
    dk = k_pos[..., None, :]
    ok = dk >= 0
    if causal:
        ok &= dk <= dq
    win_ok = (window <= 0) | (dq - dk < window)
    ok &= win_ok
    return jnp.where(ok, 0.0, NEG_INF).astype(jnp.float32)


# ---------------------------------------------------------------------------
# Core GQA attention (reference)
# ---------------------------------------------------------------------------

def gqa_attention(q, k, v, bias, compute_dtype):
    """q [B,Sq,nq,h], k/v [B,Sk,nkv,h], bias [B,Sq,Sk] -> [B,Sq,nq,h].

    Decode-path workhorse: KV heads are *not* materialized per query head;
    the einsum groups query heads over their shared KV head (KV-cache bytes
    dominate decode and must not be repeated)."""
    b, sq, nq, h = q.shape
    nkv = k.shape[2]
    g = nq // nkv
    q = q.reshape(b, sq, nkv, g, h)
    scores = jnp.einsum("bsngh,btnh->bngst", q.astype(jnp.float32),
                        k.astype(jnp.float32))
    scores = scores / np.sqrt(h) + bias[:, None, None, :, :]
    probs = jax.nn.softmax(scores, axis=-1).astype(compute_dtype)
    out = jnp.einsum("bngst,btnh->bsngh", probs, v)
    return out.reshape(b, sq, nq, h)


def attn_q_chunks(seq: int, chunk: int = 512) -> int:
    """Number of query chunks the full-sequence reference path uses."""
    if seq <= chunk:
        return 1
    return -(-seq // chunk)


CHUNK_SCAN_THRESHOLD = 4  # python-unrolled up to here; lax.scan beyond


def chunked_mha(q, k, v, qpos, kpos, window, causal, compute_dtype,
                chunk: int = 512, scores_dtype=jnp.float32, scale=None):
    """Full-sequence attention, chunked over queries (memory-bounded
    reference of the flash kernel: the live score buffer is
    [B, nq, chunk, S_kv] instead of [B, nq, S, S]).

    q/k/v are HEAD-ALIGNED ([B,S,n,h] with identical head counts — callers
    repeat KV for GQA so tensor-parallel head sharding propagates without
    resharding). Chunks beyond CHUNK_SCAN_THRESHOLD run under lax.scan; the
    body is exposed as a roofline fragment (lm.fragments). ``scale``
    multiplies the scores (default ``1 / sqrt(head_dim)``)."""
    b, s, nq, h = q.shape
    nc = attn_q_chunks(s, chunk)
    if nc == 1:
        bias = _window_bias(qpos, kpos, window, causal)
        return _mha_one_chunk(q, k, v, bias, compute_dtype, scores_dtype,
                              scale)
    pad = nc * chunk - s
    if pad:
        q = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0)))
        qpos = jnp.pad(qpos, ((0, 0), (0, pad)), constant_values=-1)

    # each chunk is rematerialized: backward recomputes the [c, S] score
    # tile instead of keeping every chunk's scores live (flash-style remat;
    # without this the scan stacks [nc, B, n, c, S] fp32 residuals).
    one_chunk = jax.checkpoint(
        functools.partial(_chunk_with_bias, window=window, causal=causal,
                          compute_dtype=compute_dtype,
                          scores_dtype=scores_dtype, scale=scale))
    hv = v.shape[-1]   # value head dim can differ from qk dim (MLA)
    if nc <= CHUNK_SCAN_THRESHOLD:
        outs = []
        for i in range(nc):
            sl = slice(i * chunk, (i + 1) * chunk)
            outs.append(one_chunk(q[:, sl], qpos[:, sl], k, v, kpos))
        out = jnp.concatenate(outs, axis=1)
    else:
        qc = jnp.moveaxis(q.reshape(b, nc, chunk, nq, h), 1, 0)
        pc = jnp.moveaxis(qpos.reshape(b, nc, chunk), 1, 0)

        def body(_, xs):
            qi, pi = xs
            return (), one_chunk(qi, pi, k, v, kpos)

        _, out = jax.lax.scan(body, (), (qc, pc))
        out = jnp.moveaxis(out, 0, 1).reshape(b, nc * chunk, nq, hv)
    return out[:, :s]


def _chunk_with_bias(q, qpos, k, v, kpos, *, window, causal,
                     compute_dtype, scores_dtype=jnp.float32, scale=None):
    bias = _window_bias(qpos, kpos, window, causal)
    return _mha_one_chunk(q, k, v, bias, compute_dtype, scores_dtype, scale)


def _mha_one_chunk(q, k, v, bias, compute_dtype,
                   scores_dtype=jnp.float32, scale=None):
    """q [B,c,n,h], k/v [B,T,n,h], bias [B,c,T] -> [B,c,n,h].

    scores_dtype=bfloat16 halves the S^2 score-tensor traffic (the dot still
    accumulates in fp32 on the MXU; softmax max-subtraction keeps bf16
    stable for O(10) logits)."""
    h = q.shape[-1]
    scores = jax.lax.dot_general(
        q, k, (((3,), (3,)), ((0, 2), (0, 2))),
        preferred_element_type=jnp.float32)            # [B,n,c,T] fp32 acc
    scores = (scores / np.sqrt(h) if scale is None
              else scores * scale).astype(scores_dtype)
    scores = scores + bias[:, None, :, :].astype(scores_dtype)
    probs = jax.nn.softmax(scores, axis=-1).astype(compute_dtype)
    return jnp.einsum("bnst,btnh->bsnh", probs, v)


def full_attention(cfg: ModelConfig, p: Params, x, positions, window,
                   impl: str = "reference", causal: bool = True,
                   kv_positions=None, xkv=None):
    """Full-sequence attention (train / prefill / encoder / cross).

    xkv: source of K/V (cross-attention); defaults to x (self-attention).
    Returns (out [B,S,d], kv) where kv = (k, v) for cache priming.
    """
    cdt = dtype_of(cfg.compute_dtype)
    b, s, _ = x.shape
    nq, nkv, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim_
    xkv = x if xkv is None else xkv
    kv_positions = positions if kv_positions is None else kv_positions

    from jax.ad_checkpoint import checkpoint_name
    x = x.astype(cdt)
    xkv = xkv.astype(cdt)
    q = checkpoint_name(x @ p["wq"].astype(cdt), "qkv")
    k = checkpoint_name(xkv @ p["wk"].astype(cdt), "qkv")
    v = checkpoint_name(xkv @ p["wv"].astype(cdt), "qkv")
    if cfg.qkv_bias:
        q = q + p["bq"].astype(cdt)
        k = k + p["bk"].astype(cdt)
        v = v + p["bv"].astype(cdt)
    q = q.reshape(b, s, nq, hd)
    k = k.reshape(b, xkv.shape[1], nkv, hd)
    v = v.reshape(b, xkv.shape[1], nkv, hd)

    if cfg.use_rope and (causal or xkv is x):  # rope only for self-attention
        if cfg.mrope:
            q = layers.apply_mrope(q, positions, cfg.rope_theta,
                                   cfg.mrope_sections)
            k = layers.apply_mrope(k, kv_positions, cfg.rope_theta,
                                   cfg.mrope_sections)
        else:
            q = layers.apply_rope(q, positions, cfg.rope_theta)
            k = layers.apply_rope(k, kv_positions, cfg.rope_theta)

    pos1 = positions[1] if cfg.mrope else positions  # temporal stream masks
    kpos1 = kv_positions[1] if cfg.mrope else kv_positions
    if impl == "flash" and causal:
        from repro.kernels.flash_attention import ops as flash_ops
        out = flash_ops.flash_attention(q, k, v, window=int(window))
    else:
        # repeat KV to full query heads: keeps TP head sharding aligned
        # through the einsums (no GSPMD resharding of S x S scores)
        g = nq // nkv
        kf = jnp.repeat(k, g, axis=2) if g > 1 else k
        vf = jnp.repeat(v, g, axis=2) if g > 1 else v
        out = chunked_mha(q, kf, vf, pos1, kpos1, window, causal, cdt,
                          scores_dtype=dtype_of(cfg.attn_scores_dtype))
    out = out.reshape(b, s, nq * hd) @ p["wo"].astype(cdt)
    return out, (k, v)


def decode_attention(cfg: ModelConfig, p: Params, x, cache, position,
                     window):
    """One-token decode with cache append. x [B,1,d]; position scalar int32.

    Ring-buffer write at ``position % capacity``; masking is driven by the
    per-slot absolute positions, so linear and ring caches share one path.
    """
    cdt = dtype_of(cfg.compute_dtype)
    b = x.shape[0]
    nq, nkv, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim_
    cap = cache["pos"].shape[-1]
    slot = position % cap

    x = x.astype(cdt)
    q = (x @ p["wq"].astype(cdt))
    k = (x @ p["wk"].astype(cdt))
    v = (x @ p["wv"].astype(cdt))
    if cfg.qkv_bias:
        q, k, v = q + p["bq"].astype(cdt), k + p["bk"].astype(cdt), \
            v + p["bv"].astype(cdt)
    q = q.reshape(b, 1, nq, hd)
    k = k.reshape(b, 1, nkv, hd)
    v = v.reshape(b, 1, nkv, hd)
    pos_b = jnp.full((b, 1), position, jnp.int32)
    if cfg.mrope:
        p3 = jnp.broadcast_to(position, (3, b, 1)).astype(jnp.int32)
        q = layers.apply_mrope(q, p3, cfg.rope_theta, cfg.mrope_sections)
        k = layers.apply_mrope(k, p3, cfg.rope_theta, cfg.mrope_sections)
    elif cfg.use_rope:
        q = layers.apply_rope(q, pos_b, cfg.rope_theta)
        k = layers.apply_rope(k, pos_b, cfg.rope_theta)

    ck = jax.lax.dynamic_update_slice_in_dim(cache["k"], k, slot, axis=1)
    cv = jax.lax.dynamic_update_slice_in_dim(cache["v"], v, slot, axis=1)
    cpos = jax.lax.dynamic_update_slice_in_dim(
        cache["pos"], pos_b, slot, axis=1)
    bias = _window_bias(pos_b, cpos, window, causal=True)
    out = gqa_attention(q, ck, cv, bias, cdt)
    out = out.reshape(b, 1, nq * hd) @ p["wo"].astype(cdt)
    return out, {"k": ck, "v": cv, "pos": cpos}


# ---------------------------------------------------------------------------
# MLA (DeepSeek-V3): compressed-latent attention
# ---------------------------------------------------------------------------

def mla_softmax_scale(cfg: ModelConfig) -> float:
    """MLA's softmax scale: ``(qk_nope + qk_rope) ** -0.5``, times
    ``mscale(factor, mscale_all_dim) ** 2`` under YaRN (DeepSeek-V3: 1.874
    at factor 40)."""
    m = cfg.mla
    scale = (m.qk_nope_head_dim + m.qk_rope_head_dim) ** -0.5
    ys = cfg.rope_scaling
    if ys is not None and ys.mscale_all_dim:
        scale *= layers.yarn_mscale(ys.factor, ys.mscale_all_dim) ** 2
    return scale


def _mla_qkv(cfg, p, x, positions):
    m = cfg.mla
    cdt = dtype_of(cfg.compute_dtype)
    b, s, _ = x.shape
    nq = cfg.num_heads
    cq = layers.rmsnorm(x.astype(cdt) @ p["wq_a"].astype(cdt), p["q_norm"],
                        cfg.rms_eps)
    q = (cq @ p["wq_b"].astype(cdt)).reshape(
        b, s, nq, m.qk_nope_head_dim + m.qk_rope_head_dim)
    q_nope, q_pe = jnp.split(q, [m.qk_nope_head_dim], axis=-1)
    q_pe = layers.apply_rope(q_pe, positions, cfg.rope_theta,
                             cfg.rope_scaling)

    kv = x.astype(cdt) @ p["wkv_a"].astype(cdt)
    ckv, k_pe = jnp.split(kv, [m.kv_lora_rank], axis=-1)
    ckv = layers.rmsnorm(ckv, p["kv_norm"], cfg.rms_eps)
    k_pe = layers.apply_rope(k_pe[:, :, None, :], positions,
                             cfg.rope_theta, cfg.rope_scaling)[:, :, 0, :]
    return q_nope, q_pe, ckv, k_pe


def _mla_w_ukv(cfg, p):
    """``wkv_b`` split into the key and value up-projections, each
    [kv_lora_rank, heads, dim]."""
    m = cfg.mla
    wkv_b = p["wkv_b"].astype(dtype_of(cfg.compute_dtype)).reshape(
        m.kv_lora_rank, cfg.num_heads, m.qk_nope_head_dim + m.v_head_dim)
    return wkv_b[:, :, :m.qk_nope_head_dim], wkv_b[:, :, m.qk_nope_head_dim:]


def mla_latent_query(cfg: ModelConfig, p: Params, x, positions):
    """The inputs of an absorbed MLA attention over a latent cache, for
    ``x`` [B,S,d] at ``positions`` [B,S]: ``q_lat`` [B,S,H,r] (the no-rope
    query through ``W_UK``), ``q_pe`` [B,S,H,rope], and the tokens' cache
    rows, the normalised latent ``ckv`` [B,S,r] and the rope key ``k_pe``
    [B,S,rope]."""
    q_nope, q_pe, ckv, k_pe = _mla_qkv(cfg, p, x, positions)
    w_uk, _ = _mla_w_ukv(cfg, p)
    q_lat = jnp.einsum("bsnh,rnh->bsnr", q_nope, w_uk)
    return q_lat, q_pe, ckv, k_pe


def mla_latent_out(cfg: ModelConfig, p: Params, out_lat):
    """Attention output [B,S,d] from its latent ``out_lat`` [B,S,H,r]:
    through ``W_UV``, then ``wo``."""
    cdt = dtype_of(cfg.compute_dtype)
    b, s = out_lat.shape[:2]
    _, w_uv = _mla_w_ukv(cfg, p)
    out = jnp.einsum("bsnr,rnh->bsnh", out_lat.astype(cdt), w_uv)
    return out.reshape(b, s, -1) @ p["wo"].astype(cdt)


def mla_attention(cfg: ModelConfig, p: Params, x, positions, window):
    """Training/prefill MLA: materialize per-head K/V from the latent, then
    run the q-chunked reference path (scale matches the concatenated
    [nope ; rope] head dim)."""
    m = cfg.mla
    cdt = dtype_of(cfg.compute_dtype)
    b, s, _ = x.shape
    nq = cfg.num_heads
    q_nope, q_pe, ckv, k_pe = _mla_qkv(cfg, p, x, positions)
    kvb = (ckv @ p["wkv_b"].astype(cdt)).reshape(
        b, s, nq, m.qk_nope_head_dim + m.v_head_dim)
    k_nope, v = jnp.split(kvb, [m.qk_nope_head_dim], axis=-1)

    q_full = jnp.concatenate([q_nope, q_pe], axis=-1)
    k_full = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_pe[:, :, None, :],
                                  (b, s, nq, m.qk_rope_head_dim))], axis=-1)
    out = chunked_mha(q_full, k_full, v, positions, positions, window,
                      True, cdt,
                      scores_dtype=dtype_of(cfg.attn_scores_dtype),
                      scale=mla_softmax_scale(cfg))
    out = out.reshape(b, s, nq * m.v_head_dim) @ p["wo"].astype(cdt)
    return out, (ckv, k_pe)


def mla_decode_attention(cfg: ModelConfig, p: Params, x, cache, position,
                         window):
    """Absorbed-matrix MLA decode: attention runs in the latent space, so the
    per-step cost is O(S * kv_lora_rank) and the cache holds only the latent
    (the technique that makes MLA decode cheap; arXiv:2412.19437 §2.1)."""
    m = cfg.mla
    cdt = dtype_of(cfg.compute_dtype)
    b = x.shape[0]
    nq = cfg.num_heads
    cap = cache["pos"].shape[-1]
    slot = position % cap
    pos_b = jnp.full((b, 1), position, jnp.int32)

    q_nope, q_pe, ckv_new, kpe_new = _mla_qkv(cfg, p, x, pos_b)
    ckv = jax.lax.dynamic_update_slice_in_dim(cache["ckv"], ckv_new, slot,
                                              axis=1)
    kpe = jax.lax.dynamic_update_slice_in_dim(cache["kpe"], kpe_new, slot,
                                              axis=1)
    cpos = jax.lax.dynamic_update_slice_in_dim(cache["pos"], pos_b, slot,
                                               axis=1)

    w_uk, w_uv = _mla_w_ukv(cfg, p)               # [r, n, hk], [r, n, hv]

    # absorb K up-projection into the query
    q_lat = jnp.einsum("bsnh,rnh->bsnr", q_nope, w_uk)       # [b,1,n,r]
    scale = mla_softmax_scale(cfg)
    scores = (jnp.einsum("bsnr,btr->bnst", q_lat.astype(jnp.float32),
                         ckv.astype(jnp.float32))
              + jnp.einsum("bsnh,bth->bnst", q_pe.astype(jnp.float32),
                           kpe.astype(jnp.float32))) * scale
    bias = _window_bias(pos_b, cpos, window, causal=True)
    probs = jax.nn.softmax(scores + bias[:, None], axis=-1).astype(cdt)
    out_lat = jnp.einsum("bnst,btr->bsnr", probs, ckv)        # [b,1,n,r]
    out = jnp.einsum("bsnr,rnh->bsnh", out_lat, w_uv)
    out = out.reshape(b, 1, nq * m.v_head_dim) @ p["wo"].astype(cdt)
    return out, {"ckv": ckv, "kpe": kpe, "pos": cpos}
