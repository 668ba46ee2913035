"""Shared layer primitives (raw JAX pytrees, no framework)."""

from __future__ import annotations

import dataclasses
import math
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

Params = dict  # nested dict pytree of jnp arrays


def dtype_of(name: str):
    return jnp.dtype(name)


# -- initializers -------------------------------------------------------------

def dense_init(key, d_in: int, d_out: int, dtype, scale: float | None = None):
    s = scale if scale is not None else 1.0 / np.sqrt(d_in)
    return (jax.random.normal(key, (d_in, d_out)) * s).astype(dtype)


def embed_init(key, vocab: int, d: int, dtype):
    return (jax.random.normal(key, (vocab, d)) * 0.02).astype(dtype)


# -- norms ---------------------------------------------------------------------

def rmsnorm(x, gamma, eps: float = 1e-6):
    dt = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    out = x * jax.lax.rsqrt(var + eps) * (1.0 + gamma.astype(jnp.float32))
    return out.astype(dt)


def layernorm(x, gamma, beta, eps: float = 1e-5):
    dt = x.dtype
    x = x.astype(jnp.float32)
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    out = (x - mu) * jax.lax.rsqrt(var + eps) * gamma + beta
    return out.astype(dt)


def norm_params(key, cfg, d: int) -> Params:
    if cfg.norm == "rmsnorm":
        return {"gamma": jnp.zeros((d,), dtype_of(cfg.param_dtype))}
    return {"gamma": jnp.ones((d,), dtype_of(cfg.param_dtype)),
            "beta": jnp.zeros((d,), dtype_of(cfg.param_dtype))}


def apply_norm(cfg, p: Params, x):
    if cfg.norm == "rmsnorm":
        return rmsnorm(x, p["gamma"], cfg.rms_eps)
    return layernorm(x, p["gamma"], p["beta"], cfg.rms_eps)


# -- activations ---------------------------------------------------------------

def act_fn(name: str):
    if name == "silu":
        return jax.nn.silu
    if name in ("gelu", "geglu"):
        return lambda x: jax.nn.gelu(x, approximate=True)
    raise ValueError(name)


# -- gated MLP -------------------------------------------------------------------

def mlp_params(key, cfg, d: int, ff: int) -> Params:
    ks = jax.random.split(key, 3)
    dt = dtype_of(cfg.param_dtype)
    p = {"w_up": dense_init(ks[0], d, ff, dt),
         "w_down": dense_init(ks[1], ff, d, dt)}
    if cfg.act in ("silu", "geglu"):
        p["w_gate"] = dense_init(ks[2], d, ff, dt)
    return p


def mlp_apply(cfg, p: Params, x):
    from jax.ad_checkpoint import checkpoint_name
    cdt = dtype_of(cfg.compute_dtype)
    x = x.astype(cdt)
    up = checkpoint_name(x @ p["w_up"].astype(cdt), "mlp_pre_up")
    if "w_gate" in p:
        gate = checkpoint_name(x @ p["w_gate"].astype(cdt), "mlp_pre_gate")
        up = act_fn(cfg.act)(gate) * up
    else:
        up = act_fn(cfg.act)(up)
    return up @ p["w_down"].astype(cdt)


# -- rotary embeddings ------------------------------------------------------------

def yarn_mscale(factor: float, mscale: float = 1.0) -> float:
    """YaRN's attention factor ``0.1 * mscale * ln(factor) + 1`` (1 for no
    scaling): 1.3689 at DeepSeek-V3's factor 40."""
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def _yarn_dim(rotations: float, dim: int, theta: float, max_pos: int):
    """The rotary dimension whose wavelength fits ``rotations`` turns into
    ``max_pos`` positions."""
    return (dim * math.log(max_pos / (rotations * 2 * math.pi))
            / (2 * math.log(theta)))


def yarn_freqs(head_dim: int, theta: float, ys) -> np.ndarray:
    """YaRN inverse frequencies (``ys``: ``config.YaRNConfig``), as
    DeepSeek-V3 computes them: dimensions that turn more than
    ``beta_fast`` times over ``original_max_position_embeddings`` keep
    their frequency, those that turn fewer than ``beta_slow`` times take
    it divided by ``factor``, with a linear ramp between."""
    extra = rope_freqs(head_dim, theta)
    inter = extra / ys.factor
    orig = ys.original_max_position_embeddings
    low = max(math.floor(_yarn_dim(ys.beta_fast, head_dim, theta, orig)), 0)
    high = min(math.ceil(_yarn_dim(ys.beta_slow, head_dim, theta, orig)),
               head_dim - 1)
    ramp = np.clip((np.arange(head_dim // 2) - low)
                   / max(high - low, 1e-3), 0.0, 1.0)
    return inter * ramp + extra * (1.0 - ramp)


def rope_freqs(head_dim: int, theta: float, scaling=None):
    if scaling is not None:
        return yarn_freqs(head_dim, theta, scaling)
    return 1.0 / (theta ** (np.arange(0, head_dim, 2) / head_dim))


def apply_rope(x, positions, theta: float, scaling=None):
    """x: [..., S, H, D]; positions: broadcastable to [..., S]. With
    ``scaling`` (a ``YaRNConfig``) the frequencies are YaRN's and cos/sin
    carry ``mscale(factor, mscale) / mscale(factor, mscale_all_dim)``."""
    d = x.shape[-1]
    freqs = jnp.asarray(rope_freqs(d, theta, scaling), dtype=jnp.float32)
    ang = positions[..., :, None, None].astype(jnp.float32) * freqs  # [...,S,1,D/2]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    if scaling is not None:
        m = (yarn_mscale(scaling.factor, scaling.mscale)
             / yarn_mscale(scaling.factor, scaling.mscale_all_dim))
        if m != 1.0:
            cos, sin = cos * m, sin * m
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


def apply_mrope(x, positions3, theta: float, sections: tuple[int, ...]):
    """Qwen2-VL M-RoPE: positions3 [3, B, S] (t, h, w); the head-dim halves
    are split into `sections` (summing to D/2), each rotated with its own
    position stream (arXiv:2409.12191)."""
    d = x.shape[-1]
    half = d // 2
    assert sum(sections) == half, (sections, half)
    freqs = jnp.asarray(rope_freqs(d, theta), dtype=jnp.float32)  # [D/2]
    # choose a position stream per frequency index
    sec_id = np.repeat(np.arange(len(sections)), sections)       # [D/2]
    pos = positions3[sec_id]                                      # [D/2, B, S]
    ang = jnp.einsum("dbs,d->bsd", pos.astype(jnp.float32), freqs)
    cos, sin = jnp.cos(ang)[:, :, None, :], jnp.sin(ang)[:, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


# -- losses -----------------------------------------------------------------------

def softmax_xent(logits, labels, mask=None):
    """Mean next-token cross-entropy; logits [..., V] fp32-stable."""
    logits = logits.astype(jnp.float32)
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    nll = logz - gold
    if mask is not None:
        return (nll * mask).sum() / jnp.maximum(mask.sum(), 1.0)
    return nll.mean()


def softmax_xent_fused(h, w_head, labels, chunk: int = 0):
    """CE from hidden states without materializing [B,S,V] logits: the head
    matmul + logsumexp run per sequence chunk, so the live buffer is
    [B,chunk,V] (sized to ~256 MiB per device assuming 16-way batch
    sharding — matters for replicated odd-sized vocabs like whisper's).

    h [B,S,d] (already aligned with labels [B,S]); returns mean NLL."""
    b, s, d = h.shape
    v = w_head.shape[-1]
    if chunk <= 0:
        budget = 2 ** 28  # fp32 logits bytes per device
        b_local = max(b // 16, 1)
        v_local = v // 16 if v % 16 == 0 else v  # vocab-sharded head
        chunk = max(8, min(s, budget // max(b_local * v_local * 4, 1)))
    nll_sum = jnp.zeros((), jnp.float32)
    n = 0
    for i in range(0, s, chunk):
        hc = h[:, i:i + chunk]
        lc = labels[:, i:i + chunk]
        logits = (hc @ w_head.astype(hc.dtype)).astype(jnp.float32)
        logz = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, lc[..., None], axis=-1)[..., 0]
        nll_sum = nll_sum + (logz - gold).sum()
        n += hc.shape[1] * b
    return nll_sum / n
