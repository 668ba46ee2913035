"""Plain float32 reference of a dense decoder with grouped-query attention
(Qwen2, InternLM2): pre-norm RMSNorm blocks, rotary positions on the
rotate-half convention, causal softmax attention in which query head ``h``
reads key/value head ``h // (heads / kv_heads)``, and a SiLU-gated MLP.

It imports nothing of the program under test and reads the configuration
file's own keys and the benchmark's weight layout (``chipbench.weights``).
Every dot runs at ``Precision.HIGHEST``: on a TPU a float32 matrix product
otherwise runs in one bfloat16 pass.

``control=True`` computes the same forward with every weight matrix
rounded to float8 (e4m3, one scale per output column): the next precision
below the bfloat16 that the configurations state. The benchmark's control
tests put it in the program's place to show that the comparison fails it.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST
F8_MAX = 448.0           # largest finite float8_e4m3fn
HEAD_ROWS = 512          # rows of the output head computed at once


def _fp8(w):
    """``w`` [..., in, out] rounded to float8 e4m3 with one scale per output
    column, returned in float32."""
    w = w.astype(jnp.float32)
    s = jnp.max(jnp.abs(w), axis=-2, keepdims=True) / F8_MAX
    s = jnp.where(s == 0, 1.0, s)
    return (w / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _rms(x, w, eps):
    x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return x * w.astype(jnp.float32)


def _rope(x, theta):
    """x [S, H, D] at positions 0..S-1, rotate-half convention."""
    s, _, d = x.shape
    inv = jnp.asarray(1.0 / (theta ** (np.arange(0, d, 2) / d)), jnp.float32)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _dims(cfg):
    return (cfg["num_attention_heads"], cfg["num_key_value_heads"],
            cfg["head_dim"])


def _layer(cfg, control, x, lw):
    nq, nkv, hd = _dims(cfg)
    s = x.shape[0]
    mat = _fp8 if control else (lambda w: w.astype(jnp.float32))
    eps = cfg["rms_norm_eps"]
    h = _rms(x, lw["attn_norm"], eps)
    q = jnp.dot(h, mat(lw["wq"]), precision=HI)
    k = jnp.dot(h, mat(lw["wk"]), precision=HI)
    v = jnp.dot(h, mat(lw["wv"]), precision=HI)
    if cfg.get("qkv_bias"):
        q = q + lw["bq"].astype(jnp.float32)
        k = k + lw["bk"].astype(jnp.float32)
        v = v + lw["bv"].astype(jnp.float32)
    q = _rope(q.reshape(s, nq, hd), cfg["rope_theta"])
    k = _rope(k.reshape(s, nkv, hd), cfg["rope_theta"])
    v = v.reshape(s, nkv, hd)
    g = nq // nkv
    q = q.reshape(s, nkv, g, hd)
    scores = jnp.einsum("qkgd,tkd->kgqt", q, k, precision=HI) / np.sqrt(hd)
    causal = jnp.arange(s)[:, None] >= jnp.arange(s)[None, :]
    scores = jnp.where(causal, scores, -jnp.inf)
    p = jax.nn.softmax(scores, axis=-1)
    att = jnp.einsum("kgqt,tkd->qkgd", p, v, precision=HI).reshape(s, nq * hd)
    x = x + jnp.dot(att, mat(lw["wo"]), precision=HI)
    h = _rms(x, lw["mlp_norm"], eps)
    gate = jnp.dot(h, mat(lw["w_gate"]), precision=HI)
    up = jnp.dot(h, mat(lw["w_up"]), precision=HI)
    return x + jnp.dot(jax.nn.silu(gate) * up, mat(lw["w_down"]),
                       precision=HI)


def _head(cfg, w, control):
    head = w["embed"].T if cfg["tie_word_embeddings"] else w["head"]
    return _fp8(head) if control else head.astype(jnp.float32)


def hidden(cfg, w, tokens, control: bool):
    """Final-norm hidden states [S, d] of ``tokens`` [S]."""
    emb = w["embed"]
    x = (_fp8(emb.T).T if control else emb.astype(jnp.float32))[tokens]
    x, _ = jax.lax.scan(lambda x, lw: (_layer(cfg, control, x, lw), None),
                        x, w["layers"])
    return _rms(x, w["final_norm"], cfg["rms_norm_eps"])


@functools.partial(jax.jit, static_argnums=(0, 3))
def _gaps(cfg_items, w, tokens, with_control):
    """Per position ``p`` of ``tokens`` [S]: the reference's best logit
    for the next token, its logit of ``tokens[p + 1]``, and (with
    ``with_control``, else its best again) its logit of the token that the
    float8 control puts first."""
    cfg = dict(cfg_items)
    h_ref = hidden(cfg, w, tokens, control=False)
    h_ctl = hidden(cfg, w, tokens, control=True) if with_control else h_ref
    w_ref = _head(cfg, w, False)
    w_ctl = _head(cfg, w, True) if with_control else w_ref
    nxt = jnp.concatenate([tokens[1:], tokens[:1]])
    s = tokens.shape[0]
    rows = min(HEAD_ROWS, s)

    def block(i):
        sl = lambda a: jax.lax.dynamic_slice_in_dim(a, i * rows, rows)
        ref = jnp.dot(sl(h_ref), w_ref, precision=HI)
        ctl = (jnp.dot(sl(h_ctl), w_ctl, precision=HI) if with_control
               else ref)
        first = jnp.argmax(ctl, axis=-1)
        take = lambda t: jnp.take_along_axis(ref, t[:, None], -1)[:, 0]
        return ref.max(-1), take(sl(nxt)), take(first)

    best, served, ctl = jax.lax.map(block, jnp.arange(s // rows))
    return best.reshape(-1), served.reshape(-1), ctl.reshape(-1)


def bucket(n: int) -> int:
    """Padded length: the next power of two, at least 256 (a few programs
    serve every length; padding at the end cannot reach earlier causal
    positions)."""
    return max(256, 1 << (n - 1).bit_length())


def logit_gaps(cfg: dict, w, tokens, first_served: int,
               with_control: bool = False):
    """For a sequence ``tokens`` whose entries from ``first_served`` on were
    served by the program under test: for each served token, how far its
    reference logit lies below the reference's best at that position
    (``served``), and, ``with_control``, the same for the token the float8
    control puts first there (``control``). Float64 numpy arrays."""
    n = len(tokens)
    pad = np.zeros(bucket(n), np.int32)
    pad[:n] = tokens
    keys = ("num_attention_heads", "num_key_value_heads", "head_dim",
            "rms_norm_eps", "rope_theta", "tie_word_embeddings", "qkv_bias")
    items = tuple((k, cfg.get(k)) for k in keys)
    best, served, ctl = (np.asarray(a, np.float64)
                         for a in _gaps(items, w, jnp.asarray(pad),
                                        with_control))
    pos = np.arange(first_served - 1, n - 1)
    out = {"served": best[pos] - served[pos]}
    if with_control:
        out["control"] = best[pos] - ctl[pos]
    return out
