"""Plain float32 reference of DeepSeek-V3 as this chip serves it (the
configuration file's cut): pre-norm RMSNorm blocks; multi-head latent
attention computed without absorption (each head's key and value expanded
from the latent through ``wkv_b``), rotary positions on the rotate-half
convention with YaRN frequencies, and the softmax scale
``(nope + rope) ** -0.5 * mscale(factor, mscale_all_dim) ** 2``; a
SiLU-gated MLP in the leading dense layers; in the MoE layers, routing
over every published expert (sigmoid scores; the score-correction bias
added for the choice only; groups scored by the sum of their two best
biased scores, the best ``topk_group`` kept, the top ``num_experts_per_tok``
chosen within them; weights the unbiased scores of the chosen, normalised
with ``norm_topk_prob`` and times ``routed_scaling_factor``), the held
experts' terms and the shared expert; the output head over the
configuration's vocabulary.

It imports nothing of the program under test and reads the configuration
file's own keys and the benchmark's weight layout (``layouts/mla_moe.py``).
Every dot runs at ``Precision.HIGHEST``. Weights are cast to float32 one
layer's matrix at a time, inside the layer's scan, so the reference fits
beside the bfloat16 weights.

``control=True`` computes the same forward in float8 (e4m3), the next
precision below the bfloat16 that the configuration states, wherever the
program holds bfloat16: both operands of every weight matrix's product
(the weight with one scale per output column, the activation with one
scale per 128 of its elements along the product, DeepSeek-V3's tile of
float8 activations), the residual stream after each layer's sum, and the
cached latent and rope key, each with one scale per 128 elements;
attention's own products, softmax and the norms stay float32. The
benchmark's control tests put it in the program's place to show that the
comparison fails it.

``logit_gaps`` also counts routing flips: the token-expert choices that
differ when the router's input, the normalised hidden state, is rounded to
bfloat16 as the program's is, and prints the count to standard error.
"""

from __future__ import annotations

import functools
import math
import sys

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST
F8_MAX = 448.0           # largest finite float8_e4m3fn
HEAD_ROWS = 512          # rows of the output head computed at once
Q_ROWS = 256             # query rows of one attention block


def _fp8(w):
    """``w`` [..., in, out] rounded to float8 e4m3 with one scale per output
    column, returned in float32."""
    w = w.astype(jnp.float32)
    s = jnp.max(jnp.abs(w), axis=-2, keepdims=True) / F8_MAX
    s = jnp.where(s == 0, 1.0, s)
    return (w / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _fp8_act(a, tile: int = 128):
    """Activations ``a`` [..., n] rounded to float8 e4m3 with one scale per
    ``tile`` elements of the last axis (per row where ``tile`` does not
    divide it), returned in float32."""
    n = a.shape[-1]
    t = tile if n % tile == 0 else n
    x = a.astype(jnp.float32).reshape(*a.shape[:-1], n // t, t)
    s = jnp.max(jnp.abs(x), axis=-1, keepdims=True) / F8_MAX
    s = jnp.where(s == 0, 1.0, s)
    x = (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s
    return x.reshape(a.shape)


def _dot(a, b):
    return jnp.matmul(a, b, precision=HI)


def _linear(control: bool):
    """``lin(a, w)``: the product of activations ``a`` [..., in] and a
    weight ``w`` [in, out] (or held experts' ``e`` [T, in] by [E, in, out]
    through ``lin.einsum``), float32, or with ``control`` both rounded to
    float8; ``lin.keep(a)``: a value the forward keeps between products
    (the residual stream, a cache row), as float32 or float8 holds it."""
    wt = _fp8 if control else (lambda w: w.astype(jnp.float32))
    act = _fp8_act if control else (lambda a: a)

    def lin(a, w):
        return _dot(act(a), wt(w))
    lin.einsum = lambda spec, a, w: jnp.einsum(spec, act(a), wt(w),
                                               precision=HI)
    lin.keep = act
    return lin


def _rms(x, w, eps):
    x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return x * w.astype(jnp.float32)


def _yarn(cfg):
    """(inverse frequencies [rope / 2], cos/sin multiplier, softmax scale)
    by DeepSeek-V3's YaRN formulation (its modeling code's
    ``yarn_find_correction_range``, ``yarn_linear_ramp_mask`` and
    ``yarn_get_mscale``)."""
    ys, dim, base = (cfg["rope_scaling"], cfg["qk_rope_head_dim"],
                     float(cfg["rope_theta"]))
    mscale = lambda s, m: 1.0 if s <= 1 else 0.1 * m * math.log(s) + 1.0
    corr = lambda rot: (dim * math.log(ys["original_max_position_embeddings"]
                                       / (rot * 2 * math.pi))
                        / (2 * math.log(base)))
    low = max(math.floor(corr(ys["beta_fast"])), 0)
    high = min(math.ceil(corr(ys["beta_slow"])), dim - 1)
    if low == high:
        high += 0.001
    extra = 1.0 / base ** (np.arange(0, dim, 2) / dim)
    inter = extra / ys["factor"]
    ramp = np.clip((np.arange(dim // 2) - low) / (high - low), 0, 1)
    inv = inter * ramp + extra * (1 - ramp)
    mult = (mscale(ys["factor"], ys["mscale"])
            / mscale(ys["factor"], ys["mscale_all_dim"]))
    scale = ((cfg["qk_nope_head_dim"] + dim) ** -0.5
             * mscale(ys["factor"], ys["mscale_all_dim"]) ** 2)
    return inv, mult, scale


def _rope(x, inv, mult):
    """x [S, H, D] at positions 0..S-1, rotate-half convention."""
    s = x.shape[0]
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * jnp.asarray(
        inv, jnp.float32)[None, :]
    cos = (jnp.cos(ang) * mult)[:, None, :]
    sin = (jnp.sin(ang) * mult)[:, None, :]
    d = x.shape[-1] // 2
    x1, x2 = x[..., :d], x[..., d:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _attention(cfg, lin, x, lw):
    h_, nope, rope, v = (cfg["num_attention_heads"], cfg["qk_nope_head_dim"],
                         cfg["qk_rope_head_dim"], cfg["v_head_dim"])
    r, eps = cfg["kv_lora_rank"], cfg["rms_norm_eps"]
    inv, mult, scale = _yarn(cfg)
    s = x.shape[0]
    h = _rms(x, lw["attn_norm"], eps)
    cq = _rms(lin(h, lw["wq_a"]), lw["q_norm"], eps)
    q = lin(cq, lw["wq_b"]).reshape(s, h_, nope + rope)
    q_nope, q_pe = q[..., :nope], _rope(q[..., nope:], inv, mult)
    kv = lin(h, lw["wkv_a"])
    ckv = lin.keep(_rms(kv[:, :r], lw["kv_norm"], eps))
    k_pe = lin.keep(_rope(kv[:, None, r:], inv, mult)[:, 0])
    kvb = lin(ckv, lw["wkv_b"]).reshape(s, h_, nope + v)
    k_nope, val = kvb[..., :nope], kvb[..., nope:]
    rows = min(Q_ROWS, s)

    def block(i):
        sl = lambda a: jax.lax.dynamic_slice_in_dim(a, i * rows, rows)
        sc = (jnp.einsum("qhd,thd->hqt", sl(q_nope), k_nope, precision=HI)
              + jnp.einsum("qhe,te->hqt", sl(q_pe), k_pe, precision=HI))
        qpos = i * rows + jnp.arange(rows)
        sc = jnp.where(qpos[:, None] >= jnp.arange(s)[None, :],
                       sc * scale, -jnp.inf)
        p = jax.nn.softmax(sc, axis=-1)
        return jnp.einsum("hqt,thv->qhv", p, val, precision=HI)

    att = jax.lax.map(block, jnp.arange(s // rows)).reshape(s, h_ * v)
    return lin.keep(x + lin(att, lw["wo"]))


def _mlp(lin, h, gate, up, down):
    return lin(jax.nn.silu(lin(h, gate)) * lin(h, up), down)


def route(cfg, logits, bias):
    """(experts [T, K], weights [T, K]) of DeepSeek-V3's routing for
    float32 router ``logits`` [T, E] and correction ``bias`` [E]."""
    t, e = logits.shape
    g, k = cfg["n_group"], cfg["num_experts_per_tok"]
    scores = jax.nn.sigmoid(logits)
    choice = scores + bias.astype(jnp.float32)[None, :]
    per_group = jnp.sort(choice.reshape(t, g, e // g), axis=-1)
    group_score = per_group[..., -1] + per_group[..., -2]
    cut = jnp.sort(group_score, axis=-1)[:, g - cfg["topk_group"]]
    keep = jnp.repeat(group_score >= cut[:, None], e // g, axis=1)
    _, experts = jax.lax.top_k(jnp.where(keep, choice, -jnp.inf), k)
    w = jnp.take_along_axis(scores, experts, axis=-1)
    if cfg["norm_topk_prob"]:
        w = w / (w.sum(-1, keepdims=True) + 1e-20)
    return experts, w * cfg["routed_scaling_factor"]


def _experts(cfg, lin, x, lw, count_flips):
    """An MoE layer of the held experts; with ``count_flips`` also the
    number of token-expert choices [T] that change when the router's
    input is rounded to bfloat16."""
    h = _rms(x, lw["mlp_norm"], cfg["rms_norm_eps"])
    experts, w = route(cfg, lin(h, lw["router"]), lw["router_bias"])
    flips = jnp.zeros(h.shape[0], jnp.int32)
    if count_flips:
        hb = h.astype(jnp.bfloat16).astype(jnp.float32)
        eb, _ = route(cfg, lin(hb, lw["router"]), lw["router_bias"])
        same = (experts[:, :, None] == eb[:, None, :]).any(-1)
        flips = (~same).sum(-1).astype(jnp.int32)
    first, held = cfg["held_experts_from"], cfg["n_routed_experts"]
    gates = jnp.einsum("tk,tke->te", w,
                       jax.nn.one_hot(experts - first, held))
    y = lin.einsum("etf,efd->etd",
                   jax.nn.silu(lin.einsum("td,edf->etf", h, lw["w_gate"]))
                   * lin.einsum("td,edf->etf", h, lw["w_up"]), lw["w_down"])
    out = (jnp.einsum("te,etd->td", gates, y, precision=HI)
           + _mlp(lin, h, lw["shared_gate"], lw["shared_up"],
                  lw["shared_down"]))
    return lin.keep(x + out), flips


def hidden(cfg, w, tokens, control: bool):
    """Final-norm hidden states [S, d] of ``tokens`` [S], and the routing
    flips of each token over the MoE layers [S] (zeros with
    ``control``)."""
    lin = _linear(control)
    emb = w["embed"]
    x = (_fp8(emb.T).T if control else emb.astype(jnp.float32))[tokens]

    def dense(x, lw):
        x = _attention(cfg, lin, x, lw)
        h = _rms(x, lw["mlp_norm"], cfg["rms_norm_eps"])
        return lin.keep(x + _mlp(lin, h, lw["w_gate"], lw["w_up"],
                                 lw["w_down"])), None

    def moe(x, lw):
        return _experts(cfg, lin, _attention(cfg, lin, x, lw), lw,
                        not control)

    x, _ = jax.lax.scan(dense, x, w["dense_layers"])
    x, flips = jax.lax.scan(moe, x, w["moe_layers"])
    return _rms(x, w["final_norm"], cfg["rms_norm_eps"]), flips.sum(0)


@functools.partial(jax.jit, static_argnums=(0, 3))
def _gaps(cfg_items, w, tokens, with_control):
    """Per position ``p`` of ``tokens`` [S]: the reference's best logit
    for the next token, its logit of ``tokens[p + 1]``, its logit of the
    token that the float8 control puts first (with ``with_control``, else
    its best again), and the routing flips of the token at ``p``."""
    cfg = dict(cfg_items)
    cfg["rope_scaling"] = dict(cfg["rope_scaling"])
    h_ref, flips = hidden(cfg, w, tokens, control=False)
    h_ctl = (hidden(cfg, w, tokens, control=True)[0] if with_control
             else h_ref)
    w_ref = w["head"].astype(jnp.float32)
    w_ctl = _fp8(w["head"]) if with_control else w_ref
    nxt = jnp.concatenate([tokens[1:], tokens[:1]])
    s = tokens.shape[0]
    rows = min(HEAD_ROWS, s)

    def block(i):
        sl = lambda a: jax.lax.dynamic_slice_in_dim(a, i * rows, rows)
        ref = _dot(sl(h_ref), w_ref)
        ctl = (_dot(_fp8_act(sl(h_ctl)), w_ctl) if with_control
               else ref)
        first = jnp.argmax(ctl, axis=-1)
        take = lambda t: jnp.take_along_axis(ref, t[:, None], -1)[:, 0]
        return ref.max(-1), take(sl(nxt)), take(first)

    best, served, ctl = jax.lax.map(block, jnp.arange(s // rows))
    return best.reshape(-1), served.reshape(-1), ctl.reshape(-1), flips


def bucket(n: int) -> int:
    """Padded length: the next power of two, at least 256 (a few programs
    serve every length; padding at the end cannot reach earlier causal
    positions)."""
    return max(256, 1 << (n - 1).bit_length())


KEYS = ("num_attention_heads", "qk_nope_head_dim", "qk_rope_head_dim",
        "v_head_dim", "kv_lora_rank", "rms_norm_eps", "rope_theta",
        "n_group", "topk_group", "num_experts_per_tok", "norm_topk_prob",
        "routed_scaling_factor", "held_experts_from", "n_routed_experts")


def logit_gaps(cfg: dict, w, tokens, first_served: int,
               with_control: bool = False):
    """For a sequence ``tokens`` whose entries from ``first_served`` on were
    served by the program under test: for each served token, how far its
    reference logit lies below the reference's best at that position
    (``served``), and, ``with_control``, the same for the token the float8
    control puts first there (``control``). Float64 numpy arrays; the
    sequence's routing flips (``routing_flips``, of ``routing_choices``)
    are printed to standard error."""
    n = len(tokens)
    pad = np.zeros(bucket(n), np.int32)
    pad[:n] = tokens
    items = tuple((k, cfg[k]) for k in KEYS) + (
        ("rope_scaling", tuple(sorted(cfg["rope_scaling"].items()))),)
    best, served, ctl, flips = _gaps(items, w, jnp.asarray(pad),
                                     with_control)
    best, served, ctl = (np.asarray(a, np.float64)
                         for a in (best, served, ctl))
    pos = np.arange(first_served - 1, n - 1)
    out = {"served": best[pos] - served[pos],
           "routing_flips": int(np.asarray(flips)[:n].sum()),
           "routing_choices": n * cfg["num_experts_per_tok"] * (
               cfg["num_hidden_layers"] - cfg["first_k_dense_replace"])}
    print(f"routing flips: {out['routing_flips']} of "
          f"{out['routing_choices']} token-expert choices", file=sys.stderr)
    if with_control:
        out["control"] = best[pos] - ctl[pos]
    return out

