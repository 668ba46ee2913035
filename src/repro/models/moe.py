"""Mixture-of-Experts: top-k router + capacity-grouped sorted dispatch, and
a dropless layer that holds a share of the experts (``held_moe_apply``).

Dispatch is ROW-LOCAL: routing/sort/capacity run independently per batch row
(vmapped sort), so under pjit everything stays batch-sharded — no global
argsort (which GSPMD can only lower by all-gathering the token stream; the
first dry-run iteration measured an 18 TB/step collective term from exactly
that). Expert weights are sharded per sharding/specs.py:
  - few big-model experts (deepseek 256e): E over 'model', FFN dim over
    'data' (FSDP-style weight gathers at use; EP all-to-all via shard_map is
    the §Perf upgrade path),
  - many small experts (granite 40e): replicated over E, TP over the FFN dim.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models import layers
from repro.models.config import ModelConfig
from repro.models.layers import Params, dense_init, dtype_of

#: Optional PartitionSpecs for the dispatch path, set by the launcher
#: (launch/dryrun.py) when experts are sharded E x (data, model).
#: The scatter/gather must stay BATCH-major (token-local; GSPMD's scatter
#: into an expert-major buffer falls back to full replication — measured
#: 9 TB/device of temps on deepseek-671b), while the expert einsums must be
#: EXPERT-major (aligned with the weights). The two constraints around the
#: reshape force GSPMD to emit the token all-to-all of production EP.
_BUF_SPEC_E = None     # [B, E, C, d] expert-major
_BUF_SPEC_B = None     # [B, slots, d] batch-major


def set_buf_spec(spec_e, spec_b=None):
    global _BUF_SPEC_E, _BUF_SPEC_B
    _BUF_SPEC_E = spec_e
    _BUF_SPEC_B = spec_b


def _constrain_e(x):
    if _BUF_SPEC_E is not None:
        return jax.lax.with_sharding_constraint(x, _BUF_SPEC_E)
    return x


def _constrain_b(x):
    if _BUF_SPEC_B is not None:
        return jax.lax.with_sharding_constraint(x, _BUF_SPEC_B)
    return x


def capacity(tokens_per_row: int, cfg: ModelConfig) -> int:
    m = cfg.moe
    c = int(tokens_per_row * m.top_k / m.num_experts * m.capacity_factor)
    return max(8, -(-c // 8) * 8)  # round up to a multiple of 8


def padded_experts(cfg: ModelConfig, align: int = 16) -> int:
    """Expert tensors are padded to a multiple of the model-axis size so the
    expert dim shards cleanly (granite's 40 -> 48; dead experts are never
    routed to — the router stays at the true expert count). A layer that
    holds a share of the experts has that share's count."""
    if cfg.moe.held_count:
        return cfg.moe.held_count
    e = cfg.moe.num_experts
    if e % align == 0 or e < align:
        return e
    return -(-e // align) * align


def moe_params(key, cfg: ModelConfig) -> Params:
    m = cfg.moe
    dt = dtype_of(cfg.param_dtype)
    d, f, e = cfg.d_model, m.d_expert, padded_experts(cfg)
    ks = jax.random.split(key, 5)

    def experts(k, d_in, d_out):
        s = 1.0 / jnp.sqrt(d_in)
        return (jax.random.normal(k, (e, d_in, d_out)) * s).astype(dt)

    p = {
        # router stays at the TRUE expert count (padded experts unreachable)
        "router": dense_init(ks[0], d, m.num_experts, jnp.float32),
        "w_gate": experts(ks[1], d, f),
        "w_up": experts(ks[2], d, f),
        "w_down": experts(ks[3], f, d),
    }
    if m.scoring == "sigmoid":
        # the score-correction bias: it chooses experts, never weights them
        p["router_bias"] = jnp.zeros((m.num_experts,), jnp.float32)
    if m.num_shared_experts:
        p["shared"] = layers.mlp_params(
            ks[4], cfg, d, f * m.num_shared_experts)
    return p


def _route_one_row(xf, router, cfg: ModelConfig, cap: int):
    """Routing for one row: xf [S, d] -> (dest [S*K], weights [S*K],
    counts [E]). dest == E*cap means 'dropped'."""
    m = cfg.moe
    k, e = m.top_k, m.num_experts
    s = xf.shape[0]
    logits = xf.astype(jnp.float32) @ router                   # [S, E]
    probs = jax.nn.softmax(logits, axis=-1)
    top_p, top_i = jax.lax.top_k(probs, k)                     # [S, K]
    if m.router_scale:
        top_p = top_p / jnp.maximum(top_p.sum(-1, keepdims=True), 1e-9)

    flat_e = top_i.reshape(s * k)
    order = jnp.argsort(flat_e, stable=True)
    sorted_e = flat_e[order]
    counts = jnp.bincount(flat_e, length=e)
    offsets = jnp.cumsum(counts) - counts
    rank = jnp.arange(s * k, dtype=jnp.int32) - offsets[sorted_e]
    keep = rank < cap
    dest_sorted = jnp.where(keep, sorted_e * cap + rank, e * cap)
    # un-sort so dest aligns with copy index (token t, choice j) = t*K+j
    dest = jnp.zeros((s * k,), jnp.int32).at[order].set(dest_sorted)
    return dest, top_p.reshape(s * k), counts, probs


def moe_apply(cfg: ModelConfig, p: Params, x):
    """x [B, S, d] -> (out [B, S, d], aux_loss scalar)."""
    m = cfg.moe
    cdt = dtype_of(cfg.compute_dtype)
    b, s, d = x.shape
    k, e = m.top_k, m.num_experts
    cap = capacity(s, cfg)

    e_pad = padded_experts(cfg)
    xf = x.astype(cdt)                                         # [B, S, d]
    dest, weights, counts, probs = jax.vmap(
        lambda row: _route_one_row(row, p["router"], cfg, cap))(xf)
    # dest [B, S*K]; weights [B, S*K]; counts [B, E]

    copies = jnp.repeat(xf, k, axis=1)                         # [B, S*K, d]
    buf = _constrain_b(jnp.zeros((b, e_pad * cap + 1, d), cdt))
    drop_slot = e * cap
    dest = jnp.where(dest >= drop_slot, e_pad * cap, dest)
    buf = _constrain_b(
        jax.vmap(lambda bb, dd, cc: bb.at[dd].set(cc))(buf, dest, copies))
    buf = _constrain_e(buf[:, :-1].reshape(b, e_pad, cap, d))

    act = layers.act_fn(cfg.act)
    h = act(jnp.einsum("becd,edf->becf", buf, p["w_gate"].astype(cdt))) \
        * jnp.einsum("becd,edf->becf", buf, p["w_up"].astype(cdt))
    y = _constrain_e(jnp.einsum("becf,efd->becd", h,
                                p["w_down"].astype(cdt)))

    y_flat = _constrain_b(
        jnp.concatenate([y.reshape(b, e_pad * cap, d),
                         jnp.zeros((b, 1, d), cdt)], axis=1))
    out_copies = jax.vmap(lambda yy, dd: yy[dd])(y_flat, dest)  # [B,S*K,d]
    out = (out_copies.reshape(b, s, k, d)
           * weights.reshape(b, s, k)[..., None].astype(cdt)).sum(axis=2)

    if m.num_shared_experts:
        out = out + layers.mlp_apply(cfg, p["shared"], xf)

    # load-balance aux loss (Switch-style), averaged over rows
    frac_tokens = counts.astype(jnp.float32) / jnp.maximum(s * k, 1)
    mean_prob = probs.mean(axis=1)                              # [B, E]
    aux = e * jnp.sum(frac_tokens * mean_prob, axis=-1).mean() \
        * m.aux_loss_weight
    return out.reshape(b, s, d), aux


# -- a share of the experts, dropless (DeepSeek-V3 serving) -------------------

def route_grouped(cfg: ModelConfig, logits, bias):
    """DeepSeek-V3 routing over all ``num_experts`` experts for ``logits``
    [T, E] (float32): sigmoid scores; the correction ``bias`` [E] added for
    the choice only; each of ``n_group`` groups scored by the sum of its two
    best biased scores; the best ``topk_group`` groups kept, and the
    ``top_k`` best biased scores within them chosen. Returns (experts
    [T, K] int32, weights [T, K] float32): the unbiased scores of the
    chosen, normalised to sum to 1 (``router_scale``), times
    ``routed_scaling_factor``."""
    m = cfg.moe
    t, e = logits.shape
    scores = jax.nn.sigmoid(logits.astype(jnp.float32))
    choice = scores + bias.astype(jnp.float32)
    groups = choice.reshape(t, m.n_group, e // m.n_group)
    group_score = jax.lax.top_k(groups, 2)[0].sum(-1)          # [T, G]
    _, keep = jax.lax.top_k(group_score, m.topk_group)
    kept = jax.nn.one_hot(keep, m.n_group, dtype=jnp.bool_).any(1)
    choice = jnp.where(jnp.repeat(kept, e // m.n_group, axis=1), choice,
                       -jnp.inf)
    _, experts = jax.lax.top_k(choice, m.top_k)
    w = jnp.take_along_axis(scores, experts, -1)
    if m.router_scale:
        w = w / (w.sum(-1, keepdims=True) + 1e-20)
    return experts, w * m.routed_scaling_factor


def held_moe_apply(cfg: ModelConfig, p: Params, x):
    """The expert layer of one chip that holds ``cfg.moe.held`` of the
    experts: routes ``x`` [T, d] over all of them (``route_grouped``,
    scores in float32) and drops no token; computes the held experts'
    terms, each weighted by its gate (0 for a token that did not choose
    it), plus the shared expert. The other chips' terms are theirs to add.

    The held experts run as one dense pass over every token: at decode
    batches their weights are read once whatever the tokens, and that read
    bounds the layer. Returns (out [T, d], held [T] int32: how many of
    each token's ``top_k`` choices fell on a held expert)."""
    m = cfg.moe
    cdt = dtype_of(cfg.compute_dtype)
    first, count = m.held
    logits = jnp.dot(x.astype(jnp.float32), p["router"].astype(jnp.float32),
                     precision=jax.lax.Precision.HIGHEST)
    experts, w = route_grouped(cfg, logits, p["router_bias"])
    local = jax.nn.one_hot(experts - first, count, dtype=jnp.float32)
    gates = jnp.einsum("tk,tke->te", w, local)                 # [T, held]
    xc = x.astype(cdt)
    act = layers.act_fn(cfg.act)
    h = act(jnp.einsum("td,edf->etf", xc, p["w_gate"].astype(cdt))) \
        * jnp.einsum("td,edf->etf", xc, p["w_up"].astype(cdt))
    y = jnp.einsum("etf,efd->etd", h, p["w_down"].astype(cdt))
    out = jnp.einsum("te,etd->td", gates, y.astype(jnp.float32))
    if m.num_shared_experts:
        out = out + layers.mlp_apply(cfg, p["shared"], xc).astype(jnp.float32)
    return out.astype(cdt), local.sum((1, 2)).astype(jnp.int32)
