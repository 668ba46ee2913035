"""DeepSeek-V3 as the harness runs it: multi-head latent attention (MLA)
with YaRN rotary positions, leading dense layers, then MoE layers of which
this chip holds a share of the routed experts (``held_experts_from``,
``n_routed_experts`` of ``published_n_routed_experts``) beside the shared
expert. The benchmark's weight tree, its hand-over to the program under
test, the program's ``ModelConfig``, the latent decode kernel's warm-up
call, and the operations and bytes of the work of a served step.

The weight tree (``shapes``), in the dtype the configuration serves in,
each group's layers stacked on a leading axis:

- ``embed`` [vocab, d], ``head`` [d, vocab], ``final_norm`` [d];
- per layer of both groups (``dense_layers``, the first
  ``first_k_dense_replace``; ``moe_layers``, the rest): ``attn_norm``,
  ``mlp_norm`` [d]; ``wq_a`` [d, q_lora], ``q_norm`` [q_lora], ``wq_b``
  [q_lora, heads * (nope + rope)], ``wkv_a`` [d, kv_lora + rope],
  ``kv_norm`` [kv_lora], ``wkv_b`` [kv_lora, heads * (nope + v)], ``wo``
  [heads * v, d]; the norms all ones;
- ``dense_layers``: ``w_gate``, ``w_up`` [d, ff] and ``w_down`` [ff, d];
- ``moe_layers``: ``router`` [d, published experts] and its
  score-correction ``router_bias`` [published experts] (``INIT``: the
  ``bias`` rule, so that choosing an expert differs from weighting it);
  the held experts' ``w_gate``, ``w_up`` [held, d, moe_ff] and ``w_down``
  [held, moe_ff, d]; the shared expert's ``shared_gate``, ``shared_up``
  [d, moe_ff * n_shared] and ``shared_down``.

``references/mla_moe.py`` reads the same tree.

The counts are of what the algorithm needs (``chipbench.roofline``):

- absorbed MLA decode: each of the ``heads`` queries reads one shared key
  of ``kv_lora + rope`` and a value that is the ``kv_lora`` latent, one
  multiply-add per element per context token; whole latent and rope pages
  are read (the page is the unit the table maps);
- a model token costs two operations per weight it passes through: every
  MLA matrix (``W_UK`` and ``W_UV`` once each, absorbed or not), the dense
  MLP or the router, the shared expert and the held experts at their
  expectation (``num_experts_per_tok`` of the published experts, so 0.25
  of an expert at 8 held of 256, top-8), and the output head.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.roofline import Work
from repro.models.config import MLAConfig, ModelConfig, MoEConfig, YaRNConfig

BF16 = 2                 # bytes of a served latent, query or output element
INIT = {"['moe_layers']['router_bias']": "bias"}


class Launch(Work):
    """One layer's work of a launch, and the tokens it served (the rows an
    expert layer of the same step routes)."""

    def __init__(self, tokens: int):
        super().__init__()
        self.tokens = tokens


def _mla(cfg):
    return (cfg["num_attention_heads"], cfg["q_lora_rank"],
            cfg["kv_lora_rank"], cfg["qk_nope_head_dim"],
            cfg["qk_rope_head_dim"], cfg["v_head_dim"])


def _attn_shapes(cfg, n: int) -> dict:
    d = cfg["hidden_size"]
    h, qr, r, nope, rope, v = _mla(cfg)
    return {"attn_norm": (n, d), "mlp_norm": (n, d),
            "wq_a": (n, d, qr), "q_norm": (n, qr),
            "wq_b": (n, qr, h * (nope + rope)),
            "wkv_a": (n, d, r + rope), "kv_norm": (n, r),
            "wkv_b": (n, r, h * (nope + v)), "wo": (n, h * v, d)}


def shapes(cfg: dict) -> dict:
    d, vocab = cfg["hidden_size"], cfg["vocab_size"]
    k = cfg["first_k_dense_replace"]
    m = cfg["num_hidden_layers"] - k
    ff, f = cfg["intermediate_size"], cfg["moe_intermediate_size"]
    held, fs = cfg["n_routed_experts"], f * cfg["n_shared_experts"]
    e = cfg["published_n_routed_experts"]
    dense = {**_attn_shapes(cfg, k), "w_gate": (k, d, ff),
             "w_up": (k, d, ff), "w_down": (k, ff, d)}
    moe = {**_attn_shapes(cfg, m), "router": (m, d, e),
           "router_bias": (m, e), "w_gate": (m, held, d, f),
           "w_up": (m, held, d, f), "w_down": (m, held, f, d),
           "shared_gate": (m, d, fs), "shared_up": (m, d, fs),
           "shared_down": (m, fs, d)}
    return {"embed": (vocab, d), "head": (d, vocab), "final_norm": (d,),
            "dense_layers": dense, "moe_layers": moe}


# -- the program under test --------------------------------------------------

def program_config(cfg: dict):
    """The program's ``ModelConfig`` for a configuration file: the router
    scores every published expert, and the layer holds
    ``n_routed_experts`` of them from ``held_experts_from``."""
    ys = cfg["rope_scaling"]
    if ys["type"] != "yarn" or cfg["scoring_func"] != "sigmoid":
        raise ValueError(f"{cfg['name']}: the program serves YaRN and "
                         "sigmoid routing only")
    _, qr, r, nope, rope, v = _mla(cfg)
    return ModelConfig(
        name=cfg["name"], family="moe",
        num_layers=cfg["num_hidden_layers"], d_model=cfg["hidden_size"],
        num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_key_value_heads"],
        d_ff=cfg["moe_intermediate_size"], vocab_size=cfg["vocab_size"],
        tie_embeddings=cfg["tie_word_embeddings"],
        rope_theta=float(cfg["rope_theta"]), rms_eps=cfg["rms_norm_eps"],
        rope_scaling=YaRNConfig(
            factor=float(ys["factor"]),
            original_max_position_embeddings=(
                ys["original_max_position_embeddings"]),
            beta_fast=float(ys["beta_fast"]),
            beta_slow=float(ys["beta_slow"]), mscale=float(ys["mscale"]),
            mscale_all_dim=float(ys["mscale_all_dim"])),
        moe=MoEConfig(
            num_experts=cfg["published_n_routed_experts"],
            top_k=cfg["num_experts_per_tok"],
            d_expert=cfg["moe_intermediate_size"],
            num_shared_experts=cfg["n_shared_experts"],
            first_k_dense=cfg["first_k_dense_replace"],
            d_ff_dense=cfg["intermediate_size"],
            router_scale=cfg["norm_topk_prob"], scoring="sigmoid",
            n_group=cfg["n_group"], topk_group=cfg["topk_group"],
            routed_scaling_factor=float(cfg["routed_scaling_factor"]),
            held_first=cfg["held_experts_from"],
            held_count=cfg["n_routed_experts"]),
        mla=MLAConfig(q_lora_rank=qr, kv_lora_rank=r, qk_nope_head_dim=nope,
                      qk_rope_head_dim=rope, v_head_dim=v),
        param_dtype=cfg["torch_dtype"], compute_dtype=cfg["torch_dtype"])


def program_params(w: dict) -> dict:
    """The same arrays in the layout ``repro`` models take: one stacked
    tree per layer group (``LM.make_groups``' split), RMSNorm scales as
    ``1 + gamma``."""
    gamma = lambda x: {"gamma": x - 1}

    def group(lw, ffn):
        attn = {k: lw[k] for k in ("wq_a", "wq_b", "wkv_a", "wkv_b", "wo")}
        attn.update(q_norm=lw["q_norm"] - 1, kv_norm=lw["kv_norm"] - 1)
        return {"norm1": gamma(lw["attn_norm"]), "attn": attn,
                "norm2": gamma(lw["mlp_norm"]), **ffn}

    dw, mw = w["dense_layers"], w["moe_layers"]
    dense = group(dw, {"mlp": {k: dw[k] for k in
                               ("w_gate", "w_up", "w_down")}})
    moe = group(mw, {"moe": {
        "router": mw["router"], "router_bias": mw["router_bias"],
        "w_gate": mw["w_gate"], "w_up": mw["w_up"], "w_down": mw["w_down"],
        "shared": {"w_gate": mw["shared_gate"], "w_up": mw["shared_up"],
                   "w_down": mw["shared_down"]}}})
    return {"embed": w["embed"], "head": w["head"],
            "final_norm": gamma(w["final_norm"]), "groups": [dense, moe]}


def warm_decode_call(eng, cfg: dict, b: int, mp: int):
    """The latent decode kernel's call for a batch of ``b`` sequences over
    page tables ``mp`` pages wide, on the stacked pools, as
    ``PagedDecoder.decode_step`` makes it (the tables widened to
    ``latent_table_width``); returns its output. The queries are placed
    on the pools' device, as the decoder's are (a program's outputs):
    a call whose arguments are placed differently compiles anew."""
    from repro.kernels.paged_attention import ops as paged_ops
    from repro.serve.engine import latent_table_width
    h, _, r, _, rope, _ = _mla(cfg)
    dt = jnp.dtype(cfg["torch_dtype"])
    on_pool = lambda shape: jax.device_put(jnp.zeros(shape, dt),
                                           eng.view.k_pool.sharding)
    return paged_ops.paged_latent_attention(
        on_pool((b, h, r)), on_pool((b, h, rope)),
        eng.view.k_pool, eng.view.v_pool,
        jnp.asarray(np.zeros((b, latent_table_width(mp)), np.int32)),
        jnp.asarray(np.ones(b, np.int32)), 0, scale=eng.decoder.scale,
        impl=eng.decoder.impl)


# -- operations and bytes ----------------------------------------------------

def _pages(tokens: int, page_size: int) -> int:
    return -(-tokens // page_size)


def _page_bytes(cfg, page_size: int) -> int:
    _, _, r, _, rope, _ = _mla(cfg)
    return page_size * (r + rope) * BF16


def _key_flops(cfg) -> float:
    """Operations per query token per context token of one layer's
    absorbed attention: heads x (kv_lora + rope) multiply-adds for the
    score and heads x kv_lora for the value."""
    h, _, r, _, rope, _ = _mla(cfg)
    return 2.0 * h * (r + rope + r)


def _query_bytes(cfg) -> int:
    """The query (latent and rope) and the output latent of one token."""
    h, _, r, _, rope, _ = _mla(cfg)
    return h * (r + rope + r) * BF16


def decode_attention(cfg: dict, page_size: int, contexts) -> Launch:
    """One layer's latent decode launch: each sequence's new query attends
    over ``contexts[b]`` keys (its committed tokens plus the new one)."""
    w = Launch(tokens=len(contexts))
    for c in contexts:
        w.flops += _key_flops(cfg) * c
        w.bytes += (_pages(c, page_size) * _page_bytes(cfg, page_size)
                    + _query_bytes(cfg))
    return w


def prefill_attention(cfg: dict, page_size: int, chunks) -> Launch:
    """One layer's prefill over ``chunks`` of ``(start, length)``: query
    ``i`` of a chunk sits at ``start + i`` and attends causally over
    ``start + i + 1`` keys."""
    w = Launch(tokens=sum(n for _, n in chunks))
    for start, n in chunks:
        keys = n * start + n * (n + 1) / 2
        w.flops += _key_flops(cfg) * keys
        w.bytes += (_pages(start + n, page_size) * _page_bytes(cfg, page_size)
                    + n * _query_bytes(cfg))
    return w


def attention_params(cfg: dict) -> int:
    """Weights of one layer's MLA."""
    d = cfg["hidden_size"]
    h, qr, r, nope, rope, v = _mla(cfg)
    return (d * qr + qr * h * (nope + rope) + d * (r + rope)
            + r * h * (nope + v) + h * v * d)


def expert_params(cfg: dict) -> tuple[int, int]:
    """(weights one MoE layer reads per call: router, shared expert and
    every held expert; weights one token passes through there: router,
    shared expert and the held experts at their expectation)."""
    d, f = cfg["hidden_size"], cfg["moe_intermediate_size"]
    e = cfg["published_n_routed_experts"]
    held = cfg["n_routed_experts"]
    expert = 3 * d * f
    fixed = d * e + expert * cfg["n_shared_experts"]
    expected = cfg["num_experts_per_tok"] * held / e
    return fixed + expert * held, fixed + expert * expected


def expert_layer_work(cfg: dict, tokens: int) -> Work:
    """One MoE layer's call over ``tokens`` tokens: every weight it holds
    read once, two operations per weight each token passes through."""
    read, per_token = expert_params(cfg)
    return Work(flops=2.0 * per_token * tokens, bytes=read * BF16)


def matmul_params(cfg: dict, head: bool = True) -> float:
    """Weights a token passes through in matrix products: every layer's
    MLA, the dense layers' MLP, the MoE layers' router, shared and
    expected held experts, and with ``head`` the output head."""
    d = cfg["hidden_size"]
    k = cfg["first_k_dense_replace"]
    m = cfg["num_hidden_layers"] - k
    dense = 3 * d * cfg["intermediate_size"]
    return (cfg["num_hidden_layers"] * attention_params(cfg) + k * dense
            + m * expert_params(cfg)[1]
            + (d * cfg["vocab_size"] if head else 0))


def token_flops(cfg: dict, context: int, head: bool = True) -> float:
    """Model operations of one token that attends over ``context`` keys."""
    return (2.0 * matmul_params(cfg, head)
            + cfg["num_hidden_layers"] * _key_flops(cfg) * context)


def chunk_flops(cfg: dict, start: int, n: int) -> float:
    """Model operations of a prefill chunk of ``n`` prompt tokens from
    position ``start``: no logits, and causal attention."""
    keys = n * start + n * (n + 1) / 2
    return (2.0 * matmul_params(cfg, head=False) * n
            + cfg["num_hidden_layers"] * _key_flops(cfg) * keys)
