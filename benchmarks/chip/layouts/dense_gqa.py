"""Dense decoder with grouped-query attention (Qwen2, InternLM2), as the
harness runs it: the benchmark's weight tree, its hand-over to the program
under test, the program's ``ModelConfig``, the decode kernel's warm-up call,
and the operations and bytes of the work of a served step.

The weight tree (``shapes``), in the dtype the configuration serves in,
with each layer's matrices stacked on a leading layer axis:

- ``embed`` [vocab, d] and, untied, ``head`` [d, vocab];
- ``final_norm`` [d]; per layer ``attn_norm`` and ``mlp_norm`` [d], all
  ones, the published RMSNorm's multiplier as a fresh checkpoint holds it;
- ``wq`` [d, heads * hd], ``wk``/``wv`` [d, kv_heads * hd], ``wo``
  [heads * hd, d], and with ``qkv_bias`` the biases ``bq``, ``bk``, ``bv``;
- ``w_gate``, ``w_up`` [d, ff] and ``w_down`` [ff, d].

``references/dense_gqa.py`` reads the same tree. Every leaf takes the
rule ``chipbench.weights`` gives its name; ``INIT`` overrides none.

The counts are of what the algorithm needs, not of what a kernel happens to
do (``chipbench.roofline``):

- paged attention reads the K and V pages that hold a sequence's tokens
  (whole pages, since a page is the unit the table maps) and the queries,
  and writes the outputs; a query at absolute position ``p`` attends over
  ``p + 1`` keys with one multiply-add each for the scores and one for the
  values;
- a model token costs two operations per weight of every matrix it passes
  through (the attention projections, the MLP and the output head; the
  embedding lookup is a read), plus its attention over its context.
"""

from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from chipbench.roofline import Work

BF16 = 2                 # bytes of a served K/V, query or output element
INIT: dict = {}


def shapes(cfg: dict) -> dict:
    d, ff, v = cfg["hidden_size"], cfg["intermediate_size"], cfg["vocab_size"]
    nl = cfg["num_hidden_layers"]
    q = cfg["num_attention_heads"] * cfg["head_dim"]
    kv = cfg["num_key_value_heads"] * cfg["head_dim"]
    layers = {"attn_norm": (nl, d), "mlp_norm": (nl, d),
              "wq": (nl, d, q), "wk": (nl, d, kv), "wv": (nl, d, kv),
              "wo": (nl, q, d), "w_gate": (nl, d, ff), "w_up": (nl, d, ff),
              "w_down": (nl, ff, d)}
    if cfg.get("qkv_bias"):
        layers.update(bq=(nl, q), bk=(nl, kv), bv=(nl, kv))
    out = {"embed": (v, d), "final_norm": (d,), "layers": layers}
    if not cfg["tie_word_embeddings"]:
        out["head"] = (d, v)
    return out


# -- the program under test --------------------------------------------------

def program_config(cfg: dict):
    """The program's ``ModelConfig`` for a configuration file."""
    from repro.models.config import ModelConfig
    return ModelConfig(
        name=cfg["name"], family="dense",
        num_layers=cfg["num_hidden_layers"], d_model=cfg["hidden_size"],
        num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_key_value_heads"],
        d_ff=cfg["intermediate_size"], vocab_size=cfg["vocab_size"],
        head_dim=cfg["head_dim"], qkv_bias=bool(cfg.get("qkv_bias")),
        tie_embeddings=cfg["tie_word_embeddings"],
        rope_theta=float(cfg["rope_theta"]), rms_eps=cfg["rms_norm_eps"],
        param_dtype=cfg["torch_dtype"], compute_dtype=cfg["torch_dtype"])


def program_params(w: dict) -> dict:
    """The same arrays in the layout ``repro`` models take (``LM.init``'s:
    one stacked layer group; its RMSNorm scales by ``1 + gamma``)."""
    lw = w["layers"]
    gamma = lambda x: {"gamma": x - 1}
    attn = {k: lw[k] for k in ("wq", "wk", "wv", "wo", "bq", "bk", "bv")
            if k in lw}
    out = {"embed": w["embed"], "final_norm": gamma(w["final_norm"]),
           "groups": [{"norm1": gamma(lw["attn_norm"]), "attn": attn,
                       "norm2": gamma(lw["mlp_norm"]),
                       "mlp": {"w_gate": lw["w_gate"], "w_up": lw["w_up"],
                               "w_down": lw["w_down"]}}]}
    if "head" in w:
        out["head"] = w["head"]
    return out


def warm_decode_call(eng, cfg: dict, b: int, mp: int):
    """One layer's decode-kernel call for a batch of ``b`` sequences over
    page tables ``mp`` pages wide, as ``PagedDecoder.decode_step`` makes
    it; returns its output."""
    from repro.kernels.paged_attention import ops as paged_ops
    q = jnp.zeros((b, cfg["num_attention_heads"], cfg["head_dim"]),
                  jnp.dtype(cfg["torch_dtype"]))
    tables = jnp.asarray(np.zeros((b, mp), np.int32))
    lens = jnp.asarray(np.ones(b, np.int32))
    return paged_ops.paged_attention(q, eng.view.k_pool[0], eng.view.v_pool[0],
                                     tables, lens, impl=eng.decoder.impl)


# -- operations and bytes ----------------------------------------------------

def _dims(cfg: dict):
    return (cfg["num_attention_heads"], cfg["num_key_value_heads"],
            cfg["head_dim"])


def _pages(tokens: int, page_size: int) -> int:
    return -(-tokens // page_size)


def decode_attention(cfg: dict, page_size: int, contexts) -> Work:
    """One layer's decode launch: each sequence's new query attends over
    ``contexts[b]`` keys (its committed tokens plus the new one)."""
    nq, nkv, hd = _dims(cfg)
    page = page_size * nkv * hd * BF16
    w = Work()
    for c in contexts:
        w.flops += 4.0 * c * nq * hd
        w.bytes += 2 * _pages(c, page_size) * page + 2 * nq * hd * BF16
    return w


def prefill_attention(cfg: dict, page_size: int, chunks) -> Work:
    """One layer's batched prefill launch over ``chunks`` of
    ``(start, length)``: query ``i`` of a chunk sits at ``start + i`` and
    attends causally over ``start + i + 1`` keys."""
    nq, nkv, hd = _dims(cfg)
    page = page_size * nkv * hd * BF16
    w = Work()
    for start, n in chunks:
        keys = n * start + n * (n + 1) / 2       # sum of start + i + 1
        w.flops += 4.0 * keys * nq * hd
        w.bytes += (2 * _pages(start + n, page_size) * page
                    + 2 * n * nq * hd * BF16)
    return w


def matmul_params(cfg: dict, head: bool = True) -> int:
    """Weights a token passes through in matrix products: every layer's
    projections and MLP, and with ``head`` the output head (a prompt token
    that only fills the cache needs no logits)."""
    nq, nkv, hd = _dims(cfg)
    d, ff = cfg["hidden_size"], cfg["intermediate_size"]
    layer = d * (nq + 2 * nkv) * hd + nq * hd * d + 3 * d * ff
    return (cfg["num_hidden_layers"] * layer
            + (d * cfg["vocab_size"] if head else 0))


def token_flops(cfg: dict, context: int, head: bool = True) -> float:
    """Model operations of one token that attends over ``context`` keys."""
    nq, _, hd = _dims(cfg)
    return (2.0 * matmul_params(cfg, head)
            + cfg["num_hidden_layers"] * 4.0 * context * nq * hd)


def chunk_flops(cfg: dict, start: int, n: int) -> float:
    """Model operations of a prefill chunk of ``n`` prompt tokens from
    position ``start``: no logits, and causal attention."""
    nq, _, hd = _dims(cfg)
    keys = n * start + n * (n + 1) / 2
    return (2.0 * matmul_params(cfg, head=False) * n
            + cfg["num_hidden_layers"] * 4.0 * keys * nq * hd)
