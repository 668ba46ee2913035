"""Seeded random weights in the benchmark's own layout, and their hand-over
to the program under test.

The benchmark makes the weights, not the program, so that the reference
(``references/``) takes nothing the program has made. They are made on
the device in one jitted call from the seed, in the dtype they are served
in (``torch_dtype`` of the configuration file), with each layer's matrices
stacked on a leading layer axis:

- ``embed`` [vocab, d] and, untied, ``head`` [d, vocab];
- ``final_norm`` [d]; per layer ``attn_norm`` and ``mlp_norm`` [d], all
  ones, the published RMSNorm's multiplier as a fresh checkpoint holds it;
- ``wq`` [d, heads * hd], ``wk``/``wv`` [d, kv_heads * hd], ``wo``
  [heads * hd, d], and with ``qkv_bias`` the biases ``bq``, ``bk``, ``bv``;
- ``w_gate``, ``w_up`` [d, ff] and ``w_down`` [ff, d].
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

EMBED_SCALE = 0.02       # std of embedding rows (the usual initializer range)
BIAS_SCALE = 0.1         # std of the QKV biases


def seed_key(seed: int):
    """A PRNG key from a seed of up to 64 bits (``PRNGKey`` keeps only the
    low 32)."""
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)


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


def _is_shape(x) -> bool:
    return isinstance(x, tuple)


def _init(cfg_items, key):
    cfg = dict(cfg_items)
    dt = jnp.dtype(cfg["torch_dtype"])
    leaves, tree = jax.tree.flatten_with_path(shapes(cfg), is_leaf=_is_shape)
    out = []
    for i, (path, shape) in enumerate(leaves):
        name = jax.tree_util.keystr(path)
        k = jax.random.fold_in(key, i)
        if "norm" in name:
            out.append(jnp.ones(shape, dt))
        elif name.endswith("['embed']"):
            out.append((jax.random.normal(k, shape) * EMBED_SCALE).astype(dt))
        elif "['b" in name:
            out.append((jax.random.normal(k, shape) * BIAS_SCALE).astype(dt))
        else:            # [.., in, out]: unit-variance outputs
            out.append((jax.random.normal(k, shape)
                        * shape[-2] ** -0.5).astype(dt))
    return jax.tree.unflatten(tree, out)


_init_jit = jax.jit(_init, static_argnums=0)


def make(cfg: dict, seed: int) -> dict:
    """The configuration's weights for ``seed``, on the default device."""
    return _init_jit(tuple(sorted((k, v) for k, v in cfg.items()
                                  if isinstance(v, (int, float, str, bool)))),
                     seed_key(seed))


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

