"""Seeded random weights in the benchmark's own layout.

The benchmark makes the weights, not the program, so that the reference
(``references/``) takes nothing the program has made. They are made on
the device in one jitted call from the seed, in the dtype they are served
in (``torch_dtype`` of the configuration file). The tree is the
architecture's: ``shapes(cfg)`` of its layout (``layouts/``), which also
hands the weights over to the program (``program_params``).

Each leaf is initialised by the rule its name selects (the name as
``jax.tree_util.keystr`` gives it, leaves in the tree's flattened order,
each from its own fold of the seed's key):

- a name holding ``norm``: ``ones``;
- ``['embed']``: ``embed``, rows of std ``EMBED_SCALE``;
- a last key starting with ``b``: ``bias``, std ``BIAS_SCALE``;
- any other, a matrix [.., in, out]: ``fan_in``, unit-variance outputs.

A layout's ``INIT`` maps the name of a leaf these rules do not fit to one
of ``RULES``, such as ``zeros`` for a router's score-correction bias that
``fan_in`` would scale by its layer count.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

EMBED_SCALE = 0.02       # std of embedding rows (the usual initializer range)
BIAS_SCALE = 0.1         # std of the QKV biases

RULES = {
    "ones": lambda k, shape: jnp.ones(shape),
    "zeros": lambda k, shape: jnp.zeros(shape),
    "embed": lambda k, shape: jax.random.normal(k, shape) * EMBED_SCALE,
    "bias": lambda k, shape: jax.random.normal(k, shape) * BIAS_SCALE,
    "fan_in": lambda k, shape: (jax.random.normal(k, shape)
                                * shape[-2] ** -0.5),
}


def seed_key(seed: int):
    """A PRNG key from a seed of up to 64 bits (``PRNGKey`` keeps only the
    low 32)."""
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)


def _rule(name: str) -> str:
    """The rule a leaf's name selects."""
    if "norm" in name:
        return "ones"
    if name.endswith("['embed']"):
        return "embed"
    if "['b" in name:
        return "bias"
    return "fan_in"


def _is_shape(x) -> bool:
    return isinstance(x, tuple)


def _init(leaves, dtype, key):
    """``leaves``: (shape, rule) of each leaf, in order."""
    return [RULES[r](jax.random.fold_in(key, i), shape).astype(dtype)
            for i, (shape, r) in enumerate(leaves)]


_init_jit = jax.jit(_init, static_argnums=(0, 1))


def make(layout, cfg: dict, seed: int) -> dict:
    """The configuration's weights for ``seed``, in ``layout``'s tree, on
    the default device."""
    leaves, tree = jax.tree.flatten_with_path(layout.shapes(cfg),
                                              is_leaf=_is_shape)
    names = [jax.tree_util.keystr(path) for path, _ in leaves]
    spec = tuple((shape, layout.INIT.get(n) or _rule(n))
                 for n, (_, shape) in zip(names, leaves))
    return jax.tree.unflatten(
        tree, _init_jit(spec, cfg["torch_dtype"], seed_key(seed)))
