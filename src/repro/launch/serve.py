"""Serving entry point: a registry architecture through ``ServeEngine`` —
the request scheduler, the fabric view and the paged-attention kernels.

By default the model is built at its published widths and dtypes
(``registry.get_config``) with seeded random weights. On TPU the engine
runs the Pallas paged kernels; on CPU it runs their jnp reference.
``--smoke`` swaps in the architecture's tiny smoke config (2 layers,
float32) so a CPU run takes seconds; full width belongs on the chip:

    PYTHONPATH=src python -m repro.launch.serve --arch qwen2-0.5b
    PYTHONPATH=src JAX_PLATFORMS=cpu python -m repro.launch.serve --smoke

The request mix is ``--requests`` prompts of 32 to 300 tokens (so the
longest is chunked across prefill steps) with ``--new`` tokens each, and
the KV pool is sized to hold the whole mix at once. Compiled programs go
to ``JAX_COMPILATION_CACHE_DIR`` when it is set, else to ``.jax_cache``
in the checkout. ``chip_smoke.py`` drives these same functions.
"""

from __future__ import annotations

import argparse
import dataclasses
import os
import pathlib
import time

import jax
import numpy as np

from repro.configs import registry
from repro.core import topology
from repro.models.config import ModelConfig
from repro.models.lm import LM
from repro.placement.pool import BwapPagePool, MemoryDomain
from repro.serve.engine import ServeEngine

CHECKOUT = pathlib.Path(__file__).resolve().parents[3]
PROMPT_MIN, PROMPT_MAX = 32, 300
SEED = 0                  # the weights' seed; prompts are drawn from it too


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compile cache and return its directory:
    ``JAX_COMPILATION_CACHE_DIR`` when set (JAX reads it itself), else one
    fixed directory inside the checkout. Every program is cached, however
    fast it compiled: the engine's layer loop dispatches many small
    per-op programs, and each would otherwise recompile on every run."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(CHECKOUT / ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path


def load_config(arch: str, smoke: bool) -> ModelConfig:
    """Published widths and dtypes, or the 2-layer float32 smoke cut."""
    if smoke:
        return dataclasses.replace(registry.get_smoke_config(arch),
                                   num_layers=2, compute_dtype="float32")
    return registry.get_config(arch)


def prompt_lengths(requests: int) -> list[int]:
    """Prompt lengths spread evenly over [PROMPT_MIN, PROMPT_MAX]."""
    return [int(n) for n in np.linspace(PROMPT_MIN, PROMPT_MAX, requests)]


def make_prompts(cfg: ModelConfig, lengths, seed: int) -> list[list[int]]:
    rng = np.random.default_rng(seed)
    return [rng.integers(1, cfg.vocab_size, n).tolist() for n in lengths]


def pool_pages(lengths, new: int, page_size: int) -> int:
    """Pages that hold every request's whole footprint (prompt plus new
    tokens) at once, plus one per request for a copy-on-write fork, so the
    mix is served without preemption."""
    return sum(-(-(n + new) // page_size) + 1 for n in lengths)


def build_engine(cfg: ModelConfig, lengths, new: int, *,
                 page_size: int = 16) -> ServeEngine:
    """Seeded random weights and a pool sized for the request mix. The pool
    is one domain: on one chip only its HBM is physical memory."""
    params = jax.jit(LM(cfg).init)(jax.random.PRNGKey(SEED))
    pool = BwapPagePool(cfg, [
        MemoryDomain("hbm_local", pool_pages(lengths, new, page_size),
                     topology.V5E_HBM_BW, True),
    ], page_size=page_size)
    return ServeEngine(cfg, params, pool, max_batch=len(lengths),
                       max_new=new)


def serve(eng: ServeEngine, prompts) -> dict:
    """Submit ``prompts`` and step the engine until all of them finish.
    Each step's wall time ends when the pool's K/V writes are done."""
    for p in prompts:
        eng.submit(p)
    finished0, tokens0 = len(eng.finished), eng.tokens_emitted
    # every step prefills at least one prompt token or decodes one token
    max_steps = sum(len(p) for p in prompts) + \
        len(prompts) * eng.scheduler.default_max_new
    step_s: list[float] = []
    while eng.active or eng.waiting:
        if len(step_s) >= max_steps:
            raise RuntimeError(f"engine made no progress in {max_steps} steps")
        t0 = time.perf_counter()
        eng.step()
        jax.block_until_ready((eng.view.k_pool, eng.view.v_pool))
        step_s.append(time.perf_counter() - t0)
    return {"finished": len(eng.finished) - finished0,
            "tokens": eng.tokens_emitted - tokens0,
            "steps": len(step_s), "step_s": step_s}


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--arch", default="qwen2-0.5b")
    ap.add_argument("--smoke", action="store_true",
                    help="the arch's 2-layer float32 smoke config (CPU)")
    ap.add_argument("--requests", type=int, default=4)
    ap.add_argument("--new", type=int, default=16)
    ap.add_argument("--page-size", type=int, default=16)
    args = ap.parse_args()

    enable_compile_cache()
    cfg = load_config(args.arch, args.smoke)
    lengths = prompt_lengths(args.requests)
    eng = build_engine(cfg, lengths, args.new, page_size=args.page_size)
    out = serve(eng, make_prompts(cfg, lengths, SEED))
    dev = jax.devices()[0]
    print(f"{cfg.name} ({cfg.num_layers} layers, d {cfg.d_model}, "
          f"{cfg.compute_dtype}) on {dev.platform} ({dev.device_kind}), "
          f"attention {eng.decoder.impl}: served {out['finished']}/"
          f"{len(lengths)} requests, {out['tokens']} tokens in "
          f"{out['steps']} engine steps; pool "
          f"{eng.view.pool.total_pages} pages of {args.page_size}")


if __name__ == "__main__":
    main()
