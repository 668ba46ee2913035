"""One-chip smoke test of the served path at published widths.

    python chip_smoke.py

Serves a few seeded requests with qwen2-0.5b at its published widths
(24 layers, d 896, 14/2 heads, ff 4864, vocab 151936, bf16 compute, random
weights from a seed) through ``repro.launch.serve``: ``ServeEngine.step``
over the request scheduler, the fabric view and the Pallas paged kernels.
The mix is served twice: a warm-up pass that compiles every shape, then a
timed pass with fresh prompts of the same lengths. Checks, each fatal:

- every request of both passes finishes with all its new tokens;
- in the first decode batch of every request, and in the first prefill
  batch with a chunk that starts past position 0 (so its kernel reads
  pages an earlier chunk wrote), every attention launch agrees within
  ``ATTENTION_RTOL`` with the jnp reference attention run on the same
  inputs;
- that decode batch, rerun through the reference attention path from the
  pool state it started from, gives logits within ``LOGITS_RTOL``;
- the compiled decode and prefill attention ops hold the Pallas kernel
  (``tpu_custom_call``).

Exits non-zero, printing no result, when JAX finds no TPU. The last line
of standard output is one JSON object naming the device.
"""

from __future__ import annotations

import contextlib
import copy
import json
import pathlib
import statistics
import sys
import time

ARCH = "qwen2-0.5b"
REQUESTS = 4
NEW_TOKENS = 16
PAGE_SIZE = 16
# Relative L2 errors allowed against the jnp reference attention path.
#
# ATTENTION_RTOL bounds one attention launch against the reference on the
# same inputs. Both walk the pages with the same online softmax after
# casting q, K and V to f32; only the order of f32 additions differs, which
# flips an occasional bf16 rounding of an output (about 4e-6 on a v5e at
# qwen2-0.5b widths). A wrong page, slot or mask moves whole rows.
ATTENTION_RTOL = 1e-3
# LOGITS_RTOL bounds the logits of a whole step against the reference path
# run from the same pool. Through 24 layers with a bf16 residual stream,
# one flipped rounding grows into bf16 noise on every activation: about
# sqrt(24) * 2^-8 = 1.9e-2 if each layer's rounding adds independently. On
# a v5e, a prefill batch whose launches each agree within 4e-6 ends 1.2e-2
# apart in its logits. The decode kernel happens to sum in the reference's
# order there, and its logits agree bit for bit.
LOGITS_RTOL = 5e-2
TOLERANCES = {"decode_attention": ATTENTION_RTOL,
              "prefill_attention": ATTENTION_RTOL,
              "decode_logits": LOGITS_RTOL}


class CompileCounter(contextlib.AbstractContextManager):
    """Counts XLA backend compiles (persistent-cache loads included) and
    their seconds while active, and how many the persistent cache served."""

    _COMPILE = "/jax/core/compile/backend_compile_duration"
    _CACHE_HIT = "/jax/compilation_cache/cache_hits"

    def __init__(self):
        self.compiles, self.seconds, self.cache_hits = 0, 0.0, 0

    def _on_duration(self, event, secs, **_):
        if event == self._COMPILE:
            self.compiles += 1
            self.seconds += secs

    def _on_event(self, event, **_):
        if event == self._CACHE_HIT:
            self.cache_hits += 1

    def __enter__(self):
        import jax
        jax.monitoring.register_event_duration_secs_listener(
            self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)
        return self

    def __exit__(self, *exc):
        import jax
        jax.monitoring.unregister_event_duration_listener(self._on_duration)
        jax.monitoring.unregister_event_listener(self._on_event)
        return False


def _rel_err(got, want) -> float:
    """Relative L2 error of ``got`` against ``want``."""
    import numpy as np
    a, b = np.asarray(got, np.float32), np.asarray(want, np.float32)
    return float(np.linalg.norm(a - b) / np.linalg.norm(b))


class CheckedOps:
    """The paged-attention ops module as the engine calls it, with every
    launch also run through the jnp reference on the same inputs; the
    worst relative error so far is ``error``."""

    def __init__(self):
        self.error = 0.0

    def __getattr__(self, name):
        from repro.kernels.paged_attention import ops as paged_ops
        op = getattr(paged_ops, name)

        def launch(*args, impl):
            out = op(*args, impl=impl)
            ref = op(*args, impl="reference")
            self.error = max(self.error, _rel_err(out, ref))
            return out
        return launch


class ReferenceProbe:
    """Checks two served decoder calls of the engine against the jnp
    reference attention: the first decode batch of ``batch`` rows and the
    first prefill batch with a chunk that starts past position 0 (it reads
    K/V an earlier chunk wrote). Each of their attention launches is rerun
    on the same inputs; the decode batch is also rerun whole through the
    reference path from the pool state it started from, after which the
    served path's pool goes back, so serving goes on unchanged."""

    def __init__(self, eng, batch: int):
        self.dec = eng.decoder
        self.ref = copy.copy(self.dec)
        self.ref.impl = "reference"
        self.batch = batch
        self.errors: dict[str, float] = {}
        # shadow the decoder's methods on the instance
        self.dec.decode_step = self._decode_step
        self.dec.forward_chunks = self._forward_chunks

    def remove(self):
        del self.dec.decode_step, self.dec.forward_chunks

    def _checked(self, key, method, *args, **kwargs):
        """The served call with each attention launch checked."""
        from repro.serve import engine
        ops, engine.paged_ops = engine.paged_ops, CheckedOps()
        try:
            out = getattr(type(self.dec), method)(self.dec, *args, **kwargs)
            self.errors[key] = engine.paged_ops.error
        finally:
            engine.paged_ops = ops
        return out

    def _decode_step(self, tokens, tables, lens, positions):
        args = (tokens, tables, lens, positions)
        if "decode_logits" in self.errors or tokens.shape[0] != self.batch:
            return type(self.dec).decode_step(self.dec, *args)
        view = self.dec.view
        before = view.k_pool, view.v_pool
        served = self._checked("decode_attention", "decode_step", *args)
        after = view.k_pool, view.v_pool
        view.k_pool, view.v_pool = before
        ref = self.ref.decode_step(*args)
        view.k_pool, view.v_pool = after
        self.errors["decode_logits"] = _rel_err(served, ref)
        return served

    def _forward_chunks(self, chunks, **kwargs):
        if "prefill_attention" in self.errors or \
                all(start == 0 for _, _, start in chunks):
            return type(self.dec).forward_chunks(self.dec, chunks, **kwargs)
        return self._checked("prefill_attention", "forward_chunks", chunks,
                             **kwargs)


def kernels_in_hlo(eng) -> dict[str, bool]:
    """Whether the compiled decode and prefill attention ops, as the engine
    calls them (its ``impl``, its pool), hold the Pallas kernel."""
    import jax
    import jax.numpy as jnp
    from repro.kernels.paged_attention import ops as paged_ops
    cfg, view = eng.cfg, eng.view
    cdt = jnp.dtype(cfg.compute_dtype)
    nq, hd, b = cfg.num_heads, cfg.head_dim_, REQUESTS
    pool = jax.ShapeDtypeStruct(view.k_pool.shape[1:], view.k_pool.dtype)
    table = jax.ShapeDtypeStruct((b, 8), jnp.int32)
    vec = jax.ShapeDtypeStruct((b,), jnp.int32)
    decode = paged_ops.paged_attention.lower(
        jax.ShapeDtypeStruct((b, nq, hd), cdt), pool, pool, table, vec,
        impl=eng.decoder.impl)
    prefill = paged_ops.paged_prefill_attention_batch.lower(
        jax.ShapeDtypeStruct((b, 64, nq, hd), cdt), pool, pool, table, vec,
        impl=eng.decoder.impl)
    return {name: "tpu_custom_call" in low.compile().as_text()
            for name, low in (("decode", decode), ("prefill", prefill))}


def run(cfg, lengths, new: int, *, page_size: int = PAGE_SIZE):
    """Build the engine, serve the mix twice and probe a decode and a
    prefill call of the first pass. Returns the engine and a report of what
    to print and check."""
    import jax
    from repro.launch import serve as launch

    t0 = time.perf_counter()
    eng = launch.build_engine(cfg, lengths, new, page_size=page_size)
    jax.block_until_ready(eng.params)
    setup_s = time.perf_counter() - t0
    probe = ReferenceProbe(eng, batch=len(lengths))
    with CompileCounter() as warm_c:
        warm = launch.serve(eng, launch.make_prompts(cfg, lengths,
                                                     launch.SEED + 1))
    probe.remove()
    with CompileCounter() as steady_c:
        steady = launch.serve(eng, launch.make_prompts(cfg, lengths,
                                                       launch.SEED + 2))
    return eng, {
        "setup_s": setup_s, "warm": warm, "steady": steady,
        "warm_compiles": warm_c.compiles,
        "warm_compile_s": warm_c.seconds,
        "warm_cache_hits": warm_c.cache_hits,
        "steady_compiles": steady_c.compiles,
        "errors": probe.errors,
    }


def failures(report, requests: int, new: int) -> list[str]:
    out = []
    for name in ("warm", "steady"):
        p = report[name]
        if p["finished"] != requests or p["tokens"] != requests * new:
            out.append(f"{name} pass served {p['finished']}/{requests} "
                       f"requests, {p['tokens']}/{requests * new} tokens")
    errors = report["errors"]
    if "decode_logits" not in errors:
        out.append(f"no decode batch of {requests} rows was probed")
    if "prefill_attention" not in errors:
        out.append("no prefill chunk past position 0 was probed")
    out += [f"{key} error {err} against the reference > {TOLERANCES[key]}"
            for key, err in errors.items() if not err <= TOLERANCES[key]]
    return out


def main() -> int:
    sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent / "src"))
    import jax
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {dev.platform}",
              file=sys.stderr)
        return 1
    from repro.launch import serve as launch
    cache = launch.enable_compile_cache()
    cfg = launch.load_config(ARCH, smoke=False)
    lengths = launch.prompt_lengths(REQUESTS)

    eng, rep = run(cfg, lengths, NEW_TOKENS)
    hlo = kernels_in_hlo(eng)
    steady = rep["steady"]
    stats = dev.memory_stats() or {}
    print(f"device_kind: {dev.device_kind}")
    print(f"model: {cfg.name} {cfg.num_layers} layers, d {cfg.d_model}, "
          f"heads {cfg.num_heads}/{cfg.num_kv_heads}, ff {cfg.d_ff}, "
          f"vocab {cfg.vocab_size}, {cfg.compute_dtype}; attention "
          f"{eng.decoder.impl}; pool {eng.view.pool.total_pages} pages of "
          f"{PAGE_SIZE}; prompts {lengths} + {NEW_TOKENS} new")
    print(f"compile cache: {cache}")
    print(f"setup_s (weights init, incl. its compile): {rep['setup_s']}")
    print(f"warm-up: {rep['warm_compiles']} compiles, "
          f"{rep['warm_compile_s']} s compiling "
          f"({rep['warm_cache_hits']} loaded from the cache), "
          f"{rep['warm']['steps']} steps")
    print(f"compiles after warm-up: {rep['steady_compiles']}")
    print(f"served after warm-up: {steady['finished']} requests, "
          f"{steady['tokens']} tokens, {steady['steps']} steps")
    print(f"smoke timing, not a benchmark number: median step wall "
          f"{statistics.median(steady['step_s'])} s over "
          f"{steady['steps']} steps")
    print(f"rel L2 err against the reference path: {rep['errors']} "
          f"(tolerances {TOLERANCES})")
    print(f"tpu_custom_call in compiled attention ops: {hlo}")
    print(f"peak_bytes_in_use: {stats.get('peak_bytes_in_use')}")

    bad = failures(rep, REQUESTS, NEW_TOKENS)
    bad += [f"no tpu_custom_call in the compiled {k} attention op"
            for k, ok in hlo.items() if not ok]
    if bad:
        for msg in bad:
            print(f"chip_smoke FAILED: {msg}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
