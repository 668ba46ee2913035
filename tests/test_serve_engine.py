"""BWAP page pool + serving engine integration tests (CPU, small model)."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import registry
from repro.core.dwp import DWPConfig
from repro.serve.engine import ServeEngine
from repro.serve.kvcache import BwapPagePool, MemoryDomain


def _pool(cfg, pages=64, page_size=8):
    domains = [
        MemoryDomain("hbm_local", pages // 2, 819.0, True),
        MemoryDomain("hbm_peer", pages // 4, 50.0, False),
        MemoryDomain("host", pages - pages // 2 - pages // 4, 16.0, False),
    ]
    return BwapPagePool(cfg, domains, page_size=page_size,
                        dwp_config=DWPConfig(n=4, c=1))


@pytest.fixture(scope="module")
def small_lm():
    cfg = registry.get_smoke_config("qwen2-0.5b")
    cfg = dataclasses.replace(cfg, num_layers=2, compute_dtype="float32")
    from repro.models.lm import LM
    model = LM(cfg)
    params = model.init(jax.random.PRNGKey(0))
    return cfg, params


def test_pool_placement_follows_weights(small_lm):
    cfg, _ = small_lm
    pool = _pool(cfg, pages=64)
    ids = [pool.alloc_page() for _ in range(32)]
    domains = np.asarray([pool.domain_of(i) for i in ids])
    frac_local = (domains == 0).mean()
    # canonical weights put most pages on the fast domain
    assert frac_local > 0.7
    # but slower domains are used too (Observation 1)
    assert (domains != 0).any()


def test_pool_alloc_free_roundtrip(small_lm):
    cfg, _ = small_lm
    pool = _pool(cfg, pages=16, page_size=4)
    ids = [pool.alloc_page() for _ in range(16)]
    assert len(set(ids)) == 16
    with pytest.raises(RuntimeError):
        pool.alloc_page()
    pool.free_pages(ids)
    assert sum(len(f) for f in pool.free) == 16


def test_engine_generates_and_respects_pages(small_lm):
    cfg, params = small_lm
    pool = _pool(cfg, pages=128, page_size=4)
    eng = ServeEngine(cfg, params, pool, max_batch=3, max_new=6)
    rng = np.random.default_rng(0)
    sids = [eng.submit(rng.integers(1, cfg.vocab_size, 5).tolist())
            for _ in range(3)]
    for _ in range(30):
        info = eng.step()
        if not eng.active and not eng.waiting:
            break
    assert len(eng.finished) == 3
    for s in eng.finished:
        assert s.produced == 6
        assert all(np.isfinite(t) for t in s.tokens)
    # pool fully reclaimed
    assert sum(len(f) for f in pool.free) == pool.total_pages


def test_engine_decode_matches_dense_decode(small_lm):
    """Paged decode must produce the same logits as the dense cache path."""
    cfg, params = small_lm
    pool = _pool(cfg, pages=64, page_size=4)
    eng = ServeEngine(cfg, params, pool, max_batch=1, max_new=1)
    prompt = [3, 17, 29, 5]
    eng.submit(list(prompt))
    eng.step()  # prefill + 1 decode
    paged_next = eng.finished[0].tokens[len(prompt)] if eng.finished else \
        eng.active[0].tokens[len(prompt)]

    # dense reference: full forward, argmax of last position
    from repro.models.lm import LM
    model = LM(cfg)
    toks = jnp.asarray([prompt], jnp.int32)
    logits = model.prefill(params, {"tokens": toks})
    dense_next = int(jnp.argmax(logits[0, -1]))
    assert paged_next == dense_next


def _lm(num_layers):
    cfg = dataclasses.replace(registry.get_smoke_config("qwen2-0.5b"),
                              num_layers=num_layers, compute_dtype="float32")
    from repro.models.lm import LM
    return cfg, LM(cfg).init(jax.random.PRNGKey(1))


def _full_logits(cfg, params, tokens):
    """Float32 logits [S, V] of every position of ``tokens``, from the
    model's own full forward."""
    from repro.models.lm import LM
    model = LM(cfg)
    x = model.embed(params, {"tokens": jnp.asarray([tokens], jnp.int32)})
    pos = jnp.arange(len(tokens), dtype=jnp.int32)[None]
    h, _, _ = model.hidden(params, x, pos)
    return np.asarray(model.logits(params, h)[0])


def _rel_err(got, want) -> float:
    return float(np.linalg.norm(np.asarray(got) - want)
                 / np.linalg.norm(want))


def _decoder(cfg, params, pages=64):
    from repro.serve.engine import PagedDecoder
    pool = BwapPagePool(cfg, [MemoryDomain("hbm_local", pages, 819.0, True)],
                        page_size=4)
    return PagedDecoder(cfg, params, pool)


def _prefill_and_decode(dec, prompts, nxt):
    """Each prompt prefilled whole in one fused call, then one decode step
    of token ``nxt[i]`` for each; returns the prefill logits [B,T,V] and
    the decode logits [B,V] (both of the padded batch)."""
    ps = dec.view.page_size
    pages = [[dec.view.pool.alloc_page() for _ in range(len(p) // ps + 1)]
             for p in prompts]
    pre = dec.forward_chunks([(p, pg, 0) for p, pg in zip(prompts, pages)],
                             want_logits=True)
    tables = np.zeros((len(prompts), max(map(len, pages))), np.int32)
    for i, pg in enumerate(pages):
        tables[i, :len(pg)] = pg
    lens = np.asarray([len(p) for p in prompts], np.int32)
    step = dec.decode_step(np.asarray(nxt, np.int32)[:, None], tables, lens,
                           lens)
    return np.asarray(pre), np.asarray(step)


@pytest.mark.parametrize("num_layers", [2, 4], ids=["list", "stacked"])
def test_dense_body_matches_the_float32_forward(num_layers):
    """The dense-GQA body's layer programs, over weights held as a list of
    layers (2) or stacked (4): fused prefill and decode logits match the
    model's float32 forward, and the real rows of a batch padded to a
    wider batch run before equal the same rows run unpadded."""
    cfg, params = _lm(num_layers)
    assert isinstance(params["groups"][0], list) == (num_layers < 4)
    prompts = [[3, 17, 29, 5, 7], [9, 1, 4], [11, 2, 8, 6, 30, 12, 4]]
    nxt = [21, 13, 40]
    pre, step = _prefill_and_decode(_decoder(cfg, params), prompts, nxt)
    assert pre.shape[0] == step.shape[0] == 3
    for i, (p, t) in enumerate(zip(prompts, nxt)):
        full = _full_logits(cfg, params, p + [t])
        assert _rel_err(pre[i, :len(p)], full[:len(p)]) < 1e-5
        assert _rel_err(step[i], full[len(p)]) < 1e-5

    wide = _decoder(cfg, params)
    _prefill_and_decode(wide, prompts + [[5] * 7, [1, 2]], nxt + [3, 4])
    pre_pad, step_pad = _prefill_and_decode(wide, prompts, nxt)
    assert pre_pad.shape[0] == step_pad.shape[0] == 5
    np.testing.assert_array_equal(pre_pad[:3], pre)
    np.testing.assert_array_equal(step_pad[:3], step)


def test_dense_body_compiles_nothing_for_narrower_batches(small_lm):
    """After a decoder has run a batch of 4, prefill batches of 3 and 1
    chunks and decode batches of 3 and 1 compile nothing: each is padded
    to 4 rows, and the padding is counted in
    ``repro_decode_rows_total{row}``."""
    cfg, params = small_lm
    dec = _decoder(cfg, params, pages=128)
    rng = np.random.default_rng(5)

    def serve(n):
        prompts = [rng.integers(1, cfg.vocab_size, 6).tolist()
                   for _ in range(n)]
        _prefill_and_decode(dec, prompts, [1] * n)
    serve(4)
    compiles = []

    def on_duration(event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            compiles.append(secs)
    jax.monitoring.register_event_duration_secs_listener(on_duration)
    try:
        serve(3)
        serve(1)
    finally:
        jax.monitoring.unregister_event_duration_listener(on_duration)
    assert compiles == []
    rows = dec.view.telemetry.metrics.get("repro_decode_rows_total")
    assert (rows.value("real"), rows.value("pad")) == (8, 4)


def _scatter_write(pool, layer, page_ids, slots, k_rows, v_rows):
    """The K/V write as it was before it went in place: one eager scatter
    per pool, each a new pool array."""
    lay = layer if k_rows.ndim == pool.k_pool.ndim - 2 else \
        slice(layer, layer + k_rows.shape[0])
    pool.k_pool = pool.k_pool.at[lay, page_ids, slots].set(k_rows)
    pool.v_pool = pool.v_pool.at[lay, page_ids, slots].set(v_rows)


def test_engine_writes_in_place_with_the_scatter_path_tokens(small_lm,
                                                              monkeypatch):
    """40 greedy steps of a 4-sequence batch serve the same tokens through
    the in-place write as through the eager scatter, and every write of
    the run is counted in place."""
    cfg, params = small_lm
    rng = np.random.default_rng(3)
    prompts = [rng.integers(1, cfg.vocab_size, n).tolist()
               for n in (5, 7, 9, 12)]

    def serve():
        pool = _pool(cfg, pages=128, page_size=4)
        eng = ServeEngine(cfg, params, pool, max_batch=4, max_new=48,
                          wall_clock=False)
        for p in prompts:
            eng.submit(list(p))
        for _ in range(40):
            eng.step()
        return pool, [list(s.tokens) for s in eng.active + eng.finished]

    calls = []
    write = BwapPagePool.write_rows
    with monkeypatch.context() as m:
        def counted(self, *args):
            calls.append(1)
            write(self, *args)
        m.setattr(BwapPagePool, "write_rows", counted)
        pool, served = serve()
    with monkeypatch.context() as m:
        m.setattr(BwapPagePool, "write_rows", _scatter_write)
        _, scattered = serve()
    assert served == scattered
    assert len(calls) >= 40 * cfg.num_layers
    assert pool.telemetry.snapshot()["kv_writes"] == {
        "in_place": len(calls), "copied": 0}


def _three_steps(cfg, params):
    pool = _pool(cfg, pages=64, page_size=4)
    eng = ServeEngine(cfg, params, pool, max_batch=2, max_new=8)
    eng.submit([3, 17, 29, 5, 7])
    eng.submit([9, 1, 4])
    for _ in range(3):
        eng.step()
    return [list(s.tokens) for s in eng.active + eng.finished]


def test_engine_spans_land_in_the_profiler_trace(small_lm, tmp_path):
    """Each engine step writes its ``serve.*`` host spans into the JAX
    profiler's trace, nested as the code nests, and tracing changes no
    token."""
    cfg, params = small_lm
    untraced = _three_steps(cfg, params)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(tmp_path, profiler_options=opts)
    try:
        traced = _three_steps(cfg, params)
    finally:
        jax.profiler.stop_trace()
    assert traced == untraced

    (xp,) = tmp_path.rglob("*.xplane.pb")
    planes = jax.profiler.ProfileData.from_file(str(xp)).planes
    spans = [(e.start_ns, e.start_ns + e.duration_ns, e.name)
             for p in planes if p.name.startswith("/host:")
             for line in p.lines for e in line.events
             if e.name.startswith("serve.")]

    def inside(outer, name):
        return [x for x in spans if x[2] == name
                and outer[0] <= x[0] and x[1] <= outer[1]]

    steps = [x for x in spans if x[2] == "serve.step"]
    assert len(steps) == 3
    for i, step in enumerate(steps):
        for name in ("schedule", "pages", "decode", "decode.dispatch",
                     "decode.sync", "account"):
            assert len(inside(step, "serve." + name)) == 1, (i, name)
        # both prompts are prefilled in the first step, in one launch
        assert len(inside(step, "serve.prefill")) == (1 if i == 0 else 0)
        (decode,) = inside(step, "serve.decode")
        assert inside(decode, "serve.decode.dispatch")
    for dispatch in (x for x in spans if x[2] == "serve.decode.dispatch"):
        for name in ("kv_write", "attention"):
            assert len(inside(dispatch, "serve.decode." + name)) == \
                cfg.num_layers


def test_dwp_migration_changes_allocation(small_lm):
    cfg, _ = small_lm
    pool = _pool(cfg, pages=64, page_size=4)
    w0 = pool.weights.copy()
    # feed decreasing latencies -> tuner raises DWP -> more worker-local mass
    lat = 1.0
    while not pool.tuner.done and lat > 0.2:
        pool.record_latency(lat)
        lat -= 0.02
    assert pool.tuner.dwp > 0
    assert pool.weights[0] > w0[0]


def test_attention_impl_follows_platform(small_lm):
    """CPU pools run the jnp reference (the engine's CPU tests are
    bit-exact against it), TPU pools the Pallas kernels; any other platform
    is refused rather than served through a fallback."""
    from repro.serve.engine import PagedDecoder, attention_impl
    cfg, params = small_lm
    pool = BwapPagePool(cfg, [MemoryDomain("hbm_local", 8, 819.0, True)],
                        page_size=4)
    assert PagedDecoder(cfg, params, pool).impl == "reference"
    assert attention_impl("cpu") == "reference"
    assert attention_impl("tpu") == "pallas"
    with pytest.raises(ValueError, match="gpu"):
        attention_impl("gpu")


def _chip_smoke():
    """chip_smoke.py, which lives at the repo root, as a module."""
    import importlib.util
    import pathlib
    path = pathlib.Path(__file__).resolve().parents[1] / "chip_smoke.py"
    spec = importlib.util.spec_from_file_location("chip_smoke", path)
    smoke = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(smoke)
    return smoke


def test_chip_smoke_run_on_interpreted_kernels(monkeypatch):
    """chip_smoke.py's serving path at a tiny size, with the engine steered
    onto the Pallas kernels in interpret mode as a TPU pool would put it:
    both passes serve every token, each attention launch of the probed
    decode and prefill calls agrees with the reference on the same inputs,
    and so do the decode logits from the same pool state. The longer
    prompt outruns the 256-token prefill budget, so its second chunk reads
    pages its first chunk wrote."""
    import functools

    from repro.kernels.paged_attention import ops as paged_ops
    from repro.launch import serve as launch
    from repro.serve import engine as engine_mod

    smoke = _chip_smoke()
    monkeypatch.setattr(engine_mod, "attention_impl", lambda _: "pallas")
    for name in ("paged_attention", "paged_prefill_attention_batch"):
        monkeypatch.setattr(paged_ops, name, functools.partial(
            getattr(paged_ops, name), interpret=True))

    cfg = launch.load_config("qwen2-0.5b", smoke=True)
    lengths, new = [5, 270], 3
    eng, rep = smoke.run(cfg, lengths, new, page_size=16)
    assert eng.decoder.impl == "pallas"
    assert smoke.failures(rep, len(lengths), new) == []
    # float32 smoke config: kernel and reference differ only in f32
    # summation order
    assert set(rep["errors"]) == {"decode_attention", "decode_logits",
                                  "prefill_attention"}
    assert max(rep["errors"].values()) < 1e-5
    assert rep["steady"]["steps"] == rep["warm"]["steps"]
    # the probe leaves the decoder's own methods and ops in place
    assert not {"decode_step", "forward_chunks"} & vars(eng.decoder).keys()
    assert engine_mod.paged_ops is paged_ops


@pytest.mark.parametrize("key, err, tokens", [
    ("decode_attention", 2e-3, 8),     # past ATTENTION_RTOL
    ("prefill_attention", 2e-3, 8),
    ("decode_logits", 6e-2, 8),        # past LOGITS_RTOL
    ("decode_logits", 1e-4, 7),        # one token short
])
def test_chip_smoke_failures_name_each_broken_check(key, err, tokens):
    """An error against the reference past its tolerance, a request served
    short, or a probe that never ran fails the smoke run by name."""
    smoke = _chip_smoke()
    passed = {"finished": 2, "tokens": 8}
    errors = {"decode_attention": 0.0, "decode_logits": 1e-2,
              "prefill_attention": 1e-4}
    rep = {"warm": passed, "steady": dict(passed, tokens=tokens),
           "errors": dict(errors, **{key: err})}
    (msg,) = smoke.failures(rep, requests=2, new=4)
    assert ("steady pass" if tokens < 8 else key) in msg
    assert smoke.failures(dict(rep, steady=passed, errors={}), 2, 4) == [
        "no decode batch of 2 rows was probed",
        "no prefill chunk past position 0 was probed"]
