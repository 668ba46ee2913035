"""DeepSeek-V3 served through ``ServeEngine`` at the smoke widths
(``deepseek_v3_671b.smoke_config``: MLA with YaRN, sigmoid group-limited
routing, one leading dense layer and four MoE layers), on seeded random
weights: the latent pages, the latent decode, the expert layer that holds
a share of the experts, its routing, YaRN, and the new span and counter;
and the benchmark's ``mla_moe`` files rehearsed on the CPU.

Tolerances: the served path runs in float32 here, so it differs from a
float32 full forward only in the order of its sums (absorbed against
expanded attention, a page walk against one softmax, a held share of the
experts against all of them): 1e-4 on logits of unit scale is some thirty
times that rounding and far under any real fault, which moves logits by a
tenth or more. The routing check is exact: the same choices, and weights
to float32 rounding.
"""

import dataclasses
import json
import math
import pathlib
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import registry
from repro.kernels.paged_attention import ops as paged_ops
from repro.models import attention, layers, moe
from repro.models.lm import LM
from repro.placement.pool import BwapPagePool, MemoryDomain
from repro.serve.engine import ServeEngine, latent_table_width

BENCH = pathlib.Path(__file__).resolve().parents[1] / "benchmarks" / "chip"
TOL = 1e-4


def _cfg(held=(0, 0), **kw):
    cfg = registry.get_smoke_config("deepseek-v3-671b")
    return dataclasses.replace(
        cfg, compute_dtype="float32",
        moe=dataclasses.replace(cfg.moe, held_first=held[0],
                                held_count=held[1]), **kw)


@pytest.fixture(scope="module")
def served():
    """A held share (experts 2-5 of 8), its params, and a biased router so
    that choosing differs from weighting."""
    cfg = _cfg(held=(2, 4))
    params = LM(cfg).init(jax.random.PRNGKey(0))
    bias = jax.random.normal(jax.random.PRNGKey(1), (4, 8)) * 0.1
    params["groups"][1]["moe"]["router_bias"] = bias
    return cfg, params


def _engine(cfg, params, pages=64, page_size=4, **kw):
    pool = BwapPagePool(cfg, [MemoryDomain("hbm_local", pages, 819.0, True)],
                        page_size=page_size)
    return ServeEngine(cfg, params, pool, **kw)


def _full_forward(cfg, params, tokens):
    """Float32 logits [S, V] of ``tokens`` [S]: expanded (un-absorbed) MLA
    over the whole sequence, and each layer's FFN."""
    x = params["embed"][jnp.asarray(tokens)][None].astype(jnp.float32)
    pos = jnp.arange(len(tokens), dtype=jnp.int32)[None]
    for group, gp in zip(LM(cfg).groups, params["groups"]):
        for i in range(group.size):
            p = gp[i] if isinstance(gp, list) else \
                jax.tree.map(lambda a: a[i], gp)
            h = layers.apply_norm(cfg, p["norm1"], x)
            x = x + attention.mla_attention(cfg, p["attn"], h, pos, 0)[0]
            h = layers.apply_norm(cfg, p["norm2"], x)
            if group.kind == "moe":
                out, _ = moe.held_moe_apply(cfg, p["moe"], h[0])
                x = x + out[None]
            else:
                x = x + layers.mlp_apply(cfg, p["mlp"], h)
    x = layers.apply_norm(cfg, params["final_norm"], x)
    return np.asarray(x[0] @ params["head"])


def test_engine_matches_float32_full_forward(served):
    """Chunked prefill (prompts of 11 in chunks of at most 5 tokens a
    step), then decode through the engine and the latent pages: every
    decode step's logits match a float32 full forward of the sequence."""
    cfg, params = served
    eng = _engine(cfg, params, max_batch=3, max_new=6)
    eng.scheduler.prefill_token_budget = 5
    rng = np.random.default_rng(0)
    for _ in range(3):
        eng.submit(rng.integers(1, cfg.vocab_size, 11).tolist())
    served_logits, out = [], []
    decode, launch = eng.decoder.decode_step, eng._decode_launch

    def spy_decode(*args):
        out.append(decode(*args))
        return out[-1]

    def spy_launch(seqs):
        at = [len(s.tokens) - 1 for s in seqs]
        launch(seqs)
        served_logits.extend(zip(seqs, at, np.asarray(out[-1])))
    eng.decoder.decode_step = spy_decode
    eng._decode_launch = spy_launch
    while eng.active or eng.waiting:
        eng.step()
    assert len(eng.finished) == 3
    assert len(served_logits) == 3 * 6
    full = {id(s): _full_forward(cfg, params, s.tokens[:-1])
            for s in eng.finished}
    for s, p, logits in served_logits:
        np.testing.assert_allclose(logits, full[id(s)][p], atol=TOL)
        assert s.tokens[p + 1] == int(np.argmax(logits))


def test_latent_decode_equals_unabsorbed_mla():
    """The absorbed decode over latent pages (rows written into the folded
    pools through ``write_rows``, the jnp page walk, ``W_UV`` and ``wo``)
    equals expanded MLA at the same positions, and the Pallas kernel in
    interpret mode equals the jnp walk, over the table as given and as the
    decoder widens it."""
    cfg = _cfg()
    p = LM(cfg).init(jax.random.PRNGKey(2))["groups"][0][0]["attn"]
    s, ps = 13, 4
    x = jax.random.normal(jax.random.PRNGKey(3), (1, s, cfg.d_model))
    pos = jnp.arange(s, dtype=jnp.int32)[None]
    full, _ = attention.mla_attention(cfg, p, x, pos, 0)

    pool = BwapPagePool(cfg, [MemoryDomain("hbm_local", 16, 819.0, True)],
                        page_size=ps)
    pages = [7, 2, 11, 5]
    q_lat, q_pe, ckv, k_pe = attention.mla_latent_query(cfg, p, x, pos)
    layer = 3
    pool.write_rows(layer, np.asarray([pages[i // ps] for i in range(s)]),
                    np.arange(s) % ps, k_pe[0], ckv[0])
    table = jnp.asarray([pages])
    # the decoder widens tables with page 0 (latent_table_width): masked
    wide = jnp.pad(table, ((0, 0), (0, latent_table_width(4) - 4)))
    scale = attention.mla_softmax_scale(cfg)
    for t in (0, 5, s - 1):
        args = (q_lat[:, t], q_pe[:, t], pool.k_pool, pool.v_pool)
        rest = (jnp.asarray([t + 1]), layer)
        lat = paged_ops.paged_latent_attention(*args, table, *rest,
                                               scale=scale, impl="reference")
        out = attention.mla_latent_out(cfg, p, lat[:, None])
        np.testing.assert_allclose(out[0, 0], full[0, t], atol=TOL)
        for tbl in (table, wide):
            kern = paged_ops.paged_latent_attention(
                *args, tbl, *rest, scale=scale, impl="pallas",
                interpret=True)
            np.testing.assert_allclose(kern, lat, atol=TOL)


def _route_numpy(logits, bias, n_group, topk_group, k, scale):
    """DeepSeek-V3's noaux_tc routing, stated directly."""
    scores = 1.0 / (1.0 + np.exp(-logits))
    out_e, out_w = [], []
    for sc, choice in zip(scores, scores + bias):
        groups = choice.reshape(n_group, -1)
        gscore = np.sort(groups, axis=1)[:, -2:].sum(1)
        kept = np.argsort(-gscore)[:topk_group]
        allowed = np.concatenate([np.arange(g * groups.shape[1],
                                            (g + 1) * groups.shape[1])
                                  for g in kept])
        chosen = allowed[np.argsort(-choice[allowed])[:k]]
        w = sc[chosen] / sc[chosen].sum() * scale
        out_e.append(chosen)
        out_w.append(w)
    return np.asarray(out_e), np.asarray(out_w)


def test_group_limited_sigmoid_routing_matches_numpy():
    cfg = registry.get_config("deepseek-v3-671b")
    rng = np.random.default_rng(4)
    logits = rng.normal(size=(64, 256)).astype(np.float32)
    bias = (rng.normal(size=256) * 0.1).astype(np.float32)
    experts, w = moe.route_grouped(cfg, jnp.asarray(logits),
                                   jnp.asarray(bias))
    ne, nw = _route_numpy(logits, bias, 8, 4, 8, 2.5)
    order = np.argsort(np.asarray(experts), axis=1)
    np.testing.assert_array_equal(
        np.take_along_axis(np.asarray(experts), order, 1), np.sort(ne, 1))
    np.testing.assert_allclose(
        np.take_along_axis(np.asarray(w), order, 1),
        np.take_along_axis(nw, np.argsort(ne, 1), 1), rtol=1e-6)
    # the bias chooses but does not weight: some choice differs from the
    # unbiased one, and every weight is an unbiased score's share
    plain, _ = moe.route_grouped(cfg, jnp.asarray(logits), jnp.zeros(256))
    assert (np.sort(np.asarray(plain), 1) != np.sort(ne, 1)).any()
    np.testing.assert_allclose(np.asarray(w).sum(1), 2.5, rtol=1e-6)


def test_expert_shares_sum_to_the_uncut_layer():
    """Four chips' shares of two experts each: their experts' terms, plus
    the shared expert once, equal the layer that holds all eight."""
    cfg = _cfg()
    p = LM(cfg).init(jax.random.PRNGKey(5))["groups"][1]
    p = jax.tree.map(lambda a: a[0], p)["moe"]
    p["router_bias"] = jax.random.normal(jax.random.PRNGKey(6), (8,)) * 0.1
    x = jax.random.normal(jax.random.PRNGKey(7), (16, cfg.d_model))
    whole, held = moe.held_moe_apply(cfg, p, x)
    np.testing.assert_array_equal(held, cfg.moe.top_k)
    shared = layers.mlp_apply(cfg, p["shared"], x)
    total = shared
    counts = 0
    for first in range(0, 8, 2):
        share = dataclasses.replace(cfg, moe=dataclasses.replace(
            cfg.moe, held_first=first, held_count=2))
        sp = {**p, **{k: p[k][first:first + 2]
                      for k in ("w_gate", "w_up", "w_down")}}
        out, n = moe.held_moe_apply(share, sp, x)
        total = total + (out - shared)
        counts = counts + n
    np.testing.assert_allclose(total, whole, atol=TOL)
    np.testing.assert_array_equal(counts, cfg.moe.top_k)


def test_yarn_matches_the_published_formula():
    """DeepSeek-V3's YaRN (modeling_deepseek.py): inverse frequencies from
    the correction range of beta_fast 32 and beta_slow 1 over 4096
    positions, mscale 0.1 ln(40) + 1 = 1.3689, and the MLA softmax scale
    192^-0.5 mscale^2; the rotation at chosen positions."""
    cfg = registry.get_config("deepseek-v3-671b")
    ys, dim, base = cfg.rope_scaling, 64, 10000.0

    def corr(rot):
        return dim * math.log(4096 / (rot * 2 * math.pi)) / (2 * math.log(base))
    low, high = max(math.floor(corr(32)), 0), min(math.ceil(corr(1)), dim - 1)
    extra = 1.0 / base ** (np.arange(0, dim, 2, dtype=np.float32) / dim)
    mask = 1 - np.clip((np.arange(dim // 2) - low) / (high - low), 0, 1)
    inv = extra / 40 * (1 - mask) + extra * mask
    np.testing.assert_allclose(layers.yarn_freqs(dim, base, ys), inv,
                               rtol=1e-6)
    assert (low, high) == (10, 23)
    assert layers.yarn_mscale(40.0, 1.0) == pytest.approx(1.3689, abs=1e-4)
    assert attention.mla_softmax_scale(cfg) == pytest.approx(
        192 ** -0.5 * (0.1 * math.log(40) + 1) ** 2)
    positions = np.asarray([0, 1, 100, 4095, 4096, 100000])
    x = np.zeros((len(positions), 1, dim), np.float32)
    x[..., :dim // 2] = 1.0                    # rotates to (cos, sin)
    out = np.asarray(layers.apply_rope(jnp.asarray(x),
                                       jnp.asarray(positions), base, ys))
    # the angle as float32 forms it (position times frequency); float32
    # cos and sin of angles up to 1e5 radians lose some 2e-4 in range
    # reduction, and a wrong frequency moves them by order 1
    ang = (positions[:, None].astype(np.float32)
           * inv[None, :].astype(np.float32)).astype(np.float64)
    np.testing.assert_allclose(out[:, 0, :dim // 2], np.cos(ang), atol=1e-3)
    np.testing.assert_allclose(out[:, 0, dim // 2:], np.sin(ang), atol=1e-3)


def test_expert_span_and_routed_counter_are_written(served, tmp_path):
    """Each decode step writes one ``serve.decode.experts`` span per MoE
    layer into the profiler's trace, and ``repro_moe_routed_total`` folds
    in, when read, every real token's choices: held plus absent is tokens
    times top-k times MoE layers."""
    cfg, params = served
    eng = _engine(cfg, params, max_batch=2, max_new=3)
    for prompt in ([3, 17, 29, 5, 8], [9, 4, 1]):
        eng.submit(prompt)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(tmp_path, profiler_options=opts)
    try:
        while eng.active or eng.waiting:
            eng.step()
    finally:
        jax.profiler.stop_trace()
    (xp,) = tmp_path.rglob("*.xplane.pb")
    names = [e.name for p in jax.profiler.ProfileData.from_file(
        str(xp)).planes if p.name.startswith("/host:")
        for line in p.lines for e in line.events]
    assert names.count("serve.decode.experts") == eng.decode_steps * 4
    fam = eng.view.telemetry.metrics.get("repro_moe_routed_total")
    held, absent = fam.value("held"), fam.value("absent")
    tokens = (4 + 2) + 2 * 3                   # prefill, then decode
    assert held + absent == tokens * cfg.moe.top_k * 4
    assert held > 0 and absent > 0
    assert "repro_moe_routed_total" in \
        eng.view.telemetry.metrics.prometheus_text()


def test_mla_engine_refuses_what_it_does_not_serve(served):
    cfg, params = served
    with pytest.raises(ValueError, match="greedy decode"):
        _engine(cfg, params, incremental_prefill=False)


def _rehearsal_cfg():
    cfg = json.loads((BENCH / "configs" / "deepseek-v3.json").read_text())
    # the smoke widths in the configuration file's keys; float32, since in
    # bfloat16 a routing flip onto one of four held experts of eight moves
    # logits by up to 2 at these widths, past any limit a smoke cell holds
    return {**cfg, "name": "deepseek-smoke", "hidden_size": 64,
            "intermediate_size": 48, "moe_intermediate_size": 32,
            "num_attention_heads": 4, "num_key_value_heads": 4,
            "q_lora_rank": 32, "kv_lora_rank": 16, "qk_nope_head_dim": 16,
            "qk_rope_head_dim": 8, "v_head_dim": 16, "n_routed_experts": 4,
            "published_n_routed_experts": 8, "held_experts_from": 2,
            "num_experts_per_tok": 2, "n_group": 4, "topk_group": 2,
            "vocab_size": 256, "torch_dtype": "float32", "max_batch": 8,
            "prefill_token_budget": 64, "kv_pool_pages": 256}


@pytest.fixture(scope="module")
def mla_bench(tmp_path_factory):
    """The benchmark's test kit and harness, and a bench directory holding
    the smoke-width ``mla_moe`` cell ``deepseek-smoke.closed``."""
    sys.path.insert(0, str(BENCH))
    try:
        import chipbench_testkit as kit
        from chipbench import harness
    finally:
        sys.path.remove(str(BENCH))
    root = kit.bench_dir(tmp_path_factory.mktemp("mla"))
    kit.write(root, "configs", "deepseek-smoke", _rehearsal_cfg())
    kit.write(root, "limits", "deepseek-smoke.closed",
              {"max_logit_gap": {"limit": kit.SMOKE_LIMIT}})
    b = kit.bench([("deepseek-smoke.closed", "deepseek-smoke", "closed")])
    return kit, harness, root, b


def test_benchmark_rehearses_the_mla_moe_files(mla_bench, capfd):
    """The benchmark's new-architecture path on ``layouts/mla_moe.py`` and
    ``references/mla_moe.py`` at the smoke widths: the cell runs, is
    correct, counts the work of its decode launches, and the reference
    prints its routing flips."""
    kit, harness, root, b = mla_bench
    recs = []

    class Rec(harness.Record):
        def __init__(self, *args, **kw):
            super().__init__(*args, **kw)
            recs.append(self)

    record = harness.Record
    harness.Record = Rec
    try:
        out = kit.run(b, "deepseek-smoke.closed", root)
    finally:
        harness.Record = record
    assert out["correct"] is True
    assert out["checks"]["served_tokens_checked"]["value"] >= 1
    (rec,) = recs
    assert rec.decode_launches and all(
        w.flops > 0 and w.bytes > 0 for w in rec.decode_launches)
    assert "routing flips:" in capfd.readouterr().err


def test_mla_moe_float8_control_fails_where_the_program_passes(mla_bench):
    """The reference's float8 control (weights and activations rounded to
    float8 at every weight matrix's product) in the program's place is not
    correct at the smoke cell's limit, on the sample where the program is."""
    kit, _, root, b = mla_bench
    out = kit.run(b, "deepseek-smoke.closed", root, control=True)
    assert out["correct"] is False
    assert out["checks"]["max_logit_gap"]["value"] > kit.SMOKE_LIMIT
    assert out["program_gap"] <= kit.SMOKE_LIMIT


def test_warmed_latent_widths_compile_nothing_in_decode(served, mla_bench):
    """The layout's warm-up call of the latent decode kernel meets the
    decoder's own calls: once every table width a window reaches is
    warmed, decode steps whose tables widen compile no program."""
    kit, harness, root, _ = mla_bench
    layout = harness.load_module(root, "layouts", "mla_moe")
    cfg, params = served
    eng = _engine(cfg, params, max_batch=2, max_new=60)
    for n in (3, 5):
        eng.submit(list(range(1, n + 1)))
    eng.step()
    eng.step()
    running = eng.scheduler.running
    lo = max(len(s.pages) for s in running)
    for mp in range(lo, lo + 16):
        layout.warm_decode_call(eng, _rehearsal_cfg(), len(running), mp)
    counter = harness.CompileCounter()
    try:
        for _ in range(40):              # tables widen past 8 pages of 4
            eng.step()
    finally:
        counter.close()
    assert max(len(s.pages) for s in running) > 8
    assert counter.compiles == 0


def test_expert_layer_work_by_hand():
    """One MoE layer's call at the configuration's widths: the router
    (7168 x 256), the shared expert and the 8 held experts (3 x 7168 x 2048
    each) read once, 796,393,472 B in bfloat16; a token passes through the
    router, the shared expert and 8 x 8 / 256 = 0.25 of a held expert."""
    sys.path.insert(0, str(BENCH))
    try:
        from chipbench import harness
    finally:
        sys.path.remove(str(BENCH))
    layout = harness.load_module(BENCH, "layouts", "mla_moe")
    cfg = json.loads((BENCH / "configs" / "deepseek-v3.json").read_text())
    expert = 3 * 7168 * 2048
    fixed = 7168 * 256 + expert
    assert layout.expert_params(cfg) == (fixed + 8 * expert,
                                         fixed + expert // 4)
    w = layout.expert_layer_work(cfg, 128)
    assert w.bytes == 796_393_472
    assert w.flops == 2 * 128 * (fixed + expert // 4)
    assert layout.decode_attention(cfg, 16, [17, 40]).tokens == 2
    assert layout.prefill_attention(cfg, 16, [(0, 32), (32, 32)]).tokens == 64


class _Trace:
    def __init__(self, module_s, window_s=2.0):
        self.module_s, self.window_s = module_s, window_s


@pytest.mark.parametrize("name", ["mla_decode_attn_roofline",
                                  "expert_layer_roofline",
                                  "mla_moe_step_mfu"])
def test_mla_moe_metric_readers(mla_bench, name):
    """Each reader of the cell's three per-layer metrics: its quotient over
    a traced record, worked out here from the layout's counts, and no
    value (the metric left out of the line) without a trace or without its
    program in the trace."""
    kit, harness, _, _ = mla_bench
    layout = harness.load_module(kit.BENCH_DIR, "layouts", "mla_moe")
    reader = harness.load_module(kit.BENCH_DIR, "metrics", name)
    cfg = json.loads((BENCH / "configs" / "deepseek-v3.json").read_text())
    peaks = harness.roofline.peaks("TPU v5 lite")
    rec = harness.Record(cfg, peaks=peaks)
    rec.decode_launches = [layout.decode_attention(cfg, 16, [40] * 128)]
    rec.prefill_launches = [layout.prefill_attention(cfg, 16, [(0, 32)])]
    rec.model_flops = 128 * layout.token_flops(cfg, 40)
    assert reader.read(rec) is None
    modules = {"jit_paged_latent_attention": 0.25, "jit_expert_layer": 0.5}
    rec.trace = _Trace(modules)
    if name == "mla_decode_attn_roofline":
        want = 5 * rec.decode_launches[0].seconds(peaks) / 0.25
    elif name == "expert_layer_roofline":
        want = 4 * (layout.expert_layer_work(cfg, 128).seconds(peaks)
                    + layout.expert_layer_work(cfg, 32).seconds(peaks)) / 0.5
    else:
        want = rec.model_flops / (2.0 * peaks.flops)
    assert reader.read(rec) == pytest.approx(100.0 * want, rel=1e-12)
    if name != "mla_moe_step_mfu":
        rec.trace = _Trace({})
        assert reader.read(rec) is None
