"""Compute-follows-data (DESIGN.md §11): per-domain micro-batch decode
partitioning, heat-driven re-homing of hot shared pages, per-launch drift
billing, and bytes-weighted heat — micro-batched execution must be
token-identical and leak-free vs the global-batch oracle."""

import dataclasses
import types

import numpy as np
import pytest

from hypothesis import given, settings
from hypothesis import strategies as st

from repro.configs import registry
from repro.core import bwmodel
from repro.core.dwp import DWPConfig
from repro.obs.drift import DriftLedger
from repro.obs.heat import PageHeat
from repro.obs.observatory import Observatory
from repro.placement.fabric import as_view
from repro.scheduler import RequestScheduler, WorkloadSpec, generate
from repro.serve.engine import ServeEngine
from repro.serve.kvcache import BwapPagePool, MemoryDomain


@pytest.fixture(scope="module")
def tiny_lm():
    cfg = dataclasses.replace(registry.get_smoke_config("qwen2-0.5b"),
                              num_layers=1, compute_dtype="float32")
    from repro.models.lm import LM
    model = LM(cfg)
    params = model.init(__import__("jax").random.PRNGKey(0))
    return cfg, params


def _pool(cfg, fast=8, peer=24, host=40, page_size=4, policy="bwap_dwp"):
    """Small fast domain; slow bandwidths in the engine-latency range so
    Eq.-1 terms (and re-homing savings) are visible; tuner frozen."""
    return BwapPagePool(cfg, [
        MemoryDomain("hbm_local", fast, 819.0, True),
        MemoryDomain("hbm_peer", peer, 0.00125, False),
        MemoryDomain("host", host, 0.0004, False),
    ], page_size=page_size, policy=policy,
        dwp_config=DWPConfig(n=10 ** 6, c=1))


# ---------------------------------------------------------------------------
# bwmodel.move_cost
# ---------------------------------------------------------------------------

def test_move_cost_read_and_write_bottlenecks():
    bw = np.array([2.0, 1.0])
    # 2 GB from domain 0: read 2/2 = 1 s, write into domain 1: 2/1 = 2 s
    assert bwmodel.move_cost(np.array([2e9, 0.0]), bw, 1) \
        == pytest.approx(2.0)
    # same bytes into domain 0: write 2/2 = 1 s, read from 1: 2/1 = 2 s
    assert bwmodel.move_cost(np.array([0.0, 2e9]), bw, 0) \
        == pytest.approx(2.0)
    # reads overlap across sources (Eq.-1 shape), writes serialize
    assert bwmodel.move_cost(np.array([2e9, 1e9]), bw, 0) \
        == pytest.approx(1.5)
    assert bwmodel.move_cost(np.zeros(2), bw, 0) == 0.0


# ---------------------------------------------------------------------------
# scheduler: launch partitioning + remap patching
# ---------------------------------------------------------------------------

def _grow(pool, n):
    pages = []
    pool.table.grow(pages, n)
    return pages


def test_launch_groups_partition_by_bottleneck_domain(tiny_lm):
    cfg, _ = tiny_lm
    pool = _pool(cfg, fast=4, peer=8, host=8)
    sched = RequestScheduler(pool, max_batch=8, default_max_new=4,
                             micro_batch=True)
    fast_pages = _grow(pool, 4)              # fills hbm_local exactly
    slow_pages = _grow(pool, 3)              # spills to a slow domain
    assert {pool.domain_of(p) for p in fast_pages} == {0}
    assert 0 not in {pool.domain_of(p) for p in slow_pages}
    r_fast = types.SimpleNamespace(pages=fast_pages)
    r_slow = types.SimpleNamespace(pages=slow_pages)
    r_none = types.SimpleNamespace(pages=[])

    groups = sched._launch_groups([r_fast, r_slow])
    assert groups is not None and len(groups) == 2
    by_dom = dict(groups)
    assert by_dom[0] == [r_fast]
    assert [r_slow] in [g for d, g in groups if d != 0]

    # all requests bottlenecked on one domain -> no partition (None)
    assert sched._launch_groups([r_fast, r_fast]) is None
    # empty footprint falls back to the fastest domain
    assert sched._launch_groups([r_fast, r_none]) is None


def test_apply_page_remap_patches_every_queue(tiny_lm):
    cfg, _ = tiny_lm
    pool = _pool(cfg)
    sched = RequestScheduler(pool, max_batch=4, default_max_new=4)
    mk = lambda *pages: types.SimpleNamespace(pages=list(pages))
    a, b, c, d = mk(1, 2), mk(2, 3), mk(7), mk()
    sched.queued, sched.prefilling = [a], [b]
    sched.running, sched.swapped = [c], [d]
    sched._apply_page_remap({2: 20, 7: 70})
    assert a.pages == [1, 20] and b.pages == [20, 3]
    assert c.pages == [70] and d.pages == []


# ---------------------------------------------------------------------------
# fabric: re-home candidate ranking + budgeted migration
# ---------------------------------------------------------------------------

def _shared_slow_setup(cfg, *, n_prefix=3):
    """Fill fast with exclusive pages, then land a shared prefix chain in
    the slow domains — all through the fabric view, so the ownership map
    the re-homer consults is live. Returns (pool, view, filler, prefix,
    holder)."""
    pool = _pool(cfg, fast=4, peer=16, host=16)
    view = as_view(pool)
    ps = pool.page_size
    filler: list = []
    view.grow(filler, 4)
    prefix: list = []
    view.grow(prefix, n_prefix)
    assert all(pool.domain_of(p) != 0 for p in prefix)
    tokens = list(range(1, 1 + n_prefix * ps))
    view.register_prefix(tokens, prefix, len(tokens))
    holder: list = []
    assert view.probe_prefix(tokens, holder) == n_prefix * ps
    assert all(view.shared(p) for p in prefix)
    return pool, view, filler, prefix, holder


def test_rehome_candidates_only_hot_shared_slow_pages(tiny_lm):
    cfg, _ = tiny_lm
    pool, view, filler, prefix, _ = _shared_slow_setup(cfg)
    heat = PageHeat(pool)
    # filler (exclusive, fast) and prefix[2] (shared, cold) must not rank
    heat.touch(filler)
    heat.touch(prefix[:2], weights=[4.0, 1.0])
    heat.step()
    cands = view.rehome_candidates(heat)
    assert [pid for pid, _, _ in cands] == [prefix[0], prefix[1]]
    ranks = [rank for _, _, rank in cands]
    assert ranks == sorted(ranks, reverse=True)     # hotter-x-saving first


def test_rehome_hot_respects_budget_and_profitability(tiny_lm):
    cfg, _ = tiny_lm
    pool, view, filler, prefix, _ = _shared_slow_setup(cfg)
    heat = PageHeat(pool)
    heat.touch(prefix, weights=[8.0, 8.0, 0.5])     # third page barely warm
    heat.step()
    bw = view.fabric.bw_effective
    pb = float(view.page_bytes)
    one_page = max(pb / (bw[pool.domain_of(prefix[0])] * 1e9),
                   pb / (bw[0] * 1e9))
    # room in fast but budget covers only one page's transfer
    view.release(filler)
    moves, cost = view.rehome_hot(heat, budget_s=one_page * 1.5)
    assert len(moves) == 1 and cost <= one_page * 1.5
    assert set(moves) <= {prefix[0], prefix[1]}      # a hot page, not warm
    view.fabric.check_invariants()
    # ample budget: the other hot page moves, the barely-warm one is
    # skipped (its heat x per-read saving does not pay for the transfer)
    moves2, _ = view.rehome_hot(heat, budget_s=10.0)
    assert set(moves) | set(moves2) == {prefix[0], prefix[1]}
    assert prefix[2] not in moves2
    view.fabric.check_invariants()


def test_rehome_hot_all_holders_remap_preserves_kv(tiny_lm):
    cfg, _ = tiny_lm
    pool, view, filler, prefix, holder = _shared_slow_setup(cfg)
    pool.k_pool = pool.k_pool.at[:, prefix].set(3.5)
    pool.v_pool = pool.v_pool.at[:, prefix].set(-3.5)
    heat = PageHeat(pool)
    heat.touch(prefix, weights=[9.0, 9.0, 9.0])
    heat.step()
    seen = []
    view.on_page_remap(seen.append)
    view.release(filler)                             # fast frees up
    free0 = pool.free_count()
    moves, _ = view.rehome_hot(heat, budget_s=10.0)
    assert set(moves) == set(prefix)
    assert all(pool.domain_of(new) == 0 for new in moves.values())
    assert seen == [moves]                           # holders were notified
    view.fabric.check_invariants()
    assert pool.free_count() == free0                # old ids recycled
    new = [moves[p] for p in prefix]
    assert (np.asarray(pool.k_pool)[:, new] == 3.5).all()
    assert (np.asarray(pool.v_pool)[:, new] == -3.5).all()
    # both holders still release cleanly through the remapped ids
    view.release([moves.get(p, p) for p in holder])
    view.release(new)
    view.fabric.check_invariants()


# ---------------------------------------------------------------------------
# drift: per-launch billing
# ---------------------------------------------------------------------------

def test_observe_launches_bills_only_read_domains(tiny_lm):
    cfg, _ = tiny_lm
    pool = _pool(cfg)
    view = as_view(pool)
    led = DriftLedger(view.fabric, calibrate_every=10 ** 9)
    bw = view.fabric.bw_effective

    def probe(kind, bpd):
        return np.asarray(bpd) / (bw * 1e9)

    launches = [(np.array([4096.0, 0.0, 0.0]), 1e-8),
                (np.array([0.0, 8192.0, 0.0]), 1e-3),
                (np.zeros(3), 0.5)]                  # zero bytes: skipped
    assert led.observe_launches("batch_read", launches, probe) == 2
    assert led.summary()["domain_samples"] == [1, 1, 0]


# ---------------------------------------------------------------------------
# heat: bytes-weighted touches + Prometheus export
# ---------------------------------------------------------------------------

def test_heat_touch_weights(tiny_lm):
    cfg, _ = tiny_lm
    pool = _pool(cfg)
    pages = _grow(pool, 2)
    heat = PageHeat(pool)
    heat.touch(pages, weights=[1.0, 0.25])
    assert heat.value(pages[0]) == pytest.approx(1.0)
    assert heat.value(pages[1]) == pytest.approx(0.25)
    heat.touch([pages[1]])                           # default weight 1.0
    assert heat.value(pages[1]) == pytest.approx(1.25)


def test_engine_page_read_weights_partial_tail(tiny_lm):
    cfg, params = tiny_lm
    pool = _pool(cfg)
    eng = ServeEngine(cfg, params, pool, wall_clock=False, sim_step_s=0.01)
    # 6 tokens over page_size 4: full first page, half-full tail page
    seq = types.SimpleNamespace(pages=[10, 11], length=6)
    w = eng._page_read_weights([seq])
    assert w == {10: 1.0, 11: pytest.approx(0.5)}
    # a second holder reading deeper takes the max
    seq2 = types.SimpleNamespace(pages=[11], length=4)
    w = eng._page_read_weights([seq, seq2])
    assert w[11] == 1.0


def test_heat_histograms_in_prometheus_text(tiny_lm):
    cfg, _ = tiny_lm
    pool = _pool(cfg)
    obs = Observatory(pool, tracer=False, drift=False)
    pages = _grow(pool, 3)
    obs.heat.touch(pages, weights=[2.0, 1.0, 0.5])
    obs.heat.step()
    obs.refresh_heat_gauges()
    text = obs.metrics.prometheus_text()
    assert 'repro_page_heat{domain="hbm_local",stat="pages"} 3' in text
    assert 'stat="max"' in text and 'stat="p95"' in text


# ---------------------------------------------------------------------------
# workload: domain_skew and hot_prefix traces
# ---------------------------------------------------------------------------

def test_domain_skew_trace_shape():
    spec = WorkloadSpec(kind="domain_skew", num_requests=8, skew_frac=0.5,
                        mean_interarrival_s=0.02, prompt_mean=4,
                        prompt_max=24, max_new=8, vocab_size=500, seed=3,
                        prefix_len=8, prefix_groups=1, prefix_frac=1.0)
    trace = generate(spec)
    again = generate(spec)
    assert [t.prompt for t in trace] == [t.prompt for t in again]
    flood, tail = trace[:4], trace[4:]
    # the flood: prompt_max-length prompts, back-to-back, no prefix
    prefix = tail[0].prompt[:8]
    assert all(len(t.prompt) == 24 for t in flood)
    assert all(t.prompt[:8] != prefix for t in flood)
    assert flood[-1].arrival_s < 4 * 0.02 / 50      # gaps at mean/100
    # the steady tail all carries the shared template
    assert all(t.prompt[:8] == prefix for t in tail)
    assert all(len(t.prompt) < 24 for t in tail)


def test_hot_prefix_trace_defaults_one_hot_template():
    spec = WorkloadSpec(kind="hot_prefix", num_requests=6,
                        mean_interarrival_s=0.01, prompt_mean=6,
                        prompt_max=40, max_new=4, vocab_size=500, seed=1)
    trace = generate(spec)
    head = trace[0].prompt[:12]                     # 2 * prompt_mean tokens
    assert all(t.prompt[:12] == head for t in trace)
    arrivals = [t.arrival_s for t in trace]
    assert arrivals == sorted(arrivals) and arrivals[0] > 0


# ---------------------------------------------------------------------------
# engine: micro-batch + re-homing vs the global-batch oracle
# ---------------------------------------------------------------------------

def _contention_trace(cfg, seed=0):
    """Fillers claim the fast domain first; sharers of one hot 16-token
    template arrive while it is full, so the template lands slow."""
    rng = np.random.default_rng(seed)
    prefix = rng.integers(1, cfg.vocab_size, 16).tolist()
    fillers = [(rng.integers(1, cfg.vocab_size, 16).tolist(), 4, 0.0)
               for _ in range(3)]
    sharers = [(prefix + rng.integers(1, cfg.vocab_size, 4).tolist(),
                24, 0.02 + 0.01 * i) for i in range(3)]
    return fillers + sharers


def _run_policy(cfg, params, policy, trace, *, invariants_every_step=False):
    pool = _pool(cfg, fast=8, peer=24, host=40, policy=policy)
    view = as_view(pool)
    obs = Observatory(pool, tracer=False, drift=False)
    sched = RequestScheduler(pool, max_batch=8, prefill_token_budget=32,
                             default_max_new=24)
    eng = ServeEngine(cfg, params, pool, scheduler=sched,
                      wall_clock=False, sim_step_s=0.01)
    free0 = pool.free_count()
    for prompt, max_new, arr in trace:
        eng.submit(list(prompt), max_new=max_new, arrival_s=arr)
    steps = 0
    while (eng.active or eng.waiting) and steps < 600:
        eng.step()
        if invariants_every_step:
            view.fabric.check_invariants()
        steps += 1
    view.fabric.check_invariants()
    assert len(eng.finished) == len(trace)
    assert pool.free_count() == free0, "run leaked pages"
    assert pool.table.ref == {}, "run leaked page-table holds"
    return ({s.sid: list(s.tokens) for s in eng.finished}, eng, obs)


def test_coda_token_identical_rehomes_and_counts_launches(tiny_lm):
    cfg, params = tiny_lm
    trace = _contention_trace(cfg)
    toks_coda, eng_coda, obs = _run_policy(
        cfg, params, "coda", trace, invariants_every_step=True)
    toks_glob, eng_glob, _ = _run_policy(cfg, params, "bwap_dwp", trace)
    assert toks_coda == toks_glob, \
        "micro-batching/re-homing changed generated tokens"
    assert eng_coda.rehome and eng_coda.scheduler.micro_batch
    assert not eng_glob.rehome and not eng_glob.scheduler.micro_batch
    assert eng_coda.rehomed_pages > 0 and eng_glob.rehomed_pages == 0
    text = obs.metrics.prometheus_text()
    assert 'repro_decode_launches_total{view="default",domain=' in text
    assert 'repro_rehomed_pages_total{view="default"}' in text


def _random_schedule_roundtrip(cfg, params, seed):
    trace = generate(WorkloadSpec(
        kind="domain_skew", num_requests=5, skew_frac=0.4,
        mean_interarrival_s=0.02, prompt_mean=4, prompt_max=16,
        max_new=6, vocab_size=cfg.vocab_size, seed=seed,
        prefix_len=8, prefix_groups=1, prefix_frac=1.0))
    rows = [(t.prompt, t.max_new, t.arrival_s) for t in trace]
    toks_coda, _, _ = _run_policy(cfg, params, "coda", rows,
                                  invariants_every_step=True)
    toks_glob, _, _ = _run_policy(cfg, params, "bwap_dwp", rows)
    assert toks_coda == toks_glob


@pytest.mark.parametrize("seed", [1, 7])
def test_coda_random_schedules_match_oracle(tiny_lm, seed):
    cfg, params = tiny_lm
    _random_schedule_roundtrip(cfg, params, seed)


@settings(max_examples=3, deadline=None)
@given(seed=st.integers(min_value=0, max_value=2 ** 20))
def test_coda_random_schedules_match_oracle_property(tiny_lm, seed):
    cfg, params = tiny_lm
    _random_schedule_roundtrip(cfg, params, seed)
