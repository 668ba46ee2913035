"""Scheduler subsystem: priority continuous batching, chunked prefill,
bandwidth-aware KV swap (preemption round-trips must be bit-exact)."""

import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from hypothesis import given, settings
from hypothesis import strategies as st

from repro.configs import registry
from repro.core.dwp import DWPConfig
from repro.placement.arbiter import DomainArbiter, DomainSpec, Priority
from repro.scheduler import (KVSwapManager, PriorityClass, RequestScheduler,
                             SloSpec, SloTracker, State, WorkloadSpec,
                             generate, total_kv_pages)
from repro.serve.engine import ServeEngine
from repro.serve.kvcache import BwapPagePool, MemoryDomain


@pytest.fixture(scope="module")
def small_lm():
    cfg = registry.get_smoke_config("qwen2-0.5b")
    cfg = dataclasses.replace(cfg, num_layers=2, compute_dtype="float32")
    from repro.models.lm import LM
    model = LM(cfg)
    params = model.init(jax.random.PRNGKey(0))
    return cfg, params


def _pool(cfg, fast=8, peer=8, host=60, page_size=4, n=100):
    """Small fast domain, large slow domains (slow bw in the engine-latency
    range so Eq.-1 terms are visible); tuner effectively frozen (n large)."""
    domains = [
        MemoryDomain("hbm_local", fast, 819.0, True),
        MemoryDomain("hbm_peer", peer, 0.05, False),
        MemoryDomain("host", host, 0.016, False),
    ]
    return BwapPagePool(cfg, domains, page_size=page_size,
                        dwp_config=DWPConfig(n=n, c=1))


def _drain(eng, max_steps=500):
    steps = 0
    while (eng.active or eng.waiting) and steps < max_steps:
        eng.step()
        steps += 1
    return steps


# ---------------------------------------------------------------------------
# workload generation
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("kind", ["poisson", "bursty", "heavy_tail"])
def test_workload_deterministic_and_bounded(kind):
    spec = WorkloadSpec(kind=kind, num_requests=40, prompt_mean=10,
                        prompt_max=32, vocab_size=500, seed=3,
                        class_mix=(("a", 0.5), ("b", 0.5)))
    t1, t2 = generate(spec), generate(spec)
    assert t1 == t2                                   # seeded determinism
    t3 = generate(dataclasses.replace(spec, seed=4))
    assert t1 != t3
    arr = [r.arrival_s for r in t1]
    assert arr == sorted(arr) and arr[0] >= 0
    for r in t1:
        assert 1 <= len(r.prompt) <= 32
        assert all(1 <= t < 500 for t in r.prompt)
        assert r.cls in ("a", "b")
    assert total_kv_pages(t1, 4) == sum(
        -(-(len(r.prompt) + r.max_new) // 4) for r in t1)


def test_workload_kind_shapes():
    n, mean = 200, 0.1
    bursty = generate(WorkloadSpec(kind="bursty", num_requests=n,
                                   mean_interarrival_s=mean, seed=0,
                                   burst_len=4, burst_factor=8.0))
    gaps = np.diff([0.0] + [r.arrival_s for r in bursty])
    # within-burst gaps are ~mean/8; burst starts are ~8x longer
    assert np.percentile(gaps, 50) < mean
    assert gaps.max() > 2 * mean
    heavy = generate(WorkloadSpec(kind="heavy_tail", num_requests=n,
                                  prompt_mean=8, prompt_max=64, seed=0))
    lens = np.asarray([len(r.prompt) for r in heavy])
    assert lens.max() > 4 * np.percentile(lens, 50)   # a heavy tail exists


def test_workload_shared_prefixes():
    spec = WorkloadSpec(kind="heavy_tail", num_requests=40, prompt_mean=6,
                        prompt_max=24, vocab_size=500, seed=2,
                        prefix_len=12, prefix_groups=2, prefix_frac=0.8)
    t1, t2 = generate(spec), generate(spec)
    assert t1 == t2                                   # still deterministic
    heads = {}
    for r in t1:
        heads.setdefault(r.prompt[:12], 0)
        heads[r.prompt[:12]] += 1
    # at most 2 shared heads dominate; the rest are unique leading tokens
    shared = sorted(heads.values(), reverse=True)[:2]
    assert sum(shared) >= 0.5 * len(t1)
    assert len([h for h, n in heads.items() if n > 1]) <= 2
    # prefix_len=0 keeps the original generator byte-for-byte
    base = WorkloadSpec(kind="heavy_tail", num_requests=8, seed=3)
    assert generate(base) == generate(dataclasses.replace(
        base, prefix_groups=4, prefix_frac=0.5))


# ---------------------------------------------------------------------------
# SLO accounting
# ---------------------------------------------------------------------------

def test_slo_tracker_deadlines_and_goodput():
    tr = SloTracker({"fast": SloSpec(ttft_s=1.0, tpot_s=0.5),
                     "free": SloSpec()})
    tr.on_submit(0, "fast", arrival_s=0.0)
    tr.on_first_token(0, now=0.5)                 # ttft 0.5 <= 1.0
    tr.on_finish(0, now=2.0, produced=4)          # tpot 0.5 <= 0.5
    tr.on_submit(1, "fast", arrival_s=0.0)
    tr.on_first_token(1, now=3.0)                 # ttft miss
    tr.on_finish(1, now=4.0, produced=2)
    tr.on_submit(2, "free", arrival_s=0.0)
    tr.on_first_token(2, now=9.0)                 # inf deadlines: always good
    tr.on_finish(2, now=10.0, produced=3)
    s = tr.summary(now=10.0)
    fast = s["classes"]["fast"]
    assert fast["completed"] == 2 and fast["good"] == 1
    assert math.isclose(fast["ttft_mean_s"], (0.5 + 3.0) / 2)
    assert s["classes"]["free"]["good"] == 1
    assert s["good_tokens"] == 4 + 3
    assert math.isclose(s["goodput_tok_s"], 7 / 10.0)
    assert tr.counters.get("fast", "ttft_missed") == 1
    assert tr.counters.get("fast", "goodput_tokens") == 4


# ---------------------------------------------------------------------------
# swap manager: reservation, placement, exact round-trips
# ---------------------------------------------------------------------------

def test_swap_roundtrip_preserves_exact_kv(small_lm):
    cfg, _ = small_lm
    pool = _pool(cfg, fast=8, peer=8, host=16)
    swap = KVSwapManager(pool, reserve_fraction=0.5)
    reserved = swap.reserved_total
    assert reserved > 0
    assert pool.free_count() + reserved == pool.total_pages
    pages = [pool.alloc_page() for _ in range(5)]
    rng = np.random.default_rng(0)
    for p in pages:      # distinct recognizable content per page
        pool.k_pool = pool.k_pool.at[:, p].set(
            jnp.asarray(rng.normal(size=pool.k_pool.shape[2:]), jnp.float32))
        pool.v_pool = pool.v_pool.at[:, p].set(
            jnp.asarray(rng.normal(size=pool.v_pool.shape[2:]), jnp.float32))
    k_ref = np.asarray(pool.k_pool)[:, pages].copy()
    v_ref = np.asarray(pool.v_pool)[:, pages].copy()
    free_before = pool.free_count()

    parked, secs_out = swap.swap_out(list(pages))
    assert secs_out > 0
    assert pool.free_count() == free_before + len(pages)  # sources freed
    for p in parked:
        assert pool.domain_of(p) in pool.slow_domains
    np.testing.assert_array_equal(np.asarray(pool.k_pool)[:, parked], k_ref)
    np.testing.assert_array_equal(np.asarray(pool.v_pool)[:, parked], v_ref)

    back, secs_in = swap.swap_in(parked)
    assert secs_in > 0
    assert swap.slots_free() == reserved               # slots all returned
    np.testing.assert_array_equal(np.asarray(pool.k_pool)[:, back], k_ref)
    np.testing.assert_array_equal(np.asarray(pool.v_pool)[:, back], v_ref)
    tel = pool.telemetry.snapshot()
    assert tel["swap_outs"] == 5 and tel["swap_ins"] == 5


def test_swap_placement_follows_policy(small_lm):
    cfg, _ = small_lm
    pool = _pool(cfg, fast=4, peer=24, host=24)
    # bwap: spread over slow domains proportional to bandwidth
    bwap = KVSwapManager(pool, placement="bwap_canonical",
                         reserve_fraction=0.9)
    counts = bwap._slot_counts(20)
    assert counts.sum() == 20
    # peer (0.05 GB/s) gets ~3x host's share (0.016 GB/s)
    assert counts[0] > 2 * counts[1]
    # local_first: everything into the fastest slow domain while it fits
    pool2 = _pool(cfg, fast=4, peer=24, host=24)
    lf = KVSwapManager(pool2, placement="local_first", reserve_fraction=0.9)
    counts2 = lf._slot_counts(10)
    assert counts2[0] == 10 and counts2[1] == 0


@given(st.lists(st.integers(min_value=1, max_value=6), min_size=1,
                max_size=5),
       st.integers(min_value=0, max_value=2 ** 31 - 1))
@settings(max_examples=20, deadline=None)
def test_swap_random_interleavings_preserve_kv(footprints, seed):
    """Random sequences of swap-out/swap-in (random preemption points at the
    page level) never corrupt or cross-wire K/V contents."""
    cfg = dataclasses.replace(registry.get_smoke_config("qwen2-0.5b"),
                              num_layers=1, compute_dtype="float32")
    pool = _pool(cfg, fast=8, peer=10, host=24)
    swap = KVSwapManager(pool, reserve_fraction=0.8)
    # draws may overdraw the pool outside the swap reservation; keep the
    # prefix that fits, so the property exercises swaps, not exhaustion
    free = pool.free_count()
    footprints = [n for n, total in zip(footprints, np.cumsum(footprints))
                  if total <= free]
    assert footprints, "the first draw alone must fit the unreserved pool"
    rng = np.random.default_rng(seed)
    seqs = []
    for i, n in enumerate(footprints):
        pages = [pool.alloc_page() for _ in range(n)]
        fill = float(i + 1)
        pool.k_pool = pool.k_pool.at[:, pages].set(fill)
        pool.v_pool = pool.v_pool.at[:, pages].set(-fill)
        seqs.append({"pages": pages, "fill": fill, "parked": False})
    for _ in range(12):                      # random preemption points
        s = seqs[int(rng.integers(len(seqs)))]
        if s["parked"]:
            s["pages"], _ = swap.swap_in(s["pages"])
        elif swap.can_swap_out(len(s["pages"])):
            s["pages"], _ = swap.swap_out(s["pages"])
        s["parked"] = not s["parked"]
    for s in seqs:
        got_k = np.asarray(pool.k_pool)[:, s["pages"]]
        got_v = np.asarray(pool.v_pool)[:, s["pages"]]
        assert (got_k == s["fill"]).all() and (got_v == -s["fill"]).all()


def test_unreserve_returns_slots_to_allocator(small_lm):
    """Dropping part of the reservation through the fabric view hands the
    slots back to the allocator and keeps every ledger consistent (the
    incremental reserve/unreserve API that replaced the old bulk
    set_reserved_counts resync)."""
    cfg, _ = small_lm
    pool = _pool(cfg, fast=8, peer=8, host=8)
    swap = KVSwapManager(pool, reserve_fraction=1.0)
    assert swap.reserved_total == 16
    view = swap.view
    free_before = view.free_count()
    give_back = [swap.slots[1].pop() for _ in range(4)]   # peer slots
    for pid in give_back:
        view.unreserve(pid)
    swap.reserved_total -= 4
    assert view.free_count() == free_before + 4
    assert int(pool.reserved.sum()) == 12
    assert swap.slots_free() == 12
    assert swap.can_swap_out(12) and not swap.can_swap_out(13)
    assert swap._slot_counts(12).sum() == 12      # placeable = claimed


# ---------------------------------------------------------------------------
# scheduler: chunked prefill, priority, capacity preemption
# ---------------------------------------------------------------------------

def test_chunked_prefill_respects_token_budget(small_lm):
    cfg, _ = small_lm
    pool = _pool(cfg, fast=32, peer=8, host=8)
    # the second prompt is a prefix of the first: disable trie matching so
    # the budget accounting below counts every prompt token (sharing has
    # its own tests in test_pagetable.py)
    pool.table.prefix_reuse = False
    sched = RequestScheduler(pool, max_batch=4, prefill_token_budget=5,
                             default_max_new=4)
    sched.submit(list(range(1, 18)))          # prompt 17 -> target 16 tokens
    sched.submit(list(range(1, 8)))           # prompt 7  -> target 6 tokens
    seen = []
    for _ in range(10):
        plan = sched.schedule()
        total = sum(hi - lo for _, lo, hi in plan.prefill_chunks)
        assert total <= 5
        seen.append(total)
        for r, lo, hi in plan.prefill_chunks:   # stand in for the engine
            r.length = hi
        if not sched.queued and not sched.prefilling:
            break
    assert sum(seen) == 16 + 6                # every prompt token admitted
    assert len(sched.running) == 2


def test_priority_class_preempts_lower(small_lm):
    cfg, params = small_lm
    pool = _pool(cfg, fast=8, peer=8, host=60)
    swap = KVSwapManager(pool, reserve_fraction=0.9)
    sched = RequestScheduler(
        pool, max_batch=4, prefill_token_budget=64,
        classes=[PriorityClass("hi", 5, SloSpec(1.0, 1.0)),
                 PriorityClass("lo", 0)],
        default_class="lo", default_max_new=6, swap=swap)
    eng = ServeEngine(cfg, params, pool, scheduler=sched, wall_clock=False,
                      sim_step_s=0.01)
    rng = np.random.default_rng(0)
    for _ in range(4):                         # fill every batch slot
        eng.submit(rng.integers(1, cfg.vocab_size, 10).tolist(), cls="lo")
    eng.step()
    assert len(sched.running) == 4
    eng.submit(rng.integers(1, cfg.vocab_size, 10).tolist(), cls="hi")
    eng.step()                                 # must evict a "lo" victim
    assert any(r.cls == "hi" for r in sched.running)
    assert len(sched.swapped) >= 1
    assert all(r.cls == "lo" for r in sched.swapped)
    _drain(eng)
    assert len(eng.finished) == 5
    slo = pool.telemetry.snapshot()["slo"]
    assert slo["lo"]["preemptions"] >= 1
    assert slo["hi"]["preemptions"] == 0
    assert slo["hi"]["swap_out_pages"] == 0


def test_oversubscribed_completes_with_zero_failures(small_lm):
    """Total KV footprint >> hbm_local (and > unreserved pool): everything
    still completes, via parking cold sequences in reserved slow slots."""
    cfg, params = small_lm
    pool = _pool(cfg, fast=10, peer=10, host=50)
    swap = KVSwapManager(pool, reserve_fraction=0.9)
    sched = RequestScheduler(pool, max_batch=6, prefill_token_budget=24,
                             default_max_new=8, swap=swap)
    eng = ServeEngine(cfg, params, pool, scheduler=sched, wall_clock=False,
                      sim_step_s=0.01)
    trace = generate(WorkloadSpec(
        kind="bursty", num_requests=12, mean_interarrival_s=0.005,
        prompt_mean=12, prompt_max=20, max_new=8,
        vocab_size=cfg.vocab_size, seed=1))
    assert total_kv_pages(trace, pool.page_size) > 10   # oversubscribed
    for t in trace:
        eng.submit(t.prompt, max_new=t.max_new, arrival_s=t.arrival_s)
    _drain(eng)
    assert len(eng.finished) == len(trace)              # zero failures
    assert all(s.produced == s.max_new for s in eng.finished)
    assert pool.telemetry.swap_outs > 0                 # swap did the work
    assert pool.telemetry.swap_outs == pool.telemetry.swap_ins
    # every page accounted for: free pool + untouched reservation
    assert pool.free_count() + swap.reserved_total == pool.total_pages
    assert swap.slots_free() == swap.reserved_total


def test_infeasible_request_rejected_at_submit(small_lm):
    cfg, _ = small_lm
    pool = _pool(cfg, fast=2, peer=1, host=1)
    sched = RequestScheduler(pool, max_batch=2, default_max_new=4)
    with pytest.raises(ValueError, match="allocatable"):
        sched.submit(list(range(1, 40)))      # footprint > whole pool
    # a swap reservation shrinks what one sequence may hold
    pool2 = _pool(cfg, fast=4, peer=8, host=8)
    swap = KVSwapManager(pool2, reserve_fraction=1.0)   # all slow reserved
    sched2 = RequestScheduler(pool2, max_batch=2, default_max_new=4,
                              swap=swap)
    assert sched2.allocatable_pages() == 4
    with pytest.raises(ValueError, match="allocatable"):
        sched2.submit(list(range(1, 20)))


def test_joint_exhaustion_raises_not_spins(small_lm):
    """Individually feasible requests that jointly exceed the pool must
    fail loudly once no step can make progress (no swap to fall back on)."""
    cfg, _ = small_lm
    pool = _pool(cfg, fast=4, peer=2, host=2)     # 8 pages, 2 seqs x 3+
    sched = RequestScheduler(pool, max_batch=2, prefill_token_budget=6,
                             default_max_new=20)
    sched.submit(list(range(1, 14)))              # 8 pages each at full
    sched.submit(list(range(1, 14)))              # length: jointly 16 > 8
    with pytest.raises(RuntimeError, match="exhausted|grow"):
        for _ in range(60):                       # simulate engine decode
            plan = sched.schedule()
            for r in plan.batch:
                if r.length % pool.page_size == 0:
                    r.pages.append(pool.alloc_page())
                r.tokens.append(1)
                r.length += 1


def test_stall_preemption_evicts_read_time_hog(small_lm):
    """A sequence whose pages sit in a glacial domain dominates the batch's
    Eq.-1 read time: the stall trigger must evict exactly it (and only when
    the trigger is enabled)."""
    cfg, _ = small_lm

    def setup(frac):
        pool = _pool(cfg, fast=16, peer=12, host=12)
        swap = KVSwapManager(pool, reserve_fraction=0.8)
        sched = RequestScheduler(pool, max_batch=4, prefill_token_budget=64,
                                 default_max_new=8, swap=swap,
                                 stall_preempt_fraction=frac,
                                 stall_preempt_cooldown_s=10.0)
        sched.submit([1, 2, 3, 4, 5])
        sched.submit([6, 7, 8, 9, 10])
        plan = sched.schedule()                  # both prefill + run
        for r, lo, hi in plan.prefill_chunks:
            r.length = hi
        hog, other = sched.running
        # drag the hog's pages into the slowest domain by hand (domain 2),
        # carrying the page-table refs along like a real mover would
        new = [pool.free[2].pop() for _ in hog.pages]
        for old, n in zip(hog.pages, new):
            pool.free[pool.domain_of(old)].append(old)
            pool.table.remap_physical(old, n)
        hog.pages[:] = new
        return pool, sched, hog, other

    pool, sched, hog, other = setup(0.5)
    sched.schedule()
    assert hog in sched.swapped                  # evicted: it gated reads
    assert other in sched.running
    assert hog.resume_after > sched.now          # cooldown armed
    sched.schedule()
    assert hog in sched.swapped                  # cooldown blocks thrash

    pool2, sched2, hog2, _ = setup(None)         # trigger disabled
    sched2.schedule()
    assert hog2 in sched2.running


def test_swap_aware_dwp_respects_reservation(small_lm):
    """Reserved swap slots must leave the capacities the DWP tuner sees:
    with every slow page reserved, the allocation cycle may only promise
    worker-domain pages (roadmap: swap-aware DWP)."""
    cfg, _ = small_lm
    pool = _pool(cfg, fast=8, peer=8, host=8, n=4)
    assert pool.tuner.capacity_fractions is None     # no reservation yet
    swap = KVSwapManager(pool, reserve_fraction=1.0)
    assert swap.reserved_total == 16
    np.testing.assert_array_equal(pool.reserved, [0, 8, 8])
    # effective capacities reach placement decisions (policy context)...
    np.testing.assert_array_equal(pool._ctx(0.0).capacities, [8, 0, 0])
    # ...and the tuner's cycle stops promising reserved-away pages
    assert set(int(d) for d in pool.tuner.assignment) == {0}
    # partial reservation: only the reserved domain's share is capped (at
    # its unreserved fraction of the allocatable pool); others stay free
    pool2 = _pool(cfg, fast=8, peer=8, host=8, n=4)
    KVSwapManager(pool2, reserve_pages={"hbm_peer": 6})
    np.testing.assert_array_equal(pool2.reserved, [0, 6, 0])
    np.testing.assert_array_equal(pool2._ctx(0.0).capacities, [8, 2, 8])
    frac = pool2.tuner.capacity_fractions
    assert frac is not None and frac[1] == pytest.approx(2 / 18)
    assert np.isinf(frac[0]) and np.isinf(frac[2])


# ---------------------------------------------------------------------------
# preemption round-trip: decode must be bit-exact
# ---------------------------------------------------------------------------

def test_preempted_decode_matches_unpressured_reference(small_lm):
    """Chunked prefill + swap-out/swap-in round-trips must not change a
    single token vs a run with no memory pressure."""
    cfg, params = small_lm
    rng = np.random.default_rng(7)
    prompts = [rng.integers(1, cfg.vocab_size, int(n)).tolist()
               for n in (9, 14, 5, 11, 7, 13)]

    def run(pressured):
        if pressured:
            pool = _pool(cfg, fast=8, peer=8, host=60)
            swap = KVSwapManager(pool, reserve_fraction=0.85)
            sched = RequestScheduler(pool, max_batch=6,
                                     prefill_token_budget=7,
                                     default_max_new=8, swap=swap)
        else:
            pool = _pool(cfg, fast=64, peer=16, host=16)
            swap = None
            sched = RequestScheduler(pool, max_batch=6,
                                     prefill_token_budget=256,
                                     default_max_new=8)
        eng = ServeEngine(cfg, params, pool, scheduler=sched,
                          wall_clock=False)
        for p in prompts:
            eng.submit(list(p))
        _drain(eng)
        assert len(eng.finished) == len(prompts)
        return ({s.sid: s.tokens for s in eng.finished},
                pool.telemetry.swap_outs)

    ref, _ = run(False)
    got, swaps = run(True)
    assert swaps > 0                           # pressure actually preempted
    assert got == ref


@pytest.mark.parametrize("preempt_step", [0, 2, 5])
def test_forced_preemption_at_point_is_exact(small_lm, preempt_step):
    """Force a swap-out at a specific decode step, resume, and compare the
    full generation against the dense-path reference engine."""
    cfg, params = small_lm
    prompt = [3, 17, 29, 5, 41, 11]
    max_new = 8

    def reference():
        pool = _pool(cfg, fast=64, peer=8, host=8)
        eng = ServeEngine(cfg, params, pool, max_batch=1, max_new=max_new)
        eng.submit(list(prompt))
        _drain(eng)
        return eng.finished[0].tokens

    pool = _pool(cfg, fast=16, peer=8, host=40)
    swap = KVSwapManager(pool, reserve_fraction=0.8)
    sched = RequestScheduler(pool, max_batch=1, prefill_token_budget=64,
                             default_max_new=max_new, swap=swap)
    eng = ServeEngine(cfg, params, pool, scheduler=sched, wall_clock=False)
    eng.submit(list(prompt))
    for _ in range(preempt_step + 1):
        eng.step()
    victim = sched.running[0]
    sched._swap_out(victim)                    # forced preemption point
    assert victim.state is State.SWAPPED
    _drain(eng)
    assert len(eng.finished) == 1
    assert eng.finished[0].tokens == reference()


# ---------------------------------------------------------------------------
# arbiter integration: tenants as priority classes
# ---------------------------------------------------------------------------

def test_fabric_views_register_tenants_as_priority_classes(small_lm):
    """Schedulers built on named fabric views pick up the tenant's class
    level and default class from the view itself — the wiring the old
    arbiter.attach_engine back-channel used to reach in and do."""
    cfg, params = small_lm
    arb = DomainArbiter([DomainSpec("hbm_local", 48, 819.0),
                         DomainSpec("hbm_peer", 32, 0.05),
                         DomainSpec("host", 64, 0.016)], page_size=4)
    ta = arb.register("prod", cfg, priority=Priority.HIGH, share=0.5)
    tb = arb.register("bulk", cfg, priority=Priority.BEST_EFFORT, share=0.5)
    sched_a = RequestScheduler(
        ta.view, max_batch=2, default_max_new=4,
        classes=[PriorityClass("prod", 0, SloSpec(ttft_s=0.5, tpot_s=0.1))])
    eng_a = ServeEngine(cfg, params, ta.view, scheduler=sched_a)
    eng_b = ServeEngine(cfg, params, tb.view, max_batch=2, max_new=4)
    assert eng_a.scheduler.classes["prod"].level \
        > eng_b.scheduler.classes["bulk"].level
    assert eng_a.scheduler.default_class == "prod"
    # operator-configured deadlines survive the arbiter's level override
    assert eng_a.scheduler.classes["prod"].slo.ttft_s == 0.5
    assert eng_a.scheduler.slo.specs["prod"].tpot_s == 0.1
    # submits land in the tenant's class and serve normally
    eng_a.submit([5, 9, 2])
    eng_b.submit([7, 1, 8])
    _drain(eng_a)
    _drain(eng_b)
    assert eng_a.finished[0].cls == "prod"
    assert eng_b.finished[0].cls == "bulk"
    # one shared fabric telemetry carries both tenants' SLO rows
    snap = ta.view.snapshot()
    assert sorted(snap.get("slo", {})) == ["bulk", "prod"]
    arb.fabric.check_invariants()
