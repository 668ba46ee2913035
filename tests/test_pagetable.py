"""Logical page-table layer (ISSUE 3 / DESIGN.md §6): refcount lifecycle,
prefix-trie hit/miss, CoW fork exactness, swap pinning of shared pages, and
O(n) incremental chunked prefill vs the recompute oracle."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from hypothesis import given, settings
from hypothesis import strategies as st

from repro.configs import registry
from repro.core.dwp import DWPConfig
from repro.kernels.paged_attention import ops as paged_ops
from repro.kernels.paged_attention.ref import (paged_attention_ref,
                                               paged_prefill_attention_ref)
from repro.scheduler import KVSwapManager, RequestScheduler
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


def _pool(cfg, fast=32, peer=16, host=16, page_size=4):
    domains = [
        MemoryDomain("hbm_local", fast, 819.0, True),
        MemoryDomain("hbm_peer", peer, 0.05, False),
        MemoryDomain("host", host, 0.016, False),
    ]
    return BwapPagePool(cfg, domains, page_size=page_size,
                        dwp_config=DWPConfig(n=10 ** 6, c=1))


def _drain(eng, max_steps=500):
    steps = 0
    while (eng.active or eng.waiting) and steps < max_steps:
        eng.step()
        steps += 1
    return steps


# ---------------------------------------------------------------------------
# refcounts + trie
# ---------------------------------------------------------------------------

def test_refcount_lifecycle(small_lm):
    cfg, _ = small_lm
    pool = _pool(cfg)
    t = pool.table
    ps = pool.page_size
    tokens = list(range(1, 1 + 3 * ps))          # 3 full blocks
    donor: list = []
    t.grow(donor, 3)
    assert all(t.ref[p] == 1 for p in donor)
    t.register_prefix(tokens, donor, len(tokens))
    assert t.stats()["trie_nodes"] == 3

    view: list = []
    assert t.match_prefix(tokens, view) == 3 * ps
    assert view == donor
    assert all(t.ref[p] == 2 for p in donor)
    assert t.exclusive(view) == []               # everything shared
    assert t.stats()["shared_pages"] == 3
    assert t.stats()["saved_pages"] == 3

    free0 = pool.free_count()
    t.release(view)                              # drop one holder
    assert pool.free_count() == free0            # donor still holds
    assert all(t.ref[p] == 1 for p in donor)
    t.release(donor)                             # last holder: pages free,
    assert pool.free_count() == free0 + 3        # trie nodes gone
    assert t.stats()["trie_nodes"] == 0
    assert t.ref == {}


def test_trie_chain_keying_blocks_position_aliasing(small_lm):
    """An identical token block after a *different* prefix must not match:
    K/V depends on the whole preceding context, so trie keys chain."""
    cfg, _ = small_lm
    pool = _pool(cfg)
    t = pool.table
    ps = pool.page_size
    blk_a, blk_b = list(range(10, 10 + ps)), list(range(50, 50 + ps))
    donor: list = []
    t.grow(donor, 2)
    t.register_prefix(blk_a + blk_b, donor, 2 * ps)

    hit: list = []
    assert t.match_prefix(blk_a + blk_b, hit) == 2 * ps      # full chain
    t.release(hit)
    partial: list = []
    assert t.match_prefix(blk_a + list(range(90, 90 + ps)),
                          partial) == ps                     # prefix only
    t.release(partial)
    aliased: list = []
    # blk_b exists in the trie, but only as a *child* of blk_a's node:
    # leading with it must miss
    assert t.match_prefix(blk_b + blk_a, aliased) == 0
    assert t.stats()["prefix_misses"] >= 1
    t.release(donor)


def test_fork_for_write_isolates_holders(small_lm):
    cfg, _ = small_lm
    pool = _pool(cfg)
    t = pool.table
    a: list = []
    t.grow(a, 1)
    pool.k_pool = pool.k_pool.at[:, a[0]].set(7.0)
    pool.v_pool = pool.v_pool.at[:, a[0]].set(-7.0)
    tokens = list(range(1, 1 + pool.page_size))
    t.register_prefix(tokens, a, pool.page_size)
    b: list = []
    t.match_prefix(tokens, b)
    assert b == a

    pid = t.fork_for_write(b, 0)                 # CoW: b gets a clone
    assert pid != a[0] and b[0] == pid
    assert t.ref[a[0]] == 1 and t.ref[pid] == 1
    assert t.cow_faults == 1
    # clone carries the bytes; writes to it don't touch the original
    assert (np.asarray(pool.k_pool)[:, pid] == 7.0).all()
    pool.k_pool = pool.k_pool.at[:, pid].set(9.0)
    assert (np.asarray(pool.k_pool)[:, a[0]] == 7.0).all()
    # forking an exclusive page is a no-op
    assert t.fork_for_write(b, 0) == pid and t.cow_faults == 1
    t.release(a)
    t.release(b)
    assert pool.free_count() == pool.total_pages


# ---------------------------------------------------------------------------
# engine integration: sharing is invisible in tokens, visible in footprint
# ---------------------------------------------------------------------------

def _shared_prompts(cfg, ps, prefix_blocks=2, n=3, seed=0):
    rng = np.random.default_rng(seed)
    prefix = rng.integers(1, cfg.vocab_size, prefix_blocks * ps).tolist()
    return [prefix + rng.integers(1, cfg.vocab_size, 3 + i).tolist()
            for i in range(n)]


def _run_engine(cfg, params, prompts, *, reuse, incremental=True,
                max_new=5, budget=64, arrivals=None):
    pool = _pool(cfg, fast=64, peer=16, host=16)
    sched = RequestScheduler(pool, max_batch=8,
                             prefill_token_budget=budget,
                             default_max_new=max_new)
    eng = ServeEngine(cfg, params, pool, scheduler=sched, wall_clock=False,
                      sim_step_s=0.01, prefix_reuse=reuse,
                      incremental_prefill=incremental)
    for i, p in enumerate(prompts):
        eng.submit(list(p), arrival_s=arrivals[i] if arrivals else None)
    _drain(eng)
    assert len(eng.finished) == len(prompts)
    return eng, pool


def test_prefix_sharing_saves_pages_tokens_identical(small_lm):
    """Requests sharing a prompt prefix must generate the same tokens as
    without sharing, while mapping the prefix onto shared physical pages."""
    cfg, params = small_lm
    prompts = _shared_prompts(cfg, ps=4, prefix_blocks=2, n=3)
    # staggered arrivals: the donor's prefix registers (end of its prefill
    # step) before the matchers' first planning probes, and every holder
    # chain overlaps a live sequence so the trie pages stay resident
    arrivals = [0.0, 0.02, 0.04]
    on, pool_on = _run_engine(cfg, params, prompts, reuse=True,
                              arrivals=arrivals)
    off, _ = _run_engine(cfg, params, prompts, reuse=False,
                         arrivals=arrivals)
    tok_on = {s.sid: s.tokens for s in on.finished}
    tok_off = {s.sid: s.tokens for s in off.finished}
    assert tok_on == tok_off
    st_ = pool_on.table.stats()
    assert st_["prefix_hit_pages"] >= 2 * 2      # 2 matchers x 2 blocks
    assert on.prefill_tokens_computed < off.prefill_tokens_computed
    # all pages reclaimed at the end — sharing never leaks
    assert pool_on.free_count() == pool_on.total_pages


def test_cow_fork_on_full_prompt_match_is_exact(small_lm):
    """A prompt fully covered by registered blocks: the first decode step
    writes the last prompt position *into a shared page* — the CoW fork —
    and generation must equal the unshared baseline."""
    cfg, params = small_lm
    ps = 4
    rng = np.random.default_rng(3)
    prefix = rng.integers(1, cfg.vocab_size, 3 * ps).tolist()
    donor = prefix + rng.integers(1, cfg.vocab_size, 5).tolist()
    matcher = list(prefix)                       # block-aligned full prompt

    base, _ = _run_engine(cfg, params, [matcher], reuse=False)
    eng, pool = _run_engine(cfg, params, [donor, matcher], reuse=True,
                            arrivals=[0.0, 0.015])
    assert pool.table.cow_faults >= 1            # the fork actually fired
    got = next(s for s in eng.finished if s.prompt_len == len(matcher))
    want = base.finished[0]
    assert got.tokens[got.prompt_len:] == want.tokens[want.prompt_len:]
    assert pool.free_count() == pool.total_pages


# ---------------------------------------------------------------------------
# incremental chunked prefill: O(n) compute, token-exact vs recompute
# ---------------------------------------------------------------------------

def test_incremental_prefill_is_o_n_and_token_exact(small_lm):
    """With a small chunk budget the recompute path forwards O(n²) prompt
    tokens across chunks; the incremental path must forward each prompt
    token exactly once and produce identical generations."""
    cfg, params = small_lm
    rng = np.random.default_rng(1)
    prompts = [rng.integers(1, cfg.vocab_size, n).tolist()
               for n in (23, 17, 9)]
    targets = sum(len(p) - 1 for p in prompts)
    inc, _ = _run_engine(cfg, params, prompts, reuse=False,
                         incremental=True, budget=6)
    rec, _ = _run_engine(cfg, params, prompts, reuse=False,
                         incremental=False, budget=6)
    assert {s.sid: s.tokens for s in inc.finished} \
        == {s.sid: s.tokens for s in rec.finished}
    # the O(n) assertion: exactly one forward per materialized position
    assert inc.prefill_tokens_computed == targets
    # the recompute oracle re-forwards the prefix every chunk: O(n²)
    assert rec.prefill_tokens_computed > targets
    assert inc.prefill_chunks_run > len(prompts)     # chunking did happen


# ---------------------------------------------------------------------------
# prefill-mode paged attention op
# ---------------------------------------------------------------------------

def test_prefill_op_matches_decode_op_per_position():
    """The prefill-mode op at chunk [lo, hi) must agree with the decode op
    queried position-by-position (lens = pos+1) over the same pool."""
    ps, pages, nkv, g, h, t, lo = 4, 8, 2, 2, 16, 5, 6
    nq = nkv * g
    kp = jax.random.normal(jax.random.PRNGKey(0), (pages, ps, nkv, h))
    vp = jax.random.normal(jax.random.PRNGKey(1), (pages, ps, nkv, h))
    q = jax.random.normal(jax.random.PRNGKey(2), (t, nq, h))
    tbl = jnp.asarray([3, 1, 4], jnp.int32)      # covers lo + t = 11 < 12
    out = paged_prefill_attention_ref(q, kp, vp, tbl, lo)
    per_pos = paged_attention_ref(
        q, kp, vp, jnp.broadcast_to(tbl, (t, 3)),
        lo + 1 + jnp.arange(t, dtype=jnp.int32))
    np.testing.assert_allclose(np.asarray(out), np.asarray(per_pos),
                               rtol=1e-6, atol=1e-6)


def test_prefill_kernel_matches_ref_interpret():
    """Pallas prefill kernel (interpret mode) vs the jnp oracle."""
    ps, pages, nkv, g, h, t, lo = 4, 8, 2, 2, 16, 5, 6
    nq = nkv * g
    kp = jax.random.normal(jax.random.PRNGKey(0), (pages, ps, nkv, h))
    vp = jax.random.normal(jax.random.PRNGKey(1), (pages, ps, nkv, h))
    q = jax.random.normal(jax.random.PRNGKey(2), (t, nq, h))
    tbl = jnp.asarray([3, 1, 4], jnp.int32)
    ref = paged_prefill_attention_ref(q, kp, vp, tbl, lo)
    try:
        out = paged_ops.paged_prefill_attention(q, kp, vp, tbl, lo,
                                                impl="pallas",
                                                interpret=True)
    except Exception as e:                        # pragma: no cover
        pytest.skip(f"pallas interpret unavailable: {e}")
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


# ---------------------------------------------------------------------------
# swap x sharing: shared pages pin, exclusive pages park
# ---------------------------------------------------------------------------

def test_swap_roundtrip_pins_shared_pages(small_lm):
    cfg, _ = small_lm
    pool = _pool(cfg, fast=8, peer=12, host=12)
    t = pool.table
    ps = pool.page_size
    swap = KVSwapManager(pool, reserve_fraction=0.8)
    tokens = list(range(1, 1 + 2 * ps))
    donor: list = []
    t.grow(donor, 2)
    for i, p in enumerate(donor):
        pool.k_pool = pool.k_pool.at[:, p].set(float(i + 1))
    t.register_prefix(tokens, donor, 2 * ps)
    victim: list = []
    t.match_prefix(tokens, victim)               # 2 shared pages
    t.grow(victim, 2)                            # + 2 exclusive pages
    for i in (2, 3):
        pool.k_pool = pool.k_pool.at[:, victim[i]].set(float(10 + i))
    shared_before = victim[:2]

    out_pages, secs = swap.swap_out(list(victim), table=t)
    assert out_pages[:2] == shared_before        # pinned in place
    assert out_pages[2] != victim[2] and out_pages[3] != victim[3]
    assert swap.parked_count(out_pages) == 2
    for i, p in enumerate(out_pages):            # refs followed the bytes
        assert t.ref[p] == (2 if i < 2 else 1)
    assert (np.asarray(pool.k_pool)[:, out_pages[2]] == 12.0).all()

    back, _ = swap.swap_in(out_pages, table=t)
    assert back[:2] == shared_before
    assert swap.parked_count(back) == 0
    assert swap.slots_free() == swap.reserved_total
    assert (np.asarray(pool.k_pool)[:, back[2]] == 12.0).all()
    assert (np.asarray(pool.k_pool)[:, back[3]] == 13.0).all()
    assert (np.asarray(pool.k_pool)[:, back[0]] == 1.0).all()
    t.release(back)
    t.release(donor)
    assert pool.free_count() + swap.reserved_total == pool.total_pages


def test_oversubscribed_shared_prefix_completes(small_lm):
    """Preemption under sharing: a pool that only fits the workload through
    both swap *and* prefix sharing completes with token-exact results."""
    cfg, params = small_lm
    ps = 4
    rng = np.random.default_rng(5)
    prefix = rng.integers(1, cfg.vocab_size, 3 * ps).tolist()
    prompts = [prefix + rng.integers(1, cfg.vocab_size, 2 + i).tolist()
               for i in range(5)]
    arrivals = [0.0] + [0.05 + 0.01 * i for i in range(4)]

    def run(fast, peer, host, swap_on):
        pool = _pool(cfg, fast=fast, peer=peer, host=host)
        swap = KVSwapManager(pool, reserve_fraction=0.9) if swap_on else None
        sched = RequestScheduler(pool, max_batch=4, prefill_token_budget=24,
                                 default_max_new=12, swap=swap)
        eng = ServeEngine(cfg, params, pool, scheduler=sched,
                          wall_clock=False, sim_step_s=0.01)
        for p, a in zip(prompts, arrivals):
            eng.submit(list(p), arrival_s=a)
        _drain(eng)
        assert len(eng.finished) == len(prompts)
        return ({s.sid: s.tokens for s in eng.finished},
                pool.telemetry.swap_outs, pool.table.prefix_hit_pages)

    ref, _, _ = run(64, 16, 16, swap_on=False)       # roomy baseline
    got, swaps, hits = run(8, 10, 22, swap_on=True)   # pressured + shared
    assert swaps > 0 and hits > 0                # both mechanisms engaged
    assert got == ref


# ---------------------------------------------------------------------------
# property test: random share/fork/swap/free interleavings
# ---------------------------------------------------------------------------

@given(st.lists(st.tuples(st.integers(0, 3), st.integers(0, 5)),
                min_size=1, max_size=24),
       st.integers(min_value=0, max_value=2 ** 31 - 1))
@settings(max_examples=15, deadline=None)
def test_random_share_fork_swap_interleavings(ops, seed):
    """Random interleavings of share / CoW-fork / swap round-trip / release
    never cross-wire contents, leak pages, or corrupt refcounts."""
    cfg = dataclasses.replace(registry.get_smoke_config("qwen2-0.5b"),
                              num_layers=1, compute_dtype="float32")
    pool = _pool(cfg, fast=10, peer=12, host=14)
    t = pool.table
    ps = pool.page_size
    swap = KVSwapManager(pool, reserve_fraction=0.7)
    rng = np.random.default_rng(seed)
    # a small pool of recurring token streams: identical streams are what
    # makes match_prefix actually share pages between views
    streams = [[int(x) for x in rng.integers(1, 10 ** 6, 2 * ps)]
               for _ in range(3)]
    views: list[dict] = []
    next_fill = [1.0]

    def fill_page(pid, val):
        pool.k_pool = pool.k_pool.at[:, pid].set(val)

    def new_view():
        n = int(rng.integers(1, 3))
        tokens = streams[int(rng.integers(len(streams)))][:n * ps]
        pages: list = []
        matched = t.match_prefix(tokens, pages) // ps
        content = [None] * n
        for b in range(matched):
            content[b] = None                    # resolved via donor below
        for b in range(matched, n):
            t.append_page(pages)
            v = next_fill[0]
            next_fill[0] += 1.0
            fill_page(pages[b], v)
            content[b] = v
        # matched blocks inherit the registered content values
        for b in range(matched):
            content[b] = float(np.asarray(pool.k_pool)[0, pages[b], 0, 0, 0])
        t.register_prefix(tokens, pages, n * ps)
        views.append({"pages": pages, "content": content, "parked": False})

    for op, which in ops:
        if op == 0 or not views:
            if pool.free_count() >= 3:
                new_view()
            continue
        s = views[which % len(views)]
        if op == 1 and not s["parked"]:          # CoW fork + private write
            idx = int(rng.integers(len(s["pages"])))
            if pool.free_count() < 1:
                continue
            t.fork_for_write(s["pages"], idx)
            v = next_fill[0]
            next_fill[0] += 1.0
            fill_page(s["pages"][idx], v)
            s["content"][idx] = v
        elif op == 2:                            # swap round-trip leg
            if s["parked"]:
                if pool.free_count() >= swap.parked_count(s["pages"]):
                    s["pages"], _ = swap.swap_in(s["pages"], table=t)
                    s["parked"] = False
            else:
                excl = len(t.exclusive(s["pages"]))
                if swap.can_swap_out(excl):
                    s["pages"], _ = swap.swap_out(s["pages"], table=t)
                    s["parked"] = True
        elif op == 3 and not s["parked"]:        # release
            t.release(s["pages"])
            views.remove(s)

    # invariants: contents intact, refcounts = holder counts, no leaks
    holder_counts: dict[int, int] = {}
    for s in views:
        for pid, val in zip(s["pages"], s["content"]):
            holder_counts[pid] = holder_counts.get(pid, 0) + 1
            got = np.asarray(pool.k_pool)[0, pid, 0, 0, 0]
            assert got == val, f"page {pid}: {got} != {val}"
    for pid, n in holder_counts.items():
        assert t.ref[pid] == n
    assert sum(t.ref.values()) == sum(len(s["pages"]) for s in views)
    live = len(t.ref)
    parked = sum(swap.parked_count(s["pages"]) for s in views)
    assert pool.free_count() + swap.reserved_total + live - parked \
        == pool.total_pages
