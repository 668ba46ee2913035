"""Speculative multi-token decode (ISSUE 4 / DESIGN.md §7): drafter
behavior, token-identity vs greedy, exact rollback of rejected speculation
(pool bytes, refcounts, free lists, allocation cycle), the Eq.-1
latency-signal regression fixes, and scheduler spec accounting."""

import dataclasses

import jax
import numpy as np
import pytest

from hypothesis import given, settings
from hypothesis import strategies as st

from repro.configs import registry
from repro.core.dwp import DWPConfig
from repro.scheduler import RequestScheduler
from repro.scheduler.scheduler import Request
from repro.serve.engine import ServeEngine
from repro.serve.kvcache import BwapPagePool, MemoryDomain
from repro.serve.spec import PromptLookupDrafter


@pytest.fixture(scope="module")
def small_lm():
    cfg = registry.get_smoke_config("qwen2-0.5b")
    cfg = dataclasses.replace(cfg, num_layers=1, compute_dtype="float32")
    from repro.models.lm import LM
    model = LM(cfg)
    params = model.init(jax.random.PRNGKey(0))
    return cfg, params


def _pool(cfg, fast=32, peer=16, host=16, page_size=4):
    domains = [
        MemoryDomain("hbm_local", fast, 819.0, True),
        MemoryDomain("hbm_peer", peer, 50.0, False),
        MemoryDomain("host", host, 16.0, False),
    ]
    return BwapPagePool(cfg, domains, page_size=page_size,
                        dwp_config=DWPConfig(n=10 ** 6, c=1))


def _drain(eng, cap=500):
    steps = 0
    while (eng.active or eng.waiting) and steps < cap:
        eng.step()
        steps += 1
    assert not eng.active and not eng.waiting, "engine did not drain"


def _state(pool):
    """Everything speculative rollback must leave bit-identical to greedy."""
    return (np.asarray(pool.k_pool).copy(), np.asarray(pool.v_pool).copy(),
            [list(f) for f in pool.free], dict(pool.table.ref),
            {nid: (n.parent, n.block, n.phys)
             for nid, n in pool.table._nodes.items()},
            pool._cycle_pos)


def _assert_states_equal(a, b):
    ak, av, afree, aref, atrie, acyc = a
    bk, bv, bfree, bref, btrie, bcyc = b
    assert (ak == bk).all(), "k_pool bytes differ from greedy"
    assert (av == bv).all(), "v_pool bytes differ from greedy"
    assert afree == bfree, "free lists differ from greedy"
    assert aref == bref, "refcounts differ from greedy"
    assert atrie == btrie, "trie nodes differ from greedy"
    assert acyc == bcyc, "allocation cycle position differs from greedy"


# ---------------------------------------------------------------------------
# drafter
# ---------------------------------------------------------------------------

def test_drafter_unrolls_runs_and_cycles():
    d = PromptLookupDrafter(max_tokens=4, max_ngram=3)
    # constant run: full-depth draft even when the recorded continuation is
    # one token long
    assert d.draft([7, 7, 7]) == [7, 7, 7, 7]
    # short cycle unrolls past the end of history
    assert d.draft([1, 2, 3, 1, 2, 3]) == [1, 2, 3, 1]
    # no repeated n-gram anywhere -> no proposal
    assert d.draft([1, 2, 3, 4, 5]) == []
    assert d.draft([9]) == []
    # deterministic
    toks = [4, 1, 4, 1, 4]
    assert d.draft(toks) == d.draft(list(toks))


def test_drafter_prefers_longest_ngram():
    d = PromptLookupDrafter(max_tokens=2, max_ngram=2)
    # 1-gram [2] would match position 1 (-> 9), but the 2-gram [1, 2]
    # matches earlier with continuation [5, ...]
    assert d.draft([1, 2, 5, 9, 1, 2]) == [5, 9]


# ---------------------------------------------------------------------------
# token identity + exact rollback vs greedy
# ---------------------------------------------------------------------------

LOOP_PROMPT = [5, 9, 3, 5, 9, 3, 5, 9, 3, 7]


def _run_engine(cfg, params, drafter, prompts, max_new=12, max_batch=4):
    pool = _pool(cfg)
    eng = ServeEngine(cfg, params, pool, max_batch=max_batch,
                      max_new=max_new, wall_clock=False, sim_step_s=0.001,
                      drafter=drafter)
    for p in prompts:
        eng.submit(list(p))
    _drain(eng)
    return eng, pool


def test_spec_token_identical_and_fewer_steps(small_lm):
    cfg, params = small_lm
    g_eng, _ = _run_engine(cfg, params, None, [LOOP_PROMPT], max_new=16)
    s_eng, s_pool = _run_engine(cfg, params,
                                PromptLookupDrafter(max_tokens=4),
                                [LOOP_PROMPT], max_new=16)
    assert g_eng.finished[0].tokens == s_eng.finished[0].tokens
    assert s_eng.decode_steps < g_eng.decode_steps
    sp = s_pool.telemetry.snapshot()["spec"]
    assert sp["accepted"] > 0
    assert s_eng.tokens_emitted == 16          # greedy + verify steps
    # verify steps emit their accepted drafts plus one bonus token each
    assert sp["emitted"] == sp["accepted"] + sp["steps"]
    assert sp["emitted"] <= s_eng.tokens_emitted


def test_spec_batch_token_identical(small_lm):
    """Mixed batch: drafting and non-drafting sequences verify together."""
    cfg, params = small_lm
    prompts = [LOOP_PROMPT, [2, 11, 2, 11, 2, 11, 4],
               [17, 23, 31, 40, 8]]          # last one: nothing to draft
    g_eng, _ = _run_engine(cfg, params, None, prompts, max_new=10)
    s_eng, _ = _run_engine(cfg, params, PromptLookupDrafter(max_tokens=3),
                           prompts, max_new=10)
    g = {s.sid: s.tokens for s in g_eng.finished}
    s = {s.sid: s.tokens for s in s_eng.finished}
    assert g == s


def test_spec_rollback_bit_identical_to_greedy(small_lm):
    """The tentpole guarantee: a speculative run leaves pool bytes, free
    lists, refcounts, trie, and the allocation cycle exactly where a greedy
    run leaves them — rejected speculation is invisible."""
    cfg, params = small_lm
    _, g_pool = _run_engine(cfg, params, None, [LOOP_PROMPT], max_new=16)
    _, s_pool = _run_engine(cfg, params, PromptLookupDrafter(max_tokens=4),
                            [LOOP_PROMPT], max_new=16)
    _assert_states_equal(_state(s_pool), _state(g_pool))


class ScriptedDrafter:
    """Proposes ``good`` greedy-consistent tokens then ``bad`` wrong ones,
    per call, from a fixed plan — drives every accept/reject boundary the
    rollback path has (``oracle`` = the greedy run's full token stream)."""

    def __init__(self, oracle, vocab, plan, max_tokens=6):
        self.oracle = list(oracle)
        self.vocab = vocab
        self.plan = list(plan)
        self.calls = 0
        self.max_tokens = max_tokens

    def draft(self, tokens):
        good, bad = self.plan[self.calls % len(self.plan)]
        self.calls += 1
        pos = len(tokens)
        assert self.oracle[:pos] == list(tokens), \
            "speculative run diverged from the greedy oracle"
        out = self.oracle[pos:pos + good]
        good = len(out)                       # oracle may run out near the end
        for i in range(bad):
            true = self.oracle[pos + good + i] \
                if pos + good + i < len(self.oracle) else 0
            out.append((true + 7) % self.vocab or 1)   # guaranteed mismatch
        return out[:self.max_tokens]


@given(st.lists(st.tuples(st.integers(0, 4), st.integers(0, 2)),
                min_size=1, max_size=8))
@settings(max_examples=10, deadline=None)
def test_spec_rollback_property(plan):
    """Random accept/reject prefixes over random draft lengths leave the
    pagetable (refcounts, trie nodes, free lists) and the pool bit-identical
    to having decoded the accepted tokens greedily (ISSUE 4)."""
    cfg = dataclasses.replace(registry.get_smoke_config("qwen2-0.5b"),
                              num_layers=1, compute_dtype="float32")
    from repro.models.lm import LM
    params = LM(cfg).init(jax.random.PRNGKey(0))
    g_eng, g_pool = _run_engine(cfg, params, None, [LOOP_PROMPT], max_new=12)
    oracle = list(g_eng.finished[0].tokens)
    drafter = ScriptedDrafter(oracle, cfg.vocab_size, plan)
    s_eng, s_pool = _run_engine(cfg, params, drafter, [LOOP_PROMPT],
                                max_new=12)
    assert s_eng.finished[0].tokens == oracle
    _assert_states_equal(_state(s_pool), _state(g_pool))


class AlwaysWrongDrafter:
    """Proposes tokens guaranteed to mismatch the model's argmax — every
    draft rejects, so every lookahead allocation must roll back."""

    max_tokens = 6

    def __init__(self, vocab):
        self.vocab = vocab

    def draft(self, tokens):
        # the engine never emits vocab-1 for these prompts (checked by the
        # oracle assertion in the tests below via token identity)
        return [self.vocab - 1] * self.max_tokens \
            if tokens[-1] != self.vocab - 1 else [1] * self.max_tokens


def test_spec_multiseq_all_rejected_restores_allocator(small_lm):
    """Two sequences speculating past page boundaries in one *mid-page*
    step (no kept pages): every allocation of the step is rejected, so the
    unwind — which must run in reverse batch order, the step's allocations
    being one stack across sequences — has to restore pool bytes, free
    lists, and the allocation cycle exactly. A forward unwind leaves the
    free lists permuted and the cycle advanced."""
    cfg, params = small_lm
    # targets 6 and 5: first decode writes land at positions 6 and 5 with
    # page_size 4 — both mid-page for two consecutive steps
    prompts = [[5, 9, 3, 5, 9, 3, 7], [2, 11, 2, 11, 2, 11]]

    def mk(drafter):
        pool = _pool(cfg)
        eng = ServeEngine(cfg, params, pool, max_batch=4, max_new=8,
                          wall_clock=False, sim_step_s=0.001,
                          drafter=drafter)
        for p in prompts:
            eng.submit(list(p))
        while len(eng.scheduler.running) < 2:   # drain prefill only
            eng.step()
        return eng, pool

    g_eng, g_pool = mk(None)
    s_eng, s_pool = mk(AlwaysWrongDrafter(cfg.vocab_size))
    _assert_states_equal(_state(s_pool), _state(g_pool))   # same start
    for _ in range(2):                          # both mid-page both steps
        g_eng.step()
        s_eng.step()
        assert [s.tokens for s in g_eng.scheduler.running] == \
            [s.tokens for s in s_eng.scheduler.running]
        _assert_states_equal(_state(s_pool), _state(g_pool))
    assert s_pool.telemetry.snapshot()["spec"]["drafted"] > 0
    assert s_pool.telemetry.snapshot()["spec"]["accepted"] == 0


def test_spec_multiseq_token_identical_and_leak_free(small_lm):
    """Several sequences accepting different amounts per step: page ids
    may permute vs greedy (kept lookahead pages pin the allocation cycle),
    but tokens are identical and every page is reclaimed."""
    cfg, params = small_lm
    prompts = [LOOP_PROMPT, [2, 11, 2, 11, 2, 11, 4], [8, 8, 8, 8, 8]]
    g_eng, _ = _run_engine(cfg, params, None, prompts, max_new=10)
    s_eng, s_pool = _run_engine(cfg, params,
                                PromptLookupDrafter(max_tokens=4),
                                prompts, max_new=10)
    assert {s.sid: s.tokens for s in g_eng.finished} == \
        {s.sid: s.tokens for s in s_eng.finished}
    assert s_pool.telemetry.snapshot()["spec"]["accepted"] > 0
    assert sum(len(f) for f in s_pool.free) == s_pool.total_pages
    assert not s_pool.table.ref


def test_spec_respects_max_new(small_lm):
    """Acceptance clamps at the token allowance: a deep draft near the end
    must not overshoot max_new (greedy produces exactly max_new tokens)."""
    cfg, params = small_lm
    g_eng, _ = _run_engine(cfg, params, None, [LOOP_PROMPT], max_new=3)
    s_eng, _ = _run_engine(cfg, params, PromptLookupDrafter(max_tokens=6),
                           [LOOP_PROMPT], max_new=3)
    assert s_eng.finished[0].produced == 3
    assert g_eng.finished[0].tokens == s_eng.finished[0].tokens


# ---------------------------------------------------------------------------
# Eq.-1 latency-signal regression tests
# ---------------------------------------------------------------------------

def test_eq1_read_set_includes_finishing_sequences(small_lm):
    """A sequence producing its final token was read by that decode step —
    its pages must be billed (the old expression dropped them, feeding the
    DWP tuner an underestimated stall signal on every completing step)."""
    cfg, params = small_lm
    pool = _pool(cfg)
    eng = ServeEngine(cfg, params, pool, max_batch=1, max_new=1,
                      wall_clock=False, sim_step_s=0.001)
    seen = []
    orig = eng.view.expected_read_time
    eng.view.expected_read_time = lambda pages: (seen.append(list(pages)),
                                                 orig(pages))[1]
    eng.submit([3, 17, 29, 5, 8])
    _drain(eng)
    assert len(eng.finished) == 1
    # the only decode step finished the sequence; its pages were billed
    decode_reads = [p for p in seen if p]
    assert decode_reads, "finishing step billed no pages (Eq.-1 regression)"
    assert len(decode_reads[-1]) == 2          # ceil(5/4) prompt pages + decode page


def test_eq1_read_set_dedups_shared_pages(small_lm):
    """Two sequences sharing a trie prefix bill each shared physical page
    once per step, not once per holder (Eq. 1 models resident bytes; the
    kernel reads each physical page once per launch)."""
    cfg, params = small_lm
    pool = _pool(cfg)
    eng = ServeEngine(cfg, params, pool, max_batch=2, max_new=4,
                      wall_clock=False, sim_step_s=0.001)
    seen = []
    orig = eng.view.expected_read_time
    eng.view.expected_read_time = lambda pages: (seen.append(list(pages)),
                                                 orig(pages))[1]
    prompt = [3, 17, 29, 5, 8, 2, 40, 11, 9]   # target 8 = 2 full pages
    eng.submit(list(prompt))
    eng.step()                                 # A prefills + registers
    eng.submit(list(prompt))                   # B matches A's prefix
    shared_seen = False
    for _ in range(30):
        if not (eng.active or eng.waiting):
            break
        both = len(eng.scheduler.running) == 2
        eng.step()
        if both and seen and seen[-1]:
            reads = seen[-1]
            assert len(reads) == len(set(reads)), \
                "shared trie pages double-billed in bytes_per_domain"
            shared_seen = True
    assert shared_seen
    # sharing actually happened (the dedup mattered): both requests matched
    assert pool.table.prefix_hit_pages >= 2


def test_request_equality_is_identity():
    """The hot-path membership fix: two field-identical requests are
    distinct; ``in``/``remove`` on request lists are pointer compares, not
    O(tokens) deep compares."""
    a = Request(sid=0, tokens=[1, 2], pages=[])
    b = Request(sid=0, tokens=[1, 2], pages=[])
    assert a != b and a == a
    assert a in [a] and a not in [b]
    assert len({a, b}) == 2                    # hashable again (identity)


# ---------------------------------------------------------------------------
# scheduler speculative accounting
# ---------------------------------------------------------------------------

def test_scheduler_spec_growth_need(small_lm):
    cfg, _ = small_lm
    pool = _pool(cfg)                          # page_size 4
    sched = RequestScheduler(pool, spec_tokens=4)
    # length 8, 2 pages: a verify step writes positions 8..12 -> needs
    # ceil(13/4) = 4 pages -> 2 fresh ones
    assert sched._seq_growth(8, [0, 1]) == 2
    # mid-page with room for the whole span
    assert sched._seq_growth(6, [0, 1]) == 1   # positions 6..10 -> 3 pages
    sched0 = RequestScheduler(pool)
    assert sched0._seq_growth(8, [0, 1]) == 1  # plain decode: one boundary page
    assert sched0._seq_growth(6, [0, 1]) == 0


def test_scheduler_spec_budget_charges_verify_tokens(small_lm):
    cfg, _ = small_lm
    pool = _pool(cfg, fast=64, peer=32, host=32)

    def first_chunk(spec_tokens):
        sched = RequestScheduler(pool.__class__(
            cfg, [MemoryDomain("hbm_local", 64, 819.0, True),
                  MemoryDomain("host", 32, 16.0, False)], page_size=4,
            dwp_config=DWPConfig(n=10 ** 6, c=1)),
            max_batch=4, prefill_token_budget=16,
            default_max_new=4, spec_tokens=spec_tokens)
        # one running sequence that will decode this step
        sched.submit(list(range(1, 6)))        # target 4 -> fits one chunk
        plan = sched.schedule()
        for r, lo, hi in plan.prefill_chunks:  # stand in for the engine
            r.length = hi
        assert len(sched.running) == 1
        # a long prompt now shares the step budget with the running decode
        sched.submit(list(range(1, 40)))
        plan = sched.schedule()
        return sum(hi - lo for _, lo, hi in plan.prefill_chunks)

    assert first_chunk(0) == 16                # full budget for prefill
    # one running sequence charges 1 + spec_tokens verify tokens first
    assert first_chunk(4) == 16 - 5


def test_scheduler_spec_footprint_margin(small_lm):
    cfg, _ = small_lm
    pool = _pool(cfg, fast=2, peer=2, host=2)  # 6 pages, page_size 4
    sched = RequestScheduler(pool, spec_tokens=0, default_max_new=4)
    sched.submit(list(range(1, 22)))           # 20 target + 4 new = 6 pages
    spec = RequestScheduler(pool, spec_tokens=4, default_max_new=4)
    with pytest.raises(ValueError):            # lookahead page doesn't fit
        spec.submit(list(range(1, 22)))


# ---------------------------------------------------------------------------
# fused (batched) incremental prefill
# ---------------------------------------------------------------------------

def test_fused_prefill_matches_recompute_oracle(small_lm):
    """Same-step chunks of different sequences fuse into one launch; tokens
    must equal the per-sequence recompute-oracle path bit-for-bit."""
    cfg, params = small_lm
    rng = np.random.default_rng(3)
    prompts = [rng.integers(1, cfg.vocab_size, n).tolist()
               for n in (19, 11, 7)]

    def run(incremental):
        pool = _pool(cfg, fast=64, peer=32, host=32)
        eng = ServeEngine(cfg, params, pool, max_batch=3, max_new=4,
                          wall_clock=False, sim_step_s=0.001,
                          incremental_prefill=incremental)
        # small budget: chunks of several sequences share steps
        eng.scheduler.prefill_token_budget = 8
        for p in prompts:
            eng.submit(list(p))
        _drain(eng)
        assert eng.prefill_chunks_run > len(prompts)   # chunking happened
        return {s.sid: s.tokens for s in eng.finished}

    assert run(True) == run(False)
