"""Memory-fabric API (ISSUE 5 / DESIGN.md §8): the single placement surface.

Covers the API boundary itself (grep-enforced: serve/scheduler modules only
touch FabricView, the attach back-channels are gone), per-view quota and
ownership ledgers, the cross-tenant read-only prefix tier, the swap-slot
loan broker (grant → use → reclaim with Eq.-1 accounting), Eq.-1
calibration, the reservation-aware occupancy fix, trie-aware admission, and
a hypothesis property test over random multi-tenant interleavings."""

import dataclasses
import pathlib
import re

import jax
import numpy as np
import pytest

from hypothesis import given, settings
from hypothesis import strategies as st

from repro.configs import registry
from repro.core import bwmodel
from repro.placement.arbiter import DomainArbiter, DomainSpec, Priority
from repro.placement.fabric import MemoryFabric, as_view
from repro.placement.pool import BwapPagePool, MemoryDomain
from repro.scheduler import KVSwapManager, RequestScheduler
from repro.serve.engine import ServeEngine

SRC = pathlib.Path(__file__).resolve().parent.parent / "src" / "repro"

SPECS = [
    DomainSpec("hbm_local", 64, 819.0),
    DomainSpec("hbm_peer", 48, 50.0),
    DomainSpec("host", 64, 16.0),
]


@pytest.fixture(scope="module")
def small_cfg():
    cfg = registry.get_smoke_config("qwen2-0.5b")
    return dataclasses.replace(cfg, num_layers=1, compute_dtype="float32")


@pytest.fixture(scope="module")
def small_lm(small_cfg):
    from repro.models.lm import LM
    params = LM(small_cfg).init(jax.random.PRNGKey(0))
    return small_cfg, params


def _domains(fast=32, peer=24, host=24):
    return [MemoryDomain("hbm_local", fast, 819.0, True),
            MemoryDomain("hbm_peer", peer, 50.0, False),
            MemoryDomain("host", host, 16.0, False)]


def two_views(cfg, *, share_prefix=True, quota_a=(24, 18, 18),
              quota_b=(8, 6, 6)):
    fab = MemoryFabric(cfg, _domains(), page_size=4, seed=0)
    a = fab.view("A", quota=quota_a, home=(0,), level=10,
                 share_prefix=share_prefix)
    b = fab.view("B", quota=quota_b, home=(1,), level=0,
                 share_prefix=share_prefix)
    return fab, a, b


# ---------------------------------------------------------------------------
# API boundary (grep-enforced acceptance criterion)
# ---------------------------------------------------------------------------

def test_serve_scheduler_layers_only_touch_fabric_views():
    """No serve/scheduler module imports the pool or page-table internals —
    all placement access goes through FabricView. The old compat shims
    (serve/kvcache.py, serve/pagetable.py) re-export only."""
    banned = re.compile(
        r"from repro\.placement\.(pool|pagetable) import"
        r"|from repro\.serve\.(kvcache|pagetable) import"
        r"|import repro\.placement\.(pool|pagetable)\b"
        r"|BwapPagePool\(")
    shims = {"kvcache.py", "pagetable.py"}
    for pkg in ("serve", "scheduler"):
        for f in sorted((SRC / pkg).glob("*.py")):
            if f.name in shims:
                text = f.read_text()
                assert "class " not in text and "def " not in text, \
                    f"{f} must stay a pure re-export shim"
                continue
            text = f.read_text()
            m = banned.search(text)
            assert m is None, f"{f} touches pool internals: {m.group(0)!r}"


def test_attach_backchannels_are_gone():
    """attach_engine / attach_pagetable / set_reserved_counts — the four
    subsystems' pairwise glue — are neither defined nor called anywhere in
    src/ (docstrings may still name them as the design they replaced)."""
    pat = re.compile(
        r"def (attach_engine|attach_pagetable|set_reserved_counts)\b"
        r"|\.(attach_engine|attach_pagetable|set_reserved_counts)\(")
    hits = [f"{f}: {m.group(0)}" for f in SRC.rglob("*.py")
            if (m := pat.search(f.read_text()))]
    assert not hits, f"back-channel survives: {hits}"


# ---------------------------------------------------------------------------
# ledgers: quota, ownership, adoption
# ---------------------------------------------------------------------------

def test_view_quota_caps_allocation(small_cfg):
    fab, a, b = two_views(small_cfg, quota_b=(2, 1, 1))
    pages = []
    for _ in range(4):                     # B's whole quota
        b.append_page(pages)
    assert b.free_count() == 0
    with pytest.raises(RuntimeError, match="quota exhausted"):
        b.append_page(pages)
    # A is unaffected by B's exhaustion
    other = []
    a.append_page(other)
    fab.check_invariants()
    b.release(pages)
    a.release(other)
    fab.check_invariants()
    assert not fab.owner and not fab.table.ref


def test_adopted_pool_matches_direct_driving(small_cfg):
    """as_view over a bare pool delegates placement to the pool's own
    cycle: allocation order is bit-identical to pool.alloc_page."""
    mk = lambda: BwapPagePool(small_cfg, _domains(), page_size=4, seed=0)
    direct, adopted = mk(), mk()
    view = as_view(adopted)
    assert as_view(adopted) is view        # cached, one fabric per pool
    got = []
    want = [direct.alloc_page() for _ in range(20)]
    pages = []
    for _ in range(20):
        got.append(view.append_page(pages))
    assert got == want
    view.fabric.check_invariants()


def test_ownership_follows_last_holder(small_cfg):
    """A page allocated by A but shared into B survives A's release with
    ownership (and the quota charge) moving to B."""
    fab, a, b = two_views(small_cfg)
    ps = fab.pool.page_size
    tokens = list(range(100, 100 + ps))
    pages_a = []
    a.append_page(pages_a)
    a.register_prefix(tokens, pages_a, ps)
    pages_b = []
    assert b.probe_prefix(tokens, pages_b) == ps
    pid = pages_b[0]
    assert fab.owner[pid] == "A"
    a.release(pages_a)
    assert fab.owner[pid] == "B"           # re-owned, not freed
    assert fab.table.ref[pid] == 1
    fab.check_invariants()
    b.release(pages_b)
    assert pid not in fab.table.ref
    fab.check_invariants()


# ---------------------------------------------------------------------------
# cross-tenant prefix tier
# ---------------------------------------------------------------------------

def test_cross_tenant_prefix_sharing_is_gated(small_cfg):
    ps = 4
    tokens = list(range(7, 7 + 2 * ps))

    def donor_and_probe(share):
        fab, a, b = two_views(small_cfg, share_prefix=share)
        pages_a = []
        a.append_page(pages_a)
        a.append_page(pages_a)
        a.register_prefix(tokens, pages_a, 2 * ps)
        pages_b = []
        matched = b.probe_prefix(tokens, pages_b)
        return fab, matched, pages_a, pages_b

    fab, matched, pages_a, pages_b = donor_and_probe(True)
    assert matched == 2 * ps               # opted in: full cross-match
    assert pages_b == pages_a              # same physical pages
    assert fab.cross_shared_pages() == 2
    fab.check_invariants()

    fab, matched, _, pages_b = donor_and_probe(False)
    assert matched == 0 and not pages_b    # opted out: tier closed
    assert fab.cross_shared_pages() == 0


def test_share_events_fire_on_cross_tenant_match(small_cfg):
    fab, a, b = two_views(small_cfg)
    events = []
    fab.subscribe("share", lambda **kw: events.append(kw))
    ps = fab.pool.page_size
    tokens = list(range(50, 50 + ps))
    pages_a = []
    a.append_page(pages_a)
    a.register_prefix(tokens, pages_a, ps)
    pages_b = []
    b.probe_prefix(tokens, pages_b)
    assert [e for e in events if e.get("kind") == "prefix"
            and e["owner"] == "A" and e["view"] == "B"]


# ---------------------------------------------------------------------------
# swap-slot loans: grant -> use -> reclaim
# ---------------------------------------------------------------------------

def test_loan_cycle_grant_use_reclaim(small_cfg):
    fab, a, b = two_views(small_cfg, quota_a=(20, 16, 16),
                          quota_b=(10, 8, 8))
    swap_a = KVSwapManager(a, reserve_fraction=0.5)      # idle lender
    swap_b = KVSwapManager(b, reserve_pages={"host": 2})
    lender_free = swap_a.slots_free()
    assert b.borrowable() > 0
    # s1 fits B's own 2 slots; s2 (3 pages) must borrow 3 from A
    s1, s2 = [], []
    for _ in range(2):
        b.append_page(s1)
    for _ in range(3):
        b.append_page(s2)
    fab.pool.k_pool = fab.pool.k_pool.at[:, s2].set(7.25)
    p1, _ = swap_b.swap_out(list(s1))
    assert swap_a.slots_free() == lender_free            # no loan yet
    assert swap_b.can_swap_out(3)
    p2, _ = swap_b.swap_out(list(s2))
    assert swap_a.slots_free() == lender_free - 3        # grant
    loan = fab.loans[0]
    assert (loan.lender, loan.borrower) == ("A", "B")
    assert loan.granted == 3 and len(loan.slots) == 3
    fab.check_invariants()
    # use: parked KV sits in borrowed slots
    assert sum(1 for p in p2 if p in swap_b._borrowed) > 0
    # s1 swaps back in: B's own slots are free again
    s1b, _ = swap_b.swap_in(p1)
    # reclaim while s2 is parked: B vacates a loaned slot by relocating
    # the bytes into its own reservation (one copy, Eq.-1 accounted)
    got, secs = a.recall_loans(1)
    assert got == 1 and secs > 0.0
    assert loan.reclaimed == 1 and loan.reclaim_seconds == secs
    assert len(loan.slots) == 2
    fab.check_invariants()
    # ...and s2 still swaps in bit-intact through the forwarding map
    s2b, _ = swap_b.swap_in(p2)
    assert (np.asarray(fab.pool.k_pool)[:, s2b] == 7.25).all()
    fab.check_invariants()
    # idle loaned slots return instantly on recall
    got, secs = a.recall_loans(99)
    assert got == 2 and secs == 0.0
    assert not loan.slots and swap_a.slots_free() == lender_free
    b.release(s1b)
    b.release(s2b)
    fab.check_invariants()


def test_loans_respect_lend_optout(small_cfg):
    fab, a, b = two_views(small_cfg)
    KVSwapManager(a, reserve_fraction=0.5, lend=False)
    KVSwapManager(b, reserve_pages={"host": 1})
    assert b.borrowable() == 0
    assert fab.request_loan(b, 4) == 0


# ---------------------------------------------------------------------------
# Eq.-1 calibration (ROADMAP real-machine calibration)
# ---------------------------------------------------------------------------

def test_calibrate_ewma_tracks_measured_transfer_times(small_cfg):
    fab = MemoryFabric(small_cfg, _domains(), page_size=4, seed=0,
                       calibration_alpha=0.5)
    view = fab.view("t", quota=(8, 8, 8), home=(0,))
    pages = []
    for _ in range(3):
        view.append_page(pages)
    analytic = view.stall_cost(pages)
    assert analytic == pytest.approx(bwmodel.stall_cost(
        view.footprint(pages), np.asarray([819.0, 50.0, 16.0])))
    # the machine is 10x slower than the analytic profile says: feed
    # measured seconds-per-page samples until the EWMA converges
    measured = [10 * fab.pool.page_bytes / (bw * 1e9)
                for bw in (819.0, 50.0, 16.0)]
    prev = analytic
    for _ in range(12):
        fab.calibrate(measured)
        cur = view.stall_cost(pages)
        assert cur >= prev - 1e-18         # EWMA approaches monotonically
        prev = cur
    assert view.stall_cost(pages) == pytest.approx(10 * analytic, rel=0.01)
    # None skips a domain; partial samples only move their own domain
    bw_before = fab.bw_effective.copy()
    fab.calibrate([None, measured[1], None])
    assert fab.bw_effective[0] == bw_before[0]
    assert fab.bw_effective[2] == bw_before[2]
    # swap transfer estimates read the calibrated bandwidths too: one page
    # read from domain 1, written to (slower) domain 2 — Eq.-1 takes the
    # slower side under the *effective* bandwidths
    sw = KVSwapManager(view, reserve_fraction=0.2)
    assert sw._transfer_seconds([1], [2]) == pytest.approx(
        fab.pool.page_bytes / (fab.bw_effective[2] * 1e9), rel=1e-6)


# ---------------------------------------------------------------------------
# occupancy regression (reserved slots are not free headroom)
# ---------------------------------------------------------------------------

def test_occupancy_counts_reserved_pages_per_domain(small_cfg):
    pool = BwapPagePool(small_cfg, _domains(peer=20), page_size=4)
    # reserving alone is not utilization: occupancy stays zero
    pool.reserve_pages(1, 10)
    assert pool.occupancy()["hbm_peer"] == 0.0
    assert pool.used_pages()[1] == 0
    # fill everything the domain can still allocate: occupancy must read
    # 1.0 — the old num_pages denominator reported 0.5 free headroom on a
    # domain with nothing left, and capacity readers over-allocated into it
    taken = [pool.free[1].pop() for _ in range(len(pool.free[1]))]
    assert pool.occupancy()["hbm_peer"] == 1.0
    assert pool.used_pages()[1] == len(taken)


# ---------------------------------------------------------------------------
# trie-aware admission (ROADMAP)
# ---------------------------------------------------------------------------

def test_trie_aware_admission_admits_shared_prefix_concurrently(small_lm):
    """Conservative admission bounds a request by its *physical* remaining
    footprint: trie-shared pages are already resident, so a second
    same-prefix request joins the batch even though the pair's logical
    worst case (2 x 11 = 22 pages) exceeds the 16-page pool. With sharing
    off, the identical trace serializes — the second request stays queued
    until the first finishes."""
    cfg, params = small_lm
    ps = 4
    prefix = list(range(1, 1 + 8 * ps))    # 8 pages of shared prompt

    def run(reuse: bool):
        pool = BwapPagePool(cfg, _domains(fast=8, peer=4, host=4),
                            page_size=ps)
        sched = RequestScheduler(pool, max_batch=2, default_max_new=8,
                                 conservative_admission=True)
        eng = ServeEngine(cfg, params, pool, scheduler=sched,
                          wall_clock=False, sim_step_s=0.001,
                          prefix_reuse=reuse)
        eng.submit(prefix + [7, 7])
        eng.submit(prefix + [9, 9])
        peak_running = steps = 0
        while (eng.active or eng.waiting) and steps < 200:
            eng.step()
            steps += 1
            peak_running = max(peak_running, len(eng.scheduler.running))
        assert len(eng.finished) == 2
        return peak_running, steps, pool

    concurrent, steps_on, pool = run(True)
    assert concurrent == 2                 # physically fits: batched
    assert pool.table.prefix_hit_pages >= 8
    serialized, steps_off, _ = run(False)
    assert serialized == 1                 # logical worst case: queued
    assert steps_on < steps_off


# ---------------------------------------------------------------------------
# multi-tenant property test: alloc/share/loan/reclaim/migrate
# ---------------------------------------------------------------------------

@given(st.lists(st.tuples(st.integers(0, 5), st.integers(0, 10 ** 6)),
                min_size=1, max_size=40),
       st.integers(min_value=0, max_value=2 ** 31 - 1))
@settings(max_examples=25, deadline=None)
def test_fabric_invariants_under_random_interleavings(ops, seed):
    """Random multi-tenant interleavings of alloc / cross-tenant share /
    swap (loan) / reclaim / migrate / release hold the fabric invariants
    after every operation: refcounts == view holds, per-domain ledgers ==
    ownership map, page ids conserved — and unregister leaks nothing."""
    cfg = dataclasses.replace(registry.get_smoke_config("qwen2-0.5b"),
                              num_layers=1, compute_dtype="float32")
    fab = MemoryFabric(cfg, _domains(), page_size=4, seed=0)
    views = {
        "A": fab.view("A", quota=(12, 9, 9), home=(0,)),
        "B": fab.view("B", quota=(12, 9, 9), home=(1,)),
    }
    swaps = {n: KVSwapManager(v, reserve_pages={"host": 2})
             for n, v in views.items()}
    rng = np.random.default_rng(seed)
    ps = fab.pool.page_size
    streams = {g: list(range(1000 * (g + 1), 1000 * (g + 1) + 3 * ps))
               for g in range(3)}
    seqs = []                              # {view, pages, parked}

    def pick_view():
        return "A" if rng.integers(2) == 0 else "B"

    for op, arg in ops:
        name = pick_view()
        v, sw = views[name], swaps[name]
        mine = [s for s in seqs if s["view"] == name]
        if op == 0:                        # alloc a fresh sequence
            if v.free_count() < 3:
                continue
            pages = []
            v.grow(pages, int(rng.integers(1, 4)))
            seqs.append({"view": name, "pages": pages, "parked": False})
        elif op == 1:                      # share: probe + register prefix
            toks = streams[arg % 3]
            if v.free_count() < 3:
                continue
            pages = []
            matched = v.probe_prefix(toks, pages) // ps
            for _ in range(matched, 3):
                v.append_page(pages)
            v.register_prefix(toks, pages, 3 * ps)
            seqs.append({"view": name, "pages": pages, "parked": False})
        elif op == 2 and mine:             # swap out (may borrow slots)
            s = mine[arg % len(mine)]
            if s["parked"]:
                continue
            excl = len(v.exclusive(s["pages"]))
            if excl and sw.can_swap_out(excl):
                s["pages"], _ = sw.swap_out(s["pages"])
                s["parked"] = True
        elif op == 3 and mine:             # swap in / lender reclaim
            s = mine[arg % len(mine)]
            if s["parked"]:
                if v.free_count() >= sw.parked_count(s["pages"]):
                    s["pages"], _ = sw.swap_in(s["pages"])
                    s["parked"] = False
            else:
                v.recall_loans(int(rng.integers(1, 4)))
        elif op == 4 and mine:             # migrate live pages
            s = mine[arg % len(mine)]
            if not s["parked"]:
                s["pages"] = v.migrate(s["pages"])
        elif op == 5 and mine:             # release
            s = mine[arg % len(mine)]
            if not s["parked"]:
                v.release(s["pages"])
                seqs.remove(s)
        fab.check_invariants()

    # unregister B: drain it first — live sequences release, parked ones
    # swap in when capacity allows and otherwise discard in place
    # (release_parked), then the fabric closes B's swap manager (loans
    # settle, reservation returns) as part of unregister
    for s in [s for s in seqs if s["view"] == "B"]:
        if s["parked"]:
            if views["B"].free_count() >= swaps["B"].parked_count(
                    s["pages"]):
                s["pages"], _ = swaps["B"].swap_in(s["pages"])
                s["parked"] = False
            else:
                live = swaps["B"].release_parked(s["pages"])
                views["B"].release(live)
                seqs.remove(s)
                continue
        views["B"].release(s["pages"])
        seqs.remove(s)
    fab.check_invariants()
    fab.unregister("B")
    # no cross-tenant page leaks: every live page is owned by A (or parked
    # by A's swap manager), none by the ghost tenant
    assert all(o == "A" for o in fab.owner.values())
    a_parked = set(swaps["A"].parked_ids())
    for pid in fab.table.ref:
        held = views["A"]._held.get(pid, 0)
        assert held > 0 or pid in a_parked, f"page {pid} leaked"
    assert not any(ln.slots for ln in fab.loans), "loan slots dangling"
