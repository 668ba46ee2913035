"""Placement runtime: policy registry, migration executor, domain arbiter,
telemetry, pool rebalancing, and the two-stage co-scheduled search."""

import dataclasses

import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import registry
from repro.core import interleave
from repro.core.dwp import CoScheduledTuner, DWPConfig
from repro.placement import policy as pol
from repro.placement.arbiter import DomainArbiter, DomainSpec, Priority
from repro.placement.executor import MigrationExecutor
from repro.placement.telemetry import DomainTelemetry, Ring
from repro.serve.kvcache import BwapPagePool, MemoryDomain


@pytest.fixture(scope="module")
def small_cfg():
    cfg = registry.get_smoke_config("qwen2-0.5b")
    return dataclasses.replace(cfg, num_layers=1, compute_dtype="float32")


def _ctx(bws=(819.0, 50.0, 16.0), pages=1000, workers=(0,), dwp=0.0,
         caps=None):
    return pol.PlacementContext(
        bandwidths=np.asarray(bws), num_pages=pages, workers=workers,
        dwp=dwp, capacities=None if caps is None else np.asarray(caps))


def _pool(cfg, pages=64, page_size=4, **kw):
    domains = [
        MemoryDomain("hbm_local", pages // 2, 819.0, True),
        MemoryDomain("hbm_peer", pages // 4, 50.0, False),
        MemoryDomain("host", pages - pages // 2 - pages // 4, 16.0, False),
    ]
    return BwapPagePool(cfg, domains, page_size=page_size,
                        dwp_config=DWPConfig(n=4, c=1), **kw)


# -- policy registry ----------------------------------------------------------

def test_registry_has_all_four_policies():
    assert {"uniform", "bwap_canonical", "bwap_dwp",
            "local_first"} <= set(pol.available())
    with pytest.raises(KeyError):
        pol.get("no_such_policy")


def test_uniform_weights_are_equal():
    w = pol.weights("uniform", _ctx())
    np.testing.assert_allclose(w, 1 / 3)


def test_canonical_weights_proportional_to_bw():
    w = pol.weights("bwap_canonical", _ctx())
    bw = np.asarray([819.0, 50.0, 16.0])
    np.testing.assert_allclose(w, bw / bw.sum())


def test_bwap_dwp_matches_core_dwp_weights():
    ctx = _ctx(dwp=0.4)
    w = pol.weights("bwap_dwp", ctx)
    canon = interleave.normalize(np.asarray([819.0, 50.0, 16.0]))
    np.testing.assert_allclose(w, interleave.dwp_weights(canon, [0], 0.4))


def test_local_first_fills_fastest_then_spills():
    c = pol.get("local_first").counts(_ctx(pages=150, caps=(100, 100, 100)))
    np.testing.assert_array_equal(c, [100, 50, 0])


def test_counts_respect_capacity_and_total():
    ctx = _ctx(pages=1000, caps=(100, 600, 600))
    for name in pol.available():
        c = pol.get(name).counts(ctx)
        assert int(c.sum()) == 1000, name
        assert (c <= np.asarray([100, 600, 600])).all(), name


def test_counts_raise_when_capacity_exceeded():
    ctx = _ctx(pages=1000, caps=(100, 100, 100))
    for name in pol.available():
        with pytest.raises(ValueError):
            pol.get(name).counts(ctx)


def test_assign_fractions_follow_clamped_counts():
    ctx = _ctx(pages=1024, caps=(64, 2000, 2000))
    a = pol.assign("bwap_canonical", ctx)
    counts = np.bincount(a, minlength=3)
    assert counts[0] <= 64
    assert counts.sum() == 1024
    # overflow spilled toward the faster of the remaining domains
    assert counts[1] > counts[2]


# -- migration executor -------------------------------------------------------

def test_executor_matches_per_page_oracle():
    k = jnp.arange(2 * 16 * 3 * 2 * 4, dtype=jnp.float32).reshape(
        2, 16, 3, 2, 4)
    v = k * 2.0
    src = [0, 3, 5, 7]
    dst = [8, 9, 12, 15]
    ex = MigrationExecutor()
    (bk, bv), res = ex.execute((k, v), src, dst)
    (lk, lv), _ = ex.execute_looped((k, v), src, dst)
    assert jnp.array_equal(bk, lk) and jnp.array_equal(bv, lv)
    assert res.num_moves == 4
    # 2 arrays x 4 pages x (page bytes of one array)
    page_bytes = 2 * 3 * 2 * 4 * 4
    assert res.bytes_moved == 2 * 4 * page_bytes


def test_executor_empty_moves_is_noop():
    k = jnp.ones((1, 4, 2))
    ex = MigrationExecutor()
    (out,), res = ex.execute((k,), [], [])
    assert out is k and res.num_moves == 0


def test_executor_copy_across_pools():
    src_arr = jnp.arange(1 * 8 * 2, dtype=jnp.float32).reshape(1, 8, 2)
    dst_arr = jnp.zeros((1, 12, 2), jnp.float32)
    ex = MigrationExecutor()
    (out,), res = ex.copy((src_arr,), (dst_arr,), [1, 7], [0, 11])
    assert jnp.array_equal(out[:, 0], src_arr[:, 1])
    assert jnp.array_equal(out[:, 11], src_arr[:, 7])
    assert res.num_moves == 2


def test_executor_records_pair_telemetry():
    tel = DomainTelemetry(["a", "b"])
    ex = MigrationExecutor(telemetry=tel)
    k = jnp.ones((1, 8, 2))
    ex.execute((k,), [0, 1, 2], [4, 5, 6],
               src_domains=[0, 0, 0], dst_domains=[1, 1, 1])
    assert tel.migrations_out[0] == 3
    assert tel.migrations_in[1] == 3
    assert tel.bytes_moved > 0


# -- telemetry ----------------------------------------------------------------

def test_ring_overwrites_oldest():
    r = Ring(capacity=3)
    for x in [1.0, 2.0, 3.0, 4.0]:
        r.push(x)
    np.testing.assert_array_equal(r.values(), [2.0, 3.0, 4.0])
    assert r.last() == 4.0
    assert len(r) == 3


def test_telemetry_snapshot_counters():
    t = DomainTelemetry(["fast", "slow"], ring_capacity=8)
    t.record_alloc(0, 3)
    t.record_free(0, 1)
    t.record_migration(0, 1, pages=2, nbytes=256)
    t.record_latency(0.5)
    t.record_stall(1, 0.1)
    s = t.snapshot()
    assert s["domains"]["fast"]["allocs"] == 3
    assert s["domains"]["fast"]["migr_out"] == 2
    assert s["domains"]["slow"]["migr_in"] == 2
    assert s["domains"]["slow"]["bytes_in"] == 256
    assert s["latency_last_s"] == 0.5
    assert s["executed_moves"] == 2


# -- pool on the new runtime --------------------------------------------------

def test_pool_migrate_sequence_is_batched_and_conserves_pages(small_cfg):
    pool = _pool(small_cfg, pages=64)
    ids = [pool.alloc_page() for _ in range(12)]
    # stamp each page so we can track the physical copy
    for pid in ids:
        pool.k_pool = pool.k_pool.at[:, pid].set(float(pid))
    before_total = sum(len(f) for f in pool.free)
    # force a strong worker shift so migration actually moves pages
    pool.tuner.dwp = 1.0
    new_ids = pool.migrate_sequence(ids)
    assert len(new_ids) == len(ids)
    assert sum(len(f) for f in pool.free) == before_total
    for old, new in zip(ids, new_ids):
        np.testing.assert_allclose(np.asarray(pool.k_pool[:, new]),
                                   float(old))
    moved = sum(1 for o, n in zip(ids, new_ids) if o != n)
    tel = pool.telemetry.snapshot()
    assert tel["executed_moves"] == moved > 0


def test_pool_alloc_fallback_uses_precomputed_bw_order(small_cfg):
    pool = _pool(small_cfg, pages=16)
    assert pool._bw_order[0] == 0                    # fastest domain first
    # drain the worker domain; allocation must fall back by bandwidth order
    pool.free[0] = []
    pid = pool.alloc_page()
    assert pool.domain_of(pid) in (1, 2)


def test_pool_rebalance_grows_capacity_and_remaps(small_cfg):
    pool = _pool(small_cfg, pages=32)
    ids = [pool.alloc_page() for _ in range(10)]
    for pid in ids:
        pool.k_pool = pool.k_pool.at[:, pid].set(float(pid) + 1.0)
    id_map = pool.rebalance([24, 12, 12])
    assert pool.total_pages == 48
    assert [d.num_pages for d in pool.domains] == [24, 12, 12]
    for old in ids:
        new = int(id_map[old])
        assert new >= 0
        np.testing.assert_allclose(np.asarray(pool.k_pool[:, new]),
                                   float(old) + 1.0)
    live = sum(len(p) for p in pool.live_pages())
    assert live == 10
    assert sum(len(f) for f in pool.free) == 48 - 10
    # pool still allocates after the rebuild
    assert pool.domain_of(pool.alloc_page()) in (0, 1, 2)


def test_pool_rebalance_spills_overfull_domain(small_cfg):
    pool = _pool(small_cfg, pages=32)   # domain 0 has 16 pages
    ids = []
    while len(ids) < 12:                # fill domain 0 with >8 live pages
        pid = pool.free[0].pop() if pool.free[0] else None
        if pid is None:
            break
        ids.append(pid)
    id_map = pool.rebalance([8, 20, 4])  # domain 0 shrinks below its live set
    assert (id_map[np.asarray(ids)] >= 0).all()
    doms = [pool.domain_of(int(id_map[p])) for p in ids]
    assert sum(1 for d in doms if d == 0) == 8       # kept up to capacity
    assert all(d == 1 for d in doms if d != 0)       # spill to next-fastest


def test_pool_rebalance_raises_when_live_exceeds_capacity(small_cfg):
    pool = _pool(small_cfg, pages=32)
    for _ in range(20):
        pool.alloc_page()
    with pytest.raises(ValueError):
        pool.rebalance([4, 4, 4])


# -- K/V row writes in place ------------------------------------------------

# (layer, page ids, slots, rows span every layer, padded on the host) per
# write: as the decode step, a prefill over two chunks, a write padded to a
# power of two rows, the all-layer writes of the recompute prefill and the
# verify rollback, and writes split into blocks of ROW_BLOCK rows (set to 2
# there), the last block of five rows moved back over the one before.
ROW_WRITES = {
    "decode": [(2, [3, 9, 12], [1, 0, 3], False, False)],
    "prefill_two_chunks": [(1, [5] * 4, [0, 1, 2, 3], False, True),
                           (1, [11] * 3, [0, 1, 2], False, True)],
    "padded": [(0, [1, 4, 4, 8, 15], [3, 0, 1, 2, 3], False, True)],
    "all_layers": [(0, [2, 2, 7], [0, 1, 3], True, True)],
    "blocks": [(1, [6, 6, 9, 13, 2], [2, 3, 0, 1, 1], False, False),
               (0, [2, 2, 7, 7], [0, 1, 0, 1], True, False)],
}


@pytest.mark.parametrize("case", list(ROW_WRITES))
def test_pool_write_rows_in_place(small_cfg, monkeypatch, case):
    from repro.placement import pool as pool_mod
    if case == "blocks":
        monkeypatch.setattr(pool_mod, "ROW_BLOCK", 2)
    cfg = dataclasses.replace(small_cfg, num_layers=3)
    pool = _pool(cfg, pages=16)
    rng = np.random.default_rng(7)
    want_k = rng.standard_normal(pool.k_pool.shape).astype(np.float32)
    want_v = rng.standard_normal(pool.v_pool.shape).astype(np.float32)
    pool.k_pool, pool.v_pool = jnp.asarray(want_k.copy()), jnp.asarray(
        want_v.copy())
    handed = []                 # the pool arrays given to the donated write
    donated = pool_mod._write_rows_in_place

    def spy(*args, **kw):
        handed.append(args[:2])
        return donated(*args, **kw)
    monkeypatch.setattr(pool_mod, "_write_rows_in_place", spy)
    programs = 0
    for layer, pids, slots, every_layer, pad in ROW_WRITES[case]:
        n = len(pids)
        lead = (cfg.num_layers, n) if every_layer else (n,)
        row = pool.k_pool.shape[3:]
        k, v = (rng.standard_normal(lead + row).astype(np.float32)
                for _ in range(2))
        for i, (p, s) in enumerate(zip(pids, slots)):
            at = (slice(None), p, s) if every_layer else (layer, p, s)
            want_k[at] = k[..., i, :, :]
            want_v[at] = v[..., i, :, :]
        if pad:
            pids, slots, src = pool.pad_rows(pids, slots, np.arange(n))
            assert len(pids) == 1 << (n - 1).bit_length()
            k, v = (np.take(a, src, axis=a.ndim - 3) for a in (k, v))
        block = min(len(pids), pool_mod.ROW_BLOCK)
        programs += -(-len(pids) // block)
        args = (layer, np.asarray(pids, np.int32),
                np.asarray(slots, np.int32), jnp.asarray(k), jnp.asarray(v))
        pool.write_rows(*args)
    # written rows read back; every other row, padding's included, is as it
    # was
    np.testing.assert_array_equal(np.asarray(pool.k_pool), want_k)
    np.testing.assert_array_equal(np.asarray(pool.v_pool), want_v)
    assert len(handed) == programs
    assert all(a.is_deleted() for pair in handed for a in pair)
    assert pool.telemetry.snapshot()["kv_writes"] == {
        "in_place": len(ROW_WRITES[case]), "copied": 0}
    text = donated.lower(pool.k_pool, pool.v_pool, *args, 0,
                         block=block).compile().as_text()
    assert "{0}: (0, {}, may-alias)" in text
    assert "{1}: (1, {}, may-alias)" in text


def test_pool_write_copies_while_another_holder_keeps_the_pool(small_cfg):
    """Donating an array someone else still holds would delete it under
    them: that write copies, and the telemetry says so."""
    pool = _pool(small_cfg, pages=16)
    row = jnp.ones((1,) + pool.k_pool.shape[3:], jnp.float32)
    at = (np.asarray([3], np.int32), np.asarray([1], np.int32))
    held = pool.k_pool
    pool.write_rows(0, *at, row, row)
    assert not held.is_deleted() and not np.asarray(held).any()
    assert (np.asarray(pool.k_pool[0, 3, 1]) == 1).all()
    assert pool.telemetry.kv_writes_copied == 1
    del held
    pool.write_rows(0, *at, 2 * row, 2 * row)
    assert (np.asarray(pool.v_pool[0, 3, 1]) == 2).all()
    assert (pool.telemetry.kv_writes_in_place,
            pool.telemetry.kv_writes_copied) == (1, 1)


# -- two-stage co-scheduled search (paper §III-B3) ---------------------------

def _drive_cotuner(tuner, stall_a_of_dwp, stall_b_of_dwp, max_periods=60):
    periods = 0
    while not tuner.done and periods < max_periods:
        for _ in range(tuner.cfg.n):
            tuner.record(stall_a_of_dwp(tuner.dwp),
                         stall_b_of_dwp(tuner.dwp))
        periods += 1
    return tuner


def test_cotuner_stage1_freezes_bound_where_a_stabilises():
    canon = interleave.normalize(np.asarray([3.0, 2, 1, 1]))
    t = CoScheduledTuner(canon, workers_b=[0, 1], num_pages=1024)
    # A improves until B's DWP reaches 0.3, then flat
    _drive_cotuner(t, lambda d: max(1.0 - d, 0.7), lambda d: 1.0)
    assert t.stage == 2 or t.done
    assert t.dwp_lower_bound == pytest.approx(0.3, abs=t.cfg.x + 1e-9)


def test_cotuner_stage2_respects_floor_when_optimum_below():
    canon = interleave.normalize(np.asarray([3.0, 2, 1, 1]))
    t = CoScheduledTuner(canon, workers_b=[0, 1], num_pages=1024)
    # bound lands at ~0.4; B's own optimum is at 0.0 — floor must win
    _drive_cotuner(t, lambda d: max(1.0 - d, 0.6), lambda d: 1.0 + d)
    assert t.done
    assert t.dwp_lower_bound >= 0.4 - 1e-9
    assert t.dwp >= t.dwp_lower_bound - 1e-9


def test_cotuner_stage2_climbs_above_bound_when_beneficial():
    canon = interleave.normalize(np.asarray([3.0, 2, 1, 1]))
    t = CoScheduledTuner(canon, workers_b=[0, 1], num_pages=1024)
    # A stabilises immediately (bound ~0.1); B keeps improving with DWP
    _drive_cotuner(t, lambda d: 1.0, lambda d: 2.0 - d)
    assert t.done
    assert t.dwp == pytest.approx(1.0)
    assert t.dwp > t.dwp_lower_bound


# -- domain arbiter -----------------------------------------------------------

SPECS = [
    DomainSpec("hbm_local", 64, 819.0),
    DomainSpec("hbm_peer", 48, 50.0),
    DomainSpec("host", 64, 16.0),
]


def test_arbiter_partitions_capacity_and_homes(small_cfg):
    arb = DomainArbiter(SPECS, page_size=4)
    a = arb.register("A", small_cfg, priority=Priority.HIGH, share=0.5)
    b = arb.register("B", small_cfg, priority=Priority.BEST_EFFORT,
                     share=0.5)
    # disjoint quotas within every domain's budget
    totals = np.asarray([s.total_pages for s in SPECS])
    assert ((a.quotas + b.quotas) <= totals).all()
    assert (arb.free >= 0).all()
    # high-priority claimed the fastest domain; best-effort the next one
    assert a.home == (0,)
    assert b.home == (1,)
    assert b.cotuner is not None and a.cotuner is None
    # both tenants are views over ONE shared fabric pool; each view's
    # quota ledger caps what it can allocate
    assert a.view.pool is b.view.pool
    np.testing.assert_array_equal(a.view.quota, a.quotas)
    assert a.view.capacity() == int(a.quotas.sum())
    assert a.view.free_count() <= a.view.capacity()


def test_arbiter_runs_two_stage_search_from_latency_streams(small_cfg):
    arb = DomainArbiter(SPECS, page_size=4)
    arb.register("A", small_cfg, priority=Priority.HIGH, share=0.4)
    b = arb.register("B", small_cfg, priority=Priority.BEST_EFFORT,
                     share=0.4, dwp_config=DWPConfig(n=2, c=0))
    for _ in range(200):
        if b.cotuner.done:
            break
        d = b.dwp
        arb.observe("A", max(1.0 - 2 * d, 0.6))     # improves until d=0.2
        arb.observe("B", (d - 0.1) ** 2 + 1.0)      # optimum below the bound
    assert b.cotuner.done
    assert b.cotuner.dwp_lower_bound >= 0.2 - 1e-9
    assert b.dwp >= b.cotuner.dwp_lower_bound - 1e-9


def test_arbiter_observe_rehomes_view_sequences(small_cfg):
    """Cycle moves from the co-scheduled search re-home live pages through
    the view's assignment-change subscription — no attach_engine."""
    arb = DomainArbiter(SPECS, page_size=4)
    arb.register("A", small_cfg, priority=Priority.HIGH, share=0.4)
    b = arb.register("B", small_cfg, priority=Priority.BEST_EFFORT,
                     share=0.4, dwp_config=DWPConfig(n=2, c=0))
    seq = type("S", (), {})()
    seq.pages = []
    for _ in range(6):
        b.view.append_page(seq.pages)
    b.view.on_assignment_change(
        lambda: seq.__setattr__("pages", b.view.migrate(seq.pages)))
    moved_any = False
    for _ in range(40):
        arb.observe("A", 1.0 - 0.5 * b.dwp)         # keep stage 1 climbing
        moved_any |= arb.observe("B", 1.0)
        if b.dwp >= 0.5:
            break
    assert moved_any
    # pages were re-homed (valid ids, ledgers consistent) as B's DWP rose
    assert all(p < b.view.pool.total_pages for p in seq.pages)
    arb.fabric.check_invariants()
    b.view.release(seq.pages)
    arb.fabric.check_invariants()


def test_arbiter_pins_hottest_preambles(small_cfg):
    """The arbiter's pin selection ranks cross-tenant chains by
    refcount × heat and pins the winners into the persistence tier;
    re-selection refreshes the LRU stamp instead of duplicating pins."""
    from repro.obs.observatory import Observatory
    from repro.placement.persist import PersistentTier

    arb = DomainArbiter(SPECS, page_size=4)
    a = arb.register("A", small_cfg, priority=Priority.HIGH, share=0.4)
    b = arb.register("B", small_cfg, priority=Priority.BEST_EFFORT,
                     share=0.4)
    tier = PersistentTier(capacity_pages=64)
    arb.fabric.attach_persist(tier)
    obs = Observatory(arb.fabric, tracer=False, drift=False)

    def chain(toks, val):
        pages = []
        for i in range(len(toks) // 4):
            a.view.append_page(pages)
        a.view.register_prefix(list(toks), pages, len(toks))
        return pages

    cold = chain(list(range(100, 108)), 1)     # cross-tenant shared, cool
    hot = chain(list(range(200, 208)), 2)      # cross-tenant shared, hot
    private = chain(list(range(300, 308)), 3)  # only tenant A: ref 1
    shared_b = []
    for toks in (list(range(100, 108)), list(range(200, 208))):
        got = []
        assert b.view.probe_prefix(toks, got) == 8    # B shares: ref -> 2
        shared_b.append(got)
    for _ in range(5):
        obs.heat.touch(hot)

    keys = arb.pin_hot_preambles(top_k=1, min_ref=2)
    assert len(keys) == 1 and keys[0] in tier._pins
    assert tier.pinned_pages() == set(hot)     # heat broke the ref tie
    assert not (set(private) & tier.pinned_pages())
    stamp0 = tier._pins[keys[0]]["stamp"]
    assert arb.pin_hot_preambles(top_k=1, min_ref=2) == keys
    assert tier._pins[keys[0]]["stamp"] > stamp0   # touched, not re-pinned

    # with room for two, the cool shared chain joins; the private never does
    keys2 = arb.pin_hot_preambles(top_k=3, min_ref=2)
    assert tier.pinned_pages() == set(hot) | set(cold)
    assert len(keys2) == 2
    arb.fabric.check_invariants()


def test_arbiter_unregister_redistributes_quota(small_cfg):
    """Tenant leave is pure ledger arithmetic on the shared fabric: the
    survivor's quota grows in place — no pool rebuild, no id remapping,
    live pages untouched."""
    arb = DomainArbiter(SPECS, page_size=4)
    a = arb.register("A", small_cfg, priority=Priority.HIGH, share=0.5)
    b = arb.register("B", small_cfg, priority=Priority.BEST_EFFORT,
                     share=0.5)
    seq = type("S", (), {})()
    seq.pages = []
    for _ in range(5):
        a.view.append_page(seq.pages)
    pages_before = list(seq.pages)
    quota_before = a.quotas.copy()
    b_quota = b.quotas.copy()
    grants = arb.unregister("B")
    np.testing.assert_array_equal(a.quotas, quota_before + grants["A"])
    np.testing.assert_array_equal(grants["A"], b_quota)   # sole survivor
    np.testing.assert_array_equal(a.view.quota, a.quotas)
    assert seq.pages == pages_before                # live pages untouched
    assert "B" not in arb.tenants
    assert "B" not in arb.fabric.views
    # all freed capacity went to the sole survivor...
    assert (arb.free == 0).all()
    # ...and B's home domain is claimable again
    assert 1 not in arb._claimed_homes
    arb.fabric.check_invariants()


def test_arbiter_interference_tracks_foreign_residency(small_cfg):
    arb = DomainArbiter(SPECS, page_size=4)
    arb.register("A", small_cfg, priority=Priority.HIGH, share=0.4)
    b = arb.register("B", small_cfg, priority=Priority.BEST_EFFORT,
                     share=0.4)
    base = arb.interference("A")
    # push B pages onto A's home domain (domain 0): allocate until the
    # view ledger shows residency there
    pages = []
    while int(b.view.used_pages()[0]) < 4:
        b.view.append_page(pages)
    assert arb.interference("A") > base
    b.view.release(pages)


# -- checkpoint staging through the registry ---------------------------------

def test_ckpt_plan_staging_spreads_bytes_by_bandwidth():
    from repro.checkpoint.ckpt import StagingTier, plan_staging
    tiers = [StagingTier("host", 16.0, 1 << 34),
             StagingTier("peer", 4.0, 1 << 34)]
    plan = plan_staging([10 << 20, 30 << 20], tiers)
    total = sum(plan["tiers"].values())
    assert total == 40 << 20
    # canonical split ∝ bandwidth: host gets ~4x the peer bytes
    assert plan["tiers"]["host"] > 3 * plan["tiers"]["peer"]
    assert plan["drain_time_s"] > 0


def test_ckpt_manager_records_staging_plan(tmp_path):
    import json

    from repro.checkpoint.ckpt import CheckpointManager, StagingTier
    mgr = CheckpointManager(tmp_path, staging_tiers=[
        StagingTier("host", 16.0, 1 << 34),
        StagingTier("nvme", 2.0, 1 << 34)])
    tree = {"w": np.ones((64, 64), np.float32)}
    mgr.save(3, tree)
    manifest = json.loads(
        (tmp_path / "step_0000000003" / "manifest.json").read_text())
    staging = manifest["staging"]
    assert set(staging["tiers"]) == {"host", "nvme"}
    assert staging["policy"] == "bwap_canonical"
    step, restored = mgr.restore(like=tree)
    assert step == 3
    np.testing.assert_array_equal(restored["w"], tree["w"])


def test_ckpt_staging_overflow_does_not_abort_save(tmp_path):
    import json

    from repro.checkpoint.ckpt import CheckpointManager, StagingTier
    mgr = CheckpointManager(tmp_path, staging_tiers=[
        StagingTier("tiny", 16.0, 2 << 20)])       # 2 MiB < leaf size
    tree = {"w": np.ones((1024, 1024), np.float32)}
    mgr.save(1, tree)                              # must still publish
    manifest = json.loads(
        (tmp_path / "step_0000000001" / "manifest.json").read_text())
    assert "error" in manifest["staging"]
    step, restored = mgr.restore(like=tree)
    assert step == 1
    np.testing.assert_array_equal(restored["w"], tree["w"])


# -- engine surfaces telemetry ------------------------------------------------

def test_engine_step_reports_telemetry(small_cfg):
    import jax

    from repro.models.lm import LM
    from repro.serve.engine import ServeEngine
    cfg = dataclasses.replace(small_cfg, num_layers=2)
    params = LM(cfg).init(jax.random.PRNGKey(0))
    pool = _pool(cfg, pages=64)
    eng = ServeEngine(cfg, params, pool, max_batch=2, max_new=3)
    eng.submit([3, 5, 7, 11])
    info = eng.step()
    tel = info["telemetry"]
    assert sum(d["allocs"] for d in tel["domains"].values()) > 0
    assert tel["latency_last_s"] > 0
