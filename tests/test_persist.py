"""Persistence tier under the fabric (DESIGN.md §9): Eq.-1 tier pricing,
cold demotion / promotion bit-exactness, the restart-surviving prefix
store, peer page export/import, tier telemetry, and the property test
over demote → restart → promote → free interleavings."""

from __future__ import annotations

import dataclasses
import pathlib
import re

import numpy as np
import pytest

from hypothesis import given, settings, strategies as st

from repro.configs import registry
from repro.core import bwmodel
from repro.core.dwp import DWPConfig
from repro.placement.fabric import as_view
from repro.placement.persist import (PersistentTier, deserialize_range,
                                     kv_layout_metadata, serialize_range)
from repro.placement.pool import BwapPagePool, MemoryDomain
from repro.scheduler import KVSwapManager

SRC = pathlib.Path(__file__).resolve().parent.parent / "src" / "repro"


@pytest.fixture(scope="module")
def cfg():
    return dataclasses.replace(registry.get_smoke_config("qwen2-0.5b"),
                               num_layers=2, compute_dtype="float32")


def _pool(cfg, fast=12, peer=12, host=16, page_size=4):
    return BwapPagePool(cfg, [
        MemoryDomain("hbm_local", fast, 819.0, True),
        MemoryDomain("hbm_peer", peer, 0.05, False),
        MemoryDomain("host", host, 0.016, False),
    ], page_size=page_size, dwp_config=DWPConfig(n=10 ** 6, c=1))


def _rig(cfg, **tier_kw):
    tier_kw.setdefault("bw_gbps", 0.008)
    tier_kw.setdefault("capacity_pages", 32)
    pool = _pool(cfg)
    view = as_view(pool)
    tier = PersistentTier(**tier_kw)
    view.fabric.attach_persist(tier)
    return pool, view, tier


def _fill(pool, pid, val):
    pool.k_pool = pool.k_pool.at[:, pid].set(float(val))
    pool.v_pool = pool.v_pool.at[:, pid].set(float(-val))


def _chain(view, pool, tokens, val):
    """Register one page-aligned prompt chain with recognizable bytes."""
    pages = []
    for i in range(len(tokens) // pool.page_size):
        view.append_page(pages)
        _fill(pool, pages[-1], val + i)
    view.register_prefix(list(tokens), pages, len(tokens))
    return pages


# ---------------------------------------------------------------------------
# Eq. 1 with the tier row
# ---------------------------------------------------------------------------

def test_stall_cost_tier_row():
    b, bw = np.array([8e9]), np.array([8.0])
    assert bwmodel.stall_cost(b, bw) == pytest.approx(1.0)
    # the tier is just one more (slow) row under the same max
    assert bwmodel.stall_cost(b, bw, tier_bytes=8e9, tier_bw_gbps=0.8) \
        == pytest.approx(10.0)
    # a fast tier row never dominates a slow domain row
    assert bwmodel.stall_cost(b, bw, tier_bytes=8e9, tier_bw_gbps=80.0) \
        == pytest.approx(1.0)
    # tier_bytes=0 keeps the pre-tier behaviour exactly
    assert bwmodel.stall_cost(b, bw, tier_bytes=0.0) == pytest.approx(1.0)
    with pytest.raises(AssertionError):
        bwmodel.stall_cost(b, bw, tier_bytes=1.0)       # no tier bandwidth


# ---------------------------------------------------------------------------
# demotion / promotion through the swap path
# ---------------------------------------------------------------------------

def test_demote_promote_bit_exact(cfg):
    pool, view, tier = _rig(cfg)
    swap = KVSwapManager(pool, reserve_fraction=0.5)
    pages = []
    for i in range(4):
        view.append_page(pages)
        _fill(pool, pages[-1], 10 + i)
    orig_k = np.asarray(pool.k_pool[:, pages]).copy()
    orig_v = np.asarray(pool.v_pool[:, pages]).copy()

    parked, _ = swap.swap_out(pages)
    demoted, secs = swap.demote_cold(2)
    assert demoted == 2 and secs > 0
    view.fabric.check_invariants()
    # handles are negative, never physical pages, and the admission path
    # counts them as promotable footprint while parked_count excludes them
    assert swap.demoted_count() == 2
    assert swap.promotable_count(parked) == 4
    assert swap.parked_count(parked) == 2
    assert sorted(tier.persisted_ids()) == sorted(
        h for h in (swap._resolve(p) for p in parked) if h < 0)

    back, _ = swap.swap_in(parked)
    view.fabric.check_invariants()
    assert np.array_equal(np.asarray(pool.k_pool[:, back]), orig_k)
    assert np.array_equal(np.asarray(pool.v_pool[:, back]), orig_v)
    assert tier.used_pages() == 0 and swap.demoted_count() == 0
    view.release(back)
    swap.close()
    view.fabric.check_invariants()


def test_demote_pricing_matches_eq1(cfg):
    """Demotion seconds equal Eq. 1 over {source domains} ∪ {tier row} —
    with the tier far slower than every slow domain, the tier row is the
    max: total_bytes / tier_bw."""
    pool, view, tier = _rig(cfg)
    swap = KVSwapManager(pool, reserve_fraction=0.5)
    pages = []
    for _ in range(3):
        view.append_page(pages)
    parked, _ = swap.swap_out(pages)
    n, secs = swap.demote_cold(3)
    assert n == 3
    expect = 3 * pool.page_bytes / (tier.bw_gbps * 1e9)
    assert secs == pytest.approx(expect)
    back, _ = swap.swap_in(parked)
    view.release(back)
    swap.close()


def test_demoted_page_dies_cold(cfg):
    """release_parked on a demoted page drops the tier bytes in place —
    no promotion copy, no leaked handle, empty tier at close."""
    pool, view, tier = _rig(cfg)
    swap = KVSwapManager(pool, reserve_fraction=0.5)
    pages = []
    for _ in range(2):
        view.append_page(pages)
    moved, _ = swap.swap_out(pages)
    swap.demote_cold(2)
    assert tier.used_pages() == 2
    live = swap.release_parked(moved)
    assert live == [] and tier.used_pages() == 0
    view.fabric.check_invariants()
    swap.close()


# ---------------------------------------------------------------------------
# restart-surviving prefix store
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("on_disk", [False, True])
def test_prefix_store_restart_roundtrip(cfg, tmp_path, on_disk):
    directory = tmp_path if on_disk else None
    pool, view, tier = _rig(cfg, directory=directory)
    tokens = list(range(500, 512))
    pages = _chain(view, pool, tokens, 40)
    orig_k = np.asarray(pool.k_pool[:, pages]).copy()
    assert tier.pin(view, tokens) is not None
    manifest = tier.export_prefixes(view)
    assert len(manifest["chains"]) == 1
    assert manifest["staging"]["drain_time_s"] > 0
    view.release(pages)
    tier.release_pins()
    view.fabric.check_invariants()

    if on_disk:
        store = tmp_path / "prefix_store"
        assert (store / "manifest.json").exists()
        # restart = a brand-new tier object bound to the same directory
        tier = PersistentTier(bw_gbps=0.008, capacity_pages=32,
                              directory=directory)
    else:
        tier.fabric = None                 # rebind the surviving object

    pool2 = _pool(cfg)
    view2 = as_view(pool2)
    view2.fabric.attach_persist(tier)
    restored, secs = tier.import_prefixes(view2)
    assert restored == 3 and secs > 0
    view2.fabric.check_invariants()
    got = []
    assert view2.probe_prefix(tokens, got) == 12
    assert np.array_equal(np.asarray(pool2.k_pool[:, got]), orig_k)
    view2.release(got)


def test_prefix_store_geometry_mismatch(cfg):
    pool, view, tier = _rig(cfg)
    pages = _chain(view, pool, list(range(300, 308)), 7)
    tier.pin(view, list(range(300, 308)))
    tier.export_prefixes(view)
    other = _pool(cfg, page_size=8)        # different geometry
    view8 = as_view(other)
    tier.fabric = None
    view8.fabric.attach_persist(tier)
    with pytest.raises(ValueError, match="geometry"):
        tier.import_prefixes(view8)


def test_prefix_store_quota_full_never_aborts(cfg):
    """A store bigger than the importing view's quota restores what fits
    and keeps the fabric consistent — never raises."""
    pool, view, tier = _rig(cfg)
    for i in range(3):                     # three 2-page chains, 6 pages
        toks = [1000 * (i + 1) + t for t in range(8)]
        _chain(view, pool, toks, 50 + 10 * i)
        tier.pin(view, toks)
    tier.export_prefixes(view)

    tiny = BwapPagePool(cfg, [MemoryDomain("hbm_local", 3, 819.0, True)],
                        page_size=4, dwp_config=DWPConfig(n=10 ** 6, c=1))
    tview = as_view(tiny)
    tier.fabric = None
    tview.fabric.attach_persist(tier)
    restored, _ = tier.import_prefixes(tview)
    assert restored == 2                   # first chain fits, second breaks
    tview.fabric.check_invariants()


def test_prefix_store_corruption_detected(cfg, tmp_path):
    pool, view, tier = _rig(cfg, directory=tmp_path)
    pages = _chain(view, pool, list(range(700, 708)), 3)
    tier.pin(view, list(range(700, 708)))
    tier.export_prefixes(view)
    victim = sorted((tmp_path / "prefix_store").glob("chain_*_k.npy"))[0]
    arr = np.load(victim)
    arr.flat[0] += 1.0
    np.save(victim, arr)
    fresh = PersistentTier(bw_gbps=0.008, capacity_pages=32,
                           directory=tmp_path)
    pool2 = _pool(cfg)
    view2 = as_view(pool2)
    view2.fabric.attach_persist(fresh)
    with pytest.raises(IOError, match="checksum"):
        fresh.import_prefixes(view2)


# ---------------------------------------------------------------------------
# peer page export / import
# ---------------------------------------------------------------------------

def test_peer_export_import_bit_exact(cfg):
    pool, view, tier = _rig(cfg)
    tokens = list(range(900, 912))
    pages = _chain(view, pool, tokens, 60)
    orig_k = np.asarray(pool.k_pool[:, pages]).copy()
    used_before = view.used.copy()

    blob = deserialize_range(serialize_range(tier.export_range(view, pages)))
    assert blob["layout"]["mesh_axes"] == {"data": 4, "model": 2}
    assert blob["ledger"]["bytes"] == len(pages) * pool.page_bytes

    poolB = _pool(cfg)
    viewB = as_view(poolB)
    tierB = PersistentTier(bw_gbps=0.008, capacity_pages=32)
    viewB.fabric.attach_persist(tierB)
    new_ids, secs = tierB.import_range(viewB, blob)
    assert secs > 0
    # bit-exact adoption, balanced ledgers on both fabrics
    assert np.array_equal(np.asarray(poolB.k_pool[:, new_ids]), orig_k)
    assert np.array_equal(view.used, used_before)      # exporter unchanged
    assert int(viewB.used.sum()) == len(new_ids)
    view.fabric.check_invariants()
    viewB.fabric.check_invariants()
    # the trie chain arrived under remapped ids: same prompt, new pages
    got = []
    assert viewB.probe_prefix(tokens, got) == 12
    assert got == new_ids
    viewB.release(got)
    viewB.release(new_ids)
    viewB.fabric.check_invariants()


def test_peer_import_rejects_tampered_blob(cfg):
    pool, view, tier = _rig(cfg)
    pages = _chain(view, pool, list(range(20, 28)), 5)
    blob = deserialize_range(serialize_range(tier.export_range(view, pages)))
    blob["k"] = blob["k"].copy()
    blob["k"].flat[0] += 1.0
    poolB = _pool(cfg)
    viewB = as_view(poolB)
    tierB = PersistentTier()
    viewB.fabric.attach_persist(tierB)
    with pytest.raises(IOError, match="checksum"):
        tierB.import_range(viewB, blob)


def test_kv_layout_metadata_defaults(cfg):
    meta = kv_layout_metadata(cfg, page_size=4)
    assert meta["mesh_axes"] == {"data": 4, "model": 2}
    assert meta["dp_axes"] == ["data"]
    assert meta["mp_axis"] == "model"
    assert len(meta["kv_pool_spec"]) == 5  # [L, page, slot, kv_head, dim]


# ---------------------------------------------------------------------------
# telemetry
# ---------------------------------------------------------------------------

def test_tier_telemetry_counters(cfg):
    pool, view, tier = _rig(cfg)
    swap = KVSwapManager(pool, reserve_fraction=0.5)
    pages = []
    for _ in range(2):
        view.append_page(pages)
    parked, _ = swap.swap_out(pages)
    swap.demote_cold(2)
    back, _ = swap.swap_in(parked)
    snap = pool.telemetry.snapshot()
    ops = snap["tiers"]["ops"]
    assert ops["demote"]["pages"] == 2 and ops["demote"]["seconds"] > 0
    assert ops["promote"]["pages"] == 2 and ops["promote"]["seconds"] > 0
    occ = snap["tiers"]["occupancy"]
    assert occ["pmem"]["used"] == 0 and occ["pmem"]["capacity"] == 32
    assert set(occ) >= {"fast_domains", "swap_slots", "pmem"}
    view.release(back)
    swap.close()


def test_restore_telemetry(cfg):
    pool, view, tier = _rig(cfg)
    toks = list(range(40, 48))
    _chain(view, pool, toks, 9)
    tier.pin(view, toks)
    tier.export_prefixes(view)
    tier.release_pins()
    pool2 = _pool(cfg)
    view2 = as_view(pool2)
    tier.fabric = None
    view2.fabric.attach_persist(tier)
    tier.import_prefixes(view2)
    ops = pool2.telemetry.snapshot()["tiers"]["ops"]
    assert ops["restore"]["pages"] == 2 and ops["restore"]["seconds"] > 0


# ---------------------------------------------------------------------------
# prefix-store capacity: LRU eviction among pins, surfaced skips
# ---------------------------------------------------------------------------

def test_prefix_store_lru_evicts_stalest_pin(cfg):
    """Over the byte cap, pinned chains are kept most-recently-touched
    first; the LRU loser is unpinned, counted, and emitted as ``evict``."""
    pool, view, tier = _rig(cfg, capacity_pages=4)   # room for 2 chains
    toks = [[1000 * (i + 1) + t for t in range(8)] for i in range(3)]
    keys = []
    for i in range(3):                               # stamps 1, 2, 3
        _chain(view, pool, toks[i], 10 * i)
        keys.append(tier.pin(view, toks[i]))
    tier.touch_pin(keys[0])                          # chain 0 now newest
    events = []
    view.fabric.subscribe("evict", lambda **kw: events.append(kw))

    manifest = tier.export_prefixes(view)
    kept = [tuple(ch["tokens"]) for ch in manifest["chains"]]
    assert kept == [tuple(toks[0]), tuple(toks[2])]  # stalest (1) evicted
    assert tier.evicted_chains == 1 and tier.skipped_chains == 0
    assert keys[1] not in tier._pins and keys[0] in tier._pins
    assert events == [{"view": view.name, "pages": 2, "chains": 1}]
    assert tier.stats()["evicted_chains"] == 1
    # the eviction shows up in the tier telemetry like any other tier op
    assert pool.telemetry.snapshot()["tiers"]["ops"]["evict"]["pages"] == 2
    view.fabric.check_invariants()


def test_prefix_store_capacity_skips_are_surfaced(cfg):
    """Unpinned shared chains rejected at the cap emit ``export_skip`` and
    are counted — in the tier and in the observatory — not dropped
    silently (pinned chains always outrank them)."""
    from repro.obs.observatory import Observatory

    pool, view, tier = _rig(cfg, capacity_pages=4)
    obs = Observatory(pool, tracer=False, drift=False)
    held = []
    for i in range(3):                     # three shared (ref-2) chains
        toks = [2000 * (i + 1) + t for t in range(8)]
        _chain(view, pool, toks, 20 + i)
        got = []
        view.probe_prefix(toks, got)       # second reader: ref -> 2
        held.append(got)
    events = []
    view.fabric.subscribe("export_skip", lambda **kw: events.append(kw))

    manifest = tier.export_prefixes(view)
    assert len(manifest["chains"]) == 2
    assert tier.skipped_chains == 1 and tier.evicted_chains == 0
    assert events == [{"view": view.name, "pages": 2, "chains": 1}]
    assert tier.stats()["skipped_chains"] == 1
    assert obs.metrics.get("repro_tier_export_skips_total").value(
        view.name) == 1
    for got in held:
        view.release(got)
    view.fabric.check_invariants()


# ---------------------------------------------------------------------------
# PR-5 shim retirement (grep-enforced)
# ---------------------------------------------------------------------------

def test_no_internal_shim_imports():
    """Nothing under src/repro imports through the serve/kvcache or
    serve/pagetable compat shims — internal code goes to the placement
    package (or the fabric); the shims exist for external callers only."""
    pat = re.compile(r"from repro\.serve\.(kvcache|pagetable) import"
                     r"|import repro\.serve\.(kvcache|pagetable)\b")
    hits = [f"{f}: {m.group(0)}" for f in sorted(SRC.rglob("*.py"))
            if (m := pat.search(f.read_text()))]
    assert not hits, f"internal shim import survives: {hits}"
    # ...while the external paths keep working
    from repro.serve.kvcache import BwapPagePool as compat_pool
    from repro.serve.pagetable import PageTable as compat_table  # noqa: F401
    assert compat_pool is BwapPagePool


# ---------------------------------------------------------------------------
# property tests
# ---------------------------------------------------------------------------

@settings(max_examples=15, deadline=None)
@given(st.lists(st.tuples(st.sampled_from(["out", "demote", "in", "free"]),
                          st.integers(min_value=0, max_value=2)),
                max_size=14))
def test_property_demote_interleavings(ops):
    """Random park → demote → promote → free interleavings against a
    never-demoted oracle fabric: invariants hold after every op, surviving
    K/V is bit-identical, and the ledgers drain to zero."""
    cfg = dataclasses.replace(registry.get_smoke_config("qwen2-0.5b"),
                              num_layers=1, compute_dtype="float32")
    pool, view, tier = _rig(cfg)
    opool = _pool(cfg)
    oview = as_view(opool)
    swap = KVSwapManager(pool, reserve_fraction=0.5)
    oswap = KVSwapManager(opool, reserve_fraction=0.5)

    seqs, oseqs, state = [], [], []
    # a demoted slot parks the next swap-out's KV; holders learn the
    # handle from the remap, as the scheduler does
    view.on_page_remap(lambda moves: seqs.__setitem__(
        slice(None), [[moves.get(p, p) for p in s] for s in seqs]))
    for s in range(3):
        pages, opages = [], []
        for i in range(2):
            view.append_page(pages)
            oview.append_page(opages)
            _fill(pool, pages[-1], 10 * s + i)
            _fill(opool, opages[-1], 10 * s + i)
        seqs.append(pages)
        oseqs.append(opages)
        state.append("live")

    for act, s in ops:
        if act == "out" and state[s] == "live":
            seqs[s], _ = swap.swap_out(seqs[s])
            oseqs[s], _ = oswap.swap_out(oseqs[s])
            state[s] = "parked"
        elif act == "demote":
            swap.demote_cold(2)            # oracle never demotes
        elif act == "in" and state[s] == "parked":
            seqs[s], _ = swap.swap_in(seqs[s])
            oseqs[s], _ = oswap.swap_in(oseqs[s])
            state[s] = "live"
        elif act == "free" and state[s] != "freed":
            if state[s] == "parked":
                swap.release_parked(seqs[s])
                oswap.release_parked(oseqs[s])
            else:
                view.release(seqs[s])
                oview.release(oseqs[s])
            state[s] = "freed"
        view.fabric.check_invariants()
        oview.fabric.check_invariants()

    for s in range(3):                     # drain: promote + compare + free
        if state[s] == "parked":
            seqs[s], _ = swap.swap_in(seqs[s])
            oseqs[s], _ = oswap.swap_in(oseqs[s])
            state[s] = "live"
        if state[s] == "live":
            assert np.array_equal(np.asarray(pool.k_pool[:, seqs[s]]),
                                  np.asarray(opool.k_pool[:, oseqs[s]]))
            assert np.array_equal(np.asarray(pool.v_pool[:, seqs[s]]),
                                  np.asarray(opool.v_pool[:, oseqs[s]]))
            view.release(seqs[s])
            oview.release(oseqs[s])
    swap.close()
    oswap.close()
    assert tier.used_pages() == 0
    assert int(view.used.sum()) == 0 and int(oview.used.sum()) == 0
    view.fabric.check_invariants()
    oview.fabric.check_invariants()


@settings(max_examples=10, deadline=None)
@given(st.lists(st.integers(min_value=1, max_value=3),
                min_size=1, max_size=3))
def test_property_restart_roundtrip(lens):
    """Random chain shapes survive export → fabric teardown → import with
    bit-identical bytes and a consistent importing fabric."""
    cfg = dataclasses.replace(registry.get_smoke_config("qwen2-0.5b"),
                              num_layers=1, compute_dtype="float32")
    pool, view, tier = _rig(cfg)
    chains = []
    for i, npages in enumerate(lens):
        toks = [10_000 * (i + 1) + t for t in range(4 * npages)]
        pages = _chain(view, pool, toks, 100 * (i + 1))
        chains.append((toks, np.asarray(pool.k_pool[:, pages]).copy()))
        tier.pin(view, toks)
        view.release(pages)                # only the pin keeps it alive
    tier.export_prefixes(view)
    tier.release_pins()
    view.fabric.check_invariants()

    pool2 = _pool(cfg)
    view2 = as_view(pool2)
    tier.fabric = None
    view2.fabric.attach_persist(tier)
    restored, _ = tier.import_prefixes(view2)
    assert restored == sum(lens)
    view2.fabric.check_invariants()
    for toks, orig in chains:
        got = []
        assert view2.probe_prefix(toks, got) == len(toks)
        assert np.array_equal(np.asarray(pool2.k_pool[:, got]), orig)
        view2.release(got)
    view2.fabric.check_invariants()


# ---------------------------------------------------------------------------
# wire-format round-trip over every page geometry (cluster satellite)
# ---------------------------------------------------------------------------

GEOMETRY_KINDS = ("paged_kv", "mla_latent", "ssm_state", "encoder_kv")


def _geom_pool(kind, page_size=4):
    from repro.placement.geometry import encoder_kv_geometry
    name = {"paged_kv": "qwen2-0.5b", "mla_latent": "deepseek-v3-671b",
            "ssm_state": "xlstm-125m", "encoder_kv": "whisper-tiny"}[kind]
    gcfg = registry.get_smoke_config(name)
    if kind == "paged_kv":
        gcfg = dataclasses.replace(gcfg, num_layers=1,
                                   compute_dtype="float32")
    geometry = encoder_kv_geometry(gcfg, page_size) \
        if kind == "encoder_kv" else None
    pool = BwapPagePool(gcfg, [
        MemoryDomain("hbm_local", 12, 819.0, True),
        MemoryDomain("host", 12, 0.016, False),
    ], page_size=page_size, geometry=geometry,
        dwp_config=DWPConfig(n=10 ** 6, c=1))
    assert pool.geometry.kind == kind
    return pool


def _rand_fill(pool, pages, seed):
    rng = np.random.default_rng(seed)
    dt = np.asarray(pool.k_pool).dtype
    k = rng.standard_normal(pool.k_pool[:, pages].shape).astype(dt)
    v = rng.standard_normal(pool.v_pool[:, pages].shape).astype(dt)
    pool.k_pool = pool.k_pool.at[:, pages].set(k)
    pool.v_pool = pool.v_pool.at[:, pages].set(v)
    return k, v


def _wire_roundtrip(kind, npages, seed):
    """serialize → deserialize → import on a same-geometry peer is
    bit-exact for any geometry, any page count, any bytes."""
    pool = _geom_pool(kind)
    view = as_view(pool)
    tier = PersistentTier()
    view.fabric.attach_persist(tier)
    pages = []
    for _ in range(npages):
        view.append_page(pages)
    k, v = _rand_fill(pool, pages, seed)
    toks = None
    if pool.geometry.shareable and npages * pool.page_size >= 1:
        toks = [seed % 97 + t for t in range(npages * pool.page_size)]
        view.register_prefix(toks, pages, len(toks))
    blob = deserialize_range(serialize_range(tier.export_range(
        view, pages, tokens=toks,
        ntokens=npages * pool.page_size)))
    assert blob["geometry"]["kind"] == kind

    pool2 = _geom_pool(kind)
    view2 = as_view(pool2)
    tier2 = PersistentTier()
    view2.fabric.attach_persist(tier2)
    new_ids, _ = tier2.import_range(view2, blob)
    assert np.array_equal(np.asarray(pool2.k_pool[:, new_ids]), k)
    assert np.array_equal(np.asarray(pool2.v_pool[:, new_ids]), v)
    view.fabric.check_invariants()
    view2.fabric.check_invariants()
    view2.release(new_ids)


def _convert_roundtrip(ps_src, ps_dst, ntokens, seed):
    """A paged_kv range re-chunks across page sizes through the channel,
    bit-exact per valid token, with balanced ledgers on both fabrics."""
    from repro.cluster import Interconnect, Link, PageChannel

    pool_a = _geom_pool("paged_kv", page_size=ps_src)
    view_a = as_view(pool_a)
    view_a.fabric.attach_persist(PersistentTier())
    pool_b = _geom_pool("paged_kv", page_size=ps_dst)
    view_b = as_view(pool_b)
    view_b.fabric.attach_persist(PersistentTier())
    npages = -(-ntokens // ps_src)
    pages = []
    for _ in range(npages):
        view_a.append_page(pages)
    k, v = _rand_fill(pool_a, pages, seed)
    toks = [seed % 89 + t for t in range(ntokens)]

    ch = PageChannel(Interconnect([Link("wire", 0.1)]), chunk_bytes=1 << 14)
    ch.send(view_a, pages, now=0.0, tokens=toks, ntokens=ntokens)
    new_ids, _, _ = ch.recv(view_b)
    assert len(new_ids) == -(-ntokens // ps_dst)
    assert ch.converted_imports == (1 if ps_src != ps_dst else 0)

    def tokens_of(arr, npg, ps):
        a = np.asarray(arr)
        return a.reshape(a.shape[0], npg * ps, *a.shape[3:])[:, :ntokens]

    assert np.array_equal(tokens_of(pool_b.k_pool[:, new_ids],
                                    len(new_ids), ps_dst),
                          tokens_of(k, npages, ps_src))
    assert np.array_equal(tokens_of(pool_b.v_pool[:, new_ids],
                                    len(new_ids), ps_dst),
                          tokens_of(v, npages, ps_src))
    view_a.fabric.check_invariants()
    view_b.fabric.check_invariants()
    view_b.release(new_ids)


@pytest.mark.parametrize("kind", GEOMETRY_KINDS)
def test_wire_roundtrip_each_geometry(kind):
    _wire_roundtrip(kind, npages=2, seed=7)


@pytest.mark.parametrize("ps_src,ps_dst,ntokens",
                         [(4, 8, 14), (8, 4, 9), (2, 8, 7), (4, 4, 12)])
def test_convert_on_import_each_direction(ps_src, ps_dst, ntokens):
    _convert_roundtrip(ps_src, ps_dst, ntokens, seed=11)


@settings(max_examples=12, deadline=None)
@given(st.sampled_from(GEOMETRY_KINDS), st.integers(1, 3),
       st.integers(0, 2 ** 31 - 1))
def test_property_wire_roundtrip_all_geometries(kind, npages, seed):
    _wire_roundtrip(kind, npages, seed)


@settings(max_examples=12, deadline=None)
@given(st.sampled_from([2, 4, 8]), st.sampled_from([2, 4, 8]),
       st.integers(1, 24), st.integers(0, 2 ** 31 - 1))
def test_property_convert_on_import(ps_src, ps_dst, ntokens, seed):
    _convert_roundtrip(ps_src, ps_dst, ntokens, seed)
