"""Geometry-polymorphic page fabric + capacity market (ISSUE 9 /
DESIGN.md §12).

Covers the `PageGeometry` protocol (paged K/V bit-identity with the
historical layout, MLA latent asymmetry, 1-page SSM state, read-only
encoder K/V), the pool/pagetable behavior it induces (asymmetric arrays,
fork-as-copy vs fork-as-refcount, prefix trie gated off for
non-shareable groups), the deprecation shims for the old serve-layer
import paths, the `PageFabricZoo` byte ledger + capacity market
(annex / escrow / repay / leak-free unregister), and a hypothesis
property test interleaving alloc / fork / migrate / release / market
ticks across a transformer + MLA + SSM trio.
"""

import dataclasses
import importlib
import math

import numpy as np
import pytest

from hypothesis import given, settings
from hypothesis import strategies as st

import jax.numpy as jnp

from repro.configs import registry
from repro.placement.geometry import (PageGeometry, encoder_kv_geometry,
                                      geometry_for, mla_latent_geometry,
                                      paged_kv_geometry, ssm_state_geometry)
from repro.placement.pool import BwapPagePool, MemoryDomain
from repro.placement.zoo import ByteDomain, PageFabricZoo


def _cfg(name, **over):
    cfg = registry.get_smoke_config(name)
    return dataclasses.replace(cfg, **over) if over else cfg


CHAT = _cfg("qwen2-0.5b", num_layers=1, compute_dtype="float32")
MLA = _cfg("deepseek-v3-671b")
SSM = _cfg("xlstm-125m")
ASR = _cfg("whisper-tiny")


def _domains(fast=32, slow=24):
    return [MemoryDomain("hbm_local", fast, 819.0, True),
            MemoryDomain("host", slow, 16.0, False)]


def _arena():
    return [ByteDomain("hbm_local", 64 * 1024, 819.0, True),
            ByteDomain("host", 128 * 1024, 8.0)]


# ---------------------------------------------------------------------------
# the geometry protocol
# ---------------------------------------------------------------------------

def test_paged_geometry_matches_historical_layout():
    """The default geometry reproduces the old hardcoded pool layout
    bit-for-bit: same page_bytes formula, same array shapes."""
    ps = 4
    g = geometry_for(CHAT, ps)
    assert g.kind == "paged_kv" and g.shareable and g.grows
    itemsize = jnp.dtype(CHAT.compute_dtype).itemsize
    old = 2 * ps * CHAT.num_kv_heads * CHAT.head_dim_ \
        * itemsize * CHAT.num_layers
    assert g.page_bytes == old
    k, v = g.array_shapes(10)
    assert k == v == (CHAT.num_layers, 10, ps, CHAT.num_kv_heads,
                      CHAT.head_dim_)
    assert g.pages_for_tokens(0) == 0
    assert g.pages_for_tokens(1) == 1
    assert g.pages_for_tokens(9) == 3


def test_mla_geometry_is_asymmetric_and_compressed():
    g = geometry_for(MLA, 4)
    assert g.kind == "mla_latent" and g.shareable and g.grows
    assert g.k_block == (4, 1, MLA.mla.qk_rope_head_dim)
    assert g.v_block == (4, 1, MLA.mla.kv_lora_rank)
    assert g.k_block != g.v_block, "latent cache must be asymmetric"
    # the whole point: far below the materialized-heads footprint
    assert g.page_bytes < paged_kv_geometry(MLA, 4).page_bytes
    assert g.page_bytes == (4 * (MLA.mla.qk_rope_head_dim
                                 + MLA.mla.kv_lora_rank)
                            * jnp.dtype(MLA.compute_dtype).itemsize
                            * MLA.num_layers)


def test_ssm_geometry_is_one_fixed_nonshareable_page():
    g = geometry_for(SSM, 4)                # page_size arg ignored: state
    assert g.kind == "ssm_state"
    assert g.page_size == 1 and g.fixed_pages == 1 and not g.grows
    assert not g.shareable, "in-place-mutated state must not CoW-alias"
    for tokens in (0, 1, 7, 10 ** 6):       # never grows
        assert g.pages_for_tokens(tokens) == 1
    assert math.prod(g.k_block) != math.prod(g.v_block)


def test_encoder_geometry_is_fixed_and_shareable():
    g = encoder_kv_geometry(ASR, 4)
    assert g.kind == "encoder_kv" and g.shareable and not g.grows
    assert g.fixed_pages == -(-ASR.enc_frames // 4)
    assert g.num_layers == ASR.enc_layers
    # never the default: whisper's decode-path cache stays paged K/V
    assert geometry_for(ASR, 4).kind == "paged_kv"


# ---------------------------------------------------------------------------
# pool + pagetable under a geometry (satellite: page_bytes from geometry)
# ---------------------------------------------------------------------------

def test_pool_defaults_are_bit_identical():
    pool = BwapPagePool(CHAT, _domains(), page_size=4)
    g = pool.geometry
    assert g.kind == "paged_kv"
    assert pool.page_bytes == g.page_bytes
    assert pool.k_pool.shape == pool.v_pool.shape \
        == g.array_shapes(pool.total_pages)[0]
    pid = pool.alloc_page()
    assert pool.bytes_per_domain([pid])[0] == g.page_bytes


def test_pool_materializes_asymmetric_mla_arrays():
    pool = BwapPagePool(MLA, _domains(), page_size=4)
    assert pool.geometry.kind == "mla_latent"
    assert pool.k_pool.shape != pool.v_pool.shape
    assert pool.k_pool.shape[-1] == MLA.mla.qk_rope_head_dim
    assert pool.v_pool.shape[-1] == MLA.mla.kv_lora_rank
    assert pool.page_bytes == pool.geometry.page_bytes


def test_ssm_pool_follows_geometry_page_size():
    pool = BwapPagePool(SSM, _domains(), page_size=4)
    assert pool.geometry.kind == "ssm_state"
    assert pool.page_size == 1, "pool token granularity follows geometry"


def test_prefix_trie_gated_off_for_nonshareable_geometry():
    from repro.placement.fabric import MemoryFabric
    fab = MemoryFabric(SSM, _domains(), page_size=1, seed=0)
    view = fab.view("s", quota=(8, 6), home=(0,))
    pages = []
    view.append_page(pages)
    view.register_prefix([1], pages, 1)     # must be a silent no-op
    probe = []
    assert view.probe_prefix([1], probe) == 0 and probe == []
    view.release(pages)
    fab.check_invariants()


def test_fork_semantics_copy_vs_refcount():
    from repro.placement.fabric import MemoryFabric
    # SSM: fork copies state into fresh pages
    fab = MemoryFabric(SSM, _domains(), page_size=1, seed=0)
    v = fab.view("s", quota=(8, 6), home=(0,))
    pages = []
    v.append_page(pages)
    v.k_pool = v.k_pool.at[:, pages[0]].set(3.0)
    clone = v.fork_sequence(pages)
    assert clone and set(clone).isdisjoint(pages), "SSM fork must copy"
    np.testing.assert_array_equal(np.asarray(v.k_pool)[:, clone[0]],
                                  np.asarray(v.k_pool)[:, pages[0]])
    assert all(fab.table.ref[p] == 1 for p in pages + clone)
    # shareable: fork bumps refcounts, no new pages
    fab2 = MemoryFabric(CHAT, _domains(), page_size=4, seed=0)
    v2 = fab2.view("c", quota=(8, 6), home=(0,))
    pages2 = []
    v2.grow(pages2, 2)
    free_before = v2.free_count()
    clone2 = v2.fork_sequence(pages2)
    assert clone2 == pages2 and v2.free_count() == free_before
    assert all(fab2.table.ref[p] == 2 for p in pages2)
    v2.release(clone2)
    v2.release(pages2)
    for f in (fab, fab2):
        f.check_invariants()


# ---------------------------------------------------------------------------
# deprecation shims (satellite: old serve-layer import paths keep working)
# ---------------------------------------------------------------------------

def test_serve_kvcache_shim_warns_and_reexports():
    import repro.serve.kvcache as shim
    with pytest.warns(DeprecationWarning, match="repro.serve.kvcache"):
        shim = importlib.reload(shim)
    from repro.placement import pool
    assert shim.BwapPagePool is pool.BwapPagePool
    assert shim.MemoryDomain is pool.MemoryDomain
    assert shim.default_domains is pool.default_domains


def test_serve_pagetable_shim_warns_and_reexports():
    import repro.serve.pagetable as shim
    with pytest.warns(DeprecationWarning, match="repro.serve.pagetable"):
        shim = importlib.reload(shim)
    from repro.placement import pagetable
    assert shim.PageTable is pagetable.PageTable
    assert shim.ROOT is pagetable.ROOT


# ---------------------------------------------------------------------------
# the zoo: byte arena + capacity market
# ---------------------------------------------------------------------------

def _zoo():
    zoo = PageFabricZoo(_arena(), seed=0)
    zoo.register("chat", CHAT, share=0.25, page_size=4)
    zoo.register("mla", MLA, share=0.25, page_size=4)
    zoo.register("ssm", SSM, share=0.3)
    return zoo


def test_zoo_three_geometries_one_arena():
    zoo = _zoo()
    kinds = {g.geometry.kind for g in zoo.groups.values()}
    assert kinds == {"paged_kv", "mla_latent", "ssm_state"}
    # funding is byte-denominated: floor(share * capacity / page_bytes)
    for g in zoo.groups.values():
        assert (g.funded_bytes()
                <= np.asarray([0.31 * d.capacity_bytes
                               for d in zoo.domains])).all()
    zoo.check_invariants()


def test_zoo_market_annex_and_repay():
    zoo = _zoo()
    chat = zoo.groups["chat"]
    start = {n: g.view.quota.copy() for n, g in zoo.groups.items()}
    # a chat burst: demand far beyond its funding, everyone else idle
    zoo.observe_demand("chat", 80 * chat.page_bytes)
    assert zoo.page_value("chat") > zoo.page_value("ssm") == 0.0
    flows = zoo.market_tick()
    assert flows["granted_bytes"] > 0
    assert {ln.lender for ln in zoo.leases if ln.granted_bytes} \
        <= {"mla", "ssm"}
    assert (chat.view.quota > start["chat"]).any()
    zoo.check_invariants()
    # burst over: demand drops, the next tick unwinds every lease
    zoo.observe_demand("chat", 0)
    zoo.market_tick()
    assert zoo.outstanding_bytes() == 0
    for n, q in start.items():
        np.testing.assert_array_equal(zoo.groups[n].view.quota, q)
    zoo.check_invariants()


def test_zoo_escrow_balances_mismatched_page_sizes():
    """SSM pages (16+ KiB) never divide chat pages (1 KiB): a trade must
    escrow the remainder bytes in the lease, and the ledger must balance
    mid-lease, not just after repayment."""
    zoo = _zoo()
    chat = zoo.groups["chat"]
    zoo.observe_demand("chat", 10 ** 9)     # starve: annex everything idle
    zoo.market_tick()
    zoo.check_invariants()                  # balances WITH escrow held
    ssm_leases = [ln for ln in zoo.leases
                  if ln.lender == "ssm" and ln.granted_bytes]
    assert ssm_leases, "ssm funding never traded"
    ln = ssm_leases[0]
    lent = int(ln.lender_pages.sum()) * zoo.groups["ssm"].page_bytes
    funded = int(ln.borrower_pages.sum()) * chat.page_bytes
    assert lent == funded + int(ln.escrow_bytes.sum())


def test_zoo_unregister_is_leak_free():
    zoo = _zoo()
    cap = zoo.capacity_bytes.copy()
    for name in list(zoo.groups):
        zoo.unregister(name)
    np.testing.assert_array_equal(zoo.free_bytes(), cap)


def test_zoo_rejects_oversubscription():
    zoo = PageFabricZoo(_arena(), seed=0)
    zoo.register("a", CHAT, share=0.7, page_size=4)
    with pytest.raises(AssertionError, match="oversubscribe"):
        zoo.register("b", CHAT, share=0.5, page_size=4)


# ---------------------------------------------------------------------------
# property test: the trio under random interleavings
# ---------------------------------------------------------------------------

@given(st.lists(st.tuples(st.integers(0, 4), st.integers(0, 2),
                          st.integers(0, 10 ** 6)),
                min_size=1, max_size=40),
       st.integers(min_value=0, max_value=2 ** 31 - 1))
@settings(max_examples=20, deadline=None)
def test_zoo_invariants_under_random_interleavings(ops, seed):
    """Random interleavings of alloc / fork / migrate / release / market
    ticks across the transformer + MLA + SSM trio hold the zoo byte
    ledger (funded + escrow + free == capacity per domain) and every
    member fabric's page invariants after every operation — and a full
    drain + unregister leaks nothing."""
    zoo = _zoo()
    names = list(zoo.groups)
    rng = np.random.default_rng(seed)
    seqs = {n: [] for n in names}

    for op, gi, arg in ops:
        name = names[gi]
        g = zoo.groups[name]
        v, mine = g.view, seqs[name]
        if op == 0:                        # alloc
            n = 1 if not g.geometry.grows else int(rng.integers(1, 4))
            if v.free_count() < n:
                continue
            pages = []
            if g.geometry.grows:
                v.grow(pages, n)
            else:
                for _ in range(g.geometry.fixed_pages):
                    v.append_page(pages)
            mine.append(pages)
        elif op == 1 and mine:             # fork: copy or refcount
            pages = mine[arg % len(mine)]
            if not g.geometry.shareable \
                    and v.free_count() < len(pages):
                continue
            mine.append(v.fork_sequence(pages))
        elif op == 2 and mine:             # migrate live pages
            i = arg % len(mine)
            mine[i] = v.migrate(mine[i])
        elif op == 3 and mine:             # release
            v.release(mine.pop(arg % len(mine)))
        elif op == 4:                      # market tick under this demand
            for other in names:
                zoo.observe_demand(other, 0)
            zoo.observe_demand(name, arg * g.page_bytes)
            zoo.market_tick()
        zoo.check_invariants()

    # drain: everything releases, demand clears, leases unwind, and
    # unregistering the whole zoo returns every byte to the arena
    cap = zoo.capacity_bytes.copy()
    for name in names:
        for pages in seqs[name]:
            zoo.groups[name].view.release(pages)
        zoo.observe_demand(name, 0)
    zoo.market_tick()
    assert zoo.outstanding_bytes() == 0, "idle leases must fully repay"
    zoo.check_invariants()
    for name in names:
        zoo.unregister(name)
    np.testing.assert_array_equal(zoo.free_bytes(), cap)
