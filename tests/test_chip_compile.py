"""Compiles of the served path's kernels for a described TPU v5e.

Nothing runs: the TPU compiler, which is installed without a chip, compiles
each kernel at qwen2-0.5b's published widths (14 query heads, 2 KV heads,
head dim 64, bf16, page_size 16) for one chip of a described ``v5e:2x2``,
and the batched prefill kernel at the engine's chunk limit for every other
registry width the paged decoder serves. The compiler refuses what the chip
would refuse — a slice not aligned to the tiling, more VMEM than a kernel
may use — which interpret mode cannot show. The pool's K/V row write is
compiled at the benchmark cell's pool, where the compiled program shows
whether the pool is updated in place or copied in the chip's layouts.

The topology is described inside a module fixture, never at import: only
one process may load the TPU library, and every test worker imports this
file.
"""

import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import registry
from repro.kernels.flash_attention.kernel import flash_attention_bh
from repro.kernels.paged_attention import ops as paged_ops
from repro.models.lm import LM
from repro.placement import pool as pool_mod
from repro.placement.pool import BwapPagePool, MemoryDomain
from repro.serve.engine import PagedDecoder, pallas_prefill_max_chunk

CFG = registry.get_config("qwen2-0.5b")
NQ, NKV, HD = CFG.num_heads, CFG.num_kv_heads, CFG.head_dim_
PAGE_SIZE = 16
POOL_PAGES = 2048
BF16 = jnp.bfloat16
# every other registry architecture PagedDecoder serves (dense GQA)
OTHER_PAGED_ARCHS = [
    a for a in registry.ARCH_IDS if a != CFG.name
    and registry.get_config(a).family in ("dense", "vlm")
    and registry.get_config(a).mla is None]


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler here: nothing to compile for
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(autouse=True)
def no_persistent_cache():
    """A program compiled for a described chip can be written to the
    persistent cache but not read back without one; keep it out."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _pool(sharding, nkv=NKV, hd=HD):
    return _sds((POOL_PAGES, PAGE_SIZE, nkv, hd), BF16, sharding)


def _lower_prefill(sharding, b, t, mp, nq=NQ, nkv=NKV, hd=HD):
    return paged_ops.paged_prefill_attention_batch.lower(
        _sds((b, t, nq, hd), BF16, sharding), _pool(sharding, nkv, hd),
        _pool(sharding, nkv, hd), _sds((b, mp), jnp.int32, sharding),
        _sds((b,), jnp.int32, sharding), impl="pallas")


def _compiled_text(lowered) -> str:
    text = lowered.compile().as_text()
    assert "tpu_custom_call" in text, "the Pallas kernel is not in the HLO"
    return text


def test_decode_kernel_compiles(one_chip):
    b, mp = 4, 32
    _compiled_text(paged_ops.paged_attention.lower(
        _sds((b, NQ, HD), BF16, one_chip), _pool(one_chip), _pool(one_chip),
        _sds((b, mp), jnp.int32, one_chip), _sds((b,), jnp.int32, one_chip),
        impl="pallas"))


def test_batched_prefill_compiles_at_guard_limit(one_chip):
    """The longest chunk the engine lets through fits the chip's VMEM."""
    t = pallas_prefill_max_chunk(CFG, PAGE_SIZE)
    assert t == 256          # the scheduler's default prefill budget
    _compiled_text(_lower_prefill(one_chip, b=2, t=t, mp=32))


@pytest.mark.parametrize("arch", OTHER_PAGED_ARCHS)
def test_batched_prefill_compiles_at_guard_limit_at_other_widths(one_chip,
                                                                  arch):
    """The engine's chunk limit follows the widths: at each other
    architecture the decoder serves, the longest chunk it lets through
    fits the chip's VMEM too."""
    cfg = registry.get_config(arch)
    t = pallas_prefill_max_chunk(cfg, PAGE_SIZE)
    assert t >= 16
    _compiled_text(_lower_prefill(one_chip, b=2, t=t, mp=32,
                                  nq=cfg.num_heads, nkv=cfg.num_kv_heads,
                                  hd=cfg.head_dim_))


def test_engine_guard_refuses_chunk_past_limit(one_chip):
    """At T = 512 the compiler refuses the batched prefill kernel for lack
    of VMEM; the engine's Pallas path refuses such a chunk itself, before
    it writes or launches anything."""
    b, t, mp = 1, 512, 32
    with pytest.raises(Exception, match="(?i)vmem"):
        _lower_prefill(one_chip, b, t, mp).compile()

    # the engine at published widths; weights as shapes — the guard must
    # fire before anything reads them
    params = jax.eval_shape(LM(CFG).init, jax.random.PRNGKey(0))
    pool = BwapPagePool(CFG, [MemoryDomain("hbm_local", mp, 819.0, True)],
                        page_size=PAGE_SIZE)
    dec = PagedDecoder(CFG, params, pool)
    dec.impl = "pallas"          # what the decoder resolves on a TPU pool
    k_before, v_before = pool.k_pool, pool.v_pool
    with pytest.raises(ValueError, match="prefill_token_budget"):
        dec.forward_chunks([(list(range(1, t + 1)), list(range(mp)), 0)])
    assert pool.k_pool is k_before and pool.v_pool is v_before


def test_flash_kernel_compiles(one_chip):
    bh, s = 2 * NQ, 512
    x = _sds((bh, s, HD), BF16, one_chip)
    _compiled_text(flash_attention_bh.lower(x, x, x))


# the benchmark cell's pool: 26,624 pages of 16 tokens at every layer
CELL_POOL_PAGES = 26624


@pytest.mark.parametrize("rows", [(32, NKV, HD), (512, NKV, HD),
                                  (CFG.num_layers, 8, NKV, HD)],
                         ids=["decode_batch", "prefill_step", "all_layers"])
def test_kv_write_updates_the_pool_in_place(one_chip, rows):
    """The K/V row write at the cell's pool, for a decode batch, a block of
    a padded prefill step, and an all-layer write: both pools are aliased to
    the outputs, and no copy of the pool's shape is left in the program (the
    pool's layout puts pages on the lanes, where a scatter would copy the
    whole pool into its own layout and back)."""
    pool = _sds((CFG.num_layers, CELL_POOL_PAGES, PAGE_SIZE, NKV, HD), BF16,
                one_chip)
    n = rows[-3]
    idx = _sds((n,), jnp.int32, one_chip)
    scalar = _sds((), jnp.int32, one_chip)
    text = pool_mod._write_rows_in_place.lower(
        pool, pool, scalar, idx, idx, _sds(rows, BF16, one_chip),
        _sds(rows, BF16, one_chip), scalar,
        block=min(n, pool_mod.ROW_BLOCK)).compile().as_text()
    assert "{0}: (0, {}, may-alias)" in text
    assert "{1}: (1, {}, may-alias)" in text
    shape = re.escape(f"bf16[{CFG.num_layers},{CELL_POOL_PAGES},{PAGE_SIZE},"
                      f"{NKV},{HD}]")
    assert not re.search(shape + r"\{[^}]*\} copy", text)
