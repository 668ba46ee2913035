"""Compiles of the served path's kernels for a described TPU v5e.

Nothing runs: the TPU compiler, which is installed without a chip, compiles
each kernel at qwen2-0.5b's published widths (14 query heads, 2 KV heads,
head dim 64, bf16, page_size 16) for one chip of a described ``v5e:2x2``,
and the batched prefill kernel at the engine's chunk limit for every other
registry width the paged decoder serves, and the latent (MLA) decode kernel,
row write and prefill at DeepSeek-V3's widths over the benchmark cell's
stacked latent pools. The compiler refuses what the chip would refuse — a
slice not aligned to the tiling, more VMEM than a kernel may use — which
interpret mode cannot show. The pool's K/V row write is
compiled at the benchmark cell's pool, where the compiled program shows
whether the pool is updated in place or copied in the chip's layouts.

The topology is described inside a module fixture, never at import: only
one process may load the TPU library, and every test worker imports this
file.
"""

import dataclasses
import math
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
from repro.serve import engine
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


def _stacked_layers(sharding):
    """qwen2-0.5b's 24 layers as the benchmark cell serves them: one
    stacked group of bf16 weights, as shapes on ``sharding``."""
    cfg = dataclasses.replace(CFG, param_dtype="bfloat16")
    params = jax.eval_shape(LM(cfg).init, jax.random.PRNGKey(0))
    return cfg, jax.tree.map(lambda a: _sds(a.shape, a.dtype, sharding),
                             params["groups"][0])


@pytest.mark.parametrize("b, t", [(32, 1), (8, 32)],
                         ids=["decode_batch", "prefill_step"])
def test_gqa_layer_programs_read_the_stacked_weights_in_place(one_chip, b,
                                                              t):
    """The dense-GQA body's input and output programs at qwen2-0.5b widths,
    on the stacked 24-layer tree with the layer index traced (a decode
    batch of 32, and 8 chunks of 32 tokens): neither holds a copy of a
    whole stacked weight, which slicing the layer out before reading it,
    or prefetching a whole stacked weight into vector memory, would
    make."""
    cfg, layers = _stacked_layers(one_chip)
    assert all(a.shape[0] == CFG.num_layers for a in jax.tree.leaves(layers))
    j = _sds((), jnp.int32, one_chip)
    x = _sds((b, t, cfg.d_model), BF16, one_chip)
    pos = _sds((b, t), jnp.int32, one_chip)
    if t == 1:
        rows, att = None, _sds((b, NQ, HD), BF16, one_chip)
    else:
        idx = _sds((b * t,), jnp.int32, one_chip)
        rows, att = (idx, idx), _sds((b, t, NQ, HD), BF16, one_chip)
    gqa_in, gqa_out = engine.gqa_programs("tpu")
    texts = [gqa_in.lower(cfg, layers["norm1"], layers["attn"], j, x, pos,
                          rows).compile().as_text(),
             gqa_out.lower(cfg, layers["attn"], j, x, att).compile().as_text()]
    for leaf in jax.tree.leaves(layers):
        shape = re.escape(f"bf16[{','.join(map(str, leaf.shape))}]")
        for text in texts:
            assert not re.search(shape + r"\{[^}]*\} copy", text), leaf.shape


def test_layer_pools_copy_one_layer_of_each_pool(one_chip):
    """The dense kernels' per-layer pools at the cell's pool: one program
    returns layer ``l`` of both pools, each copied once, with no
    temporaries (a slice then a squeeze, each its own program, copied it
    twice)."""
    pool = _sds((CFG.num_layers, CELL_POOL_PAGES, PAGE_SIZE, NKV, HD), BF16,
                one_chip)
    mem = engine._layer_pools.lower(pool, pool, _sds(
        (), jnp.int32, one_chip)).compile().memory_analysis()
    layer_bytes = CELL_POOL_PAGES * PAGE_SIZE * NKV * HD * 2
    assert 2 * layer_bytes <= mem.output_size_in_bytes < 2.01 * layer_bytes
    assert mem.temp_size_in_bytes == 0


# DeepSeek-V3's latent pools at the benchmark cell: 5 layers, 65,000 pages
# of 16 tokens; rope keys folded two tokens to a 128-lane row, latents
# [16, 512] (placement/geometry.py)
DS = registry.get_config("deepseek-v3-671b")
DS_LAYERS, DS_PAGES, DS_BATCH = 5, 65000, 128
DS_ROPE, DS_LATENT = DS.mla.qk_rope_head_dim, DS.mla.kv_lora_rank


def _latent_pools(sharding):
    from repro.placement.geometry import mla_latent_geometry
    geo = mla_latent_geometry(DS, PAGE_SIZE)
    assert geo.k_block == (PAGE_SIZE // 2, 2 * DS_ROPE)
    assert geo.v_block == (PAGE_SIZE, DS_LATENT)
    lead = (DS_LAYERS, DS_PAGES)
    return (_sds(lead + geo.k_block, BF16, sharding),
            _sds(lead + geo.v_block, BF16, sharding))


def _no_pool_copy(text):
    for pool in ("8,128", "16,512"):
        shape = re.escape(f"bf16[{DS_LAYERS},{DS_PAGES},{pool}]")
        assert not re.search(shape + r"\{[^}]*\} copy", text), pool


def test_latent_decode_kernel_compiles_on_the_stacked_pools(one_chip):
    """128 query heads over one 576-wide latent key per token, batch 128,
    tables 64 pages wide: the kernel reads both stacked pools in place,
    the layer picked by its index maps."""
    kp, vp = _latent_pools(one_chip)
    mp = 64
    text = _compiled_text(paged_ops.paged_latent_attention.lower(
        _sds((DS_BATCH, DS.num_heads, DS_LATENT), BF16, one_chip),
        _sds((DS_BATCH, DS.num_heads, DS_ROPE), BF16, one_chip), kp, vp,
        _sds((DS_BATCH, mp), jnp.int32, one_chip),
        _sds((DS_BATCH,), jnp.int32, one_chip),
        _sds((), jnp.int32, one_chip), scale=0.1, impl="pallas"))
    _no_pool_copy(text)


def test_latent_write_updates_the_pools_in_place(one_chip):
    """A decode batch's rope-key and latent rows written into the stacked
    latent pools: both pools aliased to the outputs, neither copied."""
    kp, vp = _latent_pools(one_chip)
    idx = _sds((DS_BATCH,), jnp.int32, one_chip)
    scalar = _sds((), jnp.int32, one_chip)
    text = pool_mod._write_rows_in_place.lower(
        kp, vp, scalar, idx, idx, _sds((DS_BATCH, DS_ROPE), BF16, one_chip),
        _sds((DS_BATCH, DS_LATENT), BF16, one_chip), scalar,
        block=pool_mod.ROW_BLOCK).compile().as_text()
    assert "{0}: (0, {}, may-alias)" in text
    assert "{1}: (1, {}, may-alias)" in text
    _no_pool_copy(text)


def test_latent_pools_hold_no_padding(one_chip):
    """The chip keeps both latent pools in row-major tiles with nothing
    beyond their logical bytes (a [16, 1, 64] rope block is laid out with
    pages on the lanes instead, which a page-by-page read must copy)."""
    kp, vp = _latent_pools(one_chip)
    compiled = jax.jit(lambda k, v: (k, v), donate_argnums=(0, 1)).lower(
        kp, vp).compile()
    logical = sum(math.prod(p.shape) * 2 for p in (kp, vp))
    assert compiled.memory_analysis().argument_size_in_bytes == logical
    text = compiled.as_text()
    for p in (kp, vp):
        dims = ",".join(map(str, p.shape))
        assert re.search(re.escape(f"bf16[{dims}]") + r"\{3,2,1,0:T", text)


def test_latent_prefill_reads_the_stacked_pools_without_a_layer_copy(
        one_chip):
    """The jnp prefill over latent pages (32 prompts of 32 tokens, the
    cell's fill) gathers its pages with the layer index: its temporaries
    stay far under one layer's latent pool (1.06 GB), which a slice of the
    layer before the gather would copy."""
    kp, vp = _latent_pools(one_chip)
    b, t = 32, 32
    compiled = paged_ops.paged_latent_prefill.lower(
        _sds((b, t, DS.num_heads, DS_LATENT), BF16, one_chip),
        _sds((b, t, DS.num_heads, DS_ROPE), BF16, one_chip), kp, vp,
        _sds((b, 3), jnp.int32, one_chip), _sds((b,), jnp.int32, one_chip),
        _sds((), jnp.int32, one_chip), scale=0.1).compile()
    layer_bytes = math.prod(vp.shape[1:]) * 2
    assert compiled.memory_analysis().temp_size_in_bytes < layer_bytes / 2
    _no_pool_copy(compiled.as_text())
