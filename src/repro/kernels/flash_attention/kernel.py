"""Flash attention Pallas TPU kernel (GQA, causal, sliding window).

VMEM tiling: one [block_q, head_dim] query tile and one [block_kv, head_dim]
key/value tile resident per step; fp32 online-softmax accumulators live in
VMEM scratch across the sequential kv grid dimension. Block sizes default to
MXU-aligned 128x128 tiles; the kv loop is the innermost ("arbitrary") grid
axis so q tiles stream while accumulators persist.

The TPU adaptation of the paper's hot loop: HBM->VMEM traffic is the
bandwidth term the BWAP-style placement optimizes; tiles are sized so the
working set (q + k + v + acc ~ 4 * 128 * hd * 4B) stays far under the
~16 MiB/core VMEM budget even at head_dim 256.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1.0e38


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
                  scale: float, block_q: int, block_kv: int, window: int,
                  causal: bool, seq_kv: int):
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q_start = qi * block_q
    k_start = ki * block_kv

    # Skip fully-masked tiles (causal upper triangle / outside the window).
    needed = True
    if causal:
        needed = k_start <= q_start + block_q - 1
    if window > 0:
        needed = needed & (q_start - (k_start + block_kv - 1) < window)

    @pl.when(needed)
    def _compute():
        q = q_ref[0].astype(jnp.float32)           # [bq, h]
        k = k_ref[0].astype(jnp.float32)           # [bkv, h]
        v = v_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ()))) * scale

        qpos = q_start + jax.lax.broadcasted_iota(jnp.int32,
                                                  (block_q, block_kv), 0)
        kpos = k_start + jax.lax.broadcasted_iota(jnp.int32,
                                                  (block_q, block_kv), 1)
        ok = kpos < seq_kv
        if causal:
            ok &= kpos <= qpos
        if window > 0:
            ok &= qpos - kpos < window
        s = jnp.where(ok, s, NEG_INF)

        m_prev = m_ref[...]
        l_prev = l_ref[...]
        m_cur = jnp.max(s, axis=1)[:, None]
        m_new = jnp.maximum(m_prev, m_cur)
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)                     # [bq, bkv]
        l_new = l_prev * alpha + jnp.sum(p, axis=1)[:, None]
        acc_ref[...] = acc_ref[...] * alpha \
            + jax.lax.dot_general(p, v, (((1,), (0,)), ((), ())))
        m_ref[...] = m_new
        l_ref[...] = l_new

    @pl.when(ki == nk - 1)
    def _finish():
        l = l_ref[...]
        # rows with no valid kv (shouldn't happen causally) stay zero
        o_ref[0] = (acc_ref[...] / jnp.where(l == 0.0, 1.0, l)
                    ).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("window", "causal", "block_q",
                                             "block_kv", "interpret"))
def flash_attention_bh(q, k, v, *, window: int = 0, causal: bool = True,
                       block_q: int = 128, block_kv: int = 128,
                       interpret: bool = False):
    """Batched-heads layout: q [BH, S, h]; k/v [BH, T, h] (kv heads already
    aligned with q heads — ops.py handles the GQA head mapping)."""
    bh, s, h = q.shape
    t = k.shape[1]
    block_q = min(block_q, s)
    block_kv = min(block_kv, t)
    s_pad = -(-s // block_q) * block_q
    t_pad = -(-t // block_kv) * block_kv
    if s_pad != s:
        q = jnp.pad(q, ((0, 0), (0, s_pad - s), (0, 0)))
    if t_pad != t:
        k = jnp.pad(k, ((0, 0), (0, t_pad - t), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, t_pad - t), (0, 0)))

    grid = (bh, s_pad // block_q, t_pad // block_kv)
    kernel = functools.partial(
        _flash_kernel, scale=1.0 / np.sqrt(h), block_q=block_q,
        block_kv=block_kv, window=window, causal=causal, seq_kv=t)
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, h), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_kv, h), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, block_kv, h), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, h), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, s_pad, h), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),    # m
            pltpu.VMEM((block_q, 1), jnp.float32),    # l
            pltpu.VMEM((block_q, h), jnp.float32),    # acc
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(q, k, v)
    return out[:, :s, :]
