"""Batched serving engine over the BWAP page pool (dense GQA archs, and
MLA with a held share of its MoE experts: DeepSeek-V3).

CPU-runnable end-to-end: priority continuous batching through the request
scheduler (admission, chunked prefill, preemption with KV swap to slow
domains — ``repro.scheduler``), paged prefill + decode through
kernels/paged_attention (the Pallas kernels on TPU, the jnp reference on
CPU — chosen from the pool's platform, :func:`attention_impl`), BWAP
placement of fresh pages, and online DWP tuning fed by each step's latency
through ``record_latency``. examples/serve_paged.py drives it.

``ServeEngine.step`` and ``PagedDecoder.decode_step`` mark their phases
with profiler host spans (``serve.*``, :func:`repro.obs.trace.span`), so a
device trace can put its idle time down to what the host was doing.
"""

from __future__ import annotations

import contextlib
import functools
import time
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.paged_attention import ops as paged_ops
from repro.models import attention, moe
from repro.models import layers as L
from repro.models.config import ModelConfig
from repro.models.lm import LM, make_groups
from repro.obs.trace import SPAN_PREFIX, span
from repro.placement.fabric import as_view
from repro.scheduler.scheduler import Request, RequestScheduler

# The per-sequence record moved into the scheduler subsystem; the old name
# stays importable (tests, examples).
Sequence_ = Request

# The batched Pallas prefill kernel holds a chunk's whole query block in
# v5e VMEM: as a float32 [nkv, T, groups, lanes] tile (groups padded to the
# 8 sublanes and lanes, the wider of head_dim and page_size, to 128), beside
# softmax state and scores of the same order. At qwen2-0.5b widths T = 512
# does not fit, and a 2 MiB tile (T = 256) does. tests/test_chip_compile.py
# proves that a chunk of pallas_prefill_max_chunk() tokens compiles at every
# registry width the decoder serves.
PALLAS_PREFILL_TILE_BYTES = 2 << 20

_ATTENTION_IMPL = {"tpu": "pallas", "cpu": "reference"}


def pallas_prefill_max_chunk(cfg: ModelConfig, page_size: int) -> int:
    """Longest prefill chunk (tokens) whose query tile stays within
    ``PALLAS_PREFILL_TILE_BYTES``: 256 for qwen2-0.5b at page size 16."""
    groups = cfg.num_heads // cfg.num_kv_heads
    lanes = -(-max(cfg.head_dim_, page_size) // 128) * 128
    token_bytes = 4 * cfg.num_kv_heads * (-(-groups // 8) * 8) * lanes
    return PALLAS_PREFILL_TILE_BYTES // token_bytes


def attention_impl(platform: str) -> str:
    """Paged-attention implementation for the platform that holds the pool:
    the Pallas kernels on TPU, the jnp reference on CPU (where the engine's
    tests are bit-exact against it). Any other platform is an error."""
    try:
        return _ATTENTION_IMPL[platform]
    except KeyError:
        raise ValueError(
            f"no paged-attention implementation for platform {platform!r} "
            f"(supported: {sorted(_ATTENTION_IMPL)})") from None


# -- the MLA body's programs: each takes a layer group's stacked weights and
# the layer's index in it (None for a group held as a list of layers), so
# no layer's weights are sliced out before a program reads them

def _pick(tree, j):
    return tree if j is None else jax.tree.map(lambda a: a[j], tree)


def latent_table_width(mp: int) -> int:
    """The page-table width the latent decode kernel runs at for tables
    ``mp`` pages wide: a multiple of 8 up to 64, then the next power of
    two. The kernel compiles per width and a batch's tables grow a page
    every ``page_size`` steps, so widths come in few sizes, and the grid
    steps spent past ``mp`` stay few where most steps run; the columns
    past ``mp`` hold page 0, which the kernel masks and fetches once."""
    return -(-mp // 8) * 8 if mp <= 64 else 1 << (mp - 1).bit_length()


def _repeat_last(n: int, *rows):
    """Host arrays padded to ``n`` entries along the first axis by
    repeating their last entry."""
    return tuple(np.concatenate([a, np.repeat(a[-1:], n - len(a), axis=0)])
                 for a in (np.asarray(r) for r in rows))


@functools.partial(jax.jit, static_argnums=0)
def _latent_in(cfg, norm1, attn, j, x, positions, rows):
    """Norm, MLA projections and ``W_UK`` absorption of ``x`` [B,S,d]: the
    queries (squeezed to [B,H,.] when ``rows`` is None, a decode step) and
    the cache rows, ``k_pe`` and ``ckv`` of each token (at
    ``rows = (sequence, token)`` indices, else of the one decode token)."""
    h = L.apply_norm(cfg, _pick(norm1, j), x)
    q_lat, q_pe, ckv, k_pe = attention.mla_latent_query(
        cfg, _pick(attn, j), h, positions)
    if rows is None:
        return q_lat[:, 0], q_pe[:, 0], k_pe[:, 0], ckv[:, 0]
    return q_lat, q_pe, k_pe[rows], ckv[rows]


@functools.partial(jax.jit, static_argnums=0)
def _latent_out(cfg, attn, j, x, out_lat):
    """The residual stream after attention, from the heads' latent."""
    if out_lat.ndim == 3:                            # decode: [B,H,r]
        out_lat = out_lat[:, None]
    return x + attention.mla_latent_out(cfg, _pick(attn, j), out_lat)


@functools.partial(jax.jit, static_argnums=0)
def _dense_ffn(cfg, norm2, mlp, j, x):
    return x + L.mlp_apply(cfg, _pick(mlp, j),
                           L.apply_norm(cfg, _pick(norm2, j), x))


@functools.partial(jax.jit, static_argnums=0, donate_argnums=5)
def expert_layer(cfg, norm2, experts, j, x, routed, real):
    """One MoE layer of a chip that holds a share of the experts, as one
    program: norm, routing over all experts, the held experts and the
    shared expert (``moe.held_moe_apply``), residual. ``routed`` [2] adds
    the token-expert choices on held and on absent experts of the tokens
    that ``real`` [B*S] marks."""
    b, s, d = x.shape
    h = L.apply_norm(cfg, _pick(norm2, j), x)
    out, held = moe.held_moe_apply(cfg, _pick(experts, j),
                                   h.reshape(b * s, d))
    held = jnp.where(real, held, 0).sum()
    absent = real.sum() * cfg.moe.top_k - held
    return x + out.reshape(b, s, d), routed + jnp.stack([held, absent])


@functools.partial(jax.jit, static_argnums=0)
def _latent_head(cfg, final_norm, w, x):
    cdt = jnp.dtype(cfg.compute_dtype)
    return L.apply_norm(cfg, final_norm, x) @ w.astype(cdt)


class PagedDecoder:
    """Per-layer decode through the page pool: the dense-GQA body (dense,
    VLM families) or, for a config with ``mla``, the MLA body over the
    latent pools with each layer group's FFN (a dense MLP, or an expert
    layer that holds ``cfg.moe.held`` of the experts)."""

    def __init__(self, cfg: ModelConfig, params, pool):
        assert cfg.family in ("dense", "vlm", "moe")
        assert cfg.mla is not None or cfg.family != "moe", \
            "the MoE layers served are MLA's"
        self.cfg = cfg
        self.params = params
        self.view = as_view(pool)        # placement + data plane surface
        (device,) = self.view.k_pool.devices()
        self.impl = attention_impl(device.platform)
        gp = params["groups"][0]
        self.stacked = not isinstance(gp, list)
        if cfg.mla is not None:
            self._init_latent(device)

    def _init_latent(self, device):
        """Each layer's weights as the MLA body's programs take them (its
        group's tree and its index there, or its own tree and None; and
        its group's kind, from ``LM.make_groups``' split), the softmax
        scale, and the routed-choice counter: counts stay on the device
        and fold into the registry's ``repro_moe_routed_total{to}`` when it
        is read. Each batch's embeddings are placed on the pools'
        ``device``, so that every layer's programs see their arguments
        placed alike: a program compiles anew for arguments placed
        otherwise."""
        self.device = device
        self.where = []
        for group, gp in zip(make_groups(self.cfg), self.params["groups"]):
            for i in range(group.size):
                self.where.append((gp[i], None, group.kind)
                                  if isinstance(gp, list)
                                  else (gp, i, group.kind))
        self.scale = attention.mla_softmax_scale(self.cfg)
        self.widest: dict = {}       # chunk length, or "decode" -> batch
        self.routed = jax.device_put(jnp.zeros(2, jnp.int32), device)
        registry = self.view.telemetry.metrics
        family = registry.counter(
            "repro_moe_routed_total",
            "Token-expert routing choices, to an expert held here or not.",
            ("to",))
        held, absent = family.labels("held"), family.labels("absent")

        def fold():
            n = np.asarray(self.routed)
            if n.any():
                self.routed = jnp.zeros_like(self.routed)
                held.inc(int(n[0]))
                absent.inc(int(n[1]))
        registry.on_read(fold)

    def _layer(self, l: int):
        gp = self.params["groups"][0]
        if self.stacked:
            return jax.tree.map(lambda x: x[l], gp)
        return gp[l]

    def prefill_chunk(self, token_ids, pages, lo: int, hi: int):
        """Single-sequence incremental prefill (kept for callers/tests):
        one-chunk special case of :meth:`forward_chunks`."""
        self.forward_chunks([(list(token_ids[lo:hi]), pages, lo)])

    def forward_chunks(self, chunks, *, want_logits: bool = False):
        """Fused multi-sequence chunk forward: ``chunks`` is a list of
        ``(token_ids, pages, start)`` — one sequence's token chunk at
        absolute positions ``[start, start + len(token_ids))`` over its page
        view. Per layer, every chunk's K/V is written into its pages first
        (one in-place write for the whole batch), then all chunks' queries
        run *one* batched prefill-mode paged-attention launch — prior
        chunks' (and any trie-shared prefix's) K/V is read from the pool,
        never recomputed, and same-step chunks of different sequences no
        longer pay one dispatch each (ROADMAP: batched incremental prefill).

        Chunks are right-padded to the longest one; padded queries' K/V
        never lands in the pool and their outputs are discarded, so real
        positions are bit-identical to running each chunk alone. The K/V
        write's rows are padded to a power of two (``pad_rows``). With
        ``want_logits`` the padded [B,T,V] logits are returned — the
        speculative verify step (DESIGN.md §7) reads the model's argmax at
        every draft position from them."""
        cfg = self.cfg
        if cfg.mla is not None:
            return self._latent_chunks(chunks, want_logits)
        cdt = jnp.dtype(cfg.compute_dtype)
        b = len(chunks)
        nq, nkv, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim_
        ps = self.view.page_size
        t = max(len(toks) for toks, _, _ in chunks)
        if self.impl == "pallas":
            limit = pallas_prefill_max_chunk(cfg, ps)
            if t > limit:
                raise ValueError(
                    f"prefill chunk of {t} tokens exceeds the {limit} the "
                    f"Pallas prefill kernel fits in VMEM at {cfg.name}'s "
                    "widths; lower the scheduler's prefill_token_budget")
        x, pos, tbl, qs, pids, slots, seq_i, tok_i = self._chunk_inputs(
            chunks, t)

        for l in range(cfg.num_layers):
            p = self._layer(l)
            h = L.apply_norm(cfg, p["norm1"], x)
            q = (h @ p["attn"]["wq"].astype(cdt)).reshape(b, t, nq, hd)
            k = (h @ p["attn"]["wk"].astype(cdt)).reshape(b, t, nkv, hd)
            v = (h @ p["attn"]["wv"].astype(cdt)).reshape(b, t, nkv, hd)
            if cfg.qkv_bias:
                q = q + p["attn"]["bq"].astype(cdt).reshape(nq, hd)
                k = k + p["attn"]["bk"].astype(cdt).reshape(nkv, hd)
                v = v + p["attn"]["bv"].astype(cdt).reshape(nkv, hd)
            if cfg.use_rope:
                q = L.apply_rope(q, pos, cfg.rope_theta)
                k = L.apply_rope(k, pos, cfg.rope_theta)
            # real positions' K/V lands before attention: the causal mask
            # then covers prefix and intra-chunk keys uniformly (padded
            # positions never land)
            self.view.write_rows(l, pids, slots, k[seq_i, tok_i],
                                 v[seq_i, tok_i])
            att = paged_ops.paged_prefill_attention_batch(
                q, self.view.k_pool[l], self.view.v_pool[l], tbl, qs,
                impl=self.impl)
            x = x + (att.reshape(b, t, nq * hd)
                     @ p["attn"]["wo"].astype(cdt))
            h = L.apply_norm(cfg, p["norm2"], x)
            x = x + L.mlp_apply(cfg, p["mlp"], h)
        if not want_logits:
            return None
        x = L.apply_norm(cfg, self.params["final_norm"], x)
        w = (self.params["embed"].T if cfg.tie_embeddings
             else self.params["head"])
        return x @ w.astype(cdt)                         # [B,T,V]

    def _chunk_inputs(self, chunks, t: int, b: int | None = None):
        """Host-side inputs of a fused chunk forward padded to ``t``
        tokens: embeddings [B,T,d], positions [B,T], page tables [B,MP],
        chunk starts [B], and the write coordinates of the real positions
        (page, slot, sequence and token index of each). The coordinates
        are padded to a power of two rows (``pad_rows``); with ``b`` rows,
        to ``b * t``, and chunks past the given ones repeat the last."""
        cfg = self.cfg
        cdt = jnp.dtype(cfg.compute_dtype)
        n = len(chunks)
        b = n if b is None else b
        ps = self.view.page_size
        toks_pad = np.zeros((b, t), np.int32)
        pos_pad = np.zeros((b, t), np.int32)
        starts = np.zeros(b, np.int32)
        mp = max(-(-(start + len(toks)) // ps) for toks, _, start in chunks)
        tables = np.zeros((b, mp), np.int32)
        seq_i: list[int] = []      # write coordinates of real positions
        tok_i: list[int] = []
        pids: list[int] = []
        slots: list[int] = []
        for i, (toks, pages, start) in enumerate(chunks):
            ti = len(toks)
            toks_pad[i, :ti] = toks
            pos_pad[i] = start + np.arange(t)
            starts[i] = start
            cover = -(-(start + ti) // ps)
            tables[i, :cover] = pages[:cover]
            seq_i.extend([i] * ti)
            tok_i.extend(range(ti))
            pids.extend(int(pages[p // ps]) for p in range(start, start + ti))
            slots.extend(p % ps for p in range(start, start + ti))
        if b > n:
            for a in (toks_pad, pos_pad, starts, tables):
                a[n:] = a[n - 1]
            rows = _repeat_last(b * t, pids, slots, seq_i, tok_i)
        else:
            rows = self.view.pad_rows(pids, slots, seq_i, tok_i)
        pids, slots, seq_i, tok_i = (jnp.asarray(a) for a in rows)
        tbl = jnp.asarray(tables)
        qs = jnp.asarray(starts)

        x = self.params["embed"][jnp.asarray(toks_pad)].astype(cdt)  # [B,T,d]
        if cfg.embed_scale:
            x = x * np.sqrt(cfg.d_model)
        pos = jnp.asarray(pos_pad)                       # [B,T]
        return x, pos, tbl, qs, pids, slots, seq_i, tok_i

    # -- the MLA body -------------------------------------------------------------
    #
    # Its batches are padded to the widest batch the decoder has already run
    # at the same chunk length (or of decode steps), by repeating the last
    # row: once set-up has run the widest batch, a batch that shrinks as
    # requests finish, or a lone prompt's chunk, compiles nothing new.
    # Repeated rows write the bytes of the row they repeat to its slot again
    # and are left out of the routing counts.

    def _rows(self, key, n: int) -> int:
        b = self.widest[key] = max(n, self.widest.get(key, 0))
        return b

    def _latent_chunks(self, chunks, want_logits: bool):
        """``forward_chunks`` of the MLA body: per layer, the chunks'
        latent rows (``k_pe`` into the k pool, normalised ``ckv`` into the
        v pool) land first, then every chunk's queries read their
        sequences' latent pages through the tables in jnp
        (``paged_latent_prefill``), causal at their positions. Returns the
        padded batch's logits with ``want_logits``."""
        cfg = self.cfg
        t = max(len(toks) for toks, _, _ in chunks)
        b = self._rows(t, len(chunks))
        x, pos, tbl, qs, pids, slots, seq_i, tok_i = self._chunk_inputs(
            chunks, t, b)
        x = jax.device_put(x, self.device)
        real = np.zeros((b, t), bool)
        for i, (toks, _, _) in enumerate(chunks):
            real[i, :len(toks)] = True
        real = jnp.asarray(real.reshape(-1))
        for l, (p, j, kind) in enumerate(self.where):
            q_lat, q_pe, k_pe, ckv = _latent_in(cfg, p["norm1"], p["attn"],
                                                j, x, pos, (seq_i, tok_i))
            self.view.write_rows(l, pids, slots, k_pe, ckv)
            att = paged_ops.paged_latent_prefill(
                q_lat, q_pe, self.view.k_pool, self.view.v_pool, tbl, qs, l,
                scale=self.scale)
            x = self._latent_ffn(p, j, kind, _latent_out(cfg, p["attn"], j,
                                                         x, att), real)
        if want_logits:
            return self._latent_logits(x)
        return None

    def _latent_ffn(self, p, j, kind, x, real):
        if kind == "moe":
            x, self.routed = expert_layer(self.cfg, p["norm2"], p["moe"], j,
                                          x, self.routed, real)
            return x
        return _dense_ffn(self.cfg, p["norm2"], p["mlp"], j, x)

    def _latent_logits(self, x):
        w = (self.params["embed"].T if self.cfg.tie_embeddings
             else self.params["head"])
        return _latent_head(self.cfg, self.params["final_norm"], w, x)

    def _latent_decode_step(self, tokens, tables, lens, positions):
        """``decode_step`` of the MLA body: per layer one projection
        program, the latent row write, the latent decode kernel over the
        stacked pools (the layer picked inside it), the output program and
        the layer's FFN. Tables are widened to ``latent_table_width``.
        Returns the padded batch's logits: rows past the given ones repeat
        the last."""
        cfg = self.cfg
        ps = self.view.page_size
        n = tokens.shape[0]
        b = self._rows("decode", n)
        tokens, tables, lens, positions = _repeat_last(
            b, tokens, tables, lens, positions)
        pages = tables[np.arange(b), positions // ps]
        mp = tables.shape[1]
        tables = np.pad(tables, ((0, 0), (0, latent_table_width(mp) - mp)))
        real = jnp.asarray(np.arange(b) < n)
        x = jax.device_put(self.params["embed"][jnp.asarray(tokens)].astype(
            jnp.dtype(cfg.compute_dtype)), self.device)
        pos_b = jnp.asarray(positions[:, None])
        pages, slots, seen, tables = (jnp.asarray(a) for a in (
            pages, positions % ps, lens + 1, tables))
        for l, (p, j, kind) in enumerate(self.where):
            q_lat, q_pe, k_pe, ckv = _latent_in(cfg, p["norm1"], p["attn"],
                                                j, x, pos_b, None)
            with span("decode.kv_write"):
                self.view.write_rows(l, pages, slots, k_pe, ckv)
            with span("decode.attention"):
                att = paged_ops.paged_latent_attention(
                    q_lat, q_pe, self.view.k_pool, self.view.v_pool, tables,
                    seen, l, scale=self.scale, impl=self.impl)
            x = _latent_out(cfg, p["attn"], j, x, att)
            with (span("decode.experts") if kind == "moe"
                  else contextlib.nullcontext()):
                x = self._latent_ffn(p, j, kind, x, real)
        return self._latent_logits(x)[:, 0]

    def decode_step(self, tokens, tables, lens, positions):
        """tokens [B,1]; tables [B,MP]; lens [B]; positions [B]."""
        with span("decode.dispatch"):
            if self.cfg.mla is not None:
                return self._latent_decode_step(tokens, tables, lens,
                                                positions)
            cfg = self.cfg
            cdt = jnp.dtype(cfg.compute_dtype)
            b = tokens.shape[0]
            nq, nkv, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim_
            ps = self.view.page_size
            x = self.params["embed"][tokens].astype(cdt)     # [B,1,d]
            if cfg.embed_scale:
                x = x * np.sqrt(cfg.d_model)
            pos_b = positions[:, None].astype(jnp.int32)

            for l in range(cfg.num_layers):
                with span("decode.layer_params"):
                    p = self._layer(l)
                h = L.apply_norm(cfg, p["norm1"], x)
                q = (h @ p["attn"]["wq"].astype(cdt)).reshape(b, 1, nq, hd)
                k = (h @ p["attn"]["wk"].astype(cdt)).reshape(b, 1, nkv, hd)
                v = (h @ p["attn"]["wv"].astype(cdt)).reshape(b, 1, nkv, hd)
                if cfg.qkv_bias:
                    q = q + p["attn"]["bq"].astype(cdt).reshape(nq, hd)
                    k = k + p["attn"]["bk"].astype(cdt).reshape(nkv, hd)
                    v = v + p["attn"]["bv"].astype(cdt).reshape(nkv, hd)
                if cfg.use_rope:
                    q = L.apply_rope(q, pos_b, cfg.rope_theta)
                    k = L.apply_rope(k, pos_b, cfg.rope_theta)
                # write the batch's K/V into its pages in place: one
                # program per layer for the whole batch, at the batch's own
                # size (every op of this step compiles per batch size)
                with span("decode.kv_write"):
                    pages = jnp.take_along_axis(
                        tables, (positions // ps)[:, None], axis=1)[:, 0]
                    self.view.write_rows(l, pages, positions % ps,
                                         k[:, 0], v[:, 0])
                with span("decode.attention"):
                    att = paged_ops.paged_attention(
                        q[:, 0], self.view.k_pool[l], self.view.v_pool[l],
                        tables, lens + 1, impl=self.impl)
                x = x + (att.reshape(b, 1, nq * hd)
                         @ p["attn"]["wo"].astype(cdt))
                h = L.apply_norm(cfg, p["norm2"], x)
                x = x + L.mlp_apply(cfg, p["mlp"], h)
            x = L.apply_norm(cfg, self.params["final_norm"], x)
            w = (self.params["embed"].T if cfg.tie_embeddings
                 else self.params["head"])
            return (x @ w.astype(cdt))[:, 0]             # [B, V]


class ServeEngine:
    """Model execution over the pool; the request lifecycle — admission,
    batch composition, chunked prefill pacing, preemption — is owned by the
    :class:`RequestScheduler` (pass one in to configure priority classes and
    KV swap; the default scheduler reproduces plain continuous batching)."""

    def __init__(self, cfg: ModelConfig, params, pool,
                 max_batch: int = 8, max_new: int = 32, seed: int = 0,
                 scheduler: RequestScheduler | None = None,
                 wall_clock: bool = True, sim_step_s: float = 0.0,
                 incremental_prefill: bool = True,
                 prefix_reuse: bool = True,
                 drafter=None,
                 rehome: bool | None = None,
                 rehome_budget_frac: float = 0.5):
        if cfg.mla is not None and (drafter is not None
                                    or not incremental_prefill):
            raise ValueError(
                f"{cfg.name}: the latent (MLA) path serves greedy decode "
                "over incremental prefill only")
        self.cfg = cfg
        self.view = as_view(pool)        # the only placement surface
        self.model = LM(cfg)
        self.decoder = PagedDecoder(cfg, params, self.view)
        self.params = params
        self.scheduler = scheduler if scheduler is not None else \
            RequestScheduler(self.view, max_batch=max_batch,
                             default_max_new=max_new)
        # wall_clock=False runs the virtual clock on the Eq.-1 analytic
        # terms only — deterministic SLO numbers for benchmarks/tests;
        # sim_step_s then stands in for per-step compute time
        self.wall_clock = wall_clock
        self.sim_step_s = sim_step_s
        # incremental_prefill=False falls back to prefix recompute (the
        # bit-exactness oracle); prefix_reuse=False disables trie matching
        # (the footprint baseline benchmarks compare against)
        self.incremental_prefill = incremental_prefill
        self.view.table.prefix_reuse = prefix_reuse
        # speculative multi-token decode (DESIGN.md §7): a drafter proposes
        # continuations, the verify step accepts only what the model's own
        # argmax confirms — outputs stay token-identical to greedy. The
        # scheduler must reserve page growth and token budget for the
        # lookahead, so its spec_tokens tracks the drafter's depth.
        self.drafter = drafter
        if drafter is not None:
            self.scheduler.spec_tokens = max(self.scheduler.spec_tokens,
                                             drafter.max_tokens)
        # heat-driven re-homing (DESIGN.md §11): after each decode step,
        # migrate the hottest shared slow-domain pages into fast domains
        # under an Eq.-1 budget of `rehome_budget_frac` of the step's
        # measured stall — migration can never exceed the stall it saves.
        # Default follows the view's policy (the `coda` policy turns it
        # on); an explicit bool overrides. Heat comes from the attached
        # observatory when it has one, else from a private PageHeat.
        self.rehome = (bool(rehome) if rehome is not None
                       else bool(getattr(self.view.placement_policy,
                                         "rehome", False)))
        self.rehome_budget_frac = float(rehome_budget_frac)
        self._heat = None
        self.rehomed_pages = 0
        # cluster handoff hook (DESIGN.md §13): callbacks fired for each
        # finishing request BEFORE scheduler.finish releases its pages —
        # the ClusterRouter exports the prompt range while the trie chain
        # still has a live holder
        self._finish_cbs: list = []
        self.prefill_tokens_computed = 0   # forward-pass tokens spent on
        self.prefill_chunks_run = 0        # prefill (the O(n) vs O(n²) gap)
        self.decode_steps = 0              # steps that ran a decode batch
        self.tokens_emitted = 0            # decode tokens committed
        self.steps = 0                     # calls of step(): step_num

    # scheduler views under the pre-scheduler attribute names
    @property
    def active(self) -> list[Sequence_]:
        return self.scheduler.running

    @property
    def waiting(self) -> list[Sequence_]:
        return self.scheduler.pending

    @property
    def finished(self) -> list[Sequence_]:
        return self.scheduler.finished

    def submit(self, prompt: Sequence[int], *, cls: str | None = None,
               max_new: int | None = None,
               arrival_s: float | None = None) -> int:
        return self.scheduler.submit(prompt, cls=cls, max_new=max_new,
                                     arrival_s=arrival_s)

    def on_request_finish(self, cb) -> None:
        """Register ``cb(engine, seq)`` to run when a request finishes,
        *before* the scheduler releases its pages — the only window where
        a handoff can export the sequence's range (release may drop the
        last reference and the trie chain dies with it)."""
        self._finish_cbs.append(cb)

    # -- chunked prefill ------------------------------------------------------

    def _run_prefills(self, chunks) -> None:
        """Materialize K/V for this step's prompt chunks. Two paths:

        - **incremental** (default): O(hi-lo) per chunk — each chunk reads
          prior chunks' (and trie-shared prefix) K/V from the pool through
          the prefill-mode paged-attention op, and *all* same-step chunks
          of different sequences fuse into one batched launch
          (``PagedDecoder.forward_chunks``). Long-prompt admission is O(n)
          across chunks, and a step's prefill work is one dispatch.
        - **recompute**: forward over ``tokens[:hi]``, write [lo, hi) —
          O(hi) per chunk, O(n²) across chunks; kept as the exactness
          oracle (causal attention makes position p's K/V depend only on
          tokens[:p+1], so it equals one-shot prefill bit-for-bit).

        The last prompt token is never prefilled — the first decode step
        consumes it and writes its K/V at the true position (double-writing
        it shifted the decode RoPE position by one)."""
        chunks = [(s, lo, hi) for s, lo, hi in chunks if hi > lo]
        if not chunks:
            return
        with span("prefill"):
            if not self.incremental_prefill:
                for seq, lo, hi in chunks:
                    self._prefill_chunk_recompute(seq, lo, hi)
                return
            fused = []
            for seq, lo, hi in chunks:
                # defensive CoW: prefill chunks land in freshly-allocated
                # exclusive pages, but a fork here is what keeps a
                # mis-planned write from corrupting another sequence's
                # shared prefix
                self.view.ensure_writable(seq.pages, lo, hi)
                self.prefill_chunks_run += 1
                self.prefill_tokens_computed += hi - lo
                fused.append((seq.tokens[lo:hi], seq.pages, lo))
            self.decoder.forward_chunks(fused)
            for seq, lo, hi in chunks:
                seq.length = hi
                self._register_if_done(seq, hi)

    def _prefill_chunk_recompute(self, seq: Sequence_, lo: int, hi: int):
        self.view.ensure_writable(seq.pages, lo, hi)
        self.prefill_chunks_run += 1
        self.prefill_tokens_computed += hi
        ps = self.view.page_size
        toks = jnp.asarray([seq.tokens[:hi]], jnp.int32)
        x = self.model.embed(self.params, {"tokens": toks})
        pos = jnp.arange(hi, dtype=jnp.int32)[None]
        _, _, caches = self.model.hidden(self.params, x, pos,
                                         want_cache=True)
        kv = caches[0]  # single dense group: {"k": [L,1,S,nkv,hd] or list}
        if isinstance(kv, list):
            k = jnp.stack([c["k"][0] for c in kv])   # [L,S,nkv,hd]
            v = jnp.stack([c["v"][0] for c in kv])
        else:
            k, v = kv["k"][:, 0], kv["v"][:, 0]
        positions = np.arange(lo, hi)
        pids, slots, rows = self.view.pad_rows(
            [seq.pages[p // ps] for p in positions], positions % ps,
            positions)
        # one write of the whole chunk, every layer
        self.view.write_rows(0, pids, slots, k[:, rows], v[:, rows])
        seq.length = hi
        self._register_if_done(seq, hi)

    def _register_if_done(self, seq: Sequence_, hi: int) -> None:
        """Final chunk just landed: the prompt pages' bytes are now real —
        only now may they enter the prefix trie (registering any earlier
        lets a matcher reference pages that were never written)."""
        if hi >= seq.prefill_target:
            self.view.register_prefix(seq.tokens, seq.pages,
                                      seq.prefill_target)

    def step(self) -> dict:
        self.steps += 1
        with jax.profiler.StepTraceAnnotation(SPAN_PREFIX + "step",
                                              step_num=self.steps):
            t0 = time.monotonic()
            with span("schedule"):
                plan = self.scheduler.schedule()
            self._run_prefills(plan.prefill_chunks)
            batch = plan.batch
            if not batch and not plan.prefill_chunks:
                self.scheduler.advance(plan.swap_seconds)
                return {"active": 0, "pending": len(self.scheduler.pending)}
            done: list[Sequence_] = []
            produced_before = {s.sid: s.produced for s in batch}
            groups = plan.launch_groups
            if batch:
                drafts = self._draft(batch)
                if drafts is not None:
                    # the verify path fuses the whole batch into one
                    # prefill-mode launch; micro-batching applies to plain
                    # greedy decode only
                    groups = None
                    with span("decode"):
                        self._verify_step(batch, drafts)
                else:
                    self._greedy_step(batch, groups)
                self.decode_steps += 1
                for s in batch:
                    if s.produced >= s.max_new:
                        done.append(s)
            wall = time.monotonic() - t0
            with span("account"):
                return self._account(plan, batch, groups, done,
                                     produced_before, wall)

    def _account(self, plan, batch, groups, done, produced_before,
                 wall) -> dict:
        """Everything a step does after its model work: the Eq.-1 bill and
        the virtual clock, observatory hooks, first-token and finish
        callbacks, the DWP tuner and migration, re-homing, telemetry."""
        # latency signal = wall clock + analytic BWAP read time + swap
        # transfer time (the CPU has no real memory-domain asymmetry;
        # the Eq.-1 model supplies it); prefill-only steps read no KV, and
        # sampling them would dilute the per-domain stall rings with zeros.
        # The read set is every *physical* page the decode batch gathered:
        # finishing sequences' pages count (the step that produced their
        # final token read them — dropping them fed the DWP tuner an
        # underestimated stall signal on every completing step), and a trie
        # page shared by several holders is billed once, not once per
        # holder (Eq. 1 models resident bytes, and the kernel reads each
        # physical page once per launch).
        read_pages = list(dict.fromkeys(
            p for s in batch for p in s.pages)) if batch else []
        launches = None
        if batch and groups is not None:
            # compute-follows-data: one Eq.-1 bill per launch — the step
            # stall is the max over per-launch bottlenecks, since launches
            # to different domain groups overlap (DESIGN.md §11)
            launches = []
            for dom, grp in groups:
                rp = list(dict.fromkeys(p for s in grp for p in s.pages))
                launches.append(
                    (dom, rp,
                     max(self.view.expected_read_time(rp), 0.0)))
            sim = max(t for _, _, t in launches)
        elif batch:
            sim = max(self.view.expected_read_time(read_pages), 0.0)
        else:
            sim = 0.0
        dt = ((wall if self.wall_clock else 0.0) + sim + plan.swap_seconds
              + (self.sim_step_s if batch else 0.0))
        v0 = self.scheduler.now
        self.scheduler.advance(dt)
        # bytes-weighted heat: a sequence's partial tail page streams
        # fewer bytes than an interior page and must not look equally hot
        read_weights = self._page_read_weights(batch) if batch else {}
        obs = self.view.fabric.obs
        if obs is not None:
            # spans for this step's prefill chunks + decode batch, page
            # heat touches, and (probe-equipped) the batch-read drift
            # pairs — one per launch in micro-batch mode
            obs.on_engine_step(self.view, plan, batch, read_pages,
                               sim, v0, dt, launches=launches,
                               read_weights=read_weights)
        for s in batch:
            if produced_before[s.sid] == 0 and s.produced > 0:
                self.scheduler.notice_first_token(s)
        for s in done:
            for cb in self._finish_cbs:
                cb(self, s)
            self.scheduler.finish(s)
        moved = False
        if batch:
            # the DWP tuner judges *placement*: feed it the step latency
            # minus swap transfers — a preemption spike says nothing about
            # where the live pages sit and would trigger spurious re-homing
            if self.view.record_latency(dt - plan.swap_seconds):
                # the tuner moved the allocation cycle: re-home live
                # sequences (batched gather/scatter through the executor);
                # shared pages are pinned and refcounts follow the moves
                for s in self.scheduler.running:
                    s.pages = self.view.migrate(s.pages)
                moved = True
        rehomed = 0
        if self.rehome and batch:
            rehomed = self._rehome_step(obs, read_pages, read_weights, sim)
        tel = self.view.snapshot()
        return {"active": len(self.scheduler.running),
                "latency": dt, "migrated": moved, "rehomed": rehomed,
                "launches": (len(groups) if groups is not None
                             else (1 if batch else 0)),
                "dwp": self.view.dwp,
                "occupancy": self.view.occupancy(),
                "swapped": len(self.scheduler.swapped),
                "swapped_out": len(plan.swapped_out),
                "swapped_in": len(plan.swapped_in),
                # one stats() pass per step: the view snapshot carries
                # the page-table block alongside the domain counters
                "pagetable": tel["pagetable"],
                "prefill_tokens_computed": self.prefill_tokens_computed,
                "decode_steps": self.decode_steps,
                "tokens_emitted": self.tokens_emitted,
                "spec": tel["spec"],
                "telemetry": tel}

    # -- decode: greedy single-token and speculative multi-token --------------

    def _draft(self, batch) -> list[list[int]] | None:
        """Ask the drafter for each sequence's proposal, capped at the
        scheduler's reserved lookahead and the sequence's remaining token
        allowance (drafting past ``max_new`` would be rolled back anyway).
        Returns None when there is nothing to verify — the plain decode
        kernel is cheaper than a 1-token verify launch."""
        if self.drafter is None:
            return None
        k = self.scheduler.spec_tokens
        drafts = []
        for s in batch:
            allowed = s.max_new - s.produced     # >= 1: finished seqs left
            d = self.drafter.draft(s.tokens)[:min(k, allowed - 1)] \
                if allowed > 1 else []
            drafts.append([int(t) for t in d])
        return drafts if any(drafts) else None

    def _greedy_step(self, batch, groups=None) -> None:
        ps = self.view.page_size
        # grow pages where needed (the scheduler reserved capacity);
        # a decode write into a shared page — the full-prompt-match
        # case: position prompt_len-1 lives in a trie page — forks it.
        # Growth always runs over the FULL batch in global order — even in
        # micro-batch mode — so page ids (and therefore everything
        # downstream) are bit-identical to a single global launch.
        # Constant-footprint geometries (SSM state, DESIGN.md §12) never
        # append: their one state page absorbs every step in place.
        if self.view.geometry.grows:
            with span("pages"):
                for s in batch:
                    if s.length % ps == 0:
                        self.view.append_page(s.pages)
                    else:
                        self.view.fork_for_write(s.pages, s.length // ps)
        if groups is not None:
            # compute-follows-data (DESIGN.md §11): one launch per domain
            # group. Each row's attention reads only its own page table and
            # argmax is per-row, so the partition cannot change tokens.
            for _dom, grp in groups:
                self._decode_launch(grp)
        else:
            self._decode_launch(batch)
        self.tokens_emitted += len(batch)

    def _decode_launch(self, seqs) -> None:
        """One decode launch over ``seqs`` (the whole batch, or one
        per-domain micro-batch)."""
        with span("decode"):
            mp = max(len(s.pages) for s in seqs)
            tables = np.zeros((len(seqs), mp), np.int32)
            for i, s in enumerate(seqs):
                tables[i, :len(s.pages)] = s.pages
            lens = np.asarray([s.length for s in seqs], np.int32)
            toks = np.asarray([[s.tokens[-1]] for s in seqs], np.int32)
            logits = self.decoder.decode_step(
                jnp.asarray(toks), jnp.asarray(tables), jnp.asarray(lens),
                jnp.asarray(lens))
            with span("decode.sync"):
                nxt = np.asarray(jnp.argmax(logits, axis=-1))
        for s, t in zip(seqs, nxt):
            s.tokens.append(int(t))
            s.length += 1          # the decoded token's K/V is now pooled

    # -- compute-follows-data: heat + re-homing (DESIGN.md §11) ---------------

    def _page_read_weights(self, batch) -> dict[int, float]:
        """Fraction of each physical page this step's gather actually
        streamed: interior pages read in full, a sequence's tail page only
        up to its committed length. A page that is one holder's partial
        tail but another's interior counts as a full read."""
        ps = self.view.page_size
        out: dict[int, float] = {}
        for s in batch:
            for i, p in enumerate(s.pages):
                if p < 0:
                    continue
                frac = min(1.0, max(0.0, (s.length - i * ps) / ps))
                if frac > out.get(p, 0.0):
                    out[p] = frac
        return out

    def _own_heat(self):
        """Private heat map for policy-driven re-homing when no
        observatory (or a heatless one) is attached."""
        if self._heat is None:
            from repro.obs.heat import PageHeat
            heat = PageHeat(self.view.pool)
            self.view.fabric.subscribe(
                "free", lambda page=-1, **_: heat.on_free(page=page))
            self._heat = heat
        return self._heat

    def _rehome_step(self, obs, read_pages, read_weights, sim) -> int:
        """Post-step re-homing: pull the hottest shared slow-domain pages
        into fast domains, spending at most ``rehome_budget_frac`` of this
        step's Eq.-1 stall. The spent seconds advance the virtual clock —
        migration traffic is real traffic."""
        if obs is not None and obs.heat is not None:
            heat = obs.heat          # the observatory already touched it
        else:
            heat = self._own_heat()
            heat.touch(read_pages,
                       weights=[read_weights.get(p, 1.0)
                                for p in read_pages])
            heat.step()
        budget = self.rehome_budget_frac * sim
        if budget <= 0.0:
            return 0
        moves, secs = self.view.rehome_hot(heat, budget_s=budget)
        if not moves:
            return 0
        v0 = self.scheduler.now
        self.scheduler.advance(secs)
        self.rehomed_pages += len(moves)
        if obs is not None:
            obs.on_rehome(self.view, v0, secs, len(moves))
        return len(moves)

    def _verify_step(self, batch, drafts) -> None:
        """Speculative multi-token decode (DESIGN.md §7). Per sequence the
        chunk ``[tokens[-1], draft...]`` writes K/V at positions
        ``[length, length + d]`` and runs through one batched prefill-mode
        attention launch; the longest draft prefix the model's own argmax
        confirms is accepted, plus one bonus token from the first
        disagreeing position — so every verify step emits >= 1 token and
        outputs are token-identical to greedy decoding.

        Rejected speculation rolls back *exactly*: snapshotted K/V bytes
        are written back, pages greedy would not yet have allocated
        return to the allocator LIFO with the allocation cycle rewound
        (``pool.undo_alloc``), and their references leave the table
        (``table.pop_page``). The unwind runs in **reverse batch order** —
        the step's allocations form one stack across sequences, so only a
        right-to-left unwind restores free-list order and lets the cycle
        rewinds chain. A single speculating sequence is then bit-identical
        to its greedy run (``tests/test_spec_decode.py`` drives this
        property); with several sequences speculating past page boundaries
        in one step, a kept page allocated between two rejected ones pins
        the cycle, so page *ids* may permute across sequences vs greedy —
        tokens, refcount structure, and leak-freedom still hold exactly
        (DESIGN.md §7.3). CoW forks never need undoing: the only forkable
        write position is ``length`` (the committed token — draft
        positions land in the forked clone or in fresh pages), and at
        least one token always commits."""
        ps = self.view.page_size
        recs = []                       # per seq: (appended allocs, snap base)
        chunks = []
        snap_pids: list[int] = []
        snap_slots: list[int] = []
        for s, d in zip(batch, drafts):
            lo = s.length
            if lo % ps:
                self.view.fork_for_write(s.pages, lo // ps)
            appended = []               # (pid, marker_before, marker_after)
            while len(s.pages) * ps <= lo + len(d):
                m0 = self.view.alloc_marker()
                pid = self.view.append_page(s.pages)
                appended.append((pid, m0, self.view.alloc_marker()))
            base = len(snap_pids)
            for p in range(lo + 1, lo + len(d) + 1):   # speculative slots
                snap_pids.append(int(s.pages[p // ps]))
                snap_slots.append(p % ps)
            recs.append((appended, base))
            chunks.append(([s.tokens[-1]] + d, s.pages, lo))
        snap_k = snap_v = None
        if snap_pids:
            # pre-write bytes of every speculative slot, all layers at once
            snap_k = self.view.k_pool[:, snap_pids, snap_slots]
            snap_v = self.view.v_pool[:, snap_pids, snap_slots]
        logits = self.decoder.forward_chunks(chunks, want_logits=True)
        nxt = np.asarray(jnp.argmax(logits, axis=-1))   # [B,T]
        drafted = accepted = emitted = 0
        rest_idx: list[int] = []        # snapshot rows to write back
        rest_pids: list[int] = []
        rest_slots: list[int] = []
        for i, (s, d) in enumerate(zip(batch, drafts)):
            lo = s.length
            allowed = s.max_new - s.produced
            a = 0
            while a < len(d) and a + 1 < allowed and int(nxt[i, a]) == d[a]:
                a += 1
            emit = a + 1                # accepted drafts + the bonus token
            s.tokens.extend(int(nxt[i, j]) for j in range(emit))
            s.length = lo + emit        # committed K/V: positions lo..lo+a
            drafted += len(d)
            accepted += a
            emitted += emit
            appended, base = recs[i]
            for j in range(emit, len(d) + 1):   # rejected: lo+emit..lo+d
                rest_idx.append(base + j - 1)
                rest_pids.append(snap_pids[base + j - 1])
                rest_slots.append(snap_slots[base + j - 1])
        # unwind rejected page allocations strictly right-to-left: the
        # step's allocations are one stack across the whole batch, so only
        # reverse order puts pages back in LIFO position and keeps each
        # undo_alloc's cycle-marker check satisfied for the next one
        for s, (appended, _) in zip(reversed(batch), reversed(recs)):
            keep = -(-s.length // ps)   # pages greedy would hold right now
            while len(s.pages) > keep:
                pid, m0, m1 = appended.pop()
                popped = self.view.pop_page(s.pages)
                assert popped == pid, "speculative page stack out of order"
                self.view.undo_alloc(pid, m0, m1)
        if rest_idx:
            pids, slots, idx = self.view.pad_rows(rest_pids, rest_slots,
                                                  rest_idx)
            self.view.write_rows(0, pids, slots, snap_k[:, idx],
                                 snap_v[:, idx])
        self.tokens_emitted += emitted
        self.view.telemetry.record_spec(drafted, accepted, emitted)
