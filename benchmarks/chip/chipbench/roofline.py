"""Peaks of each chip, and the operations and bytes that the work of a
served step needs, computed from its shapes: the yardstick of the
benchmark's roofline and utilization metrics.

The counts are of what the algorithm needs, not of what a kernel happens
to do, so they read the same work whatever implements it:

- paged attention reads the K and V pages that hold a sequence's tokens
  (whole pages, since a page is the unit the table maps) and the queries,
  and writes the outputs; a query at absolute position ``p`` attends over
  ``p + 1`` keys with one multiply-add each for the scores and one for the
  values;
- a model token costs two operations per weight of every matrix it passes
  through (the attention projections, the MLP and the output head; the
  embedding lookup is a read), plus its attention over its context.
"""

from __future__ import annotations

import dataclasses

BF16 = 2                 # bytes of a served K/V, query or output element


@dataclasses.dataclass(frozen=True)
class Peaks:
    flops: float         # dense bf16 FLOP/s
    hbm_bytes_s: float   # HBM bytes/s
    hbm_bytes: float     # HBM capacity
    source: str


# Keyed by ``device.device_kind`` as JAX reports it.
PEAKS = {
    "TPU v5 lite": Peaks(197e12, 819e9, 16e9,
                         'Google Cloud documentation, "TPU v5e": 197 TFLOP/s '
                         "bf16, 819 GB/s and 16 GB of HBM per chip"),
}


def peaks(device_kind: str) -> Peaks:
    """The peaks of ``device_kind``; a device not in the table is an
    error, never a default."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no peaks for device kind {device_kind!r}; add it "
                       f"to PEAKS with its source (known: {sorted(PEAKS)})"
                       ) from None


@dataclasses.dataclass
class Work:
    flops: float = 0.0
    bytes: float = 0.0

    def seconds(self, p: Peaks) -> float:
        """The least time the chip could take: the larger of the compute and
        the memory bound."""
        return max(self.flops / p.flops, self.bytes / p.hbm_bytes_s)


def launches_seconds(cfg: dict, launches, p: Peaks) -> float:
    """Least device time of one launch per layer of each of ``launches``
    (one layer's work each)."""
    return cfg["num_hidden_layers"] * sum(w.seconds(p) for w in launches)


def _dims(cfg: dict):
    return (cfg["num_attention_heads"], cfg["num_key_value_heads"],
            cfg["head_dim"])


def _pages(tokens: int, page_size: int) -> int:
    return -(-tokens // page_size)


def decode_attention(cfg: dict, page_size: int, contexts) -> Work:
    """One layer's decode launch: each sequence's new query attends over
    ``contexts[b]`` keys (its committed tokens plus the new one)."""
    nq, nkv, hd = _dims(cfg)
    page = page_size * nkv * hd * BF16
    w = Work()
    for c in contexts:
        w.flops += 4.0 * c * nq * hd
        w.bytes += 2 * _pages(c, page_size) * page + 2 * nq * hd * BF16
    return w


def prefill_attention(cfg: dict, page_size: int, chunks) -> Work:
    """One layer's batched prefill launch over ``chunks`` of
    ``(start, length)``: query ``i`` of a chunk sits at ``start + i`` and
    attends causally over ``start + i + 1`` keys."""
    nq, nkv, hd = _dims(cfg)
    page = page_size * nkv * hd * BF16
    w = Work()
    for start, n in chunks:
        keys = n * start + n * (n + 1) / 2       # sum of start + i + 1
        w.flops += 4.0 * keys * nq * hd
        w.bytes += (2 * _pages(start + n, page_size) * page
                    + 2 * n * nq * hd * BF16)
    return w


def matmul_params(cfg: dict, head: bool = True) -> int:
    """Weights a token passes through in matrix products: every layer's
    projections and MLP, and with ``head`` the output head (a prompt token
    that only fills the cache needs no logits)."""
    nq, nkv, hd = _dims(cfg)
    d, ff = cfg["hidden_size"], cfg["intermediate_size"]
    layer = d * (nq + 2 * nkv) * hd + nq * hd * d + 3 * d * ff
    return (cfg["num_hidden_layers"] * layer
            + (d * cfg["vocab_size"] if head else 0))


def token_flops(cfg: dict, context: int, head: bool = True) -> float:
    """Model operations of one token that attends over ``context`` keys."""
    nq, _, hd = _dims(cfg)
    return (2.0 * matmul_params(cfg, head)
            + cfg["num_hidden_layers"] * 4.0 * context * nq * hd)


def chunk_flops(cfg: dict, start: int, n: int) -> float:
    """Model operations of a prefill chunk of ``n`` prompt tokens from
    position ``start``: no logits, and causal attention."""
    nq, _, hd = _dims(cfg)
    keys = n * start + n * (n + 1) / 2
    return (2.0 * matmul_params(cfg, head=False) * n
            + cfg["num_hidden_layers"] * 4.0 * keys * nq * hd)
