"""Peaks of each chip, the work of a launch as operations and bytes, and the
least time the chip could take for it: the yardstick of the benchmark's
roofline and utilization metrics.

Each architecture's layout (``layouts/<reference>.py``) counts the work of
a served step from its shapes. The counts are of what the algorithm needs,
not of what a kernel happens to do, so they read the same work whatever
implements it.
"""

from __future__ import annotations

import dataclasses


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
