"""Per-platform chip peaks: the one table every chip-relative gauge reads.

Keyed by the **exact** ``device_kind`` string the TPU runtime publishes,
with bandwidth next to compute so the roofline gauge has a ridge point, and
the source of every row beside it.

Null-not-fake: anything not in the table — CPU, GPU, a TPU this repository
has not run on — resolves to ``None``, never a peak guessed from a similar
name.  A fabricated MFU is worse than no MFU (the 195%-MFU lesson in
`bench/mfu.py`); on the chip path an unknown kind is an error
(`chip_smoke.py`), not a default.
"""

from __future__ import annotations

import dataclasses
from typing import Optional


@dataclasses.dataclass(frozen=True)
class ChipPeaks:
    """Public per-chip peaks (bf16 matmul compute + HBM bandwidth)."""

    kind: str                # canonical table key, lowercase
    tflops_bf16: float       # peak bf16 TFLOP/s per chip
    hbm_gbps: float          # peak HBM bandwidth, GB/s per chip

    @property
    def ridge_flops_per_byte(self) -> float:
        """Roofline ridge point: programs below this arithmetic intensity
        are bandwidth-bound at peak, above it compute-bound."""
        return self.tflops_bf16 * 1e12 / (self.hbm_gbps * 1e9)


# device_kind as the TPU runtime publishes it (lowercased for lookup) →
# peaks, one row per part this repository has run on.  A run on another
# part adds its row, with its source, first.
CHIP_TABLE = {
    # Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16 and 16 GB of
    # HBM at 819 GB/s per chip; the runtime reports the part as
    # "TPU v5 lite" (chip_smoke.py prints it).
    "tpu v5 lite": ChipPeaks("tpu v5 lite", 197.0, 819.0),
}


def resolve_kind(device_kind: str) -> Optional[ChipPeaks]:
    """The table row whose key IS the lowercased kind, else None."""
    return CHIP_TABLE.get((device_kind or "").strip().lower())


def chip_peaks(device) -> Optional[ChipPeaks]:
    """Peaks for a jax device (or a raw device_kind string).  ``None``
    for CPU/GPU/unknown — callers must treat that as "no chip-relative
    number", never substitute a default."""
    if isinstance(device, str):
        return resolve_kind(device)
    kind = getattr(device, "device_kind", "") or ""
    if "tpu" not in kind.lower() and getattr(device, "platform", "") != "tpu":
        return None
    return resolve_kind(kind)


def chip_peak_tflops(device) -> Optional[float]:
    """bf16 peak for a jax device, or None when unknown (bench/mfu.py
    delegates here)."""
    peaks = chip_peaks(device)
    return peaks.tflops_bf16 if peaks else None
