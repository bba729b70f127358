"""Published per-chip peaks, keyed by JAX's ``device_kind``.

Source: Google Cloud documentation, "TPU v5e" (system architecture
table): 819 GB/s HBM bandwidth, 1,600 Gbit/s inter-chip interconnect,
197 TFLOP/s bf16 and 16 GB HBM per chip. Copied from the program's
``utils/profiling.CHIP_PEAKS``. A kind that is not in the table is an
error, never a default: a share of another chip's roof is a wrong
number.
"""

from __future__ import annotations

from dataclasses import dataclass


@dataclass(frozen=True)
class ChipPeaks:
    hbm_bytes_per_s: float
    ici_bytes_per_s: float  # all links of one chip
    flops_per_s: float  # bf16
    hbm_bytes: float


PEAKS = {
    "TPU v5 lite": ChipPeaks(
        hbm_bytes_per_s=819e9,
        ici_bytes_per_s=1600e9 / 8,
        flops_per_s=197e12,
        hbm_bytes=16e9,
    ),
}


def peaks(device_kind: str) -> ChipPeaks:
    """The published peaks of one chip; raises for an unknown kind."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(
            f"no published peaks for device kind {device_kind!r} "
            f"(known: {sorted(PEAKS)})"
        ) from None
