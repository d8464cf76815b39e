"""Published peaks of each chip, keyed by the ``device_kind`` JAX reports.

Source: Google Cloud documentation, "TPU v5e" (system architecture): per
chip 197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM2 at 819 GB/s.  No float32
peak is published; shares of a peak use the bf16 figure.  A kind that is
not in the table is an error, never a default.
"""
from __future__ import annotations

import dataclasses

__all__ = ["Peak", "PEAKS", "peak_for"]


@dataclasses.dataclass(frozen=True)
class Peak:
    flops: float          # FLOP/s per chip (bf16 matmul)
    hbm_bw: float         # bytes/s per chip
    hbm_bytes: float      # bytes per chip


PEAKS = {
    "TPU v5 lite": Peak(flops=197e12, hbm_bw=819e9, hbm_bytes=16e9),
}


def peak_for(device_kind: str) -> Peak:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(f"no published peak for device kind "
                         f"{device_kind!r}; known: {sorted(PEAKS)}") from None
