"""Published per-chip peaks, keyed by ``jax.Device.device_kind``.

The one table every roofline, MFU and link-bandwidth figure divides by.  A
device kind that is not here has no peaks: callers report no utilization
for it (``lookup``) or refuse to run (``peaks_for``), never a borrowed
number.

Source: Google Cloud documentation, "TPU v5e" (system architecture): per
chip 197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM at 819 GB/s, and
1,600 Gbit/s of inter-chip interconnect over 4 links (50 GB/s per link).
JAX names that chip ``"TPU v5 lite"``.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, Optional


@dataclasses.dataclass(frozen=True)
class ChipPeaks:
    bf16_flops: float        # FLOP/s
    int8_ops: float          # OP/s
    hbm_bytes: float         # bytes of HBM
    hbm_bw: float            # bytes/s
    ici_link_bw: float       # bytes/s per ICI link


V5E = "TPU v5 lite"

PEAKS: Dict[str, ChipPeaks] = {
    V5E: ChipPeaks(bf16_flops=197e12, int8_ops=393e12, hbm_bytes=16e9,
                   hbm_bw=819e9, ici_link_bw=50e9),
}


def lookup(device_kind: Optional[str]) -> Optional[ChipPeaks]:
    """The kind's peaks, or None when the table does not have it."""
    return PEAKS.get(device_kind) if device_kind else None


def peaks_for(device_kind: str) -> ChipPeaks:
    """The kind's peaks; a kind missing from the table is an error."""
    p = lookup(device_kind)
    if p is None:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r} (known: {sorted(PEAKS)})")
    return p
