"""Published per-chip peaks, keyed by ``jax.Device.device_kind``.

The benchmark's own copy of the table: every roofline and MFU share the
benchmark reports divides by a number from here, so a change to the
program cannot move the yardstick.  A device kind that is not in the table
is an error, never a borrowed number.

Source: Google Cloud documentation, "TPU v5e" (system architecture): per
chip 197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM at 819 GB/s.  JAX names
that chip ``"TPU v5 lite"``.
"""
from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class ChipPeaks:
    bf16_flops: float        # FLOP/s
    int8_ops: float          # OP/s
    hbm_bytes: float         # bytes of HBM
    hbm_bw: float            # bytes/s


PEAKS = {
    "TPU v5 lite": ChipPeaks(bf16_flops=197e12, int8_ops=393e12,
                             hbm_bytes=16e9, hbm_bw=819e9),
}


def peaks_for(device_kind: str) -> ChipPeaks:
    """The kind's peaks; a kind missing from the table raises KeyError."""
    if device_kind not in PEAKS:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r} (known: {sorted(PEAKS)})")
    return PEAKS[device_kind]
