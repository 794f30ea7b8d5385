"""Per-chip peaks, keyed by ``jax.Device.device_kind``.

Source: Google Cloud documentation, "TPU v5e": 197 TFLOP/s in bf16,
393 TOP/s in int8, 16 GB of HBM at 819 GB/s, 1,600 Gbit/s of
chip-to-chip interconnect (four links of 50 GB/s).  A device that is not
in the table is an error, never a default.
"""
from __future__ import annotations

from dataclasses import dataclass


@dataclass(frozen=True)
class Peaks:
    bf16_flops: float        # FLOP/s
    hbm_bytes_per_s: float   # B/s
    hbm_bytes: float         # B
    ici_bytes_per_s: float   # B/s per link


PEAKS = {
    "TPU v5 lite": Peaks(bf16_flops=197e12, hbm_bytes_per_s=819e9,
                         hbm_bytes=16e9, ici_bytes_per_s=50e9),
}


def peaks_of(device_kind: str) -> Peaks:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no peaks for device kind {device_kind!r}; add a "
                       f"row with its source to chipbench/peaks.py") from None
