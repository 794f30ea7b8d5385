"""Token-transfer threshold q (paper §4.4, Eq. 4), adapted to TPU v5e.

Paper (GPU):   q > phi * d_type / (2 * beta),  beta = PCIe bandwidth.
TPU adaptation: the fetch source is peer HBM over ICI and the fetch primitive
is a dense all_to_all whose ring cost scales the effective bandwidth by ~1/G
(DESIGN.md §2), so:

    q > phi * d_type / (2 * beta_ici / G_penalty)

with G_penalty = G for the dense a2a fetch (zeros ride the wire) and 1 for a
hypothetical sparse fetch. The estimator exposes both so benchmarks can show
the trade-off.
"""
from __future__ import annotations

from dataclasses import dataclass

import jax


@dataclass(frozen=True)
class HardwareSpec:
    """Per-chip peaks Eq. 4 and the cost models read."""
    peak_flops: float                # bf16 FLOP/s
    hbm_bw: float                    # B/s
    ici_bw: float                    # B/s per link
    dtype_bytes: int = 2             # bf16


# Per-chip peaks keyed by ``jax.Device.device_kind``.  Source: Google Cloud
# TPU documentation, "TPU v5e": 197 TFLOP/s bf16, 16 GB HBM at 819 GB/s,
# 1,600 Gbit/s of chip-to-chip interconnect over 4 links (50 GB/s each).
PEAKS = {
    "TPU v5 lite": HardwareSpec(peak_flops=197e12, hbm_bw=819e9,
                                ici_bw=50e9),
}

# The chip the CPU backend stands in for: CPU runs (tests, simulator)
# model a v5e, they never measure one.
V5E = PEAKS["TPU v5 lite"]


def device_hardware(device=None) -> HardwareSpec:
    """Peaks of ``device`` (default: the first device JAX sees).  A TPU
    kind missing from ``PEAKS`` raises; a non-TPU backend gets the
    modelled v5e target."""
    dev = device if device is not None else jax.devices()[0]
    if dev.platform != "tpu":
        return V5E
    if dev.device_kind not in PEAKS:
        raise KeyError(f"no peaks for TPU kind {dev.device_kind!r}; add a "
                       f"row with its source to qthreshold.PEAKS")
    return PEAKS[dev.device_kind]


def q_threshold(hw: HardwareSpec = V5E, *, ep_degree: int = 1,
                dense_fetch: bool = True) -> int:
    """Eq. 4 with the ICI substitution. Returns a per-chunk token count."""
    penalty = ep_degree if dense_fetch else 1
    beta_eff = hw.ici_bw / max(penalty, 1)
    q = hw.peak_flops * hw.dtype_bytes / (2.0 * beta_eff)
    return int(q) + 1


def expert_fetch_seconds(expert_bytes: float, hw: HardwareSpec = V5E, *,
                         ep_degree: int = 1, dense_fetch: bool = True) -> float:
    penalty = ep_degree if dense_fetch else 1
    return expert_bytes * penalty / hw.ici_bw


def expert_compute_seconds(tokens: float, d_model: int, d_ff: int,
                           n_matrices: int, hw: HardwareSpec = V5E) -> float:
    flops = 2.0 * tokens * d_model * d_ff * n_matrices
    return flops / hw.peak_flops
