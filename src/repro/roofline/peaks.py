"""Per-chip peaks for the roofline, keyed by ``jax.Device.device_kind``.

Source: Google Cloud documentation, "TPU v5e" (system architecture): one
v5e chip peaks at 197 TFLOP/s in bf16 and 393 TOP/s in int8, holds 16 GB
of HBM at 819 GB/s, and has 1,600 Gbit/s of chip-to-chip interconnect
(four links, so 50 GB/s per link).

A kind that is not in :data:`PEAKS` is an error, never a default: a
roofline share computed against another chip's peaks is a wrong number.
"""
from __future__ import annotations

import dataclasses
from typing import Dict


@dataclasses.dataclass(frozen=True)
class ChipPeaks:
    flops_bf16: float        # FLOP/s
    hbm_bw: float            # B/s
    ici_bw: float            # B/s per link


# the device_kind JAX reports for a TPU v5e chip
V5E = "TPU v5 lite"

PEAKS: Dict[str, ChipPeaks] = {
    V5E: ChipPeaks(flops_bf16=197e12, hbm_bw=819e9, ici_bw=50e9),
}


def peaks_for(device_kind: str) -> ChipPeaks:
    """The peaks of ``device_kind``; raises ``KeyError`` for a kind with no
    entry in :data:`PEAKS`."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no roofline peaks for device kind {device_kind!r}; known: "
            f"{sorted(PEAKS)} — add an entry with its published source"
        ) from None
