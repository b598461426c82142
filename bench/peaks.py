"""Per-chip peaks, keyed by ``jax.Device.device_kind``.

Source: Google Cloud documentation, "TPU v5e" (system architecture): one
v5e chip peaks at 197 TFLOP/s in bf16 and 393 TOP/s in int8, holds 16 GB
of HBM at 819 GB/s, and has 1,600 Gbit/s of chip-to-chip interconnect.

A float32 matmul at the TPU's default precision is one bf16 MXU pass, so
the bf16 peak is the ceiling of every matmul the benchmarked paths run.
A kind that is not in :data:`PEAKS` is an error, never a default: a share
computed against another chip's peaks is a wrong number.
"""
from __future__ import annotations

import dataclasses
from typing import Dict


@dataclasses.dataclass(frozen=True)
class ChipPeaks:
    flops: float        # FLOP/s, bf16 MXU
    hbm_bw: float       # bytes/s
    hbm_bytes: float    # bytes of device memory


PEAKS: Dict[str, ChipPeaks] = {
    "TPU v5 lite": ChipPeaks(flops=197e12, hbm_bw=819e9, hbm_bytes=16e9),
}


def peaks_for(device_kind: str) -> ChipPeaks:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no peaks for device kind {device_kind!r}; known: "
                       f"{sorted(PEAKS)}") from None


def least_seconds(flops: float, nbytes: float, device_kind: str) -> float:
    """The least time the chip could take for ``flops`` and ``nbytes``: the
    larger of the compute bound and the memory bound."""
    p = peaks_for(device_kind)
    return max(flops / p.flops, nbytes / p.hbm_bw)
