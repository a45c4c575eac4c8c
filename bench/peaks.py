"""Published peak rates of one chip, keyed by ``jax.Device.device_kind``.

Source: Google Cloud documentation, "TPU v5e" (system architecture page):
197 TFLOP/s bf16, 16 GB of HBM at 819 GB/s per chip.  A float32 matmul at
JAX's default precision runs as bf16 passes on the MXU, so the bf16 peak is
the ceiling for the fp32 training step too.

A device that is not in the table is an error, never a default.
"""
from __future__ import annotations

import dataclasses

__all__ = ["Peaks", "PEAKS", "peaks_for"]


@dataclasses.dataclass(frozen=True)
class Peaks:
    flops_per_s: float        # dense bf16 matmul peak
    hbm_bytes_per_s: float
    source: str


PEAKS = {
    "TPU v5 lite": Peaks(flops_per_s=197e12, hbm_bytes_per_s=819e9,
                         source='Google Cloud documentation, "TPU v5e"'),
}


def peaks_for(device_kind: str) -> Peaks:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(
            f"no peak rates for device kind {device_kind!r}; the table has "
            f"{sorted(PEAKS)}") from None
