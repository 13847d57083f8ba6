"""Published peaks of the chips the benchmark may run on, keyed by JAX's
``device_kind``. A device that is not in the table is an error, never a
default: a share of a guessed peak is no measurement.

TPU v5e ("TPU v5 lite"): Google Cloud documentation, "TPU v5e" system
architecture page — 197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM at
819 GB/s. No float32 peak is published; shares of float32 work are taken
against the bf16 peak, so they read low, never high.
"""
from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class Peaks:
    flops_per_s: float        # dense bf16 matmul peak
    hbm_bytes_per_s: float
    hbm_bytes: int
    source: str


PEAKS = {
    "TPU v5 lite": Peaks(flops_per_s=197e12, hbm_bytes_per_s=819e9,
                         hbm_bytes=16 * 10**9,
                         source="Google Cloud documentation, TPU v5e"),
}


def peaks_for(device_kind: str) -> Peaks:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r}; add them to the table with "
                       f"their source") from None
