"""Published peaks of the chips the benchmark runs on, keyed by ``device_kind``.

A device that is not in the table is an error, never a default: a roofline
share or an MFU is only as true as the peak it is divided by.
"""

from __future__ import annotations

from dataclasses import dataclass


@dataclass(frozen=True)
class Peak:
    bf16_flops: float  # FLOP/s of dense bf16 matrix multiplication
    hbm_bytes_per_s: float
    source: str


PEAKS = {
    "TPU v5 lite": Peak(
        bf16_flops=197e12,
        hbm_bytes_per_s=819e9,
        source='Google Cloud documentation, "TPU v5e" (per chip: 197 TFLOP/s '
        "bf16, 16 GB HBM2 at 819 GB/s)",
    ),
}


def peak_for(device_kind: str) -> Peak:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no published peak for device kind {device_kind!r}; "
            f"add it to bench/peaks.py with its source"
        ) from None
