"""Published peaks per chip, keyed by the ``device_kind`` JAX reports.

A kind that is not in the table is an error, never a default: a roofline
share against the wrong chip's peak would be a wrong number.
"""
from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class Peaks:
    flops_per_s: float
    bytes_per_s: float
    hbm_bytes: float
    source: str


PEAKS = {
    "TPU v5 lite": Peaks(
        flops_per_s=197e12, bytes_per_s=819e9, hbm_bytes=16e9,
        source="Google Cloud documentation, 'TPU v5e': 197 TFLOP/s bf16, "
               "16 GB HBM at 819 GB/s per chip"),
}


def peaks_for(device_kind: str) -> Peaks:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no peaks recorded for device kind {device_kind!r}; "
                       f"known: {sorted(PEAKS)}") from None
