"""Published peaks of the chips the benchmark runs on, keyed by the
``device_kind`` JAX reports.

Source: Google Cloud documentation, "TPU v5e" (system architecture page):
197 TFLOP/s bf16, 393 TOP/s int8, 16 GiB HBM2 at 819 GB/s per chip.
A v5e reports its kind as "TPU v5 lite".  A kind missing here is an
error, never a default: a roofline share against the wrong peak is a
wrong number.
"""
from __future__ import annotations

from dataclasses import dataclass


@dataclass(frozen=True)
class Peaks:
    name: str
    flops_bf16: float       # FLOP/s per chip
    hbm_bytes_s: float      # bytes/s per chip
    hbm_bytes: int          # device memory per chip


PEAKS = {
    "TPU v5 lite": Peaks("tpu-v5e", flops_bf16=197e12, hbm_bytes_s=819e9,
                         hbm_bytes=16 * 1024 ** 3),
}


def peaks_for(device_kind: str) -> Peaks:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(f"no published peaks for device_kind "
                         f"{device_kind!r}; add them to bench/peaks.py"
                         ) from None
