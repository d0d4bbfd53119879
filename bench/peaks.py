"""Published per-chip peaks, keyed by JAX's ``Device.device_kind``.

Source: Google Cloud TPU documentation, "TPU v5e": 197 TFLOP/s in bf16 and
819 GB/s of HBM bandwidth per chip. (A copy of the program's
``repro.launch.roofline.PEAKS``, kept here so that no change to the program
moves the yardstick.) A kind missing from the table is an error, never a
default.
"""
from __future__ import annotations

PEAKS = {"TPU v5 lite": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}}


def chip_peaks(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(f"no published peaks for device kind "
                         f"{device_kind!r}; add them to bench/peaks.py"
                         ) from None
