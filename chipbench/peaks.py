"""Published peaks of each chip, keyed by JAX's ``Device.device_kind``.

TPU v5e: Google Cloud documentation, "TPU v5e" -- 197 TFLOP/s bf16,
16 GB of HBM at 819 GB/s, 1,600 Gbit/s of chip-to-chip interconnect.
A device kind that is not here is an error, never a default.
"""
from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9,
                    "hbm_bytes": 16e9, "ici_bytes_per_s": 200e9},
}


def peak(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r}") from None
