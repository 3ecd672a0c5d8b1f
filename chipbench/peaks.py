"""Published peaks of the chips the benchmark knows, keyed by JAX's
``device_kind``. A kind that is not here is an error, not a default.

Source: Google Cloud documentation, "TPU v5e": 197 TFLOP/s in bf16, 16 GB
of HBM at 819 GB/s per chip.
"""

from __future__ import annotations

from typing import Dict

PEAKS: Dict[str, Dict[str, float]] = {
    "TPU v5 lite": {"bf16_flops_per_s": 197e12,
                    "hbm_bytes_per_s": 819e9,
                    "hbm_bytes": 16e9},
    "TPU v5e": {"bf16_flops_per_s": 197e12,
                "hbm_bytes_per_s": 819e9,
                "hbm_bytes": 16e9},
}


def peaks_of(device_kind: str) -> Dict[str, float]:
    if device_kind not in PEAKS:
        raise KeyError(
            f"no published peaks for device kind {device_kind!r}; add it to "
            "chipbench/peaks.py with its source")
    return PEAKS[device_kind]


def roofline_seconds(flops: float, hbm_bytes: float, device_kind: str):
    """(least seconds the chip could take, which bound it is)."""
    peak = peaks_of(device_kind)
    by_flops = flops / peak["bf16_flops_per_s"]
    by_bytes = hbm_bytes / peak["hbm_bytes_per_s"]
    return ((by_flops, "flops") if by_flops >= by_bytes
            else (by_bytes, "bytes"))
