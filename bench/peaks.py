"""Published peaks per chip, keyed by the ``device_kind`` JAX reports.

Source: Google Cloud documentation, "TPU v5e" (per chip: 197 TFLOP/s bf16,
393 TOP/s int8, 16 GB HBM at 819 GB/s).  A device kind that is not here is
an error, never a default.
"""
from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {"bf16_flops": 197e12, "hbm_bytes_s": 819e9,
                    "hbm_bytes": 16e9,
                    "source": "Google Cloud documentation, TPU v5e"},
}


def peaks(device_kind: str) -> dict:
    if device_kind not in PEAKS:
        raise KeyError(f"no published peaks for device kind {device_kind!r}; "
                       f"add them to bench/peaks.py (have {sorted(PEAKS)})")
    return PEAKS[device_kind]


def least_seconds(flops: float, nbytes: float, peak: dict) -> float:
    """The least time the chip could take: the larger of compute and memory
    time at the published peaks."""
    return max(flops / peak["bf16_flops"], nbytes / peak["hbm_bytes_s"])
