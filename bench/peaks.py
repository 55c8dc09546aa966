"""Published peaks of the chips the benchmark runs on, keyed by JAX's
``device_kind``.  Source: Google Cloud documentation, "TPU v5e"
(cloud.google.com/tpu/docs/v5e): 197 TFLOP/s bf16, 393 TOP/s int8,
16 GB HBM at 819 GB/s per chip.  A device that is not in the table is
an error, never a default.
"""
from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {"flops_bf16": 197e12, "hbm_bytes_per_s": 819e9,
                    "hbm_bytes": 16e9},
}
PEAKS["TPU v5e"] = PEAKS["TPU v5 lite"]


def peaks_for(device_kind: str) -> dict:
    if device_kind not in PEAKS:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r}; known: {sorted(PEAKS)}")
    return PEAKS[device_kind]
