"""Published peaks of the chips this benchmark runs on, keyed by JAX's
``device_kind``. A kind that is not here is an error, never a default.

Source: Google Cloud documentation, "TPU v5e": 197 TFLOP/s in bf16, 16 GB
of HBM at 819 GB/s per chip.
"""

from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {"flops_per_s": 197e12, "bytes_per_s": 819e9,
                    "hbm_bytes": 16 * 2**30},
}


def peaks_for(device_kind: str) -> dict:
    if device_kind not in PEAKS:
        raise KeyError(f"no published peaks for device kind {device_kind!r}; "
                       f"known: {sorted(PEAKS)}")
    return PEAKS[device_kind]
