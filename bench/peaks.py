"""Published peaks of the chips the benchmark may run on, keyed by the
``device_kind`` JAX reports.

TPU v5e (Google Cloud documentation, "TPU v5e"): 197 TFLOP/s bf16,
819 GB/s HBM bandwidth, 16 GB HBM per chip.  A kind that is not listed is
an error: a roofline or an MFU against a guessed peak means nothing.
"""
from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9,
                    "hbm_bytes": 16e9},
}


def peaks_for(device_kind: str) -> dict:
    if device_kind not in PEAKS:
        raise KeyError(f"no published peaks for device kind {device_kind!r}; "
                       f"known: {sorted(PEAKS)}")
    return PEAKS[device_kind]
