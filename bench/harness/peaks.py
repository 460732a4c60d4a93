"""Published peaks per chip, keyed by JAX's ``device_kind``.

Source: Google Cloud documentation, "TPU v5e" (system architecture page):
197 TFLOP/s bf16, 16 GB of HBM at 819 GB/s per chip.  A kind that is not
in the table is an error: a share of an unknown peak is no number.
"""
from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {
        "flops_per_s": 197e12,
        "hbm_bytes_per_s": 819e9,
        "source": "Google Cloud TPU v5e documentation",
    },
}


def peak(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no published peak for device kind {device_kind!r}; add it to bench/harness/peaks.py with its source"
        ) from None
