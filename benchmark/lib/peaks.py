"""Peaks of the chips the benchmark may run on, keyed by `device_kind`.

A device that is not in the table is an error, never a default: a share of
an unknown peak is no number.
"""

from __future__ import annotations

PEAKS = {
    # Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 16 GB of HBM
    # at 819 GB/s per chip.
    "TPU v5 lite": {
        "flops_per_s": 197e12,
        "bytes_per_s": 819e9,
        "memory_bytes": 16e9,
        "source": "Google Cloud documentation, TPU v5e",
    },
}


def peaks(device_kind: str) -> dict:
    if device_kind not in PEAKS:
        raise KeyError(
            f"no peaks for device_kind {device_kind!r}: add an entry with "
            f"its source to benchmark/lib/peaks.py (known: {sorted(PEAKS)})")
    return PEAKS[device_kind]
