"""The one table of chip peaks, keyed by ``device_kind`` as JAX reports it.

Source: Google Cloud documentation, "TPU v5e" (197 TFLOP/s bf16, 819 GB/s HBM).
A device that is not in the table is an error, never a default.
"""

from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {
        "flops_per_s": 197e12,  # bf16 multiply, f32 accumulate
        "bytes_per_s": 819e9,  # HBM
        "source": "Google Cloud documentation, TPU v5e",
    },
}


def peaks_for(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no peaks on record for device_kind {device_kind!r}; add it to "
            "benchmarks/harness/peaks.py with its source"
        ) from None
