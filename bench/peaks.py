"""Published peaks of each accelerator the benchmark runs on, keyed by the
``device_kind`` JAX reports. A kind that is not here is an error: a
roofline or utilization against a guessed peak is no number at all."""
from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {
        "bf16_flops_per_s": 197e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
        "source": ("Google Cloud documentation, 'TPU v5e': 197 TFLOP/s "
                   "bf16, 16 GB of HBM2 at 819 GB/s per chip"),
    },
}


def peaks(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no published peaks for device kind {device_kind!r}; known: "
            f"{sorted(PEAKS)}") from None
