"""Published peaks of the chips the benchmark runs on, keyed by JAX's
``device_kind``. Source: Google Cloud documentation, "TPU v5e"
(https://cloud.google.com/tpu/docs/v5e): 197 TFLOP/s bf16, 819 GB/s HBM
per chip."""
from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
}


def peak(device_kind: str) -> dict:
    """The peaks of `device_kind`; a kind not in the table is an error."""
    if device_kind not in PEAKS:
        raise KeyError(f"no published peaks for device kind {device_kind!r}; "
                       f"known: {sorted(PEAKS)}")
    return PEAKS[device_kind]
