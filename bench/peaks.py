"""Published peaks of one chip, keyed by `jax.Device.device_kind`.

TPU v5e ("TPU v5 lite"): Google Cloud documentation, "TPU v5e": 197 TFLOP/s
in bf16, 393 TOP/s in int8, 16 GB of HBM at 819 GB/s. A device kind that is
not here is an error, not a default.
"""
from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {
        "bf16_flops": 197e12,  # FLOP/s
        "int8_ops": 393e12,  # OP/s
        "hbm_bytes_per_s": 819e9,  # B/s
        "hbm_bytes": 16e9,
    },
}


def peaks(device_kind: str) -> dict:
    if device_kind not in PEAKS:
        raise KeyError(f"no peaks for device kind {device_kind!r}; known: {sorted(PEAKS)}")
    return PEAKS[device_kind]
