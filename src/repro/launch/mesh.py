"""Production mesh builders (assignment-mandated shapes).

Functions, not module constants, so importing never touches jax device state.
"""
from __future__ import annotations

import jax

from repro import compat


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return compat.make_mesh(shape, axes,
                            axis_types=(compat.AUTO,) * len(axes))


def make_test_mesh(shape=(2, 4), axes=("data", "model")):
    return compat.make_mesh(shape, axes,
                            axis_types=(compat.AUTO,) * len(axes))


def make_pod_mesh():
    """pod=2 x data=n/2 over every visible device: the gradient transports
    run over 'pod', so this is the mesh on which they engage."""
    n = jax.device_count()
    if n < 2 or n % 2:
        raise ValueError(f"a pod mesh needs an even device count >= 2, got {n}")
    return make_test_mesh((2, n // 2), ("pod", "data"))


# Per-chip peaks, keyed by ``jax.Device.device_kind``. TPU v5e ("TPU v5
# lite"): Google Cloud documentation, "TPU v5e" — 197 TFLOP/s bf16, 16 GB HBM
# at 819 GB/s, 1,600 Gbit/s of ICI per chip (counted here as 4 links of
# 50 GB/s). ``dcn_bw`` is not published there: it is the roofline's assumed
# per-chip share of the data-centre network between pods.
PEAKS = {
    "TPU v5 lite": {
        "peak_flops_bf16": 197e12,  # FLOP/s
        "hbm_bw": 819e9,  # B/s
        "ici_link_bw": 50e9,  # B/s per link
        "ici_links": 4,  # torus links per chip usable for a collective
        "dcn_bw": 25e9,  # B/s per chip across pods (assumed)
    },
}


def peaks(device_kind: str) -> dict:
    """Peaks of one chip of ``device_kind``; an unknown kind is an error."""
    if device_kind not in PEAKS:
        raise KeyError(f"no peak table for device kind {device_kind!r}; "
                       f"known: {sorted(PEAKS)}")
    return PEAKS[device_kind]
