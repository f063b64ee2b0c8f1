"""Pallas TPU kernels: int8 block quantization, flash attention, SSM scan.

Each kernel takes ``interpret``: None decides by the platform when the kernel
is traced — compiled Mosaic on TPU, the Pallas interpreter on the CPU (tests
run there with ``JAX_PLATFORMS=cpu``) — and any other platform raises.
"""
from __future__ import annotations

from typing import Optional

import jax


def interpret_mode(interpret: Optional[bool] = None) -> bool:
    """Resolve a kernel's ``interpret`` argument. An explicit bool wins (a
    compile against a described TPU passes False); None means interpret on
    the CPU platform and compile on TPU."""
    if interpret is not None:
        return interpret
    platform = jax.default_backend()
    if platform == "cpu":
        return True
    if platform == "tpu":
        return False
    raise RuntimeError(
        f"Pallas kernels compile for TPU or are interpreted on CPU; "
        f"the default backend is {platform!r}")
