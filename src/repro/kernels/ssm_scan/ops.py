"""jit'd public wrapper; see repro.kernels for where it runs."""
from __future__ import annotations

from repro.kernels.ssm_scan.ssm_scan import ssm_scan_chunk as _scan


def ssm_scan_chunk(a, bx, h0):
    return _scan(a, bx, h0)
