"""Three-term roofline from the dry-run artifacts, for a named device kind.

  compute    = FLOPs_per_device / peak_bf16
  memory     = HBM_bytes_per_device / hbm_bw
  collective = ICI_bytes/(links*link_bw) + DCN_bytes/dcn_bw   (per device)

FLOPs/HBM bytes come from the analytic implementation-faithful model
(analysis/flops.py — see its docstring for why not cost_analysis), validated
against an unrolled HLO compile in tests/test_flops_validation.py.
Collective bytes are parsed from the compiled HLO (per-device shapes) with
while-loop trip-count correction; ops are attributed to the DCN tier when
their replica groups cross a pod boundary.
"""
from __future__ import annotations

import re
from dataclasses import dataclass
from typing import Optional

from repro.analysis import flops as F
from repro.analysis import hloparse
from repro.launch.mesh import peaks


@dataclass
class Roofline:
    compute_s: float
    memory_s: float
    collective_s: float
    dominant: str
    flops_per_dev: float
    bytes_per_dev: float
    coll_bytes_per_dev: float
    dcn_bytes_per_dev: float
    model_flops: float
    hlo_useful_ratio: float  # MODEL_FLOPS / implementation FLOPs
    step_time_s: float  # max of the three terms (no-overlap bound is their sum)
    mfu: float  # model_flops / (chips * peak * step_time)

    def to_dict(self):
        return {k: getattr(self, k) for k in self.__dataclass_fields__}


def _split_ici_dcn(hlo: str, pod_size: int) -> tuple[float, float, dict]:
    """Return (ici_bytes, dcn_bytes, stats_dict) per device."""
    stats = hloparse.collective_stats(hlo)
    ici = dcn = 0.0
    for kind, nbytes, mult, ln in hloparse.iter_collectives(hlo):
        if _crosses_pod(ln, pod_size):
            dcn += nbytes * mult
        else:
            ici += nbytes * mult
    return ici, dcn, stats.to_dict()


def _crosses_pod(line: str, pod_size: int) -> bool:
    if pod_size <= 0:
        return False
    m = re.search(r"replica_groups=\{\{([^}]+)\}", line)
    if m:
        ids = [int(x) for x in re.split(r"[,\s]+", m.group(1)) if x.strip().isdigit()]
        return len({i // pod_size for i in ids}) > 1
    # iota format: replica_groups=[G,S]<=[N](perm) — groups of stride layout.
    m = re.search(r"replica_groups=\[(\d+),(\d+)\]<=\[(\d+)\](?:T\(([\d,]+)\))?", line)
    if m:
        g, s, n = int(m.group(1)), int(m.group(2)), int(m.group(3))
        perm = m.group(4)
        if n <= pod_size:
            return False
        # default iota: consecutive ids per group -> crosses only if group size
        # exceeds pod; transposed iota (T(1,0)) strides across pods.
        if perm and perm != "0,1":
            return True
        return s > pod_size
    return False


def analyze(
    hlo: str,
    cfg,
    shape,
    mesh_shape: dict,
    *,
    device_kind: str,
    extra_collective_bytes: float = 0.0,
) -> Roofline:
    """Roofline of one step on chips of ``device_kind`` (see
    ``repro.launch.mesh.PEAKS``; an unknown kind raises)."""
    hw = peaks(device_kind)
    n_chips = 1
    for v in mesh_shape.values():
        n_chips *= v
    pod_chips = n_chips // mesh_shape.get("pod", 1)
    cost = F.step_cost(cfg, shape, mesh_shape)
    fpd = cost.flops / n_chips
    bpd = cost.bytes_hbm / n_chips
    ici, dcn, _ = _split_ici_dcn(hlo, pod_chips if mesh_shape.get("pod", 1) > 1 else 0)
    ici += extra_collective_bytes

    compute_s = fpd / hw["peak_flops_bf16"]
    memory_s = bpd / hw["hbm_bw"]
    collective_s = ici / (hw["ici_links"] * hw["ici_link_bw"]) + dcn / hw["dcn_bw"]
    terms = {"compute": compute_s, "memory": memory_s, "collective": collective_s}
    dominant = max(terms, key=terms.get)
    step = max(terms.values())
    mfu = cost.model_flops / (n_chips * hw["peak_flops_bf16"] * step) if step > 0 else 0.0
    return Roofline(
        compute_s=compute_s,
        memory_s=memory_s,
        collective_s=collective_s,
        dominant=dominant,
        flops_per_dev=fpd,
        bytes_per_dev=bpd,
        coll_bytes_per_dev=ici + dcn,
        dcn_bytes_per_dev=dcn,
        model_flops=cost.model_flops,
        hlo_useful_ratio=cost.model_flops / max(cost.flops, 1.0),
        step_time_s=step,
        mfu=mfu,
    )
