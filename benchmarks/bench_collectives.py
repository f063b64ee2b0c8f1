"""TPU-side transport Select: per-transport collective profile (bytes by kind,
DCN vs ICI) from the compiled multi-pod HLO for a small dense arch.

This is the §Perf instrument: the numbers show what each gradient-transport
chunnel does to the collective roofline term. Numerical equivalence of the
transports is covered by tests/test_comm.py; wall-clock on real links is out
of scope for the CPU container (see EXPERIMENTS.md §Roofline).

Each transport compiles in its own CPU-only subprocess (512 fake host
devices, never the chip): a 512-device XLA compile retains several GB.
compressed_int8 (full-tree quantized all-gather) is excluded — it exceeds the
XLA-CPU compiler's host memory at 1.2B params (§Perf refuted-hypothesis log);
its compile-feasible form is hier_compressed (quantizes 1/16 shards).
"""
from __future__ import annotations

import json
import os
import subprocess
import sys

from benchmarks.common import emit

TRANSPORTS = ("xla", "psum", "ring", "hierarchical", "hier_compressed")

_INNER = r"""
import json, sys
from repro.launch.dryrun import lower_cell, use_fake_host_devices
use_fake_host_devices()
rec = lower_cell("llama3.2-1b", "train_4k", multi_pod=True, transport=sys.argv[1])
r = rec["roofline"]
print("RESULT " + json.dumps({
    "collective_s": r["collective_s"],
    "dcn": r["dcn_bytes_per_dev"],
    "total": r["coll_bytes_per_dev"],
    "dom": r["dominant"],
}))
"""


def main() -> None:
    env = dict(os.environ)
    env["PYTHONPATH"] = env.get("PYTHONPATH", "src")
    failed = []
    for transport in TRANSPORTS:
        try:
            out = subprocess.run(
                [sys.executable, "-c", _INNER, transport],
                env=env, capture_output=True, text=True, timeout=1200)
        except subprocess.TimeoutExpired:
            failed.append(f"{transport}: timeout")
            continue
        line = next((l for l in out.stdout.splitlines()
                     if l.startswith("RESULT ")), None)
        if line is None:
            failed.append(f"{transport}: rc={out.returncode} "
                          f"{out.stderr.strip().splitlines()[-1:]}")
            continue
        r = json.loads(line[len("RESULT "):])
        emit(f"collectives_{transport}", r["collective_s"] * 1e6,
             f"dcn_GB={r['dcn']/1e9:.3f};total_GB={r['total']/1e9:.2f};"
             f"dom={r['dom']}")
    # psum/ring over pod hit an XLA-CPU SPMD partitioner assertion
    # (spmd_partitioner_util.cc:504) on the 3-axis production mesh; they work
    # on 2-axis meshes (tests/test_substrate.py, examples/train_reconfigure.py)
    if failed:
        raise SystemExit("bench_collectives: " + "; ".join(failed))


if __name__ == "__main__":
    main()
