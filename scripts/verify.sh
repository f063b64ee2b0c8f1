#!/usr/bin/env bash
# One-stop verification entrypoint (CI + pre-PR):
#   1. JAX report             — JAX version, backend and devices (repro.compat)
#   2. static lint            — repro.lint --strict: stack verification,
#                               concurrency analysis, compat-boundary + hygiene
#                               over src/repro (docs/architecture.md §7)
#   3. tier-1 test suite      — pyproject pythonpath makes the prefix optional,
#                               but we keep it so the script also works on
#                               pytest < 7 installs
#   4. observability smoke    — repro.obs CLI: KV-switch scenario traced end
#                               to end; asserts the Chrome trace stitches one
#                               causal trace across both endpoints and the
#                               Prometheus export parses
# Performance is measured on the chip by bench/run.py (BENCHMARK.json) and
# recorded in PERF_LEDGER.jsonl; nothing here times anything. Reports go to
# the git-ignored results/.
set -euo pipefail
cd "$(dirname "$0")/.."
export PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}"
# the tests and smokes run on the CPU (Pallas kernels interpreted); the chip
# path is chip_smoke.py
export JAX_PLATFORMS="${JAX_PLATFORMS:-cpu}"

echo "== repro.compat report =="
python -m repro.compat

echo "== repro.lint (strict) =="
python -m repro.lint --strict --stacks --json results/lint_report.json

echo "== tier-1 tests =="
python -m pytest -q

echo "== observability smoke (stitched trace + metrics export) =="
# runs the KV-switch scenario end to end, writes a Chrome trace_event JSON
# and a Prometheus-text export, then re-parses both and asserts ONE stitched
# trace covering controller decision -> negotiation -> 2PC -> swap on both
# endpoints (docs/architecture.md §10)
python -m repro.obs --trace results/kv_switch.trace.json \
  --metrics results/metrics.prom --check

echo "verify.sh: all green"
