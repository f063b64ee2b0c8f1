#!/usr/bin/env bash
# One-stop verification entrypoint (CI + pre-PR):
#   1. JAX report             — JAX version, backend and devices (repro.compat)
#   2. static lint            — repro.lint --strict: stack verification,
#                               concurrency analysis, compat-boundary + hygiene
#                               over src/repro (docs/architecture.md §7)
#   3. tier-1 test suite      — pyproject pythonpath makes the prefix optional,
#                               but we keep it so the script also works on
#                               pytest < 7 installs
#   4. benchmark smoke pass   — import + mesh/shard_map sanity for the bench
#                               tier, plus the controller-driven reconfigure
#                               scenario (telemetry -> policy -> switch) and
#                               the chaos smoke (WAN-weather region switch +
#                               coordinator crash mid-commit, emitting
#                               benchmarks/out/chaos_scenarios.json) run
#                               headless so the close-the-loop and failure
#                               paths are tier-1
#   5. perf regression gate   — benchmarks/check_regression.py compares this
#                               run's artifacts (dataplane.json, overhead.json)
#                               against the committed benchmarks/baseline.json
#                               and fails on >30% regression, writing
#                               benchmarks/out/regression_report.json
#   6. observability smoke     — repro.obs CLI: KV-switch scenario traced end
#                               to end; asserts the Chrome trace stitches one
#                               causal trace across both endpoints and the
#                               Prometheus export parses
set -euo pipefail
cd "$(dirname "$0")/.."
export PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}"
# the tests and smokes run on the CPU (Pallas kernels interpreted); the chip
# path is chip_smoke.py
export JAX_PLATFORMS="${JAX_PLATFORMS:-cpu}"

echo "== repro.compat report =="
python -m repro.compat

echo "== repro.lint (strict) =="
python -m repro.lint --strict --stacks --json benchmarks/out/lint_report.json

echo "== tier-1 tests =="
python -m pytest -q

echo "== benchmark smoke (incl. chaos scenarios) =="
python -m benchmarks.run --smoke

echo "== data-plane throughput smoke =="
# scaled-down batched-vs-per-message sweep; asserts the >=10x batch=64
# speedup and writes benchmarks/out/dataplane.json (a CI artifact)
python -m benchmarks.bench_dataplane --smoke

echo "== perf regression gate (vs benchmarks/baseline.json) =="
# re-run after the full-size dataplane smoke so the gate judges the freshest
# artifacts; fails (exit 1) on >30% regression and writes
# benchmarks/out/regression_report.json for inspection
python -m benchmarks.check_regression

echo "== observability smoke (stitched trace + metrics export) =="
# runs the KV-switch scenario end to end, writes a Chrome trace_event JSON
# and a Prometheus-text export, then re-parses both and asserts ONE stitched
# trace covering controller decision -> negotiation -> 2PC -> swap on both
# endpoints (docs/architecture.md §10)
python -m repro.obs --trace benchmarks/out/kv_switch.trace.json \
  --metrics benchmarks/out/metrics.prom --check

echo "verify.sh: all green"
