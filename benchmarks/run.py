"""Benchmark harness — one module per paper table/figure (DESIGN.md §6).

Prints ``name,us_per_call,derived`` CSV rows.

  Fig 4  bench_pipeline      ETL e2e latency: Kafka vs managed pub/sub
  Fig 5  bench_ordering      receive-side vs service ordering + renegotiation
  Fig 6  bench_sharding      client-side vs server-side KV sharding
  Fig7/8 bench_overhead      marginal no-op chunnel cost (jit + eager)
  Fig 9  bench_kv_latency    full stack vs inlined baselines
  Fig 10 bench_reconfigure   lock vs barrier reconfiguration
  (TPU)  bench_collectives   gradient-transport Select collective profile
  (§8)   bench_dataplane     batched data plane msgs/s vs per-message baseline
"""
from __future__ import annotations

import argparse
import importlib
import subprocess
import sys
import time

MODULES = [
    "benchmarks.bench_dataplane",
    "benchmarks.bench_overhead",
    "benchmarks.bench_slo",
    "benchmarks.bench_reconfigure",
    "benchmarks.bench_kv_latency",
    "benchmarks.bench_sharding",
    "benchmarks.bench_ordering",
    "benchmarks.bench_pipeline",
    "benchmarks.bench_collectives",
]


def smoke() -> None:
    """Dry pass for CI (scripts/verify.sh): import every bench module (their
    heavy work lives in main(), so imports are cheap), run one compat
    mesh + shard_map sanity, run the scored-vs-first-compatible negotiation
    comparison, and run the controller-driven KV reconfigure scenario
    headless through the policy registry — a regression anywhere in the
    close-the-loop path (telemetry -> scorer -> policy -> switch) fails
    tier-1, not just the full bench sweep. Fails loudly on any import or
    compat regression."""
    from benchmarks import common
    from repro import compat

    print("name,us_per_call,derived")
    for mod_name in MODULES:
        importlib.import_module(mod_name)
        print(f"# {mod_name} import ok", file=sys.stderr)
    common.smoke_check()

    from benchmarks.bench_reconfigure import (
        emit_chaos_scenarios,
        emit_fleet_scenario,
        emit_scored_negotiation,
        run_controller_kv,
    )

    scored = emit_scored_negotiation()
    print("smoke_scored_negotiation,0.00,"
          f"chatty={scored['chatty']['scored']};bulk={scored['bulk']['scored']}")

    res = run_controller_kv(fast=True)
    assert res["switches"], "controller-initiated KV switch did not fire"
    assert res["policy"] == "kv_load_adaptive", res.get("policy")  # via registry
    assert "ClientShard" in res["switches"][0]["target"], res["switches"][0]
    print(f"smoke_controller_kv,{res['blip_s'] * 1e6:.2f},"
          f"switches={len(res['switches'])};policy={res['policy']}")

    # batched data plane: scaled-down throughput pass (asserts the ≥10x
    # batch=64 speedup over the per-message baseline internally and writes
    # benchmarks/out/dataplane.json — a CI artifact)
    from benchmarks.bench_dataplane import run as run_dataplane

    dp = run_dataplane(smoke=True)
    print("smoke_dataplane,0.00,"
          f"speedup_batch64={dp['speedup_batch64']:.1f}x;"
          f"default_b64_msgs_per_s={dp['default']['64']['msgs_per_s']:.0f}")

    # fleet signal plane: aggregate-driven switch, one rendezvous epoch for
    # the whole fleet (asserts the acceptance shape internally and writes
    # benchmarks/out/fleet_scenario.json — a CI artifact)
    fleet = emit_fleet_scenario(fast=True)
    print("smoke_fleet_kv,0.00,"
          f"clients={fleet['n_clients']};"
          f"switches={fleet['counts']['committed']};"
          f"epochs={fleet['phases'][-1]['epoch']};"
          f"peak_member_qps={fleet['peak_member_qps']:.0f}")

    # chaos harness: injected WAN weather + storm drives the region onto the
    # compressed+reliable WAN option while the clean region keeps the fast
    # path, and a coordinator crashed exactly mid-commit converges with zero
    # stranded prepared peers (asserts the acceptance shape internally and
    # writes benchmarks/out/chaos_scenarios.json — a CI artifact)
    chaos = emit_chaos_scenarios(fast=True)
    _wan, _p2 = chaos["regions"]["wan"], chaos["partition_2pc"]
    print("smoke_chaos,0.00,"
          f"wan_rule={_wan['switches'][0]['rule']};"
          f"dcn_switches={len(chaos['regions']['dcn']['switches'])};"
          f"stranded={_p2['stranded_prepared']};"
          f"resync_failures={sum(_p2['resync_failures'].values())}")

    # tracing plane: the disabled path must be ~free and the enabled path
    # cheap at batch=64 (gates asserted inside run_tracing_overhead)
    from benchmarks.bench_overhead import run_tracing_overhead

    tr = run_tracing_overhead(batch=64, smoke=True)
    print("smoke_tracing_overhead,0.00,"
          f"enabled_overhead={tr['enabled_overhead']:.3f};"
          f"disabled_guard_frac={tr['disabled_guard_frac']:.5f}")

    # SLO plane: federated metrics drive an error-budget burn-rate alarm
    # that arms the switch BEFORE the raw p95 threshold would (asserts the
    # acceptance shape internally and writes benchmarks/out/slo_scenario.json
    # — a CI artifact)
    from benchmarks.bench_slo import emit_slo_scenario

    slo = emit_slo_scenario(fast=True)
    _g = slo["guard_scenario"]["guard"]
    print("smoke_slo_guard,0.00,"
          f"guard_tick={_g['switch_tick']};"
          f"raw_tick={slo['guard_scenario']['raw']['fired_tick']};"
          f"rank_changed={slo['calibration']['rank_changed']}")

    # regression gate: committed baseline vs this run's artifacts
    from benchmarks.check_regression import check as check_regression

    reg = check_regression()
    print("smoke_regression_gate,0.00,"
          f"checked={len(reg['checks'])};regressions={len(reg['regressions'])}")

    print("# smoke ok on jax compat paths:", file=sys.stderr)
    for line in compat.report().splitlines():
        print(f"#   {line}", file=sys.stderr)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="import-and-sanity dry pass (no full benchmarks)")
    args = ap.parse_args()
    if args.smoke:
        smoke()
        return
    print("name,us_per_call,derived", flush=True)
    failures = 0
    # One process per module, and none of JAX here: a chip belongs to one
    # process at a time, so a parent holding it would starve the modules.
    for mod_name in MODULES:
        t0 = time.time()
        rc = subprocess.run([sys.executable, "-m", mod_name]).returncode
        if rc:
            failures += 1
            print(f"{mod_name}_FAILED,0,rc={rc}", flush=True)
        print(f"# {mod_name} done in {time.time()-t0:.1f}s", file=sys.stderr)
    if failures:
        raise SystemExit(f"{failures} benchmark modules failed")


if __name__ == "__main__":
    main()
