# One function per paper table. Print ``name,us_per_call,derived`` CSV.
"""Benchmark harness entry point.

    PYTHONPATH=src python -m benchmarks.run [--quick]

  table1_frameworks       - Table I analogue (execution-style comparison)
  table2_mixed_precision  - Table II reproduction (Dx-Wy exploration)
  adaptive_switch         - MDC runtime-adaptivity benchmark
  serve_throughput        - coalesced vs naive per-request serving
  qpath_latency           - fake-quant f32 vs packed-kernel execution path
  dse_pareto              - resource-constrained Pareto fronts of working points
  fleet_chaos             - replicated serving under injected faults
  integrity_sdc           - SDC detection/scrub/self-heal under bit-flip chaos
  roofline                - §Roofline table aggregated from dry-run artifacts
"""
from __future__ import annotations

import argparse
import sys
import traceback


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="reduced sizes for CI-speed runs")
    ap.add_argument("--only", default=None)
    args = ap.parse_args()
    full = not args.quick

    failures = []

    def section(name, fn):
        if args.only and args.only != name:
            return
        print(f"# --- {name} ---", flush=True)
        try:
            fn()
        except Exception as e:  # keep the harness going; report at the end
            failures.append((name, repr(e)))
            traceback.print_exc()

    from benchmarks import (adaptive_switch, dse_pareto, fleet_chaos,
                            integrity_sdc, qpath_latency, roofline_table,
                            serve_throughput, table1_frameworks,
                            table2_mixed_precision)

    section("table1_frameworks", lambda: [
        print("table1_frameworks," + ",".join(f"{k}={v}" for k, v in r.items()))
        for r in table1_frameworks.run(full)])
    section("table2_mixed_precision", lambda: [
        print("table2_mixed_precision," + ",".join(f"{k}={v}"
                                                   for k, v in r.items()))
        for r in table2_mixed_precision.run(full)])
    section("adaptive_switch", lambda: [
        print("adaptive_switch," + ",".join(f"{k}={v}" for k, v in r.items()))
        for r in adaptive_switch.run(full)])
    section("serve_throughput", lambda: [
        print("serve_throughput," + ",".join(f"{k}={v}" for k, v in r.items()))
        for r in serve_throughput.run(full)])
    section("qpath_latency", lambda: [
        print("qpath_latency," + ",".join(f"{k}={v}" for k, v in r.items()))
        for r in qpath_latency.run(full)])
    section("dse_pareto", lambda: [
        print("dse_pareto," + ",".join(f"{k}={v}" for k, v in r.items()))
        for r in dse_pareto.run(full)])
    section("fleet_chaos", lambda: print(
        "fleet_chaos," + ",".join(f"{k}={v}"
                                  for k, v in fleet_chaos.run(full).items())))
    section("integrity_sdc", lambda: print(
        "integrity_sdc," + ",".join(
            f"{k}={v}" for k, v in integrity_sdc.run(full).items()
            if k != "flips")))
    section("roofline", roofline_table.main)

    if failures:
        for name, err in failures:
            print(f"BENCH FAILURE: {name}: {err}", file=sys.stderr)
        raise SystemExit(1)
    print("# all benchmarks complete")


if __name__ == "__main__":
    from repro.caches import enable_compile_cache
    enable_compile_cache()
    main()
