"""Benchmark harness — one module per paper table/figure.

Usage:
  PYTHONPATH=src python -m benchmarks.run             # everything
  PYTHONPATH=src python -m benchmarks.run --quick
  PYTHONPATH=src python -m benchmarks.run --only bench_protocol

Output: ``name,us_per_call,derived`` CSV rows on stdout.
"""
from __future__ import annotations

import argparse
import sys
import time
import traceback

BENCHES = [
    ("fig4_device_profile", "benchmarks.bench_device_profile"),
    ("fig5_quality_degradation", "benchmarks.bench_quality_degradation"),
    ("fig9_protocol", "benchmarks.bench_protocol"),
    ("fig10a_cost", "benchmarks.bench_cost"),
    ("fig10b_11_latency", "benchmarks.bench_latency"),
    ("fig12_content_types", "benchmarks.bench_content_types"),
    ("fig13_hitl", "benchmarks.bench_hitl"),
    # also emits machine-readable artifacts/BENCH_fault.json
    ("fig15_fault_tolerance", "benchmarks.bench_fault_tolerance"),
    ("fig16_autoscale", "benchmarks.bench_autoscale"),
    ("multistream", "benchmarks.bench_multistream"),
    ("slo_serving", "benchmarks.bench_slo_serving"),
    ("drift_recovery", "benchmarks.bench_drift_recovery"),
    # also emits machine-readable artifacts/BENCH_per_site.json
    ("per_site", "benchmarks.bench_per_site"),
    # also emits machine-readable artifacts/BENCH_e2e.json
    ("e2e_throughput", "benchmarks.bench_e2e_throughput"),
    # also emits machine-readable artifacts/BENCH_steady.json
    ("steady_state", "benchmarks.bench_steady_state"),
    # also emits machine-readable artifacts/BENCH_shard.json
    ("shard_scale", "benchmarks.bench_shard_scale"),
    # also emits machine-readable artifacts/BENCH_tenancy.json
    ("tenancy", "benchmarks.bench_tenancy"),
    # also emits machine-readable artifacts/BENCH_chaos.json
    ("chaos", "benchmarks.bench_chaos"),
    # also emits machine-readable artifacts/BENCH_coldstart.json
    ("coldstart", "benchmarks.bench_coldstart"),
    ("kernels", "benchmarks.bench_kernels"),
    ("roofline", "benchmarks.roofline_table"),
]


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--only", default=None)
    args = ap.parse_args()

    from repro import compile_cache
    compile_cache.configure()
    from benchmarks.common import emit, load_context

    print("name,us_per_call,derived")
    t0 = time.time()
    ctx = load_context()
    print(f"# context ready in {time.time() - t0:.1f}s", file=sys.stderr)

    failures = []
    for prefix, module_name in BENCHES:
        if args.only and args.only not in (prefix, module_name.split(".")[-1]):
            continue
        t0 = time.time()
        try:
            module = __import__(module_name, fromlist=["run"])
            rows = module.run(ctx, quick=args.quick)
            emit(rows, prefix)
            print(f"# {prefix} done in {time.time() - t0:.1f}s",
                  file=sys.stderr)
        except Exception:   # noqa: BLE001
            failures.append(prefix)
            traceback.print_exc()
    if failures:
        print(f"# FAILURES: {failures}", file=sys.stderr)
        raise SystemExit(1)


if __name__ == "__main__":
    main()
