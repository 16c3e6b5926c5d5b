"""CI perf-regression gate: diff a fresh benchmark payload against the
committed baseline.

The e2e hot-path and steady-state benchmarks emit machine-readable
results (``BENCH_e2e.json``, ``BENCH_steady.json``); the repository
commits baselines under ``benchmarks/baselines/`` (the root/artifacts
copies are scratch outputs, gitignored).  CI re-runs the benchmarks and
this script fails the build when a gated metric regresses beyond
tolerance — a perf claim that is not continuously re-checked stops being
true silently.  Refresh a baseline by re-running the full benchmark and
committing the new file alongside the change that moved the number.
The gate only compares metrics present in both payloads, so one invocation
per payload pair covers both benchmark families.

Gated metrics:

  * ``host_syncs_per_flush_fused``  — blocking device->host reads per
    flush, lower is better.  Workload-invariant (the device-residency
    guarantee is ONE sync per flush regardless of stream count), so it is
    always compared.
  * ``classify_flops_saved_frac``   — compacted-classify savings, higher
    is better.  Compared only when the fresh run used the SAME workload as
    the baseline: the quick CI smoke (4 streams) measures a different
    operating point than the committed 8-stream baseline.
  * ``p99_latency_s``               — steady-state tail chunk latency on
    the simulated clock, lower is better; compared when workloads match
    (tail latency moves with stream count and batch window).
  * ``bundle_bytes_peak``           — peak device-buffer residency under
    bounded flush-bundle retention, lower is better; workload-matched.
  * ``residency_flat``              — hard gate: with a retention cap the
    bundle_bytes series must plateau over the run; a growing series is
    the lazy-bundle leak regardless of operating point.
  * ``overhead_ratio``              — shard-scale sweep: per-chunk
    scheduling overhead at the top of the stream sweep over the bottom,
    lower is better; workload-matched (the sweep shape defines it).
  * ``overhead_flat``               — hard gate: the sharded scheduler's
    per-stream overhead must stay within the sweep's flat_factor bound;
    a growing ratio is the O(Q) scan creeping back regardless of machine.
  * ``store_bytes_peak``            — claim-check artifact-store peak
    physical bytes, lower is better; workload-matched.
  * ``cost_per_mframes``            — multi-tenant fleet $ per million
    frames under cost-aware scaling, lower is better; workload-matched
    (the bill scales with tenant mix and demand).
  * ``slo_attainment``              — worst per-tenant SLO attainment
    under cost-aware scaling, higher is better; workload-matched.
  * ``cost_beats_max``              — hard gate: cost-aware scaling must
    bill less than always-max provisioning at equal-or-better attainment.
  * ``isolation_ok``                — hard gate: a flooding tenant must
    not push another tenant's p99 past its class's isolation factor.
  * ``tenant_bit_identical``        — hard gate: the single-tenant default
    configuration must stay bitwise-identical to the plain scheduler.
  * ``hedge_p99_ratio``             — chaos bench: hedged p99 over unhedged
    p99 under the straggler wave, lower is better; workload-matched (the
    ratio is defined by the straggler schedule and fleet shape).
  * ``chaos_zero_loss``             — hard gate: no chunk may be lost under
    any injected fault class.
  * ``chaos_bit_identical``         — hard gate: an idle ``FaultInjector``
    must leave results and the full throughput report bitwise-identical
    to the plain scheduler.
  * ``corruption_recovered_all``    — hard gate: every injected artifact
    corruption must be detected by the store's content hash and repaired
    by re-derivation, with results bitwise equal to the fault-free run.
  * ``coldstart_p99_ratio``         — cold-start bench: predictive
    warm-pool tail p99 over always-cold p99 under bursty diurnal
    traffic, lower is better; workload-matched (the ratio is defined by
    the burst shape and cold_start_s).
  * ``warmpool_usd_ratio``          — predictive warm-pool ledger $ over
    always-warm $, lower is better; workload-matched.
  * ``warmpool_p99_beats_cold``     — hard gate: prewarming must beat the
    scale-to-zero extreme on tail latency.
  * ``warmpool_cost_beats_warm``    — hard gate: prediction must bill less
    than pinning the pool at max.
  * ``warmpool_attainment_ok``      — hard gate: the predictive policy may
    not attain less SLO than either provisioning extreme.
  * ``warmpool_bit_identical``      — hard gate: with prewarming disabled
    the serving plane must stay bitwise-identical to the policy-free
    plane at 1 and K shards.
  * ``fallback_chunks`` / ``fallback_frames`` — Fig. 15 fog-fallback
    absorption, gated EXACTLY when workloads match: the mode timeline is
    deterministic, so any drift means heartbeat detection timing changed.
  * ``fault_zero_loss`` / ``fault_recovered`` — hard gates: the WAN outage
    may degrade quality but never drop chunks, and the run must end back
    in cloud mode.

Usage:
  python scripts/check_bench_regression.py \
      --baseline benchmarks/baselines/BENCH_e2e.json \
      --fresh artifacts/BENCH_e2e.json
  python scripts/check_bench_regression.py \
      --baseline benchmarks/baselines/BENCH_steady.json \
      --fresh artifacts/BENCH_steady.json
  python scripts/check_bench_regression.py \
      --baseline benchmarks/baselines/BENCH_shard.json \
      --fresh artifacts/BENCH_shard.json
  python scripts/check_bench_regression.py --self-test   # gate the gate
"""
from __future__ import annotations

import argparse
import json
import sys
from typing import Dict, List, Tuple


def _load(path: str) -> Dict:
    with open(path) as fh:
        return json.load(fh)


def same_workload(baseline: Dict, fresh: Dict) -> bool:
    """Identical workload descriptors, field-for-field.

    Comparing only overlapping keys would let a payload that renamed or
    dropped a field masquerade as the baseline's workload and put the
    noisy, workload-bound gates back in play across operating points."""
    wb, wf = baseline.get("workload"), fresh.get("workload")
    if not isinstance(wb, dict) or not isinstance(wf, dict) or not wb:
        return False
    return wb == wf


def compare(baseline: Dict, fresh: Dict, tolerance: float
            ) -> Tuple[List[str], List[str]]:
    """Returns (ok lines, regression lines)."""
    ok: List[str] = []
    bad: List[str] = []
    matched = same_workload(baseline, fresh)

    def gate(metric: str, higher_better: bool, workload_bound: bool) -> None:
        if metric not in baseline or metric not in fresh:
            ok.append(f"skip {metric}: absent from "
                      f"{'baseline' if metric not in baseline else 'fresh'}")
            return
        if workload_bound and not matched:
            ok.append(f"skip {metric}: fresh run uses a different workload "
                      "(workload-bound metric)")
            return
        b, f = float(baseline[metric]), float(fresh[metric])
        if higher_better:
            floor = b * (1.0 - tolerance)
            line = (f"{metric}: fresh {f:.4g} vs baseline {b:.4g} "
                    f"(floor {floor:.4g})")
            (ok if f >= floor else bad).append(
                line if f >= floor else f"REGRESSION {line}")
        else:
            ceil = b * (1.0 + tolerance)
            line = (f"{metric}: fresh {f:.4g} vs baseline {b:.4g} "
                    f"(ceiling {ceil:.4g})")
            (ok if f <= ceil else bad).append(
                line if f <= ceil else f"REGRESSION {line}")

    def exact_gate(metric: str) -> None:
        """Workload-bound metric that must not move AT ALL: used for
        deterministic counts (the Fig. 15 mode timeline) where any drift
        is a behaviour change, not noise."""
        if metric not in baseline or metric not in fresh:
            ok.append(f"skip {metric}: absent from "
                      f"{'baseline' if metric not in baseline else 'fresh'}")
            return
        if not matched:
            ok.append(f"skip {metric}: fresh run uses a different workload "
                      "(workload-bound metric)")
            return
        b, f = baseline[metric], fresh[metric]
        line = f"{metric}: fresh {f} vs baseline {b} (exact)"
        (ok if f == b else bad).append(
            line if f == b else f"REGRESSION {line}")

    gate("host_syncs_per_flush_fused", higher_better=False,
         workload_bound=False)
    gate("classify_flops_saved_frac", higher_better=True,
         workload_bound=True)
    gate("p99_latency_s", higher_better=False, workload_bound=True)
    gate("bundle_bytes_peak", higher_better=False, workload_bound=True)
    gate("overhead_ratio", higher_better=False, workload_bound=True)
    gate("store_bytes_peak", higher_better=False, workload_bound=True)
    gate("cost_per_mframes", higher_better=False, workload_bound=True)
    gate("slo_attainment", higher_better=True, workload_bound=True)
    gate("hedge_p99_ratio", higher_better=False, workload_bound=True)
    gate("coldstart_p99_ratio", higher_better=False, workload_bound=True)
    gate("warmpool_usd_ratio", higher_better=False, workload_bound=True)
    exact_gate("fallback_chunks")
    exact_gate("fallback_frames")
    if "residency_flat" in fresh and not fresh["residency_flat"]:
        bad.append("REGRESSION residency_flat: device-buffer residency grew "
                   "over the steady-state run (flush-bundle retention leak)")
    if "overhead_flat" in fresh and not fresh["overhead_flat"]:
        bad.append("REGRESSION overhead_flat: per-stream scheduling "
                   "overhead grew with the stream count (sharded scheduler "
                   "no longer bounds the per-flush scan)")
    if "cost_beats_max" in fresh and not fresh["cost_beats_max"]:
        bad.append("REGRESSION cost_beats_max: cost-aware autoscaling no "
                   "longer bills less than always-max provisioning at equal "
                   "SLO attainment")
    if "isolation_ok" in fresh and not fresh["isolation_ok"]:
        bad.append("REGRESSION isolation_ok: a noisy tenant degraded "
                   "another tenant's p99 beyond its SLO class's isolation "
                   "factor (WFQ isolation broken)")
    if "tenant_bit_identical" in fresh and not fresh["tenant_bit_identical"]:
        bad.append("REGRESSION tenant_bit_identical: the single-tenant "
                   "default path diverged from the plain scheduler")
    if "chaos_zero_loss" in fresh and not fresh["chaos_zero_loss"]:
        bad.append("REGRESSION chaos_zero_loss: chunks were lost under "
                   "fault injection (graceful degradation broken)")
    if "chaos_bit_identical" in fresh and not fresh["chaos_bit_identical"]:
        bad.append("REGRESSION chaos_bit_identical: an idle fault injector "
                   "changed scheduler results or the throughput report")
    if ("corruption_recovered_all" in fresh
            and not fresh["corruption_recovered_all"]):
        bad.append("REGRESSION corruption_recovered_all: an injected "
                   "artifact corruption was served or lost instead of "
                   "detected-and-re-derived")
    if ("warmpool_p99_beats_cold" in fresh
            and not fresh["warmpool_p99_beats_cold"]):
        bad.append("REGRESSION warmpool_p99_beats_cold: predictive "
                   "prewarming no longer beats always-cold provisioning "
                   "on tail latency (cold start back on the critical path)")
    if ("warmpool_cost_beats_warm" in fresh
            and not fresh["warmpool_cost_beats_warm"]):
        bad.append("REGRESSION warmpool_cost_beats_warm: the predictive "
                   "warm pool no longer bills less than always-warm "
                   "provisioning")
    if ("warmpool_attainment_ok" in fresh
            and not fresh["warmpool_attainment_ok"]):
        bad.append("REGRESSION warmpool_attainment_ok: the predictive "
                   "policy attains less SLO than a provisioning extreme")
    if ("warmpool_bit_identical" in fresh
            and not fresh["warmpool_bit_identical"]):
        bad.append("REGRESSION warmpool_bit_identical: the prewarm-off "
                   "plane diverged from the policy-free scheduler")
    if "fault_zero_loss" in fresh and not fresh["fault_zero_loss"]:
        bad.append("REGRESSION fault_zero_loss: the WAN outage dropped "
                   "chunks instead of absorbing them on the fog fallback")
    if "fault_recovered" in fresh and not fresh["fault_recovered"]:
        bad.append("REGRESSION fault_recovered: the coordinator never "
                   "returned to cloud mode after the outage lifted")
    return ok, bad


def run_check(baseline_path: str, fresh_path: str, tolerance: float) -> int:
    ok, bad = compare(_load(baseline_path), _load(fresh_path), tolerance)
    for line in ok:
        print(f"  {line}")
    for line in bad:
        print(f"  {line}")
    if bad:
        print(f"# FAIL: {len(bad)} metric(s) regressed beyond "
              f"{tolerance:.0%} vs {baseline_path}")
        return 1
    print(f"# PASS: no perf regression beyond {tolerance:.0%} vs "
          f"{baseline_path}")
    return 0


def self_test(tolerance: float) -> int:
    """Gate the gate: the checker must accept an identical run, accept
    in-tolerance wobble, and reject a synthetically degraded one."""
    base = {"host_syncs_per_flush_fused": 1.0,
            "classify_flops_saved_frac": 0.6,
            "workload": {"streams": 8, "chunks_per_stream": 4}}
    cases = [
        ("identical", dict(base), False),
        ("in-tolerance wobble",
         dict(base, classify_flops_saved_frac=0.6 * 0.85), False),
        ("degraded compaction", dict(base, classify_flops_saved_frac=0.3),
         True),
        ("sync crept back", dict(base, host_syncs_per_flush_fused=4.0),
         True),
        ("quick workload, bad syncs",
         dict(base, host_syncs_per_flush_fused=4.0,
              workload={"streams": 4, "chunks_per_stream": 2}), True),
        ("quick workload, low compaction only",
         dict(base, classify_flops_saved_frac=0.3,
              workload={"streams": 4, "chunks_per_stream": 2}), False),
    ]
    steady_base = {"p99_latency_s": 9.0, "bundle_bytes_peak": 7.0e6,
                   "residency_flat": True,
                   "workload": {"streams": 64, "rounds": 10}}
    steady_cases = [
        ("steady identical", dict(steady_base), False),
        ("degraded p99 tail", dict(steady_base, p99_latency_s=12.0), True),
        ("grown residency peak",
         dict(steady_base, bundle_bytes_peak=1.5e7), True),
        ("lost residency flatness",
         dict(steady_base, residency_flat=False), True),
        ("quick steady workload, slow p99 only",
         dict(steady_base, p99_latency_s=12.0,
              workload={"streams": 8, "rounds": 3}), False),
        ("quick steady workload, growing residency",
         dict(steady_base, residency_flat=False,
              workload={"streams": 8, "rounds": 3}), True),
    ]
    shard_base = {"overhead_ratio": 1.05, "overhead_flat": True,
                  "p99_latency_s": 4.0, "store_bytes_peak": 2.0e7,
                  "workload": {"streams": [64, 256, 1024], "rounds": 4}}
    shard_cases = [
        ("shard identical", dict(shard_base), False),
        ("lost overhead flatness",
         dict(shard_base, overhead_ratio=1.8, overhead_flat=False), True),
        ("crept overhead ratio (still under flat bound)",
         dict(shard_base, overhead_ratio=1.29), True),
        ("grown store peak", dict(shard_base, store_bytes_peak=4.0e7), True),
        ("quick shard workload, grown store only",
         dict(shard_base, store_bytes_peak=4.0e7,
              workload={"streams": [16, 64], "rounds": 2}), False),
        ("quick shard workload, lost flatness",
         dict(shard_base, overhead_flat=False,
              workload={"streams": [16, 64], "rounds": 2}), True),
    ]
    tenancy_base = {"cost_per_mframes": 1200.0, "slo_attainment": 1.0,
                    "cost_beats_max": True, "isolation_ok": True,
                    "tenant_bit_identical": True,
                    "workload": {"rounds": 6, "streams_per_tenant": 2,
                                 "noisy_factor": 6}}
    tenancy_cases = [
        ("tenancy identical", dict(tenancy_base), False),
        ("bill crept up", dict(tenancy_base, cost_per_mframes=1600.0), True),
        ("attainment dropped",
         dict(tenancy_base, slo_attainment=0.7), True),
        ("cost-aware lost to always-max",
         dict(tenancy_base, cost_beats_max=False), True),
        ("noisy neighbor broke isolation",
         dict(tenancy_base, isolation_ok=False), True),
        ("tenancy broke bitwise identity",
         dict(tenancy_base, tenant_bit_identical=False), True),
        ("quick tenancy workload, pricier bill only",
         dict(tenancy_base, cost_per_mframes=1600.0,
              workload={"rounds": 2, "streams_per_tenant": 1,
                        "noisy_factor": 3}), False),
        ("quick tenancy workload, broken isolation",
         dict(tenancy_base, isolation_ok=False,
              workload={"rounds": 2, "streams_per_tenant": 1,
                        "noisy_factor": 3}), True),
    ]
    chaos_base = {"hedge_p99_ratio": 0.45, "chaos_zero_loss": True,
                  "chaos_bit_identical": True,
                  "corruption_recovered_all": True,
                  "workload": {"streams": 64, "chunks_per_stream": 5,
                               "straggler_factor": 10.0}}
    chaos_cases = [
        ("chaos identical", dict(chaos_base), False),
        ("hedge ratio crept up",
         dict(chaos_base, hedge_p99_ratio=0.58), True),
        ("chunk lost under fault", dict(chaos_base, chaos_zero_loss=False),
         True),
        ("idle injector diverged",
         dict(chaos_base, chaos_bit_identical=False), True),
        ("corruption served",
         dict(chaos_base, corruption_recovered_all=False), True),
        ("quick chaos workload, bad ratio only",
         dict(chaos_base, hedge_p99_ratio=0.9,
              workload={"streams": 16, "chunks_per_stream": 3,
                        "straggler_factor": 10.0}), False),
        ("quick chaos workload, chunk lost",
         dict(chaos_base, chaos_zero_loss=False,
              workload={"streams": 16, "chunks_per_stream": 3,
                        "straggler_factor": 10.0}), True),
    ]
    coldstart_base = {"coldstart_p99_ratio": 0.55,
                      "warmpool_usd_ratio": 0.6,
                      "warmpool_p99_beats_cold": True,
                      "warmpool_cost_beats_warm": True,
                      "warmpool_attainment_ok": True,
                      "warmpool_bit_identical": True,
                      "workload": {"streams": 12, "bursts": 6,
                                   "cold_start_s": 0.6}}
    coldstart_cases = [
        ("coldstart identical", dict(coldstart_base), False),
        ("p99 ratio crept up",
         dict(coldstart_base, coldstart_p99_ratio=0.75), True),
        ("usd ratio crept up",
         dict(coldstart_base, warmpool_usd_ratio=0.85), True),
        ("prewarming lost to always-cold",
         dict(coldstart_base, warmpool_p99_beats_cold=False), True),
        ("prediction pricier than pinning",
         dict(coldstart_base, warmpool_cost_beats_warm=False), True),
        ("attainment regressed",
         dict(coldstart_base, warmpool_attainment_ok=False), True),
        ("prewarm-off diverged",
         dict(coldstart_base, warmpool_bit_identical=False), True),
        ("quick coldstart workload, bad ratio only",
         dict(coldstart_base, coldstart_p99_ratio=0.95,
              workload={"streams": 8, "bursts": 5,
                        "cold_start_s": 0.6}), False),
        ("quick coldstart workload, prewarm-off diverged",
         dict(coldstart_base, warmpool_bit_identical=False,
              workload={"streams": 8, "bursts": 5,
                        "cold_start_s": 0.6}), True),
    ]
    fault_base = {"fallback_chunks": 2, "fallback_frames": 8,
                  "fault_zero_loss": True, "fault_recovered": True,
                  "workload": {"n": 10, "outage": [3, 6],
                               "failure_threshold": 2}}
    fault_cases = [
        ("fault identical", dict(fault_base), False),
        # exact gate: a one-chunk drift in either direction is a timing
        # behaviour change even though it is "within 20%"
        ("failover tripped one chunk late",
         dict(fault_base, fallback_chunks=1, fallback_frames=4), True),
        ("failover tripped one chunk early",
         dict(fault_base, fallback_chunks=3, fallback_frames=12), True),
        ("outage dropped chunks", dict(fault_base, fault_zero_loss=False),
         True),
        ("never recovered", dict(fault_base, fault_recovered=False), True),
        ("quick fault workload, different count only",
         dict(fault_base, fallback_chunks=1, fallback_frames=4,
              workload={"n": 6, "outage": [2, 4],
                        "failure_threshold": 2}), False),
        ("quick fault workload, dropped chunks",
         dict(fault_base, fault_zero_loss=False,
              workload={"n": 6, "outage": [2, 4],
                        "failure_threshold": 2}), True),
    ]
    failures = 0
    for ref, suite in ((base, cases), (steady_base, steady_cases),
                       (shard_base, shard_cases),
                       (tenancy_base, tenancy_cases),
                       (chaos_base, chaos_cases),
                       (coldstart_base, coldstart_cases),
                       (fault_base, fault_cases)):
        for name, fresh, want_fail in suite:
            _, bad = compare(ref, fresh, tolerance)
            got_fail = bool(bad)
            verdict = "ok" if got_fail == want_fail else "SELF-TEST FAILURE"
            print(f"  {verdict}: {name} -> "
                  f"{'rejected' if got_fail else 'accepted'}")
            failures += got_fail != want_fail
    if failures:
        print(f"# FAIL: self-test — {failures} case(s) misjudged")
        return 1
    print("# PASS: regression gate rejects degraded results and accepts "
          "healthy ones")
    return 0


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--baseline",
                    default="benchmarks/baselines/BENCH_e2e.json",
                    help="committed baseline json")
    ap.add_argument("--fresh", default="artifacts/BENCH_e2e.json",
                    help="freshly measured json")
    ap.add_argument("--tolerance", type=float, default=0.2,
                    help="allowed relative regression (default 20%%)")
    ap.add_argument("--self-test", action="store_true",
                    help="verify the gate fails on synthetic degradations")
    args = ap.parse_args()
    if args.self_test:
        raise SystemExit(self_test(args.tolerance))
    raise SystemExit(run_check(args.baseline, args.fresh, args.tolerance))


if __name__ == "__main__":
    main()
