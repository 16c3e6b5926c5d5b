"""Multi-tenant pipeline-as-a-service economics: 3 tenants x mixed SLO
classes on one shared fleet, with the monetary cost model driving the
autoscaler (the source paper's 50%-cloud-cost headline, reproduced at
simulated scale).

Three tenants register distinct function graphs on the SAME
registry/executor substrate (``tenancy.Tenancy``):

  * ``vision``  — the default High-Low detection pipeline, GOLD SLO,
    WFQ weight 4;
  * ``cascade`` — the big/little LLM cascade (cloud billed only for
    escalated frames), SILVER, weight 2;
  * ``retail``  — the Hysia-style video-to-retail content pipeline,
    BRONZE, weight 1.

The bench proves three claims, all hard-gated here and re-checked in CI
against the committed ``benchmarks/baselines/BENCH_tenancy.json``:

  (a) **cost-aware beats always-max**: scaling the shared replica pool
      with ``CostAwareAutoscaler`` (keep-alive $ vs ``cold_start_s``
      spin-up latency in the objective) lands a lower total $ than
      provisioning the pool at max the whole run, at equal-or-better
      per-tenant SLO attainment;
  (b) **noisy-neighbor isolation**: flooding the retail tenant with 6x
      its demand cannot degrade the vision tenant's p99 beyond its SLO
      class's ``isolation_factor`` (WFQ weights decide flush assembly
      before pipelines diverge);
  (c) **single-tenant bitwise identity**: the default configuration with
      tenancy machinery attached produces bit-identical results to the
      plain PR-7 scheduler.

Usage:
  PYTHONPATH=src python benchmarks/bench_tenancy.py          # full, gated
  PYTHONPATH=src python benchmarks/bench_tenancy.py --quick  # CI smoke
  PYTHONPATH=src python -m benchmarks.run --only bench_tenancy
"""
from __future__ import annotations

import argparse
import math
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))
sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import jax
import numpy as np

from benchmarks.common import write_json
from repro.configs.vpaas_video import ClassifierConfig, DetectorConfig
from repro.core.protocol import HighLowProtocol
from repro.models import classifier as clf_mod
from repro.models import detector as det_mod
from repro.serving.autoscaler import CostAwareAutoscaler
from repro.serving.batching import CrossStreamBatcher
from repro.serving.graph import GraphScheduler, VideoFunctionGraph
from repro.serving.ingest import ArtifactStore
from repro.serving.tenancy import (CostModel, SLOClass, Tenancy, TenantSpec,
                                   content_pipeline, llm_cascade_pipeline)
from repro.video import synthetic

BENCH_DET = DetectorConfig(name="bench-tenancy-det", image_hw=(32, 32),
                           widths=(8, 16))
BENCH_CLF = ClassifierConfig(name="bench-tenancy-clf", crop_hw=(16, 16),
                             widths=(8, 16), feature_dim=16)

# bench SLO classes: per-chunk latency on this simulated WAN sits ~1.8 s,
# so the classes bracket it with real headroom differences
GOLD_B = SLOClass("gold", 4.0, isolation_factor=1.3)
SILVER_B = SLOClass("silver", 6.0, isolation_factor=1.6)
BRONZE_B = SLOClass("bronze", 12.0, isolation_factor=2.0)

MAX_REPLICAS = 4
COLD_START_S = 0.2

# the three shipped pipelines (module-level: jit caches shared across the
# bench's runs, so per-run wall time is model-free scheduling work)
PIPE_CASCADE = llm_cascade_pipeline(name="bench-cascade")
PIPE_RETAIL = content_pipeline(name="bench-retail")

TENANTS = (
    ("vision", GOLD_B, 4.0, None),
    ("cascade", SILVER_B, 2.0, PIPE_CASCADE),
    ("retail", BRONZE_B, 1.0, PIPE_RETAIL),
)


def _chunks(seed: int, n: int, frames: int):
    rng = np.random.default_rng(seed)
    return [synthetic.make_chunk(rng, "traffic", num_frames=frames,
                                 hw=(32, 32)) for _ in range(n)]


def _autoscaler():
    proto_cloud_fps = 75.0          # CLOUD.detect_fps — frames/s per replica
    return CostAwareAutoscaler(
        min_devices=1, max_devices=MAX_REPLICAS, unit="replicas",
        replica_rate_usd_s=0.004, miss_value_usd=0.004,
        frame_service_s=1.0 / proto_cloud_fps,
        slo_slack_s=GOLD_B.slo_s * 0.5, cold_start_s=COLD_START_S)


def _run_fleet(graph, clf_params, *, rounds: int, frames: int,
               streams_per_tenant: int, cost_aware: bool,
               noisy_factor: int = 1):
    """One full simulated run of the 3-tenant fleet; returns (report,
    cost_report, states, wall)."""
    cost = CostModel()
    kw = dict(cloud_replicas=1, autoscaler=_autoscaler(),
              scale_unit="replicas", cold_start_s=COLD_START_S) \
        if cost_aware else dict(cloud_replicas=MAX_REPLICAS)
    sched = GraphScheduler(
        graph, batcher=CrossStreamBatcher(max_chunks=8, window=0.05),
        cost_model=cost,
        store=ArtifactStore(ttl=5.0, capacity_bytes=64e6), **kw)
    ten = Tenancy(graph, cost)
    states = []
    for name, slo_class, weight, pipe in TENANTS:
        ten.register(TenantSpec(name, slo_class, weight=weight,
                                pipeline=pipe))
        for i in range(streams_per_tenant):
            skw = {"W": clf_params["W"]} if pipe is None else {}
            states.append(ten.add_stream(sched, name, f"{name}-{i}", **skw))

    t0 = time.perf_counter()
    for i, st in enumerate(states):
        mult = noisy_factor if st.tenant.name == "retail" else 1
        for c in _chunks(5000 + 17 * i, rounds * mult, frames):
            sched.submit(st, c, learn=False)
    sched.run_until_idle()
    wall = time.perf_counter() - t0
    cost.close(max(s.clock for s in states))
    return sched.throughput_report(), states, wall


def _bitwise_check(graph, clf_params, *, rounds: int, frames: int) -> bool:
    """Claim (c): default single-tenant config with tenancy machinery
    attached is bit-identical to the plain scheduler."""
    streams = [_chunks(6000 + i, rounds, frames) for i in range(4)]

    def drive(sched, states):
        for st, cs in zip(states, streams):
            for c in cs:
                sched.submit(st, c, learn=False)
        sched.run_until_idle()

    plain = GraphScheduler(
        graph, batcher=CrossStreamBatcher(max_chunks=8, window=0.05))
    sa = [plain.add_stream(f"cam{i}", W=clf_params["W"], slo=GOLD_B.slo_s)
          for i in range(4)]
    drive(plain, sa)

    spec = TenantSpec("vision", GOLD_B, weight=1.0)
    tenanted = GraphScheduler(
        graph, batcher=CrossStreamBatcher(max_chunks=8, window=0.05),
        cost_model=CostModel())
    sb = [tenanted.add_stream(f"cam{i}", W=clf_params["W"],
                              slo=GOLD_B.slo_s, tenant=spec)
          for i in range(4)]
    drive(tenanted, sb)

    for x, y in zip(sa, sb):
        if len(x.results) != len(y.results):
            return False
        for (_, r1, m1), (_, r2, m2) in zip(x.results, y.results):
            if m1 != m2 or r1.latency.total != r2.latency.total:
                return False
            if r1.wan_bytes != r2.wan_bytes \
                    or r1.coord_bytes != r2.coord_bytes:
                return False
            if not (np.array_equal(r1.boxes, r2.boxes)
                    and np.array_equal(r1.labels, r2.labels)
                    and np.array_equal(r1.valid, r2.valid)
                    and np.array_equal(r1.fog_scores, r2.fog_scores)):
                return False
    return True


def bench(rounds: int = 6, frames: int = 2, streams_per_tenant: int = 2,
          noisy_factor: int = 6, quick: bool = False):
    det_params = det_mod.init_detector(BENCH_DET, jax.random.PRNGKey(0))
    clf_params = clf_mod.init_classifier(BENCH_CLF, jax.random.PRNGKey(1))
    proto = HighLowProtocol(BENCH_DET, BENCH_CLF)
    graph = VideoFunctionGraph(proto, det_params, clf_params)

    # warm jit caches on a throwaway run so wall figures are schedule-only
    _run_fleet(graph, clf_params, rounds=1, frames=frames,
               streams_per_tenant=1, cost_aware=False)

    # (a) cost-aware vs always-max on the identical clean workload
    rep_max, states_max, wall_max = _run_fleet(
        graph, clf_params, rounds=rounds, frames=frames,
        streams_per_tenant=streams_per_tenant, cost_aware=False)
    rep_ca, states_ca, wall_ca = _run_fleet(
        graph, clf_params, rounds=rounds, frames=frames,
        streams_per_tenant=streams_per_tenant, cost_aware=True)
    usd_max = rep_max["cost"]["total_usd"]
    usd_ca = rep_ca["cost"]["total_usd"]
    att_max = min(v["slo_attainment"] for v in rep_max["tenants"].values())
    att_ca = min(v["slo_attainment"] for v in rep_ca["tenants"].values())
    saving = 1.0 - usd_ca / max(usd_max, 1e-12)
    cost_beats_max = usd_ca < usd_max and att_ca >= att_max

    # (b) noisy neighbor: retail floods; vision's p99 must hold its bound
    rep_noisy, _, _ = _run_fleet(
        graph, clf_params, rounds=rounds, frames=frames,
        streams_per_tenant=streams_per_tenant, cost_aware=True,
        noisy_factor=noisy_factor)
    p99_clean = rep_ca["tenants"]["vision"]["p99_latency_s"]
    p99_noisy = rep_noisy["tenants"]["vision"]["p99_latency_s"]
    noisy_ratio = p99_noisy / max(p99_clean, 1e-12)
    isolation_ok = noisy_ratio <= GOLD_B.isolation_factor

    # (c) bitwise single-tenant identity
    bit_identical = _bitwise_check(graph, clf_params, rounds=rounds,
                                   frames=frames)

    # ledger conservation, asserted on every full payload
    cr = rep_ca["cost"]
    ledger_ok = bool(np.isclose(
        math.fsum(v["total_usd"] for v in cr["tenants"].values()),
        cr["total_usd"], rtol=1e-9))

    payload = {
        "workload": {"rounds": rounds, "frames_per_chunk": frames,
                     "streams_per_tenant": streams_per_tenant,
                     "tenants": [t[0] for t in TENANTS],
                     "noisy_factor": noisy_factor,
                     "max_replicas": MAX_REPLICAS,
                     "cold_start_s": COLD_START_S, "quick": bool(quick)},
        "always_max_usd": usd_max,
        "cost_aware_usd": usd_ca,
        "cost_saving_frac": saving,
        "cost_per_mframes": cr["cost_per_mframes"],
        "slo_attainment": att_ca,
        "slo_attainment_always_max": att_max,
        "per_tenant": {
            name: {
                "cost_per_mframes": cr["tenants"][name]["cost_per_mframes"],
                "total_usd": cr["tenants"][name]["total_usd"],
                "invocations": cr["tenants"][name]["invocations"],
                "p99_latency_s": rep_ca["tenants"][name]["p99_latency_s"],
                "slo_attainment": rep_ca["tenants"][name]["slo_attainment"],
            } for name, *_ in TENANTS},
        "provisioned_replica_s_max": rep_max["cost"][
            "provisioned_replica_s"],
        "provisioned_replica_s_ca": cr["provisioned_replica_s"],
        "noisy_p99_ratio": noisy_ratio,
        "isolation_bound": GOLD_B.isolation_factor,
        "isolation_ok": bool(isolation_ok),
        "cost_beats_max": bool(cost_beats_max),
        "tenant_bit_identical": bool(bit_identical),
        "ledger_conserves": ledger_ok,
        "store_spills": rep_ca.get("store_spills", 0),
        "wall_s_cost_aware": wall_ca,
        "wall_s_always_max": wall_max,
    }
    rows = [
        {"name": "always_max", "us_per_call": f"{1e6 * wall_max:.0f}",
         "usd": f"{usd_max:.5f}",
         "slo_attainment": f"{att_max:.3f}",
         "replica_s": f"{payload['provisioned_replica_s_max']:.1f}"},
        {"name": "cost_aware", "us_per_call": f"{1e6 * wall_ca:.0f}",
         "usd": f"{usd_ca:.5f}",
         "slo_attainment": f"{att_ca:.3f}",
         "replica_s": f"{payload['provisioned_replica_s_ca']:.1f}",
         "saving_frac": f"{saving:.2f}"},
        {"name": "noisy_neighbor", "us_per_call": "0",
         "vision_p99_ratio": f"{noisy_ratio:.3f}",
         "bound": f"{GOLD_B.isolation_factor:.2f}",
         "isolated": "ok" if isolation_ok else "VIOLATED"},
        {"name": "bitwise_default_path", "us_per_call": "0",
         "identical": "ok" if bit_identical else "DIVERGED"},
    ]
    rows += [{"name": f"tenant_{name}", "us_per_call": "0",
              "cost_per_mframes": f"{v['cost_per_mframes']:.1f}",
              "p99_s": f"{v['p99_latency_s']:.3f}",
              "slo_attainment": f"{v['slo_attainment']:.3f}"}
             for name, v in payload["per_tenant"].items()]
    return rows, payload


def gate(payload) -> list:
    fails = []
    if not payload["cost_beats_max"]:
        fails.append(
            f"cost-aware scaling did not beat always-max at equal SLO "
            f"attainment (${payload['cost_aware_usd']:.5f} vs "
            f"${payload['always_max_usd']:.5f}, attainment "
            f"{payload['slo_attainment']:.3f} vs "
            f"{payload['slo_attainment_always_max']:.3f})")
    if not payload["isolation_ok"]:
        fails.append(
            f"noisy neighbor degraded vision p99 by "
            f"{payload['noisy_p99_ratio']:.2f}x "
            f"(bound {payload['isolation_bound']:.2f}x)")
    if not payload["tenant_bit_identical"]:
        fails.append("single-tenant default path diverged from the plain "
                     "scheduler (bitwise identity broken)")
    if not payload["ledger_conserves"]:
        fails.append("cost ledger does not conserve: per-tenant spend sum "
                     "!= fleet spend")
    return fails


def run(ctx=None, quick: bool = False):
    """benchmarks.run entry point — also emits artifacts/BENCH_tenancy.json."""
    rows, payload = bench(rounds=2 if quick else 6,
                          streams_per_tenant=1 if quick else 2,
                          noisy_factor=3 if quick else 6, quick=quick)
    write_json(payload, os.path.join(os.path.dirname(__file__), "..",
                                     "artifacts", "BENCH_tenancy.json"))
    fails = gate(payload)
    if fails:
        raise RuntimeError("; ".join(fails))
    return rows


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="small workload, gates still asserted (CI smoke)")
    ap.add_argument("--rounds", type=int, default=6)
    ap.add_argument("--frames", type=int, default=2)
    ap.add_argument("--streams-per-tenant", type=int, default=2)
    ap.add_argument("--noisy-factor", type=int, default=6)
    ap.add_argument("--json", default="BENCH_tenancy.json")
    args = ap.parse_args()

    if args.quick:
        rows, payload = bench(rounds=2, frames=args.frames,
                              streams_per_tenant=1, noisy_factor=3,
                              quick=True)
    else:
        rows, payload = bench(rounds=args.rounds, frames=args.frames,
                              streams_per_tenant=args.streams_per_tenant,
                              noisy_factor=args.noisy_factor)
    for row in rows:
        print(",".join(f"{k}={v}" for k, v in row.items()))
    write_json(payload, args.json)
    print(f"# tenancy: cost-aware ${payload['cost_aware_usd']:.5f} vs "
          f"always-max ${payload['always_max_usd']:.5f} "
          f"({100 * payload['cost_saving_frac']:.0f}% saved) at min "
          f"attainment {payload['slo_attainment']:.3f}; noisy vision p99 "
          f"{payload['noisy_p99_ratio']:.2f}x (bound "
          f"{payload['isolation_bound']:.2f}x); bitwise "
          f"{'ok' if payload['tenant_bit_identical'] else 'BROKEN'}")
    print(f"# wrote {args.json}")
    fails = gate(payload)
    for f in fails:
        print(f"# FAIL: {f}", file=sys.stderr)
    if fails:
        raise SystemExit(1)
    print("# PASS: cost-aware beats always-max; tenants isolated; "
          "default path bitwise-identical")


if __name__ == "__main__":
    main()
