"""Cold-start economics under bursty diurnal traffic: predictive warm
pool vs always-cold and always-warm provisioning.

The paper's serverless-elasticity claim hinges on what spin-up costs when
demand returns.  ``Router(cold_start_s=)`` *models* the spin-up; the
:class:`~repro.serving.autoscaler.WarmPoolPolicy` *manages* it — a
diurnal forecaster learns the burst period from arrival history and the
scheduler prewarms the pool ``cold_start_s + margin`` ahead of each
predicted burst, then sheds it past the break-even keep-alive horizon
(``miss_value_usd / replica_rate_usd_s``).

The harness drives one fleet of streams through periodic bursts (every
stream submits one chunk per burst; events are stepped open-loop in
simulated-time order so a forecast check can never observe the future)
under three provisioning policies over the SAME frozen workload:

  * **always-cold** — reactive autoscaler only; the pool is torn down to
    one replica between bursts, so every burst pays spin-up on the
    critical path (the serverless scale-to-zero extreme);
  * **always-warm** — the pool pinned at ``MAX_REPLICAS`` for the whole
    run; no spin-up ever, maximal keep-alive spend (the provisioned
    extreme);
  * **predictive** — the warm-pool policy: prewarm ahead of forecast
    bursts, shed between them.

Gates (hard here, re-checked in CI against the committed
``benchmarks/baselines/BENCH_coldstart.json``):

  (a) predictive tail p99 latency beats always-cold
      (``coldstart_p99_ratio < 1``) — the cold start left the critical
      path;
  (b) predictive ledger $ beats always-warm
      (``warmpool_usd_ratio < 1``) — prediction is cheaper than pinning;
  (c) equal SLO attainment: predictive attains at least what BOTH
      baselines attain;
  (d) **prewarm-off bitwise identity**: a scheduler with the policy
      attached but disabled produces bit-identical results AND reports
      to the policy-free plane, at 1 and K shards.

Usage:
  PYTHONPATH=src python benchmarks/bench_coldstart.py          # full, gated
  PYTHONPATH=src python benchmarks/bench_coldstart.py --quick  # CI smoke
  PYTHONPATH=src python -m benchmarks.run --only bench_coldstart
"""
from __future__ import annotations

import argparse
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
from repro.serving.autoscaler import CostAwareAutoscaler, WarmPoolPolicy
from repro.serving.batching import CrossStreamBatcher
from repro.serving.graph import GraphScheduler, VideoFunctionGraph
from repro.serving.shards import ShardedScheduler
from repro.serving.tenancy import CostModel, SLOClass, TenantSpec
from repro.video import synthetic

# cold-start economics is a control-plane property: bench-size models keep
# the wall time in the scheduler, not the matmuls
BENCH_DET = DetectorConfig(name="bench-coldstart-det", image_hw=(32, 32),
                           widths=(8, 16))
BENCH_CLF = ClassifierConfig(name="bench-coldstart-clf", crop_hw=(16, 16),
                             widths=(8, 16), feature_dim=16)

# wall-clock-derived report keys (everything else must match bitwise
# between the plain plane and the disabled-policy plane)
REPORT_SKIP = ("wall", "per_s", "overhead")

PERIOD_S = 8.0          # burst spacing, > one chunk's closed-loop latency
COLD_START_S = 0.6      # deliberately fat: the latency the policy hides
MAX_REPLICAS = 4
SLO_S = 5.0             # generous: every policy attains it; p99 is gated
FRAMES = 4
# p99 is measured on bursts after the forecaster has >= 2 full periods of
# history (detection needs them); earlier bursts are its warm-up
TAIL_FROM_BURST = 3


class _Harness:
    """One shared graph (jit caches) + a frozen per-burst chunk schedule;
    every policy replays the identical workload on a fresh scheduler."""

    def __init__(self, n_streams: int, bursts: int):
        self.n_streams = n_streams
        self.bursts = bursts
        det_params = det_mod.init_detector(BENCH_DET, jax.random.PRNGKey(0))
        self.clf_params = clf_mod.init_classifier(BENCH_CLF,
                                                  jax.random.PRNGKey(1))
        self.graph = VideoFunctionGraph(HighLowProtocol(BENCH_DET, BENCH_CLF),
                                        det_params, self.clf_params)
        rng = np.random.default_rng(17)
        pool = [synthetic.make_chunk(rng, "traffic", num_frames=FRAMES,
                                     hw=(32, 32)) for _ in range(8)]
        # stream i submits chunk schedule[i][b] in burst b
        self.schedule = [[pool[(i + b) % len(pool)] for b in range(bursts)]
                         for i in range(n_streams)]
        self.end_t = bursts * PERIOD_S

    def policy(self, enabled: bool = True) -> WarmPoolPolicy:
        return WarmPoolPolicy(
            cold_start_s=COLD_START_S, frame_service_s=1.0 / 75.0,
            slo_slack_s=0.2, max_replicas=MAX_REPLICAS, enabled=enabled)

    def _sched(self, *, replicas: int, autoscaler, warm_pool, cost):
        return GraphScheduler(
            self.graph,
            batcher=CrossStreamBatcher(max_chunks=4, window=0.05),
            cost_model=cost, cloud_replicas=replicas,
            autoscaler=autoscaler,
            scale_unit="replicas" if autoscaler is not None else "devices",
            cold_start_s=COLD_START_S, warm_pool=warm_pool)

    def drive(self, mode: str):
        """Open-loop diurnal run under one provisioning policy.  Returns
        (throughput_report, cost_report, tail latencies, all latencies)."""
        cost = CostModel()
        cost.register(TenantSpec("default", slo_class=SLOClass(
            "gold", SLO_S)))
        pol = None
        if mode == "predictive":
            pol = self.policy()
            asc = CostAwareAutoscaler(
                min_devices=1, max_devices=MAX_REPLICAS, unit="replicas",
                cold_start_s=COLD_START_S, warm_pool=pol)
            sched = self._sched(replicas=1, autoscaler=asc,
                                warm_pool=pol, cost=cost)
        elif mode == "cold":
            asc = CostAwareAutoscaler(
                min_devices=1, max_devices=MAX_REPLICAS, unit="replicas",
                cold_start_s=COLD_START_S)
            sched = self._sched(replicas=1, autoscaler=asc, warm_pool=None,
                                cost=cost)
        elif mode == "warm":
            sched = self._sched(replicas=MAX_REPLICAS, autoscaler=None,
                                warm_pool=None, cost=cost)
        else:
            raise ValueError(mode)

        states = [sched.add_stream(f"cam{i:03d}", W=self.clf_params["W"],
                                   slo=SLO_S)
                  for i in range(self.n_streams)]
        for b in range(self.bursts):
            t0 = b * PERIOD_S
            for st in states:
                st.clock = max(st.clock, t0)
            for st, cs in zip(states, self.schedule):
                sched.submit(st, cs[b], learn=False)
            # step events in simulated order up to the next burst, so a
            # forecast check never observes arrivals from its own future
            while True:
                k = sched._peek_key()
                if k is None or k[0] >= (b + 1) * PERIOD_S:
                    break
                sched.step()
            if mode == "cold":
                # serverless scale-to-zero extreme: tear the pool down
                # after every burst drains, so the next one starts cold
                sched.router.scale_replicas(
                    1, now=(b + 1) * PERIOD_S - 0.05)
        sched.run_until_idle()
        cost.close(max(self.end_t, max(st.clock for st in states)))

        lat = np.asarray([[r.latency.total for _, r, _ in st.results]
                          for st in states])          # (streams, bursts)
        assert lat.shape == (self.n_streams, self.bursts), "chunk loss"
        tail = lat[:, TAIL_FROM_BURST:].ravel()
        return (sched.throughput_report(), cost.cost_report(store=None),
                tail, lat.ravel())

    # -- identity leg ----------------------------------------------------
    def identity_run(self, warm_pool, shards: int):
        sched = ShardedScheduler(
            self.graph, num_shards=shards, use_store=False,
            batcher_factory=lambda i: CrossStreamBatcher(max_chunks=4,
                                                         window=0.05),
            cloud_replicas=2, warm_pool=warm_pool)
        states = [sched.add_stream(f"cam{i:03d}", W=self.clf_params["W"],
                                   slo=SLO_S)
                  for i in range(self.n_streams)]
        for st, cs in zip(states, self.schedule):
            for c in cs[:3]:
                sched.submit(st, c, learn=False)
        sched.run_until_idle()
        results = [[(np.asarray(r.boxes), np.asarray(r.labels),
                     np.asarray(r.valid), r.latency.total)
                    for _, r, _ in s.results] for s in states]
        return sched.throughput_report(), results


def _results_bitwise(results_a, results_b) -> bool:
    for sa, sb in zip(results_a, results_b):
        if len(sa) != len(sb):
            return False
        for (ba, la, va, ta), (bb, lb, vb, tb) in zip(sa, sb):
            if not (np.array_equal(ba, bb) and np.array_equal(la, lb)
                    and np.array_equal(va, vb) and ta == tb):
                return False
    return True


def _report_diff(rep_a: dict, rep_b: dict) -> list:
    return sorted(k for k in set(rep_a) | set(rep_b)
                  if not any(s in k for s in REPORT_SKIP)
                  and rep_a.get(k) != rep_b.get(k))


def bench(n_streams: int = 12, bursts: int = 6, shards_k: int = 2):
    h = _Harness(n_streams, bursts)
    t0 = time.perf_counter()

    cold_rep, cold_cost, cold_tail, cold_all = h.drive("cold")
    warm_rep, warm_cost, warm_tail, warm_all = h.drive("warm")
    pred_rep, pred_cost, pred_tail, pred_all = h.drive("predictive")

    # -- prewarm-off bitwise identity at 1 and K shards ------------------
    rep_p1, res_p1 = h.identity_run(None, 1)
    rep_o1, res_o1 = h.identity_run(h.policy(enabled=False), 1)
    rep_pK, res_pK = h.identity_run(None, shards_k)
    rep_oK, res_oK = h.identity_run(h.policy(enabled=False), shards_k)
    diff1 = _report_diff(rep_p1, rep_o1)
    diffK = _report_diff(rep_pK, rep_oK)
    bit_identical = (not diff1 and not diffK
                     and _results_bitwise(res_p1, res_o1)
                     and _results_bitwise(res_pK, res_oK))
    wall = time.perf_counter() - t0

    p99 = lambda xs: float(np.percentile(np.asarray(xs), 99))
    cold_p99, warm_p99, pred_p99 = p99(cold_tail), p99(warm_tail), p99(
        pred_tail)
    attain = {"cold": cold_rep["slo_attainment"],
              "warm": warm_rep["slo_attainment"],
              "predictive": pred_rep["slo_attainment"]}

    payload = {
        "workload": {"streams": n_streams, "bursts": bursts,
                     "frames_per_chunk": FRAMES, "period_s": PERIOD_S,
                     "cold_start_s": COLD_START_S,
                     "max_replicas": MAX_REPLICAS, "slo_s": SLO_S,
                     "tail_from_burst": TAIL_FROM_BURST,
                     "shards_k": shards_k},
        "cold_p99_s": cold_p99,
        "warm_p99_s": warm_p99,
        "predictive_p99_s": pred_p99,
        "coldstart_p99_ratio": pred_p99 / cold_p99 if cold_p99 else 1.0,
        "cold_usd": cold_cost["total_usd"],
        "warm_usd": warm_cost["total_usd"],
        "predictive_usd": pred_cost["total_usd"],
        "warmpool_usd_ratio": (pred_cost["total_usd"]
                               / warm_cost["total_usd"]
                               if warm_cost["total_usd"] else 1.0),
        # scalar so the shared slo_attainment gate applies (higher-better,
        # workload-matched); the per-policy split rides alongside
        "slo_attainment": attain["predictive"],
        "attainment_cold": attain["cold"],
        "attainment_warm": attain["warm"],
        "prewarm_events": pred_rep["warm_prewarm_events"],
        "replicas_prewarmed": pred_rep["warm_replicas_prewarmed"],
        "shed_events": pred_rep["warm_shed_events"],
        "prewarm_spinups": pred_cost["prewarm_spinups"],
        "prewarm_cost_usd": pred_cost["prewarm_cost"],
        "warmpool_p99_beats_cold": pred_p99 < cold_p99,
        "warmpool_cost_beats_warm": (pred_cost["total_usd"]
                                     < warm_cost["total_usd"]),
        "warmpool_attainment_ok": (
            attain["predictive"] >= attain["cold"] - 1e-12
            and attain["predictive"] >= attain["warm"] - 1e-12),
        "warmpool_bit_identical": bit_identical,
        "identity_diff_keys": diff1 + diffK,
        "wall_s": wall,
    }
    rows = [
        {"name": "always_cold", "us_per_call": "0",
         "p99_s": f"{cold_p99:.3f}", "usd": f"{cold_cost['total_usd']:.6f}",
         "attainment": f"{attain['cold']:.3f}"},
        {"name": "always_warm", "us_per_call": "0",
         "p99_s": f"{warm_p99:.3f}", "usd": f"{warm_cost['total_usd']:.6f}",
         "attainment": f"{attain['warm']:.3f}"},
        {"name": "predictive", "us_per_call": "0",
         "p99_s": f"{pred_p99:.3f}", "usd": f"{pred_cost['total_usd']:.6f}",
         "attainment": f"{attain['predictive']:.3f}",
         "prewarms": pred_rep["warm_replicas_prewarmed"],
         "sheds": pred_rep["warm_shed_events"]},
        {"name": "prewarm_off_identity", "us_per_call": "0",
         "bitwise": "ok" if bit_identical else "DIVERGED",
         "diff_keys": len(diff1) + len(diffK)},
    ]
    return rows, payload


def gate(payload: dict) -> list:
    fails = []
    if not payload["warmpool_p99_beats_cold"]:
        fails.append(
            f"predictive p99 {payload['predictive_p99_s']:.3f}s does not "
            f"beat always-cold {payload['cold_p99_s']:.3f}s")
    if not payload["warmpool_cost_beats_warm"]:
        fails.append(
            f"predictive ${payload['predictive_usd']:.6f} does not beat "
            f"always-warm ${payload['warm_usd']:.6f}")
    if not payload["warmpool_attainment_ok"]:
        fails.append(f"SLO attainment regressed: "
                     f"{payload['slo_attainment']}")
    if not payload["warmpool_bit_identical"]:
        fails.append("prewarm-off plane diverged from the policy-free "
                     f"plane: {payload['identity_diff_keys']}")
    if payload["replicas_prewarmed"] <= 0:
        fails.append("predictive run never prewarmed a replica")
    return fails


def run(ctx=None, quick: bool = False):
    """benchmarks.run entry point — emits artifacts/BENCH_coldstart.json."""
    rows, payload = (bench(n_streams=8, bursts=5) if quick else bench())
    write_json(payload, os.path.join(os.path.dirname(__file__), "..",
                                     "artifacts", "BENCH_coldstart.json"))
    fails = gate(payload)
    if fails:
        raise SystemExit("bench_coldstart gate FAILED:\n  "
                         + "\n  ".join(fails))
    return rows


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="smaller fleet / fewer bursts (CI smoke)")
    ap.add_argument("--json", default="BENCH_coldstart.json")
    args = ap.parse_args()

    rows, payload = (bench(n_streams=8, bursts=5) if args.quick
                     else bench())
    for row in rows:
        print(",".join(f"{k}={v}" for k, v in row.items()))
    write_json(payload, args.json)
    print(f"# coldstart: predictive p99 {payload['predictive_p99_s']:.3f}s "
          f"vs cold {payload['cold_p99_s']:.3f}s "
          f"(ratio {payload['coldstart_p99_ratio']:.3f}); "
          f"$ {payload['predictive_usd']:.6f} vs warm "
          f"{payload['warm_usd']:.6f} "
          f"(ratio {payload['warmpool_usd_ratio']:.3f}); "
          f"{payload['replicas_prewarmed']} prewarms, "
          f"{payload['shed_events']} sheds")
    print(f"# wrote {args.json}")
    fails = gate(payload)
    if fails:
        raise SystemExit("bench_coldstart gate FAILED:\n  "
                         + "\n  ".join(fails))


if __name__ == "__main__":
    main()
