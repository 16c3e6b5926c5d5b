"""Chaos plane: a 64-stream fleet driven through a multi-domain fault
schedule with verified graceful degradation.

The fault-tolerance bench (Fig. 15) covers the binary failure domains —
WAN outage and permanent replica death.  Real cloud-fog deployments fail
mostly through *degraded* states, so this harness drives the sharded
serving plane through the :class:`~repro.serving.fault.FaultInjector`'s
generalized schedule, one scenario per fault class, and gates the
platform's degradation contract:

  * **Idle-injector identity** — a scheduler with a ``FaultInjector``
    attached but nothing scheduled must stay *bitwise identical* to the
    plain scheduler (results AND the full ``throughput_report``), at one
    shard and at K shards.  The chaos plane must cost nothing when quiet.
  * **Straggler wave** — two replicas serve 10x slower for the whole run;
    deadline-aware hedged dispatch must cut the p99 chunk latency to
    <= ``hedge_bound`` (0.6) of the unhedged run, with zero chunk loss
    and every speculative duplicate billed.
  * **Flap storm** — staggered down-then-up windows across the pool
    (always >= 1 replica healthy); health probes must re-admit every
    flapped replica and no chunk may be lost.
  * **Link brownout** — mid-run bandwidth/RTT degradation; transfers get
    slower, nothing is lost.
  * **Artifact corruption** — stored payload bytes flipped under the
    scheduler; the store's content-hash check must detect every injected
    corruption and the scheduler must re-derive the payload from the
    source chunk: detected == repaired == injected, and results stay
    bitwise equal to the fault-free run.

Reported and written to ``BENCH_chaos.json``; gated in CI by
``scripts/check_bench_regression.py`` (hedge p99 ratio, zero loss,
bit identity, corruption recovery).

Usage:
  PYTHONPATH=src python benchmarks/bench_chaos.py          # full, gated
  PYTHONPATH=src python benchmarks/bench_chaos.py --quick  # CI smoke
  PYTHONPATH=src python -m benchmarks.run --only bench_chaos
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
from repro.serving.batching import CrossStreamBatcher
from repro.serving.fault import FaultInjector
from repro.serving.graph import VideoFunctionGraph
from repro.serving.ingest import ArtifactStore
from repro.serving.shards import ShardedScheduler
from repro.video import synthetic

# chaos is a control-plane property: bench-size models keep the wall time
# in the scheduler, not the matmuls
BENCH_DET = DetectorConfig(name="bench-chaos-det", image_hw=(32, 32),
                           widths=(8, 16))
BENCH_CLF = ClassifierConfig(name="bench-chaos-clf", crop_hw=(16, 16),
                             widths=(8, 16), feature_dim=16)

# wall-clock-derived report keys (everything else must match bitwise
# between a plain run and an idle-injector run)
REPORT_SKIP = ("wall", "per_s", "overhead")

STRAGGLER_FACTOR = 10.0
STRAGGLER_UIDS = (0, 1)     # 2 of 4 replicas slow: pick() alone can't dodge
HEDGE_SLO = 0.5


class _Harness:
    """One shared graph (jit caches) + a frozen chunk schedule; every
    scenario replays the identical workload against a fresh scheduler."""

    def __init__(self, n_streams: int, n_chunks: int, frames: int,
                 replicas: int):
        self.n_streams = n_streams
        self.n_chunks = n_chunks
        self.frames = frames
        self.replicas = replicas
        det_params = det_mod.init_detector(BENCH_DET, jax.random.PRNGKey(0))
        self.clf_params = clf_mod.init_classifier(BENCH_CLF,
                                                  jax.random.PRNGKey(1))
        self.graph = VideoFunctionGraph(HighLowProtocol(BENCH_DET, BENCH_CLF),
                                        det_params, self.clf_params)
        # shared 8-chunk pool, offset per stream: heavy cross-stream dedup
        # plus enough distinct payloads for the corruption scenario
        rng = np.random.default_rng(7)
        pool = [synthetic.make_chunk(rng, "traffic", num_frames=frames,
                                     hw=(32, 32)) for _ in range(8)]
        self.streams = [[pool[(i + j) % len(pool)] for j in range(n_chunks)]
                        for i in range(n_streams)]

    def injector(self) -> FaultInjector:
        return FaultInjector(network=self.graph.protocol.network)

    def run(self, fault, *, shards: int = 1, slo=None, hedging: bool = True):
        store = ArtifactStore(integrity=True)
        sched = ShardedScheduler(
            self.graph, num_shards=shards, store=store,
            batcher_factory=lambda i: CrossStreamBatcher(max_chunks=4,
                                                         window=0.05),
            cloud_replicas=self.replicas, fault=fault,
            hedging=hedging,
            # cross-scenario bitwise comparison reads every result after
            # the run; sealing would discard the fields first
            max_retained_bundles=None)
        states = [sched.add_stream(f"cam{i:03d}", W=self.clf_params["W"],
                                   slo=slo)
                  for i in range(self.n_streams)]
        for st, cs in zip(states, self.streams):
            for c in cs:
                sched.submit(st, c, learn=False)
        sched.drain()
        # the NetworkModel is shared through the graph: scrub any brownout
        # schedule so the next scenario starts on a clean link
        self.graph.protocol.network.brownouts.clear()
        # materialize result fields NOW — they are lazy views into flush
        # bundles, and the retention cap seals old bundles long before the
        # cross-scenario comparisons run
        results = [[(np.asarray(r.boxes), np.asarray(r.labels),
                     np.asarray(r.valid), r.latency.total)
                    for _, r, _ in s.results] for s in states]
        return sched, results

    @property
    def expected(self) -> int:
        return self.n_streams * self.n_chunks


def _latencies(results) -> np.ndarray:
    return np.asarray([lat for s in results for _, _, _, lat in s])


def _count(results) -> int:
    return sum(len(s) for s in results)


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
    """Keys whose values differ, ignoring wall-clock-derived figures."""
    return sorted(k for k in set(rep_a) | set(rep_b)
                  if not any(s in k for s in REPORT_SKIP)
                  and rep_a.get(k) != rep_b.get(k))


def bench(n_streams: int = 64, n_chunks: int = 5, frames: int = 2,
          replicas: int = 4, shards_k: int = 4, corruptions: int = 4,
          hedge_bound: float = 0.6):
    h = _Harness(n_streams, n_chunks, frames, replicas)
    losses = {}     # scenario -> chunks finalized (all must == expected)

    # -- idle-injector identity at 1 and K shards ------------------------
    t0 = time.perf_counter()
    plain1, s_plain1 = h.run(None, shards=1, slo=HEDGE_SLO)
    idle1, s_idle1 = h.run(h.injector(), shards=1, slo=HEDGE_SLO)
    plainK, s_plainK = h.run(None, shards=shards_k, slo=HEDGE_SLO)
    idleK, s_idleK = h.run(h.injector(), shards=shards_k, slo=HEDGE_SLO)
    diff1 = _report_diff(plain1.throughput_report(),
                         idle1.throughput_report())
    diffK = _report_diff(plainK.throughput_report(),
                         idleK.throughput_report())
    bit_identical = (not diff1 and not diffK
                     and _results_bitwise(s_plain1, s_idle1)
                     and _results_bitwise(s_plainK, s_idleK))
    losses["plain"] = _count(s_plain1)

    # -- straggler wave: hedged vs unhedged ------------------------------
    def straggler_injector():
        fi = h.injector()
        for uid in STRAGGLER_UIDS:
            fi.add_straggler(uid, 0.0, 1e9, STRAGGLER_FACTOR)
        return fi

    unhedged, s_unhedged = h.run(straggler_injector(), slo=HEDGE_SLO,
                                 hedging=False)
    hedged, s_hedged = h.run(straggler_injector(), slo=HEDGE_SLO,
                             hedging=True)
    hrep = hedged.throughput_report()
    p99_u = float(np.percentile(_latencies(s_unhedged), 99))
    p99_h = float(np.percentile(_latencies(s_hedged), 99))
    ratio = p99_h / p99_u if p99_u > 0 else 1.0
    losses["straggler_unhedged"] = _count(s_unhedged)
    losses["straggler_hedged"] = _count(s_hedged)

    # -- flap storm: staggered outages, >= 1 replica always healthy ------
    fi_flap = h.injector()
    fi_flap.flap_replica(1, 0.05, 0.40)
    fi_flap.flap_replica(2, 0.20, 0.60)
    fi_flap.flap_replica(3, 0.45, 0.90)
    flap, s_flap = h.run(fi_flap)
    frep = flap.throughput_report()
    losses["flap"] = _count(s_flap)

    # -- mid-run link brownout -------------------------------------------
    fi_brown = h.injector()
    fi_brown.inject_brownout(0.2, 1.2, bw_factor=0.3, rtt_factor=3.0)
    brown, s_brown = h.run(fi_brown)
    losses["brownout"] = _count(s_brown)
    plain_mean = float(np.mean(_latencies(s_plain1)))
    brown_mean = float(np.mean(_latencies(s_brown)))

    # -- artifact corruption: detect, re-derive, stay bitwise ------------
    fi_corr = h.injector()
    fi_corr.inject_corruption(0.0, count=corruptions)
    corr, s_corr = h.run(fi_corr, slo=HEDGE_SLO)
    crep = corr.throughput_report()
    detected = corr.store.stats["corruptions_detected"]
    repaired = crep["chaos_corruptions_repaired"]
    corruption_ok = (fi_corr.corruptions_injected == corruptions
                     and detected == corruptions
                     and repaired == corruptions
                     and _results_bitwise(s_plain1, s_corr))
    losses["corruption"] = _count(s_corr)
    wall = time.perf_counter() - t0

    zero_loss = all(v == h.expected for v in losses.values())
    payload = {
        "workload": {"streams": n_streams, "chunks_per_stream": n_chunks,
                     "frames_per_chunk": frames, "replicas": replicas,
                     "shards_k": shards_k, "slo_s": HEDGE_SLO,
                     "straggler_factor": STRAGGLER_FACTOR,
                     "straggler_uids": list(STRAGGLER_UIDS),
                     "corruptions": corruptions,
                     "hedge_bound": hedge_bound},
        "chunks_expected": h.expected,
        "chunks_finalized": losses,
        "chaos_zero_loss": zero_loss,
        "chaos_bit_identical": bit_identical,
        "identity_diff_keys": diff1 + diffK,
        "hedge_p99_ratio": ratio,
        "hedged_p99_s": p99_h,
        "unhedged_p99_s": p99_u,
        "hedges": hrep["chaos_hedges"],
        "hedge_wins": hrep["chaos_hedge_wins"],
        "hedge_busy_s": hrep["chaos_hedge_busy_s"],
        "flap_probes": frep["chaos_probes"],
        "flap_readmits": frep["chaos_readmits"],
        "flap_requeues": frep["chaos_requeues"],
        "corruptions_injected": fi_corr.corruptions_injected,
        "corruptions_detected": detected,
        "corruptions_repaired": repaired,
        "corruption_recovered_all": corruption_ok,
        "brownout_mean_latency_s": brown_mean,
        "plain_mean_latency_s": plain_mean,
        "wall_s": wall,
    }
    rows = [
        {"name": "idle_identity", "us_per_call": "0",
         "bitwise": "ok" if bit_identical else "DIVERGED",
         "diff_keys": len(diff1) + len(diffK)},
        {"name": "straggler_wave", "us_per_call": "0",
         "hedges": hrep["chaos_hedges"], "wins": hrep["chaos_hedge_wins"],
         "p99_hedged_s": f"{p99_h:.3f}", "p99_unhedged_s": f"{p99_u:.3f}",
         "ratio": f"{ratio:.3f}", "bound": f"{hedge_bound:.2f}"},
        {"name": "flap_storm", "us_per_call": "0",
         "probes": frep["chaos_probes"], "readmits": frep["chaos_readmits"],
         "requeues": frep["chaos_requeues"],
         "finalized": losses["flap"]},
        {"name": "brownout", "us_per_call": "0",
         "mean_s": f"{brown_mean:.3f}", "plain_mean_s": f"{plain_mean:.3f}",
         "finalized": losses["brownout"]},
        {"name": "corruption", "us_per_call": "0",
         "injected": fi_corr.corruptions_injected, "detected": detected,
         "repaired": repaired,
         "recovered": "ok" if corruption_ok else "LOST"},
    ]
    return rows, payload


def run(ctx=None, quick: bool = False):
    """benchmarks.run entry point — also emits artifacts/BENCH_chaos.json."""
    rows, payload = (bench(n_streams=16, n_chunks=3, shards_k=2,
                           corruptions=2)
                     if quick else bench())
    write_json(payload, os.path.join(os.path.dirname(__file__), "..",
                                     "artifacts", "BENCH_chaos.json"))
    return rows


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="small fleet, hedge ratio not gated (CI smoke)")
    ap.add_argument("--hedge-bound", type=float, default=0.6,
                    help="hedged p99 must be <= this fraction of unhedged")
    ap.add_argument("--json", default="BENCH_chaos.json")
    args = ap.parse_args()

    if args.quick:
        rows, payload = bench(n_streams=16, n_chunks=3, shards_k=2,
                              corruptions=2, hedge_bound=args.hedge_bound)
    else:
        rows, payload = bench(hedge_bound=args.hedge_bound)
    for row in rows:
        print(",".join(f"{k}={v}" for k, v in row.items()))
    write_json(payload, args.json)
    print(f"# chaos: {payload['chunks_expected']} chunks/scenario — "
          f"hedged p99 {payload['hedged_p99_s']:.3f}s vs unhedged "
          f"{payload['unhedged_p99_s']:.3f}s "
          f"(ratio {payload['hedge_p99_ratio']:.3f}), "
          f"{payload['flap_readmits']} readmits, "
          f"{payload['corruptions_repaired']} corruptions repaired")
    print(f"# wrote {args.json}")

    fails = []
    if not payload["chaos_zero_loss"]:
        lost = {k: v for k, v in payload["chunks_finalized"].items()
                if v != payload["chunks_expected"]}
        fails.append(f"chunk loss under fault injection: {lost} "
                     f"(expected {payload['chunks_expected']})")
    if not payload["chaos_bit_identical"]:
        fails.append("idle-injector run diverged from the plain scheduler: "
                     f"{payload['identity_diff_keys'] or 'results differ'}")
    if not payload["corruption_recovered_all"]:
        fails.append(
            f"corruption not fully recovered: "
            f"injected {payload['corruptions_injected']}, "
            f"detected {payload['corruptions_detected']}, "
            f"repaired {payload['corruptions_repaired']}")
    if payload["flap_readmits"] < 1:
        fails.append("flap storm re-admitted no replicas — health probes "
                     "not engaging")
    if args.quick:
        for f in fails:
            print(f"# FAIL: {f}", file=sys.stderr)
        if fails:
            raise SystemExit(1)
        print("# smoke mode: degradation contract holds, hedge ratio not "
              "gated")
        return
    if payload["hedge_p99_ratio"] > args.hedge_bound:
        fails.append(
            f"hedged p99 only {payload['hedge_p99_ratio']:.3f}x the "
            f"unhedged straggler run (bound {args.hedge_bound:.2f}x) — "
            "hedged dispatch no longer covers the tail")
    for f in fails:
        print(f"# FAIL: {f}", file=sys.stderr)
    if fails:
        raise SystemExit(1)
    print("# PASS: graceful degradation verified across all fault domains")


if __name__ == "__main__":
    main()
