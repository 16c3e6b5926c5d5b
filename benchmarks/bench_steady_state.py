"""Sustained steady-state serving: minutes of simulated traffic at 64-256
closed-loop streams through ``GraphScheduler``.

The e2e throughput bench measures a ~0.13 s burst — enough to compare hot
paths, useless as a scale story.  This harness drives the fused hot path
for >= 60 s of *simulated* traffic (the event clock, paced by the cloud
detector's service model) and reports what a long-running service is
actually judged on:

  * p50 / p99 / p999 chunk latency — tail, not mean;
  * sustained simulated frames/sec over the detect span;
  * ``inflight_peak`` — device futures outstanding at once;
  * peak device-buffer residency (``bundle_bytes_peak``) under the
    scheduler's bounded flush-bundle retention, plus a flatness check:
    with ``max_retained_bundles`` set, residency must plateau instead of
    growing with run length (the lazy-bundle leak this PR closes).

Each stream is closed-loop: chunk k+1 is pulled only when chunk k
finalizes, so the offered load self-paces to the serving capacity and the
measured tail is the *steady-state* tail, not a backlog artifact.

Reported and written to ``BENCH_steady.json``; gated in CI by
``scripts/check_bench_regression.py`` (p99 latency, peak residency,
residency flatness).

Usage:
  PYTHONPATH=src python benchmarks/bench_steady_state.py          # full, gated
  PYTHONPATH=src python benchmarks/bench_steady_state.py --quick  # CI smoke
  PYTHONPATH=src python -m benchmarks.run --only bench_steady_state
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
from repro.serving.batching import CrossStreamBatcher
from repro.serving.graph import GraphScheduler, VideoFunctionGraph
from repro.video import synthetic

# same bench-size models as the e2e bench: steady-state behaviour is a
# scheduler property, weight-independent
BENCH_DET = DetectorConfig(name="bench-steady-det", image_hw=(32, 32),
                           widths=(8, 16))
BENCH_CLF = ClassifierConfig(name="bench-steady-clf", crop_hw=(16, 16),
                             widths=(8, 16), feature_dim=16)

# power-of-two crop buckets all the way up to the largest possible flush
# (max_chunks * frames * 64 proposal slots): long runs see many distinct
# valid-proposal counts, and every exact-size batch above the largest
# bucket would be a fresh jit compile
CROP_BUCKETS = tuple(2 ** k for k in range(2, 14))


def _chunk_pool(n_streams: int, frames: int, pool: int = 4):
    """A small cycled pool per stream: content doesn't matter to the
    scheduler, so don't hold minutes of video in host memory."""
    return [[synthetic.make_chunk(np.random.default_rng(7000 + 31 * i + j),
                                  "traffic", num_frames=frames, hw=(32, 32))
             for j in range(pool)] for i in range(n_streams)]


def bench(n_streams: int = 64, duration_s: float = 60.0, frames: int = 8,
          max_batch_chunks: int = 16, window: float = 0.05,
          max_retained_bundles: int = 8):
    det_params = det_mod.init_detector(BENCH_DET, jax.random.PRNGKey(0))
    clf_params = clf_mod.init_classifier(BENCH_CLF, jax.random.PRNGKey(1))
    proto = HighLowProtocol(BENCH_DET, BENCH_CLF)
    graph = VideoFunctionGraph(proto, det_params, clf_params)
    sched = GraphScheduler(
        graph,
        batcher=CrossStreamBatcher(max_chunks=max_batch_chunks,
                                   window=window),
        crop_buckets=CROP_BUCKETS,
        max_retained_bundles=max_retained_bundles)
    pools = _chunk_pool(n_streams, frames)
    states = [sched.add_stream(f"cam{i:03d}", W=clf_params["W"])
              for i in range(n_streams)]

    # one detect replica serializes flushes, so the simulated span is
    # ~ total_frames / detect_fps; round up to clear the duration target
    per_round = n_streams * frames
    detect_fps = 1.0 / proto.cloud.detect_time(1)
    rounds = max(2, math.ceil(duration_s * detect_fps / per_round) + 1)

    t0 = time.perf_counter()
    for r in range(rounds):
        for st, pool in zip(states, pools):
            sched.submit(st, pool[r % len(pool)], learn=False)
    sched.run_until_idle()
    wall = time.perf_counter() - t0

    rep = sched.throughput_report()
    mon = sched.monitor
    lat = mon.values("latency")

    # residency flatness: with bounded retention the bundle_bytes series
    # must plateau — compare the mean of the run's second half against the
    # first (which includes the fill-up ramp and therefore reads lower)
    resid = mon.values("bundle_bytes")
    half = len(resid) // 2
    ratio = (float(np.mean(resid[half:])) / float(np.mean(resid[:half]))
             if half and np.mean(resid[:half]) > 0 else 1.0)
    flat = ratio <= 1.2

    payload = {
        "workload": {"streams": n_streams, "rounds": rounds,
                     "frames_per_chunk": frames,
                     "max_batch_chunks": max_batch_chunks, "window": window,
                     "max_retained_bundles": max_retained_bundles,
                     "total_chunks": rounds * n_streams,
                     "total_frames": rounds * per_round},
        "sim_duration_s": rep.get("detect_span_s", 0.0),
        "sim_frames_per_s": rep.get("sim_frames_per_s", 0.0),
        "wall_s": wall,
        "wall_frames_per_s": rounds * per_round / wall,
        "chunks_finalized": len(lat),
        "p50_latency_s": mon.percentile("latency", 50),
        "p99_latency_s": mon.percentile("latency", 99),
        "p999_latency_s": mon.percentile("latency", 99.9),
        "inflight_peak": rep.get("hot_inflight_peak", 0),
        "bundle_bytes_peak": rep.get("hot_bundle_bytes_peak", 0),
        "bundle_bytes_final": rep.get("hot_bundle_bytes", 0),
        "bundles_sealed": rep.get("hot_bundles_sealed", 0),
        "bundles_retained_peak": rep.get("hot_bundles_retained_peak", 0),
        "host_syncs_per_flush": rep.get("host_syncs_per_flush", 0.0),
        "classify_flops_saved_frac": rep.get("classify_flops_saved_frac",
                                             0.0),
        "residency_ratio_2nd_half": ratio,
        "residency_flat": flat,
    }
    rows = [{
        "name": f"{n_streams}streams_{payload['sim_duration_s']:.0f}s_sim",
        "us_per_call": f"{1e6 * wall:.0f}",
        "sim_fps": f"{payload['sim_frames_per_s']:.0f}",
        "p50_s": f"{payload['p50_latency_s']:.3f}",
        "p99_s": f"{payload['p99_latency_s']:.3f}",
        "p999_s": f"{payload['p999_latency_s']:.3f}",
        "inflight_peak": payload["inflight_peak"],
        "resident_mb_peak": f"{payload['bundle_bytes_peak'] / 1e6:.1f}",
        "sealed": payload["bundles_sealed"],
        "flat": "ok" if flat else "GROWING",
    }]
    return rows, payload


def run(ctx=None, quick: bool = False):
    """benchmarks.run entry point — also emits artifacts/BENCH_steady.json."""
    rows, payload = bench(n_streams=8 if quick else 64,
                          duration_s=10.0 if quick else 60.0)
    write_json(payload, os.path.join(os.path.dirname(__file__), "..",
                                     "artifacts", "BENCH_steady.json"))
    return rows


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="small run, no duration/streams gate (CI smoke)")
    ap.add_argument("--streams", type=int, default=64)
    ap.add_argument("--duration", type=float, default=60.0,
                    help="minimum simulated seconds of traffic")
    ap.add_argument("--frames", type=int, default=8)
    ap.add_argument("--batch-chunks", type=int, default=16)
    ap.add_argument("--retained-bundles", type=int, default=8)
    ap.add_argument("--json", default="BENCH_steady.json")
    args = ap.parse_args()

    if args.quick:
        rows, payload = bench(n_streams=8, duration_s=10.0,
                              frames=args.frames,
                              max_batch_chunks=args.batch_chunks,
                              max_retained_bundles=args.retained_bundles)
    else:
        rows, payload = bench(n_streams=args.streams,
                              duration_s=args.duration, frames=args.frames,
                              max_batch_chunks=args.batch_chunks,
                              max_retained_bundles=args.retained_bundles)
    for row in rows:
        print(",".join(f"{k}={v}" for k, v in row.items()))
    write_json(payload, args.json)
    print(f"# steady state: {payload['sim_duration_s']:.0f}s simulated at "
          f"{payload['workload']['streams']} streams — "
          f"p50 {payload['p50_latency_s']:.3f}s / "
          f"p99 {payload['p99_latency_s']:.3f}s / "
          f"p999 {payload['p999_latency_s']:.3f}s, "
          f"{payload['sim_frames_per_s']:.0f} sim fps, peak residency "
          f"{payload['bundle_bytes_peak'] / 1e6:.1f} MB "
          f"({payload['bundles_sealed']} bundles sealed)")
    print(f"# wrote {args.json}")
    if args.quick:
        if not payload["residency_flat"]:
            print("# FAIL: device residency grew even in smoke mode",
                  file=sys.stderr)
            raise SystemExit(1)
        print("# smoke mode: machinery + bounded residency verified")
        return
    fails = []
    if payload["sim_duration_s"] < args.duration:
        fails.append(f"simulated span {payload['sim_duration_s']:.1f}s "
                     f"< required {args.duration:.0f}s")
    if not payload["residency_flat"]:
        fails.append("device-buffer residency is not flat "
                     f"(2nd-half/1st-half ratio "
                     f"{payload['residency_ratio_2nd_half']:.2f})")
    if payload["bundles_sealed"] == 0:
        fails.append("retention cap never engaged (bundles_sealed == 0)")
    if payload["host_syncs_per_flush"] > 1.0 + 1e-9:
        fails.append("host syncs per flush "
                     f"{payload['host_syncs_per_flush']:.2f} > 1")
    for f in fails:
        print(f"# FAIL: {f}", file=sys.stderr)
    if fails:
        raise SystemExit(1)
    print(f"# PASS: >={args.duration:.0f}s sustained at {args.streams} "
          "streams with flat device residency")


if __name__ == "__main__":
    main()
