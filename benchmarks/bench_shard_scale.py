"""Shard-scale sweep: 64 -> 1024 concurrent streams at flat per-stream
scheduling overhead through ``ShardedScheduler``.

The steady-state bench showed one ``GraphScheduler`` sustaining 64-256
closed-loop streams; past that the per-flush O(Q) batcher scans and the
single event heap make the *scheduling* cost per chunk grow with the
stream count even though the model work per chunk is constant.  This
harness drives the claim-check ingestion plane + sharded scheduler across
a stream sweep (64 / 256 / 1024 by default, ~64 streams per shard) and
reports the scale story:

  * ``sched_overhead_per_chunk_s`` — wall time spent in the event loop
    minus wall time inside model dispatch, per finalized chunk.  The
    flatness gate: overhead at the top of the sweep must stay within
    ``flat_factor`` (1.3x) of the 64-stream value.  This is an intra-run
    ratio, so it is machine-independent.
  * p50 / p99 / p999 simulated chunk latency per sweep point;
  * claim-check artifact-store physical bytes vs the logical bytes the
    old heap-held-payload design would have retained (dedup + refcount
    eviction savings).

Each point submits the same number of chunks *per stream*, so per-chunk
figures are comparable across the sweep.

Reported and written to ``BENCH_shard.json``; gated in CI by
``scripts/check_bench_regression.py`` (overhead flatness, p99 latency,
store peak bytes).

Usage:
  PYTHONPATH=src python benchmarks/bench_shard_scale.py          # full, gated
  PYTHONPATH=src python benchmarks/bench_shard_scale.py --quick  # CI smoke
  PYTHONPATH=src python -m benchmarks.run --only bench_shard_scale
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
from repro.serving.graph import VideoFunctionGraph
from repro.serving.ingest import ArtifactStore
from repro.serving.shards import ShardedScheduler
from repro.video import synthetic

# same bench-size models as the steady-state bench: scheduling overhead is
# a control-plane property, model-weight-independent
BENCH_DET = DetectorConfig(name="bench-shard-det", image_hw=(32, 32),
                           widths=(8, 16))
BENCH_CLF = ClassifierConfig(name="bench-shard-clf", crop_hw=(16, 16),
                             widths=(8, 16), feature_dim=16)

CROP_BUCKETS = tuple(2 ** k for k in range(2, 14))

STREAMS_PER_SHARD = 64
# groups of co-located cameras publish identical chunks (dense deployments
# see heavy near-duplicate content): the claim-check store dedups the
# group's uploads down to one stored payload per distinct chunk
CONTENT_GROUP = 4


def _chunk_pool(n_streams: int, frames: int, pool: int = 2):
    """One cycled pool per CONTENT_GROUP of streams: the scheduler never
    looks at pixel content, so don't hold a thousand streams of video in
    host memory — and sharing pools across a group exercises the store's
    content-addressed dedup the way co-located feeds do."""
    groups = [[synthetic.make_chunk(
        np.random.default_rng(7000 + 31 * g + j), "traffic",
        num_frames=frames, hw=(32, 32)) for j in range(pool)]
        for g in range((n_streams + CONTENT_GROUP - 1) // CONTENT_GROUP)]
    return [groups[i // CONTENT_GROUP] for i in range(n_streams)]


def _one_point(graph, clf_params, n_streams: int, *, rounds: int,
               frames: int, max_batch_chunks: int, window: float):
    shards = max(1, (n_streams + STREAMS_PER_SHARD - 1) // STREAMS_PER_SHARD)
    store = ArtifactStore(ttl=5.0)
    sched = ShardedScheduler(
        graph, num_shards=shards, store=store,
        batcher_factory=lambda i: CrossStreamBatcher(
            max_chunks=max_batch_chunks, window=window),
        crop_buckets=CROP_BUCKETS,
        # replica pool grows with the fleet (constant per-stream service
        # capacity across the sweep); p2c routing engages at 3+ replicas
        cloud_replicas=shards,
        max_retained_bundles=8)
    pools = _chunk_pool(n_streams, frames)
    states = [sched.add_stream(f"cam{i:04d}", W=clf_params["W"])
              for i in range(n_streams)]

    t0 = time.perf_counter()
    for r in range(rounds):
        for st, pool in zip(states, pools):
            sched.submit(st, pool[r % len(pool)], learn=False)
    sched.run_until_idle()
    wall = time.perf_counter() - t0

    rep = sched.throughput_report()
    mon = sched.monitor
    lat = mon.values("latency")
    srep = rep.get("store", {})
    point = {
        "streams": n_streams,
        "shards": shards,
        "chunks": rounds * n_streams,
        "chunks_finalized": len(lat),
        "wall_s": wall,
        "sched_overhead_per_chunk_s": rep.get("sched_overhead_per_chunk_s",
                                              0.0),
        "sched_events": rep.get("sched_events", 0),
        "steals": rep.get("steals", 0),
        "p50_latency_s": mon.percentile("latency", 50),
        "p99_latency_s": mon.percentile("latency", 99),
        "p999_latency_s": mon.percentile("latency", 99.9),
        "store_bytes_peak": srep.get("bytes_peak", 0),
        "store_logical_bytes_peak": srep.get("logical_bytes_peak", 0),
        "store_dedup_hits": srep.get("dedup_hits", 0),
        "store_evictions": srep.get("evictions", 0),
        "store_bytes_saved_peak": srep.get("bytes_saved_peak", 0),
    }
    # per-point sanity: every submitted chunk must finalize exactly once
    assert point["chunks_finalized"] == point["chunks"], (
        f"{point['chunks_finalized']} finalized != {point['chunks']} "
        f"submitted at {n_streams} streams")
    return point


def bench(streams=(64, 256, 1024), rounds: int = 4, frames: int = 4,
          max_batch_chunks: int = 16, window: float = 0.05,
          flat_factor: float = 1.3):
    det_params = det_mod.init_detector(BENCH_DET, jax.random.PRNGKey(0))
    clf_params = clf_mod.init_classifier(BENCH_CLF, jax.random.PRNGKey(1))
    proto = HighLowProtocol(BENCH_DET, BENCH_CLF)
    graph = VideoFunctionGraph(proto, det_params, clf_params)

    # warm the jit caches on a throwaway point so the first sweep entry
    # doesn't carry compile time in its overhead figure
    _one_point(graph, clf_params, min(streams), rounds=1, frames=frames,
               max_batch_chunks=max_batch_chunks, window=window)

    points = [_one_point(graph, clf_params, n, rounds=rounds, frames=frames,
                         max_batch_chunks=max_batch_chunks, window=window)
              for n in streams]

    base = points[0]["sched_overhead_per_chunk_s"]
    top = points[-1]["sched_overhead_per_chunk_s"]
    ratio = (top / base) if base > 0 else 1.0
    flat = ratio <= flat_factor

    payload = {
        "workload": {"streams": list(streams), "rounds": rounds,
                     "frames_per_chunk": frames,
                     "max_batch_chunks": max_batch_chunks, "window": window,
                     "streams_per_shard": STREAMS_PER_SHARD,
                     "flat_factor": flat_factor},
        "points": points,
        "overhead_base_s": base,
        "overhead_top_s": top,
        "overhead_ratio": ratio,
        "overhead_flat": flat,
        "p99_latency_s": points[-1]["p99_latency_s"],
        "store_bytes_peak": points[-1]["store_bytes_peak"],
        "store_logical_bytes_peak": points[-1]["store_logical_bytes_peak"],
    }
    rows = [{
        "name": f"{p['streams']}streams_{p['shards']}shards",
        "us_per_call": f"{1e6 * p['wall_s']:.0f}",
        "overhead_us_per_chunk":
            f"{1e6 * p['sched_overhead_per_chunk_s']:.1f}",
        "p50_s": f"{p['p50_latency_s']:.3f}",
        "p99_s": f"{p['p99_latency_s']:.3f}",
        "p999_s": f"{p['p999_latency_s']:.3f}",
        "steals": p["steals"],
        "store_mb_peak": f"{p['store_bytes_peak'] / 1e6:.1f}",
        "heap_mb_peak": f"{p['store_logical_bytes_peak'] / 1e6:.1f}",
    } for p in points]
    rows.append({
        "name": "overhead_flatness",
        "us_per_call": "0",
        "ratio": f"{ratio:.2f}",
        "bound": f"{flat_factor:.2f}",
        "flat": "ok" if flat else "GROWING",
    })
    return rows, payload


def run(ctx=None, quick: bool = False):
    """benchmarks.run entry point — also emits artifacts/BENCH_shard.json."""
    rows, payload = bench(streams=(16, 64) if quick else (64, 256, 1024),
                          rounds=2 if quick else 4)
    write_json(payload, os.path.join(os.path.dirname(__file__), "..",
                                     "artifacts", "BENCH_shard.json"))
    return rows


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="small sweep, no flatness gate (CI smoke)")
    ap.add_argument("--rounds", type=int, default=4,
                    help="chunks submitted per stream")
    ap.add_argument("--frames", type=int, default=4)
    ap.add_argument("--batch-chunks", type=int, default=16)
    ap.add_argument("--flat-factor", type=float, default=1.3)
    ap.add_argument("--json", default="BENCH_shard.json")
    args = ap.parse_args()

    if args.quick:
        rows, payload = bench(streams=(16, 64), rounds=2, frames=args.frames,
                              max_batch_chunks=args.batch_chunks,
                              flat_factor=args.flat_factor)
    else:
        rows, payload = bench(streams=(64, 256, 1024), rounds=args.rounds,
                              frames=args.frames,
                              max_batch_chunks=args.batch_chunks,
                              flat_factor=args.flat_factor)
    for row in rows:
        print(",".join(f"{k}={v}" for k, v in row.items()))
    write_json(payload, args.json)
    top = payload["points"][-1]
    print(f"# shard scale: {top['streams']} streams on {top['shards']} "
          f"shards — overhead "
          f"{1e6 * payload['overhead_top_s']:.1f}us/chunk "
          f"({payload['overhead_ratio']:.2f}x the "
          f"{payload['points'][0]['streams']}-stream point), "
          f"p99 {top['p99_latency_s']:.3f}s, store peak "
          f"{top['store_bytes_peak'] / 1e6:.1f} MB vs "
          f"{top['store_logical_bytes_peak'] / 1e6:.1f} MB logical")
    print(f"# wrote {args.json}")
    if args.quick:
        print("# smoke mode: machinery verified, flatness not gated")
        return
    fails = []
    if not payload["overhead_flat"]:
        fails.append(
            f"per-chunk scheduling overhead grew "
            f"{payload['overhead_ratio']:.2f}x from "
            f"{payload['points'][0]['streams']} to {top['streams']} streams "
            f"(bound {args.flat_factor:.2f}x)")
    if payload["store_bytes_peak"] > payload["store_logical_bytes_peak"]:
        fails.append("claim-check store held more bytes than the logical "
                     "heap baseline — dedup/eviction not engaging")
    for f in fails:
        print(f"# FAIL: {f}", file=sys.stderr)
    if fails:
        raise SystemExit(1)
    print(f"# PASS: flat per-stream overhead through {top['streams']} "
          "streams")


if __name__ == "__main__":
    main()
