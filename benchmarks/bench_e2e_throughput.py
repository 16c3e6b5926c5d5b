"""End-to-end device-residency counters of the served hot path.

One multi-stream workload runs through the event-driven scheduler's one
hot path: device-side packing, one fused ``cloud.detect_split`` dispatch
and ONE blocking host read per flush, one compacted bucketed cross-stream
``fog.classify_batched`` dispatch, results drained as device futures at
finalize.

Reported (and written to ``BENCH_e2e.json``): host syncs per flush, the
share of full-grid classify FLOPs the compaction avoided, readout uploads
and the in-flight future depth.  These are counts, the same on any
backend; no wall-clock figure is reported, because a CPU time says nothing
about the chip (the chip benchmark lives under ``bench/``).  The gate is
one host sync per flush.

Usage:
  PYTHONPATH=src python benchmarks/bench_e2e_throughput.py            # gate
  PYTHONPATH=src python benchmarks/bench_e2e_throughput.py --quick    # CI
  PYTHONPATH=src python -m benchmarks.run --only bench_e2e_throughput
"""
from __future__ import annotations

import argparse
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))
sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import jax
import numpy as np

from benchmarks.common import write_json
from repro.configs.vpaas_video import ClassifierConfig, DetectorConfig
from repro.core.coordinator import MultiStreamCoordinator
from repro.core.protocol import HighLowProtocol
from repro.models import classifier as clf_mod
from repro.models import detector as det_mod
from repro.video import synthetic

# Small models: the counters depend on the dataflow, not on model size or
# weights, so untrained params are fine.
BENCH_DET = DetectorConfig(name="bench-e2e-det", image_hw=(32, 32),
                           widths=(8, 16))
BENCH_CLF = ClassifierConfig(name="bench-e2e-clf", crop_hw=(16, 16),
                             widths=(8, 16), feature_dim=16)


def _streams(n_streams: int, chunks: int, frames: int):
    return [[synthetic.make_chunk(np.random.default_rng(4000 + 31 * i + j),
                                  "traffic", num_frames=frames, hw=(32, 32))
             for j in range(chunks)] for i in range(n_streams)]


def bench(n_streams: int = 8, chunks: int = 4, frames: int = 2,
          window: float = 0.05):
    det_params = det_mod.init_detector(BENCH_DET, jax.random.PRNGKey(0))
    clf_params = clf_mod.init_classifier(BENCH_CLF, jax.random.PRNGKey(1))
    streams = _streams(n_streams, chunks, frames)
    multi = MultiStreamCoordinator(HighLowProtocol(BENCH_DET, BENCH_CLF),
                                   det_params, clf_params, streams,
                                   max_batch_chunks=len(streams),
                                   batch_window=window)
    multi.run(learn=False)
    rep = multi.report()
    payload = {
        "workload": {"streams": n_streams, "chunks_per_stream": chunks,
                     "frames_per_chunk": frames, "window": window,
                     "total_frames": n_streams * chunks * frames},
        "host_syncs_per_flush_fused": rep.get("host_syncs_per_flush", 0.0),
        "classify_flops_saved_frac": rep.get("classify_flops_saved_frac",
                                             0.0),
        "inflight_peak": rep.get("hot_inflight_peak", 0),
        "w_uploads_fused": rep.get("w_uploads", 0),
    }
    rows = [{
        "name": f"{n_streams}streams_x{chunks}chunks_x{frames}f",
        "flushes": rep["hot_flushes"],
        "syncs_per_flush": f"{payload['host_syncs_per_flush_fused']:.1f}",
        "flops_saved": f"{payload['classify_flops_saved_frac']:.2f}",
        "inflight_peak": payload["inflight_peak"],
        "w_uploads": payload["w_uploads_fused"],
    }]
    return rows, payload


def run(ctx=None, quick: bool = False):
    """benchmarks.run entry point — also emits artifacts/BENCH_e2e.json."""
    rows, payload = bench(n_streams=4 if quick else 8,
                          chunks=2 if quick else 4)
    write_json(payload, os.path.join(os.path.dirname(__file__), "..",
                                     "artifacts", "BENCH_e2e.json"))
    return rows


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="small run (CI smoke)")
    ap.add_argument("--streams", type=int, default=8)
    ap.add_argument("--chunks", type=int, default=4)
    ap.add_argument("--frames", type=int, default=2)
    ap.add_argument("--window", type=float, default=0.05)
    ap.add_argument("--json", default="BENCH_e2e.json",
                    help="write machine-readable results here")
    args = ap.parse_args()

    if args.quick:
        rows, payload = bench(n_streams=4, chunks=2, frames=args.frames,
                              window=args.window)
    else:
        rows, payload = bench(n_streams=args.streams, chunks=args.chunks,
                              frames=args.frames, window=args.window)
    for row in rows:
        print(",".join(f"{k}={v}" for k, v in row.items()))
    write_json(payload, args.json)
    print(f"# device-resident hot path: host syncs/flush "
          f"{payload['host_syncs_per_flush_fused']:.1f}; classify FLOPs "
          f"saved {payload['classify_flops_saved_frac']:.0%}")
    print(f"# wrote {args.json}")
    if payload["host_syncs_per_flush_fused"] > 1.0 + 1e-9:
        print("# FAIL: the hot path must hold ONE host sync per flush, got "
              f"{payload['host_syncs_per_flush_fused']:.2f}",
              file=sys.stderr)
        raise SystemExit(1)
    print("# PASS: one host sync per flush")


if __name__ == "__main__":
    main()
