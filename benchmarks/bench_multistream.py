"""Cross-stream batched inference throughput (the Tangram lever).

Measures frames/sec through the jit'd cloud-detector stage in two modes:

  * sequential — N cameras served one after another, each chunk its own
    detector call (the pre-refactor execution model),
  * concurrent — N cameras through ``MultiStreamCoordinator``: the
    event-driven scheduler packs frames from concurrent chunks into single
    padded detector calls via the cross-stream batcher.

Also asserts single-stream graph execution is numerically identical to the
sequential protocol path (the refactor's safety property).

Both sides run the one served path, so the timed stage is the same fused
``cloud.detect_split`` dispatch plus its one validity-mask read in both
modes.  Only how many chunks one call carries differs, so the ratio still
isolates the cross-stream *batching* lever: the per-call overhead that
packing concurrent chunks amortizes.

Usage:
  PYTHONPATH=src python benchmarks/bench_multistream.py             # full
  PYTHONPATH=src python benchmarks/bench_multistream.py --smoke     # CI
  PYTHONPATH=src python -m benchmarks.run --only bench_multistream
"""
from __future__ import annotations

import argparse
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))
sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import jax
import numpy as np

from repro.configs.vpaas_video import ClassifierConfig, DetectorConfig
from repro.core.coordinator import CloudFogCoordinator, MultiStreamCoordinator
from repro.core.protocol import HighLowProtocol
from repro.models import classifier as clf_mod
from repro.models import detector as det_mod
from repro.serving.autoscaler import Autoscaler
from repro.video import synthetic

# Small models so the per-invocation overhead the batcher amortizes is the
# dominant term — the regime serverless video functions actually live in
# (many cheap invocations, not one giant conv). Throughput of the *stage*
# is weight-independent, so no training is needed.
BENCH_DET = DetectorConfig(name="bench-ms-det", image_hw=(32, 32),
                           widths=(8, 16))
BENCH_CLF = ClassifierConfig(name="bench-ms-clf", crop_hw=(16, 16),
                             widths=(8, 16), feature_dim=16)


def _streams(n_streams: int, chunks: int, frames: int):
    return [[synthetic.make_chunk(np.random.default_rng(1000 + 17 * i + j),
                                  "traffic", num_frames=frames, hw=(32, 32))
             for j in range(chunks)] for i in range(n_streams)]


def _run_sequential(det_params, clf_params, streams):
    """N independent single-stream runs; sums jit'd-detect wall time."""
    stats = {"frames": 0, "wall_s": 0.0, "calls": 0}
    for chunks in streams:
        coord = CloudFogCoordinator(HighLowProtocol(BENCH_DET, BENCH_CLF),
                                    det_params, clf_params)
        coord.run(chunks, learn=False)
        d = coord.scheduler.detect_stats
        stats["frames"] += d["frames"]
        stats["wall_s"] += d["wall_s"]
        stats["calls"] += d["calls"]
    return stats


def _run_concurrent(det_params, clf_params, streams, *, max_batch, window,
                    autoscale: bool):
    scaler = (Autoscaler(min_devices=1, max_devices=8, cooldown_s=0.0)
              if autoscale else None)
    multi = MultiStreamCoordinator(HighLowProtocol(BENCH_DET, BENCH_CLF),
                                   det_params, clf_params, streams,
                                   max_batch_chunks=max_batch,
                                   batch_window=window, autoscaler=scaler)
    multi.run(learn=False)
    rep = multi.report()
    if scaler is not None:
        rep.update({f"scale_{k}": v for k, v in scaler.summary().items()})
    return rep


def _check_single_stream_identity(det_params, clf_params) -> None:
    """Graph path must be numerically identical to the sequential path."""
    chunk = _streams(1, 1, 2)[0][0]
    coord = CloudFogCoordinator(HighLowProtocol(BENCH_DET, BENCH_CLF),
                                det_params, clf_params)
    g = coord.process_chunk(chunk, learn=False)
    s = HighLowProtocol(BENCH_DET, BENCH_CLF).process_chunk(
        det_params, clf_params, chunk.frames)
    assert np.array_equal(g.boxes, s.boxes)
    assert np.array_equal(g.labels, s.labels)
    assert np.array_equal(g.valid, s.valid)
    assert g.wan_bytes == s.wan_bytes and g.coord_bytes == s.coord_bytes
    assert g.latency.total == s.latency.total


def bench(n_streams: int = 8, chunks: int = 4, frames: int = 2,
          window: float = 0.05, autoscale: bool = True):
    det_params = det_mod.init_detector(BENCH_DET, jax.random.PRNGKey(0))
    clf_params = clf_mod.init_classifier(BENCH_CLF, jax.random.PRNGKey(1))

    _check_single_stream_identity(det_params, clf_params)
    streams = _streams(n_streams, chunks, frames)

    # round 1 warms the jit caches for both batch shapes; round 2 measures
    for _ in range(2):
        seq = _run_sequential(det_params, clf_params, streams)
        conc = _run_concurrent(det_params, clf_params, streams,
                               max_batch=n_streams, window=window,
                               autoscale=autoscale)

    seq_fps = seq["frames"] / max(seq["wall_s"], 1e-9)
    conc_fps = conc["frames_per_s"]
    speedup = conc_fps / max(seq_fps, 1e-9)
    rows = [{
        "name": f"{n_streams}streams_x{chunks}chunks_x{frames}f",
        "us_per_call": f"{1e6 * conc['wall_s'] / max(conc['calls'], 1):.0f}",
        "seq_fps": f"{seq_fps:.0f}",
        "conc_fps": f"{conc_fps:.0f}",
        "speedup": f"{speedup:.2f}",
        "seq_calls": seq["calls"],
        "conc_calls": conc["calls"],
        "max_batch_chunks": conc["batch_max_batch_chunks"],
        "padded_frames": conc["padded_frames"],
        "peak_devices": conc.get("scale_peak_devices", 1),
        "single_stream_identity": "ok",
    }]
    return rows, speedup


def run(ctx=None, quick: bool = False):
    """benchmarks.run entry point (trained ctx not needed — see above)."""
    rows, _ = bench(n_streams=4 if quick else 8, chunks=2 if quick else 4)
    return rows


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="tiny run, no throughput threshold (CI)")
    ap.add_argument("--streams", type=int, default=8)
    ap.add_argument("--chunks", type=int, default=4)
    ap.add_argument("--frames", type=int, default=2)
    ap.add_argument("--window", type=float, default=0.05)
    args = ap.parse_args()

    if args.smoke:
        rows, speedup = bench(n_streams=2, chunks=1, frames=2,
                              window=args.window)
    else:
        rows, speedup = bench(n_streams=args.streams, chunks=args.chunks,
                              frames=args.frames, window=args.window)
    for row in rows:
        print(",".join(f"{k}={v}" for k, v in row.items()))
    print(f"# cross-stream batched detect speedup: {speedup:.2f}x")
    if args.smoke:
        print("# smoke mode: machinery + single-stream identity verified")
        return
    if speedup < 2.0:
        print(f"# FAIL: expected >=2x at {args.streams} streams, "
              f"got {speedup:.2f}x", file=sys.stderr)
        raise SystemExit(1)
    print(f"# PASS: >=2x cloud-detector throughput at {args.streams} "
          "concurrent streams")


if __name__ == "__main__":
    main()
