#!/usr/bin/env python3
"""Chip smoke: the video serving path, end to end, on one TPU.

Runs the main path once at the full width of the repo's video models --
``DETECTOR`` (128x128 input, widths 48/96/192) and ``CLASSIFIER`` (40x40
crops, widths 16/32/64, d=128, 8 classes) -- with random weights made from fixed
seeds and synthetic traffic made from a seed.  It never reads
``artifacts/``.

  phase 1  16 streams x 4 chunks x 8 frames through MultiStreamCoordinator
           (cloud_replicas=2, a chunk-latency SLO that makes
           deadline-driven multi-request flushes; every flush crops its
           regions with the Pallas crop_gather kernel, which the crop
           step picks on a TPU whatever ``impl`` says).  Halfway through,
           one GraphScheduler.hot_swap installs a readout that
           incremental.batch_update computed on the chip from the labelled
           fog features served so far.  Every finalized chunk is checked
           against the sequential reference HighLowProtocol.process_chunk
           (impl="ref", matmul precision "highest", the readout that served
           the chunk).
  phase 2  the same run with ProtocolConfig(impl="pallas") -- the Pallas
           region-filter kernel on the served path too -- checked against
           phase 1.

Usage:  python chip_smoke.py

Exits non-zero and prints no result line when JAX finds no TPU, or when any
phase fails.  The last line of a passing run is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": 1}}``.
"""
from __future__ import annotations

import json
import os
import sys
import time
import warnings
from dataclasses import dataclass, field
from typing import Dict, List

import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))

SEED = 0
STREAMS, CHUNKS, FRAMES = 16, 4, 8
SLO_S = 0.5                  # simulated per-chunk latency target
MAX_BATCH_CHUNKS = 8         # a flush packs up to 8 chunks = 64 frames
KERNEL_IMPL = "pallas"       # phase 2's ProtocolConfig.impl
LABEL_IOU = 0.1              # annotator match threshold (random weights)

# Tolerance.  The served path runs f32 convolutions and matmuls at the
# TPU's DEFAULT precision: one bf16 pass per product (8-bit significand,
# unit roundoff 2**-8 ~ 3.9e-3); the reference runs them at "highest"
# (f32).  Through the detector's four conv layers, or the classifier's
# three convs and its readout, the rounding compounds to a few 2**-8 on
# the logits.  Scores and box coordinates are sigmoid/softmax outputs in
# [0, 1] whose slope is at most 1/4 (softmax: 1/2), so 2**-5 = 0.03125
# absolute bounds them with room for the compounding.  Fog features are
# unbounded ReLU outputs: the same bound relative to the chunk's largest.
TOL = 2.0 ** -5
# Box coordinates that move by TOL move the IoU of two of the synthetic
# scenes' objects (sides of roughly a third of the frame) by up to about
# 4 * TOL / (1/3) ~ 0.4; an IoU threshold decision is marginal inside
# that band.
IOU_TOL = 12 * TOL
NMS_IOU = 0.45               # the NMS threshold of regions.split_regions


def log(msg: str) -> None:
    print(msg, flush=True)


# ---------------------------------------------------------------------------
# Checks against the reference
# ---------------------------------------------------------------------------
@dataclass
class Check:
    """Largest deviations of one phase and its discrete disagreements."""
    name: str
    dev: Dict[str, float] = field(default_factory=dict)
    frames: int = 0
    marginal_frames: int = 0
    discrete_diffs: int = 0          # regions whose decision differs
    discrete_marginal: int = 0       # ... of which in a marginal place
    failures: List[str] = field(default_factory=list)

    def deviation(self, key: str, value: float) -> None:
        self.dev[key] = max(self.dev.get(key, 0.0), float(value))

    def fail(self, msg: str) -> None:
        if len(self.failures) < 20:
            self.failures.append(msg)

    def summary(self) -> str:
        devs = ", ".join(f"{k} {v:.3g} (tol {TOL:.3g})"
                         for k, v in sorted(self.dev.items()))
        return (f"{self.name}: max deviation {devs}; discrete decisions "
                f"differ at {self.discrete_diffs} regions, "
                f"{self.discrete_marginal} of them where the reference is "
                f"within tolerance of a threshold ({self.marginal_frames}/"
                f"{self.frames} frames have such a region)")


def marginal_places(det: Dict[str, np.ndarray], ref, pcfg):
    """Where a reference decision lies within tolerance of its threshold.

    Returns ``(frame, fog)``: ``frame[f]`` is True when a region of frame f
    that can pass the location test has a score, an area or an IoU within
    tolerance of the threshold it is tested against (through NMS and the
    IoU filter one such region can flip its neighbours' decisions);
    ``fog[f, n]`` is True when the fog classifier's confidence or its
    top-two gap for region n is within tolerance."""
    from repro.core.hitl import iou_np
    loc, probs, boxes = det["loc_scores"], det["cls_probs"], det["boxes"]
    conf = probs.max(-1)
    top2 = np.sort(probs, -1)
    gap = top2[..., -1] - top2[..., -2]
    area = ((boxes[..., 2] - boxes[..., 0]).clip(0)
            * (boxes[..., 3] - boxes[..., 1]).clip(0))
    cand = loc >= pcfg.theta_loc - TOL
    own = np.abs(loc - pcfg.theta_loc) < TOL
    own |= cand & (np.abs(conf - pcfg.theta_cls) < TOL)
    own |= cand & (gap < TOL)
    own |= cand & (np.abs(area - pcfg.theta_back) < TOL)
    frame = own.any(axis=1)
    nms_scores = (loc * conf, loc)
    for f in range(loc.shape[0]):
        idx = np.nonzero(cand[f])[0]
        if len(idx) < 2:
            continue
        iou = iou_np(boxes[f, idx], boxes[f, idx])
        off = ~np.eye(len(idx), dtype=bool)
        near = ((np.abs(iou - pcfg.theta_iou) < IOU_TOL)
                | (np.abs(iou - NMS_IOU) < IOU_TOL)) & off
        overlap = (iou >= NMS_IOU - IOU_TOL) & off
        for s in nms_scores:
            si = s[f, idx]
            near |= overlap & (np.abs(si[:, None] - si[None, :]) < TOL)
        frame[f] |= near.any()
    fs = np.sort(ref.fog_scores, -1)
    fog = ((np.abs(fs[..., -1] - pcfg.fog_min_conf) < TOL)
           | (fs[..., -1] - fs[..., -2] < TOL))
    return frame, fog


def compare(check: Check, got, want, frame_marg, fog_marg) -> None:
    """Hold one served chunk to another result of the same chunk."""
    check.frames += frame_marg.shape[0]
    check.marginal_frames += int(frame_marg.sum())
    check.deviation("boxes", np.abs(got.boxes - want.boxes).max())
    both = got.prop_valid & want.prop_valid
    if both.any():
        check.deviation("fog_scores", np.abs(
            got.fog_scores[both] - want.fog_scores[both]).max())
        scale = max(1.0, float(np.abs(want.fog_features[both]).max()))
        check.deviation("fog_features", np.abs(
            got.fog_features[both] - want.fog_features[both]).max() / scale)
    # discrete decisions: the fog stage's own (its label and acceptance)
    # are marginal per region; the detector's cascade through NMS and the
    # IoU filter, so those are marginal per frame
    det_diff = ((got.prop_valid != want.prop_valid)
                | (got.source != want.source)
                | ((got.valid != want.valid) & ~both)
                | ((got.labels != want.labels) & ~both
                   & got.valid & want.valid))
    fog_diff = both & ((got.valid != want.valid)
                       | ((got.labels != want.labels)
                          & got.valid & want.valid))
    allowed_det = det_diff & frame_marg[:, None]
    allowed_fog = fog_diff & (fog_marg | frame_marg[:, None])
    check.discrete_diffs += int(det_diff.sum() + fog_diff.sum())
    check.discrete_marginal += int(allowed_det.sum() + allowed_fog.sum())
    bad = (det_diff & ~allowed_det) | (fog_diff & ~allowed_fog)
    if bad.any():
        f, n = np.argwhere(bad)[0]
        check.fail(f"{int(bad.sum())} decisions differ away from any "
                   f"threshold (first: frame {f}, region {n})")


def check_deviations(check: Check) -> None:
    for key, value in check.dev.items():
        if not value <= TOL:
            check.fail(f"{key} deviates by {value:.4g} > {TOL:.4g}")


# ---------------------------------------------------------------------------
# One served run
# ---------------------------------------------------------------------------
class CompileClock:
    """Seconds JAX spends in backend compilation while it is installed."""

    def __init__(self):
        self.seconds = 0.0

    def __call__(self, event: str, duration: float, **_) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration


def labelled_features(results):
    """(X, Y) from the served chunks: fog features of the valid proposals
    with the labels an oracle annotator gives them (background skipped).

    The random-weight detector proposes loose boxes around objects, so the
    annotator matches a proposal to the object it overlaps most at IoU
    LABEL_IOU rather than the serving default."""
    from repro.core.hitl import OracleAnnotator
    ann = OracleAnnotator(iou_threshold=LABEL_IOU)
    xs, ys = [], []
    for chunk, res in results:
        for t in range(chunk.frames.shape[0]):
            idx = np.nonzero(res.prop_valid[t])[0]
            if not len(idx):
                continue
            labels = ann.label_regions(res.prop_boxes[t][idx],
                                       chunk.gt_boxes[t], chunk.gt_labels[t])
            for i, lab in zip(idx, labels):
                if lab >= 0:
                    xs.append(res.fog_features[t, i])
                    ys.append(int(lab))
    return np.asarray(xs, np.float32), np.asarray(ys, np.int64)


def serve(pcfg, det_params, clf_params, streams, *, swap_after: int,
          new_W=None):
    """One served run with a readout hot swap after ``swap_after`` chunks.

    Returns the per-stream results, the readout that served each result
    (by ``id``), the swapped-in readout and the scheduler's report."""
    import jax.numpy as jnp

    from repro.configs.vpaas_video import CLASSIFIER, DETECTOR
    from repro.core import incremental
    from repro.core.coordinator import MultiStreamCoordinator, StreamSpec
    from repro.core.protocol import HighLowProtocol
    from repro.serving.graph import STAGE_DETECT_SPLIT_DON

    specs = [StreamSpec(name=f"cam{i}", chunks=chunks, slo=SLO_S)
             for i, chunks in enumerate(streams)]
    multi = MultiStreamCoordinator(
        HighLowProtocol(DETECTOR, CLASSIFIER, pcfg), det_params, clf_params,
        specs, max_batch_chunks=MAX_BATCH_CHUNKS, cloud_replicas=2)
    sched = multi.scheduler
    states = [sched.streams[s.name] for s in specs]
    for spec, st in zip(specs, states):
        for chunk in spec.chunks:
            sched.submit(st, chunk, learn=False)

    def finalized():
        return [(c, r) for st in states for c, r, _ in st.results]

    while len(finalized()) < swap_after and sched.step():
        pass
    old_W = np.asarray(clf_params["W"])
    if new_W is None:
        X, Y = labelled_features(finalized())
        if not len(X):
            raise RuntimeError("no labelled fog features to learn from")
        # the serving learner's rule (IncrementalLearner: Eq. 4 proximal)
        new_W = np.asarray(incremental.batch_update(
            jnp.asarray(old_W), jnp.asarray(X),
            jnp.eye(old_W.shape[1], dtype=jnp.float32)[Y],
            rule="proximal", eta=0.3))
    # results that exist now were classified with the old readout: the
    # finalized ones and the ones still in flight; everything dispatched
    # from here on reads the new one
    served_old = {id(r) for _, r in finalized()}
    served_old |= {id(r) for r in sched._inflight}
    sched.hot_swap(new_W, version=1)
    sched.run_until_idle()
    readout = {id(r): (old_W if id(r) in served_old else new_W)
               for _, r in finalized()}
    donated = sum(1 for rep in sched.router.replicas
                  for rec in rep.executor.records
                  if rec.fn_name == STAGE_DETECT_SPLIT_DON)
    rep = multi.report()
    rep["donated_flushes"] = donated
    rep["swapped_mid_run"] = len(served_old) < len(readout)
    rep["served_old"] = len(served_old)
    return states, readout, new_W, rep


def reference(det_params, clf_params, chunk, W):
    """Sequential reference of one chunk, plus the detector outputs its
    discrete decisions were taken from, at f32 ("highest") precision."""
    import jax
    import jax.numpy as jnp

    from repro.configs.vpaas_video import CLASSIFIER, DETECTOR
    from repro.core import protocol as pm
    pcfg = pm.ProtocolConfig(impl="ref")
    with jax.default_matmul_precision("highest"):
        res = pm.HighLowProtocol(DETECTOR, CLASSIFIER, pcfg).process_chunk(
            det_params, clf_params, chunk.frames, W=W)
        enc = pm.encode_low(pcfg, jnp.asarray(chunk.frames))
        det = pm.detect_regions(DETECTOR, det_params, enc.frames)
    return res, {k: np.asarray(v) for k, v in det.items()}


def classify_holds_kernel(pcfg, det_params, clf_params, chunk) -> bool:
    """Whether the compacted classify stage, compiled for one chunk's
    flush, holds a Mosaic (Pallas TPU) call.  On ``impl="ref"`` the crop
    kernel is the only one the stage can hold."""
    import jax.numpy as jnp

    from repro.configs.vpaas_video import CLASSIFIER, DETECTOR
    from repro.core import protocol as pm
    from repro.core import regions as reg
    frames = jnp.asarray(chunk.frames)
    split = pm.detect_split(DETECTOR, pcfg, det_params,
                            pm.encode_low(pcfg, frames).frames)
    fidx, ridx, _, bucket = reg.compaction_indices(
        np.asarray(split.prop_valid))
    idxs = np.zeros((3, bucket), np.int32)
    idxs[0], idxs[1] = fidx, ridx
    compiled = pm.classify_compacted.lower(
        CLASSIFIER, pcfg, clf_params, jnp.asarray(clf_params["W"])[None],
        frames, split, jnp.asarray(idxs)).compile()
    return "tpu_custom_call" in compiled.as_text()


def peak_bytes(device) -> str:
    stats = device.memory_stats() or {}
    return str(stats.get("peak_bytes_in_use", "not reported"))


def run_phase(name, pcfg, det_params, clf_params, streams, device, *,
              new_W=None):
    import jax
    clock = CompileClock()
    jax.monitoring.register_event_duration_secs_listener(clock)
    t0 = time.perf_counter()
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        states, readout, new_W, rep = serve(
            pcfg, det_params, clf_params, streams,
            swap_after=STREAMS * CHUNKS // 2, new_W=new_W)
    wall = time.perf_counter() - t0
    jax.monitoring.unregister_event_duration_listener(clock)
    unusable = sum("donated buffers were not usable" in str(w.message)
                   for w in caught)
    chunks = sum(len(st.results) for st in states)
    log(f"[{name}] served {chunks} chunks in {wall:.1f} s wall "
        f"({clock.seconds:.1f} s compiling), {rep['calls']} flushes "
        f"(up to {rep['batch_max_batch_chunks']} chunks, "
        f"{rep['batch_deadline_flushes']:.0f} deadline-driven), "
        f"{rep['hot_crops_classified']} crops classified "
        f"({rep['hot_crops_kernel']} by the crop kernel), peak_bytes_in_use "
        f"{peak_bytes(device)}")
    log(f"[{name}] donation: {rep['donated_flushes']} flushes ran "
        f"detect_split_donated, XLA {'could not use' if unusable else 'used'}"
        f" the donated buffer; hot swap after {rep['served_old']} of "
        f"{chunks} chunks (mid-run: {rep['swapped_mid_run']})")
    return states, readout, new_W, rep


def run() -> bool:
    """Both phases; True when every check passed."""
    import jax

    from repro.configs.vpaas_video import CLASSIFIER, DETECTOR
    from repro.core.protocol import ProtocolConfig
    from repro.models import classifier as clf_mod
    from repro.models import detector as det_mod
    from repro.video import synthetic

    device = jax.devices()[0]
    t0 = time.perf_counter()
    det_params = det_mod.init_detector(DETECTOR, jax.random.PRNGKey(SEED))
    clf_params = clf_mod.init_classifier(CLASSIFIER,
                                         jax.random.PRNGKey(SEED + 1))
    streams = [[synthetic.make_chunk(
        np.random.default_rng(SEED + 1000 * i + j), "traffic",
        num_frames=FRAMES) for j in range(CHUNKS)] for i in range(STREAMS)]
    log(f"[setup] weights and {STREAMS}x{CHUNKS}x{FRAMES} frames of "
        f"traffic in {time.perf_counter() - t0:.1f} s wall")

    ok = True
    # phase 1: the served path (impl="ref") vs the sequential reference
    pcfg = ProtocolConfig()
    states, readout, new_W, rep = run_phase(
        "phase 1 serving", pcfg, det_params, clf_params, streams, device)
    check1 = Check("phase 1 vs process_chunk")
    margins = {}
    t0 = time.perf_counter()
    for st in states:
        for chunk, res, _ in st.results:
            want, det = reference(det_params, clf_params, chunk,
                                  readout[id(res)])
            frame_m, fog_m = marginal_places(det, want, pcfg)
            margins[id(chunk)] = (frame_m, fog_m)
            compare(check1, res, want, frame_m, fog_m)
    check_deviations(check1)
    if rep["donated_flushes"] == 0:
        check1.fail("no flush ran detect_split_donated")
    if rep["hot_crops_kernel"] != rep["hot_crops_classified"]:
        check1.fail(f"the crop kernel made {rep['hot_crops_kernel']} of "
                    f"{rep['hot_crops_classified']} crops")
    if not classify_holds_kernel(pcfg, det_params, clf_params,
                                 streams[0][0]):
        check1.fail("the compiled classify stage holds no Mosaic call")
    if not rep["swapped_mid_run"]:
        check1.fail("the hot swap did not land mid-run")
    if np.array_equal(new_W, np.asarray(clf_params["W"])):
        check1.fail("batch_update left the readout unchanged")
    log(f"[phase 1 check] {check1.summary()} "
        f"({time.perf_counter() - t0:.1f} s wall)")
    for msg in check1.failures:
        log(f"[phase 1 check] FAIL {msg}")
    ok &= not check1.failures

    # phase 2: the same run on the Pallas kernels vs phase 1
    states2, readout2, _, _ = run_phase(
        "phase 2 kernels", ProtocolConfig(impl=KERNEL_IMPL), det_params,
        clf_params, streams, device, new_W=new_W)
    check2 = Check("phase 2 vs phase 1")
    first = {id(c): (r, readout[id(r)]) for st in states
             for c, r, _ in st.results}
    for st in states2:
        for chunk, res, _ in st.results:
            want, W = first[id(chunk)]
            if not np.array_equal(readout2[id(res)], W):
                check2.fail("a chunk was served by another readout than "
                            "in phase 1")
            compare(check2, res, want, *margins[id(chunk)])
    check_deviations(check2)
    if check2.frames != check1.frames:
        check2.fail(f"served {check2.frames} frames, phase 1 "
                    f"{check1.frames}")
    log(f"[phase 2 check] {check2.summary()}")
    for msg in check2.failures:
        log(f"[phase 2 check] FAIL {msg}")
    ok &= not check2.failures
    return ok


def main() -> int:
    sys.path.insert(0, os.path.join(ROOT, "src"))
    from repro import compile_cache
    cache_dir = compile_cache.configure()

    import jax
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU found: JAX reports {len(devices)} "
              f"{dev.platform} device(s); this check runs only on a TPU",
              file=sys.stderr)
        return 1
    log(f"[device] {dev.platform} {dev.device_kind} x{len(devices)}, "
        f"compile cache {cache_dir}")
    if not run():
        print("chip_smoke: FAILED", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
