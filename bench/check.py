"""The comparison that decides ``correct``: served chunks against the reference.

Grown from the repo's chip smoke check (``Check``, ``compare``) and kept
here so that later changes to the program cannot move it.

The served path runs float32 arrays whose convolutions and matrix products
take one bfloat16 pass on the TPU (the backend's DEFAULT precision); the
reference runs them in float32 at HIGHEST.  Through the detector's
convolutions, or the classifier's and its readout, that rounding compounds
to a few 2**-8 on the logits.  Scores and box coordinates are
sigmoid or softmax outputs in [0, 1] with slope at most 1/4 (softmax 1/2);
fog features are unbounded ReLU outputs, compared relative to the chunk's
largest.

Each stage is held on the same inputs as the served one.  The cloud side
(codec, detector, split) runs on the chunk's HQ frames; the fog side
(crop, classifier, readout) runs at the proposals the program served, since
the scenes' texture has a wavelength of 3-4 pixels and a box that moves by
a rounding's worth of a pixel crops a different pattern; the merge runs on
the served split and fog scores.

Six numbers are compared, each against its own limit, which the
configuration states under ``limits`` with its reason:

  boxes          largest |box coordinate| gap over every region of a frame
  split_errors   accept and proposal decisions of the section IV.B split
                 that differ from the reference's at regions where the
                 reference's decision is clear (below)
  overlap_errors pairs of served regions that the split rules out, by the
                 served boxes: two accepted, or two proposals, at IoU >= 0.45
                 (what each NMS pass removes), a proposal at IoU >= theta_iou
                 with an accepted region or over theta_back of the frame (the
                 filter), a region both accepted and proposed
  fog_scores     largest one-vs-all score gap over the served proposals
  fog_features   largest feature gap there, over the chunk's largest feature
  merge_errors   regions whose served label or validity is not what the
                 merge makes of the served split and fog scores, or
                 whose label differs where both sides accepted the region in
                 the cloud (the reference's top class then leads by at
                 least 2 * theta_cls - 1 = 0.7, which no rounding crosses)

A split decision is clear where no deviation of the detector's outputs
within their rounding bounds can change it: the region's objectness, class
confidence, box area and its overlaps with every region that could
suppress or filter it lie farther from their thresholds than the bounds,
and so does the order of its NMS score against every region it overlaps
(``split_status``).  The bound on each output of each region is
``ROUND_K`` times its gap to the same detector run with bfloat16 operands
(the configuration's one bfloat16 pass), plus ``ROUND_FLOOR``.  With random
weights every frame has regions within rounding of a threshold, and NMS
passes a flip on to its neighbours, so sound runs differ from the
reference at some of the regions they use; those are the unclear ones.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Dict, List

import numpy as np

from bench import reference as ref


def limits(cfg: dict) -> Dict[str, float]:
    """The configuration's limit of each compared number: each between the
    largest reading of sound runs (lower) and the smallest reading of its
    control (upper), an exact comparison where it is 0."""
    return {k: v["limit"] for k, v in cfg["limits"].items()}


@dataclass
class Check:
    """Largest deviations over the compared chunks."""
    dev: Dict[str, float] = field(default_factory=lambda: {
        "boxes": 0.0, "split_errors": 0, "overlap_errors": 0,
        "fog_scores": 0.0, "fog_features": 0.0, "merge_errors": 0})
    chunks: int = 0
    frames: int = 0
    proposals: int = 0                # regions the program sent to the fog
    regions: int = 0                  # regions either side used
    decisions_differ: int = 0         # ... whose thresholded outcome differs
    split_held: int = 0               # clear split decisions compared
    split_held_true: int = 0          # ... of them accepts or proposals
    failures: List[str] = field(default_factory=list)

    def deviation(self, key: str, value: float) -> None:
        self.dev[key] = max(self.dev[key], float(value))

    def finish(self, limits: Dict[str, float]) -> bool:
        """Hold every number to its limit; True when all pass."""
        if not self.chunks or not self.proposals or not self.split_held_true:
            self.failures.append(f"nothing to compare: {self.chunks} chunks, "
                                 f"{self.proposals} fog regions, "
                                 f"{self.split_held_true} clear accepts or "
                                 f"proposals")
        for key, value in self.dev.items():
            if not value <= limits[key]:
                self.failures.append(f"{key} {value:.6g} > limit "
                                     f"{limits[key]:.6g}")
        return not self.failures

    def numbers(self, limits: Dict[str, float]) -> Dict[str, list]:
        """Each compared number beside its limit."""
        return {k: [v, limits[k]] for k, v in self.dev.items()}

    def summary(self) -> str:
        return (f"{self.chunks} chunks, {self.frames} frames, "
                f"{self.proposals} fog regions; {self.split_held} clear split "
                f"decisions held ({self.split_held_true} accepts or "
                f"proposals); thresholded decisions differ at "
                f"{self.decisions_differ} of {self.regions} regions used")


def compare(check: Check, got: Dict[str, np.ndarray],
            want: Dict[str, np.ndarray], rough: Dict[str, np.ndarray],
            forced: Dict[str, np.ndarray], pcfg: dict) -> None:
    """Hold one served chunk (``got``: ChunkResult fields) to the cloud-side
    reference of the chunk (``want``; ``rough`` is the same with bfloat16
    operands, for the rounding bounds) and the fog-side reference at the
    served proposals (``forced``)."""
    check.chunks += 1
    check.frames += got["boxes"].shape[0]
    check.deviation("boxes", np.abs(got["boxes"] - want["boxes"]).max())
    served = got["prop_valid"]
    got_acc = got["source"] == 0
    for f in range(got["boxes"].shape[0]):
        acc, prop = split_status(want, rough, f, pcfg)
        for status, decided in ((acc, got_acc[f]), (prop, served[f])):
            held = status >= 0
            check.split_held += int(held.sum())
            check.split_held_true += int((status == 1).sum())
            check.dev["split_errors"] += int(
                (held & (decided != (status == 1))).sum())
        check.dev["overlap_errors"] += overlaps(
            got["boxes"][f], got_acc[f], served[f], pcfg)
    check.proposals += int(served.sum())
    fs = forced["fog_scores"]
    if served.any():
        check.deviation("fog_scores", np.abs(
            got["fog_scores"][served] - fs[served]).max())
        scale = max(1.0, float(np.abs(forced["fog_features"][served]).max()))
        check.deviation("fog_features", np.abs(
            got["fog_features"][served] - forced["fog_features"][served]
        ).max() / scale)

    # the merge, run on what the program served
    want_acc = want["acc_valid"]
    own = got["fog_scores"]
    fog_region = served & ~got_acc
    merged_valid = got_acc | (fog_region & (own.max(-1) >= pcfg["fog_min_conf"]))
    wrong = got["valid"] != merged_valid
    wrong |= fog_region & (got["labels"] != own.argmax(-1))
    wrong |= got_acc & want_acc & (got["labels"] != want["acc_labels"])
    check.dev["merge_errors"] += int(wrong.sum())

    ref_keep = want["acc_valid"] | (served & ~want_acc
                                    & (fs.max(-1) >= pcfg["fog_min_conf"]))
    differ = ((served != want["prop_valid"]) | (got_acc != want_acc)
              | (got["valid"] != ref_keep))
    used = served | want["prop_valid"] | got_acc | want_acc | got["valid"]
    check.decisions_differ += int((differ & used).sum())
    check.regions += int(used.sum())


# ---------------------------------------------------------------------------
# Clear split decisions
# ---------------------------------------------------------------------------
ROUND_K = 3.0
ROUND_FLOOR = 1e-3


def _all(*statuses) -> np.ndarray:
    """Status of a conjunction: 0 where any part is 0, 1 where all are 1,
    -1 (could go either way) elsewhere."""
    s = np.stack(statuses)
    return np.where((s == 0).any(0), 0, np.where((s == 1).all(0), 1, -1))


def _threshold(value, bound, theta) -> np.ndarray:
    """Status of ``value >= theta`` when value may move by ``bound``."""
    return np.where(value - bound >= theta, 1,
                    np.where(value + bound >= theta, -1, 0))


def iou_bounds(boxes: np.ndarray, d: np.ndarray):
    """Smallest and largest IoU of every pair of boxes (N, 4) xyxy when
    each coordinate of box i may move by up to ``d[i]``."""
    dd = 2.0 * np.maximum(d[:, None], d[None, :])
    iw = (np.minimum(boxes[:, None, 2], boxes[None, :, 2])
          - np.maximum(boxes[:, None, 0], boxes[None, :, 0]))
    ih = (np.minimum(boxes[:, None, 3], boxes[None, :, 3])
          - np.maximum(boxes[:, None, 1], boxes[None, :, 1]))
    i_lo = np.maximum(iw - dd, 0.0) * np.maximum(ih - dd, 0.0)
    i_hi = np.maximum(iw + dd, 0.0) * np.maximum(ih + dd, 0.0)
    a_lo, a_hi = area_bounds(boxes, d)
    lo = i_lo / np.maximum(a_hi[:, None] + a_hi[None, :] - i_lo, 1e-9)
    hi = i_hi / np.maximum(a_lo[:, None] + a_lo[None, :] - i_hi, 1e-9)
    return lo, np.minimum(hi, 1.0)


def area_bounds(boxes: np.ndarray, d: np.ndarray):
    w, h = boxes[:, 2] - boxes[:, 0], boxes[:, 3] - boxes[:, 1]
    return (np.maximum(w - 2 * d, 0.0) * np.maximum(h - 2 * d, 0.0),
            np.maximum(w + 2 * d, 0.0) * np.maximum(h + 2 * d, 0.0))


def nms_status(cand, score, dscore, iou_lo, iou_hi) -> np.ndarray:
    """Greedy NMS (a candidate is kept unless a kept region that ranks
    above it overlaps it at IoU >= NMS_IOU) over candidates whose status,
    score and overlaps may each be off by their bounds: 1 where every
    such deviation keeps the region, 0 where none does, -1 elsewhere."""
    overlap = iou_lo >= ref.NMS_IOU          # [j, i], certainly
    may_overlap = iou_hi >= ref.NMS_IOU
    np.fill_diagonal(overlap, False)
    np.fill_diagonal(may_overlap, False)
    above = (score - dscore)[:, None] > (score + dscore)[None, :]
    may_be_above = (score + dscore)[:, None] >= (score - dscore)[None, :]
    kept = np.where(cand == 0, 0, -1)        # -1: not known (yet)
    for i in np.argsort(-score, kind="stable"):
        if cand[i] == 0:
            continue
        if np.any((kept == 1) & overlap[:, i] & above[:, i]):
            kept[i] = 0
        elif cand[i] == 1 and not np.any(
                (kept != 0) & may_overlap[:, i] & may_be_above[:, i]):
            kept[i] = 1
    return kept


def split_status(want: Dict[str, np.ndarray], rough: Dict[str, np.ndarray],
                 f: int, pcfg: dict):
    """Accept and proposal status (1, 0, or -1 for unclear) of every region
    of frame ``f`` under the section IV.B split, from the reference's
    detector outputs (``want``) with each output's rounding bound taken
    from its gap to the bfloat16 run (``rough``)."""
    def bound(key, reduce=None):
        gap = np.abs(np.asarray(want[key][f], np.float64)
                     - np.asarray(rough[key][f], np.float64))
        if reduce is not None:
            gap = reduce(gap)
        return ROUND_K * gap + ROUND_FLOOR

    boxes = np.asarray(want["boxes"][f], np.float64)
    loc = np.asarray(want["loc_scores"][f], np.float64)
    conf = np.asarray(want["cls_probs"][f], np.float64).max(-1)
    d_box = bound("boxes", lambda g: g.max(-1))
    d_loc = bound("loc_scores")
    d_conf = bound("cls_probs", lambda g: g.max(-1))
    iou_lo, iou_hi = iou_bounds(boxes, d_box)
    loc_ok = _threshold(loc, d_loc, pcfg["theta_loc"])
    acc = nms_status(_all(loc_ok, _threshold(conf, d_conf, pcfg["theta_cls"])),
                     loc * conf, d_loc * conf + d_conf * loc + d_loc * d_conf,
                     iou_lo, iou_hi)
    # the filter: overlap with an accepted region, background area
    others = ~np.eye(len(acc), dtype=bool)
    filtered = np.any((acc[:, None] == 1) & others
                      & (iou_lo >= pcfg["theta_iou"]), 0)
    may_filter = np.any((acc[:, None] != 0) & others
                        & (iou_hi >= pcfg["theta_iou"]), 0)
    a_lo, a_hi = area_bounds(boxes, d_box)
    keep = _all(loc_ok,
                np.where(filtered, 0, np.where(may_filter, -1, 1)),
                np.where(a_hi <= pcfg["theta_back"], 1,
                         np.where(a_lo <= pcfg["theta_back"], -1, 0)),
                np.where(acc == -1, -1, 1 - acc))
    return acc, nms_status(keep, loc, d_loc, iou_lo, iou_hi)


def overlaps(boxes, acc, prop, pcfg: dict, tol: float = 1e-5) -> int:
    """Pairs of one frame's served regions that the split rules out, by
    the served boxes; ``tol`` allows for the program's own float32 IoU."""
    boxes = np.asarray(boxes, np.float64)
    iou = ref.iou(boxes, boxes)
    upper = np.triu(np.ones(iou.shape, bool), 1)
    n = 0
    for kept in (acc, prop):
        n += int((upper & np.outer(kept, kept)
                  & (iou >= ref.NMS_IOU + tol)).sum())
    n += int((np.outer(prop, acc) & ~np.eye(len(acc), dtype=bool)
              & (iou >= pcfg["theta_iou"] + tol)).sum())
    area = (boxes[:, 2] - boxes[:, 0]) * (boxes[:, 3] - boxes[:, 1])
    n += int((prop & (area > pcfg["theta_back"] + tol)).sum())
    return n + int((prop & acc).sum())


def hold(check: Check, cfg: dict, det_params, clf_params, W, chunks,
         served) -> None:
    """Compare served results (dicts of ChunkResult fields) of the HQ
    ``chunks`` with the float32 reference, stage by stage."""
    want = ref.detect(cfg, det_params, chunks)
    rough = ref.detect(cfg, det_params, chunks, precision="bf16")
    forced = ref.fog(cfg, clf_params, W, chunks,
                     [g["prop_boxes"] for g in served],
                     [g["prop_valid"] for g in served])
    for g, w, r, f in zip(served, want, rough, forced):
        compare(check, g, w, r, f, cfg["protocol"])
