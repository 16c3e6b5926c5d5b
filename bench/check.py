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

Regions are matched by identity before anything is compared.  Each slot of
the family's detector carries a region identity (``ids``; -1 where the slot
is empty; the slot index where the family gives none, as the stand-in's one
region a grid cell), and a served chunk carries the program's own
(``region_ids``; the slot index where it has none).  The reference's and the
bfloat16 run's per-slot outputs are laid onto the served slots by identity
(``lay``).  A served region the reference did not select, and a region the
reference selected that the program did not serve, are not compared; each
counts in ``regions_unmatched``, which has no limit.

Six numbers are compared, and a seventh where the family selects its
regions, each against its own limit, which the configuration states under
``limits`` with its reason:

  boxes          largest |box coordinate| gap over the matched regions
  split_errors   accept and proposal decisions of the section IV.B split
                 that differ from the reference's at matched regions where
                 the reference's decision is clear (below), and decisions
                 to accept or propose an empty slot
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
  selection_errors
                 served regions that the family's selection clearly leaves
                 out, regions it clearly selects that the program did not
                 serve, and regions served twice (``selection_status``);
                 reported only where the family returns a selection record

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
The split is the one the reference's outputs make over the regions the
program serves.  A matched region that the bfloat16 run lacks has no
rounding bound: its own decisions are unclear, and towards its neighbours
it takes the frame's largest bounds.  A served region the reference lacks
has an objectness and class confidence anywhere in [0, 1] and its served
box, so it may suppress or filter every region it may overlap.

A selection membership is clear in the same way (``selection_status``):
each candidate's selection score and box are bounded by their gaps to the
bfloat16 run's record; a top-k cut is clear where the candidate's score
interval lies wholly above or below those of the k-th survivor (fewer than
k others may rank above it, or k others surely do); NMS decisions go
through ``nms_status`` at the record's ``nms_iou``; candidates the record
left out lie at or under its stated cut, plus the group's largest bound.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Dict, List, Optional

import numpy as np

from bench import reference as ref

# the per-slot arrays of ``reference.detect`` that the check lays onto the
# served slots
SLOT_KEYS = ("boxes", "loc_scores", "cls_probs", "acc_valid", "acc_labels",
             "prop_valid")


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
    regions: int = 0                  # matched regions either side used
    decisions_differ: int = 0         # ... whose thresholded outcome differs
    split_held: int = 0               # clear split decisions compared
    split_held_true: int = 0          # ... of them accepts or proposals
    regions_unmatched: int = 0        # served or reference regions alone
    selection_held: int = 0           # clear memberships compared
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
        if "selection_errors" in self.dev and not self.selection_held:
            self.failures.append("nothing to compare: no clear selection "
                                 "membership")
        for key, value in self.dev.items():
            if key not in limits:
                self.failures.append(f"{key} has no limit in the "
                                     f"configuration")
            elif not value <= limits[key]:
                self.failures.append(f"{key} {value:.6g} > limit "
                                     f"{limits[key]:.6g}")
        return not self.failures

    def numbers(self, limits: Dict[str, float]) -> Dict[str, list]:
        """Each compared number beside its limit; where the family selects
        its regions, ``regions_unmatched`` too, with no limit."""
        out = {k: [v, limits.get(k)] for k, v in self.dev.items()}
        if "selection_errors" in self.dev:
            out["regions_unmatched"] = [self.regions_unmatched, None]
        return out

    def summary(self) -> str:
        return (f"{self.chunks} chunks, {self.frames} frames, "
                f"{self.proposals} fog regions; {self.split_held} clear split "
                f"decisions held ({self.split_held_true} accepts or "
                f"proposals); thresholded decisions differ at "
                f"{self.decisions_differ} of {self.regions} regions used; "
                f"{self.regions_unmatched} regions unmatched; "
                f"{self.selection_held} clear selection memberships held")


# ---------------------------------------------------------------------------
# Matching by region identity
# ---------------------------------------------------------------------------
def slot_ids(res: Dict[str, np.ndarray], key: str) -> np.ndarray:
    """Region identities (F, N) of a result's slots: ``res[key]``, or the
    slot index where the result has none."""
    if res.get(key) is not None:
        return np.asarray(res[key])
    f, n = res["boxes"].shape[:2]
    return np.broadcast_to(np.arange(n, dtype=np.int32), (f, n))


def find(have: np.ndarray, ids: np.ndarray) -> np.ndarray:
    """Position in ``have`` (N,) of each identity of ``ids`` (M,), -1 where
    ``have`` lacks it or the identity is -1 (an empty slot)."""
    order = np.argsort(have, kind="stable")
    ranked = have[order]
    if not len(ranked):
        return np.full(len(ids), -1, np.int64)
    pos = np.minimum(np.searchsorted(ranked, ids), len(ranked) - 1)
    return np.where((ranked[pos] == ids) & (ids >= 0), order[pos], -1)


def lay(res: Dict[str, np.ndarray], ids: np.ndarray) -> Dict[str, np.ndarray]:
    """``res``'s per-slot arrays (``SLOT_KEYS``) on the slots whose region
    identities are ``ids`` (F, N), zero where ``res`` lacks the region;
    ``found`` (F, N) marks where it has it."""
    idx = np.stack([find(h, i) for h, i in zip(slot_ids(res, "ids"), ids)])
    found = idx >= 0
    rows = np.arange(idx.shape[0])[:, None]
    out = {"found": found}
    for k in SLOT_KEYS:
        v = np.asarray(res[k])[rows, np.maximum(idx, 0)]
        out[k] = np.where(found.reshape(found.shape + (1,) * (v.ndim - 2)),
                          v, np.zeros((), v.dtype))
    return out


def compare(check: Check, got: Dict[str, np.ndarray],
            want: Dict[str, np.ndarray], rough: Dict[str, np.ndarray],
            forced: Dict[str, np.ndarray], pcfg: dict) -> None:
    """Hold one served chunk (``got``: ChunkResult fields, and the program's
    region identities ``region_ids`` where it has them) to the cloud-side
    reference of the chunk (``want``; ``rough`` is the same with bfloat16
    operands, for the rounding bounds) and the fog-side reference at the
    served proposals (``forced``)."""
    check.chunks += 1
    check.frames += got["boxes"].shape[0]
    ids = slot_ids(got, "region_ids")
    present = ids >= 0
    w, r = lay(want, ids), lay(rough, ids)
    matched = present & w["found"]
    check.regions_unmatched += int((present & ~w["found"]).sum()) + sum(
        int(((h >= 0) & (find(i, h) < 0)).sum())
        for h, i in zip(slot_ids(want, "ids"), ids))
    if matched.any():
        check.deviation("boxes", np.abs(got["boxes"] - w["boxes"])
                        [matched].max())
    # a region the reference lacks is held at its served box
    w["boxes"] = np.where(w["found"][..., None], w["boxes"], got["boxes"])
    served = got["prop_valid"]
    got_acc = got["source"] == 0
    for f in range(got["boxes"].shape[0]):
        acc, prop = split_status(w, r, f, pcfg, present=present[f],
                                 known=w["found"][f], bounded=r["found"][f])
        for status, decided in ((acc, got_acc[f]), (prop, served[f])):
            held = status >= 0
            check.split_held += int(held.sum())
            check.split_held_true += int((status == 1).sum())
            check.dev["split_errors"] += int(
                (held & (decided != (status == 1))).sum())
        check.dev["overlap_errors"] += overlaps(
            got["boxes"][f], got_acc[f], served[f], pcfg)
    if "selection" in want:
        check.dev.setdefault("selection_errors", 0)
        for f in range(ids.shape[0]):
            errors, held = selection_errors(want["selection"],
                                            rough["selection"], f, ids[f])
            check.dev["selection_errors"] += errors
            check.selection_held += held
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
    want_acc = w["acc_valid"]
    own = got["fog_scores"]
    fog_region = served & ~got_acc
    merged_valid = got_acc | (fog_region & (own.max(-1) >= pcfg["fog_min_conf"]))
    wrong = got["valid"] != merged_valid
    wrong |= fog_region & (got["labels"] != own.argmax(-1))
    wrong |= got_acc & want_acc & (got["labels"] != w["acc_labels"])
    check.dev["merge_errors"] += int(wrong.sum())

    ref_keep = want_acc | (served & ~want_acc
                           & (fs.max(-1) >= pcfg["fog_min_conf"]))
    differ = ((served != w["prop_valid"]) | (got_acc != want_acc)
              | (got["valid"] != ref_keep))
    used = matched & (served | w["prop_valid"] | got_acc | want_acc
                      | got["valid"])
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


def nms_status(cand, score, dscore, iou_lo, iou_hi,
               iou: float = ref.NMS_IOU) -> np.ndarray:
    """Greedy NMS (a candidate is kept unless a kept region that ranks
    above it overlaps it at IoU >= ``iou``) over candidates whose status,
    score and overlaps may each be off by their bounds: 1 where every
    such deviation keeps the region, 0 where none does, -1 elsewhere."""
    overlap = iou_lo >= iou                  # [j, i], certainly
    may_overlap = iou_hi >= iou
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


def _widest(d: np.ndarray, bounded: np.ndarray):
    """``d`` where ``bounded``, elsewhere the largest bounded ``d`` (the
    floor where there is none)."""
    top = d[bounded].max(0) if bounded.any() else ROUND_FLOOR
    return np.where(bounded, d, top)


def split_status(want: Dict[str, np.ndarray], rough: Dict[str, np.ndarray],
                 f: int, pcfg: dict, present: Optional[np.ndarray] = None,
                 known: Optional[np.ndarray] = None,
                 bounded: Optional[np.ndarray] = None):
    """Accept and proposal status (1, 0, or -1 for unclear) of every region
    of frame ``f`` under the section IV.B split, from the reference's
    detector outputs (``want``) with each output's rounding bound taken
    from its gap to the bfloat16 run (``rough``).

    Where the slots are the program's: ``present`` marks the slots that hold
    a served region (the others take part in nothing and read 0),
    ``known`` those whose region the reference has (elsewhere ``want``
    holds the served box, and the objectness and class confidence are
    unknown), ``bounded`` those whose region the bfloat16 run has."""
    n = len(want["loc_scores"][f])
    present = np.ones(n, bool) if present is None else present
    known = np.ones(n, bool) if known is None else known
    bounded = np.ones(n, bool) if bounded is None else bounded
    bounded = bounded & known

    def bound(key, reduce=None):
        gap = np.abs(np.asarray(want[key][f], np.float64)
                     - np.asarray(rough[key][f], np.float64))
        if reduce is not None:
            gap = reduce(gap)
        return _widest(ROUND_K * gap + ROUND_FLOOR, bounded & present)

    boxes = np.asarray(want["boxes"][f], np.float64)
    # a served region the reference lacks: objectness and confidence
    # anywhere in [0, 1]
    loc = np.where(known, np.asarray(want["loc_scores"][f], np.float64), 0.5)
    conf = np.where(known, np.asarray(want["cls_probs"][f], np.float64
                                      ).max(-1), 0.5)
    d_box = bound("boxes", lambda g: g.max(-1))
    d_loc = np.where(known, bound("loc_scores"), 1.0)
    d_conf = np.where(known, bound("cls_probs", lambda g: g.max(-1)), 1.0)
    unclear = present & ~bounded

    def own(status):
        return np.where(present, np.where(unclear, -1, status), 0)

    iou_lo, iou_hi = iou_bounds(boxes, d_box)
    loc_ok = _threshold(loc, d_loc, pcfg["theta_loc"])
    acc = own(nms_status(
        own(_all(loc_ok, _threshold(conf, d_conf, pcfg["theta_cls"]))),
        loc * conf, d_loc * conf + d_conf * loc + d_loc * d_conf,
        iou_lo, iou_hi))
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
    return acc, own(nms_status(own(keep), loc, d_loc, iou_lo, iou_hi))


# ---------------------------------------------------------------------------
# Clear selection memberships
# ---------------------------------------------------------------------------
def _counts(q_lo, q_hi, lo: np.ndarray, hi: np.ndarray):
    """For each query interval [q_lo, q_hi]: how many of the intervals
    [lo_j, hi_j] surely lie above it (lo_j > q_hi) and how many may
    (hi_j >= q_lo)."""
    sure = len(lo) - np.searchsorted(np.sort(lo), q_hi, side="right")
    may = len(hi) - np.searchsorted(np.sort(hi), q_lo, side="left")
    return sure, may


def _top_k(status, lo, hi, k, pool, cut_hi):
    """Status of each candidate of ``pool`` (an index) in the top ``k`` of
    the pool by score, given its status before (``status``; 0 takes no
    part) and the highest score ``cut_hi`` that a candidate left out of the
    record may have (-inf where none is left out)."""
    alive = pool[status[pool] != 0]
    ones = alive[status[alive] == 1]
    sure, _ = _counts(lo, hi, lo[ones], hi[ones])
    _, may = _counts(lo, hi, lo[alive], hi[alive])
    may = may - (status != 0)                 # not itself
    may = np.where(cut_hi >= lo, np.inf, may)
    out = np.where(status == 0, 0, np.where(
        sure >= k, 0, np.where((status == 1) & (may < k), 1, -1)))
    return out[pool]


def selection_status(want_sel: Dict, rough_sel: Dict, f: int):
    """Membership of frame ``f``'s candidates in the family's selection:
    top ``pre_k`` a group by score, greedy NMS at ``nms_iou`` within a
    group, top ``post_k`` of the survivors.  Returns the record's ``ids``,
    their status (1 selected, 0 left out whatever the rounding, -1
    unclear), and the status of a candidate the record left out (0 or -1).

    Scores and boxes are bounded by their gaps to the bfloat16 run's record
    (``rough_sel``), matched by identity; a candidate that record lacks is
    unclear itself and takes its group's largest bound towards the others.
    A record cut at ``cut`` (F, G) holds every candidate of group g that
    scores above ``cut[f, g]``."""
    ids = np.asarray(want_sel["ids"][f])
    real = ids >= 0
    s = np.asarray(want_sel["scores"][f], np.float64)
    b = np.asarray(want_sel["boxes"][f], np.float64)
    g = np.asarray(want_sel["group"][f])
    r = find(np.asarray(rough_sel["ids"][f]), ids)
    bounded = real & (r >= 0)
    rs = np.asarray(rough_sel["scores"][f], np.float64)[np.maximum(r, 0)]
    rb = np.asarray(rough_sel["boxes"][f], np.float64)[np.maximum(r, 0)]
    d_s = ROUND_K * np.abs(s - rs) + ROUND_FLOOR
    d_b = ROUND_K * np.abs(b - rb).max(-1) + ROUND_FLOOR
    groups = np.unique(g[real])
    cut = want_sel.get("cut")
    cut_hi = np.full(len(ids), -np.inf)
    cut_top = {}                 # group -> top score of what was cut
    pre = np.where(real, 1, 0)
    for grp in groups:
        in_g = real & (g == grp)
        d_s[in_g] = _widest(d_s[in_g], bounded[in_g])
        d_b[in_g] = _widest(d_b[in_g], bounded[in_g])
        if cut is not None and np.isfinite(cut[f][grp]):
            top = float(cut[f][grp]) + d_s[in_g].max()
            cut_hi[in_g] = top
            cut_top[grp] = top
    lo, hi = s - d_s, s + d_s
    for grp in groups:
        pool = np.flatnonzero(real & (g == grp))
        pre[pool] = _top_k(pre, lo, hi, int(want_sel["pre_k"]), pool,
                           cut_hi)
        if grp in cut_top and (lo[pool] > cut_top[grp]).sum() >= int(
                want_sel["pre_k"]):
            del cut_top[grp]             # nothing cut can pass pre_k
    pre = np.where(real & ~bounded, -1, pre)
    # NMS needs nothing of what was cut: a cut candidate suppresses only
    # candidates it may rank above, and those are unclear in pre_k already
    kept = np.zeros(len(ids), np.int64)
    for grp in groups:
        pool = np.flatnonzero(real & (g == grp))
        kept[pool] = nms_status(pre[pool], s[pool], d_s[pool],
                                *iou_bounds(b[pool], d_b[pool]),
                                iou=float(want_sel["nms_iou"]))
    # what was cut and may pass pre_k may also survive NMS and rank above
    cut_any = max(cut_top.values(), default=-np.inf)
    post_k = int(want_sel["post_k"])
    pool = np.flatnonzero(real)
    status = np.zeros(len(ids), np.int64)
    status[pool] = _top_k(kept, lo, hi, post_k, pool,
                          np.full(len(ids), cut_any))
    status = np.where(real & ~bounded, -1, status)
    ones = pool[kept[pool] == 1]
    sure, _ = _counts(cut_any, cut_any, lo[ones], hi[ones])
    left_out = -1 if cut_top and sure < post_k else 0
    return ids, status, left_out


def selection_errors(want_sel: Dict, rough_sel: Dict, f: int,
                     served: np.ndarray):
    """(errors, clear memberships held) of frame ``f``'s served region
    identities ``served`` (N,) against the family's selection: served
    regions it clearly leaves out, regions it clearly selects that were
    not served, and identities served twice."""
    ids, status, left_out = selection_status(want_sel, rough_sel, f)
    served = served[served >= 0]
    pos = find(ids, served)
    of_served = np.where(pos >= 0, status[np.maximum(pos, 0)], left_out)
    missed = (status == 1) & (find(served, ids) < 0)
    errors = (int((of_served == 0).sum()) + int(missed.sum())
              + len(served) - len(np.unique(served)))
    return errors, int((of_served >= 0).sum() + missed.sum())


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
    """Compare served results (dicts of ChunkResult fields, with
    ``region_ids`` where the program has them) of the HQ ``chunks`` with
    the float32 reference, stage by stage."""
    want = ref.detect(cfg, det_params, chunks)
    rough = ref.detect(cfg, det_params, chunks, precision="bf16")
    forced = ref.fog(cfg, clf_params, W, chunks,
                     [g["prop_boxes"] for g in served],
                     [g["prop_valid"] for g in served])
    for g, w, r, f in zip(served, want, rough, forced):
        compare(check, g, w, r, f, cfg["protocol"])
