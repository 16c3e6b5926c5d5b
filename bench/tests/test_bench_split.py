"""The clear split decisions of the check are sound: no deviation of the
detector's outputs within their bounds changes a decision the check calls
clear, also where the program serves regions the reference did not select
or lacks a bound for, and the served-box overlap count is zero on a split
and positive where an NMS pass or the IoU filter is left out.  So are the
clear memberships of a selection: no deviation of the candidates' scores
and boxes within their bounds, and no candidate the record left out,
changes a membership the check calls clear."""
from __future__ import annotations

import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))
sys.path.insert(0, ROOT)

from bench import check, reference  # noqa: E402

PCFG = {"theta_cls": 0.85, "theta_loc": 0.5, "theta_iou": 0.3,
        "theta_back": 0.5}


def _frame(rng, n=64, grid=8):
    """One frame's detector outputs: a box per grid cell, as the detector
    makes them, with objectness and class confidence spread over their
    thresholds."""
    gy, gx = np.divmod(np.arange(n), grid)
    cx = (gx + rng.uniform(size=n)) / grid
    cy = (gy + rng.uniform(size=n)) / grid
    side = rng.uniform(0.08, 0.3, size=(n, 2))
    boxes = np.clip(np.stack([cx - side[:, 0] / 2, cy - side[:, 1] / 2,
                              cx + side[:, 0] / 2, cy + side[:, 1] / 2], -1),
                    0.0, 1.0)
    loc = rng.uniform(0.3, 0.7, size=n)
    logits = rng.normal(size=(n, 8)) * rng.uniform(0.5, 4.0, size=(n, 1))
    probs = np.exp(logits) / np.exp(logits).sum(-1, keepdims=True)
    return {"boxes": boxes[None], "loc_scores": loc[None],
            "cls_probs": probs[None]}


def _moved(rng, frame, scale):
    return {k: v + rng.uniform(-scale, scale, size=v.shape)
            for k, v in frame.items()}


def _split(frame):
    out = reference.split(frame["boxes"][0], frame["loc_scores"][0],
                          frame["cls_probs"][0], PCFG)
    return out["acc_valid"], out["prop_valid"]


@pytest.mark.parametrize("seed", [1, 2, 3, 2**31 + 7])
def test_clear_decisions_survive_every_deviation_within_the_bounds(seed):
    rng = np.random.default_rng(seed)
    held = 0
    for _ in range(6):
        want = _frame(rng)
        rough = _moved(rng, want, 0.01)
        acc, prop = check.split_status(want, rough, 0, PCFG)
        held += int((acc == 1).sum() + (prop == 1).sum())
        for _ in range(20):
            # every output within its bound: ROUND_K x the rough gap + floor
            moved = {}
            for k, v in want.items():
                gap = np.abs(v - rough[k])
                if k == "boxes":
                    gap = gap.max(-1, keepdims=True)
                if k == "cls_probs":
                    gap = gap.max(-1, keepdims=True)
                bound = check.ROUND_K * gap + check.ROUND_FLOOR
                moved[k] = v + bound * rng.uniform(-1, 1, size=v.shape)
            got_acc, got_prop = _split(moved)
            assert np.all(got_acc[acc >= 0] == (acc[acc >= 0] == 1))
            assert np.all(got_prop[prop >= 0] == (prop[prop >= 0] == 1))
    assert held > 0


def _within(rng, value, bound):
    return value + bound * rng.uniform(-1, 1, size=np.shape(value))


@pytest.mark.parametrize("seed", [1, 2, 2**31 + 7])
def test_clear_decisions_hold_over_the_programs_own_regions(seed):
    """Slots that are the program's: some empty, some holding regions the
    reference did not select (anything in [0, 1] for objectness and class
    confidence, the served box, close to another region's, within the
    frame's largest box bound), some that the bfloat16 run lacks (within
    the frame's largest bounds)."""
    rng = np.random.default_rng(seed)
    held = 0
    for _ in range(6):
        want = _frame(rng)
        rough = _moved(rng, want, 0.01)
        n = want["loc_scores"].shape[1]
        kind = rng.choice(4, size=n, p=[0.7, 0.1, 0.1, 0.1])
        present, known = kind != 1, (kind != 1) & (kind != 2)
        bounded = known & (kind != 3)
        ghost = np.flatnonzero(kind == 2)
        want["boxes"][0, ghost] = np.clip(want["boxes"][0, rng.choice(
            np.flatnonzero(kind == 0), len(ghost))] + rng.uniform(
            -0.01, 0.01, (len(ghost), 4)), 0.0, 1.0)
        acc, prop = check.split_status(want, rough, 0, PCFG, present=present,
                                       known=known, bounded=bounded)
        assert np.all(acc[~present] == 0) and np.all(prop[~present] == 0)
        assert np.all(acc[present & ~bounded] == -1)
        held += int((acc == 1).sum() + (prop == 1).sum())

        def bound(k, reduce):
            gap = reduce(np.abs(want[k] - rough[k])[0])
            d = check.ROUND_K * gap + check.ROUND_FLOOR
            return np.where(bounded, d, d[bounded & present].max())

        d_box = bound("boxes", lambda g: g.max(-1))
        d_loc = bound("loc_scores", lambda g: g)
        d_cls = bound("cls_probs", lambda g: g.max(-1))
        for _ in range(20):
            boxes = _within(rng, want["boxes"][0], d_box[:, None])
            loc = np.where(known, _within(rng, want["loc_scores"][0], d_loc),
                           rng.uniform(0, 1, n))
            logits = rng.normal(size=(n, 8)) * rng.uniform(0.5, 6.0, (n, 1))
            other = np.exp(logits) / np.exp(logits).sum(-1, keepdims=True)
            probs = np.where(known[:, None], _within(
                rng, want["cls_probs"][0], d_cls[:, None]), other)
            got = reference.split(boxes[present], loc[present],
                                  probs[present], PCFG)
            for status, mask in ((acc, got["acc_valid"]),
                                 (prop, got["prop_valid"])):
                st = status[present]
                assert np.all(mask[st >= 0] == (st[st >= 0] == 1))
    assert held > 0


def _record(rng, groups=2, per_group=64, grid=8):
    """One frame's selection record: two anchors a location, the second
    the first shrunk about its centre to an IoU of 0.56-0.9, so that NMS
    decisions at 0.7 lie close; scores packed closely enough that rounding
    moves the cuts."""
    ids, scores, boxes, group = [], [], [], []
    n = per_group // 2
    for g in range(groups):
        gy, gx = np.divmod(np.arange(n), grid)
        cx = (gx + rng.uniform(size=n)) / grid
        cy = (gy + rng.uniform(size=n)) / (n // grid)
        side = rng.uniform(0.1, 0.3, size=(n, 2)) * (g + 1) / 2
        shrink = rng.uniform(0.75, 0.95, size=(n, 1))
        for half in (side, side * shrink):
            boxes.append(np.stack([cx - half[:, 0], cy - half[:, 1],
                                   cx + half[:, 0], cy + half[:, 1]], -1))
        scores.append(rng.normal(size=per_group))
        ids.append(1000 * g + np.arange(per_group))
        group.append(np.full(per_group, g))
    return {"ids": np.concatenate(ids)[None], "scores":
            np.concatenate(scores)[None], "boxes": np.concatenate(boxes)[None],
            "group": np.concatenate(group)[None], "pre_k": 24,
            "nms_iou": 0.7, "post_k": 12}


def _selected(ids, scores, boxes, group, rule) -> set:
    """The selection: top pre_k a group, greedy NMS within it, top post_k."""
    surv = []
    for g in np.unique(group):
        m = np.flatnonzero(group == g)
        order = m[np.argsort(-scores[m], kind="stable")][:rule["pre_k"]]
        over = reference.iou(boxes[order], boxes[order]) >= rule["nms_iou"]
        alive = np.ones(len(order), bool)
        for i in range(len(order)):
            if alive[i]:
                surv.append(order[i])
                alive[i + 1:] &= ~over[i, i + 1:]
    surv = np.array(surv)
    top = surv[np.argsort(-scores[surv], kind="stable")][:rule["post_k"]]
    return set(ids[top].tolist())


@pytest.mark.parametrize("seed", [1, 2, 3, 2**31 + 7])
@pytest.mark.parametrize("cut", [0, 40, 6], ids=["whole", "cut40", "cut6"])
@pytest.mark.parametrize("noise", [(0.02, 0.003), (0.08, 0.015)],
                         ids=["fine", "coarse"])
def test_clear_memberships_survive_every_deviation_within_the_bounds(
        seed, cut, noise):
    rng = np.random.default_rng(seed)
    held = 0
    for _ in range(4):
        want = _record(rng)
        rough = dict(want, scores=want["scores"] + rng.uniform(
            -noise[0], noise[0], want["scores"].shape), boxes=want["boxes"]
            + rng.uniform(-noise[1], noise[1], want["boxes"].shape))
        # the bfloat16 record lacks a few candidates
        rough["ids"] = np.where(rng.uniform(size=rough["ids"].shape) < 0.05,
                                -1, rough["ids"])
        if cut:
            # each group's record holds its top ``cut``: the rest lie under
            # the cut
            keep, cuts = [], []
            for g in range(2):
                m = np.flatnonzero(want["group"][0] == g)
                order = m[np.argsort(-want["scores"][0, m])]
                keep.append(order[:cut])
                cuts.append(want["scores"][0, order[cut]])
            keep = np.concatenate(keep)
            full = want
            want = dict(want, cut=np.array([cuts]), **{
                k: want[k][:, keep] for k in ("ids", "scores", "boxes",
                                              "group")})
        ids, status, left_out = check.selection_status(want, rough, 0)
        held += int((status >= 0).sum())
        r = check.find(rough["ids"][0], ids)
        gap_s = np.abs(want["scores"][0] - rough["scores"][0][r])
        gap_b = np.abs(want["boxes"][0] - rough["boxes"][0][r]).max(-1)
        d_s = check.ROUND_K * gap_s + check.ROUND_FLOOR
        d_b = check.ROUND_K * gap_b + check.ROUND_FLOOR
        for g in range(2):
            m = (want["group"][0] == g)
            d_s[m & (r < 0)] = d_s[m & (r >= 0)].max()
            d_b[m & (r < 0)] = d_b[m & (r >= 0)].max()
        for _ in range(30):
            s = _within(rng, want["scores"][0], d_s)
            b = _within(rng, want["boxes"][0], d_b[:, None])
            grp, cid = want["group"][0], ids
            if cut:
                # what was cut: anywhere under the cut plus the group's
                # largest bound, any box
                rest = np.setdiff1d(full["ids"][0], ids)
                at = check.find(full["ids"][0], rest)
                top = np.array([want["cut"][0, g] + d_s[want["group"][0] == g
                                                        ].max()
                                for g in full["group"][0, at]])
                s = np.concatenate([s, top - rng.exponential(0.05, len(at))])
                b = np.concatenate([b, full["boxes"][0, at]
                                    + rng.uniform(-0.1, 0.1, (len(at), 4))])
                grp = np.concatenate([grp, full["group"][0, at]])
                cid = np.concatenate([cid, rest])
            chosen = _selected(cid, s, b, grp, want)
            assert all(i in chosen for i in ids[status == 1])
            assert not any(i in chosen for i in ids[status == 0])
            if left_out == 0:
                assert chosen <= set(ids.tolist())
    assert held > 0


def test_overlaps_count_what_the_split_rules_out():
    rng = np.random.default_rng(5)
    frame = _frame(rng)
    boxes = frame["boxes"][0]
    acc, prop = _split(frame)
    assert acc.any() and prop.any()
    assert check.overlaps(boxes, acc, prop, PCFG) == 0
    # a copy of an accepted box, moved by a hair, kept by each pass
    a, p = int(np.argmax(acc)), int(np.argmax(prop))
    twin = np.concatenate([boxes, boxes[[a, p]] + 1e-3])
    acc2, prop2 = (np.append(m, [False, False]) for m in (acc, prop))
    both = acc2.copy()
    both[-2] = True                                  # accept NMS left out
    assert check.overlaps(twin, both, prop2, PCFG) == 1
    both = prop2.copy()
    both[-1] = True                                  # proposal NMS left out
    assert check.overlaps(twin, acc2, both, PCFG) == 1
    both = prop2.copy()
    both[-2] = True                                  # IoU filter left out
    assert check.overlaps(twin, acc2, both, PCFG) == 1
    big = np.concatenate([boxes, [[0.0, 0.0, 0.9, 0.9]]])
    both = np.append(prop, True)                     # background filter
    assert check.overlaps(big, np.append(acc, False), both, PCFG) >= 1
