"""The clear split decisions of the check are sound: no deviation of the
detector's outputs within their bounds changes a decision the check calls
clear, and the served-box overlap count is zero on a split and positive
where an NMS pass or the IoU filter is left out."""
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
