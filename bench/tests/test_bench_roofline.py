"""Operation and byte counts of the stand-in family's served kernels, and
the chip's roofline, against hand counts."""
from __future__ import annotations

import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from bench import harness, roofline  # noqa: E402
from bench.models import family  # noqa: E402

STANDIN = family(harness.load_cell("single-backlog")["config"])

TINY_DET = {"image_hw": [8, 8], "in_channels": 3, "widths": [2, 4],
            "num_classes": 3}
TINY_CLF = {"crop_hw": [4, 4], "in_channels": 3, "widths": [2],
            "feature_dim": 5, "num_classes": 3}


def test_detector_flops_by_hand():
    # conv0: 4x4 out x 2 ch x 3*3*3 taps; conv1: 2x2 x 4 x 3*3*2;
    # head: 2x2 cells x (1 + 4 + 3) outputs x 4 inputs; 2 ops a MAC
    conv0 = 4 * 4 * 2 * 27
    conv1 = 2 * 2 * 4 * 18
    head = 2 * 2 * 8 * 4
    assert STANDIN.detector_flops_per_frame(TINY_DET) == 2 * (
        conv0 + conv1 + head)
    assert STANDIN.detector_regions(TINY_DET) == 4


def test_detector_flops_at_the_served_width():
    det = {"image_hw": [128, 128], "in_channels": 3, "widths": [48, 96, 192],
           "num_classes": 8}
    # 10.6 + 84.9 + 84.9 MFLOP of convolutions and 1.3 of head a frame
    assert STANDIN.detector_flops_per_frame(det) == (
        2 * (64 * 64 * 48 * 27 + 32 * 32 * 96 * 432 + 16 * 16 * 192 * 864
             + 256 * 13 * 192))


def test_classifier_flops_by_hand():
    # conv0: 2x2 out x 2 ch x 27 taps; proj 2 -> 5; readout 6 -> 3
    assert STANDIN.classifier_flops_per_crop(TINY_CLF) == 2 * (
        2 * 2 * 2 * 27 + 2 * 5 + 6 * 3)


def test_costs_by_hand():
    flops, nbytes = STANDIN.detect_split_cost(TINY_DET, frames=3, calls=2)
    assert flops == 3 * STANDIN.detector_flops_per_frame(TINY_DET)
    weights = 4 * ((27 * 2 + 2) + (18 * 4 + 4) + (4 * 8 + 8))
    per_frame = 8 * 8 * 3 * 4 + 4 * (16 + 4 + 1 + 1)
    assert nbytes == 3 * per_frame + 2 * weights

    flops, nbytes = STANDIN.classify_cost(TINY_CLF, TINY_DET, rows=5,
                                           frames=3, calls=1)
    assert flops == 5 * STANDIN.classifier_flops_per_crop(TINY_CLF)
    weights = 4 * ((27 * 2 + 2) + 2 * 5 + 6 * 3)
    per_frame = (8 * 8 * 3 * 4 + 4 * (16 + 1 + 4 + 1) + 4 * (6 + 3) * 4
                 + 4 * (4 + 1 + 4))
    assert nbytes == 3 * per_frame + 5 * 12 + weights


def test_least_time_and_unknown_device():
    t, bound = roofline.least_time(197e12, 1.0, "TPU v5 lite")
    assert bound == "compute" and t == pytest.approx(1.0)
    t, bound = roofline.least_time(1.0, 819e9, "TPU v5 lite")
    assert bound == "memory" and t == pytest.approx(1.0)
    with pytest.raises(KeyError):
        roofline.peaks("cpu")
