"""Peaks of the chip, and the operations and bytes of each served kernel.

Operations and bytes are computed from shapes alone, as a lower bound on
what a call needs: the multiply-adds of every convolution and matrix
product (2 operations each), and the bytes of the call's inputs, weights
and outputs read or written once.  Elementwise work, NMS and the bilinear
taps are not counted, and neither is any intermediate, so a kernel's
roofline share (least time / measured time) can only be understated.
"""
from __future__ import annotations

from typing import Dict

# Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 393 TOP/s int8,
# 16 GB HBM at 819 GB/s.  Keyed by jax's device_kind.
PEAKS: Dict[str, Dict[str, float]] = {
    "TPU v5 lite": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9},
}
PEAKS_SOURCE = "Google Cloud documentation, TPU v5e: 197 TFLOP/s bf16, 819 GB/s HBM"

F32 = 4


def peaks(device_kind: str) -> Dict[str, float]:
    if device_kind not in PEAKS:
        raise KeyError(f"no peaks for device kind {device_kind!r}; "
                       f"known: {sorted(PEAKS)}")
    return PEAKS[device_kind]


def _conv_stack(hw, cin: int, widths, k: int = 3):
    """(flops, weight bytes, out hw, out channels) of stride-2 SAME convs."""
    h, w = hw
    flops = wbytes = 0
    for cout in widths:
        h, w = -(-h // 2), -(-w // 2)
        flops += 2 * h * w * cout * k * k * cin
        wbytes += (k * k * cin * cout + cout) * F32
        cin = cout
    return flops, wbytes, (h, w), cin


def detector_flops_per_frame(det: dict) -> int:
    flops, _, (gh, gw), c = _conv_stack(det["image_hw"], det["in_channels"],
                                        det["widths"])
    return flops + 2 * gh * gw * c * (5 + det["num_classes"])


def detector_regions(det: dict) -> int:
    s = 2 ** len(det["widths"])
    return (det["image_hw"][0] // s) * (det["image_hw"][1] // s)


def detect_split_cost(det: dict, frames: int, calls: int):
    """(flops, bytes) of ``calls`` fused detect+split calls over ``frames``
    frames in all: LQ frames in; boxes, labels and two masks out."""
    _, wbytes, _, c = _conv_stack(det["image_hw"], det["in_channels"],
                                  det["widths"])
    wbytes += (c * (5 + det["num_classes"]) + 5 + det["num_classes"]) * F32
    h, w = det["image_hw"]
    per_frame_in = h * w * det["in_channels"] * F32
    per_frame_out = detector_regions(det) * (4 * F32 + 4 + 1 + 1)
    return (detector_flops_per_frame(det) * frames,
            frames * (per_frame_in + per_frame_out) + calls * wbytes)


def classifier_flops_per_crop(clf: dict) -> int:
    flops, _, _, c = _conv_stack(clf["crop_hw"], clf["in_channels"],
                                 clf["widths"])
    d = clf["feature_dim"]
    return flops + 2 * c * d + 2 * (d + 1) * clf["num_classes"]


def classify_cost(clf: dict, det: dict, rows: int, frames: int, calls: int):
    """(flops, bytes) of ``calls`` compacted classify calls that crop
    ``rows`` bucket rows in all from ``frames`` HQ frames: frames, boxes,
    gather plan and weights in; score and feature grids, labels, validity
    and source out."""
    _, wbytes, _, c = _conv_stack(clf["crop_hw"], clf["in_channels"],
                                  clf["widths"])
    d, n_cls = clf["feature_dim"], clf["num_classes"]
    wbytes += (c * d + (d + 1) * n_cls) * F32
    h, w = det["image_hw"]
    n = detector_regions(det)
    per_frame = (h * w * clf["in_channels"] * F32            # HQ frame
                 + n * (4 * F32 + 1 + 4 + 1)                  # boxes, masks
                 + n * ((d + 1) + n_cls) * F32                # grids out
                 + n * (4 + 1 + 4))                           # labels etc.
    return (classifier_flops_per_crop(clf) * rows,
            frames * per_frame + rows * 3 * 4 + calls * wbytes)


def least_time(flops: float, nbytes: float, device_kind: str):
    """(seconds, bound) of the roofline: the larger of the two times."""
    p = peaks(device_kind)
    tc, tm = flops / p["bf16_flops"], nbytes / p["hbm_bytes_per_s"]
    return (tc, "compute") if tc >= tm else (tm, "memory")
