"""The repo's stand-in models: a 3-conv detector and a 3-conv fog classifier.

The family of ``DETECTOR`` / ``CLASSIFIER`` (``src/repro/configs/
vpaas_video.py``), as the program's ``models/detector.py`` and
``models/classifier.py`` compute them.  The weights are made here, on the
device, in one jitted call, in the layout the program reads:

  detector    ``conv{i}`` {``w`` (3, 3, cin, widths[i]), ``b``} for each of
              ``widths``, stride-2 SAME convolutions with ReLU; ``head``
              {``w`` (1, 1, widths[-1], 5 + num_classes), ``b``}, a 1x1
              convolution whose channels are objectness (0), the cell offset
              (1:3), the box size (3:5) and the class logits (5:)
  classifier  ``conv{i}`` as above on the crop; ``proj`` (widths[-1],
              feature_dim) after a global mean pool; ``W`` (feature_dim + 1,
              num_classes), the one-vs-all readout with the bias row last

The detector's grid is ``image_hw / 2**len(widths)``, one region a cell.
The same arrays go to the program and to the reference.
"""
from __future__ import annotations

import functools
import math
from typing import List

import jax
import jax.numpy as jnp
import numpy as np

from bench.reference import HIGHEST, conv, encode, rounded
from bench.roofline import F32


# ---------------------------------------------------------------------------
# Weights
# ---------------------------------------------------------------------------
def _shapes(det: dict, clf: dict):
    d, cin = {}, det["in_channels"]
    for i, w in enumerate(det["widths"]):
        d[f"conv{i}"] = {"w": (3, 3, cin, w), "b": (w,)}
        cin = w
    out = 1 + 4 + det["num_classes"]
    d["head"] = {"w": (1, 1, cin, out), "b": (out,)}
    c, cin = {}, clf["in_channels"]
    for i, w in enumerate(clf["widths"]):
        c[f"conv{i}"] = {"w": (3, 3, cin, w), "b": (w,)}
        cin = w
    c["proj"] = (cin, clf["feature_dim"])
    c["W"] = (clf["feature_dim"] + 1, clf["num_classes"])
    return d, c


def make_weights(cfg: dict, seed: int):
    """(det_params, clf_params) on the default device, from ``seed``.

    Weights are normal with variance 1/fan_in (fan_in is the second-last
    dim: input channels of a conv), biases normal with std 0.05."""
    shapes = _shapes(cfg["detector"], cfg["classifier"])
    leaves, treedef = jax.tree.flatten(
        list(shapes), is_leaf=lambda x: isinstance(x, tuple)
        and all(isinstance(i, int) for i in x))

    @jax.jit
    def build(key):
        keys = jax.random.split(key, len(leaves))
        out = []
        for k, shp in zip(keys, leaves):
            z = jax.random.normal(k, shp, jnp.float32)
            out.append(z * 0.05 if len(shp) == 1
                       else z / math.sqrt(shp[-2]))
        return jax.tree.unflatten(treedef, out)

    key = jax.random.PRNGKey(int(np.random.default_rng(seed).integers(2**31)))
    return tuple(build(key))


def calibrate(cfg: dict, det_params, chunks: List[np.ndarray]):
    """Detector weights whose head is set on the traffic's own scenes.

    Random weights alone leave the head's operating point to chance: the
    backbone's ReLU features are large and not centred, so every head
    channel carries an offset and a spread of the features' size; seeds
    whose boxes come out small keep ten times the proposals through NMS,
    and seeds with one dominant class accept every region in the cloud.  On
    the decoded frames of ``chunks`` each head channel is centred and
    scaled to spread 1, then: the objectness bias is placed so that a share
    ``weights.objectness_pass`` of cells clears theta_loc, the box-size
    biases so that the median box side is ``weights.box_side``, and the
    class logits scaled so that a share ``weights.accept_share`` of the
    cells clearing theta_loc is confident enough (theta_cls) to be accepted
    in the cloud."""
    w, pc = cfg["weights"], cfg["protocol"]
    head = det_params["head"]
    zero = dict(det_params, head=dict(head, b=jnp.zeros_like(head["b"])))
    lq = jnp.concatenate([encode(jnp.asarray(c), pc["r_low"], pc["q_low"])
                          for c in chunks])
    raw = np.asarray(head_logits(zero, lq,
                                 depth=len(cfg["detector"]["widths"])),
                     np.float64)
    raw = raw.reshape(-1, raw.shape[-1])
    mean, std = raw.mean(axis=0), np.maximum(raw.std(axis=0), 1e-12)
    z = (raw - mean) / std

    def logit(p):
        return float(np.log(p / (1.0 - p)))

    passing = z[:, 0] >= np.quantile(z[:, 0], 1.0 - w["objectness_pass"])

    def accepted(scale):
        c = z[passing, 5:] * scale
        c = np.exp(c - c.max(-1, keepdims=True))
        return float(np.mean(c.max(-1) / c.sum(-1) >= pc["theta_cls"]))

    lo, hi = 0.0, 64.0
    for _ in range(40):                     # accepted() rises with scale
        mid = 0.5 * (lo + hi)
        lo, hi = (mid, hi) if accepted(mid) < w["accept_share"] else (lo, mid)
    scale = np.ones(raw.shape[-1])
    scale[5:] = hi
    shift = np.zeros(raw.shape[-1])
    shift[0] = logit(pc["theta_loc"]) - np.quantile(
        z[:, 0], 1.0 - w["objectness_pass"])
    shift[3:5] = logit(w["box_side"]) - np.median(z[:, 3:5], axis=0)
    # head(x) = raw * a + b with a = scale / std, b = shift - mean * a
    a = scale / std
    return dict(det_params, head={
        "w": head["w"] * jnp.asarray(a, jnp.float32),
        "b": jnp.asarray(shift - mean * a, jnp.float32)})


# ---------------------------------------------------------------------------
# Forwards, at the reference's precisions
# ---------------------------------------------------------------------------
def _head(params, images, depth: int, precision: str):
    x = images
    for i in range(depth):
        x = jax.nn.relu(conv(x, params[f"conv{i}"], 2, precision))
    b, gh, gw, _ = x.shape
    return conv(x, params["head"], 1, precision).reshape(b, gh * gw, -1), gh, gw


@functools.partial(jax.jit, static_argnames=("depth",))
def head_logits(params, images, *, depth: int):
    """The head's raw outputs (B, N, 5 + C) at HIGHEST."""
    return _head(params, images, depth, "highest")[0]


@functools.partial(jax.jit, static_argnames=("depth", "precision"))
def _detector(params, images, *, depth: int, precision: str):
    head, gh, gw = _head(params, images, depth, precision)
    loc = jax.nn.sigmoid(head[..., 0])
    off = jax.nn.sigmoid(head[..., 1:3])
    size = jax.nn.sigmoid(head[..., 3:5])
    gy, gx = np.meshgrid(np.arange(gh), np.arange(gw), indexing="ij")
    cx = (gx.reshape(-1).astype(np.float32) + off[..., 0]) / gw
    cy = (gy.reshape(-1).astype(np.float32) + off[..., 1]) / gh
    w, h = size[..., 0], size[..., 1]
    boxes = jnp.clip(jnp.stack([cx - w / 2, cy - h / 2, cx + w / 2,
                                cy + h / 2], -1), 0.0, 1.0)
    return boxes, loc, jax.nn.softmax(head[..., 5:], axis=-1)


def detector(det_params, images, cfg: dict, precision: str):
    """boxes (B, N, 4) xyxy, loc (B, N) objectness, probs (B, N, C)."""
    return _detector(det_params, images, depth=len(cfg["detector"]["widths"]),
                     precision=precision)


@functools.partial(jax.jit, static_argnames=("depth", "precision"))
def _classifier(params, crops, W, *, depth: int, precision: str):
    x = crops
    for i in range(depth):
        x = jax.nn.relu(conv(x, params[f"conv{i}"], 2, precision))
    x = jnp.mean(x, axis=(1, 2))
    x = jax.nn.relu(jnp.matmul(rounded(x, precision),
                               rounded(params["proj"], precision),
                               precision=HIGHEST))
    x = jnp.concatenate([x, jnp.ones((x.shape[0], 1), x.dtype)], -1)
    scores = jax.nn.sigmoid(jnp.matmul(rounded(x, precision),
                                       rounded(W, precision),
                                       precision=HIGHEST))
    return x, scores


def classifier(clf_params, crops, W, cfg: dict, precision: str):
    """features (K, d+1) with the bias-absorbing 1, scores (K, C)."""
    return _classifier(clf_params, crops, W,
                       depth=len(cfg["classifier"]["widths"]),
                       precision=precision)


# ---------------------------------------------------------------------------
# Operations and bytes of the served kernels (see bench/roofline.py)
# ---------------------------------------------------------------------------
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
