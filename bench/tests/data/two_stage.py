"""A toy two-stage detector family for the check's tests: a region proposal
network over two levels of anchors that selects its regions, and a box head.

The check's tests copy this file into ``bench/models/`` under a temporary
root, so that it is a family as a new configuration's would be, and never
one of the benchmark's own.

  backbone   ``conv0`` (3x3, stride 2) to level 0, ``conv1`` (3x3, stride 2)
             to level 1, ReLU after each
  RPN        ``rpn{l}``, a 1x1 convolution on level l with len(anchors[l])
             x (objectness logit, 4 box deltas) a location
  selection  per frame: the top ``pre_k`` anchors of a level by logit, greedy
             NMS at IoU >= ``nms_iou`` within a level, the top ``post_k``
             survivors over both levels, in order of logit
  box head   level 0 sampled on a 3x3 grid in each selected box, ``fc1``
             with ReLU, ``fc2`` to 4 box refinements and the class logits
  classifier ``conv`` (3x3, stride 2) with ReLU on the crop, a mean pool,
             ``proj`` with ReLU, the bias-absorbing 1 and the readout ``W``

A region's identity is its anchor: the level's offset plus (y * width + x)
* anchors + anchor.  Objectness is the sigmoid of the RPN logit, the box the
refined proposal.  The selection record holds the top ``record_k``
candidates of each level, with the next one's logit as the cut.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from bench.reference import HIGHEST, conv, crops, rounded


def _shapes(cfg):
    det, clf = cfg["detector"], cfg["classifier"]
    w0, w1 = det["widths"]
    a0, a1 = (len(a) for a in det["anchors"])
    head_in = 9 * w0
    d = {"conv0": {"w": (3, 3, det["in_channels"], w0), "b": (w0,)},
         "conv1": {"w": (3, 3, w0, w1), "b": (w1,)},
         "rpn0": {"w": (1, 1, w0, 5 * a0), "b": (5 * a0,)},
         "rpn1": {"w": (1, 1, w1, 5 * a1), "b": (5 * a1,)},
         "fc1": {"w": (head_in, det["head_dim"]), "b": (det["head_dim"],)},
         "fc2": {"w": (det["head_dim"], 4 + det["num_classes"]),
                 "b": (4 + det["num_classes"],)}}
    c = {"conv": {"w": (3, 3, clf["in_channels"], clf["width"]),
                  "b": (clf["width"],)},
         "proj": (clf["width"], clf["feature_dim"]),
         "W": (clf["feature_dim"] + 1, clf["num_classes"])}
    return d, c


@functools.partial(jax.jit, static_argnames=("shapes",))
def _normal(key, *, shapes):
    keys = jax.random.split(key, len(shapes))
    return tuple(jax.random.normal(k, s) * (0.05 if len(s) == 1
                                             else 1.0 / math.sqrt(s[-2]))
                 for k, s in zip(keys, shapes))


def make_weights(cfg, seed):
    leaves, treedef = jax.tree.flatten(
        list(_shapes(cfg)), is_leaf=lambda x: isinstance(x, tuple)
        and all(isinstance(i, int) for i in x))
    det, clf = jax.tree.unflatten(treedef, list(_normal(
        jax.random.PRNGKey(seed % 2**31), shapes=tuple(leaves))))
    scale = cfg["detector"]["class_scale"]
    det["fc2"]["w"] = det["fc2"]["w"].at[:, 4:].multiply(scale)
    return det, clf


def calibrate(cfg, det_params, chunks):
    return det_params


def _anchors(hw, cell_sizes):
    """(K, 4) cx, cy, w, h of a level's anchors, location-major."""
    gh, gw = hw
    gy, gx = np.meshgrid((np.arange(gh) + 0.5) / gh,
                         (np.arange(gw) + 0.5) / gw, indexing="ij")
    out = [[x, y, s, s] for y, x in zip(gy.reshape(-1), gx.reshape(-1))
           for s in cell_sizes]
    return np.asarray(out, np.float32)


def _iou(a, b):
    iw = jnp.maximum(jnp.minimum(a[:, None, 2], b[None, :, 2])
                     - jnp.maximum(a[:, None, 0], b[None, :, 0]), 0.0)
    ih = jnp.maximum(jnp.minimum(a[:, None, 3], b[None, :, 3])
                     - jnp.maximum(a[:, None, 1], b[None, :, 1]), 0.0)
    inter = iw * ih
    area_a = (a[:, 2] - a[:, 0]) * (a[:, 3] - a[:, 1])
    area_b = (b[:, 2] - b[:, 0]) * (b[:, 3] - b[:, 1])
    return inter / jnp.maximum(area_a[:, None] + area_b[None, :] - inter,
                               1e-9)


def _nms(boxes, thr):
    """Greedy NMS over boxes already in order of score."""
    k = boxes.shape[0]
    over = _iou(boxes, boxes) >= thr
    before = jnp.arange(k)

    def body(i, keep):
        return keep.at[i].set(~jnp.any(keep & over[:, i] & (before < i)))

    return jax.lax.fori_loop(0, k, body, jnp.zeros(k, bool))


def _select(levels, pre_k, nms_iou, post_k, record_k):
    """One frame: ``levels`` is [(logits (K_l,), boxes (K_l, 4))]."""
    rec, surv, offset = [], [], 0
    for g, (logit, box) in enumerate(levels):
        top, idx = jax.lax.top_k(logit, record_k + 1)
        rec.append((offset + idx[:record_k], top[:record_k], box[idx[:record_k]],
                    jnp.full(record_k, g, jnp.int32), top[record_k]))
        pre = idx[:pre_k]
        keep = _nms(box[pre], nms_iou)
        surv.append((offset + pre, jnp.where(keep, top[:pre_k], -jnp.inf)))
        offset += logit.shape[0]
    ids = jnp.concatenate([s[0] for s in surv])
    score = jnp.concatenate([s[1] for s in surv])
    best, at = jax.lax.top_k(score, post_k)
    chosen = jnp.where(jnp.isfinite(best), ids[at], -1).astype(jnp.int32)
    record = {"ids": jnp.concatenate([r[0] for r in rec]).astype(jnp.int32),
              "scores": jnp.concatenate([r[1] for r in rec]),
              "boxes": jnp.concatenate([r[2] for r in rec]),
              "group": jnp.concatenate([r[3] for r in rec]),
              "cut": jnp.stack([r[4] for r in rec])}
    return chosen, record


@functools.partial(jax.jit, static_argnames=("det", "precision"))
def _detector(params, images, *, det, precision):
    det = dict(det)
    x0 = jax.nn.relu(conv(images, params["conv0"], 2, precision))
    x1 = jax.nn.relu(conv(x0, params["conv1"], 2, precision))
    b = images.shape[0]
    levels = []
    for lvl, x in enumerate((x0, x1)):
        sizes = det["anchors"][lvl]
        h = conv(x, params[f"rpn{lvl}"], 1, precision)
        h = h.reshape(b, -1, 5)
        anc = jnp.asarray(_anchors(x.shape[1:3], sizes))
        cx = anc[:, 0] + 0.5 * anc[:, 2] * jnp.tanh(h[..., 1])
        cy = anc[:, 1] + 0.5 * anc[:, 3] * jnp.tanh(h[..., 2])
        w = anc[:, 2] * jnp.exp(0.5 * jnp.tanh(h[..., 3]))
        hh = anc[:, 3] * jnp.exp(0.5 * jnp.tanh(h[..., 4]))
        boxes = jnp.clip(jnp.stack([cx - w / 2, cy - hh / 2, cx + w / 2,
                                    cy + hh / 2], -1), 0.0, 1.0)
        levels.append((h[..., 0], boxes))
    chosen, record = jax.vmap(
        lambda lg: _select(lg, det["pre_k"], det["nms_iou"], det["post_k"],
                           det["record_k"]))([(lv[0], lv[1]) for lv in levels])
    all_logit = jnp.concatenate([lv[0] for lv in levels], 1)
    all_box = jnp.concatenate([lv[1] for lv in levels], 1)
    safe = jnp.maximum(chosen, 0)
    logit = jnp.take_along_axis(all_logit, safe, 1)
    prop = jnp.take_along_axis(all_box, safe[..., None], 1)
    n = chosen.shape[1]
    fidx = jnp.repeat(jnp.arange(b), n)
    feat = crops(x0, fidx, prop.reshape(-1, 4), out_hw=(3, 3)).reshape(b * n, -1)
    z = jax.nn.relu(jnp.matmul(rounded(feat, precision),
                               rounded(params["fc1"]["w"], precision),
                               precision=HIGHEST) + params["fc1"]["b"])
    z = jnp.matmul(rounded(z, precision), rounded(params["fc2"]["w"], precision),
                   precision=HIGHEST) + params["fc2"]["b"]
    z = z.reshape(b, n, -1)
    pw, ph = prop[..., 2] - prop[..., 0], prop[..., 3] - prop[..., 1]
    d = 0.1 * jnp.tanh(z[..., :4])
    boxes = jnp.clip(prop + d * jnp.stack([pw, ph, pw, ph], -1), 0.0, 1.0)
    loc = jnp.where(chosen >= 0, jax.nn.sigmoid(logit), 0.0)
    probs = jax.nn.softmax(z[..., 4:], axis=-1)
    return boxes, loc, probs, chosen, record


def _frozen(det):
    return tuple(sorted((k, tuple(tuple(a) for a in v) if k == "anchors"
                         else tuple(v) if isinstance(v, list) else v)
                        for k, v in det.items()))


def detector(det_params, images, cfg, precision):
    det = cfg["detector"]
    boxes, loc, probs, ids, record = _detector(
        det_params, images, det=_frozen(det), precision=precision)
    record = dict(record, pre_k=det["pre_k"], nms_iou=det["nms_iou"],
                  post_k=det["post_k"])
    return boxes, loc, probs, ids, record


@functools.partial(jax.jit, static_argnames=("precision",))
def _classifier(params, crops_, W, *, precision):
    x = jax.nn.relu(conv(crops_, params["conv"], 2, precision))
    x = jnp.mean(x, axis=(1, 2))
    x = jax.nn.relu(jnp.matmul(rounded(x, precision),
                               rounded(params["proj"], precision),
                               precision=HIGHEST))
    x = jnp.concatenate([x, jnp.ones((x.shape[0], 1), x.dtype)], -1)
    return x, jax.nn.sigmoid(jnp.matmul(rounded(x, precision),
                                        rounded(W, precision),
                                        precision=HIGHEST))


def classifier(clf_params, crops_, W, cfg, precision):
    return _classifier(clf_params, crops_, W, precision=precision)


def detector_flops_per_frame(det):
    h, w = det["image_hw"]
    w0, w1 = det["widths"]
    return 2 * 9 * (h * w // 4 * det["in_channels"] * w0
                    + h * w // 16 * w0 * w1)


def classifier_flops_per_crop(clf):
    h, w = clf["crop_hw"]
    return 2 * 9 * h * w // 4 * clf["in_channels"] * clf["width"]


def detect_split_cost(det, frames, calls):
    return detector_flops_per_frame(det) * frames, frames * 4 * 3 * 32 * 32


def classify_cost(clf, det, rows, frames, calls):
    return classifier_flops_per_crop(clf) * rows, rows * 4 * 3 * 8 * 8
