"""Weights from the seed, and the plain reference of the served High-Low path.

Nothing here imports the program.  The benchmark makes the weights itself,
on the device, in one jitted call, in the layout the program's models read
(``conv{i}/{w,b}`` and ``head`` for the detector, ``conv{i}``, ``proj`` and
the one-vs-all readout ``W`` for the fog classifier), and hands the same
arrays to the program and to this reference.

The reference follows the protocol as written (paper section IV; the
repo's ``DETECTOR`` / ``CLASSIFIER``): the closed-loop inter-frame DCT
codec, the conv detector with its two-signal head, the section IV.B split
(accept, then location / overlap / background filter, then greedy NMS), a
bilinear HQ crop of each uncertain region, the classifier backbone and the
sigmoid one-vs-all readout, and the merge.  Matrix products and
convolutions run at ``precision`` -- ``"highest"`` (float32) for the
reference, ``"bf16"`` or ``"fp8"`` for the controls, whose operands are
rounded to that type before an exact product.  NMS and the filter run on
the host in numpy, one frame at a time.
"""
from __future__ import annotations

import functools
import math
from typing import Dict, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
NMS_IOU = 0.45                      # section IV.B, both NMS passes
BLOCK = 8                           # codec block size
_ROUND_TO = {"highest": None, "bf16": jnp.bfloat16,
             "fp8": jnp.float8_e4m3fn}


# ---------------------------------------------------------------------------
# Weights
# ---------------------------------------------------------------------------
def _shapes(det: dict, clf: dict) -> Tuple[dict, dict]:
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


def make_weights(det: dict, clf: dict, seed: int):
    """(det_params, clf_params) on the default device, from ``seed``.

    Weights are normal with variance 1/fan_in (fan_in is the second-last
    dim: input channels of a conv), biases normal with std 0.05."""
    shapes = _shapes(det, clf)
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
    """Detector weights whose head is set from the seed's own scenes, so
    that every seed serves about the same amount of work.

    Random weights alone leave the head's operating point to the seed: the
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
# Codec: closed-loop inter-frame DCT (Eq. 2's F_v(r, q))
# ---------------------------------------------------------------------------
def _dct(n: int = BLOCK) -> np.ndarray:
    k = np.arange(n)
    mat = np.sqrt(2.0 / n) * np.cos(np.pi * (2 * k[None, :] + 1)
                                    * k[:, None] / (2 * n))
    mat[0] /= np.sqrt(2.0)
    return mat.astype(np.float32)


def _blocks(x):                       # (T, H, W, C) -> (T, H/8, W/8, C, 8, 8)
    t, h, w, c = x.shape
    return x.reshape(t, h // BLOCK, BLOCK, w // BLOCK, BLOCK, c
                     ).transpose(0, 1, 3, 5, 2, 4)


def _unblocks(x):
    t, hb, wb, c, _, _ = x.shape
    return x.transpose(0, 1, 4, 2, 5, 3).reshape(t, hb * BLOCK, wb * BLOCK, c)


@functools.partial(jax.jit, static_argnames=("r",))
def encode(frames, r: float, q):
    """Decoded LQ frames of one chunk: downscale by r, code each frame's
    residual against the previous reconstruction with an 8x8 DCT quantised
    at the H.264-style step 2**((q-4)/6)/64, reconstruct, upscale."""
    t, h0, w0, c = frames.shape
    hs, ws = max(BLOCK, int(h0 * r)), max(BLOCK, int(w0 * r))
    small = (jax.image.resize(frames, (t, hs, ws, c), "linear")
             if r != 1.0 else frames)
    small = jnp.pad(small, ((0, 0), (0, (-hs) % BLOCK), (0, (-ws) % BLOCK),
                            (0, 0)), "edge")
    dct = jnp.asarray(_dct())
    step = jnp.asarray(2.0 ** ((jnp.asarray(q, jnp.float32) - 4.0) / 6.0)
                       ) / 64.0

    def one(prev, frame):
        coef = jnp.einsum("ij,...jk,lk->...il", dct,
                          _blocks((frame - prev)[None]), dct,
                          precision=HIGHEST)
        quant = jnp.round(coef / step)
        res = jnp.einsum("ji,...jk,kl->...il", dct, quant * step, dct,
                         precision=HIGHEST)
        rec = jnp.clip(prev + _unblocks(res)[0], 0.0, 1.0)
        return rec, rec

    _, recs = jax.lax.scan(one, jnp.full_like(small[0], 0.5), small)
    recs = recs[:, :hs, :ws]
    if r != 1.0:
        recs = jax.image.resize(recs, (t, h0, w0, c), "linear")
    return jnp.clip(recs, 0.0, 1.0)


# ---------------------------------------------------------------------------
# Models
# ---------------------------------------------------------------------------
def _rounded(x, precision: str):
    to = _ROUND_TO[precision]
    return x if to is None else x.astype(to).astype(jnp.float32)


def _conv(x, p, stride: int, precision: str):
    y = jax.lax.conv_general_dilated(
        _rounded(x, precision), _rounded(p["w"], precision),
        (stride, stride), "SAME", dimension_numbers=("NHWC", "HWIO", "NHWC"),
        precision=HIGHEST)
    return y + p["b"]


def _head(params, images, depth: int, precision: str):
    x = images
    for i in range(depth):
        x = jax.nn.relu(_conv(x, params[f"conv{i}"], 2, precision))
    b, gh, gw, _ = x.shape
    return _conv(x, params["head"], 1, precision).reshape(b, gh * gw, -1), gh, gw


@functools.partial(jax.jit, static_argnames=("depth",))
def head_logits(params, images, *, depth: int):
    """The head's raw outputs (B, N, 5 + C) at HIGHEST."""
    return _head(params, images, depth, "highest")[0]


@functools.partial(jax.jit, static_argnames=("depth", "precision"))
def detector(params, images, *, depth: int, precision: str):
    """boxes (B, N, 4) xyxy, loc (B, N) objectness, probs (B, N, C)."""
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


@functools.partial(jax.jit, static_argnames=("depth", "precision"))
def classifier(params, crops, W, *, depth: int, precision: str):
    """features (K, d+1) with the bias-absorbing 1, scores (K, C)."""
    x = crops
    for i in range(depth):
        x = jax.nn.relu(_conv(x, params[f"conv{i}"], 2, precision))
    x = jnp.mean(x, axis=(1, 2))
    x = jax.nn.relu(jnp.matmul(_rounded(x, precision),
                               _rounded(params["proj"], precision),
                               precision=HIGHEST))
    x = jnp.concatenate([x, jnp.ones((x.shape[0], 1), x.dtype)], -1)
    scores = jax.nn.sigmoid(jnp.matmul(_rounded(x, precision),
                                       _rounded(W, precision),
                                       precision=HIGHEST))
    return x, scores


@functools.partial(jax.jit, static_argnames=("out_hw",))
def crops(frames, fidx, boxes, *, out_hw: Tuple[int, int]):
    """Bilinear resample of box k of frame fidx[k] to out_hw, zero outside
    the frame: sample row i of an oh-row crop lies at
    y1*(H-1) + (y2-y1)*(H-1)*i/(oh-1), and likewise for columns."""
    _, hi, wi, _ = frames.shape
    oh, ow = out_hw
    ys = (boxes[:, 1:2] * (hi - 1)
          + (boxes[:, 3:4] - boxes[:, 1:2]) * (hi - 1)
          * jnp.linspace(0.0, 1.0, oh)[None])                 # (K, oh)
    xs = (boxes[:, 0:1] * (wi - 1)
          + (boxes[:, 2:3] - boxes[:, 0:1]) * (wi - 1)
          * jnp.linspace(0.0, 1.0, ow)[None])                 # (K, ow)
    y0, x0 = jnp.floor(ys), jnp.floor(xs)
    wy, wx = ys - y0, xs - x0
    y0, x0 = y0.astype(jnp.int32), x0.astype(jnp.int32)
    f = fidx[:, None, None]

    def tap(yi, xi):
        ok = (yi[:, :, None] >= 0) & (yi[:, :, None] < hi) \
            & (xi[:, None, :] >= 0) & (xi[:, None, :] < wi)
        px = frames[f, jnp.clip(yi, 0, hi - 1)[:, :, None],
                    jnp.clip(xi, 0, wi - 1)[:, None, :]]
        return jnp.where(ok[..., None], px, 0.0)

    wy, wx = wy[:, :, None, None], wx[:, None, :, None]
    return ((1 - wy) * (1 - wx) * tap(y0, x0) + (1 - wy) * wx * tap(y0, x0 + 1)
            + wy * (1 - wx) * tap(y0 + 1, x0) + wy * wx * tap(y0 + 1, x0 + 1))


# ---------------------------------------------------------------------------
# Section IV.B split, on the host
# ---------------------------------------------------------------------------
def iou(a: np.ndarray, b: np.ndarray) -> np.ndarray:
    """(N, 4) x (M, 4) xyxy -> (N, M)."""
    iw = np.maximum(np.minimum(a[:, None, 2], b[None, :, 2])
                    - np.maximum(a[:, None, 0], b[None, :, 0]), 0.0)
    ih = np.maximum(np.minimum(a[:, None, 3], b[None, :, 3])
                    - np.maximum(a[:, None, 1], b[None, :, 1]), 0.0)
    inter = iw * ih
    area_a = (np.maximum(a[:, 2] - a[:, 0], 0.0)
              * np.maximum(a[:, 3] - a[:, 1], 0.0))
    area_b = (np.maximum(b[:, 2] - b[:, 0], 0.0)
              * np.maximum(b[:, 3] - b[:, 1], 0.0))
    return inter / np.maximum(area_a[:, None] + area_b[None, :] - inter, 1e-9)


def nms(boxes: np.ndarray, scores: np.ndarray, valid: np.ndarray
        ) -> np.ndarray:
    """Greedy NMS: take the best remaining box (first on ties), drop every
    remaining box overlapping it at IoU >= NMS_IOU."""
    keep = np.zeros(len(boxes), bool)
    alive = valid.copy()
    ov = iou(boxes, boxes) >= NMS_IOU
    for i in np.argsort(-scores, kind="stable"):
        if alive[i]:
            keep[i] = True
            alive &= ~ov[i]
    return keep


def split(boxes, loc, probs, pcfg: dict) -> Dict[str, np.ndarray]:
    """One frame's accepted detections and uncertain proposals."""
    conf = probs.max(-1)
    acc = nms(boxes, loc * conf, (loc >= pcfg["theta_loc"])
              & (conf >= pcfg["theta_cls"]))
    keep = loc >= pcfg["theta_loc"]
    if acc.any():
        keep &= iou(boxes, boxes[acc]).max(-1) < pcfg["theta_iou"]
    area = (np.maximum(boxes[:, 2] - boxes[:, 0], 0.0)
            * np.maximum(boxes[:, 3] - boxes[:, 1], 0.0))
    keep &= (area <= pcfg["theta_back"]) & ~acc
    return {"acc_valid": acc, "acc_labels": probs.argmax(-1).astype(np.int32),
            "prop_valid": nms(boxes, loc, keep)}


# ---------------------------------------------------------------------------
# Whole chunks
# ---------------------------------------------------------------------------
def _pow2(n: int) -> int:
    return 1 << max(0, (n - 1).bit_length())


def detect(cfg: dict, det_params, chunks: List[np.ndarray],
           precision: str = "highest") -> List[Dict[str, np.ndarray]]:
    """Cloud side of each HQ chunk (T, H, W, 3): the detector outputs
    (``boxes``, ``loc_scores``, ``cls_probs``) on the decoded LQ frames and
    the split (``acc_valid``, ``acc_labels``, ``prop_valid``)."""
    det, pc = cfg["detector"], cfg["protocol"]
    if not pc["inter_coding"]:
        raise NotImplementedError("the reference codes chunks inter-frame")
    out = []
    for hq in chunks:
        lq = encode(jnp.asarray(hq), pc["r_low"], pc["q_low"])
        boxes, loc, probs = (np.asarray(a) for a in detector(
            det_params, lq, depth=len(det["widths"]), precision=precision))
        parts = [split(boxes[f], loc[f], probs[f], pc)
                 for f in range(len(boxes))]
        res = {k: np.stack([p[k] for p in parts]) for k in parts[0]}
        res.update(boxes=boxes, loc_scores=loc, cls_probs=probs)
        out.append(res)
    return out


def fog(cfg: dict, clf_params, W, chunks: List[np.ndarray], boxes, valid,
        precision: str = "highest") -> List[Dict[str, np.ndarray]]:
    """Fog side: crop the HQ frames at ``boxes[c]`` (F, N, 4) where
    ``valid[c]`` (F, N) and classify; ``fog_scores`` and ``fog_features``
    grids, zero elsewhere."""
    clf = cfg["classifier"]
    n_cls, d1 = clf["num_classes"], clf["feature_dim"] + 1
    out = []
    for hq, bx, pv in zip(chunks, boxes, valid):
        f, n = pv.shape
        r = {"fog_scores": np.zeros((f, n, n_cls), np.float32),
             "fog_features": np.zeros((f, n, d1), np.float32)}
        fi, ni = (a.astype(np.int32) for a in np.nonzero(pv))
        if len(fi):
            k = _pow2(len(fi))
            fpad = np.zeros(k, np.int32)
            fpad[:len(fi)] = fi
            bpad = np.zeros((k, 4), np.float32)
            bpad[:len(fi)] = bx[fi, ni]
            cr = crops(jnp.asarray(hq), jnp.asarray(fpad), jnp.asarray(bpad),
                       out_hw=tuple(clf["crop_hw"]))
            x, s = classifier(clf_params, cr, W, depth=len(clf["widths"]),
                              precision=precision)
            r["fog_scores"][fi, ni] = np.asarray(s)[:len(fi)]
            r["fog_features"][fi, ni] = np.asarray(x)[:len(fi)]
        out.append(r)
    return out


def serve(cfg: dict, det_params, clf_params, W, chunks: List[np.ndarray],
          precision: str = "highest") -> List[Dict[str, np.ndarray]]:
    """The whole path for each HQ chunk: ``detect``, ``fog`` at its own
    proposals, and the merge into the ChunkResult fields (``boxes``,
    ``labels``, ``valid``, ``source``, ``prop_boxes``, ``prop_valid``,
    ``fog_scores``, ``fog_features``)."""
    pc = cfg["protocol"]
    out = detect(cfg, det_params, chunks, precision)
    fogs = fog(cfg, clf_params, W, chunks, [r["boxes"] for r in out],
               [r["prop_valid"] for r in out], precision)
    for r, fr in zip(out, fogs):
        r.update(fr)
        s = r["fog_scores"]
        fog_valid = r["prop_valid"] & (s.max(-1) >= pc["fog_min_conf"])
        r["labels"] = np.where(r["acc_valid"], r["acc_labels"],
                               s.argmax(-1)).astype(np.int32)
        r["valid"] = r["acc_valid"] | fog_valid
        r["source"] = np.where(r["acc_valid"], 0, 1).astype(np.int32)
        r["prop_boxes"] = r["boxes"]
    return out
