"""The plain reference of the served High-Low path.

Nothing here imports the program, and nothing here knows a model: the
detector and the classifier, their weights and their costs belong to the
configuration's model family (``bench/models/<models>.py``, loaded by
``bench.models.family``).  The benchmark makes the weights itself from the
configuration's ``weights.seed`` and hands the same arrays to the program
and to this reference.

The reference follows the protocol as written (paper section IV): the
closed-loop inter-frame DCT codec, the family's detector on the decoded
frames, the section IV.B split (accept, then location / overlap /
background filter, then greedy NMS), a bilinear HQ crop of each uncertain
region, the family's classifier with its sigmoid one-vs-all readout, and
the merge.  Matrix products and convolutions run at ``precision`` --
``"highest"`` (float32) for the reference, ``"bf16"`` or ``"fp8"`` for the
controls, whose operands are rounded to that type before an exact product
(``rounded``, ``conv``).  NMS and the filter run on the host in numpy,
one frame at a time.
"""
from __future__ import annotations

import functools
from typing import Dict, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from bench.models import family

HIGHEST = jax.lax.Precision.HIGHEST
NMS_IOU = 0.45                      # section IV.B, both NMS passes
BLOCK = 8                           # codec block size
ROUND_TO = {"highest": None, "bf16": jnp.bfloat16,
            "fp8": jnp.float8_e4m3fn}


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
# Products at a precision, for the model families' forwards
# ---------------------------------------------------------------------------
def rounded(x, precision: str):
    """``x`` with its operands rounded to ``precision``'s type and back."""
    to = ROUND_TO[precision]
    return x if to is None else x.astype(to).astype(jnp.float32)


def conv(x, p, stride: int, precision: str):
    """SAME convolution NHWC x HWIO of ``x`` with ``p["w"]``, plus ``p["b"]``,
    on operands rounded to ``precision`` and an exact product."""
    y = jax.lax.conv_general_dilated(
        rounded(x, precision), rounded(p["w"], precision),
        (stride, stride), "SAME", dimension_numbers=("NHWC", "HWIO", "NHWC"),
        precision=HIGHEST)
    return y + p["b"]


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
    (``boxes``, ``loc_scores``, ``cls_probs``) on the decoded LQ frames,
    each slot's region identity (``ids``: the family's, or the slot index
    where it gives none; an empty slot, -1, takes part in no split), the
    family's ``selection`` record where it returns one, and the split
    (``acc_valid``, ``acc_labels``, ``prop_valid``)."""
    pc = cfg["protocol"]
    fam = family(cfg)
    if not pc["inter_coding"]:
        raise NotImplementedError("the reference codes chunks inter-frame")
    out = []
    for hq in chunks:
        lq = encode(jnp.asarray(hq), pc["r_low"], pc["q_low"])
        det = fam.detector(det_params, lq, cfg, precision)
        boxes, loc, probs = (np.asarray(a) for a in det[:3])
        ids = (np.asarray(det[3], np.int32) if len(det) > 3 else
               np.broadcast_to(np.arange(loc.shape[1], dtype=np.int32),
                               loc.shape))
        loc = np.where(ids >= 0, loc, np.zeros((), loc.dtype))
        parts = [split(boxes[f], loc[f], probs[f], pc)
                 for f in range(len(boxes))]
        res = {k: np.stack([p[k] for p in parts]) for k in parts[0]}
        res.update(boxes=boxes, loc_scores=loc, cls_probs=probs, ids=ids)
        if len(det) > 4:
            res["selection"] = {k: np.asarray(v) for k, v in det[4].items()}
        out.append(res)
    return out


def fog(cfg: dict, clf_params, W, chunks: List[np.ndarray], boxes, valid,
        precision: str = "highest") -> List[Dict[str, np.ndarray]]:
    """Fog side: crop the HQ frames at ``boxes[c]`` (F, N, 4) where
    ``valid[c]`` (F, N) and classify; ``fog_scores`` and ``fog_features``
    grids, zero elsewhere."""
    clf, fam = cfg["classifier"], family(cfg)
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
            x, s = fam.classifier(clf_params, cr, W, cfg, precision)
            r["fog_scores"][fi, ni] = np.asarray(s)[:len(fi)]
            r["fog_features"][fi, ni] = np.asarray(x)[:len(fi)]
        out.append(r)
    return out


def serve(cfg: dict, det_params, clf_params, W, chunks: List[np.ndarray],
          precision: str = "highest") -> List[Dict[str, np.ndarray]]:
    """The whole path for each HQ chunk: ``detect``, ``fog`` at its own
    proposals, and the merge into the ChunkResult fields (``boxes``,
    ``labels``, ``valid``, ``source``, ``prop_boxes``, ``prop_valid``,
    ``fog_scores``, ``fog_features``) with the region identities
    ``region_ids``, as a program that carries them serves them."""
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
        r["region_ids"] = r["ids"]
    return out
