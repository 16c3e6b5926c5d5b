"""Region selection, the §IV.B three-stage filter, and HQ crop extraction.

Everything is fixed-shape / lax-friendly: each frame carries a constant
region budget N with validity masks, so the whole protocol jits and shards.
"""
from __future__ import annotations

from typing import Dict, NamedTuple, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import ops


class RegionSplit(NamedTuple):
    # accepted: cloud-confident detections, used directly as labels (RQ1)
    acc_boxes: jax.Array        # (F, N, 4)
    acc_labels: jax.Array       # (F, N) int32
    acc_valid: jax.Array        # (F, N) bool
    # uncertain: only coordinates travel back to the fog (RQ3)
    prop_boxes: jax.Array       # (F, N, 4)
    prop_valid: jax.Array       # (F, N) bool


def split_regions(
    det: Dict[str, jax.Array],  # detector output on LOW-quality frames
    *,
    theta_cls: float,           # classification confidence to accept directly
    theta_loc: float,           # §IV.B location-confidence threshold
    theta_iou: float,           # §IV.B overlap threshold
    theta_back: float,          # §IV.B background-area threshold (fraction)
    impl: str = "ref",
) -> RegionSplit:
    boxes, loc, probs = det["boxes"], det["loc_scores"], det["cls_probs"]
    cls_conf = jnp.max(probs, axis=-1)
    labels = jnp.argmax(probs, axis=-1).astype(jnp.int32)

    nms_iou = 0.45
    acc_raw = (loc >= theta_loc) & (cls_conf >= theta_cls)
    acc_valid = jax.vmap(
        lambda b, s, v: ops.nms_mask(b, s, v, iou_threshold=nms_iou,
                                     impl=impl))(boxes, loc * cls_conf,
                                                 acc_raw)

    if impl in ("ref", "ref_unchunked"):
        def per_frame(bx, lc, av):
            keep = ops.region_filter_mask(
                bx, lc >= theta_loc, bx, av, lc,
                theta_loc=theta_loc, theta_iou=theta_iou,
                theta_back=theta_back, impl=impl)
            keep = keep & ~av      # accepted regions don't go to the fog
            return ops.nms_mask(bx, lc, keep, iou_threshold=nms_iou,
                                impl=impl)

        prop_valid = jax.vmap(per_frame)(boxes, loc, acc_valid)
    else:
        # kernel impls: ONE whole-batch fused filter pass over the flush's
        # (F, N) grid instead of F vmapped per-frame kernel launches —
        # the filter is fused into the detect_split dispatch itself
        keep = ops.region_filter_mask_batch(
            boxes, loc >= theta_loc, boxes, acc_valid, loc,
            theta_loc=theta_loc, theta_iou=theta_iou,
            theta_back=theta_back, impl=impl)
        keep = keep & ~acc_valid   # accepted regions don't go to the fog
        prop_valid = jax.vmap(
            lambda bx, lc, kp: ops.nms_mask(bx, lc, kp,
                                            iou_threshold=nms_iou,
                                            impl=impl))(boxes, loc, keep)
    return RegionSplit(boxes, labels, acc_valid, boxes, prop_valid)


def split_regions_dynamic(
    det: Dict[str, jax.Array],
    *,
    theta_cls: jax.Array,       # (F,) per-frame (per-site) thresholds
    theta_loc: jax.Array,       # (F,)
    theta_iou: float,
    theta_back: float,
) -> RegionSplit:
    """§IV.B split with *traced* per-frame acceptance thresholds.

    Per-site threshold adaptation packs streams with different
    ``theta_cls`` / ``theta_loc`` into one fused flush, so the thresholds
    arrive as (F,) arrays instead of static config floats.  The reference
    filter uses thetas only in elementwise comparisons, so tracing them is
    exact: with every frame at the global defaults this returns the same
    bits as :func:`split_regions` (impl="ref").  The Pallas filter bakes
    thetas in as static kernel params, so this variant always runs the
    reference math."""
    from repro.kernels import ref

    boxes, loc, probs = det["boxes"], det["loc_scores"], det["cls_probs"]
    cls_conf = jnp.max(probs, axis=-1)
    labels = jnp.argmax(probs, axis=-1).astype(jnp.int32)
    tc = jnp.asarray(theta_cls)
    tl = jnp.asarray(theta_loc)

    nms_iou = 0.45
    acc_raw = (loc >= tl[:, None]) & (cls_conf >= tc[:, None])
    acc_valid = jax.vmap(
        lambda b, s, v: ops.nms_mask(b, s, v, iou_threshold=nms_iou))(
            boxes, loc * cls_conf, acc_raw)

    def per_frame(bx, lc, av, tl_f):
        keep = ref.region_filter_mask(
            bx, lc >= tl_f, bx, av, lc,
            theta_loc=tl_f, theta_iou=theta_iou, theta_back=theta_back)
        keep = keep & ~av          # accepted regions don't go to the fog
        return ops.nms_mask(bx, lc, keep, iou_threshold=nms_iou)

    prop_valid = jax.vmap(per_frame)(boxes, loc, acc_valid, tl)
    return RegionSplit(boxes, labels, acc_valid, boxes, prop_valid)


def nms_rounds(acc_valid: np.ndarray, prop_valid: np.ndarray) -> int:
    """Rounds that a split's two greedy NMS passes ran over its frames.

    ``acc_valid`` and ``prop_valid`` are the passes' (F, N) keep masks.
    A pass keeps one box a round in each frame that still has a candidate
    alive and stops once none has, so it runs as many rounds as its
    largest per-frame kept count."""
    return int(np.sum(acc_valid, axis=1).max(initial=0)
               + np.sum(prop_valid, axis=1).max(initial=0))


def coordinate_bytes(split: RegionSplit) -> jax.Array:
    """Bytes for the returned coordinates (paper: 'only several bytes').

    4 x float16 coords + 1 byte header per proposal region.
    """
    return jnp.sum(split.prop_valid.astype(jnp.float32)) * 9.0


def compaction_indices(prop_valid: np.ndarray,
                       buckets: Tuple[int, ...] = (4, 8, 16, 32, 64, 128)
                       ) -> Tuple[np.ndarray, np.ndarray, int, int]:
    """Host-side gather plan for the compacted classify path.

    From the (F, N) validity mask (the flush's single host transfer) build
    the (frame, region) index lists of the valid proposals, padded up to the
    next bucket size so the jit'd compacted classifier sees few distinct
    shapes.  Pad rows use the out-of-bounds frame index F: gathers clip
    (harmless garbage crop), scatters drop (the result grid keeps its
    zeros).  Past the largest bucket the batch runs at its exact size —
    padding down would silently drop proposals.

    Returns ``(fidx, ridx, n_valid, bucket_size)``.
    """
    pv = np.asarray(prop_valid, bool)
    f = pv.shape[0]
    idx = np.argwhere(pv)
    n = len(idx)
    size = next((b for b in buckets if n <= b), n)
    fidx = np.full(size, f, np.int32)       # OOB pad: scatter-dropped
    ridx = np.zeros(size, np.int32)
    if n:
        fidx[:n] = idx[:, 0]
        ridx[:n] = idx[:, 1]
    return fidx, ridx, n, size


# ---------------------------------------------------------------------------
# HQ crop extraction (fog side)
# ---------------------------------------------------------------------------
# Both entry points delegate to ref.bilinear_crops — the single
# fixed-lowering bilinear program shared with the Pallas crop_gather kernel
# and its oracle — so the shared-grid path and the compacted kernel path
# produce bit-identical crops under jit.
def crop_and_resize(
    frame: jax.Array,           # (H, W, 3)
    boxes: jax.Array,           # (N, 4) xyxy in [0, 1]
    out_hw: Tuple[int, int],
) -> jax.Array:
    """Bilinear crop of each box to out_hw; returns (N, h, w, 3)."""
    from repro.kernels import ref
    n = boxes.shape[0]
    return ref.bilinear_crops(frame[None], jnp.zeros(n, jnp.int32), boxes,
                              out_hw)


def crop_batch(frames: jax.Array, boxes: jax.Array,
               out_hw: Tuple[int, int]) -> jax.Array:
    """frames (F, H, W, 3), boxes (F, N, 4) -> (F, N, h, w, 3)."""
    from repro.kernels import ref
    f, n = boxes.shape[0], boxes.shape[1]
    fmap = jnp.repeat(jnp.arange(f, dtype=jnp.int32), n)
    crops = ref.bilinear_crops(frames, fmap, boxes.reshape(f * n, 4), out_hw)
    return crops.reshape(f, n, *out_hw, frames.shape[-1])


