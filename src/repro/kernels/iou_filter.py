"""Pallas TPU kernel for the §IV.B region filter hot spot.

The filter's inner loop is the pairwise IoU of N proposals vs M accepted
boxes.  Tiling: grid = (N/BN, M/BM); each program computes one IoU tile
from two box tiles living in VMEM.  One side of a tile arrives as box rows
(BA, 4) and the other transposed, as coordinate rows (4, BB), so every
coordinate is a (BA, 1) column or a (1, BB) row and the tile broadcasts
without a relayout.  The fused filter puts the proposals on the lane axis:
its running max-IoU, the validity and score operands and the keep mask are
all lane-dense (1, BN) rows, and the three-stage threshold logic
(theta_loc / max-IoU / theta_back) runs in the last tile pass, so the mask
never round-trips HBM.

Block shapes follow the TPU rule that the last two block dims are multiples
of (8, 128) or span the array: the (F, N) operands travel as (F, 1, N).
Validated against ``repro.kernels.ref`` in interpret mode; compiled for a
v5e in ``tests/test_tpu_compile.py``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _iou_tile(a: jax.Array, bt: jax.Array) -> jax.Array:
    """a (BA, 4) box rows, bt (4, BB) coordinate rows -> IoU (BA, BB) fp32.

    Every operation is symmetric in its two operands, so the tile is
    bitwise the transpose of ``_iou_tile(b, a.T)``."""
    a = a.astype(jnp.float32)
    bt = bt.astype(jnp.float32)
    ax1, ay1, ax2, ay2 = a[:, 0:1], a[:, 1:2], a[:, 2:3], a[:, 3:4]
    bx1, by1, bx2, by2 = bt[0:1], bt[1:2], bt[2:3], bt[3:4]
    iw = jnp.maximum(jnp.minimum(ax2, bx2) - jnp.maximum(ax1, bx1), 0.0)
    ih = jnp.maximum(jnp.minimum(ay2, by2) - jnp.maximum(ay1, by1), 0.0)
    inter = iw * ih
    area_a = jnp.maximum(ax2 - ax1, 0.0) * jnp.maximum(ay2 - ay1, 0.0)
    area_b = jnp.maximum(bx2 - bx1, 0.0) * jnp.maximum(by2 - by1, 0.0)
    union = area_a + area_b - inter
    return inter / jnp.maximum(union, 1e-9)


def _iou_kernel(a_ref, bt_ref, o_ref):
    o_ref[...] = _iou_tile(a_ref[...], bt_ref[...])


@functools.partial(jax.jit, static_argnames=("bn", "bm", "interpret"))
def iou_matrix(boxes_a: jax.Array, boxes_b: jax.Array, *, bn: int = 128,
               bm: int = 128, interpret: bool = False) -> jax.Array:
    n, m = boxes_a.shape[0], boxes_b.shape[0]
    bn = min(bn, n)
    bm = min(bm, m)
    pn, pm = (-n) % bn, (-m) % bm
    if pn:
        boxes_a = jnp.pad(boxes_a, ((0, pn), (0, 0)))
    if pm:
        boxes_b = jnp.pad(boxes_b, ((0, pm), (0, 0)))
    out = pl.pallas_call(
        _iou_kernel,
        grid=((n + pn) // bn, (m + pm) // bm),
        in_specs=[pl.BlockSpec((bn, 4), lambda i, j: (i, 0)),
                  pl.BlockSpec((4, bm), lambda i, j: (0, j))],
        out_specs=pl.BlockSpec((bn, bm), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((n + pn, m + pm), jnp.float32),
        interpret=interpret,
    )(boxes_a, boxes_b.T)
    return out[:n, :m]


# ---------------------------------------------------------------------------
# Fused three-stage filter over a (F, N) region grid
# ---------------------------------------------------------------------------
def _filter_kernel(propt_ref, pv_ref, acc_ref, av_ref, loc_ref, keep_ref,
                   maxiou_scr, *, theta_loc, theta_iou, theta_back,
                   frame_area):
    # grid (F, N/BN, M/BM): blocks carry a size-1 frame dim, and the
    # max-IoU scratch resets at the first M-tile of every (frame, N-tile)
    # pair.  The grid iterates the last axis fastest, so the j sweep over
    # M-tiles for one (f, i) is contiguous and the accumulation stays
    # private.
    j = pl.program_id(2)
    nm = pl.num_programs(2)

    @pl.when(j == 0)
    def _init():
        maxiou_scr[...] = jnp.zeros_like(maxiou_scr)

    pt = propt_ref[0].astype(jnp.float32)                 # (4, BN)
    iou = _iou_tile(acc_ref[0], pt)                       # (BM, BN)
    iou = jnp.where(av_ref[0] > 0, iou, 0.0)              # av (BM, 1)
    maxiou_scr[...] = jnp.maximum(maxiou_scr[...],
                                  jnp.max(iou, axis=0, keepdims=True))

    @pl.when(j == nm - 1)
    def _finalize():
        w = jnp.maximum(pt[2:3] - pt[0:1], 0.0)           # (1, BN)
        h = jnp.maximum(pt[3:4] - pt[1:2], 0.0)
        keep = (pv_ref[0] > 0) & (loc_ref[0] >= theta_loc)
        keep &= maxiou_scr[...] < theta_iou
        keep &= (w * h / frame_area) <= theta_back
        keep_ref[0] = keep.astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=(
    "theta_loc", "theta_iou", "theta_back", "frame_area", "bn", "bm",
    "interpret"))
def region_filter_mask_batch(proposals, prop_valid, accepted, acc_valid,
                             loc_scores, *, theta_loc: float,
                             theta_iou: float, theta_back: float,
                             frame_area: float = 1.0, bn: int = 128,
                             bm: int = 128,
                             interpret: bool = False) -> jax.Array:
    """Whole-flush filter: (F, N, 4) proposals vs (F, M, 4) accepted.

    One pallas_call over grid (F, N/BN, M/BM) replaces F per-frame kernel
    launches, so the fused ``cloud.detect_split`` stage pays a single
    filtering pass for the packed cross-stream batch.  Bit-identical to
    vmapping ``ref.region_filter_mask`` over frames."""
    f, n = proposals.shape[0], proposals.shape[1]
    m = accepted.shape[1]
    bn = min(bn, n)
    bm = min(bm, m)
    pn, pm = (-n) % bn, (-m) % bm
    if pn:
        proposals = jnp.pad(proposals, ((0, 0), (0, pn), (0, 0)))
        prop_valid = jnp.pad(prop_valid, ((0, 0), (0, pn)))
        loc_scores = jnp.pad(loc_scores, ((0, 0), (0, pn)))
    if pm:
        accepted = jnp.pad(accepted, ((0, 0), (0, pm), (0, 0)))
        acc_valid = jnp.pad(acc_valid, ((0, 0), (0, pm)))
    np_, mp = n + pn, m + pm

    # a lane-dense (1, BN) slice of an (F, 1, N) operand
    row = pl.BlockSpec((1, 1, bn), lambda f_, i, j: (f_, 0, i))

    keep = pl.pallas_call(
        functools.partial(_filter_kernel, theta_loc=theta_loc,
                          theta_iou=theta_iou, theta_back=theta_back,
                          frame_area=frame_area),
        grid=(f, np_ // bn, mp // bm),
        in_specs=[
            pl.BlockSpec((1, 4, bn), lambda f_, i, j: (f_, 0, i)),
            row,
            pl.BlockSpec((1, bm, 4), lambda f_, i, j: (f_, j, 0)),
            pl.BlockSpec((1, bm, 1), lambda f_, i, j: (f_, j, 0)),
            row,
        ],
        out_specs=row,
        out_shape=jax.ShapeDtypeStruct((f, 1, np_), jnp.int32),
        scratch_shapes=[pltpu.VMEM((1, bn), jnp.float32)],
        interpret=interpret,
    )(jnp.swapaxes(proposals, 1, 2),
      prop_valid.astype(jnp.int32).reshape(f, 1, np_),
      accepted,
      acc_valid.astype(jnp.int32).reshape(f, mp, 1),
      loc_scores.reshape(f, 1, np_))
    return keep[:, 0, :n].astype(bool)


def region_filter_mask(proposals, prop_valid, accepted, acc_valid, loc_scores,
                       *, theta_loc: float, theta_iou: float,
                       theta_back: float, frame_area: float = 1.0,
                       bn: int = 128, bm: int = 128,
                       interpret: bool = False) -> jax.Array:
    """Single-frame filter: :func:`region_filter_mask_batch` with F=1."""
    return region_filter_mask_batch(
        proposals[None], prop_valid[None], accepted[None], acc_valid[None],
        loc_scores[None], theta_loc=theta_loc, theta_iou=theta_iou,
        theta_back=theta_back, frame_area=frame_area, bn=bn, bm=bm,
        interpret=interpret)[0]
