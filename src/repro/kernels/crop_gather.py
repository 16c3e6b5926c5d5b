"""Pallas TPU kernel for the compacted crop gather — the detect→split→
classify chain's crop stage.

Given the flush's HQ frames (F, H, W, 3), proposal boxes (F, N, 4) and the
(3, B) compaction indices, emit the bucketed (B, oh, ow, 3) crop batch
directly: only the B valid-proposal rows pay crop cost, where the old
shared-grid path materialized all F x N crops before gathering.

The grid runs one program per bucket row.  The row's frame index lives in
the scalar-prefetch operand, so the frame BlockSpec streams exactly ONE
channel-major (1, C, H, W) frame into VMEM per row; the row's sample
positions (:func:`repro.kernels.ref.crop_positions` of its box, computed
ahead of the call) arrive as an (oh, 1) column and a (1, ow) row.  Pad
rows (frame index F, out of bounds) clip to the last frame, matching the
oracle's gather-clips / scatter-drops semantics.

A TPU kernel cannot gather from VMEM, so the body picks the four bilinear
taps with one-hot selection matmuls: the sample grid of a box is separable
(row positions depend only on the output row, column positions only on the
output column), so ``R_y (oh, H) @ frame_c (H, W) @ R_x (W, ow)`` with
one-hot ``R`` yields exactly ``frame_c[y_i, x_j]`` — one nonzero term per
sum, and taps outside the frame match no row, so they read 0 (the
oracle's ``mode='constant'``).  The blend is the oracle's own
:func:`repro.kernels.ref.bilinear_sample`, so the kernel is bit-identical
to the oracle where its matmuls are exact (interpret mode on CPU, f32
``HIGHEST`` precision on TPU).
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import ref


def _select(a, b):
    return jnp.dot(a, b, precision=jax.lax.Precision.HIGHEST,
                   preferred_element_type=jnp.float32)


def _crop_kernel(idx_ref, frame_ref, ys_ref, xs_ref, out_ref):
    del idx_ref                      # consumed by the frame index map
    _, ch, h_img, w_img = frame_ref.shape
    ys, xs = ys_ref[0], xs_ref[0]                          # (oh, 1), (1, ow)
    rows = jax.lax.broadcasted_iota(jnp.int32, (ys.shape[0], h_img), 1)
    cols = jax.lax.broadcasted_iota(jnp.int32, (w_img, xs.shape[1]), 0)
    for c in range(ch):
        frame_c = frame_ref[0, c].astype(jnp.float32)      # (H, W)

        def fetch(yi, xi):           # yi (oh, 1), xi (1, ow) -> (oh, ow)
            picked = _select(frame_c, (cols == xi).astype(jnp.float32))
            return _select((rows == yi).astype(jnp.float32), picked)

        out_ref[0, c] = ref.bilinear_sample(ys, xs, fetch).astype(
            out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("out_hw", "interpret"))
def crop_gather(frames: jax.Array,       # (F, H, W, C)
                boxes: jax.Array,        # (F, N, 4)
                idxs: jax.Array,         # (>=2, B) int32
                *, out_hw: Tuple[int, int],
                interpret: bool = False) -> jax.Array:
    """(B, oh, ow, C) bucketed crop batch; see module docstring."""
    f, h, w, ch = frames.shape
    n = boxes.shape[1]
    b = idxs.shape[1]
    oh, ow = out_hw
    idxs = idxs.astype(jnp.int32)
    fidx = jnp.clip(idxs[0], 0, f - 1)
    ys, xs = ref.crop_positions(
        boxes[fidx, jnp.clip(idxs[1], 0, n - 1)], h, w,
        jnp.asarray(ref._crop_lin(oh)), jnp.asarray(ref._crop_lin(ow)))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b,),
        in_specs=[
            pl.BlockSpec(
                (1, ch, h, w),
                lambda i, idx_ref: (jnp.clip(idx_ref[0, i], 0, f - 1),
                                    0, 0, 0)),
            pl.BlockSpec((1, oh, 1), lambda i, idx_ref: (i, 0, 0)),
            pl.BlockSpec((1, 1, ow), lambda i, idx_ref: (i, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, ch, oh, ow),
                               lambda i, idx_ref: (i, 0, 0, 0)),
    )
    out = pl.pallas_call(
        _crop_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, ch, oh, ow), frames.dtype),
        interpret=interpret,
    )(idxs, jnp.transpose(frames, (0, 3, 1, 2)), ys.reshape(b, oh, 1),
      xs.reshape(b, 1, ow))
    return jnp.transpose(out, (0, 2, 3, 1))
