"""Jit'd dispatch wrappers over the Pallas kernels and their jnp oracles.

``impl`` selects the implementation:
  * ``"ref"``       pure-jnp oracle (CPU, dry-run lowering, XLA:TPU fallback)
  * ``"pallas"``    compiled Pallas TPU kernel (requires a real TPU)
  * ``"interpret"`` Pallas kernel body executed in interpret mode (CPU tests)

``crop_gather`` alone picks by backend (:func:`crop_gather_impl`): ``"ref"``
and ``"pallas"`` both run the kernel on a TPU and the oracle elsewhere.
"""
from __future__ import annotations

from typing import Optional, Union

import jax

from repro.kernels import ref

Scalar = Union[int, jax.Array]


def flash_attention(q, k, v, *, causal: bool = True,
                    window: Optional[int] = None,
                    softcap: Optional[float] = None,
                    q_offset: Scalar = 0,
                    q_offset_arr: Optional[jax.Array] = None,
                    impl: str = "ref") -> jax.Array:
    if q_offset_arr is not None:
        q_offset = q_offset_arr
    if impl == "ref_unchunked":
        # dry-run cost probes: the chunked variant hides attention flops
        # inside a lax.scan that XLA's cost_analysis counts once; windowed
        # layers use the unrolled windowed form (the Pallas kernel's actual
        # work profile — out-of-window KV blocks are skipped, not masked)
        if window is not None and causal and q.shape[1] > 1024:
            return ref.flash_attention_windowed_unrolled(
                q, k, v, window=window, softcap=softcap, q_offset=q_offset,
                chunk=512)
        return ref.flash_attention(q, k, v, causal=causal, window=window,
                                   softcap=softcap, q_offset=q_offset)
    if impl == "ref":
        # chunk long sequences so the live score buffer stays bounded (the
        # XLA-level flash analog; the Pallas kernel covers real TPUs)
        if q.shape[1] > 1024:
            return ref.flash_attention_chunked(
                q, k, v, causal=causal, window=window, softcap=softcap,
                q_offset=q_offset, chunk=512)
        return ref.flash_attention(q, k, v, causal=causal, window=window,
                                   softcap=softcap, q_offset=q_offset)
    from repro.kernels import flash_attention as fa
    return fa.flash_attention(q, k, v, causal=causal, window=window,
                              softcap=softcap, q_offset=q_offset,
                              interpret=(impl == "interpret"))


def decode_attention(q, k_cache, v_cache, cache_len, *,
                     window: Optional[int] = None,
                     softcap: Optional[float] = None,
                     impl: str = "ref") -> jax.Array:
    if impl in ("ref", "ref_unchunked"):
        return ref.decode_attention(q, k_cache, v_cache, cache_len,
                                    window=window, softcap=softcap)
    from repro.kernels import decode_attention as da
    return da.decode_attention(q, k_cache, v_cache, cache_len, window=window,
                               softcap=softcap,
                               interpret=(impl == "interpret"))


def ssd_scan(x, dt, A, B, C, *, chunk: int = 64, initial_state=None,
             impl: str = "ref"):
    if impl in ("ref", "ref_unchunked"):
        return ref.ssd_scan(x, dt, A, B, C, chunk=chunk,
                            initial_state=initial_state)
    from repro.kernels import ssd_scan as sk
    return sk.ssd_scan(x, dt, A, B, C, chunk=chunk,
                       initial_state=initial_state,
                       interpret=(impl == "interpret"))


def ssd_step(x, dt, A, B, C, state):
    # Single recurrent step: memory-bound rank-1 update; jnp is already
    # optimal on TPU (no kernel needed).
    return ref.ssd_step(x, dt, A, B, C, state)


def nms_mask(boxes, scores, valid, *, iou_threshold: float = 0.45,
             impl: str = "ref"):
    # greedy NMS is inherently sequential over selections, and a round
    # needs only the kept box's IoU row: no matrix for a kernel to tile.
    del impl
    return ref.nms_mask(boxes, scores, valid, iou_threshold)


def iou_matrix(boxes_a, boxes_b, *, impl: str = "ref"):
    if impl == "ref":
        return ref.iou_matrix(boxes_a, boxes_b)
    from repro.kernels import iou_filter as ik
    return ik.iou_matrix(boxes_a, boxes_b, interpret=(impl == "interpret"))


def region_filter_mask(proposals, prop_valid, accepted, acc_valid, loc_scores,
                       *, theta_loc: float, theta_iou: float,
                       theta_back: float, frame_area: float = 1.0,
                       impl: str = "ref"):
    if impl == "ref":
        return ref.region_filter_mask(
            proposals, prop_valid, accepted, acc_valid, loc_scores,
            theta_loc=theta_loc, theta_iou=theta_iou, theta_back=theta_back,
            frame_area=frame_area)
    from repro.kernels import iou_filter as ik
    return ik.region_filter_mask(
        proposals, prop_valid, accepted, acc_valid, loc_scores,
        theta_loc=theta_loc, theta_iou=theta_iou, theta_back=theta_back,
        frame_area=frame_area, interpret=(impl == "interpret"))


def region_filter_mask_batch(proposals, prop_valid, accepted, acc_valid,
                             loc_scores, *, theta_loc: float,
                             theta_iou: float, theta_back: float,
                             frame_area: float = 1.0, impl: str = "ref"):
    """Whole-flush §IV.B filter over a (F, N) region grid.

    Kernel impls run ONE fused pallas_call over grid (F, N/BN, M/BM) —
    the detect_split dispatch stops paying a per-frame filtering pass;
    the ref oracle is the vmapped per-frame filter (bit-identical)."""
    if impl in ("ref", "ref_unchunked"):
        return jax.vmap(
            lambda p, pv, a, av, ls: ref.region_filter_mask(
                p, pv, a, av, ls, theta_loc=theta_loc, theta_iou=theta_iou,
                theta_back=theta_back, frame_area=frame_area)
        )(proposals, prop_valid, accepted, acc_valid, loc_scores)
    from repro.kernels import iou_filter as ik
    return ik.region_filter_mask_batch(
        proposals, prop_valid, accepted, acc_valid, loc_scores,
        theta_loc=theta_loc, theta_iou=theta_iou, theta_back=theta_back,
        frame_area=frame_area, interpret=(impl == "interpret"))


# The crop kernel's VMEM, against the TPU compiler's default scoped-VMEM
# limit: 16 MiB on a v5e (its error for a kernel over it reads "limit
# 16.00M").  The kernel holds two (1, C, H, W) frame blocks (double-
# buffered) and, in its body, up to 9 more (H, W) float32 planes.  The 9
# is fitted from compiles for a described v5e with float32 3-channel frames
# and 40x40 crops: 512x768 frames needed 19.78 MiB, 600x1000 29.47 MiB and
# 256x2048 28.91 MiB, the two blocks plus 7.2, 6.6 and 8.5 planes; 512x512
# frames compiled, with 40x40 and with 128x128 crops.  The estimate leaves
# out the crop size: the (oh, H) and (W, ow) selections are small next to
# the (H, W) planes while the crop is small next to the frame.
SCOPED_VMEM_BYTES = 16 * 2 ** 20
_CROP_BODY_PLANES = 9


def _plane_bytes(h: int, w: int) -> int:
    """VMEM bytes of one float32 (h, w) plane in (8, 128) tiles."""
    return 4 * (-(-h // 8) * 8) * (-(-w // 128) * 128)


def crop_gather_impl(impl: str, frame_shape) -> str:
    """The implementation ``crop_gather`` runs for ``impl`` on frames of
    ``frame_shape`` (F, H, W, C): ``"interpret"`` stays (tests only); on a
    TPU, ``"ref"`` and ``"pallas"`` pick the compiled kernel (``"pallas"``)
    where its VMEM fits the scoped limit, else the oracle (``"ref"``);
    every other backend runs the oracle."""
    if impl == "interpret":
        return impl
    if impl not in ("ref", "pallas") or jax.default_backend() != "tpu":
        return "ref"
    _, h, w, c = frame_shape
    if (2 * c + _CROP_BODY_PLANES) * _plane_bytes(h, w) > SCOPED_VMEM_BYTES:
        return "ref"
    return "pallas"


def crop_gather(frames, boxes, idxs, *, out_hw, impl: str = "ref"):
    """Compacted crop gather: (F,H,W,C) x (F,N,4) x (3,B) -> (B,oh,ow,C).

    Runs what :func:`crop_gather_impl` picks: the Pallas kernel on a TPU,
    the jitted ``ref.crop_gather`` oracle elsewhere or where the kernel's
    frame blocks would not fit VMEM.  Both share the bilinear program of
    ``ref.crop_positions`` and ``ref.bilinear_sample``, so their outputs
    are bit-identical to gathering from the full shared crop grid (the
    kernel's one-hot tap matmuls run at ``HIGHEST`` precision, and
    interpret mode is exact).
    """
    impl = crop_gather_impl(impl, frames.shape)
    if impl == "ref":
        return ref.crop_gather(frames, boxes, idxs, out_hw=out_hw)
    from repro.kernels import crop_gather as cg
    return cg.crop_gather(frames, boxes, idxs, out_hw=out_hw,
                          interpret=(impl == "interpret"))


def onevsall_scores(x, w, *, impl: str = "ref"):
    if impl in ("ref", "ref_unchunked"):
        from repro.kernels import onevsall as ov
        return ov.onevsall_scores_ref(x, w)
    from repro.kernels import onevsall as ov
    return ov.onevsall_scores(x, w, interpret=(impl == "interpret"))


def onevsall_update(x, y, w, *, eta: float = 0.3, impl: str = "ref"):
    if impl in ("ref", "ref_unchunked"):
        from repro.kernels import onevsall as ov
        return ov.onevsall_update_ref(x, y, w, eta=eta)
    from repro.kernels import onevsall as ov
    return ov.onevsall_update(x, y, w, eta=eta,
                              interpret=(impl == "interpret"))
