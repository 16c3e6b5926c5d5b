"""Jit'd dispatch wrappers over the Pallas kernels and their jnp oracles.

``impl`` selects the implementation:
  * ``"ref"``       pure-jnp oracle (CPU, dry-run lowering, XLA:TPU fallback)
  * ``"pallas"``    compiled Pallas TPU kernel (requires a real TPU)
  * ``"interpret"`` Pallas kernel body executed in interpret mode (CPU tests)
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


def crop_gather(frames, boxes, idxs, *, out_hw, impl: str = "ref"):
    """Compacted crop gather: (F,H,W,C) x (F,N,4) x (3,B) -> (B,oh,ow,C).

    All impls share the bilinear program of ``ref.crop_positions`` and
    ``ref.bilinear_sample``, so ref and interpret outputs are bit-identical
    to gathering from the full shared crop grid (pallas too, where its
    one-hot tap matmuls run at f32 precision).
    """
    if impl in ("ref", "ref_unchunked"):
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
