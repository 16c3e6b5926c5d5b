"""Pure-jnp oracles for every Pallas kernel.

These are the semantic ground truth: the Pallas kernels in this package must
match them (tests sweep shapes/dtypes with assert_allclose), and they are the
implementation used on CPU and in multi-pod dry-runs (Pallas lowers only on
real TPUs; ``interpret=True`` validates the kernel bodies on CPU).
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

NEG_INF = -1e30


def _softcap(x: jax.Array, cap: Optional[float]) -> jax.Array:
    return x if cap is None else cap * jnp.tanh(x / cap)


# ---------------------------------------------------------------------------
# Flash attention (training / prefill), GQA, causal, optional sliding window
# ---------------------------------------------------------------------------
def flash_attention(
    q: jax.Array,               # (b, s_q, n_q, d)
    k: jax.Array,               # (b, s_kv, n_kv, d)
    v: jax.Array,               # (b, s_kv, n_kv, d)
    *,
    causal: bool = True,
    window: Optional[int] = None,
    softcap: Optional[float] = None,
    q_offset: int = 0,
) -> jax.Array:
    b, s_q, n_q, d = q.shape
    _, s_kv, n_kv, _ = k.shape
    d_v = v.shape[-1]            # may differ from d (MLA)
    groups = n_q // n_kv
    scale = d ** -0.5
    # operands stay in input dtype (bf16 on the serving path) with fp32
    # accumulation — the Pallas kernel's dataflow; no fp32 KV copies in HBM
    qf = q.reshape(b, s_q, n_kv, groups, d)
    logits = jnp.einsum("bqhgd,bkhd->bhgqk", qf, k,
                        preferred_element_type=jnp.float32) * scale
    logits = _softcap(logits, softcap)
    q_pos = jnp.arange(s_q) + q_offset
    k_pos = jnp.arange(s_kv)
    mask = jnp.ones((s_q, s_kv), dtype=bool)
    if causal:
        mask &= q_pos[:, None] >= k_pos[None, :]
    if window is not None:
        mask &= q_pos[:, None] - k_pos[None, :] < window
    logits = jnp.where(mask[None, None, None], logits, NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bhgqk,bkhd->bqhgd", probs.astype(q.dtype), v,
                     preferred_element_type=jnp.float32)
    return out.reshape(b, s_q, n_q, d_v).astype(q.dtype)


def flash_attention_chunked(
    q: jax.Array,               # (b, s_q, n_q, d)
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    window: Optional[int] = None,
    softcap: Optional[float] = None,
    q_offset=0,
    chunk: int = 512,
) -> jax.Array:
    """Memory-bounded oracle: sequential scan over q chunks, so the live
    score buffer is (b, h, chunk, s_kv) instead of (b, h, s_q, s_kv).  This
    is the XLA-level flash-attention analog used for dry-run lowering (the
    Pallas kernel fills the same role on real TPUs)."""
    b, s_q, n_q, d = q.shape
    s_kv = k.shape[1]
    if s_q <= chunk:
        return flash_attention(q, k, v, causal=causal, window=window,
                               softcap=softcap, q_offset=q_offset)
    pad = (-s_q) % chunk
    if pad:
        q = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0)))
    nc = (s_q + pad) // chunk
    qc = jnp.moveaxis(q.reshape(b, nc, chunk, n_q, d), 1, 0)

    if window is not None and causal:
        # sliding-window: each q chunk only sees kv in
        # [chunk_end - window - chunk, chunk_end) — slice instead of masking
        # the full sequence (drops score traffic by ~s_kv/(window+chunk))
        span = min(window + chunk, s_kv)

        def one_w(carry, xs):
            qi, idx = xs
            off = jnp.asarray(q_offset) + idx * chunk
            start = jnp.clip(off + chunk - span, 0, s_kv - span)
            ks = jax.lax.dynamic_slice_in_dim(k, start, span, axis=1)
            vs = jax.lax.dynamic_slice_in_dim(v, start, span, axis=1)
            # positions relative to the slice
            out = flash_attention_rel(qi, ks, vs, q_pos0=off,
                                      k_pos0=start, window=window,
                                      softcap=softcap)
            return carry, out

        _, outs = jax.lax.scan(one_w, 0, (qc, jnp.arange(nc)))
        out = jnp.moveaxis(outs, 0, 1).reshape(b, s_q + pad, n_q, -1)
        return out[:, :s_q]

    def one(carry, xs):
        qi, idx = xs
        out = flash_attention(qi, k, v, causal=causal, window=window,
                              softcap=softcap,
                              q_offset=q_offset + idx * chunk)
        return carry, out

    _, outs = jax.lax.scan(one, 0, (qc, jnp.arange(nc)))
    out = jnp.moveaxis(outs, 0, 1).reshape(b, s_q + pad, n_q, -1)
    return out[:, :s_q]


def flash_attention_rel(q, k, v, *, q_pos0, k_pos0, window, softcap):
    """Causal+windowed attention where q/k global positions start at the
    (possibly traced) offsets q_pos0 / k_pos0."""
    b, s_q, n_q, d = q.shape
    _, s_kv, n_kv, _ = k.shape
    d_v = v.shape[-1]
    groups = n_q // n_kv
    scale = d ** -0.5
    qf = q.reshape(b, s_q, n_kv, groups, d)
    logits = jnp.einsum("bqhgd,bkhd->bhgqk", qf, k,
                        preferred_element_type=jnp.float32) * scale
    logits = _softcap(logits, softcap)
    q_pos = jnp.arange(s_q) + q_pos0
    k_pos = jnp.arange(s_kv) + k_pos0
    mask = (q_pos[:, None] >= k_pos[None, :])
    mask &= (q_pos[:, None] - k_pos[None, :]) < window
    logits = jnp.where(mask[None, None, None], logits, NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bhgqk,bkhd->bqhgd", probs.astype(q.dtype), v,
                     preferred_element_type=jnp.float32)
    return out.reshape(b, s_q, n_q, d_v).astype(q.dtype)


# ---------------------------------------------------------------------------
# Decode attention: one query token vs a (possibly partially filled) KV cache
# ---------------------------------------------------------------------------
def decode_attention(
    q: jax.Array,               # (b, n_q, d)      -- single new token
    k_cache: jax.Array,         # (b, S, n_kv, d)
    v_cache: jax.Array,         # (b, S, n_kv, d)
    cache_len: jax.Array,       # scalar or (b,): number of valid cache slots
    *,
    window: Optional[int] = None,
    softcap: Optional[float] = None,
) -> jax.Array:
    b, n_q, d = q.shape
    _, S, n_kv, _ = k_cache.shape
    d_v = v_cache.shape[-1]
    groups = n_q // n_kv
    scale = d ** -0.5
    qf = q.reshape(b, n_kv, groups, d)
    logits = jnp.einsum("bhgd,bkhd->bhgk", qf, k_cache,
                        preferred_element_type=jnp.float32) * scale
    logits = _softcap(logits, softcap)
    pos = jnp.arange(S)
    clen = jnp.asarray(cache_len)
    clen = clen[:, None] if clen.ndim == 1 else clen[None, None]
    valid = pos[None, :] < clen                       # (b|1, S)
    if window is not None:
        valid &= pos[None, :] >= (clen - window)
    valid = jnp.broadcast_to(valid, (b, S))
    logits = jnp.where(valid[:, None, None, :], logits, NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bhgk,bkhd->bhgd", probs.astype(q.dtype), v_cache,
                     preferred_element_type=jnp.float32)
    return out.reshape(b, n_q, d_v).astype(q.dtype)


# ---------------------------------------------------------------------------
# Mamba2 SSD (state-space duality) chunked scan
# ---------------------------------------------------------------------------
def _segsum(x: jax.Array) -> jax.Array:
    """Stable segment-sum: out[..., i, j] = sum_{j < k <= i} x[..., k]."""
    T = x.shape[-1]
    cum = jnp.cumsum(x, axis=-1)
    out = cum[..., :, None] - cum[..., None, :]
    mask = jnp.tril(jnp.ones((T, T), dtype=bool), k=0)
    return jnp.where(mask, out, -jnp.inf)


def ssd_scan(
    x: jax.Array,               # (b, s, h, p)   head inputs
    dt: jax.Array,              # (b, s, h)      softplus'd step sizes
    A: jax.Array,               # (h,)           negative decay rates
    B: jax.Array,               # (b, s, n)      input maps (n_groups=1)
    C: jax.Array,               # (b, s, n)      output maps
    *,
    chunk: int = 64,
    initial_state: Optional[jax.Array] = None,   # (b, h, p, n)
) -> tuple[jax.Array, jax.Array]:
    """Returns (y (b,s,h,p), final_state (b,h,p,n))."""
    b, s, h, p = x.shape
    n = B.shape[-1]
    dtype = x.dtype
    if s % chunk != 0:
        pad = chunk - s % chunk
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
        B = jnp.pad(B, ((0, 0), (0, pad), (0, 0)))
        C = jnp.pad(C, ((0, 0), (0, pad), (0, 0)))
    s_pad = x.shape[1]
    c = s_pad // chunk

    xf = x.astype(jnp.float32).reshape(b, c, chunk, h, p)
    dtf = dt.astype(jnp.float32).reshape(b, c, chunk, h)
    Bf = B.astype(jnp.float32).reshape(b, c, chunk, n)
    Cf = C.astype(jnp.float32).reshape(b, c, chunk, n)
    Af = A.astype(jnp.float32)

    dA = dtf * Af[None, None, None, :]               # (b,c,q,h)
    dA = jnp.moveaxis(dA, -1, 2)                     # (b,c,h,q)

    # 1. intra-chunk (diagonal blocks)
    L = jnp.exp(_segsum(dA))                         # (b,c,h,q,q)
    scores = jnp.einsum("bcqn,bckn->bcqk", Cf, Bf)   # (b,c,q,k)
    dtx = xf * dtf[..., None]                        # (b,c,k,h,p)
    y_diag = jnp.einsum("bcqk,bchqk,bckhp->bcqhp", scores, L, dtx)

    # 2. chunk states: decay from position k to end of chunk = exp(sum_{j>k} dA_j)
    cums = jnp.cumsum(dA, axis=-1)                   # (b,c,h,q)
    decay_states = jnp.exp(cums[..., -1:] - cums)    # (b,c,h,q)
    states = jnp.einsum("bckn,bchk,bckhp->bchpn", Bf, decay_states, dtx)

    # 3. inter-chunk recurrence over chunk states
    chunk_decay = jnp.exp(cums[..., -1])             # (b,c,h)
    init = (jnp.zeros((b, h, p, n), jnp.float32) if initial_state is None
            else initial_state.astype(jnp.float32))

    def step(carry, inp):
        st, dec = inp                                # (b,h,p,n), (b,h)
        new = carry * dec[..., None, None] + st
        return new, carry                            # emit state *entering* chunk

    xs = (jnp.moveaxis(states, 1, 0), jnp.moveaxis(chunk_decay, 1, 0))
    final, prev_states = jax.lax.scan(step, init, xs)
    prev_states = jnp.moveaxis(prev_states, 0, 1)    # (b,c,h,p,n)

    # 4. inter-chunk output: y_off[q] = C_q . (decay_in(q) * prev_state)
    decay_in = jnp.exp(cums)                         # (b,c,h,q)
    y_off = jnp.einsum("bcqn,bchq,bchpn->bcqhp", Cf, decay_in, prev_states)

    y = (y_diag + y_off).reshape(b, s_pad, h, p)[:, :s]
    return y.astype(dtype), final.astype(jnp.float32)


def ssd_step(
    x: jax.Array,               # (b, h, p)
    dt: jax.Array,              # (b, h)
    A: jax.Array,               # (h,)
    B: jax.Array,               # (b, n)
    C: jax.Array,               # (b, n)
    state: jax.Array,           # (b, h, p, n)
) -> tuple[jax.Array, jax.Array]:
    """Single recurrent step (decode)."""
    xf, dtf = x.astype(jnp.float32), dt.astype(jnp.float32)
    Bf, Cf = B.astype(jnp.float32), C.astype(jnp.float32)
    dA = jnp.exp(dtf * A[None, :])                   # (b,h)
    upd = jnp.einsum("bhp,bn->bhpn", xf * dtf[..., None], Bf)
    new_state = state * dA[..., None, None] + upd
    y = jnp.einsum("bhpn,bn->bhp", new_state, Cf)
    return y.astype(x.dtype), new_state


# ---------------------------------------------------------------------------
# Pairwise IoU + region filter mask (the paper's §IV.B filter hot spot)
# ---------------------------------------------------------------------------
def iou_matrix(boxes_a: jax.Array, boxes_b: jax.Array) -> jax.Array:
    """boxes: (..., N, 4) as (x1, y1, x2, y2). Returns (..., N, M)."""
    a = boxes_a.astype(jnp.float32)
    b = boxes_b.astype(jnp.float32)
    ax1, ay1, ax2, ay2 = [a[..., :, None, i] for i in range(4)]
    bx1, by1, bx2, by2 = [b[..., None, :, i] for i in range(4)]
    iw = jnp.maximum(jnp.minimum(ax2, bx2) - jnp.maximum(ax1, bx1), 0.0)
    ih = jnp.maximum(jnp.minimum(ay2, by2) - jnp.maximum(ay1, by1), 0.0)
    inter = iw * ih
    area_a = jnp.maximum(ax2 - ax1, 0.0) * jnp.maximum(ay2 - ay1, 0.0)
    area_b = jnp.maximum(bx2 - bx1, 0.0) * jnp.maximum(by2 - by1, 0.0)
    union = area_a + area_b - inter
    return inter / jnp.maximum(union, 1e-9)


def nms_mask(boxes: jax.Array, scores: jax.Array, valid: jax.Array,
             iou_threshold: float = 0.45) -> jax.Array:
    """Greedy non-maximum suppression; fixed-shape (returns keep mask).

    Each round keeps the alive candidate with the highest score (the lowest
    slot among equal scores) and suppresses the alive ones whose IoU with
    it reaches the threshold; a slot scored ``NEG_INF`` or lower is never
    kept.  The loop ends once nothing is alive, so it runs one round per
    kept box (under ``vmap``: as many as the frame that keeps the most),
    and a round computes only the kept box's IoU row, never an (N, N)
    matrix."""
    n = boxes.shape[0]
    neg = jnp.asarray(NEG_INF, scores.dtype)
    slots = jnp.arange(n)

    def body(st):
        keep, alive = st
        idx = jnp.argmax(jnp.where(alive, scores, neg))
        iou = iou_matrix(boxes[idx][None], boxes)[0]
        keep = keep | (slots == idx)
        alive = alive & ~((iou >= iou_threshold) | (slots == idx))
        return keep, alive

    keep, _ = jax.lax.while_loop(lambda st: jnp.any(st[1]), body,
                                 (jnp.zeros(n, bool), valid & (scores > neg)))
    return keep


def region_filter_mask(
    proposals: jax.Array,       # (N, 4)
    prop_valid: jax.Array,      # (N,) bool
    accepted: jax.Array,        # (M, 4)
    acc_valid: jax.Array,       # (M,) bool
    loc_scores: jax.Array,      # (N,)
    *,
    theta_loc: float,
    theta_iou: float,
    theta_back: float,
    frame_area: float = 1.0,
) -> jax.Array:
    """The paper's three-stage filter as one fused mask computation."""
    keep = prop_valid & (loc_scores >= theta_loc)
    iou = iou_matrix(proposals, accepted)            # (N, M)
    iou = jnp.where(acc_valid[None, :], iou, 0.0)
    keep &= jnp.max(iou, axis=-1, initial=0.0) < theta_iou
    w = jnp.maximum(proposals[:, 2] - proposals[:, 0], 0.0)
    h = jnp.maximum(proposals[:, 3] - proposals[:, 1], 0.0)
    keep &= (w * h / frame_area) <= theta_back
    return keep


# ---------------------------------------------------------------------------
# Bilinear crop gather (the compacted classify path's crop stage)
# ---------------------------------------------------------------------------
@functools.lru_cache(maxsize=None)
def _crop_lin(n: int) -> np.ndarray:
    """The [0, 1] sample grid as a host-computed float32 literal.

    ``jnp.linspace`` is NOT used on purpose: under jit its internal
    arithmetic is constant-folded by XLA with different rounding than the
    eager op-by-op path, so two programs embedding the "same" linspace can
    disagree by an ulp — enough to flip a floor() and break the bitwise
    contract between the crop kernel and the shared-grid path.  A numpy
    literal is one fixed bit pattern everywhere.  The cache holds numpy
    (never jnp: a device array created under a jit trace would leak its
    tracer into later calls)."""
    return np.linspace(0.0, 1.0, n, dtype=np.float32)


# XLA:CPU contracts a multiply that feeds an add into one FMA wherever the
# two share a fusion (``optimization_barrier`` does not stop it), so two
# programs that fuse the same formula differently round it differently.
# Adding a zero that is only known at run time to every product that feeds
# an add pins the rounding: contracted or not, ``a * b + 0`` is the rounded
# product, and an add of two such sums has no multiply left to contract.
def crop_positions(boxes: jax.Array,          # (..., 4) xyxy in [0, 1]
                   h_img: int, w_img: int,
                   lin_y: jax.Array,        # (oh,) sample grid
                   lin_x: jax.Array         # (ow,)
                   ) -> Tuple[jax.Array, jax.Array]:
    """Sample rows ``ys`` (..., oh) and columns ``xs`` (..., ow) of boxes.

    The grid is separable: a crop's row positions depend only on its
    output row and its column positions only on its output column."""
    x1, y1 = boxes[..., 0:1], boxes[..., 1:2]
    x2, y2 = boxes[..., 2:3], boxes[..., 3:4]
    z = 0.0 * y1                             # the run-time zero (see above)
    ys = (y1 * (h_img - 1) + z) + (((y2 - y1) * (h_img - 1)) * lin_y + z)
    xs = (x1 * (w_img - 1) + z) + (((x2 - x1) * (w_img - 1)) * lin_x + z)
    return ys, xs


def bilinear_sample(ys: jax.Array, xs: jax.Array, fetch) -> jax.Array:
    """Blend the four taps around every (ys, xs) sample point.

    ``ys`` and ``xs`` broadcast against each other; ``fetch(yi, xi)``
    returns the frame values at integer rows ``yi`` and columns ``xi``, and
    0 where a tap falls off the frame (``mode='constant'``).  The crop
    oracle fetches with a gather and the Pallas kernel with one-hot
    selection matmuls; both blend here."""
    y_lo_f = jnp.floor(ys)
    x_lo_f = jnp.floor(xs)
    wy_hi = ys - y_lo_f
    wy_lo = 1 - wy_hi
    wx_hi = xs - x_lo_f
    wx_lo = 1 - wx_hi
    y_lo = y_lo_f.astype(jnp.int32)
    x_lo = x_lo_f.astype(jnp.int32)
    z = 0.0 * ys                             # the run-time zero (see above)
    t00 = (wy_lo * wx_lo) * fetch(y_lo, x_lo) + z
    t01 = (wy_lo * wx_hi) * fetch(y_lo, x_lo + 1) + z
    t10 = (wy_hi * wx_lo) * fetch(y_lo + 1, x_lo) + z
    t11 = (wy_hi * wx_hi) * fetch(y_lo + 1, x_lo + 1) + z
    return ((t00 + t01) + t10) + t11


def bilinear_crops(frames: jax.Array,    # (F, H, W, C)
                   fmap: jax.Array,      # (K,) int32 in-range frame index
                   boxes: jax.Array,     # (K, 4) xyxy in [0, 1]
                   out_hw: Tuple[int, int]) -> jax.Array:
    """Bilinear-resample K boxes to ``out_hw``; returns (K, oh, ow, C).

    This is THE crop program of the shared-grid path (``crop_batch``) and
    the compacted gather oracle (``crop_gather``); the Pallas kernel runs
    the same :func:`crop_positions` and :func:`bilinear_sample`, so every
    path computes bit-identical pixels where its tap fetch is exact.  The
    sample grid is a baked float32 literal (see ``_crop_lin``).  Math is
    bit-identical to ``jax.scipy.ndimage.map_coordinates(order=1,
    mode='constant')`` evaluated eagerly."""
    _, h_img, w_img, _ = frames.shape
    oh, ow = out_hw
    ys, xs = crop_positions(boxes, h_img, w_img, jnp.asarray(_crop_lin(oh)),
                            jnp.asarray(_crop_lin(ow)))
    fk = fmap[:, None, None]

    def fetch(yi, xi):               # yi (K, oh, 1, 1), xi (K, 1, ow, 1)
        yi, xi = yi[..., 0], xi[..., 0]
        valid = (yi >= 0) & (yi < h_img) & (xi >= 0) & (xi < w_img)
        px = frames[fk, jnp.clip(yi, 0, h_img - 1), jnp.clip(xi, 0, w_img - 1)]
        return jnp.where(valid[..., None], px, 0.0)

    return bilinear_sample(ys[:, :, None, None], xs[:, None, :, None], fetch)


@functools.partial(jax.jit, static_argnames=("out_hw",))
def crop_gather(frames: jax.Array,       # (F, H, W, C) HQ frames
                boxes: jax.Array,        # (F, N, 4) proposal boxes
                idxs: jax.Array,         # (>=2, B) compaction indices
                *, out_hw: Tuple[int, int]) -> jax.Array:
    """The compacted crop gather: (B, oh, ow, C).  The served crop off
    the TPU (and on it where the kernel's frame blocks would not fit
    VMEM; ``ops.crop_gather_impl``), and the Pallas kernel's oracle.

    ``idxs[0]/idxs[1]`` are the flush's (frame, region) gather rows; pad
    rows carry the out-of-bounds frame index F and clip to the last frame
    (harmless garbage crop — the classify path's scatter drops them), the
    same semantics as gathering from the full crop grid with jnp's clamping
    indexing.

    Jitted here (not at the call site) because the bitwise contract with
    the shared-grid path holds for the *jitted* lowering of this program —
    an eager evaluation rounds each op independently and can drift by an
    ulp."""
    f, n = boxes.shape[0], boxes.shape[1]
    fidx = jnp.clip(idxs[0], 0, f - 1)
    ridx = jnp.clip(idxs[1], 0, n - 1)
    return bilinear_crops(frames, fidx, boxes[fidx, ridx], out_hw)


def flash_attention_windowed_unrolled(q, k, v, *, window, softcap=None,
                                      q_offset=0, chunk: int = 512):
    """Python-unrolled windowed attention: identical math to the windowed
    chunked scan, but with the chunk loop unrolled so XLA's cost_analysis
    counts every chunk (dry-run probes) — this is also the work profile of
    the Pallas kernel, which skips out-of-window KV blocks."""
    b, s_q, n_q, d = q.shape
    s_kv = k.shape[1]
    if s_q <= chunk:
        return flash_attention(q, k, v, causal=True, window=window,
                               softcap=softcap, q_offset=q_offset)
    pad = (-s_q) % chunk
    if pad:
        q = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0)))
    nc = (s_q + pad) // chunk
    span = min(window + chunk, s_kv)
    outs = []
    for idx in range(nc):
        off = jnp.asarray(q_offset) + idx * chunk
        start = jnp.clip(off + chunk - span, 0, s_kv - span)
        ks = jax.lax.dynamic_slice_in_dim(k, start, span, axis=1)
        vs = jax.lax.dynamic_slice_in_dim(v, start, span, axis=1)
        outs.append(flash_attention_rel(
            q[:, idx * chunk:(idx + 1) * chunk], ks, vs, q_pos0=off,
            k_pos0=start, window=window, softcap=softcap))
    out = jnp.concatenate(outs, axis=1)
    return out[:, :s_q]
