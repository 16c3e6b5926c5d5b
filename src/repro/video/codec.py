"""Quality control: the JAX codec behind F_v(r, q) (paper Eq. 2).

The paper adjusts video quality with FFmpeg (resolution scale + H.264 QP).
We reproduce the same byte/quality trade-off with a real transform codec:

  encode(frames, r, q):
    1. downscale by resolution factor r  (bilinear)
    2. 8x8 block DCT per channel
    3. uniform quantization with H.264-style step  2^((q - 4) / 6)
    4. byte estimate from an exp-Golomb-style code-length model over the
       quantized coefficients (derived from data, not hard-coded)
    5. decode = dequantize -> inverse DCT -> upscale back

The protocol layer consumes only (frames_out, bytes) — exactly the F_v(r, q)
abstraction of Eq. 2.
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp
import numpy as np

BLOCK = 8


class EncodedChunk(NamedTuple):
    frames: jax.Array           # decoded (degraded) frames (T, H, W, 3)
    nbytes: jax.Array           # scalar float: estimated compressed size
    r: float
    q: int


def _transform(spec: str, *operands) -> jax.Array:
    """A block DCT (or its inverse) at full f32 precision.  The quantizer
    rounds its output, so the codec's decisions must not depend on the
    backend's default matmul precision: a TPU's one bf16 pass moves
    coefficients across rounding boundaries and changes the decoded frames
    by whole quantization steps."""
    return jnp.einsum(spec, *operands, precision=jax.lax.Precision.HIGHEST)


@functools.lru_cache(maxsize=None)
def _dct_matrix(n: int = BLOCK) -> np.ndarray:
    k = np.arange(n)
    mat = np.sqrt(2.0 / n) * np.cos(np.pi * (2 * k[None, :] + 1)
                                    * k[:, None] / (2 * n))
    mat[0] /= np.sqrt(2.0)
    return mat.astype(np.float32)


def qp_to_step(q: jax.Array | float) -> jax.Array:
    """H.264-style quantization step (doubles every 6 QP)."""
    return jnp.asarray(2.0 ** ((jnp.asarray(q, jnp.float32) - 4.0) / 6.0)) / 64.0


def _blockify(x: jax.Array) -> jax.Array:
    """(T, H, W, C) -> (T, H/8, W/8, C, 8, 8)."""
    t, h, w, c = x.shape
    x = x.reshape(t, h // BLOCK, BLOCK, w // BLOCK, BLOCK, c)
    return x.transpose(0, 1, 3, 5, 2, 4)


def _unblockify(x: jax.Array) -> jax.Array:
    t, hb, wb, c, _, _ = x.shape
    x = x.transpose(0, 1, 4, 2, 5, 3)
    return x.reshape(t, hb * BLOCK, wb * BLOCK, c)


def _pad_to_block(x: jax.Array) -> Tuple[jax.Array, Tuple[int, int]]:
    t, h, w, c = x.shape
    ph = (-h) % BLOCK
    pw = (-w) % BLOCK
    return jnp.pad(x, ((0, 0), (0, ph), (0, pw), (0, 0)), "edge"), (h, w)


def code_length_bits(coef: jax.Array) -> jax.Array:
    """Exp-Golomb-style bit cost of integer coefficients (byte model)."""
    a = jnp.abs(coef)
    bits = jnp.where(a > 0, 2.0 * jnp.ceil(jnp.log2(a + 1.0)) + 1.0, 0.0)
    # run-length proxy for zeros: ~0.06 bits per zero coefficient
    bits = bits + jnp.where(a == 0, 0.0625, 0.0)
    return jnp.sum(bits)


@functools.partial(jax.jit, static_argnames=("r",))
def encode(frames: jax.Array, r: float, q: jax.Array | int) -> EncodedChunk:
    """frames (T, H, W, 3) float in [0,1]; r in (0,1]; q = QP (0..51)."""
    t, h0, w0, c = frames.shape
    if r != 1.0:
        hs, ws = max(BLOCK, int(h0 * r)), max(BLOCK, int(w0 * r))
        small = jax.image.resize(frames, (t, hs, ws, c), "linear")
    else:
        small = frames
    small, (h, w) = _pad_to_block(small)

    dct = jnp.asarray(_dct_matrix())
    blocks = _blockify(small - 0.5)
    coef = _transform("ij,...jk,lk->...il", dct, blocks, dct)
    step = qp_to_step(q)
    quant = jnp.round(coef / step)

    nbits = code_length_bits(quant)
    # decode side
    deq = quant * step
    rec = _transform("ji,...jk,kl->...il", dct, deq, dct) + 0.5
    rec = _unblockify(rec)[:, :h, :w]
    if r != 1.0:
        rec = jax.image.resize(rec, (t, h0, w0, c), "linear")
    rec = jnp.clip(rec, 0.0, 1.0)
    return EncodedChunk(rec, nbits / 8.0, r, int(q) if not hasattr(q, "shape")
                        else q)


@functools.partial(jax.jit, static_argnames=("r",))
def encode_inter(frames: jax.Array, r: float, q) -> EncodedChunk:
    """Closed-loop inter-frame (P-frame) coding: each frame encodes the
    DCT-quantized residual against the previous *reconstructed* frame, so
    static content costs ~nothing — the H.264 temporal-compression behavior
    the intra-only ``encode`` misses.  Same (frames, bytes) contract."""
    t, h0, w0, c = frames.shape
    if r != 1.0:
        hs, ws = max(BLOCK, int(h0 * r)), max(BLOCK, int(w0 * r))
        small = jax.image.resize(frames, (t, hs, ws, c), "linear")
    else:
        small = frames
    small, (h, w) = _pad_to_block(small)
    dct = jnp.asarray(_dct_matrix())
    step = qp_to_step(q)

    def one(prev_rec, frame):
        resid = frame - prev_rec
        blocks = _blockify(resid[None])
        coef = _transform("ij,...jk,lk->...il", dct, blocks, dct)
        quant = jnp.round(coef / step)
        bits = code_length_bits(quant)
        rec_res = _transform("ji,...jk,kl->...il", dct, quant * step, dct)
        rec = jnp.clip(prev_rec + _unblockify(rec_res)[0], 0.0, 1.0)
        return rec, (rec, bits)

    gray = jnp.full_like(small[0], 0.5)       # intra-frame = residual vs gray
    _, (recs, bits) = jax.lax.scan(one, gray, small)
    recs = recs[:, :h, :w]
    if r != 1.0:
        recs = jax.image.resize(recs, (t, h0, w0, c), "linear")
    return EncodedChunk(jnp.clip(recs, 0.0, 1.0), jnp.sum(bits) / 8.0, r,
                        int(q) if not hasattr(q, "shape") else q)


def raw_bytes(frames: jax.Array) -> float:
    """Uncompressed size (the MPEG/original-video bandwidth reference)."""
    return float(np.prod(frames.shape))  # 1 byte/channel-pixel


def psnr(a: jax.Array, b: jax.Array) -> jax.Array:
    mse = jnp.mean((a - b) ** 2)
    return -10.0 * jnp.log10(jnp.maximum(mse, 1e-10))
