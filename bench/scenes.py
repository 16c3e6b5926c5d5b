"""Camera scenes for the benchmark, made from a seed.

A copy of the repo's procedural traffic generator (``video/synthetic.py``:
``make_chunk`` and its content types), kept here so that the traffic a
cell sends cannot move with the program.  Classes differ only by fine
texture (stripe frequency and pattern), objects move across a smooth
background, and ``texture_drift`` shifts the two frequency bands toward
each other (the paper's data drift, section V).
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, Tuple

import numpy as np

NUM_CLASSES = 8


@dataclass(frozen=True)
class ContentType:
    name: str
    num_objects: Tuple[int, int]      # min/max simultaneous objects
    size: Tuple[float, float]         # min/max object size (frame fraction)
    speed: Tuple[float, float]        # min/max speed (frame fraction / frame)


CONTENT_TYPES: Dict[str, ContentType] = {
    "dashcam": ContentType("dashcam", (2, 4), (0.18, 0.30), (0.010, 0.030)),
    "drone": ContentType("drone", (4, 8), (0.08, 0.14), (0.004, 0.012)),
    "traffic": ContentType("traffic", (5, 10), (0.10, 0.18), (0.003, 0.010)),
}

# two tints over eight classes: colour carries one bit, texture the rest
_CLASS_TINT = np.array([[0.85, 0.55, 0.45], [0.5, 0.65, 0.85]], np.float32)


@dataclass
class Chunk:
    """One camera chunk: HQ frames (T, H, W, 3) in [0, 1] and ground truth."""
    frames: np.ndarray
    gt_boxes: np.ndarray              # (T, M, 4) xyxy in [0, 1]
    gt_labels: np.ndarray             # (T, M) int32, -1 padding
    content: str


def _texture(cls: int, yy, xx, rng: np.random.Generator,
             drift: float = 0.0) -> np.ndarray:
    ptype, fbit = divmod(cls, 2)
    freq = 32.0 + 16.0 * drift if fbit == 0 else 48.0 - 16.0 * drift
    angle = rng.uniform(0, np.pi)
    phase0 = rng.uniform(0, 2 * np.pi)
    u = np.cos(angle) * xx + np.sin(angle) * yy
    v = -np.sin(angle) * xx + np.cos(angle) * yy
    su = np.sin(2 * np.pi * freq * u + phase0)
    sv = np.sin(2 * np.pi * freq * v + phase0)
    if ptype == 0:       # stripes
        pat = su
    elif ptype == 1:     # checkerboard
        pat = su * sv
    elif ptype == 2:     # dots
        pat = np.where((su > 0.3) & (sv > 0.3), 1.0, -0.6)
    else:                # cross-hatch
        pat = 0.5 * (np.sign(su) + np.sign(sv))
    return 0.5 + 0.45 * np.clip(pat, -1.0, 1.0)


def make_chunk(rng: np.random.Generator, content: str = "traffic", *,
               num_frames: int = 8, hw: Tuple[int, int] = (128, 128),
               max_objects: int = 10, texture_drift: float = 0.0) -> Chunk:
    ct = CONTENT_TYPES[content]
    h, w = hw
    yy, xx = np.meshgrid(np.linspace(0, 1, h), np.linspace(0, 1, w),
                         indexing="ij")
    bg_phase = rng.uniform(0, 2 * np.pi, 3)
    bg = np.stack([0.45 + 0.15 * np.sin(2 * np.pi * (0.7 * xx + 0.4 * yy)
                                        + p) for p in bg_phase], -1)
    k = min(int(rng.integers(ct.num_objects[0], ct.num_objects[1] + 1)),
            max_objects)
    cls = rng.integers(0, NUM_CLASSES, k)
    size = rng.uniform(*ct.size, k)
    pos = rng.uniform(0.15, 0.85, (k, 2))
    ang = rng.uniform(0, 2 * np.pi, k)
    spd = rng.uniform(*ct.speed, k)
    vel = np.stack([np.cos(ang), np.sin(ang)], -1) * spd[:, None]

    frames = np.empty((num_frames, h, w, 3), np.float32)
    boxes = np.zeros((num_frames, max_objects, 4), np.float32)
    labels = np.full((num_frames, max_objects), -1, np.int32)
    tex = [_texture(int(c), yy, xx, rng, drift=texture_drift) for c in cls]
    for t in range(num_frames):
        img = bg + rng.normal(0, 0.015, bg.shape).astype(np.float32)
        for i in range(k):
            cxy = pos[i] + vel[i] * t
            cxy = 0.5 + 0.5 * np.sin(np.pi * (cxy - 0.5))   # soft bounce
            half = size[i] / 2
            x1, y1 = cxy[0] - half, cxy[1] - half
            x2, y2 = cxy[0] + half, cxy[1] + half
            mask = (xx >= x1) & (xx <= x2) & (yy >= y1) & (yy <= y2)
            col = tex[i][..., None] * _CLASS_TINT[(int(cls[i]) // 2) % 2]
            img = np.where(mask[..., None], col, img)
            boxes[t, i] = np.clip([x1, y1, x2, y2], 0.0, 1.0)
            labels[t, i] = cls[i]
        frames[t] = np.clip(img, 0.0, 1.0)
    return Chunk(frames, boxes, labels, content)
