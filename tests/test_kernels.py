"""Pallas kernel validation: shape/dtype sweeps, interpret mode vs the
pure-jnp oracles in repro.kernels.ref."""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import regions as reg
from repro.kernels import ops, ref
from repro.kernels.decode_attention import decode_attention
from repro.kernels.flash_attention import flash_attention
from repro.kernels import iou_filter as ik
from repro.kernels.ssd_scan import ssd_scan

KEY = jax.random.PRNGKey(7)


def _tol(dtype):
    return 2e-2 if dtype == jnp.bfloat16 else 3e-5


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("b,sq,skv,nq,nkv,d", [
    (2, 128, 128, 4, 2, 64),
    (1, 192, 192, 8, 8, 128),
    (2, 64, 256, 4, 1, 64),
    (1, 96, 96, 6, 3, 32),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_sweep(b, sq, skv, nq, nkv, d, dtype):
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (b, sq, nq, d), dtype)
    k = jax.random.normal(ks[1], (b, skv, nkv, d), dtype)
    v = jax.random.normal(ks[2], (b, skv, nkv, d), dtype)
    want = ref.flash_attention(q, k, v, causal=True)
    got = flash_attention(q, k, v, causal=True, bq=64, bk=64, interpret=True)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               atol=_tol(dtype), rtol=_tol(dtype))


@pytest.mark.parametrize("window,softcap,q_offset,causal", [
    (64, None, 0, True),
    (None, 30.0, 0, True),
    (None, None, 128, True),
    (None, None, 0, False),
])
def test_flash_attention_variants(window, softcap, q_offset, causal):
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (2, 128, 4, 64))
    k = jax.random.normal(ks[1], (2, 256, 2, 64))
    v = jax.random.normal(ks[2], (2, 256, 2, 64))
    want = ref.flash_attention(q, k, v, causal=causal, window=window,
                               softcap=softcap, q_offset=q_offset)
    got = flash_attention(q, k, v, causal=causal, window=window,
                          softcap=softcap, q_offset=q_offset,
                          bq=64, bk=64, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=3e-5)


def test_flash_chunked_matches_plain():
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (1, 300, 4, 32))
    k = jax.random.normal(ks[1], (1, 300, 4, 32))
    v = jax.random.normal(ks[2], (1, 300, 4, 32))
    want = ref.flash_attention(q, k, v, causal=True)
    got = ref.flash_attention_chunked(q, k, v, causal=True, chunk=64)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)


# ---------------------------------------------------------------------------
# decode attention
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("b,S,nq,nkv,d,clen", [
    (2, 256, 8, 2, 64, 100),
    (1, 512, 4, 4, 128, 512),
    (3, 300, 6, 3, 32, None),   # per-row lengths
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_decode_attention_sweep(b, S, nq, nkv, d, clen, dtype):
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (b, nq, d), dtype)
    kc = jax.random.normal(ks[1], (b, S, nkv, d), dtype)
    vc = jax.random.normal(ks[2], (b, S, nkv, d), dtype)
    cl = (jnp.asarray([10, S // 2, S])[:b] if clen is None
          else jnp.asarray(clen, jnp.int32))
    want = ref.decode_attention(q, kc, vc, cl)
    got = decode_attention(q, kc, vc, cl, bk=128, interpret=True)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               atol=_tol(dtype), rtol=_tol(dtype))


def test_decode_attention_window():
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (2, 8, 64))
    kc = jax.random.normal(ks[1], (2, 1024, 1, 64))
    vc = jax.random.normal(ks[2], (2, 1024, 1, 64))
    cl = jnp.asarray(700, jnp.int32)
    want = ref.decode_attention(q, kc, vc, cl, window=256)
    got = decode_attention(q, kc, vc, cl, window=256, bk=256, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=3e-5)


# ---------------------------------------------------------------------------
# SSD scan
# ---------------------------------------------------------------------------
def _naive_ssd(x, dt, A, B, C, init=None):
    b, s, h, p = x.shape
    n = B.shape[-1]
    st = np.zeros((b, h, p, n)) if init is None else np.array(init)
    ys = []
    for t in range(s):
        dA = np.exp(np.asarray(dt[:, t]) * np.asarray(A))
        st = (st * dA[..., None, None]
              + np.einsum("bhp,bn->bhpn",
                          np.asarray(x[:, t]) * np.asarray(dt[:, t])[..., None],
                          np.asarray(B[:, t])))
        ys.append(np.einsum("bhpn,bn->bhp", st, np.asarray(C[:, t])))
    return np.stack(ys, 1), st


@pytest.mark.parametrize("b,s,h,p,n,chunk", [
    (2, 64, 3, 8, 16, 16),
    (1, 100, 2, 16, 8, 32),   # non-multiple seq
    (2, 37, 4, 4, 4, 16),
])
def test_ssd_scan_vs_naive_and_kernel(b, s, h, p, n, chunk):
    ks = jax.random.split(KEY, 6)
    x = jax.random.normal(ks[0], (b, s, h, p))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, s, h)))
    A = -jnp.exp(jax.random.normal(ks[2], (h,)))
    B = jax.random.normal(ks[3], (b, s, n)) * 0.5
    C = jax.random.normal(ks[4], (b, s, n)) * 0.5
    init = jax.random.normal(ks[5], (b, h, p, n)) * 0.1

    y_naive, st_naive = _naive_ssd(x, dt, A, B, C, init)
    y_ref, st_ref = ref.ssd_scan(x, dt, A, B, C, chunk=chunk,
                                 initial_state=init)
    y_k, st_k = ssd_scan(x, dt, A, B, C, chunk=chunk, initial_state=init,
                         interpret=True)
    np.testing.assert_allclose(np.asarray(y_ref), y_naive, atol=1e-3)
    np.testing.assert_allclose(np.asarray(st_ref), st_naive, atol=1e-3)
    np.testing.assert_allclose(np.asarray(y_k), np.asarray(y_ref), atol=1e-4)
    np.testing.assert_allclose(np.asarray(st_k), np.asarray(st_ref),
                               atol=1e-4)


def test_ssd_step_consistent_with_scan():
    b, s, h, p, n = 1, 8, 2, 4, 8
    ks = jax.random.split(KEY, 5)
    x = jax.random.normal(ks[0], (b, s, h, p))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, s, h)))
    A = -jnp.exp(jax.random.normal(ks[2], (h,)))
    B = jax.random.normal(ks[3], (b, s, n)) * 0.5
    C = jax.random.normal(ks[4], (b, s, n)) * 0.5
    y_scan, final = ref.ssd_scan(x, dt, A, B, C, chunk=4)
    st = jnp.zeros((b, h, p, n))
    ys = []
    for t in range(s):
        y, st = ref.ssd_step(x[:, t], dt[:, t], A, B[:, t], C[:, t], st)
        ys.append(y)
    np.testing.assert_allclose(np.asarray(jnp.stack(ys, 1)),
                               np.asarray(y_scan), atol=1e-4)
    np.testing.assert_allclose(np.asarray(st), np.asarray(final), atol=1e-4)


# ---------------------------------------------------------------------------
# IoU / region filter
# ---------------------------------------------------------------------------
def _rand_boxes(key, n):
    pts = jax.random.uniform(key, (n, 2, 2))
    lo = jnp.min(pts, axis=1)
    hi = jnp.max(pts, axis=1)
    return jnp.concatenate([lo, hi], axis=-1)


@pytest.mark.parametrize("n,m", [(64, 32), (200, 100), (13, 7), (256, 256)])
def test_iou_kernel_sweep(n, m):
    ka, kb = jax.random.split(KEY)
    a, b = _rand_boxes(ka, n), _rand_boxes(kb, m)
    want = ref.iou_matrix(a, b)
    got = ik.iou_matrix(a, b, bn=64, bm=64, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-6)


@pytest.mark.parametrize("n,m", [(64, 32), (130, 70)])
def test_region_filter_kernel(n, m):
    ka, kb = jax.random.split(KEY)
    a, b = _rand_boxes(ka, n), _rand_boxes(kb, m)
    pv = jax.random.uniform(ka, (n,)) > 0.2
    av = jax.random.uniform(kb, (m,)) > 0.2
    loc = jax.random.uniform(kb, (n,))
    kw = dict(theta_loc=0.4, theta_iou=0.3, theta_back=0.5)
    want = ref.region_filter_mask(a, pv, b, av, loc, **kw)
    got = ik.region_filter_mask(a, pv, b, av, loc, bn=64, bm=64,
                                interpret=True, **kw)
    assert bool(jnp.all(want == got))


@pytest.mark.parametrize("f,n,m", [(1, 64, 64), (3, 64, 32), (4, 130, 70)])
def test_region_filter_kernel_batch(f, n, m):
    # the whole-flush (F, N) grid filter fused into detect_split dispatch
    # must match the vmapped per-frame reference bit-for-bit
    ka, kb = jax.random.split(KEY)
    a = jnp.stack([_rand_boxes(jax.random.fold_in(ka, i), n)
                   for i in range(f)])
    b = jnp.stack([_rand_boxes(jax.random.fold_in(kb, i), m)
                   for i in range(f)])
    pv = jax.random.uniform(ka, (f, n)) > 0.2
    av = jax.random.uniform(kb, (f, m)) > 0.2
    loc = jax.random.uniform(kb, (f, n))
    kw = dict(theta_loc=0.4, theta_iou=0.3, theta_back=0.5)
    want = ops.region_filter_mask_batch(a, pv, b, av, loc, impl="ref", **kw)
    got = ik.region_filter_mask_batch(a, pv, b, av, loc, bn=64, bm=64,
                                      interpret=True, **kw)
    assert got.shape == (f, n)
    assert bool(jnp.all(want == got))


def test_nms_removes_duplicates():
    boxes = jnp.asarray([[0.1, 0.1, 0.4, 0.4],
                         [0.11, 0.11, 0.41, 0.41],   # duplicate of 0
                         [0.6, 0.6, 0.9, 0.9]])
    scores = jnp.asarray([0.9, 0.8, 0.7])
    keep = ref.nms_mask(boxes, scores, jnp.ones(3, bool), 0.5)
    assert keep.tolist() == [True, False, True]


NMS_IOU = 0.45      # split_regions' threshold
NMS_MARGIN = 1e-4   # every IoU of a case lies at least this far from it


def _iou_row(box, boxes):
    """float64 IoU of one box against each of ``boxes``."""
    iw = np.maximum(np.minimum(box[2], boxes[:, 2])
                    - np.maximum(box[0], boxes[:, 0]), 0.0)
    ih = np.maximum(np.minimum(box[3], boxes[:, 3])
                    - np.maximum(box[1], boxes[:, 1]), 0.0)
    inter = iw * ih
    area = lambda b: (b[..., 2] - b[..., 0]) * (b[..., 3] - b[..., 1])
    return inter / (area(box) + area(boxes) - inter)


def _greedy_nms(boxes, scores, valid):
    """Plain greedy NMS of one frame in float64: keep the highest alive
    score (the lowest slot among equals), drop what it overlaps."""
    keep = np.zeros(len(scores), bool)
    alive = valid & (scores > ref.NEG_INF)
    while alive.any():
        i = np.flatnonzero(alive)[np.argmax(scores[alive])]
        keep[i] = True
        alive &= _iou_row(boxes[i], boxes) < NMS_IOU
        alive[i] = False
    return keep


def _grid_boxes(rng, n):
    """n boxes on the 1/64 grid, a quarter of them duplicates of others,
    drawn until no pair's IoU lies within NMS_MARGIN of NMS_IOU."""
    boxes = np.zeros((0, 4))
    while len(boxes) < n:
        if len(boxes) and rng.random() < 0.25:
            box = boxes[rng.integers(len(boxes))]
        else:
            xy = rng.integers(0, 49, 2)
            box = np.concatenate([xy, xy + rng.integers(4, 17, 2)]) / 64.0
        if len(boxes) and np.any(
                np.abs(_iou_row(box, boxes) - NMS_IOU) < NMS_MARGIN):
            continue
        boxes = np.vstack([boxes, box])
    return boxes


def _nms_case(kind, f, n, rng):
    """(boxes (F, N, 4), scores (F, N), valid (F, N)) of one case."""
    if kind == "chain":
        # slot i overlaps i + 1 at IoU 0.5 and i + 2 at 0.2, scores fall
        # along the chain: every other box survives, N / 2 rounds
        x = np.arange(n) / 64.0
        box = np.stack([x, np.zeros(n), x + 3 / 64.0, np.ones(n)], -1)
        return (np.broadcast_to(box, (f, n, 4)),
                np.broadcast_to(1.0 - np.arange(n) / n, (f, n)),
                np.ones((f, n), bool))
    boxes = np.stack([_grid_boxes(rng, n) for _ in range(f)])
    scores = rng.integers(0, 16, (f, n)) / 16.0     # many equal scores
    valid = rng.random((f, n)) < 0.6
    if kind == "equal_scores":
        scores[:] = 0.5
    elif kind == "empty_frame":
        valid[f // 2] = False
    elif kind == "all_empty":
        valid[:] = False
    elif kind == "neg_inf":
        scores[rng.random((f, n)) < 0.3] = ref.NEG_INF
        scores[:, 0] = 2 * ref.NEG_INF
    return boxes, scores, valid


@pytest.mark.parametrize("f", [1, 8, 64])
@pytest.mark.parametrize("kind", ["random", "equal_scores", "empty_frame",
                                  "all_empty", "neg_inf", "chain"])
def test_nms_mask_matches_greedy_oracle(kind, f, monkeypatch):
    """ops.nms_mask, vmapped over a flush's frames as split_regions calls
    it, against plain greedy NMS.  Coordinates on the 1/64 grid make every
    area and intersection exact in float32, so only the IoU's division
    rounds (relative error 2**-24); with every IoU at least NMS_MARGIN from
    the threshold, float32 and the oracle's float64 decide alike.  The
    loop's rounds, counted by a callback in its body, are the largest
    per-frame kept count, as regions.nms_rounds reads them."""
    rng = np.random.default_rng([f, len(kind)])
    boxes, scores, valid = _nms_case(kind, f, 256, rng)
    want = np.stack([_greedy_nms(*a) for a in zip(boxes, scores, valid)])

    rounds = []
    while_loop = jax.lax.while_loop

    def counted(cond, body, init):
        def body_counted(st):
            jax.debug.callback(lambda: rounds.append(1))
            return body(st)
        return while_loop(cond, body_counted, init)

    monkeypatch.setattr(jax.lax, "while_loop", counted)
    got = jax.jit(jax.vmap(lambda b, s, v: ops.nms_mask(
        b, s, v, iou_threshold=NMS_IOU)))(
            jnp.asarray(boxes, jnp.float32), jnp.asarray(scores, jnp.float32),
            jnp.asarray(valid))
    got = np.asarray(got)
    np.testing.assert_array_equal(got, want)
    assert len(rounds) == want.sum(axis=1).max()
    assert len(rounds) == reg.nms_rounds(got, np.zeros_like(got))
    if kind == "chain":
        assert len(rounds) == 128
    if kind == "all_empty" or (kind == "empty_frame" and f == 1):
        assert len(rounds) == 0


def _loops_text(hlo: str) -> str:
    """The compiled HLO's while loops: their result shapes and the text of
    every computation their bodies and conditions reach."""
    comps, name = {}, None
    for line in hlo.splitlines():
        head = re.match(r"(?:ENTRY )?%(\S+) .*\{$", line)
        if head:
            name = head.group(1)
            comps[name] = []
        elif name is not None:
            comps[name].append(line)
    calls = re.compile(r"(?:calls|to_apply|body|condition)=%([\w.\-]+)")
    shapes, todo = [], []
    for line in hlo.splitlines():
        if " while(" in line:
            shapes.append(line.split(" while(")[0])
            todo += calls.findall(line)
    seen = set()
    while todo:
        c = todo.pop()
        if c not in seen:
            seen.add(c)
            shapes += comps[c]
            todo += calls.findall("\n".join(comps[c]))
    return "\n".join(shapes)


@pytest.mark.parametrize("program", ["nms_mask", "detect_split"])
def test_nms_builds_no_pairwise_iou_matrix(program):
    """At the served cell's shapes (8 frames of 128x128, 256 slots) the
    NMS passes hold no (F, N, N) IoU matrix: the vmapped nms_mask has no
    such array at all, and no while loop of detect_split carries or
    computes one (its §IV.B filter's (F, N, M) IoU against the accepted
    regions is computed once, outside the loops)."""
    from repro.configs.vpaas_video import DETECTOR
    from repro.core import protocol as pm
    from repro.models import detector as det_mod
    f, n = 8, 256
    sds = jax.ShapeDtypeStruct
    if program == "nms_mask":
        text = jax.jit(jax.vmap(lambda b, s, v: ops.nms_mask(b, s, v))).lower(
            sds((f, n, 4), jnp.float32), sds((f, n), jnp.float32),
            sds((f, n), jnp.bool_)).compile().as_text()
    else:
        assert np.prod(DETECTOR.grid_hw) == n
        params = jax.eval_shape(lambda: det_mod.init_detector(
            DETECTOR, jax.random.PRNGKey(0)))
        hlo = pm.detect_split.lower(
            DETECTOR, pm.ProtocolConfig(), params,
            sds((f, 128, 128, 3), jnp.float32)).compile().as_text()
        assert hlo.count(" while(") == 2
        text = _loops_text(hlo)
    assert f"f32[{f},{n},{n}]" not in text


# ---------------------------------------------------------------------------
# one-vs-all kernels via the ops dispatch layer
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("b,d,c", [(64, 17, 10), (130, 33, 21), (8, 8, 4)])
def test_onevsall_scores_dispatch(b, d, c):
    kx, kw = jax.random.split(KEY)
    x = jax.random.normal(kx, (b, d))
    w = jax.random.normal(kw, (d, c))
    want = ops.onevsall_scores(x, w, impl="ref")
    got = ops.onevsall_scores(x, w, impl="interpret")
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-6)


@pytest.mark.parametrize("b,d,c", [(64, 17, 10), (96, 16, 8)])
def test_onevsall_update_dispatch(b, d, c):
    kx, kw, ky = jax.random.split(KEY, 3)
    x = jax.random.normal(kx, (b, d))
    w = jax.random.normal(kw, (d, c))
    y = jax.nn.one_hot(jax.random.randint(ky, (b,), 0, c), c)
    want = ops.onevsall_update(x, y, w, eta=0.3, impl="ref")
    got = ops.onevsall_update(x, y, w, eta=0.3, impl="interpret")
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)
