"""Pallas crop_gather kernel: interpret-mode bitwise equality vs the jnp
oracle and vs the shared-grid materialize-then-gather path, the choice of
implementation by backend (``ops.crop_gather_impl``), the compacted
stages' ``impl="ref"`` crop step (bucket rows only, bitwise the grid's),
plus the compacted classify stages under ``impl="interpret"`` — plain,
ensemble, and empty-flush cases, and a scheduler serving on the kernel."""
import functools
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.vpaas_video import ClassifierConfig, DetectorConfig
from repro.core import protocol as pm
from repro.core import regions as reg
from repro.kernels import ops
from repro.kernels import ref
from repro.models import classifier as clf_mod
from repro.models import detector as det_mod

KEY = jax.random.PRNGKey(11)

DET = DetectorConfig(name="cropk-test-det", image_hw=(32, 32), widths=(8, 16))
CLF = ClassifierConfig(name="cropk-test-clf", crop_hw=(16, 16),
                       widths=(8, 16), feature_dim=16)


@pytest.fixture(scope="module")
def models():
    det_params = det_mod.init_detector(DET, jax.random.PRNGKey(0))
    clf_params = clf_mod.init_classifier(CLF, jax.random.PRNGKey(1))
    return det_params, clf_params


@functools.partial(jax.jit, static_argnames=("out_hw",))
def _grid_gather(frames, boxes, idxs, *, out_hw):
    """The pre-kernel structure: materialize all F x N crops, then gather."""
    crops = reg.crop_batch(frames, boxes, out_hw)
    return crops[idxs[0], idxs[1]]


def _rand_case(key, f, n, hw, valid_frac):
    k1, k2, k3 = jax.random.split(key, 3)
    frames = jax.random.uniform(k1, (f, *hw, 3))
    pts = jax.random.uniform(k2, (f, n, 2, 2))
    boxes = jnp.concatenate([jnp.min(pts, 2), jnp.max(pts, 2)], -1)
    # degenerate boxes: zero-area and full-frame
    boxes = boxes.at[0, 0].set(jnp.array([0.5, 0.5, 0.5, 0.5]))
    boxes = boxes.at[0, 1].set(jnp.array([0.0, 0.0, 1.0, 1.0]))
    pv = np.asarray(jax.random.uniform(k3, (f, n)) < valid_frac)
    return frames, boxes, pv


def _idxs(pv, buckets=(4, 8, 16, 32, 64, 128)):
    fidx, ridx, n_valid, bucket = reg.compaction_indices(pv, buckets)
    idxs = np.zeros((3, bucket), np.int32)
    idxs[0], idxs[1] = fidx, ridx
    return jnp.asarray(idxs), n_valid, bucket


# ---------------------------------------------------------------------------
# kernel vs oracle vs shared grid — bitwise
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("f,n,hw,out_hw,valid_frac", [
    (6, 9, (32, 32), (16, 16), 0.3),    # generic padded bucket
    (4, 16, (24, 40), (8, 8), 0.0),     # empty flush: every row OOB pad
    (3, 5, (16, 16), (16, 16), 1.0),    # all valid, non-square source
    (8, 12, (32, 32), (16, 16), 0.5),
    (5, 30, (48, 48), (16, 16), 0.9),   # past the largest bucket: exact B
])
def test_crop_gather_bitwise_sweep(f, n, hw, out_hw, valid_frac):
    frames, boxes, pv = _rand_case(
        jax.random.fold_in(KEY, f * 1000 + n), f, n, hw, valid_frac)
    idxs, n_valid, bucket = _idxs(pv)
    grid = np.asarray(_grid_gather(frames, boxes, idxs, out_hw=out_hw))
    oracle = np.asarray(ref.crop_gather(frames, boxes, idxs, out_hw=out_hw))
    kernel = np.asarray(ops.crop_gather(frames, boxes, idxs, out_hw=out_hw,
                                        impl="interpret"))
    assert grid.shape == (bucket, *out_hw, 3)
    np.testing.assert_array_equal(oracle, grid)
    np.testing.assert_array_equal(kernel, grid)


def test_crop_gather_oob_pad_rows_clip():
    """Pad rows carry frame index F: the gather must clip, not wrap or
    crash, and the clipped rows must equal the last frame's row-0 crop."""
    frames, boxes, _ = _rand_case(KEY, 3, 4, (16, 16), 0.0)
    idxs = jnp.asarray(np.array([[3, 3, 0, 2],      # 2 OOB pad rows
                                 [0, 0, 0, 1],
                                 [0, 0, 0, 0]], np.int32))
    out = np.asarray(ops.crop_gather(frames, boxes, idxs, out_hw=(8, 8),
                                     impl="interpret"))
    want = np.asarray(ref.crop_gather(frames, boxes, idxs, out_hw=(8, 8)))
    np.testing.assert_array_equal(out, want)
    # a pad row's crop is the clipped (last-frame, region-0) crop
    np.testing.assert_array_equal(out[0], out[1])
    ref_row = np.asarray(_grid_gather(
        frames, boxes, jnp.asarray([[2], [0], [0]], jnp.int32),
        out_hw=(8, 8)))[0]
    np.testing.assert_array_equal(out[0], ref_row)


def test_bucket_boundary_sizes():
    """Exact-bucket, min-bucket-pad, and past-largest-bucket gather plans
    all run the kernel at their planned batch size."""
    frames, boxes, _ = _rand_case(KEY, 4, 8, (16, 16), 0.0)
    for n_set, want_b in [(0, 4), (4, 4), (5, 8), (32, 32)]:
        pv = np.zeros((4, 8), bool)
        pv.ravel()[:n_set] = True
        idxs, n_valid, bucket = _idxs(pv, buckets=(4, 8))
        assert (n_valid, bucket) == (n_set, want_b)
        grid = np.asarray(_grid_gather(frames, boxes, idxs, out_hw=(8, 8)))
        kernel = np.asarray(ops.crop_gather(frames, boxes, idxs,
                                            out_hw=(8, 8), impl="interpret"))
        np.testing.assert_array_equal(kernel, grid)


# ---------------------------------------------------------------------------
# the implementation ops.crop_gather runs, by backend and frame size
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("backend,impl,frame_shape,want", [
    ("tpu", "ref", (8, 128, 128, 3), "pallas"),     # the served cell's frames
    ("tpu", "pallas", (8, 128, 128, 3), "pallas"),
    ("tpu", "interpret", (8, 128, 128, 3), "interpret"),
    ("tpu", "ref", (8, 720, 1280, 3), "ref"),       # HQ 720p: over VMEM
    ("tpu", "pallas", (8, 720, 1280, 3), "ref"),
    ("cpu", "ref", (8, 128, 128, 3), "ref"),
    ("cpu", "pallas", (8, 128, 128, 3), "ref"),
    ("cpu", "interpret", (8, 128, 128, 3), "interpret"),
    ("gpu", "ref", (8, 128, 128, 3), "ref"),
])
def test_crop_gather_impl(monkeypatch, backend, impl, frame_shape, want):
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    assert ops.crop_gather_impl(impl, frame_shape) == want


def test_crop_gather_impl_vmem_budget(monkeypatch):
    """The kernel is chosen up to the scoped-VMEM budget and not past it:
    two frame blocks and the body's planes, in (8, 128) tiles."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    # 15 planes (2 x 3 channels + 9) of 512 x 512 float32: 15 MiB
    assert ops.crop_gather_impl("ref", (8, 512, 512, 3)) == "pallas"
    # 520 rows tile to 520, 520 columns to 640: 19.04 MiB
    assert ops.crop_gather_impl("ref", (8, 520, 520, 3)) == "ref"
    assert ops._plane_bytes(520, 520) == 4 * 520 * 640
    assert ops._plane_bytes(1, 1) == 4 * 8 * 128


# ---------------------------------------------------------------------------
# the compacted stages' crop step on impl="ref": bucket rows only
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("f,n,hw,n_valid,buckets", [
    (4, 32, (32, 32), 11, (4, 8, 16, 32)),  # sparse: 11 rows + 5 OOB pads
    (3, 4, (16, 16), 9, (4, 8, 16)),        # bucket 16 > F x N = 12
])
def test_crop_bucket_ref_matches_grid(f, n, hw, n_valid, buckets):
    """``_crop_bucket`` on impl="ref" crops only the plan's rows, bitwise
    what cropping the whole grid and indexing it gives, pad rows (frame
    index F, clipped to the last frame) included."""
    frames, boxes, _ = _rand_case(jax.random.fold_in(KEY, f * n), f, n, hw,
                                  0.0)
    pv = np.zeros((f, n), bool)
    pv.ravel()[np.random.default_rng(f * n).choice(f * n, n_valid,
                                                   replace=False)] = True
    idxs, got_valid, bucket = _idxs(pv, buckets=buckets)
    assert got_valid == n_valid and bucket > n_valid
    assert (np.asarray(idxs[0, n_valid:]) == f).all()
    split = reg.RegionSplit(boxes, jnp.zeros((f, n), jnp.int32),
                            jnp.zeros((f, n), bool), boxes, jnp.asarray(pv))
    crop = jax.jit(pm._crop_bucket, static_argnums=(0, 1))
    got = np.asarray(crop(CLF, pm.ProtocolConfig(impl="ref"), frames, split,
                          idxs))
    want = np.asarray(_grid_gather(frames, boxes, idxs, out_hw=CLF.crop_hw))
    assert got.shape == (bucket, *CLF.crop_hw, 3)
    np.testing.assert_array_equal(got, want)


def _array_sizes(text):
    """Element counts of every array type in StableHLO (``tensor<8x3xf32>``)
    or compiled HLO (``f32[8,3]{1,0}``) text."""
    dims = re.findall(r"tensor<((?:\d+x)+)[a-z]", text)
    dims = [d.rstrip("x").split("x") for d in dims]
    dims += [d.split(",") for d in
             re.findall(r"\b(?:f|bf|s|u|pred)\d*\[(\d+(?:,\d+)*)\]", text)]
    return [int(np.prod([int(x) for x in d])) for d in dims]


@pytest.mark.parametrize("stage", ["plain", "ensemble"])
@pytest.mark.parametrize("bucket", [128, 256])
def test_classify_compacted_ref_crops_no_region_grid(stage, bucket):
    """The served cell's shapes (8 frames x 256 regions of 128x128, 40x40
    crops, the full-width classifier): with impl="ref" neither the lowered
    program nor the compiled one holds an array as large as the whole
    region grid's crops (F x N x oh x ow x 3), so the crop step cannot
    have gone back to cropping every region before taking the bucket's."""
    from repro.configs.vpaas_video import CLASSIFIER
    f, n, c = 8, 256, 3
    oh, ow = CLASSIFIER.crop_hw
    sds = jax.ShapeDtypeStruct
    params = jax.eval_shape(lambda: clf_mod.init_classifier(
        CLASSIFIER, jax.random.PRNGKey(0)))
    boxes = sds((f, n, 4), jnp.float32)
    split = reg.RegionSplit(boxes, sds((f, n), jnp.int32),
                            sds((f, n), jnp.bool_), boxes,
                            sds((f, n), jnp.bool_))
    frames = sds((f, 128, 128, c), jnp.float32)
    idxs = sds((3, bucket), jnp.int32)
    w = (CLASSIFIER.feature_dim + 1, CLASSIFIER.num_classes)
    pcfg = pm.ProtocolConfig(impl="ref")
    if stage == "plain":
        lowered = pm.classify_compacted.lower(
            CLASSIFIER, pcfg, params, sds((4, *w), jnp.float32), frames,
            split, idxs)
    else:
        lowered = pm.classify_compacted_ensemble.lower(
            CLASSIFIER, pcfg, params, sds((4, 2, *w), jnp.float32),
            sds((4, 2), jnp.float32), frames, split, idxs)
    grid = f * n * oh * ow * c
    for text in (lowered.as_text(), lowered.compile().as_text()):
        sizes = _array_sizes(text)
        assert bucket * oh * ow * c in sizes   # the bucket's crops are there
        assert max(sizes) < grid


# ---------------------------------------------------------------------------
# the compacted classify stages under impl="interpret" — bitwise vs "ref"
# ---------------------------------------------------------------------------
def _split_with_valid(det_params, frames, n_valid, rng):
    pcfg = pm.ProtocolConfig()
    split = pm.detect_split(DET, pcfg, det_params, frames)
    pv = np.zeros(split.prop_valid.shape, bool)
    pos = np.argwhere(np.ones_like(pv))
    picks = rng.choice(len(pos), size=n_valid, replace=False)
    pv[tuple(pos[picks].T)] = True
    return reg.RegionSplit(split.acc_boxes, split.acc_labels,
                           split.acc_valid, split.prop_boxes,
                           jnp.asarray(pv)), pv


@pytest.mark.parametrize("n_valid", [0, 4, 11])
def test_classify_compacted_kernel_bitwise(models, n_valid):
    det_params, clf_params = models
    rng = np.random.default_rng(21)
    frames = jnp.asarray(rng.random((4, 32, 32, 3), np.float32))
    split, pv = _split_with_valid(det_params, frames, n_valid, rng)
    W = jnp.asarray(clf_params["W"])
    idxs, _, _ = _idxs(pv, buckets=(4, 8))
    outs = {}
    for impl in ("ref", "interpret"):
        pcfg = pm.ProtocolConfig(impl=impl)
        outs[impl] = pm.classify_compacted(CLF, pcfg, clf_params, W[None],
                                           frames, split, idxs)
    for k in outs["ref"]:
        np.testing.assert_array_equal(np.asarray(outs["ref"][k]),
                                      np.asarray(outs["interpret"][k]))


@pytest.mark.parametrize("n_valid", [0, 7])
def test_classify_compacted_ensemble_kernel_bitwise(models, n_valid):
    """Mixed flush: one real 2-snapshot lineage + one plain stream riding
    along as the zero-padded degenerate lineage."""
    det_params, clf_params = models
    rng = np.random.default_rng(22)
    frames = jnp.asarray(rng.random((4, 32, 32, 3), np.float32))
    split, pv = _split_with_valid(det_params, frames, n_valid, rng)
    W = np.asarray(clf_params["W"], np.float32)
    snaps = np.zeros((2, 2, *W.shape), np.float32)
    snaps[0, 0], snaps[0, 1] = W, 0.9 * W
    snaps[1, 0] = W                       # plain stream, zero-padded T=2
    omegas = np.asarray([[0.6, 0.4], [1.0, 0.0]], np.float32)
    idxs, n, _ = _idxs(pv, buckets=(4, 8))
    idxs = idxs.at[2, :n].set(jnp.asarray(
        rng.integers(0, 2, size=n), jnp.int32))
    outs = {}
    for impl in ("ref", "interpret"):
        pcfg = pm.ProtocolConfig(impl=impl)
        outs[impl] = pm.classify_compacted_ensemble(
            CLF, pcfg, clf_params, jnp.asarray(snaps), jnp.asarray(omegas),
            frames, split, idxs)
    for k in outs["ref"]:
        np.testing.assert_array_equal(np.asarray(outs["ref"][k]),
                                      np.asarray(outs["interpret"][k]))
    if n_valid == 0:
        assert not np.asarray(outs["interpret"]["fog_scores"]).any()


# ---------------------------------------------------------------------------
# shared-grid entry points still match the old per-crop semantics
# ---------------------------------------------------------------------------
def test_crop_and_resize_matches_map_coordinates():
    """regions.crop_and_resize now routes through ref.bilinear_crops; its
    *eager* output must stay bit-identical to the original per-channel
    map_coordinates formulation it replaced."""
    k1, k2 = jax.random.split(KEY)
    frame = jax.random.uniform(k1, (20, 28, 3))
    pts = jax.random.uniform(k2, (6, 2, 2))
    boxes = jnp.concatenate([jnp.min(pts, 1), jnp.max(pts, 1)], -1)
    oh, ow = 8, 8
    h_img, w_img = frame.shape[0], frame.shape[1]

    def one(box):
        x1, y1, x2, y2 = box[0], box[1], box[2], box[3]
        ys = y1 * (h_img - 1) + (y2 - y1) * (h_img - 1) * \
            jnp.linspace(0.0, 1.0, oh)
        xs = x1 * (w_img - 1) + (x2 - x1) * (w_img - 1) * \
            jnp.linspace(0.0, 1.0, ow)
        yy, xx = jnp.meshgrid(ys, xs, indexing="ij")
        coords = jnp.stack([yy.ravel(), xx.ravel()])
        out = jnp.stack([
            jax.scipy.ndimage.map_coordinates(frame[..., c], coords, order=1)
            for c in range(frame.shape[-1])], axis=-1)
        return out.reshape(oh, ow, frame.shape[-1])

    with jax.disable_jit():
        want = np.asarray(jnp.stack([one(b) for b in boxes]))
        got = np.asarray(reg.crop_and_resize(frame, boxes, (oh, ow)))
    np.testing.assert_array_equal(got, want)


# ---------------------------------------------------------------------------
# a scheduler serving its crops on the kernel
# ---------------------------------------------------------------------------
RESULT_FIELDS = ("boxes", "labels", "valid", "source", "fog_features",
                 "prop_boxes", "prop_valid", "fog_scores")


def _serve_two_streams(models):
    """Serve 2 streams x 2 chunks in shared flushes; the scheduler's
    counters and every chunk's result fields, in stream order."""
    from repro.core.coordinator import MultiStreamCoordinator
    from repro.video import synthetic
    det_params, clf_params = models
    streams = [[synthetic.make_chunk(np.random.default_rng(70 + 10 * i + j),
                                     "traffic", num_frames=2, hw=(32, 32))
                for j in range(2)] for i in range(2)]
    multi = MultiStreamCoordinator(pm.HighLowProtocol(DET, CLF), det_params,
                                   clf_params, streams, max_batch_chunks=2,
                                   batch_window=0.05)
    multi.run(learn=False)
    sched = multi.scheduler
    fields = [np.asarray(getattr(res, f))
              for name in sorted(sched.streams)
              for _, res, _ in sched.streams[name].results
              for f in RESULT_FIELDS]
    return dict(sched.hot_path_stats), fields


@pytest.fixture
def fresh_traces():
    """The crop implementation is chosen while a stage is traced: drop the
    traced programs and their recorded choices before and after, so that a
    choice the test forces is traced anew and does not outlive the test."""
    jax.clear_caches()
    pm.CROP_IMPL_TRACED.clear()
    yield
    jax.clear_caches()
    pm.CROP_IMPL_TRACED.clear()


def test_scheduler_counts_kernel_crops(models, monkeypatch, fresh_traces):
    """On the kernel (the chooser forced to ``"interpret"``) every crop a
    flush classifies counts in ``crops_kernel`` and the results are bitwise
    those of the oracle run, which counts none."""
    oracle_stats, oracle_fields = _serve_two_streams(models)
    assert set(pm.CROP_IMPL_TRACED.values()) == {"ref"}
    assert oracle_stats["crops_classified"] > 0
    assert oracle_stats["crops_kernel"] == 0
    jax.clear_caches()
    pm.CROP_IMPL_TRACED.clear()
    monkeypatch.setattr(ops, "crop_gather_impl",
                        lambda impl, frame_shape: "interpret")
    stats, fields = _serve_two_streams(models)
    assert set(pm.CROP_IMPL_TRACED.values()) == {"interpret"}
    assert stats["crops_kernel"] == stats["crops_classified"]
    assert stats["crops_classified"] == oracle_stats["crops_classified"]
    assert len(fields) == len(oracle_fields) == 4 * len(RESULT_FIELDS)
    for got, want in zip(fields, oracle_fields):
        np.testing.assert_array_equal(got, want)
