"""Device-resident hot path: fused detect->split, zero-copy packing,
compacted bucketed classify, async flush pipelining — agreement with the
per-chunk reference plus the packing/compaction edge cases.

Random-init models throughout: every check is about execution semantics
(numerics against the reference, host-transfer budgets, bucket
arithmetic), not accuracy."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.vpaas_video import ClassifierConfig, DetectorConfig
from repro.core import protocol as pm
from repro.core import regions as reg
from repro.core.coordinator import CloudFogCoordinator, MultiStreamCoordinator
from repro.core.protocol import HighLowProtocol
from repro.learning.labeling import LabelCandidate, LabelingQueue
from repro.models import classifier as clf_mod
from repro.models import detector as det_mod
from repro.serving.batching import pack_frames_device
from repro.serving.graph import GraphScheduler, VideoFunctionGraph
from reference_check import assert_matches_reference

DET = DetectorConfig(name="hotpath-test-det", image_hw=(32, 32),
                     widths=(8, 16))
CLF = ClassifierConfig(name="hotpath-test-clf", crop_hw=(16, 16),
                       widths=(8, 16), feature_dim=16)


@pytest.fixture(scope="module")
def models():
    det_params = det_mod.init_detector(DET, jax.random.PRNGKey(0))
    clf_params = clf_mod.init_classifier(CLF, jax.random.PRNGKey(1))
    return det_params, clf_params


def _chunks(seed, n, frames=2):
    from repro.video import synthetic
    rng = np.random.default_rng(seed)
    return [synthetic.make_chunk(rng, "traffic", num_frames=frames,
                                 hw=(32, 32)) for _ in range(n)]


# ---------------------------------------------------------------------------
# the served path against the per-chunk reference
# ---------------------------------------------------------------------------
def _serve_against_reference(models):
    """Serve 4 streams, each with its own readout, in shared flushes; hold
    every chunk to ``process_chunk`` with that stream's readout."""
    det_params, clf_params = models
    streams = [_chunks(50 + i, 2) for i in range(4)]
    proto = HighLowProtocol(DET, CLF)
    multi = MultiStreamCoordinator(proto, det_params, clf_params, streams,
                                   max_batch_chunks=4, batch_window=0.05)
    W0 = np.asarray(clf_params["W"])
    for i in range(len(streams)):
        multi.scheduler.hot_swap(W0 + 0.1 * i, stream=f"cam{i}")
    multi.run(learn=False)
    sched = multi.scheduler
    # the streams did share flushes, so readouts rode side by side
    assert sched.hot_path_stats["flushes"] < sum(map(len, streams))
    for st in sched.streams.values():
        assert len(st.results) == 2
        for chunk, res, mode in st.results:
            assert mode == "cloud"
            ref = proto.process_chunk(det_params, clf_params, chunk.frames,
                                      W=st.W)
            assert_matches_reference(res, ref)


def test_fused_matches_reference_multi_stream(models):
    _serve_against_reference(models)


def _swap_readouts(orig):
    def swapped(clf_cfg, pcfg, clf_params, Ws, *a):
        return orig(clf_cfg, pcfg, clf_params, Ws[::-1], *a)
    return swapped


def _zero_one_crop(orig):
    def zeroed(*a):
        out = dict(orig(*a))
        idxs = a[-1]                 # the flush's first gathered crop
        out["fog_scores"] = out["fog_scores"].at[idxs[0, 0], idxs[1, 0]].set(
            0.0, mode="drop")
        return out
    return zeroed


def _move_one_box(orig):
    def moved(*a):
        split = orig(*a)
        return split._replace(
            acc_boxes=split.acc_boxes.at[0, 0].add(1.0 / 128))
    return moved


# fault -> (served-path function it is planted in, wrapper)
FAULTS = {"swapped_readouts": ("classify_compacted", _swap_readouts),
          "zeroed_crop": ("classify_compacted", _zero_one_crop),
          "moved_box": ("detect_split", _move_one_box)}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_reference_check_catches_fault(models, monkeypatch, fault):
    """The tolerance still catches real faults planted in the served
    path: two streams' readouts swapped in a flush, one valid crop's
    scores zeroed, one box moved by 1/128."""
    attr, plant = FAULTS[fault]
    monkeypatch.setattr(pm, attr, plant(getattr(pm, attr)))
    with pytest.raises(AssertionError):
        _serve_against_reference(models)


def test_fused_single_stream_bitwise_vs_sequential(models):
    det_params, clf_params = models
    chunk = _chunks(7, 1)[0]
    coord = CloudFogCoordinator(HighLowProtocol(DET, CLF), det_params,
                                clf_params)
    res_graph = coord.process_chunk(chunk, learn=False)
    res_seq = HighLowProtocol(DET, CLF).process_chunk(
        det_params, clf_params, chunk.frames)
    np.testing.assert_array_equal(res_graph.boxes, res_seq.boxes)
    np.testing.assert_array_equal(res_graph.labels, res_seq.labels)
    np.testing.assert_array_equal(res_graph.valid, res_seq.valid)
    np.testing.assert_array_equal(res_graph.fog_features,
                                  res_seq.fog_features)
    assert res_graph.latency.total == res_seq.latency.total


def test_hot_path_keyword_names_only_the_served_path(models):
    """``hot_path`` survives only for configurations that name the one
    served path; any other value is refused, not silently served."""
    det_params, clf_params = models
    graph = VideoFunctionGraph(HighLowProtocol(DET, CLF), det_params,
                               clf_params)
    GraphScheduler(graph, hot_path="fused")
    with pytest.raises(ValueError, match="hot_path"):
        GraphScheduler(graph, hot_path="sync")


# ---------------------------------------------------------------------------
# Device-residency regression: host transfers per flush must not grow
# ---------------------------------------------------------------------------
def test_fused_one_host_sync_per_flush(models):
    det_params, clf_params = models
    streams = [_chunks(150 + i, 3) for i in range(8)]
    multi = MultiStreamCoordinator(HighLowProtocol(DET, CLF), det_params,
                                   clf_params, streams, max_batch_chunks=8,
                                   batch_window=0.05)
    multi.run(learn=False)
    hps = multi.scheduler.hot_path_stats
    assert hps["flushes"] > 0
    # THE device-residency guarantee: exactly one blocking device->host
    # read per flush on the dispatch path.  If this ratio grows, a host
    # round-trip crept back into the hot loop — fail loudly.
    assert hps["host_syncs"] == hps["flushes"]
    # result materialization is per *flush* (bundle), not per chunk
    assert hps["result_downloads"] == hps["flushes"]
    # compaction actually compacted (random init leaves invalid regions)
    assert hps["crops_classified"] < hps["crops_budget"]
    # per-stream readouts uploaded once each, not once per chunk
    assert multi.report()["w_uploads"] == len(streams)


def test_w_device_cache_refreshes_only_on_swap(models):
    det_params, clf_params = models
    coord = CloudFogCoordinator(HighLowProtocol(DET, CLF), det_params,
                                clf_params)
    for chunk in _chunks(31, 3):
        coord.process_chunk(chunk, learn=False)
    st = coord._stream
    assert st.w_uploads == 1                   # one upload, three chunks
    dev = st.W_device()
    assert st.W_device() is dev                # cache hit, no re-upload
    coord.scheduler.hot_swap(np.asarray(st.W) + 1.0)
    assert st.W_device() is not dev            # swap invalidated the cache
    assert st.w_uploads == 2


# ---------------------------------------------------------------------------
# Packing / compaction edge cases
# ---------------------------------------------------------------------------
def test_pack_frames_device_matches_numpy_semantics():
    a = np.random.rand(2, 8, 8, 3).astype(np.float32)
    b = np.random.rand(3, 8, 8, 3).astype(np.float32)
    # single request: the array object passes through untouched
    a_dev = jnp.asarray(a)
    batch, slices, pad = pack_frames_device([a_dev])
    assert batch is a_dev and pad == 0
    np.testing.assert_array_equal(np.asarray(batch), a)
    # multi request: concat + zero-pad to the bucket, as numpy does it
    d_batch, d_slices, d_pad = pack_frames_device(
        [jnp.asarray(a), jnp.asarray(b)], buckets=(2, 4, 8))
    want = np.concatenate([a, b, np.zeros((3, 8, 8, 3), np.float32)])
    assert d_pad == 3 and d_slices == [slice(0, 2), slice(2, 5)]
    np.testing.assert_array_equal(np.asarray(d_batch), want)
    # overflow past the largest bucket: exact size, nothing truncated
    big = [jnp.asarray(np.random.rand(3, 8, 8, 3).astype(np.float32))
           for _ in range(4)]
    batch, slices, pad = pack_frames_device(big, buckets=(2, 4, 8))
    assert batch.shape[0] == 12 and pad == 0


def test_compaction_indices_edges():
    pv = np.zeros((4, 8), bool)
    # empty valid set: min-bucket pad, every row out-of-bounds
    fidx, ridx, n, size = reg.compaction_indices(pv, buckets=(4, 8))
    assert (n, size) == (0, 4) and (fidx == 4).all()
    # exactly at a bucket boundary: no padding
    pv[0, :4] = True
    fidx, ridx, n, size = reg.compaction_indices(pv, buckets=(4, 8))
    assert (n, size) == (4, 4)
    assert (fidx < 4).all() and (ridx < 8).all()
    # past the largest bucket: exact size (padding down would drop work)
    pv[:] = True
    fidx, ridx, n, size = reg.compaction_indices(pv, buckets=(4, 8))
    assert (n, size) == (32, 32)


@pytest.mark.parametrize("n_valid", [0, 4, 11])
def test_classify_compacted_matches_full_budget(models, n_valid):
    """Scatter/gather round trip matches the masked full-budget reference
    for empty, bucket-exact, and padded valid sets."""
    det_params, clf_params = models
    pcfg = pm.ProtocolConfig()
    rng = np.random.default_rng(9)
    frames = jnp.asarray(rng.random((4, 32, 32, 3), np.float32))
    split = pm.detect_split(DET, pcfg, det_params, frames)
    # overwrite the validity pattern to hit the exact edge case
    pv = np.zeros(split.prop_valid.shape, bool)
    pos = np.argwhere(np.ones_like(pv))
    picks = rng.choice(len(pos), size=n_valid, replace=False)
    pv[tuple(pos[picks].T)] = True
    split = reg.RegionSplit(split.acc_boxes, split.acc_labels,
                            split.acc_valid, split.prop_boxes,
                            jnp.asarray(pv))
    W = jnp.asarray(clf_params["W"])
    fidx, ridx, n, size = reg.compaction_indices(pv, buckets=(4, 8))
    assert n == n_valid
    idxs = np.zeros((3, size), np.int32)
    idxs[0], idxs[1] = fidx, ridx
    merged_c = pm.classify_compacted(CLF, pcfg, clf_params, W[None], frames,
                                     split, jnp.asarray(idxs))
    merged_f = pm.classify_regions(CLF, pcfg, clf_params, W, frames, split)
    assert set(merged_c) == set(merged_f)
    assert_matches_reference(merged_c, merged_f)
    if n_valid == 0:
        assert not np.asarray(merged_c["fog_scores"]).any()


def test_empty_proposals_end_to_end(models):
    """Thresholds nothing can pass -> zero proposals per chunk; the fused
    pipeline must still flow (min-bucket classify, all-zero fog grids)."""
    det_params, clf_params = models
    proto = HighLowProtocol(DET, CLF,
                            pcfg=pm.ProtocolConfig(theta_loc=1.5,
                                                   theta_cls=1.5))
    coord = CloudFogCoordinator(proto, det_params, clf_params)
    res = coord.process_chunk(_chunks(77, 1)[0], learn=False)
    assert not res.prop_valid.any()
    assert not res.valid.any()
    assert not res.fog_scores.any()
    assert res.coord_bytes == 0.0


# ---------------------------------------------------------------------------
# Label-queue aging (learning-plane satellite)
# ---------------------------------------------------------------------------
def _candidate(features, W, **kw):
    scores = 1.0 / (1.0 + np.exp(-(features @ W)))
    return LabelCandidate(features=features, box=np.zeros(4),
                          scores=scores, gt_boxes=np.zeros((1, 4)),
                          gt_labels=np.zeros(1, np.int64), **kw)


def test_label_queue_rescore_reranks_and_expires():
    rng = np.random.default_rng(0)
    d, c = 8, 4
    W_old = rng.normal(size=(d, c))
    queue = LabelingQueue(max_size=16)
    # scaled basis features: candidate i scores as 10 * W[i] — lets the
    # test construct exact confidence under a chosen readout
    feats = [10.0 * np.eye(d)[i] for i in range(6)]
    for f in feats:
        queue.push(_candidate(f, W_old, model_version=0))
    order_old = [queue._heap[0][2].uncertainty]
    # a new readout that answers every queued candidate confidently:
    # class 0 strongly on, every other head strongly off
    W_new = np.tile(np.array([5.0, -5.0, -5.0, -5.0]), (d, 1))
    aged = queue.rescore(W_new, version=1, expire_below=0.05)
    assert aged["rescored"] == 6
    # the new model's near-certain scores (top1 ~1, top2 ~0) expire all
    assert aged["expired"] == 6 and len(queue) == 0
    assert queue.stats["expired"] == 6

    # re-ranking without expiry: stale candidates re-sort by new margins
    queue2 = LabelingQueue(max_size=16)
    for f in feats:
        queue2.push(_candidate(f, W_old, model_version=0))
    aged2 = queue2.rescore(rng.normal(size=(d, c)), version=1,
                           expire_below=0.0)
    assert aged2 == {"rescored": 6, "expired": 0} and len(queue2) == 6
    assert all(c_.model_version == 1 for _, _, c_ in queue2._heap)
    # fresh candidates (already at the current version) are left alone
    fresh = _candidate(feats[0], W_old, model_version=1)
    queue2.push(fresh)
    aged3 = queue2.rescore(W_old, version=1)
    assert aged3 == {"rescored": 0, "expired": 0}
    assert order_old  # silence lint: old ordering captured above


def test_plane_ages_queue_on_hot_swap(models):
    """A promotion hot-swap bumps the swap epoch and rescored/expired
    counters flow into the queue stats the plane reports."""
    from repro.learning.plane import ContinualLearningPlane, LearningConfig

    plane = ContinualLearningPlane(num_classes=CLF.num_classes,
                                   cfg=LearningConfig())
    rng = np.random.default_rng(1)
    W = rng.normal(size=(CLF.feature_dim + 1, CLF.num_classes))
    for _ in range(5):
        plane.queue.push(_candidate(
            rng.normal(size=CLF.feature_dim + 1), W,
            model_version=plane.swap_epoch))
    epoch0 = plane.swap_epoch
    plane._age_queue(W, t=1.0)
    assert plane.swap_epoch == epoch0 + 1
    assert plane.queue.stats["rescored"] == 5
    # harvested candidates are tagged with the *current* epoch
    assert all(c_.model_version <= plane.swap_epoch
               for _, _, c_ in plane.queue._heap)
