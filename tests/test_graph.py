"""Function-graph execution: dispatcher stages, event-driven scheduling,
cross-stream batching, and equivalence with the sequential protocol path.

Uses randomly initialised (untrained) models throughout — every check here
is about *execution semantics* (bit-identical numerics, conservation,
batching/scaling behaviour), not accuracy, so no training is needed and the
module stays fast."""
import jax
import numpy as np
import pytest

from repro.configs.vpaas_video import ClassifierConfig, DetectorConfig
from repro.core.bandwidth import NetworkModel
from repro.core.coordinator import (CloudFogCoordinator,
                                    MultiStreamCoordinator, StreamSpec)
from repro.core.incremental import IncrementalLearner
from repro.core.protocol import HighLowProtocol
from repro.models import classifier as clf_mod
from repro.models import detector as det_mod
from repro.serving.autoscaler import Autoscaler
from repro.serving.batching import (CrossStreamBatcher, DetectRequest,
                                    pack_frames_device)
from repro.serving.fault import FaultTolerantCoordinator
from repro.serving.graph import STAGES, VideoFunctionGraph

# small configs: the graph semantics are size-independent
DET = DetectorConfig(name="graph-test-det", image_hw=(32, 32),
                     widths=(8, 16))
CLF = ClassifierConfig(name="graph-test-clf", crop_hw=(16, 16),
                       widths=(8, 16), feature_dim=16)
FB = DetectorConfig(name="graph-test-fallback", image_hw=(32, 32),
                    widths=(4, 8))


@pytest.fixture(scope="module")
def models():
    det_params = det_mod.init_detector(DET, jax.random.PRNGKey(0))
    clf_params = clf_mod.init_classifier(CLF, jax.random.PRNGKey(1))
    fb_params = det_mod.init_detector(FB, jax.random.PRNGKey(2))
    return det_params, clf_params, fb_params


def _chunks(seed, n, frames=2):
    from repro.video import synthetic
    rng = np.random.default_rng(seed)
    return [synthetic.make_chunk(rng, "traffic", num_frames=frames,
                                 hw=(32, 32)) for _ in range(n)]


# ---------------------------------------------------------------------------
# Stage registration / dispatch surface
# ---------------------------------------------------------------------------
def test_graph_registers_stages_and_models(models):
    det_params, clf_params, _ = models
    graph = VideoFunctionGraph(HighLowProtocol(DET, CLF), det_params,
                               clf_params)
    for name in STAGES:
        assert name in graph.registry
    assert graph.registry.entry(
        "cloud.detect_split").metadata["tier"] == "cloud"
    assert graph.registry.entry("cloud.detect_split").metadata["batchable"]
    assert graph.registry.entry("fog.encode_low").kind == "preprocess"
    assert graph.registry.list(kind="inference") == [
        "cloud.detect_split", "cloud.detect_split_donated",
        "cloud.detect_split_dynamic", "fog.classify_batched",
        "fog.classify_ensemble_batched"]
    # the fused cloud stage and the compacted fog stage are both batchable
    assert graph.registry.entry("cloud.detect_split").metadata["fused"]
    assert graph.registry.entry("fog.classify_batched").metadata["batchable"]
    # the Eq. 9 stage is flagged as the multi-readout ensemble variant
    assert graph.registry.entry(
        "fog.classify_ensemble_batched").metadata["ensemble"]
    assert graph.registry.entry(
        "fog.classify_ensemble_batched").metadata["batchable"]
    assert "cloud-detector" in graph.zoo and "fog-classifier" in graph.zoo
    assert "cloud.detect_split" in graph.dispatcher.deployed("cloud")
    assert "fog.classify_batched" in graph.dispatcher.deployed("fog")
    assert "fog.classify_ensemble_batched" in graph.dispatcher.deployed("fog")


# ---------------------------------------------------------------------------
# Single-stream graph execution == sequential protocol path
# ---------------------------------------------------------------------------
def test_single_stream_matches_sequential(models):
    det_params, clf_params, _ = models
    chunks = _chunks(42, 3)

    coord = CloudFogCoordinator(HighLowProtocol(DET, CLF), det_params,
                                clf_params)
    out = coord.run(chunks, learn=False)

    # reference: drive the stage functions strictly sequentially
    proto = HighLowProtocol(DET, CLF)
    from repro.video.metrics import F1Accumulator
    acc = F1Accumulator()
    bytes_ref, cost_ref, lats_ref = 0.0, 0.0, []
    for c in chunks:
        res = proto.process_chunk(det_params, clf_params, c.frames)
        for t in range(c.frames.shape[0]):
            keep = res.valid[t]
            acc.update(res.boxes[t][keep], res.labels[t][keep],
                       c.gt_boxes[t], c.gt_labels[t])
        bytes_ref += res.wan_bytes + res.coord_bytes
        cost_ref += proto.cloud_cost(res)
        lats_ref.append(res.latency.total)

    assert out.f1 == acc.summary()          # exact, not approximate
    assert out.bandwidth == bytes_ref
    assert out.cloud_cost == cost_ref
    assert out.latencies == lats_ref
    # graph bookkeeping: every chunk passed through the executors (the
    # fused hot path dispatches the cloud.detect_split stage)
    assert coord.scheduler.cloud_executor.records
    assert all(r.fn_name == "cloud.detect_split"
               for r in coord.scheduler.cloud_executor.records)
    # no batching delay on the sequential path
    assert all(r.latency.queue_wait == 0.0
               for _, r, _ in coord._stream.results)


def test_single_stream_results_bitwise_equal(models):
    det_params, clf_params, _ = models
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
    assert res_graph.wan_bytes == res_seq.wan_bytes
    assert res_graph.coord_bytes == res_seq.coord_bytes
    assert res_graph.latency.total == res_seq.latency.total


# ---------------------------------------------------------------------------
# Multi-stream: conservation + batching actually happens
# ---------------------------------------------------------------------------
def test_four_streams_conserve_per_stream_detections(models):
    det_params, clf_params, _ = models
    streams = [_chunks(100 + i, 2) for i in range(4)]

    multi = MultiStreamCoordinator(HighLowProtocol(DET, CLF), det_params,
                                   clf_params, streams,
                                   max_batch_chunks=4, batch_window=0.05)
    mout = multi.run(learn=False)
    report = multi.report()
    assert report["batch_max_batch_chunks"] > 1   # cross-stream batches formed

    for i, chunks in enumerate(streams):
        solo = CloudFogCoordinator(HighLowProtocol(DET, CLF), det_params,
                                   clf_params)
        sout = solo.run(chunks, learn=False)
        name = f"cam{i}"
        assert mout[name].f1 == sout.f1
        assert mout[name].bandwidth == sout.bandwidth
        assert mout[name].cloud_cost == sout.cloud_cost
        for (_, r1, _), (_, r2, _) in zip(
                multi.scheduler.streams[name].results,
                solo._stream.results):
            np.testing.assert_array_equal(r1.valid, r2.valid)
            np.testing.assert_array_equal(r1.boxes, r2.boxes)
            np.testing.assert_array_equal(r1.labels, r2.labels)


def test_multi_stream_hitl_stays_per_stream(models):
    det_params, clf_params, _ = models
    specs = [StreamSpec(name=f"cam{i}", chunks=_chunks(200 + i, 2),
                        learner=IncrementalLearner(
                            num_classes=CLF.num_classes, trigger=4,
                            budget=64, rule="proximal"))
             for i in range(2)]
    multi = MultiStreamCoordinator(HighLowProtocol(DET, CLF), det_params,
                                   clf_params, specs, max_batch_chunks=2,
                                   batch_window=0.05)
    out = multi.run(learn=True)
    for spec in specs:
        assert out[spec.name].learner_summary["labels_used"] \
            == spec.learner.labels_used
    # per-stream model caches are independent objects
    w0 = multi.scheduler.streams["cam0"].W
    w1 = multi.scheduler.streams["cam1"].W
    assert w0 is not w1


# ---------------------------------------------------------------------------
# Batching substrate
# ---------------------------------------------------------------------------
def test_cross_stream_batcher_flush_rules():
    b = CrossStreamBatcher(max_chunks=3, window=0.05)
    f = np.zeros((2, 8, 8, 3), np.float32)
    b.submit(DetectRequest(frames=f, arrival=0.00))
    b.submit(DetectRequest(frames=f, arrival=0.01))
    b.submit(DetectRequest(frames=f, arrival=0.50))   # arrives much later
    assert not b.ready(now=0.01)          # 2 arrived, window not elapsed
    assert b.ready(now=0.06)              # oldest waited past the window
    batch = b.take(now=0.06)
    assert len(batch) == 2                # the late request is NOT grabbed
    assert b.pending_frames == 2
    assert b.ready(now=0.60)
    assert len(b.take(now=0.60)) == 1
    assert len(b) == 0

    b2 = CrossStreamBatcher(max_chunks=2, window=10.0)
    b2.submit(DetectRequest(frames=f, arrival=0.0))
    b2.submit(DetectRequest(frames=f, arrival=0.0))
    assert b2.ready(now=0.0)              # full beats the window
    assert len(b2.take(now=0.0)) == 2

    # float-rounding regression: the flush event fires at exactly
    # arrival + window; summation error (0.3 + 0.05 -> 0.04999...) must
    # not strand the batch
    b3 = CrossStreamBatcher(max_chunks=8, window=0.05)
    b3.submit(DetectRequest(frames=f, arrival=0.3))
    assert b3.ready(now=0.3 + 0.05)


def test_cross_stream_batcher_arrival_gated_readiness():
    """A submitted-but-not-yet-arrived request must not trigger or join a
    flush: its simulated upload has not completed."""
    b = CrossStreamBatcher(max_chunks=4, window=0.0)
    f = np.zeros((2, 8, 8, 3), np.float32)
    b.submit(DetectRequest(frames=f, arrival=1.0))
    assert len(b) == 1 and b.pending_frames == 2
    assert not b.ready(now=0.5)            # uploaded, not arrived
    assert b.next_deadline() == 1.0        # event horizon at its arrival
    assert b.ready(now=1.0)
    assert len(b.take(now=1.0)) == 1


def test_cross_stream_batcher_float_tolerance_boundary():
    """The flush event fires at exactly oldest+window; float summation
    (e.g. 0.3 + 0.05 -> 0.35000000000000003 vs 0.34999999999999997) must
    not strand the batch on either side of the 1e-9 tolerance."""
    f = np.zeros((1, 8, 8, 3), np.float32)
    for arrival, window in [(0.3, 0.05), (0.1, 0.2), (0.7, 0.1)]:
        b = CrossStreamBatcher(max_chunks=8, window=window)
        b.submit(DetectRequest(frames=f, arrival=arrival))
        fire = arrival + window            # how the scheduler computes it
        assert not b.ready(now=fire - 1e-6)
        assert b.ready(now=fire)           # exact event time
        assert b.ready(now=fire - 1e-10)   # inside the tolerance band


def test_cross_stream_batcher_deadline_driven_flush():
    """SLO requests flush when the tightest deadline would otherwise be
    missed given the estimated batch service time — not on the window."""
    f4 = np.zeros((4, 8, 8, 3), np.float32)
    b = CrossStreamBatcher(max_chunks=8, window=10.0,   # window is idle
                           service_model=lambda frames: 0.01 * frames)
    b.submit(DetectRequest(frames=f4, arrival=0.0, deadline=0.5))
    # flush-by = deadline - est = 0.5 - 0.04
    assert not b.ready(now=0.40)
    assert b.next_deadline() == pytest.approx(0.46)
    assert b.ready(now=0.46)
    # a second pending request grows the batch -> larger estimated service
    # time -> the same deadline now forces an *earlier* flush
    b2 = CrossStreamBatcher(max_chunks=8, window=10.0,
                            service_model=lambda frames: 0.01 * frames)
    b2.submit(DetectRequest(frames=f4, arrival=0.0, deadline=0.5))
    b2.submit(DetectRequest(frames=f4, arrival=0.0, deadline=9.9))
    assert b2.next_deadline() == pytest.approx(0.42)
    assert b2.ready(now=0.42) and not b2.ready(now=0.41)
    # an already-missed deadline flushes immediately on arrival
    b3 = CrossStreamBatcher(max_chunks=8, window=10.0,
                            service_model=lambda frames: 1.0)
    b3.submit(DetectRequest(frames=f4, arrival=0.2, deadline=0.1))
    assert b3.ready(now=0.2)


def test_cross_stream_batcher_weighted_fair_order():
    """When the batch is full, a high-weight stream's chunks preempt the
    backlog of an equal-arrival bulk stream (WFQ virtual finish times)."""
    f = np.zeros((2, 8, 8, 3), np.float32)
    prio, bulk = object(), object()
    b = CrossStreamBatcher(max_chunks=2, window=0.0)
    # bulk stream submits first: strict arrival order would pick its two
    b.submit(DetectRequest(frames=f, arrival=0.0, stream=bulk, weight=1.0))
    b.submit(DetectRequest(frames=f, arrival=0.0, stream=bulk, weight=1.0))
    b.submit(DetectRequest(frames=f, arrival=0.0, stream=prio, weight=8.0))
    b.submit(DetectRequest(frames=f, arrival=0.0, stream=prio, weight=8.0))
    batch = b.take(now=0.0)
    assert [r.stream for r in batch] == [prio, prio]
    # equal weights degenerate to (stream-interleaved) arrival order
    b2 = CrossStreamBatcher(max_chunks=2, window=0.0)
    b2.submit(DetectRequest(frames=f, arrival=0.0, stream=bulk))
    b2.submit(DetectRequest(frames=f, arrival=0.0, stream=prio))
    assert [r.stream for r in b2.take(now=0.0)] == [bulk, prio]


def test_pack_frames_padding_semantics():
    a = np.random.rand(2, 8, 8, 3).astype(np.float32)
    b = np.random.rand(3, 8, 8, 3).astype(np.float32)
    # single request: exact shape, no padding (bit-identical fast path)
    batch, slices, pad = pack_frames_device([a])
    assert batch.shape[0] == 2 and pad == 0
    np.testing.assert_array_equal(np.asarray(batch), a)
    # multi request: concatenated then zero-padded to the next bucket
    batch, slices, pad = pack_frames_device([a, b], buckets=(2, 4, 8))
    assert batch.shape[0] == 8 and pad == 3
    np.testing.assert_array_equal(
        np.asarray(batch),
        np.concatenate([a, b, np.zeros((3, 8, 8, 3), np.float32)]))
    np.testing.assert_array_equal(np.asarray(batch[slices[0]]), a)
    np.testing.assert_array_equal(np.asarray(batch[slices[1]]), b)
    # overflow past the largest bucket: exact concatenated size, no padding
    # (and no truncation — every frame must reach the detector)
    big = [np.random.rand(3, 8, 8, 3).astype(np.float32) for _ in range(4)]
    batch, slices, pad = pack_frames_device(big, buckets=(2, 4, 8))
    assert batch.shape[0] == 12 and pad == 0
    np.testing.assert_array_equal(np.asarray(batch), np.concatenate(big))
    for arr, sl in zip(big, slices):
        np.testing.assert_array_equal(np.asarray(batch[sl]), arr)


# ---------------------------------------------------------------------------
# Autoscaler sees real queue depths
# ---------------------------------------------------------------------------
def test_autoscaler_fed_real_queue_depth(models):
    det_params, clf_params, _ = models
    streams = [_chunks(300 + i, 2) for i in range(6)]
    scaler = Autoscaler(min_devices=1, max_devices=4, cooldown_s=0.0,
                        target_queue_per_device=2.0)
    multi = MultiStreamCoordinator(HighLowProtocol(DET, CLF), det_params,
                                   clf_params, streams,
                                   max_batch_chunks=2, batch_window=0.0,
                                   autoscaler=scaler)
    multi.run(learn=False)
    assert scaler.history                      # decisions were recorded
    assert max(h["queue"] for h in scaler.history) > 0   # real backlog seen
    assert scaler.summary()["peak_devices"] >= 1
    assert multi.scheduler.cloud_executor.num_devices >= 1


# ---------------------------------------------------------------------------
# Multi-replica sharding: batches split across the router's replica pool
# ---------------------------------------------------------------------------
def test_replica_sharding_conserves_results(models):
    det_params, clf_params, _ = models
    streams = [_chunks(400 + i, 2) for i in range(4)]
    multi = MultiStreamCoordinator(HighLowProtocol(DET, CLF), det_params,
                                   clf_params, streams, max_batch_chunks=4,
                                   batch_window=0.05, cloud_replicas=2)
    mout = multi.run(learn=False)
    rep = multi.report()
    assert rep["replicas"] == 2
    # both replicas actually served sub-batches
    mon = multi.scheduler.monitor
    assert mon.counters["served_replica_0"] > 0
    assert mon.counters["served_replica_1"] > 0
    # sharding must not change any stream's detections
    for i, chunks in enumerate(streams):
        solo = CloudFogCoordinator(HighLowProtocol(DET, CLF), det_params,
                                   clf_params)
        sout = solo.run(chunks, learn=False)
        assert mout[f"cam{i}"].f1 == sout.f1
        assert mout[f"cam{i}"].bandwidth == sout.bandwidth
        assert mout[f"cam{i}"].cloud_cost == sout.cloud_cost


def test_autoscaler_scales_replica_pool(models):
    det_params, clf_params, _ = models
    streams = [_chunks(500 + i, 2) for i in range(8)]
    scaler = Autoscaler(min_devices=1, max_devices=6, cooldown_s=0.0,
                        target_queue_per_device=1.0, unit="replicas")
    multi = MultiStreamCoordinator(HighLowProtocol(DET, CLF), det_params,
                                   clf_params, streams, max_batch_chunks=2,
                                   batch_window=0.0, cloud_replicas=2,
                                   autoscaler=scaler)
    multi.run(learn=False)
    assert multi.scheduler.router.scale_unit == "replicas"
    mon = multi.scheduler.monitor
    assert mon.counters["replicas_added"] > 0       # pool actually grew
    assert len(multi.scheduler.router.replicas) >= 2
    assert scaler.summary()["scale_ups"] > 0
    # the primary replica survives any scale-down
    assert multi.scheduler.router.replicas[0].executor \
        is multi.scheduler.cloud_executor


# ---------------------------------------------------------------------------
# SLO-aware batching + weighted fair queueing, end to end
# ---------------------------------------------------------------------------
def test_slo_deadline_flush_beats_idle_window(models):
    """With a huge fixed window, SLO streams must still flush on their
    deadlines (deadline-driven policy overrides the window) and the monitor
    must record attainment."""
    det_params, clf_params, _ = models
    streams = [_chunks(600 + i, 2) for i in range(2)]
    specs = [StreamSpec(name=f"cam{i}", chunks=c, slo=5.0)
             for i, c in enumerate(streams)]
    multi = MultiStreamCoordinator(HighLowProtocol(DET, CLF), det_params,
                                   clf_params, specs, max_batch_chunks=8,
                                   batch_window=60.0)   # absurd window
    out = multi.run(learn=False)
    mon = multi.scheduler.monitor
    att = mon.values("slo_attained")
    assert len(att) == 4                      # one sample per chunk
    assert all(a == 1.0 for a in att)         # 5s SLO easily met
    assert multi.report()["slo_attainment"] == 1.0
    # no chunk waited anywhere near the 60s window
    for r in out.values():
        assert all(lat < 5.0 for lat in r.latencies)


def test_wfq_prioritizes_high_weight_stream(models):
    """Under a backlogged detector, the high-weight camera's chunks must
    see less batch-formation/queue wait than the bulk cameras'."""
    det_params, clf_params, _ = models
    prio = StreamSpec(name="prio", chunks=_chunks(700, 3), weight=16.0)
    bulk = [StreamSpec(name=f"bulk{i}", chunks=_chunks(710 + i, 3))
            for i in range(5)]
    multi = MultiStreamCoordinator(HighLowProtocol(DET, CLF), det_params,
                                   clf_params, [prio] + bulk,
                                   max_batch_chunks=2, batch_window=0.2)
    multi.run(learn=False)
    waits = {s.name: [r.latency.queue_wait for _, r, _ in st.results]
             for s, st in zip(multi.specs, multi._states)}
    bulk_mean = np.mean([w for n, ws in waits.items() if n != "prio"
                         for w in ws])
    assert np.mean(waits["prio"]) < bulk_mean


# ---------------------------------------------------------------------------
# Replica outage mid multi-stream run: re-queue, zero chunk loss
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("fail_at", [0.0, 0.15])
def test_replica_outage_requeues_without_chunk_loss(models, fail_at):
    det_params, clf_params, fb_params = models
    streams = [_chunks(800 + i, 3) for i in range(4)]
    fault = FaultTolerantCoordinator(NetworkModel())
    fault.fail_replica(1, at=fail_at)
    multi = MultiStreamCoordinator(
        HighLowProtocol(DET, CLF), det_params, clf_params, streams,
        max_batch_chunks=4, batch_window=0.05, cloud_replicas=2,
        fallback_params=fb_params, fallback_cfg=FB, fault=fault)
    mout = multi.run(learn=False)

    # the outage was detected and survivors took over
    assert any(e["event"] == "replica_failover" for e in fault.events)
    assert multi.scheduler.router.load_report()["healthy"] == 1
    # zero lost, zero double-counted: every submitted chunk finalizes
    # exactly once, in order, on its own stream
    seen = set()
    for i, chunks in enumerate(streams):
        st = multi.scheduler.streams[f"cam{i}"]
        assert [id(c) for c, _, _ in st.results] == [id(c) for c in chunks]
        for c, res, mode in st.results:
            assert id(c) not in seen
            seen.add(id(c))
            assert res.boxes.shape[0] == c.frames.shape[0]
        assert len(mout[f"cam{i}"].latencies) == len(chunks)
    assert len(seen) == sum(len(c) for c in streams)


def test_all_replicas_dead_falls_back_to_fog(models):
    det_params, clf_params, fb_params = models
    streams = [_chunks(900 + i, 2) for i in range(2)]
    fault = FaultTolerantCoordinator(NetworkModel())
    fault.fail_replica(0, at=0.0)
    fault.fail_replica(1, at=0.0)
    multi = MultiStreamCoordinator(
        HighLowProtocol(DET, CLF), det_params, clf_params, streams,
        max_batch_chunks=2, batch_window=0.0, cloud_replicas=2,
        fallback_params=fb_params, fallback_cfg=FB, fault=fault)
    mout = multi.run(learn=False)
    for i, chunks in enumerate(streams):
        r = mout[f"cam{i}"]
        assert len(r.latencies) == len(chunks)    # nothing dropped
        assert all(m == "fog-fallback" for m in r.modes)
        assert r.cloud_cost == 0.0                # no cloud frames billed
    assert multi.scheduler.router.load_report()["healthy"] == 0


# ---------------------------------------------------------------------------
# Fog fallback keeps real HITL hand-off shapes (outage regression)
# ---------------------------------------------------------------------------
def test_fog_fallback_feature_shapes(models):
    det_params, clf_params, fb_params = models
    chunks = _chunks(5, 2)
    learner = IncrementalLearner(num_classes=CLF.num_classes, trigger=2,
                                 budget=16)
    coord = CloudFogCoordinator(
        HighLowProtocol(DET, CLF), det_params, clf_params,
        fallback_params=fb_params, fallback_cfg=FB, learner=learner)
    coord.network.up = False
    coord.process_chunk(chunks[0], learn=True)     # first miss tolerated
    res = coord.process_chunk(chunks[1], learn=True)  # failover
    assert coord.fault.mode == "fog-fallback"
    # the stub must carry the classifier's real feature/score dims,
    # derived from clf_params — not a zero-width placeholder
    assert res.fog_features.shape[-1] == CLF.feature_dim + 1
    assert res.fog_scores.shape[-1] == CLF.num_classes
    # a feature row from the outage stub is shape-compatible with the learner
    import jax.numpy as jnp
    assert learner.collect(res.fog_features[0, 0], 0)
    assert learner.collect(res.fog_features[0, 1], 1)
    newW, updated = learner.maybe_update(jnp.asarray(clf_params["W"]))
    assert updated and newW.shape == np.asarray(clf_params["W"]).shape
