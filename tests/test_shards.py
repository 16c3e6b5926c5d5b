"""Claim-check ingestion + sharded scheduling semantics.

The sharding contract: one shard is *bitwise* today's scheduler, K shards
replay the unsharded simulated timeline at small scale, stolen work is
dispatched exactly once (even through a replica outage), and the artifact
store never evicts a payload something still references.  All checks are
execution semantics on untrained models — no accuracy, module stays fast."""
import dataclasses
import warnings

import jax
import numpy as np
import pytest

from repro.configs.vpaas_video import ClassifierConfig, DetectorConfig
from repro.core.bandwidth import NetworkModel
from repro.core.protocol import HighLowProtocol
from repro.learning.plane import ContinualLearningPlane, LearningConfig
from repro.models import classifier as clf_mod
from repro.models import detector as det_mod
from repro.serving.batching import CrossStreamBatcher
from repro.serving.fault import FaultTolerantCoordinator
from repro.serving.graph import GraphScheduler, VideoFunctionGraph
from repro.serving.ingest import ArtifactStore, ClaimCheck, content_key
from repro.serving.shards import ShardedScheduler
from reference_check import assert_matches_reference

DET = DetectorConfig(name="shard-test-det", image_hw=(32, 32),
                     widths=(8, 16))
CLF = ClassifierConfig(name="shard-test-clf", crop_hw=(16, 16),
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


def _graph(models):
    det_params, clf_params = models
    return VideoFunctionGraph(HighLowProtocol(DET, CLF), det_params,
                              clf_params), clf_params


def _run(sched, add, streams, clf_params):
    states = [add(f"cam{i}", W=clf_params["W"]) for i in range(len(streams))]
    for st, chunks in zip(states, streams):
        for c in chunks:
            sched.submit(st, c, learn=False)
    sched.run_until_idle()
    return states


def _assert_results_bitwise(st_a, st_b):
    assert len(st_a.results) == len(st_b.results)
    for (c1, r1, m1), (c2, r2, m2) in zip(st_a.results, st_b.results):
        assert c1 is c2 and m1 == m2
        np.testing.assert_array_equal(r1.boxes, r2.boxes)
        np.testing.assert_array_equal(r1.labels, r2.labels)
        np.testing.assert_array_equal(r1.valid, r2.valid)
        np.testing.assert_array_equal(r1.fog_features, r2.fog_features)
        np.testing.assert_array_equal(r1.fog_scores, r2.fog_scores)
        assert r1.latency.total == r2.latency.total
        assert r1.wan_bytes == r2.wan_bytes
        assert r1.coord_bytes == r2.coord_bytes


# report keys that depend on host wall time (or exist only on the sharded
# wrapper) — everything else must match exactly.  ``peaks=False`` also
# drops resource-peak gauges: a K-way partition changes which buffers are
# simultaneously live, not the simulated timeline.
def _assert_reports_match(rep_a, rep_b, peaks=True):
    skip = ["wall", "per_s", "overhead"]
    if not peaks:
        # partition-dependent gauges: which buffers are simultaneously
        # live, per-shard occupancy spans, and the event count (stale
        # flush re-pushes scan only the shard's own queue — the O(Q)
        # work sharding exists to remove).  sched_finalizes stays exact.
        skip += ["peak", "occupancy", "sched_events"]
    extra = {"shards", "steals", "store", "store_spills", "batch_stolen",
             "batch_adopted"}
    keys = (set(rep_a) | set(rep_b)) - extra
    for k in keys:
        if any(s in k for s in skip):
            continue
        assert rep_a.get(k) == rep_b.get(k), k


# ---------------------------------------------------------------------------
# 1 shard == today's scheduler, bitwise (with the claim-check store on)
# ---------------------------------------------------------------------------
def test_one_shard_bitwise_identity(models):
    graph, clf_params = _graph(models)
    streams = [_chunks(300 + i, 3) for i in range(4)]

    plain = GraphScheduler(
        graph, batcher=CrossStreamBatcher(max_chunks=4, window=0.05))
    _run(plain, plain.add_stream, streams, clf_params)

    sharded = ShardedScheduler(
        graph, num_shards=1,
        batcher_factory=lambda i: CrossStreamBatcher(max_chunks=4,
                                                     window=0.05))
    _run(sharded, sharded.add_stream, streams, clf_params)

    for name in plain.streams:
        _assert_results_bitwise(plain.streams[name], sharded.streams[name])
    _assert_reports_match(plain.throughput_report(),
                          sharded.throughput_report())
    # the store actually carried the payloads (events were claim checks)
    srep = sharded.throughput_report()["store"]
    assert srep["puts"] == sum(len(s) for s in streams)
    assert srep["bytes_current"] <= srep["bytes_peak"]


# ---------------------------------------------------------------------------
# K shards replay the unsharded simulated timeline at small scale
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("num_shards", [2, 3])
def test_k_shards_match_unsharded_oracle(models, num_shards):
    graph, clf_params = _graph(models)
    # distinct content => distinct encode/arrival times => no timeline ties
    streams = [_chunks(400 + i, 3) for i in range(6)]

    # max_chunks=1 / window=0: batch composition cannot depend on the
    # partition, so the merged K-shard timeline must equal one scheduler's
    oracle = GraphScheduler(
        graph, batcher=CrossStreamBatcher(max_chunks=1, window=0.0))
    _run(oracle, oracle.add_stream, streams, clf_params)

    sharded = ShardedScheduler(graph, num_shards=num_shards, steal=False)
    _run(sharded, sharded.add_stream, streams, clf_params)

    for name in oracle.streams:
        _assert_results_bitwise(oracle.streams[name], sharded.streams[name])
    _assert_reports_match(oracle.throughput_report(),
                          sharded.throughput_report(), peaks=False)


# ---------------------------------------------------------------------------
# work stealing: overflow moves, every chunk still finalizes exactly once
# ---------------------------------------------------------------------------
def test_work_stealing_conserves_chunks_under_outage(models):
    graph, clf_params = _graph(models)
    n_busy = 6
    # identical chunk objects across streams: identical encode/transfer
    # times make all arrivals tie, so one flush sees 6 due >> max_chunks=2
    # and the overflow has to move
    shared = _chunks(500, 3)
    streams = [list(shared) for _ in range(n_busy)]
    fault = FaultTolerantCoordinator(NetworkModel())
    fault.fail_replica(1, at=0.15)   # dies mid-run: in-service work requeues

    sharded = ShardedScheduler(
        graph, num_shards=2,
        batcher_factory=lambda i: CrossStreamBatcher(max_chunks=2,
                                                     window=0.05),
        cloud_replicas=2, fault=fault)
    # pin every stream to shard 0: shard 1 exists only to have work stolen
    states = [sharded.add_stream(f"cam{i}", W=clf_params["W"], shard=0)
              for i in range(n_busy)]
    for st, chunks in zip(states, streams):
        for c in chunks:
            sharded.submit(st, c, learn=False)
    sharded.run_until_idle()

    assert sharded.steals > 0                       # overflow actually moved
    assert any(e["event"] == "replica_failover" for e in fault.events)
    assert sharded.router.load_report()["healthy"] == 1
    # conservation: every submitted chunk finalized exactly once, in order,
    # on its own stream — stolen or not, requeued or not
    for i, chunks in enumerate(streams):
        st = sharded.streams[f"cam{i}"]
        assert [id(c) for c, _, _ in st.results] == [id(c) for c in chunks]
    rep = sharded.throughput_report()
    assert rep["batch_stolen"] == rep["batch_adopted"] == sharded.steals
    # nothing left behind in any batcher or event heap
    for sh in sharded.shards:
        assert len(sh.batcher) == 0 and not sh._events


# ---------------------------------------------------------------------------
# artifact store: refcount + TTL eviction semantics
# ---------------------------------------------------------------------------
def test_store_never_evicts_referenced_payload():
    store = ArtifactStore(ttl=1.0)
    frames = np.arange(24, dtype=np.float32).reshape(2, 2, 2, 3)
    key = content_key(frames, "salt")

    ref1 = store.put(frames, key=key, now=0.0)
    ref2 = store.put(frames.copy(), key=key, now=0.1)   # dedup: same bytes
    assert isinstance(ref1, ClaimCheck) and ref1.key == ref2.key
    assert store.stats["dedup_hits"] == 1 and len(store) == 1
    # physical holds ONE copy; the heap baseline would hold two
    assert store.stats["bytes_current"] == frames.nbytes
    assert store.stats["logical_bytes_current"] == 2 * frames.nbytes

    store.release(ref1, now=0.2)
    store.sweep(now=100.0)          # far past TTL: ref2 still holds it
    assert len(store) == 1
    np.testing.assert_array_equal(store.get(ref2), frames)

    # re-acquire between release and sweep: the stale expiry record from
    # the first release must not evict the re-referenced payload
    store.release(ref2, now=100.0)
    ref3 = store.put(frames.copy(), key=key, now=100.5)
    store.sweep(now=200.0)
    np.testing.assert_array_equal(store.get(ref3), frames)

    store.release(ref3, now=200.0)
    store.sweep(now=200.5)          # within TTL: retained for dedup
    assert len(store) == 1
    store.sweep(now=201.5)          # past TTL with zero refs: evicted
    assert len(store) == 0 and store.stats["evictions"] == 1
    assert store.stats["bytes_current"] == 0
    with pytest.raises(KeyError):
        store.get(ref3)


def test_store_eviction_under_serving_load(models):
    graph, clf_params = _graph(models)
    # repeat each chunk so dedup and re-acquire paths run under a TTL
    # short enough to evict between rounds
    base = _chunks(600, 2)
    streams = [[base[0], base[1], base[0], base[1]] for _ in range(2)]
    store = ArtifactStore(ttl=1e-6)
    sharded = ShardedScheduler(graph, num_shards=1, store=store)
    _run(sharded, sharded.add_stream, streams, clf_params)
    for name, st in sharded.streams.items():
        assert len(st.results) == 4       # nothing dropped by eviction
    assert store.stats["evictions"] > 0   # the tiny TTL actually evicted
    store.sweep(now=float("inf"))
    assert len(store) == 0                # nothing leaked either


# ---------------------------------------------------------------------------
# per-site detector thresholds
# ---------------------------------------------------------------------------
def test_stream_thresholds_fused_matches_reference(models):
    graph, clf_params = _graph(models)
    det_params, _ = models
    streams = [_chunks(700 + i, 2) for i in range(3)]
    s = GraphScheduler(
        graph, batcher=CrossStreamBatcher(max_chunks=3, window=0.05))
    states = [s.add_stream(f"cam{i}", W=clf_params["W"])
              for i in range(len(streams))]
    # cam1 runs off-default thresholds; cam0/cam2 stay global — one
    # fused flush mixes default and override frames
    s.set_stream_thresholds("cam1", theta_cls=0.55, theta_loc=0.3)
    for st, chunks in zip(states, streams):
        for c in chunks:
            s.submit(st, c, learn=False)
    s.run_until_idle()
    proto = graph.protocol
    site = HighLowProtocol(DET, CLF, pcfg=dataclasses.replace(
        proto.pcfg, theta_cls=0.55, theta_loc=0.3))
    for name, st in s.streams.items():
        ref_proto = site if name == "cam1" else proto
        assert len(st.results) == 2
        for chunk, res, _ in st.results:
            assert_matches_reference(res, ref_proto.process_chunk(
                det_params, clf_params, chunk.frames, W=st.W))


def test_stream_thresholds_defaults_bit_compatible(models):
    graph, clf_params = _graph(models)
    pcfg = graph.protocol.pcfg
    streams = [_chunks(750 + i, 2) for i in range(2)]
    plain = GraphScheduler(
        graph, batcher=CrossStreamBatcher(max_chunks=2, window=0.05))
    _run(plain, plain.add_stream, streams, clf_params)

    # explicitly pinning the global defaults routes through the dynamic
    # stage but must reproduce the static stage bit-for-bit
    pinned = GraphScheduler(
        graph, batcher=CrossStreamBatcher(max_chunks=2, window=0.05))
    states = [pinned.add_stream(f"cam{i}", W=clf_params["W"])
              for i in range(len(streams))]
    for i in range(len(streams)):
        pinned.set_stream_thresholds(f"cam{i}", theta_cls=pcfg.theta_cls,
                                     theta_loc=pcfg.theta_loc)
    for st, chunks in zip(states, streams):
        for c in chunks:
            pinned.submit(st, c, learn=False)
    pinned.run_until_idle()

    for name in plain.streams:
        _assert_results_bitwise(plain.streams[name], pinned.streams[name])
    # restoring defaults returns to the static fused stage
    pinned.set_stream_thresholds("cam0")
    assert pinned.streams["cam0"].theta_cls is None


def test_plane_adapts_thresholds_on_drift_episode(models):
    graph, clf_params = _graph(models)
    sched = GraphScheduler(
        graph, batcher=CrossStreamBatcher(max_chunks=1, window=0.0))
    sched.add_stream("cam0", W=clf_params["W"])
    plane = ContinualLearningPlane(
        CLF.num_classes, LearningConfig(adapt_theta_cls=0.4,
                                        adapt_theta_loc=0.25))
    site = plane._default_site
    plane._apply_theta(site, sched, "cam0", t=1.0)
    assert sched.streams["cam0"].theta_cls == 0.4
    assert sched.streams["cam0"].theta_loc == 0.25
    assert site.theta_overrides == {"cam0"}
    plane._apply_theta(site, sched, "cam0", t=1.5)   # idempotent
    plane._restore_theta(site, sched, t=2.0)
    assert sched.streams["cam0"].theta_cls is None
    assert sched.streams["cam0"].theta_loc is None
    assert not site.theta_overrides
    events = [e for e in sched.monitor.events
              if e["event"] == "stream_thresholds"]
    assert len(events) == 2


# ---------------------------------------------------------------------------
# donated detect dispatch: bitwise no-op where donation is unsupported
# ---------------------------------------------------------------------------
def test_donated_detect_bitwise_on_cpu(models):
    graph, clf_params = _graph(models)
    streams = [_chunks(800 + i, 2) for i in range(3)]
    plain = GraphScheduler(
        graph, batcher=CrossStreamBatcher(max_chunks=3, window=0.05))
    _run(plain, plain.add_stream, streams, clf_params)

    donating = GraphScheduler(
        graph, batcher=CrossStreamBatcher(max_chunks=3, window=0.05))
    donating.donate_detect = True    # forced on: CPU warns and ignores it
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        _run(donating, donating.add_stream, streams, clf_params)

    for name in plain.streams:
        _assert_results_bitwise(plain.streams[name], donating.streams[name])
