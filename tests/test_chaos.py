"""Chaos plane: multi-domain fault injection, hedged dispatch, probes,
corruption recovery, and the billing/claim-check bookkeeping that must
survive every failure path.

The degradation contract under test: an idle injector is bitwise free,
no fault class loses a chunk, flapped replicas re-admit with clean load
stats, corrupted artifacts are detected and re-derived (never served),
hedged duplicates are billed, and dead replicas stop accruing keep-alive
spend at their failure time.  All on untrained models — execution
semantics only."""
import jax
import numpy as np
import pytest

from repro.configs.vpaas_video import ClassifierConfig, DetectorConfig
from repro.core.bandwidth import NetworkModel
from repro.core.protocol import HighLowProtocol
from repro.models import classifier as clf_mod
from repro.models import detector as det_mod
from repro.serving.batching import CrossStreamBatcher
from repro.serving.executor import Executor
from repro.serving.fault import FaultInjector
from repro.serving.graph import GraphScheduler, VideoFunctionGraph
from repro.serving.ingest import ArtifactCorrupted, ArtifactStore
from repro.serving.router import Router
from repro.serving.shards import ShardedScheduler
from repro.serving.tenancy import CostModel

DET = DetectorConfig(name="chaos-test-det", image_hw=(32, 32),
                     widths=(8, 16))
CLF = ClassifierConfig(name="chaos-test-clf", crop_hw=(16, 16),
                       widths=(8, 16), feature_dim=16)


@pytest.fixture(scope="module")
def models():
    det_params = det_mod.init_detector(DET, jax.random.PRNGKey(0))
    clf_params = clf_mod.init_classifier(CLF, jax.random.PRNGKey(1))
    return det_params, clf_params


def _graph(models):
    det_params, clf_params = models
    return VideoFunctionGraph(HighLowProtocol(DET, CLF), det_params,
                              clf_params), clf_params


def _chunks(seed, n, frames=2):
    from repro.video import synthetic
    rng = np.random.default_rng(seed)
    return [synthetic.make_chunk(rng, "traffic", num_frames=frames,
                                 hw=(32, 32)) for _ in range(n)]


def _sched(graph, **kw):
    kw.setdefault("batcher", CrossStreamBatcher(max_chunks=4, window=0.05))
    return GraphScheduler(graph, **kw)


def _run(sched, add, streams, clf_params, slo=None):
    states = [add(f"cam{i}", W=clf_params["W"], slo=slo)
              for i in range(len(streams))]
    for st, chunks in zip(states, streams):
        for c in chunks:
            sched.submit(st, c, learn=False)
    sched.run_until_idle()
    return states


def _assert_results_bitwise(st_a, st_b):
    assert len(st_a.results) == len(st_b.results)
    for (c1, r1, _), (c2, r2, _) in zip(st_a.results, st_b.results):
        assert c1 is c2
        np.testing.assert_array_equal(r1.boxes, r2.boxes)
        np.testing.assert_array_equal(r1.labels, r2.labels)
        np.testing.assert_array_equal(r1.valid, r2.valid)
        assert r1.latency.total == r2.latency.total


def _assert_reports_match(rep_a, rep_b):
    skip = ["wall", "per_s", "overhead"]
    extra = {"shards", "steals", "store", "store_spills", "batch_stolen",
             "batch_adopted"}
    for k in (set(rep_a) | set(rep_b)) - extra:
        if any(s in k for s in skip):
            continue
        assert rep_a.get(k) == rep_b.get(k), k


# ---------------------------------------------------------------------------
# fault-domain unit queries
# ---------------------------------------------------------------------------
def test_brownout_degrades_wan_time_only_inside_window():
    net = NetworkModel()
    base = net.wan_time(1e6)
    net.brownouts.append((1.0, 2.0, 0.5, 2.0))
    # outside the window (or with no t supplied) the ORIGINAL arithmetic
    # path runs — bitwise, not just approximately equal
    assert net.wan_time(1e6) == base
    assert net.wan_time(1e6, t=0.5) == base
    assert net.wan_time(1e6, t=2.0) == base
    degraded = net.wan_time(1e6, t=1.5)
    assert degraded == net.wan_rtt_s * 2.0 + 1e6 * 8.0 / (net.wan_mbps
                                                          * 0.5 * 1e6)
    # overlapping windows compound
    net.brownouts.append((1.4, 1.6, 0.5, 1.0))
    bw, rtt = net.degradation(1.5)
    assert bw == 0.25 and rtt == 2.0


def test_injector_flap_straggler_queries():
    fi = FaultInjector(network=NetworkModel())
    fi.flap_replica(1, 2.0, 3.0)
    assert not fi.replica_down(1, 1.9)
    assert fi.replica_down(1, 2.0) and fi.replica_down(1, 2.9)
    assert not fi.replica_down(1, 3.0)
    # a flap overlapping the service window interrupts it at its onset
    assert fi.fail_time_in(1, 1.0, 2.5) == 2.0
    assert fi.fail_time_in(1, 2.2, 2.8) == 2.0
    assert fi.fail_time_in(1, 3.1, 4.0) is None
    # transient: flaps recover, permanent deaths don't
    assert fi.transient(1, 2.5)
    fi.fail_replica(2, 1.0)
    assert fi.replica_down(2, 1.5) and not fi.transient(2, 1.5)
    # stragglers multiply inside their windows
    fi.add_straggler(0, 0.0, 10.0, 4.0)
    fi.add_straggler(0, 5.0, 10.0, 2.0)
    assert fi.service_multiplier(0, 1.0) == 4.0
    assert fi.service_multiplier(0, 6.0) == 8.0
    assert fi.service_multiplier(0, 10.0) == 1.0
    assert fi.service_multiplier(3, 1.0) == 1.0


def test_due_corruptions_pops_with_limit():
    fi = FaultInjector(network=NetworkModel())
    fi.inject_corruption(1.0, count=3)
    assert fi.due_corruptions(0.5) == 0 and fi.corruptions_injected == 0
    # a flush with only 2 distinct payloads applies 2; the third stays
    # queued so injected only ever counts applied faults
    assert fi.due_corruptions(1.0, limit=2) == 2
    assert fi.corruptions_injected == 2
    assert fi.due_corruptions(1.5) == 1
    assert fi.corruptions_injected == 3
    assert fi.due_corruptions(9.9) == 0


# ---------------------------------------------------------------------------
# artifact-store integrity
# ---------------------------------------------------------------------------
def test_store_integrity_detects_and_repairs():
    store = ArtifactStore(integrity=True)
    payload = np.arange(32, dtype=np.float32)
    ref = store.put(payload.copy(), key="k0")
    np.testing.assert_array_equal(store.get(ref), payload)
    store.corrupt("k0")
    with pytest.raises(ArtifactCorrupted) as ei:
        store.get(ref)
    assert ei.value.key == "k0"
    assert store.stats["corruptions_detected"] == 1
    store.repair("k0", payload.copy())
    np.testing.assert_array_equal(store.get(ref), payload)
    assert store.stats["corruptions_repaired"] == 1
    assert store.live_refs() == {"k0": 1}
    store.release(ref)
    assert store.live_refs() == {}


def test_store_without_integrity_serves_corrupted_bytes():
    # documents WHY integrity mode exists: without the checksum the flip
    # is invisible and garbage is served
    store = ArtifactStore()
    payload = np.arange(32, dtype=np.float32)
    ref = store.put(payload.copy(), key="k0")
    store.corrupt("k0")
    assert not np.array_equal(store.get(ref), payload)
    assert store.stats["corruptions_detected"] == 0


# ---------------------------------------------------------------------------
# idle injector == plain scheduler, bitwise (results AND report)
# ---------------------------------------------------------------------------
def test_idle_injector_bitwise_identity(models):
    graph, clf_params = _graph(models)
    streams = [_chunks(400 + i, 3) for i in range(4)]
    plain = _sched(graph)
    sp = _run(plain, plain.add_stream, streams, clf_params, slo=0.5)
    idle = _sched(graph, fault=FaultInjector(network=graph.protocol.network))
    si = _run(idle, idle.add_stream, streams, clf_params, slo=0.5)
    for a, b in zip(sp, si):
        _assert_results_bitwise(a, b)
    _assert_reports_match(plain.throughput_report(),
                          idle.throughput_report())
    assert idle.chaos_stats["hedges"] == 0


def test_idle_injector_identity_sharded(models):
    graph, clf_params = _graph(models)
    streams = [_chunks(430 + i, 3) for i in range(4)]

    def build(fault):
        sched = ShardedScheduler(
            graph, num_shards=2, store=ArtifactStore(integrity=True),
            batcher_factory=lambda i: CrossStreamBatcher(max_chunks=4,
                                                         window=0.05),
            cloud_replicas=2, fault=fault)
        return sched, _run(sched, sched.add_stream, streams, clf_params,
                           slo=0.5)

    plain, sp = build(None)
    idle, si = build(FaultInjector(network=graph.protocol.network))
    for a, b in zip(sp, si):
        _assert_results_bitwise(a, b)
    _assert_reports_match(plain.throughput_report(),
                          idle.throughput_report())


# ---------------------------------------------------------------------------
# flap storm: probes re-admit, zero loss, load stats reset
# ---------------------------------------------------------------------------
def test_flap_probe_readmits_replica_zero_loss(models):
    graph, clf_params = _graph(models)
    streams = [_chunks(460 + i, 3) for i in range(6)]
    fi = FaultInjector(network=graph.protocol.network)
    fi.flap_replica(1, 0.05, 0.30)
    fi.flap_replica(2, 0.15, 0.45)
    sched = _sched(graph, cloud_replicas=3, fault=fi)
    states = _run(sched, sched.add_stream, streams, clf_params)
    assert sum(len(s.results) for s in states) == 18
    assert sched.chaos_stats["probes"] >= 1
    assert sched.chaos_stats["readmits"] >= 1
    assert (sched.monitor.event_count("replica_readmit")
            == sched.chaos_stats["readmits"])
    # every flapped replica is healthy again at the end
    assert sched.router.healthy_count() == 3


def test_readmit_resets_load_stats(models):
    graph, _ = _graph(models)
    proto = graph.protocol
    router = Router([Executor("cloud", graph.registry, proto.cloud),
                     Executor("cloud-1", graph.registry, proto.cloud)])
    rep = router.replicas[1]
    rep.inflight = 7
    rep.rate_ewma = 0.123
    rep.executor.busy_until = [99.0]
    router.mark_unhealthy(1)
    assert router.healthy_count() == 1
    assert router.readmit(1, now=3.0)
    assert rep.healthy and rep.inflight == 0 and rep.rate_ewma is None
    assert rep.executor.busy_until == [3.0]
    # duplicate probe chains no-op
    assert not router.readmit(1, now=4.0)


# ---------------------------------------------------------------------------
# hedged dispatch: tail cut, first-result-wins, duplicates billed
# ---------------------------------------------------------------------------
def test_hedged_dispatch_cuts_straggler_tail(models):
    graph, clf_params = _graph(models)
    streams = [_chunks(500 + i, 3) for i in range(16)]

    def run_one(hedging):
        fi = FaultInjector(network=graph.protocol.network)
        fi.add_straggler(0, 0.0, 1e9, 10.0)
        fi.add_straggler(1, 0.0, 1e9, 10.0)
        cm = CostModel()
        sched = _sched(graph, cloud_replicas=4, fault=fi, hedging=hedging,
                       cost_model=cm)
        states = _run(sched, sched.add_stream, streams, clf_params, slo=0.5)
        lats = [r.latency.total for s in states for _, r, _ in s.results]
        assert len(lats) == 48          # zero loss under the wave
        return sched, cm, np.percentile(lats, 99)

    unhedged, _, p99_u = run_one(False)
    hedged, cm, p99_h = run_one(True)
    assert unhedged.chaos_stats["hedges"] == 0
    assert hedged.chaos_stats["hedges"] >= 1
    assert hedged.chaos_stats["hedge_wins"] >= 1
    assert p99_h < p99_u
    # billing conservation: every speculative duplicate lands in the same
    # pools the pricing lines bill from, and the visibility counters see
    # exactly the booked device time
    usage = list(cm.usage.values())
    assert sum(u["hedge_invocations"] for u in usage) > 0
    assert sum(u["hedge_busy_s"] for u in usage) == pytest.approx(
        hedged.chaos_stats["hedge_busy_s"])
    for u in usage:
        assert u["cloud_busy_s"] >= u["hedge_busy_s"]
        assert u["invocations"] >= u["hedge_invocations"]


def test_executor_occupy_books_device_time(models):
    graph, _ = _graph(models)
    ex = Executor("cloud", graph.registry, graph.protocol.cloud)
    n_rec = len(ex.records)
    start, done = ex.occupy("hedge", now=1.0, model_time=0.5)
    assert start >= 1.0 and done == start + 0.5
    assert done in ex.busy_until
    assert len(ex.records) == n_rec + 1


# ---------------------------------------------------------------------------
# corruption recovery: detected, re-derived, bitwise vs fault-free
# ---------------------------------------------------------------------------
def test_corruption_detected_and_recovered_bitwise(models):
    graph, clf_params = _graph(models)
    streams = [_chunks(530 + i, 3) for i in range(4)]

    plain = _sched(graph, store=ArtifactStore(integrity=True))
    sp = _run(plain, plain.add_stream, streams, clf_params)

    fi = FaultInjector(network=graph.protocol.network)
    fi.inject_corruption(0.0, count=2)
    store = ArtifactStore(integrity=True)
    sched = _sched(graph, store=store, fault=fi)
    sc = _run(sched, sched.add_stream, streams, clf_params)

    assert fi.corruptions_injected == 2
    assert store.stats["corruptions_detected"] == 2
    assert sched.chaos_stats["corruptions_repaired"] == 2
    assert store.stats["corruptions_repaired"] == 2
    for a, b in zip(sp, sc):
        _assert_results_bitwise(a, b)


# ---------------------------------------------------------------------------
# claim-check hygiene on terminal paths
# ---------------------------------------------------------------------------
def test_terminal_failure_releases_claims(models):
    graph, clf_params = _graph(models)
    fi = FaultInjector(network=graph.protocol.network)
    fi.fail_replica(0, 0.0)
    fi.fail_replica(1, 0.0)
    store = ArtifactStore(integrity=True)
    sched = _sched(graph, store=store, cloud_replicas=2, fault=fi)
    states = [sched.add_stream(f"cam{i}", W=clf_params["W"])
              for i in range(2)]
    for st, c in zip(states, _chunks(560, 2)):
        sched.submit(st, c, learn=False)
    with pytest.raises(RuntimeError, match="no healthy replicas"):
        sched.run_until_idle()
    # the flush died, but its claims did not leak
    assert store.live_refs() == {}


def test_drain_asserts_refcounts_return_to_zero(models):
    graph, clf_params = _graph(models)
    store = ArtifactStore(integrity=True)
    sched = _sched(graph, store=store)
    _run(sched, sched.add_stream, [_chunks(590, 2)], clf_params)
    sched.drain()                                   # clean run: no leak
    store.put(np.zeros(4, dtype=np.float32), key="leaked")
    with pytest.raises(AssertionError, match="leaked"):
        sched.drain()


# ---------------------------------------------------------------------------
# keep-alive billing stops at the failure time (LOCF interval closed)
# ---------------------------------------------------------------------------
def test_mark_unhealthy_closes_keepalive_interval(models):
    graph, _ = _graph(models)
    proto = graph.protocol
    router = Router([Executor("cloud", graph.registry, proto.cloud),
                     Executor("cloud-1", graph.registry, proto.cloud)])
    cm = CostModel()
    router.cost_model = cm
    cm.observe_pool(0.0, router.healthy_count())
    router.mark_unhealthy(0, now=5.0)
    cm.close(10.0)
    # 2 replicas for 5s, then 1 survivor for 5s — NOT 2x10: the dead
    # replica stopped accruing keep-alive spend at its failure time
    assert cm.provisioned_replica_s() == pytest.approx(15.0)
    # readmission reopens the interval at the recovery time
    router.readmit(0, now=10.0)
    cm.close(12.0)
    assert cm.provisioned_replica_s() == pytest.approx(19.0)
