"""Multi-tenant serving + cost-model invariants (tenancy.py).

The tenancy contract: the default single-tenant configuration is *bitwise*
the pre-tenancy scheduler; the cost ledger conserves (per-tenant spend sums
to fleet spend exactly); WFQ keeps cross-tenant shares proportional to
weight under overload; HITL work on a fog node's background lane can never
head-of-line block that node's own serving work; a capacity-bounded
ArtifactStore spills with costs the CostModel sees; and the cost-aware
autoscaler scales up on SLO pressure but sheds replicas only past the
keep-alive/cold-start break-even.  All execution semantics on untrained
models — no accuracy, module stays fast."""
import math

import jax
import numpy as np
import pytest

from repro.configs.vpaas_video import ClassifierConfig, DetectorConfig
from repro.core.incremental import IncrementalLearner
from repro.core.protocol import HighLowProtocol
from repro.models import classifier as clf_mod
from repro.models import detector as det_mod
from repro.serving.autoscaler import CostAwareAutoscaler
from repro.serving.batching import CrossStreamBatcher
from repro.serving.executor import Executor
from repro.serving.graph import GraphScheduler, VideoFunctionGraph
from repro.serving.ingest import ArtifactStore
from repro.serving.registry import FunctionRegistry
from repro.serving.shards import ShardedScheduler
from repro.serving.tenancy import (BRONZE, GOLD, SILVER, BillingRates,
                                   CostModel, Tenancy, TenantSpec,
                                   content_pipeline, llm_cascade_pipeline)

DET = DetectorConfig(name="tenancy-test-det", image_hw=(32, 32),
                     widths=(8, 16))
CLF = ClassifierConfig(name="tenancy-test-clf", crop_hw=(16, 16),
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


def _drain(sched, states, streams, learn=False):
    for st, chunks in zip(states, streams):
        for c in chunks:
            sched.submit(st, c, learn=learn)
    sched.run_until_idle()


# ---------------------------------------------------------------------------
# Satellite: single-tenant default path is bitwise the pre-tenancy scheduler
# ---------------------------------------------------------------------------
def test_default_path_bitwise_identity(models):
    graph, clf_params = _graph(models)
    streams = [_chunks(700 + i, 3) for i in range(4)]

    plain = GraphScheduler(
        graph, batcher=CrossStreamBatcher(max_chunks=4, window=0.05))
    sa = [plain.add_stream(f"cam{i}", W=clf_params["W"], slo=5.0)
          for i in range(4)]
    _drain(plain, sa, streams)

    # tenancy machinery attached: cost model metering + a tenant tag on
    # every stream — pure accounting must not move a single event
    spec = TenantSpec("vision", GOLD, weight=1.0)
    tenant = GraphScheduler(
        graph, batcher=CrossStreamBatcher(max_chunks=4, window=0.05),
        cost_model=CostModel())
    sb = [tenant.add_stream(f"cam{i}", W=clf_params["W"], slo=5.0,
                            tenant=spec) for i in range(4)]
    _drain(tenant, sb, streams)

    for x, y in zip(sa, sb):
        assert len(x.results) == len(y.results)
        for (c1, r1, m1), (c2, r2, m2) in zip(x.results, y.results):
            assert c1 is c2 and m1 == m2
            np.testing.assert_array_equal(r1.boxes, r2.boxes)
            np.testing.assert_array_equal(r1.labels, r2.labels)
            np.testing.assert_array_equal(r1.valid, r2.valid)
            np.testing.assert_array_equal(r1.fog_scores, r2.fog_scores)
            assert r1.latency.total == r2.latency.total
            assert r1.wan_bytes == r2.wan_bytes
            assert r1.coord_bytes == r2.coord_bytes
    ra, rb = plain.throughput_report(), tenant.throughput_report()
    skip = ("wall", "per_s", "overhead")
    for k in set(ra) | set(rb):
        if any(s in k for s in skip) or k in ("cost", "tenants"):
            continue
        assert ra.get(k) == rb.get(k), k
    # the attached machinery did meter: one tenant, every chunk attributed
    assert set(rb["tenants"]) == {"vision"}
    assert rb["tenants"]["vision"]["chunks"] == sum(len(s) for s in streams)


# ---------------------------------------------------------------------------
# Cost ledger conserves: sum of per-tenant spend == fleet spend
# ---------------------------------------------------------------------------
def test_cost_ledger_conservation(models):
    graph, clf_params = _graph(models)
    cost = CostModel()
    sched = GraphScheduler(
        graph, batcher=CrossStreamBatcher(max_chunks=4, window=0.05),
        cost_model=cost,
        store=ArtifactStore(ttl=5.0, capacity_bytes=1.0))
    ten = Tenancy(graph, cost)
    ten.register(TenantSpec("vision", GOLD, weight=4.0))
    ten.register(TenantSpec("cascade", SILVER, weight=2.0,
                            pipeline=llm_cascade_pipeline(
                                name="t-cascade-led")))
    ten.register(TenantSpec("retail", BRONZE, weight=1.0,
                            rates=BillingRates(cloud_replica_s=0.002),
                            pipeline=content_pipeline(name="t-retail-led")))
    states = [ten.add_stream(sched, t, f"cam-{t}",
                             **({"W": clf_params["W"]} if t == "vision"
                                else {}))
              for t in ("vision", "cascade", "retail")]
    _drain(sched, states, [_chunks(800 + i, 3) for i in range(3)])
    cost.close(max(s.clock for s in states))
    rep = sched.throughput_report()
    cr = rep["cost"]
    per_tenant = math.fsum(v["total_usd"] for v in cr["tenants"].values())
    assert np.isclose(per_tenant, cr["total_usd"], rtol=1e-12)
    assert cr["total_usd"] > 0
    # every chunk was attributed to exactly one tenant
    assert sum(v["chunks"] for v in cr["tenants"].values()) == 9
    assert set(cr["tenants"]) == {"vision", "cascade", "retail"}
    # provisioned time decomposes into busy + idle (keep-alive)
    assert np.isclose(cr["provisioned_replica_s"],
                      cr["busy_replica_s"] + cr["idle_replica_s"])
    for v in cr["tenants"].values():
        assert v["frames"] > 0 and v["cost_per_mframes"] > 0
    # the cascade bills cloud invocations only for escalated frames
    casc = cr["tenants"]["cascade"]
    assert casc["invocations"] <= casc["frames"]


# ---------------------------------------------------------------------------
# WFQ share conservation across tenants under overload
# ---------------------------------------------------------------------------
def test_wfq_share_conservation_under_overload(models):
    graph, clf_params = _graph(models)
    # two default-pipeline tenants, same demand, 3:1 weights; a tiny flush
    # budget (max_chunks=1) forces a long backlog so assembly order is
    # purely the WFQ virtual-finish-time order
    cost = CostModel()
    sched = GraphScheduler(
        graph, batcher=CrossStreamBatcher(max_chunks=1, window=10.0),
        cost_model=cost, deadline_batching=False)
    heavy = TenantSpec("heavy", BRONZE, weight=3.0)
    light = TenantSpec("light", BRONZE, weight=1.0)
    shared = _chunks(900, 8)
    sa = sched.add_stream("cam-heavy", W=clf_params["W"], weight=3.0,
                          tenant=heavy)
    sb = sched.add_stream("cam-light", W=clf_params["W"], weight=1.0,
                          tenant=light)
    _drain(sched, [sa, sb], [shared, list(shared)])
    # per-stream fair share: with weights 3:1 and equal backlog, the heavy
    # tenant's chunks must never wait longer than the light tenant's
    lat_h = [r.latency.total for _, r, _ in sa.results]
    lat_l = [r.latency.total for _, r, _ in sb.results]
    assert len(lat_h) == len(lat_l) == 8
    assert np.mean(lat_h) <= np.mean(lat_l)
    # both tenants' full demand was served (work conservation)
    assert sched.sched_stats["finalizes"] == 16


# ---------------------------------------------------------------------------
# Satellite: fog background lane — HITL cannot head-of-line block serving
# ---------------------------------------------------------------------------
def test_executor_background_lane_never_blocks_serving():
    reg = FunctionRegistry()
    reg.register("work", lambda: "ok", kind="test")
    from repro.core.bandwidth import FOG
    ex = Executor("fog-x", reg, FOG)
    # a 5-simulated-second background job lands at t=0
    _, done_bg = ex.run("work", now=0.0, model_time=5.0,
                        priority="background")
    assert done_bg == 5.0
    # a serve-lane call at t=1 is NOT queued behind it
    _, done_serve = ex.run("work", now=1.0, model_time=1.0)
    assert done_serve == 2.0
    # whereas a serve-lane job of the same size WOULD have blocked it
    ex2 = Executor("fog-y", reg, FOG)
    ex2.run("work", now=0.0, model_time=5.0)
    _, done_blocked = ex2.run("work", now=1.0, model_time=1.0)
    assert done_blocked == 6.0
    # background work queues FIFO behind itself on its own lane
    _, done_bg2 = ex.run("work", now=1.0, model_time=1.0,
                         priority="background")
    assert done_bg2 == 6.0


def test_hitl_cost_never_delays_chunks(models):
    """Regression for the PR-2 follow-up: pricing HITL collect work at 5
    simulated seconds per chunk must leave every chunk's serving latency
    identical to the free-HITL run (the old serve-lane dispatch would
    have head-of-line blocked the stream's next chunk)."""
    graph, clf_params = _graph(models)

    def run(hitl_cost_s):
        sched = GraphScheduler(
            graph, batcher=CrossStreamBatcher(max_chunks=2, window=0.05),
            hitl_cost_s=hitl_cost_s)
        st = sched.add_stream(
            "cam0", W=clf_params["W"],
            learner=IncrementalLearner(num_classes=CLF.num_classes,
                                       trigger=4, budget=64,
                                       rule="proximal"))
        for c in _chunks(910, 4):
            sched.submit(st, c, learn=True)
        sched.run_until_idle()
        return [r.latency.total for _, r, _ in st.results], st

    lat_free, _ = run(0.0)
    lat_priced, st = run(5.0)
    assert lat_free == lat_priced
    # the background lane actually carried the priced work
    assert any(r.device.endswith("/bg") and r.duration == 5.0
               for r in st.fog_exec.records)


# ---------------------------------------------------------------------------
# Satellite: ArtifactStore capacity bound + spill accounting
# ---------------------------------------------------------------------------
def test_store_capacity_spills():
    store = ArtifactStore(ttl=100.0, capacity_bytes=3000.0)
    refs = []
    for i in range(4):
        payload = np.full((16, 16), i, np.float32)     # 1024 B each
        ref = store.put(payload, key=f"k{i}", now=float(i))
        refs.append(ref)
        store.release(ref, now=float(i))               # idle immediately
    # capacity 3000 B < 4096 B stored: the two oldest idle payloads spill
    # (4096 -> 3072 is still over) long before their 100 s TTL
    assert store.stats["spills"] == 2
    assert store.stats["spill_bytes"] == 2048.0
    assert store.stats["bytes_current"] <= 3000.0
    assert store.stats["evictions"] == 2
    # referenced payloads are never spilled, even over capacity
    held = ArtifactStore(ttl=100.0, capacity_bytes=1000.0)
    keep = [held.put(np.full((16, 16), i, np.float32), key=f"h{i}", now=0.0)
            for i in range(3)]
    assert held.stats["spills"] == 0 and len(held) == 3
    for r in keep:
        held.release(r, now=0.0)
    held.put(np.zeros((16, 16), np.float32), key="h3", now=1.0)
    assert held.stats["spills"] > 0
    # the CostModel prices spill bytes at the fleet rate
    cost = CostModel(BillingRates(spill_per_gb=2.0))
    cost.register(TenantSpec("t", BRONZE))
    cost.charge_egress("t", 100.0, 0.0)
    cost.observe_pool(0.0, 0)
    rep = cost.cost_report(held.report())
    assert rep["spill_bytes"] == held.stats["spill_bytes"]
    assert np.isclose(rep["spill_cost"],
                      held.stats["spill_bytes"] / 1e9 * 2.0)
    assert np.isclose(rep["tenants"]["t"]["spill_cost"], rep["spill_cost"])


def test_store_spills_surface_in_throughput_report(models):
    graph, clf_params = _graph(models)
    # a capacity too small for even one encoded chunk: every idle payload
    # spills as soon as the next publish lands
    sched = GraphScheduler(
        graph, batcher=CrossStreamBatcher(max_chunks=1, window=0.0),
        store=ArtifactStore(ttl=100.0, capacity_bytes=1.0))
    st = sched.add_stream("cam0", W=clf_params["W"])
    for c in _chunks(920, 3):
        sched.submit(st, c, learn=False)
    sched.run_until_idle()
    rep = sched.throughput_report()
    assert rep["store_spills"] >= 1
    assert rep["store"]["spill_bytes"] > 0
    assert len(st.results) == 3          # spills never lose in-flight work


# ---------------------------------------------------------------------------
# Cost-aware autoscaler: SLO-driven up, break-even-driven down
# ---------------------------------------------------------------------------
def test_cost_aware_autoscaler_policy():
    sc = CostAwareAutoscaler(min_devices=1, max_devices=8,
                             replica_rate_usd_s=0.01, miss_value_usd=0.05,
                             frame_service_s=0.1, slo_slack_s=1.0,
                             cold_start_s=0.2, ewma_alpha=1.0)
    # queue of 40 frames needs 40*0.1/(1.0-0.2) = 5 replicas: immediate up
    assert sc.decide(0.0, 40, 1) == 5
    # demand drops to zero — but the break-even idle horizon is
    # miss_value/rate = 5 s, so no scale-down before then
    assert sc.decide(1.0, 0, 5) == 5
    assert sc.decide(4.0, 0, 5) == 5
    # past break-even: shed ONE replica at a time
    assert sc.decide(6.5, 0, 5) == 4
    assert sc.decide(7.0, 0, 4) == 4          # grace restarts per step
    assert sc.decide(12.0, 0, 4) == 3
    # never below min, never above max
    assert sc.decide(13.0, 10_000, 3) == 8
    s = sc.summary()
    assert s["peak_devices"] == 8 and s["scale_downs"] == 2


# ---------------------------------------------------------------------------
# Three pipelines share one fleet through the sharded scheduler
# ---------------------------------------------------------------------------
def test_tenant_pipelines_share_fleet_sharded(models):
    graph, clf_params = _graph(models)
    cost = CostModel()
    sched = ShardedScheduler(
        graph, num_shards=2,
        batcher_factory=lambda i: CrossStreamBatcher(max_chunks=4,
                                                     window=0.05),
        cost_model=cost)
    ten = Tenancy(graph, cost)
    ten.register(TenantSpec("vision", GOLD, weight=4.0))
    ten.register(TenantSpec("cascade", SILVER, weight=2.0,
                            pipeline=llm_cascade_pipeline(
                                name="t-cascade-shard")))
    ten.register(TenantSpec("retail", BRONZE, weight=1.0,
                            pipeline=content_pipeline(
                                name="t-retail-shard")))
    # tenant function graphs landed in the SHARED registry
    assert "cloud.tenant.t-cascade-shard" in graph.registry
    assert "fog.tenant.t-retail-shard" in graph.registry
    states = []
    for i, t in enumerate(("vision", "cascade", "retail", "vision")):
        states.append(ten.add_stream(
            sched, t, f"cam{i}",
            **({"W": clf_params["W"]} if t == "vision" else {})))
    _drain(sched, states, [_chunks(930 + i, 3) for i in range(4)])
    cost.close(max(s.clock for s in states))
    rep = sched.throughput_report()
    # merged report carries the shared rollups exactly once
    assert set(rep["tenants"]) == {"vision", "cascade", "retail"}
    assert rep["tenants"]["vision"]["chunks"] == 6
    cr = rep["cost"]
    assert np.isclose(math.fsum(v["total_usd"]
                                for v in cr["tenants"].values()),
                      cr["total_usd"], rtol=1e-12)
    for st in states:
        assert len(st.results) == 3
        if st.tenant.pipeline is not None:
            assert st.results[0][1].outputs["frames"] == 2
    # per-tenant SLO attainment is tracked per class
    for v in rep["tenants"].values():
        assert 0.0 <= v["slo_attainment"] <= 1.0
