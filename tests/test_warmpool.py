"""Warm-pool management plane: diurnal forecasting, break-even keep-alive
economics, prewarm-ahead scheduling, and the bitwise-identity contract.

The contract under test: the forecaster converges on periodic traffic
(EWMA fallback before that), the keep-alive horizon is the break-even
``miss_value / replica_rate`` tradeoff, the scheduler prewarms *ahead* of
forecast bursts (spin-up off the critical path) and sheds after them,
prewarm spend shows up in the ledger without breaking conservation,
prewarmed replicas are first-class fault targets (a flap mid-spin-up
resumes the remaining spin-up, never grants a free warm start), and with
the policy disabled the serving plane is bitwise-identical to the
policy-free scheduler at 1 and K shards."""
import math

import jax
import numpy as np
import pytest

from repro.configs.vpaas_video import ClassifierConfig, DetectorConfig
from repro.core.bandwidth import NetworkModel
from repro.core.protocol import HighLowProtocol
from repro.models import classifier as clf_mod
from repro.models import detector as det_mod
from repro.serving.autoscaler import (CostAwareAutoscaler, DiurnalForecaster,
                                      WarmPoolPolicy)
from repro.serving.batching import CrossStreamBatcher
from repro.serving.fault import FaultInjector
from repro.serving.graph import GraphScheduler, VideoFunctionGraph
from repro.serving.router import Router
from repro.serving.shards import ShardedScheduler
from repro.serving.tenancy import CostModel, SLOClass, TenantSpec
from repro.video import synthetic

DET = DetectorConfig(name="warmpool-test-det", image_hw=(32, 32),
                     widths=(8, 16))
CLF = ClassifierConfig(name="warmpool-test-clf", crop_hw=(16, 16),
                       widths=(8, 16), feature_dim=16)

# forecaster-unit tests drive bins directly at this period; scheduler
# tests reuse it as the burst spacing, chosen longer than one chunk's
# closed-loop completion (~5 s here) so arrivals stay periodic
PERIOD_S = 8.0


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
    rng = np.random.default_rng(seed)
    return [synthetic.make_chunk(rng, "traffic", num_frames=frames,
                                 hw=(32, 32)) for _ in range(n)]


def _periodic(fc, periods=6, frames=8.0):
    for k in range(periods):
        fc.observe(k * PERIOD_S, frames)


# ---------------------------------------------------------------------------
# forecaster units
# ---------------------------------------------------------------------------
def test_diurnal_forecast_converges_on_periodic_traffic():
    fc = DiurnalForecaster(bin_s=0.25)
    _periodic(fc)
    assert fc.period_s == PERIOD_S
    # the profile forecasts arbitrarily far into the future: burst bins
    # read the burst rate, quiet bins read zero
    assert fc.rate_at(100 * PERIOD_S) == pytest.approx(8.0 / 0.25)
    assert fc.rate_at(100 * PERIOD_S + 2.0) == 0.0
    assert fc.next_burst_after(17.0) == pytest.approx(3 * PERIOD_S)
    assert fc.burst_end_after(23.9) == pytest.approx(3 * PERIOD_S + 0.25)
    assert fc.volume_in_window(24.0, 25.0) == pytest.approx(8.0)
    assert fc.volume_in_window(25.0, 27.0) == 0.0


def test_forecaster_ewma_fallback_before_convergence():
    fc = DiurnalForecaster(bin_s=0.25)
    fc.observe(0.0, 8.0)
    fc.observe(0.5, 8.0)       # aperiodic: too little history for a lag
    assert fc.period_s is None
    assert fc.rate_at(3.0) == pytest.approx(fc.ewma_rate())
    assert fc.next_burst_after(0.0) is None
    # volume falls back to rate * dt
    assert fc.volume_in_window(1.0, 3.0) == pytest.approx(
        fc.ewma_rate() * 2.0)


def test_forecaster_prefers_fundamental_over_harmonics():
    # a perfectly periodic signal correlates equally at lag L and 2L; the
    # smallest near-best lag must win or prewarms fire every OTHER burst
    fc = DiurnalForecaster(bin_s=0.25)
    _periodic(fc, periods=10)
    assert fc.period_s == PERIOD_S        # not 2 * PERIOD_S


# ---------------------------------------------------------------------------
# policy economics
# ---------------------------------------------------------------------------
def test_break_even_keep_warm_horizon():
    pol = WarmPoolPolicy(replica_rate_usd_s=0.004, miss_value_usd=0.004)
    assert pol.keep_warm_horizon_s == pytest.approx(1.0)
    pol = WarmPoolPolicy(replica_rate_usd_s=0.002, miss_value_usd=0.01)
    # cheaper keep-alive / pricier miss -> hold the pool through longer gaps
    assert pol.keep_warm_horizon_s == pytest.approx(5.0)


def test_target_replicas_sheds_past_break_even_holds_within():
    def _pol(**kw):
        pol = WarmPoolPolicy(frame_service_s=0.05, slo_slack_s=0.5,
                             min_replicas=1, max_replicas=8, **kw)
        _periodic(pol.forecasters.setdefault("default", DiurnalForecaster(
            bin_s=pol.bin_s)), frames=40.0)
        return pol

    # quiet time, next burst 6 s out (t=18.0, bursts every 8 s at k*8)
    quiet_t = 18.0
    short = _pol(replica_rate_usd_s=0.004, miss_value_usd=0.004)   # 1 s
    long = _pol(replica_rate_usd_s=0.0004, miss_value_usd=0.004)   # 10 s
    assert short.target_replicas(quiet_t) == 1          # gap > horizon: shed
    sized = long.target_replicas(quiet_t)               # gap < horizon: hold
    assert sized == math.ceil(40.0 * 0.05 / 0.5)
    # inside the lookahead of a burst both size for the imminent volume
    assert short.target_replicas(23.8) == sized


def test_next_check_epoch_budget_terminates_without_traffic():
    pol = WarmPoolPolicy(cold_start_s=0.5)
    _periodic(pol.forecasters.setdefault("default", DiurnalForecaster(
        bin_s=pol.bin_s)), frames=8.0)
    pol.observe(6 * PERIOD_S, 8.0)           # on-phase arrival
    seen = []
    now = 6 * PERIOD_S + 0.1
    while True:
        t = pol.next_check(now)
        if t is None:
            break
        pol.fired()
        seen.append(t)
        now = t
    # bounded fires per observation epoch: the chain self-terminates, so
    # run_until_idle cannot livelock on a periodic forecast
    assert 0 < len(seen) <= pol.max_checks_per_obs
    assert pol.next_check(now) is None
    pol.observe(7 * PERIOD_S, 8.0)           # new arrival resets the budget
    assert pol.next_check(7 * PERIOD_S + 0.1) is not None


def test_cost_aware_autoscaler_consumes_forecast():
    def _asc(pol):
        return CostAwareAutoscaler(min_devices=1, max_devices=8,
                                   unit="replicas", frame_service_s=0.05,
                                   slo_slack_s=0.5, warm_pool=pol)

    pol = WarmPoolPolicy(cold_start_s=0.5, frame_service_s=0.05,
                         slo_slack_s=0.5, max_replicas=8)
    for k in range(6):
        pol.observe(k * PERIOD_S, 40.0)
    # just before a forecast burst with an EMPTY queue: the reactive
    # signal says 1 replica, the forecast floor says size for the burst
    t = 6 * PERIOD_S - 0.2
    assert _asc(None).decide(t, 0, 1) == 1
    assert _asc(pol).decide(t, 0, 1) == pol.target_replicas(t) > 1
    # disabled policy: bitwise the reactive decision
    pol.enabled = False
    assert _asc(pol).decide(t, 0, 1) == 1


# ---------------------------------------------------------------------------
# router / fault-plane units
# ---------------------------------------------------------------------------
def _router(cold_start_s=1.5):
    from repro.serving.executor import Executor
    from repro.serving.registry import FunctionRegistry
    reg = FunctionRegistry()
    proto = HighLowProtocol(DET, CLF)

    def factory(uid):
        return Executor(f"cloud-{uid}", reg, proto.cloud, num_devices=1)

    return Router([factory(0)], replica_factory=factory,
                  cold_start_s=cold_start_s, scale_unit="replicas")


def test_prewarm_scale_up_tracks_spinning_state():
    r = _router(cold_start_s=1.5)
    r.scale_replicas(3, now=10.0, prewarm=True)
    assert r.healthy_count() == 3
    # replica 0 was warm from t=0; the two new ones spin until 11.5
    assert r.warm_count(10.0) == 1 and r.spinning_count(10.0) == 2
    assert r.warm_count(11.5) == 3 and r.spinning_count(11.5) == 0
    assert r.monitor.counters["replicas_prewarmed"] == 2
    for rep in r.replicas[1:]:
        assert rep.ready_at == pytest.approx(11.5)
        assert all(b == pytest.approx(11.5)
                   for b in rep.executor.busy_until)


def test_flap_mid_spinup_resumes_remaining_spinup():
    r = _router(cold_start_s=1.5)
    r.scale_replicas(2, now=10.0, prewarm=True)
    # flap the spinning replica before it ever got warm
    r.mark_unhealthy(1, now=10.2)
    assert r.readmit(1, now=10.6)
    rep = r.replicas[1]
    # re-admission mid-spin-up resumes the REMAINING spin-up (devices
    # free at ready_at=11.5), it does not grant a free warm start at 10.6
    assert all(b == pytest.approx(11.5) for b in rep.executor.busy_until)
    # ...whereas re-admitting after ready_at comes up free immediately,
    # exactly the pre-warm-pool behaviour
    r.mark_unhealthy(1, now=11.6)
    assert r.readmit(1, now=12.0)
    assert all(b == pytest.approx(12.0) for b in rep.executor.busy_until)


def test_injector_down_until_reports_flap_recovery():
    fi = FaultInjector(network=NetworkModel())
    fi.flap_replica(3, 2.0, 3.5)
    assert fi.down_until(3, 2.5) == pytest.approx(3.5)
    assert fi.down_until(3, 1.9) is None
    assert fi.down_until(3, 3.5) is None
    fi.fail_replica(4, at=1.0)              # permanent: no recovery time
    assert fi.down_until(4, 2.0) is None


# ---------------------------------------------------------------------------
# scheduler integration
# ---------------------------------------------------------------------------
def _run_until(sched, t_limit):
    while True:
        k = sched._peek_key()
        if k is None or k[0] >= t_limit:
            return
        sched.step()


def _drive_bursts(sched, states, *, bursts=5, seed=0):
    """Open-loop diurnal traffic: every stream submits one chunk per
    burst, bursts PERIOD_S apart, events stepped in simulated order."""
    per = [_chunks(seed + i, bursts, frames=4) for i in range(len(states))]
    for b in range(bursts):
        t0 = b * PERIOD_S
        for st in states:
            st.clock = max(st.clock, t0)
        for st, cs in zip(states, per):
            sched.submit(st, cs[b], learn=False)
        _run_until(sched, (b + 1) * PERIOD_S)
    sched.run_until_idle()
    return per


def _warm_policy(**kw):
    kw.setdefault("cold_start_s", 0.6)
    kw.setdefault("frame_service_s", 0.05)
    kw.setdefault("slo_slack_s", 0.5)
    kw.setdefault("max_replicas", 4)
    return WarmPoolPolicy(**kw)


def test_scheduler_prewarms_ahead_and_sheds_after_bursts(models):
    graph, clf_params = _graph(models)
    cost = CostModel()
    cost.register(TenantSpec("default", slo_class=SLOClass("gold", 5.0)))
    pol = _warm_policy()
    asc = CostAwareAutoscaler(min_devices=1, max_devices=4, unit="replicas",
                              cold_start_s=0.6, warm_pool=pol)
    sched = GraphScheduler(
        graph, batcher=CrossStreamBatcher(max_chunks=8, window=0.05),
        cost_model=cost, cloud_replicas=1,
        autoscaler=asc, scale_unit="replicas", cold_start_s=0.6,
        warm_pool=pol)
    states = [sched.add_stream(f"cam{i}", W=clf_params["W"], slo=5.0)
              for i in range(6)]
    _drive_bursts(sched, states)
    rep = sched.throughput_report()
    assert rep["warm_prewarm_events"] > 0
    assert rep["warm_replicas_prewarmed"] > 0
    assert rep["warm_shed_events"] > 0
    assert rep["warm_spinup_replica_s"] == pytest.approx(
        rep["warm_replicas_prewarmed"] * 0.6)
    # prewarms fire AHEAD of arrivals: each prewarm time must precede an
    # arrival bin within the spin-up lookahead (off the critical path).
    # A trailing prewarm after the LAST arrival is legitimate — the
    # forecast cannot know traffic ended — so only pre-end prewarms are
    # held to it.
    fc = pol.forecasters["default"]
    arrivals = [i * fc.bin_s for i, v in enumerate(fc._bins) if v > 0]
    ahead = [t for t, _ in sched.monitor.series["replica_prewarm"]
             if t <= max(arrivals)]
    assert ahead, "every prewarm fired after traffic ended"
    for t in ahead:
        assert any(t < a <= t + 0.6 + 0.05 + 2 * fc.bin_s
                   for a in arrivals)
    # the ledger saw the same spin-ups, and conservation still holds
    cost.close(max(st.clock for st in states))
    cr = cost.cost_report()
    assert cr["prewarm_spinups"] == rep["warm_replicas_prewarmed"]
    assert cr["prewarm_replica_s"] == pytest.approx(
        rep["warm_spinup_replica_s"])
    assert cr["prewarm_cost"] == pytest.approx(
        cr["prewarm_replica_s"] * cost.rates.cloud_replica_s)
    assert cr["total_usd"] == pytest.approx(
        sum(t["total_usd"] for t in cr["tenants"].values()))


def _results_of(states):
    out = []
    for st in states:
        for c, r, _ in st.results:
            out.append((c, np.asarray(r.boxes), np.asarray(r.labels),
                        np.asarray(r.valid), r.latency.total))
    return out


def _assert_bitwise(a, b):
    assert len(a) == len(b)
    for (c1, b1, l1, v1, t1), (c2, b2, l2, v2, t2) in zip(a, b):
        np.testing.assert_array_equal(c1.frames, c2.frames)
        np.testing.assert_array_equal(b1, b2)
        np.testing.assert_array_equal(l1, l2)
        np.testing.assert_array_equal(v1, v2)
        assert t1 == t2


@pytest.mark.parametrize("num_shards", [1, 2])
def test_disabled_policy_is_bitwise_identical(models, num_shards):
    graph, clf_params = _graph(models)

    def _run(warm_pool):
        sched = ShardedScheduler(
            graph, num_shards=num_shards,
            batcher_factory=lambda i: CrossStreamBatcher(max_chunks=8,
                                                         window=0.05),
            use_store=False, cloud_replicas=2,
            warm_pool=warm_pool)
        states = [sched.add_stream(f"cam{i}", W=clf_params["W"], slo=5.0)
                  for i in range(4)]
        for st, cs in zip(states, [_chunks(i, 3) for i in range(4)]):
            for c in cs:
                sched.submit(st, c, learn=False)
        sched.run_until_idle()
        return _results_of(states), sched.throughput_report()

    res_plain, rep_plain = _run(None)
    res_off, rep_off = _run(_warm_policy(enabled=False))
    _assert_bitwise(res_plain, res_off)
    skip = ("wall", "per_s", "overhead")
    for k in set(rep_plain) | set(rep_off):
        if any(s in k for s in skip):
            continue
        assert rep_plain.get(k) == rep_off.get(k), k
    # the disabled run still emits the warm_* keys — as zeros
    assert rep_off["warm_replicas_prewarmed"] == 0
    assert rep_off["warm_prewarm_events"] == 0


def test_prewarmed_replica_survives_injected_flap(models):
    """A flap scheduled on a prewarmed uid interrupts its spin-up; the
    probe chain re-admits it with the REMAINING spin-up intact and the
    run loses no chunk."""
    graph, clf_params = _graph(models)
    pol = _warm_policy()
    fi = FaultInjector(network=graph.protocol.network)
    asc = CostAwareAutoscaler(min_devices=1, max_devices=4, unit="replicas",
                              cold_start_s=0.6, warm_pool=pol)
    sched = GraphScheduler(
        graph, batcher=CrossStreamBatcher(max_chunks=8, window=0.05),
        cloud_replicas=1, autoscaler=asc,
        scale_unit="replicas", cold_start_s=0.6, warm_pool=pol,
        fault=fi)
    states = [sched.add_stream(f"cam{i}", W=clf_params["W"], slo=5.0)
              for i in range(6)]
    # uid 1 is the first prewarmed replica; flap it across the whole
    # pre-burst spin-up window of every later burst
    for b in range(1, 5):
        t0 = b * PERIOD_S
        fi.flap_replica(1, t0 - 1.0, t0 + 0.5)
    per = _drive_bursts(sched, states)
    rep = sched.throughput_report()
    assert rep["warm_replicas_prewarmed"] > 0
    expected = sum(len(cs) for cs in per)
    assert sum(len(st.results) for st in states) == expected
    assert rep["frames"] == 4 * expected
