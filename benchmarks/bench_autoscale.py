"""Paper Fig. 16, revived on the real serving plane: three provisioning
policies drive the SAME ramped workload through ``GraphScheduler`` +
``Router(scale_unit="replicas")`` and are billed by the ``CostModel``:

* ``always_max``    — pool pinned at max replicas (no autoscaler);
* ``queue_depth``   — the original PR-era backlog heuristic;
* ``cost_aware``    — ``CostAwareAutoscaler``: minimize $ subject to SLO
  attainment, cold-start priced into the scale-up headroom and keep-alive
  $ setting the scale-down grace.

The workload ramps by adding cameras (2 -> 6 -> 2 streams across three
waves), so the pool must grow with the wave and should be retired after.
Rows report the $ bill, provisioned replica-seconds, p99 latency, and
the scaling trace for each policy; the hard economics gate lives in
``bench_tenancy.py``.
"""
from __future__ import annotations

import numpy as np

from repro.configs.vpaas_video import CLASSIFIER, DETECTOR
from repro.core.bandwidth import CLOUD
from repro.core.protocol import HighLowProtocol
from repro.serving.autoscaler import Autoscaler, CostAwareAutoscaler
from repro.serving.batching import CrossStreamBatcher
from repro.serving.graph import GraphScheduler, VideoFunctionGraph
from repro.serving.tenancy import CostModel
from repro.video import synthetic

from benchmarks.common import BenchContext

MAX_REPLICAS = 4
COLD_START_S = 0.2
SLO_S = 6.0


def _policy(name: str):
    if name == "always_max":
        return None
    if name == "queue_depth":
        return Autoscaler(min_devices=1, max_devices=MAX_REPLICAS,
                          cooldown_s=1.0, unit="replicas")
    # slo_slack is the queue-drain budget left once WAN + fog costs
    # (~5 s/chunk on this profile) are spent from the 6 s SLO
    return CostAwareAutoscaler(
        min_devices=1, max_devices=MAX_REPLICAS, unit="replicas",
        frame_service_s=1.0 / CLOUD.detect_fps, slo_slack_s=1.0,
        cold_start_s=COLD_START_S)


def _run(graph, ctx: BenchContext, policy: str, waves, frames: int):
    cost = CostModel()
    scaler = _policy(policy)
    replicas = MAX_REPLICAS if scaler is None else 1
    sched = GraphScheduler(
        graph, batcher=CrossStreamBatcher(max_chunks=6, window=0.05),
        cost_model=cost, cloud_replicas=replicas,
        autoscaler=scaler, scale_unit="replicas",
        cold_start_s=COLD_START_S)
    n_streams = max(w[0] for w in waves)
    streams = [sched.add_stream(f"cam{i}", W=ctx.clf_params["W"], slo=SLO_S)
               for i in range(n_streams)]
    rng = np.random.default_rng(0)
    for cams, rounds in waves:
        for _ in range(rounds):
            for st in streams[:cams]:
                sched.submit(st, synthetic.make_chunk(
                    rng, "traffic", num_frames=frames), learn=False)
        sched.run_until_idle()
    cost.close(max(st.clock for st in streams))
    rep = sched.throughput_report()
    lats = [r.latency.total for st in streams for _, r, _ in st.results]
    return rep, scaler, float(np.percentile(np.asarray(lats), 99))


def run(ctx: BenchContext, quick: bool = False):
    proto = HighLowProtocol(DETECTOR, CLASSIFIER)
    graph = VideoFunctionGraph(proto, ctx.det_params, ctx.clf_params)
    # cameras added then removed: (active_cameras, chunk rounds per wave);
    # the middle wave's simultaneous arrivals build genuine detector
    # backlog, so the policies have to take a position on scaling
    waves = [(2, 1), (8, 1), (2, 1)] if quick \
        else [(2, 2), (16, 2), (2, 2)]

    rows = []
    for policy in ("always_max", "queue_depth", "cost_aware"):
        rep, scaler, p99 = _run(graph, ctx, policy, waves, frames=8)
        bill = rep["cost"]
        row = {"name": policy, "us_per_call": "",
               "total_usd": f"{bill['total_usd']:.6f}",
               "replica_s": f"{bill['provisioned_replica_s']:.1f}",
               "idle_usd": f"{bill['idle_cost']:.6f}",
               "p99_latency_s": f"{p99:.2f}",
               "peak_replicas": rep.get("peak_devices", MAX_REPLICAS)}
        if scaler is not None:
            s = scaler.summary()
            row["scale_ups"] = s["scale_ups"]
            row["scale_downs"] = s["scale_downs"]
        rows.append(row)
    return rows
