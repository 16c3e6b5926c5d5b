"""Paper Fig. 10a + the monetary serving-plane bill.

Two complementary views of "what does the cloud cost":

* the paper's normalized serverless per-frame billing comparison against
  the CloudSeg/DDS baselines (c_F = p_F * n* * rounds), unchanged; and
* the PR-8 ``CostModel`` ledger: the same chunks pushed through the real
  ``GraphScheduler`` with metering attached, producing an itemized $
  bill (replica keep-alive, busy time, per-invocation serverless charge,
  egress) and cost-per-million-frames — the figure the multi-tenant
  autoscaler optimizes in ``bench_tenancy.py``.
"""
from __future__ import annotations

from repro.baselines import CloudSegBaseline, DDSBaseline
from repro.configs.vpaas_video import CLASSIFIER, DETECTOR
from repro.core.protocol import HighLowProtocol
from repro.serving.batching import CrossStreamBatcher
from repro.serving.graph import GraphScheduler, VideoFunctionGraph
from repro.serving.tenancy import CostModel

from benchmarks.common import BenchContext


def _serving_bill(ctx: BenchContext, datasets) -> dict:
    """Meter the real serving plane over the same chunks: one stream per
    content type on a shared single-replica fleet, fleet price book."""
    proto = HighLowProtocol(DETECTOR, CLASSIFIER)
    graph = VideoFunctionGraph(proto, ctx.det_params, ctx.clf_params)
    cost = CostModel()
    sched = GraphScheduler(
        graph, batcher=CrossStreamBatcher(max_chunks=4, window=0.05),
        cost_model=cost)
    streams = {name: sched.add_stream(name, W=ctx.clf_params["W"])
               for name in datasets}
    for name, chunks in datasets.items():
        for ch in chunks:
            sched.submit(streams[name], ch, learn=False)
    sched.run_until_idle()
    cost.close(max(st.clock for st in streams.values()))
    return sched.throughput_report()["cost"]


def run(ctx: BenchContext, quick: bool = False):
    datasets = ctx.datasets(chunks_per_type=1, frames=8)
    chunks = [c for cs in datasets.values() for c in cs]
    vpaas = HighLowProtocol(DETECTOR, CLASSIFIER)
    cloudseg = CloudSegBaseline(DETECTOR)
    dds = DDSBaseline(DETECTOR)

    cost = {"vpaas": 0.0, "cloudseg": 0.0, "dds": 0.0}
    for ch in chunks:
        r = vpaas.process_chunk(ctx.det_params, ctx.clf_params, ch.frames)
        cost["vpaas"] += vpaas.cloud_cost(r)
        rc = cloudseg.process_chunk(ctx.det_params, ch.frames)
        cost["cloudseg"] += cloudseg.cost_model.cost(rc.cloud_frames)
        rd = dds.process_chunk(ctx.det_params, ch.frames)
        cost["dds"] += rd.cloud_frames * rd.cloud_rounds

    ref = cost["vpaas"]
    rows = [{"name": k, "us_per_call": "",
             "cloud_cost": f"{v:.1f}",
             "cost_norm_to_vpaas": f"{v / max(ref, 1e-9):.2f}"}
            for k, v in cost.items()]

    bill = _serving_bill(ctx, datasets)
    rows.append({
        "name": "vpaas_usd_bill", "us_per_call": "",
        "total_usd": f"{bill['total_usd']:.6f}",
        "cost_per_mframes": f"{bill['cost_per_mframes']:.1f}",
        "idle_usd": f"{bill['idle_cost']:.6f}",
        "busy_replica_s": f"{bill['busy_replica_s']:.2f}",
        "frames": bill["frames"],
    })
    return rows
