#!/usr/bin/env python
"""Docs/metrics sync gate: docs/METRICS.md must match the code's reports.

Drives one small fully-loaded serving run (cost model + capacity-bounded
store + three tenants including custom pipelines + cost-aware autoscaler
with a warm pool + per-stream SLOs) so every *top-level* key of
``GraphScheduler.throughput_report()`` and ``CostModel.cost_report()``
is actually emitted, then checks two directions:

- **forward**: every emitted key appears as backticked text somewhere in
  docs/METRICS.md — new report keys cannot ship undocumented;
- **reverse**: every key listed inside the doc's marker-delimited
  sections::

      <!-- begin-keys: throughput_report -->
      ... markdown tables whose first column is | `key` | ...
      <!-- end-keys -->

  must exist in the emitted set — documented-but-removed keys are flagged
  instead of rotting silently.  Only the first table cell of each row
  counts as a key claim; backticks in prose or description cells don't.

Exit 0 on sync, 1 with a per-key diff otherwise.  ``--dump`` prints the
emitted key lists (used to author/refresh the doc).

Usage::

    PYTHONPATH=src python scripts/check_docs_sync.py [--dump]
"""
from __future__ import annotations

import argparse
import re
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT / "src"))
sys.path.insert(0, str(ROOT))

DOC = ROOT / "docs" / "METRICS.md"

MARKER = re.compile(
    r"<!--\s*begin-keys:\s*(?P<section>[\w.]+)\s*-->"
    r"(?P<body>.*?)"
    r"<!--\s*end-keys\s*-->",
    re.S,
)
BACKTICKED = re.compile(r"`([A-Za-z_][\w]*)`")
# a key *claim* is the first cell of a table row: "| `key` | ..."
TABLE_KEY = re.compile(r"^\|\s*`([A-Za-z_][\w]*)`\s*\|", re.M)


# ---------------------------------------------------------------------------
# the kitchen-sink run: one scheduler exercising every reporting subsystem
# ---------------------------------------------------------------------------
def collect():
    import jax
    import numpy as np

    from repro.configs.vpaas_video import ClassifierConfig, DetectorConfig
    from repro.core.protocol import HighLowProtocol
    from repro.models import classifier as clf_mod
    from repro.models import detector as det_mod
    from repro.serving.autoscaler import CostAwareAutoscaler, WarmPoolPolicy
    from repro.serving.batching import CrossStreamBatcher
    from repro.serving.graph import GraphScheduler, VideoFunctionGraph
    from repro.serving.ingest import ArtifactStore
    from repro.serving.tenancy import (BRONZE, GOLD, SILVER, BillingRates,
                                       CostModel, Tenancy, TenantSpec,
                                       content_pipeline,
                                       llm_cascade_pipeline)
    from repro.video import synthetic

    det = DetectorConfig(name="docsync-det", image_hw=(32, 32),
                         widths=(8, 16))
    clf = ClassifierConfig(name="docsync-clf", crop_hw=(16, 16),
                           widths=(8, 16), feature_dim=16)
    det_params = det_mod.init_detector(det, jax.random.PRNGKey(0))
    clf_params = clf_mod.init_classifier(clf, jax.random.PRNGKey(1))
    graph = VideoFunctionGraph(HighLowProtocol(det, clf), det_params,
                               clf_params)

    cost = CostModel()
    autoscaler = CostAwareAutoscaler(
        min_devices=1, max_devices=3, unit="replicas",
        replica_rate_usd_s=0.004, miss_value_usd=0.004,
        frame_service_s=1.0 / 75.0, slo_slack_s=2.5, cold_start_s=0.5,
        warm_pool=WarmPoolPolicy(cold_start_s=0.5, max_replicas=3))
    sched = GraphScheduler(
        graph, batcher=CrossStreamBatcher(max_chunks=4, window=0.05),
        cost_model=cost,
        # 1-byte capacity forces spills so the spill cost keys are live
        store=ArtifactStore(ttl=5.0, capacity_bytes=1.0),
        autoscaler=autoscaler, scale_unit="replicas", cold_start_s=0.5,
        warm_pool=autoscaler.warm_pool)

    ten = Tenancy(graph, cost)
    ten.register(TenantSpec("vision", GOLD, weight=4.0))
    ten.register(TenantSpec("cascade", SILVER, weight=2.0,
                            pipeline=llm_cascade_pipeline(
                                name="docsync-cascade")))
    ten.register(TenantSpec("retail", BRONZE, weight=1.0,
                            rates=BillingRates(cloud_replica_s=0.002),
                            pipeline=content_pipeline(name="docsync-retail")))
    states = [ten.add_stream(sched, t, f"cam-{t}",
                             **({"W": clf_params["W"]} if t == "vision"
                                else {}))
              for t in ("vision", "cascade", "retail")]

    rng = np.random.default_rng(42)
    for i, st in enumerate(states):
        for _ in range(3):
            sched.submit(st, synthetic.make_chunk(
                rng, "traffic", num_frames=2, hw=(32, 32)), learn=False)
    sched.run_until_idle()
    cost.close(max(s.clock for s in states))

    rep = sched.throughput_report()
    return {"throughput_report": sorted(rep),
            "cost_report": sorted(rep["cost"])}


# ---------------------------------------------------------------------------
def check(emitted) -> int:
    if not DOC.exists():
        print(f"FAIL: {DOC} does not exist")
        return 1
    text = DOC.read_text()
    documented_anywhere = set(BACKTICKED.findall(text))
    sections = {m.group("section"): set(TABLE_KEY.findall(m.group("body")))
                for m in MARKER.finditer(text)}

    failures = []
    for name, keys in emitted.items():
        if name not in sections:
            failures.append(
                f"docs/METRICS.md has no '<!-- begin-keys: {name} -->' "
                f"section")
            continue
        # forward: emitted keys must be documented
        for k in keys:
            if k not in documented_anywhere:
                failures.append(
                    f"{name}: emitted key `{k}` is not documented in "
                    f"docs/METRICS.md")
        # reverse: keys listed in the marker section must still be emitted
        for k in sorted(sections[name] - set(keys)):
            failures.append(
                f"{name}: documented key `{k}` is no longer emitted "
                f"(stale — remove it from docs/METRICS.md)")

    if failures:
        for f in failures:
            print(f"  {f}")
        print(f"FAIL: docs/METRICS.md out of sync ({len(failures)} issues)")
        return 1
    n = sum(len(v) for v in emitted.values())
    print(f"# PASS: docs/METRICS.md documents all {n} emitted report keys "
          f"and lists no stale ones")
    return 0


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--dump", action="store_true",
                    help="print emitted key lists (for authoring the doc)")
    args = ap.parse_args()
    emitted = collect()
    if args.dump:
        for name, keys in emitted.items():
            print(f"## {name} ({len(keys)} keys)")
            for k in keys:
                print(f"  {k}")
        return
    raise SystemExit(check(emitted))


if __name__ == "__main__":
    main()
