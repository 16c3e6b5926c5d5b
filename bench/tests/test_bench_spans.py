"""The readers of the scheduler's host-span parts and upload counter
(bench/span_readers.py) on hand-made contexts."""
from __future__ import annotations

import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))
sys.path.insert(0, ROOT)

from bench import harness, span_readers  # noqa: E402

SPAN_METRICS = ("loop_self_ms_per_chunk.backlog",
                "loop_wait_ms_per_chunk.backlog",
                "dispatch_self_ms_per_flush.backlog",
                "prop_valid_wait_ms_per_flush.backlog",
                "h2d_bytes_per_frame.backlog")


def _ctx(window, trace_window):
    return {"window": window, "trace_window": trace_window}


def _counters(scale, finalizes, flushes, frames, h2d=None):
    c = {"sched.finalizes": finalizes, "hot.flushes": flushes,
         "detect.frames": frames,
         "sched.loop_self_wall_s": 0.002 * scale * finalizes,
         "sched.loop_wait_wall_s": 0.080 * scale * finalizes,
         "sched.dispatch_self_wall_s": 0.004 * scale * flushes,
         "sched.prop_valid_wait_wall_s": 0.060 * scale * flushes}
    if h2d is not None:
        c["hot.h2d_bytes"] = h2d
    return c


def test_rest_is_window_less_trace_window():
    # the traced part runs 5x slower; the rest reads the untraced host
    traced = _counters(5.0, 20, 20, 160)
    rest_only = _counters(1.0, 100, 100, 800)
    window = {k: traced[k] + rest_only[k] for k in traced}
    ctx = _ctx(window, traced)
    r = span_readers.rest(ctx)
    assert r["sched.finalizes"] == 100
    assert r["sched.loop_self_wall_s"] == pytest.approx(0.2)
    assert span_readers.ms_per(ctx, "sched.loop_self_wall_s",
                               "sched.finalizes") == pytest.approx(2.0)
    read = {m: harness.metric_reader(m)(ctx) for m in SPAN_METRICS[:4]}
    assert read == {
        "loop_self_ms_per_chunk.backlog": pytest.approx(2.0),
        "loop_wait_ms_per_chunk.backlog": pytest.approx(80.0),
        "dispatch_self_ms_per_flush.backlog": pytest.approx(4.0),
        "prop_valid_wait_ms_per_flush.backlog": pytest.approx(60.0)}


def test_rest_without_a_finished_chunk_reads_none():
    traced = _counters(5.0, 20, 20, 160)
    ctx = _ctx(dict(traced), traced)
    for m in SPAN_METRICS[:4]:
        assert harness.metric_reader(m)(ctx) is None


def test_h2d_bytes_per_frame_reads_the_whole_window():
    window = _counters(1.0, 10, 10, 80, h2d=80 * 393_216 + 10 * 12_288)
    ctx = _ctx(window, _counters(1.0, 2, 2, 16, h2d=0))
    assert harness.metric_reader("h2d_bytes_per_frame.backlog")(ctx) == \
        pytest.approx(393_216 + 1_536)


def test_a_program_without_spans_reads_none():
    # the counters of a scheduler that has no span recorder
    bare = {"sched.finalizes": 10, "sched.step_wall_s": 1.0,
            "sched.model_wall_s": 0.5, "hot.flushes": 10,
            "detect.frames": 80}
    ctx = _ctx(dict(bare), {k: 0 for k in bare})
    for m in SPAN_METRICS:
        assert harness.metric_reader(m)(ctx) is None


def test_the_cell_reports_the_span_metrics():
    cell = harness.load_cell("single-backlog")
    names = [m["name"] for m in cell["per_layer"]]
    assert all(m in names for m in SPAN_METRICS)
    units = {m["name"]: m["unit"] for m in cell["per_layer"]}
    assert units["h2d_bytes_per_frame.backlog"] == "B"


def test_a_traced_run_reads_the_span_metrics_and_step_mfu(monkeypatch):
    """A traced run of the small backlog cell on the CPU: the untraced rest
    after ``stop_trace`` holds serving, so the span parts and ``step_mfu``
    read a number.  (The CPU is given a peak here only so that the MFU
    arithmetic runs; no device number comes from this run.)"""
    import time

    import jax

    from bench import roofline
    sys.path.insert(0, os.path.join(ROOT, "src"))
    monkeypatch.setitem(roofline.PEAKS, jax.devices()[0].device_kind,
                        {"bf16_flops": 1e12, "hbm_bytes_per_s": 1e11})
    cell = harness.load_cell("single-backlog")
    cell["traffic"].update(cameras=4, scenes=1, check_chunks=4,
                           warmup_quiet_s=0.5, warmup_max_s=120.0,
                           trace_s=1.0)
    res = harness.run(cell, 2**31 + 7, 3.0, True, time.perf_counter())
    for m in SPAN_METRICS[:4] + ("step_mfu",):
        assert isinstance(res["metrics"][m]["value"], float), m
    assert res["metrics"]["step_mfu"]["value"] > 0
    assert res["device"]["window_s"] > 0
