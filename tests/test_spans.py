"""Host spans of the serving event loop (serving/spans.py): the recorder's
aggregates, the profiler annotations they open, and the spans a fused
GraphScheduler run crosses, with the counters they feed."""
import glob
import os
import sys

import jax
import numpy as np
import pytest

from repro.configs.vpaas_video import ClassifierConfig, DetectorConfig
from repro.core.protocol import HighLowProtocol
from repro.models import classifier as clf_mod
from repro.models import detector as det_mod
from repro.serving import spans
from repro.serving.batching import CrossStreamBatcher
from repro.serving.graph import GraphScheduler, VideoFunctionGraph

DET = DetectorConfig(name="spans-test-det", image_hw=(32, 32),
                     widths=(8, 16))
CLF = ClassifierConfig(name="spans-test-clf", crop_hw=(16, 16),
                       widths=(8, 16), feature_dim=16)

# every span a fused flush crosses on its way from ingest to finalize
FUSED_SPANS = (
    "vpaas.step", "vpaas.ingest", "vpaas.encode.launch", "vpaas.arrive",
    "vpaas.wait.encode_nbytes", "vpaas.flush", "vpaas.dispatch",
    "vpaas.dispatch.pack", "vpaas.detect", "vpaas.wait.prop_valid",
    "vpaas.dispatch.plan", "vpaas.dispatch.hq_upload",
    "vpaas.classify.launch", "vpaas.dispatch.results", "vpaas.finalize",
    "vpaas.wait.result_fields")


class _Clock:
    """A nanosecond clock that moves only when told."""

    def __init__(self):
        self.ns = 0

    def __call__(self):
        return self.ns


@pytest.fixture
def clock(monkeypatch):
    c = _Clock()
    monkeypatch.setattr(spans, "_now", c)
    return c


def test_nested_spans_self_time_and_parts(clock):
    stats = {"step_wall_s": 0.0, "model_wall_s": 0.0}
    rec = spans.SpanRecorder(stats, {
        "vpaas.step": (stats, "step_wall_s"),
        "vpaas.dispatch": (stats, "model_wall_s")})
    with rec.span("vpaas.step", action="flush"):
        clock.ns += 1_000
        with rec.span("vpaas.wait.encode_nbytes"):
            clock.ns += 2_000
        with rec.span("vpaas.dispatch", flush=0):
            clock.ns += 3_000
            with rec.span("vpaas.wait.prop_valid", flush=0):
                clock.ns += 4_000
            with rec.span("vpaas.dispatch.plan", flush=0):
                clock.ns += 5_000
        with rec.span("vpaas.wait.encode_nbytes"):
            clock.ns += 6_000
    # a read outside the loop: aggregated, but in no part
    with rec.span("vpaas.wait.result_fields"):
        clock.ns += 7_000
    st = rec.stats
    assert st["vpaas.step"] == {"n": 1, "s": pytest.approx(21e-6),
                                "self_s": pytest.approx(1e-6)}
    assert st["vpaas.dispatch"] == {"n": 1, "s": pytest.approx(12e-6),
                                    "self_s": pytest.approx(3e-6)}
    assert st["vpaas.wait.encode_nbytes"]["n"] == 2
    assert st["vpaas.wait.encode_nbytes"]["s"] == pytest.approx(8e-6)
    assert st["vpaas.wait.result_fields"]["n"] == 1
    for name, a in st.items():
        if name.startswith(spans.WAIT_PREFIX) or name.endswith(".plan"):
            assert a["self_s"] == a["s"]          # leaves
    assert stats["step_wall_s"] == pytest.approx(21e-6)
    assert stats["model_wall_s"] == pytest.approx(12e-6)
    assert stats["loop_self_wall_s"] == pytest.approx(1e-6)
    assert stats["loop_wait_wall_s"] == pytest.approx(8e-6)
    assert stats["dispatch_self_wall_s"] == pytest.approx(8e-6)
    assert stats["prop_valid_wait_wall_s"] == pytest.approx(4e-6)


def test_a_span_that_raises_is_still_recorded(clock):
    stats = {}
    rec = spans.SpanRecorder(stats)
    with pytest.raises(ValueError):
        with rec.span("vpaas.step"):
            with rec.span("vpaas.arrive"):
                clock.ns += 5
                raise ValueError
    assert rec.stats["vpaas.arrive"] == {"n": 1, "s": pytest.approx(5e-9),
                                         "self_s": pytest.approx(5e-9)}
    assert rec.stats["vpaas.step"]["n"] == 1
    assert stats["loop_self_wall_s"] == pytest.approx(5e-9)
    assert not rec._stack and rec._open == [0, 0, 0]


def test_spans_lie_on_the_profiler_host_plane(tmp_path):
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    sys.path.insert(0, root)
    from bench import tracing

    rec = spans.SpanRecorder({})
    f = jax.jit(lambda x: x * 2.0)
    f(np.ones(4, np.float32)).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    try:
        with rec.span("vpaas.step", action="flush"):
            with rec.span("vpaas.dispatch", flush=7):
                with rec.span("vpaas.wait.prop_valid", flush=7):
                    np.asarray(f(np.ones(4, np.float32)))
    finally:
        jax.profiler.stop_trace()
    path = sorted(glob.glob(os.path.join(str(tmp_path), "plugins",
                                         "profile", "*", "*.xplane.pb")))[-1]
    host = {}
    for plane, lines in tracing.load(path):
        if plane.startswith("/host:"):
            for _, events in lines:
                for name, start, dur in events:
                    if name.startswith("vpaas."):
                        host[name] = (start, dur)
    assert set(host) == {"vpaas.step", "vpaas.dispatch",
                         "vpaas.wait.prop_valid"}
    (s0, d0), (s1, d1) = host["vpaas.step"], host["vpaas.wait.prop_valid"]
    assert s0 <= s1 and s1 + d1 <= s0 + d0          # nested on one clock
    assert rec.stats["vpaas.wait.prop_valid"]["n"] == 1


class _TouchResults:
    """Finalize hook that reads the served fields, as an operator does."""

    def on_chunk(self, scheduler, stream, chunk, res, t, mode):
        for f in ("boxes", "labels", "valid", "source"):
            getattr(res, f)


@pytest.fixture(scope="module")
def fused_run():
    det_params = det_mod.init_detector(DET, jax.random.PRNGKey(0))
    clf_params = clf_mod.init_classifier(CLF, jax.random.PRNGKey(1))
    from repro.video import synthetic
    rng = np.random.default_rng(3)
    sched = GraphScheduler(
        VideoFunctionGraph(HighLowProtocol(DET, CLF), det_params, clf_params),
        batcher=CrossStreamBatcher(max_chunks=4, window=0.05))
    sched.plane = _TouchResults()
    chunks = []
    for i in range(4):
        st = sched.add_stream(f"cam{i}", W=np.asarray(clf_params["W"]))
        for _ in range(3):
            c = synthetic.make_chunk(rng, "traffic", num_frames=2,
                                     hw=(32, 32))
            chunks.append(c)
            sched.submit(st, c, learn=True)
    sched.run_until_idle()
    return sched, chunks


def test_fused_run_crosses_every_span(fused_run):
    sched, _ = fused_run
    st = sched.span_stats
    missing = [n for n in FUSED_SPANS if not st.get(n, {}).get("n")]
    assert not missing
    assert st["vpaas.wait.prop_valid"]["n"] == \
        sched.hot_path_stats["flushes"]
    assert st["vpaas.finalize"]["n"] == sched.sched_stats["finalizes"] == 12
    assert st["vpaas.step"]["n"] == sched.sched_stats["events"]
    assert sched.hot_path_stats["flushes"] < 12      # flushes held chunks
    for name, a in st.items():
        assert 0.0 <= a["self_s"] <= a["s"] + 1e-12, name


def test_fused_run_identities(fused_run):
    sched, _ = fused_run
    ss, st = sched.sched_stats, sched.span_stats
    assert ss["step_wall_s"] == pytest.approx(st["vpaas.step"]["s"])
    assert ss["model_wall_s"] == pytest.approx(st["vpaas.dispatch"]["s"])
    assert sched.detect_stats["wall_s"] == pytest.approx(
        st["vpaas.detect"]["s"])
    assert ss["loop_self_wall_s"] + ss["loop_wait_wall_s"] == pytest.approx(
        ss["step_wall_s"] - ss["model_wall_s"], rel=1e-9)
    assert ss["dispatch_self_wall_s"] + ss["prop_valid_wait_wall_s"] == \
        pytest.approx(ss["model_wall_s"], rel=1e-9)
    assert ss["prop_valid_wait_wall_s"] == pytest.approx(
        st["vpaas.wait.prop_valid"]["s"])
    assert ss["loop_wait_wall_s"] == pytest.approx(
        st["vpaas.wait.encode_nbytes"]["s"]
        + st["vpaas.wait.result_fields"]["s"])


def test_fused_run_h2d_bytes(fused_run):
    sched, chunks = fused_run
    hps = sched.hot_path_stats
    frames = sum(c.frames.nbytes for c in chunks)
    # each chunk's HQ frames go up at encode and again for classify; each
    # flush uploads its (3, bucket) int32 index rows
    assert hps["h2d_bytes"] == 2 * frames + 3 * 4 * hps["crops_classified"]
