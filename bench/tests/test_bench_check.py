"""The check that decides ``correct`` fails what it must.

A run of the harness on the CPU, with its look for a chip skipped, at the
served model widths and a small traffic mix: sound, it is correct; with the
timed path broken underneath (an answer altered where it is produced:
detections moved, fog scores scaled, labels changed, an NMS pass or the
IoU filter of the split left out; part of a flush's frames left out), it
is not.  And the float8 control -- the
reference computed with float8 operands in the program's place -- fails
the comparison with the float32 reference.
"""
from __future__ import annotations

import os
import sys
import time

import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.join(ROOT, "src"))

from bench import check, harness, reference  # noqa: E402
from bench.models import family  # noqa: E402
from bench.scenes import make_chunk  # noqa: E402

SEED = 2**31 + 5


def _small_cell():
    cell = harness.load_cell("single-backlog")
    cell["traffic"].update(cameras=4, scenes=1, check_chunks=16,
                           warmup_quiet_s=0.5, warmup_max_s=120.0)
    return cell


def _correct() -> bool:
    res = harness.run(_small_cell(), SEED, 1.0, False, time.perf_counter())
    return res["correct"]


def test_sound_run_is_correct():
    assert _correct()


def test_altered_detections_are_caught(monkeypatch):
    from repro.core import protocol
    orig = protocol.detect_split

    def shifted(*a, **k):
        split = orig(*a, **k)
        boxes = jnp.clip(split.acc_boxes + 0.05, 0.0, 1.0)
        return split._replace(acc_boxes=boxes, prop_boxes=boxes)

    monkeypatch.setattr(protocol, "detect_split", shifted)
    assert not _correct()


def test_altered_fog_scores_are_caught(monkeypatch):
    from repro.core import protocol
    orig = protocol.classify_compacted

    def scaled(*a, **k):
        out = dict(orig(*a, **k))
        out["fog_scores"] = out["fog_scores"] * 0.8
        return out

    monkeypatch.setattr(protocol, "classify_compacted", scaled)
    assert not _correct()


def test_altered_labels_are_caught(monkeypatch):
    from repro.core import protocol
    orig = protocol.classify_compacted

    def relabelled(*a, **k):
        out = dict(orig(*a, **k))
        out["labels"] = (out["labels"] + 1) % out["fog_scores"].shape[-1]
        return out

    monkeypatch.setattr(protocol, "classify_compacted", relabelled)
    assert not _correct()


def test_live_mix_reports_latency_tails():
    """The open-loop generator: every chunk due in the window is served,
    its latency runs from its due time, and the run is checked."""
    cell = _small_cell()
    cell["traffic"].update(mode="live", fps=10.0)
    cell["end_to_end"] = [{"name": "chunk_p50_ms", "unit": "ms"},
                          {"name": "chunk_p95_ms", "unit": "ms"},
                          {"name": "setup_s", "unit": "s"}]
    res = harness.run(cell, SEED, 2.0, False, time.perf_counter())
    assert res["correct"], res["check"]
    assert res["attempted"] >= 8 and res["failed"] == 0
    p50 = res["metrics"]["chunk_p50_ms"]["value"]
    assert 0 < p50 <= res["metrics"]["chunk_p95_ms"]["value"] < 2e3


@pytest.fixture
def fresh_programs():
    """Programs traced anew, so that a fault planted in a function that a
    jitted stage calls is compiled in, and compiled out again after."""
    import jax
    jax.clear_caches()
    yield
    jax.clear_caches()


def test_nms_left_out_is_caught(monkeypatch, fresh_programs):
    from repro.kernels import ops
    monkeypatch.setattr(ops, "nms_mask", lambda boxes, scores, valid, **k:
                        valid)
    assert not _correct()


def test_iou_filter_left_out_is_caught(monkeypatch, fresh_programs):
    from repro.kernels import ops
    orig = ops.region_filter_mask

    def unfiltered(proposals, prop_valid, accepted, acc_valid, loc, **k):
        return orig(proposals, prop_valid, accepted,
                    jnp.zeros_like(acc_valid), loc, **k)

    monkeypatch.setattr(ops, "region_filter_mask", unfiltered)
    assert not _correct()


def test_frames_left_out_of_a_flush_are_caught(monkeypatch):
    from repro.serving import graph
    orig = graph.pack_frames_device

    def half(payloads, buckets):
        batch, slices, pad = orig(payloads, buckets=buckets)
        n = batch.shape[0] - pad
        return batch.at[n // 2:n].set(0.0), slices, pad

    monkeypatch.setattr(graph, "pack_frames_device", half)
    assert not _correct()


@pytest.fixture(scope="module")
def control_readings():
    cfg = harness.load_cell("single-backlog")["config"]
    det, clf = family(cfg).make_weights(cfg, SEED)
    rng = np.random.default_rng(SEED)
    chunks = [make_chunk(rng, "traffic", num_frames=8).frames
              for _ in range(2)]
    out = {}
    for prec in ("fp8",):
        c = check.Check()
        check.hold(c, cfg, det, clf, clf["W"], chunks, reference.serve(
            cfg, det, clf, clf["W"], chunks, precision=prec))
        out[prec] = c
    return out


def test_float8_control_fails(control_readings):
    c = control_readings["fp8"]
    limits = check.limits(harness.load_cell("single-backlog")["config"])
    assert not c.finish(limits), c.numbers(limits)
