"""The check that decides ``correct`` fails what it must.

A run of the harness on the CPU, with its look for a chip skipped, at the
served model widths and a small traffic mix: sound, it is correct; with the
timed path broken underneath (an answer altered where it is produced:
detections moved, fog scores scaled, labels changed, an NMS pass or the
IoU filter of the split left out; part of a flush's frames left out), it
is not.  And the float8 control -- the
reference computed with float8 operands in the program's place -- fails
the comparison with the float32 reference.

The stand-in's numbers are the ones the check gave before it matched
regions by identity, with the program's identities or without.  A toy
two-stage family that selects its regions (``data/two_stage.py``, written
with its configuration under a temporary root, as a new family's files
would be) is checked with its bfloat16 reference standing for the
program: it selects other regions than the float32 reference and is
correct; a clear region dropped, ``post_k`` halved, NMS at 0.5, identities
shuffled against their boxes, boxes moved and the float8 control are not.
"""
from __future__ import annotations

import json
import os
import shutil
import sys
import time

import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.join(ROOT, "src"))

from bench import check, harness, models, reference  # noqa: E402
from bench.models import INTERFACE, family  # noqa: E402
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
def standin_chunks():
    """The stand-in configuration, weights from SEED and two 8-frame chunks
    made from it."""
    cfg = harness.load_cell("single-backlog")["config"]
    det, clf = family(cfg).make_weights(cfg, SEED)
    rng = np.random.default_rng(SEED)
    chunks = [make_chunk(rng, "traffic", num_frames=8).frames
              for _ in range(2)]
    return cfg, det, clf, chunks


@pytest.fixture(scope="module")
def control_readings(standin_chunks):
    cfg, det, clf, chunks = standin_chunks
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


# the check's numbers on standin_chunks with the reference at each precision
# in the program's place, as the check gave them before it matched regions
# by identity (recorded on the CPU)
PARENT_NUMBERS = {
    "bf16": ({"boxes": 0.005262255668640137, "split_errors": 0,
              "overlap_errors": 0, "fog_scores": 0.006623953580856323,
              "fog_features": 0.004876167979091406, "merge_errors": 0},
             {"chunks": 2, "frames": 16, "proposals": 865, "regions": 1343,
              "decisions_differ": 98, "split_held": 4239,
              "split_held_true": 339}),
    "fp8": ({"boxes": 0.11725160479545593, "split_errors": 105,
             "overlap_errors": 0, "fog_scores": 0.14292806386947632,
             "fog_features": 0.10165867954492569, "merge_errors": 0},
            {"chunks": 2, "frames": 16, "proposals": 885, "regions": 1705,
             "decisions_differ": 802, "split_held": 4239,
             "split_held_true": 339}),
}


@pytest.mark.parametrize("with_ids", [False, True], ids=["slots", "ids"])
@pytest.mark.parametrize("prec", sorted(PARENT_NUMBERS))
def test_standin_numbers_are_the_parents(standin_chunks, prec, with_ids):
    cfg, det, clf, chunks = standin_chunks
    served = reference.serve(cfg, det, clf, clf["W"], chunks, precision=prec)
    fields = harness.CHECK_FIELDS + ((harness.REGION_IDS,) if with_ids
                                     else ())
    c = check.Check()
    check.hold(c, cfg, det, clf, clf["W"], chunks,
               [{k: g[k] for k in fields} for g in served])
    dev, counts = PARENT_NUMBERS[prec]
    assert set(c.dev) == set(dev)
    for k, v in dev.items():
        assert c.dev[k] == pytest.approx(v, rel=1e-9, abs=0), k
    assert {k: getattr(c, k) for k in counts} == counts
    assert c.regions_unmatched == 0
    assert list(c.numbers(check.limits(cfg))) == list(dev)


# ---------------------------------------------------------------------------
# A detector that selects its regions
# ---------------------------------------------------------------------------
TOY_SEEDS = [1, 3, 2**31 + 9]
TOY_CONFIG = {
    "name": "toy-two-stage",
    "models": "two_stage",
    "detector": {"image_hw": [32, 32], "in_channels": 3, "widths": [16, 32],
                 "anchors": [[0.15, 0.25], [0.3, 0.45]], "num_classes": 4,
                 "pre_k": 48, "nms_iou": 0.7, "post_k": 16, "record_k": 64,
                 "head_dim": 32, "class_scale": 4.0},
    "classifier": {"crop_hw": [8, 8], "in_channels": 3, "width": 8,
                   "feature_dim": 16, "num_classes": 4},
    "protocol": {"r_low": 0.8, "q_low": 36, "theta_cls": 0.85,
                 "theta_loc": 0.5, "theta_iou": 0.3, "theta_back": 0.5,
                 "fog_min_conf": 0.5, "inter_coding": True, "impl": "ref"},
    # bfloat16 in the program's place reads at most boxes 0.0045, fog_scores
    # 0.0020, fog_features 0.0113 over 18 seeds on the CPU; float8 fails
    # split_errors and selection_errors on each
    "limits": {"boxes": {"limit": 0.015}, "split_errors": {"limit": 0},
               "overlap_errors": {"limit": 0}, "fog_scores": {"limit": 0.005},
               "fog_features": {"limit": 0.025}, "merge_errors": {"limit": 0},
               "selection_errors": {"limit": 0}},
}


@pytest.fixture(scope="module")
def toy_root(tmp_path_factory):
    """A checkout's files for the toy family's cell, and nothing else of
    the benchmark's: the family, its configuration, its traffic and the
    ``BENCHMARK.json`` that names them."""
    root = tmp_path_factory.mktemp("toy")
    bench = root / "bench"
    for sub in ("models", "configs", "traffic"):
        (bench / sub).mkdir(parents=True)
    shutil.copy(os.path.join(ROOT, "bench", "tests", "data", "two_stage.py"),
                bench / "models" / "two_stage.py")
    (bench / "configs" / "toy-two-stage.json").write_text(
        json.dumps(TOY_CONFIG))
    (bench / "traffic" / "toy-backlog.json").write_text(json.dumps(
        {"mode": "backlog", "content": "traffic", "frames": 8,
         "hw": [32, 32]}))
    (root / "BENCHMARK.json").write_text(json.dumps({
        "configs": [{"name": "toy-two-stage",
                     "file": "bench/configs/toy-two-stage.json"}],
        "workloads": [{"name": "toy-two-stage.backlog",
                       "config": "toy-two-stage", "traffic": "toy-backlog",
                       "chips": 1}],
        "end_to_end": [{"name": "setup_s", "unit": "s"}], "per_layer": []}))
    return str(root)


@pytest.fixture
def toy(toy_root, monkeypatch):
    """The toy cell's configuration, its family found under its root."""
    monkeypatch.setattr(models, "ROOT", toy_root)
    return harness.load_cell("toy-two-stage.backlog", toy_root)["config"]


def _toy_inputs(cfg, seed):
    det, clf = family(cfg).make_weights(cfg, seed)
    rng = np.random.default_rng(seed)
    hw = tuple(cfg["detector"]["image_hw"])
    chunks = [make_chunk(rng, "traffic", num_frames=8, hw=hw).frames
              for _ in range(2)]
    return det, clf, chunks


def _toy_check(cfg, seed, fault=None) -> check.Check:
    """The toy cell checked with the bfloat16 reference in the program's
    place, under ``fault`` (a rule changed in the program's configuration,
    or a function of its results and of the two references)."""
    det, clf, chunks = _toy_inputs(cfg, seed)
    prog = cfg
    if isinstance(fault, dict):
        prog = dict(cfg, detector=dict(cfg["detector"], **fault))
    served = reference.serve(prog, det, clf, clf["W"], chunks,
                             precision="fp8" if fault == "fp8" else "bf16")
    if callable(fault):
        served = [fault(g, w, r) for g, w, r in zip(
            served, reference.detect(cfg, det, chunks),
            reference.detect(cfg, det, chunks, precision="bf16"))]
    c = check.Check()
    check.hold(c, cfg, det, clf, clf["W"], chunks, served)
    c.finish(check.limits(cfg))
    return c


@pytest.mark.parametrize("seed", TOY_SEEDS)
def test_toy_sound_run_is_correct(toy, seed):
    c = _toy_check(toy, seed)
    assert not c.failures, (c.failures, c.summary())
    assert c.selection_held and c.split_held_true and c.proposals


def test_toy_sound_runs_select_other_regions(toy):
    """The bfloat16 run selects other regions than the float32 reference
    in some frames, so the matching has slots to leave out."""
    differ = 0
    for seed in TOY_SEEDS:
        det, _, chunks = _toy_inputs(toy, seed)
        for w, r in zip(reference.detect(toy, det, chunks),
                        reference.detect(toy, det, chunks, precision="bf16")):
            differ += sum(set(a) != set(b) for a, b in zip(w["ids"], r["ids"]))
    assert differ


def _drop_a_clear_region(got, want, rough):
    """Empty the served slot of a region the selection clearly keeps."""
    got = dict(got)
    ids, status, _ = check.selection_status(want["selection"],
                                            rough["selection"], 0)
    slot = np.flatnonzero(np.isin(got["region_ids"][0], ids[status == 1]))[0]
    for key, empty in (("region_ids", -1), ("source", 1),
                       ("prop_valid", False), ("valid", False)):
        got[key] = got[key].copy()
        got[key][0, slot] = empty
    return got


def _shuffle_ids(got, want, rough):
    rng = np.random.default_rng(0)
    ids = got["region_ids"].copy()
    for f in range(len(ids)):
        used = np.flatnonzero(ids[f] >= 0)
        ids[f, used] = ids[f, rng.permutation(used)]
    return dict(got, region_ids=ids)


def _move_boxes(got, want, rough):
    boxes = np.clip(got["boxes"] + 0.05, 0.0, 1.0)
    return dict(got, boxes=boxes, prop_boxes=boxes)


TOY_FAULTS = {
    "clear_region_dropped": (_drop_a_clear_region, "selection_errors"),
    "post_k_halved": ({"post_k": 8}, "selection_errors"),
    "nms_at_0.5": ({"nms_iou": 0.5}, "selection_errors"),
    "ids_shuffled": (_shuffle_ids, "boxes"),
    "boxes_moved": (_move_boxes, "boxes"),
    "float8_control": ("fp8", None),
}


@pytest.mark.parametrize("name", list(TOY_FAULTS))
def test_toy_faults_are_caught(toy, name):
    fault, number = TOY_FAULTS[name]
    limits = check.limits(toy)
    for seed in TOY_SEEDS:
        c = _toy_check(toy, seed, fault)
        assert c.failures, (seed, c.numbers(limits))
        if number is not None:
            assert c.dev[number] > limits[number], (seed, c.numbers(limits))


def test_a_selecting_family_needs_only_its_own_files(toy, toy_root):
    """Its cell is checked from the family, the configuration and the
    traffic alone, and reports selection_errors beside its limit."""
    fam = family(toy)
    for name in INTERFACE:
        assert callable(getattr(fam, name)), name
    assert os.path.dirname(fam.__file__) == os.path.join(
        toy_root, "bench", "models")
    assert "two_stage" not in models.known(ROOT)
    numbers = _toy_check(toy, TOY_SEEDS[0]).numbers(check.limits(toy))
    assert numbers["selection_errors"] == [0, 0]
    assert numbers["regions_unmatched"][1] is None
