"""Per-site continual learning + Eq. 9 ensemble serving: multi-readout
stage numerics (against oracles, bitwise in degenerate cases), per-stream
hot-swap isolation, active sentinel scheduling, lazy per-field results,
and the benchmark regression gate."""
import json
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.vpaas_video import ClassifierConfig, DetectorConfig
from repro.core import protocol as pm
from repro.core import regions as reg
from repro.core.coordinator import MultiStreamCoordinator
from repro.core.hitl import OracleAnnotator
from repro.core.incremental import ensemble_predict
from repro.core.protocol import HighLowProtocol
from repro.learning import (ContinualLearningPlane, HealthPosterior,
                            LearningConfig)
from repro.models import classifier as clf_mod
from repro.models import detector as det_mod
from reference_check import assert_close, assert_matches_reference

DET = DetectorConfig(name="persite-test-det", image_hw=(32, 32),
                     widths=(8, 16))
CLF = ClassifierConfig(name="persite-test-clf", crop_hw=(16, 16),
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


def _ensemble(W0, t, seed=3):
    rng = np.random.default_rng(seed)
    snaps = np.stack([W0] + [W0 + rng.normal(0, 0.1, W0.shape
                                             ).astype(np.float32)
                             for _ in range(t - 1)])
    omega = rng.random(t).astype(np.float32)
    return snaps, omega / omega.sum()


# ---------------------------------------------------------------------------
# classify_multi with G>1 groups vs a per-stream loop oracle (satellite)
# ---------------------------------------------------------------------------
def test_classify_multi_matches_per_stream_loop(models):
    _, clf_params = models
    rng = np.random.default_rng(5)
    b, g = 11, 3
    crops = jnp.asarray(rng.random((b, 16, 16, 3), np.float32))
    W0 = np.asarray(clf_params["W"])
    Ws = np.stack([W0 + k * 0.1 for k in range(g)]).astype(np.float32)
    widx = rng.integers(0, g, b).astype(np.int32)

    out = clf_mod.classify_multi(CLF, clf_params, crops, jnp.asarray(Ws),
                                 jnp.asarray(widx))
    # oracle: classify each crop's group with the plain single-readout path
    for k in range(g):
        rows = np.nonzero(widx == k)[0]
        if not len(rows):
            continue
        ref = clf_mod.classify(CLF, clf_params, crops[rows],
                               W=jnp.asarray(Ws[k]))
        assert_close(np.asarray(out["scores"])[rows], ref["scores"],
                     "scores")
        assert_close(np.asarray(out["features"])[rows], ref["features"],
                     "features")


# ---------------------------------------------------------------------------
# Eq. 9 ensemble stages: degenerate bitwise + ensemble_predict equivalence
# ---------------------------------------------------------------------------
def test_classify_ensemble_matches_ensemble_predict(models):
    _, clf_params = models
    rng = np.random.default_rng(6)
    crops = jnp.asarray(rng.random((9, 16, 16, 3), np.float32))
    snaps, omega = _ensemble(np.asarray(clf_params["W"]), t=3)

    out = clf_mod.classify_ensemble(CLF, clf_params, crops,
                                    jnp.asarray(snaps), jnp.asarray(omega))
    ref = ensemble_predict(jnp.asarray(snaps), jnp.asarray(omega),
                           out["features"])
    np.testing.assert_allclose(np.asarray(out["scores"]), np.asarray(ref),
                               rtol=0, atol=1e-6)


def test_classify_ensemble_stage_degenerate_bitwise(models):
    """classify_ensemble with one snapshot and omega=[1.0] must be
    bitwise-identical to classify_regions — the multi-readout reference
    contains the single-readout reference as its degenerate case."""
    det_params, clf_params = models
    pcfg = pm.ProtocolConfig()
    rng = np.random.default_rng(7)
    frames = jnp.asarray(rng.random((3, 32, 32, 3), np.float32))
    split = pm.detect_split(DET, pcfg, det_params, frames)
    W = jnp.asarray(clf_params["W"])

    ref = pm.classify_regions(CLF, pcfg, clf_params, W, frames, split)
    ens = pm.classify_ensemble(CLF, pcfg, clf_params, W[None],
                               jnp.ones(1, jnp.float32), frames, split)
    for k in ref:
        np.testing.assert_array_equal(np.asarray(ref[k]),
                                      np.asarray(ens[k]))


def test_classify_compacted_ensemble_matches_full(models):
    """The compacted cross-stream ensemble scatters into the same grids as
    the full-budget ensemble stage — including zero-padded short lineages
    riding in a mixed flush."""
    det_params, clf_params = models
    pcfg = pm.ProtocolConfig()
    rng = np.random.default_rng(8)
    frames = jnp.asarray(rng.random((4, 32, 32, 3), np.float32))
    split = pm.detect_split(DET, pcfg, det_params, frames)
    pv = np.asarray(split.prop_valid)
    W0 = np.asarray(clf_params["W"])
    snaps, omega = _ensemble(W0, t=3)
    # group 0: the 3-snapshot ensemble; group 1: a plain readout padded to
    # T=3 with zero snapshots / zero omega (the mixed-flush degenerate row)
    snaps_g = np.zeros((2,) + snaps.shape, np.float32)
    omegas_g = np.zeros((2, 3), np.float32)
    snaps_g[0], omegas_g[0] = snaps, omega
    snaps_g[1, 0], omegas_g[1, 0] = W0 + 0.2, 1.0

    fidx, ridx, n_valid, size = reg.compaction_indices(pv, buckets=(4, 8))
    idxs = np.zeros((3, size), np.int32)
    idxs[0], idxs[1] = fidx, ridx
    # frames 0-1 -> group 0, frames 2-3 -> group 1
    if n_valid:
        idxs[2, :n_valid] = (fidx[:n_valid] >= 2).astype(np.int32)

    merged_c = pm.classify_compacted_ensemble(
        CLF, pcfg, clf_params, jnp.asarray(snaps_g), jnp.asarray(omegas_g),
        frames, split, jnp.asarray(idxs))

    # full-budget oracle, one ensemble stage per group over its own frames
    sl0, sl1 = slice(0, 2), slice(2, 4)
    for sl, g in ((sl0, 0), (sl1, 1)):
        sub = reg.RegionSplit(*(v[sl] for v in split))
        t_g = int(np.count_nonzero(omegas_g[g])) or 1
        ref = pm.classify_ensemble(
            CLF, pcfg, clf_params, jnp.asarray(snaps_g[g, :t_g]),
            jnp.asarray(omegas_g[g, :t_g]), frames[sl], sub)
        assert set(ref) == set(merged_c)
        assert_matches_reference(
            {k: np.asarray(v)[sl] for k, v in merged_c.items()}, ref)


def _ensemble_reference(proto, det_params, clf_params, frames, snaps,
                        omega):
    """``process_chunk``'s detect and split, then the full-grid Eq. 9
    ensemble classify: the one-chunk reference of an ensemble stream."""
    pcfg = proto.pcfg
    fhq = jnp.asarray(frames)
    enc = pm.encode_low(pcfg, fhq)
    det = pm.detect_regions(DET, det_params, enc.frames)
    split, coord_bytes = pm.split_uncertain(pcfg, det)
    merged = pm.classify_ensemble(CLF, pcfg, clf_params, jnp.asarray(snaps),
                                  jnp.asarray(omega), fhq, split)
    return pm.assemble_result(split, merged, wan_bytes=float(enc.nbytes),
                              coord_bytes=float(coord_bytes),
                              cloud_frames=frames.shape[0], latency=None)


def test_fused_matches_reference_with_mixed_ensemble_flush(models):
    """Flushes that mix an Eq. 9 ensemble stream with plain-readout
    streams serve each chunk as its one-chunk reference does."""
    det_params, clf_params = models
    streams = [_chunks(70 + i, 2) for i in range(3)]
    snaps, omega = _ensemble(np.asarray(clf_params["W"]), t=3)
    proto = HighLowProtocol(DET, CLF)
    multi = MultiStreamCoordinator(proto, det_params, clf_params, streams,
                                   max_batch_chunks=3, batch_window=0.05)
    multi.scheduler.hot_swap_ensemble(snaps, omega, stream="cam0")
    multi.run(learn=False)
    hps = multi.scheduler.hot_path_stats
    assert hps["ensemble_flushes"] > 0
    # the stacked (snaps, omegas) upload is memoized on flush composition:
    # uploads are counted and must not scale with flushes in a steady mix
    assert 1 <= hps["ensemble_uploads"] <= hps["ensemble_flushes"]
    for name, st in multi.scheduler.streams.items():
        assert len(st.results) == 2
        for chunk, res, _ in st.results:
            if name == "cam0":
                ref = _ensemble_reference(proto, det_params, clf_params,
                                          chunk.frames, snaps, omega)
            else:
                ref = proto.process_chunk(det_params, clf_params,
                                          chunk.frames, W=st.W)
            assert_matches_reference(res, ref)


# ---------------------------------------------------------------------------
# Per-stream hot-swap isolation
# ---------------------------------------------------------------------------
def test_hot_swap_single_stream_leaves_others_untouched(models):
    det_params, clf_params = models
    streams = [_chunks(90 + i, 1) for i in range(3)]
    multi = MultiStreamCoordinator(HighLowProtocol(DET, CLF), det_params,
                                   clf_params, streams)
    sched = multi.scheduler
    W0 = {n: s.W for n, s in sched.streams.items()}
    W_new = np.asarray(clf_params["W"]) + 0.5
    sched.hot_swap(W_new, version=7, stream="cam1")
    np.testing.assert_array_equal(sched.streams["cam1"].W, W_new)
    for name in ("cam0", "cam2"):
        assert sched.streams[name].W is W0[name]   # not even copied
    ev = sched.monitor.events_of("hot_swap")[-1]
    assert ev["stream"] == "cam1" and ev["version"] == 7

    # an ensemble swap targets one stream; a later W swap supersedes it
    snaps, omega = _ensemble(np.asarray(clf_params["W"]), t=2)
    sched.hot_swap_ensemble(snaps, omega, stream="cam1")
    assert sched.streams["cam1"].ensemble is not None
    assert sched.streams["cam0"].ensemble is None
    sched.hot_swap(W_new, stream="cam1")
    assert sched.streams["cam1"].ensemble is None


# ---------------------------------------------------------------------------
# Per-site learning plane: one camera's episode stays on that camera
# ---------------------------------------------------------------------------
def test_per_site_plane_isolates_lineages(models):
    det_params, clf_params = models
    plane = ContinualLearningPlane(
        CLF.num_classes,
        LearningConfig(label_budget=48, labels_per_round=8,
                       sentinel_per_chunk=1, min_batch=2, min_holdout=2,
                       per_site=True),
        annotator=OracleAnnotator(iou_threshold=0.0, budget=48))
    streams = [_chunks(1300 + i, 3) for i in range(3)]
    multi = MultiStreamCoordinator(HighLowProtocol(DET, CLF), det_params,
                                   clf_params, streams, max_batch_chunks=3,
                                   batch_window=0.05, learning_plane=plane)
    # pre-open cam0's site and force it into adaptation (random-init models
    # give no usable drift statistic); cam1/cam2 stay monitoring
    site0 = plane._site_for(multi.scheduler.streams["cam0"])
    site0.state = "adapt"
    W_before = {n: np.array(s.W) for n, s in multi.scheduler.streams.items()}
    multi.run(learn=True)

    zoo = multi.scheduler.graph.zoo
    # cam0's lineage trained and registered candidate versions ...
    assert site0.trainer.rounds >= 1
    assert len(zoo.versions("fog-classifier[cam0]")) >= 2
    # ... the other sites monitored only: no training, no new versions
    for name in ("cam1", "cam2"):
        site = plane._sites[name]
        assert site.state in ("monitor", "exhausted")
        assert site.trainer.rounds == 0
        assert zoo.versions(f"fog-classifier[{name}]") == [1]
        # zero weight changes on undrifted streams, bitwise
        np.testing.assert_array_equal(multi.scheduler.streams[name].W,
                                      W_before[name])
    # the budget is shared and hard-capped
    assert 0 < plane.annotator.labels_provided <= 48
    s = plane.summary()
    assert s["per_site"] and set(s["sites"]) == {"cam0", "cam1", "cam2"}


# ---------------------------------------------------------------------------
# Episode lineage mechanics: regime archive + pinned anchor
# ---------------------------------------------------------------------------
def test_replay_buffer_drop_archives_into_sibling():
    from repro.learning import ReplayBuffer
    holdout, archive = ReplayBuffer(), ReplayBuffer()
    for i in range(6):
        holdout.add(np.full(3, float(i)), i % 2, t=float(i))
    dropped = holdout.drop_older_than(3.0, into=archive)
    assert dropped == 3 and len(holdout) == 3 and len(archive) == 3
    xs, labels = archive.data()
    np.testing.assert_array_equal(xs[:, 0], [0.0, 1.0, 2.0])
    assert list(labels) == [0, 1, 0]
    # default behaviour (no sibling) still just discards
    assert holdout.drop_older_than(10.0) == 3 and len(archive) == 3


def test_trainer_pins_seed_anchor_through_trim():
    from repro.learning import BackgroundTrainer
    from repro.serving.registry import ModelZoo
    rng = np.random.default_rng(2)
    xs = np.concatenate([rng.normal(size=(200, 4)),
                         np.ones((200, 1))], -1).astype(np.float32)
    labels = rng.integers(0, 3, 200)
    zoo = ModelZoo()
    W0 = np.zeros((5, 3), np.float32)
    zoo.register("fog-classifier", {"W": W0})
    tr = BackgroundTrainer(zoo, num_classes=3, min_batch=4,
                           keep_snapshots=4)
    tr.seed_snapshot(W0, version=1)
    assert tr.seed_version == 1
    W = W0
    for round_ in range(8):                  # far beyond keep_snapshots
        for i in range(4):
            j = 4 * round_ + i
            tr.add_labeled(xs[j], int(labels[j]), t=float(j))
        rec = tr.maybe_train(W, t=float(round_), parent_version=1)
        W = rec.params["W"]
    # the rolling window trimmed the middle, never the anchor
    assert len(tr.snapshots) == 4
    assert tr.snapshot_versions[0] == 1
    np.testing.assert_array_equal(tr.snapshots[0], W0)
    assert tr.snapshot_versions[-1] == rec.version
    # fit over a restricted lineage keeps exactly those versions
    keep = {1, rec.version}
    omega = tr.fit_ensemble(versions=keep)
    snaps, om = tr.ensemble()
    assert omega is not None and snaps.shape[0] == 2 and om.shape == (2,)
    np.testing.assert_array_equal(snaps[0], W0)

    # degenerate cap: keep_snapshots=1 cannot honour both the cap and the
    # pin — it must stay capped (plain newest-only trim), never grow
    tr1 = BackgroundTrainer(zoo, num_classes=3, min_batch=4,
                            keep_snapshots=1)
    tr1.seed_snapshot(W0, version=1)
    for round_ in range(5):
        for i in range(4):
            j = 4 * round_ + i
            tr1.add_labeled(xs[j], int(labels[j]), t=float(j))
        tr1.maybe_train(W0, t=float(round_), parent_version=1)
    assert len(tr1.snapshots) == 1 and len(tr1.snapshot_versions) == 1


# ---------------------------------------------------------------------------
# Active sentinel scheduling
# ---------------------------------------------------------------------------
def test_health_posterior_concentrates_and_decays():
    h = HealthPosterior(decay=0.9)
    prior_std = h.std("fresh")
    for _ in range(40):
        h.observe_chunk("steady")
        h.update("steady", True)
    assert h.std("steady") < prior_std
    assert h.mean("steady") > 0.8
    # without new verdicts the pseudo-counts decay back toward the prior
    before = h.std("steady")
    for _ in range(200):
        h.observe_chunk("steady")
    assert h.std("steady") > before
    assert h.std("steady") == pytest.approx(prior_std, abs=1e-3)


def test_active_sentinel_targets_uncertain_stream_under_budget():
    cfg = LearningConfig(sentinel_mode="active", sentinel_per_chunk=2,
                         sentinel_max_per_chunk=6)
    plane = ContinualLearningPlane(4, cfg)
    rng = np.random.default_rng(0)
    spent = {"steady": 0, "erratic": 0}
    chunks = 0
    for _ in range(120):
        for name in ("steady", "erratic"):
            chunks += 1
            plane.health.observe_chunk(name)
            k = plane._sentinel_allowance(name)
            spent[name] += k
            # the sentinel's verdicts drive the posterior: steady is always
            # right, erratic is a coin flip
            for _ in range(k):
                plane.health.update(
                    name, True if name == "steady" else bool(rng.random()
                                                             < 0.5))
    # conservation: never more than the uniform policy's total allowance
    assert spent["steady"] + spent["erratic"] <= chunks * 2
    # the checks concentrate where the health posterior is least certain
    assert spent["erratic"] > 1.3 * spent["steady"]
    # nobody is starved: decay keeps even the steady stream checked
    assert spent["steady"] > 0


def test_uniform_sentinel_unchanged():
    plane = ContinualLearningPlane(4, LearningConfig(sentinel_per_chunk=3))
    assert all(plane._sentinel_allowance("s") == 3 for _ in range(5))


# ---------------------------------------------------------------------------
# Per-field lazy ChunkResult (satellite): HITL-off never pays for features
# ---------------------------------------------------------------------------
def test_lazy_result_fields_download_on_demand(models):
    det_params, clf_params = models
    streams = [_chunks(1500 + i, 2) for i in range(3)]
    multi = MultiStreamCoordinator(HighLowProtocol(DET, CLF), det_params,
                                   clf_params, streams, max_batch_chunks=3,
                                   batch_window=0.05)
    sched = multi.scheduler
    for state, spec in zip(multi._states, multi.specs):
        for chunk in spec.chunks:
            sched.submit(state, chunk, learn=False)
    sched.run_until_idle()
    # the serving drain itself reads only scalars: no field downloads at all
    assert sched.field_downloads == {}
    assert sched.hot_path_stats["result_downloads"] == 0
    multi.results()                                  # offline F1 evaluation
    flushes = sched.hot_path_stats["flushes"]
    # the F1 pass touches exactly boxes/labels/valid, once per flush ...
    assert sched.field_downloads["boxes"] == flushes
    assert sched.field_downloads["labels"] == flushes
    assert sched.field_downloads["valid"] == flushes
    # ... and the HITL hand-off arrays are never materialized (regression:
    # HITL-off runs used to download fog_features they never read)
    assert sched.field_downloads.get("fog_features", 0) == 0
    assert sched.field_downloads.get("fog_scores", 0) == 0
    assert sched.hot_path_stats["result_downloads"] == flushes

    # repeated access does not re-download
    res = sched.streams["cam0"].results[0][1]
    _ = res.boxes, res.boxes, res.valid
    assert sched.field_downloads["boxes"] == flushes

    # a learning run DOES read the hand-off fields
    plane = ContinualLearningPlane(
        CLF.num_classes,
        LearningConfig(label_budget=16, sentinel_per_chunk=1),
        annotator=OracleAnnotator(iou_threshold=0.0, budget=16))
    multi2 = MultiStreamCoordinator(HighLowProtocol(DET, CLF), det_params,
                                    clf_params, streams, max_batch_chunks=3,
                                    batch_window=0.05, learning_plane=plane)
    multi2.run(learn=True)
    assert multi2.scheduler.field_downloads.get("fog_scores", 0) > 0


# ---------------------------------------------------------------------------
# Benchmark regression gate (satellite)
# ---------------------------------------------------------------------------
BASELINE = {
    "host_syncs_per_flush_fused": 1.0, "classify_flops_saved_frac": 0.79,
    "workload": {"streams": 8, "chunks_per_stream": 4},
}


def _run_gate(tmp_path, fresh, args=()):
    base = tmp_path / "baseline.json"
    new = tmp_path / "fresh.json"
    base.write_text(json.dumps(BASELINE))
    new.write_text(json.dumps(fresh))
    return subprocess.run(
        [sys.executable, "scripts/check_bench_regression.py",
         "--baseline", str(base), "--fresh", str(new), *args],
        capture_output=True, text=True)


def test_bench_regression_gate_passes_on_equal(tmp_path):
    out = _run_gate(tmp_path, dict(BASELINE))
    assert out.returncode == 0, out.stdout + out.stderr


def test_bench_regression_gate_fails_on_degraded(tmp_path):
    degraded = dict(BASELINE, classify_flops_saved_frac=0.4)
    out = _run_gate(tmp_path, degraded)
    assert out.returncode != 0
    assert "classify_flops_saved_frac" in out.stdout + out.stderr

    worse_syncs = dict(BASELINE, host_syncs_per_flush_fused=3.0)
    out = _run_gate(tmp_path, worse_syncs)
    assert out.returncode != 0
    assert "host_syncs" in out.stdout + out.stderr


def test_bench_regression_gate_tolerates_noise(tmp_path):
    wobble = dict(BASELINE,                       # within 20% tolerance
                  classify_flops_saved_frac=0.79 * 0.85)
    out = _run_gate(tmp_path, wobble)
    assert out.returncode == 0, out.stdout + out.stderr


def test_bench_regression_gate_skips_speedup_across_workloads(tmp_path):
    """A quick-mode fresh run (different workload) still gates the
    workload-invariant metrics but not the workload-bound compaction
    share."""
    quick = dict(BASELINE, classify_flops_saved_frac=0.4,
                 workload={"streams": 4, "chunks_per_stream": 2})
    out = _run_gate(tmp_path, quick)
    assert out.returncode == 0, out.stdout + out.stderr
    quick_bad = dict(quick, host_syncs_per_flush_fused=5.0)
    out = _run_gate(tmp_path, quick_bad)
    assert out.returncode != 0
    # a payload that DROPS workload fields must not masquerade as the
    # baseline's workload (field-for-field equality, not intersection)
    dropped = dict(BASELINE, classify_flops_saved_frac=0.4,
                   workload={"streams": 8})
    out = _run_gate(tmp_path, dropped)
    assert out.returncode == 0, out.stdout + out.stderr
    assert "different workload" in out.stdout


def test_bench_regression_gate_self_test():
    out = subprocess.run(
        [sys.executable, "scripts/check_bench_regression.py", "--self-test"],
        capture_output=True, text=True)
    assert out.returncode == 0, out.stdout + out.stderr
