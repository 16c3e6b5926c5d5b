"""Continual-learning plane: zoo version lineage, drift debouncing,
budgeted labeling, background training, promotion/rollback, hot-swap,
adaptive SLO margin, and replica cold-start."""
import jax
import numpy as np
import pytest

from repro.configs.vpaas_video import ClassifierConfig, DetectorConfig
from repro.core.coordinator import MultiStreamCoordinator, StreamSpec
from repro.core.hitl import BACKGROUND, UNLABELED, OracleAnnotator
from repro.core.incremental import eval_accuracy
from repro.core.protocol import HighLowProtocol
from repro.learning import (BackgroundTrainer, ContinualLearningPlane,
                            DriftConfig, DriftDetector, LabelCandidate,
                            LabelingQueue, LearningConfig, PromotionGate,
                            ReplayBuffer, ShadowEvaluator)
from repro.models import classifier as clf_mod
from repro.models import detector as det_mod
from repro.serving.graph import MARGIN_BOUNDS
from repro.serving.registry import ModelZoo
from repro.serving.router import Router

DET = DetectorConfig(name="learn-test-det", image_hw=(32, 32),
                     widths=(8, 16))
CLF = ClassifierConfig(name="learn-test-clf", crop_hw=(16, 16),
                       widths=(8, 16), feature_dim=16)


@pytest.fixture(scope="module")
def models():
    det_params = det_mod.init_detector(DET, jax.random.PRNGKey(0))
    clf_params = clf_mod.init_classifier(CLF, jax.random.PRNGKey(1))
    return det_params, clf_params


def _chunks(seed, n, frames=2, drift=0.0):
    from repro.video import synthetic
    rng = np.random.default_rng(seed)
    return [synthetic.drifted_chunk(rng, "traffic", drift=drift,
                                    num_frames=frames, hw=(32, 32))
            for _ in range(n)]


def _features(n, seed=0, d=8, c=4):
    rng = np.random.default_rng(seed)
    centers = rng.normal(size=(c, d)) * 2.0
    labels = rng.integers(0, c, n)
    xs = centers[labels] + rng.normal(0, 0.3, (n, d))
    xs = np.concatenate([xs, np.ones((n, 1))], -1).astype(np.float32)
    return xs, labels


# ---------------------------------------------------------------------------
# ModelZoo version lineage
# ---------------------------------------------------------------------------
def test_model_zoo_lineage_roundtrip():
    """register -> candidate -> promote -> promote -> rollback twice must
    restore each prior live version's weights bit-identically."""
    zoo = ModelZoo()
    W1 = np.random.default_rng(0).normal(size=(5, 3)).astype(np.float32)
    zoo.register("fog-classifier", {"W": W1})
    assert zoo.get("fog-classifier").version == 1

    r2 = zoo.register_version("fog-classifier", {"W": W1 + 1.0},
                              lineage={"parent_version": 1,
                                       "data_span": (0.0, 2.5),
                                       "labels": 32})
    # candidates do not move the live pointer
    assert zoo.get("fog-classifier").version == 1
    assert r2.lineage["parent_version"] == 1
    assert r2.lineage["data_span"] == (0.0, 2.5)

    zoo.promote("fog-classifier", 2)
    assert zoo.get("fog-classifier").version == 2
    zoo.register_version("fog-classifier", {"W": W1 + 2.0})
    zoo.promote("fog-classifier", 3)
    assert zoo.promotion_log("fog-classifier") == [1, 2, 3]

    back = zoo.rollback("fog-classifier")
    assert back.version == 2
    np.testing.assert_array_equal(back.params["W"], W1 + 1.0)
    back = zoo.rollback("fog-classifier")
    assert back.version == 1
    np.testing.assert_array_equal(back.params["W"], W1)
    with pytest.raises(ValueError):
        zoo.rollback("fog-classifier")
    assert zoo.versions("fog-classifier") == [1, 2, 3]


def test_model_zoo_plain_register_promotes():
    zoo = ModelZoo()
    zoo.register("m", {"W": np.zeros(2)})
    zoo.register("m", {"W": np.ones(2)})
    assert zoo.get("m").version == 2         # pre-versioning behaviour
    assert zoo.rollback("m").version == 1


def test_model_zoo_prunes_stale_candidates():
    zoo = ModelZoo(keep_candidates=3)
    zoo.register("m", {"W": np.zeros(2)})    # v1: live
    for k in range(2, 9):                    # v2..v8: never promoted
        zoo.register_version("m", {"W": np.full(2, float(k))})
    assert zoo.versions("m") == [1, 6, 7, 8]   # oldest candidates evicted
    zoo.promote("m", 7)
    zoo.register_version("m", {"W": np.full(2, 9.0)})
    zoo.register_version("m", {"W": np.full(2, 10.0)})
    kept = zoo.versions("m")
    assert 1 in kept and 7 in kept           # promotion log survives
    assert kept == [1, 7, 8, 9, 10]          # newest candidates retained


# ---------------------------------------------------------------------------
# Drift detection + debouncing
# ---------------------------------------------------------------------------
def test_drift_detector_quiet_on_noisy_stationary_stream():
    rng = np.random.default_rng(3)
    det = DriftDetector(DriftConfig(window=6, warmup=4, threshold=0.3,
                                    patience=2, cooldown=4))
    for t in range(200):
        ev = det.observe("cam0", 0.7 + rng.normal(0.0, 0.05), t)
        assert ev is None
    assert det.events == []


def test_drift_detector_debounces_noisy_drop():
    """A persistent noisy drop raises events spaced >= cooldown apart, not
    one per observation."""
    rng = np.random.default_rng(4)
    det = DriftDetector(DriftConfig(window=4, warmup=4, threshold=0.3,
                                    patience=2, cooldown=6))
    series = [0.8] * 8 + [0.3] * 30
    times = []
    for t, v in enumerate(series):
        if det.observe("cam0", v + rng.normal(0.0, 0.03), t) is not None:
            times.append(t)
    assert times, "the drop must be detected"
    assert all(b - a > 6 for a, b in zip(times, times[1:]))
    ev = det.events[0]
    assert ev.severity > 0.3
    assert 8 <= ev.onset_t <= ev.t          # onset at/after the step


def test_drift_detector_rebaseline_resets_reference():
    det = DriftDetector(DriftConfig(window=4, warmup=2, threshold=0.2,
                                    patience=1, cooldown=2))
    for t in range(6):
        det.observe("s", 0.8, t)
    for t in range(6, 12):
        det.observe("s", 0.4, t)
    assert det.events                        # drift fired
    det.rebaseline("s")
    assert det.baseline("s") == pytest.approx(det.ewma("s"))
    assert det.recovered("s")                # judged against the new level
    n = len(det.events)
    for t in range(12, 18):
        det.observe("s", 0.4, t)
    assert len(det.events) == n              # stable-at-new-level: no event


# ---------------------------------------------------------------------------
# Budgeted labeling (satellite: charge only labels actually issued)
# ---------------------------------------------------------------------------
def test_oracle_charges_only_issued_labels():
    gt_b = np.array([[0.1, 0.1, 0.5, 0.5]])
    gt_l = np.array([2])
    boxes = np.tile(gt_b, (5, 1))
    ann = OracleAnnotator(budget=3)
    out = ann.label_regions(boxes, gt_b, gt_l)
    assert list(out) == [2, 2, 2, UNLABELED, UNLABELED]
    assert ann.labels_provided == 3          # NOT 5: only issued labels
    assert ann.remaining == 0
    out = ann.label_regions(boxes, gt_b, gt_l)
    assert all(lab == UNLABELED for lab in out)
    assert ann.labels_provided == 3

    # a background verdict is charged (the operator inspected the region)
    ann2 = OracleAnnotator(budget=2)
    far = np.array([[0.8, 0.8, 0.9, 0.9]])
    out = ann2.label_regions(far, gt_b, gt_l)
    assert out[0] == BACKGROUND and ann2.labels_provided == 1


def test_labeling_queue_most_uncertain_first():
    gt_b = np.array([[0.1, 0.1, 0.5, 0.5]])
    gt_l = np.array([1])
    q = LabelingQueue(max_size=3)
    for margin in (0.8, 0.1, 0.4, 0.6):      # top-2 margin; low = uncertain
        q.push(LabelCandidate(
            features=np.ones(3), box=gt_b[0],
            scores=np.array([0.9, 0.9 - margin]),
            gt_boxes=gt_b, gt_labels=gt_l))
    assert len(q) == 3                       # bounded: least-uncertain evicted
    ann = OracleAnnotator()
    issued = q.issue(ann, 10)
    uncs = [i.candidate.uncertainty for i in issued]
    assert uncs == sorted(uncs, reverse=True)
    assert uncs[0] == pytest.approx(0.9)     # margin 0.1 candidate first
    assert ann.labels_provided == 3
    assert q.stats["issued"] == 3 and q.stats["dropped"] == 1


def test_labeling_queue_stops_at_budget():
    gt_b = np.array([[0.1, 0.1, 0.5, 0.5]])
    gt_l = np.array([1])
    q = LabelingQueue()
    for _ in range(6):
        q.push(LabelCandidate(features=np.ones(3), box=gt_b[0],
                              scores=np.array([0.6, 0.5]),
                              gt_boxes=gt_b, gt_labels=gt_l))
    ann = OracleAnnotator(budget=2)
    issued = q.issue(ann, 6)
    assert len(issued) == 2 and ann.labels_provided == 2
    assert len(q) == 4                       # unissued candidates remain


# ---------------------------------------------------------------------------
# Background trainer: versioned candidates with lineage
# ---------------------------------------------------------------------------
def test_trainer_registers_versions_with_lineage():
    xs, labels = _features(80, seed=7)
    zoo = ModelZoo()
    W0 = np.zeros((xs.shape[1], 4), np.float32)
    zoo.register("fog-classifier", {"W": W0})
    tr = BackgroundTrainer(zoo, num_classes=4, min_batch=16, eta=0.5)
    assert tr.maybe_train(W0) is None        # nothing buffered
    for i in range(40):
        tr.add_labeled(xs[i], int(labels[i]), t=float(i))
    rec = tr.maybe_train(W0, t=40.0, parent_version=1)
    assert rec is not None and rec.version == 2
    assert rec.lineage["parent_version"] == 1
    assert rec.lineage["data_span"] == (0.0, 39.0)
    assert rec.lineage["labels"] == 40       # fresh labels this round cost
    assert zoo.get("fog-classifier").version == 1    # candidate, not live
    assert tr.snapshots and tr.snapshot_versions == [2]
    # the candidate actually learned the labeling
    assert eval_accuracy(rec.params["W"], xs, labels) > 0.8
    # a second round charges only its own fresh labels, not the replay size
    for i in range(40, 60):
        tr.add_labeled(xs[i], int(labels[i]), t=float(i))
    rec2 = tr.maybe_train(rec.params["W"], t=60.0, parent_version=2)
    assert rec2.lineage["labels"] == 20
    assert rec2.lineage["replayed"] == 60
    # stale-data invalidation keeps only post-cutoff samples
    dropped = tr.drop_older_than(50.0)
    assert dropped == 50 and tr.buffered == 10


# ---------------------------------------------------------------------------
# Shadow evaluation, promotion gate, rollback
# ---------------------------------------------------------------------------
def test_promotion_gate_and_rollback_restore_bits():
    xs, labels = _features(120, seed=9)
    zoo = ModelZoo()
    W_good = np.zeros((xs.shape[1], 4), np.float32)
    for x, lab in zip(xs, labels):           # crude but sufficient readout
        W_good[:, lab] += 0.1 * x
    W_bad = -W_good
    zoo.register("fog-classifier", {"W": W_bad})

    ev = ShadowEvaluator(ReplayBuffer())
    gate = PromotionGate(ev, min_holdout=8, min_gain=0.05,
                         rollback_margin=0.2)
    # invariant 1: no promotion below min_holdout
    dec = gate.evaluate(W_bad, W_good)
    assert not dec["promote"]
    for x, lab in zip(xs[:40], labels[:40]):
        ev.holdout.add(x, int(lab), t=0.0)
    dec = gate.evaluate(W_bad, W_good)
    assert dec["promote"] and dec["cand_score"] > dec["live_score"]
    # invariant 2: a non-improving candidate is rejected
    assert not gate.evaluate(W_good, W_good)["promote"]

    rec = zoo.register_version("fog-classifier", {"W": W_good},
                               lineage={"parent_version": 1})
    zoo.promote("fog-classifier", rec.version)
    gate.note_promotion(dec["cand_score"])
    do, _ = gate.should_rollback(W_good, W_bad)
    assert not do                            # healthy: parent is worse
    # invariant 3: the parent beating the live model past the margin (on
    # the SAME holdout) triggers rollback...
    do, score = gate.should_rollback(W_bad, W_good)
    assert do and score < gate.promoted_score
    back = zoo.rollback("fog-classifier")
    gate.note_rollback()
    # invariant 4: ...and restores the prior weights bit-identically
    np.testing.assert_array_equal(back.params["W"], W_bad)
    assert gate.rollbacks == 1


# ---------------------------------------------------------------------------
# Hot-swap into the live scheduler: zero loss, no stall
# ---------------------------------------------------------------------------
class _SwapAt:
    """Test plane stub: hot-swaps a fixed W at the k-th finalized chunk."""

    def __init__(self, W, at):
        self.W, self.at, self.seen, self.inflight = W, at, 0, None

    def on_chunk(self, scheduler, stream, chunk, res, t, mode):
        self.seen += 1
        if self.seen == self.at:
            self.inflight = scheduler.hot_swap(self.W, version=99, t=t)


def test_hot_swap_mid_run_conserves_chunks(models):
    det_params, clf_params = models
    streams = [_chunks(1000 + i, 3) for i in range(4)]
    multi = MultiStreamCoordinator(HighLowProtocol(DET, CLF), det_params,
                                   clf_params, streams, max_batch_chunks=4,
                                   batch_window=0.05)
    W_new = np.asarray(clf_params["W"]) + 0.25
    stub = _SwapAt(W_new, at=2)
    multi.scheduler.plane = stub
    mout = multi.run(learn=True)

    assert stub.inflight is not None         # the swap actually ran mid-run
    # zero lost / duplicated chunk results across the swap
    seen = set()
    for i, chunks in enumerate(streams):
        st = multi.scheduler.streams[f"cam{i}"]
        assert [id(c) for c, _, _ in st.results] == [id(c) for c in chunks]
        seen.update(id(c) for c, _, _ in st.results)
        assert len(mout[f"cam{i}"].latencies) == len(chunks)
        np.testing.assert_array_equal(st.W, W_new)   # swap reached the stream
    assert len(seen) == sum(len(c) for c in streams)
    swaps = multi.scheduler.monitor.events_of("hot_swap")
    assert len(swaps) == 1 and swaps[0]["version"] == 99
    assert multi.scheduler.monitor.counters["hot_swaps"] == 1


def test_plane_attaches_and_collects_under_budget(models):
    det_params, clf_params = models
    # iou_threshold=0: random-init proposals never overlap ground truth,
    # and the machinery under test needs *class* labels, not all-background
    plane = ContinualLearningPlane(
        CLF.num_classes,
        LearningConfig(label_budget=32, labels_per_round=8,
                       sentinel_per_chunk=1, min_batch=2, min_holdout=2),
        annotator=OracleAnnotator(iou_threshold=0.0, budget=32))
    streams = [_chunks(1100 + i, 3) for i in range(2)]
    multi = MultiStreamCoordinator(HighLowProtocol(DET, CLF), det_params,
                                   clf_params, streams, max_batch_chunks=2,
                                   batch_window=0.05, learning_plane=plane)
    # random-init models give no usable drift statistic; force the
    # adaptation state to exercise label->train->version under budget
    plane.state = "adapt"
    multi.run(learn=True)
    s = plane.summary()
    assert 0 < s["labels_charged"] <= 32     # hard budget cap
    assert s["trainer"]["rounds"] >= 1       # background training happened
    zoo = multi.scheduler.graph.zoo
    assert len(zoo.versions("fog-classifier")) >= 2
    cand = zoo.get_version("fog-classifier",
                           zoo.versions("fog-classifier")[-1])
    assert "parent_version" in cand.lineage and "data_span" in cand.lineage
    assert multi.report()["learning"]["state"] in ("adapt", "exhausted",
                                                   "monitor")


# ---------------------------------------------------------------------------
# Adaptive SLO margin (satellite)
# ---------------------------------------------------------------------------
def test_adaptive_slo_margin_tracks_attainment(models):
    det_params, clf_params = models
    # impossible SLO: every chunk misses -> the margin must widen
    specs = [StreamSpec(name="cam0", chunks=_chunks(1200, 3), slo=1e-6)]
    multi = MultiStreamCoordinator(HighLowProtocol(DET, CLF), det_params,
                                   clf_params, specs, max_batch_chunks=1,
                                   batch_window=0.0)
    st = multi.scheduler.streams["cam0"]
    m0 = st.slo_margin
    multi.run(learn=False)
    assert st.slo_margin > m0
    assert st.att_ewma < 0.5

    # generous SLO: every chunk meets -> the margin tightens below initial
    specs = [StreamSpec(name="cam0", chunks=_chunks(1201, 3), slo=60.0)]
    multi = MultiStreamCoordinator(HighLowProtocol(DET, CLF), det_params,
                                   clf_params, specs, max_batch_chunks=1,
                                   batch_window=0.0)
    st = multi.scheduler.streams["cam0"]
    m0 = st.slo_margin
    multi.run(learn=False)
    assert st.slo_margin < m0
    lo, hi = MARGIN_BOUNDS
    assert lo <= st.slo_margin <= hi

    # opting out keeps the static headroom
    specs = [StreamSpec(name="cam0", chunks=_chunks(1202, 2), slo=1e-6)]
    multi = MultiStreamCoordinator(HighLowProtocol(DET, CLF), det_params,
                                   clf_params, specs, max_batch_chunks=1,
                                   batch_window=0.0, adaptive_margin=False)
    st = multi.scheduler.streams["cam0"]
    m0 = st.slo_margin
    multi.run(learn=False)
    assert st.slo_margin == m0


# ---------------------------------------------------------------------------
# Replica cold-start (satellite)
# ---------------------------------------------------------------------------
def test_scale_replicas_models_cold_start(models):
    det_params, clf_params = models
    graph_proto = HighLowProtocol(DET, CLF)
    from repro.serving.executor import Executor
    from repro.serving.registry import FunctionRegistry

    reg = FunctionRegistry()

    def factory(uid):
        return Executor(f"cloud-{uid}", reg, graph_proto.cloud,
                        num_devices=2)

    router = Router([factory(0)], replica_factory=factory, cold_start_s=1.5)
    router.scale_replicas(3, now=5.0)
    assert len(router.replicas) == 3
    for rep in router.replicas[1:]:          # the new replicas spin up busy
        assert rep.executor.busy_until == [6.5, 6.5]
        assert rep.executor.clock >= 5.0
    # primary is untouched
    assert router.replicas[0].executor.busy_until == [0.0, 0.0]
    assert len(router.monitor.values("replica_cold_start")) == 2

    # zero cold-start keeps free-at-now semantics
    router2 = Router([factory(0)], replica_factory=factory)
    router2.scale_replicas(2, now=3.0)
    assert router2.replicas[1].executor.busy_until == [3.0, 3.0]
