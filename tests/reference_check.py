"""One comparison of what the served path returns with the reference.

The served path (fused ``detect_split`` and compacted classify over a
packed cross-stream flush) and the one-chunk reference
(``HighLowProtocol.process_chunk``, or ``classify_regions`` /
``classify_ensemble`` on a given split) compute the same function in
different XLA programs.  A compiler may contract multiply-adds differently
in each fusion, so their floats may differ in the last bits; every mask,
count and clearly decided label must still agree exactly.

Works on a :class:`~repro.core.protocol.ChunkResult` (or the scheduler's
lazy twin) and on a classify stage's merged dict alike: a field is
compared when the reference has it.
"""
import numpy as np

# The largest gap seen between the served and the reference programs on
# the CPU is 1.19e-7 (fog scores; 2.98e-8 on other fields).  1e-5 is about
# 80 times that, and far under what a fault moves: a swapped readout, a
# zeroed crop or a box moved by 1/128 each move a value by 1e-3 or more.
ATOL = 1e-5

FLOAT_FIELDS = ("boxes", "prop_boxes", "fog_scores", "fog_features",
                "scores", "features")
EXACT_FIELDS = ("valid", "prop_valid", "source")
EXACT_SCALARS = ("coord_bytes", "wan_bytes", "cloud_frames")


def _get(obj, name):
    if isinstance(obj, dict):
        return obj.get(name)
    return getattr(obj, name, None)


def assert_close(got, want, name: str = "") -> None:
    """One float field, at the one stated tolerance."""
    got, want = np.asarray(got), np.asarray(want)
    assert got.shape == want.shape, (name, got.shape, want.shape)
    np.testing.assert_allclose(got, want, rtol=0, atol=ATOL, err_msg=name)


def clear_labels(ref) -> np.ndarray:
    """Where the reference's label is decided by more than twice the
    tolerance: every cloud-accepted slot, and a fog-decided slot whose
    top-two fog score gap exceeds 2 x ATOL."""
    labels = np.asarray(_get(ref, "labels"))
    source = _get(ref, "source")
    scores = _get(ref, "fog_scores")
    if source is None or scores is None:
        return np.ones(labels.shape, bool)
    top2 = np.sort(np.asarray(scores), axis=-1)[..., -2:]
    gap = top2[..., 1] - top2[..., 0]
    return (np.asarray(source) == 0) | (gap > 2 * ATOL)


def assert_matches_reference(got, ref) -> None:
    """Hold a served result (or merged grid) to the reference's."""
    for name in FLOAT_FIELDS:
        want = _get(ref, name)
        if want is not None:
            assert_close(_get(got, name), want, name)
    for name in EXACT_FIELDS:
        want = _get(ref, name)
        if want is not None:
            np.testing.assert_array_equal(np.asarray(_get(got, name)),
                                          np.asarray(want), err_msg=name)
    for name in EXACT_SCALARS:
        want = _get(ref, name)
        if want is not None:
            assert _get(got, name) == want, (name, _get(got, name), want)
    want = _get(ref, "labels")
    if want is not None:
        clear = clear_labels(ref)
        np.testing.assert_array_equal(np.asarray(_get(got, "labels"))[clear],
                                      np.asarray(want)[clear],
                                      err_msg="labels")
