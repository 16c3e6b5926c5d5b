"""Model families: each configuration names one, a new one is a new file,
and the stand-in family makes the weights it always made; the traffic's
cameras keep one frame shape at any aspect ratio."""
from __future__ import annotations

import json
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))
sys.path.insert(0, ROOT)

from bench import harness  # noqa: E402
from bench.models import INTERFACE, family, known  # noqa: E402

with open(os.path.join(ROOT, "BENCHMARK.json")) as _f:
    BENCH = json.load(_f)


def _config(entry):
    with open(os.path.join(ROOT, entry["file"])) as f:
        return json.load(f)


@pytest.mark.parametrize("entry", BENCH["configs"], ids=lambda c: c["name"])
def test_every_configuration_names_a_family_with_the_interface(entry):
    cfg = _config(entry)
    fam = family(cfg)
    for name in INTERFACE:
        assert callable(getattr(fam, name)), name
    assert family(cfg) is fam             # loaded once, compiled once


def test_an_unknown_family_lists_the_known_ones():
    with pytest.raises(KeyError) as err:
        family({"name": "x", "models": "no-such-family"})
    for name in known():
        assert name in str(err.value)
    assert "standin" in known()
    with pytest.raises(KeyError):
        family({"name": "x"})             # the key is required


TOY = '''
import numpy as np

def make_weights(cfg, seed):
    return {"seed": seed}, {"W": np.zeros((2, 1))}

def detector_flops_per_frame(det):
    return 7 * det["side"]
'''


def test_a_new_family_is_a_new_file(tmp_path):
    models = tmp_path / "bench" / "models"
    models.mkdir(parents=True)
    (models / "toy.py").write_text(TOY)
    fam = family({"name": "toy-cfg", "models": "toy"}, root=str(tmp_path))
    det, clf = fam.make_weights({}, 11)
    assert det == {"seed": 11} and clf["W"].shape == (2, 1)
    assert fam.detector_flops_per_frame({"side": 3}) == 21
    assert known(str(tmp_path)) == ["toy"]


# sums, sums of magnitudes, first and last elements of a few leaves at seed
# 2**31 + 5, recorded on the CPU when the weights were made in
# bench/reference.py, before the families
PARENT_LEAVES = {
    ("det", "conv0", "w"): (-11.31708008266287, 578.695749217004,
                            0.35333630442619324, -0.3737393021583557),
    ("det", "conv2", "b"): (0.8319510615401668, 8.64896383388259,
                            -0.027527768164873123, 0.008928636088967323),
    ("det", "head", "w"): (-3.415904242923716, 142.99941694863082,
                           -0.04567667096853256, -0.0007311898516491055),
    ("clf", "conv1", "w"): (-13.099834678392654, 931.7503224059001,
                            0.354153037071228, -0.04809902235865593),
    ("clf", "proj"): (-9.8295787492209, 809.6249506749334,
                      -0.2081221491098404, 0.036302682012319565),
    ("clf", "W"): (3.71355950034922, 71.18999514676398,
                   -0.14193741977214813, -0.04499597102403641),
}


def test_standin_weights_are_the_ones_made_before():
    cfg = harness.load_cell("single-backlog")["config"]
    det, clf = family(cfg).make_weights(cfg, 2**31 + 5)
    for path, (total, mag, first, last) in PARENT_LEAVES.items():
        leaf = {"det": det, "clf": clf}[path[0]]
        for k in path[1:]:
            leaf = leaf[k]
        a = np.asarray(leaf, np.float64).reshape(-1)
        assert a.sum() == pytest.approx(total, rel=1e-9, abs=1e-9), path
        assert np.abs(a).sum() == pytest.approx(mag, rel=1e-9), path
        assert (a[0], a[-1]) == (first, last), path


def _traffic(hw, cameras=4, per_group=2, scenes=1):
    return {"cameras": cameras, "content_group": 1, "scenes": scenes,
            "scenes_seed": 5, "pool_chunks_per_group": per_group,
            "content": "traffic", "frames": 2, "hw": list(hw)}


def test_cameras_keep_a_wide_frame_shape():
    cams = harness.Cameras(_traffic((72, 128), scenes=2), seed=3)
    entries = [e for pool in cams.pools for e in pool]
    assert len(entries) == 8
    assert {e.frames.shape for e in entries} == {(2, 72, 128, 3)}
    with pytest.raises(ValueError, match="4 variants"):
        harness.Cameras(_traffic((72, 128), cameras=5, scenes=2), seed=3)


def test_cameras_deal_all_eight_variants_of_a_square_frame():
    cams = harness.Cameras(_traffic((32, 32)), seed=3)
    entries = cams.entries
    assert len(entries) == 8
    base = entries[0].frames
    for d, e in enumerate(entries):
        f = base
        if d & 1:
            f = f[:, :, ::-1]
        if d & 2:
            f = f[:, ::-1]
        if d & 4:
            f = f.transpose(0, 2, 1, 3)
        np.testing.assert_array_equal(e.frames, f)
    assert len({e.frames.tobytes() for e in entries}) == 8


def _entry_bytes(pools):
    return sorted(e.frames.tobytes() for pool in pools for e in pool)


def test_every_seed_deals_the_same_pool_in_another_order():
    tr = _traffic((32, 32), cameras=8, per_group=2, scenes=2)
    a, b = (harness.Cameras(tr, seed=s) for s in (3, 2**31 + 11))
    assert _entry_bytes(a.pools) == _entry_bytes(b.pools)
    assert len(_entry_bytes(a.pools)) == 16
    assert ([e.frames.tobytes() for p in a.pools for e in p]
            != [e.frames.tobytes() for p in b.pools for e in p])
    for fa, fb in zip(a.calibration_frames(4), b.calibration_frames(4)):
        np.testing.assert_array_equal(fa, fb)
    other = harness.Cameras(dict(tr, scenes_seed=6), seed=3)
    assert _entry_bytes(other.pools) != _entry_bytes(a.pools)


def test_nested_lists_become_hashable_tuples():
    out = harness._tuples({"sizes": [[32], [64, 128]], "hw": [720, 1280],
                           "name": "x", "n": 3})
    assert out == {"sizes": ((32,), (64, 128)), "hw": (720, 1280),
                   "name": "x", "n": 3}
    hash(tuple(out.values()))
