"""The reduction from a device trace to the per-layer numbers."""
from __future__ import annotations

import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))

from bench import tracing  # noqa: E402

MS = 1_000_000   # ns


def _planes():
    ops = [("%fusion.12 = f32[8,4]{1,0:T(8,128)} fusion(f32[8,4] %p)",
            0, 2 * MS),
           ("%convolution.3 = f32[8,4]{1,0} convolution(f32[8,4] %a)",
            1 * MS, 2 * MS),
           ("%fusion.12 = f32[8,4]{1,0:T(8,128)} fusion(f32[8,4] %p)",
            6 * MS, 1 * MS),
           ("copy.1", 9 * MS, 1 * MS)]
    mods = [("jit_detect_split(17)", 0, 3 * MS),
            ("jit_classify_compacted(4)", 6 * MS, 1 * MS),
            ("jit_detect_split(17)", 9 * MS, 1 * MS)]
    host = [("bench.step", 0, 10 * MS), ("bench.submit", 3 * MS, 2 * MS),
            ("PjitFunction(detect_split)", 3 * MS, 1 * MS)]
    return [("/host:CPU", [("python", host)]),
            ("/device:TPU:0", [("XLA Modules", mods), ("XLA Ops", ops),
                               ("Steps", [("0", 0, 10 * MS)])]),
            # a device plane of the profiler's own, with no ops: no chip
            ("/device:CUSTOM:Megascale Trace", [])]


def test_reduce_by_hand():
    red = tracing.reduce_planes(_planes())
    assert red["devices"] == 1
    # ops cover [0, 3], [6, 7], [9, 10] ms
    assert red["busy_s"] == pytest.approx(5e-3)
    assert red["modules"]["detect_split"] == {"count": 2,
                                              "seconds": pytest.approx(4e-3)}
    assert red["modules"]["classify_compacted"]["count"] == 1
    assert red["device_ops"][0] == ["fusion.12 f32[8,4]",
                                    pytest.approx(3e-3)]
    assert ["copy.1", pytest.approx(1e-3)] in red["device_ops"]
    # gaps [3, 6] (middle 4.5 ms: inside submit, inside step) and [7, 9]
    assert red["idle_gaps"] == [["submit", pytest.approx(3e-3)],
                                ["step", pytest.approx(2e-3)]]


def test_op_name():
    assert tracing.op_name("%copy.41 = f32[8,256,40,40,3]{3,2,4,1,0:T(8,128)}"
                           " copy(f32[8,256,40,40,3] %bitcast.11)") == \
        "copy.41 f32[8,256,40,40,3]"
    assert tracing.op_name("fusion.7") == "fusion.7"


def test_module_name():
    assert tracing.module_name("jit_detect_split_donated(3)") == \
        "detect_split_donated"
    assert tracing.module_name("encode_inter") == "encode_inter"


def test_reduce_recorded_slice():
    """The events that start in 40 ms of a traced single-backlog run on a
    TPU v5e: the start of one compacted classify call (153 ms), whose crop
    gather is one 23 ms fusion, with the host's benchmark spans around."""
    path = os.path.join(HERE, "data", "trace_slice.json")
    with open(path) as f:
        planes = json.load(f)
    red = tracing.reduce_planes([(n, [(ln, [tuple(e) for e in evs])
                                      for ln, evs in lines])
                                 for n, lines in planes])
    assert red["devices"] == 1
    assert red["modules"] == {"classify_compacted": {
        "count": 1, "seconds": pytest.approx(0.1534, abs=1e-4)}}
    # the ops that start in the slice run back to back for 26 ms
    assert red["busy_s"] == pytest.approx(0.0261, abs=5e-4)
    assert red["device_ops"][0][0] == "fusion f32[3276800,3]"
    assert red["device_ops"][0][1] == pytest.approx(0.02339, abs=1e-4)
    assert red["idle_gaps"] and all(name for name, _ in red["idle_gaps"])
