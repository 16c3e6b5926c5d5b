"""CPU rehearsal of the benchmark's plumbing: every cell resolves its files,
the contract's shape holds, and the entry point refuses to run off the chip."""
from __future__ import annotations

import json
import os
import re
import shutil
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))
sys.path.insert(0, ROOT)

from bench import harness  # noqa: E402

with open(os.path.join(ROOT, "BENCHMARK.json")) as _f:
    BENCH = json.load(_f)
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


@pytest.mark.parametrize("workload", [w["name"] for w in BENCH["workloads"]])
def test_workload_resolves_its_files(workload):
    cell = harness.load_cell(workload)
    assert cell["config"]["name"] == next(
        w["config"] for w in BENCH["workloads"] if w["name"] == workload)
    assert cell["traffic"]["mode"] in ("backlog", "live")
    names = {m["name"] for m in cell["end_to_end"]}
    assert "setup_s" in names and len(names) >= 2
    assert cell["per_layer"]
    for m in cell["per_layer"]:
        assert callable(harness.metric_reader(m["name"]))
        assert m["moves"] in names


def test_benchmark_json_keeps_the_contract_shape():
    assert BENCH["command"][1] == "bench/run.py"
    assert BENCH["paths"] == ["bench"]
    assert 1 <= BENCH["run_seconds"] <= 51
    for c in BENCH["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert c["file"].startswith("bench/")
        assert os.path.isfile(os.path.join(ROOT, c["file"]))
    seen = set()
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        for e in BENCH[group]:
            assert NAME.match(e["name"]), e["name"]
            assert e["name"] not in seen
            seen.add(e["name"])
    cells = {w["name"] for w in BENCH["workloads"]}
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    for m in BENCH["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    for m in BENCH["per_layer"]:
        assert set(m["workloads"]) <= cells
        moved = e2e[m["moves"]]
        assert set(m["workloads"]) <= set(moved.get("workloads", cells))
        if m["name"].endswith("_roofline"):
            assert m["unit"] == "%"


def _run_cpu(cwd, *args):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, os.path.join(cwd, "bench", "run.py"), *args],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_run_refuses_the_cpu():
    proc = _run_cpu(ROOT, "--workload", BENCH["workloads"][0]["name"],
                    "--seed", str(2**31 + 11), "--seconds", "1",
                    "--trace", "0")
    assert proc.returncode != 0
    assert "no TPU" in proc.stderr
    assert not proc.stdout.strip()


def test_run_refuses_a_checkout_without_the_program(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "bench"), tmp_path / "bench",
                    ignore=shutil.ignore_patterns("out", "__pycache__"))
    proc = _run_cpu(str(tmp_path), "--workload",
                    BENCH["workloads"][0]["name"], "--seed", "1",
                    "--seconds", "1", "--trace", "0")
    assert proc.returncode != 0
    assert not proc.stdout.strip()
