#!/usr/bin/env python3
"""Readings for the limits of the check: sound runs of a cell and its controls.

For each seed, one run of the cell as the benchmark runs it (a short window
at the cell's own load), checked against the float32 reference; then, on
the same sampled chunks, the reference itself put in the program's place at
a lower precision (``--precisions``, default float8 and bfloat16 operands)
and held to the float32 reference by the same comparison.  All seeds run in
one process, so programs compile once.  One JSON line per seed:
``{"seed", "correct", "check": {number: [value, limit]}, "controls":
{precision: {number: [value, limit]}}}``.

  python3 bench/control.py --workload single-backlog --seeds 1,2,3 --seconds 3

The benchmark's own runs never run the controls.  Exits non-zero without a
TPU.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import run as bench_run  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated seeds")
    ap.add_argument("--seconds", type=float, default=3.0)
    ap.add_argument("--precisions", default="fp8,bf16")
    args = ap.parse_args(argv)
    bench_run.setup_jax()
    import jax

    from bench import harness
    cell = harness.load_cell(args.workload)
    if jax.devices()[0].platform != "tpu":
        print("control: no TPU", file=sys.stderr)
        return 1
    precisions = tuple(p for p in args.precisions.split(",") if p)
    for seed in (int(s) for s in args.seeds.split(",")):
        res = harness.run(cell, seed, args.seconds, False, time.perf_counter(),
                          controls=precisions)
        print(json.dumps({"seed": seed, "correct": res["correct"],
                          "check": res["check"],
                          "controls": res["controls"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
