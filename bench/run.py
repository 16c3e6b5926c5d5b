#!/usr/bin/env python3
"""Chip benchmark of the VPaaS cloud-fog serving path: one run of one cell.

Usage (from the root of a checkout, on a machine with the cell's chips):

  python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell names a configuration, a traffic mix and its metrics in
``BENCHMARK.json``.  With ``--trace 0`` the last line of standard output is
one JSON object with the cell's end-to-end metrics; with ``--trace 1`` a
traced run reports its per-layer metrics, the device's busy seconds over
the traced window and the breakdown.  Either way the run is checked against
the reference and the numbers compared, each beside its limit, are the
last lines of standard error and the last key of the result line.

Exits non-zero and prints no result when JAX finds no TPU (or fewer chips
than the cell asks for), or when the program is not in the checkout.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def setup_jax():
    """The program's compile cache, with every program kept in it."""
    sys.path.insert(0, os.path.join(ROOT, "src"))
    sys.path.insert(0, ROOT)
    from repro import compile_cache
    path = compile_cache.configure()
    import jax
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path


def main(argv=None, require_chip: bool = True) -> int:
    args = parse(argv)
    if not os.path.isdir(os.path.join(ROOT, "src", "repro")):
        print("bench: the program (src/repro) is not in this checkout",
              file=sys.stderr)
        return 2
    cache = setup_jax()
    import jax

    from bench import harness
    cell = harness.load_cell(args.workload)
    devices = jax.devices()
    if require_chip and (devices[0].platform != "tpu"
                         or len(devices) < cell["chips"]):
        print(f"bench: no TPU: JAX reports {len(devices)} "
              f"{devices[0].platform} device(s); cell {cell['name']} needs "
              f"{cell['chips']} TPU chip(s)", file=sys.stderr)
        return 1
    harness.log(f"[device] {devices[0].platform} {devices[0].device_kind} "
                f"x{len(devices)}, compile cache {cache}")
    result = harness.run(cell, args.seed, args.seconds, bool(args.trace),
                         T_START)
    for key, (value, limit) in result["check"].items():
        print(f"check {key} {value!r} limit {limit!r}", file=sys.stderr)
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
