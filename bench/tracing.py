"""Profiler capture and the reduction from a device trace to numbers.

``reduce`` reads one ``.xplane.pb`` (``jax.profiler.ProfileData``) and
returns, for the device planes:

  busy_s      union of the intervals in which an XLA op ran, averaged over
              the devices traced
  modules     per jitted program (``XLA Modules`` line, name without the
              ``jit_`` prefix and the ``(id)`` suffix): executions, seconds
  device_ops  the ten ops (HLO name and output shape) that took most time
  idle_gaps   the ten longest gaps between busy intervals, each named by the
              innermost benchmark host span (``bench.*``) over its middle

The harness names its host spans with ``jax.profiler.TraceAnnotation``:
``bench.step`` (one scheduler event), ``bench.submit`` (a camera hands in
a chunk), ``bench.result_download`` (result fields to the host) and
``bench.sleep`` (the generator waits for the next due chunk).
"""
from __future__ import annotations

import glob
import os
from typing import Dict, List, Tuple

HOST_SPAN_PREFIX = "bench."


def module_name(raw: str) -> str:
    """``jit_detect_split(123)`` -> ``detect_split``."""
    name = raw.split("(", 1)[0]
    return name[4:] if name.startswith("jit_") else name


def op_name(raw: str) -> str:
    """``%fusion.1 = f32[8,3]{1,0:T(8,128)} fusion(...)`` ->
    ``fusion.1 f32[8,3]``; a name with no HLO text is kept."""
    lhs, _, rhs = raw.partition(" = ")
    shape = rhs.split("{", 1)[0].split(" ", 1)[0]
    return f"{lhs.lstrip('%')} {shape}".strip()


def latest_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile",
                                          "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def _union(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[Tuple[float, float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def reduce_planes(planes) -> Dict:
    """The reduction over ``[(plane_name, [(line_name, [(name, start_ns,
    dur_ns), ...]), ...]), ...]`` -- the shape ``load`` gives a trace."""
    # a device plane that ran programs has an "XLA Ops" line; others, such
    # as "/device:CUSTOM:Megascale Trace", hold no ops and are not chips
    devices = [(n, lines) for n, lines in planes
               if n.startswith("/device:")
               and any(ln == "XLA Ops" for ln, _ in lines)]
    host_spans = [(s, s + d, name) for n, lines in planes
                  if n.startswith("/host:") for _, evs in lines
                  for name, s, d in evs if name.startswith(HOST_SPAN_PREFIX)]
    modules: Dict[str, List[float]] = {}
    ops: Dict[str, float] = {}
    busy_total = 0.0
    gaps: List[Tuple[float, str]] = []
    for _, lines in devices:
        by_name = dict(lines)
        op_events = by_name["XLA Ops"]
        for name, _, d in by_name.get("XLA Modules", []):
            m = modules.setdefault(module_name(name), [0, 0.0])
            m[0] += 1
            m[1] += d * 1e-9
        for name, _, d in op_events:
            key = op_name(name)
            ops[key] = ops.get(key, 0.0) + d * 1e-9
        busy = _union([(s, s + d) for _, s, d in op_events])
        busy_total += sum(e - s for s, e in busy) * 1e-9
        for (_, e0), (s1, _) in zip(busy, busy[1:]):
            mid = 0.5 * (e0 + s1)
            over = [(he - hs, name) for hs, he, name in host_spans
                    if hs <= mid <= he]
            name = (min(over)[1][len(HOST_SPAN_PREFIX):] if over
                    else "no benchmark span")
            gaps.append(((s1 - e0) * 1e-9, name))
    gaps.sort(reverse=True)
    n_dev = max(1, len(devices))
    return {
        "devices": len(devices),
        "busy_s": busy_total / n_dev,
        "modules": {k: {"count": v[0], "seconds": v[1]}
                    for k, v in modules.items()},
        "device_ops": [[k, v] for k, v in
                       sorted(ops.items(), key=lambda kv: -kv[1])[:10]],
        "idle_gaps": [[name, s] for s, name in gaps[:10]],
    }


def load(path: str):
    """A trace file as ``reduce_planes`` takes it."""
    import jax
    pd = jax.profiler.ProfileData.from_file(path)
    return [(p.name, [(ln.name, [(e.name, e.start_ns, e.duration_ns)
                                 for e in ln.events]) for ln in p.lines])
            for p in pd.planes]


def reduce(path: str) -> Dict:
    return reduce_planes(load(path))
