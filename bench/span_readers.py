"""Readers of the scheduler's host-span parts and its upload counter.

The program's span recorder (``serving/spans.py``) splits the event loop's
wall time into host work and device waits, as ``sched_stats`` keys:

  loop_self_wall_s        work spans outside the ``vpaas.dispatch`` subtree
  loop_wait_wall_s        ``vpaas.wait.encode_nbytes`` + ``wait.result_fields``
  dispatch_self_wall_s    the dispatch subtree net of ``wait.prop_valid``
  prop_valid_wait_wall_s  ``vpaas.wait.prop_valid``

and ``hot_path_stats["h2d_bytes"]`` counts the host bytes handed to the
device.  The time parts are read over the untraced rest of the measured
window (``window`` less ``trace_window``), which the harness serves for
``seconds - trace_s`` after ``stop_trace`` returns, so that the host is
read as it runs untraced; per finished chunk or per flush.  A program
without the recorder has none of these keys, and every reader here then
returns None.
"""
from __future__ import annotations

from typing import Dict, Optional


def rest(ctx) -> Dict[str, float]:
    """The counters over the measured window less its traced part."""
    w, tw = ctx["window"], ctx["trace_window"]
    return {k: v - tw.get(k, 0) for k, v in w.items()}


def ms_per(ctx, key: str, per: str) -> Optional[float]:
    """Milliseconds of ``key`` per event counted by ``per``, over the
    untraced rest; None without the key or without an event."""
    r = rest(ctx)
    if key not in r or not r.get(per):
        return None
    return 1e3 * r[key] / r[per]


def h2d_bytes_per_frame(ctx) -> Optional[float]:
    """Host bytes handed to the device per real detected frame, over the
    whole window (a byte count, which tracing does not move)."""
    w = ctx["window"]
    if "hot.h2d_bytes" not in w or not w.get("detect.frames"):
        return None
    return w["hot.h2d_bytes"] / w["detect.frames"]
