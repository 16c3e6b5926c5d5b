"""Host spans of the serving event loop.

A span is a ``jax.profiler.TraceAnnotation`` -- while a profile is being
captured it lies on the host plane, on the same clock as the device's XLA
ops -- and an aggregate kept always: per name the count ``n``, the total
seconds ``s`` and the self seconds ``self_s`` (the total less the part its
child spans cover).  Spans nest through a stack on the recorder.

Spans named ``vpaas.wait.*`` are leaves that block on a device-to-host
read; every other span is host work.  Inside the event loop (under a
``vpaas.step`` root) each span's self time also lands in one of four parts
that together make up the loop's wall time:

  loop_self_wall_s        work outside the ``vpaas.dispatch`` subtree
  loop_wait_wall_s        waits outside that subtree
  dispatch_self_wall_s    the dispatch subtree, net of the prop_valid wait
  prop_valid_wait_wall_s  ``vpaas.wait.prop_valid`` inside that subtree

so that, over any interval between steps, the first two add up to the
``vpaas.step`` total less the ``vpaas.dispatch`` total, and the last two
to the ``vpaas.dispatch`` total.  Times are kept in integer nanoseconds
and added to the seconds counters as each span ends.
"""
from __future__ import annotations

import time
from typing import Dict, List, Optional, Tuple

from jax.profiler import TraceAnnotation

ROOT = "vpaas.step"
SUBTREE = "vpaas.dispatch"
WAIT_PREFIX = "vpaas.wait."
PROP_VALID_WAIT = "vpaas.wait.prop_valid"
PARTS = ("loop_self_wall_s", "loop_wait_wall_s", "dispatch_self_wall_s",
         "prop_valid_wait_wall_s")

_now = time.perf_counter_ns
_profiling = TraceAnnotation.is_enabled


class _Name:
    """What a span name resolves to once: its aggregate ``[n, total_ns,
    self_ns]``, the part its self time takes outside and inside the
    dispatch subtree, the ``(dict, key)`` its totals feed, if any, and
    which open-span count it raises (1: the loop, 2: the subtree)."""
    __slots__ = ("agg", "outside", "inside", "feed", "opens")

    def __init__(self, name: str, feed: Optional[Tuple[dict, str]]):
        self.agg = [0, 0, 0]
        self.outside = PARTS[1 if name.startswith(WAIT_PREFIX) else 0]
        self.inside = PARTS[3 if name == PROP_VALID_WAIT else 2]
        self.feed = feed
        self.opens = 1 if name == ROOT else 2 if name == SUBTREE else 0


class _Span:
    __slots__ = ("rec", "info", "ann", "t0", "child")

    def __init__(self, rec: "SpanRecorder", info: _Name, ann):
        self.rec, self.info, self.ann = rec, info, ann
        self.child = 0

    def __enter__(self):
        if self.ann is not None:
            self.ann.__enter__()
        rec = self.rec
        rec._stack.append(self)
        if self.info.opens:
            rec._open[self.info.opens] += 1
        self.t0 = _now()
        return self

    def __exit__(self, *exc):
        total = _now() - self.t0
        rec, info = self.rec, self.info
        stack = rec._stack
        stack.pop()
        if stack:
            stack[-1].child += total
        own = total - self.child
        agg = info.agg
        agg[0] += 1
        agg[1] += total
        agg[2] += own
        if info.feed is not None:
            info.feed[0][info.feed[1]] += total * 1e-9
        opened = rec._open
        if opened[1]:
            rec.parts[info.inside if opened[2] else info.outside] += own * 1e-9
        if info.opens:
            opened[info.opens] -= 1
        if self.ann is not None:
            self.ann.__exit__(*exc)
        return False


class SpanRecorder:
    """The span recorder one scheduler owns.

    ``parts`` is the dict the four loop parts accumulate in (the
    scheduler's ``sched_stats``); ``feeds`` maps a span name to the
    ``(dict, key)`` its totals add to, in seconds."""

    def __init__(self, parts: dict,
                 feeds: Optional[Dict[str, Tuple[dict, str]]] = None):
        self.parts = parts
        for k in PARTS:
            parts.setdefault(k, 0.0)
        self._feeds = dict(feeds or {})
        self._names: Dict[str, _Name] = {}
        self._stack: List[_Span] = []
        self._open = [0, 0, 0]        # -, open roots, open subtrees

    def span(self, name: str, **ids) -> _Span:
        """A context manager timing ``name``; ``ids`` go on the profiler
        annotation only, which is made only while a profile is captured."""
        info = self._names.get(name)
        if info is None:
            info = self._names[name] = _Name(name, self._feeds.get(name))
        ann = TraceAnnotation(name, **ids) if _profiling() else None
        return _Span(self, info, ann)

    @property
    def stats(self) -> Dict[str, Dict[str, float]]:
        """Per span name: ``n``, ``s`` (total seconds), ``self_s``."""
        out = {}
        for name, info in self._names.items():
            n, total, own = info.agg
            out[name] = {"n": n, "s": total * 1e-9, "self_s": own * 1e-9}
        return out
