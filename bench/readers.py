"""Per-layer readers shared by the metric files in ``bench/metrics``.

Each reader takes the run's context and returns a number, or None when the
run has nothing to read (it never returns 0 for a share of a roofline or a
peak).  The context holds:

  window        the scheduler's counters over the measured window
                (``sched.*``: step_wall_s, model_wall_s, finalizes;
                ``detect.*``: calls, frames, padded_frames; ``hot.*``:
                flushes, crops_classified, ...)
  window_s      the window's wall seconds
  trace         the reduced device trace (``bench.tracing.reduce``)
  trace_window  the counters over the traced part of the window
  trace_s       the traced part's wall seconds
  rest_s        the wall seconds of the untraced rest, served after
                ``stop_trace`` returned (its counters are ``window`` less
                ``trace_window``: ``bench.span_readers.rest``)
  rest_valid_crops  uncertain regions of the chunks finished in the rest
  config, traffic, device_kind

The operations and bytes of a kernel come from the configuration's model
family (``bench.models.family``).
"""
from __future__ import annotations

from typing import Optional, Sequence

from bench import roofline
from bench.models import family
from bench.span_readers import rest


def sched_host_ms_per_chunk(ctx) -> Optional[float]:
    """Event-loop host time net of flush dispatch, per finished chunk."""
    w = ctx["window"]
    if not w["sched.finalizes"]:
        return None
    return 1e3 * (w["sched.step_wall_s"] - w["sched.model_wall_s"]) \
        / w["sched.finalizes"]


def frames_per_flush(ctx) -> Optional[float]:
    w = ctx["window"]
    return w["detect.frames"] / w["detect.calls"] if w["detect.calls"] \
        else None


def dispatch_host_ms_per_flush(ctx) -> Optional[float]:
    """Flush assembly, detect dispatch, the prop_valid wait and the
    classify dispatch (``_dispatch``), host wall per flush."""
    w = ctx["window"]
    return 1e3 * w["sched.model_wall_s"] / w["hot.flushes"] \
        if w["hot.flushes"] else None


def module_ms(ctx, modules: Sequence[str]) -> Optional[float]:
    """Device milliseconds per execution of the named jitted programs."""
    mods = ctx["trace"]["modules"]
    n = sum(mods[m]["count"] for m in modules if m in mods)
    s = sum(mods[m]["seconds"] for m in modules if m in mods)
    return 1e3 * s / n if n else None


def device_idle_pct(ctx) -> Optional[float]:
    t = ctx["trace"]
    if not t["devices"] or ctx["trace_s"] <= 0:
        return None
    return 100.0 * (1.0 - t["busy_s"] / ctx["trace_s"])


def roofline_pct(ctx, modules: Sequence[str], flops: float,
                 nbytes: float) -> Optional[float]:
    """Least time of the traced calls' work over their device time."""
    mods = ctx["trace"]["modules"]
    s = sum(mods[m]["seconds"] for m in modules if m in mods)
    if s <= 0 or flops <= 0:
        return None
    least, _ = roofline.least_time(flops, nbytes, ctx["device_kind"])
    return 100.0 * least / s


def detect_split_roofline(ctx, modules) -> Optional[float]:
    w, det = ctx["trace_window"], ctx["config"]["detector"]
    flops, nbytes = family(ctx["config"]).detect_split_cost(
        det, frames=w["detect.frames"] + w["detect.padded_frames"],
        calls=w["detect.calls"])
    return roofline_pct(ctx, modules, flops, nbytes)


def classify_roofline(ctx, modules) -> Optional[float]:
    w, cfg = ctx["trace_window"], ctx["config"]
    flops, nbytes = family(cfg).classify_cost(
        cfg["classifier"], cfg["detector"], rows=w["hot.crops_classified"],
        frames=w["detect.frames"], calls=w["hot.flushes"])
    return roofline_pct(ctx, modules, flops, nbytes)


def step_mfu(ctx) -> Optional[float]:
    """Useful model operations per second -- detector over every real
    frame, classifier over every uncertain region -- over the bf16 peak,
    in the untraced rest of the window, where the host runs as it does
    untraced."""
    cfg, fam = ctx["config"], family(ctx["config"])
    flops = (fam.detector_flops_per_frame(cfg["detector"])
             * rest(ctx)["detect.frames"]
             + fam.classifier_flops_per_crop(cfg["classifier"])
             * ctx["rest_valid_crops"])
    if flops <= 0 or ctx["rest_s"] <= 0:
        return None
    peak = roofline.peaks(ctx["device_kind"])["bf16_flops"]
    return 100.0 * flops / ctx["rest_s"] / peak
