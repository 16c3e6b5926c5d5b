"""One benchmark run of one cell: set-up, warm-up, the measured window,
the trace, and the check against the reference.

Everything a cell is made of comes from files named in ``BENCHMARK.json``:
its configuration (``bench/configs/<config>.json``, which names its model
family, ``bench/models/<models>.py``: weights, calibration, forwards and
costs), its traffic mix (``bench/traffic/<traffic>.json``, read by the one
generator here) and its per-layer metrics (``bench/metrics/<metric>.py``,
each a ``read(ctx)``).

Cameras hand the scheduler a fresh chunk object for every submission (the
content cycles through a pool of chunks that is the same for every seed:
the seed deals it out to the cameras, so that every seed serves the same
work in another order).  The scheduler's own clock is simulated, so every
time here is the host's wall clock, taken from the camera's side: a
chunk's time ends when the fields its operator receives -- boxes, labels,
valid, source -- are on the host.  The harness
hooks the scheduler's finalize event (the ``plane.on_chunk`` hook that the
learning plane uses) to touch them there.  The modelled WAN time is never
slept: there is no WAN in a run.

Two ways to send traffic (``mode`` in the traffic file):

  backlog  every camera always has its next chunk waiting: the hook hands
           the scheduler the camera's next chunk as the last one finishes.
  live     open loop: camera i's k-th chunk is due when its last frame is
           captured, phase_i + k * frames / fps after the window opens; the
           generator submits whatever is due, runs the scheduler until it
           is idle, and sleeps to the next due time.  A chunk's latency runs
           from its due time, so a late generator is charged to the system.
"""
from __future__ import annotations

import gc
import importlib.util
import json
import os
import shutil
import statistics
import sys
import time
from contextlib import nullcontext
from typing import Dict, List

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
RESULT_FIELDS = ("boxes", "labels", "valid", "source")
CHECK_FIELDS = RESULT_FIELDS + ("prop_boxes", "prop_valid", "fog_scores",
                                 "fog_features")
# read with the check's fields where a result has it: each slot's region
# identity, by which the check matches served regions to the reference's
# (bench/check.py); never part of a chunk's timed download
REGION_IDS = "region_ids"


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


# ---------------------------------------------------------------------------
# The cell, from the files BENCHMARK.json names
# ---------------------------------------------------------------------------
def load_cell(workload: str, root: str = ROOT) -> Dict:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r}; known: {sorted(cells)}")
    cell = cells[workload]
    cfg_entry = next(c for c in bench["configs"] if c["name"] == cell["config"])
    with open(os.path.join(root, cfg_entry["file"])) as f:
        config = json.load(f)
    with open(os.path.join(root, "bench", "traffic",
                           cell["traffic"] + ".json")) as f:
        traffic = json.load(f)
    e2e = [m for m in bench["end_to_end"]
           if workload in m.get("workloads", [workload])]
    e2e_names = {m["name"] for m in e2e}
    per_layer = [m for m in bench["per_layer"]
                 if (workload in m["workloads"] if "workloads" in m
                     else m["moves"] in e2e_names)]
    return {"name": workload, "chips": cell["chips"], "config": config,
            "traffic": traffic, "end_to_end": e2e, "per_layer": per_layer,
            "root": root}


def metric_reader(name: str, root: str = ROOT):
    path = os.path.join(root, "bench", "metrics", name + ".py")
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


# ---------------------------------------------------------------------------
# The system under test
# ---------------------------------------------------------------------------
class CompileCount:
    """Programs JAX compiles or loads from its cache while installed."""

    def __init__(self):
        self.count = 0
        self.seconds = 0.0
        self.last = time.perf_counter()

    def __call__(self, event: str, duration: float, **_) -> None:
        if event == BACKEND_COMPILE_EVENT:
            self.count += 1
            self.seconds += duration
            self.last = time.perf_counter()


def _variant(chunk, d: int, cls):
    """Chunk under flip x (bit 0), flip y (bit 1), transpose (bit 2; a
    square frame only)."""
    f, b = chunk.frames, chunk.gt_boxes.copy()
    if d & 1:
        f = f[:, :, ::-1]
        b[..., [0, 2]] = 1.0 - b[..., [2, 0]]
    if d & 2:
        f = f[:, ::-1]
        b[..., [1, 3]] = 1.0 - b[..., [3, 1]]
    if d & 4:
        f = f.transpose(0, 2, 1, 3)
        b = b[..., [1, 0, 3, 2]]
    return cls(np.ascontiguousarray(f), b, chunk.gt_labels, chunk.content)


def _tuple(v):
    return tuple(_tuple(x) for x in v) if isinstance(v, list) else v


def _tuples(d: dict) -> dict:
    """``d`` with every list, nested ones too, a tuple: hashable fields of
    a frozen config."""
    return {k: _tuple(v) for k, v in d.items()}


def build_system(config: dict, det_params, clf_params):
    """The scheduler and the host readout every camera starts from."""
    from repro.configs.vpaas_video import ClassifierConfig, DetectorConfig
    from repro.core.protocol import HighLowProtocol, ProtocolConfig
    from repro.serving.batching import CrossStreamBatcher
    from repro.serving.graph import GraphScheduler, VideoFunctionGraph

    sv = config["serving"]
    proto = HighLowProtocol(DetectorConfig(**_tuples(config["detector"])),
                            ClassifierConfig(**_tuples(config["classifier"])),
                            ProtocolConfig(**config["protocol"]))
    sched = GraphScheduler(
        VideoFunctionGraph(proto, det_params, clf_params),
        batcher=CrossStreamBatcher(max_chunks=sv["max_batch_chunks"],
                                   window=sv["batch_window_s"]),
        cloud_replicas=sv["cloud_replicas"], hot_path=sv["hot_path"],
        deadline_batching=sv["deadline_batching"],
        crop_buckets=tuple(sv["crop_buckets"]))
    return sched, np.asarray(clf_params["W"])


def counters(sched) -> Dict[str, float]:
    """The scheduler's own counters."""
    return {f"{prefix}.{k}": v
            for prefix, d in (("sched", sched.sched_stats),
                              ("detect", sched.detect_stats),
                              ("hot", sched.hot_path_stats))
            for k, v in d.items()}


def delta(a: Dict[str, float], b: Dict[str, float]) -> Dict[str, float]:
    return {k: b[k] - a[k] for k in b}


# ---------------------------------------------------------------------------
# Cameras and the finalize hook
# ---------------------------------------------------------------------------
class Cameras:
    """The traffic mix: per content group of cameras a pool of chunks,
    cycled per camera from a seeded offset, a fresh chunk object per
    submission.

    The pool entries are the same for every seed: ``scenes`` base scenes
    made from the traffic's ``scenes_seed``, each under its variants (the
    eight flips and transposes of a square frame, the four flips of any
    other, so that every frame keeps the shape ``hw``); entry k is scene
    ``k // variants`` under variant ``k % variants``.  The run's seed only
    deals the entries out to the groups and sets each camera's offset into
    its pool, so every seed serves the same chunks in another order."""

    def __init__(self, traffic: dict, seed: int):
        from bench.scenes import Chunk, make_chunk
        self._chunk = Chunk
        rng = np.random.default_rng(traffic["scenes_seed"])
        n, group = traffic["cameras"], traffic["content_group"]
        per_group, n_scenes = traffic["pool_chunks_per_group"], traffic["scenes"]
        groups = -(-n // group)
        hw = tuple(traffic["hw"])
        variants = 8 if hw[0] == hw[1] else 4
        if groups * per_group > variants * n_scenes:
            raise ValueError(f"more pool entries than scenes x {variants} "
                             f"variants")
        base = [make_chunk(rng, traffic["content"], num_frames=traffic["frames"],
                           hw=hw) for _ in range(n_scenes)]
        self.entries = [_variant(base[(k // variants) % n_scenes],
                                 k % variants, Chunk)
                        for k in range(groups * per_group)]
        self.per_group = per_group
        deal = np.random.default_rng(seed)
        order = deal.permutation(len(self.entries))
        self.pools = [[self.entries[i] for i in order[g * per_group:
                                                      (g + 1) * per_group]]
                      for g in range(groups)]
        self.pool_of = [self.pools[i // group] for i in range(n)]
        self.pos = [int(deal.integers(per_group)) for _ in range(n)]
        self.n = n
        self.made = 0

    def calibration_frames(self, count: int) -> List[np.ndarray]:
        """HQ frames of the first entry of each of the first ``count``
        groups as they stand before the seed deals them out: the same
        chunks for every seed."""
        return [self.entries[g * self.per_group].frames for g in range(count)]

    def next(self, cam: int):
        pool = self.pool_of[cam]
        src = pool[self.pos[cam] % len(pool)]
        self.pos[cam] += 1
        chunk = self._chunk(src.frames, src.gt_boxes, src.gt_labels,
                            src.content)
        chunk.bench_id = self.made
        chunk.bench_cam = cam
        self.made += 1
        return chunk


class Run:
    """One cell's run: the scheduler, its cameras and what they saw."""

    def __init__(self, cell: dict, seed: int, traced: bool = False):
        import jax
        from bench.models import family
        self.cell, self.seed = cell, seed
        cfg, tr = cell["config"], cell["traffic"]
        self.traffic, self.config = tr, cfg
        self.cams = Cameras(tr, seed)
        # the deployment's one model: the same weights for every seed
        fam = family(cfg)
        det, self.clf_params = fam.make_weights(cfg, cfg["weights"]["seed"])
        self.det_params = fam.calibrate(
            cfg, det, self.cams.calibration_frames(cfg["weights"]["chunks"]))
        jax.block_until_ready((self.det_params, self.clf_params))
        self.sched, W = build_system(cfg, self.det_params, self.clf_params)
        self.sched.plane = self            # the finalize hook
        self.streams = [self.sched.add_stream(
            f"cam{i:04d}", W=W, slo=cfg["serving"]["slo_s"])
            for i in range(self.cams.n)]
        self.backlog = tr["mode"] == "backlog"
        self.traced = traced
        self.finished: List[tuple] = []    # (finish wall, chunk, result)
        self.due: Dict[int, float] = {}   # bench_id -> due wall time

    def span(self, name: str):
        if not self.traced:
            return nullcontext()
        import jax
        return jax.profiler.TraceAnnotation("bench." + name)

    # -- the scheduler's plane hook --------------------------------------
    def on_chunk(self, scheduler, stream, chunk, res, t, mode) -> None:
        with self.span("result_download"):
            for f in RESULT_FIELDS:
                getattr(res, f)
        self.finished.append((time.perf_counter(), chunk, res))
        if self.backlog and self.feeding:
            self.submit(chunk.bench_cam)

    def submit(self, cam: int) -> None:
        with self.span("submit"):
            chunk = self.cams.next(cam)
            self.sched.submit(self.streams[cam], chunk, learn=True)

    def step(self) -> bool:
        with self.span("step"):
            return self.sched.step()

    # -- traffic --------------------------------------------------------
    def drive(self, until: float, stop_when=None) -> None:
        """Serve traffic until the wall clock passes ``until`` (or
        ``stop_when()`` holds)."""
        if self.backlog:
            while time.perf_counter() < until:
                if not self.step():
                    raise RuntimeError("backlog cell ran out of work")
                if stop_when is not None and stop_when():
                    return
            return
        tr = self.traffic
        period = tr["frames"] / tr["fps"]
        while True:
            now = time.perf_counter()
            if now >= until or (stop_when is not None and stop_when()):
                return
            if self.next_due > now:
                with self.span("sleep"):
                    time.sleep(min(self.next_due, until) - now)
                continue
            self.submit_due(now, period)
            while self.step():
                pass

    def start_live(self, t0: float) -> None:
        """Each camera's first due time: the cameras' phases are spread
        evenly over a period and the seed deals them out, so that every
        seed sends the same arrivals in another order."""
        tr = self.traffic
        period = tr["frames"] / tr["fps"]
        n = self.cams.n
        rng = np.random.default_rng(self.seed + 1)
        phases = period * (rng.permutation(n) + 0.5) / n
        self.live_due = [t0 + float(p) for p in phases]
        self.next_due = min(self.live_due)

    def submit_due(self, now: float, period: float) -> None:
        for cam, due in enumerate(self.live_due):
            while due <= now:
                chunk_id = self.cams.made
                self.submit(cam)
                self.due[chunk_id] = due
                self.lateness.append(now - due)
                due += period
            self.live_due[cam] = due
        self.next_due = min(self.live_due)


# ---------------------------------------------------------------------------
# One run
# ---------------------------------------------------------------------------
def trace_dir_for(cell: dict, seed: int) -> str:
    """Where a traced run writes its trace, deleted once reduced."""
    return os.path.join(cell["root"], "bench", "out", "trace",
                        f"{cell['name']}-{seed}")


def run(cell: dict, seed: int, seconds: float, trace: bool, t_start: float,
        controls=()) -> Dict:
    """Set up, warm up, measure, check.  Returns the result line's dict
    (and, for ``controls``, the control readings under ``controls``)."""
    import jax

    from bench import check as chk
    from bench import reference as ref
    from bench import tracing

    tr = cell["traffic"]
    clock = CompileCount()
    jax.monitoring.register_event_duration_secs_listener(clock)
    try:
        r = Run(cell, seed, traced=trace)
        # warm-up: the cell's own traffic until no program has compiled
        # for warmup_quiet_s (every shape the window will use is then
        # built) and, backlogged, every camera has had a chunk served: the
        # first pass dispatches all the cameras' first chunks before any
        # finishes, with flushes of other sizes than the steady state's.
        # Its chunks are not counted
        w0 = time.perf_counter()
        if r.backlog:
            r.feeding = True
            for cam in range(r.cams.n):
                r.submit(cam)
        else:
            r.feeding = False
            r.lateness = []
            r.start_live(w0)
        quiet, cap = tr["warmup_quiet_s"], tr["warmup_max_s"]
        served = r.cams.n if r.backlog else 0
        r.drive(w0 + cap, stop_when=lambda: (
            time.perf_counter() - max(clock.last, w0) >= quiet
            and len(r.finished) >= served))
        if not r.backlog:
            while r.step():
                pass
        sync = jax.jit(lambda: jax.numpy.zeros(()))
        sync().block_until_ready()       # the traced window's device sync
        warm_compiles = clock.count
        setup_s = time.perf_counter() - t_start
        n_warm = len(r.finished)

        # the measured window
        win0 = time.perf_counter()
        c0 = counters(r.sched)
        if not r.backlog:
            r.lateness, r.due = [], {}
            r.start_live(win0)
        compiles0 = clock.count
        tc = None
        win_end = win0 + seconds
        if trace:
            tdir = trace_dir_for(cell, seed)
            shutil.rmtree(tdir, ignore_errors=True)
            t_span = min(tr["trace_s"], seconds)
            # host spans are TraceMe events, which the host tracer keeps;
            # the Python tracer's events would only slow the host
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            sync().block_until_ready()
            tc0, tt0 = counters(r.sched), time.perf_counter()
            jax.profiler.start_trace(tdir, profiler_options=opts)
            r.drive(tt0 + t_span)
            sync().block_until_ready()
            # the traced window ends here: stop_trace spends seconds
            # writing the trace, with no device work to record
            tc = (delta(tc0, counters(r.sched)),
                  time.perf_counter() - tt0)
            t_stop = time.perf_counter()
            jax.profiler.stop_trace()
            # the untraced rest: as much serving again as the window holds
            # beyond the traced part, whatever stop_trace took
            rest0 = time.perf_counter()
            stop_s = rest0 - t_stop
            win_end = rest0 + seconds - t_span
        r.drive(win_end)
        win1 = time.perf_counter()
        c1 = counters(r.sched)
        in_window = clock.count - compiles0
        # close, live: every chunk due in the window finishes, late or not.
        # A backlog cell's answers are the chunks finished in the window;
        # what is still queued is dropped with the scheduler
        r.feeding = False
        if not r.backlog:
            r.submit_due(win_end, tr["frames"] / tr["fps"])
            while r.step():
                pass
        dev = jax.devices()[0]
        mem = (dev.memory_stats() or {}).get("peak_bytes_in_use")
    finally:
        jax.monitoring.unregister_event_duration_listener(clock)

    done = [(t, c, res) for t, c, res in r.finished[n_warm:]]
    win_done = [(t, c, res) for t, c, res in done if t <= win1]
    window_s = win1 - win0
    frames_done = sum(c.frames.shape[0] for _, c, _ in win_done)
    if trace:
        rest_s = win1 - rest0
        # coordinates travel back at 9 bytes per uncertain region
        rest_valid_crops = sum(int(round(res.coord_bytes / 9.0))
                               for t, _, res in win_done if t >= rest0)
    e2e: Dict[str, float] = {"setup_s": setup_s}
    attempted = failed = 0
    if r.backlog:
        e2e["frames_per_s"] = frames_done / window_s
        attempted = len(done)
    else:
        lat = sorted(t - r.due[c.bench_id] for t, c, _ in done
                     if c.bench_id in r.due)
        finished_ids = {c.bench_id for _, c, _ in done}
        attempted = len(r.due)
        failed = sum(1 for i in r.due if i not in finished_ids)
        lat += [float("inf")] * failed
        q = statistics.quantiles(lat, n=100, method="inclusive")
        e2e["chunk_p50_ms"] = 1e3 * statistics.median(lat)
        e2e["chunk_p95_ms"] = 1e3 * q[94]
        e2e["frames_per_s"] = frames_done / window_s
        late = sorted(r.lateness) or [0.0]
        log(f"[generator] {len(late)} chunks due in the window, lateness "
            f"p50 {1e3 * late[len(late) // 2]:.3f} ms, max "
            f"{1e3 * late[-1]:.3f} ms; {len(lat)} latencies, p50 "
            f"{e2e['chunk_p50_ms']:.3f} ms, p95 {e2e['chunk_p95_ms']:.3f} ms")
    win = delta(c0, c1)
    log(f"[window] {window_s:.3f} s, {len(win_done)} chunks / {frames_done} "
        f"frames finished, {int(win['hot.flushes'])} flushes, "
        f"{in_window} programs compiled or loaded in the window "
        f"({warm_compiles} in set-up, {clock.seconds:.1f} s in all), "
        f"peak_bytes_in_use {mem}")
    # where the window's time went: chunks finished in each quarter, and
    # the longest waits between two finishes (a host stall shows as one)
    ends = [win0] + sorted(t for t, _, _ in win_done) + [win1]
    gaps = sorted(np.diff(ends))[::-1]
    quarters = np.histogram(ends[1:-1], bins=4, range=(win0, win1))[0]
    log(f"[window] chunks a quarter {quarters.tolist()}, longest gaps "
        f"{[round(1e3 * float(g), 1) for g in gaps[:5]]} ms, median gap "
        f"{1e3 * float(np.median(gaps)):.2f} ms, gaps over 50 ms "
        f"{sum(g for g in gaps if g > 0.05):.3f} s")

    # the sample to check: drawn from the seed among chunks finished in the
    # window whose flush still holds its device results
    rng = np.random.default_rng(seed + 2)
    want_n = tr["check_chunks"]
    sample = []
    for i in rng.permutation(len(win_done)):
        _, c, res = win_done[i]
        try:
            got = {f: np.asarray(getattr(res, f)) for f in CHECK_FIELDS}
            ids = getattr(res, REGION_IDS, None)
        except RuntimeError:          # flush sealed past the retention cap
            continue
        if ids is not None:
            got[REGION_IDS] = np.asarray(ids)
        sample.append((c.frames, got))
        if len(sample) == want_n:
            break
    r.sched = r.streams = None
    r.finished = done = win_done = None
    gc.collect()

    t_ref = time.perf_counter()
    W = r.clf_params["W"]
    frames = [f for f, _ in sample]
    limits = chk.limits(r.config)
    check = chk.Check()
    chk.hold(check, r.config, r.det_params, r.clf_params, W, frames,
             [g for _, g in sample])
    correct = check.finish(limits)
    log(f"[check] {check.summary()} ({time.perf_counter() - t_ref:.1f} s)")
    out_controls = {}
    for prec in controls:
        # the reference in the program's place, at a lower precision
        cc = chk.Check()
        chk.hold(cc, r.config, r.det_params, r.clf_params, W, frames,
                 ref.serve(r.config, r.det_params, r.clf_params, W, frames,
                           precision=prec))
        cc.finish(limits)
        out_controls[prec] = cc.numbers(limits)
        log(f"[control {prec}] {cc.summary()}; " + ", ".join(
            f"{k} {v[0]:.6g}" for k, v in cc.numbers(limits).items()))

    kind = jax.devices()[0].device_kind
    result = {
        "correct": bool(correct), "attempted": int(attempted),
        "failed": int(failed),
        "metrics": {}, "device": {
            "platform": jax.devices()[0].platform, "kind": kind,
            "count": len(jax.devices()), "memory_peak_bytes": mem},
    }
    if not trace:
        for m in cell["end_to_end"]:
            result["metrics"][m["name"]] = {"value": e2e[m["name"]],
                                           "unit": m["unit"]}
    else:
        path = tracing.latest_xplane(tdir)
        red = tracing.reduce(path)
        shutil.rmtree(tdir, ignore_errors=True)
        log(f"[trace] traced {tc[1]:.3f} s, stop_trace {stop_s:.3f} s, "
            f"untraced rest {rest_s:.3f} s")
        ctx = {"config": r.config, "traffic": tr, "device_kind": kind,
               "window": win, "window_s": window_s, "trace": red,
               "trace_window": tc[0], "trace_s": tc[1], "rest_s": rest_s,
               "rest_valid_crops": rest_valid_crops}
        for m in cell["per_layer"]:
            v = metric_reader(m["name"], cell["root"])(ctx)
            if v is not None:
                result["metrics"][m["name"]] = {"value": v,
                                               "unit": m["unit"]}
        result["device"]["busy_s"] = red["busy_s"]
        result["device"]["window_s"] = tc[1]
        result["breakdown"] = {"device_ops": red["device_ops"],
                               "idle_gaps": red["idle_gaps"]}
        log("[trace] modules " + ", ".join(
            f"{k} x{v['count']} {v['seconds']:.6f} s"
            for k, v in sorted(red["modules"].items(),
                               key=lambda kv: -kv[1]["seconds"])[:12]))
    result["check"] = check.numbers(limits)
    for msg in check.failures:
        log(f"[check] FAIL {msg}")
    if controls:
        result["controls"] = out_controls
    return result
