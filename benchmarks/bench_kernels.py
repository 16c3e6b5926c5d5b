"""Kernel micro-benchmarks: jnp-oracle wall time on CPU (the interpret-mode
Pallas path validates correctness, not speed — noted in derived fields).

Includes the video serving hot-path stages (fused detect->split, compacted
bucketed classify) so kernel-level and e2e throughput numbers
(``bench_e2e_throughput``) can be correlated."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import ref

from benchmarks.common import BenchContext, timeit

KEY = jax.random.PRNGKey(0)


def _video_stage_rows(quick: bool):
    """Fused vs unfused cloud stage + compacted vs full fog classify."""
    from repro.configs.vpaas_video import ClassifierConfig, DetectorConfig
    from repro.core import protocol as pm
    from repro.core import regions as reg
    from repro.models import classifier as clf_mod
    from repro.models import detector as det_mod

    det_cfg = DetectorConfig(name="bench-k-det", image_hw=(32, 32),
                             widths=(8, 16))
    clf_cfg = ClassifierConfig(name="bench-k-clf", crop_hw=(16, 16),
                               widths=(8, 16), feature_dim=16)
    pcfg = pm.ProtocolConfig()
    det_params = det_mod.init_detector(det_cfg, jax.random.PRNGKey(0))
    clf_params = clf_mod.init_classifier(clf_cfg, jax.random.PRNGKey(1))
    W = jnp.asarray(clf_params["W"])
    rows = []
    f = 8 if quick else 16
    frames = jax.random.uniform(jax.random.PRNGKey(2), (f, 32, 32, 3))

    # cloud stage: detect + per-chunk split (2 dispatches + sliced splits,
    # the sync path) vs the fused single-dispatch detect_split
    def unfused():
        det = pm.detect_regions(det_cfg, det_params, frames)
        outs = [pm.split_uncertain(pcfg, {k: v[i:i + 2]
                                          for k, v in det.items()})
                for i in range(0, f, 2)]
        jax.block_until_ready([s.prop_valid for s, _ in outs])

    def fused():
        split = pm.detect_split(det_cfg, pcfg, det_params, frames)
        np.asarray(split.prop_valid)

    unfused(), fused()                      # warm both jit caches
    us_u = timeit(unfused)
    us_f = timeit(fused)
    rows.append({"name": f"detect_split_fused/f{f}",
                 "us_per_call": f"{us_f:.0f}",
                 "unfused_us": f"{us_u:.0f}",
                 "fusion_speedup": f"{us_u / max(us_f, 1e-9):.2f}",
                 "note": "1 dispatch + 1 host read vs 1+chunks dispatches"})

    # fog stage: full-budget F x N classify vs compacted bucketed gather
    split = pm.detect_split(det_cfg, pcfg, det_params, frames)
    pv = np.asarray(split.prop_valid)
    fidx, ridx, n_valid, bucket = reg.compaction_indices(pv)
    idxs = np.zeros((3, bucket), np.int32)
    idxs[0], idxs[1] = fidx, ridx
    idxs_d = jnp.asarray(idxs)

    def full():
        m = pm.classify_regions(clf_cfg, pcfg, clf_params, W, frames, split)
        np.asarray(m["fog_scores"])

    def compacted():
        m = pm.classify_compacted(clf_cfg, pcfg, clf_params, W[None],
                                  frames, split, idxs_d)
        np.asarray(m["fog_scores"])

    full(), compacted()
    us_full = timeit(full)
    us_comp = timeit(compacted)
    rows.append({"name": f"classify_compacted/f{f}n{pv.shape[1]}",
                 "us_per_call": f"{us_comp:.0f}",
                 "full_budget_us": f"{us_full:.0f}",
                 "compaction_speedup": f"{us_full / max(us_comp, 1e-9):.2f}",
                 "valid_frac": f"{n_valid / pv.size:.2f}",
                 "crops": f"{bucket}/{pv.size}"})

    # crop stage in isolation: full-grid materialize-then-gather (the
    # structure the kernel replaces: the F x N crop grid committed as a
    # device intermediate, then indexed) vs the served crop_gather program
    # that only ever touches the B bucket rows (the Pallas kernel on a TPU,
    # the oracle elsewhere: the row's "impl" names which ran).  The scaling
    # claim is measured,
    # not asserted: B is held at one bucket while F x N grows 8x, so the
    # grid time climbs and the kernel time does not.  (The baseline is two
    # dispatches on purpose — in a single jitted program XLA *may* elide
    # the un-gathered rows on CPU; the kernel makes that structural and
    # backend-independent.)
    import functools
    from repro.kernels import ops

    out_hw = clf_cfg.crop_hw
    n_prop = pv.shape[1]

    _materialize = jax.jit(functools.partial(reg.crop_batch, out_hw=out_hw))
    _take = jax.jit(lambda crops, idxs: crops[idxs[0], idxs[1]])

    rng = np.random.default_rng(3)
    for f_s in ([8, 64] if quick else [8, 32, 64]):
        k1, k2 = jax.random.split(jax.random.PRNGKey(100 + f_s))
        frames_s = jax.random.uniform(k1, (f_s, 32, 32, 3))
        pts = jax.random.uniform(k2, (f_s, n_prop, 2, 2))
        boxes_s = jnp.concatenate([jnp.min(pts, 2), jnp.max(pts, 2)], -1)
        pv_s = np.zeros((f_s, n_prop), bool)
        picks = rng.choice(pv_s.size, size=16, replace=False)
        pv_s.ravel()[picks] = True
        fidx_s, ridx_s, _, b_s = reg.compaction_indices(pv_s)
        idxs_s = np.zeros((3, b_s), np.int32)
        idxs_s[0], idxs_s[1] = fidx_s, ridx_s
        idxs_s = jnp.asarray(idxs_s)

        def grid():
            crops = _materialize(frames_s, boxes_s)
            np.asarray(_take(crops, idxs_s))

        def gathered():
            np.asarray(ops.crop_gather(frames_s, boxes_s, idxs_s,
                                       out_hw=out_hw, impl="ref"))

        grid(), gathered()
        us_grid = timeit(grid)
        us_gath = timeit(gathered)
        rows.append({"name": f"crop_gather/B{b_s}_grid{pv_s.size}",
                     "us_per_call": f"{us_gath:.0f}",
                     "full_grid_us": f"{us_grid:.0f}",
                     "crop_speedup": f"{us_grid / max(us_gath, 1e-9):.2f}",
                     "crops": f"{b_s}/{pv_s.size}",
                     "impl": ops.crop_gather_impl("ref", frames_s.shape),
                     "note": "cost scales with B, not F x N"})
    return rows


def run(ctx: BenchContext, quick: bool = False):
    rows = []
    shapes = [(1, 512, 8, 2, 64)] if quick else [
        (1, 512, 8, 2, 64), (2, 1024, 8, 8, 128)]
    for (b, s, nq, nkv, d) in shapes:
        ks = jax.random.split(KEY, 3)
        q = jax.random.normal(ks[0], (b, s, nq, d), jnp.bfloat16)
        k = jax.random.normal(ks[1], (b, s, nkv, d), jnp.bfloat16)
        v = jax.random.normal(ks[2], (b, s, nkv, d), jnp.bfloat16)
        fn = jax.jit(lambda q, k, v: ref.flash_attention(q, k, v))
        fn(q, k, v).block_until_ready()
        us = timeit(lambda: fn(q, k, v).block_until_ready())
        flops = 4 * b * s * s * nq * d / 2
        rows.append({"name": f"flash_ref/b{b}s{s}h{nq}d{d}",
                     "us_per_call": f"{us:.0f}",
                     "gflops_s": f"{flops / us / 1e3:.1f}",
                     "note": "jnp oracle; pallas targets TPU"})

    # decode attention
    b, S, nq, nkv, d = 4, 4096, 8, 2, 128
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (b, nq, d), jnp.bfloat16)
    kc = jax.random.normal(ks[1], (b, S, nkv, d), jnp.bfloat16)
    vc = jax.random.normal(ks[2], (b, S, nkv, d), jnp.bfloat16)
    fn = jax.jit(lambda q, kc, vc: ref.decode_attention(
        q, kc, vc, jnp.asarray(S, jnp.int32)))
    fn(q, kc, vc).block_until_ready()
    us = timeit(lambda: fn(q, kc, vc).block_until_ready())
    cache_gb = 2 * b * S * nkv * d * 2 / 1e9
    rows.append({"name": f"decode_ref/b{b}S{S}", "us_per_call": f"{us:.0f}",
                 "cache_gb_per_step": f"{cache_gb:.3f}"})

    # SSD scan
    b, s, h, p, n = 2, 1024, 8, 64, 64
    ks = jax.random.split(KEY, 5)
    x = jax.random.normal(ks[0], (b, s, h, p), jnp.bfloat16)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, s, h)))
    A = -jnp.exp(jax.random.normal(ks[2], (h,)))
    B = jax.random.normal(ks[3], (b, s, n)) * 0.5
    C = jax.random.normal(ks[4], (b, s, n)) * 0.5
    fn = jax.jit(lambda *a: ref.ssd_scan(*a, chunk=128)[0])
    fn(x, dt, A, B, C).block_until_ready()
    us = timeit(lambda: fn(x, dt, A, B, C).block_until_ready())
    rows.append({"name": f"ssd_ref/b{b}s{s}h{h}", "us_per_call": f"{us:.0f}"})

    # IoU filter
    na, nb = 256, 256
    ka, kb = jax.random.split(KEY)
    pa = jax.random.uniform(ka, (na, 4))
    pb = jax.random.uniform(kb, (nb, 4))
    fn = jax.jit(ref.iou_matrix)
    fn(pa, pb).block_until_ready()
    us = timeit(lambda: fn(pa, pb).block_until_ready())
    rows.append({"name": f"iou_ref/{na}x{nb}", "us_per_call": f"{us:.0f}"})

    rows.extend(_video_stage_rows(quick))
    return rows
