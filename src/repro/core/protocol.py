"""High-and-Low Video Streaming — the paper's §IV protocol, decomposed into
serverless *stage functions*.

One chunk flows client -> fog -> cloud -> fog:

  1. client ships HQ video to the co-located fog (LAN; negligible bytes
     against the WAN budget),
  2. fog re-encodes to LOW quality (r_low, q_low) and ships that to the
     cloud (the only WAN upload — this is the bandwidth win),
  3. the cloud detector returns (a) confident detections, accepted directly
     as labels, and (b) coordinates of uncertain regions (bytes ~ 0),
  4. the fog crops the uncertain regions from its cached HQ frames and
     classifies them with the lightweight one-vs-all pipeline (no extra
     cloud cost — RQ2), dynamic batching included,
  5. crops + predictions are queued for the §V HITL loop.

Each hop is a separately jit'd **stage function**.  The serving layer
(``repro.serving.graph``) dispatches the fused ones as serverless
functions, so tensors stay on the device end to end:

  ``encode_low``          fog quality control (fog.encode_low)
  ``detect_split``        detect + §IV.B split in ONE jit call over the
                          packed cross-stream batch (cloud.detect_split) —
                          per-chunk coord bytes / crop counts come back as
                          arrays, so the scheduler needs one host transfer
                          per flush
  ``classify_compacted``  gathers only the valid proposals of the whole
                          flush into one bucketed crop batch, classifies
                          cross-stream with per-stream readouts, and
                          scatters scores back (fog.classify_batched)

The unfused stages are the **one-chunk reference** that tests, baselines
and ``chip_smoke.py`` compare the served path against:

  ``detect_regions``      the heavy cloud detector
  ``split_uncertain``     §IV.B three-stage filter
  ``classify_regions``    HQ crop of every region slot + one-vs-all merge
  ``classify_ensemble``   the same, scored by an Eq. (9) snapshot ensemble

``HighLowProtocol.process_chunk`` drives them strictly sequentially on one
chunk.  The served path computes the same function: splitting a packed
batch then slicing equals slicing then splitting (per-frame vmap), and the
compacted classifier's crop stage runs the bilinear program of the
full-grid path over just the bucket rows, feeding a backbone whose
per-row outputs do not depend on the batch.  The two are different XLA
programs, so their floats may differ in the last bits (a compiler may
contract multiply-adds differently in each fusion); tests hold them to a
stated tolerance.  Orchestration (bytes, latency, cost accounting) happens
at the stage boundaries.
"""
from __future__ import annotations

import functools
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.vpaas_video import ClassifierConfig, DetectorConfig
from repro.core import regions as reg
from repro.core.bandwidth import (CLOUD, FOG, CostModel, DeviceProfile,
                                  LatencyBreakdown, NetworkModel)
from repro.kernels import ops
from repro.models import classifier as clf_mod
from repro.models import detector as det_mod
from repro.video import codec


@dataclass(frozen=True)
class ProtocolConfig:
    # quality control (paper §VI settings: first-round QP 36, RS 0.8)
    r_low: float = 0.8
    q_low: int = 36
    # §IV.B filter thresholds
    theta_cls: float = 0.85
    theta_loc: float = 0.5
    theta_iou: float = 0.3
    theta_back: float = 0.5
    # fog classifier acceptance
    fog_min_conf: float = 0.5
    # closed-loop inter-frame coding (H.264-faithful temporal compression)
    inter_coding: bool = True
    # kernel implementation ("ref" / "pallas" / "interpret", kernels/ops.py)
    # of the region filter and the one-vs-all head; the crop step picks by
    # backend instead (``ops.crop_gather_impl``: the Pallas kernel on a TPU
    # for "ref" and "pallas", the oracle elsewhere)
    impl: str = "ref"


@dataclass
class ChunkResult:
    boxes: np.ndarray            # (F, N, 4) final detections
    labels: np.ndarray           # (F, N)
    valid: np.ndarray            # (F, N) bool
    source: np.ndarray           # (F, N) 0=cloud-accepted 1=fog-classified
    wan_bytes: float
    coord_bytes: float
    cloud_frames: int
    latency: LatencyBreakdown
    # HITL hand-off
    fog_features: np.ndarray     # (F, N, d+1)
    prop_boxes: np.ndarray       # (F, N, 4)
    prop_valid: np.ndarray       # (F, N)
    fog_scores: np.ndarray       # (F, N, C)


# ---------------------------------------------------------------------------
# Stage functions (each one a dispatchable serverless function)
# ---------------------------------------------------------------------------
def encode_low(pcfg: ProtocolConfig, frames_hq: jax.Array) -> codec.EncodedChunk:
    """fog.encode_low — quality-control re-encode to (r_low, q_low)."""
    enc_fn = codec.encode_inter if pcfg.inter_coding else codec.encode
    return enc_fn(frames_hq, pcfg.r_low, pcfg.q_low)


@functools.partial(jax.jit, static_argnames=("det_cfg",))
def detect_regions(det_cfg: DetectorConfig, det_params,
                   frames: jax.Array) -> Dict[str, jax.Array]:
    """Reference: the heavy cloud detector on LOW-quality frames.

    The leading axis is a plain frame batch; per-frame outputs are
    independent.  The served path runs the detector inside
    :func:`detect_split`."""
    return det_mod.detect(det_cfg, det_params, frames)


@functools.partial(jax.jit, static_argnames=("pcfg",))
def split_uncertain(pcfg: ProtocolConfig, det: Dict[str, jax.Array]
                    ) -> Tuple[reg.RegionSplit, jax.Array]:
    """Reference: the §IV.B split into accepted vs uncertain regions, and
    the coordinate bytes the cloud sends back."""
    split = reg.split_regions(
        det, theta_cls=pcfg.theta_cls, theta_loc=pcfg.theta_loc,
        theta_iou=pcfg.theta_iou, theta_back=pcfg.theta_back, impl=pcfg.impl)
    return split, reg.coordinate_bytes(split)


@functools.partial(jax.jit, static_argnames=("det_cfg", "pcfg"))
def detect_split(det_cfg: DetectorConfig, pcfg: ProtocolConfig, det_params,
                 frames: jax.Array) -> reg.RegionSplit:
    """cloud.detect_split — fused detector + §IV.B split, one dispatch.

    Takes the packed cross-stream frame batch and returns the full-batch
    :class:`~repro.core.regions.RegionSplit`.  Both the split filter and
    the detector are per-frame independent, so slicing the fused output per
    chunk computes what ``detect_regions`` + ``split_uncertain`` (the
    reference) compute on that chunk — but the scheduler issues ONE jit
    call and needs one host transfer (the validity mask, from which
    per-chunk coord bytes and crop counts are derived) per flush."""
    det = det_mod.detect(det_cfg, det_params, frames)
    return reg.split_regions(
        det, theta_cls=pcfg.theta_cls, theta_loc=pcfg.theta_loc,
        theta_iou=pcfg.theta_iou, theta_back=pcfg.theta_back, impl=pcfg.impl)


@functools.partial(jax.jit, static_argnames=("det_cfg", "pcfg"),
                   donate_argnums=(3,))
def detect_split_donated(det_cfg: DetectorConfig, pcfg: ProtocolConfig,
                         det_params, frames: jax.Array) -> reg.RegionSplit:
    """:func:`detect_split` with the packed frame batch donated to XLA.

    The scheduler routes here only when the batch is the dispatch-owned
    multi-request concat (dead after this call) on a non-CPU backend, so
    XLA may reuse the buffer in place.  On CPU donation is a warning-level
    no-op and the scheduler keeps the plain stage; either way the math —
    and therefore the output — is identical to :func:`detect_split`."""
    det = det_mod.detect(det_cfg, det_params, frames)
    return reg.split_regions(
        det, theta_cls=pcfg.theta_cls, theta_loc=pcfg.theta_loc,
        theta_iou=pcfg.theta_iou, theta_back=pcfg.theta_back, impl=pcfg.impl)


@functools.partial(jax.jit, static_argnames=("det_cfg", "pcfg"))
def detect_split_dynamic(det_cfg: DetectorConfig, pcfg: ProtocolConfig,
                         det_params, frames: jax.Array,
                         theta_cls: jax.Array, theta_loc: jax.Array
                         ) -> reg.RegionSplit:
    """Fused detect + split with per-frame (per-site) traced thresholds.

    Used when a flush packs streams whose ``theta_cls`` / ``theta_loc``
    were adapted away from the global config: the (F,) theta vectors ride
    in as traced args, so the handful of per-site values never force a
    recompile.  With every frame at the config defaults the output is
    bitwise-equal to :func:`detect_split` (thetas only enter elementwise
    comparisons — see :func:`repro.core.regions.split_regions_dynamic`)."""
    det = det_mod.detect(det_cfg, det_params, frames)
    return reg.split_regions_dynamic(
        det, theta_cls=theta_cls, theta_loc=theta_loc,
        theta_iou=pcfg.theta_iou, theta_back=pcfg.theta_back)


def _merge_fog(pcfg: ProtocolConfig, split: reg.RegionSplit,
               fog_scores: jax.Array, fog_feats: jax.Array
               ) -> Dict[str, jax.Array]:
    """Shared cloud-accepted + fog-classified merge.

    ``fog_scores`` / ``fog_feats`` are zero at invalid proposal positions
    (masked or scatter-initialised), so the merge — and therefore the whole
    ChunkResult — is deterministic there regardless of which classify path
    produced them."""
    fog_labels = jnp.argmax(fog_scores, axis=-1).astype(jnp.int32)
    fog_conf = jnp.max(fog_scores, axis=-1)
    fog_valid = split.prop_valid & (fog_conf >= pcfg.fog_min_conf)
    labels = jnp.where(split.acc_valid, split.acc_labels, fog_labels)
    valid = split.acc_valid | fog_valid
    source = jnp.where(split.acc_valid, 0, 1).astype(jnp.int32)
    return {"boxes": split.acc_boxes, "labels": labels, "valid": valid,
            "source": source, "fog_features": fog_feats,
            "fog_scores": fog_scores}


@functools.partial(jax.jit, static_argnames=("clf_cfg", "pcfg"))
def classify_regions(clf_cfg: ClassifierConfig, pcfg: ProtocolConfig,
                     clf_params, W, frames_hq: jax.Array,
                     split: reg.RegionSplit) -> Dict[str, jax.Array]:
    """Reference: HQ crop + one-vs-all classify + merge for one stream.

    Every region slot in the F x N grid is cropped and classified.  Outputs
    at invalid proposal positions are masked to zero, as the compacted
    path (which never computes them) leaves them.  The served path runs
    :func:`classify_compacted`."""
    crops = reg.crop_batch(frames_hq, split.prop_boxes, clf_cfg.crop_hw)
    f, n = crops.shape[0], crops.shape[1]
    flat = crops.reshape(f * n, *crops.shape[2:])
    # the one-vs-all head follows the same kernel knob as the filter: on
    # kernel impls the fused Pallas head scores the crops (bit-validated
    # against the inline sigmoid matmul)
    out = clf_mod.classify(clf_cfg, clf_params, flat, W=W, impl=pcfg.impl)
    mask = split.prop_valid[..., None]
    fog_scores = jnp.where(mask, out["scores"].reshape(f, n, -1), 0.0)
    fog_feats = jnp.where(mask, out["features"].reshape(f, n, -1), 0.0)
    return _merge_fog(pcfg, split, fog_scores, fog_feats)


# The implementation each compacted classify stage traced its crop step
# onto, by (``impl``, HQ frames shape, bucket): what
# ``ops.crop_gather_impl`` chose when the stage was traced, and so what its
# compiled program runs.  The scheduler counts kernel crops from it.
CROP_IMPL_TRACED: Dict[Tuple[str, Tuple[int, ...], int], str] = {}


def crop_impl_traced(pcfg: ProtocolConfig, frames_shape, bucket: int
                     ) -> Optional[str]:
    """The crop implementation the stage traced for these shapes, or None
    before the stage has been traced for them."""
    return CROP_IMPL_TRACED.get((pcfg.impl, tuple(frames_shape), bucket))


def _crop_bucket(clf_cfg: ClassifierConfig, pcfg: ProtocolConfig,
                 frames_hq: jax.Array, split: reg.RegionSplit,
                 idxs: jax.Array) -> jax.Array:
    """The compacted classify stages' crop step: (B, h, w, 3).

    Crops only the B bucket rows that the gather plan ``idxs`` names, with
    what ``ops.crop_gather_impl`` picks by backend: the ``crop_gather``
    Pallas kernel on a TPU, the jitted ``ref.crop_gather`` program
    elsewhere (``impl="interpret"`` runs the kernel body, for tests), and
    records the choice in ``CROP_IMPL_TRACED``.  Both run the bilinear
    program of ``ref.bilinear_crops``, so the rows are bitwise what cropping
    the whole F x N grid and indexing it gives, pad rows included, and the
    cost scales with B, not with F x N."""
    impl = ops.crop_gather_impl(pcfg.impl, frames_hq.shape)
    CROP_IMPL_TRACED[(pcfg.impl, tuple(frames_hq.shape),
                      idxs.shape[1])] = impl
    return ops.crop_gather(frames_hq, split.prop_boxes, idxs,
                           out_hw=clf_cfg.crop_hw, impl=impl)


@functools.partial(jax.jit, static_argnames=("clf_cfg", "pcfg"))
def classify_compacted(clf_cfg: ClassifierConfig, pcfg: ProtocolConfig,
                       clf_params, Ws: jax.Array, frames_hq: jax.Array,
                       split: reg.RegionSplit, idxs: jax.Array
                       ) -> Dict[str, jax.Array]:
    """fog.classify_batched — compacted cross-stream classify.

    ``idxs`` is one (3, B) int32 upload — rows ``(fidx, ridx, widx)``.
    ``(fidx, ridx)`` index the valid proposals of the whole flush (padded to
    a bucket with out-of-bounds rows: gathers clip, scatters drop), and
    ``widx`` picks each crop's per-stream readout from the stacked ``Ws``
    (G, d+1, C).  Only the B bucket rows are cropped (``_crop_bucket``,
    bitwise the full grid's rows) and pay the classifier-backbone FLOPs —
    the full-budget path pays F x N for both, so cost here scales with
    valid proposals — and the scores/features are scattered back into
    zero-initialised grids, matching the masked full-budget output."""
    fidx, ridx, widx = idxs[0], idxs[1], idxs[2]
    gathered = _crop_bucket(clf_cfg, pcfg, frames_hq, split, idxs)
    out = clf_mod.classify_multi(clf_cfg, clf_params, gathered, Ws, widx)
    x, scores = out["features"], out["scores"]
    f, n = split.prop_valid.shape
    fog_scores = jnp.zeros((f, n, scores.shape[-1]), scores.dtype
                           ).at[fidx, ridx].set(scores, mode="drop")
    fog_feats = jnp.zeros((f, n, x.shape[-1]), x.dtype
                          ).at[fidx, ridx].set(x, mode="drop")
    return _merge_fog(pcfg, split, fog_scores, fog_feats)


@functools.partial(jax.jit, static_argnames=("clf_cfg", "pcfg"))
def classify_ensemble(clf_cfg: ClassifierConfig, pcfg: ProtocolConfig,
                      clf_params, snaps: jax.Array, omega: jax.Array,
                      frames_hq: jax.Array, split: reg.RegionSplit
                      ) -> Dict[str, jax.Array]:
    """Reference: Eq. (9) snapshot-ensemble classify + merge for one stream.

    Every region slot is cropped, one backbone pass feeds all T stacked
    snapshots, and the per-crop score is the omega-weighted sigmoid
    combination.  With one snapshot and omega=[1.0] the output is
    bitwise-identical to :func:`classify_regions` — the multi-readout
    stage *contains* the single-readout stage as its degenerate case.  The
    served path runs :func:`classify_compacted_ensemble`."""
    crops = reg.crop_batch(frames_hq, split.prop_boxes, clf_cfg.crop_hw)
    f, n = crops.shape[0], crops.shape[1]
    flat = crops.reshape(f * n, *crops.shape[2:])
    out = clf_mod.classify_ensemble(clf_cfg, clf_params, flat, snaps, omega)
    mask = split.prop_valid[..., None]
    fog_scores = jnp.where(mask, out["scores"].reshape(f, n, -1), 0.0)
    fog_feats = jnp.where(mask, out["features"].reshape(f, n, -1), 0.0)
    return _merge_fog(pcfg, split, fog_scores, fog_feats)


@functools.partial(jax.jit, static_argnames=("clf_cfg", "pcfg"))
def classify_compacted_ensemble(clf_cfg: ClassifierConfig,
                                pcfg: ProtocolConfig, clf_params,
                                snaps: jax.Array, omegas: jax.Array,
                                frames_hq: jax.Array, split: reg.RegionSplit,
                                idxs: jax.Array) -> Dict[str, jax.Array]:
    """fog.classify_ensemble_batched — compacted cross-stream Eq. (9).

    The ensemble twin of :func:`classify_compacted`: same (3, B) gather
    plan (``widx`` now picks a per-stream snapshot *lineage* from ``snaps``
    (G, T, d+1, C) with ridge weights ``omegas`` (G, T)), same
    scatter-back into zero grids.  Lineages padded with zero snapshots and
    zero omega stay bitwise-equal to their unpadded scores, so one flush
    can mix streams with different snapshot counts — including plain
    single-readout streams (T=1, omega=[1.0])."""
    fidx, ridx, widx = idxs[0], idxs[1], idxs[2]
    gathered = _crop_bucket(clf_cfg, pcfg, frames_hq, split, idxs)
    out = clf_mod.classify_ensemble_multi(clf_cfg, clf_params, gathered,
                                          snaps, omegas, widx)
    x, scores = out["features"], out["scores"]
    f, n = split.prop_valid.shape
    fog_scores = jnp.zeros((f, n, scores.shape[-1]), scores.dtype
                           ).at[fidx, ridx].set(scores, mode="drop")
    fog_feats = jnp.zeros((f, n, x.shape[-1]), x.dtype
                          ).at[fidx, ridx].set(x, mode="drop")
    return _merge_fog(pcfg, split, fog_scores, fog_feats)


def assemble_result(split: reg.RegionSplit, merged: Dict[str, jax.Array],
                    *, wan_bytes: float, coord_bytes: float,
                    cloud_frames: int, latency: LatencyBreakdown
                    ) -> ChunkResult:
    """Host-side :class:`ChunkResult` of one chunk's split and merge."""
    return ChunkResult(
        boxes=np.asarray(merged["boxes"]), labels=np.asarray(merged["labels"]),
        valid=np.asarray(merged["valid"]), source=np.asarray(merged["source"]),
        wan_bytes=float(wan_bytes), coord_bytes=float(coord_bytes),
        cloud_frames=cloud_frames, latency=latency,
        fog_features=np.asarray(merged["fog_features"]),
        prop_boxes=np.asarray(split.prop_boxes),
        prop_valid=np.asarray(split.prop_valid),
        fog_scores=np.asarray(merged["fog_scores"]))


# ---------------------------------------------------------------------------
# Sequential protocol driver with bytes / latency / cost accounting
# ---------------------------------------------------------------------------
@dataclass
class HighLowProtocol:
    det_cfg: DetectorConfig
    clf_cfg: ClassifierConfig
    pcfg: ProtocolConfig = field(default_factory=ProtocolConfig)
    network: NetworkModel = field(default_factory=NetworkModel)
    cost_model: CostModel = field(default_factory=CostModel)
    fog: DeviceProfile = FOG
    cloud: DeviceProfile = CLOUD

    def process_chunk(self, det_params, clf_params, frames_hq: np.ndarray,
                      W=None) -> ChunkResult:
        fhq = jnp.asarray(frames_hq)
        enc = encode_low(self.pcfg, fhq)
        det = detect_regions(self.det_cfg, det_params, enc.frames)
        split, coord_bytes = split_uncertain(self.pcfg, det)
        merged = classify_regions(
            self.clf_cfg, self.pcfg, clf_params,
            W if W is not None else clf_params["W"], fhq, split)

        f = frames_hq.shape[0]
        n_crops = int(np.sum(np.asarray(split.prop_valid)))
        lat = LatencyBreakdown(
            quality_control=self.fog.encode_time(f),
            transmission=(self.network.wan_time(float(enc.nbytes))
                          + self.network.wan_time(float(coord_bytes))),
            cloud_inference=self.cloud.detect_time(f),
            fog_inference=self.fog.classify_time(max(n_crops, 1)),
        )
        return assemble_result(split, merged, wan_bytes=float(enc.nbytes),
                               coord_bytes=float(coord_bytes),
                               cloud_frames=f, latency=lat)

    def cloud_cost(self, result: ChunkResult) -> float:
        # RQ2: one cloud detector pass per frame, nothing else
        return self.cost_model.cost(result.cloud_frames, rounds=1.0)


def detections_for_metrics(res: ChunkResult, frame: int
                           ) -> Tuple[np.ndarray, np.ndarray]:
    """Extract (boxes, labels) arrays for the F1 accumulator."""
    keep = res.valid[frame]
    return res.boxes[frame][keep], res.labels[frame][keep]
