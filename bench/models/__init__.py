"""Model families: what the benchmark knows of one kind of detector and
classifier, one file each, named by a configuration's ``"models"`` key.

A family module ``bench/models/<name>.py`` provides:

  make_weights(cfg, seed) -> (det_params, clf_params)
      the weights on the device, from ``seed`` (the configuration's
      ``weights.seed``), in the layout the program's models read
  calibrate(cfg, det_params, chunks) -> det_params
      the detector set on HQ chunks (T, H, W, 3) of the cell's traffic
  detector(det_params, images, cfg, precision) -> (boxes, loc, probs
      [, ids [, selection]])
      over the detector's N region slots: boxes (B, N, 4) xyxy in [0, 1],
      objectness (B, N), class probabilities (B, N, C); optionally
      ``ids`` (B, N) int32, each slot's region identity (an RPN's
      (level, y, x, anchor) flattened to one int; -1 where the slot is
      empty), which the check matches served regions by (without it, the
      slot index: one region a slot, as the stand-in's grid); and, for a
      detector that selects its regions, the ``selection`` record, a dict:
        ids (B, M) int32       the candidates the selection looked at
                               (-1 pads)
        scores (B, M)          their selection score
        boxes (B, M, 4)        their boxes, xyxy
        group (B, M) int32     their group (an FPN level), 0 .. G-1
        pre_k, nms_iou, post_k the rule: top pre_k a group by score, greedy
                               NMS at IoU >= nms_iou within a group, top
                               post_k of the survivors over all groups
        cut (B, G), optional   where the record holds only a group's
                               candidates scoring above cut[b, g] (those
                               that could enter within rounding); -inf
                               where it holds them all
      from which the check works out which memberships are clear and
      counts ``selection_errors``; its configuration then states that
      number's limit (0, exact) with the others
  classifier(clf_params, crops, W, cfg, precision) -> (features, scores)
      features (K, feature_dim + 1) with the bias-absorbing 1, one-vs-all
      scores (K, C) under the readout ``W``
  detector_flops_per_frame(det), classifier_flops_per_crop(clf),
  detect_split_cost(det, frames, calls), classify_cost(clf, det, rows,
  frames, calls)
      operations and bytes of the served kernels, as ``bench/roofline.py``
      describes them

``precision`` is one of ``bench.reference.ROUND_TO``.  A new family is a
new file here and a configuration that names it; nothing else changes.  A
program serving a family with identities carries them in its results as
``region_ids`` (F, N), read with the check's fields.
"""
from __future__ import annotations

import glob
import importlib.util
import os
from types import ModuleType
from typing import Dict, List, Optional

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))
INTERFACE = ("make_weights", "calibrate", "detector", "classifier",
             "detector_flops_per_frame", "classifier_flops_per_crop",
             "detect_split_cost", "classify_cost")

# one module per file, so that a family's jitted forwards compile once
_loaded: Dict[str, ModuleType] = {}


def known(root: Optional[str] = None) -> List[str]:
    return sorted(os.path.basename(p)[:-3] for p in glob.glob(
        os.path.join(root or ROOT, "bench", "models", "*.py"))
        if not os.path.basename(p).startswith("_"))


def family(cfg: dict, root: Optional[str] = None) -> ModuleType:
    """The model family that configuration ``cfg`` names (``"models"``),
    from ``bench/models`` under ``root`` (by default the checkout's,
    ``ROOT``, looked up at the call)."""
    root = root or ROOT
    name = cfg.get("models")
    if name not in known(root):
        raise KeyError(f"configuration {cfg.get('name')!r} names model family "
                       f"{name!r}; known families: {known(root)}")
    path = os.path.join(root, "bench", "models", name + ".py")
    if path not in _loaded:
        spec = importlib.util.spec_from_file_location(
            "bench_models_" + name.replace(".", "_").replace("-", "_"), path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        _loaded[path] = mod
    return _loaded[path]
