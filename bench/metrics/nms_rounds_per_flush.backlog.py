"""Rounds of the split's two greedy NMS passes per detector flush, backlog
cells (kernels/ref.py nms_mask, run twice by core/regions.py
split_regions): detect_stats nms_rounds / calls over the window.  A
program that does not count them has no nms_rounds key, and this reads
None."""
from typing import Optional


def read(ctx) -> Optional[float]:
    w = ctx["window"]
    if "detect.nms_rounds" not in w or not w.get("detect.calls"):
        return None
    return w["detect.nms_rounds"] / w["detect.calls"]
