"""Compacted classify: least time of the traced calls' operations and bytes
(bench/roofline.py classify_cost) over their device time."""
from bench.readers import classify_roofline

MODULES = ("classify_compacted",)


def read(ctx):
    return classify_roofline(ctx, MODULES)
