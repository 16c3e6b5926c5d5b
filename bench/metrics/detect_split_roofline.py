"""Fused detect + split: least time of the traced calls' operations and
bytes (bench/roofline.py detect_split_cost) over their device time."""
from bench.readers import detect_split_roofline

MODULES = ("detect_split", "detect_split_donated")


def read(ctx):
    return detect_split_roofline(ctx, MODULES)
