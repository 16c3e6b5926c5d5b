"""Device ms per fused detect + split call (core/protocol.py detect_split,
detect_split_donated: detector, section IV.B filter and both NMS passes)."""
from bench.readers import module_ms

MODULES = ("detect_split", "detect_split_donated")


def read(ctx):
    return module_ms(ctx, MODULES)
