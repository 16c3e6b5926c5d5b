"""Device ms per compacted classify call (core/protocol.py classify_compacted:
crop gather, classifier backbone, per-stream one-vs-all readout, scatter)."""
from bench.readers import module_ms

MODULES = ("classify_compacted",)


def read(ctx):
    return module_ms(ctx, MODULES)
