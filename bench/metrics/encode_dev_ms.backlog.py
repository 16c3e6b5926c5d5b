"""Device ms per call of the codec (video/codec.py encode_inter, one chunk)."""
from bench.readers import module_ms

MODULES = ("encode_inter",)


def read(ctx):
    return module_ms(ctx, MODULES)
