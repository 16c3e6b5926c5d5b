"""The served path's stages compile for a TPU v5e, at the served widths.

Nothing runs: the TPU compiler that ships with JAX compiles for a v5e that
is described, not attached, so these tests catch what interpret mode
cannot (block shapes the chip refuses, unsupported ops in a kernel body)
at no chip time.  Shapes are the served ones: ``DETECTOR``/``CLASSIFIER``
at full width, a 64-frame flush and a 256-row crop bucket.

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU library, so a description made
while pytest-xdist workers import this file would fail in all but one.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs.vpaas_video import CLASSIFIER, DETECTOR
from repro.core import protocol as pm
from repro.kernels import ops
from repro.models import classifier as clf_mod
from repro.models import detector as det_mod

FLUSH_FRAMES = 64
CROP_BUCKET = 256


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "no compiler"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_persistent_cache():
    # a program compiled for a described chip is written to the persistent
    # cache but cannot be read back without one; keep these compiles out
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def shapes(one_chip, no_persistent_cache):
    def on_chip(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=one_chip), tree)

    det_p = on_chip(jax.eval_shape(
        lambda: det_mod.init_detector(DETECTOR, jax.random.PRNGKey(0))))
    clf_p = on_chip(jax.eval_shape(
        lambda: clf_mod.init_classifier(CLASSIFIER, jax.random.PRNGKey(1))))
    frames = on_chip(jax.ShapeDtypeStruct(
        (FLUSH_FRAMES, *DETECTOR.image_hw, 3), jnp.float32))
    split = on_chip(jax.eval_shape(
        lambda p, f: pm.detect_split(DETECTOR, pm.ProtocolConfig(), p, f),
        det_p, frames))
    d1 = CLASSIFIER.feature_dim + 1
    return dict(
        det_p=det_p, clf_p=clf_p, frames=frames, split=split,
        Ws=on_chip(jax.ShapeDtypeStruct((4, d1, CLASSIFIER.num_classes),
                                        jnp.float32)),
        idxs=on_chip(jax.ShapeDtypeStruct((3, CROP_BUCKET), jnp.int32)),
        feats=on_chip(jax.ShapeDtypeStruct((CROP_BUCKET, d1), jnp.float32)),
        W=on_chip(jax.ShapeDtypeStruct((d1, CLASSIFIER.num_classes),
                                       jnp.float32)))


@pytest.fixture
def tpu_backend(monkeypatch):
    """``ops.crop_gather`` picks its implementation by ``jax.default_backend``,
    which here is the CPU: answer as the chip does.  The choice is made
    while a stage is traced, so traced programs are dropped around it."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    jax.clear_caches()
    yield
    jax.clear_caches()


def _has_kernel(compiled) -> bool:
    return "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("impl", ["ref", "pallas"])
def test_detect_split_compiles(shapes, impl):
    pcfg = pm.ProtocolConfig(impl=impl)
    compiled = jax.jit(pm.detect_split, static_argnums=(0, 1)).lower(
        DETECTOR, pcfg, shapes["det_p"], shapes["frames"]).compile()
    # the pallas impl runs the region-filter kernel inside the stage
    assert _has_kernel(compiled) == (impl == "pallas")


@pytest.mark.parametrize("impl", ["ref", "pallas"])
def test_classify_compacted_compiles(shapes, tpu_backend, impl):
    pcfg = pm.ProtocolConfig(impl=impl)
    compiled = jax.jit(pm.classify_compacted, static_argnums=(0, 1)).lower(
        CLASSIFIER, pcfg, shapes["clf_p"], shapes["Ws"], shapes["frames"],
        shapes["split"], shapes["idxs"]).compile()
    # on a TPU both impls gather the bucket's crops with the crop kernel
    assert _has_kernel(compiled)


def test_classify_compacted_ensemble_compiles(shapes, tpu_backend):
    """The per-site learning stage crops with the same kernel on "ref"."""
    d1 = CLASSIFIER.feature_dim + 1
    snaps = jax.ShapeDtypeStruct((4, 2, d1, CLASSIFIER.num_classes),
                                 jnp.float32,
                                 sharding=shapes["frames"].sharding)
    omegas = jax.ShapeDtypeStruct((4, 2), jnp.float32,
                                  sharding=shapes["frames"].sharding)
    compiled = jax.jit(pm.classify_compacted_ensemble,
                       static_argnums=(0, 1)).lower(
        CLASSIFIER, pm.ProtocolConfig(impl="ref"), shapes["clf_p"], snaps,
        omegas, shapes["frames"], shapes["split"], shapes["idxs"]).compile()
    assert _has_kernel(compiled)


@pytest.mark.parametrize("hw,kernel", [((512, 512), True),
                                       ((720, 1280), False)])
def test_crop_gather_vmem_choice_compiles(one_chip, no_persistent_cache,
                                          tpu_backend, hw, kernel):
    """At the edge of the VMEM budget the kernel compiles; HQ 720p frames,
    whose blocks the kernel could not hold, fall back to the oracle and
    compile too (the kernel there runs out of scoped VMEM)."""
    def on_chip(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    frames = on_chip((8, *hw, 3), jnp.float32)
    assert (ops.crop_gather_impl("ref", frames.shape) == "pallas") == kernel
    compiled = jax.jit(lambda f, b, i: ops.crop_gather(
        f, b, i, out_hw=CLASSIFIER.crop_hw)).lower(
        frames, on_chip((8, 256, 4), jnp.float32),
        on_chip((3, 64), jnp.int32)).compile()
    assert _has_kernel(compiled) == kernel


def test_onevsall_scores_compiles(shapes):
    compiled = jax.jit(lambda x, w: ops.onevsall_scores(
        x, w, impl="pallas")).lower(shapes["feats"], shapes["W"]).compile()
    assert _has_kernel(compiled)
