"""The main path's kernels, compiled for a described TPU v5e chip.

No chip is attached here: the TPU compiler builds for a topology it is
only told about, so Mosaic's refusals (block shapes against the (8, 128)
tiling rule, layouts it cannot lower, scoped-VMEM overruns) surface in
the CPU suite at no chip time. Nothing runs; results are checked by the
interpret-mode tests (tests/test_fused_hotpath.py) and on the chip by
chip_smoke.py.

The topology is described inside a module fixture, never at import:
only one process at a time may load the TPU library, and every xdist
worker imports every test file.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs.base import TahomaCNNConfig
from repro.core.executor import Stage0, make_fused_ingest
from repro.core.transforms import Representation
from repro.models.cnn import cnn_predict_proba, init_cnn, quantize_cnn

BASE = 224
LEVELS = (112, 56, 28)
# the reduced grid's extremes (configs/tahoma_cnn.py): smallest model on
# the smallest gray input, largest on the largest rgb input
ARCHS = {"1conv8-gray28": (TahomaCNNConfig(1, 8, 16), 28, "gray"),
         "2conv32-rgb56": (TahomaCNNConfig(2, 32, 32), 56, "rgb")}


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def no_compile_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one; keep the cache out of it."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


def _stage0(arch_name, int8=False):
    arch, res, color = ARCHS[arch_name]
    rep = Representation(res, color)
    cfg = TahomaCNNConfig(arch.n_conv_layers, arch.conv_nodes,
                          arch.dense_nodes, input_hw=res,
                          input_channels=rep.channels)
    params = init_cnn(jax.random.PRNGKey(0), cfg)
    return Stage0(params, rep, quantize_cnn(params) if int8 else None)


def _images(width, sharding):
    return jax.ShapeDtypeStruct((width, BASE, BASE, 3), jnp.float32,
                                sharding=sharding)


@pytest.mark.parametrize("arch_name", sorted(ARCHS))
@pytest.mark.parametrize("int8", [False, True], ids=["f32", "int8"])
@pytest.mark.parametrize("width", [16, 256])
def test_fused_pyramid_stage0_compiles_for_v5e(one_chip, no_compile_cache,
                                               arch_name, int8, width):
    """The chunk-ingest kernel at base 224 with levels {112, 56, 28}, at
    the service's smallest slab (16) and the scan chunk (256). The
    compiled Mosaic op carries the kernel's name."""
    from repro.kernels.image_transform import fused_pyramid_stage0

    s0 = _stage0(arch_name, int8)

    def run(imgs):
        return fused_pyramid_stage0(imgs, LEVELS, s0.params, s0.rep,
                                    qparams=s0.qparams, interpret=False)
    compiled = jax.jit(run).lower(_images(width, one_chip)).compile()
    kernel = [ln for ln in compiled.as_text().splitlines()
              if 'custom_call_target="tpu_custom_call"' in ln]
    assert kernel
    assert all("%fused_pyramid_stage0" in ln for ln in kernel)


@pytest.mark.parametrize("width,specs", [
    (16, ((112, "rgb"), (56, "gray"), (28, "r"))),
    (64, ((224, "rgb"), (28, "gray")))], ids=["three-levels", "with-base"])
def test_fused_pyramid_transform_compiles_for_v5e(one_chip, no_compile_cache,
                                                  width, specs):
    """The multi-output representation kernel shares the row-slab layout
    and the pooling with the stage-0 kernel."""
    from repro.kernels.image_transform import (color_weight_matrix,
                                               fused_pyramid_transform)

    def run(imgs):
        return fused_pyramid_transform(
            imgs, [(r, color_weight_matrix(c)) for r, c in specs],
            interpret=False)
    compiled = jax.jit(run).lower(_images(width, one_chip)).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_matmul_compiles_for_v5e(one_chip, no_compile_cache):
    """The streaming evaluator's product: a 128-row chunk of certainty
    masks against the paper grid's 360 models x 5 targets."""
    from repro.kernels.matmul import matmul

    a = jax.ShapeDtypeStruct((128, 2048), jnp.float32, sharding=one_chip)
    b = jax.ShapeDtypeStruct((2048, 1800), jnp.float32, sharding=one_chip)
    compiled = jax.jit(lambda x, y: matmul(x, y, interpret=False)).lower(
        a, b).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("int8", [False, True], ids=["f32", "int8"])
def test_fused_ingest_program_compiles_for_v5e(one_chip, no_compile_cache,
                                               monkeypatch, int8):
    """The whole jitted chunk-ingest program (kernel + the rest of the
    stage-0 cascade + carried levels) with the kernel steered to its
    compiled form, as resolve_interpret picks it on a TPU backend."""
    import repro.kernels.image_transform as it

    monkeypatch.setattr(it, "resolve_interpret", lambda interpret: False)
    s0 = _stage0("2conv32-rgb56", int8)
    arch2 = TahomaCNNConfig(1, 16, 32, input_hw=112, input_channels=1)
    p2 = init_cnn(jax.random.PRNGKey(1), arch2)
    fns = [lambda x: cnn_predict_proba(s0.params, x),
           lambda x: cnn_predict_proba(p2, x)]
    reps = [s0.rep, Representation(112, "gray")]
    ingest = make_fused_ingest(fns, [(0.2, 0.8), (None, None)], reps,
                               [256], (56, 28), stage0=s0,
                               use_kernel=True, int8=int8)
    compiled = ingest.lower(_images(256, one_chip)).compile()
    assert "tpu_custom_call" in compiled.as_text()
