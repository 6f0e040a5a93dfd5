"""The main path's Pallas kernels compile for a TPU v5e chip.

Nothing runs: the TPU compiler installed with JAX compiles for a chip
that is described (``v5e:2x2``, device 0) rather than attached, and
refuses what the chip would refuse (a bitwidth-changing bitcast in a
kernel, an unaligned store) even where interpret mode passes.  The
topology is described only inside the fixture below, so each test worker
collects the same tests and only the worker that runs this file loads
the TPU library.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.quantize.kernel import (quantize_int8_raw,
                                           quantize_pack_int8_raw)

#: the cut shapes the int8 codec quantizes: the paper's MNIST head
#: (batch 128, cut width 64) and an LM sequence-slice cut at llama3.2-3b
#: width (B=4 x 512 tokens, d_model 3072)
CUT_SHAPES = [(128, 64), (2048, 3072)]


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import compilation_cache as cc
    from jax.experimental import topologies
    # a compile for a described chip can be written to the persistent
    # cache but never read back here: keep these compiles out of it
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.compilation_cache.reset_cache()
    try:
        with pytest.MonkeyPatch.context() as mp:
            mp.setenv("TPU_LOG_DIR", "disabled")
            try:
                desc = topologies.get_topology_desc(platform="tpu",
                                                    topology_name="v5e:2x2")
            except Exception as e:      # noqa: BLE001 — any failure skips
                pytest.skip(f"no v5e:2x2 topology can be described here: "
                            f"{e}")
            yield desc
    finally:
        jax.config.update("jax_enable_compilation_cache", enabled)
        cc.compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.mark.parametrize("kernel", [quantize_int8_raw,
                                    quantize_pack_int8_raw],
                         ids=["quantize", "quantize_pack"])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("shape", CUT_SHAPES, ids=["mnist", "lm"])
def test_quantize_kernels_compile_for_v5e(kernel, dtype, shape, one_chip):
    x = jax.ShapeDtypeStruct(shape, jnp.dtype(dtype), sharding=one_chip)
    compiled = jax.jit(kernel).lower(x).compile()
    assert "tpu_custom_call" in compiled.as_text()
