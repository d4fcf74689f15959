"""Compile the Pallas implicit-GEMM conv (kernels.conv2d) for a described
TPU v5e at mesh1k's widths — what interpret mode cannot check: Mosaic's
lowering (strided ref reads, the 2-D MXU dot) and the scoped-VMEM budget.
Nothing runs; the compile needs only the TPU compiler, not a chip.

The topology is described inside a fixture (never at import), the only
place in the suite that loads the TPU library; every test here compiles
in this process, and JAX's persistent compilation cache is off around
them (an entry compiled for a described chip cannot be read back here).
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.conv2d import conv2d

# (x shape, w shape, stride): the 4-way H-split shard of mesh1k's first
# layers, halo rows and 'SAME' padding included — conv1_1 (C=18, stride
# 2, 1024^2 input), conv1_2 (64->64 at 512^2) and conv2_1 (64->128,
# stride 2)
CASES = {
    "conv1_1": ((1, 257, 1025, 18), (3, 3, 18, 64), 2),
    "conv1_2": ((1, 130, 514, 64), (3, 3, 64, 64), 1),
    "conv2_1": ((1, 129, 513, 64), (3, 3, 64, 128), 2),
}


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:      # noqa: BLE001 — no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def no_compile_cache():
    from jax.experimental.compilation_cache import compilation_cache
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    compilation_cache.reset_cache()


@pytest.mark.parametrize("name", sorted(CASES))
def test_pallas_conv_compiles_for_v5e(name, one_chip, no_compile_cache):
    xs, ws, stride = CASES[name]
    x = jax.ShapeDtypeStruct(xs, jnp.float32, sharding=one_chip)
    w = jax.ShapeDtypeStruct(ws, jnp.float32, sharding=one_chip)
    compiled = jax.jit(lambda x, w: conv2d(x, w, stride=stride)).lower(
        x, w).compile()
    assert "tpu_custom_call" in compiled.as_text()
    h_out = (xs[1] - ws[0]) // stride + 1
    w_out = (xs[2] - ws[1]) // stride + 1
    out = compiled.memory_analysis().output_size_in_bytes
    assert out == xs[0] * h_out * w_out * ws[3] * 4
