"""Channel/filter-parallel conv runtime (core.channel_conv) tests.

Single-device half here (dense fallbacks are the 1x1-mesh oracle path and
must be bitwise-identical; the Pallas implicit-GEMM backend runs in
interpret mode on CPU).  The multi-device parity half — both CF modes vs
the dense oracle, fwd + grads, BN/bias, and the solved-plan acceptance
check — lives in tests/dist_checks.py group 'cf' (subprocess, 8 host
devices), run by tests/test_plan.py::test_plan_cf_distributed, which is
intentionally NOT marked slow so the CI fast lane exercises the CF
parity group too.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax
from jax.sharding import PartitionSpec as P

from repro.core.channel_conv import (CFSharding, cf_batch_norm, cf_bias_add,
                                     cf_conv2d)
from repro.core.spatial_conv import ConvSharding, spatial_conv2d
from repro.core.spatial_norm import batch_norm
from repro.utils import same_pads


def _oracle(x, w, s=1):
    k_h, k_w = w.shape[0], w.shape[1]
    return lax.conv_general_dilated(
        x, w, (s, s), (same_pads(k_h, s), same_pads(k_w, s)),
        dimension_numbers=("NHWC", "HWIO", "NHWC"))


# -------------------------------------------------------------- descriptor --
def test_cfsharding_surface():
    sh = CFSharding(batch_axes=("data",), cf_axis="model")
    assert not sh.is_spatial
    assert sh.h_axis is None and sh.w_axis is None
    assert sh.fit(32, 32, 3, 1, None) == sh          # geometry fit: no-op
    # PartitionSpec equality normalizes a 1-tuple entry to its axis name
    assert sh.x_spec() == P(("data",), None, None, "model")
    assert sh.x_spec() == P("data", None, None, "model")
    assert sh.fits_channels(8, 16, {"model": 2})
    assert not sh.fits_channels(5, 16, {"model": 2})
    assert not sh.fits_channels(8, 7, {"model": 2})
    with pytest.raises(ValueError):
        CFSharding(cf_axis="model", mode="diagonal")


# ----------------------------------------------------- dense (1x1) fallback --
def test_cf_conv_dense_fallback_bitwise():
    """cf_axis on a size-1 (or absent) mesh takes the dense path and is
    bitwise-identical to both the oracle and the spatial dense path."""
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 8, 8, 6))
    w = jax.random.normal(jax.random.PRNGKey(1), (3, 3, 6, 4)) * 0.1
    for mode in ("channel", "filter"):
        got = cf_conv2d(x, w, sharding=CFSharding(mode=mode))
        np.testing.assert_array_equal(np.asarray(got),
                                      np.asarray(_oracle(x, w)))
    sp = spatial_conv2d(x, w, sharding=ConvSharding())
    np.testing.assert_array_equal(
        np.asarray(cf_conv2d(x, w, sharding=CFSharding())), np.asarray(sp))


def test_cf_conv_dense_strided():
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 8, 8, 6))
    w = jax.random.normal(jax.random.PRNGKey(1), (3, 3, 6, 4)) * 0.1
    got = cf_conv2d(x, w, strides=(2, 2), sharding=CFSharding())
    np.testing.assert_array_equal(np.asarray(got),
                                  np.asarray(_oracle(x, w, 2)))


def test_cf_bn_dense_matches_spatial_norm_bitwise():
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 8, 8, 6)) * 3 + 1
    g = jax.random.normal(jax.random.PRNGKey(1), (6,)) + 2
    b = jax.random.normal(jax.random.PRNGKey(2), (6,))
    ref = batch_norm(x, g, b, sharding=ConvSharding(), scope="local")
    for scope in ("local", "spatial", "global"):
        got = cf_batch_norm(x, g, b, sharding=CFSharding(), scope=scope)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))
    with pytest.raises(ValueError):
        cf_batch_norm(x, g, b, sharding=CFSharding(), scope="galactic")


def test_cf_bias_dense():
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 4, 4, 6))
    b = jax.random.normal(jax.random.PRNGKey(1), (6,))
    got = cf_bias_add(x, b, sharding=CFSharding())
    np.testing.assert_array_equal(np.asarray(got), np.asarray(x + b))


# ------------------------------------------------ pallas interpret backend --
def test_cf_conv_pallas_interpret_parity():
    """backend='pallas' routes the CF local conv through the implicit-GEMM
    MXU kernel; interpret mode on CPU is numerics-identical to the TPU
    lowering, so parity here is parity there."""
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 8, 8, 8))
    w = jax.random.normal(jax.random.PRNGKey(1), (3, 3, 8, 8)) * 0.1
    got = cf_conv2d(x, w, sharding=CFSharding(), backend="pallas")
    np.testing.assert_allclose(np.asarray(got), np.asarray(_oracle(x, w)),
                               rtol=2e-6, atol=2e-6)


def test_cf_mixed_precision_casts_to_weight_dtype():
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 4, 4, 4))
    w = (jax.random.normal(jax.random.PRNGKey(1), (1, 1, 4, 4)) * 0.1
         ).astype(jnp.bfloat16)
    y = cf_conv2d(x, w, sharding=CFSharding())
    assert y.dtype == jnp.bfloat16


def test_mixed_precision_rule_unified_across_conv_paths():
    """Both conv runtimes share cast_to_weight_dtype (compute in the
    *weight* dtype), so a mixed sample/spatial/CF plan cannot change
    dtype — or numerics — at a reshard boundary: the same layer computes
    the same values whichever decomposition executes it."""
    from repro.core.spatial_conv import cast_to_weight_dtype
    x32 = jax.random.normal(jax.random.PRNGKey(0), (2, 8, 8, 4))
    w16 = (jax.random.normal(jax.random.PRNGKey(1), (3, 3, 4, 4)) * 0.1
           ).astype(jnp.bfloat16)
    # f32 activations into bf16 weights: both paths compute in bf16
    y_sp = spatial_conv2d(x32, w16, sharding=ConvSharding())
    y_cf = cf_conv2d(x32, w16, sharding=CFSharding())
    assert y_sp.dtype == y_cf.dtype == jnp.bfloat16
    np.testing.assert_array_equal(np.asarray(y_sp), np.asarray(y_cf))
    # bf16 activations into f32 weights: both paths upcast to f32
    x16 = x32.astype(jnp.bfloat16)
    w32 = jax.random.normal(jax.random.PRNGKey(1), (3, 3, 4, 4)) * 0.1
    y_sp = spatial_conv2d(x16, w32, sharding=ConvSharding())
    y_cf = cf_conv2d(x16, w32, sharding=CFSharding())
    assert y_sp.dtype == y_cf.dtype == jnp.float32
    np.testing.assert_array_equal(np.asarray(y_sp), np.asarray(y_cf))
    # the shared helper is the single source of the rule
    assert cast_to_weight_dtype(x32, w16).dtype == jnp.bfloat16
    assert cast_to_weight_dtype(x16, w32).dtype == jnp.float32
    assert cast_to_weight_dtype(x32, w32) is x32      # no-op when equal


def test_cfsharding_spatial_composition_surface():
    """CFSharding carries composed spatial axes: spec, fit and the
    same-axis guard."""
    sh = CFSharding(batch_axes=("pod",), cf_axis="model",
                    h_axis=("data", "x"))
    assert sh.is_spatial and sh.h_axes == ("data", "x")
    assert sh.x_spec() == P("pod", ("data", "x"), None, "model")
    # geometry fit drops an unfit product split (shard < kernel)
    fitted = sh.fit(4, 4, 3, 1, _FakeMesh({"data": 2, "x": 2,
                                           "model": 2, "pod": 2}))
    assert fitted.h_axis is None and fitted.cf_axis == "model"
    # cf axis colliding with a spatial axis is rejected at construction
    with pytest.raises(ValueError):
        CFSharding(cf_axis="model", h_axis=("model", "data"))


class _FakeMesh:
    def __init__(self, shape):
        self.shape = shape
