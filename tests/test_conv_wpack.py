"""W-pair packing of 64-channel activations (core.spatial_conv.wpack): a
3-wide conv of a 64-channel input and the BN after it run on
(N, H, W/2, 128) arrays.  Packed convs and BN equal the plain ones in
value and gradients on every piece a split conv builds; every other conv
keeps the plain path; the packed conv runs at the caller's precision; the
collective auditor still sees the interior conv as `conv_interior`."""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

from conftest import run_dist_group
from repro.analysis.collectives import collect_ops
from repro.core.spatial_conv import (DIMNUMS, ConvSharding, spatial_conv2d,
                                     wpack, wunpack)
from repro.core.spatial_norm import _batch_norm, batch_norm
from repro.launch.mesh import make_mesh
from repro.utils import FP32, same_pads

KEY = jax.random.PRNGKey(0)
WHOLE = ConvSharding()
H_SPLIT = ConvSharding(batch_axes=("data",), h_axis="model")
W_SPLIT = ConvSharding(batch_axes=("data",), w_axis="model")


@pytest.fixture(scope="module")
def mesh():
    """One device: a split over it builds the interior and boundary
    pieces, halo exchange and all, as cell 1's 1x1 plan does."""
    return make_mesh(data=1, model=1)


def plain_conv(x, w, s):
    return lax.conv_general_dilated(
        x, w, (s, s), (same_pads(w.shape[0], s), same_pads(w.shape[1], s)),
        dimension_numbers=DIMNUMS)


def convs(fn, *args):
    """(name-stack path, lhs shape) of every conv in fn's jaxpr."""
    out = []

    def walk(jaxpr, prefix):
        for eqn in jaxpr.eqns:
            ns = str(eqn.source_info.name_stack)
            path = f"{prefix}/{ns}" if ns else prefix
            if eqn.primitive.name == "conv_general_dilated":
                out.append((path, eqn.invars[0].aval.shape))
            for v in eqn.params.values():
                for it in v if isinstance(v, (list, tuple)) else [v]:
                    sub = it if hasattr(it, "eqns") else getattr(
                        it, "jaxpr", None)
                    if hasattr(sub, "eqns"):
                        walk(sub, path)

    walk(jax.make_jaxpr(fn)(*args).jaxpr, "")
    return out


def assert_close(got, want):
    scale = float(jnp.max(jnp.abs(want)))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=2e-6 * scale)


def test_pack_is_a_pairing_of_adjacent_columns():
    x = jnp.arange(2 * 3 * 4 * 64.0).reshape(2, 3, 4, 64)
    p = wpack(x)
    assert p.shape == (2, 3, 2, 128)
    np.testing.assert_array_equal(p[:, :, 1, :64], x[:, :, 2])
    np.testing.assert_array_equal(p[:, :, 1, 64:], x[:, :, 3])
    np.testing.assert_array_equal(wunpack(p), x)


@pytest.mark.parametrize("piece,sharding,s,f", [
    ("whole W, stride 1", WHOLE, 1, 64),
    ("H-split interior and boundary pieces, stride 1", H_SPLIT, 1, 64),
    ("whole W, stride 2, 64->128", WHOLE, 2, 128),
    ("H-split pieces, stride 2, 64->128", H_SPLIT, 2, 128),
    ("whole W, stride 1, 64->32", WHOLE, 1, 32),
])
def test_packed_conv_equals_the_plain_conv(mesh, piece, sharding, s, f):
    x = jax.random.normal(KEY, (2, 8, 16, 64), jnp.float32)
    w = jax.random.normal(jax.random.PRNGKey(1), (3, 3, 64, f)) * 0.05
    ct = jax.random.normal(jax.random.PRNGKey(2), plain_conv(x, w, s).shape)

    def packed(x, w):
        return spatial_conv2d(x, w, strides=(s, s), sharding=sharding,
                              mesh=mesh)

    def loss(conv):
        return lambda x, w: jnp.sum(conv(x, w) * ct)

    with mesh:
        # forward and both backward convs read the packed 128 channels
        seen = convs(jax.grad(loss(packed), argnums=(0, 1)), x, w)
        assert len(seen) >= 3 and len(seen) % 3 == 0
        assert all("conv_wpack" in path for path, _ in seen)
        assert all(shape[-1] in (128, 2 * f) for _, shape in seen)
        y = jax.jit(packed)(x, w)
        got = jax.jit(jax.grad(loss(packed), argnums=(0, 1)))(x, w)
    assert_close(y, plain_conv(x, w, s))
    want = jax.grad(loss(lambda x, w: plain_conv(x, w, s)),
                    argnums=(0, 1))(x, w)
    for g, r in zip(got, want):
        assert_close(g, r)


@pytest.mark.parametrize("case,shape,wshape,s,sharding,backend", [
    ("odd W", (2, 8, 15, 64), (3, 3, 64, 64), 1, WHOLE, "xla"),
    ("C_in 18, stride 2", (2, 8, 16, 18), (3, 3, 18, 64), 2, WHOLE, "xla"),
    ("C 128", (2, 8, 16, 128), (3, 3, 128, 128), 1, WHOLE, "xla"),
    ("1x1 kernel", (2, 8, 16, 64), (1, 1, 64, 64), 1, WHOLE, "xla"),
    ("W split", (2, 8, 16, 64), (3, 3, 64, 64), 1, W_SPLIT, "xla"),
    ("pallas backend", (1, 6, 8, 64), (3, 3, 64, 64), 1, WHOLE, "pallas"),
])
def test_other_convs_stay_unpacked(mesh, case, shape, wshape, s, sharding,
                                   backend):
    x = jax.random.normal(KEY, shape, jnp.float32)
    w = jax.random.normal(jax.random.PRNGKey(1), wshape) * 0.05

    def conv(x, w):
        return spatial_conv2d(x, w, strides=(s, s), sharding=sharding,
                              mesh=mesh, backend=backend)

    with mesh:
        seen = convs(conv, x, w)
        y = jax.jit(conv)(x, w)
    assert not any("conv_wpack" in path for path, _ in seen)
    assert all(shape[-1] == x.shape[-1] for _, shape in seen)
    assert_close(y, plain_conv(x, w, s))


@pytest.mark.parametrize("sharding", [WHOLE, H_SPLIT], ids=["whole", "h"])
@pytest.mark.parametrize("scope", ["local", "spatial", "global"])
def test_packed_batch_norm_equals_the_plain_one(mesh, sharding, scope):
    x = jax.random.normal(KEY, (2, 8, 12, 64)) * 3 + 1
    g = 1 + 0.1 * jax.random.normal(jax.random.PRNGKey(1), (64,))
    b = 0.1 * jax.random.normal(jax.random.PRNGKey(2), (64,))
    ct = jax.random.normal(jax.random.PRNGKey(3), x.shape)

    def loss(bn):
        return jax.value_and_grad(lambda *a: jnp.sum(bn(*a) * ct),
                                  argnums=(0, 1, 2))

    def packed(x, g, b):
        return batch_norm(x, g, b, sharding=sharding, mesh=mesh, scope=scope)

    def plain(x, g, b):
        return _batch_norm(x, g, b, sharding, mesh, scope, 1e-5, fold=1)

    with mesh:
        jaxpr = str(jax.make_jaxpr(packed)(x, g, b))
        got = jax.jit(loss(packed))(x, g, b)
        want = jax.jit(loss(plain))(x, g, b)
    assert "f32[2,8,6,128]" in jaxpr
    for a, r in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert_close(a, r)


@pytest.mark.parametrize("shape,sharding", [
    ((2, 8, 12, 32), WHOLE), ((2, 8, 11, 64), WHOLE),
    ((2, 8, 12, 64), W_SPLIT)], ids=["C 32", "odd W", "W split"])
def test_other_batch_norms_stay_unpacked(mesh, shape, sharding):
    x = jax.random.normal(KEY, shape)
    g, b = jnp.ones(shape[-1]), jnp.zeros(shape[-1])
    with mesh:
        jaxpr = str(jax.make_jaxpr(lambda x: batch_norm(
            x, g, b, sharding=sharding, mesh=mesh))(x))
    assert f",{2 * shape[-1]}]" not in jaxpr


def test_packed_conv_runs_at_the_fp32_policys_precision():
    x = jnp.ones((1, 4, 8, 64))
    w = jnp.ones((3, 3, 64, 64))

    def loss(x, w):
        return jnp.sum(spatial_conv2d(x, w, sharding=WHOLE) ** 2)

    with FP32.scope():
        text = jax.jit(jax.grad(loss, argnums=(0, 1))).lower(x, w).as_text()
    lines = [ln for ln in text.splitlines() if "stablehlo.convolution" in ln]
    assert len(lines) == 3
    for ln in lines:
        assert "128xf32>" in ln
        assert re.search(r"precision_config = \[#stablehlo<precision "
                         r"HIGHEST>, #stablehlo<precision HIGHEST>\]", ln)


def test_auditor_keeps_packed_interior_convs_in_conv_interior(mesh):
    """The packed conv's scope `conv_wpack` is no auditor region: the
    interior conv still reads `conv_interior`, the halo `halo_exchange`."""
    x = jax.random.normal(KEY, (2, 8, 16, 64), jnp.float32)
    w = jax.random.normal(jax.random.PRNGKey(1), (3, 3, 64, 64)) * 0.05

    def loss(x, w):
        return jnp.sum(spatial_conv2d(x, w, sharding=H_SPLIT,
                                      mesh=mesh) ** 2)

    closed = jax.make_jaxpr(jax.grad(loss, argnums=(0, 1)))(x, w)
    ops = collect_ops(closed, [])
    conv = [o for o in ops if o.kind == "conv_general_dilated"]
    assert conv and all("conv_wpack" in o.path for o in conv)
    regions = {(o.direction, o.region) for o in conv}
    assert {("fwd", "conv_interior"), ("bwd", "conv_interior"),
            ("fwd", "conv_boundary")} <= regions
    halos = [o for o in ops if o.kind == "ppermute"]
    assert halos and all(o.region == "halo_exchange" for o in halos)


def test_spatial_conv2d_on_two_devices_matches_one():
    """64-channel convs split by H (packed) and by W (plain) over a
    2-device CPU mesh (the `wpack` group of tests/dist_checks.py) equal the
    single-device conv in value and gradients."""
    run_dist_group("wpack")
