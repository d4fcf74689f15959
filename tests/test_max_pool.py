"""core.spatial_conv.spatial_pool on one device: the max pool and its
gradient against `lax.reduce_window`'s, ties included; the average pool
against the same pool written as stacked strided slices; and the
gradient's program, which must hold no gather and no scatter."""
import itertools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

from repro.core.spatial_conv import ConvSharding, spatial_pool
from repro.launch.mesh import make_mesh

CASES = [(k, s, h, w) for k, s in itertools.product((2, 3), (1, 2))
         for h, w in ((8, 8), (9, 7), (6, 11))]


def relu_with_zero_windows(shape, seed=0):
    """ReLU'd N(0,1) data with a block of zeros, so that some windows tie."""
    x = jnp.maximum(jax.random.normal(jax.random.PRNGKey(seed), shape), 0)
    return x.at[:, :4, :4].set(0.0)


def reference_max(x, k, s):
    return lax.reduce_window(x, -jnp.inf, lax.max, (1, k, k, 1),
                             (1, s, s, 1), "SAME")


def pool(x, k, s, kind="max"):
    return spatial_pool(x, window=(k, k), strides=(s, s),
                        sharding=ConvSharding(), kind=kind)


@pytest.mark.parametrize("k,s,h,w", CASES)
def test_max_pool_and_its_gradient_match_reduce_window(k, s, h, w):
    x = relu_with_zero_windows((2, h, w, 3))
    ref = reference_max(x, k, s)
    got = pool(x, k, s)
    assert got.shape == ref.shape
    np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))
    g = jax.random.normal(jax.random.PRNGKey(1), ref.shape)
    want = jax.vjp(lambda z: reference_max(z, k, s), x)[1](g)[0]
    dx = jax.jit(jax.grad(lambda z: jnp.sum(pool(z, k, s) * g)))(x)
    # the same cotangents reach the same inputs; where windows overlap
    # they may be summed in another order
    np.testing.assert_allclose(np.asarray(dx), np.asarray(want),
                               rtol=1e-6, atol=1e-6)


def test_tied_windows_send_the_cotangent_to_their_first_tap():
    x = jnp.zeros((1, 4, 4, 1))
    dx = jax.grad(lambda z: jnp.sum(pool(z, 3, 2)))(x)
    # windows start at rows/cols 0 and 2; 'SAME' pads after the end
    want = np.zeros((1, 4, 4, 1))
    want[0, ::2, ::2, 0] = 1
    np.testing.assert_array_equal(np.asarray(dx), want)


def stacked_avg(x, k, s):
    """The average pool as stacked strided slices, zero padded
    (count_include_pad=True)."""
    (lo_h, hi_h), (lo_w, hi_w) = lax.padtype_to_pads(
        x.shape[1:3], (k, k), (s, s), "SAME")
    x = jnp.pad(x, ((0, 0), (lo_h, hi_h), (lo_w, hi_w), (0, 0)))
    h_out = (x.shape[1] - k) // s + 1
    w_out = (x.shape[2] - k) // s + 1
    taps = [x[:, i:i + h_out * s:s, j:j + w_out * s:s, :]
            for i in range(k) for j in range(k)]
    return jnp.sum(jnp.stack(taps, axis=-1), axis=-1) / (k * k)


@pytest.mark.parametrize("k,s,h,w", CASES)
def test_avg_pool_and_its_gradient_match_stacked_slices(k, s, h, w):
    x = jax.random.normal(jax.random.PRNGKey(2), (2, h, w, 3))
    np.testing.assert_allclose(np.asarray(pool(x, k, s, "avg")),
                               np.asarray(stacked_avg(x, k, s)),
                               rtol=1e-6, atol=1e-6)
    dx = jax.grad(lambda z: jnp.sum(jnp.sin(pool(z, k, s, "avg"))))(x)
    want = jax.grad(lambda z: jnp.sum(jnp.sin(stacked_avg(z, k, s))))(x)
    np.testing.assert_allclose(np.asarray(dx), np.asarray(want),
                               rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("kind", ["max", "avg"])
@pytest.mark.parametrize("split", [False, True])
def test_the_gradient_has_no_gather_and_no_scatter(kind, split):
    """The pool's gradient reads its windows in place, unsplit and under
    the one-chip plan's H split alike: the max pool's through one
    `select_and_scatter`, the average pool's through a padded sum."""
    mesh = make_mesh(data=1, model=1)
    sh = (ConvSharding(batch_axes=("data",), h_axis="model") if split
          else ConvSharding())
    x = relu_with_zero_windows((2, 16, 16, 8))

    def loss(z):
        return jnp.sum(spatial_pool(z, sharding=sh, mesh=mesh, kind=kind))

    with mesh:
        text = jax.jit(jax.grad(loss)).lower(x).as_text()
    assert "stablehlo.gather" not in text
    assert "stablehlo.scatter" not in text
    assert text.count("stablehlo.select_and_scatter") == (kind == "max")
