"""Batch normalization under spatial decomposition (paper §III-B).

The paper: "Both purely local batch normalization and a variant that
aggregates over the spatial distribution of a sample are easy to implement."
We provide three statistics scopes:

  'local'   per-shard statistics (the paper's default; zero communication)
  'spatial' aggregate over the spatial shards of a sample (psum over the
            model axis) — the paper's proposed variant
  'global'  aggregate over all batch+spatial shards (true global BN)

All scopes share parameters (gamma/beta replicated).  Training-mode only
(running statistics are maintained by the train loop state).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from repro.core import trace as trace_lib
from repro.core.spatial_conv import (ConvSharding, wpack, wpack_applies,
                                     wunpack)
from repro.utils import shard_map


def _stats(x, axes, fold: int = 1):
    """Per-channel sum, sum of squares and count over `axes`; with `fold`
    2, x is W-pair packed (core.spatial_conv.wpack) and each channel's two
    packed halves are summed into one."""
    n = fold
    for a in axes:
        n *= x.shape[a]
    s = jnp.sum(x, axes)
    ss = jnp.sum(jnp.square(x), axes)
    if fold > 1:
        s = s.reshape(fold, -1).sum(0)
        ss = ss.reshape(fold, -1).sum(0)
    return s, ss, n


def batch_norm(x, gamma, beta, *, sharding: ConvSharding, mesh=None,
               scope: str = "local", eps: float = 1e-5):
    """BN over (N, H, W) of an NHWC tensor with the given statistics scope.

    A 64-channel x that the convs around it run W-pair packed
    (core.spatial_conv.wpack_applies) is normalized packed too, so the
    step carries one packed array from conv through BN and ReLU to the
    next conv: statistics fold the two packed halves of each channel."""
    if wpack_applies(x, sharding):
        return wunpack(_batch_norm(wpack(x), gamma, beta, sharding, mesh,
                                   scope, eps, fold=2))
    return _batch_norm(x, gamma, beta, sharding, mesh, scope, eps, fold=1)


def _batch_norm(x, gamma, beta, sharding, mesh, scope, eps, fold):
    reduce_axes = (0, 1, 2)

    def normalize(x, mean, var):
        inv = lax.rsqrt(var + eps)
        return ((x - jnp.tile(mean, fold).astype(x.dtype))
                * jnp.tile(inv, fold).astype(x.dtype))

    def affine(y):
        return y * jnp.tile(gamma, fold) + jnp.tile(beta, fold)

    if scope == "local" or not sharding.is_spatial:
        def local_fn(x):
            s, ss, n = _stats(x.astype(jnp.float32), reduce_axes, fold)
            mean = s / n
            return normalize(x, mean, ss / n - jnp.square(mean))
        if scope == "local" and sharding.is_spatial and mesh is not None:
            spec = sharding.x_spec()
            y = shard_map(local_fn, mesh=mesh, in_specs=(spec,),
                          out_specs=spec)(x)
        else:
            y = local_fn(x)
        return affine(y)

    comm_axes: tuple[str, ...]
    if scope == "spatial":
        comm_axes = sharding.spatial_axes
    elif scope == "global":
        comm_axes = tuple(sharding.batch_axes or ()) + sharding.spatial_axes
    else:
        raise ValueError(f"unknown BN scope {scope!r}")

    mesh = mesh or jax.sharding.get_abstract_mesh()

    def fn(x):
        s, ss, n = _stats(x.astype(jnp.float32), reduce_axes, fold)
        with trace_lib.annotate("bn_collective"):
            s = lax.psum(s, comm_axes)
            ss = lax.psum(ss, comm_axes)
        n = n * functools.reduce(
            lambda a, b: a * b, (dict(mesh.shape)[ax] for ax in comm_axes), 1)
        mean = s / n
        return normalize(x, mean, ss / n - jnp.square(mean))

    spec = sharding.x_spec()
    y = shard_map(fn, mesh=mesh, in_specs=(spec,), out_specs=spec)(x)
    return affine(y)
