"""Distributed-memory convolution with spatial decomposition (paper §III).

The input tensor (NHWC) is block-partitioned: N over the data axes (sample
parallelism), H — and optionally W — over mesh axes (spatial parallelism).
Each of H and W may be split over a *tuple* of mesh axes treated as one
product axis (core.halo's linearized-index convention) — the decomposition
16x16 meshes need when a single torus dimension is not enough ways.
Forward convolution needs a stencil halo of the neighbor shards' boundary
rows (paper Eq. 1 with restricted index sets); the halo exchange lowers to
``collective-permute`` on the TPU ICI torus.

Backpropagation is obtained by autodiff *through* the shard-local program:
the VJP of ``ppermute`` is the inverted ``ppermute``, so dL/dx receives
exactly the paper's halo exchange on dL/dy (Eq. 3) plus boundary-gradient
accumulation, and dL/dw is the local contraction (Eq. 2) completed by the
``psum`` that shard_map inserts for the replicated-weight cotangent — i.e.
the paper's allreduce.

Overlap (paper §IV-A): with ``overlap=True`` the local conv is split into an
interior block that depends only on local data and two boundary blocks that
consume the halo.  This makes the halo exchange and the interior convolution
*independent in dataflow*, which is what allows XLA's latency-hiding
scheduler to run the collective-permute concurrently with the interior conv
on TPU (the JAX analogue of the paper's separate cuDNN calls on interior and
boundary domains).  The same split in the transposed program hides the
dL/dx halo under the dL/dw contraction, which needs no halo (§IV-A).

All functions replicate single-device convolution exactly (up to float
accumulation order), as the paper requires.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Mapping

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from repro.core import halo as halo_lib
from repro.core import trace as trace_lib
from repro.utils import cdiv, same_pads, shard_map

DIMNUMS = ("NHWC", "HWIO", "NHWC")


def cast_to_weight_dtype(x, w):
    """The repo-wide mixed-precision rule for conv layers: compute in the
    *weight* dtype.  Both conv runtimes (spatial_conv2d, channel_conv's
    cf_conv2d) apply this same rule, so a mixed sample/spatial/CF plan can
    never change numerics at a reshard boundary — every layer sees x in
    params' dtype regardless of which decomposition executes it."""
    return x.astype(w.dtype) if x.dtype != w.dtype else x


def fit_spatial_axis(size: int, axis, k: int, s: int,
                     mesh_shape: Mapping[str, int]):
    """The §III-A geometry test for one (possibly product) spatial axis:
    keep it only when every shard divides evenly, stays stride-aligned, and
    is at least kernel-sized; else None (the layer's spatial split demotes
    and the distribution change becomes a §III-C shuffle)."""
    if axis is None:
        return None
    m = halo_lib.product_size(axis, mesh_shape)
    good = size % m == 0 and (size // m) % s == 0 and size // m >= max(k, s)
    return axis if good else None


@dataclasses.dataclass(frozen=True)
class ConvSharding:
    """Distribution descriptor for a conv/pool layer (paper's D).

    batch_axes: mesh axes sharding N (sample parallelism).
    h_axis / w_axis: the mesh axis — or *tuple* of mesh axes forming one
        product axis (16x16-mesh splits, core.halo) — sharding H / W
        (spatial parallelism), or None.
    """
    batch_axes: tuple[str, ...] = ()
    h_axis: str | tuple[str, ...] | None = None
    w_axis: str | tuple[str, ...] | None = None

    @property
    def is_spatial(self) -> bool:
        return self.h_axis is not None or self.w_axis is not None

    @property
    def h_axes(self) -> tuple[str, ...]:
        return halo_lib.axes_tuple(self.h_axis)

    @property
    def w_axes(self) -> tuple[str, ...]:
        return halo_lib.axes_tuple(self.w_axis)

    @property
    def spatial_axes(self) -> tuple[str, ...]:
        """All mesh axes sharding H or W, flattened (BN psums, pooling)."""
        return self.h_axes + self.w_axes

    def x_spec(self) -> P:
        return P(self.batch_axes or None, self.h_axis, self.w_axis, None)

    def fit(self, h: int, w: int, k: int, s: int, mesh) -> "ConvSharding":
        """Drop spatial axes that this layer's geometry cannot support —
        the paper's 'spatial dimension ~ kernel size' edge case (§III-A):
        the layer falls back to sample parallelism and the distribution
        change between layers becomes a §III-C shuffle (resharding)."""
        if mesh is None or not self.is_spatial:
            return self
        shape = dict(mesh.shape)
        return dataclasses.replace(
            self, h_axis=fit_spatial_axis(h, self.h_axis, k, s, shape),
            w_axis=fit_spatial_axis(w, self.w_axis, k, s, shape))


#: Channel width whose activations run W-pair packed: two adjacent W
#: columns of 64 channels fill the 128 lanes of the TPU's MXU and vregs.
WPACK_C = 64


def wpack_applies(x, sharding: ConvSharding) -> bool:
    """Whether NHWC activation `x` runs W-pair packed: 64 channels, an even
    W, and W not split over the mesh.  Shapes and the layout alone decide,
    so a conv, the BN after it and the conv reading it all agree, and the
    reshapes between them cancel in the compiled step."""
    return (x.shape[-1] == WPACK_C and x.shape[2] % 2 == 0
            and sharding.w_axis is None)


def wpack(x):
    """(N, H, W, C) -> (N, H, W/2, 2C): column 2m + a of x is packed
    column m, channels [a*C, (a+1)*C)."""
    n, h, w, c = x.shape
    return x.reshape(n, h, w // 2, 2 * c)


def wunpack(x):
    """The inverse of `wpack`."""
    n, h, w, c = x.shape
    return x.reshape(n, h, 2 * w, c // 2)


def _wpack_weights(w, stride_w: int):
    """3-wide conv weights (K_h, 3, C, F) for a W-pair packed input.

    stride_w 1: (K_h, 3, 2C, 2F), packed output, 'SAME' in packed columns:
    ``Wp[kh, t + 1, a*C + ci, b*F + co] = w[kh, 2t + a - b + 1, ci, co]``
    for t in {-1, 0, 1} where the tap is in [0, 2], else zero (half the
    blocks).  stride_w 2: (K_h, 2, 2C, F), stride 1 over packed columns,
    output j reading columns 2j, 2j + 1, 2j + 2 (SAME's (0, 1) padding):
    ``Wp[kh, t, a*C + ci, co] = w[kh, 2t + a, ci, co]``.  Built from `w`
    inside the traced step, so `w` gets its gradient through it.
    """
    zero = jnp.zeros_like(w[:, 0])

    def tap(kw):
        return w[:, kw] if 0 <= kw <= 2 else zero

    if stride_w == 2:
        return jnp.stack([jnp.concatenate([tap(2 * t + a) for a in (0, 1)],
                                          axis=-2) for t in (0, 1)], axis=1)
    return jnp.stack([
        jnp.concatenate([
            jnp.concatenate([tap(2 * t + a - b + 1) for b in (0, 1)], axis=-1)
            for a in (0, 1)], axis=-2)
        for t in (-1, 0, 1)], axis=1)


def _conv_nhwc(x, w, strides, pads, backend: str = "xla",
               interior_first: bool = False):
    """Local dense conv — the per-shard compute the paper times as cuDNN.

    backend='pallas' routes through the implicit-GEMM MXU kernel
    (repro.kernels.conv2d).  That kernel computes VALID convolution with one
    stride for both spatial dims, so padding is materialized first and
    unequal strides are an error.  Off-TPU it runs in interpret mode
    (numerics-identical, for tests and CPU smoke runs).  `interior_first`
    asks the Pallas kernel for its §IV-A schedule (boundary row blocks
    visited last); the XLA route ignores it.
    """
    if backend == "pallas":
        if strides[0] != strides[1]:
            raise ValueError(f"backend='pallas' needs equal strides, got "
                             f"{tuple(strides)}")
        from repro.kernels.conv2d import conv2d as pallas_conv2d
        xp = jnp.pad(x, ((0, 0), pads[0], pads[1], (0, 0)))
        return pallas_conv2d(xp, w, stride=strides[0],
                             interpret=jax.default_backend() != "tpu",
                             interior_first=interior_first)
    return lax.conv_general_dilated(
        x, w, window_strides=tuple(strides), padding=tuple(pads),
        dimension_numbers=DIMNUMS)


def _split_dim_conv(x, w, *, dim, s, k, lo, hi, axis_name, axis_size,
                    other_pads, stride_other, overlap, backend="xla"):
    """Conv along one sharded spatial `dim` (1=H or 2=W) of local block x.

    `other_pads`/`stride_other` apply to the other (unsharded) spatial dim.
    `axis_name` may be a tuple of mesh axes forming one product axis of
    total size `axis_size` (core.halo's linearized-index convention).
    Returns the local output block for this shard.
    """
    hl = x.shape[dim]
    assert hl % s == 0, f"local extent {hl} not divisible by stride {s}"
    assert hl >= k, (
        "spatial shard smaller than the kernel — the paper notes this edge "
        "case; use sample/channel parallelism for this layer instead")
    ho = hl // s

    def conv(z, pad_dim, interior_first=False):
        pads = [(0, 0), (0, 0)]
        pads[dim - 1] = pad_dim
        pads[2 - dim] = other_pads
        strides = [0, 0]
        strides[dim - 1] = s
        strides[2 - dim] = stride_other
        return _conv_nhwc(z, w, tuple(strides), tuple(pads), backend,
                          interior_first)

    if lo == 0 and hi == 0:
        return conv(x, (0, 0))

    # issue the halo transfers up front (§IV-A): every compute op below is
    # built AFTER the ppermutes, so the transfers head the dataflow graph.
    sched = halo_lib.HaloSchedule(x, dim, lo, hi, axis_name, axis_size)
    halo_lo, halo_hi = sched.lo, sched.hi

    if not overlap:
        parts = [p for p in (halo_lo, x, halo_hi) if p is not None]
        with trace_lib.annotate("conv_serialized"):
            return conv(lax.concatenate(parts, dimension=dim), (0, 0))

    # --- interior/boundary latency-hiding schedule (paper §IV-A) ---
    t_lo = cdiv(lo, s)                       # output rows needing the lo halo
    i_hi = cdiv(hl + lo - k + 1, s)          # first output row needing hi halo
    t_hi = ho - i_hi
    if t_lo + t_hi >= ho:                    # shard too small to split
        # no XLA-level split possible; when the halo rides along H the
        # Pallas kernel can still run its own interior-first block order.
        parts = [p for p in (halo_lo, x, halo_hi) if p is not None]
        with trace_lib.annotate("conv_serialized"):
            return conv(lax.concatenate(parts, dimension=dim), (0, 0),
                        interior_first=(dim == 1))

    # interior first: rows [t_lo, i_hi) read input [t_lo*s - lo,
    # (i_hi-1)s - lo + k) — no halo dependence, so this conv runs while the
    # transfers are in flight.  pin() then barriers the halos behind the
    # interior result, so the boundary convs cannot be hoisted above it
    # (nor the transfers sunk below it) by the compiler.
    inner_in = lax.slice_in_dim(
        x, t_lo * s - lo, (i_hi - 1) * s - lo + k, axis=dim)
    with trace_lib.annotate("conv_interior"):
        interior = conv(inner_in, (0, 0))
    interior, halo_lo, halo_hi = sched.pin(interior)

    blocks = []
    with trace_lib.annotate("conv_boundary"):
        if t_lo > 0:
            # top boundary: rows [0, t_lo) read input
            # [-lo, (t_lo-1)s - lo + k)
            top_in = lax.concatenate(
                [halo_lo,
                 lax.slice_in_dim(x, 0, (t_lo - 1) * s - lo + k, axis=dim)],
                dimension=dim)
            blocks.append(conv(top_in, (0, 0)))
        blocks.append(interior)
        if t_hi > 0:
            bot_in = lax.slice_in_dim(x, i_hi * s - lo, hl, axis=dim)
            bot_in = lax.concatenate([bot_in, halo_hi], dimension=dim)
            blocks.append(conv(bot_in, (0, 0)))
    return lax.concatenate(blocks, dimension=dim) if len(blocks) > 1 \
        else blocks[0]


def _local_conv(x, w, *, strides, sharding: ConvSharding, mesh_shape,
                overlap: bool, backend: str = "xla"):
    """Shard-local forward conv (runs inside shard_map)."""
    k_h, k_w = w.shape[0], w.shape[1]
    s_h, s_w = strides
    ph = same_pads(k_h, s_h)
    pw = same_pads(k_w, s_w)

    if sharding.h_axis is not None and sharding.w_axis is not None:
        # shard H first (halo on H incl. full local W), then W.
        x = halo_lib.halo_exchange(
            x, 1, ph[0], ph[1], sharding.h_axis,
            halo_lib.product_size(sharding.h_axis, mesh_shape))
        return _split_dim_conv(
            x, w, dim=2, s=s_w, k=k_w, lo=pw[0], hi=pw[1],
            axis_name=sharding.w_axis,
            axis_size=halo_lib.product_size(sharding.w_axis, mesh_shape),
            other_pads=(0, 0), stride_other=s_h, overlap=overlap,
            backend=backend)
    if sharding.h_axis is not None:
        return _split_dim_conv(
            x, w, dim=1, s=s_h, k=k_h, lo=ph[0], hi=ph[1],
            axis_name=sharding.h_axis,
            axis_size=halo_lib.product_size(sharding.h_axis, mesh_shape),
            other_pads=pw, stride_other=s_w, overlap=overlap,
            backend=backend)
    if sharding.w_axis is not None:
        return _split_dim_conv(
            x, w, dim=2, s=s_w, k=k_w, lo=pw[0], hi=pw[1],
            axis_name=sharding.w_axis,
            axis_size=halo_lib.product_size(sharding.w_axis, mesh_shape),
            other_pads=ph, stride_other=s_h, overlap=overlap,
            backend=backend)
    raise AssertionError("not spatial")


def spatial_conv2d(x, w, *, strides=(1, 1), sharding: ConvSharding,
                   mesh=None, overlap: bool = True, backend: str = "xla"):
    """'SAME'-padded strided conv2d under hybrid sample/spatial parallelism.

    x: (N, H, W, C) global array (sharded per `sharding` under jit).
    w: (K_h, K_w, C, F) weights, replicated across the spatial/batch axes
       (FSDP resharding at the shard_map boundary gathers them if needed).
    backend: 'xla' (default) or 'pallas' — which kernel runs the local conv
       each shard computes after its halo exchange (see _conv_nhwc).

    On the XLA route a 3-wide conv of a 64-channel input whose W is even
    and unsplit runs W-pair packed (`wpack_applies`, `_wpack_weights`):
    the same conv, halo exchange and interior/boundary schedule, on the
    packed array.
    """
    x = cast_to_weight_dtype(x, w)   # the repo-wide mixed-precision rule
    if (backend == "xla" and w.shape[1] == 3 and strides[1] in (1, 2)
            and wpack_applies(x, sharding)):
        with jax.named_scope("conv_wpack"):
            y = _spatial_conv2d(wpack(x), _wpack_weights(w, strides[1]),
                                (strides[0], 1), sharding, mesh, overlap,
                                backend)
        return wunpack(y) if strides[1] == 1 else y
    return _spatial_conv2d(x, w, strides, sharding, mesh, overlap, backend)


def _spatial_conv2d(x, w, strides, sharding, mesh, overlap, backend):
    if not sharding.is_spatial:
        # pure sample parallelism: local conv, XLA batches it (paper Fig 1a).
        k_h, k_w = w.shape[0], w.shape[1]
        y = _conv_nhwc(x, w, strides,
                       (same_pads(k_h, strides[0]),
                        same_pads(k_w, strides[1])), backend)
        if mesh is not None:
            y = lax.with_sharding_constraint(
                y, jax.sharding.NamedSharding(mesh, sharding.x_spec()))
        return y

    mesh = mesh or jax.sharding.get_abstract_mesh()
    mesh_shape = dict(mesh.shape)
    fn = functools.partial(_local_conv, strides=strides, sharding=sharding,
                           mesh_shape=mesh_shape, overlap=overlap,
                           backend=backend)
    spec = sharding.x_spec()
    return shard_map(fn, mesh=mesh, in_specs=(spec, P()), out_specs=spec)(x, w)


# ---------------------------------------------------------------------------
# Pooling under spatial decomposition (paper §III-B: "parallelized similarly")
# ---------------------------------------------------------------------------

def _local_pool(x, *, window, strides, sharding: ConvSharding, mesh_shape,
                kind: str):
    k_h, k_w = window
    s_h, s_w = strides
    ph = same_pads(k_h, s_h)
    pw = same_pads(k_w, s_w)
    edge = float("-inf") if kind == "max" else 0.0

    pads = [(0, 0), ph, pw, (0, 0)]
    if sharding.h_axis is not None:
        x = halo_lib.halo_exchange(
            x, 1, ph[0], ph[1], sharding.h_axis,
            halo_lib.product_size(sharding.h_axis, mesh_shape),
            edge_value=edge)
        pads[1] = (0, 0)
    if sharding.w_axis is not None:
        x = halo_lib.halo_exchange(
            x, 2, pw[0], pw[1], sharding.w_axis,
            halo_lib.product_size(sharding.w_axis, mesh_shape),
            edge_value=edge)
        pads[2] = (0, 0)
    return _pool_windows(x, window, strides, tuple(pads), kind)


def _pool_windows(x, window, strides, pads, kind):
    """Pool the local block padded by `pads` (-inf for max, zeros for avg)
    with `lax.reduce_window`, which reads each window in place.  Its
    gradient is XLA's `select_and_scatter` for max, which routes each
    window's cotangent to its first tap equal to the max, and a padded
    `reduce_window` sum for avg: neither gathers the window taps."""
    dims, steps = (1, *window, 1), (1, *strides, 1)
    if kind == "max":
        return lax.reduce_window(x, -jnp.inf, lax.max, dims, steps, pads)
    y = lax.reduce_window(x, 0.0, lax.add, dims, steps, pads)
    return y / (window[0] * window[1])


def spatial_pool(x, *, window=(3, 3), strides=(2, 2),
                 sharding: ConvSharding, mesh=None, kind: str = "max"):
    """'SAME' max/avg pool under the same decomposition as spatial_conv2d.

    Max pooling fills the *global-edge* halo with -inf (not the zeros that
    ppermute produces) so edge windows match single-device 'SAME' semantics.
    Avg pooling uses count_include_pad=True (zero pad), matching the oracle in
    models/cnn/layers.py.  Every op of the pool, its gradient's included,
    carries the `max_pool` (or `avg_pool`) scope in its HLO `op_name`.
    """
    with jax.named_scope(f"{kind}_pool"):
        if not sharding.is_spatial:
            pads = lax.padtype_to_pads(x.shape[1:3], window, strides, "SAME")
            return _pool_windows(x, window, strides,
                                 ((0, 0), *pads, (0, 0)), kind)

        mesh = mesh or jax.sharding.get_abstract_mesh()
        fn = functools.partial(_local_pool, window=window, strides=strides,
                               sharding=sharding,
                               mesh_shape=dict(mesh.shape), kind=kind)
        spec = sharding.x_spec()
        return shard_map(fn, mesh=mesh, in_specs=(spec,),
                         out_specs=spec)(x)
