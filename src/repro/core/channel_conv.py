"""Channel/filter-parallel convolution (paper §III-D) — the runtime.

The paper sketches partitioning the *hidden* dimensions of a conv layer: the
C input channels and the F filters (output channels).  This module makes
those distributions executable, as the convolution analogue of Megatron's
row/column-parallel linear layers:

  'channel' (row-parallel, the scheme the §V perf model costs):
      x enters C-sharded; each processor holds the C-rows of w for its
      channel block and convolves them against *all* F filters, producing a
      full-F partial sum; a reduce-scatter over the CF mesh axis completes
      the channel sum (Eq. 1's sum over c) and leaves y F-sharded.  The VJP
      of the reduce-scatter is the all-gather that hands backprop the full-F
      dL/dy it needs for the filter-gradient contraction (§III-D's
      allreduce, in its reduce-scatter/all-gather factorization).

  'filter' (column-parallel):
      x is all-gathered over the CF axis to full C; each processor convolves
      against its F-block of w, so y comes out F-sharded with no output
      collective.  Backprop reverses the all-gather into a psum on dL/dx.

Both modes consume C-sharded input and produce F-sharded output under the
*same* PartitionSpec, so consecutive CF layers chain with zero resharding —
layer i's F-shard IS layer i+1's C-shard — and a §III-C shuffle appears
exactly when the plan transitions between CF and sample/spatial layers.

CF x spatial composition (the 16x16-mesh unlock): a `CFSharding` may also
carry `h_axis`/`w_axis` on *different* mesh axes than `cf_axis`.  The halo
exchange on H/W and the CF collective then live inside ONE shard_map — the
Megatron-style composition of tensor-parallel collectives with another
parallel axis — with the §IV-A interior/boundary overlap split preserved on
the spatial dims (the halo ppermute is dataflow-independent of the interior
conv, so XLA's latency-hiding scheduler can run them concurrently).

Overlapped channel mode (§IV-A analogue for the hidden dimension): with
``overlap=True`` and ``channel_chunks > 1`` the local conv is split into
channel blocks and each block's partial sum is reduce-scattered as it
completes — the psum_scatter of block b pipelines with the convolution of
block b+1, which is what the perf model's η-scaled overlap credit charges
CF layers with.  The chunk count defaults from the *calibrated* achieved-
overlap efficiency η (see chunks_decision: 2 on TPU, 2 when a measured
η ≥ 0.5 says overlap actually pays, 1 otherwise); psum_scatter is linear,
so summing the scattered partials is numerically a reordering of the
single-collective channel sum.

Weights stay *globally* addressed (replicated into the shard_map, sliced
per-shard with `axis_index`): parameter trees, checkpoints and the FSDP
at-rest sharding are untouched, and autodiff reconstitutes the full dL/dw
through the slice-VJP + shard_map psum, which is the §V-A allreduce over the
processors sharing each (C, F) block.

BN under a CF distribution is embarrassingly parallel over channels (the
statistics are per-channel), so `cf_batch_norm` needs *zero* communication
at 'local'/'spatial' scope and a batch-axes-only psum at 'global' scope —
one of the paper's arguments for channel decompositions of late layers.

All functions replicate single-device convolution exactly (up to float
accumulation order), like their spatial counterparts.
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from repro.core import halo as halo_lib
from repro.core import trace as trace_lib
from repro.core.spatial_conv import (ConvSharding, _conv_nhwc, _local_conv,
                                     cast_to_weight_dtype, fit_spatial_axis,
                                     spatial_conv2d)
from repro.utils import same_pads, shard_map

MODES = ("channel", "filter")

# ---------------------------------------------------------------------------
# calibrated chunked-CF default (replaces PR 4's hard `1 off-TPU` paper-over)
# ---------------------------------------------------------------------------

# the measured achieved-overlap efficiency (Machine.overlap_eta), installed
# by core.calibrate whenever a calibration with live overlap samples runs or
# loads; None means "no measurement yet — assume nothing".
_MEASURED_ETA: float | None = None

# chunking must hide at least this fraction of the hideable min(comm,
# compute) to pay for its extra per-block collective launches and slices.
ETA_CHUNK_THRESHOLD = 0.5


def set_measured_eta(eta: float | None) -> None:
    """Install (or clear with None) the calibrated η that
    default_channel_chunks resolves against — called by core.calibrate
    after a fit or load that carries real overlap samples."""
    global _MEASURED_ETA
    _MEASURED_ETA = eta


def measured_eta() -> float | None:
    return _MEASURED_ETA


def chunks_decision() -> tuple[int, str]:
    """The calibrated 'channel'-mode chunk default, with its reason.

    Chunking pipelines the psum_scatter of block b with the conv of block
    b+1, which only pays when the machine demonstrably hides collectives
    behind compute: TPU's async collective engine does by construction;
    elsewhere chunking needs a *measured* η ≥ ETA_CHUNK_THRESHOLD.  With no
    calibration it stays off — PR 4 measured chunked CF as pure overhead on
    host XLA, and that evidence (not a hardcoded backend switch) is what
    this default now encodes."""
    if jax.default_backend() == "tpu":
        return 2, "tpu async collectives"
    if _MEASURED_ETA is None:
        return 1, "eta unmeasured"
    if _MEASURED_ETA >= ETA_CHUNK_THRESHOLD:
        return 2, f"measured eta {_MEASURED_ETA:.2f} >= {ETA_CHUNK_THRESHOLD}"
    return 1, f"measured eta {_MEASURED_ETA:.2f} < {ETA_CHUNK_THRESHOLD}"


def default_channel_chunks() -> int:
    return chunks_decision()[0]


@dataclasses.dataclass(frozen=True)
class CFSharding:
    """Distribution descriptor for a channel/filter-parallel conv layer.

    batch_axes: mesh axes sharding N (sample parallelism), as ConvSharding.
    cf_axis:    the mesh axis partitioning C of the input and F of the
                output (one axis — the §III-D group).
    mode:       'channel' (row-parallel, reduce-scatter on y — the perf
                model's costing) or 'filter' (column-parallel, all-gather
                on x).  The plan compiler picks per layer from the
                AG(x)-vs-RS(y) message sizes (core.plan).
    h_axis / w_axis: optional spatial sharding of H / W on *different* mesh
                axes than `cf_axis` (each may be a tuple forming a product
                axis, core.halo) — the CF x spatial composition: halo
                exchange and CF collective in one shard_map.
    """
    batch_axes: tuple[str, ...] = ()
    cf_axis: str | None = None
    mode: str = "channel"
    h_axis: str | tuple[str, ...] | None = None
    w_axis: str | tuple[str, ...] | None = None

    def __post_init__(self):
        if self.mode not in MODES:
            raise ValueError(f"CFSharding mode {self.mode!r} not in {MODES}")
        overlap_axes = {self.cf_axis} & set(self.spatial_axes)
        if overlap_axes:
            raise ValueError(
                f"CFSharding cf_axis {self.cf_axis!r} also shards a spatial "
                f"dim — the CF collective and the halo exchange must live "
                f"on different mesh axes")

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
        return self.h_axes + self.w_axes

    def x_spec(self) -> P:
        """NHWC placement: channels on the CF axis, N on the batch axes,
        H/W on the spatial axes when composed."""
        return P(self.batch_axes or None, self.h_axis, self.w_axis,
                 self.cf_axis)

    def fit(self, h: int, w: int, k: int, s: int, mesh) -> "CFSharding":
        """Apply the §III-A geometry fit to the composed spatial axes (the
        CF group is untouched); channel divisibility is validated at
        plan-compile time (core.plan demotes non-divisible layers and
        records it)."""
        if mesh is None or not self.is_spatial:
            return self
        shape = dict(mesh.shape)
        return dataclasses.replace(
            self,
            h_axis=fit_spatial_axis(h, self.h_axis, k, s, shape),
            w_axis=fit_spatial_axis(w, self.w_axis, k, s, shape))

    def fits_channels(self, c: int, f: int, mesh_shape) -> bool:
        if self.cf_axis is None:
            return True
        ways = dict(mesh_shape).get(self.cf_axis, 1)
        return c % ways == 0 and f % ways == 0


def _resolve_mesh(mesh):
    """`mesh`, else the ambient abstract mesh."""
    return mesh if mesh is not None else jax.sharding.get_abstract_mesh()


def _slice_block(v, axis_name: str, n_blocks: int, dim: int):
    """This shard's block of a replicated array, along `dim`."""
    size = v.shape[dim] // n_blocks
    return lax.dynamic_slice_in_dim(v, lax.axis_index(axis_name) * size,
                                    size, axis=dim)


def _conv_local_block(x, w, *, strides, sharding: CFSharding, mesh_shape,
                      overlap, backend):
    """Local conv of a (possibly spatially sharded) block with the already-
    sliced weights `w`: plain dense when nothing spatial is sharded, else
    the halo-exchange path of core.spatial_conv — including the §IV-A
    interior/boundary split — on the composed H/W axes."""
    if not sharding.is_spatial:
        k_h, k_w = w.shape[0], w.shape[1]
        return _conv_nhwc(x, w, strides,
                          (same_pads(k_h, strides[0]),
                           same_pads(k_w, strides[1])), backend)
    spatial_view = ConvSharding(h_axis=sharding.h_axis,
                                w_axis=sharding.w_axis)
    return _local_conv(x, w, strides=strides, sharding=spatial_view,
                       mesh_shape=mesh_shape, overlap=overlap,
                       backend=backend)


def _local_cf_conv(x, w, *, strides, sharding: CFSharding, mesh_shape,
                   overlap: bool = True, backend: str = "xla",
                   channel_chunks: int = 1):
    """Shard-local CF conv (runs inside shard_map).

    x: this shard's (n_local, H_local, W_local, C/p) channel block — the
       spatial extents are local too when the sharding composes CF with
       spatial axes.
    w: the full (K, K, C, F) weights (replicated into the shard_map).
    channel_chunks: 'channel'-mode §IV-A split granularity (see cf_conv2d).
    """
    ax = sharding.cf_axis
    p = mesh_shape[ax]

    if sharding.mode == "filter":
        # column-parallel: restore full C, convolve my F-block (with its
        # halo when spatial axes compose in).  y needs no collective; the
        # all-gather's VJP is the reduce-scatter completing dL/dx.
        with trace_lib.annotate("cf_all_gather"):
            xg = lax.all_gather(x, ax, axis=3, tiled=True)
        wp = _slice_block(w, ax, p, dim=3)
        return _conv_local_block(xg, wp, strides=strides, sharding=sharding,
                                 mesh_shape=mesh_shape, overlap=overlap,
                                 backend=backend)

    # row-parallel: my C-rows of w against all F filters, then the
    # reduce-scatter that completes the channel sum and leaves y F-sharded.
    wp = _slice_block(w, ax, p, dim=2)
    c_loc = x.shape[3]
    n_blk = channel_chunks if overlap and not sharding.is_spatial else 1
    n_blk = max(1, min(n_blk, c_loc))
    if n_blk <= 1:
        # single-collective path.  Under CF x spatial composition the
        # §IV-A overlap comes from the interior/boundary split inside
        # _conv_local_block — chunking the channels on top would repeat
        # the halo exchange per block, paying its latency twice.
        partial = _conv_local_block(x, wp, strides=strides,
                                    sharding=sharding,
                                    mesh_shape=mesh_shape, overlap=overlap,
                                    backend=backend)
        with trace_lib.annotate("cf_reduce_scatter"):
            return lax.psum_scatter(partial, ax, scatter_dimension=3,
                                    tiled=True)

    # overlapped channel mode (§IV-A analogue): convolve per channel block
    # and reduce-scatter each partial as it completes, so the collective of
    # block b pipelines with the compute of block b+1.  psum_scatter is
    # linear, so the summed scattered partials equal the single-collective
    # channel sum up to float reassociation.
    bounds = [round(i * c_loc / n_blk) for i in range(n_blk + 1)]
    y = None
    for lo, hi in zip(bounds, bounds[1:]):
        if hi <= lo:
            continue
        partial = _conv_local_block(
            lax.slice_in_dim(x, lo, hi, axis=3),
            lax.slice_in_dim(wp, lo, hi, axis=2),
            strides=strides, sharding=sharding, mesh_shape=mesh_shape,
            overlap=overlap, backend=backend)
        with trace_lib.annotate("cf_reduce_scatter"):
            scat = lax.psum_scatter(partial, ax, scatter_dimension=3,
                                    tiled=True)
        y = scat if y is None else y + scat
    return y


def cf_conv2d(x, w, *, strides=(1, 1), sharding: CFSharding, mesh=None,
              overlap: bool = True, backend: str = "xla",
              channel_chunks: int | None = None):
    """'SAME'-padded strided conv2d under channel/filter parallelism,
    optionally composed with spatial parallelism on different mesh axes.

    x: (N, H, W, C) global array, C sharded on `sharding.cf_axis` (N on
       the batch axes, H/W on the spatial axes when composed) under jit.
    w: (K_h, K_w, C, F) weights, globally addressed (replicated into the
       shard, sliced per-processor — FSDP owns the at-rest layout).
    overlap: enables the §IV-A-style splits that make communication
       independent of interior compute in dataflow: the interior/boundary
       split on composed spatial dims, and in 'channel' mode the
       channel-block split that pipelines the psum_scatter with the local
       conv (see _local_cf_conv).
    channel_chunks: 'channel'-mode block count for that split.  None (the
       default) resolves through chunks_decision(): 2 on TPU — where the
       latency-hiding scheduler actually runs the scattered partial of
       block b under the conv of block b+1 — 2 when core.calibrate has
       measured an achieved-overlap η ≥ ETA_CHUNK_THRESHOLD on this mesh,
       and 1 otherwise (with no evidence that collectives hide behind
       compute, extra collectives are pure overhead — measured so in
       benchmarks/strategy_exec).  Tests pass an explicit 2 to pin the
       chunked path's numerics on any backend.
    backend: 'xla' or 'pallas' — the local conv kernel (see _conv_nhwc).
    """
    x = cast_to_weight_dtype(x, w)   # the repo-wide mixed-precision rule
    mesh = _resolve_mesh(mesh)
    mesh_shape = dict(mesh.shape) if mesh is not None else {}
    p = mesh_shape.get(sharding.cf_axis, 1) if sharding.cf_axis else 1
    k_h, k_w = w.shape[0], w.shape[1]
    if p <= 1:
        if sharding.is_spatial:
            # a size-1 CF group with live spatial axes is just spatial
            # parallelism — route to the halo-exchange runtime.
            return spatial_conv2d(
                x, w, strides=strides,
                sharding=ConvSharding(batch_axes=sharding.batch_axes,
                                      h_axis=sharding.h_axis,
                                      w_axis=sharding.w_axis),
                mesh=mesh, overlap=overlap, backend=backend)
        # dense fallback — the 1x1-mesh oracle path, bitwise-identical.
        return _conv_nhwc(x, w, strides,
                          (same_pads(k_h, strides[0]),
                           same_pads(k_w, strides[1])), backend)
    c, f = w.shape[2], w.shape[3]
    if c % p or f % p:
        # hard error, not an assert: under `python -O` a stripped assert
        # would let _slice_block truncate the channel sum silently
        raise ValueError(
            f"channels C={c}, F={f} not divisible by {p}-way CF axis "
            f"{sharding.cf_axis!r} — core.plan demotes such layers at "
            "compile time; direct callers must pre-check "
            "CFSharding.fits_channels")
    if channel_chunks is None:
        channel_chunks = default_channel_chunks()
    fn = functools.partial(_local_cf_conv, strides=strides,
                           sharding=sharding, mesh_shape=mesh_shape,
                           overlap=overlap, backend=backend,
                           channel_chunks=channel_chunks)
    spec = sharding.x_spec()
    return shard_map(fn, mesh=mesh, in_specs=(spec, P()), out_specs=spec)(x, w)


def cf_bias_add(x, b, *, sharding: CFSharding, mesh=None):
    """Add a per-channel bias to a C-sharded NHWC tensor (b stays global)."""
    mesh = _resolve_mesh(mesh)
    mesh_shape = dict(mesh.shape) if mesh is not None else {}
    p = mesh_shape.get(sharding.cf_axis, 1) if sharding.cf_axis else 1
    if p <= 1:
        return x + b
    spec = sharding.x_spec()

    def fn(x, b):
        return x + _slice_block(b, sharding.cf_axis, p, dim=0)

    return shard_map(fn, mesh=mesh, in_specs=(spec, P()),
                     out_specs=spec)(x, b)


def cf_batch_norm(x, gamma, beta, *, sharding: CFSharding, mesh=None,
                  scope: str = "local", eps: float = 1e-5):
    """BN over (N, H, W) of a C-sharded NHWC tensor.

    Per-channel statistics never cross the CF axis (each channel lives on
    exactly one shard of the CF group), so with no composed spatial axes
    'local' and 'spatial' scopes are communication-free and 'global' psums
    the moments over the batch axes only.  Under CF x spatial composition a
    channel's rows DO cross the spatial axes, so 'spatial'/'global' scopes
    psum over them too — same aggregation as core.spatial_norm.  gamma/beta
    stay globally addressed, sliced per shard like the conv weights.
    """
    if scope not in ("local", "spatial", "global"):
        raise ValueError(f"unknown BN scope {scope!r}")
    mesh = _resolve_mesh(mesh)
    mesh_shape = dict(mesh.shape) if mesh is not None else {}
    p = mesh_shape.get(sharding.cf_axis, 1) if sharding.cf_axis else 1
    stat_axes = ()
    if scope in ("spatial", "global"):
        stat_axes += sharding.spatial_axes
    if scope == "global":
        stat_axes += tuple(sharding.batch_axes or ())
    comm_axes = tuple(a for a in stat_axes if mesh_shape.get(a, 1) > 1)
    if p <= 1 and not comm_axes:
        # dense fallback, formulated exactly like core.spatial_norm's local
        # path so the 1x1-mesh numerics are bitwise-identical
        xf = x.astype(jnp.float32)
        n = x.shape[0] * x.shape[1] * x.shape[2]
        mean = jnp.sum(xf, (0, 1, 2)) / n
        var = jnp.sum(jnp.square(xf), (0, 1, 2)) / n - jnp.square(mean)
        inv = lax.rsqrt(var + eps)
        y = (x - mean.astype(x.dtype)) * inv.astype(x.dtype)
        return y * gamma + beta

    def fn(x, g, b):
        xf = x.astype(jnp.float32)
        s = jnp.sum(xf, (0, 1, 2))
        ss = jnp.sum(jnp.square(xf), (0, 1, 2))
        n = x.shape[0] * x.shape[1] * x.shape[2]
        if comm_axes:
            with trace_lib.annotate("bn_collective"):
                s = lax.psum(s, comm_axes)
                ss = lax.psum(ss, comm_axes)
            for a in comm_axes:
                n *= mesh_shape[a]
        mean = s / n
        var = ss / n - jnp.square(mean)
        inv = lax.rsqrt(var + eps)
        y = (x - mean.astype(x.dtype)) * inv.astype(x.dtype)
        if p > 1:
            g = _slice_block(g, sharding.cf_axis, p, dim=0)
            b = _slice_block(b, sharding.cf_axis, p, dim=0)
        return y * g + b

    spec = sharding.x_spec()
    return shard_map(fn, mesh=mesh, in_specs=(spec, P(), P()),
                     out_specs=spec)(x, gamma, beta)
