"""Measured-cost calibration — closing the paper's §V feedback loop.

The paper's performance model is fed by *measured* primitive costs: the
authors time cuDNN kernels and MPI collectives on the target machine and
only then trust the model to rank distributions.  This module is that loop
for the live jax backend:

  1. microbenchmark the local convolution at every shard shape the strategy
     optimizer's candidate distributions would produce for the network at
     hand (forward, and the BPx data-conv shape when it differs) — these
     fill a per-shape `EmpiricalTable`, the model's first-choice lookup;
  2. microbenchmark the communication primitives at the message sizes the
     plan compiler will emit: the p2p halo exchange (one `ppermute` ring
     step — the §III-A stencil pattern) and the ring collectives
     (all-reduce / reduce-scatter / all-gather) on each mesh axis;
  3. fit the `Machine` constants from those samples: α/β for p2p and for
     the collective fabric (least squares on the linear α-β model, §II-B),
     achieved peak FLOP/s, memory bandwidth, and the compute-efficiency /
     half-performance-work pair that shapes the analytic fallback for
     table-miss shapes.

The result round-trips through JSON (`BENCH_calibration.json`) so a
calibration can be produced once (CI's bench lane, a TPU reservation) and
consumed later: `train.py --calibrate[=path]` solves `--strategy auto` on
the measured costs, and `benchmarks/strategy_exec.py` cross-checks the
calibrated predictions against measured step times.

Everything downstream already speaks the table dialect: `strategy.solve_line
/ solve_dag`, `plan.plan_line / plan_graph` and `perfmodel.network_cost`
accept `table=`; missing shapes fall back to the analytic roofline, so a
partial calibration degrades gracefully instead of failing.
"""
from __future__ import annotations

import dataclasses
import functools
import json
import os
from typing import Callable, Mapping, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core import channel_conv
from repro.core.perfmodel import (LAUNCH_OVERHEAD, SHUFFLE_KIND, ConvLayer,
                                  EmpiricalTable, Machine, _halo_time,
                                  all_to_all_time, reduce_scatter_time,
                                  shuffle_block_bytes)
from repro.core.plan import executable_candidates
from repro.utils import same_pads, shard_map, time_fn

SCHEMA = "repro/calibration@1"
DEFAULT_PATH = "BENCH_calibration.json"

# starting point for constants a single-device calibration cannot fit:
# loopback-ish host comm (shared memory), overwritten whenever the mesh has
# a >1 axis to measure on.
HOST_BASE = Machine("host-base", peak_flops=1e11, mem_bw=20e9,
                    alpha=5e-6, beta=1 / 10.0e9,
                    alpha_coll=8e-6, beta_coll=1 / 10.0e9, wordsize=4,
                    compute_efficiency=1.0)


# ---------------------------------------------------------------------------
# device memory capacity + the model-vs-XLA memory cross-check
# ---------------------------------------------------------------------------

@functools.lru_cache(maxsize=None)
def _detect_mem_capacity() -> tuple[float, str]:
    """(bytes, source) behind detect_mem_capacity / mem_capacity_source.

    Source precedence: the REPRO_MEM_CAPACITY env var (deterministic CI /
    non-Linux override, plain bytes), then the live backend — an
    accelerator's memory_stats bytes_limit, or on the CPU backend the
    /proc/meminfo MemAvailable share.  There is no default: a source that
    cannot answer raises.  Memoized: MemAvailable jitters call-to-call,
    and a calibration must stay deterministic within a process.
    """
    env = os.environ.get("REPRO_MEM_CAPACITY")
    if env:
        try:
            cap = float(env)
            if cap > 0:
                return cap, "env:REPRO_MEM_CAPACITY"
        except ValueError:
            print(f"calibrate: WARNING: ignoring non-numeric "
                  f"REPRO_MEM_CAPACITY={env!r}")
    dev = jax.local_devices()[0]
    if dev.platform != "cpu":
        # an accelerator reports its own capacity; a failure here must
        # not turn into a host-RAM guess for a 16 GB chip
        stats = dev.memory_stats()
        if not stats or not stats.get("bytes_limit"):
            raise RuntimeError(
                f"{dev.device_kind}: memory_stats() reports no bytes_limit "
                f"({stats!r}); pass --mem-limit BYTES or set "
                f"REPRO_MEM_CAPACITY")
        return float(stats["bytes_limit"]), "device:memory_stats"
    with open("/proc/meminfo") as f:
        for line in f:
            if line.startswith("MemAvailable:"):
                kb = float(line.split()[1])
                return (kb * 1024 / max(jax.local_device_count(), 1),
                        "host:/proc/meminfo")
    raise RuntimeError("/proc/meminfo has no MemAvailable line; set "
                       "REPRO_MEM_CAPACITY")


def detect_mem_capacity() -> float:
    """Per-device memory capacity in bytes, for Machine.mem_capacity and
    `--mem-limit auto`.

    A REPRO_MEM_CAPACITY env var (plain bytes) wins outright — the
    deterministic-capacity knob for CI and non-Linux hosts.  Otherwise
    accelerators report it directly (``memory_stats()['bytes_limit']``,
    an error when absent); the host CPU backend returns None from
    memory_stats, so there the /proc/meminfo MemAvailable share among the
    (possibly xla_force_host_platform forced) device count answers — all
    host 'devices' share one RAM, so the per-device share is the honest
    capacity.  `mem_capacity_source()` names which source answered
    (recorded in Calibration.meta)."""
    return _detect_mem_capacity()[0]


def mem_capacity_source() -> str:
    """Which source detect_mem_capacity's answer came from."""
    return _detect_mem_capacity()[1]


# tests (and long-lived processes changing REPRO_MEM_CAPACITY) reset the
# memoized detection through the same knob the old lru_cached function had
detect_mem_capacity.cache_clear = _detect_mem_capacity.cache_clear


def compiled_peak_bytes(compiled) -> float:
    """Per-device peak of a compiled executable from XLA's
    memory_analysis — arguments + outputs + temps - aliased, the pattern
    launch.dryrun proves out.  0.0 when the backend exposes nothing."""
    try:
        mem = compiled.memory_analysis()
    except Exception:
        return 0.0
    if mem is None:
        return 0.0
    return float(getattr(mem, "argument_size_in_bytes", 0)
                 + getattr(mem, "output_size_in_bytes", 0)
                 + getattr(mem, "temp_size_in_bytes", 0)
                 - getattr(mem, "alias_size_in_bytes", 0))


def xla_peak_bytes(fn, *args) -> float:
    """Lower + compile `fn(*args)` and report its XLA peak bytes/device."""
    jitted = fn if hasattr(fn, "lower") else jax.jit(fn)
    return compiled_peak_bytes(jitted.lower(*args).compile())


def crosscheck_memory(plan, fn, *args) -> dict:
    """The §VI memory-model validation loop: compare a compiled plan's
    *predicted* peak (plan.predicted['memory'], core.perfmodel
    .network_memory) against XLA's measured peak for the train step that
    executes it.  `fn(*args)` must be the step the plan drives (jittable
    or already jitted).  Returns predicted/measured bytes and their ratio
    (nan when the backend reports no memory analysis)."""
    predicted = float(plan.predicted["memory"]["peak_bytes"])
    measured = xla_peak_bytes(fn, *args)
    return {"predicted_bytes": predicted, "measured_bytes": measured,
            "ratio": predicted / measured if measured else float("nan")}


# ---------------------------------------------------------------------------
# what to measure: the shapes and message sizes the model will ask about
# ---------------------------------------------------------------------------

def _local_shards(layer: ConvLayer, dist, mesh_shape):
    """Mirror of perfmodel.layer_cost's shard arithmetic for one dist."""
    n_l = layer.n // max(dist.ways("N", mesh_shape), 1)
    h_l = layer.h // max(dist.ways("H", mesh_shape), 1)
    w_l = layer.w // max(dist.ways("W", mesh_shape), 1)
    c_l = layer.c // max(dist.ways("C", mesh_shape), 1)
    f_l = layer.f // max(dist.ways("F", mesh_shape), 1)
    p_c = dist.ways("C", mesh_shape)
    p_f = dist.ways("F", mesh_shape)
    return n_l, c_l, h_l, w_l, f_l, p_c, p_f


def table_shapes(specs: Sequence[ConvLayer], mesh_shape: Mapping[str, int],
                 allow_w_split: bool = True,
                 allow_channel_filter: bool = True) -> list[tuple]:
    """Every EmpiricalTable key `layer_cost` can query while solving these
    layers over this mesh: for each executable candidate distribution, the
    local forward/BPw conv shape and the BPx data-conv shape (Eq. 2/3)."""
    keys = set()
    for layer in specs:
        for d in executable_candidates(layer, mesh_shape, allow_w_split,
                                       allow_channel_filter):
            n_l, c_l, h_l, w_l, f_l, p_c, p_f = \
                _local_shards(layer, d, mesh_shape)
            f_fwd = layer.f if p_c > 1 else f_l
            keys.add((layer.kind, n_l, c_l, h_l, w_l, f_fwd,
                      layer.k, layer.s))
            if layer.kind != "pool":
                c_bpx = layer.c if p_f > 1 else c_l
                keys.add((layer.kind, n_l, c_bpx, h_l, w_l, f_l,
                          layer.k, layer.s))
    return sorted(keys)


def comm_sizes(specs: Sequence[ConvLayer], mesh_shape: Mapping[str, int],
               wordsize: int = 4,
               allow_w_split: bool = True,
               allow_channel_filter: bool = True
               ) -> tuple[list[int], list[int]]:
    """(p2p bytes, collective bytes) the §V-A/B cost terms will charge for
    these layers: halo SR messages, CF reduce-scatter/all-gather payloads,
    the dL/dw allreduce and the §III-C shuffle blocks."""
    p_total = 1
    for sz in mesh_shape.values():
        p_total *= sz
    p2p, coll = set(), set()
    for layer in specs:
        coll.add(int(layer.weight_words()) * wordsize)       # BPa allreduce
        # §III-C shuffle: priced by all_to_all_time with the *p2p* α/β
        # (pairwise exchange), so its per-processor block must be sampled
        # by the p2p grid, not the collective one
        p2p.add(int(layer.act_words() / max(p_total, 1)) * wordsize)
        for d in executable_candidates(layer, mesh_shape, allow_w_split,
                                       allow_channel_filter):
            n_l, c_l, h_l, w_l, f_l, p_c, p_f = \
                _local_shards(layer, d, mesh_shape)
            o = layer.o
            h_out_l = layer.h_out // max(d.ways("H", mesh_shape), 1)
            w_out_l = layer.w_out // max(d.ways("W", mesh_shape), 1)
            # dL/dy halos run at the *output* extents (layer_cost's
            # halo_dy), so strided layers sample the smaller message too
            if o and d.ways("H", mesh_shape) > 1:
                p2p.add(o * n_l * c_l * w_l * wordsize)      # halo on x
                p2p.add(o * n_l * f_l * w_out_l * wordsize)  # halo on dL/dy
            if o and d.ways("W", mesh_shape) > 1:
                p2p.add(o * n_l * c_l * h_l * wordsize)
                p2p.add(o * n_l * f_l * h_out_l * wordsize)
            if p_c > 1:
                coll.add(n_l * layer.f * h_out_l * w_out_l * wordsize)
            if p_f > 1:
                coll.add(n_l * layer.c * h_l * w_l * wordsize)
    return (sorted(b for b in p2p if b > 0),
            sorted(b for b in coll if b > 0))


def _representative(values: Sequence, cap: int) -> list:
    """A deterministic <=cap subset spread evenly over the sorted range
    (always keeping the extremes) — the benchmark grid stays bounded while
    covering the span the model will interpolate over."""
    values = sorted(set(values))
    if len(values) <= cap:
        return values
    idx = np.linspace(0, len(values) - 1, cap).round().astype(int)
    return [values[i] for i in sorted(set(idx.tolist()))]


def _choose_shapes(wanted: Sequence[tuple], max_shapes: int) -> list[tuple]:
    """The deterministic <=max_shapes subset a calibration run measures:
    spread over the FLOP range so both the launch-bound tail and the
    throughput-bound head get covered.  `coverage` recomputes this, so a
    legitimately capped calibration is judged against what a fresh run
    would measure, not the full (unmeasurable) candidate set."""
    by_flops = sorted(wanted, key=lambda k: (_conv_flops_bytes(k)[0], k))
    return [by_flops[i]
            for i in _representative(range(len(by_flops)), max_shapes)]


# ---------------------------------------------------------------------------
# microbenchmarks (timer-injectable: tests pass a deterministic fake)
# ---------------------------------------------------------------------------

Timer = Callable[..., float]        # timer(fn, *args) -> seconds/call


def _bench_conv_shape(key: tuple, timer: Timer) -> float | None:
    """Time the local dense kernel for one table key on the live backend —
    the per-shard compute the paper times as cuDNN."""
    kind, n, c, h, w, f, k, s = key
    if min(n, c, h, w, f) <= 0:
        return None
    rk = jax.random.PRNGKey(0)
    if kind == "pool":
        x = jax.random.normal(rk, (n, h, w, c), jnp.float32)
        from repro.core.spatial_conv import _pool_windows
        pads = ((0, 0), same_pads(k, s), same_pads(k, s), (0, 0))
        fn = jax.jit(lambda x: _pool_windows(x, (k, k), (s, s), pads, "max"))
        return timer(fn, x)
    x = jax.random.normal(rk, (n, h, w, c), jnp.float32)
    wt = jax.random.normal(jax.random.PRNGKey(1), (k, k, c, f),
                           jnp.float32) * 0.1
    fn = jax.jit(lambda x, w: lax.conv_general_dilated(
        x, w, (s, s), (same_pads(k, s), same_pads(k, s)),
        dimension_numbers=("NHWC", "HWIO", "NHWC")))
    return timer(fn, x, wt)


def _bench_p2p(mesh, axis: str, nbytes: int, timer: Timer) -> float:
    """One halo-pattern ppermute ring step: every device sends and receives
    `nbytes` — the perf model's SR(n) primitive."""
    n = dict(mesh.shape)[axis]
    elems = max(1, nbytes // 4)
    x = jax.device_put(jnp.zeros((n * elems,), jnp.float32),
                       NamedSharding(mesh, P(axis)))
    perm = [(i, (i + 1) % n) for i in range(n)]
    fn = jax.jit(shard_map(lambda v: lax.ppermute(v, axis, perm),
                           mesh=mesh, in_specs=(P(axis),),
                           out_specs=P(axis)))
    return timer(fn, x)


def _bench_collective(mesh, axis: str, op: str, nbytes: int,
                      timer: Timer) -> float:
    """allreduce / reduce-scatter / all-gather of an `nbytes` buffer over
    one mesh axis — the collective terms of §V-A (CF conv, BPa)."""
    n = dict(mesh.shape)[axis]
    elems = max(n, nbytes // 4) // n * n      # divisible by the group
    if op == "allreduce":
        x = jax.device_put(jnp.ones((elems,), jnp.float32),
                           NamedSharding(mesh, P()))
        body = lambda v: lax.psum(v, axis)                  # noqa: E731
        in_spec, out_spec = P(), P()
    elif op == "reduce_scatter":
        x = jax.device_put(jnp.ones((elems,), jnp.float32),
                           NamedSharding(mesh, P()))
        body = lambda v: lax.psum_scatter(                  # noqa: E731
            v, axis, scatter_dimension=0, tiled=True)
        in_spec, out_spec = P(), P(axis)
    elif op == "all_gather":
        x = jax.device_put(jnp.ones((elems,), jnp.float32),
                           NamedSharding(mesh, P(axis)))
        body = lambda v: lax.all_gather(v, axis, axis=0,    # noqa: E731
                                        tiled=True)
        in_spec, out_spec = P(axis), P()
    else:
        raise ValueError(op)
    # forward-only timing: VMA checking is off because a psum over one
    # axis of a fully-replicated input is rejected by the checker (nothing
    # is differentiated here, so it is safe).
    fn = jax.jit(shard_map(body, mesh=mesh, in_specs=(in_spec,),
                           out_specs=out_spec, check_vma=False))
    return timer(fn, x)


def _bench_membw(timer: Timer, nbytes: int = 32 << 20) -> float:
    """Achieved streaming bandwidth (read+write) from a saxpy-style pass."""
    x = jnp.zeros((nbytes // 4,), jnp.float32)
    t = timer(jax.jit(lambda v: v + 1.0), x)
    return 2 * nbytes / max(t, 1e-9)


def _bench_overlap(mesh, axis: str, timer: Timer, rounds: int = 3,
                   n: int = 2, c: int = 8, f: int = 8, k: int = 3) -> dict:
    """Interleaved overlapped-vs-serialized A/B of the §IV-A schedule on
    one mesh axis: the same H-split conv step with the interior/boundary
    schedule on vs forced serial, plus a halo-free local conv at the shard
    shape as the compute-only anchor.  The achieved-overlap efficiency is
    the measured gain over the hideable min(comm, compute):

        η = (t_serial − t_overlap) / min(t_serial − t_compute, t_compute)

    clamped to [0, 1]; None when the comm term is too small to resolve
    above timing noise (the sample is kept in meta for inspection but
    excluded from the fit)."""
    from repro.core.spatial_conv import ConvSharding, spatial_conv2d
    p = dict(mesh.shape)[axis]
    h_l = max(4 * k, 16)
    h, w = h_l * p, 64
    sh = ConvSharding(h_axis=axis)
    x = jax.device_put(
        jax.random.normal(jax.random.PRNGKey(0), (n, h, w, c), jnp.float32),
        NamedSharding(mesh, sh.x_spec()))
    wt = jax.random.normal(jax.random.PRNGKey(1), (k, k, c, f),
                           jnp.float32) * 0.1
    ov_fn = jax.jit(lambda x, w: spatial_conv2d(
        x, w, strides=(1, 1), sharding=sh, mesh=mesh, overlap=True))
    ser_fn = jax.jit(lambda x, w: spatial_conv2d(
        x, w, strides=(1, 1), sharding=sh, mesh=mesh, overlap=False))
    x_loc = jax.random.normal(jax.random.PRNGKey(2), (n, h_l, w, c),
                              jnp.float32)
    loc_fn = jax.jit(lambda x, w: lax.conv_general_dilated(
        x, w, (1, 1), (same_pads(k, 1), same_pads(k, 1)),
        dimension_numbers=("NHWC", "HWIO", "NHWC")))
    t_ov, t_ser = [], []
    for _ in range(rounds):     # alternate arms so clock drift hits both
        t_ov.append(timer(ov_fn, x, wt))
        t_ser.append(timer(ser_fn, x, wt))
    t_ov, t_ser = min(t_ov), min(t_ser)
    t_loc = timer(loc_fn, x_loc, wt)
    comm = max(t_ser - t_loc, 0.0)
    hideable = min(comm, t_loc)
    eta = None
    if hideable > 0.05 * t_ser:
        eta = min(max((t_ser - t_ov) / hideable, 0.0), 1.0)
    return {"axis": axis, "p": p, "t_overlap": t_ov, "t_serial": t_ser,
            "t_compute": t_loc, "eta": eta}


def fit_eta(mesh, *, timer: Timer | None = None, reps: int = 5,
            base: Machine = HOST_BASE) -> tuple[float, list]:
    """Measure the achieved-overlap efficiency η (Machine.overlap_eta)
    over every size > 1 mesh axis and take the median across axes.

    Returns (base.overlap_eta, []) when `mesh` carries no live multi-device
    axis (a plain {axis: size} mapping, or every axis of size 1): an
    analytic calibration keeps the optimistic default rather than inventing
    a measurement it cannot make."""
    if timer is None:
        timer = lambda fn, *a: time_fn(fn, *a, reps=reps)   # noqa: E731
    mesh_shape = _mesh_shape_of(mesh)
    real_mesh = mesh if hasattr(mesh, "devices") else None
    axes = sorted(ax for ax, sz in mesh_shape.items() if sz > 1) \
        if real_mesh is not None else []
    samples = [_bench_overlap(real_mesh, ax, timer) for ax in axes]
    etas = [s["eta"] for s in samples if s["eta"] is not None]
    eta = float(np.median(etas)) if etas else base.overlap_eta
    return eta, samples


# ---------------------------------------------------------------------------
# composition microbenchmarks: what a §III-C shuffle, a product-axis halo
# and a CF collective *inside* a halo'd spatial block actually cost — the
# terms where the composed workloads' 4–13× model/measured gap lives
# ---------------------------------------------------------------------------

def shuffle_sizes(specs: Sequence[ConvLayer],
                  mesh_shape: Mapping[str, int],
                  wordsize: int = 4) -> list[tuple[int, int]]:
    """The (p_total, local_bytes) shuffle keys a plan transition over these
    layers can price — shuffle_block_bytes is the shared definition, so the
    measured `shuffle:` entries land on exactly the keys shuffle_time asks
    for."""
    p_total = 1
    for sz in mesh_shape.values():
        p_total *= sz
    out = set()
    for layer in specs:
        nb = shuffle_block_bytes(layer, p_total, wordsize)
        if nb > 0:
            out.add((p_total, nb))
    return sorted(out)


def _bench_shuffle(mesh, axes: Sequence[str], nbytes: int,
                   timer: Timer) -> float:
    """One direction of a §III-C shuffle: reshard a (p, elems) array from
    row-sharded to column-sharded over the product of `axes` — the
    all-to-all transpose every dist change pays, at `nbytes` local."""
    shape = dict(mesh.shape)
    p = 1
    for ax in axes:
        p *= shape[ax]
    elems = max(p, nbytes // 4) // p * p
    src = NamedSharding(mesh, P(tuple(axes), None))
    dst = NamedSharding(mesh, P(None, tuple(axes)))
    x = jax.device_put(jnp.zeros((p, elems), jnp.float32), src)
    fn = jax.jit(lambda v: lax.with_sharding_constraint(v, dst))
    return timer(fn, x)


def _bench_product_halo(mesh, axes: tuple[str, str], timer: Timer,
                        n: int = 2, c: int = 8, f: int = 8,
                        k: int = 3) -> dict:
    """Serialized H-split conv with H over a *product* of two mesh axes
    (boundary-crossing hops), plus the local conv at the shard shape as the
    compute-only anchor — (t_fused − t_compute) isolates the measured halo
    exchange the model prices with sr_time(…, hops=2)."""
    from repro.core.spatial_conv import ConvSharding, spatial_conv2d
    shape = dict(mesh.shape)
    p = shape[axes[0]] * shape[axes[1]]
    h_l = max(4 * k, 16)
    h, w = h_l * p, 32
    sh = ConvSharding(h_axis=tuple(axes))
    x = jax.device_put(
        jax.random.normal(jax.random.PRNGKey(0), (n, h, w, c), jnp.float32),
        NamedSharding(mesh, sh.x_spec()))
    wt = jax.random.normal(jax.random.PRNGKey(1), (k, k, c, f),
                           jnp.float32) * 0.1
    ser_fn = jax.jit(lambda x, w: spatial_conv2d(
        x, w, strides=(1, 1), sharding=sh, mesh=mesh, overlap=False))
    x_loc = jax.random.normal(jax.random.PRNGKey(2), (n, h_l, w, c),
                              jnp.float32)
    loc_fn = jax.jit(lambda x, w: lax.conv_general_dilated(
        x, w, (1, 1), (same_pads(k, 1), same_pads(k, 1)),
        dimension_numbers=("NHWC", "HWIO", "NHWC")))
    return {"axes": list(axes), "p": p,
            "t_fused": timer(ser_fn, x, wt),
            "t_compute": timer(loc_fn, x_loc, wt),
            "geom": {"o": k // 2, "n": n, "c": c, "h_l": h_l, "w_l": w,
                     "hops": 2}}


def _bench_composed_cf(mesh, cf_axis: str, sp_axis: str, timer: Timer,
                       n: int = 2, k: int = 3) -> dict:
    """Serialized fused CF×spatial conv (the §III-D reduce-scatter running
    *inside* an H-split shard_map) plus its local-conv anchor — what the CF
    collective costs when composed with a halo'd spatial block, vs the
    standalone collective fit."""
    from repro.core.channel_conv import CFSharding, cf_conv2d
    shape = dict(mesh.shape)
    p_cf, p_sp = shape[cf_axis], shape[sp_axis]
    c = f = 8 * p_cf
    h_l = max(4 * k, 16)
    h, w = h_l * p_sp, 32
    sh = CFSharding(cf_axis=cf_axis, h_axis=sp_axis, mode="channel")
    x = jax.device_put(
        jax.random.normal(jax.random.PRNGKey(0), (n, h, w, c), jnp.float32),
        NamedSharding(mesh, sh.x_spec()))
    wt = jax.random.normal(jax.random.PRNGKey(1), (k, k, c, f),
                           jnp.float32) * 0.1
    fused_fn = jax.jit(lambda x, w: cf_conv2d(
        x, w, strides=(1, 1), sharding=sh, mesh=mesh, overlap=False))
    # channel mode computes (c_l -> full F) locally, then RS(y) completes
    # the channel sum — the anchor is that local conv at the shard shape
    x_loc = jax.random.normal(jax.random.PRNGKey(2), (n, h_l, w, c // p_cf),
                              jnp.float32)
    wt_loc = wt[:, :, : c // p_cf, :]
    loc_fn = jax.jit(lambda x, w: lax.conv_general_dilated(
        x, w, (1, 1), (same_pads(k, 1), same_pads(k, 1)),
        dimension_numbers=("NHWC", "HWIO", "NHWC")))
    return {"cf_axis": cf_axis, "sp_axis": sp_axis,
            "p_cf": p_cf, "p_sp": p_sp,
            "t_fused": timer(fused_fn, x, wt),
            "t_compute": timer(loc_fn, x_loc, wt_loc),
            "geom": {"o": k // 2, "n": n, "c_l": c // p_cf, "f": f,
                     "h_l": h_l, "w_l": w}}


def _clamp(x: float, lo: float, hi: float) -> float:
    return min(max(x, lo), hi)


def _fit_composed_factors(m: Machine, cf_samples: Sequence[Mapping],
                          halo_samples: Sequence[Mapping]
                          ) -> tuple[float, float]:
    """(composed_cf_factor, composed_halo_factor) from the fused
    microbenchmarks, decomposed against the *fitted* machine `m` so the
    factors isolate what composition adds on top of the standalone α-β
    fits.  Per-sample ratios are clamped to [0.25, 8] (a factor outside
    that is a measurement failure, not a model truth) and the median is
    taken; 1.0 when nothing measured."""
    ws = 4                       # the benches allocate float32
    halo_ratios = []
    for s in halo_samples:
        g = s["geom"]
        pred = _halo_time(m, g["o"], g["n"], g["c"], g["h_l"], g["w_l"],
                          g["hops"], 0)
        meas = s["t_fused"] - s["t_compute"]
        if pred > 0 and meas > 0:
            halo_ratios.append(_clamp(meas / pred, 0.25, 8.0))
    cf_ratios = []
    for s in cf_samples:
        g = s["geom"]
        pred_halo = _halo_time(m, g["o"], g["n"], g["c_l"], g["h_l"],
                               g["w_l"], 1, 0)
        pred_cf = reduce_scatter_time(
            m, s["p_cf"], g["n"] * g["f"] * g["h_l"] * g["w_l"] * ws)
        meas = s["t_fused"] - s["t_compute"] - pred_halo
        if pred_cf > 0 and meas > 0:
            cf_ratios.append(_clamp(meas / pred_cf, 0.25, 8.0))
    cf = float(np.median(cf_ratios)) if cf_ratios else 1.0
    halo = float(np.median(halo_ratios)) if halo_ratios else 1.0
    return cf, halo


def _measure_composition(specs: Sequence[ConvLayer], real_mesh,
                         mesh_shape: Mapping[str, int],
                         comm_axes: Sequence[str], machine: Machine,
                         timer: Timer, max_sizes: int,
                         wordsize: int) -> dict:
    """Run the composed-cost microbenchmarks against an already-fitted
    `machine` and return the table entries + fitted correction factors —
    shared by calibrate() and load_or_run's backfill of pre-composition
    files.  No live comm axes -> analytic defaults (factors 1.0, empty
    entries), mirroring fit_eta's discipline."""
    entries: dict[tuple, float] = {}
    shuffle_samples: list[list] = []       # [p, nbytes, seconds]
    if comm_axes:
        for p_tot, nb in _representative(
                shuffle_sizes(specs, mesh_shape, wordsize), max_sizes):
            t = _bench_shuffle(real_mesh, comm_axes, nb, timer)
            entries[(SHUFFLE_KIND, p_tot, nb)] = t
            shuffle_samples.append([p_tot, nb, t])
    ratios = []
    for p, nb, t in shuffle_samples:
        pred = all_to_all_time(machine, p, nb)
        if pred > 0 and t > 0:
            ratios.append(_clamp(t / pred, 0.25, 8.0))
    shuffle_factor = float(np.median(ratios)) if ratios else 1.0

    cf_samples, halo_samples = [], []
    if len(comm_axes) >= 2:
        a0, a1 = comm_axes[0], comm_axes[1]
        cf_samples = [_bench_composed_cf(real_mesh, a0, a1, timer),
                      _bench_composed_cf(real_mesh, a1, a0, timer)]
        halo_samples = [_bench_product_halo(real_mesh, (a0, a1), timer)]
        for s in cf_samples:
            entries[("composed:cf", s["p_cf"], s["p_sp"])] = s["t_fused"]
        for s in halo_samples:
            entries[("composed:halo", s["p"], s["geom"]["hops"])] = \
                s["t_fused"]
    cf_factor, halo_factor = _fit_composed_factors(machine, cf_samples,
                                                   halo_samples)
    return {"entries": entries,
            "shuffle_factor": shuffle_factor,
            "cf_factor": cf_factor,
            "halo_factor": halo_factor,
            "shuffle_samples": shuffle_samples,
            "cf_samples": cf_samples,
            "halo_samples": halo_samples}


# ---------------------------------------------------------------------------
# fitting
# ---------------------------------------------------------------------------

def _fit_alpha_beta(rows: Sequence[tuple[float, float, float]],
                    default: tuple[float, float]) -> tuple[float, float]:
    """Least squares for t = a_coef*α + b_coef*β over (a_coef, b_coef, t)
    samples; falls back to `default` when the system is degenerate."""
    if len(rows) < 2:
        return default
    A = np.array([[r[0], r[1]] for r in rows], dtype=np.float64)
    y = np.array([r[2] for r in rows], dtype=np.float64)
    if np.linalg.matrix_rank(A) < 2:
        return default
    (alpha, beta), *_ = np.linalg.lstsq(A, y, rcond=None)
    return max(float(alpha), 1e-8), max(float(beta), 1e-13)


def _fit_compute(samples: Sequence[tuple[float, float]],
                 base: Machine) -> tuple[float, float, float]:
    """(peak_flops, efficiency, halfwork) from (flops, seconds) conv samples.

    The analytic model prices a compute-bound conv at
    t = (fl + halfwork) / (eff * peak) + launch, so a linear fit of t vs fl
    yields eff*peak from the slope and halfwork from the intercept; peak is
    anchored at the best achieved rate so eff lands in (0, 1]."""
    samples = [(fl, t) for fl, t in samples if fl > 0 and t > 0]
    if not samples:
        return base.peak_flops, base.compute_efficiency, base.eff_halfwork
    peak = max(fl / t for fl, t in samples)
    if len({fl for fl, _ in samples}) < 2:
        return peak, 1.0, 0.0
    A = np.array([[fl, 1.0] for fl, _ in samples], dtype=np.float64)
    y = np.array([t for _, t in samples], dtype=np.float64)
    (slope, intercept), *_ = np.linalg.lstsq(A, y, rcond=None)
    if slope <= 0:
        return peak, 1.0, 0.0
    eff = min(1.0, max(0.05, 1.0 / (slope * peak)))
    halfwork = max(0.0, (float(intercept) - LAUNCH_OVERHEAD) / float(slope))
    return peak, eff, halfwork


def _conv_flops_bytes(key: tuple, wordsize: int = 4) -> tuple[float, float]:
    kind, n, c, h, w, f, k, s = key
    h_out, w_out = -(-h // s), -(-w // s)
    if kind == "pool":
        return (float(n * f * h_out * w_out * k * k),
                float((n * c * h * w + n * f * h_out * w_out) * wordsize))
    return (2.0 * n * c * h_out * w_out * k * k * f,
            float((n * c * h * w + n * f * h_out * w_out + k * k * c * f)
                  * wordsize))


# ---------------------------------------------------------------------------
# the calibration object (JSON round-trip)
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Calibration:
    """A fitted Machine + measured EmpiricalTable + provenance metadata —
    everything the solver needs to run on measured costs."""
    machine: Machine
    table: EmpiricalTable
    meta: dict

    def to_json(self) -> dict:
        return {"schema": SCHEMA,
                "machine": dataclasses.asdict(self.machine),
                "table": self.table.to_json(),
                "meta": self.meta}

    @classmethod
    def from_json(cls, obj: Mapping) -> "Calibration":
        if obj.get("schema") != SCHEMA:
            raise ValueError(f"not a calibration file "
                             f"(schema={obj.get('schema')!r}, "
                             f"expected {SCHEMA!r})")
        return cls(machine=Machine(**obj["machine"]),
                   table=EmpiricalTable.from_json(obj["table"]),
                   meta=dict(obj.get("meta", {})))

    def save(self, path: str) -> None:
        with open(path, "w") as f:
            json.dump(self.to_json(), f, indent=1, sort_keys=True)

    @classmethod
    def load(cls, path: str) -> "Calibration":
        with open(path) as f:
            return cls.from_json(json.load(f))

    def summary(self) -> str:
        m = self.machine
        return (f"{m.name}: {len(self.table)} table entries, "
                f"peak {m.peak_flops/1e9:.1f} GFLOP/s "
                f"(eff {m.compute_efficiency:.2f}, "
                f"halfwork {m.eff_halfwork:.2e}), "
                f"capacity {m.mem_capacity/2**30:.1f} GiB/device, "
                f"mem {m.mem_bw/1e9:.1f} GB/s, "
                f"overlap eta {m.overlap_eta:.2f}, "
                f"p2p a={m.alpha*1e6:.1f}us b=1/{1/m.beta/1e9:.2f}GB/s, "
                f"coll a={m.alpha_coll*1e6:.1f}us "
                f"b=1/{1/m.beta_coll/1e9:.2f}GB/s")


# ---------------------------------------------------------------------------
# the calibration run
# ---------------------------------------------------------------------------

def _mesh_shape_of(mesh) -> dict[str, int]:
    if mesh is None:
        return {}
    if isinstance(mesh, Mapping):
        return dict(mesh)
    return dict(mesh.shape)


def calibrate(specs: Sequence[ConvLayer], mesh, *,
              base: Machine = HOST_BASE,
              reps: int = 5,
              max_shapes: int = 64,
              max_sizes: int = 5,
              timer: Timer | None = None,
              allow_w_split: bool = True,
              allow_channel_filter: bool = True) -> Calibration:
    """Microbenchmark + fit for `specs` over `mesh` on the live backend.

    `mesh` may be a jax Mesh (communication axes of size > 1 are measured)
    or a plain {axis: size} mapping (shapes only — comm constants keep the
    `base` values).  `timer(fn, *args) -> seconds` defaults to the shared
    trimmed-mean loop (repro.utils.time_fn); tests inject a deterministic
    fake so calibration logic is checkable without wall clocks.
    """
    if timer is None:
        timer = lambda fn, *a: time_fn(fn, *a, reps=reps)   # noqa: E731
    mesh_shape = _mesh_shape_of(mesh)
    real_mesh = mesh if hasattr(mesh, "devices") else None

    # -- 1. local conv table over the candidate shard shapes ----------------
    wanted = table_shapes(specs, mesh_shape, allow_w_split,
                          allow_channel_filter)
    chosen = _choose_shapes(wanted, max_shapes)
    entries: dict[tuple, float] = {}
    for key in chosen:
        t = _bench_conv_shape(key, timer)
        if t is not None:
            entries[key] = t
    dropped = len(wanted) - len(chosen)
    if dropped:
        print(f"calibrate: capped conv grid at {len(chosen)} of "
              f"{len(wanted)} shapes (analytic fallback covers the rest)")

    # -- 2. communication primitives at the emitted message sizes -----------
    p2p_all, coll_all = comm_sizes(specs, mesh_shape,
                                   wordsize=base.wordsize,
                                   allow_w_split=allow_w_split,
                                   allow_channel_filter=allow_channel_filter)
    p2p_sizes = _representative(p2p_all, max_sizes)
    coll_sizes = _representative(coll_all, max_sizes)
    comm_axes = sorted(ax for ax, sz in mesh_shape.items() if sz > 1) \
        if real_mesh is not None else []

    p2p_samples: list[list] = []        # [axis, nbytes, seconds]
    coll_samples: list[list] = []       # [op, axis, p, nbytes, seconds]
    for ax in comm_axes:
        p = mesh_shape[ax]
        for nbytes in p2p_sizes:
            p2p_samples.append([ax, nbytes,
                                _bench_p2p(real_mesh, ax, nbytes, timer)])
        for op in ("allreduce", "reduce_scatter", "all_gather"):
            for nbytes in coll_sizes:
                coll_samples.append(
                    [op, ax, p, nbytes,
                     _bench_collective(real_mesh, ax, op, nbytes, timer)])

    # -- 3. fit the Machine constants ---------------------------------------
    alpha, beta = _fit_alpha_beta(
        [(1.0, float(nb), t) for _, nb, t in p2p_samples],
        (base.alpha, base.beta))
    # fit the collective fabric from the reduce-scatter / all-gather
    # samples only, whose model coefficients are unambiguous
    # ((p-1)·α + (p-1)/p·n·β).  The allreduce samples are measured for
    # validation (meta) but NOT fitted: perfmodel prices an allreduce as
    # the *min* over candidate algorithms, so attributing the samples to
    # any single algorithm's coefficients would fit constants that
    # under-predict the very samples they were fit to.
    coll_rows = [(float(p - 1), (p - 1) / p * nb, t)
                 for op, _, p, nb, t in coll_samples
                 if op != "allreduce"]
    alpha_coll, beta_coll = _fit_alpha_beta(
        coll_rows, (base.alpha_coll, base.beta_coll))

    conv_fit = [( _conv_flops_bytes(k)[0], t) for k, t in entries.items()
                if k[0] != "pool"]
    peak, eff, halfwork = _fit_compute(conv_fit, base)
    mem_bw = _bench_membw(timer)
    # achieved-overlap efficiency η: interleaved overlapped-vs-serialized
    # A/B per comm axis (see _bench_overlap) — what scales the solver's
    # §IV-A overlap credit down to what this machine actually hides.
    overlap_eta, eta_samples = fit_eta(mesh, timer=timer, base=base)
    if eta_samples:
        # let the runtime's chunked-CF default resolve against the
        # measurement (channel_conv.chunks_decision)
        channel_conv.set_measured_eta(overlap_eta)

    machine = Machine(
        name=f"calibrated-{jax.default_backend()}",
        peak_flops=peak, mem_bw=mem_bw,
        alpha=alpha, beta=beta,
        alpha_coll=alpha_coll, beta_coll=beta_coll,
        wordsize=base.wordsize,
        compute_efficiency=eff, eff_halfwork=halfwork,
        mem_capacity=detect_mem_capacity(),
        overlap_eta=overlap_eta)

    # -- 4. composed costs: §III-C shuffles at the real transition sizes,
    # fused CF×spatial, product-axis halo — measured against the fitted
    # constants above so the correction factors isolate composition -------
    comp = _measure_composition(specs, real_mesh, mesh_shape, comm_axes,
                                machine, timer, max_sizes, base.wordsize)
    entries.update(comp["entries"])
    machine = dataclasses.replace(
        machine,
        composed_cf_factor=comp["cf_factor"],
        composed_halo_factor=comp["halo_factor"],
        shuffle_factor=comp["shuffle_factor"])

    meta = {
        "backend": jax.default_backend(),
        "ndevices": jax.device_count(),
        "mesh": dict(mesh_shape),
        "reps": reps,
        "max_shapes": max_shapes,
        "allow_w_split": allow_w_split,
        "allow_channel_filter": allow_channel_filter,
        "shapes": {"requested": len(wanted), "measured": len(entries),
                   "dropped": dropped},
        "p2p_samples": p2p_samples,
        "collective_samples": coll_samples,
        "eta_fit": {"eta": overlap_eta, "samples": eta_samples},
        "shuffle_fit": {"factor": comp["shuffle_factor"],
                        "samples": comp["shuffle_samples"]},
        "composed_fit": {"cf_factor": comp["cf_factor"],
                         "halo_factor": comp["halo_factor"],
                         "cf_samples": comp["cf_samples"],
                         "halo_samples": comp["halo_samples"]},
        "mem_capacity_source": mem_capacity_source(),
        "layers": [l.name for l in specs],
    }
    return Calibration(machine=machine, table=EmpiricalTable(entries),
                       meta=meta)


def _chosen_shapes_for(cal: Calibration, specs: Sequence[ConvLayer],
                       mesh_shape: Mapping[str, int]) -> list[tuple]:
    """The conv-shape grid a fresh calibration of `specs` over `mesh_shape`
    would measure under `cal`'s own stored settings (shape cap, candidate
    flags) — the single definition both `coverage` and `grow` judge
    against, so the growth policy and the coverage warning cannot drift."""
    m = cal.meta
    wanted = table_shapes(specs, mesh_shape,
                          allow_w_split=m.get("allow_w_split", True),
                          allow_channel_filter=m.get("allow_channel_filter",
                                                     True))
    return _choose_shapes(wanted, int(m.get("max_shapes", 64)))


def coverage(cal: Calibration, specs: Sequence[ConvLayer],
             mesh_shape: Mapping[str, int]) -> float:
    """Fraction of the table keys a fresh calibration of `specs` over
    `mesh_shape` — run with `cal`'s own settings — would measure that
    `cal`'s table actually holds.  Judging against what a run *would
    measure* (not the full candidate set) means a legitimately capped
    self-calibration scores 1.0, while a table measured for a different
    network or mesh scores near 0."""
    chosen = _chosen_shapes_for(cal, specs, mesh_shape)
    if not chosen:
        return 1.0
    return sum(k in cal.table.entries for k in chosen) / len(chosen)


def grow(cal: Calibration, specs: Sequence[ConvLayer], mesh, *,
         reps: int = 5, timer: Timer | None = None) -> int:
    """Measure the conv shapes a calibration of `specs`/`mesh` would pick
    that `cal`'s table is missing, and merge them in — the cross-run table
    growth the CI bench lane relies on (the cached BENCH_calibration.json
    accumulates shard shapes across pushes instead of being re-measured).
    Machine constants are kept: they are shape-independent fits and
    re-fitting them from a partial sample would only add noise.  Returns
    the number of entries added."""
    if timer is None:
        timer = lambda fn, *a: time_fn(fn, *a, reps=reps)   # noqa: E731
    mesh_shape = _mesh_shape_of(mesh)
    chosen = _chosen_shapes_for(cal, specs, mesh_shape)
    missing = [k for k in chosen if k not in cal.table.entries]
    added = 0
    for key in missing:
        t = _bench_conv_shape(key, timer)
        if t is not None:
            cal.table.entries[key] = t
            added += 1
    if added:
        grown = cal.meta.setdefault("grown", [])
        grown.append({"layers": [l.name for l in specs],
                      "mesh": dict(mesh_shape), "added": added})
    return added


def load_or_run(path: str, specs: Sequence[ConvLayer], mesh, *,
                grow_table: bool = False, **kwargs) -> Calibration:
    """Load a calibration from `path` when it exists, else run one over
    `specs`/`mesh` and save it there — the one-liner train.py and the
    benchmarks use to make `--calibrate` idempotent across runs.

    A loaded file is checked against the *requested* specs/mesh: a table
    measured for a different network or mesh mostly misses and silently
    degrades to the analytic model, so low coverage gets a loud warning
    (not an error — a TPU-measured table driving a dry run is legitimate).
    With `grow_table=True` the missing shard shapes are measured on the
    live backend instead and merged back into `path`, so a cached table
    (CI's actions/cache) accumulates coverage across runs.
    """
    if path and os.path.exists(path):
        cal = Calibration.load(path)
        print(f"calibration loaded from {path}: {cal.summary()}")
        mesh_shape = _mesh_shape_of(mesh)
        if cal.meta.get("mesh") not in (None, dict(mesh_shape)):
            print(f"calibrate: WARNING: {path} was measured on mesh "
                  f"{cal.meta['mesh']}, not {dict(mesh_shape)}")
        if "eta_fit" not in cal.meta:
            # a pre-η calibration file: backfill the achieved-overlap
            # measurement now (the Machine JSON simply lacked the field and
            # deserialized at the optimistic η=1 default) and persist it.
            eta, samples = fit_eta(mesh, timer=kwargs.get("timer"),
                                   reps=kwargs.get("reps", 5))
            cal.machine = dataclasses.replace(cal.machine, overlap_eta=eta)
            cal.meta["eta_fit"] = {"eta": eta, "samples": samples}
            if path:
                cal.save(path)
            print(f"calibrate: backfilled overlap eta={eta:.2f} into {path}")
        if "mem_capacity_source" not in cal.meta:
            cal.meta["mem_capacity_source"] = mem_capacity_source()
            if path:
                cal.save(path)
        if "shuffle_fit" not in cal.meta or \
                "composed_fit" not in cal.meta:
            # a pre-composition calibration file: measure the §III-C
            # shuffle / fused-composition benches now against the stored
            # machine constants (the Machine JSON simply lacked the factor
            # fields and deserialized at the analytic 1.0 defaults), record
            # the capacity-detection source, and persist.
            timer = kwargs.get("timer")
            if timer is None:
                reps = kwargs.get("reps", 5)
                timer = lambda fn, *a: time_fn(fn, *a,      # noqa: E731
                                               reps=reps)
            mesh_shape = _mesh_shape_of(mesh)
            real_mesh = mesh if hasattr(mesh, "devices") else None
            comm_axes = sorted(ax for ax, sz in mesh_shape.items()
                               if sz > 1) if real_mesh is not None else []
            comp = _measure_composition(
                specs, real_mesh, mesh_shape, comm_axes, cal.machine,
                timer, kwargs.get("max_sizes", 5),
                cal.machine.wordsize)
            cal.table.entries.update(comp["entries"])
            cal.machine = dataclasses.replace(
                cal.machine,
                composed_cf_factor=comp["cf_factor"],
                composed_halo_factor=comp["halo_factor"],
                shuffle_factor=comp["shuffle_factor"])
            cal.meta.setdefault(
                "shuffle_fit", {"factor": comp["shuffle_factor"],
                                "samples": comp["shuffle_samples"]})
            cal.meta.setdefault(
                "composed_fit", {"cf_factor": comp["cf_factor"],
                                 "halo_factor": comp["halo_factor"],
                                 "cf_samples": comp["cf_samples"],
                                 "halo_samples": comp["halo_samples"]})
            if path:
                cal.save(path)
            print(f"calibrate: backfilled composed-cost fit into {path} "
                  f"(shuffle x{comp['shuffle_factor']:.2f}, "
                  f"cf x{comp['cf_factor']:.2f}, "
                  f"halo x{comp['halo_factor']:.2f})")
        ef = cal.meta.get("eta_fit") or {}
        if ef.get("samples"):
            # loaded file carries a real measurement — install it for the
            # runtime's chunked-CF default, same as a fresh calibrate()
            channel_conv.set_measured_eta(ef["eta"])
        if grow_table:
            added = grow(cal, specs, mesh,
                         reps=kwargs.get("reps", 5),
                         timer=kwargs.get("timer"))
            if added:
                cal.save(path)
                print(f"calibrate: grew {path} by {added} table entries "
                      f"({len(cal.table)} total)")
        cov = coverage(cal, specs, mesh_shape)
        if cov < 0.5:
            print(f"calibrate: WARNING: {path} covers only {cov:.0%} of "
                  f"this network's shard shapes — the rest falls back to "
                  f"the analytic model; delete the file (or pass another "
                  f"path) to re-measure for this network")
        return cal
    cal = calibrate(specs, mesh, **kwargs)
    if path:
        cal.save(path)
        print(f"calibration written to {path}: {cal.summary()}")
    return cal


def refit_from_attribution(cal: Calibration, report: Mapping, *,
                           path: str | None = None,
                           damp: float = 1.0) -> dict:
    """Close the attribution loop: fold a measured per-term drift report
    (NetworkPlan.attribution_report / BENCH_attribution.json) back into the
    calibration's composition factors, so model/measured drift *drives
    recalibration* instead of only printing a warning.

    The comm-side term drifts map onto the factors that price them:
    `shuffle` -> shuffle_factor; `fp_comm`/`bp_comm` (halo + CF
    collectives, which the composed workloads dominate with composed
    terms) -> both composed factors, weighted by predicted seconds.
    Compute-side terms (fp/bp_compute, bpa) are left to the conv table and
    the collective fit — nudging factors by compute drift would smear
    kernel noise over comm terms.

    Each factor takes a multiplicative step drift**damp clamped to
    [0.25, 4] per refit and [0.1, 10] absolute; the applied steps append to
    meta["attribution_refits"].  Saves to `path` when given.  Returns the
    {factor: new value} dict of what changed."""
    terms = report.get("terms") or {}

    def drift_of(*names):
        num = den = 0.0
        for t in names:
            row = terms.get(t)
            if row and row.get("predicted_s", 0) > 0 and \
                    row.get("drift", 0) > 0:
                num += row["predicted_s"] * row["drift"]
                den += row["predicted_s"]
        return (num / den) if den > 0 else None

    def step(cur, drift):
        mult = _clamp(drift ** damp, 0.25, 4.0)
        return _clamp(cur * mult, 0.1, 10.0)

    changed: dict[str, float] = {}
    sh_drift = drift_of("shuffle")
    if sh_drift is not None:
        changed["shuffle_factor"] = step(cal.machine.shuffle_factor,
                                         sh_drift)
    comm_drift = drift_of("fp_comm", "bp_comm")
    if comm_drift is not None:
        changed["composed_cf_factor"] = step(
            cal.machine.composed_cf_factor, comm_drift)
        changed["composed_halo_factor"] = step(
            cal.machine.composed_halo_factor, comm_drift)
    if changed:
        cal.machine = dataclasses.replace(cal.machine, **changed)
        cal.meta.setdefault("attribution_refits", []).append(
            {"worst_term": report.get("worst_term"),
             "drifts": {"shuffle": sh_drift, "comm": comm_drift},
             "applied": dict(changed)})
        if path:
            cal.save(path)
    return changed


# ---------------------------------------------------------------------------
# CLI:  PYTHONPATH=src python -m repro.core.calibrate --arch mesh1k --smoke
# (fake multi-device with XLA_FLAGS=--xla_force_host_platform_device_count=N)
# ---------------------------------------------------------------------------

def main(argv=None):
    import argparse
    ap = argparse.ArgumentParser(
        description="Calibrate the §V perf model on the live backend and "
                    "write BENCH_calibration.json")
    ap.add_argument("--arch", default="mesh1k",
                    help="CNN arch whose layer shapes seed the table "
                         "(mesh1k | mesh2k | resnet50)")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--data", type=int, default=1)
    ap.add_argument("--model", type=int, default=1)
    ap.add_argument("--reps", type=int, default=5)
    ap.add_argument("--max-shapes", type=int, default=64)
    ap.add_argument("--out", default=DEFAULT_PATH)
    args = ap.parse_args(argv)

    from repro.configs import registry
    from repro.launch.mesh import make_mesh
    arch = registry.canon(args.arch)
    if arch not in registry.CNN_ARCHS:
        ap.error(f"--arch {args.arch}: calibration covers the CNN archs "
                 f"{registry.CNN_ARCHS}")
    cfg = registry.get(arch, smoke=args.smoke)
    if arch == "resnet50":
        from repro.models.cnn import resnet
        specs = resnet.layer_specs(args.batch, cfg)
    else:
        from repro.models.cnn import meshnet
        specs = meshnet.layer_specs(cfg, args.batch)
    mesh = make_mesh(data=args.data, model=args.model)
    # load_or_run keeps the CLI idempotent: an existing --out is loaded
    # (with the coverage check), never silently re-measured over
    cal = load_or_run(args.out, specs, mesh, reps=args.reps,
                      max_shapes=args.max_shapes)
    print(cal.summary())


if __name__ == "__main__":
    main()
