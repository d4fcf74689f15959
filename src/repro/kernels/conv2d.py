"""Pallas TPU conv2d — implicit GEMM over the MXU.

The paper's compute hot spot is the local convolution each shard runs after
its halo exchange (§IV: cuDNN there).  The TPU-native formulation is an
implicit GEMM: for each of the K*K filter taps, a (rows x W_out, C) @ (C, F)
matmul on the MXU, accumulated in fp32 and written once.  No im2col buffer
is materialized at element granularity; the input is re-tiled into
*overlapping row blocks* (overlap = K - stride rows, a ~(1 + K/s/block_h)
duplication) so every VMEM block is perfectly Blocked-indexable.

Grid: (N, H_out/block_h, F/block_f).  VMEM blocks (N and row-block dims
squeezed):
  x: (block_h*stride + K - stride, W, C)   rows feeding this tile
  w: (K, K, C, block_f)
  y: (block_h, W_out, block_f)

Each tap reads its rows and columns straight from the x block with a
strided `pl.ds` (Mosaic lowers no strided value slice) and runs one 2-D
(block_h*W_out, C) @ (C, block_f) dot.  block_f is MXU-lane-aligned (128
when F allows); the scoped-VMEM request is sized from the blocks
(`_vmem_limit`): mesh1k's first layer (C=18 padded to 128 lanes, W=1025)
needs ~17 MiB, above v5e's 16 MiB default.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.utils import pcast_varying, round_up


def _kernel(x_ref, w_ref, *refs, kh, kw, stride, block_h, w_out):
    y_ref = refs[-1]              # refs[:-1]: interpret-mode output seed
    c, bf = x_ref.shape[-1], y_ref.shape[-1]
    w = w_ref[...]                                   # (kh, kw, C, bf)
    acc = None                                       # (bh * w_out, bf) f32
    for i in range(kh):
        for j in range(kw):
            # strided ref reads (no value gather); one 2-D MXU matmul per tap
            xs = x_ref[_rows(i, block_h, stride), _rows(j, w_out, stride), :]
            tap = jnp.dot(xs.reshape(block_h * w_out, c), w[i, j],
                          preferred_element_type=jnp.float32)
            acc = tap if acc is None else acc + tap
    y_ref[...] = acc.reshape(block_h, w_out, bf).astype(y_ref.dtype)


def _rows(start, size, stride):
    return pl.ds(start, size, stride=stride) if stride > 1 \
        else pl.ds(start, size)


def conv2d(x, w, *, stride: int = 1, block_h: int = 8, block_f: int = 128,
           interpret: bool = False, interior_first: bool = False):
    """VALID conv, NHWC x HWIO -> NHWC (same dtype as x).

    Halo/padding is the caller's job (core.spatial_conv supplies the halo
    rows), mirroring the paper's split between communication and the local
    cuDNN call.

    interior_first: visit the interior row blocks before the two boundary
    blocks — the §IV-A interior/boundary schedule inside the kernel.  The
    boundary blocks are the only ones whose input rows include the halo,
    so an in-flight halo transfer gets the whole interior pass to land
    before its rows are read.  Pure grid reorder: every block is computed
    exactly once, numerics unchanged.
    """
    n, h, wd, c = x.shape
    kh, kw, _, f = w.shape
    h_out = (h - kh) // stride + 1
    w_out = (wd - kw) // stride + 1
    block_h = min(block_h, h_out)
    while h_out % block_h:
        block_h -= 1
    block_f = min(block_f, f)
    while f % block_f:
        block_f -= 1
    in_rows = block_h * stride + (kh - stride)
    nh = h_out // block_h

    # overlapping row blocks: (n, nh, in_rows, W, C)
    xb = jnp.stack([
        jax.lax.slice_in_dim(x, b * block_h * stride,
                             b * block_h * stride + in_rows, axis=1)
        for b in range(nh)], axis=1)

    if interior_first and nh > 2:
        # grid step -> row block: interior blocks first, boundaries last.
        order = jnp.asarray(tuple(range(1, nh - 1)) + (0, nh - 1), jnp.int32)
        hmap = lambda hi: order[hi]                  # noqa: E731
    else:
        hmap = lambda hi: hi                         # noqa: E731

    # inside shard_map every operand of the call must vary over the same
    # manual axes: the weights arrive replicated while x is per-shard
    vma = jax.typeof(x).vma
    w = pcast_varying(w, tuple(vma - jax.typeof(w).vma))
    operands = [xb, w]
    in_specs = [
        pl.BlockSpec((None, None, in_rows, wd, c),
                     lambda ni, hi, fi: (ni, hmap(hi), 0, 0, 0)),
        pl.BlockSpec((kh, kw, c, block_f), lambda ni, hi, fi: (0, 0, 0, fi)),
    ]
    out_spec = pl.BlockSpec((None, block_h, w_out, block_f),
                            lambda ni, hi, fi: (ni, hmap(hi), 0, fi))
    out_shape = jax.ShapeDtypeStruct((n, h_out, w_out, f), x.dtype, vma=vma)
    aliases = {}
    if interpret and vma:
        # the interpreter seeds its output buffer unvarying, which shard_map
        # rejects; seed it from a varying zeros operand instead
        operands.append(pcast_varying(jnp.zeros(out_shape.shape, x.dtype),
                                      tuple(vma)))
        in_specs.append(out_spec)
        aliases = {2: 0}
    kern = functools.partial(_kernel, kh=kh, kw=kw, stride=stride,
                             block_h=block_h, w_out=w_out)
    return pl.pallas_call(
        kern,
        grid=(n, nh, f // block_f),
        in_specs=in_specs,
        out_specs=out_spec,
        out_shape=out_shape,
        input_output_aliases=aliases,
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=_vmem_limit(
            in_rows, wd, c, kh * kw, block_h * w_out, block_f,
            x.dtype.itemsize)),
        interpret=interpret,
    )(*operands)


def _vmem_limit(in_rows, wd, c, taps, rows_out, bf, itemsize) -> int:
    """Scoped-VMEM request for one grid step: double-buffered x, w and y
    blocks (TPU tiles pad the two minor dims to (8, 128)) plus the f32
    accumulator and per-tap temporaries, with headroom.  Never below the
    16 MiB default; capped well under v5e's 128 MiB of VMEM."""
    lanes = lambda d: round_up(d, 128)               # noqa: E731
    sub = lambda d: round_up(d, 8)                   # noqa: E731
    x_blk = in_rows * sub(wd) * lanes(c) * itemsize
    w_blk = taps * sub(c) * lanes(bf) * itemsize
    y_blk = sub(rows_out) * lanes(bf) * itemsize
    temps = sub(rows_out) * (3 * lanes(bf) + lanes(c)) * 4
    need = 2 * (x_blk + w_blk + y_blk) + temps
    return int(min(max(need * 5 // 4 + (4 << 20), 16 << 20), 100 << 20))
