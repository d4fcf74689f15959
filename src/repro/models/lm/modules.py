"""Transformer/SSM building blocks, sequence-parallel-aware.

Everything except attention and the SSM recurrence is pointwise in the
sequence dimension, so under the paper's spatial (=sequence) decomposition
it runs with zero communication; attention goes through
core.ring_attention (ring / windowed-halo) and the SSM through
core.seq_ssm (boundary-state halo).
"""
from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from repro.core.ring_attention import ring_attention
from repro.core.seq_ssm import seq_prefix_state
from repro.models.lm.config import LMConfig
from repro.utils import cdiv, pcast_varying, shard_map


# ---------------------------------------------------------------------------
# context: where/how the model is sharded
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class ShardCtx:
    mesh: Any = None
    seq_axis: str | None = None          # paper's spatial axis (None = off)
    batch_axes: tuple[str, ...] = ()
    tp_axis: str | None = None           # beyond-paper channel/filter axis
    unroll: bool = False                 # unroll inner comm scans (dry-run
                                         # probes: loop-free HLO accounting)

    @property
    def seq_size(self) -> int:
        if self.mesh is None or self.seq_axis is None:
            return 1
        axes = (self.seq_axis,) if isinstance(self.seq_axis, str) \
            else tuple(self.seq_axis)
        n = 1
        for a in axes:
            n *= dict(self.mesh.shape)[a]
        return n


# ---------------------------------------------------------------------------
# norms
# ---------------------------------------------------------------------------

def norm_init(cfg: LMConfig, d: int):
    if cfg.norm == "nonparam_ln":        # olmo: no learnable affine
        return jnp.zeros((0,), jnp.float32)
    return jnp.ones((d,), jnp.float32)


def norm_apply(cfg: LMConfig, w, x):
    xf = x.astype(jnp.float32)
    if cfg.norm == "rmsnorm":
        y = xf * lax.rsqrt(jnp.mean(jnp.square(xf), -1, keepdims=True) + 1e-6)
        return (y * w).astype(x.dtype)
    mean = jnp.mean(xf, -1, keepdims=True)
    var = jnp.mean(jnp.square(xf - mean), -1, keepdims=True)
    y = (xf - mean) * lax.rsqrt(var + 1e-5)
    if cfg.norm == "layernorm":
        y = y * w
    return y.astype(x.dtype)


# ---------------------------------------------------------------------------
# rotary embeddings
# ---------------------------------------------------------------------------

def rope(x, positions, theta: float):
    """x: (B, S, H, D) with D even; positions: (S,) or (B, S)."""
    d = x.shape[-1]
    freqs = jnp.exp(-jnp.arange(0, d, 2, dtype=jnp.float32)
                    * (math.log(theta) / d))
    if positions.ndim == 1:
        ang = positions[:, None].astype(jnp.float32) * freqs[None, :]
        ang = ang[None, :, None, :]                      # (1, S, 1, D/2)
    else:
        ang = positions[..., None].astype(jnp.float32) * freqs
        ang = ang[:, :, None, :]                         # (B, S, 1, D/2)
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# attention
# ---------------------------------------------------------------------------

def attn_init(key, cfg: LMConfig, dtype):
    d, hq, hkv, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    ks = jax.random.split(key, 4)
    sc = 1.0 / math.sqrt(d)
    p = {"wq": jax.random.normal(ks[0], (d, hq * hd), dtype) * sc,
         "wk": jax.random.normal(ks[1], (d, hkv * hd), dtype) * sc,
         "wv": jax.random.normal(ks[2], (d, hkv * hd), dtype) * sc,
         "wo": jax.random.normal(ks[3], (hq * hd, d), dtype)
         * (1.0 / math.sqrt(hq * hd))}
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((hq * hd,), dtype)
        p["bk"] = jnp.zeros((hkv * hd,), dtype)
        p["bv"] = jnp.zeros((hkv * hd,), dtype)
    return p


def attn_qkv(p, cfg: LMConfig, x, positions, rope_on=True):
    b, s, _ = x.shape
    hq, hkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    q = x @ p["wq"]
    k = x @ p["wk"]
    v = x @ p["wv"]
    if cfg.qkv_bias:
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    q = q.reshape(b, s, hq, hd)
    k = k.reshape(b, s, hkv, hd)
    v = v.reshape(b, s, hkv, hd)
    if rope_on:
        q = rope(q, positions, cfg.rope_theta)
        k = rope(k, positions, cfg.rope_theta)
    return q, k, v


def attn_apply(p, x, *, cfg: LMConfig, ctx: ShardCtx, positions,
               window: int | None, causal: bool = True,
               kv_override=None, return_kv: bool = False):
    """Self- (or cross-, via kv_override) attention with ring/halo comm."""
    q, k, v = attn_qkv(p, cfg, x, positions)
    if kv_override is not None:
        k, v = kv_override
    scale = cfg.attn_scale or 1.0 / math.sqrt(cfg.head_dim)
    o = ring_attention(q, k, v, mesh=ctx.mesh, seq_axis=ctx.seq_axis,
                       scale=scale, causal=causal, window=window,
                       softcap=cfg.attn_softcap,
                       batch_axes=ctx.batch_axes, unroll=ctx.unroll)
    b, s = x.shape[:2]
    out = o.reshape(b, s, cfg.n_heads * cfg.head_dim) @ p["wo"]
    if return_kv:
        return out, (k, v)
    return out


# ---------------------------------------------------------------------------
# MLP / MoE
# ---------------------------------------------------------------------------

def mlp_init(key, cfg: LMConfig, dtype):
    d, f = cfg.d_model, cfg.d_ff
    ks = jax.random.split(key, 3)
    sc_in, sc_out = 1.0 / math.sqrt(d), 1.0 / math.sqrt(f)
    p = {"wi": jax.random.normal(ks[0], (d, f), dtype) * sc_in,
         "wo": jax.random.normal(ks[2], (f, d), dtype) * sc_out}
    if cfg.mlp in ("swiglu", "geglu"):
        p["wg"] = jax.random.normal(ks[1], (d, f), dtype) * sc_in
    return p


def mlp_apply(p, x, cfg: LMConfig):
    h = x @ p["wi"]
    if cfg.mlp == "swiglu":
        h = jax.nn.silu(x @ p["wg"]) * h
    elif cfg.mlp == "geglu":
        h = jax.nn.gelu(x @ p["wg"], approximate=True) * h
    else:
        h = jax.nn.gelu(h, approximate=True)
    return h @ p["wo"]


def moe_init(key, cfg: LMConfig, dtype):
    d, f, e = cfg.d_model, cfg.d_ff, cfg.n_experts
    ks = jax.random.split(key, 4)
    sc_in, sc_out = 1.0 / math.sqrt(d), 1.0 / math.sqrt(f)
    return {"router": jax.random.normal(ks[0], (d, e), jnp.float32) * sc_in,
            "wi": jax.random.normal(ks[1], (e, d, f), dtype) * sc_in,
            "wg": jax.random.normal(ks[2], (e, d, f), dtype) * sc_in,
            "wo": jax.random.normal(ks[3], (e, f, d), dtype) * sc_out}


MOE_GROUP = 256      # tokens per routing group (GShard "group" dimension)


def moe_apply(p, x, cfg: LMConfig, ctx: ShardCtx):
    """GShard-style capacity-based top-k dispatch via one-hot matmuls
    (TPU-friendly: no scatter).

    Tokens are routed in fixed *groups* of MOE_GROUP consecutive sequence
    positions, so capacity/cumsum/dispatch tensors are (G, gs, e, cap) —
    O(tokens) total — instead of the O(tokens^2/e) global one-hot.  Group
    boundaries align with sequence shards (gs | S_shard), so under the
    paper's spatial decomposition all routing math is shard-local and the
    only cross-device traffic for MoE is the FSDP weight gather (or the
    all-to-all when the strategy engine picks expert parallelism instead).
    The grouping is a pure function of the shape — independent of the mesh —
    so sharded and unsharded execution are numerically identical.
    """
    b, s, d = x.shape
    e, k = cfg.n_experts, cfg.top_k
    gs = min(s, MOE_GROUP)
    ns = s // gs
    # keep (batch, seq-chunk) as separate dims: dim0 stays sharded over the
    # data axes and dim1 over the model axis, so every routing tensor below
    # shards cleanly (a merged b*s/gs dim defeats SPMD propagation and
    # replicates the dispatch one-hots on every device).
    xt = x.reshape(b, ns, gs, d)
    logits = (xt.astype(jnp.float32) @ p["router"])       # (b, ns, gs, e)
    probs = jax.nn.softmax(logits, -1)
    gate, idx = lax.top_k(probs, k)                       # (b, ns, gs, k)
    gate = gate / jnp.maximum(gate.sum(-1, keepdims=True), 1e-9)

    cap = max(1, int(cfg.capacity_factor * k * gs / e))
    onehot = jax.nn.one_hot(idx, e, dtype=jnp.float32)    # (b,ns,gs,k,e)
    # position of each (token, choice) within its expert's group buffer
    pos = jnp.cumsum(onehot.reshape(b, ns, gs * k, e), 2) \
        .reshape(b, ns, gs, k, e) - 1.0
    pos_sel = jnp.sum(pos * onehot, axis=-1)              # (b, ns, gs, k)
    keep = (pos_sel < cap)
    oh = onehot * keep[..., None]
    pos_c = jax.nn.one_hot(pos_sel, cap, dtype=jnp.float32) \
        * keep[..., None]                                  # (b,ns,gs,k,cap)
    disp = jnp.einsum("bgtke,bgtkc->bgtec", oh, pos_c)    # 0/1
    comb = jnp.einsum("bgtke,bgtk,bgtkc->bgtec", oh, gate, pos_c)

    xe = jnp.einsum("bgtec,bgtd->bgecd", disp.astype(x.dtype), xt)
    if ctx.tp_axis is not None and e % (dict(ctx.mesh.shape)[ctx.tp_axis]) \
            == 0:
        # expert parallelism (paper §III-D filter parallelism): dispatched
        # tokens all-to-all onto the expert shards; expert weights stay
        # sharded on E and are never gathered.
        espec = P(tuple(ctx.batch_axes) or None, None, ctx.tp_axis, None,
                  None)
        xe = lax.with_sharding_constraint(xe, espec)
    h = jnp.einsum("bgecd,edf->bgecf", xe, p["wi"])
    if cfg.mlp in ("swiglu", "geglu"):
        g = jnp.einsum("bgecd,edf->bgecf", xe, p["wg"])
        act = jax.nn.silu if cfg.mlp == "swiglu" else \
            functools.partial(jax.nn.gelu, approximate=True)
        h = act(g) * h
    ye = jnp.einsum("bgecf,efd->bgecd", h, p["wo"])
    if ctx.tp_axis is not None and e % (dict(ctx.mesh.shape)[ctx.tp_axis]) \
            == 0:
        ye = lax.with_sharding_constraint(
            ye, P(tuple(ctx.batch_axes) or None, None, ctx.tp_axis, None,
                  None))
    y = jnp.einsum("bgtec,bgecd->bgtd", comb.astype(x.dtype), ye)
    return y.reshape(b, s, d)


# ---------------------------------------------------------------------------
# SSD (mamba2) — chunked state-space duality
# ---------------------------------------------------------------------------

def ssm_init(key, cfg: LMConfig, dtype):
    d, di, ds, h = cfg.d_model, cfg.d_inner, cfg.ssm_state, cfg.ssm_heads
    conv_dim = di + 2 * ds
    ks = jax.random.split(key, 4)
    return {
        "in_proj": jax.random.normal(ks[0], (d, 2 * di + 2 * ds + h), dtype)
        / math.sqrt(d),
        "conv_w": jax.random.normal(ks[1], (cfg.ssm_conv, conv_dim), dtype)
        / math.sqrt(cfg.ssm_conv),
        "conv_b": jnp.zeros((conv_dim,), dtype),
        "dt_bias": jnp.zeros((h,), jnp.float32),
        "A_log": jnp.log(jnp.linspace(1.0, 16.0, h).astype(jnp.float32)),
        "D": jnp.ones((h,), jnp.float32),
        "gate_norm": jnp.ones((di,), jnp.float32),
        "out_proj": jax.random.normal(ks[3], (di, d), dtype) / math.sqrt(di),
    }


def _match_vma(x, like):
    """Mark x varying over the same manual axes as `like` (shard_map VMA)."""
    return pcast_varying(x, tuple(jax.typeof(like).vma))


def _ssd_chunked(xdt, la, B, C, chunk: int, h0=None):
    """Exact chunked SSD scan.

    xdt: (b, l, h, p)  dt-scaled inputs;  la: (b, l, h) log-decay;
    B, C: (b, l, n).  h0: optional initial state (b, h, p, n).
    Returns y: (b, l, h, p), h_final: (b, h, p, n).
    """
    b, l, h, p = xdt.shape
    n = B.shape[-1]
    chunk = min(chunk, l)
    while l % chunk:            # largest divisor of l not exceeding `chunk`
        chunk -= 1
    nc = cdiv(l, chunk)
    xz = xdt.reshape(b, nc, chunk, h, p)
    laz = la.reshape(b, nc, chunk, h)
    Bz = B.reshape(b, nc, chunk, n)
    Cz = C.reshape(b, nc, chunk, n)

    cum = jnp.cumsum(laz, axis=2)                       # (b,nc,cl,h)
    # intra-chunk: y_i += sum_{j<=i} C_i.B_j exp(cum_i - cum_j) xdt_j
    seg = cum[:, :, :, None, :] - cum[:, :, None, :, :]  # (b,nc,i,j,h)
    mask = jnp.tril(jnp.ones((chunk, chunk), bool))
    # mask the *exponent*, not the result: exp of the (positive) upper
    # triangle overflows and 0*inf => NaN in the backward pass otherwise.
    seg = jnp.where(mask[None, None, :, :, None], seg, -1e30)
    decay = jnp.exp(seg)
    G = jnp.einsum("bzin,bzjn->bzij", Cz, Bz)
    y = jnp.einsum("bzij,bzijh,bzjhp->bzihp", G, decay, xz)

    # chunk summaries: state contributed by each chunk (zero inflow)
    dec_end = jnp.exp(cum[:, :, -1:, :] - cum)           # (b,nc,cl,h)
    S = jnp.einsum("bzjhp,bzjn,bzjh->bzhpn", xz, Bz, dec_end)
    a_tot = jnp.exp(cum[:, :, -1, :])                    # (b,nc,h)

    # inter-chunk recurrence over chunks
    def scan_fn(hprev, inp):
        a_z, S_z = inp
        hnew = hprev * a_z[..., None, None] + S_z
        return hnew, hprev
    h_init = _match_vma(jnp.zeros((b, h, p, n), jnp.float32), xdt) \
        if h0 is None else h0.astype(jnp.float32)
    a_sw = jnp.moveaxis(a_tot, 1, 0)                     # (nc,b,h)
    S_sw = jnp.moveaxis(S, 1, 0).astype(jnp.float32)     # (nc,b,h,p,n)
    h_fin, h_in = lax.scan(scan_fn, h_init, (a_sw, S_sw))
    h_in = jnp.moveaxis(h_in, 0, 1)                      # (b,nc,h,p,n)

    # inflowing-state contribution to each position
    y_inter = jnp.einsum("bzin,bzhpn,bzih->bzihp", Cz,
                         h_in.astype(xdt.dtype),
                         jnp.exp(cum).astype(xdt.dtype))
    y = (y + y_inter).reshape(b, l, h, p)
    return y, h_fin


def _ssd_local(x, p, cfg: LMConfig, *, axis_name, axis_size, conv_tail=None):
    """Shard-local SSD block body (inside shard_map when seq-sharded)."""
    b, l, d = x.shape
    di, ds, h = cfg.d_inner, cfg.ssm_state, cfg.ssm_heads
    zxbcdt = x @ p["in_proj"]
    z, xbc, dt = jnp.split(zxbcdt, [di, 2 * di + 2 * ds], axis=-1)

    # depthwise causal conv over the sequence; under sequence sharding the
    # (ssm_conv-1)-sample tail of the left neighbor is a literal halo.
    k = cfg.ssm_conv
    if axis_name is not None:
        from repro.core.halo import halo_exchange
        xbc_pad = halo_exchange(xbc, 1, k - 1, 0, axis_name, axis_size)
    else:
        xbc_pad = jnp.pad(xbc, ((0, 0), (k - 1, 0), (0, 0)))
    idx = jnp.arange(l)[:, None] + jnp.arange(k)[None, :]
    windows = xbc_pad[:, idx]                            # (b, l, k, conv)
    xbc = jax.nn.silu(jnp.einsum("blkc,kc->blc", windows, p["conv_w"])
                      + p["conv_b"])

    xin, B, C = jnp.split(xbc, [di, di + ds], axis=-1)
    xin = xin.reshape(b, l, h, cfg.ssm_head_dim)
    dt = jax.nn.softplus(dt.astype(jnp.float32) + p["dt_bias"])  # (b,l,h)
    A = -jnp.exp(p["A_log"])
    la = dt * A                                          # log decay
    xdt = xin * dt[..., None].astype(xin.dtype)

    if axis_name is None:
        y, _ = _ssd_chunked(xdt, la, B, C, cfg.ssm_chunk)
    else:
        # local pass from zero state -> per-shard summary -> boundary halo
        y0, s_loc = _ssd_chunked(xdt, la, B, C, cfg.ssm_chunk)
        cum_all = jnp.cumsum(la, axis=1)                 # (b,l,h)
        a_tot = jnp.exp(cum_all[:, -1])[:, :, None, None]  # (b,h,1,1)
        h_in = seq_prefix_state(a_tot, s_loc, axis_name, axis_size)
        y_in = jnp.einsum("bln,bhpn,blh->blhp", C, h_in.astype(xdt.dtype),
                          jnp.exp(cum_all).astype(xdt.dtype))
        y = y0 + y_in

    y = y + p["D"][None, None, :, None].astype(y.dtype) * xin
    y = y.reshape(b, l, di)
    y = y * jax.nn.silu(z)
    yf = y.astype(jnp.float32)
    y = (yf * lax.rsqrt(jnp.mean(jnp.square(yf), -1, keepdims=True) + 1e-6)
         * p["gate_norm"]).astype(x.dtype)
    return y @ p["out_proj"]


def ssm_apply(p, x, cfg: LMConfig, ctx: ShardCtx):
    if ctx.seq_axis is None or ctx.seq_size == 1:
        return _ssd_local(x, p, cfg, axis_name=None, axis_size=1)
    spec = P(tuple(ctx.batch_axes) or None, ctx.seq_axis, None)
    fn = functools.partial(_ssd_local, cfg=cfg, axis_name=ctx.seq_axis,
                           axis_size=ctx.seq_size)
    pspec = jax.tree.map(lambda _: P(), p)
    return shard_map(lambda x, p: fn(x, p), mesh=ctx.mesh,
                     in_specs=(spec, pspec), out_specs=spec)(x, p)


def ssm_decode_step(p, x, cfg: LMConfig, state, conv_buf):
    """One-token SSD update.  x: (b, 1, d); state: (b, h, p, n);
    conv_buf: (b, k-1, conv_dim) previous inputs."""
    b = x.shape[0]
    di, ds, h = cfg.d_inner, cfg.ssm_state, cfg.ssm_heads
    zxbcdt = x[:, 0] @ p["in_proj"]
    z, xbc, dt = jnp.split(zxbcdt, [di, 2 * di + 2 * ds], axis=-1)
    win = jnp.concatenate([conv_buf, xbc[:, None]], axis=1)  # (b,k,conv)
    new_buf = win[:, 1:]
    xbc = jax.nn.silu(jnp.einsum("bkc,kc->bc", win, p["conv_w"])
                      + p["conv_b"])
    xin, B, C = jnp.split(xbc, [di, di + ds], axis=-1)
    xin = xin.reshape(b, h, cfg.ssm_head_dim)
    dt = jax.nn.softplus(dt.astype(jnp.float32) + p["dt_bias"])  # (b,h)
    a = jnp.exp(dt * (-jnp.exp(p["A_log"])))                     # (b,h)
    xdt = xin * dt[..., None].astype(xin.dtype)
    state = state * a[..., None, None] \
        + jnp.einsum("bhp,bn->bhpn", xdt, B).astype(jnp.float32)
    y = jnp.einsum("bhpn,bn->bhp", state.astype(xin.dtype), C)
    y = y + p["D"][None, :, None].astype(y.dtype) * xin
    y = y.reshape(b, di) * jax.nn.silu(z)
    yf = y.astype(jnp.float32)
    y = (yf * lax.rsqrt(jnp.mean(jnp.square(yf), -1, keepdims=True) + 1e-6)
         * p["gate_norm"]).astype(x.dtype)
    return (y @ p["out_proj"])[:, None], state, new_buf
