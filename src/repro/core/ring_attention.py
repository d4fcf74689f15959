"""Sequence-parallel exact attention — the paper's spatial decomposition
applied to the transformer sequence dimension.

The activation tensors are block-partitioned along the sequence (the
"spatial" dimension of a transformer); each shard holds a Q/K/V block.  The
"halo" a query block needs is its causal past:

  * full/global attention   — the halo spans every predecessor shard, so the
    K/V blocks sweep the ring (`ppermute` per step) while an online-softmax
    accumulator merges partial results (ring attention).  Cost = (P-1)
    neighbor exchanges of the local K/V block — the paper's SR(·) halo terms
    with the block as the halo.

  * sliding-window attention (mixtral SWA, gemma2 local layers, hymba) — a
    query needs at most `window` past keys, i.e. a *constant-width halo* of
    ceil((window-1)/S_local) predecessor blocks.  This is the literal
    transformer instantiation of the paper's O-row conv halo: the ring stops
    after n_steps = 1 + that many exchanges instead of P.

  * bidirectional (encoders) — full ring sweep, no causal mask.

Exactness: results equal single-device attention up to fp accumulation
(verified in tests), mirroring the paper's exact-replication requirement.

Everything here runs *inside* shard_map over the sequence axis; the public
wrapper builds the shard_map.  bf16 inputs accumulate in fp32.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from repro.utils import cdiv, pcast_varying, shard_map

NEG_INF = -1e30  # large-negative instead of -inf: keeps masked softmax NaN-free


def _softcap(logits, cap):
    return cap * jnp.tanh(logits / cap) if cap else logits


def _block_attend(q, k, v, *, q_off, k_off, scale, causal, window, softcap,
                  m, l, o):
    """One (Q-block, KV-block) tile of online-softmax attention.

    q: (B, Sq, Hq, D)   k, v: (B, Sk, Hkv, D)   GQA via head grouping.
    m, l: (B, Hq, Sq)   o: (B, Sq, Hq, D) accumulators (fp32).
    q_off/k_off: global offsets of the blocks (for causal/window masks).
    """
    b, sq, hq, d = q.shape
    hkv = k.shape[2]
    g = hq // hkv
    qg = q.reshape(b, sq, hkv, g, d)
    s = jnp.einsum("bqhgd,bkhd->bhgqk", qg, k,
                   preferred_element_type=jnp.float32) * scale
    s = _softcap(s, softcap)

    qpos = q_off + jnp.arange(sq)[:, None]
    kpos = k_off + jnp.arange(k.shape[1])[None, :]
    mask = jnp.ones((sq, k.shape[1]), jnp.bool_)
    if causal:
        mask &= qpos >= kpos
    if window is not None:
        mask &= (qpos - kpos) < window
    s = jnp.where(mask[None, None, None], s, NEG_INF)
    s = s.reshape(b, hq, sq, k.shape[1])

    m_new = jnp.maximum(m, jnp.max(s, axis=-1))
    p = jnp.exp(s - m_new[..., None])
    corr = jnp.exp(m - m_new)
    l_new = l * corr + jnp.sum(p, axis=-1)
    pv = jnp.einsum("bhgqk,bkhd->bqhgd",
                    p.reshape(b, hkv, g, sq, k.shape[1]),
                    v.astype(jnp.float32),
                    preferred_element_type=jnp.float32)
    o_new = o * corr.transpose(0, 2, 1)[..., None] + pv.reshape(b, sq, hq, d)
    return m_new, l_new, o_new


def _ring_attention_local(q, k, v, *, axis_name, axis_size, vma_axes, scale,
                          causal, window, softcap, unroll=False):
    """Shard-local ring attention (inside shard_map over the seq axis)."""
    b, sl, hq, d = q.shape
    idx = lax.axis_index(axis_name)
    q_off = idx * sl

    if window is None:
        n_steps = axis_size
    else:
        n_steps = min(axis_size, 1 + cdiv(max(window - 1, 0), sl))

    def var(x):  # mark device-varying for shard_map's VMA tracking
        return pcast_varying(x, vma_axes)

    m = var(jnp.full((b, hq, sl), NEG_INF, jnp.float32))
    l = var(jnp.zeros((b, hq, sl), jnp.float32))
    o = var(jnp.zeros((b, sl, hq, d), jnp.float32))
    kv = jnp.concatenate([k, v], axis=-1)

    def step(carry, t):
        kv, m, l, o = carry
        src = (idx - t) % axis_size  # which shard's KV we currently hold
        k_t, v_t = jnp.split(kv, 2, axis=-1)
        m2, l2, o2 = _block_attend(
            q, k_t, v_t, q_off=q_off, k_off=src * sl, scale=scale,
            causal=causal, window=window, softcap=softcap, m=m, l=l, o=o)
        if causal:
            # shards strictly after us contribute nothing; skip their update
            # (the tile was fully masked anyway — this keeps l exact at 0+).
            use = src <= idx
            m, l, o = jax.tree.map(
                lambda new, old: jnp.where(use, new, old),
                (m2, l2, o2), (m, l, o))
        else:
            m, l, o = m2, l2, o2
        # rotate KV: shard i sends to i+1 so next step we hold (idx - t - 1)'s
        kv = lax.ppermute(
            kv, axis_name,
            [(i, (i + 1) % axis_size) for i in range(axis_size)])
        return (kv, m, l, o), None

    # remat each ring step: the scan's backward otherwise saves the fp32
    # attention probabilities of EVERY step (n_steps x B x Hq x Sl x Sl —
    # 16 GiB/device for gemma2 train_4k); recomputing them per step in the
    # backward sweep is the standard flash/ring-attention trade.
    (kv, m, l, o), _ = lax.scan(jax.checkpoint(step), (kv, m, l, o),
                                jnp.arange(n_steps),
                                unroll=n_steps if unroll else 1)
    out = o / jnp.maximum(l, 1e-30).transpose(0, 2, 1)[..., None]
    return out.astype(q.dtype)


def ring_attention(q, k, v, *, mesh, seq_axis: str | None, scale=None,
                   causal: bool = True, window: int | None = None,
                   softcap: float | None = None, batch_axes=("data",),
                   unroll: bool = False):
    """Exact attention with sequence sharded over `seq_axis`.

    q: (B, S, Hq, D), k/v: (B, S, Hkv, D) — S block-partitioned on seq_axis,
    B on batch_axes.  seq_axis=None falls back to single-shard attention
    (used as the oracle and for unsharded configs).
    """
    scale = scale if scale is not None else 1.0 / math.sqrt(q.shape[-1])
    if seq_axis is None:
        m = jnp.full(q.shape[:1] + (q.shape[2], q.shape[1]), NEG_INF,
                     jnp.float32)
        l = jnp.zeros_like(m)
        o = jnp.zeros(q.shape[:3] + (v.shape[-1],), jnp.float32)
        m, l, o = _block_attend(q, k, v, q_off=0, k_off=0, scale=scale,
                                causal=causal, window=window, softcap=softcap,
                                m=m, l=l, o=o)
        return (o / jnp.maximum(l, 1e-30).transpose(0, 2, 1)[..., None]
                ).astype(q.dtype)

    axis_size = dict(mesh.shape)[seq_axis]
    vma_axes = tuple(batch_axes) + (seq_axis,)
    fn = functools.partial(
        _ring_attention_local, axis_name=seq_axis, axis_size=axis_size,
        vma_axes=vma_axes, scale=scale, causal=causal, window=window,
        softcap=softcap, unroll=unroll)
    bspec = tuple(batch_axes) or None
    spec = P(bspec, seq_axis, None, None)
    return shard_map(fn, mesh=mesh, in_specs=(spec, spec, spec),
                     out_specs=spec)(q, k, v)
