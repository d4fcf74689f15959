"""Ring vocab-parallel embedding + cross-entropy (hillclimb optimization —
the paper's §III-D channel/filter parallelism applied to the embedding,
executed as a ring exactly like the spatial halo sweeps).

Baseline lowering materializes the (B, S, V) logits (2.1 GiB/device bf16
for gemma2 train_4k, x2 again in fp32 for the stable CE) and all-gathers
the tied (V, d) embedding for the output matmul.  Here the embedding stays
V-sharded on the model axis and *rotates around the ring*; each sequence
shard streams its softmax statistics (running max / sum-exp / gold score)
over the visiting vocab blocks:

  transient per step:  (B, S_l, V/P) logits chunk — P^2 x smaller than the
                       global logits tensor;
  collective traffic:  one full table rotation (same bytes the baseline's
                       embedding all-gather already paid) — and the logits
                       never exist.

Exactness: equals the dense path up to fp accumulation order (tests).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from repro.models.lm.config import LMConfig
from repro.utils import pcast_varying, shard_map


def _ring(x, axis, axis_size):
    return lax.ppermute(
        x, axis, [(i, (i + 1) % axis_size) for i in range(axis_size)])


def _vma(x, like):
    return pcast_varying(x, tuple(jax.typeof(like).vma))


def _lookup_local(tokens, table, *, axis, axis_size, unroll):
    """tokens: (B, S_l) local block; table: (V/P, d) local vocab rows.
    The table blocks rotate; each step contributes the rows it owns."""
    vshard = table.shape[0]
    idx = lax.axis_index(axis)
    x = _vma(jnp.zeros(tokens.shape + (table.shape[1],), table.dtype),
             tokens)

    def step(carry, t):
        tbl, x = carry
        src = (idx - t) % axis_size
        lo = src * vshard
        local = jnp.clip(tokens - lo, 0, vshard - 1)
        owns = (tokens >= lo) & (tokens < lo + vshard)
        x = x + jnp.where(owns[..., None], tbl[local], 0)
        return (_ring(tbl, axis, axis_size), x), None

    (_, x), _ = lax.scan(jax.checkpoint(step), (table, x),
                         jnp.arange(axis_size),
                         unroll=axis_size if unroll else 1)
    return x


def embed_lookup(table, cfg: LMConfig, tokens, ctx, seq_axis="model"):
    mesh = ctx.mesh
    n = dict(mesh.shape)[seq_axis]
    if table.shape[0] % n:   # pad (rows beyond the real vocab never match)
        table = jnp.pad(table, ((0, n - table.shape[0] % n), (0, 0)))
    fn = functools.partial(_lookup_local, axis=seq_axis, axis_size=n,
                           unroll=ctx.unroll)
    bspec = tuple(ctx.batch_axes) or None
    return shard_map(
        fn, mesh=mesh,
        in_specs=(P(bspec, seq_axis), P(seq_axis, None)),
        out_specs=P(bspec, seq_axis, None))(tokens, table)


def _logits_chunk(x, tbl, lo, *, scale, softcap, v_real, vshard):
    logits = ((x * scale) @ tbl.T.astype(x.dtype)).astype(jnp.float32)
    if softcap:
        logits = softcap * jnp.tanh(logits / softcap)
    if v_real % vshard:
        pad = lo + jnp.arange(vshard) >= v_real
        logits = jnp.where(pad[None, None], -1e30, logits)
    return logits


def _make_xent_ring(*, axis, axis_size, scale, softcap, unroll, v_real,
                    vshard, batch_axes=()):
    """(B,S_l) per-token CE via a table ring, with a custom VJP whose
    backward *re-rotates* the table instead of saving per-step residuals:
    forward keeps only (m, se, gold) statistics; backward recomputes each
    logits chunk, emits dlogits = softmax - onehot, accumulates dx locally
    and sends each table block's cotangent around the ring so it arrives
    home after the full rotation.  O(B*S + V/P) memory — no logits tensor,
    no stacked residuals (the flash-attention trick applied to the CE)."""

    def ring_stats(x, tbl, lbl, valid):
        idx = lax.axis_index(axis)
        b, sl, _ = x.shape
        m0 = _vma(jnp.full((b, sl), -1e30, jnp.float32), x)
        se0 = _vma(jnp.zeros((b, sl), jnp.float32), x)
        g0 = _vma(jnp.zeros((b, sl), jnp.float32), x)

        def step(carry, t):
            tblc, m, se, gold = carry
            lo = ((idx - t) % axis_size) * vshard
            logits = _logits_chunk(x, tblc, lo, scale=scale,
                                   softcap=softcap, v_real=v_real,
                                   vshard=vshard)
            m_new = jnp.maximum(m, jnp.max(logits, -1))
            corr = jnp.exp(m - m_new)
            se = se * corr + jnp.sum(jnp.exp(logits - m_new[..., None]), -1)
            local = jnp.clip(lbl - lo, 0, vshard - 1)
            owns = (lbl >= lo) & (lbl < lo + vshard)
            g = jnp.take_along_axis(logits, local[..., None], -1)[..., 0]
            gold = gold + jnp.where(owns, g, 0.0)
            return (_ring(tblc, axis, axis_size), m_new, se, gold), None

        (_, m, se, gold), _ = lax.scan(
            step, (tbl, m0, se0, g0), jnp.arange(axis_size),
            unroll=axis_size if unroll else 1)
        return m, se, gold

    @jax.custom_vjp
    def xent_ring(x, tbl, lbl, valid):
        m, se, gold = ring_stats(x, tbl, lbl, valid)
        logz = m + jnp.log(jnp.maximum(se, 1e-30))
        return jnp.where(valid, logz - gold, 0.0)

    def fwd(x, tbl, lbl, valid):
        m, se, gold = ring_stats(x, tbl, lbl, valid)
        logz = m + jnp.log(jnp.maximum(se, 1e-30))
        return (jnp.where(valid, logz - gold, 0.0),
                (x, tbl, lbl, valid, m, se))

    def bwd(res, g):
        x, tbl, lbl, valid, m, se = res
        idx = lax.axis_index(axis)
        gv = (g * valid).astype(jnp.float32)            # (B, S_l)
        dx0 = _vma(jnp.zeros(x.shape, jnp.float32), x)
        dtbl0 = _vma(jnp.zeros(tbl.shape, jnp.float32), x)

        def step(carry, t):
            tblc, dtblc, dx = carry
            lo = ((idx - t) % axis_size) * vshard
            logits = _logits_chunk(x, tblc, lo, scale=scale,
                                   softcap=softcap, v_real=v_real,
                                   vshard=vshard)
            p = jnp.exp(logits - m[..., None]) / \
                jnp.maximum(se, 1e-30)[..., None]
            local = jnp.clip(lbl - lo, 0, vshard - 1)
            owns = (lbl >= lo) & (lbl < lo + vshard)
            onehot = (jax.nn.one_hot(local, vshard, dtype=jnp.float32)
                      * owns[..., None])
            dlogits = gv[..., None] * (p - onehot)      # (B, S_l, V/P)
            if softcap:   # d tanh-cap: (1 - (logits/cap)^2)
                dlogits = dlogits * (1.0 - jnp.square(logits / softcap))
            if v_real % vshard:   # padded rows: kill 0 * inf from the cap
                pad = lo + jnp.arange(vshard) >= v_real
                dlogits = jnp.where(pad[None, None], 0.0, dlogits)
            b, sl, vs = dlogits.shape
            dlf = dlogits.reshape(b * sl, vs)
            dx = dx + scale * (dlf @ tblc.astype(jnp.float32)) \
                .reshape(b, sl, -1)
            # flat 2-D matmul: einsum("bsv,bsd->vd") would materialize a
            # (b, v, d) partial-product tensor (3.4 GiB here)
            dtblc = dtblc + scale * \
                (dlf.T @ x.reshape(b * sl, -1).astype(jnp.float32))
            return (_ring(tblc, axis, axis_size),
                    _ring(dtblc, axis, axis_size), dx), None

        (_, dtbl, dx), _ = lax.scan(
            step, (tbl, dtbl0, dx0), jnp.arange(axis_size),
            unroll=axis_size if unroll else 1)
        # after a full rotation every block's cotangent is back home; the
        # table is replicated over the batch axes, so its cotangent sums
        # across them (the usual replicated-param psum).
        if batch_axes:
            dtbl = lax.psum(dtbl, batch_axes)
        return dx.astype(x.dtype), dtbl.astype(tbl.dtype), None, None

    xent_ring.defvjp(fwd, bwd)
    return xent_ring


def _xent_local(x, labels, table, *, axis, axis_size, all_axes, scale,
                softcap, unroll, v_real):
    """x: (B, S_l, d); labels: (B, S_l) with -1 = unscored; table (V/P, d).
    Rows >= v_real are padding (vocab rounded up to the shard count)."""
    vshard = table.shape[0]
    valid = labels >= 0
    lbl = jnp.where(valid, labels, 0)
    ring = _make_xent_ring(axis=axis, axis_size=axis_size, scale=scale,
                           softcap=softcap, unroll=unroll, v_real=v_real,
                           vshard=vshard,
                           batch_axes=tuple(a for a in all_axes
                                            if a != axis))
    per_tok = ring(x, table, lbl, valid)
    s = lax.psum(jnp.sum(per_tok), all_axes)
    n = lax.psum(jnp.sum(valid.astype(jnp.float32)), all_axes)
    return s, n


def xent_loss(table, cfg: LMConfig, x, labels, ctx, seq_axis="model",
              embed_scale: float = 1.0):
    """Mean next-token CE without materializing global logits.

    x: final hidden states (B, S, d) sequence-sharded; labels (B, S) with
    -1 marking unscored positions; table (V, d) sharded P(seq_axis, None).
    """
    mesh = ctx.mesh
    nsh = dict(mesh.shape)[seq_axis]
    v_real = table.shape[0]
    if v_real % nsh:     # pad the vocab to the shard count (Megatron-style)
        pad = nsh - v_real % nsh
        table = jnp.pad(table, ((0, pad), (0, 0)))
    all_axes = tuple(ctx.batch_axes) + (seq_axis,)
    fn = functools.partial(_xent_local, axis=seq_axis, axis_size=nsh,
                           all_axes=all_axes, scale=embed_scale,
                           softcap=cfg.final_softcap, unroll=ctx.unroll,
                           v_real=v_real)
    bspec = tuple(ctx.batch_axes) or None
    s, n = shard_map(
        fn, mesh=mesh,
        in_specs=(P(bspec, seq_axis, None), P(bspec, seq_axis),
                  P(seq_axis, None)),
        out_specs=(P(), P()))(x, labels, table)
    return s / n
