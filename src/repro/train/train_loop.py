"""Train-step builders: mixed precision, gradient accumulation
(micro-batching), remat, cross-pod gradient compression.

`make_train_step` builds one jit-compiled SPMD step.  Sharding is pjit-style:
the caller provides PartitionSpecs for params and batch; the paper's
fine-grained primitives (spatial conv / ring attention / ...) live inside
the loss function as shard_map islands.

Gradient accumulation implements the out-of-core "micro-batching" the paper
cites ([43], §VII Memory pressure): the global batch is split into
`grad_accum` micro-batches scanned sequentially, trading time for activation
memory — composable with spatial parallelism, which shrinks per-sample
memory instead.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.optim.grad_compress import cross_pod_mean
from repro.optim.optimizer import Optimizer
from repro.utils import Precision, BF16


@dataclasses.dataclass(frozen=True)
class TrainStepConfig:
    grad_accum: int = 1
    precision: Precision = BF16
    remat: bool = False                  # rematerialize the loss fn
    pod_compression: str = "none"        # none | bf16 | int8_ef


def make_train_step(loss_fn: Callable, opt: Optimizer, mesh,
                    cfg: TrainStepConfig = TrainStepConfig(),
                    state_shardings=None):
    """loss_fn(params, batch) -> scalar loss (params in compute dtype),
    traced under the precision policy's conv/dot precision.

    Returns step(params, opt_state, ef_state, batch) ->
            (params, opt_state, ef_state, metrics).

    `state_shardings` — the shardings of (params, opt_state, ...) as
    placed — pins the updated params and optimizer state to their input
    layout.  Without it XLA
    picks output shardings from the step's own collectives (a CF layer's
    gradient leaves model-sharded), so the next call sees new input
    shardings and compiles the step again, and the donated buffers cannot
    be reused in place.
    """
    lfn = jax.checkpoint(loss_fn) if cfg.remat else loss_fn

    def fwd_bwd(params, batch):
        cparams = cfg.precision.cast_compute(params)
        with cfg.precision.scope():
            loss, grads = jax.value_and_grad(lfn)(cparams, batch)
        # master-dtype grads for the optimizer
        grads = jax.tree.map(
            lambda g, p: g.astype(p.dtype), grads, params)
        return loss, grads

    def step(params, opt_state, ef_state, batch):
        if cfg.grad_accum > 1:
            def split(x):
                return x.reshape((cfg.grad_accum,
                                  x.shape[0] // cfg.grad_accum) + x.shape[1:])
            micro = jax.tree.map(split, batch)

            def body(carry, mb):
                acc_loss, acc_grads = carry
                loss, grads = fwd_bwd(params, mb)
                return (acc_loss + loss,
                        jax.tree.map(jnp.add, acc_grads, grads)), None

            zero = jax.tree.map(jnp.zeros_like, params)
            (loss, grads), _ = jax.lax.scan(body, (0.0, zero), micro)
            loss = loss / cfg.grad_accum
            grads = jax.tree.map(lambda g: g / cfg.grad_accum, grads)
        else:
            loss, grads = fwd_bwd(params, batch)

        if cfg.pod_compression != "none" and "pod" in mesh.axis_names:
            grads, ef_state = cross_pod_mean(
                grads, mesh=mesh, method=cfg.pod_compression,
                error_feedback=ef_state)

        new_params, new_opt = opt.update(grads, opt_state, params)
        if state_shardings is not None:
            new_params, new_opt = jax.lax.with_sharding_constraint(
                (new_params, new_opt), tuple(state_shardings[:2]))
        gnorm = jnp.sqrt(sum(jnp.sum(jnp.square(g.astype(jnp.float32)))
                             for g in jax.tree.leaves(grads)))
        return new_params, new_opt, ef_state, {"loss": loss,
                                               "grad_norm": gnorm}

    return jax.jit(step, donate_argnums=(0, 1, 2))


def shard_tree(tree, mesh, spec_fn: Callable[[Any], P]):
    """device_put every leaf with the sharding given by spec_fn(leaf)."""
    return jax.tree.map(
        lambda x: jax.device_put(x, NamedSharding(mesh, spec_fn(x))), tree)


def fsdp_spec_for(shape, mesh_axis_size: int, axis: str = "data",
                  min_size: int = 2 ** 14) -> P:
    """ZeRO/FSDP rule: shard the largest evenly-divisible dim of every
    big tensor over the data axis; small tensors stay replicated."""
    size = 1
    for s in shape:
        size *= s
    if not shape or size < min_size:
        return P()
    for d in sorted(range(len(shape)), key=lambda d: -shape[d]):
        if shape[d] % mesh_axis_size == 0:
            spec = [None] * len(shape)
            spec[d] = axis
            return P(*spec)
    return P()
