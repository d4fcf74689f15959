"""Plain reference of a training step.

Written from the paper and the configuration file alone; it imports
nothing of the program and takes nothing the program made.  The network's
weights and loss come from the configuration's model module
(bench/models/<model>.py): straightforward `jax.numpy` in float32, one
global array per tensor, convolutions at the configuration's matmul
precision.  Shared here:

- gradients by autodiff of the model's loss;
- SGD with momentum (mu <- m mu + g; p <- p - lr(t) mu) under a linear
  warm-up then cosine decay to `final_frac` of the base rate.

A run too large for one chip runs sample-parallel over the cell's chips
under XLA's own partitioner: the batch is split over a one-axis mesh and
everything else is replicated, so no halo or plan code is involved.

`fault` plants one of the benchmark's known faults in the model's loss for
reading its distance on the chip ("half_batch", "no_halo": the model
module says what each does); `halo_parts` is the number of row blocks a
spatial split would make.
"""
from __future__ import annotations

import numpy as np

import cells


def lr_at(step, opt: dict, total: int):
    """Learning rate of update number `step` (1 for the first update)."""
    import jax.numpy as jnp
    t = step.astype(jnp.float32)
    warm = opt["lr"] * t / opt["warmup_steps"]
    prog = jnp.clip((t - opt["warmup_steps"]) /
                    max(total - opt["warmup_steps"], 1), 0.0, 1.0)
    f = opt["final_frac"]
    cos = opt["lr"] * (f + (1 - f) * 0.5 * (1 + jnp.cos(jnp.pi * prog)))
    return jnp.where(t < opt["warmup_steps"], warm, cos)


def make_step(config: dict, schedule_steps: int, precision: str,
              fault: str | None = None, halo_parts: int = 1):
    """step(params, mu, t, batch) -> (params, mu, loss, grads), jittable."""
    import jax
    from jax import lax

    prec = {"default": lax.Precision.DEFAULT, "high": lax.Precision.HIGH,
            "highest": lax.Precision.HIGHEST}[precision]
    opt = config["optimizer"]
    loss_fn = cells.model_of(config).loss(config, prec, fault, halo_parts)

    def step(params, mu, t, batch):
        loss, grads = jax.value_and_grad(loss_fn)(params, batch)
        mu = jax.tree.map(lambda m, g: opt["momentum"] * m + g, mu, grads)
        lr = lr_at(t + 1, opt, schedule_steps)
        params = jax.tree.map(lambda p, m: p - lr * m, params, mu)
        return params, mu, loss, grads

    return step


class Runner:
    """The reference step compiled for `devices` (the batch split over
    them, everything else replicated); `run` takes it from a seed's
    weights through the given batches."""

    def __init__(self, config: dict, schedule_steps: int, devices,
                 precision: str, fault: str | None = None,
                 halo_parts: int = 1):
        import jax
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

        mesh = Mesh(np.asarray(devices), ("b",))
        self.config = config
        self.rep = NamedSharding(mesh, P())
        self.rows = NamedSharding(mesh, P("b"))
        self.step = jax.jit(
            make_step(config, schedule_steps, precision, fault, halo_parts),
            in_shardings=(self.rep, self.rep, self.rep, self.rows),
            out_shardings=(self.rep, self.rep, self.rep, self.rep),
            donate_argnums=(0, 1))

    def run(self, seed: int, batches: list) -> dict:
        """Host readings: each step's loss, each leaf's first gradient
        norm, and each leaf's change over the run, keyed by leaf path."""
        import jax
        import jax.numpy as jnp

        params = jax.device_put(
            cells.model_of(self.config).init(seed, self.config), self.rep)
        p0 = leaves_host(params)
        mu = jax.tree.map(jnp.zeros_like, params)
        losses, first_grad = [], None
        for t, b in enumerate(batches):
            b = {k: jax.device_put(v, self.rows) for k, v in b.items()}
            params, mu, loss, grads = self.step(params, mu, jnp.int32(t), b)
            losses.append(float(loss))
            if first_grad is None:
                first_grad = leaves_host(grads)
            del grads, b
        p_end = leaves_host(params)
        del params, mu
        return {"losses": losses,
                "grad": {k: float(np.linalg.norm(v))
                         for k, v in first_grad.items()},
                "change": {k: float(np.linalg.norm(p_end[k] - p0[k]))
                           for k in p0}}


def leaves_host(tree) -> dict:
    """{leaf path: float64 numpy array} of a pytree on the device."""
    import jax
    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    vals = jax.device_get([v for _, v in flat])
    return {jax.tree_util.keystr(p): np.asarray(v, np.float64)
            for (p, _), v in zip(flat, vals)}
