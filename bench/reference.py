"""Plain reference of the mesh-tangling CNN's training step.

Written from the paper's description (arXiv:1903.06681 §VI) and the
configuration file alone; it imports nothing of the program and takes
nothing the program made.  Straightforward `jax.numpy` in float32, one
global array per tensor, convolutions at the configuration's matmul
precision:

- weights: PRNGKey(seed), one split per conv in execution order, He-normal
  (std sqrt(2 / fan_in)); BN gamma 1 and beta 0;
- each body layer: 'SAME' conv (stride 2 at a block's head), BN in training
  mode over the whole global batch (N, H, W) with the two-pass variance,
  ReLU; then a 1x1 prediction conv;
- loss: per-pixel sigmoid binary cross-entropy, mean over every logit;
- gradients by autodiff of that loss;
- SGD with momentum (mu <- m mu + g; p <- p - lr(t) mu) under a linear
  warm-up then cosine decay to `final_frac` of the base rate.

A run too large for one chip runs sample-parallel over the cell's chips
under XLA's own partitioner: the batch is split over a one-axis mesh and
everything else is replicated, so no halo or plan code is involved.

`fault` plants one of the benchmark's known faults in the reference for
reading its distance on the chip: "half_batch" takes the loss over the
first half of the batch only; "no_halo" convolves each of `halo_parts`
row blocks of H alone, with zero rows where a neighbour's rows belong, as
a spatial split that skips its exchange does.
"""
from __future__ import annotations

import numpy as np

def init(seed: int, config: dict):
    import jax
    import jax.numpy as jnp

    def he(key, k, c, f):
        std = np.float32(np.sqrt(2.0 / (k * k * c)))
        return jax.random.normal(key, (k, k, c, f), jnp.float32) * std

    key = jax.random.PRNGKey(seed)
    params = []
    c = config["in_channels"]
    for width in config["widths"]:
        for _ in range(config["convs_per_block"]):
            key, k1 = jax.random.split(key)
            params.append({"conv": {"w": he(k1, config["kernel"], c, width)},
                           "bn": {"gamma": jnp.ones((width,), jnp.float32),
                                  "beta": jnp.zeros((width,), jnp.float32)}})
            c = width
    key, k1 = jax.random.split(key)
    params.append({"conv": {"w": he(k1, config["pred_kernel"], c,
                                    config["n_classes"])}})
    return params


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
    import jax.numpy as jnp
    from jax import lax

    prec = {"default": lax.Precision.DEFAULT, "high": lax.Precision.HIGH,
            "highest": lax.Precision.HIGHEST}[precision]
    eps = config["bn_eps"]
    opt = config["optimizer"]

    def conv(x, w, s):
        def one(z):
            return lax.conv_general_dilated(
                z, w, (s, s), "SAME",
                dimension_numbers=("NHWC", "HWIO", "NHWC"), precision=prec)
        if fault != "no_halo" or halo_parts == 1:
            return one(x)
        return jnp.concatenate(
            [one(z) for z in jnp.split(x, halo_parts, axis=1)], axis=1)

    def bn(x, gamma, beta):
        mean = jnp.mean(x, axis=(0, 1, 2))
        var = jnp.mean(jnp.square(x - mean), axis=(0, 1, 2))
        return (x - mean) * lax.rsqrt(var + eps) * gamma + beta

    def loss_fn(params, batch):
        x = batch["image"]
        li = 0
        for _ in config["widths"]:
            for i in range(config["convs_per_block"]):
                lp = params[li]
                x = conv(x, lp["conv"]["w"], 2 if i == 0 else 1)
                x = jnp.maximum(bn(x, lp["bn"]["gamma"], lp["bn"]["beta"]),
                                0)
                li += 1
        z = conv(x, params[li]["conv"]["w"], 1)
        y = batch["label"]
        if fault == "half_batch":
            z, y = z[:z.shape[0] // 2], y[:y.shape[0] // 2]
        bce = jnp.maximum(z, 0) - z * y + jnp.log1p(jnp.exp(-jnp.abs(z)))
        return jnp.mean(bce)

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

        params = jax.device_put(init(seed, self.config), self.rep)
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
