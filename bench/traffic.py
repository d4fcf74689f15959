"""The one traffic generator: a pool of training batches from the seed.

A traffic file (bench/traffic/<name>.json) gives the global batch and the
pool size; the configuration gives the sample shape.  Samples follow the
program's synthetic mesh-tangling data (the paper trained its speed runs on
synthetic data, §VI): an `in_channels`-deep `input_hw`^2 image of standard
normals, and a per-pixel tangle mask on the prediction grid that is 1 with
probability `label_positive_rate`.  Every batch of the pool holds other
rows, so the first steps, which the reference follows, all differ.

The pool is drawn on the device in one compiled call per batch and held in
host memory: each step copies its batch to the chips, as a job does that
trains from a dataset cached in host RAM.  The same seed gives the same
pool, whatever the seed's size.
"""
from __future__ import annotations

import numpy as np


def data_key(seed: int) -> np.ndarray:
    """A raw threefry key (uint32[2]) for the data, derived from `seed` and
    kept apart from the program's weight key, PRNGKey(seed)."""
    ss = np.random.SeedSequence([abs(int(seed)), 0x7E4F, int(seed < 0)])
    return ss.generate_state(2, dtype=np.uint32)


def batch_shapes(config: dict, traffic: dict) -> dict:
    n, hw = traffic["batch"], config["input_hw"]
    out_hw = hw // 2 ** len(config["widths"])
    return {"image": (n, hw, hw, config["in_channels"]),
            "label": (n, out_hw, out_hw, config["n_classes"])}


def batch_pool(config: dict, traffic: dict, seed: int) -> list[dict]:
    """`traffic['pool']` batches of numpy float32 arrays."""
    import jax
    import jax.numpy as jnp

    shapes = batch_shapes(config, traffic)
    rate = traffic["label_positive_rate"]

    @jax.jit
    def draw(key):
        ki, kl = jax.random.split(key)
        return {"image": jax.random.normal(ki, shapes["image"], jnp.float32),
                "label": jax.random.bernoulli(kl, rate, shapes["label"])
                .astype(jnp.float32)}

    keys = jax.random.split(jnp.asarray(data_key(seed)), traffic["pool"])
    pool = []
    for k in keys:
        pool.append({name: np.asarray(v) for name, v in draw(k).items()})
    return pool
