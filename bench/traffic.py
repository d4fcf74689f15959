"""The one traffic generator: a pool of training batches from the seed.

A traffic file (bench/traffic/<name>.json) gives the global batch and the
pool size; the configuration's model module (bench/models/<model>.py) gives
each batch key's shape and dtype and draws one batch from a key, with what
else the traffic file states for it.  Every batch of the pool holds other
rows, so the first steps, which the reference follows, all differ.

The pool is drawn on the device in one compiled call per batch and held in
host memory: each step copies its batch to the chips, as a job does that
trains from a dataset cached in host RAM.  The same seed gives the same
pool, whatever the seed's size.
"""
from __future__ import annotations

import numpy as np

import cells


def data_key(seed: int) -> np.ndarray:
    """A raw threefry key (uint32[2]) for the data, derived from `seed` and
    kept apart from the program's weight key, PRNGKey(seed)."""
    ss = np.random.SeedSequence([abs(int(seed)), 0x7E4F, int(seed < 0)])
    return ss.generate_state(2, dtype=np.uint32)


def batch_pool(config: dict, traffic: dict, seed: int) -> list[dict]:
    """`traffic['pool']` batches of numpy arrays."""
    import jax
    import jax.numpy as jnp

    model = cells.model_of(config)
    draw = jax.jit(lambda key: model.draw(key, config, traffic))
    keys = jax.random.split(jnp.asarray(data_key(seed)), traffic["pool"])
    pool = []
    for k in keys:
        pool.append({name: np.asarray(v) for name, v in draw(k).items()})
    return pool
