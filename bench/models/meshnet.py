"""The mesh-tangling CNN (arXiv:1903.06681 §VI): what only this network
knows, for the shared benchmark code.  A configuration file names it with
`"model": "meshnet"`; bench/cells.py finds it by that name.

A model module gives:

  PROGRAM_KEYS              the config file's keys the program's own config
                            must agree with (attributes of the same name)
  batch_spec(config, traffic) -> {key: (shape, dtype name)}
  draw(key, config, traffic) -> {key: array}, one batch from a raw key,
                            traced inside the pool's one jitted call
  init(seed, config)        the reference's weights
  loss(config, prec, fault, halo_parts) -> loss_fn(params, batch), the
                            reference's loss, with the planted faults
  convs(config)             the network's convolutions (bench/flops.py)

The network: six blocks of `convs_per_block` k x k conv-BN-ReLU layers,
stride 2 at each block's first conv, then a `pred_kernel` prediction conv
to `n_classes` logits per pixel of the `input_hw / 2^blocks` grid.

Data follows the program's synthetic mesh-tangling batches (the paper
trained its speed runs on synthetic data, §VI): an `in_channels`-deep
`input_hw`^2 image of standard normals, and a per-pixel tangle mask that
is 1 with probability `label_positive_rate`.

The reference, written from the paper and the configuration alone:
- weights: PRNGKey(seed), one split per conv in execution order, He-normal
  (std sqrt(2 / fan_in)); BN gamma 1 and beta 0;
- each body layer: 'SAME' conv, BN in training mode over the whole global
  batch (N, H, W) with the two-pass variance, ReLU; then the prediction
  conv;
- loss: per-pixel sigmoid binary cross-entropy, mean over every logit.

Faults: "half_batch" takes the loss over the first half of the batch
only; "no_halo" convolves each of `halo_parts` row blocks of H alone, with
zero rows where a neighbour's rows belong, as a spatial split that skips
its exchange does.
"""
from __future__ import annotations

import numpy as np

from flops import Conv

PROGRAM_KEYS = ("input_hw", "in_channels", "convs_per_block", "widths",
                "n_classes")


def batch_spec(config: dict, traffic: dict) -> dict:
    n, hw = traffic["batch"], config["input_hw"]
    out_hw = hw // 2 ** len(config["widths"])
    return {"image": ((n, hw, hw, config["in_channels"]), "float32"),
            "label": ((n, out_hw, out_hw, config["n_classes"]), "float32")}


def draw(key, config: dict, traffic: dict) -> dict:
    import jax
    import jax.numpy as jnp

    spec = batch_spec(config, traffic)
    ki, kl = jax.random.split(key)
    return {"image": jax.random.normal(ki, spec["image"][0], jnp.float32),
            "label": jax.random.bernoulli(kl, traffic["label_positive_rate"],
                                          spec["label"][0])
            .astype(jnp.float32)}


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


def loss(config: dict, prec, fault: str | None = None, halo_parts: int = 1):
    """loss_fn(params, batch) -> the mean loss; convolutions at the
    `lax.Precision` `prec`."""
    import jax.numpy as jnp
    from jax import lax

    eps = config["bn_eps"]

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

    return loss_fn


def convs(config: dict) -> list[Conv]:
    """The network's convolutions in execution order, from the config
    file's `input_hw`, `in_channels`, `widths`, `convs_per_block`,
    `kernel`, `pred_kernel` and `n_classes`."""
    out = []
    c, hw = config["in_channels"], config["input_hw"]
    for b, width in enumerate(config["widths"]):
        for i in range(config["convs_per_block"]):
            s = 2 if i == 0 else 1
            out.append(Conv(f"conv{b + 1}_{i + 1}", c, width,
                            config["kernel"], s, hw, hw))
            hw = -(-hw // s)
            c = width
    out.append(Conv("pred", c, config["n_classes"], config["pred_kernel"], 1,
                    hw, hw))
    return out
