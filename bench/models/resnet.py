"""ResNet-50 v1.5 (He et al., arXiv:1512.03385, Table 1, 50-layer column):
what only this network knows, for the shared benchmark code.  A
configuration file names it with `"model": "resnet"`; bench/cells.py finds
it by that name.  The module gives what bench/models/meshnet.py lists.

The network: a 7x7/2 stem conv to `stem_width` channels, BN, ReLU and a
3x3/2 max pool; then `stages[i]` bottleneck blocks of width `widths[i]`,
each 1x1 (branch2a), 3x3 (branch2b) and 1x1 to `expansion` x width
(branch2c) conv-BN, ReLU after the first two, and a shortcut that is the
block's input or, where the channels or the stride change, a 1x1 conv-BN
(branch1); the block's output is ReLU(shortcut + branch2c).  The first
block of every stage but the first has stride 2, on its 3x3 conv and its
shortcut (v1.5).  Then a mean over H and W and a dense head to `n_classes`
logits.

Data is ImageNet-shaped and synthetic: `in_channels`-deep `input_hw`^2
images of standard normals and int32 labels uniform in [0, n_classes).

The reference, written from the paper and the configuration alone:
- weights, in the program's key order, which is this convention:
  PRNGKey(seed) splits into (key, k_stem, unused); the stem conv takes
  k_stem; each block in order splits key into (key, k_block), and k_block
  into four: branch2a, branch2b, branch2c, branch1 (if the block has one);
  last the head takes the second half of one more split of key.  Convs are
  He-normal (std sqrt(2 / fan_in)); the head's weight is normal with std
  sqrt(1 / fan_in) and its bias 0; every BN gamma is 1 and beta 0.  The
  pytree: {"conv1": {"w"}, "bn1": {"gamma", "beta"}, "blocks": [{"conv1",
  "bn1", "conv2", "bn2", "conv3", "bn3", and "proj", "bn_proj" where the
  block has a branch1}], "head": {"w", "b"}};
- every conv and the max pool 'SAME' (TF style), the pool padded with
  -inf; BN in training mode over the whole global batch (N, H, W) with
  the two-pass variance;
- loss: the mean softmax cross-entropy of the logits against the labels.

Faults: "half_batch" takes the loss over the first half of the batch
only; "no_halo" convolves and pools each of `halo_parts` row blocks of H
alone, with the padding's edge where a neighbour's rows belong, as a
spatial split that skips its exchange does (where the rows do not split
into blocks of whole strides, the layer runs whole).
"""
from __future__ import annotations

import numpy as np

from flops import Conv

PROGRAM_KEYS = ("input_hw", "in_channels", "n_classes", "stages", "widths")


def batch_spec(config: dict, traffic: dict) -> dict:
    n, hw = traffic["batch"], config["input_hw"]
    return {"image": ((n, hw, hw, config["in_channels"]), "float32"),
            "label": ((n,), "int32")}


def draw(key, config: dict, traffic: dict) -> dict:
    import jax
    import jax.numpy as jnp

    spec = batch_spec(config, traffic)
    ki, kl = jax.random.split(key)
    return {"image": jax.random.normal(ki, spec["image"][0], jnp.float32),
            "label": jax.random.randint(kl, spec["label"][0], 0,
                                        config["n_classes"], jnp.int32)}


def blocks(config: dict):
    """(stage, block, stride, input channels, width) of every bottleneck
    block in order; stage counts from 2, as the layer names do."""
    c, out = config["stem_width"], []
    for s, (n, width) in enumerate(zip(config["stages"], config["widths"])):
        for b in range(n):
            out.append((s + 2, b, 2 if b == 0 and s > 0 else 1, c, width))
            c = width * config["expansion"]
    return out


def has_branch1(config: dict, stride: int, c: int, width: int) -> bool:
    return stride != 1 or c != width * config["expansion"]


def init(seed: int, config: dict):
    import jax
    import jax.numpy as jnp

    def normal(key, shape, fan_in, gain):
        std = np.float32(np.sqrt(gain / fan_in))
        return jax.random.normal(key, shape, jnp.float32) * std

    def conv(key, k, c, f):
        return {"w": normal(key, (k, k, c, f), k * k * c, 2.0)}

    def bn(c):
        return {"gamma": jnp.ones((c,), jnp.float32),
                "beta": jnp.zeros((c,), jnp.float32)}

    key, k_stem, _ = jax.random.split(jax.random.PRNGKey(seed), 3)
    stem = config["stem_width"]
    params = {"conv1": conv(k_stem, 7, config["in_channels"], stem),
              "bn1": bn(stem), "blocks": []}
    e = config["expansion"]
    for _, _, stride, c, width in blocks(config):
        key, kb = jax.random.split(key)
        ks = jax.random.split(kb, 4)
        p = {"conv1": conv(ks[0], 1, c, width), "bn1": bn(width),
             "conv2": conv(ks[1], 3, width, width), "bn2": bn(width),
             "conv3": conv(ks[2], 1, width, width * e), "bn3": bn(width * e)}
        if has_branch1(config, stride, c, width):
            p["proj"] = conv(ks[3], 1, c, width * e)
            p["bn_proj"] = bn(width * e)
        params["blocks"].append(p)
    c_out = config["widths"][-1] * e
    _, kh = jax.random.split(key)
    params["head"] = {
        "w": normal(kh, (c_out, config["n_classes"]), c_out, 1.0),
        "b": jnp.zeros((config["n_classes"],), jnp.float32)}
    return params


def loss(config: dict, prec, fault: str | None = None, halo_parts: int = 1):
    """loss_fn(params, batch) -> the mean loss; convolutions and the head
    at the `lax.Precision` `prec`."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    eps = config["bn_eps"]

    def rows(fn, x, s):
        if fault != "no_halo" or halo_parts == 1 or \
                x.shape[1] % (halo_parts * s):
            return fn(x)
        return jnp.concatenate(
            [fn(z) for z in jnp.split(x, halo_parts, axis=1)], axis=1)

    def conv(x, p, s):
        return rows(lambda z: lax.conv_general_dilated(
            z, p["w"], (s, s), "SAME",
            dimension_numbers=("NHWC", "HWIO", "NHWC"), precision=prec),
            x, s)

    def max_pool(x):
        return rows(lambda z: lax.reduce_window(
            z, -jnp.inf, lax.max, (1, 3, 3, 1), (1, 2, 2, 1), "SAME"), x, 2)

    def bn(x, p):
        mean = jnp.mean(x, axis=(0, 1, 2))
        var = jnp.mean(jnp.square(x - mean), axis=(0, 1, 2))
        return (x - mean) * lax.rsqrt(var + eps) * p["gamma"] + p["beta"]

    def relu(x):
        return jnp.maximum(x, 0)

    def loss_fn(params, batch):
        x = relu(bn(conv(batch["image"], params["conv1"], 2), params["bn1"]))
        x = max_pool(x)
        for p, (_, _, s, _, _) in zip(params["blocks"], blocks(config)):
            y = relu(bn(conv(x, p["conv1"], 1), p["bn1"]))
            y = relu(bn(conv(y, p["conv2"], s), p["bn2"]))
            y = bn(conv(y, p["conv3"], 1), p["bn3"])
            if "proj" in p:
                x = bn(conv(x, p["proj"], s), p["bn_proj"])
            x = relu(x + y)
        x = jnp.mean(x, axis=(1, 2))
        z = jnp.dot(x, params["head"]["w"], precision=prec) \
            + params["head"]["b"]
        y = batch["label"]
        if fault == "half_batch":
            z, y = z[:z.shape[0] // 2], y[:y.shape[0] // 2]
        logp = jax.nn.log_softmax(z)
        return -jnp.mean(jnp.take_along_axis(logp, y[:, None], axis=1))

    return loss_fn


def convs(config: dict) -> list[Conv]:
    """The network's convolutions in execution order, named as the
    program's plan layers: `conv1`, then per block `res{s}{b}_branch2a`,
    `_branch2b`, `_branch2c` and `_branch1` where it has one; last the head
    as a 1x1 conv on a 1x1 grid, `fc`."""
    hw, e = config["input_hw"], config["expansion"]
    out = [Conv("conv1", config["in_channels"], config["stem_width"], 7, 2,
                hw, hw)]
    hw = -(-hw // 4)                 # the stem conv's and max pool's strides
    for stage, b, stride, c, width in blocks(config):
        pre = f"res{stage}{chr(ord('a') + b)}_branch"
        out.append(Conv(pre + "2a", c, width, 1, 1, hw, hw))
        out.append(Conv(pre + "2b", width, width, 3, stride, hw, hw))
        hw_out = -(-hw // stride)
        out.append(Conv(pre + "2c", width, width * e, 1, 1, hw_out, hw_out))
        if has_branch1(config, stride, c, width):
            out.append(Conv(pre + "1", c, width * e, 1, stride, hw, hw))
        hw = hw_out
    out.append(Conv("fc", config["widths"][-1] * e, config["n_classes"], 1,
                    1, 1, 1))
    return out
