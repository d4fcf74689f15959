"""ResNet-50 (He et al.) for ImageNet-1K — the paper's §VI-B2 workload.

Functional implementation on the distribution-aware layers; `apply` executes
a `NetworkPlan` (core.plan): a per-layer distribution for every conv/pool —
keyed by the same names `resnet_graph` exports to the strategy optimizer —
with explicit §III-C reshard points at distribution changes.  Per-layer
entries may be `CFSharding`s (§III-D channel/filter parallelism,
core.channel_conv): the optimizer discovers those for the res4/res5 blocks,
where 7x7 feature maps stop admitting spatial splits but C reaches
1024/2048.  A legacy single `ConvSharding` is accepted too (lowered to a
uniform plan), which runs the whole network under one sample/spatial/hybrid
distribution exactly as before (paper Table III uses 32 samples per 1/2/4
GPUs).

`resnet_graph` exports the branchy layer DAG consumed by the strategy
optimizer's longest-path-first pass (paper §V-C).

Every plan layer runs under ``trace.layer_context(name)``, as in
models.cnn.meshnet, so the compiled HLO's ``op_name`` carries the layer:
``conv1`` (stem conv, BN, ReLU), ``pool1``, ``res{s}{b}_branch2a/2b/2c``
and ``_branch1`` (conv and the BN after it, and the ReLU where there is
one), ``res{s}{b}`` (the residual add and its ReLU, Caffe's name for the
sum), ``pool5`` (global average pool) and ``fc`` (the head).
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import networkx as nx

from repro.core import trace as trace_lib
from repro.core.perfmodel import ConvLayer
from repro.core.spatial_conv import ConvSharding
from repro.models.cnn import layers as L

STAGES = (3, 4, 6, 3)
WIDTHS = (64, 128, 256, 512)
EXPANSION = 4


@dataclasses.dataclass(frozen=True)
class ResNetConfig:
    name: str = "resnet50"
    input_hw: int = 224
    in_channels: int = 3
    n_classes: int = 1000
    stages: tuple = STAGES
    widths: tuple = WIDTHS
    bn_scope: str = "local"


RESNET50 = ResNetConfig()


def _bottleneck_init(key, c_in, width, stride, dtype):
    ks = jax.random.split(key, 4)
    p = {"conv1": L.conv_init(ks[0], 1, c_in, width, dtype),
         "bn1": L.bn_init(width, dtype),
         "conv2": L.conv_init(ks[1], 3, width, width, dtype),
         "bn2": L.bn_init(width, dtype),
         "conv3": L.conv_init(ks[2], 1, width, width * EXPANSION, dtype),
         "bn3": L.bn_init(width * EXPANSION, dtype)}
    if c_in != width * EXPANSION or stride != 1:
        p["proj"] = L.conv_init(ks[3], 1, c_in, width * EXPANSION, dtype)
        p["bn_proj"] = L.bn_init(width * EXPANSION, dtype)
    return p


def init(key, cfg: ResNetConfig = RESNET50, dtype=jnp.float32):
    key, k1, k2 = jax.random.split(key, 3)
    params = {"conv1": L.conv_init(k1, 7, cfg.in_channels, 64, dtype),
              "bn1": L.bn_init(64, dtype),
              "blocks": [],
              "head": None}
    c_in = 64
    for s, (n_blocks, width) in enumerate(zip(cfg.stages, cfg.widths)):
        for b in range(n_blocks):
            key, kb = jax.random.split(key)
            stride = 2 if (b == 0 and s > 0) else 1
            params["blocks"].append(
                _bottleneck_init(kb, c_in, width, stride, dtype))
            c_in = width * EXPANSION
    key, kh = jax.random.split(key)
    params["head"] = L.dense_init(kh, c_in, cfg.n_classes, dtype)
    return params


def _bottleneck_apply(p, x, *, pre, stride, plan, mesh, scope, overlap):
    """`pre` is the block's name prefix (e.g. "res3a_branch"): convs are
    named pre+"2a"/"2b"/"2c" and the projection pre+"1", matching
    `resnet_graph`, so the plan addresses every conv individually."""
    def conv(name, pp, z, s):
        z = plan.reshard(z, name, mesh)
        return L.conv_apply(pp, z, stride=s, sharding=plan.sharding(name),
                            mesh=mesh, overlap=overlap)

    def bn(name, pp, z):
        shb = plan.sharding(name).fit(z.shape[1], z.shape[2], 1, 1, mesh)
        return L.bn_apply(pp, z, sharding=shb, mesh=mesh, scope=scope)

    with trace_lib.layer_context(pre + "2a"):
        y = conv(pre + "2a", p["conv1"], x, 1)
        y = L.relu(bn(pre + "2a", p["bn1"], y))
    with trace_lib.layer_context(pre + "2b"):
        y = conv(pre + "2b", p["conv2"], y, stride)
        y = L.relu(bn(pre + "2b", p["bn2"], y))
    with trace_lib.layer_context(pre + "2c"):
        y = conv(pre + "2c", p["conv3"], y, 1)
        y = bn(pre + "2c", p["bn3"], y)
    if "proj" in p:
        with trace_lib.layer_context(pre + "1"):
            x = conv(pre + "1", p["proj"], x, stride)
            x = bn(pre + "1", p["bn_proj"], x)
    with trace_lib.layer_context(pre.removesuffix("_branch")):
        return L.relu(x + y)


def apply(params, x, cfg: ResNetConfig = RESNET50, plan=None, mesh=None,
          overlap=True):
    """x: (N, H, W, 3) -> logits (N, n_classes).

    `plan`: a core.plan.NetworkPlan (per-layer distributions + reshard
    points) or a single legacy ConvSharding applied uniformly.
    """
    from repro.core.plan import NetworkPlan
    plan = NetworkPlan.of(plan)
    with trace_lib.layer_context("conv1"):
        x = plan.reshard(x, "conv1", mesh)
        x = L.conv_apply(params["conv1"], x, stride=2,
                         sharding=plan.sharding("conv1"), mesh=mesh,
                         overlap=overlap)
        shb = plan.sharding("conv1").fit(x.shape[1], x.shape[2], 1, 1, mesh)
        x = L.relu(L.bn_apply(params["bn1"], x, sharding=shb, mesh=mesh,
                              scope=cfg.bn_scope))
    with trace_lib.layer_context("pool1"):
        x = plan.reshard(x, "pool1", mesh)
        x = L.max_pool(x, window=3, stride=2,
                       sharding=plan.sharding("pool1"), mesh=mesh)
    bi = 0
    last = "pool1"
    for s, (n_blocks, width) in enumerate(zip(cfg.stages, cfg.widths)):
        for b in range(n_blocks):
            stride = 2 if (b == 0 and s > 0) else 1
            pre = f"res{s+2}{chr(ord('a')+b)}_branch"
            x = _bottleneck_apply(params["blocks"][bi], x, pre=pre,
                                  stride=stride, plan=plan, mesh=mesh,
                                  scope=cfg.bn_scope, overlap=overlap)
            last = pre + "2c"
            bi += 1
    with trace_lib.layer_context("pool5"):
        x = L.global_avg_pool(x, sharding=plan.sharding(last).fit(
            x.shape[1], x.shape[2], 1, 1, mesh), mesh=mesh)
    with trace_lib.layer_context("fc"):
        return L.dense_apply(params["head"], x)


def loss_fn(params, batch, cfg: ResNetConfig = RESNET50, plan=None,
            mesh=None, overlap=True):
    logits = apply(params, batch["image"], cfg, plan, mesh, overlap)
    logits = logits.astype(jnp.float32)
    logp = jax.nn.log_softmax(logits)
    nll = -jnp.take_along_axis(logp, batch["label"][:, None], axis=1)
    return jnp.mean(nll)


# ---------------------------------------------------------------------------
# perf-model / strategy views
# ---------------------------------------------------------------------------

def layer_specs(n: int, cfg: ResNetConfig = RESNET50) -> list[ConvLayer]:
    """Flat (main-path) conv list for the line-network perf model."""
    out = [ConvLayer("conv1", n=n, c=cfg.in_channels, h=cfg.input_hw,
                     w=cfg.input_hw, f=64, k=7, s=2)]
    hw = cfg.input_hw // 4           # conv1 /2, maxpool /2
    out.append(ConvLayer("pool1", n=n, c=64, h=cfg.input_hw // 2,
                         w=cfg.input_hw // 2, f=64, k=3, s=2, kind="pool"))
    c_in = 64
    for s, (n_blocks, width) in enumerate(zip(cfg.stages, cfg.widths)):
        for b in range(n_blocks):
            stride = 2 if (b == 0 and s > 0) else 1
            pre = f"res{s+2}{chr(ord('a')+b)}_branch2"
            out.append(ConvLayer(pre + "a", n=n, c=c_in, h=hw, w=hw,
                                 f=width, k=1, s=1))
            out.append(ConvLayer(pre + "b", n=n, c=width, h=hw, w=hw,
                                 f=width, k=3, s=stride))
            hw2 = hw // stride
            out.append(ConvLayer(pre + "c", n=n, c=width, h=hw2, w=hw2,
                                 f=width * EXPANSION, k=1, s=1))
            hw = hw2
            c_in = width * EXPANSION
    return out


def resnet_graph(n: int, cfg: ResNetConfig = RESNET50) -> nx.DiGraph:
    """Branchy DAG (residual shortcuts included) for §V-C longest-path-first."""
    g = nx.DiGraph()
    specs = layer_specs(n, cfg)
    prev = None

    def add(node, layer):
        g.add_node(node, layer=layer)

    add("conv1", specs[0]); add("pool1", specs[1])
    g.add_edge("conv1", "pool1")
    prev = "pool1"
    i = 2
    c_in, hw = 64, cfg.input_hw // 4
    for s, (n_blocks, width) in enumerate(zip(cfg.stages, cfg.widths)):
        for b in range(n_blocks):
            stride = 2 if (b == 0 and s > 0) else 1
            names = [specs[i].name, specs[i + 1].name, specs[i + 2].name]
            for j in range(3):
                add(names[j], specs[i + j])
            g.add_edge(prev, names[0])
            g.add_edge(names[0], names[1])
            g.add_edge(names[1], names[2])
            # projection branch exists iff init added one (channel change
            # OR strided block — e.g. equal-width stage transitions)
            if c_in != width * EXPANSION or stride != 1:
                pname = f"res{s+2}{chr(ord('a')+b)}_branch1"
                add(pname, ConvLayer(pname, n=n, c=c_in, h=hw, w=hw,
                                     f=width * EXPANSION, k=1, s=stride))
                g.add_edge(prev, pname)
                g.add_edge(pname, names[2])
            hw //= stride
            c_in = width * EXPANSION
            prev = names[2]
            i += 3
    return g
