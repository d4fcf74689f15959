"""Multi-device distributed checks, run in a subprocess so the main pytest
process keeps a single CPU device (the 512-device env is dry-run-only).

Usage:  python tests/dist_checks.py <group>
Groups: conv | attention | ssm | models | train | compress | plan | cf |
        spatial2d | multiaxis | memfit | overlap | trace | elastic | audit |
        wpack
Exits 0 on success; any assertion failure exits non-zero.
"""
import os
import sys

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"

import functools  # noqa: E402
import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax import lax  # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402

from repro.launch.mesh import make_mesh  # noqa: E402
from repro.utils import same_pads  # noqa: E402


def oracle_conv(x, w, s):
    kh, kw = w.shape[0], w.shape[1]
    return lax.conv_general_dilated(
        x, w, (s, s), (same_pads(kh, s), same_pads(kw, s)),
        dimension_numbers=("NHWC", "HWIO", "NHWC"))


def check_conv():
    from repro.core.spatial_conv import spatial_conv2d, spatial_pool, \
        ConvSharding
    mesh = make_mesh(data=2, model=4)
    key = jax.random.PRNGKey(0)
    for (K, s, H, W, C, F) in [(3, 1, 16, 12, 5, 7), (7, 2, 32, 16, 3, 8),
                               (1, 1, 16, 8, 4, 4), (3, 2, 16, 16, 6, 6)]:
        x = jax.random.normal(key, (4, H, W, C), jnp.float32)
        w = jax.random.normal(jax.random.PRNGKey(1), (K, K, C, F)) * 0.1
        ref = oracle_conv(x, w, s)
        for overlap in (False, True):
            sh = ConvSharding(batch_axes=("data",), h_axis="model")
            with mesh:
                got = jax.jit(lambda x, w: spatial_conv2d(
                    x, w, strides=(s, s), sharding=sh, mesh=mesh,
                    overlap=overlap))(x, w)
                np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                           rtol=2e-5, atol=2e-5)
                gd = jax.jit(jax.grad(lambda x, w: jnp.sum(spatial_conv2d(
                    x, w, strides=(s, s), sharding=sh, mesh=mesh,
                    overlap=overlap) ** 2), argnums=(0, 1)))(x, w)
            gr = jax.grad(lambda x, w: jnp.sum(oracle_conv(x, w, s) ** 2),
                          argnums=(0, 1))(x, w)
            for a, b in zip(gd, gr):
                np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                           rtol=3e-4, atol=3e-4)
    # pooling (max needs -inf edge halo) and 2-D H x W decomposition
    x = jax.random.normal(key, (4, 32, 16, 5), jnp.float32)
    for kind in ("max", "avg"):
        sh = ConvSharding(batch_axes=("data",), h_axis="model")
        with mesh:
            got = jax.jit(lambda x: spatial_pool(
                x, window=(3, 3), strides=(2, 2), sharding=sh, mesh=mesh,
                kind=kind))(x)
        ref = spatial_pool(x, window=(3, 3), strides=(2, 2),
                           sharding=ConvSharding(), kind=kind)
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   rtol=1e-6, atol=1e-6)
    sh2 = ConvSharding(batch_axes=(), h_axis="model", w_axis="data")
    x = jax.random.normal(key, (2, 16, 16, 3), jnp.float32)
    w = jax.random.normal(jax.random.PRNGKey(1), (3, 3, 3, 5)) * 0.1
    with mesh:
        got = jax.jit(lambda x, w: spatial_conv2d(
            x, w, strides=(1, 1), sharding=sh2, mesh=mesh))(x, w)
    np.testing.assert_allclose(np.asarray(got), np.asarray(oracle_conv(
        x, w, 1)), rtol=2e-5, atol=2e-5)
    # spatially-aggregated batch norm == global stats over the shards
    from repro.core.spatial_norm import batch_norm
    sh = ConvSharding(batch_axes=("data",), h_axis="model")
    x = jax.random.normal(key, (4, 16, 8, 6), jnp.float32) * 3 + 1
    g = jnp.ones((6,)); b = jnp.zeros((6,))
    with mesh:
        got = jax.jit(lambda x: batch_norm(
            x, g, b, sharding=sh, mesh=mesh, scope="global"))(x)
    ref = batch_norm(x, g, b, sharding=ConvSharding(), scope="local")
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=1e-4, atol=1e-4)


def check_attention():
    from repro.core.ring_attention import ring_attention
    from repro.core.decode_attention import decode_attention, cache_append
    mesh = make_mesh(data=2, model=4)
    key = jax.random.PRNGKey(0)
    B, S, Hq, Hkv, D = 2, 32, 8, 4, 16
    ks = jax.random.split(key, 3)
    q = jax.random.normal(ks[0], (B, S, Hq, D))
    k = jax.random.normal(ks[1], (B, S, Hkv, D))
    v = jax.random.normal(ks[2], (B, S, Hkv, D))
    for causal, window, cap in [(True, None, None), (True, 7, None),
                                (False, None, None), (True, 12, 30.0)]:
        ref = ring_attention(q, k, v, mesh=None, seq_axis=None,
                             causal=causal, window=window, softcap=cap)
        with mesh:
            got = jax.jit(lambda q, k, v: ring_attention(
                q, k, v, mesh=mesh, seq_axis="model", causal=causal,
                window=window, softcap=cap))(q, k, v)
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)
    qd = jax.random.normal(ks[0], (B, 1, Hq, D))
    L = jnp.int32(23)
    for window in (None, 6):
        ref = decode_attention(qd, k, v, L, mesh=None, seq_axis=None,
                               window=window)
        with mesh:
            got = jax.jit(lambda q, k, v, L: decode_attention(
                q, k, v, L, mesh=mesh, seq_axis="model",
                window=window))(qd, k, v, L)
            # multi-axis sequence sharding (long_500k layout)
            got2 = jax.jit(lambda q, k, v, L: decode_attention(
                q, k, v, L, mesh=mesh, seq_axis=("data", "model"),
                batch_axes=(), window=window))(qd, k, v, L)
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)
        np.testing.assert_allclose(np.asarray(got2), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)
    kn = jax.random.normal(ks[1], (B, 1, Hkv, D))
    vn = jax.random.normal(ks[2], (B, 1, Hkv, D))
    kr, vr = cache_append(k, v, kn, vn, 23, mesh=None, seq_axis=None)
    with mesh:
        kg, vg = jax.jit(lambda *a: cache_append(
            *a, mesh=mesh, seq_axis="model"))(k, v, kn, vn, jnp.int32(23))
    np.testing.assert_allclose(np.asarray(kg), np.asarray(kr))
    np.testing.assert_allclose(np.asarray(vg), np.asarray(vr))


def check_ssm():
    from repro.core.seq_ssm import seq_prefix_state
    key = jax.random.PRNGKey(0)
    ks = jax.random.split(key, 2)
    B, H, dh, ds = 2, 3, 4, 5
    a = jax.random.uniform(ks[0], (8, B, H, 1, 1), minval=0.5, maxval=0.99)
    s = jax.random.normal(ks[1], (8, B, H, dh, ds))
    st = jnp.zeros_like(s[0])
    outs = []
    for i in range(8):
        outs.append(st)
        st = st * a[i] + s[i]
    ref = jnp.stack(outs)
    from repro.utils import shard_map
    mesh1 = make_mesh(data=1, model=8)
    with mesh1:
        f = shard_map(
            lambda a, s: seq_prefix_state(a[0], s[0], "model", 8)[None],
            mesh=mesh1, in_specs=(P("model"), P("model")),
            out_specs=P("model"))
        got = jax.jit(f)(a, s)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


def check_models():
    from repro.configs import registry
    from repro.models.lm import transformer as T
    from repro.models.lm.modules import ShardCtx
    from repro.data.pipeline import synthetic_lm_batch
    mesh = make_mesh(data=2, model=4)
    ctx = ShardCtx(mesh=mesh, seq_axis="model", batch_axes=("data",))
    for a in ["gemma2_9b", "mixtral_8x7b", "mamba2_780m", "hymba_1_5b",
              "seamless_m4t_large_v2"]:
        cfg = registry.get(a, smoke=True)
        params = T.init(jax.random.PRNGKey(0), cfg)
        B, S = 2, 64
        batch = {k: jnp.asarray(v) for k, v in
                 synthetic_lm_batch(0, B, S, cfg.vocab).items()}
        if cfg.frontend == "audio_stub":
            batch["frames"] = jax.random.normal(
                jax.random.PRNGKey(1), (B, S, cfg.d_model))
        ref = T.loss_fn(params, batch, cfg, ShardCtx(), remat=False)
        with mesh:
            sb = dict(batch)
            sb["tokens"] = jax.device_put(
                batch["tokens"], NamedSharding(mesh, P("data", "model")))
            sb["labels"] = jax.device_put(
                batch["labels"], NamedSharding(mesh, P("data", "model")))
            if "frames" in sb:
                sb["frames"] = jax.device_put(
                    batch["frames"],
                    NamedSharding(mesh, P("data", "model", None)))
            got = jax.jit(lambda p, b: T.loss_fn(
                p, b, cfg, ctx, remat=False))(params, sb)
        np.testing.assert_allclose(float(got), float(ref), rtol=2e-5)
    # ring vocab-parallel CE == dense CE (fwd + grads), incl. untied + VLM
    for a in ["gemma2_9b", "pixtral_12b"]:
        cfg = registry.get(a, smoke=True)
        params = T.init(jax.random.PRNGKey(0), cfg)
        B, S = 2, 64
        batch = {k: jnp.asarray(v) for k, v in
                 synthetic_lm_batch(0, B, S, cfg.vocab).items()}
        if cfg.frontend == "vit_stub":
            batch["patch_embeds"] = jax.random.normal(
                jax.random.PRNGKey(1), (B, cfg.frontend_len, cfg.d_model))
        ref = T.loss_fn(params, batch, cfg, ShardCtx(), remat=False)
        with mesh:
            sb = {k: jax.device_put(v, NamedSharding(
                      mesh, P("data", "model") if v.ndim == 2
                      else P("data", None, None)))
                  for k, v in batch.items()}
            got = jax.jit(lambda p, b: T.loss_fn(
                p, b, cfg, ctx, remat=False, vocab_parallel=True))(params, sb)
            g_ref = jax.grad(lambda p: T.loss_fn(
                p, batch, cfg, ShardCtx(), remat=False))(params)
            g_got = jax.jit(jax.grad(lambda p: T.loss_fn(
                p, sb, cfg, ctx, remat=False, vocab_parallel=True)))(params)
        np.testing.assert_allclose(float(got), float(ref), rtol=3e-5)
        for gr, gg in zip(jax.tree.leaves(g_ref), jax.tree.leaves(g_got)):
            np.testing.assert_allclose(np.asarray(gg), np.asarray(gr),
                                       rtol=5e-3, atol=5e-5)

    # sharded-KV decode == oracle, 2 steps
    for a in ["gemma2_9b", "qwen2_5_14b"]:
        cfg = registry.get(a, smoke=True)
        params = T.init(jax.random.PRNGKey(0), cfg)
        B = 2
        cr = T.init_decode_state(params, cfg, B, 32, dtype=jnp.float32)
        tok = jnp.array([[3], [5]], jnp.int32)
        ref, cr = T.decode_step(params, cfg, tok, cr, jnp.int32(0))
        ref2, _ = T.decode_step(params, cfg, jnp.array([[7], [9]]), cr,
                                jnp.int32(1))
        with mesh:
            cs = T.init_decode_state(params, cfg, B, 32, dtype=jnp.float32)
            cs = jax.tree.map(
                lambda x: jax.device_put(x, NamedSharding(
                    mesh, P(None, "data", "model", None, None)))
                if x.ndim == 5 else x, cs)
            f = jax.jit(lambda p, t, c, L: T.decode_step(
                p, cfg, t, c, L, ctx))
            got, cs = f(params, tok, cs, jnp.int32(0))
            got2, _ = f(params, jnp.array([[7], [9]]), cs, jnp.int32(1))
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   rtol=2e-4, atol=2e-4)
        np.testing.assert_allclose(np.asarray(got2), np.asarray(ref2),
                                   rtol=2e-4, atol=2e-4)


def check_train():
    import shutil
    import tempfile
    from repro.core.spatial_conv import ConvSharding
    from repro.models.cnn import meshnet
    from repro.optim.optimizer import sgd
    from repro.train.train_loop import make_train_step, TrainStepConfig, \
        shard_tree
    from repro.checkpoint.checkpoint import CheckpointManager
    from repro.runtime.fault_tolerance import ResilientLoop, \
        StragglerMonitor
    from repro.data.pipeline import synthetic_mesh_batch
    from repro.utils import FP32
    mesh = make_mesh(data=2, model=2, pod=2)
    cfg = meshnet.MeshNetConfig("tiny", input_hw=64, in_channels=4,
                                convs_per_block=1, widths=(8, 16, 16))
    sh = ConvSharding(batch_axes=("pod", "data"), h_axis="model")
    params = shard_tree(meshnet.init(jax.random.PRNGKey(0), cfg), mesh,
                        lambda x: P())
    loss = functools.partial(meshnet.loss_fn, cfg=cfg, plan=sh,
                             mesh=mesh)
    opt = sgd(0.05, momentum=0.9)
    tstep = make_train_step(
        lambda p, b: loss(p, b), opt, mesh,
        TrainStepConfig(grad_accum=2, precision=FP32,
                        pod_compression="int8_ef"))

    def put(b):
        return {"image": jax.device_put(b["image"], NamedSharding(
                    mesh, P(("pod", "data"), "model"))),
                "label": jax.device_put(b["label"], NamedSharding(
                    mesh, P(("pod", "data"),)))}

    ckdir = tempfile.mkdtemp()
    try:
        ck = CheckpointManager(ckdir, keep=2, async_save=True)
        state = (params, opt.init(params), None)

        def make_step():
            def run(state, step):
                p, o, ef = state
                b = put(synthetic_mesh_batch(step, 8, 64, 4, out_hw=8))
                p, o, ef, m = tstep(p, o, ef, b)
                return (p, o, ef), m
            return run

        boom = {"armed": True}

        def inject(step):
            if step == 7 and boom["armed"]:
                boom["armed"] = False
                raise RuntimeError("synthetic node failure")

        loop = ResilientLoop(ckpt=ck, make_step=make_step, ckpt_every=5,
                             max_failures=2)
        state, step, metrics = loop.run(state, 0, 12,
                                        monitor=StragglerMonitor(),
                                        inject_failure=inject)
        assert step == 12
        losses = []
        p, o, ef = state
        for s in range(12, 36):
            b = put(synthetic_mesh_batch(s, 8, 64, 4, out_hw=8))
            p, o, ef, m = tstep(p, o, ef, b)
            losses.append(float(m["loss"]))
        assert losses[-1] < losses[0], (losses[0], losses[-1])
        assert np.isfinite(losses).all()
    finally:
        shutil.rmtree(ckdir, ignore_errors=True)


def check_plan():
    """Uniform vs solved-auto NetworkPlan vs single-device oracle on a 2x2
    mesh: loss and grads agree (numerically; resharding changes fp order)."""
    from repro.core import plan as plan_lib
    from repro.core.distribution import Dist
    from repro.core.perfmodel import TPU_V5E
    from repro.core.spatial_conv import ConvSharding
    from repro.models.cnn import meshnet, resnet
    from repro.data.pipeline import synthetic_mesh_batch

    mesh = make_mesh(data=2, model=2)
    uni = ConvSharding(batch_axes=("data",), h_axis="model")

    # --- meshnet (line network, solve_line) -------------------------------
    # global-scope BN: per-shard ("local") statistics legitimately differ
    # between decompositions, so oracle comparison needs aggregated stats
    cfg = meshnet.MeshNetConfig("t", input_hw=32, in_channels=4,
                                convs_per_block=1, widths=(8, 16),
                                bn_scope="global")
    specs = meshnet.layer_specs(cfg, 4)
    auto = plan_lib.plan_line(TPU_V5E, specs, mesh)
    uplan = plan_lib.NetworkPlan.uniform(uni, meshnet.layer_names(cfg))
    params = meshnet.init(jax.random.PRNGKey(0), cfg)
    b = {k: jnp.asarray(v) for k, v in
         synthetic_mesh_batch(0, 4, 32, 4, out_hw=8).items()}
    ref_l = meshnet.loss_fn(params, b, cfg, ConvSharding())
    ref_g = jax.grad(lambda p: meshnet.loss_fn(p, b, cfg,
                                               ConvSharding()))(params)
    for plan in (uplan, auto):
        with mesh:
            got_l = jax.jit(lambda p, bb: meshnet.loss_fn(
                p, bb, cfg, plan, mesh))(params, b)
            got_g = jax.jit(jax.grad(lambda p: meshnet.loss_fn(
                p, b, cfg, plan, mesh)))(params)
        np.testing.assert_allclose(float(got_l), float(ref_l), rtol=2e-5)
        for a, r in zip(jax.tree.leaves(got_g), jax.tree.leaves(ref_g)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(r),
                                       rtol=3e-4, atol=3e-5)

    # --- a genuinely mixed plan with forced reshard points ----------------
    hybrid = Dist("hybrid", {"N": ("data",), "H": ("model",)})
    sample = Dist("sample", {"N": ("data", "model")})
    mixed = plan_lib.compile_plan(
        {"conv1_1": hybrid, "conv2_1": sample, "pred": hybrid},
        specs, mesh)
    assert mixed.n_reshards == 2, mixed.describe()
    with mesh:
        got_l = jax.jit(lambda p, bb: meshnet.loss_fn(
            p, bb, cfg, mixed, mesh))(params, b)
        got_g = jax.jit(jax.grad(lambda p: meshnet.loss_fn(
            p, b, cfg, mixed, mesh)))(params)
    np.testing.assert_allclose(float(got_l), float(ref_l), rtol=2e-5)
    for a, r in zip(jax.tree.leaves(got_g), jax.tree.leaves(ref_g)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(r),
                                   rtol=3e-4, atol=3e-5)

    # --- resnet (branchy DAG, solve_dag longest-path-first) ---------------
    rcfg = resnet.ResNetConfig(name="tiny", input_hw=32, n_classes=10,
                               stages=(1, 1), widths=(8, 16),
                               bn_scope="global")
    graph = resnet.resnet_graph(2, rcfg)
    rspecs = resnet.layer_specs(2, rcfg)
    rauto = plan_lib.plan_graph(TPU_V5E, graph, rspecs, mesh)
    rparams = resnet.init(jax.random.PRNGKey(0), rcfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 32, 32, 3))
    lbl = jnp.array([1, 7])
    rb = {"image": x, "label": lbl}
    ref_l = resnet.loss_fn(rparams, rb, rcfg, ConvSharding())
    ref_g = jax.grad(lambda p: resnet.loss_fn(p, rb, rcfg,
                                              ConvSharding()))(rparams)
    rub = plan_lib.NetworkPlan.uniform(uni, [l.name for l in rspecs])
    for plan in (rub, rauto):
        with mesh:
            got_l = jax.jit(lambda p, bb: resnet.loss_fn(
                p, bb, rcfg, plan, mesh))(rparams, rb)
            got_g = jax.jit(jax.grad(lambda p: resnet.loss_fn(
                p, rb, rcfg, plan, mesh)))(rparams)
        np.testing.assert_allclose(float(got_l), float(ref_l), rtol=3e-5)
        for a, r in zip(jax.tree.leaves(got_g), jax.tree.leaves(ref_g)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(r),
                                       rtol=5e-4, atol=5e-5)


def check_cf():
    """Channel/filter-parallel runtime (core.channel_conv, §III-D):
    both modes vs the dense oracle, fwd + grads, plus the Pallas
    implicit-GEMM backend in interpret mode; BN/bias; and a 4-device
    solved auto plan containing CF layers vs the single-device oracle."""
    from repro.core.channel_conv import (CFSharding, cf_batch_norm,
                                         cf_bias_add, cf_conv2d)
    from repro.core.spatial_conv import ConvSharding
    from repro.core.spatial_norm import batch_norm

    mesh = make_mesh(data=2, model=2)
    key = jax.random.PRNGKey(0)

    # --- conv parity: modes x strides x kernel sizes ----------------------
    for (K, s, C, F) in [(3, 1, 8, 12), (3, 2, 8, 8), (1, 1, 4, 8)]:
        x = jax.random.normal(key, (4, 8, 8, C), jnp.float32)
        w = jax.random.normal(jax.random.PRNGKey(1), (K, K, C, F)) * 0.1
        ref = oracle_conv(x, w, s)
        gr = jax.grad(lambda x, w: jnp.sum(oracle_conv(x, w, s) ** 2),
                      argnums=(0, 1))(x, w)
        for mode in ("channel", "filter"):
            sh = CFSharding(batch_axes=("data",), cf_axis="model",
                            mode=mode)
            with mesh:
                got = jax.jit(lambda x, w: cf_conv2d(
                    x, w, strides=(s, s), sharding=sh, mesh=mesh))(x, w)
                gd = jax.jit(jax.grad(lambda x, w: jnp.sum(cf_conv2d(
                    x, w, strides=(s, s), sharding=sh, mesh=mesh) ** 2),
                    argnums=(0, 1)))(x, w)
            np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                       rtol=2e-5, atol=2e-5)
            for a, b in zip(gd, gr):
                np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                           rtol=3e-4, atol=3e-4)

    # --- the §IV-A chunked channel-block split (overlapped channel mode,
    # the TPU default) pinned explicitly: parity incl. grads -------------
    x = jax.random.normal(key, (4, 8, 8, 8), jnp.float32)
    w = jax.random.normal(jax.random.PRNGKey(1), (3, 3, 8, 8)) * 0.1
    sh = CFSharding(batch_axes=("data",), cf_axis="model")
    ref = oracle_conv(x, w, 1)
    gr = jax.grad(lambda x, w: jnp.sum(oracle_conv(x, w, 1) ** 2),
                  argnums=(0, 1))(x, w)
    for chunks in (2, 3):
        with mesh:
            got = jax.jit(lambda x, w: cf_conv2d(
                x, w, sharding=sh, mesh=mesh,
                channel_chunks=chunks))(x, w)
            gd = jax.jit(jax.grad(lambda x, w: jnp.sum(cf_conv2d(
                x, w, sharding=sh, mesh=mesh,
                channel_chunks=chunks) ** 2), argnums=(0, 1)))(x, w)
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)
        for a, b in zip(gd, gr):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=3e-4, atol=3e-4)

    # --- the Pallas implicit-GEMM kernel through the CF path (interpret
    # mode on CPU — numerics-identical to the TPU lowering) ----------------
    with mesh:
        got = jax.jit(lambda x, w: cf_conv2d(
            x, w, sharding=sh, mesh=mesh, backend="pallas"))(x, w)
    np.testing.assert_allclose(np.asarray(got),
                               np.asarray(oracle_conv(x, w, 1)),
                               rtol=2e-5, atol=2e-5)

    # --- BN: per-channel stats never cross the CF axis --------------------
    x = jax.random.normal(key, (4, 8, 8, 8), jnp.float32) * 3 + 1
    g = jax.random.normal(jax.random.PRNGKey(2), (8,)) + 2
    b = jax.random.normal(jax.random.PRNGKey(3), (8,))
    ref = batch_norm(x, g, b, sharding=ConvSharding(), scope="local")
    with mesh:
        got = jax.jit(lambda x: cf_batch_norm(
            x, g, b, sharding=sh, mesh=mesh, scope="global"))(x)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=1e-4, atol=1e-4)
    with mesh:
        got = jax.jit(lambda x: cf_bias_add(x, b, sharding=sh,
                                            mesh=mesh))(x)
    np.testing.assert_allclose(np.asarray(got), np.asarray(x + b),
                               rtol=1e-6, atol=1e-6)

    # --- acceptance: a solved 4-device auto plan with >= 1 CF layer
    # matches the single-device oracle (loss + grads) ----------------------
    from repro.core import plan as plan_lib
    from repro.core.perfmodel import TPU_V5E
    from repro.data.pipeline import synthetic_mesh_batch
    from repro.models.cnn import meshnet

    # late layers: h=4 < k=3 — no spatial split fits, channels do (§III-D)
    cfg = meshnet.MeshNetConfig("t", input_hw=16, in_channels=8,
                                convs_per_block=1, widths=(16, 32, 32),
                                bn_scope="global")
    specs = meshnet.layer_specs(cfg, 2)
    auto = plan_lib.plan_line(TPU_V5E, specs, mesh)
    n_cf = sum(isinstance(lp.sharding, CFSharding)
               for lp in auto.layers.values())
    assert n_cf >= 1, auto.describe()
    assert auto.n_reshards >= 1, auto.describe()   # CF <-> spatial shuffle

    params = meshnet.init(jax.random.PRNGKey(0), cfg)
    batch = {k: jnp.asarray(v) for k, v in
             synthetic_mesh_batch(0, 2, 16, 8, out_hw=2).items()}
    ref_l = meshnet.loss_fn(params, batch, cfg, ConvSharding())
    ref_g = jax.grad(lambda p: meshnet.loss_fn(
        p, batch, cfg, ConvSharding()))(params)
    with mesh:
        got_l = jax.jit(lambda p, bb: meshnet.loss_fn(
            p, bb, cfg, auto, mesh))(params, batch)
        got_g = jax.jit(jax.grad(lambda p: meshnet.loss_fn(
            p, batch, cfg, auto, mesh)))(params)
    np.testing.assert_allclose(float(got_l), float(ref_l), rtol=2e-5)
    for a, r in zip(jax.tree.leaves(got_g), jax.tree.leaves(ref_g)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(r),
                                   rtol=3e-4, atol=3e-5)

    # --- consecutive CF layers chain with zero resharding -----------------
    cf = {"C": ("model",), "F": ("model",), "N": ("data",)}
    from repro.core.distribution import Dist
    forced = plan_lib.compile_plan(
        {"conv1_1": Dist("hybrid", {"N": ("data",), "H": ("model",)}),
         "conv2_1": Dist("channel_filter", cf),
         "conv3_1": Dist("channel_filter", cf),
         "pred": Dist("sample", {"N": ("data",)})},
        specs, mesh)
    lps = forced.layers
    assert lps["conv2_1"].reshard_in and not lps["conv3_1"].reshard_in
    with mesh:
        got_l = jax.jit(lambda p, bb: meshnet.loss_fn(
            p, bb, cfg, forced, mesh))(params, batch)
    np.testing.assert_allclose(float(got_l), float(ref_l), rtol=2e-5)


def check_spatial2d():
    """W-axis and 2-D (H x W) spatial decompositions: conv fwd + grads,
    pooling, and a compiled plan with W-splits vs the oracle (the ROADMAP
    item on exercising the 2-D decomposition)."""
    from repro.core.spatial_conv import spatial_conv2d, spatial_pool, \
        ConvSharding

    mesh = make_mesh(data=2, model=2)
    key = jax.random.PRNGKey(0)
    shw = ConvSharding(batch_axes=("model",), w_axis="data")   # W only
    sh2 = ConvSharding(batch_axes=(), h_axis="model", w_axis="data")
    for sh in (shw, sh2):
        for (K, s) in [(3, 1), (3, 2), (7, 2)]:
            x = jax.random.normal(key, (2, 16, 16, 3), jnp.float32)
            w = jax.random.normal(jax.random.PRNGKey(1),
                                  (K, K, 3, 5)) * 0.1
            ref = oracle_conv(x, w, s)
            gr = jax.grad(lambda x, w: jnp.sum(oracle_conv(x, w, s) ** 2),
                          argnums=(0, 1))(x, w)
            for overlap in (False, True):
                with mesh:
                    got = jax.jit(lambda x, w: spatial_conv2d(
                        x, w, strides=(s, s), sharding=sh, mesh=mesh,
                        overlap=overlap))(x, w)
                    gd = jax.jit(jax.grad(
                        lambda x, w: jnp.sum(spatial_conv2d(
                            x, w, strides=(s, s), sharding=sh, mesh=mesh,
                            overlap=overlap) ** 2),
                        argnums=(0, 1)))(x, w)
                np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                           rtol=2e-5, atol=2e-5)
                for a, b in zip(gd, gr):
                    np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                               rtol=3e-4, atol=3e-4)
        # pooling under W / H x W splits (max needs the -inf edge halo)
        x = jax.random.normal(key, (2, 16, 16, 3), jnp.float32)
        for kind in ("max", "avg"):
            ref = spatial_pool(x, window=(3, 3), strides=(2, 2),
                               sharding=ConvSharding(), kind=kind)
            with mesh:
                got = jax.jit(lambda x: spatial_pool(
                    x, window=(3, 3), strides=(2, 2), sharding=sh,
                    mesh=mesh, kind=kind))(x)
            np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                       rtol=1e-6, atol=1e-6)

    # the max pool's gradient under H, W and H x W splits: ReLU'd input
    # ties at 0, and the halo keeps each window's first tap first
    x = jnp.maximum(jax.random.normal(key, (2, 16, 16, 3)), 0)

    def pool_loss(sh, mesh=None):
        return lambda x: jnp.sum(jnp.sin(spatial_pool(
            x, window=(3, 3), strides=(2, 2), sharding=sh, mesh=mesh)))

    ref = jax.grad(pool_loss(ConvSharding()))(x)
    for sh in (ConvSharding(batch_axes=("data",), h_axis="model"), shw, sh2):
        with mesh:
            got = jax.jit(jax.grad(pool_loss(sh, mesh)))(x)
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   rtol=1e-6, atol=1e-6)

    # a compiled plan whose dists shard W — through the full model stack
    from repro.core import plan as plan_lib
    from repro.core.distribution import Dist
    from repro.data.pipeline import synthetic_mesh_batch
    from repro.models.cnn import meshnet
    cfg = meshnet.MeshNetConfig("t", input_hw=32, in_channels=4,
                                convs_per_block=1, widths=(8, 16),
                                bn_scope="global")
    specs = meshnet.layer_specs(cfg, 4)
    plan = plan_lib.compile_plan(
        {"conv1_1": Dist("s2d", {"H": ("model",), "W": ("data",)}),
         "conv2_1": Dist("wsplit", {"N": ("model",), "W": ("data",)}),
         "pred": Dist("hybrid", {"N": ("data",), "H": ("model",)})},
        specs, mesh)
    assert plan.n_reshards == 2, plan.describe()
    params = meshnet.init(jax.random.PRNGKey(0), cfg)
    b = {k: jnp.asarray(v) for k, v in
         synthetic_mesh_batch(0, 4, 32, 4, out_hw=8).items()}
    ref_l = meshnet.loss_fn(params, b, cfg, ConvSharding())
    ref_g = jax.grad(lambda p: meshnet.loss_fn(p, b, cfg,
                                               ConvSharding()))(params)
    with mesh:
        got_l = jax.jit(lambda p, bb: meshnet.loss_fn(
            p, bb, cfg, plan, mesh))(params, b)
        got_g = jax.jit(jax.grad(lambda p: meshnet.loss_fn(
            p, b, cfg, plan, mesh)))(params)
    np.testing.assert_allclose(float(got_l), float(ref_l), rtol=2e-5)
    for a, r in zip(jax.tree.leaves(got_g), jax.tree.leaves(ref_g)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(r),
                                   rtol=3e-4, atol=3e-5)


def check_multiaxis():
    """Multi-axis spatial + CF x spatial composition on an 8-device mesh
    reshaped to (2, 2, 2) — the 16x16-mesh decompositions at test scale:
    halo exchange over a *product* of mesh axes, the CF collective and the
    halo in one shard_map (both modes, overlapped and not, Pallas interpret
    backend), pooling/BN over product axes, and the acceptance check — a
    solved auto plan containing >= 1 multi-axis-H layer and >= 1
    CF x spatial layer matches the single-device oracle (fwd + grads)."""
    from repro.core.channel_conv import CFSharding, cf_batch_norm, cf_conv2d
    from repro.core.spatial_conv import (ConvSharding, spatial_conv2d,
                                         spatial_pool)
    from repro.core.spatial_norm import batch_norm

    mesh = make_mesh(data=2, model=2, pod=2)
    key = jax.random.PRNGKey(0)

    # --- conv under H split over the ('data','model') product axis --------
    sh = ConvSharding(batch_axes=("pod",), h_axis=("data", "model"))
    for (K, s, H, W) in [(3, 1, 16, 8), (3, 2, 16, 16), (7, 2, 32, 8),
                         (1, 1, 8, 8)]:
        x = jax.random.normal(key, (2, H, W, 3), jnp.float32)
        w = jax.random.normal(jax.random.PRNGKey(1), (K, K, 3, 5)) * 0.1
        ref = oracle_conv(x, w, s)
        gr = jax.grad(lambda x, w: jnp.sum(oracle_conv(x, w, s) ** 2),
                      argnums=(0, 1))(x, w)
        for overlap in (False, True):
            with mesh:
                got = jax.jit(lambda x, w: spatial_conv2d(
                    x, w, strides=(s, s), sharding=sh, mesh=mesh,
                    overlap=overlap))(x, w)
                gd = jax.jit(jax.grad(lambda x, w: jnp.sum(spatial_conv2d(
                    x, w, strides=(s, s), sharding=sh, mesh=mesh,
                    overlap=overlap) ** 2), argnums=(0, 1)))(x, w)
            np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                       rtol=2e-5, atol=2e-5)
            for a, b in zip(gd, gr):
                np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                           rtol=3e-4, atol=3e-4)

    # --- 2-D decomposition where one dim is a product: H x (W product) ----
    sh2 = ConvSharding(batch_axes=(), h_axis="model",
                       w_axis=("pod", "data"))
    x = jax.random.normal(key, (2, 16, 16, 3), jnp.float32)
    w = jax.random.normal(jax.random.PRNGKey(1), (3, 3, 3, 5)) * 0.1
    with mesh:
        got = jax.jit(lambda x, w: spatial_conv2d(
            x, w, sharding=sh2, mesh=mesh))(x, w)
    np.testing.assert_allclose(np.asarray(got),
                               np.asarray(oracle_conv(x, w, 1)),
                               rtol=2e-5, atol=2e-5)

    # --- pooling and BN over the product axis -----------------------------
    x = jax.random.normal(key, (2, 16, 8, 6), jnp.float32) * 3 + 1
    for kind in ("max", "avg"):
        ref = spatial_pool(x, window=(3, 3), strides=(2, 2),
                           sharding=ConvSharding(), kind=kind)
        with mesh:
            got = jax.jit(lambda x: spatial_pool(
                x, window=(3, 3), strides=(2, 2), sharding=sh, mesh=mesh,
                kind=kind))(x)
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   rtol=1e-6, atol=1e-6)
    g = jnp.ones((6,)); b = jnp.zeros((6,))
    ref = batch_norm(x, g, b, sharding=ConvSharding(), scope="local")
    with mesh:
        got = jax.jit(lambda x: batch_norm(
            x, g, b, sharding=sh, mesh=mesh, scope="global"))(x)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=1e-4, atol=1e-4)

    # --- CF x spatial: halo + CF collective in ONE shard_map --------------
    x = jax.random.normal(key, (2, 16, 8, 8), jnp.float32)
    w = jax.random.normal(jax.random.PRNGKey(1), (3, 3, 8, 12)) * 0.1
    ref = oracle_conv(x, w, 1)
    gr = jax.grad(lambda x, w: jnp.sum(oracle_conv(x, w, 1) ** 2),
                  argnums=(0, 1))(x, w)
    for mode in ("channel", "filter"):
        for overlap in (False, True):
            shc = CFSharding(batch_axes=(), cf_axis="model", mode=mode,
                             h_axis=("pod", "data"))
            with mesh:
                got = jax.jit(lambda x, w: cf_conv2d(
                    x, w, sharding=shc, mesh=mesh, overlap=overlap))(x, w)
                gd = jax.jit(jax.grad(lambda x, w: jnp.sum(cf_conv2d(
                    x, w, sharding=shc, mesh=mesh, overlap=overlap) ** 2),
                    argnums=(0, 1)))(x, w)
            np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                       rtol=2e-5, atol=2e-5)
            for a, b in zip(gd, gr):
                np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                           rtol=3e-4, atol=3e-4)

    # CF x spatial BN: per-channel stats cross the spatial axes now
    shc = CFSharding(batch_axes=(), cf_axis="model", h_axis=("pod", "data"))
    xb = jax.random.normal(key, (2, 16, 8, 8), jnp.float32) * 3 + 1
    gb = jnp.ones((8,)); bb = jnp.zeros((8,))
    ref = batch_norm(xb, gb, bb, sharding=ConvSharding(), scope="local")
    with mesh:
        got = jax.jit(lambda x: cf_batch_norm(
            x, gb, bb, sharding=shc, mesh=mesh, scope="global"))(xb)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=1e-4, atol=1e-4)

    # --- the Pallas implicit-GEMM backend through the composed path
    # (interpret mode on CPU — numerics-identical to the TPU lowering) -----
    with mesh:
        got = jax.jit(lambda x, w: spatial_conv2d(
            x, w, sharding=ConvSharding(batch_axes=("pod",),
                                        h_axis=("data", "model")),
            mesh=mesh, backend="pallas"))(x, w)
    np.testing.assert_allclose(np.asarray(got),
                               np.asarray(oracle_conv(x, w, 1)),
                               rtol=2e-5, atol=2e-5)

    # --- acceptance: a solved auto plan on the (2,2,2) mesh with >= 1
    # multi-axis-H layer and >= 1 CF x spatial layer vs the oracle ---------
    from repro.core import plan as plan_lib
    from repro.core.perfmodel import TPU_V5E
    from repro.data.pipeline import synthetic_mesh_batch
    from repro.models.cnn import meshnet

    cfg = meshnet.MeshNetConfig("t", input_hw=32, in_channels=8,
                                convs_per_block=1, widths=(16, 32, 64),
                                bn_scope="global")
    specs = meshnet.layer_specs(cfg, 2)
    auto = plan_lib.plan_line(TPU_V5E, specs, mesh)
    n_multi = sum(len(lp.sharding.h_axes) > 1 or len(lp.sharding.w_axes) > 1
                  for lp in auto.layers.values())
    n_cfsp = sum(isinstance(lp.sharding, CFSharding)
                 and lp.sharding.cf_axis is not None
                 and lp.sharding.is_spatial
                 for lp in auto.layers.values())
    assert n_multi >= 1, auto.describe()
    assert n_cfsp >= 1, auto.describe()

    params = meshnet.init(jax.random.PRNGKey(0), cfg)
    batch = {k: jnp.asarray(v) for k, v in
             synthetic_mesh_batch(0, 2, 32, 8, out_hw=4).items()}
    ref_l = meshnet.loss_fn(params, batch, cfg, ConvSharding())
    ref_g = jax.grad(lambda p: meshnet.loss_fn(
        p, batch, cfg, ConvSharding()))(params)
    with mesh:
        got_l = jax.jit(lambda p, bb: meshnet.loss_fn(
            p, bb, cfg, auto, mesh))(params, batch)
        got_g = jax.jit(jax.grad(lambda p: meshnet.loss_fn(
            p, batch, cfg, auto, mesh)))(params)
    np.testing.assert_allclose(float(got_l), float(ref_l), rtol=2e-5)
    for a, r in zip(jax.tree.leaves(got_g), jax.tree.leaves(ref_g)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(r),
                                   rtol=3e-4, atol=3e-5)

    # a forced mixed plan crossing single-axis, product-axis and CF x
    # spatial layers: each transition is one §III-C reshard point
    from repro.core.distribution import Dist
    forced = plan_lib.compile_plan(
        {"conv1_1": Dist("hyb", {"N": ("pod",), "H": ("data", "model")}),
         "conv2_1": Dist("cfh", {"N": ("pod",), "H": ("data",),
                                 "C": ("model",), "F": ("model",)}),
         "conv3_1": Dist("hyb1", {"N": ("data",), "H": ("model",)}),
         "pred": Dist("wprod", {"N": ("pod",), "W": ("data", "model")})},
        specs, mesh)
    assert forced.n_reshards == 3, forced.describe()
    with mesh:
        got_l = jax.jit(lambda p, bb: meshnet.loss_fn(
            p, bb, cfg, forced, mesh))(params, batch)
    np.testing.assert_allclose(float(got_l), float(ref_l), rtol=2e-5)


def check_memfit():
    """Memory-aware planning acceptance (paper §VI, Table 2): on a 2x2
    host mesh with a synthetic per-device capacity limit chosen so the
    uniform sample-parallel plan cannot fit (batch < devices: sample
    parallelism cannot reduce per-device memory below one sample), the
    --mem-limit solve returns a spatial/hybrid plan whose modeled peak
    fits, whose XLA-measured peak agrees with the model within the
    property-test tolerance (2x), and which executes fwd + bwd matching
    the single-device oracle."""
    from repro.core import calibrate as calib
    from repro.core import plan as plan_lib
    from repro.core.distribution import Dist
    from repro.core.perfmodel import machine_for, network_memory
    from repro.core.spatial_conv import ConvSharding
    from repro.core.strategy import CapacityError, prune_by_memory
    from repro.data.pipeline import synthetic_mesh_batch
    from repro.models.cnn import meshnet
    from repro.utils import FP32

    mesh = make_mesh(data=2, model=2)
    # the Machine launch.train solves its fp32 CNN step with: 4-byte
    # words.  The catalog v5e's 2-byte words halve every modeled byte
    # (the predicted/XLA ratio was 0.425 with them, 0.85 with these)
    M = machine_for(mesh.devices.flat[0], FP32.compute_bytes)
    ms = dict(mesh.shape)
    cfg = meshnet.MeshNetConfig("t", input_hw=32, in_channels=4,
                                convs_per_block=1, widths=(8, 16),
                                bn_scope="global")
    BATCH = 2        # < 4 devices: sample parallelism caps at 2-way
    specs = meshnet.layer_specs(cfg, BATCH)

    # the best sample-only residency (2-way N) must NOT fit the limit
    sample = [Dist("sample", {"N": ("data",)})] * len(specs)
    sample_peak = network_memory(M, specs, sample, ms)["peak_bytes"]
    limit = 0.75 * sample_peak
    assert sample_peak > limit

    plan = plan_lib.plan_line(M, specs, mesh, mem_limit=limit)
    mem = plan.predicted["memory"]
    assert mem["peak_bytes"] <= limit, plan.describe()
    assert mem["limit_bytes"] == limit
    # the fit must have been bought with spatial decomposition
    assert any(lp.sharding.is_spatial for lp in plan.layers.values()), \
        plan.describe()

    # a hopeless limit raises CapacityError with footprint diagnostics
    try:
        prune_by_memory(M, specs[0],
                        [Dist("sample", {"N": ("data",)})], ms, 64.0)
        raise AssertionError("expected CapacityError")
    except CapacityError as e:
        assert "conv1_1" in str(e) and "act_in" in str(e), e

    # XLA cross-check + oracle equivalence of the executed plan
    params = meshnet.init(jax.random.PRNGKey(0), cfg)
    batch = {k: jnp.asarray(v) for k, v in
             synthetic_mesh_batch(0, BATCH, 32, 4, out_hw=8).items()}
    ref_l = meshnet.loss_fn(params, batch, cfg, ConvSharding())
    ref_g = jax.grad(lambda p: meshnet.loss_fn(
        p, batch, cfg, ConvSharding()))(params)
    first = specs[0]
    with mesh:
        spec = plan.input_spec(first.name, first.h, first.w, first.k,
                               first.s, mesh)
        bb = dict(batch)
        bb["image"] = jax.device_put(batch["image"],
                                     NamedSharding(mesh, spec))
        step = jax.jit(jax.value_and_grad(
            lambda p, b: meshnet.loss_fn(p, b, cfg, plan, mesh)))
        res = calib.crosscheck_memory(plan, step, params, bb)
        assert 0.5 <= res["ratio"] <= 2.0, res
        got_l, got_g = step(params, bb)
    np.testing.assert_allclose(float(got_l), float(ref_l), rtol=2e-5)
    for a, r in zip(jax.tree.leaves(got_g), jax.tree.leaves(ref_g)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(r),
                                   rtol=3e-4, atol=3e-5)
    print(f"memfit: limit {limit:.0f}B, sample {sample_peak:.0f}B (out), "
          f"solved {mem['peak_bytes']:.0f}B (fits), "
          f"xla ratio {res['ratio']:.2f}")


def check_overlap():
    """The §IV-A latency-hiding schedule is a pure reorder: on a 4-device
    mesh the interior/boundary split (overlap=True) matches both the
    serialized path (overlap=False) and the single-device oracle, forward
    and grads, on the XLA and the Pallas-interpret local-conv backends —
    and the optimization_barrier pin survives jit (it is findable in the
    lowered HLO, so XLA cannot re-serialize the schedule behind our back).
    """
    from repro.core.spatial_conv import spatial_conv2d, ConvSharding

    mesh = make_mesh(data=2, model=2)
    key = jax.random.PRNGKey(0)
    sh = ConvSharding(batch_axes=("data",), h_axis="model")
    # shards tall enough that the interior/boundary split engages
    # (h_local=16 vs k): plain k=3 and a strided k=5 geometry
    for (K, s, H, W, C, F) in [(3, 1, 32, 12, 5, 7), (5, 2, 32, 16, 3, 8)]:
        x = jax.random.normal(key, (4, H, W, C), jnp.float32)
        w = jax.random.normal(jax.random.PRNGKey(1), (K, K, C, F)) * 0.1
        ref = oracle_conv(x, w, s)
        gr = jax.grad(lambda x, w: jnp.sum(oracle_conv(x, w, s) ** 2),
                      argnums=(0, 1))(x, w)
        for backend in ("xla", "pallas"):
            with mesh:
                def fn(x, w, ov):
                    return spatial_conv2d(
                        x, w, strides=(s, s), sharding=sh, mesh=mesh,
                        overlap=ov, backend=backend)
                got_ov = jax.jit(functools.partial(fn, ov=True))(x, w)
                got_ser = jax.jit(functools.partial(fn, ov=False))(x, w)
                np.testing.assert_allclose(np.asarray(got_ov),
                                           np.asarray(ref),
                                           rtol=2e-5, atol=2e-5)
                # overlap on/off is the same math in a different order
                np.testing.assert_allclose(np.asarray(got_ov),
                                           np.asarray(got_ser),
                                           rtol=2e-5, atol=2e-5)
                if backend == "xla":
                    # grads ride the XLA local conv: the Pallas kernel is
                    # forward-only (no VJP)
                    gd = jax.jit(jax.grad(
                        lambda x, w: jnp.sum(spatial_conv2d(
                            x, w, strides=(s, s), sharding=sh, mesh=mesh,
                            overlap=True, backend=backend) ** 2),
                        argnums=(0, 1)))(x, w)
                    for a, b in zip(gd, gr):
                        np.testing.assert_allclose(np.asarray(a),
                                                   np.asarray(b),
                                                   rtol=3e-4, atol=3e-4)

    # the HaloSchedule pin must survive jit: the lowered module contains
    # the opt-barrier that orders boundary convs after the interior conv
    x = jax.random.normal(key, (4, 32, 12, 5), jnp.float32)
    w = jax.random.normal(jax.random.PRNGKey(1), (3, 3, 5, 7)) * 0.1
    with mesh:
        jitted = jax.jit(lambda x, w: spatial_conv2d(
            x, w, sharding=sh, mesh=mesh, overlap=True))
        hlo = jitted.lower(x, w).as_text()
        assert "optimization_barrier" in hlo, \
            "optimization_barrier pin lost in lowering"
        ser = jax.jit(lambda x, w: spatial_conv2d(
            x, w, sharding=sh, mesh=mesh, overlap=False))
        assert "optimization_barrier" not in ser.lower(x, w).as_text(), \
            "serialized path must not carry the schedule pin"
    print("overlap: schedule parity (xla + pallas-interpret) OK, "
          "opt-barrier pinned through jit")


def check_trace():
    """Plan-aware tracing (core.trace) on a 4-device solved plan: the
    segmented re-execution profiler attributes every plan layer with a
    positive measured fwd+bwd cost, the isolated per-layer sums land
    within dispatch-overhead tolerance of the whole fused step, the
    attribution join (plan.attribution_report) covers every layer and
    names a worst-drifting cost term, and the layer/region annotations
    survive into the *compiled* HLO op_name metadata (named_scope names
    are absent from the StableHLO lowering on this jax — the compiled
    module is where profiles become decodable)."""
    from repro.core import plan as plan_lib
    from repro.core.perfmodel import TPU_V5E
    from repro.core.trace import StepTrace, trace_plan
    from repro.data.pipeline import synthetic_mesh_batch
    from repro.models.cnn import meshnet

    mesh = make_mesh(data=2, model=2)
    cfg = meshnet.MeshNetConfig("t", input_hw=32, in_channels=4,
                                convs_per_block=1, widths=(8, 16, 16),
                                bn_scope="global")
    specs = meshnet.layer_specs(cfg, 2)
    plan = plan_lib.plan_line(TPU_V5E, specs, mesh)
    params = meshnet.init(jax.random.PRNGKey(0), cfg)
    b = {k: jnp.asarray(v) for k, v in
         synthetic_mesh_batch(0, 2, 32, 4, out_hw=4).items()}
    first = specs[0]
    spec = plan.input_spec(first.name, first.h, first.w, first.k,
                           first.s, mesh)
    b["image"] = jax.device_put(b["image"], NamedSharding(mesh, spec))
    b["label"] = jax.device_put(b["label"], NamedSharding(mesh, P("data")))
    trace = trace_plan(plan, params, b, cfg=cfg, mesh=mesh,
                       reps=2, rounds=2)

    names = meshnet.layer_names(cfg)
    assert list(trace.layers) == names, list(trace.layers)
    for name, r in trace.layers.items():
        assert r["fwd_s"] > 0, (name, r)
        assert r["fwd_bwd_s"] >= r["fwd_s"] * 0.5, (name, r)
        assert r["bwd_s"] >= 0, (name, r)
    # segmentation-overhead bound: the isolated sums track the fused step
    # (isolated layers lose cross-layer fusion and pay extra dispatch, so
    # the bound is loose — catching 100x pathologies, not noise)
    ratio = trace.layer_sum_s / trace.step["fwd_bwd_s"]
    assert 0.1 <= ratio <= 10.0, (ratio, trace.layers, trace.step)
    assert trace.meta["measured_peak_bytes"] > 0
    assert StepTrace.from_dict(trace.to_dict()).to_dict() == trace.to_dict()

    # the attribution join covers every plan layer and names a worst term
    rep = plan.attribution_report(trace)
    assert set(rep["per_layer"]) == set(names), rep["per_layer"].keys()
    assert rep["worst_term"] in rep["terms"], rep
    assert rep["totals"]["measured_s"] > 0

    # annotations land in the COMPILED HLO metadata (op_name)
    with mesh:
        txt = jax.jit(lambda p, bb: meshnet.loss_fn(
            p, bb, cfg, plan, mesh)).lower(params, b).compile().as_text()
    for needle in names:
        assert needle in txt, f"layer scope {needle!r} not in compiled HLO"
    assert ("conv_interior" in txt or "conv_serialized" in txt
            or "cf_all_gather" in txt or "cf_reduce_scatter" in txt), \
        "no conv region annotation in compiled HLO"
    print(f"trace: {len(names)} layers attributed, layer_sum/step "
          f"{ratio:.2f}, worst term {rep['worst_term']}")


def check_compress():
    from repro.optim.grad_compress import cross_pod_mean
    mesh = make_mesh(data=2, model=2, pod=2)
    key = jax.random.PRNGKey(0)
    g = {"a": jax.random.normal(key, (64, 32)),
         "b": jax.random.normal(jax.random.PRNGKey(1), (128,))}
    with mesh:
        out_none, _ = jax.jit(lambda g: cross_pod_mean(
            g, mesh=mesh, method="none"))(g)
        out_bf16, _ = jax.jit(lambda g: cross_pod_mean(
            g, mesh=mesh, method="bf16"))(g)
    # replicated input => mean == input
    np.testing.assert_allclose(np.asarray(out_none["a"]),
                               np.asarray(g["a"]), rtol=1e-6)
    np.testing.assert_allclose(np.asarray(out_bf16["a"]),
                               np.asarray(g["a"]), rtol=2e-2, atol=2e-2)
    # int8 + EF: quantization error is carried, not lost — two applications
    # of the same gradient converge toward it on average
    ef = None
    with mesh:
        f = jax.jit(lambda g, ef: cross_pod_mean(
            g, mesh=mesh, method="int8_ef", error_feedback=ef))
        out1, ef = f(g, ef)
        out2, ef = f(g, ef)
    err1 = float(jnp.abs(out1["a"] - g["a"]).mean())
    two_step = (np.asarray(out1["a"]) + np.asarray(out2["a"])) / 2
    err2 = float(np.abs(two_step - np.asarray(g["a"])).mean())
    assert err2 < err1 + 1e-7, (err1, err2)
    assert err1 < 0.05  # int8 quantization error is small


def check_elastic():
    """The chaos-lane acceptance (ISSUE PR-8): a 4-device training run is
    faulted mid-run and must recover with a loss trajectory matching the
    uninterrupted oracle.  Three fault modes, selected by $CHAOS_MODE:

      step-fault   raise at step 7, same-mesh rollback to the step-6
                   checkpoint; post-restore losses match bitwise-ish
      kill-device  drop 1 of 4 devices at step 7 (DeviceLoss) -> elastic
                   remesh onto the 3 survivors, plan recovered from the
                   checkpoint's repro/plan@1 record (plan_from_spec, with
                   the designed PlanError -> fresh re-solve fallback),
                   reshard-on-restore, resume; losses match numerically
      corrupt-tmp  plant mid-save debris (torn tmp dir + malformed step
                   entry) then fault: latest_step must ignore the garbage,
                   rollback picks the valid step-6, gc sweeps the tmp

    With $CHAOS_ARTIFACTS_DIR set, the checkpoint dir, metrics JSONL and
    loss trajectories land there (CI uploads them on failure)."""
    import json
    import shutil
    import tempfile
    from repro.checkpoint.checkpoint import CheckpointManager
    from repro.core import plan as plan_lib
    from repro.core.perfmodel import TPU_V5E
    from repro.data.pipeline import synthetic_mesh_batch
    from repro.models.cnn import meshnet
    from repro.launch.mesh import elastic_factorization
    from repro.optim.optimizer import sgd
    from repro.runtime import chaos
    from repro.runtime.fault_tolerance import ResilientLoop, \
        StragglerMonitor
    from repro.train.metrics import MetricsLogger
    from repro.train.train_loop import make_train_step, TrainStepConfig, \
        shard_tree
    from repro.utils import FP32

    mode = os.environ.get("CHAOS_MODE", "kill-device")
    assert mode in ("step-fault", "kill-device", "corrupt-tmp"), mode
    NUM, EVERY, FAULT, BATCH = 10, 3, 7, 4
    devices = jax.devices()[:4]
    mesh4 = make_mesh(data=2, model=2, devices=devices)
    cfg = meshnet.MeshNetConfig("t", input_hw=24, in_channels=6,
                                convs_per_block=1, widths=(12, 24),
                                bn_scope="global")
    specs = meshnet.layer_specs(cfg, BATCH)
    opt = sgd(0.05, momentum=0.9)

    # a capacity limit both the 4-device and the shrunk 3-device solve can
    # meet — the elastic restart re-solves under the SAME limit
    peak4 = plan_lib.plan_line(TPU_V5E, specs, mesh4) \
        .predicted["memory"]["peak_bytes"]
    peak3 = plan_lib.plan_line(TPU_V5E, specs, {"data": 1, "model": 3}) \
        .predicted["memory"]["peak_bytes"]
    limit = 1.25 * max(peak4, peak3)
    plan4 = plan_lib.plan_line(TPU_V5E, specs, mesh4, mem_limit=limit)

    def init_state(mesh):
        # a fresh state every time: the train step DONATES its buffers,
        # so the oracle run and the faulted run cannot share arrays
        params = shard_tree(meshnet.init(jax.random.PRNGKey(0), cfg),
                            mesh, lambda x: P())
        return shard_tree((params, opt.init(params), None),
                          mesh, lambda x: P())

    def make_rig(mesh, plan):
        loss = functools.partial(meshnet.loss_fn, cfg=cfg, plan=plan,
                                 mesh=mesh)
        tstep = make_train_step(lambda p, b: loss(p, b), opt, mesh,
                                TrainStepConfig(precision=FP32))
        first = specs[0]
        spec = plan.input_spec(first.name, first.h, first.w, first.k,
                               first.s, mesh)

        def put(b):
            return {"image": jax.device_put(
                        b["image"], NamedSharding(mesh, spec)),
                    "label": jax.device_put(
                        b["label"], NamedSharding(mesh, P("data")))}
        return tstep, put

    tstep4, put4 = make_rig(mesh4, plan4)

    # --- the uninterrupted oracle -----------------------------------------
    oracle = []
    p, o, ef = init_state(mesh4)
    for s in range(NUM):
        b = put4(synthetic_mesh_batch(s, BATCH, cfg.input_hw,
                                      cfg.in_channels, out_hw=cfg.out_hw))
        p, o, ef, m = tstep4(p, o, ef, b)
        oracle.append(float(m["loss"]))

    # --- the faulted run --------------------------------------------------
    art = os.environ.get("CHAOS_ARTIFACTS_DIR")
    base = art or tempfile.mkdtemp()
    os.makedirs(base, exist_ok=True)
    ckdir = os.path.join(base, "ckpt")
    metrics_path = os.path.join(base, "metrics.jsonl")
    try:
        ck = CheckpointManager(ckdir, keep=3, async_save=True)
        mlog = MetricsLogger(metrics_path, echo=False)
        plan_spec = plan4.to_spec(mesh4, mem_limit=limit, config_hash="t",
                                  calibration_fingerprint=None)
        ctx = {"tstep": tstep4, "put": put4, "plan_spec": plan_spec}
        got: dict[int, float] = {}

        def make_step():
            def run(state, step):
                p, o, ef = state
                b = ctx["put"](synthetic_mesh_batch(
                    step, BATCH, cfg.input_hw, cfg.in_channels,
                    out_hw=cfg.out_hw))
                p, o, ef, m = ctx["tstep"](p, o, ef, b)
                got[step] = float(m["loss"])
                return (p, o, ef), m
            return run

        def remesh(survivors):
            assert len(survivors) == 3, survivors
            data, model = elastic_factorization(len(survivors),
                                                batch=BATCH)
            mesh3 = make_mesh(data=data, model=model,
                              devices=list(survivors))
            rec = ck.read_manifest()["plan"]
            assert rec["schema"] == plan_lib.PLAN_SCHEMA, rec
            assert rec["mesh"] == {"data": 2, "model": 2}, rec
            try:
                plan3 = plan_lib.plan_from_spec(
                    rec, specs, mesh3, machine=TPU_V5E,
                    mem_limit=rec["mem_limit"])
            except plan_lib.PlanError:
                # the stored dists don't lower onto the shrunk mesh —
                # the designed fallback is a fresh solve, SAME limit
                plan3 = plan_lib.plan_line(TPU_V5E, specs, mesh3,
                                           mem_limit=rec["mem_limit"])
            assert plan3.predicted["memory"]["peak_bytes"] <= \
                rec["mem_limit"], plan3.describe()
            tstep3, put3 = make_rig(mesh3, plan3)
            template3 = init_state(mesh3)
            ctx.update(tstep=tstep3, put=put3,
                       plan_spec=plan3.to_spec(
                           mesh3, mem_limit=rec["mem_limit"],
                           config_hash="t",
                           calibration_fingerprint=None))
            return make_step, template3

        if mode == "step-fault":
            inject = chaos.raise_at_step(FAULT)
            use_remesh = None
        elif mode == "kill-device":
            inject = chaos.drop_device_at_step(FAULT, devices=devices)
            use_remesh = remesh
        else:
            inject = chaos.compose(
                chaos.corrupt_checkpoint_tmp(ckdir, FAULT - 3),
                chaos.raise_at_step(FAULT))
            use_remesh = None

        loop = ResilientLoop(ckpt=ck, make_step=make_step,
                             ckpt_every=EVERY, max_failures=2,
                             remesh=use_remesh, metrics=mlog,
                             plan_spec=lambda: ctx["plan_spec"])
        state, step, _ = loop.run(init_state(mesh4), 0, NUM,
                                  monitor=StragglerMonitor(),
                                  inject_failure=inject)
        mlog.close()
        assert step == NUM, step
        assert sorted(got) == list(range(NUM)), sorted(got)

        with open(os.path.join(base, "losses.json"), "w") as f:
            json.dump({"oracle": oracle,
                       "got": [got[s] for s in range(NUM)]}, f)

        events = [json.loads(ln) for ln in open(metrics_path)]
        kinds = [e["kind"] for e in events]
        assert "fault" in kinds, kinds
        rollbacks = [e for e in events if e["kind"] == "rollback"]
        assert rollbacks and rollbacks[0]["step"] == FAULT - 1, rollbacks

        # pre-fault steps ran once on the original mesh: exact agreement
        np.testing.assert_allclose(
            [got[s] for s in range(FAULT - 1)], oracle[:FAULT - 1],
            rtol=1e-6)
        post = [got[s] for s in range(FAULT - 1, NUM)]
        if mode == "kill-device":
            assert "remesh" in kinds, kinds
            rm = next(e for e in events if e["kind"] == "remesh")
            assert rm["n_devices"] == 3, rm
            # the 3-device decomposition reorders the fp math — numeric,
            # not bitwise, agreement with the oracle trajectory
            np.testing.assert_allclose(post, oracle[FAULT - 1:],
                                       rtol=5e-3)
        else:
            np.testing.assert_allclose(post, oracle[FAULT - 1:],
                                       rtol=1e-5)
        if mode == "corrupt-tmp":
            left = os.listdir(ckdir)
            assert not [d for d in left if d.startswith("tmp-")], left
            assert "step-garbage" in left, left       # ignored, not fatal
            assert ck.latest_step() == NUM - 1, (ck.latest_step(), left)
        print(f"elastic[{mode}]: recovered at step {FAULT - 1}, "
              f"{NUM} steps, max post-restore drift "
              f"{max(abs(a - b) for a, b in zip(post, oracle[FAULT - 1:])):.2e}")
    finally:
        if not art:
            shutil.rmtree(base, ignore_errors=True)


def check_audit():
    """Property: EVERY executable candidate dist, over several mesh
    factorizations and layer shapes, lowers and audits clean on the XLA
    backend — zero unpriced collectives, zero phantom charges (no
    error-severity finding at all).  This is the pin that keeps
    perfmodel.layer_collectives (the priced inventory) and the runtime's
    actual shard_map lowerings from drifting apart."""
    from repro import analysis
    from repro.core import perfmodel as pm
    from repro.core import plan as plan_lib
    from repro.core import trace as trace_lib
    from repro.models.cnn import layers as L

    shapes = [
        pm.ConvLayer("probe", n=4, c=8, h=16, w=16, f=8),          # vanilla
        pm.ConvLayer("probe", n=1, c=16, h=16, w=16, f=16, s=2),   # stride 2
        pm.ConvLayer("probe", n=2, c=12, h=8, w=8, f=6, k=1),      # 1x1, c=12
        pm.ConvLayer("probe", n=2, c=4, h=32, w=8, f=8),           # tall
        pm.ConvLayer("probe", n=8, c=8, h=8, w=8, f=32),           # batch-rich
        pm.ConvLayer("probe", n=1, c=32, h=4, w=4, f=32),          # CF terrain
    ]
    checked = 0
    for data, model in [(2, 4), (4, 2), (1, 8), (8, 1)]:
        mesh = make_mesh(data=data, model=model)
        for spec in shapes:
            for dist in plan_lib.executable_candidates(spec,
                                                       dict(mesh.shape)):
                plan = plan_lib.compile_plan({spec.name: dist}, [spec],
                                             mesh)
                sh = plan.sharding(spec.name)
                params = {"w": jax.ShapeDtypeStruct(
                    (spec.k, spec.k, spec.c, spec.f), jnp.float32)}
                x = jax.ShapeDtypeStruct((spec.n, spec.h, spec.w, spec.c),
                                         jnp.float32)

                def loss(p, xx, sh=sh, spec=spec):
                    with trace_lib.layer_context(spec.name):
                        y = L.conv_apply(p, xx, stride=spec.s, sharding=sh,
                                         mesh=mesh, overlap=True)
                    return jnp.sum(y * y)

                findings = analysis.audit_step_fn(
                    jax.value_and_grad(loss, argnums=(0, 1)), (params, x),
                    plan, [spec], mesh, overlap=True, hlo=False,
                    grad_wrt_inputs=True)
                bad = [f for f in findings if f.severity == "error"]
                assert not bad, (
                    f"mesh data={data} model={model} "
                    f"layer={spec} dist={dist}: " +
                    "; ".join(f"{f.rule}: {f.message}" for f in bad))
                checked += 1
    print(f"audit: {checked} (mesh x shape x dist) lowerings audit clean")

    # --- negative direction: a broken program MUST fire the named rule ---
    from repro.core.spatial_conv import ConvSharding
    from repro.utils import shard_map
    mesh = make_mesh(data=2, model=4)
    spec = pm.ConvLayer("probe", n=4, c=8, h=16, w=16, f=8)
    dist = plan_lib._sharding_to_dist(
        ConvSharding(batch_axes=("data",), h_axis="model"))
    plan = plan_lib.compile_plan({spec.name: dist}, [spec], mesh)
    sh = plan.sharding(spec.name)
    params = {"w": jax.ShapeDtypeStruct((3, 3, spec.c, spec.f),
                                        jnp.float32)}
    x = jax.ShapeDtypeStruct((spec.n, spec.h, spec.w, spec.c), jnp.float32)

    # 1) inject a collective the model never priced -> unpriced-collective
    def loss_inj(p, xx):
        with trace_lib.layer_context(spec.name):
            y = L.conv_apply(p, xx, stride=1, sharding=sh, mesh=mesh,
                             overlap=True)
            extra = shard_map(
                lambda t: lax.psum(t, "data"), mesh=mesh,
                in_specs=P("data", "model", None, None),
                out_specs=P(None, "model", None, None))(xx)
        return jnp.sum(y * y) + jnp.sum(extra) * 1e-9

    found = analysis.audit_step_fn(
        jax.value_and_grad(loss_inj, argnums=(0, 1)), (params, x), plan,
        [spec], mesh, overlap=True, hlo=False, grad_wrt_inputs=True)
    assert any(f.rule == "unpriced-collective" and f.severity == "error"
               for f in found), [f"{f.rule}: {f.message}" for f in found]

    # 2) strip the overlap pin (lower serialized, declare overlapped) ->
    #    schedule-pin-missing
    def loss_ser(p, xx):
        with trace_lib.layer_context(spec.name):
            y = L.conv_apply(p, xx, stride=1, sharding=sh, mesh=mesh,
                             overlap=False)
        return jnp.sum(y * y)

    found = analysis.audit_step_fn(
        jax.value_and_grad(loss_ser, argnums=(0, 1)), (params, x), plan,
        [spec], mesh, overlap=True, hlo=False, grad_wrt_inputs=True)
    assert any(f.rule == "schedule-pin-missing" and f.severity == "error"
               for f in found), [f"{f.rule}: {f.message}" for f in found]
    print("audit: negative cases fire the named rules")


def check_wpack():
    """64-channel 3x3 convs split over a 2-device mesh: by H they run
    W-pair packed (core.spatial_conv.wpack), by W plain; either way they
    equal the single-device conv in value and gradients, and the packed
    BN under the global scope equals the plain single-device BN."""
    from repro.core.spatial_conv import spatial_conv2d, ConvSharding
    from repro.core.spatial_norm import _batch_norm, batch_norm
    mesh = make_mesh(data=1, model=2, devices=jax.devices()[:2])
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 16, 16, 64))
    w = jax.random.normal(jax.random.PRNGKey(1), (3, 3, 64, 64)) * 0.05
    ref = oracle_conv(x, w, 1)
    gr = jax.grad(lambda x, w: jnp.sum(oracle_conv(x, w, 1) ** 2),
                  argnums=(0, 1))(x, w)
    for sh, packed in ((ConvSharding(h_axis="model"), True),
                       (ConvSharding(w_axis="model"), False)):
        for overlap in (False, True):
            def conv(x, w):
                return spatial_conv2d(x, w, sharding=sh, mesh=mesh,
                                      overlap=overlap)
            with mesh:
                text = jax.jit(conv).lower(x, w).as_text(debug_info=True)
                assert ("conv_wpack" in text) == packed, (sh, overlap)
                got = jax.jit(conv)(x, w)
                gd = jax.jit(jax.grad(lambda x, w: jnp.sum(conv(x, w) ** 2),
                                      argnums=(0, 1)))(x, w)
            np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                       rtol=1e-5, atol=1e-5)
            for a, b in zip(gd, gr):
                np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                           rtol=1e-5, atol=1e-4)
    sh = ConvSharding(h_axis="model")
    g = 1 + 0.1 * jax.random.normal(jax.random.PRNGKey(2), (64,))
    with mesh:
        got = jax.jit(lambda x: batch_norm(x, g, g, sharding=sh, mesh=mesh,
                                           scope="global"))(x)
    want = _batch_norm(x, g, g, ConvSharding(), None, "local", 1e-5, fold=1)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


GROUPS = {"conv": check_conv, "attention": check_attention,
          "ssm": check_ssm, "models": check_models, "train": check_train,
          "compress": check_compress, "plan": check_plan,
          "cf": check_cf, "spatial2d": check_spatial2d,
          "multiaxis": check_multiaxis, "memfit": check_memfit,
          "overlap": check_overlap, "trace": check_trace,
          "elastic": check_elastic, "audit": check_audit,
          "wpack": check_wpack}

if __name__ == "__main__":
    GROUPS[sys.argv[1]]()
    print(f"dist_checks {sys.argv[1]} OK")
