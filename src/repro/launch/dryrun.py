"""Multi-pod dry-run: AOT-lower + compile every (arch x shape) cell on the
production mesh, proving the distribution config is coherent, that it fits
HBM (memory_analysis) and extracting roofline terms (cost_analysis +
collective bytes parsed from the compiled HLO).

  PYTHONPATH=src python -m repro.launch.dryrun --arch gemma2-9b \
      --shape train_4k [--multi-pod] [--variant opt] [--out DIR]

Writes one JSON artifact per cell to benchmarks/artifacts/dryrun/.

With --audit this becomes the static-analysis lane's driver instead: no
arch cell, no 512-device pod — the bench workloads' plans are solved
(repro.analysis.workloads, the same registry strategy_exec times) on a
small host mesh and each is linted + collective-audited lowering-only
(NetworkPlan.audit: jaxpr + StableHLO vs the priced inventory).  Findings
print as a table and land in one JSON artifact; any error-severity
finding exits non-zero.  Not a single timed step runs.

  PYTHONPATH=src python -m repro.launch.dryrun --audit \
      [mesh16cf mesh16_proxy ...] [--audit-out FILE]
"""
import os
import sys

# device count MUST precede every other import (jax locks it on first
# init): the pod-scale lowering wants 512 host devices, the --audit lane
# wants the small bench mesh (2x2, matching the CI bench lane).  This is a
# host-lowering tool: it pins the CPU platform so it never takes a chip.
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=4"
    if "--audit" in sys.argv else
    "--xla_force_host_platform_device_count=512")

import argparse
import dataclasses
import json
import re
import time
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import registry
from repro.launch import shardings as SH
from repro.launch.mesh import make_production_mesh, batch_axes
from repro.models.lm import transformer as T
from repro.models.lm.modules import ShardCtx
from repro.optim.optimizer import adamw
from repro.utils import BF16

# ---------------------------------------------------------------------------
# hardware constants (TPU v5e per assignment)
# ---------------------------------------------------------------------------
PEAK_FLOPS = 197e12          # bf16 / chip
HBM_BW = 819e9               # bytes/s / chip
ICI_BW = 50e9                # bytes/s / link


def sds(shape, dtype, mesh, spec):
    return jax.ShapeDtypeStruct(shape, dtype,
                                sharding=NamedSharding(mesh, spec))


# ---------------------------------------------------------------------------
# per-cell step builders
# ---------------------------------------------------------------------------

def build_lm_cell(arch: str, shape: str, mesh, variant: str = "base",
                  cfg=None, unroll: bool = False):
    cfg = cfg or registry.get(arch)
    info = registry.SHAPES[shape]
    seq, gbatch, kind = info["seq_len"], info["global_batch"], info["kind"]
    ba = batch_axes(mesh)
    nb = 1
    for a in ba:
        nb *= dict(mesh.shape)[a]
    bspec = ba if gbatch % max(nb, 1) == 0 and gbatch >= nb else None
    seq_axes = "model" if bspec is not None else ("data", "model")

    ctx = ShardCtx(mesh=mesh, seq_axis=seq_axes if kind != "train"
                   else "model",
                   batch_axes=(ba if bspec is not None else ()),
                   unroll=unroll)
    # training always has batch >= devices in the assigned cells
    if kind == "train":
        assert bspec is not None
        ctx = ShardCtx(mesh=mesh, seq_axis="model", batch_axes=ba,
                       unroll=unroll,
                       tp_axis="model" if variant == "opt" and
                       cfg.n_experts else None)

    # parameters (abstract init — no allocation).  Non-EP MoE (mixtral's
    # 8 experts < 16 shards) in the opt variant keeps bf16 master weights
    # (fp32 Adam moments remain) — the remaining lever that fits 46.7B
    # params after ZeRO-1 (§Perf).
    big_moe = cfg.n_experts and cfg.n_experts % dict(mesh.shape)["model"]
    p_dtype = jnp.bfloat16 if kind != "train" or \
        (variant in ("opt", "vpz") and big_moe) else jnp.float32
    p_abs = jax.eval_shape(lambda k: T.init(k, cfg, dtype=p_dtype),
                           jax.random.PRNGKey(0))
    pspecs = SH.fsdp_tree_specs(p_abs, mesh)
    m_sz = dict(mesh.shape)["model"]
    if variant in ("opt", "vpz"):
        # hillclimbed sharding (EXPERIMENTS.md §Perf): vocab-parallel
        # embedding (V over the model axis, when divisible — otherwise the
        # in-loss pad/reshard handles it) + expert parallelism for MoE
        # (E over the model axis — the paper's §III-D filter parallelism).
        pspecs = dict(pspecs)
        if cfg.vocab % m_sz == 0:
            pspecs["embed"] = P("model", None)
            if "unembed" in pspecs:
                pspecs["unembed"] = P(None, "model")
        if variant == "opt" and cfg.n_experts and \
                cfg.n_experts % m_sz == 0:
            def ep_spec(leaf_spec, leaf):
                if leaf.ndim >= 4 and leaf.shape[1] == cfg.n_experts:
                    rest = [None] * (leaf.ndim - 2)
                    for d in range(2, leaf.ndim):
                        if leaf.shape[d] % dict(mesh.shape)["data"] == 0:
                            rest[d - 2] = "data"
                            break
                    return P(None, "model", *rest)
                return leaf_spec
            pspecs["segments"] = jax.tree.map(
                ep_spec, pspecs["segments"], p_abs["segments"])
    params = SH.with_sharding(p_abs, mesh, pspecs)

    extra: dict[str, Any] = {}
    text_len = seq
    if cfg.frontend == "vit_stub" and kind != "decode":
        fl = min(cfg.frontend_len, seq // 2)
        text_len = seq - fl
        extra["patch_embeds"] = sds((gbatch, fl, cfg.d_model), jnp.bfloat16,
                                    mesh, P(bspec, "model", None))
    if cfg.frontend == "audio_stub":
        enc_len = seq if kind != "decode" else min(seq, 4096)
        extra["frames"] = sds((gbatch, enc_len, cfg.d_model), jnp.bfloat16,
                              mesh, P(bspec, "model", None))

    if kind == "train":
        opt = adamw(3e-4)
        opt_state = jax.eval_shape(opt.init, p_abs)
        # optimizer state: inherits param shardings (baseline) or ZeRO-1
        # over all chips (opt variant — EXPERIMENTS.md §Perf)
        ospecs = SH.zero1_tree_specs(p_abs, mesh) \
            if variant in ("opt", "vpz") else pspecs
        from repro.optim.optimizer import OptState
        opt_sds = OptState(
            sds((), jnp.int32, mesh, P()),
            SH.with_sharding(opt_state.mu, mesh, ospecs),
            SH.with_sharding(opt_state.nu, mesh, ospecs)
            if opt_state.nu is not None else None)

        batch = {"tokens": sds((gbatch, text_len), jnp.int32, mesh,
                               P(bspec, "model")),
                 "labels": sds((gbatch, text_len), jnp.int32, mesh,
                               P(bspec, "model"))}
        batch.update(extra)

        def loss(p, b):
            return T.loss_fn(p, b, cfg, ctx, remat=True, unroll=unroll,
                             vocab_parallel=variant in ("opt", "vpz"))

        from repro.train.train_loop import make_train_step, TrainStepConfig
        # micro-batching (the paper's memory lever [43]) for the non-EP
        # MoE opt variant: halves activation residency per micro-step.
        ga = 2 if (variant in ("opt", "vpz") and big_moe) else 1
        step = make_train_step(loss, opt, mesh,
                               TrainStepConfig(precision=BF16, remat=False,
                                               grad_accum=ga))
        args = (params, opt_sds, None, batch)
        return step, args, cfg

    if kind == "prefill":
        batch = {"tokens": sds((gbatch, text_len), jnp.int32, mesh,
                               P(bspec, "model"))}
        batch.update(extra)

        def prefill_fn(p, b):
            return T.prefill(p, cfg, b["tokens"], ctx,
                             extra_embeds=b.get("patch_embeds"),
                             frames=b.get("frames"), unroll=unroll)
        return jax.jit(prefill_fn), (params, batch), cfg

    # decode
    cache_abs = jax.eval_shape(
        lambda: T.init_decode_state(None, cfg, gbatch, seq, jnp.bfloat16))
    cspecs = SH.kv_cache_specs(cache_abs, mesh, bspec is not None, seq_axes)
    caches = SH.with_sharding(cache_abs, mesh, cspecs)
    tokens = sds((gbatch, 1), jnp.int32, mesh, P(bspec, None))
    length = sds((), jnp.int32, mesh, P())
    mem = None
    if cfg.is_encdec:
        mem = sds((gbatch, min(seq, 4096), cfg.d_model), jnp.bfloat16, mesh,
                  P(bspec, seq_axes if seq >= 8192 else "model", None))

    def decode_fn(p, t, c, L, m):
        return T.decode_step(p, cfg, t, c, L, ctx, memory=m, unroll=unroll)

    # donate the cache: decode updates it in place (aliased buffers)
    return (jax.jit(decode_fn, donate_argnums=(2,)),
            (params, tokens, caches, length, mem), cfg)


def build_cnn_cell(arch: str, mesh, batch: int = 32, variant: str = "base"):
    """Bonus cells: the paper's own CNN workloads under hybrid parallelism.

    variant="opt": bf16 activations/compute (fp32 master + BN stats) — the
    v5e-native precision the fp32-trained paper never used."""
    from repro.configs import registry as R
    import functools
    from repro.core.spatial_conv import ConvSharding
    from repro.optim.optimizer import sgd
    from repro.train.train_loop import make_train_step, TrainStepConfig
    from repro.utils import BF16, FP32
    cfg = R.get(arch)
    ba = batch_axes(mesh)
    sh = ConvSharding(batch_axes=ba, h_axis="model")
    if arch == "resnet50":
        from repro.models.cnn import resnet as M
        x = sds((batch, cfg.input_hw, cfg.input_hw, cfg.in_channels),
                jnp.float32, mesh, P(ba, "model", None, None))
        y = sds((batch,), jnp.int32, mesh, P(ba))
        loss = functools.partial(M.loss_fn, cfg=cfg, plan=sh, mesh=mesh)
        bdict = {"image": x, "label": y}
    else:
        from repro.models.cnn import meshnet as M
        x = sds((batch, cfg.input_hw, cfg.input_hw, cfg.in_channels),
                jnp.float32, mesh, P(ba, "model", None, None))
        y = sds((batch, cfg.out_hw, cfg.out_hw, 1), jnp.float32,
                mesh, P(ba, None, None, None))
        loss = functools.partial(M.loss_fn, cfg=cfg, plan=sh, mesh=mesh)
        bdict = {"image": x, "label": y}
    p_abs = jax.eval_shape(lambda k: M.init(k, cfg), jax.random.PRNGKey(0))
    pspecs = SH.fsdp_tree_specs(p_abs, mesh)
    params = SH.with_sharding(p_abs, mesh, pspecs)
    opt = sgd(0.1, momentum=0.9)
    opt_state = jax.eval_shape(opt.init, p_abs)
    from repro.optim.optimizer import OptState
    opt_sds = OptState(sds((), jnp.int32, mesh, P()),
                       SH.with_sharding(opt_state.mu, mesh, pspecs), None)
    prec = BF16 if variant == "opt" else FP32
    step = make_train_step(lambda p, b: loss(p, b), opt, mesh,
                           TrainStepConfig(precision=prec))
    return step, (params, opt_sds, None, bdict), cfg


# ---------------------------------------------------------------------------
# HLO collective analysis
# ---------------------------------------------------------------------------

_SHAPE_RE = re.compile(r"(f32|f16|bf16|s32|u32|s8|u8|f64|s64|pred|s16|u16)"
                       r"\[([0-9,]*)\]")
_BYTES = {"f64": 8, "s64": 8, "f32": 4, "s32": 4, "u32": 4, "bf16": 2,
          "f16": 2, "s16": 2, "u16": 2, "s8": 1, "u8": 1, "pred": 1}
COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
               "collective-permute")


def _shape_bytes(text: str) -> int:
    total = 0
    for m in _SHAPE_RE.finditer(text):
        dt, dims = m.groups()
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _BYTES[dt]
    return total


def collective_bytes(hlo: str) -> dict[str, int]:
    """Sum result-shape bytes of every collective op in the compiled HLO.

    These are *per-device* shard shapes in SPMD modules, i.e. bytes each
    device injects into the fabric per op instance.
    """
    out = {k: 0 for k in COLLECTIVES}
    out["count"] = 0
    for line in hlo.splitlines():
        s = line.strip()
        if "fusion" in s.split("=")[0]:
            continue
        for kind in COLLECTIVES:
            # match op name: `%all-gather.N = shape all-gather(...)`
            if f" {kind}(" in s or f" {kind}-start(" in s:
                lhs = s.split("=")[1] if "=" in s else s
                out[kind] += _shape_bytes(lhs.split(f" {kind}")[0])
                out["count"] += 1
                break
    return out


# ---------------------------------------------------------------------------
# cell runner
# ---------------------------------------------------------------------------

def _measure(fn, args, mesh):
    lowered = fn.lower(*args)
    compiled = lowered.compile()
    cost = compiled.cost_analysis()
    coll = collective_bytes(compiled.as_text())
    return {"flops": float(cost.get("flops", 0.0)),
            "bytes": float(cost.get("bytes accessed", 0.0)),
            "coll": float(sum(v for k, v in coll.items() if k != "count")),
            "colls": coll,
            "compiled": compiled}


def _probe_extrapolate(arch, shape, mesh, variant, n_layers):
    """XLA cost analysis counts while-loop (scan) bodies ONCE, so the full
    lowering under-reports per-layer work.  Probe the same cell at depth 2
    and 4 with the layer scans *unrolled* (loop-free HLO) and extrapolate
    linearly:  total(L) = C2 + (C4 - C2)/2 * (L - 2).  The marginal slope
    is exactly one layer's flops/bytes/collective traffic (incl. its FSDP
    gathers and optimizer update); the intercept holds embed/logits/loss."""
    cfg0 = registry.get(arch)
    out = {}
    for d in (2, 4):
        kw = {"n_layers": d}
        if cfg0.is_encdec:
            kw["n_enc_layers"] = d
        cfg_d = dataclasses.replace(cfg0, **kw)
        fn, args, _ = build_lm_cell(arch, shape, mesh, variant, cfg=cfg_d,
                                    unroll=True)
        m = _measure(fn, args, mesh)
        m.pop("compiled")
        out[d] = m
    ex = {}
    for k in ("flops", "bytes", "coll"):
        # clamp: XLA may pick different collective strategies at different
        # depths; a negative marginal is an artifact, not a saving.
        slope = max(0.0, (out[4][k] - out[2][k]) / 2.0)
        ex[k] = out[2][k] + slope * (n_layers - 2)
    ex["probe"] = {2: {k: out[2][k] for k in ("flops", "bytes", "coll")},
                   4: {k: out[4][k] for k in ("flops", "bytes", "coll")}}
    return ex


def run_cell(arch: str, shape: str, multi_pod: bool, out_dir: str,
             variant: str = "base") -> dict:
    t0 = time.time()
    mesh = make_production_mesh(multi_pod=multi_pod)
    nchips = int(np.prod(list(dict(mesh.shape).values())))
    if arch in registry.CNN_ARCHS:
        fn, args, cfg = build_cnn_cell(arch, mesh, variant=variant)
    else:
        fn, args, cfg = build_lm_cell(arch, shape, mesh, variant)

    with mesh:
        lowered = fn.lower(*args)
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower

    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    hlo = compiled.as_text()
    coll = collective_bytes(hlo)

    flops_dev = float(cost.get("flops", 0.0))
    bytes_dev = float(cost.get("bytes accessed", 0.0))
    coll_dev = float(sum(v for k, v in coll.items() if k != "count"))
    raw = {"flops": flops_dev, "bytes": bytes_dev, "coll": coll_dev}
    probe = None
    if arch not in registry.CNN_ARCHS:
        with mesh:
            probe = _probe_extrapolate(arch, shape, mesh, variant,
                                       cfg.n_layers)
        flops_dev = probe["flops"]
        bytes_dev = probe["bytes"]
        coll_dev = probe["coll"]

    result = {
        "arch": arch, "shape": shape, "variant": variant,
        "mesh": "2x16x16" if multi_pod else "16x16", "chips": nchips,
        "ok": True,
        "per_device": {
            "flops": flops_dev,
            "hbm_bytes": bytes_dev,
            "collective_bytes": coll_dev,
            "collectives": coll,
            "argument_bytes": getattr(mem, "argument_size_in_bytes", 0),
            "output_bytes": getattr(mem, "output_size_in_bytes", 0),
            "temp_bytes": getattr(mem, "temp_size_in_bytes", 0),
            "alias_bytes": getattr(mem, "alias_size_in_bytes", 0),
            "peak_bytes": getattr(mem, "argument_size_in_bytes", 0)
            + getattr(mem, "output_size_in_bytes", 0)
            + getattr(mem, "temp_size_in_bytes", 0)
            - getattr(mem, "alias_size_in_bytes", 0),
        },
        "roofline_s": {
            "compute": flops_dev / PEAK_FLOPS,
            "memory": bytes_dev / HBM_BW,
            "collective": coll_dev / ICI_BW,
        },
        "timing": {"lower_s": round(t_lower, 1),
                   "compile_s": round(t_compile, 1)},
        "raw_scan_counted_once": raw,
        "probe": probe["probe"] if probe else None,
    }
    dom = max(result["roofline_s"], key=result["roofline_s"].get)
    result["dominant"] = dom
    if arch not in registry.CNN_ARCHS:
        info = registry.SHAPES[shape]
        n_act = cfg.params_per_token()
        toks = info["seq_len"] * info["global_batch"] if \
            info["kind"] != "decode" else info["global_batch"]
        mf = 6.0 * n_act * toks if info["kind"] == "train" \
            else 2.0 * n_act * toks
        result["model_flops_total"] = mf
        result["model_flops_ratio"] = mf / max(flops_dev * nchips, 1.0)

    os.makedirs(out_dir, exist_ok=True)
    tag = f"{arch}-{shape}-{'pod2' if multi_pod else 'pod1'}"
    if variant != "base":
        tag += f"-{variant}"
    with open(os.path.join(out_dir, tag + ".json"), "w") as f:
        json.dump(result, f, indent=1)
    return result


def run_audit(workload_names, out_path: str, hlo: bool = True,
              search: str = "greedy") -> int:
    """--audit: lint + collective-audit the bench workloads' solved plans,
    lowering-only.  `search` selects the solver's search mode (greedy |
    beam[:N] | hillclimb) so the widened-search plans audit costed ==
    executed too.  Returns the process exit code (non-zero iff any
    error-severity finding)."""
    from repro import analysis
    from repro.analysis import workloads as WL
    from repro.core import perfmodel as pm
    from repro.launch.mesh import make_mesh

    ndev = jax.device_count()
    data = max(1, ndev // 2)
    model = max(1, ndev // data)
    mesh = make_mesh(data=data, model=model)
    names = list(workload_names) or list(WL.WORKLOADS)
    report = {
        "schema": "repro/plan_audit@1",
        "backend": jax.default_backend(),
        "mesh": dict(mesh.shape),
        "search": search,
        "workloads": {},
    }
    n_errors = 0
    for name in names:
        w = WL.WORKLOADS[name]
        if w.needs_model_axis and model <= 1:
            print(f"# audit/{name}: SKIPPED (mesh has no model axis)")
            report["workloads"][name] = {"skipped": True}
            continue
        t0 = time.time()
        plan, specs, cfg = WL.solve_workload(name, pm.TPU_V5E, mesh,
                                             search=search)
        findings = plan.audit(specs, mesh, cfg=cfg, overlap=True, hlo=hlo)
        errs = analysis.error_count(findings)
        n_errors += errs
        print(f"# audit/{name}: {len(findings)} finding(s), {errs} "
              f"error(s) ({time.time() - t0:.1f}s lowering-only)")
        print(analysis.format_findings(findings))
        report["workloads"][name] = {
            "skipped": False,
            "n_findings": len(findings),
            "n_errors": errs,
            "n_reshards": plan.n_reshards,
            "findings": [f.to_json() for f in findings],
        }
    report["n_errors"] = n_errors
    os.makedirs(os.path.dirname(out_path) or ".", exist_ok=True)
    with open(out_path, "w") as f:
        json.dump(report, f, indent=1, sort_keys=True)
    print(f"# wrote {out_path}")
    if n_errors:
        print(f"# AUDIT FAILED: {n_errors} error-severity finding(s) — "
              f"costed != executed")
    return 1 if n_errors else 0


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape", default="train_4k",
                    choices=list(registry.SHAPES) + ["cnn"])
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--variant", default="base")
    ap.add_argument("--out", default="benchmarks/artifacts/dryrun")
    ap.add_argument("--audit", nargs="*", default=None,
                    metavar="WORKLOAD",
                    help="static-analysis mode: lint + collective-audit "
                         "the named bench workload plans (all when none "
                         "named) instead of lowering an arch cell; exits "
                         "non-zero on any error-severity finding")
    ap.add_argument("--audit-out",
                    default="benchmarks/artifacts/audit/PLAN_audit.json")
    ap.add_argument("--no-hlo", action="store_true",
                    help="with --audit: skip the StableHLO cross-check "
                         "pass (jaxpr-only, faster)")
    ap.add_argument("--search", default="greedy",
                    metavar="greedy|beam[:N]|hillclimb",
                    help="with --audit: solver search mode for the audited "
                         "workload plans — CI audits the widened beam "
                         "search's plans next to the greedy ones")
    args = ap.parse_args()
    if args.audit is not None:
        raise SystemExit(run_audit(args.audit, args.audit_out,
                                   hlo=not args.no_hlo,
                                   search=args.search))
    if not args.arch:
        ap.error("--arch is required (unless running --audit)")
    r = run_cell(registry.canon(args.arch), args.shape, args.multi_pod,
                 args.out, args.variant)
    rl = r["roofline_s"]
    print(f"{args.arch} {args.shape} {r['mesh']}: OK "
          f"compute={rl['compute']*1e3:.2f}ms memory={rl['memory']*1e3:.2f}ms "
          f"collective={rl['collective']*1e3:.2f}ms dominant={r['dominant']} "
          f"peak={r['per_device']['peak_bytes']/2**30:.2f}GiB/dev "
          f"(lower {r['timing']['lower_s']}s compile {r['timing']['compile_s']}s)")


if __name__ == "__main__":
    main()
