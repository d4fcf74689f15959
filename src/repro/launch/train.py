"""Fault-tolerant training driver.

  PYTHONPATH=src python -m repro.launch.train --arch qwen1.5-0.5b --smoke \
      --steps 50 --batch 8 --seq 64 --ckpt-dir /tmp/ck [--data 2 --model 2]

Runs the resilient loop (checkpoint/restart, straggler monitor) around the
jit'd train step, on whatever devices JAX finds; the startup line names
them.  On a TPU the full configs train as they are: `python chip_smoke.py`
runs mesh1k at full width on one v5e chip (and `--chips 4` across a 2x2
host).  On a CPU host use --smoke (reduced config) with
`JAX_PLATFORMS=cpu`.  JAX's persistent compilation cache is on
(launch.compile_cache: `$JAX_COMPILATION_CACHE_DIR`, else
`<repo>/.jax_cache`).  `--arch mesh1k/mesh2k/resnet50` trains the
paper's CNN workloads under hybrid sample x spatial parallelism; add
`--strategy auto` to run the paper's §V-C strategy optimizer at startup
and execute its per-layer distribution plan (with automatic inter-layer
resharding) instead of the uniform default.
The solved plan may mix sample, spatial and channel/filter (§III-D) layers
— including H/W split over *products* of mesh axes (core.halo) and
CF x spatial compositions whose halo exchange and CF collective share one
shard_map (core.channel_conv), the decompositions 16x16 meshes need; the
CF mode ('filter' vs 'channel') is picked per layer from the
AG(x)-vs-RS(y) payload sizes.  Pass --no-cf to restrict the search to
sample/spatial for A/B comparison.
"""
from __future__ import annotations

import argparse
import dataclasses
import functools
import logging
import time

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.checkpoint.checkpoint import CheckpointManager
from repro.configs import registry
from repro.core import trace as trace_lib
from repro.data import pipeline
from repro.launch import compile_cache
from repro.launch import shardings as SH
from repro.launch.mesh import batch_axes, elastic_factorization, make_mesh
from repro.optim.optimizer import adamw, sgd, warmup_cosine
from repro.runtime.fault_tolerance import ResilientLoop, StragglerMonitor
from repro.train.metrics import MetricsLogger, debug_nan_check
from repro.train.train_loop import TrainStepConfig, make_train_step
from repro.utils import BF16, FP32, human_count, tree_num_params

logging.basicConfig(level=logging.INFO)


def parse_mem_limit(value) -> float | None:
    """--mem-limit BYTES|auto -> bytes/device (None = unconstrained).
    'auto' detects the live device's capacity (accelerators report it via
    memory_stats; hosts fall back to a MemAvailable share —
    core.calibrate.detect_mem_capacity)."""
    if value is None:
        return None
    if str(value).lower() == "auto":
        from repro.core.calibrate import detect_mem_capacity
        return detect_mem_capacity()
    return float(value)


def build_cnn_plan(args, arch, cfg, mesh, ba):
    """--strategy uniform: the legacy one-ConvSharding-everywhere plan.
    --strategy auto: run the §V-C optimizer on the arch's layer DAG and
    compile the solved per-layer distributions (core.plan).  With
    --calibrate the optimizer solves on *measured* costs: a calibration
    (core.calibrate) is loaded from the given path when it exists, else
    microbenchmarked on the live backend and written there.  With
    --mem-limit the solve is memory-aware: min-time subject to every
    layer's resident set (and the network peak) fitting the per-device
    capacity — the paper's §VI Table-2 'unreachable workloads' lever.

    The analytic Machine comes from the mesh's device kind
    (perfmodel.machine_for); off-TPU it is TPU_V5E, the rehearsal model.
    Only the mesh's structure is used: it may be a mesh of described
    devices (tests/rehearse_v5e.py)."""
    from repro.core import plan as plan_lib
    from repro.core.perfmodel import machine_for
    from repro.core.spatial_conv import ConvSharding
    from repro.utils import human_bytes
    if arch == "resnet50":
        from repro.models.cnn import resnet as M
        specs = M.layer_specs(args.batch, cfg)
        graph = M.resnet_graph(args.batch, cfg)
    else:
        from repro.models.cnn import meshnet as M
        specs = M.layer_specs(cfg, args.batch)
        graph = None
    # the step's own words: 4-byte fp32 (launch.train trains CNNs in FP32)
    machine = machine_for(mesh.devices.flat[0], FP32.compute_bytes)
    table, calib_fp = None, None
    if args.calibrate and args.strategy != "auto":
        # measured costs only feed the solver — don't spend minutes
        # microbenchmarking for a plan that ignores them
        logging.warning("--calibrate only affects --strategy auto; "
                        "skipping calibration for --strategy %s",
                        args.strategy)
    elif args.calibrate:
        from repro.core import calibrate as calib
        from repro.utils import fingerprint
        t0 = time.time()
        # honor --no-cf: don't spend startup time measuring CF candidate
        # shapes and collective sizes the solver is forbidden to pick
        cal = calib.load_or_run(args.calibrate, specs, mesh,
                                allow_channel_filter=not args.no_cf)
        print(f"calibration ready ({time.time() - t0:.2f}s, "
              f"{len(cal.table)} table entries)")
        machine, table = cal.machine, cal.table
        calib_fp = fingerprint(cal.to_json())
    mem_limit = parse_mem_limit(args.mem_limit)
    if mem_limit and args.strategy != "auto":
        logging.warning("--mem-limit constrains the --strategy auto solve "
                        "only; the uniform plan is not validated")
    if args.strategy == "auto":
        t0 = time.time()
        allow_cf = not args.no_cf
        if mem_limit:
            print(f"memory limit: {human_bytes(mem_limit)}/device")
        if graph is not None:
            plan = plan_lib.plan_graph(machine, graph, specs, mesh,
                                       table=table,
                                       allow_channel_filter=allow_cf,
                                       mem_limit=mem_limit,
                                       search=args.search)
        else:
            plan = plan_lib.plan_line(machine, specs, mesh, table=table,
                                      allow_channel_filter=allow_cf,
                                      mem_limit=mem_limit,
                                      search=args.search)
        print(f"strategy optimizer ({time.time() - t0:.2f}s):")
        print(plan.describe())
    else:
        plan = plan_lib.NetworkPlan.uniform(
            ConvSharding(batch_axes=ba, h_axis="model"),
            [l.name for l in specs])
    return plan, specs, calib_fp


def plan_record(args, cfg, extras, mesh) -> dict | None:
    """The ``repro/plan@1`` spec recorded in every checkpoint manifest:
    the solved per-layer dists + the solve's inputs (mesh shape,
    mem_limit, config hash, calibration fingerprint) — what an elastic
    restart lowers/re-solves on a new mesh (core.plan.plan_from_spec)."""
    plan = extras.get("plan")
    if plan is None:
        return None
    from repro.utils import fingerprint
    return plan.to_spec(
        mesh, mem_limit=parse_mem_limit(args.mem_limit),
        config_hash=fingerprint(cfg),
        calibration_fingerprint=extras.get("calib_fp"))


def on_mesh(tree, mesh):
    """Pin every leaf to `mesh`: leaves already placed there (e.g. params
    under their fsdp specs) pass through; everything else — notably the
    scalar optimizer counters opt.init leaves uncommitted on one device —
    is replicated.  A restore template must be *fully* committed to its
    mesh or reshard-on-restore would re-commit stray leaves to a single
    device and the jitted step would see mixed device sets.  A leaf on
    the mesh's devices but not under a NamedSharding of it (an uncommitted
    scalar on a 1x1 mesh) is re-placed too: the step's outputs carry the
    mesh in their type, so it would recompile at step 1."""
    def fix(x):
        sh = getattr(x, "sharding", None)
        if isinstance(sh, NamedSharding) and sh.mesh == mesh:
            return x
        return jax.device_put(x, NamedSharding(mesh, P()))
    return jax.tree.map(fix, tree)


def shardings_of(tree):
    return jax.tree.map(lambda x: x.sharding, tree)


def build(args, mesh):
    """Model, plan, loss, optimizer and batch source for `args` on `mesh`.
    Returns params on the host; train_state places them."""
    arch = registry.canon(args.arch)
    ba = batch_axes(mesh)
    extras = {"arch": arch, "plan": None, "specs": None, "layer_names": None,
              "calib_fp": None}
    if arch in registry.CNN_ARCHS:
        cfg = registry.get(arch, smoke=args.smoke)
        if args.bn_scope:
            cfg = dataclasses.replace(cfg, bn_scope=args.bn_scope)
        plan, specs, calib_fp = build_cnn_plan(args, arch, cfg, mesh, ba)
        extras.update(plan=plan, specs=specs, calib_fp=calib_fp)
        if arch == "resnet50":
            from repro.models.cnn import resnet as M
            mk = lambda s: pipeline.synthetic_imagenet_batch(
                s, args.batch, cfg.input_hw, cfg.n_classes)
        else:
            from repro.models.cnn import meshnet as M
            extras["layer_names"] = M.layer_names(cfg)
            mk = lambda s: pipeline.synthetic_mesh_batch(
                s, args.batch, cfg.input_hw, cfg.in_channels,
                out_hw=cfg.out_hw)
        loss = functools.partial(M.loss_fn, cfg=cfg, plan=plan, mesh=mesh)
        params = M.init(jax.random.PRNGKey(args.seed), cfg)
        opt = sgd(warmup_cosine(args.lr, 10, args.steps), momentum=0.9)
        prec = FP32
        first = specs[0]
        im_spec = plan.input_spec(first.name, first.h, first.w, first.k,
                                  first.s, mesh)
        batch_spec = lambda k: im_spec if k == "image" else P(ba)
    else:
        from repro.models.lm import transformer as T
        from repro.models.lm.modules import ShardCtx
        if args.strategy == "auto":
            # quarantine, not silence: the §V-C optimizer covers the CNN
            # archs (registry.SOLVABLE_ARCHS); an LM arch asking for a
            # solved plan would silently train uniform otherwise
            raise SystemExit(
                f"--strategy auto covers the solvable CNN archs "
                f"{registry.SOLVABLE_ARCHS}; {arch!r} is an LM arch the "
                f"§V-C optimizer has no candidate space for (drop "
                f"--strategy auto to train it with the uniform sharding)")
        if args.calibrate:
            logging.warning("--calibrate covers the CNN archs only; "
                            "ignored for %s", arch)
        if args.mem_limit:
            logging.warning("--mem-limit covers the CNN archs only; "
                            "ignored for %s", arch)
        if args.bn_scope:
            logging.warning("--bn-scope covers the CNN archs only; "
                            "ignored for %s", arch)
        cfg = registry.get(arch, smoke=args.smoke)
        ctx = ShardCtx(mesh=mesh, seq_axis="model", batch_axes=ba)
        loss = functools.partial(T.loss_fn, cfg=cfg, ctx=ctx,
                                 remat=args.remat)
        params = T.init(jax.random.PRNGKey(args.seed), cfg)
        opt = adamw(warmup_cosine(args.lr, 20, args.steps))
        prec = BF16 if args.bf16 else FP32
        mk = lambda s: pipeline.synthetic_lm_batch(
            s, args.batch, args.seq, cfg.vocab)
        batch_spec = lambda k: P(ba, "model")

    extras["batch_spec"] = batch_spec

    def put(b):
        return {k: jax.device_put(v, NamedSharding(mesh, batch_spec(k)))
                for k, v in b.items()}
    return cfg, params, opt, loss, mk, put, prec, extras


def train_state(params, opt, mesh):
    """(params, optimizer state, error-feedback state) committed to `mesh`:
    params under their FSDP specs, the optimizer state as `opt.init` lays
    it out beside them, stray leaves replicated (on_mesh)."""
    params = jax.tree.map(
        lambda x, s: jax.device_put(x, NamedSharding(mesh, s)),
        params, SH.fsdp_tree_specs(params, mesh))
    return on_mesh((params, opt.init(params), None), mesh)


def train_step(args, opt, loss, prec, mesh, state):
    """The jitted step, its new state pinned to `state`'s shardings (the
    leaves may be arrays or ShapeDtypeStructs)."""
    return make_train_step(
        lambda p, b: loss(p, b), opt, mesh,
        TrainStepConfig(grad_accum=args.grad_accum, precision=prec,
                        pod_compression=args.pod_compression),
        state_shardings=shardings_of(state))


def run_step(tstep, put, state, batch, step_num: int):
    """One training step, as `main`'s loop runs it: place the host `batch`
    on the mesh (`put`), run the compiled step `tstep` on `state` (params,
    optimizer state, error feedback), and read its metrics back to the
    host, which waits for the step to finish.  Returns (new state,
    {metric: float}).

    Under a profiler session the step is a ``StepTraceAnnotation``
    ``train.step`` (with `step_num`) holding ``train.put``,
    ``train.dispatch`` and ``train.readback``, and ``train.h2d`` runs from
    the start of the placement until every shard of the batch is on its
    device (core.trace.span_until_ready): the host->device copy, which
    ``put`` returns before finishing."""
    with jax.profiler.StepTraceAnnotation("train.step", step_num=step_num):
        with jax.profiler.TraceAnnotation("train.put"):
            b = trace_lib.span_until_ready("train.h2d", put, batch)
        with jax.profiler.TraceAnnotation("train.dispatch"):
            *state, m = tstep(*state, b)
        with jax.profiler.TraceAnnotation("train.readback"):
            host = {k: float(v) for k, v in m.items()}
    return tuple(state), host


def parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="mesh1k",
                    help="architecture id (registry); defaults to the "
                         "paper's 1K mesh-tangling CNN")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--strategy", default="uniform",
                    choices=["uniform", "auto"],
                    help="CNN parallelization: 'uniform' applies one hybrid "
                         "ConvSharding to every layer (legacy); 'auto' runs "
                         "the paper's §V-C optimizer at startup and executes "
                         "the solved per-layer plan with resharding — "
                         "including §III-D channel/filter layers, CF x "
                         "spatial compositions and product-axis spatial "
                         "splits (core.channel_conv, core.halo) unless "
                         "--no-cf")
    ap.add_argument("--no-cf", action="store_true",
                    help="exclude channel/filter candidates from --strategy "
                         "auto (sample/spatial only, the pre-CF behavior)")
    ap.add_argument("--search", default="greedy",
                    metavar="greedy|beam[:N]|hillclimb",
                    help="--strategy auto search mode: 'greedy' is the "
                         "paper's one-target-per-axis DP (default); "
                         "'beam[:N]' widens the candidate space (mesh axes "
                         "may go unassigned) and, on branchy DAGs, replaces "
                         "longest-path-first with a reshard-cost-aware "
                         "global beam DP of width N (default 4); "
                         "'hillclimb' is the stochastic local-search "
                         "baseline over the same wide space.  An elastic "
                         "remesh re-solves with the same mode")
    ap.add_argument("--calibrate", nargs="?", const="BENCH_calibration.json",
                    default=None, metavar="PATH",
                    help="solve --strategy auto on measured costs: "
                         "microbenchmark local conv at this arch's layer "
                         "shapes plus halo/collective primitives on the "
                         "live backend, fit Machine constants and an "
                         "EmpiricalTable (core.calibrate), and feed them to "
                         "the §V-C solver.  PATH (default "
                         "BENCH_calibration.json) is loaded when it exists, "
                         "else written — CNN archs only")
    ap.add_argument("--mem-limit", nargs="?", const="auto", default=None,
                    metavar="BYTES|auto",
                    help="per-device memory capacity for --strategy auto: "
                         "the §V-C solve becomes min-time subject to every "
                         "layer's resident set fitting (core.perfmodel."
                         "layer_memory), unlocking workloads sample "
                         "parallelism cannot fit (paper §VI Table 2).  "
                         "'auto' (the bare-flag default) detects the live "
                         "device capacity; an integer sets a synthetic "
                         "limit in bytes — CNN archs only")
    ap.add_argument("--bn-scope", default=None,
                    choices=["local", "spatial", "global"],
                    help="CNN batch-norm statistics scope (core."
                         "spatial_norm; default: the arch config's, "
                         "'local' per shard for the paper's models).  "
                         "'global' normalizes over the whole global batch, "
                         "so any mesh computes the 1-device function")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--data", type=int, default=1)
    ap.add_argument("--model", type=int, default=1)
    ap.add_argument("--pod", type=int, default=1)
    ap.add_argument("--grad-accum", type=int, default=1)
    ap.add_argument("--bf16", action="store_true")
    ap.add_argument("--remat", action="store_true")
    ap.add_argument("--pod-compression", default="none",
                    choices=["none", "bf16", "int8_ef"])
    ap.add_argument("--ckpt-dir", default="/tmp/repro_ckpt")
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--elastic", action="store_true",
                    help="survive device loss: on a DeviceLoss step fault "
                         "the loop rebuilds the mesh from the surviving "
                         "devices (launch.mesh.elastic_factorization), "
                         "re-solves the plan on the shrunk mesh under the "
                         "same --mem-limit, reshards the last checkpoint "
                         "onto it and resumes the deterministic batch "
                         "stream — CapacityError surfaces with the usual "
                         "diagnostics when nothing fits the survivors")
    ap.add_argument("--chaos", default=None, metavar="SPEC",
                    help="fault injection (runtime.chaos): e.g. 'raise@7' "
                         "(step fault), 'kill@5' / 'kill@5x2' (drop "
                         "devices -> DeviceLoss; pair with --elastic), "
                         "'corrupt@3' (plant checkpoint-tmp debris); "
                         "comma-compose")
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--metrics", nargs="?", const="METRICS.jsonl",
                    default=None, metavar="PATH",
                    help="write structured JSONL step records (loss, "
                         "step time, samples/s) to PATH (default "
                         "METRICS.jsonl) next to the terminal echo")
    ap.add_argument("--profile", nargs="?", const="BENCH_step_trace.json",
                    default=None, metavar="PATH",
                    help="profile instead of train: measure every plan "
                         "layer's isolated fwd/bwd cost (core.trace."
                         "trace_plan), print the predicted-vs-measured "
                         "attribution table, write the StepTrace JSON to "
                         "PATH (default BENCH_step_trace.json) plus a "
                         "Chrome-trace timeline next to it, then exit — "
                         "meshnet archs (mesh1k/mesh2k) only")
    ap.add_argument("--audit", action="store_true",
                    help="static fail-fast gate before training: lint the "
                         "built plan and audit its priced collectives "
                         "against the traced step (repro.analysis, "
                         "lowering-only — no timed work); abort when any "
                         "error-severity finding shows costed != executed "
                         "— meshnet archs (mesh1k/mesh2k) only")
    ap.add_argument("--debug-nans", action="store_true",
                    help="check loss/grad_norm for NaN/inf every step and "
                         "fail fast naming the first offending layer "
                         "(train.metrics.debug_nan_check)")
    return ap


def main(argv=None):
    """Train from command-line style `argv` (default: sys.argv[1:])."""
    ap = parser()
    args = ap.parse_args(argv)
    compile_cache.enable()
    try:
        from repro.core.strategy import parse_search
        parse_search(args.search)
    except ValueError as e:
        ap.error(str(e))

    mesh = make_mesh(data=args.data, model=args.model, pod=args.pod)
    cfg, params, opt, loss, mk, put, prec, extras = build(args, mesh)
    dev = mesh.devices.flat[0]
    print(f"arch={cfg.name} params={human_count(tree_num_params(params))} "
          f"mesh={dict(mesh.shape)} device={dev.platform}:{dev.device_kind} "
          f"x{mesh.devices.size}")

    if args.audit:
        audit_gate(args, cfg, mesh, extras)

    state = train_state(params, opt, mesh)
    if args.profile:
        profile(args, cfg, state[0], mk, put, mesh, extras)
        return

    ck = CheckpointManager(args.ckpt_dir, keep=3, async_save=True)
    tstep = train_step(args, opt, loss, prec, mesh, state)
    start = 0
    restored, manifest = ck.restore(state) if ck.latest_step() else (None,
                                                                     None)
    if restored is not None:
        state, start = restored, manifest["extra"]["step"]
        print(f"resumed from step {start}")
        rec = manifest.get("plan")
        if rec and rec.get("mesh") and rec["mesh"] != dict(mesh.shape):
            print(f"reshard-on-restore: checkpoint recorded mesh "
                  f"{rec['mesh']}, restoring onto {dict(mesh.shape)} "
                  f"(global arrays re-placed under the current plan)")

    pf = pipeline.Prefetcher(mk, start_step=start)
    mon = StragglerMonitor()
    t0 = time.time()
    losses = []
    mlog = MetricsLogger(args.metrics)
    mlog.log_run(arch=cfg.name, n_params=tree_num_params(params),
                 mesh=dict(mesh.shape), batch=args.batch, steps=args.steps,
                 strategy=args.strategy, start_step=start)

    # mutable execution context: an elastic remesh swaps the compiled step,
    # the batch placer and the recorded plan spec without rebuilding the
    # closures the loop already holds
    ctx = {"tstep": tstep, "put": put, "layer_names": extras["layer_names"],
           "plan_spec": plan_record(args, cfg, extras, mesh)}

    def put_noted(batch):
        b = ctx["put"](batch)
        ctx["batch_shardings"] = {k: v.sharding for k, v in b.items()}
        return b

    def make_step():
        def run(state, step):
            t_step = time.perf_counter()
            state, host = run_step(ctx["tstep"], put_noted, state,
                                   pf.get(step), step)
            wall = time.perf_counter() - t_step
            losses.append(host["loss"])
            if args.debug_nans:
                debug_nan_check(step, host, state[0], ctx["layer_names"])
            dt = (time.time() - t0) / (len(losses) or 1)
            mlog.log_step(step, losses[-1], step_time_s=dt,
                          samples_per_s=args.batch / dt if dt else None,
                          grad_norm=host["grad_norm"], wall_s=wall,
                          echo=step % args.log_every == 0)
            return state, host
        return run

    def remesh(survivors):
        """Elastic restart: rebuild mesh + plan + step over the survivors.

        Re-runs the full build (so --strategy auto re-solves under the same
        --mem-limit on the shrunk mesh — CapacityError surfaces here when
        nothing fits) and returns the step factory plus a state template
        sharded under the new mesh; the loop reshards-on-restore the last
        checkpoint's global arrays into it."""
        data, model = elastic_factorization(len(survivors),
                                            batch=args.batch)
        print(f"elastic restart: {len(survivors)} survivors -> mesh "
              f"data={data} model={model}; re-solving plan")
        new_mesh = make_mesh(data=data, model=model,
                             devices=list(survivors))
        cfg2, params2, opt2, loss2, _, put2, prec2, extras2 = \
            build(args, new_mesh)
        state2 = train_state(params2, opt2, new_mesh)
        ctx["tstep"] = train_step(args, opt2, loss2, prec2, new_mesh, state2)
        ctx["put"] = put2
        ctx["layer_names"] = extras2["layer_names"]
        ctx["plan_spec"] = plan_record(args, cfg2, extras2, new_mesh)
        return make_step, state2

    loop = ResilientLoop(ckpt=ck, make_step=make_step,
                         ckpt_every=args.ckpt_every,
                         remesh=remesh if args.elastic else None,
                         metrics=mlog,
                         plan_spec=lambda: ctx["plan_spec"])
    inject = None
    if args.chaos:
        from repro.runtime import chaos
        inject = chaos.parse(args.chaos, ckpt_dir=args.ckpt_dir,
                             devices=list(mesh.devices.flat))
    state, step, metrics = loop.run(state, start, args.steps, monitor=mon,
                                    inject_failure=inject)
    ck.save(step, state, extra={"step": step}, plan=ctx["plan_spec"])
    ck.wait()
    pf.close()
    mlog.log_done(step, loss=losses[-1], straggler=mon.stats)
    mlog.close()
    print(f"done at step {step}; final loss {losses[-1]:.4f}; "
          f"straggler stats {mon.stats}")
    return {"step": step, "losses": losses, "state": state, "mesh": mesh,
            "batch_shardings": ctx.get("batch_shardings", {})}


def audit_gate(args, cfg, mesh, extras):
    """--audit: prove costed == executed before spending a single step.

    Lints the built plan (repro.analysis.lint_plan via NetworkPlan.audit)
    and joins its priced collective inventory against the traced jaxpr of
    the real train step — all lowering-only.  Any error-severity finding
    aborts the run; warnings and infos print and training proceeds."""
    from repro import analysis
    if extras["layer_names"] is None:
        raise SystemExit("--audit covers the meshnet archs (mesh1k/"
                         "mesh2k) — the collective auditor walks "
                         "meshnet.loss_fn")
    t0 = time.time()
    findings = extras["plan"].audit(extras["specs"], mesh, cfg=cfg,
                                    overlap=True, hlo=False)
    errs = analysis.error_count(findings)
    print(f"plan audit: {len(findings)} finding(s), {errs} error(s) "
          f"({time.time() - t0:.1f}s, lowering-only)")
    print(analysis.format_findings(findings))
    if errs:
        raise SystemExit(
            f"--audit: {errs} error-severity finding(s) — the plan's "
            f"costed collectives do not match the traced step; refusing "
            f"to train on it")


def profile(args, cfg, params, mk, put, mesh, extras):
    """--profile: segmented per-layer cost measurement instead of training.

    Runs core.trace.trace_plan on the built plan, prints the
    predicted-vs-measured attribution table (when the plan carries a
    perf-model report, i.e. --strategy auto), and writes the StepTrace
    JSON (attribution embedded in meta) plus a Chrome-trace timeline."""
    from repro.core.trace import format_attribution, trace_plan
    if extras["layer_names"] is None:
        raise SystemExit("--profile covers the meshnet archs "
                         "(mesh1k/mesh2k) — the segmented profiler walks "
                         "meshnet.layer_fns")
    plan = extras["plan"]
    batch = put(mk(0))
    t0 = time.time()
    trace = trace_plan(plan, params, batch, cfg=cfg, mesh=mesh,
                       reps=2, rounds=2)
    print(f"profiled {len(trace.layers)} layers in {time.time() - t0:.1f}s "
          f"(step fwd+bwd {trace.step['fwd_bwd_s']*1e3:.3f} ms, "
          f"layer sum {trace.layer_sum_s*1e3:.3f} ms)")
    if plan.predicted and "layer_costs" in plan.predicted:
        report = plan.attribution_report(trace)
        trace.meta["attribution"] = report
        print(format_attribution(report))
    else:
        print("no perf-model prediction on this plan (use --strategy auto "
              "for the predicted-vs-measured attribution)")
    trace.save(args.profile)
    chrome = args.profile[:-5] if args.profile.endswith(".json") \
        else args.profile
    trace.save_chrome(chrome + ".chrome.json")
    print(f"wrote {args.profile} and {chrome}.chrome.json")


if __name__ == "__main__":
    main()
