"""Uniform vs solved per-layer plans: measured step time cross-checked
against the §V perf model — the validation loop the paper closes with
(predicted vs measured, Table I-III), now on *calibrated* costs.

  PYTHONPATH=src python -m benchmarks.strategy_exec [ndevices] \
      [--out BENCH_strategy.json] [--calibration BENCH_calibration.json] \
      [--gate] [--gate-tol 0.10] [--reps N] [--attribute] [--audit] \
      [--search beam:4] [--ratio-tol 10] [--ratio-warn-only]

Three gates ride on the measurements: the ordering promise (solved auto
plans measure no slower than their uniform baselines), the widened-search
promise (the wide-candidate beam/hillclimb plan measures no slower than
the greedy solve on at least one workload), and the model-fidelity gate
(the composed-calibrated model/measured ratio on mesh16cf/mesh16_proxy
stays within --ratio-tol of 1.0; the same plans are also re-priced
through the factor-free analytic view so BENCH_strategy.json records
whether composition calibration tightened the ratio).  With --attribute,
per-term drift additionally feeds calibrate.refit_from_attribution so
the next run's factors absorb the measured drift.

Runs on `ndevices` host CPU devices (default 4, set before jax import).
First the §V cost inputs are calibrated on the live backend
(core.calibrate: local-conv EmpiricalTable over the workloads' shard
shapes, fitted α/β and roofline constants; written to --calibration so CI
uploads it and later runs reuse it), then three workloads execute:

  * mesh128 — the strategy-choice workload from PR 1: uniform hybrid vs
    the §V-C solved auto plan (per-layer dists + reshard points);
  * mesh16cf — a small-spatial, channel-heavy meshnet where the solver
    picks §III-D channel/filter layers: cross-checks the perf model's CF
    cost terms (reduce-scatter fwd, all-gather BPw) against the
    core.channel_conv runtime, and A/Bs auto-with-CF vs auto-no-CF;
  * mesh2k_proxy — the 2K mesh-tangling geometry (5 convs/block) at
    reduced resolution under the 2-D H x W spatial decomposition;
  * mesh16_proxy — the 16x16-mesh decompositions at bench scale (batch 1,
    so sample parallelism is impossible): the solved plan mixes
    CF x spatial layers (CF collective + halo in one shard_map) and
    H split over the *product* of both mesh axes (core.halo), vs the
    uniform H x W baseline.
  * mesh2k_unreachable — the paper's §VI Table-2 memory story: batch 1
    under a synthetic per-device capacity limit that the sample-parallel
    (= replicated) plan cannot fit but the memory-aware solve
    (plan_line mem_limit=) does; both execute, and the solved plan's
    XLA-measured peak cross-checks the memory model.
  * overlap — the §IV-A latency-hiding A/B on ONE plan: the uniform
    H-split plan runs overlap-on (interior/boundary split, pinned halo
    issue order) vs force-serialized (loss_fn overlap=False: halo
    concatenated before one full conv).  The gate enforces that the
    schedule the calibrated η recommends (overlapped when η clears
    channel_conv.ETA_CHUNK_THRESHOLD, serialized below it) never
    measures slower than the rejected arm beyond tolerance — i.e. the
    calibration picks the measured winner of its own A/B.  The measured
    achieved-overlap η is emitted alongside the calibrated one.

A `ckpt_overhead` lane rides along (top-level report key): the same
compiled step runs bare vs with an async CheckpointManager.save enqueued
per call, and the gate fails when the save stalls the step beyond
--ckpt-tol — asynchronous checkpointing must stay off the critical path
(the fault-tolerance lever the elastic runtime depends on).

With --attribute the mesh16cf and mesh16_proxy auto plans additionally run
the segmented per-layer profiler (core.trace.trace_plan) and the
predicted-vs-measured join (plan.attribution_report): the workloads' known
single-digit model/measured end-to-end gap is decomposed into named
per-term drift ({fp,bp}_compute/{fp,bp}_comm/bpa/shuffle), written to
BENCH_attribution.json with the worst-drifting term named per workload.
Per-term drift beyond 5x prints an `# ATTRIBUTION WARNING` without
failing the exit code (the drift is a model-fidelity signal, not an
ordering-promise violation).

Output is both the legacy `name,us_per_call,derived` CSV rows and a
machine-readable BENCH_strategy.json: per-workload measured/predicted step
times AND peak memory (model-predicted vs XLA memory_analysis measured, so
the bench trajectory tracks memory alongside time), the auto-vs-uniform
measured ratio (the optimizer's ordering promise), and calibrated-vs-
analytic solver agreement (does the measured table change the solved plan,
and by how much the predicted cost).  With --gate the exit code enforces
the ordering promise — the CI bench lane fails when a solved auto plan
measures slower than uniform anywhere — and the capacity promise: a
mesh2k_unreachable memory-aware solve that fails (the solver cannot fit
its limit anymore) fails the gate too.  The capacity workload is exempt
from the ordering gate: its baseline is infeasible under the limit, so
beating it in time is not part of the promise.
"""
import os
import sys

if __name__ == "__main__":
    # the positional device count must come first: it is consumed before
    # jax import (XLA fixes the host device count at backend init)
    _n = int(sys.argv[1]) if len(sys.argv) > 1 and sys.argv[1].isdigit() \
        else 4
    # a host-CPU lane: pin the CPU platform so it never takes a chip
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ.setdefault(
        "XLA_FLAGS", f"--xla_force_host_platform_device_count={_n}")

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402

from benchmarks._timing import interleaved_samples, percentile  # noqa: E402

SCHEMA = "repro/bench_strategy@1"


def _uniform_plan(plan_lib, sh, names, specs, mesh, machine, table):
    """A uniform plan costed through the same §V-B model for comparability."""
    uniform = plan_lib.NetworkPlan.uniform(sh, names)
    return dataclasses.replace(
        uniform, predicted=plan_lib.compile_plan(
            {n: plan_lib._sharding_to_dist(sh) for n in names},
            specs, mesh, machine=machine, table=table).predicted)


def _measure_plans(cfg, batch, specs, plans, mesh, reps, rounds=4):
    """Measured seconds/step for every plan of one workload: compile and
    warm each train step, then hand the competing steps to the shared
    interleaved comparator (benchmarks/_timing.interleaved_min) so the
    auto-vs-uniform ratio is robust to host-load drift.  Each step is
    AOT-compiled so its XLA memory_analysis peak rides along.  A plan may
    be a (tag, plan) pair or a (tag, plan, overlap) triple — the overlap
    flag (default True) threads to meshnet.loss_fn, which is how the
    `overlap` workload force-serializes one arm of its A/B.  Returns
    ({tag: seconds}, {tag: measured peak bytes}, {tag: per-round means})
    — the point estimate is min-over-round-means as always; the raw round
    samples ride along so callers can report the p50/p95 spread."""
    import functools
    from repro.core.calibrate import compiled_peak_bytes
    from repro.data.pipeline import synthetic_mesh_batch
    from repro.models.cnn import meshnet
    params = meshnet.init(jax.random.PRNGKey(0), cfg)
    b = {k: jnp.asarray(v) for k, v in synthetic_mesh_batch(
        0, batch, cfg.input_hw, cfg.in_channels,
        out_hw=cfg.out_hw).items()}
    first = specs[0]
    lbl_spec = P("data") if batch % dict(mesh.shape)["data"] == 0 else P(None)
    with mesh:
        steps, peaks = {}, {}
        for entry in plans:
            tag, plan = entry[0], entry[1]
            ov = entry[2] if len(entry) > 2 else True
            spec = plan.input_spec(first.name, first.h, first.w, first.k,
                                   first.s, mesh)
            bb = {"image": jax.device_put(b["image"],
                                          NamedSharding(mesh, spec)),
                  "label": jax.device_put(b["label"],
                                          NamedSharding(mesh, lbl_spec))}
            step = jax.jit(jax.value_and_grad(
                lambda p, x, plan=plan, ov=ov: meshnet.loss_fn(
                    p, x, cfg, plan, mesh, overlap=ov)))
            compiled = step.lower(params, bb).compile()    # AOT: peak + call
            peaks[tag] = compiled_peak_bytes(compiled)
            compiled(params, bb)[0].block_until_ready()    # warm
            steps[tag] = functools.partial(compiled, params, bb)
        samples = interleaved_samples(steps, reps=reps, rounds=rounds)
        return {t: min(s) for t, s in samples.items()}, peaks, samples


def _analytic_view(machine, table):
    """The pre-composition cost model: the composition calibration factors
    reset to 1.0 and the shuffle:/composed: key families dropped from the
    table.  The local-conv entries stay — both views share them (they
    predate the composed calibration; the A/B isolates what composition
    calibration bought, not what conv timing bought)."""
    from repro.core.perfmodel import EmpiricalTable
    m = dataclasses.replace(machine, composed_cf_factor=1.0,
                            composed_halo_factor=1.0, shuffle_factor=1.0)
    t = EmpiricalTable({k: v for k, v in table.entries.items()
                        if not str(k[0]).startswith(("shuffle", "composed"))})
    return m, t


def _ratio_views(plan_lib, plan, specs, mesh, machine, table, measured_s):
    """Re-price the SAME measured plan through the analytic (factor-free,
    shuffle-table-free) view and report both model/measured ratios.  The
    `calibration_improves` bit is the tentpole's win condition: the
    composed-calibrated prediction must sit closer to the measurement
    (in log distance — over- and under-prediction count alike)."""
    m_a, t_a = _analytic_view(machine, table)
    pred_ana = plan_lib.compile_plan(
        {n: lp.dist for n, lp in plan.layers.items()}, specs, mesh,
        machine=m_a, table=t_a).predicted["total"]
    pred_cal = plan.predicted["total"]
    r_cal = float(pred_cal / measured_s)
    r_ana = float(pred_ana / measured_s)
    return {"ratio_calibrated": r_cal, "ratio_analytic": r_ana,
            "analytic_predicted_s": float(pred_ana),
            "calibrated_predicted_s": float(pred_cal),
            "calibration_improves":
                bool(abs(math.log(r_cal)) <= abs(math.log(r_ana)))}


def _solver_agreement(plan_lib, machine, table, specs, mesh, **kw):
    """Does solving on the measured table change the plan vs the analytic
    model, and by how much the predicted cost?  (The calibrated and the
    analytic solver must both return executable plans — this runs both.)"""
    auto_cal = plan_lib.plan_line(machine, specs, mesh, table=table, **kw)
    auto_ana = plan_lib.plan_line(machine, specs, mesh, **kw)
    differ = [n for n in auto_cal.layers
              if not auto_cal.layers[n].dist.same_as(auto_ana.layers[n].dist)]
    return auto_cal, {
        "calibrated_predicted_s": auto_cal.predicted["total"],
        "analytic_predicted_s": auto_ana.predicted["total"],
        "n_layers_differ": len(differ),
        "layers_differ": differ,
        "same_plan": not differ,
    }


def _bench_workload(name, cfg, batch, specs, plans, mesh, reps, rounds,
                    baseline_tag, auto_tag, agreement):
    measured, peaks, samples = _measure_plans(cfg, batch, specs, plans,
                                              mesh, reps, rounds)
    entries = {}
    for entry in plans:
        tag, plan = entry[0], entry[1]
        dt = measured[tag]
        p50 = percentile(samples[tag], 50)
        p95 = percentile(samples[tag], 95)
        pred = plan.predicted["total"] if plan.predicted else float("nan")
        pmem = plan.predicted["memory"]["peak_bytes"] \
            if plan.predicted and "memory" in plan.predicted else float("nan")
        mmem = peaks[tag]
        entries[tag] = {"measured_s": dt, "predicted_s": pred,
                        "measured_p50_s": p50, "measured_p95_s": p95,
                        "model_measured_ratio": pred / dt,
                        "predicted_peak_bytes": pmem,
                        "measured_peak_bytes": mmem,
                        "mem_model_measured_ratio":
                            pmem / mmem if mmem else float("nan"),
                        "n_reshards": plan.n_reshards}
        print(f"strategy_exec/{name}/{tag},{dt*1e6:.1f},"
              f"p50_us={p50*1e6:.1f} p95_us={p95*1e6:.1f} "
              f"predicted_us={pred*1e6:.1f} "
              f"model_measured_ratio={pred/dt:.3f} "
              f"predicted_peak_bytes={pmem:.0f} "
              f"measured_peak_bytes={mmem:.0f} "
              f"reshards={plan.n_reshards}")
    ratio = entries[auto_tag]["measured_s"] / \
        entries[baseline_tag]["measured_s"]
    return {"baseline": baseline_tag, "auto": auto_tag, "entries": entries,
            "auto_vs_uniform_measured": ratio,
            "solver_agreement": agreement}


def _bench_ckpt_overhead(cfg, batch, specs, plan, mesh, reps, rounds, tol):
    """Async checkpointing must stay off the step critical path.  The same
    compiled train-ish step runs in two interleaved arms: bare, and with a
    CheckpointManager.save enqueued per call (host copy synchronous, npz
    write on the daemon thread).  The measured ratio gates the CI bench
    lane: an async save that stalls the step beyond `tol` is the classic
    checkpoint-stall regression the async path exists to prevent."""
    import functools
    import itertools
    import shutil
    import tempfile
    from repro.checkpoint.checkpoint import CheckpointManager
    from repro.data.pipeline import synthetic_mesh_batch
    from repro.models.cnn import meshnet
    params = meshnet.init(jax.random.PRNGKey(0), cfg)
    b = {k: jnp.asarray(v) for k, v in synthetic_mesh_batch(
        0, batch, cfg.input_hw, cfg.in_channels,
        out_hw=cfg.out_hw).items()}
    first = specs[0]
    lbl_spec = P("data") if batch % dict(mesh.shape)["data"] == 0 else P(None)
    ckdir = tempfile.mkdtemp()
    try:
        with mesh:
            spec = plan.input_spec(first.name, first.h, first.w, first.k,
                                   first.s, mesh)
            bb = {"image": jax.device_put(b["image"],
                                          NamedSharding(mesh, spec)),
                  "label": jax.device_put(b["label"],
                                          NamedSharding(mesh, lbl_spec))}
            step = jax.jit(jax.value_and_grad(
                lambda p, x: meshnet.loss_fn(p, x, cfg, plan, mesh)))
            compiled = step.lower(params, bb).compile()
            compiled(params, bb)[0].block_until_ready()        # warm
            ck = CheckpointManager(ckdir, keep=2, async_save=True)
            counter = itertools.count()

            def with_save():
                out = compiled(params, bb)
                ck.save(next(counter), params, extra={"step": 0})
                return out
            samples = interleaved_samples(
                {"no_ckpt": functools.partial(compiled, params, bb),
                 "async_ckpt": with_save}, reps=reps, rounds=rounds)
            ck.wait()
    finally:
        shutil.rmtree(ckdir, ignore_errors=True)
    no = min(samples["no_ckpt"])
    asy = min(samples["async_ckpt"])
    return {"no_ckpt_s": no, "async_ckpt_s": asy,
            "overhead_ratio": asy / no, "tolerance": tol,
            "ok": asy / no <= 1 + tol}


def _attribute(targets, mesh, out_path, reps, rounds):
    """--attribute: decompose each target's model-vs-measured gap into
    named per-term drift.  Runs the segmented per-layer profiler
    (core.trace.trace_plan) on the solved plan and joins it against the
    perf-model prediction (plan.attribution_report); the JSON written to
    `out_path` names the worst-drifting cost term per workload.  Returns
    (warned, {workload: attribution report}) — warned is whether any term
    drifted beyond 5x (warn-only — printed, not gated); the reports feed
    calibrate.refit_from_attribution so the drift drives recalibration."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.core.trace import format_attribution, trace_plan
    from repro.data.pipeline import synthetic_mesh_batch
    from repro.models.cnn import meshnet
    report = {"schema": "repro/bench_attribution@1",
              "backend": jax.default_backend(),
              "mesh": dict(mesh.shape), "workloads": {}}
    warned = False
    for name, (cfg, batch, specs, plan) in targets.items():
        params = meshnet.init(jax.random.PRNGKey(0), cfg)
        b = {k: jnp.asarray(v) for k, v in synthetic_mesh_batch(
            0, batch, cfg.input_hw, cfg.in_channels,
            out_hw=cfg.out_hw).items()}
        first = specs[0]
        spec = plan.input_spec(first.name, first.h, first.w, first.k,
                               first.s, mesh)
        lbl = P("data") if batch % dict(mesh.shape)["data"] == 0 else P(None)
        bb = {"image": jax.device_put(b["image"], NamedSharding(mesh, spec)),
              "label": jax.device_put(b["label"], NamedSharding(mesh, lbl))}
        trace = trace_plan(plan, params, bb, cfg=cfg, mesh=mesh,
                           reps=reps, rounds=rounds)
        rep = plan.attribution_report(trace)
        print(f"# attribution/{name} (worst term: {rep['worst_term']}):")
        print(format_attribution(rep))
        report["workloads"][name] = {"trace": trace.to_dict(),
                                     "attribution": rep}
        for term, t in rep["terms"].items():
            if t["drift"] > 5.0 or t["drift"] < 0.2:
                warned = True
                print(f"# ATTRIBUTION WARNING: {name} term {term} drifts "
                      f"{t['drift']:.2f}x from the model (warn-only)")
    with open(out_path, "w") as f:
        json.dump(report, f, indent=1, sort_keys=True)
    print(f"# wrote {out_path}")
    return warned, {n: w["attribution"]
                    for n, w in report["workloads"].items()}


def run(args) -> int:
    from repro.core import calibrate as calib
    from repro.core import plan as plan_lib
    from repro.core.channel_conv import CFSharding
    from repro.core.spatial_conv import ConvSharding
    from repro.launch.mesh import make_mesh
    from repro.models.cnn import meshnet

    ndev = jax.device_count()
    # only a positional count the user actually passed can be "ignored"
    # (XLA_FLAGS set in the environment is honored as-is, no warning)
    if args.ndevices is not None and args.ndevices != ndev:
        print(f"# WARNING: requested {args.ndevices} devices but the "
              f"backend has {ndev} — the positional count only takes "
              f"effect as the FIRST argument (it must be consumed before "
              f"jax import) and is overridden by XLA_FLAGS in the "
              f"environment")
    data = max(1, ndev // 2)
    model = max(1, ndev // data)
    mesh = make_mesh(data=data, model=model)
    uni_sh = ConvSharding(batch_axes=("data",), h_axis="model")

    # --- workloads: the ONE registry the static-analysis lane audits -----
    # (repro.analysis.workloads — keeping the configs there means the
    # plans this bench times are exactly the plans dryrun --audit proves)
    from repro.analysis.workloads import (CFG128 as cfg128, CFG16 as cfg16,
                                          CFG2K as cfg2k, CFG16P as cfg16p,
                                          CFG2KU as cfg2ku)
    specs128 = meshnet.layer_specs(cfg128, 2)
    specs16 = meshnet.layer_specs(cfg16, 2)
    specs2k = meshnet.layer_specs(cfg2k, 1)
    specs16p = meshnet.layer_specs(cfg16p, 1)
    specs2ku = meshnet.layer_specs(cfg2ku, 1)

    # --- calibrate the cost inputs on the live backend (§V, measured) ----
    # grow_table: a calibration restored from the CI cache (or a previous
    # local run) is extended with any shard shapes these workloads add,
    # instead of silently degrading to the analytic model for them
    union = list(specs128) + list(specs16) + \
        (list(specs2k) + list(specs16p) + list(specs2ku)
         if data > 1 else [])
    cal = calib.load_or_run(args.calibration, union, mesh, reps=args.reps,
                            grow_table=True)
    machine, table = cal.machine, cal.table

    workloads = {}
    attr_targets = {}     # --attribute: {workload: (cfg, batch, specs, plan)}
    audit_targets = {}    # --audit: {workload: (plan, specs, cfg)}

    # --- mesh128: the strategy choice is non-trivial on this mesh --------
    # (batch 2 < device count: pure sample parallelism invalid)
    names = meshnet.layer_names(cfg128)
    auto, agree = _solver_agreement(plan_lib, machine, table, specs128, mesh)
    uni128 = _uniform_plan(plan_lib, uni_sh, names, specs128, mesh,
                           machine, table)
    workloads["mesh128"] = _bench_workload(
        "mesh128", cfg128, 2, specs128,
        (("uniform", uni128), ("auto", auto)),
        mesh, args.reps, args.rounds, "uniform", "auto", agree)
    audit_targets["mesh128"] = (auto, specs128, cfg128)

    # --- overlap: the §IV-A latency-hiding A/B on the SAME plan ----------
    # one uniform H-split plan, two arms: overlap=True (interior/boundary
    # split + pinned halo issue order) vs overlap=False (halo concatenated
    # before one full-tile conv — nothing to hide).  The gate checks the
    # calibration's CALL, not a fixed winner: the arm the fitted η
    # recommends (overlap pays iff η clears the same threshold that
    # enables CF chunking) must not measure slower than the rejected arm
    # beyond tolerance.  On hardware whose scheduler genuinely hides the
    # halo (high η) that means overlapped <= serialized; on a machine
    # that cannot hide it (low η — host XLA) it means the split's
    # overhead stays on the serialized side of tolerance.  Either way a
    # calibration that mispredicts its own A/B fails the lane.  The
    # measured achieved η rides into the report next to the calibrated
    # one so the trajectory can watch them drift.
    from repro.core.channel_conv import ETA_CHUNK_THRESHOLD
    names = meshnet.layer_names(cfg128)
    ov_plan = _uniform_plan(plan_lib, uni_sh, names, specs128, mesh,
                            machine, table)
    ser_plan = dataclasses.replace(
        ov_plan, predicted=plan_lib.compile_plan(
            {n: plan_lib._sharding_to_dist(uni_sh) for n in names},
            specs128, mesh, machine=machine, table=table,
            overlap=False).predicted)
    overlap_pays = machine.overlap_eta >= ETA_CHUNK_THRESHOLD
    chosen, rejected = ("overlapped", "serialized") if overlap_pays \
        else ("serialized", "overlapped")
    workloads["overlap"] = _bench_workload(
        "overlap", cfg128, 2, specs128,
        (("serialized", ser_plan, False), ("overlapped", ov_plan, True)),
        mesh, args.reps, args.rounds, rejected, chosen,
        {"same_plan": True, "n_layers_differ": 0, "layers_differ": [],
         "note": "same plan both arms; the A/B toggles overlap only"})
    workloads["overlap"]["calibrated_choice"] = chosen
    audit_targets["overlap"] = (ov_plan, specs128, cfg128)
    t_ov = workloads["overlap"]["entries"]["overlapped"]["measured_s"]
    t_ser = workloads["overlap"]["entries"]["serialized"]["measured_s"]
    credit = sum(ov_plan.predicted.get("overlap_credit", {}).values())
    eta_cal = machine.overlap_eta
    hidden_at_1 = credit / eta_cal if eta_cal > 0 else 0.0
    eta_meas = min(max((t_ser - t_ov) / hidden_at_1, 0.0), 1.0) \
        if hidden_at_1 > 0 else None
    workloads["overlap"]["eta"] = {
        "calibrated": eta_cal,
        "measured": eta_meas,
        "predicted_hidden_s": credit,
        "measured_hidden_s": t_ser - t_ov,
    }
    print(f"# overlap: serialized {t_ser*1e6:.1f}us, overlapped "
          f"{t_ov*1e6:.1f}us; eta calibrated {eta_cal:.2f}, measured "
          + (f"{eta_meas:.2f}" if eta_meas is not None else "n/a"))

    # --- mesh16cf: late layers too small to split spatially (h=4 < k) but
    # channel-heavy — the §III-D sweet spot.  The auto plan should contain
    # CF layers; its model_measured_ratio cross-checks the CF cost terms
    # against the core.channel_conv runtime. -----------------------------
    names = meshnet.layer_names(cfg16)
    auto_cf, agree = _solver_agreement(plan_lib, machine, table, specs16,
                                       mesh)
    n_cf = sum(isinstance(lp.sharding, CFSharding)
               for lp in auto_cf.layers.values())
    print(f"# mesh16cf auto plan: {n_cf} CF layers")
    wide16 = plan_lib.plan_line(machine, specs16, mesh, table=table,
                                search=args.search)
    workloads["mesh16cf"] = _bench_workload(
        "mesh16cf", cfg16, 2, specs16,
        (("uniform", _uniform_plan(plan_lib, uni_sh, names, specs16, mesh,
                                   machine, table)),
         ("auto_cf", auto_cf),
         ("auto_wide", wide16),
         ("auto_nocf", plan_lib.plan_line(machine, specs16, mesh,
                                          table=table,
                                          allow_channel_filter=False))),
        mesh, args.reps, args.rounds, "uniform", "auto_cf", agree)
    workloads["mesh16cf"]["n_cf_layers"] = n_cf
    workloads["mesh16cf"]["ratio_views"] = _ratio_views(
        plan_lib, auto_cf, specs16, mesh, machine, table,
        workloads["mesh16cf"]["entries"]["auto_cf"]["measured_s"])
    attr_targets["mesh16cf"] = (cfg16, 2, specs16, auto_cf)
    audit_targets["mesh16cf"] = (auto_cf, specs16, cfg16)
    audit_targets["mesh16cf_wide"] = (wide16, specs16, cfg16)

    # --- mesh2k_proxy: the 2K model's depth (5 convs/block) at reduced
    # resolution, under the 2-D H x W decomposition (W on the data axis,
    # H on the model axis; batch 1 — the paper's memory-bound regime). ----
    if data > 1:
        names = meshnet.layer_names(cfg2k)
        hw_sh = ConvSharding(batch_axes=(), h_axis="model", w_axis="data")
        auto, agree = _solver_agreement(plan_lib, machine, table, specs2k,
                                        mesh)
        workloads["mesh2k_proxy"] = _bench_workload(
            "mesh2k_proxy", cfg2k, 1, specs2k,
            (("hxw", _uniform_plan(plan_lib, hw_sh, names, specs2k, mesh,
                                   machine, table)),
             ("auto", auto)),
            mesh, args.reps, args.rounds, "hxw", "auto", agree)
        audit_targets["mesh2k_proxy"] = (auto, specs2k, cfg2k)

    # --- mesh16_proxy: the 16x16-mesh decompositions at bench scale.
    # Batch 1 rules out sample parallelism, so the solver composes: CF on
    # one axis with H on the other (one shard_map: halo + CF collective)
    # and H over the *product* of both axes where channels are thin.  The
    # auto plan must hold the ordering promise against uniform H x W. ----
    if data > 1:
        names = meshnet.layer_names(cfg16p)
        hw_sh = ConvSharding(batch_axes=(), h_axis="model", w_axis="data")
        auto, agree = _solver_agreement(plan_lib, machine, table, specs16p,
                                        mesh)
        n_cfsp = sum(isinstance(lp.sharding, CFSharding)
                     and lp.sharding.is_spatial
                     for lp in auto.layers.values())
        n_multi = sum(len(lp.sharding.h_axes) > 1
                      or len(lp.sharding.w_axes) > 1
                      for lp in auto.layers.values())
        print(f"# mesh16_proxy auto plan: {n_cfsp} CF x spatial layers, "
              f"{n_multi} product-axis spatial layers")
        wide16p = plan_lib.plan_line(machine, specs16p, mesh, table=table,
                                     search=args.search)
        workloads["mesh16_proxy"] = _bench_workload(
            "mesh16_proxy", cfg16p, 1, specs16p,
            (("uniform", _uniform_plan(plan_lib, hw_sh, names, specs16p,
                                       mesh, machine, table)),
             ("auto", auto),
             ("auto_wide", wide16p)),
            mesh, args.reps, args.rounds, "uniform", "auto", agree)
        workloads["mesh16_proxy"]["n_cf_spatial_layers"] = n_cfsp
        workloads["mesh16_proxy"]["n_product_axis_layers"] = n_multi
        workloads["mesh16_proxy"]["ratio_views"] = _ratio_views(
            plan_lib, auto, specs16p, mesh, machine, table,
            workloads["mesh16_proxy"]["entries"]["auto"]["measured_s"])
        attr_targets["mesh16_proxy"] = (cfg16p, 1, specs16p, auto)
        audit_targets["mesh16_proxy"] = (auto, specs16p, cfg16p)
        audit_targets["mesh16_proxy_wide"] = (wide16p, specs16p, cfg16p)

    # --- mesh2k_unreachable: the paper's Table-2 memory story as an
    # executable benchmark.  Batch 1: sample parallelism cannot reduce
    # per-device memory below one full sample, so the 'sample-parallel'
    # uniform plan is the replicated one.  A synthetic capacity limit is
    # set between the replicated peak and what the spatial decompositions
    # reach — the memory-aware solve (plan_line mem_limit=) must return a
    # plan that fits AND executes, while uniform sample-parallel is
    # infeasible under the limit.  Its measured XLA peak cross-checks the
    # §VI memory model on a real compiled step. -------------------------
    mem_failures = []
    if data > 1:
        namesu = meshnet.layer_names(cfg2ku)
        rep_plan = _uniform_plan(plan_lib, ConvSharding(), namesu, specs2ku,
                                 mesh, machine, table)
        rep_peak = rep_plan.predicted["memory"]["peak_bytes"]
        limit = 0.5 * rep_peak
        try:
            auto_u, agree = _solver_agreement(plan_lib, machine, table,
                                              specs2ku, mesh,
                                              mem_limit=limit)
        except Exception as e:
            auto_u = None
            mem_failures.append(
                f"mesh2k_unreachable: memory-aware solve failed under "
                f"limit {limit:.0f}B: {e}")
        if auto_u is not None:
            # plan_line already validated the fit (it raises into the
            # except-branch above when the solve stops fitting — THAT is
            # the "stops fitting" gate); the limit is derived from the
            # uniform peak, so uniform is infeasible by construction.
            # Peaks are recorded so the bench trajectory tracks them.
            auto_peak = auto_u.predicted["memory"]["peak_bytes"]
            workloads["mesh2k_unreachable"] = _bench_workload(
                "mesh2k_unreachable", cfg2ku, 1, specs2ku,
                (("uniform_sample", rep_plan), ("auto_memfit", auto_u)),
                mesh, args.reps, args.rounds, "uniform_sample",
                "auto_memfit", agree)
            workloads["mesh2k_unreachable"]["mem"] = {
                "limit_bytes": limit,
                "uniform_peak_bytes": rep_peak,
                "auto_peak_bytes": auto_peak,
            }
            print(f"# mesh2k_unreachable: limit {limit:.0f}B, uniform "
                  f"{rep_peak:.0f}B (DOES NOT FIT), "
                  f"auto {auto_peak:.0f}B (fits)")
            audit_targets["mesh2k_unreachable"] = (auto_u, specs2ku, cfg2ku)

    # --- ckpt_overhead: async save must stay off the critical path -------
    # (top-level report key, NOT a workload: the ordering gate below
    # iterates workloads and this lane has its own tolerance)
    ckpt_overhead = _bench_ckpt_overhead(cfg128, 2, specs128, uni128, mesh,
                                         args.reps, args.rounds,
                                         args.ckpt_tol)
    print(f"# ckpt_overhead: no_ckpt "
          f"{ckpt_overhead['no_ckpt_s']*1e6:.1f}us, async_ckpt "
          f"{ckpt_overhead['async_ckpt_s']*1e6:.1f}us, ratio "
          f"{ckpt_overhead['overhead_ratio']:.3f} "
          f"(tol {1 + args.ckpt_tol:.2f}x)")

    # --- --audit: static collective audit of the measured plans ----------
    # (recorded per workload, NOT gated here — the CI static lane gates;
    # this rides along so BENCH_strategy.json carries the findings next to
    # the timings they explain)
    if args.audit:
        from repro import analysis
        for name, (plan, specs, cfg) in audit_targets.items():
            findings = plan.audit(specs, mesh, cfg=cfg, overlap=True,
                                  hlo=False)
            errs = analysis.error_count(findings)
            print(f"# audit/{name}: {len(findings)} finding(s), "
                  f"{errs} error(s)")
            rec = {"n_findings": len(findings), "n_errors": errs,
                   "findings": [f.to_json() for f in findings]}
            if name in workloads:
                workloads[name]["audit"] = rec
            else:
                # widened-search plans audit under their parent workload
                # ("mesh16cf_wide" -> mesh16cf["audit_wide"]) — the
                # widened solver must stay as auditable as the greedy one
                workloads[name.rsplit("_wide", 1)[0]]["audit_wide"] = rec

    # --- the gate: the optimizer's ordering promise ----------------------
    tol = args.gate_tol
    # the ordering promise applies where the baseline was a *feasible*
    # alternative; the capacity workload's baseline is infeasible under
    # its limit by construction, so only its fit ("mem" key) gates
    failures = [
        f"{name}: {wl['auto']} "
        f"{wl['entries'][wl['auto']]['measured_s']*1e6:.1f}us"
        f" > {1 + tol:.2f}x {wl['baseline']} "
        f"{wl['entries'][wl['baseline']]['measured_s']*1e6:.1f}us"
        for name, wl in workloads.items()
        if "mem" not in wl and wl["auto_vs_uniform_measured"] > 1 + tol]
    failures += mem_failures          # capacity promises gate too
    if not ckpt_overhead["ok"]:
        failures.append(
            f"ckpt_overhead: async save slows the step "
            f"{ckpt_overhead['overhead_ratio']:.2f}x "
            f"(> {1 + args.ckpt_tol:.2f}x) — checkpoint stall on the "
            f"critical path")

    # --- the widened-search promise: the wider candidate space + global
    # search must MEASURE no slower than greedy somewhere (the wide set is
    # a superset of the narrow one, so the predicted cost can only drop;
    # this gate checks the measurement backs the prediction on at least
    # one workload — gated like the ordering promise, same tolerance) ----
    search_cmp = {}
    for name, wl in workloads.items():
        e = wl["entries"]
        if "auto_wide" not in e:
            continue
        greedy_tag = wl["auto"]
        r = e["auto_wide"]["measured_s"] / e[greedy_tag]["measured_s"]
        search_cmp[name] = {
            "mode": args.search,
            "greedy_measured_s": e[greedy_tag]["measured_s"],
            "wide_measured_s": e["auto_wide"]["measured_s"],
            "greedy_predicted_s": e[greedy_tag]["predicted_s"],
            "wide_predicted_s": e["auto_wide"]["predicted_s"],
            "wide_vs_greedy_measured": r,
        }
        wl["search"] = search_cmp[name]
        print(f"# search/{name}: wide({args.search})/greedy measured "
              f"{r:.3f}, predicted "
              f"{e['auto_wide']['predicted_s']*1e6:.1f}us vs "
              f"{e[greedy_tag]['predicted_s']*1e6:.1f}us")
    if search_cmp:
        best = min(s["wide_vs_greedy_measured"] for s in search_cmp.values())
        if best > 1 + tol:
            failures.append(
                f"search: widened search ({args.search}) measured slower "
                f"than greedy on every workload (best wide/greedy "
                f"{best:.3f} > {1 + tol:.2f}) — the wider strategy space "
                f"must pay somewhere")

    # --- the model-fidelity gate: the composed calibration's headline ----
    # (ISSUE win condition: the calibrated model/measured ratio on the
    # composition-heavy workloads must sit within --ratio-tol of 1.0,
    # either side; --ratio-warn-only downgrades a miss to a warning so
    # the first CI run records the baseline before the gate flips on)
    ratio_gate = {"tolerance": args.ratio_tol,
                  "warn_only": bool(args.ratio_warn_only), "checks": {}}
    for name in ("mesh16cf", "mesh16_proxy"):
        rv = workloads.get(name, {}).get("ratio_views")
        if not rv:
            continue
        r = rv["ratio_calibrated"]
        off = float(max(r, 1 / r)) if r > 0 else float("inf")
        ok = bool(off <= args.ratio_tol)
        ratio_gate["checks"][name] = dict(rv, off_by=off, ok=ok)
        print(f"# ratio/{name}: calibrated {r:.3f} "
              f"(off {off:.2f}x, tol {args.ratio_tol:.1f}x), analytic "
              f"{rv['ratio_analytic']:.3f}, "
              f"calibration_improves={rv['calibration_improves']}")
        if not ok:
            msg = (f"ratio: {name} calibrated model/measured {r:.3f} is "
                   f"off by {off:.2f}x > --ratio-tol "
                   f"{args.ratio_tol:.1f}x")
            if args.ratio_warn_only:
                print(f"# RATIO WARNING (warn-only): {msg}")
            else:
                failures.append(msg)

    # --- --attribute + refit: measured drift drives recalibration --------
    # (before the report write so the refit outcome rides along in it)
    attribution_refit = {}
    if args.attribute:
        _, attr_reps = _attribute(attr_targets, mesh, args.attribution_out,
                                  args.reps, args.rounds)
        for name, rep in attr_reps.items():
            changed = calib.refit_from_attribution(
                cal, rep, path=args.calibration, damp=0.5)
            if changed:
                attribution_refit[name] = changed
                print(f"# refit/{name}: " + ", ".join(
                    f"{k}={v:.3f}" for k, v in sorted(changed.items())))

    report = {
        "schema": SCHEMA,
        "backend": jax.default_backend(),
        "ndevices": ndev,
        "mesh": dict(mesh.shape),
        "reps": args.reps,
        "rounds": args.rounds,
        "calibration": {"path": args.calibration,
                        "machine": dataclasses.asdict(machine),
                        "table_entries": len(table)},
        "workloads": workloads,
        "ckpt_overhead": ckpt_overhead,
        "search": search_cmp,
        "ratio_gate": ratio_gate,
        "attribution_refit": attribution_refit,
        "gate": {"enabled": bool(args.gate), "tolerance": tol,
                 "ok": not failures, "failures": failures},
    }
    with open(args.out, "w") as f:
        json.dump(report, f, indent=1, sort_keys=True)
    print(f"# wrote {args.out}")
    for name, wl in workloads.items():
        print(f"# {name}: auto/uniform measured "
              f"{wl['auto_vs_uniform_measured']:.3f}, solver agreement "
              f"{'same plan' if wl['solver_agreement']['same_plan'] else str(wl['solver_agreement']['n_layers_differ']) + ' layers differ'}")
    if failures:
        print("# GATE FAILURES (solved plan measured slower than its "
              "baseline):")
        for x in failures:
            print(f"#   {x}")
        return 1 if args.gate else 0
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("ndevices", nargs="?", type=int, default=None,
                    help="host CPU device count (must be first arg; read "
                         "before jax import to set XLA_FLAGS; default 4)")
    ap.add_argument("--out", default="BENCH_strategy.json")
    ap.add_argument("--calibration", default="BENCH_calibration.json",
                    help="calibration JSON: loaded when present, else "
                         "measured over the bench workloads and written")
    ap.add_argument("--reps", type=int, default=5,
                    help="timed calls per round")
    ap.add_argument("--rounds", type=int, default=4,
                    help="interleaved measurement rounds per workload (the "
                         "per-plan time is the min over per-round means)")
    ap.add_argument("--gate", action="store_true",
                    help="exit non-zero when a solved auto plan measures "
                         "slower than the uniform baseline (the CI lane's "
                         "perf-trajectory gate)")
    ap.add_argument("--gate-tol", type=float, default=0.10,
                    help="noise tolerance for the gate: fail only when "
                         "auto > (1+tol) * uniform measured")
    ap.add_argument("--ckpt-tol", type=float, default=0.5,
                    help="tolerance for the checkpoint-overhead lane: fail "
                         "when the async-save arm is slower than the bare "
                         "step beyond (1+tol)x — the save must overlap, "
                         "not stall")
    ap.add_argument("--attribute", action="store_true",
                    help="segmented per-layer profiling of the mesh16cf/"
                         "mesh16_proxy auto plans (core.trace.trace_plan): "
                         "decompose the model-vs-measured gap into named "
                         "per-term drift and write --attribution-out; "
                         "drift beyond 5x warns without failing")
    ap.add_argument("--attribution-out", default="BENCH_attribution.json")
    ap.add_argument("--search", default="beam:4",
                    metavar="beam[:N]|hillclimb|greedy",
                    help="search mode for the widened-search arm "
                         "(auto_wide) on mesh16cf/mesh16_proxy: wide "
                         "candidate set + this solver, A/B'd against the "
                         "greedy longest-path-first solve and gated like "
                         "the ordering promise")
    ap.add_argument("--ratio-tol", type=float, default=10.0,
                    help="model-fidelity gate: fail when the calibrated "
                         "model/measured ratio on mesh16cf/mesh16_proxy "
                         "is off from 1.0 by more than this factor "
                         "(either side)")
    ap.add_argument("--ratio-warn-only", action="store_true",
                    help="downgrade --ratio-tol misses to warnings (for "
                         "the first CI run that records the baseline "
                         "before the gate flips on)")
    ap.add_argument("--audit", action="store_true",
                    help="run the static collective auditor "
                         "(repro.analysis) on every measured auto plan "
                         "and record the findings per workload in the "
                         "report JSON — lowering-only, never gates here "
                         "(the CI static lane gates)")
    args = ap.parse_args(argv)
    from repro.core.strategy import parse_search
    try:
        parse_search(args.search)
    except ValueError as e:
        ap.error(str(e))
    return run(args)


if __name__ == "__main__":
    sys.exit(main())
