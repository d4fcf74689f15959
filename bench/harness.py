"""One run of one cell: set-up, the measured window, and the check.

Set-up builds one object, `Program`: the trainer's own construction
(`launch.train.build` -> `train_state` -> `train_step`, as
tests/rehearse_v5e.py builds it) with the cell's flags, its step compiled
ahead of time for the cell's batch.  `Program.step` is the window's call:
place the batch with the trainer's `put`, run the compiled step, read
`loss` and `grad_norm` back to the host, as the trainer's own loop does.
The first steps of set-up go through that same call on batches that all
differ, and their readings are what the reference is held to; then the
same object runs the window.  The reference runs once the window has
closed, memory has been read and the program's state is freed.

With `trace` the window runs under JAX's profiler, and the cell's
per-layer metrics are read from the trace by bench/metrics/<name>.py.
"""
from __future__ import annotations

import dataclasses
import gc
import glob
import math
import os
import shutil
import time
from collections import defaultdict

import numpy as np

import cells
import check
import devtrace
import flops
import reference
import traffic as traffic_lib

OUT_DIR = os.path.join(cells.BENCH_DIR, "out")
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CHECKED_STEPS = 3
HEAD_STEPS = 3          # steps of the trace kept in bench/out/<cell>/


class BenchError(RuntimeError):
    """The cell cannot be run as its files describe it."""


def program_sizes(cfg, keys) -> dict:
    """The model module's PROGRAM_KEYS `keys` of a program config, as the
    config file writes them."""
    return {k: list(v) if isinstance(v, tuple) else v
            for k, v in ((k, getattr(cfg, k)) for k in keys)}


def train_argv(cell: cells.Cell, seed: int) -> list[str]:
    """The trainer's flags for the cell.  The program has two sizes of
    each arch, the published one and a reduced one (`--smoke`); the
    config file's sizes pick the one they state."""
    from repro.configs import registry
    cfg, tr = cell.config, cell.traffic
    argv = ["--arch", cfg["arch"], "--batch", str(tr["batch"]),
            "--data", str(tr["data"]), "--model", str(tr["model"]),
            "--strategy", tr["strategy"], "--bn-scope", tr["bn_scope"],
            "--seed", str(seed), "--steps", str(tr["schedule_steps"]),
            "--lr", repr(cfg["optimizer"]["lr"])]
    keys = cells.model_of(cfg).PROGRAM_KEYS
    reduced = program_sizes(registry.get(cfg["arch"], smoke=True), keys)
    if all(reduced[k] == cfg[k] for k in keys):
        argv.append("--smoke")
    return argv


def check_cell(cell: cells.Cell, devices) -> None:
    tr = cell.traffic
    if tr["data"] * tr["model"] != cell.chips:
        raise BenchError(f"{cell.name}: mesh {tr['data']}x{tr['model']} "
                         f"is not the cell's {cell.chips} chip(s)")
    if len(devices) != cell.chips:
        raise BenchError(f"{cell.name}: given {len(devices)} devices, the "
                         f"cell runs on {cell.chips}")
    if tr["bn_scope"] != "global" and cell.chips > 1:
        raise BenchError(f"{cell.name}: BN scope {tr['bn_scope']!r} on "
                         f"{cell.chips} chips normalizes each shard alone; "
                         f"the reference normalizes the global batch")
    missing = set(check.NUMBERS) - set(tr["limits"])
    if missing:
        raise BenchError(f"{cell.name}: no limit for {sorted(missing)}")
    if tr["pool"] < CHECKED_STEPS or tr["warmup_steps"] < CHECKED_STEPS:
        raise BenchError(f"{cell.name}: pool and warm-up need at least "
                         f"{CHECKED_STEPS} steps, the ones the check reads")


def check_program_config(cfg, cell: cells.Cell) -> None:
    keys = cells.model_of(cell.config).PROGRAM_KEYS
    for k, have in program_sizes(cfg, keys).items():
        if have != cell.config[k]:
            raise BenchError(f"{cell.name}: the program's {cfg.name} has "
                             f"{k}={have!r}, the config file "
                             f"{cell.config[k]!r}")
    if cfg.bn_scope != cell.traffic["bn_scope"]:
        raise BenchError(f"{cell.name}: the program runs BN scope "
                         f"{cfg.bn_scope!r}, the traffic file states "
                         f"{cell.traffic['bn_scope']!r}")


def leaf_norms(tree) -> dict:
    return {k: float(np.linalg.norm(v))
            for k, v in reference.leaves_host(tree).items()}


class Program:
    """The trainer's compiled step and its state, for one cell on
    `devices`.  `precision` replaces the trainer's precision policy (the
    lower-precision control); `plant(opt, loss) -> (opt, loss)` lets a
    test break the step underneath."""

    def __init__(self, cell: cells.Cell, seed: int, devices,
                 precision=None, plant=None):
        import jax
        import jax.numpy as jnp
        from jax.sharding import NamedSharding
        from repro.launch import train
        from repro.launch.mesh import make_mesh

        tr = cell.traffic
        self.cell, self._train = cell, train
        self.mesh = make_mesh(data=tr["data"], model=tr["model"],
                              devices=list(devices))
        args = train.parser().parse_args(train_argv(cell, seed))
        cfg, params, opt, loss, _, put, prec, extras = train.build(
            args, self.mesh)
        check_program_config(cfg, cell)
        if plant is not None:
            opt, loss = plant(opt, loss)
        self.opt, self.put = opt, put
        self.state = train.train_state(params, opt, self.mesh)
        step = train.train_step(args, opt, loss, precision or prec,
                                self.mesh, self.state)
        spec = cells.model_of(cell.config).batch_spec(cell.config, tr)
        batch = {k: jax.ShapeDtypeStruct(
            shape, jnp.dtype(dtype),
            sharding=NamedSharding(self.mesh, extras["batch_spec"](k)))
            for k, (shape, dtype) in spec.items()}
        self.compiled = step.lower(*self.state, batch).compile()

    def reseed(self, seed: int) -> None:
        """Fresh weights from `seed` for the same compiled step."""
        args = self._train.parser().parse_args(train_argv(self.cell, seed))
        params = self._train.build(args, self.mesh)[1]
        self.state = None
        gc.collect()
        self.state = self._train.train_state(params, self.opt, self.mesh)

    def step(self, batch: dict) -> dict:
        """One step as the window runs it; returns the host's metrics."""
        import jax
        with jax.profiler.TraceAnnotation("bench.step"):
            with jax.profiler.TraceAnnotation("bench.put"):
                b = self.put(batch)
            with jax.profiler.TraceAnnotation("bench.dispatch"):
                *self.state, m = self.compiled(*self.state, b)
            with jax.profiler.TraceAnnotation("bench.readback"):
                return {k: float(v) for k, v in m.items()}

    def first_steps(self, pool: list) -> dict:
        """The checked steps: each step's loss, each leaf's first gradient
        (the momentum after one step from zero) and each leaf's change
        over the steps, read from the state between steps."""
        p0 = reference.leaves_host(self.state[0])
        losses, grad = [], None
        for i in range(CHECKED_STEPS):
            losses.append(self.step(pool[i])["loss"])
            if grad is None:
                grad = leaf_norms(self.state[1].mu)
        p3 = reference.leaves_host(self.state[0])
        return {"losses": losses, "grad": grad,
                "change": {k: float(np.linalg.norm(p3[k] - p0[k]))
                           for k in p0}}

    def memory_peak_bytes(self) -> int:
        """The fullest device's peak: the larger of the compiled step's
        footprint and the runtime's high-water mark."""
        ma = self.compiled.memory_analysis()
        compiled = (ma.argument_size_in_bytes + ma.output_size_in_bytes +
                    ma.temp_size_in_bytes - ma.alias_size_in_bytes)
        seen = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                for d in self.mesh.devices.flat]
        return int(max([compiled] + seen))

    def free(self) -> None:
        self.state = self.compiled = None
        gc.collect()


@dataclasses.dataclass
class ReaderContext:
    """What a per-layer metric reader gets."""
    trace: devtrace.Trace
    config: dict
    traffic: dict
    chips: int
    peaks: dict
    word: int
    counters: dict


class CompileCounter:
    """Counts JAX's backend compilations while `armed`."""

    def __init__(self):
        import jax
        self.armed, self.count = False, 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **kw):
        if event == COMPILE_EVENT and self.armed:
            self.count += 1


def measure(prog: Program, pool: list, seconds: float, start: int) -> dict:
    """Run steps until `seconds` have passed; every step's wall time."""
    times, failed, i = [], 0, start
    t_start = time.perf_counter()
    end = t_start + seconds
    while True:
        t0 = time.perf_counter()
        m = prog.step(pool[i % len(pool)])
        t1 = time.perf_counter()
        times.append(t1 - t0)
        failed += not all(math.isfinite(v) for v in m.values())
        i += 1
        if t1 >= end:
            break
    return {"times": times, "wall": t1 - t_start, "failed": failed}


def trace_window(prog, pool, seconds, start, trace_dir) -> tuple:
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    shutil.rmtree(trace_dir, ignore_errors=True)
    jax.profiler.start_trace(trace_dir, profiler_options=opts)
    try:
        run = measure(prog, pool, seconds, start)
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                        recursive=True)
    return run, devtrace.parse(path)


def breakdown(tr: devtrace.Trace) -> dict:
    """The device ops that took most time (layer/category, seconds per
    device) and the longest idle gaps of the first device, each named by
    what the harness was doing on the host meanwhile."""
    total = defaultdict(int)
    for ops in tr.devices.values():
        for o in ops:
            s, e = max(o.start, tr.window[0]), min(o.end, tr.window[1])
            total[f"{o.layer or '-'}/{o.category or o.name}"] += e - s
    n = max(len(tr.devices), 1)
    top = sorted(total.items(), key=lambda kv: -kv[1])[:10]
    gaps = []
    if tr.devices:
        busy = tr.busy(next(iter(tr.devices)))
        gaps = devtrace.minus([tr.window], busy)
    longest = sorted(gaps, key=lambda g: g[0] - g[1])[:10]
    return {"device_ops": [[k, v / n / 1e9] for k, v in top],
            "idle_gaps": [[tr.host_activity((s + e) // 2), (e - s) / 1e9]
                          for s, e in longest]}


def end_to_end(cell, run, setup_s, peaks, chips, mem_peak) -> dict:
    n = len(run["times"])
    samples = n * cell.traffic["batch"]
    work = flops.step_flops(cell.config, cell.traffic["batch"]) * n
    return {
        "setup_s": setup_s,
        "samples_per_s": samples / run["wall"],
        "mfu": 100.0 * work / (run["wall"] * chips * peaks["bf16_flops"]),
        "step_ms_p90": float(np.percentile(run["times"], 90)) * 1e3,
        "peak_hbm_gib": mem_peak / 2 ** 30,
    }


def run_cell(cell: cells.Cell, seed: int, seconds: float, trace: bool,
             t_process: float, devices, peaks: dict, plant=None) -> dict:
    """One run of `cell`; returns the result line as a dict (its `check`
    entry last).  `t_process` is when the process started (set-up counts
    from there); `peaks` is the device kind's row of bench/peaks.json."""
    from repro.launch import compile_cache

    check_cell(cell, devices)
    compile_cache.enable()
    counter = CompileCounter()
    tr = cell.traffic
    pool = traffic_lib.batch_pool(cell.config, tr, seed)
    prog = Program(cell, seed, devices, plant=plant)
    readings = prog.first_steps(pool)
    for i in range(CHECKED_STEPS, tr["warmup_steps"]):
        prog.step(pool[i % len(pool)])

    counter.armed = True
    setup_s = time.time() - t_process
    trace_dir = os.path.join(OUT_DIR, cell.name, "trace")
    if trace:
        run, raw = trace_window(prog, pool, seconds, tr["warmup_steps"],
                                trace_dir)
    else:
        run = measure(prog, pool, seconds, tr["warmup_steps"])
    counter.armed = False

    mem_peak = prog.memory_peak_bytes()
    if trace:
        raw["hlo"] = devtrace.hlo_map(prog.compiled.as_text())
    prog.free()
    del prog
    ref = reference.Runner(cell.config, tr["schedule_steps"], devices,
                           cell.config["matmul_precision"]).run(
        seed, pool[:CHECKED_STEPS])
    numbers = check.gaps(readings, ref)
    limits = tr["limits"]

    dev = devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices), "memory_peak_bytes": mem_peak}
    result = {"correct": check.verdict(numbers, limits) and not run["failed"],
              "attempted": len(run["times"]), "failed": run["failed"]}
    if trace:
        t = devtrace.Trace(raw)
        os.makedirs(os.path.join(OUT_DIR, cell.name), exist_ok=True)
        devtrace.save(devtrace.trim(raw, HEAD_STEPS),
                      os.path.join(OUT_DIR, cell.name, "trace_head.json.gz"))
        shutil.rmtree(trace_dir, ignore_errors=True)
        ctx = ReaderContext(t, cell.config, tr, cell.chips, peaks,
                            word=np.dtype(cell.config["dtype"]).itemsize,
                            counters={"window_compiles": counter.count})
        metrics = {}
        for m in cell.per_layer:
            v = cells.load_reducer(cell, m["name"])(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
        busy = [devtrace.length(t.busy(d)) for d in t.devices]
        device["busy_s"] = sum(busy) / max(len(busy), 1) / 1e9
        device["window_s"] = t.window_ns / 1e9
        result.update(metrics=metrics, device=device, breakdown=breakdown(t))
    else:
        values = end_to_end(cell, run, setup_s, peaks, cell.chips, mem_peak)
        result.update(metrics={m["name"]: {"value": values[m["name"]],
                                           "unit": m["unit"]}
                               for m in cell.end_to_end},
                      device=device)
    result["check"] = check.report(numbers, limits)
    return result
