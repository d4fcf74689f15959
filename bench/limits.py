"""Readings that a cell's correctness limits are set from, on the chip.

  python bench/limits.py --workload <cell> --seeds 12 --first-seed <n> \
      [--control-seeds 3] [--faults half_batch,no_halo]

In one process, at the cell's own size and through the same set-up and
step as bench/run.py (no measured window):
- the program, as the configuration states it, on `--seeds` seeds: the
  lower reading of each compared number is the largest of these;
- the control, the program with its step traced at the next precision
  below the configuration's ('high', three bf16 passes, for 'highest'),
  on `--control-seeds` seeds: its smallest reading is the upper one;
- each fault named in `--faults`, planted in the reference put in the
  program's place (bench/reference.py), on `--control-seeds` seeds.
Each is compared with the reference on the same seed and batches, as a run
compares, and judged at the cell's limits by the run's own verdict.  Prints
one line per reading and a JSON summary last.
"""
import argparse
import gc
import json
import math
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import run  # noqa: E402

# the next precision below the one the configuration states
LOWER = {"highest": "high"}


class Reference:
    """The reference's readings per seed, each computed once."""

    def __init__(self, cell, devices):
        import reference
        self.runner = reference.Runner(cell.config,
                                       cell.traffic["schedule_steps"],
                                       devices,
                                       cell.config["matmul_precision"])
        self.seen = {}

    def run(self, seed, batches):
        if seed not in self.seen:
            self.seen[seed] = self.runner.run(seed, batches)
        return self.seen[seed]


def readings(cell, devices, ref, seeds, precision=None, fault=None,
             log=print):
    """{seed: compared numbers} of the program (or the reference with
    `fault` planted) against the reference, one seed after another."""
    import check
    import harness
    import reference
    import traffic as traffic_lib

    tr = cell.traffic
    if fault:
        subject = reference.Runner(cell.config, tr["schedule_steps"],
                                   devices, cell.config["matmul_precision"],
                                   fault=fault, halo_parts=tr["model"])
    out = {}
    prog = None
    for seed in seeds:
        pool = traffic_lib.batch_pool(cell.config, tr, seed)
        batches = pool[:harness.CHECKED_STEPS]
        if fault:
            got = subject.run(seed, batches)
        else:
            if prog is None:
                prog = harness.Program(cell, seed, devices,
                                       precision=precision)
            else:
                prog.reseed(seed)
            got = prog.first_steps(pool)
            prog.state = None
            gc.collect()
        numbers = check.gaps(got, ref.run(seed, batches))
        label = fault or ("control" if precision else "program")
        ok = check.verdict(numbers, tr["limits"])
        log(f"reading {label} seed {seed} correct {ok} "
            f"{json.dumps(numbers)}", flush=True)
        out[seed] = numbers
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--first-seed", type=int, required=True)
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--faults", default="")
    args = ap.parse_args(argv)
    cell, devices, _ = run.start(args.workload)

    from repro.utils import Precision
    import check
    import jax.numpy as jnp

    seeds = [args.first_seed + i for i in range(args.seeds)]
    ctl_seeds = seeds[:args.control_seeds]
    limits = cell.traffic["limits"]
    summary, correct = {}, {}
    ref = Reference(cell, devices)
    prog = readings(cell, devices, ref, seeds)
    summary["program_max"] = {k: max(r[k] for r in prog.values())
                              for k in check.NUMBERS}
    correct["program"] = [check.verdict(r, limits) for r in prog.values()]
    lower = LOWER[cell.config["matmul_precision"]]
    ctl = readings(cell, devices, ref, ctl_seeds,
                   precision=Precision(jnp.float32, jnp.float32, jnp.float32,
                                       matmul=lower))
    summary["control_" + lower + "_min"] = {
        k: min(r[k] for r in ctl.values()) for k in check.NUMBERS}
    correct["control_" + lower] = [check.verdict(r, limits)
                                   for r in ctl.values()]
    for fault in filter(None, args.faults.split(",")):
        got = readings(cell, devices, ref, ctl_seeds, fault=fault)
        summary[fault + "_min"] = {k: min(r[k] for r in got.values())
                                   for k in check.NUMBERS}
        correct[fault] = [check.verdict(r, limits) for r in got.values()]
    out = {k: {n: (v if math.isfinite(v) else None) for n, v in d.items()}
           for k, d in summary.items()}
    # `correct` at the cell's limits, per seed: true for every program
    # seed, false for every control and fault seed, or the limits are wrong
    out["correct"] = correct
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
