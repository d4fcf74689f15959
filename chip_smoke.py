"""Smoke run of the main training path on TPU v5e chips.

  python chip_smoke.py             # one chip: mesh1k at full width, 3 steps
  python chip_smoke.py --chips 4   # four chips: mesh1k split across a 2x2
                                   # host, each run compared with a 1x1 mesh

Every phase calls the trainer's own entry point, `repro.launch.train.main`,
in this process: one process holds the chips and no child is started after
JAX is up.  The model is mesh1k at full width (1024^2 x 18 input, VGG widths
64-512, 3 convs per block, ~22M parameters, random weights from --seed 0)
at global batch 4.  A phase fails on a non-finite loss or gradient norm and
on any step fault the trainer's resilient loop caught, even one a retry got
past.

Lines before the last are smoke output (device, seconds, peak memory,
losses), not benchmark metrics.  The last line is one JSON object:
{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}.
Without a TPU, or without the repo's sources beside it, the script exits
non-zero and prints no such line.

Four-chip comparison: both sides must compute the same function, to a
tolerance tight enough that a wrong halo row cannot hide in it.
- BN scope: mesh1k's own is 'local' (paper §III-B), under which a shard
  normalizes with its own statistics and a 4-chip run computes a
  different function from the 1x1 mesh (0.94% first-step loss on the
  CPU-rehearsal smoke config).  The phase trains with `--bn-scope global`.
- Precision: the trainer's FP32 policy (repro.utils.FP32) traces the
  step at `highest` matmul precision, so an fp32 conv is fp32 on the MXU.
  LOSS_RTOL and GNORM_RTOL come from readings on a TPU v5e host: at that
  precision the 4-chip runs agreed with the 1x1 run to 1.9e-7 and 2.9e-7
  (loss) and 2.2e-5 (grad norm); at JAX's default precision, where an
  fp32 conv runs one bf16 MXU pass, they differed by 1.16e-4 and 4.35e-4
  (loss) and 8.7e-4 (grad norm).  Each limit sits well above the first
  readings and below the second, so a step that fell back to bf16
  passes, like a wrong halo row, fails the phase.
- Memory: `peak_bytes_in_use` is a process high-water mark, so after the
  first phase it no longer says what a phase put where.  Each phase also
  reads `bytes_in_use` per chip before it starts and while its trained
  state is held, and every chip must hold more in the second reading.
"""
from __future__ import annotations

import argparse
import gc
import json
import math
import os
import shutil
import statistics
import sys
import tempfile

REPO = os.path.dirname(os.path.abspath(__file__))
SRC = os.path.join(REPO, "src")

FULL = ["--arch", "mesh1k", "--batch", "4", "--seed", "0"]
LOSS_RTOL = 1e-5
GNORM_RTOL = 2e-4


class SmokeError(RuntimeError):
    pass


def check(cond, msg: str) -> None:
    if not cond:
        raise SmokeError(msg)


def train_run(train, workdir: str, name: str, argv: list[str], steps: int):
    """One in-process trainer run with a fresh checkpoint dir (only the
    final save runs); returns (main's summary, its JSONL step records)."""
    d = os.path.join(workdir, name)
    os.makedirs(d)
    metrics = os.path.join(d, "metrics.jsonl")
    argv = argv + ["--steps", str(steps), "--ckpt-every", str(steps + 1),
                   "--ckpt-dir", os.path.join(d, "ckpt"),
                   "--metrics", metrics, "--log-every", "1"]
    print(f"smoke {name}: train {' '.join(argv)}", flush=True)
    out = train.main(argv)
    with open(metrics) as f:
        recs = [json.loads(line) for line in f]
    faults = [r for r in recs if r["kind"] == "fault"]
    check(not faults, f"{name}: the trainer caught step faults: {faults}")
    recs = [r for r in recs if r["kind"] == "step"]
    check(len(recs) == steps, f"{name}: {len(recs)} step records, "
                              f"expected {steps}")
    for r in recs:
        check(math.isfinite(r["loss"]) and math.isfinite(r["grad_norm"]),
              f"{name}: non-finite step record {r}")
    walls = [r["wall_s"] for r in recs]
    print(f"smoke {name}: losses {[r['loss'] for r in recs]} "
          f"grad_norms {[r['grad_norm'] for r in recs]}")
    line = f"smoke {name}: step wall seconds {walls}"
    if len(walls) > 1:
        compile_s = walls[0] - statistics.median(walls[1:])
        line += (f"; compile seconds ~ {compile_s} (first step minus the "
                 f"median of the later ones)")
    print(line, flush=True)
    return out, recs


def mem(devices, key: str) -> list[int]:
    return [d.memory_stats()[key] for d in devices]


def one_chip(train, devices, workdir: str, base: list[str]) -> None:
    """mesh1k, 3 steps, uniform plan on a 1x1 mesh."""
    train_run(train, workdir, "1chip", base + [
        "--strategy", "uniform", "--data", "1", "--model", "1"], 3)
    print(f"smoke 1chip: peak_bytes_in_use "
          f"{mem(devices[:1], 'peak_bytes_in_use')}", flush=True)


def four_chips(train, devices, workdir: str, base: list[str]) -> None:
    """mesh1k across 4 chips — a pure H split (halo ppermutes), then the
    solved per-layer plan on 2x2 — each against a 1x1-mesh run of the same
    global batch and seed in this process, all under global BN (see the
    module docstring)."""
    import jax
    base = base + ["--bn-scope", "global"]
    firsts = {}
    for name, extra in (
            ("4chip_h_split", ["--data", "1", "--model", "4",
                               "--strategy", "uniform"]),
            ("4chip_auto", ["--data", "2", "--model", "2",
                            "--strategy", "auto"])):
        gc.collect()
        before = mem(devices[:4], "bytes_in_use")
        out, recs = train_run(train, workdir, name, base + extra, 2)
        check(out["mesh"].devices.size == 4, f"{name}: mesh {out['mesh']}")
        for leaf in jax.tree.leaves(out["state"][0]):
            check(len(leaf.sharding.device_set) == 4,
                  f"{name}: a parameter lives on "
                  f"{len(leaf.sharding.device_set)} device(s)")
        check(out["batch_shardings"], f"{name}: no batch was placed")
        for key, sh in out["batch_shardings"].items():
            check(len(sh.device_set) == 4,
                  f"{name}: batch {key!r} on {len(sh.device_set)} device(s)")
        held = mem(devices[:4], "bytes_in_use")
        pk = mem(devices[:4], "peak_bytes_in_use")
        print(f"smoke {name}: bytes_in_use per chip before {before}, with "
              f"the trained state held {held}; peak_bytes_in_use so far "
              f"{pk}", flush=True)
        check(all(p > 0 for p in pk), f"{name}: idle chip(s): {pk}")
        check(all(h > b for h, b in zip(held, before)),
              f"{name}: a chip holds none of the state: {before} -> {held}")
        firsts[name] = recs[0]
        del out
    _, ref = train_run(train, workdir, "1x1_reference", base + [
        "--strategy", "uniform", "--data", "1", "--model", "1"], 1)
    for name, r in firsts.items():
        for key, rtol in (("loss", LOSS_RTOL), ("grad_norm", GNORM_RTOL)):
            rel = abs(r[key] - ref[0][key]) / abs(ref[0][key])
            print(f"smoke {name}: first-step {key} {r[key]} vs 1x1 "
                  f"{ref[0][key]}: rel diff {rel} (tol {rtol})")
            check(rel <= rtol, f"{name}: first-step {key} off by {rel}")


def cache_entries(path: str) -> int:
    return len(os.listdir(path)) if os.path.isdir(path) else 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1 (default): the one-chip phase; 4: the "
                         "four-chip phase and its 1x1 comparison only")
    args = ap.parse_args(argv)
    if not os.path.isdir(os.path.join(SRC, "repro")):
        print(f"chip_smoke: FAIL: no repro sources under {SRC}",
              file=sys.stderr)
        return 1
    sys.path.insert(0, SRC)
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < args.chips:
        print(f"chip_smoke: FAIL: needs {args.chips} TPU chip(s), JAX "
              f"found {len(devices)} {devices[0].platform} device(s)",
              file=sys.stderr)
        return 1
    from repro.launch import compile_cache, train
    cache = compile_cache.enable()
    n_cached = cache_entries(cache)
    print(f"smoke: device {devices[0].device_kind} x{len(devices)}; "
          f"compile cache {cache} ({n_cached} files)", flush=True)
    workdir = tempfile.mkdtemp(prefix="chip_smoke_")
    try:
        phase = one_chip if args.chips == 1 else four_chips
        phase(train, devices, workdir, FULL)
    except SmokeError as e:
        print(f"chip_smoke: FAIL: {e}", file=sys.stderr)
        return 1
    finally:
        shutil.rmtree(workdir, ignore_errors=True)
    print(f"smoke: compile cache files {n_cached} before, "
          f"{cache_entries(cache)} after", flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
