"""Run one benchmark cell once and print its result line.

  python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace 0|1

From the root of a checkout.  The cell is an entry of `BENCHMARK.json`
(bench/cells.py finds its files).  The last line of standard output is one
JSON object: `correct`, `attempted`, `failed`, `metrics` (the cell's
end-to-end metrics with --trace 0, its per-layer metrics with --trace 1),
`device`, with --trace 1 `breakdown`, and last `check`, each compared
number beside its limit; the same numbers end standard error.

It runs only on TPU chips: without them, or without the program's sources
beside it, it exits non-zero and prints no result.  JAX's persistent
compilation cache is kept in bench/.jax_cache inside the checkout.
"""
import time

T_PROCESS = time.time()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
CACHE_DIR = os.path.join(BENCH, ".jax_cache")


def fail(msg: str) -> int:
    print(f"bench/run.py: {msg}", file=sys.stderr)
    return 2


def start(workload: str):
    """Resolve the cell, point JAX's compile cache into the checkout, and
    find the chips: (cell, devices, peaks).  Raises SystemExit with a
    message when any of that fails."""
    sys.path.insert(0, BENCH)
    import cells
    try:
        cell = cells.resolve(workload, ROOT)
    except cells.CellError as e:
        raise SystemExit(fail(str(e)))
    src = os.path.join(ROOT, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        raise SystemExit(fail(f"no program sources under {src}"))
    sys.path.insert(1, src)

    # before JAX is imported: it reads the variable once
    os.environ["JAX_COMPILATION_CACHE_DIR"] = CACHE_DIR
    import jax
    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell.chips:
        raise SystemExit(fail(
            f"{cell.name} needs {cell.chips} TPU chip(s); JAX found "
            f"{len(devices)} {devices[0].platform} device(s) "
            f"({devices[0].device_kind})"))
    peaks = cells.load_json(os.path.join(BENCH, "peaks.json"))
    kind = devices[0].device_kind
    if kind not in peaks:
        raise SystemExit(fail(f"no peaks for device kind {kind!r} in "
                              f"bench/peaks.json"))
    return cell, devices[:cell.chips], peaks[kind]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    cell, devices, peaks = start(args.workload)

    import harness
    result = harness.run_cell(cell, args.seed, args.seconds,
                              bool(args.trace), T_PROCESS, devices, peaks)
    for name, c in result["check"].items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
