"""Resolve a benchmark cell from `BENCHMARK.json` and the files it names.

Everything that belongs to one configuration, traffic mix or per-layer
metric is a file of its own, found by name:

  bench/configs/<config>.json    sizes as run, source, `reduced`, `assumed`,
                                 and `model`, the module below
  bench/models/<model>.py        what only that network knows: its program
                                 keys, batch spec, data draw, reference
                                 loss and convolutions
  bench/traffic/<traffic>.json   batch, mesh, plan flags, pool, warm-up,
                                 and the limits of the correctness check
  bench/metrics/<metric>.py      one reducer per per-layer metric

so a later cell, configuration or metric is added as files alone.  Pure
Python: importing this module touches neither JAX nor a device.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
import re
import sys

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)

NAME_RE = re.compile(r"[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}")
UNIT_RE = re.compile(r"[A-Za-z0-9_/%.\-]{1,16}")


class CellError(ValueError):
    """A cell, or a file it names, is missing or malformed."""


def check_name(name: str, what: str) -> str:
    if not isinstance(name, str) or not NAME_RE.fullmatch(name):
        raise CellError(f"{what} {name!r}: a name is 1-64 of A-Z a-z 0-9 "
                        f"_ . - and starts with a letter, digit or _")
    return name


def check_unit(unit: str, what: str) -> str:
    if not isinstance(unit, str) or not UNIT_RE.fullmatch(unit):
        raise CellError(f"{what}: unit {unit!r} is 1-16 of A-Z a-z 0-9 "
                        f"_ / % . -")
    return unit


def load_json(path: str) -> dict:
    try:
        with open(path) as f:
            return json.load(f)
    except FileNotFoundError as e:
        raise CellError(f"missing file {path}") from e
    except json.JSONDecodeError as e:
        raise CellError(f"{path}: not JSON: {e}") from e


def load_benchmark(root: str = ROOT) -> dict:
    """`BENCHMARK.json` with every name and unit checked."""
    bench = load_json(os.path.join(root, "BENCHMARK.json"))
    for c in bench["configs"]:
        check_name(c["name"], "config")
        for k in c["reduced"]:
            check_name(k, f"config {c['name']}: reduced key")
    for w in bench["workloads"]:
        check_name(w["name"], "workload")
        check_name(w["config"], f"workload {w['name']}: config")
        check_name(w["traffic"], f"workload {w['name']}: traffic")
    for m in bench["end_to_end"] + bench["per_layer"]:
        check_name(m["name"], "metric")
        check_unit(m["unit"], f"metric {m['name']}")
    return bench


@dataclasses.dataclass(frozen=True)
class Cell:
    """One workload of `BENCHMARK.json` with its files loaded."""
    name: str
    chips: int
    config: dict          # bench/configs/<config>.json
    traffic: dict         # bench/traffic/<traffic>.json
    end_to_end: tuple     # metric entries this cell reports with --trace 0
    per_layer: tuple      # metric entries this cell reports with --trace 1
    bench_dir: str

    def metric_path(self, name: str) -> str:
        return os.path.join(self.bench_dir, "metrics", f"{name}.py")

    @property
    def model_path(self) -> str:
        return model_path(self.bench_dir, self.config["model"])


def model_path(bench_dir: str, model: str) -> str:
    return os.path.join(bench_dir, "models", f"{model}.py")


def reports(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def resolve(cell_name: str, root: str = ROOT) -> Cell:
    """The cell named `cell_name`, its files loaded; CellError if it, or
    any file it needs, is missing."""
    bench = load_benchmark(root)
    cells = {w["name"]: w for w in bench["workloads"]}
    if cell_name not in cells:
        raise CellError(f"no workload {cell_name!r} in BENCHMARK.json "
                        f"(has {sorted(cells)})")
    w = cells[cell_name]
    configs = {c["name"]: c for c in bench["configs"]}
    if w["config"] not in configs:
        raise CellError(f"workload {cell_name}: no config {w['config']!r}")
    entry = configs[w["config"]]
    config = load_json(os.path.join(root, entry["file"]))
    if "model" not in config:
        raise CellError(f"{entry['file']}: no `model` naming "
                        f"bench/models/<model>.py")
    check_name(config["model"], f"config {entry['name']}: model")
    bench_dir = os.path.join(root, os.path.dirname(os.path.dirname(
        entry["file"])))
    traffic = load_json(os.path.join(bench_dir, "traffic",
                                     f"{w['traffic']}.json"))
    cell = Cell(
        name=cell_name, chips=int(w["chips"]), config=config,
        traffic=traffic,
        end_to_end=tuple(m for m in bench["end_to_end"]
                         if reports(m, cell_name)),
        per_layer=tuple(m for m in bench["per_layer"]
                        if reports(m, cell_name)),
        bench_dir=bench_dir)
    for m in cell.per_layer:
        if not os.path.isfile(cell.metric_path(m["name"])):
            raise CellError(f"metric {m['name']}: no reducer at "
                            f"{cell.metric_path(m['name'])}")
    if not os.path.isfile(cell.model_path):
        raise CellError(f"config {entry['name']}: no model module at "
                        f"{cell.model_path}")
    load_model(cell)
    return cell


def load_file(path: str, modname: str):
    """The module at `path`, executed afresh under `modname`."""
    spec = importlib.util.spec_from_file_location(modname, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_reducer(cell: Cell, name: str):
    """The `reduce(ctx)` function of bench/metrics/<name>.py."""
    modname = "bench_metric_" + re.sub(r"[^A-Za-z0-9_]", "_", name)
    return load_file(cell.metric_path(name), modname).reduce


def model_modname(model: str) -> str:
    return "bench_model_" + re.sub(r"[^A-Za-z0-9_]", "_", model)


def load_model(cell: Cell):
    """The module bench/models/<model>.py that the cell's config names,
    kept among the process's modules, where `model_of` finds it."""
    name = model_modname(cell.config["model"])
    sys.modules[name] = load_file(cell.model_path, name)
    return sys.modules[name]


def model_of(config: dict):
    """The module of `config['model']`: the one that resolving a cell
    loaded, else bench/models/<model>.py beside this file.  The shared
    code (traffic, reference, flops) asks it for what only one network
    knows."""
    name = model_modname(config["model"])
    if name not in sys.modules:
        sys.modules[name] = load_file(
            model_path(BENCH_DIR, config["model"]), name)
    return sys.modules[name]
