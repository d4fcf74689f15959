"""The benchmark's files: BENCHMARK.json against its rules, every cell
resolving to its files, a cell added as files alone, and run.py refusing
to run anywhere but on TPU chips.  No test here starts JAX in-process."""
import os
import re
import shutil
import subprocess
import sys

import pytest

from benchtest import BENCH, ROOT, dump, load, tiny_root

import cells
import check

BENCHMARK = os.path.join(ROOT, "BENCHMARK.json")
TOP_KEYS = {"command", "paths", "run_seconds", "configs", "workloads",
            "end_to_end", "per_layer"}
LINE = re.compile(r"[^\t\n]{1,200}")


def bench():
    return load(BENCHMARK)


def test_benchmark_json_shape():
    b = bench()
    assert set(b) == TOP_KEYS
    assert os.path.getsize(BENCHMARK) <= 64 * 1024
    assert b["command"] == ["python3", "bench/run.py"]
    assert b["paths"] == ["bench"]
    assert isinstance(b["run_seconds"], int) and 1 <= b["run_seconds"] <= 51
    for c in b["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert c["file"].startswith("bench/") and LINE.fullmatch(c["why"])
        assert c["reduced"] == load(os.path.join(ROOT, c["file"]))["reduced"]
    four = sum(w["chips"] == 4 for w in b["workloads"])
    assert four <= max(1, len(b["workloads"]) // 2)
    pairs = set()
    for w in b["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["chips"] in (1, 4) and LINE.fullmatch(w["why"])
        assert (w["config"], w["traffic"]) not in pairs
        pairs.add((w["config"], w["traffic"]))
    e2e = {m["name"] for m in b["end_to_end"]}
    assert "setup_s" in e2e
    for m in b["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25 and m["better"] in ("lower",
                                                               "higher")
    for m in b["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert m["moves"] in e2e and LINE.fullmatch(m["layer"])
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
    names = [m["name"] for m in b["end_to_end"] + b["per_layer"]]
    assert len(names) == len(set(names))


@pytest.mark.parametrize("name", [w["name"] for w in bench()["workloads"]])
def test_every_cell_resolves_to_its_files(name):
    cell = cells.resolve(name, ROOT)
    assert set(check.NUMBERS) <= set(cell.traffic["limits"])
    assert cell.traffic["data"] * cell.traffic["model"] == cell.chips
    assert {m["name"] for m in cell.end_to_end} >= {"setup_s"}
    assert len(cell.end_to_end) >= 2 and cell.per_layer
    for m in cell.per_layer:
        assert callable(cells.load_reducer(cell, m["name"]))


@pytest.mark.parametrize("bad", ["", "has space", "a,b", "a/b", "-lead",
                                 "µs", "x" * 65])
def test_bad_names_are_refused(bad):
    with pytest.raises(cells.CellError):
        cells.check_name(bad, "test")


@pytest.mark.parametrize("unit,ok", [("samples/s", True), ("%", True),
                                     ("GiB", True), ("tokens per s", False),
                                     ("µs", False), ("", False)])
def test_units_use_only_allowed_characters(unit, ok):
    if ok:
        assert cells.check_unit(unit, "test") == unit
    else:
        with pytest.raises(cells.CellError):
            cells.check_unit(unit, "test")


def test_cell_and_metric_added_as_files_alone(tmp_path):
    root = tiny_root(tmp_path, name="added")
    os.unlink(os.path.join(root, "bench", "metrics"))
    shutil.copytree(os.path.join(BENCH, "metrics"),
                    os.path.join(root, "bench", "metrics"))
    with open(os.path.join(root, "bench", "metrics", "steps_seen.py"),
              "w") as f:
        f.write("def reduce(ctx):\n    return ctx.trace.steps\n")
    b = load(os.path.join(root, "BENCHMARK.json"))
    b["per_layer"].append({"name": "steps_seen", "unit": "count",
                           "better": "higher", "source": "device_trace",
                           "layer": "device", "moves": "samples_per_s",
                           "workloads": ["added"]})
    dump(b, os.path.join(root, "BENCHMARK.json"))
    cell = cells.resolve("added", root)
    assert cell.config["input_hw"] == 64 and cell.traffic["batch"] == 4
    assert "steps_seen" in {m["name"] for m in cell.per_layer}

    class Ctx:
        class trace:
            steps = 7
    assert cells.load_reducer(cell, "steps_seen")(Ctx) == 7
    other = cells.resolve("mesh1k.1chip.b8", root)
    assert "steps_seen" not in {m["name"] for m in other.per_layer}


def test_missing_files_are_named(tmp_path):
    root = tiny_root(tmp_path, name="gone")
    os.unlink(os.path.join(root, "bench", "traffic", "gone.json"))
    with pytest.raises(cells.CellError, match="gone.json"):
        cells.resolve("gone", root)
    with pytest.raises(cells.CellError, match="no workload"):
        cells.resolve("nowhere", root)


def test_importing_the_benchmark_touches_no_jax():
    mods = ["cells", "check", "devtrace", "flops", "harness", "limits",
            "reference", "run", "traffic", "models.meshnet"]
    code = (f"import sys; sys.path.insert(0, {BENCH!r}); "
            + "; ".join(f"import {m}" for m in mods)
            + "; print(sorted(m for m in sys.modules "
              "if m.split('.')[0] in ('jax', 'jaxlib', 'libtpu')))")
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=120)
    assert r.returncode == 0, r.stderr
    assert r.stdout.strip() == "[]"


def run_py(cwd, env_extra=None):
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env.update(env_extra or {})
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "mesh1k.1chip.b8",
         "--seed", "2147483659", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_run_without_a_tpu_exits_nonzero_and_prints_no_result():
    r = run_py(ROOT, {"JAX_PLATFORMS": "cpu"})
    assert r.returncode != 0
    assert "{" not in r.stdout
    assert "cpu" in r.stderr and "TPU" in r.stderr


def test_run_with_only_the_benchmark_files_exits_nonzero(tmp_path):
    shutil.copy(BENCHMARK, tmp_path / "BENCHMARK.json")
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("out", ".jax_cache",
                                                  "__pycache__"))
    r = run_py(str(tmp_path), {"JAX_PLATFORMS": "cpu"})
    assert r.returncode != 0
    assert "{" not in r.stdout
    assert "sources" in r.stderr


def test_peaks_table_names_v5e_and_its_source():
    peaks = load(os.path.join(BENCH, "peaks.json"))
    v5e = peaks["TPU v5 lite"]
    assert v5e["bf16_flops"] == 197e12 and v5e["hbm_bytes_per_s"] == 819e9
    assert "TPU v5e" in v5e["source"]


def test_a_null_limit_is_reported_but_not_compared():
    numbers = {"loss1_gap": 1e-7, "loss_gap": 5.0, "grad_gap": 1e-3,
               "change_gap": 1e-3}
    limits = {"loss1_gap": 1e-6, "loss_gap": None, "grad_gap": 1e-2,
              "change_gap": 1e-2}
    assert check.verdict(numbers, limits)
    assert check.report(numbers, limits)["loss_gap"] == {"value": 5.0,
                                                         "limit": None}
    assert not check.verdict(dict(numbers, grad_gap=0.5), limits)
