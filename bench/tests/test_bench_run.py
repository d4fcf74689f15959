"""A whole run of a tiny cell on the CPU, past the harness's look for a
chip: the result line's shape, and `correct` coming out false when the
timed path is broken underneath, once per fault a training cell can have.
The CPU computes float32 exactly at every matmul precision, so the tiny
cells hold the tight TINY_LIMITS."""
import json
import os
import subprocess
import sys

import pytest

from benchtest import BENCH, FAKE_PEAKS, ROOT, tiny_root

import cells
import check
import harness


def run_tiny(root, name, plant=None, trace=False, seed=3):
    import jax
    import time
    cell = cells.resolve(name, root)
    return harness.run_cell(cell, seed, 0.5, trace, time.time(),
                            jax.devices()[:cell.chips], FAKE_PEAKS,
                            plant=plant)


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny_root(tmp_path_factory.mktemp("root"))


def test_a_sound_run_is_correct_and_reports_its_metrics(root):
    res = run_tiny(root, "tiny")
    assert list(res) == ["correct", "attempted", "failed", "metrics",
                         "device", "check"]
    assert res["correct"] and res["failed"] == 0 and res["attempted"] > 0
    cell = cells.resolve("tiny", root)
    assert set(res["metrics"]) == {m["name"] for m in cell.end_to_end}
    assert all(v["value"] > 0 for v in res["metrics"].values())
    assert set(res["device"]) == {"platform", "kind", "count",
                                  "memory_peak_bytes"}
    assert list(res["check"]) == list(check.NUMBERS)
    json.dumps(res)


def test_a_traced_run_reports_per_layer_metrics(root, tmp_path,
                                                monkeypatch):
    monkeypatch.setattr(harness, "OUT_DIR", str(tmp_path))
    res = run_tiny(root, "tiny", trace=True)
    assert res["correct"]
    cell = cells.resolve("tiny", root)
    assert set(res["metrics"]) <= {m["name"] for m in cell.per_layer}
    assert res["metrics"]["window_compiles"]["value"] == 0
    assert {"busy_s", "window_s", "memory_peak_bytes"} <= set(res["device"])
    assert list(res)[-1] == "check" and "breakdown" in res
    assert os.path.isfile(tmp_path / "tiny" / "trace_head.json.gz")
    assert not os.path.exists(tmp_path / "tiny" / "trace")


def state_unchanged(opt, loss):
    from repro.optim.optimizer import Optimizer
    return Optimizer(opt.init, lambda g, s, p: (p, s)), loss


def half_batch(opt, loss):
    return opt, lambda p, b: loss(
        p, {k: v[:v.shape[0] // 2] for k, v in b.items()})


def answer_altered(opt, loss):
    return opt, lambda p, b: loss(p, b) * 1.01


@pytest.mark.parametrize("plant", [state_unchanged, half_batch,
                                   answer_altered])
def test_a_broken_step_is_not_correct(root, plant):
    res = run_tiny(root, "tiny", plant=plant)
    assert not res["correct"]
    assert any(c["value"] > c["limit"] for c in res["check"].values())


EXCHANGE = """
import sys, time
sys.path[:0] = [{bench!r}, {src!r}]
import jax
import cells, harness
from repro.core import halo

root, broken = sys.argv[1], sys.argv[2] == "1"
if broken:
    def no_exchange(x, dim, lo, hi, axis_name, axis_size):
        import jax.numpy as jnp
        from jax import lax
        edge = lambda n: None if n == 0 else jnp.zeros_like(
            lax.slice_in_dim(x, 0, n, axis=dim))
        return edge(lo), edge(hi)
    halo.halo_slices = no_exchange
cell = cells.resolve("tiny2x2", root)
res = harness.run_cell(cell, 3, 0.5, False, time.time(), jax.devices()[:4],
                       {{"bf16_flops": 1e12, "hbm_bytes_per_s": 1e11}})
print("CORRECT", res["correct"])
"""


def test_leaving_out_the_exchange_between_chips_is_not_correct(tmp_path):
    """mesh2k at smoke size on a 2x2 mesh of host devices, H split over
    'model': with the halo exchange replaced by zero rows the step is
    wrong, and `correct` says so."""
    root = tiny_root(tmp_path, arch="mesh2k", data=2, model=2,
                     name="tiny2x2")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    code = EXCHANGE.format(bench=BENCH, src=os.path.join(ROOT, "src"))
    out = {}
    for broken in ("0", "1"):
        r = subprocess.run([sys.executable, "-c", code, root, broken],
                           env=env, capture_output=True, text=True,
                           timeout=400)
        assert r.returncode == 0, r.stderr[-3000:]
        out[broken] = "CORRECT True" in r.stdout
    assert out == {"0": True, "1": False}


def test_the_control_reads_farther_than_the_program(root):
    """limits.readings, as run on the chip: here the control is the
    bf16 policy, since the CPU runs every float32 precision alike."""
    import jax
    import limits
    from repro.utils import BF16
    cell = cells.resolve("tiny", root)
    devices = jax.devices()[:1]
    ref = limits.Reference(cell, devices)
    quiet = lambda *a, **k: None  # noqa: E731
    prog = limits.readings(cell, devices, ref, [5], log=quiet)[5]
    ctl = limits.readings(cell, devices, ref, [5], precision=BF16,
                          log=quiet)[5]
    half = limits.readings(cell, devices, ref, [5], fault="half_batch",
                           log=quiet)[5]
    limits = cell.traffic["limits"]
    assert prog["loss1_gap"] < limits["loss1_gap"]
    assert ctl["loss1_gap"] > 10 * max(prog["loss1_gap"], 1e-7)
    assert half["grad_gap"] > 10 * max(prog["grad_gap"], 1e-6)
    # the run's own verdict at the cell's limits
    assert check.verdict(prog, limits)
    assert not check.verdict(ctl, limits)
    assert not check.verdict(half, limits)
