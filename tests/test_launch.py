"""Entry points: the trainer's in-process `main(argv)`, the compile-cache
placement, and `chip_smoke.py`'s refusal to run without a chip."""
import json
import os
import shutil
import subprocess
import sys

import jax
import pytest

from conftest import host_events
from repro.launch import compile_cache, train

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_compile_cache_env_dir_wins(monkeypatch):
    calls = []
    monkeypatch.setattr(compile_cache.jax.config, "update",
                        lambda *a: calls.append(a))
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere/cache")
    assert compile_cache.enable() == "/elsewhere/cache"
    assert calls == []                     # JAX reads the env var itself


def test_compile_cache_default_is_fixed_repo_path(monkeypatch):
    calls = []
    monkeypatch.setattr(compile_cache.jax.config, "update",
                        lambda *a: calls.append(a))
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    path = compile_cache.enable()
    assert path == os.path.join(REPO, ".jax_cache")
    assert calls == [("jax_compilation_cache_dir", path)]
    with open(os.path.join(REPO, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()


def test_train_main_in_process(tmp_path, monkeypatch):
    """`main(argv)` trains in the caller's process and returns what a
    smoke check reads: per-step loss/grad_norm/wall records in the
    metrics JSONL, no fault, and a state committed to the run's mesh."""
    monkeypatch.setattr(train.compile_cache, "enable", lambda: None)
    metrics = tmp_path / "m.jsonl"
    out = train.main([
        "--arch", "mesh1k", "--smoke", "--batch", "2", "--steps", "3",
        "--ckpt-every", "4", "--ckpt-dir", str(tmp_path / "ck"),
        "--metrics", str(metrics), "--log-every", "1"])
    recs = [json.loads(line) for line in open(metrics)]
    steps = [r for r in recs if r["kind"] == "step"]
    assert [r["step"] for r in steps] == [0, 1, 2]
    assert not [r for r in recs if r["kind"] == "fault"]
    assert all(r["grad_norm"] > 0 and r["wall_s"] > 0 for r in steps)
    assert out["step"] == 3 and len(out["losses"]) == 3
    assert set(out["batch_shardings"]) == {"image", "label"}
    # every state leaf is committed to the mesh: step 1 reuses step 0's
    # executable instead of recompiling for an uncommitted scalar
    for leaf in jax.tree.leaves(out["state"]):
        assert leaf.sharding.mesh == out["mesh"]


def test_train_main_steps_through_run_step(tmp_path, monkeypatch):
    """`main`'s loop takes every step through `run_step`, numbered from
    the start step."""
    monkeypatch.setattr(train.compile_cache, "enable", lambda: None)
    seen, real = [], train.run_step

    def spy(tstep, put, state, batch, step_num):
        seen.append(step_num)
        return real(tstep, put, state, batch, step_num)

    monkeypatch.setattr(train, "run_step", spy)
    out = train.main([
        "--arch", "mesh1k", "--smoke", "--batch", "2", "--steps", "2",
        "--ckpt-every", "4", "--ckpt-dir", str(tmp_path / "ck")])
    assert seen == [0, 1] and len(out["losses"]) == 2


STEP_SPANS = ("train.step", "train.put", "train.h2d", "train.dispatch",
              "train.readback")


def test_run_step_spans_on_the_profiler_clock(tmp_path):
    """Three steps of mesh1k at smoke size under a profiler session: each
    step has one train.step holding train.put, train.dispatch and
    train.readback in that order; train.h2d begins inside train.put and
    closes on a host line of its own (the waiter thread's)."""
    args = train.parser().parse_args(
        ["--arch", "mesh1k", "--smoke", "--batch", "2", "--steps", "4"])
    mesh = train.make_mesh(data=1, model=1)
    cfg, params, opt, loss, mk, put, prec, _ = train.build(args, mesh)
    state = train.train_state(params, opt, mesh)
    tstep = train.train_step(args, opt, loss, prec, mesh, state)
    state, host = train.run_step(tstep, put, state, mk(0), 0)   # compiles
    assert set(host) >= {"loss", "grad_norm"}
    jax.profiler.start_trace(str(tmp_path))
    try:
        for i in range(1, 4):
            state, host = train.run_step(tstep, put, state, mk(i), i)
    finally:
        jax.profiler.stop_trace()
    ev = [e for e in host_events(str(tmp_path)) if e[0] in STEP_SPANS]
    steps = [e for e in ev if e[0] == "train.step"]
    assert len(steps) == 3
    for _, s, e, line in steps:
        inner = {n: (a, b, ln) for n, a, b, ln in ev
                 if s <= a <= e and n != "train.step"}
        assert len([n for n, a, *_ in ev if s <= a <= e]) == 5
        put_, h2d = inner["train.put"], inner["train.h2d"]
        assert put_[0] <= h2d[0] <= put_[1] and h2d[2] != line
        assert (put_[1] <= inner["train.dispatch"][0]
                <= inner["train.dispatch"][1]
                <= inner["train.readback"][0] <= inner["train.readback"][1]
                <= e)
        assert all(ln == line for n, (_, _, ln) in inner.items()
                   if n != "train.h2d")


def _smoke(cwd, env_extra=None):
    env = dict(os.environ, **(env_extra or {}))
    return subprocess.run([sys.executable, "chip_smoke.py"], cwd=cwd,
                          env=env, capture_output=True, text=True,
                          timeout=120)


def test_chip_smoke_refuses_without_tpu():
    r = _smoke(REPO, {"JAX_PLATFORMS": "cpu"})
    assert r.returncode != 0
    assert '"ok"' not in r.stdout and "needs 1 TPU" in r.stderr


def test_chip_smoke_refuses_without_repo(tmp_path):
    shutil.copy(os.path.join(REPO, "chip_smoke.py"), tmp_path)
    r = _smoke(tmp_path)
    assert r.returncode != 0
    assert '"ok"' not in r.stdout and "no repro sources" in r.stderr


def test_pallas_backend_refuses_unequal_strides():
    import jax.numpy as jnp
    from repro.core.spatial_conv import _conv_nhwc
    x = jnp.zeros((1, 8, 8, 2))
    w = jnp.zeros((3, 3, 2, 2))
    with pytest.raises(ValueError, match="equal strides"):
        _conv_nhwc(x, w, (2, 1), ((0, 1), (1, 1)), backend="pallas")
