"""Elastic fault-tolerance units (PR 8): plan-recording checkpoints and
their malformed-entry hygiene, the repro/plan@1 spec round trip, chaos
hooks, straggler detection, resilient-loop rollback determinism and the
DeviceLoss -> remesh handoff, step-addressable prefetch — plus the
4-device chaos acceptance (dist_checks group 'elastic')."""
import json
import os
import shutil
import tempfile

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import run_dist_group
from repro.checkpoint.checkpoint import (SCHEMA, CheckpointError,
                                         CheckpointManager)
from repro.data.pipeline import Prefetcher
from repro.launch.mesh import elastic_factorization
from repro.runtime import chaos
from repro.runtime.fault_tolerance import (DeviceLoss, ResilientLoop,
                                           StragglerMonitor)
from repro.train.metrics import MetricsLogger


# ---------------------------------------------------------- checkpoints --
def test_checkpoint_ignores_malformed_entries_and_sweeps_tmp():
    d = tempfile.mkdtemp()
    try:
        # debris a crash / stray tooling leaves behind
        os.makedirs(os.path.join(d, "step-garbage"))
        os.makedirs(os.path.join(d, "step-"))
        os.makedirs(os.path.join(d, "tmp-7"))
        with open(os.path.join(d, "step-123"), "w") as f:
            f.write("a plain file, not a checkpoint dir")
        ck = CheckpointManager(d, keep=2, async_save=False)
        assert not [x for x in os.listdir(d) if x.startswith("tmp-")]
        assert ck.latest_step() is None          # nothing valid committed
        ck.save(5, {"w": jnp.arange(3.0)})
        ck.save(9, {"w": jnp.arange(3.0)})
        assert ck.latest_step() == 9
        got, manifest = ck.restore({"w": jnp.zeros(3)})
        assert manifest["schema"] == SCHEMA
        np.testing.assert_allclose(np.asarray(got["w"]), [0, 1, 2])
        # gc kept the garbage names out of the rotation accounting
        ck.save(11, {"w": jnp.arange(3.0)})
        steps = sorted(x for x in os.listdir(d)
                       if x.startswith("step-")
                       and os.path.isdir(os.path.join(d, x)))
        assert "step-garbage" in steps and "step-" in steps
    finally:
        shutil.rmtree(d)


def test_checkpoint_manifest_records_plan():
    d = tempfile.mkdtemp()
    try:
        ck = CheckpointManager(d, async_save=False)
        spec = {"schema": "repro/plan@1", "mesh": {"data": 2, "model": 2},
                "mem_limit": 1e6, "layers": {}}
        ck.save(3, {"w": jnp.zeros(2)}, extra={"step": 3}, plan=spec)
        m = ck.read_manifest()
        assert m["plan"]["mesh"] == {"data": 2, "model": 2}
        assert m["extra"]["step"] == 3
        # the restore-error hint names the recorded mesh
        with pytest.raises(CheckpointError, match="data"):
            ck.restore({"w": jnp.zeros(2), "x": jnp.zeros(1)})
    finally:
        shutil.rmtree(d)


def test_checkpoint_torn_manifest_raises():
    d = tempfile.mkdtemp()
    try:
        ck = CheckpointManager(d, async_save=False)
        os.makedirs(os.path.join(d, "step-4"))
        with open(os.path.join(d, "step-4", "manifest.json"), "w") as f:
            f.write("{not json")
        with pytest.raises(CheckpointError, match="torn"):
            ck.read_manifest(4)
    finally:
        shutil.rmtree(d)


# ------------------------------------------------------ plan spec record --
def test_plan_spec_roundtrip():
    from repro.core import plan as plan_lib
    from repro.core.perfmodel import TPU_V5E
    from repro.models.cnn import meshnet
    cfg = meshnet.MeshNetConfig("t", input_hw=32, in_channels=4,
                                convs_per_block=1, widths=(8, 16),
                                bn_scope="global")
    specs = meshnet.layer_specs(cfg, 4)
    mesh = {"data": 2, "model": 2}
    plan = plan_lib.plan_line(TPU_V5E, specs, mesh)
    spec = plan.to_spec(mesh, mem_limit=2.5e6, config_hash="abc",
                        calibration_fingerprint="deadbeef")
    blob = json.loads(json.dumps(spec))          # JSON-serializable
    assert blob["schema"] == plan_lib.PLAN_SCHEMA
    assert blob["mesh"] == mesh and blob["mem_limit"] == 2.5e6
    assert blob["config_hash"] == "abc"
    assert set(blob["layers"]) == set(plan.layers)
    dists = plan_lib.dists_from_spec(blob)
    re_plan = plan_lib.plan_from_spec(blob, specs, mesh, machine=TPU_V5E)
    for name, lp in plan.layers.items():
        assert dists[name].dims == re_plan.layers[name].dist.dims, name
    with pytest.raises(plan_lib.PlanError, match="schema"):
        plan_lib.dists_from_spec({"schema": "repro/plan@99", "layers": {}})
    with pytest.raises(plan_lib.PlanError, match="no entry"):
        plan_lib.plan_from_spec(
            {"schema": plan_lib.PLAN_SCHEMA,
             "layers": {"conv1_1": blob["layers"]["conv1_1"]}},
            specs, mesh, machine=TPU_V5E)


def test_elastic_factorization():
    assert elastic_factorization(4, batch=8) == (2, 2)
    assert elastic_factorization(3, batch=4) == (1, 3)   # nothing divides
    assert elastic_factorization(6, batch=6) == (2, 3)
    assert elastic_factorization(1) == (1, 1)
    assert elastic_factorization(8) == (2, 4)            # sqrt-balanced
    for n in (2, 3, 4, 5, 6, 7, 8):
        d, m = elastic_factorization(n, batch=4)
        assert d * m == n and 4 % d == 0


# -------------------------------------------------------------- straggler --
def test_straggler_warmup_suppresses_flags():
    mon = StragglerMonitor(k=5.0, warmup=3)
    assert not mon.record(0, 99.0)       # warmup: even huge steps pass
    assert not mon.record(1, 0.1)
    assert not mon.record(2, 0.1)


def test_straggler_mad_flags_and_action():
    hits = []
    mon = StragglerMonitor(k=5.0, warmup=3,
                           action=lambda s, dt: hits.append((s, dt)))
    for i in range(8):
        assert not mon.record(i, 0.1 + 0.001 * (i % 2))
    assert mon.record(8, 2.0)
    assert hits == [(8, 2.0)]
    assert mon.stats["flagged"] == 1
    assert mon.stats["p95"] >= mon.stats["median"]
    # mild jitter under 1.5x median is never a straggler
    assert not mon.record(9, 0.14)


# --------------------------------------------------------- resilient loop --
def _np_loop(ckdir, **kw):
    """A ResilientLoop over plain-numpy state with a real manager."""
    ck = CheckpointManager(ckdir, keep=3, async_save=False)

    def make_step():
        def run(state, step):
            return {"x": state["x"] * 0.9 + step}, {"loss": state["x"]}
        return run
    return ck, ResilientLoop(ckpt=ck, make_step=make_step, ckpt_every=5,
                             max_failures=2, **kw)


def test_rollback_determinism():
    """A faulted run lands on exactly the fault-free final state: rollback
    replays the identical step sequence from the last checkpoint."""
    d1, d2 = tempfile.mkdtemp(), tempfile.mkdtemp()
    try:
        _, clean = _np_loop(d1)
        ref, step, _ = clean.run({"x": np.float32(1.0)}, 0, 12)
        ck, loop = _np_loop(d2)
        state, step, _ = loop.run({"x": np.float32(1.0)}, 0, 12,
                                  inject_failure=chaos.raise_at_step(7))
        assert step == 12
        np.testing.assert_array_equal(np.asarray(state["x"]),
                                      np.asarray(ref["x"]))
    finally:
        shutil.rmtree(d1)
        shutil.rmtree(d2)


def test_deviceloss_without_remesh_is_fatal():
    d = tempfile.mkdtemp()
    try:
        _, loop = _np_loop(d)
        with pytest.raises(DeviceLoss):
            loop.run({"x": np.float32(1.0)}, 0, 12,
                     inject_failure=chaos.drop_device_at_step(
                         3, devices=["d0", "d1", "d2", "d3"]))
    finally:
        shutil.rmtree(d)


def test_deviceloss_hands_survivors_to_remesh():
    d = tempfile.mkdtemp()
    seen = []
    try:
        ck, loop = _np_loop(d)

        def remesh(survivors):
            seen.append(list(survivors))

            def make_step():
                def run(state, step):
                    return {"x": state["x"] * 0.9 + step}, {}
                return run
            return make_step, {"x": np.float32(0.0)}     # template
        loop.remesh = remesh
        mpath = os.path.join(d, "m.jsonl")
        loop.metrics = MetricsLogger(mpath, echo=False)
        state, step, _ = loop.run({"x": np.float32(1.0)}, 0, 12,
                                  inject_failure=chaos.drop_device_at_step(
                                      7, n_drop=2,
                                      devices=["d0", "d1", "d2", "d3"]))
        loop.metrics.close()
        assert step == 12
        assert seen == [["d0", "d1"]]
        kinds = [json.loads(ln)["kind"] for ln in open(mpath)]
        assert "fault" in kinds and "remesh" in kinds \
            and "rollback" in kinds
    finally:
        shutil.rmtree(d)


def test_persistent_failure_gives_up():
    d = tempfile.mkdtemp()
    try:
        _, loop = _np_loop(d)
        with pytest.raises(RuntimeError, match="always"):
            loop.run({"x": np.float32(1.0)}, 0, 12,
                     inject_failure=lambda s: (_ for _ in ()).throw(
                         RuntimeError("always broken")))
    finally:
        shutil.rmtree(d)


def test_fault_before_first_checkpoint_reraises():
    """A step fault with no committed checkpoint is not retried: the
    original exception surfaces (after its fault event), never a retry on
    the step's donated input state."""
    d = tempfile.mkdtemp()
    try:
        _, loop = _np_loop(d)
        mpath = os.path.join(d, "m.jsonl")
        loop.metrics = MetricsLogger(mpath, echo=False)
        calls = []

        def inject(step):
            calls.append(step)
            if step == 2:
                raise ValueError("compile failed")
        with pytest.raises(ValueError, match="compile failed"):
            loop.run({"x": np.float32(1.0)}, 0, 12, inject_failure=inject)
        loop.metrics.close()
        assert calls == [0, 1, 2]                  # no retry of step 2
        kinds = [json.loads(ln)["kind"] for ln in open(mpath)]
        assert "fault" in kinds and "rollback" not in kinds
    finally:
        shutil.rmtree(d)


# ------------------------------------------------------------------ chaos --
def test_chaos_parse_and_fire_once():
    h = chaos.parse("raise@2")
    h(0); h(1)
    with pytest.raises(RuntimeError, match="step 2"):
        h(2)
    h(2)                                     # disarmed after firing
    with pytest.raises(ValueError, match="kind@step"):
        chaos.parse("raise")
    with pytest.raises(ValueError, match="unknown"):
        chaos.parse("explode@3")
    with pytest.raises(ValueError, match="checkpoint dir"):
        chaos.parse("corrupt@3")
    k = chaos.parse("kill@1x2", devices=["a", "b", "c"])
    with pytest.raises(DeviceLoss) as ei:
        k(1)
    assert ei.value.survivors == ["a"]


def test_chaos_corrupt_plants_debris():
    d = tempfile.mkdtemp()
    try:
        h = chaos.parse("corrupt@0,raise@5", ckpt_dir=d)
        h(0)                                 # plants, does not raise
        assert os.path.isdir(os.path.join(d, "tmp-0"))
        assert os.path.isdir(os.path.join(d, "step-garbage"))
        ck = CheckpointManager(d, async_save=False)   # sweeps + ignores
        assert ck.latest_step() is None
        assert not os.path.exists(os.path.join(d, "tmp-0"))
        with pytest.raises(RuntimeError):
            h(5)
    finally:
        shutil.rmtree(d)


# ------------------------------------------------------------- prefetcher --
def test_prefetcher_step_addressable():
    pf = Prefetcher(lambda s: {"step": np.array([s])}, start_step=0)
    try:
        assert pf.get(0)["step"][0] == 0
        assert pf.get(3)["step"][0] == 3     # skips stale 1, 2 forward
        assert pf.get(1)["step"][0] == 1     # rollback: seek backward
        assert pf.get(2)["step"][0] == 2
    finally:
        pf.close()


# --------------------------------------------------- 4-device acceptance --
def test_elastic_distributed():
    """The chaos-lane acceptance: a 4-device run faulted mid-run recovers
    onto the 3 survivors via the recorded plan spec + re-solve and its
    post-restore loss trajectory matches the uninterrupted oracle
    (dist_checks group 'elastic', default mode kill-device; the CI chaos
    job drives all three fault modes)."""
    run_dist_group("elastic")
