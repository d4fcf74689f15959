"""Memory model tests (the §VI Table-2 companion of the §V perf model):
per-layer/network footprints (core.perfmodel.layer_memory/network_memory),
the capacity-constrained solve (core.strategy), plan-compile validation
(core.plan mem_limit) and the model-vs-XLA cross-check (core.calibrate).

The 4-device acceptance path (uniform sample-parallel infeasible under a
synthetic limit, solved plan fits + matches the oracle) lives in
tests/dist_checks.py group 'memfit'.
"""
import dataclasses
import math

import jax
import jax.numpy as jnp
import pytest

from repro.core import perfmodel as pm
from repro.core.distribution import Dist
from repro.core.perfmodel import (ConvLayer, LayerMemory, layer_memory,
                                  network_memory)
from repro.core.plan import (PlanError, compile_plan, executable_candidates,
                             plan_line)
from repro.core.strategy import CapacityError, prune_by_memory, solve_line
from repro.models.cnn import meshnet

M = dataclasses.replace(pm.LASSEN, wordsize=4)   # fp32 words, 16 GB device
MS22 = {"data": 2, "model": 2}
MS222 = {"pod": 2, "data": 2, "model": 2}
REP = Dist("replicated", {})


# ------------------------------------------------------- word-count pins --
def test_act_words_uses_output_extents():
    """Output activations live at h_out/w_out — strided convs and pools
    shrink (the §VI accounting regression this PR pins down)."""
    strided = ConvLayer("s", n=4, c=8, h=32, w=32, f=16, k=3, s=2)
    pool = ConvLayer("p", n=4, c=16, h=32, w=32, f=16, k=3, s=2,
                     kind="pool")
    assert strided.act_words() == 4 * 16 * 16 * 16       # not 32x32
    assert pool.act_words() == 4 * 16 * 16 * 16


def test_layer_memory_word_counts_strided_conv():
    """Exact fwd+bwd byte counts for a strided conv, replicated and under
    a 2-way H split (pins the h_out/w_out extents in act_out and in the
    backward dL/dy halo buffer)."""
    layer = ConvLayer("s", n=4, c=8, h=32, w=32, f=16, k=3, s=2)
    lm = layer_memory(M, layer, REP, {})
    assert lm.weights == lm.grads == lm.opt == 3 * 3 * 8 * 16 * 4
    assert lm.act_in == 4 * 8 * 32 * 32 * 4
    assert lm.act_out == 4 * 16 * 16 * 16 * 4            # output extents
    assert lm.stash == 2 * lm.act_in + lm.act_out
    assert lm.halo == lm.cf == 0
    assert lm.total == lm.weights * 3 + 2 * lm.act_in + lm.act_out

    ms = {"m": 2}
    lm_h = layer_memory(M, layer, Dist("h", {"H": ("m",)}), ms)
    assert lm_h.act_in == lm.act_in / 2
    assert lm_h.act_out == lm.act_out / 2
    # fwd halo on x: 2*o*n*c*w_local; bwd halo on dL/dy: 2*o*n*f*w_out_local
    # — equal here (c*w == f*w_out at s=2, f=2c), which pins that the bwd
    # buffer uses OUTPUT extents: with input extents it would be 2x larger
    # and the max() would change the answer.
    assert lm_h.halo == 2 * 1 * 4 * 8 * 32 * 4
    assert lm_h.halo == 2 * 1 * 4 * 16 * 16 * 4


def test_layer_memory_word_counts_pool():
    """Pool layers hold no weights/grads/optimizer words; activations pin
    the same output-extents rule."""
    layer = ConvLayer("p", n=4, c=16, h=32, w=32, f=16, k=3, s=2,
                      kind="pool")
    lm = layer_memory(M, layer, REP, {})
    assert lm.weights == lm.grads == lm.opt == 0
    assert lm.act_in == 4 * 16 * 32 * 32 * 4
    assert lm.act_out == 4 * 16 * 16 * 16 * 4
    assert lm.total == 2 * lm.act_in + lm.act_out
    # max-pool backward needs its input: the stash is real for pools too
    assert lm.stash == 2 * lm.act_in + lm.act_out


def test_layer_memory_cf_shards_weights():
    """Under a CF dist both §III-D modes hold weight_words/p_cf resident,
    plus the staging buffer of the cheaper collective."""
    layer = ConvLayer("cf", n=4, c=16, h=8, w=8, f=32, k=3, s=1)
    cf = Dist("cf", {"N": ("data",), "C": ("model",), "F": ("model",)})
    lm = layer_memory(M, layer, cf, MS22)
    rep = layer_memory(M, layer, Dist("n", {"N": ("data",)}), MS22)
    assert lm.weights == rep.weights / 2
    assert lm.grads == rep.grads / 2 and lm.opt == rep.opt / 2
    words = pm.cf_collective_words(layer, cf, MS22)
    assert lm.cf == min(words["ag_x"], words["rs_y"]) * 4
    assert rep.cf == 0


# ------------------------------------------------------ property checks --
LAYERS = [
    ConvLayer("big", n=8, c=16, h=64, w=64, f=32, k=3, s=1),
    ConvLayer("strided", n=4, c=8, h=32, w=32, f=16, k=3, s=2),
    ConvLayer("late", n=2, c=32, h=8, w=8, f=64, k=3, s=1),
    ConvLayer("pool", n=8, c=16, h=32, w=32, f=16, k=3, s=2, kind="pool"),
    ConvLayer("pred", n=2, c=64, h=8, w=8, f=1, k=1, s=1),
]
MESHES = [MS22, MS222, {"data": 4, "model": 2}, {"data": 2}]


def test_layer_memory_finite_positive_over_candidate_families():
    """Every dist executable_candidates emits yields a finite, positive
    footprint with non-negative components, on every mesh."""
    for ms in MESHES:
        for layer in LAYERS:
            for d in executable_candidates(layer, ms):
                lm = layer_memory(M, layer, d, ms)
                assert math.isfinite(lm.total) and lm.total > 0, (layer, d)
                for f in dataclasses.fields(LayerMemory):
                    assert getattr(lm, f.name) >= 0, (layer, d, f.name)


def test_layer_memory_monotone_as_spatial_grid_grows():
    """Growing the spatial shard grid never increases the footprint: the
    activation terms shrink with the grid while halo buffers stay fixed —
    the §VI forcing function that makes spatial decomposition the only way
    down once sample parallelism hits one sample per device."""
    layer = ConvLayer("c", n=2, c=8, h=64, w=64, f=8, k=3, s=1)
    # deeper single-axis splits
    prev = None
    for p in (2, 4, 8):
        t = layer_memory(M, layer, Dist("h", {"H": ("m",)}), {"m": p}).total
        if prev is not None:
            assert t <= prev, p
        prev = t
    # widening a split into a product axis (the 16x16-mesh move)
    ms = {"a": 2, "b": 2}
    t_one = layer_memory(M, layer, Dist("h", {"H": ("a",)}), ms).total
    t_prod = layer_memory(M, layer, Dist("hh", {"H": ("a", "b")}), ms).total
    t_hw = layer_memory(M, layer,
                        Dist("hw", {"H": ("a",), "W": ("b",)}), ms).total
    assert t_prod <= t_one and t_hw <= t_one
    # and the unsplit layer is the ceiling
    t_rep = layer_memory(M, layer, REP, ms).total
    assert t_one <= t_rep


def test_network_memory_accumulates_stashes():
    """The network peak is larger than any single layer's resident set:
    forward stashes of earlier layers stay live (what remat-free training
    actually holds)."""
    specs = meshnet.layer_specs(
        meshnet.MeshNetConfig("t", input_hw=32, in_channels=4,
                              convs_per_block=1, widths=(8, 16)), 4)
    dists = [REP] * len(specs)
    net = network_memory(M, specs, dists, {})
    worst = max(lm.total for lm in net["per_layer"])
    assert net["peak_bytes"] > worst
    assert net["peak_layer"] == specs[-1].name     # stash-accumulated tail


def test_memory_model_agrees_with_xla_within_2x():
    """Predicted peak vs XLA's compiled memory_analysis on a small compiled
    plan (single device): within the 2x property tolerance — the §VI
    cross-check the dryrun pattern proves out (core.calibrate)."""
    from repro.core import calibrate as calib
    from repro.data.pipeline import synthetic_mesh_batch
    cfg = meshnet.MeshNetConfig("t", input_hw=32, in_channels=4,
                                convs_per_block=1, widths=(8, 16),
                                bn_scope="global")
    specs = meshnet.layer_specs(cfg, 4)
    # opt_words=0: the compiled step is loss+grads, no optimizer state
    plan = plan_line(M, specs, {"d": 1}, opt_words=0.0)
    params = meshnet.init(jax.random.PRNGKey(0), cfg)
    b = {k: jnp.asarray(v) for k, v in
         synthetic_mesh_batch(0, 4, 32, 4, out_hw=8).items()}
    step = jax.jit(jax.value_and_grad(
        lambda p, bb: meshnet.loss_fn(p, bb, cfg, plan, None)))
    res = calib.crosscheck_memory(plan, step, params, b)
    assert res["measured_bytes"] > 0, "backend exposes no memory_analysis"
    assert 0.5 <= res["ratio"] <= 2.0, res


# -------------------------------------------------- solver + plan layers --
def test_prune_by_memory_keeps_fitting_dists():
    layer = ConvLayer("c", n=4, c=8, h=32, w=32, f=8, k=3, s=1)
    cands = executable_candidates(layer, MS22)
    totals = [layer_memory(M, layer, d, MS22).total for d in cands]
    lim = sorted(totals)[len(totals) // 2]
    kept = prune_by_memory(M, layer, cands, MS22, lim)
    assert kept and all(
        layer_memory(M, layer, d, MS22).total <= lim for d in kept)
    # no limit: everything passes through
    assert prune_by_memory(M, layer, cands, MS22, None) == list(cands)


def test_capacity_error_names_layer_and_breakdown():
    """CapacityError follows the PlanError diagnostics discipline: layer
    name, smallest-achievable footprint, the dist achieving it, and the
    weights/acts/halo/grads breakdown."""
    layer = ConvLayer("res9", n=4, c=8, h=32, w=32, f=8, k=3, s=1)
    cands = executable_candidates(layer, MS22)
    with pytest.raises(CapacityError, match=r"'res9'.*smallest"):
        prune_by_memory(M, layer, cands, MS22, 64.0)
    try:
        prune_by_memory(M, layer, cands, MS22, 64.0)
    except CapacityError as e:
        msg = str(e)
        assert "act_in=" in msg and "weights=" in msg and "grads=" in msg
        best = min(cands, key=lambda d: layer_memory(M, layer, d,
                                                     MS22).total)
        assert repr(best.name) in msg


def test_solve_line_respects_memory_limit():
    """min-time SUBJECT TO the capacity constraint: with the limit, every
    solved dist fits; without, the solver may pick bigger-footprint ones."""
    specs = meshnet.layer_specs(
        meshnet.MeshNetConfig("t", input_hw=32, in_channels=4,
                              convs_per_block=1, widths=(8, 16)), 2)
    cands = [executable_candidates(l, MS22) for l in specs]
    lim = max(min(layer_memory(M, l, d, MS22).total for d in cs)
              for l, cs in zip(specs, cands)) * 1.05
    res = solve_line(M, specs, cands, MS22, mem_limit=lim)
    for l, d in zip(specs, res.dists):
        assert layer_memory(M, l, d, MS22).total <= lim, (l.name, d)


def test_compile_plan_validates_fit_with_breakdown():
    specs = [ConvLayer("a", n=8, c=4, h=32, w=32, f=8, k=3, s=1)]
    dists = {"a": Dist("sample", {"N": ("data", "model")})}
    with pytest.raises(PlanError, match=r"(?s)does not fit.*act_in="):
        compile_plan(dists, specs, MS22, machine=M, mem_limit=1024.0)
    # mem_limit without a machine is a usage error, not a silent skip
    with pytest.raises(PlanError, match="machine"):
        compile_plan(dists, specs, MS22, mem_limit=1024.0)


def test_demotion_note_records_capacity_violation():
    """A geometry demotion falls back to a coarser split; when that blows
    the capacity limit the note (and the raised PlanError) say so."""
    # H=4 over 2-way model with k=3: spatial demotes to sample-parallel,
    # whose footprint exceeds the tiny limit
    specs = [ConvLayer("a", n=8, c=16, h=4, w=4, f=16, k=3, s=1)]
    dists = {"a": Dist("hybrid", {"N": ("data",), "H": ("model",)})}
    demoted = layer_memory(M, specs[0], Dist("n", {"N": ("data",)}),
                           MS22).total
    with pytest.raises(PlanError, match="demotion violates capacity"):
        compile_plan(dists, specs, MS22, machine=M,
                     mem_limit=demoted * 0.9)
    # with headroom the same plan compiles, note records the demotion only
    plan = compile_plan(dists, specs, MS22, machine=M,
                        mem_limit=demoted * 10)
    assert "demoted" in plan.layers["a"].note
    assert "violates capacity" not in plan.layers["a"].note


def test_plan_line_memory_aware_solve_changes_plan():
    """The analytic half of the dist_checks 'memfit' acceptance: batch <
    devices makes sample parallelism memory-bound; under the limit the
    solve goes spatial and the recorded report carries limit + peak."""
    specs = meshnet.layer_specs(
        meshnet.MeshNetConfig("t", input_hw=32, in_channels=4,
                              convs_per_block=1, widths=(8, 16),
                              bn_scope="global"), 2)
    sample = [Dist("s", {"N": ("data",)})] * len(specs)
    sample_peak = network_memory(pm.TPU_V5E, specs, sample,
                                 MS22)["peak_bytes"]
    limit = 0.75 * sample_peak
    plan = plan_line(pm.TPU_V5E, specs, MS22, mem_limit=limit)
    mem = plan.predicted["memory"]
    assert mem["peak_bytes"] <= limit < sample_peak
    assert mem["limit_bytes"] == limit
    assert any(lp.sharding.is_spatial for lp in plan.layers.values())
    assert "limit" in plan.describe()
    # per-layer breakdowns ride along, keyed by layer name
    assert set(mem["per_layer"]) == {l.name for l in specs}


def test_plan_line_infeasible_limit_raises():
    specs = meshnet.layer_specs(
        meshnet.MeshNetConfig("t", input_hw=32, in_channels=4,
                              convs_per_block=1, widths=(8, 16)), 2)
    with pytest.raises((CapacityError, PlanError)):
        plan_line(pm.TPU_V5E, specs, MS22, mem_limit=256.0)


# --------------------------------------------------- capacity detection --
def test_detect_mem_capacity_host_fallback():
    """On the CPU backend memory_stats() is None, so the /proc/meminfo
    share answers — finite, positive, and memoized so calibrations stay
    deterministic within a process."""
    from repro.core.calibrate import detect_mem_capacity
    cap = detect_mem_capacity()
    assert math.isfinite(cap) and cap > 0
    assert detect_mem_capacity() == cap


class _FakeChip:
    platform, device_kind = "tpu", "TPU v5 lite"

    def __init__(self, stats):
        self._stats = stats

    def memory_stats(self):
        if isinstance(self._stats, Exception):
            raise self._stats
        return self._stats


@pytest.mark.parametrize("stats, expect", [
    ({"bytes_limit": 17_000_000_000, "peak_bytes_in_use": 0},
     17_000_000_000.0),
    ({"peak_bytes_in_use": 0}, RuntimeError),
    (None, RuntimeError),
    (OSError("runtime down"), OSError),
])
def test_detect_mem_capacity_accelerator_never_guesses(monkeypatch, stats,
                                                       expect):
    """An accelerator's capacity comes from its memory_stats bytes_limit
    or not at all: a failing or limit-less report raises instead of
    falling back to a host-RAM share or a default."""
    from repro.core import calibrate as cal
    monkeypatch.delenv("REPRO_MEM_CAPACITY", raising=False)
    monkeypatch.setattr(cal.jax, "local_devices",
                        lambda: [_FakeChip(stats)])
    cal.detect_mem_capacity.cache_clear()
    try:
        if isinstance(expect, float):
            assert cal.detect_mem_capacity() == expect
            assert cal.mem_capacity_source() == "device:memory_stats"
        else:
            with pytest.raises(expect):
                cal.detect_mem_capacity()
    finally:
        cal.detect_mem_capacity.cache_clear()


def test_calibration_roundtrips_mem_capacity():
    from repro.core.calibrate import Calibration
    from repro.core.perfmodel import EmpiricalTable
    mach = dataclasses.replace(M, mem_capacity=123456.0)
    cal = Calibration(machine=mach, table=EmpiricalTable({}), meta={})
    back = Calibration.from_json(cal.to_json())
    assert back.machine.mem_capacity == 123456.0
