"""Performance model (paper §V) + strategy optimizer (§V-C) tests."""
import dataclasses

import numpy as np
import pytest
from _hyp import given, settings, st  # optional-hypothesis shim

from repro.core import perfmodel as pm
from repro.core import strategy as strat
from repro.core.distribution import Dist, hybrid, sample
from repro.models.cnn import meshnet, resnet

M = dataclasses.replace(pm.LASSEN, compute_efficiency=0.119,
                        eff_halfwork=1.49e9)


def test_collective_models_sane():
    # allreduce cost grows with message size and is >= 0
    assert pm.allreduce_time(M, 4, 1 << 20) > pm.allreduce_time(M, 4, 1 << 10)
    assert pm.allreduce_time(M, 1, 1 << 20) == 0.0
    # ring beats recursive doubling for large messages (Thakur)
    big = 64 << 20
    ring = 2 * 63 * M.alpha_coll + 2 * 63 / 64 * big * M.beta_coll
    assert pm.allreduce_time(M, 64, big) <= ring + 1e-12
    assert pm.sr_time(M, 0) == 0.0
    assert pm.all_to_all_time(M, 8, 1 << 20) > 0


def test_layer_cost_sample_cheapest_comm():
    """Paper: 'sample parallelism is the cheapest approach: it requires
    only the allreduce time in BPa'."""
    layer = pm.ConvLayer("c", n=32, c=64, h=56, w=56, f=64, k=3, s=1)
    ms = {"data": 2, "model": 2}
    cs = pm.layer_cost(M, layer, sample(("data", "model")), ms,
                       overlap=False)
    ch = pm.layer_cost(M, layer, hybrid(("data",), ("model",)), ms,
                       overlap=False)
    # same compute split, but hybrid adds halo time
    comm_h = ch.total - ch.fp_compute - ch.bp_compute
    assert comm_h > cs.bpa * 0.99


def test_overlap_reduces_cost():
    layer = pm.ConvLayer("c", n=4, c=64, h=1024, w=1024, f=64, k=3, s=1)
    ms = {"model": 4}
    d = Dist("h", {"H": ("model",)})
    c_ov = pm.layer_cost(M, layer, d, ms, overlap=True)
    c_no = pm.layer_cost(M, layer, d, ms, overlap=False)
    assert c_ov.total <= c_no.total


def test_product_axis_halo_hop_pricing():
    """Halo over a product of mesh axes (H split 2x2 ways) pays extra link
    hops on its boundary-crossing sends but sends fewer messages than the
    H x W decomposition at the same total bytes (no corner exchanges):
    dearer than a true single-axis split, cheaper than H x W on squares."""
    assert pm.sr_time(M, 1024, hops=2) > pm.sr_time(M, 1024)
    assert pm.sr_time(M, 1024, hops=2) == M.alpha * 2 + M.beta * 1024
    layer = pm.ConvLayer("c", n=2, c=16, h=64, w=64, f=16, k=3, s=1)
    ms = {"a": 2, "b": 2}
    comm = lambda c: c.fp - c.fp_compute    # noqa: E731
    c_prod = pm.layer_cost(M, layer, Dist("hh", {"H": ("a", "b")}), ms,
                           overlap=False)
    c_hw = pm.layer_cost(M, layer, Dist("hw", {"H": ("a",), "W": ("b",)}),
                         ms, overlap=False)
    c_one = pm.layer_cost(M, layer, Dist("h4", {"H": ("a",)}), {"a": 4},
                          overlap=False)
    assert c_prod.fp_compute == c_hw.fp_compute == c_one.fp_compute
    assert comm(c_one) < comm(c_prod) < comm(c_hw)


def test_cf_overlap_credit_matches_runtime_semantics():
    """The model's CF forward term credits overlap η-scaled:
    fp = compute + RS - η·min(RS, compute).  At the analytic machines'
    η=1 default that is exactly max(compute, RS) — justified now that
    channel_conv's overlapped channel mode pipelines the psum_scatter
    with per-channel-block compute (§IV-A analogue) — while a calibrated
    η < 1 keeps the unhidden share of the collective on the bill."""
    layer = pm.ConvLayer("cf", n=4, c=32, h=8, w=8, f=32, k=3, s=1)
    ms = {"data": 2, "model": 2}
    cf = Dist("cf", {"N": ("data",), "C": ("model",), "F": ("model",)})
    ov = pm.layer_cost(M, layer, cf, ms, overlap=True)
    no = pm.layer_cost(M, layer, cf, ms, overlap=False)
    rs = no.fp - no.fp_compute
    assert rs > 0, "CF layer must pay a forward reduce-scatter"
    assert M.overlap_eta == 1.0       # analytic machines stay at full credit
    assert ov.fp == max(ov.fp_compute, rs)
    assert ov.fp_saved == pytest.approx(min(rs, ov.fp_compute))
    assert ov.total <= no.total
    # η = 0.5: exactly half of the hideable min is credited, and the saved
    # seconds are surfaced per layer via LayerCost.overlap_credit
    M5 = dataclasses.replace(M, overlap_eta=0.5)
    half = pm.layer_cost(M5, layer, cf, ms, overlap=True)
    assert half.fp == pytest.approx(
        half.fp_compute + rs - 0.5 * min(rs, half.fp_compute))
    assert half.fp_saved == pytest.approx(0.5 * min(rs, half.fp_compute))
    assert no.fp_saved == no.bp_saved == 0.0 and no.overlap_credit == 0.0
    assert ov.fp < half.fp < no.fp
    # η = 0 degenerates to the serialized bill even with overlap=True
    z = pm.layer_cost(dataclasses.replace(M, overlap_eta=0.0), layer, cf,
                      ms, overlap=True)
    assert z.fp == no.fp and z.overlap_credit == 0.0


def test_cf_collective_words_at_submesh_sizes():
    """AG(x)/RS(y) payloads shrink with composed spatial splits and the
    collective runs at the CF sub-mesh size, not the whole mesh."""
    layer = pm.ConvLayer("cf", n=4, c=16, h=16, w=16, f=32, k=3, s=1)
    ms = {"pod": 2, "data": 2, "model": 2}
    pure = Dist("cf", {"N": ("pod", "data"), "C": ("model",),
                       "F": ("model",)})
    comp = Dist("cfh", {"N": ("pod",), "H": ("data",), "C": ("model",),
                        "F": ("model",)})
    wp = pm.cf_collective_words(layer, pure, ms)
    wc = pm.cf_collective_words(layer, comp, ms)
    assert wp["p_cf"] == wc["p_cf"] == 2          # sub-mesh, not 8
    assert wc["rs_y"] == wp["rs_y"]               # n doubles, H halves
    assert pm.cf_mode_for(layer, pure, ms) == "filter"   # F=2C at s=1


class _Dev:
    def __init__(self, platform, kind):
        self.platform, self.device_kind = platform, kind


def test_machine_for_device_kind():
    """A TPU's Machine is looked up by its exact device_kind (an unknown
    kind is an error, never a default); off-TPU the analytic v5e model
    prices the rehearsal plan.  Either way the words are the step's."""
    fp32 = dataclasses.replace(pm.TPU_V5E, wordsize=4)
    assert pm.machine_for(_Dev("tpu", "TPU v5 lite"), 4) == fp32
    assert pm.machine_for(_Dev("cpu", "cpu"), 4) == fp32
    assert pm.machine_for(_Dev("cpu", "cpu"), 2) == pm.TPU_V5E
    with pytest.raises(ValueError, match="TPU v4"):
        pm.machine_for(_Dev("tpu", "TPU v4"), 4)


def test_candidates_valid():
    layer = pm.ConvLayer("c", n=6, c=18, h=96, w=96, f=64, k=3, s=2)
    ms = {"data": 3, "model": 2}
    cands = strat.candidate_dists(layer, ms, allow_channel_filter=True)
    assert cands, "must generate at least one candidate"
    for d in cands:
        for dim, size in [("N", layer.n), ("H", layer.h), ("W", layer.w),
                          ("C", layer.c), ("F", layer.f)]:
            assert size % d.ways(dim, ms) == 0
        if d.ways("H", ms) > 1:
            assert layer.h // d.ways("H", ms) >= layer.k


@settings(max_examples=20, deadline=None)
@given(n_layers=st.integers(2, 5), seed=st.integers(0, 100))
def test_line_solver_optimal(n_layers, seed):
    """DP shortest path == brute force on small strategy spaces."""
    ms = {"data": 2, "model": 2}
    layers = [pm.ConvLayer(f"l{i}", n=4, c=8, h=32, w=32, f=8, k=3, s=1)
              for i in range(n_layers)]
    cands = [strat.candidate_dists(l, ms) for l in layers]
    res = strat.solve_line(M, layers, cands, ms)
    # brute force
    import itertools
    best = np.inf
    for combo in itertools.product(*cands):
        c = sum(pm.layer_cost(M, l, d, ms).total
                for l, d in zip(layers, combo))
        c += sum(pm.shuffle_time(M, layers[i], combo[i], combo[i + 1], ms)
                 for i in range(n_layers - 1))
        best = min(best, c)
    assert res.cost <= best * (1 + 1e-9)


def test_dag_solver_covers_resnet():
    g = resnet.resnet_graph(32)
    sol = strat.solve_dag(M, g, {"data": 2, "model": 2})
    assert set(sol) == set(g.nodes)


def test_paper_conclusions():
    """Strategy engine reproduces the paper's qualitative findings:
    spatial wins for large-spatial mesh layers, sample for ResNet."""
    ms = {"data": 4, "model": 4}
    mesh_layers = meshnet.layer_specs(meshnet.MESH1K, 4)
    cands = [strat.candidate_dists(l, ms) for l in mesh_layers]
    res = strat.solve_line(M, mesh_layers, cands, ms)
    assert any(d.ways("H", ms) > 1 for d in res.dists), \
        "mesh model should use spatial parallelism"
    rn = resnet.layer_specs(256)
    cands = [strat.candidate_dists(l, ms) for l in rn]
    res_rn = strat.solve_line(M, rn, cands, ms)
    n_sample = sum(d.ways("N", ms) == 16 for d in res_rn.dists)
    assert n_sample > len(rn) * 0.6, \
        "ResNet at large batch should be mostly sample-parallel"


def test_table1_reproduction():
    """Perf model reproduces paper Table I (1K mesh strong scaling) within
    tolerance after the 2-constant calibration (EXPERIMENTS.md §Paper)."""
    SPLITS = {1: (1, 1), 2: (2, 1), 4: (2, 2), 8: (4, 2), 16: (4, 4)}
    TABLE1 = {4: {1: 0.403, 2: 0.2, 4: 0.121, 8: 0.0906, 16: 0.066},
              32: {1: 0.401, 2: 0.207, 4: 0.123, 8: 0.0874, 16: 0.0794}}
    errs = []
    for N, row in TABLE1.items():
        for p, t in row.items():
            hy, wx = SPLITS[p]
            ms = {"d": N, "mh": hy, "mw": wx}
            dims = {"N": ("d",)}
            if hy > 1:
                dims["H"] = ("mh",)
            if wx > 1:
                dims["W"] = ("mw",)
            d = Dist(f"hyb{p}", dims)
            layers = meshnet.layer_specs(meshnet.MESH1K, N)
            pred = pm.network_cost(M, layers, [d] * len(layers), ms)["total"]
            errs.append(abs(pred / t - 1))
    assert np.mean(errs) < 0.10, f"mean error {np.mean(errs):.1%}"
    assert np.max(errs) < 0.25, f"max error {np.max(errs):.1%}"
