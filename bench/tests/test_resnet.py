"""ResNet-50 added as files: the model module against the program at a size
where W-pair packing runs, its weights against the program's, its
convolutions against the program's layer graph and a hand count, the tiny
cell through the harness with each planted break, and the reader of
`conv1x1_roofline` on hand-made traces."""
import os
import subprocess
import sys
import time

import pytest

from benchtest import BENCH, FAKE_PEAKS, ROOT, TINY_LIMITS, dump, load, \
    tiny_root

import cells
import check
import devtrace
import flops
import harness
from test_bench_run import answer_altered, half_batch, state_unchanged

CONFIG = load(os.path.join(BENCH, "configs", "resnet50.json"))
CELL = "resnet50.1chip.b64"
# the program's smoke sizes (repro.configs.resnet50.SMOKE)
SMOKE = dict(input_hw=32, in_channels=3, n_classes=10, stages=[1, 1, 1, 1],
             widths=[4, 8, 16, 16])
# stage 2 at 64 channels and an even W: its BN and 3x3 convs run packed
PACKED = dict(input_hw=32, in_channels=3, n_classes=1000,
              stages=[2, 1, 1, 1], widths=[64, 8, 16, 16])


def module():
    return cells.model_of(CONFIG)


def sized(**sizes):
    return dict(CONFIG, **sizes)


def program_config(cfg):
    from repro.models.cnn import resnet
    return resnet.ResNetConfig(
        name="test", input_hw=cfg["input_hw"], in_channels=cfg["in_channels"],
        n_classes=cfg["n_classes"], stages=tuple(cfg["stages"]),
        widths=tuple(cfg["widths"]))


@pytest.mark.parametrize("sizes", [SMOKE, PACKED], ids=["smoke", "packed"])
def test_init_gives_the_programs_leaves_exactly(sizes):
    import jax
    import numpy as np
    from repro.models.cnn import resnet
    cfg = sized(**sizes)
    seed = 2147483659
    got = harness.reference.leaves_host(module().init(seed, cfg))
    want = harness.reference.leaves_host(
        resnet.init(jax.random.PRNGKey(seed), program_config(cfg)))
    assert list(got) == list(want)
    for k in want:
        np.testing.assert_array_equal(got[k], want[k], err_msg=k)


def readings(cfg, fault=None, seed=5, n=4):
    """The program's (uniform plan on a 1x1 mesh, the trainer's FP32
    policy) and the reference's first loss and leaf gradient norms on one
    batch, and the program's compiled HLO."""
    import functools

    import jax
    from repro.core.plan import NetworkPlan
    from repro.core.spatial_conv import ConvSharding
    from repro.launch.mesh import make_mesh
    from repro.models.cnn import resnet
    from repro.utils import FP32

    import reference
    import traffic

    mod = module()
    batch = jax.tree.map(jax.numpy.asarray, traffic.batch_pool(
        cfg, {"batch": n, "pool": 1}, seed)[0])
    params = mod.init(seed, cfg)
    pcfg = program_config(cfg)
    mesh = make_mesh(data=1, model=1)
    plan = NetworkPlan.uniform(
        ConvSharding(batch_axes=("data",), h_axis="model"),
        [l.name for l in resnet.layer_specs(n, pcfg)])
    prog = jax.jit(jax.value_and_grad(functools.partial(
        resnet.loss_fn, cfg=pcfg, plan=plan, mesh=mesh)))
    with FP32.scope():
        lowered = prog.lower(params, batch)
        loss, grads = prog(params, batch)
    ref = jax.jit(jax.value_and_grad(mod.loss(
        cfg, jax.lax.Precision.HIGHEST, fault, 1)))
    ref_loss, ref_grads = ref(params, batch)

    def norms(tree):
        import numpy as np
        return {k: float(np.linalg.norm(v))
                for k, v in reference.leaves_host(tree).items()}
    return ({"loss": float(loss), "grad": norms(grads)},
            {"loss": float(ref_loss), "grad": norms(ref_grads)},
            lowered.compile().as_text())


# The program's BN takes the one-pass variance (E[x^2] - E[x]^2) and the
# reference the two-pass one, and the packed path sums each channel's
# statistics in another order: float32 rounding.  Over five seeds the first
# loss read at most 1.4e-6 and the worst leaf's gradient norm (a BN leaf)
# at most 7.7e-5; the limits leave room above that, and a half batch reads
# 2.6e-2 and 0.43.
LOSS_TOL, GRAD_TOL = 1e-5, 2e-4


def compared(prog, ref):
    return (abs(prog["loss"] - ref["loss"]) / abs(ref["loss"]),
            check.leaf_gap(prog["grad"], ref["grad"]))


def test_reference_matches_the_packed_program():
    prog, ref, hlo = readings(sized(**PACKED))
    assert "conv_wpack" in hlo      # the packed path is the one compared
    loss_gap, grad_gap = compared(prog, ref)
    assert loss_gap < LOSS_TOL and grad_gap < GRAD_TOL, (loss_gap, grad_gap)


def test_a_half_batch_reference_fails_the_comparison():
    prog, ref, _ = readings(sized(**PACKED), fault="half_batch")
    loss_gap, grad_gap = compared(prog, ref)
    assert loss_gap > LOSS_TOL and grad_gap > GRAD_TOL, (loss_gap, grad_gap)


def test_no_halo_splits_rows_and_is_a_no_op_at_one_part():
    import jax
    import jax.numpy as jnp
    cfg = sized(**SMOKE)
    mod = module()
    params = mod.init(3, cfg)
    batch = {"image": jax.random.normal(jax.random.PRNGKey(1),
                                        (2, 32, 32, 3)),
             "label": jnp.array([1, 7], jnp.int32)}
    hi = jax.lax.Precision.HIGHEST
    whole = mod.loss(cfg, hi)(params, batch)
    assert mod.loss(cfg, hi, "no_halo", 1)(params, batch) == whole
    assert abs(mod.loss(cfg, hi, "no_halo", 2)(params, batch) - whole) \
        > 1e-4 * abs(whole)


def test_forward_flops_hand_count():
    """8,178,368,512 forward FLOPs a sample: the stem 236,027,904; per
    stage the bottlenecks (three convs of 2*56^2*64*64*{1, 9, 4} and the
    like), the four 1x1 projections; the head 2*2048*1000."""
    assert len(flops.convs(CONFIG)) == 54
    assert flops.forward_flops(CONFIG, 1) == 8_178_368_512
    stem = flops.convs(CONFIG)[0]
    assert stem.fwd_flops(1) == 2 * 112 * 112 * 49 * 3 * 64 == 236_027_904
    assert flops.convs(CONFIG)[-1].fwd_flops(1) == 2 * 2048 * 1000
    assert flops.step_flops(CONFIG, 64) == 1_555_140_968_448


def test_conv_names_are_the_programs_graph_nodes_plus_fc():
    from repro.models.cnn import resnet
    graph = resnet.resnet_graph(1, program_config(CONFIG))
    convs = flops.convs(CONFIG)
    assert {c.name for c in convs} == (set(graph.nodes) - {"pool1"}) | {"fc"}
    for c in convs[:-1]:
        layer = graph.nodes[c.name]["layer"]
        assert (c.c, c.f, c.k, c.s, c.h_in) == (layer.c, layer.f, layer.k,
                                                layer.s, layer.h), c.name


def test_importing_the_model_touches_no_jax():
    code = (f"import sys; sys.path.insert(0, {BENCH!r}); "
            "import models.resnet; print(sorted(m for m in sys.modules "
            "if m.split('.')[0] in ('jax', 'jaxlib', 'libtpu')))")
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=120)
    assert r.returncode == 0, r.stderr
    assert r.stdout.strip() == "[]"


# ---------------------------------------------------------------- cell --

@pytest.fixture(scope="module")
def root(tmp_path_factory):
    """A checkout root with the ResNet cell's files at the program's smoke
    sizes, as the cell `tinyres`."""
    root = tiny_root(tmp_path_factory.mktemp("root"), name="tinyres")
    dump(sized(**SMOKE, name="tinyres"),
         os.path.join(root, "bench", "configs", "tinyres.json"))
    traffic = load(os.path.join(BENCH, "traffic", f"{CELL}.json"))
    traffic.update(batch=4, limits=dict(TINY_LIMITS))
    dump(traffic, os.path.join(root, "bench", "traffic", "tinyres.json"))
    return root


def run_tiny(root, plant=None):
    import jax
    cell = cells.resolve("tinyres", root)
    return harness.run_cell(cell, 2147483659, 0.5, False, time.time(),
                            jax.devices()[:1], FAKE_PEAKS, plant=plant)


def test_the_cell_at_smoke_size_runs_correct(root):
    cell = cells.resolve("tinyres", root)
    assert "--smoke" in harness.train_argv(cell, 1)
    res = run_tiny(root)
    assert res["correct"], res["check"]
    assert res["failed"] == 0 and res["attempted"] > 0


@pytest.mark.parametrize("plant", [state_unchanged, half_batch,
                                   answer_altered])
def test_the_cell_with_a_broken_step_is_not_correct(root, plant):
    res = run_tiny(root, plant=plant)
    assert not res["correct"]
    assert any(c["value"] > c["limit"] for c in res["check"].values())


# ------------------------------------------------------ the 1x1 reader --

PEAKS = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}
ONE = "jit(step)/jvp(res2a_branch2a)/conv_general_dilated"
BACK = "jit(step)/transpose(jvp(res3a_branch1))/conv_general_dilated"
LAST = "jit(step)/jvp(res5c_branch2c)/conv_general_dilated"
THREE = "jit(step)/jvp(res2a_branch2b)/conv_wpack/conv_general_dilated"
HEAD = "jit(step)/jvp(fc)/dot_general"
# The least time of the body's 36 1x1 convs in a step of 64 images, counted
# by hand: every one is bound by bandwidth at four-byte words (16-181 FLOP a
# byte, against v5e's ridge of 240), so it is their step bytes over 819 GB/s.
LEAST_1X1_S = 16.622e-3


def trace(ops, window=(0, 1000)):
    """One device's ops [(start, end, category, op_name)] inside one
    harness step spanning `window`."""
    events = [[f"op.{i}", s, e - s] for i, (s, e, _, _) in enumerate(ops)]
    hlo = {f"op.{i}": [cat, name] for i, (_, _, cat, name) in enumerate(ops)}
    s, e = window
    return {"planes": [
        {"name": "/device:TPU:0", "lines": [{"name": "XLA Ops",
                                             "events": events}]},
        {"name": "/host:CPU", "lines": [{"name": "main", "events": [
            ["bench.step", s, e - s]]}]}],
        "hlo": hlo}


def read(raw):
    cell = cells.resolve(CELL)
    ctx = harness.ReaderContext(devtrace.Trace(raw), cell.config,
                                cell.traffic, 1, PEAKS, word=4,
                                counters={"window_compiles": 0})
    return cells.load_reducer(cell, "conv1x1_roofline")(ctx)


def test_1x1_roofline_reads_the_1x1_scopes_alone():
    raw = trace([(0, 100, "convolution", ONE),
                 (100, 200, "convolution", BACK),
                 (150, 250, "convolution", LAST),     # overlaps: union
                 (300, 600, "convolution", THREE),
                 (600, 700, "convolution", HEAD),
                 (700, 900, "loop fusion", ONE)])
    assert read(raw) == pytest.approx(100 * LEAST_1X1_S / 250e-9, rel=1e-4)


def test_1x1_roofline_hand_value():
    """A step whose 1x1 convs take exactly their least time reads 100%."""
    ones = [c for c in flops.convs(CONFIG) if c.k == 1 and c.name != "fc"]
    assert len(ones) == 36
    least_ns = round(LEAST_1X1_S * 1e9)
    raw = trace([(0, least_ns, "convolution", ONE)], window=(0, 2 * least_ns))
    assert read(raw) == pytest.approx(100.0, rel=1e-4)


@pytest.mark.parametrize("ops", [[], [(0, 10, "convolution", THREE)],
                                 [(0, 10, "convolution", HEAD)],
                                 [(0, 10, "loop fusion", ONE)]])
def test_1x1_roofline_reads_nothing_without_1x1_convs(ops):
    assert read(trace(ops)) is None


def test_a_mesh_trace_reads_nothing():
    """The chip trace of a mesh cell has no ResNet scope."""
    fixture = os.path.join(BENCH, "tests", "fixtures",
                           "mesh1k.1chip.b8.trace_head.json.gz")
    assert read(devtrace.load(fixture)) is None


def test_the_cell_reports_the_1x1_roofline():
    b = load(os.path.join(ROOT, "BENCHMARK.json"))
    (entry,) = [c for c in b["configs"] if c["name"] == "resnet50"]
    assert entry["reduced"] == [] and entry["source"] == CONFIG["source"]
    (m,) = [m for m in b["per_layer"] if m["name"] == "conv1x1_roofline"]
    assert m["workloads"] == [CELL] and m["unit"] == "%"
    cell = cells.resolve(CELL)
    assert cell.chips == 1 and cell.traffic["batch"] == 64
    assert "conv1x1_roofline" in {m["name"] for m in cell.per_layer}
