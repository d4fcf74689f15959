"""The yardstick: FLOP counts against the hand counts, the trace readers
on a trace recorded on a TPU v5e and on hand-made traces, and the shares
of a peak that can never pass 100."""
import os

import pytest

from benchtest import BENCH, load

import cells
import devtrace
import flops
import harness

FIXTURE = os.path.join(BENCH, "tests", "fixtures",
                       "mesh1k.1chip.b8.trace_head.json.gz")
PEAKS = load(os.path.join(BENCH, "peaks.json"))["TPU v5 lite"]


def config(name):
    return load(os.path.join(BENCH, "configs", f"{name}.json"))


def traffic(name):
    return load(os.path.join(BENCH, "traffic", f"{name}.json"))


def hand_count(hw, per_block):
    """2 * Ho*Wo * 9*C*F per 3x3 conv, block by block, plus the 1x1 head."""
    total, c = 0, 18
    for f in (64, 128, 256, 512, 512, 512):
        hw //= 2
        total += 2 * hw * hw * 9 * (c * f + (per_block - 1) * f * f)
        c = f
    return total + 2 * hw * hw * 512


@pytest.mark.parametrize("name,hw,per_block,gflop", [
    ("mesh1k", 1024, 3, 207.15), ("mesh2k", 2048, 5, 1495.3)])
def test_forward_flops_match_the_hand_counts(name, hw, per_block, gflop):
    got = flops.forward_flops(config(name), 1)
    assert got == hand_count(hw, per_block)
    # the rounded hand counts (exactly 207.165 and 1495.455 GFLOP)
    assert got / 1e9 == pytest.approx(gflop, rel=2e-4)


def test_step_counts_no_input_gradient_for_the_image():
    cfg = config("mesh1k")
    first = flops.convs(cfg)[0]
    assert flops.step_flops(cfg, 8) == \
        3 * flops.forward_flops(cfg, 8) - first.fwd_flops(8)
    assert [c.name for c in flops.convs(cfg)][-1] == "pred"
    assert len(flops.convs(config("mesh2k"))) == 31


def ctx_for(raw, cfg="mesh1k", cell="mesh1k.1chip.b8", chips=1,
            peaks=PEAKS, compiles=0):
    return harness.ReaderContext(devtrace.Trace(raw), config(cfg),
                                 traffic(cell), chips, peaks, word=4,
                                 counters={"window_compiles": compiles})


def read(name, ctx):
    cell = cells.resolve("mesh1k.1chip.b8")
    return cells.load_reducer(cell, name)(ctx)


def test_readers_on_a_chip_trace():
    """Three steps of mesh1k.1chip.b8 traced on a TPU v5e, cut to the
    device's ops and the harness's spans."""
    raw = devtrace.load(FIXTURE)
    assert os.path.getsize(FIXTURE) < 1 << 20
    ctx = ctx_for(raw)
    assert ctx.trace.steps == 3 and list(ctx.trace.devices) == [0]
    idle = read("device_idle_pct", ctx)
    conv = read("conv_ms", ctx)
    roof = read("conv_roofline", ctx)
    mfu = read("busy_mfu", ctx)
    assert 5 < idle < 40
    assert 100 < conv < ctx.trace.window_ns / 1e6 / 3
    assert 0 < roof < 100 and 0 < mfu < 100
    # the device's share of peak while busy, times its busy share, is the
    # whole window's share
    window_mfu = 100.0 * flops.step_flops(ctx.config, 8) * 3 / (
        ctx.trace.window_ns / 1e9 * PEAKS["bf16_flops"])
    assert mfu * (1 - idle / 100) == pytest.approx(window_mfu, rel=1e-9)
    assert read("window_compiles", ctx) == 0
    # the 1x1 plan's permutes over a size-1 axis are collectives the trace
    # shows; they are read, not hidden
    assert read("collective_exposed_ms", ctx) >= 0
    b = harness.breakdown(ctx.trace)
    assert 0 < len(b["device_ops"]) <= 10 and 0 < len(b["idle_gaps"]) <= 10
    assert b["device_ops"][0][0].endswith("/convolution")
    assert all(label.startswith(("bench.", "host:"))
               for label, _ in b["idle_gaps"])


def synthetic(ops, steps):
    """A trace of `steps` back-to-back steps and one device whose ops are
    [(start, end, category)], on a hand-made HLO map."""
    events, hlo = [], {}
    for i, (s, e, cat) in enumerate(ops):
        name = f"op.{i}"
        events.append([name, s, e - s])
        hlo[name] = [cat, "jit(step)/conv1_1/x"]
    spans = []
    for s, e in steps:
        spans.append(["bench.step", s, e - s])
        spans.append(["bench.readback", s, e - s])
    return {"planes": [
        {"name": "/device:TPU:0", "lines": [{"name": "XLA Ops",
                                             "events": events}]},
        {"name": "/host:CPU", "lines": [{"name": "main", "events": spans}]}],
        "hlo": hlo}


def test_roofline_and_mfu_reach_100_and_no_further():
    cfg, tr = config("mesh1k"), traffic("mesh1k.1chip.b8")
    n = tr["batch"]
    t_min, bound = flops.roofline_seconds(
        flops.step_flops(cfg, n), flops.step_bytes(cfg, n, 4),
        PEAKS["bf16_flops"], PEAKS["hbm_bytes_per_s"])
    assert bound == "compute"
    t_step = flops.step_flops(cfg, n) / PEAKS["bf16_flops"]
    for stretch in (1.0, 1.5, 4.0):
        conv_ns = int(t_min * 1e9 * stretch)
        step_ns = int(t_step * 1e9 * stretch)
        raw = synthetic([(0, conv_ns, "convolution")], [(0, step_ns)])
        ctx = ctx_for(raw)
        assert read("conv_roofline", ctx) == \
            pytest.approx(100 / stretch, rel=1e-6)
        assert read("busy_mfu", ctx) == pytest.approx(100 / stretch,
                                                      rel=1e-6)
        run = {"times": [t_step * stretch], "wall": t_step * stretch,
               "failed": 0}
        e2e = harness.end_to_end(
            cells.resolve("mesh1k.1chip.b8"), run, 1.0, PEAKS, 1, 1)
        assert e2e["mfu"] == pytest.approx(100 / stretch, rel=1e-6)
        assert e2e["samples_per_s"] == pytest.approx(n / (t_step * stretch))


def test_collective_time_counts_only_what_no_compute_hides():
    raw = synthetic([(0, 100, "fusion"), (50, 300, "collective"),
                     (200, 250, "convolution")], [(0, 1000)])
    ctx = ctx_for(raw)
    # collective 50-300 minus compute 0-100 and 200-250: 50 + 100 ns
    assert read("collective_exposed_ms", ctx) == pytest.approx(150 / 1e6)
    assert read("device_idle_pct", ctx) == pytest.approx(70.0)
    assert read("conv_ms", ctx) == pytest.approx(50 / 1e6)
    quiet = ctx_for(synthetic([(0, 100, "fusion")], [(0, 1000)]))
    assert read("collective_exposed_ms", quiet) is None


def test_a_reader_with_nothing_to_read_returns_nothing():
    ctx = ctx_for(synthetic([], [(0, 1000)]))
    for name in ("conv_ms", "conv_roofline", "collective_exposed_ms",
                 "busy_mfu"):
        assert read(name, ctx) is None


def test_intervals():
    assert devtrace.union([(5, 7), (0, 2), (1, 3)]) == [(0, 3), (5, 7)]
    assert devtrace.minus([(0, 10)], [(2, 3), (5, 12)]) == [(0, 2), (3, 5)]
    assert devtrace.length([(0, 3), (5, 7)]) == 5


def test_trim_keeps_the_first_steps_and_their_hlo():
    raw = synthetic([(0, 10, "convolution"), (110, 120, "fusion")],
                    [(0, 100), (100, 200)])
    cut = devtrace.trim(raw, 1)
    tr = devtrace.Trace(cut)
    assert tr.steps == 1 and len(tr.devices[0]) == 1
    assert set(cut["hlo"]) == {"op.0"}


HLO = """\
HloModule jit_step, entry_computation_layout={...}

%fused_computation.3 (param_0: f32[8,16,16,64]) -> f32[8,16,16,64] {
  %param_0 = f32[8,16,16,64]{3,2,1,0:T(8,128)} parameter(0)
  ROOT %convolution.1 = f32[8,16,16,64]{3,2,1,0:T(8,128)} convolution(f32[8,16,16,64]{3,2,1,0:T(8,128)} %param_0, f32[3,3,64,64]{3,2,1,0} %w), window={size=3x3 pad=1_1x1_1}, dim_labels=b01f_01io->b01f, metadata={op_name="jit(step)/jvp(conv2_3)/conv_general_dilated"}
}

%fused_computation.4 (param_0.1: f32[64]) -> f32[64] {
  ROOT %add.2 = f32[64]{0} add(f32[64]{0} %param_0.1, f32[64]{0} %param_0.1)
}

ENTRY %main.9 (p: f32[8,16,16,64]) -> f32[8,16,16,64] {
  %fusion.7 = f32[8,16,16,64]{3,2,1,0:T(8,128)S(1)} fusion(f32[8,16,16,64]{3,2,1,0} %p), kind=kOutput, calls=%fused_computation.3
  %fusion.8 = f32[64]{0} fusion(f32[64]{0} %q), kind=kLoop, calls=%fused_computation.4, metadata={op_name="jit(step)/pred/add"}
  %collective-permute-start.2 = (f32[8,1,16,64]{3,2,1,0:T(8,128)}, f32[8,1,16,64]{3,2,1,0:T(8,128)}) collective-permute-start(f32[8,1,16,64]{3,2,1,0} %s), channel_id=1, source_target_pairs={{0,1}}
  ROOT %copy.1 = f32[8,16,16,64]{3,2,1,0} copy(f32[8,16,16,64]{3,2,1,0:T(8,128)S(1)} %fusion.7)
}
"""


def test_hlo_map_names_category_and_layer():
    m = devtrace.hlo_map(HLO)
    assert m["fusion.7"] == ["convolution",
                             "jit(step)/jvp(conv2_3)/conv_general_dilated"]
    assert m["fusion.8"] == ["loop fusion", "jit(step)/pred/add"]
    assert m["collective-permute-start.2"][0] == "collective"
    assert m["copy.1"][0] == "copy"
    assert devtrace.instruction(
        "%fusion.7 = f32[8]{0} fusion(f32[8]{0} %p), kind=kOutput") == \
        "fusion.7"
    op = devtrace.Op("fusion.7", 0, 1, *m["fusion.7"])
    assert op.is_conv and op.layer == "conv2_3"
