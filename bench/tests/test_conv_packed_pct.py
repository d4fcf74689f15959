"""conv_packed_pct, the share of convolution device time in W-pair packed
convolutions: on hand-made traces with packed and plain convolutions, and
on the chip trace of a program without packing."""
import os

import pytest

from benchtest import BENCH, load

import cells
import devtrace
import harness

PEAKS = load(os.path.join(BENCH, "peaks.json"))["TPU v5 lite"]
FIXTURE = os.path.join(BENCH, "tests", "fixtures",
                       "mesh1k.1chip.b8.trace_head.json.gz")
PACKED = "jit(step)/jvp(conv1_2)/conv_interior/conv_wpack/conv_general_dilated"
PLAIN = "jit(step)/jvp(conv2_2)/conv_interior/conv_general_dilated"


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
    cell = cells.resolve("mesh1k.1chip.b8")
    ctx = harness.ReaderContext(devtrace.Trace(raw), cell.config,
                                cell.traffic, 1, PEAKS, word=4,
                                counters={"window_compiles": 0})
    return cells.load_reducer(cell, "conv_packed_pct")(ctx)


def test_share_of_conv_time_in_packed_convs():
    raw = trace([(0, 30, "convolution", PACKED),
                 (40, 100, "convolution", PLAIN),
                 (100, 400, "loop fusion", PACKED)])
    # 30 ns packed of 90 ns of convolution; the fusion is not a conv
    assert read(raw) == pytest.approx(100 * 30 / 90)


def test_only_the_window_counts():
    raw = trace([(0, 50, "convolution", PACKED),
                 (50, 100, "convolution", PLAIN)], window=(25, 100))
    assert read(raw) == pytest.approx(100 * 25 / 75)


@pytest.mark.parametrize("ops,want", [
    ([(0, 10, "convolution", PLAIN)], 0.0),
    ([(0, 10, "loop fusion", PLAIN)], None),
    ([], None),
])
def test_plain_convs_read_zero_and_no_convs_nothing(ops, want):
    assert read(trace(ops)) == want


def test_a_program_without_packing_reads_zero():
    """The chip trace of cell 1 before W pairing existed."""
    assert read(devtrace.load(FIXTURE)) == 0.0
