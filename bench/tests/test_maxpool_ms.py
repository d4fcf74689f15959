"""maxpool_ms, the max pool's device time a step: on hand-made traces with
pool and other ops on two devices, and on the chip trace of a program
without the pool."""
import os

import pytest

from benchtest import BENCH, ROOT, load

import cells
import devtrace
import harness

CELL = "resnet50.1chip.b64"
PEAKS = load(os.path.join(BENCH, "peaks.json"))["TPU v5 lite"]
FIXTURE = os.path.join(BENCH, "tests", "fixtures",
                       "mesh1k.1chip.b8.trace_head.json.gz")
FWD = "jit(step)/jvp(pool1)/max_pool/reduce_window_max"
BWD = "jit(step)/transpose(jvp(pool1))/max_pool/pad"
HALO = "jit(step)/jvp(pool1)/max_pool/halo_exchange/ppermute"
CONV = "jit(step)/jvp(conv1)/conv_general_dilated"
STEM_MAX = "jit(step)/jvp(conv1)/max"


def trace(devices, steps=((0, 1000),)):
    """Device n's ops [(start, end, op_name)] = devices[n], inside harness
    steps spanning each of `steps` (ns)."""
    planes, hlo = [], {}
    for d, ops in enumerate(devices):
        events = []
        for i, (s, e, name) in enumerate(ops):
            events.append([f"op.{d}.{i}", s, e - s])
            hlo[f"op.{d}.{i}"] = ["loop fusion", name]
        planes.append({"name": f"/device:TPU:{d}", "lines": [
            {"name": "XLA Ops", "events": events}]})
    planes.append({"name": "/host:CPU", "lines": [{"name": "main", "events": [
        ["bench.step", s, e - s] for s, e in steps]}]})
    return {"planes": planes, "hlo": hlo}


def read(raw):
    cell = cells.resolve(CELL)
    ctx = harness.ReaderContext(devtrace.Trace(raw), cell.config,
                                cell.traffic, cell.chips, PEAKS, word=4,
                                counters={"window_compiles": 0})
    return cells.load_reducer(cell, "maxpool_ms")(ctx)


def test_pool_time_a_step_is_the_union_of_its_ops():
    raw = trace([[(0, 100_000, FWD),
                  (50_000, 150_000, HALO),          # overlaps: union
                  (200_000, 500_000, CONV),
                  (500_000, 700_000, BWD),
                  (700_000, 800_000, STEM_MAX)]],
                steps=((0, 500_000), (500_000, 1_000_000)))
    # 150 us forward + 200 us backward over 2 steps
    assert read(raw) == pytest.approx(0.175)


def test_devices_are_averaged_and_the_window_clips():
    raw = trace([[(0, 400_000, FWD)],
                 [(0, 200_000, BWD), (1_500_000, 1_900_000, FWD)]],
                steps=((100_000, 1_100_000),))
    # device 0: 300 us in the window; device 1: 100 us (its late op is out)
    assert read(raw) == pytest.approx((0.3 + 0.1) / 2)


@pytest.mark.parametrize("ops", [
    [], [(0, 10, CONV)], [(0, 10, STEM_MAX)],
    [(0, 10, "jit(step)/jvp(pool1)/reduce_window_max")],
    [(0, 10, "jit(step)/jvp(max_pooling)/max")],
])
def test_no_max_pool_scope_reads_nothing(ops):
    assert read(trace([ops])) is None


def test_a_program_without_the_scope_reads_nothing():
    """The chip trace of cell 1, whose network has no pool."""
    assert read(devtrace.load(FIXTURE)) is None


def test_the_cell_reports_the_pool_time():
    b = load(os.path.join(ROOT, "BENCHMARK.json"))
    (m,) = [m for m in b["per_layer"] if m["name"] == "maxpool_ms"]
    assert m["workloads"] == [CELL] and m["moves"] == "samples_per_s"
    assert "maxpool_ms" in {m["name"] for m in cells.resolve(CELL).per_layer}
