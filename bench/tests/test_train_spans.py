"""Three steps of mesh1k.1chip.b8 traced on a TPU v5e with the trainer's
own step (`launch.train.run_step`) inside the harness's `bench.step`: the
trainer's spans as the chip recorded them, the device's idle time split by
the input copy's span `train.h2d`, and the current readers reading the
trace as they read one of the harness's own step."""
import os

import pytest

from benchtest import BENCH, load

import cells
import devtrace
import harness

FIXTURE = os.path.join(BENCH, "tests", "fixtures",
                       "mesh1k.1chip.b8.train_spans.json.gz")
OLD = os.path.join(BENCH, "tests", "fixtures",
                   "mesh1k.1chip.b8.trace_head.json.gz")
PEAKS = load(os.path.join(BENCH, "peaks.json"))["TPU v5 lite"]
PARTS = ("train.step", "train.put", "train.dispatch", "train.readback")


def host_spans(raw):
    """[(name, start, end, line)] of the host planes' events, by start."""
    out = []
    for pl in raw["planes"]:
        if devtrace.DEVICE_PLANE.search(pl["name"]):
            continue
        for i, ln in enumerate(pl["lines"]):
            out += [(n, s, s + d, i) for n, s, d, *_ in ln["events"]]
    return sorted(out, key=lambda x: x[1])


@pytest.fixture(scope="module")
def raw():
    assert os.path.getsize(FIXTURE) < 1 << 20
    return devtrace.load(FIXTURE)


def read(name, raw):
    cell = cells.resolve("mesh1k.1chip.b8")
    ctx = harness.ReaderContext(
        devtrace.Trace(raw), cell.config, cell.traffic, 1, PEAKS, word=4,
        counters={"window_compiles": 0})
    return cells.load_reducer(cell, name)(ctx)


def test_each_step_holds_the_trainers_spans_in_order(raw):
    spans = host_spans(raw)
    steps = [x for x in spans if x[0] == "bench.step"]
    assert len(steps) == 3
    for _, s, e, line in steps:
        inside = [x for x in spans if s <= x[1] < e and x[0] != "bench.step"]
        parts = [x for x in inside if x[0] in PARTS]
        assert [x[0] for x in parts] == list(PARTS)
        assert all(x[3] == line for x in parts)
        assert all(a[2] <= b[1] for a, b in zip(parts[2:], parts[3:]))
        (h2d,) = [x for x in inside if x[0] == "train.h2d"]
        put = parts[1]
        assert put[1] <= h2d[1] <= put[2] and h2d[3] != line


def split(raw):
    """Per step, the device idle inside and outside `train.h2d`, ms."""
    tr = devtrace.Trace(raw)
    h2d = devtrace.union(tr.clip(
        (s, e) for n, s, e, _ in host_spans(raw) if n == "train.h2d"))
    idle = devtrace.minus([tr.window], tr.busy(0))
    outside = devtrace.length(devtrace.minus(idle, h2d))
    inside = devtrace.length(idle) - outside
    return inside / tr.steps / 1e6, outside / tr.steps / 1e6, tr


def test_the_device_waits_on_the_input_copy(raw):
    """The idle time splits into the wait on the copy and the rest, and
    the two sum to what device_idle_pct reads; the copy is most of it."""
    inside, outside, tr = split(raw)
    idle_ms = read("device_idle_pct", raw) / 100 * tr.window_ns / 1e6 / 3
    assert inside + outside == pytest.approx(idle_ms, rel=1e-9)
    assert 50 < inside < 75 and 0 < outside < 5
    # one long gap a step, which ends inside a train.h2d span and lies in
    # it but for the few ms the host takes from read-back to the next put
    h2d = [(s, e) for n, s, e, _ in host_spans(raw) if n == "train.h2d"]
    long = [g for g in devtrace.minus([tr.window], tr.busy(0))
            if g[1] - g[0] > 10e6]
    assert len(long) == 3
    for a, b in long:
        ((s, e),) = [(s, e) for s, e in h2d if s < b <= e]
        assert 0 <= s - a < 5e6


def test_the_copy_span_closes_when_the_step_starts(raw):
    """train.h2d closes after the copy is done, so after the step's first
    device op starts, and within 2 ms of it: the waiter thread was not
    starved."""
    tr = devtrace.Trace(raw)
    starts = sorted(o.start for o in tr.devices[0])
    for n, s, e, _ in host_spans(raw):
        if n == "train.h2d":
            first = next(t for t in starts if t >= s)
            assert 0 <= e - first < 2e6


def test_the_device_work_is_the_harness_steps(raw):
    """The same compiled step, whichever loop calls it: the readers give
    the device the same work per step as on the harness's own step."""
    old = devtrace.load(OLD)
    assert read("conv_ms", raw) == pytest.approx(read("conv_ms", old),
                                                 rel=1e-3)
    assert read("busy_mfu", raw) == pytest.approx(read("busy_mfu", old),
                                                  rel=1e-3)
    assert 0 < read("conv_roofline", raw) < 100
    assert 5 < read("device_idle_pct", raw) < 40
    assert read("collective_exposed_ms", raw) >= 0
    assert read("window_compiles", raw) == 0
