"""Plan-aware tracing & attribution (core.trace, plan.attribution_report).

Single-device half here: StepTrace schema round-trip, Chrome-trace export,
annotation wrappers (identity on values, layer-qualified region names in
the compiled HLO, no host span), spans closed when device arrays are
ready, the attribution join against a compile_plan'd prediction, and the timing
helpers' new sample-returning surface.  The 4-device segmented-profiler
acceptance (every layer attributed, sums vs whole step, annotations in
compiled HLO) lives in tests/dist_checks.py group 'trace'.
"""
import json
import math
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import host_events, run_dist_group
from repro.core import trace as trace_lib
from repro.core.distribution import Dist
from repro.core.perfmodel import TPU_V5E
from repro.core.plan import PlanError, compile_plan
from repro.core.trace import StepTrace, format_attribution
from repro.models.cnn import meshnet
from repro.utils import interleaved_samples, percentile, time_fn

MS22 = {"data": 2, "model": 2}


def _trace(layers, fwd=1e-3, bwd=2e-3):
    rows = {n: {"fwd_s": fwd, "bwd_s": bwd, "fwd_bwd_s": fwd + bwd}
            for n in layers}
    step = {"fwd_s": fwd * len(layers), "bwd_s": bwd * len(layers),
            "fwd_bwd_s": (fwd + bwd) * len(layers)}
    return StepTrace(layers=rows, step=step, meta={"backend": "test"})


# ------------------------------------------------------------ StepTrace --
def test_steptrace_roundtrip(tmp_path):
    t = _trace(["conv1_1", "pred"])
    assert StepTrace.from_dict(t.to_dict()).to_dict() == t.to_dict()
    p = tmp_path / "trace.json"
    t.save(str(p))
    t2 = StepTrace.load(str(p))
    assert t2.layers == t.layers and t2.step == t.step
    assert t2.schema == trace_lib.SCHEMA


def test_steptrace_rejects_wrong_schema():
    with pytest.raises(ValueError, match="not a step trace"):
        StepTrace.from_dict({"schema": "something/else@9", "layers": {},
                             "step": {}})


def test_steptrace_sums():
    t = _trace(["a", "b", "c"], fwd=1.0, bwd=3.0)
    assert t.layer_fwd_sum_s == pytest.approx(3.0)
    assert t.layer_bwd_sum_s == pytest.approx(9.0)
    assert t.layer_sum_s == pytest.approx(12.0)


def test_chrome_trace_export(tmp_path):
    t = _trace(["conv1_1", "conv2_1", "pred"])
    ct = t.chrome_trace()
    assert "traceEvents" in ct and ct["displayTimeUnit"] == "ms"
    xs = [e for e in ct["traceEvents"] if e["ph"] == "X"]
    # one fwd + one bwd slice per layer, all with non-negative ts/dur
    assert len(xs) == 2 * len(t.layers)
    assert all(e["ts"] >= 0 and e["dur"] >= 0 for e in xs)
    fwd = [e for e in xs if e["cat"] == "fwd"]
    bwd = [e for e in xs if e["cat"] == "bwd"]
    assert [e["name"] for e in fwd] == ["conv1_1", "conv2_1", "pred"]
    assert [e["name"] for e in bwd] == ["pred", "conv2_1", "conv1_1"]
    # the export is valid JSON on disk
    p = tmp_path / "trace.chrome.json"
    t.save_chrome(str(p))
    with open(p) as f:
        assert json.load(f)["traceEvents"]


# ----------------------------------------------------------- annotation --
def test_annotate_identity_on_values():
    def f(x):
        with trace_lib.layer_context("conv9_9"):
            with trace_lib.annotate("halo_exchange"):
                return x * 2 + 1

    x = jnp.arange(6.0)
    np.testing.assert_array_equal(np.asarray(f(x)), np.asarray(x * 2 + 1))
    np.testing.assert_array_equal(np.asarray(jax.jit(f)(x)),
                                  np.asarray(x * 2 + 1))
    g = jax.grad(lambda x: jnp.sum(f(x)))(x)
    np.testing.assert_array_equal(np.asarray(g), np.full(6, 2.0))


def test_layer_context_qualifies_regions():
    """A region traced inside a layer reads <layer>/<region> in the
    compiled HLO's op_name, nested layers one path segment each."""
    def f(x):
        with trace_lib.layer_context("conv2_1"):
            with trace_lib.annotate("reshard"):
                y = jnp.sin(x)
            with trace_lib.layer_context("inner"):
                with trace_lib.annotate("halo_exchange"):
                    return jnp.cos(y)

    txt = jax.jit(f).lower(jnp.arange(4.0)).compile().as_text()
    assert "conv2_1/reshard/sin" in txt
    assert "conv2_1/inner/halo_exchange/cos" in txt


def test_annotate_opens_no_host_span(tmp_path):
    """annotate acts while the step is traced: under a profiler session
    neither tracing nor running the step leaves a host event named by the
    region, and the region still lands in the compiled HLO's op_name."""
    def f(x):
        with trace_lib.layer_context("conv7_7"):
            with trace_lib.annotate("bn_collective"):
                return jnp.tanh(x) * 3

    x = jnp.arange(8.0)
    jax.profiler.start_trace(str(tmp_path))
    try:
        step = jax.jit(f)
        txt = step.lower(x).compile().as_text()
        step(x).block_until_ready()
    finally:
        jax.profiler.stop_trace()
    assert "conv7_7/bn_collective/tanh" in txt
    names = {e[0] for e in host_events(str(tmp_path))}
    assert not {n for n in names if "bn_collective" in n}, names


class _Span:
    def __init__(self):
        self.closed = threading.Event()
        self.thread = None

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.thread = threading.current_thread().name
        self.closed.set()


class _Pending:
    """A leaf that becomes ready when `done` is set."""

    def __init__(self, fail=False):
        self.done, self.fail = threading.Event(), fail

    def block_until_ready(self):
        assert self.done.wait(30)
        if self.fail:
            raise RuntimeError("transfer failed")
        return self


def test_span_until_ready_closes_when_the_arrays_are(monkeypatch):
    spans = []
    monkeypatch.setattr(jax.profiler, "TraceAnnotation",
                        lambda name: spans.append(_Span()) or spans[-1])
    leaf = _Pending()
    out = trace_lib.span_until_ready("train.h2d", lambda b: {"x": b}, leaf)
    assert out == {"x": leaf}              # the caller never waits
    (span,) = spans
    assert not span.closed.wait(0.2)
    leaf.done.set()
    assert span.closed.wait(30)
    assert span.thread == "repro-trace-ready"


def test_span_until_ready_closes_on_failure(monkeypatch):
    """A failed wait, and a placement that raises, both close the span."""
    spans = []
    monkeypatch.setattr(jax.profiler, "TraceAnnotation",
                        lambda name: spans.append(_Span()) or spans[-1])
    leaf = _Pending(fail=True)
    leaf.done.set()
    trace_lib.span_until_ready("train.h2d", lambda b: b, leaf)
    assert spans[0].closed.wait(30)

    def refuse(b):
        raise ValueError("not divisible")

    with pytest.raises(ValueError, match="not divisible"):
        trace_lib.span_until_ready("train.h2d", refuse, leaf)
    assert spans[1].closed.is_set()
    # the waiter outlives the failure and serves the next span
    ok = _Pending()
    ok.done.set()
    trace_lib.span_until_ready("train.h2d", lambda b: b, ok)
    assert spans[2].closed.wait(30)


def test_span_until_ready_keeps_one_waiter_thread():
    xs = [jnp.full((4,), float(i)) for i in range(20)]
    for x in xs:
        trace_lib.span_until_ready("train.h2d", jax.device_put, x)
    waiters = [t for t in threading.enumerate()
               if t.name == "repro-trace-ready"]
    assert len(waiters) == 1 and waiters[0].daemon


def test_layer_names_in_compiled_hlo():
    """layer_context names survive into the compiled HLO op_name metadata
    (single device; the distributed variant is dist_checks 'trace')."""
    cfg = meshnet.MeshNetConfig("t", input_hw=16, in_channels=4,
                                convs_per_block=1, widths=(8,))
    params = meshnet.init(jax.random.PRNGKey(0), cfg)
    x = jnp.ones((1, 16, 16, 4))
    txt = jax.jit(lambda p, x: meshnet.apply(p, x, cfg)) \
        .lower(params, x).compile().as_text()
    for name in meshnet.layer_names(cfg):
        assert name in txt, f"{name!r} missing from compiled HLO"


# ---------------------------------------------------------- attribution --
def _compiled_plan():
    cfg = meshnet.MeshNetConfig("t", input_hw=32, in_channels=4,
                                convs_per_block=1, widths=(8, 16))
    specs = meshnet.layer_specs(cfg, 4)
    hybrid = Dist("hybrid", {"N": ("data",), "H": ("model",)})
    sample = Dist("sample", {"N": ("data", "model")})
    plan = compile_plan({"conv1_1": hybrid, "conv2_1": sample,
                         "pred": hybrid}, specs, MS22, machine=TPU_V5E)
    return plan, [l.name for l in specs]


def test_attribution_covers_every_layer():
    plan, names = _compiled_plan()
    assert set(plan.predicted["layer_costs"]) == set(names)
    rep = plan.attribution_report(_trace(names))
    assert set(rep["per_layer"]) == set(names)
    assert rep["schema"] == "repro/attribution@1"
    for r in rep["per_layer"].values():
        assert r["predicted_fwd_s"] > 0
        assert r["measured_fwd_s"] == pytest.approx(1e-3)
        assert isinstance(r["flagged"], bool)
    # the report is json-clean as-is (no numpy scalars)
    json.dumps(rep)
    # per-term drift names a worst term from the emitted set
    assert rep["worst_term"] in rep["terms"]
    for t in rep["terms"].values():
        assert t["drift"] > 0 and math.isfinite(t["drift"])
    # the plan charges its two reshard points to the receiving layers
    shuf = plan.predicted["shuffle_per_layer"]
    assert shuf["conv1_1"] == 0.0
    assert shuf["conv2_1"] > 0 and shuf["pred"] > 0


def test_attribution_flags_drifting_layers():
    plan, names = _compiled_plan()
    pred_total = {n: plan.predicted["layer_costs"][n].total for n in names}
    # measured 100x the prediction everywhere -> every layer flagged
    t = _trace(names, fwd=100 * max(pred_total.values()), bwd=0.0)
    rep = plan.attribution_report(t, tol=5.0)
    assert rep["flagged"] == names
    assert rep["totals"]["ratio"] > 5.0
    out = format_attribution(rep)
    assert "<-- drift" in out and "worst:" in out


def test_attribution_requires_predictions_and_full_trace():
    plan, names = _compiled_plan()
    import dataclasses
    bare = dataclasses.replace(plan, predicted=None)
    with pytest.raises(PlanError, match="machine"):
        bare.attribution_report(_trace(names))
    with pytest.raises(PlanError, match="no measurement"):
        plan.attribution_report(_trace(names[:-1]))


# -------------------------------------------------------------- timing --
def test_time_fn_return_samples():
    est = time_fn(lambda: jnp.zeros(4), reps=2, warmup=1)
    est2, samples = time_fn(lambda: jnp.zeros(4), reps=3, warmup=1,
                            return_samples=True)
    assert est > 0 and est2 > 0
    assert len(samples) == 3 and all(s > 0 for s in samples)


def test_interleaved_samples_and_percentile():
    fns = {"a": lambda: jnp.zeros(2), "b": lambda: jnp.zeros(2)}
    for f in fns.values():
        f()
    samples = interleaved_samples(fns, reps=2, rounds=3)
    assert set(samples) == {"a", "b"}
    assert all(len(s) == 3 for s in samples.values())
    assert percentile([1.0, 2.0, 3.0, 4.0], 50) == pytest.approx(2.5)
    assert percentile([1.0, 2.0, 3.0, 4.0], 95) == pytest.approx(3.85)
    assert math.isnan(percentile([], 50))


# ------------------------------------------------------------ 4-device --
def test_trace_distributed():
    """4-device segmented profiler acceptance: every solved-plan layer
    attributed with measured fwd+bwd, per-layer sums within tolerance of
    the whole fused step, attribution join complete, annotations present
    in the compiled HLO (dist_checks group 'trace'; fast — run by the CI
    fast lane like 'cf')."""
    run_dist_group("trace")
