"""Plan-aware tracing & attribution (the observability half of the §V loop).

The perf model *prices* every §III distribution; this module makes the
runtime *attribute* where a measured step actually spends its time, so the
model-vs-measured comparison decomposes per layer and per cost term instead
of being one opaque end-to-end ratio.

Three mechanisms:

  * **Named-region annotation** — `annotate(region)` wraps a stretch of
    traced code in ``jax.named_scope``, so the name lands in the compiled
    HLO's ``op_name`` metadata and device profiles and
    `compiled.as_text()` decode to plan terms.  `layer_context(name)`
    opens the layer's own scope around it, so every region inside an
    execution path is keyed by the layer that ran it
    (``conv3_1/halo_exchange``) — the paths thread it through halo
    exchange (core.halo), interior/boundary conv (core.spatial_conv), the
    CF collectives and BN psums (core.channel_conv) and §III-C reshard
    points (core.plan).  Both act while the step is traced, never while it
    runs, and are identity on values: they change metadata only.

  * **Spans that close when the device is ready** — `span_until_ready(
    name, fn, *args)` opens a ``jax.profiler.TraceAnnotation`` on the
    caller's thread, runs `fn`, and leaves the span to one daemon thread
    that closes it once every array `fn` returned is ready on its device.
    The trainer's step (launch.train.run_step) times its host->device
    input copy (``train.h2d``) this way, on the profiler's clock beside the
    device planes, without blocking the step loop.

  * **Segmented re-execution profiling** — `trace_plan(plan, params,
    batch)` AOT-compiles each plan layer's forward and forward+backward
    in isolation (the real per-layer callables from
    models.cnn.meshnet.layer_fns, fed the real intermediate activations
    captured from one forward pass, each under its plan sharding) and
    times them with the repo's interleaved-rounds discipline
    (utils.interleaved_min — the same estimator benchmarks/strategy_exec
    uses), producing a `StepTrace` of measured per-layer fwd/bwd seconds
    next to the whole-step time, with JSON round-trip and Chrome-trace
    export (load the file in chrome://tracing or Perfetto).

`NetworkPlan.attribution_report(trace)` (core.plan) joins a StepTrace
against the `layer_cost`/`layer_memory` predictions into the per-layer
predicted-vs-measured table; `format_attribution` renders it.
"""
from __future__ import annotations

import contextlib
import dataclasses
import json
import logging
import queue
import threading
from typing import Mapping

import jax

SCHEMA = "repro/step_trace@1"

log = logging.getLogger(__name__)

# ---------------------------------------------------------------------------
# named-region annotation
# ---------------------------------------------------------------------------

#: Canonical region names every execution path annotates with — the closed
#: vocabulary the static collective auditor (repro.analysis.collectives)
#: keys its jaxpr/StableHLO attribution on.  Adding a region to an
#: execution path means adding it here, or the auditor cannot attribute
#: its collectives to a cost term.
REGIONS = (
    "halo_exchange",      # spatial ppermute halos (core.halo)
    "conv_interior",      # overlap-pinned interior conv (core.spatial_conv)
    "conv_boundary",      # boundary strips after the halo arrives
    "conv_serialized",    # non-overlapped halo+conv fallback
    "cf_all_gather",      # CF filter-mode x gather (core.channel_conv)
    "cf_reduce_scatter",  # CF channel-mode y scatter
    "bn_collective",      # BN stats psums (core.spatial_norm)
    "reshard",            # §III-C reshard points (core.plan)
)


@contextlib.contextmanager
def layer_context(name: str):
    """Key every region traced inside with layer `name`: a
    ``jax.named_scope(name)``, so all ops of the layer carry the layer
    name in their HLO ``op_name`` path, and `annotate`'s regions inside it
    read ``<layer>/<region>`` there."""
    with jax.named_scope(name):
        yield


@contextlib.contextmanager
def annotate(region: str):
    """Mark a named region of traced code; identity on values.

    Inside jit tracing the ``jax.named_scope`` lands `region` in the
    compiled HLO op_name metadata (nested under any `layer_context`), so
    XLA profiles decode to plan terms.  It opens no host span: the body
    runs while the step is traced, not while it executes.
    """
    with jax.named_scope(region):
        yield


# ---------------------------------------------------------------------------
# host spans closed when device arrays are ready
# ---------------------------------------------------------------------------

class _ReadyCloser:
    """One daemon thread that takes (span, arrays) from a queue, waits
    until the arrays are ready and closes the span.  Started on first
    use."""

    def __init__(self):
        self._queue = queue.SimpleQueue()
        self._lock = threading.Lock()
        self._thread = None

    def close_when_ready(self, span, tree) -> None:
        with self._lock:
            if self._thread is None:
                self._thread = threading.Thread(
                    target=self._run, name="repro-trace-ready", daemon=True)
                self._thread.start()
        self._queue.put((span, tree))

    def _run(self) -> None:
        while True:
            span, tree = self._queue.get()
            try:
                jax.block_until_ready(tree)
            except Exception:  # noqa: BLE001 — the span still closes
                log.exception("a traced span's arrays never became ready")
            finally:
                del tree
                span.__exit__(None, None, None)


_READY = _ReadyCloser()


def span_until_ready(name: str, fn, *args):
    """`fn(*args)` under a host profiler span `name` that opens now and
    closes when every array `fn` returned is ready on its device(s), e.g.
    a batch's host->device copy.  The caller never waits on the span; with
    no profiler session it costs a TraceMe check and one queue put."""
    span = jax.profiler.TraceAnnotation(name)
    span.__enter__()
    try:
        out = fn(*args)
    except BaseException:
        span.__exit__(None, None, None)
        raise
    _READY.close_when_ready(span, out)
    return out


# ---------------------------------------------------------------------------
# StepTrace — measured per-layer costs
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class StepTrace:
    """Measured per-layer cost breakdown of one training step.

    layers: {layer name: {"fwd_s", "bwd_s", "fwd_bwd_s"}} in execution
            order — seconds per call of the layer's isolated AOT-compiled
            forward / forward+backward.
    step:   {"fwd_s", "bwd_s", "fwd_bwd_s"} of the WHOLE fused step (the
            same estimator), the cross-check target: the per-layer sums
            should land within dispatch-overhead tolerance of it.
    meta:   backend, mesh shape, device count, timing reps/rounds,
            measured peak bytes (XLA memory_analysis), overlap flag and
            the calibrated achieved-overlap η in force (when measured).
    """
    layers: dict[str, dict]
    step: dict[str, float]
    meta: dict = dataclasses.field(default_factory=dict)
    schema: str = SCHEMA

    # -- derived ------------------------------------------------------------
    @property
    def layer_fwd_sum_s(self) -> float:
        return sum(r["fwd_s"] for r in self.layers.values())

    @property
    def layer_bwd_sum_s(self) -> float:
        return sum(r["bwd_s"] for r in self.layers.values())

    @property
    def layer_sum_s(self) -> float:
        """Sum of isolated per-layer fwd+bwd times — compare to
        step['fwd_bwd_s'] to bound the segmentation overhead."""
        return self.layer_fwd_sum_s + self.layer_bwd_sum_s

    # -- JSON round-trip ----------------------------------------------------
    def to_dict(self) -> dict:
        return {"schema": self.schema, "layers": self.layers,
                "step": self.step, "meta": self.meta}

    @classmethod
    def from_dict(cls, d: Mapping) -> "StepTrace":
        if d.get("schema") != SCHEMA:
            raise ValueError(f"not a step trace: schema "
                             f"{d.get('schema')!r} != {SCHEMA!r}")
        return cls(layers=dict(d["layers"]), step=dict(d["step"]),
                   meta=dict(d.get("meta", {})))

    def save(self, path: str) -> None:
        with open(path, "w") as f:
            json.dump(self.to_dict(), f, indent=1, sort_keys=True)

    @classmethod
    def load(cls, path: str) -> "StepTrace":
        with open(path) as f:
            return cls.from_dict(json.load(f))

    # -- Chrome-trace export ------------------------------------------------
    def chrome_trace(self) -> dict:
        """The measured breakdown as a Chrome-trace / Perfetto JSON object.

        Forward segments lie on one track in execution order, backward
        segments on a second track in reverse (backprop) order, laid out
        end to end from their measured durations — a synthetic but
        to-scale timeline of where the step's time goes.  Timestamps and
        durations are microseconds, per the trace-event spec.
        """
        events = [
            {"ph": "M", "pid": 0, "name": "process_name",
             "args": {"name": "repro step trace"}},
            {"ph": "M", "pid": 0, "tid": 0, "name": "thread_name",
             "args": {"name": "forward"}},
            {"ph": "M", "pid": 0, "tid": 1, "name": "thread_name",
             "args": {"name": "backward"}},
        ]
        ts = 0.0
        for name, r in self.layers.items():
            dur = r["fwd_s"] * 1e6
            events.append({"ph": "X", "pid": 0, "tid": 0, "name": name,
                           "cat": "fwd", "ts": ts, "dur": dur})
            ts += dur
        for name, r in reversed(list(self.layers.items())):
            dur = r["bwd_s"] * 1e6
            events.append({"ph": "X", "pid": 0, "tid": 1, "name": name,
                           "cat": "bwd", "ts": ts, "dur": dur})
            ts += dur
        return {"traceEvents": events, "displayTimeUnit": "ms",
                "otherData": dict(self.meta, schema=self.schema)}

    def save_chrome(self, path: str) -> None:
        with open(path, "w") as f:
            json.dump(self.chrome_trace(), f, indent=1)


# ---------------------------------------------------------------------------
# segmented re-execution profiler
# ---------------------------------------------------------------------------

def trace_plan(plan, params, batch, *, cfg, mesh, overlap: bool = True,
               reps: int = 3, rounds: int = 3) -> StepTrace:
    """Measure every plan layer's fwd/bwd cost by isolated re-execution.

    plan:   a core.plan.NetworkPlan (or anything NetworkPlan.of accepts).
    params: the model parameter list (models.cnn.meshnet layout).
    batch:  {"image", "label"} device arrays, image sharded per the plan's
            first-layer input spec.
    cfg:    the MeshNetConfig the plan was solved for.

    One forward pass captures the real intermediate activation entering
    each layer (each under the sharding the plan's reshard points leave it
    in), then each layer's callable (meshnet.layer_fns — the exact code
    `apply` runs, §III-C reshard included) is AOT-compiled standalone as
    forward and as forward+backward and timed with the interleaved-rounds
    estimator against the whole fused step, so host-load drift hits every
    segment equally.  bwd_s is (fwd+bwd) - fwd, floored at 0.
    """
    import functools

    import jax.numpy as jnp

    from repro.core.calibrate import compiled_peak_bytes
    from repro.core.channel_conv import measured_eta
    from repro.core.plan import NetworkPlan
    from repro.models.cnn import meshnet
    from repro.utils import interleaved_min

    plan = NetworkPlan.of(plan)
    fns = meshnet.layer_fns(cfg, plan, mesh, overlap)

    with mesh:
        # the whole fused step: fwd-only and fwd+bwd, AOT so the XLA
        # memory_analysis peak rides along with the timing
        fwd_step = jax.jit(lambda p, b: meshnet.apply(
            p, b["image"], cfg, plan, mesh, overlap))
        full_step = jax.jit(jax.value_and_grad(lambda p, b: meshnet.loss_fn(
            p, b, cfg, plan, mesh, overlap)))
        c_fwd = fwd_step.lower(params, batch).compile()
        c_full = full_step.lower(params, batch).compile()
        peak = compiled_peak_bytes(c_full)
        c_fwd(params, batch)[0].block_until_ready()           # warm
        jax.tree.leaves(c_full(params, batch))[0].block_until_ready()

        # capture the activation entering each layer (plan-sharded)
        def capture(p, x):
            xs = []
            for (name, fn), lp in zip(fns, p):
                xs.append(x)
                x = fn(lp, x)
            return tuple(xs)

        xs = jax.jit(capture)(params, batch["image"])

        segments = {"__step__|fwd": functools.partial(c_fwd, params, batch),
                    "__step__|fwd_bwd": functools.partial(c_full, params,
                                                          batch)}
        for (name, fn), lp, x in zip(fns, params, xs):
            c_f = jax.jit(fn).lower(lp, x).compile()

            def fwd_bwd(lp, x, fn=fn):
                return jax.value_and_grad(
                    lambda lp, x: jnp.sum(fn(lp, x)), argnums=(0, 1))(lp, x)

            c_fb = jax.jit(fwd_bwd).lower(lp, x).compile()
            c_f(lp, x).block_until_ready()                    # warm
            jax.tree.leaves(c_fb(lp, x))[0].block_until_ready()
            segments[f"{name}|fwd"] = functools.partial(c_f, lp, x)
            segments[f"{name}|fwd_bwd"] = functools.partial(c_fb, lp, x)

        times = interleaved_min(segments, reps=reps, rounds=rounds)

    layers = {}
    for name, _ in fns:
        fwd = times[f"{name}|fwd"]
        fb = times[f"{name}|fwd_bwd"]
        layers[name] = {"fwd_s": fwd, "bwd_s": max(fb - fwd, 0.0),
                        "fwd_bwd_s": fb}
    step = {"fwd_s": times["__step__|fwd"],
            "fwd_bwd_s": times["__step__|fwd_bwd"],
            "bwd_s": max(times["__step__|fwd_bwd"]
                         - times["__step__|fwd"], 0.0)}
    meta = {"backend": jax.default_backend(),
            "mesh": dict(mesh.shape),
            "ndevices": jax.device_count(),
            "reps": reps, "rounds": rounds,
            "overlap": bool(overlap),
            "overlap_eta_measured": (float(measured_eta())
                                     if measured_eta() is not None else None),
            "measured_peak_bytes": int(peak)}
    return StepTrace(layers=layers, step=step, meta=meta)


# ---------------------------------------------------------------------------
# attribution rendering
# ---------------------------------------------------------------------------

def format_attribution(report: Mapping) -> str:
    """Render a plan.attribution_report dict as the predicted-vs-measured
    table (seconds in ms; ratio = measured / predicted, >1 means slower
    than the model; flagged rows exceed the tolerance either way)."""
    rows = [f"{'layer':20s} {'pred fwd':>9s} {'meas fwd':>9s} "
            f"{'pred bwd':>9s} {'meas bwd':>9s} {'ratio':>7s}  note"]
    for name, r in report["per_layer"].items():
        flag = " <-- drift" if r["flagged"] else ""
        rows.append(
            f"{name:20s} {r['predicted_fwd_s']*1e3:8.3f}m "
            f"{r['measured_fwd_s']*1e3:8.3f}m "
            f"{r['predicted_bwd_s']*1e3:8.3f}m "
            f"{r['measured_bwd_s']*1e3:8.3f}m "
            f"{r['ratio_total']:7.2f}{flag}")
    t = report["totals"]
    rows.append(
        f"{'TOTAL':20s} {t['predicted_s']*1e3:8.3f}m "
        f"{t['measured_s']*1e3:8.3f}m   ratio "
        f"{t['ratio']:.2f}  (step measured "
        f"{t['step_measured_s']*1e3:.3f}m)")
    terms = report.get("terms", {})
    if terms:
        worst = report.get("worst_term")
        parts = [f"{k}={v['drift']:.2f}x" for k, v in terms.items()]
        rows.append(f"per-term drift (measured/predicted, "
                    f"weighted by predicted seconds): {' '.join(parts)}"
                    + (f"; worst: {worst}" if worst else ""))
    return "\n".join(rows)
