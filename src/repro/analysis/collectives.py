"""SPMD collective auditor — prove costed == executed before running a step.

The perf model (core.perfmodel.layer_collectives) declares the priced
inventory: every collective the runtime should issue for a layer under its
distribution, with kind, payload bytes, mesh axes and the cost term that
charges it.  This module walks the *traced* program — the closed jaxpr of
the plan's AOT step, and optionally its lowered StableHLO — inventories
every collective actually issued (attributed to layers via the named-region
op_name metadata, core.trace), and joins the two, flagging:

  unpriced-collective   comm in the program the solver never charged — the
                        prime suspect for the mesh16 model/measured drift;
  phantom-charge        priced comm absent from the program — the solver
                        penalized a plan for messages it never sends;
  payload-mismatch      priced and executed bytes disagree beyond
                        tolerance (>25% error, >5% warning);
  uncharged-collective  comm the model *knowingly* leaves unpriced
                        (charged=False inventory entries, e.g. the CF
                        slice-VJP weight psum) — warning, never error;
  schedule-pin-missing  an interior-split layer without its §IV-A
                        optimization_barrier pin (fwd or bwd);
  halo-after-interior   halo ppermutes issued after the interior conv —
                        the latency-hiding order violated;
  lowering-mismatch /   (hlo pass) layer attribution or per-kind op counts
  hlo-count-mismatch    lost between jaxpr and StableHLO.

Everything here is lowering-only: jax.make_jaxpr / jax.jit(...).lower on
ShapeDtypeStructs.  No timers, no devices doing real work.

Byte convention: an executed collective's payload is the SUM of its input
avals' bytes (a two-operand psum counts both), and inventory entries carry
the TOTAL bytes over their `count` ops — so chunked collectives compare on
totals.
"""
from __future__ import annotations

import dataclasses
import re
from typing import Mapping, Sequence

import numpy as np

from repro.analysis.lint import Finding
from repro.core import perfmodel as pm
from repro.core import trace as trace_lib

# jaxpr primitive names that move data between devices.  `psum_invariant`
# is what shard_map's varying-manual-axes tracking emits for the psum of a
# value that is invariant in the program's type (notably the transpose of
# `pvary`: the weight-gradient reduction of a replicated input); `pvary`
# itself is no-communication bookkeeping — deliberately NOT listed.
COLLECTIVE_PRIMS = ("ppermute", "psum", "psum_invariant", "all_gather",
                    "reduce_scatter", "all_to_all")
_KIND_NORM = {"psum_invariant": "psum"}

# relative payload error thresholds for the priced-vs-executed join
PAYLOAD_WARN = 0.05
PAYLOAD_ERROR = 0.25

_CHUNKS_RE = re.compile(r"cf chunks=(\d+)")


@dataclasses.dataclass(frozen=True)
class ExecutedOp:
    """One op of interest found in the traced jaxpr, with attribution."""
    kind: str                 # normalized primitive name (psum_invariant
                              # -> psum)
    layer: str | None         # via the name-stack layer_context prefix
    direction: str            # fwd | bwd ('transpose(' in the name stack)
    region: str | None        # innermost trace.REGIONS name on the path
    path: str                 # full name-stack path (diagnostics)
    bytes: float              # sum over input avals
    axes: frozenset           # mesh axis names the op runs over
    index: int                # pre-order position (schedule checks)


def _axes_of(prim: str, params: Mapping) -> frozenset:
    raw = params.get("axes", params.get("axis_name", ()))
    if isinstance(raw, str):
        raw = (raw,)
    return frozenset(a for a in tuple(raw) if isinstance(a, str))


def _aval_bytes(v) -> float:
    aval = getattr(v, "aval", None)
    shape = getattr(aval, "shape", None)
    dtype = getattr(aval, "dtype", None)
    if shape is None or dtype is None:
        return 0.0
    return float(np.prod(shape, dtype=np.int64)) * np.dtype(dtype).itemsize


def _attr_layer(path: str, names: Sequence[str]) -> str | None:
    for n in sorted(names, key=len, reverse=True):
        if n in path:
            return n
    return None


def _attr_region(path: str) -> str | None:
    best, best_at = None, -1
    for r in trace_lib.REGIONS:
        at = path.rfind(r)
        if at > best_at:
            best, best_at = r, at
    return best


def collect_ops(closed, layer_names: Sequence[str]) -> list[ExecutedOp]:
    """Walk a ClosedJaxpr (pre-order, recursing into every sub-jaxpr in
    eqn params) and inventory the collectives, optimization_barriers and
    conv applications with name-stack attribution."""
    ops: list[ExecutedOp] = []
    counter = [0]

    def walk(jaxpr, prefix):
        for eqn in jaxpr.eqns:
            counter[0] += 1
            nm = eqn.primitive.name
            ns = str(eqn.source_info.name_stack)
            path = (prefix + "/" + ns).strip("/") if ns else prefix
            if nm in COLLECTIVE_PRIMS or nm in (
                    "optimization_barrier", "conv_general_dilated"):
                kind = _KIND_NORM.get(nm, nm)
                ops.append(ExecutedOp(
                    kind=kind,
                    layer=_attr_layer(path, layer_names),
                    direction="bwd" if "transpose(" in path else "fwd",
                    region=_attr_region(path),
                    path=path,
                    bytes=sum(_aval_bytes(v) for v in eqn.invars),
                    axes=_axes_of(nm, eqn.params),
                    index=counter[0]))
            for v in eqn.params.values():
                items = v if isinstance(v, (list, tuple)) else [v]
                for it in items:
                    if hasattr(it, "eqns"):
                        walk(it, path)
                    elif hasattr(it, "jaxpr") and hasattr(it.jaxpr, "eqns"):
                        walk(it.jaxpr, path)

    walk(closed.jaxpr, "")
    return ops


# ---------------------------------------------------------------------------
# the priced-vs-executed join
# ---------------------------------------------------------------------------

def _minor(op: ExecutedOp, cmax: int) -> bool:
    """Small bookkeeping comm the model never prices: BN statistics psums
    and per-channel-vector gradients (gamma/beta) — O(C) words against the
    O(N·H·W·C) collectives the cost terms track."""
    return op.region == "bn_collective" or op.bytes <= 16 * max(cmax, 1)


def join_findings(inventory: Mapping[str, Sequence[pm.CollectiveSpec]],
                  ops: Sequence[ExecutedOp],
                  specs: Sequence[pm.ConvLayer]) -> list[Finding]:
    """Greedy per-entry matching of executed collectives against the
    priced inventory, per (layer, direction, kind): exact axes-set matches
    claim first (largest payload first), then unmatched entries claim any
    remaining same-kind ops — so a tiny priced psum (e.g. the pred layer's
    16-element weight gradient) is matched before leftover classification
    can misroute it."""
    out: list[Finding] = []
    spec_by_name = {s.name: s for s in specs}
    cmax_global = max((max(s.c, s.f) for s in specs), default=1)

    coll = [o for o in ops if o.kind in
            ("ppermute", "psum", "all_gather", "reduce_scatter",
             "all_to_all")]
    by_key: dict[tuple, list[ExecutedOp]] = {}
    for o in coll:
        by_key.setdefault((o.layer, o.direction, o.kind), []).append(o)

    ent_by_key: dict[tuple, list[pm.CollectiveSpec]] = {}
    for layer, entries in inventory.items():
        for e in entries:
            if e.visibility != "jaxpr":
                continue
            ent_by_key.setdefault(
                (layer, e.direction, _KIND_NORM.get(e.kind, e.kind)),
                []).append(e)

    leftovers: list[ExecutedOp] = []
    for key in sorted(set(by_key) | set(ent_by_key),
                      key=lambda k: (str(k[0]), k[1], k[2])):
        layer, direction, kind = key
        remaining = sorted(by_key.get(key, []),
                           key=lambda o: -o.bytes)
        entries = sorted(ent_by_key.get(key, []), key=lambda e: -e.bytes)
        claims: list[list[ExecutedOp]] = [[] for _ in entries]
        for i, e in enumerate(entries):          # pass 1: exact axes match
            want = frozenset(e.axes)
            for o in list(remaining):
                if len(claims[i]) >= e.count:
                    break
                if o.axes == want:
                    claims[i].append(o)
                    remaining.remove(o)
        for i, e in enumerate(entries):          # pass 2: any same-kind op
            while len(claims[i]) < e.count and remaining:
                claims[i].append(remaining.pop(0))
        leftovers.extend(remaining)

        for e, claimed in zip(entries, claims):
            what = (f"{direction} {kind} "
                    f"[{e.region}] over {sorted(e.axes)}")
            if not claimed:
                if e.charged:
                    out.append(Finding(
                        "error", "phantom-charge", layer=layer,
                        message=f"priced {what} "
                                f"({e.bytes:.0f} B, term {e.term}) absent "
                                f"from the traced program — the solver "
                                f"charged comm that never executes",
                        fix="fix layer_collectives' geometry for this "
                            "dist, or the runtime dropped a collective"))
                continue
            cb = sum(o.bytes for o in claimed)
            rel = abs(cb - e.bytes) / max(e.bytes, 1.0)
            if rel > PAYLOAD_WARN:
                sev = "error" if rel > PAYLOAD_ERROR else "warning"
                out.append(Finding(
                    sev, "payload-mismatch", layer=layer,
                    message=f"{what}: priced {e.bytes:.0f} B but the "
                            f"program moves {cb:.0f} B "
                            f"({rel * 100:.0f}% off)",
                    fix="re-derive the shard geometry in "
                        "layer_collectives against the traced shapes"))
            if len(claimed) != e.count:
                out.append(Finding(
                    "warning", "collective-count", layer=layer,
                    message=f"{what}: priced as {e.count} op(s) but the "
                            f"program issues {len(claimed)}",
                    fix="check the chunking/boundary-application count"))
            bad_axes = [o for o in claimed if o.axes != frozenset(e.axes)]
            if bad_axes:
                out.append(Finding(
                    "warning", "collective-axes", layer=layer,
                    message=f"{what}: executed over "
                            f"{sorted(bad_axes[0].axes)} instead",
                    fix="the dist's axis mapping and the runtime's "
                        "shard_map axes disagree"))
            if not e.charged:
                spec = spec_by_name.get(layer)
                cmax = max(spec.c, spec.f) if spec else cmax_global
                out.append(Finding(
                    "info" if e.bytes <= 16 * cmax else "warning",
                    "uncharged-collective", layer=layer,
                    message=f"{what} ({e.bytes:.0f} B) executes but no "
                            f"cost term prices it (known gap — e.g. the "
                            f"CF slice-VJP weight psum, the standing "
                            f"mesh16cf drift suspect)",
                    fix="price it in layer_cost and mark the inventory "
                        "entry charged"))

    minors: dict[tuple, list[ExecutedOp]] = {}
    for o in leftovers:
        spec = spec_by_name.get(o.layer)
        cmax = max(spec.c, spec.f) if spec else cmax_global
        if _minor(o, cmax):
            minors.setdefault((o.layer, o.direction), []).append(o)
        else:
            out.append(Finding(
                "error", "unpriced-collective", layer=o.layer,
                message=f"{o.direction} {o.kind} [{o.region}] over "
                        f"{sorted(o.axes)} moves {o.bytes:.0f} B with no "
                        f"matching priced inventory entry "
                        f"(path {o.path})",
                fix="add it to perfmodel.layer_collectives and charge a "
                    "cost term — unpriced comm is how plans win on paper "
                    "and lose on hardware"))
    for (layer, direction), ms in sorted(
            minors.items(), key=lambda kv: (str(kv[0][0]), kv[0][1])):
        out.append(Finding(
            "info", "uncharged-minor-comm", layer=layer,
            message=f"{len(ms)} {direction} bookkeeping collective(s) "
                    f"({sum(o.bytes for o in ms):.0f} B total: BN stats "
                    f"/ per-channel vectors) — below pricing granularity",
            fix=""))
    return out


# ---------------------------------------------------------------------------
# schedule checks (§IV-A)
# ---------------------------------------------------------------------------

def schedule_findings(ops: Sequence[ExecutedOp], plan,
                      specs: Sequence[pm.ConvLayer],
                      mesh_shape: Mapping[str, int],
                      overlap: bool) -> list[Finding]:
    out: list[Finding] = []
    barriers = [o for o in ops if o.kind == "optimization_barrier"]
    reshard_pins = [o for o in barriers if "reshard" in o.path]
    layer_pins = [o for o in barriers if "reshard" not in o.path]

    for spec in specs:
        lp = plan.layers.get(spec.name)
        dist = lp.dist if lp is not None else None
        if dist is None:
            continue
        expected = pm.interior_split(spec, dist, mesh_shape, overlap)
        mine = [o for o in layer_pins if o.layer == spec.name]
        if expected:
            for direction in ("fwd", "bwd"):
                if not any(o.direction == direction for o in mine):
                    out.append(Finding(
                        "error", "schedule-pin-missing", layer=spec.name,
                        message=f"interior-split layer has no {direction} "
                                f"optimization_barrier pin — XLA is free "
                                f"to reorder the boundary conv before the "
                                f"halo overlap window",
                        fix="HaloSchedule.pin must wrap the interior "
                            "conv (core.spatial_conv)"))
        elif not overlap and mine:
            out.append(Finding(
                "warning", "schedule-pin-unexpected", layer=spec.name,
                message=f"{len(mine)} optimization_barrier pin(s) in a "
                        f"serialized (overlap=False) lowering",
                fix="the serialized path should not pay pin constraints"))

    n_reshards = plan.n_reshards
    if n_reshards and len(reshard_pins) < n_reshards:
        out.append(Finding(
            "warning", "schedule-reshard-pin",
            message=f"{n_reshards} reshard point(s) compiled but only "
                    f"{len(reshard_pins)} reshard double-buffer "
                    f"barrier(s) traced",
            fix="NetworkPlan.reshard pins each redistributed tensor"))

    # halo-before-interior: within each layer's forward, the halo
    # ppermutes must be issued before the interior conv.
    for spec in specs:
        halos = [o.index for o in ops
                 if o.kind == "ppermute" and o.layer == spec.name
                 and o.direction == "fwd" and o.region == "halo_exchange"]
        interior = [o.index for o in ops
                    if o.kind == "conv_general_dilated"
                    and o.layer == spec.name and o.direction == "fwd"
                    and o.region == "conv_interior"]
        if halos and interior and min(halos) > min(interior):
            out.append(Finding(
                "error", "halo-after-interior", layer=spec.name,
                message="halo ppermute issued after the interior conv — "
                        "the §IV-A overlap window is empty",
                fix="HaloSchedule must issue halos before the interior "
                    "conv in program order"))
    return out


# ---------------------------------------------------------------------------
# StableHLO cross-check (attribution survives lowering)
# ---------------------------------------------------------------------------

_HLO_OPS = {"ppermute": "stablehlo.collective_permute",
            "psum": "stablehlo.all_reduce",
            "all_gather": "stablehlo.all_gather",
            "reduce_scatter": "stablehlo.reduce_scatter",
            "optimization_barrier": "stablehlo.optimization_barrier"}


def hlo_findings(asm: str, ops: Sequence[ExecutedOp]) -> list[Finding]:
    out: list[Finding] = []
    layers = sorted({o.layer for o in ops
                     if o.layer and o.kind in ("ppermute", "psum",
                                               "all_gather",
                                               "reduce_scatter")})
    for layer in layers:
        if layer not in asm:
            out.append(Finding(
                "warning", "lowering-mismatch", layer=layer,
                message="layer issues collectives but its name is absent "
                        "from the StableHLO location metadata — profiles "
                        "and the measured-attribution join go blind here",
                fix="layer_context must wrap the whole layer body"))
    for kind, hlo_name in _HLO_OPS.items():
        want = sum(1 for o in ops if o.kind == kind)
        got = asm.count(hlo_name)
        if want != got:
            out.append(Finding(
                "warning", "hlo-count-mismatch",
                message=f"{kind}: {want} in the jaxpr vs {got} "
                        f"{hlo_name} op(s) in the lowered StableHLO",
                fix="lowering fused or duplicated collectives; verify "
                    "against the compiled HLO before trusting payloads"))
    return out


# ---------------------------------------------------------------------------
# drivers
# ---------------------------------------------------------------------------

def _traced_wordsize(args) -> int:
    import jax
    for leaf in jax.tree.leaves(args):
        dt = np.dtype(getattr(leaf, "dtype", np.float32))
        if np.issubdtype(dt, np.floating):
            return dt.itemsize
    return 4


def plan_inventory(plan, specs: Sequence[pm.ConvLayer],
                   mesh_shape: Mapping[str, int], *,
                   machine: pm.Machine | None = None,
                   overlap: bool = True,
                   grad_wrt_inputs: bool = False,
                   wordsize: int = 4) -> dict:
    """The priced inventory for `plan` at the traced wordsize.

    Regenerated (not read from plan.predicted) so the byte comparison is
    dtype-exact: plans are usually costed at the machine's training
    wordsize (TPU_V5E prices bf16) while the audit traces whatever dtype
    the step uses."""
    from repro.core.plan import NetworkPlan, _sharding_to_dist
    plan = NetworkPlan.of(plan)
    m = dataclasses.replace(machine or pm.TPU_V5E, wordsize=wordsize)
    inv = {}
    for i, spec in enumerate(specs):
        lp = plan.layers.get(spec.name)
        if lp is not None and lp.dist is not None:
            dist = lp.dist
        else:
            dist = _sharding_to_dist(plan.sharding(spec.name), spec.name)
        chunks = 1
        if lp is not None:
            mm = _CHUNKS_RE.search(lp.note or "")
            if mm:
                chunks = int(mm.group(1))
        inv[spec.name] = pm.layer_collectives(
            m, spec, dist, mesh_shape, overlap=overlap,
            first=(i == 0 and not grad_wrt_inputs),
            channel_chunks=chunks)
    return inv


def audit_step_fn(fn, args, plan, specs: Sequence[pm.ConvLayer], mesh, *,
                  overlap: bool = True, hlo: bool = True,
                  machine: pm.Machine | None = None,
                  backend: str = "xla",
                  grad_wrt_inputs: bool = False) -> list[Finding]:
    """Audit an arbitrary step function against `plan`'s priced inventory.

    fn:    the step callable (typically jax.value_and_grad of the loss).
    args:  ShapeDtypeStructs (or arrays) matching fn's signature — only
           shapes/dtypes are read; nothing executes.
    specs: the ConvLayers of the plan, in execution order.
    `grad_wrt_inputs=False` declares that the first layer's input gradient
    is dead code (loss wrt params only), so its backward halos are
    expected to be DCE'd.
    """
    import jax
    from repro.core.plan import NetworkPlan
    plan = NetworkPlan.of(plan)
    mesh_shape = dict(mesh.shape)
    with mesh:
        closed = jax.make_jaxpr(fn)(*args)
    ops = collect_ops(closed, [s.name for s in specs])
    inv = plan_inventory(plan, specs, mesh_shape, machine=machine,
                         overlap=overlap, grad_wrt_inputs=grad_wrt_inputs,
                         wordsize=_traced_wordsize(args))
    findings = join_findings(inv, ops, specs)
    findings += schedule_findings(ops, plan, specs, mesh_shape, overlap)
    if hlo:
        with mesh:
            lowered = jax.jit(fn).lower(*args)
        asm = lowered.compiler_ir().operation.get_asm(
            enable_debug_info=True)
        findings += hlo_findings(asm, ops)
    return findings


def audit_meshnet(plan, specs: Sequence[pm.ConvLayer], cfg, mesh, *,
                  machine: pm.Machine | None = None, overlap: bool = True,
                  hlo: bool = False, backend: str = "xla") -> list[Finding]:
    """Audit a meshnet plan's real training step (value_and_grad of
    models.cnn.meshnet.loss_fn) — the convenience entry NetworkPlan.audit
    and the --audit drivers use.  Lowering-only."""
    import jax
    import jax.numpy as jnp

    from repro.models.cnn import meshnet

    n = specs[0].n
    params = jax.eval_shape(
        lambda k: meshnet.init(k, cfg), jax.random.PRNGKey(0))
    batch = {"image": jax.ShapeDtypeStruct(
                 (n, cfg.input_hw, cfg.input_hw, cfg.in_channels),
                 jnp.float32),
             "label": jax.ShapeDtypeStruct(
                 (n, cfg.out_hw, cfg.out_hw, cfg.n_classes), jnp.float32)}

    def loss(p, b):
        return meshnet.loss_fn(p, b, cfg, plan, mesh, overlap)

    return audit_step_fn(
        jax.value_and_grad(loss), (params, batch), plan, specs, mesh,
        overlap=overlap, hlo=hlo, machine=machine, backend=backend,
        grad_wrt_inputs=False)
