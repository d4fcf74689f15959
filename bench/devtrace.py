"""From the profiler's trace to the plain records the metric readers take.

`parse(path)` reads an `.xplane.pb` with JAX's own `ProfileData` into
plain lists: planes, their lines, and events as [name, start_ns,
duration_ns], an HLO operation's name cut to its instruction's name.  `Trace` then gives what the
readers need on one clock: each device's operations in the window, the
harness's host spans (`bench.step`, `bench.put`, `bench.dispatch`,
`bench.readback`), and the window itself, from the first step's start to
the last step's end.

A device is a plane named `/device:TPU:<n>`; its operations are the events
of its `XLA Ops` line, each named by its HLO instruction's text.  The
profiler's events carry no category or source op, so `hlo_map` reads them
from the compiled program's HLO text: an instruction is a "convolution"
when it is one or calls a fused computation that holds one, a
"collective" when its opcode is one, else its opcode (with a fusion's
kind); its layer is the first plan-layer name (`conv3_1`, `pred`, ...) in
its `op_name`, which `core.trace.layer_context` writes.
"""
from __future__ import annotations

import dataclasses
import gzip
import json
import re

DEVICE_PLANE = re.compile(r"/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
SPAN_PREFIX = "bench."
LAYER = re.compile(r"\b(conv\d+_\d+|pred)\b")
COLLECTIVE = ("all-reduce", "all-gather", "collective-permute",
              "reduce-scatter", "all-to-all", "send", "recv")
COMPUTATION = re.compile(r"^(?:ENTRY )?%?([\w.\-]+) .*\{$")
INSTRUCTION = re.compile(r"^\s*(?:ROOT )?%?([\w.\-]+) = (.*)$")
OPCODE = re.compile(r"\s([a-z][a-z0-9\-]*)\(")
CALLS = re.compile(r"calls=%?([\w.\-]+)")
KIND = re.compile(r"kind=k(\w+)")
OP_NAME = re.compile(r'op_name="([^"]*)"')


def hlo_map(text: str) -> dict:
    """{instruction: [category, op_name]} of a compiled module's HLO text
    (`compiled.as_text()`); see the module doc for the categories."""
    comp, instrs, bodies = None, {}, {}
    for line in text.splitlines():
        m = COMPUTATION.match(line)
        if m:
            comp = m.group(1)
            bodies[comp] = []
            continue
        m = INSTRUCTION.match(line)
        if not m or comp is None:
            continue
        name, rhs = m.groups()
        op = OPCODE.search(" " + rhs)
        calls = CALLS.search(rhs)
        op_name = OP_NAME.search(rhs)
        rec = (op.group(1) if op else "", calls.group(1) if calls else None,
               op_name.group(1) if op_name else "", KIND.search(rhs))
        instrs[name] = rec
        bodies[comp].append(rec)
    conv = {c for c, recs in bodies.items()
            if any(r[0] == "convolution" for r in recs)}
    out = {}
    for name, (op, calls, op_name, kind) in instrs.items():
        if op == "convolution" or (op == "fusion" and calls in conv):
            cat = "convolution"
        elif op.startswith(COLLECTIVE):
            cat = "collective"
        elif op == "fusion" and kind:
            cat = f"{kind.group(1).lower()} fusion"
        else:
            cat = op
        if not op_name and calls in bodies:
            op_name = next((r[2] for r in bodies[calls] if r[2]), "")
        out[name] = [cat, op_name]
    return out


def instruction(event_name: str) -> str:
    """The HLO instruction an `XLA Ops` event names ("%fusion.12 = ...")."""
    return event_name.split(" = ", 1)[0].lstrip("%")


def parse(path: str) -> dict:
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    planes = []
    for pl in pd.planes:
        lines = []
        for ln in pl.lines:
            lines.append({"name": ln.name, "events": [
                [instruction(e.name), int(e.start_ns), int(e.duration_ns)]
                for e in ln.events]})
        planes.append({"name": pl.name, "lines": lines})
    return {"planes": planes}


def save(trace: dict, path: str) -> None:
    with gzip.open(path, "wt") as f:
        json.dump(trace, f)


def load(path: str) -> dict:
    with gzip.open(path, "rt") as f:
        return json.load(f)


@dataclasses.dataclass(frozen=True)
class Op:
    name: str
    start: int
    end: int
    category: str
    op_name: str

    @property
    def layer(self) -> str | None:
        m = LAYER.search(self.op_name)
        return m.group(1) if m else None

    @property
    def is_conv(self) -> bool:
        return self.category == "convolution"

    @property
    def is_collective(self) -> bool:
        return self.category == "collective"


def union(intervals) -> list[tuple[int, int]]:
    """Merged, sorted [start, end) intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], e))
        else:
            out.append((s, e))
    return out


def length(intervals) -> int:
    return sum(e - s for s, e in intervals)


def minus(a, b) -> list[tuple[int, int]]:
    """Parts of merged intervals `a` not covered by merged intervals `b`."""
    out, j = [], 0
    for s, e in a:
        while j < len(b) and b[j][1] <= s:
            j += 1
        k, cur = j, s
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


class Trace:
    def __init__(self, raw: dict):
        self.spans = []
        hlo = raw.get("hlo", {})
        devs = {}
        for pl in raw["planes"]:
            m = DEVICE_PLANE.search(pl["name"])
            for ln in pl["lines"]:
                if m and ln["name"] == OPS_LINE:
                    devs[int(m.group(1))] = [
                        Op(instruction(n), s, s + d,
                           *hlo.get(instruction(n), ["", ""]))
                        for n, s, d, *_ in ln["events"]]
                elif not m:
                    self.spans += [(n, s, s + d) for n, s, d, *_ in
                                   ln["events"] if n.startswith(SPAN_PREFIX)]
        self.spans.sort(key=lambda x: x[1])
        steps = [s for s in self.spans if s[0] == "bench.step"]
        self.steps = len(steps)
        self.window = (steps[0][1], steps[-1][2]) if steps else (0, 0)
        t0, t1 = self.window
        self.devices = {d: [o for o in ops if o.end > t0 and o.start < t1]
                        for d, ops in sorted(devs.items())}

    @property
    def window_ns(self) -> int:
        return self.window[1] - self.window[0]

    def clip(self, intervals):
        t0, t1 = self.window
        return [(max(s, t0), min(e, t1)) for s, e in intervals
                if e > t0 and s < t1]

    def busy(self, dev: int) -> list[tuple[int, int]]:
        return union(self.clip((o.start, o.end) for o in self.devices[dev]))

    def conv_seconds_per_step(self) -> float | None:
        """Convolution device seconds per step, averaged over devices;
        None where no convolution ran."""
        per_dev = [length(union(self.clip(
            (o.start, o.end) for o in ops if o.is_conv)))
            for ops in self.devices.values()]
        if not any(per_dev) or not self.steps:
            return None
        return sum(per_dev) / len(per_dev) / self.steps / 1e9

    def host_activity(self, t: int) -> str:
        """The innermost harness span around time `t` (the latest-starting
        one that covers it), or 'host:outside-spans'."""
        best = None
        for name, s, e in self.spans:
            if s > t:
                break
            if e >= t and name != "bench.step":
                best = name
        return best or "host:outside-spans"


def trim(raw: dict, steps: int) -> dict:
    """The trace cut to its first `steps` harness steps: events that
    overlap them, every plane and line kept."""
    tr = Trace(raw)
    marks = [s for s in tr.spans if s[0] == "bench.step"][:steps]
    t0, t1 = marks[0][1], marks[-1][2]
    planes = [
        {"name": pl["name"], "lines": [
            {"name": ln["name"], "events": [
                ev for ev in ln["events"]
                if ev[1] + ev[2] > t0 and ev[1] < t1]}
            for ln in pl["lines"]]}
        for pl in raw["planes"]]
    seen = {instruction(ev[0]) for pl in planes for ln in pl["lines"]
            for ev in ln["events"]}
    return {"planes": planes,
            "hlo": {k: v for k, v in raw.get("hlo", {}).items()
                    if k in seen}}
