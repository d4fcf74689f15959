"""collective_exposed_ms: per step, the device time of collective
operations (halo permutes, BN and gradient all-reduces, FSDP gathers)
during which no other operation ran on that device; the largest over the
cell's devices (layer: collectives).  Nothing to read where no collective
ran."""
from devtrace import length, minus, union


def reduce(ctx):
    tr = ctx.trace
    worst, seen = 0, False
    for ops in tr.devices.values():
        coll = union(tr.clip((o.start, o.end) for o in ops
                             if o.is_collective))
        if not coll:
            continue
        seen = True
        other = union(tr.clip((o.start, o.end) for o in ops
                              if not o.is_collective))
        worst = max(worst, length(minus(coll, other)))
    if not seen or not tr.steps:
        return None
    return worst / tr.steps / 1e6
