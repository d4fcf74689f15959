"""maxpool_ms: device time per step of the max pool, forward and backward:
the union of the operations whose HLO `op_name` holds the `max_pool` scope
(the program's `core.spatial_conv.spatial_pool`), averaged over the cell's
devices (layer: layers).  Nothing to read where no such operation ran."""
import re

from devtrace import length, union

SCOPE = re.compile(r"(^|[/(])max_pool([/)]|$)")


def reduce(ctx):
    tr = ctx.trace
    per_dev = [length(union(tr.clip(
        (o.start, o.end) for o in ops if SCOPE.search(o.op_name))))
        for ops in tr.devices.values()]
    if not any(per_dev) or not tr.steps:
        return None
    return sum(per_dev) / len(per_dev) / tr.steps / 1e6
