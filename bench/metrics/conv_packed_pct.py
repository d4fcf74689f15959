"""conv_packed_pct: the share of the convolutions' device time spent in
convolutions that run with pairs of W columns packed into channels (their
HLO `op_name` holds the `conv_wpack` scope of the program's
`core.spatial_conv._conv_wpack`), each a union of the ops' intervals
summed over the cell's devices (layer: local conv).  0 where convolutions
ran and none was packed; nothing to read where no convolution ran."""
from devtrace import length, union

SCOPE = "conv_wpack"


def reduce(ctx):
    tr = ctx.trace
    total = packed = 0
    for ops in tr.devices.values():
        conv = [o for o in ops if o.is_conv]
        total += length(union(tr.clip((o.start, o.end) for o in conv)))
        packed += length(union(tr.clip((o.start, o.end) for o in conv
                                       if SCOPE in o.op_name)))
    if not total:
        return None
    return 100.0 * packed / total
