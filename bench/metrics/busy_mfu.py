"""busy_mfu: the traced steps' model FLOPs (bench/flops.py) over the time
the devices were busy, times the chips' bf16 peak, as a share (layer:
device).  The busy time is the union of each device's operations in the
window, from the device trace, averaged over the devices.  It bounds every
kernel's roofline share: a kernel taken off the path leaves its roofline
silent, and this one still reads.  Beside `device_idle_pct` it splits the
end-to-end `mfu`: mfu ~ busy_mfu * (1 - idle)."""
import flops
from devtrace import length


def reduce(ctx):
    tr = ctx.trace
    busy = [length(tr.busy(d)) for d in tr.devices]
    if not tr.steps or not sum(busy):
        return None
    work = flops.step_flops(ctx.config, ctx.traffic["batch"]) * tr.steps
    busy_s = sum(busy) / len(busy) / 1e9
    return 100.0 * work / (busy_s * ctx.chips * ctx.peaks["bf16_flops"])
