"""device_idle_pct: the share of the traced window in which no operation
ran on a device, averaged over the cell's devices (layer: device)."""


def reduce(ctx):
    tr = ctx.trace
    if not tr.devices or tr.window_ns <= 0:
        return None
    busy = sum(sum(e - s for s, e in tr.busy(d)) for d in tr.devices)
    return 100.0 * (1.0 - busy / len(tr.devices) / tr.window_ns)
