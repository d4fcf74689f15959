"""conv_ms: device time of the convolution operations per step, averaged
over the cell's devices (layer: local conv, XLA's convolution)."""


def reduce(ctx):
    s = ctx.trace.conv_seconds_per_step()
    return None if s is None else s * 1e3
