"""window_compiles: compilations JAX started inside the measured window,
counted by its backend-compile monitoring event (layer: host loop).  The
reading should be 0."""


def reduce(ctx):
    return ctx.counters.get("window_compiles")
