"""conv_roofline: the least time one device's share of the step's
convolutions could take on this chip (the larger of its FLOPs over the
bf16 matmul peak and its bytes over the HBM bandwidth, bench/flops.py),
over the device time the convolutions took (layer: local conv)."""
import flops


def reduce(ctx):
    s = ctx.trace.conv_seconds_per_step()
    if s is None:
        return None
    n, chips = ctx.traffic["batch"], ctx.chips
    t_min, _ = flops.roofline_seconds(
        flops.step_flops(ctx.config, n) / chips,
        flops.step_bytes(ctx.config, n, ctx.word) / chips,
        ctx.peaks["bf16_flops"], ctx.peaks["hbm_bytes_per_s"])
    return 100.0 * t_min / s
