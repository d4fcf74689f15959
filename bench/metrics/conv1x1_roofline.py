"""conv1x1_roofline: the least time one device's share of the step's 1x1
convolutions of the ResNet body could take on this chip, over the device
time they took (layer: local conv).

The least time is the sum over the model's `k == 1` convolutions but the
head (`fc`) of the larger of each one's step FLOPs over the bf16 matmul
peak and its step bytes over the HBM bandwidth, counted as bench/flops.py
counts a convolution (forward, weight gradient, and the input gradient
unless it is the first layer).  At four-byte words every one of
ResNet-50's is bound by bandwidth.  The device time is the union of
the convolution operations whose HLO `op_name` holds a `_branch2a`,
`_branch2c` or `_branch1` scope (the program's
`models.cnn.resnet` plan layers), per step, averaged over the devices.
Nothing to read where no such convolution ran."""
import re

import flops
from devtrace import length, union

SCOPE = re.compile(r"_branch(?:2a|2c|1)\b")


def reduce(ctx):
    tr = ctx.trace
    per_dev = [length(union(tr.clip(
        (o.start, o.end) for o in ops
        if o.is_conv and SCOPE.search(o.op_name))))
        for ops in tr.devices.values()]
    if not any(per_dev) or not tr.steps:
        return None
    seconds = sum(per_dev) / len(per_dev) / tr.steps / 1e9
    n, chips = ctx.traffic["batch"], ctx.chips
    t_min = 0.0
    for i, cv in enumerate(flops.convs(ctx.config)):
        if cv.k != 1 or cv.name == "fc":
            continue
        passes = 3 if i else 2
        elems = cv.x_elems(n) + cv.y_elems(n) + cv.w_elems
        t_min += flops.roofline_seconds(
            passes * cv.fwd_flops(n) / chips,
            passes * elems * ctx.word / chips,
            ctx.peaks["bf16_flops"], ctx.peaks["hbm_bytes_per_s"])[0]
    return 100.0 * t_min / seconds
