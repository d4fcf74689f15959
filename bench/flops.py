"""Operations and bytes of a network's convolutions, from shapes.

The yardstick for `mfu`, `busy_mfu` and `conv_roofline`: it counts what the
algorithm needs, from the configuration file's sizes alone, so it does not
move when the program does.  The configuration's model module
(bench/models/<model>.py) lists the convolutions; per layer (NHWC, 'SAME'
padding, a k x k filter from C to F channels at stride s):

  forward        2 * N * Ho * Wo * k*k * C * F
  weight grad    the same count (dL/dw contracts x with dL/dy)
  input grad     the same count, for every layer but the first: the image
                 needs no gradient, so a step that computes one wastes it

Bytes are the least each pass must move through HBM at the step's word
size: forward reads x and w and writes y; the weight gradient reads x and
dL/dy and writes dL/dw; the input gradient reads dL/dy and w and writes
dL/dx.  BN, ReLU and the loss are not counted: they carry no matmul work.
"""
from __future__ import annotations

import dataclasses

import cells


@dataclasses.dataclass(frozen=True)
class Conv:
    name: str
    c: int          # input channels
    f: int          # output channels
    k: int          # square kernel
    s: int          # stride
    h_in: int
    w_in: int

    @property
    def h_out(self) -> int:
        return -(-self.h_in // self.s)

    @property
    def w_out(self) -> int:
        return -(-self.w_in // self.s)

    def fwd_flops(self, n: int) -> int:
        return 2 * n * self.h_out * self.w_out * self.k * self.k * self.c \
            * self.f

    def x_elems(self, n: int) -> int:
        return n * self.h_in * self.w_in * self.c

    def y_elems(self, n: int) -> int:
        return n * self.h_out * self.w_out * self.f

    @property
    def w_elems(self) -> int:
        return self.k * self.k * self.c * self.f


def convs(config: dict) -> list[Conv]:
    """The network's convolutions in execution order, as the config's
    model module lists them."""
    return cells.model_of(config).convs(config)


def forward_flops(config: dict, n: int) -> int:
    return sum(cv.fwd_flops(n) for cv in convs(config))


def step_flops(config: dict, n: int) -> int:
    """Model FLOPs of one training step over a global batch of `n`:
    forward, weight gradient, and input gradient except the first layer's."""
    cs = convs(config)
    return sum(2 * cv.fwd_flops(n) for cv in cs) + \
        sum(cv.fwd_flops(n) for cv in cs[1:])


def step_bytes(config: dict, n: int, word: int) -> int:
    """Least HBM bytes the step's convolutions move (see module doc)."""
    total = 0
    for i, cv in enumerate(convs(config)):
        x, y, w = cv.x_elems(n), cv.y_elems(n), cv.w_elems
        total += x + w + y            # forward
        total += x + y + w            # weight gradient
        if i:
            total += y + w + x        # input gradient
    return total * word


def roofline_seconds(flops: float, nbytes: float, peak_flops: float,
                     peak_bw: float) -> tuple[float, str]:
    """The least time the chip could take, and which bound sets it."""
    t_c, t_m = flops / peak_flops, nbytes / peak_bw
    return (t_c, "compute") if t_c >= t_m else (t_m, "bandwidth")
