"""Production mesh construction.

The production target is a TPU v5e pod slice: 256 chips arranged (16, 16)
with logical axes ("data", "model"); the multi-pod configuration prepends a
"pod" axis of size 2 (512 chips).  Axis roles:

  pod    pure data parallelism across pods (DCN); cross-pod gradient
         reduction optionally compressed (repro.optim.grad_compress).
  data   sample parallelism (paper's N dimension) + FSDP weight sharding.
  model  the paper's fine-grained axis: spatial (H) for CNNs, sequence for
         transformers/SSMs; beyond-paper channel/filter (TP/EP) parallelism
         lives on the same axis, selectable per layer (core.strategy).

Defined as functions (never module-level constants) so importing this module
does not touch jax device state.
"""
from __future__ import annotations

import jax

DATA_AXES = ("pod", "data")     # axes that shard the sample (N) dimension
MODEL_AXIS = "model"            # the paper's fine-grained axis


def _mk(shape, axes, devices=None):
    kw = {"devices": devices} if devices is not None else {}
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes),
                         **kw)


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mk(shape, axes)


def make_mesh(data: int = 1, model: int = 1, pod: int = 1, devices=None):
    """Small/elastic mesh for tests, examples and CPU runs.

    Always uses the same axis names as production so every sharding rule and
    shard_map island is identical from 1 chip to 512 — this is the elastic-
    scaling contract: checkpoints are mesh-independent (global shapes) and any
    (pod, data, model) factorization of the available devices works.

    `devices` restricts the mesh to an explicit device list — how an
    elastic restart rebuilds over the *survivors* of a device loss (and
    how tests carve a 4-device mesh out of an 8-device backend).
    """
    ndev = len(devices) if devices is not None else jax.device_count()
    if pod * data * model > ndev:
        raise ValueError(f"mesh {(pod, data, model)} needs {pod*data*model} "
                         f"devices, have {ndev}")
    if devices is not None:
        devices = list(devices)[:pod * data * model]
    if pod > 1:
        return _mk((pod, data, model), ("pod", "data", "model"), devices)
    return _mk((data, model), ("data", "model"), devices)


def elastic_factorization(n: int, *, batch: int | None = None
                          ) -> tuple[int, int]:
    """A (data, model) factorization of `n` surviving devices.

    Prefers the most balanced split whose data size divides the global
    batch (sample parallelism needs N % data == 0); when nothing divides —
    e.g. 3 survivors with batch 4 — everything lands on the model axis,
    where the paper's fine-grained spatial/CF parallelism needs no batch
    divisibility at all.  This is what makes a 4->3 shrink solvable.
    """
    best = 1
    for data in range(1, int(n ** 0.5) + 1):
        if n % data == 0 and (batch is None or batch % data == 0):
            best = data
    return best, n // best


def batch_axes(mesh) -> tuple[str, ...]:
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)


def model_axis_size(mesh) -> int:
    return mesh.shape.get(MODEL_AXIS, 1)
