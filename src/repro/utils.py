"""Shared small utilities."""
from __future__ import annotations

import contextlib
import dataclasses
from typing import Any, Sequence

import jax
import jax.numpy as jnp
import numpy as np


def shard_map(f, *, mesh, in_specs, out_specs, axis_names=None,
              check_vma=None):
    """`jax.shard_map`, passing `axis_names` (the manual axes) and
    `check_vma` only when given so jax's own defaults apply otherwise."""
    kw = {}
    if axis_names is not None:
        kw["axis_names"] = axis_names
    if check_vma is not None:
        kw["check_vma"] = check_vma
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, **kw)


def pcast_varying(x, axes):
    """`lax.pcast(..., to='varying')` over `axes`; identity when empty."""
    if not axes:
        return x
    return jax.lax.pcast(x, tuple(axes), to="varying")


def cdiv(a: int, b: int) -> int:
    return -(-a // b)


def round_up(a: int, b: int) -> int:
    return cdiv(a, b) * b


def same_pads(k: int, s: int) -> tuple[int, int]:
    """TF/XLA 'SAME' padding amounts for kernel k, stride s, size % s == 0."""
    total = max(k - s, 0)
    lo = total // 2
    return lo, total - lo


def fingerprint(obj: Any) -> str:
    """Short stable content hash of a JSON-able object (dataclasses and
    tuples welcome) — how checkpoint manifests identify the model config
    and calibration a plan was solved against without embedding them."""
    import hashlib
    import json as _json
    if dataclasses.is_dataclass(obj) and not isinstance(obj, type):
        obj = dataclasses.asdict(obj)
    blob = _json.dumps(obj, sort_keys=True, default=str)
    return hashlib.sha1(blob.encode()).hexdigest()[:16]


def tree_size_bytes(tree: Any) -> int:
    return sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(tree))


def tree_num_params(tree: Any) -> int:
    return sum(x.size for x in jax.tree.leaves(tree))


def human_bytes(n: float) -> str:
    for unit in ("B", "KiB", "MiB", "GiB", "TiB"):
        if abs(n) < 1024.0:
            return f"{n:.2f}{unit}"
        n /= 1024.0
    return f"{n:.2f}PiB"


def human_count(n: float) -> str:
    for unit in ("", "K", "M", "B", "T"):
        if abs(n) < 1000.0:
            return f"{n:.2f}{unit}"
        n /= 1000.0
    return f"{n:.2f}Q"


def trimmed_mean(xs: Sequence[float], trim: float = 0.2) -> float:
    """Mean of `xs` after dropping the `trim` fraction from each tail —
    the robust estimator every benchmark timing loop in this repo uses
    (one slow outlier on a shared CI runner must not move the estimate)."""
    xs = sorted(xs)
    k = int(len(xs) * trim)
    kept = xs[k:len(xs) - k] or xs
    return sum(kept) / len(kept)


def time_fn(fn, *args, reps: int = 5, warmup: int = 1,
            trim: float = 0.2, return_samples: bool = False):
    """Wall-clock seconds per call of a jax callable (the shared benchmark
    timing loop: warmup calls absorb compilation, every timed rep blocks on
    the result, and the per-rep samples are trimmed-mean reduced).

    With `return_samples=True` returns ``(estimate, samples)`` — the raw
    per-rep seconds alongside the trimmed mean, so callers can report the
    measurement spread (p50/p95) instead of a bare point estimate.

    `benchmarks/_timing.py` re-exports this for the benchmark scripts; the
    calibrator (core.calibrate) injects it as its default timer.
    """
    import time as _time
    for _ in range(max(warmup, 1)):
        out = fn(*args)
        jax.tree.leaves(out)[0].block_until_ready()
    samples = []
    for _ in range(max(reps, 1)):
        t0 = _time.perf_counter()
        out = fn(*args)
        jax.tree.leaves(out)[0].block_until_ready()
        samples.append(_time.perf_counter() - t0)
    est = trimmed_mean(samples, trim)
    return (est, samples) if return_samples else est


def percentile(xs: Sequence[float], q: float) -> float:
    """Linear-interpolated percentile of `xs` (q in [0, 100]) — the spread
    statistic the benchmark columns report next to their point estimate."""
    xs = sorted(xs)
    if not xs:
        return float("nan")
    pos = (len(xs) - 1) * q / 100.0
    lo = int(pos)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def interleaved_samples(fns, reps: int = 5, rounds: int = 4):
    """Per-round mean seconds/call for competing callables:
    {tag: [round means]}.

    Candidates are timed in alternating rounds (A, B, A, B, ...) so
    machine-load drift during the run hits every candidate equally —
    timing each in one contiguous block makes their ratio track whatever
    else the host was doing rather than the candidates (observed 40%
    swings between *identical* programs).  Callables must already be
    compiled/warmed (call each once first) and take no arguments.

    `interleaved_min` reduces this to the comparable point estimate;
    callers wanting the spread (p50/p95 over rounds) use the samples.
    """
    import time as _time
    samples = {tag: [] for tag in fns}
    for _ in range(rounds):
        for tag, fn in fns.items():
            t0 = _time.perf_counter()
            for _ in range(max(reps, 1)):
                out = fn()
            jax.tree.leaves(out)[0].block_until_ready()
            samples[tag].append((_time.perf_counter() - t0) / max(reps, 1))
    return samples


def interleaved_min(fns, reps: int = 5, rounds: int = 4):
    """Comparative wall-clock for competing callables: {tag: seconds/call}.

    The per-tag estimate is the minimum over per-round means
    (interleaved_samples): the noise-floor round is the one where the host
    interfered least, and it is the comparable number across candidates.
    Shared by benchmarks/_timing (the benchmark scripts) and
    core.trace.trace_plan (the segmented re-execution profiler).
    """
    return {tag: min(ts)
            for tag, ts in interleaved_samples(fns, reps, rounds).items()}


def assert_no_nans(tree: Any, where: str = "") -> None:
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        arr = np.asarray(leaf)
        if np.isnan(arr).any():
            raise AssertionError(f"NaN in {where}{jax.tree_util.keystr(path)}")


@dataclasses.dataclass(frozen=True)
class Precision:
    """Mixed-precision policy."""
    param_dtype: Any = jnp.float32     # master weights
    compute_dtype: Any = jnp.bfloat16  # activations / matmul inputs
    accum_dtype: Any = jnp.float32     # softmax / loss / BN stats
    # conv/dot precision the step is traced under (None: JAX's default,
    # which on a TPU runs an fp32 conv as one bf16 MXU pass)
    matmul: str | None = None

    def cast_compute(self, tree):
        return jax.tree.map(
            lambda x: x.astype(self.compute_dtype)
            if jnp.issubdtype(x.dtype, jnp.floating) else x, tree)

    @property
    def compute_bytes(self) -> int:
        """Bytes per element of the compute dtype (the perf model's word)."""
        return jnp.dtype(self.compute_dtype).itemsize

    def scope(self):
        """Context in which traced convs and dots take `matmul`."""
        if self.matmul is None:
            return contextlib.nullcontext()
        return jax.default_matmul_precision(self.matmul)


# fp32 means fp32 through the MXU too: on a TPU v5e the default precision
# moved mesh1k's first-step loss by 2.6e-3 relative against `highest`
FP32 = Precision(jnp.float32, jnp.float32, jnp.float32, matmul="highest")
BF16 = Precision(jnp.float32, jnp.bfloat16, jnp.float32)
