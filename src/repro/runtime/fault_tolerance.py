"""Fault-tolerant training driver + straggler monitoring + elastic restart.

On a 1000+-node fleet the failure model is: any step may raise (XLA error,
host OOM, preempted worker surfacing as a collective timeout), and a node
may *leave* — the device set shrinks.  The driver's contract:

  * checkpoint every `ckpt_every` steps (async, atomic — see
    repro.checkpoint), recording the solved plan spec in the manifest;
  * on a step fault: roll back to the latest committed checkpoint, rebuild
    the step function (fresh compilation), continue; give up after
    `max_failures` *consecutive* failures.  A fault before any checkpoint
    is committed re-raises at once: the jitted step donates its input
    state, so there is nothing intact to retry from;
  * on device loss (`DeviceLoss`, carrying the surviving devices): hand
    the survivors to the `remesh` callback, which rebuilds the mesh from
    them, re-solves the plan on the shrunk mesh under the same mem_limit
    (launch.train --elastic), and returns a fresh step factory plus a
    state template sharded under the new mesh — the checkpoint's global
    arrays then reshard-on-restore into it;
  * deterministic data: batches are derived from the step index, so a
    restart replays the exact stream (no sample skips/duplicates);
  * observability: with a `metrics` MetricsLogger every fault, rollback,
    remesh and flagged straggler emits a ``repro/metrics@1`` event record.

StragglerMonitor implements the detection half of straggler mitigation: an
online median/MAD filter over step times; slow steps beyond `k` MADs are
flagged and counted.  On a real cluster the action hook would evict/replace
the slow host (the SPMD program itself cannot out-run its slowest member);
in-process we expose the hook + stats, and the *prevention* levers live in
the step itself (static shapes everywhere -> no recompile jitter; async
checkpointing -> no I/O stalls on the critical path).
"""
from __future__ import annotations

import dataclasses
import logging
import time
from typing import Any, Callable, Sequence

import numpy as np

log = logging.getLogger("repro.runtime")


class DeviceLoss(RuntimeError):
    """A step fault caused by devices leaving the fleet.

    Carries the devices that survive; a `ResilientLoop` with a `remesh`
    callback recovers elastically, anything else treats it as fatal (a
    same-mesh retry cannot succeed without the lost devices).
    """

    def __init__(self, survivors: Sequence, message: str | None = None):
        self.survivors = list(survivors)
        super().__init__(message or
                         f"device loss: {len(self.survivors)} survivors")


class StragglerMonitor:
    def __init__(self, k: float = 5.0, warmup: int = 3,
                 action: Callable[[int, float], None] | None = None):
        self.k = k
        self.warmup = warmup
        self.times: list[float] = []
        self.flagged: list[tuple[int, float]] = []
        self.action = action

    def record(self, step: int, dt: float) -> bool:
        self.times.append(dt)
        if len(self.times) <= self.warmup:
            return False
        hist = np.asarray(self.times[:-1])
        med = np.median(hist)
        mad = np.median(np.abs(hist - med)) + 1e-9
        if dt > med + self.k * mad and dt > 1.5 * med:
            self.flagged.append((step, dt))
            log.warning("straggler step %d: %.3fs (median %.3fs)",
                        step, dt, med)
            if self.action:
                self.action(step, dt)
            return True
        return False

    @property
    def stats(self) -> dict:
        t = np.asarray(self.times) if self.times else np.zeros(1)
        return {"median": float(np.median(t)), "p95": float(np.percentile(t, 95)),
                "flagged": len(self.flagged)}


@dataclasses.dataclass
class ResilientLoop:
    """Runs `run_step(state, step) -> state, metrics` with checkpoint/restart.

    `state` is an arbitrary pytree (params, opt state, ef state, ...).
    `make_step` rebuilds the compiled step fn after a failure.
    `remesh` (optional) handles `DeviceLoss`: survivors ->
    (new make_step factory, state template sharded under the new mesh);
    the loop then reshards-on-restore the last checkpoint into the
    template and replays from its step.  Without `remesh`, DeviceLoss is
    fatal — retrying the same mesh without the lost devices cannot work.
    `plan_spec` (dict or zero-arg callable returning one) is recorded in
    every checkpoint manifest; `metrics` (train.metrics.MetricsLogger)
    streams fault/rollback/remesh/straggler events as JSONL records.
    """
    ckpt: Any                      # CheckpointManager
    make_step: Callable[[], Callable]
    ckpt_every: int = 50
    max_failures: int = 3
    remesh: Callable[[Sequence], tuple[Callable, Any]] | None = None
    metrics: Any = None            # MetricsLogger | None
    plan_spec: Any = None          # dict | Callable[[], dict] | None

    def _plan(self) -> dict | None:
        return self.plan_spec() if callable(self.plan_spec) \
            else self.plan_spec

    def _event(self, kind: str, **fields):
        if self.metrics is not None:
            self.metrics.log_event(kind, **fields)

    def _rollback(self, state_like, start_step: int):
        """Restore the latest committed checkpoint into `state_like`'s
        structure and shardings (reshard-on-restore); fall back to the
        template itself at `start_step` when nothing is committed yet."""
        restored, manifest = self.ckpt.restore(state_like)
        if restored is not None:
            step = manifest["extra"]["step"]
            log.info("rolled back to step %d", step)
            self._event("rollback", step=step)
            return restored, step
        self._event("rollback", step=start_step, note="no checkpoint")
        return state_like, start_step

    def run(self, state, start_step: int, num_steps: int,
            monitor: StragglerMonitor | None = None,
            inject_failure: Callable[[int], None] | None = None):
        step_fn = self.make_step()
        failures = 0
        step = start_step
        metrics = None
        while step < num_steps:
            try:
                t0 = time.perf_counter()
                if inject_failure:
                    inject_failure(step)           # test hook
                state, metrics = step_fn(state, step)
                dt = time.perf_counter() - t0
                if monitor and monitor.record(step, dt):
                    self._event("straggler", step=step, dt_s=dt,
                                **monitor.stats)
                failures = 0
                step += 1
                if step % self.ckpt_every == 0:
                    self.ckpt.save(step, state, extra={"step": step},
                                   plan=self._plan())
            except KeyboardInterrupt:
                raise
            except DeviceLoss as e:
                failures += 1
                log.error("step %d lost devices (%d survive); "
                          "failure %d/%d", step, len(e.survivors),
                          failures, self.max_failures)
                self._event("fault", step=step, error="DeviceLoss",
                            survivors=len(e.survivors), failures=failures)
                if failures > self.max_failures or self.remesh is None:
                    raise
                self.ckpt.wait()
                # elastic restart: new mesh + re-solved plan from the
                # survivors, then reshard-on-restore into its template
                self.make_step, state_like = self.remesh(e.survivors)
                self._event("remesh", step=step,
                            n_devices=len(e.survivors))
                state, step = self._rollback(state_like, start_step)
                step_fn = self.make_step()
            except Exception as e:     # noqa: BLE001 — any step fault
                failures += 1
                log.error("step %d failed (%s); failure %d/%d",
                          step, type(e).__name__, failures,
                          self.max_failures)
                self._event("fault", step=step, error=type(e).__name__,
                            failures=failures)
                if failures > self.max_failures:
                    raise
                self.ckpt.wait()
                if self.ckpt.latest_step() is None:
                    # no checkpoint: the state in hand may be the step's
                    # donated (deleted) input, and a retry on it would
                    # bury this error under "Array has been deleted"
                    raise
                state, step = self._rollback(state, start_step)
                step_fn = self.make_step()          # fresh compile
        self.ckpt.wait()
        return state, step, metrics
