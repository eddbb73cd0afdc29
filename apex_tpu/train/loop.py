"""Deferred-metrics training loop driver, with the robustness layer.

The serving engine's deferred sync (PR 3) restated for training: the
host must never stand between two device dispatches. A loop that reads
``loss`` right after ``step()`` serializes host and device — every step
pays a full dispatch + fetch round trip. :class:`TrainLoop` instead
keeps step ``t``'s metrics as unfetched device scalars, dispatches step
``t+1``, and only THEN fetches ``t``'s values: the fetch overlaps the
in-flight step, so the device queue never drains.

Contract (docs/training.md): ``loop.step(batch)`` returns the metrics
of the PREVIOUS step (``None`` on the first call); ``loop.drain()``
returns the final pending metrics after the last step. Metrics arrive
as host scalars (plain Python ``float``/``int``/``bool``), with any
``aux`` pytree left as numpy arrays.

Robustness (docs/robustness.md) — a long pretraining run survives the
three ways a step dies:

- **Transient dispatch failure**: the step call is retried up to
  ``max_retries`` times with exponential backoff. Sound when the
  failure precedes buffer consumption (the fault harness fires before
  the launch; a compile-service drop raises at dispatch) — a real
  mid-flight device failure with donated buffers is NOT retryable, and
  the loop re-raises for checkpoint recovery instead.
- **Non-finite loss**: amp's in-graph overflow skip already protects
  the params inside the graph, but it would happily skip *forever* on
  persistently-poisoned data. The host-side watchdog escalates on
  CONSECUTIVE non-finite losses: tolerate (skip) → halve the loss
  scale (rescale) → raise :class:`NonFiniteLossError` (halt). Because
  metrics are deferred, the watchdog sees step ``t`` after dispatching
  ``t+1``; its actions land one step late — the price of never
  blocking the device.
- **Process death**: periodic checkpoints of the (host-copied, so
  donation-safe) :class:`TrainState` via
  :mod:`apex_tpu.utils.checkpoint`; ``load_train_state`` +
  a fresh loop resumes bit-identically to the uninterrupted run
  (certified in tests/test_faults.py).
"""

from __future__ import annotations

import math
import dataclasses
from typing import Any, Dict, Iterable, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from apex_tpu import profiler
from apex_tpu.utils.faults import guarded_call


def _to_host(metrics) -> Dict[str, Any]:
    """One host fetch of a metrics pytree, scalars unwrapped to Python."""
    fetched = jax.device_get(metrics)

    def unwrap(x):
        arr = np.asarray(x)
        return arr.item() if arr.ndim == 0 else arr

    return jax.tree.map(unwrap, fetched)


def _fetch(metrics) -> Dict[str, Any]:
    """The loop's blocking fetch, as a ``train_fetch`` host annotation."""
    with profiler.annotate(profiler.TRAIN_FETCH):
        return _to_host(metrics)


class NonFiniteLossError(RuntimeError):
    """The watchdog's halt rung: the loss stayed non-finite through the
    skip and rescale rungs — training is wedged, and silently skipping
    every step forever would burn the cluster while the curves flatline.
    Carries the offending host ``metrics`` and the loop's ``stats()``."""

    def __init__(self, message: str, metrics: Dict[str, Any],
                 stats: Dict[str, Any]):
        super().__init__(f"{message} (metrics: {metrics})")
        self.metrics = metrics
        self.loop_stats = stats


@dataclasses.dataclass(frozen=True)
class WatchdogConfig:
    """The non-finite-loss escalation ladder, rung widths in
    CONSECUTIVE non-finite steps (a single finite loss resets the
    climb): the first ``skip_steps`` are tolerated (amp's in-graph skip
    already protected the params — this rung just counts), the next
    ``rescale_steps`` each halve the loss scale from the host (floored
    at ``min_scale``; a scale the in-graph backoff may be too slow to
    reach while every step overflows), and anything past that raises
    :class:`NonFiniteLossError`. Distinct from the scaler's own
    in-graph backoff: the watchdog is host policy about *giving up*,
    not graph arithmetic about the next scale."""

    skip_steps: int = 3
    rescale_steps: int = 3
    min_scale: float = 1.0
    loss_key: str = "loss"

    def __post_init__(self):
        if self.skip_steps < 0 or self.rescale_steps < 0:
            raise ValueError("watchdog rung widths must be >= 0")


class TrainLoop:
    """Drive a :class:`~apex_tpu.train.TrainStep` with deferred metric
    fetches.

    The loop OWNS the evolving :class:`TrainState`: with a donating step
    the previous state's buffers are consumed by each dispatch, so
    callers must not hold references to past states (see the donation
    caveats in docs/training.md). Read ``loop.state`` only between
    steps, and only the latest value.

    Keyword-only robustness knobs (all default off / inert):
    ``faults`` (a :class:`~apex_tpu.utils.faults.FaultPlan`, fired at
    site ``"train_step"`` before each dispatch), ``max_retries`` /
    ``retry_backoff_s`` (transient-failure retry), ``watchdog`` (a
    :class:`WatchdogConfig`), ``checkpoint_dir`` + ``checkpoint_every``
    (periodic :func:`apex_tpu.utils.checkpoint.save_train_state` every
    N completed steps — each save host-syncs the full state, so pick N
    against your step time), and ``obs`` (an
    :class:`~apex_tpu.observability.Observability` —
    docs/observability.md): a per-step host-span histogram, step/
    retry/non-finite counters with Prometheus exposition via
    ``stats(deep=True)``, and watchdog/checkpoint events into the
    flight recorder. Observation-only, like the engine's: nothing the
    loop computes ever reads observer state.
    """

    def __init__(self, train_step, state, *, faults=None,
                 max_retries: int = 2, retry_backoff_s: float = 0.0,
                 watchdog: Optional[WatchdogConfig] = None,
                 checkpoint_dir: Optional[str] = None,
                 checkpoint_every: int = 0, obs=None):
        self._train_step = train_step
        self.state = state
        self._obs = obs
        if obs is not None:
            obs.bind_train()
        self._pending = None  # last step's unfetched device metrics
        self._faults = faults
        self._max_retries = int(max_retries)
        self._retry_backoff_s = float(retry_backoff_s)
        self._watchdog = watchdog
        self._ckpt_dir = checkpoint_dir
        self._ckpt_every = int(checkpoint_every)
        self._steps_dispatched = 0
        self._retries = 0
        self._nonfinite_run = 0        # consecutive non-finite losses
        self._watchdog_trips = 0       # total non-finite losses observed
        self._watchdog_skips = 0
        self._watchdog_rescales = 0
        self._watchdog_halts = 0
        self._checkpoints_saved = 0
        self._last_checkpoint_step: Optional[int] = None
        self._record = profiler.compile_record()
        self._step_compiles: Dict[int, int] = {}   # loop step -> compiles
        # metrics collected by the current/last run(), INCLUDING the
        # finally-drained last step when run() unwinds on an exception
        self.last_run_metrics: List[Dict[str, Any]] = []

    # -- the dispatch path -------------------------------------------------

    def step(self, batch) -> Optional[Dict[str, Any]]:
        """Dispatch one global step; return the PREVIOUS step's metrics
        (fetched only now, while this step runs) — ``None`` on the
        first call. Transient dispatch failures retry with bounded
        backoff; exhaustion raises
        :class:`~apex_tpu.utils.faults.DispatchFailedError`. The
        watchdog inspects every fetched metrics dict and may raise
        :class:`NonFiniteLossError` from here (halt rung)."""
        obs = self._obs
        t0 = obs.now() if obs is not None else 0.0

        def count(attempt):
            self._retries += 1
            if obs is not None:
                obs.record("fault_retry", site="train_step",
                           attempt=attempt)
                obs.inc("retries")

        n = self._steps_dispatched + 1
        with self._record.program_span(profiler.TRAIN_DISPATCH, step=n,
                                       keep=False) as dispatch:
            (new_state, metrics), nan_hit = guarded_call(
                self._train_step, self.state, batch, plan=self._faults,
                site="train_step", retries=self._max_retries,
                backoff_s=self._retry_backoff_s, on_retry=count)
        self.state = new_state
        self._steps_dispatched = n
        if dispatch.kids is not None:      # something compiled: rare
            self._note_compiles(dispatch)
        if nan_hit:
            # the injected silent failure: the step ran, its loss is
            # garbage — exactly what the watchdog exists to catch
            metrics = dict(metrics)
            metrics[self._watchdog.loss_key if self._watchdog is not None
                    else "loss"] = float("nan")
        prev, self._pending = self._pending, metrics
        out = None if prev is None else _fetch(prev)
        if obs is not None:
            # the deferred-metrics host span: this step's dispatch plus
            # the PREVIOUS step's fetch — exactly what the loop's
            # overlap design is supposed to keep short
            dt = obs.now() - t0
            obs.inc("steps")
            obs.observe("step", dt)
            mesh_shape = getattr(self._train_step, "mesh_shape", None)
            if mesh_shape is not None:
                obs.record("train_step", step=self._steps_dispatched,
                           host_span_s=dt, mesh=list(mesh_shape))
            else:
                obs.record("train_step", step=self._steps_dispatched,
                           host_span_s=dt)
        if out is not None:
            self._observe(out, raise_on_halt=True)
        self._maybe_checkpoint()
        return out

    def drain(self, raise_on_halt: bool = False) -> Optional[Dict[str, Any]]:
        """Fetch the final pending metrics (call after the last
        :meth:`step`); ``None`` if nothing is pending. Also the
        loop-end synchronization barrier: once it returns, every
        dispatched step has executed. By default the watchdog observes
        (counts) the drained metrics but never raises from here —
        drain runs in ``finally`` blocks, where a fresh raise would
        mask the original failure. Pass ``raise_on_halt=True`` when
        nothing is unwinding (the completed-run drain), so a halt
        threshold first crossed by the LAST step's metrics still
        halts instead of returning a wedged run as success."""
        prev, self._pending = self._pending, None
        out = None if prev is None else _fetch(prev)
        if out is not None:
            self._observe(out, raise_on_halt=raise_on_halt)
        return out

    def run(self, batches: Iterable) -> List[Dict[str, Any]]:
        """Feed every batch, deferred throughout; returns all metrics in
        step order (the last entry fetched by the closing drain).

        The in-flight dispatch is drained in a ``finally``: an
        exception mid-iteration (watchdog halt, exhausted retries, a
        poisoned fetch) no longer silently drops the last completed
        step's metrics — everything fetched so far, including that
        final drain, stays readable on ``loop.last_run_metrics``."""
        out: List[Dict[str, Any]] = []
        self.last_run_metrics = out
        completed = False
        try:
            for batch in batches:
                m = self.step(batch)
                if m is not None:
                    out.append(m)
            completed = True
        finally:
            if completed:
                # nothing is unwinding here, so the watchdog may halt
                m = self.drain(raise_on_halt=True)
            else:
                # already unwinding: the drain must not mask the
                # original exception, so its own failure is dropped
                try:
                    m = self.drain()
                except Exception:
                    m = None
            if m is not None:
                out.append(m)
        return out

    def _note_compiles(self, dispatch) -> None:
        """A dispatch under which JAX traced, lowered or compiled: count
        its compiles at that loop step and tell the observer (a histogram
        per stage, the compile and cache-miss counters, and a ``compile``
        recorder event each)."""
        compiles = [s for s in dispatch.kids if s.stage == profiler.COMPILE]
        if compiles:
            self._step_compiles[dispatch.step] = len(compiles)
        obs = self._obs
        if obs is None:
            return
        for s in dispatch.kids:
            obs.observe(s.stage, s.seconds)
        obs.inc("compiles", len(compiles))
        obs.inc("cache_misses", sum(s.cache != "hit" for s in compiles))
        for s in compiles:
            obs.record("compile", step=dispatch.step, fun_name=s.fun_name,
                       seconds=s.seconds, cache=s.cache,
                       program=self._record.program_of(s, {}))

    # -- the non-finite-loss watchdog --------------------------------------

    def _observe(self, metrics: Dict[str, Any], raise_on_halt: bool) -> None:
        wd = self._watchdog
        if wd is None:
            return
        loss = metrics.get(wd.loss_key)
        if loss is None:
            return
        if math.isfinite(float(loss)):
            self._nonfinite_run = 0
            return
        self._nonfinite_run += 1
        self._watchdog_trips += 1
        obs = self._obs
        if obs is not None:
            obs.inc("nonfinite")
        run = self._nonfinite_run
        if run <= wd.skip_steps:
            self._watchdog_skips += 1
            if obs is not None:
                obs.record("watchdog", action="skip", run=run)
        elif run <= wd.skip_steps + wd.rescale_steps:
            self._watchdog_rescales += 1
            if obs is not None:
                obs.record("watchdog", action="rescale", run=run)
            self._rescale(wd)
        elif raise_on_halt:
            # counted only when actually raised: a drain (already
            # unwinding) may observe one more halt-level loss, which is
            # the same failure, not a second halt
            self._watchdog_halts += 1
            if obs is not None:
                obs.record("watchdog", action="halt", run=run)
                obs.incident("watchdog_halt", run=run)
            raise NonFiniteLossError(
                f"loss non-finite for {run} consecutive steps "
                f"(through {wd.skip_steps} skips and "
                f"{wd.rescale_steps} rescales)", metrics, self.stats())

    def _rescale(self, wd: WatchdogConfig) -> None:
        """The ladder's middle rung: halve the loss scale FROM THE HOST
        (one scalar fetch + re-upload — rare by construction). The
        scaler's own in-graph backoff does this too, but only per
        overflow step and only down its own schedule; the watchdog's
        version is the blunt recovery lever for runs where every step
        overflows and waiting for the in-graph walk means burning the
        job."""
        sst = self.state.scaler_state
        cur = float(jax.device_get(sst.loss_scale))
        new = max(cur / 2.0, wd.min_scale)
        fresh = jnp.asarray(new, jnp.float32)
        # a mesh-sharded state (the GSPMD train step) commits every
        # leaf; the replacement scalar must land on the same sharding
        # or the next dispatch retraces on the one uncommitted leaf
        sharding = getattr(sst.loss_scale, "sharding", None)
        if getattr(sharding, "mesh", None) is not None:
            fresh = jax.device_put(fresh, sharding)
        self.state = self.state._replace(
            scaler_state=sst._replace(loss_scale=fresh))

    # -- checkpoint / resume ----------------------------------------------

    def save_checkpoint(self) -> str:
        """Host-copy the current :class:`TrainState` and write it under
        ``checkpoint_dir`` (step number read from ``state.step``).
        Forces a device sync of the whole state — donation-safe, since
        the copy owns its buffers. Returns the checkpoint path."""
        from apex_tpu.utils.checkpoint import save_train_state

        if self._ckpt_dir is None:
            raise ValueError("TrainLoop was built without checkpoint_dir")
        path = save_train_state(self._ckpt_dir, self.state)
        self._checkpoints_saved += 1
        self._last_checkpoint_step = int(
            np.asarray(jax.device_get(self.state.step)))
        if self._obs is not None:
            self._obs.inc("checkpoints")
            self._obs.record("checkpoint",
                             step=self._last_checkpoint_step, path=path)
        return path

    def _maybe_checkpoint(self) -> None:
        if (self._ckpt_dir is None or self._ckpt_every <= 0
                or self._steps_dispatched % self._ckpt_every):
            return
        self.save_checkpoint()

    # -- observability -----------------------------------------------------

    def stats(self, deep: bool = False) -> Dict[str, Any]:
        """Failure-path counters (docs/robustness.md): everything the
        chaos suite asserts nonzero rides here. ``deep=True`` merges
        the attached observer's section (metric values, recorder
        depth) under ``"observability"`` — the same contract as
        ``InferenceEngine.stats(deep=True)``
        (docs/observability.md)."""
        out = {
            "steps_dispatched": self._steps_dispatched,
            "dispatch_retries": self._retries,
            "watchdog_nonfinite": self._watchdog_trips,
            "watchdog_skips": self._watchdog_skips,
            "watchdog_rescales": self._watchdog_rescales,
            "watchdog_halts": self._watchdog_halts,
            "checkpoints_saved": self._checkpoints_saved,
            "last_checkpoint_step": self._last_checkpoint_step,
            "step_compiles": dict(self._step_compiles),
        }
        if deep and self._obs is not None:
            out["observability"] = self._obs.deep_stats()
        return out
