"""Training-loop callbacks.

Reference: ``horovod/_keras/callbacks.py`` — ``BroadcastGlobalVariables``
(:23-47), ``MetricAverageCallback`` (:49-93), ``LearningRateWarmupCallback``
(:118-192). The reference hooks Keras; here the hooks are framework-neutral
callables for JAX training loops (works with any loop that calls
``on_train_begin`` / ``on_epoch_end``-style hooks or uses them directly).
"""

from __future__ import annotations

import re
import time
from typing import Any, Callable, Dict, Optional

import numpy as np

from horovod_tpu.common.basics import rank, size
from horovod_tpu.common.logging import get_logger
from horovod_tpu.metrics.registry import Gauge, Registry, default_registry
from horovod_tpu.ops import collectives as C
from horovod_tpu.ops.reduce_op import Average


class BroadcastGlobalVariablesCallback:
    """Broadcast params/opt-state from root at training start (reference:
    ``BroadcastGlobalVariablesCallbackImpl:23-47``)."""

    def __init__(self, root_rank: int = 0) -> None:
        self.root_rank = root_rank

    def on_train_begin(self, params, opt_state=None):
        from horovod_tpu.train.optimizer import (broadcast_optimizer_state,
                                                 broadcast_parameters)
        params = broadcast_parameters(params, self.root_rank)
        if opt_state is not None:
            opt_state = broadcast_optimizer_state(opt_state, self.root_rank)
            return params, opt_state
        return params


class MetricAverageCallback:
    """Average logged metrics across workers at epoch end (reference:
    ``MetricAverageCallbackImpl:49-93``)."""

    def on_epoch_end(self, logs: Dict[str, Any]) -> Dict[str, Any]:
        if size() == 1:
            return dict(logs)
        out = {}
        for k, v in logs.items():
            if isinstance(v, (int, float, np.floating, np.integer)):
                red = C.allreduce(np.asarray([float(v)], np.float64),
                                  op=Average, name=f"metric.{k}")
                out[k] = float(np.asarray(red)[0])
            else:
                out[k] = v
        return out


class StepTimer:
    """Step-time + throughput recorder feeding the metrics registry.

    Records every step into ``hvd_step_time_seconds`` (log-scale
    histogram), counts steps and processed units (images / tokens /
    sequences — your choice of ``unit``), and keeps live gauges for
    units/s and, when FLOPs are known, MFU. Everything it writes appears
    on the worker's ``/metrics`` endpoint and in
    ``hvd.metrics_snapshot()["registry"]``.

    Use directly::

        timer = StepTimer(unit="images")
        for batch in data:
            with timer.step(units=batch_size):
                state, loss = train_step(state, batch)
            # or: timer.start_step(); ...; timer.end_step(units=...)

    ``flops_per_step`` is per-device FLOPs for ONE step (see
    :func:`horovod_tpu.metrics.mfu.hlo_flops_per_device`); the peak is
    looked up from the local chip on first use.
    """

    def __init__(self, unit: str = "examples",
                 flops_per_step: Optional[float] = None,
                 registry: Optional[Registry] = None) -> None:
        reg = registry or default_registry()
        self._reg = reg
        self.unit = unit
        # "tokens/s" or "img-sec" would break the Prometheus metric-name
        # charset and take the whole /metrics response down with it
        metric_unit = re.sub(r"[^a-zA-Z0-9_]", "_", unit)
        self.step_time = reg.histogram(
            "hvd_step_time_seconds", help="training step wall time")
        self.steps = reg.counter("hvd_steps_total",
                                 help="training steps completed")
        self.units = reg.counter(f"hvd_{metric_unit}_total",
                                 help=f"{unit} processed")
        self.throughput = reg.gauge(
            f"hvd_{metric_unit}_per_second",
            help=f"{unit}/s over the last step (sum across workers)",
            agg="sum")
        # registered lazily on the first computed MFU: an eager gauge
        # would export 0.0 from workers that never compute MFU and drag
        # the mean-merged fleet value toward zero
        self.mfu_gauge: Optional[Gauge] = None
        self.flops_per_step = flops_per_step
        self._peak: Any = _UNSET
        self._t0: Optional[float] = None
        self.last_step_seconds: Optional[float] = None
        # MFU actually computed for the most recent step, None when it
        # could not be (flops or device peak unknown) — the gauge's 0.0
        # default is indistinguishable from a measured zero
        self.last_mfu: Optional[float] = None

    def set_flops_per_step(self, flops: Optional[float]) -> None:
        self.flops_per_step = flops

    def start_step(self) -> None:
        self._t0 = time.perf_counter()
        from horovod_tpu.diagnostics.flight_recorder import record_event
        # +1: number the step being ENTERED, matching the post-increment
        # number its step_end will carry (begin/end pairs must agree)
        step_no = int(self.steps.value) + 1
        record_event("step_begin", step=step_no)
        # deep-profiling seam (docs/OBSERVABILITY.md "Deep profiling"):
        # a pending capture request opens its jax.profiler window at
        # this step boundary; cheap no-op otherwise
        from horovod_tpu import profiling
        profiling.on_step_begin(step_no)
        # goodput ledger (docs/OBSERVABILITY.md "Goodput ledger"): the
        # step envelope is the ledger's spine — begin/end bracket the
        # in-step account, the gap between them is the out-of-step one
        from horovod_tpu.metrics import goodput
        goodput.note_step_begin()

    def end_step(self, units: float = 0.0) -> Optional[float]:
        """Close the step opened by :meth:`start_step`; returns the step
        seconds (None if no step was open)."""
        if self._t0 is None:
            return None
        dt = time.perf_counter() - self._t0
        self._t0 = None
        self.last_step_seconds = dt
        self.step_time.observe(dt)
        self.steps.inc()
        # a completed step IS forward progress: feed the hang watchdog
        # and the flight recorder (docs/OBSERVABILITY.md)
        step_no = int(self.steps.value)
        from horovod_tpu.diagnostics.flight_recorder import record_event
        from horovod_tpu.diagnostics.watchdog import notify_progress
        record_event("step_end", step=step_no, seconds=round(dt, 6))
        notify_progress(step_no)
        # step-aligned history: the bounded ring (always) + the
        # HVD_TPU_OBS_DIR JSONL (when set) — docs/OBSERVABILITY.md
        # "Step time-series history"
        from horovod_tpu.metrics import timeseries
        timeseries.record_step(step_no, dt, units)
        # deep-profiling seam: close an active capture window when its
        # step budget is spent, and sample the HBM gauges; a completed
        # step also closes the re-mesh timeline's first_step phase
        from horovod_tpu import profiling
        profiling.on_step_end(step_no)
        from horovod_tpu.elastic import remesh
        remesh.note_step_end(step_no)
        from horovod_tpu.metrics import goodput
        goodput.note_step_end(dt)
        if units:
            self.units.inc(units)
            if dt > 0:
                self.throughput.set(units / dt)
        self.last_mfu = None
        if self.flops_per_step and dt > 0:
            if self._peak is _UNSET:
                from horovod_tpu.metrics.mfu import device_peak_flops
                # None off-TPU; an untabled TPU raises (no guessed MFU)
                self._peak = device_peak_flops()
            if self._peak:
                self.last_mfu = self.flops_per_step / dt / self._peak
                if self.mfu_gauge is None:
                    self.mfu_gauge = self._reg.gauge(
                        "hvd_mfu",
                        help="model FLOPs utilization of the last step",
                        agg="mean")
                self.mfu_gauge.set(self.last_mfu)
        return dt

    class _StepCtx:
        def __init__(self, timer: "StepTimer", units: float) -> None:
            self._timer = timer
            self._units = units

        def __enter__(self):
            self._timer.start_step()
            return self._timer

        def __exit__(self, exc_type, exc, tb):
            if exc_type is None:
                self._timer.end_step(self._units)
            else:
                self._timer._t0 = None  # failed step: don't pollute stats
            return False

    def step(self, units: float = 0.0) -> "StepTimer._StepCtx":
        return StepTimer._StepCtx(self, units)


_UNSET = object()


class TelemetryCallback:
    """Train-loop hook bundle around :class:`StepTimer`.

    Call ``on_step_begin()`` / ``on_step_end()`` from any loop (same hook
    style as the other callbacks in this module). FLOPs for MFU are
    resolved lazily on the first completed step from ``lowerable`` — a
    zero-arg callable returning ``(jitted, args)`` — via the compiled
    executable's cost analysis
    (:func:`horovod_tpu.metrics.mfu.hlo_flops_per_device`);
    a failure there just leaves MFU unset, never breaks the loop.

    ``log_every_n_steps`` > 0 logs a one-line telemetry summary (step
    time, units/s, MFU) through the rank-tagged logger.

    ``profile_steps`` > 0 schedules a ProfileManager device-trace
    capture of the FIRST ``profile_steps`` training steps
    (docs/OBSERVABILITY.md "Deep profiling"); independent of it, the
    anomaly engine can fire captures later in the run
    (``HVD_TPU_PROFILE_ON_ANOMALY``).

    Creating the callback also arms the process-wide hang watchdog
    (``HVD_TPU_WATCHDOG_SECONDS``, default 600; 0 disarms): if no step
    completes for that long, an autopsy bundle is written —
    docs/OBSERVABILITY.md "Flight recorder & hang autopsy".
    """

    def __init__(self, units_per_step: float = 0.0,
                 unit: str = "examples",
                 lowerable: Optional[Callable[[], tuple]] = None,
                 flops_per_step: Optional[float] = None,
                 hlo_flops_factor: int = 1,
                 log_every_n_steps: int = 0,
                 profile_steps: int = 0,
                 registry: Optional[Registry] = None) -> None:
        self.timer = StepTimer(unit=unit, flops_per_step=flops_per_step,
                               registry=registry)
        self.units_per_step = units_per_step
        self._lowerable = lowerable
        self._hlo_factor = hlo_flops_factor
        self._log_every = log_every_n_steps
        self._steps = 0
        # armed-by-default: a training loop with telemetry gets hang
        # autopsies for free (None when WATCHDOG_SECONDS=0).  Only for
        # an INITIALIZED process: a callback constructed without
        # hvd.init (unit tests, dry imports) has no world to autopsy,
        # and a leaked 600s daemon in a long pytest process would
        # eventually fire mid-suite — the false positive the acceptance
        # criteria forbid.
        from horovod_tpu.common.basics import is_initialized
        from horovod_tpu.diagnostics.watchdog import ensure_watchdog
        self.watchdog = ensure_watchdog() if is_initialized() else None
        # online anomaly engine (docs/OBSERVABILITY.md "Anomaly
        # engine"; HVD_TPU_ANOMALY=0 disables): every completed step
        # feeds the drift detectors — a degradation is flagged as an
        # hvd_anomaly_total{kind} counter + flight event while the job
        # still runs, and lands in any later autopsy bundle's summary
        from horovod_tpu.metrics.anomaly import default_engine
        self.anomaly_engine = default_engine()
        # compile observability rides every telemetry loop (idempotent;
        # HVD_TPU_COMPILE_METRICS=0 disables)
        from horovod_tpu.profiling import compile_watch
        compile_watch.ensure_installed()
        if profile_steps > 0:
            # armed now, opens at the first step boundary
            from horovod_tpu.profiling import default_manager
            default_manager().request_capture(steps=profile_steps,
                                              reason="telemetry")

    def on_train_begin(self, *args, **kwargs):
        return args[0] if len(args) == 1 else (args or None)

    def on_step_begin(self) -> None:
        self.timer.start_step()
        # chaos `step` seam (docs/CHAOS.md): rank kill/stall schedules
        # key on the step counter; dead when no fault plan is armed.
        # AFTER start_step: an injected stall must land INSIDE the
        # timed window — it models a slow step, and the observability
        # plane (step-time histogram, time-series, anomaly engine) has
        # to see it exactly like a real one (a kill/exit does not care,
        # and this way the step_begin flight event precedes it)
        from horovod_tpu import chaos
        chaos.step_tick(self._steps)

    def on_step_end(self, units: Optional[float] = None) -> None:
        dt = self.timer.end_step(
            self.units_per_step if units is None else units)
        self._steps += 1
        if self.anomaly_engine is not None and dt is not None:
            # exposed-comm gauge is optional (eager overlap path only);
            # Registry.get never creates — absent stays absent
            exposed = self.timer._reg.get(
                "hvd_overlap_exposed_comm_seconds")
            thr = self.timer.throughput.value or None
            try:
                self.anomaly_engine.observe_step(
                    int(self.timer.steps.value), dt, units_per_s=thr,
                    exposed_comm_s=exposed.value
                    if exposed is not None else None)
            except Exception:
                pass  # detection must never break the loop
        if self.timer.flops_per_step is None and self._lowerable is not None:
            from horovod_tpu.metrics.mfu import hlo_flops_per_device
            try:
                jitted, fargs = self._lowerable()
                self.timer.set_flops_per_step(hlo_flops_per_device(
                    jitted, fargs, factor=self._hlo_factor))
            except Exception:
                pass
            finally:
                self._lowerable = None  # one attempt: lowering isn't free
        if self._log_every > 0 and self._steps % self._log_every == 0 \
                and dt is not None:
            get_logger().info(
                "telemetry: step %d took %.4fs (%.1f %s/s, mfu=%s)",
                self._steps, dt,
                self.timer.throughput.value, self.timer.unit,
                f"{self.timer.last_mfu:.3f}"
                if self.timer.last_mfu is not None else "n/a")

    def on_epoch_end(self, logs: Dict[str, Any]) -> Dict[str, Any]:
        """Pass-through hook so the callback can ride the same list as
        :class:`MetricAverageCallback`."""
        return logs

    def on_train_end(self, *args, **kwargs) -> None:
        """Stand down the hang watchdog: after the last step, a long
        eval/export phase with no step completions is legitimate, not a
        hang (the watchdog is suspended, not dropped — a later
        ``hvd.init`` or ``ensure_watchdog`` re-arms it)."""
        from horovod_tpu.diagnostics import watchdog as _wd
        _wd.suspend()


class CheckpointCallback:
    """Durable periodic checkpointing through the native sharded store
    (:class:`horovod_tpu.checkpoint.ShardedCheckpointer`; docs/ELASTIC.md
    "Durable commits").  Every rank must run the callback — each writes
    only its shard of the state.

    Hooks follow this module's convention::

        ckpt = CheckpointCallback("/ckpt/run1", every_n_steps=200)
        state = ckpt.on_train_begin(state)      # resume if possible
        for step in range(ckpt.next_step, total_steps):
            state = train_step(state, batch)
            ckpt.on_step_end(step, state)       # async save every N
        ckpt.on_train_end(step, state)          # final synchronous save

    Saves are asynchronous (device→host snapshot inline, disk on the
    store's writer thread); save/restore bytes + durations land on
    ``/metrics``.  ``directory`` defaults to the ``CHECKPOINT_DIR`` env
    knob (docs/KNOBS.md).
    """

    def __init__(self, directory: Optional[str] = None,
                 every_n_steps: int = 100,
                 max_to_keep: Optional[int] = None,
                 max_inflight: Optional[int] = None,
                 store=None) -> None:
        if store is None:
            from horovod_tpu.checkpoint import ShardedCheckpointer
            from horovod_tpu.common.config import env_str
            directory = directory or env_str("CHECKPOINT_DIR")
            if not directory:
                raise ValueError(
                    "CheckpointCallback needs a directory (argument or "
                    "the CHECKPOINT_DIR / HVD_TPU_CHECKPOINT_DIR env "
                    "knob)")
            store = ShardedCheckpointer(directory, max_to_keep=max_to_keep,
                                        max_inflight=max_inflight)
        self.store = store
        self.every_n_steps = int(every_n_steps)
        self.restored_step: Optional[int] = None
        self._last_saved = -1

    @property
    def next_step(self) -> int:
        """First step the loop should run: 0 on a fresh start,
        ``restored_step + 1`` after a restore (restored_step can BE 0 —
        don't use ``restored_step or -1``, 0 is falsy)."""
        return 0 if self.restored_step is None else self.restored_step + 1

    def on_train_begin(self, state):
        """Restore the latest checkpoint onto the CURRENT mesh (``state``
        is the ``like=`` template) or return ``state`` untouched."""
        out = self.store.restore_latest(like=state)
        if out is None:
            return state
        self.restored_step = self.store.latest_step()
        self._last_saved = self.restored_step
        return out

    def on_step_end(self, step: int, state) -> None:
        if self.every_n_steps > 0 and step > self._last_saved \
                and step % self.every_n_steps == 0:
            self.store.save(step, state)
            self._last_saved = step

    def on_epoch_end(self, logs: Dict[str, Any]) -> Dict[str, Any]:
        """Pass-through so the callback rides the same list as
        :class:`MetricAverageCallback`."""
        return logs

    def on_train_end(self, step: Optional[int] = None,
                     state: Any = None) -> None:
        """Final synchronous save (when ``step``/``state`` are given and
        newer than the last save), then drain the writer."""
        if state is not None and step is not None \
                and step > self._last_saved:
            self.store.save(step, state)
            self._last_saved = step
        self.store.wait()

    def close(self) -> None:
        self.store.close()


class LearningRateWarmupCallback:
    """Linear LR warmup from ``initial_lr/size`` to ``initial_lr * size``
    over warmup epochs (reference: ``LearningRateWarmupCallbackImpl:118-192``
    — the "facebook 1-hour" scaling recipe). Returns a schedule fn usable as
    an optax learning-rate schedule."""

    def __init__(self, initial_lr: float, warmup_epochs: int = 5,
                 steps_per_epoch: int = 1, momentum_correction: bool = True,
                 verbose: bool = False) -> None:
        self.initial_lr = initial_lr
        self.warmup_epochs = warmup_epochs
        self.steps_per_epoch = steps_per_epoch
        self.verbose = verbose

    def schedule(self) -> Callable[[int], float]:
        import jax.numpy as jnp
        scale = size()
        warm_steps = max(1, self.warmup_epochs * self.steps_per_epoch)
        base = self.initial_lr

        def fn(step):
            frac = jnp.minimum(step / warm_steps, 1.0)
            # exponential ramp from lr to lr*size (reference uses
            # lr * (size ** (epoch/warmup)) per batch)
            return base * (scale ** frac)

        return fn
