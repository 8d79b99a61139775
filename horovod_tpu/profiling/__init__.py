"""Deep-profiling subsystem: evidence of *why*, captured exactly when
the cheap always-on layer says something is wrong.

Layers (docs/OBSERVABILITY.md "Deep profiling" / "Compile & memory
observability"):

* :mod:`horovod_tpu.profiling.manager` — bounded, step-windowed
  ``jax.profiler`` device traces (on demand, scheduled, or fired by the
  anomaly engine);
* :mod:`horovod_tpu.profiling.compile_watch` — compile-time metrics,
  tracing-cache misses, and the ``recompile_storm`` detector;
* :mod:`horovod_tpu.profiling.memory` — per-device HBM gauges + the
  ``hbm_growth`` slow-leak detector.

This package owns the two cross-cutting seams:

* the **step seam** — :func:`on_step_begin` / :func:`on_step_end`,
  called by :class:`horovod_tpu.train.callbacks.StepTimer` on every
  step (cheap no-ops unless a capture is pending/active or the HBM
  sampler has a backend that reports stats);
* the **anomaly seam** — :func:`on_anomaly`, called by the anomaly
  engine for every finding: when ``HVD_TPU_PROFILE_ON_ANOMALY`` is on
  (default), a finding arms a capture of the next
  ``HVD_TPU_PROFILE_STEPS`` steps and stamps the planned trace path
  into the finding itself, so the flight event, ``/metrics`` and the
  autopsy all point at the same evidence.

Also here: the phase vocabulary (:mod:`horovod_tpu.profiling.scopes`:
the ``jax.named_scope`` names of the train step's parts and the host
spans), the host log (:mod:`horovod_tpu.profiling.host_log`: one bounded
ring of the host's spans, garbage collections and compiles on
``time.perf_counter()``) and ``annotate``, the one door for host spans.
"""

from __future__ import annotations

from typing import Optional

from horovod_tpu.profiling.manager import (ProfileManager, default_manager,
                                           profile_dir)
from horovod_tpu.profiling import compile_watch, host_log, memory, scopes

__all__ = [
    "ProfileManager", "default_manager", "profile_dir",
    "compile_watch", "host_log", "memory", "scopes",
    "on_step_begin", "on_step_end", "on_anomaly",
    "recent_captures", "finalize_open_capture", "reset",
    "annotate",
]


# -- step seam (called from StepTimer; must never raise) ---------------------
def on_step_begin(step: int) -> None:
    try:
        default_manager().on_step_begin(step)
    except Exception:
        pass


def on_step_end(step: int) -> None:
    try:
        default_manager().on_step_end(step)
    except Exception:
        pass
    try:
        finding = memory.default_sampler().on_step(step)
        if finding is not None:
            from horovod_tpu.metrics.anomaly import report_finding
            report_finding(**finding)
    except Exception:
        pass


# -- anomaly seam (called from AnomalyEngine._flag) --------------------------
def on_anomaly(finding: dict) -> Optional[dict]:
    """A fresh anomaly finding: arm a rate-limited capture of the next
    K steps and stamp the planned path into the finding (the engine
    stores the same dict, so the path shows up in
    ``recent_findings()`` / the autopsy summary / the flight event)."""
    if finding.get("kind") == "world_changed":
        # a control-plane event, not a degradation: the re-mesh
        # timeline already measures recovery, a trace of the freshly
        # recompiling world would be pure noise, and burning the
        # rate-limited capture here would starve a REAL post-re-mesh
        # anomaly of its evidence
        return None
    from horovod_tpu.profiling.manager import on_anomaly_enabled
    if not on_anomaly_enabled():
        return None
    try:
        info = default_manager().request_capture(
            reason=f"anomaly:{finding.get('kind', 'unknown')}",
            trigger=finding, rate_limited=True)
    except Exception:
        return None
    if info is not None:
        finding["profile"] = info["path"]
    return info


# -- autopsy integration -----------------------------------------------------
def recent_captures() -> list:
    """Completed (and aborted-but-flushed) capture records — what the
    autopsy summary embeds under ``profiles``."""
    from horovod_tpu.profiling import manager as _m
    mgr = _m._MANAGER
    return mgr.recent_captures() if mgr is not None else []


def finalize_open_capture(reason: str = "aborted") -> Optional[dict]:
    """Close a mid-window capture NOW (autopsy/crash paths): a job that
    degraded, started its trace, and then hung still ships the trace."""
    from horovod_tpu.profiling import manager as _m
    mgr = _m._MANAGER
    return mgr.finalize_open_capture(reason) if mgr is not None else None


def reset() -> None:
    """Drop process-wide state so env is re-read (tests, elastic)."""
    from horovod_tpu.profiling import manager as _m
    _m.reset()
    memory.reset()
    compile_watch.reset_counts()


# -- host spans ---------------------------------------------------------------
def annotate(name: str) -> host_log.Span:
    """Named range on the host: a ``jax.profiler.TraceAnnotation`` (on the
    profiler's host plane and the device planes' clock while a session is
    open; NVTX-range analog) that also leaves ``(name, start, duration)``
    on ``time.perf_counter()`` in the host log, session or none. Under a
    microsecond. Names come from :mod:`.scopes`."""
    return host_log.Span(name)
