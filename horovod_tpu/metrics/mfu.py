"""MFU accounting helpers: chip peak FLOPs + compiled-HLO FLOPs counting.

Used by the train-loop telemetry
(:class:`horovod_tpu.train.callbacks.TelemetryCallback`; MLPerf TPU-pod
scaling work emphasizes step-time/MFU accounting as the scaling metric —
PAPERS.md, arXiv:1909.09756).  The benchmark's ``mfu_pct`` is computed
from shapes in ``benchmarks/chip/``, not here.
"""

from __future__ import annotations

from typing import Optional

# Peak dense bf16 FLOPs per chip by device-kind substring (Google Cloud
# TPU documentation, per-chip figures; v5e: "TPU v5e", 197 TFLOP/s).
PEAK_BF16_FLOPS = (
    ("v5 lite", 197e12), ("v5litepod", 197e12), ("v5e", 197e12),
    ("v5p", 459e12), ("v6", 918e12), ("v4", 275e12), ("v3", 123e12),
    ("v2", 45e12),
)


def peak_flops(device_kind: str) -> float:
    """Peak dense bf16 FLOPs/s for a TPU device-kind string. A kind with
    no row raises ``LookupError``: a device that is not in the table is
    an error, not a default — an MFU against a guessed peak is worse
    than none."""
    kind = device_kind.lower()
    for sub, peak in PEAK_BF16_FLOPS:
        if sub in kind:
            return peak
    raise LookupError(
        f"no peak-FLOPs row for device kind {device_kind!r}; add it to "
        "horovod_tpu.metrics.mfu.PEAK_BF16_FLOPS with its source")


def device_peak_flops() -> Optional[float]:
    """Peak FLOPs of the first local device. None says "not a TPU" (a CPU
    host has no MFU) and nothing else: a TPU the table does not know
    raises (:func:`peak_flops`)."""
    import jax
    dev = jax.devices()[0]
    return peak_flops(dev.device_kind) if dev.platform == "tpu" else None


def hlo_flops_per_device(jitted, args, factor: int = 1) -> Optional[float]:
    """Per-device FLOPs of one dispatch of ``jitted(*args)`` from the
    compiled executable's ``cost_analysis()`` (post-SPMD, so per-device by
    construction). ``factor`` scales for in-graph multi-step: XLA counts a
    while-loop (``lax.scan``) body ONCE, not trip-count times. Returns
    None when cost analysis is unavailable (caller falls back to an
    analytic estimate)."""
    try:
        cost = jitted.lower(*args).compile().cost_analysis()
        if isinstance(cost, (list, tuple)):
            cost = cost[0]
        return (float(cost.get("flops", 0.0)) * factor) or None
    except Exception:
        return None


def mfu(flops_per_device_per_step: float, step_seconds: float,
        peak: Optional[float] = None) -> Optional[float]:
    """Model FLOPs utilization for one step; None when the peak is
    unknown or inputs are degenerate."""
    if peak is None:
        peak = device_peak_flops()
    if not peak or not flops_per_device_per_step or step_seconds <= 0:
        return None
    return flops_per_device_per_step / step_seconds / peak
