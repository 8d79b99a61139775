"""Environment-variable configuration surface.

Mirrors the reference's env-knob config system (reference:
``horovod/common/common.h:107-139`` knob list, parsed in
``horovod/common/operations.cc:487-588`` and ``horovod/common/utils/env_parser.cc``).
Every knob accepts a ``HOROVOD_``-prefixed name for drop-in familiarity and an
``HVD_TPU_``-prefixed alias; the ``HVD_TPU_`` name wins if both are set.
"""

from __future__ import annotations

import dataclasses
import os
from typing import Optional


def _env(name: str, default: Optional[str] = None) -> Optional[str]:
    """Read ``HVD_TPU_<name>`` falling back to ``HOROVOD_<name>``."""
    v = os.environ.get("HVD_TPU_" + name)
    if v is None:
        v = os.environ.get("HOROVOD_" + name)
    return default if v is None else v


def env_int(name: str, default: int) -> int:
    v = _env(name)
    try:
        return int(v) if v not in (None, "") else default
    except ValueError:
        return default


def env_float(name: str, default: float) -> float:
    v = _env(name)
    try:
        return float(v) if v not in (None, "") else default
    except ValueError:
        return default


def env_bool(name: str, default: bool = False) -> bool:
    v = _env(name)
    if v in (None, ""):
        return default
    return v.lower() not in ("0", "false", "no", "off")


def env_str(name: str, default: str = "") -> str:
    v = _env(name)
    return default if v in (None, "") else v


@dataclasses.dataclass
class Config:
    """Snapshot of all runtime knobs.

    Defaults follow the reference: fusion threshold 64 MiB — the
    reference's own default (``operations.cc:487``) and what our C++
    core's env parser falls back to (``capi.cc``); the two layers must
    agree because the bucket planner (``train/buckets.py``) reuses this
    number as the overlap bucket budget. Cycle time 1 ms; cache
    capacity 1024.
    """

    # Fusion / cycle (reference: operations.cc:487-538)
    fusion_threshold_bytes: int = 64 * 1024 * 1024
    cycle_time_ms: float = 1.0
    cache_capacity: int = 1024
    # Gradient bucketing / overlap (train/overlap.py):
    # bucket_bytes 0 = follow fusion_threshold_bytes; overlap_buckets
    # gates the eager per-bucket async issue path (off = one grouped
    # call for the whole tree, the pre-bucketing behavior).
    bucket_bytes: int = 0
    overlap_buckets: bool = True
    # Small-bucket latency floor (train/autotune.py): gradient
    # buckets under this many bytes skip quantization and ring /
    # hierarchical chunking and take one dense psum (latency-optimized
    # small-tensor path, arxiv 1909.09756). 0 = off.
    small_bucket_floor: int = 0
    # Mesh-path communication autotuner (train/autotune.py): online plan
    # search over bucket_bytes x algorithm x codec x small-bucket floor
    # on the traced path, bounded by a step budget, winner persisted to
    # a fingerprint-keyed JSON cache. Distinct from the C++ core's
    # eager-path autotune= below.
    autotune_mesh: bool = False
    autotune_budget_steps: int = 48
    autotune_cache_dir: str = ""
    # Hierarchical ops (reference: operations.cc:514-538)
    hierarchical_allreduce: bool = False
    hierarchical_allgather: bool = False
    # Autotune (reference: parameter_manager.h:42-105)
    autotune: bool = False
    autotune_log: str = ""
    autotune_warmup_samples: int = 3
    autotune_steps_per_sample: int = 10
    autotune_bayes_opt_max_samples: int = 24
    autotune_gaussian_process_noise: float = 1e-6
    # Timeline (reference: timeline.h:48-183)
    timeline: str = ""
    timeline_mark_cycles: bool = False
    # Diagnostics (docs/OBSERVABILITY.md "Flight recorder & hang
    # autopsy"): every rank writes a timeline shard
    # (<timeline>.rank<r>.json) with span ids + wall-clock anchors;
    # merge with `python -m horovod_tpu.diagnostics merge`.  The other
    # diagnostics knobs (WATCHDOG_SECONDS, FLIGHT_RECORDER_SIZE,
    # AUTOPSY_DIR) are read live from env by horovod_tpu/diagnostics —
    # they must track env changes across elastic re-init and tests, so
    # they deliberately bypass this cached snapshot.
    timeline_all_ranks: bool = False
    # Stall inspection (reference: stall_inspector.h:30-99)
    stall_check_disable: bool = False
    stall_warning_time_seconds: float = 60.0
    stall_shutdown_time_seconds: float = 0.0
    # Elastic
    elastic: bool = False
    reset_limit: int = 0
    # Backend selection (reference: HOROVOD_CPU_OPERATIONS / HOROVOD_CONTROLLER,
    # common.h:128; here XLA is the TPU data plane, TCP the host reference plane)
    tpu_operations: str = "XLA"
    controller: str = "tcp"
    # Group fusion (reference: HOROVOD_DISABLE_GROUP_FUSION, group_table.h)
    disable_group_fusion: bool = False
    # Compression
    compression_fp16_on_tpu: bool = True
    # Transport (reference: HOROVOD_GLOO_TIMEOUT_SECONDS)
    gloo_timeout_seconds: float = 30.0
    # Background-thread CPU pinning (reference: HOROVOD_THREAD_AFFINITY)
    thread_affinity: int = -1
    # Metrics / telemetry (docs/OBSERVABILITY.md)
    # Per-worker Prometheus exporter base port; 0 = disabled. Worker i on a
    # host binds metrics_port + local_rank(i).
    metrics_port: int = 0
    # Coordinator logs a rank-attributed negotiation-wait summary every
    # this many seconds; 0 = disabled (snapshot stays queryable via
    # hvd.metrics_snapshot() either way).
    straggler_report_secs: float = 0.0
    # Misc
    log_level: str = "WARNING"
    log_hide_timestamp: bool = False
    rendezvous_addr: str = ""
    rendezvous_port: int = 0

    @classmethod
    def from_env(cls) -> "Config":
        d = cls()
        return cls(
            fusion_threshold_bytes=env_int(
                "FUSION_THRESHOLD", d.fusion_threshold_bytes),
            cycle_time_ms=env_float("CYCLE_TIME", d.cycle_time_ms),
            bucket_bytes=env_int("BUCKET_BYTES", d.bucket_bytes),
            overlap_buckets=env_bool("OVERLAP_BUCKETS", d.overlap_buckets),
            small_bucket_floor=env_int("SMALL_BUCKET_FLOOR",
                                       d.small_bucket_floor),
            autotune_mesh=env_bool("AUTOTUNE_MESH"),
            autotune_budget_steps=env_int("AUTOTUNE_BUDGET_STEPS",
                                          d.autotune_budget_steps),
            autotune_cache_dir=env_str("AUTOTUNE_CACHE_DIR",
                                       d.autotune_cache_dir),
            cache_capacity=env_int("CACHE_CAPACITY", d.cache_capacity),
            hierarchical_allreduce=env_bool("HIERARCHICAL_ALLREDUCE"),
            hierarchical_allgather=env_bool("HIERARCHICAL_ALLGATHER"),
            autotune=env_bool("AUTOTUNE"),
            autotune_log=env_str("AUTOTUNE_LOG"),
            autotune_warmup_samples=env_int(
                "AUTOTUNE_WARMUP_SAMPLES", d.autotune_warmup_samples),
            autotune_steps_per_sample=env_int(
                "AUTOTUNE_STEPS_PER_SAMPLE", d.autotune_steps_per_sample),
            autotune_bayes_opt_max_samples=env_int(
                "AUTOTUNE_BAYES_OPT_MAX_SAMPLES",
                d.autotune_bayes_opt_max_samples),
            autotune_gaussian_process_noise=env_float(
                "AUTOTUNE_GAUSSIAN_PROCESS_NOISE",
                d.autotune_gaussian_process_noise),
            timeline=env_str("TIMELINE"),
            timeline_mark_cycles=env_bool("TIMELINE_MARK_CYCLES"),
            timeline_all_ranks=env_bool("TIMELINE_ALL_RANKS"),
            stall_check_disable=env_bool("STALL_CHECK_DISABLE"),
            stall_warning_time_seconds=env_float(
                "STALL_CHECK_TIME_SECONDS", d.stall_warning_time_seconds),
            stall_shutdown_time_seconds=env_float(
                "STALL_SHUTDOWN_TIME_SECONDS", d.stall_shutdown_time_seconds),
            elastic=env_bool("ELASTIC"),
            reset_limit=env_int("RESET_LIMIT", d.reset_limit),
            tpu_operations=env_str("TPU_OPERATIONS", d.tpu_operations).upper(),
            controller=env_str("CONTROLLER", d.controller).lower(),
            disable_group_fusion=env_bool("DISABLE_GROUP_FUSION"),
            compression_fp16_on_tpu=env_bool(
                "COMPRESSION_FP16_ON_TPU", d.compression_fp16_on_tpu),
            gloo_timeout_seconds=env_float("GLOO_TIMEOUT_SECONDS",
                                           d.gloo_timeout_seconds),
            metrics_port=env_int("METRICS_PORT", d.metrics_port),
            straggler_report_secs=env_float(
                "STRAGGLER_REPORT_SECONDS", d.straggler_report_secs),
            thread_affinity=env_int("THREAD_AFFINITY", d.thread_affinity),
            log_level=env_str("LOG_LEVEL", d.log_level).upper(),
            log_hide_timestamp=env_bool("LOG_HIDE_TIME",
                                        d.log_hide_timestamp),
            rendezvous_addr=env_str("RENDEZVOUS_ADDR",
                                    os.environ.get("HOROVOD_GLOO_RENDEZVOUS_ADDR", "")),
            rendezvous_port=env_int("RENDEZVOUS_PORT", d.rendezvous_port),
        )


_config: Optional[Config] = None


def get_config() -> Config:
    global _config
    if _config is None:
        _config = Config.from_env()
    return _config


def reset_config() -> None:
    """Re-read env on next access (used by elastic re-init and tests)."""
    global _config
    _config = None
