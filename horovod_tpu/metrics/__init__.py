"""Unified metrics & telemetry subsystem.

Seven layers (see ``docs/OBSERVABILITY.md``):

* :mod:`~horovod_tpu.metrics.registry` — dependency-free Counter / Gauge /
  Histogram with mergeable snapshots and Prometheus text rendering.
* :mod:`~horovod_tpu.metrics.engine` — derived view over the C++ engine's
  control-plane counters (cache-hit rate, fusion efficiency, bytes/s) and
  the coordinator's straggler attribution.
* :mod:`~horovod_tpu.metrics.exporter` — per-worker HTTP ``/metrics`` +
  ``/healthz`` endpoints, enabled by ``HVD_TPU_METRICS_PORT``.
* :mod:`~horovod_tpu.metrics.fleet` — tree-aggregated whole-job view:
  ranks push mergeable snapshots up a fan-in tree; rank 0 serves one
  ``/metrics/fleet`` scrape with per-rank breakdown gauges.
* :mod:`~horovod_tpu.metrics.timeseries` — step-aligned history: bounded
  ring + ``HVD_TPU_OBS_DIR`` JSONL, queryable by
  ``python -m horovod_tpu.metrics history``.
* :mod:`~horovod_tpu.metrics.anomaly` — online EWMA+MAD detectors over
  the series: step-time drift, throughput regression, persistent
  straggler, exposed-comm growth.
* :mod:`~horovod_tpu.metrics.mfu` — chip peak FLOPs + compiled-HLO FLOPs
  counting for the train-loop telemetry.
"""

from horovod_tpu.metrics.registry import (  # noqa: F401
    Counter,
    Gauge,
    Histogram,
    Registry,
    DEFAULT_BUCKETS,
    default_registry,
    render_prometheus,
)
from horovod_tpu.metrics.engine import (  # noqa: F401
    EngineCollector,
    derived_ratios,
)
from horovod_tpu.metrics.exporter import (  # noqa: F401
    MetricsExporter,
    start_worker_exporter,
)
from horovod_tpu.metrics.fleet import FleetAggregator  # noqa: F401
from horovod_tpu.metrics.timeseries import (  # noqa: F401
    StepSeriesRecorder,
    TimeSeriesRing,
    read_series,
)
from horovod_tpu.metrics.anomaly import AnomalyEngine  # noqa: F401
