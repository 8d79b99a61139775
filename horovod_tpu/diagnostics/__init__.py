"""Flight recorder & hang autopsy: the post-mortem observability layer.

Three cooperating parts (docs/OBSERVABILITY.md "Flight recorder & hang
autopsy"):

* **Cross-rank trace** — every rank can write a timeline shard
  (``HVD_TPU_TIMELINE_ALL_RANKS``) with per-collective span ids
  (:mod:`.spans`) and wall-clock anchors (:mod:`.clock`);
  :mod:`.merge` folds N shards into one Perfetto trace, one track per
  rank, the same collective correlated across tracks.
* **Flight recorder** (:mod:`.flight_recorder`) — a bounded in-memory
  ring of recent control-plane events (collective enqueue/complete,
  step begin/end, checkpoint save/commit, elastic re-mesh, codec
  choice), dumpable on demand and automatically on crash.
* **Hang watchdog** (:mod:`.watchdog`) + **autopsy** (:mod:`.autopsy`)
  — no step progress for ``HVD_TPU_WATCHDOG_SECONDS`` writes a bundle
  with per-rank stacks, engine pending-tensor state, the flight dump, a
  metrics snapshot and the merged trace; rank 0 also fetches every
  peer's evidence over the exporter's ``/debug/*`` endpoints.

CLI: ``python -m horovod_tpu.diagnostics merge ...``.
"""

from horovod_tpu.diagnostics.flight_recorder import (  # noqa: F401
    FlightRecorder,
    install_crash_hooks,
    record_event,
    recorder,
)
from horovod_tpu.diagnostics.spans import (  # noqa: F401
    active_span,
    current_span,
    next_span,
)
from horovod_tpu.diagnostics.clock import estimate_wall_offset  # noqa: F401
from horovod_tpu.diagnostics.merge import merge_shards  # noqa: F401
from horovod_tpu.diagnostics.watchdog import (  # noqa: F401
    Watchdog,
    ensure_watchdog,
    notify_progress,
)
from horovod_tpu.diagnostics.autopsy import write_autopsy  # noqa: F401
