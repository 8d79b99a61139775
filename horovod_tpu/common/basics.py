"""Process identity and lifecycle: ``init`` / ``shutdown`` / rank & size queries.

TPU-native re-think of the reference's ``HorovodBasics`` ctypes wrapper
(reference: ``horovod/common/basics.py:29-487``) and the C API behind it
(``horovod/common/operations.cc:869-1083``).

Identity model on TPU: one **process per TPU host** (not per chip, unlike the
reference's one-process-per-GPU). ``rank``/``size`` count processes, as in the
reference; the chips a process drives form its local device set and are
addressed through the data-plane mesh (:mod:`horovod_tpu.parallel.mesh`). The
launcher (``hvdrun``) injects ``HOROVOD_RANK``-style env vars exactly as the
reference's launcher does (reference: ``horovod/runner/gloo_run.py:65-76``).
"""

from __future__ import annotations

import atexit
import os
import threading
from typing import List, Optional, Sequence

from horovod_tpu.common.config import Config, get_config, reset_config
from horovod_tpu.common.logging import get_logger


class NotInitializedError(RuntimeError):
    def __init__(self) -> None:
        super().__init__(
            "horovod_tpu has not been initialized; call hvd.init() first.")


class _GlobalState:
    """Per-process singleton (reference: ``HorovodGlobalState``,
    ``horovod/common/global_state.h:39-126``)."""

    def __init__(self) -> None:
        self.lock = threading.Lock()
        self.initialized = False
        self.rank = 0
        self.size = 1
        self.local_rank = 0
        self.local_size = 1
        self.cross_rank = 0
        self.cross_size = 1
        self.hostname = ""
        self.launched_rank = None  # pre-restriction rank when init(ranks) used
        self.launched_size = 1     # env world size before any restriction
        self.world_ranks = None    # restricted global set (init(ranks))
        self.backend = None          # ops.backend.Backend for the global set
        self.config: Optional[Config] = None
        self.process_set_table = None  # common.process_sets._ProcessSetTable
        self.timeline = None
        self.metrics_exporter = None  # metrics.exporter.MetricsExporter
        self.elastic_enabled = False
        self.jax_distributed_initialized = False


_state = _GlobalState()


def _read_identity_from_env() -> dict:
    """Launcher-injected identity (reference env names,
    ``horovod/runner/gloo_run.py:65-76``)."""
    def geti(name: str, default: int) -> int:
        v = os.environ.get("HVD_TPU_" + name, os.environ.get("HOROVOD_" + name))
        return int(v) if v not in (None, "") else default

    return dict(
        rank=geti("RANK", 0),
        size=geti("SIZE", 1),
        local_rank=geti("LOCAL_RANK", 0),
        local_size=geti("LOCAL_SIZE", 1),
        cross_rank=geti("CROSS_RANK", 0),
        cross_size=geti("CROSS_SIZE", 1),
        hostname=os.environ.get(
            "HVD_TPU_HOSTNAME", os.environ.get("HOROVOD_HOSTNAME", "")),
    )


def _create_backend(state: "_GlobalState"):
    """Pick the communication backend for eager (process-level) collectives.

    Priority-ordered like the reference's ``CreateOperationManager``
    (``horovod/common/operations.cc:144-253``): the first available backend
    wins. On TPU pods the data plane is XLA collectives over ICI/DCN; the
    TCP core backend is the host-side reference implementation (the
    "Gloo-equivalent") used for CPU tests and as the control plane.
    """
    from horovod_tpu.ops.backend import make_backend
    return make_backend(state)


def init(ranks: Optional[Sequence[int]] = None,
         process_sets: Optional[list] = None) -> None:
    """Initialize horovod_tpu (reference: ``horovod_init``,
    ``operations.cc:869-878`` via ``basics.py:48-146``).

    Args:
      ranks: optional restriction of the global set to a subset of launched
        processes (reference semantics of ``hvd.init(ranks)``). Rarely used.
      process_sets: optional list of :class:`~horovod_tpu.ProcessSet` to
        register at init time (reference: dynamic/static process sets,
        ``operations.cc:1194-1260``).

    One ``scopes.HOST_INIT`` span of the host log, the backend's creation
    a span inside it: what a start, a re-mesh or a restart pays here.
    """
    if _state.initialized:      # (checked again under the lock)
        return
    from horovod_tpu.profiling import annotate, scopes
    with annotate(scopes.HOST_INIT):
        _init(ranks, process_sets)


def _init(ranks: Optional[Sequence[int]], process_sets: Optional[list]
          ) -> None:
    with _state.lock:
        if _state.initialized:
            return
        reset_config()
        _state.config = get_config()
        ident = _read_identity_from_env()
        _state.rank = ident["rank"]
        _state.size = ident["size"]
        _state.launched_size = ident["size"]
        _state.local_rank = ident["local_rank"]
        _state.local_size = ident["local_size"]
        _state.cross_rank = ident["cross_rank"]
        _state.cross_size = ident["cross_size"]
        _state.hostname = ident["hostname"] or os.uname().nodename

        # Chaos harness (docs/CHAOS.md): arm the fault plan BEFORE the
        # backend boots — transport.* rules compile into the env spec the
        # C++ core reads at Transport::Init, and rank-scoped rules must
        # track the rank an elastic re-mesh just handed us.  No plan set
        # = everything stays disarmed (zero-cost seams).
        from horovod_tpu import chaos as _chaos
        _chaos.install(rank=ident["rank"])

        if ranks is not None and len(ranks) > 0:
            ranks = sorted(set(ranks))
            # Restrict the world to the given launched ranks (reference
            # semantics of ``hvd.init(ranks)``: the global process set is the
            # sub-communicator over those ranks, and rank/size are relative
            # to it — ``operations.cc:881-965`` init_multi_comm). Launched
            # processes NOT in the list still participate in the core world
            # (so rendezvous completes) but are excluded from the global set
            # — their rank() is -1. Single-process, exclusion is an error.
            if _state.rank not in ranks:
                if _state.size == 1:
                    raise ValueError(
                        f"hvd.init(ranks={list(ranks)}): this process has "
                        f"rank {_state.rank}, which is not in the ranks "
                        "list.")
                _state.launched_rank = _state.rank
                _state.world_ranks = ranks
                _state.rank = -1
                _state.size = len(ranks)
            else:
                _state.launched_rank = _state.rank
                _state.world_ranks = ranks
                _state.rank = ranks.index(_state.rank)
                _state.size = len(ranks)

        # re-mesh timeline (docs/OBSERVABILITY.md "Re-mesh timeline"):
        # when an elastic recovery episode is active, backend creation
        # is its "rendezvous" phase and the remainder of init its
        # "rebuild" phase; both are pass-throughs on a first init
        import time as _time

        from horovod_tpu.elastic import remesh as _remesh
        from horovod_tpu.profiling import annotate, scopes
        with _remesh.phase("rendezvous"), \
                annotate(scopes.HOST_INIT + "/backend"):
            _state.backend = _create_backend(_state)
        _t_rebuild = _time.perf_counter()

        from horovod_tpu.common.process_sets import _init_process_set_table
        _state.process_set_table = _init_process_set_table(
            _state, process_sets or [])

        # Timeline (host-side chrome tracing; reference timeline.h:48-183).
        # In multi-process mode the C++ core writes the timeline file (it
        # sees the same env var); opening it here too would interleave two
        # writers into one path — so the Python timeline only owns the file
        # single-process.  With HVD_TPU_TIMELINE_ALL_RANKS every rank
        # ALSO writes a per-rank shard (<timeline>.rank<r>.json — a
        # distinct path, never shared with the core's file) carrying
        # per-collective span ids and a wall-clock anchor, merged
        # post-hoc via `python -m horovod_tpu.diagnostics merge`.
        from horovod_tpu.common.timeline import Timeline, shard_path
        cfg = _state.config
        all_shards = bool(cfg.timeline) and cfg.timeline_all_ranks
        own_file = cfg.timeline \
            if (_state.launched_size == 1 and not all_shards) else ""
        _state.timeline = Timeline(_state.rank, own_file)
        if all_shards:
            # wall-clock offset vs the coordinator, piggybacked on the
            # just-built collective plane, so shards from skew-clocked
            # hosts align in the merged trace
            from horovod_tpu.diagnostics.clock import estimate_wall_offset
            offset = estimate_wall_offset(_state.backend)
            # the flight recorder shares the shard's offset so the
            # merged timeline (diagnostics timeline) aligns flight
            # events with shard spans across skew-clocked hosts
            from horovod_tpu.diagnostics.flight_recorder import \
                set_wall_offset
            set_wall_offset(offset)
            _state.timeline.start_shard(
                shard_path(cfg.timeline, _state.rank),
                wall_offset_s=offset,
                mark_cycles=cfg.timeline_mark_cycles)

        # Flight recorder: always on (bounded ring, docs/OBSERVABILITY.md
        # "Flight recorder & hang autopsy"); crash hooks make an uncaught
        # exception leave a dump next to the autopsy bundle.  Span
        # counters restart with the world: after an elastic re-mesh the
        # new engine counts enqueues from zero, and the Python ids must
        # keep agreeing with it.
        from horovod_tpu.diagnostics import spans as _spans
        _spans.reset()
        # observability history follows the world: the step-series
        # recorder re-reads rank + HVD_TPU_OBS_DIR (a re-mesh can
        # renumber us) and the anomaly detectors drop their baselines
        # (a different world size legitimately changes step time —
        # re-learn instead of flagging the re-mesh itself; findings
        # already flagged are kept for the autopsy)
        from horovod_tpu.metrics import timeseries as _timeseries
        _timeseries.reset()
        from horovod_tpu.metrics import anomaly as _anomaly
        _anomaly.reset_baselines()
        # the profiling detectors follow the same rule: a re-meshed
        # world legitimately recompiles its jitted steps and re-learns
        # its HBM baseline — per-function storm counts and the growth
        # detector must not accumulate across generations into false
        # recompile_storm/hbm_growth findings (the capture manager and
        # its records DO survive: cooldown + autopsy history)
        from horovod_tpu.profiling import compile_watch as _cw
        from horovod_tpu.profiling import memory as _hbm
        _cw.reset_counts()
        _hbm.reset()
        from horovod_tpu.diagnostics import watchdog as _wd
        _wd.resume()  # re-arm across an elastic shutdown->init cycle
        from horovod_tpu.diagnostics.flight_recorder import (
            install_crash_hooks, record_event)
        install_crash_hooks()
        record_event("init", rank=_state.rank, size=_state.size,
                     backend=type(_state.backend).__name__)

        _state.initialized = True

        # Per-worker /metrics + /healthz exporter (HVD_TPU_METRICS_PORT;
        # docs/OBSERVABILITY.md). After the initialized flag: /healthz
        # reports live state, and a bind failure only warns.
        from horovod_tpu.metrics.exporter import start_worker_exporter
        _state.metrics_exporter = start_worker_exporter(_state)
        # Proactive preemption watcher (docs/ELASTIC.md "Proactive drain
        # & preemption"): armed only under an elastic driver; idempotent
        # across re-meshes (the singleton reads identity from env live).
        try:
            from horovod_tpu.elastic import preemption as _preemption
            _preemption.ensure_watcher()
        except Exception:
            get_logger().debug("preemption watcher not armed",
                               exc_info=True)
        # compile observability (docs/OBSERVABILITY.md "Compile & memory
        # observability"): compile-time metrics + the recompile_storm
        # detector; idempotent, gated on HVD_TPU_COMPILE_METRICS
        try:
            from horovod_tpu.profiling import compile_watch, host_log
            compile_watch.ensure_installed()
            # garbage collections as ``scopes.HOST_GC`` spans of the host log
            # ("Host pauses"); one ``gc.callbacks`` entry, removed by
            # shutdown()
            host_log.install_gc_callback()
        except Exception:
            pass
        # autopilot policy engine (docs/OBSERVABILITY.md "Autopilot"):
        # armed here so a typo'd HVD_TPU_AUTOPILOT_POLICY fails the job
        # LOUDLY at init — the same contract as a typo'd chaos fault
        # plan — instead of running policy-free; no-op when
        # HVD_TPU_AUTOPILOT=off
        from horovod_tpu import autopilot as _autopilot
        _autopilot.ensure_engine()
        _ep = _remesh.current()
        if _ep is not None and not _ep.finished:
            _ep.add_phase("rebuild", _time.perf_counter() - _t_rebuild)
        get_logger().info(
            "initialized: rank=%d size=%d local=%d/%d cross=%d/%d backend=%s",
            _state.rank, _state.size, _state.local_rank, _state.local_size,
            _state.cross_rank, _state.cross_size,
            type(_state.backend).__name__)


def shutdown(force: bool = False) -> None:
    """Tear down (reference: ``horovod_shutdown``, ``operations.cc:994-1005``).
    ``force=True`` skips the negotiated-shutdown grace — used by elastic
    in-place shrink, where a dead peer makes consensus impossible."""
    with _state.lock:
        if not _state.initialized:
            return
        from horovod_tpu.diagnostics.flight_recorder import record_event
        record_event("shutdown", rank=_state.rank, force=force)
        try:
            # a watchdog must not run against a torn-down world — but an
            # elastic shutdown→init cycle must not silently disarm it
            # either, so suspend (remember armed) rather than drop;
            # init() resumes it for the new world
            from horovod_tpu.diagnostics import watchdog as _wd
            _wd.suspend()
        except Exception:
            pass
        try:
            from horovod_tpu.profiling import host_log
            host_log.uninstall_gc_callback()
        except Exception:
            pass
        try:
            if _state.backend is not None:
                import inspect
                params = inspect.signature(
                    _state.backend.shutdown).parameters
                if "force" in params:
                    _state.backend.shutdown(force=force)
                else:  # backends without a force knob
                    _state.backend.shutdown()
        finally:
            if _state.metrics_exporter is not None:
                try:
                    _state.metrics_exporter.stop()
                except Exception:
                    pass
                _state.metrics_exporter = None
            if _state.timeline is not None:
                _state.timeline.close()
            _state.backend = None
            _state.process_set_table = None
            _state.timeline = None
            _state.initialized = False


atexit.register(shutdown)


def is_initialized() -> bool:
    """Reference: ``horovod_is_initialized`` (``operations.cc:1007``)."""
    return _state.initialized


def _require_init() -> _GlobalState:
    if not _state.initialized:
        raise NotInitializedError()
    return _state


def start_timeline(file_path: str, mark_cycles: bool = False) -> None:
    """Dynamic timeline start (reference: ``horovod_start_timeline``,
    ``operations.cc:1011-1041``; coordinator-only file).

    Multi-process, the C++ engine owns the timeline file (it records the
    negotiation phases and execute sub-activities); single-process the
    Python timeline does. One writer per path — never both."""
    st = _require_init()
    if st.backend is not None and st.backend.start_core_timeline(
            file_path, mark_cycles=mark_cycles):
        return
    st.timeline.start(file_path, mark_cycles=mark_cycles)


def stop_timeline() -> None:
    st = _require_init()
    if st.backend is not None and st.backend.stop_core_timeline():
        return
    st.timeline.stop()


def counters() -> dict:
    """Control-plane observability counters from the active backend:
    negotiation cycles, response-cache hits/misses/evictions, fused units,
    bytes moved. The reference exposes this only via timeline/autotune
    traces; first-class counters make the steady-state fast path
    measurable (VERDICT r2 #7). Empty dict for backends with no
    negotiating control plane (single-process / XLA-eager)."""
    st = _require_init()
    return st.backend.counters() if st.backend is not None else {}


def stragglers() -> dict:
    """Coordinator-side rank-attributed negotiation-wait report: for each
    rank, total seconds the others spent waiting on it being the LAST to
    announce a tensor, and how many tensors it held up (the C++ core's
    per-tensor negotiation tracking aggregated per rank; reference
    surfaces this only as per-tensor timeline NEGOTIATE_* spans). Only the
    coordinator (rank 0 of the core world) accumulates data; other ranks
    and non-core backends return an empty report."""
    st = _require_init()
    fn = getattr(st.backend, "stragglers", None)
    return fn() if fn is not None else {}


def engine_state() -> dict:
    """Pending-tensor autopsy snapshot from the engine
    (``hvd_engine_state_json``): per coordination domain, the tensors
    still waiting for announcements with ready/missing ranks, queue
    depth and join state.  The data behind the hang watchdog's "which
    rank is stuck in what" summary (docs/OBSERVABILITY.md "Flight
    recorder & hang autopsy").  Meaningful on the coordinator; empty for
    backends without a negotiating control plane."""
    st = _require_init()
    fn = getattr(st.backend, "engine_state", None)
    return fn() if fn is not None else {}


def metrics_snapshot() -> dict:
    """One-call observability snapshot: raw engine counters, derived
    ratios (cache-hit rate, fusion efficiency), the coordinator's
    straggler report, and the process-local metrics registry (step-time
    histograms, throughput/MFU gauges from the train-loop telemetry).
    The same data the per-worker ``/metrics`` endpoint serves, as a dict.
    """
    from horovod_tpu.metrics.engine import derived_ratios
    from horovod_tpu.metrics.registry import default_registry
    engine = counters()
    return {
        "engine": engine,
        "derived": derived_ratios(engine),
        "stragglers": stragglers(),
        "registry": default_registry().snapshot(),
    }


def rank() -> int:
    return _require_init().rank


def size() -> int:
    return _require_init().size


def local_rank() -> int:
    return _require_init().local_rank


def local_size() -> int:
    return _require_init().local_size


def cross_rank() -> int:
    return _require_init().cross_rank


def cross_size() -> int:
    return _require_init().cross_size


def is_homogeneous() -> bool:
    """True if every host runs the same number of processes
    (reference: ``horovod_is_homogeneous``, ``operations.cc:1077-1083``).

    Without a cross-host gather of local sizes (done by the controller at
    init in the multi-process core), the best local test is that this host's
    ``local_size`` times the host count accounts for every process.
    """
    st = _require_init()
    return st.local_size * max(st.cross_size, 1) == st.size


def num_devices() -> int:
    """TPU chips driven by this process (no reference analog: the reference is
    one-process-per-GPU; on TPU one process drives a host's chips)."""
    import jax
    return jax.local_device_count()


def global_device_count() -> int:
    import jax
    return jax.device_count()


# Build/availability queries (reference: horovod_mpi_built etc.,
# operations.cc:1085-1130). On TPU, XLA is the data plane; the TCP core is the
# Gloo-class host backend; there is no MPI/NCCL.
def xla_built() -> bool:
    return True


def tcp_core_built() -> bool:
    from horovod_tpu.core import core_available
    return core_available()


def gloo_built() -> bool:  # compat alias: our TCP core fills Gloo's role
    return tcp_core_built()


def mpi_built() -> bool:
    return False


def nccl_built() -> bool:
    return False


def ccl_built() -> bool:
    return False


def cuda_built() -> bool:
    return False


def rocm_built() -> bool:
    return False


def ddl_built() -> bool:
    return False


def sycl_built() -> bool:
    return False


def mpi_enabled() -> bool:
    return False


def gloo_enabled() -> bool:  # the TCP core is the Gloo-role plane
    return tcp_core_built()


def mpi_threads_supported() -> bool:
    return False
