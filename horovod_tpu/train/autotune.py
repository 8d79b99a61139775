"""Mesh-path communication autotuner: online plan search with a
persistent tuning cache.

The eager TCP core closes its tuning loop in C++ (``cpp/core.cc``
ParameterManager driving the GP/EI optimizer in ``cpp/bayes_opt.cc``
over fusion bytes / cycle time / hierarchical / cache). The traced mesh
path — where every real TPU step runs — had no analog: bucket bytes,
collective algorithm and codec were hand-set knobs. This module closes
that loop:

* :class:`Plan` — one point in the discrete search space:
  ``bucket_bytes × algorithm {psum, ring, hier} × codec {none, int8,
  fp8} × small-bucket floor``.
* :class:`AutotuneController` — successive halving over candidate
  plans, scored by REAL measured step time (the same wall clock
  ``StepTimer`` feeds the PR-7 time-series ring), bounded by a step
  budget; every trial and the final choice land on ``/metrics``
  (``hvd_autotune_*``), in the flight recorder, and in a CSV trace like
  the C++ core's ``HVD_TPU_AUTOTUNE_LOG``.
* :class:`PlanCache` — the winner is persisted to a JSON cache keyed by
  a fingerprint (grad-tree structure, mesh shape, world size, dtype,
  codec availability), so subsequent runs — including elastic re-meshes
  back to a previously seen world size — start at the tuned config with
  ZERO search trials. Corrupt or stale entries are ignored with a
  warning and retuned, never crash init.
* :func:`make_autotuned_train_step` — the ``autotune=`` seam behind
  :func:`horovod_tpu.train.overlap.make_overlap_train_step`: candidate
  steps are compiled per plan, measured, and the locked winner serves
  steady state with no further timing overhead.

Successive halving (a bandit equivalent of the reference's sample-and-
converge ParameterManager, simpler and deterministic for a discrete
space): every surviving plan gets ``1 + steps_per_trial`` steps per
round — the first is a warmup absorbing compile — then the slower half
is dropped and the per-plan window doubles, until one survivor remains
or the step budget runs out (then the best-scored plan locks).

CPU note: autotune trials must run with the persistent XLA compile
cache DISABLED on the 8-device CPU test mesh (known heap-corruption
signature under warm-cache multi-device dispatch — tests/conftest.py);
nothing here touches the compile-cache config.
"""

from __future__ import annotations

import dataclasses
import hashlib
import json
import os
import time
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

from horovod_tpu.common.logging import get_logger

log = get_logger()

PLAN_CACHE_VERSION = 2   # v2: the cache may hold a ParallelPlan (ISSUE 11)
_ALGORITHMS = ("psum", "ring", "hier")
_CODECS = ("none", "int8", "fp8")
DEFAULT_SMALL_FLOOR = 32 * 1024  # latency-path floor candidate (bytes)


# ---------------------------------------------------------------------------
# Plan: one point in the search space
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class Plan:
    """One communication configuration for the traced mesh path.

    ``algorithm``: ``psum`` (flat), ``ring`` (chunked ppermute), or
    ``hier`` (topology-aware two-level). ``codec``: ``none``/``int8``/
    ``fp8`` — applied EQuARX-style (gather phase for psum, inter-host
    hop for hier; ring has no codec seam). ``small_floor``: buckets
    under this many bytes take the dense latency path.
    """

    bucket_bytes: int
    algorithm: str = "psum"
    codec: str = "none"
    small_floor: int = 0

    def __post_init__(self):
        if self.algorithm not in _ALGORITHMS:
            raise ValueError(f"unknown algorithm {self.algorithm!r}; "
                             f"expected one of {_ALGORITHMS}")
        if self.codec not in _CODECS:
            raise ValueError(f"unknown codec {self.codec!r}; "
                             f"expected one of {_CODECS}")
        if self.algorithm == "ring" and self.codec != "none":
            raise ValueError("ring has no compression seam")
        if self.bucket_bytes < 1:
            raise ValueError("bucket_bytes must be >= 1")
        if self.small_floor < 0:
            raise ValueError("small_floor must be >= 0")

    @property
    def key(self) -> str:
        """Short human label (CSV / flight / metric labels)."""
        return (f"{self.algorithm}/{self.codec}"
                f"/b{self.bucket_bytes}/f{self.small_floor}")

    def to_dict(self) -> Dict[str, Any]:
        return dataclasses.asdict(self)

    @classmethod
    def from_dict(cls, d: Dict[str, Any]) -> "Plan":
        return cls(bucket_bytes=int(d["bucket_bytes"]),
                   algorithm=str(d.get("algorithm", "psum")),
                   codec=str(d.get("codec", "none")),
                   small_floor=int(d.get("small_floor", 0)))

    def resolve_codec(self):
        """The codec string as a live Quantizer (None for ``none``)."""
        if self.codec == "none":
            return None
        from horovod_tpu.compression.quantizers import resolve_compressor
        return resolve_compressor(self.codec)

    def step_kwargs(self, topology=None) -> Dict[str, Any]:
        """Keyword arguments for ``make_overlap_train_step`` /
        ``bucketed_grad_sync`` realizing this plan."""
        return dict(bucket_bytes=self.bucket_bytes,
                    algorithm=self.algorithm,
                    compression=self.resolve_codec(),
                    small_floor=self.small_floor,
                    topology=topology)


def _codec_name(compression) -> str:
    if compression is None:
        return "none"
    name = getattr(compression, "name", None)
    if name not in _CODECS:
        raise ValueError(
            f"autotune searches codecs {_CODECS}; got compression="
            f"{compression!r} — drop autotune= or pass a supported codec")
    return name


def _codecs_available() -> Tuple[str, ...]:
    from horovod_tpu.compression.quantizers import fp8_supported
    return ("none", "int8") + (("fp8",) if fp8_supported() else ())


def candidate_plans(topology=None, *, baseline: Optional[Plan] = None,
                    include_fp8: bool = False) -> List[Plan]:
    """The default discrete search space, most-promising-first (the
    controller trims the tail when the step budget can't score them
    all — trimming must drop the speculative end, not the baseline).

    Floor variants are generated only for plans where the floor changes
    semantics (codec or non-flat algorithm); for a dense flat psum the
    latency path IS the plan, so the variant would be a duplicate
    compile.
    """
    from horovod_tpu.train.buckets import resolve_bucket_bytes
    hier_ok = topology is not None and topology.is_hierarchical
    combos: List[Tuple[str, str]] = [("psum", "none"), ("psum", "int8")]
    if hier_ok:
        combos += [("hier", "none"), ("hier", "int8")]
    combos.append(("ring", "none"))
    if include_fp8 and "fp8" in _codecs_available():
        combos.append(("psum", "fp8"))
        if hier_ok:
            combos.append(("hier", "fp8"))
    default_bucket = resolve_bucket_bytes(None)
    buckets = []
    for b in (default_bucket, 1 << 20):
        if b not in buckets:
            buckets.append(b)
    plans: List[Plan] = []
    if baseline is not None:
        plans.append(baseline)
    for bucket in buckets:
        for algo, codec in combos:
            plans.append(Plan(bucket, algo, codec, 0))
    for bucket in buckets:
        for algo, codec in combos:
            if algo == "psum" and codec == "none":
                continue  # floor is a no-op on the dense flat path
            plans.append(Plan(bucket, algo, codec, DEFAULT_SMALL_FLOOR))
    seen, out = set(), []
    for p in plans:
        if p not in seen:
            seen.add(p)
            out.append(p)
    return out


# ---------------------------------------------------------------------------
# Fingerprint + persistent plan cache
# ---------------------------------------------------------------------------

def topology_key(topology, pp: int = 1) -> Dict[str, int]:
    """Canonical mesh/topology component of the cache fingerprint:
    reduction width plus the (hosts × local) structure, WITHOUT the
    mesh axis name — a plan tuned over axis "dp" must warm-start the
    same model reduced over an axis called "data", and the eager
    ``DistributedOptimizer(autotune=True)`` seam (which has no mesh at
    all) must be able to reconstruct the same key from the world size.

    ``pp`` (ISSUE 11): the pipeline dimension of the key. A
    communication plan is tuned UNDER a fixed dp x pp mesh, so its key
    carries that mesh's pp size (default 1). A parallelism-plan search
    passes ``pp=0`` — the sentinel for "the dp x pp split is an axis of
    the search space, keyed by the whole world" — so comm-plan and
    parallel-plan entries for the same model can never shadow each
    other."""
    return {"world": int(topology.world),
            "hosts": int(topology.num_hosts),
            "local": int(topology.local_size),
            "pp": int(pp)}


def plan_fingerprint(tree, mesh_shape: Dict[str, int], world: int,
                     dtype: Optional[str] = None) -> str:
    """Cache key for a tuned plan: sha256 over everything that changes
    which plan wins — gradient-tree structure (leaf shapes + dtypes in
    flatten order), the canonical topology key (:func:`topology_key` —
    pass it as ``mesh_shape``), world size, compute dtype, and codec
    availability (an fp8-capable jax must not reuse a plan tuned
    without fp8 in the space, and vice versa)."""
    import jax
    import numpy as np
    leaves = jax.tree_util.tree_leaves(tree)
    struct = [[list(getattr(l, "shape", np.shape(l))),
               str(getattr(l, "dtype", np.asarray(l).dtype))]
              for l in leaves]
    doc = {
        "v": PLAN_CACHE_VERSION,
        "tree": struct,
        "mesh": sorted((str(k), int(v)) for k, v in mesh_shape.items()),
        "world": int(world),
        "dtype": dtype or (struct[0][1] if struct else "none"),
        "codecs": list(_codecs_available()),
    }
    blob = json.dumps(doc, sort_keys=True, separators=(",", ":"))
    return hashlib.sha256(blob.encode()).hexdigest()


def resolve_cache_dir(cache_dir: Optional[str] = None) -> str:
    """Effective cache directory: explicit argument >
    ``HVD_TPU_AUTOTUNE_CACHE_DIR``. Empty = persistence disabled (the
    search still runs; it just can't warm-start the next run)."""
    if cache_dir is not None:
        return cache_dir
    from horovod_tpu.common.config import get_config
    return get_config().autotune_cache_dir


class PlanCache:
    """Fingerprint-keyed JSON plan store (one small file per
    fingerprint). Load NEVER raises: a corrupt file (truncated JSON,
    wrong spec version), a fingerprint mismatch (stale rename / copied
    dir) or an unreadable plan logs a warning and returns None — init
    must degrade to a retune, not a crash."""

    def __init__(self, directory: str) -> None:
        self.directory = directory

    def path(self, fingerprint: str) -> str:
        return os.path.join(self.directory,
                            f"plan_{fingerprint[:32]}.json")

    def load(self, fingerprint: str) -> Optional[Plan]:
        if not self.directory:
            return None
        path = self.path(fingerprint)
        try:
            with open(path) as f:
                doc = json.load(f)
        except FileNotFoundError:
            return None
        except (OSError, ValueError) as e:
            log.warning("autotune plan cache %s unreadable (%s); "
                        "retuning", path, e)
            return None
        try:
            if doc.get("version") != PLAN_CACHE_VERSION:
                log.warning(
                    "autotune plan cache %s has spec version %r (want "
                    "%d); retuning", path, doc.get("version"),
                    PLAN_CACHE_VERSION)
                return None
            if doc.get("fingerprint") != fingerprint:
                log.warning(
                    "autotune plan cache %s fingerprint mismatch "
                    "(stale entry for a different tree/mesh/world); "
                    "retuning", path)
                return None
            # the cache holds either kind of plan: a communication Plan
            # or a full ParallelPlan (dp x pp split + schedule +
            # microbatches + nested comms) — dispatch on the doc
            from horovod_tpu.parallel.plan import plan_from_dict
            return plan_from_dict(doc["plan"])
        except (KeyError, TypeError, ValueError) as e:
            log.warning("autotune plan cache %s carries an invalid "
                        "plan (%s); retuning", path, e)
            return None

    def invalidate(self, fingerprint: Optional[str] = None) -> int:
        """Drop one cached plan (or, with ``fingerprint=None``, every
        plan in the directory); returns how many entries were removed.
        Failures are swallowed — invalidation is hygiene, never an
        error (a missing entry is already the desired state)."""
        if not self.directory:
            return 0
        if fingerprint is not None:
            try:
                os.remove(self.path(fingerprint))
                return 1
            except OSError:
                return 0
        removed = 0
        try:
            names = os.listdir(self.directory)
        except OSError:
            return 0
        for name in names:
            if name.startswith("plan_") and name.endswith(".json"):
                try:
                    os.remove(os.path.join(self.directory, name))
                    removed += 1
                except OSError:
                    pass
        return removed

    def store(self, fingerprint: str, plan: Plan,
              meta: Optional[Dict[str, Any]] = None) -> Optional[str]:
        """Atomic write (tmp + rename) so a killed run can't leave a
        truncated entry that poisons the next. Failures log and return
        None — persistence is an optimization, never an error."""
        if not self.directory:
            return None
        doc = {"version": PLAN_CACHE_VERSION,
               "fingerprint": fingerprint,
               "plan": plan.to_dict(),
               "meta": meta or {}}
        path = self.path(fingerprint)
        try:
            os.makedirs(self.directory, exist_ok=True)
            tmp = path + f".tmp.{os.getpid()}"
            with open(tmp, "w") as f:
                json.dump(doc, f, indent=1, sort_keys=True)
            os.replace(tmp, path)
            return path
        except OSError as e:
            log.warning("autotune plan cache write failed (%s); the "
                        "tuned plan will not survive this process", e)
            return None


def invalidate_plan_cache(cache_dir: Optional[str] = None) -> int:
    """Drop every persisted tuned plan under the resolved cache
    directory (argument > ``HVD_TPU_AUTOTUNE_CACHE_DIR``); returns the
    number of entries removed (0 when persistence is off).  The
    autopilot's ``retune`` remediation calls this on a topology/world
    change (docs/OBSERVABILITY.md "Autopilot"): the cached plans encode
    the OLD world's measured tradeoffs, and the next search must run
    against the world that actually exists."""
    directory = resolve_cache_dir(cache_dir)
    removed = PlanCache(directory).invalidate()
    if removed:
        log.warning("autotune plan cache invalidated: %d entr%s removed "
                    "from %s", removed, "y" if removed == 1 else "ies",
                    directory)
    return removed


# ---------------------------------------------------------------------------
# Online search: successive halving over measured step time
# ---------------------------------------------------------------------------

def _autotune_metrics():
    from horovod_tpu.metrics.registry import default_registry
    return default_registry()


def _record_locked_plan(plan: Plan, best_s: Optional[float],
                        from_cache: bool, trials: int) -> None:
    reg = _autotune_metrics()
    reg.gauge("hvd_autotune_locked",
              help="1 once the mesh autotuner locked a plan").set(1.0)
    reg.gauge("hvd_autotune_plan_bucket_bytes",
              help="bucket byte budget of the locked plan"
              ).set(float(plan.bucket_bytes))
    reg.gauge("hvd_autotune_plan_small_floor_bytes",
              help="small-bucket latency floor of the locked plan"
              ).set(float(plan.small_floor))
    # exactly ONE combination may read 1: a re-lock (elastic re-mesh
    # retune) must zero the previously active series, or the fleet view
    # shows two live plans at once
    for algo in _ALGORITHMS:
        for codec in _CODECS:
            reg.gauge("hvd_autotune_plan",
                      help="locked plan identity (1 on the active "
                           "algorithm/codec combination)",
                      labels={"algorithm": algo, "codec": codec}).set(
                1.0 if (algo, codec) == (plan.algorithm, plan.codec)
                else 0.0)
    if best_s is not None:
        reg.gauge("hvd_autotune_best_step_seconds",
                  help="measured step seconds of the locked plan"
                  ).set(best_s)
    if hasattr(plan, "schedule"):
        # a locked ParallelPlan also lands the pipeline-layout gauges
        # (hvd_pipeline_*, docs/OBSERVABILITY.md "Pipeline metrics")
        from horovod_tpu.train.pipeline import _pipeline_metrics
        _pipeline_metrics(plan)
    if from_cache:
        reg.counter("hvd_autotune_cache_hits_total",
                    help="runs that started from a cached tuned plan "
                         "with zero search trials").inc()
    from horovod_tpu.diagnostics.flight_recorder import record_event
    record_event("autotune_locked", plan=plan.key,
                 from_cache=from_cache, trials=trials,
                 best_step_s=best_s)


class AutotuneController:
    """Budget-bounded successive halving over candidate :class:`Plan`\\ s.

    Drive it one step at a time: ``begin_step()`` names the plan to run,
    ``end_step(seconds)`` (or :meth:`observe` from an external clock
    like ``StepTimer``) scores it. The first step a plan runs in a
    round is a WARMUP — it absorbs the plan's compile — and is never
    scored. When one survivor remains, or ``budget_steps`` search steps
    have been consumed, the best plan locks: ``locked_plan`` is set,
    metrics/flight/CSV record the choice, and the cache (when
    configured) is written so the next run starts locked with zero
    trials.
    """

    def __init__(self, plans: Sequence[Plan], *,
                 budget_steps: Optional[int] = None,
                 steps_per_trial: int = 2,
                 log_path: Optional[str] = None,
                 cache: Optional[PlanCache] = None,
                 fingerprint: Optional[str] = None) -> None:
        if not plans:
            raise ValueError("need at least one candidate plan")
        if budget_steps is None:
            from horovod_tpu.common.config import get_config
            budget_steps = get_config().autotune_budget_steps
        self.budget_steps = max(1, int(budget_steps))
        self.steps_per_trial = max(1, int(steps_per_trial))
        self.cache = cache
        self.fingerprint = fingerprint
        self._log_path = log_path
        self._log_header_written = False
        # trim the speculative tail so at least one full scoring round
        # fits the budget — and SAY what was dropped (no silent caps)
        per_plan = 1 + self.steps_per_trial
        max_plans = max(1, self.budget_steps // per_plan)
        plans = list(dict.fromkeys(plans))
        if len(plans) > max_plans:
            dropped = plans[max_plans:]
            log.warning(
                "autotune budget %d steps fits %d of %d candidate "
                "plans (%d steps each); dropping: %s",
                self.budget_steps, max_plans, len(plans), per_plan,
                ", ".join(p.key for p in dropped))
            plans = plans[:max_plans]
        self._survivors: List[Plan] = plans
        self._round = 0
        self._trial_steps = self.steps_per_trial
        self._scores: Dict[Plan, float] = {}
        self._samples: List[float] = []
        self._plan_idx = 0
        self._step_in_plan = 0
        self.steps_used = 0
        self.trials = 0          # scored (non-warmup) measurements
        self.from_cache = False
        self.locked_plan: Optional[Plan] = None
        self.best_seconds: Optional[float] = None
        self._pending: Optional[Plan] = None

    # -- cache warm start ---------------------------------------------------

    def try_cache(self) -> bool:
        """Adopt a cached plan for this controller's fingerprint; True
        when warm (zero trials will run)."""
        if self.cache is None or not self.fingerprint:
            return False
        plan = self.cache.load(self.fingerprint)
        if plan is None:
            return False
        self.from_cache = True
        self._lock(plan, best=None)
        log.info("autotune: warm plan cache hit — locked %s with zero "
                 "search trials", plan.key)
        return True

    # -- stepping -----------------------------------------------------------

    @property
    def done(self) -> bool:
        return self.locked_plan is not None

    def begin_step(self) -> Plan:
        """The plan the NEXT training step should run."""
        if self.locked_plan is not None:
            return self.locked_plan
        self._pending = self._survivors[self._plan_idx]
        return self._pending

    def end_step(self, seconds: float) -> None:
        """Score the step issued by the last ``begin_step``."""
        if self.locked_plan is not None:
            return
        plan = self._pending
        if plan is None:
            return
        self._pending = None
        self.steps_used += 1
        warmup = self._step_in_plan == 0
        self._step_in_plan += 1
        if not warmup:
            self._samples.append(float(seconds))
            self.trials += 1
            reg = _autotune_metrics()
            reg.counter("hvd_autotune_trials_total",
                        help="scored mesh-autotune trial steps").inc()
            reg.gauge("hvd_autotune_trial_step_seconds",
                      help="last scored trial step time",
                      labels={"plan": plan.key}).set(float(seconds))
            from horovod_tpu.diagnostics.flight_recorder import record_event
            record_event("autotune_trial", plan=plan.key,
                         round=self._round, step_s=round(seconds, 6))
        if self._step_in_plan >= 1 + self._trial_steps:
            # plan's window complete. Score = MIN over the window:
            # contention only ever adds time, so the fastest observed
            # step is the cleanest estimate of what the plan can do
            # (a mean/median would let one scheduler hiccup on a
            # loaded box evict the true winner)
            if self._samples:
                score = min(self._samples)
                self._scores[plan] = score
                self._log_trial(plan, score)
            self._samples = []
            self._step_in_plan = 0
            self._plan_idx += 1
            if self._plan_idx >= len(self._survivors):
                self._finish_round()
        if self.locked_plan is None and self.steps_used >= self.budget_steps:
            self._lock_best("step budget exhausted")

    # external clock (StepTimer / the PR-7 time-series ring feed)
    observe = end_step

    def _finish_round(self) -> None:
        scored = [p for p in self._survivors if p in self._scores]
        if not scored:
            self._lock_best("no scored plans")
            return
        scored.sort(key=lambda p: self._scores[p])
        keep = max(1, len(scored) // 2)
        if keep == 1 or self.steps_used >= self.budget_steps:
            # a lone survivor cannot be out-raced by anyone: locking now
            # saves an entire doubled re-measurement window of pure
            # timing overhead
            self._lock(scored[0], best=self._scores[scored[0]])
            return
        self._survivors = scored[:keep]
        self._round += 1
        self._trial_steps *= 2  # fewer survivors, finer measurement
        self._plan_idx = 0
        self._step_in_plan = 0
        log.info("autotune round %d: %d survivors (best %s @ %.6fs)",
                 self._round, len(self._survivors), scored[0].key,
                 self._scores[scored[0]])

    def _lock_best(self, why: str) -> None:
        if self._scores:
            best = min(self._scores, key=self._scores.get)
            self._lock(best, best=self._scores[best])
        else:
            # budget too small to score anything: the baseline
            # (first candidate) is the only defensible choice
            self._lock(self._survivors[0], best=None)
        log.info("autotune: locked %s (%s, %d scored trials, %d steps)",
                 self.locked_plan.key, why, self.trials, self.steps_used)

    def _lock(self, plan: Plan, best: Optional[float]) -> None:
        self.locked_plan = plan
        self.best_seconds = best
        _record_locked_plan(plan, best, self.from_cache, self.trials)
        self._log_trial(plan, best if best is not None else float("nan"),
                        final=True)
        if self.cache is not None and self.fingerprint \
                and not self.from_cache:
            self.cache.store(self.fingerprint, plan, meta={
                "best_step_seconds": best,
                "trials": self.trials,
                "steps_used": self.steps_used,
            })

    # -- CSV trace (like the C++ core's HVD_TPU_AUTOTUNE_LOG) ---------------

    _CSV_HEADER = ("round,bucket_bytes,algorithm,codec,small_floor,"
                   "plan,step_s,final\n")

    def _log_trial(self, plan: Plan, score: float,
                   final: bool = False) -> None:
        if not self._log_path:
            return
        try:
            # append-only: a second controller in the same process (an
            # elastic re-mesh retuning) must extend the audit trail, not
            # truncate the previous search's rows. Header only when the
            # file is new/empty. A trace written under an OLDER column
            # schema is rotated to <path>.v1 first — appending 8-field
            # rows under a 7-column header would silently misalign every
            # consumer parsing by header.
            if not self._log_header_written \
                    and os.path.exists(self._log_path):
                with open(self._log_path) as f:
                    first = f.readline()
                if first and first != self._CSV_HEADER:
                    os.replace(self._log_path, self._log_path + ".v1")
                    log.info("autotune CSV trace %s used an older "
                             "schema; rotated to %s.v1",
                             self._log_path, self._log_path)
            with open(self._log_path, "a") as f:
                if not self._log_header_written:
                    if f.tell() == 0:
                        f.write(self._CSV_HEADER)
                    self._log_header_written = True
                f.write(f"{self._round},{plan.bucket_bytes},"
                        f"{plan.algorithm},{plan.codec},"
                        f"{plan.small_floor},{plan.key},{score:.6f},"
                        f"{1 if final else 0}\n")
        except OSError:
            pass  # the trace is advisory, never fatal


# ---------------------------------------------------------------------------
# The autotune= seam behind make_overlap_train_step
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class AutotuneOptions:
    """Configuration for the ``autotune=`` seam. ``True`` resolves to
    env-driven defaults (``HVD_TPU_AUTOTUNE_BUDGET_STEPS``,
    ``HVD_TPU_AUTOTUNE_CACHE_DIR``, ``HVD_TPU_AUTOTUNE_LOG``)."""

    budget_steps: Optional[int] = None
    steps_per_trial: int = 2
    cache_dir: Optional[str] = None
    log_path: Optional[str] = None
    plans: Optional[Sequence[Plan]] = None
    include_fp8: bool = False

    @classmethod
    def resolve(cls, autotune) -> "AutotuneOptions":
        if isinstance(autotune, AutotuneOptions):
            return autotune
        if autotune is True or autotune is None:
            return cls()
        if isinstance(autotune, Plan):
            # a pinned plan: zero search, just realize it
            return cls(plans=[autotune], budget_steps=1)
        raise TypeError(
            f"autotune= takes True, AutotuneOptions or Plan; got "
            f"{autotune!r}")

    def resolved_log_path(self) -> str:
        if self.log_path is not None:
            return self.log_path
        from horovod_tpu.common.config import get_config
        base = get_config().autotune_log
        return (base + ".mesh.csv") if base else ""


class AutotunedStep:
    """Callable train step that searches, then serves.

    While searching, every call picks the controller's candidate plan,
    runs that plan's compiled step, blocks for the result and feeds the
    measured wall time back. Once locked (search converged, budget
    spent, or warm cache hit on the first call), calls dispatch straight
    to the winning compiled step with zero added overhead.
    """

    def __init__(self, build_step: Callable[[Plan], Callable],
                 controller_factory: Callable[[Any], AutotuneController]
                 ) -> None:
        self._build_step = build_step
        self._controller_factory = controller_factory
        self._steps: Dict[Plan, Callable] = {}
        self.autotune: Optional[AutotuneController] = None
        self._locked_fn: Optional[Callable] = None

    def _get(self, plan: Plan) -> Callable:
        fn = self._steps.get(plan)
        if fn is None:
            fn = self._steps[plan] = self._build_step(plan)
        return fn

    def __call__(self, params, opt_state, batch):
        import jax
        if self.autotune is None:
            # first call: the params tree is finally in hand — resolve
            # the fingerprint and try the warm cache before any trial
            self.autotune = self._controller_factory(params)
        ctl = self.autotune
        if self._locked_fn is None and ctl.locked_plan is not None:
            self._locked_fn = self._get(ctl.locked_plan)
        if self._locked_fn is not None:
            return self._locked_fn(params, opt_state, batch)
        plan = ctl.begin_step()
        fn = self._get(plan)
        t0 = time.perf_counter()
        out = fn(params, opt_state, batch)
        jax.block_until_ready(out)
        ctl.end_step(time.perf_counter() - t0)
        if ctl.locked_plan is not None:
            self._locked_fn = self._get(ctl.locked_plan)
        return out


def make_autotuned_train_step(loss_fn, optimizer, mesh,
                              axis_name: str = "dp", *,
                              autotune=True,
                              n_micro: int = 1,
                              op=None,
                              bucket_bytes: Optional[int] = None,
                              compression=None,
                              ring: bool = False,
                              algorithm: Optional[str] = None,
                              topology=None,
                              small_floor: Optional[int] = None,
                              overlap: bool = True,
                              sync: bool = True,
                              donate: bool = True,
                              guard=None) -> AutotunedStep:
    """Build the searching/serving step for
    ``make_overlap_train_step(..., autotune=...)``.

    The explicit communication kwargs (``bucket_bytes`` / ``algorithm``
    / ``compression`` / ``small_floor``) become the BASELINE candidate —
    the search can only confirm or beat the hand-set config.
    """
    from horovod_tpu.common.topology import detect_topology
    from horovod_tpu.ops.reduce_op import Average
    from horovod_tpu.train.buckets import resolve_bucket_bytes
    from horovod_tpu.train.overlap import (make_overlap_train_step,
                                           resolve_small_floor)

    opts = AutotuneOptions.resolve(autotune)
    if op is None:
        op = Average
    topo = topology if topology is not None \
        else detect_topology(mesh, axis_name)
    world = int(mesh.shape[axis_name])
    baseline = Plan(
        bucket_bytes=resolve_bucket_bytes(bucket_bytes),
        algorithm=algorithm or ("ring" if ring else "psum"),
        codec=_codec_name(compression),
        small_floor=resolve_small_floor(small_floor))
    plans = list(opts.plans) if opts.plans else candidate_plans(
        topo, baseline=baseline, include_fp8=opts.include_fp8)
    cache_dir = resolve_cache_dir(opts.cache_dir)
    cache = PlanCache(cache_dir) if cache_dir else None
    # comm plans are tuned UNDER a fixed mesh: the key carries that
    # mesh's pp size (the eager DistributedOptimizer seam has no mesh
    # and reconstructs the key with the default pp=1)
    mesh_shape = topology_key(topo, pp=int(mesh.shape.get("pp", 1)))

    def build_step(plan: Plan):
        # autotune=False is load-bearing: with HVD_TPU_AUTOTUNE_MESH=1
        # the factory's env default would otherwise re-enter THIS
        # function for every candidate, forever
        return make_overlap_train_step(
            loss_fn, optimizer, mesh, axis_name, n_micro=n_micro, op=op,
            overlap=overlap, sync=sync, donate=donate, autotune=False,
            guard=guard, **plan.step_kwargs(topo))

    def controller_factory(params) -> AutotuneController:
        fp = plan_fingerprint(params, mesh_shape, world)
        ctl = AutotuneController(
            plans, budget_steps=opts.budget_steps,
            steps_per_trial=opts.steps_per_trial,
            log_path=opts.resolved_log_path(),
            cache=cache, fingerprint=fp)
        ctl.try_cache()
        return ctl

    return AutotunedStep(build_step, controller_factory)


# ---------------------------------------------------------------------------
# ISSUE 11: the PARALLELISM plan joins the same search
# ---------------------------------------------------------------------------

def parallel_candidate_plans(world: int, n_layers: int, *,
                             baseline=None,
                             schedules: Sequence[str] = ("1f1b", "gpipe",
                                                         "interleaved"),
                             max_pp: Optional[int] = None,
                             include_comms: bool = True) -> List[Any]:
    """The discrete (dp x pp) x schedule x n_microbatches x comms search
    space for :func:`make_parallel_train_step`, most-promising-first.

    Layout candidates: every pp that divides both the world and the
    layer count (pp=1 — pure DP with the comm defaults — is the
    baseline and always first: the search can only confirm or beat it).
    Per pipeline layout: each schedule, microbatch counts {pp, 2*pp}
    (enough to fill the pipe vs halve the bubble), and interleaved adds
    ``virtual_stages=2`` where the layers split. ``include_comms`` adds
    an int8-codec bucketed-sync variant of each layout with dp > 1 —
    (pp, M, schedule) joining bucket x algorithm x codec as axes of ONE
    search, per the ROADMAP. The tail is ordered cheapest-compile-first
    so budget trimming (the controller's no-silent-caps warning) drops
    the speculative end."""
    from horovod_tpu.parallel.plan import ParallelPlan
    from horovod_tpu.train.buckets import resolve_bucket_bytes

    plans: List[Any] = []
    if baseline is not None:
        plans.append(baseline)
    plans.append(ParallelPlan(dp=world, pp=1))
    pps = [p for p in range(2, (max_pp or world) + 1)
           if world % p == 0 and n_layers % p == 0]
    comm_variant = Plan(resolve_bucket_bytes(None), "psum", "int8") \
        if include_comms else None
    for pp in pps:
        dp = world // pp
        for M in (pp, 2 * pp):
            for schedule in schedules:
                if schedule == "interleaved":
                    if n_layers % (pp * 2) != 0:
                        continue
                    v = 2
                else:
                    v = 1
                plans.append(ParallelPlan(
                    dp=dp, pp=pp, schedule=schedule, n_microbatches=M,
                    virtual_stages=v))
                if comm_variant is not None and dp > 1:
                    plans.append(ParallelPlan(
                        dp=dp, pp=pp, schedule=schedule,
                        n_microbatches=M, virtual_stages=v,
                        comms=comm_variant))
    seen, out = set(), []
    for p in plans:
        if p not in seen:
            seen.add(p)
            out.append(p)
    return out


class ParallelAutotunedStep:
    """Searching/serving step over whole :class:`ParallelPlan`\\ s.

    Like :class:`AutotunedStep`, but candidate filtering needs the BATCH
    (a plan whose ``dp * n_microbatches`` does not tile the global batch
    cannot compile), so the controller is constructed on the first call
    when params AND batch are finally in hand. Candidate steps keep the
    caller's params in natural layer order — each candidate permutes
    in/out of its own storage layout internally — so one (params,
    opt_state) pair flows through every trial unchanged. Once locked,
    ``pin()`` returns the underlying
    :class:`~horovod_tpu.train.pipeline.PipelineTrainStep` for
    permutation-free steady state (pin once, re-``prepare_params``)."""

    def __init__(self, plans: Sequence[Any],
                 build_step: Callable[[Any], Any],
                 controller_factory: Callable, n_layers: int) -> None:
        self._plans = list(plans)
        self._build_step = build_step
        self._controller_factory = controller_factory
        self._n_layers = n_layers
        self._steps: Dict[Any, Callable] = {}
        self._raw: Dict[Any, Any] = {}
        self.autotune: Optional[AutotuneController] = None
        self._locked_fn: Optional[Callable] = None

    def _fits(self, plan, batch_dim: int, n_layers: int) -> bool:
        per_replica = batch_dim // plan.dp if batch_dim % plan.dp == 0 \
            else 0
        return (batch_dim % plan.dp == 0
                and per_replica % plan.n_microbatches == 0
                and n_layers % plan.total_stages == 0)

    def _get(self, plan):
        fn = self._steps.get(plan)
        if fn is None:
            raw = self._build_step(plan)
            self._raw[plan] = raw

            def fn(params, opt_state, batch, _raw=raw):
                p = _raw.prepare_params(params)
                o = _raw.prepare_params(opt_state)
                p, o, loss = _raw(p, o, batch)
                return (_raw.restore_params(p), _raw.restore_params(o),
                        loss)
            self._steps[plan] = fn
        return fn

    def pin(self):
        """The locked plan's bare step (natural-order permutation
        stripped); None while still searching."""
        ctl = self.autotune
        if ctl is None or ctl.locked_plan is None:
            return None
        self._get(ctl.locked_plan)
        return self._raw[ctl.locked_plan]

    def __call__(self, params, opt_state, batch):
        import jax
        if self.autotune is None:
            leaves = jax.tree_util.tree_leaves(batch)
            batch_dim = int(leaves[0].shape[0])
            self.autotune = self._controller_factory(
                params, batch_dim,
                lambda plan: self._fits(plan, batch_dim,
                                        self._n_layers))
        ctl = self.autotune
        if self._locked_fn is None and ctl.locked_plan is not None:
            self._locked_fn = self._get(ctl.locked_plan)
        if self._locked_fn is not None:
            return self._locked_fn(params, opt_state, batch)
        plan = ctl.begin_step()
        fn = self._get(plan)
        t0 = time.perf_counter()
        out = fn(params, opt_state, batch)
        jax.block_until_ready(out)
        ctl.end_step(time.perf_counter() - t0)
        if ctl.locked_plan is not None:
            self._locked_fn = self._get(ctl.locked_plan)
        return out


def make_parallel_train_step(layer_fn, loss_fn, optimizer, *,
                             n_layers: int,
                             devices=None,
                             autotune=True,
                             op=None,
                             donate: bool = True,
                             guard=None
                             ) -> ParallelAutotunedStep:
    """Search the unified parallelism space (ROADMAP 1, ISSUE 11): the
    dp x pp split, pipeline schedule, microbatch count and dp
    communication plan are scored together by measured step time on the
    layer-major model, successive-halving style, and the winner is
    fingerprinted into the SAME persistent plan cache as the
    communication tuner — a warm hit on a re-meshed world locks the
    full parallelism plan with zero trials.

    Called by ``make_pipeline_train_step(..., autotune=...)``; the model
    contract is that factory's layer-major one. The pure-DP layout
    (dp=world, pp=1) is always the baseline candidate."""
    import jax

    from horovod_tpu.common.topology import detect_topology, flat_topology
    from horovod_tpu.ops.reduce_op import Average

    if op is None:
        op = Average
    opts = AutotuneOptions.resolve(autotune)
    devs = list(devices) if devices is not None else list(jax.devices())
    world = len(devs)
    try:
        topo = detect_topology(n=world)
    except Exception:
        topo = flat_topology(world)
    plans = list(opts.plans) if opts.plans else parallel_candidate_plans(
        world, n_layers)
    cache_dir = resolve_cache_dir(opts.cache_dir)
    cache = PlanCache(cache_dir) if cache_dir else None
    # pp=0: the dp x pp split is itself a searched axis (see
    # topology_key); the key identifies the WORLD + model
    mesh_shape = topology_key(topo, pp=0)

    def build_step(plan):
        from horovod_tpu.train.pipeline import make_pipeline_train_step
        return make_pipeline_train_step(
            layer_fn, loss_fn, optimizer, plan=plan, n_layers=n_layers,
            devices=devs, op=op, donate=donate, autotune=False,
            guard=guard)

    def controller_factory(params, batch_dim: int,
                           fits) -> AutotuneController:
        usable = [p for p in plans if fits(p)]
        dropped = [p for p in plans if not fits(p)]
        if dropped:
            log.info(
                "parallel autotune: %d of %d candidate plans cannot "
                "tile batch=%d x %d layers and were skipped: %s",
                len(dropped), len(plans), batch_dim, n_layers,
                ", ".join(p.key for p in dropped[:8])
                + ("..." if len(dropped) > 8 else ""))
        if not usable:
            raise ValueError(
                f"no parallelism plan tiles global batch {batch_dim} "
                f"over {world} devices with {n_layers} layers")
        fp = plan_fingerprint(params, mesh_shape, world)
        ctl = AutotuneController(
            usable, budget_steps=opts.budget_steps,
            steps_per_trial=opts.steps_per_trial,
            log_path=opts.resolved_log_path(),
            cache=cache, fingerprint=fp)
        # the fingerprint covers tree+world, NOT the batch: a cached
        # plan tuned at another global batch size may not tile this
        # one. Validate BEFORE adopting — the documented cache contract
        # is "stale entries retune, never crash"
        cached = cache.load(fp) if cache is not None else None
        if cached is not None and (not hasattr(cached, "total_stages")
                                   or not fits(cached)):
            log.warning(
                "cached parallelism plan %s cannot tile global batch "
                "%d x %d layers on this run; retuning",
                getattr(cached, "key", cached), batch_dim, n_layers)
        else:
            ctl.try_cache()
        return ctl

    return ParallelAutotunedStep(plans, build_step, controller_factory,
                                 n_layers)
