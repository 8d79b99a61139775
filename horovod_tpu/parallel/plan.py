"""Unified parallelism plan: "how should this model run on this mesh"
as a frozen, cacheable object (ROADMAP item 1).

PR 8 froze the COMMUNICATION decision into
:class:`horovod_tpu.train.autotune.Plan` (bucket bytes x algorithm x
codec x small floor) and made it a searched, fingerprint-cached choice.
This module generalizes that object one level up: a
:class:`ParallelPlan` fixes the dp x pp mesh split, the pipeline
schedule (GPipe / 1F1B / interleaved-1F1B with ``virtual_stages``
chunks per device), the microbatch count, and NESTS a communication
plan for the dp gradient traffic. The same successive-halving search
(``train/autotune.py``) scores whole parallelism plans by measured step
time and persists the winner to the same plan cache, so an elastic
re-mesh back to a seen world locks dp split, schedule, microbatching
AND communication config with zero trials.

:func:`compile_step_with_plan` is the Titanax-style single compile seam
(SNIPPETS.md [2]/[3]): ``pjit`` (jit with explicit shardings) when the
caller provides shardings, ``shard_map`` for map-style SPMD bodies, and
a plain mesh-scoped ``jit`` on a single device. Step factories go
through this one entry point so "how a step is compiled" is decided by
the plan, not scattered per call site.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, Optional, Sequence, Tuple

SCHEDULES: Tuple[str, ...] = ("gpipe", "1f1b", "interleaved")


def _comm_plan_cls():
    # lazy: parallel.plan must stay importable without pulling the train
    # package's heavier deps at import time
    from horovod_tpu.train.autotune import Plan
    return Plan


@dataclasses.dataclass(frozen=True)
class ParallelPlan:
    """One point in the parallelism search space.

    ``dp`` x ``pp`` must multiply to the device count the plan is bound
    to. ``schedule``: ``gpipe`` (all forwards, then autodiff backward —
    fastest ticks, activation memory grows with ``n_microbatches``),
    ``1f1b`` (combined fwd+bwd ticks, ``min(2*pp-1, M)``-entry remat
    ring — bounded memory), ``interleaved`` (1F1B with
    ``virtual_stages`` chunks per device — ``~1/v`` of the 1F1B fill/
    drain bubble at the same ``M``). ``comms`` is the nested
    communication :class:`~horovod_tpu.train.autotune.Plan` for dp
    gradient reduction (None = dense psum defaults).
    """

    dp: int = 1
    pp: int = 1
    schedule: str = "1f1b"
    n_microbatches: int = 1
    virtual_stages: int = 1
    comms: Optional[Any] = None

    def __post_init__(self):
        if self.dp < 1 or self.pp < 1:
            raise ValueError(
                f"dp and pp must be >= 1, got dp={self.dp} pp={self.pp}")
        if self.schedule not in SCHEDULES:
            raise ValueError(f"unknown schedule {self.schedule!r}; "
                             f"expected one of {SCHEDULES}")
        if self.n_microbatches < 1:
            raise ValueError("n_microbatches must be >= 1")
        if self.virtual_stages < 1:
            raise ValueError("virtual_stages must be >= 1")
        if self.virtual_stages > 1 and self.schedule != "interleaved":
            raise ValueError(
                f"virtual_stages={self.virtual_stages} only makes sense "
                f"for the interleaved schedule, not {self.schedule!r}")
        if self.pp > 1 and self.n_microbatches < 2:
            raise ValueError(
                "a pipeline (pp > 1) needs n_microbatches >= 2 — with one "
                "microbatch every schedule is pure bubble")
        if self.comms is not None and not hasattr(self.comms, "step_kwargs"):
            raise ValueError(
                f"comms must be a communication Plan (train.autotune.Plan), "
                f"got {self.comms!r}")

    # -- identity -----------------------------------------------------------

    @property
    def world(self) -> int:
        return self.dp * self.pp

    @property
    def total_stages(self) -> int:
        return self.pp * self.virtual_stages

    @property
    def key(self) -> str:
        """Short human label (CSV / flight / metric labels)."""
        base = f"dp{self.dp}xpp{self.pp}/{self.schedule}"
        if self.schedule == "interleaved":
            base += f"v{self.virtual_stages}"
        base += f"/m{self.n_microbatches}"
        if self.comms is not None:
            base += f"[{self.comms.key}]"
        return base

    # the communication-plan facade: the shared autotune controller /
    # CSV trace / locked-plan gauges read these four knobs off any plan
    # they score, so a ParallelPlan delegates to its nested comms plan
    @property
    def bucket_bytes(self) -> int:
        return self.comms.bucket_bytes if self.comms is not None else 0

    @property
    def algorithm(self) -> str:
        return self.comms.algorithm if self.comms is not None else "psum"

    @property
    def codec(self) -> str:
        return self.comms.codec if self.comms is not None else "none"

    @property
    def small_floor(self) -> int:
        return self.comms.small_floor if self.comms is not None else 0

    # -- serialization (plan cache) -----------------------------------------

    def to_dict(self) -> Dict[str, Any]:
        d = {"kind": "parallel", "dp": self.dp, "pp": self.pp,
             "schedule": self.schedule,
             "n_microbatches": self.n_microbatches,
             "virtual_stages": self.virtual_stages}
        if self.comms is not None:
            d["comms"] = self.comms.to_dict()
        return d

    @classmethod
    def from_dict(cls, d: Dict[str, Any]) -> "ParallelPlan":
        comms = d.get("comms")
        return cls(dp=int(d["dp"]), pp=int(d["pp"]),
                   schedule=str(d.get("schedule", "1f1b")),
                   n_microbatches=int(d.get("n_microbatches", 1)),
                   virtual_stages=int(d.get("virtual_stages", 1)),
                   comms=_comm_plan_cls().from_dict(comms)
                   if comms is not None else None)

    # -- analytics / binding ------------------------------------------------

    def bubble_fraction(self) -> float:
        """Analytic fill+drain bubble fraction of this plan's schedule
        (0.0 when pp == 1; cost model in ``parallel/pipeline.py``)."""
        from horovod_tpu.parallel.pipeline import bubble_fraction
        return bubble_fraction(self.schedule, self.pp,
                               self.n_microbatches, self.virtual_stages)

    def build_mesh(self, devices: Optional[Sequence] = None):
        """Realize this plan's dp x pp mesh
        (:func:`horovod_tpu.parallel.mesh.dp_pp_mesh`)."""
        from horovod_tpu.parallel.mesh import dp_pp_mesh
        return dp_pp_mesh(dp=self.dp, pp=self.pp, devices=devices)

    def validate_for(self, n_devices: int, n_layers: Optional[int] = None,
                     batch_per_replica: Optional[int] = None) -> None:
        """Bind-time checks: the plan must tile ``n_devices`` exactly;
        ``n_layers`` (when known) must split into ``total_stages`` equal
        chunks; the per-replica batch must split into microbatches."""
        if self.world != n_devices:
            raise ValueError(
                f"plan {self.key} needs dp*pp == {self.world} devices, "
                f"have {n_devices}")
        if n_layers is not None and n_layers % self.total_stages != 0:
            raise ValueError(
                f"{n_layers} layers not divisible into "
                f"{self.total_stages} stages (pp={self.pp} x "
                f"v={self.virtual_stages})")
        if batch_per_replica is not None \
                and batch_per_replica % self.n_microbatches != 0:
            raise ValueError(
                f"per-replica batch {batch_per_replica} not divisible by "
                f"{self.n_microbatches} microbatches")


def plan_from_dict(d: Dict[str, Any]):
    """Revive a plan of either kind from its cache dict: a
    :class:`ParallelPlan` when the doc says so (``kind`` tag or pipeline
    fields), else a communication
    :class:`~horovod_tpu.train.autotune.Plan`."""
    if d.get("kind") == "parallel" or "schedule" in d:
        return ParallelPlan.from_dict(d)
    return _comm_plan_cls().from_dict(d)


# ---------------------------------------------------------------------------
# The single compile seam (Titanax-style, SNIPPETS.md [2]/[3])
# ---------------------------------------------------------------------------

def compile_step_with_plan(step_fn: Callable, mesh, *,
                           in_shardings=None, out_shardings=None,
                           in_specs=None, out_specs=None,
                           donate_argnums: Tuple[int, ...] = (),
                           static_argnums: Tuple[int, ...] = (),
                           check_vma: bool = False) -> Callable:
    """Compile a step function one of three ways, chosen by what the
    caller can describe:

    * **pjit path** — explicit ``in_shardings``/``out_shardings``
      (BOTH required): ``jax.jit`` with shardings. For GSPMD-auto
      programs where the sharding annotations carry the parallelism.
    * **shard_map path** — ``in_specs``/``out_specs`` (BOTH required):
      map-style SPMD body (collectives spelled out: psum/ppermute/...)
      wrapped in ``shard_map`` then jitted. This is what every pure-DP
      and pipeline step factory uses.
    * **single-device / fallback** — neither given, or the mesh has one
      device: plain ``jax.jit`` with the mesh entered around the body,
      so ``lax.axis_index``-free code runs unchanged.

    Mixing the two description styles, or providing only half of one,
    raises — the seam exists so there is exactly one way a step gets
    compiled for a given plan.
    """
    import jax

    from horovod_tpu._compat import shard_map

    have_shardings = (in_shardings is not None) or (out_shardings is not None)
    have_specs = (in_specs is not None) or (out_specs is not None)
    if have_shardings and have_specs:
        raise ValueError(
            "pass either explicit shardings (pjit path) or shard_map "
            "specs, not both")
    if have_shardings and (in_shardings is None or out_shardings is None):
        raise ValueError(
            "compile_step_with_plan requires BOTH in_shardings and "
            "out_shardings for the pjit path")
    if have_specs and (in_specs is None or out_specs is None):
        raise ValueError(
            "compile_step_with_plan requires BOTH in_specs and out_specs "
            "for the shard_map path")

    if have_shardings:
        return jax.jit(step_fn, in_shardings=in_shardings,
                       out_shardings=out_shardings,
                       donate_argnums=donate_argnums,
                       static_argnums=static_argnums)
    if have_specs:
        # even on a 1-device mesh: the body may use named-axis
        # collectives (axis size 1), which only exist under shard_map
        mapped = shard_map(step_fn, mesh=mesh, in_specs=in_specs,
                           out_specs=out_specs, check_vma=check_vma)
        return jax.jit(mapped, donate_argnums=donate_argnums,
                       static_argnums=static_argnums)

    def single_device_fn(*args, **kwargs):
        if mesh is not None:
            with mesh:
                return step_fn(*args, **kwargs)
        return step_fn(*args, **kwargs)

    return jax.jit(single_device_fn, donate_argnums=donate_argnums,
                   static_argnums=static_argnums)
