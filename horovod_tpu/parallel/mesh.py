"""Device-mesh construction and sharding rules — the TPU data plane.

There is no reference analog: the reference's data plane is NCCL rings
(``horovod/common/ops/nccl_operations.cc``). On TPU the equivalent of "create a
NCCL communicator per (process set, device map, stream)"
(``nccl_operations.cc:65-107``) is "build a named `jax.sharding.Mesh` per
process set and let XLA place collectives on ICI/DCN". This module owns the
axis conventions used across the framework:

==========  =========================================  ==================
axis name   parallelism                                collective traffic
==========  =========================================  ==================
``dp``      data parallel (gradient reduction)          psum / reduce_scatter
``pp``      pipeline parallel (stage to stage)          ppermute
``ep``      expert parallel (MoE token dispatch)        all_to_all
``sp``      sequence/context parallel (ring attention,  ppermute / all_to_all
            Ulysses)
``tp``      tensor parallel (sharded matmuls)           psum / all_gather
==========  =========================================  ==================

Axis order is chosen so that ``tp`` (highest bandwidth need, per-layer
collectives) maps to the innermost — most tightly ICI-coupled — devices, and
``dp`` to the outermost (can ride DCN across slices), following the standard
TPU scaling recipe (jax-ml scaling book).
"""

from __future__ import annotations

import dataclasses
import math
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

# Canonical axis order, outermost → innermost.
AXIS_ORDER: Tuple[str, ...] = ("dp", "pp", "ep", "sp", "tp")

DATA_AXIS = "dp"
PIPELINE_AXIS = "pp"
EXPERT_AXIS = "ep"
SEQUENCE_AXIS = "sp"
TENSOR_AXIS = "tp"


@dataclasses.dataclass(frozen=True)
class MeshSpec:
    """Declarative mesh shape. ``-1`` for at most one axis means "absorb all
    remaining devices" (conventionally ``dp``)."""

    dp: int = -1
    pp: int = 1
    ep: int = 1
    sp: int = 1
    tp: int = 1

    def resolve(self, n_devices: int) -> Dict[str, int]:
        sizes = {"dp": self.dp, "pp": self.pp, "ep": self.ep,
                 "sp": self.sp, "tp": self.tp}
        wild = [a for a, s in sizes.items() if s == -1]
        if len(wild) > 1:
            raise ValueError(f"At most one axis may be -1, got {wild}")
        fixed = math.prod(s for s in sizes.values() if s != -1)
        if wild:
            if n_devices % fixed != 0:
                raise ValueError(
                    f"{n_devices} devices not divisible by fixed axes product "
                    f"{fixed} ({sizes})")
            sizes[wild[0]] = n_devices // fixed
        elif fixed != n_devices:
            raise ValueError(
                f"Mesh spec {sizes} needs {fixed} devices, have {n_devices}")
        return sizes


def build_mesh(spec: Optional[MeshSpec] = None,
               devices: Optional[Sequence[jax.Device]] = None,
               **axis_sizes: int) -> Mesh:
    """Build the framework's canonical 5-axis mesh.

    ``build_mesh(dp=2, tp=4)`` or ``build_mesh(MeshSpec(dp=2, tp=4))``.
    Unspecified axes get size 1 (``dp`` defaults to -1 = remainder), so every
    program is written against the full 5-axis mesh and degrades gracefully to
    fewer chips — the TPU analog of the reference working identically from 1
    to 512 GPUs.
    """
    if spec is None:
        spec = MeshSpec(**axis_sizes)
    elif axis_sizes:
        raise ValueError("Pass either a MeshSpec or keyword sizes, not both.")
    devices = list(devices if devices is not None else jax.devices())
    sizes = spec.resolve(len(devices))
    shape = tuple(sizes[a] for a in AXIS_ORDER)
    dev_array = np.asarray(devices).reshape(shape)
    return Mesh(dev_array, AXIS_ORDER)


def dp_pp_mesh(dp: int = -1, pp: int = 1,
               devices: Optional[Sequence[jax.Device]] = None) -> Mesh:
    """The documented two-axis dp x pp mesh for pipelined data-parallel
    training (``parallel/pipeline.py``): ``dp`` replicas each
    running a ``pp``-deep pipeline. ``dp=-1`` (default) absorbs the
    remaining devices, so ``dp_pp_mesh(pp=4)`` on 8 devices is the
    2x4 layout. ``pp`` is innermost (the canonical axis order), keeping
    stage-to-stage ``ppermute`` traffic on the most tightly coupled
    links while dp gradient reduction can ride slower links. This is
    the mesh constructor behind
    :func:`horovod_tpu.train.pipeline.make_pipeline_train_step` and
    :meth:`horovod_tpu.parallel.plan.ParallelPlan.build_mesh`."""
    return build_mesh(MeshSpec(dp=dp, pp=pp), devices=devices)


def single_axis_mesh(axis: str = DATA_AXIS,
                     devices: Optional[Sequence[jax.Device]] = None) -> Mesh:
    devices = list(devices if devices is not None else jax.devices())
    return Mesh(np.asarray(devices), (axis,))


def mesh_axis_size(mesh: Mesh, axis: str) -> int:
    return mesh.shape.get(axis, 1)


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


def sharded(mesh: Mesh, *spec) -> NamedSharding:
    return NamedSharding(mesh, P(*spec))


def batch_sharding(mesh: Mesh) -> NamedSharding:
    """Shard the leading (batch) dim over every data-like axis present."""
    axes = tuple(a for a in (DATA_AXIS,) if mesh_axis_size(mesh, a) > 1)
    return NamedSharding(mesh, P(axes if axes else None))


# ---------------------------------------------------------------------------
# Logical axis rules (t5x/flax-style): models annotate arrays with logical
# names; the rules map them to mesh axes. This is how one model definition
# serves pure-DP, TP, PP, SP and EP layouts without edits.
# ---------------------------------------------------------------------------

DEFAULT_RULES: Tuple[Tuple[str, Optional[Tuple[str, ...]]], ...] = (
    ("batch", ("dp",)),
    ("seq", ("sp",)),
    ("embed", None),
    ("mlp", ("tp",)),
    ("heads", ("tp",)),
    ("kv", None),
    ("vocab", ("tp",)),
    ("expert", ("ep",)),
    ("stage", ("pp",)),
    ("unsharded", None),
)


class AxisRules:
    def __init__(self, rules: Sequence[Tuple[str, Optional[Sequence[str]]]]
                 = DEFAULT_RULES) -> None:
        self._rules: Dict[str, Optional[Tuple[str, ...]]] = {
            k: (tuple(v) if v is not None else None) for k, v in rules}

    def spec(self, logical_axes: Sequence[Optional[str]], mesh: Mesh) -> P:
        parts: List = []
        used: set = set()
        for name in logical_axes:
            if name is None:
                parts.append(None)
                continue
            mapped = self._rules.get(name)
            if mapped is None:
                parts.append(None)
                continue
            live = tuple(a for a in mapped
                         if mesh_axis_size(mesh, a) > 1 and a not in used)
            used.update(live)
            if not live:
                parts.append(None)
            elif len(live) == 1:
                parts.append(live[0])
            else:
                parts.append(live)
        while parts and parts[-1] is None:
            parts.pop()
        return P(*parts)

    def sharding(self, logical_axes: Sequence[Optional[str]],
                 mesh: Mesh) -> NamedSharding:
        return NamedSharding(mesh, self.spec(logical_axes, mesh))


default_rules = AxisRules()


def logical_sharding(mesh: Mesh,
                     logical_axes: Sequence[Optional[str]],
                     rules: Optional[AxisRules] = None) -> NamedSharding:
    return (rules or default_rules).sharding(logical_axes, mesh)
