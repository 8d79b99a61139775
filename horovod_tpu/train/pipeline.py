"""Composed DP x PP train-step factory: pipelined training whose dp
gradient traffic goes through the bucketed overlap engine.

``make_pipeline_train_step`` is the pipeline analog of
:func:`horovod_tpu.train.overlap.make_overlap_train_step` — and
degenerates INTO it when the plan has ``pp == 1``, so one factory serves
the whole dp x pp plane. The model contract is layer-major (the layout
the flagship transformer's scanned blocks already use):

* ``params``: a pytree whose every leaf has leading dim ``n_layers``
  (layer ``i``'s parameters are ``tree_map(lambda p: p[i], params)``).
* ``layer_fn(layer_params, x) -> x`` applies ONE layer (activation
  shape preserved — the pipeline carry is a single array).
* ``loss_fn(y, targets) -> scalar`` consumes the last layer's output.

Layer-major is what makes (pp, virtual_stages) SEARCHABLE axes: the
same params restack into any ``pp x v`` split by reshaping the leading
dim, so the autotuner can score ``dp8/pp1`` against ``dp2xpp4/1f1b/m8``
against ``dp4xpp2/interleaved`` without touching the model.

Inside the step, stage gradients leave the pipeline scan through
:func:`~horovod_tpu.train.overlap.bucketed_grad_sync` over the dp axis
— byte-budgeted buckets, psum/ring/hierarchical algorithms, int8/fp8
error-feedback codecs, and the overlap telemetry all apply — instead of
the dense inline ``lax.pmean`` the island schedules used
(``dp_sync="dense"`` keeps the exact-parity fallback). Parameters and
optimizer state live pp-SHARDED along the layer dim (each pipeline rank
holds only its stages — the door to models too big for one chip), and
the (elementwise) optimizer applies inside ``shard_map`` on the local
shard with buffer donation, like ``make_overlap_train_step``.
"""

from __future__ import annotations

from typing import Callable, Optional, Sequence

import numpy as np

from horovod_tpu.common.logging import get_logger
from horovod_tpu.ops.reduce_op import Average, ReduceOp

log = get_logger()


def _pipeline_metrics(plan) -> None:
    """Land the locked parallelism layout on /metrics
    (docs/OBSERVABILITY.md "Pipeline metrics")."""
    try:
        from horovod_tpu.metrics.registry import default_registry
        from horovod_tpu.parallel.plan import SCHEDULES
        reg = default_registry()
        reg.gauge("hvd_pipeline_stages",
                  help="pipeline depth (pp mesh axis) of the active "
                       "train step").set(float(plan.pp))
        reg.gauge("hvd_pipeline_virtual_stages",
                  help="virtual stage chunks per device (interleaved "
                       "schedule)").set(float(plan.virtual_stages))
        reg.gauge("hvd_pipeline_microbatches",
                  help="microbatches per step of the active pipeline "
                       "plan").set(float(plan.n_microbatches))
        reg.gauge("hvd_pipeline_bubble_fraction",
                  help="analytic fill+drain bubble fraction of the "
                       "active schedule").set(plan.bubble_fraction())
        # exactly one schedule series reads 1 (re-lock zeroes the rest)
        for s in SCHEDULES:
            reg.gauge("hvd_pipeline_schedule",
                      help="active pipeline schedule (1 on the locked "
                           "schedule's series)",
                      labels={"schedule": s}).set(
                1.0 if s == plan.schedule else 0.0)
    except Exception:   # metrics are telemetry, never a step failure
        log.debug("pipeline metrics unavailable", exc_info=True)


def stage_layout_permutation(n_layers: int, pp: int,
                             virtual_stages: int = 1) -> np.ndarray:
    """Natural-layer-order -> storage-order permutation for a pp x v
    split. Storage is device-major (device d's chunks contiguous) so a
    plain contiguous shard over ``pp`` hands every pipeline rank its own
    stages; for ``v == 1`` this is the identity. ``perm[i]`` is the
    natural index stored at slot ``i``."""
    if n_layers % (pp * virtual_stages) != 0:
        raise ValueError(
            f"{n_layers} layers not divisible into pp={pp} x "
            f"v={virtual_stages} stages")
    per_stage = n_layers // (pp * virtual_stages)
    order = []
    for d in range(pp):
        for j in range(virtual_stages):
            q = j * pp + d        # semantic stage of chunk j on device d
            order.extend(range(q * per_stage, (q + 1) * per_stage))
    return np.asarray(order, np.int64)


class PipelineTrainStep:
    """Callable ``step(params, opt_state, batch) -> (params, opt_state,
    loss)`` with the plan's layout captured.

    ``prepare_params`` / ``restore_params`` convert between the model's
    natural layer order and the plan's device-major storage order
    (identity unless the schedule is interleaved) — run ``params``
    through ``prepare_params`` ONCE before ``optimizer.init`` and
    training, and ``restore_params`` before export."""

    def __init__(self, fn_builder: Callable, plan, mesh,
                 perm: np.ndarray) -> None:
        self._fn_builder = fn_builder
        self._fn: Optional[Callable] = None
        self.plan = plan
        self.mesh = mesh
        self._perm = perm
        self._inv = np.argsort(perm)

    def _permute(self, tree, perm):
        import jax
        L = len(perm)
        if np.array_equal(perm, np.arange(L)):
            return tree
        # only layer-major leaves move; optimizer scalars (adam count)
        # and any non-layer state pass through untouched, so this also
        # converts a whole optimizer state tree
        return jax.tree_util.tree_map(
            lambda p: p[perm] if (np.ndim(p) >= 1
                                  and np.shape(p)[0] == L) else p, tree)

    def prepare_params(self, params):
        """Natural layer order -> this plan's device-major storage order
        (identity unless interleaved). Also converts optimizer state."""
        return self._permute(params, self._perm)

    def restore_params(self, params):
        """Storage order back to natural layer order (for export)."""
        return self._permute(params, self._inv)

    def __call__(self, params, opt_state, batch, *extra):
        # *extra: the guard-enabled factory's injection scalars ride
        # through to the compiled body (train/guard.py GuardedStep)
        if self._fn is None:
            self._fn = self._fn_builder(params, opt_state)
        return self._fn(params, opt_state, batch, *extra)

    def __getattr__(self, name):
        # forward to the wrapped step: the pp==1 degenerate path nests
        # an (already guard-wrapped) overlap step INSIDE this shell, and
        # its surface (flush(), observer, guard_spec — train/guard.py)
        # must stay reachable through it
        fn = self.__dict__.get("_fn")
        if fn is None:
            raise AttributeError(name)
        return getattr(fn, name)


def _layer_specs(tree, n_layers: int, axis_name: str):
    """Per-leaf shard_map specs: leaves carrying the layer dim shard
    over ``axis_name``; everything else (optimizer scalars like adam's
    ``count``) replicates."""
    import jax
    from jax.sharding import PartitionSpec as P

    def spec(leaf):
        shape = np.shape(leaf)
        return P(axis_name) if (len(shape) >= 1 and shape[0] == n_layers) \
            else P()
    return jax.tree_util.tree_map(spec, tree)


def make_pipeline_train_step(layer_fn: Callable, loss_fn: Callable,
                             optimizer, plan=None, *,
                             n_layers: int,
                             mesh=None,
                             devices: Optional[Sequence] = None,
                             schedule: str = "1f1b",
                             pp: Optional[int] = None,
                             n_micro: int = 1,
                             virtual_stages: int = 1,
                             op: ReduceOp = Average,
                             dp_sync: str = "bucketed",
                             bucket_bytes: Optional[int] = None,
                             compression=None,
                             algorithm: Optional[str] = None,
                             topology=None,
                             small_floor: Optional[int] = None,
                             donate: bool = True,
                             autotune=None,
                             guard=None) -> PipelineTrainStep:
    """Build the composed DP x PP train step for a layer-major model
    (module docstring for the contract).

    Either pass a bound :class:`~horovod_tpu.parallel.plan.ParallelPlan`
    (``plan=``, optionally with its nested comms plan) or the individual
    knobs (``schedule``/``pp``/``n_micro``/``virtual_stages`` plus the
    ``bucketed_grad_sync`` communication kwargs). ``pp == 1`` (or a
    1-device world) degenerates into
    :func:`~horovod_tpu.train.overlap.make_overlap_train_step` — same
    signature, same microbatch-accumulation semantics, bucket overlap
    engine and all. ``autotune`` (or ``HVD_TPU_AUTOTUNE_MESH=1``) hands
    (pp, n_microbatches, schedule) AND the communication knobs to the
    parallel-plan search (``train/autotune.py``); an explicit
    ``plan=`` pins the layout with zero search.

    ``dp_sync="bucketed"`` (default) routes stage gradients through
    :func:`~horovod_tpu.train.overlap.bucketed_grad_sync` on the dp
    axis; ``"dense"`` is the exact-parity dense-``pmean`` fallback.
    Quantized codecs change wire numerics (error feedback recommended at
    the optimizer level; trajectory-level parity is what the tests
    hold). The optimizer applies per pipeline rank on its own stage
    shard — elementwise transforms (sgd/adam/adamw/...) only; a
    cross-parameter transform (e.g. global-norm clipping) would see one
    rank's stages.
    """
    import jax

    from horovod_tpu.parallel.mesh import dp_pp_mesh, mesh_axis_size
    from horovod_tpu.parallel.plan import ParallelPlan

    if autotune is None:
        from horovod_tpu.common.config import get_config
        autotune = get_config().autotune_mesh or None
    if autotune and plan is None:
        from horovod_tpu.train.autotune import make_parallel_train_step
        return make_parallel_train_step(
            layer_fn, loss_fn, optimizer, n_layers=n_layers,
            devices=devices, autotune=autotune, op=op, donate=donate,
            guard=guard)

    if plan is None:
        if mesh is not None:
            world = int(np.prod(list(mesh.shape.values())))
            pp_ = pp if pp is not None else mesh_axis_size(mesh, "pp")
        else:
            world = len(list(devices)) if devices is not None \
                else jax.device_count()
            pp_ = pp if pp is not None else 1
        if world % pp_ != 0:
            raise ValueError(
                f"pp={pp_} does not divide the {world}-device world")
        comms = None
        if bucket_bytes is not None or algorithm is not None \
                or compression is not None or small_floor is not None:
            from horovod_tpu.train.autotune import Plan
            from horovod_tpu.train.autotune import _codec_name
            from horovod_tpu.train.buckets import resolve_bucket_bytes
            from horovod_tpu.train.overlap import resolve_small_floor
            comms = Plan(
                bucket_bytes=resolve_bucket_bytes(bucket_bytes),
                algorithm=algorithm or "psum",
                codec=_codec_name(compression),
                small_floor=resolve_small_floor(small_floor))
        plan = ParallelPlan(
            dp=max(1, world // pp_), pp=pp_,
            schedule=schedule if pp_ > 1 else "1f1b",
            n_microbatches=n_micro,
            virtual_stages=virtual_stages
            if (pp_ > 1 and schedule == "interleaved") else 1,
            comms=comms)
    if mesh is None:
        mesh = plan.build_mesh(devices=devices)
    plan.validate_for(int(np.prod(list(mesh.shape.values()))),
                      n_layers=n_layers)
    if mesh_axis_size(mesh, "pp") != plan.pp:
        raise ValueError(
            f"mesh pp axis is {mesh_axis_size(mesh, 'pp')} but the plan "
            f"wants pp={plan.pp}; build the mesh with dp_pp_mesh or "
            f"plan.build_mesh()")

    # the quantizer instance for the dp hop, from explicit kwarg or the
    # plan's nested comms codec
    if compression is None and plan.comms is not None:
        compression = plan.comms.resolve_codec()
    comm_kwargs = dict(
        bucket_bytes=plan.comms.bucket_bytes if plan.comms else bucket_bytes,
        compression=compression,
        algorithm=(plan.comms.algorithm if plan.comms else algorithm),
        topology=topology,
        small_floor=(plan.comms.small_floor if plan.comms else small_floor))

    _pipeline_metrics(plan)

    if plan.pp == 1:
        from jax import lax

        from horovod_tpu.train.overlap import make_overlap_train_step

        def full_loss(params, batch):
            x, tgt = batch

            def body(h, lp):
                return layer_fn(lp, h), None
            y, _ = lax.scan(body, x, params)
            return loss_fn(y, tgt)

        inner = make_overlap_train_step(
            full_loss, optimizer, mesh, "dp",
            n_micro=plan.n_microbatches, op=op, donate=donate,
            autotune=False, guard=guard, **comm_kwargs)
        # the inner step is already guard-wrapped (or plain, guard off):
        # the pipeline shell only carries the plan/permutation surface.
        # Bind it EAGERLY — the guard surface (flush()/observer) must be
        # reachable through __getattr__ before the first call too.
        step = PipelineTrainStep(lambda *_: inner, plan, mesh,
                                 np.arange(n_layers))
        step._fn = inner
        return step

    perm = stage_layout_permutation(n_layers, plan.pp, plan.virtual_stages)

    from horovod_tpu.train import guard as guard_mod
    gspec = guard_mod.resolve_spec(guard)
    from horovod_tpu import chaos as _chaos
    inject_armed = gspec.enabled and _chaos.grad_rules_armed()

    def fn_builder(params_ex, opt_state_ex):
        import optax
        from jax import lax
        from jax.sharding import PartitionSpec as P

        from horovod_tpu.parallel.pipeline import (pipeline_1f1b_spmd,
                                                   pipeline_spmd)
        from horovod_tpu.parallel.plan import compile_step_with_plan
        from horovod_tpu.train.overlap import bucketed_grad_sync

        S = plan.pp
        M = plan.n_microbatches
        v = plan.virtual_stages
        dp_live = mesh_axis_size(mesh, "dp") > 1

        def stage_scan(stage_params, x):
            def body(h, lp):
                return layer_fn(lp, h), None
            y, _ = lax.scan(body, x, stage_params)
            return y

        def dp_reduce(grads):
            if not dp_live:
                return grads
            if dp_sync == "dense":
                return jax.tree_util.tree_map(
                    lambda g: lax.pmean(g, "dp"), grads)
            return bucketed_grad_sync(grads, "dp", op=op, **comm_kwargs)

        def body(params, opt_state, batch, *inj):
            x, tgt = batch
            xm = x.reshape((M, x.shape[0] // M) + x.shape[1:])
            tm = tgt.reshape((M, tgt.shape[0] // M) + tgt.shape[1:])
            if plan.schedule == "interleaved":
                from horovod_tpu.parallel.pipeline import (
                    pipeline_interleaved_spmd)
                per_chunk = n_layers // (S * v)
                chunks = jax.tree_util.tree_map(
                    lambda p: p.reshape((v, per_chunk) + p.shape[1:]),
                    params)
                loss, grads = pipeline_interleaved_spmd(
                    stage_scan, loss_fn, chunks, xm, tm, v, "pp")
                grads = jax.tree_util.tree_map(
                    lambda g: g.reshape((v * per_chunk,) + g.shape[2:]),
                    grads)
            elif plan.schedule == "1f1b":
                loss, grads = pipeline_1f1b_spmd(
                    stage_scan,
                    loss_fn,
                    jax.tree_util.tree_map(lambda p: p[None], params),
                    xm, tm, "pp")
            else:  # gpipe-by-autodiff
                def total(pl):
                    ym = pipeline_spmd(
                        stage_scan,
                        jax.tree_util.tree_map(lambda p: p[None], pl),
                        xm, "pp")
                    return jax.vmap(loss_fn)(ym, tm).mean()
                loss, grads = jax.value_and_grad(total)(params)
            if plan.schedule != "gpipe":
                # the 1F1B-family schedules accumulate gradient SUMS
                # over microbatches; gpipe's vmap-mean carries the 1/M
                grads = jax.tree_util.tree_map(lambda g: g / M, grads)
            grads = dp_reduce(grads)
            if dp_live:
                loss = lax.pmean(loss, "dp")
            if not gspec.enabled:
                updates, opt_state = optimizer.update(grads, opt_state,
                                                      params)
                params = optax.apply_updates(params, updates)
                return params, opt_state, loss
            if inject_armed:
                grads = guard_mod.apply_injection(grads, inj[0])
            # the verdict scalar is psum'd over pp: stage grads are
            # pp-SHARDED, and every stage must reach the same
            # skip/apply decision (docs/TROUBLESHOOTING.md)
            params, opt_state, ok = guard_mod.guarded_apply(
                optimizer, grads, opt_state, params, gspec,
                pp_axis="pp")
            return params, opt_state, loss, ok

        # distinct per-plan name: the compile watcher labels compiles by
        # function name, and an autotune search compiling one `body` per
        # candidate would read as a recompile storm (and burn an anomaly
        # capture) when it is really N different programs
        body.__name__ = f"pipeline_body[{plan.key}]"
        p_specs = _layer_specs(params_ex, n_layers, "pp")
        o_specs = _layer_specs(opt_state_ex, n_layers, "pp")
        batch_spec = P("dp")
        in_specs = (p_specs, o_specs, (batch_spec, batch_spec))
        out_specs = (p_specs, o_specs, P())
        if gspec.enabled:
            in_specs = in_specs + (P(),)       # the injection scalars
            out_specs = out_specs + (P(),)     # the guard verdict
        return compile_step_with_plan(
            body, mesh,
            in_specs=in_specs,
            out_specs=out_specs,
            donate_argnums=(0, 1) if donate else ())

    step = PipelineTrainStep(fn_builder, plan, mesh, perm)
    if gspec.enabled:
        return guard_mod.GuardedStep(step, gspec, inject=inject_armed)
    return step
