"""Backprop/collective overlap engine: software-pipelined, bucketed
gradient reduction for traced (shard_map) training loops.

The reference's whole reason to exist is that gradient reduction runs
WHILE autograd is still producing later gradients (PAPER.md: the
background thread fuses and dispatches collectives mid-backward). Our
traced mesh path used to reduce the entire gradient pytree in one shot
after backward completed — every byte of collective time fully exposed.
This module restructures microbatch accumulation into a software
pipeline:

    iteration k:   issue reduce of microbatch k−1's gradients (bucketed)
                   run microbatch k's forward+backward

Inside ``lax.scan`` the bucket collectives for iteration k−1 have no
data dependency on iteration k's backward, so XLA's latency-hiding
scheduler overlaps them — the compiler-scheduled analog of the
reference's background fusion thread. Reduction is linear, so
``reduce(Σₖ gₖ) == Σₖ reduce(gₖ)`` and the pipelined result matches the
reduce-at-the-end result up to fp reassociation (bit-exact quantized
parity is NOT preserved — each microbatch quantizes separately — which
is why the parity tests compare loss trajectories under int8+EF).

Buckets come from :mod:`horovod_tpu.train.buckets` (reverse
registration order, fusion-threshold byte budget); each bucket is one
``psum``/``pmean`` — or reduce_scatter→quantize→allgather when a
quantizer is given (EQuARX shape, ``preduce_quantized``), or a chunked
``ppermute`` ring (``pring_allreduce``) for the large-bucket case.

With accumulation off (one microbatch) there is nothing to overlap
with: the exact numerics-parity fallback computes the gradients and
then syncs them, identical to the serialized path.
"""

from __future__ import annotations

from typing import Any, Callable, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from horovod_tpu._compat import axis_size
from horovod_tpu.ops.reduce_op import Average, ReduceOp, Sum
from horovod_tpu.train.buckets import (BucketPlan, pack, plan_buckets,
                                       unpack)

_tree = jax.tree_util


def _tree_add(a, b):
    return _tree.tree_map(jnp.add, a, b)


def resolve_small_floor(small_floor: Optional[int] = None) -> int:
    """Effective small-bucket latency floor in bytes: explicit argument >
    ``HVD_TPU_SMALL_BUCKET_FLOOR`` (``Config.small_bucket_floor``).
    0 disables the latency path."""
    if small_floor is not None:
        return max(0, int(small_floor))
    from horovod_tpu.common.config import get_config
    return max(0, get_config().small_bucket_floor)


def bucketed_grad_sync(grads, axis_name: str,
                       plan: Optional[BucketPlan] = None,
                       bucket_bytes: Optional[int] = None,
                       op: ReduceOp = Average,
                       compression=None,
                       ring: bool = False,
                       algorithm: Optional[str] = None,
                       topology=None,
                       small_floor: Optional[int] = None):
    """Reduce a gradient pytree along ``axis_name`` bucket by bucket.

    Call inside ``shard_map`` (a live named axis). Each bucket's leaves
    are packed into one flat vector and reduced with ONE collective,
    selected by ``algorithm``:

    * ``"psum"`` (default) — ``psum``/``pmean``; with ``compression`` (a
      :class:`~horovod_tpu.compression.quantizers.Quantizer`) the EQuARX
      ``reduce_scatter → quantize → all_gather`` path.
    * ``"ring"`` — the chunked ``ppermute`` ring
      (:func:`ops.mesh_collectives.pring_allreduce`); ``ring=True`` is
      the back-compat spelling. No compression seam (per-hop
      requantization would accumulate error).
    * ``"hier"`` — the topology-aware two-level path
      (:func:`ops.mesh_collectives.phier_allreduce`): intra-host
      reduce_scatter → inter-host allreduce → intra-host allgather,
      with ``compression`` applied to the inter-host hop only.
      ``topology`` (a :class:`~horovod_tpu.common.topology.MeshTopology`)
      defaults to :func:`~horovod_tpu.common.topology.detect_topology`
      over the axis size; a non-hierarchical topology degrades to psum.

    ``small_floor`` (bytes; default ``HVD_TPU_SMALL_BUCKET_FLOOR``):
    buckets under the floor skip quantization and ring/hierarchical
    chunking and take one dense ``psum`` — the latency-optimized
    small-tensor path (arxiv 1909.09756). Emitting one independent
    collective per bucket — instead of one per leaf or one for the
    whole tree — is what gives XLA's scheduler units it can overlap
    with compute.

    Quantized, ring and hierarchical paths support Sum/Average only.
    """
    from horovod_tpu.ops.mesh_collectives import (phier_allreduce, preduce,
                                                  preduce_quantized,
                                                  pring_allreduce)
    algo = algorithm or ("ring" if ring else "psum")
    if algo not in ("psum", "ring", "hier"):
        raise ValueError(
            f"unknown bucket algorithm {algorithm!r}; expected "
            "psum | ring | hier")
    if algo == "ring" and compression is not None:
        raise ValueError(
            "ring allreduce has no compression seam (per-hop "
            "requantization accumulates error); use algorithm='psum' or "
            "'hier' with a quantizer")
    leaves, treedef = _tree.tree_flatten(grads)
    if not leaves:
        return grads
    if plan is None:
        plan = plan_buckets(leaves, bucket_bytes)
    n = axis_size(axis_name)
    floor = resolve_small_floor(small_floor)
    if algo == "hier":
        if topology is None:
            from horovod_tpu.common.topology import detect_topology
            topology = detect_topology(n=n)
        if not topology.is_hierarchical:
            algo = "psum"  # flat topology: the two-level path IS psum
    out: list = [None] * len(leaves)
    for bucket in plan.buckets:
        small = floor > 0 and bucket.nbytes < floor
        if small or (algo == "psum" and compression is None):
            vec = pack(leaves, bucket)
            reduced = preduce(vec, axis_name, op)
        elif algo == "psum":
            if op not in (Sum, ReduceOp.AVERAGE):
                raise ValueError(
                    f"quantized bucket sync supports Sum/Average, got {op}")
            vec = pack(leaves, bucket, pad_to=n)
            reduced = preduce_quantized(vec, axis_name, compression, op)
        elif algo == "ring":
            vec = pack(leaves, bucket)
            reduced = pring_allreduce(vec, axis_name, op)
        else:  # hier
            vec = pack(leaves, bucket)
            reduced = phier_allreduce(vec, axis_name, topology, op,
                                      inter_codec=compression)
        for i, leaf in zip(bucket.indices,
                           unpack(reduced, bucket, leaves)):
            out[i] = leaf
    return _tree.tree_unflatten(treedef, out)


def pipelined_accumulate(grad_fn: Callable, params,
                         microbatches, *,
                         axis_name: str,
                         op: ReduceOp = Average,
                         plan: Optional[BucketPlan] = None,
                         bucket_bytes: Optional[int] = None,
                         compression=None,
                         ring: bool = False,
                         algorithm: Optional[str] = None,
                         topology=None,
                         small_floor: Optional[int] = None,
                         overlap: bool = True,
                         sync: bool = True,
                         microbatch_mean: bool = True
                         ) -> Tuple[jax.Array, Any]:
    """Microbatch-accumulated, cross-replica-reduced gradients with the
    bucket collectives software-pipelined one iteration behind their
    production.

    ``grad_fn(params, microbatch) -> (loss, grads)`` runs one
    microbatch's forward+backward; ``microbatches`` is a pytree whose
    leaves carry the microbatch count as their leading axis. Returns
    ``(mean_loss, reduced_grads)`` where the gradients are reduced over
    ``axis_name`` (per ``op``) and averaged over microbatches (set
    ``microbatch_mean=False`` to keep the sum).

    ``overlap=True`` (default): scan iteration k issues microbatch
    k−1's bucket reductions and runs microbatch k's backward — no data
    dependency between the two, so XLA overlaps them. ``overlap=False``
    is the serialized comparator: identical numerics, but an
    ``optimization_barrier`` pins every reduction onto the critical
    path before the next backward may start (this is the
    bucket-pipelining-off baseline the overlap bench measures against).
    ``sync=False`` skips reduction entirely — the compute-only baseline
    for exposed-communication attribution.

    With ONE microbatch the pipeline degenerates to the exact
    numerics-parity fallback: backward, then the same bucketed sync —
    there is no second backward to hide the collectives behind.
    """
    sizes = {x.shape[0] for x in _tree.tree_leaves(microbatches)}
    if len(sizes) != 1:
        raise ValueError(
            f"microbatch leaves disagree on the leading axis: {sizes}")
    n_micro = sizes.pop()
    if n_micro < 1:
        raise ValueError("need at least one microbatch")

    def _sync(grads):
        if not sync:
            return grads
        return bucketed_grad_sync(grads, axis_name, plan=plan,
                                  bucket_bytes=bucket_bytes, op=op,
                                  compression=compression, ring=ring,
                                  algorithm=algorithm, topology=topology,
                                  small_floor=small_floor)

    def _take(k):
        return _tree.tree_map(lambda x: x[k], microbatches)

    scale = (1.0 / n_micro) if microbatch_mean else 1.0

    if n_micro == 1:
        loss, grads = grad_fn(params, _take(0))
        return loss, _sync(grads)

    loss0, g0 = grad_fn(params, _take(0))
    rest = _tree.tree_map(lambda x: x[1:], microbatches)
    zeros = _tree.tree_map(jnp.zeros_like, g0)

    if overlap:
        def body(carry, mb):
            pending, acc = carry
            # no data dependency between these two lines: the bucket
            # collectives of the PREVIOUS microbatch overlap this one's
            # forward+backward on the XLA schedule
            reduced = _sync(pending)
            loss, g = grad_fn(params, mb)
            return (g, _tree_add(acc, reduced)), loss

        (last, acc), losses = lax.scan(body, (g0, zeros), rest)
        total = _tree_add(acc, _sync(last))
    else:
        acc0 = _sync(g0)

        def body(carry, mb):
            acc = carry
            # serialize: the next backward's params are gated behind the
            # finished reduction, putting every collective on the
            # critical path (numerics unchanged — this is a pure
            # scheduling barrier)
            p_gated, acc = lax.optimization_barrier((params, acc))
            loss, g = grad_fn(p_gated, mb)
            return _tree_add(acc, _sync(g)), loss

        total, losses = lax.scan(body, acc0, rest)

    mean_loss = (loss0 + jnp.sum(losses)) / n_micro
    if scale != 1.0:
        total = _tree.tree_map(lambda x: x * scale, total)
    return mean_loss, total


def make_overlap_train_step(loss_fn: Callable, optimizer, mesh,
                            axis_name: str = "dp", *,
                            n_micro: int = 1,
                            op: ReduceOp = Average,
                            bucket_bytes: Optional[int] = None,
                            compression=None,
                            ring: bool = False,
                            algorithm: Optional[str] = None,
                            topology=None,
                            small_floor: Optional[int] = None,
                            overlap: bool = True,
                            sync: bool = True,
                            donate: bool = True,
                            autotune=None,
                            guard=None) -> Callable:
    """jit-compiled data-parallel train step with pipelined bucket
    overlap: ``shard_map`` over ``mesh[axis_name]``, ``n_micro``
    microbatches split from the batch's leading axis, gradients reduced
    via :func:`pipelined_accumulate`, then ``optimizer`` applied.

    ``loss_fn(params, batch) -> scalar loss``. The returned callable is
    ``step(params, opt_state, batch) -> (params, opt_state, loss)``
    with the batch's leading axis sharded over ``axis_name`` and
    divisible by ``n_micro`` per shard. Keyword knobs mirror
    :func:`pipelined_accumulate`.

    ``autotune`` hands the communication knobs (``bucket_bytes``,
    ``algorithm``, ``compression`` codec, ``small_floor``) to the online
    plan search (``train/autotune.py``): pass ``True`` for the
    default search, or a :class:`horovod_tpu.train.autotune.AutotuneOptions`.
    The returned step then measures candidate plans during early steps,
    locks the winner, and persists it to the plan cache; explicit values
    for the tuned knobs become the search's baseline candidate.

    ``guard`` controls the numeric guardrail
    (:mod:`horovod_tpu.train.guard`): ``None`` reads ``HVD_TPU_GUARD``
    (default ON — a non-finite or over-``HVD_TPU_GUARD_MAX_NORM``
    gradient skips the step with the optimizer state preserved, counted
    on ``hvd_guard_skipped_steps_total``), ``False`` disables (the
    exact pre-guard step, three outputs, no wrapper), ``True`` or a
    :class:`~horovod_tpu.train.guard.GuardSpec` pins it.  With the
    guard on, the returned callable is a
    :class:`~horovod_tpu.train.guard.GuardedStep` — same call surface,
    attributes forwarded — and the chaos ``grad`` seam (when armed) is
    compiled into the step.
    """
    import optax
    from jax.sharding import PartitionSpec as P

    from horovod_tpu._compat import shard_map

    if autotune is None:
        # HVD_TPU_AUTOTUNE_MESH turns the search on fleet-wide without
        # touching call sites; an explicit autotune=False still wins
        from horovod_tpu.common.config import get_config
        autotune = get_config().autotune_mesh or None
    if autotune:
        from horovod_tpu.train.autotune import make_autotuned_train_step
        return make_autotuned_train_step(
            loss_fn, optimizer, mesh, axis_name, autotune=autotune,
            n_micro=n_micro, op=op, bucket_bytes=bucket_bytes,
            compression=compression, ring=ring, algorithm=algorithm,
            topology=topology, small_floor=small_floor, overlap=overlap,
            sync=sync, donate=donate, guard=guard)

    from horovod_tpu.train import guard as guard_mod
    gspec = guard_mod.resolve_spec(guard)
    grad_fn = jax.value_and_grad(loss_fn)

    def _loss_and_grads(params, batch):
        def micro_grad(p, mb):
            return grad_fn(p, mb)

        micro = _tree.tree_map(
            lambda x: x.reshape((n_micro, x.shape[0] // n_micro)
                                + x.shape[1:]), batch)
        return pipelined_accumulate(
            micro_grad, params, micro, axis_name=axis_name, op=op,
            bucket_bytes=bucket_bytes, compression=compression, ring=ring,
            algorithm=algorithm, topology=topology, small_floor=small_floor,
            overlap=overlap, sync=sync)

    if not gspec.enabled:
        def shard_body(params, opt_state, batch):
            loss, grads = _loss_and_grads(params, batch)
            updates, opt_state = optimizer.update(grads, opt_state, params)
            params = optax.apply_updates(params, updates)
            return params, opt_state, lax.pmean(loss, axis_name)

        wrapped = shard_map(
            shard_body, mesh=mesh,
            in_specs=(P(), P(), P(axis_name)),
            out_specs=(P(), P(), P()),
            check_vma=False)
        return jax.jit(wrapped, donate_argnums=(0, 1) if donate else ())

    # guard on: the body grows the chaos injection seam (data-driven —
    # compiled in only when a grad fault plan is armed for this rank)
    # and a 4th output, the guard verdict, which the GuardedStep wrapper
    # strips and observes one step late
    from horovod_tpu import chaos
    inject = chaos.grad_rules_armed()

    def shard_body(params, opt_state, batch, inj):
        loss, grads = _loss_and_grads(params, batch)
        if inject:
            grads = guard_mod.apply_injection(grads, inj)
        params, opt_state, ok = guard_mod.guarded_apply(
            optimizer, grads, opt_state, params, gspec)
        return params, opt_state, lax.pmean(loss, axis_name), ok

    wrapped = shard_map(
        shard_body, mesh=mesh,
        in_specs=(P(), P(), P(axis_name), P()),
        out_specs=(P(), P(), P(), P()),
        check_vma=False)
    fn = jax.jit(wrapped, donate_argnums=(0, 1) if donate else ())
    return guard_mod.GuardedStep(fn, gspec, inject=inject)
