"""Distributed training API: optimizer wrapper, gradient transform, parameter
broadcast.

TPU-native re-think of the reference's high-level API:

* reference ``_DistributedOptimizer`` hooks torch grad accumulators
  (``horovod/torch/optimizer.py:128-171``) and allreduces each grad
  asynchronously; here the same contract is an **optax gradient
  transformation** — the JAX-idiomatic seam for "do something to gradients
  before the update".
* reference ``DistributedGradientTape`` (``horovod/tensorflow/__init__.py:777``)
  wraps ``tape.gradient``; here :func:`distributed_grad` wraps
  ``jax.value_and_grad``.
* reference ``broadcast_parameters`` / ``broadcast_optimizer_state`` /
  ``broadcast_object`` (``horovod/torch/functions.py:29-266``) map to pytree
  broadcasts.

Execution regimes of the gradient sync (``DistributedGradTransform``):

* **global-SPMD jit** (one program over a global mesh, batch sharded):
  XLA inserts the reduction from shardings — the transform is an identity
  (modulo pre/post-scale). This is the default traced behavior.
* **shard_map** with a live ``axis_name``: explicit in-graph ``psum/pmean``.
* **eager multi-process**: grouped host allreduce through the backend
  (the C++ core fuses the whole set into large buffers, as the reference's
  fusion buffer does — ``fusion_buffer_manager.h:30-56``).
* **per-process jit + host sync** (``host_sync_in_jit=True``): an ordered
  ``io_callback`` hands gradients to the negotiating host core from inside
  the compiled step — for programs jitted per process over LOCAL arrays
  only. Requires the TCP core backend (device-data-plane backends would
  re-enter the device from the callback).
"""

from __future__ import annotations

import pickle
from typing import Any, Callable, NamedTuple, Optional, Sequence

import numpy as np

import jax
import jax.numpy as jnp
import optax

from horovod_tpu.common.basics import _require_init, rank, size
from horovod_tpu.common.process_sets import ProcessSet, global_process_set
from horovod_tpu.common.util import is_traced as _is_traced
from horovod_tpu.compression import (Compression, Compressor, EFState,
                                     ErrorFeedback, Quantizer, ef_apply,
                                     init_residual)
from horovod_tpu.ops import collectives as C
from horovod_tpu.ops.reduce_op import Average, ReduceOp, Sum


def _record_sync_timing(exposed_s: float, total_s: float,
                        n_buckets: int) -> None:
    """Overlap efficiency on /metrics (docs/OBSERVABILITY.md): how much
    of the eager gradient sync was spent BLOCKED on the wire (exposed)
    vs overlapped with local codec/enqueue work."""
    from horovod_tpu.metrics.registry import default_registry
    reg = default_registry()
    reg.gauge("hvd_overlap_exposed_comm_seconds",
              help="seconds blocked on collective completion in the last "
              "gradient sync").set(exposed_s)
    reg.gauge("hvd_overlap_sync_seconds",
              help="wall seconds of the last eager gradient sync"
              ).set(total_s)
    reg.counter("hvd_overlap_exposed_comm_seconds_total",
                help="cumulative exposed-communication seconds"
                ).inc(exposed_s)
    reg.gauge("hvd_overlap_bucket_count",
              help="gradient buckets in the active overlap plan"
              ).set(n_buckets)


def _eager_allreduce_tree(grads, op: ReduceOp, process_set: ProcessSet,
                          compression: Compressor,
                          prescale: float, postscale: float,
                          bucket_bytes=None):
    """Bucketed (fused) eager allreduce of a gradient pytree.

    The tree is partitioned into byte-budgeted buckets in reverse
    registration order (``train/buckets.py``, the engine's
    fusion-threshold budget) and each bucket is issued as ONE async
    group: bucket ``b``'s payload is on the wire while bucket ``b+1``
    is still being compressed/enqueued — the eager-path analog of the
    reference's background thread reducing early gradients mid-backward.
    ``HVD_TPU_OVERLAP_BUCKETS=0`` restores the single grouped call.

    Cast compressors ride the plain grouped allreduce in their wire
    dtype (sum in fp16/bf16 is well-defined); quantizers take the
    quantized allgather path (``C.quantized_grouped_allreduce``) — their
    per-block-scaled payloads are not sum-reducible, and the C++ wire
    moves ~4x fewer bytes for the int8 codec. Exposed-communication
    seconds (time blocked in ``wait`` after all local work) land on the
    overlap metrics either way."""
    import time as _time

    from horovod_tpu.common.config import get_config
    from horovod_tpu.train.buckets import Bucket, BucketPlan, plan_buckets

    leaves, treedef = jax.tree_util.tree_flatten(grads)
    if not leaves:
        return grads
    if get_config().overlap_buckets and len(leaves) > 1:
        plan = plan_buckets(leaves, bucket_bytes)
    else:
        from horovod_tpu.train.buckets import _leaf_nbytes
        nbytes = sum(_leaf_nbytes(l) for l in leaves)
        plan = BucketPlan((Bucket(tuple(range(len(leaves))), nbytes),),
                          nbytes)

    quantized = isinstance(compression, Quantizer)
    t0 = _time.perf_counter()
    pending = []  # (bucket, handle, ctxs or None)
    for bi, bucket in enumerate(plan.buckets):
        vals = [leaves[i] for i in bucket.indices]
        if quantized:
            if prescale != 1.0:
                vals = [v * prescale for v in vals]
            h = C.quantized_grouped_allreduce_async(
                vals, compression, op=op, name=f"grad.b{bi}",
                process_set=process_set)
            pending.append((bucket, h, None))
        else:
            compressed, ctxs = [], []
            for leaf in vals:
                c, ctx = compression.compress(leaf)
                compressed.append(c)
                ctxs.append(ctx)
            h = C.grouped_allreduce_async(
                compressed, op=op, name=f"grad.b{bi}",
                prescale_factor=prescale, postscale_factor=postscale,
                process_set=process_set)
            pending.append((bucket, h, ctxs))

    out: list = [None] * len(leaves)
    exposed = 0.0
    for bucket, h, ctxs in pending:
        tw = _time.perf_counter()
        reduced = h.wait()
        exposed += _time.perf_counter() - tw
        if ctxs is None:
            if postscale != 1.0:
                reduced = [r * postscale for r in reduced]
        else:
            reduced = [compression.decompress(r, ctx)
                       for r, ctx in zip(reduced, ctxs)]
        for i, r in zip(bucket.indices, reduced):
            out[i] = r
    _record_sync_timing(exposed, _time.perf_counter() - t0,
                        plan.num_buckets)
    return jax.tree_util.tree_unflatten(treedef, out)


_warned_traced_identity = False


def _warn_traced_identity_once() -> None:
    """The traced no-axis path is an identity, which is only correct under
    single-program global-SPMD jit. A reference user who jits a PER-PROCESS
    train step with size() > 1 would get silently divergent replicas — too
    dangerous to leave undetected on a drop-in surface (ADVICE r1)."""
    global _warned_traced_identity
    if _warned_traced_identity:
        return
    _warned_traced_identity = True
    import warnings
    warnings.warn(
        "horovod_tpu: gradient sync was traced with size() > 1 but no "
        "axis_name and host_sync_in_jit=False. This is an IDENTITY: it is "
        "correct only when the step is jitted once over a GLOBAL mesh "
        "(global-SPMD, XLA reduces from shardings). If you are jitting a "
        "per-process step over local arrays (the reference pattern), your "
        "replicas will silently diverge — pass axis_name= under shard_map, "
        "or host_sync_in_jit=True with the TCP core backend. See the "
        "'Execution regimes' section of horovod_tpu.train.optimizer.",
        UserWarning, stacklevel=4)


def _traced_allreduce_tree(grads, op: ReduceOp, axis_name: Optional[str],
                           prescale: float, postscale: float):
    """Inside jit/shard_map: emit in-graph collectives.

    With no live named axis (plain global-SPMD jit), gradients are already
    globally reduced by XLA from the shardings, so this is an identity modulo
    pre/post-scale. With a named axis (shard_map per-device training loops),
    emit the explicit in-graph collective — the XLA analog of the NCCL launch
    in ``nccl_operations.cc:156-214``.
    """
    from horovod_tpu.ops.mesh_collectives import preduce

    if axis_name is None and size() > 1:
        _warn_traced_identity_once()

    def one(g):
        if prescale != 1.0:
            g = g * prescale
        if axis_name is not None:
            g = preduce(g, axis_name, op)
        if postscale != 1.0:
            g = g * postscale
        return g
    return jax.tree_util.tree_map(one, grads)


class DistributedState(NamedTuple):
    inner_state: Any


def _host_callback_allreduce_tree(grads, op: ReduceOp,
                                  process_set: ProcessSet,
                                  compression: Compressor,
                                  prescale: float, postscale: float):
    """Cross-process sync from INSIDE jit (SURVEY.md §7 hard part (d)):
    an ordered ``io_callback`` hands the gradient tree to the host backend
    mid-program. jit traces once, so every process emits the identical
    callback sequence — exactly the same-order contract the eager path
    already relies on — and the C++ core negotiates/fuses as usual.

    Only valid for PER-PROCESS jit over local arrays with the host (TCP
    core) backend: under global-SPMD, GSPMD pins callbacks to device 0's
    process (the others would never call in → deadlock), and device-data-
    plane backends (XLA_EAGER) would re-enter the devices that are blocked
    on this very callback.
    """
    from jax.experimental import io_callback

    be = _require_init().backend
    from horovod_tpu.core.core_backend import CoreBackend
    if not isinstance(be, CoreBackend):
        raise RuntimeError(
            "host_sync_in_jit requires the TCP core backend; the "
            f"{type(be).__name__} data plane cannot be driven from inside "
            "a compiled program (unset HOROVOD_TPU_OPERATIONS, or use "
            "global-SPMD sharding / an explicit axis_name instead)")

    leaves, treedef = jax.tree_util.tree_flatten(grads)
    shapes = [jax.ShapeDtypeStruct(l.shape, l.dtype) for l in leaves]

    def host(*flat):
        tree = jax.tree_util.tree_unflatten(treedef, list(flat))
        out = _eager_allreduce_tree(tree, op, process_set, compression,
                                    prescale, postscale)
        return tuple(np.asarray(x) for x in
                     jax.tree_util.tree_leaves(out))

    out_flat = io_callback(host, tuple(shapes), *leaves, ordered=True)
    return jax.tree_util.tree_unflatten(treedef, list(out_flat))


def DistributedGradTransform(op: ReduceOp = Average,
                             process_set: ProcessSet = global_process_set,
                             compression: Compressor = Compression.none,
                             axis_name: Optional[str] = None,
                             prescale_factor: float = 1.0,
                             postscale_factor: float = 1.0,
                             host_sync_in_jit: bool = False,
                             bucket_bytes: Optional[int] = None
                             ) -> optax.GradientTransformation:
    """optax transform that synchronizes gradients across the process set.

    The moral equivalent of the reference's per-parameter allreduce hooks
    (``torch/optimizer.py:164-206``), but batched over the whole tree so the
    core can fuse one buffer per cycle instead of negotiating per-tensor.

    Regimes (see module docstring): eager multi-process → grouped host
    allreduce; ``axis_name`` under shard_map → in-graph collective;
    traced with no axis → identity by default (global-SPMD jit: XLA
    reduces from shardings), or — with ``host_sync_in_jit=True`` and a
    per-process jit over local arrays — an ordered ``io_callback`` into
    the negotiating core.

    ``compression`` accepts the cast compressors (fp16/bf16 wire
    dtype), a quantizer (``Compression.int8``/``fp8``/``onebit`` — the
    eager wire then moves quantized payloads), or
    ``ErrorFeedback(codec)``: the transform state grows a per-leaf fp32
    residual and every step compresses ``grad + residual``, carrying
    the quantization error to the next step (so lossy codecs
    converge). With EF the in-graph
    quantize∘dequantize runs in EVERY regime, including global-SPMD jit
    where the sync itself is an identity; a bare (non-EF) quantizer
    compresses the eager wire only — traced regimes leave gradients to
    XLA's sharding-derived reduction untouched.
    """
    ef = isinstance(compression, ErrorFeedback)
    codec = compression.inner if ef else compression

    def init_fn(params):
        if ef:
            return EFState(residual=init_residual(params))
        del params
        return optax.EmptyState()

    def update_fn(updates, state, params=None):
        del params
        if ef:
            # compress(grad + residual), carry the error; the synced
            # values are the (losslessly re-quantizable) compressed ones
            updates, new_residual = ef_apply(codec, updates, state.residual)
        if _is_traced(updates):
            if host_sync_in_jit and axis_name is None and size() > 1:
                new = _host_callback_allreduce_tree(
                    updates, op, process_set, codec,
                    prescale_factor, postscale_factor)
            else:
                new = _traced_allreduce_tree(updates, op, axis_name,
                                             prescale_factor,
                                             postscale_factor)
        elif size() == 1:
            new = _traced_allreduce_tree(updates, op, None,
                                         prescale_factor, postscale_factor)
        else:
            new = _eager_allreduce_tree(updates, op, process_set, codec,
                                        prescale_factor, postscale_factor,
                                        bucket_bytes)
        return new, (EFState(residual=new_residual) if ef else state)

    return optax.GradientTransformation(init_fn, update_fn)


def DistributedOptimizer(optimizer: optax.GradientTransformation,
                         op: ReduceOp = Average,
                         process_set: ProcessSet = global_process_set,
                         compression: Compressor = Compression.none,
                         backward_passes_per_step: int = 1,
                         axis_name: Optional[str] = None,
                         prescale_factor: float = 1.0,
                         postscale_factor: float = 1.0,
                         host_sync_in_jit: bool = False,
                         autotune=None
                         ) -> optax.GradientTransformation:
    """Wrap an optax optimizer with distributed gradient synchronization.

    Reference: ``hvd.DistributedOptimizer`` factory
    (``horovod/torch/optimizer.py:506``, ``horovod/tensorflow/__init__.py:627``).
    ``backward_passes_per_step > 1`` reproduces the reference's delayed
    allreduce (local accumulation, sync every k steps —
    ``torch/optimizer.py:249-292``) via ``optax.MultiSteps``.
    ``compression`` accepts casts, quantizers, or ``ErrorFeedback(...)``
    (see :func:`DistributedGradTransform`); the Adasum path has no
    compression seam — combining them raises.

    ``autotune=True`` warm-starts the communication knobs from the
    persistent plan cache (``train/autotune.py``): at ``init`` the
    gradient tree's fingerprint is looked up in
    ``HVD_TPU_AUTOTUNE_CACHE_DIR`` and a hit applies the tuned
    ``bucket_bytes`` (and — when you passed no ``compression`` of your
    own — the tuned codec, wrapped in error feedback so the lossy wire
    converges). A miss keeps your settings unchanged: the ONLINE search
    that fills the cache lives in
    ``make_overlap_train_step(..., autotune=True)``, because restarting
    the search per candidate means recompiling the step — something an
    optax transform cannot do from inside your jit.
    """
    from horovod_tpu.train.fused_apply import (FusedOptSpec,
                                               make_fused_transform)
    env_autotune = False
    if autotune is None:
        from horovod_tpu.common.config import get_config
        autotune = get_config().autotune_mesh
        env_autotune = bool(autotune)
    if autotune:
        if op == ReduceOp.ADASUM or isinstance(optimizer, FusedOptSpec):
            if not env_autotune:
                raise ValueError(
                    "autotune= applies to the standard sync path only "
                    "(Adasum has no codec/bucket seam; the fused apply "
                    "pins its own codec)")
            autotune = False  # fleet-wide env default: skip, don't raise
    if autotune:
        return _warm_start_optimizer(
            optimizer, op=op, process_set=process_set,
            compression=compression,
            backward_passes_per_step=backward_passes_per_step,
            axis_name=axis_name, prescale_factor=prescale_factor,
            postscale_factor=postscale_factor,
            host_sync_in_jit=host_sync_in_jit)
    if isinstance(optimizer, FusedOptSpec):
        # fused dequantize+apply path (train/fused_apply.py): sync and
        # optimizer lower into ONE transform so the int8 codes feed the
        # Pallas kernel directly — no separate dequantize sweep.
        if op == ReduceOp.ADASUM:
            raise ValueError("fused_sgd/fused_adam have no Adasum path")
        if prescale_factor != 1.0 or postscale_factor != 1.0:
            raise ValueError(
                "fused apply does not take pre/postscale factors; fold "
                "them into the learning rate")
        if host_sync_in_jit:
            raise ValueError(
                "fused apply and host_sync_in_jit are mutually "
                "exclusive (the fused path keeps codes on device)")
        fused = make_fused_transform(optimizer, op=op,
                                     process_set=process_set,
                                     compression=compression,
                                     axis_name=axis_name)
        if backward_passes_per_step > 1:
            return optax.MultiSteps(
                fused, every_k_schedule=backward_passes_per_step)
        return fused
    if op == ReduceOp.ADASUM:
        if compression is not Compression.none:
            raise ValueError(
                "op=Adasum has no compression seam (the scaled-add tree "
                "needs exact contributions); drop compression= or use a "
                "different op")
        from horovod_tpu.ops.adasum import AdasumGradTransform
        sync = AdasumGradTransform(process_set=process_set,
                                   axis_name=axis_name)
    else:
        sync = DistributedGradTransform(op, process_set, compression,
                                        axis_name, prescale_factor,
                                        postscale_factor, host_sync_in_jit)
    chained = optax.chain(sync, optimizer)
    if backward_passes_per_step > 1:
        return optax.MultiSteps(chained,
                                every_k_schedule=backward_passes_per_step)
    return chained


def _warm_start_optimizer(optimizer, *, op, process_set, compression,
                          backward_passes_per_step, axis_name,
                          prescale_factor, postscale_factor,
                          host_sync_in_jit) -> optax.GradientTransformation:
    """``DistributedOptimizer(autotune=True)``: resolve the tuned plan
    lazily at ``init`` — the first moment the gradient-tree structure
    (== params structure) is in hand to fingerprint — then build the
    real sync chain with the cached ``bucket_bytes``/codec applied.
    A cache miss (or no cache dir) degrades to the caller's settings
    unchanged; resolution NEVER raises."""
    cell: dict = {}

    def _build(params):
        from horovod_tpu.common.topology import detect_topology
        from horovod_tpu.train.autotune import (PlanCache,
                                                plan_fingerprint,
                                                resolve_cache_dir,
                                                topology_key)
        comp, bucket = compression, None
        try:
            cache_dir = resolve_cache_dir(None)
            if cache_dir:
                # canonical topology key (NOT a mesh-axis-name dict):
                # hits entries the mesh search wrote for the same model
                # at this world size regardless of what the axis was
                # called over there. Prefer the launcher's own
                # hosts×local split (the eager world has no mesh to
                # inspect); virtual-hosts/flat fallback otherwise.
                from horovod_tpu.common.basics import local_size
                from horovod_tpu.common.topology import MeshTopology
                w, ls = size(), local_size()
                if ls > 0 and w % ls == 0 and w // ls > 1:
                    topo = MeshTopology(w // ls, ls)
                else:
                    topo = detect_topology(n=w)
                fp = plan_fingerprint(params, topology_key(topo), w)
                plan = PlanCache(cache_dir).load(fp)
                if plan is not None:
                    bucket = plan.bucket_bytes
                    codec = plan.resolve_codec()
                    if codec is not None and \
                            compression is Compression.none:
                        # lossy codec on the wire needs the residual
                        # carry to converge
                        comp = ErrorFeedback(codec)
                    from horovod_tpu.diagnostics.flight_recorder import \
                        record_event
                    record_event("autotune_warm_start", plan=plan.key)
                    from horovod_tpu.metrics.registry import \
                        default_registry
                    default_registry().counter(
                        "hvd_autotune_cache_hits_total",
                        help="runs that started from a cached tuned "
                             "plan with zero search trials").inc()
        except Exception:  # warm start is best-effort, never fatal
            comp, bucket = compression, None
        sync = DistributedGradTransform(op, process_set, comp, axis_name,
                                        prescale_factor, postscale_factor,
                                        host_sync_in_jit, bucket)
        inner = optax.chain(sync, optimizer)
        if backward_passes_per_step > 1:
            inner = optax.MultiSteps(
                inner, every_k_schedule=backward_passes_per_step)
        return inner

    def init_fn(params):
        cell["inner"] = _build(params)
        return cell["inner"].init(params)

    def update_fn(updates, state, params=None):
        if "inner" not in cell:  # init skipped (restored state)
            cell["inner"] = _build(updates)
        return cell["inner"].update(updates, state, params)

    return optax.GradientTransformation(init_fn, update_fn)


def distributed_grad(fun: Callable, argnums=0, has_aux: bool = False,
                     op: ReduceOp = Average,
                     process_set: ProcessSet = global_process_set,
                     compression: Compressor = Compression.none,
                     axis_name: Optional[str] = None,
                     host_sync_in_jit: bool = False) -> Callable:
    """``jax.grad`` with cross-worker gradient reduction — the JAX analog of
    ``DistributedGradientTape`` (``horovod/tensorflow/__init__.py:777-851``).
    Same regime routing as :func:`DistributedGradTransform`; error
    feedback needs cross-step state, which a stateless grad wrapper
    cannot hold — use ``DistributedOptimizer(compression=ErrorFeedback(
    ...))`` for that."""
    if isinstance(compression, ErrorFeedback):
        raise ValueError(
            "distributed_grad is stateless and cannot carry ErrorFeedback "
            "residuals; wrap your optimizer with DistributedOptimizer("
            "compression=ErrorFeedback(...)) instead")
    vg = jax.value_and_grad(fun, argnums=argnums, has_aux=has_aux)

    def wrapped(*args, **kwargs):
        value, grads = vg(*args, **kwargs)
        if _is_traced(grads):
            if host_sync_in_jit and axis_name is None and size() > 1:
                grads = _host_callback_allreduce_tree(
                    grads, op, process_set, compression, 1.0, 1.0)
            else:
                grads = _traced_allreduce_tree(grads, op, axis_name, 1.0,
                                               1.0)
        elif size() > 1:
            grads = _eager_allreduce_tree(grads, op, process_set, compression,
                                          1.0, 1.0)
        return value, grads

    return wrapped


# ---------------------------------------------------------------------------
# Parameter / state broadcast (reference: horovod/torch/functions.py:29-266)
# ---------------------------------------------------------------------------

def broadcast_parameters(params, root_rank: int = 0,
                         process_set: ProcessSet = global_process_set):
    """Broadcast a parameter pytree from ``root_rank`` to all workers
    (reference: ``broadcast_parameters``, ``torch/functions.py:29-68``)."""
    if size() == 1:
        return params
    leaves, treedef = jax.tree_util.tree_flatten(params)
    # Enqueue all broadcasts before waiting so the core can fuse them into
    # few large buffers (mirrors the reference enqueuing every parameter in
    # one pass, ``torch/functions.py:58-66``).
    handles = [C.broadcast_async(leaf, root_rank, name=f"bcast.param.{i}",
                                 process_set=process_set)
               for i, leaf in enumerate(leaves)]
    return jax.tree_util.tree_unflatten(treedef,
                                        [h.wait() for h in handles])


def broadcast_optimizer_state(opt_state, root_rank: int = 0,
                              process_set: ProcessSet = global_process_set):
    """Reference: ``broadcast_optimizer_state`` (``torch/functions.py:116-266``).
    optax states are pytrees, so this is the same tree broadcast; non-array
    leaves (step counters etc.) travel via :func:`broadcast_object`."""
    if size() == 1:
        return opt_state
    leaves, treedef = jax.tree_util.tree_flatten(opt_state)
    # Async-enqueue all array broadcasts first (see broadcast_parameters);
    # non-array leaves go through the pickle path synchronously.
    handles = {}
    for i, leaf in enumerate(leaves):
        if isinstance(leaf, (jax.Array, np.ndarray)):
            handles[i] = C.broadcast_async(leaf, root_rank,
                                           name=f"bcast.opt.{i}",
                                           process_set=process_set)
    out = []
    for i, leaf in enumerate(leaves):
        if i in handles:
            out.append(handles[i].wait())
        else:
            out.append(broadcast_object(leaf, root_rank,
                                        name=f"bcast.opt.obj.{i}",
                                        process_set=process_set))
    return jax.tree_util.tree_unflatten(treedef, out)


def broadcast_object(obj, root_rank: int = 0, name: Optional[str] = None,
                     process_set: ProcessSet = global_process_set):
    """Pickle-based arbitrary-object broadcast (reference:
    ``broadcast_object``, ``torch/functions.py:193-241``: serialize, bcast
    length, bcast bytes)."""
    if size() == 1:
        return obj
    name = name or "broadcast_object"
    if rank() == root_rank:
        payload = np.frombuffer(pickle.dumps(obj), dtype=np.uint8).copy()
        length = np.array([payload.size], dtype=np.int64)
    else:
        payload = None
        length = np.zeros(1, dtype=np.int64)
    length = np.asarray(C.broadcast(length, root_rank, name=f"{name}.len",
                                    process_set=process_set))
    if rank() != root_rank:
        payload = np.zeros(int(length[0]), dtype=np.uint8)
    payload = np.asarray(C.broadcast(payload, root_rank, name=f"{name}.data",
                                     process_set=process_set))
    return pickle.loads(payload.tobytes())


def allgather_object(obj, name: Optional[str] = None,
                     process_set: ProcessSet = global_process_set):
    """Pickle-based arbitrary-object allgather: returns the list of every
    rank's object, ordered by rank (reference: ``allgather_object``,
    ``torch/functions.py:233-266``: serialize, allgather sizes, allgather
    ragged bytes, split)."""
    if size() == 1:
        return [obj]
    name = name or "allgather_object"
    payload = np.frombuffer(pickle.dumps(obj), dtype=np.uint8).copy()
    # enqueue both collectives before waiting (independent: the backend
    # handles ragged dim 0 itself) so the core can fuse them in one
    # negotiation cycle, as broadcast_parameters does
    sizes_h = C.allgather_async(np.array([payload.size], dtype=np.int64),
                                name=f"{name}.len", process_set=process_set)
    data_h = C.allgather_async(payload, name=f"{name}.data",
                               process_set=process_set)
    sizes = np.asarray(sizes_h.wait())
    gathered = np.asarray(data_h.wait())
    out, offset = [], 0
    for n in sizes.tolist():
        out.append(pickle.loads(gathered[offset:offset + n].tobytes()))
        offset += n
    return out
