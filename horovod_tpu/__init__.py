"""horovod_tpu — a TPU-native distributed training framework.

Horovod-class capabilities (reference: uber/horovod v0.22.1) re-designed for
TPU: the data plane is XLA collectives over ICI/DCN meshes instead of
NCCL/MPI rings; the host control plane is a C++ negotiation core over TCP;
parallelism (dp/tp/pp/sp/ep) is first-class via ``jax.sharding``.

Drop-in-familiar surface::

    import horovod_tpu as hvd
    hvd.init()
    ...
    grads = hvd.allreduce(grads, op=hvd.Average)

TPU-idiomatic surface::

    mesh = hvd.build_mesh(dp=-1, tp=4)
    tx = hvd.DistributedOptimizer(optax.adamw(1e-3))   # optax transform
"""

import time as _time
_T_IMPORT = _time.perf_counter()    # scopes.HOST_IMPORT, this file's last lines

from horovod_tpu.version import __version__  # noqa: F401

# Lifecycle / identity (reference: horovod/common/basics.py)
from horovod_tpu.common.basics import (  # noqa: F401
    init,
    shutdown,
    is_initialized,
    rank,
    size,
    local_rank,
    local_size,
    cross_rank,
    cross_size,
    is_homogeneous,
    num_devices,
    global_device_count,
    start_timeline,
    stop_timeline,
    counters,
    engine_state,
    metrics_snapshot,
    stragglers,
    xla_built,
    tcp_core_built,
    gloo_built,
    mpi_built,
    nccl_built,
    ccl_built,
    cuda_built,
    rocm_built,
    ddl_built,
    sycl_built,
    mpi_enabled,
    gloo_enabled,
    mpi_threads_supported,
)

# Process sets (reference: horovod/common/process_sets.py)
from horovod_tpu.common.process_sets import (  # noqa: F401
    ProcessSet,
    add_process_set,
    remove_process_set,
    global_process_set,
    process_set_ids,
    get_process_set_by_id,
)

# Reduce ops (reference: horovod.torch.mpi_ops constants)
from horovod_tpu.ops.reduce_op import (  # noqa: F401
    Adasum,
    Average,
    Max,
    Min,
    Product,
    ReduceOp,
    Sum,
)

# Eager collectives (reference: horovod/torch/mpi_ops.py surface)
from horovod_tpu.ops.collectives import (  # noqa: F401
    allreduce,
    allreduce_async,
    grouped_allreduce,
    grouped_allreduce_async,
    allgather,
    allgather_async,
    broadcast,
    broadcast_async,
    alltoall,
    alltoall_async,
    reducescatter,
    reducescatter_async,
    poll,
    synchronize,
    join,
    barrier,
)

# Mesh / parallelism (TPU-native; no reference analog)
from horovod_tpu.parallel import (  # noqa: F401
    AXIS_ORDER,
    MeshSpec,
    build_mesh,
    dp_pp_mesh,
    single_axis_mesh,
    batch_sharding,
    logical_sharding,
)
# Unified parallelism plan (cost model: parallel/pipeline.py): the
# frozen dp x pp / schedule / microbatch / comms decision object, the
# single compile seam behind the step factories, and the composed
# DP x PP pipelined train step.
from horovod_tpu.parallel.plan import (  # noqa: F401
    ParallelPlan,
    compile_step_with_plan,
)
from horovod_tpu.train.pipeline import (  # noqa: F401
    make_pipeline_train_step,
)
# Data-plane integrity (ISSUE 13; docs/TROUBLESHOOTING.md "My loss
# went NaN / my replicas disagree"): the numeric guardrail's spec and
# the cross-replica SDC canary
from horovod_tpu.train.guard import (  # noqa: F401
    GuardSpec,
    ReplicaCanary,
    param_digest,
)

# High-level training API (reference: horovod/torch/optimizer.py,
# horovod/tensorflow/__init__.py DistributedGradientTape)
from horovod_tpu.train.optimizer import (  # noqa: F401
    DistributedOptimizer,
    DistributedGradTransform,
    distributed_grad,
    broadcast_parameters,
    broadcast_optimizer_state,
    broadcast_object,
    allgather_object,
)
# Backprop/collective overlap engine (train/overlap.py):
# byte-budgeted gradient buckets, software-pipelined
# microbatch accumulation, fused dequantize+apply optimizers.
from horovod_tpu.train.buckets import (  # noqa: F401
    BucketPlan,
    plan_buckets,
)
from horovod_tpu.train.overlap import (  # noqa: F401
    bucketed_grad_sync,
    make_overlap_train_step,
    pipelined_accumulate,
)
# Mesh-path communication autotuner (train/autotune.py):
# topology-aware hierarchical collectives + online plan search with a
# persistent, fingerprint-keyed tuning cache.
from horovod_tpu.common.topology import (  # noqa: F401
    MeshTopology,
    detect_topology,
)
from horovod_tpu.train.autotune import (  # noqa: F401
    AutotuneOptions,
    Plan as AutotunePlan,
    make_parallel_train_step,
    parallel_candidate_plans,
)
from horovod_tpu.train.fused_apply import (  # noqa: F401
    fused_adam,
    fused_sgd,
)
# Gradient compression subsystem (quantizers + error feedback +
# quantized wire paths; reference analog: horovod/torch/compression.py,
# grown per EQuARX — see compression/__init__.py)
from horovod_tpu.compression import (  # noqa: F401
    Compression,
    Compressor,
    ErrorFeedback,
)
from horovod_tpu.ops.collectives import (  # noqa: F401
    quantized_allreduce,
    quantized_allreduce_async,
    quantized_grouped_allreduce,
    quantized_grouped_allreduce_async,
)
from horovod_tpu.train.sync_batch_norm import SyncBatchNorm  # noqa: F401
# Durable sharded checkpointing (native subsystem; Checkpointer is the
# same class via the train.checkpoint back-compat shim, orbax optional)
from horovod_tpu.checkpoint import (  # noqa: F401
    CheckpointError,
    ShardedCheckpointer,
)
from horovod_tpu.train.checkpoint import Checkpointer  # noqa: F401
from horovod_tpu.train import callbacks  # noqa: F401

# Metrics & telemetry subsystem (docs/OBSERVABILITY.md; no reference
# analog — the reference's only runtime introspection is the timeline)
from horovod_tpu import metrics  # noqa: F401

# Flight recorder & hang autopsy (docs/OBSERVABILITY.md "Flight
# recorder & hang autopsy"): cross-rank trace merging, bounded event
# ring, hang watchdog with autopsy bundles
from horovod_tpu import diagnostics  # noqa: F401

# Elastic worker API (reference: horovod.elastic)
from horovod_tpu import elastic  # noqa: F401

# Zero-drop online serving (docs/SERVING.md): replica fleet, dynamic
# batcher, hedging router, hot weight swap (reference analog: the
# elastic driver's Spark/Ray serving integrations)
from horovod_tpu import serving  # noqa: F401

# What a start pays for this package's own import, before any line of a job
# runs (``scopes.HOST_IMPORT``: one record of the host log, from the first
# line above to here; profiling/host_log.py)
from horovod_tpu.profiling import host_log as _host_log
from horovod_tpu.profiling import scopes as _scopes
_host_log.record(_scopes.HOST_IMPORT, _T_IMPORT,
                 _time.perf_counter() - _T_IMPORT)
