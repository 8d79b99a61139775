"""The one vocabulary of phase names: what the train step's parts are
called inside the compiled program and on the profiler's host plane.

Device phases are put with :func:`scope` (``jax.named_scope`` and, for
what the phase's Python costs while a step is traced, a host span) where
the work happens (``models/bert.py``, ``models/transformer.py``). A scope is
metadata: it adds a path component to the ``op_name`` of every HLO instruction
traced under it and changes no jaxpr and no compiled code. (Nor the
persistent cache's key, which leaves metadata out, except where the
program holds a Pallas kernel, whose serialised body carries the name
stack: an executable read from the cache has the names of the build
that wrote it.) Differentiation writes the direction into the same
path, around its outermost component: ``jvp(hvd.head)/..`` forward and
``transpose(jvp(hvd.head))/..`` backward where the scope is outermost,
``transpose(jvp(Bert))/layer_3/hvd.mlp/..`` under a flax module; the
optimizer update carries plain ``hvd.optimizer``. Any ``jax.profiler``
/ XProf trace groups by these names, and
``benchmarks/chip/scope_reduce.py`` reads device time per phase and
direction from them.

Host spans go through :func:`horovod_tpu.profiling.annotate`, which also
keeps them in the host log (``profiling/host_log.py``) outside a profiler
session; :func:`scope` and ``compile_watch.kernel_trace`` open the same
kind of span.

**Owner and reason.** A reader that gives every executed device
instruction to one part of the step
(``benchmarks/chip/readers/step_owners.py``) takes two things from an
instruction's path. Its *owner* is the path's phases in their order
(``hvd.layers/hvd.ssm/hvd.ssm.conv``): what part of the model the work is
for. Its *reason* is why the work ran, and two reasons are names in the
path: :data:`RECOMPUTED`, the component JAX itself writes around the
second run of a ``jax.checkpoint``ed function when its transposition runs
it again (``.../checkpoint/rematted_computation/hvd.mlp/..``: every
checkpointed block of ``models/transformer.py`` and the held share's
``gathered`` of ``parallel/moe.py``), and :data:`RECOMPUTE`, the scope the
program puts where a hand-written ``custom_vjp`` backward runs forward work
again *outside* a kernel. One place does today, ``parallel/moe.py``'s
``_ffn_held_bwd`` (the hidden rows, made again from the kept products for
the way down's weight gradient). Looked through and found to run none: the
other backwards of ``parallel/moe.py`` (``_gmm_bwd``, ``_dispatch_bwd``,
``_combine_bwd``), ``models/transformer.py:_table_rows_bwd``,
``ops/pallas_xent.py`` (both keep the softmax's derivative),
``ops/pallas_attention.py`` and ``ops/pallas_ssm.py`` (their backwards lay
the kept operands out for the kernel again, heads first or column and row
form: transposes, which a reader files under relayout whatever their name).
Work made again *inside* a kernel (``hvd_flash_bwd``'s scores,
``hvd_ssm_scan_bwd``'s decays) is the kernel's and has no name of its own.

A model that needs another phase adds it here, and
nowhere else: ``tests/test_scopes.py`` holds the strings to this file.
The Pallas kernels' names are instruction names, not scopes, and stay where
the kernels are: of the train steps ``hvd_flash_attention`` and
``hvd_flash_bwd``, ``hvd_block_attention`` and ``hvd_block_attention_bwd``
(``ops/pallas_attention.py``), ``hvd_fused_xent`` (``ops/pallas_xent.py``),
``hvd_ssm_scan`` and ``hvd_ssm_scan_bwd`` (``ops/pallas_ssm.py``),
``hvd_moe_gmm`` (``parallel/moe.py``, a scope around the grouped-matmul
kernels, which keep megablox's own names under it); of the compressed
exchange ``hvd_block_quantize``, ``hvd_block_quantize_ef``,
``hvd_block_dequantize``, ``hvd_fused_sgd_apply`` and
``hvd_fused_adam_apply`` (``ops/pallas_quantize.py``). A metric finds a
kernel by searching its pattern in the instruction's name, so no kernel's
name holds another's: attention's backward is not
``hvd_flash_attention_bwd``, which every metric of the forward kernel would
sum in.
"""

from __future__ import annotations

# -- device phases (jax.named_scope) -----------------------------------------
EMBED = "hvd.embed"
#: the stack of blocks and what runs it: under a scan (or a pipeline
#: schedule) its slicing and stacking of per-layer weights, residuals and
#: gradients, which belong to no one block's attention or MLP
LAYERS = "hvd.layers"
#: the attention block with its projections, residual and norm
ATTENTION = "hvd.attention"
#: nested in ATTENTION: scores, softmax, weighted sum — what a kernel replaces
ATTENTION_CORE = "hvd.attention.core"
#: nested in ATTENTION_CORE, in a stack with several kinds of layer
#: (``TransformerConfig.layer_pattern``): the core of a layer with a
#: window, and of one that sees the whole causal history
ATTENTION_CORE_WINDOW = "hvd.attention.core.window"
ATTENTION_CORE_FULL = "hvd.attention.core.full"
#: nested in ATTENTION, in a block of a gated kind (``("attention", window,
#: rope, heads, True)``): the gate's projection of the block's normed input,
#: its sigmoid and the multiply of the core's output, a scalar a head
ATTENTION_GATE = "hvd.attention.gate"
#: nested in ATTENTION, around everything between a latent-attention block's
#: norm and its core (``models/latent.py``), and its two parts. Down: the
#: projections onto the two latents and the shared rope key, and the
#: latents' norms. Up: the heads' queries, keys and values from the latents,
#: rope, the rope key's broadcast over the heads, the concatenations
#: nested in ATTENTION, in a block with a learned index over its keys
#: (``TransformerConfig.index_topk``; ``ops/sparse_attention.py``), and its
#: three parts. Index: the indexer's three projections of the block's normed
#: input, its key's LayerNorm and rope. Scores: every causal key's index score
#: for every query, a block of query rows at a time. Select: the exact top-k
#: a query row (the searches by value and by index, the mask and its bits).
#: Loss: the KL of the heads' mean attention against the index's softmax over
#: the selected keys
ATTENTION_INDEX = "hvd.attention.index"
ATTENTION_INDEX_SCORES = "hvd.attention.index.scores"
ATTENTION_INDEX_SELECT = "hvd.attention.index.select"
ATTENTION_INDEX_LOSS = "hvd.attention.index.loss"
#: nested in ATTENTION_CORE, in such a block: scores, softmax and weighted sum
#: under the selection's mask, forward and (autodiff's) backward
ATTENTION_CORE_SPARSE = "hvd.attention.core.sparse"
ATTENTION_LATENT = "hvd.attention.latent"
ATTENTION_LATENT_DOWN = "hvd.attention.latent.down"
ATTENTION_LATENT_UP = "hvd.attention.latent.up"
MLP = "hvd.mlp"
#: nested in MLP: the expert layer of an MoE block, and its four parts.
#: Router: logits, softmax, top-k, the auxiliary losses and counters.
#: Dispatch: the sort by expert and the row gather (with ``ep`` > 1 the
#: exchange of tokens). Experts: the grouped matmuls and the activation
#: between them. Combine: rows back to their tokens, the weighted sum.
MOE = "hvd.moe"
MOE_ROUTER = "hvd.moe.router"
MOE_DISPATCH = "hvd.moe.dispatch"
MOE_EXPERTS = "hvd.moe.experts"
MOE_COMBINE = "hvd.moe.combine"
#: nested in MOE: the expert every token runs (``moe_shared_width``), whole
#: on every chip of an expert-parallel group
MOE_SHARED = "hvd.moe.shared"
#: a state-space (Mamba-2) block with its norm and residual, and its four
#: parts. Proj: both projections, in and out. Conv: the causal depthwise
#: convolution and its activation. Scan: the time steps and decays, the
#: chunks' products, the carried state, the skip ``D x``. Norm: the gate
#: and the grouped RMSNorm
SSM = "hvd.ssm"
SSM_PROJ = "hvd.ssm.proj"
SSM_CONV = "hvd.ssm.conv"
SSM_SCAN = "hvd.ssm.scan"
SSM_NORM = "hvd.ssm.norm"
#: a gated short-convolution block (``models/short_conv.py``) with its norm
#: and residual, and its two parts. Proj: both projections, in and out.
#: Gate: ``B * u``, the causal depthwise taps, ``C *``, the rounding to the
#: compute dtype
SHORT_CONV = "hvd.short_conv"
SHORT_CONV_PROJ = "hvd.short_conv.proj"
SHORT_CONV_GATE = "hvd.short_conv.gate"
#: a gated delta-rule block (``models/delta.py``) with its norm and residual,
#: and its five parts. Proj: the three projections of the normed input and
#: the out-projection. Conv: the three causal depthwise convolutions, their
#: silu, the L2 norms of q and k. Gates: the decay's two matmuls, softplus
#: and rate, beta, the output gate's two matmuls. Scan: the chunks' sums,
#: decayed pairs, triangular inverse, the carried state, the outputs. Norm:
#: the head's RMSNorm and the sigmoid gate
DELTA = "hvd.delta"
DELTA_PROJ = "hvd.delta.proj"
DELTA_CONV = "hvd.delta.conv"
DELTA_GATES = "hvd.delta.gates"
DELTA_SCAN = "hvd.delta.scan"
DELTA_NORM = "hvd.delta.norm"
#: nested in LAYERS: a looped model's passes through its stack, with the
#: final norm that closes each loop step
LOOP = "hvd.loop"
#: nested in HEAD: a looped model's exit gate, exit distribution and the
#: mixing of the loop steps' losses
LOOP_GATE = "hvd.loop.gate"
#: final norm or transform, logits, loss
HEAD = "hvd.head"
#: the multi-token-prediction module (``mtp_depth``): its blocks and its head
#: call carry it beside their own names. Proj: the module's two input
#: norms, the second read of the embedding and the projection of their
#: concatenation
MTP = "hvd.mtp"
MTP_PROJ = "hvd.mtp.proj"
GRAD_SYNC = "hvd.grad_sync"
OPTIMIZER = "hvd.optimizer"

# -- reasons (the module docstring's "Owner and reason") ----------------------
#: JAX's own path component (``ad_checkpoint.py``) around a
#: ``jax.checkpoint``ed function's second run; ``tests/test_scopes.py`` tells
#: the JAX upgrade that renames it
RECOMPUTED = "rematted_computation"
#: where a hand-written backward runs forward work again outside a kernel; a
#: reason, not a part of the model: a reader leaves it out of an owner
RECOMPUTE = "hvd.recompute"

#: phases of the model proper: each appears forward and backward
MODEL_PHASES = (EMBED, LAYERS, ATTENTION, ATTENTION_CORE, MLP, HEAD)
#: phases only an MoE model has, each forward and backward
MOE_PHASES = (MOE, MOE_ROUTER, MOE_DISPATCH, MOE_EXPERTS, MOE_COMBINE)
#: phases only a stack of one-sublayer blocks with state-space blocks and a
#: shared expert has (Nemotron-H), each forward and backward
HYBRID_PHASES = (MOE_SHARED, SSM, SSM_PROJ, SSM_CONV, SSM_SCAN, SSM_NORM)
#: phases only a looped model has, each forward and backward
LOOP_PHASES = (LOOP, LOOP_GATE)
#: phases only a stack with several kinds of layer has, each forward and
#: backward
MIXED_PHASES = (ATTENTION_CORE_WINDOW, ATTENTION_CORE_FULL)
#: the phase only a stack with gated attention blocks has (Laguna), forward
#: and backward
GATED_PHASES = (ATTENTION_GATE,)
#: phases only a model with latent attention and a multi-token-prediction
#: module has (GLM-4.7-Flash), each forward and backward
LATENT_PHASES = (ATTENTION_LATENT, ATTENTION_LATENT_DOWN, ATTENTION_LATENT_UP,
                 MTP, MTP_PROJ)
#: phases only a stack with gated short-convolution blocks has (LFM2), each
#: forward and backward
SHORT_CONV_PHASES = (SHORT_CONV, SHORT_CONV_PROJ, SHORT_CONV_GATE)
#: phases only a stack whose attention selects keys by a learned index has,
#: each forward and backward but the selection, which no gradient passes
INDEX_PHASES = (ATTENTION_INDEX, ATTENTION_INDEX_SCORES,
                ATTENTION_INDEX_SELECT, ATTENTION_INDEX_LOSS,
                ATTENTION_CORE_SPARSE)
#: phases only a stack with gated delta-rule blocks has, each forward and
#: backward
DELTA_PHASES = (DELTA, DELTA_PROJ, DELTA_CONV, DELTA_GATES, DELTA_SCAN,
                DELTA_NORM)
DEVICE_PHASES = (MODEL_PHASES + MOE_PHASES + LOOP_PHASES + MIXED_PHASES
                 + HYBRID_PHASES + LATENT_PHASES + GATED_PHASES
                 + SHORT_CONV_PHASES + INDEX_PHASES + DELTA_PHASES
                 + (GRAD_SYNC, OPTIMIZER, RECOMPUTE))

# -- host spans (profiling.annotate) ------------------------------------------
#: the input iterator's ``next()``: the host makes the batch
INPUT_SOURCE = "hvd.input.source"
#: ``jax.device_put`` of the batch onto its sharding
INPUT_PLACE = "hvd.input.place"

#: one garbage collection of the interpreter (``profiling/host_log.py``'s
#: ``gc.callbacks`` entry): the host runs no Python while it lasts
HOST_GC = "hvd.host.gc"
#: one trace, lowering, backend compile or persistent-cache read that JAX
#: timed (``profiling/compile_watch.py``'s listener); a record of the host
#: log only, written when it ends
HOST_COMPILE = "hvd.host.compile"
#: the Python of one part of a function while JAX traces it, the part after a
#: slash: a device phase (:func:`scope`: ``hvd.host.trace/hvd.moe.experts``)
#: or a Pallas call site with its kernel's body
#: (``compile_watch.kernel_trace``: ``hvd.host.trace/kernel/hvd_flash_bwd``)
HOST_TRACE = "hvd.host.trace"
#: the import of this package, from ``horovod_tpu/__init__.py``'s first line
#: to its last; a record only, written once
HOST_IMPORT = "hvd.host.import"
#: one ``hvd.init()``; its backend's creation is ``hvd.host.init/backend``
HOST_INIT = "hvd.host.init"

HOST_SPANS = (INPUT_SOURCE, INPUT_PLACE, HOST_GC, HOST_COMPILE, HOST_TRACE,
              HOST_IMPORT, HOST_INIT)


# -- the one door for a device phase ------------------------------------------
import contextlib                                       # noqa: E402

import jax                                              # noqa: E402

# host_log reads this module's names when a span ends, never at import
from horovod_tpu.profiling import host_log              # noqa: E402


@contextlib.contextmanager
def scope(name: str):
    """``with scopes.scope(scopes.MLP):`` is ``jax.named_scope`` (the jaxpr
    and every ``op_name`` as before) inside one :class:`host_log.Span` named
    :data:`HOST_TRACE` ``/`` the phase: what the block's Python cost. That
    Python runs while a function is traced and not when its executable runs,
    so a steady step leaves none; a retrace inside a profiler session lies on
    the device planes' clock. (Called op by op, outside any ``jit``, the span
    is the block's eager run.)"""
    with host_log.Span(f"{HOST_TRACE}/{name}"), jax.named_scope(name):
        yield
