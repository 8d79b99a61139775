"""Flagship model: GPT-style (optionally MoE) transformer with full 5-axis
parallelism — dp (batch), pp (stages), ep (experts), sp (sequence/ring
attention), tp (tensor) — written as ONE manual-SPMD program under
``shard_map`` over the canonical mesh.

The reference framework scales *batch only* (SURVEY.md §2.6); its model zoo
is "whatever TF/Torch model you wrap". This module is the TPU-native
counterpart of that contract at modern scale: the training step compiles to
a single XLA program whose collectives (psum over tp, ppermute rings over
sp and pp, all_to_all over ep, psum over dp for gradients) all ride ICI.

This file is the training model; the Mamba-2 mixer is ``models/mamba.py``,
the gated short-convolution mixer ``models/short_conv.py``, the gated
delta-rule mixer ``models/delta.py``, latent attention ``models/latent.py``,
the
serving plane's paged decode model and its oracle ``models/decode.py``. A leaf
is declared once, in its block's ``*_leaves`` function (``models/_kinds.py``:
name, shape, draw, partition spec, under the one ``if`` that says when the
config has it), and ``init_params`` and ``param_shardings`` are both built
from that; a kind of block once, as a row of ``_BLOCK_KINDS``.

Layout conventions (local = per-device shapes):
  tokens          [B/dp, S/sp]
  embedding       [V/tp, M]          (vocab-sharded, tied softmax)
  attention       heads sharded tp → q/k/v [B', S', H/tp, Dh], ring over sp
  mlp             w1 [M, F/tp], w2 [F/tp, M], psum(tp) after w2
  MoE             experts sharded ep; dropless sorted dispatch, the ep
                  group's tokens exchanged by all_gather / psum_scatter
  layers          stacked [pp, L/pp, ...]; GPipe schedule over pp
  loop            n_loops > 1: a scan over loop steps around the scan over
                  layers, the same weights each step, ln_f after each; every
                  step's state goes to the head and the exit gate (no pp).
                  Checkpointed (remat None or True), the backward pass is
                  the stack's own (``_looped_stack``): one accumulator of
                  the stacked parameters in the loops' carry, each pass's
                  weight gradient added into its layer's slice in place,
                  where the scans' transpose holds two stacks and adds one
                  to the other whole, once a loop step
  layer kinds     ``layer_pattern``: one period of (window, rope) kinds; the
                  scan goes over periods, a period's layers unrolled inside
  one sublayer    a kind that starts with a word of ``_BLOCK_KINDS``,
                  ("mamba",), ("experts",), ("attention", window, rope):
                  ``x + mixer(norm(x))`` and no more (Nemotron-H); the tree's
                  ``layers`` then holds one stack a word, ``[pp, blocks of
                  that word / pp, ...]`` (a Mamba block: ``models/mamba.py``;
                  ("latent",) latent attention: ``models/latent.py``;
                  ("dense",) the dense FFN at ``dense_ff``; ("conv",) a
                  gated short convolution: ``models/short_conv.py``;
                  ("delta",) a gated delta rule: ``models/delta.py``)
  attention kinds ("attention", window, rope, heads, gated): ``rope`` may be
                  a table of the kind's own (``_kinds.Rope``: a theta, the
                  rotated part of the head, YaRN); ``heads`` query heads
                  where they are not ``n_heads`` and ``gated``, a sigmoid
                  gate on the core's output, a head (True: ``wg``) or a
                  channel ("channel": the second half of a ``wq`` twice as
                  wide, split a head), change the
                  block's leaves, so such kinds are stacks of their own
                  (``layers["attention_64_gated"]``) and two attention
                  shapes share one scan over periods (Laguna)
  leading blocks  ``lead_pattern``: blocks before the periodic stack, each
                  once (``lead``, a stack a word ``[blocks, ...]``), so a
                  dense layer leads a scan over (attention, experts) periods
  prediction      ``mtp_depth`` 1: a module on the stack's output and the
                  next token's embedding, one more period of the pattern,
                  through the main head for the token after the next; its
                  loss times ``mtp_weight`` joins the first (``mtp``)
  expert share    ``expert_share=(i, of)``: this device holds that share of
                  every layer's experts with no ep axis live (one chip of an
                  expert-parallel group, run alone)
Gradient sync: params are replicated over (dp, sp) → psum over those axes
after ``jax.grad``; tp/ep/pp-sharded leaves keep local (sharded) grads.
"""

from __future__ import annotations

import contextlib
import dataclasses
import functools
from typing import Any, Dict, Optional, Tuple

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from horovod_tpu._compat import axis_size, shard_map

from horovod_tpu.models import delta, latent, mamba, short_conv
from horovod_tpu.models._kinds import (BlockKind, Leaf, Rope, layernorm,
                                       normal, ones, remat, rmsnorm, rope,
                                       scaled, zeros)
from horovod_tpu.models.scan_util import multi_step
from horovod_tpu.parallel.ring_attention import ring_attention_spmd
from horovod_tpu.parallel.moe import expert_ffn, moe_layer_spmd, rows_held
from horovod_tpu.profiling import scopes


@dataclasses.dataclass(frozen=True)
class TransformerConfig:
    vocab_size: int = 32000
    d_model: int = 512
    n_heads: int = 8
    n_layers: int = 4
    d_ff: int = 2048
    max_seq: int = 2048
    n_experts: int = 0          # 0 → dense FFN; >0 → MoE every layer
    moe_top_k: int = 2
    # -- what the architecture is (defaults: the GPT block this file began
    # as; OLMoE sets every one of them) --------------------------------
    moe_gated: bool = False     # experts down(silu(gate(x)) * up(x)), three
    #                             matrices of width d_ff; else gelu, two
    moe_renormalize: bool = False   # top-k weights divided by their sum
    moe_balance_weight: float = 0.01    # load-balancing loss, over all k
    moe_z_weight: float = 0.0   # router z-loss mean(logsumexp(logits)^2)
    qk_norm: Any = False        # True: RMSNorm over the whole q and k
    #                             projections, before the head split and rope
    #                             (OLMoE). "head": over each head's channels,
    #                             after the split and before rope, one weight
    #                             ``[head_dim]`` for all q heads and one for
    #                             all k heads (LFM2)
    tie_embeddings: bool = True     # logits from the embedding table; else
    #                                 an ``lm_head`` [M, V] of its own
    post_norm: bool = False     # an RMSNorm after each sublayer too, before
    #                             the residual add ("sandwich": ln1_post,
    #                             ln2_post), as Ouro has them
    ffn_gated: bool = False     # dense FFN down(silu(gate(x)) * up(x)), three
    #                             matrices (w1, w3, w2); else gelu, two
    n_loops: int = 1            # the whole stack applied this many times with
    #                             the same weights (a looped language model,
    #                             arXiv:2510.25741): ln_f closes every loop
    #                             step, each step's state feeds the next step
    #                             and the head, an exit gate mixes the losses
    # -- attention's shape and the kinds of layer (defaults: multi-head
    # attention at d_model / n_heads, every layer causal with rope) -------
    head_width: Optional[int] = None    # a head's width where it is not
    #                             d_model // n_heads (q and o are then
    #                             ``n_heads * head_width`` wide, not d_model)
    n_kv_heads: Optional[int] = None    # k/v heads (grouped-query attention:
    #                             q head h reads k/v head h // (n_heads //
    #                             n_kv_heads)). None: n_heads
    layer_pattern: Tuple[Tuple, ...] = ((None, True),)
    #                             one period of the stack's layer kinds, each
    #                             (window or None, rope or not): layer l is
    #                             of kind l % len. A window W keeps of a query
    #                             at t the keys t - W < j <= t; a layer
    #                             without rope has no positions at all (NoPE).
    #                             A kind that starts with a word is a block of
    #                             ONE sublayer, ``x + mixer(norm(x))``:
    #                             ("mamba",) a Mamba-2 mixer (``ssm_*``),
    #                             ("experts",) the expert layer, ("attention",
    #                             window, rope) attention; a pattern is of
    #                             such kinds throughout or of none. ``rope``
    #                             may be a ``_kinds.Rope`` table; an attention
    #                             kind may go on (.., heads, gated): its own
    #                             number of query heads (None: ``n_heads``)
    #                             and a sigmoid gate on the core's output,
    #                             read from the block's normed input: True a
    #                             logit a head, "channel" a logit a channel,
    #                             the second half of each head's columns of a
    #                             query projection twice as wide
    moe_router_input: str = "tokens"    # what the router's logits are
    #                             computed from: the normed tokens the experts
    #                             get ("tokens"), or the block's input, before
    #                             its first norm and attention ("block_input")
    moe_activation: str = "silu"    # a gated expert's gate activation:
    #                             "silu", or "relu" (ReGLU); "relu2": an
    #                             ungated expert down(relu(up(x)) ** 2), which
    #                             is gelu otherwise
    moe_router_scores: str = "softmax"  # the experts' scores: the softmax
    #                             over them, or each one's own "sigmoid", the
    #                             top-k then taken of score + ``router_bias``
    #                             (a leaf no gradient reaches) and the weights
    #                             the chosen scores (parallel/moe.py:route)
    moe_routed_scale: float = 1.0   # the top-k weights times this, after
    #                             their renormalisation
    moe_shared_width: int = 0   # > 0: an expert of this width every token
    #                             runs beside its chosen ones, of their form
    #                             (``ws1``, ``ws2``, and ``ws3`` where they
    #                             are gated; ``moe_activation``), whole on
    #                             every device that holds a share of the others
    moe_shared_gate: bool = False   # the shared expert's output times the
    #                             sigmoid of one logit a token (``ws_gate``)
    # -- latent attention (``models/latent.py``), where the pattern has it --
    q_latent: int = 0           # channels of the queries' latent; 0: none,
    #                             the queries come from the block's input
    kv_latent: int = 0          # channels of the keys' and values' latent
    rope_width: int = 0         # a head's last channels, which carry the
    #                             positions; the key's are one head shared by
    #                             all (``head_width`` is both parts together)
    latent_rope: bool = True    # False: those channels are not rotated (a
    #                             latent block without positions, NoPE)
    value_width: Optional[int] = None   # a latent block's value channels a
    #                             head where they are not ``head_width``
    # -- blocks beside the periodic stack -----------------------------------
    lead_pattern: Tuple[Tuple, ...] = ()    # one-sublayer kinds of the blocks
    #                             before the periodic stack, each once with
    #                             weights of its own and not counted in
    #                             ``n_layers`` (``params["lead"]``, a stack a
    #                             word): ("latent",), ("dense",) is a dense
    #                             layer leading a stack of expert layers
    dense_ff: Optional[int] = None  # width of a ("dense",) block's FFN
    #                             (``ffn_gated``). None: d_ff, which is an
    #                             expert's width where there are experts
    mtp_depth: int = 0          # multi-token-prediction modules (DeepSeek-V3,
    #                             arXiv:2412.19437 section 2.2; 0 or 1): one
    #                             more period of ``layer_pattern`` with
    #                             weights of its own (``params["mtp"]``) on
    #                             the stack's output before ``ln_f`` and the
    #                             next token's embedding, through the main
    #                             head, for the token after the next
    mtp_weight: float = 0.3     # the second prediction's loss times this,
    #                             added to the first's
    # -- a Mamba-2 mixer (arXiv:2405.21060), where the pattern has one ------
    ssm_heads: int = 0          # heads of ``ssm_head_dim`` channels: the
    #                             inner width is their product, not a
    #                             multiple of d_model
    ssm_head_dim: int = 64
    ssm_state: int = 128        # a head's state is [ssm_head_dim, ssm_state]
    ssm_groups: int = 1         # B and C are shared by the heads of a group:
    #                             head h reads group h // (heads / groups)
    ssm_conv: int = 4           # taps of the causal depthwise convolution
    ssm_chunk: int = 128        # positions a chunk of the scan; the sequence
    #                             is whole chunks
    # -- a gated short convolution (``models/short_conv.py``), where the
    # pattern has a ("conv",) block --------------------------------------
    conv_taps: int = 0          # taps of its causal depthwise convolution,
    #                             the last on the current position
    # -- a gated delta rule (``models/delta.py``), where the pattern has a
    # ("delta",) block ----------------------------------------------------
    delta_heads: int = 0        # heads of ``delta_head_dim`` key and value
    #                             channels; 0: none
    delta_head_dim: int = 128
    delta_taps: int = 4         # taps of the causal depthwise convolutions
    #                             on q, k and v
    delta_chunk: int = 64       # positions a chunk of the scan; the sequence
    #                             is whole chunks
    delta_decay: str = "channel"    # the state's decay a position: a vector
    #                             over a head's key channels, or one scalar a
    #                             "head": Gated DeltaNet's block (one fused
    #                             in-projection and convolution, ``b`` and
    #                             ``a`` from one direct projection, the output
    #                             norm times silu of a full-rank ``z``)
    delta_key_heads: Optional[int] = None   # key heads where they are fewer
    #                             than ``delta_heads``, which then counts the
    #                             value heads: value head h reads key head
    #                             h // (delta_heads // delta_key_heads)
    # -- a learned index over the keys (DeepSeek-V3.2, arXiv:2512.02556
    # section 2.1; ``ops/sparse_attention.py``), beside grouped-query
    # attention in every block of two sublayers ------------------------------
    index_topk: int = 0         # > 0: a query attends to the keys of its
    #                             ``index_topk`` largest index scores among
    #                             the causal ones (all of them while it has
    #                             no more), one set for all its heads, exact;
    #                             the indexer (``wq_idx``, ``wk_idx``,
    #                             ``k_idx_norm`` + bias, ``w_idx``) reads the
    #                             block's normed input behind a
    #                             ``stop_gradient`` and learns from its own
    #                             loss alone (``index_loss``, the step's loss
    #                             plus its sum over the layers). 0: no indexer
    index_heads: int = 0        # the indexer's query heads
    index_head_dim: int = 0     # their width, and its one key head's
    expert_share: Tuple[int, int] = (0, 1)  # (index, of): this device holds
    #                             experts [index * E / of, (index + 1) * E /
    #                             of) of every layer, as the leading dimension
    #                             of we1 / we3 / we2, with no ep axis live;
    #                             the router keeps its n_experts columns and
    #                             the layer's output is those experts' part
    # -- scalar multipliers (Granite's "power scheduler" parametrisation,
    # arXiv:2408.13359; at their defaults no multiply is traced) -----------
    embed_scale: float = 1.0    # the embedding's rows times this, as they
    #                             enter the residual stream
    residual_scale: float = 1.0     # every sublayer's output times this,
    #                             before the residual add: x + c * f(norm(x))
    attention_scale: Optional[float] = None     # the attention scores'
    #                             multiplier where it is not 1 / sqrt(head
    #                             width)
    logits_scale: float = 1.0   # the logits times this (the final normed
    #                             state is, before the head: one [tokens, M]
    #                             multiply, no second array of logits)
    norm_eps: float = 1e-6
    zero_centred_norms: bool = False    # the norms' scale is ``1 + w`` in
    #                             float32 and ``w`` drawn as zeros (weight
    #                             decay pulls the scale to 1): the blocks' and
    #                             the final norm and a head's q and k norm,
    #                             not a mixer's output norm
    rope_theta: float = 10000.0
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    n_microbatches: int = 1     # pipeline microbatches (per pp>1)
    remat: Optional[bool] = None    # jax.checkpoint each block (HBM for
    #                             FLOPs). None: where the architecture needs
    #                             it: the pipeline's stages, a looped stack,
    #                             a stack with a prediction module, a Mamba
    #                             and a latent attention block yes, the single
    #                             scan over layers of any other kind no

    @property
    def head_dim(self) -> int:
        return self.head_width or self.d_model // self.n_heads

    @property
    def kv_heads(self) -> int:
        return self.n_kv_heads or self.n_heads

    @property
    def held_experts(self) -> int:
        """Experts whose weights one unsharded copy of a layer holds."""
        return self.n_experts // self.expert_share[1]

    @property
    def one_sublayer(self) -> bool:
        """Whether the pattern's kinds are blocks of one sublayer."""
        return isinstance(self.layer_pattern[0][0], str)

    @property
    def ssm_inner(self) -> int:
        return self.ssm_heads * self.ssm_head_dim

    @property
    def ssm_conv_width(self) -> int:
        """Channels the convolution runs over: x, B and C."""
        return self.ssm_inner + 2 * self.ssm_groups * self.ssm_state

    def __post_init__(self):
        index, of = self.expert_share
        if self.n_experts % of or not 0 <= index < of:
            raise ValueError(f"expert_share={self.expert_share} does not "
                             f"divide n_experts={self.n_experts}")
        if self.n_heads % self.kv_heads:
            raise ValueError(f"n_kv_heads={self.n_kv_heads} does not divide "
                             f"n_heads={self.n_heads}")
        if not self.layer_pattern or self.n_layers % len(self.layer_pattern):
            raise ValueError(
                f"layer_pattern of {len(self.layer_pattern)} kinds does not "
                f"divide n_layers={self.n_layers}")
        if self.qk_norm not in (False, True, "head"):
            raise ValueError(f"qk_norm={self.qk_norm!r}")
        if self.moe_router_input not in ("tokens", "block_input"):
            raise ValueError(f"moe_router_input={self.moe_router_input!r}")
        if self.moe_activation not in ("silu", "relu", "relu2"):
            raise ValueError(f"moe_activation={self.moe_activation!r}")
        if self.moe_router_scores not in ("softmax", "sigmoid"):
            raise ValueError(
                f"moe_router_scores={self.moe_router_scores!r}")
        if self.lead_pattern and not self.one_sublayer:
            raise ValueError(
                f"lead_pattern={self.lead_pattern} before a layer_pattern "
                "of two-sublayer blocks: the leading blocks are of one "
                "sublayer, as is the stack they lead")
        kinds = self.layer_pattern + self.lead_pattern
        words = dict.fromkeys(kind[0] for kind in kinds
                              if isinstance(kind[0], str))
        if words and not all(
                kind[0] in _BLOCK_KINDS
                and 0 <= len(kind) - _BLOCK_KINDS[kind[0]].length
                <= _BLOCK_KINDS[kind[0]].optional
                for kind in kinds):
            raise ValueError(
                f"layer_pattern={self.layer_pattern}, lead_pattern="
                f"{self.lead_pattern}: a block of one "
                f"sublayer is one of {sorted(_BLOCK_KINDS)}, and a pattern "
                "is of such blocks throughout or of none")
        for word in words:
            _BLOCK_KINDS[word].validate(self)
        for kind in kinds:
            heads, gated = _kind_fields(kind)
            if (heads or self.n_heads) % self.kv_heads:
                raise ValueError(f"n_kv_heads={self.kv_heads} does not "
                                 f"divide the {heads or self.n_heads} heads "
                                 f"of {kind}")
            if gated not in (False, True, "channel") or (
                    gated == "channel" and self.qk_norm is True):
                raise ValueError(
                    f"{kind}: an attention block's gate is a head's (True) "
                    "or a \"channel\"'s, and a gate a channel shares the "
                    "query projection, which qk_norm=True would norm whole")
        unsupported = [
            name for name, on in (
                ("post_norm", self.post_norm),
                ("qk_norm=True", self.qk_norm is True),
                ("n_loops", self.n_loops > 1), ("mtp_depth", self.mtp_depth),
                ("index_topk", self.index_topk),
                *((f'("{word}",) blocks', word in words)
                  for word in ("mamba", "latent", "conv"))) if on]
        if self.zero_centred_norms and unsupported:
            raise NotImplementedError(
                f"zero_centred_norms with {', '.join(unsupported)}: "
                "zero-centred weights are built "
                "for the attention, delta and FFN blocks' norms, a head's q "
                "and k norm and the final norm")
        if self.index_topk:
            if self.index_heads <= 0 or self.index_head_dim <= 0 \
                    or self.index_head_dim % 2:
                raise ValueError(
                    f"index_topk={self.index_topk} with index_heads="
                    f"{self.index_heads} of index_head_dim="
                    f"{self.index_head_dim}: the indexer has heads of an "
                    "even width (rope)")
            windowed = any(kind[0] is not None for kind in kinds
                           if not isinstance(kind[0], str))
            if windowed or self.one_sublayer or self.n_loops > 1:
                raise NotImplementedError(
                    f"index_topk={self.index_topk} with layer_pattern="
                    f"{self.layer_pattern}, n_loops={self.n_loops}: the "
                    "index selects among ALL causal keys of a block of two "
                    "sublayers, whose terms join the expert layer's in one "
                    "stack. A window beside the selection, a latent "
                    "attention block (whose keys are one shared latent: the "
                    "published form of the index, which nobody has asked "
                    "for here), a stack of one-sublayer blocks (an attention "
                    "block's terms would not stack with an expert block's) "
                    "and a looped stack are not built")
        if self.mtp_depth not in (0, 1):
            raise NotImplementedError(
                f"mtp_depth={self.mtp_depth}: one multi-token-prediction "
                "module is built, a chain of them is not")
        if self.n_loops > 1 and (self.lead_pattern or self.mtp_depth):
            raise NotImplementedError(
                f"n_loops={self.n_loops} with lead_pattern or mtp_depth: a "
                "looped stack hands every loop step's normed state to the "
                "head; which of them leading blocks run before, and which a "
                "prediction module reads, nobody has said")


# ---------------------------------------------------------------------------
# SPMD building blocks (all run inside shard_map over the full mesh)
# ---------------------------------------------------------------------------

def _axis_live(name: str) -> bool:
    """True if ``name`` is a manual axis of size > 1 in the current context."""
    try:
        return axis_size(name) > 1
    except NameError:
        return False


def _psum_if(x, name):
    return lax.psum(x, name) if _axis_live(name) else x


def _qk_norm(x, g, eps):
    """RMSNorm over the whole projection width, of which this tp shard
    holds ``x``'s last dimension."""
    xf = x.astype(jnp.float32)
    total = _psum_if(jnp.sum(jnp.square(xf), axis=-1, keepdims=True), "tp")
    width = x.shape[-1] * (axis_size("tp") if _axis_live("tp") else 1)
    return (xf * jax.lax.rsqrt(total / width + eps)
            ).astype(x.dtype) * g.astype(x.dtype)


#: the most columns a row of the embedding gradient's float32 sums has when
#: it is added: on a v5e 8192 rows of 2560 add in 3.5 ms whole and in 0.68 as
#: two pieces of 1280, rows of 2048 in 0.84 and 0.53 (PERF.md §6, PR 38;
#: ``chip_smoke.py``'s ``embed lookup`` lines time both forms again)
SUM_COLUMNS = 1280


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def _table_rows(table, ids, dtype):
    """``table[ids]`` in ``dtype``: the rows taken are cast, not the table
    (the same bits), and the table's gradient is by hand. Autodiff's is a
    scatter-add of the rows into the table, which a v5e runs at 1.9 us a
    row for a table of 2560 columns, bf16 or float32 (PERF.md §6, PR 38)."""
    return table[ids].astype(dtype)


def _table_rows_fwd(table, ids, dtype):
    # (the table for its shape and dtype: a parameter, live anyway)
    return _table_rows(table, ids, dtype), (table, ids)


def _table_rows_bwd(dtype, res, cot):
    """The table's gradient with no scatter into the table: the sorted
    ids' equal runs are summed in float32 into ``[n + 1, M]`` (a target
    that small stays in the chip's near memory; the columns are added in
    pieces of at most ``SUM_COLUMNS``, each a sum of its own over the same
    sorted runs), and every row of the table then *gathers* its run's sum,
    or the zeros of slot ``n``."""
    table, ids = res
    vocab, width = table.shape
    ids = ids.reshape(-1)
    n = ids.shape[0]
    order = jnp.argsort(ids)
    ids = ids[order]
    run = jnp.cumsum(jnp.concatenate(
        [jnp.zeros((1,), jnp.int32),
         (ids[1:] != ids[:-1]).astype(jnp.int32)]))           # [n], sorted
    pieces = next(r for r in range(1, width + 1)
                  if width % r == 0 and width // r <= SUM_COLUMNS)
    rows = cot.reshape(n, width)[order]
    sums = jnp.concatenate(
        [jax.ops.segment_sum(piece.astype(jnp.float32), run,
                             num_segments=n + 1, indices_are_sorted=True)
         for piece in jnp.split(rows, pieces, axis=1)], axis=1)
    slot = jnp.full((vocab,), n, jnp.int32).at[ids].set(
        run, indices_are_sorted=True)
    return sums[slot].astype(table.dtype), None


_table_rows.defvjp(_table_rows_fwd, _table_rows_bwd)


def _embed_lookup(emb_local, tokens, cfg: TransformerConfig):
    """Vocab-sharded embedding lookup in ``cfg.dtype``: mask + psum over tp.

    With a head of its own the float32 table's rows are taken and cast
    (:func:`_table_rows`), so the table's gradient adds repeated tokens'
    rows in float32. A tied head reads the whole table in ``cfg.dtype``
    anyway: the lookup shares that cast, and its gradient joins the head's
    in the cast's transpose."""
    def take(ids):
        rows = (emb_local.astype(cfg.dtype)[ids] if cfg.tie_embeddings
                else _table_rows(emb_local, ids, cfg.dtype))
        return scaled(rows, cfg.embed_scale)
    Vl, M = emb_local.shape
    if _axis_live("tp"):
        off = lax.axis_index("tp") * Vl
        idx = tokens - off
        ok = (idx >= 0) & (idx < Vl)
        x = jnp.where(ok[..., None], take(jnp.clip(idx, 0, Vl - 1)), 0)
        return lax.psum(x, "tp")
    return take(tokens)


def _sharded_softmax_xent(logits_local, targets):
    """Cross-entropy with vocab dim sharded over tp. logits [B, S, V/tp]."""
    lf = logits_local.astype(jnp.float32)
    m_loc = jnp.max(lf, axis=-1)
    # stability shift only — stop the gradient *before* pmax (pmax has no
    # differentiation rule, and the shift cancels in exact arithmetic)
    m_loc = lax.stop_gradient(m_loc)
    m = lax.pmax(m_loc, "tp") if _axis_live("tp") else m_loc
    se = jnp.sum(jnp.exp(lf - m[..., None]), axis=-1)
    se = _psum_if(se, "tp")
    Vl = lf.shape[-1]
    if _axis_live("tp"):
        off = lax.axis_index("tp") * Vl
        idx = targets - off
        ok = (idx >= 0) & (idx < Vl)
        corr = jnp.take_along_axis(
            lf, jnp.clip(idx, 0, Vl - 1)[..., None], axis=-1)[..., 0]
        corr = lax.psum(jnp.where(ok, corr, 0.0), "tp")
    else:
        corr = jnp.take_along_axis(lf, targets[..., None], axis=-1)[..., 0]
    return jnp.log(se) + m - corr     # [B, S]


def _head_xent(x, head, targets, scale: float = 1.0):
    """Per-token loss of the head ``scale * (x @ head)``, the scale on ``x``
    (``logits_scale``; a power of two is exact there). A tp-sharded
    vocabulary
    takes the psum algebra above; a full local one takes the fused Pallas
    kernel, which leaves the logits' gradient in the logits' buffer for
    the two backward matmuls (one HBM pass over ``[N, V]``; falls back
    off-TPU / untiled by ``pallas_xent.xent_path``, the self-gating
    pattern of ``pallas_attention.attend``)."""
    x = scaled(x, scale)
    if _axis_live("tp"):
        return _sharded_softmax_xent(x @ head, targets)       # [B,S,V/tp]
    from horovod_tpu.ops.pallas_xent import head_softmax_xent
    return head_softmax_xent(x, head, targets)


def _kind_fields(kind) -> Tuple[Optional[int], Any]:
    """What an attention kind gives beyond (window, rope): (its query heads
    or None, how its core's output is gated: False, True a head, "channel");
    (None, False) for a kind that names neither and for any other kind."""
    if kind[0] != "attention":
        return None, False
    heads, gated = (tuple(kind[3:]) + (None, False))[:2]
    return heads, gated or False


def _kind_heads(cfg: TransformerConfig, kind) -> int:
    """Query heads of a block of ``kind``: its own, or ``cfg.n_heads``."""
    return _kind_fields(kind)[0] or cfg.n_heads


def _attention_leaves(cfg: TransformerConfig, kind=("attention",)):
    M = cfg.d_model
    heads, gated = _kind_heads(cfg, kind), _kind_fields(kind)[1]
    q, kv = heads * cfg.head_dim, cfg.kv_heads * cfg.head_dim
    norm = zeros if cfg.zero_centred_norms else ones
    yield Leaf("ln1", (M,), norm)
    # with a gate a channel a head's columns are its query then its gate
    yield Leaf("wq", (M, 2 * q if gated == "channel" else q), normal(),
               (None, "tp"))
    yield Leaf("wk", (M, kv), normal(), (None, "tp"))
    yield Leaf("wv", (M, kv), normal(), (None, "tp"))
    yield Leaf("wo", (q, M), normal(), ("tp", None))
    if gated is True:
        # one logit a head and position: the core's output times its sigmoid
        yield Leaf("wg", (M, heads), normal(), (None, "tp"))
    if cfg.qk_norm == "head":
        # one weight for all the heads: whole on every tp shard
        yield Leaf("q_norm", (cfg.head_dim,), norm)
        yield Leaf("k_norm", (cfg.head_dim,), norm)
    elif cfg.qk_norm:
        yield Leaf("q_norm", (q,), ones, ("tp",))
        yield Leaf("k_norm", (kv,), ones, ("tp",))
    if cfg.index_topk:
        # the indexer: its heads' queries, its ONE key head with a LayerNorm
        # of its own, a weight a head; whole on every device
        Hi, Di = cfg.index_heads, cfg.index_head_dim
        yield Leaf("wq_idx", (M, Hi * Di), normal())
        yield Leaf("wk_idx", (M, Di), normal())
        yield Leaf("k_idx_norm", (Di,), ones)
        yield Leaf("k_idx_norm_bias", (Di,), zeros)
        yield Leaf("w_idx", (M, Hi), normal())
    if cfg.post_norm:
        yield Leaf("ln1_post", (M,), ones)


#: a layer of the default pattern: the whole causal triangle, with rope
_PLAIN_LAYER = (None, True)


def _index_inputs(p, h, positions, cfg: TransformerConfig):
    """The indexer's queries ``[B, S, Hi, Di]``, its one key head ``[B, S,
    Di]`` and its heads' weights ``[B, S, Hi]`` (float32, times ``Hi ** -0.5
    * Di ** -0.5``) of the block's normed input ``h``, which it reads behind
    a ``stop_gradient``: the key through a LayerNorm with bias, rope over
    the whole index head on both."""
    B, S, _ = h.shape
    Hi, Di = cfg.index_heads, cfg.index_head_dim
    with scopes.scope(scopes.ATTENTION_INDEX):
        h = lax.stop_gradient(h)
        qi = (h @ p["wq_idx"].astype(h.dtype)).reshape(B, S, Hi, Di)
        ki = layernorm(h @ p["wk_idx"].astype(h.dtype), p["k_idx_norm"],
                       p["k_idx_norm_bias"], cfg.norm_eps)
        qi = rope(qi, positions, cfg.rope_theta)
        ki = rope(ki[:, :, None], positions, cfg.rope_theta)[:, :, 0]
        w = (h @ p["w_idx"].astype(h.dtype)).astype(jnp.float32) * (
            Hi ** -0.5 * Di ** -0.5)
        return qi, ki, w


def _attention_block(p, x, positions, cfg: TransformerConfig,
                     kind=_PLAIN_LAYER):
    """The attention sublayer's new residual (:func:`_attention_sublayer`
    without its terms)."""
    return _attention_sublayer(p, x, positions, cfg, kind)[0]


def _attention_sublayer(p, x, positions, cfg: TransformerConfig,
                        kind=_PLAIN_LAYER):
    """x: [B', S', M] local. Heads sharded over tp; sequence over sp.
    ``kind``: the layer's (window or None, rope or not or a
    :class:`Rope` table of the kind's own); the block's query heads are its
    ``wq``'s, and with a ``wg`` its core's output is gated a head, with a
    ``wq`` twice as wide as ``wo`` is tall a channel. Returns
    (the new residual, the sublayer's auxiliary terms: None, or with a
    learned index (``cfg.index_topk``) its ``index_loss``, the
    ``selected_keys`` a query attended on average and the ``selection``'s
    bits)."""
    B, S, M = x.shape
    terms = None
    if cfg.index_topk and (_axis_live("sp") or _axis_live("tp")):
        raise NotImplementedError(
            "a learned index over the keys (index_topk) on a live sp or tp "
            "axis: a query's selection is over every causal key, which an "
            "sp shard does not hold, and one set for all the heads, which a "
            "tp shard's heads would have to agree on (the heads' mean "
            "attention, the indexer's target, is a sum over tp)")
    window, roped = kind[:2]
    grouped = cfg.kv_heads != cfg.n_heads
    if _axis_live("sp") and (window is not None or grouped
                             or cfg.attention_scale is not None):
        raise NotImplementedError(
            "a window (layer_pattern), grouped heads (n_kv_heads) or "
            "attention_scale on a live sp axis: ring_attention_spmd passes "
            "whole k/v blocks of n_heads heads round the ring, masks by the "
            "diagonal only and scales by 1 / sqrt(head width)")
    with scopes.scope(scopes.ATTENTION):
        h = rmsnorm(x, p["ln1"], cfg.norm_eps, cfg.zero_centred_norms)
        q = (h @ p["wq"].astype(h.dtype))
        channel_gate = None
        if p["wq"].shape[-1] == 2 * p["wo"].shape[-2]:
            q = q.reshape(B, S, -1, 2 * cfg.head_dim)
            q, channel_gate = (q[..., :cfg.head_dim].reshape(B, S, -1),
                               q[..., cfg.head_dim:])
        k = (h @ p["wk"].astype(h.dtype))
        v = (h @ p["wv"].astype(h.dtype))
        if cfg.qk_norm is True:
            q = _qk_norm(q, p["q_norm"], cfg.norm_eps)
            k = _qk_norm(k, p["k_norm"], cfg.norm_eps)
        Hl = q.shape[-1] // cfg.head_dim
        q = q.reshape(B, S, Hl, cfg.head_dim)
        k = k.reshape(B, S, k.shape[-1] // cfg.head_dim, cfg.head_dim)
        v = v.reshape(B, S, v.shape[-1] // cfg.head_dim, cfg.head_dim)
        if cfg.qk_norm == "head":
            q = rmsnorm(q, p["q_norm"], cfg.norm_eps, cfg.zero_centred_norms)
            k = rmsnorm(k, p["k_norm"], cfg.norm_eps, cfg.zero_centred_norms)
        if roped:
            table = roped if isinstance(roped, Rope) else cfg.rope_theta
            q = rope(q, positions, table)
            k = rope(k, positions, table)
        # the core is the call a kernel replaces: its custom_vjp backward
        # is traced under the same scope
        with scopes.scope(scopes.ATTENTION_CORE):
            if cfg.index_topk:
                pass    # below: the index's scopes are the core's siblings
            elif _axis_live("sp"):
                o = ring_attention_spmd(q, k, v, "sp", causal=True)
            else:
                # pallas flash kernel on TPU when tiling permits, XLA
                # otherwise; a stack of several kinds says which kind a
                # call is of
                from horovod_tpu.ops.pallas_attention import attend
                with (contextlib.nullcontext()
                      if cfg.layer_pattern == (_PLAIN_LAYER,)
                      else scopes.scope(scopes.ATTENTION_CORE_FULL
                                        if window is None else
                                        scopes.ATTENTION_CORE_WINDOW)):
                    o = attend(q, k, v, causal=True, window=window,
                               scale=cfg.attention_scale)
        if cfg.index_topk:
            from horovod_tpu.ops.sparse_attention import indexed_attention
            o, index_loss, selected, bits = indexed_attention(
                q, k, v, *_index_inputs(p, h, positions, cfg),
                cfg.index_topk, cfg.attention_scale or cfg.head_dim ** -0.5)
            terms = {"index_loss": index_loss, "selected_keys": selected,
                     "selection": bits}
        if "wg" in p:
            with scopes.scope(scopes.ATTENTION_GATE):
                gate = jax.nn.sigmoid(
                    (h @ p["wg"].astype(h.dtype)).astype(jnp.float32))
                o = o * gate[..., None].astype(o.dtype)
        if channel_gate is not None:
            with scopes.scope(scopes.ATTENTION_GATE):
                o = o * jax.nn.sigmoid(channel_gate.astype(jnp.float32)
                                       ).astype(o.dtype)
        o = o.reshape(B, S, Hl * cfg.head_dim) @ p["wo"].astype(x.dtype)
        o = _psum_if(o, "tp")
        if cfg.post_norm:
            o = rmsnorm(o, p["ln1_post"], cfg.norm_eps)
        return x + scaled(o, cfg.residual_scale), terms


def _routed(cfg: TransformerConfig, routed: Optional[bool]) -> bool:
    """Whether an FFN block is the expert layer: what its kind says
    (("experts",) yes, ("dense",) no), else whether the config has
    experts."""
    return cfg.n_experts > 0 if routed is None else routed


def _ffn_leaves(cfg: TransformerConfig, routed: Optional[bool] = None):
    M, E, held = cfg.d_model, cfg.n_experts, cfg.held_experts
    routed = _routed(cfg, routed)
    F = cfg.d_ff if routed or cfg.dense_ff is None else cfg.dense_ff
    shared = cfg.moe_shared_width
    yield Leaf("ln2", (M,), zeros if cfg.zero_centred_norms else ones)
    if cfg.post_norm:
        yield Leaf("ln2_post", (M,), ones)
    if routed:
        # we1 is the gate of a gated expert, we3 its up projection, we2
        # the way back down (the Mixtral numbering); the experts held
        # here lead, the router scores them all
        yield Leaf("router", (M, E), normal(0.02))
        yield Leaf("we1", (held, M, F), normal(), ("ep", None, "tp"))
        yield Leaf("we2", (held, F, M), normal(), ("ep", "tp", None))
        if cfg.moe_gated:
            yield Leaf("we3", (held, M, F), normal(), ("ep", None, "tp"))
        if cfg.moe_router_scores == "sigmoid":
            # the correction of the choice: a buffer, held at zero (what
            # moves it in training is no gradient and not implemented)
            yield Leaf("router_bias", (E,), zeros)
        if shared:
            yield Leaf("ws1", (M, shared), normal(), (None, "tp"))
            yield Leaf("ws2", (shared, M), normal(), ("tp", None))
            if cfg.moe_gated:
                yield Leaf("ws3", (M, shared), normal(), (None, "tp"))
            if cfg.moe_shared_gate:
                yield Leaf("ws_gate", (M, 1), normal())
    else:
        # w1 is the gate of a gated FFN and w3 its up projection, as the
        # experts number theirs
        yield Leaf("w1", (M, F), normal(), (None, "tp"))
        yield Leaf("w2", (F, M), normal(), ("tp", None))
        if cfg.ffn_gated:
            yield Leaf("w3", (M, F), normal(), (None, "tp"))


def _dense_ffn(p, x, cfg: TransformerConfig):
    h = x @ p["w1"].astype(x.dtype)
    if cfg.ffn_gated:
        h = jax.nn.silu(h) * (x @ p["w3"].astype(x.dtype))
    else:
        h = jax.nn.gelu(h)
    o = h @ p["w2"].astype(x.dtype)
    return _psum_if(o, "tp")


def _router_logits(p, x):
    """The router's float32 logits ``[B' * S', E]`` of ``x`` ``[B', S',
    M]``, for a router that reads something other than the experts'
    tokens (``moe_router_input``), and for one with sigmoid scores. The
    residual stream is not normed, so the logits are as large as it is and
    a top-k weight moves with their absolute error; and of 128 sigmoid
    scores the 6th and 7th lie some 0.08 logits apart, where a bfloat16
    pass over the router's weights moves a logit by 0.002: the product is
    float32 in fact ("highest": a TPU multiplies float32 operands as
    bfloat16 otherwise), on a matmul of ``E`` columns."""
    with scopes.scope(scopes.MOE), scopes.scope(scopes.MOE_ROUTER):
        return jnp.matmul(x.reshape(-1, x.shape[-1]).astype(jnp.float32),
                          p["router"].astype(jnp.float32),
                          precision=lax.Precision.HIGHEST)


def _moe_ffn(p, x, cfg: TransformerConfig, logits=None):
    """x: [B', S', M] local → tokens [G, M]; experts over ep, inner mats tp.
    ``logits``: the router's, where it does not read ``x``. Returns the
    layer's output and its auxiliary terms (:func:`_no_aux`'s keys and the
    layer's metrics)."""
    B, S, M = x.shape
    toks = x.reshape(B * S, M)
    if cfg.moe_activation == ("relu2" if cfg.moe_gated else "relu"):
        raise NotImplementedError(
            f"moe_activation={cfg.moe_activation!r} with moe_gated="
            f"{cfg.moe_gated}: \"silu\" and \"relu\" name a gated expert's "
            "gate activation, \"relu2\" an ungated expert's")
    gate = jax.nn.relu if cfg.moe_activation == "relu" else jax.nn.silu

    def ungated(h):
        if cfg.moe_activation == "relu2":
            return jnp.square(jax.nn.relu(h))
        return jax.nn.gelu(h)

    def expert_fn(ep, rows, group_sizes):
        # fewer groups than the router has columns (a share, a live ep
        # axis): the rows behind them are not this device's to compute
        return expert_ffn(rows, ep["we1"], ep.get("we3"), ep["we2"],
                          group_sizes, rows_held(group_sizes, cfg.n_experts),
                          gate if cfg.moe_gated else ungated)

    if logits is None and cfg.moe_router_scores == "sigmoid":
        logits = _router_logits(p, x)
    with scopes.scope(scopes.MOE):
        y, m = moe_layer_spmd(
            toks, p["router"], expert_fn,
            {leaf.name: p[leaf.name] for leaf in _ffn_leaves(cfg)
             if "ep" in leaf.spec},
            axis_name="ep" if _axis_live("ep") else None,
            k=cfg.moe_top_k, renormalize=cfg.moe_renormalize,
            stat_axes=[a for a in ("dp", "ep", "sp") if _axis_live(a)],
            logits=logits, share=cfg.expert_share,
            scores=cfg.moe_router_scores, bias=p.get("router_bias"),
            scale=cfg.moe_routed_scale)
        if cfg.moe_shared_width:
            y = y + _shared_expert(p, toks, gate if cfg.moe_gated
                                   else ungated)
        y = _psum_if(y, "tp")
    metrics = m._asdict()
    if cfg.expert_share == (0, 1):
        del metrics["held_rows"]    # every assignment: nothing to report
    aux = {"aux_loss": (cfg.moe_balance_weight * m.load_balance_loss
                        + cfg.moe_z_weight * m.router_z_loss),
           **metrics}
    return y.reshape(B, S, M), aux


def _shared_expert(p, toks, activation):
    """The expert every token runs, of the routed experts' form:
    ``down(activation(up(toks)))``, or with a ``ws3`` (gated experts)
    ``down(activation(gate(toks)) * up(toks))``, ``ws1`` the gate; dense
    matmuls over all the tokens (inner width over tp, the caller's psum);
    the same on every device that holds a share of the others. With a
    ``ws_gate`` (``moe_shared_gate``) its output times the sigmoid of one
    logit a token, ``toks . ws_gate``."""
    with scopes.scope(scopes.MOE_SHARED):
        h = activation(toks @ p["ws1"].astype(toks.dtype))
        if "ws3" in p:
            h = h * (toks @ p["ws3"].astype(toks.dtype))
        out = h @ p["ws2"].astype(toks.dtype)
        if "ws_gate" in p:
            logit = jnp.matmul(toks, p["ws_gate"].astype(toks.dtype),
                               preferred_element_type=jnp.float32)
            out = out * jax.nn.sigmoid(logit).astype(out.dtype)
        return out


def _no_aux():
    return {"aux_loss": jnp.zeros((), jnp.float32)}


def _over_layers(auxs):
    """One layer's auxiliary terms stacked ``[L]`` → the step's: the
    losses averaged, the largest load, the dropped assignments summed
    (the tokens' choices are :func:`router_choices`' to return)."""
    how = {"max_expert_load": jnp.max, "dropped": jnp.sum,
           "held_rows": jnp.sum, "index_loss": jnp.sum,
           "delta_min_log_decay": jnp.min}
    return {k: how.get(k, jnp.mean)(v) for k, v in auxs.items()
            if k not in ("experts", "selection")}


def _ffn_block(p, x, cfg: TransformerConfig, logits=None, routed=None):
    """``x + ffn(norm(x))``, the FFN dense or the experts (:func:`_routed`);
    ``logits``: a router's that read something else than the normed
    tokens."""
    with scopes.scope(scopes.MLP):
        h = rmsnorm(x, p["ln2"], cfg.norm_eps, cfg.zero_centred_norms)
        if _routed(cfg, routed):
            o, aux = _moe_ffn(p, h, cfg, logits)
        else:
            o, aux = _dense_ffn(p, h, cfg), _no_aux()
        o = o.astype(x.dtype)
        if cfg.post_norm:
            o = rmsnorm(o, p["ln2_post"], cfg.norm_eps)
        return x + scaled(o, cfg.residual_scale), aux


def _attention_then_ffn(p, x, positions, cfg: TransformerConfig, kind):
    """The block of two sublayers, ``kind`` its attention's (window, rope):
    the attention row's function, then the FFN row's."""
    logits = None
    if cfg.n_experts > 0 and cfg.moe_router_input == "block_input":
        # before attention, from the residual as it comes in: nothing of
        # this block stands between the choice and its experts' weights
        logits = _router_logits(p, x)
    x, terms = _attention_sublayer(p, x, positions, cfg, kind)
    x, aux = _ffn_block(p, x, cfg, logits)
    return x, {**aux, **(terms or {})}


def _kept_across(cfg: TransformerConfig) -> Dict:
    """``jax.checkpoint``'s arguments for a block of ``cfg``: nothing (every
    config's checkpoint as it was), or with a learned index the policy that
    keeps the sparse core's output and its selection across the block's
    checkpoint (``ops/sparse_attention.py:KEPT``: 134 + 33.5 MB a layer at
    16 384 tokens), so that the block's second run selects nothing and
    attends to nothing again: the backward pass has the selection, and each
    block of rows makes its own scores and softmax again as it must."""
    if not cfg.index_topk:
        return {}
    from horovod_tpu.ops.sparse_attention import KEPT
    return {"policy": jax.checkpoint_policies.save_only_these_names(*KEPT)}


def _needs_experts(cfg: TransformerConfig) -> None:
    if not cfg.n_experts:
        raise ValueError("layer_pattern has (\"experts\",) blocks and "
                         "n_experts=0")


#: the kinds of block of ONE sublayer, by the word a kind of
#: ``layer_pattern`` starts with: the one place that says which exist. A new
#: mixer is its config fields, scopes, leaves and function, and a row here.
_BLOCK_KINDS = {
    "mamba": mamba.KIND,
    "latent": latent.KIND,
    "conv": short_conv.KIND,
    "delta": delta.KIND,
    "experts": BlockKind(
        length=1, leaves=_ffn_leaves, validate=_needs_experts,
        apply=lambda p, x, positions, cfg, kind: _ffn_block(p, x, cfg)),
    "dense": BlockKind(
        length=1, leaves=functools.partial(_ffn_leaves, routed=False),
        apply=lambda p, x, positions, cfg, kind: (
            _ffn_block(p, x, cfg, routed=False)[0], None)),
    "attention": BlockKind(
        length=3, optional=2, leaves=_attention_leaves,
        apply=lambda p, x, positions, cfg, kind: (
            _attention_block(p, x, positions, cfg, kind[1:]), None)),
}

#: a kind (window, rope) that starts with no word: the attention row and the
#: FFN row in one block, their leaves in one stack
_TWO_SUBLAYERS = BlockKind(
    length=2, apply=_attention_then_ffn,
    leaves=lambda cfg: (*_BLOCK_KINDS["attention"].leaves(cfg),
                        *_BLOCK_KINDS["experts"].leaves(cfg)))


def _stack_of(kind) -> Optional[str]:
    """The stack of ``layers`` a block of ``kind`` is in: its word's, or the
    one unnamed stack (None) of two-sublayer blocks; a kind that gives
    fields which change a leaf's shape (:func:`_kind_fields`) is in a stack
    of the word and those (``attention_64_gated``)."""
    if not isinstance(kind[0], str):
        return None
    heads, gated = _kind_fields(kind)
    return "_".join([kind[0]] + [str(heads)] * (heads is not None)
                    + ["gated"] * bool(gated)
                    + ["channel"] * (gated == "channel"))


def _row(kind) -> BlockKind:
    """The row of ``_BLOCK_KINDS`` a block of ``kind`` is of, its leaves
    those of the kind where the kind changes them."""
    if not isinstance(kind[0], str):
        return _TWO_SUBLAYERS
    row = _BLOCK_KINDS[kind[0]]
    if _kind_fields(kind) != (None, False):
        row = row._replace(leaves=functools.partial(row.leaves, kind=kind))
    return row


def _block(p, x, positions, cfg: TransformerConfig, kind=_PLAIN_LAYER):
    """One block of ``kind`` on ``x``; returns (the new residual, the
    block's auxiliary terms, None where it has none to stack)."""
    return _row(kind).apply(p, x, positions, cfg, kind)


# ---------------------------------------------------------------------------
# Parameter init (host-side, then device_put with shardings)
# ---------------------------------------------------------------------------

def _model_leaves(cfg: TransformerConfig):
    """The leaves beside the stack of blocks, drawn after it."""
    M, V = cfg.d_model, cfg.vocab_size
    yield Leaf("embed", (V, M), normal(0.02), ("tp",))
    yield Leaf("ln_f", (M,), zeros if cfg.zero_centred_norms else ones)
    if not cfg.tie_embeddings:
        yield Leaf("lm_head", (M, V), normal(), (None, "tp"))
    if cfg.n_loops > 1:
        # Linear(M -> 1), read on every loop step's state
        yield Leaf("exit_gate", (M, 1), normal())
        yield Leaf("exit_gate_bias", (1,), zeros)


def _mtp_leaves(cfg: TransformerConfig):
    """The prediction module's leaves beside its blocks (``params["mtp"]``):
    the norms of its two inputs, the projection of their concatenation
    ``[state ; next token's embedding]``, and the norm before the main
    head."""
    M = cfg.d_model
    yield Leaf("norm_h", (M,), ones)
    yield Leaf("norm_e", (M,), ones)
    yield Leaf("proj", (2 * M, M), normal())
    yield Leaf("ln_f", (M,), ones)


def _stacks(cfg: TransformerConfig, layers: int = 0, pattern=None) -> Dict:
    """The stacks of blocks a part of the tree holds (``layers``; ``lead``
    and the prediction module's by their ``pattern``), in the order the
    pattern first names them: {:func:`_stack_of` its kinds: (their row, how
    many of ``layers`` consecutive layers are blocks of it)}."""
    pattern = cfg.layer_pattern if pattern is None else pattern
    if _stack_of(pattern[0]) is None:
        return {None: (_TWO_SUBLAYERS, layers)}
    of = [_stack_of(kind) for kind in pattern]
    first = {}
    for key, kind in zip(of, pattern):
        first.setdefault(key, kind)
    return {key: (_row(kind), layers // len(of) * of.count(key))
            for key, kind in first.items()}


def _build_tree(cfg: TransformerConfig, n_stages: int, leaf_of) -> Dict:
    """The parameter tree with ``leaf_of(leaf, stack)`` at every leaf, in
    ``init_params``' order of draws. ``stack``: None for a leaf in no
    stack, else (the stack's word, its row, its leading dimensions:
    ``(stages, blocks a stage)`` in ``layers``, ``(blocks,)`` beside it)."""
    def stacks_of(pattern, *lead):
        stacks = {
            key: {leaf.name: leaf_of(leaf, (key, row, lead[:-1] + (blocks,)))
                  for leaf in row.leaves(cfg)}
            for key, (row, blocks) in _stacks(cfg, lead[-1], pattern).items()}
        # (two-sublayer blocks are one unnamed stack: its leaves, bare)
        return stacks.get(None, stacks)
    layers = stacks_of(cfg.layer_pattern, n_stages, cfg.n_layers // n_stages)
    tree = {leaf.name: leaf_of(leaf, None) for leaf in _model_leaves(cfg)}
    tree["layers"] = layers
    if cfg.lead_pattern:
        tree["lead"] = stacks_of(cfg.lead_pattern, len(cfg.lead_pattern))
    if cfg.mtp_depth:
        tree["mtp"] = {
            **{leaf.name: leaf_of(leaf, None) for leaf in _mtp_leaves(cfg)},
            "layers": stacks_of(cfg.layer_pattern, len(cfg.layer_pattern))}
    return tree


def init_params(rng: np.random.RandomState, cfg: TransformerConfig,
                n_stages: int = 1) -> Dict:
    """Initialize parameters in the stacked-stage layout ``[pp, L/pp, ...]``;
    a pattern of one-sublayer blocks has one such stack a word under
    ``layers`` (``layers["mamba"]["ssm_in"]`` ``[pp, blocks / pp, ...]``).
    ``lead_pattern``'s blocks are ``lead``, a stack a word ``[blocks of that
    word, ...]``; the prediction module is ``mtp``: its own leaves
    (:func:`_mtp_leaves`) and ``mtp["layers"]``, one period of the pattern
    in stacks ``[blocks, ...]``."""
    assert cfg.n_layers % n_stages == 0, (cfg.n_layers, n_stages)
    return _build_tree(
        cfg, n_stages, lambda leaf, stack: leaf.draw(
            rng, (stack[2] if stack else ()) + leaf.shape))


def param_shardings(cfg: TransformerConfig, mesh: Mesh) -> Dict:
    """NamedSharding tree matching :func:`init_params` layout."""
    live = {a: a if mesh.shape.get(a, 1) > 1 else None
            for a in ("sp", "pp", "tp", "ep")}
    if live["tp"] and cfg.kv_heads % mesh.shape["tp"]:
        raise ValueError(
            f"tp={mesh.shape['tp']} does not divide n_kv_heads="
            f"{cfg.kv_heads}: a tp shard holds whole k/v heads")
    if live["ep"] and cfg.expert_share != (0, 1):
        raise ValueError(
            f"expert_share={cfg.expert_share} on a mesh with a live ep "
            "axis: a device holds its experts by its place on the axis or "
            "by being told, not both")
    if live["pp"] and (cfg.n_layers // mesh.shape["pp"]
                       ) % len(cfg.layer_pattern):
        raise ValueError(
            f"layer_pattern of {len(cfg.layer_pattern)} kinds on pp="
            f"{mesh.shape['pp']}: a stage of "
            f"{cfg.n_layers // mesh.shape['pp']} layers is not whole periods")
    cut = [axis for axis in ("sp", "tp", "pp") if live[axis]]
    if cfg.index_topk and cut:
        raise NotImplementedError(
            f"index_topk={cfg.index_topk} on a live {' / '.join(cut)} axis: "
            "a query's selection is over every causal key (an sp shard "
            "holds a part), one set for all the heads (a tp shard holds "
            "some), and the pipeline's schedule carries one auxiliary "
            "column, the experts', not the indexers' loss")
    beside = [field for field in ("lead_pattern", "mtp_depth")
              if getattr(cfg, field)]
    split = [axis for axis in ("sp", "pp") if live[axis]]
    if beside and split:
        raise NotImplementedError(
            f"{' and '.join(beside)} on a live {' / '.join(split)} axis: "
            "the leading blocks and the prediction module are whole on "
            "every device and belong to no stage of the pipeline's "
            "schedule, and the module's targets are the sequence moved by "
            "one more position, across the sp shards' edges")

    def sharding(leaf: Leaf, stack):
        spec = tuple(axis and live[axis] for axis in leaf.spec)
        if stack is None:
            return NamedSharding(mesh, P(*spec))
        word, row, lead_shape = stack
        refused = [axis for axis in row.refuses if live[axis]]
        if refused:
            raise NotImplementedError(
                f"a (\"{word}\",) block of layer_pattern on a live "
                f"{' / '.join(refused)} axis: {row.refusal}")
        # [stage, block of the stage, ...] in ``layers``, the stages over
        # pp where the kind runs on one; [block, ...] beside it
        staged = len(lead_shape) == 2 and "pp" not in row.refuses
        lead = (live["pp"],) if staged else ()
        pad = (None,) * (len(lead_shape) - len(lead))
        return NamedSharding(mesh, P(*lead, *pad, *spec) if spec
                             else P(*lead))
    return _build_tree(cfg, 1, sharding)


def shard_params(params: Dict, cfg: TransformerConfig, mesh: Mesh) -> Dict:
    sh = param_shardings(cfg, mesh)
    return jax.tree_util.tree_map(
        lambda x, s: jax.device_put(jnp.asarray(x), s), params, sh)


# ---------------------------------------------------------------------------
# Forward + loss (SPMD body)
# ---------------------------------------------------------------------------

def forward_loss_spmd(params, tokens, targets, cfg: TransformerConfig):
    """Local shapes: tokens/targets [B', S']. Returns (loss, aux): ``aux``
    a dict with ``aux_loss``, the weighted auxiliary losses that training
    adds to the loss (0 for a dense model), and for an MoE model off the
    pipeline path its parts and counters: ``load_balance_loss``,
    ``router_z_loss``, ``max_expert_load``, ``dropped`` (always 0). A
    looped model's loss is the exit-weighted objective of
    :func:`_looped_loss`, and ``aux`` gains ``step_losses`` ``[T]``,
    ``exit_share`` ``[T]`` and ``gate_entropy``. With a prediction module
    (``mtp_depth``) the loss is ``main_loss + mtp_weight * mtp_loss``, both
    in ``aux``, and the counters are over its expert layer too."""
    S = tokens.shape[1]
    sp_idx = lax.axis_index("sp") if _axis_live("sp") else 0
    positions = sp_idx * S + jnp.arange(S)

    with scopes.scope(scopes.EMBED):
        x = _embed_lookup(params["embed"], tokens, cfg)         # [B,S,M]

    beside = bool(cfg.lead_pattern or cfg.mtp_depth)
    with scopes.scope(scopes.LAYERS):
        if cfg.n_loops > 1:
            x, aux_total = _loop_layers(params["layers"], params["ln_f"], x,
                                        positions, cfg)      # [T,B,S,M]
        elif beside:
            x, auxs = _lead_then_layers(params, x, positions, cfg)
        else:
            x, aux_total = _run_layers(params["layers"], x, positions, cfg)

    with scopes.scope(scopes.HEAD):
        if cfg.tie_embeddings:
            head = params["embed"].astype(cfg.dtype).T
        else:
            head = params["lm_head"].astype(cfg.dtype)
        if cfg.n_loops > 1:
            # every loop step's state is already through ln_f
            nll = jnp.stack([_head_xent(x[t], head, targets,
                                        cfg.logits_scale)
                             for t in range(cfg.n_loops)])      # [T,B,S]
            loss, exits = _looped_loss(_exit_gate(params, x), nll)
            aux_total = {**aux_total, **exits}
        else:
            state = x       # what a prediction module reads: before ln_f
            x = rmsnorm(x, params["ln_f"], cfg.norm_eps, cfg.zero_centred_norms)
            nll = _head_xent(x, head, targets, cfg.logits_scale)    # [B,S]
            loss = jnp.mean(nll)
    if beside:
        parts = {}
        if cfg.mtp_depth:
            with scopes.scope(scopes.MTP):
                mtp_loss, mtp_auxs = _mtp_loss(params, state, targets, head,
                                               positions, cfg)
            auxs = _join_aux(cfg, [(cfg.layer_pattern, auxs),
                                   (cfg.layer_pattern, mtp_auxs)])
            parts = {"main_loss": loss, "mtp_loss": mtp_loss}
            loss = loss + cfg.mtp_weight * mtp_loss
        aux_total = {**_over_layers(auxs), **parts}
    with scopes.scope(scopes.HEAD):
        # average over data-like axes so every shard reports the global
        # loss (ep subdivides the batch — see data_sharding_spec)
        for ax in ("dp", "ep", "sp"):
            if _axis_live(ax):
                loss = lax.pmean(loss, ax)
                aux_total = jax.tree_util.tree_map(
                    lambda a: lax.pmean(a, ax), aux_total)
    return loss, aux_total


def _mtp_input(params, state, targets, cfg: TransformerConfig):
    """What the prediction module's layer reads: ``[norm(h_i) ;
    norm(Emb(t_{i+1}))] W``, ``h`` the main stack's output before ``ln_f``
    (``state``), ``t_{i+1}`` the next token (``targets[i]``), two norms of
    the module's own and the main model's table."""
    mp = params["mtp"]
    with scopes.scope(scopes.MTP_PROJ):
        nxt = _embed_lookup(params["embed"], targets, cfg)
        return jnp.concatenate(
            [rmsnorm(state, mp["norm_h"], cfg.norm_eps),
             rmsnorm(nxt, mp["norm_e"], cfg.norm_eps)], -1
        ) @ mp["proj"].astype(state.dtype)


def _mtp_targets(targets):
    """The token after the next: ``targets`` one position on. The last
    position's lies beyond the sequence (:func:`_mtp_loss` leaves it out)."""
    return jnp.roll(targets, -1, axis=1)


def _mtp_loss(params, state, targets, head, positions, cfg):
    """The multi-token-prediction module's loss (DeepSeek-V3,
    arXiv:2412.19437 eq. 21-25, one module) and its blocks' auxiliary
    terms, stacked: :func:`_mtp_input`, one more period of the pattern
    with the module's own weights, a norm of its own and the MAIN head
    (``head``), for :func:`_mtp_targets`; the mean over the positions whose
    target lies in the sequence."""
    mp = params["mtp"]
    u = _mtp_input(params, state, targets, cfg)
    with scopes.scope(scopes.LAYERS):
        z, auxs = _scan_periods(u, mp["layers"], positions, cfg,
                                functools.partial(_checkpointed, cfg))
    with scopes.scope(scopes.HEAD):
        z = rmsnorm(z, mp["ln_f"], cfg.norm_eps)
        nll = _head_xent(z, head, _mtp_targets(targets), cfg.logits_scale)
        return jnp.mean(nll[:, :-1]), auxs


def _exit_gate(params, states):
    """The exit gate's logits ``z_t = h_t w + b`` ``[T, B, S]`` of the loop
    steps' normed states ``[T, B, S, M]``, in float32: a multiply and a
    sum, not a matmul the MXU would take in bfloat16."""
    with scopes.scope(scopes.LOOP_GATE):
        return (jnp.sum(states.astype(jnp.float32)
                        * params["exit_gate"][:, 0].astype(jnp.float32), -1)
                + params["exit_gate_bias"].astype(jnp.float32))


#: beta of the looped objective ``mean(sum_t p_t xent_t - beta H(p))``
EXIT_ENTROPY_WEIGHT = 0.1


def _looped_loss(z, nll):
    """The looped model's training objective (arXiv:2510.25741, stage I)
    from the gate's logits and the loop steps' per-token losses, both
    ``[T, B, S]``: ``lambda_t = sigmoid(z_t)``, the exit distribution
    ``p_t = lambda_t prod_{j<t}(1 - lambda_j)`` with the last step taking
    what is left (``lambda_T`` is not read), and
    ``mean(sum_t p_t nll_t - beta H(p))``, in log space and the dtype of
    ``z`` (float32). Returns (loss, what the step reports of it)."""
    with scopes.scope(scopes.LOOP_GATE):
        # log p_t = log lambda_t + sum_{j<t} log(1 - lambda_j)
        stay = jnp.cumsum(jax.nn.log_sigmoid(-z[:-1]), axis=0)
        stay = jnp.concatenate([jnp.zeros_like(z[:1]), stay])
        log_p = stay.at[:-1].add(jax.nn.log_sigmoid(z[:-1]))
        p = jnp.exp(log_p)
        entropy = -jnp.sum(p * log_p, axis=0)                   # [B,S]
        loss = jnp.mean(jnp.sum(p * nll, axis=0)
                        - EXIT_ENTROPY_WEIGHT * entropy)
        return loss, {"step_losses": jnp.mean(nll, axis=(1, 2)),
                      "exit_share": jnp.mean(p, axis=(1, 2)),
                      "gate_entropy": jnp.mean(entropy)}


def _loop_layers(lp, ln_f, x, positions, cfg: TransformerConfig):
    """``n_loops`` passes through the same stack of blocks, ``ln_f`` after
    each. Returns (every step's normed state ``[T, B, S, M]``, the
    auxiliary terms over all passes).

    Where the stack checkpoints its blocks (every looped config that leaves
    ``remat`` at None) the backward pass is :func:`_looped_stack`'s own, not
    the scans' transpose. Left to autodiff, a scan over loop steps around the
    scan over layers closes over the stacked parameters, and JAX transposes
    that as it must: the inner backward scan stacks a pass's weight gradients
    into a fresh ``[L, ...]`` set, the outer one carries a second set and
    adds the first into it whole, once a loop step (two float32 stacks of
    every layer parameter and ``n_loops`` adds of three stacks' traffic:
    42.6 of the Ouro cell's 431 ms a step, PERF.md section 6, PR 52). A
    weight's gradient is the sum over its uses, so the hand-written backward
    carries ONE such set and every pass adds its gradient into its layer's
    slice in place. An explicit ``remat=False`` keeps the scans and their
    transpose (every pass's activations stored): the path the other is
    tested against."""
    if _axis_live("pp"):
        raise NotImplementedError(
            "a looped stack (n_loops > 1) on a live pp axis: the pipeline "
            "schedule would have to send the last stage's output, through "
            "ln_f, back to the first stage for every loop step and hand "
            "every step's state to the head; pipeline_spmd runs the stages "
            "once")

    def loop_step(h, _):
        y, auxs = _scan_layers(lp, h, positions, cfg)
        y = rmsnorm(y, ln_f, cfg.norm_eps)
        return y, (y, _over_layers(auxs))
    with scopes.scope(scopes.LOOP):
        if all(remat(cfg, _checkpointed(cfg, kind))
               for kind in cfg.layer_pattern):
            flat = jax.tree_util.tree_map(
                lambda a: a.reshape((-1,) + a.shape[2:]), lp)
            # a stack a word, or the one unnamed stack (:func:`_stack_of`)
            stacks = flat if _stack_of(cfg.layer_pattern[0]) else {None: flat}
            states, auxs = _looped_stack(cfg, stacks, ln_f, x, positions)
            auxs = jax.vmap(_over_layers)(auxs)
        else:
            _, (states, auxs) = lax.scan(loop_step, x, None,
                                         length=cfg.n_loops)
    return states, _over_layers(auxs)


def _period_blocks(cfg: TransformerConfig):
    """The blocks of one period of ``cfg.layer_pattern``: [(kind, its
    stack's key in ``stacks``, how many blocks a period that stack holds,
    this block's place among them)]."""
    of = [_stack_of(kind) for kind in cfg.layer_pattern]
    return [(kind, key, of.count(key), of[:i].count(key))
            for i, (kind, key) in enumerate(zip(cfg.layer_pattern, of))]


def _period_params(cfg: TransformerConfig, stacks, i):
    """The parameters of period ``i``'s blocks, one tree a block, out of
    ``stacks`` ({stack's key: leaves ``[blocks of that stack, ...]``})."""
    return [jax.tree_util.tree_map(
        lambda a: lax.dynamic_index_in_dim(a, i * per + place,
                                           keepdims=False), stacks[key])
        for _kind, key, per, place in _period_blocks(cfg)]


def _period(cfg: TransformerConfig, positions, layer_ps, x):
    """``x`` through one period's blocks; returns (activations, the
    auxiliary terms of the blocks that have any, stacked)."""
    auxs = []
    for (kind, *_), layer_p in zip(_period_blocks(cfg), layer_ps):
        x, aux = _block(layer_p, x, positions, cfg, kind)
        auxs.append(aux)
    auxs = [aux for aux in auxs if aux is not None] or [_no_aux()]
    return x, jax.tree_util.tree_map(lambda *a: jnp.stack(a), *auxs)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _looped_stack(cfg: TransformerConfig, stacks, ln_f, x, positions):
    """``cfg.n_loops`` passes of ``x`` through the blocks of ``stacks``
    ({stack's key: leaves ``[blocks, ...]``}), ``ln_f`` after each, every
    block checkpointed: a scan over loop steps around a scan over periods.
    Returns (every step's normed state ``[T, B, S, M]``, every period's
    auxiliary terms ``[T, periods, blocks with any, ...]``).

    Stored for the backward pass: each period's input of each loop step
    ``[T, periods, B, S, M]`` (what ``jax.checkpoint`` of a block stores;
    of a period of several kinds the first block's alone) and each step's
    state before ``ln_f``. The backward pass walks the loop steps and the
    periods from the last, with ONE accumulator of ``stacks``' shapes and
    dtypes in its carry: a period's cotangent is pulled back through
    ``jax.checkpoint`` of the same :func:`_block`s (every kernel, norm and
    rope is the code the forward ran, and the second run carries JAX's own
    name, ``scopes.RECOMPUTED``, as a checkpointed block's does in a scan's
    transpose; the first run ``jax.vjp`` traces beside it has no reader and
    XLA drops it), and the period writes
    ``acc[l] + dw`` at ``acc[l]``, a dynamic-update-slice on the carry that
    XLA keeps in place. The additions are the transpose's, in its order (the last pass
    first, a layer's passes one at a time in the parameters' dtype), and
    ``ln_f``'s gradient is summed over the loop steps the same way."""
    return _looped_stack_fwd(cfg, stacks, ln_f, x, positions)[0]


def _looped_stack_fwd(cfg: TransformerConfig, stacks, ln_f, x, positions):
    periods = cfg.n_layers // len(cfg.layer_pattern)

    def loop_step(h, _):
        def period(h, i):
            y, auxs = _period(cfg, positions,
                              _period_params(cfg, stacks, i), h)
            return y, (h, auxs)
        y, (inputs, auxs) = lax.scan(period, h, jnp.arange(periods))
        state = rmsnorm(y, ln_f, cfg.norm_eps)
        return state, (state, auxs, inputs, y)
    _, (states, auxs, inputs, ys) = lax.scan(loop_step, x, None,
                                             length=cfg.n_loops)
    return (states, auxs), (stacks, ln_f, positions, inputs, ys)


def _looped_stack_bwd(cfg: TransformerConfig, res, cotangents):
    stacks, ln_f, positions, inputs, ys = res
    d_states, d_auxs = cotangents
    # a count or a choice (an integer term) has no cotangent to scan over
    d_auxs = {k: ct for k, ct in d_auxs.items()
              if ct.dtype != jax.dtypes.float0}

    def add_at(at, acc, g):
        return lax.dynamic_update_index_in_dim(
            acc, lax.dynamic_index_in_dim(acc, at, keepdims=False) + g, at, 0)

    def loop_step(carry, step):
        d_next, acc, d_ln_f = carry
        inputs_t, y, d_state, d_auxs_t = step
        _, pull_norm = jax.vjp(jax.checkpoint(
            lambda y, g: rmsnorm(y, g, cfg.norm_eps)), y, ln_f)
        # the state went to the head and into the next loop step
        d_y, d_g = pull_norm(d_next + d_state)

        def period(carry, step):
            d_y, acc = carry
            i, h, d_aux = step
            (_, aux), pull = jax.vjp(
                jax.checkpoint(functools.partial(_period, cfg, positions)),
                _period_params(cfg, stacks, i), h)
            d_ps, d_h = pull((d_y, {
                k: d_aux[k] if k in d_aux
                else np.zeros(v.shape, jax.dtypes.float0)
                for k, v in aux.items()}))
            acc = dict(acc)
            for (_kind, key, per, place), d_p in zip(_period_blocks(cfg),
                                                     d_ps):
                acc[key] = jax.tree_util.tree_map(
                    functools.partial(add_at, i * per + place),
                    acc[key], d_p)
            return (d_h, acc), None
        (d_h, acc), _ = lax.scan(
            period, (d_y, acc),
            (jnp.arange(inputs_t.shape[0]), inputs_t, d_auxs_t),
            reverse=True)
        return (d_h, acc, d_ln_f + d_g), None
    zeros = (jnp.zeros(ys.shape[1:], ys.dtype),
             *jax.tree_util.tree_map(jnp.zeros_like, (stacks, ln_f)))
    (d_x, d_stacks, d_ln_f), _ = lax.scan(
        loop_step, zeros, (inputs, ys, d_states, d_auxs), reverse=True)
    return d_stacks, d_ln_f, d_x, None


_looped_stack.defvjp(_looped_stack_fwd, _looped_stack_bwd)


def _run_layers(lp, x, positions, cfg: TransformerConfig):
    """The stack of blocks: the GPipe schedule over live pp stages, else
    one scan over all layers. Returns (activations, the step's auxiliary
    terms)."""
    B = x.shape[0]
    if _axis_live("pp"):
        from horovod_tpu.parallel.pipeline import (pipeline_spmd,
                                                   psum_cotangent)

        def stage_fn(stage_params, act_with_aux):
            # the weighted sum of the MoE's auxiliary losses rides as one
            # extra feature column of the activation, so that the carry
            # stays a single array (pipeline_spmd requirement); it
            # accumulates across stages and is read back after the pipeline
            act, aux_in = act_with_aux[..., :-1], act_with_aux[..., -1:]
            # a stage is whole periods (param_shardings), so its first
            # layer is of the pattern's first kind
            y, auxs = _scan_periods(act.astype(cfg.dtype), stage_params,
                                    positions, cfg, lambda kind: True)
            aux_out = aux_in + (jnp.sum(auxs["aux_loss"])
                                / max(cfg.n_layers, 1))
            return jnp.concatenate([y.astype(jnp.float32), aux_out], axis=-1)
        aux_col = jnp.zeros(x.shape[:-1] + (1,), jnp.float32)
        xa = jnp.concatenate([x.astype(jnp.float32), aux_col], -1)
        # the embedding is computed replicated over pp, but only stage 0
        # CONSUMES its output — without this, the lookup's gradient
        # contribution exists only on the pp-rank-0 shards and the
        # assembled embed gradient depends on which replica the
        # out_specs pick (pipeline.py module docstring)
        xa = psum_cotangent(xa, "pp")
        M = cfg.n_microbatches
        xm = xa.reshape((M, B // M) + xa.shape[1:])
        ym = pipeline_spmd(stage_fn, lp, xm, "pp")
        ya = ym.reshape((B,) + ym.shape[2:])
        x = ya[..., :-1].astype(cfg.dtype)
        aux_total = {"aux_loss": jnp.mean(ya[..., -1])}
    else:
        # no pipeline: scan all layers of the single stage
        x, auxs = _scan_layers(lp, x, positions, cfg)
        aux_total = _over_layers(auxs)
    return x, aux_total


def _scan_layers(lp, x, positions, cfg: TransformerConfig):
    """One scan over the blocks of ``lp`` (``[stage, layer, ...]`` leaves).
    Returns (activations, every layer's auxiliary terms stacked ``[L]``).
    Which blocks are checkpointed is :func:`_checkpointed`'s to say, unless
    ``cfg.remat`` says otherwise."""
    flat = jax.tree_util.tree_map(
        lambda a: a.reshape((-1,) + a.shape[2:]), lp)
    return _scan_periods(x, flat, positions, cfg,
                         functools.partial(_checkpointed, cfg))


def _checkpointed(cfg: TransformerConfig, kind) -> bool:
    """Whether the stack checkpoints a block of ``kind`` where ``cfg.remat``
    says nothing. A looped stack does every block (its passes' activations
    would not fit beside the weights), and so does a stack with a
    prediction module, whose layer's activations and second head's logits
    lie beside the stack's (with only its attention blocks checkpointed
    GLM-4.7-Flash's step of 8192 tokens asks for 16.2 GB: PERF.md section
    6, PR 43); else the kinds whose row says so."""
    return bool(cfg.n_loops > 1 or cfg.mtp_depth or _row(kind).checkpointed)


def _has_experts(cfg: TransformerConfig, pattern) -> bool:
    return cfg.n_experts > 0 and any(
        _stack_of(kind) in (None, "experts") for kind in pattern)


def _join_aux(cfg: TransformerConfig, parts):
    """Several runs' stacked auxiliary terms, ``[(pattern, terms)]``, as one
    stack a term: of the runs whose pattern has expert layers, where any of
    them has the term (a run without experts stacks a zero a period for the
    auxiliary loss); else, a mixer's own term, of every run that has it."""
    every = [aux for _pattern, aux in parts]
    routed = [aux for pattern, aux in parts if _has_experts(cfg, pattern)]
    terms = dict.fromkeys(k for aux in (routed or every)[:1] + every
                          for k in aux)

    def stacked(k):
        among = ([aux for aux in routed if k in aux]
                 or [aux for aux in every if k in aux])
        return jnp.concatenate([aux[k] for aux in among])
    return {k: stacked(k) for k in terms}


def _lead_then_layers(params, x, positions, cfg: TransformerConfig):
    """``lead_pattern``'s blocks, each once, then the periodic stack, all
    on one stage (no pp: ``param_shardings``). Returns (activations, the
    auxiliary terms of every layer that has any, stacked)."""
    parts = []
    if cfg.lead_pattern:
        x, auxs = _scan_periods(x, params["lead"], positions, cfg,
                                functools.partial(_checkpointed, cfg),
                                cfg.lead_pattern)
        parts.append((cfg.lead_pattern, auxs))
    x, auxs = _scan_layers(params["layers"], x, positions, cfg)
    return x, _join_aux(cfg, parts + [(cfg.layer_pattern, auxs)])


def _scan_periods(x, layers, positions, cfg: TransformerConfig, needed,
                  pattern=None):
    """``x`` through every layer of ``layers`` (leaves ``[L, ...]``), layer
    ``l`` a block of kind ``pattern[l % len(pattern)]``, checkpointed where
    ``cfg.remat`` says or, where it says nothing, ``needed(kind)``: a scan
    over periods with a period's layers unrolled inside, each with its
    static kind; a period of one is a scan over layers. A pattern of
    one-sublayer blocks has a stack a word (``layers[word]``, leaves
    ``[blocks of that word, ...]``), and a period's i-th block of a word
    takes the i-th of the period's layers in that stack. ``pattern``: the
    stack's where it is not ``cfg.layer_pattern`` (``lead_pattern``).
    Returns (activations, the auxiliary terms of every layer that has any,
    stacked)."""
    pattern = cfg.layer_pattern if pattern is None else pattern
    one_sublayer = _stack_of(pattern[0]) is not None

    def block_of(kind):
        def block(layer_p, x):
            return _block(layer_p, x, positions, cfg, kind)
        if not remat(cfg, needed(kind)):
            return block
        return jax.checkpoint(block, **_kept_across(cfg))
    blocks = {kind: block_of(kind) for kind in pattern}
    if len(pattern) == 1 and not one_sublayer:
        # the program of every config without a pattern, to the letter
        def scan_body(carry, layer_p):
            y, aux = blocks[pattern[0]](layer_p, carry)
            return y, aux
        return lax.scan(scan_body, x, layers)
    stacks = layers if one_sublayer else {None: layers}
    # a stack as [periods, the period's blocks in it, ...], and each block
    # of a period as (its stack, its place among those)
    periods = {
        key: jax.tree_util.tree_map(
            lambda a, per=per: a.reshape(
                (a.shape[0] // per, per) + a.shape[1:]), stacks[key])
        for key, (_kind, per) in _stacks(cfg, len(pattern), pattern).items()}
    of = [_stack_of(kind) for kind in pattern]
    places = [(key, of[:i].count(key)) for i, key in enumerate(of)]

    def period_body(carry, period_p):
        auxs = []
        for kind, (key, place) in zip(pattern, places):
            layer_p = jax.tree_util.tree_map(lambda a: a[place],
                                             period_p[key])
            carry, aux = blocks[kind](layer_p, carry)
            auxs.append(aux)
        auxs = [aux for aux in auxs if aux is not None]
        if not any("aux_loss" in aux for aux in auxs):
            auxs.append(_no_aux())
        # one stack a set of terms: the expert blocks', a mixer's own
        by_terms = {}
        for aux in auxs:
            by_terms.setdefault(tuple(aux), []).append(aux)
        return carry, {k: v for same in by_terms.values()
                       for k, v in jax.tree_util.tree_map(
                           lambda *a: jnp.stack(a), *same).items()}
    y, auxs = lax.scan(period_body, x, periods)
    return y, jax.tree_util.tree_map(
        lambda a: a.reshape((-1,) + a.shape[2:]), auxs)


def router_choices(params, tokens, cfg: TransformerConfig):
    """The experts the router of each layer chooses for each token,
    ``[L, B * S, k]``: the model's own blocks, on one device (no mesh). For
    diagnostics, such as telling what differing choices explain of an error
    against a reference (benchmarks/chip/tools/olmoe_routing.py)."""
    x = _embed_lookup(params["embed"], tokens, cfg)
    _x, auxs = _lead_then_layers(params, x, jnp.arange(tokens.shape[1]), cfg)
    return auxs["experts"]


def index_selections(params, tokens, cfg: TransformerConfig):
    """The keys each layer's index selects for each query, as bits ``[L, B,
    S, S // 8]`` (``jnp.packbits`` along the keys): the model's own blocks,
    on one device (no mesh), as :func:`router_choices` gives the experts
    (benchmarks/chip/tools/keye_vl2_precision.py)."""
    x = _embed_lookup(params["embed"], tokens, cfg)
    _x, auxs = _lead_then_layers(params, x, jnp.arange(tokens.shape[1]), cfg)
    return auxs["selection"]


# ---------------------------------------------------------------------------
# Jitted train/eval step factories
# ---------------------------------------------------------------------------

def data_sharding_spec(mesh: Mesh) -> P:
    """Batch dim shards over every live data-like axis (dp and — because
    expert parallelism subdivides the data-parallel groups, DeepSpeed-MoE
    style — ep); sequence dim over sp."""
    batch_axes = tuple(a for a in ("dp", "ep") if mesh.shape.get(a, 1) > 1)
    sp = "sp" if mesh.shape.get("sp", 1) > 1 else None
    return P(batch_axes if batch_axes else None, sp)


def _grad_sync(grads, pspec):
    """psum each gradient over the *data* axes (dp, ep, sp) its parameter is
    replicated over; axes present in the leaf's own sharding spec (tp/ep on
    sharded weights, pp on stages) keep shard-local gradients — the Megatron
    rule, and the in-graph analog of the reference's allreduce hooks
    (``torch/optimizer.py:164-206``)."""
    def one(g, spec):
        used = set()
        for part in spec:
            if part is None:
                continue
            if isinstance(part, (tuple, list)):
                used.update(part)
            else:
                used.add(part)
        for ax in ("dp", "ep", "sp"):
            if ax not in used:
                g = _psum_if(g, ax)
        return g
    with scopes.scope(scopes.GRAD_SYNC):
        return jax.tree_util.tree_map(one, grads, pspec)


def _objective(loss, aux):
    """What training descends: the loss, the weighted auxiliary losses and,
    of a stack with a learned index, the indexers' own (``index_loss``, the
    sum over the layers at weight 1: its gradient reaches the indexers'
    leaves alone, so the weight only scales their rate)."""
    total = loss + aux["aux_loss"]
    return total + aux["index_loss"] if "index_loss" in aux else total


def make_grad_fn(cfg: TransformerConfig, mesh: Mesh):
    """SPMD (loss, aux, grads) function over the mesh; grads come back with
    param shardings, ready for any optax optimizer applied under jit."""
    data_spec = data_sharding_spec(mesh)
    psh = param_shardings(cfg, mesh)
    pspec = jax.tree_util.tree_map(lambda s: s.spec, psh)

    @functools.partial(
        shard_map, mesh=mesh,
        in_specs=(pspec, data_spec, data_spec),
        out_specs=(P(), P(), pspec),
        check_vma=False)
    def grad_fn(params, tokens, targets):
        def loss_fn(p):
            loss, aux = forward_loss_spmd(p, tokens, targets, cfg)
            return _objective(loss, aux), (loss, aux)
        grads, (loss, aux) = jax.grad(loss_fn, has_aux=True)(params)
        grads = _grad_sync(grads, pspec)
        return loss, aux, grads

    return grad_fn


def make_train_step(cfg: TransformerConfig, mesh: Mesh, optimizer,
                    scan_steps: int = 1):
    """Jitted full train step: manual-SPMD fwd/bwd (shard_map) + optimizer
    update in GSPMD-auto mode (XLA keeps the elementwise update sharded as
    the params are).

    ``scan_steps > 1`` runs that many optimizer steps per call via
    ``lax.scan`` in ONE compiled program (one dispatch per chain; see
    ``make_resnet_train_step``). All scanned steps consume the SAME
    ``tokens``/``targets`` batch (``scan_util.multi_step`` same-batch
    semantics — a throughput construct, not multi-batch training).
    Returned loss/aux are the last step's.

    ``params``/``opt_state`` buffers are DONATED (in-place update on
    device): keep only the returned state — the inputs are invalidated
    after the call on TPU.

    Where a block kind's row says ``gradients_first`` (a stack with a Mamba
    block) every gradient is finished as an array of its own before the
    optimizer reads any; elsewhere XLA is free to fuse a weight's gradient
    into its update."""
    import optax
    grad_fn = make_grad_fn(cfg, mesh)

    gradients_first = any(_row(kind).gradients_first
                          for kind in cfg.lead_pattern + cfg.layer_pattern)

    def one_step(params, opt_state, tokens, targets):
        loss, aux, grads = grad_fn(params, tokens, targets)
        if gradients_first:
            # XLA:TPU otherwise fuses weight gradients into the update;
            # the kind's row says why this stack does without
            grads = lax.optimization_barrier(grads)
        with scopes.scope(scopes.OPTIMIZER):
            updates, opt_state = optimizer.update(grads, opt_state, params)
            params = optax.apply_updates(params, updates)
        return params, opt_state, loss, aux

    chain = multi_step(one_step, n_carry=2, scan_steps=scan_steps)

    @functools.partial(jax.jit, donate_argnums=(0, 1))
    def step(params, opt_state, tokens, targets):
        return chain(params, opt_state, tokens, targets)

    return step


def make_forward(cfg: TransformerConfig, mesh: Mesh):
    """Jitted forward (loss only) — used by ``__graft_entry__.entry``."""
    data_spec = data_sharding_spec(mesh)
    psh = param_shardings(cfg, mesh)
    pspec = jax.tree_util.tree_map(lambda s: s.spec, psh)

    @functools.partial(shard_map, mesh=mesh,
                       in_specs=(pspec, data_spec, data_spec),
                       out_specs=P(), check_vma=False)
    def fwd(params, tokens, targets):
        loss, aux = forward_loss_spmd(params, tokens, targets, cfg)
        return _objective(loss, aux)

    return jax.jit(fwd)


def init_opt_state(optimizer, params, mesh: Mesh, cfg=None):
    """Optimizer state placed as the train step will return it: a leaf
    that mirrors a parameter (adam moments) takes that parameter's
    sharding, anything else (step counts) is replicated on the mesh.

    Left to itself ``jax.jit(optimizer.init)`` puts its zeros on ONE
    device whatever the params' shardings are; the step then compiles
    twice — once for that placement, once for its own outputs' — and a
    dp mesh starts with the whole state on device 0."""
    import optax
    replicated = NamedSharding(mesh, P())
    shardings = optax.tree_utils.tree_map_params(
        optimizer, lambda _, p: p.sharding,
        jax.eval_shape(optimizer.init, params), params,
        transform_non_params=lambda _: replicated)
    return jax.jit(optimizer.init, out_shardings=shardings)(params)


def shard_batch(tokens, targets, mesh: Mesh):
    spec = data_sharding_spec(mesh)
    sh = NamedSharding(mesh, spec)
    return jax.device_put(tokens, sh), jax.device_put(targets, sh)
