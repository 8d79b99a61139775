"""Flagship model: GPT-style (optionally MoE) transformer with full 5-axis
parallelism — dp (batch), pp (stages), ep (experts), sp (sequence/ring
attention), tp (tensor) — written as ONE manual-SPMD program under
``shard_map`` over the canonical mesh.

The reference framework scales *batch only* (SURVEY.md §2.6); its model zoo
is "whatever TF/Torch model you wrap". This module is the TPU-native
counterpart of that contract at modern scale: the training step compiles to
a single XLA program whose collectives (psum over tp, ppermute rings over
sp and pp, all_to_all over ep, psum over dp for gradients) all ride ICI.

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
                  step's state goes to the head and the exit gate (no pp)
  layer kinds     ``layer_pattern``: one period of (window, rope) kinds; the
                  scan goes over periods, a period's layers unrolled inside
  one sublayer    a kind that starts with a word, ("mamba",), ("experts",),
                  ("attention", window, rope): ``x + mixer(norm(x))`` and no
                  more (Nemotron-H); the tree's ``layers`` then holds one
                  stack a word, ``[pp, blocks of that word / pp, ...]``
  mamba           a Mamba-2 mixer: projection, causal depthwise convolution,
                  the chunked scan with its carried state in float32, the
                  gated grouped norm; no sp, pp or tp
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

from horovod_tpu.models.scan_util import multi_step
from horovod_tpu.ops import pallas_ssm
from horovod_tpu.parallel.ring_attention import ring_attention_spmd
from horovod_tpu.parallel.moe import grouped_matmul, moe_layer_spmd
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
    qk_norm: bool = False       # RMSNorm over the whole q and k projections,
    #                             before the head split and rope
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
    #                             such kinds throughout or of none
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
    #                             runs beside its chosen ones (``ws1``,
    #                             ``ws2``; ``moe_activation``), whole on every
    #                             device that holds a share of the others
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
    expert_share: Tuple[int, int] = (0, 1)  # (index, of): this device holds
    #                             experts [index * E / of, (index + 1) * E /
    #                             of) of every layer, as the leading dimension
    #                             of we1 / we3 / we2, with no ep axis live;
    #                             the router keeps its n_experts columns and
    #                             the layer's output is those experts' part
    norm_eps: float = 1e-6
    rope_theta: float = 10000.0
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    n_microbatches: int = 1     # pipeline microbatches (per pp>1)
    remat: Optional[bool] = None    # jax.checkpoint each block (HBM for
    #                             FLOPs). None: where the architecture needs
    #                             it: the pipeline's stages, a looped stack
    #                             and a Mamba block yes, the single scan over
    #                             layers of any other kind no

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
        return _one_sublayer(self.layer_pattern)

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
        if self.moe_router_input not in ("tokens", "block_input"):
            raise ValueError(f"moe_router_input={self.moe_router_input!r}")
        if self.moe_activation not in ("silu", "relu", "relu2"):
            raise ValueError(f"moe_activation={self.moe_activation!r}")
        if self.moe_router_scores not in ("softmax", "sigmoid"):
            raise ValueError(
                f"moe_router_scores={self.moe_router_scores!r}")
        words = {kind[0] for kind in self.layer_pattern
                 if isinstance(kind[0], str)}
        if words and (not words <= set(_SUBLAYERS) or any(
                len(kind) != _SUBLAYERS.get(kind[0])
                for kind in self.layer_pattern)):
            raise ValueError(
                f"layer_pattern={self.layer_pattern}: a block of one "
                f"sublayer is one of {sorted(_SUBLAYERS)}, and a pattern is "
                "of such blocks throughout or of none")
        if "experts" in words and not self.n_experts:
            raise ValueError("layer_pattern has (\"experts\",) blocks and "
                             "n_experts=0")
        if "mamba" in words and (
                self.ssm_heads < 1 or self.ssm_heads % self.ssm_groups):
            raise ValueError(
                f"layer_pattern has (\"mamba\",) blocks: ssm_groups="
                f"{self.ssm_groups} does not divide ssm_heads="
                f"{self.ssm_heads}")


#: the blocks of one sublayer, and the length of each one's kind
_SUBLAYERS = {"mamba": 1, "experts": 1, "attention": 3}


def _one_sublayer(pattern) -> bool:
    """Whether a pattern's kinds start with a word (a valid pattern's do
    throughout or not at all)."""
    return isinstance(pattern[0][0], str)


# ---------------------------------------------------------------------------
# Parameter init (host-side, then device_put with shardings)
# ---------------------------------------------------------------------------

def _sublayer_counts(cfg: TransformerConfig) -> Dict[str, int]:
    """Blocks of each word in one period of a pattern of one-sublayer
    blocks, in the order the words first appear."""
    words = [kind[0] for kind in cfg.layer_pattern]
    return {word: words.count(word) for word in dict.fromkeys(words)}


def _attention_leaves(cfg: TransformerConfig, w, ones, lead) -> Dict:
    M, H, Dh, Hkv = cfg.d_model, cfg.n_heads, cfg.head_dim, cfg.kv_heads
    leaves = {
        "ln1": ones(*lead, M),
        "wq": w(*lead, M, H * Dh),
        "wk": w(*lead, M, Hkv * Dh),
        "wv": w(*lead, M, Hkv * Dh),
        "wo": w(*lead, H * Dh, M),
    }
    if cfg.qk_norm:
        leaves.update({"q_norm": ones(*lead, H * Dh),
                       "k_norm": ones(*lead, Hkv * Dh)})
    if cfg.post_norm:
        leaves["ln1_post"] = ones(*lead, M)
    return leaves


def _ffn_leaves(cfg: TransformerConfig, w, ones, lead) -> Dict:
    M, F = cfg.d_model, cfg.d_ff
    leaves = {"ln2": ones(*lead, M)}
    if cfg.post_norm:
        leaves["ln2_post"] = ones(*lead, M)
    if cfg.n_experts > 0:
        # we1 is the gate of a gated expert, we3 its up projection, we2
        # the way back down (the Mixtral numbering); the experts held
        # here lead, the router scores them all
        held = cfg.held_experts
        leaves.update({
            "router": w(*lead, M, cfg.n_experts, scale=0.02),
            "we1": w(*lead, held, M, F),
            "we2": w(*lead, held, F, M),
        })
        if cfg.moe_gated:
            leaves["we3"] = w(*lead, held, M, F)
        if cfg.moe_router_scores == "sigmoid":
            # the correction of the choice: a buffer, held at zero (what
            # moves it in training is no gradient and not implemented)
            leaves["router_bias"] = np.zeros((*lead, cfg.n_experts),
                                             np.float32)
        if cfg.moe_shared_width:
            leaves.update({"ws1": w(*lead, M, cfg.moe_shared_width),
                           "ws2": w(*lead, cfg.moe_shared_width, M)})
    else:
        # w1 is the gate of a gated FFN and w3 its up projection, as the
        # experts number theirs
        leaves.update({"w1": w(*lead, M, F), "w2": w(*lead, F, M)})
        if cfg.ffn_gated:
            leaves["w3"] = w(*lead, M, F)
    return leaves


#: the range a Mamba-2 head's time step ``softplus(dt_bias)`` is drawn
#: from, log-uniformly, its floor, and the range of ``-A`` (the reference
#: implementation's defaults, which Nemotron-H's config repeats)
SSM_DT_RANGE, SSM_DT_FLOOR, SSM_A_RANGE = (1e-3, 1e-1), 1e-4, (1.0, 16.0)


def _mamba_leaves(cfg: TransformerConfig, rng, w, ones, lead) -> Dict:
    """``ssm_in`` maps to ``[z | x B C | dt]``; the convolution's taps are
    ``[tap, channel]``, tap ``ssm_conv - 1`` on the current position."""
    M, H, inner, K = cfg.d_model, cfg.ssm_heads, cfg.ssm_inner, cfg.ssm_conv
    dt = np.exp(rng.uniform(*np.log(SSM_DT_RANGE), size=(*lead, H)))
    dt = np.maximum(dt, SSM_DT_FLOOR)

    def taps(*shape):
        return (rng.uniform(-1, 1, shape) / np.sqrt(K)).astype(np.float32)
    return {
        "ln1": ones(*lead, M),
        "ssm_in": w(*lead, M, inner + cfg.ssm_conv_width + H),
        "ssm_conv_w": taps(*lead, K, cfg.ssm_conv_width),
        "ssm_conv_b": taps(*lead, cfg.ssm_conv_width),
        # softplus(dt_bias) = dt
        "ssm_dt_bias": np.log(np.expm1(dt)).astype(np.float32),
        "ssm_a_log": np.log(rng.uniform(*SSM_A_RANGE, size=(*lead, H))
                            ).astype(np.float32),
        "ssm_d": ones(*lead, H),
        "ssm_norm": ones(*lead, inner),
        "ssm_out": w(*lead, inner, M),
    }


def init_params(rng: np.random.RandomState, cfg: TransformerConfig,
                n_stages: int = 1) -> Dict:
    """Initialize parameters in the stacked-stage layout ``[pp, L/pp, ...]``;
    a pattern of one-sublayer blocks has one such stack a word under
    ``layers`` (``layers["mamba"]["ssm_in"]`` ``[pp, blocks / pp, ...]``)."""
    L = cfg.n_layers
    assert L % n_stages == 0, (L, n_stages)
    lps = L // n_stages
    M = cfg.d_model

    def w(*shape, scale=None):
        scale = scale if scale is not None else (1.0 / np.sqrt(shape[-2]))
        return (rng.randn(*shape) * scale).astype(np.float32)

    def ones(*shape):
        return np.ones(shape, np.float32)

    if cfg.one_sublayer:
        layer = {}
        for word, count in _sublayer_counts(cfg).items():
            lead = (n_stages, lps // len(cfg.layer_pattern) * count)
            layer[word] = (
                _mamba_leaves(cfg, rng, w, ones, lead) if word == "mamba"
                else _ffn_leaves(cfg, w, ones, lead) if word == "experts"
                else _attention_leaves(cfg, w, ones, lead))
    else:
        layer = {**_attention_leaves(cfg, w, ones, (n_stages, lps)),
                 **_ffn_leaves(cfg, w, ones, (n_stages, lps))}
    params = {
        "embed": (rng.randn(cfg.vocab_size, M) * 0.02).astype(np.float32),
        "ln_f": np.ones((M,), np.float32),
        "layers": layer,
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = w(M, cfg.vocab_size)
    if cfg.n_loops > 1:
        # Linear(M -> 1), read on every loop step's state
        params["exit_gate"] = w(M, 1)
        params["exit_gate_bias"] = np.zeros((1,), np.float32)
    return params


def param_shardings(cfg: TransformerConfig, mesh: Mesh) -> Dict:
    """NamedSharding tree matching :func:`init_params` layout."""
    def s(*spec):
        return NamedSharding(mesh, P(*spec))
    tp = "tp" if mesh.shape.get("tp", 1) > 1 else None
    pp = "pp" if mesh.shape.get("pp", 1) > 1 else None
    ep = "ep" if mesh.shape.get("ep", 1) > 1 else None
    if tp and cfg.kv_heads % mesh.shape["tp"]:
        raise ValueError(
            f"tp={mesh.shape['tp']} does not divide n_kv_heads="
            f"{cfg.kv_heads}: a tp shard holds whole k/v heads")
    if ep and cfg.expert_share != (0, 1):
        raise ValueError(
            f"expert_share={cfg.expert_share} on a mesh with a live ep "
            "axis: a device holds its experts by its place on the axis or "
            "by being told, not both")
    if pp and (cfg.n_layers // mesh.shape["pp"]) % len(cfg.layer_pattern):
        raise ValueError(
            f"layer_pattern of {len(cfg.layer_pattern)} kinds on pp="
            f"{mesh.shape['pp']}: a stage of "
            f"{cfg.n_layers // mesh.shape['pp']} layers is not whole periods")
    live = [a for a in ("sp", "pp", "tp") if mesh.shape.get(a, 1) > 1]
    if live and ("mamba",) in cfg.layer_pattern:
        raise NotImplementedError(
            f"a (\"mamba\",) block of layer_pattern on a live "
            f"{' / '.join(live)} axis: the convolution and the scan's "
            "carried state run over the whole sequence on one device (no "
            "hand-over between sp shards), its heads and groups are not "
            "split over tp, and no pipeline schedule has run it")
    attention = {
        "ln1": s(pp),
        "wq": s(pp, None, None, tp), "wk": s(pp, None, None, tp),
        "wv": s(pp, None, None, tp), "wo": s(pp, None, tp, None),
    }
    ffn = {"ln2": s(pp)}
    if cfg.qk_norm:
        attention.update({"q_norm": s(pp, None, tp),
                          "k_norm": s(pp, None, tp)})
    if cfg.post_norm:
        attention["ln1_post"] = ffn["ln2_post"] = s(pp)
    if cfg.n_experts > 0:
        ffn.update({
            "router": s(pp),
            "we1": s(pp, None, ep, None, tp),
            "we2": s(pp, None, ep, tp, None),
        })
        if cfg.moe_gated:
            ffn["we3"] = s(pp, None, ep, None, tp)
        if cfg.moe_router_scores == "sigmoid":
            ffn["router_bias"] = s(pp)
        if cfg.moe_shared_width:
            ffn.update({"ws1": s(pp, None, None, tp),
                        "ws2": s(pp, None, tp, None)})
    else:
        ffn.update({"w1": s(pp, None, None, tp),
                    "w2": s(pp, None, tp, None)})
        if cfg.ffn_gated:
            ffn["w3"] = s(pp, None, None, tp)
    if cfg.one_sublayer:
        # (a Mamba block's leaves are whole on every device: no pp, tp)
        mamba = {name: s() for name in (
            "ln1", "ssm_in", "ssm_conv_w", "ssm_conv_b", "ssm_dt_bias",
            "ssm_a_log", "ssm_d", "ssm_norm", "ssm_out")}
        layers = {word: {"mamba": mamba, "experts": ffn,
                         "attention": attention}[word]
                  for word in _sublayer_counts(cfg)}
    else:
        layers = {**attention, **ffn}
    shardings = {"embed": s(tp), "ln_f": s(), "layers": layers}
    if not cfg.tie_embeddings:
        shardings["lm_head"] = s(None, tp)
    if cfg.n_loops > 1:
        shardings.update({"exit_gate": s(), "exit_gate_bias": s()})
    return shardings


def shard_params(params: Dict, cfg: TransformerConfig, mesh: Mesh) -> Dict:
    sh = param_shardings(cfg, mesh)
    return jax.tree_util.tree_map(
        lambda x, s: jax.device_put(jnp.asarray(x), s), params, sh)


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


def _rmsnorm(x, g, eps=1e-6):
    var = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=-1, keepdims=True)
    return (x.astype(jnp.float32) * jax.lax.rsqrt(var + eps)
            ).astype(x.dtype) * g.astype(x.dtype)


def _qk_norm(x, g, eps):
    """RMSNorm over the whole projection width, of which this tp shard
    holds ``x``'s last dimension."""
    xf = x.astype(jnp.float32)
    total = _psum_if(jnp.sum(jnp.square(xf), axis=-1, keepdims=True), "tp")
    width = x.shape[-1] * (axis_size("tp") if _axis_live("tp") else 1)
    return (xf * jax.lax.rsqrt(total / width + eps)
            ).astype(x.dtype) * g.astype(x.dtype)


def _rope(x, positions, theta=10000.0):
    """Rotary embedding, halves layout; x [B, S, H, D], positions [S]
    absolute."""
    B, S, H, D = x.shape
    half = D // 2
    freqs = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = positions[:, None].astype(jnp.float32) * freqs[None, :]  # [S, half]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :half], x[..., half:]
    cos = cos[None, :, None, :].astype(x.dtype)
    sin = sin[None, :, None, :].astype(x.dtype)
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


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
        if cfg.tie_embeddings:
            return emb_local.astype(cfg.dtype)[ids]
        return _table_rows(emb_local, ids, cfg.dtype)
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


def _head_xent(x, head, targets):
    """Per-token loss of the head ``x @ head``. A tp-sharded vocabulary
    takes the psum algebra above; a full local one takes the fused Pallas
    kernel, which leaves the logits' gradient in the logits' buffer for
    the two backward matmuls (one HBM pass over ``[N, V]``; falls back
    off-TPU / untiled by ``pallas_xent.xent_path``, the self-gating
    pattern of ``pallas_attention.attend``)."""
    if _axis_live("tp"):
        return _sharded_softmax_xent(x @ head, targets)       # [B,S,V/tp]
    from horovod_tpu.ops.pallas_xent import head_softmax_xent
    return head_softmax_xent(x, head, targets)


#: a layer of the default pattern: the whole causal triangle, with rope
_PLAIN_LAYER = (None, True)


def _attention_block(p, x, positions, cfg: TransformerConfig,
                     kind=_PLAIN_LAYER):
    """x: [B', S', M] local. Heads sharded over tp; sequence over sp.
    ``kind``: the layer's (window or None, rope or not)."""
    B, S, M = x.shape
    window, rope = kind
    grouped = cfg.kv_heads != cfg.n_heads
    if _axis_live("sp") and (window is not None or grouped):
        raise NotImplementedError(
            "a window (layer_pattern) or grouped heads (n_kv_heads) on a "
            "live sp axis: ring_attention_spmd passes whole k/v blocks of "
            "n_heads heads round the ring and masks by the diagonal only")
    with jax.named_scope(scopes.ATTENTION):
        h = _rmsnorm(x, p["ln1"], cfg.norm_eps)
        q = (h @ p["wq"].astype(h.dtype))
        k = (h @ p["wk"].astype(h.dtype))
        v = (h @ p["wv"].astype(h.dtype))
        if cfg.qk_norm:
            q = _qk_norm(q, p["q_norm"], cfg.norm_eps)
            k = _qk_norm(k, p["k_norm"], cfg.norm_eps)
        Hl = q.shape[-1] // cfg.head_dim
        q = q.reshape(B, S, Hl, cfg.head_dim)
        k = k.reshape(B, S, k.shape[-1] // cfg.head_dim, cfg.head_dim)
        v = v.reshape(B, S, v.shape[-1] // cfg.head_dim, cfg.head_dim)
        if rope:
            q = _rope(q, positions, cfg.rope_theta)
            k = _rope(k, positions, cfg.rope_theta)
        # the core is the call a kernel replaces: its custom_vjp backward
        # is traced under the same scope
        with jax.named_scope(scopes.ATTENTION_CORE):
            if _axis_live("sp"):
                o = ring_attention_spmd(q, k, v, "sp", causal=True)
            else:
                # pallas flash kernel on TPU when tiling permits, XLA
                # otherwise; a stack of several kinds says which kind a
                # call is of
                from horovod_tpu.ops.pallas_attention import attend
                with (contextlib.nullcontext()
                      if cfg.layer_pattern == (_PLAIN_LAYER,)
                      else jax.named_scope(scopes.ATTENTION_CORE_FULL
                                           if window is None else
                                           scopes.ATTENTION_CORE_WINDOW)):
                    o = attend(q, k, v, causal=True, window=window)
        o = o.reshape(B, S, Hl * cfg.head_dim) @ p["wo"].astype(x.dtype)
        o = _psum_if(o, "tp")
        if cfg.post_norm:
            o = _rmsnorm(o, p["ln1_post"], cfg.norm_eps)
        return x + o


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
    with jax.named_scope(scopes.MOE), jax.named_scope(scopes.MOE_ROUTER):
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
        h = grouped_matmul(rows, ep["we1"], group_sizes)
        if cfg.moe_gated:
            h = gate(h) * grouped_matmul(rows, ep["we3"], group_sizes)
        else:
            h = ungated(h)
        return grouped_matmul(h, ep["we2"], group_sizes)

    if logits is None and cfg.moe_router_scores == "sigmoid":
        logits = _router_logits(p, x)
    with jax.named_scope(scopes.MOE):
        y, m = moe_layer_spmd(
            toks, p["router"], expert_fn,
            {n: p[n] for n in ("we1", "we2", "we3") if n in p},
            axis_name="ep" if _axis_live("ep") else None,
            k=cfg.moe_top_k, renormalize=cfg.moe_renormalize,
            stat_axes=[a for a in ("dp", "ep", "sp") if _axis_live(a)],
            logits=logits, share=cfg.expert_share,
            scores=cfg.moe_router_scores, bias=p.get("router_bias"),
            scale=cfg.moe_routed_scale)
        if cfg.moe_shared_width:
            y = y + _shared_expert(p, toks, ungated)
        y = _psum_if(y, "tp")
    metrics = m._asdict()
    if cfg.expert_share == (0, 1):
        del metrics["held_rows"]    # every assignment: nothing to report
    aux = {"aux_loss": (cfg.moe_balance_weight * m.load_balance_loss
                        + cfg.moe_z_weight * m.router_z_loss),
           **metrics}
    return y.reshape(B, S, M), aux


def _causal_conv(x, taps, bias):
    """Depthwise causal convolution over the sequence: ``y[t] = bias +
    sum_j taps[j] * x[t - (K - 1) + j]`` with zeros before the start. x
    ``[B, S, C]``, taps ``[K, C]``; K shifted multiply-adds in float32."""
    K, S = taps.shape[0], x.shape[1]
    padded = jnp.pad(x, ((0, 0), (K - 1, 0), (0, 0)))
    y = bias.astype(jnp.float32)
    for j in range(K):
        y = y + (taps[j].astype(jnp.float32)
                 * padded[:, j:j + S].astype(jnp.float32))
    return y


def _ssm_decay(log_decay):
    """``exp`` of a sum of ``dt_t a`` (never positive), in float32 as it
    comes: the one place the scan's decays are made (a test swaps it for
    the nearest precision below)."""
    return jnp.exp(log_decay)


def _carried_states(whole, states):
    """The state each chunk starts from, ``[B, n, ...]``: ``H <- whole_c H
    + states_c`` over the ``n`` chunks from ``H = 0``, in float32. whole
    ``[B, n, G, R]`` a chunk's whole decay ``exp(s_Q)``, states ``[B, n, G,
    R, P, N]`` what a chunk's own positions leave behind."""
    def carry(h, chunk):
        decay, state = chunk
        return decay[..., None, None] * h + state, h
    _, before = lax.scan(
        carry, jnp.zeros_like(states[:, 0]),
        (jnp.moveaxis(whole, 1, 0), jnp.moveaxis(states, 1, 0)))
    return jnp.moveaxis(before, 0, 1)


def _gated_norm(y, z, weight, groups: int, eps: float):
    """``rmsnorm(y * silu(z)) * weight`` in float32, the gate before the
    norm and the norm over each of ``groups`` groups of channels. y, z
    ``[B, S, C]``."""
    B, S, C = y.shape
    y = (y.astype(jnp.float32) * jax.nn.silu(z.astype(jnp.float32))
         ).reshape(B, S, groups, C // groups)
    y = y * jax.lax.rsqrt(jnp.mean(jnp.square(y), axis=-1, keepdims=True)
                          + eps)
    return y.reshape(B, S, C) * weight.astype(jnp.float32)


def _within_chunks(x, b, c, s, dt):
    """``y_i = sum_{j <= i} exp(s_i - s_j) (c_i . b_j) dt_j x_j`` inside
    every chunk: the scores, decays and their product are ``[B, n, G, R, Q,
    Q]`` (at 8192 positions and 64 heads of chunk 128, 268 MB in float32).
    x ``[B, n, Q, G, R, P]``, b and c ``[B, n, Q, G, N]``, s and dt ``[B,
    n, G, R, Q]`` float32; returns float32 ``[B, n, Q, G, R, P]``."""
    chunk = x.shape[2]
    scores = jnp.einsum("bnigs,bnjgs->bngij", c, b,
                        preferred_element_type=jnp.float32)
    causal = jnp.tril(jnp.ones((chunk, chunk), bool))
    decay = _ssm_decay(jnp.where(
        causal, s[..., :, None] - s[..., None, :], -jnp.inf))
    weights = (scores[:, :, :, None] * decay * dt[..., None, :]
               ).astype(x.dtype)
    return jnp.einsum("bngrij,bnjgrp->bnigrp", weights, x,
                      preferred_element_type=jnp.float32)


def _chunk_sums(steps, chunk: int):
    """``s_i = sum_{t <= i} steps_t`` inside every chunk of ``chunk``
    positions, ``[B, S, H]`` float32."""
    B, S, H = steps.shape
    return jnp.cumsum(steps.reshape(B, S // chunk, chunk, H), axis=2
                      ).reshape(B, S, H)


def ssm_chunked(x, dt, a, b, c, chunk: int, interpret: bool = False):
    """The selective state-space recurrence of Mamba-2 in its chunked
    (dual) form (arXiv:2405.21060, section 6). Per head, with ``a_t = dt_t
    a`` (``a`` < 0) and the state ``H`` ``[P, N]``:

        H_t = exp(a_t) H_{t-1} + dt_t x_t (x) b_t        y_t = H_t c_t

    Inside a chunk of ``chunk`` positions, ``s_i = sum_{t <= i} a_t``:

        y_i = sum_{j <= i} exp(s_i - s_j) (c_i . b_j) dt_j x_j
              + exp(s_i) c_i . H_prev
        H_next = exp(s_Q) H_prev + sum_j exp(s_Q - s_j) dt_j x_j (x) b_j

    so a chunk is three batches of matmuls (scores ``c b^T``, scores times
    x, x^T times b) and the sequence a loop over chunks that carries
    ``H``. The time steps, the sums ``s``, every decay and the carried
    state are float32; the matmuls take operands of ``x.dtype`` and
    accumulate in float32, the decays and ``dt`` multiplied into the
    scores before they are cast.

    x ``[B, S, H, P]``; dt ``[B, S, H]`` float32, after its softplus; a
    ``[H]`` float32; b, c ``[B, S, G, N]``, head h reading group ``h // (H
    / G)``. Returns y ``[B, S, H, P]`` float32 (without the skip ``D x``).

    On a TPU (and under ``interpret``) the chunks run in the Pallas kernels
    of ``ops/pallas_ssm.py`` wherever the shapes fit their tiles
    (:func:`pallas_ssm.ssm_eligible`): the same algorithm at the same
    precision, the sums ``s`` made here, nothing of a chunk's inside in
    HBM. Elsewhere, and as what the kernels are held against, the
    ``jax.numpy`` form below (:func:`_ssm_chunked_numpy`).
    """
    B, S, H, P = x.shape
    G, N = b.shape[2:]
    if S % chunk:
        raise ValueError(f"ssm_chunk={chunk} does not divide the sequence "
                         f"of {S} positions")
    s = _chunk_sums(dt * a, chunk)
    if interpret or (jax.default_backend() == "tpu"
                     and pallas_ssm.ssm_eligible(S, H, P, G, N, chunk)):
        return pallas_ssm.ssm_scan(x, dt, s, b, c, chunk, interpret)
    return _ssm_chunked_numpy(x, dt, s, b, c, chunk)


def _ssm_chunked_numpy(x, dt, s, b, c, chunk: int):
    """:func:`ssm_chunked` from the sums ``s`` ``[B, S, H]`` on, in
    ``jax.numpy`` and differentiated by JAX: the scores, decays and their
    product inside a chunk, the chunks' own states and the states they
    start from are arrays of their own."""
    B, S, H, P = x.shape
    G, N = b.shape[2:]
    n, R = S // chunk, H // G
    x = x.reshape(B, n, chunk, G, R, P)
    b, c = (v.reshape(B, n, chunk, G, N) for v in (b, c))
    # [B, n, G, R, Q]: a head's positions last
    dt, s = (v.reshape(B, n, chunk, G, R).transpose(0, 1, 3, 4, 2)
             for v in (dt, s))

    y = _within_chunks(x, b, c, s, dt)

    # -- a chunk's own state, and the state each chunk starts from ---------
    to_end = (_ssm_decay(s[..., -1:] - s) * dt).transpose(0, 1, 4, 2, 3)
    states = jnp.einsum("bnjgrp,bnjgs->bngrps",
                        (x.astype(jnp.float32) * to_end[..., None]
                         ).astype(x.dtype), b,
                        preferred_element_type=jnp.float32)
    since_start = _ssm_decay(s)                 # exp(s_i); the last: exp(s_Q)
    before = _carried_states(since_start[..., -1], states)
    y = y + (jnp.einsum("bnigs,bngrps->bnigrp", c, before.astype(x.dtype),
                        preferred_element_type=jnp.float32)
             * since_start.transpose(0, 1, 4, 2, 3)[..., None])
    return y.reshape(B, S, H, P)


def ssm_path(cfg: TransformerConfig, seq_len: int) -> str:
    """How a Mamba block's scan runs at ``seq_len`` positions and what the
    backward pass keeps of the block (``chip_smoke.py`` prints it, as it
    does ``attend``'s choice)."""
    kept = ("each Mamba block checkpointed: its input kept, the block run "
            "again in the backward pass" if _remat(cfg, True) else
            "everything kept for the backward pass")
    how = pallas_ssm.ssm_scan_path(
        seq_len, cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_groups,
        cfg.ssm_state, cfg.ssm_chunk)
    return (f"{how}; chunked scan, {seq_len // cfg.ssm_chunk} chunks of "
            f"{cfg.ssm_chunk}, float32 sums, decays and carried state "
            f"[{cfg.ssm_heads}, {cfg.ssm_head_dim}, {cfg.ssm_state}]; {kept}")


def _mamba_block(p, x, cfg: TransformerConfig):
    """``x + mamba2(norm(x))``, x ``[B', S', M]`` with the whole sequence
    here (no sp). The mixer: ``[z | x B C | dt] = h W_in``; x, B and C
    through the causal convolution and silu; ``dt = softplus(dt +
    dt_bias)``, ``a = -exp(a_log)`` a head; the scan (:func:`ssm_chunked`)
    plus the skip ``d x``; ``rmsnorm(y * silu(z))`` over each of the
    ``ssm_groups`` groups of channels; ``W_out``."""
    B, S, M = x.shape
    H, P, G, N = cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_groups, cfg.ssm_state
    inner, wide = cfg.ssm_inner, cfg.ssm_conv_width
    with jax.named_scope(scopes.SSM):
        h = _rmsnorm(x, p["ln1"], cfg.norm_eps)
        with jax.named_scope(scopes.SSM_PROJ):
            zxbcdt = h @ p["ssm_in"].astype(h.dtype)
        z, xbc, dt = (zxbcdt[..., :inner], zxbcdt[..., inner:inner + wide],
                      zxbcdt[..., inner + wide:])
        with jax.named_scope(scopes.SSM_CONV):
            xbc = jax.nn.silu(_causal_conv(
                xbc, p["ssm_conv_w"], p["ssm_conv_b"]).astype(h.dtype))
        xs = xbc[..., :inner].reshape(B, S, H, P)
        b = xbc[..., inner:inner + G * N].reshape(B, S, G, N)
        c = xbc[..., inner + G * N:].reshape(B, S, G, N)
        with jax.named_scope(scopes.SSM_SCAN):
            dt = jax.nn.softplus(dt.astype(jnp.float32)
                                 + p["ssm_dt_bias"].astype(jnp.float32))
            a = -jnp.exp(p["ssm_a_log"].astype(jnp.float32))
            y = ssm_chunked(xs, dt, a, b, c, cfg.ssm_chunk)
            y = y + (p["ssm_d"].astype(jnp.float32)[:, None]
                     * xs.astype(jnp.float32))
        with jax.named_scope(scopes.SSM_NORM):
            y = _gated_norm(y.reshape(B, S, inner), z, p["ssm_norm"], G,
                            cfg.norm_eps).astype(h.dtype)
        with jax.named_scope(scopes.SSM_PROJ):
            o = y @ p["ssm_out"].astype(h.dtype)
        return x + o


def _shared_expert(p, toks, activation):
    """The expert every token runs, ``down(activation(up(toks)))``, two
    dense matmuls over all the tokens (inner width over tp, the caller's
    psum); the same on every device that holds a share of the others."""
    with jax.named_scope(scopes.MOE_SHARED):
        h = activation(toks @ p["ws1"].astype(toks.dtype))
        return h @ p["ws2"].astype(toks.dtype)


def _no_aux():
    return {"aux_loss": jnp.zeros((), jnp.float32)}


def _over_layers(auxs):
    """One layer's auxiliary terms stacked ``[L]`` → the step's: the
    losses averaged, the largest load, the dropped assignments summed
    (the tokens' choices are :func:`router_choices`' to return)."""
    how = {"max_expert_load": jnp.max, "dropped": jnp.sum,
           "held_rows": jnp.sum}
    return {k: how.get(k, jnp.mean)(v) for k, v in auxs.items()
            if k != "experts"}


def _block(p, x, positions, cfg: TransformerConfig, kind=_PLAIN_LAYER):
    """One block of ``kind`` on ``x``; returns (the new residual, the
    block's auxiliary terms, None where it has none to stack)."""
    if kind[0] == "mamba":
        return _mamba_block(p, x, cfg), None
    if kind[0] == "attention":
        return _attention_block(p, x, positions, cfg, kind[1:]), None
    if kind[0] == "experts":
        return _ffn_block(p, x, cfg)
    logits = None
    if cfg.n_experts > 0 and cfg.moe_router_input == "block_input":
        # before attention, from the residual as it comes in: nothing of
        # this block stands between the choice and its experts' weights
        logits = _router_logits(p, x)
    x = _attention_block(p, x, positions, cfg, kind)
    return _ffn_block(p, x, cfg, logits)


def _ffn_block(p, x, cfg: TransformerConfig, logits=None):
    """``x + ffn(norm(x))``, the FFN dense or the experts; ``logits``: a
    router's that read something else than the normed tokens."""
    with jax.named_scope(scopes.MLP):
        h = _rmsnorm(x, p["ln2"], cfg.norm_eps)
        if cfg.n_experts > 0:
            o, aux = _moe_ffn(p, h, cfg, logits)
        else:
            o, aux = _dense_ffn(p, h, cfg), _no_aux()
        o = o.astype(x.dtype)
        if cfg.post_norm:
            o = _rmsnorm(o, p["ln2_post"], cfg.norm_eps)
        return x + o, aux


def _remat(cfg: TransformerConfig, needed: bool) -> bool:
    """Whether a path checkpoints its blocks: what the config says, or
    where it says nothing, whether the path needs it to fit."""
    return needed if cfg.remat is None else cfg.remat


def _stage_fn_factory(cfg: TransformerConfig, positions):
    """Returns stage_fn(stage_params, act) running L/pp blocks via scan.

    The weighted sum of the MoE's auxiliary losses rides as one extra
    feature column of the activation so the pipeline carry stays a single
    array (pipeline_spmd requirement); it accumulates across stages and is
    read back after the pipeline.
    """
    def block_of(kind):
        def one_block(lp, x):
            def fn(xx):
                return _block(lp, xx, positions, cfg, kind)
            if _remat(cfg, True):
                fn = jax.checkpoint(fn)
            return fn(x)
        return one_block

    def stage_fn(stage_params, act_with_aux):
        act = act_with_aux[..., :-1]
        aux_in = act_with_aux[..., -1:]
        # a stage is whole periods (param_shardings), so its first layer
        # is of the pattern's first kind
        y, auxs = _scan_periods(block_of, act.astype(cfg.dtype),
                                stage_params, cfg.layer_pattern)
        aux_out = aux_in + jnp.sum(auxs["aux_loss"]) / max(cfg.n_layers, 1)
        return jnp.concatenate([y.astype(jnp.float32), aux_out], axis=-1)

    return stage_fn


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
    ``exit_share`` ``[T]`` and ``gate_entropy``."""
    S = tokens.shape[1]
    sp_idx = lax.axis_index("sp") if _axis_live("sp") else 0
    positions = sp_idx * S + jnp.arange(S)

    with jax.named_scope(scopes.EMBED):
        x = _embed_lookup(params["embed"], tokens, cfg)         # [B,S,M]

    with jax.named_scope(scopes.LAYERS):
        if cfg.n_loops > 1:
            x, aux_total = _loop_layers(params["layers"], params["ln_f"], x,
                                        positions, cfg)      # [T,B,S,M]
        else:
            x, aux_total = _run_layers(params["layers"], x, positions, cfg)

    with jax.named_scope(scopes.HEAD):
        if cfg.tie_embeddings:
            head = params["embed"].astype(cfg.dtype).T
        else:
            head = params["lm_head"].astype(cfg.dtype)
        if cfg.n_loops > 1:
            # every loop step's state is already through ln_f
            nll = jnp.stack([_head_xent(x[t], head, targets)
                             for t in range(cfg.n_loops)])      # [T,B,S]
            loss, exits = _looped_loss(_exit_gate(params, x), nll)
            aux_total = {**aux_total, **exits}
        else:
            x = _rmsnorm(x, params["ln_f"], cfg.norm_eps)
            nll = _head_xent(x, head, targets)                  # [B,S]
            loss = jnp.mean(nll)
        # average over data-like axes so every shard reports the global
        # loss (ep subdivides the batch — see data_sharding_spec)
        for ax in ("dp", "ep", "sp"):
            if _axis_live(ax):
                loss = lax.pmean(loss, ax)
                aux_total = jax.tree_util.tree_map(
                    lambda a: lax.pmean(a, ax), aux_total)
    return loss, aux_total


def _exit_gate(params, states):
    """The exit gate's logits ``z_t = h_t w + b`` ``[T, B, S]`` of the loop
    steps' normed states ``[T, B, S, M]``, in float32: a multiply and a
    sum, not a matmul the MXU would take in bfloat16."""
    with jax.named_scope(scopes.LOOP_GATE):
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
    with jax.named_scope(scopes.LOOP_GATE):
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
    each: a scan over loop steps around the scan over layers, the stacked
    parameters closed over, so a weight's gradient is the sum over its
    uses. Returns (every step's normed state ``[T, B, S, M]``, the
    auxiliary terms over all passes)."""
    if _axis_live("pp"):
        raise NotImplementedError(
            "a looped stack (n_loops > 1) on a live pp axis: the pipeline "
            "schedule would have to send the last stage's output, through "
            "ln_f, back to the first stage for every loop step and hand "
            "every step's state to the head; pipeline_spmd runs the stages "
            "once")

    def loop_step(h, _):
        y, auxs = _scan_layers(lp, h, positions, cfg)
        y = _rmsnorm(y, ln_f, cfg.norm_eps)
        return y, (y, _over_layers(auxs))
    with jax.named_scope(scopes.LOOP):
        _, (states, auxs) = lax.scan(loop_step, x, None, length=cfg.n_loops)
    return states, _over_layers(auxs)


def _run_layers(lp, x, positions, cfg: TransformerConfig):
    """The stack of blocks: the GPipe schedule over live pp stages, else
    one scan over all layers. Returns (activations, the step's auxiliary
    terms)."""
    B = x.shape[0]
    if _axis_live("pp"):
        from horovod_tpu.parallel.pipeline import (pipeline_spmd,
                                                   psum_cotangent)
        stage_fn = _stage_fn_factory(cfg, positions)
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
    A looped stack checkpoints each block (its passes' activations would
    not fit beside the weights), the single pass only its Mamba blocks (a
    block's float32 chunk states, decays and gate keep 1.2 GB at 8192
    positions: PERF.md section 6, PR 39), unless ``cfg.remat`` says
    otherwise."""
    def block_of(kind):
        def block(layer_p, x):
            return _block(layer_p, x, positions, cfg, kind)
        if _remat(cfg, cfg.n_loops > 1 or kind[0] == "mamba"):
            block = jax.checkpoint(block)
        return block
    flat = jax.tree_util.tree_map(
        lambda a: a.reshape((-1,) + a.shape[2:]), lp)
    return _scan_periods(block_of, x, flat, cfg.layer_pattern)


def _scan_periods(block_of, x, layers, pattern):
    """``x`` through ``block_of(kind)(layer_p, x)`` for every layer of
    ``layers`` (leaves ``[L, ...]``), layer ``l`` of kind ``pattern[l %
    len(pattern)]``: a scan over periods with a period's layers unrolled
    inside, each with its static kind; a period of one is a scan over
    layers. A pattern of one-sublayer blocks has a stack a word
    (``layers[word]``, leaves ``[blocks of that word, ...]``), and a
    period's i-th block of a word takes the i-th of the period's layers
    in that stack. Returns (activations, the auxiliary terms of every
    layer that has any, stacked)."""
    blocks = {kind: block_of(kind) for kind in pattern}
    by_word = _one_sublayer(pattern)
    if len(pattern) == 1 and not by_word:
        def scan_body(carry, layer_p):
            y, aux = blocks[pattern[0]](layer_p, carry)
            return y, aux
        return lax.scan(scan_body, x, layers)
    n = len(pattern)
    if by_word:
        words = [kind[0] for kind in pattern]
        # (the stack, the place in a period's part of it) of each block
        places = [(w, words[:i].count(w)) for i, w in enumerate(words)]
        periods = {
            w: jax.tree_util.tree_map(
                lambda a, per=words.count(w): a.reshape(
                    (a.shape[0] // per, per) + a.shape[1:]), layers[w])
            for w in set(words)}

        def layer_of(period_p, i):
            word, place = places[i]
            return jax.tree_util.tree_map(lambda a: a[place], period_p[word])
    else:
        periods = jax.tree_util.tree_map(
            lambda a: a.reshape((a.shape[0] // n, n) + a.shape[1:]), layers)

        def layer_of(period_p, i):
            return jax.tree_util.tree_map(lambda a: a[i], period_p)

    def period_body(carry, period_p):
        auxs = []
        for i, kind in enumerate(pattern):
            carry, aux = blocks[kind](layer_of(period_p, i), carry)
            auxs.append(aux)
        auxs = [aux for aux in auxs if aux is not None] or [_no_aux()]
        return carry, jax.tree_util.tree_map(lambda *a: jnp.stack(a), *auxs)
    y, auxs = lax.scan(period_body, x, periods)
    return y, jax.tree_util.tree_map(
        lambda a: a.reshape((-1,) + a.shape[2:]), auxs)


def router_choices(params, tokens, cfg: TransformerConfig):
    """The experts the router of each layer chooses for each token,
    ``[L, B * S, k]``: the model's own blocks, on one device (no mesh). For
    diagnostics, such as telling what differing choices explain of an error
    against a reference (benchmarks/chip/tools/olmoe_routing.py)."""
    x = _embed_lookup(params["embed"], tokens, cfg)
    _x, auxs = _scan_layers(params["layers"], x,
                            jnp.arange(tokens.shape[1]), cfg)
    return auxs["experts"]


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
    with jax.named_scope(scopes.GRAD_SYNC):
        return jax.tree_util.tree_map(one, grads, pspec)


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
            return loss + aux["aux_loss"], (loss, aux)
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
    after the call on TPU."""
    import optax
    grad_fn = make_grad_fn(cfg, mesh)

    def one_step(params, opt_state, tokens, targets):
        loss, aux, grads = grad_fn(params, tokens, targets)
        with jax.named_scope(scopes.OPTIMIZER):
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
        return loss + aux["aux_loss"]

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


# ---------------------------------------------------------------------------
# Generative decode: KV-cache forward over the serving engine's paged pool
# ---------------------------------------------------------------------------
# The serving-side decode path (horovod_tpu/serving/generate/) runs the
# SAME weights the training step produced, but at token granularity: one
# fixed-shape decode step over a static slot array, with K/V history in
# block-granular pages.  Everything below is single-device math in fp32
# (serving replicas are world_size=1; bitwise-stable greedy decode is
# the parity contract tests/test_generate.py enforces).  Layout:
#
#   k_pages / v_pages  [L, total_pages + 1, page_tokens, H*Dh]
#       (+1 = the scratch page inactive/padded lanes write into, so
#       membership churn never changes the compiled shape)
#   page_table         [slots, pages_per_slot] int32 — a slot's j-th
#       page holds its token positions [j*page_tokens, (j+1)*page_tokens);
#       gathered back, position p of a slot lands at flat index p.

def _dense_decode_only(cfg: TransformerConfig) -> None:
    """The decode paths below compute multi-head attention over the whole
    causal history with rope on every layer: refuse, by name, what they
    would silently compute otherwise."""
    off = [name for name, plain in (
        ("layer_pattern", cfg.layer_pattern == (_PLAIN_LAYER,)),
        ("n_kv_heads", cfg.kv_heads == cfg.n_heads),
        ("moe_router_input", cfg.moe_router_input == "tokens"),
        ("expert_share", cfg.expert_share == (0, 1)),
        ("moe_router_scores", cfg.moe_router_scores == "softmax"),
        ("moe_shared_width", cfg.moe_shared_width == 0),
        ("ssm_heads (a Mamba block's recurrent state is no page of keys)",
         cfg.ssm_heads == 0)) if not plain]
    if off:
        raise NotImplementedError(
            f"paged decode does not implement {', '.join(off)}: its cache "
            "holds n_heads k/v heads of every position, and its layers "
            "attend to all of them with rope")


def kv_cache_spec(cfg: TransformerConfig) -> Tuple[int, int, Any]:
    """(n_layers, per-token K width, cache dtype) — the model
    fingerprint the page planner sizes pages from."""
    _dense_decode_only(cfg)
    return cfg.n_layers, cfg.n_heads * cfg.head_dim, jnp.float32


def flatten_decode_params(params: Dict) -> Dict:
    """Collapse the stacked-stage layout ``[pp, L/pp, ...]`` to
    ``[L, ...]`` — decode scans all layers on one device; the pipeline
    split is a training-time concern."""
    layers = params["layers"]
    if "w1" not in layers or "q_norm" in layers or "lm_head" in params \
            or "w3" in layers or "ln1_post" in layers \
            or "exit_gate" in params:
        raise NotImplementedError(
            "paged decode supports the dense GPT block: n_experts=0, no "
            "qk_norm, tied embeddings, no post_norm, no ffn_gated, no "
            "looped stack (n_loops > 1)")
    flat = {k: jnp.asarray(v).reshape((-1,) + tuple(np.shape(v)[2:]))
            for k, v in layers.items()}
    return {"embed": jnp.asarray(params["embed"]),
            "ln_f": jnp.asarray(params["ln_f"]),
            "layers": flat}


def _rope_rows(x, pos, theta=10000.0):
    """Rotary embedding for per-row positions: x [N, H, D], pos [N] —
    the decode-time counterpart of :func:`_rope` (one token per row,
    each at its own absolute position)."""
    half = x.shape[-1] // 2
    freqs = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = pos[:, None].astype(jnp.float32) * freqs[None, :]   # [N, half]
    cos = jnp.cos(ang)[:, None, :].astype(x.dtype)
    sin = jnp.sin(ang)[:, None, :].astype(x.dtype)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _paged_layer(lp, x, q_pos, kv_pages, dest_page, offs, gather_rows,
                 key_mask, cfg: TransformerConfig):
    """One transformer block over paged KV: write this call's K/V into
    the pool, gather the full history back, attend, FFN.

    x [N, M] (N = slots for decode, chunk for prefill); ``dest_page``/
    ``offs`` [N] address each row's write; ``gather_rows`` indexes the
    pages to read back ([N, P] per-row for decode, [P] shared for
    prefill); ``key_mask`` [N, T] marks the attended positions."""
    kp, vp = kv_pages
    H, Dh = cfg.n_heads, cfg.head_dim
    N = x.shape[0]
    h = _rmsnorm(x, lp["ln1"].astype(jnp.float32), cfg.norm_eps)
    q = _rope_rows((h @ lp["wq"].astype(jnp.float32)).reshape(N, H, Dh),
                   q_pos, cfg.rope_theta)
    k = _rope_rows((h @ lp["wk"].astype(jnp.float32)).reshape(N, H, Dh),
                   q_pos, cfg.rope_theta)
    v = (h @ lp["wv"].astype(jnp.float32))
    kp = kp.at[dest_page, offs].set(k.reshape(N, H * Dh))
    vp = vp.at[dest_page, offs].set(v)
    k_all = kp[gather_rows].reshape(gather_rows.shape[:-1] + (-1, H, Dh))
    v_all = vp[gather_rows].reshape(gather_rows.shape[:-1] + (-1, H, Dh))
    if k_all.ndim == 3:           # shared gather (prefill): [T, H, Dh]
        scores = jnp.einsum("nhd,thd->nht", q, k_all)
    else:                         # per-row gather (decode): [N, T, H, Dh]
        scores = jnp.einsum("nhd,nthd->nht", q, k_all)
    scores = scores / np.sqrt(Dh).astype(np.float32)
    scores = jnp.where(key_mask[:, None, :], scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1)
    if k_all.ndim == 3:
        o = jnp.einsum("nht,thd->nhd", probs, v_all)
    else:
        o = jnp.einsum("nht,nthd->nhd", probs, v_all)
    x = x + o.reshape(N, H * Dh) @ lp["wo"].astype(jnp.float32)
    h2 = _rmsnorm(x, lp["ln2"].astype(jnp.float32), cfg.norm_eps)
    f = jax.nn.gelu(h2 @ lp["w1"].astype(jnp.float32))
    return x + f @ lp["w2"].astype(jnp.float32), (kp, vp)


def decode_step_paged(params: Dict, k_pages, v_pages, page_table,
                      lengths, last_token, active,
                      cfg: TransformerConfig):
    """ONE decode step for every slot at once — the function the engine
    jits exactly once, whatever joins or leaves between calls.

    Shapes (all static): page_table [S, P] int32, lengths/last_token
    [S] int32, active [S] bool.  Each active slot embeds its last
    token, appends its K/V at position ``lengths[s]``, attends over its
    own gathered history, and emits the greedy next token.  Inactive
    slots compute masked garbage into the scratch page — their lanes
    exist only to keep the shape constant.  Returns
    ``(next_token [S] int32, k_pages, v_pages)``."""
    _dense_decode_only(cfg)
    S = last_token.shape[0]
    pt = k_pages.shape[2]
    scratch = k_pages.shape[1] - 1
    emb = params["embed"].astype(jnp.float32)
    x = emb[last_token]                                    # [S, M]
    page_idx = jnp.clip(lengths // pt, 0, page_table.shape[1] - 1)
    dest = jnp.take_along_axis(page_table, page_idx[:, None], axis=1)[:, 0]
    dest = jnp.where(active, dest, scratch)
    offs = lengths % pt
    T = page_table.shape[1] * pt
    key_mask = jnp.arange(T)[None, :] <= lengths[:, None]  # incl. new token

    def body(x, layer):
        lp, kp, vp = layer
        x, pages = _paged_layer(lp, x, lengths, (kp, vp), dest, offs,
                                page_table, key_mask, cfg)
        return x, pages

    x, (k_pages, v_pages) = lax.scan(
        body, x, (params["layers"], k_pages, v_pages))
    x = _rmsnorm(x, params["ln_f"].astype(jnp.float32), cfg.norm_eps)
    logits = x @ emb.T                                     # [S, V]
    return jnp.argmax(logits, axis=-1).astype(jnp.int32), k_pages, v_pages


def prefill_chunk_paged(params: Dict, k_pages, v_pages, page_row,
                        tokens, pos0, valid, cfg: TransformerConfig):
    """Prefill ONE ``chunk``-token slice of ONE slot's prompt (fixed
    chunk shape — the last chunk arrives padded with ``valid`` marking
    the real tokens).  Writes the chunk's K/V into the slot's pages and
    returns the greedy next token after the last VALID position — the
    first generated token once the final chunk lands.  Returns
    ``(next_token scalar int32, k_pages, v_pages)``."""
    _dense_decode_only(cfg)
    C = tokens.shape[0]
    pt = k_pages.shape[2]
    scratch = k_pages.shape[1] - 1
    emb = params["embed"].astype(jnp.float32)
    x = emb[tokens]                                        # [C, M]
    pos = pos0 + jnp.arange(C, dtype=jnp.int32)
    live = jnp.arange(C) < valid
    dest = jnp.where(live,
                     page_row[jnp.clip(pos // pt, 0,
                                       page_row.shape[0] - 1)],
                     scratch)
    offs = pos % pt
    T = page_row.shape[0] * pt
    # causal within the chunk AND over every earlier chunk's positions
    key_mask = jnp.arange(T)[None, :] <= pos[:, None]

    def body(x, layer):
        lp, kp, vp = layer
        x, pages = _paged_layer(lp, x, pos, (kp, vp), dest, offs,
                                page_row, key_mask, cfg)
        return x, pages

    x, (k_pages, v_pages) = lax.scan(
        body, x, (params["layers"], k_pages, v_pages))
    x = _rmsnorm(x, params["ln_f"].astype(jnp.float32), cfg.norm_eps)
    x_last = x[jnp.clip(valid - 1, 0, C - 1)]
    logits = x_last @ emb.T                                # [V]
    return jnp.argmax(logits).astype(jnp.int32), k_pages, v_pages


def reference_greedy_decode(params: Dict, cfg: TransformerConfig,
                            prompt, max_new: int) -> list:
    """Sequential non-paged oracle: recompute full-history attention
    for every emitted token (no cache, no paging, no batching).  Slow
    on purpose — this is the ground truth the paged continuous engine
    must match token-for-token (tests/test_generate.py)."""
    _dense_decode_only(cfg)
    flat = flatten_decode_params(params)
    H, Dh, L = cfg.n_heads, cfg.head_dim, cfg.n_layers
    toks = [int(t) for t in np.asarray(prompt).reshape(-1)]
    out = []
    for _ in range(int(max_new)):
        ids = jnp.asarray(toks, dtype=jnp.int32)
        Tn = ids.shape[0]
        emb = flat["embed"].astype(jnp.float32)
        x = emb[ids]
        pos = jnp.arange(Tn, dtype=jnp.int32)
        for li in range(L):
            lp = {k: v[li] for k, v in flat["layers"].items()}
            h = _rmsnorm(x, lp["ln1"].astype(jnp.float32), cfg.norm_eps)
            q = _rope_rows((h @ lp["wq"].astype(jnp.float32))
                           .reshape(Tn, H, Dh), pos, cfg.rope_theta)
            k = _rope_rows((h @ lp["wk"].astype(jnp.float32))
                           .reshape(Tn, H, Dh), pos, cfg.rope_theta)
            v = (h @ lp["wv"].astype(jnp.float32)).reshape(Tn, H, Dh)
            scores = jnp.einsum("qhd,khd->hqk", q, k) / np.sqrt(Dh)
            mask = pos[None, :] <= pos[:, None]
            scores = jnp.where(mask[None, :, :], scores, -1e30)
            probs = jax.nn.softmax(scores, axis=-1)
            o = jnp.einsum("hqk,khd->qhd", probs, v).reshape(Tn, H * Dh)
            x = x + o @ lp["wo"].astype(jnp.float32)
            h2 = _rmsnorm(x, lp["ln2"].astype(jnp.float32), cfg.norm_eps)
            f = jax.nn.gelu(h2 @ lp["w1"].astype(jnp.float32))
            x = x + f @ lp["w2"].astype(jnp.float32)
        x = _rmsnorm(x, flat["ln_f"].astype(jnp.float32), cfg.norm_eps)
        nxt = int(jnp.argmax(x[-1] @ emb.T))
        out.append(nxt)
        toks.append(nxt)
    return out
