"""Adapter ``qwen3_next``: Qwen3-Next-80B-A3B-Instruct (``model_type``
``qwen3_next``; its linear mixer is Gated DeltaNet, arXiv:2412.06464, its
attention grouped-query softmax attention gated a channel) through
models/transformer.py and ``make_train_step``, the entry points the other
adapters call: gated delta-rule blocks with a decay a head (``("delta",)`` of
``layer_pattern`` with ``delta_decay`` "head": ``delta_heads`` value heads
on ``delta_key_heads`` key heads; ``models/delta.py``) three layers in four,
an attention block the fourth whose query projection is twice as wide and
gates the core's output a channel (``("attention", None, Rope(theta, 64),
None, "channel")``: rope on a quarter of the head, a zero-centred q and k
norm a head), zero-centred norm weights (``zero_centred_norms``), softmax-routed
SiLU-gated experts beside a shared expert times a sigmoid of the token
(``moe_shared_gate``), and one chip's share of every expert layer and of the
vocabulary (``expert_share``; the configuration's ``deployment``). On a TPU
the delta rule's scan is ``hvd_delta_scan`` / ``hvd_delta_scan_bwd`` in
their form for a decay a head, the attention core ``hvd_flash_attention`` /
``hvd_flash_bwd`` at 16 / 2 heads of 256, the routed experts' matmuls
``hvd_moe_gmm`` and the head's loss ``hvd_fused_xent``.

The configuration file uses the source's key names. ``num_experts`` counts
the experts held here; the router's width is that times ``share.of``. The
host batch, the step and the checks are the ``olmoe`` adapter's.
"""

from __future__ import annotations

import math

from adapters import olmoe
from adapters.flagship import host_batch, tokens_per_step  # noqa: F401
from trees import as_shapes


def _mixers(config: dict) -> list:
    """"delta" or "attention" a layer, layers counted from 0: layer ``i`` is
    ``full_attention`` where ``(i + 1) % full_attention_interval == 0``."""
    every = config["full_attention_interval"]
    return ["attention" if (i + 1) % every == 0 else "delta"
            for i in range(config["num_hidden_layers"])]


#: the stack of ``layers`` the attention blocks are in
#: (``transformer._stack_of`` of their kind)
_ATTENTION = "attention_gated_channel"


def _leaf_paths(mixers: list) -> dict:
    """See trees.py; ``layers`` is a stack a word ``[stage, block, ...]``.
    The first delta mixer's in-projection sees every later layer through
    the residual; the last delta mixer's ``b`` / ``a`` projection, decay
    rate and taps are read by the scan alone; the attention block's query
    projection holds the gate's columns, its key norm's gradient is summed
    over both heads; the router, the shared expert's gate and the held
    experts' way down see the choices directly."""
    last = (0, len(mixers) - 1)
    last_delta = (0, mixers.count("delta") - 1)
    last_attention = (0, mixers.count("attention") - 1)
    return {
        "lm_head": (("lm_head",), None),
        "first_delta_in": (("layers", "delta", "w_in"), (0, 0)),
        "last_delta_in": (("layers", "delta", "w_in"), last_delta),
        "last_beta_decay": (("layers", "delta", "w_ba"), last_delta),
        "last_decay_rate": (("layers", "delta", "a_log"), last_delta),
        "last_taps": (("layers", "delta", "conv"), last_delta),
        "last_query_gate": (("layers", _ATTENTION, "wq"), last_attention),
        "last_key_norm": (("layers", _ATTENTION, "k_norm"), last_attention),
        "last_router": (("layers", "experts", "router"), last),
        "last_shared_gate": (("layers", "experts", "ws_gate"), last),
        "last_experts_down": (("layers", "experts", "we2"), last),
    }


def shapes(config: dict, job: dict) -> dict:
    share, held = config["share"], config["num_experts"]
    layers, mixers = config["num_hidden_layers"], _mixers(config)
    attention = mixers.count("attention")
    head = config["head_dim"]
    return {
        "batch": job["batch_per_chip"], "seq": job["seq_len"],
        "layers": layers, "d_model": config["hidden_size"],
        "heads": config["num_attention_heads"],
        "kv_heads": config["num_key_value_heads"], "head_dim": head,
        "rope_width": int(head * config["partial_rotary_factor"]),
        "rope_theta": float(config["rope_theta"]),
        "d_ff": config["moe_intermediate_size"],
        "vocab": config["vocab_size"], "causal": True,
        "layer_mixers": mixers,
        "delta_layers": mixers.count("delta"),
        "delta_heads": config["linear_num_value_heads"],
        "delta_key_heads": config["linear_num_key_heads"],
        "delta_head_dim": config["linear_value_head_dim"],
        "delta_decay_width": 1,
        "delta_taps": config["linear_conv_kernel_dim"],
        "delta_chunk": config.get("delta_chunk",
                                  config["assumed"]["delta_chunk"]),
        # every delta block is checkpointed: its forward kernel runs again
        # in the backward pass (``assumed.recomputation``)
        "delta_forward_calls": 2 * mixers.count("delta"),
        "experts": held * share["of"],
        "held_experts": held, "first_expert": held * share["index"],
        "experts_per_token": config["num_experts_per_tok"],
        "d_expert": config["moe_intermediate_size"],
        "d_shared": config["shared_expert_intermediate_size"],
        # what the kernels' roofline functions count: the attention block
        # keeps its activations (the kind's row: one forward and one
        # backward call, a full causal layer's, ``layer_windows``); an
        # expert layer a layer; one head call
        "attention_layers": attention,
        "attention_forward_calls": attention,
        "layer_windows": [None] * attention,
        "routed_layers": layers,
        "head_calls": 1,
        # what the reference needs beside sizes
        "norm_eps": config["rms_norm_eps"],
    }


def delta_scan_flops(s: dict) -> float:
    """Matmul FLOPs a token and delta mixer of the chunked algorithm at
    chunk ``C`` = ``delta_chunk``, forward, the triangular products over the
    rows they need on the mean (a position's ``(C - 1) / 2`` earlier rows of
    its chunk, ``(C + 1) / 2`` with its own). A key head, shared by the
    value heads that read it (the scalar decay factors out of the pairs):
    ``k k^T`` ``2 D (C - 1) / 2`` and ``q k^T`` ``2 D (C + 1) / 2``. A value
    head: the solve applied to ``[K * decay | V]``, ``2 (D + Dv) (C + 1) /
    2``; ``P R``, ``2 Dv (C + 1) / 2``; and three products with the ``D x
    Dv`` state, ``W S``, ``(Q * decay) S`` and the state's update, ``2 D
    Dv`` each."""
    c, d = s["delta_chunk"], s["delta_head_dim"]
    a_key_head = d * (c - 1) + d * (c + 1)
    a_value_head = 2 * d * (c + 1) + d * (c + 1) + 3 * 2 * d * d
    return (s["delta_key_heads"] * a_key_head
            + s["delta_heads"] * a_value_head)


def flops_per_token(config: dict, job: dict) -> float:
    """Matmul FLOPs this chip's share needs per trained token: forward +
    backward (= 3 x forward), nothing recomputed. With M the hidden size:

    * a delta mixer, Hk key heads and H value heads of D: the in-projection
      ``2 M (2 Hk D + 2 H D)``, ``b`` and ``a`` ``2 M 2 H``, the
      out-projection ``2 H D M``, the scan :func:`delta_scan_flops` (the
      convolution's taps are no matmul and count 0);
    * the gated attention block, A query heads and K key/value heads of d:
      queries and gates ``2 M 2 A d``, keys and values ``2 * 2 M K d``, the
      output ``2 A d M``, and the scores and the weighted sum over the
      causal half, ``(S + 1) / 2`` keys a query: ``2 A 2 d (S + 1) / 2``;
    * an expert layer: the router onto all the experts' columns, the shared
      expert's three matrices and its gate's one column on every token, and
      ``num_experts_per_tok`` routed experts of three matrices of which
      this chip holds ``held / experts`` (uniform routing: by arithmetic,
      not by the run's counts);
    * the head over the vocabulary slice at every position; the embedding
      lookup counts 0."""
    s = shapes(config, job)
    m = s["d_model"]
    h, hk, d = s["delta_heads"], s["delta_key_heads"], s["delta_head_dim"]
    delta = (2 * m * (2 * hk * d + 2 * h * d) + 2 * m * 2 * h
             + 2 * h * d * m + delta_scan_flops(s))
    a, kv, dh = s["heads"], s["kv_heads"], s["head_dim"]
    attention = (2 * m * 2 * a * dh + 2 * 2 * m * kv * dh + 2 * a * dh * m
                 + 2 * a * 2 * dh * (s["seq"] + 1) / 2)
    experts = (2 * m * s["experts"] + 3 * 2 * m * s["d_shared"] + 2 * m
               + s["experts_per_token"] * s["held_experts"] / s["experts"]
               * 3 * 2 * m * s["d_expert"])
    forward = (s["delta_layers"] * delta + s["attention_layers"] * attention
               + s["routed_layers"] * experts
               + s["head_calls"] * 2 * m * s["vocab"])
    return 3.0 * forward


def _model_config(config: dict, job: dict):
    import jax.numpy as jnp
    from horovod_tpu.models._kinds import Rope
    from horovod_tpu.models.transformer import TransformerConfig
    if job["seq_len"] > config["max_position_embeddings"]:
        raise ValueError("seq_len beyond the configuration's positions")
    if (config["hidden_act"], config["decoder_sparse_step"],
            config["mlp_only_layers"], config["norm_topk_prob"],
            config["tie_word_embeddings"], config["rope_scaling"],
            config["use_sliding_window"], config["linear_key_head_dim"]) != (
                "silu", 1, [], True, False, None, False,
                config["linear_value_head_dim"]):
        raise ValueError("not the blocks the program implements")
    if config["num_hidden_layers"] % config["full_attention_interval"]:
        raise ValueError("the layers are not whole periods")
    share, head = config["share"], config["head_dim"]
    table = Rope(float(config["rope_theta"]),
                 int(head * config["partial_rotary_factor"]))
    kinds = {"delta": ("delta",),
             "attention": ("attention", None, table, None, "channel")}
    period = _mixers(config)[:config["full_attention_interval"]]
    return TransformerConfig(
        vocab_size=config["vocab_size"], d_model=config["hidden_size"],
        n_heads=config["num_attention_heads"],
        n_kv_heads=config["num_key_value_heads"], head_width=head,
        qk_norm="head", zero_centred_norms=True,
        delta_heads=config["linear_num_value_heads"],
        delta_key_heads=config["linear_num_key_heads"],
        delta_head_dim=config["linear_value_head_dim"],
        delta_taps=config["linear_conv_kernel_dim"], delta_decay="head",
        delta_chunk=config.get("delta_chunk",
                               config["assumed"]["delta_chunk"]),
        n_layers=2 * config["num_hidden_layers"],
        layer_pattern=tuple(kind for mixer in period
                            for kind in (kinds[mixer], ("experts",))),
        d_ff=config["moe_intermediate_size"],
        max_seq=config["max_position_embeddings"],
        n_experts=config["num_experts"] * share["of"],
        moe_top_k=config["num_experts_per_tok"], moe_gated=True,
        moe_activation="silu", moe_renormalize=config["norm_topk_prob"],
        moe_balance_weight=0.0,
        moe_shared_width=config["shared_expert_intermediate_size"],
        moe_shared_gate=True,
        expert_share=(share["index"], share["of"]),
        tie_embeddings=config["tie_word_embeddings"],
        norm_eps=config["rms_norm_eps"],
        rope_theta=float(config["rope_theta"]),
        dtype=jnp.dtype(config["compute_dtype"]))


def _init_function(c, config: dict):
    """Draws the tree of transformer.init_params (one stage; ``layers`` a
    stack a word) in its shapes and scales from a key, on the device; the
    embedding at ``assumed.embedding_std``."""
    import jax
    import jax.numpy as jnp
    from horovod_tpu.models.mamba import (SSM_A_RANGE, SSM_DT_FLOOR,
                                          SSM_DT_RANGE)
    m, a, kv, dh = c.d_model, c.n_heads, c.kv_heads, c.head_dim
    h, hk, d, taps = (c.delta_heads, c.delta_key_heads, c.delta_head_dim,
                      c.delta_taps)
    f, fs, held = c.d_ff, c.moe_shared_width, c.held_experts
    pattern = [kind[0] for kind in c.layer_pattern]
    periods = c.n_layers // len(pattern)

    def make(key):
        keys = iter(jax.random.split(key, 64))

        def w(*shape, scale=None):
            scale = 1.0 / math.sqrt(shape[-2]) if scale is None else scale
            return jax.random.normal(next(keys), shape, jnp.float32) * scale

        def uniform(shape, low, high):
            return jax.random.uniform(next(keys), shape, jnp.float32, low,
                                      high)

        def zeros(*shape):      # a zero-centred norm weight
            return jnp.zeros(shape, jnp.float32)

        def dt_bias(*shape):    # softplus(dt_bias) = dt, log-uniform
            dt = jnp.exp(uniform(shape, *(math.log(x)
                                          for x in SSM_DT_RANGE)))
            return jnp.log(jnp.expm1(jnp.maximum(dt, SSM_DT_FLOOR)))

        def delta(*n):
            return {
                "dt_bias": dt_bias(*n, h),
                "a_log": jnp.log(uniform(n + (h,), *SSM_A_RANGE)),
                "ln1": zeros(*n, m),
                "w_in": w(*n, m, 2 * hk * d + 2 * h * d),
                "conv": uniform(n + (taps, 2 * hk * d + h * d), -1.0, 1.0)
                / math.sqrt(taps),
                "w_ba": w(*n, m, 2 * h),
                "norm": jnp.ones(n + (d,), jnp.float32),
                "wo": w(*n, h * d, m)}

        def attention(*n):
            return {
                "ln1": zeros(*n, m), "wq": w(*n, m, 2 * a * dh),
                "wk": w(*n, m, kv * dh), "wv": w(*n, m, kv * dh),
                "wo": w(*n, a * dh, m),
                "q_norm": zeros(*n, dh), "k_norm": zeros(*n, dh)}

        def experts(*n):
            return {
                "ln2": zeros(*n, m),
                "router": w(*n, m, c.n_experts, scale=0.02),
                "we1": w(*n, held, m, f), "we2": w(*n, held, f, m),
                "we3": w(*n, held, m, f),
                "ws1": w(*n, m, fs), "ws2": w(*n, fs, m),
                "ws3": w(*n, m, fs), "ws_gate": w(*n, m, 1)}
        draw = {"delta": ("delta", delta), "attention": (_ATTENTION,
                                                         attention),
                "experts": ("experts", experts)}
        return {
            "embed": w(c.vocab_size, m,
                       scale=config["assumed"]["embedding_std"]),
            "ln_f": zeros(m),
            "lm_head": w(m, c.vocab_size),
            "layers": {draw[word][0]: draw[word][1](
                1, periods * pattern.count(word))
                for word in dict.fromkeys(pattern)},
        }
    return make


def abstract_step(config: dict, job: dict, mesh, tx):
    """(jitted step, its arguments as shapes with shardings) for a compile
    without devices: everything replicated but the batch (dp meshes)."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P
    from horovod_tpu.models.transformer import (data_sharding_spec,
                                                make_train_step)
    cfg, rep = _model_config(config, job), NamedSharding(mesh, P())
    params = as_shapes(jax.eval_shape(_init_function(cfg, config),
                                      jax.random.PRNGKey(0)), rep)
    opt_state = as_shapes(jax.eval_shape(tx.init, params), rep)
    batch = as_shapes(
        host_batch(config, job, 0, 0, job["batch_per_chip"] * mesh.size),
        NamedSharding(mesh, data_sharding_spec(mesh)))
    return (make_train_step(cfg, mesh, tx),
            (params, opt_state, batch["tokens"], batch["targets"]))


class Cell(olmoe.Cell):
    """The ``olmoe`` cell's checks and step (``program_choices``,
    ``dropped`` held to 0 after the window) on this adapter's configuration
    and tree. ``last_aux`` also holds ``held_rows`` (the step's assignments
    to the experts held here), ``max_expert_load`` and
    ``delta_min_log_decay`` (the most negative sum of a chunk's log decays
    of any delta layer, chunk and head)."""

    def __init__(self, config: dict, job: dict, mesh, seed: int):
        import jax
        from horovod_tpu.models.transformer import param_shardings
        self.mesh = mesh
        self.cfg = _model_config(config, job)
        self.leaf_paths = _leaf_paths(_mixers(config))
        self.params = jax.jit(
            _init_function(self.cfg, config),
            out_shardings=param_shardings(self.cfg, mesh))(
                jax.random.PRNGKey(seed))
        self.opt_state = None
        self._step = None
        self.last_aux = None

    def check_sequences(self) -> int:
        # what the timed step computes: one sequence a data shard
        shards = 1
        for axis in ("dp", "ep"):
            shards *= self.mesh.shape.get(axis, 1)
        return shards
