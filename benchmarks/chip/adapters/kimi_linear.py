"""Adapter ``kimi_linear``: Kimi-Linear-48B-A3B-Instruct (``model_type``
``kimi_linear``; its mixer is Kimi Delta Attention, arXiv:2510.26692 section
3, its latent attention DeepSeek-V2's without a query latent and without
rotation, its router DeepSeek-V3's) through models/transformer.py and
``make_train_step``, the entry points the other adapters call: gated
delta-rule blocks (``("delta",)`` of ``layer_pattern``: ``delta_heads``,
``delta_head_dim``, ``delta_taps``, ``delta_chunk``; ``models/delta.py``)
three layers in four, latent attention blocks the fourth (``("latent",)``
with ``q_latent`` 0, ``latent_rope`` False and ``value_width`` 128 beside keys
of 192), ``first_k_dense_replace`` dense layers leading the expert layers
(``lead_pattern``, ``dense_ff``), sigmoid-routed SiLU-gated experts with a
gated shared expert, and one chip's share of every expert layer and of the
vocabulary (``expert_share``; the configuration's ``deployment``). On a TPU
the delta rule's scan is ``jax.numpy`` over chunks (no kernel), the latent
core ``hvd_flash_attention`` / ``hvd_flash_bwd`` at 32 / 32 heads padded to
256 channels, the routed experts' matmuls ``hvd_moe_gmm`` and the head's
loss ``hvd_fused_xent``.

The configuration file uses the source's key names. ``num_experts`` counts
the experts held here; the router's width is that times ``share.of``. The
host batch, the step and the checks are the ``olmoe`` adapter's.
"""

from __future__ import annotations

import math

from adapters import olmoe
from adapters.flagship import host_batch, tokens_per_step  # noqa: F401
from trees import as_shapes

#: the lanes of a tile: the width the latent core's q, k and v are padded to
#: is the next multiple that holds both the keys and the values
_LANES = 128


def _mixers(config: dict) -> list:
    """"delta" or "latent" a layer, layers counted from 1 as
    ``linear_attn_config`` counts them."""
    linear = config["linear_attn_config"]
    kinds = []
    for layer in range(1, config["num_hidden_layers"] + 1):
        if (layer in linear["kda_layers"]) == (
                layer in linear["full_attn_layers"]):
            raise ValueError(f"layer {layer} is in one of kda_layers and "
                             "full_attn_layers")
        kinds.append("delta" if layer in linear["kda_layers"] else "latent")
    return kinds


def _leaf_paths(mixers: list, dense: int) -> dict:
    """See trees.py; ``layers`` is a stack a word ``[stage, block, ...]``,
    ``lead`` ``[block, ...]``. The first delta mixer's key projection sees
    every later layer through the residual; the last delta mixer's decay
    (its way down), beta and taps are read by the scan alone; the latent
    block's ``wkva`` holds the 64 unrotated columns whose gradient is
    summed over 32 heads; the router and the held experts' way down see
    the choices directly."""
    after = mixers[dense:]
    last = (0, len(after) - 1)
    last_delta = (0, after.count("delta") - 1)
    last_latent = (0, after.count("latent") - 1)
    return {
        "lm_head": (("lm_head",), None),
        "first_delta_key": (("lead", "delta", "wk"), (0,)),
        "dense_down": (("lead", "dense", "w2"), (0,)),
        "last_delta_key": (("layers", "delta", "wk"), last_delta),
        "last_decay_down": (("layers", "delta", "wf_down"), last_delta),
        "last_beta": (("layers", "delta", "w_beta"), last_delta),
        "last_key_taps": (("layers", "delta", "conv_k"), last_delta),
        "last_kv_down": (("layers", "latent", "wkva"), last_latent),
        "last_kv_up": (("layers", "latent", "wkvb"), last_latent),
        "last_router": (("layers", "experts", "router"), last),
        "last_experts_down": (("layers", "experts", "we2"), last),
    }


def shapes(config: dict, job: dict) -> dict:
    share, held = config["share"], config["num_experts"]
    layers, dense = (config["num_hidden_layers"],
                     config["first_k_dense_replace"])
    mixers, linear = _mixers(config), config["linear_attn_config"]
    nope, rot = config["qk_nope_head_dim"], config["qk_rope_head_dim"]
    value = config["v_head_dim"]
    latent = mixers.count("latent")
    return {
        "batch": job["batch_per_chip"], "seq": job["seq_len"],
        "layers": layers, "d_model": config["hidden_size"],
        "heads": config["num_attention_heads"],
        "kv_heads": config["num_key_value_heads"],
        # the head the flash kernels are called with: q, k and v padded
        # with zero channels to whole lane tiles (``padded_core``); the
        # published widths are qk_head_dim and value_head_dim
        "head_dim": -(-max(nope + rot, value) // _LANES) * _LANES,
        "qk_head_dim": nope + rot, "qk_nope": nope, "qk_rope": rot,
        "value_head_dim": value,
        "kv_latent": config["kv_lora_rank"],
        "d_ff": config["intermediate_size"],
        "dense_ff": config["intermediate_size"],
        "vocab": config["vocab_size"], "causal": True,
        "layer_mixers": mixers,
        "delta_layers": mixers.count("delta"),
        "delta_heads": linear["num_heads"],
        "delta_head_dim": linear["head_dim"],
        "delta_taps": linear["short_conv_kernel_size"],
        "delta_chunk": config.get("delta_chunk",
                                  config["assumed"]["delta_chunk"]),
        "dense_layers": dense, "expert_layers": layers - dense,
        "experts": held * share["of"],
        "held_experts": held, "first_expert": held * share["index"],
        "experts_per_token": config["num_experts_per_token"],
        "d_expert": config["moe_intermediate_size"],
        "d_shared": (config["moe_intermediate_size"]
                     * config["num_shared_experts"]),
        # what the kernels' roofline functions count: every latent block's
        # forward kernel runs again in its checkpointed backward
        # (``assumed.recomputation``); an expert layer after the dense ones;
        # one head call
        "attention_layers": latent,
        "attention_forward_calls": 2 * latent,
        "routed_layers": layers - dense,
        "head_calls": 1,
        # what the reference needs beside sizes
        "norm_eps": config["rms_norm_eps"],
        "routed_scale": config["routed_scaling_factor"],
    }


def delta_scan_flops(s: dict) -> float:
    """Matmul FLOPs a token and delta head of the chunked algorithm at chunk
    ``C`` = ``delta_chunk``, forward, the triangular products over the
    rows they need on the mean (a position's ``(C - 1) / 2`` earlier rows
    of its chunk, ``(C + 1) / 2`` with its own): the pairs ``k k^T`` ``2 D
    (C - 1) / 2`` and ``q k^T`` ``2 D (C + 1) / 2``; the solve applied to
    ``[K * decay | V]``, ``2 (D + Dv) (C + 1) / 2``; ``P R``, ``2 Dv (C +
    1) / 2``; and three products with the ``D x Dv`` state, ``W S``, ``(Q *
    decay) S`` and the state's update, ``2 D Dv`` each."""
    c, d = s["delta_chunk"], s["delta_head_dim"]
    return (d * (c - 1) + d * (c + 1) + 2 * d * (c + 1) + d * (c + 1)
            + 3 * 2 * d * d)


def flops_per_token(config: dict, job: dict) -> float:
    """Matmul FLOPs this chip's share needs per trained token: forward +
    backward (= 3 x forward), nothing recomputed, the PUBLISHED work (the
    latent core at keys of 192 and values of 128, not the padded 256 the
    kernels run). With M the hidden size:

    * a delta mixer, H heads of D: the projections ``3 * 2 M H D`` and the
      out-projection ``2 H D M``; the decay's and the output gate's two
      low-rank matmuls each ``2 M D + 2 D H D``; beta ``2 M H``; the scan
      :func:`delta_scan_flops` a head (the convolutions' taps are no
      matmul and count 0);
    * a latent attention block, H heads of qk = nope + rope channels and
      values of v: the queries ``2 M H qk``, the way down ``2 M (kv_latent
      + rope)``, up ``2 kv_latent H (nope + v)``, the output ``2 H v M``,
      and the scores and the weighted sum over the causal half, ``(S + 1)
      / 2`` keys a query: ``2 H (qk + v) (S + 1) / 2``;
    * a dense layer's gated FFN: three matrices ``M x intermediate_size``;
    * an expert layer: the router onto all the experts' columns, the shared
      expert's three matrices on every token, and ``num_experts_per_token``
      routed experts of three matrices of which this chip holds ``held /
      experts`` (uniform routing: by arithmetic, not by the run's counts);
    * the head over the vocabulary slice at every position; the embedding
      lookup counts 0."""
    s = shapes(config, job)
    m = s["d_model"]
    h, d = s["delta_heads"], s["delta_head_dim"]
    delta = (3 * 2 * m * h * d + 2 * h * d * m
             + 2 * (2 * m * d + 2 * d * h * d) + 2 * m * h
             + h * delta_scan_flops(s))
    a, qk, v = s["heads"], s["qk_head_dim"], s["value_head_dim"]
    latent = (2 * m * a * qk + 2 * m * (s["kv_latent"] + s["qk_rope"])
              + 2 * s["kv_latent"] * a * (s["qk_nope"] + v)
              + 2 * a * v * m
              + 2 * a * (qk + v) * (s["seq"] + 1) / 2)
    dense = 3 * 2 * m * s["dense_ff"]
    experts = (2 * m * s["experts"] + 3 * 2 * m * s["d_shared"]
               + s["experts_per_token"] * s["held_experts"] / s["experts"]
               * 3 * 2 * m * s["d_expert"])
    forward = (s["delta_layers"] * delta + s["attention_layers"] * latent
               + s["dense_layers"] * dense + s["routed_layers"] * experts
               + s["head_calls"] * 2 * m * s["vocab"])
    return 3.0 * forward


def _period(kinds: list) -> list:
    """The shortest period ``kinds`` is whole repeats of."""
    for n in range(1, len(kinds) + 1):
        if len(kinds) % n == 0 and kinds == kinds[:n] * (len(kinds) // n):
            return kinds[:n]
    return kinds


def _model_config(config: dict, job: dict):
    import jax.numpy as jnp
    from horovod_tpu.models.transformer import TransformerConfig
    if job["seq_len"] > config["model_max_length"]:
        raise ValueError("seq_len beyond the configuration's positions")
    if (config["hidden_act"], config["moe_router_activation_func"],
            config["num_expert_group"], config["topk_group"],
            config["num_shared_experts"], config["moe_layer_freq"],
            config["tie_word_embeddings"], config["q_lora_rank"],
            config["mla_use_nope"], config["rope_scaling"],
            config["num_nextn_predict_layers"],
            config["num_key_value_heads"]) != (
                "silu", "sigmoid", 1, 1, 1, 1, False, None, True, None, 0,
                config["num_attention_heads"]):
        raise ValueError("not the blocks the program implements")
    share, dense = config["share"], config["first_k_dense_replace"]
    linear, mixers = config["linear_attn_config"], _mixers(config)
    period = _period(mixers[dense:])
    return TransformerConfig(
        vocab_size=config["vocab_size"], d_model=config["hidden_size"],
        n_heads=config["num_attention_heads"],
        head_width=config["qk_nope_head_dim"] + config["qk_rope_head_dim"],
        q_latent=0, kv_latent=config["kv_lora_rank"],
        rope_width=config["qk_rope_head_dim"],
        latent_rope=not config["mla_use_nope"],
        value_width=config["v_head_dim"],
        delta_heads=linear["num_heads"], delta_head_dim=linear["head_dim"],
        delta_taps=linear["short_conv_kernel_size"],
        delta_chunk=config.get("delta_chunk",
                               config["assumed"]["delta_chunk"]),
        n_layers=2 * (config["num_hidden_layers"] - dense),
        layer_pattern=tuple(kind for mixer in period
                            for kind in ((mixer,), ("experts",))),
        lead_pattern=tuple(kind for mixer in mixers[:dense]
                           for kind in ((mixer,), ("dense",))),
        d_ff=config["moe_intermediate_size"],
        dense_ff=config["intermediate_size"], ffn_gated=True,
        max_seq=config["model_max_length"],
        n_experts=config["num_experts"] * share["of"],
        moe_top_k=config["num_experts_per_token"], moe_gated=True,
        moe_activation="silu", moe_renormalize=config["moe_renormalize"],
        moe_balance_weight=0.0, moe_router_scores="sigmoid",
        moe_routed_scale=config["routed_scaling_factor"],
        moe_shared_width=(config["moe_intermediate_size"]
                          * config["num_shared_experts"]),
        expert_share=(share["index"], share["of"]),
        tie_embeddings=config["tie_word_embeddings"],
        norm_eps=config["rms_norm_eps"],
        dtype=jnp.dtype(config["compute_dtype"]))


def _init_function(c, config: dict):
    """Draws the tree of transformer.init_params (one stage; ``lead`` and
    ``layers`` a stack a word) in its shapes and scales from a key, on the
    device; the embedding at ``assumed.embedding_std``."""
    import jax
    import jax.numpy as jnp
    from horovod_tpu.models.mamba import (SSM_A_RANGE, SSM_DT_FLOOR,
                                          SSM_DT_RANGE)
    m, a, qk, rot = c.d_model, c.n_heads, c.head_dim, c.rope_width
    value = c.value_width
    h, d, taps = c.delta_heads, c.delta_head_dim, c.delta_taps
    f, fs, held = c.d_ff, c.moe_shared_width, c.held_experts
    pattern = [kind[0] for kind in c.layer_pattern]
    periods = c.n_layers // len(pattern)
    lead = [kind[0] for kind in c.lead_pattern]

    def make(key):
        keys = iter(jax.random.split(key, 96))

        def w(*shape, scale=None):
            scale = 1.0 / math.sqrt(shape[-2]) if scale is None else scale
            return jax.random.normal(next(keys), shape, jnp.float32) * scale

        def uniform(shape, low, high):
            return jax.random.uniform(next(keys), shape, jnp.float32, low,
                                      high)

        def ones(*shape):
            return jnp.ones(shape, jnp.float32)

        def dt_bias(*shape):    # softplus(dt_bias) = dt, log-uniform
            dt = jnp.exp(uniform(shape, *(math.log(x)
                                          for x in SSM_DT_RANGE)))
            return jnp.log(jnp.expm1(jnp.maximum(dt, SSM_DT_FLOOR)))

        def delta(*n):
            return {
                "dt_bias": dt_bias(*n, h * d),
                "a_log": jnp.log(uniform(n + (h,), *SSM_A_RANGE)),
                "ln1": ones(*n, m),
                "wq": w(*n, m, h * d), "wk": w(*n, m, h * d),
                "wv": w(*n, m, h * d),
                **{name: uniform(n + (taps, h * d), -1.0, 1.0)
                   / math.sqrt(taps)
                   for name in ("conv_q", "conv_k", "conv_v")},
                "wf_down": w(*n, m, d), "wf_up": w(*n, d, h * d),
                "w_beta": w(*n, m, h),
                "wg_down": w(*n, m, d), "wg_up": w(*n, d, h * d),
                "norm": ones(*n, d), "wo": w(*n, h * d, m)}

        def latent(*n):
            return {
                "ln1": ones(*n, m), "wq": w(*n, m, a * qk),
                "wkva": w(*n, m, c.kv_latent + rot),
                "kv_latent_norm": ones(*n, c.kv_latent),
                "wkvb": w(*n, c.kv_latent, a * (qk - rot + value)),
                "wo": w(*n, a * value, m)}

        def experts(*n):
            return {
                "ln2": ones(*n, m),
                "router": w(*n, m, c.n_experts, scale=0.02),
                "router_bias": jnp.zeros(n + (c.n_experts,), jnp.float32),
                "we1": w(*n, held, m, f), "we2": w(*n, held, f, m),
                "we3": w(*n, held, m, f),
                "ws1": w(*n, m, fs), "ws2": w(*n, fs, m),
                "ws3": w(*n, m, fs)}

        def dense(*n):
            return {"ln2": ones(*n, m), "w1": w(*n, m, c.dense_ff),
                    "w2": w(*n, c.dense_ff, m), "w3": w(*n, m, c.dense_ff)}
        draw = {"delta": delta, "latent": latent, "experts": experts,
                "dense": dense}
        return {
            "embed": w(c.vocab_size, m,
                       scale=config["assumed"]["embedding_std"]),
            "ln_f": ones(m),
            "lm_head": w(m, c.vocab_size),
            "layers": {word: draw[word](1, periods * pattern.count(word))
                       for word in dict.fromkeys(pattern)},
            "lead": {word: draw[word](lead.count(word))
                     for word in dict.fromkeys(lead)},
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
    of any delta layer, chunk, head and channel)."""

    def __init__(self, config: dict, job: dict, mesh, seed: int):
        import jax
        from horovod_tpu.models.transformer import param_shardings
        self.mesh = mesh
        self.cfg = _model_config(config, job)
        self.leaf_paths = _leaf_paths(_mixers(config),
                                      config["first_k_dense_replace"])
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
