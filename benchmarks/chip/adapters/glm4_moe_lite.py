"""Adapter ``glm4_moe_lite``: GLM-4.7-Flash (``model_type`` ``glm4_moe_lite``;
its attention, router and prediction module are DeepSeek-V2/V3's,
arXiv:2405.04434 and arXiv:2412.19437) through models/transformer.py and
``make_train_step``, the entry points the other adapters call: latent
attention blocks (``("latent",)`` of ``layer_pattern``: ``q_latent``,
``kv_latent``, ``rope_width``), ``first_k_dense_replace`` dense layers
leading the expert layers (``lead_pattern``, ``dense_ff``), sigmoid-routed
SiLU-gated experts with a gated shared expert (``moe_router_scores``,
``moe_routed_scale``, ``moe_shared_width``), a multi-token-prediction module
on the main head (``mtp_depth``, ``mtp_weight``), and one chip's share of
every expert layer and of the vocabulary (``expert_share``; the
configuration's ``deployment``). On a TPU the attention core is
``hvd_flash_attention`` / ``hvd_flash_bwd`` at 20 / 20 heads of 256, the
routed experts' matmuls are ``hvd_moe_gmm`` and both heads' losses are
``hvd_fused_xent``.

The configuration file uses the source's key names. ``n_routed_experts``
counts the experts held here; the router's width is that times
``share.of``. The host batch, the step and the checks are the ``olmoe``
adapter's.
"""

from __future__ import annotations

import math

from adapters import olmoe
from adapters.flagship import host_batch, tokens_per_step  # noqa: F401
from trees import as_shapes


def _leaf_paths(expert_layers: int) -> dict:
    """See trees.py; ``layers`` is a stack a word ``[stage, block, ...]``,
    ``lead`` and ``mtp.layers`` ``[block, ...]``. Layer 0's query latent
    sees every later layer through the residual; the last main layer's
    ``wkva`` holds the 64 rope columns whose gradient is summed over 20
    heads; ``wkvb`` brings keys and values up from the latent; the router
    and the held experts' way down see the choices directly; ``proj`` is
    the prediction module's, and the head is read by both predictions."""
    last = (0, expert_layers - 1)
    return {
        "lm_head": (("lm_head",), None),
        "first_query_down": (("lead", "latent", "wqa"), (0,)),
        "last_kv_down": (("layers", "latent", "wkva"), last),
        "last_kv_up": (("layers", "latent", "wkvb"), last),
        "dense_down": (("lead", "dense", "w2"), (0,)),
        "last_router": (("layers", "experts", "router"), last),
        "last_experts_down": (("layers", "experts", "we2"), last),
        "mtp_proj": (("mtp", "proj"), None),
    }


def shapes(config: dict, job: dict) -> dict:
    share, held = config["share"], config["n_routed_experts"]
    layers, dense = (config["num_hidden_layers"],
                     config["first_k_dense_replace"])
    mtp = config["num_nextn_predict_layers"]
    nope, rot = config["qk_nope_head_dim"], config["qk_rope_head_dim"]
    return {
        "batch": job["batch_per_chip"], "seq": job["seq_len"],
        "layers": layers, "d_model": config["hidden_size"],
        "heads": config["num_attention_heads"],
        "kv_heads": config["num_key_value_heads"],
        "head_dim": nope + rot, "qk_nope": nope, "qk_rope": rot,
        "v_head": config["v_head_dim"],
        "q_latent": config["q_lora_rank"],
        "kv_latent": config["kv_lora_rank"],
        "d_ff": config["intermediate_size"], "vocab": config["vocab_size"],
        "causal": True,
        "dense_layers": dense, "expert_layers": layers - dense,
        "mtp_layers": mtp,
        "experts": held * share["of"],
        "held_experts": held, "first_expert": held * share["index"],
        "experts_per_token": config["num_experts_per_tok"],
        "d_expert": config["moe_intermediate_size"],
        "d_shared": (config["moe_intermediate_size"]
                     * config["n_shared_experts"]),
        # what the kernels' roofline functions count: an attention block a
        # layer and one in the prediction module, each one's forward kernel
        # run again by its checkpointed backward (``assumed.recomputation``);
        # an expert layer after the dense ones and the module's; the main
        # head's call and the module's
        "attention_layers": layers + mtp,
        "attention_forward_calls": 2 * (layers + mtp),
        "routed_layers": layers - dense + mtp,
        "head_calls": 1 + mtp,
        # what the reference needs beside sizes
        "norm_eps": config["rms_norm_eps"],
        "rope_theta": float(config["rope_theta"]),
        "routed_scale": config["routed_scaling_factor"],
        "mtp_weight": config["assumed"]["mtp_loss_weight"],
    }


def flops_per_token(config: dict, job: dict) -> float:
    """Matmul FLOPs this chip's share needs per trained token: forward +
    backward (= 3 x forward), nothing recomputed. With M the hidden size, H
    heads of D = nope + rope channels:

    * a latent attention block: the projections down ``2 M (q_latent +
      kv_latent + rope)``, up ``2 q_latent H D + 2 kv_latent H (nope +
      v)``, the output ``2 H v M``, and the scores and the weighted sum
      over the causal half, ``(S + 1) / 2`` keys a query: ``2 H (D + v) (S
      + 1) / 2``; one a layer and one in the prediction module;
    * a dense layer's gated FFN: three matrices ``M x intermediate_size``;
    * an expert layer (the layers after the dense ones, and the module's):
      the router onto all the experts' columns, the shared expert's three
      matrices on every token, and ``num_experts_per_tok`` routed experts
      of three matrices of which this chip holds ``held / experts``
      (uniform routing: by arithmetic, not by the run's counts);
    * the prediction module's projection ``2 (2 M) M``;
    * the head over the vocabulary slice at every position, once for each
      prediction; the embedding lookups count 0."""
    s = shapes(config, job)
    m, h, d, v = s["d_model"], s["heads"], s["head_dim"], s["v_head"]
    attention = (2 * m * (s["q_latent"] + s["kv_latent"] + s["qk_rope"])
                 + 2 * s["q_latent"] * h * d
                 + 2 * s["kv_latent"] * h * (s["qk_nope"] + v)
                 + 2 * h * v * m
                 + 2 * h * (d + v) * (s["seq"] + 1) / 2)
    dense = 3 * 2 * m * s["d_ff"]
    experts = (2 * m * s["experts"] + 3 * 2 * m * s["d_shared"]
               + s["experts_per_token"] * s["held_experts"] / s["experts"]
               * 3 * 2 * m * s["d_expert"])
    forward = (s["attention_layers"] * attention
               + s["dense_layers"] * dense + s["routed_layers"] * experts
               + s["mtp_layers"] * 2 * 2 * m * m
               + s["head_calls"] * 2 * m * s["vocab"])
    return 3.0 * forward


def _model_config(config: dict, job: dict):
    import jax.numpy as jnp
    from horovod_tpu.models.transformer import TransformerConfig
    if job["seq_len"] > config["max_position_embeddings"]:
        raise ValueError("seq_len beyond the configuration's positions")
    if (config["hidden_act"], config["topk_method"], config["n_group"],
            config["topk_group"], config["n_shared_experts"],
            config["norm_topk_prob"], config["tie_word_embeddings"],
            config["attention_bias"], config["rope_scaling"],
            config["num_key_value_heads"], config["v_head_dim"]) != (
                "silu", "noaux_tc", 1, 1, 1, True, False, False, None,
                config["num_attention_heads"],
                config["qk_nope_head_dim"] + config["qk_rope_head_dim"]):
        raise ValueError("not the blocks the program implements")
    share, dense = config["share"], config["first_k_dense_replace"]
    period = (("latent",), ("experts",))
    return TransformerConfig(
        vocab_size=config["vocab_size"], d_model=config["hidden_size"],
        n_heads=config["num_attention_heads"],
        head_width=config["qk_nope_head_dim"] + config["qk_rope_head_dim"],
        q_latent=config["q_lora_rank"], kv_latent=config["kv_lora_rank"],
        rope_width=config["qk_rope_head_dim"],
        n_layers=len(period) * (config["num_hidden_layers"] - dense),
        layer_pattern=period,
        lead_pattern=(("latent",), ("dense",)) * dense,
        d_ff=config["moe_intermediate_size"],
        dense_ff=config["intermediate_size"], ffn_gated=True,
        max_seq=config["max_position_embeddings"],
        n_experts=config["n_routed_experts"] * share["of"],
        moe_top_k=config["num_experts_per_tok"], moe_gated=True,
        moe_activation="silu", moe_renormalize=config["norm_topk_prob"],
        moe_balance_weight=0.0, moe_router_scores="sigmoid",
        moe_routed_scale=config["routed_scaling_factor"],
        moe_shared_width=(config["moe_intermediate_size"]
                          * config["n_shared_experts"]),
        expert_share=(share["index"], share["of"]),
        mtp_depth=config["num_nextn_predict_layers"],
        mtp_weight=config["assumed"]["mtp_loss_weight"],
        tie_embeddings=config["tie_word_embeddings"],
        norm_eps=config["rms_norm_eps"],
        rope_theta=float(config["rope_theta"]),
        dtype=jnp.dtype(config["compute_dtype"]))


def _init_function(c, config: dict):
    """Draws the tree of transformer.init_params (one stage; ``lead``,
    ``layers`` and ``mtp.layers`` a stack a word) in its shapes and scales
    from a key, on the device; the embedding at
    ``assumed.embedding_std``."""
    import jax
    import jax.numpy as jnp
    m, h, d, rot = c.d_model, c.n_heads, c.head_dim, c.rope_width
    f, fs, held = c.d_ff, c.moe_shared_width, c.held_experts
    n_main = c.n_layers // len(c.layer_pattern)
    n_lead = len(c.lead_pattern) // 2

    def make(key):
        keys = iter(jax.random.split(key, 64))

        def w(*shape, scale=None):
            scale = 1.0 / math.sqrt(shape[-2]) if scale is None else scale
            return jax.random.normal(next(keys), shape, jnp.float32) * scale

        def ones(*shape):
            return jnp.ones(shape, jnp.float32)

        def latent(*lead):
            return {
                "ln1": ones(*lead, m),
                "wqa": w(*lead, m, c.q_latent),
                "q_latent_norm": ones(*lead, c.q_latent),
                "wqb": w(*lead, c.q_latent, h * d),
                "wkva": w(*lead, m, c.kv_latent + rot),
                "kv_latent_norm": ones(*lead, c.kv_latent),
                "wkvb": w(*lead, c.kv_latent, h * (2 * d - rot)),
                "wo": w(*lead, h * d, m)}

        def experts(*lead):
            return {
                "ln2": ones(*lead, m),
                "router": w(*lead, m, c.n_experts, scale=0.02),
                "router_bias": jnp.zeros(lead + (c.n_experts,),
                                         jnp.float32),
                "we1": w(*lead, held, m, f), "we2": w(*lead, held, f, m),
                "we3": w(*lead, held, m, f),
                "ws1": w(*lead, m, fs), "ws2": w(*lead, fs, m),
                "ws3": w(*lead, m, fs)}
        return {
            "embed": w(c.vocab_size, m,
                       scale=config["assumed"]["embedding_std"]),
            "ln_f": ones(m),
            "lm_head": w(m, c.vocab_size),
            "layers": {"latent": latent(1, n_main),
                       "experts": experts(1, n_main)},
            "lead": {
                "latent": latent(n_lead),
                "dense": {"ln2": ones(n_lead, m),
                          "w1": w(n_lead, m, c.dense_ff),
                          "w2": w(n_lead, c.dense_ff, m),
                          "w3": w(n_lead, m, c.dense_ff)}},
            "mtp": {"norm_h": ones(m), "norm_e": ones(m),
                    "proj": w(2 * m, m), "ln_f": ones(m),
                    "layers": {"latent": latent(1), "experts": experts(1)}},
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
    """The ``olmoe`` cell's checks and step (the loss with its auxiliary
    term, ``program_choices``, ``dropped`` held to 0 after the window) on
    this adapter's configuration and tree. ``last_aux`` also holds
    ``held_rows`` (the step's assignments to the experts held here, the
    prediction module's layer among them), ``main_loss`` and
    ``mtp_loss``."""

    def __init__(self, config: dict, job: dict, mesh, seed: int):
        import jax
        from horovod_tpu.models.transformer import param_shardings
        self.mesh = mesh
        self.cfg = _model_config(config, job)
        self.leaf_paths = _leaf_paths(shapes(config, job)["expert_layers"])
        self.params = jax.jit(
            _init_function(self.cfg, config),
            out_shardings=param_shardings(self.cfg, mesh))(
                jax.random.PRNGKey(seed))
        self.opt_state = None
        self._step = None
        self.last_aux = None
