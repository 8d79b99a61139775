"""Adapter ``nemotron_h``: Nemotron-H (arXiv:2504.03624; the configuration
is Nemotron-3-Nano-30B-A3B's) through models/transformer.py and
``make_train_step``, the entry points the other adapters call: a stack of
blocks of ONE sublayer each, by the letters of ``hybrid_override_pattern``
(``layer_pattern`` of ``("mamba",)``, ``("experts",)``, ``("attention",
None, False)``): Mamba-2 mixers (``ssm_*``), sigmoid-routed ungated ReLU²
experts with a shared expert (``moe_router_scores``, ``moe_routed_scale``,
``moe_activation`` "relu2", ``moe_shared_width``), full causal attention
without positions on 32 query / 2 key-value heads, and one chip's share of
every expert layer and of the vocabulary (``expert_share``; the
configuration's ``deployment``). On a TPU the attention block is
``hvd_flash_attention`` / ``hvd_flash_bwd``, the routed experts' matmuls are
``hvd_moe_gmm`` (a block spans the whole expert width 1856, which no
128-multiple divides) and the loss is ``hvd_fused_xent``; the Mamba-2 scan
is ``jax.numpy`` in its chunked form.

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

#: ``hybrid_override_pattern``'s letters as ``layer_pattern`` kinds
KINDS = {"M": ("mamba",), "E": ("experts",), "*": ("attention", None, False)}


def _leaf_paths(pattern: str) -> dict:
    """See trees.py; a pattern of one-sublayer blocks has a stack a word
    under ``layers``, ``[stage, block of that word, ...]``. The first Mamba
    block's in-projection sees every later block through the residual; the
    last Mamba block's decay rates see the scan's float32 sums directly;
    the attention block's key projection is a gradient summed over a group
    of 16 query heads; the router, the held experts' way down and the
    shared expert's see the choices directly."""
    last_m, last_e = pattern.count("M") - 1, pattern.count("E") - 1
    return {
        "lm_head": (("lm_head",), None),
        "first_ssm_in": (("layers", "mamba", "ssm_in"), (0, 0)),
        "last_ssm_a_log": (("layers", "mamba", "ssm_a_log"), (0, last_m)),
        "attention_key": (("layers", "attention", "wk"), (0, 0)),
        "last_router": (("layers", "experts", "router"), (0, last_e)),
        "last_experts_down": (("layers", "experts", "we2"), (0, last_e)),
        "last_shared_down": (("layers", "experts", "ws2"), (0, last_e)),
    }


def shapes(config: dict, job: dict) -> dict:
    pattern, share = config["hybrid_override_pattern"], config["share"]
    held = config["n_routed_experts"]
    return {
        "batch": job["batch_per_chip"], "seq": job["seq_len"],
        "layers": config["num_hidden_layers"], "pattern": pattern,
        "d_model": config["hidden_size"],
        "heads": config["num_attention_heads"],
        "kv_heads": config["num_key_value_heads"],
        "head_dim": config["head_dim"],
        "d_ff": config["moe_intermediate_size"],
        "vocab": config["vocab_size"], "causal": True,
        # the attention blocks as the mixed roofline functions count them
        "layer_windows": [None] * pattern.count("*"),
        "mamba_layers": pattern.count("M"),
        "expert_layers": pattern.count("E"),
        "ssm_heads": config["mamba_num_heads"],
        "ssm_head_dim": config["mamba_head_dim"],
        "ssm_state": config["ssm_state_size"],
        "ssm_groups": config["n_groups"], "ssm_conv": config["conv_kernel"],
        "ssm_chunk": config["chunk_size"],
        "experts": held * share["of"],
        "held_experts": held, "first_expert": held * share["index"],
        "experts_per_token": config["num_experts_per_tok"],
        "d_expert": config["moe_intermediate_size"],
        "d_shared": config["moe_shared_expert_intermediate_size"],
        # what the reference needs beside sizes
        "norm_eps": config["norm_eps"],
        "routed_scale": config["routed_scaling_factor"],
    }


def flops_per_token(config: dict, job: dict) -> float:
    """Matmul FLOPs this chip's share needs per trained token: forward +
    backward (= 3 x forward), nothing recomputed. With M the hidden size:

    * a Mamba block: the in-projection ``2 M (2 inner + 2 G N + H)`` onto z,
      x, B, C and dt, the out-projection ``2 inner M``, the convolution ``2
      K (inner + 2 G N)``, and the scan in its chunked form at chunk Q with
      the causal half of the products inside a chunk (a position meets ``(Q
      + 1) / 2`` of its chunk): the scores ``c . b`` ``2 G N (Q + 1) / 2``,
      the scores times x ``2 H P (Q + 1) / 2``, a chunk's state ``x^T b``
      ``2 H P N`` and the carried state's part ``c . H`` ``2 H P N``;
    * the attention block: q and o at ``heads * head_dim``, k and v at
      ``kv_heads * head_dim``, the scores over the causal half, ``(S + 1) /
      2`` keys a query;
    * an expert block: the router onto all the experts' columns, the
      shared expert's two matrices on every token, and
      ``experts_per_token`` routed experts of two matrices of which this
      chip holds ``held / experts`` (uniform routing: by arithmetic, not
      by the run's counts);
    * the head over the vocabulary slice at every position; the embedding
      lookup counts 0."""
    s = shapes(config, job)
    m = s["d_model"]
    heads, p, n, g = (s[k] for k in ("ssm_heads", "ssm_head_dim",
                                     "ssm_state", "ssm_groups"))
    inner, in_chunk = heads * p, (s["ssm_chunk"] + 1) / 2
    mamba = (2 * m * (2 * inner + 2 * g * n + heads) + 2 * inner * m
             + 2 * s["ssm_conv"] * (inner + 2 * g * n)
             + 2 * g * n * in_chunk + 2 * heads * p * in_chunk
             + 2 * 2 * heads * p * n)
    q_width = s["heads"] * s["head_dim"]
    kv_width = s["kv_heads"] * s["head_dim"]
    attention = (2 * 2 * m * q_width + 2 * 2 * m * kv_width
                 + 2 * 2 * q_width * (s["seq"] + 1) / 2)
    experts = (2 * m * s["experts"] + 2 * 2 * m * s["d_shared"]
               + s["experts_per_token"] * s["held_experts"] / s["experts"]
               * 2 * 2 * m * s["d_expert"])
    forward = (s["mamba_layers"] * mamba
               + len(s["layer_windows"]) * attention
               + s["expert_layers"] * experts + 2 * m * s["vocab"])
    return 3.0 * forward


def _model_config(config: dict, job: dict):
    import jax.numpy as jnp
    from horovod_tpu.models.transformer import TransformerConfig
    if job["seq_len"] > config["max_position_embeddings"]:
        raise ValueError("seq_len beyond the configuration's positions")
    if (config["mlp_hidden_act"], config["mamba_hidden_act"],
            config["n_shared_experts"], config["n_group"],
            config["topk_group"], config["norm_topk_prob"],
            config["use_conv_bias"], config["tie_word_embeddings"]) != (
                "relu2", "silu", 1, 1, 1, True, True, False) or any(
                    config[k] for k in ("attention_bias", "mamba_proj_bias",
                                        "mlp_bias", "use_bias",
                                        "sliding_window")):
        raise ValueError("not the blocks the program implements")
    pattern, share = config["hybrid_override_pattern"], config["share"]
    if len(pattern) != config["num_hidden_layers"]:
        raise ValueError("hybrid_override_pattern is not num_hidden_layers "
                         "letters")
    period = next(p for p in range(1, len(pattern) + 1)
                  if len(pattern) % p == 0
                  and pattern == pattern[:p] * (len(pattern) // p))
    return TransformerConfig(
        vocab_size=config["vocab_size"], d_model=config["hidden_size"],
        n_heads=config["num_attention_heads"],
        n_layers=config["num_hidden_layers"],
        d_ff=config["moe_intermediate_size"],
        max_seq=config["max_position_embeddings"],
        n_experts=config["n_routed_experts"] * share["of"],
        moe_top_k=config["num_experts_per_tok"], moe_gated=False,
        moe_activation="relu2", moe_renormalize=config["norm_topk_prob"],
        moe_balance_weight=0.0, moe_router_scores="sigmoid",
        moe_routed_scale=config["routed_scaling_factor"],
        moe_shared_width=config["moe_shared_expert_intermediate_size"],
        tie_embeddings=config["tie_word_embeddings"],
        norm_eps=config["norm_eps"],
        dtype=jnp.dtype(config["compute_dtype"]),
        head_width=config["head_dim"],
        n_kv_heads=config["num_key_value_heads"],
        layer_pattern=tuple(KINDS[letter] for letter in pattern[:period]),
        expert_share=(share["index"], share["of"]),
        ssm_heads=config["mamba_num_heads"],
        ssm_head_dim=config["mamba_head_dim"],
        ssm_state=config["ssm_state_size"], ssm_groups=config["n_groups"],
        ssm_conv=config["conv_kernel"], ssm_chunk=config["chunk_size"])


def _init_function(c, config: dict):
    """Draws the tree of transformer.init_params (one stage, a stack a
    word: Mamba, experts, attention) in its shapes and scales from a key,
    on the device; the Mamba leaves by the configuration's ``time_step_*``
    (``assumed.mamba_init``), the embedding at ``assumed.embedding_std``."""
    import jax
    import jax.numpy as jnp
    m, f, fs = c.d_model, c.d_ff, c.moe_shared_width
    q, kv = c.n_heads * c.head_dim, c.kv_heads * c.head_dim
    held, heads, inner, wide = (c.held_experts, c.ssm_heads, c.ssm_inner,
                                c.ssm_conv_width)
    words = [kind[0] for kind in c.layer_pattern]
    periods = c.n_layers // len(words)
    n_m, n_e, n_a = (periods * words.count(w)
                     for w in ("mamba", "experts", "attention"))
    dt_lo, dt_hi, dt_floor = (config[k] for k in (
        "time_step_min", "time_step_max", "time_step_floor"))

    def make(key):
        k = jax.random.split(key, 18)

        def w(key, *shape, scale=None):
            scale = 1.0 / math.sqrt(shape[-2]) if scale is None else scale
            return jax.random.normal(key, shape, jnp.float32) * scale

        def uniform(key, lo, hi, *shape):
            return jax.random.uniform(key, shape, jnp.float32, lo, hi)

        def ones(*shape):
            return jnp.ones((1,) + shape, jnp.float32)
        dt = jnp.maximum(jnp.exp(uniform(
            k[2], math.log(dt_lo), math.log(dt_hi), 1, n_m, heads)),
            dt_floor)
        taps = 1.0 / math.sqrt(c.ssm_conv)
        return {
            "embed": w(k[0], c.vocab_size, m,
                       scale=config["assumed"]["embedding_std"]),
            "ln_f": jnp.ones((m,), jnp.float32),
            "lm_head": w(k[1], m, c.vocab_size),
            "layers": {
                "mamba": {
                    "ln1": ones(n_m, m),
                    "ssm_in": w(k[3], 1, n_m, m, inner + wide + heads),
                    "ssm_conv_w": uniform(k[4], -taps, taps, 1, n_m,
                                          c.ssm_conv, wide),
                    "ssm_conv_b": uniform(k[5], -taps, taps, 1, n_m, wide),
                    "ssm_dt_bias": jnp.log(jnp.expm1(dt)),
                    "ssm_a_log": jnp.log(uniform(k[6], 1.0, 16.0, 1, n_m,
                                                 heads)),
                    "ssm_d": ones(n_m, heads),
                    "ssm_norm": ones(n_m, inner),
                    "ssm_out": w(k[7], 1, n_m, inner, m),
                },
                "experts": {
                    "ln2": ones(n_e, m),
                    "router": w(k[8], 1, n_e, m, c.n_experts, scale=0.02),
                    "router_bias": jnp.zeros((1, n_e, c.n_experts),
                                             jnp.float32),
                    "we1": w(k[9], 1, n_e, held, m, f),
                    "we2": w(k[10], 1, n_e, held, f, m),
                    "ws1": w(k[11], 1, n_e, m, fs),
                    "ws2": w(k[12], 1, n_e, fs, m),
                },
                "attention": {
                    "ln1": ones(n_a, m),
                    "wq": w(k[13], 1, n_a, m, q),
                    "wk": w(k[14], 1, n_a, m, kv),
                    "wv": w(k[15], 1, n_a, m, kv),
                    "wo": w(k[16], 1, n_a, q, m),
                },
            },
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
    ``held_rows``: the step's assignments to the experts held here."""

    def __init__(self, config: dict, job: dict, mesh, seed: int):
        import jax
        from horovod_tpu.models.transformer import param_shardings
        self.mesh = mesh
        self.cfg = _model_config(config, job)
        self.leaf_paths = _leaf_paths(config["hybrid_override_pattern"])
        self.params = jax.jit(
            _init_function(self.cfg, config),
            out_shardings=param_shardings(self.cfg, mesh))(
                jax.random.PRNGKey(seed))
        self.opt_state = None
        self._step = None
        self.last_aux = None
