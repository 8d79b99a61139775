"""Adapter ``granite_hybrid``: Granite 4.0-H (``model_type``
``granitemoehybrid``; the configuration is granite-4.0-h-micro's, dense)
through models/transformer.py and ``make_train_step``, the entry points the
other adapters call. A layer of the source is TWO blocks of one sublayer
here: its mixer by ``layer_types`` (``("mamba",)`` a Mamba-2 mixer of ONE
group at chunk 256, ``("attention", None, False)`` causal attention without
positions on 32 query / 8 key-value heads of 64) and a ``("dense",)`` SwiGLU
FFN, so one period of ten layers is a ``layer_pattern`` of twenty kinds; the
four scalar multipliers are ``embed_scale``, ``residual_scale``,
``attention_scale`` and ``logits_scale`` (= 1 / ``logits_scaling``); the
table is tied and sliced (the configuration's ``deployment``). On a TPU the
scans are ``hvd_ssm_scan`` / ``hvd_ssm_scan_bwd`` in head tiles, the
attention block ``hvd_flash_attention`` / ``hvd_flash_bwd`` at a head of 64
and the loss ``hvd_fused_xent``.

The configuration file uses the source's key names. The host batch, the
step and the checks are the ``flagship`` adapter's.
"""

from __future__ import annotations

import math

from adapters import flagship
from adapters.flagship import host_batch, tokens_per_step  # noqa: F401
from trees import as_shapes

#: ``layer_types``' words as the mixer's ``layer_pattern`` kind; every
#: mixer is followed by the FFN's
KINDS = {"mamba": ("mamba",), "attention": ("attention", None, False)}
FFN = ("dense",)


def _leaf_paths(layer_types: list) -> dict:
    """See trees.py; a stack a word under ``layers``, ``[stage, block of
    that word, ...]``. The table is reached by the lookup (times 12) and by
    the head (over 8); the first Mamba block's in-projection and norm weight
    see every later layer through the residual; the last one's decay rates
    and time-step bias see the scan's float32 sums directly; the attention
    block's key projection is a gradient summed over a group of 4 query
    heads, through the kernels at a head of 64; the last FFN's gate."""
    last_m = layer_types.count("mamba") - 1
    return {
        "table": (("embed",), None),
        "first_ssm_in": (("layers", "mamba", "ssm_in"), (0, 0)),
        "first_ssm_norm": (("layers", "mamba", "ssm_norm"), (0, 0)),
        "last_ssm_a_log": (("layers", "mamba", "ssm_a_log"), (0, last_m)),
        "last_ssm_dt_bias": (("layers", "mamba", "ssm_dt_bias"),
                             (0, last_m)),
        "attention_query": (("layers", "attention", "wq"), (0, 0)),
        "attention_key": (("layers", "attention", "wk"), (0, 0)),
        "last_ffn_gate": (("layers", "dense", "w1"),
                          (0, len(layer_types) - 1)),
    }


def shapes(config: dict, job: dict) -> dict:
    types = config["layer_types"]
    return {
        "batch": job["batch_per_chip"], "seq": job["seq_len"],
        "layers": config["num_hidden_layers"], "layer_types": types,
        "d_model": config["hidden_size"],
        "heads": config["num_attention_heads"],
        "kv_heads": config["num_key_value_heads"],
        "head_dim": config["hidden_size"] // config["num_attention_heads"],
        "d_ff": config["shared_intermediate_size"],
        "vocab": config["vocab_size"], "causal": True,
        # the attention blocks as the mixed roofline functions count them
        "layer_windows": [None] * types.count("attention"),
        "mamba_layers": types.count("mamba"),
        "ssm_heads": config["mamba_n_heads"],
        "ssm_head_dim": config["mamba_d_head"],
        "ssm_state": config["mamba_d_state"],
        "ssm_groups": config["mamba_n_groups"],
        "ssm_conv": config["mamba_d_conv"],
        "ssm_chunk": config["mamba_chunk_size"],
        "head_calls": 1,
        # what the reference needs beside sizes
        "norm_eps": config["rms_norm_eps"],
        "embedding_multiplier": config["embedding_multiplier"],
        "residual_multiplier": config["residual_multiplier"],
        "attention_multiplier": config["attention_multiplier"],
        "logits_scaling": config["logits_scaling"],
    }


def flops_per_token(config: dict, job: dict) -> float:
    """Matmul FLOPs this chip's share needs per trained token: forward +
    backward (= 3 x forward), nothing recomputed. With M the hidden size:

    * every layer's FFN: ``3 * 2 M F`` (gate, up, down);
    * a Mamba mixer: the in-projection ``2 M (2 inner + 2 G N + H)`` onto z,
      x, B, C and dt, the out-projection ``2 inner M``, the convolution ``2
      K (inner + 2 G N)``, and the scan in its chunked form at chunk Q with
      the causal half of the products inside a chunk (a position meets ``(Q
      + 1) / 2`` of its chunk): the scores ``c . b`` ``2 G N (Q + 1) / 2``
      (once a group: ONE here), the scores times x ``2 H P (Q + 1) / 2``, a
      chunk's state ``x^T b`` ``2 H P N`` and the carried state's part ``c .
      H`` ``2 H P N`` (``adapters/nemotron_h.py``'s count);
    * the attention mixer: q and o at ``heads * head_dim``, k and v at
      ``kv_heads * head_dim``, the scores over the causal half, ``(S + 1) /
      2`` keys a query;
    * the tied head over the vocabulary slice at every position; the
      embedding lookup counts 0."""
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
    forward = (s["layers"] * 3 * 2 * m * s["d_ff"]
               + s["mamba_layers"] * mamba
               + len(s["layer_windows"]) * attention + 2 * m * s["vocab"])
    return 3.0 * forward


def _model_config(config: dict, job: dict):
    import jax.numpy as jnp
    from horovod_tpu.models.transformer import TransformerConfig
    if job["seq_len"] > config["max_position_embeddings"]:
        raise ValueError("seq_len beyond the configuration's positions")
    if (config["hidden_act"], config["normalization_function"],
            config["position_embedding_type"], config["mamba_conv_bias"],
            config["tie_word_embeddings"], config["num_local_experts"],
            config["shared_intermediate_size"]) != (
                "silu", "rmsnorm", "nope", True, True, 0,
                config["intermediate_size"]) or any(
                    config[k] for k in ("attention_bias", "mamba_proj_bias")):
        raise ValueError("not the blocks the program implements")
    types = config["layer_types"]
    if len(types) != config["num_hidden_layers"]:
        raise ValueError("layer_types is not num_hidden_layers words")
    if (config["mamba_n_heads"] * config["mamba_d_head"]
            != config["mamba_expand"] * config["hidden_size"]):
        raise ValueError("mamba_n_heads * mamba_d_head is not mamba_expand "
                         "* hidden_size")
    pattern = tuple(kind for word in types for kind in (KINDS[word], FFN))
    return TransformerConfig(
        vocab_size=config["vocab_size"], d_model=config["hidden_size"],
        n_heads=config["num_attention_heads"], n_layers=len(pattern),
        d_ff=config["shared_intermediate_size"],
        dense_ff=config["shared_intermediate_size"],
        max_seq=config["max_position_embeddings"], ffn_gated=True,
        tie_embeddings=config["tie_word_embeddings"],
        norm_eps=config["rms_norm_eps"],
        dtype=jnp.dtype(config["compute_dtype"]),
        head_width=config["hidden_size"] // config["num_attention_heads"],
        n_kv_heads=config["num_key_value_heads"], layer_pattern=pattern,
        ssm_heads=config["mamba_n_heads"],
        ssm_head_dim=config["mamba_d_head"],
        ssm_state=config["mamba_d_state"],
        ssm_groups=config["mamba_n_groups"], ssm_conv=config["mamba_d_conv"],
        ssm_chunk=config["mamba_chunk_size"],
        embed_scale=float(config["embedding_multiplier"]),
        residual_scale=config["residual_multiplier"],
        attention_scale=config["attention_multiplier"],
        logits_scale=1.0 / config["logits_scaling"],
        remat=config["assumed"]["checkpoint_every_block"] or None)


def _init_function(c, config: dict):
    """Draws the tree of transformer.init_params (one stage, a stack a
    word: Mamba, dense, attention) in its shapes and scales from a key, on
    the device; the Mamba leaves by ``assumed.mamba_init``."""
    import jax
    import jax.numpy as jnp
    m, f = c.d_model, c.dense_ff
    q, kv = c.n_heads * c.head_dim, c.kv_heads * c.head_dim
    heads, inner, wide = c.ssm_heads, c.ssm_inner, c.ssm_conv_width
    words = [kind[0] for kind in c.layer_pattern]
    n_m, n_f, n_a = (words.count(w) for w in ("mamba", "dense", "attention"))
    dt_lo, dt_hi, dt_floor = config["assumed"]["time_step"]

    def make(key):
        k = jax.random.split(key, 14)

        def w(key, *shape, scale=None):
            scale = 1.0 / math.sqrt(shape[-2]) if scale is None else scale
            return jax.random.normal(key, shape, jnp.float32) * scale

        def uniform(key, lo, hi, *shape):
            return jax.random.uniform(key, shape, jnp.float32, lo, hi)

        def ones(*shape):
            return jnp.ones((1,) + shape, jnp.float32)
        dt = jnp.maximum(jnp.exp(uniform(
            k[1], math.log(dt_lo), math.log(dt_hi), 1, n_m, heads)),
            dt_floor)
        taps = 1.0 / math.sqrt(c.ssm_conv)
        return {
            "embed": w(k[0], c.vocab_size, m,
                       scale=config["assumed"]["embedding_std"]),
            "ln_f": jnp.ones((m,), jnp.float32),
            "layers": {
                "mamba": {
                    "ln1": ones(n_m, m),
                    "ssm_in": w(k[2], 1, n_m, m, inner + wide + heads),
                    "ssm_conv_w": uniform(k[3], -taps, taps, 1, n_m,
                                          c.ssm_conv, wide),
                    "ssm_conv_b": uniform(k[4], -taps, taps, 1, n_m, wide),
                    "ssm_dt_bias": jnp.log(jnp.expm1(dt)),
                    "ssm_a_log": jnp.log(uniform(k[5], 1.0, 16.0, 1, n_m,
                                                 heads)),
                    "ssm_d": ones(n_m, heads),
                    "ssm_norm": ones(n_m, inner),
                    "ssm_out": w(k[6], 1, n_m, inner, m),
                },
                "dense": {
                    "ln2": ones(n_f, m),
                    "w1": w(k[7], 1, n_f, m, f),
                    "w2": w(k[8], 1, n_f, f, m),
                    "w3": w(k[9], 1, n_f, m, f),
                },
                "attention": {
                    "ln1": ones(n_a, m),
                    "wq": w(k[10], 1, n_a, m, q),
                    "wk": w(k[11], 1, n_a, m, kv),
                    "wv": w(k[12], 1, n_a, m, kv),
                    "wo": w(k[13], 1, n_a, q, m),
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


class Cell(flagship.Cell):
    """The ``flagship`` cell's checks and step on this adapter's
    configuration and tree."""

    def __init__(self, config: dict, job: dict, mesh, seed: int):
        import jax
        from horovod_tpu.models.transformer import param_shardings
        self.mesh = mesh
        self.cfg = _model_config(config, job)
        self.leaf_paths = _leaf_paths(config["layer_types"])
        self.params = jax.jit(
            _init_function(self.cfg, config),
            out_shardings=param_shardings(self.cfg, mesh))(
                jax.random.PRNGKey(seed))
        self.opt_state = None
        self._step = None
