"""Adapter ``smallthinker``: SmallThinker (arXiv:2507.20984) through the
flagship block of models/transformer.py and ``make_train_step``, the entry
points the ``flagship`` and ``olmoe`` adapters call: window and full (NoPE)
layers in one stack (``layer_pattern``), 28 query heads of 128 on 4
key/value heads (``head_width``, ``n_kv_heads``), a router that reads the
block's input (``moe_router_input``), ReLU-gated experts
(``moe_activation``), and one chip's share of every layer's experts and of
the vocabulary (``expert_share``; the configuration's ``deployment``). On a
TPU every layer's attention is ``hvd_flash_attention`` / ``hvd_flash_bwd``
with the band and the group in their index maps, the experts' matmuls are
``hvd_moe_gmm`` and the loss is ``hvd_fused_xent``.

The configuration file uses the source's key names. ``moe_num_primary_
experts`` counts the experts held here; the router's width is that times
``share.of``. The host batch, the step and the checks are the ``olmoe``
adapter's.
"""

from __future__ import annotations

import math

from adapters import olmoe
from adapters.flagship import host_batch, tokens_per_step  # noqa: F401
from trees import as_shapes


def _leaf_paths(layer_windows: list) -> dict:
    """See trees.py; weights are stacked ``[stage, layer, ...]``. Layer 0
    is the full layer without positions; a window layer's ``wk`` is a
    gradient summed over a group of query heads; the router and the held
    experts' way down see the choices directly."""
    n = len(layer_windows)
    window = next(i for i, w in enumerate(layer_windows) if w is not None)
    return {
        "lm_head": (("lm_head",), None),
        "first_query": (("layers", "wq"), (0, 0)),
        "window_key": (("layers", "wk"), (0, window)),
        "last_router": (("layers", "router"), (0, n - 1)),
        "last_experts_down": (("layers", "we2"), (0, n - 1)),
    }


def _layer_kinds(config: dict) -> list:
    """[(window or None, rope or not)] of the layers that are run: the
    first ``num_hidden_layers`` entries of the source's two lists."""
    n = config["num_hidden_layers"]
    return [(config["sliding_window_size"] if w else None, bool(r))
            for w, r in zip(config["sliding_window_layout"][:n],
                            config["rope_layout"][:n])]


def shapes(config: dict, job: dict) -> dict:
    kinds, share = _layer_kinds(config), config["share"]
    held = config["moe_num_primary_experts"]
    return {
        "batch": job["batch_per_chip"], "seq": job["seq_len"],
        "layers": config["num_hidden_layers"],
        "d_model": config["hidden_size"],
        "heads": config["num_attention_heads"],
        "kv_heads": config["num_key_value_heads"],
        "head_dim": config["head_dim"],
        "d_ff": config["moe_ffn_hidden_size"], "vocab": config["vocab_size"],
        "causal": True,
        "layer_windows": [w for w, _ in kinds],
        "layer_rope": [r for _, r in kinds],
        "experts": held * share["of"],
        "held_experts": held, "first_expert": held * share["index"],
        "experts_per_token": config["moe_num_active_primary_experts"],
        "d_expert": config["moe_ffn_hidden_size"],
        # what the reference needs beside sizes
        "norm_eps": config["rms_norm_eps"],
        "rope_theta": config["rope_theta"],
        "balance_weight": config["router_aux_loss_coef"],
    }


def mean_live_keys(seq: int, window) -> float:
    """Keys a query meets on average over positions 0 .. seq - 1: ``t + 1``
    at position ``t``, at most ``window``."""
    w = seq if window is None else min(window, seq)
    return (w * (w + 1) / 2 + (seq - w) * w) / seq


def flops_per_token(config: dict, job: dict) -> float:
    """Matmul FLOPs this chip's share needs per trained token: forward +
    backward (= 3 x forward), nothing recomputed. q and o are ``heads *
    head_dim`` wide, k and v ``kv_heads * head_dim``; the scores count the
    live keys of each layer's kind; the router is a matmul onto all the
    experts' columns; a token runs ``experts_per_token`` experts of which
    this chip holds ``held / experts`` (uniform routing: by arithmetic, not
    by the run's counts); the head counts the vocabulary slice at every
    position; the embedding lookup counts 0."""
    s = shapes(config, job)
    m, f = s["d_model"], s["d_expert"]
    q_width = s["heads"] * s["head_dim"]
    kv_width = s["kv_heads"] * s["head_dim"]
    forward = 2 * m * s["vocab"]
    for window in s["layer_windows"]:
        forward += (
            2 * 2 * m * q_width + 2 * 2 * m * kv_width  # q, o and k, v
            + 2 * 2 * q_width * mean_live_keys(s["seq"], window)
            + 2 * m * s["experts"]                      # router
            + s["experts_per_token"] * s["held_experts"] / s["experts"]
            * 3 * 2 * m * f)                            # held experts
    return 3.0 * forward


def _model_config(config: dict, job: dict):
    import jax.numpy as jnp
    from horovod_tpu.models.transformer import TransformerConfig
    if job["seq_len"] > config["max_position_embeddings"]:
        raise ValueError("seq_len beyond the configuration's positions")
    if (config["moe_primary_router_apply_softmax"], config["norm_topk_prob"],
            config["rope_scaling"], config["tie_word_embeddings"]) != (
                True, True, None, False):
        raise ValueError("not the block the program implements")
    kinds = _layer_kinds(config)
    period = next(p for p in range(1, len(kinds) + 1)
                  if len(kinds) % p == 0
                  and kinds == kinds[:p] * (len(kinds) // p))
    share = config["share"]
    return TransformerConfig(
        vocab_size=config["vocab_size"], d_model=config["hidden_size"],
        n_heads=config["num_attention_heads"],
        n_layers=config["num_hidden_layers"],
        d_ff=config["moe_ffn_hidden_size"],
        max_seq=config["max_position_embeddings"],
        n_experts=config["moe_num_primary_experts"] * share["of"],
        moe_top_k=config["moe_num_active_primary_experts"], moe_gated=True,
        moe_renormalize=config["norm_topk_prob"],
        moe_balance_weight=config["router_aux_loss_coef"],
        tie_embeddings=config["tie_word_embeddings"],
        norm_eps=config["rms_norm_eps"],
        rope_theta=float(config["rope_theta"]),
        dtype=jnp.dtype(config["compute_dtype"]),
        head_width=config["head_dim"],
        n_kv_heads=config["num_key_value_heads"],
        layer_pattern=tuple(kinds[:period]),
        moe_router_input="block_input", moe_activation="relu",
        expert_share=(share["index"], share["of"]))


def _init_function(c, embed_std: float):
    """Draws the tree of transformer.init_params (one stage, gated experts
    of which ``held_experts`` lead, grouped heads, an untied head) in its
    shapes from a key, on the device; its scales but the embedding's,
    which is the configuration's ``assumed.embedding_std``: the router
    reads the residual stream as it is, and under a table of std 0.02 the
    blocks' outputs, much of them common to all tokens, decide the deeper
    layers' choices (every token the same six experts: PERF.md section 6,
    PR 32)."""
    import jax
    import jax.numpy as jnp
    m, f, n = c.d_model, c.d_ff, c.n_layers
    q, kv = c.n_heads * c.head_dim, c.kv_heads * c.head_dim
    held = c.held_experts

    def make(key):
        k = jax.random.split(key, 10)

        def w(key, *shape, scale=None):
            scale = 1.0 / math.sqrt(shape[-2]) if scale is None else scale
            return jax.random.normal(key, shape, jnp.float32) * scale

        def ones(width):
            return jnp.ones((1, n, width), jnp.float32)
        return {
            "embed": w(k[0], c.vocab_size, m, scale=embed_std),
            "ln_f": jnp.ones((m,), jnp.float32),
            "lm_head": w(k[1], m, c.vocab_size),
            "layers": {
                "ln1": ones(m), "ln2": ones(m),
                "wq": w(k[2], 1, n, m, q), "wk": w(k[3], 1, n, m, kv),
                "wv": w(k[4], 1, n, m, kv), "wo": w(k[5], 1, n, q, m),
                "router": w(k[6], 1, n, m, c.n_experts, scale=0.02),
                "we1": w(k[7], 1, n, held, m, f),
                "we3": w(k[8], 1, n, held, m, f),
                "we2": w(k[9], 1, n, held, f, m),
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
    params = as_shapes(jax.eval_shape(
        _init_function(cfg, config["assumed"]["embedding_std"]),
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
        self.leaf_paths = _leaf_paths(shapes(config, job)["layer_windows"])
        self.params = jax.jit(
            _init_function(self.cfg, config["assumed"]["embedding_std"]),
            out_shardings=param_shardings(self.cfg, mesh))(
                jax.random.PRNGKey(seed))
        self.opt_state = None
        self._step = None
        self.last_aux = None
