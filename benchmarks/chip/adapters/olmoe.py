"""Adapter ``olmoe``: OLMoE (arXiv:2409.02060) through the flagship block of
models/transformer.py and ``make_train_step``, the entry points the
``flagship`` adapter calls: QK-norm, 64 SiLU-gated experts with a dropless
sorted top-8 dispatch (``parallel/moe.py``; on a TPU the grouped matmuls are
the ``hvd_moe_gmm`` kernels), an untied head, the load-balancing and router
z losses. Attention and the loss are the GPT cell's (``hvd_flash_attention``,
``hvd_fused_xent``).

The configuration file uses the source's key names (``hidden_size``,
``intermediate_size`` = one expert's width, ``num_experts``,
``num_experts_per_tok``, ...). The host batch, the step and the checks are
the flagship adapter's.
"""

from __future__ import annotations

import math

from adapters import flagship
from adapters.flagship import host_batch, tokens_per_step  # noqa: F401
from trees import as_shapes


def _leaf_paths(n_layers: int) -> dict:
    """See trees.py; weights are stacked ``[stage, layer, ...]``. The head
    and a query projection see the router's choices only through the
    residual; the router and the experts' way down see them directly (all
    64 experts' matrices as one leaf: a single expert's gradient swings
    with the few differing choices that happen to meet it)."""
    last = (0, n_layers - 1)
    return {
        "lm_head": (("lm_head",), None),
        "first_query": (("layers", "wq"), (0, 0)),
        "last_router": (("layers", "router"), last),
        "last_experts_down": (("layers", "we2"), last),
    }


def shapes(config: dict, job: dict) -> dict:
    return {
        "batch": job["batch_per_chip"], "seq": job["seq_len"],
        "layers": config["num_hidden_layers"],
        "d_model": config["hidden_size"],
        "heads": config["num_attention_heads"],
        "head_dim": config["hidden_size"] // config["num_attention_heads"],
        "d_ff": config["intermediate_size"], "vocab": config["vocab_size"],
        "causal": True,
        "experts": config["num_experts"],
        "experts_per_token": config["num_experts_per_tok"],
        "d_expert": config["intermediate_size"],
        # what the reference needs beside sizes
        "norm_eps": config["rms_norm_eps"],
        "rope_theta": config["rope_theta"],
        "norm_topk_prob": config["norm_topk_prob"],
        "balance_weight": config["router_aux_loss_coef"],
        "z_weight": config["assumed"]["router_z_loss_coef"],
    }


def flops_per_token(config: dict, job: dict) -> float:
    """Matmul FLOPs OLMoE needs per trained token: forward + backward (= 3 x
    forward), nothing recomputed. Attention counts the causal half: a query
    at position t multiplies t + 1 keys, (S + 1) / 2 on average. A token
    runs ``num_experts_per_tok`` experts of three matrices each; the router
    is a matmul onto ``num_experts`` columns; the untied head counts at
    every position; the embedding lookup counts 0."""
    m, f = config["hidden_size"], config["intermediate_size"]
    s, v = job["seq_len"], config["vocab_size"]
    keys = (s + 1) / 2
    layer = (
        4 * 2 * m * m              # q, k, v and output projections
        + 2 * 2 * keys * m         # q k^T and probabilities times v
        + 2 * m * config["num_experts"]                      # router
        + config["num_experts_per_tok"] * 3 * 2 * m * f)     # experts
    forward = config["num_hidden_layers"] * layer + 2 * m * v
    return 3.0 * forward


def _model_config(config: dict, job: dict):
    import jax.numpy as jnp
    from horovod_tpu.models.transformer import TransformerConfig
    if job["seq_len"] > config["max_position_embeddings"]:
        raise ValueError("seq_len beyond the configuration's positions")
    if (config["hidden_act"], config["num_key_value_heads"],
            config["attention_bias"], config["clip_qkv"],
            config["rope_scaling"]) != (
                "silu", config["num_attention_heads"], False, None, None):
        raise ValueError("not the OLMoE block the program implements")
    return TransformerConfig(
        vocab_size=config["vocab_size"], d_model=config["hidden_size"],
        n_heads=config["num_attention_heads"],
        n_layers=config["num_hidden_layers"],
        d_ff=config["intermediate_size"],
        max_seq=config["max_position_embeddings"],
        n_experts=config["num_experts"],
        moe_top_k=config["num_experts_per_tok"], moe_gated=True,
        moe_renormalize=config["norm_topk_prob"],
        moe_balance_weight=config["router_aux_loss_coef"],
        moe_z_weight=config["assumed"]["router_z_loss_coef"],
        qk_norm=True, tie_embeddings=config["tie_word_embeddings"],
        norm_eps=config["rms_norm_eps"],
        rope_theta=float(config["rope_theta"]),
        dtype=jnp.dtype(config["compute_dtype"]))


def _init_function(c):
    """Draws the tree of transformer.init_params (one stage, gated experts,
    QK-norm, an untied head) in its shapes and scales from a key, on the
    device."""
    import jax
    import jax.numpy as jnp
    m, hd, f = c.d_model, c.n_heads * c.head_dim, c.d_ff
    n, e = c.n_layers, c.n_experts

    def make(key):
        k = jax.random.split(key, 10)

        def w(key, *shape, scale=None):
            scale = 1.0 / math.sqrt(shape[-2]) if scale is None else scale
            return jax.random.normal(key, shape, jnp.float32) * scale

        def ones(width):
            return jnp.ones((1, n, width), jnp.float32)
        return {
            "embed": w(k[0], c.vocab_size, m, scale=0.02),
            "ln_f": jnp.ones((m,), jnp.float32),
            "lm_head": w(k[1], m, c.vocab_size),
            "layers": {
                "ln1": ones(m), "ln2": ones(m),
                "q_norm": ones(hd), "k_norm": ones(hd),
                "wq": w(k[2], 1, n, m, hd), "wk": w(k[3], 1, n, m, hd),
                "wv": w(k[4], 1, n, m, hd), "wo": w(k[5], 1, n, hd, m),
                "router": w(k[6], 1, n, m, e, scale=0.02),
                "we1": w(k[7], 1, n, e, m, f),
                "we3": w(k[8], 1, n, e, m, f),
                "we2": w(k[9], 1, n, e, f, m),
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
    params = as_shapes(jax.eval_shape(_init_function(cfg),
                                      jax.random.PRNGKey(0)), rep)
    opt_state = as_shapes(jax.eval_shape(tx.init, params), rep)
    batch = as_shapes(
        host_batch(config, job, 0, 0, job["batch_per_chip"] * mesh.size),
        NamedSharding(mesh, data_sharding_spec(mesh)))
    return (make_train_step(cfg, mesh, tx),
            (params, opt_state, batch["tokens"], batch["targets"]))


class Cell(flagship.Cell):
    def __init__(self, config: dict, job: dict, mesh, seed: int):
        import jax
        from horovod_tpu.models.transformer import param_shardings
        self.mesh = mesh
        self.cfg = _model_config(config, job)
        self.leaf_paths = _leaf_paths(config["num_hidden_layers"])
        self.params = jax.jit(
            _init_function(self.cfg),
            out_shardings=param_shardings(self.cfg, mesh))(
                jax.random.PRNGKey(seed))
        self.opt_state = None
        self._step = None
        #: the last step's auxiliary output, still on the device: the two
        #: losses, ``max_expert_load``, ``dropped``
        self.last_aux = None

    def program_loss_and_grads(self, batch: dict):
        """The loss training descends, cross-entropy plus both weighted
        auxiliary terms, and its gradients."""
        import jax
        from horovod_tpu.models.transformer import make_grad_fn
        from trees import get_leaves
        grad_fn, paths = make_grad_fn(self.cfg, self.mesh), self.leaf_paths

        @jax.jit
        def fn(params, b):
            loss, aux, grads = grad_fn(params, b["tokens"], b["targets"])
            return loss + aux["aux_loss"], get_leaves(grads, paths)
        return fn(self.params, batch)

    def program_choices(self, batch: dict):
        """The experts the program's router chooses, ``[L, T, k]`` (for
        ``reference.loss_and_grads(.., choices=..)``: what part of an error
        differing choices explain)."""
        import functools
        import jax
        from horovod_tpu.models.transformer import router_choices
        return jax.jit(functools.partial(router_choices, cfg=self.cfg))(
            self.params, batch["tokens"])

    def step(self, batch: dict):
        self.params, self.opt_state, loss, self.last_aux = self._step(
            self.params, self.opt_state, batch["tokens"], batch["targets"])
        return loss

    def compiled_step(self, batch: dict):
        """The harness asks for this once, after the window: the place to
        hold the last step to ``dropped`` 0 without a readback inside it
        (the count is an identity of the dispatch; the tier-1 tests hold
        every step to it)."""
        dropped = float(self.last_aux["dropped"])
        if dropped != 0:
            raise RuntimeError(f"the last step dropped {dropped} assignments")
        return super().compiled_step(batch)
