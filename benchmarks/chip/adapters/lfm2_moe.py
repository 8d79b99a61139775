"""Adapter ``lfm2_moe``: LFM2-24B-A2B (``model_type`` ``lfm2_moe``) through
models/transformer.py and ``make_train_step``, the entry points the other
adapters call: gated short-convolution mixers (``("conv",)``,
``models/short_conv.py``: two matmuls with a three-tap, two-gate float32
chain between them, no scan) beside grouped-query attention with an RMSNorm
over each head of q and k before the rotation (``qk_norm="head"``), the
leading layers' dense FFN (``lead_pattern``, ``dense_ff``), sigmoid-routed
SiLU-gated experts with no shared one (``moe_router_scores``), a tied head,
and one chip's share of every expert layer and of the vocabulary
(``expert_share``; the configuration's ``deployment``). On a TPU the
attention core is ``hvd_flash_attention`` / ``hvd_flash_bwd`` at 32 / 8
heads of 64, the routed experts' matmuls are ``hvd_moe_gmm`` and the loss is
``hvd_fused_xent``.

The configuration file uses the source's key names. ``num_experts`` counts
the experts held here; the router's width is that times ``share.of``.
``layer_types`` lists the layers that are run, ``num_dense_layers`` how many
of them lead with the dense FFN. The host batch, the step and the checks are
the ``olmoe`` adapter's.
"""

from __future__ import annotations

import math

from adapters import olmoe
from adapters.flagship import host_batch, tokens_per_step  # noqa: F401
from adapters.smallthinker import mean_live_keys
from trees import as_shapes

#: the program's one-sublayer kind, and the stack of its tree, of a layer
#: type's mixer
MIXERS = {"conv": ("conv",), "full_attention": ("attention", None, True)}
FFNS = {True: "dense", False: "experts"}


def _layers(config: dict) -> list:
    """The layers that are run, ``[{"type": layer type, "dense": whether
    the FFN is the dense one}]``."""
    types = config["layer_types"]
    if len(types) != config["num_hidden_layers"]:
        raise ValueError("layer_types is not num_hidden_layers words")
    return [{"type": t, "dense": i < config["num_dense_layers"]}
            for i, t in enumerate(types)]


def _places(config: dict) -> list:
    """Where each layer's two blocks lie in the program's tree, ``[(the
    mixer block's (path, index), the FFN block's)]`` as trees.py reads
    them: ``lead`` a stack a kind ``[block, ...]``, ``layers`` a stack a
    kind ``[stage, block, ...]``, a stack's blocks in the layers' order."""
    seen, out = {}, []
    for layer in _layers(config):
        part = "lead" if layer["dense"] else "layers"
        both = []
        for stack in (MIXERS[layer["type"]][0], FFNS[layer["dense"]]):
            at = seen.get((part, stack), 0)
            seen[part, stack] = at + 1
            both.append(((part, stack),
                         (at,) if layer["dense"] else (0, at)))
        out.append(tuple(both))
    return out


def _leaf_paths(config: dict) -> dict:
    """See trees.py. The tied table is lookup and head at once; the leading
    mixer's in-projection sees every later layer through the residual; the
    taps and the out-projection of the last mixer are the leaves the new
    kind adds, a head's norm weight the one the new norm adds; the
    attention block's ``wk`` is a gradient summed over a group of 4 query
    heads; the router and the held experts' way down see the choices
    directly."""
    layers, places = _layers(config), _places(config)

    def first(kind, dense=False):
        return next(i for i, layer in enumerate(layers)
                    if layer["type"] == kind and layer["dense"] == dense)

    def leaf(layer: int, block: int, name: str):
        path, index = places[layer][block]
        return (path + (name,), index)
    attention, last = first("full_attention"), len(layers) - 1
    last_conv = max(i for i, layer in enumerate(layers)
                    if layer["type"] == "conv")
    return {
        "embed": (("embed",), None),
        "first_conv_in": leaf(0, 0, "conv_in"),
        "dense_down": leaf(0, 1, "w2"),
        "attention_key": leaf(attention, 0, "wk"),
        "attention_q_norm": leaf(attention, 0, "q_norm"),
        "last_conv_taps": leaf(last_conv, 0, "conv_w"),
        "last_conv_out": leaf(last_conv, 0, "conv_out"),
        "last_router": leaf(last, 1, "router"),
        "last_experts_down": leaf(last, 1, "we2"),
    }


def shapes(config: dict, job: dict) -> dict:
    layers, share = _layers(config), config["share"]
    held = config["num_experts"]
    types = [layer["type"] for layer in layers]
    return {
        "batch": job["batch_per_chip"], "seq": job["seq_len"],
        "layers": len(layers), "d_model": config["hidden_size"],
        "heads": config["num_attention_heads"],
        "kv_heads": config["num_key_value_heads"],
        # (the source has no head_dim: a head is hidden / heads; the tiny
        # sizes name one)
        "head_dim": config.get("head_dim") or (
            config["hidden_size"] // config["num_attention_heads"]),
        "d_ff": config["moe_intermediate_size"],
        "vocab": config["vocab_size"], "causal": True,
        "layer_types": types,
        "layer_dense": [layer["dense"] for layer in layers],
        # the attention blocks as the mixed roofline functions count them
        "layer_windows": [None] * types.count("full_attention"),
        "conv_layers": types.count("conv"),
        "conv_taps": config["conv_L_cache"],
        "dense_ff": config["intermediate_size"],
        "experts": held * share["of"],
        "held_experts": held, "first_expert": held * share["index"],
        "experts_per_token": config["num_experts_per_tok"],
        "d_expert": config["moe_intermediate_size"],
        "routed_layers": len(layers) - config["num_dense_layers"],
        "head_calls": 1,
        # what the reference needs beside sizes
        "layer_places": _places(config),
        "norm_eps": config["norm_eps"],
        "rope_theta": float(config["rope_parameters"]["rope_theta"]),
        "routed_scale": config["routed_scaling_factor"],
    }


def flops_per_token(config: dict, job: dict) -> float:
    """Matmul FLOPs this chip's share needs per trained token: forward +
    backward (= 3 x forward), nothing recomputed. With M the hidden size
    and D a head's width:

    * a conv mixer: the in-projection ``2 M 3 M`` and the out-projection
      ``2 M M`` (the gates and taps are no matmul and count 0);
    * an attention mixer: q and o ``2 * 2 M H D``, k and v ``2 * 2 M
      kv_heads D``; the scores and the weighted sum over the causal half,
      ``(S + 1) / 2`` keys a query: ``2 * 2 H D keys``;
    * a leading layer's gated FFN: three matrices ``M x intermediate_size``;
    * an expert layer: the router onto all the experts' columns and
      ``num_experts_per_tok`` routed experts of three matrices of which
      this chip holds ``held / experts`` (uniform routing: by arithmetic,
      not by the run's counts);
    * the tied head over the vocabulary slice at every position; the
      embedding lookup counts 0."""
    s = shapes(config, job)
    m, d = s["d_model"], s["head_dim"]
    forward = 2 * m * s["vocab"]
    for kind, dense in zip(s["layer_types"], s["layer_dense"]):
        if kind == "conv":
            forward += 2 * m * 3 * m + 2 * m * m
        else:
            forward += (2 * 2 * m * s["heads"] * d
                        + 2 * 2 * m * s["kv_heads"] * d
                        + 2 * 2 * s["heads"] * d
                        * mean_live_keys(s["seq"], None))
        if dense:
            forward += 3 * 2 * m * s["dense_ff"]
        else:
            forward += (2 * m * s["experts"]
                        + s["experts_per_token"] * s["held_experts"]
                        / s["experts"] * 3 * 2 * m * s["d_expert"])
    return 3.0 * forward


def _model_config(config: dict, job: dict):
    import jax.numpy as jnp
    from horovod_tpu.models.transformer import TransformerConfig
    if job["seq_len"] > config["max_position_embeddings"]:
        raise ValueError("seq_len beyond the configuration's positions")
    if (config["conv_bias"], config["use_expert_bias"],
            config["tie_word_embeddings"],
            config["rope_parameters"]["rope_type"]) != (
                False, True, True, "default"):
        raise ValueError("not the blocks the program implements")
    layers, share = _layers(config), config["share"]
    lead = config["num_dense_layers"]
    mixers = [MIXERS[layer["type"]] for layer in layers[lead:]]
    period = next(p for p in range(1, len(mixers) + 1)
                  if len(mixers) % p == 0
                  and mixers == mixers[:p] * (len(mixers) // p))
    return TransformerConfig(
        vocab_size=config["vocab_size"], d_model=config["hidden_size"],
        n_heads=config["num_attention_heads"],
        n_kv_heads=config["num_key_value_heads"],
        head_width=config.get("head_dim"), n_layers=2 * len(mixers),
        layer_pattern=tuple(k for mixer in mixers[:period]
                            for k in (mixer, ("experts",))),
        lead_pattern=tuple(k for layer in layers[:lead]
                           for k in (MIXERS[layer["type"]], ("dense",))),
        d_ff=config["moe_intermediate_size"],
        dense_ff=config["intermediate_size"], ffn_gated=True,
        max_seq=config["max_position_embeddings"],
        n_experts=config["num_experts"] * share["of"],
        moe_top_k=config["num_experts_per_tok"], moe_gated=True,
        moe_activation="silu", moe_renormalize=config["norm_topk_prob"],
        moe_balance_weight=0.0, moe_router_scores="sigmoid",
        moe_routed_scale=float(config["routed_scaling_factor"]),
        moe_shared_width=0, expert_share=(share["index"], share["of"]),
        qk_norm="head", conv_taps=config["conv_L_cache"],
        tie_embeddings=config["tie_word_embeddings"],
        norm_eps=config["norm_eps"],
        rope_theta=float(config["rope_parameters"]["rope_theta"]),
        remat=config["assumed"]["checkpoint_every_block"] or None,
        dtype=jnp.dtype(config["compute_dtype"]))


def _init_function(c, config: dict):
    """Draws the tree of transformer.init_params (one stage; ``lead`` and
    ``layers`` a stack a kind) in its shapes and scales from a key, on the
    device; the tied table at ``assumed.embedding_std``, the routers at
    ``assumed.router_std``."""
    import jax
    import jax.numpy as jnp
    m, d = c.d_model, c.head_dim
    q, kv = c.n_heads * d, c.kv_heads * d
    f, held, taps = c.d_ff, c.held_experts, c.conv_taps
    blocks = {}     # (part, stack) -> its blocks
    for pair in _places(config):
        for path, _index in pair:
            blocks[path] = blocks.get(path, 0) + 1

    def make(key):
        keys = iter(jax.random.split(key, 32))

        def w(*shape, scale=None):
            scale = 1.0 / math.sqrt(shape[-2]) if scale is None else scale
            return jax.random.normal(next(keys), shape, jnp.float32) * scale

        def ones(*shape):
            return jnp.ones(shape, jnp.float32)

        def conv(*lead):
            bound = 1.0 / math.sqrt(taps)
            return {"ln1": ones(*lead, m), "conv_in": w(*lead, m, 3 * m),
                    "conv_w": jax.random.uniform(
                        next(keys), lead + (taps, m), jnp.float32,
                        -bound, bound),
                    "conv_out": w(*lead, m, m)}

        def attention(*lead):
            return {"ln1": ones(*lead, m),
                    "wq": w(*lead, m, q), "wk": w(*lead, m, kv),
                    "wv": w(*lead, m, kv), "wo": w(*lead, q, m),
                    "q_norm": ones(*lead, d), "k_norm": ones(*lead, d)}

        def dense(*lead):
            return {"ln2": ones(*lead, m), "w1": w(*lead, m, c.dense_ff),
                    "w2": w(*lead, c.dense_ff, m),
                    "w3": w(*lead, m, c.dense_ff)}

        def experts(*lead):
            return {
                "ln2": ones(*lead, m),
                "router": w(*lead, m, c.n_experts,
                            scale=config["assumed"]["router_std"]),
                "router_bias": jnp.zeros(lead + (c.n_experts,),
                                         jnp.float32),
                "we1": w(*lead, held, m, f), "we2": w(*lead, held, f, m),
                "we3": w(*lead, held, m, f)}
        draw = {"conv": conv, "attention": attention, "dense": dense,
                "experts": experts}
        tree = {"embed": w(c.vocab_size, m,
                           scale=config["assumed"]["embedding_std"]),
                "ln_f": ones(m)}
        for (part, stack), n in blocks.items():
            tree.setdefault(part, {})[stack] = draw[stack](
                *((n,) if part == "lead" else (1, n)))
        return tree
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
    term, here zero; ``program_choices``; ``dropped`` held to 0 after the
    window) on this adapter's configuration and tree. ``last_aux`` also
    holds ``held_rows`` (the step's assignments to the experts held here)
    and ``max_expert_load``."""

    def __init__(self, config: dict, job: dict, mesh, seed: int):
        import jax
        from horovod_tpu.models.transformer import param_shardings
        self.mesh = mesh
        self.cfg = _model_config(config, job)
        self.leaf_paths = _leaf_paths(config)
        self.params = jax.jit(
            _init_function(self.cfg, config),
            out_shardings=param_shardings(self.cfg, mesh))(
                jax.random.PRNGKey(seed))
        self.opt_state = None
        self._step = None
        self.last_aux = None
