"""Adapter ``laguna``: Laguna-XS.2 (``model_type`` ``laguna``) through
models/transformer.py and ``make_train_step``, the entry points the other
adapters call: two attention shapes in one stack (``layer_pattern`` kinds
``("attention", window, rope, heads, gated)``: window layers of 64 query
heads and full layers of 48 on the same 8 key/value heads of 128, each kind
a stack of its own under one scan over periods), a rotary table a kind
(``_kinds.Rope``: the window layers' default table at theta 10 000 over the
whole head, the full layers' YaRN table over its first half), a sigmoid gate
a head on the core's output (``wg``), a dense layer leading the expert
layers (``lead_pattern``, ``dense_ff``), sigmoid-routed SiLU-gated experts
with a shared expert (``moe_router_scores``, ``moe_routed_scale``,
``moe_shared_width``), and one chip's share of every expert layer and of the
vocabulary (``expert_share``; the configuration's ``deployment``). On a TPU
every layer's attention core is ``hvd_flash_attention`` / ``hvd_flash_bwd``
with the band and the group (6 or 8) in their index maps, the routed
experts' matmuls are ``hvd_moe_gmm`` and the loss is ``hvd_fused_xent``.

The configuration file uses the source's key names. ``num_experts`` counts
the experts held here; the router's width is that times ``share.of``. The
lists ``layer_types``, ``mlp_layer_types`` and
``num_attention_heads_per_layer`` stay whole as published and their first
``num_hidden_layers`` entries are read. The host batch, the step and the
checks are the ``olmoe`` adapter's.
"""

from __future__ import annotations

import math

from adapters import olmoe
from adapters.flagship import host_batch, tokens_per_step  # noqa: F401
from adapters.smallthinker import mean_live_keys
from trees import as_shapes


def _layers(config: dict) -> list:
    """The layers that are run, ``[{"window": W or None, "heads": query
    heads, "dense": whether the FFN is the dense one}]``: the first
    ``num_hidden_layers`` entries of the source's three lists."""
    n = config["num_hidden_layers"]
    kinds = {"full_attention": None,
             "sliding_attention": config["sliding_window"]}
    ffns = {"dense": True, "sparse": False}
    return [{"type": t, "window": kinds[t], "heads": h, "dense": ffns[f]}
            for t, h, f in zip(config["layer_types"][:n],
                               config["num_attention_heads_per_layer"][:n],
                               config["mlp_layer_types"][:n])]


def _lead(layers: list) -> int:
    """The leading layers with the dense FFN; every later one has experts."""
    lead = next(i for i, layer in enumerate(layers + [{"dense": False}])
                if not layer["dense"])
    if any(layer["dense"] for layer in layers[lead:]):
        raise ValueError("a dense FFN after the first expert layer")
    return lead


def _stack(layer: dict, gated: bool) -> str:
    """The stack of the program's tree an attention block of ``layer``'s
    shape is in (models/transformer.py: a kind that names its heads or a
    gate is a stack of the word and those)."""
    return f"attention_{layer['heads']}" + "_gated" * gated


def _places(config: dict) -> list:
    """Where each layer's two blocks lie in the program's tree, ``[(the
    attention block's (path, index), the FFN block's)]`` as trees.py reads
    them: ``lead`` a stack a kind ``[block, ...]``, ``layers`` a stack a
    kind ``[stage, block, ...]``, a stack's blocks in the layers' order."""
    layers, seen, out = _layers(config), {}, []
    lead = _lead(layers)
    for i, layer in enumerate(layers):
        part = "lead" if i < lead else "layers"
        both = []
        for stack in (_stack(layer, config["gating"]),
                      "dense" if layer["dense"] else "experts"):
            at = seen.get((part, stack), 0)
            seen[part, stack] = at + 1
            both.append(((part, stack), (at,) if i < lead else (0, at)))
        out.append(tuple(both))
    return out


def _leaf_paths(config: dict) -> dict:
    """See trees.py. Layer 0's query sees every later layer through the
    residual; the first window layer's ``wk`` is a gradient summed over a
    group of 8 query heads under the band, the last full layer's ``wq``
    goes through the YaRN table at a group of 6; a gate's ``wg`` is the
    leaf this configuration adds; the router and the held experts' way down
    see the choices directly."""
    layers, places = _layers(config), _places(config)
    window = next(i for i, layer in enumerate(layers) if layer["window"])
    full = max(i for i, layer in enumerate(layers) if not layer["window"])
    dense = next(i for i, layer in enumerate(layers) if layer["dense"])

    def leaf(layer: int, block: int, name: str):
        path, index = places[layer][block]
        return (path + (name,), index)
    return {
        "lm_head": (("lm_head",), None),
        "first_query": leaf(0, 0, "wq"),
        "window_key": leaf(window, 0, "wk"),
        "window_gate": leaf(window, 0, "wg"),
        "last_full_query": leaf(full, 0, "wq"),
        "dense_down": leaf(dense, 1, "w2"),
        "last_router": leaf(len(layers) - 1, 1, "router"),
        "last_experts_down": leaf(len(layers) - 1, 1, "we2"),
    }


def shapes(config: dict, job: dict) -> dict:
    layers, share = _layers(config), config["share"]
    held, lead = config["num_experts"], _lead(layers)
    return {
        "batch": job["batch_per_chip"], "seq": job["seq_len"],
        "layers": len(layers), "d_model": config["hidden_size"],
        "heads": config["num_attention_heads"],
        "kv_heads": config["num_key_value_heads"],
        "head_dim": config["head_dim"],
        "d_ff": config["moe_intermediate_size"],
        "vocab": config["vocab_size"], "causal": True,
        "layer_windows": [layer["window"] for layer in layers],
        "layer_heads": [layer["heads"] for layer in layers],
        "layer_dense": [layer["dense"] for layer in layers],
        "layer_types": [layer["type"] for layer in layers],
        "dense_ff": config["intermediate_size"],
        "experts": held * share["of"],
        "held_experts": held, "first_expert": held * share["index"],
        "experts_per_token": config["num_experts_per_tok"],
        "d_expert": config["moe_intermediate_size"],
        "d_shared": config["shared_expert_intermediate_size"],
        # what the kernels' roofline functions count: a forward flash call
        # a layer and one more for each checkpointed attention block
        # (``assumed.recomputation``), an expert layer after the dense
        # ones, one head call
        "attention_forward_calls": len(layers) * (
            1 + bool(config["assumed"]["checkpoint_every_block"])),
        "routed_layers": len(layers) - lead,
        "head_calls": 1,
        # what the reference needs beside sizes
        "layer_places": _places(config),
        "gated": config["gating"],
        "norm_eps": config["rms_norm_eps"],
        "rope": config["rope_parameters"],
        "routed_scale": config["moe_routed_scaling_factor"],
    }


def flops_per_token(config: dict, job: dict) -> float:
    """Matmul FLOPs this chip's share needs per trained token: forward +
    backward (= 3 x forward), nothing recomputed. With M the hidden size
    and D the head width, a layer of ``H`` query heads on ``kv_heads``:

    * q and o ``2 * 2 M H D``, k and v ``2 * 2 M kv_heads D``, the gate ``2
      M H``; the scores and the weighted sum over the live keys of the
      layer's kind (``mean_live_keys``): ``2 * 2 H D keys``;
    * the dense layer's gated FFN: three matrices ``M x intermediate_size``;
    * an expert layer: the router onto all the experts' columns, the shared
      expert's three matrices on every token, and ``num_experts_per_tok``
      routed experts of three matrices of which this chip holds ``held /
      experts`` (uniform routing: by arithmetic, not by the run's counts);
    * the head over the vocabulary slice at every position; the embedding
      lookup counts 0."""
    s = shapes(config, job)
    m, d, kv = s["d_model"], s["head_dim"], s["kv_heads"]
    forward = 2 * m * s["vocab"]
    for heads, window, dense in zip(s["layer_heads"], s["layer_windows"],
                                    s["layer_dense"]):
        forward += (2 * 2 * m * heads * d + 2 * 2 * m * kv * d
                    + 2 * m * heads * s["gated"]
                    + 2 * 2 * heads * d * mean_live_keys(s["seq"], window))
        if dense:
            forward += 3 * 2 * m * s["dense_ff"]
        else:
            forward += (2 * m * s["experts"] + 3 * 2 * m * s["d_shared"]
                        + s["experts_per_token"] * s["held_experts"]
                        / s["experts"] * 3 * 2 * m * s["d_expert"])
    return 3.0 * forward


def _rope(config: dict, layer_type: str):
    """The rotary table of a layer type, from ``rope_parameters``."""
    from horovod_tpu.models._kinds import Rope, Yarn
    r, d = config["rope_parameters"][layer_type], config["head_dim"]
    width = int(d * r["partial_rotary_factor"])
    if r["rope_type"] == "default":
        yarn = None
    elif r["rope_type"] == "yarn":
        yarn = Yarn(float(r["factor"]),
                    r["original_max_position_embeddings"],
                    float(r["beta_fast"]), float(r["beta_slow"]),
                    r["attention_factor"])
    else:
        raise ValueError(f"rope_type {r['rope_type']!r}")
    return Rope(float(r["rope_theta"]), None if width == d else width, yarn)


def _model_config(config: dict, job: dict):
    import jax.numpy as jnp
    from horovod_tpu.models.transformer import TransformerConfig
    if job["seq_len"] > config["max_position_embeddings"]:
        raise ValueError("seq_len beyond the configuration's positions")
    if (config["attention_bias"], config["tie_word_embeddings"],
            config["moe_apply_router_weight_on_input"]) != (
                False, False, False):
        raise ValueError("not the blocks the program implements")
    layers, share = _layers(config), config["share"]

    def kind(layer):
        return ("attention", layer["window"], _rope(config, layer["type"]),
                layer["heads"], config["gating"])
    lead = _lead(layers)
    routed = [kind(layer) for layer in layers[lead:]]
    period = next(p for p in range(1, len(routed) + 1)
                  if len(routed) % p == 0
                  and routed == routed[:p] * (len(routed) // p))
    return TransformerConfig(
        vocab_size=config["vocab_size"], d_model=config["hidden_size"],
        n_heads=config["num_attention_heads"],
        head_width=config["head_dim"],
        n_kv_heads=config["num_key_value_heads"],
        n_layers=2 * len(routed),
        layer_pattern=tuple(k for a in routed[:period]
                            for k in (a, ("experts",))),
        lead_pattern=tuple(k for layer in layers[:lead]
                           for k in (kind(layer), ("dense",))),
        d_ff=config["moe_intermediate_size"],
        dense_ff=config["intermediate_size"], ffn_gated=True,
        max_seq=config["max_position_embeddings"],
        n_experts=config["num_experts"] * share["of"],
        moe_top_k=config["num_experts_per_tok"], moe_gated=True,
        moe_activation="silu", moe_renormalize=True,
        moe_balance_weight=0.0, moe_router_scores="sigmoid",
        moe_routed_scale=config["moe_routed_scaling_factor"],
        moe_shared_width=config["shared_expert_intermediate_size"],
        expert_share=(share["index"], share["of"]),
        tie_embeddings=config["tie_word_embeddings"],
        norm_eps=config["rms_norm_eps"],
        remat=config["assumed"]["checkpoint_every_block"] or None,
        dtype=jnp.dtype(config["compute_dtype"]))


def _init_function(c, config: dict):
    """Draws the tree of transformer.init_params (one stage; ``lead`` and
    ``layers`` a stack a kind) in its shapes and scales from a key, on the
    device; the embedding at ``assumed.embedding_std``."""
    import jax
    import jax.numpy as jnp
    m, d, kv = c.d_model, c.head_dim, c.kv_heads * c.head_dim
    f, fs, held = c.d_ff, c.moe_shared_width, c.held_experts
    blocks, heads_of = {}, {}   # (part, stack) -> its blocks, query heads
    for layer, pair in zip(_layers(config), _places(config)):
        for path, _index in pair:
            blocks[path] = blocks.get(path, 0) + 1
        heads_of[pair[0][0]] = layer["heads"]

    def make(key):
        keys = iter(jax.random.split(key, 64))

        def w(*shape, scale=None):
            scale = 1.0 / math.sqrt(shape[-2]) if scale is None else scale
            return jax.random.normal(next(keys), shape, jnp.float32) * scale

        def ones(*shape):
            return jnp.ones(shape, jnp.float32)

        def attention(heads, gated, *lead):
            p = {"ln1": ones(*lead, m),
                 "wq": w(*lead, m, heads * d), "wk": w(*lead, m, kv),
                 "wv": w(*lead, m, kv), "wo": w(*lead, heads * d, m)}
            if gated:
                p["wg"] = w(*lead, m, heads)
            return p

        def dense(*lead):
            return {"ln2": ones(*lead, m), "w1": w(*lead, m, c.dense_ff),
                    "w2": w(*lead, c.dense_ff, m),
                    "w3": w(*lead, m, c.dense_ff)}

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
        tree = {"embed": w(c.vocab_size, m,
                           scale=config["assumed"]["embedding_std"]),
                "ln_f": ones(m), "lm_head": w(m, c.vocab_size)}
        for (part, stack), n in blocks.items():
            lead = (n,) if part == "lead" else (1, n)
            if stack == "dense":
                made = dense(*lead)
            elif stack == "experts":
                made = experts(*lead)
            else:
                made = attention(heads_of[part, stack], config["gating"],
                                 *lead)
            tree.setdefault(part, {})[stack] = made
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
