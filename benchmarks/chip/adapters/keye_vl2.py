"""Adapter ``keye_vl2``: the language model of Keye-VL-2.0-30B-A3B through
the flagship block of models/transformer.py and ``make_train_step``, the
entry points the ``olmoe`` and ``smallthinker`` adapters call: 32 query heads
of 128 on 4 key/value heads with an RMSNorm a head (``head_width``,
``n_kv_heads``, ``qk_norm="head"``), a learned index over the keys beside
them (``index_topk``, ``index_heads``, ``index_head_dim``: DeepSeek-V3.2's
indexer, arXiv:2512.02556 section 2.1; ``ops/sparse_attention.py``), whose
own loss joins the step's, and one chip's share of every layer's 128 gated
experts and of the vocabulary (``expert_share``; the configuration's
``deployment``). The index, its selection and the core under the selection
are XLA code, a block of query rows at a time; on a TPU the experts' matmuls
are ``hvd_moe_gmm`` and the loss is ``hvd_fused_xent``.

The configuration file uses the source's key names. ``num_experts`` counts
the experts held here; the router's width is that times ``share.of``. The
host batch, the step and the checks are the ``olmoe`` adapter's, the
objective with the indexers' loss beside the cross-entropy.
"""

from __future__ import annotations

import math

from adapters import olmoe
from adapters.flagship import host_batch, tokens_per_step  # noqa: F401
from trees import as_shapes


def _leaf_paths(layers: int) -> dict:
    """See trees.py; weights are stacked ``[stage, layer, ...]``. A query's
    and a key's projection see the selection through the core; the last
    layer's index queries and the first layer's index weights learn from
    the indexer's loss alone; the router and the held experts' way down see
    the residual every selection below them shaped."""
    return {
        "lm_head": (("lm_head",), None),
        "first_query": (("layers", "wq"), (0, 0)),
        "first_key": (("layers", "wk"), (0, 0)),
        "last_index_query": (("layers", "wq_idx"), (0, layers - 1)),
        "first_index_weight": (("layers", "w_idx"), (0, 0)),
        "last_router": (("layers", "router"), (0, layers - 1)),
        "last_experts_down": (("layers", "we2"), (0, layers - 1)),
    }


def shapes(config: dict, job: dict) -> dict:
    share, index = config["share"], config["sa_config"]
    held = config["num_experts"]
    return {
        "batch": job["batch_per_chip"], "seq": job["seq_len"],
        "layers": config["num_hidden_layers"],
        "d_model": config["hidden_size"],
        "heads": config["num_attention_heads"],
        "kv_heads": config["num_key_value_heads"],
        "head_dim": config["head_dim"],
        "d_ff": config["moe_intermediate_size"],
        "vocab": config["vocab_size"], "causal": True,
        "index_heads": index["indexer_num_heads"],
        "index_head_dim": index["indexer_head_dim"],
        "index_topk": index["topk"],
        "experts": held * share["of"],
        "held_experts": held, "first_expert": held * share["index"],
        "experts_per_token": config["num_experts_per_tok"],
        "d_expert": config["moe_intermediate_size"],
        "routed_layers": config["num_hidden_layers"], "head_calls": 1,
        # what the reference needs beside sizes
        "norm_eps": config["rms_norm_eps"],
        "rope_theta": float(config["rope_theta"]),
    }


def mean_keys(seq: int, most=None) -> float:
    """Keys a query meets on average over positions 0 .. seq - 1: ``t + 1``
    at position ``t``, at most ``most``."""
    w = seq if most is None else min(most, seq)
    return (w * (w + 1) / 2 + (seq - w) * w) / seq


def flops_per_token(config: dict, job: dict) -> float:
    """Matmul FLOPs the published algorithm needs per trained token on this
    chip's share: forward + backward, nothing recomputed, whatever
    implements it. q and o are ``heads * head_dim`` wide, k and v ``kv_heads
    * head_dim``; the indexer's three projections; its score pass meets
    every causal key forward (``(seq + 1) / 2`` on average, ``index_heads *
    index_head_dim`` wide) and, backward, only the selected ones (the KL's
    gradient is zero elsewhere: two products of that width over them); the
    core meets the selected keys, ``min(t + 1, topk)``, forward and twice
    backward; the selection itself is no matmul and counts 0; the router is
    a matmul onto all the experts' columns; a token runs
    ``experts_per_token`` experts of which this chip holds ``held /
    experts`` (uniform routing: by arithmetic, not by the run's counts); the
    head counts the vocabulary slice at every position; the embedding
    lookup counts 0."""
    s = shapes(config, job)
    m, f = s["d_model"], s["d_expert"]
    q_width = s["heads"] * s["head_dim"]
    kv_width = s["kv_heads"] * s["head_dim"]
    index_width = s["index_heads"] * s["index_head_dim"]
    causal, selected = mean_keys(s["seq"]), mean_keys(s["seq"],
                                                      s["index_topk"])
    dense = (2 * 2 * m * q_width + 2 * 2 * m * kv_width   # q, o and k, v
             + 2 * m * (index_width + s["index_head_dim"]
                        + s["index_heads"])               # the indexer's
             + 2 * m * s["experts"]                       # router
             + s["experts_per_token"] * s["held_experts"] / s["experts"]
             * 3 * 2 * m * f)                             # held experts
    layer = (3 * dense
             + 2 * index_width * (causal + 2 * selected)  # the score pass
             + 3 * 2 * 2 * q_width * selected)            # the core
    return s["layers"] * layer + 3 * 2 * m * s["vocab"]


def _model_config(config: dict, job: dict):
    import jax.numpy as jnp
    from horovod_tpu.models.transformer import TransformerConfig
    if job["seq_len"] > config["max_position_embeddings"]:
        raise ValueError("seq_len beyond the configuration's positions")
    index = config["sa_config"]
    if (config["norm_topk_prob"], config["tie_word_embeddings"],
            config["attention_bias"], config["decoder_sparse_step"],
            config["mlp_only_layers"], config["hidden_act"],
            config["use_sliding_window"], index["indexer_num_kv_heads"],
            config["rope_scaling"]["rope_type"]) != (
                True, False, False, 1, [], "silu", False, 1, "default"):
        raise ValueError("not the block the program implements")
    share = config["share"]
    return TransformerConfig(
        vocab_size=config["vocab_size"], d_model=config["hidden_size"],
        n_heads=config["num_attention_heads"],
        n_layers=config["num_hidden_layers"],
        d_ff=config["moe_intermediate_size"],
        max_seq=config["max_position_embeddings"],
        n_experts=config["num_experts"] * share["of"],
        moe_top_k=config["num_experts_per_tok"], moe_gated=True,
        moe_renormalize=config["norm_topk_prob"], moe_balance_weight=0.0,
        qk_norm="head", tie_embeddings=config["tie_word_embeddings"],
        norm_eps=config["rms_norm_eps"],
        rope_theta=float(config["rope_theta"]),
        dtype=jnp.dtype(config["compute_dtype"]),
        head_width=config["head_dim"],
        n_kv_heads=config["num_key_value_heads"],
        index_topk=index["topk"], index_heads=index["indexer_num_heads"],
        index_head_dim=index["indexer_head_dim"],
        expert_share=(share["index"], share["of"]), remat=True)


def _init_function(c, embed_std: float):
    """Draws the tree of transformer.init_params (one stage, gated experts
    of which ``held_experts`` lead, grouped heads with a norm a head, the
    indexer's leaves, an untied head) in its shapes from a key, on the
    device; its scales but the embedding's, which is the configuration's
    ``assumed.embedding_std``."""
    import jax
    import jax.numpy as jnp
    m, f, n = c.d_model, c.d_ff, c.n_layers
    q, kv = c.n_heads * c.head_dim, c.kv_heads * c.head_dim
    hi, di, held = c.index_heads, c.index_head_dim, c.held_experts

    def make(key):
        k = jax.random.split(key, 13)

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
                "q_norm": ones(c.head_dim), "k_norm": ones(c.head_dim),
                "wq": w(k[2], 1, n, m, q), "wk": w(k[3], 1, n, m, kv),
                "wv": w(k[4], 1, n, m, kv), "wo": w(k[5], 1, n, q, m),
                "wq_idx": w(k[6], 1, n, m, hi * di),
                "wk_idx": w(k[7], 1, n, m, di),
                "k_idx_norm": ones(di),
                "k_idx_norm_bias": jnp.zeros((1, n, di), jnp.float32),
                "w_idx": w(k[8], 1, n, m, hi),
                "router": w(k[9], 1, n, m, c.n_experts, scale=0.02),
                "we1": w(k[10], 1, n, held, m, f),
                "we3": w(k[11], 1, n, held, m, f),
                "we2": w(k[12], 1, n, held, f, m),
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
    """The ``olmoe`` cell's checks and step (``dropped`` held to 0 after the
    window) on this adapter's configuration and tree. ``last_aux`` also
    holds ``held_rows``, ``index_loss`` (the layers' summed) and
    ``selected_keys`` (the mean keys a query attended)."""

    def __init__(self, config: dict, job: dict, mesh, seed: int):
        import jax
        from horovod_tpu.models.transformer import param_shardings
        self.mesh = mesh
        self.cfg = _model_config(config, job)
        self.leaf_paths = _leaf_paths(config["num_hidden_layers"])
        self.params = jax.jit(
            _init_function(self.cfg, config["assumed"]["embedding_std"]),
            out_shardings=param_shardings(self.cfg, mesh))(
                jax.random.PRNGKey(seed))
        self.opt_state = None
        self._step = None
        self.last_aux = None

    def check_sequences(self) -> int:
        # what the timed step computes: one sequence a data shard (the
        # expert layer lays out 8 assignments a token, so a second sequence
        # of 16 384 tokens is 2 GB more of rows beside the reference)
        shards = 1
        for axis in ("dp", "ep"):
            shards *= self.mesh.shape.get(axis, 1)
        return shards

    def program_loss_and_grads(self, batch: dict):
        """The objective training descends, the cross-entropy plus the
        indexers' summed loss, and its gradients."""
        import jax
        from horovod_tpu.models.transformer import make_grad_fn
        from trees import get_leaves
        grad_fn, paths = make_grad_fn(self.cfg, self.mesh), self.leaf_paths

        @jax.jit
        def fn(params, b):
            loss, aux, grads = grad_fn(params, b["tokens"], b["targets"])
            return (loss + aux["aux_loss"] + aux["index_loss"],
                    get_leaves(grads, paths))
        return fn(self.params, batch)

    def program_selection(self, batch: dict):
        """The keys the program's indexers select, as bits ``[L, B, S, S //
        8]`` (for ``reference.loss_and_grads(.., selection=..)``: what part
        of an error differing selections explain)."""
        import functools
        import jax
        from horovod_tpu.models.transformer import index_selections
        return jax.jit(functools.partial(index_selections, cfg=self.cfg))(
            self.params, batch["tokens"])
