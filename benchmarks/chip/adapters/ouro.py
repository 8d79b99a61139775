"""Adapter ``ouro``: Ouro (arXiv:2510.25741), a looped language model,
through the flagship block of models/transformer.py and ``make_train_step``,
the entry points the ``flagship`` adapter calls: the stack of layers run
``total_ut_steps`` times with the same weights (``n_loops``), an RMSNorm
before and after each sublayer (``post_norm``), a dense SiLU-gated FFN
(``ffn_gated``), the final norm after every loop step, the untied head on
every step's state, and the exit gate that mixes the steps' losses.
Attention and the loss are the GPT cell's kernels (``hvd_flash_attention``,
``hvd_fused_xent``), run 24 to 48 and 4 times a step here.

The configuration file uses the source's key names (``hidden_size``,
``intermediate_size``, ``num_hidden_layers``, ``total_ut_steps``, ...). The
host batch, the step and the checks are the flagship adapter's.
"""

from __future__ import annotations

import math

from adapters import flagship
from adapters.flagship import host_batch, tokens_per_step  # noqa: F401
from trees import as_shapes

#: Kernel calls a step in the compiled program, per layer pass and per loop
#: step (read from the compiled step's text; tests/test_tpu_compile.py
#: holds them to it): every block pass is checkpointed and its backward
#: runs the forward flash kernel again; the head is not recomputed.
ATTENTION_FORWARD_CALLS_PER_PASS = 2
HEAD_CALLS_PER_LOOP_STEP = 1


def _leaf_paths(n_layers: int) -> dict:
    """See trees.py; weights are stacked ``[stage, layer, ...]``. The head
    and the gate see every loop step's state; the first layer's query
    projection and the last layer's FFN and post-norm are used once a loop
    step, so their gradients are sums over the four uses."""
    last = (0, n_layers - 1)
    return {
        "lm_head": (("lm_head",), None),
        "exit_gate": (("exit_gate",), None),
        "first_query": (("layers", "wq"), (0, 0)),
        "last_ffn_down": (("layers", "w2"), last),
        "last_post_norm": (("layers", "ln2_post"), last),
    }


def shapes(config: dict, job: dict) -> dict:
    passes = config["num_hidden_layers"] * config["total_ut_steps"]
    return {
        "batch": job["batch_per_chip"], "seq": job["seq_len"],
        "layers": config["num_hidden_layers"],
        "loops": config["total_ut_steps"],
        "d_model": config["hidden_size"],
        "heads": config["num_attention_heads"],
        "head_dim": config["head_dim"],
        "d_ff": config["intermediate_size"], "vocab": config["vocab_size"],
        "causal": True,
        # the calls a step the compiled program makes, recomputed ones
        # included (the roofline functions of the two kernels)
        "attention_forward_calls": ATTENTION_FORWARD_CALLS_PER_PASS * passes,
        "head_calls": HEAD_CALLS_PER_LOOP_STEP * config["total_ut_steps"],
        # what the reference needs beside sizes
        "norm_eps": config["rms_norm_eps"],
        "rope_theta": config["rope_theta"],
        "entropy_weight": config["assumed"]["exit_entropy_weight"],
    }


def flops_per_token(config: dict, job: dict) -> float:
    """Matmul FLOPs Ouro needs per trained token: forward + backward (= 3 x
    forward), nothing recomputed. A token passes ``num_hidden_layers`` x
    ``total_ut_steps`` blocks, the untied head once a loop step, the exit
    gate (a matmul onto one column) once a loop step; attention counts the
    causal half: a query at position t multiplies t + 1 keys, (S + 1) / 2
    on average; the embedding lookup counts 0."""
    m, f = config["hidden_size"], config["intermediate_size"]
    s, v = job["seq_len"], config["vocab_size"]
    steps = config["total_ut_steps"]
    keys = (s + 1) / 2
    block = (
        4 * 2 * m * m              # q, k, v and output projections
        + 2 * 2 * keys * m         # q k^T and probabilities times v
        + 3 * 2 * m * f)           # gate, up, down
    forward = steps * (config["num_hidden_layers"] * block
                       + 2 * m * v      # head
                       + 2 * m)         # gate
    return 3.0 * forward


def _model_config(config: dict, job: dict):
    import jax.numpy as jnp
    from horovod_tpu.models.transformer import (EXIT_ENTROPY_WEIGHT,
                                                TransformerConfig)
    if config["assumed"]["exit_entropy_weight"] != EXIT_ENTROPY_WEIGHT:
        raise ValueError("the program's beta is not the configuration's")
    if job["seq_len"] > config["max_position_embeddings"]:
        raise ValueError("seq_len beyond the configuration's positions")
    if (config["hidden_act"], config["num_key_value_heads"],
            config["rope_scaling"], config["use_sliding_window"],
            config["head_dim"] * config["num_attention_heads"]) != (
                "silu", config["num_attention_heads"], None, False,
                config["hidden_size"]):
        raise ValueError("not the Ouro block the program implements")
    return TransformerConfig(
        vocab_size=config["vocab_size"], d_model=config["hidden_size"],
        n_heads=config["num_attention_heads"],
        n_layers=config["num_hidden_layers"],
        d_ff=config["intermediate_size"],
        max_seq=config["max_position_embeddings"],
        n_loops=config["total_ut_steps"], post_norm=True, ffn_gated=True,
        tie_embeddings=config["tie_word_embeddings"],
        norm_eps=config["rms_norm_eps"],
        rope_theta=float(config["rope_theta"]),
        dtype=jnp.dtype(config["compute_dtype"]))


def _init_function(c):
    """Draws the tree of transformer.init_params (one stage, sandwich
    norms, a gated FFN, an untied head, the exit gate) in its shapes and
    scales from a key, on the device."""
    import jax
    import jax.numpy as jnp
    m, hd, f, n = c.d_model, c.n_heads * c.head_dim, c.d_ff, c.n_layers

    def make(key):
        k = jax.random.split(key, 10)

        def w(key, *shape, scale=None):
            scale = 1.0 / math.sqrt(shape[-2]) if scale is None else scale
            return jax.random.normal(key, shape, jnp.float32) * scale

        def ones():
            return jnp.ones((1, n, m), jnp.float32)
        return {
            "embed": w(k[0], c.vocab_size, m, scale=0.02),
            "ln_f": jnp.ones((m,), jnp.float32),
            "lm_head": w(k[1], m, c.vocab_size),
            "exit_gate": w(k[2], m, 1),
            "exit_gate_bias": jnp.zeros((1,), jnp.float32),
            "layers": {
                "ln1": ones(), "ln1_post": ones(),
                "ln2": ones(), "ln2_post": ones(),
                "wq": w(k[3], 1, n, m, hd), "wk": w(k[4], 1, n, m, hd),
                "wv": w(k[5], 1, n, m, hd), "wo": w(k[6], 1, n, hd, m),
                "w1": w(k[7], 1, n, m, f), "w3": w(k[8], 1, n, m, f),
                "w2": w(k[9], 1, n, f, m),
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

    def check_sequences(self) -> int:
        """One sequence a data shard: the reference holds float32 logits of
        every loop step and 24 block passes of the cell's whole 4096-token
        context beside the program's own weights."""
        shards = 1
        for axis in ("dp", "ep"):
            shards *= self.mesh.shape.get(axis, 1)
        return shards
