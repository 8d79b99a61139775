"""Adapter ``flagship``: the dense GPT block of models/transformer.py
through ``make_train_step`` (shard_map path; on a TPU at head_dim 128 the
``hvd_flash_attention`` and ``hvd_fused_xent`` kernels engage).

The configuration file uses the source's key names (GPT-2 style:
``n_embd``, ``n_head``, ``n_inner``, ``n_layer``, ``n_positions``).
"""

from __future__ import annotations

import math

import numpy as np

from trees import as_shapes, get_leaves


def _leaf_paths(n_layers: int) -> dict:
    """See trees.py; weights are stacked ``[stage, layer, ...]``."""
    return {
        "embedding": (("embed",), None),
        "first_query": (("layers", "wq"), (0, 0)),
        "last_ffn_out": (("layers", "w2"), (0, n_layers - 1)),
    }


def shapes(config: dict, job: dict) -> dict:
    return {
        "batch": job["batch_per_chip"], "seq": job["seq_len"],
        "layers": config["n_layer"], "d_model": config["n_embd"],
        "heads": config["n_head"],
        "head_dim": config["n_embd"] // config["n_head"],
        "d_ff": config["n_inner"], "vocab": config["vocab_size"],
        "causal": True,
    }


def tokens_per_step(job: dict, chips: int) -> int:
    return job["batch_per_chip"] * job["seq_len"] * chips


def flops_per_token(config: dict, job: dict) -> float:
    """Matmul FLOPs a causal decoder needs per trained token: forward +
    backward (= 3 x forward), nothing recomputed. Attention counts the
    causal half: a query at position t multiplies t + 1 keys, (S + 1) / 2
    on average. The tied softmax counts at every position."""
    m, f = config["n_embd"], config["n_inner"]
    s, v = job["seq_len"], config["vocab_size"]
    keys = (s + 1) / 2
    layer = (
        3 * 2 * m * m          # q, k, v projections
        + 2 * keys * m         # q k^T over all heads
        + 2 * keys * m         # probabilities times v
        + 2 * m * m            # attention output projection
        + 2 * m * f + 2 * f * m)
    forward = config["n_layer"] * layer + 2 * m * v
    return 3.0 * forward


def host_batch(config: dict, job: dict, seed: int, index: int,
               n_seqs: int) -> dict:
    rng = np.random.default_rng([seed, index + 1])
    tokens = rng.integers(0, config["vocab_size"],
                          (n_seqs, job["seq_len"]), dtype=np.int32)
    return {"tokens": tokens, "targets": np.roll(tokens, -1, axis=1)}


def _model_config(config: dict, job: dict):
    from horovod_tpu.models.transformer import TransformerConfig
    if job["seq_len"] > config["n_positions"]:
        raise ValueError("seq_len beyond the configuration's positions")
    return TransformerConfig(
        vocab_size=config["vocab_size"], d_model=config["n_embd"],
        n_heads=config["n_head"], n_layers=config["n_layer"],
        d_ff=config["n_inner"], max_seq=config["n_positions"])


def _init_function(c):
    """Draws the tree of transformer.init_params (one stage, dense FFN)
    in its shapes and scales from a key: init_params itself draws with
    NumPy on the host, 17 s for 405 M parameters."""
    import jax
    import jax.numpy as jnp
    m, hd, f, n = c.d_model, c.n_heads * c.head_dim, c.d_ff, c.n_layers

    def make(key):
        k = jax.random.split(key, 7)

        def w(key, *shape, scale=None):
            scale = 1.0 / math.sqrt(shape[-2]) if scale is None else scale
            return jax.random.normal(key, shape, jnp.float32) * scale
        return {
            "embed": w(k[0], c.vocab_size, m, scale=0.02),
            "ln_f": jnp.ones((m,), jnp.float32),
            "layers": {
                "ln1": jnp.ones((1, n, m), jnp.float32),
                "wq": w(k[1], 1, n, m, hd), "wk": w(k[2], 1, n, m, hd),
                "wv": w(k[3], 1, n, m, hd), "wo": w(k[4], 1, n, hd, m),
                "ln2": jnp.ones((1, n, m), jnp.float32),
                "w1": w(k[5], 1, n, m, f), "w2": w(k[6], 1, n, f, m),
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


class Cell:
    def __init__(self, config: dict, job: dict, mesh, seed: int):
        import jax
        from horovod_tpu.models.transformer import param_shardings
        self.mesh = mesh
        self.cfg = _model_config(config, job)
        self.leaf_paths = _leaf_paths(config["n_layer"])
        # on the device in one jitted call, placed as shard_params would
        self.params = jax.jit(
            _init_function(self.cfg),
            out_shardings=param_shardings(self.cfg, mesh))(
                jax.random.PRNGKey(seed))
        self.opt_state = None
        self._step = None

    def batch_sharding(self):
        from jax.sharding import NamedSharding
        from horovod_tpu.models.transformer import data_sharding_spec
        return NamedSharding(self.mesh, data_sharding_spec(self.mesh))

    def check_sequences(self) -> int:
        # make_grad_fn's shard_map splits the batch over the data axes:
        # two sequences, or one a data shard where there are more shards
        shards = 1
        for axis in ("dp", "ep"):
            shards *= self.mesh.shape.get(axis, 1)
        return max(2, shards)

    def check_sharding(self):
        return self.batch_sharding()

    def plain_params(self) -> dict:
        return self.params

    def named_leaves(self) -> dict:
        return get_leaves(self.params, self.leaf_paths)

    def program_loss_and_grads(self, batch: dict):
        import jax
        from horovod_tpu.models.transformer import make_grad_fn
        grad_fn, paths = make_grad_fn(self.cfg, self.mesh), self.leaf_paths

        @jax.jit
        def fn(params, b):
            loss, _aux, grads = grad_fn(params, b["tokens"], b["targets"])
            return loss, get_leaves(grads, paths)
        return fn(self.params, batch)

    def init_optimizer(self, tx) -> None:
        from horovod_tpu.models.transformer import (init_opt_state,
                                                    make_train_step)
        self.opt_state = init_opt_state(tx, self.params, self.mesh,
                                        self.cfg)
        self._step = make_train_step(self.cfg, self.mesh, tx)

    def step(self, batch: dict):
        self.params, self.opt_state, loss, _aux = self._step(
            self.params, self.opt_state, batch["tokens"], batch["targets"])
        return loss

    def compiled_step(self, batch: dict):
        return self._step.lower(self.params, self.opt_state,
                                batch["tokens"], batch["targets"]).compile()
