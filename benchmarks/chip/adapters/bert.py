"""Adapter ``bert``: models/bert.py through ``init_bert`` and
``make_bert_train_step`` (GSPMD path, XLA attention).

The interface every adapter implements is in README.md. Nothing here is
imported until the harness has chosen the device, so the module touches
JAX only inside its functions.
"""

from __future__ import annotations

import numpy as np

from trees import as_shapes, get_leaves

def _leaf_paths(n_layers: int) -> dict:
    """The three leaves whose gradients are held against the reference
    and whose checksums are compared across replicas (see trees.py)."""
    return {
        "embedding": (("word_embeddings", "embedding"), None),
        "first_query": (("layer_0", "attention", "query", "kernel"), None),
        "last_ffn_out": ((f"layer_{n_layers - 1}", "ffn_out", "kernel"),
                         None),
    }


def shapes(config: dict, job: dict) -> dict:
    """Sizes under the generic names the roofline functions read, for one
    chip's share of a step."""
    return {
        "batch": job["batch_per_chip"], "seq": job["seq_len"],
        "layers": config["num_hidden_layers"],
        "d_model": config["hidden_size"],
        "heads": config["num_attention_heads"],
        "head_dim": config["hidden_size"] // config["num_attention_heads"],
        "d_ff": config["intermediate_size"], "vocab": config["vocab_size"],
        "causal": False,
    }


def tokens_per_step(job: dict, chips: int) -> int:
    """A token is one position of one sequence."""
    return job["batch_per_chip"] * job["seq_len"] * chips


def flops_per_token(config: dict, job: dict) -> float:
    """Matmul FLOPs the published algorithm needs per trained token:
    forward + backward (backward = 2 x forward), nothing recomputed.
    The masked-LM head (transform + tied decoder) counts at
    ``max_predictions_per_seq`` positions of ``seq_len``, as
    run_pretraining.py gathers them; pooler and next-sentence head once
    a sequence. Embedding lookups, softmax, LayerNorm and gelu count 0.
    """
    h, i = config["hidden_size"], config["intermediate_size"]
    s, v = job["seq_len"], config["vocab_size"]
    layer = (
        3 * 2 * h * h        # q, k, v projections
        + 2 * s * h          # q k^T over all heads: 2 * S * head_dim * heads
        + 2 * s * h          # probabilities times v
        + 2 * h * h          # attention output projection
        + 2 * h * i + 2 * i * h)   # FFN in and out
    mlm = (2 * h * h + 2 * h * v) * config["max_predictions_per_seq"] / s
    nsp = (2 * h * h + 2 * h * 2) / s
    forward = config["num_hidden_layers"] * layer + mlm + nsp
    return 3.0 * forward


def host_batch(config: dict, job: dict, seed: int, index: int,
               n_seqs: int) -> dict:
    """Batch ``index`` of the run, on the host, from the seed alone."""
    rng = np.random.default_rng([seed, index + 1])
    s, v = job["seq_len"], config["vocab_size"]
    return {
        "input_ids": rng.integers(0, v, (n_seqs, s), dtype=np.int32),
        "token_type_ids": np.zeros((n_seqs, s), np.int32),
        "attention_mask": np.ones((n_seqs, s), bool),
        "mlm_labels": rng.integers(0, v, (n_seqs, s), dtype=np.int32),
        "mlm_mask": (rng.random((n_seqs, s)) < job["mlm_mask_rate"]
                     ).astype(np.float32),
        "nsp_labels": rng.integers(0, 2, (n_seqs,), dtype=np.int32),
    }


def _model(config: dict):
    from horovod_tpu.models.bert import Bert, BertConfig
    return Bert(BertConfig(
        vocab_size=config["vocab_size"],
        hidden_size=config["hidden_size"],
        num_layers=config["num_hidden_layers"],
        num_heads=config["num_attention_heads"],
        intermediate_size=config["intermediate_size"],
        max_position=config["max_position_embeddings"],
        type_vocab_size=config["type_vocab_size"]))


def abstract_step(config: dict, job: dict, mesh, tx):
    """(jitted step, its arguments as shapes with shardings) for a compile
    without devices: everything replicated but the batch (dp meshes)."""
    import jax
    import jax.numpy as jnp
    import horovod_tpu as hvd
    from jax.sharding import NamedSharding, PartitionSpec as P
    from horovod_tpu.models.bert import make_bert_train_step
    model, rep = _model(config), NamedSharding(mesh, P())
    ids = jax.ShapeDtypeStruct((1, job["seq_len"]), jnp.int32)
    params = as_shapes(jax.eval_shape(
        model.init, jax.random.PRNGKey(0), ids, ids,
        jax.ShapeDtypeStruct(ids.shape, bool))["params"], rep)
    opt_state = as_shapes(jax.eval_shape(tx.init, params), rep)
    batch = as_shapes(
        host_batch(config, job, 0, 0, job["batch_per_chip"] * mesh.size),
        hvd.batch_sharding(mesh))
    return (make_bert_train_step(model, tx, mesh, scan_steps=1),
            (params, opt_state, batch))


class Cell:
    """One cell's model on one mesh: parameters, step, and the program's
    side of the reference check."""

    def __init__(self, config: dict, job: dict, mesh, seed: int):
        import jax
        from horovod_tpu.models.bert import init_bert
        self.mesh = mesh
        self.model = _model(config)
        self.leaf_paths = _leaf_paths(config["num_hidden_layers"])
        self.params = init_bert(self.model, jax.random.PRNGKey(seed),
                                job["seq_len"], mesh)
        self.opt_state = None
        self._step = None

    # -- placement --------------------------------------------------------
    def batch_sharding(self):
        import horovod_tpu as hvd
        return hvd.batch_sharding(self.mesh)

    def check_sequences(self) -> int:
        return 2

    def check_sharding(self):
        # two sequences do not split over every mesh: each replica
        # computes the whole check
        from jax.sharding import NamedSharding, PartitionSpec as P
        return NamedSharding(self.mesh, P())

    # -- the reference check's two sides -----------------------------------
    def plain_params(self) -> dict:
        """The same arrays without flax's partitioning boxes, for the
        reference (no copy)."""
        import flax.linen as nn
        return nn.meta.unbox(self.params)

    def named_leaves(self) -> dict:
        return get_leaves(self.plain_params(), self.leaf_paths)

    def program_loss_and_grads(self, batch: dict):
        """Loss and gradients as make_bert_train_step computes them
        inside: jax.value_and_grad over Bert.apply + pretrain_loss."""
        import flax.linen as nn
        import jax
        from horovod_tpu.models.bert import pretrain_loss
        model, paths = self.model, self.leaf_paths

        @jax.jit
        def fn(params, b):
            def loss_fn(p):
                mlm, nsp = model.apply(
                    {"params": p}, b["input_ids"], b["token_type_ids"],
                    b["attention_mask"])
                return pretrain_loss(mlm, nsp, b["mlm_labels"],
                                     b["mlm_mask"], b["nsp_labels"])
            loss, grads = jax.value_and_grad(loss_fn)(params)
            return loss, get_leaves(nn.meta.unbox(grads), paths)
        return fn(self.params, batch)

    # -- the train step ----------------------------------------------------
    def init_optimizer(self, tx) -> None:
        from horovod_tpu.models import init_opt_state
        from horovod_tpu.models.bert import make_bert_train_step
        self.opt_state = init_opt_state(tx, self.params, self.mesh)
        self._step = make_bert_train_step(self.model, tx, self.mesh,
                                          scan_steps=1)

    def step(self, batch: dict):
        """One optimizer step; returns the loss, still on the device."""
        self.params, self.opt_state, loss = self._step(
            self.params, self.opt_state, batch)
        return loss

    def compiled_step(self, batch: dict):
        return self._step.lower(self.params, self.opt_state,
                                batch).compile()
