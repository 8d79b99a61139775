"""BERT-Large — the second headline benchmark family
(reference: BASELINE "BERT-Large pretraining (PyTorch DistributedOptimizer +
fp16 compression)"; the reference has no model zoo — users bring torch/TF
BERT and wrap its optimizer).

TPU-native: a flax encoder in bf16 with fp32 layernorms, MLM + NSP heads,
trained in GSPMD-auto mode — batch over data axes, optionally tensor-
parallel via logical axis annotations (``nn.with_partitioning``) so heads /
mlp shard over ``tp`` when the mesh has one.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Optional

import jax
import jax.numpy as jnp

from horovod_tpu.models.scan_util import multi_step
from horovod_tpu.ops.pallas_attention import attend, attention_path
from horovod_tpu.profiling import scopes
import flax.linen as nn
import optax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


@dataclasses.dataclass(frozen=True)
class BertConfig:
    vocab_size: int = 30522
    hidden_size: int = 1024          # BERT-Large
    num_layers: int = 24
    num_heads: int = 16
    intermediate_size: int = 4096
    max_position: int = 512
    type_vocab_size: int = 2
    dtype: Any = jnp.bfloat16


def bert_large(dtype=jnp.bfloat16) -> "BertConfig":
    return BertConfig(dtype=dtype)


def bert_base(dtype=jnp.bfloat16) -> "BertConfig":
    return BertConfig(hidden_size=768, num_layers=12, num_heads=12,
                      intermediate_size=3072, dtype=dtype)


class HeadsDense(nn.Module):
    """``nn.DenseGeneral`` into or out of ``[heads, head_dim]``, with its
    parameters (names, shapes, initial values, ``tp`` partitioning), as
    one 2-D matmul on ``[.., heads·head_dim]``. ``kernel`` is ``[hidden,
    heads, head_dim]`` (``n_in`` 1) or ``[heads, head_dim, hidden]``
    (``n_in`` 2). An activation shaped ``[B, S, heads, 64]`` makes XLA:TPU
    put S on the lanes (64 would pad to 128), and every array that then
    crosses into the attention kernel's ``[B, S, heads·64]`` is copied:
    q, k, v, o and their cotangents, 8 copies of 16.8 MB a layer at
    BERT-Large (PERF.md, PR 27). Born flat, none is."""
    kernel_shape: tuple
    kernel_names: tuple
    n_in: int
    dtype: Any

    @nn.compact
    def __call__(self, x):
        shape, n_in = self.kernel_shape, self.n_in
        rows, cols = math.prod(shape[:n_in]), math.prod(shape[n_in:])

        def flat_normal(key, shape, dtype=jnp.float32):
            # DenseGeneral draws the matrix flat and folds it
            return nn.initializers.normal(0.02)(
                key, (rows, cols), dtype).reshape(shape)
        kernel = self.param("kernel", nn.with_partitioning(
            flat_normal, self.kernel_names), shape)
        bias = self.param("bias", nn.initializers.zeros, shape[n_in:])
        x, kernel, bias = nn.dtypes.promote_dtype(x, kernel, bias,
                                                  dtype=self.dtype)
        return x @ kernel.reshape(rows, cols) + bias.reshape(cols)


class SelfAttention(nn.Module):
    cfg: BertConfig

    @nn.compact
    def __call__(self, x, mask):
        c = self.cfg
        heads = (c.num_heads, c.hidden_size // c.num_heads)
        # the projections write what the core reads: flat rows for the
        # block kernels; for XLA's einsums DenseGeneral's [B, S, H, D],
        # whose layout is then XLA's to choose. Same parameters either way
        flat = attention_path(x.shape[1], x.shape[1], *heads, False,
                              True) == "block"
        if flat:
            q, k, v = (HeadsDense((c.hidden_size,) + heads,
                                  (None, "tp", None), 1, c.dtype,
                                  name=name)(x).reshape(x.shape[:2] + heads)
                       for name in ("query", "key", "value"))
        else:
            q, k, v = (nn.DenseGeneral(
                heads, dtype=c.dtype, name=name,
                kernel_init=nn.with_partitioning(
                    nn.initializers.normal(0.02), (None, "tp", None)))(x)
                for name in ("query", "key", "value"))
        with scopes.scope(scopes.ATTENTION_CORE):
            o = attend(q, k, v, causal=False, key_mask=mask)
        if flat:
            return HeadsDense(heads + (c.hidden_size,), ("tp", None, None),
                              2, c.dtype, name="out")(o.reshape(x.shape))
        return nn.DenseGeneral(c.hidden_size, axis=(-2, -1), dtype=c.dtype,
                               name="out",
                               kernel_init=nn.with_partitioning(
                                   nn.initializers.normal(0.02),
                                   ("tp", None, None)))(o)


class BertLayer(nn.Module):
    cfg: BertConfig

    @nn.compact
    def __call__(self, x, mask):
        c = self.cfg
        with scopes.scope(scopes.ATTENTION):
            a = SelfAttention(c, name="attention")(x, mask)
            x = nn.LayerNorm(dtype=jnp.float32, name="ln_att")(x + a)
        with scopes.scope(scopes.MLP):
            h = nn.Dense(c.intermediate_size, dtype=c.dtype, name="ffn_in",
                         kernel_init=nn.with_partitioning(
                             nn.initializers.normal(0.02), (None, "tp")))(x)
            h = nn.gelu(h)
            h = nn.Dense(c.hidden_size, dtype=c.dtype, name="ffn_out",
                         kernel_init=nn.with_partitioning(
                             nn.initializers.normal(0.02), ("tp", None)))(h)
            x = nn.LayerNorm(dtype=jnp.float32, name="ln_ffn")(x + h)
        return x


class Bert(nn.Module):
    cfg: BertConfig

    @nn.compact
    def __call__(self, input_ids, token_type_ids, attention_mask):
        c = self.cfg
        emb = nn.Embed(c.vocab_size, c.hidden_size, dtype=c.dtype,
                       name="word_embeddings",
                       embedding_init=nn.with_partitioning(
                           nn.initializers.normal(0.02), ("tp", None)))
        with scopes.scope(scopes.EMBED):
            x = emb(input_ids)
            pos = jnp.arange(input_ids.shape[1])[None]
            x = x + nn.Embed(c.max_position, c.hidden_size, dtype=c.dtype,
                             name="position_embeddings")(pos)
            x = x + nn.Embed(c.type_vocab_size, c.hidden_size,
                             dtype=c.dtype,
                             name="token_type_embeddings")(token_type_ids)
            x = nn.LayerNorm(dtype=jnp.float32, name="ln_emb")(x)
        with scopes.scope(scopes.LAYERS):
            for i in range(c.num_layers):
                x = BertLayer(c, name=f"layer_{i}")(x, attention_mask)
        # MLM head (tied to word embeddings) + NSP head on [CLS]
        with scopes.scope(scopes.HEAD):
            h = nn.Dense(c.hidden_size, dtype=c.dtype,
                         name="mlm_transform")(x)
            h = nn.LayerNorm(dtype=jnp.float32, name="mlm_ln")(nn.gelu(h))
            mlm_logits = emb.attend(h.astype(c.dtype)).astype(jnp.float32)
            nsp_logits = nn.Dense(2, dtype=jnp.float32, name="nsp")(
                x[:, 0].astype(jnp.float32))
        return mlm_logits, nsp_logits


def pretrain_loss(mlm_logits, nsp_logits, mlm_labels, mlm_mask, nsp_labels):
    """Masked-LM + next-sentence loss (standard BERT pretraining)."""
    with scopes.scope(scopes.HEAD):
        v = mlm_logits.shape[-1]
        mlm = optax.softmax_cross_entropy(
            mlm_logits, jax.nn.one_hot(mlm_labels, v))
        denom = jnp.maximum(jnp.sum(mlm_mask), 1.0)
        mlm = jnp.sum(mlm * mlm_mask) / denom
        nsp = optax.softmax_cross_entropy(
            nsp_logits, jax.nn.one_hot(nsp_labels, 2)).mean()
        return mlm + nsp


def make_bert_train_step(model: Bert, optimizer, mesh: Mesh,
                         scan_steps: int = 1):
    """GSPMD-auto pretraining step; flax partitioning metadata shards the
    big matrices over ``tp`` while XLA handles dp gradient reduction.

    ``scan_steps > 1`` runs that many optimizer steps per call via
    ``lax.scan`` in ONE compiled program (one dispatch per chain; see
    ``make_resnet_train_step``). All scanned steps consume the SAME
    batch (``scan_util.multi_step`` same-batch semantics — a throughput
    construct, not multi-batch training). The returned loss is the last
    step's.

    ``params``/``opt_state`` buffers are DONATED (in-place update on
    device): keep only the returned state — the inputs are invalidated
    after the call on TPU."""

    def one_step(params, opt_state, batch):
        def loss_fn(p):
            mlm_logits, nsp_logits = model.apply(
                {"params": p}, batch["input_ids"], batch["token_type_ids"],
                batch["attention_mask"])
            return pretrain_loss(mlm_logits, nsp_logits,
                                 batch["mlm_labels"], batch["mlm_mask"],
                                 batch["nsp_labels"])
        loss, grads = jax.value_and_grad(loss_fn)(params)
        with scopes.scope(scopes.OPTIMIZER):
            updates, opt_state = optimizer.update(grads, opt_state, params)
            params = optax.apply_updates(params, updates)
        return params, opt_state, loss

    chain = multi_step(one_step, n_carry=2, scan_steps=scan_steps)

    @functools.partial(jax.jit, donate_argnums=(0, 1))
    def step(params, opt_state, batch):
        return chain(params, opt_state, batch)

    return step


def init_bert(model: Bert, rng_key, seq_len: int = 128, mesh: Mesh = None):
    """Initialize; apply flax logical partitioning onto the mesh's tp axis
    (replicated when tp is absent)."""
    # no parameter's shape or value depends on the sequence length, and a
    # few positions keep the attention kernels (their lowering and their
    # compile) out of a program that only draws the parameters
    dummy = jnp.zeros((1, min(seq_len, 8)), jnp.int32)
    # one compiled program: op by op, 24 layers of initializers are a
    # few hundred small compiles on the chip
    variables = jax.jit(model.init)(rng_key, dummy, dummy,
                                    jnp.ones(dummy.shape, bool))
    params = variables["params"]
    if mesh is not None:
        import flax
        tp_live = mesh.shape.get("tp", 1) > 1

        def place(x):
            if isinstance(x, nn.Partitioned):
                spec = P(*x.names) if tp_live else P()
                arr = jax.device_put(x.value, NamedSharding(mesh, spec))
                return x.replace_boxed(arr)
            return jax.device_put(x, NamedSharding(mesh, P()))
        params = jax.tree_util.tree_map(
            place, params,
            is_leaf=lambda x: isinstance(x, nn.Partitioned))
    return params
