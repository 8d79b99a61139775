"""BERT pretraining on synthetic data (reference analog: the BASELINE's
"BERT-Large pretraining (DistributedOptimizer + fp16 compression)" config).

Use --large for BERT-Large (needs TPU HBM); default is BERT-Base-shaped but
tiny for smoke-running anywhere.
"""

import argparse
import time

import numpy as np
import jax
import jax.numpy as jnp
import optax

import horovod_tpu as hvd
from horovod_tpu.models import init_opt_state
from horovod_tpu.models.bert import (Bert, BertConfig, bert_large, init_bert,
                                     make_bert_train_step)


def synthetic_batch(rng, B, S, vocab):
    return {
        "input_ids": jnp.asarray(rng.randint(0, vocab, (B, S)), jnp.int32),
        "token_type_ids": jnp.zeros((B, S), jnp.int32),
        "attention_mask": jnp.ones((B, S), bool),
        "mlm_labels": jnp.asarray(rng.randint(0, vocab, (B, S)), jnp.int32),
        "mlm_mask": jnp.asarray(rng.rand(B, S) < 0.15, jnp.float32),
        "nsp_labels": jnp.asarray(rng.randint(0, 2, (B,)), jnp.int32),
    }


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--large", action="store_true")
    ap.add_argument("--batch-size", type=int, default=8)
    ap.add_argument("--seq-len", type=int, default=128)
    ap.add_argument("--steps", type=int, default=10)
    ap.add_argument("--tp", type=int, default=1,
                    help="tensor-parallel width")
    args = ap.parse_args()

    hvd.init()
    mesh = hvd.build_mesh(dp=-1, tp=args.tp)
    if args.large:
        cfg = bert_large()
    else:
        cfg = BertConfig(vocab_size=8192, hidden_size=256, num_layers=4,
                         num_heads=8, intermediate_size=1024,
                         dtype=jnp.bfloat16
                         if jax.default_backend() == "tpu" else jnp.float32)
    model = Bert(cfg)
    params = init_bert(model, jax.random.PRNGKey(0), args.seq_len, mesh)
    tx = optax.adamw(1e-4)
    opt_state = init_opt_state(tx, params, mesh)
    step = make_bert_train_step(model, tx, mesh)

    rng = np.random.RandomState(0)
    batch = synthetic_batch(rng, args.batch_size * jax.device_count(),
                            args.seq_len, cfg.vocab_size)

    params, opt_state, loss = step(params, opt_state, batch)  # compile
    float(loss)
    t0 = time.perf_counter()
    for i in range(args.steps):
        params, opt_state, loss = step(params, opt_state, batch)
    final = float(loss)
    dt = time.perf_counter() - t0
    if hvd.rank() == 0:
        seq_sec = args.batch_size * jax.device_count() * args.steps / dt
        print(f"loss {final:.4f}; {seq_sec:.1f} sequences/sec")
    hvd.shutdown()


if __name__ == "__main__":
    main()
