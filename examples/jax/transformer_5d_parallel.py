"""Flagship demo: MoE transformer LM trained with all five parallelism axes
(dp / pp / ep / sp / tp) over a single device mesh.

On a TPU slice this runs as-is; on CPU try:
  XLA_FLAGS=--xla_force_host_platform_device_count=8 JAX_PLATFORMS=cpu \
      python examples/jax/transformer_5d_parallel.py
"""

import numpy as np
import jax
import jax.numpy as jnp
import optax

import horovod_tpu as hvd
from horovod_tpu.models import (TransformerConfig, init_params,
                                init_opt_state, make_train_step,
                                shard_batch, shard_params)


def main():
    hvd.init()
    n = jax.device_count()
    # pick a mesh for the available chips (all axes exercised at n >= 32)
    if n >= 32:
        mesh = hvd.build_mesh(dp=n // 16, pp=2, ep=2, sp=2, tp=2)
        n_stages = 2
    elif n >= 8:
        mesh = hvd.build_mesh(dp=n // 8, pp=2, sp=2, tp=2)
        n_stages = 2
    else:
        mesh = hvd.build_mesh(dp=-1)
        n_stages = 1
    print("mesh:", dict(mesh.shape))

    cfg = TransformerConfig(
        vocab_size=1024, d_model=128, n_heads=8, n_layers=4, d_ff=256,
        max_seq=128, n_experts=4 if mesh.shape.get("ep", 1) > 1 else 0,
        dtype=jnp.bfloat16 if jax.default_backend() == "tpu"
        else jnp.float32,
        n_microbatches=2, remat=True)

    params = shard_params(init_params(np.random.RandomState(0), cfg,
                                      n_stages), cfg, mesh)
    tx = optax.adamw(3e-4)
    step = make_train_step(cfg, mesh, tx)
    opt_state = init_opt_state(tx, params, mesh, cfg)

    rng = np.random.RandomState(1)
    B, S = 16, 128
    tokens = jnp.asarray(rng.randint(0, cfg.vocab_size, (B, S)), jnp.int32)
    targets = jnp.asarray(np.roll(np.asarray(tokens), -1, 1), jnp.int32)
    tokens, targets = shard_batch(tokens, targets, mesh)

    for i in range(10):
        params, opt_state, loss, aux = step(params, opt_state, tokens,
                                            targets)
        print(f"step {i}: loss {float(loss):.4f} "
              f"aux {float(aux['aux_loss']):.4f}")
    hvd.shutdown()


if __name__ == "__main__":
    main()
