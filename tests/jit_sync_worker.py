"""Worker: the WHOLE train step (including cross-process gradient sync) runs
under jax.jit — the io_callback bridge to the negotiating core (SURVEY §7
hard part (d))."""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
os.environ["JAX_PLATFORMS"] = "cpu"
import jax  # noqa: E402

import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
import optax  # noqa: E402

import horovod_tpu as hvd  # noqa: E402


def main():
    hvd.init()
    rank, size = hvd.rank(), hvd.size()
    rng = np.random.RandomState(0)
    W_true = rng.randn(8, 2).astype(np.float32)
    X = rng.randn(32, 8).astype(np.float32)
    Y = X @ W_true
    shard = 32 // size
    Xs = jnp.asarray(X[rank * shard:(rank + 1) * shard])
    Ys = jnp.asarray(Y[rank * shard:(rank + 1) * shard])

    params = {"w": jnp.zeros((8, 2))}
    tx = hvd.DistributedOptimizer(optax.sgd(0.1), host_sync_in_jit=True)
    st = tx.init(params)

    @jax.jit
    def step(params, st, x, y):
        loss, g = jax.value_and_grad(
            lambda p: jnp.mean((x @ p["w"] - y) ** 2))(params)
        u, st = tx.update(g, st, params)  # io_callback -> core allreduce
        return optax.apply_updates(params, u), st, loss

    for _ in range(40):
        params, st, loss = step(params, st, Xs, Ys)
        jax.block_until_ready(loss)

    # must equal serial full-batch training (equal shards)
    ref = {"w": jnp.zeros((8, 2))}
    rtx = optax.sgd(0.1)
    rst = rtx.init(ref)
    gf = jax.jit(jax.value_and_grad(
        lambda p, x, y: jnp.mean((x @ p["w"] - y) ** 2)))
    for _ in range(40):
        _, g = gf(ref, jnp.asarray(X), jnp.asarray(Y))
        u, rst = rtx.update(g, rst, ref)
        ref = optax.apply_updates(ref, u)
    np.testing.assert_allclose(np.asarray(params["w"]),
                               np.asarray(ref["w"]), rtol=1e-5, atol=1e-6)
    print(f"rank {rank}: jitted-step distributed == serial ✓", flush=True)
    hvd.shutdown()


if __name__ == "__main__":
    main()
