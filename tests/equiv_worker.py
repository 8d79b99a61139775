"""Worker proving distributed == serial: 2-process DP training must produce
bit-comparable weights to single-process full-batch training (reference
analog: the convergence guarantees its allreduce semantics imply)."""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
os.environ["JAX_PLATFORMS"] = "cpu"

import jax  # noqa: E402

import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
import optax  # noqa: E402

import horovod_tpu as hvd  # noqa: E402


def serial_reference(X, Y, steps, lr):
    params = {"w": jnp.zeros((8, 2))}
    tx = optax.sgd(lr)
    st = tx.init(params)
    gf = jax.jit(jax.value_and_grad(
        lambda p, x, y: jnp.mean((x @ p["w"] - y) ** 2)))
    for _ in range(steps):
        _, g = gf(params, X, Y)
        u, st = tx.update(g, st, params)
        params = optax.apply_updates(params, u)
    return params


def main():
    hvd.init()
    rank, size = hvd.rank(), hvd.size()
    rng = np.random.RandomState(0)
    W_true = rng.randn(8, 2).astype(np.float32)
    X = rng.randn(32, 8).astype(np.float32)
    Y = X @ W_true

    # distributed: each rank holds an equal contiguous shard; grads averaged
    shard = 32 // size
    Xs = jnp.asarray(X[rank * shard:(rank + 1) * shard])
    Ys = jnp.asarray(Y[rank * shard:(rank + 1) * shard])
    params = {"w": jnp.zeros((8, 2))}
    tx = hvd.DistributedOptimizer(optax.sgd(0.1))
    st = tx.init(params)
    gf = jax.jit(jax.value_and_grad(
        lambda p, x, y: jnp.mean((x @ p["w"] - y) ** 2)))
    for _ in range(40):
        _, g = gf(params, Xs, Ys)
        u, st = tx.update(g, st, params)  # eager allreduce(mean) via core
        params = optax.apply_updates(params, u)

    ref = serial_reference(jnp.asarray(X), jnp.asarray(Y), 40, 0.1)
    # mean of shard-mean grads == full-batch mean grad (equal shards), so
    # the trajectories must agree to float tolerance
    np.testing.assert_allclose(np.asarray(params["w"]),
                               np.asarray(ref["w"]), rtol=1e-5, atol=1e-6)
    print(f"rank {rank}: distributed == serial ✓", flush=True)
    hvd.shutdown()


if __name__ == "__main__":
    main()
