"""Full-stack worker: the public hvd API over the native core with jax-cpu
arrays (launched by test_core_multiprocess.py)."""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

os.environ["JAX_PLATFORMS"] = "cpu"
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=1")

import jax  # noqa: E402

import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
import optax  # noqa: E402

import horovod_tpu as hvd  # noqa: E402


def main():
    rank = int(os.environ["HOROVOD_RANK"])
    size = int(os.environ["HOROVOD_SIZE"])
    hvd.init()
    assert hvd.rank() == rank and hvd.size() == size

    # eager allreduce on jax arrays
    x = jnp.arange(8.0) + rank
    out = hvd.allreduce(x, op=hvd.Sum, name="x")
    np.testing.assert_allclose(
        np.asarray(out), sum(np.arange(8.0) + r for r in range(size)))

    # average (the default)
    out = hvd.allreduce(jnp.ones(4) * (rank + 1), name="avg")
    np.testing.assert_allclose(np.asarray(out),
                               np.mean([r + 1 for r in range(size)]))

    # broadcast_parameters + broadcast_object
    params = {"w": jnp.full((3,), float(rank)), "b": {"c": jnp.ones(2) * rank}}
    params = hvd.broadcast_parameters(params, root_rank=0)
    np.testing.assert_allclose(np.asarray(params["w"]), 0.0)
    obj = hvd.broadcast_object({"val": rank * 7}, root_rank=1)
    assert obj == {"val": 7}

    # allgather_object: arbitrary (differently-sized) python objects,
    # rank-ordered (reference: torch/functions.py:233-266)
    gathered = hvd.allgather_object({"rank": rank, "pad": "x" * (rank * 13)})
    assert [g["rank"] for g in gathered] == list(range(size))
    assert all(len(g["pad"]) == 13 * g["rank"] for g in gathered)

    # DistributedOptimizer: eager grads differ per rank, must sync to mean
    tx = hvd.DistributedOptimizer(optax.sgd(1.0))
    p = {"w": jnp.zeros(4)}
    st = tx.init(p)
    grads = {"w": jnp.full(4, float(rank + 1))}
    updates, st = tx.update(grads, st, p)
    mean_grad = np.mean([r + 1 for r in range(size)])
    np.testing.assert_allclose(np.asarray(updates["w"]), -mean_grad)

    # allgather (ragged)
    rows = rank + 1
    g = hvd.allgather(jnp.ones((rows, 2)) * rank, name="ag")
    assert np.asarray(g).shape == (sum(r + 1 for r in range(size)), 2)

    # alltoall even splits
    t, rs = hvd.alltoall(jnp.arange(float(size * 2)).reshape(size * 2, 1))
    assert np.asarray(t).shape == (size * 2, 1)

    # process set on ranks [0, 1]
    if size >= 2:
        ps = hvd.add_process_set([0, 1])
        if rank < 2:
            out = hvd.allreduce(jnp.ones(2), op=hvd.Sum, name="ps",
                                process_set=ps)
            np.testing.assert_allclose(np.asarray(out), 2.0)
        hvd.barrier()

    # reducescatter (default backend path: allreduce + slice)
    rsc = hvd.reducescatter(jnp.ones((size * 2, 3)), op=hvd.Sum, name="rs")
    np.testing.assert_allclose(np.asarray(rsc), float(size))
    assert np.asarray(rsc).shape == (2, 3)

    # join
    last = hvd.join()
    assert isinstance(last, int)

    hvd.shutdown()
    print(f"hvd worker {rank}: OK", flush=True)


if __name__ == "__main__":
    main()
