"""Worker for the XLA eager backend (HVD_TPU_OPERATIONS=XLA_EAGER):
collectives ride jitted XLA programs over the jax.distributed global mesh."""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["HOROVOD_TPU_OPERATIONS"] = "XLA_EAGER"

import jax  # noqa: E402

import numpy as np  # noqa: E402
import jax.numpy as jnp  # noqa: E402

import horovod_tpu as hvd  # noqa: E402


def main():
    rank = int(os.environ["HOROVOD_RANK"])
    size = int(os.environ["HOROVOD_SIZE"])
    hvd.init()
    assert hvd.rank() == rank and hvd.size() == size
    from horovod_tpu.ops.xla_backend import XlaBackend
    from horovod_tpu.common.basics import _require_init
    assert isinstance(_require_init().backend, XlaBackend)

    # allreduce sum / average
    out = hvd.allreduce(jnp.arange(8.0) + rank, op=hvd.Sum, name="s")
    np.testing.assert_allclose(
        np.asarray(out), sum(np.arange(8.0) + r for r in range(size)))
    out = hvd.allreduce(jnp.ones(4) * (rank + 1), name="a")
    np.testing.assert_allclose(np.asarray(out),
                               np.mean([r + 1 for r in range(size)]))
    # min/max
    mn = hvd.allreduce(jnp.asarray([float(rank)]), op=hvd.Min, name="mn")
    mx = hvd.allreduce(jnp.asarray([float(rank)]), op=hvd.Max, name="mx")
    assert float(np.asarray(mn)[0]) == 0 and \
        float(np.asarray(mx)[0]) == size - 1

    # broadcast from nonzero root
    b = hvd.broadcast(jnp.full(3, float(rank)), root_rank=size - 1, name="b")
    np.testing.assert_allclose(np.asarray(b), float(size - 1))

    # ragged allgather
    g = hvd.allgather(jnp.ones((rank + 1, 2)) * rank, name="g")
    assert np.asarray(g).shape == (sum(r + 1 for r in range(size)), 2)

    # uniform alltoall
    t, rs = hvd.alltoall(jnp.arange(float(size * 2)).reshape(size * 2, 1),
                         name="t")
    assert list(np.asarray(rs)) == [2] * size

    # uneven alltoall: rank r sends (i+1) rows of value r*10+i to rank i
    splits = [i + 1 for i in range(size)]
    sendbuf = np.concatenate([
        np.full((i + 1, 2), rank * 10 + i, np.float32)
        for i in range(size)])
    out, recv = hvd.alltoall(jnp.asarray(sendbuf), splits=splits, name="u")
    expect = np.concatenate([
        np.full((rank + 1, 2), r * 10 + rank, np.float32)
        for r in range(size)])
    np.testing.assert_allclose(np.asarray(out), expect)

    # grouped allreduce: ONE fused program — check numerics here and that
    # the compiled program has a single all-reduce per dtype group
    vals = [jnp.full((16,), float(rank + 1)),
            jnp.ones((4, 4)) * rank,
            jnp.asarray(np.arange(6, dtype=np.int32))]
    outs = hvd.grouped_allreduce(vals, op=hvd.Sum, name="grp")
    np.testing.assert_allclose(
        np.asarray(outs[0]), sum(r + 1.0 for r in range(size)))
    np.testing.assert_allclose(
        np.asarray(outs[1]), np.ones((4, 4)) * sum(range(size)))
    np.testing.assert_allclose(np.asarray(outs[2]),
                               np.arange(6) * size)
    be = _require_init().backend
    grouped_keys = [k for k in be._group._fn_cache if k[0] == "grouped"]
    assert len(grouped_keys) == 1, grouped_keys
    fused = be._group._fn_cache[grouped_keys[0]]
    arrs = [np.asarray(v) for v in vals]
    garrs = [be._group.to_global(a) for a in arrs]
    hlo = fused.lower(*garrs).compile().as_text()
    n_ar = hlo.count("all-reduce(") + hlo.count("all-reduce-start(")
    # one per dtype group (f32, i32); XLA's combiner may merge further —
    # the claim is it is NOT one collective per tensor (= 3)
    assert 1 <= n_ar <= 2, \
        f"expected <=2 fused all-reduces for 3 tensors, got {n_ar}"

    # async overlap: enqueue returns before completion (a fresh-shape
    # collective must still be compiling when the handle comes back)
    h = hvd.allreduce_async(jnp.ones((257, 129)), op=hvd.Sum, name="ov")
    assert not h.poll(), "handle completed synchronously - no overlap"
    np.testing.assert_allclose(np.asarray(h.wait(120)),
                               np.ones((257, 129)) * size)

    # Adasum must apply the VHDD combine, not a plain sum (ADVICE r1)
    from horovod_tpu.ops.adasum import adasum_tree_reduce
    xs = [np.full((8,), float(r + 1), np.float32) for r in range(size)]
    ad = hvd.allreduce(jnp.asarray(xs[rank]), op=hvd.Adasum, name="ad")
    expect = np.asarray(adasum_tree_reduce(jnp.asarray(np.stack(xs))))
    np.testing.assert_allclose(np.asarray(ad), expect, rtol=1e-5)

    # grouped Adasum: fused transfer but PER-TENSOR combine coefficients
    # (one big + one small tensor would pollute each other if the combine
    # ran over the concatenated buffer)
    a_r = np.full((6,), float(rank + 1), np.float32)
    b_r = np.full((3,), float(10 * (rank + 1)), np.float32)
    ga, gb = hvd.grouped_allreduce(
        [jnp.asarray(a_r), jnp.asarray(b_r)], op=hvd.Adasum, name="gad")
    ea = np.asarray(adasum_tree_reduce(jnp.asarray(np.stack(
        [np.full((6,), float(r + 1), np.float32) for r in range(size)]))))
    eb = np.asarray(adasum_tree_reduce(jnp.asarray(np.stack(
        [np.full((3,), float(10 * (r + 1)), np.float32)
         for r in range(size)]))))
    np.testing.assert_allclose(np.asarray(ga), ea, rtol=1e-5)
    np.testing.assert_allclose(np.asarray(gb), eb, rtol=1e-5)

    # reducescatter over dim 0
    rs = hvd.reducescatter(jnp.ones((size * 2, 3)) * (rank + 1),
                           op=hvd.Sum, name="rs")
    np.testing.assert_allclose(np.asarray(rs),
                               np.ones((2, 3)) * sum(r + 1 for r in range(size)))

    # join needs negotiation: must raise with a pointer to the core, not
    # silently pretend to work
    try:
        hvd.join()
        raise AssertionError("join must raise on the XLA eager backend")
    except NotImplementedError:
        pass

    hvd.barrier()
    hvd.shutdown()
    print(f"xla worker {rank}: OK", flush=True)


if __name__ == "__main__":
    main()
