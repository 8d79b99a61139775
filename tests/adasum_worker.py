"""Worker validating the C++ VHDD Adasum against the Python tree oracle
(reference analog: test/parallel/test_adasum_*.py numeric checks)."""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
os.environ["JAX_PLATFORMS"] = "cpu"

import numpy as np  # noqa: E402

from horovod_tpu.core.core_backend import CoreBackend  # noqa: E402
from horovod_tpu.ops.reduce_op import ReduceOp  # noqa: E402


def main():
    be = CoreBackend()
    rank, size = be.rank, be.size

    # 1) identical inputs: Adasum(a, a, ...) == a (idempotent)
    a = np.linspace(1, 2, 32).astype(np.float32)
    out = be.allreduce_async("ad.same", a.copy(), ReduceOp.ADASUM).wait(60)
    np.testing.assert_allclose(out, a, rtol=1e-5)

    # 2) orthogonal inputs: Adasum == plain sum
    x = np.zeros(size * 4, np.float32)
    x[rank * 4:(rank + 1) * 4] = rank + 1.0
    out = be.allreduce_async("ad.orth", x, ReduceOp.ADASUM).wait(60)
    expect = np.concatenate([np.full(4, r + 1.0) for r in range(size)])
    np.testing.assert_allclose(out, expect, rtol=1e-5)

    # 3) random inputs: match the Python binary-tree oracle
    import jax
    import jax.numpy as jnp
    from horovod_tpu.ops.adasum import adasum_combine, adasum_tree_reduce

    def vhdd_oracle(contribs):
        """Mirror the C++ structure: fold extras onto partners, then the
        power-of-two binary tree (for pow2 sizes this IS the plain tree)."""
        p = len(contribs)
        pow2 = 1
        while pow2 * 2 <= p:
            pow2 *= 2
        folded = []
        for i in range(pow2):
            c = jnp.asarray(contribs[i])
            if i < p - pow2:
                c = adasum_combine(c, jnp.asarray(contribs[i + pow2]))
            folded.append(c)
        return np.asarray(adasum_tree_reduce(jnp.stack(folded)))

    rng = np.random.RandomState(7)
    all_contribs = rng.randn(size, 64).astype(np.float32)
    mine = all_contribs[rank].copy()
    out = be.allreduce_async("ad.rand", mine, ReduceOp.ADASUM).wait(60)
    oracle = vhdd_oracle(all_contribs)
    np.testing.assert_allclose(out, oracle, rtol=1e-4, atol=1e-5)

    # 4) float64 path
    out = be.allreduce_async("ad.f64", all_contribs[rank].astype(np.float64),
                             ReduceOp.ADASUM).wait(60)
    np.testing.assert_allclose(out, oracle, rtol=1e-4, atol=1e-5)

    be.shutdown()
    print(f"adasum worker {rank}: OK", flush=True)


if __name__ == "__main__":
    main()
