"""Quantized eager allreduce over the native core (launched by
test_core_multiprocess.py): int8 payloads move over the TCP wire
(allgather-of-codes + local dequantize/reduce), numerics match the
locally recomputed expectation exactly, the EF-wrapped
DistributedOptimizer syncs in the eager regime, and the compression
metrics report > 3.5x for the int8 path (ISSUE 2 acceptance)."""

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
from horovod_tpu.compression import Compression, ErrorFeedback  # noqa: E402
from horovod_tpu.compression.metrics import compression_ratio  # noqa: E402


def _rank_tensor(r, n=4096, seed=0):
    return jnp.asarray(np.random.RandomState(seed + r).randn(n), jnp.float32)


def main():
    rank = int(os.environ["HOROVOD_RANK"])
    size = int(os.environ["HOROVOD_SIZE"])
    hvd.init()
    assert hvd.rank() == rank and hvd.size() == size
    q = Compression.int8

    # quantized allreduce: every rank can recompute the EXACT expectation
    # locally — sum over ranks of each contribution's quantize∘dequantize
    x = _rank_tensor(rank)
    out = hvd.quantized_allreduce(x, q, op=hvd.Sum, name="qsum")
    expect = sum(np.asarray(q.qdq(_rank_tensor(r))) for r in range(size))
    np.testing.assert_allclose(np.asarray(out), expect, rtol=1e-5,
                               atol=1e-5)
    # ...and the quantized sum tracks the true fp32 sum within the codec
    # bound (one quantization step per contribution)
    true = sum(np.asarray(_rank_tensor(r)) for r in range(size))
    assert np.abs(np.asarray(out) - true).max() <= \
        size * np.abs(true).max() / 254 + 1e-5

    # grouped: both leaves fuse into one negotiation cycle
    y = _rank_tensor(rank, seed=100)
    outs = hvd.quantized_grouped_allreduce([x, y], q, op=hvd.Average,
                                           name="qgrp")
    expect_y = sum(np.asarray(q.qdq(_rank_tensor(r, seed=100)))
                   for r in range(size)) / size
    np.testing.assert_allclose(np.asarray(outs[1]), expect_y, rtol=1e-5,
                               atol=1e-5)

    # EF-wrapped DistributedOptimizer in the EAGER regime: the wire moves
    # int8, every rank lands on the identical averaged update
    tx = hvd.DistributedOptimizer(optax.sgd(1.0),
                                  compression=ErrorFeedback(q))
    params = {"w": jnp.zeros(2048)}
    st = tx.init(params)
    g = {"w": _rank_tensor(rank, n=2048, seed=7)}
    updates, st = tx.update(g, st, params)
    expect_u = -sum(np.asarray(q.qdq(_rank_tensor(r, n=2048, seed=7)))
                    for r in range(size)) / size
    np.testing.assert_allclose(np.asarray(updates["w"]), expect_u,
                               rtol=1e-5, atol=1e-5)

    # ISSUE 6: bucketed eager path parity under int8+EF — the same tree
    # synced through many per-bucket async groups and through the single
    # grouped call must land on IDENTICAL values (per-leaf codec math is
    # order-independent), and the overlap metrics must be recorded.
    from horovod_tpu.common.config import reset_config

    def _ef_update(bucket_env):
        os.environ.update(bucket_env)
        reset_config()
        tx2 = hvd.DistributedOptimizer(optax.sgd(1.0),
                                       compression=ErrorFeedback(q))
        params2 = {f"l{i}": jnp.zeros(512) for i in range(6)}
        st2 = tx2.init(params2)
        g2 = {f"l{i}": _rank_tensor(rank, n=512, seed=20 + i)
              for i in range(6)}
        u2, _ = tx2.update(g2, st2, params2)
        return u2

    # 512 floats = 2 KiB/leaf, 4 KiB budget -> 3 buckets of 2 leaves
    u_bucketed = _ef_update({"HVD_TPU_BUCKET_BYTES": "4096"})
    reg = hvd.metrics_snapshot()["registry"]
    assert reg["hvd_overlap_bucket_count"]["value"] == 3, \
        reg.get("hvd_overlap_bucket_count")
    assert "hvd_overlap_exposed_comm_seconds" in reg, sorted(
        k for k in reg if "overlap" in k)
    u_single = _ef_update({"HVD_TPU_OVERLAP_BUCKETS": "0"})
    for k in u_single:
        np.testing.assert_array_equal(np.asarray(u_bucketed[k]),
                                      np.asarray(u_single[k]))
    os.environ.pop("HVD_TPU_BUCKET_BYTES")
    os.environ.pop("HVD_TPU_OVERLAP_BUCKETS")
    reset_config()

    # acceptance: the int8 path's cumulative pre/wire ratio on the
    # metrics registry (scraped by /metrics) exceeds 3.5x
    ratio = compression_ratio("int8")
    assert ratio > 3.5, ratio
    reg = hvd.metrics_snapshot()["registry"]
    key = 'hvd_compression_ratio{codec="int8"}'
    assert key in reg and reg[key]["value"] > 3.5, sorted(reg)

    hvd.shutdown()
    print(f"quantized worker {rank}: OK ratio={ratio:.2f}", flush=True)


if __name__ == "__main__":
    main()
