"""Worker stressing concurrent disjoint process sets (reference analog:
test/parallel/test_process_sets_*): sets {0,1} and {2,3} run independent
collectives at the same time over their own coordination domains.

Backend-agnostic: uses the public API so the same script validates the TCP
core (default) and the XLA data plane (HOROVOD_TPU_OPERATIONS=XLA_EAGER).
"""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

os.environ["JAX_PLATFORMS"] = "cpu"

import jax  # noqa: E402

import numpy as np  # noqa: E402

import horovod_tpu as hvd  # noqa: E402


def main():
    rank = int(os.environ["HOROVOD_RANK"])
    size = int(os.environ["HOROVOD_SIZE"])
    hvd.init()
    assert hvd.rank() == rank and hvd.size() == size == 4
    # Regression knob for the registration race (r2): one rank registers
    # seconds after the others; inactive-until-consensus must absorb the
    # skew instead of deadlocking the domain-0 lockstep.
    if rank == int(os.environ.get("HVD_TEST_REG_DELAY_RANK", "-1")):
        import time
        time.sleep(float(os.environ.get("HVD_TEST_REG_DELAY_SECS", "2")))
    # all ranks register both sets in the same order (ids stay aligned)
    low = hvd.add_process_set([0, 1])
    high = hvd.add_process_set([2, 3])
    mine = low if rank < 2 else high
    peer_base = 0 if rank < 2 else 2

    # each set allreduces its own tensors concurrently with the other set
    for it in range(10):
        x = np.full((64,), float(rank + 1), np.float32)
        out = hvd.allreduce(x, op=hvd.Sum, name=f"ps.{it}",
                            process_set=mine)
        expect = (peer_base + 1.0) + (peer_base + 2.0)
        np.testing.assert_allclose(np.asarray(out), expect)
        # interleave a global-set op to stress cross-domain cycles
        g = hvd.allreduce(np.ones(8, np.float32), op=hvd.Sum,
                          name=f"glob.{it}")
        np.testing.assert_allclose(np.asarray(g), 4.0)

    # grouped (fused) allreduce within the subset
    outs = hvd.grouped_allreduce(
        [np.full(5, float(rank), np.float32),
         np.full((2, 3), 1.0, np.float32)],
        op=hvd.Sum, name="ps.grp", process_set=mine)
    np.testing.assert_allclose(np.asarray(outs[0]),
                               float(peer_base) + peer_base + 1.0)
    np.testing.assert_allclose(np.asarray(outs[1]), 2.0)

    # ragged allgather within the subset
    set_rank = mine.rank()
    rows = set_rank + 1
    out = hvd.allgather(np.full((rows, 2), float(rank), np.float32),
                        name="ps.ag", process_set=mine)
    assert np.asarray(out).shape[0] == 3  # 1 + 2 rows

    # broadcast with a GLOBAL root rank (reference semantics)
    root = peer_base + 1
    b = hvd.broadcast(np.full(3, float(rank), np.float32),
                      root_rank=root, name="ps.bc", process_set=mine)
    np.testing.assert_allclose(np.asarray(b), float(root))

    # per-set join must reject on the same-order XLA data plane (the
    # subset backend shares the global backend's no-negotiation limit)
    if os.environ.get("HOROVOD_TPU_OPERATIONS", "").upper() == "XLA_EAGER":
        from horovod_tpu.ops.collectives import _backend_for
        try:
            _backend_for(mine).join()
            raise AssertionError("subset join must raise on XLA eager")
        except NotImplementedError:
            pass

    hvd.barrier()
    hvd.shutdown()
    print(f"psets worker {rank}: OK", flush=True)


if __name__ == "__main__":
    main()
