"""Sharded-checkpoint worker (launched by test_core_multiprocess.py).

Exercises the REAL multi-process two-phase commit — no collectives, no
core: the commit barrier is the shared filesystem, exactly as on a TPU
pod with an NFS/GCS-fuse checkpoint dir.  Modes (``CKPT_MODE``):

* ``save``     — every rank writes only its shards for steps 10 and 11;
  rank 0 commits, the others poll until the commit is visible.
* ``crash``    — like ``save``, but ``CKPT_CRASH_RANK`` kill -9's
  ITSELF mid-write of step 11 (partial npz on disk, no marker): rank 0's
  commit must time out, step 10 must stay restorable, and GC must
  reclaim the wreckage (ISSUE 3 acceptance).
* ``restore``  — restore the latest checkpoint at the CURRENT world
  size (1 or 3, saved at 2) and verify the global arrays bit-for-bit;
  optionally re-save at ``CKPT_RESAVE_STEP`` from the new world.
"""

import os
import signal
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

os.environ["JAX_PLATFORMS"] = "cpu"
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=1")

import jax  # noqa: E402

import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from horovod_tpu.checkpoint import CheckpointError, ShardedCheckpointer  # noqa: E402
from horovod_tpu.checkpoint import format as fmt  # noqa: E402


def make_state(step):
    """Deterministic, rank-independent state (the replication contract):
    every leaf kind the store supports."""
    return {
        "params": {
            "w": jnp.arange(48.0).reshape(12, 4) + step,
            "b": jnp.linspace(0.0, 1.0, 7) * (step + 1),
            "h": jnp.full((5,), step, jnp.bfloat16),
        },
        "step": int(step),
        "name": f"run-{step}",
        "hist": [1, (2.0, step)],
    }


def check_state(out, step):
    expect = make_state(step)
    np.testing.assert_array_equal(out["params"]["w"],
                                  np.asarray(expect["params"]["w"]))
    np.testing.assert_array_equal(out["params"]["b"],
                                  np.asarray(expect["params"]["b"]))
    assert out["params"]["h"].dtype == jnp.bfloat16
    np.testing.assert_array_equal(
        out["params"]["h"].astype(np.float32),
        np.asarray(expect["params"]["h"], np.float32))
    assert out["step"] == step and type(out["step"]) is int
    assert out["name"] == f"run-{step}"
    assert isinstance(out["hist"][1], tuple) and out["hist"][1][1] == step


def poll_step(store, step, timeout=60):
    deadline = time.time() + timeout
    while time.time() < deadline:
        if store.latest_step() == step:
            return
        time.sleep(0.1)
    raise AssertionError(f"step {step} never committed; "
                         f"steps={store.all_steps()}")


def arm_crash(crash_step):
    """kill -9 OURSELVES mid-shard-write of ``crash_step``: a partial
    ``.npz.part`` lands on disk, the completion marker never does."""
    real = fmt.write_shard

    def sabotaged(dirpath, rank, arrays, entries, **kw):
        if dirpath.endswith(f"step_{crash_step}.tmp"):
            os.makedirs(dirpath, exist_ok=True)
            part = os.path.join(dirpath, fmt.shard_npz(rank) + ".part")
            with open(part, "wb") as f:
                f.write(b"\x93NUMPY partial garbage")
                f.flush()
                os.fsync(f.fileno())
            os.kill(os.getpid(), signal.SIGKILL)
        return real(dirpath, rank, arrays, entries, **kw)

    fmt.write_shard = sabotaged


def main():
    rank = int(os.environ["HOROVOD_RANK"])
    size = int(os.environ["HOROVOD_SIZE"])
    mode = os.environ["CKPT_MODE"]
    store = ShardedCheckpointer(os.environ["CKPT_DIR"])

    if mode in ("save", "crash"):
        crash_rank = int(os.environ.get("CKPT_CRASH_RANK", "-1"))
        store.save(10, make_state(10), wait=True)
        poll_step(store, 10)  # everyone sees the commit before step 11
        if mode == "crash" and rank == crash_rank:
            arm_crash(11)
        if mode == "crash" and rank == 0:
            # the peer dies mid-write: commit must fail loudly...
            try:
                store.save(11, make_state(11), wait=True)
            except CheckpointError as e:
                assert "timed out" in str(e), e
            else:
                raise AssertionError("commit succeeded without the peer")
            # ...the previous checkpoint is untouched and restorable...
            assert store.latest_step() == 10
            check_state(store.restore_latest(), 10)
            # ...and GC reclaims the wreckage once it goes idle
            time.sleep(1.0)
            store.gc(tmp_ttl=0.5)
            assert fmt.list_tmp_steps(os.environ["CKPT_DIR"]) == []
            assert store.latest_step() == 10
        else:
            store.save(11, make_state(11), wait=True)  # crash rank dies here
            poll_step(store, 11)
    elif mode == "restore":
        expect = int(os.environ["CKPT_EXPECT_STEP"])
        assert store.latest_step() == expect
        check_state(store.restore_latest(), expect)
        # the manifest remembers the world that WROTE it, not ours
        saved_world = fmt.read_manifest(os.environ["CKPT_DIR"],
                                        expect)["world_size"]
        assert saved_world == int(os.environ["CKPT_SAVED_WORLD"]), saved_world
        resave = os.environ.get("CKPT_RESAVE_STEP")
        if resave:
            store.save(int(resave), make_state(int(resave)), wait=True)
            poll_step(store, int(resave))
    else:
        raise SystemExit(f"unknown CKPT_MODE {mode!r}")

    store.close()
    print(f"checkpoint worker {rank}/{size} mode={mode}: OK", flush=True)


if __name__ == "__main__":
    main()
