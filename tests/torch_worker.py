"""Multi-process torch drop-in worker (reference analog: the torch cases
of test/parallel/test_torch.py under horovodrun): eager collectives,
sparse allreduce, and DistributedOptimizer equivalence to single-process
full-batch training."""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

os.environ["JAX_PLATFORMS"] = "cpu"

import jax  # noqa: E402

import numpy as np  # noqa: E402
import torch  # noqa: E402

import horovod_tpu.torch as hvd  # noqa: E402


def main():
    rank = int(os.environ["HOROVOD_RANK"])
    size = int(os.environ["HOROVOD_SIZE"])
    hvd.init()
    assert hvd.rank() == rank and hvd.size() == size

    # dense allreduce
    out = hvd.allreduce(torch.arange(6, dtype=torch.float32) + rank,
                        op=hvd.Sum, name="d")
    expect = sum(torch.arange(6, dtype=torch.float32) + r
                 for r in range(size))
    assert torch.allclose(out, expect), (out, expect)

    # sparse allreduce: overlapping + disjoint coordinates across ranks
    i = torch.tensor([[0, rank + 1], [0, 0]])
    v = torch.tensor([1.0, 2.0])
    sp = torch.sparse_coo_tensor(i, v, (size + 2, 2))
    handle = hvd.sparse_allreduce_async(sp, name="sp", op=hvd.Sum)
    dense = hvd.synchronize(handle).to_dense()
    expect = torch.zeros(size + 2, 2)
    expect[0, 0] = float(size)          # every rank contributed 1.0 there
    for r in range(size):
        expect[r + 1, 0] += 2.0         # each rank's private coordinate
    assert torch.allclose(dense, expect), (dense, expect)

    # allgather_object (reference: torch/functions.py:233-266)
    metas = hvd.allgather_object({"rank": rank, "loss": 0.5 * rank})
    assert [m["rank"] for m in metas] == list(range(size))

    # in-place async variants (reference: torch/mpi_ops.py allreduce_async_
    # / broadcast_async_ / grouped_allreduce family): the handle's
    # synchronize writes back into the argument tensors
    t = torch.full((3,), float(rank + 1))
    out = hvd.synchronize(hvd.allreduce_async_(t, op=hvd.Sum, name="ip"))
    assert out is t
    expect_sum = float(sum(r + 1 for r in range(size)))
    assert torch.allclose(t, torch.full((3,), expect_sum)), t

    b = torch.full((2,), float(rank))
    hvd.synchronize(hvd.broadcast_async_(b, root_rank=0, name="ipb"))
    assert torch.allclose(b, torch.zeros(2)), b

    g1, g2 = torch.full((2,), float(rank)), torch.full((4,), 2.0 * rank)
    outs = hvd.grouped_allreduce([g1, g2], op=hvd.Average, name="ga")
    mean_r = float(sum(range(size))) / size
    assert torch.allclose(outs[0], torch.full((2,), mean_r))
    assert torch.allclose(outs[1], torch.full((4,), 2 * mean_r))
    hvd.synchronize(hvd.grouped_allreduce_async_(
        [g1, g2], op=hvd.Average, name="ga_"))
    assert torch.allclose(g1, torch.full((2,), mean_r)), g1
    hvd.grouped_allreduce_([g2], op=hvd.Average, name="ga2_")
    # g2 was already reduced in place once, so averaging the averages is
    # idempotent across equal ranks' values
    assert torch.allclose(g2, torch.full((4,), 2 * mean_r)), g2

    # async alltoall returns (tensor, recv_splits) from wait
    a2a = torch.arange(size, dtype=torch.float32) + rank * 10
    at, asplits = hvd.synchronize(
        hvd.alltoall_async(a2a, splits=[1] * size, name="a2a"))
    assert at.shape[0] == size and list(asplits) == [1] * size
    assert float(at[0]) == float(rank)  # rank 0's slot r element

    # DistributedOptimizer: equal shards => identical to full-batch SGD
    torch.manual_seed(0)
    model = hvd.broadcast_object(torch.nn.Linear(4, 1), 0, name="m")
    ref = torch.nn.Linear(4, 1)
    ref.load_state_dict(model.state_dict())
    rng = np.random.RandomState(0)
    X = torch.from_numpy(rng.randn(8 * size, 4).astype(np.float32))
    Y = torch.from_numpy(rng.randn(8 * size, 1).astype(np.float32))
    mine = slice(rank * 8, (rank + 1) * 8)
    opt = hvd.DistributedOptimizer(
        torch.optim.SGD(model.parameters(), lr=0.1),
        named_parameters=model.named_parameters())
    ref_opt = torch.optim.SGD(ref.parameters(), lr=0.1)
    for step in range(5):
        opt.zero_grad()
        torch.nn.functional.mse_loss(model(X[mine]), Y[mine]).backward()
        opt.step()
        ref_opt.zero_grad()
        torch.nn.functional.mse_loss(ref(X), Y).backward()
        ref_opt.step()
    for a, b in zip(model.parameters(), ref.parameters()):
        assert torch.allclose(a, b, atol=1e-5), (a, b)

    # hook mode: each param's allreduce is enqueued DURING .backward()
    # (post-accumulate-grad hook), so handles are already in flight when
    # backward returns; step() drains them (reference: grad-accumulator
    # hooks, torch/optimizer.py:128-171)
    hX = torch.from_numpy(rng.randn(8, 4).astype(np.float32))
    hY = torch.from_numpy(rng.randn(8, 1).astype(np.float32))
    hmodel = hvd.broadcast_object(torch.nn.Linear(4, 1), 0, name="hm")
    hopt = hvd.DistributedOptimizer(
        torch.optim.SGD(hmodel.parameters(), lr=0.1),
        named_parameters=hmodel.named_parameters())
    assert hopt._use_hooks
    hopt.zero_grad()
    torch.nn.functional.mse_loss(hmodel(hX), hY).backward()
    if size > 1:
        assert len(hopt._handles) == 2, hopt._handles  # weight + bias
    hopt.step()
    assert not hopt._handles

    # backward_passes_per_step=2 under hooks: the first backward only
    # counts down; the SECOND enqueues — and the result equals one
    # full-batch step on the summed gradient scaled by 1/2
    amodel = hvd.broadcast_object(torch.nn.Linear(4, 1), 0, name="am")
    aref = torch.nn.Linear(4, 1)
    aref.load_state_dict(amodel.state_dict())
    aopt = hvd.DistributedOptimizer(
        torch.optim.SGD(amodel.parameters(), lr=0.1),
        named_parameters=amodel.named_parameters(),
        backward_passes_per_step=2)
    aopt.zero_grad()
    torch.nn.functional.mse_loss(amodel(hX[:4]), hY[:4]).backward()
    assert not aopt._handles  # countdown, nothing in flight yet
    torch.nn.functional.mse_loss(amodel(hX[4:]), hY[4:]).backward()
    if size > 1:
        assert len(aopt._handles) == 2
    aopt.step()
    aref_opt = torch.optim.SGD(aref.parameters(), lr=0.1)
    torch.nn.functional.mse_loss(aref(hX[:4]), hY[:4]).backward()
    torch.nn.functional.mse_loss(aref(hX[4:]), hY[4:]).backward()
    for p in aref.parameters():
        p.grad.div_(2.0)  # same shard on every rank -> avg == local
    aref_opt.step()
    for a, b in zip(amodel.parameters(), aref.parameters()):
        assert torch.allclose(a, b, atol=1e-5), (a, b)

    # more backwards than backward_passes_per_step raises like the
    # reference (a re-enqueue would collide with the in-flight op)
    aopt.zero_grad()
    torch.nn.functional.mse_loss(amodel(hX[:4]), hY[:4]).backward()
    torch.nn.functional.mse_loss(amodel(hX[4:]), hY[4:]).backward()
    try:
        torch.nn.functional.mse_loss(amodel(hX[:4]), hY[:4]).backward()
        raise AssertionError("expected over-backward error")
    except (ValueError, RuntimeError) as e:
        assert "backward_passes_per_step" in str(e), e
    aopt.synchronize()  # drain the legal in-flight enqueues

    # fallback (HVD_TORCH_HOOKS=0): per-tensor sync in step(), same numerics
    os.environ["HVD_TORCH_HOOKS"] = "0"
    try:
        fmodel = hvd.broadcast_object(torch.nn.Linear(4, 1), 0, name="fm")
        fopt = hvd.DistributedOptimizer(
            torch.optim.SGD(fmodel.parameters(), lr=0.1),
            named_parameters=fmodel.named_parameters())
        assert not fopt._use_hooks
        fopt.zero_grad()
        torch.nn.functional.mse_loss(fmodel(hX), hY).backward()
        assert not fopt._handles  # nothing enqueued during backward
        fopt.step()
    finally:
        del os.environ["HVD_TORCH_HOOKS"]

    # SyncBatchNorm: sharded batch must match plain BN on the full batch
    # for output, input grad, affine grads (after averaging), and running
    # stats (reference: torch/sync_batch_norm.py numerics)
    torch.manual_seed(1)
    X = torch.from_numpy(rng.randn(4 * size, 3, 5, 5).astype(np.float32))
    mine = slice(rank * 4, (rank + 1) * 4)
    sbn = hvd.SyncBatchNorm(3, momentum=0.1)
    bn = torch.nn.BatchNorm2d(3, momentum=0.1)
    bn.load_state_dict({k: v.clone() for k, v in sbn.state_dict().items()})
    xs = X[mine].clone().requires_grad_(True)
    xf = X.clone().requires_grad_(True)
    out_s = sbn(xs)
    out_f = bn(xf)
    assert torch.allclose(out_s, out_f[mine], atol=1e-5)
    out_s.sum().backward()
    out_f.sum().backward()
    assert torch.allclose(xs.grad, xf.grad[mine], atol=1e-5)
    # affine grads are LOCAL sums; averaging across ranks then scaling by
    # size reproduces the full-batch sums (sum-over-shards contract)
    gw = hvd.allreduce(sbn.weight.grad, op=hvd.Sum, name="sbn.gw")
    gb = hvd.allreduce(sbn.bias.grad, op=hvd.Sum, name="sbn.gb")
    assert torch.allclose(gw, bn.weight.grad, atol=1e-4), (gw, bn.weight.grad)
    assert torch.allclose(gb, bn.bias.grad, atol=1e-4)
    assert torch.allclose(sbn.running_mean, bn.running_mean, atol=1e-5)
    assert torch.allclose(sbn.running_var, bn.running_var, atol=1e-5)

    # join with genuinely uneven batches (reference:
    # test/parallel/test_torch.py join tests; controller.cc:94-98,262-265):
    # rank r trains on r+1 batches, calling hvd.join() when it runs out —
    # later ranks keep allreducing gradients while joined ranks contribute
    # nothing, then everyone agrees on the last rank to join
    if size >= 2:
        jmodel = hvd.broadcast_object(torch.nn.Linear(4, 1), 0, name="jm")
        jopt = hvd.DistributedOptimizer(
            torch.optim.SGD(jmodel.parameters(), lr=0.05),
            named_parameters=jmodel.named_parameters())
        for b in range(rank + 1):  # uneven: rank r has r+1 batches
            jopt.zero_grad()
            xb = torch.from_numpy(
                rng.randn(4, 4).astype(np.float32))
            yb = torch.from_numpy(rng.randn(4, 1).astype(np.float32))
            torch.nn.functional.mse_loss(jmodel(xb), yb).backward()
            jopt.step()
        last = hvd.join()
        # every rank agrees on who joined last (it holds the most-trained
        # parameters), and the standard post-join broadcast from that rank
        # leaves the whole world with identical parameters
        lasts = hvd.allgather_object(last)
        assert len(set(lasts)) == 1, lasts
        hvd.broadcast_parameters(jmodel.state_dict(), root_rank=lasts[0])
        ws = hvd.allgather_object(
            [p.detach().numpy() for p in jmodel.parameters()])
        for other in ws[1:]:
            for a, b in zip(ws[0], other):
                assert np.allclose(a, b, atol=1e-6)

    hvd.barrier()
    hvd.shutdown()
    print(f"torch worker {rank}: OK", flush=True)


if __name__ == "__main__":
    main()
