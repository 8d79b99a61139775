"""Bucketed backprop/collective overlap (ISSUE 6 tentpole): numerics
parity of the software-pipelined accumulation against the unbucketed
reduce-after-backward path on the traced mesh regime, the chunked ring
collective, and loss-trajectory parity under int8 compression."""

import functools

import numpy as np
import pytest
import jax
import jax.numpy as jnp
import optax
from jax.sharding import PartitionSpec as P

import horovod_tpu as hvd_mod
from horovod_tpu._compat import shard_map
from horovod_tpu.ops.mesh_collectives import pring_allreduce
from horovod_tpu.ops.reduce_op import ReduceOp
from horovod_tpu.train.overlap import (bucketed_grad_sync,
                                       make_overlap_train_step,
                                       pipelined_accumulate)



@pytest.fixture
def dp_mesh(hvd):
    return hvd.build_mesh(dp=-1)  # all 8 virtual devices on one axis


def _grad_tree(rng):
    return {"w": jnp.asarray(rng.randn(8, 16, 3).astype(np.float32)),
            "b": jnp.asarray(rng.randn(8, 5).astype(np.float32))}


def _run_sync(mesh, g, **kw):
    @functools.partial(shard_map, mesh=mesh, in_specs=(P("dp"),),
                       out_specs=P("dp"), check_vma=False)
    def body(gs):
        loc = jax.tree_util.tree_map(lambda x: x[0], gs)
        out = bucketed_grad_sync(loc, "dp", **kw)
        return jax.tree_util.tree_map(lambda x: x[None], out)

    return jax.jit(body)(g)


@pytest.mark.parametrize("kw", [
    {},                                  # single psum bucket
    {"bucket_bytes": 64},                # many buckets
    {"ring": True},                      # chunked ppermute ring
    {"op": ReduceOp.SUM, "bucket_bytes": 128},
], ids=["one-bucket", "many-buckets", "ring", "sum"])
def test_bucketed_sync_matches_dense_reduction(hvd, dp_mesh, kw):
    rng = np.random.RandomState(0)
    g = _grad_tree(rng)
    out = _run_sync(dp_mesh, g, **kw)
    red = np.sum if kw.get("op") == ReduceOp.SUM else np.mean
    for got, want in zip(jax.tree_util.tree_leaves(out),
                         jax.tree_util.tree_leaves(g)):
        ref = red(np.asarray(want), axis=0, keepdims=True).repeat(8, 0)
        np.testing.assert_allclose(np.asarray(got), ref, atol=1e-5)


def test_bucketed_sync_quantized_within_codec_bound(hvd, dp_mesh):
    rng = np.random.RandomState(1)
    g = _grad_tree(rng)
    out = _run_sync(dp_mesh, g, compression=hvd.Compression.int8,
                    bucket_bytes=256)
    for got, want in zip(jax.tree_util.tree_leaves(out),
                         jax.tree_util.tree_leaves(g)):
        ref = np.mean(np.asarray(want), axis=0, keepdims=True).repeat(8, 0)
        # one quantization step of error on the gathered phase
        bound = np.abs(ref).max() / 254 + 1e-6
        assert np.abs(np.asarray(got) - ref).max() <= bound


def test_ring_allreduce_matches_psum_any_shape(hvd, dp_mesh):
    rng = np.random.RandomState(2)
    for shape in [(8, 13), (8, 4, 5), (8, 1)]:
        x = jnp.asarray(rng.randn(*shape).astype(np.float32))

        @functools.partial(shard_map, mesh=dp_mesh, in_specs=(P("dp"),),
                           out_specs=P("dp"), check_vma=False)
        def body(xs):
            return pring_allreduce(xs[0], "dp")[None]

        out = jax.jit(body)(x)
        ref = np.sum(np.asarray(x), axis=0, keepdims=True).repeat(8, 0)
        np.testing.assert_allclose(np.asarray(out), ref, rtol=2e-5,
                                   atol=1e-5)


# -- pipelined accumulation parity -----------------------------------------

def _linear_problem(rng, n=64, din=6, dout=4):
    params = {"w": jnp.asarray(rng.randn(din, dout).astype(np.float32)),
              "b": jnp.zeros((dout,), jnp.float32)}
    X = jnp.asarray(rng.randn(n, din).astype(np.float32))
    Y = jnp.asarray(rng.randn(n, dout).astype(np.float32))

    def loss_fn(p, batch):
        x, y = batch
        return jnp.mean((x @ p["w"] + p["b"] - y) ** 2)

    return params, (X, Y), loss_fn


def _accumulate(mesh, params, batch, loss_fn, n_micro, **kw):
    gf = jax.value_and_grad(loss_fn)

    @functools.partial(shard_map, mesh=mesh,
                       in_specs=(P(), P("dp"), P("dp")),
                       out_specs=(P(), P()), check_vma=False)
    def body(p, x, y):
        mb = jax.tree_util.tree_map(
            lambda a: a.reshape((n_micro, a.shape[0] // n_micro)
                                + a.shape[1:]), (x, y))
        loss, g = pipelined_accumulate(gf, p, mb, axis_name="dp", **kw)
        return jax.lax.pmean(loss, "dp"), g

    return jax.jit(body)(params, *batch)


@pytest.mark.parametrize("kw", [
    {"n_micro": 1},                          # exact fallback, no pipeline
    {"n_micro": 4},                          # pipelined
    {"n_micro": 4, "overlap": False},        # serialized comparator
    {"n_micro": 4, "bucket_bytes": 32},      # many buckets
    {"n_micro": 2, "ring": True},            # ring collective
], ids=["fallback", "pipelined", "serialized", "buckets", "ring"])
def test_pipelined_accumulate_matches_full_batch(hvd, dp_mesh, kw):
    """Bucketed/pipelined == unbucketed single-shot to fp32 tolerance:
    reduction is linear, so reducing each microbatch one iteration late
    and summing must equal reducing the full-batch gradient."""
    kw = dict(kw)
    n_micro = kw.pop("n_micro")
    rng = np.random.RandomState(0)
    params, batch, loss_fn = _linear_problem(rng)
    ref_loss, ref_g = jax.value_and_grad(loss_fn)(params, batch)
    loss, g = _accumulate(dp_mesh, params, batch, loss_fn, n_micro, **kw)
    assert abs(float(loss) - float(ref_loss)) < 1e-5
    for got, want in zip(jax.tree_util.tree_leaves(g),
                         jax.tree_util.tree_leaves(ref_g)):
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-4, atol=1e-5)


def test_pipelined_rejects_mismatched_microbatch_axes(hvd, dp_mesh):
    rng = np.random.RandomState(0)
    params, (X, Y), loss_fn = _linear_problem(rng)
    gf = jax.value_and_grad(loss_fn)
    with pytest.raises(ValueError, match="leading axis"):
        pipelined_accumulate(
            gf, params, (X.reshape(4, 16, 6), Y.reshape(2, 32, 4)),
            axis_name="dp")


def test_loss_trajectory_parity_bucketed_vs_unbucketed(hvd, dp_mesh):
    """Acceptance: bucketed (pipelined, quantized) training matches
    unbucketed loss trajectories within tolerance — exact under plain
    psum, codec-bounded under int8."""
    rng = np.random.RandomState(3)
    params, batch, loss_fn = _linear_problem(rng, n=64)
    tx = optax.sgd(0.05)

    def train(**kw):
        step = make_overlap_train_step(loss_fn, tx, dp_mesh, "dp",
                                       donate=False, **kw)
        p, o = dict(params), tx.init(params)
        losses = []
        for _ in range(6):
            p, o, loss = step(p, o, batch)
            losses.append(float(loss))
        return np.asarray(losses)

    base = train(n_micro=1)                       # unbucketed, serialized
    pipelined = train(n_micro=4, bucket_bytes=64)  # bucketed + pipelined
    quantized = train(n_micro=4, bucket_bytes=64,
                      compression=hvd_mod.Compression.int8)
    np.testing.assert_allclose(pipelined, base, rtol=2e-2)
    np.testing.assert_allclose(quantized, base, rtol=5e-2)
    assert quantized[-1] < quantized[0]  # it actually trains
