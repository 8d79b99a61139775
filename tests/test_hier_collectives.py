"""phier_allreduce parity battery (ISSUE 8 satellite): the hierarchical
intra-host reduce_scatter → inter-host allreduce → intra-host allgather
must match flat psum within fp tolerance on every tested virtual
topology of the 8-device CPU mesh — Sum and Average, with and without
the int8 codec on the inter-host hop (EQuARX error bound), and the
small-bucket latency floor path must match the dense reduction."""

import functools

import numpy as np
import pytest
import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from horovod_tpu._compat import shard_map
from horovod_tpu.common.topology import MeshTopology
from horovod_tpu.compression.quantizers import BlockInt8Quantizer
from horovod_tpu.ops import mesh_collectives as mc
from horovod_tpu.ops.reduce_op import ReduceOp
from horovod_tpu.parallel import build_mesh
from horovod_tpu.train.overlap import bucketed_grad_sync

TOPOLOGIES = [MeshTopology(2, 4), MeshTopology(4, 2), MeshTopology(8, 1)]


@pytest.fixture(scope="module")
def mesh():
    return build_mesh(dp=-1)


def _run_hier(mesh, x, topo, op, codec=None, floor=None):
    @functools.partial(shard_map, mesh=mesh, in_specs=(P("dp"),),
                       out_specs=P("dp"), check_vma=False)
    def body(s):
        out = mc.phier_allreduce(s[0], "dp", topo, op,
                                 inter_codec=codec, small_floor=floor)
        return out[None]

    return np.asarray(jax.jit(body)(jnp.asarray(x)))


def _flat_ref(x, op):
    red = np.sum if op == ReduceOp.SUM else np.mean
    return red(np.asarray(x, np.float64), axis=0,
               keepdims=True).repeat(x.shape[0], 0)


@pytest.mark.parametrize("topo", TOPOLOGIES,
                         ids=["2x4", "4x2", "8x1"])
@pytest.mark.parametrize("op", [ReduceOp.SUM, ReduceOp.AVERAGE],
                         ids=["sum", "avg"])
def test_hier_matches_flat_psum(mesh, topo, op):
    # 37 elements: not divisible by local/world — exercises the padding
    x = np.random.RandomState(0).randn(8, 37).astype(np.float32)
    out = _run_hier(mesh, x, topo, op)
    np.testing.assert_allclose(out, _flat_ref(x, op), atol=1e-4)


@pytest.mark.parametrize("topo", TOPOLOGIES,
                         ids=["2x4", "4x2", "8x1"])
@pytest.mark.parametrize("op", [ReduceOp.SUM, ReduceOp.AVERAGE],
                         ids=["sum", "avg"])
def test_hier_quantized_inter_hop_within_codec_bound(mesh, topo, op):
    x = np.random.RandomState(1).randn(8, 64).astype(np.float32)
    out = _run_hier(mesh, x, topo, op, codec=BlockInt8Quantizer())
    ref = _flat_ref(x, op)
    # one quantization step on the already-reduced inter-host payload:
    # |err| <= absmax/254 per block (the int8 codec's bound) —
    # absmax bounded by the reduced tensor's max
    bound = np.abs(ref).max() / 254 + 1e-6
    assert np.abs(out - ref).max() <= bound


def test_hier_2d_tensor_and_dtype_preserved(mesh):
    x = np.random.RandomState(2).randn(8, 6, 10).astype(np.float32)
    topo = MeshTopology(2, 4)
    out = _run_hier(mesh, x, topo, ReduceOp.AVERAGE)
    assert out.shape == x.shape and out.dtype == np.float32
    np.testing.assert_allclose(out, _flat_ref(x, ReduceOp.AVERAGE),
                               atol=1e-4)


def test_small_floor_takes_dense_path_exactly(mesh):
    """Below the byte floor the hierarchical (and quantized) machinery
    is skipped entirely: the result must be BIT-comparable to flat psum
    — same collective, not merely within codec tolerance."""
    x = np.random.RandomState(3).randn(8, 16).astype(np.float32)
    topo = MeshTopology(2, 4)
    dense = _run_hier(mesh, x, topo, ReduceOp.SUM, floor=None)

    @functools.partial(shard_map, mesh=mesh, in_specs=(P("dp"),),
                       out_specs=P("dp"), check_vma=False)
    def flat(s):
        return mc.preduce(s[0], "dp", ReduceOp.SUM)[None]

    floored = _run_hier(mesh, x, topo, ReduceOp.SUM,
                        codec=BlockInt8Quantizer(), floor=1 << 30)
    ref = np.asarray(jax.jit(flat)(jnp.asarray(x)))
    np.testing.assert_array_equal(floored, ref)
    # and the unfloored hierarchy still agrees within fp tolerance
    np.testing.assert_allclose(dense, ref, atol=1e-4)


def test_topology_mismatch_raises(mesh):
    x = jnp.zeros((8, 4))
    with pytest.raises(Exception, match="does not cover"):
        _run_hier(mesh, np.asarray(x), MeshTopology(2, 2), ReduceOp.SUM)


def test_unsupported_op_raises(mesh):
    with pytest.raises(Exception, match="Sum/Average"):
        _run_hier(mesh, np.zeros((8, 4), np.float32), MeshTopology(2, 4),
                  ReduceOp.MIN)


# -- bucketed_grad_sync wiring (the PR-6 planner seam) ----------------------

def _sync(mesh, g, **kw):
    @functools.partial(shard_map, mesh=mesh, in_specs=(P("dp"),),
                       out_specs=P("dp"), check_vma=False)
    def body(gs):
        loc = jax.tree_util.tree_map(lambda x: x[0], gs)
        out = bucketed_grad_sync(loc, "dp", **kw)
        return jax.tree_util.tree_map(lambda x: x[None], out)

    return jax.jit(body)(g)


def _tree(rng):
    return {"w": jnp.asarray(rng.randn(8, 16, 3).astype(np.float32)),
            "b": jnp.asarray(rng.randn(8, 5).astype(np.float32))}


@pytest.mark.parametrize("topo", TOPOLOGIES[:2], ids=["2x4", "4x2"])
def test_bucketed_sync_hier_matches_dense(mesh, topo):
    g = _tree(np.random.RandomState(4))
    out = _sync(mesh, g, algorithm="hier", topology=topo,
                bucket_bytes=128)
    for got, want in zip(jax.tree_util.tree_leaves(out),
                         jax.tree_util.tree_leaves(g)):
        ref = np.mean(np.asarray(want), axis=0, keepdims=True).repeat(8, 0)
        np.testing.assert_allclose(np.asarray(got), ref, atol=1e-5)


def test_bucketed_sync_hier_quantized_inter_hop(mesh):
    g = _tree(np.random.RandomState(5))
    out = _sync(mesh, g, algorithm="hier", topology=MeshTopology(2, 4),
                compression=BlockInt8Quantizer())
    # the bucket packs all leaves into one vector, so a quantizer block
    # can span leaves: the codec bound is governed by the PACKED
    # vector's absmax, not each leaf's own
    packed_max = max(np.abs(np.mean(np.asarray(l), axis=0)).max()
                     for l in jax.tree_util.tree_leaves(g))
    bound = packed_max / 254 + 1e-6
    for got, want in zip(jax.tree_util.tree_leaves(out),
                         jax.tree_util.tree_leaves(g)):
        ref = np.mean(np.asarray(want), axis=0, keepdims=True).repeat(8, 0)
        assert np.abs(np.asarray(got) - ref).max() <= bound


def test_bucketed_sync_small_floor_skips_codec(mesh):
    """Buckets under the floor move dense even when a codec is set:
    result equals the exact mean, not merely within the codec bound."""
    g = _tree(np.random.RandomState(6))
    out = _sync(mesh, g, compression=BlockInt8Quantizer(),
                small_floor=1 << 30)
    for got, want in zip(jax.tree_util.tree_leaves(out),
                         jax.tree_util.tree_leaves(g)):
        ref = np.mean(np.asarray(want), axis=0, keepdims=True).repeat(8, 0)
        np.testing.assert_allclose(np.asarray(got), ref, atol=1e-6,
                                   rtol=1e-6)


def test_bucketed_sync_ring_with_codec_raises(mesh):
    g = _tree(np.random.RandomState(7))
    with pytest.raises(ValueError, match="no compression seam"):
        _sync(mesh, g, algorithm="ring",
              compression=BlockInt8Quantizer())


def test_bucketed_sync_flat_topology_degrades_to_psum(mesh):
    g = _tree(np.random.RandomState(8))
    out = _sync(mesh, g, algorithm="hier")  # detect: 1x8 on one process
    for got, want in zip(jax.tree_util.tree_leaves(out),
                         jax.tree_util.tree_leaves(g)):
        ref = np.mean(np.asarray(want), axis=0, keepdims=True).repeat(8, 0)
        np.testing.assert_allclose(np.asarray(got), ref, atol=1e-5)
