"""Numerical tests for ring attention, Ulysses SP, MoE-EP and pipeline-PP
against single-device oracles (the TPU analog of the reference's
test/parallel numeric-equality suite)."""

import functools

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from horovod_tpu.parallel import build_mesh
from horovod_tpu.parallel.ring_attention import (ring_attention,
                                                 _plain_attention)
from horovod_tpu.parallel.ulysses import ulysses_attention
from horovod_tpu.parallel.moe import grouped_matmul, moe_layer
from horovod_tpu.parallel.pipeline import (pipeline_apply, stage_stacked)


def _qkv(B=2, S=16, H=4, D=8, seed=0):
    rng = np.random.RandomState(seed)
    mk = lambda: jnp.asarray(rng.randn(B, S, H, D), jnp.float32) * 0.5
    return mk(), mk(), mk()


@pytest.mark.parametrize("causal", [True, False])
def test_ring_attention_matches_full(causal):
    mesh = build_mesh(dp=2, sp=4)
    q, k, v = _qkv()
    ref = _plain_attention(q, k, v, causal=causal)
    out = ring_attention(q, k, v, mesh, axis_name="sp", causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("causal", [True, False])
def test_ring_attention_flash_path_matches_full(causal):
    """The Pallas-kernel ring path (per-step flash + logaddexp merge of
    normalized (o, lse) partials) must agree with the full oracle —
    interpret mode stands in for the TPU kernel on the CPU mesh (2-device
    sub-mesh: flash blocks need S/sp >= 256, too big for an 8-way ring on
    the tiny test shapes)."""
    mesh = build_mesh(dp=1, sp=2, devices=jax.devices()[:2])
    rng = np.random.RandomState(3)
    mk = lambda: jnp.asarray(rng.randn(1, 512, 2, 128), jnp.float32) * 0.3
    q, k, v = mk(), mk(), mk()
    ref = _plain_attention(q, k, v, causal=causal)
    out = ring_attention(q, k, v, mesh, axis_name="sp", causal=causal,
                         use_flash=True, interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)


def test_ring_attention_flash_path_grads():
    """Training goes through the ring: the flash ring path's gradients
    (custom-VJP kernel + lse merge + ppermute loop) must match autodiff
    through the oracle."""
    mesh = build_mesh(dp=1, sp=2, devices=jax.devices()[:2])
    rng = np.random.RandomState(4)
    mk = lambda: jnp.asarray(rng.randn(1, 256, 2, 128), jnp.float32) * 0.3
    q, k, v = mk(), mk(), mk()

    def loss_ring(q, k, v):
        o = ring_attention(q, k, v, mesh, axis_name="sp", causal=True,
                           use_flash=True, interpret=True)
        return jnp.sum(o ** 2)

    def loss_ref(q, k, v):
        return jnp.sum(_plain_attention(q, k, v, causal=True) ** 2)

    gr = jax.jit(jax.grad(loss_ring, argnums=(0, 1, 2)))(q, k, v)
    gf = jax.jit(jax.grad(loss_ref, argnums=(0, 1, 2)))(q, k, v)
    for a, b, name in zip(gr, gf, "qkv"):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=5e-4, atol=5e-4, err_msg=name)


def test_ring_attention_sp1_fast_path():
    mesh = build_mesh(dp=8)
    q, k, v = _qkv()
    out = ring_attention(q, k, v, mesh, axis_name="sp")
    ref = _plain_attention(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=1e-6)


@pytest.mark.parametrize("causal", [True, False])
def test_ulysses_matches_full(causal):
    mesh = build_mesh(dp=2, sp=4)
    q, k, v = _qkv()
    ref = _plain_attention(q, k, v, causal=causal)
    out = ulysses_attention(q, k, v, mesh, axis_name="sp", causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def _ffn_experts(p, rows, group_sizes):
    """moe_layer's expert function: sorted rows, one group an expert."""
    h = jnp.tanh(grouped_matmul(rows, p["w1"], group_sizes))
    return grouped_matmul(h, p["w2"], group_sizes)


def _expert_params(E, M, Hdim, seed=1):
    rng = np.random.RandomState(seed)
    return {"w1": jnp.asarray(rng.randn(E, M, Hdim), jnp.float32) * 0.1,
            "w2": jnp.asarray(rng.randn(E, Hdim, M), jnp.float32) * 0.1}


def _moe_oracle(x, rw, p, k, renormalize=False):
    """Every expert on every token, masked by the float32 top-k choice."""
    probs = jax.nn.softmax(x @ rw, axis=-1)
    top, idx = jax.lax.top_k(probs, k)
    if renormalize:
        top = top / top.sum(-1, keepdims=True)
    combine = jnp.zeros_like(probs).at[
        jnp.arange(x.shape[0])[:, None], idx].set(top)
    hidden = jnp.tanh(jnp.einsum("tm,emh->eth", x, p["w1"]))
    every = jnp.einsum("eth,ehm->tem", hidden, p["w2"])
    return jnp.einsum("te,tem->tm", combine, every)


def _moe_inputs(seed, E=4, M=8, Hd=16, T=64):
    rng = np.random.RandomState(seed)
    return (jnp.asarray(rng.randn(T, M), jnp.float32),
            jnp.asarray(rng.randn(M, E), jnp.float32) * 0.5,
            _expert_params(E, M, Hd))


def _assert_gmm_gradients(got, want, inside):
    """(d_rows, d_weights) against the per-expert loop's: the rows
    ``inside`` the groups (all of them on ``ragged_dot``'s path), every
    weight."""
    np.testing.assert_allclose(got[0][inside], want[0][inside], rtol=1e-5,
                               atol=1e-4, err_msg="d_rows")
    np.testing.assert_allclose(got[1], want[1], rtol=1e-5, atol=1e-4,
                               err_msg="d_weights")


def _per_expert_loop(sizes, x, w):
    """``x[group g] @ w[g]`` group by group, zeros beyond the groups."""
    out, a = [], 0
    for e, n in enumerate(sizes):
        out.append(x[a:a + n] @ w[e])
        a += n
    out.append(jnp.zeros((x.shape[0] - a, w.shape[2]), x.dtype))
    return jnp.concatenate(out)


@pytest.mark.parametrize("interpret", [False, True])
@pytest.mark.parametrize("sizes", [[100, 0, 30, 0, 126], [0, 0, 256, 0, 0],
                                   [50, 50, 50, 50, 56], [60, 0, 40, 0, 28],
                                   [0, 0, 0, 0, 0]])
def test_grouped_matmul_matches_a_per_expert_loop(sizes, interpret):
    """Ragged groups with empty ones among them, forward and both gradients;
    ``interpret`` runs the TPU path's Pallas kernels on the CPU, where a row
    the kernel did not write reads NaN. Rows beyond the groups (the last two
    cases: with ep > 1 they are another shard's) add nothing to the weights'
    gradient on either path; ``ragged_dot``'s path gives zeros there and for
    their gradient, the kernels never write them and nothing may read them
    (ISSUE 37: the expert layer does not)."""
    from horovod_tpu.parallel.moe import _gmm_tile
    assert _gmm_tile(256, 128, 256, 4) is not None       # the kernels apply
    assert _gmm_tile(256, 64, 256, 4) is None            # 64 lanes: XLA
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randn(256, 128), jnp.float32)
    w = jnp.asarray(rng.randn(5, 128, 256), jnp.float32)
    ct = jnp.asarray(rng.randn(256, 256), jnp.float32)
    gs = jnp.asarray(sizes, jnp.int32)

    loop = functools.partial(_per_expert_loop, sizes)

    def ours(x, w):
        return grouped_matmul(x, w, gs, interpret=interpret)
    inside = slice(0, sum(sizes) if interpret else None)
    np.testing.assert_allclose(ours(x, w)[inside], loop(x, w)[inside],
                               rtol=1e-5, atol=1e-4)
    got, want = jax.vjp(ours, x, w)[1](ct), jax.vjp(loop, x, w)[1](ct)
    _assert_gmm_gradients(got, want, inside)


# rows, k, f, itemsize: the share cell's up and down calls
# (smallthinker-21b-a3b.s8192), OLMoE's, the four-chip smoke's float32 case,
# widths whose only common 128-multiple divisor is 128, a width of three
# 128-lane columns, 64-lane widths, rows that no row tile divides
GMM_SHAPES = [(49152, 2560, 768, 2), (49152, 768, 2560, 2),
              (65536, 2048, 1024, 2), (65536, 1024, 2048, 2),
              (256, 128, 128, 4), (1024, 384, 640, 2), (1024, 384, 640, 4),
              (128, 20480, 384, 2), (256, 64, 256, 2), (256, 256, 64, 4),
              (200, 128, 128, 2), (4160, 128, 128, 2)]


@pytest.mark.parametrize("n_rows,k,f,itemsize", GMM_SHAPES)
def test_gmm_tiles_come_from_each_calls_own_shape(n_rows, k, f, itemsize):
    """Each of the three megablox calls gets a tile from its own (rows,
    contraction, columns): 128-multiples that divide them, blocks under the
    stated VMEM budget at the operands' itemsize, the input gradient's
    contraction being ``f`` and its columns ``k``; XLA exactly where the
    one shared tile gave up (rows or a width that 128 does not divide)."""
    from horovod_tpu.parallel import moe
    tiles = moe._gmm_tile(n_rows, k, f, itemsize)
    assert (tiles is None) == bool(n_rows % 128 or k % 128 or f % 128)
    if tiles is None:
        return
    calls = {"forward": (tiles.forward, k, f),
             "input_grad": (tiles.input_grad, f, k),
             "weight_grad": (tiles.weight_grad, k, f)}
    for name, ((tm, tk, tn), contraction, columns) in calls.items():
        assert tm % 128 == 0 and n_rows % tm == 0, (name, tm)
        assert tk % 128 == 0 and contraction % tk == 0, (name, tk)
        assert tn % 128 == 0 and columns % tn == 0, (name, tn)
        out = (tk, tn) if name == "weight_grad" else (tm, tn)
        blocks = [(tm, tk), (tk, tn), (tm, tn)]      # two inputs, one output
        assert (2 * itemsize * sum(a * b for a, b in blocks)
                + 4 * out[0] * out[1]) <= moe.GMM_VMEM_BUDGET, name
    if k * f * 2 * itemsize <= moe.GMM_VMEM_BUDGET // 2:
        # a group's whole weight matrix fits beside the rows: one block,
        # resident across the group's row tiles, both ways round
        assert tiles.forward[1:] == (k, f), tiles
        assert tiles.input_grad[1:] == (f, k), tiles


@pytest.mark.parametrize("tiles", [None, ((128, 128, 384), (128, 384, 128),
                                          (128, 256, 128))])
def test_grouped_matmul_at_unequal_k_and_n_tiles(tiles):
    """Widths 256 x 384 in interpret mode: the rule's own tiles (k and n
    tiles differ, the input gradient's the other way round), and tiles of
    several k and n steps handed to ``_gmm``; ragged groups with empty
    ones, rows beyond the groups (which the kernels leave unwritten and keep
    out of the weights' gradient); forward and both gradients against the
    per-expert loop."""
    from horovod_tpu.parallel import moe
    sizes, n_rows, k, f = [130, 0, 77, 200, 0, 41], 512, 256, 384
    rule = moe._gmm_tile(n_rows, k, f, 4)
    assert rule.forward[1:] == (k, f) and rule.input_grad[1:] == (f, k)
    tiles = rule if tiles is None else moe.GmmTiles(*tiles)
    rng = np.random.RandomState(3)
    x = jnp.asarray(rng.randn(n_rows, k), jnp.float32)
    w = jnp.asarray(rng.randn(len(sizes), k, f), jnp.float32)
    ct = jnp.asarray(rng.randn(n_rows, f), jnp.float32)
    gs = jnp.asarray(sizes, jnp.int32)

    loop = functools.partial(_per_expert_loop, sizes)

    def ours(x, w):
        return moe._gmm(x, w, gs, tiles, True)
    inside = slice(0, sum(sizes))
    with jax.default_matmul_precision("highest"):
        np.testing.assert_allclose(ours(x, w)[inside], loop(x, w)[inside],
                                   rtol=1e-5, atol=1e-4)
        got, want = jax.vjp(ours, x, w)[1](ct), jax.vjp(loop, x, w)[1](ct)
    _assert_gmm_gradients(got, want, inside)


@pytest.mark.parametrize("renormalize", [False, True])
def test_moe_layer_matches_the_dense_oracle(renormalize):
    x, rw, p = _moe_inputs(2)
    mesh = build_mesh(dp=8)
    y, m = jax.jit(lambda x, rw, p: moe_layer(
        x, rw, _ffn_experts, p, mesh, k=2, renormalize=renormalize,
        token_axes=()))(x, rw, p)
    np.testing.assert_allclose(y, _moe_oracle(x, rw, p, 2, renormalize),
                               rtol=1e-5, atol=1e-5)
    assert float(m.dropped) == 0.0
    assert float(m.load_balance_loss) > 0 and float(m.router_z_loss) > 0


def test_moe_layer_is_dropless_under_total_imbalance():
    """Every token to experts 0 and 1: two groups of all the rows, the
    others empty, nothing dropped, the oracle's result."""
    x, _rw, p = _moe_inputs(5)
    x = x.at[:, 0].set(1.0)
    rw = jnp.zeros((8, 4), jnp.float32).at[0, 0].set(40.0).at[0, 1].set(20.0)
    mesh = build_mesh(dp=8)
    y, m = jax.jit(lambda x, rw, p: moe_layer(
        x, rw, _ffn_experts, p, mesh, k=2, token_axes=()))(x, rw, p)
    assert float(m.dropped) == 0.0
    assert float(m.max_expert_load) == 2.0      # 64 rows where 32 is even
    np.testing.assert_allclose(y, _moe_oracle(x, rw, p, 2), rtol=1e-5,
                               atol=1e-5)


@pytest.mark.parametrize("layout, token_axes", [
    (dict(dp=2, ep=4), ()), (dict(ep=2), ()), (dict(dp=2, ep=2), ("dp",)),
    (dict(dp=4, ep=2), ("dp",))])
def test_moe_ep_matches_single_device(layout, token_axes):
    """Expert parallelism is a layout: output, metrics and gradients are one
    device's, tokens replicated over ep or sharded over dp beside it."""
    x, rw, p = _moe_inputs(3)
    n = int(np.prod(list(layout.values())))

    def run(mesh, axes):
        def f(x, rw, p):
            y, m = moe_layer(x, rw, _ffn_experts, p, mesh, k=2,
                             token_axes=axes)
            return jnp.sum(y * y) + m.load_balance_loss + m.router_z_loss, \
                (y, m)
        (_, (y, m)), grads = jax.jit(jax.value_and_grad(
            f, (0, 1, 2), has_aux=True))(x, rw, p)
        return y, m, grads
    y1, m1, g1 = run(build_mesh(dp=1, devices=jax.devices()[:1]), ())
    y2, m2, g2 = run(build_mesh(**layout, devices=jax.devices()[:n]),
                     token_axes)
    np.testing.assert_allclose(y1, y2, rtol=1e-5, atol=1e-5)
    for a, b, name in zip(m1, m2, m1._fields):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5,
                                   err_msg=name)
    assert float(m2.dropped) == 0.0
    for a, b in zip(jax.tree_util.tree_leaves(g1),
                    jax.tree_util.tree_leaves(g2)):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("layout, token_axes", [
    (dict(ep=2), ()), (dict(dp=2, ep=2), ("dp",))])
def test_moe_ep_on_the_kernels_matches_single_device(layout, token_axes):
    """The same on the TPU path's Pallas kernels (interpret mode, 128-wide
    experts): each ep shard's groups end before its rows do, and the kernel
    leaves the rows behind them unwritten (NaN here, stale memory on a
    chip). Output and the gradients of the tokens, the router and both
    expert matrices are one device's on XLA's ragged_dot."""
    x, rw, p = _moe_inputs(7, E=4, M=128, Hd=128, T=64)
    n = int(np.prod(list(layout.values())))

    def run(mesh, axes, interpret):
        def experts(p, rows, group_sizes):
            h = jnp.tanh(grouped_matmul(rows, p["w1"], group_sizes,
                                        interpret=interpret))
            return grouped_matmul(h, p["w2"], group_sizes,
                                  interpret=interpret)

        def f(x, rw, p):
            y, m = moe_layer(x, rw, experts, p, mesh, k=2, token_axes=axes)
            return jnp.sum(y * y) + m.load_balance_loss, y
        (_, y), grads = jax.jit(jax.value_and_grad(
            f, (0, 1, 2), has_aux=True))(x, rw, p)
        return y, grads
    y1, g1 = run(build_mesh(dp=1, devices=jax.devices()[:1]), (), False)
    y2, g2 = run(build_mesh(**layout, devices=jax.devices()[:n]),
                 token_axes, True)
    np.testing.assert_allclose(y1, y2, rtol=1e-4, atol=1e-5)
    for a, b, name in zip(jax.tree_util.tree_leaves(g1),
                          jax.tree_util.tree_leaves(g2),
                          ("x", "router", "w1", "w2")):
        assert np.all(np.isfinite(b)), name
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-4, err_msg=name)


def _stage_fn(p, x):
    return jnp.tanh(x @ p["w"] + p["b"])


def test_pipeline_matches_sequential():
    S, T, M = 4, 16, 8
    rng = np.random.RandomState(4)
    stages = [{"w": jnp.asarray(rng.randn(M, M), jnp.float32) * 0.5,
               "b": jnp.asarray(rng.randn(M), jnp.float32) * 0.1}
              for _ in range(S)]
    x = jnp.asarray(rng.randn(T, M), jnp.float32)

    ref = x
    for p in stages:
        ref = _stage_fn(p, ref)

    mesh = build_mesh(dp=2, pp=4)
    stacked = stage_stacked(stages)
    out = pipeline_apply(_stage_fn, stacked, x, mesh, n_microbatches=4)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


def test_pipeline_pp1_fast_path():
    rng = np.random.RandomState(5)
    p = [{"w": jnp.asarray(rng.randn(8, 8), jnp.float32),
          "b": jnp.zeros(8, jnp.float32)}]
    x = jnp.asarray(rng.randn(6, 8), jnp.float32)
    mesh = build_mesh(dp=8)
    out = pipeline_apply(_stage_fn, stage_stacked(p), x, mesh,
                         n_microbatches=2)
    np.testing.assert_allclose(np.asarray(out), np.asarray(_stage_fn(p[0], x)),
                               rtol=1e-6)


def test_pipeline_bad_microbatch_count():
    mesh = build_mesh(dp=2, pp=4)
    p = stage_stacked([{"w": jnp.eye(4), "b": jnp.zeros(4)}] * 4)
    with pytest.raises(ValueError):
        pipeline_apply(_stage_fn, p, jnp.ones((10, 4)), mesh,
                       n_microbatches=3)


def _mse_loss(y, t):
    return jnp.mean((y - t) ** 2)


@pytest.mark.parametrize("pp,dp,n_mb", [(4, 2, 8), (2, 4, 3), (8, 1, 8)])
def test_pipeline_1f1b_matches_jax_grad(pp, dp, n_mb):
    """The 1F1B schedule's loss AND gradients must equal jax.grad of the
    sequentially applied stages (incl. M not a multiple of S, and a
    sharded batch axis)."""
    from horovod_tpu.parallel.pipeline import pipeline_1f1b_apply
    H = 8
    T = n_mb * 4
    rng = np.random.RandomState(7)
    stages = [{"w": jnp.asarray(rng.randn(H, H), jnp.float32) * 0.4,
               "b": jnp.asarray(rng.randn(H), jnp.float32) * 0.1}
              for _ in range(pp)]
    x = jnp.asarray(rng.randn(T, H), jnp.float32)
    tgt = jnp.asarray(rng.randn(T, H), jnp.float32)

    def oracle(stacked):
        xm = x.reshape(n_mb, T // n_mb, H)
        tm = tgt.reshape(n_mb, T // n_mb, H)

        def one_mb(xb, tb):
            h = xb
            for s in range(pp):
                h = _stage_fn(jax.tree_util.tree_map(
                    lambda p: p[s], stacked), h)
            return _mse_loss(h, tb)
        return jax.vmap(one_mb)(xm, tm).mean()

    stacked = stage_stacked(stages)
    ref_loss, ref_grads = jax.value_and_grad(oracle)(stacked)

    mesh = build_mesh(dp=dp, pp=pp)
    loss, grads = pipeline_1f1b_apply(
        _stage_fn, _mse_loss, stacked, x, tgt, mesh, n_microbatches=n_mb)
    np.testing.assert_allclose(float(loss), float(ref_loss), rtol=1e-5)
    for g, rg in zip(jax.tree_util.tree_leaves(grads),
                     jax.tree_util.tree_leaves(ref_grads)):
        np.testing.assert_allclose(np.asarray(g), np.asarray(rg),
                                   rtol=1e-4, atol=1e-5)


def test_pipeline_1f1b_pp1_fast_path():
    from horovod_tpu.parallel.pipeline import pipeline_1f1b_apply
    rng = np.random.RandomState(9)
    p = stage_stacked([{"w": jnp.asarray(rng.randn(6, 6), jnp.float32),
                        "b": jnp.zeros(6, jnp.float32)}])
    x = jnp.asarray(rng.randn(8, 6), jnp.float32)
    tgt = jnp.asarray(rng.randn(8, 6), jnp.float32)
    mesh = build_mesh(dp=8)
    loss, grads = pipeline_1f1b_apply(_stage_fn, _mse_loss, p, x, tgt,
                                      mesh, n_microbatches=2)
    assert np.isfinite(float(loss))
    assert jax.tree_util.tree_leaves(grads)[0].shape[0] == 1
