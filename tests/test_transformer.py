"""Flagship transformer: forward/backward under every parallelism layout on
the 8-device virtual mesh, checked for finiteness, cross-layout loss
agreement, and training progress."""

import numpy as np
import pytest
import jax
import jax.numpy as jnp
import optax

from horovod_tpu.models import (TransformerConfig, init_params, shard_params,
                                make_train_step, make_forward, init_opt_state,
                                shard_batch)
from horovod_tpu.parallel import build_mesh

CFG = TransformerConfig(vocab_size=64, d_model=32, n_heads=4, n_layers=4,
                        d_ff=64, max_seq=32, dtype=jnp.float32,
                        n_microbatches=2, remat=False)
MOE_CFG = TransformerConfig(vocab_size=64, d_model=32, n_heads=4, n_layers=4,
                            d_ff=64, max_seq=32, n_experts=4,
                            dtype=jnp.float32, n_microbatches=2, remat=False)


def _batch(B=8, S=16, vocab=64, seed=0):
    rng = np.random.RandomState(seed)
    tokens = rng.randint(0, vocab, (B, S)).astype(np.int32)
    targets = np.roll(tokens, -1, axis=1).astype(np.int32)
    return jnp.asarray(tokens), jnp.asarray(targets)


MESHES = {
    "dp8": dict(dp=8),
    "dp2_tp4": dict(dp=2, tp=4),
    "dp2_sp2_tp2": dict(dp=2, sp=2, tp=2),
    "dp2_pp2_tp2": dict(dp=2, pp=2, tp=2),
    "dp2_pp2_sp2": dict(dp=2, pp=2, sp=2),
}


@pytest.mark.parametrize("name", list(MESHES))
def test_forward_loss_agrees_across_layouts(name):
    """Same params + data must give (nearly) the same loss on every layout —
    the cross-layout analog of the reference's multi-rank numeric equality
    tests."""
    mesh_ref = build_mesh(dp=8)
    fwd_ref = make_forward(CFG, mesh_ref)
    rngp = np.random.RandomState(42)
    params_host = init_params(rngp, CFG, n_stages=1)
    tokens, targets = _batch()

    p_ref = shard_params(params_host, CFG, mesh_ref)
    t_ref, y_ref = shard_batch(tokens, targets, mesh_ref)
    ref = float(fwd_ref(p_ref, t_ref, y_ref))

    mesh = build_mesh(**MESHES[name])
    n_stages = MESHES[name].get("pp", 1)
    params_host_s = init_params(np.random.RandomState(42), CFG,
                                n_stages=n_stages)
    p = shard_params(params_host_s, CFG, mesh)
    t, y = shard_batch(tokens, targets, mesh)
    fwd = make_forward(CFG, mesh)
    out = float(fwd(p, t, y))
    assert np.isfinite(out)
    np.testing.assert_allclose(out, ref, rtol=1e-4, atol=1e-4)


def test_moe_forward_all_axes():
    """MoE config on a mesh using dp, ep and tp simultaneously."""
    mesh = build_mesh(dp=2, ep=2, tp=2)
    params_host = init_params(np.random.RandomState(1), MOE_CFG, n_stages=1)
    p = shard_params(params_host, MOE_CFG, mesh)
    tokens, targets = _batch()
    t, y = shard_batch(tokens, targets, mesh)
    out = float(make_forward(MOE_CFG, mesh)(p, t, y))
    assert np.isfinite(out)


def test_train_step_reduces_loss():
    mesh = build_mesh(dp=2, sp=2, tp=2)
    params_host = init_params(np.random.RandomState(3), CFG, n_stages=1)
    p = shard_params(params_host, CFG, mesh)
    tokens, targets = _batch()
    t, y = shard_batch(tokens, targets, mesh)
    tx = optax.adam(1e-2)
    step = make_train_step(CFG, mesh, tx)
    opt_state = init_opt_state(tx, p, mesh, CFG)
    losses = []
    for i in range(10):
        p, opt_state, loss, aux = step(p, opt_state, t, y)
        jax.block_until_ready(loss)  # 1-core CPU: avoid rendezvous pile-up
        losses.append(float(loss))
    assert losses[-1] < losses[0] * 0.9, losses


def test_train_step_pipeline_moe():
    """The everything-at-once layout: dp, pp, and ep+tp shared... (8 devices:
    dp2 × pp2 × ep... ) — use dp2/pp2/tp2 with MoE (ep=1 degenerates to
    replicated experts, still exercising the MoE code path in the pipeline)."""
    mesh = build_mesh(dp=2, pp=2, tp=2)
    cfg = MOE_CFG
    params_host = init_params(np.random.RandomState(4), cfg, n_stages=2)
    p = shard_params(params_host, cfg, mesh)
    tokens, targets = _batch()
    t, y = shard_batch(tokens, targets, mesh)
    tx = optax.sgd(1e-2)
    step = make_train_step(cfg, mesh, tx)
    opt_state = init_opt_state(tx, p, mesh, cfg)
    p, opt_state, loss, aux = step(p, opt_state, t, y)
    jax.block_until_ready(loss)
    assert np.isfinite(float(loss))
    assert np.isfinite(float(aux["aux_loss"]))


def test_train_step_pipeline_matches_pure_dp_trajectory():
    """Pipeline-parallel training must be a pure LAYOUT change (ISSUE
    11): at the same data-parallel width, dp4 alone (4 devices) and
    dp4 x pp2 (8 devices) run identical math, so their loss
    trajectories must agree to fp tolerance. This is the regression
    test for the pipeline gradient-scale bug — differentiating the
    replicated loss inside shard_map over-counted every STAGE gradient
    by pp while the embed/head gradients stayed x1, silently skewing
    stage-vs-embedding training balance on every pp>1 mesh
    (parallel/pipeline.py `replicate_from_stage`)."""
    tokens, targets = _batch()

    def run(mesh_kw, n_stages, n_dev, steps=4):
        mesh = build_mesh(**mesh_kw, devices=jax.devices()[:n_dev])
        params_host = init_params(np.random.RandomState(42), CFG,
                                  n_stages=n_stages)
        p = shard_params(params_host, CFG, mesh)
        t, y = shard_batch(tokens, targets, mesh)
        tx = optax.sgd(5e-2)
        step = make_train_step(CFG, mesh, tx)
        s = init_opt_state(tx, p, mesh, CFG)
        out = []
        for _ in range(steps):
            p, s, loss, aux = step(p, s, t, y)
            jax.block_until_ready(loss)
            out.append(float(loss))
        return out

    ref = run(dict(dp=4), 1, 4)
    pp2 = run(dict(dp=4, pp=2), 2, 8)
    np.testing.assert_allclose(pp2, ref, rtol=1e-5, atol=1e-5)


# -- the embedding's lookup (ISSUE 38) ----------------------------------------

_LOOKUP_V, _LOOKUP_M = 64, 32


def _lookup_on(tp, cfg):
    """``f(table, tokens, cotangent) -> (rows, the table's gradient)`` of
    ``_embed_lookup`` as the train step runs it: inside a shard_map, the
    table's vocabulary split over a ``tp`` axis of that size."""
    from jax.sharding import PartitionSpec as P
    from horovod_tpu._compat import shard_map
    from horovod_tpu.models.transformer import _embed_lookup
    mesh = build_mesh(devices=jax.devices()[:tp], tp=tp)

    def body(table, tokens, cot):
        rows, back = jax.vjp(lambda e: _embed_lookup(e, tokens, cfg), table)
        # (without check_vma the psum's transpose is a psum: the replicated
        # cotangent comes back tp times; a power of two, exact in bf16)
        return rows, back(cot / tp)[0]
    return jax.jit(shard_map(body, mesh=mesh,
                             in_specs=(P("tp"), P(), P()),
                             out_specs=(P(), P("tp")), check_vma=False))


def _lookup_cfg(dtype, tied):
    return TransformerConfig(vocab_size=_LOOKUP_V, d_model=_LOOKUP_M,
                             n_heads=4, n_layers=1, d_ff=64, max_seq=512,
                             dtype=dtype, tie_embeddings=tied)


@pytest.mark.parametrize("tied", [True, False], ids=["tied", "untied"])
@pytest.mark.parametrize("tp", [1, 4], ids=["tp1", "tp4"])
@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "f32"])
def test_embed_lookup_gives_the_cast_table_s_rows(dtype, tp, tied):
    """Casting the rows taken is casting the table, bit for bit, whichever
    way the layer does it."""
    rng = np.random.RandomState(3)
    table = jnp.asarray(rng.randn(_LOOKUP_V, _LOOKUP_M), jnp.float32)
    tokens = jnp.asarray(rng.randint(0, _LOOKUP_V, (2, 16)), jnp.int32)
    cot = jnp.zeros((2, 16, _LOOKUP_M), dtype)
    rows, _grad = _lookup_on(tp, _lookup_cfg(dtype, tied))(table, tokens, cot)
    want = table.astype(dtype)[tokens]
    assert rows.dtype == want.dtype
    np.testing.assert_array_equal(np.asarray(rows, np.float32),
                                  np.asarray(want, np.float32))


@pytest.mark.parametrize("columns", [1280, 8], ids=["whole_rows",
                                                     "four_pieces"])
@pytest.mark.parametrize("tp", [1, 4], ids=["tp1", "tp4"])
def test_untied_embedding_gradient_adds_a_repeated_token_in_float32(
        tp, columns, monkeypatch):
    """A token 300 times in the batch: with a head of its own the table's
    gradient row is the float32 sum of the 300 bf16 cotangent rows (a sum
    kept in bf16 stops growing long before: it is ~1 % off, not 1e-6),
    whether the runs' sums are added as whole rows or in pieces."""
    from horovod_tpu.models import transformer
    monkeypatch.setattr(transformer, "SUM_COLUMNS", columns)
    rng = np.random.RandomState(4)
    table = jnp.asarray(rng.randn(_LOOKUP_V, _LOOKUP_M), jnp.float32)
    tokens = rng.randint(0, _LOOKUP_V, (1, 400)).astype(np.int32)
    tokens[0, :300] = 7
    cot = jnp.asarray(1.0 + rng.rand(1, 400, _LOOKUP_M), jnp.bfloat16)
    want = np.zeros((_LOOKUP_V, _LOOKUP_M), np.float64)
    np.add.at(want, tokens[0], np.asarray(cot[0], np.float64))

    def grad(tied):
        cfg = _lookup_cfg(jnp.bfloat16, tied)
        _rows, g = _lookup_on(tp, cfg)(table, jnp.asarray(tokens), cot)
        assert g.dtype == jnp.float32
        return np.abs(np.asarray(g, np.float64) - want).max() / want.max()

    # (the tied form casts the table first and adds the rows in bf16: it
    # reads some 1e-2 here, which is no promise of the layer's)
    assert grad(tied=False) < 1e-6


@pytest.mark.parametrize("columns", [1280, 8], ids=["whole_rows",
                                                     "four_pieces"])
def test_embedding_gradient_s_sums_flagged_sorted_are_sorted(columns,
                                                             monkeypatch):
    """``indices_are_sorted`` is a promise XLA:TPU builds on (it skips its
    own sort) and the CPU ignores: every segment sum and scatter of the
    hand-written gradient that makes it is handed ids that do not fall,
    with repeated tokens and the rows added in pieces."""
    from horovod_tpu.models import transformer
    monkeypatch.setattr(transformer, "SUM_COLUMNS", columns)
    seen = []
    real = jax.ops.segment_sum

    def checked(data, ids, *args, indices_are_sorted=False, **kwargs):
        seen.append(bool(indices_are_sorted))
        assert not indices_are_sorted or (np.diff(np.asarray(ids)) >= 0).all()
        return real(data, ids, *args, indices_are_sorted=indices_are_sorted,
                    **kwargs)
    monkeypatch.setattr(jax.ops, "segment_sum", checked)
    rng = np.random.RandomState(5)
    table = jnp.zeros((_LOOKUP_V, _LOOKUP_M), jnp.float32)
    tokens = rng.randint(0, 8, (1, 40)).astype(np.int32)      # 8 ids, 40 rows
    cot = jnp.asarray(rng.randn(1, 40, _LOOKUP_M), jnp.bfloat16)
    grad, _ = transformer._table_rows_bwd(jnp.bfloat16, (table, tokens), cot)
    want = np.zeros(table.shape, np.float64)
    np.add.at(want, tokens[0], np.asarray(cot[0], np.float64))
    np.testing.assert_allclose(np.asarray(grad, np.float64), want, rtol=1e-6,
                               atol=1e-6)
    assert seen == [True] * (_LOOKUP_M // min(columns, _LOOKUP_M))
