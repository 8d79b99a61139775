"""The block attention kernels (a head's whole score tile in VMEM, forward
and backward) against the XLA core of ``models/bert.py``, in interpret
mode on the CPU; the dispatch of ``attend``; ``Bert`` on the kernel path
and on a dp=4 mesh. The compile for a described v5e is in
``tests/test_tpu_compile.py``, the chip's answer in ``chip_smoke.py``."""

import numpy as np
import pytest
import jax
import jax.numpy as jnp
import optax

from horovod_tpu.ops import pallas_attention as pa


def _inputs(B, S, H, D, dtype, padded, seed=0):
    """q, k, v, a [B, S] key mask (row 0 half padded, row 1 all padded
    when ``padded``) and a float32 cotangent."""
    rng = np.random.RandomState(seed)

    def mk():
        return (jnp.asarray(rng.randn(B, S, H, D), jnp.float32)
                * 0.5).astype(dtype)
    mask = np.ones((B, S), bool)
    if padded:
        mask[0, S // 2:] = False
        mask[1, :] = False
    return (mk(), mk(), mk(), jnp.asarray(mask),
            jnp.asarray(rng.randn(B, S, H, D), jnp.float32))


def _loss(attn, w):
    return lambda q, k, v: jnp.sum(attn(q, k, v).astype(jnp.float32) * w)


def _close(got, want, tol, what):
    np.testing.assert_allclose(
        np.asarray(got.astype(jnp.float32)),
        np.asarray(want.astype(jnp.float32)), rtol=tol, atol=tol,
        err_msg=what)


@pytest.mark.parametrize("padded", [False, True])
@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
@pytest.mark.parametrize("D", [64, 128])
@pytest.mark.parametrize("S", [128, 384, 512])
def test_block_kernels_match_the_xla_core(S, D, dtype, padded):
    """Forward and gradients, two 128-lane columns of heads, against the
    XLA core in float32 on the same values (bf16 at chip_smoke.py's
    tolerance for bf16 kernels). A row whose keys are all masked attends
    evenly, as the XLA core does, and moves no q or k."""
    q, k, v, mask, w = _inputs(2, S, 256 // D, D, dtype, padded)
    f32 = [x.astype(jnp.float32) for x in (q, k, v)]
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5

    def kernel(q, k, v):
        return pa.block_attention(q, k, v, mask, interpret=True)

    def core(q, k, v):
        return pa._key_masked_attention(q, k, v, mask)

    out = kernel(q, k, v)
    assert out.dtype == dtype and out.shape == q.shape
    _close(out, core(*f32), tol, "o")
    got = jax.jit(jax.grad(_loss(kernel, w), (0, 1, 2)))(q, k, v)
    want = jax.jit(jax.grad(_loss(core, w), (0, 1, 2)))(*f32)
    for g, r, name in zip(got, want, ("dq", "dk", "dv")):
        assert g.dtype == dtype
        _close(g, r, tol * max(1.0, float(jnp.max(jnp.abs(r)))), name)
    if padded:
        assert float(jnp.max(jnp.abs(got[0][1]))) == 0.0
        assert float(jnp.max(jnp.abs(got[1][1]))) == 0.0
        assert float(jnp.max(jnp.abs(got[2][1]))) > 0.0


def test_block_kernels_without_a_mask_are_plain_attention():
    from horovod_tpu.parallel.ring_attention import _plain_attention
    q, k, v, _mask, w = _inputs(2, 128, 2, 64, jnp.float32, False, seed=1)

    def kernel(q, k, v):
        return pa.block_attention(q, k, v, interpret=True)

    def plain(q, k, v):
        return _plain_attention(q, k, v, causal=False)
    _close(kernel(q, k, v), plain(q, k, v), 2e-5, "o")
    for g, r in zip(jax.jit(jax.grad(_loss(kernel, w), (0, 1, 2)))(q, k, v),
                    jax.jit(jax.grad(_loss(plain, w), (0, 1, 2)))(q, k, v)):
        _close(g, r, 2e-5, "grad")


@pytest.mark.parametrize("rows", [1, 2, 4])
def test_rows_a_step_do_not_change_the_result(rows):
    q, k, v, mask, _w = _inputs(4, 128, 2, 64, jnp.float32, True, seed=2)
    a = pa.block_attention(q, k, v, mask, rows=rows, interpret=True)
    b = pa.block_attention(q, k, v, mask, interpret=True)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_rectangular_scores():
    """Sq != Sk (cross-attention's shape)."""
    rng = np.random.RandomState(3)
    q = jnp.asarray(rng.randn(2, 128, 2, 64), jnp.float32)
    k, v = (jnp.asarray(rng.randn(2, 256, 2, 64), jnp.float32)
            for _ in range(2))
    mask = jnp.asarray(rng.rand(2, 256) < 0.7)
    w = jnp.asarray(rng.randn(2, 128, 2, 64), jnp.float32)

    def kernel(q, k, v):
        return pa.block_attention(q, k, v, mask, interpret=True)

    def core(q, k, v):
        return pa._key_masked_attention(q, k, v, mask)
    _close(kernel(q, k, v), core(q, k, v), 2e-5, "o")
    for g, r in zip(jax.jit(jax.grad(_loss(kernel, w), (0, 1, 2)))(q, k, v),
                    jax.jit(jax.grad(_loss(core, w), (0, 1, 2)))(q, k, v)):
        _close(g, r, 2e-4, "grad")


# -- the dispatch -------------------------------------------------------------

# (Sq, Sk, H, D, causal, masked) -> path on a TPU
_PATHS = {
    (512, 512, 16, 64, False, True): "block",      # bert-large.s512
    (384, 384, 12, 64, False, True): "block",      # BERT-Base fine-tuning
    (256, 256, 16, 64, False, True): "block",
    (128, 512, 16, 64, False, False): "block",     # 65 536 scores a head
    (512, 512, 8, 128, False, True): "block",      # a mask: not flash's
    (512, 512, 8, 128, False, False): "flash",     # as before this kernel
    (2048, 2048, 16, 128, True, False): "flash",   # gpt-1.3b-widths.s2048
    (4096, 4096, 16, 128, True, False): "flash",   # olmoe-1b-7b.s4096
    (128, 128, 16, 64, False, True): "xla",        # bert-large.s128: too few
    (128, 256, 16, 64, False, True): "xla",        # scores to win (PR 27)
    (2048, 2048, 16, 64, True, False): "flash",    # causal at head_dim 64:
    (512, 512, 16, 64, True, False): "flash",      # heads first since PR 49
    (4096, 4096, 32, 64, True, False): "flash",    # granite-4.0-h-micro.s4096
    (2048, 2048, 16, 64, False, False): "flash",   # too long for a block
    (2048, 2048, 16, 64, False, True): "xla",      # and a mask is not flash's
    (512, 512, 16, 32, True, False): "xla",        # no head under 64
    (1024, 1024, 16, 64, False, True): "xla",      # one score tile too many
    (300, 300, 16, 64, False, True): "xla",
    (256, 260, 16, 64, False, True): "xla",
    (256, 256, 3, 64, False, True): "xla",         # half a column of heads
    (256, 256, 16, 32, False, True): "xla",
}


@pytest.mark.parametrize("shape", sorted(_PATHS))
def test_attention_path_is_a_function_of_the_shape(shape, monkeypatch):
    assert pa.attention_path(*shape) == "xla"       # the CPU
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert pa.attention_path(*shape) == _PATHS[shape]
    assert pa.block_eligible(128, 128, 16, 64)      # can, is not picked


@pytest.mark.parametrize("S", [2048, 4096])
def test_the_flash_cells_keep_their_tiles(S):
    assert pa.flash_eligible(S, S, 128)
    assert pa.flash_blocks(S, S, 128, jnp.bfloat16) == (1024, 1024)


@pytest.mark.parametrize("B,S,rows,unroll", [
    (64, 128, 16, 8),       # bert-large.s128: 4 x 8 grid steps
    (8, 512, 2, 2),         # bert-large.s512: 4 x 8 grid steps
    (32, 256, 4, 4), (16, 384, 4, 2), (6, 512, 3, 1), (2, 128, 2, 2)])
def test_block_rows_fill_the_vmem_budget_and_divide_the_batch(B, S, rows,
                                                              unroll):
    """``unroll``: the rows the loop has in flight, the rule's as far as
    it divides the rows of a step."""
    import math
    assert pa.block_rows(B, S, S, jnp.bfloat16) == rows
    assert math.gcd(rows, pa._row_unroll(S, S)) == unroll
    assert pa.block_vmem_bytes(rows, S, S, 2) <= pa.VMEM_BUDGET
    assert pa.block_grid(B, 16, 64, rows) == (B // rows, 8)


def _primitives(fn, *args):
    # a new function each time: make_jaxpr caches a trace by function
    return str(jax.make_jaxpr(lambda *a: fn(*a))(*args))


def test_attend_takes_the_kernels_on_a_tpu_only(monkeypatch):
    q, k, v, mask, _w = _inputs(2, 256, 2, 64, jnp.bfloat16, True)

    def bert(q, k, v):
        return pa.attend(q, k, v, causal=False, key_mask=mask)
    assert "pallas_call" not in _primitives(bert, q, k, v)
    _close(bert(q, k, v), pa._key_masked_attention(q, k, v, mask), 0, "cpu")
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert pa.FWD_NAME in _primitives(bert, q, k, v)
    assert pa.BWD_NAME in _primitives(
        jax.grad(lambda q: bert(q, k, v).astype(jnp.float32).sum()), q)
    with pytest.raises(ValueError):
        pa.attend(q, k, v, causal=True, key_mask=mask)


def test_block_attention_refuses_what_it_does_not_take():
    q, k, v, mask, _w = _inputs(2, 128, 2, 32, jnp.float32, False)
    with pytest.raises(ValueError):
        pa.block_attention(q, k, v, mask, interpret=True)


# -- models/bert.py on the kernel path ----------------------------------------

@pytest.fixture
def kernel_path(monkeypatch):
    """``attend`` as on a TPU, its block kernels in interpret mode; yields
    the list of shapes it was called with."""
    calls = []
    real = pa.block_attention

    def interpreted(q, k, v, key_mask=None, scale=None):
        calls.append((q.shape, jax.typeof(q).sharding.mesh.shape_tuple))
        return real(q, k, v, key_mask, scale, interpret=True)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(pa, "block_attention", interpreted)
    return calls


def _tiny_bert():
    from horovod_tpu.models.bert import Bert, BertConfig
    return Bert(BertConfig(vocab_size=96, hidden_size=128, num_layers=2,
                           num_heads=2, intermediate_size=256,
                           max_position=256, dtype=jnp.float32))


def _bert_batch(B, S=256, vocab=96, seed=0):
    rng = np.random.RandomState(seed)
    mask = np.ones((B, S), bool)
    mask[0, S // 2:] = False
    return {
        "input_ids": jnp.asarray(rng.randint(0, vocab, (B, S)), jnp.int32),
        "token_type_ids": jnp.zeros((B, S), jnp.int32),
        "attention_mask": jnp.asarray(mask),
        "mlm_labels": jnp.asarray(rng.randint(0, vocab, (B, S)), jnp.int32),
        "mlm_mask": jnp.asarray(rng.rand(B, S) < 0.15, jnp.float32),
        "nsp_labels": jnp.asarray(rng.randint(0, 2, (B,)), jnp.int32),
    }


def _bert_loss_and_grads(model, params, b):
    from horovod_tpu.models.bert import pretrain_loss

    def loss_fn(p):
        mlm, nsp = model.apply({"params": p}, b["input_ids"],
                               b["token_type_ids"], b["attention_mask"])
        return pretrain_loss(mlm, nsp, b["mlm_labels"], b["mlm_mask"],
                             b["nsp_labels"])
    return jax.value_and_grad(loss_fn)(params)


def test_bert_is_the_same_model_on_the_kernel_and_xla_paths(kernel_path,
                                                            monkeypatch):
    import flax.linen as nn
    from horovod_tpu.models.bert import init_bert
    model = _tiny_bert()
    params = nn.meta.unbox(init_bert(model, jax.random.PRNGKey(0), 256))
    batch = _bert_batch(2)
    loss_k, grads_k = _bert_loss_and_grads(model, params, batch)
    assert [s for s, _m in kernel_path] == [(2, 256, 2, 64)] * 2
    monkeypatch.undo()
    loss_x, grads_x = _bert_loss_and_grads(model, params, batch)
    assert len(kernel_path) == 2
    np.testing.assert_allclose(float(loss_k), float(loss_x), rtol=1e-5)
    flat_x = dict(jax.tree_util.tree_leaves_with_path(grads_x))
    for path, g in jax.tree_util.tree_leaves_with_path(grads_k):
        np.testing.assert_allclose(np.asarray(g), np.asarray(flat_x[path]),
                                   rtol=1e-3, atol=1e-5, err_msg=str(path))


def test_bert_step_on_dp4_equals_one_device(kernel_path):
    """The train step with the kernels under GSPMD, batch over dp=4 (each
    device's kernels on its own rows), against one device on the same
    global batch: same loss, same parameters after the step."""
    import horovod_tpu as hvd
    from horovod_tpu.models import init_opt_state
    from horovod_tpu.models.bert import init_bert, make_bert_train_step
    model, batch, tx = _tiny_bert(), _bert_batch(8), optax.sgd(0.1)
    results = []
    for devices in (jax.devices()[:4], jax.devices()[:1]):
        mesh = hvd.build_mesh(dp=-1, devices=devices)
        params = init_bert(model, jax.random.PRNGKey(0), 256, mesh)
        step = make_bert_train_step(model, tx, mesh)
        placed = jax.device_put(batch, hvd.batch_sharding(mesh))
        params, _opt, loss = step(params, init_opt_state(tx, params, mesh),
                                  placed)
        results.append((float(loss), jax.device_get(params)))
        assert dict(kernel_path[-1][1])["dp"] == len(devices)
    (loss4, params4), (loss1, params1) = results
    np.testing.assert_allclose(loss4, loss1, rtol=1e-5)
    for a, b in zip(jax.tree_util.tree_leaves(params4),
                    jax.tree_util.tree_leaves(params1)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-6)


def test_the_kernels_run_on_each_devices_shard():
    """Arrays placed on a mesh carry it in their type: the call becomes a
    shard_map over dp (batch rows) and tp (128-lane columns of heads), and
    no device sees another's rows. Nobody tells the kernel the mesh."""
    import horovod_tpu as hvd
    from jax.sharding import NamedSharding, PartitionSpec as P
    mesh = hvd.build_mesh(dp=2, tp=2, devices=jax.devices()[:4])
    q, k, v, mask, w = _inputs(4, 128, 4, 64, jnp.float32, True, seed=5)
    sh = NamedSharding(mesh, P("dp", None, "tp", None))
    qs, ks, vs = (jax.device_put(x, sh) for x in (q, k, v))

    def kernel(q, k, v):
        return pa.block_attention(q, k, v, mask, interpret=True)
    text = _primitives(kernel, qs, ks, vs)
    assert "shard_map" in text and "f32[2,128,128]" in text
    want = kernel(q, k, v)
    _close(jax.jit(kernel)(qs, ks, vs), want, 1e-6, "o")
    got = jax.jit(jax.grad(_loss(kernel, w), (0, 1, 2)))(qs, ks, vs)
    for g, r in zip(got, jax.grad(_loss(kernel, w), (0, 1, 2))(q, k, v)):
        _close(g, r, 1e-6, "grad")


def test_replicated_arrays_on_a_mesh_run_the_whole_call_on_every_device():
    """The benchmark's reference check: two sequences, replicated over
    dp=4, in a jit of its own. The batch does not split, so every device
    computes all of it, still inside a shard_map: a bare pallas_call does
    not lower for several devices."""
    import horovod_tpu as hvd
    from jax.sharding import NamedSharding, PartitionSpec as P
    mesh = hvd.build_mesh(dp=-1, devices=jax.devices()[:4])
    q, k, v, mask, w = _inputs(2, 128, 2, 64, jnp.float32, True, seed=6)
    rep = NamedSharding(mesh, P())
    qs, ks, vs = (jax.device_put(x, rep) for x in (q, k, v))

    def kernel(q, k, v):
        return pa.block_attention(q, k, v, mask, interpret=True)
    text = _primitives(jax.grad(_loss(kernel, w), (0, 1, 2)), qs, ks, vs)
    assert text.count("shard_map") >= 2 and "f32[2,128,128]" in text
    got = jax.jit(kernel)(qs, ks, vs)
    assert got.sharding.is_fully_replicated
    _close(got, kernel(q, k, v), 1e-6, "o")
