"""Pallas fused softmax-xent kernel vs the XLA/optax oracle (interpret
mode on the CPU mesh; the real-TPU path is exercised by bench/models)."""

import numpy as np
import pytest
import jax
import jax.numpy as jnp
import optax

from horovod_tpu.ops import pallas_xent as px
from horovod_tpu.ops.pallas_xent import fused_softmax_xent


def _case(n=256, v=1024, seed=0, dtype=jnp.float32):
    rng = np.random.RandomState(seed)
    logits = jnp.asarray(rng.randn(n, v), dtype) * 2.0
    labels = jnp.asarray(rng.randint(0, v, (n,)), jnp.int32)
    return logits, labels


def _oracle(logits, labels):
    return optax.softmax_cross_entropy_with_integer_labels(
        logits.astype(jnp.float32), labels)


def test_fused_xent_matches_oracle():
    logits, labels = _case()
    out = fused_softmax_xent(logits, labels, interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(_oracle(
        logits, labels)), rtol=1e-5, atol=1e-5)


def test_fused_xent_odd_vocab():
    # 30522-style vocab: not a multiple of 128 -> a narrower last piece
    logits, labels = _case(n=128, v=700)
    out = fused_softmax_xent(logits, labels, interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(_oracle(
        logits, labels)), rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("v", [1024, 700])  # 700: a narrower last piece
def test_fused_xent_grads_match_oracle(v):
    logits, labels = _case(n=128, v=v)

    def f_fused(lg):
        return jnp.mean(fused_softmax_xent(lg, labels, interpret=True))

    def f_ref(lg):
        return jnp.mean(_oracle(lg, labels))

    g_fused = jax.grad(f_fused)(logits)
    g_ref = jax.grad(f_ref)(logits)
    assert np.isfinite(np.asarray(g_fused)).all()
    np.testing.assert_allclose(np.asarray(g_fused), np.asarray(g_ref),
                               rtol=1e-4, atol=1e-6)


def test_fused_xent_leading_shape_and_bf16():
    # [B, S, V] logits with bf16 storage: per-token losses keep shape
    logits, labels = _case(n=256, v=512, dtype=jnp.bfloat16)
    logits3 = logits.reshape(2, 128, 512)
    labels3 = labels.reshape(2, 128)
    out = fused_softmax_xent(logits3, labels3, interpret=True)
    assert out.shape == (2, 128)
    ref = _oracle(logits3, labels3)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-2, atol=2e-2)  # bf16 inputs


def test_fused_xent_cpu_fallback_without_interpret():
    # CPU backend without interpret -> XLA fallback, identical numbers
    logits, labels = _case(n=64, v=256)
    out = fused_softmax_xent(logits, labels)
    np.testing.assert_allclose(np.asarray(out), np.asarray(_oracle(
        logits, labels)), rtol=1e-5, atol=1e-5)


def test_fused_xent_out_of_range_label_consistent():
    """Out-of-range labels (ignore-id style) give loss = lse on BOTH the
    kernel and the fallback — a CPU debug run reproduces the TPU loss."""
    logits, labels = _case(n=128, v=512)
    bad = labels.at[0].set(99999).at[1].set(-7)
    out_kernel = fused_softmax_xent(logits, bad, interpret=True)
    out_fb = fused_softmax_xent(logits[:100], bad[:100])  # untiled -> fb
    lse = jax.scipy.special.logsumexp(logits.astype(jnp.float32), -1)
    np.testing.assert_allclose(np.asarray(out_kernel[0]), float(lse[0]),
                               rtol=1e-5)
    np.testing.assert_allclose(np.asarray(out_kernel[1]), float(lse[1]),
                               rtol=1e-5)
    np.testing.assert_allclose(np.asarray(out_fb[:2]),
                               np.asarray(out_kernel[:2]), rtol=1e-5)


def test_fused_xent_untiled_rows_fall_back():
    # n not a multiple of a row tile -> fallback still correct
    logits, labels = _case(n=37, v=512)
    out = fused_softmax_xent(logits, labels, interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(_oracle(
        logits, labels)), rtol=1e-5, atol=1e-5)


# (rows, vocab, dtype, chunk): vocabularies that are multiples of 128
# (393 * 128 = 50304 scaled down) and that are not (1000; k * 128 + 81 like
# 50257), below one chunk and over several, so that the loop, the whole
# pieces after it and the narrower last piece all run; chunk None is the
# shape's own
SHAPES = [
    (64, 1000, jnp.float32, None),
    (64, 1000, jnp.float32, 256),
    (32, 3 * 128 + 81, jnp.bfloat16, 128),
    (32, 9 * 128, jnp.bfloat16, 512),
    (16, 2 * 4096 + 2 * 512 + 81, jnp.bfloat16, None),
    (8, 4096 + 1024, jnp.float32, None),
]
_IDS = [f"{n}x{v}-{jnp.dtype(d).name}-chunk{c}" for n, v, d, c in SHAPES]


def _weights_and_labels(n, v, labels):
    """Per-row cotangents with zeros among them, and labels with both
    kinds of out-of-range id and the last column among them."""
    w = jnp.asarray(np.random.RandomState(1).rand(n) + 0.5, jnp.float32)
    w = w.at[3].set(0.0).at[n - 1].set(0.0)
    return w, labels.at[0].set(v + 5).at[1].set(-7).at[2].set(v - 1)


def _f32(x):
    return np.asarray(x.astype(jnp.float32))


@pytest.mark.parametrize("n,v,dtype,chunk", SHAPES, ids=_IDS)
def test_kernel_loss_and_gradient_match_xla(n, v, dtype, chunk):
    """Loss and d(loss . w)/dlogits against ``_xla_xent`` under a
    non-uniform cotangent: where ``g`` is applied is tested, not only
    ``softmax - onehot``."""
    logits, labels = _case(n=n, v=v, dtype=dtype)
    w, labels = _weights_and_labels(n, v, labels)
    assert px.xent_path(n, v, dtype, interpret=True)[0] == "kernel"

    def kernel(lg):
        return fused_softmax_xent(lg, labels, chunk=chunk, interpret=True)

    np.testing.assert_allclose(_f32(kernel(logits)),
                               _f32(px._xla_xent(logits, labels)),
                               rtol=1e-5, atol=1e-5)
    got = jax.grad(lambda lg: (kernel(lg) * w).sum())(logits)
    want = jax.grad(lambda lg: (px._xla_xent(lg, labels) * w).sum())(logits)
    assert got.dtype == logits.dtype
    # bf16: both round a float32 gradient of magnitude <= 1.5 (one ulp)
    atol = 1e-6 if dtype == jnp.float32 else 1e-2
    np.testing.assert_allclose(_f32(got), _f32(want), rtol=1e-4, atol=atol)
    assert not _f32(got)[3].any() and not _f32(got)[n - 1].any()


@pytest.mark.parametrize("n,v,dtype,chunk", SHAPES, ids=_IDS)
@pytest.mark.parametrize("tied", [False, True])
def test_head_form_matches_autodiff(n, v, dtype, chunk, tied):
    """``head_softmax_xent(x, w, labels)``: the loss, dx and dw against
    ``jax.grad`` of ``x @ w`` -> ``_xla_xent``, the head given as
    ``[M, V]`` or as a tied table's transpose."""
    rng = np.random.RandomState(2)
    m = 48
    x = jnp.asarray(rng.randn(n, m), dtype)
    table = jnp.asarray(rng.randn(*((v, m) if tied else (m, v))) * 0.3, dtype)
    _, labels = _case(n=n, v=v)
    w, labels = _weights_and_labels(n, v, labels)

    def head(t):
        return t.T if tied else t

    def kernel(x, t):
        return (px.head_softmax_xent(x, head(t), labels, chunk=chunk,
                                     interpret=True) * w).sum()

    def reference(x, t):
        return (px._xla_xent(x @ head(t), labels) * w).sum()

    tol = 1e-5 if dtype == jnp.float32 else 1e-2
    np.testing.assert_allclose(float(kernel(x, table)),
                               float(reference(x, table)), rtol=tol)
    got = jax.grad(kernel, (0, 1))(x, table)
    want = jax.grad(reference, (0, 1))(x, table)
    for g, r in zip(got, want):
        assert g.shape == r.shape and g.dtype == r.dtype
        err = np.linalg.norm(_f32(g) - _f32(r)) / np.linalg.norm(_f32(r))
        assert err < tol, err
    assert not _f32(got[0])[3].any()        # a zero cotangent's row of dx


def test_head_form_leading_shape_and_fallback():
    """``[B, S, M]`` activations keep their leading shape; off the TPU
    without interpret the head form is autodiff through ``x @ w``."""
    rng = np.random.RandomState(3)
    x = jnp.asarray(rng.randn(2, 16, 24), jnp.float32)
    w = jnp.asarray(rng.randn(24, 300), jnp.float32)
    labels = jnp.asarray(rng.randint(0, 300, (2, 16)), jnp.int32)
    want = px._xla_xent(x @ w, labels)
    for interpret in (True, False):
        got = px.head_softmax_xent(x, w, labels, interpret=interpret)
        assert got.shape == (2, 16)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-5, atol=1e-5)


def test_forward_alone_writes_no_gradient():
    """Not differentiated, the call's only result is the per-row loss
    (sweeps 1 and 2, O(N) bytes written); differentiated, the gradient
    comes out of the same call, in the logits' shape and dtype."""
    import re
    logits, labels = _case(n=64, v=1000)

    def results(fn):
        text = str(jax.make_jaxpr(fn)(logits))
        calls = re.findall(r"((?:\w+:\S+ )+)= pallas_call", text)
        assert len(calls) == 1, text
        return re.findall(r":(\S+)", calls[0])

    def loss(lg):
        return fused_softmax_xent(lg, labels, interpret=True).sum()

    assert results(loss) == ["f32[64,1]"]
    assert results(jax.grad(loss)) == ["f32[64,1]", "f32[64,1000]"]


@pytest.mark.parametrize("n,v,dtype,path,bn,steps", [
    # the two flagship cells: gpt-1.3b-widths.s2048, olmoe-1b-7b.s4096
    (4096, 50257, jnp.bfloat16, "kernel", 16, 256),
    (8192, 50304, jnp.bfloat16, "kernel", 16, 512),
    # a shorter row: more rows a step; float32 rows tile by 8
    (4096, 30522, jnp.bfloat16, "kernel", 32, 128),
    (1024, 50257, jnp.float32, "kernel", 8, 128),
    # a row longer than a block asks for: the fewest rows that tile
    (2048, 262144, jnp.bfloat16, "kernel", 16, 128),
    # rows that do not tile (bf16 packs 16 a tile), a row VMEM cannot hold
    (100, 50257, jnp.float32, "xla", None, None),
    (4104, 50257, jnp.bfloat16, "xla", None, None),
    (1024, 600000, jnp.bfloat16, "xla", None, None),
])
def test_xent_path_from_the_shape(monkeypatch, n, v, dtype, path, bn, steps):
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    got, detail = px.xent_path(n, v, dtype)
    assert got == path, detail
    if path == "kernel":
        assert px.xent_blocks(n, v, dtype) == (bn, px.CHUNK)
        assert detail == (f"{bn} rows x {px.CHUNK}-column chunks, "
                          f"{steps} steps")
        assert px.xent_vmem_bytes(bn, v, jnp.dtype(dtype).itemsize) \
            <= px.VMEM_MOST
    else:
        assert "no row block" in detail
        with pytest.raises(ValueError):
            px.xent_blocks(n, v, dtype)


def test_xent_path_off_the_tpu():
    assert px.xent_path(4096, 50257, jnp.bfloat16) == ("xla", "off the TPU")
    assert px.xent_path(4096, 50257, jnp.bfloat16,
                        interpret=True)[0] == "kernel"


@pytest.mark.parametrize("tied", [True, False], ids=["tied", "lm_head"])
@pytest.mark.parametrize("layout", [dict(dp=1), dict(dp=2, sp=2)],
                         ids=["one_device", "dp2_sp2"])
def test_flagship_grads_on_the_kernel_path(monkeypatch, tied, layout):
    """``make_grad_fn``'s loss and gradients with the head on the kernel
    (interpret mode; head_dim 16 keeps attention on XLA) against the XLA
    head: the tied table transposed and an ``lm_head`` of its own, whole
    and with the rows split over dp x sp inside ``shard_map``."""
    from horovod_tpu.models import (TransformerConfig, init_params,
                                    shard_batch, shard_params)
    from horovod_tpu.models.transformer import make_grad_fn
    from horovod_tpu.parallel import build_mesh

    cfg = TransformerConfig(vocab_size=3 * 128 + 81, d_model=32, n_heads=2,
                            n_layers=1, d_ff=64, max_seq=32,
                            tie_embeddings=tied, dtype=jnp.float32,
                            remat=False)
    mesh = build_mesh(devices=jax.devices()[:int(np.prod(list(
        layout.values())))], **layout)
    params = shard_params(init_params(np.random.RandomState(0), cfg), cfg,
                          mesh)
    tokens = np.random.RandomState(1).randint(
        0, cfg.vocab_size, (4, 32)).astype(np.int32)
    tokens, targets = shard_batch(tokens, np.roll(tokens, -1, 1), mesh)

    want = jax.jit(make_grad_fn(cfg, mesh))(params, tokens, targets)
    calls = []

    def on_kernel(x, w, labels):
        calls.append(x.shape)
        assert px.xent_path(x.shape[0] * x.shape[1], w.shape[1], x.dtype,
                            interpret=True)[0] == "kernel"
        return head(x, w, labels, interpret=True)

    head = px.head_softmax_xent
    monkeypatch.setattr(px, "head_softmax_xent", on_kernel)
    got = jax.jit(make_grad_fn(cfg, mesh))(params, tokens, targets)
    assert calls
    np.testing.assert_allclose(float(got[0]), float(want[0]), rtol=1e-6)
    for g, r in zip(jax.tree_util.tree_leaves(got[2]),
                    jax.tree_util.tree_leaves(want[2])):
        np.testing.assert_allclose(np.asarray(g), np.asarray(r),
                                   rtol=1e-4, atol=1e-6)
