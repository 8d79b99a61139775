"""What the flash-attention kernels' test files share (a plain module, as
``tests/arch.py`` is; pytest collects nothing here): seeded operands, the XLA
oracles, and ``one_trace``, a side's outputs and gradients from one traced
and compiled program."""

import numpy as np
import jax
import jax.numpy as jnp

from horovod_tpu.ops import pallas_attention as pa
from horovod_tpu.ops.pallas_attention import flash_attention_tpu
from horovod_tpu.parallel.ring_attention import _plain_attention


#: the lengths the tile rules are swept over
LENGTHS = [128, 256, 384, 512, 640, 1024, 1536, 2048, 4096, 8192]


def qkv(B=2, S=256, H=2, D=128, seed=0, Sk=None, dtype=jnp.float32):
    rng = np.random.RandomState(seed)
    mk = lambda s: (jnp.asarray(rng.randn(B, s, H, D), jnp.float32)
                    * 0.3).astype(dtype)
    return mk(S), mk(Sk or S), mk(Sk or S)


def heads(B, S, H, Hkv, D, seed=50):
    """q of ``H`` heads on k, v of ``Hkv``."""
    rng = np.random.RandomState(seed)

    def mk(n):
        return jnp.asarray(rng.randn(B, S, n, D) * 0.5, jnp.float32)
    return mk(H), mk(Hkv), mk(Hkv)


def assert_forward(q, k, v, causal, rtol=1e-5, atol=1e-5, **blocks):
    out = flash_attention_tpu(q, k, v, causal=causal, interpret=True,
                              **blocks)
    ref = _plain_attention(*(x.astype(jnp.float32) for x in (q, k, v)),
                           causal=causal)
    assert out.dtype == q.dtype
    np.testing.assert_allclose(np.asarray(out.astype(jnp.float32)),
                               np.asarray(ref), rtol=rtol, atol=atol)



def assert_grads(q, k, v, causal, cotangent, **blocks):
    """The custom-VJP backward (blockwise recompute from lse) must agree
    with autodiff through the XLA oracle — the kernel is used in training
    forwards, so its gradient is load-bearing."""
    def loss_flash(q, k, v):
        return cotangent(flash_attention_tpu(q, k, v, causal=causal,
                                             interpret=True, **blocks))

    def loss_ref(q, k, v):
        return cotangent(_plain_attention(q, k, v, causal=causal))

    g_flash = jax.jit(jax.grad(loss_flash, argnums=(0, 1, 2)))(q, k, v)
    g_ref = jax.jit(jax.grad(loss_ref, argnums=(0, 1, 2)))(q, k, v)
    for gf, gr, name in zip(g_flash, g_ref, "qkv"):
        np.testing.assert_allclose(np.asarray(gf), np.asarray(gr),
                                   rtol=2e-4, atol=2e-4, err_msg=name)


def cos_cotangent(o):
    return jnp.sum(o * jnp.cos(o))   # non-trivial cotangent



def backward(q, k, v, causal, cotangent, blocks=None, lse_weight=None):
    """(dq, dk, dv) from ``flash_backward`` on the forward kernel's own
    residuals, and the oracle's by autodiff; with ``lse_weight`` the
    loss also reads the log-sum-exp (ring attention's merge does)."""
    scale = q.shape[-1] ** -0.5
    o, lse = pa.flash_attention_with_lse(q, k, v, causal, interpret=True)

    def loss(o, lse):
        extra = 0.0 if lse_weight is None else jnp.sum(lse * lse_weight)
        return cotangent(o) + extra
    do, dlse = jax.grad(loss, (0, 1))(o.astype(jnp.float32), lse)
    got = pa.flash_backward(q, k, v, o, lse, do.astype(q.dtype), dlse,
                            causal, scale, blocks=blocks, interpret=True)

    def oracle(q, k, v):
        B, Sq, H, _D = q.shape
        s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
        if causal:
            s = jnp.where(jnp.tril(jnp.ones(s.shape[-2:], bool)), s,
                          -jnp.inf)
        o = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1), v)
        return loss(o, jax.nn.logsumexp(s, -1).reshape(B * H, Sq))
    want = jax.jit(jax.grad(oracle, (0, 1, 2)))(
        *(x.astype(jnp.float32) for x in (q, k, v)))
    return got, want


def assert_backward(got, want, tol=2e-4):
    for g, w, name in zip(got, want, ("dq", "dk", "dv")):
        assert g.dtype == got[0].dtype
        np.testing.assert_allclose(np.asarray(g.astype(jnp.float32)),
                                   np.asarray(w), rtol=tol, atol=tol,
                                   err_msg=name)


def banded_lse(q, k, window, scale):
    """A row's log-partition over its live keys, ``[B * H, S]`` float32."""
    B, S, H, D = q.shape
    Hkv = k.shape[2]
    s = jnp.einsum("bqhgd,bkhd->bhgqk",
                   q.reshape(B, S, Hkv, H // Hkv, D), k) * scale
    t, j = jnp.arange(S)[:, None], jnp.arange(S)[None, :]
    live = j <= t
    if window is not None:
        live = jnp.logical_and(live, j > t - window)
    return jax.nn.logsumexp(jnp.where(live, s, -jnp.inf), axis=-1).reshape(
        B * H, S)


def one_trace(f, q, k, v, w, u):
    """``(o, lse), (dq, dk, dv)`` of ``f(q, k, v) -> (o, lse)`` under the loss
    ``sum(o w) + sum(lse u)``, which reads both: one program, traced and
    compiled once (an interpret-mode case is its traces and compiles)."""
    def total(q, k, v):
        o, lse = f(q, k, v)
        return jnp.sum(o * w) + jnp.sum(lse * u), (o, lse)
    (_, out), grads = jax.jit(jax.value_and_grad(
        total, (0, 1, 2), has_aux=True))(q, k, v)
    return out, grads


def weights(q, rows):
    """The cotangents of ``one_trace``'s loss: ``w`` as ``q``, ``u [rows,
    S]``."""
    S = q.shape[1]
    return (jnp.cos(jnp.arange(q.size, dtype=jnp.float32).reshape(q.shape)),
            jnp.sin(jnp.arange(rows * S, dtype=jnp.float32).reshape(rows, S)))
