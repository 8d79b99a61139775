"""Plain reference for the ``flagship`` adapter: a pre-norm GPT decoder
block stack and its causal language-model loss (Radford et al. 2019; the
widths of Cerebras-GPT, arXiv:2304.03208), with the departures the
configuration file states (RMSNorm, rotary positions, no biases, tanh
gelu), in jax.numpy, float32, matmuls at "highest" precision. Imports
nothing of the program; it reads the program's parameter tree by the
program's names (stacked ``[stage, layer, ...]`` weights, shared arrays).

Each block runs under ``jax.checkpoint`` so that two sequences of 2048
tokens with their float32 ``[B, H, S, S]`` scores fit beside the model on
one chip; that changes what is stored, not what is computed.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from trees import get_leaves, with_leaves

#: How far the program may be from this reference, and why.
#: The program's matmuls take bfloat16 operands (relative rounding 2e-3)
#: and accumulate in float32, its flash kernel and its fused
#: cross-entropy read bfloat16 inputs; the reference is float32
#: throughout. Measured on the chip at the cell's widths over 17 runs, each
#: another seed (PERF.md section 6, PR 23): the loss differs by 3e-7 to
#: 2.2e-5 relative (4096 predicted positions average the rounding out), a
#: gradient leaf by 0.9-1.5% of its L2 norm. The loss bound is a hundred
#: times that and far under what a dropped term does; the gradient bound
#: is four times the worst seen. A dropped
#: term (no rotary, no final norm, an unscaled score) moves the loss by
#: percents and gradients by tens of percent; bfloat16 accumulation over
#: 2048-8192 terms does the same to the gradients: both fail.
TOLERANCE = {"loss_rel": 3e-3, "grad_rel_l2": 6e-2}

RMS_EPS = 1e-6
ROPE_BASE = 10000.0


def _rms_norm(x, g):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True)
                             + RMS_EPS) * g


def _gelu(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        math.sqrt(2.0 / math.pi) * (x + 0.044715 * x ** 3)))


def _rope(x):
    """Rotary embedding, halves layout; x is [B, S, H, D]."""
    half = x.shape[-1] // 2
    freqs = ROPE_BASE ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * freqs[None]
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def block(p, x, n_heads: int):
    b, s, m = x.shape
    h = _rms_norm(x, p["ln1"])
    q, k, v = ((h @ p[n]).reshape(b, s, n_heads, -1)
               for n in ("wq", "wk", "wv"))
    q, k = _rope(q), _rope(k)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) / math.sqrt(q.shape[-1])
    causal = jnp.arange(s)[:, None] >= jnp.arange(s)[None, :]
    scores = jnp.where(causal[None, None], scores, -jnp.inf)
    o = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(scores, -1), v)
    x = x + o.reshape(b, s, -1) @ p["wo"]
    h = _rms_norm(x, p["ln2"])
    return x + _gelu(h @ p["w1"]) @ p["w2"]


def loss(params, batch, n_layers: int, n_heads: int):
    x = params["embed"][batch["tokens"]]
    for i in range(n_layers):
        p = {k: v[0, i] for k, v in params["layers"].items()}
        x = jax.checkpoint(lambda p, x: block(p, x, n_heads))(p, x)
    x = _rms_norm(x, params["ln_f"])
    logits = x @ params["embed"].T
    lse = jax.scipy.special.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(logits, batch["targets"][..., None],
                                 -1)[..., 0]
    return jnp.mean(lse - picked)


def loss_and_grads(params, leaf_specs: dict, batch, sizes: dict):
    """Loss, and its gradients by ``jax.grad`` over the named leaves
    only."""
    @jax.jit
    def fn(leaves, params, batch):
        with jax.default_matmul_precision("highest"):
            return jax.value_and_grad(
                lambda lv: loss(with_leaves(params, leaf_specs, lv), batch,
                                sizes["layers"], sizes["heads"]))(leaves)
    return fn(get_leaves(params, leaf_specs), params, batch)
