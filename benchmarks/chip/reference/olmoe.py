"""Plain reference for the ``olmoe`` adapter: OLMoE's decoder (arXiv:2409.02060;
the layer's equations as transformers 4.57's ``models/olmoe/modeling_olmoe.py``
states them) and the loss its training descends, in jax.numpy, float32,
matmuls at "highest" precision. Imports nothing of the program; it reads the
program's parameter tree by the program's names (stacked ``[stage, layer,
...]`` weights; ``we1`` an expert's gate, ``we3`` its up projection, ``we2``
its way down; matrices stored ``[in, out]``).

A block: ``x += wo(attention(rope(q_norm(wq h)), rope(k_norm(wk h)), wv h))``
with ``h = rmsnorm(x)`` and the two norms over the whole projection width
before the heads are split; then ``x += sum_e c_e(h) * down_e(silu(gate_e(h))
* up_e(h))`` with ``h = rmsnorm(x)``, where ``c_e(h)`` is the float32 softmax
probability of expert e if e is among the token's top-k and 0 otherwise
(divided by the top-k's sum if ``norm_topk_prob``). The experts are computed
the plain way: every expert on every token, masked by the choice. The loss:
mean next-token cross-entropy + 0.01 x the load-balancing loss + 0.001 x the
router z-loss, both averaged over the layers.

Departures from the source, each noted where it is made: the load-balancing
loss is taken per layer and averaged (the paper), where transformers
concatenates the layers first (equal at one layer); the z-loss is the
paper's and is not in transformers' file; the blocks and the loop over the
experts run under ``jax.checkpoint`` so that two sequences of 4096 tokens
fit beside the model on one chip, which changes what is stored, not what is
computed.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from trees import get_leaves, with_leaves

#: How far the program may be from this reference, and why.
#: The program's matmuls take bfloat16 operands (relative rounding 2e-3) and
#: accumulate in float32; its residual stream, flash kernel and fused
#: cross-entropy hold bfloat16; the router's logits, softmax, top-k weights
#: and the combine are float32 on both sides. One hazard is the model's own:
#: the router's input is the bfloat16 residual, so a token whose 8th and 9th
#: probabilities lie within that rounding picks another expert than here.
#: Measured on the chip at the cell's widths (PERF.md section 6, PR 26;
#: tools/olmoe_routing.py, three seeds, and eleven runs of the cell): 0.63-
#: 0.74% of the 65 536 (token, slot) assignments differ (5.0-5.9% of tokens
#: have one). The loss differs by 2e-6 to 1.4e-4 relative; a gradient leaf
#: by 5.6-8.4% of its L2 norm (lm_head 5.6-6.1, first_query 6.5-7.0, the
#: experts' down matrices 6.5-6.9, the router 7.1-8.4). Against this
#: reference forced to the program's choices (``loss_and_grads(..,
#: choices=..)``) the same leaves are 1.2-1.5% off, the flagship cell's
#: rounding: the differing choices explain four fifths of the error. Such a
#: token swaps its weakest expert for one of nearly the same weight, so the
#: loss barely moves and every leaf sees it, directly or through the
#: residual. The gradient bound is twice the worst seen, the loss bound
#: twenty times. Both stay far under what a wrong term does: tests/
#: test_olmoe.py holds the program in float32 to this reference at 1e-4,
#: where a bfloat16 router softmax, bfloat16 top-k weights, a bfloat16
#: combine or one dropped assignment each fail; a missing QK-norm moves
#: first_query by more than 20% (benchmarks/chip/tests/test_olmoe.py).
TOLERANCE = {"loss_rel": 3e-3, "grad_rel_l2": 1.5e-1}


def _rms_norm(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True)
                             + eps) * g


def _rope(x, theta):
    """Rotary embedding, halves layout (rotate_half); x is [B, S, H, D]."""
    half = x.shape[-1] // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * freqs[None]
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def attention(p, x, sizes: dict):
    b, s, _ = x.shape
    h = _rms_norm(x, p["ln1"], sizes["norm_eps"])
    # QK-norm over the whole projection, then the heads, then rope
    q = _rms_norm(h @ p["wq"], p["q_norm"], sizes["norm_eps"])
    k = _rms_norm(h @ p["wk"], p["k_norm"], sizes["norm_eps"])
    q, k, v = (t.reshape(b, s, sizes["heads"], -1)
               for t in (q, k, h @ p["wv"]))
    q, k = _rope(q, sizes["rope_theta"]), _rope(k, sizes["rope_theta"])
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) / math.sqrt(q.shape[-1])
    causal = jnp.arange(s)[:, None] >= jnp.arange(s)[None, :]
    scores = jnp.where(causal[None, None], scores, -jnp.inf)
    o = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(scores, -1), v)
    return x + o.reshape(b, s, -1) @ p["wo"]


def route(logits, sizes: dict, choice=None):
    """(probabilities [T, E], chosen experts [T, k], combine weights [T, E]:
    the probability of a chosen expert, 0 elsewhere). ``choice`` forces the
    chosen experts (to tell what differing choices explain)."""
    probs = jax.nn.softmax(logits, axis=-1)
    if choice is None:
        _, choice = jax.lax.top_k(probs, sizes["experts_per_token"])
    chosen = jnp.sum(jax.nn.one_hot(choice, sizes["experts"],
                                    dtype=probs.dtype), axis=1)
    combine = probs * chosen
    if sizes["norm_topk_prob"]:
        combine = combine / jnp.sum(combine, axis=-1, keepdims=True)
    return probs, choice, combine


def experts(p, h, combine):
    """``sum_e combine[:, e] * down_e(silu(gate_e(h)) * up_e(h))``: every
    expert on every token, one expert at a time."""
    def one(y, expert):
        gate, up, down, c = expert
        out = (jax.nn.silu(h @ gate) * (h @ up)) @ down
        return y + c[:, None] * out, None
    y, _ = jax.lax.scan(jax.checkpoint(one), jnp.zeros_like(h),
                        (p["we1"], p["we3"], p["we2"], combine.T))
    return y


def moe(p, x, sizes: dict, choice=None):
    """The expert layer on [B, S, M]; returns the new residual, the layer's
    load-balancing loss, its z-loss and the chosen experts."""
    b, s, m = x.shape
    h = _rms_norm(x, p["ln2"], sizes["norm_eps"]).reshape(b * s, m)
    logits = h @ p["router"]
    probs, choice, combine = route(logits, sizes, choice)
    # f_e: share of tokens that chose e among their k (sums to k); P_e: mean
    # router probability
    f = jnp.mean(jnp.sum(jax.nn.one_hot(choice, sizes["experts"]), axis=1),
                 axis=0)
    balance = sizes["experts"] * jnp.sum(f * jnp.mean(probs, axis=0))
    z = jnp.mean(jnp.square(jax.scipy.special.logsumexp(logits, axis=-1)))
    return x + experts(p, h, combine).reshape(b, s, m), balance, z, choice


def forward(params, tokens, sizes: dict, choices=None):
    """Logits [B, S, V], the two auxiliary losses averaged over the layers,
    and every layer's chosen experts [L, T, k]."""
    x = params["embed"][tokens]
    balance, z, chosen = 0.0, 0.0, []
    for i in range(sizes["layers"]):
        p = {k: v[0, i] for k, v in params["layers"].items()}
        x = jax.checkpoint(lambda p, x: attention(p, x, sizes))(p, x)
        x, b_i, z_i, c_i = jax.checkpoint(
            lambda p, x, c: moe(p, x, sizes, c))(
                p, x, None if choices is None else choices[i])
        balance, z = balance + b_i, z + z_i
        chosen.append(c_i)
    x = _rms_norm(x, params["ln_f"], sizes["norm_eps"])
    return (x @ params["lm_head"], balance / sizes["layers"],
            z / sizes["layers"], jnp.stack(chosen))


def losses(params, batch, sizes: dict, choices=None):
    """(total, cross-entropy, load-balancing loss, z-loss, choices)."""
    logits, balance, z, chosen = forward(params, batch["tokens"], sizes,
                                         choices)
    lse = jax.scipy.special.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(logits, batch["targets"][..., None],
                                 -1)[..., 0]
    xent = jnp.mean(lse - picked)
    total = (xent + sizes["balance_weight"] * balance
             + sizes["z_weight"] * z)
    return total, xent, balance, z, chosen


def loss_and_grads(params, leaf_specs: dict, batch, sizes: dict,
                   choices=None):
    """Loss, and its gradients by ``jax.grad`` over the named leaves
    only."""
    @jax.jit
    def fn(leaves, params, batch, choices):
        with jax.default_matmul_precision("highest"):
            return jax.value_and_grad(
                lambda lv: losses(with_leaves(params, leaf_specs, lv),
                                  batch, sizes, choices)[0])(leaves)
    return fn(get_leaves(params, leaf_specs), params, batch, choices)
