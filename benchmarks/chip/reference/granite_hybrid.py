"""Plain reference for the ``granite_hybrid`` adapter: Granite 4.0-H Micro
(``model_type`` ``granitemoehybrid`` with ``num_local_experts`` 0: dense), a
stack of layers of TWO sublayers each, a mixer (nine Mamba-2 mixers of ONE
group to one attention layer without positions) and a SwiGLU FFN, with four
scalar multipliers and a tied table, and the loss its training descends; in
jax.numpy, float32, matmuls at "highest" precision. Imports nothing of the
program; it reads the program's parameter tree by the program's names
(``layers`` a stack a word, ``[stage, block of that word, ...]``: layer l's
mixer is the next block of ``mamba`` or ``attention``, its FFN block ``l`` of
``dense``; matrices stored ``[in, out]``; the convolution's taps ``[tap,
channel]``, the last tap on the current position).

There is no network here, so these are the issue writer's reading of the
catalog's ``config`` and of ``transformers``' ``modeling_granitemoehybrid.py``;
the configuration lists each inference under ``assumed``. With ``x`` ``[S,
M]`` the residual stream, ``e`` = ``embedding_multiplier`` 12, ``r`` =
``residual_multiplier`` 0.22, ``m`` = ``attention_multiplier`` 1 / 64, ``d``
= ``logits_scaling`` 8:

    x_0 = e * E[ids]
    layer l:    x <- x + r * mixer_l(rmsnorm(x; g1_l, eps 1e-5))
                [a | b] = rmsnorm(x; g2_l) W_in          M -> 2 x 8192, no bias
                x <- x + r * (silu(a) * b) W_out         8192 -> M
    logits = rmsnorm(x; g_f) E^T / d                     the tied table

(the tree holds ``W_in``'s halves as ``w1``, the gate, and ``w3``) and the
mean next-token cross-entropy over the vocabulary slice the table holds.

**mamba**, a Mamba-2 mixer on ``u`` ``[S, M]`` (``inner`` = 64 heads x 64 =
4096; ONE group: every head reads the same B and C; state N = 128):

    [z | xBC | dt] = u W_in                 widths inner | inner + 2 N | H, no bias
    xBC = silu(conv1d(xBC))                 causal, depthwise, 4 taps, with bias
    xBC -> x [S, H, P] | B [S, N] | C [S, N]
    D_t = softplus(dt_t + dt_bias)          a head
    A   = -exp(A_log)                       a head, a scalar
    H_t = exp(D_t A) H_{t-1} + D_t x_t (x) B_t      H [P, N] a head
    y_t = H_t C_t + D_h x_t
    y   = rmsnorm(y * silu(z); w)           the gate BEFORE the norm, the norm
                                            over all 4096 channels, eps 1e-5
    out = y W_out                           no bias

The recurrence is computed **step by step** (``lax.scan`` over the positions,
``reference/nemotron_h.py:recurrence``), never in chunks: the program's
chunked form and its kernels are held to it.

**attention**: q ``[S, 32, 64]``, k and v ``[S, 8, 64]``, no biases, **no
positions** (``position_embedding_type`` "nope"), causal, **scores times m =
1 / 64**, not 1 / sqrt(64); query head i reads k/v head ``i // 4``; ``Wo``.

**The share.** The table is rows 0 - 12 543 of 100 352 (chip 0 of eight by
vocabulary parallelism): a sliced vocabulary is a smaller vocabulary, ids
and logits are over the slice. ``forward(.., lookup=)`` takes the ids' rows
from another table than the one the logits are over, for the test that the
eight slices' logits side by side are the uncut model's.

Departures, each one of storage and not of arithmetic: the recurrence runs in
checkpointed segments (``nemotron_h.SCAN_SEGMENT``), attention in blocks of
``ATTENTION_ROWS`` query rows under ``jax.checkpoint``, and every sublayer
under ``jax.checkpoint``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from reference.nemotron_h import _conv, recurrence
from reference.smallthinker import _rms_norm
from trees import get_leaves, with_leaves

#: How far the program may be from this reference, and why. The program's
#: matmuls take bfloat16 operands and accumulate in float32; its residual
#: stream, the convolution's output, the flash kernels and the fused
#: cross-entropy hold bfloat16; the scan's time steps, sums, decays and
#: carried state, the gate + norm and every norm's statistics are float32 on
#: both sides. There is no router: no leaf's gradient hangs on a near-tied
#: choice, so both bounds are far tighter than the expert cells'. Readings on
#: the chip at the cell's widths (PERF.md section 6, PR 49,
#: tools/granite_hybrid_precision.py, six seeds):
#:
#: * the sound program: the loss differs by 3.6e-6 to 1.4e-5 relative; the
#:   named leaves by 1.2-3.3 % of their L2 norm (the table 1.4, the first
#:   Mamba block's in-projection 1.6 and norm weight 1.3, wq / wk and the
#:   last FFN gate 1.8, the last Mamba block's decay rates 1.2-1.8 and
#:   time-step bias 1.8-3.3; 4.3 the worst of some thirty further readings,
#:   the time-step bias again);
#: * the nearest precision below, this reference computed in bfloat16
#:   throughout (the recurrence's decays and state too): the loss differs by
#:   2.5e-3 to 3.3e-3, the worst leaf by 2.8-14.9 %.
#:
#: The loss bound lies between the two readings with room on both sides (7 x
#: the worst sound seed, a twenty-fifth of the best lower-precision one) and
#: is what fails the lower precision on every seed. The gradient bound, 10 %,
#: is 2.3 x the worst sound leaf seen; the lower precision's worst leaf
#: passes under it on five seeds of six, so alone it holds the equations, not
#: the precision. (With the multipliers applied in the activations' bfloat16
#: the loss read 8.2e-5 to 1.1e-4 off on every seed: 0.22 rounds to 0.2197,
#: models/_kinds.py:scaled.) tests/test_granite_hybrid.py holds the program
#: in float32 to this reference at 1e-4, where each multiplier left out or
#: misplaced, 1 / sqrt(D), the norm before the gate, a norm over groups, an
#: untied head and rope each fail, and each stated float32 part in bfloat16.
TOLERANCE = {"loss_rel": 1e-4, "grad_rel_l2": 1e-1}

#: query rows of one checkpointed block of attention
ATTENTION_ROWS = 512


def mamba(p, x, sizes: dict):
    """The mixer's output (without the residual) on the normed ``x``."""
    bsz, s, _ = x.shape
    heads, width, n = (sizes[k] for k in (
        "ssm_heads", "ssm_head_dim", "ssm_state"))
    assert sizes["ssm_groups"] == 1, sizes["ssm_groups"]
    inner = heads * width
    z, xbc, dt = jnp.split(x @ p["ssm_in"], [inner, 2 * inner + 2 * n],
                           axis=-1)
    xbc = jax.nn.silu(_conv(xbc, p["ssm_conv_w"], p["ssm_conv_b"]))
    xs, b, c = jnp.split(xbc, [inner, inner + n], axis=-1)
    xs = xs.reshape(bsz, s, heads, width)
    # ONE group: every head the same B and C
    b, c = (jnp.broadcast_to(v[:, :, None, :], (bsz, s, heads, n))
            for v in (b, c))
    dt = jax.nn.softplus(dt + p["ssm_dt_bias"])
    y = recurrence(xs, dt, -jnp.exp(p["ssm_a_log"]), b, c)
    y = (y + p["ssm_d"][:, None] * xs).reshape(bsz, s, inner)
    y = _rms_norm(y * jax.nn.silu(z), p["ssm_norm"], sizes["norm_eps"])
    return y @ p["ssm_out"]


def _attend(q, k, v, scale: float):
    """q ``[B, S, Hkv, G, D]`` against k, v ``[B, S, Hkv, D]``, causal, no
    positions, ``scores * scale``; a block of query rows at a time."""
    b, s, hkv, g, d = q.shape
    rows = min(ATTENTION_ROWS, s)
    assert s % rows == 0, (s, rows)

    @jax.checkpoint
    def block(args):
        q_rows, t0 = args
        scores = jnp.einsum("bqhgd,bkhd->bhgqk", q_rows, k) * scale
        live = jnp.arange(s)[None, :] <= t0 + jnp.arange(rows)[:, None]
        scores = jnp.where(live, scores, -jnp.inf)
        return jnp.einsum("bhgqk,bkhd->bqhgd", jax.nn.softmax(scores, -1), v)
    blocks = q.reshape(b, s // rows, rows, hkv, g, d).swapaxes(0, 1)
    out = jax.lax.map(block, (blocks, jnp.arange(0, s, rows)))
    return out.swapaxes(0, 1).reshape(b, s, hkv * g * d)


def attention(p, x, sizes: dict):
    """The mixer's output (without the residual) on the normed ``x``."""
    bsz, s, _ = x.shape
    heads, kv_heads, d = sizes["heads"], sizes["kv_heads"], sizes["head_dim"]
    q = (x @ p["wq"]).reshape(bsz, s, kv_heads, heads // kv_heads, d)
    k = (x @ p["wk"]).reshape(bsz, s, kv_heads, d)
    v = (x @ p["wv"]).reshape(bsz, s, kv_heads, d)
    return _attend(q, k, v, sizes["attention_multiplier"]) @ p["wo"]


def ffn(p, x):
    """``(silu(a) * b) W_out`` on the normed ``x``."""
    return (jax.nn.silu(x @ p["w1"]) * (x @ p["w3"])) @ p["w2"]


def _layer(mixer, pm, pf, x, sizes: dict):
    r, eps = sizes["residual_multiplier"], sizes["norm_eps"]
    x = x + r * mixer(pm, _rms_norm(x, pm["ln1"], eps), sizes)
    return x + r * ffn(pf, _rms_norm(x, pf["ln2"], eps))


def hidden(params, tokens, sizes: dict, lookup=None):
    """The final normed state ``[B, S, M]``."""
    table = params["embed"] if lookup is None else lookup
    x = sizes["embedding_multiplier"] * table[tokens]
    seen = {"mamba": 0, "attention": 0}
    for l, kind in enumerate(sizes["layer_types"]):
        pm = {k: v[0, seen[kind]] for k, v in params["layers"][kind].items()}
        pf = {k: v[0, l] for k, v in params["layers"]["dense"].items()}
        mixer = mamba if kind == "mamba" else attention
        x = jax.checkpoint(
            lambda pm, pf, x, m=mixer: _layer(m, pm, pf, x, sizes))(pm, pf, x)
        seen[kind] += 1
    return _rms_norm(x, params["ln_f"], sizes["norm_eps"])


def forward(params, tokens, sizes: dict, lookup=None):
    """Logits ``[B, S, V]`` over the table's rows."""
    return (hidden(params, tokens, sizes, lookup) @ params["embed"].T
            / sizes["logits_scaling"])


def loss(params, batch, sizes: dict):
    logits = forward(params, batch["tokens"], sizes)
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
                                sizes))(leaves)
    return fn(get_leaves(params, leaf_specs), params, batch)
