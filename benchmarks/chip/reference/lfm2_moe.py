"""Plain reference for the ``lfm2_moe`` adapter: LFM2-24B-A2B's layers as its
``config.json`` (``model_type`` ``lfm2_moe``) gives them, and the loss its
training descends, in jax.numpy, float32, matmuls at "highest" precision.
Imports nothing of the program; it reads the program's parameter tree by
the program's names, through ``sizes["layer_places"]`` (the adapter's: where
each layer's mixer block and FFN block lie in ``lead`` and ``layers``;
matrices stored ``[in, out]``; ``we1`` / ``w1`` a gate, ``we3`` / ``w3`` an
up projection, ``we2`` / ``w2`` the way down).

There is no network here, so these are the issue writer's reading of
``transformers``' ``lfm2_moe``; the configuration lists each inference under
``assumed``.

**Layer** l, ``x`` ``[S, M]`` the residual stream (M 2048, eps 1e-5 in every
norm, no biases):

    x <- x + mixer_l(rmsnorm(x))
    x <- x + ffn_l(rmsnorm(x))

and after the last layer ``rmsnorm`` and the tied head: the embedding table's
rows held here, transposed. ``layer_types[l]`` says the mixer, the first
``num_dense_layers`` layers have the dense FFN and every later one experts.

**conv mixer** on ``h`` ``[S, M]`` (``conv_L_cache`` K = 3 taps, no bias, no
activation):

    [B | C | u] = h W_in                     W_in [M, 3 M], the thirds in that order
    z    = B * u
    c[t] = sum_{j=0..K-1} w[j] * z[t - (K - 1) + j]      depthwise, causal, zeros before the start
    out  = (C * c) W_out                     W_out [M, M]

**full_attention mixer**: 32 query heads on 8 key/value heads of D = 64
(query head i reads k/v head ``i // 4``):

    q = h W_q -> [S, 32, D]     k = h W_k, v = h W_v -> [S, 8, D]
    q, k = rmsnorm_D(q) g_q, rmsnorm_D(k) g_k        over each head's 64 channels; one weight [D] for all q heads, one for all k heads
    q, k = rot(q), rot(k)                    whole head, halves layout, theta 1e6
    a_i = softmax_{j <= t}(q_i k^T / sqrt(D)) v
    out = concat_i(a_i) W_o

**Dense FFN**: ``(silu(h W_1) * (h W_3)) W_2``, 2048 -> 11 776 -> 2048.

**Expert layer** on ``h`` ``[T, M]``:

    s   = sigmoid(h W_r)                    float32, all E = 64 experts
    idx = top-4 of s + b                    b: the expert bias, a buffer (no gradient); ties to the lower index
    w   = 1 * s[idx] / sum(s[idx])          the UNBIASED scores (norm_topk_prob, routed_scaling_factor 1)
    y   = sum_{e in idx, e held here} w_e (silu(h W1_e) * (h W3_e)) W2_e      2048 -> 1536 -> 2048

**The share.** The tree holds the experts ``[first, first + held)`` of every
expert layer (``sizes["first_expert"]``, ``sizes["held_experts"]``) and a
slice of the vocabulary; the router scores all ``E``. What the absent
experts would have added is left out here as in the program, and that
partial result goes on to the next layer (model-configs guide, section 4).

Departures, each one of storage and not of arithmetic: attention in blocks
of ``ATTENTION_ROWS`` query rows, the dense FFN in blocks of ``FFN_ROWS``
tokens and the loop over the held experts under ``jax.checkpoint``, and
every block under ``jax.checkpoint``. The sigmoid router
(``reference/nemotron_h.py:route``: scores, top-k of score + bias, ``scale *
s / sum s``), the loop over the held SiLU-gated experts
(``reference/olmoe.py:experts``), the blocked causal attention over grouped
heads (``reference/laguna.py:_attend``) and the norm are the other
references' functions, as ``reference/laguna.py`` takes them.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from reference.laguna import _attend, _block_params
from reference.nemotron_h import route
from reference.olmoe import experts as routed
from reference.smallthinker import _rms_norm
from trees import get_leaves, with_leaves

#: How far the program may be from this reference, and why.
#: The program's matmuls take bfloat16 operands and accumulate in float32;
#: its residual stream, the in-projection's thirds, the flash kernels and the
#: fused cross-entropy hold bfloat16; the mixer's chain ``C * conv(B * u)``,
#: the heads' norms, the router's logits (a float32 product in fact:
#: "highest"), scores, top-k weights, the combine and the per-token loss are
#: float32 on both sides. As in the other expert cells a token whose 4th and
#: 5th scores lie within the rounding of the normed tokens picks another
#: expert than here, and this chip holds ~1024 rows an expert, so a differing
#: row is a visible part of a held expert's gradient; with the tied table at
#: its natural scale (std 0.02: ``assumed.init``) the tokens' states lie
#: closer together than in the cells whose table is drawn wide, and more
#: choices are near ties. Both bounds come from readings on the chip at the
#: cell's widths and 2 x 8192 positions (PERF.md section 6, PR 55):
#:
#: * the sound program, 15 seeds at the configuration's init (9 runs of the
#:   cell: ``correct``'s own numbers; 6 of tools/lfm2_moe_precision.py): the
#:   loss differs by 5.2e-6 to 6.9e-5 relative; the leaves no choice reaches
#:   directly by 6.7-9.8 % of their L2 norm, the held experts' down matrices
#:   by 23.9-25.8 %, the last router by 32.6-35.2 %;
#: * the nearest precision below, 6 seeds (tools/lfm2_moe_precision.py):
#:   this reference computed in bfloat16 throughout differs in the loss by
#:   1.6e-3 to 2.4e-3, and in its worst gradient leaf by 38.3-41.4 %: its own
#:   routers' near-ties, hardly more than the sound program's.
#:
#: The loss bound, 3e-4, lies between the two readings with room on both
#: sides (4.3 x the worst sound seed, 5.5 x under the best lower-precision
#: one) and is what fails the lower precision. The gradient bound, 50 %, has
#: to admit the near-tied choices and is 1.42 x the worst leaf seen (the 15
#: seeds' worst leaves lie within 0.326-0.352); it does not tell the two
#: precisions apart, nor does it have to (one of a cell's limits does):
#: tests/test_lfm2_moe.py holds the program in float32 to this reference at
#: 1e-4 on 27 leaves, where each of twenty-nine wrong readings of the
#: equations above fails.
TOLERANCE = {"loss_rel": 3e-4, "grad_rel_l2": 5e-1}

#: tokens of one block of the dense FFN: the float32 hidden rows of a block
#: are ``[FFN_ROWS, 11 776]`` three times over (193 MB each)
FFN_ROWS = 4096


def _rotate(x, theta: float):
    """Rotary embedding over the whole head, halves layout (rotate_half);
    x ``[B, S, H, D]``."""
    half = x.shape[-1] // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * freqs[None]
    cos = jnp.cos(ang)[None, :, None].astype(x.dtype)
    sin = jnp.sin(ang)[None, :, None].astype(x.dtype)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def short_conv(p, h):
    """The conv mixer on normed tokens ``h`` ``[B, S, M]``."""
    gate_b, gate_c, u = jnp.split(h @ p["conv_in"], 3, axis=-1)
    z = gate_b * u
    taps, s = p["conv_w"].shape[0], h.shape[1]
    padded = jnp.pad(z, ((0, 0), (taps - 1, 0), (0, 0)))
    c = sum(p["conv_w"][j] * padded[:, j:j + s] for j in range(taps))
    return (gate_c * c) @ p["conv_out"]


def conv_block(p, x, sizes: dict):
    return x + short_conv(p, _rms_norm(x, p["ln1"], sizes["norm_eps"]))


def attention(p, x, sizes: dict):
    b, s, _ = x.shape
    heads, kv_heads, d = sizes["heads"], sizes["kv_heads"], sizes["head_dim"]
    eps = sizes["norm_eps"]
    h = _rms_norm(x, p["ln1"], eps)
    q = _rms_norm((h @ p["wq"]).reshape(b, s, heads, d), p["q_norm"], eps)
    k = _rms_norm((h @ p["wk"]).reshape(b, s, kv_heads, d), p["k_norm"], eps)
    v = (h @ p["wv"]).reshape(b, s, kv_heads, d)
    q, k = _rotate(q, sizes["rope_theta"]), _rotate(k, sizes["rope_theta"])
    o = _attend(q.reshape(b, s, kv_heads, heads // kv_heads, d), k, v, None)
    return x + o.reshape(b, s, heads * d) @ p["wo"]


def dense_ffn(p, x, sizes: dict):
    b, s, m = x.shape
    h = _rms_norm(x, p["ln2"], sizes["norm_eps"]).reshape(b * s, m)
    rows = min(FFN_ROWS, b * s)
    assert (b * s) % rows == 0, (b, s, rows)

    @jax.checkpoint
    def block(h):
        return (jax.nn.silu(h @ p["w1"]) * (h @ p["w3"])) @ p["w2"]
    y = jax.lax.map(block, h.reshape(-1, rows, m))
    return x + y.reshape(b, s, m)


def expert_layer(p, h, sizes: dict, choice=None):
    """The expert layer on normed tokens ``[T, M]``: the held experts' part
    of it, and the chosen experts."""
    choice, combine = route(h @ p["router"], p["router_bias"], sizes, choice)
    first, held = sizes["first_expert"], sizes["held_experts"]
    return routed(p, h, combine[:, first:first + held]), choice


def experts(p, x, sizes: dict, choice=None):
    b, s, m = x.shape
    h = _rms_norm(x, p["ln2"], sizes["norm_eps"]).reshape(b * s, m)
    y, choice = expert_layer(p, h, sizes, choice)
    return x + y.reshape(b, s, m), choice


MIXERS = {"conv": conv_block, "full_attention": attention}


def forward(params, tokens, sizes: dict, choices=None):
    """Logits ``[B, S, V]`` and the expert layers' chosen experts ``[expert
    layers, T, k]``."""
    x = params["embed"][tokens]
    chosen = []
    for i, (mixer_at, ffn_at) in enumerate(sizes["layer_places"]):
        mixer = MIXERS[sizes["layer_types"][i]]
        x = jax.checkpoint(lambda p, x, mixer=mixer: mixer(p, x, sizes))(
            _block_params(params, mixer_at), x)
        p = _block_params(params, ffn_at)
        if sizes["layer_dense"][i]:
            x = jax.checkpoint(lambda p, x: dense_ffn(p, x, sizes))(p, x)
        else:
            x, c = jax.checkpoint(lambda p, x, c: experts(p, x, sizes, c))(
                p, x, None if choices is None else choices[len(chosen)])
            chosen.append(c)
    logits = _rms_norm(x, params["ln_f"], sizes["norm_eps"]) \
        @ params["embed"].T
    return logits, jnp.stack(chosen)


def losses(params, batch, sizes: dict, choices=None):
    """(total, cross-entropy, load-balancing loss: none, 0.0, z-loss: none,
    0.0, the expert layers' choices): the tuple tools/olmoe_routing.py
    reads."""
    logits, chosen = forward(params, batch["tokens"], sizes, choices)
    lse = jax.scipy.special.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(logits, batch["targets"][..., None],
                                 -1)[..., 0]
    xent = jnp.mean(lse - picked)
    return xent, xent, 0.0, 0.0, chosen


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
