"""Plain reference for the ``glm4_moe_lite`` adapter: latent attention (MLA),
one dense layer leading a stack of expert layers, and a multi-token-
prediction module on the shared head, as GLM-4.7-Flash's ``config.json``
(``model_type`` ``glm4_moe_lite``) gives them, and the loss its training
descends, in jax.numpy, float32, matmuls at "highest" precision. Imports
nothing of the program; it reads the program's parameter tree by the
program's names (``lead`` and ``mtp["layers"]`` a stack a word ``[block,
...]``, ``layers`` a stack a word ``[stage, block, ...]``; matrices stored
``[in, out]``; ``we1`` / ``ws1`` an expert's gate, ``we3`` / ``ws3`` its up
projection, ``we2`` / ``ws2`` its way down).

There is no network here, so these are the issue writer's reading of the
config, of DeepSeek-V2 (arXiv:2405.04434 section 2.1) and DeepSeek-V3
(arXiv:2412.19437 sections 2.1-2.2), whose attention, router and prediction
module the model takes, and of ``transformers``' DeepSeek-V3 attention; the
configuration lists each inference under ``assumed``.

**Layer** l, ``x`` ``[S, M]`` the residual stream (eps 1e-5 in every norm):

    x <- x + attn_l(rmsnorm(x))
    x <- x + ffn_l(rmsnorm(x))          layer 0: the dense FFN; layers >= 1: the experts

and after the last layer ``rmsnorm`` and the untied head over the
vocabulary held here.

**Latent attention** on ``h`` ``[S, M]``, no biases (M 2048, H = 20 heads,
a head's 256 = 192 position-free | 64 rope channels, values 256 wide):

    c_q          = rmsnorm(h W_qa)                  2048 -> 768, a weight of 768
    q            = c_q W_qb -> [S, H, 192 | 64]     768 -> 20 * 256: q_nope | q_rope
    [c_kv | k_r] = h W_kva                          2048 -> 512 + 64
    c_kv         = rmsnorm(c_kv)                    a weight of 512; k_r is NOT normed
    [k_nope | v] = c_kv W_kvb -> [S, H, 192 | 256]  512 -> 20 * (192 + 256)
    q_rope, k_r  = rope(q_rope), rope(k_r)          halves layout over the 64 channels, theta 1e6;
                                                    k_r ONE head that all 20 query heads read
    q = [q_nope | q_rope]        k = [k_nope | k_r]
    a_i = softmax_j<=t(q_i k_i^T / sqrt(256)) v_i   rope_scaling null: no further factor
    out = concat_i(a_i) W_o                         20 * 256 -> 2048

**Dense FFN** (layer 0): ``(silu(h W_1) * (h W_3)) W_2``, 2048 -> 10 240 ->
2048.

**Expert layer** (layers >= 1) on ``h`` ``[T, M]``:

    s   = sigmoid(h W_r)                    float32, all E = 64 experts
    idx = top-4 of s + b                    b: the correction bias, a buffer (no gradient); ties to the lower index
    w   = 1.8 * s[idx] / sum(s[idx])
    y   = sum_{e in idx, e held here} w_e (silu(h W1_e) * (h W3_e)) W2_e
          + (silu(h V1) * (h V3)) V2        the shared expert on every token, whole on every chip

**Multi-token prediction**, one module (arXiv:2412.19437 eq. 21-25). With
``h_i`` the main stack's output at position ``i`` BEFORE the final norm and
``t_{i+1}`` the next token (``targets[i]``):

    u_i    = [rmsnorm(h_i) ; rmsnorm(Emb(t_{i+1}))] W_eh    4096 -> 2048; two norms of the module's own, Emb the main model's table
    z      = Layer_mtp(u)                   one more layer of the expert kind, its own weights, the same share
    logits = rmsnorm(z) W_head              the MAIN model's head, a norm of the module's own
    target at i: t_{i+2} (``targets[i + 1]``)

    loss = mean_i CE_main + lambda * mean_{i < S - 1} CE_mtp

the last position's second target lies beyond the sequence and is left out
of the second mean (the first mean takes every position, as the other
cells' do).

**The share.** The tree holds the experts ``[first, first + held)`` of every
expert layer (``sizes["first_expert"]``, ``sizes["held_experts"]``) and a
slice of the vocabulary; the router scores all ``E``. What the absent
experts would have added is left out here as in the program, and that
partial result goes on to the next layer (model-configs guide, section 4).
``expert_layer(.., shared=False)`` leaves the shared expert out, for the
test that the shares add up.

Departures, each one of storage and not of arithmetic: attention in blocks
of query rows and the loop over the held experts under ``jax.checkpoint``
(``reference/smallthinker.py``'s and ``reference/olmoe.py``'s, whose
functions these are), and every layer under ``jax.checkpoint``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from reference.nemotron_h import route
from reference.olmoe import experts as gated_experts
from reference.smallthinker import _attend, _rms_norm, _rope
from trees import get_leaves, with_leaves

#: How far the program may be from this reference, and why.
#: The program's matmuls take bfloat16 operands and accumulate in float32;
#: its residual stream, both latents, the flash kernels and the fused
#: cross-entropy hold bfloat16; the router's logits (a float32 product in
#: fact: "highest"), scores, top-k weights, the combine and both per-token
#: losses are float32 on both sides. As in the other expert cells a token
#: whose 4th and 5th scores lie within the rounding of the normed tokens
#: picks another expert than here, and this chip holds 512 rows an expert,
#: so a differing row is a visible part of a held expert's gradient. Both
#: bounds come from readings on the chip at the cell's widths (PERF.md
#: section 6, PR 43):
#:
#: * the sound program, 11 seeds at the configuration's embedding scale (5
#:   runs of the cell: ``correct``'s own numbers; 6 of
#:   tools/glm4_moe_lite_precision.py): the loss differs by 4.2e-7 to 1.6e-5
#:   relative; the leaves no choice reaches directly by 1.2-2.7 % of their L2
#:   norm (lm_head 1.2, dense_down 1.6-1.7, first_query_down 1.7-1.9,
#:   last_kv_up 1.8-2.1, last_kv_down 2.0-2.5, mtp_proj 2.4-2.7), the held
#:   experts' down matrices by 11.1-11.9 %, the last router by 16.1-16.7 %;
#: * the nearest precision below, 6 seeds
#:   (tools/glm4_moe_lite_precision.py): this reference computed in bfloat16
#:   throughout differs in the loss by 1.9e-4 to 1.8e-3.
#:
#: The loss bound, 6e-5, lies between the two readings with room on both
#: sides (3.7 x the worst sound seed, a third of the best lower-precision
#: one) and is what fails the lower precision. The gradient bound, 30 %, has
#: to admit the near-tied choices and is 1.8 x the worst leaf seen (the
#: hybrid cell's bound, whose router reads the same): tests/
#: test_glm4_moe_lite.py holds the program in float32 to this reference at
#: 1e-4, where each of eighteen wrong readings of the equations above
#: fails.
TOLERANCE = {"loss_rel": 6e-5, "grad_rel_l2": 3e-1}


def latent_attention(p, x, sizes: dict):
    b, s, _ = x.shape
    heads, nope, rot = sizes["heads"], sizes["qk_nope"], sizes["qk_rope"]
    eps, theta = sizes["norm_eps"], sizes["rope_theta"]
    h = _rms_norm(x, p["ln1"], eps)
    c_q = _rms_norm(h @ p["wqa"], p["q_latent_norm"], eps)
    q = (c_q @ p["wqb"]).reshape(b, s, heads, nope + rot)
    down = h @ p["wkva"]
    c_kv = _rms_norm(down[..., :sizes["kv_latent"]], p["kv_latent_norm"],
                     eps)
    k_r = down[..., sizes["kv_latent"]:]                # not normed
    kv = (c_kv @ p["wkvb"]).reshape(b, s, heads, nope + sizes["v_head"])
    k_nope, v = kv[..., :nope], kv[..., nope:]
    q_r = _rope(q[..., nope:], theta)
    k_r = _rope(k_r[:, :, None, :], theta)              # one head for all
    q = jnp.concatenate([q[..., :nope], q_r], -1)
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_r, (b, s, heads, rot))], -1)
    # (_attend: q [B, S, Hkv, G, D] on k, v [B, S, Hkv, D], / sqrt(D))
    return x + _attend(q[:, :, :, None, :], k, v, None) @ p["wo"]


def _gated(h, gate, up, down):
    return (jax.nn.silu(h @ gate) * (h @ up)) @ down


def dense_ffn(p, x, sizes: dict):
    h = _rms_norm(x, p["ln2"], sizes["norm_eps"])
    return x + _gated(h, p["w1"], p["w3"], p["w2"])


def expert_layer(p, h, sizes: dict, choice=None, shared=True):
    """The expert layer on normed tokens ``[T, M]``: the held experts' part
    and (``shared``) the shared expert's; and the chosen experts."""
    choice, combine = route(h @ p["router"], p["router_bias"], sizes, choice)
    first, held = sizes["first_expert"], sizes["held_experts"]
    y = gated_experts(p, h, combine[:, first:first + held])
    if shared:
        y = y + _gated(h, p["ws1"], p["ws3"], p["ws2"])
    return y, choice


def experts(p, x, sizes: dict, choice=None):
    b, s, m = x.shape
    h = _rms_norm(x, p["ln2"], sizes["norm_eps"]).reshape(b * s, m)
    y, choice = expert_layer(p, h, sizes, choice)
    return x + y.reshape(b, s, m), choice


def _layer(tree, index):
    return {k: v[index] for k, v in tree.items()}


def _expert_kind_layer(attn_p, experts_p, x, sizes, choice=None):
    x = jax.checkpoint(lambda p, x: latent_attention(p, x, sizes))(attn_p, x)
    return jax.checkpoint(lambda p, x, c: experts(p, x, sizes, c))(
        experts_p, x, choice)


def forward(params, tokens, targets, sizes: dict, choices=None):
    """Both heads' logits ``[B, S, V]`` (the second's at ``i`` are for
    ``targets[i + 1]``) and the main stack's chosen experts ``[expert
    layers, T, k]``."""
    eps = sizes["norm_eps"]
    x = params["embed"][tokens]
    lead = params["lead"]
    x = jax.checkpoint(lambda p, x: latent_attention(p, x, sizes))(
        _layer(lead["latent"], 0), x)
    x = jax.checkpoint(lambda p, x: dense_ffn(p, x, sizes))(
        _layer(lead["dense"], 0), x)
    chosen = []
    for i in range(sizes["expert_layers"]):
        x, c = _expert_kind_layer(
            _layer(params["layers"]["latent"], (0, i)),
            _layer(params["layers"]["experts"], (0, i)), x, sizes,
            None if choices is None else choices[i])
        chosen.append(c)
    logits = _rms_norm(x, params["ln_f"], eps) @ params["lm_head"]
    # the prediction module reads the stack's output before ln_f
    mtp = params["mtp"]
    u = jnp.concatenate(
        [_rms_norm(x, mtp["norm_h"], eps),
         _rms_norm(params["embed"][targets], mtp["norm_e"], eps)], -1
    ) @ mtp["proj"]
    z, _ = _expert_kind_layer(_layer(mtp["layers"]["latent"], 0),
                              _layer(mtp["layers"]["experts"], 0), u, sizes)
    second = _rms_norm(z, mtp["ln_f"], eps) @ params["lm_head"]
    return logits, second, jnp.stack(chosen)


def _xent(logits, targets):
    lse = jax.scipy.special.logsumexp(logits, axis=-1)
    return lse - jnp.take_along_axis(logits, targets[..., None], -1)[..., 0]


def losses(params, batch, sizes: dict, choices=None):
    """(total, the first head's cross-entropy, load-balancing loss: none,
    0.0, z-loss: none, 0.0, the main stack's choices: the tuple
    tools/olmoe_routing.py reads; then the second head's cross-entropy)."""
    targets = batch["targets"]
    logits, second, chosen = forward(params, batch["tokens"], targets, sizes,
                                     choices)
    main = jnp.mean(_xent(logits, targets))
    mtp = jnp.mean(_xent(second[:, :-1], targets[:, 1:]))
    return main + sizes["mtp_weight"] * mtp, main, 0.0, 0.0, chosen, mtp


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
