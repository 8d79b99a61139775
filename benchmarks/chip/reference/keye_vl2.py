"""Plain reference for the ``keye_vl2`` adapter: the language model of
Keye-VL-2.0-30B-A3B, a decoder whose grouped-query attention sees only the
keys a learned index selects (DeepSeek-V3.2's indexer and its training,
arXiv:2512.02556 section 2.1, eq. 1-4) before a layer of routed experts, and
the loss its sparse training stage descends, in jax.numpy, float32, matmuls
at "highest" precision. Imports nothing of the program; it reads the
program's parameter tree by the program's names (stacked ``[stage, layer,
...]`` weights; ``we1`` an expert's gate, ``we3`` its up projection, ``we2``
its way down; matrices stored ``[in, out]``).

One layer, ``x`` ``[S, M]`` the residual stream, pre-norm, eps 1e-6, no
biases but the index key's (the catalog row's ``config`` and
``described_as`` and the issue writer's reading of the family's code: no
network here, so the configuration lists every reading under ``assumed``):

    h   = rmsnorm(x; ln1)
    q   = h Wq -> [S, H, D]   k = h Wk -> [S, Hkv, D]   v = h Wv -> [S, Hkv, D]
    q, k = rmsnorm over each head's D channels (q_norm, k_norm: one weight
           [D] for all the heads), then rope(theta, halves layout, whole head)
    -- the indexer, on h behind a stop_gradient --
    qI  = rope(h W_qI -> [S, Hi, Di])
    kI  = rope(LayerNorm(h W_kI; k_idx_norm, k_idx_norm_bias) -> [S, Di])   ONE key head
    w   = (h W_wI) * Hi^-1/2 * Di^-1/2                                      [S, Hi]
    I[t, s] = sum_j w[t, j] relu(qI[t, j] . kI[s])              s <= t   (eq. 1)
    S_t = the topk keys of largest I[t, s] among s <= t, ties to the lower
          index; every causal key while t < topk; one set for all H heads;
          no gradient passes through the selection
    -- the core --
    a_i = softmax_{s in S_t}(q_i k_{i // (H / Hkv)}^T / sqrt(D)) v_{i // (H / Hkv)}
    x'  = x + concat_i(a_i) Wo
    -- the indexer's loss (eq. 4) --
    p[t, s] = stop_gradient(mean_i softmax_{S_t}(..)_i[s])
    L_I = mean_t KL(p[t, .] || softmax_{s in S_t} I[t, s])
    -- the experts --
    h2  = rmsnorm(x'; ln2);   r = h2 W_r  [S, E]
    idx = top-k of r (ties to the lower index);  c = softmax(r[idx])
    y   = sum_{e in idx, e held here} c_e W2_e(silu(W1_e h2) * (W3_e h2))
    out = x' + y

then ``rmsnorm``, the untied head over the vocabulary held here and the mean
next-token cross-entropy; the objective is that plus the SUM over the layers
of ``L_I`` (weight 1). The indexer's leaves get their gradient from ``L_I``
alone, every other leaf from the cross-entropy alone: text-only M-RoPE is the
plain rope table (``mrope_section`` [16, 24, 24] sums to the head's 64
frequencies and a text token carries one id in all three sections).

**The share.** The tree holds the experts ``[first, first + held)`` of every
layer and a slice of the vocabulary; the router scores all ``E``. What the
absent experts would have added is left out here as in the program
(model-configs guide, section 4).

Departures, each one of storage and not of arithmetic: attention, index and
selection run in blocks of ``ATTENTION_ROWS`` query rows (``lax.map``, each
block under ``jax.checkpoint``), the layers and the loop over the held
experts under ``jax.checkpoint``, so that the check's sequences of 16 384
tokens fit beside 2.6 GB of weights on one chip. ``selection`` (bits
``[L, B, S, S // 8]``, ``jnp.packbits`` along the keys) forces every layer's
``S_t``: what differing selections explain of an error is the difference
between the two readings (tools/keye_vl2_precision.py).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from trees import get_leaves, with_leaves

#: How far the program may be from this reference, and why.
#: The program's matmuls take bfloat16 operands (relative rounding 2e-3) and
#: accumulate in float32; its residual stream holds bfloat16; the router's
#: logits, both softmaxes' statistics, the index scores' accumulation, the
#: selection and the KL are float32 on both sides. The hazard is
#: SmallThinker's, moved from experts to keys: the indexer reads bfloat16
#: activations through bfloat16 products, so where a row's 2048th and 2049th
#: index scores lie within that rounding the program selects another key than
#: this reference does, and every leaf sees it through the residual. Both
#: bounds come from readings on the chip at the cell's widths (PERF.md
#: section 6, PR 64; tools/keye_vl2_precision.py on 4 seeds and 7 runs of the
#: cell on 7 more, one sequence of 16 384 tokens x 4 layers: 117 M (query,
#: key) choices in the rows that select):
#:
#: * the sound program, 11 seeds: 0.461-0.463 % of its choices are not the
#:   reference's (the tool's 4); the loss differs by 8.9e-7 to 1.43e-5
#:   relative; a gradient leaf by 0.45-9.1 % of its L2 norm (lm_head 0.57,
#:   the first layer's index weights 0.45, the last layer's index queries
#:   3.0-3.6, first_query and first_key 6.1, the held experts' down matrices
#:   6.4-7.5, the last router 7.4-9.1). Against this reference told the program's selection
#:   (``loss_and_grads(.., selection=..)``) first_query and first_key are
#:   1.1 % off and the others as before (experts' down 6.8-7.2, router
#:   7.5-8.7, index queries 2.6-2.9, lm_head 0.55): the differing choices
#:   explain five sixths of what the attention's own leaves see and nothing
#:   of the rest, which is the routers' near-ties (OLMoE's hazard, a norm
#:   later) and bfloat16 rounding through four blocks;
#: * the nearest precision below: this reference computed in bfloat16
#:   throughout differs in the loss by 2.2e-4 to 1.2e-3 (4 seeds: 2.24e-4,
#:   3.25e-4, 4.19e-4, 1.24e-3).
#:
#: The loss bound, 5e-5, lies between the two readings with room on both
#: sides (3.5 x the worst sound seed, a quarter of the best lower-precision
#: one) and is what fails the lower precision. The gradient bound, 15 %, has
#: to admit the near-tied choices of keys and of experts and is 1.6 x the
#: worst leaf seen; tests/test_keye_vl2.py holds the program in float32 to
#: this reference at 1e-4 on every leaf, the selection bit for bit, where
#: topk off by one, a leaked gradient, relu dropped, w unscaled, the key's
#: norm dropped, rope off the index, a selection a half of the heads and a
#: softmax over all causal keys each fail.
TOLERANCE = {"loss_rel": 5e-5, "grad_rel_l2": 1.5e-1}

#: query rows of one block: its float32 scores are ``[B, H, ATTENTION_ROWS, S]``
ATTENTION_ROWS = 256


def _rms_norm(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True)
                             + eps) * g


def _layer_norm(x, g, b, eps):
    x = x - jnp.mean(x, -1, keepdims=True)
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True)
                             + eps) * g + b


def _rope(x, theta):
    """Rotary embedding, halves layout (rotate_half); x is [B, S, H, D]."""
    half = x.shape[-1] // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * freqs[None]
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def select(scores, t, topk: int):
    """``S_t`` as a mask ``[.., rows, S]`` of index scores ``[.., rows, S]``
    for the queries at positions ``t`` ``[rows]``: the causal keys of a row
    with at most ``topk`` of them, else the ``topk`` largest, of equal
    scores the lower index first."""
    s = scores.shape[-1]
    causal = jnp.arange(s) <= t[:, None]
    if topk >= s:
        return jnp.broadcast_to(causal, scores.shape)
    scores = jnp.where(causal, scores, -jnp.inf)
    kth = jax.lax.top_k(scores, topk)[0][..., -1:]
    above, at = scores > kth, (scores == kth) & causal
    need = topk - jnp.sum(above, -1, keepdims=True)
    chosen = above | (at & (jnp.cumsum(at, -1) <= need))
    return jnp.where((t + 1 > topk)[:, None], chosen, causal)


def _attend(q, k, v, qi, ki, w, topk, selection=None):
    """q ``[B, S, Hkv, G, D]`` against k, v ``[B, S, Hkv, D]`` (q head ``(h,
    g)`` is head ``h * G + g`` and reads k/v head ``h``) under the selection
    of the index scores of qi ``[B, S, Hi, Di]``, ki ``[B, S, Di]``, w ``[B,
    S, Hi]``; a block of query rows at a time. Returns (the heads' outputs
    ``[B, S, H * D]``, ``L_I``, the selections as bits ``[B, S, S // 8]``).
    ``selection``: such bits, which replace the index's own choice."""
    b, s, hkv, g, d = q.shape
    rows = min(ATTENTION_ROWS, s)
    assert s % rows == 0 and s % 8 == 0, (s, rows)

    @jax.checkpoint
    def block(args):
        q_rows, qi_rows, w_rows, t0, forced = args
        t = t0 + jnp.arange(rows)
        index = jnp.einsum("bqj,bqjk->bqk", w_rows, jax.nn.relu(
            jnp.einsum("bqjd,bkd->bqjk", qi_rows, ki)))         # eq. 1
        if forced is None:
            chosen = select(jax.lax.stop_gradient(index), t, topk)
        else:
            chosen = jnp.unpackbits(forced, axis=-1).astype(bool)
        scores = jnp.einsum("bqhgd,bkhd->bhgqk", q_rows, k) / math.sqrt(d)
        probs = jax.nn.softmax(
            jnp.where(chosen[:, None, None], scores, -jnp.inf), -1)
        out = jnp.einsum("bhgqk,bkhd->bqhgd", probs, v)
        target = jax.lax.stop_gradient(jnp.mean(probs, axis=(1, 2)))
        log_index = jax.nn.log_softmax(
            jnp.where(chosen, index, -jnp.inf), -1)
        kl = jnp.sum(jnp.where(
            target > 0, target * (jnp.log(jnp.where(target > 0, target, 1.0))
                                  - jnp.where(chosen, log_index, 0.0)), 0.0))
        return out, kl, jnp.packbits(chosen, axis=-1)

    def cut(a):
        return a.reshape((b, s // rows, rows) + a.shape[2:]).swapaxes(0, 1)
    forced = None if selection is None else cut(selection)
    out, kl, bits = jax.lax.map(block, (
        cut(q), cut(qi), cut(w), jnp.arange(0, s, rows), forced))
    return (out.swapaxes(0, 1).reshape(b, s, hkv * g * d),
            jnp.sum(kl) / (b * s), bits.swapaxes(0, 1).reshape(b, s, s // 8))


def attention(p, x, sizes: dict, selection=None):
    """The attention sublayer: (the new residual, the layer's ``L_I``, its
    selections as bits)."""
    b, s, _ = x.shape
    heads, kv_heads, d = sizes["heads"], sizes["kv_heads"], sizes["head_dim"]
    hi, di = sizes["index_heads"], sizes["index_head_dim"]
    eps, theta = sizes["norm_eps"], sizes["rope_theta"]
    h = _rms_norm(x, p["ln1"], eps)
    q = _rms_norm((h @ p["wq"]).reshape(b, s, heads, d), p["q_norm"], eps)
    k = _rms_norm((h @ p["wk"]).reshape(b, s, kv_heads, d), p["k_norm"], eps)
    v = (h @ p["wv"]).reshape(b, s, kv_heads, d)
    q, k = _rope(q, theta), _rope(k, theta)
    hs = jax.lax.stop_gradient(h)           # the indexer's graph is its own
    qi = _rope((hs @ p["wq_idx"]).reshape(b, s, hi, di), theta)
    ki = _rope(_layer_norm(hs @ p["wk_idx"], p["k_idx_norm"],
                           p["k_idx_norm_bias"], eps)[:, :, None],
               theta)[:, :, 0]
    w = (hs @ p["w_idx"]) * (hi ** -0.5 * di ** -0.5)
    out, index_loss, bits = _attend(
        q.reshape(b, s, kv_heads, heads // kv_heads, d), k, v, qi, ki, w,
        sizes["index_topk"], selection)
    return x + out @ p["wo"], index_loss, bits


def route(logits, sizes: dict):
    """(the chosen experts ``[T, k]``, the combine weights ``[T, E]``: the
    softmax over a token's top-k logits, 0 elsewhere)."""
    _, choice = jax.lax.top_k(logits, sizes["experts_per_token"])
    chosen = jnp.sum(jax.nn.one_hot(choice, sizes["experts"],
                                    dtype=logits.dtype), axis=1)
    return choice, jax.nn.softmax(jnp.where(chosen > 0, logits, -jnp.inf),
                                  -1)


def routed(p, h, combine):
    """``sum_e combine[:, e] * down_e(silu(gate_e(h)) * up_e(h))`` over the
    experts the tree holds (``combine`` ``[T, held]``): every held expert on
    every token, one expert at a time."""
    def one(y, expert):
        gate, up, down, c = expert
        out = (jax.nn.silu(h @ gate) * (h @ up)) @ down
        return y + c[:, None] * out, None
    y, _ = jax.lax.scan(jax.checkpoint(one), jnp.zeros_like(h),
                        (p["we1"], p["we3"], p["we2"], combine.T))
    return y


def expert_layer(p, h, sizes: dict):
    """The expert layer on normed tokens ``[T, M]``: the held experts' part
    of it, and the chosen experts."""
    choice, combine = route(h @ p["router"], sizes)
    first, held = sizes["first_expert"], sizes["held_experts"]
    return routed(p, h, combine[:, first:first + held]), choice


def experts(p, x, sizes: dict):
    """The expert sublayer on ``[B, S, M]``: the new residual."""
    b, s, m = x.shape
    h = _rms_norm(x, p["ln2"], sizes["norm_eps"]).reshape(b * s, m)
    return x + expert_layer(p, h, sizes)[0].reshape(b, s, m)


def block(p, x, sizes: dict, selection=None):
    x, index_loss, bits = attention(p, x, sizes, selection)
    return experts(p, x, sizes), index_loss, bits


def forward(params, tokens, sizes: dict, selection=None):
    """Logits ``[B, S, V]``, the sum over the layers of ``L_I``, and every
    layer's selections as bits ``[L, B, S, S // 8]``."""
    x = params["embed"][tokens]
    index_loss, chosen = 0.0, []
    for i in range(sizes["layers"]):
        p = {k: v[0, i] for k, v in params["layers"].items()}
        x, l_i, bits = jax.checkpoint(
            lambda p, x, forced: block(p, x, sizes, forced))(
                p, x, None if selection is None else selection[i])
        index_loss = index_loss + l_i
        chosen.append(bits)
    x = _rms_norm(x, params["ln_f"], sizes["norm_eps"])
    return x @ params["lm_head"], index_loss, jnp.stack(chosen)


def losses(params, batch, sizes: dict, selection=None):
    """(the objective, the cross-entropy, the indexers' summed loss, the
    selections' bits)."""
    logits, index_loss, chosen = forward(params, batch["tokens"], sizes,
                                         selection)
    lse = jax.scipy.special.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(logits, batch["targets"][..., None],
                                 -1)[..., 0]
    xent = jnp.mean(lse - picked)
    return xent + index_loss, xent, index_loss, chosen


def loss_and_grads(params, leaf_specs: dict, batch, sizes: dict,
                   selection=None):
    """The objective, and its gradients by ``jax.grad`` over the named
    leaves only."""
    @jax.jit
    def fn(leaves, params, batch, selection):
        with jax.default_matmul_precision("highest"):
            return jax.value_and_grad(
                lambda lv: losses(with_leaves(params, leaf_specs, lv),
                                  batch, sizes, selection)[0])(leaves)
    return fn(get_leaves(params, leaf_specs), params, batch, selection)
