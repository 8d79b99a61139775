"""Attention over the keys a learned index selects (DeepSeek-V3.2,
arXiv:2512.02556, section 2.1): beside a grouped-query attention core an
*indexer* scores every causal key of a query with a few small heads, the
``topk`` highest scored keys of a query are the only ones its attention heads
see, and the indexer is trained to predict where those heads put their weight.

    I[t, s] = sum_j w[t, j] * relu(qI[t, j] . kI[s])            s <= t   (eq. 1)
    S_t     = the topk keys of largest I[t, s] among s <= t, ties to the
              lower index (every causal key while t < topk); one set a query,
              shared by all its heads; no gradient passes through it
    o[t, h] = sum_{s in S_t} softmax_{s in S_t}(q[t, h] . k[s, h // G] * scale) v[s, h // G]
    p[t, s] = stop_gradient(mean_h softmax_{S_t}(..)[s])
    L_I     = mean_t KL(p[t, .] || softmax_{s in S_t} I[t, s])            (eq. 4)

**The form.** Plain XLA, no kernel: a block of ``ROWS`` query rows at a time
(``lax.map``; the batch's sequences one at a time around it), against the
keys of the block's *band*: the sequence's rows are cut into ``BANDS`` equal
bands and a band's blocks read the keys up to the band's end, so that no
block walks the whole upper triangle (8 bands: 9/16 of the square). A block
computes its rows' index scores ``[rows, keys]``, selects, and attends under
the selection's mask: it walks every causal tile and masks, it does not
gather. No ``[S, S]`` array exists: the widest is one block's scores, ``[H,
rows, keys]`` float32.

**The selection is exact.** A row's ``topk``-th largest score is found by
value, bit by bit: float32 scores map to unsigned integers in the same order,
and 32 counts ``sum(u >= candidate)`` build the largest threshold that still
has ``topk`` keys at or above it. Keys above the threshold are in; of those
*at* it the first ``topk - (keys above)`` by index are (a second search, over
the index, which runs only where a block has a tie to break). No sort, no
approximate top-k, no block-level stand-in.

**The backward pass** is autodiff's, a block at a time: each block is a
``jax.checkpoint`` that keeps its selection (bits, a byte for eight keys:
the name :data:`SELECTION`) and nothing else, so the transposed map runs a
block's scores and softmax again, as a flash kernel's backward does, and
selects nothing twice. A checkpoint AROUND the call (a block of the model)
that keeps :data:`KEPT` runs neither the selection nor the core a second
time: 33.5 MB of bits and 134 MB of outputs a layer at 16 384 tokens. The
keys, values and index keys enter the map in float32, so their gradients
add up over the blocks in float32. Two
``stop_gradient``s keep the graphs apart: the target ``p`` and the scores the
selection reads; the caller stops the gradient into the indexer's input.
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name

from horovod_tpu.profiling import scopes

#: query rows of a block; its float32 scores are ``[H, ROWS, keys]``
ROWS = 128
#: bands of rows a sequence is cut into; a band's blocks read the keys up to
#: the band's end
BANDS = 8
#: the name of a block's kept selection (``jax.checkpoint``'s policy)
SELECTION = "hvd_sparse_selection"
#: the name of the core's output and the indexer's loss as the caller gets
#: them, and with SELECTION what a checkpoint AROUND the call keeps so that
#: its second run makes neither again
OUTPUT = "hvd_sparse_output"
KEPT = (SELECTION, OUTPUT)


def blocks(seq: int) -> Tuple[int, int]:
    """(rows a block, bands) for a sequence of ``seq`` positions: ``ROWS``
    and ``BANDS``, or as many as divide it."""
    rows = min(ROWS, seq)
    while seq % rows:
        rows -= 1
    bands = max(1, min(BANDS, seq // rows))
    while (seq // rows) % bands or (seq // bands) % 8:
        bands -= 1      # (a band's keys are whole bytes of the selection)
    return rows, bands


def index_scores(qi, w, ki):
    """``I[r, k] = sum_j w[r, j] relu(qi[r, j] . ki[k])`` in float32 (the
    products in ``qi``'s dtype, accumulated in float32): ``qi`` ``[rows, Hi,
    Di]``, ``w`` ``[rows, Hi]`` float32, ``ki`` ``[keys, Di]``. A zero is
    +0.0, so that equal scores have equal bits."""
    s = jnp.einsum("rjd,kd->rjk", qi, ki.astype(qi.dtype),
                   preferred_element_type=jnp.float32)
    scores = jnp.sum(jax.nn.relu(s) * w[:, :, None], axis=1)
    return jnp.where(scores == 0, 0.0, scores)


def _ordered(scores):
    """Float32 scores as unsigned integers in the same order (0 is below
    every finite score and -inf)."""
    bits = lax.bitcast_convert_type(scores, jnp.uint32)
    return jnp.where(bits >> 31 == 1, ~bits, bits | jnp.uint32(1 << 31))


def select(scores, t, topk: int):
    """The selection ``[rows, keys]`` (bool) of ``scores`` ``[rows, keys]``
    float32, the query rows at positions ``t`` ``[rows]`` and key ``k`` at
    position ``k``: the causal keys of a row that has at most ``topk``, else
    exactly the ``topk`` of largest score, ties to the lower index."""
    rows, keys = scores.shape
    kpos = jnp.arange(keys, dtype=jnp.int32)
    causal = kpos[None, :] <= t[:, None]
    u = jnp.where(causal, _ordered(scores), jnp.uint32(0))

    def raise_bit(i, thr):
        cand = thr | (jnp.uint32(1) << (31 - i).astype(jnp.uint32))
        enough = jnp.sum(u >= cand[:, None], axis=1, dtype=jnp.int32) >= topk
        return jnp.where(enough, cand, thr)
    # the largest value that topk keys reach: the topk-th largest score
    thr = lax.fori_loop(0, 32, raise_bit, jnp.zeros((rows,), jnp.uint32))
    above = u > thr[:, None]
    at = u == thr[:, None]
    need = topk - jnp.sum(above, axis=1, dtype=jnp.int32)   # of those at it
    many = t + 1 > topk
    steps = max(keys.bit_length(), 1)

    def first_by_index():
        # the largest c with fewer than ``need`` of the tied keys before c
        def raise_bit(i, cut):
            cand = cut + (1 << (steps - 1 - i))
            few = jnp.sum(at & (kpos[None, :] < cand[:, None]), axis=1,
                          dtype=jnp.int32) < need
            return jnp.where(few, cand, cut)
        return lax.fori_loop(0, steps, raise_bit,
                             jnp.zeros((rows,), jnp.int32))
    tied = many & (jnp.sum(at, axis=1, dtype=jnp.int32) > need)
    cut = lax.cond(jnp.any(tied), first_by_index,
                   lambda: jnp.full((rows,), keys, jnp.int32))
    chosen = above | (at & (kpos[None, :] <= cut[:, None]))
    return jnp.where(many[:, None], chosen, causal)


def _block(topk: int, scale: float, total: int, x, consts):
    """One block of rows of one sequence: (its heads' outputs ``[rows, H,
    D]``, its rows' summed KL, its selected keys counted, its selection as
    bits ``[rows, total // 8]``)."""
    q, qi, w, t0 = x
    k, v, ki = consts                               # float32, the band's
    rows, heads, d = q.shape
    keys, kv_heads, _ = k.shape
    dtype = q.dtype
    t = t0 + jnp.arange(rows, dtype=jnp.int32)
    with jax.named_scope(scopes.ATTENTION_INDEX_SCORES):
        scores = index_scores(qi, w, ki)
    with jax.named_scope(scopes.ATTENTION_INDEX_SELECT):
        # kept as bits (a byte for eight keys), read back as the mask
        bits = checkpoint_name(jnp.packbits(
            select(lax.stop_gradient(scores), t, topk), axis=1), SELECTION)
        chosen = jnp.unpackbits(bits, axis=1).astype(bool)
        bits = jnp.pad(bits, ((0, 0), (0, total // 8 - bits.shape[1])))
    with jax.named_scope(scopes.ATTENTION_CORE), \
            jax.named_scope(scopes.ATTENTION_CORE_SPARSE):
        s = jnp.einsum(
            "rhgd,khd->hgrk", q.reshape(rows, kv_heads, -1, d),
            k.astype(dtype), preferred_element_type=jnp.float32) * scale
        s = jnp.where(chosen[None, None], s, -jnp.inf)
        e = jnp.exp(s - jnp.max(s, axis=-1, keepdims=True))
        # normalised after the weighted sum: [.., rows, D], not [.., keys]
        share = 1.0 / jnp.sum(e, axis=-1)                   # [Hkv, G, rows]
        o = jnp.einsum("hgrk,khd->rhgd", e.astype(dtype), v.astype(dtype),
                       preferred_element_type=jnp.float32
                       ) * share.transpose(2, 0, 1)[..., None]
    with jax.named_scope(scopes.ATTENTION_INDEX_LOSS):
        # both are 0 outside the selection: exp(-inf) on both sides
        target = lax.stop_gradient(
            jnp.sum(e * share[..., None], axis=(0, 1)) / heads)
        log_index = jax.nn.log_softmax(
            jnp.where(chosen, scores, -jnp.inf), axis=-1)
        log_target = jnp.log(jnp.where(target > 0, target, 1.0))
        kl = jnp.sum(target * (log_target
                               - jnp.where(chosen, log_index, 0.0)))
    return (o.reshape(rows, heads, d).astype(dtype), kl,
            jnp.sum(chosen, dtype=jnp.float32), bits)


def _sequence(topk: int, scale: float, args):
    """One sequence's blocks, band by band."""
    q, k, v, qi, ki, w = args                       # [S, ..]
    seq = q.shape[0]
    rows, bands = blocks(seq)
    per_band = seq // bands
    k, v, ki = (a.astype(jnp.float32) for a in (k, v, ki))
    block = jax.checkpoint(
        functools.partial(_block, topk, scale, seq),
        policy=jax.checkpoint_policies.save_only_these_names(SELECTION))
    outs = []
    for band in range(bands):
        lo, hi = band * per_band, (band + 1) * per_band
        consts = (k[:hi], v[:hi], ki[:hi])
        xs = tuple(a[lo:hi].reshape((per_band // rows, rows) + a.shape[1:])
                   for a in (q, qi, w))
        t0 = lo + rows * jnp.arange(per_band // rows, dtype=jnp.int32)
        outs.append(lax.map(lambda x, consts=consts: block(x, consts),
                            xs + (t0,)))
    o, kl, count, bits = (jnp.concatenate(parts) for parts in zip(*outs))
    return (o.reshape((seq,) + q.shape[1:]), jnp.sum(kl), jnp.sum(count),
            bits.reshape(seq, seq // 8))


def indexed_attention(q, k, v, qi, ki, w, topk: int, scale: float):
    """Causal grouped-query attention over the ``topk`` keys a query's index
    scores select, and the indexer's loss (the module docstring's equations).

    ``q`` ``[B, S, H, D]``; ``k``, ``v`` ``[B, S, Hkv, D]`` (query head ``h``
    reads head ``h // (H / Hkv)``); the indexer's queries ``qi`` ``[B, S, Hi,
    Di]``, its one key head ``ki`` ``[B, S, Di]`` and its heads' weights ``w``
    ``[B, S, Hi]`` float32. Returns ``o`` ``[B, S, H, D]`` in ``q``'s dtype,
    ``L_I`` (the mean over the batch's tokens), the mean number of keys a
    query attended, and the selection as bits ``[B, S, S // 8]`` (uint8,
    ``jnp.packbits`` along the keys: for a reference to be told the same
    selection; nothing else reads it and XLA drops it).

    The gradient of ``o`` reaches ``q``, ``k``, ``v`` and that of ``L_I``
    reaches ``qi``, ``ki``, ``w``; neither reaches the other three."""
    if q.shape[1] % 8:
        raise ValueError(f"a sequence of {q.shape[1]} positions: the "
                         "selection's bits are whole bytes a row")
    o, kl, count, bits = lax.map(
        functools.partial(_sequence, topk, scale), (q, k, v, qi, ki, w))
    tokens = q.shape[0] * q.shape[1]
    o, kl = checkpoint_name((o, jnp.sum(kl) / tokens), OUTPUT)
    return o, kl, jnp.sum(count) / tokens, bits
