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

**The form.** A block of ``ROWS`` query rows at a time (``lax.map``; the
batch's sequences one at a time around it), against the keys of the block's
*band*: the sequence's rows are cut into ``BANDS`` equal bands and a band's
blocks read the keys up to the band's end, so that no block walks the whole
upper triangle (8 bands: 9/16 of the square). A block computes its rows' index
scores ``[rows, keys]``, selects, and attends under the selection's mask: it
walks every causal tile and masks, it does not gather. No ``[S, S]`` float
array exists. Two forms of the core, chosen from the backend and the shape
(:func:`sparse_path`; no setting):

* ``"pallas"`` (a TPU, whole blocks of 128 positions, heads of whole lane
  tiles): the kernels of ``ops/pallas_sparse_attention.py``. The block
  packs its selection as the kernels' mask (a bit a (query, key), the name
  :data:`SELECTION`) and calls ``hvd_sparse_fwd`` (its outputs and the rows'
  log-sum-exp, :data:`STATS`) and ``hvd_sparse_mean`` (the heads' mean
  attention ``p``, ``[rows, keys]`` float32, for the loss) there, on q, k, v
  behind ``stop_gradient``; the scores never leave VMEM. The gradient of the
  outputs reaches q, k, v through ONE ``custom_vjp`` a sequence
  (:func:`_attached`), whose backward is one ``hvd_sparse_bwd`` call beside
  ``hvd_flash_adj``: dk and dv of a k tile are summed in VMEM over the q
  blocks and the group's heads and written once a range of 2048 positions.
  The gradient of the indexer's loss reaches the index's queries, weights
  and keys through one ``custom_vjp`` a block (:func:`_index_attached`),
  whose backward is one ``hvd_index_bwd`` call, where the index heads are
  whole shares of a lane tile (``ps.index_kernel_shapes``; else autodiff of
  :func:`index_scores`): the ``[rows, Hi, keys]`` products of the score
  pass are made again a tile at a time in VMEM, and from them the scores,
  the KL's gradient ``softmax(I) - p`` and the three gradients; the
  forward pass's score expression is the XLA form's, so both forms select
  from the same bits.
* ``"xla"`` (the CPU, odd shapes; what the tests hold the kernels to): the
  block's masked scores ``[H, rows, keys]`` float32 and their softmax are
  ``jax.numpy``, and the backward is autodiff's, a block at a time.

**The selection is exact.** A row's ``topk``-th largest score is found by
value, bit by bit: float32 scores map to unsigned integers in the same order,
and 32 counts ``sum(u >= candidate)`` build the largest threshold that still
has ``topk`` keys at or above it. Keys above the threshold are in; of those
*at* it the first ``topk - (keys above)`` by index are (a second search, over
the index, which runs only where a block has a tie to break). No sort, no
approximate top-k, no block-level stand-in.

**The backward pass.** Each block is a ``jax.checkpoint`` that keeps its
selection (and, in the kernels' form, its rows' log-sum-exp and two more
rows, :data:`INDEX_ROWS`) and nothing else: the transposed map computes, for
the KL's gradient ``softmax(I) - p``, the target again (``hvd_sparse_mean``
once more) and the block's index scores (inside ``hvd_index_bwd``, which
needs of the forward pass's scores only their log-sum-exp over the
selection, a kept row; in the XLA form, and at an index shape the kernel does
not take, the block's scores and softmax by XLA, which in the XLA form also
carry the gradient to q, k, v, the keys, values and index keys in float32 so
that their gradients add up over the blocks in float32), and selects nothing
twice. A
checkpoint AROUND the call (a block of the model) that keeps :data:`KEPT` runs
neither the selection nor the core a second time: at 16 384 tokens 33.5 MB of
mask, 134 MB of outputs and 2 MB of rows a layer, and in the kernels' form
the backward pass calls ``hvd_sparse_fwd`` never and runs no score pass
outside ``hvd_index_bwd``. Two ``stop_gradient``s keep
the graphs apart: the target ``p`` and the scores the selection reads; the
caller stops the gradient into the indexer's input.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name

from horovod_tpu.ops import pallas_sparse_attention as ps
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
#: the name of the rows' log-sum-exp (the kernels' form): with the selection,
#: all the backward pass needs of the forward kernel's run
STATS = "hvd_sparse_lse"
#: the name of the index scores' log-sum-exp over the selection and the
#: target's sums, a block's rows (the kernels' form): all ``hvd_index_bwd``
#: needs of the forward pass's scores
INDEX_ROWS = "hvd_sparse_index_rows"
KEPT = (SELECTION, OUTPUT, STATS, INDEX_ROWS)


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


def _index_loss(target, scores, chosen):
    """A block's summed ``KL(target || softmax_chosen(scores))``: ``target``
    the heads' mean attention ``[rows, keys]`` (0 outside the selection, as
    the softmax of the masked scores is: exp(-inf) on both sides)."""
    log_index = jax.nn.log_softmax(
        jnp.where(chosen, scores, -jnp.inf), axis=-1)
    log_target = jnp.log(jnp.where(target > 0, target, 1.0))
    return jnp.sum(target * (log_target - jnp.where(chosen, log_index, 0.0)))


def _block(topk: int, scale: float, total: int, x, consts):
    """One block of rows of one sequence, the XLA form: (its heads' outputs
    ``[rows, H, D]``, its rows' summed KL, its selected keys counted, its
    selection as bits ``[rows, total // 8]``)."""
    q, qi, w, t0 = x
    k, v, ki = consts                               # float32, the band's
    rows, heads, d = q.shape
    keys, kv_heads, _ = k.shape
    dtype = q.dtype
    t = t0 + jnp.arange(rows, dtype=jnp.int32)
    with scopes.scope(scopes.ATTENTION_INDEX_SCORES):
        scores = index_scores(qi, w, ki)
    with scopes.scope(scopes.ATTENTION_INDEX_SELECT):
        # kept as bits (a byte for eight keys), read back as the mask
        bits = checkpoint_name(jnp.packbits(
            select(lax.stop_gradient(scores), t, topk), axis=1), SELECTION)
        chosen = jnp.unpackbits(bits, axis=1).astype(bool)
        bits = jnp.pad(bits, ((0, 0), (0, total // 8 - bits.shape[1])))
    with scopes.scope(scopes.ATTENTION_CORE), \
            scopes.scope(scopes.ATTENTION_CORE_SPARSE):
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
    with scopes.scope(scopes.ATTENTION_INDEX_LOSS):
        target = lax.stop_gradient(
            jnp.sum(e * share[..., None], axis=(0, 1)) / heads)
        kl = _index_loss(target, scores, chosen)
    return (o.reshape(rows, heads, d).astype(dtype), kl,
            jnp.sum(chosen, dtype=jnp.float32), bits)


def _over_bands(block, seq: int, xs, consts):
    """``block(x, consts(hi))`` over the sequence's blocks of rows, band by
    band: ``xs`` are ``[S, ..]`` arrays cut into blocks of rows, to which a
    block's first position is added; ``consts(hi)`` is what the blocks of the
    band that ends at ``hi`` share. The blocks' results, concatenated."""
    rows, bands = blocks(seq)
    per_band = seq // bands
    outs = []
    for band in range(bands):
        lo, hi = band * per_band, (band + 1) * per_band
        shared = consts(hi)
        cut = tuple(a[lo:hi].reshape((per_band // rows, rows) + a.shape[1:])
                    for a in xs)
        t0 = lo + rows * jnp.arange(per_band // rows, dtype=jnp.int32)
        outs.append(lax.map(lambda x, shared=shared: block(x, shared),
                            cut + (t0,)))
    return tuple(jnp.concatenate(parts) for parts in zip(*outs))


def _sequence(topk: int, scale: float, args):
    """One sequence's blocks, band by band, the XLA form."""
    q, k, v, qi, ki, w = args                       # [S, ..]
    seq = q.shape[0]
    k, v, ki = (a.astype(jnp.float32) for a in (k, v, ki))
    block = jax.checkpoint(
        functools.partial(_block, topk, scale, seq),
        policy=jax.checkpoint_policies.save_only_these_names(SELECTION))
    o, kl, count, bits = _over_bands(
        block, seq, (q, qi, w), lambda hi: (k[:hi], v[:hi], ki[:hi]))
    return (o.reshape((seq,) + q.shape[1:]), jnp.sum(kl), jnp.sum(count),
            bits.reshape(seq, seq // 8))


# -- the kernels' form ---------------------------------------------------------

def _call_tiles(keys: int, seq: int, block_k: int) -> int:
    """The k tiles that the kernels' calls of a band of ``keys`` keys cover:
    the sequence's, or the first half of them where the band ends there. Two
    shapes of each kernel in a program and not one a band: a kernel is traced
    and lowered for Mosaic once a shape in every run's set-up, and a grid
    step past a block's diagonal costs ~0.35 us (at 16 384 positions 448 of
    them a k/v head and layer more than a grid a band would run, of 1088
    live)."""
    tiles = seq // block_k
    half = tiles // 2
    return half if tiles % 2 == 0 and keys <= half * block_k else tiles


@functools.partial(jax.custom_vjp, nondiff_argnums=(9,))
def _index_attached(qi, w, ki, kl, placed, target, mask, rows, t0, kern):
    """``kl``, a block's summed KL as :func:`_index_loss` made it, with its
    gradient to the index's qi, w and ki: one ``hvd_index_bwd`` call
    (``ps.index_backward``) on the block's target, mask and kept rows, so
    that the backward pass makes the ``[rows, Hi, keys]`` products again in
    VMEM only and runs no score pass and no softmax of its own."""
    return kl


def _index_attached_fwd(qi, w, ki, kl, placed, target, mask, rows, t0, kern):
    return kl, (qi, w, ki, placed, target, mask, rows, t0)


def _index_attached_bwd(kern, res, ct):
    qi, w, ki, placed, target, mask, rows, t0 = res
    with scopes.scope(scopes.ATTENTION_INDEX_SCORES):
        dqi, dw, dki = ps.index_backward(qi, w, placed, target, mask, rows,
                                         ct, t0, ki.shape[0], kern)
    return (dqi, dw, dki) + (None,) * 6


_index_attached.defvjp(_index_attached_fwd, _index_attached_bwd)


def _kernel_block(topk: int, scale: float, seq: int, head_dim: int,
                  kern: ps.Kernels, x, consts):
    """One block of ``ps.ROWS`` positions of one sequence, the kernels' form:
    :func:`_block`'s results with, between the outputs and the KL, the rows'
    log-sum-exp ``[H, 1, rows]``, and last the selection as the kernels read
    it, ``[seq / block_k, 128, rows]`` (``ps.pack_selection``). q, k and v
    come flat (heads side by side) and behind ``stop_gradient``: their
    gradient is :func:`_attached`'s."""
    q, qi, w, t0 = x        # q [rows, H * D]
    k, v, ki, placed = consts   # k, v the sequence's [S, Hkv * D]; ki the
    rows = q.shape[0]           # band's, placed its ps.place_keys or None
    keys = ki.shape[0]
    t = t0 + jnp.arange(rows, dtype=jnp.int32)
    with scopes.scope(scopes.ATTENTION_INDEX_SCORES):
        scores = index_scores(qi, w, ki)
        if placed is not None:      # the gradient is _index_attached's
            scores = lax.stop_gradient(scores)
    with scopes.scope(scopes.ATTENTION_INDEX_SELECT):
        picked = select(lax.stop_gradient(scores), t, topk)
        # kept as the kernels' mask (a byte for eight keys at a tile of
        # 1024), read back for the loss
        mask = checkpoint_name(ps.pack_selection(picked, kern.block_k),
                               SELECTION)
        chosen = ps.unpack_selection(mask, kern.block_k)[:, :keys]
        tiles = _call_tiles(keys, seq, kern.block_k)
        called = jnp.pad(mask, ((0, tiles - mask.shape[0]), (0, 0), (0, 0)))
        bits = jnp.packbits(picked, axis=1)
        bits = jnp.pad(bits, ((0, 0), (0, seq // 8 - bits.shape[1])))
    with scopes.scope(scopes.ATTENTION_CORE), \
            scopes.scope(scopes.ATTENTION_CORE_SPARSE):
        o, lse = ps.sparse_forward(q, k, v, called, t0, scale=scale,
                                   head_dim=head_dim, kern=kern)
        lse = checkpoint_name(lse, STATS)
    with scopes.scope(scopes.ATTENTION_INDEX_LOSS):
        target = ps.heads_mean(q, k, lse, called, t0, scale=scale,
                               head_dim=head_dim, kern=kern)
        kl = _index_loss(target[:, :keys], scores, chosen)
        if placed is not None:
            kept = checkpoint_name(
                ps.index_rows(target, scores, chosen), INDEX_ROWS)
    if placed is not None:      # (its backward names its own scope)
        kl = _index_attached(qi, w, ki, kl, placed, target, called, kept, t0,
                             kern)
    mask = jnp.pad(called, ((0, seq // kern.block_k - tiles), (0, 0), (0, 0)))
    return o, lse, kl, jnp.sum(chosen, dtype=jnp.float32), bits, mask


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7))
def _attached(q, k, v, o, lse, mask, scale, kern):
    """``o``, the sequence's outputs as the blocks' ``hvd_sparse_fwd`` calls
    made them, with its gradient to q, k and v: one ``hvd_sparse_bwd`` call
    on the blocks' rows (``lse``) and masks."""
    return o


def _attached_fwd(q, k, v, o, lse, mask, scale, kern):
    return o, (q, k, v, o, lse, mask)


def _attached_bwd(scale, kern, res, do):
    q, k, v, o, lse, mask = res
    with scopes.scope(scopes.ATTENTION_CORE), \
            scopes.scope(scopes.ATTENTION_CORE_SPARSE):
        dq, dk, dv = ps.sparse_backward(q, k, v, o, lse, mask, do, scale,
                                        kern)
    return dq, dk, dv, None, None, None


_attached.defvjp(_attached_fwd, _attached_bwd)


def _kernel_sequence(topk: int, scale: float, kern: ps.Kernels, args):
    """One sequence's blocks, band by band, the kernels' form; then the
    outputs' one custom_vjp."""
    q, k, v, qi, ki, w = args                       # [S, ..]
    seq, heads, head_dim = q.shape
    ki = ki.astype(jnp.float32)
    block = jax.checkpoint(
        functools.partial(_kernel_block, topk, scale, seq, head_dim, kern),
        policy=jax.checkpoint_policies.save_only_these_names(
            SELECTION, STATS, INDEX_ROWS))
    # flat once, here: a reshape inside the map is a copy of k and v a block
    flat_q, flat_k, flat_v = (lax.stop_gradient(a.reshape(seq, -1))
                              for a in (q, k, v))
    index_kernel = ps.index_kernel_shapes(ps.ROWS, *qi.shape[1:])

    def consts(hi):     # the band's index keys, and as hvd_index_bwd reads them
        placed = lax.stop_gradient(ps.place_keys(
            ki[:hi].astype(qi.dtype), qi.shape[2],
            _call_tiles(hi, seq, kern.block_k) * kern.block_k)
        ) if index_kernel else None
        return flat_k, flat_v, ki[:hi], placed
    o, lse, kl, count, bits, mask = _over_bands(
        block, seq, (flat_q, qi, w), consts)
    o = checkpoint_name(o.reshape(q.shape), OUTPUT)
    lse = lse.transpose(1, 2, 0, 3).reshape(heads, 1, seq)
    return (_attached(q, k, v, o, lse, mask, scale, kern), jnp.sum(kl),
            jnp.sum(count), bits.reshape(seq, seq // 8))


def sparse_path(seq: int, heads: int, kv_heads: int, head_dim: int) -> str:
    """Which form :func:`indexed_attention` takes, from the backend and the
    shape alone: ``"pallas"`` (``ops/pallas_sparse_attention.py``) on a TPU
    at whole blocks of 128 positions, heads of whole lane tiles and whole
    groups, else ``"xla"``."""
    if (jax.default_backend() == "tpu" and seq % ps.ROWS == 0
            and head_dim % ps.MIN_BLOCK == 0 and heads % kv_heads == 0):
        return "pallas"
    return "xla"


def indexed_attention(q, k, v, qi, ki, w, topk: int, scale: float,
                      kernels: Optional[ps.Kernels] = None):
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
    reaches ``qi``, ``ki``, ``w``; neither reaches the other three.
    :func:`sparse_path` says which form runs; ``kernels`` overrides it
    (tests: the kernels in interpret mode, a smaller k tile)."""
    if q.shape[1] % 8:
        raise ValueError(f"a sequence of {q.shape[1]} positions: the "
                         "selection's bits are whole bytes a row")
    if kernels is None and sparse_path(q.shape[1], q.shape[2], k.shape[2],
                                       q.shape[3]) == "pallas":
        kernels = ps.Kernels(ps.key_tile(q.shape[1]))
    sequence = (functools.partial(_sequence, topk, scale) if kernels is None
                else functools.partial(_kernel_sequence, topk, float(scale),
                                       kernels))
    o, kl, count, bits = lax.map(sequence, (q, k, v, qi, ki, w))
    tokens = q.shape[0] * q.shape[1]
    kl = jnp.sum(kl) / tokens
    if kernels is None:
        o, kl = checkpoint_name((o, kl), OUTPUT)
    else:       # (o is named where the custom_vjp keeps it)
        kl = checkpoint_name(kl, OUTPUT)
    return o, kl, jnp.sum(count) / tokens, bits
