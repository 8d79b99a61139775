"""The sparse attention core as Pallas TPU kernels: causal grouped-query flash
attention under a per-query selection of keys (``ops/sparse_attention.py``
makes the selection; this module reads it).

Three kernels of the core in ``ops/pallas_attention.py``'s form (scores
transposed, ``sT = k @ qT`` as ``[k rows, q rows]``, a q row's softmax state
along the lanes, a key tile in pieces of 128 k rows, a piece's score matmul
written before the piece before it is reduced), with two differences that
make them bodies of their own:

**The group is on the rows.** A grid step takes ``ROWS`` = 128 query positions
of ALL the ``G`` query heads of one k/v head: the q operand is ``[G * 128,
D]`` (the heads' ``[128, D]`` lane columns of ``[S, H * D]`` stacked once a
step), so a piece's scores are ``[128 keys, G * 128]``, the MXU has ``G``
times a head's work for a step's ~0.35 us, k and v are read once a group, and
the backward's ``dv += pT @ do`` and ``dk += dsT @ q`` contract over the
group's rows: the ``G`` heads add into one dk / dv tile.

**The mask is an operand, a bit a (query, key).** A query's keys are its own
(``topk`` of its causal keys), so no tile is clean and none can be skipped by
its place. The selection reaches the kernels as ``[q blocks, k tiles, 128,
128]`` int8 (:func:`pack_selection`): of k tile ``j`` and q block ``i`` the
byte at ``[c, r]`` holds, in bit ``n``, whether query ``i * 128 + r`` selected
key ``j * block_k + n * 128 + c``: piece ``n`` of the tile reads its ``[128
keys, 128 queries]`` mask as ``byte & (1 << n)``, an elementwise op on the
tile as it lies (nothing moves across lanes or sublanes), once for the ``G``
heads, and adds it to the scores as a bias of 0 or ``NEG_INF``. At a k tile
of 1024 that is a byte for eight keys: ``S * S / 8`` bytes a layer, what
``jnp.packbits`` along the keys takes.

``hvd_sparse_fwd`` (o and the rows' log-sum-exp) and ``hvd_sparse_mean`` (the
heads' mean attention ``p``, the indexer's target) run a block of 128 query
positions a call, inside the map that selects: grid (k/v heads, k tiles) and
(k tiles, k/v heads). The block's first position is a scalar-prefetch
operand: k tiles past the diagonal run nothing and fetch nothing.
``hvd_sparse_bwd`` runs once a sequence, ``hvd_flash_bwd``'s form: grid (k/v
heads, q ranges, k tiles, q blocks of a range), dk and dv of a k tile in VMEM
over the q blocks that see it and written once, a range's float32 dq (all
``G`` heads') resident over its k tiles.

**The pieces are unrolled.** v5e, a layer at ``[16384, 32 on 4, 128]`` under
2048 keys a query, the host's clock round the calls alone: the forward's 128
calls 18.91 ms, the mean's 13.11, the backward (with ``hvd_flash_adj`` and the
ranges' sums) 36.06. With the pieces as a ``lax.fori_loop`` that carries the
next piece's scores and stops at the diagonal tile's last live piece (the
unrolled form runs all eight there: 5 % of the pieces) 26.20 / 17.84 / 48.62:
the scheduler sees one iteration at a time and the MXU waits for the vector
unit again (PERF.md, PR 65).

``hvd_index_bwd`` is the indexer's: the backward of its score pass and of the
loss on it, a block of 128 query positions a call inside the transposed map
(:func:`index_backward`; grid (k tiles), the same scalar-prefetch skip of
the tiles past the diagonal). Of the score pass ``I[t, s] = sum_j w[t, j]
relu(qI[t, j] . kI[s])`` the ``[16 heads, 128 rows, keys]`` float32 products
are 134 MB a block at 16 384 keys: XLA fuses them away in the forward pass
and autodiff writes them in the backward. The kernel makes them again a
piece of 128 keys at a time in the sparse kernels' form (keys on the
sublanes, the stacked heads' positions on the lanes), the scores ``I`` and
the KL's gradient ``dI`` from them, and writes the three gradients; neither
the products nor ``dI`` reach HBM.

**A row with no selected key in its first tiles** (causal flash has none:
every row's first tile holds key 0). The running max starts at ``M_FLOOR`` =
-1e20, far above a masked score's ``NEG_INF`` = -1e30 and far below any real
score: ``exp(NEG_INF - M_FLOOR)`` is 0 where ``exp(NEG_INF - NEG_INF)`` would
be 1 and count masked keys into the row's sum. The mean and the backward
subtract the finished ``lse``, which is finite.
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from horovod_tpu.ops.pallas_attention import (
    BWD_AHEAD, BWD_VMEM_BUDGET, MIN_BLOCK, NEG_INF, TILES, _NN, _NT, _TN,
    _dot, _flash_adj_local, _group)
from horovod_tpu.profiling.compile_watch import kernel_call

FWD_NAME = "hvd_sparse_fwd"
MEAN_NAME = "hvd_sparse_mean"
BWD_NAME = "hvd_sparse_bwd"
INDEX_BWD_NAME = "hvd_index_bwd"

#: query positions of a block: the lanes of a mask tile
ROWS = MIN_BLOCK
#: k rows of a piece of a tile: the sublanes of a mask tile, one bit of its
#: bytes
PIECE = MIN_BLOCK
#: where a row's running max starts (the module docstring's last paragraph)
M_FLOOR = -1e20


class Kernels(NamedTuple):
    """What the calls are compiled for beside their shapes: the k tile
    (:func:`key_tile`; a smaller one for tests) and interpret mode (tests
    off the TPU)."""
    block_k: int
    interpret: bool = False


def key_tile(seq: int) -> int:
    """The k tile of a sequence of ``seq`` positions: the largest of
    ``TILES`` that divides it, at most eight pieces (a byte's bits)."""
    if seq % MIN_BLOCK:
        raise ValueError(f"seq={seq} must be a multiple of {MIN_BLOCK}")
    return next(t for t in TILES if seq % t == 0)


def pack_selection(chosen, block_k: int):
    """``chosen`` ``[ROWS, keys]`` bool (a block's selection) as the kernels'
    mask ``[tiles, PIECE, ROWS]`` int8 (the module docstring), ``keys`` padded
    with unselected keys to whole tiles of ``block_k``."""
    rows, keys = chosen.shape
    tiles = -(-keys // block_k)
    x = jnp.pad(chosen, ((0, 0), (0, tiles * block_k - keys)))
    x = x.reshape(rows, tiles, block_k // PIECE, PIECE).astype(jnp.uint8)
    bit = jnp.arange(block_k // PIECE, dtype=jnp.uint8)[None, None, :, None]
    words = jnp.sum(x << bit, axis=2, dtype=jnp.uint8)  # [rows, tiles, PIECE]
    return lax.bitcast_convert_type(words.transpose(1, 2, 0), jnp.int8)


def unpack_selection(mask, block_k: int):
    """:func:`pack_selection`'s ``[tiles, PIECE, ROWS]`` back as ``[ROWS,
    tiles * block_k]`` bool."""
    tiles, _, rows = mask.shape
    words = lax.bitcast_convert_type(mask, jnp.uint8).transpose(2, 0, 1)
    bit = jnp.arange(block_k // PIECE, dtype=jnp.uint8)[None, None, :, None]
    live = (words[:, :, None, :] >> bit) & jnp.uint8(1)
    return live.reshape(rows, tiles * block_k).astype(bool)


def _group_rows(ref, group: int, D: int):
    """A ``[ROWS, group * D]`` block (the group's heads side by side on the
    lanes) as ``[group * ROWS, D]``: head ``g``'s rows at ``[g * ROWS, (g +
    1) * ROWS)``."""
    if group == 1:
        return ref[...]
    return jnp.concatenate(
        [ref[:, g * D:(g + 1) * D] for g in range(group)], axis=0)


def _group_lanes(ref, group: int):
    """A ``[group, 1, ROWS]`` block of rows (a head each) as ``[1, group *
    ROWS]``, in :func:`_group_rows`' order."""
    if group == 1:
        return ref[0]
    return jnp.concatenate([ref[g] for g in range(group)], axis=1)


def _bias(words, n: int, group: int):
    """Piece ``n``'s mask as a float32 bias ``[PIECE, group * ROWS]``: 0 at a
    selected key and ``NEG_INF`` elsewhere, of the tile's mask bytes
    ``words`` ``[PIECE, ROWS]`` (int32), once for the group's heads."""
    bias = jnp.where((words & (1 << n)) != 0, 0.0, NEG_INF)
    return bias if group == 1 else jnp.concatenate([bias] * group, axis=1)


def _last_tile(t0, block_k: int):
    """The last k tile with a key at or before the last of the ``ROWS``
    positions from ``t0``."""
    return (t0 + ROWS - 1) // block_k


def _sparse_fwd_kernel(t0_ref, q_ref, k_ref, v_ref, mask_ref, o_ref, lse_ref,
                       qc_ref, acc_ref, m_ref, l_ref, *, scale: float,
                       block_k: int, group: int):
    """One (k/v head, k tile) step of a block of ``ROWS`` positions; k tile
    innermost. ``qc_ref`` holds the group's q rows stacked, ``[G * ROWS,
    D]``; ``m_ref`` / ``l_ref`` ``[1, G * ROWS]`` and ``acc_ref`` ``[D, G *
    ROWS]`` are ``_flash_kernel``'s state, a head's rows a lane tile."""
    j = pl.program_id(1)
    D = k_ref.shape[1]

    @pl.when(j == 0)
    def _init():
        qc_ref[...] = _group_rows(q_ref, group, D)
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, M_FLOOR)
        l_ref[...] = jnp.zeros_like(l_ref)

    @pl.when(j <= _last_tile(t0_ref[0], block_k))
    def _tile():
        words = mask_ref[0].astype(jnp.int32)

        def scores(n):
            st = _dot(k_ref[pl.ds(n * PIECE, PIECE)], qc_ref[...], _NT)
            return st * scale + _bias(words, n, group)

        def update(n, st):
            v = v_ref[pl.ds(n * PIECE, PIECE)]
            m_prev = m_ref[...]
            m_next = jnp.maximum(m_prev, jnp.max(st, axis=0, keepdims=True))
            pt = jnp.exp(st - m_next)
            alpha = jnp.exp(m_prev - m_next)
            l_ref[...] = l_ref[...] * alpha + jnp.sum(pt, axis=0,
                                                      keepdims=True)
            m_ref[...] = m_next
            acc_ref[...] = acc_ref[...] * alpha + _dot(
                v, pt.astype(v.dtype), _TN)
        pieces = block_k // PIECE
        st = scores(0)
        for n in range(pieces):
            ahead = scores(n + 1) if n + 1 < pieces else None
            update(n, st)
            st = ahead

    @pl.when(j == pl.num_programs(1) - 1)
    def _finalize():
        l_safe = jnp.maximum(l_ref[...], 1e-30)
        o = acc_ref[...] / l_safe
        lse = m_ref[...] + jnp.log(l_safe)
        for g in range(group):
            at = slice(g * ROWS, (g + 1) * ROWS)
            o_ref[:, g * D:(g + 1) * D] = jnp.transpose(
                o[:, at]).astype(o_ref.dtype)
            lse_ref[g] = lse[:, at]


def _block_operands(q, k, mask, head_dim: int, kern: Kernels):
    """(heads, k/v heads, group, k tiles of the call) of a block's call,
    checked."""
    D = head_dim
    if (q.shape[0] != ROWS or D % MIN_BLOCK or q.shape[1] % D
            or k.shape[1] % D or k.shape[0] % kern.block_k):
        raise ValueError(
            f"q {q.shape} against k {k.shape} at heads of {D} and a k tile "
            f"of {kern.block_k}: a block is {ROWS} positions, a head whole "
            "lane tiles, the keys whole tiles")
    if mask.shape[1:] != (PIECE, ROWS) or mask.dtype != jnp.int8:
        raise ValueError(f"a mask of {mask.dtype}{mask.shape}")
    H, Hkv = q.shape[1] // D, k.shape[1] // D
    return H, Hkv, _group(H, Hkv), mask.shape[0]


# jitted (this and :func:`heads_mean`) so that a program's call sites of one
# shape share one trace of the kernel and one Mosaic lowering: both are paid
# in every run's set-up, compile cache or none
@functools.partial(jax.jit, static_argnames=("scale", "head_dim", "kern"))
def sparse_forward(q, k, v, mask, t0, *, scale: float, head_dim: int,
                   kern: Kernels):
    """(o ``[ROWS, H * D]``, lse ``[H, 1, ROWS]`` float32) of the block of
    positions ``t0 .. t0 + ROWS`` of one sequence: q ``[ROWS, H * D]``, k and
    v ``[S, Hkv * D]`` (the arrays the projections wrote, a head ``D =
    head_dim`` lanes: the caller flattens them once, outside the map over
    the blocks, so that no copy of k and v is made a block), ``mask``
    :func:`pack_selection`'s of the block over the sequence's first
    ``mask.shape[0]`` k tiles (the grid's), ``t0`` an int32 scalar. Not
    differentiable: the gradient is :func:`sparse_backward`'s, a sequence at
    a time."""
    D, bk = head_dim, kern.block_k
    H, Hkv, group, tiles = _block_operands(q, k, mask, D, kern)

    def k_at(h, j, t0_ref):
        return (jnp.minimum(j, _last_tile(t0_ref[0], bk)), h)
    wide = pl.BlockSpec((ROWS, group * D), lambda h, j, t0_ref: (0, h))
    return kernel_call(pl.pallas_call,
        functools.partial(_sparse_fwd_kernel, scale=scale, block_k=bk,
                          group=group),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(Hkv, tiles),
            in_specs=[
                wide,
                pl.BlockSpec((bk, D), k_at),
                pl.BlockSpec((bk, D), k_at),
                pl.BlockSpec((1, PIECE, ROWS),
                             lambda h, j, t0_ref: (k_at(h, j, t0_ref)[0],
                                                   0, 0)),
            ],
            out_specs=[
                wide,
                pl.BlockSpec((group, 1, ROWS), lambda h, j, t0_ref: (h, 0, 0)),
            ],
            scratch_shapes=[
                pltpu.VMEM((group * ROWS, D), q.dtype),         # q stacked
                pltpu.VMEM((D, group * ROWS), jnp.float32),     # acc
                pltpu.VMEM((1, group * ROWS), jnp.float32),     # m
                pltpu.VMEM((1, group * ROWS), jnp.float32),     # l
            ]),
        out_shape=[
            jax.ShapeDtypeStruct((ROWS, H * D), q.dtype),
            jax.ShapeDtypeStruct((H, 1, ROWS), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=kern.interpret,
        name=FWD_NAME,
    )(jnp.reshape(t0, (1,)).astype(jnp.int32), q, k, v, mask)


def _sparse_mean_kernel(t0_ref, q_ref, k_ref, lse_ref, mask_ref, p_ref,
                        acc_ref, *, scale: float, block_k: int, group: int,
                        heads: int):
    """One (k tile, k/v head) step of a block of ``ROWS`` positions; the
    k/v head innermost: the tile's ``pT`` summed over the heads in
    ``acc_ref`` ``[block_k, ROWS]``, transposed on its way out."""
    j, h = pl.program_id(0), pl.program_id(1)
    D = k_ref.shape[1]
    pieces = block_k // PIECE

    @pl.when(h == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(j <= _last_tile(t0_ref[0], block_k))
    def _tile():
        qc = _group_rows(q_ref, group, D)
        lse = _group_lanes(lse_ref, group)
        words = mask_ref[0].astype(jnp.int32)

        def scores(n):
            st = _dot(k_ref[pl.ds(n * PIECE, PIECE)], qc, _NT)
            return st * scale + _bias(words, n, group)

        def add(n, st):
            pt = jnp.exp(st - lse)
            acc_ref[pl.ds(n * PIECE, PIECE)] += functools.reduce(
                jnp.add, (pt[:, g * ROWS:(g + 1) * ROWS]
                          for g in range(group)))
        st = scores(0)
        for n in range(pieces):
            ahead = scores(n + 1) if n + 1 < pieces else None
            add(n, st)
            st = ahead

    @pl.when(h == pl.num_programs(1) - 1)
    def _write():
        for n in range(pieces):
            at = pl.ds(n * PIECE, PIECE)
            p_ref[:, at] = jnp.transpose(acc_ref[at]) * (1.0 / heads)


@functools.partial(jax.jit, static_argnames=("scale", "head_dim", "kern"))
def heads_mean(q, k, lse, mask, t0, *, scale: float, head_dim: int,
               kern: Kernels):
    """``p[r, s] = (1 / H) sum_h exp(q[r, h] . k[s, h // G] * scale - lse[h,
    r])`` at the block's selected keys and 0 elsewhere, float32 ``[ROWS,
    tiles * block_k]``: the heads' mean attention of :func:`sparse_forward`'s
    block (its operands; ``lse`` its second result). Not differentiable (a
    target)."""
    D, bk = head_dim, kern.block_k
    H, Hkv, group, tiles = _block_operands(q, k, mask, D, kern)

    def tile(j, t0_ref):
        return jnp.minimum(j, _last_tile(t0_ref[0], bk))
    return kernel_call(pl.pallas_call,
        functools.partial(_sparse_mean_kernel, scale=scale, block_k=bk,
                          group=group, heads=H),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(tiles, Hkv),
            in_specs=[
                pl.BlockSpec((ROWS, group * D), lambda j, h, t0_ref: (0, h)),
                pl.BlockSpec((bk, D),
                             lambda j, h, t0_ref: (tile(j, t0_ref), h)),
                pl.BlockSpec((group, 1, ROWS), lambda j, h, t0_ref: (h, 0, 0)),
                pl.BlockSpec((1, PIECE, ROWS),
                             lambda j, h, t0_ref: (tile(j, t0_ref), 0, 0)),
            ],
            out_specs=pl.BlockSpec((ROWS, bk), lambda j, h, t0_ref: (0, j)),
            scratch_shapes=[pltpu.VMEM((bk, ROWS), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((ROWS, tiles * bk), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=kern.interpret,
        name=MEAN_NAME,
    )(jnp.reshape(t0, (1,)).astype(jnp.int32), q, k, lse, mask)


# ---------------------------------------------------------------------------
# The backward: one call a sequence, k tile outer, q block inner
# ---------------------------------------------------------------------------

def sparse_bwd_vmem_bytes(block_k: int, rows: int, group: int, D: int,
                          itemsize: int) -> int:
    """Working set of one grid step of the backward, as
    ``flash_bwd_vmem_bytes`` counts it: the q, do, k, v blocks and the dq, dk,
    dv output blocks double-buffered, the float32 accumulators (a range's
    dq, ``rows`` positions of ``group`` heads), the stacked q and do, and the
    pieces in flight (``sT``, ``dpT``, ``pT``, ``dsT``, two pieces of
    each)."""
    wide = group * ROWS
    io = 2 * (2 * wide + 2 * block_k) * D * itemsize + 2 * block_k * ROWS
    out = 2 * (2 * block_k + group * rows) * D * itemsize
    scratch = (2 * block_k + group * rows) * D * 4
    stacked = 2 * wide * D * itemsize
    pieces = 2 * PIECE * wide * (4 * 4 + 2 * itemsize)
    return io + out + scratch + stacked + pieces


def sparse_bwd_rows(seq: int, block_k: int, group: int, D: int,
                    dtype) -> int:
    """The positions of a q range of the backward: the sequence in the
    fewest equal ranges of whole blocks whose working set fits
    ``BWD_VMEM_BUDGET``, which the call asks for as its scoped-VMEM limit
    (2048 at the cell's 16 384 positions, 8 heads a group of 128)."""
    itemsize = jnp.dtype(dtype).itemsize
    units = seq // ROWS
    return seq // next(
        n for n in range(1, units + 1) if units % n == 0 and (
            n == units or sparse_bwd_vmem_bytes(
                block_k, seq // n, group, D, itemsize) <= BWD_VMEM_BUDGET))


def _sparse_bwd_kernel(q_ref, do_ref, k_ref, v_ref, lse_ref, adj_ref,
                       mask_ref, dq_ref, dk_ref, dv_ref, dq_acc, dk_acc,
                       dv_acc, *, scale: float, block_k: int, group: int):
    """One (k tile, q block) step; grid (k/v heads, q ranges, k tiles, q
    blocks of a range), the q block innermost: dk and dv of the k tile
    accumulate over the q blocks (and, in the matmuls' contraction, over the
    group's heads), dq of the range's positions over the k tiles, transposed
    (``dq_acc`` ``[q blocks of a range, D, G * ROWS]``)."""
    r, j, i = pl.program_id(1), pl.program_id(2), pl.program_id(3)
    nk, nq = pl.num_programs(2), pl.num_programs(3)
    D = k_ref.shape[1]
    qi = r * nq + i                           # the q block in the sequence
    last_kj = jnp.minimum(_last_tile(qi * ROWS, block_k), nk - 1)

    @pl.when(i == 0)
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    @pl.when(j <= last_kj)
    def _tile():
        qc = _group_rows(q_ref, group, D)
        doc = _group_rows(do_ref, group, D)
        lse = _group_lanes(lse_ref, group)
        adj = _group_lanes(adj_ref, group)
        words = mask_ref[0, 0].astype(jnp.int32)

        def ahead(n):
            ks = pl.ds(n * PIECE, PIECE)
            st = _dot(k_ref[ks], qc, _NT) * scale + _bias(words, n, group)
            return st, _dot(v_ref[ks], doc, _NT)

        def finish(n, st, dpt):
            ks = pl.ds(n * PIECE, PIECE)
            pt = jnp.exp(st - lse)
            dv_acc[ks] += _dot(pt.astype(doc.dtype), doc, _NN)
            # d loss / d s = p * (dp - adj); the scale goes on dq and dk as
            # they are written
            dst = (pt * (dpt - adj)).astype(qc.dtype)
            dk_acc[ks] += _dot(dst, qc, _NN)
            dq = _dot(k_ref[ks], dst, _TN)              # [D, G * ROWS]
            if n == 0:      # every q block meets the first k tile
                @pl.when(j == 0)
                def _first():
                    dq_acc[i] = dq

                @pl.when(j > 0)
                def _later():
                    dq_acc[i] += dq
            else:
                dq_acc[i] += dq
        issued = []
        for n in range(block_k // PIECE):
            for nxt in range(len(issued), min(n + 1 + BWD_AHEAD,
                                              block_k // PIECE)):
                issued.append(ahead(nxt))
            finish(n, *issued[n])

    @pl.when(j == last_kj)
    def _write_dq():
        dq = dq_acc[i] * scale
        rows = pl.ds(pl.multiple_of(i * ROWS, ROWS), ROWS)
        for g in range(group):
            dq_ref[rows, g * D:(g + 1) * D] = jnp.transpose(
                dq[:, g * ROWS:(g + 1) * ROWS]).astype(dq_ref.dtype)

    @pl.when(i == nq - 1)
    def _write_dkv():
        dk_ref[0] = (dk_acc[...] * scale).astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[...].astype(dv_ref.dtype)


@functools.partial(jax.jit, static_argnames=("H", "scale", "kern"))
def _sparse_bwd_local(q, k, v, do, lse, adj, mask, *, H, scale, kern):
    """(dq ``[S, H*D]``, dk and dv ``[ranges, S, Hkv*D]``) of q, do ``[S,
    H*D]`` and k, v ``[S, Hkv*D]``, lse and adj ``[H, 1, S]`` float32 and the
    sequence's mask ``[S / ROWS, S / block_k, PIECE, ROWS]``."""
    S, M = q.shape
    D = M // H
    Hkv = k.shape[1] // D
    group = _group(H, Hkv)
    bk = kern.block_k
    rows = sparse_bwd_rows(S, bk, group, D, q.dtype)
    nq = rows // ROWS

    def q_block(r, j, i):
        # above the diagonal the block index repeats the k tile's first
        # live q block: no DMA for a skipped step
        return jnp.clip(j * bk // ROWS, r * nq + i, r * nq + nq - 1)
    wide = pl.BlockSpec((ROWS, group * D),
                        lambda h, r, j, i: (q_block(r, j, i), h))
    tile = pl.BlockSpec((bk, D), lambda h, r, j, i: (j, h))
    row = pl.BlockSpec((group, 1, ROWS),
                       lambda h, r, j, i: (h, 0, q_block(r, j, i)))
    part_spec = pl.BlockSpec((1, bk, D), lambda h, r, j, i: (r, j, h))
    part = jax.ShapeDtypeStruct((S // rows,) + k.shape, k.dtype)
    return kernel_call(pl.pallas_call,
        functools.partial(_sparse_bwd_kernel, scale=scale, block_k=bk,
                          group=group),
        grid=(Hkv, S // rows, S // bk, nq),
        in_specs=[wide, wide, tile, tile, row, row,
                  pl.BlockSpec((1, 1, PIECE, ROWS),
                               lambda h, r, j, i: (q_block(r, j, i), j, 0,
                                                   0))],
        out_specs=[pl.BlockSpec((rows, group * D),
                                lambda h, r, j, i: (r, h)),
                   part_spec, part_spec],
        out_shape=[jax.ShapeDtypeStruct(q.shape, q.dtype), part, part],
        scratch_shapes=[
            pltpu.VMEM((nq, D, group * ROWS), jnp.float32),     # dqT
            pltpu.VMEM((bk, D), jnp.float32),                   # dk
            pltpu.VMEM((bk, D), jnp.float32),                   # dv
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary",
                                 "arbitrary"),
            vmem_limit_bytes=BWD_VMEM_BUDGET),
        interpret=kern.interpret,
        name=BWD_NAME,
    )(q, do, k, v, lse, adj, mask)


def sparse_backward(q, k, v, o, lse, mask, do, scale: float, kern: Kernels
                    ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """(dq, dk, dv) of one sequence's sparse core from its residuals: q, o,
    do ``[S, H, D]``, k, v ``[S, Hkv, D]``, ``lse`` ``[H, 1, S]`` float32
    (the blocks' :func:`sparse_forward` rows side by side) and ``mask`` ``[S
    / ROWS, S / block_k, PIECE, ROWS]`` (the blocks' masks over the whole
    sequence's k tiles). ``p = exp(s - lse)`` is made again a tile at a time
    in VMEM, as ``flash_backward`` does; the row term ``sum_d do * o`` is
    ``hvd_flash_adj``'s. dk and dv come a q range (in k's dtype) and are
    summed here in float32."""
    S, H, D = q.shape
    Hkv = k.shape[1]
    wide = (1, S, H * D)
    adj = _flash_adj_local(do.reshape(wide), o.reshape(wide),
                           jnp.zeros((H, 1, S), jnp.float32), H=H,
                           interpret=kern.interpret)
    dq, dk, dv = _sparse_bwd_local(
        q.reshape(S, H * D), k.reshape(S, Hkv * D), v.reshape(S, Hkv * D),
        do.reshape(S, H * D), lse, adj, mask, H=H, scale=scale, kern=kern)

    def total(parts):
        if parts.shape[0] > 1:
            parts = parts.astype(jnp.float32).sum(0).astype(parts.dtype)
        return parts.reshape(S, Hkv, D)
    return dq.reshape(S, H, D), total(dk), total(dv)


# ---------------------------------------------------------------------------
# The indexer's score pass, backward: one call a block of positions
# ---------------------------------------------------------------------------

def index_kernel_shapes(rows: int, heads: int, dim: int) -> bool:
    """Whether :func:`index_backward` takes a block's index queries ``[rows,
    heads, dim]``: a block of ``ROWS`` positions, an index head a whole
    sublane tile wide and a whole share of a lane tile, the heads whole lane
    tiles of ``[rows, heads * dim]`` (16 heads of 64: two a tile)."""
    return (rows == ROWS and dim % 8 == 0 and dim <= MIN_BLOCK
            and MIN_BLOCK % dim == 0 and heads % (MIN_BLOCK // dim) == 0)


def _index_bwd_kernel(t0_ref, q_ref, w_ref, k_ref, p_ref, mask_ref, rows_ref,
                      dq_ref, dw_ref, dk_ref, qc_ref, wl_ref, dq_acc, dw_acc,
                      *, block_k: int, dim: int):
    """One k tile of a block of ``ROWS`` positions. The index queries come
    transposed, ``[heads * dim, ROWS]`` (a head's channels on the sublanes,
    the positions on the lanes); ``pack`` = 128 / ``dim`` heads are a sublane
    tile ``[128, ROWS]`` of it and ``qc_ref`` holds the block's ``T`` tiles
    side by side, ``[128, T * ROWS]``. Head ``t * pack + p``'s products are a
    matmul of the whole tile against the keys laid in lanes ``[p * dim, (p +
    1) * dim)`` and zeros elsewhere (``k_ref[p]``: the other heads' channels
    meet zeros, and a contraction of ``dim`` fills no more of the MXU than
    one of 128), so a piece of 128 keys is ``pack`` units ``s[p]`` of ``[128
    keys, T * ROWS]`` float32, keys on the sublanes, a head's positions a
    lane tile, and nothing crosses lanes: ``w`` is a lane row (``wl_ref[p]``),
    and what is ``[128 keys, ROWS]`` (the piece's mask bits, ``p`` transposed
    here once a piece, the rows' three statistics) repeats over the ``T``
    tiles. A piece's scores are written one piece ahead of its vector work.
    From them the index scores ``I = sum_heads w relu(s)``, ``dI = ct (sum_p
    exp(I - lse) - p)`` at the selected keys, and a unit's two more matmuls:
    ``dk = g @ qc^T`` (``[128 keys, 128]``, of which lanes ``p``'s are the
    keys' gradient; written transposed, the keys on the lanes as the index
    keys lie in HBM) and ``dq += k_ref[p]^T @ g`` (``[128, T * ROWS]``: rows
    ``p``'s, the others add zeros), which is the q operand's own form."""
    j = pl.program_id(0)
    pack = MIN_BLOCK // dim
    tiles = qc_ref.shape[1] // ROWS
    pieces = block_k // PIECE

    @pl.when(j == 0)
    def _init():
        for t in range(tiles):
            at = slice(t * ROWS, (t + 1) * ROWS)
            qc_ref[:, at] = q_ref[t * MIN_BLOCK:(t + 1) * MIN_BLOCK]
            for p in range(pack):
                wl_ref[p, :, at] = w_ref[t * pack + p]
        dq_acc[...] = jnp.zeros_like(dq_acc)
        dw_acc[...] = jnp.zeros_like(dw_acc)

    @pl.when(j <= _last_tile(t0_ref[0], block_k))
    def _tile():
        lane = lax.broadcasted_iota(jnp.int32, (PIECE, MIN_BLOCK), 1)
        words = mask_ref[0].astype(jnp.int32)
        lse, mass, ct = rows_ref[0:1], rows_ref[1:2], rows_ref[2:3]

        def scores(n):
            return [_dot(k_ref[p, pl.ds(n * PIECE, PIECE)], qc_ref[...], _NN)
                    for p in range(pack)]

        def over_tiles(x):
            return x if tiles == 1 else jnp.concatenate([x] * tiles, axis=1)
        s = scores(0)
        for n in range(pieces):
            ahead = scores(n + 1) if n + 1 < pieces else None
            ks = pl.ds(n * PIECE, PIECE)
            weighted = [jnp.maximum(s[p], 0.0) * wl_ref[p]
                        for p in range(pack)]
            index = functools.reduce(jnp.add, (
                x[:, t * ROWS:(t + 1) * ROWS]
                for x in weighted for t in range(tiles)))   # [keys, ROWS]
            e = jnp.where((words & (1 << n)) != 0, jnp.exp(index - lse), 0.0)
            di = over_tiles(mass * e - ct * jnp.transpose(p_ref[:, ks]))
            for p in range(pack):
                live = jnp.where(s[p] > 0, di, 0.0)         # relu'(0) = 0
                dw_acc[p] += jnp.sum(s[p] * live, axis=0, keepdims=True)
                g = (live * wl_ref[p]).astype(qc_ref.dtype)
                part = _dot(g, qc_ref[...], _NT)            # [keys, 128]
                dk = part if p == 0 else jnp.where(lane >= p * dim, part, dk)
                dq_acc[...] += _dot(k_ref[p, ks], g, _TN)
            dk_ref[:, ks] = jnp.transpose(dk)
            s = ahead

    @pl.when(j == pl.num_programs(0) - 1)
    def _write():
        for t in range(tiles):
            at = slice(t * ROWS, (t + 1) * ROWS)
            dq_ref[t * MIN_BLOCK:(t + 1) * MIN_BLOCK] = dq_acc[:, at].astype(
                dq_ref.dtype)
            for p in range(pack):
                dw_ref[t * pack + p] = dw_acc[p, :, at]


# jitted on the call's own operands (every one already in the call's shape):
# a program's call sites (a band's map each) share one trace and one Mosaic
# lowering a shape
@functools.partial(jax.jit, static_argnames=("dim", "kern"))
def _index_bwd_local(t0, q, w, k, p, mask, rows, *, dim: int, kern: Kernels):
    """(dq ``[heads * dim, ROWS]`` in q's dtype, dw ``[heads, 1, ROWS]`` and
    dk ``[128, span]`` float32: the keys' gradient of heads ``p``'s in rows
    ``[p * dim, (p + 1) * dim)``) of q ``[heads * dim, ROWS]``, w ``[heads, 1,
    ROWS]`` float32, k ``[pack, span, 128]`` (:func:`place_keys`), p ``[ROWS,
    span]`` float32, ``mask`` ``[span / block_k, PIECE, ROWS]`` and ``rows``
    ``[3, ROWS]``. dk's columns past the block's last live tile are not
    written."""
    bk = kern.block_k
    pack, span, _ = k.shape
    wide = q.shape[0] // MIN_BLOCK * ROWS

    def tile(j, t0_ref):
        return jnp.minimum(j, _last_tile(t0_ref[0], bk))

    def whole(shape):
        return pl.BlockSpec(shape, lambda j, t0_ref: (0,) * len(shape))
    return kernel_call(pl.pallas_call,
        functools.partial(_index_bwd_kernel, block_k=bk, dim=dim),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(span // bk,),
            in_specs=[
                whole(q.shape),
                whole(w.shape),
                pl.BlockSpec((pack, bk, MIN_BLOCK),
                             lambda j, t0_ref: (0, tile(j, t0_ref), 0)),
                pl.BlockSpec((ROWS, bk),
                             lambda j, t0_ref: (0, tile(j, t0_ref))),
                pl.BlockSpec((1, PIECE, ROWS),
                             lambda j, t0_ref: (tile(j, t0_ref), 0, 0)),
                whole(rows.shape),
            ],
            out_specs=[
                whole(q.shape),
                whole(w.shape),
                pl.BlockSpec((MIN_BLOCK, bk),
                             lambda j, t0_ref: (0, tile(j, t0_ref))),
            ],
            scratch_shapes=[
                pltpu.VMEM((MIN_BLOCK, wide), q.dtype),         # q's tiles
                pltpu.VMEM((pack, 1, wide), jnp.float32),       # w's rows
                pltpu.VMEM((MIN_BLOCK, wide), jnp.float32),     # dq
                pltpu.VMEM((pack, 1, wide), jnp.float32),       # dw
            ]),
        out_shape=[
            jax.ShapeDtypeStruct(q.shape, q.dtype),
            jax.ShapeDtypeStruct(w.shape, jnp.float32),
            jax.ShapeDtypeStruct((MIN_BLOCK, span), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=kern.interpret,
        name=INDEX_BWD_NAME,
    )(jnp.reshape(t0, (1,)).astype(jnp.int32), q, w, k, p, mask, rows)


def place_keys(ki, dim: int, span: int):
    """The index keys ``[keys, dim]`` as :func:`index_backward` reads them,
    ``[pack, span, 128]``: padded with zero keys to ``span`` and laid in
    lanes ``[p * dim, (p + 1) * dim)`` of a tile of zeros, ``p = 0 .. pack -
    1``. Made once a band, outside the map over its blocks."""
    keys = ki.shape[0]
    return jnp.stack([
        jnp.pad(ki, ((0, span - keys), (p * dim, MIN_BLOCK - (p + 1) * dim)))
        for p in range(MIN_BLOCK // dim)])


def index_rows(target, scores, chosen):
    """What :func:`index_backward` keeps of a block's forward pass beside
    its operands, ``[2, ROWS]`` float32: the rows' log-sum-exp of the index
    scores ``[ROWS, keys]`` over the selection ``chosen``, and the rows'
    sums of ``target`` (the heads' mean attention: 1 but for rounding)."""
    lse = jax.nn.logsumexp(jnp.where(chosen, scores, -jnp.inf), axis=-1)
    return jnp.stack([lse, jnp.sum(target, axis=-1)])


def index_backward(qi, w, placed, target, mask, kept, ct, t0, keys: int,
                   kern: Kernels):
    """The gradient of a block's ``ct * KL(target || softmax_chosen(I))``, ``I
    = sparse_attention.index_scores(qi, w, ki)``, to (qi, w, ki): one
    ``hvd_index_bwd`` call over the k tiles of ``placed`` (:func:`place_keys`
    of the band's ``ki`` in ``qi``'s dtype), ``target`` ``[ROWS, span]``
    (:func:`heads_mean`'s, as it writes it) and ``mask`` (the selection over
    the same tiles), with ``kept`` :func:`index_rows` of the forward pass and
    ``t0`` the block's first position. For a tile up to the block's diagonal
    the products ``s = kI . qI^T`` are made again in VMEM, the scores ``I``
    from them, ``dI = ct (sum(target) softmax_chosen(I) - target)`` at the
    selected keys (what autodiff makes of the KL), ``g = (s > 0) * w * dI``
    rounded to ``qi``'s dtype as the transposed matmuls' operand, and ``dqi +=
    g^T . kI``, ``dki = g . qI``, ``dw += sum_keys relu(s) * dI`` in float32:
    what autodiff makes of the expression at default precision, with neither
    the ``[heads, rows, keys]`` products nor ``dI`` ever in HBM. (Where a
    row's weighted sum cancels to exactly 0 under live heads autodiff's
    ``where`` stops the gradient; the kernel passes it: the expression is the
    identity there.) ``dki`` is float32 ``[keys, dim]``."""
    rows, heads, dim = qi.shape
    pack, span, _ = placed.shape
    stats = jnp.stack([kept[0], ct * kept[1], jnp.full_like(kept[0], ct)])
    # the operands as XLA lays them for the forward pass's contraction: a
    # head's channels by positions, the keys' channels by keys
    dq, dw, dk = _index_bwd_local(
        t0, qi.transpose(1, 2, 0).reshape(heads * dim, rows),
        w.T.reshape(heads, 1, rows), placed, target, mask, stats, dim=dim,
        kern=kern)
    dk = dk.reshape(pack, dim, span).sum(0)[:, :keys]
    live = jnp.arange(keys) < (_last_tile(t0, kern.block_k) + 1) * kern.block_k
    return (dq.reshape(heads, dim, rows).transpose(2, 0, 1),
            dw.reshape(heads, rows).T, jnp.where(live, dk, 0.0).T)
