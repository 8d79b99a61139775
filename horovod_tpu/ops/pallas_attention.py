"""Flash attention as a Pallas TPU kernel — the hot op of the transformer
path.

No reference analog (the reference's only kernel is a batched-memcpy .cu,
``horovod/common/ops/cuda/cuda_kernels.cu``); on TPU the analogous "write
the hot loop yourself" target is attention. The kernel streams K/V tiles
through VMEM while a Q tile stays resident, maintaining the flash
running-softmax (m, l, acc) in VMEM scratch so HBM traffic is O(S·D)
instead of O(S²):

  grid = (batch·heads, Sq/block_q, Sk/block_k)   — K tile innermost
  per (q tile): for each k tile, in pieces of 128 k rows:
      sT = k @ qᵀ·scale; m, l, alpha as [1, q rows]; accT += vᵀ @ pT
  (under a window the k axis spans a q tile's band alone: **Window**)

**The softmax state lies along the lanes** (PR 56). The scores are computed
transposed, ``[k rows, q rows]``, as the backward computes them: a q row's
running max ``m``, running sum ``l`` and rescale ``alpha`` are ``[1, q
rows]`` float32 rows (128 rows a vreg, every lane used), the max and the sum
run down the sublanes (elementwise across vregs, one reduce in a vreg at the
end), every broadcast is along the sublanes, the accumulator is ``[D, q
rows]`` and is transposed once a q tile on its way out, and ``lse`` is
written as the row it is. With ``m`` and ``l`` as ``[q rows, 1]`` columns
(a vreg for 8 rows, one lane used) every q row paid ~4.2 ns a k step for two
reductions across the lanes and three broadcasts back. Since a step's row
work is now small, a tile runs as **pieces of ``PIECE_ROWS`` k rows**,
each an online-softmax update of its own, and a piece's score matmul is
written before the reduction of the piece before it: Mosaic's scheduler keeps
the order it is given, so written score tile, reduction, p·v the MXU waits
for the vector unit and back; written one ahead it runs a piece's scores
while the vector unit reduces the last (v5e, 48 query heads on 8 over 8192
keys: 7.39 ms a call with the column state, 7.95 transposed in the order
written, 6.14 one ahead; PERF.md, PR 56).

**Operands where they lie.** A head of whole lane tiles (``D % 128 == 0``)
is read and written in place: q, k, v and o are ``[B, S, heads·D]``, the
array a projection writes and ``[B, S, heads, D]`` by a bitcast, and a grid
step's block is ``(1, tile, D)`` lane columns of it at ``(b // H, tile, b %
H)`` (k and v at the q head's k/v head). Nothing is transposed around
either kernel's call (PR 31 the backward, PR 50 the forward: in
glm-4.7-flash.s8192 the heads-first copies and the fusions that wrote q and
k heads first were 15.6 ms of a 362.6 ms step). What XLA still puts in
front of a call is its own choice of layout for what feeds it: at a batch of
one sequence it keeps activations with the tokens on the lanes and copies
q and k to row-major where an elementwise op (rope) stands between the
projection and the call (PERF.md §7).

**Tiles come from the shape and the window** (:func:`flash_blocks`): the
largest of 1024/512/256/128 that divide ``Sq`` / ``Sk`` and are no wider
than a causal window (or than 128), the q tile halved until the working set
fits ``VMEM_BUDGET``. A grid step that runs a tile costs about 0.35 µs
beside the tile's work, so a 128 × 128 tile (0.04 µs of MXU work at bf16)
is all overhead: at B2·S2048·H16·D128 causal a call takes 2.59 ms with
128 × 128 tiles and 0.48 ms with 1024 × 1024 (v5e; PERF.md, PR 25).
The score tile is in VMEM a piece at a time, so what fills the budget is
the operands' blocks (``flash_vmem_bytes``: 5.6 MiB at 1024 × 1024 and a
bf16 head of 128, 8.6 at 256). Callers pass no block; ``block_q`` /
``block_k`` are overrides for tests. The contract to callers is only
``MIN_BLOCK``: sequence lengths and head_dim are multiples of 128, or the
head is :data:`NARROW_HEAD` = 64 wide (below).

**Dtypes.** q·kᵀ and p·v multiply operands in the dtype the caller passed
(bf16 in training: what the MXU multiplies; float32 inputs give float32
matmuls) and accumulate in float32; ``p`` is cast to ``v``'s dtype for
p·v. The scale, the mask, the running max ``m``, the running sum ``l``,
the accumulator and the log-sum-exp are float32 for every input dtype.

**Causal.** A tile strictly above the diagonal runs no work and fetches
nothing: the K/V index maps clamp to the q tile's last live tile, and
Pallas issues no DMA for a block index that repeats. Only the tiles the
diagonal crosses build a mask; those below it skip it. A square tile meets
the diagonal corner to corner, and its pieces (:func:`tile_pieces`) leave
out the q rows before a piece's first k row, which the mask kills for all
of it (of a 1024 x 1024 tile 28 of its 64 blocks of 128 x 128), as the
backward's do. A masked score added ``exp(-1e30 - m) = 0`` to ``l`` and to
``p·v``: leaving it out changes no term.

**Window.** ``window=W`` (causal only) keeps of a query at ``t`` the keys
``t - W < j <= t``: a band under the diagonal, and a windowed call walks
its band alone. Its tile is no wider than the window (a window of 512 under
1024 x 1024 tiles would leave every live tile crossed by the diagonal or
the band's lower edge, 3.87 scores computed for one live), and its grid's
innermost axis has as many steps as a row of tiles' band has tiles
(:func:`flash_grid`: ``block_q / block_k + ceil((W - 1) / block_k)``, held
to the sequence's), the block index the band's first tile plus the step
(:func:`_band_k_tile`); the backward's q axis likewise
(:func:`flash_bwd_grid`, :func:`_band_q_tile`). A step past the band's last
tile (the first q tiles, whose band the sequence's start cuts; the last k
tiles in the backward) runs nothing and fetches nothing: the index stays on
the last live tile. A tile the band's lower edge crosses is masked as a
diagonal tile is; where the window is a multiple of the (square) tile the
edge crosses its tiles corner to corner too, and they run in the mirror
image of the diagonal's pieces. At 8192 x 8192 under a window of 4096 the
tile stays 1024 x 1024 and a head takes 8 x 5 = 40 steps for its 30 live
tiles (of 36 causal, four of them edge tiles); under a window of 512 the
tile is 512 x 512 and a head takes 16 x 2 = 32 steps for 31 live tiles,
every one on the diagonal or the edge (v5e, a call at 64 query heads on 8:
5.21 ms forward and 7.26 backward where 1024 x 1024 tiles on the sequence's
grid took 6.19 and 11.14; PERF.md, PR 54; the forward 2.43 since PR 56).

**Grouped heads.** k and v may have fewer heads than q (``H % Hkv == 0``):
q head ``h`` reads k/v head ``h // (H // Hkv)`` through the block index,
and the backward writes a q head's part of dk and dv, summed over the
group outside the kernel.

The kernel is DIFFERENTIABLE: a ``jax.custom_vjp`` pairs the forward
kernel (which also emits the per-row log-sum-exp residual) with a second
kernel, ``hvd_flash_bwd``, that recomputes the attention probabilities
tile by tile in VMEM from (q, k, v, o, lse) — the standard flash-attention
backward (Dao et al.) — so training through the kernel never writes a
score-shaped array to HBM:

  grid = (batch·heads, q ranges, Sk/block_k, rows/block_q) — q tile innermost
  (under a window: the q tiles of a k tile's band)
  per (k tile): for each q tile: pT = exp(k @ qᵀ·scale − lse);
      dv += pT @ do; dsT = pT ⊙ (v @ doᵀ − adj); dk += dsT @ q; dqT += kᵀ @ dsT

One kernel, not the usual dk/dv + dq pair: a head's whole float32 dq
(``Sq·D·4`` bytes, 2 MiB at 4096 positions) stays in a VMEM scratch across
its k tiles, so every tile is computed once: five matmuls and one pass of
the vector work where two kernels do seven and two. Where that does not
fit (tens of thousands of positions on one device) the q rows go in ranges
(:func:`flash_bwd_blocks`). Its tile is a rule of its own
(``flash_bwd_blocks``; no wider than a window either): 1024 × 1024 at the
benchmark's shapes without a window narrower than that, where the
MXU is at 86 % of its peak on the tiles it runs whole (v5e; PERF.md, PR
31). A tile that a mask's line crosses runs in the forward's pieces
(:func:`bwd_tile_pieces`; on the diagonal and a window's edge they leave
out the q rows masked for the whole piece), and a piece is written in two
parts: the two matmuls that need operands alone (``sT``, ``dpT``)
``BWD_AHEAD`` pieces before the ``exp``, ``dsT`` and the three matmuls that
need the piece's scores, so the MXU runs a piece's first two while the
vector unit is at the piece before; a clean tile, which its five matmuls
bound, runs whole; dq accumulates transposed, ``dqT += kT @ dsT`` (PR 58).
Same precision as the forward:
operands multiply as they come and accumulate in float32, ``pT`` and
``dsT`` are float32 in VMEM and cast for their matmuls. q, k, v and do are
read as ``[B, S, H·D]``, a head whole 128-lane columns, and dq, dk, dv
written so: no transpose around the call (``adj``: ``hvd_flash_adj``, below).

**A head of 64** (half a lane tile) runs both kernels heads-first, ``[B·H,
S, 64]``: a ``(1, tile, 64)`` block spans the array's whole last dimension,
which Mosaic takes where it refuses 64 of ``H·64`` lanes; both kernels'
operands are transposed around their calls (at 32 / 8 heads over 4096
positions 16 MB a q-sized array). The form follows ``D % 128``, which the
code sees in its input: no setting, one kernel each. The MXU contracts
over 64 of its 128 rows for the scores and fills 64 of its columns for the
outputs: half empty either way, as two heads side by side on one lane tile
with the other's lanes zeroed would leave it (``ops/pallas_ssm.py``), and
the ``[bq, bk]`` vector work, which bounds a tile, is the same.

Falls back to the pure-XLA implementation on CPU or when shapes don't meet
TPU tiling constraints (last dim 128-multiple, 128-divisible sequence).

A second pair of kernels, further down, serves short non-causal sequences
with a key mask (BERT): a head's whole score tile stays in VMEM, forward
and backward, at head_dim 64 too. :func:`attend` picks between the two
and XLA from the shape (:func:`attention_path`).
"""

from __future__ import annotations

import functools
import math
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import PartitionSpec as P

from horovod_tpu.profiling.compile_watch import kernel_call

NEG_INF = -1e30

#: what callers are held to: Sq, Sk and head_dim are multiples of this
#: (the TPU's lane count; the smallest tile)
MIN_BLOCK = 128
#: the one head width below a lane tile that the flash kernels take
NARROW_HEAD = 64
TILES = (1024, 512, 256, MIN_BLOCK)
#: bytes the forward's working set may take by ``flash_vmem_bytes``: the
#: v5e's default scoped-VMEM limit (16 MiB of 128), so no limit is asked
#: for
VMEM_BUDGET = 16 * 1024 * 1024

_NT = (((1,), (1,)), ((), ()))      # a · bᵀ
_NN = (((1,), (0,)), ((), ()))      # a · b
_TN = (((0,), (0,)), ((), ()))      # aᵀ · b


def _dot(a, b, dims):
    return lax.dot_general(a, b, dims, preferred_element_type=jnp.float32)



def _group(H: int, Hkv: int) -> int:
    """q heads a k/v head serves."""
    if H % Hkv:
        raise ValueError(f"{Hkv} k/v heads do not divide {H} q heads")
    return H // Hkv


def _check_window(window, causal) -> None:
    if window is not None and (not causal or window < 1):
        raise ValueError(f"window={window}: a window is a band under the "
                         "diagonal, at least one key wide (causal only)")


def flash_vmem_bytes(block_q: int, block_k: int, D: int, itemsize: int) -> int:
    """Working set of one grid step: q, k, v and o tiles and the ``[1,
    bq]`` lse row (8 sublanes) double-buffered by the pipeline, two pieces
    of the score tile in flight (one's ``sT`` while the other is reduced),
    each ``[piece rows, bq]`` in float32 as ``sT`` and ``pT`` and ``pT``
    cast for p·v, the float32 accumulator ``[D, bq]`` and its transpose on
    the way out, and the ``m`` / ``l`` rows."""
    io = 2 * (2 * block_q + 2 * block_k) * D * itemsize + 2 * 8 * block_q * 4
    pieces = 2 * piece_rows(block_k) * block_q * (4 + 4 + itemsize)
    scratch = 2 * block_q * D * 4 + 2 * 8 * block_q * 4
    return io + pieces + scratch


def _tiles_under(window: Optional[int]) -> Tuple[int, ...]:
    """The tiles a call under a causal ``window`` may take: none wider than
    the window (or than ``MIN_BLOCK``), so that the diagonal and the band's
    lower edge cross different tiles; all of ``TILES`` without a window."""
    if window is None:
        return TILES
    return tuple(t for t in TILES if t <= max(window, MIN_BLOCK))


def flash_blocks(Sq: int, Sk: int, D: int, dtype,
                 window: Optional[int] = None) -> Tuple[int, int]:
    """``(block_q, block_k)`` of the forward kernel for q ``[.., Sq, D]``
    and k/v ``[.., Sk, D]``: the largest of ``TILES`` that divide the
    sequence lengths and, under a causal ``window``, are no wider than it
    (:func:`_tiles_under`), halved until the working set fits
    ``VMEM_BUDGET``, the q tile first. The v5e sweep (PERF.md, PR 25) put a
    kernel call at 0.35 µs a grid step + 4.3 µs a million ``s`` elements +
    a cost per q row and step that a wide k tile spreads thin, so
    ``block_k`` is the one to keep."""
    if Sq % MIN_BLOCK or Sk % MIN_BLOCK:
        raise ValueError(f"Sq={Sq} and Sk={Sk} must be multiples of "
                         f"{MIN_BLOCK}")
    itemsize = jnp.dtype(dtype).itemsize
    tiles = _tiles_under(window)
    bq = next(t for t in tiles if Sq % t == 0)
    bk = next(t for t in tiles if Sk % t == 0)
    while (flash_vmem_bytes(bq, bk, D, itemsize) > VMEM_BUDGET
           and max(bq, bk) > MIN_BLOCK):
        if bq > MIN_BLOCK:
            bq //= 2
        else:
            bk //= 2
    return bq, bk


def flash_grid(B: int, H: int, Sq: int, Sk: int, block_q: int,
               block_k: int,
               window: Optional[int] = None) -> Tuple[int, int, int]:
    """The forward kernel's grid; its product is the grid steps of a call.
    Under a causal ``window`` the k axis spans a q tile's band, not the
    sequence: as many steps as the widest band has k tiles
    (:func:`_band_k_tile` says which tile a step is)."""
    nq, nk = Sq // block_q, Sk // block_k
    if window is not None:
        bands = (_band_k_tile(qi, 0, block_q, block_k, window)
                 for qi in range(nq))
        nk = min(nk, max(last - first + 1 for first, last in bands))
    return (B * H, nq, nk)


def _band_tiles(first_row, first_col, block_q: int, block_k: int,
                window: int):
    """(crossed, whole) of the tile at ``(first_row, first_col)`` under a
    causal window: whether the diagonal or the band's lower edge crosses
    it (some of it is masked, not all), and whether all of it is live.
    Neither: the tile is wholly above the diagonal or below the band."""
    last_row, last_col = first_row + block_q - 1, first_col + block_k - 1
    diagonal = jnp.logical_and(first_col <= last_row, last_col > first_row)
    edge = jnp.logical_and(first_col <= last_row - window,
                           last_col > first_row - window)
    crossed = jnp.logical_or(diagonal, edge)
    live = jnp.logical_and(first_col <= last_row,
                           last_col > first_row - window)
    return crossed, jnp.logical_and(live, jnp.logical_not(crossed))


def band_tile_counts(S: int, block_q: int, block_k: int,
                     window: Optional[int]) -> Tuple[int, int, int]:
    """Of a head's ``S x S`` scores in ``block_q x block_k`` tiles: (the
    tiles the causal triangle touches, those of them a window of
    ``window`` keeps, those of them the band's lower edge crosses). What a
    call runs, for ``chip_smoke.py``'s ``attention_path`` and the tests."""
    causal = live = edge = 0
    for r0 in range(0, S, block_q):
        for c0 in range(0, S, block_k):
            r1, c1 = r0 + block_q - 1, c0 + block_k - 1
            if c0 > r1:
                continue
            causal += 1
            if window is None or c1 > r0 - window:
                live += 1
                edge += window is not None and c0 <= r1 - window
    return causal, live, edge


def _larger(a, b):
    """``max`` of two tile indices: Python ints (a grid's arithmetic, the
    tests) or a kernel's or an index map's traced values."""
    ints = isinstance(a, int) and isinstance(b, int)
    return max(a, b) if ints else jnp.maximum(a, b)


def _smaller(a, b):
    """``min``, as :func:`_larger`."""
    ints = isinstance(a, int) and isinstance(b, int)
    return min(a, b) if ints else jnp.minimum(a, b)


def _first_band_k_tile(qi, block_q: int, block_k: int, window: int):
    """Window: the first k tile with a column inside q tile ``qi``'s
    band; the k tiles before it are wholly below the band."""
    return _larger(qi * block_q - window + 1, 0) // block_k


def _band_k_tile(qi, step, block_q: int, block_k: int, window: int):
    """Window, the forward: (the k tile that grid step ``step`` of q tile
    ``qi`` stands for, the q tile's last live k tile). The k axis starts at
    the band's first tile; a step past the last live tile (the first q
    tiles, whose band the sequence's start cuts) runs nothing, and its
    block index stays on the last live tile so that nothing is fetched."""
    return (_first_band_k_tile(qi, block_q, block_k, window) + step,
            _last_live_k_tile(qi, block_q, block_k))


def _last_band_q_tile(kj, block_q: int, block_k: int, window: int):
    """Window: the last q tile with a row whose band reaches k tile
    ``kj``'s last column (not yet held to the sequence's end)."""
    return (kj * block_k + block_k - 1 + window - 1) // block_q


def _band_q_tile(kj, step, first_tile, tiles: int, block_q: int,
                 block_k: int, window: int):
    """Window, the backward: (the q tile that grid step ``step`` of k tile
    ``kj`` stands for, the last q tile of the k tile's band) among the q
    tiles ``[first_tile, first_tile + tiles)`` of a q range (the whole
    sequence where dq is resident). The q axis starts at the first q tile
    at or below the diagonal; a step past the last (the last k tiles, whose
    band the sequence's end cuts, or a range the band leaves) runs
    nothing."""
    first = _larger(_first_live_q_tile(kj, block_q, block_k), first_tile)
    last = _smaller(_last_band_q_tile(kj, block_q, block_k, window),
                    first_tile + tiles - 1)
    return first + step, last


#: k rows of a piece of the forward (:func:`tile_pieces`): every tile runs as
#: pieces of this many k rows against its q rows, a piece's score matmul
#: issued before the piece before it is reduced (:func:`_flash_kernel`). v5e,
#: the kernel's ms a call alone, the parent (q-major scores, ``[rows, 1]``
#: state, the tile whole) / pieces of 512 / 256 / 128 k rows issued one ahead:
#: [1, 8192, 48 on 8, 128] 7.387 / 7.241 / 7.044 / 6.143, [2, 2048, 16, 128]
#: 0.457 / 0.324 / 0.319 / 0.298, [1, 8192, 20, 256] 5.096 / 5.119 / 5.089 /
#: 4.682, heads first [1, 4096, 32 on 8, 64] 1.336 / 0.959 / 0.938 / 0.818;
#: under a window of 512 (512 x 512 tiles, [1, 8192, 64 on 8, 128]) 4.264 /
#: - / 2.448 / 2.434. Issued in the order written (a piece's scores, its
#: reduction, its p . v) the same pieces take what the tile whole takes
#: (7.949, 0.389, 5.489, 1.117): Mosaic's scheduler keeps the order it is
#: given, so the MXU waits for the vector unit and back (PERF.md, PR 56)
PIECE_ROWS = 128


def piece_rows(block_k: int) -> int:
    """k rows of a piece of :func:`tile_pieces`, from the tile."""
    return min(block_k, PIECE_ROWS)


def banded_tiles(block_q: int, block_k: int, window: Optional[int]) -> bool:
    """Whether the forward's pieces of a tile the diagonal (or a window's
    lower edge) crosses leave out the q rows masked for a whole piece:
    square tiles, which the diagonal crosses corner to corner, and a window
    of whole tiles, so that its edge does too. Else such a tile's pieces
    span all its q rows, under the mask."""
    return block_q == block_k and (window is None or window % block_k == 0)


def tile_pieces(block_q: int, block_k: int, crossed: Optional[str] = None):
    """The static pieces the forward runs of a tile, as ``(k0, rows, q0,
    q1)``: k rows ``[k0, k0 + rows)`` against q rows ``[q0, q1)`` of the
    tile (the backward's ``diagonal()`` / ``edge()`` geometry), in
    :func:`piece_rows` k rows. ``crossed`` is None for all q rows
    (a tile inside the band, or one a mask's line cuts anywhere), else the
    line that crosses a square tile corner to corner: ``"diagonal"`` leaves
    out the q rows before a piece's first k row and ``"edge"`` (a window's
    lower edge) the ones from its last k row on, which the mask kills for
    the whole piece: a 1024 x 1024 tile runs 36 of its 64 blocks of 128 x
    128 in eight pieces."""
    rows = piece_rows(block_k)
    span = {None: lambda k0: (0, block_q),
            "diagonal": lambda k0: (k0, block_q),
            "edge": lambda k0: (0, k0 + rows)}[crossed]
    return [(k0, rows, *span(k0)) for k0 in range(0, block_k, rows)]


def tile_piece_blocks(block_q: int, block_k: int,
                      crossed: Optional[str] = None) -> Tuple[int, int]:
    """(the ``MIN_BLOCK`` x ``MIN_BLOCK`` blocks :func:`tile_pieces` runs
    of such a tile, the blocks the tile has): (36, 64) on the diagonal of
    1024 x 1024. For ``chip_smoke.py``'s ``attention_path`` and the
    tests."""
    ran = sum(rows * (q1 - q0) for _, rows, q0, q1
              in tile_pieces(block_q, block_k, crossed))
    return ran // MIN_BLOCK ** 2, block_q * block_k // MIN_BLOCK ** 2


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, acc_ref, m_ref,
                  l_ref, *, scale: float, causal: bool, block_q: int,
                  block_k: int, window: Optional[int] = None):
    """One (q-tile, k-tile) step; grid (BH, nq, nk) with k innermost. Under
    a window the k axis spans the q tile's band (:func:`flash_grid`) and
    ``kv_idx`` is the k tile the step stands for. The scores are computed
    transposed, ``sT = k @ qT`` as ``[k rows, q rows]``, so a q row's
    running max ``m``, sum ``l`` and rescale ``alpha`` are ``[1, q rows]``
    rows along the lanes (``m_ref``, ``l_ref``; the accumulator ``[D, q
    rows]`` beside them): the max and the sum run down the sublanes and
    every broadcast is along them."""
    kv_step = pl.program_id(2)
    q_idx = pl.program_id(1)
    kv_idx = kv_step if window is None else _band_k_tile(
        q_idx, kv_step, block_q, block_k, window)[0]

    @pl.when(kv_step == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        m_ref[:] = jnp.full_like(m_ref, NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)

    def scores(masked: bool, piece):
        """float32 ``sT`` of a piece (static), masked where asked."""
        k0, rows, q0, q1 = piece
        st = _dot(k_ref[0, pl.ds(k0, rows)], q_ref[0, pl.ds(q0, q1 - q0)],
                  _NT) * scale                          # [rows, q1 - q0]
        if masked:
            kpos = kv_idx * block_k + k0 + lax.broadcasted_iota(
                jnp.int32, st.shape, 0)
            qpos = q_idx * block_q + q0 + lax.broadcasted_iota(
                jnp.int32, st.shape, 1)
            live = qpos >= kpos
            if window is not None:
                live = jnp.logical_and(live, kpos > qpos - window)
            st = jnp.where(live, st, NEG_INF)
        return st

    def update(piece, st):
        """The online-softmax update of the piece's q rows over its k
        rows."""
        k0, rows, q0, q1 = piece
        qs = pl.ds(q0, q1 - q0)
        v = v_ref[0, pl.ds(k0, rows)]                   # [rows, D]
        m_prev = m_ref[:, qs]                           # [1, q1 - q0]
        m_next = jnp.maximum(m_prev, jnp.max(st, axis=0, keepdims=True))
        pt = jnp.exp(st - m_next)
        alpha = jnp.exp(m_prev - m_next)
        l_ref[:, qs] = l_ref[:, qs] * alpha + jnp.sum(pt, axis=0,
                                                      keepdims=True)
        m_ref[:, qs] = m_next
        acc_ref[:, qs] = acc_ref[:, qs] * alpha + _dot(
            v, pt.astype(v.dtype), _TN)                 # [D, q1 - q0]

    def run(masked: bool, line: Optional[str] = None):
        """A tile in :func:`tile_pieces`, all in one basic block, a piece's
        score matmul written before the piece before it is reduced: the
        scheduler keeps that order, and the MXU then runs a piece's scores
        while the vector unit reduces the last (``PIECE_ROWS``)."""
        def tile():
            pieces = tile_pieces(block_q, block_k, line)
            st = scores(masked, pieces[0])
            for piece, ahead in zip(pieces, pieces[1:] + [None]):
                st_ahead = None if ahead is None else scores(masked, ahead)
                update(piece, st)
                st = st_ahead
        return tile

    banded = banded_tiles(block_q, block_k, window)
    if causal:
        first_row = q_idx * block_q
        first_col = kv_idx * block_k
        last_col = first_col + block_k - 1
        # the diagonal crosses the tile: some of it is masked, not all.
        # Square tiles meet it corner to corner (q_idx == kv_idx)
        crossed = jnp.logical_and(first_col <= first_row + block_q - 1,
                                  last_col > first_row)
        if window is None:
            pl.when(crossed)(run(True, "diagonal" if banded else None))
            # wholly at or below the diagonal: no mask to build. Tiles
            # strictly above it run nothing (and fetch nothing: k_tile)
            pl.when(last_col <= first_row)(run(False))
        else:
            crossed, clean = _band_tiles(first_row, first_col, block_q,
                                         block_k, window)
            # a q row the band's edge masks for all of a piece (of its
            # first tile) holds garbage under m = NEG_INF until its next
            # piece's alpha = 0 wipes it: every row's diagonal piece comes
            # later
            if banded:
                # the diagonal and the edge cross different tiles, each
                # corner to corner
                pl.when(q_idx == kv_idx)(run(True, "diagonal"))
                pl.when(first_col == first_row - window)(run(True, "edge"))
            else:
                pl.when(crossed)(run(True))
            pl.when(clean)(run(False))
    else:
        run(False)()

    @pl.when(kv_step == pl.num_programs(2) - 1)
    def _finalize():
        l_safe = jnp.maximum(l_ref[:], 1e-30)
        # the accumulator is transposed once a q tile, on its way out
        o_ref[0] = jnp.transpose(acc_ref[:] / l_safe).astype(o_ref.dtype)
        # log-sum-exp residual for the backward pass: lse = m + log(l), the
        # [1, bq] row it already is, of the [BH, 1, Sq] output (a (1, bq)
        # block of a 2-D [BH, Sq] array is not tile-aligned and the TPU
        # lowering refuses it)
        lse_ref[0] = m_ref[:] + jnp.log(l_safe)


def _heads_first(x):
    """``[B, S, heads, D]`` -> ``[B * heads, S, D]``: the operands' form at
    a head of ``NARROW_HEAD``, which is no whole lane column of ``[B, S,
    heads * D]``."""
    return x.transpose(0, 2, 1, 3).reshape(-1, x.shape[1], x.shape[3])


def _flash_fwd_impl(q, k, v, causal, scale, block_q, block_k, interpret,
                    window=None):
    """Run the kernel; q [B, S, H, D], k/v [B, S, Hkv, D] → (o [B, S, H,
    D], lse [BH, Sq]). A head of whole lane tiles (``D % MIN_BLOCK == 0``)
    is read and written where it lies, as 128-lane columns of ``[B, S,
    heads * D]`` (a bitcast of the operand); a head of 64 goes heads first,
    transposed around the call."""
    B, Sq, H, D = q.shape
    Sk, Hkv = k.shape[1], k.shape[2]
    group = _group(H, Hkv)
    _check_window(window, causal)
    in_place = D % MIN_BLOCK == 0

    if window is not None:
        # the k axis walks the q tile's band alone; past the diagonal the
        # index stays on the last live tile
        def k_tile(i, j):
            return jnp.minimum(*_band_k_tile(i, j, block_q, block_k, window))
    elif causal:
        # above the diagonal the block index repeats the q tile's last
        # live tile, so the pipeline issues no DMA for a skipped step
        def k_tile(i, j):
            return jnp.minimum(j, _last_live_k_tile(i, block_q, block_k))
    else:
        def k_tile(i, j):
            return j

    # grid axis 0 is (batch row, q head); q head h reads kv head h // group
    if in_place:
        operands = (q.reshape(B, Sq, H * D), k.reshape(B, Sk, Hkv * D),
                    v.reshape(B, Sk, Hkv * D))

        def q_at(b, i, j):
            return (b // H, i, b % H)

        def k_at(b, i, j):
            return (b // H, k_tile(i, j), b % H // group)
    else:
        operands = (_heads_first(q), _heads_first(k), _heads_first(v))

        def q_at(b, i, j):
            return (b, i, 0)

        def k_at(b, i, j):
            return (b // H * Hkv + b % H // group, k_tile(i, j), 0)

    out, lse = kernel_call(pl.pallas_call,
        functools.partial(_flash_kernel, scale=scale, causal=causal,
                          block_q=block_q, block_k=block_k, window=window),
        grid=flash_grid(B, H, Sq, Sk, block_q, block_k, window),
        in_specs=[
            pl.BlockSpec((1, block_q, D), q_at),
            pl.BlockSpec((1, block_k, D), k_at),
            pl.BlockSpec((1, block_k, D), k_at),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, D), q_at),
            pl.BlockSpec((1, 1, block_q), lambda b, i, j: (b, 0, i)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(operands[0].shape, q.dtype),
            jax.ShapeDtypeStruct((B * H, 1, Sq), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((D, block_q), jnp.float32),   # acc, transposed
            pltpu.VMEM((1, block_q), jnp.float32),   # m (running max)
            pltpu.VMEM((1, block_q), jnp.float32),   # l (running sum)
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="hvd_flash_attention",
    )(*operands)
    if not in_place:
        out = out.reshape(B, H, Sq, D).transpose(0, 2, 1, 3)
    return out.reshape(B, Sq, H, D), lse.reshape(B * H, Sq)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8))
def _flash_lse(q, k, v, causal, scale, block_q, block_k, interpret, window):
    return _flash_fwd_impl(q, k, v, causal, scale, block_q, block_k,
                           interpret, window)


def _flash_fwd(q, k, v, causal, scale, block_q, block_k, interpret, window):
    o, lse = _flash_fwd_impl(q, k, v, causal, scale, block_q, block_k,
                             interpret, window)
    return (o, lse), (q, k, v, o, lse)


# ---------------------------------------------------------------------------
# The backward: one kernel, k tile outer, q tile inner, dq resident
# ---------------------------------------------------------------------------
#
# Both kernels compute the scores transposed, ``sT = k @ qT`` as ``[k rows, q
# rows]``: what belongs to a q row then lies along the lanes. In the forward
# that is the online-softmax state (``m``, ``l``, ``alpha`` as ``[1, bq]``
# rows, the accumulator ``[D, bq]``); here it is the row's log-sum-exp and
# its ``adj``, read as the ``[1, bq]`` rows the forward wrote, and of the
# five matmuls only dq's needs a transposed operand (q-major scores need it
# for dv and for dk): computed as ``dqT = kT @ dsT`` into a ``[D, bq]``
# accumulator a q tile, it is the k rows that are transposed, ``[rows, D]``,
# and not the score-shaped ``dsT`` (the forward's ``accT``; PR 58).

#: pieces of a crossed tile whose two operand-only matmuls (``sT``, ``dpT``)
#: the backward writes ahead of the piece whose ``exp`` and ``dsT`` it is at
#: (:func:`_flash_bwd_kernel`, :func:`bwd_tile_pieces`). v5e, the kernel's
#: ms a call alone under a window of 512 ([1, 8192, 64 on 8, 128], 512 x 512
#: tiles, every live tile crossed): the parent (a piece's five matmuls in
#: the order ``sT``, dv, ``dpT``, dk, dq) 4.292, ``sT`` and ``dpT`` first
#: and none ahead 3.881, one ahead 3.542, two 3.433 (with ``dqT`` 3.581 /
#: 3.538), pieces of 256 k rows one ahead 3.789. On the full calls the
#: diagonal tiles are a seventh of the work: none / one / two ahead 12.581 /
#: 12.543 / 12.533 at [1, 8192, 48 on 8, 128] (PERF.md, PR 58)
BWD_AHEAD = 1


def bwd_tile_pieces(block_q: int, block_k: int, masked: bool,
                    crossed: Optional[str] = None):
    """The static pieces the backward runs of a tile, as
    :func:`tile_pieces` gives them: the forward's where a mask's line
    crosses the tile (``masked``; ``crossed`` as there), the tile whole
    where none does. A clean tile is bound by its five matmuls and gains
    nothing from any order, and every piece adds a pass over the q rows'
    float32 dq: v5e, ms a call alone, the tile whole / in two pieces of 512
    k rows / in eight of 128, each one ahead: [1, 8192, 48 on 8, 128]
    12.308 / 12.451 / 12.499 (the parent whole 12.549; with ``dsT``
    transposed for dq, as the parent has it, 12.543 / 12.602 / 13.541),
    [1, 4096, 16, 128] 1.079 / 1.090 / 1.093, [2, 8192, 32 on 8, 64]
    heads first 14.380 / 14.565 / 14.647 (PERF.md, PR 58)."""
    if masked:
        return tile_pieces(block_q, block_k, crossed)
    return [(0, block_k, 0, block_q)]


#: bytes the backward's working set may take by ``flash_bwd_vmem_bytes``,
#: asked for as the call's scoped-VMEM limit (the v5e has 128 MiB)
BWD_VMEM_BUDGET = 32 * 1024 * 1024


class BwdBlocks(NamedTuple):
    """The backward's tile and the q rows whose dq a head keeps in VMEM."""
    block_q: int
    block_k: int
    rows: int


def flash_bwd_vmem_bytes(block_q: int, block_k: int, rows: int, D: int,
                         itemsize: int) -> int:
    """Working set of one grid step of the backward: q, do, k, v tiles and
    the dk, dv and ``[rows, D]`` dq output blocks double-buffered by the
    pipeline, the lse and adj rows (a ``[1, bq]`` float32 block takes 8
    sublanes), the float32 accumulators of dk, dv and dqT, and the score
    tile of a clean tile, which runs whole (:func:`bwd_tile_pieces`; a
    crossed tile's pieces in flight are less): a float32 copy and a cast
    and a half. That is the compiler's own count rounded up: the least
    scoped-VMEM limit that compiles for a described v5e leaves, above the
    blocks and accumulators, 5.3 to 6.8 bytes a score at bfloat16 (512 x
    512 and 1024 x 1024 at heads of 128; 1024 x 1024 at a head of 256:
    30.87 MiB in all) and 8.3 at float32 (PERF.md, PR 58; the parent
    counted the tile five times, 18 bytes)."""
    io = 2 * (2 * block_q + 2 * block_k) * D * itemsize
    stats = 2 * 2 * 8 * block_q * 4
    out = 2 * (2 * block_k + rows) * D * itemsize
    scratch = (2 * block_k + rows) * D * 4
    tile = block_q * block_k * (4 + 3 * itemsize // 2)
    return io + stats + out + scratch + tile


def flash_bwd_blocks(Sq: int, Sk: int, D: int, dtype,
                     window: Optional[int] = None) -> BwdBlocks:
    """The backward kernel's ``(block_q, block_k, rows)`` for q/do ``[..,
    Sq, D]`` against k/v ``[.., Sk, D]``, a tile no wider than a causal
    ``window`` (:func:`_tiles_under`). ``rows == Sq`` is the resident
    form: a head's whole float32 dq stays in VMEM across its k tiles and
    every tile is computed once (five matmuls, one pass of the vector
    work). Where that does not fit ``BWD_VMEM_BUDGET`` (a sequence of tens
    of thousands of positions on one device) the q rows are split into
    ranges of ``rows``, each of which walks all k tiles and writes its own
    partial dk and dv, summed outside the kernel."""
    if Sq % MIN_BLOCK or Sk % MIN_BLOCK:
        raise ValueError(f"Sq={Sq} and Sk={Sk} must be multiples of "
                         f"{MIN_BLOCK}")
    itemsize = jnp.dtype(dtype).itemsize

    def over(bq, bk, rows):
        return flash_bwd_vmem_bytes(bq, bk, rows, D, itemsize) \
            > BWD_VMEM_BUDGET
    # the fewest ranges whose accumulators leave room for the smallest tile
    units = Sq // MIN_BLOCK
    rows = Sq // next(n for n in range(1, units + 1) if units % n == 0
                      and not over(MIN_BLOCK, MIN_BLOCK, Sq // n))
    tiles = _tiles_under(window)
    bq = next(t for t in tiles if rows % t == 0)
    bk = next(t for t in tiles if Sk % t == 0)
    while over(bq, bk, rows) and max(bq, bk) > MIN_BLOCK:
        if bq >= bk:
            bq //= 2
        else:
            bk //= 2
    return BwdBlocks(bq, bk, rows)


def flash_bwd_grid(B: int, H: int, Sq: int, Sk: int, blocks: BwdBlocks,
                   window: Optional[int] = None) -> Tuple[int, int, int, int]:
    """The backward kernel's grid: (heads, q ranges, k tiles, q tiles of a
    range), the last innermost. Under a causal ``window`` the q axis spans
    a k tile's band, not the range: as many steps as the widest band has q
    tiles (:func:`_band_q_tile` says which tile a step is)."""
    bq, bk, rows = blocks
    nq = rows // bq
    if window is not None:
        bands = (_band_q_tile(kj, 0, 0, Sq // bq, bq, bk, window)
                 for kj in range(Sk // bk))
        nq = min(nq, max(last - first + 1 for first, last in bands))
    return (B * H, Sq // rows, Sk // bk, nq)


def _first_live_q_tile(kj, block_q: int, block_k: int):
    """Causal: the first q tile with a row at or below k tile ``kj``'s
    first column; the q tiles before it are wholly above the diagonal."""
    return (kj * block_k) // block_q


def _last_live_k_tile(qi, block_q: int, block_k: int):
    """Causal: the last k tile with a column at or before q tile ``qi``'s
    last row."""
    return (qi * block_q + block_q - 1) // block_k


def _flash_bwd_kernel(q_ref, do_ref, k_ref, v_ref, lse_ref, adj_ref,
                      dq_ref, dk_ref, dv_ref, dq_acc, dk_acc, dv_acc, *,
                      scale: float, causal: bool, block_q: int,
                      block_k: int, window: Optional[int] = None):
    """One (k tile, q tile) step; grid (BH, q ranges, nk, nq) with q
    innermost: dk and dv of the k tile accumulate over the q tiles, dq of
    the range's rows over the k tiles, transposed (``dq_acc`` is ``[q tiles
    of the range, D, block_q]``). Under a window the q axis spans the
    k tile's band (:func:`flash_bwd_grid`): ``qi`` is the q tile the step
    stands for and ``i`` its place in the range, both past the band's last
    tile where the step runs nothing (``live``)."""
    kj, step = pl.program_id(2), pl.program_id(3)
    nk, nq = pl.num_programs(2), pl.num_programs(3)
    if window is None:
        i = step
        qi = pl.program_id(1) * nq + i        # the q tile in the sequence
    else:
        tiles = dq_acc.shape[0]               # the q tiles of a range
        first_tile = pl.program_id(1) * tiles
        qi, last_qi = _band_q_tile(kj, step, first_tile, tiles, block_q,
                                   block_k, window)
        i, live = qi - first_tile, qi <= last_qi

    @pl.when(step == 0)
    def _init():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    def ahead(masked: bool, piece):
        """What of a piece (static) needs its operands alone: float32
        ``sT``, masked where asked, and ``dpT = v @ doT``, both ``[rows, q1
        - q0]``."""
        k0, n_k, q0, q1 = piece
        ks, qs = pl.ds(k0, n_k), pl.ds(q0, q1 - q0)
        st = _dot(k_ref[0, ks], q_ref[0, qs], _NT) * scale
        if masked:
            kpos = kj * block_k + k0 + lax.broadcasted_iota(
                jnp.int32, st.shape, 0)
            qpos = qi * block_q + q0 + lax.broadcasted_iota(
                jnp.int32, st.shape, 1)
            live = qpos >= kpos
            if window is not None:
                live = jnp.logical_and(live, kpos > qpos - window)
            st = jnp.where(live, st, NEG_INF)
        return st, _dot(v_ref[0, ks], do_ref[0, qs], _NT)

    def finish(piece, st, dpt):
        """What needs the piece's scores: dk and dv of its k rows, dq of
        its q rows."""
        k0, n_k, q0, q1 = piece
        ks, qs = pl.ds(k0, n_k), pl.ds(q0, q1 - q0)
        q, do = q_ref[0, qs], do_ref[0, qs]
        pt = jnp.exp(st - lse_ref[0, :, qs])            # lse: [1, q1 - q0]
        dv_acc[ks] += _dot(pt.astype(do.dtype), do, _NN)
        # d loss / d s = p * (dp - adj); s = scale * q kT, and the scale
        # goes on dq and dk as they are written, not on the score tile
        dst = (pt * (dpt - adj_ref[0, :, qs])).astype(q.dtype)
        dk_acc[ks] += _dot(dst, q, _NN)
        # dqT = kT @ dsT: the operand to transpose is the k rows, not the
        # score-shaped dsT
        dq = _dot(k_ref[0, ks], dst, _TN)               # [D, q1 - q0]
        at = (i, slice(None), qs)
        if window is not None:  # zeroed at the rows' first live tile
            dq_acc[at] += dq
        elif k0 == 0:   # the rows' first k rows, if this is the first tile
            @pl.when(kj == 0)       # every q tile meets the first k tile
            def _first():
                dq_acc[at] = dq

            @pl.when(kj > 0)
            def _later():
                dq_acc[at] += dq
        else:
            dq_acc[at] += dq

    def run(masked: bool, line: Optional[str] = None):
        """A tile in :func:`bwd_tile_pieces`, all in one basic block, the
        two operand-only matmuls of the next ``BWD_AHEAD`` pieces written
        before a piece's vector work: the scheduler keeps that order, and
        the MXU then runs them while the vector unit takes the mask, the
        ``exp`` and ``dsT`` of the piece before."""
        def tile():
            pieces = bwd_tile_pieces(block_q, block_k, masked, line)
            issued = []
            for n, piece in enumerate(pieces):
                for nxt in pieces[len(issued):n + 1 + BWD_AHEAD]:
                    issued.append(ahead(masked, nxt))
                finish(piece, *issued[n])
        return tile

    def when(cond):
        """``pl.when`` of a step inside the k tile's band."""
        return pl.when(cond if window is None
                       else jnp.logical_and(live, cond))

    banded = banded_tiles(block_q, block_k, window)
    if window is not None:
        first_row, first_col = qi * block_q, kj * block_k

        @when(kj == _first_band_k_tile(qi, block_q, block_k, window))
        def _zero_dq():
            dq_acc[i] = jnp.zeros(dq_acc.shape[1:], jnp.float32)
        crossed, clean = _band_tiles(first_row, first_col, block_q, block_k,
                                     window)
        if banded:
            # the diagonal and the edge cross different tiles, each corner
            # to corner
            when(qi == kj)(run(True, "diagonal"))
            when(first_col == first_row - window)(run(True, "edge"))
        else:
            when(crossed)(run(True))
        when(clean)(run(False))
        last_kj = jnp.minimum(_last_live_k_tile(qi, block_q, block_k),
                              nk - 1)
    elif causal:
        first_row, first_col = qi * block_q, kj * block_k
        last_row, last_col = first_row + block_q - 1, first_col + block_k - 1
        # the diagonal crosses the tile: some of it is masked, not all.
        # Square tiles meet the diagonal corner to corner (qi == kj)
        crossed = jnp.logical_and(first_col <= last_row,
                                  last_col > first_row)
        pl.when(crossed)(run(True, "diagonal" if banded else None))
        # wholly at or below the diagonal: no mask to build. Tiles
        # strictly above it run nothing (and fetch nothing: q_tile)
        pl.when(last_col <= first_row)(run(False))
        last_kj = jnp.minimum(_last_live_k_tile(qi, block_q, block_k),
                              nk - 1)
    else:
        run(False)()
        last_kj = nk - 1

    rows = pl.ds(pl.multiple_of(i * block_q, block_q), block_q)

    @when(kj == last_kj)
    def _write_dq():
        # the accumulator is transposed once a q tile, on its way out
        dq_ref[0, rows] = jnp.transpose(dq_acc[i] * scale).astype(
            dq_ref.dtype)

    @pl.when(step == nq - 1)
    def _write_dkv():
        dk_ref[0, 0] = (dk_acc[:] * scale).astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_acc[:].astype(dv_ref.dtype)


# jitted so that the call sites of a program (a ring step's switch
# branches, layers outside a scan) share one trace and one Mosaic lowering
@functools.partial(jax.jit, static_argnames=("H", "causal", "scale",
                                             "blocks", "interpret",
                                             "window", "heads_first"))
def _flash_bwd_local(q, k, v, do, lse, adj, *, H, causal, scale, blocks,
                     interpret, window=None, heads_first=False):
    """(dq ``[B, Sq, H*D]``, dk and dv ``[ranges, B, Sk, H*D]``) of q, do
    ``[B, Sq, H*D]`` and k, v ``[B, Sk, Hkv*D]`` as the projections wrote
    them: a head is whole 128-lane columns (one at head_dim 128), so a
    ``(1, tile, D)`` block addresses it with no transpose around the call.
    lse and adj are ``[B*H, 1, Sq]`` float32, a q row along the lanes. dk
    and dv come a q head: with grouped heads a k/v head's are the sum over
    its group, the caller's to take. ``heads_first`` (a head of 64): q, do
    and dq ``[B*H, Sq, D]``, k and v ``[B*Hkv, Sk, D]``, dk and dv
    ``[ranges, B*H, Sk, D]``."""
    if heads_first:
        (BH, Sq, D), Sk = q.shape, k.shape[1]
        B = BH // H
        Hkv = k.shape[0] // B
        group = _group(H, Hkv)
    else:
        B, Sq, M = q.shape
        Sk, D = k.shape[1], M // H
        group = _group(H, k.shape[2] // D)
    bq, bk, rows = blocks
    grid = flash_bwd_grid(B, H, Sq, Sk, blocks, window)
    nq = rows // bq

    if window is not None:
        # the q axis walks the k tile's band alone; past its last tile the
        # index stays there (and inside the range, if the band leaves it)
        def q_tile(r, j, i):
            return jnp.clip(
                jnp.minimum(*_band_q_tile(j, i, r * nq, nq, bq, bk, window)),
                r * nq, r * nq + nq - 1)
    elif causal:
        # above the diagonal the block index repeats the k tile's first
        # live q tile, so the pipeline issues no DMA for a skipped step
        def q_tile(r, j, i):
            return jnp.clip(_first_live_q_tile(j, bq, bk), r * nq + i,
                            r * nq + nq - 1)
    else:
        def q_tile(r, j, i):
            return r * nq + i
    row_spec = pl.BlockSpec((1, 1, bq),
                            lambda b, r, j, i: (b, 0, q_tile(r, j, i)))
    if heads_first:
        q_spec = pl.BlockSpec((1, bq, D),
                              lambda b, r, j, i: (b, q_tile(r, j, i), 0))
        k_spec = pl.BlockSpec(
            (1, bk, D),
            lambda b, r, j, i: (b // H * Hkv + b % H // group, j, 0))
        dq_spec = pl.BlockSpec((1, rows, D), lambda b, r, j, i: (b, r, 0))
        part_spec = pl.BlockSpec((1, 1, bk, D),
                                 lambda b, r, j, i: (r, b, j, 0))
        part = jax.ShapeDtypeStruct((grid[1], B * H, Sk, D), k.dtype)
    else:
        q_spec = pl.BlockSpec(
            (1, bq, D), lambda b, r, j, i: (b // H, q_tile(r, j, i), b % H))
        if group == 1:
            k_spec = pl.BlockSpec((1, bk, D),
                                  lambda b, r, j, i: (b // H, j, b % H))
        else:
            k_spec = pl.BlockSpec(
                (1, bk, D), lambda b, r, j, i: (b // H, j, b % H // group))
        dq_spec = pl.BlockSpec((1, rows, D),
                               lambda b, r, j, i: (b // H, r, b % H))
        part_spec = pl.BlockSpec((1, 1, bk, D),
                                 lambda b, r, j, i: (r, b // H, j, b % H))
        part = jax.ShapeDtypeStruct((grid[1], B, Sk, M), k.dtype)
    return kernel_call(pl.pallas_call,
        functools.partial(_flash_bwd_kernel, scale=scale, causal=causal,
                          block_q=bq, block_k=bk, window=window),
        grid=grid,
        in_specs=[q_spec, q_spec, k_spec, k_spec, row_spec, row_spec],
        out_specs=[dq_spec, part_spec, part_spec],
        out_shape=[jax.ShapeDtypeStruct(q.shape, q.dtype), part, part],
        scratch_shapes=[
            pltpu.VMEM((nq, D, bq), jnp.float32),    # dqT, a q tile each
            pltpu.VMEM((bk, D), jnp.float32),        # dk of the k tile
            pltpu.VMEM((bk, D), jnp.float32),        # dv
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary",
                                 "arbitrary"),
            vmem_limit_bytes=BWD_VMEM_BUDGET),
        interpret=interpret,
        name="hvd_flash_bwd",
    )(q, do, k, v, lse, adj)


# ``adj = sum_d do · o - dlse``, the ``[B·H, 1, Sq]`` float32 rows the
# backward reads beside ``lse``, is a third kernel's at heads of whole lane
# tiles, ``hvd_flash_adj`` (PR 62): it reads do and o once, as the ``[B, S,
# H·D]`` arrays the o-projection's backward and the forward kernel wrote,
# whole rows a grid step, and is bound by those bytes (v5e, ``[1, 8192,
# 20·256]`` in glm-4.7-flash.s8192's step: 0.22 ms a call, 753 GB/s). As
# ``jax.numpy`` the sums were XLA's to lay out: the arithmetic was 0.11 ms a
# call there, but XLA fed it do and o relaid with the tokens on the lanes,
# 0.83 ms a call of copies and 0.26 of waits. Alone, on row-major operands,
# XLA's sums are as fast as this kernel: the gain is the step's, not the
# call's. Inside ``hvd_flash_bwd`` (o a fourth q-shaped operand, the rows
# made at a q tile's first live k tile) the sums read within 0.12 ms a call
# of this form and took the backward's last 0.27 MiB of VMEM at 20 heads of
# 256 (PERF.md, PR 62).
ADJ_NAME = "hvd_flash_adj"
#: bytes of do (and of o) a grid step of ``hvd_flash_adj`` may read: two
#: operands double-buffered are four such blocks, under the v5e's default
#: scoped-VMEM limit
ADJ_BLOCK_BYTES = 2 * 1024 * 1024


def flash_adj_blocks(Sq: int, H: int, D: int, dtype) -> Tuple[int, int]:
    """``(rows, heads)`` of a grid step of ``hvd_flash_adj``: a block is
    ``rows`` positions of ``heads`` heads' lane tiles of ``[B, Sq, H·D]``.
    The kernel is bound by the bytes it reads, so a block is as many whole
    heads as ``ADJ_BLOCK_BYTES`` hold at ``MIN_BLOCK`` rows (all of them in
    every cell: whole rows of the array, one contiguous read), then as many
    rows of ``TILES`` as still fit."""
    unit = MIN_BLOCK * D * jnp.dtype(dtype).itemsize
    heads = max(h for h in range(1, H + 1) if H % h == 0
                and (h == 1 or h * unit <= ADJ_BLOCK_BYTES))
    rows = next((t for t in TILES if Sq % t == 0
                 and t // MIN_BLOCK * heads * unit <= ADJ_BLOCK_BYTES),
                MIN_BLOCK)
    return rows, heads


def _flash_adj_kernel(do_ref, o_ref, dlse_ref, adj_ref, *, D: int):
    """One (batch row, q rows, heads) step: ``adj = sum_d do · o - dlse`` of
    the block's heads, each a ``[1, rows]`` row of ``adj_ref [heads, 1,
    rows]``, from ``[1, rows, heads · D]`` blocks of do and o. Of a head's
    ``[MIN_BLOCK positions, D]`` float32 products the lane tiles are added,
    the one that is left is transposed and the sum runs down its sublanes,
    so that a position's sum lies on its lane: no MXU pass, nothing rounded
    below float32."""
    rows, lanes = do_ref.shape[1:]
    for r0 in range(0, rows, MIN_BLOCK):
        at = pl.ds(r0, MIN_BLOCK)
        for h in range(lanes // D):
            tiles = [pl.ds(l0, MIN_BLOCK)
                     for l0 in range(h * D, (h + 1) * D, MIN_BLOCK)]
            folded = functools.reduce(jnp.add, (
                do_ref[0, at, t].astype(jnp.float32)
                * o_ref[0, at, t].astype(jnp.float32) for t in tiles))
            adj_ref[h, :, at] = jnp.sum(jnp.transpose(folded), axis=0,
                                        keepdims=True) - dlse_ref[h, :, at]


@functools.partial(jax.jit, static_argnames=("H", "interpret"))
def _flash_adj_local(do, o, dlse, *, H, interpret):
    """``adj [B·H, 1, Sq]`` float32, the rows ``hvd_flash_bwd`` reads, of do
    and o ``[B, Sq, H·D]`` as they arrive (the forward's output and its
    cotangent, in the dtype they have; heads of whole lane tiles) and
    ``dlse [B·H, 1, Sq]`` float32: do and o are read once, by this kernel,
    and nothing float32 of their size is written."""
    B, Sq, M = do.shape
    D = M // H
    rows, heads = flash_adj_blocks(Sq, H, D, do.dtype)
    groups = H // heads
    wide = pl.BlockSpec((1, rows, heads * D), lambda b, i, g: (b, i, g))
    row = pl.BlockSpec((heads, 1, rows),
                       lambda b, i, g: (b * groups + g, 0, i))
    return kernel_call(pl.pallas_call,
        functools.partial(_flash_adj_kernel, D=D),
        grid=(B, Sq // rows, groups),
        in_specs=[wide, wide, row],
        out_specs=row,
        out_shape=jax.ShapeDtypeStruct((B * H, 1, Sq), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel")),
        interpret=interpret,
        name=ADJ_NAME,
    )(do, o, dlse)


def flash_backward(q, k, v, o, lse, do, dlse, causal: bool, scale: float,
                   blocks: Optional[BwdBlocks] = None,
                   interpret: bool = False, window: Optional[int] = None):
    """(dq, dk, dv) of flash attention from its residuals (q, k, v, o
    ``[B, S, H, D]``, lse ``[B*H, Sq]``) and the cotangents of ``o`` and
    ``lse``: p = exp(s - lse) is recomputed tile by tile in VMEM (Dao et
    al.), dv = pT do, ds = p * (do vT - adj), dq = ds k, dk = dsT q, all
    times ``scale``. ``adj = sum_d do * o - dlse`` is ``hvd_flash_adj``'s,
    once a call, in float32: the lse cotangent enters through d lse / d
    s_j = p_j (lse is the row log-partition), which is what makes the (o,
    lse) pair usable as a mergeable partial result (ring attention). Operands
    multiply in the dtype they come in and accumulate in float32; p and ds
    are float32 in VMEM and cast for their matmuls. ``blocks`` overrides
    :func:`flash_bwd_blocks` (tests, sweeps). k and v may have fewer heads
    than q (grouped heads): the kernel writes each q head's part of dk and
    dv and the parts of a group are summed here, in float32. ``window``:
    the causal band of the forward."""
    B, Sq, H, D = q.shape
    Sk, Hkv = k.shape[1], k.shape[2]
    _check_window(window, causal)
    blocks = blocks or flash_bwd_blocks(Sq, Sk, D, q.dtype, window)
    if D % MIN_BLOCK:
        return _flash_backward_heads_first(q, k, v, o, lse, do, dlse, causal,
                                           scale, blocks, interpret, window)
    adj = _flash_adj_local(
        do.reshape(B, Sq, H * D), o.reshape(B, Sq, H * D),
        dlse.astype(jnp.float32).reshape(B * H, 1, Sq), H=H,
        interpret=interpret)
    dq, dk, dv = _flash_bwd_local(
        q.reshape(B, Sq, H * D), k.reshape(B, Sk, Hkv * D),
        v.reshape(B, Sk, Hkv * D), do.reshape(B, Sq, H * D),
        lse.reshape(B * H, 1, Sq), adj, H=H, causal=causal, scale=scale,
        blocks=blocks, interpret=interpret, window=window)

    def total(parts):
        if Hkv != H:    # a k/v head's gradient: its group's, every range's
            parts = parts.reshape(-1, B, Sk, Hkv, H // Hkv, D)
            return parts.astype(jnp.float32).sum((0, 4)).astype(parts.dtype)
        if parts.shape[0] > 1:
            parts = parts.astype(jnp.float32).sum(0).astype(parts.dtype)
        return parts.reshape(B, Sk, H, D)
    return dq.reshape(B, Sq, H, D), total(dk), total(dv)


def _flash_backward_heads_first(q, k, v, o, lse, do, dlse, causal, scale,
                                blocks, interpret, window):
    """:func:`flash_backward` for a head that is no whole lane column of
    ``[B, S, H·D]`` (D = 64): the operands heads first, ``[B·H, S, D]``, as
    the forward takes them; a k/v head's dk and dv are the float32 sum
    over its group's q heads and the q ranges. ``adj``'s row sums stay
    ``jax.numpy``: XLA makes them in the pass that moves do heads first,
    where ``hvd_flash_adj`` would read do a second time
    (lfm2-24b-a2b.s8192: -0.18 % with the kernel reading do and o in place,
    two heads a lane tile; PERF.md, PR 62)."""
    B, Sq, H, D = q.shape
    Sk, Hkv = k.shape[1], k.shape[2]
    # o's float32 copy is made when do arrives, not before: alone, the
    # conversion depends on the forward pass only, and XLA:TPU has started it
    # there and kept 4 bytes an element of o alive into the backward pass in
    # place of 2 (PERF.md, PR 50)
    do, o = lax.optimization_barrier((do, o))
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1)
    adj = delta.transpose(0, 2, 1).reshape(B * H, Sq) \
        - dlse.astype(jnp.float32)

    dq, dk, dv = _flash_bwd_local(
        *(_heads_first(x) for x in (q, k, v, do)),
        lse.reshape(B * H, 1, Sq), adj.reshape(B * H, 1, Sq), H=H,
        causal=causal, scale=scale, blocks=blocks, interpret=interpret,
        window=window, heads_first=True)

    def total(parts):   # [ranges, B * H, Sk, D] -> [B, Sk, Hkv, D]
        parts = parts.reshape(-1, B, Hkv, H // Hkv, Sk, D)
        summed = parts.astype(jnp.float32).sum((0, 3)).astype(parts.dtype)
        return summed.transpose(0, 2, 1, 3)
    return (dq.reshape(B, H, Sq, D).transpose(0, 2, 1, 3), total(dk),
            total(dv))


def _flash_bwd(causal, scale, block_q, block_k, interpret, window, res, cts):
    """``block_q`` / ``block_k`` are the forward's tile and set nothing
    here: the backward's come from :func:`flash_bwd_blocks`."""
    q, k, v, o, lse = res
    do, dlse = cts
    return flash_backward(q, k, v, o, lse, do, dlse, causal, scale,
                          interpret=interpret, window=window)


_flash_lse.defvjp(_flash_fwd, _flash_bwd)


def _call(q, k, v, causal, scale, block_q, block_k, interpret, window=None):
    D = q.shape[-1]
    scale = float(scale) if scale is not None else float(1.0 / (D ** 0.5))
    if block_q is None or block_k is None:
        bq, bk = flash_blocks(q.shape[1], k.shape[1], D, q.dtype, window)
        block_q, block_k = block_q or bq, block_k or bk
    return _flash_lse(q, k, v, causal, scale, block_q, block_k, interpret,
                      window)


def flash_attention_with_lse(q: jax.Array, k: jax.Array, v: jax.Array,
                             causal: bool = True,
                             scale: Optional[float] = None,
                             block_q: Optional[int] = None,
                             block_k: Optional[int] = None,
                             interpret: bool = False,
                             window: Optional[int] = None):
    """Flash attention returning ``(o, lse)``: the normalized output plus
    the per-row log-partition (``lse`` shaped ``[B*H, Sq]``). The pair is
    a mergeable partial softmax — two results over disjoint key sets
    combine exactly via logaddexp (ring attention's per-step merge).
    Differentiable in both outputs. ``block_q`` / ``block_k`` override the
    forward tile of :func:`flash_blocks` (tests). ``window`` and grouped
    heads as :func:`flash_attention_tpu` takes them."""
    return _call(q, k, v, causal, scale, block_q, block_k, interpret, window)


def flash_attention_tpu(q: jax.Array, k: jax.Array, v: jax.Array,
                        causal: bool = True,
                        scale: Optional[float] = None,
                        block_q: Optional[int] = None,
                        block_k: Optional[int] = None,
                        interpret: bool = False,
                        window: Optional[int] = None) -> jax.Array:
    """q [B, S, H, D], k/v [B, S, Hkv, D] (``H % Hkv == 0``; q head ``h``
    attends to k/v head ``h // (H // Hkv)``) → [B, S, H, D]. Requires
    S % 128 == 0 and D % 128 == 0 (use :func:`attend` for the auto-fallback
    wrapper). ``window=W`` (causal only): a query at ``t`` sees the keys
    ``t - W < j <= t``. Differentiable (custom VJP with blockwise recompute
    backward)."""
    return _call(q, k, v, causal, scale, block_q, block_k, interpret,
                 window)[0]


# ---------------------------------------------------------------------------
# Block attention: a head's whole [Sq, Sk] score tile in VMEM
# ---------------------------------------------------------------------------
#
# For short sequences (BERT: 128 to 512 positions) there is one k tile, so
# no online softmax and no running max: a grid step reads ``rows`` batch
# rows of one 128-lane column of q, k and v as the projections wrote them,
# ``[B, S, H·D]``, and keeps every score in VMEM, forward and backward.
# Nothing is transposed around the call. At head_dim 64 a 128-lane column
# holds two heads: q (and do) are stacked, one copy a head with the other
# head's lanes zeroed (:func:`_stack`), so one matmul against the unsplit
# k gives both heads' scores as ``[2·Sq, Sk]``, the softmax runs once over
# all of it, and every matmul is 128 lanes wide. The MXU passes are half
# empty either way at head_dim 64.

#: the lanes of a vreg: the width of the column of ``H·D`` a grid step takes
LANES = 128
#: the longest sequence the block kernels take: at 512 a column's two
#: float32 score tiles are 2 MiB each and the backward holds several
BLOCK_MAX_SEQ = 512
BLOCK_HEAD_DIMS = (64, 128)
#: the fewest score elements a head must have for :func:`attend` to pick
#: the block kernels. At 128 x 128 they take what XLA's fused core takes:
#: bert-large.s128 +0.84 % on one chip, -1.04 % beside dp=4's all-reduce
#: (PERF.md, PR 27). At 512 x 512 they halve the core (+7.4 %); alone, a
#: layer's forward + backward at 256 x 256 is 1067 µs against XLA's 1307
BLOCK_MIN_SCORES = 256 * 256
#: a masked key's score, as models/bert.py writes it
MASKED_SCORE = -1e9
FWD_NAME = "hvd_block_attention"
BWD_NAME = "hvd_block_attention_bwd"


def block_eligible(Sq: int, Sk: int, H: int, D: int) -> bool:
    """Whether the block kernels take q ``[.., Sq, H, D]`` against k/v
    ``[.., Sk, H, D]`` (non-causal): a head_dim that fills a 128-lane
    column alone or in pairs, heads that fill whole columns, lengths that
    are whole lane tiles and short enough for one score tile."""
    return (D in BLOCK_HEAD_DIMS and H * D % LANES == 0
            and Sq % MIN_BLOCK == 0 and Sk % MIN_BLOCK == 0
            and max(Sq, Sk) <= BLOCK_MAX_SEQ)


def block_vmem_bytes(rows: int, Sq: int, Sk: int, itemsize: int) -> int:
    """Working set of one grid step of the backward kernel, the larger of
    the two: q, o, do, dq and k, v, dk, dv columns of ``rows`` batch rows,
    double-buffered by the pipeline, the float32 lse rows (a ``[heads,
    Sq]`` tile is padded to 8 sublanes), and for each row the loop has in
    flight two float32 score tiles of a column's two heads and one cast
    for the matmuls, as ``flash_vmem_bytes`` counts them (the backward's
    dp and ds take the room of s and p: B8 S512 with two rows in flight,
    21 MiB if all four were counted apart, runs under the 16 MiB limit)."""
    io = 2 * rows * 4 * (Sq + Sk) * LANES * itemsize
    lse = 2 * rows * 8 * Sq * 4
    in_flight = math.gcd(rows, _row_unroll(Sq, Sk))
    tiles = in_flight * 2 * Sq * Sk * (4 + 4 + itemsize)
    return io + lse + tiles


def block_rows(B: int, Sq: int, Sk: int, dtype) -> int:
    """Batch rows a grid step takes: the most that divide ``B`` and fit
    ``VMEM_BUDGET`` by :func:`block_vmem_bytes`. A grid step costs 0.35 µs
    whatever it does and one 128 x 128 head is 0.07 µs of work (PERF.md,
    PR 25), so a step takes all it can hold."""
    itemsize = jnp.dtype(dtype).itemsize
    return max((r for r in range(1, B + 1) if B % r == 0 and
                block_vmem_bytes(r, Sq, Sk, itemsize) <= VMEM_BUDGET),
               default=1)


def block_grid(B: int, H: int, D: int, rows: int) -> Tuple[int, int]:
    """The block kernels' grid: (row groups, 128-lane columns of H·D)."""
    return (B // rows, H * D // LANES)


def _head_lanes(h: int, D: int):
    """[1, LANES] bool: the lanes of head ``h`` of a column's heads."""
    lane = lax.broadcasted_iota(jnp.int32, (1, LANES), 1)
    return jnp.logical_and(lane >= h * D, lane < (h + 1) * D)


def _stack(x, D: int):
    """``[S, LANES]`` → ``[heads·S, LANES]``: one copy of ``x`` a head of
    the column, each with the other heads' lanes zeroed. A matmul that
    contracts the lanes then gives that head's product alone, and one that
    contracts the rows sums the heads into their own lanes."""
    if D == LANES:
        return x
    return jnp.concatenate(
        [jnp.where(_head_lanes(h, D), x, jnp.zeros_like(x))
         for h in range(LANES // D)], axis=0)


def _unstack(y, D: int):
    """``[heads·S, LANES]`` → ``[S, LANES]``: head ``h``'s lanes from the
    ``h``-th copy."""
    heads = LANES // D
    S = y.shape[0] // heads
    out = y[:S]
    for h in range(1, heads):
        out = jnp.where(_head_lanes(h, D), y[h * S:(h + 1) * S], out)
    return out


def _scaled(q, scale: float):
    """(q with ``scale`` folded in, what is left to multiply the scores
    by). A power of two (head_dim 64: 1/8) folds exactly in any float
    dtype and saves a pass over the score tile; any other scale stays on
    the float32 scores."""
    if math.log2(scale).is_integer():
        return q * jnp.asarray(scale, q.dtype), 1.0
    return q, scale


def _scores(q, k, fill, scale: float, masked: bool):
    """float32 ``q·kᵀ · scale`` with the key mask applied: ``fill`` is
    positive at a live key and holds the score to write at a masked one."""
    s = _dot(q, k, _NT)
    if scale != 1.0:
        s = s * scale
    return jnp.where(fill > 0, s, fill) if masked else s


#: batch rows the kernels' row loop may have in flight (code size)
MAX_ROW_UNROLL = 8


def _row_unroll(Sq: int, Sk: int) -> int:
    """Batch rows the kernels' row loop has in flight: a row of short
    sequences is a short chain of small matmuls and reductions, each
    waiting for the last, and more rows in flight fill the gaps (v5e,
    B64 S128, forward + backward of a layer: 1069 µs at 1, 901 at 2, 826 at
    4, 780 at 8; PERF.md, PR 27). As many as hold the score elements of
    two rows of 512 x 512, a power of two so that it divides the rows
    of a step."""
    fit = max(1, 2 * BLOCK_MAX_SEQ ** 2 // (Sq * Sk))
    return min(MAX_ROW_UNROLL, 1 << (fit.bit_length() - 1))


def _for_rows(row, rows: int, unroll: int) -> None:
    """``row(i)`` for the ``rows`` batch rows of a grid step, ``unroll``
    (as far as it divides them) to an iteration of the loop: Mosaic
    unrolls a loop wholly or not at all."""
    unroll = math.gcd(rows, unroll)

    def body(g, carry):
        for j in range(unroll):
            row(g * unroll + j)
        return carry
    lax.fori_loop(0, rows // unroll, body, 0)


def _block_fwd_kernel(fill_ref, q_ref, k_ref, v_ref, o_ref, lse_ref, *,
                      scale: float, D: int, masked: bool):
    Sq, Sk = q_ref.shape[1], k_ref.shape[1]

    def row(i):
        v = v_ref[i]
        q2, left = _scaled(_stack(q_ref[i], D), scale)
        s = _scores(q2, k_ref[i], fill_ref[i], left, masked)
        m = jnp.max(s, axis=-1, keepdims=True)      # [heads·Sq, 1]
        p = jnp.exp(s - m)
        l = jnp.sum(p, axis=-1, keepdims=True)
        # copy h holds p_h·v: head h's lanes of it are head h's output
        o = _dot(p.astype(v.dtype), v, _NN) / l
        o_ref[i] = _unstack(o, D).astype(o_ref.dtype)
        lse = m + jnp.log(l)
        for h in range(LANES // D):
            lse_ref[i, 0, h] = lse[h * Sq:(h + 1) * Sq, 0]

    _for_rows(row, q_ref.shape[0], _row_unroll(Sq, Sk))


def _block_bwd_kernel(fill_ref, q_ref, k_ref, v_ref, o_ref, do_ref, lse_ref,
                      dq_ref, dk_ref, dv_ref, *, scale: float, D: int,
                      masked: bool):
    Sq, Sk = q_ref.shape[1], k_ref.shape[1]

    def row(i):
        k, v, do = k_ref[i], v_ref[i], do_ref[i]
        fill = fill_ref[i]
        q2, do2 = _stack(q_ref[i], D), _stack(do, D)
        lse = jnp.concatenate([lse_ref[i, 0, h][:, None]
                               for h in range(LANES // D)], axis=0)
        qs, left = _scaled(q2, scale)
        p = jnp.exp(_scores(qs, k, fill, left, masked) - lse)
        # d loss / d s through softmax is p ⊙ (dp − Δ), Δ = rowsum(do ⊙ o)
        # per head, and s = scale · q·kᵀ: the scale goes on dq and dk, not
        # on the score tile. A masked key's s is a constant, and there p
        # is 0 unless every key of the row is masked (p uniform): then the
        # whole row's ds is 0, which the same scalar says
        delta = jnp.sum(_stack(do.astype(jnp.float32)
                               * o_ref[i].astype(jnp.float32), D),
                        axis=-1, keepdims=True)
        ds = (p * (_dot(do2, v, _NT) - delta)).astype(q2.dtype)
        live = jnp.max(fill, axis=-1, keepdims=True) > 0
        ds_scale = jnp.where(live, scale, 0.0) if masked else scale
        # contracting the stacked rows sums the heads into their lanes
        dv_ref[i] = _dot(p.astype(do.dtype), do2, _TN).astype(dv_ref.dtype)
        dk_ref[i] = (_dot(ds, q2, _TN) * ds_scale).astype(dk_ref.dtype)
        dq_ref[i] = (_unstack(_dot(ds, k, _NN), D)
                     * ds_scale).astype(dq_ref.dtype)

    _for_rows(row, q_ref.shape[0], _row_unroll(Sq, Sk))


class _Statics(NamedTuple):
    """What a call is compiled for, beside its shapes."""
    scale: float
    D: int
    masked: bool
    rows: Optional[int]
    interpret: bool


# the operands of each kernel, by the block spec each takes: the mask's
# fill, a q-shaped column, a k-shaped column, the lse rows
_FWD_IO = (("fill", "q", "k", "k"), ("q", "lse"))
_BWD_IO = (("fill", "q", "k", "k", "q", "q", "lse"), ("q", "k", "k"))


def _block_call(kernel, name, io, q, k, statics: _Statics):
    """One pallas_call over (row groups, 128-lane columns)."""
    B, Sq, M = q.shape
    Sk, heads = k.shape[1], LANES // statics.D
    rows = statics.rows or block_rows(B, Sq, Sk, q.dtype)
    if B % rows:
        raise ValueError(f"rows={rows} does not divide the batch {B}")

    def column(S):
        return pl.BlockSpec((rows, S, LANES), lambda b, g: (b, 0, g))
    spec = {"fill": pl.BlockSpec((rows, 1, Sk), lambda b, g: (b, 0, 0)),
            "q": column(Sq), "k": column(Sk),
            "lse": pl.BlockSpec((rows, 1, heads, Sq),
                                lambda b, g: (b, g, 0, 0))}
    shape = {"q": jax.ShapeDtypeStruct(q.shape, q.dtype),
             "k": jax.ShapeDtypeStruct(k.shape, k.dtype),
             "lse": jax.ShapeDtypeStruct((B, M // LANES, heads, Sq),
                                         jnp.float32)}
    ins, outs = io
    return kernel_call(pl.pallas_call,
        functools.partial(kernel, scale=statics.scale, D=statics.D,
                          masked=statics.masked),
        grid=(B // rows, M // LANES),
        in_specs=[spec[x] for x in ins],
        out_specs=[spec[x] for x in outs],
        out_shape=[shape[x] for x in outs],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=statics.interpret, name=name)


# jitted so that a model's layers share one trace and one lowering of each
# kernel: 48 Mosaic lowerings add 7.6 s to the trace of BERT-Large's step
@functools.partial(jax.jit, static_argnames="statics")
def _block_fwd_local(fill, q, k, v, statics):
    """(o ``[B, Sq, H·D]``, lse ``[B, H·D/128, 128/D, Sq]``) of the arrays
    one device holds."""
    return _block_call(_block_fwd_kernel, FWD_NAME, _FWD_IO, q, k,
                       statics)(fill, q, k, v)


@functools.partial(jax.jit, static_argnames="statics")
def _block_bwd_local(fill, q, k, v, o, do, lse, statics):
    """(dq, dk, dv) of the arrays one device holds."""
    return _block_call(_block_bwd_kernel, BWD_NAME, _BWD_IO, q, k,
                       statics)(fill, q, k, v, o, do, lse)


def _over_mesh(local, io, q):
    """``local`` on each device's own shard of the mesh that ``q`` is
    placed on (its type says which, under any jit whose arguments are
    committed to a mesh): the kernels are independent over batch rows and
    over 128-lane columns of ``H·D``, and a bare pallas_call is a custom
    call GSPMD cannot split (JAX refuses to lower one for several
    devices). Batch rows go over ``dp`` and columns over ``tp`` where the
    axis divides them, and what is not split is computed on every device.
    On one device, or already inside a shard_map, the call is ``local``
    itself."""
    from horovod_tpu.parallel.mesh import DATA_AXIS, TENSOR_AXIS
    mesh = jax.typeof(q).sharding.mesh
    if mesh.empty or mesh.size == 1 or mesh.manual_axes:
        return local

    def axis(name, n):
        return name if mesh.shape.get(name, 1) > 1 \
            and n % mesh.shape[name] == 0 else None
    batch = axis(DATA_AXIS, q.shape[0])
    columns = axis(TENSOR_AXIS, q.shape[2] // LANES)
    spec = {"fill": P(batch, None, None), "q": P(batch, None, columns),
            "k": P(batch, None, columns),
            "lse": P(batch, columns, None, None)}
    ins, outs = io
    return jax.shard_map(local, mesh=mesh,
                         in_specs=tuple(spec[x] for x in ins),
                         out_specs=tuple(spec[x] for x in outs),
                         check_vma=False)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _block(statics, fill, q, k, v):
    return _block_fwd(statics, fill, q, k, v)[0]


def _block_fwd(statics, fill, q, k, v):
    call = _over_mesh(functools.partial(_block_fwd_local, statics=statics),
                      _FWD_IO, q)
    o, lse = call(fill, q, k, v)
    return o, (fill, q, k, v, o, lse)


def _block_bwd(statics, res, do):
    fill, q, k, v, o, lse = res
    call = _over_mesh(functools.partial(_block_bwd_local, statics=statics),
                      _BWD_IO, q)
    return (None,) + tuple(call(fill, q, k, v, o, do, lse))


_block.defvjp(_block_fwd, _block_bwd)


def block_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                    key_mask: Optional[jax.Array] = None,
                    scale: Optional[float] = None,
                    rows: Optional[int] = None,
                    interpret: bool = False) -> jax.Array:
    """Non-causal attention of q ``[B, Sq, H, D]`` on k/v ``[B, Sk, H, D]``
    with a ``[B, Sk]`` bool mask of live keys, in the block kernels
    (:func:`block_eligible` shapes; use :func:`attend` for the dispatch).
    Scores and softmax are float32, the probabilities are cast to ``v``'s
    dtype for p·v, and the residuals of the backward are q, k, v, o and
    the float32 log-sum-exp: no ``[B, H, Sq, Sk]`` array reaches HBM. A
    masked key scores ``MASKED_SCORE`` as in ``models/bert.py``, so a row
    whose keys are all masked attends to all of them evenly (its scores
    are written as 0: the log-sum-exp of a row of -1e9 loses log(Sk) in
    float32). ``rows`` overrides :func:`block_rows` (tests, sweeps). Under
    a jit whose arrays are placed on a mesh each device runs the kernels
    on its own batch rows and heads (:func:`_over_mesh`)."""
    B, Sq, H, D = q.shape
    Sk = k.shape[1]
    if not block_eligible(Sq, Sk, H, D):
        raise ValueError(f"block attention does not take Sq={Sq} Sk={Sk} "
                         f"H={H} D={D}")
    if key_mask is None:
        fill = jnp.ones((B, 1, Sk), jnp.float32)
    else:
        some = jnp.any(key_mask, axis=-1, keepdims=True)
        fill = jnp.where(key_mask, 1.0, jnp.where(some, MASKED_SCORE, 0.0))
        fill = fill.astype(jnp.float32)[:, None, :]
    statics = _Statics(float(scale) if scale is not None else D ** -0.5, D,
                       key_mask is not None, rows, interpret)
    o = _block(statics, fill, q.reshape(B, Sq, H * D),
               k.reshape(B, Sk, H * D), v.reshape(B, Sk, H * D))
    return o.reshape(B, Sq, H, D)


def _key_masked_attention(q, k, v, key_mask, scale=None):
    """The XLA core of ``models/bert.py``: scores in the inputs' dtype,
    masked keys at ``MASKED_SCORE``, a float32 softmax, probabilities cast
    back for p·v. What the block kernels are held against."""
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k)
    s = (s / jnp.sqrt(jnp.asarray(q.shape[-1], q.dtype)) if scale is None
         else s * scale)
    s = jnp.where(key_mask[:, None, None, :], s, MASKED_SCORE)
    p = jax.nn.softmax(s.astype(jnp.float32), axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v)


def flash_eligible(Sq: int, Sk: int, D: int) -> bool:
    """The kernel's contract to callers: every length a multiple of
    ``MIN_BLOCK`` (the tile is then :func:`flash_blocks`' to choose), a
    head of whole lane tiles or of ``NARROW_HEAD``."""
    return ((D % MIN_BLOCK == 0 or D == NARROW_HEAD)
            and Sq % MIN_BLOCK == 0 and Sk % MIN_BLOCK == 0)


def attention_path(Sq: int, Sk: int, H: int, D: int, causal: bool,
                   masked: bool) -> str:
    """Which implementation :func:`attend` takes, from the shape alone:
    ``"flash"`` wherever the flash kernel's contract holds and no key
    mask is given (but at a head of 64 where the block kernels serve the
    call), ``"block"`` where a head's whole score tile fits VMEM
    and is large enough to be worth a kernel (non-causal; with or without
    a key mask), else ``"xla"``; off the TPU always ``"xla"``."""
    if jax.default_backend() != "tpu":
        return "xla"
    block = (not causal and block_eligible(Sq, Sk, H, D)
             and Sq * Sk >= BLOCK_MIN_SCORES)
    # (a short non-causal call at a head of 64 stays the block kernels')
    if (not masked and flash_eligible(Sq, Sk, D)
            and not (block and D % MIN_BLOCK)):
        return "flash"
    return "block" if block else "xla"


def _banded_attention(q, k, v, window, scale=None):
    """The XLA form of causal attention with a window and / or grouped
    heads, what the flash kernels are held against: q ``[B, S, H, D]``
    against k/v ``[B, S, Hkv, D]``, float32 scores and softmax, a query at
    ``t`` on the keys ``t - window < j <= t`` (all ``j <= t`` without a
    window)."""
    B, Sq, H, D = q.shape
    Sk, Hkv = k.shape[1], k.shape[2]
    scale = scale if scale is not None else (1.0 / (D ** 0.5))
    qg = q.astype(jnp.float32).reshape(B, Sq, Hkv, _group(H, Hkv), D)
    s = jnp.einsum("bqhgd,bkhd->bhgqk", qg, k.astype(jnp.float32)) * scale
    t, j = jnp.arange(Sq)[:, None], jnp.arange(Sk)[None, :]
    live = j <= t
    if window is not None:
        live = jnp.logical_and(live, j > t - window)
    p = jax.nn.softmax(jnp.where(live, s, NEG_INF), axis=-1)
    out = jnp.einsum("bhgqk,bkhd->bqhgd", p, v.astype(jnp.float32))
    return out.reshape(B, Sq, H, D).astype(q.dtype)


def attend(q: jax.Array, k: jax.Array, v: jax.Array, causal: bool = True,
           scale: Optional[float] = None,
           key_mask: Optional[jax.Array] = None,
           window: Optional[int] = None) -> jax.Array:
    """Attention with automatic kernel selection (:func:`attention_path`)
    on a TPU, the fused-XLA fallback elsewhere. ``key_mask`` is a
    ``[B, Sk]`` bool array of live keys (non-causal only). ``window=W``
    (causal only): a query at ``t`` sees the keys ``t - W < j <= t``. k and
    v may have fewer heads than q (grouped heads, causal only): q head
    ``h`` attends to k/v head ``h // (H // Hkv)``. Differentiable on every
    path."""
    if causal and key_mask is not None:
        raise ValueError("a key mask with causal attention is not "
                         "implemented")
    _check_window(window, causal)
    grouped = k.shape[2] != q.shape[2]
    if grouped and not causal:
        raise ValueError("grouped heads without causal attention are not "
                         "implemented")
    path = attention_path(q.shape[1], k.shape[1], q.shape[2], q.shape[3],
                          causal, key_mask is not None)
    if path == "flash":
        return flash_attention_tpu(q, k, v, causal, scale, window=window)
    if window is not None or grouped:
        return _banded_attention(q, k, v, window, scale)
    if path == "block":
        return block_attention(q, k, v, key_mask, scale)
    if key_mask is not None:
        return _key_masked_attention(q, k, v, key_mask, scale)
    from horovod_tpu.parallel.ring_attention import _plain_attention
    return _plain_attention(q, k, v, causal, scale)
