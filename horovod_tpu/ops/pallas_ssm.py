"""The Mamba-2 chunked scan as Pallas TPU kernels: what is inside a chunk
stays on the chip.

``models/mamba.py:ssm_chunked`` states the algorithm (arXiv:2405.21060,
section 6). Per head, with ``s_i`` the running sum of ``dt_t a`` inside a
chunk of ``Q`` positions and ``H`` the state the chunk starts from:

    y_i    = sum_{j <= i} exp(s_i - s_j) (c_i . b_j) dt_j x_j
             + exp(s_i) c_i . H
    H_next = exp(s_Q) H + sum_j exp(s_Q - s_j) dt_j x_j (x) b_j

In ``jax.numpy`` every factor of that is an array in HBM (at 8192 positions,
64 heads of 64 and state 128: the chunks' states and their cotangents are
134 MB each, a dozen of them written, relaid and read again). Here:

  grid = (batch, groups · head tiles, chunks) — the chunk axis sequential
  forward  ``hvd_ssm_scan``:     a step reads a chunk's x ``[Q, R·P]`` (``R``
      heads of a group side by side: all ``H / G`` of them where the
      group's blocks fit :data:`VMEM_BUDGET`, else a *head tile* of them,
      :func:`ssm_head_tile`), b and c ``[Q, N]`` (read again by every head
      tile of the group), dt
      and s (twice: positions along the sublanes ``[Q, R]`` and along the
      lanes ``[R, Q]``) and writes y ``[Q, R·P]`` float32; the group's
      carried state ``[N, R·P]`` float32 lives in a VMEM scratch across
      the chunk axis, zeroed at chunk 0.
  backward ``hvd_ssm_scan_bwd``: the same grid from the last chunk to the
      first, the state's cotangent in the scratch; scores and decays are
      made again from x, b, c, s, dt, transposed (``[j, i]``) so that every
      product of a head is a plain or an a·bᵀ matmul. It reads the state
      each chunk started from, which the forward writes under
      differentiation (``[B, n, G, N, R·P]`` float32: the one array of the
      chunks' size that reaches HBM) and gives dx, db and dc (summed over
      the step's heads in the step, and over a group's head tiles outside
      the kernel in float32, as ``hvd_flash_bwd``'s dk and dv are over a
      query group), d dt and d s. The loop over a step's heads keeps what
      needs a head's ``[Q, Q]`` arrays and leaves four raw reductions a
      head; d dt and d s are then one expression over the step's ``[Q, R]``
      arrays (:func:`_ddt_ds`): in a Mosaic kernel a ``[Q, 1]`` float32
      value takes a vector register a sublane tile with one lane in use, so
      a head's column arithmetic cost what a ``[Q, 128]`` pass costs.

Nothing ``[Q, Q]``-sized, no chunk's own state and no cotangent of a state
is written to HBM in either direction.

**Heads narrower than the lanes.** A head of 64 channels is half a lane
tile. The kernels never slice one out: they work on lane tiles of
``heads_per_tile`` heads (:func:`ssm_tiles`) and give each head's ``[Q, Q]``
weights the tile with the other heads' lanes zeroed, which costs the MXU
what a 64-wide product costs (half its columns idle either way).

**Precision** (the benchmark configuration's ``assumed.mamba_scan``): dt,
s, every decay and the carried state are float32; the matmuls take
operands of ``x.dtype`` and accumulate in float32; decays and dt are
multiplied into the scores in float32 before the cast; y is float32. The
cotangent of y is cast to ``x.dtype`` for its matmuls, as XLA's default
precision does with the float32 cotangent of the ``jax.numpy`` form.

Softplus, ``a = -exp(a_log)``, the cumulative sums and the skip ``D x`` stay
in ``jax.numpy`` under autodiff: the kernels take ``s`` and ``dt`` and
return their cotangents.
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from horovod_tpu.profiling.compile_watch import kernel_call

LANES = 128
FWD_NAME = "hvd_ssm_scan"
BWD_NAME = "hvd_ssm_scan_bwd"

_NT = (((1,), (1,)), ((), ()))      # a · bᵀ
_NN = (((1,), (0,)), ((), ()))      # a · b
_TN = (((0,), (0,)), ((), ()))      # aᵀ · b


def _dot(a, b, dims):
    return lax.dot_general(a, b, dims, preferred_element_type=jnp.float32)


class SsmTiles(NamedTuple):
    """How a group's heads lie on the lanes: ``tiles`` column tiles of
    ``heads_per_tile`` heads of ``head_dim`` channels each."""
    heads_per_tile: int
    head_dim: int
    tiles: int

    @property
    def width(self) -> int:
        return self.heads_per_tile * self.head_dim


def ssm_tiles(H: int, P: int, G: int) -> SsmTiles:
    """Heads a lane tile holds: as many as fill ``LANES`` (two heads of 64),
    one where a head is a lane tile or more, never more than the group
    has."""
    R = H // G
    per_tile = max(1, min(R, LANES // P))
    while R % per_tile:
        per_tile -= 1
    return SsmTiles(per_tile, P, R // per_tile)


#: bytes one grid step of the backward (the larger of the two) may take by
#: :func:`ssm_vmem_bytes`: the v5e's default scoped-VMEM limit, which no call
#: asks to raise (the largest tile under it is the fastest a whole scan call
#: has been measured at: PERF.md section 6, PR 61)
VMEM_BUDGET = 16 * 1024 * 1024


def ssm_vmem_bytes(chunk: int, width: int, N: int, itemsize: int,
                   head_dim: int = 64) -> int:
    """Working set of one grid step of the backward at a block of ``width``
    channels: x, the float32 dy and the state the chunk started from in, dx
    out, each double-buffered by the pipeline; b and c in, db and dc out;
    dt and s in both forms and their cotangents (a ``[Q, R]`` float32 block
    takes whole 128-lane tiles); the state's cotangent in its scratch; five
    ``[Q, Q]`` float32 arrays a step (the scores, their cotangent, and a
    head's decays, weights and their cotangent, whose room the next head of
    the unrolled loop takes over); and three ``[Q, 1]`` columns a head, the
    raw reductions kept for :func:`_ddt_ds`, a vector register a sublane
    tile each. Held from above, within 3 %, to what the compiler takes of
    the scoped VMEM for the call compiled for a v5e (its
    ``used_scoped_memory_configs``, the most over sequences of 2048 to
    8192) at ONE group of heads of 64, state 128, bfloat16 (MiB here /
    taken): chunk 128 at 8, 16, 32 heads 4.69 / 4.60, 7.94 / 7.89, 14.44 /
    14.27; chunk 256 at 8, 16 heads 9.25 / 9.05, 15.0 / 14.76 (and 26.5 at
    32, which the default limit refuses). The forward takes under half
    (2.3, 3.7, 6.7; 4.4, 6.5). PERF.md section 6, PR 61."""
    wide = chunk * width * (2 * itemsize + 4) + N * width * 4
    narrow = 2 * chunk * N * (itemsize + 4)
    steps = 6 * chunk * LANES * 4
    return (2 * (wide + narrow + steps) + N * width * 4
            + 5 * chunk * chunk * 4
            + 3 * (width // head_dim) * chunk * LANES * 4)


def ssm_head_tile(H: int, P: int, G: int, N: int, chunk: int,
                  itemsize: int = 2) -> int:
    """Heads of a group one grid step works on: all ``H / G`` where their
    blocks fit :data:`VMEM_BUDGET` (8 groups of 8 heads of 64 at chunk 128:
    one block of 512 channels, 4.7 MiB), else the most whole lane tiles of
    heads that do and divide the group (ONE group of 64 heads of 64 at
    chunk 256: ``[256, 4096]`` blocks are 49.5 MiB, 32 heads 26.5; 16
    heads, 15.0 MiB)."""
    tiles = ssm_tiles(H, P, G)
    for n in range(tiles.tiles, 0, -1):
        if tiles.tiles % n == 0 and ssm_vmem_bytes(
                chunk, n * tiles.width, N, itemsize, P) <= VMEM_BUDGET:
            return n * tiles.heads_per_tile
    return tiles.heads_per_tile


def ssm_eligible(S: int, H: int, P: int, G: int, N: int, chunk: int) -> bool:
    """The kernels' contract to callers: whole chunks, and the chunk, the
    state and a tile of heads multiples of the lane tile."""
    return (S % chunk == 0 and H % G == 0 and chunk % LANES == 0
            and N % LANES == 0 and ssm_tiles(H, P, G).width % LANES == 0)


def ssm_scan_path(S: int, H: int, P: int, G: int, N: int, chunk: int) -> str:
    """Which form ``ssm_chunked`` takes, from the backend and the shapes
    alone, with the kernels' tiles where they run."""
    if jax.default_backend() != "tpu":
        return f"jax.numpy (backend {jax.default_backend()})"
    if not ssm_eligible(S, H, P, G, N, chunk):
        return ("jax.numpy (the chunk, the state or a tile of heads is no "
                f"multiple of {LANES} lanes)")
    tiles = ssm_tiles(H, P, G)
    R = ssm_head_tile(H, P, G, N, chunk)
    if R == H // G:
        return (f"kernels {FWD_NAME} / {BWD_NAME}: grid "
                f"({G} groups, {S // chunk} chunks), x and y blocks {chunk}x{H // G * P} in "
                f"{tiles.tiles} lane tiles of {tiles.heads_per_tile} heads, "
                f"b and c {chunk}x{N}, carried state {N}x{H // G * P} float32 "
                "in VMEM")
    return (f"kernels {FWD_NAME} / {BWD_NAME}: grid "
            f"({G} groups, {H // G // R} head tiles of {R} heads, "
            f"{S // chunk} chunks), x and y blocks {chunk}x{R * P} in "
            f"{R // tiles.heads_per_tile} lane tiles of "
            f"{tiles.heads_per_tile} heads, b and c {chunk}x{N} read by "
            f"every head tile, db and dc summed over them, carried state "
            f"{N}x{R * P} float32 in VMEM "
            f"({ssm_vmem_bytes(chunk, R * P, N, 2, P) / 2**20:.1f} MiB a step "
            f"of {VMEM_BUDGET / 2**20:.0f})")


# -- the pieces a test swaps for a wrong one ----------------------------------

def _decay(log_decay):
    """``exp`` of a sum of ``dt_t a`` (never positive) in float32 as it
    comes: the one place the kernels make a decay."""
    return jnp.exp(log_decay)


def _causal(Q: int, transposed: bool = False):
    """``[i, j]`` true where position ``j`` of the chunk reaches position
    ``i`` (``j <= i``); transposed, ``[j, i]``."""
    rows = lax.broadcasted_iota(jnp.int32, (Q, Q), 0)
    cols = lax.broadcasted_iota(jnp.int32, (Q, Q), 1)
    return cols >= rows if transposed else rows >= cols


def _carry(state, whole, own):
    """The state the next chunk starts from: ``exp(s_Q) H`` plus what this
    chunk's positions leave behind."""
    return state * whole + own


# -- a tile of heads ----------------------------------------------------------

def _head_of_lane(tiles: SsmTiles):
    return lax.broadcasted_iota(
        jnp.int32, (1, tiles.width), 1) // tiles.head_dim


def _spread(per_head, tile: int, tiles: SsmTiles):
    """``[rows, R]`` a value a head → ``[rows, width]`` (or ``[rows, 1]``
    where a tile is one head), each head's value on its lanes."""
    first = tile * tiles.heads_per_tile
    out = per_head[:, first + tiles.heads_per_tile - 1:
                   first + tiles.heads_per_tile]
    if tiles.heads_per_tile == 1:
        return out
    head = _head_of_lane(tiles)
    for j in reversed(range(tiles.heads_per_tile - 1)):
        out = jnp.where(head <= j, per_head[:, first + j:first + j + 1], out)
    return out


def _only(tile_values, j: int, tiles: SsmTiles):
    """The tile with the lanes of every head but its ``j``-th zeroed."""
    if tiles.heads_per_tile == 1:
        return tile_values
    return jnp.where(_head_of_lane(tiles) == j, tile_values,
                     jnp.zeros_like(tile_values))


def _per_head(tile_values, tiles: SsmTiles):
    """Row sums over each head's lanes: a list of ``[rows, 1]``."""
    if tiles.heads_per_tile == 1:
        return [jnp.sum(tile_values, axis=1, keepdims=True)]
    return [jnp.sum(_only(tile_values, j, tiles), axis=1, keepdims=True)
            for j in range(tiles.heads_per_tile)]


def _columns(cols, width: int):
    """``[rows, 1]`` columns side by side as ``[rows, width]``."""
    lane = lax.broadcasted_iota(jnp.int32, (1, width), 1)
    out = jnp.broadcast_to(cols[-1], (cols[-1].shape[0], width))
    for r in reversed(range(width - 1)):
        out = jnp.where(lane <= r, cols[r], out)
    return out


def _ddt_ds(d_dt_inside, d_since_start, d_to_end, d_whole, dt, since_start,
            until_end, to_end, whole):
    """The cotangents of dt and of the sums (their column form) for all of
    a step's heads at once, from the heads' raw reductions: every operand
    ``[Q, R]`` with a head a lane (``d_whole`` and ``whole`` ``[1, R]``),
    so the arithmetic is done once a step and not once a head on ``[Q, 1]``
    columns that take a whole vector register a sublane tile each."""
    Q = dt.shape[0]
    last = lax.broadcasted_iota(jnp.int32, (Q, 1), 0) == Q - 1
    ddt = d_dt_inside + d_to_end * until_end
    at_end = (jnp.sum(d_to_end * to_end, axis=0, keepdims=True)
              + d_whole * whole)
    ds = (d_since_start * since_start - dt * d_dt_inside - d_to_end * to_end
          + jnp.where(last, at_end, 0.0))
    return ddt, ds


# -- forward ------------------------------------------------------------------

def _fwd_kernel(x_ref, b_ref, c_ref, dtc_ref, sc_ref, sr_ref, dtr_ref, y_ref,
                *rest, tiles: SsmTiles):
    """One chunk of one group. ``rest``: the output of the states the
    chunks start from (under differentiation only), then the scratch."""
    state = rest[-1]

    @pl.when(pl.program_id(2) == 0)
    def _():
        state[...] = jnp.zeros_like(state)

    Q, T = x_ref.shape[1], tiles.width
    b, c = b_ref[0], c_ref[0]
    dtype = b.dtype
    scores = _dot(c, b, _NT)                      # [i, j]
    live = _causal(Q)
    s_col, dt_col = sc_ref[0, 0], dtc_ref[0, 0]   # [Q, R]
    s_row, dt_row = sr_ref[0, 0], dtr_ref[0, 0]   # [R, Q]
    s_last = s_col[Q - 1:Q]
    since_start = _decay(s_col)
    to_end = _decay(s_last - s_col) * dt_col
    whole = _decay(s_last)                        # [1, R]
    if len(rest) == 2:
        rest[0][0, 0, 0] = state[...]
    for t in range(tiles.tiles):
        cols = slice(t * T, (t + 1) * T)
        x_t, h_t = x_ref[0, :, cols], state[:, cols]
        y_t = _dot(c, h_t.astype(dtype), _NN) * _spread(since_start, t, tiles)
        for j in range(tiles.heads_per_tile):
            r = t * tiles.heads_per_tile + j
            decay = _decay(jnp.where(
                live, s_col[:, r:r + 1] - s_row[r:r + 1], -jnp.inf))
            weights = (scores * decay * dt_row[r:r + 1]).astype(dtype)
            y_t = y_t + _dot(weights, _only(x_t, j, tiles), _NN)
        y_ref[0, :, cols] = y_t
        leaves = (x_t.astype(jnp.float32) * _spread(to_end, t, tiles)
                  ).astype(dtype)
        state[:, cols] = _carry(h_t, _spread(whole, t, tiles),
                                _dot(b, leaves, _TN))


class _Layout(NamedTuple):
    B: int
    S: int
    H: int
    P: int
    G: int
    N: int
    chunk: int
    R: int      # heads a grid step works on: a group's, or a head tile of them

    @property
    def n(self) -> int:
        return self.S // self.chunk

    @property
    def steps(self) -> int:
        """(group, head tile) pairs: the grid's second axis."""
        return self.H // self.R

    @property
    def head_tiles(self) -> int:
        """Head tiles a group."""
        return self.H // self.G // self.R

    @property
    def tiles(self) -> SsmTiles:
        """How a step's heads lie on the lanes."""
        per_tile = ssm_tiles(self.H, self.P, self.G).heads_per_tile
        return SsmTiles(per_tile, self.P, self.R // per_tile)


def _layout(x, b, chunk: int, head_tile: Optional[int] = None) -> _Layout:
    B, S, H, P = x.shape
    G, N = b.shape[2:]
    if S % chunk or H % G:
        raise ValueError(f"chunk {chunk} does not divide {S} positions, or "
                         f"{G} groups {H} heads")
    R = head_tile or ssm_head_tile(H, P, G, N, chunk, x.dtype.itemsize)
    if (H // G) % R or R % ssm_tiles(H, P, G).heads_per_tile:
        raise ValueError(f"a head tile of {R} heads is not whole lane tiles "
                         f"of a group's {H // G} heads")
    return _Layout(B, S, H, P, G, N, chunk, R)


def _operands(x, dt, s, b, c, lay: _Layout):
    """What both kernels read, in their block specs' order: x ``[B, S,
    H·P]``, b and c ``[B, S, G·N]``, dt and s with the positions along the
    sublanes ``[B, H / R, S, R]`` (a grid step's heads last), then s and dt
    with them along the lanes ``[B, H / R, R, S]``."""
    dt_col, s_col = (v.reshape(lay.B, lay.S, lay.steps, lay.R
                               ).transpose(0, 2, 1, 3) for v in (dt, s))
    return (x.reshape(lay.B, lay.S, lay.H * lay.P),
            b.reshape(lay.B, lay.S, lay.G * lay.N),
            c.reshape(lay.B, lay.S, lay.G * lay.N), dt_col, s_col,
            s_col.transpose(0, 1, 3, 2), dt_col.transpose(0, 1, 3, 2))


def _specs(lay: _Layout, chunk_of):
    """Block specs of a chunk's (x-like, b-like, db-like, column-form,
    row-form, states) arrays; ``chunk_of(k)`` the chunk a grid step works
    on. The grid's second axis ``g`` walks the (group, head tile) pairs:
    b and c are the pair's group's, db and dc a block a pair."""
    Q, RP = lay.chunk, lay.R * lay.P
    if lay.head_tiles == 1:
        def group_of(g):
            return g
    else:
        def group_of(g):
            return g // lay.head_tiles
    return (
        pl.BlockSpec((1, Q, RP), lambda i, g, k: (i, chunk_of(k), g)),
        pl.BlockSpec((1, Q, lay.N),
                     lambda i, g, k: (i, chunk_of(k), group_of(g))),
        pl.BlockSpec((1, Q, lay.N), lambda i, g, k: (i, chunk_of(k), g)),
        pl.BlockSpec((1, 1, Q, lay.R),
                     lambda i, g, k: (i, g, chunk_of(k), 0)),
        pl.BlockSpec((1, 1, lay.R, Q),
                     lambda i, g, k: (i, g, 0, chunk_of(k))),
        pl.BlockSpec((1, 1, 1, lay.N, RP),
                     lambda i, g, k: (i, chunk_of(k), g, 0, 0)))


_SEMANTICS = ("parallel", "parallel", "arbitrary")


def _forward(x, dt, s, b, c, chunk, interpret, head_tile, save: bool):
    """(y ``[B, S, H, P]`` float32, the states the chunks start from ``[B,
    n, H / R, N, R·P]`` float32 if ``save`` else None)."""
    lay = _layout(x, b, chunk, head_tile)
    wide, narrow, _, col, row, at_start = _specs(lay, lambda k: k)
    shapes = [jax.ShapeDtypeStruct((lay.B, lay.S, lay.H * lay.P),
                                   jnp.float32),
              jax.ShapeDtypeStruct(
                  (lay.B, lay.n, lay.steps, lay.N, lay.R * lay.P),
                  jnp.float32)]
    out = kernel_call(pl.pallas_call,
        functools.partial(_fwd_kernel, tiles=lay.tiles),
        grid=(lay.B, lay.steps, lay.n),
        in_specs=[wide, narrow, narrow, col, col, row, row],
        out_specs=[wide, at_start] if save else [wide],
        out_shape=shapes if save else shapes[:1],
        scratch_shapes=[pltpu.VMEM((lay.N, lay.R * lay.P), jnp.float32)],
        compiler_params=pltpu.CompilerParams(dimension_semantics=_SEMANTICS),
        interpret=interpret, name=FWD_NAME,
    )(*_operands(x, dt, s, b, c, lay))
    return out[0].reshape(x.shape), (out[1] if save else None)


# -- backward -----------------------------------------------------------------

def _bwd_kernel(x_ref, b_ref, c_ref, dtc_ref, sc_ref, sr_ref, dy_ref, h_ref,
                dx_ref, db_ref, dc_ref, ddt_ref, dsc_ref, dsr_ref, dstate, *,
                tiles: SsmTiles):
    """One chunk of one group, the chunks walked from the last to the
    first; ``dstate`` the cotangent of the state the chunk hands on. Inside
    a head the ``[Q, Q]`` arrays are ``[j, i]``: the position that is read
    along the sublanes, the one that reads it along the lanes."""
    @pl.when(pl.program_id(2) == 0)
    def _():
        dstate[...] = jnp.zeros_like(dstate)

    Q, T = x_ref.shape[1], tiles.width
    R = tiles.tiles * tiles.heads_per_tile
    b, c = b_ref[0], c_ref[0]
    dtype = b.dtype
    scores = _dot(b, c, _NT)                      # [j, i]
    live = _causal(Q, transposed=True)
    s_col, dt_col = sc_ref[0, 0], dtc_ref[0, 0]   # [Q, R]
    s_row = sr_ref[0, 0]                          # [R, Q]
    s_last = s_col[Q - 1:Q]
    since_start = _decay(s_col)
    until_end = _decay(s_last - s_col)
    to_end = until_end * dt_col
    whole = _decay(s_last)                        # [1, R]
    dscores = jnp.zeros((Q, Q), jnp.float32)
    db = jnp.zeros(b.shape, jnp.float32)
    dc = jnp.zeros(c.shape, jnp.float32)
    # a head's four raw reductions, a [Q, 1] (d_whole: [1, 1]) column each
    d_dt_inside, d_since_start, d_to_end, d_whole = [], [], [], []
    for t in range(tiles.tiles):
        cols = slice(t * T, (t + 1) * T)
        x_t, dy_t = x_ref[0, :, cols], dy_ref[0, :, cols]
        h_t, dh_t = h_ref[0, 0, 0, :, cols], dstate[:, cols]
        x_f32, dy_low = x_t.astype(jnp.float32), dy_t.astype(dtype)
        h_low, dh_low = h_t.astype(dtype), dh_t.astype(dtype)
        start_t, end_t = (_spread(v, t, tiles) for v in (since_start, to_end))
        # y's part from the state the chunk starts from: e_i c_i . H
        from_state = _dot(c, h_low, _NN)
        dfrom = (dy_t * start_t).astype(dtype)
        dc = dc + _dot(dfrom, h_low, _NT)
        d_since_start += _per_head(dy_t * from_state, tiles)
        # the state the chunk hands on: exp(s_Q) H + b^T (to_end x)
        dleaves = _dot(b, dh_low, _NN)
        db = db + _dot((x_f32 * end_t).astype(dtype), dh_low, _NT)
        dx_t = dleaves * end_t
        d_to_end += _per_head(dleaves * x_f32, tiles)
        d_whole += _per_head(jnp.sum(dh_t * h_t, axis=0, keepdims=True),
                             tiles)
        dstate[:, cols] = _carry(dh_t, _spread(whole, t, tiles),
                                 _dot(c, dfrom, _TN))
        for j in range(tiles.heads_per_tile):
            r = t * tiles.heads_per_tile + j
            dt_r, s_r = dt_col[:, r:r + 1], s_col[:, r:r + 1]
            dy_j = _only(dy_low, j, tiles)
            decay = _decay(jnp.where(live, s_row[r:r + 1] - s_r, -jnp.inf))
            dweights = _dot(x_t, dy_j, _NT) * decay
            dscores_r = dweights * dt_r
            dscores = dscores + dscores_r
            d_dt_inside.append(jnp.sum(dweights * scores, axis=1,
                                       keepdims=True))
            dsr_ref[0, 0, r:r + 1] = jnp.sum(dscores_r * scores, axis=0,
                                             keepdims=True)
            weights = (scores * decay * dt_r).astype(dtype)
            dx_t = dx_t + _dot(weights, dy_j, _NN)
        dx_ref[0, :, cols] = dx_t.astype(dx_ref.dtype)
    dscores = dscores.astype(dtype)
    db_ref[0] = (db + _dot(dscores, c, _NN)).astype(db_ref.dtype)
    dc_ref[0] = (dc + _dot(dscores, b, _TN)).astype(dc_ref.dtype)
    ddt_ref[0, 0], dsc_ref[0, 0] = _ddt_ds(
        *(_columns(v, R) for v in (d_dt_inside, d_since_start, d_to_end,
                                   d_whole)),
        dt_col, since_start, until_end, to_end, whole)


def _backward(x, dt, s, b, c, states, dy, chunk, interpret, head_tile):
    lay = _layout(x, b, chunk, head_tile)
    wide, narrow, part, col, row, at_start = _specs(
        lay, lambda k: lay.n - 1 - k)
    flat = (lay.B, lay.S, lay.H * lay.P)
    # db and dc a (group, head tile) pair: a group's own where it is one
    # block, else its head tiles' parts in float32, summed below
    parts = (lay.B, lay.S, lay.steps * lay.N)
    whole = lay.head_tiles == 1
    col_shape = jax.ShapeDtypeStruct((lay.B, lay.steps, lay.S, lay.R),
                                     jnp.float32)
    row_shape = jax.ShapeDtypeStruct((lay.B, lay.steps, lay.R, lay.S),
                                     jnp.float32)
    dx, db, dc, ddt_col, ds_col, ds_row = kernel_call(pl.pallas_call,
        functools.partial(_bwd_kernel, tiles=lay.tiles),
        grid=(lay.B, lay.steps, lay.n),
        in_specs=[wide, narrow, narrow, col, col, row, wide, at_start],
        out_specs=[wide, part, part, col, col, row],
        out_shape=[jax.ShapeDtypeStruct(flat, x.dtype),
                   jax.ShapeDtypeStruct(parts,
                                        b.dtype if whole else jnp.float32),
                   jax.ShapeDtypeStruct(parts,
                                        c.dtype if whole else jnp.float32),
                   col_shape, col_shape, row_shape],
        scratch_shapes=[pltpu.VMEM((lay.N, lay.R * lay.P), jnp.float32)],
        compiler_params=pltpu.CompilerParams(dimension_semantics=_SEMANTICS),
        interpret=interpret, name=BWD_NAME,
    )(*_operands(x, dt, s, b, c, lay)[:6],
      dy.astype(jnp.float32).reshape(flat), states)

    def heads_last(col_form):
        return col_form.transpose(0, 2, 1, 3).reshape(lay.B, lay.S, lay.H)

    def of_group(part, like):
        if not whole:
            part = part.reshape(lay.B, lay.S, lay.G, lay.head_tiles, lay.N
                                ).sum(3).astype(like.dtype)
        return part.reshape(like.shape)
    ds = heads_last(ds_col + ds_row.transpose(0, 1, 3, 2))
    return (dx.reshape(x.shape), heads_last(ddt_col).astype(dt.dtype),
            ds.astype(s.dtype), of_group(db, b), of_group(dc, c))


# -- the differentiable call --------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7))
def ssm_scan(x, dt, s, b, c, chunk: int, interpret: bool = False,
             head_tile: Optional[int] = None):
    """The scan of ``ssm_chunked`` on the kernels. x ``[B, S, H, P]``; dt
    ``[B, S, H]`` float32 after its softplus; s ``[B, S, H]`` float32, the
    running sums of ``dt a`` inside each chunk of ``chunk`` positions; b, c
    ``[B, S, G, N]``. Returns y ``[B, S, H, P]`` float32 (without the skip).
    Differentiable in all five. ``head_tile`` overrides
    :func:`ssm_head_tile` (tests, sweeps)."""
    return _forward(x, dt, s, b, c, chunk, interpret, head_tile,
                    save=False)[0]


def _scan_fwd(x, dt, s, b, c, chunk, interpret, head_tile):
    y, states = _forward(x, dt, s, b, c, chunk, interpret, head_tile,
                         save=True)
    return y, (x, dt, s, b, c, states)


def _scan_bwd(chunk, interpret, head_tile, res, dy):
    return _backward(*res, dy, chunk, interpret, head_tile)


ssm_scan.defvjp(_scan_fwd, _scan_bwd)
